"""Device temp bytes of the grad-step program that ran the most iterations
of the window, from its ``memory_analysis()``."""

GiB = 1 << 30


def read(ctx):
    if ctx.memory is None:
        return None
    return ctx.memory["temp_bytes"] / GiB

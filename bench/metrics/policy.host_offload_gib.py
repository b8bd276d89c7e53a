"""Bytes the grad step keeps in host memory (XLA's host offload), from
``memory_analysis()`` of the grad-step program that ran the most
iterations of the window."""

GiB = 1 << 30


def read(ctx):
    if ctx.memory is None:
        return None
    return ctx.memory["host_temp_bytes"] / GiB

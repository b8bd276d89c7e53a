"""Share of the traced window in which no op ran on the device, in percent
(``bench.trace``: 1 - union of the XLA op intervals over the window)."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace["idle_share"]

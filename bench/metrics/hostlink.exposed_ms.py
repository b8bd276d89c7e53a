"""Per window iteration, device-trace time of XLA's host-offload copies
during which no other op runs on the device (``bench.trace``).  A traced
window that offloads nothing reads 0: no transfer was exposed."""


def read(ctx):
    if ctx.trace is None:
        return None
    return ctx.trace["offload_exposed_s"] / ctx.window["iterations"] * 1e3

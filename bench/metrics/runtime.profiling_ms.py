"""Host time per window iteration in the runtime's Lightweight-mode
bookkeeping: ``record_dispatch`` and the steady part of ``end_iteration``
(stage machine, mirror copies, ledger), from the increase of the runtime's
own ``profiling_overhead_s`` over the window."""


def read(ctx):
    before, after = ctx.runtime_before[0], ctx.runtime_after[0]
    return (after - before) / ctx.window["iterations"] * 1e3

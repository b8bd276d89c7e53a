"""Host time per window iteration in the runtime's adaptation path
(re-prepare after a sequence change, GenPolicy variants, policy store,
ladder), from the increase of ``adaptation_overhead_s`` over the window."""


def read(ctx):
    before, after = ctx.runtime_before[1], ctx.runtime_after[1]
    return (after - before) / ctx.window["iterations"] * 1e3

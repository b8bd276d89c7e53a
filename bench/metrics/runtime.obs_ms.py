"""Host time per window iteration in the runtime's ``runtime.obs_close``
span: the always-on observability's own cost (overlap efficiency over the
span ring, the memory ledger's replay)."""

from bench.program_spans import ms_per_iteration


def read(ctx):
    return ms_per_iteration(ctx, lambda n: n == "runtime.obs_close")

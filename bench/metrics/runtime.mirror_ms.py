"""Host time per window iteration in the runtime's ``runtime.mirror``
span: the applied policy's swap schedule copied through the host-memory
engine.  0 where the applied policy has no release plan."""

from bench.program_spans import ms_per_iteration


def read(ctx):
    return ms_per_iteration(ctx, lambda n: n == "runtime.mirror")

"""95th percentile (nearest rank) of the wall time of the window's
iterations on the host clock: the slow iterations a policy move or a
sequence change costs.  A window holds some tens of iterations, so this is
one of its few slowest, and it swings with whether the runtime's
degradation ladder moves inside the window; hence a per-layer reading and
not an end-to-end metric with a bound."""
import math


def read(ctx):
    times = sorted(ctx.window["times"])
    return times[max(0, math.ceil(0.95 * len(times)) - 1)]

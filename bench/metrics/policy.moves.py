"""Changes of the applied policy in the window: the ``policy.install``
instants the runtime records under the window's iterations (ladder
moves, adaptation installs)."""

from bench.program_spans import window_records


def read(ctx):
    recs = window_records(ctx)
    if recs is None:
        return None
    return sum(1 for r in recs if r["name"] == "policy.install")

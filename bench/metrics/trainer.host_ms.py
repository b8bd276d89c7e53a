"""Host time per window iteration on the trainer's critical path: the
program's ``trainer.train`` spans less the ``trainer.*_wait`` spans under
them, in which the host blocks on the device (``bench.program_spans``)."""

from bench.program_spans import ROOT, seconds, window_records


def _is_wait(name):
    return name.startswith("trainer.") and name.endswith("_wait")


def read(ctx):
    recs = window_records(ctx)
    if recs is None:
        return None
    host = seconds(recs, lambda n: n == ROOT) - seconds(recs, _is_wait)
    return host / ctx.window["iterations"] * 1e3

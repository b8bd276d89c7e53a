"""The program's own span tree over the window, as its per-layer metrics
read it.

The program records its host phases in ``repro.obs``'s span ring: each
``Trainer.train`` call is a ``trainer.train`` root, stamped with the
iteration it starts, and every span and instant it causes hangs under it
by ``parent`` id.  The ring is still alive in the process when the
metrics are read.  A window's records are the roots stamped with its
iterations and their descendants: all opened after the first root, so
with ids above its id.  The window is whole while the ring has dropped no
record with such an id.
"""
from __future__ import annotations

import collections
from typing import Callable, List, Optional

ROOT = "trainer.train"


def window_records(ctx) -> Optional[List[dict]]:
    """The window's records; None where the program records no span tree
    (no root for some iteration of the window) or the ring has dropped
    part of the window."""
    from repro import obs
    tracer = obs.tracer()
    recs = tracer.records()
    first, n = ctx.window["first_step"], ctx.window["iterations"]
    roots = [r for r in recs if r["name"] == ROOT and r["kind"] == "span"
             and first <= r["iter"] < first + n]
    if len(roots) != n or len({r["iter"] for r in roots}) != n:
        return None
    if tracer.stats()["dropped_id"] >= min(r["id"] for r in roots):
        return None
    children = collections.defaultdict(list)
    for r in recs:
        children[r["parent"]].append(r)
    out, todo = [], list(roots)
    while todo:
        r = todo.pop()
        out.append(r)
        todo.extend(children[r["id"]])
    return out


def seconds(recs: List[dict], keep: Callable[[str], bool]) -> float:
    """Total time of the spans among ``recs`` whose name ``keep``
    accepts."""
    return sum(r["t1"] - r["t0"] for r in recs
               if r["kind"] == "span" and keep(r["name"]))


def ms_per_iteration(ctx, keep: Callable[[str], bool]) -> Optional[float]:
    """Total time of the window's spans whose name ``keep`` accepts, in
    ms per window iteration; 0 where there is none."""
    recs = window_records(ctx)
    if recs is None:
        return None
    return seconds(recs, keep) / ctx.window["iterations"] * 1e3

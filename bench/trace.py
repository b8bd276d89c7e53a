"""Reduction of a profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes.  Device planes are
``/device:TPU:<n>``.  On each, the ``XLA Ops`` line holds one event per
executed HLO instruction, named by its HLO text; control-flow containers
(``while``, ``conditional``, ``call``) span their bodies' ops and are left
out.  The ``Async XLA Ops`` line holds the in-flight part of asynchronous
ops; those that touch memory space ``S(5)`` (pinned host) are XLA's host
offload transfers.  The host spans are the ``bench.*`` TraceAnnotations
that ``bench.run`` opens around its calls into each layer, on the host
plane and on the same clock.

- busy: the union of the intervals of ops that compute on a device inside
  the window; an op that only waits for an asynchronous op to finish
  (``*-done``) does not count, so time stalled on a host transfer is idle.
  ``busy_s`` is averaged over the devices; the idle share is
  1 - busy / window;
- device ops: total time per instruction, waits included;
- idle gaps: each stretch of the window with no op computing, named by the
  innermost ``bench.*`` span open at its middle (``host.other`` where none
  is), summed per name;
- offload exposure: time in which a host offload transfer is in flight and
  no op computes.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, ASYNC_LINE = "XLA Ops", "Async XLA Ops"
SPAN_PREFIX = "bench."
CONTAINER = re.compile(r" (while|conditional|call)\(")
WAIT = re.compile(r" [a-z-]*-done\(")
HOST_MEMORY = "S(5)"
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")

Interval = Tuple[int, int]
Event = Tuple[float, float, str]


@dataclass
class Trace:
    """Per device, the ``XLA Ops`` and ``Async XLA Ops`` events; and the
    host spans.  Times in nanoseconds."""
    ops: Dict[str, List[Event]] = field(default_factory=dict)
    inflight: Dict[str, List[Event]] = field(default_factory=dict)
    spans: List[Event] = field(default_factory=list)


def label(text: str) -> str:
    """``<instruction> <opcode> <result shape>`` of an HLO text."""
    name, _, rest = text.partition(" = ")
    m = OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    return f"{name.lstrip('%')} {opcode} {rest.split(' ', 1)[0][:64]}".strip()


def find_xplane(directory: str) -> str:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under "
                                f"{directory}, found {paths}")
    return paths[0]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops, inflight = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.extend((ev.start_ns, ev.end_ns, ev.name)
                               for ev in line.events)
                elif line.name == ASYNC_LINE:
                    inflight.extend((ev.start_ns, ev.end_ns, ev.name)
                                    for ev in line.events)
            tr.ops[plane.name] = sorted(ops)
            tr.inflight[plane.name] = sorted(inflight)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tr.spans.extend((ev.start_ns, ev.end_ns, ev.name)
                                for ev in line.events
                                if ev.name.startswith(SPAN_PREFIX))
    tr.spans.sort()
    return tr


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def length(intervals: List[Interval]) -> int:
    return sum(e - s for s, e in intervals)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """Parts of the disjoint sorted intervals ``a`` not covered by the
    disjoint sorted intervals ``b``."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def window(tr: Trace, name: str = "bench.iteration") -> Interval:
    """From the start of the first ``name`` span to the end of the last."""
    its = [(s, e) for s, e, n in tr.spans if n == name]
    if not its:
        raise ValueError(f"no {name!r} span in the trace")
    return its[0][0], its[-1][1]


def _span_at(spans: List[Event], t: float) -> str:
    """Innermost (latest-starting) span open at ``t``."""
    best = None
    for s, e, n in spans:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, n)
    return best[1] if best else "host.other"


def _in(events: List[Event], lo: float, hi: float) -> List[Event]:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def reduce(tr: Trace, win: Optional[Interval] = None, top: int = 10) -> dict:
    """Busy, idle, the top ops, idle gaps by host span and offload
    exposure over ``win`` (default: the iterations' span)."""
    lo, hi = win or window(tr)
    if not tr.ops:
        raise ValueError("no device plane with XLA ops in the trace")
    busy_ns, exposed_ns, offload_ns = [], [], []
    per_op = collections.Counter()
    gaps = collections.Counter()
    for plane, events in tr.ops.items():
        ops = [ev for ev in _in(events, lo, hi) if not CONTAINER.search(ev[2])]
        for s, e, n in ops:
            per_op[label(n)] += e - s
        busy = union([(s, e) for s, e, n in ops if not WAIT.search(n)])
        busy_ns.append(length(busy))
        for s, e in subtract([(lo, hi)], busy):
            gaps[_span_at(tr.spans, (s + e) // 2)] += e - s
        off = union([(s, e) for s, e, n in _in(tr.inflight.get(plane, []),
                                               lo, hi) if HOST_MEMORY in n])
        offload_ns.append(length(off))
        exposed_ns.append(length(subtract(off, busy)))
    n_dev = len(tr.ops)
    window_ns = hi - lo
    busy_s = sum(busy_ns) / n_dev / 1e9
    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / (window_ns / 1e9),
        "device_ops": [[n, t / n_dev / 1e9]
                       for n, t in per_op.most_common(top)],
        "idle_gaps": [[n, t / n_dev / 1e9]
                      for n, t in gaps.most_common(top)],
        "offload_s": sum(offload_ns) / n_dev / 1e9,
        "offload_exposed_s": sum(exposed_ns) / n_dev / 1e9,
    }

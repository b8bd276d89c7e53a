"""repro.obs — always-on tracing, unified metrics, and the drift audit log.

Three pillars (ISSUE 6), all bounded-memory so they stay enabled in
production, matching the monitoring hot path's "cheap enough to leave
on" bar:

  * :class:`SpanTracer` — ring-buffered span tree over six fixed lanes
    (``compute``, ``policy_swap``, ``kv_spill``, ``checkpoint``,
    ``adapt``, ``host``), mirrored onto the JAX profiler's host plane and
    exported as Chrome trace-event JSON
    (:func:`export_chrome_trace`) and reduced to a per-iteration
    **overlap-efficiency** metric (:mod:`repro.obs.overlap`);
  * :class:`MetricsRegistry` — one counter/gauge/provider registry the
    scattered ``stats()`` dicts register into, with a JSONL snapshot
    writer;
  * :class:`AuditLog` — structured drift-decision events (classify /
    demote / apply / store-put / stage transitions);
  * :class:`MemoryLedger` — per-iteration realized HBM occupancy replay
    from observed swap/spill/checkpoint events: realized peak + top-k
    attribution, the predicted-vs-realized Simulator scoreboard,
    budget-headroom feedback for the health FSM, byte-conservation leak
    detection, and the :data:`LEDGER_TRACKS` Perfetto counter tracks.

Process-wide defaults are exposed through :func:`tracer`,
:func:`metrics`, :func:`audit`, and :func:`ledger` — subsystems record
into them without plumbing an object through every constructor, exactly
like a logging root logger.  Tests that need isolation swap them with
:func:`set_tracer` / :func:`set_audit` / :func:`set_metrics` /
:func:`set_ledger` (each returns the previous instance) or simply
``clear()`` the defaults.
"""
from __future__ import annotations

from repro.obs.audit import AuditLog
from repro.obs.memledger import LEDGER_TRACKS, MemoryLedger
from repro.obs.metrics import MetricsRegistry, SNAPSHOT_KEYS
from repro.obs.overlap import (interval_union, overlap_efficiency,
                               window_efficiency)
from repro.obs.tracer import (LANE_ADAPT, LANE_CHECKPOINT, LANE_COMPUTE,
                              LANE_HOST, LANE_ID, LANE_KV_SPILL,
                              LANE_POLICY_SWAP, LANES, TRANSFER_LANES,
                              SpanTracer,
                              chrome_trace_events, export_chrome_trace)
from repro.obs.validate import validate_chrome_trace, validate_metrics_jsonl

__all__ = [
    "AuditLog", "MetricsRegistry", "SpanTracer", "SNAPSHOT_KEYS",
    "MemoryLedger", "LEDGER_TRACKS",
    "LANES", "LANE_ID", "LANE_COMPUTE", "LANE_POLICY_SWAP", "LANE_KV_SPILL",
    "LANE_CHECKPOINT", "LANE_ADAPT", "LANE_HOST", "TRANSFER_LANES",
    "chrome_trace_events", "export_chrome_trace",
    "interval_union", "overlap_efficiency", "window_efficiency",
    "validate_chrome_trace", "validate_metrics_jsonl",
    "tracer", "metrics", "audit", "ledger",
    "set_tracer", "set_metrics", "set_audit", "set_ledger",
]

_tracer = SpanTracer()
_metrics = MetricsRegistry()
_audit = AuditLog()
_ledger = MemoryLedger()


def tracer() -> SpanTracer:
    """The process-wide default tracer (always on)."""
    return _tracer


def metrics() -> MetricsRegistry:
    """The process-wide default metrics registry."""
    return _metrics


def audit() -> AuditLog:
    """The process-wide default drift audit log."""
    return _audit


def ledger() -> MemoryLedger:
    """The process-wide default memory ledger (always on)."""
    return _ledger


def set_tracer(t: SpanTracer) -> SpanTracer:
    global _tracer
    old, _tracer = _tracer, t
    return old


def set_metrics(m: MetricsRegistry) -> MetricsRegistry:
    global _metrics
    old, _metrics = _metrics, m
    return old


def set_audit(a: AuditLog) -> AuditLog:
    global _audit
    old, _audit = _audit, a
    return old


def set_ledger(l: MemoryLedger) -> MemoryLedger:
    global _ledger
    old, _ledger = _ledger, l
    return old

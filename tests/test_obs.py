"""repro.obs tests (ISSUE 6).

Four families:

  * **boundedness guards** — the always-on bar, enforced the way the
    monitoring hot path enforces its own (deterministic counters first,
    a generous wall-clock ceiling second): the span ring never grows
    past capacity, name interning caps at ``max_names`` with an
    ``<other>`` overflow bucket, the audit deque and gauge series stay
    bounded;
  * **overlap math** — interval-union and overlap-efficiency identities
    on hand-computed cases, plus window clipping semantics;
  * **export schema** — a populated tracer round-trips through
    :func:`export_chrome_trace` and passes the same
    :func:`validate_chrome_trace` the nightly workflow runs;
  * **crash-proofing** — ``hostmem.metrics.format_summary`` formats
    partial/cold snapshots instead of raising (it runs in CLI
    ``finally`` blocks).
"""
import json
import time

import numpy as np
import pytest

from repro import obs
from repro.hostmem import metrics as hm_metrics
from repro.obs import (AuditLog, MetricsRegistry, SNAPSHOT_KEYS, SpanTracer,
                       interval_union, overlap_efficiency, window_efficiency)
from repro.obs.validate import validate_chrome_trace, validate_metrics_jsonl


@pytest.fixture()
def fresh_obs():
    """Isolated process-global obs state; restores the originals after."""
    old_t = obs.set_tracer(SpanTracer(capacity=1 << 10, max_names=64))
    old_m = obs.set_metrics(MetricsRegistry(series_len=32))
    old_a = obs.set_audit(AuditLog(capacity=256))
    try:
        yield obs.tracer(), obs.metrics(), obs.audit()
    finally:
        obs.set_tracer(old_t)
        obs.set_metrics(old_m)
        obs.set_audit(old_a)


# ------------------------------------------------------------- span tracer
def test_tracer_ring_is_bounded():
    tr = SpanTracer(capacity=64)
    buf_ids = (id(tr._t0), id(tr._t1), id(tr._lane))
    for i in range(64 * 2 + 5):
        tr.record(obs.LANE_COMPUTE, "step", float(i), float(i) + 0.5, arg=i)
    s = tr.stats()
    assert s["n_spans"] == 133
    assert s["retained"] == 64
    assert s["dropped"] == 69
    assert (id(tr._t0), id(tr._t1), id(tr._lane)) == buf_ids  # no realloc
    assert len(tr._arg) == 64
    # retained records are the newest, in recording order
    recs = tr.records()
    assert len(recs) == 64
    assert recs[0]["arg"] == 69 and recs[-1]["arg"] == 132


def test_tracer_name_interning_caps():
    tr = SpanTracer(capacity=256, max_names=8)
    for i in range(50):
        tr.record(obs.LANE_ADAPT, f"dyn-{i}", 0.0, 1.0)
    assert tr.stats()["names"] <= 9          # 8 real + "<other>"
    names = {r["name"] for r in tr.records()}
    assert "<other>" in names
    assert "dyn-0" in names                  # early names kept verbatim


def test_tracer_span_records_on_exception():
    tr = SpanTracer(capacity=64)
    with pytest.raises(RuntimeError):
        with tr.span(obs.LANE_CHECKPOINT, "boom"):
            raise RuntimeError("x")
    recs = tr.records()
    assert len(recs) == 1 and recs[0]["name"] == "boom"
    assert recs[0]["t1"] >= recs[0]["t0"]


def test_tracer_filters_by_lane_and_iteration():
    tr = SpanTracer(capacity=64)
    tr.set_iteration(3)
    tr.record(obs.LANE_COMPUTE, "c", 0.0, 1.0)
    tr.record(obs.LANE_KV_SPILL, "k", 1.0, 2.0)
    tr.set_iteration(4)
    tr.record(obs.LANE_COMPUTE, "c", 2.0, 3.0)
    tr.instant(obs.LANE_ADAPT, "marker", t=2.5)
    assert len(tr.spans(lanes=(obs.LANE_COMPUTE,))) == 2
    assert len(tr.spans(lanes=(obs.LANE_COMPUTE,), it=4)) == 1
    assert len(tr.spans(lanes=(obs.LANE_KV_SPILL,), it=3)) == 1
    # instants are excluded from the span view by default
    assert len(tr.spans(lanes=(obs.LANE_ADAPT,))) == 0
    tr.clear()
    assert tr.stats()["n_spans"] == 0 and tr.spans().size == 0


def test_tracer_disabled_records_nothing():
    tr = SpanTracer(capacity=64)
    tr.enabled = False
    tr.record(obs.LANE_COMPUTE, "c", 0.0, 1.0)
    tr.instant(obs.LANE_COMPUTE, "i")
    assert tr.stats()["n_spans"] == 0


def test_tracer_records_id_and_parent():
    tr = SpanTracer(capacity=64)
    tr.set_iteration(5)
    with tr.span(obs.LANE_HOST, "root"):
        tr.set_iteration(6)              # the stamp is taken at open
        with tr.span(obs.LANE_HOST, "child"):
            tr.record(obs.LANE_POLICY_SWAP, "copy", 0.0, 1.0)
            tr.instant(obs.LANE_ADAPT, "mark")
        tr.instant(obs.LANE_ADAPT, "after-child")
    tr.record(obs.LANE_COMPUTE, "loose", 2.0, 3.0)
    by = {r["name"]: r for r in tr.records()}
    root, child = by["root"], by["child"]
    assert root["parent"] == 0 and root["iter"] == 5
    assert child["parent"] == root["id"] and child["iter"] == 6
    assert by["copy"]["parent"] == by["mark"]["parent"] == child["id"]
    assert by["after-child"]["parent"] == root["id"]
    assert by["loose"]["parent"] == 0
    assert len({r["id"] for r in by.values()}) == len(by)
    assert root["t0"] <= child["t0"] <= child["t1"] <= root["t1"]


def test_tracer_nesting_is_per_thread():
    """A span opened on another thread while this one has a span open
    does not become its child; each thread keeps its own stack."""
    import threading
    tr = SpanTracer(capacity=256)
    opened, release = threading.Event(), threading.Event()

    def worker():
        with tr.span(obs.LANE_CHECKPOINT, "w.outer"):
            opened.set()
            release.wait(10)
            with tr.span(obs.LANE_CHECKPOINT, "w.inner"):
                tr.record(obs.LANE_CHECKPOINT, "w.copy", 0.0, 1.0)

    with tr.span(obs.LANE_HOST, "main.outer"):
        t = threading.Thread(target=worker)
        t.start()
        assert opened.wait(10)
        with tr.span(obs.LANE_HOST, "main.inner"):
            release.set()
            t.join(10)
    assert not t.is_alive()
    by = {r["name"]: r for r in tr.records()}
    assert by["w.outer"]["parent"] == by["main.outer"]["parent"] == 0
    assert by["w.inner"]["parent"] == by["w.outer"]["id"]
    assert by["w.copy"]["parent"] == by["w.inner"]["id"]
    assert by["main.inner"]["parent"] == by["main.outer"]["id"]


def test_tracer_tracks_the_largest_dropped_id():
    tr = SpanTracer(capacity=16)
    for i in range(16):
        tr.record(obs.LANE_COMPUTE, "s", 0.0, 1.0)
    assert tr.stats()["dropped_id"] == 0
    for i in range(3):
        tr.record(obs.LANE_COMPUTE, "s", 0.0, 1.0)
    ids = [r["id"] for r in tr.records()]
    assert tr.stats()["dropped_id"] == 3 and min(ids) == 4


def test_tracer_span_lands_on_the_profilers_host_plane(tmp_path):
    """``span()`` writes a TraceAnnotation: a profile taken around it
    carries the span's name on a ``/host:`` plane."""
    import glob
    import jax
    from jax.profiler import ProfileData
    tr = SpanTracer(capacity=64)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span(obs.LANE_HOST, "unit.profiled_span"):
            jax.numpy.ones(3).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    paths = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    planes = ProfileData.from_file(paths[0]).planes
    host = [ev.name for p in planes if p.name.startswith("/host:")
            for line in p.lines for ev in line.events]
    assert "unit.profiled_span" in host
    assert tr.records()[0]["name"] == "unit.profiled_span"


def test_tracer_record_wall_clock_budget():
    """Generous always-on ceiling: recording must stay in the microsecond
    range (CI-tolerant bound — the deterministic boundedness guards above
    are the primary enforcement)."""
    tr = SpanTracer(capacity=1 << 12)
    n = 10_000
    t0 = time.perf_counter()
    for i in range(n):
        tr.record(obs.LANE_COMPUTE, "hot", 0.0, 1.0, arg=i)
    per_span = (time.perf_counter() - t0) / n
    assert per_span < 50e-6, f"record cost {per_span * 1e6:.1f}us/span"


# ------------------------------------------------------------ overlap math
def test_interval_union_merges_and_sorts():
    spans = np.array([[5.0, 7.0], [0.0, 2.0], [1.0, 3.0], [3.0, 4.0],
                      [6.0, 6.5]])
    u = interval_union(spans)
    # [0,2]+[1,3] merge; [3,4] touches 3 -> merges too; [5,7] absorbs [6,6.5]
    assert u.tolist() == [[0.0, 4.0], [5.0, 7.0]]
    assert interval_union(np.empty((0, 2))).shape == (0, 2)


def test_overlap_efficiency_hand_case():
    compute = np.array([[0.0, 10.0]])
    transfer = np.array([[2.0, 4.0], [8.0, 12.0]])
    eff, total, hidden = overlap_efficiency(compute, transfer)
    assert total == pytest.approx(6.0)
    assert hidden == pytest.approx(4.0)      # [2,4] fully + [8,10] of [8,12]
    assert eff == pytest.approx(4.0 / 6.0)


def test_overlap_efficiency_none_without_transfer():
    eff, total, hidden = overlap_efficiency(np.array([[0.0, 1.0]]),
                                            np.empty((0, 2)))
    assert eff is None and total == 0.0 and hidden == 0.0


def test_overlap_efficiency_zero_without_compute():
    eff, total, hidden = overlap_efficiency(np.empty((0, 2)),
                                            np.array([[0.0, 2.0]]))
    assert eff == 0.0 and total == 2.0 and hidden == 0.0


def test_window_efficiency_clips_to_window():
    tr = SpanTracer(capacity=64)
    # compute crosses the window start; transfer extends past the end
    tr.record(obs.LANE_COMPUTE, "c", 0.0, 6.0)
    tr.record(obs.LANE_POLICY_SWAP, "t", 4.0, 12.0)
    eff, total, hidden = window_efficiency(tr, 5.0, 10.0)
    assert total == pytest.approx(5.0)       # transfer clipped to [5,10]
    assert hidden == pytest.approx(1.0)      # compute covers [5,6] of it
    assert eff == pytest.approx(0.2)
    # transfer entirely outside the window -> no traffic -> None
    eff2, total2, _ = window_efficiency(tr, 20.0, 30.0)
    assert eff2 is None and total2 == 0.0


# ---------------------------------------------------------------- audit log
def test_audit_log_bounded_and_counted():
    log = AuditLog(capacity=8)
    for i in range(20):
        log.event("drift.classify", tier="reuse", i=i)
    log.event("policy.apply", policy_kind="baseline")
    s = log.stats()
    assert s["n_events"] == 21 and s["retained"] == 8
    assert log.counts() == {"drift.classify": 7, "policy.apply": 1}
    tail = log.tail(3, kind="drift.classify")
    assert [e["i"] for e in tail] == [17, 18, 19]
    assert all(e["seq"] for e in tail)


def test_audit_log_file_attach(tmp_path):
    p = str(tmp_path / "audit.jsonl")
    log = AuditLog(capacity=8, path=p)
    log.event("stage.transition", to="GenPolicy", step=3)
    log.event("drift.demote", why="match-miss")
    log.detach_file()
    lines = [json.loads(l) for l in open(p) if l.strip()]
    assert [e["kind"] for e in lines] == ["stage.transition", "drift.demote"]
    assert lines[0]["to"] == "GenPolicy"


# ---------------------------------------------------------- metrics registry
def test_metrics_counters_and_gauge_series():
    reg = MetricsRegistry(series_len=4)
    assert reg.counter("iters") == 1
    assert reg.counter("iters", 5) == 6
    for i in range(10):
        reg.gauge("eff", i / 10, t=float(i))
    snap = reg.snapshot()
    assert tuple(snap.keys()) == SNAPSHOT_KEYS
    assert snap["counters"]["iters"] == 6
    assert snap["gauges"]["eff"] == pytest.approx(0.9)
    assert len(snap["series"]["eff"]) == 4   # bounded by series_len
    assert snap["series"]["eff"][-1] == [9.0, 0.9] \
        or snap["series"]["eff"][-1] == (9.0, 0.9)


def test_metrics_provider_errors_are_contained():
    reg = MetricsRegistry()
    reg.register_provider("ok", lambda: {"x": np.int64(3)})
    reg.register_provider("bad", lambda: 1 / 0)
    snap = reg.snapshot()
    assert snap["providers"]["ok"] == {"x": 3}   # numpy made JSON-safe
    assert "error" in snap["providers"]["bad"]
    reg.register_provider("ok", lambda: {"x": 4})   # replace semantics
    assert reg.snapshot()["providers"]["ok"] == {"x": 4}
    reg.unregister_provider("bad")
    assert reg.provider_names() == ["ok"]


def test_metrics_jsonl_roundtrip(tmp_path):
    p = str(tmp_path / "m.jsonl")
    reg = MetricsRegistry()
    reg.counter("c")
    reg.gauge("g", 1.5)
    reg.write_jsonl(p)
    reg.write_jsonl(p)
    assert validate_metrics_jsonl(p) == {"snapshots": 2, "gauges": ["g"],
                                         "providers": []}
    assert validate_metrics_jsonl(p, require_gauges=("g",))["snapshots"] == 2
    with pytest.raises(ValueError, match="missing gauge"):
        validate_metrics_jsonl(p, require_gauges=("absent",))


# ------------------------------------------------------------ chrome export
def test_chrome_export_roundtrips_through_validator(tmp_path):
    tr = SpanTracer(capacity=256)
    tr.set_iteration(1)
    base = time.perf_counter()
    for i, lane in enumerate(obs.LANES):
        tr.record(lane, f"{lane}-work", base + i, base + i + 0.25,
                  arg=("tag", 123))
    tr.instant(obs.LANE_ADAPT, "stage:Stable", t=base + 9.0, arg=(7, "why"))
    p = str(tmp_path / "out.trace.json")
    obs.export_chrome_trace(
        p, tr,
        counters={"overlap_efficiency": [(base + 1.0, 0.5),
                                         (base + 2.0, 0.75)]},
        meta={"run": "unit"})
    obj = json.load(open(p))
    summary = validate_chrome_trace(obj, require_lanes=obs.LANES,
                                    require_counter="overlap_efficiency")
    assert summary["n_spans"] == len(obs.LANES)
    assert summary["n_instants"] == 1
    assert summary["counters"]["overlap_efficiency"] == 2
    assert obj["otherData"]["run"] == "unit"
    # every ts is normalized (non-negative) and spans carry their iter
    xs = [e for e in obj["traceEvents"] if e["ph"] == "X"]
    assert all(e["ts"] >= 0 for e in xs)
    assert all(e["args"]["iter"] == 1 for e in xs)
    assert xs[0]["args"]["detail"] == ["tag", 123]
    # the span tree survives the export
    assert [e["args"]["id"] for e in xs] == list(range(1, len(xs) + 1))
    assert all(e["args"]["parent"] == 0 for e in xs)


def test_validator_rejects_missing_lane():
    tr = SpanTracer(capacity=64)
    tr.record(obs.LANE_COMPUTE, "c", 0.0, 1.0)
    obj = {"traceEvents": obs.chrome_trace_events(tr)}
    with pytest.raises(ValueError, match="kv_spill"):
        validate_chrome_trace(obj, require_lanes=("compute", "kv_spill"))


# ------------------------------------------------------ global default swap
def test_global_defaults_swap_and_restore(fresh_obs):
    tr, reg, log = fresh_obs
    with obs.tracer().span(obs.LANE_COMPUTE, "x"):
        pass
    obs.metrics().counter("n")
    obs.audit().event("policy.apply")
    assert tr.stats()["n_spans"] == 1
    assert reg.snapshot()["counters"]["n"] == 1
    assert log.counts() == {"policy.apply": 1}


# ---------------------------------------------- format_summary crash-proofing
def test_format_summary_tolerates_cold_and_partial_stats():
    # entirely empty snapshot (engine never constructed)
    out = hm_metrics.format_summary({})
    assert "pool:" in out and "engine:" in out and "bwmodel:" in out
    # engine with no classes; bwmodel calibrated but zero points (the
    # regression: '%d points' used to assume points > 0 implied by the
    # calibrated flag)
    out = hm_metrics.format_summary({
        "pool": {"bytes_in_use": 0},
        "engine": {"n_out": 0, "classes": {}},
        "bwmodel": {"calibrated": True, "points": 0, "constant_gbps": 32.0},
    })
    assert "constant 32.0" in out
    # queued backlog renders depth + MiB
    out = hm_metrics.format_summary({
        "engine": {"classes": {"kv_spill": {
            "n_out": 2, "queued_bytes": 2 << 20, "queue_depth": 3}}},
    })
    assert "queued 3 (2.0 MiB)" in out


def test_format_summary_real_cold_tier():
    from repro.hostmem import HostMemTier
    tier = HostMemTier()
    out = hm_metrics.format_summary(hm_metrics.collect(tier))
    assert "pool:" in out and "bwmodel:" in out

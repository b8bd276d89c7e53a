"""repro.faults: the fault-injection harness and the health-driven
degradation ladder (ISSUE 8).

Families:

  * **plan** — seeded schedules are deterministic and replayable, the
    disarmed hook is a no-op, windows/max_fires bound firing, JSON
    round-trips;
  * **engine recovery** — bounded retry with backoff recovers transient
    faults bit-exactly; terminal swap-out failure retains the block in
    HBM (later swap-in short-circuits, still bit-exact); terminal
    swap-in failure falls back to a synchronous host copy; a dropped DMA
    never loses data (the staging check fires while the source is still
    held); with resilience disabled the legacy raise survives;
  * **properties** — per-class FIFO completion order is preserved under
    random fault schedules, and no slab is ever double-released
    (hypothesis, pool invariants checked);
  * **health / ladder** — score thresholds drive healthy→degraded→failed
    and recovery needs a clean streak; the ladder descends one rung per
    hold window, trims before it abandons, probes only at reduced rungs;
  * **hardening satellites** — policy store survives corrupt records,
    mid-put crashes and a truncated lsh.index; checkpoint restore names
    the corrupt shard and falls back to the previous step; the adapt
    worker's crash/hang faults exercise the conservative fallback and
    the watchdog;
  * **integration** — a reduced-llama2 trainer under a seeded engine
    fault window never crashes, descends the ladder, and recovers, with
    the whole chain visible in the audit log.
"""
import glob
import json
import os
import shutil
import tempfile
import time
from types import SimpleNamespace

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import faults, obs
from repro.common.config import ResilienceConfig
from repro.faults import (DEGRADED, FAILED, HEALTHY, RUNG_CONSERVATIVE,
                          RUNG_FULL, RUNG_NO_SWAP, RUNG_TRIMMED,
                          DegradationLadder, Fault, FaultPlan, FaultSpec,
                          HealthMonitor, trim_swap)
from repro.hostmem import (TC_CHECKPOINT, TC_KV_SPILL, TC_POLICY_SWAP,
                           HostMemError, PinnedSlabPool, TransferEngine)


@pytest.fixture
def tmpdir():
    d = tempfile.mkdtemp()
    yield d
    shutil.rmtree(d, ignore_errors=True)


@pytest.fixture(autouse=True)
def _always_disarmed():
    """No test leaks an armed plan into the rest of the suite."""
    faults.disarm()
    yield
    faults.disarm()


def _engine(**rs_kw):
    rs = ResilienceConfig(retry_backoff_s=0.0, **rs_kw)
    return TransferEngine(PinnedSlabPool(), resilience=rs)


def _roundtrip(eng, arr, tag="t"):
    ev = eng.wait(eng.submit_swap_out(arr, tag))
    return eng.wait(eng.submit_swap_in(ev, tag))


# ------------------------------------------------------------------- plan
def test_plan_is_deterministic_in_seed():
    def fires(seed):
        plan = FaultPlan([FaultSpec("engine.transfer_error", prob=0.3)],
                         seed=seed)
        out = []
        for it in range(20):
            plan.set_iteration(it)
            out.append([plan.fire("engine.transfer_error", key="k")
                        is not None for _ in range(5)])
        return out

    assert fires(7) == fires(7)
    assert fires(7) != fires(8)         # astronomically unlikely to collide


def test_plan_window_and_max_fires():
    plan = FaultPlan([FaultSpec("pool.alloc", prob=1.0, start=3, stop=6,
                                max_fires=2)])
    hits = []
    for it in range(10):
        plan.set_iteration(it)
        if plan.fire("pool.alloc") is not None:
            hits.append(it)
    assert hits == [3, 4]               # window opens at 3, capped at 2


def test_unknown_site_rejected():
    with pytest.raises(ValueError, match="unknown fault site"):
        FaultSpec("engine.nonexistent")


def test_plan_json_roundtrip():
    plan = FaultPlan.everywhere(seed=42, prob=0.1, seconds=0.5, stop=100)
    clone = FaultPlan.from_json(json.loads(json.dumps(plan.to_json())))
    assert clone.seed == plan.seed
    assert [s.to_json() for s in clone.specs] == \
           [s.to_json() for s in plan.specs]


def test_disarmed_inject_is_noop():
    assert not faults.armed()
    assert faults.inject("engine.transfer_error", key="x") is None
    faults.tick(5)                      # no plan: silently ignored


def test_arm_disarm_and_audit_trail():
    plan = FaultPlan([FaultSpec("store.load", prob=1.0)], seed=3)
    with faults.injected(plan):
        assert faults.active() is plan
        assert faults.inject("store.load", key="rec") is not None
    assert faults.active() is None
    kinds = [e["kind"] for e in obs.audit().tail(50)]
    assert "fault.armed" in kinds and "fault.injected" in kinds \
        and "fault.disarmed" in kinds


# -------------------------------------------------------- engine recovery
def test_retry_recovers_transient_fault_bit_exactly():
    eng = _engine()
    arr = np.random.RandomState(0).randn(257).astype(np.float32)
    plan = FaultPlan([FaultSpec("engine.transfer_error", prob=1.0,
                                max_fires=2)])
    with faults.injected(plan):
        ev2 = _roundtrip(eng, arr)
    np.testing.assert_array_equal(np.asarray(ev2.result), arr)
    assert not ev2.failed
    assert eng.n_retries == 2 and eng.n_failed_out == 0
    assert eng.pool.live_blocks == 0
    eng.pool.check()


def test_terminal_swap_out_retains_in_hbm_and_short_circuits():
    eng = _engine(max_retries=1)
    arr = np.random.RandomState(1).randn(100).astype(np.float32)
    plan = FaultPlan([FaultSpec("engine.transfer_error", prob=1.0)])
    with faults.injected(plan):
        ev = eng.wait(eng.submit_swap_out(arr, "t"))
        assert ev.failed and ev.block is None
        assert ev.result is arr          # the retained device reference
        # swap-in of a failed staging short-circuits: no link traffic,
        # the retained array comes back as-is — bit-exact by identity
        ev2 = eng.wait(eng.submit_swap_in(ev, "t"))
    assert ev2.done and ev2.failed is False
    np.testing.assert_array_equal(np.asarray(ev2.result), arr)
    assert eng.n_failed_out == 1 and eng.n_hbm_fallback_in == 1
    assert eng.pool.live_blocks == 0     # the slab was released exactly once
    eng.pool.check()
    # one retry (0.5) + one terminal error (1.0): scored but not yet
    # degraded — a single bad transfer must not flap the ladder
    assert eng.health.links[TC_POLICY_SWAP].score >= 1.0
    assert eng.health.state(TC_POLICY_SWAP) == HEALTHY


def test_terminal_swap_in_falls_back_to_sync_copy():
    eng = _engine(max_retries=1)
    arr = np.random.RandomState(2).randn(64).astype(np.float32)
    ev = eng.wait(eng.submit_swap_out(arr, "t"))
    assert not ev.failed
    plan = FaultPlan([FaultSpec("engine.transfer_drop", prob=1.0)])
    with faults.injected(plan):
        ev2 = eng.wait(eng.submit_swap_in(ev, "t"))
    # the async device-put path kept failing; the staged bytes were
    # recovered by a synchronous host-side read instead
    np.testing.assert_array_equal(np.asarray(ev2.result), arr)
    assert eng.n_sync_fallback_in == 1
    assert eng.pool.live_blocks == 0
    eng.pool.check()


def test_dropped_dma_never_loses_data():
    """A swap-out whose copy silently does nothing must be caught while
    the source reference is still held — retry, don't lose the tensor."""
    eng = _engine()
    arr = np.random.RandomState(3).randn(333).astype(np.float32)
    plan = FaultPlan([FaultSpec("engine.transfer_drop", prob=1.0,
                                max_fires=1)])
    with faults.injected(plan):
        ev2 = _roundtrip(eng, arr)
    np.testing.assert_array_equal(np.asarray(ev2.result), arr)
    assert eng.n_retries >= 1


def test_stall_fault_delays_but_completes():
    eng = _engine()
    arr = np.zeros(64, np.float32)
    plan = FaultPlan([FaultSpec("engine.transfer_stall", prob=1.0,
                                seconds=0.05, max_fires=1)])
    with faults.injected(plan):
        t0 = time.perf_counter()
        ev2 = _roundtrip(eng, arr)
        dt = time.perf_counter() - t0
    assert dt >= 0.05 and not ev2.failed


def test_pool_faults_are_absorbed_by_engine_retry():
    eng = _engine()
    arr = np.random.RandomState(4).randn(50).astype(np.float32)
    plan = FaultPlan([FaultSpec("pool.alloc", prob=1.0, max_fires=1)])
    with faults.injected(plan):
        ev2 = _roundtrip(eng, arr)
    np.testing.assert_array_equal(np.asarray(ev2.result), arr)
    assert eng.n_retries == 1


def test_resilience_disabled_preserves_legacy_raise():
    eng = TransferEngine(PinnedSlabPool(),
                         resilience=ResilienceConfig(enabled=False))
    plan = FaultPlan([FaultSpec("engine.transfer_error", prob=1.0)])
    with faults.injected(plan):
        with pytest.raises(Exception):
            eng.wait(eng.submit_swap_out(np.zeros(8, np.float32), "t"))


def test_pool_pressure_spares_recycled_slabs():
    pool = PinnedSlabPool()
    blk = pool.alloc(1000, "warm")
    pool.free(blk)
    plan = FaultPlan([FaultSpec("pool.pressure", prob=1.0)])
    with faults.injected(plan):
        # same class: served from the free list, pressure fault untouched
        ok = pool.alloc(900, "recycled")
        # fresh class: the host allocator is the one under pressure
        with pytest.raises(HostMemError, match="pressure"):
            pool.alloc(1 << 20, "fresh")
    pool.free(ok)
    pool.check()


# --------------------------------------------------------------- properties
@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 32 - 1), st.floats(0.0, 0.6))
def test_per_class_fifo_order_survives_faults(seed, prob):
    """Within a (class, direction) stream, completion order must equal
    submission order no matter which copies fault and retry: retries
    happen inside the executing slot, never by re-queueing."""
    faults.disarm()
    eng = _engine()
    done: dict = {c: [] for c in (TC_POLICY_SWAP, TC_KV_SPILL,
                                  TC_CHECKPOINT)}
    plan = FaultPlan([FaultSpec("engine.transfer_error", prob=prob),
                      FaultSpec("engine.transfer_drop", prob=prob / 2)],
                     seed=seed)
    rng = np.random.RandomState(seed % (2 ** 31))
    with faults.injected(plan):
        evs = []
        for i in range(18):
            cls = (TC_POLICY_SWAP, TC_KV_SPILL,
                   TC_CHECKPOINT)[int(rng.randint(3))]
            ev = eng.submit_swap_out(np.full(8 + i, i, np.float32),
                                     f"s{i}", cls=cls)
            ev.on_done(lambda e, c=cls: done[c].append(e.eid))
            evs.append(ev)
        eng.synchronize()
    for c, order in done.items():
        assert order == sorted(order), (c, order)
    # every payload either staged faithfully or was retained in HBM
    for i, ev in enumerate(evs):
        src = np.full(8 + i, i, np.float32)
        got = (np.asarray(ev.result) if ev.failed
               else ev.block.read())
        np.testing.assert_array_equal(got, src)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 2 ** 32 - 1))
def test_no_double_release_under_chaos(seed):
    """Whatever faults fire, every slab is released exactly once: live
    blocks drain to zero and the pool's byte accounting stays exact."""
    faults.disarm()
    eng = _engine(max_retries=1)
    plan = FaultPlan.everywhere(seed=seed, prob=0.25)
    with faults.injected(plan):
        outs = [eng.submit_swap_out(np.full(16, i, np.float32), f"o{i}")
                for i in range(12)]
        for ev in outs:
            eng.wait(ev)
            eng.wait(eng.submit_swap_in(ev, ev.tag))
    assert eng.pool.live_blocks == 0
    eng.pool.check()


# ----------------------------------------------------------------- health
def test_health_degrades_fails_and_recovers():
    h = HealthMonitor(["link"], degrade_score=2.0, fail_score=4.0,
                      recover_successes=3, decay=0.5)
    assert h.worst() == HEALTHY
    h.note_error("link")
    h.note_error("link")                 # score 2.0 -> degraded
    assert h.state("link") == DEGRADED
    h.note_error("link")
    h.note_error("link")                 # score 4.0 -> failed
    assert h.state("link") == FAILED
    for _ in range(10):
        h.note_success("link")
    assert h.state("link") == HEALTHY
    assert h.links["link"].n_transitions >= 2


def test_health_retry_weighs_half_and_slow_quarter():
    h = HealthMonitor(["link"], degrade_score=2.0)
    for _ in range(3):
        h.note_retry("link")             # 1.5: still healthy
    assert h.state("link") == HEALTHY
    h.note_retry("link")                 # 2.0: degraded
    assert h.state("link") == DEGRADED
    h2 = HealthMonitor(["l2"], degrade_score=2.0, residual_limit=8.0)
    for _ in range(7):
        h2.note_success("l2", residual=50.0)   # 7 * 0.25 = 1.75
    assert h2.state("l2") == HEALTHY
    h2.note_success("l2", residual=50.0)
    assert h2.state("l2") == DEGRADED


def test_health_recovery_needs_clean_streak():
    h = HealthMonitor(["link"], degrade_score=2.0, recover_successes=4,
                      decay=0.1)
    h.note_error("link")
    h.note_error("link")
    assert h.state("link") == DEGRADED
    h.note_success("link")               # score decays fast but streak=1
    h.note_retry("link")                 # streak broken
    h.note_success("link")
    h.note_success("link")
    h.note_success("link")
    assert h.state("link") == DEGRADED   # streak only 3
    h.note_success("link")
    assert h.state("link") == HEALTHY


# ----------------------------------------------------------------- ladder
def test_ladder_descends_with_hold_and_recovers():
    lad = DegradationLadder(hold_iterations=2)
    assert lad.decide(FAILED, 10) == RUNG_TRIMMED
    assert lad.decide(FAILED, 11) is None        # hold window
    assert lad.decide(FAILED, 12) == RUNG_CONSERVATIVE
    assert lad.decide(FAILED, 14) == RUNG_NO_SWAP
    assert lad.decide(FAILED, 20) is None        # bottom rung holds
    assert lad.decide(HEALTHY, 22) == RUNG_CONSERVATIVE
    assert lad.decide(HEALTHY, 24) == RUNG_TRIMMED
    assert lad.decide(HEALTHY, 26) == RUNG_FULL
    assert lad.decide(HEALTHY, 30) is None       # already at full
    assert lad.n_descents == 3 and lad.n_ascents == 3


def test_ladder_degraded_goes_to_trimmed_only():
    lad = DegradationLadder(hold_iterations=0)
    assert lad.decide(DEGRADED, 1) == RUNG_TRIMMED
    assert lad.decide(DEGRADED, 5) is None       # never deeper on degraded


def test_ladder_reset_and_probe_throttle():
    lad = DegradationLadder(hold_iterations=0, probe_interval=4)
    assert not lad.should_probe(0)               # full rung: no probes
    lad.decide(FAILED, 1)
    assert lad.should_probe(2)
    assert not lad.should_probe(3)               # throttled
    assert lad.should_probe(6)
    lad.reset(7)
    assert lad.rung == RUNG_FULL
    assert any(t["why"] == "new-policy" for t in lad.transitions)


def test_ladder_move_records_a_policy_install():
    """A rung the ladder moves to is a new applied policy: one
    ``policy.install`` instant (arg ``("ladder", True)``: the trimmed rung
    still offloads, so its stack is pipelined) under ``runtime.ladder``,
    counted in ``policy_installs`` and ``offload_pipelined_installs``."""
    from repro.common.config import ChameleonConfig
    from repro.core.executor import AppliedPolicy
    from repro.core.runtime import ChameleonRuntime
    old_t = obs.set_tracer(obs.SpanTracer(capacity=256))
    old_m = obs.set_metrics(obs.MetricsRegistry())
    try:
        rt = ChameleonRuntime(ChameleonConfig(), lambda pol: (lambda *a: a))
        full = AppliedPolicy(None, {"ffn_act"}, set(), set(), "off=ffn_act")
        rt.applied = rt._full_applied = full
        health = rt.hostmem.engine.health
        while health.worst() != FAILED:
            health.note_error(TC_POLICY_SWAP)
        rt.end_iteration(0.01)
        assert rt.ladder.rung == RUNG_TRIMMED
        assert rt.applied.fingerprint != full.fingerprint
        by = {r["id"]: r for r in obs.tracer().records()}
        installs = [r for r in by.values() if r["name"] == "policy.install"]
        assert [(r["lane"], r["kind"], r["arg"]) for r in installs] == [
            (obs.LANE_ADAPT, "instant", ("ladder", True))]
        ladder = by[installs[0]["parent"]]
        assert ladder["name"] == "runtime.ladder"
        assert by[ladder["parent"]]["name"] == "runtime.end_iteration"
        counters = obs.metrics().snapshot()["counters"]
        assert counters["policy_installs"] == 1
        assert counters["offload_pipelined_installs"] == 1
        rt.end_iteration(0.01)               # hold: no move, no install
        assert obs.metrics().snapshot()["counters"]["policy_installs"] == 1
    finally:
        obs.set_tracer(old_t)
        obs.set_metrics(old_m)


def test_trim_swap_drops_lowest_scores_within_budget(monkeypatch):
    entries = [SimpleNamespace(uid=i, score=float(i), nbytes=10)
               for i in range(10)]
    swap = SimpleNamespace(entries=entries)
    # dropping an entry raises the peak by its footprint: monotone in the
    # number dropped, exactly what the binary search assumes
    import repro.core.policy as P
    monkeypatch.setattr(
        P, "projected_peak",
        lambda prof, kept: 100 + (len(entries) - len(kept)) * 10)
    kept = trim_swap(None, swap, budget=130, max_drop_fraction=0.5)
    assert len(kept) == 7                        # 3 dropped: peak 130
    assert [e.uid for e in kept] == [3, 4, 5, 6, 7, 8, 9]  # lowest cut
    # budget below any drop: nothing to trim
    assert trim_swap(None, swap, budget=100, max_drop_fraction=0.5) is None
    # cap respected even with infinite headroom
    kept = trim_swap(None, swap, budget=10 ** 9, max_drop_fraction=0.3)
    assert len(kept) == 7


# -------------------------------------------- policy store hardening (S2)
def _mini_store(d, n=3):
    from repro.common.config import PolicyStoreConfig
    from repro.policystore import PolicyRecord, PolicyStore, \
        fingerprint_tokens
    store = PolicyStore(PolicyStoreConfig(dir=d))
    for i in range(n):
        fp = fingerprint_tokens(np.arange(100) % (i + 5) + 1)
        store.put(PolicyRecord.from_policy(
            fingerprint=fp, prepare_fingerprint=fp, swap=None,
            candidates=[], n_ops=100, knob=1.0, measured_t=0.1,
            budget=1 << 20, policy_kind="conservative"))
    return store


def test_store_injected_corrupt_record_skipped_on_load(tmpdir):
    _mini_store(tmpdir, n=3)
    from repro.common.config import PolicyStoreConfig
    from repro.policystore import PolicyStore
    plan = FaultPlan([FaultSpec("store.load", prob=1.0, max_fires=1)])
    with faults.injected(plan):
        store = PolicyStore(PolicyStoreConfig(dir=tmpdir))
    assert len(store) == 2 and store.n_corrupt == 1
    # the LSH index was rebuilt to match the surviving record set
    assert store.index.keys() == {r.key for r in store.records()}


def test_store_mid_put_crash_is_atomic(tmpdir):
    """A writer dying mid-persist leaves a *.tmp behind; the record file
    and the next load are unaffected, and put() never raises."""
    store = _mini_store(tmpdir, n=1)
    rec = store.records()[0]
    before = open(os.path.join(tmpdir, rec.key + ".json")).read()
    rec.knob = 9.0
    plan = FaultPlan([FaultSpec("store.put", prob=1.0, max_fires=1)])
    with faults.injected(plan):
        store.put(rec)                   # must not raise
    assert store.n_io_errors == 1
    assert open(os.path.join(tmpdir, rec.key + ".json")).read() == before
    assert glob.glob(os.path.join(tmpdir, "*.json.tmp"))
    # tmp leftovers are invisible to a fresh attach; memory copy won
    from repro.common.config import PolicyStoreConfig
    from repro.policystore import PolicyStore
    store2 = PolicyStore(PolicyStoreConfig(dir=tmpdir))
    assert len(store2) == 1 and store2.n_corrupt == 0
    assert [e["kind"] for e in obs.audit().tail(20)].count("store.io_error")


def test_store_truncated_index_rebuilds_silently(tmpdir):
    store = _mini_store(tmpdir, n=3)
    keys = {r.key for r in store.records()}
    idx_path = os.path.join(tmpdir, "lsh.index")
    payload = open(idx_path).read()
    with open(idx_path, "w") as f:
        f.write(payload[: len(payload) // 3])    # truncated mid-write
    from repro.common.config import PolicyStoreConfig
    from repro.policystore import PolicyStore
    store2 = PolicyStore(PolicyStoreConfig(dir=tmpdir))
    assert len(store2) == 3
    assert store2.n_index_rebuilds == 1
    assert store2.index.keys() == keys
    # and the rebuilt index was re-persisted in valid form
    json.load(open(idx_path))


def test_store_crash_between_record_write_and_index_update(tmpdir):
    """Kill the writer after the record file lands but before the index
    flush: the on-disk index is stale, and the next attach must detect
    the key-set mismatch and rebuild instead of serving a partial index."""
    store = _mini_store(tmpdir, n=2)
    from repro.common.config import PolicyStoreConfig
    from repro.policystore import PolicyRecord, PolicyStore, \
        fingerprint_tokens
    fp = fingerprint_tokens(np.arange(100) % 13 + 1)
    rec = PolicyRecord.from_policy(
        fingerprint=fp, prepare_fingerprint=fp, swap=None, candidates=[],
        n_ops=100, knob=1.0, measured_t=0.1, budget=1 << 20,
        policy_kind="conservative")
    # simulate the crash: write the record file directly, never the index
    with open(os.path.join(tmpdir, rec.key + ".json"), "w") as f:
        json.dump(rec.to_json(), f)
    store2 = PolicyStore(PolicyStoreConfig(dir=tmpdir))
    assert len(store2) == 3
    assert store2.n_index_rebuilds == 1
    assert store2.index.keys() == {r.key for r in store2.records()}


# --------------------------------------------- checkpoint hardening (S3)
def _ckpt_trees(v):
    return {"arrays": {"w": np.full((4, 4), v, np.float32),
                       "b": np.arange(6, dtype=np.float32) + v}}


def test_ckpt_restore_falls_back_on_bit_flip(tmpdir):
    from repro.checkpointing.manager import CheckpointManager
    mgr = CheckpointManager(tmpdir, process_index=0)
    mgr.save(1, _ckpt_trees(1.0), extra={"step": 1}, block=True)
    mgr.save(2, _ckpt_trees(2.0), extra={"step": 2}, block=True)
    shard = os.path.join(tmpdir, "step_00000002", "arrays.p0.npz")
    raw = bytearray(open(shard, "rb").read())
    raw[len(raw) // 2] ^= 0xFF                   # bit-flip mid-file
    with open(shard, "wb") as f:
        f.write(raw)
    # fallback disabled: the error names the shard
    with pytest.raises(IOError, match=r"arrays\.p0\.npz"):
        mgr.restore(2, _ckpt_trees(0.0), fallback=False)
    # fallback enabled: the previous step_N restores transparently
    out, extra = mgr.restore(2, _ckpt_trees(0.0))
    assert extra["step"] == 1
    np.testing.assert_array_equal(np.asarray(out["arrays"]["w"]),
                                  np.full((4, 4), 1.0, np.float32))
    assert mgr.n_restore_fallbacks == 1
    kinds = [e["kind"] for e in obs.audit().tail(20)]
    assert "ckpt.restore_failed" in kinds and "ckpt.restore_fallback" in kinds


def test_ckpt_write_fault_retries_then_succeeds(tmpdir):
    from repro.checkpointing.manager import CheckpointManager
    mgr = CheckpointManager(tmpdir, process_index=0)
    plan = FaultPlan([FaultSpec("ckpt.write", prob=1.0, max_fires=1)])
    with faults.injected(plan):
        mgr.save(5, _ckpt_trees(5.0), extra={"step": 5}, block=True)
    out, extra = mgr.restore(5, _ckpt_trees(0.0))
    assert extra["step"] == 5
    assert any(e["kind"] == "ckpt.write_retry"
               for e in obs.audit().tail(20))


def test_ckpt_degrade_mode_survives_write_failure(tmpdir):
    from repro.checkpointing.manager import CheckpointManager
    mgr = CheckpointManager(tmpdir, process_index=0, on_error="degrade")
    plan = FaultPlan([FaultSpec("ckpt.write", prob=1.0)])  # beats retries
    with faults.injected(plan):
        mgr.save(3, _ckpt_trees(3.0), extra={"step": 3})
        mgr.wait()                       # raise-mode would explode here
    assert mgr.n_write_failures == 1
    assert mgr.all_steps() == []         # the tmp dir never got renamed
    assert any(e["kind"] == "ckpt.write_failed"
               for e in obs.audit().tail(20))
    # raise mode keeps the legacy fail-stop contract
    mgr2 = CheckpointManager(tmpdir, process_index=0)
    with faults.injected(FaultPlan([FaultSpec("ckpt.write", prob=1.0)])):
        mgr2.save(4, _ckpt_trees(4.0), extra={"step": 4})
        with pytest.raises(RuntimeError, match="checkpoint write failed"):
            mgr2.wait()


def test_ckpt_collect_snapshots_failed_staging_from_hbm(tmpdir):
    """With the engine's checkpoint-class staging failing terminally, the
    writer snapshots the retained-in-HBM arrays instead of crashing."""
    from repro.checkpointing.manager import CheckpointManager
    eng = _engine(max_retries=0)
    mgr = CheckpointManager(tmpdir, process_index=0, engine=eng)
    plan = FaultPlan([FaultSpec("engine.transfer_error", prob=1.0)])
    with faults.injected(plan):
        mgr.save(9, _ckpt_trees(9.0), extra={"step": 9}, block=True)
    out, extra = mgr.restore(9, _ckpt_trees(0.0))
    np.testing.assert_array_equal(np.asarray(out["arrays"]["w"]),
                                  np.full((4, 4), 9.0, np.float32))
    assert eng.pool.live_blocks == 0
    eng.pool.check()


# ------------------------------------------------- adapt worker faults
def _adapt_service(mode="async"):
    from tests.test_adapt_service import _EchoPipeline
    from repro.adapt import AdaptationService
    return AdaptationService(_EchoPipeline(), mode=mode)


def test_adapt_worker_crash_publishes_conservative_fallback():
    from tests.test_adapt_service import _snap
    svc = _adapt_service()
    plan = FaultPlan([FaultSpec("adapt.worker", prob=1.0, max_fires=1)])
    with faults.injected(plan):
        svc.submit(_snap("fp-a", step=1))
        assert svc.drain(timeout=10.0)
    res = svc.poll()
    assert res is not None and res.kind == "conservative-fallback"
    assert svc.n_failed == 1
    svc.close()


def test_adapt_hang_trips_watchdog_once():
    from tests.test_adapt_service import _snap
    svc = _adapt_service()
    plan = FaultPlan([FaultSpec("adapt.hang", prob=1.0, seconds=1.0,
                                max_fires=1)])
    with faults.injected(plan):
        svc.submit(_snap("fp-b", step=2))
        time.sleep(0.1)
        assert svc.watchdog(0.05) is True
        assert svc.watchdog(0.05) is False       # fires at most once per job
    assert svc.n_watchdog == 1
    assert svc.stats()["watchdog_fired"] == 1
    svc.invalidate("worker-timeout")             # what the runtime does
    svc.drain(timeout=10.0)
    assert svc.poll() is None                    # late result discarded
    svc.close()


def test_watchdog_disabled_and_clean_poll_clears_timer():
    from tests.test_adapt_service import _snap
    svc = _adapt_service()
    svc.submit(_snap("fp-c", step=3))
    assert svc.watchdog(0.0) is False            # 0 disables
    svc.drain(timeout=10.0)
    assert svc.poll() is not None
    assert svc.watchdog(1e-9) is False           # timer cleared by poll
    svc.close()


# -------------------------------------------------- trainer integration
def test_straggler_callback_emits_audit_event():
    from repro.runtime.straggler import StragglerDetector, StragglerEvent
    from repro.runtime.trainer import Trainer
    det = StragglerDetector(threshold_sigma=3.0, warmup=2,
                            on_straggler=lambda ev:
                            Trainer._on_straggler(None, ev))
    for s in range(8):
        det.observe(s, 0.01 + 0.0001 * (s % 2))
    assert det.observe(8, 10.0) is True
    ev = obs.audit().tail(5, kind="straggler.flagged")[-1]
    assert ev["step"] == 8 and ev["wall"] == 10.0


@pytest.mark.slow
def test_chaos_trainer_descends_and_recovers(tmpdir):
    """The ISSUE-8 integration bar at test scale: a reduced-llama2 run
    with a seeded engine-fault window never crashes, degrades the swap
    path while the link is bad, recovers after, and the audit log shows
    the whole chain (fault -> retry -> health -> ladder)."""
    import repro.configs as C
    from repro.common.config import ChameleonConfig, TrainConfig
    from repro.data.synthetic import SyntheticTokens
    from repro.runtime.trainer import Trainer
    cfg = C.get_reduced("llama2_paper")
    tcfg = TrainConfig(steps=48, checkpoint_every=0, checkpoint_dir=tmpdir,
                       eval_every=0, warmup_steps=2, learning_rate=1e-3)
    data = SyntheticTokens(cfg.vocab_size, 64, 4, seed=0)
    tr = Trainer(cfg, tcfg,
                 ChameleonConfig(enabled=True, hbm_budget_bytes=12 << 20),
                 data=data)
    plan = FaultPlan([FaultSpec("engine.transfer_error", prob=1.0,
                                start=12, stop=22)], seed=1)
    with faults.injected(plan):
        rep = tr.train(48)
    assert not rep.failures
    assert plan.total_fired() > 0
    eng = tr.rt.hostmem.engine
    assert eng.n_retries > 0
    lad = tr.rt.ladder
    assert lad.n_descents >= 1, lad.transitions
    assert lad.n_ascents >= 1, lad.transitions   # probe-driven recovery
    assert eng.health.worst() == HEALTHY
    kinds = {e["kind"] for e in obs.audit().tail(500)}
    assert {"fault.injected", "engine.retry",
            "ladder.transition"} <= kinds
    assert eng.pool.live_blocks == 0
    eng.pool.check()

"""Roofline autotuner (repro.kernels.autotune): cache persistence and
corruption safety, warm-restart zero re-measurement, variant parity
against the kernel references, priced spill compression, sustained-
contention pricing, and the efficiency-derated Eq-3 fallback."""
import json
import os
import typing

import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.config import AutotuneConfig, ChameleonConfig, HostMemConfig
from repro.hostmem import HostMemTier
from repro.hostmem.bwmodel import BandwidthModel
from repro.hostmem.engine import TC_CHECKPOINT, TC_KV_SPILL, TC_POLICY_SWAP
from repro.kernels.autotune import table as T
from repro.kernels.autotune.advisor import (COMPRESS_INT8, COMPRESS_RAW,
                                            CompressionAdvisor)
from repro.kernels.autotune.cache import (CACHE_FILENAME, SCHEMA_VERSION,
                                          AutotuneCache, cache_key)
from repro.kernels.autotune.device import (DEVICE_SPECS, current_device_kind,
                                           get_device_spec)
from repro.kernels.autotune.space import SPACES
from repro.kernels.autotune.tuner import HOST_LINK_KERNEL, Autotuner


@pytest.fixture(autouse=True)
def _clean_table():
    """Every test starts and ends with an empty process-wide table."""
    T.clear()
    yield
    T.clear()


# ---------------------------------------------------------- device spec
def test_device_spec_registry():
    spec = get_device_spec()                       # the device JAX reports
    assert spec.kind == current_device_kind() == "cpu"
    assert spec.hbm_bw > 0 and spec.host_bw > 0
    assert set(DEVICE_SPECS) >= {"tpu_v5e", "tpu_v5p", "tpu_v4", "cpu"}
    # a v5e reports itself as "TPU v5 lite"
    assert get_device_spec("TPU v5 lite") is DEVICE_SPECS["tpu_v5e"]
    with pytest.raises(ValueError, match="tpu_v9x"):
        get_device_spec("tpu_v9x")                 # no silent fallback
    d = spec.to_dict()
    assert d["kind"] == spec.kind and d["hbm_bw"] == spec.hbm_bw


def test_roofline_uses_device_spec():
    from repro.launch.roofline import RooflineTerms
    for kind in ("tpu_v5e", "tpu_v4"):
        spec = DEVICE_SPECS[kind]
        t = RooflineTerms(flops_per_chip=spec.peak_flops,
                          bytes_per_chip=spec.hbm_bw / 2,
                          wire_bytes_per_chip=0.0, collectives={},
                          chips=1).finalize(get_device_spec(kind))
        assert t.compute_s == pytest.approx(1.0)
        assert t.memory_s == pytest.approx(0.5)


# ------------------------------------------------------- keys / buckets
def test_shape_bucket_pow2_rounding():
    assert T.shape_bucket((1000, 900)) == "1024x1024"
    assert T.shape_bucket((1024, 1024)) == "1024x1024"
    assert T.shape_bucket((1025, 1)) == "2048x1"


def test_dtype_name_normalization():
    assert T.dtype_name(np.float32) == "float32"
    assert T.dtype_name(np.dtype(np.float32)) == "float32"
    assert T.dtype_name(jnp.zeros((1,), jnp.bfloat16).dtype) == "bfloat16"
    assert (T.table_key("quantize", (1000, 900), np.float32)
            == T.table_key("quantize", (1024, 1024),
                           jnp.zeros((1,), jnp.float32).dtype))


# ----------------------------------------------------- cache round-trip
def _entry(block_rows=128, bps=1e9):
    return {"config": {"block_rows": block_rows}, "achieved_bps": bps,
            "measured_s": 0.001, "bytes_moved": 1 << 20,
            "efficiency": 0.5, "shape": [1024, 1024]}


def test_cache_roundtrip(tmp_path):
    cache = AutotuneCache(str(tmp_path))
    cache.put("quantize", (1024, 1024), np.float32, _entry())
    cache.bwmodel = BandwidthModel(32.0, link_efficiency=0.7).to_dict()
    path = cache.save()
    assert path and os.path.exists(path)
    assert not os.path.exists(path + ".tmp")      # atomic write cleaned up
    loaded = AutotuneCache.load(str(tmp_path))
    assert loaded.entries == cache.entries
    assert loaded.bwmodel["link_efficiency"] == pytest.approx(0.7)
    assert loaded.load_errors == 0
    # bucketed hit/miss
    assert loaded.get("quantize", (1000, 900), np.float32) is not None
    assert loaded.get("quantize", (2048, 1024), np.float32) is None
    assert loaded.get("quantize", (1024, 1024), np.int8) is None


def test_cache_missing_dir_is_empty(tmp_path):
    cache = AutotuneCache.load(str(tmp_path / "nowhere"))
    assert cache.entries == {} and cache.load_errors == 0


@pytest.mark.parametrize("payload", [
    "{garbage",                                    # truncated / not JSON
    json.dumps({"schema_version": 99, "entries": {}}),
    json.dumps({"schema_version": SCHEMA_VERSION, "entries": [1, 2]}),
])
def test_cache_corruption_safe_load(tmp_path, payload):
    (tmp_path / CACHE_FILENAME).write_text(payload)
    cache = AutotuneCache.load(str(tmp_path))
    assert cache.entries == {}
    assert cache.load_errors == 1


def test_cache_malformed_entries_skipped_individually(tmp_path):
    good_key = cache_key("quantize", (1024, 1024), np.float32, "tpu_v5e")
    payload = {"schema_version": SCHEMA_VERSION,
               "entries": {good_key: _entry(),
                           "bad-key": _entry(),
                           "a|b|c|d": "not-a-dict",
                           "e|f|g|h": {"no_config": True}}}
    (tmp_path / CACHE_FILENAME).write_text(json.dumps(payload))
    cache = AutotuneCache.load(str(tmp_path))
    assert list(cache.entries) == [good_key]
    assert cache.load_errors == 3


def test_table_entries_drop_other_devices():
    cache = AutotuneCache(device_kind="tpu_v5e")
    cache.put("quantize", (1024, 1024), np.float32, _entry(128))
    cache.entries[cache_key("quantize", (1024, 1024), np.float32,
                            "tpu_v4")] = _entry(64)
    entries = cache.table_entries()
    assert list(entries.values()) == [{"block_rows": 128}]


# ----------------------------------------------- tuner counters / cache
def test_tuner_measures_all_variants_once():
    tuner = Autotuner(measure=lambda fn: 0.01)
    cfg = tuner.tune("quantize")
    assert cfg in list(SPACES["quantize"].variants)
    assert tuner.n_measured == len(SPACES["quantize"].variants)
    assert tuner.n_cache_hits == 0
    # same bucket: answered from cache, zero new measurements
    again = tuner.tune("quantize", shape=(1000, 900))
    assert again == cfg
    assert tuner.n_measured == len(SPACES["quantize"].variants)
    assert tuner.n_cache_hits == 1


def test_warm_restart_zero_remeasurement(tmp_path):
    t1 = Autotuner(cache=AutotuneCache(str(tmp_path)),
                   measure=lambda fn: 0.01)
    t1.tune_all(("quantize", "dequantize"))
    assert t1.n_measured > 0
    t1.cache.save()
    # cold process, warm directory
    t2 = Autotuner(cache=AutotuneCache.load(str(tmp_path)),
                   measure=lambda fn: pytest.fail("re-measured!"))
    t2.tune_all(("quantize", "dequantize"))
    assert t2.n_measured == 0
    assert t2.n_cache_hits == 2


def test_tuner_picks_fastest_variant():
    space = SPACES["quantize"]
    fast = dict(space.variants[2])                # not the default
    times = {i: (0.001 if dict(v) == fast else 0.01)
             for i, v in enumerate(space.variants)}
    it = iter(range(len(space.variants)))
    tuner = Autotuner(measure=lambda fn: times[next(it)])
    assert tuner.tune("quantize") == fast
    entry = tuner.cache.get("quantize", space.default_shape, np.float32)
    assert entry["achieved_bps"] == pytest.approx(
        space.bytes_moved(space.default_shape, np.dtype(np.float32)) / 0.001)
    assert 0.0 < entry["efficiency"] <= 1.0


# ------------------------------------------------------ variant parity
@pytest.mark.parametrize("kernel,shape", [
    ("quantize", (256, 64)),
    ("dequantize", (256, 64)),
    ("flash_attention", (1, 256, 2, 32)),
    ("ssd_scan", (1, 256, 2, 32)),
])
def test_every_variant_matches_reference(kernel, shape):
    """Tuning must never trade numerics for speed: every config in every
    search space reproduces the kernel's reference implementation."""
    space = SPACES[kernel]
    args = space.make_args(shape, np.dtype(np.float32))
    ref = space.ref(args)
    for config in space.variants:
        out = space.run(args, config)
        if kernel == "quantize":
            q, s = out
            qr, sr = ref
            diff = np.abs(np.asarray(q, np.int32) - np.asarray(qr, np.int32))
            assert diff.max() <= 1                # 1-quantum rounding flips
            assert (diff > 0).mean() < 0.01
            np.testing.assert_allclose(np.asarray(s), np.asarray(sr),
                                       rtol=1e-6)
        else:
            np.testing.assert_allclose(
                np.asarray(out, np.float32), np.asarray(ref, np.float32),
                rtol=2e-3, atol=2e-3)


# ------------------------------------------- table -> ops wrapper wiring
def test_ops_wrappers_consult_installed_table():
    from repro.kernels.quant_offload import ops as Q
    shape, dtype = (1024, 1024), np.dtype(np.float32)
    assert Q._tuned_block_rows("quantize", shape, dtype) == 256  # default
    T.install({T.table_key("quantize", shape, dtype): {"block_rows": 64}})
    assert Q._tuned_block_rows("quantize", shape, dtype) == 64
    x = jnp.asarray(np.random.RandomState(0).randn(100, 64), jnp.float32)
    q, s = Q.quantize(x)                          # ragged + tuned lookup
    assert q.shape == (100, 64) and s.shape == (100, 1)


def test_install_cache_roundtrip():
    from repro.kernels.autotune import install_cache
    cache = AutotuneCache()
    cache.put("ssd_scan", (1, 256, 4, 64), np.float32,
              {"config": {"chunk": 64}, "achieved_bps": 1e9})
    assert install_cache(cache) == 1
    assert T.tuned_config("ssd_scan", (1, 256, 4, 64),
                          np.float32) == {"chunk": 64}


# --------------------------------------------------- link efficiency
def test_link_efficiency_from_calibrated_model():
    bw = BandwidthModel(32.0)
    for size in (1 << 16, 1 << 20, 1 << 24):
        bw.observe(size, size / 16e9)             # measured 16 GB/s link
    tuner = Autotuner(measure=lambda fn: 0.01)
    eff = tuner.link_efficiency(bw)
    spec = tuner.spec
    assert eff == pytest.approx(16e9 / spec.host_bw, rel=0.05)
    stored = tuner.cache.entries[
        f"{HOST_LINK_KERNEL}|-|-|{tuner.cache.device_kind}"]
    assert stored["config"]["efficiency"] == pytest.approx(eff)
    # uncalibrated model + warm cache: reuse the stored value
    t2 = Autotuner(cache=tuner.cache, measure=lambda fn: 0.01)
    assert t2.link_efficiency(BandwidthModel(32.0)) == pytest.approx(eff)
    assert t2.n_cache_hits == 1
    # nothing stored and nothing calibrated: nominal link
    assert Autotuner(measure=lambda fn: 0.01).link_efficiency(None) == 1.0


def test_t_swap_derated_by_link_efficiency():
    from repro.core.simulator import Simulator
    prof = _toy_profile()
    cfg = ChameleonConfig(groups_per_phase=8)
    full = Simulator(prof, 50, cfg,
                     bwmodel=BandwidthModel(32.0, link_efficiency=1.0))
    half = Simulator(prof, 50, cfg,
                     bwmodel=BandwidthModel(32.0, link_efficiency=0.5))
    nbytes = 1 << 20
    assert half.t_swap(nbytes) == pytest.approx(2 * full.t_swap(nbytes))
    # a *calibrated* curve is already a measurement — never derated
    bw = BandwidthModel(32.0, link_efficiency=0.5)
    for size in (1 << 16, 1 << 20, 1 << 24):
        bw.observe(size, size / 16e9)
    cal = Simulator(prof, 50, cfg, bwmodel=bw)
    assert cal.t_swap(nbytes) == pytest.approx(bw.transfer_time(nbytes))


def test_link_efficiency_survives_snapshot_roundtrip():
    bw = BandwidthModel(32.0, link_efficiency=0.4)
    again = BandwidthModel.from_dict(bw.to_dict())
    assert again.link_efficiency == pytest.approx(0.4)
    assert BandwidthModel.from_dict(
        BandwidthModel(32.0).to_dict()).link_efficiency == 1.0


# ------------------------------------------------ compression advisor
def _skewed_cache(bps):
    cache = AutotuneCache()
    cache.put("quantize", (1024, 1024), np.float32,
              {"config": {"block_rows": 256}, "achieved_bps": bps})
    cache.put("dequantize", (1024, 1024), np.float32,
              {"config": {"block_rows": 256}, "achieved_bps": bps})
    return cache


def test_advisor_picks_int8_when_kernels_are_cheap():
    adv = CompressionAdvisor(bwmodel=BandwidthModel(1.0),  # slow link
                             cache=_skewed_cache(1e15))    # free kernels
    choice, detail = adv.decide(1 << 20, 4, rows=256)
    assert choice == COMPRESS_INT8
    assert detail["int8_s"] < detail["raw_s"]
    assert adv.n_int8 == 1 and adv.n_raw == 0


def test_advisor_picks_raw_when_kernels_are_slow():
    adv = CompressionAdvisor(bwmodel=BandwidthModel(1000.0),  # fast link
                             cache=_skewed_cache(1e3))        # slow kernels
    choice, detail = adv.decide(1 << 20, 4, rows=256)
    assert choice == COMPRESS_RAW
    assert detail["raw_s"] < detail["int8_s"]
    assert adv.n_raw == 1


def test_advisor_decision_is_audited():
    from repro import obs
    adv = CompressionAdvisor(bwmodel=BandwidthModel(1.0),
                             cache=_skewed_cache(1e15))
    adv.decide(1 << 20, 4, rows=256, tag="probe-row")
    ev = [e for e in obs.audit().tail(20)
          if e["kind"] == "kvspill.compression_choice"
          and e.get("tag") == "probe-row"]
    assert ev and ev[-1]["choice"] == COMPRESS_INT8
    assert ev[-1]["raw_us"] > 0


def test_advisor_untuned_reduces_to_static_int8_rule():
    adv = CompressionAdvisor(bwmodel=BandwidthModel(32.0), cache=None)
    choice, _ = adv.decide(1 << 20, 4, rows=256)
    assert choice == COMPRESS_INT8                # smaller payload wins


# -------------------------------------------- auto spill compression
class _State(typing.NamedTuple):
    attn_k: object
    pos: object


def _toy_state(rows=64, cols=512):
    rng = np.random.RandomState(0)
    return _State(attn_k=jnp.asarray(rng.randn(2, 2, rows, cols),
                                     jnp.float32),
                  pos=jnp.asarray([5, 7], jnp.int32))


def _auto_tier(advisor):
    tier = HostMemTier(HostMemConfig(spill_compression="auto",
                                     spill_compress_min_bytes=1))
    tier.kvspill.advisor = advisor
    return tier


def test_auto_compression_compresses_when_priced_cheaper():
    tier = _auto_tier(CompressionAdvisor(bwmodel=BandwidthModel(1.0),
                                         cache=_skewed_cache(1e15)))
    sp = tier.kvspill.spill(_toy_state(), 0, tag="auto-int8")
    assert all(fs.kind == "int8" for fs in sp.layout)
    assert tier.kvspill.stats()["advisor"]["n_int8"] >= 1
    tier.kvspill.discard(sp)


def test_auto_compression_stays_raw_when_priced_dearer():
    tier = _auto_tier(CompressionAdvisor(bwmodel=BandwidthModel(1000.0),
                                         cache=_skewed_cache(1e3)))
    sp = tier.kvspill.spill(_toy_state(), 0, tag="auto-raw")
    assert all(fs.kind == "raw" for fs in sp.layout)
    assert tier.kvspill.stats()["advisor"]["n_raw"] >= 1
    tier.kvspill.discard(sp)


def test_auto_roundtrip_restores_state():
    state = _toy_state()
    before = np.asarray(state.attn_k[:, 0], np.float32).copy()
    tier = _auto_tier(CompressionAdvisor(bwmodel=BandwidthModel(1.0),
                                         cache=_skewed_cache(1e15)))
    sp = tier.kvspill.spill(state, 0, tag="rt")
    zeroed = state._replace(attn_k=state.attn_k.at[:, 0].set(0),
                            pos=state.pos.at[0].set(0))
    back = tier.kvspill.restore(zeroed, sp, 0)
    tol = np.abs(before).max() / 100.0 + 1e-6
    np.testing.assert_allclose(np.asarray(back.attn_k[:, 0], np.float32),
                               before, atol=tol)
    assert int(back.pos[0]) == 5
    assert tier.pool.bytes_in_use == 0


def test_auto_without_advisor_behaves_like_int8():
    tier = HostMemTier(HostMemConfig(spill_compression="auto",
                                     spill_compress_min_bytes=1))
    tier.kvspill.advisor = None
    sp = tier.kvspill.spill(_toy_state(), 0, tag="fallback")
    assert all(fs.kind == "int8" for fs in sp.layout)
    tier.kvspill.discard(sp)


# ------------------------------------------- sustained contention EWMA
def _engine():
    return HostMemTier().engine


def test_arrival_rate_ewma_decays():
    eng = _engine()
    assert eng.arrival_rate_bps(TC_KV_SPILL) == 0.0
    eng._note_arrival(TC_KV_SPILL, 2_000_000, now=100.0)
    from repro.hostmem.engine import ARRIVAL_TAU_S
    r0 = eng.arrival_rate_bps(TC_KV_SPILL, now=100.0)
    assert r0 == pytest.approx(2_000_000 / ARRIVAL_TAU_S)
    r1 = eng.arrival_rate_bps(TC_KV_SPILL, now=100.0 + ARRIVAL_TAU_S)
    assert r1 == pytest.approx(r0 * np.exp(-1.0))


def test_sustained_contention_prices_other_classes():
    eng = _engine()
    assert eng.sustained_contention(TC_POLICY_SWAP) == 0.0
    for _ in range(4):
        eng.wait(eng.submit_swap_out(np.zeros(1 << 20, np.uint8),
                                     "spill", cls=TC_KV_SPILL))
    occ = eng.sustained_contention(TC_POLICY_SWAP)
    assert occ > 0.0
    # a class never counts its own traffic
    assert eng.sustained_contention(TC_KV_SPILL) < occ + 1e-12
    eng.synchronize()


def test_sustained_contention_clamped():
    import time
    eng = _engine()
    eng._note_arrival(TC_CHECKPOINT, 1 << 50, now=1.0)
    eng._arr_last_t[TC_CHECKPOINT] = time.perf_counter()
    assert eng.sustained_contention(TC_POLICY_SWAP) == 0.95


def test_backlog_snapshot_carries_occupancy():
    eng = _engine()
    for _ in range(3):
        eng.wait(eng.submit_swap_out(np.zeros(1 << 20, np.uint8),
                                     "spill", cls=TC_KV_SPILL))
    snap = eng.backlog_snapshot()
    for cls in snap:
        assert "occupancy" in snap[cls] and "arrival_bps" in snap[cls]
    assert snap[TC_KV_SPILL]["arrival_bps"] > 0.0
    assert snap[TC_POLICY_SWAP]["occupancy"] > 0.0
    assert snap[TC_KV_SPILL]["occupancy"] == pytest.approx(
        eng.sustained_contention(TC_KV_SPILL), rel=0.2)
    eng.synchronize()


def _toy_profile(n_ops=100):
    from repro.core.profiler import ProfileData, TensorInstance
    tensors = [TensorInstance(i, 1 << 20, i, n_ops - i, site="ffn_pre",
                              layer=i) for i in range(10)]
    return ProfileData(np.zeros(n_ops, np.int32), tensors, 1.0, 0)


class _BusyEngine:
    """Engine stand-in with sustained traffic but an empty queue."""

    def __init__(self, occ):
        self._occ = occ

    def queued_delay(self, cls="policy_swap", kind="out"):
        return 0.0

    def sustained_contention(self, cls="policy_swap"):
        return self._occ


def test_simulator_scales_budgets_by_occupancy():
    from repro.core.simulator import Simulator
    prof = _toy_profile()
    cfg = ChameleonConfig(groups_per_phase=8)
    idle = Simulator(prof, 50, cfg)
    busy = Simulator(prof, 50, cfg, engine=_BusyEngine(0.5))
    assert busy.occupancy == 0.5
    assert busy.contention_s == 0.0               # backlog pricing intact
    np.testing.assert_allclose(busy._remaining, idle._remaining * 0.5)


def test_policy_records_occupancy_and_roundtrips(llama_profile):
    from repro.core.memtrace import build_timeline
    from repro.core.policy import generate_policy
    prof, _ = llama_profile
    tl = build_timeline(prof)
    pol = generate_policy(prof, ChameleonConfig(groups_per_phase=8),
                          int(tl.peak * 0.7), timeline=tl,
                          engine=_BusyEngine(0.25),
                          register_free_times=False)
    assert pol.occupancy == 0.25
    # policystore serialization carries it through a JSON round trip
    from repro.policystore.fingerprint import fingerprint_tokens
    from repro.policystore.store import PolicyRecord
    fp = fingerprint_tokens(np.arange(100, dtype=np.int32))
    rec = PolicyRecord.from_policy(
        fingerprint=fp, prepare_fingerprint=fp, swap=pol, candidates=[],
        n_ops=pol.n_ops, knob=8.0, measured_t=0.1, budget=pol.budget)
    assert rec.policy_meta["occupancy"] == 0.25
    back = PolicyRecord.from_json(rec.to_json())
    assert back.swap_policy().occupancy == 0.25


def test_frozen_backlog_matches_live_engine():
    from repro.adapt.snapshot import AdaptSnapshot
    eng = _engine()
    for _ in range(3):
        eng.wait(eng.submit_swap_out(np.zeros(1 << 20, np.uint8),
                                     "spill", cls=TC_KV_SPILL))
    snap = AdaptSnapshot(contention_s=eng.queued_delay(),
                         backlog=eng.backlog_snapshot())
    frozen = snap.engine_view()
    live = eng.sustained_contention(TC_POLICY_SWAP)
    assert frozen.sustained_contention(TC_POLICY_SWAP) == pytest.approx(
        live, rel=0.2)
    assert frozen.sustained_contention("unknown_class") == 0.0
    eng.synchronize()


# ------------------------------------------------- tier-level wiring
def test_tier_autotune_warm_restart(tmp_path, monkeypatch):
    import repro.kernels.autotune.tuner as tuner_mod
    monkeypatch.setattr(tuner_mod, "default_measure",
                        lambda fn, iters=3: 0.01)
    atcfg = AutotuneConfig(enabled=True, cache_dir=str(tmp_path), iters=1)
    t1 = HostMemTier().autotune(atcfg)
    assert t1.n_measured > 0
    assert os.path.exists(os.path.join(str(tmp_path), CACHE_FILENAME))
    assert T.installed_count() >= 2
    t2 = HostMemTier().autotune(atcfg)            # cold process, warm dir
    assert t2.n_measured == 0
    assert t2.n_cache_hits >= 2


def test_from_chameleon_triggers_autotune(tmp_path, monkeypatch):
    import repro.kernels.autotune.tuner as tuner_mod
    monkeypatch.setattr(tuner_mod, "default_measure",
                        lambda fn, iters=3: 0.01)
    ccfg = ChameleonConfig(
        autotune=AutotuneConfig(enabled=True, cache_dir=str(tmp_path)))
    tier = HostMemTier.from_chameleon(ccfg)
    assert tier.autotuner is not None
    assert tier.autotuner.stats()["cache"]["entries"] >= 2

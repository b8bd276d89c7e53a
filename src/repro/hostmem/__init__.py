"""repro.hostmem — the host-memory tier.

One shared substrate under both branches of the system:

  * **training** (§5.4 policy execution): the simulator prices swaps with
    the measured :class:`BandwidthModel`, the policy's free-times hand
    off to the :class:`TransferEngine`'s swap-out completion events, and
    every staged tensor recycles through the :class:`PinnedSlabPool`;
  * **serving**: :class:`KVSpillManager` parks idle decode slots in the
    same pool so admission exceeds HBM-resident slots.

``HostMemTier`` bundles the four components with consistent wiring.
"""
from __future__ import annotations

from typing import Optional

from repro.common.config import ChameleonConfig, HostMemConfig
from repro.hostmem import metrics as _metrics
from repro.hostmem.bwmodel import BandwidthModel
from repro.hostmem.engine import (TC_CHECKPOINT, TC_KV_SPILL, TC_POLICY_SWAP,
                                  TRAFFIC_CLASSES, TransferEngine,
                                  TransferEvent)
from repro.hostmem.kvspill import KVSpillManager, SpilledSlot
from repro.hostmem.pool import HostBlock, HostMemError, PinnedSlabPool

__all__ = [
    "BandwidthModel", "HostBlock", "HostMemConfig", "HostMemError",
    "HostMemTier", "KVSpillManager", "PinnedSlabPool", "SpilledSlot",
    "TC_CHECKPOINT", "TC_KV_SPILL", "TC_POLICY_SWAP", "TRAFFIC_CLASSES",
    "TransferEngine", "TransferEvent",
]


class HostMemTier:
    """Pool + engine + bandwidth model + kv-spill, wired together."""

    def __init__(self, cfg: Optional[HostMemConfig] = None, *,
                 constant_gbps: float = 32.0, resilience=None):
        self.cfg = cfg or HostMemConfig()
        self.pool = PinnedSlabPool(
            capacity_bytes=self.cfg.pool_bytes or None,
            min_class_bytes=self.cfg.min_class_bytes)
        self.bwmodel = BandwidthModel(constant_gbps)
        self.engine = TransferEngine(self.pool, depth=self.cfg.engine_depth,
                                     bwmodel=self.bwmodel,
                                     class_depths=dict(self.cfg.class_depths),
                                     resilience=resilience)
        self.autotuner = None        # set by autotune()
        advisor = None
        if self.cfg.spill_compression == "auto":
            from repro.kernels.autotune.advisor import CompressionAdvisor
            advisor = CompressionAdvisor(bwmodel=self.bwmodel)
        self.kvspill = KVSpillManager(
            self.pool, self.engine,
            compression=self.cfg.spill_compression,
            compress_min_bytes=self.cfg.spill_compress_min_bytes,
            advisor=advisor)
        if self.cfg.calibrate:
            self.calibrate()

    @classmethod
    def from_chameleon(cls, ccfg: ChameleonConfig) -> Optional["HostMemTier"]:
        """Build the tier a ChameleonConfig asks for (None when disabled)."""
        if not ccfg.hostmem.enabled:
            return None
        tier = cls(ccfg.hostmem, constant_gbps=ccfg.host_link_gbps,
                   resilience=ccfg.resilience)
        if ccfg.autotune.enabled:
            tier.autotune(ccfg.autotune)
        return tier

    def autotune(self, atcfg=None, *, device_kind=None):
        """Tune the swap-path kernels against the roofline and wire the
        results into pricing (repro.kernels.autotune).

        Loads the cache (warm restart = zero re-measurement), measures
        any missing kernels, installs winners into the process-wide tuned
        table the kernel ``ops`` wrappers consult, derates the bandwidth
        model's uncalibrated fallback by the measured link efficiency,
        points the kv-spill compression advisor at the tuned rates, and
        persists cache + bandwidth snapshot atomically.  Returns the
        :class:`~repro.kernels.autotune.tuner.Autotuner`."""
        from repro.common.config import AutotuneConfig
        from repro.kernels.autotune import (Autotuner, AutotuneCache,
                                            current_device_kind,
                                            get_device_spec, install_cache)
        atcfg = atcfg or AutotuneConfig(enabled=True)
        kind = device_kind or current_device_kind()
        cache = (AutotuneCache.load(atcfg.cache_dir, device_kind=kind)
                 if atcfg.cache_dir else AutotuneCache(device_kind=kind))
        tuner = Autotuner(cache=cache, spec=get_device_spec(kind),
                          iters=atcfg.iters)
        tuner.tune_all(atcfg.kernels)
        eff = tuner.link_efficiency(self.bwmodel)
        self.bwmodel.set_link_efficiency(eff)
        cache.bwmodel = self.bwmodel.to_dict()
        if atcfg.cache_dir:
            cache.save()
        install_cache(cache)
        if self.kvspill.advisor is not None:
            self.kvspill.advisor.cache = cache
        self.autotuner = tuner
        return tuner

    def calibrate(self, sizes=None, iters=None) -> "BandwidthModel":
        """Calibration transfers through the *production* path: each size
        does real swap-out/swap-in round trips via the engine.  This
        prices exactly the copies the policy will later schedule — unlike
        a raw ``device_put`` probe, which JAX may elide on CPU.  The
        engine's per-copy EMA feed is bypassed during the sweep: per-size
        *minima* of warm runs go into the curve — min is the standard
        low-noise estimator for copy cost (the first round-trip per size
        pays slab allocation and, globally, JAX dispatch initialization —
        ~3 orders of magnitude of noise)."""
        import numpy as np
        sizes = sizes if sizes is not None else self.cfg.calibration_sizes
        iters = iters if iters is not None else self.cfg.calibration_iters
        eng = self.engine
        saved, eng.bwmodel = eng.bwmodel, None
        try:
            warm = np.zeros(1024, np.uint8)      # init JAX + tiny slab class
            eng.wait(eng.submit_swap_in(
                eng.wait(eng.submit_swap_out(warm, "warm")), "warm"))
            for size in sizes:
                arr = np.zeros(size, np.uint8)
                outs, ins = [], []
                for i in range(max(iters, 1) + 1):
                    ev = eng.wait(eng.submit_swap_out(arr, "calib"))
                    ev2 = eng.wait(eng.submit_swap_in(ev, "calib"))
                    if i:                        # drop the cold run
                        outs.append(ev.seconds)
                        ins.append(ev2.seconds)
                self.bwmodel.observe(size, (min(outs) + min(ins)) / 2)
        finally:
            eng.bwmodel = saved
        return self.bwmodel

    def stats(self) -> dict:
        return _metrics.collect(self)

    def summary(self) -> str:
        return _metrics.format_summary(self.stats())

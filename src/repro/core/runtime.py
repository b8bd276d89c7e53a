"""ChameleonRuntime — ties profiler, stage machine, policy generator and
executor into the per-iteration loop (paper Fig. 2).

Protocol (driven by ``repro.runtime.trainer.Trainer``):

    rt = ChameleonRuntime(cham_cfg, step_builder)
    rt.prepare(example_args)                  # WarmUp fit (Algo 3, proactive)
    for it in range(steps):
        fn = rt.step_fn()                     # current applied policy
        t0 = time(); out = fn(*args); block(); dt = time() - t0
        rt.record_dispatch("train", fn, args) # Lightweight-mode op stream
        ... (any extra dispatches: eval, optimizer-skip, ... recorded too)
        rt.end_iteration(dt)                  # Algo 1 stage machine

During GenPolicy the runtime generates one policy variant per step (varying
the logical-layer grouping knob) and, after n steps, keeps the variant with
the best measured iteration time — the paper's §7.1 "generates five policies
and selects the one with the best runtime performance".

The adaptation *pipeline* (classification, cached-policy re-association,
variant construction, store write-back) lives in ``repro.adapt``; this
module keeps the iteration-loop state machine and the install points.
With ``cfg.adapt.mode`` set to ``async`` or ``speculative`` the settled
WarmUp enqueues an :class:`~repro.adapt.AdaptSnapshot` to the background
:class:`~repro.adapt.AdaptationService` instead of running GenPolicy
iterations inline; the worker's result installs at the next iteration
boundary (after the engine feedback of the policy that just ran), so
drift never stalls an iteration.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from repro import obs
# PolicyVariant / VARIANT_KNOBS moved to repro.adapt.pipeline; re-exported
# here because callers import them from the runtime module
from repro.adapt import (VARIANT_KNOBS, AdaptResult, AdaptSnapshot,
                         AdaptationPipeline, AdaptationService, PolicyVariant)
from repro.common.config import ChameleonConfig
from repro.core import tokenizer
from repro.core.executor import AppliedPolicy, Executor
from repro.core.memtrace import build_timeline
from repro.core.oom import warmup_offload_sites
from repro.core.policy import (ChameleonOOMError, SwapPolicy,
                               projected_peak)
from repro.faults.health import MEM_CLASS
from repro.faults.ladder import (RUNG_CONSERVATIVE, RUNG_FULL, RUNG_NAMES,
                                 RUNG_NO_SWAP, RUNG_TRIMMED,
                                 DegradationLadder, trim_swap)
from repro.core.profiler import ProfileData, profile_jaxpr
from repro.core.stages import Stage, StageMachine
from repro.policystore import DriftClassifier, PolicyStore, Tier

__all__ = ["ChameleonRuntime", "PolicyVariant", "VARIANT_KNOBS"]


class ChameleonRuntime:
    def __init__(self, cfg: ChameleonConfig,
                 step_builder: Callable[[Optional[Any]], Callable],
                 budget: Optional[int] = None, hostmem=None):
        self.cfg = cfg
        self.budget = budget if budget is not None else cfg.hbm_budget_bytes
        self.step_builder = step_builder
        self.executor = Executor(cfg)
        if hostmem is None and cfg.enabled and cfg.hostmem.enabled:
            from repro.hostmem import HostMemTier
            hostmem = HostMemTier.from_chameleon(cfg)
        self.hostmem = hostmem
        self._step_cache: Dict[str, Callable] = {}
        self._trace_cache: Dict[Tuple, tokenizer.TokenStream] = {}
        self._jaxpr_cache: Dict[Tuple, Any] = {}
        # baseline profile per arg-shape key — pure memoization of
        # profile_jaxpr over the cached baseline trace, so a WarmUp
        # re-entry onto a recurring shape bucket skips both the re-trace
        # and the (pure-Python) profile traversal on the training thread
        self._baseprof_cache: Dict[Tuple, ProfileData] = {}
        # detailed profiles of streams adapted before, keyed by iteration
        # fingerprint: a recurring stream's snapshot carries its profile so
        # the worker skips the (GIL-heavy) profile_jaxpr traversal — only a
        # stream's *first* adaptation pays it.  The profile keeps the
        # t_iter it was measured at; a recurrence prices with that.
        self._profile_lru: "collections.OrderedDict[str, ProfileData]" = \
            collections.OrderedDict()
        self._profile_lru_cap = 8
        self.applied: AppliedPolicy = self.executor.baseline()
        self.profile: Optional[ProfileData] = None
        self.baseline_profile: Optional[ProfileData] = None
        self._iter_streams: List[tokenizer.TokenStream] = []
        # incremental iteration signature: histogram/length deltas are
        # applied only for dispatch slots whose content hash changed
        self._sig_acc = tokenizer.SignatureAccumulator()
        self._example_args: Optional[tuple] = None
        self._pending_variant: Optional[PolicyVariant] = None
        self._mirror_src: Optional[np.ndarray] = None
        self.step_idx = 0
        self.history: List[dict] = []
        self.profiling_overhead_s = 0.0      # steady-state Lightweight mode
        self.adaptation_overhead_s = 0.0     # episodic (GenPolicy/store/fit)
        # ---- policystore: persistent fingerprint-keyed adaptation cache
        self.store: Optional[PolicyStore] = None
        self.drift: Optional[DriftClassifier] = None
        if cfg.enabled and cfg.policystore.enabled:
            self.store = PolicyStore(cfg.policystore)
            self.drift = DriftClassifier(cfg.policystore)
        # ---- adaptation pipeline + placement (repro.adapt): the §5 cycle
        # itself is pipeline code shared by every mode; the service owns
        # variant bookkeeping plus the async worker/mailbox machinery
        adapt_mode = cfg.adapt.mode if cfg.enabled else "inline"
        self.pipeline = AdaptationPipeline(cfg, self.executor,
                                           store=self.store, drift=self.drift,
                                           hostmem=self.hostmem)
        self.service = AdaptationService(
            self.pipeline, adapt_mode, max_parked=cfg.adapt.max_parked,
            max_snapshots=cfg.adapt.max_snapshots, history=cfg.adapt.history,
            pace_s=cfg.adapt.pace_s, pace_cap_s=cfg.adapt.pace_cap_s)
        self.machine = StageMachine(cfg, async_mode=adapt_mode != "inline")
        # ---- degradation ladder (repro.faults): link health drives the
        # applied policy down full → trimmed → conservative → no_swap and
        # probe-driven recovery climbs it back up
        self.ladder: Optional[DegradationLadder] = None
        self._full_applied: Optional[AppliedPolicy] = None
        self._probe_src: Optional[np.ndarray] = None
        if cfg.enabled and self.hostmem is not None and cfg.resilience.enabled:
            self.ladder = DegradationLadder(
                hold_iterations=cfg.resilience.ladder_hold_iterations,
                probe_interval=cfg.resilience.probe_interval)
        self._gen_knobs: Tuple[float, ...] = VARIANT_KNOBS
        self._last_sig: Optional[tokenizer.Signature] = None
        # dispatch-shape drift: same primitives, different memory profile
        # (seq-len bucket cycling) — invisible to the token stream, so the
        # runtime tracks the train dispatch's arg shapes itself
        self._train_shape: Optional[Tuple] = None
        self._prev_train_shape: Optional[Tuple] = None
        self._last_decision = None           # DriftDecision of this adaptation
        # per-iteration swap/compute overlap (repro.obs): fraction of
        # engine transfer time hidden under compute spans this iteration
        self._iter_t0 = time.perf_counter()
        self.overlap_history: collections.deque = collections.deque(
            maxlen=512)
        obs.tracer().set_iteration(self.step_idx)

    # ------------------------------------------- adaptation state (service)
    # the GenPolicy variant list, selection winner, and adaptation-latency
    # records moved into AdaptationService with the pipeline extraction;
    # these properties keep the runtime's public surface unchanged
    @property
    def variants(self) -> List[PolicyVariant]:
        return self.service.variants

    @variants.setter
    def variants(self, v) -> None:
        self.service.variants = list(v)

    @property
    def best(self) -> Optional[PolicyVariant]:
        return self.service.best

    @best.setter
    def best(self, v) -> None:
        self.service.best = v

    @property
    def adaptations(self) -> List[dict]:
        return self.service.adaptations

    # ------------------------------------------------------------ helpers
    def _args_key(self, args) -> Tuple:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
        return tuple((getattr(x, "shape", None), str(getattr(x, "dtype", "")))
                     for x in leaves)

    def _baseline_jaxpr(self, args):
        """Trace the no-swap baseline program (save-sites policy — the
        PyTorch-autograd-equivalent memory behavior, see Executor.baseline)."""
        key = ("baseline",) + self._args_key(args)
        if key not in self._jaxpr_cache:
            import jax
            fn = self.step_builder(self.executor.baseline().to_jax())
            self._jaxpr_cache[key] = jax.make_jaxpr(
                fn.__wrapped__ if hasattr(fn, "__wrapped__") else fn)(*args)
        return self._jaxpr_cache[key]

    def _get_step(self, applied: AppliedPolicy) -> Callable:
        fn = self._step_cache.get(applied.fingerprint)
        if fn is None:
            fn = self.step_builder(applied.to_jax())
            self._step_cache[applied.fingerprint] = fn
        return fn

    # -------------------------------------------------------------- setup
    def prepare(self, example_args: tuple) -> AppliedPolicy:
        """WarmUp entry: proactive Algo-3 fit so the first iterations never
        OOM while profiling data accumulates.  With a policy store attached
        the observed program is fingerprinted first: a reuse-tier hit
        applies the cached policy directly (no WarmUp wait, no GenPolicy),
        a warm-start hit seeds the upcoming variant search."""
        self._example_args = example_args
        if not self.cfg.enabled:
            return self.applied
        self.service.begin(self.step_idx)
        with obs.tracer().span(obs.LANE_ADAPT, "prepare", arg=self.step_idx):
            key = ("baseline",) + self._args_key(example_args)
            cj = self._baseline_jaxpr(example_args)
            prof = self._baseprof_cache.get(key)
            if prof is None:
                prof = profile_jaxpr(cj, t_iter=1.0)  # timing unknown
                self._baseprof_cache[key] = prof      # pre-run; memory-only
            self.baseline_profile = prof              # warm-up fit
            tl = build_timeline(prof)
            if self.store is not None and self._try_policystore(prof, tl):
                return self.applied            # reuse tier: cached policy
            if tl.peak > self.budget:
                try:
                    sites = warmup_offload_sites(prof, self.cfg, self.budget)
                    self._install(AppliedPolicy(
                        None, sites,
                        self.executor.site_universe(prof) - sites, set(),
                        "warmup:" + ",".join(sorted(sites))), "prepare")
                    kind = "warmup"
                except ChameleonOOMError:
                    self._install(self.executor.conservative(prof), "prepare")
                    kind = "conservative"
            else:
                self._install(self.executor.baseline(), "prepare")
                kind = "baseline"
            self._audit_apply(kind)
        return self.applied

    def _install(self, applied: AppliedPolicy, reason: str) -> None:
        """Make ``applied`` the policy the next iteration runs.  A change
        of fingerprint is a ``policy.install`` instant on the adapt lane,
        with ``(reason, pipelined)`` as its arg, and counts in
        ``policy_installs`` (and in ``offload_pipelined_installs`` when the
        layer stack is handed an offload set to pipeline)."""
        if applied.fingerprint != self.applied.fingerprint:
            obs.tracer().instant(obs.LANE_ADAPT, "policy.install",
                                 arg=(reason, applied.pipelined))
            obs.metrics().counter("policy_installs")
            if applied.pipelined:
                obs.metrics().counter("offload_pipelined_installs")
        self.applied = applied

    def _audit_apply(self, kind: str, knob: Optional[float] = None) -> None:
        """Audit-log the policy taking effect (repro.obs drift trail)."""
        if self.ladder is not None:
            # a fresh adaptation supersedes any ladder degradation: it is
            # the new rung-0 policy, and if the link is still bad the
            # mirror traffic re-degrades health and the ladder re-descends
            self._full_applied = self.applied
            self.ladder.reset(self.step_idx, "new-policy")
        obs.audit().event(
            "policy.apply", policy_kind=kind, step=self.step_idx,
            policy=self.applied.fingerprint[:48], knob=knob,
            n_offload=len(self.applied.offload),
            release_plan=len(self.applied.release_plan))

    # ------------------------------------------- policystore (repro.policystore)
    def _try_policystore(self, prof: ProfileData, tl) -> bool:
        """Classify the observed program against the store (pipeline code)
        and *install* the outcome (runtime's job).  Returns True when a
        reuse-tier hit applied a cached policy (callers skip the WarmUp
        fit); warm-start/regen configure the variant search and return
        False."""
        fp, decision = self.pipeline.classify(
            prof, self.budget,
            bwmodel=self.hostmem.bwmodel if self.hostmem else None)
        if decision.tier is Tier.REUSE:
            # identity must be a hash test, not a float threshold: blended
            # similarity is capped below 1.0 for unequal hashes, but hash
            # equality is the authoritative check either way
            rec = decision.record
            exact = rec is not None and fp.exact in (
                rec.prepare_fingerprint.exact, rec.fingerprint.exact)
            hit = self.pipeline.apply_cached(rec, prof, tl, self.budget,
                                             exact_hit=exact)
            if hit is not None:
                self._last_decision = decision
                self._install(hit.applied, "prepare")
                if hit.profile is not None:
                    # the schedule remapped: engine feedback follows it
                    self.profile = hit.profile
                    if self.hostmem is not None:
                        self.executor.bind_release_points(
                            self.applied, self.hostmem.engine)
                        self.hostmem.engine.begin_iteration()
                self.store.touch(rec)
                self.machine.force_stable(self.step_idx, "policystore-reuse")
                self.machine.n_genpolicy = None
                self._gen_knobs = VARIANT_KNOBS
                self._audit_apply("reuse", knob=rec.knob if rec else None)
                self._finish_adaptation("reuse")
                return True
            decision = self.drift.demote(decision, "match-miss")
        self._last_decision = decision
        self._gen_knobs = self.pipeline.warm_knobs(decision)
        self.machine.n_genpolicy = (len(self._gen_knobs) - 1
                                    if self._gen_knobs != VARIANT_KNOBS
                                    else None)
        return False

    def _store_result(self) -> None:
        """Write the adaptation winner back to the store, keyed by the
        profiled train-step stream (cold-start exact hit) and carrying the
        full iteration signature (mid-run drift similarity)."""
        if self.store is None or self.best is None or self.profile is None:
            return
        iter_fp = None
        if self._last_sig is not None and len(self._last_sig):
            # virtual-length-aware: capped scan materializations must not
            # collapse different layer counts into one iteration key
            iter_fp = self.pipeline.iteration_fingerprint(self._last_sig)
        rec = self.pipeline.build_record(
            self.best, self.profile, self.budget, iter_fp=iter_fp,
            bwmodel=self.hostmem.bwmodel if self.hostmem else None)
        self.store.put(rec)
        obs.audit().event(
            "policy.store_put", key=rec.key[:12],
            policy_kind=rec.policy_kind, knob=self.best.knob,
            measured_t=round(self.best.measured_t or 0.0, 6),
            step=self.step_idx)

    def _finish_adaptation(self, tier: str) -> None:
        """Close the adaptation-latency window opened by ``prepare``."""
        self.service.finish(tier, self.step_idx)

    # ------------------------------------------------------ per-iteration
    def step_fn(self) -> Callable:
        return self._get_step(self.applied)

    def record_dispatch(self, name: str, fn: Callable, args: tuple) -> None:
        """Lightweight mode: token stream of this dispatch (trace cached by
        arg shapes, so steady-state cost is a dict lookup + append)."""
        t0 = time.perf_counter()
        with obs.tracer().span(obs.LANE_HOST, "runtime.record_dispatch",
                               arg=name):
            key = (name, self.applied.fingerprint) + self._args_key(args)
            toks = self._trace_cache.get(key)
            if toks is None:
                import jax
                try:
                    traced = fn.trace(*args)          # jitted fn
                    cj = traced.jaxpr
                except AttributeError:
                    cj = jax.make_jaxpr(fn)(*args)
                toks = tokenizer.tokenize_jaxpr_stream(cj)
                self._trace_cache[key] = toks
            self._iter_streams.append(toks)
            if name == "train":
                self._last_train_args = args
                self._train_shape = key[2:]           # arg shapes/dtypes only
        self.profiling_overhead_s += time.perf_counter() - t0

    def end_iteration(self, t_iter: float) -> Stage:
        t0 = time.perf_counter()
        tracer = obs.tracer()
        with tracer.span(obs.LANE_HOST, "runtime.end_iteration",
                         arg=self.step_idx):
            # the policy that *this* iteration executed — _genpolicy_step /
            # _select_best may replace self.applied for the next one below
            ran = self.applied
            with tracer.span(obs.LANE_HOST, "runtime.signature"):
                stage, prev_stage, shape_drift = self._observe_iteration(
                    t_iter)

            # episodic adaptation work (Detailed profiling, variant
            # selection, policystore write/lookup, re-prepare) is accounted
            # separately from the steady-state Lightweight-mode
            # bookkeeping: the paper's Table-1 overhead claim is
            # per-iteration, adaptation is what benchmarks/adapt_bench.py
            # measures
            t_adapt = time.perf_counter()
            with tracer.span(obs.LANE_HOST, "runtime.adapt"):
                self._stage_work(stage, prev_stage, shape_drift, t_iter)
            adapt_dt = time.perf_counter() - t_adapt
            self.adaptation_overhead_s += adapt_dt
            # §5.4.2 execution feedback for the policy that just ran:
            # mirror its swap schedule through the engine (real
            # policy_swap-class copies, released by advance_op at each
            # promised op), then sweep any remaining planned swap-outs —
            # the iteration's op stream has fully executed, so every
            # promised release point has passed — and reset the op cursor
            # for the next iteration.
            if self.hostmem is not None and ran.release_plan:
                with tracer.span(obs.LANE_HOST, "runtime.mirror"):
                    self._mirror_policy_swaps(ran)
                    eng = self.hostmem.engine
                    eng.advance_op(max(ran.release_plan.values()))
                    eng.begin_iteration()
            # async swap-in point: only *after* the executed policy's
            # engine feedback drained may a worker result replace
            # self.applied — the iteration boundary the swap-in protocol
            # promises
            if self.machine.stage is Stage.ADAPTING:
                t_install = time.perf_counter()
                with tracer.span(obs.LANE_HOST, "runtime.install"):
                    self._poll_adaptation()
                self.adaptation_overhead_s += time.perf_counter() - t_install
            # degradation ladder (repro.faults): react to link health after
            # this iteration's engine feedback; GenPolicy iterations are
            # skipped — the variant search overwrites self.applied anyway
            # and _select_best's install resets the ladder
            if self.ladder is not None and stage is not Stage.GENPOLICY:
                t_ladder = time.perf_counter()
                with tracer.span(obs.LANE_HOST, "runtime.ladder"):
                    self._ladder_step()
                self.adaptation_overhead_s += time.perf_counter() - t_ladder
            self.history.append({"step": self.step_idx, "stage": stage.value,
                                 "policy": self.applied.fingerprint,
                                 "t_iter": t_iter})
            with tracer.span(obs.LANE_HOST, "runtime.obs_close"):
                self._close_obs_window(ran)
        self.profiling_overhead_s += (time.perf_counter() - t0) - adapt_dt
        return stage

    def _observe_iteration(self, t_iter: float
                           ) -> Tuple[Stage, Stage, bool]:
        """Fold the iteration's dispatches into its signature and step the
        stage machine; returns (stage, previous stage, shape drift)."""
        sig = self._sig_acc.update(self._iter_streams)
        self._iter_streams = []
        self._last_sig = sig
        prev_stage = self.machine.stage
        stage = self.machine.observe(sig, self.step_idx)
        # shape drift (same op stream, different shapes -> different memory
        # profile): Algo 1 cannot see it, so re-enter WarmUp ourselves; the
        # policystore keys buckets separately (per-site byte aggregates) so
        # a recurring bucket reuses its own cached policy
        shape_drift = (self.cfg.enabled
                       and self._prev_train_shape is not None
                       and self._train_shape is not None
                       and self._train_shape != self._prev_train_shape)
        if shape_drift and stage is not Stage.WARMUP:
            stage = self.machine.to_warmup(self.step_idx, "shape-change")
        self._prev_train_shape = self._train_shape
        self.step_idx += 1

        # a variant ran this iteration: record its measured time
        if self._pending_variant is not None:
            self._pending_variant.measured_t = t_iter
            self._pending_variant = None
        return stage, prev_stage, shape_drift

    def _stage_work(self, stage: Stage, prev_stage: Stage,
                    shape_drift: bool, t_iter: float) -> None:
        """The adaptation work the stage transition asks for."""
        if stage is Stage.GENPOLICY:
            self._genpolicy_step(t_iter)
        elif stage is Stage.STABLE and prev_stage is Stage.GENPOLICY:
            self._select_best()
        elif stage is Stage.ADAPTING and prev_stage is not Stage.ADAPTING:
            # async placement: the sequence settled — hand the background
            # worker an immutable snapshot (or install a parked
            # speculative result on the spot) and keep iterating
            self._async_kickoff(t_iter)
        elif stage is Stage.WARMUP and (prev_stage is not Stage.WARMUP
                                        or shape_drift):
            # sequence (or dispatch shape) changed: back to the
            # conservative fit (Fig 2 loop) — shape drift re-prepares even
            # when observe() left the machine in/through WarmUp this step
            self.service.reset_search()
            if self.machine.async_mode:
                # supersede anything in flight for the old stream
                self.service.invalidate("shape-drift" if shape_drift
                                        else "seq-change")
            if self._example_args is not None:
                args = getattr(self, "_last_train_args", self._example_args)
                if not self.machine.async_mode:
                    # inline (reference mode): re-trace + re-profile from
                    # scratch, as the paper's loop does.  Async keeps the
                    # shape-keyed caches so a recurring bucket's re-entry
                    # costs a dict hit, not a trace — genuinely new
                    # shapes miss the key and still pay once.
                    self._jaxpr_cache.clear()
                    self._baseprof_cache.clear()
                self.prepare(args)

    def _poll_adaptation(self) -> None:
        """Install a finished background adaptation, or trip the watchdog
        on a hung one."""
        res = self.service.poll()
        if res is not None:
            self._install_result(res, "adapt-installed")
        elif self.service.watchdog(self.cfg.resilience.adapt_timeout_s):
            # hung or lost worker: supersede its epoch (a late result can
            # never install) and un-wedge the stage machine — the current
            # policy keeps serving, which is safe by construction (it fit
            # before the drift)
            self.service.invalidate("worker-timeout")
            self.machine.complete_adapting(self.step_idx, "adapt-timeout")
            self._finish_adaptation("timeout")

    def _close_obs_window(self, ran: Optional[AppliedPolicy] = None) -> None:
        """Per-iteration overlap efficiency: how much of this window's
        engine transfer time was hidden under compute spans (after the
        mirror swaps above, so the applied policy's traffic counts).
        Then close the memory ledger's window for the policy that ran:
        realized-peak replay, the predicted-vs-realized scoreboard, byte
        conservation, and budget-headroom feedback into the health FSM."""
        t1 = time.perf_counter()
        eff, transfer_s, hidden_s = obs.window_efficiency(
            obs.tracer(), self._iter_t0, t1)
        if transfer_s > 0.0:
            self.overlap_history.append({
                "step": self.step_idx, "t": t1,
                "efficiency": eff, "transfer_s": transfer_s,
                "hidden_s": hidden_s})
            obs.metrics().gauge("overlap_efficiency", eff, t=t1)
        obs.metrics().counter("iterations")
        rec = obs.ledger().close_iteration(
            self.step_idx,
            profile=self.profile or self.baseline_profile,
            swap=ran.swap if ran is not None else None,
            budget=self.budget,
            pool_stats=(self.hostmem.pool.stats()
                        if self.hostmem is not None else None),
            t=t1)
        self._memledger_feedback(rec)
        self._iter_t0 = t1
        obs.tracer().set_iteration(self.step_idx)

    def _memledger_feedback(self, rec: dict) -> None:
        """Ledger → health FSM: sustained margin erosion (realized peak
        above plan with the budget headroom nearly gone) degrades the
        ``memory`` pseudo-class, so the ladder backs the policy off
        *before* an OOM.  On a clean run realized == projected and the
        class decays back to healthy like any link."""
        if self.hostmem is None or self.ladder is None:
            return
        health = self.hostmem.engine.health
        if MEM_CLASS not in health.links:
            return
        headroom, error = rec.get("headroom_frac"), rec.get("peak_error")
        if headroom is None or error is None:
            # nothing scored (warmup / conservative rung: no swap plan to
            # compare against) — counts as a comfortable iteration
            health.note_success(MEM_CLASS)
            return
        severe = headroom < 0.0
        mild = (error > 0.0
                and headroom < self.cfg.resilience.headroom_degrade_frac)
        if severe or mild:
            health.note_pressure(MEM_CLASS, severe=severe)
            obs.audit().event("memory.pressure", step=rec["step"],
                              severe=severe, headroom=round(headroom, 4),
                              error=round(error, 4))
        else:
            health.note_success(MEM_CLASS)

    # --------------------------------------- §5.4.2 applied-swap traffic
    def _mirror_policy_swaps(self, applied: AppliedPolicy) -> None:
        """Route the executed policy's swap schedule through the host tier
        as real policy_swap-class copies: each entry's D2H is retired by
        ``advance_op`` at its simulator-promised release op (dropping the
        source reference there, not at first reuse), then swapped back in
        at its planned swap-in point, recycling the slabs.  This is the
        engine-visible form of the swap traffic XLA executes inside the
        compiled step; it keeps per-class counters and the bandwidth
        curve fed by the *applied* policy, capped per iteration by
        ``HostMemConfig.mirror_swap_bytes``."""
        swap = applied.swap
        cap = self.cfg.hostmem.mirror_swap_bytes
        if swap is None or not cap or not swap.entries:
            return
        eng = self.hostmem.engine
        budget = cap
        picked = []
        for e in sorted(swap.entries, key=lambda e: e.birth):
            if e.nbytes <= 0 or e.nbytes > budget:
                continue
            budget -= e.nbytes
            picked.append(e)
        if not picked:
            return
        # the schedule is in flight all at once — widen the window so
        # copies retire at their promised ops, not by overflow
        eng.set_class_depth("policy_swap", len(picked) + 2)
        biggest = max(e.nbytes for e in picked)
        if self._mirror_src is None or self._mirror_src.nbytes < biggest:
            self._mirror_src = np.zeros(biggest, np.uint8)
        outs = [(e, eng.submit_swap_out(self._mirror_src[:e.nbytes],
                                        SwapPolicy.entry_tag(e)))
                for e in picked]
        for e, _ in sorted(outs, key=lambda t: t[0].swap_out_done_op):
            eng.advance_op(e.swap_out_done_op)      # promised release point
        for e, ev in sorted(outs, key=lambda t: t[0].swap_in_op):
            eng.wait(eng.submit_swap_in(ev, SwapPolicy.entry_tag(e)))

    # ------------------------------------ degradation ladder (repro.faults)
    def _ladder_step(self) -> None:
        """Consult link health and move the applied policy along the
        ladder (full → trimmed → conservative → no_swap and back)."""
        lad = self.ladder
        eng = self.hostmem.engine
        if lad.should_probe(self.step_idx):
            self._health_probe(eng)
        move = lad.decide(eng.health.worst(), self.step_idx)
        if move is not None:
            self._apply_rung(move)

    def _health_probe(self, eng) -> None:
        """Small round-trip copies through the engine: at a reduced rung
        the applied policy may generate no link traffic at all, so these
        probes are what feeds the health machine's recovery streak (and,
        on a still-bad link, its error score)."""
        rs = self.cfg.resilience
        if self._probe_src is None:
            self._probe_src = np.zeros(max(rs.probe_bytes, 1), np.uint8)
        ok = 0
        for _ in range(max(rs.probe_burst, 1)):
            try:
                ev = eng.wait(eng.submit_swap_out(self._probe_src,
                                                  "health_probe"))
                if ev.failed:
                    continue             # failure already fed health
                eng.wait(eng.submit_swap_in(ev, "health_probe"))
                ok += 1
            except Exception:  # noqa: BLE001 — probes must never raise
                pass
        obs.audit().event("ladder.probe", step=self.step_idx,
                          rung=self.ladder.name, ok=ok,
                          burst=max(rs.probe_burst, 1),
                          health=self.hostmem.engine.health.worst())

    def _apply_rung(self, rung: int) -> None:
        """Rebuild ``self.applied`` for the rung the ladder moved to.
        Rungs that cannot be built from available state fall through to
        the next more conservative one."""
        prof = self.profile or self.baseline_profile
        applied: Optional[AppliedPolicy] = None
        if rung == RUNG_FULL:
            applied = self._full_applied or self.applied
        elif rung == RUNG_TRIMMED:
            full = self._full_applied or self.applied
            if prof is not None and full is not None and full.swap is not None:
                kept = trim_swap(prof, full.swap, self.budget,
                                 self.cfg.resilience.trim_drop_fraction)
                if kept is not None:
                    swap = SwapPolicy(
                        kept, projected_peak(prof, kept),
                        full.swap.baseline_peak, full.swap.budget,
                        full.swap.stall_time, full.swap.t_iter,
                        full.swap.n_ops,
                        contention_s=full.swap.contention_s,
                        occupancy=getattr(full.swap, "occupancy", 0.0))
                    applied = self.executor.lower(swap, prof)
        if applied is None and rung in (RUNG_TRIMMED, RUNG_CONSERVATIVE):
            # conservative WarmUp rung: the Algo-3 passive fit — no
            # per-tensor schedule, no release plan, guaranteed to fit
            if prof is not None:
                try:
                    sites = warmup_offload_sites(prof, self.cfg, self.budget)
                    applied = AppliedPolicy(
                        None, sites,
                        self.executor.site_universe(prof) - sites, set(),
                        "ladder-warmup:" + ",".join(sorted(sites)))
                except ChameleonOOMError:
                    applied = self.executor.conservative(prof)
            else:
                applied = self.executor.conservative(None)
        if applied is None:              # RUNG_NO_SWAP (or nothing else)
            applied = self.executor.baseline()
        self._install(applied, "ladder")
        self.executor.bind_release_points(applied, self.hostmem.engine)
        self.hostmem.engine.begin_iteration()
        obs.audit().event(
            "ladder.apply", step=self.step_idx, rung=RUNG_NAMES[rung],
            policy=applied.fingerprint[:48],
            swap_entries=(len(applied.swap.entries) if applied.swap else 0),
            release_plan=len(applied.release_plan))

    # ----------------------------------------------------- GenPolicy path
    def _genpolicy_step(self, t_iter: float) -> None:
        args = getattr(self, "_last_train_args", self._example_args)
        if args is None:
            return
        knob_next = self._gen_knobs[len(self.variants) % len(self._gen_knobs)]
        with obs.tracer().span(obs.LANE_ADAPT, "genpolicy_step",
                               arg=knob_next):
            self._genpolicy_step_body(args, t_iter)

    def _genpolicy_step_body(self, args, t_iter: float) -> None:
        cj = self._baseline_jaxpr(args)
        prof = profile_jaxpr(cj, t_iter=t_iter)   # Detailed mode
        self.profile = prof
        knob = self._gen_knobs[len(self.variants) % len(self._gen_knobs)]
        hm = self.hostmem
        # bwmodel prices transfer sizes and the engine prices the live
        # per-class link backlog for every variant; free-times are handed
        # to the engine only for the variant that wins (_select_best)
        var = self.pipeline.variant(prof, knob, self.budget,
                                    bwmodel=hm.bwmodel if hm else None,
                                    engine=hm.engine if hm else None)
        self.variants.append(var)
        self._pending_variant = var
        self._install(var.applied, "genpolicy")    # next iteration runs it

    def _select_best(self) -> None:
        with obs.tracer().span(obs.LANE_ADAPT, "select_best",
                               arg=len(self.variants)):
            timed = [v for v in self.variants if v.measured_t is not None]
            if timed:
                self._select_best_timed(timed)
                self._audit_apply("genpolicy", knob=self.best.knob)
            tier = (self._last_decision.tier.value
                    if self._last_decision is not None else Tier.REGEN.value)
            self._finish_adaptation(tier)
            self._last_decision = None
            self._gen_knobs = VARIANT_KNOBS    # next adaptation starts cold
            self.machine.n_genpolicy = None
            if timed:
                self._store_result()

    def _select_best_timed(self, timed: List[PolicyVariant]) -> None:
        self.best = min(timed, key=lambda v: v.measured_t)
        self._install(self.best.applied, "select_best")
        if self.hostmem is not None and self.best.swap is not None:
            # §5.4.2 hand-off: only the applied policy's release points
            # reach the engine; end_iteration drives engine.advance_op
            # over them so swapped buffers are freed at the promised op
            # instead of at first reuse.  (Rebuilt here rather than
            # trusted from Executor.lower: variants may carry an
            # applied policy constructed elsewhere.)
            self.applied.release_plan = {
                SwapPolicy.entry_tag(e): e.swap_out_done_op
                for e in self.best.swap.entries
                if e.swap_out_done_op >= 0}
            self.executor.bind_release_points(self.applied,
                                              self.hostmem.engine)
            self.hostmem.engine.begin_iteration()

    # ------------------------------------------ async placement (repro.adapt)
    def _snapshot(self, args, t_iter: float) -> AdaptSnapshot:
        """Freeze this adaptation's inputs.  Tracing stays on the training
        thread (and is cached for recurring streams); the worker only pays
        the profile traversal — never a concurrent jax trace."""
        cj = self._baseline_jaxpr(args)
        hm = self.hostmem
        iter_fp = None
        if self._last_sig is not None and len(self._last_sig):
            iter_fp = self.pipeline.iteration_fingerprint(self._last_sig)
        cached_prof = (self._profile_lru.get(iter_fp.exact)
                       if iter_fp is not None else None)
        return AdaptSnapshot(
            jaxpr=cj, t_iter=t_iter, budget=self.budget,
            bwmodel=hm.bwmodel.snapshot() if hm else None,
            contention_s=hm.engine.queued_delay() if hm else 0.0,
            backlog=hm.engine.backlog_snapshot() if hm else {},
            gen_knobs=(),                  # worker classifies + seeds itself
            iter_exact=iter_fp.exact if iter_fp is not None else None,
            iter_fp=iter_fp, step=self.step_idx, profile=cached_prof)

    def _async_kickoff(self, t_iter: float) -> None:
        """ADAPTING entry: install a parked speculative result if the
        observed stream has one (zero inline GenPolicy steps, nothing in
        flight), otherwise enqueue the snapshot for the worker."""
        args = getattr(self, "_last_train_args", self._example_args)
        if args is None:
            return
        snap = self._snapshot(args, t_iter)
        self.service.begin(self.step_idx)
        hit = self.service.take_speculative(snap.iter_exact)
        if hit is not None:
            self._install_result(hit, "speculative-hit")
            return
        self.service.submit(snap)

    def _install_result(self, res: AdaptResult, why: str) -> None:
        """Swap-in: adopt a completed (worker or parked speculative)
        adaptation at the iteration boundary.  Mirrors the inline
        ``_select_best_timed`` install — applied policy, engine release
        points, stage transition, accounting."""
        self._install(res.applied, why)
        if res.profile is not None:
            self.profile = res.profile
            if res.iter_exact:           # recurrences skip worker profiling
                self._profile_lru[res.iter_exact] = res.profile
                self._profile_lru.move_to_end(res.iter_exact)
                while len(self._profile_lru) > self._profile_lru_cap:
                    self._profile_lru.popitem(last=False)
        self.best = PolicyVariant(res.applied, res.swap,
                                  res.knob if res.knob is not None else 1.0,
                                  measured_t=None)
        if self.hostmem is not None and res.swap is not None:
            self.applied.release_plan = {
                SwapPolicy.entry_tag(e): e.swap_out_done_op
                for e in res.swap.entries if e.swap_out_done_op >= 0}
            self.executor.bind_release_points(self.applied,
                                              self.hostmem.engine)
            self.hostmem.engine.begin_iteration()
        self.machine.complete_adapting(self.step_idx, why)
        self.machine.n_genpolicy = None
        self._gen_knobs = VARIANT_KNOBS
        self._audit_apply(res.kind, knob=res.knob)
        self.service.note_adapted(res.iter_exact)
        self.service.finish(res.tier, self.step_idx)
        self._last_decision = None

    def close(self) -> None:
        """Stop the background worker (no-op for inline placement)."""
        self.service.close()

    # ----------------------------------------------------------- reports
    def stats(self) -> dict:
        return {
            "stage": self.machine.stage.value,
            "transitions": list(self.machine.transitions),
            "n_variants": len(self.variants),
            "best_knob": self.best.knob if self.best else None,
            "applied": self.applied.fingerprint,
            "release_plan": len(self.applied.release_plan),
            "contention_s": (self.best.swap.contention_s
                             if self.best and self.best.swap else 0.0),
            "profiling_overhead_s": self.profiling_overhead_s,
            "adaptation_overhead_s": self.adaptation_overhead_s,
            "ladder": self.ladder.stats() if self.ladder else None,
            "signature": self._sig_acc.stats(),
            "hostmem": self.hostmem.stats() if self.hostmem else None,
            "policystore": self.policystore_stats(),
            "adapt": self.service.stats(),
            "obs": self.obs_stats(),
        }

    def obs_stats(self) -> dict:
        """Tracing/overlap summary (repro.obs).  ``overlap`` aggregates the
        per-iteration swap/compute overlap-efficiency history; iterations
        with no engine traffic are excluded (``measured`` counts the ones
        that had transfers, ``iterations`` every closed window)."""
        effs = [h["efficiency"] for h in self.overlap_history
                if h["efficiency"] is not None]
        return {
            "overlap": {
                "last": effs[-1] if effs else None,
                "mean": float(np.mean(effs)) if effs else None,
                "measured": len(effs),
                "iterations": self.step_idx,
                "transfer_s": float(sum(h["transfer_s"]
                                        for h in self.overlap_history)),
                "hidden_s": float(sum(h["hidden_s"]
                                      for h in self.overlap_history)),
            },
            "tracer": obs.tracer().stats(),
            "audit": obs.audit().counts(),
            "memory": obs.ledger().stats(),
        }

    def policystore_stats(self) -> Optional[dict]:
        """Per-tier hit counters, store state, and adaptation latencies."""
        if self.store is None:
            return None
        gp = sum(1 for h in self.history if h["stage"] == Stage.GENPOLICY.value)
        return {
            "store": self.store.stats(),
            "tiers": self.drift.stats(),
            "adaptations": list(self.adaptations),
            "genpolicy_steps_total": gp,
        }

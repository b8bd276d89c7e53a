"""Trainer — eager-style dispatch loop with the Chameleon runtime in-line.

Faithful to the paper's setting: each iteration dispatches *separate* jitted
programs (grad step; optimizer step only when gradients are finite; optional
on-the-fly validation), so the per-iteration operator sequence genuinely
varies — loss-scale skips shorten it, eval extends it — and the Chameleon
runtime tracks it exactly as §4 describes.

Fault tolerance: async sharded checkpoints on a cadence, emergency
checkpoint on exception, ``resume()`` from the latest step, straggler
detection on step times.  One device: a mesh is refused, not ignored.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import faults, obs
from repro.checkpointing.manager import CheckpointManager
from repro.common.config import (AdaptConfig, ChameleonConfig, ModelConfig,
                                 TrainConfig)
from repro.core.runtime import ChameleonRuntime
from repro.data.synthetic import SyntheticTokens
from repro.distributed import steps as S
from repro.models.registry import get_api
from repro.optim.adamw import adamw_init
from repro.optim.loss_scale import (LossScaleState, init_loss_scale,
                                    update_loss_scale)
from repro.runtime.straggler import StragglerDetector


@dataclass
class TrainReport:
    losses: List[float] = field(default_factory=list)
    times: List[float] = field(default_factory=list)
    # full critical-path latency per step: ``times`` plus the
    # ``end_iteration`` bookkeeping/adaptation that runs before the next
    # dispatch — what a drift stall actually costs wall-clock
    wall_times: List[float] = field(default_factory=list)
    skipped_steps: List[int] = field(default_factory=list)
    eval_losses: Dict[int, float] = field(default_factory=dict)
    stages: List[str] = field(default_factory=list)
    checkpoints: List[str] = field(default_factory=list)
    failures: List[str] = field(default_factory=list)
    # repro.policystore: per-tier hit counters + adaptation latencies
    # (None when the runtime has no store attached)
    policystore: Optional[dict] = None
    # repro.adapt: service counters (jobs/published/discarded/failed/
    # installed/speculative) — populated by train() for every mode
    adapt: Optional[dict] = None

    @property
    def genpolicy_steps(self) -> int:
        return sum(1 for s in self.stages if s == "GenPolicy")


class Trainer:
    def __init__(self, cfg: ModelConfig, tcfg: TrainConfig,
                 cham: Optional[ChameleonConfig] = None,
                 mesh=None, data: Optional[SyntheticTokens] = None,
                 eval_data: Optional[SyntheticTokens] = None,
                 metrics_out: Optional[str] = None,
                 metrics_every: int = 25,
                 adapt_mode: Optional[str] = None):
        self.cfg, self.tcfg = cfg, tcfg
        self.cham = cham or ChameleonConfig(enabled=False)
        if adapt_mode is not None and adapt_mode != self.cham.adapt.mode:
            # placement override (--adapt-mode): inline keeps the paper's
            # measured GenPolicy iterations; async/speculative move the
            # variant search onto the repro.adapt background worker
            self.cham = dataclasses.replace(
                self.cham,
                adapt=dataclasses.replace(self.cham.adapt, mode=adapt_mode))
        if mesh is not None:
            # nothing below shards the params, optimizer state or batch: a
            # mesh would be accepted and silently ignored
            raise NotImplementedError(
                "Trainer runs on one device; sharded training state is not "
                "implemented")
        self.api = get_api(cfg)
        self.data = data or SyntheticTokens(cfg.vocab_size, 128, 8,
                                            seed=tcfg.seed)
        self.eval_data = eval_data or SyntheticTokens(
            cfg.vocab_size, self.data.seq_len, self.data.global_batch,
            seed=tcfg.seed + 1)

        def init_state(key):
            params = self.api.init(cfg, key)[0]
            return params, adamw_init(params)

        # one program lays the whole state out in HBM at once; eager init
        # runs hundreds of small ops whose freed temporaries fragment it
        self.params, self.opt_state = jax.jit(init_state)(
            jax.random.PRNGKey(tcfg.seed))
        self.loss_scale = init_loss_scale(tcfg.loss_scale)
        self.step = 0
        self.straggler = StragglerDetector(on_straggler=self._on_straggler)
        self.report = TrainReport()

        def step_builder(policy):
            return jax.jit(S.make_grad_step(cfg, tcfg, policy))

        self.rt = ChameleonRuntime(self.cham, step_builder)
        # checkpoint drains share the host link with policy swaps: route
        # them through the engine's lowest-priority checkpoint stream so
        # swap traffic preempts the drain instead of queueing behind it
        # resilience posture: a lost async checkpoint write degrades (one
        # fewer restore point, audited) instead of killing the train loop
        self.ckpt = CheckpointManager(
            tcfg.checkpoint_dir, keep=tcfg.keep_checkpoints,
            engine=self.rt.hostmem.engine if self.rt.hostmem else None,
            on_error="degrade" if self.cham.resilience.enabled else "raise")
        self._apply = S.jit_apply_step(cfg, tcfg)
        self._eval = jax.jit(S.make_eval_step(cfg))
        self._prepared = False
        # repro.obs: scattered stats() dicts register as lazy providers so
        # one registry snapshot carries the whole picture; with metrics_out
        # set, a JSONL snapshot is appended every metrics_every steps
        self.metrics_out = metrics_out
        self.metrics_every = max(1, int(metrics_every))
        reg = obs.metrics()
        if self.rt.hostmem is not None:
            reg.register_provider("hostmem", self.rt.hostmem.stats)
        reg.register_provider("runtime", self._runtime_provider)
        # via a lambda: set_ledger may swap the default between snapshots
        reg.register_provider("memory", lambda: obs.ledger().stats())

    def _on_straggler(self, ev) -> None:
        """Mitigation hook: structured evidence for the orchestrator."""
        obs.audit().event("straggler.flagged", step=ev.step, host=ev.host,
                          wall=round(ev.t, 6), mean=round(ev.mean, 6),
                          std=round(ev.std, 6))
        obs.metrics().counter("straggler_flagged")

    def _runtime_provider(self) -> dict:
        return {
            "step": self.step,
            "stage": self.rt.machine.stage.value,
            "profiling_overhead_s": self.rt.profiling_overhead_s,
            "adaptation_overhead_s": self.rt.adaptation_overhead_s,
            "adaptations": len(self.rt.adaptations),
            "adapt": self.rt.service.stats(),
        }

    # ------------------------------------------------------------- utils
    def _device_batch(self, batch: Dict[str, np.ndarray]):
        out = {k: jnp.asarray(v) for k, v in batch.items()}
        if self.cfg.family == "vlm":
            B = out["tokens"].shape[0]
            out["memory"] = jnp.zeros((B, self.cfg.image_tokens,
                                       self.cfg.d_model),
                                      jnp.dtype(self.cfg.dtype))
        if self.cfg.family == "encdec":
            B = out["tokens"].shape[0]
            out["memory"] = jnp.zeros((B, self.cfg.encoder_seq,
                                       self.cfg.d_model),
                                      jnp.dtype(self.cfg.dtype))
        return out

    # ------------------------------------------------------------ resume
    def resume(self) -> bool:
        latest = self.ckpt.latest_step()
        if latest is None:
            return False
        restored, extra = self.ckpt.restore(
            latest, {"params": self.params, "opt": self.opt_state})
        self.params = restored["params"]
        self.opt_state = restored["opt"]
        self.step = int(extra["step"])
        self.loss_scale = LossScaleState(
            jnp.float32(extra["loss_scale"]), jnp.int32(extra["growth"]))
        self.data.restore(extra["data"])
        return True

    def _checkpoint(self, block: bool = False):
        path = self.ckpt.save(
            self.step,
            {"params": self.params, "opt": self.opt_state},
            extra={"step": self.step,
                   "loss_scale": float(self.loss_scale.scale),
                   "growth": int(self.loss_scale.growth_count),
                   "data": self.data.state()},
            block=block)
        self.report.checkpoints.append(path)

    # -------------------------------------------------------------- train
    def train(self, steps: Optional[int] = None,
              fault_hook: Optional[Callable[[int], None]] = None
              ) -> TrainReport:
        """Train ``steps`` iterations (default ``tcfg.steps``) under one
        ``trainer.train`` span, the root of their host-span tree."""
        tracer = obs.tracer()
        with tracer.span(obs.LANE_HOST, "trainer.train", arg=steps):
            steps = steps if steps is not None else self.tcfg.steps
            with tracer.span(obs.LANE_HOST, "trainer.data"):
                batch = self._device_batch(self.data.get())
            if not self._prepared:
                self.rt.prepare((self.params, batch, self.loss_scale.scale))
                self._prepared = True
            end = self.step + steps
            while self.step < end:
                try:
                    self._one_step(batch, fault_hook)
                    with tracer.span(obs.LANE_HOST, "trainer.data"):
                        batch = self._device_batch(self.data.get())
                except (KeyboardInterrupt, Exception) as e:  # noqa: BLE001
                    self.report.failures.append(f"step {self.step}: {e!r}")
                    self.ckpt.wait()
                    self._checkpoint(block=True)   # emergency checkpoint
                    raise
            with tracer.span(obs.LANE_HOST, "trainer.finish"):
                self.ckpt.wait()
                self.report.policystore = self.rt.policystore_stats()
                self.report.adapt = self.rt.service.stats()
        return self.report

    def _one_step(self, batch, fault_hook=None):
        tracer = obs.tracer()
        faults.tick(self.step)   # armed fault plans key off the iteration
        t0 = time.perf_counter()
        fn = self.rt.step_fn()
        with tracer.span(obs.LANE_COMPUTE, "train_step", arg=self.step):
            with tracer.span(obs.LANE_HOST, "trainer.grad_dispatch"):
                loss, grads, finite = fn(self.params, batch,
                                         self.loss_scale.scale)
            with tracer.span(obs.LANE_HOST, "trainer.grad_wait"):
                jax.block_until_ready(loss)
        self.rt.record_dispatch("train", fn,
                                (self.params, batch, self.loss_scale.scale))
        # the optimizer step does not read the loss scale, so the scale's
        # update may run before it
        with tracer.span(obs.LANE_HOST, "trainer.loss_scale"):
            finite_h = bool(finite)
            self.loss_scale = update_loss_scale(self.loss_scale, finite_h)
        if finite_h:
            with tracer.span(obs.LANE_COMPUTE, "apply_step", arg=self.step):
                with tracer.span(obs.LANE_HOST, "trainer.apply_dispatch"):
                    self.params, self.opt_state, _m = self._apply(
                        self.params, self.opt_state, grads)
                with tracer.span(obs.LANE_HOST, "trainer.apply_wait"):
                    jax.block_until_ready(self.params)
            self.rt.record_dispatch("apply", self._apply,
                                    (self.params, self.opt_state, grads))
        else:
            self.report.skipped_steps.append(self.step)

        if (self.tcfg.eval_every
                and self.step > 0
                and self.step % self.tcfg.eval_every == 0):
            with tracer.span(obs.LANE_HOST, "trainer.data"):
                ebatch = self._device_batch(self.eval_data.next_batch())
            with tracer.span(obs.LANE_COMPUTE, "eval_step", arg=self.step):
                el = self._eval(self.params, ebatch)
                with tracer.span(obs.LANE_HOST, "trainer.eval_wait"):
                    jax.block_until_ready(el)
            self.rt.record_dispatch("eval", self._eval, (self.params, ebatch))
            self.report.eval_losses[self.step] = float(el)

        dt = time.perf_counter() - t0
        stage = self.rt.end_iteration(dt)
        with tracer.span(obs.LANE_HOST, "trainer.finish"):
            # flag on the full critical-path latency (compute +
            # end_iteration bookkeeping): a degraded host link or a drift
            # stall shows up in the wall time even when the jitted step
            # itself is healthy
            wall = time.perf_counter() - t0
            self.straggler.observe(self.step, wall)
            self.report.losses.append(float(loss))
            self.report.times.append(dt)
            self.report.wall_times.append(wall)
            self.report.stages.append(stage.value)
            self.step += 1
            # step is incremented BEFORE any failure can be raised for this
            # iteration: the emergency checkpoint then records post-step
            # state under step N+1 and resume does not replay an applied
            # update.
            if fault_hook is not None:
                fault_hook(self.step - 1)

            if (self.tcfg.checkpoint_every
                    and self.step % self.tcfg.checkpoint_every == 0):
                self._checkpoint()

            if self.metrics_out and self.step % self.metrics_every == 0:
                obs.metrics().write_jsonl(self.metrics_out)

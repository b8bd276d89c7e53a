"""One run of one benchmark cell on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

In one process on the chips the cell asks for:

1. JAX must report enough TPU chips; otherwise it exits 2 and prints no
   result (never a CPU fallback).
2. JAX's persistent compilation cache goes to the checkout's
   ``.jax_cache``.
3. Set-up builds the cell's ``Trainer`` (weights from ``--seed``, batches
   from ``bench.data``) with an HBM budget of ``bytes_limit`` less the
   AdamW state it holds in device memory less 2 GiB, and trains until the
   stage machine has reached Stable; then three more steps, whose losses,
   first gradient and change of the parameters the correctness check
   compares: the steps of the grad step that the window times.  A cell
   with an eval cadence then runs two whole eval cycles, so every program
   the window uses is compiled before it opens.
4. The window calls ``Trainer.train(1)`` until ``--seconds`` have passed
   (and, with an eval cadence, a cycle has closed).  With ``--trace 1`` the
   window is traced and the calls into each layer carry host spans.
5. After the window: the device memory peak, then the program's state is
   freed and the configuration's float32 reference runs every step from
   the seed again (``bench.check``).  The last line of stdout is one JSON
   object.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import collections
import dataclasses
import gc
import json
import math
import os
import shutil
import sys
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

if __name__ == "__main__":
    # Run as a script: import ``bench`` from the checkout's root, not from
    # this directory (whose ``trace.py`` would shadow the standard library's).
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from bench import spec as SPEC

ROOT = SPEC.ROOT
GiB = 1 << 30
# The runtime budgets activations only; the AdamW state it holds in device
# memory stays resident beside them.  2 GiB covers what its jaxpr-level
# profile cannot see.
MARGIN_BYTES = 2 * GiB
HOST_KINDS = ("pinned_host", "unpinned_host")
REF_STEPS = 3                # Stable steps the check compares
STABLE_ITERS = 2             # Stable iterations before the compared steps
MAX_SETUP_ITERS = 48
EVAL_SETUP_CYCLES = 2


class NoChip(RuntimeError):
    pass


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(prog="python3 bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise NoChip(f"the cell needs {n} TPU chip(s); JAX reports "
                     f"{len(devices)} {devices[0].platform} device(s)")
    return devices[:n]


def enable_cache() -> str:
    """JAX's persistent compilation cache at the fixed path
    ``<checkout>/.jax_cache``, whatever the environment names, caching
    every program: only a cell's first run in a checkout compiles."""
    import jax
    path = str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------- build
def model_config(cell: SPEC.Cell):
    from repro.common.config import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in cell.model.items() if k in fields}
    return ModelConfig(name=cell.config["name"], **kw)


def tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def placed_bytes(tree) -> Tuple[int, int]:
    """Bytes of ``tree``'s leaves in device memory and in host memory, by
    each leaf's ``sharding.memory_kind``."""
    import jax
    device = host = 0
    for x in jax.tree.leaves(tree):
        kind = getattr(getattr(x, "sharding", None), "memory_kind", None)
        if kind in HOST_KINDS:
            host += x.size * x.dtype.itemsize
        else:
            device += x.size * x.dtype.itemsize
    return device, host


def hbm_budget(limit_bytes: int, opt_state) -> int:
    """``limit_bytes`` less the AdamW state held in device memory, less
    ``MARGIN_BYTES``."""
    return limit_bytes - placed_bytes(opt_state)[0] - MARGIN_BYTES


def build(cell: SPEC.Cell, seed: int, limit_bytes: int, ckpt_dir: str):
    """The cell's Trainer, built as ``launch/train.build_trainer`` builds
    one, from the cell's files instead of ``--arch``.

    The runtime's budget is set from the state the built Trainer holds:
    its ``ChameleonConfig`` gets the budget with the whole AdamW state in
    HBM (all that the program shows before it places the state), and once
    built, the runtime's ``budget``, which every policy and OOM pass of the
    runtime is handed, gets the one from the leaves in device memory.  No
    iteration runs in between."""
    import jax
    from repro.common.config import ChameleonConfig, TrainConfig
    from repro.distributed import steps as S
    from repro.optim.adamw import adamw_init
    from repro.runtime.trainer import Trainer
    from bench.data import EVAL_STREAM, TRAIN_STREAM, Batches

    cfg = model_config(cell)
    job = cell.traffic
    opt_bytes = tree_bytes(jax.eval_shape(adamw_init, S.abstract_params(cfg)))
    tcfg = TrainConfig(steps=job["total_steps"],
                       learning_rate=job["learning_rate"],
                       warmup_steps=job["warmup_steps"],
                       weight_decay=job["weight_decay"],
                       grad_clip=job["grad_clip"], eval_every=0,
                       checkpoint_every=0, checkpoint_dir=ckpt_dir,
                       seed=seed)
    cham = ChameleonConfig(
        hbm_budget_bytes=limit_bytes - opt_bytes - MARGIN_BYTES)
    tr = Trainer(cfg, tcfg, cham,
                 data=Batches(cfg.vocab_size, job, seed, TRAIN_STREAM),
                 eval_data=Batches(cfg.vocab_size, job, seed, EVAL_STREAM))
    budget = hbm_budget(limit_bytes, tr.opt_state)
    host_bytes = placed_bytes(tr.opt_state)[1]
    if budget <= 0:
        raise RuntimeError(f"no HBM left for activations: budget {budget}")
    tr.rt.budget = budget
    log(f"build: {cell.name} seed {seed}: {cfg.param_count() / 1e6:.1f}M "
        f"params, AdamW {opt_bytes / GiB:.3f} GiB ({host_bytes} B on the "
        f"host), budget {budget} B ({budget / GiB:.3f} GiB)")
    return tr


# ------------------------------------------------------------- tracing
class Annotated:
    """A callable under a profiler span; attributes pass through, so the
    runtime can still ``.trace`` a wrapped jitted step."""

    def __init__(self, name: str, fn: Callable):
        self._name, self._fn = name, fn

    def __call__(self, *args, **kw):
        import jax
        with jax.profiler.TraceAnnotation(self._name):
            return self._fn(*args, **kw)

    def __getattr__(self, attr):
        return getattr(self._fn, attr)


def annotate(tr, grad_steps: List) -> None:
    """Host spans around the calls into each layer, set on this Trainer
    instance only.  ``grad_steps`` collects the grad-step program each
    iteration ran."""
    rt = tr.rt
    step_fn = rt.step_fn

    def traced_step_fn():
        fn = step_fn()
        grad_steps.append((rt.applied.fingerprint, fn))
        return Annotated("bench.grad_step", fn)

    rt.step_fn = traced_step_fn
    rt.end_iteration = Annotated("bench.end_iteration", rt.end_iteration)
    rt.record_dispatch = Annotated("bench.record_dispatch",
                                   rt.record_dispatch)
    tr._apply = Annotated("bench.apply_step", tr._apply)
    tr._eval = Annotated("bench.eval_step", tr._eval)
    tr.data.get = Annotated("bench.data_get", tr.data.get)


# --------------------------------------------------------------- set-up
def _master(tr):
    state = tr.opt_state
    return state.master if state.master is not None else tr.params


def setup(tr, cell: SPEC.Cell) -> dict:
    """Train until Stable has run ``STABLE_ITERS`` iterations, then the
    ``REF_STEPS`` steps the check compares, all through
    ``Trainer.train(1)``, the window's own call and feed.  Records the
    batch and the losses of every step, the first compared step's
    gradient (from the Adam state before and after it), the change of the
    float32 master parameters over the compared steps and the policy each
    ran.  With an eval cadence, then whole eval cycles, stopping before an
    eval iteration."""
    import jax
    from bench import check
    rep, batches, policies = tr.report, [], []

    def step():
        batches.append(tr.data.cursor)
        policies.append(tr.rt.applied.fingerprint)
        tr.train(1)

    while rep.stages.count("Stable") < STABLE_ITERS:
        if tr.step >= MAX_SETUP_ITERS:
            raise RuntimeError(f"no Stable by step {tr.step}: {rep.stages}")
        step()
    first = tr.step
    b1 = cell.traffic["adam_b1"]
    m0 = jax.device_get(tr.opt_state.m)
    start = jax.device_get(_master(tr))
    step()
    grad = {n: v / (1.0 - b1)
            for n, v in check.diff_norms(tr.opt_state.m, m0, b1).items()}
    del m0
    for _ in range(REF_STEPS - 1):
        step()
    out = {"losses": list(rep.losses), "grad": grad,
           "update": check.diff_norms(_master(tr), start),
           "batches": batches, "first": first,
           "policies": policies[first:]}
    del start
    every = cell.traffic["eval_every"]
    if every:
        tr.tcfg = dataclasses.replace(tr.tcfg, eval_every=every)
        n_eval = len(rep.eval_losses)
        while (len(rep.eval_losses) - n_eval < EVAL_SETUP_CYCLES
               or tr.step % every):
            tr.train(1)
    return out


# --------------------------------------------------------------- window
class CompileCounter:
    """Backend compiles seen while ``active``."""

    def __init__(self):
        import jax.monitoring
        self.active, self.count = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def window(tr, cell: SPEC.Cell, seconds: float) -> dict:
    """``Trainer.train(1)`` until ``seconds`` have passed and, with an
    eval cadence, the next iteration starts a cycle.  Times each call on
    the host clock; every call ends on the device (the trainer blocks on
    the loss and the new params).  Notes the policy of each iteration."""
    import jax
    every = cell.traffic["eval_every"]
    times: List[float] = []
    policies: List[str] = []
    step0 = tr.step
    t0 = time.perf_counter()
    while True:
        policies.append(tr.rt.applied.fingerprint)
        ti = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.iteration"):
            tr.train(1)
        t1 = time.perf_counter()
        times.append(t1 - ti)
        if t1 - t0 >= seconds and not (every and tr.step % every):
            break
    return {"seconds": t1 - t0, "times": times, "first_step": step0,
            "iterations": len(times),
            "policies": dict(collections.Counter(policies))}


def memory_peak(device) -> Optional[int]:
    """Bytes in use at their peak plus the program reservations at theirs:
    an upper bound on the device's peak (the two may not coincide)."""
    stats = device.memory_stats()
    if not stats:
        return None
    return int(stats["peak_bytes_in_use"]) + int(
        stats.get("peak_bytes_reserved", 0))


def free_state(tr) -> None:
    """Delete the program's device arrays and drop its compiled programs
    (each holds a temp reservation in HBM), so the reference has the
    chip."""
    import jax
    for x in jax.tree.leaves((tr.params, tr.opt_state)):
        x.delete()
    tr.rt.close()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------ reference
def reference_numbers(cell: SPEC.Cell, seed: int, batches: List[int],
                      first: int, ref=None, keep_state: bool = False
                      ) -> dict:
    """The losses, gradient and change norms (``bench.check``) of ``ref``
    (default: the configuration's float32 reference, ``Cell.reference``)
    run from the seed on the batches the program trained on, comparing
    from step ``first``; with ``keep_state``, also the params and AdamW
    state before that step."""
    from bench import check
    from bench.data import TRAIN_STREAM, Batches
    model, job = cell.model, cell.traffic
    reference = cell.reference()
    ref = ref or reference.Reference(model, job)
    feed = Batches(model["vocab_size"], job, seed, TRAIN_STREAM)
    out = ref.train(reference.init_params(model, seed),
                    [feed.batch_at(i) for i in batches], check.leaf_norms,
                    first, keep_state=keep_state)
    out["update"] = check.diff_norms(out.pop("params"), out["start"])
    if not keep_state:
        del out["start"]
    return out


# ------------------------------------------------------------------ run
def run_cell(cell: SPEC.Cell, seed: int, seconds: float, trace: bool,
             limit_bytes: int, device=None) -> dict:
    """Set-up, window and check of one cell; returns the result line."""
    import jax
    device = device or jax.devices()[0]
    job = cell.traffic
    counter = CompileCounter()
    ckpt_dir = tempfile.mkdtemp(prefix="bench_ckpt_")
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        tr = build(cell, seed, limit_bytes, ckpt_dir)
        log(f"set-up: built at {time.perf_counter() - T_START:.1f} s")
        prog = setup(tr, cell)
        stable = prog["policies"][0]
        setup_s = time.perf_counter() - T_START
        log(f"set-up: compared steps {prog['first']}-{tr.step - 1} under "
            f"{stable!r}")
        log(f"set-up: done at {setup_s:.1f} s, step {tr.step}")
        grad_steps: List = []
        if trace:
            annotate(tr, grad_steps)
            jax.profiler.start_trace(trace_dir)
        counter.active = True
        stage0 = len(tr.report.stages)
        rt0 = (tr.rt.profiling_overhead_s, tr.rt.adaptation_overhead_s)
        win = window(tr, cell, seconds)
        prog["window_policies"] = win["policies"]
        rt1 = (tr.rt.profiling_overhead_s, tr.rt.adaptation_overhead_s)
        counter.active = False
        if trace:
            jax.profiler.stop_trace()
        peak = memory_peak(device)
        rep = tr.report
        win_stages = collections.Counter(rep.stages[stage0:])
        print(f"stages: set-up {dict(collections.Counter(rep.stages[:stage0]))}"
              f" window {dict(win_stages)}; Stable policy {stable!r}; window "
              f"policies {win['policies']}; "
              f"compiles in window {counter.count}", flush=True)
        win_losses = rep.losses[stage0:]
        failed = (sum(1 for s in rep.skipped_steps if s >= win["first_step"])
                  + sum(1 for x in win_losses if not math.isfinite(x)))
        tokens = win["iterations"] * job["global_batch"] * job["seq_len"]
        ctx = Context(cell=cell, window=win, tokens=tokens, setup_s=setup_s,
                      peak_bytes=peak, runtime_before=rt0, runtime_after=rt1,
                      device_kind=device.device_kind)
        if trace:
            ctx.trace = _reduce_trace(trace_dir)
            ctx.memory = _window_program_memory(tr, grad_steps)
        metrics = {}
        for m in (cell.per_layer if trace else cell.end_to_end):
            value = cell.reader(m["name"])(ctx) if trace else \
                END_TO_END[m["name"]](ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        free_state(tr)
        del tr
        t_ref = time.perf_counter()
        ref = reference_numbers(cell, seed, prog["batches"], prog["first"])
        log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    from bench import check
    nums = check.numbers(prog, ref)
    correct, checks = check.verdict(nums, cell.limits)
    log("readings not compared in this cell: " + json.dumps(
        {k: v for k, (v, _) in nums.items() if k not in checks}))
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} limit {c['limit']!r} "
            f"(worst at {c['at']})")
    result = {
        "correct": bool(correct and failed == 0),
        "attempted": win["iterations"],
        "failed": failed,
        "metrics": metrics,
        "device": {"platform": device.platform, "kind": device.device_kind,
                   "count": cell.entry.get("chips", 1),
                   "memory_peak_bytes": peak},
    }
    if trace:
        result["device"]["busy_s"] = ctx.trace["busy_s"]
        result["device"]["window_s"] = ctx.trace["window_s"]
        result["breakdown"] = {"device_ops": ctx.trace["device_ops"],
                               "idle_gaps": ctx.trace["idle_gaps"]}
    result["checks"] = {n: {"value": c["value"], "limit": c["limit"]}
                        for n, c in checks.items()}
    return result


@dataclasses.dataclass
class Context:
    """What a metric reads: the window, the runtime's counters around it,
    and in a traced run the trace's reduction and the compiled memory of
    the grad step the window ran most."""
    cell: SPEC.Cell
    window: dict
    tokens: int
    setup_s: float
    peak_bytes: Optional[int]
    runtime_before: tuple
    runtime_after: tuple
    device_kind: str
    trace: Optional[dict] = None
    memory: Optional[dict] = None


END_TO_END: Dict[str, Callable[[Context], Optional[float]]] = {
    "tokens_per_s": lambda c: c.tokens / c.window["seconds"],
    "peak_hbm_gib": lambda c: (c.peak_bytes / GiB
                               if c.peak_bytes is not None else None),
    "setup_s": lambda c: c.setup_s,
}


def _reduce_trace(trace_dir: str) -> dict:
    from bench import trace as T
    t0 = time.perf_counter()
    out = T.reduce(T.load(T.find_xplane(trace_dir)))
    log(f"trace: reduced in {time.perf_counter() - t0:.1f} s: "
        f"window {out['window_s']:.3f} s busy {out['busy_s']:.3f} s, "
        f"offload ops {out['offload_s']:.3f} s "
        f"({out['offload_exposed_s']:.3f} s exposed)")
    return out


def _window_program_memory(tr, grad_steps: List) -> Optional[dict]:
    """``memory_analysis()`` of the grad step that ran the most iterations
    of the window, compiled again (from the cache) for its arguments."""
    import jax
    if not grad_steps:
        return None
    fp, _ = collections.Counter(f for f, _ in grad_steps).most_common(1)[0]
    fn = next(f for f_, f in grad_steps if f_ == fp)
    batch = tr._device_batch(tr.data.batch_at(0))
    ma = fn.lower(tr.params, batch, tr.loss_scale.scale).compile() \
        .memory_analysis()
    return {"policy": fp, "temp_bytes": int(ma.temp_size_in_bytes),
            "host_temp_bytes": int(ma.host_temp_size_in_bytes)}


def main(argv=None) -> int:
    args = parse_args(argv)
    try:
        sys.path.insert(0, str(ROOT / "src"))
        cell = SPEC.resolve(args.workload)
        import repro  # noqa: F401  (the program under test must be here)
        devices = require_chips(int(cell.entry.get("chips", 1)))
    except (NoChip, ImportError, KeyError, FileNotFoundError) as e:
        log(f"bench.run: {e}")
        return 2
    cache = enable_cache()
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{cache}")
    limit = int(devices[0].memory_stats()["bytes_limit"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), limit,
                      devices[0])
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

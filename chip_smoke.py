"""Chip smoke check: the training main path on one TPU at published widths.

    python chip_smoke.py

Runs in one process on one chip, in three phases.  A failed phase raises,
and the script exits nonzero; nothing is caught and turned into success.

1. device   JAX must report a TPU (no CPU fallback).  Prints the device
            kind, the device count and ``memory_stats()["bytes_limit"]``.
2. kernels  Each Pallas kernel runs once compiled for the chip (never in
            interpret mode) and is compared with its ``kernels/*/ref.py``
            oracle; the max error of each is printed.
3. train    ``launch/train.py``'s Trainer and ChameleonRuntime train
            qwen1.5-0.5b at its published widths (seq 2048, global batch 2,
            random weights from seed 0) under an HBM budget of
            ``bytes_limit`` less the AdamW state less a margin, until the
            stage machine has gone WarmUp -> GenPolicy -> Stable; then one
            step with an eval dispatch and a checkpoint save (to a scratch
            directory that is deleted).  Checks: finite losses, an applied
            Stable policy that offloads, device peak <= bytes_limit, and the
            step-0 loss against ``nothing_saveable`` on the same params and
            batch.

The last line of stdout is one JSON object naming the device.  The compile
cache follows ``repro.common.compile_cache``.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

GiB = 1 << 30
ARCH = "qwen1.5-0.5b"
SEQ, GLOBAL_BATCH = 2048, 2
# The runtime budgets activations only; the AdamW state (m, v, f32 master)
# stays resident beside them.  The margin covers what the runtime's
# jaxpr-level profile cannot see: compiled for a v5e, the no-swap grad step
# needs 0.96 GiB more than the profile's peak (9.48 vs 8.52 GiB), and the
# f32 logits of the loss and their gradient (~1 GiB) are outside every
# offload site.
MARGIN_BYTES = 2 * GiB
# Offload moves a tensor and never changes its value, but the two grad
# steps are compiled separately and XLA may round bf16 intermediates at
# different points in each: the step-0 losses must agree to one bf16 ulp.
LOSS_RTOL = 2.0 ** -8
# Stable must be seen on this many iterations before the eval step; the
# stage machine gets this many iterations to get there.
STABLE_ITERS, MAX_ITERS = 2, 24


def _require(ok, what) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(what)


def device_phase():
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX reports platform {dev.platform!r},"
                         f" not a TPU; nothing was run")
    limit = int(dev.memory_stats()["bytes_limit"])
    print(f"device: kind={dev.device_kind!r} count={len(devices)} "
          f"bytes_limit={limit} ({limit / GiB:.3f} GiB)")
    return dev, limit


def _max_err(out, ref) -> float:
    import numpy as np
    return float(np.max(np.abs(np.asarray(out, np.float32)
                               - np.asarray(ref, np.float32))))


def _check_close(name: str, out, ref, *, rtol: float, atol: float) -> None:
    import numpy as np
    print(f"kernel {name}: max |err| {_max_err(out, ref):.3e} "
          f"(rtol {rtol:g}, atol {atol:g})")
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=atol, err_msg=name)


def kernel_phase(seed: int = 0) -> None:
    """Each Pallas kernel once, compiled, against its reference.  bf16
    kernels are held to the repo's bf16 kernel-test tolerance (2e-2)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.flash_attention import kernel as FK
    from repro.kernels.flash_attention.ops import _default_interpret
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.quant_offload import kernel as QK
    from repro.kernels.quant_offload.ref import dequantize_ref, quantize_ref
    from repro.kernels.ssd_scan import kernel as SK
    from repro.kernels.ssd_scan.ref import ssd_ref

    interpret = _default_interpret()
    _require(not interpret, "kernels would run in interpret mode")
    rng = np.random.RandomState(seed)
    bf16 = jnp.bfloat16

    def arr(shape, scale=0.3, dtype=bf16):
        return jnp.asarray(rng.randn(*shape) * scale, dtype)

    def hi(fn, *args, **kw):        # the oracles run at f32 matmul precision
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw)

    # flash attention forward: B=2, S=2048, H=16, D=64 (qwen1.5-0.5b)
    q, k, v = (arr((2, 16, 2048, 64)) for _ in range(3))
    out = jax.jit(lambda q, k, v: FK.flash_attention_fwd(
        q, k, v, causal=True, sm_scale=0.125, interpret=interpret))(q, k, v)
    ref = hi(attention_ref, q, k, v, causal=True, sm_scale=0.125)
    _check_close("flash_attention_fwd", out, ref, rtol=2e-2, atol=2e-2)

    # flash decode: B=4 single-token queries over 2048 cached keys
    q, k, v = arr((4, 16, 1, 64)), arr((4, 16, 2048, 64)), \
        arr((4, 16, 2048, 64))
    lens = jnp.asarray([2048, 1, 1000, 1537], jnp.int32)
    out = jax.jit(lambda q, k, v, n: FK.flash_decode_fwd(
        q, k, v, n, sm_scale=0.125, interpret=interpret))(q, k, v, lens)
    ref = hi(attention_ref, q, k, v, causal=False, sm_scale=0.125,
             lens=lens)
    _check_close("flash_decode_fwd", out, ref, rtol=2e-2, atol=2e-2)

    # int8 row quantize / dequantize: (4096, 1024) bf16
    x = arr((4096, 1024), scale=1.0)
    qk, sk = jax.jit(lambda x: QK.quantize_fwd(x, interpret=interpret))(x)
    qr, sr = quantize_ref(x)
    flips = np.abs(np.asarray(qk, np.int32) - np.asarray(qr, np.int32))
    print(f"kernel quantize_fwd: max |q err| {int(flips.max())} quantum, "
          f"{float((flips > 0).mean()):.2e} of entries flipped, "
          f"max |scale err| {_max_err(sk, sr):.3e}")
    # a reciprocal-multiply may round x/scale the other way at a tie
    _require(flips.max() <= 1 and (flips > 0).mean() < 0.01,
             "quantize_fwd differs from quantize_ref")
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)
    xd = jax.jit(lambda q, s: QK.dequantize_fwd(
        q, s, bf16, interpret=interpret))(qk, sk)
    # exact in f32, then one bf16 rounding in both: one ulp at most
    _check_close("dequantize_fwd", xd, dequantize_ref(qk, sk, bf16),
                 rtol=2.0 ** -7, atol=0.0)

    # SSD chunked scan: mamba2-780m widths H=48, P=64, N=128, S=4096
    B, H, S, P, N = 1, 48, 4096, 64, 128
    x = arr((B, H, S, P), scale=0.5)
    dt = jnp.asarray(np.abs(rng.randn(B, H, S)) * 0.1, bf16)
    A = -jnp.asarray(np.abs(rng.randn(H)) + 0.5, jnp.float32)
    Bm, Cm = arr((B, S, N)), arr((B, S, N))
    out = jax.jit(lambda *a: SK.ssd_scan_fwd(
        *a, chunk=256, interpret=interpret))(x, dt, A, Bm, Cm)
    _check_close("ssd_scan_fwd", out, hi(ssd_ref, x, dt, A, Bm, Cm),
                 rtol=2e-2, atol=2e-2)


def _memory_line(tag: str, detail: bool = False) -> None:
    """Device bytes in use next to the bytes of the arrays Python holds;
    with ``detail``, the largest live arrays by shape."""
    import collections
    import jax
    stats = jax.devices()[0].memory_stats()
    live = [a for a in jax.live_arrays() if not a.is_deleted()]
    print(f"memory[{tag}]: bytes_in_use {stats['bytes_in_use']} "
          f"({stats['bytes_in_use'] / GiB:.3f} GiB), {len(live)} live arrays "
          f"{sum(a.nbytes for a in live) / GiB:.3f} GiB")
    if detail:
        print(f"  memory_stats {stats}")
        by_shape = collections.Counter()
        for a in live:
            by_shape[(a.shape, str(a.dtype))] += a.nbytes
        for (shape, dtype), nb in by_shape.most_common(12):
            print(f"  {dtype}{list(shape)}: {nb / GiB:.3f} GiB")


def _tree_bytes(tree) -> int:
    import jax
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def training_phase(limit: int) -> None:
    import jax
    from repro.distributed import steps as S
    from repro.launch import train as T
    from repro.optim.adamw import adamw_init
    import repro.configs as C

    cfg = C.get_config(ARCH)
    params_sds = S.abstract_params(cfg)
    opt_bytes = _tree_bytes(jax.eval_shape(adamw_init, params_sds))
    budget = limit - opt_bytes - MARGIN_BYTES
    print(f"train: {ARCH} {cfg.param_count() / 1e6:.1f}M params, "
          f"params {_tree_bytes(params_sds) / GiB:.3f} GiB, AdamW state "
          f"{opt_bytes / GiB:.3f} GiB; budget = bytes_limit - AdamW - "
          f"{MARGIN_BYTES / GiB:.0f} GiB margin = {budget} "
          f"({budget / GiB:.3f} GiB)")
    _require(budget > 0, f"no HBM left for activations: budget {budget}")

    ckpt_dir = tempfile.mkdtemp(prefix="chip_smoke_ckpt_")
    args = T.parse_args([
        "--arch", ARCH, "--seq", str(SEQ), "--global-batch",
        str(GLOBAL_BATCH), "--steps", str(MAX_ITERS + 1),
        "--ckpt-dir", ckpt_dir, "--budget-gib", repr(budget / GiB)])
    tr = T.build_trainer(args)
    # no eval or checkpoint until Stable has been seen (see _train_and_check)
    tr.tcfg = dataclasses.replace(tr.tcfg, eval_every=0, checkpoint_every=0)
    _memory_line("trainer built")
    try:
        _train_and_check(tr)
    except BaseException:
        _memory_line("training failed", detail=True)
        raise
    finally:
        tr.data.stop()
        tr.rt.close()
        shutil.rmtree(ckpt_dir, ignore_errors=True)


def _train_and_check(tr) -> None:
    import jax
    from repro.distributed import steps as S

    # reference: the same params and step-0 batch under nothing_saveable
    ref_step = jax.jit(S.make_grad_step(
        tr.cfg, tr.tcfg, jax.checkpoint_policies.nothing_saveable))
    batch0 = tr._device_batch(tr.data.batch_at(0))
    ref_loss, ref_grads, ref_finite = ref_step(tr.params, batch0,
                                               tr.loss_scale.scale)
    ref_loss = float(ref_loss)
    del ref_grads, ref_finite, batch0
    _memory_line("reference step done")

    rep = tr.report
    while rep.stages.count("Stable") < STABLE_ITERS:
        _require(tr.step < MAX_ITERS,
                 f"no Stable by step {tr.step}: {rep.stages}")
        tr.train(1)
    stable_policy = tr.rt.applied
    stable_fps = {h["policy"] for h in tr.rt.history
                  if h["stage"] == "Stable"}
    print(f"train: stages {rep.stages}")
    print(f"train: Stable policy {stable_policy.fingerprint!r} "
          f"offloads {sorted(stable_policy.offload)}")
    _require("GenPolicy" in rep.stages and "Stable" in rep.stages,
             rep.stages)
    _require(stable_fps == {stable_policy.fingerprint},
             f"Stable ran more than one policy: {stable_fps}")
    _require(stable_policy.offload, "Stable policy offloads nothing")

    # one more step with an eval dispatch and a checkpoint save
    tr.tcfg = dataclasses.replace(tr.tcfg, eval_every=tr.step,
                                  checkpoint_every=tr.step + 1)
    eval_step = tr.step
    tr.train(1)
    print(f"train: eval loss at step {eval_step}: "
          f"{rep.eval_losses.get(eval_step)}; stage {rep.stages[-1]}; "
          f"checkpoint {rep.checkpoints}")
    _require(math.isfinite(rep.eval_losses[eval_step]), rep.eval_losses)
    _require(len(rep.checkpoints) == 1 and os.path.isdir(rep.checkpoints[0]),
             f"checkpoint not written: {rep.checkpoints}")

    print(f"train: losses {rep.losses}")
    print(f"train: step times (s) {rep.times}")
    _require(all(math.isfinite(x) for x in rep.losses), rep.losses)
    rel = abs(rep.losses[0] - ref_loss) / abs(ref_loss)
    print(f"train: step-0 loss {rep.losses[0]!r} vs nothing_saveable "
          f"{ref_loss!r}: rel diff {rel:.3e} (limit {LOSS_RTOL:.3e})")
    _require(rel <= LOSS_RTOL, (rep.losses[0], ref_loss))

    curve = tr.rt.hostmem.calibrate().curve()
    print("host link (engine round trip): " + ", ".join(
        f"{s} B {gbps:.3f} GB/s" for s, _t, gbps in curve))

    stats = jax.devices()[0].memory_stats()
    peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
    # arrays are counted in bytes_in_use; a program's temp is a separate
    # reservation at the bottom of HBM, so the device peak is at most
    # their two peaks together
    bound = peak + stats["peak_bytes_reserved"]
    print(f"memory: peak_bytes_in_use {peak} ({peak / GiB:.3f} GiB), with "
          f"peak_bytes_reserved {bound} ({bound / GiB:.3f} GiB), of "
          f"bytes_limit {limit} ({limit / GiB:.3f} GiB); all stats {stats}")
    _require(peak <= limit, (peak, limit))
    _require(bound <= limit, (bound, limit))


def main() -> int:
    from repro.common.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    dev, limit = device_phase()
    print(f"compile cache: {cache_dir}")
    kernel_phase()
    _memory_line("kernels done")
    training_phase(limit)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

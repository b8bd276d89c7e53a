import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

import argparse          # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from typing import Optional  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

import repro.configs as C                                    # noqa: E402
from repro.common.config import (ChameleonConfig, SHAPES_BY_NAME,  # noqa: E402
                                 TrainConfig)
from repro.core.executor import Executor                     # noqa: E402
from repro.core.memtrace import build_timeline               # noqa: E402
from repro.core.policy import ChameleonOOMError, generate_policy  # noqa: E402
from repro.core.profiler import profile_jaxpr                # noqa: E402
from repro.distributed import sharding as shd                # noqa: E402
from repro.distributed import steps as S                     # noqa: E402
from repro.launch import roofline as R                       # noqa: E402
from repro.launch import specs as SP                         # noqa: E402
from repro.launch.mesh import make_production_mesh           # noqa: E402

"""Multi-pod dry-run: ``lower().compile()`` every (arch × shape × mesh) cell
on the production mesh — 16×16 single-pod and 2×16×16 multi-pod — and emit
memory analysis + roofline terms to artifacts/dryrun/*.json.

Policy modes for train cells:
  none         save-everything baseline (the PyTorch-analogue; may exceed HBM
               — the memory analysis shows by how much)
  chameleon    paper-faithful: profile the baseline jaxpr, generate the swap
               policy (Algo 2), re-lower with the offload remat policy
  remat        full recomputation (the paper's main competitor)
  offload_all  WarmUp-stage conservative policy
"""

# the production pod these meshes model is a TPU v5e slice; its peaks
# bound the roofline terms whatever backend runs the dry run itself
TARGET_KIND = "tpu_v5e"

ARTIFACTS = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                         "artifacts", "dryrun")


def _zero_stage(arch: str) -> int:
    return 3 if arch == "llama3_2_vision_90b" else 2


def _estimate_t_iter(cfg, shape, chips: int) -> float:
    tokens = shape.global_batch * shape.seq_len
    mf = R.model_flops_train(cfg.active_param_count(), tokens)
    peak = R.get_device_spec(TARGET_KIND).peak_flops
    return mf / (chips * peak * 0.4)           # assume 40% MFU


# sites whose activation shards on BOTH batch (dp) and model (tp) axes
_TP_SHARDED_SITES = {"ffn_pre", "ffn_act", "qkv_proj", "attn_ctx",
                     "moe_dispatch", "moe_act", "router_logits",
                     "ssm_in", "ssm_conv", "ssm_gate", "ssm_state"}


def _per_chip_profile(prof, cfg, mesh):
    """Rescale the (global-shape) profile to per-chip bytes using each
    site's logical sharding: batch-sharded sites divide by dp, tensor-
    parallel sites by dp·tp; params by tp; optimizer state by dp·tp
    (ZeRO).  The per-device MRL then works in the same units as the
    paper's (and XLA's memory analysis)."""
    import copy
    dp = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names:
            dp *= mesh.shape[a]
    tp = mesh.shape.get("model", 1)
    prof2 = copy.copy(prof)
    prof2.tensors = []
    for t in prof.tensors:
        f = dp
        if t.site in _TP_SHARDED_SITES:
            f = dp * tp
        elif t.site is None and t.shape and t.shape[-1] == cfg.vocab_size:
            f = dp * tp   # logits / softmax family: vocab dim on `model`
        t2 = copy.copy(t)
        t2.nbytes = max(t.nbytes // f, 1)
        prof2.tensors.append(t2)
    params_b = sum(
        int(jnp.dtype(x.dtype).itemsize) * int(jnp.asarray(x.shape).prod())
        for x in jax.tree_util.tree_leaves(S.abstract_params(cfg)))
    opt_b = 12 * (params_b // max(jnp.dtype(cfg.param_dtype).itemsize, 1))
    prof2.static_bytes = params_b // tp + opt_b // (dp * tp)
    return prof2


def _chameleon_policy(cfg, shape, step_fn, args_specs, chips: int,
                      budget_per_chip: int, mesh,
                      calib_xla_dyn_peak: Optional[int] = None):
    """Paper flow adapted to trace time: profile -> MRL -> policy -> apply.
    All quantities per-chip.  ``calib_xla_dyn_peak`` (the baseline compile's
    per-chip temp bytes) calibrates the reconstructed timeline against
    XLA's buffer assignment (double-buffering, co-live remat pairs, and
    fragmentation that liveness analysis alone cannot see)."""
    cj = jax.make_jaxpr(step_fn)(*args_specs)
    prof = profile_jaxpr(cj, t_iter=_estimate_t_iter(cfg, shape, chips))
    prof = _per_chip_profile(prof, cfg, mesh)
    tl = build_timeline(prof)
    if calib_xla_dyn_peak:
        dyn = max(tl.peak - prof.static_bytes, 1)
        calib = max(1.0, calib_xla_dyn_peak / dyn)
        if calib > 1.0:
            for t in prof.tensors:
                t.nbytes = int(t.nbytes * calib)
            tl = build_timeline(prof)
    info = {"baseline_peak_per_chip": int(tl.peak),
            "static_per_chip": int(prof.static_bytes),
            "budget_per_chip": int(budget_per_chip)}
    if tl.peak <= budget_per_chip:
        return Executor(ChameleonConfig()).baseline().to_jax(), \
            {**info, "policy": "fits-baseline"}
    ccfg = ChameleonConfig(hbm_budget_bytes=budget_per_chip)
    try:
        swap = generate_policy(prof, ccfg, budget_per_chip, timeline=tl)
        applied = Executor(ccfg).lower(swap, prof)
        info.update(policy="chameleon", summary=swap.summary(),
                    offload_sites=sorted(applied.offload),
                    projected_peak_per_chip=int(swap.projected_peak),
                    stall_s=swap.stall_time,
                    swapped_bytes_per_chip=int(swap.swapped_bytes))
        return applied.to_jax(), info
    except ChameleonOOMError as e:
        info.update(policy="offload_all-fallback", error=str(e))
        ccfg2 = ChameleonConfig(hbm_budget_bytes=budget_per_chip)
        return Executor(ccfg2).conservative(prof).to_jax(), info


def _baseline_dyn_peak(arch, shape_name, mesh_name, out_dir,
                       mesh=None, cfg=None, shape=None) -> Optional[int]:
    """Per-chip temp bytes of the baseline compile: read the cached
    ``none``-policy artifact, or compile it now (and cache)."""
    if out_dir:
        fname = os.path.join(out_dir,
                             f"{arch}__{shape_name}__{mesh_name}__none.json")
        if os.path.exists(fname):
            with open(fname) as f:
                rec = json.load(f)
            if rec.get("status") == "ok":
                return int(rec["memory"]["temp_bytes"])
    rec = run_cell(arch, shape_name, mesh_name == "multi", "none", out_dir,
                   verbose=False, mesh=mesh, cfg=cfg, shape=shape)
    if rec.get("status") == "ok":
        return int(rec["memory"]["temp_bytes"])
    return None


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             policy_mode: str = "chameleon",
             out_dir: Optional[str] = None, verbose: bool = True,
             mesh=None, cfg=None, shape=None,
             rules_name: str = "default") -> dict:
    """``mesh``/``cfg``/``shape`` overrides exist for the reduced-config
    smoke path (tests run this on an 8-device child process).
    ``rules_name='dp_only'`` applies the TP->DP hillclimb mapping."""
    cfg = cfg if cfg is not None else C.get_config(arch)
    shape = shape if shape is not None else SHAPES_BY_NAME[shape_name]
    if shape.name == "long_500k" and not cfg.sub_quadratic:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped",
                "reason": "full-attention arch; long_500k needs "
                          "sub-quadratic decode (DESIGN.md §5)"}
    mesh = mesh if mesh is not None else make_production_mesh(
        multi_pod=multi_pod)
    chips = mesh.size
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "chips": chips, "policy_mode": policy_mode,
           "rules": rules_name}
    rules = shd.DP_ONLY_RULES if rules_name == "dp_only" else None
    t0 = time.time()
    with shd.use_mesh(mesh, rules):
        args_specs, meta = SP.input_specs(cfg, shape)
        tcfg = TrainConfig()
        if shape.kind == "train":
            # dp_only: ZeRO-3 semantics come from the rules themselves
            zero = 0 if rules_name == "dp_only" else _zero_stage(arch)
            in_sh, out_sh = SP.train_shardings(cfg, shape, mesh, zero)
            policy, pol_info = None, {"policy": policy_mode}
            if policy_mode == "chameleon":
                calib = _baseline_dyn_peak(arch, shape_name, rec["mesh"],
                                           out_dir, mesh=mesh, cfg=cfg,
                                           shape=shape)
                base_step = S.make_train_step(
                    cfg, tcfg, Executor(ChameleonConfig()).baseline().to_jax())
                policy, pol_info = _chameleon_policy(
                    cfg, shape, base_step, args_specs, chips,
                    ChameleonConfig().hbm_budget_bytes, mesh,
                    calib_xla_dyn_peak=calib)
            elif policy_mode == "none":
                policy = Executor(ChameleonConfig()).baseline().to_jax()
            elif policy_mode == "raw":
                policy = None
            elif policy_mode == "remat":
                policy = "full_remat"
            elif policy_mode == "offload_all":
                policy = Executor(ChameleonConfig()).conservative(None).to_jax()
            elif policy_mode == "offload_inputs":
                # §Perf cell C iter 3: offload only the per-layer residual
                # stream snapshot to host; rematerialize everything else
                # from it (the 3-way save/offload/remat decision at its
                # memory-minimal extreme — giant models whose activations
                # exceed host DRAM if swapped wholesale).
                from repro.core.executor import jax_offload_policy
                policy = jax_offload_policy(["ln_in"], [])
            # grads pinned to the optimizer-state sharding (2D: ZeRO axis x
            # model) so XLA reduce-scatters instead of all-reducing full
            # gradients (§Perf cell C iter 3)
            gsh = in_sh[1].m if in_sh[1].m is not None else in_sh[0]
            step = S.make_train_step(cfg, tcfg, policy, grad_shardings=gsh)
            # NOTE: out_shardings must be omitted when offload is active —
            # XLA's SPMD partitioner rejects the placement annotations that
            # explicit output shardings put on scalar outputs (RET_CHECK
            # "Side-effect HLO must have sharding").  Input shardings pin
            # the layout; outputs inherit via propagation.
            jf = jax.jit(step, in_shardings=in_sh, donate_argnums=(0, 1))
            tokens = shape.global_batch * shape.seq_len
            mf = R.model_flops_train(cfg.active_param_count(), tokens)
            rec["zero_stage"] = zero
            rec["policy_info"] = pol_info
        elif meta["step"] == "prefill":
            in_sh, out_sh = SP.serve_shardings(cfg, shape, mesh)
            step = S.make_prefill_step(cfg)
            jf = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh)
            mf = 2.0 * cfg.active_param_count() * shape.global_batch * shape.seq_len
        else:  # decode
            state_sds = args_specs[2]
            in_sh, out_sh = SP.serve_shardings(cfg, shape, mesh, state_sds)
            step = S.make_decode_step(cfg)
            jf = jax.jit(step, in_shardings=in_sh, out_shardings=out_sh,
                         donate_argnums=(2,))
            mf = R.model_flops_decode(cfg.active_param_count(),
                                      shape.global_batch)

        step_cj = jax.make_jaxpr(step)(*args_specs)
        lowered = jf.lower(*args_specs)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        ma = compiled.memory_analysis()
        terms = R.analyze(compiled, chips, device_kind=TARGET_KIND,
                          model_flops=mf, step_jaxpr=step_cj)

    hbm = ChameleonConfig().hbm_budget_bytes
    per_chip = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    rec.update(
        status="ok",
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        memory={
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "host_temp_bytes": ma.host_temp_size_in_bytes,
            "peak_per_chip": per_chip,
            "fits_16g": bool(per_chip <= hbm),
        },
        roofline=terms.to_dict(),
    )
    # CPU backend folds pinned_host into device memory: report the analytic
    # device/host split that holds on real TPU.
    pol_info = rec.get("policy_info", {})
    if "swapped_bytes_per_chip" in pol_info:
        off = pol_info["swapped_bytes_per_chip"]
        rec["memory"]["offloaded_per_chip_analytic"] = int(off)
        rec["memory"]["device_peak_est_tpu"] = int(per_chip - off)
        rec["memory"]["fits_16g_with_offload"] = bool(
            per_chip - off <= hbm)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        suffix = "" if rules_name == "default" else f"__{rules_name}"
        fname = (f"{arch}__{shape_name}__{rec['mesh']}"
                 f"__{policy_mode}{suffix}.json")
        with open(os.path.join(out_dir, fname), "w") as f:
            json.dump(rec, f, indent=1, default=str)
    if verbose:
        r = rec["roofline"]
        print(f"[{rec['mesh']:6s}] {arch:24s} {shape_name:12s} "
              f"compile={rec['compile_s']:7.1f}s "
              f"peak/chip={per_chip/2**30:6.2f}GiB "
              f"compute={r['compute_s']*1e3:8.2f}ms "
              f"mem={r['memory_s']*1e3:8.2f}ms "
              f"coll={r['collective_s']*1e3:8.2f}ms "
              f"-> {r['bottleneck']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--policy", default="chameleon",
                    choices=["none", "raw", "chameleon", "remat", "offload_all", "offload_inputs"])
    ap.add_argument("--rules", choices=["default", "dp_only"],
                    default="default")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=ARTIFACTS)
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else C.ARCH_IDS
    shapes = [args.shape] if args.shape else list(SHAPES_BY_NAME)
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    failures = []
    for arch in archs:
        for shape in shapes:
            for mesh in meshes:
                sfx = "" if args.rules == "default" else f"__{args.rules}"
                fname = os.path.join(
                    args.out,
                    f"{arch}__{shape}__{mesh}__{args.policy}{sfx}.json")
                if os.path.exists(fname) and not args.force:
                    print(f"cached: {fname}")
                    continue
                try:
                    run_cell(arch, shape, mesh == "multi", args.policy,
                             args.out, rules_name=args.rules)
                except Exception as e:  # noqa: BLE001
                    failures.append((arch, shape, mesh, repr(e)))
                    print(f"FAIL {arch} {shape} {mesh}: {e!r}")
                    traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print("  ", f)
        raise SystemExit(1)
    print("\nall requested cells compiled OK")


if __name__ == "__main__":
    main()

"""Training entry point, on one device: the CPU with a reduced config, or
one TPU chip at a config's published widths.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --steps 50

``chip_smoke.py`` builds its trainer through :func:`parse_args` and
:func:`build_trainer`, so the chip check runs this exact setup.
"""
from __future__ import annotations

import argparse
import os


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama2-paper")
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-sized config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--budget-gib", type=float, default=16.0)
    ap.add_argument("--no-chameleon", action="store_true")
    ap.add_argument("--policy-store-dir", default="",
                    help="persist adaptation policies here (fingerprint-"
                         "keyed; a restart with a warm store skips "
                         "GenPolicy for recurring sequences)")
    ap.add_argument("--no-policy-store", action="store_true",
                    help="disable the in-memory policy cache too")
    ap.add_argument("--autotune", action="store_true",
                    help="tune the swap-path Pallas kernels against the "
                         "memory-bandwidth roofline at startup and price "
                         "the achieved efficiency into policy generation "
                         "(repro.kernels.autotune)")
    ap.add_argument("--autotune-cache-dir", default="",
                    help="persist tuned configs + bandwidth snapshot here "
                         "(schema-versioned autotune.json; a warm cache "
                         "means restart re-measures nothing).  Defaults "
                         "to <policy-store-dir>/autotune when a policy "
                         "store dir is set")
    ap.add_argument("--adapt-mode",
                    choices=["inline", "async", "speculative"],
                    default="inline",
                    help="adaptation placement (repro.adapt): inline runs "
                         "the paper's measured GenPolicy iterations; async "
                         "moves the variant search to a background worker "
                         "(drift never stalls an iteration); speculative "
                         "additionally pre-generates policies for "
                         "predicted-recurring op sequences")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON here on exit "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default="",
                    help="append repro.obs metrics-registry snapshots "
                         "(JSONL) here during training")
    ap.add_argument("--metrics-every", type=int, default=25,
                    help="snapshot cadence for --metrics-out (steps)")
    ap.add_argument("--stats-json", default="",
                    help="dump the full runtime stats() dict + obs "
                         "snapshot as JSON here on exit")
    ap.add_argument("--fault-plan", default="",
                    help="arm a repro.faults FaultPlan from this JSON file "
                         "(chaos drills: seeded fault schedules keyed by "
                         "site x iteration; see docs/robustness.md)")
    ap.add_argument("--audit-out", default="",
                    help="write the repro.obs audit log (JSONL) here on "
                         "exit — the evidence trail for fault drills")
    return ap.parse_args(argv)


def build_trainer(args: argparse.Namespace):
    """Config, data and :class:`Trainer` (with its ChameleonRuntime) for
    parsed arguments.  The data prefetcher is started; the caller stops
    it (``tr.data.stop()``) and closes the runtime (``tr.rt.close()``)."""
    import jax
    import repro.configs as C
    from repro.common.config import (AdaptConfig, AutotuneConfig,
                                     ChameleonConfig, PolicyStoreConfig,
                                     TrainConfig)
    from repro.data.synthetic import SyntheticTokens
    from repro.runtime.trainer import Trainer

    cfg = C.get_reduced(args.arch) if args.reduced else C.get_config(args.arch)
    seq = args.seq or (128 if args.reduced else 4096)
    gb = args.global_batch or (8 if args.reduced else 256)
    tcfg = TrainConfig(steps=args.steps, checkpoint_dir=args.ckpt_dir,
                       checkpoint_every=max(args.steps // 4, 1),
                       eval_every=max(args.steps // 3, 1))
    at_dir = args.autotune_cache_dir
    if args.autotune and not at_dir and args.policy_store_dir:
        # warm-start colocation: tuned configs restart with the policies
        at_dir = os.path.join(args.policy_store_dir, "autotune")
    cham = ChameleonConfig(enabled=not args.no_chameleon,
                           hbm_budget_bytes=int(args.budget_gib * 2 ** 30),
                           policystore=PolicyStoreConfig(
                               enabled=not args.no_policy_store,
                               dir=args.policy_store_dir),
                           adapt=AdaptConfig(mode=args.adapt_mode),
                           autotune=AutotuneConfig(
                               enabled=args.autotune, cache_dir=at_dir))
    data = SyntheticTokens(cfg.vocab_size, seq, gb,
                           host_index=jax.process_index(),
                           host_count=jax.process_count()).start()
    try:
        return Trainer(cfg, tcfg, cham, data=data,
                       metrics_out=args.metrics_out or None,
                       metrics_every=args.metrics_every)
    except BaseException:
        data.stop()
        raise


def main(argv=None):
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = parse_args(argv)
    if args.audit_out:
        # stream every audit event (not just the in-memory tail): the
        # chaos-drill evidence trail must survive a crash
        from repro import obs
        obs.audit().attach_file(args.audit_out)
    if args.fault_plan:
        from repro import faults
        faults.arm(faults.FaultPlan.load(args.fault_plan))
    tr = build_trainer(args)
    try:
        if args.resume:
            tr.resume()
        rep = tr.train(args.steps)
        print(f"done: loss {rep.losses[0]:.3f} -> {rep.losses[-1]:.3f}; "
              f"stages={set(rep.stages)}; "
              f"chameleon={tr.rt.stats()['applied'][:60]}")
        ov = tr.rt.obs_stats()["overlap"]
        if ov["measured"]:
            print(f"overlap efficiency: last {ov['last']:.1%} / "
                  f"mean {ov['mean']:.1%} over {ov['measured']} "
                  f"transfer-active iterations "
                  f"({ov['hidden_s'] * 1e3:.1f} of "
                  f"{ov['transfer_s'] * 1e3:.1f} ms hidden)")
        from repro import obs as _obs
        sb = _obs.ledger().scoreboard()
        if sb["n"]:
            print(f"memory ledger: {sb['n']} scored iterations, peak error "
                  f"mean |e| {sb['mean_abs_error']:.2%} / "
                  f"max |e| {sb['max_abs_error']:.2%}")
        ps = rep.policystore
        if ps is not None:
            t, s = ps["tiers"], ps["store"]
            print(f"policystore: {s['records']} records "
                  f"({s['dir'] or 'memory-only'}); tiers "
                  f"reuse={t['reuse']} warm={t['warm_start']} "
                  f"regen={t['regen']} demoted={t['demoted']}; "
                  f"genpolicy_steps={ps['genpolicy_steps_total']}; "
                  f"adaptations={len(ps['adaptations'])}")
        ad = rep.adapt
        if ad is not None and ad["mode"] != "inline":
            print(f"adapt[{ad['mode']}]: jobs={ad['jobs']} "
                  f"published={ad['published']} installed={ad['installed']} "
                  f"discarded={ad['discarded']} failed={ad['failed']} "
                  f"spec_hits={ad['speculative_hits']}")
    finally:
        tr.data.stop()
        if args.fault_plan:
            from repro import faults
            plan = faults.active()
            if plan is not None:
                print(f"fault plan: fired={plan.stats()['fired']}")
            faults.disarm()
        lad = tr.rt.ladder
        if lad is not None and lad.transitions:
            print(f"ladder: rung={lad.name} "
                  f"descents={lad.n_descents} ascents={lad.n_ascents}")
        tr.rt.close()
        _export_obs(args, tr.rt)


def _export_obs(args, rt) -> None:
    """Flush the repro.obs artifacts the flags asked for.  Runs from the
    ``finally`` block so a crashed run still leaves its trace behind."""
    import json

    from repro import obs

    if getattr(args, "metrics_out", ""):
        obs.metrics().write_jsonl(args.metrics_out)
    if getattr(args, "trace_out", ""):
        counters = {"overlap_efficiency": [
            (h["t"], h["efficiency"]) for h in rt.overlap_history
            if h["efficiency"] is not None]}
        counters.update(obs.ledger().counter_tracks())
        obs.export_chrome_trace(args.trace_out, obs.tracer(),
                                counters=counters,
                                meta={"arch": args.arch,
                                      "steps": args.steps})
        print(f"trace: {args.trace_out} "
              f"({obs.tracer().stats()['retained']} events)")
    if getattr(args, "stats_json", ""):
        snap = {"runtime": rt.stats(), "obs_snapshot": obs.metrics().snapshot(),
                "audit_tail": obs.audit().tail(200)}
        with open(args.stats_json, "w") as f:
            json.dump(snap, f, indent=1, default=repr)
        print(f"stats: {args.stats_json}")


if __name__ == "__main__":
    main()

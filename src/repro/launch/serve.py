"""Serving entry point: batched decode over the slot server.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced

Over-subscription: ``--max-active`` beyond ``--max-batch`` admits more
concurrent requests than HBM-resident slots by spilling preempted decode
state into the pinned host pool (repro.hostmem).
"""
from __future__ import annotations

import argparse
import time


def main():
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="HBM-resident decode slots")
    ap.add_argument("--max-active", type=int, default=0,
                    help="admitted concurrency (> max-batch spills KV state "
                         "to the host pool; 0 = max-batch)")
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--calibrate-link", action="store_true",
                    help="measure the host link before serving")
    ap.add_argument("--spill-compression", choices=["none", "int8", "auto"],
                    default="none",
                    help="int8: KV spill crosses the link row-quantized "
                         "(2-4x fewer bytes, <=0.4%% per-row error); "
                         "auto: raw-vs-int8 priced per row from the tuned "
                         "kernel rates + measured link curve")
    ap.add_argument("--autotune", action="store_true",
                    help="tune the swap-path kernels against the roofline "
                         "at startup (repro.kernels.autotune); feeds the "
                         "auto spill-compression advisor")
    ap.add_argument("--autotune-cache-dir", default="",
                    help="persist/reuse tuned configs here (warm cache = "
                         "zero re-measurement)")
    ap.add_argument("--policy-store-dir", default="",
                    help="attach the shared adaptation cache (read-only "
                         "visibility: cache warmth is reported in stats)")
    ap.add_argument("--adapt-mode",
                    choices=["inline", "async", "speculative"],
                    default="inline",
                    help="adaptation placement (repro.adapt): async/"
                         "speculative enable the background policy-store "
                         "refresher so a co-located trainer's new policies "
                         "become visible without a tick-loop stall")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome trace-event JSON here on exit "
                         "(open in Perfetto / chrome://tracing)")
    ap.add_argument("--metrics-out", default="",
                    help="write one repro.obs metrics-registry snapshot "
                         "(JSONL) here on exit")
    args = ap.parse_args()

    import jax
    import numpy as np
    import repro.configs as C
    from repro.common.config import HostMemConfig, PolicyStoreConfig
    from repro.hostmem import HostMemTier
    from repro.models.registry import get_api
    from repro.runtime.server import Server

    cfg = C.get_reduced(args.arch) if args.reduced else C.get_config(args.arch)
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(0))
    max_active = args.max_active or args.max_batch
    hostmem = None
    if (max_active > args.max_batch or args.calibrate_link
            or args.spill_compression != "none" or args.autotune):
        hostmem = HostMemTier(HostMemConfig(
            spill_compression=args.spill_compression))
        if args.calibrate_link:
            hostmem.calibrate()        # engine-path sweep, not raw device_put
        if args.autotune:
            from repro.common.config import AutotuneConfig
            hostmem.autotune(AutotuneConfig(
                enabled=True, cache_dir=args.autotune_cache_dir))
    policystore = None
    if args.policy_store_dir:
        from repro.policystore import PolicyStore
        # readonly: a shared training store must not lose records to this
        # reader's load-time eviction
        policystore = PolicyStore(PolicyStoreConfig(dir=args.policy_store_dir),
                                  readonly=True)
    srv = Server(cfg, params, max_batch=args.max_batch, max_len=args.max_len,
                 max_active=max_active, hostmem=hostmem,
                 policystore=policystore, adapt_mode=args.adapt_mode)
    rng = np.random.RandomState(0)
    for _ in range(args.requests):
        srv.submit(rng.randint(0, cfg.vocab_size, size=rng.randint(4, 16)),
                   max_new_tokens=args.new_tokens)
    t0 = time.time()
    results = srv.run_until_done(max_ticks=10_000)
    dt = time.time() - t0
    toks = sum(len(v) for v in results.values())
    print(f"{len(results)} requests, {toks} tokens, {dt:.2f}s, "
          f"{toks / dt:.1f} tok/s, {srv.ticks} ticks, "
          f"{srv.n_preemptions} preemptions")
    lat = srv.latency_stats()
    print(f"tick p50 {lat['tick_ms']['p50']:.1f} ms / "
          f"p95 {lat['tick_ms']['p95']:.1f} ms, "
          f"occupancy {lat['slot_occupancy']:.1%}, "
          f"queue-wait p95 {lat['queue_wait_ticks']['p95']:.0f} ticks")
    if hostmem is not None:
        print(hostmem.summary())          # includes per-traffic-class lines
        kv = srv.stats()["kv_spill_class"]
        if kv is not None and (kv["n_out"] or kv["n_in"]):
            print(f"kv_spill link: {kv['n_out']} spills staged / "
                  f"{kv['n_in']} restored, "
                  f"stalled {kv['stall_s'] * 1e3:.1f} ms behind "
                  f"higher-priority traffic")
        ks = hostmem.kvspill.stats()
        if ks["compression"] != "none" and ks["n_spills"]:
            print(f"spill compression ({ks['compression']}): "
                  f"{ks['bytes_raw'] / 2**20:.1f} MiB raw -> "
                  f"{ks['bytes_spilled'] / 2**20:.1f} MiB staged "
                  f"({ks['compression_ratio']:.2f}x)")
    if policystore is not None:
        print(f"policystore: {policystore.stats()}")
        ad = srv.stats()["adapt"]
        if ad["mode"] != "inline":
            print(f"adapt[{ad['mode']}]: "
                  f"store_refreshes={ad['store_refreshes']} "
                  f"records_refreshed={ad['store_records_refreshed']}")
    from repro import obs
    if args.metrics_out:
        obs.metrics().write_jsonl(args.metrics_out)
    if args.trace_out:
        obs.export_chrome_trace(args.trace_out, obs.tracer(),
                                counters=obs.ledger().counter_tracks(),
                                meta={"arch": args.arch,
                                      "requests": args.requests})
        print(f"trace: {args.trace_out} "
              f"({obs.tracer().stats()['retained']} events)")


if __name__ == "__main__":
    main()

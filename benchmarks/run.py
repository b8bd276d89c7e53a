"""Benchmark harness — one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV; ``--json PATH`` additionally
writes a machine-readable ``BENCH.json`` (schema-versioned headline
numbers per bench) so nightly runs leave a diffable perf trajectory."""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks.common import emit

BENCH_SCHEMA_VERSION = 1

MODULES = [
    "table1_profiling",
    "monitor_bench",
    "fig4_grouping",
    "table2_perf_benefit",
    "table4_max_size",
    "fig7_stability",
    "fig8_reuse_interval",
    "hostmem_bench",
    "adapt_bench",
    "serving_bench",
    "kernels_bench",
    "roofline",
]


def main() -> None:
    from repro.common.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated module substrings")
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--json", dest="json_out", default=None,
                    help="also write the results as BENCH.json here")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    failed = []
    results = []
    for mod_name in MODULES:
        if args.only and not any(s in mod_name
                                 for s in args.only.split(",")):
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            rows = mod.run(iters=args.iters)
            emit(rows)
            results.append({
                "module": mod_name,
                "rows": [{"name": name, "us_per_call": sec * 1e6,
                          "derived": derived}
                         for name, sec, derived in rows],
            })
        except Exception as e:  # noqa: BLE001
            failed.append(mod_name)
            print(f"{mod_name}.ERROR,0,{e!r}", file=sys.stderr)
            traceback.print_exc()
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"schema_version": BENCH_SCHEMA_VERSION,
                       "iters": args.iters,
                       "benches": results,
                       "failed": failed}, f, indent=1)
        print(f"wrote {args.json_out}", file=sys.stderr)
    if failed:
        raise SystemExit(f"benchmark failures: {failed}")


if __name__ == "__main__":
    main()

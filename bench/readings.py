"""Readings that set a cell's correctness limits; the benchmark's own runs
never run this.

    python3 -m bench.readings --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3

For every seed it drives the program's set-up as a run does
(``bench.run.setup``: to Stable, then the three compared steps), frees the
program, runs the float32 reference from the seed and prints
``bench.check``'s numbers.  For every control seed it also puts in the
program's place, against the same reference:

- ``control``: the configuration's reference (``Cell.reference``)
  computed with float8 matmul inputs, the precision below the
  configuration's bfloat16, every step from the seed;
- ``half_batch``: the reference's state before the compared steps, run
  through them on the first half of each batch's rows only, the mean taken
  over them (cells with more than one row);
- ``answer_altered``: the same, with one layer's FFN output-weight
  gradient doubled where it is produced.

The two faults are planted in the compared steps alone, as a fault of the
Stable grad step would be.  A state left unchanged reads 1 by the update
measure and needs no run.  One JSON object per reading goes to stdout,
then a summary: the largest program reading and the smallest reading of
each planted case.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import tempfile
import time

from bench import run as R
from bench import spec as SPEC


def _seeds(text: str):
    return [int(s) for s in text.split(",") if s]


class _Altered:
    """Mixin: one layer's ``mlp/wo`` gradient doubled."""

    def loss_and_grads(self, params, batch):
        loss, grads = super().loss_and_grads(params, batch)
        wo = grads["blocks"]["mlp"]["wo"]
        grads["blocks"]["mlp"]["wo"] = wo.at[wo.shape[0] // 2].multiply(2.0)
        return loss, grads


def _top(prog: dict, ref: dict, n: int = 4) -> list:
    """The ``n`` leaves of largest update gap, for the record."""
    import numpy as np
    med = float(np.median(list(ref.values())))
    gaps = {k: abs(prog[k] - r) / max(r, med) for k, r in ref.items()}
    return sorted(gaps.items(), key=lambda kv: -kv[1])[:n]


def planted(cell: SPEC.Cell, seed: int, prog: dict, kind: str,
            ref: dict) -> dict:
    """The numbers of the reference with ``kind`` planted, as if it were
    the program: the same dict ``bench.run.setup`` returns.  ``ref`` is
    the float32 reference's run with its state before the compared steps
    (``keep_state``), from which a fault of those steps starts."""
    import jax
    from bench import check
    from bench.data import TRAIN_STREAM, Batches
    Reference = cell.reference().Reference
    model, job = cell.model, cell.traffic
    first = prog["first"]
    if kind == "control":
        return R.reference_numbers(cell, seed, prog["batches"], first,
                                   Reference(model, job, "fp8"))
    feed = Batches(model["vocab_size"], job, seed, TRAIN_STREAM)
    batches = [feed.batch_at(i) for i in prog["batches"][first:]]
    if kind == "answer_altered":
        faulty = type("Altered", (_Altered, Reference), {})(model, job)
    else:
        faulty = Reference(model, job)
        half = job["global_batch"] // 2
        batches = [{k: v[:half] for k, v in b.items()} for b in batches]
    out = faulty.train(jax.device_put(ref["start"]), batches,
                       check.leaf_norms, 0, ref["state"])
    out["update"] = check.diff_norms(out.pop("params"), out.pop("start"))
    out["losses"] = ref["losses"][:first] + out["losses"]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="python3 -m bench.readings")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(R.ROOT / "src"))
    cell = SPEC.resolve(args.workload)
    devices = R.require_chips(int(cell.entry.get("chips", 1)))
    R.enable_cache()
    limit = int(devices[0].memory_stats()["bytes_limit"])
    from bench import check
    control = set(_seeds(args.control_seeds))
    kinds = ["control", "answer_altered"]
    if cell.traffic["global_batch"] > 1:
        kinds.append("half_batch")
    worst, least = {}, {}
    for seed in sorted(set(_seeds(args.seeds)) | control):
        t0 = time.perf_counter()
        with tempfile.TemporaryDirectory(prefix="bench_ckpt_") as ckpt:
            tr = R.build(cell, seed, limit, ckpt)
            prog = R.setup(tr, cell)
            R.free_state(tr)
            del tr
        ref = R.reference_numbers(cell, seed, prog["batches"], prog["first"],
                                  keep_state=seed in control)
        cases = {"program": prog}
        if seed in control:
            for kind in kinds:
                cases[kind] = planted(cell, seed, prog, kind, ref)
        for kind, got in cases.items():
            found = check.numbers(got, ref)
            nums = {k: v for k, (v, _) in found.items()}
            print(json.dumps({"seed": seed, "kind": kind, "numbers": nums,
                              "at": {k: w for k, (_, w) in found.items()},
                              "first": prog["first"],
                              "policy": prog["policies"][0],
                              "update_top": _top(got["update"],
                                                 ref["update"]),
                              "seconds": time.perf_counter() - t0}),
                  flush=True)
            for k, v in nums.items():
                if kind == "program":
                    worst[k] = max(worst.get(k, 0.0), v)
                else:
                    key = f"{kind}.{k}"
                    least[key] = min(least.get(key, math.inf), v)
    print(json.dumps({"workload": cell.name, "program_max": worst,
                      "planted_min": least}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Roofline term extraction from a compiled dry-run artifact.

  compute    = step_FLOPs_per_chip / peak_FLOP/s
  memory     = step_HBM_bytes_per_chip / HBM_bw
  collective = wire_bytes_per_chip / link_bw

Two accounting pitfalls (both verified empirically on the CPU backend) are
handled explicitly:

  1. ``cost_analysis()`` does NOT multiply while-loop (scan) body flops by
     the trip count, so a scanned 24-layer model reports ~1 layer of flops.
     We therefore walk the *jaxpr* of the final (policy-applied) step and
     count dot_general flops with scan multiplicity — remat recompute is
     visible in the jaxpr, so the MODEL_FLOPS/step_FLOPs ratio honestly
     reflects recompute waste.  XLA's number is kept as ``xla_flops`` for
     reference.

  2. Collectives inside scan bodies appear once in the HLO text but run
     once per iteration.  We parse the compiled module structurally:
     computations reached as a ``while`` body inherit the loop's trip count
     (read from the integer constant in its condition computation), and
     nested whiles compose multiplicatively.

HBM bytes use the same jaxpr walk (dot operands/outputs + tagged residual
stores), a post-fusion traffic proxy: elementwise chains fuse into the
surrounding matmuls on TPU.  Hardware constants come from the shared
:class:`~repro.kernels.autotune.device.DeviceSpec` registry, for the
device kind the caller names — one spec feeds this report and the kernel
autotuner.
"""
from __future__ import annotations

import math
import re
from dataclasses import dataclass, field, asdict
from typing import Dict, Optional, Tuple

import numpy as np

from repro.kernels.autotune.device import DeviceSpec, get_device_spec

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_WIRE_FACTOR = {
    "all-reduce": 2.0,        # ring RS + AG
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


# ====================================================== HLO structural walk
def _split_computations(hlo: str) -> Dict[str, str]:
    comps: Dict[str, str] = {}
    cur, buf = None, []
    for line in hlo.splitlines():
        m = re.match(r"^(?:ENTRY )?(%?[\w.\-]+) \(.*\{", line)
        if m:
            if cur is not None:
                comps[cur] = "\n".join(buf)
            cur, buf = m.group(1).lstrip("%"), []
        elif cur is not None:
            buf.append(line)
    if cur is not None:
        comps[cur] = "\n".join(buf)
    return comps


_WHILE_RE = re.compile(
    r"while\([^)]*\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_CALL_RE = re.compile(
    r"(?:call|fusion)\([^)]*\), (?:to_apply|calls)=%?([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")


def _trip_count(cond_text: str) -> int:
    consts = [int(c) for c in _CONST_RE.findall(cond_text)]
    return max(consts) if consts else 1


def _collectives_in(text: str) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for line in text.splitlines():
        eq = line.find(" = ")
        if eq < 0:
            continue
        rest = line[eq + 3:]
        for kind in _COLL_KINDS:
            k = rest.find(kind + "(")
            if k < 0:
                k = rest.find(kind + "-start(")
                if k < 0:
                    continue
            shapes_str = rest[:k]
            total = sum(_shape_bytes(d, dims)
                        for d, dims in _SHAPE_RE.findall(shapes_str))
            out[kind] = out.get(kind, 0.0) + total * _WIRE_FACTOR[kind]
            break
    return out


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-chip wire bytes by collective kind, with while-body collectives
    multiplied by their loop trip counts (nested loops compose)."""
    comps = _split_computations(hlo_text)
    local = {name: _collectives_in(text) for name, text in comps.items()}
    # computation -> list of (child computation, multiplier)
    children: Dict[str, list] = {name: [] for name in comps}
    roots = set(comps)
    for name, text in comps.items():
        for m in _WHILE_RE.finditer(text):
            cond, body = m.group(1), m.group(2)
            trip = _trip_count(comps.get(cond, ""))
            children[name].append((body, trip))
            roots.discard(body)
            roots.discard(cond)
        for m in _CALL_RE.finditer(text):
            callee = m.group(1)
            if callee in comps:
                children[name].append((callee, 1))
                roots.discard(callee)

    totals: Dict[str, float] = {}

    def accumulate(name: str, mult: float, seen: Tuple[str, ...] = ()):
        if name in seen or name not in comps:   # cycle guard
            return
        for kind, b in local.get(name, {}).items():
            totals[kind] = totals.get(kind, 0.0) + b * mult
        for child, trip in children.get(name, []):
            accumulate(child, mult * trip, seen + (name,))

    entry = None
    for name in comps:
        if "main" in name or "entry" in name.lower():
            entry = name
            break
    if entry is None and comps:
        entry = max(comps, key=lambda n: len(comps[n]))
    if entry:
        accumulate(entry, 1.0)
    # computations never reached from entry (conservatively count once)
    reached = set()

    def mark(name, seen=()):
        if name in seen or name in reached or name not in comps:
            return
        reached.add(name)
        for child, _ in children.get(name, []):
            mark(child, seen + (name,))

    if entry:
        mark(entry)
    for name in comps:
        if name not in reached:
            for kind, b in local.get(name, {}).items():
                totals[kind] = totals.get(kind, 0.0) + b
    return totals


# ============================================================= jaxpr costs
def _dot_flops(eqn) -> float:
    dn = eqn.params["dimension_numbers"]
    (lc, rc), (lb, rb) = dn
    lhs, rhs = eqn.invars[0].aval, eqn.invars[1].aval
    batch = 1.0
    for d in lb:
        batch *= lhs.shape[d]
    k = 1.0
    for d in lc:
        k *= lhs.shape[d]
    m = 1.0
    for i, s in enumerate(lhs.shape):
        if i not in lc and i not in lb:
            m *= s
    n = 1.0
    for i, s in enumerate(rhs.shape):
        if i not in rc and i not in rb:
            n *= s
    return 2.0 * batch * m * n * k


def _aval_bytes(aval) -> float:
    try:
        return float(np.prod(aval.shape)) * aval.dtype.itemsize
    except Exception:
        return 0.0


def jaxpr_cost(closed_jaxpr) -> Tuple[float, float]:
    """(flops, hbm_bytes) with scan multiplicity; recurses into remat/
    pjit/cond sub-jaxprs.  Bytes = dot operands+outputs + conv + tagged
    residual stores (post-fusion HBM-traffic proxy)."""
    from repro.core.tokenizer import _sub_jaxprs, _unwrap

    def walk(j) -> Tuple[float, float]:
        j = _unwrap(j)
        fl = by = 0.0
        for eqn in j.eqns:
            name = eqn.primitive.name
            if name == "scan":
                f, b = walk(eqn.params["jaxpr"])
                L = eqn.params.get("length", 1)
                fl += f * L
                by += b * L
                continue
            subs = _sub_jaxprs(eqn)
            if subs:
                for s in subs:
                    f, b = walk(s)
                    fl += f
                    by += b
                continue
            if name == "dot_general":
                fl += _dot_flops(eqn)
                by += sum(_aval_bytes(v.aval) for v in eqn.invars)
                by += sum(_aval_bytes(v.aval) for v in eqn.outvars)
            elif name == "conv_general_dilated":
                by += sum(_aval_bytes(v.aval) for v in eqn.invars)
                by += sum(_aval_bytes(v.aval) for v in eqn.outvars)
            elif name == "name":
                by += 2.0 * _aval_bytes(eqn.outvars[0].aval)  # store + load
            elif name in ("gather", "take", "dynamic_slice",
                          "dynamic_update_slice"):
                by += sum(_aval_bytes(v.aval) for v in eqn.outvars)
        return fl, by

    return walk(closed_jaxpr)


# ================================================================== report
@dataclass
class RooflineTerms:
    flops_per_chip: float
    bytes_per_chip: float
    wire_bytes_per_chip: float
    collectives: Dict[str, float]
    chips: int
    xla_flops_per_chip: float = 0.0
    xla_bytes_per_chip: float = 0.0
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    bottleneck: str = ""
    model_flops: float = 0.0
    useful_flops_ratio: float = 0.0
    step_time_bound_s: float = 0.0
    mfu_bound: float = 0.0

    def finalize(self, spec: DeviceSpec):
        self.compute_s = self.flops_per_chip / spec.peak_flops
        self.memory_s = self.bytes_per_chip / spec.hbm_bw
        self.collective_s = self.wire_bytes_per_chip / spec.ici_bw
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        self.bottleneck = max(terms, key=terms.get)
        self.step_time_bound_s = max(terms.values())
        if self.model_flops and self.step_time_bound_s > 0:
            self.mfu_bound = (self.model_flops
                              / (self.chips * spec.peak_flops
                                 * self.step_time_bound_s))
        if self.flops_per_chip:
            self.useful_flops_ratio = (self.model_flops
                                       / (self.flops_per_chip * self.chips))
        return self

    def to_dict(self):
        return asdict(self)


def analyze(compiled, chips: int, *, device_kind: str,
            model_flops: float = 0.0, hlo_text: Optional[str] = None,
            step_jaxpr=None) -> RooflineTerms:
    cost = compiled.cost_analysis()
    txt = hlo_text if hlo_text is not None else compiled.as_text()
    colls = collective_bytes(txt)
    xla_flops = float(cost.get("flops", 0.0))
    xla_bytes = float(cost.get("bytes accessed", 0.0))
    if step_jaxpr is not None:
        jf, jb = jaxpr_cost(step_jaxpr)
        flops_chip = max(jf / chips, xla_flops)
        bytes_chip = max(jb / chips, xla_bytes)
    else:
        flops_chip, bytes_chip = xla_flops, xla_bytes
    terms = RooflineTerms(
        flops_per_chip=flops_chip,
        bytes_per_chip=bytes_chip,
        wire_bytes_per_chip=float(sum(colls.values())),
        collectives=colls,
        chips=chips,
        xla_flops_per_chip=xla_flops,
        xla_bytes_per_chip=xla_bytes,
        model_flops=model_flops,
    )
    return terms.finalize(get_device_spec(device_kind))


def model_flops_train(param_count: int, tokens: int) -> float:
    return 6.0 * param_count * tokens


def model_flops_decode(param_count: int, batch: int) -> float:
    # one token per sequence: 2·N per token, forward only
    return 2.0 * param_count * batch

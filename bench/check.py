"""The comparison that decides ``correct`` for a training cell.

Set-up trains until the stage machine is Stable, then runs three more
steps through the window's own call and feed: the steps of the compiled
grad step that the window times.  The reference (``bench.reference``)
runs every step from the seed on the same batches, and the check compares:

- ``loss_gap``: worst relative gap of the losses, every step from the
  first;
- ``grad_gap``: worst leaf of the first compared step's gradient as the
  optimizer got it (clipped; the program's is read from its Adam state
  before and after that step, ``(m1 - b1 m0) / (1 - b1)``): the gap
  between the two norms of the leaf, over the reference's norm of that
  leaf or of the median leaf, whichever is larger;
- ``update_gap``: the same measure of the parameters' change over the
  three compared steps, leaving out leaves whose reference gradient is
  under a thousandth of the median leaf's (a key bias under softmax: Adam
  moves it by round-off alone);
- ``policy_share``: the share of window iterations that ran the policy
  of the compared steps.  A reading, not compared: the runtime's
  degradation ladder may move the Stable policy between rungs at any
  iteration, so a run compares whichever rung ran its compared steps.

A cell compares the numbers its limits file names; the others are printed
as readings.

A leaf is a tensor of one layer: stacked per-layer parameters count once
per layer, so a layer left unmoved or moved twice shows on its own.
"""
from __future__ import annotations

import math
from typing import Dict, Iterable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

STACKED = "blocks"           # leaves under this key are stacked per layer
MOVING_FRAC = 1e-3


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _norm(name: str, x):
    x = x.astype(jnp.float32)
    if name.startswith(STACKED + "/"):
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(x * x))


@jax.jit
def _tree_norms(tree):
    return jax.tree_util.tree_map_with_path(
        lambda p, x: _norm(_name(p), x), tree)


@jax.jit
def _diff_norm_stacked(a, b, s):
    d = a.astype(jnp.float32) - s * b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d, axis=tuple(range(1, d.ndim))))


@jax.jit
def _diff_norm(a, b, s):
    d = a.astype(jnp.float32) - s * b.astype(jnp.float32)
    return jnp.sqrt(jnp.sum(d * d))


def _expand(name: str, value) -> Dict[str, float]:
    value = np.asarray(value)
    if value.ndim:
        return {f"{name}[{i}]": float(v) for i, v in enumerate(value)}
    return {name: float(value)}


def leaf_norms(tree) -> Dict[str, float]:
    """Norm of every leaf of ``tree``, one per layer for stacked ones."""
    out: Dict[str, float] = {}
    flat = jax.tree_util.tree_flatten_with_path(
        jax.device_get(_tree_norms(tree)))[0]
    for path, n in flat:
        out.update(_expand(_name(path), n))
    return out


def diff_norms(a_tree, b_tree, b_scale: float = 1.0) -> Dict[str, float]:
    """Per-leaf norm of ``a - b_scale * b``, one leaf at a time (``b`` may
    live on the host), so no second copy of the whole tree is made."""
    out: Dict[str, float] = {}
    b_flat = dict((_name(p), x) for p, x in
                  jax.tree_util.tree_flatten_with_path(b_tree)[0])
    s = jnp.float32(b_scale)
    for path, a in jax.tree_util.tree_flatten_with_path(a_tree)[0]:
        name = _name(path)
        b = jax.device_put(b_flat[name], a.sharding)
        fn = _diff_norm_stacked if name.startswith(STACKED + "/") \
            else _diff_norm
        out.update(_expand(name, fn(a, b, s)))
        del b
    return out


def worst_gap(prog: Dict[str, float], ref: Dict[str, float],
              keep: Optional[Iterable[str]] = None) -> Tuple[float, str]:
    """Largest |prog - ref| / max(ref, median ref) over the leaves, and
    the leaf.  A leaf missing or not finite on the program's side is a gap
    of infinity."""
    med = float(np.median(list(ref.values())))
    names = ref if keep is None else set(keep) & set(ref)
    worst, where = 0.0, ""
    for k in names:
        p = prog.get(k, math.nan)
        gap = (abs(p - ref[k]) / max(ref[k], med, 1e-30)
               if math.isfinite(p) else math.inf)
        if gap > worst or not where:
            worst, where = gap, k
    return worst, where


def rel(a: float, b: float) -> float:
    return abs(a - b) / abs(b) if math.isfinite(a) else math.inf


def numbers(prog: dict, ref: dict) -> Dict[str, Tuple[float, str]]:
    """Each number, with where it was worst.  ``prog`` and ``ref`` hold
    ``losses`` and the ``grad`` and ``update`` norms; ``prog`` may hold
    ``policies``, what its compared steps ran, and ``window_policies``,
    the window's iterations by policy."""
    gaps = [rel(p, r) for p, r in zip(prog["losses"], ref["losses"])]
    if len(prog["losses"]) != len(ref["losses"]):
        gaps.append(math.inf)
    step = int(np.argmax(gaps))
    med = float(np.median(list(ref["grad"].values())))
    moving = [k for k, n in ref["grad"].items() if n >= MOVING_FRAC * med]
    out = {
        "loss_gap": (max(gaps), f"step {step}"),
        "grad_gap": worst_gap(prog["grad"], ref["grad"]),
        "update_gap": worst_gap(prog["update"], ref["update"], keep=moving),
    }
    if "window_policies" in prog:
        ran, win = set(prog["policies"]), prog["window_policies"]
        share = sum(n for p, n in win.items() if p in ran) / sum(win.values())
        out["policy_share"] = (share, f"compared {sorted(ran)}")
    return out


def verdict(nums: Dict[str, Tuple[float, str]],
            limits: Dict[str, float]) -> Tuple[bool, Dict[str, dict]]:
    """``correct`` and, for each number the limits name, its value, limit
    and worst place.  A limit on a number that was not computed fails."""
    checks, ok = {}, bool(limits)
    for name, limit in limits.items():
        value, where = nums.get(name, (math.inf, "not computed"))
        ok &= value <= limit
        checks[name] = {"value": value, "limit": limit, "at": where}
    return ok, checks

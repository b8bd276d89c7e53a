"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Models annotate activations/params with *logical* axis names; a rules table
maps logical names to mesh axes.  Outside of a mesh context every helper is a
no-op so the same model code runs in single-device tests, the Chameleon
runtime, and the 512-chip dry-run unchanged.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


# logical axis -> mesh axis (or tuple of mesh axes, or None = replicate)
DEFAULT_RULES = {
    "batch": ("pod", "data"),       # DP across pods and the data axis
    "seq": None,
    "act_embed": None,
    "act_heads": "model",           # activation head dim (TP)
    "act_kv_heads": None,           # GQA: few kv heads -> replicated
    "act_mlp": "model",
    "act_vocab": "model",
    "kv_seq": "model",              # decode-time sequence parallelism over KV
    # --- parameters ---
    "embed": None,                  # param d_model dim
    "fsdp_embed": ("pod", "data"),  # ZeRO-3/FSDP shard dim for big params
    "heads": "model",
    "kv_heads": None,
    "q_dim": "model",               # fused num_heads*head_dim
    "kv_dim": None,
    "mlp": "model",
    "vocab": "model",
    "experts": "model",             # expert parallelism
    "expert_mlp": None,
    "layers": None,                 # stacked scan dim
    "conv": None,
    "ssm_inner": "model",
    "ssm_state": None,
    "ssm_heads": "model",
    "pos": None,
    "scalar": None,
}

# §Perf hillclimb: swap frees the memory that forced tensor parallelism, so
# the whole mesh becomes a DP domain (paper Table 2's TP->DP substitution).
# Params/optimizer shard over every axis (ZeRO-3 via rules); activations
# shard on batch only; all per-layer TP collectives disappear in favor of
# ZeRO param all-gathers + grad reduce-scatters.
DP_ONLY_RULES = {
    "batch": ("pod", "data", "model"),
    "embed": ("pod", "data", "model"),
    "fsdp_embed": ("pod", "data", "model"),
    "heads": None, "q_dim": None, "kv_dim": None, "mlp": None,
    "vocab": None, "experts": None, "expert_mlp": None,
    "ssm_inner": None, "ssm_heads": None,
    "act_heads": None, "act_mlp": None, "act_vocab": None, "kv_seq": None,
}


class _Ctx(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.rules: dict = dict(DEFAULT_RULES)


_CTX = _Ctx()


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Install mesh + logical rules for model sharding annotations.
    Nested calls inherit the enclosing context's rules (so e.g. a dp_only
    outer context composes with the ZeRO overrides applied inside
    spec-building helpers)."""
    prev = (_CTX.mesh, _CTX.rules)
    base = _CTX.rules if _CTX.mesh is not None else DEFAULT_RULES
    _CTX.mesh = mesh
    merged = dict(base)
    if rules:
        merged.update(rules)
    _CTX.rules = merged
    try:
        if mesh is not None:
            with mesh:
                yield
        else:
            yield
    finally:
        _CTX.mesh, _CTX.rules = prev


def current_mesh() -> Optional[Mesh]:
    return _CTX.mesh


def _resolve(name: Optional[str], mesh: Mesh):
    if name is None:
        return None
    ax = _CTX.rules.get(name, None)
    if ax is None:
        return None
    if isinstance(ax, tuple):
        present = tuple(a for a in ax if a in mesh.axis_names)
        return present if present else None
    return ax if ax in mesh.axis_names else None


def spec(logical: Sequence[Optional[str]]) -> P:
    mesh = _CTX.mesh
    if mesh is None:
        return P()
    return P(*[_resolve(n, mesh) for n in logical])


def sharding(logical: Sequence[Optional[str]]) -> Optional[NamedSharding]:
    mesh = _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, spec(logical))


def constrain(x, logical: Sequence[Optional[str]]):
    """with_sharding_constraint against the active mesh; no-op without one."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec(logical)))


def tree_sharding(axes_tree, mesh: Optional[Mesh] = None):
    """Map a pytree of logical-axis tuples to a pytree of NamedShardings."""
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return None

    def one(axes):
        return NamedSharding(mesh, P(*[_resolve(n, mesh) for n in axes]))

    return jax.tree.map(one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(e, (str, type(None))) for e in x))


def tree_spec(axes_tree, mesh: Optional[Mesh] = None):
    mesh = mesh or _CTX.mesh

    def one(axes):
        if mesh is None:
            return P()
        return P(*[_resolve(n, mesh) for n in axes])

    return jax.tree.map(one, axes_tree,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(e, (str, type(None))) for e in x))

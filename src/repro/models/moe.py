"""Token-choice top-k Mixture-of-Experts with capacity-based sort dispatch.

TPU-idiomatic formulation: no (T, E, C) one-hot dispatch tensor (T5X-style
memory blow-up at 32k sequences); instead tokens are argsorted by expert id,
ranked within their expert group, and gathered into a dense (E, C, d) batch
whose expert dim shards on the ``model`` mesh axis (expert parallelism).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.core.sites import tag
from repro.distributed import sharding as shd
from repro.models.layers import dense_init, _act


def init_moe(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.moe_d_ff
    std = 1.0 / math.sqrt(d)
    dt = jnp.dtype(cfg.param_dtype)
    p = {
        "router": dense_init(ks[0], d, E, cfg),
        "wi_gate": (jax.random.normal(ks[1], (E, d, f)) * std).astype(dt),
        "wi_up": (jax.random.normal(ks[2], (E, d, f)) * std).astype(dt),
        "wo": (jax.random.normal(ks[3], (E, f, d)) * (1.0 / math.sqrt(f))).astype(dt),
    }
    a = {
        "router": ("embed", "experts"),
        "wi_gate": ("experts", "embed", "expert_mlp"),
        "wi_up": ("experts", "embed", "expert_mlp"),
        "wo": ("experts", "expert_mlp", "embed"),
    }
    return p, a


def capacity(cfg: ModelConfig, num_tokens: int) -> int:
    c = int(math.ceil(num_tokens * cfg.experts_per_token
                      * cfg.moe_capacity_factor / cfg.num_experts))
    return max(8, -(-c // 8) * 8)  # round up to a multiple of 8


# ------------------------------------------------------------ EP fast path
def apply_moe_ep(cfg: ModelConfig, p, x):
    """Expert-parallel MoE via shard_map (hillclimb optimization, see
    EXPERIMENTS.md §Perf cell A).

    The naive pjit lowering of the sort-based dispatch produced ~21 TB/chip
    of all-reduce per step (XLA replicates the scatter/gather chain).  Under
    shard_map, routing + dispatch are *local* to each (pod, data) shard —
    tokens never cross the data axis — and each model rank computes only
    its E/tp experts over its local tokens; the only communication is one
    psum of the (tokens_local, d) combine over the ``model`` axis per layer
    (268 MB/chip/layer at qwen3-moe train_4k vs ~450 GB before).
    """
    mesh = shd.current_mesh()
    assert mesh is not None and "model" in mesh.axis_names
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    from jax.sharding import PartitionSpec as P

    tp = mesh.shape["model"]
    E = cfg.num_experts
    assert E % tp == 0, (E, tp)

    def local_moe(xl, router, wg, wu, wo):
        # xl (B_loc, S, d); wg/wu/wo lead with E_loc = E/tp
        Bl, S, d = xl.shape
        E_loc = wg.shape[0]
        K = cfg.experts_per_token
        T = Bl * S
        C = capacity(cfg, T)
        xf = xl.reshape(T, d)
        r_idx = jax.lax.axis_index("model")
        e_lo = r_idx * E_loc

        logits = jnp.einsum("td,de->te", xf, router).astype(jnp.float32)
        logits = tag(logits, "router_logits")
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_idx = jax.lax.top_k(probs, K)          # (T,K)
        gate_vals = gate_vals / jnp.sum(gate_vals, -1, keepdims=True)

        me = jnp.mean(probs, axis=0)
        ce = jnp.mean(jnp.sum(
            jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=1), axis=0)
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

        # local slice of the assignment: experts in [e_lo, e_lo + E_loc)
        N = T * K
        e_flat = expert_idx.reshape(N) - e_lo
        mine = (e_flat >= 0) & (e_flat < E_loc)
        e_local = jnp.where(mine, e_flat, E_loc)                  # E_loc=drop
        sort_idx = jnp.argsort(e_local, stable=True)
        sorted_e = e_local[sort_idx]
        first = jnp.searchsorted(sorted_e, jnp.arange(E_loc), side="left")
        pos = jnp.arange(N) - first[jnp.minimum(sorted_e, E_loc - 1)]
        keep = (sorted_e < E_loc) & (pos < C)
        slot = jnp.where(keep, sorted_e * C + pos, E_loc * C)
        tok = sort_idx // K
        slot_tok = jnp.zeros((E_loc * C + 1,), jnp.int32).at[slot].set(
            tok.astype(jnp.int32) + 1, mode="drop")[: E_loc * C]
        expert_in = (xf[jnp.maximum(slot_tok - 1, 0)]
                     * (slot_tok > 0)[:, None].astype(xl.dtype))
        expert_in = tag(expert_in.reshape(E_loc, C, d), "moe_dispatch")

        gate = jnp.einsum("ecd,edf->ecf", expert_in, wg)
        up = jnp.einsum("ecd,edf->ecf", expert_in, wu)
        h = tag(_act(cfg, gate) * up, "moe_act")
        expert_out = jnp.einsum("ecf,efd->ecd", h, wo)

        out_flat = jnp.concatenate(
            [expert_out.reshape(E_loc * C, d),
             jnp.zeros((1, d), expert_out.dtype)], axis=0)
        y_sorted = out_flat[slot]
        inv = jnp.argsort(sort_idx, stable=True)
        y = y_sorted[inv].reshape(T, K, d)
        out = jnp.sum(y * gate_vals[..., None].astype(y.dtype), axis=1)
        # combine partial expert outputs across the model axis
        out = jax.lax.psum(out, "model")
        out = tag(out.reshape(Bl, S, d), "moe_out")
        if batch_axes:
            aux = jax.lax.pmean(aux, batch_axes)
        return out, aux

    sm = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(batch_axes or None, None, None),   # x
                  P(None, None),                        # router
                  P("model", None, None),               # wi_gate
                  P("model", None, None),               # wi_up
                  P("model", None, None)),              # wo
        out_specs=(P(batch_axes or None, None, None), P()),
        check_vma=False)
    return sm(x, p["router"], p["wi_gate"], p["wi_up"], p["wo"])


def apply_moe_auto(cfg: ModelConfig, p, x):
    """EP fast path when the active rules put experts on the model axis,
    else the portable gather implementation (also used under dp_only
    rules, where experts are data-local)."""
    mesh = shd.current_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and cfg.num_experts % mesh.shape["model"] == 0
            and tuple(shd.spec(("experts",)))[:1] == ("model",)):
        return apply_moe_ep(cfg, p, x)
    return apply_moe(cfg, p, x)


def apply_moe(cfg: ModelConfig, p, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B,S,d) -> (out (B,S,d), aux_loss scalar)."""
    B, S, d = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S
    C = capacity(cfg, T)
    xf = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xf, p["router"]).astype(jnp.float32)
    logits = tag(logits, "router_logits")
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)           # (T,K)
    gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

    # ---- load-balance aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)                               # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(expert_idx, E, dtype=jnp.float32), axis=1), axis=0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- sort-based dispatch
    N = T * K
    e_flat = expert_idx.reshape(N)
    sort_idx = jnp.argsort(e_flat, stable=True)                # (N,)
    sorted_e = e_flat[sort_idx]
    first = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    pos = jnp.arange(N) - first[sorted_e]
    keep = pos < C
    slot = jnp.where(keep, sorted_e * C + pos, E * C)          # E*C = drop slot
    tok = sort_idx // K
    # slot -> token map (0 = empty)
    slot_tok = jnp.zeros((E * C + 1,), jnp.int32).at[slot].set(
        tok.astype(jnp.int32) + 1, mode="drop")
    slot_tok = slot_tok[: E * C]
    expert_in = xf[jnp.maximum(slot_tok - 1, 0)] * (slot_tok > 0)[:, None].astype(x.dtype)
    expert_in = expert_in.reshape(E, C, d)
    expert_in = shd.constrain(expert_in, ("experts", None, "act_embed"))
    expert_in = tag(expert_in, "moe_dispatch")

    # ---- expert computation (E sharded on `model`)
    gate = jnp.einsum("ecd,edf->ecf", expert_in, p["wi_gate"])
    up = jnp.einsum("ecd,edf->ecf", expert_in, p["wi_up"])
    h = _act(cfg, gate) * up
    h = shd.constrain(h, ("experts", None, "expert_mlp"))
    h = tag(h, "moe_act")
    expert_out = jnp.einsum("ecf,efd->ecd", h, p["wo"])
    expert_out = shd.constrain(expert_out, ("experts", None, "act_embed"))

    # ---- combine
    out_flat = jnp.concatenate(
        [expert_out.reshape(E * C, d),
         jnp.zeros((1, d), expert_out.dtype)], axis=0)
    y_sorted = out_flat[slot]                                  # (N, d)
    inv = jnp.argsort(sort_idx, stable=True)
    y = y_sorted[inv].reshape(T, K, d)
    out = jnp.sum(y * gate_vals[..., None].astype(y.dtype), axis=1)
    out = tag(out.reshape(B, S, d), "moe_out")
    out = shd.constrain(out, ("batch", "seq", "act_embed"))
    return out, aux.astype(jnp.float32)

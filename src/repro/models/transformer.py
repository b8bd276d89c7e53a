"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

Layer stacks are scanned (``lax.scan`` over stacked params) so the HLO stays
small and remat/offload policies apply per scan step.  Heterogeneous stacks
(vlm: cross-attn every k; hybrid: shared attention block every k) scan over
*segments* with the irregular block applied inside the segment body.

``policy`` threads a ``jax.checkpoint`` policy (produced by the Chameleon
executor) into every scanned block — this is how a generated swap policy is
*applied* to the training program.  A policy that offloads
(``OffloadSites``) is run by the dense and MoE stacks themselves, pipelined
across layers (``repro.models.pipelined``), outside a mesh.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.common.config import ModelConfig
from repro.core.executor import OffloadSites
from repro.core.sites import tag
from repro.distributed import sharding as shd
from repro.models import attention as attn
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import pipelined
from repro.models import ssm as ssm_lib


# ===================================================================== init
def _init_dense_block(key, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 6)
    p, a = {}, {}
    p["ln1"], a["ln1"] = L.init_norm(cfg)
    p["attn"], a["attn"] = attn.init_attention(ks[0], cfg)
    if cross:
        p["lnx"], a["lnx"] = L.init_norm(cfg)
        p["xattn"], a["xattn"] = attn.init_attention(ks[1], cfg)
        p["xgate"] = jnp.zeros((), jnp.float32)
        a["xgate"] = ()  # rank-0: stacked form is rank-1 ("layers",)
    p["ln2"], a["ln2"] = L.init_norm(cfg)
    if cfg.family == "moe" and not cross:
        p["moe"], a["moe"] = moe_lib.init_moe(ks[2], cfg)
    else:
        p["mlp"], a["mlp"] = L.init_mlp(ks[2], cfg)
    return p, a


def _init_ssm_block(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    p, a = {}, {}
    p["ln"], a["ln"] = L.init_norm(cfg)
    p["ssm"], a["ssm"] = ssm_lib.init_ssm(ks[0], cfg)
    return p, a


def _stack_init(init_fn, key, n: int):
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_fn(k)[0])(keys)
    _, axes = init_fn(key)  # single-layer axes; prepend the layers axis
    axes = jax.tree.map(lambda t: ("layers",) + t, axes,
                        is_leaf=lambda x: isinstance(x, tuple)
                        and all(isinstance(e, (str, type(None))) for e in x))
    return params, axes


def init_model(cfg: ModelConfig, key) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    ks = jax.random.split(key, 8)
    p, a = {}, {}
    p["embed"], a["embed"] = L.init_embedding(ks[0], cfg)
    p["ln_f"], a["ln_f"] = L.init_norm(cfg)

    fam = cfg.family
    if fam in ("dense", "moe"):
        p["blocks"], a["blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg), ks[1], cfg.num_layers)
    elif fam == "ssm":
        p["blocks"], a["blocks"] = _stack_init(
            lambda k: _init_ssm_block(k, cfg), ks[1], cfg.num_layers)
    elif fam == "hybrid":
        p["blocks"], a["blocks"] = _stack_init(
            lambda k: _init_ssm_block(k, cfg), ks[1], cfg.num_layers)
        # zamba2: one *shared* attention block reused at every attn position
        p["shared_attn"], a["shared_attn"] = _init_dense_block(ks[2], cfg)
    elif fam == "vlm":
        every = cfg.cross_attn_every
        n_cross = cfg.num_layers // every
        n_self = cfg.num_layers - n_cross
        p["blocks"], a["blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg), ks[1], n_self)
        p["cross_blocks"], a["cross_blocks"] = _stack_init(
            lambda k: _init_dense_block(k, cfg, cross=True), ks[2], n_cross)
    else:
        raise ValueError(fam)
    return p, a


# ================================================================= blocks
def dense_block(cfg: ModelConfig, p, x, positions, cross_kv=None,
                causal: bool = True):
    """Pre-norm transformer block; returns (x, aux).

    ``ln_in`` tags the layer input ONCE and every path consumes the tagged
    value, so it *is* the scan carry for remat purposes — offloading
    ``ln_in`` offloads the per-layer residual-stream snapshot (the MaxText
    decoder_layer_input pattern; §Perf cell C iter 4)."""
    aux = jnp.zeros((), jnp.float32)
    x = tag(x, "ln_in")
    h = L.apply_norm(cfg, p["ln1"], x)
    with jax.named_scope("attention"):
        x = x + attn.self_attention(cfg, p["attn"], h, positions,
                                    causal=causal)
    x = tag(x, "resid_mid")
    if cross_kv is not None and "xattn" in p:
        h = L.apply_norm(cfg, p["lnx"], x)
        xa = attn.cross_attention(cfg, p["xattn"], h, cross_kv)
        x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xa
    h = L.apply_norm(cfg, p["ln2"], x)
    with jax.named_scope("ffn"):
        if "moe" in p:
            out, aux = moe_lib.apply_moe_auto(cfg, p["moe"], h)
        else:
            out = L.apply_mlp(cfg, p["mlp"], h)
    x = x + out
    return tag(x, "resid_post"), aux


def ssm_block(cfg: ModelConfig, p, x):
    x = tag(x, "ln_in")
    h = L.apply_norm(cfg, p["ln"], x)
    x = x + ssm_lib.apply_ssm(cfg, p["ssm"], h)
    return tag(x, "resid_post")


def _maybe_ckpt(fn, policy):
    if policy is None:
        return fn
    if policy == "full_remat":
        return jax.checkpoint(fn)
    if isinstance(policy, OffloadSites):
        policy = policy.checkpoint_policy
    return jax.checkpoint(fn, policy=policy)


# ============================================================ full forward
def forward(cfg: ModelConfig, params, tokens, *, positions=None,
            memory=None, policy=None, causal: bool = True):
    """tokens (B,S) -> (logits (B,S,V), aux).  ``memory`` is the stub
    modality frontend output for vlm (image patch embeds, (B,T_img,d))."""
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = L.embed_tokens(cfg, params["embed"], tokens, positions)
    aux_total = jnp.zeros((), jnp.float32)
    fam = cfg.family

    if fam in ("dense", "moe") and isinstance(policy, OffloadSites) \
            and shd.current_mesh() is None:
        x, aux_total = pipelined.offloaded_scan(
            lambda x, lp, pos: dense_block(cfg, lp, x, pos),
            x, params["blocks"], positions, policy)

    elif fam in ("dense", "moe"):
        def body(carry, lp):
            x, aux = carry
            x, a = dense_block(cfg, lp, x, positions)
            return (x, aux + a), None
        (x, aux_total), _ = jax.lax.scan(
            _maybe_ckpt(body, policy), (x, aux_total), params["blocks"])

    elif fam == "ssm":
        def body(x, lp):
            return ssm_block(cfg, lp, x), None
        x, _ = jax.lax.scan(_maybe_ckpt(body, policy), x, params["blocks"])

    elif fam == "hybrid":
        every = cfg.hybrid_attn_every
        n_seg, rem = divmod(cfg.num_layers, every)
        seg_p = jax.tree.map(
            lambda t: t[: n_seg * every].reshape((n_seg, every) + t.shape[1:]),
            params["blocks"])
        shared = params["shared_attn"]

        def seg_body(carry, sp):
            x, aux = carry
            def inner(xc, lp):
                return ssm_block(cfg, lp, xc), None
            x, _ = jax.lax.scan(inner, x, sp)
            x, a = dense_block(cfg, shared, x, positions)
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            _maybe_ckpt(seg_body, policy), (x, aux_total), seg_p)
        if rem:
            rem_p = jax.tree.map(lambda t: t[n_seg * every:], params["blocks"])
            def inner(xc, lp):
                return ssm_block(cfg, lp, xc), None
            x, _ = jax.lax.scan(_maybe_ckpt(inner, policy), x, rem_p)

    elif fam == "vlm":
        assert memory is not None, "vlm needs image patch embeddings (stub frontend)"
        every = cfg.cross_attn_every
        n_cross = cfg.num_layers // every
        n_self = cfg.num_layers - n_cross
        inner_self = every - 1
        # project cross KV once per cross block (scanned)
        def kv_one(cp):
            return attn.project_cross_kv(cfg, cp["xattn"], memory)
        cross_kv = jax.vmap(kv_one)(params["cross_blocks"])  # stacked (n_cross, ...)
        g_self = jax.tree.map(
            lambda t: t[: n_cross * inner_self].reshape(
                (n_cross, inner_self) + t.shape[1:]), params["blocks"])

        def seg_body(carry, inp):
            x, aux = carry
            sp, cp, kv = inp
            def inner(c, lp):
                xc, auxc = c
                xc, a = dense_block(cfg, lp, xc, positions)
                return (xc, auxc + a), None
            (x, aux), _ = jax.lax.scan(inner, (x, aux), sp)
            x, a = dense_block(cfg, cp, x, positions, cross_kv=kv)
            return (x, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            _maybe_ckpt(seg_body, policy), (x, aux_total),
            (g_self, params["cross_blocks"], cross_kv))
        rem = n_self - n_cross * inner_self
        if rem:
            rem_p = jax.tree.map(lambda t: t[n_cross * inner_self:], params["blocks"])
            def inner(c, lp):
                xc, auxc = c
                xc, a = dense_block(cfg, lp, xc, positions)
                return (xc, auxc + a), None
            (x, aux_total), _ = jax.lax.scan(
                _maybe_ckpt(inner, policy), (x, aux_total), rem_p)
    else:
        raise ValueError(fam)

    x = L.apply_norm(cfg, params["ln_f"], x)
    x = tag(x, "final_norm")
    logits = L.unembed(cfg, params["embed"], x)
    return logits, aux_total


def loss_fn(cfg: ModelConfig, params, batch, *, policy=None):
    logits, aux = forward(cfg, params, batch["tokens"], policy=policy,
                          memory=batch.get("memory"))
    with jax.named_scope("loss"):
        loss = L.cross_entropy(logits, batch["labels"], batch.get("mask"))
    return loss + aux, {"xent": loss, "aux": aux}


# ============================================================ decode paths
class DecodeState(NamedTuple):
    """Per-request generation state (stacked over layers where applicable)."""
    attn_k: Optional[jnp.ndarray]    # (L_attn, B, Smax, Kh, D)
    attn_v: Optional[jnp.ndarray]
    ssm_conv: Optional[jnp.ndarray]  # (L_ssm, B, W-1, ch)
    ssm_ssd: Optional[jnp.ndarray]   # (L_ssm, B, H, P, N)
    cross_k: Optional[jnp.ndarray]   # (L_cross, B, T_mem, Kh, D)
    cross_v: Optional[jnp.ndarray]
    pos: jnp.ndarray                 # (B,) next write index


def _n_attn_layers(cfg: ModelConfig) -> int:
    if cfg.family in ("dense", "moe"):
        return cfg.num_layers
    if cfg.family == "vlm":
        return cfg.num_layers  # self-attn in every layer (cross layers too)
    if cfg.family == "hybrid":
        return cfg.num_layers // cfg.hybrid_attn_every
    return 0


def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      memory=None, params=None) -> DecodeState:
    dt = jnp.dtype(cfg.dtype)
    n_attn = _n_attn_layers(cfg)
    ak = av = None
    if n_attn:
        shape = (n_attn, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
        ak, av = jnp.zeros(shape, dt), jnp.zeros(shape, dt)
    sc = sd = None
    if cfg.family in ("ssm", "hybrid"):
        n_ssm = cfg.num_layers
        sc = jnp.zeros((n_ssm, batch, cfg.ssm_conv_width - 1,
                        cfg.ssm_d_inner + 2 * cfg.ssm_state), dt)
        sd = jnp.zeros((n_ssm, batch, cfg.ssm_heads, cfg.ssm_head_dim,
                        cfg.ssm_state), jnp.float32)
    ck = cv = None
    if cfg.family == "vlm":
        assert memory is not None and params is not None
        def kv_one(cp):
            return attn.project_cross_kv(cfg, cp["xattn"], memory)
        ck, cv = jax.vmap(kv_one)(params["cross_blocks"])
    return DecodeState(ak, av, sc, sd, ck, cv,
                       jnp.zeros((batch,), jnp.int32))


def _dense_decode_block(cfg, p, x, kv, positions, cross_kv=None):
    h = L.apply_norm(cfg, p["ln1"], x)
    a_out, kv = attn.decode_self_attention(cfg, p["attn"], h, kv, positions)
    x = x + a_out
    if cross_kv is not None and "xattn" in p:
        h = L.apply_norm(cfg, p["lnx"], x)
        xa = attn.cross_attention(cfg, p["xattn"], h, cross_kv)
        x = x + jnp.tanh(p["xgate"]).astype(x.dtype) * xa
    h = L.apply_norm(cfg, p["ln2"], x)
    if "moe" in p:
        out, _ = moe_lib.apply_moe(cfg, p["moe"], h)
    else:
        out = L.apply_mlp(cfg, p["mlp"], h)
    return x + out, kv


def _ssm_decode_block(cfg, p, x, state):
    h = L.apply_norm(cfg, p["ln"], x)
    out, state = ssm_lib.decode_ssm(cfg, p["ssm"], h, state)
    return x + out, state


def decode_step(cfg: ModelConfig, params, tokens, state: DecodeState):
    """tokens (B,1) -> (logits (B,1,V), new state)."""
    B = tokens.shape[0]
    positions = state.pos
    x = L.embed_tokens(cfg, params["embed"], tokens, positions[:, None])
    fam = cfg.family

    if fam in ("dense", "moe"):
        def body(x, inp):
            lp, k, v = inp
            x, (k, v) = _dense_decode_block(cfg, lp, x, (k, v), positions)
            return x, (k, v)
        x, (nk, nv) = jax.lax.scan(body, x, (params["blocks"], state.attn_k, state.attn_v))
        state = state._replace(attn_k=nk, attn_v=nv)

    elif fam == "ssm":
        def body(x, inp):
            lp, c, s = inp
            x, (c, s) = _ssm_decode_block(cfg, lp, x, (c, s))
            return x, (c, s)
        x, (nc, ns) = jax.lax.scan(body, x, (params["blocks"], state.ssm_conv, state.ssm_ssd))
        state = state._replace(ssm_conv=nc, ssm_ssd=ns)

    elif fam == "hybrid":
        every = cfg.hybrid_attn_every
        n_seg, rem = divmod(cfg.num_layers, every)
        shared = params["shared_attn"]
        seg_p = jax.tree.map(
            lambda t: t[: n_seg * every].reshape((n_seg, every) + t.shape[1:]),
            params["blocks"])
        seg_c = jax.tree.map(
            lambda t: t[: n_seg * every].reshape((n_seg, every) + t.shape[1:]),
            (state.ssm_conv, state.ssm_ssd))

        def seg_body(x, inp):
            sp, (cs, ss), k, v = inp
            def inner(xc, i2):
                lp, c, s = i2
                xc, (c, s) = _ssm_decode_block(cfg, lp, xc, (c, s))
                return xc, (c, s)
            x, (cs, ss) = jax.lax.scan(inner, x, (sp, cs, ss))
            x, (k, v) = _dense_decode_block(cfg, shared, x, (k, v), positions)
            return x, ((cs, ss), k, v)

        x, ((nc, ns), nk, nv) = jax.lax.scan(
            seg_body, x, (seg_p, seg_c, state.attn_k, state.attn_v))
        nc = nc.reshape((n_seg * every,) + nc.shape[2:])
        ns = ns.reshape((n_seg * every,) + ns.shape[2:])
        if rem:
            rem_p = jax.tree.map(lambda t: t[n_seg * every:], params["blocks"])
            def inner(xc, i2):
                lp, c, s = i2
                xc, (c, s) = _ssm_decode_block(cfg, lp, xc, (c, s))
                return xc, (c, s)
            x, (rc, rs) = jax.lax.scan(
                inner, x, (rem_p, state.ssm_conv[n_seg * every:],
                           state.ssm_ssd[n_seg * every:]))
            nc = jnp.concatenate([nc, rc], axis=0)
            ns = jnp.concatenate([ns, rs], axis=0)
        state = state._replace(ssm_conv=nc, ssm_ssd=ns, attn_k=nk, attn_v=nv)

    elif fam == "vlm":
        every = cfg.cross_attn_every
        n_cross = cfg.num_layers // every
        inner_self = every - 1
        n_self = cfg.num_layers - n_cross
        # self-attn caches: first n_cross*inner_self belong to grouped selves,
        # then n_cross cross layers, then remainder selves.
        kks, vvs = state.attn_k, state.attn_v
        g_self = jax.tree.map(
            lambda t: t[: n_cross * inner_self].reshape(
                (n_cross, inner_self) + t.shape[1:]), params["blocks"])
        ks_g = kks[: n_cross * inner_self].reshape(
            (n_cross, inner_self) + kks.shape[1:])
        vs_g = vvs[: n_cross * inner_self].reshape(
            (n_cross, inner_self) + vvs.shape[1:])
        ks_c = kks[n_cross * inner_self: n_cross * inner_self + n_cross]
        vs_c = vvs[n_cross * inner_self: n_cross * inner_self + n_cross]

        def seg_body(x, inp):
            sp, k, v, cp, kc, vc, xk, xv = inp
            def inner(xc, i2):
                lp, kk, vv = i2
                xc, (kk, vv) = _dense_decode_block(cfg, lp, xc, (kk, vv), positions)
                return xc, (kk, vv)
            x, (k, v) = jax.lax.scan(inner, x, (sp, k, v))
            x, (kc, vc) = _dense_decode_block(cfg, cp, x, (kc, vc), positions,
                                              cross_kv=(xk, xv))
            return x, (k, v, kc, vc)

        x, (nkg, nvg, nkc, nvc) = jax.lax.scan(
            seg_body, x, (g_self, ks_g, vs_g, params["cross_blocks"],
                          ks_c, vs_c, state.cross_k, state.cross_v))
        nk = jnp.concatenate([nkg.reshape((-1,) + nkg.shape[2:]), nkc], axis=0)
        nv = jnp.concatenate([nvg.reshape((-1,) + nvg.shape[2:]), nvc], axis=0)
        rem = n_self - n_cross * inner_self
        if rem:
            rem_p = jax.tree.map(lambda t: t[n_cross * inner_self:], params["blocks"])
            base = n_cross * inner_self + n_cross
            def inner(xc, i2):
                lp, kk, vv = i2
                xc, (kk, vv) = _dense_decode_block(cfg, lp, xc, (kk, vv), positions)
                return xc, (kk, vv)
            x, (rk, rv) = jax.lax.scan(inner, x, (rem_p, kks[base:], vvs[base:]))
            nk = jnp.concatenate([nk, rk], axis=0)
            nv = jnp.concatenate([nv, rv], axis=0)
        state = state._replace(attn_k=nk, attn_v=nv)
    else:
        raise ValueError(fam)

    x = L.apply_norm(cfg, params["ln_f"], x)
    logits = L.unembed(cfg, params["embed"], x)
    return logits, state._replace(pos=state.pos + 1)


def prefill(cfg: ModelConfig, params, tokens, max_len: int, memory=None,
            policy=None):
    """Run the full-sequence forward and build the decode state.

    For attention families the KV cache is materialized by re-projecting K/V
    per layer (cheap relative to the forward); SSM families carry their final
    state out of the chunked scan."""
    B, S = tokens.shape
    logits, _ = forward(cfg, params, tokens, memory=memory, policy=policy)
    state = init_decode_state(cfg, B, max_len, memory=memory, params=params)

    # Re-run a light pass to collect per-layer states.  We reuse forward's
    # block structure but only track the stateful pieces.
    positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = L.embed_tokens(cfg, params["embed"], tokens, positions)
    fam = cfg.family

    def attn_kv_from(h, lp):
        hn = L.apply_norm(cfg, lp["ln1"], h)
        k, v = attn._project_kv(cfg, lp["attn"], hn)
        if cfg.pos_embedding == "rope":
            cos, sin = L.rope_frequencies(cfg, positions)
            k = L.apply_rope(k, cos, sin)
        return k, v

    if fam in ("dense", "moe"):
        def body(carry, lp):
            x, _aux = carry
            k, v = attn_kv_from(x, lp)
            x, a = dense_block(cfg, lp, x, positions)
            return (x, _aux + a), (k, v)
        (_, _), (ks, vs) = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                        params["blocks"])
        pad = max_len - S
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        state = state._replace(attn_k=ks.astype(state.attn_k.dtype),
                               attn_v=vs.astype(state.attn_v.dtype))
    elif fam == "ssm":
        def body(x, lp):
            h = L.apply_norm(cfg, lp["ln"], x)
            st = _ssm_final_state(cfg, lp["ssm"], h)
            x = ssm_block(cfg, lp, x)
            return x, st
        x, (convs, ssds) = jax.lax.scan(body, x, params["blocks"])
        state = state._replace(ssm_conv=convs.astype(state.ssm_conv.dtype),
                               ssm_ssd=ssds)
    else:
        # hybrid / vlm prefill reuse decode_step token-by-token in serving;
        # the benchmark shapes only exercise dense/moe/ssm prefill.
        pass
    return logits, state._replace(pos=jnp.full((B,), S, jnp.int32))


def _ssm_final_state(cfg, p, x):
    """Compute (conv_state, ssd_state) after consuming x (B,S,d)."""
    B, S, _ = x.shape
    di, ds, nh, hp = (cfg.ssm_d_inner, cfg.ssm_state, cfg.ssm_heads,
                      cfg.ssm_head_dim)
    proj = jnp.einsum("bsd,dp->bsp", x, p["in_proj"])
    _, xbc, dt_raw = ssm_lib._split_proj(cfg, proj)
    W = cfg.ssm_conv_width
    conv_state = xbc[:, S - (W - 1):, :] if S >= W - 1 else jnp.pad(
        xbc, ((0, 0), (W - 1 - S, 0), (0, 0)))
    xbc_c = ssm_lib._causal_conv(cfg, p, xbc)
    xs = xbc_c[..., :di].reshape(B, S, nh, hp)
    Bm = xbc_c[..., di: di + ds]
    Cm = xbc_c[..., di + ds:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    _, final = ssm_lib.ssd_chunked(xs, dt, A, Bm, Cm, cfg.ssm_chunk)
    return conv_state.astype(x.dtype), final

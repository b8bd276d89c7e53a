"""Blockwise flash attention — Pallas TPU kernel.

TPU adaptation (not a CUDA port): the KV dimension is the *sequential* minor
grid axis; running (m, l, acc) statistics live in VMEM scratch that persists
across grid steps (the TPU analogue of a CUDA thread-block's registers/SMEM
accumulator).  Q/K/V tiles are MXU-aligned (128-multiple block sizes for
full tiles); GQA is handled in the K/V index_map (``h // group``), so grouped
query heads stream the same K/V tile without replication in HBM.

Causal masking skips fully-masked KV blocks with ``pl.when`` (no wasted MXU
work past the diagonal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                 acc_scr, *, causal: bool, sm_scale: float, block_q: int,
                 block_k: int, num_kv_blocks: int):
    kv_len = lens_ref[pl.program_id(0)]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _body():
        q = q_ref[...].astype(jnp.float32) * sm_scale        # (bq, d)
        k = k_ref[...].astype(jnp.float32)                    # (bk, d)
        v = v_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (bq,bk)
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(kpos < kv_len, s, NEG_INF)             # padded-KV mask
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    if causal:
        # skip KV blocks entirely above the causal diagonal
        pl.when(ki * block_k <= (qi + 1) * block_q - 1)(_body)
    else:
        _body()

    @pl.when(ki == num_kv_blocks - 1)
    def _emit():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool, sm_scale: float,
                        block_q: int = 128, block_k: int = 128,
                        kv_lens=None, interpret: bool = False):
    """q (B, H, Sq, D); k/v (B, Kh, Sk, D); H % Kh == 0.  Returns (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    Kh, Sk = k.shape[1], k.shape[2]
    G = H // Kh
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k

    kernel = functools.partial(
        _attn_kernel, causal=causal, sm_scale=sm_scale,
        block_q=block_q, block_k=block_k, num_kv_blocks=nk)
    if kv_lens is None:
        kv_lens = jnp.full((B,), Sk, jnp.int32)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, qi, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(kv_lens.astype(jnp.int32), q, k, v)


def _decode_kernel(lens_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                   acc_scr, *, sm_scale: float, block_k: int,
                   num_kv_blocks: int):
    kv_len = lens_ref[pl.program_id(0)]
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32) * sm_scale        # (1, d)
    k = k_ref[...].astype(jnp.float32)                   # (bk, d)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (1,bk)
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(kpos < kv_len, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, -1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == num_kv_blocks - 1)
    def _emit():
        o_ref[...] = (acc_scr[...]
                      / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_decode_fwd(q, k, v, lens, *, sm_scale: float, block_k: int = 128,
                     interpret: bool = False):
    """Single-token decode: q (B,H,1,D), k/v (B,Kh,Sk,D), lens (B,) valid
    lengths.  KV blocks stream through VMEM with a running-(m,l) merge —
    flash-decode structure, grid-sequential instead of warp-parallel."""
    B, H, _, D = q.shape
    Kh, Sk = k.shape[1], k.shape[2]
    G = H // Kh
    block_k = min(block_k, Sk)
    assert Sk % block_k == 0
    nk = Sk // block_k
    kernel = functools.partial(_decode_kernel, sm_scale=sm_scale,
                               block_k=block_k, num_kv_blocks=nk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, None, 1, D), lambda b, h, ki: (b, h, 0, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, ki: (b, h // G, ki, 0)),
            pl.BlockSpec((None, None, block_k, D),
                         lambda b, h, ki: (b, h // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, 1, D),
                               lambda b, h, ki: (b, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        interpret=interpret,
    )(lens.astype(jnp.int32), q, k, v)

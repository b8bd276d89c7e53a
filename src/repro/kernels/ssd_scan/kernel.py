"""Mamba-2 SSD chunked scan — Pallas TPU kernel.

TPU adaptation of the SSD algorithm (arXiv:2405.21060 §6): the chunk axis is
the *sequential* minor grid dimension and the inter-chunk state (P × N) lives
in VMEM scratch carried across grid steps — where a CUDA implementation
would use a separate inter-chunk scan kernel + global-memory state passing,
the TPU grid's implicit sequentiality gives the recurrence for free and the
intra-chunk quadratic term maps straight onto the MXU.

Grid: (B, H, n_chunks).  Per step:
  y[c] = (C_c B_cᵀ ∘ L_c ∘ dt) x_c  +  (C_c · S) ∘ exp(cs)        (MXU)
  S    = S · exp(cs[-1]) + (x_c · dt · decay)ᵀ B_c                 (MXU)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state_scr, *,
                chunk: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[...].astype(jnp.float32)          # (cl, P)
    dt_row = dt_ref[...].astype(jnp.float32)    # (1, cl)
    a = a_ref[pl.program_id(1)]                  # scalar A_h (negative)
    Bm = b_ref[...].astype(jnp.float32)          # (cl, N)
    Cm = c_ref[...].astype(jnp.float32)          # (cl, N)

    # Mosaic lowers neither cumsum nor a (cl, 1) <-> (1, cl) transpose, so
    # the prefix sums and the column form of dt are masked lane / sublane
    # reductions over the (cl, cl) iota grid (exact: only zeros are added)
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dA_row = dt_row * a                                              # (1, cl)
    dt = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1,
                 keepdims=True)                                      # (cl, 1)
    cs = jnp.sum(jnp.where(ii >= jj, dA_row, 0.0), axis=1,
                 keepdims=True)                                      # (cl, 1)
    cs_row = jnp.sum(jnp.where(ii <= jj, dt * a, 0.0), axis=0,
                     keepdims=True)                                  # (1, cl)
    cs_end = jnp.sum(dA_row, axis=1, keepdims=True)                  # (1, 1)
    # intra-chunk: masked decay matrix, seg = cs_i - cs_j
    L = jnp.where(ii >= jj, jnp.exp(cs - cs_row), 0.0)
    CB = jax.lax.dot_general(Cm, Bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (cl, cl)
    M = CB * L * dt_row                          # (cl, cl) — dt_j on columns
    y = jax.lax.dot_general(M, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (cl, P)
    # inter-chunk contribution from carried state (P, N)
    y += jnp.exp(cs) * jax.lax.dot_general(
        Cm, state_scr[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)                        # (cl, P)
    # state update
    decay_to_end = jnp.exp(cs_end - cs)          # (cl, 1)
    xw = x * (dt * decay_to_end)                 # (cl, P)
    state_scr[...] = (state_scr[...] * jnp.exp(cs_end)
                      + jax.lax.dot_general(
                          xw, Bm, (((0,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32))     # (P, N)
    y_ref[...] = y.astype(y_ref.dtype)


def ssd_scan_fwd(x, dt, A, Bm, Cm, *, chunk: int, interpret: bool = False):
    """x (B,H,S,P) head-major; dt (B,H,S); A (H,); Bm/Cm (B,S,N).
    S must be a multiple of ``chunk``.  Returns y (B,H,S,P)."""
    B, H, S, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    dt4 = dt.reshape(B, H, nc, 1, chunk)         # one (1, chunk) row/chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, None, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((None, None, None, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((None, chunk, N), lambda b, h, c: (b, c, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, chunk, P),
                               lambda b, h, c: (b, h, c, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(x, dt4, A.astype(jnp.float32), Bm, Cm)

"""Plain float32 reference of a dense decoder's training steps.

Written from the published architecture (Qwen1.5 / Qwen2: pre-norm RMSNorm
blocks, rotary embeddings in the rotate-half convention, grouped-query
causal attention with biased query/key/value projections, SiLU-gated FFN,
untied output head) and plain AdamW with global-norm clipping and a linear
warmup.  It imports nothing of the program under test and takes nothing it
made: the weights are drawn again from the seed by the same recipe (normal
draws scaled by 1/sqrt(fan_in), 0.02 for the embedding, rounded to the
configuration's parameter dtype), and the batches come from
``bench.data``.

No remat, no offload, no kernels: every matmul runs in float32 under
``jax.default_matmul_precision("highest")``.  It runs one batch row at a
time and layer by layer (a forward pass that keeps each layer's input,
then each layer's VJP), and attention in blocks of query rows that the
backward pass recomputes, so that a step at the timed sizes fits one chip.
AdamW's moments stay on the device where the params, the gradients and
both moments fit it beside one row's activations; otherwise they live on
the host (numpy float32) between steps and the update runs one piece at a
time (a leaf, or one layer's slice of a stacked leaf), so the device holds
the params, the gradients and one piece's moments.

``precision="fp8"`` is the control: the same computation with both inputs
of every matmul rounded to float8 (e4m3, one scale per tensor), the step
below the configuration's bfloat16.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
FP8_MAX = 448.0          # largest finite float8_e4m3fn
QUERY_BLOCK = 512        # query rows whose attention scores are live at once
STACKED = "blocks"       # leaves under this key are stacked per layer
# Device memory for one row's activations and layer VJP, beside the float32
# params, gradients and both moments.
HEADROOM_BYTES = 4 << 30


def moments_fit(params) -> bool:
    """Whether the float32 params, gradients and both Adam moments fit the
    default device with ``HEADROOM_BYTES`` to spare (True where the device
    states no limit)."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    need = 4 * sum(x.size * 4 for x in jax.tree.leaves(params))
    return limit is None or need + HEADROOM_BYTES <= limit


def _fp8(a):
    """``a`` rounded to float8 e4m3 with one scale for the tensor."""
    a = jax.lax.stop_gradient(a)
    scale = FP8_MAX / jnp.maximum(jnp.max(jnp.abs(a)), 1e-30)
    return (a * scale).astype(jnp.float8_e4m3fn).astype(F32) / scale


def _einsum(spec: str, a, b):
    return jnp.einsum(spec, a, b, preferred_element_type=F32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm_fp8(spec: str, a, b):
    return _einsum(spec, _fp8(a), _fp8(b))


def _mm_fp8_fwd(spec, a, b):
    qa, qb = _fp8(a), _fp8(b)
    return _einsum(spec, qa, qb), (qa, qb)


def _mm_fp8_bwd(spec, res, ct):
    """Both backward matmuls take float8 inputs too: the saved operands
    and the cotangent, rounded."""
    qa, qb = res
    return jax.vjp(functools.partial(_einsum, spec), qa, qb)[1](_fp8(ct))


_mm_fp8.defvjp(_mm_fp8_fwd, _mm_fp8_bwd)


def _mm(spec: str, a, b, precision: str):
    if precision == "fp8":
        return _mm_fp8(spec, a, b)
    return _einsum(spec, a, b)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    """x (B, S, H, D), positions 0..S-1, rotate-half convention."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = jnp.arange(S, dtype=F32)[:, None] * inv
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _dims(model: dict):
    d, H = model["d_model"], model["num_heads"]
    D = model.get("head_dim") or d // H
    return d, H, model["num_kv_heads"], D


# ------------------------------------------------------------------ init
@functools.partial(jax.jit, static_argnums=(0, 2))
def _init(model_items, key, pdtype: str):
    model = dict(model_items)
    d, H, Kh, D = _dims(model)
    V, ff, L = model["vocab_size"], model["d_ff"], model["num_layers"]
    qd, kvd = H * D, Kh * D

    def w(k, shape, std):
        return (jax.random.normal(k, shape) * std).astype(pdtype).astype(F32)

    ks = jax.random.split(key, 8)
    ke = jax.random.split(ks[0], 3)

    def layer(k):
        k6 = jax.random.split(k, 6)
        ka = jax.random.split(k6[0], 5)
        km = jax.random.split(k6[2], 3)
        return {
            "ln1": {"scale": jnp.ones((d,), F32)},
            "attn": {"wq": w(ka[0], (d, qd), 1 / math.sqrt(d)),
                     "wk": w(ka[1], (d, kvd), 1 / math.sqrt(d)),
                     "wv": w(ka[2], (d, kvd), 1 / math.sqrt(d)),
                     "wo": w(ka[3], (qd, d), 1 / math.sqrt(qd)),
                     "bq": jnp.zeros((qd,), F32),
                     "bk": jnp.zeros((kvd,), F32),
                     "bv": jnp.zeros((kvd,), F32)},
            "ln2": {"scale": jnp.ones((d,), F32)},
            "mlp": {"wi_gate": w(km[0], (d, ff), 1 / math.sqrt(d)),
                    "wi_up": w(km[1], (d, ff), 1 / math.sqrt(d)),
                    "wo": w(km[2], (ff, d), 1 / math.sqrt(ff))},
        }

    return {
        "embed": {"tok": w(ke[0], (V, d), 0.02),
                  "unembed": w(ke[1], (d, V), 1 / math.sqrt(d))},
        "ln_f": {"scale": jnp.ones((d,), F32)},
        "blocks": jax.vmap(layer)(jax.random.split(ks[1], L)),
    }


def init_params(model: dict, seed: int):
    """The configuration's weights for ``seed``, in float32."""
    _check_supported(model)
    return _init(tuple(sorted(model.items())), jax.random.PRNGKey(seed),
                 model["param_dtype"])


def _check_supported(model: dict) -> None:
    want = {"family": "dense", "norm": "rmsnorm", "act": "silu", "glu": True,
            "qkv_bias": True, "tie_embeddings": False}
    bad = {k: model.get(k) for k, v in want.items() if model.get(k) != v}
    if bad or model.get("pos_embedding", "rope") != "rope":
        raise NotImplementedError(f"the reference covers the Qwen dense "
                                  f"decoder only; this config has {bad}")


# --------------------------------------------------------------- forward
def _attention(q, k, v, precision: str):
    """Causal softmax attention of q, k, v (B, S, H, D), one block of
    query rows at a time; each block's scores are recomputed in the
    backward pass, so only one block's are ever live."""
    B, S, H, D = q.shape
    block = QUERY_BLOCK if S % QUERY_BLOCK == 0 else S

    @jax.checkpoint
    def rows(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * block, block, axis=1)
        s = _mm("bqhd,bkhd->bhqk", qi, k, precision) / math.sqrt(D)
        causal = (i * block + jnp.arange(block))[:, None] >= jnp.arange(S)
        s = jnp.where(causal, s, -jnp.inf)
        return _mm("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                   precision)

    ctx = jax.lax.map(rows, jnp.arange(S // block))     # (n, B, block, H, D)
    return jnp.moveaxis(ctx, 0, 1).reshape(B, S, H, D)


def _layer(model: dict, precision: str, p, x):
    """One block on x (B, S, d)."""
    d, H, Kh, D = _dims(model)
    B, S, _ = x.shape
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    h = _rms(x, p["ln1"]["scale"], eps)
    a = p["attn"]
    q = (_mm("bsd,dq->bsq", h, a["wq"], precision) + a["bq"]
         ).reshape(B, S, H, D)
    k = (_mm("bsd,dq->bsq", h, a["wk"], precision) + a["bk"]
         ).reshape(B, S, Kh, D)
    v = (_mm("bsd,dq->bsq", h, a["wv"], precision) + a["bv"]
         ).reshape(B, S, Kh, D)
    q, k = _rope(q, theta), _rope(k, theta)
    k = jnp.repeat(k, H // Kh, axis=2)       # query head i reads kv head i // G
    v = jnp.repeat(v, H // Kh, axis=2)
    ctx = _attention(q, k, v, precision)
    x = x + _mm("bsq,qd->bsd", ctx.reshape(B, S, H * D), a["wo"], precision)
    h = _rms(x, p["ln2"]["scale"], eps)
    m = p["mlp"]
    g = jax.nn.silu(_mm("bsd,df->bsf", h, m["wi_gate"], precision))
    u = _mm("bsd,df->bsf", h, m["wi_up"], precision)
    return x + _mm("bsf,fd->bsd", g * u, m["wo"], precision)


def _head_loss(model: dict, precision: str, ln_f, unembed, x, labels):
    h = _rms(x, ln_f, model["rms_norm_eps"])
    logits = _mm("bsd,dv->bsv", h, unembed, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


class Reference:
    """Training steps of the reference for one configuration, in
    ``precision`` ("f32" or the control "fp8")."""

    def __init__(self, model: dict, job: dict, precision: str = "f32"):
        _check_supported(model)
        self.model, self.job, self.precision = model, job, precision
        m, pr = model, precision
        self._embed = jax.jit(lambda tok, t: tok[t])
        self._layer_fwd = jax.jit(functools.partial(_layer, m, pr))
        self._layer_vjp = jax.jit(
            lambda p, x, ct: jax.vjp(functools.partial(_layer, m, pr), p, x
                                     )[1](ct))
        self._head = jax.jit(jax.value_and_grad(
            functools.partial(_head_loss, m, pr), argnums=(0, 1, 2)))
        # accumulators write in place: a step at the timed sizes holds the
        # params, the gradients and, where they fit, both Adam moments
        self._acc = jax.jit(lambda acc, g, s: jax.tree.map(
            lambda a, b: a + b * s, acc, g), donate_argnums=0)
        self._acc_layer = jax.jit(lambda acc, g, i: jax.tree.map(
            lambda a, b: a.at[i].add(b), acc, g), donate_argnums=0)
        self._acc_embed = jax.jit(lambda acc, t, ct: acc.at[t].add(ct),
                                  donate_argnums=0)
        self._clip = jax.jit(self._clip_impl)
        self._coeffs = jax.jit(self._coeffs_impl)
        self._leaf = jax.jit(self._leaf_impl, donate_argnums=(0, 2, 3))
        self._layer_slice = jax.jit(self._layer_slice_impl,
                                    donate_argnums=(0, 2, 3))

    def _layer_params(self, params, i: int):
        return jax.tree.map(lambda t: t[i], params["blocks"])

    def _forward_row(self, params, tokens):
        """Inputs of each layer and the final hidden state, one row."""
        x = self._embed(params["embed"]["tok"], tokens)
        xs = []
        for i in range(self.model["num_layers"]):
            xs.append(x)
            x = self._layer_fwd(self._layer_params(params, i), x)
        return xs, x

    def loss_and_grads(self, params, batch):
        """Mean token loss of ``batch`` and its gradient, row by row."""
        with jax.default_matmul_precision("highest"):
            return self._loss_and_grads(params, batch)

    def _loss_and_grads(self, params, batch):
        tokens = jnp.asarray(batch["tokens"])
        labels = jnp.asarray(batch["labels"])
        B, L = tokens.shape[0], self.model["num_layers"]
        grads = jax.tree.map(jnp.zeros_like, params)
        total = 0.0
        for b in range(B):
            t, y = tokens[b:b + 1], labels[b:b + 1]
            xs, x = self._forward_row(params, t)
            loss, (g_ln, g_un, ct) = self._head(
                params["ln_f"]["scale"], params["embed"]["unembed"], x, y)
            total += float(loss) / B
            grads["ln_f"] = self._acc(grads["ln_f"], {"scale": g_ln}, 1 / B)
            grads["embed"]["unembed"] = self._acc(grads["embed"]["unembed"],
                                                  g_un, 1 / B)
            ct = ct / B
            for i in reversed(range(L)):
                gp, ct = self._layer_vjp(self._layer_params(params, i),
                                         xs[i], ct)
                grads["blocks"] = self._acc_layer(grads["blocks"], gp, i)
            del xs
            grads["embed"]["tok"] = self._acc_embed(grads["embed"]["tok"],
                                                    t, ct)
        return total, grads

    # ------------------------------------------------------------ AdamW
    def _lr(self, step):
        j = self.job
        step = step.astype(F32)
        warm = j["learning_rate"] * step / max(j["warmup_steps"], 1)
        prog = jnp.clip((step - j["warmup_steps"])
                        / max(j["total_steps"] - j["warmup_steps"], 1),
                        0.0, 1.0)
        cos = j["learning_rate"] * (0.1 + 0.9 * 0.5
                                    * (1 + jnp.cos(jnp.pi * prog)))
        return jnp.where(step < j["warmup_steps"], warm, cos)

    def _clip_impl(self, grads):
        """The clipping factor, from the global norm over every leaf."""
        gnorm = jnp.sqrt(sum(jnp.sum(g * g)
                             for g in jax.tree_util.tree_leaves(grads)))
        return jnp.minimum(1.0, self.job["grad_clip"]
                           / jnp.maximum(gnorm, 1e-12))

    def _coeffs_impl(self, step):
        """The learning rate of ``step``, the next step and the bias
        corrections at it."""
        j = self.job
        lr = self._lr(step)
        step = step + 1
        c1 = 1.0 - j["adam_b1"] ** step.astype(F32)
        c2 = 1.0 - j["adam_b2"] ** step.astype(F32)
        return lr, step, c1, c2

    def _leaf_impl(self, p, g, m, v, scale, lr, c1, c2):
        """AdamW on one piece: the new params and moments."""
        j = self.job
        b1, b2 = j["adam_b1"], j["adam_b2"]
        m = b1 * m + (1 - b1) * g * scale
        v = b2 * v + (1 - b2) * (g * scale) ** 2
        p = p - lr * ((m / c1) / (jnp.sqrt(v / c2) + j["adam_eps"])
                      + j["weight_decay"] * p)
        return p, m, v

    def _layer_slice_impl(self, p, g, m, v, i, scale, lr, c1, c2):
        """AdamW on layer ``i`` of a stacked leaf, written in place."""
        pi, m, v = self._leaf_impl(p[i], g[i], m, v, scale, lr, c1, c2)
        return p.at[i].set(pi), m, v

    def _adamw(self, params, grads, m, v, step):
        """One update of ``params`` (consumed) from ``grads``, the moments
        ``m`` and ``v`` and ``step``.  Returns the new params and moments,
        the next step and the clipping factor applied to ``grads``.  The
        clipping factor comes first, from every gradient leaf.  Moments on
        the device (consumed) are updated a leaf at a time.  Moments on the
        host (trees of numpy float32 arrays) are updated in place: each
        piece (a leaf, or one layer's slice of a stacked leaf) goes to the
        device with its moments, and its moments come back to the host
        while the next piece runs."""
        scale = self._clip(grads)
        lr, step, c1, c2 = self._coeffs(step)
        flat, tree = jax.tree_util.tree_flatten_with_path(params)
        g_flat, m_flat, v_flat = (jax.tree_util.tree_leaves(t)
                                  for t in (grads, m, v))
        if not isinstance(m_flat[0], np.ndarray):
            new = [self._leaf(p, g, md, vd, scale, lr, c1, c2)
                   for (_, p), g, md, vd in zip(flat, g_flat, m_flat, v_flat)]
            params, m, v = (jax.tree_util.tree_unflatten(tree, list(t))
                            for t in zip(*new))
            return params, m, v, step, scale
        out, pending = [], None
        for (path, p), g, mh, vh in zip(flat, g_flat, m_flat, v_flat):
            if getattr(path[0], "key", None) == STACKED:
                for i in range(p.shape[0]):
                    p, md, vd = self._layer_slice(
                        p, g, jax.device_put(mh[i]), jax.device_put(vh[i]),
                        jnp.int32(i), scale, lr, c1, c2)
                    pending = self._fetch(pending, mh, vh, i, md, vd)
            else:
                p, md, vd = self._leaf(p, g, jax.device_put(mh),
                                       jax.device_put(vh), scale, lr, c1, c2)
                pending = self._fetch(pending, mh, vh, Ellipsis, md, vd)
            out.append(p)
        self._fetch(pending)
        return jax.tree_util.tree_unflatten(tree, out), m, v, step, scale

    @staticmethod
    def _fetch(pending, *piece):
        """Start copying ``piece``'s new moments to the host, then write
        the previous piece's into its host arrays; returns ``piece``."""
        if piece:
            for x in piece[-2:]:
                x.copy_to_host_async()
        if pending is not None:
            mh, vh, i, md, vd = pending
            mh[i], vh[i] = np.asarray(md), np.asarray(vd)
        return piece or None

    def train(self, params, batches: List[dict], norms: Callable,
              first: int = 0, state: Optional[tuple] = None,
              keep_state: bool = False) -> dict:
        """One step per batch from ``params`` (consumed) and the AdamW
        ``state`` ``(m, v, step)`` (zeros at step 0 when None; copied, so
        ``state`` can start another run).  Returns the losses, ``norms``
        of step ``first``'s gradient as clipped for the optimizer, the
        params before that step (``start``, on the host) and the final
        params; with ``keep_state`` also ``state``, the AdamW state before
        step ``first`` (on the host), from which the steps after it can be
        run again."""
        host = lambda t: jax.tree.map(  # noqa: E731
            lambda x: np.array(x, np.float32), t)
        on_host = not moments_fit(params)
        if state is None:
            zeros = ((lambda p: np.zeros(p.shape, np.float32)) if on_host
                     else jnp.zeros_like)
            m, v = jax.tree.map(zeros, params), jax.tree.map(zeros, params)
            step = jnp.zeros((), jnp.int32)
        else:
            m, v, step = host(state[0]), host(state[1]), jnp.asarray(
                state[2], jnp.int32)
            if not on_host:
                m, v = jax.device_put((m, v))
        out = {"losses": []}
        for i, batch in enumerate(batches):
            if i == first:
                out["start"] = jax.device_get(params)
                if keep_state:
                    out["state"] = (host(m), host(v), jax.device_get(step))
            loss, grads = self.loss_and_grads(params, batch)
            out["losses"].append(loss)
            raw = norms(grads) if i == first else None
            params, m, v, step, scale = self._adamw(params, grads, m, v,
                                                    step)
            del grads
            if raw is not None:
                out["grad"] = {k: n * float(scale) for k, n in raw.items()}
        del m, v
        out["params"] = params
        return out

"""Train / serve step builders with production sharding.

Everything the dry-run lowers and the Trainer executes is built here:

  * ``make_train_step``  — fused loss+grad+clip+AdamW+loss-scale iteration
                           (what the paper's profiler sees as one sequence)
  * ``make_grad_step`` / ``make_apply_step`` — the *split* dispatch pair the
                           eager-style trainer uses so host-side loss-scale
                           skips really change the operator stream (§2.3)
  * ``make_prefill_step`` / ``make_decode_step`` — serving
  * sharding-spec derivation for params / optimizer state (ZeRO stages)

ZeRO mapping (DeepSpeed-analogue the paper builds on): stage 1/2 shard the
AdamW m/v/master tensors across (pod, data) by remapping the logical
``embed`` axis; stage 3 (FSDP) also shards the parameters themselves.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.common.config import ModelConfig, ShapeConfig, TrainConfig
from repro.distributed import sharding as shd
from repro.models.registry import ModelApi, get_api
from repro.optim.adamw import (AdamWState, adamw_init, adamw_update,
                               clip_by_global_norm)
from repro.optim.schedules import warmup_cosine

ZERO_OPT_RULES = {"embed": ("pod", "data"), "layers": None}
ZERO3_PARAM_RULES = {"embed": ("pod", "data")}


# --------------------------------------------------------- sharding specs
def sanitize_specs(spec_tree, sds_tree, mesh: Optional[Mesh]):
    """Drop sharding on dims the mesh cannot divide evenly.  jit *argument*
    shardings (unlike internal constraints) reject uneven partitions, so
    e.g. vocab=49155 or heads=20 fall back to replication on that dim."""
    if mesh is None:
        return spec_tree

    def axis_size(entry) -> int:
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            n *= mesh.shape[a]
        return n

    def one(spec: P, sds):
        shape = getattr(sds, "shape", None)
        if shape is None:
            return spec
        entries = list(spec) + [None] * (len(shape) - len(spec))
        out = []
        for dim, entry in zip(shape, entries[: len(shape)]):
            out.append(entry if dim % axis_size(entry) == 0 else None)
        return P(*out)

    return jax.tree.map(one, spec_tree, sds_tree,
                        is_leaf=lambda x: isinstance(x, P))


def param_specs(axes_tree, mesh: Optional[Mesh], zero3: bool = False,
                sds_tree=None):
    rules = ZERO3_PARAM_RULES if zero3 else None
    with shd.use_mesh(mesh, rules):
        spec = shd.tree_spec(axes_tree, mesh)
    if sds_tree is not None:
        spec = sanitize_specs(spec, sds_tree, mesh)
    return spec


def opt_specs(axes_tree, mesh: Optional[Mesh], zero_stage: int,
              opt_sds: Optional[AdamWState] = None):
    rules = ZERO_OPT_RULES if zero_stage >= 1 else None
    with shd.use_mesh(mesh, rules):
        p_spec = shd.tree_spec(axes_tree, mesh)
    out = AdamWState(P(), p_spec, p_spec, p_spec)
    if opt_sds is not None:
        out = AdamWState(
            P(),
            sanitize_specs(out.m, opt_sds.m, mesh),
            sanitize_specs(out.v, opt_sds.v, mesh),
            sanitize_specs(out.master, opt_sds.master, mesh)
            if opt_sds.master is not None else None)
    return out


def batch_specs_sharding(batch_tree, mesh: Optional[Mesh]):
    if mesh is None:
        return None
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    def one(x):
        spec = [None] * getattr(x, "ndim", len(x.shape))
        spec[0] = tuple(axes)
        return P(*spec)
    return jax.tree.map(one, batch_tree)


def to_shardings(spec_tree, mesh: Optional[Mesh]):
    if mesh is None or spec_tree is None:
        return None
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------- state init
def abstract_params(cfg: ModelConfig, api: Optional[ModelApi] = None):
    """ShapeDtypeStructs for params — no allocation (dry-run safe)."""
    api = api or get_api(cfg)
    return jax.eval_shape(lambda k: api.init(cfg, k)[0], jax.random.PRNGKey(0))


def abstract_train_state(cfg: ModelConfig):
    params_sds = abstract_params(cfg)
    opt_sds = jax.eval_shape(adamw_init, params_sds)
    return params_sds, opt_sds


@functools.lru_cache(maxsize=64)
def param_axes(cfg: ModelConfig):
    """Logical-axes tree for params.  The axes are plain Python built as a
    side effect of init, so an abstract eval_shape trace captures them
    without allocating a single parameter."""
    api = get_api(cfg)
    box = {}

    def f(k):
        p, a = api.init(cfg, k)
        box["axes"] = a
        return p

    jax.eval_shape(f, jax.random.PRNGKey(0))
    return box["axes"]


# ----------------------------------------------------------------- steps
def make_loss_fn(cfg: ModelConfig, policy=None):
    api = get_api(cfg)

    def loss_fn(params, batch, loss_scale):
        loss, metrics = api.loss_fn(cfg, params, batch, policy=policy)
        return loss * loss_scale, (loss, metrics)

    return loss_fn


def make_train_step(cfg: ModelConfig, tcfg: TrainConfig, policy=None,
                    grad_shardings=None) -> Callable:
    """Fused iteration: grads + clip + AdamW + schedule (+ scaled loss).
    ``grad_shardings`` (a params-shaped tree of NamedShardings) pins the
    gradients to the optimizer-state layout right at the backward output —
    XLA then emits a reduce-scatter instead of a full all-reduce (§Perf
    cell B iteration 2)."""
    loss_fn = make_loss_fn(cfg, policy)

    def train_step(params, opt_state: AdamWState, batch, loss_scale):
        (scaled, (loss, _m)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, loss_scale)
        if grad_shardings is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, grad_shardings)
        grads = jax.tree.map(lambda g: g / loss_scale.astype(g.dtype), grads)
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = warmup_cosine(opt_state.step, tcfg.learning_rate,
                           tcfg.warmup_steps, tcfg.steps)
        new_params, new_opt = adamw_update(params, grads, opt_state, tcfg, lr)
        metrics = {"loss": loss, "grad_norm": gnorm, "lr": lr}
        return new_params, new_opt, metrics

    return train_step


def make_grad_step(cfg: ModelConfig, tcfg: TrainConfig, policy=None):
    loss_fn = make_loss_fn(cfg, policy)

    def grad_step(params, batch, loss_scale):
        (_, (loss, _m)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, loss_scale)
        grads = jax.tree.map(lambda g: g / loss_scale, grads)
        finite = jnp.all(jnp.stack([
            jnp.all(jnp.isfinite(g)) for g in jax.tree_util.tree_leaves(grads)]))
        return loss, grads, finite

    return grad_step


def make_apply_step(cfg: ModelConfig, tcfg: TrainConfig):
    def apply_step(params, opt_state: AdamWState, grads):
        grads, gnorm = clip_by_global_norm(grads, tcfg.grad_clip)
        lr = warmup_cosine(opt_state.step, tcfg.learning_rate,
                           tcfg.warmup_steps, tcfg.steps)
        new_params, new_opt = adamw_update(params, grads, opt_state, tcfg, lr)
        return new_params, new_opt, {"grad_norm": gnorm, "lr": lr}
    return apply_step


def jit_apply_step(cfg: ModelConfig, tcfg: TrainConfig):
    """The update as the trainer dispatches it.  Params, optimizer state
    and grads are dead after it, so all three are donated and the new state
    is written in place.  The update reads only the f32 master copy, so
    ``keep_unused`` keeps the bf16 params an input of the program: pruned,
    their donation would be lost and each step would allocate new params
    beside the old ones."""
    return jax.jit(make_apply_step(cfg, tcfg), donate_argnums=(0, 1, 2),
                   keep_unused=True)


def make_eval_step(cfg: ModelConfig, policy=None):
    api = get_api(cfg)

    def eval_step(params, batch):
        loss, _ = api.loss_fn(cfg, params, batch, policy=policy)
        return loss

    return eval_step


def make_prefill_step(cfg: ModelConfig, policy=None):
    api = get_api(cfg)

    def prefill_step(params, batch):
        logits, _ = api.forward(cfg, params, batch["tokens"],
                                memory=batch.get("memory"), policy=policy)
        return logits

    return prefill_step


def make_decode_step(cfg: ModelConfig):
    api = get_api(cfg)

    def decode_step(params, tokens, state):
        return api.decode_step(cfg, params, tokens, state)

    return decode_step

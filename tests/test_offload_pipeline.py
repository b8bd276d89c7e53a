"""The pipelined offload stack (``repro.models.pipelined``) against the
``jax.checkpoint`` offload path it replaces: same loss, same gradients,
same residuals; and the stacks and policies it must leave alone."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro import obs
from repro.common.config import ChameleonConfig, TrainConfig
from repro.core.executor import (AppliedPolicy, Executor, OffloadSites,
                                 jax_offload_policy, jax_save_policy)
from repro.core.sites import OFFLOAD_SITES
from repro.distributed.steps import make_grad_step
from repro.models import pipelined
from repro.models.registry import get_api

STABLE_OFF = {"ffn_act", "ffn_pre", "ln_in"}
STABLE_SAVE = {"attn_ctx", "attn_out", "embed_out", "final_norm", "qkv_proj"}
SITE_SETS = {
    # the swap cell's Stable policy
    "stable": (STABLE_OFF, STABLE_SAVE),
    # WarmUp's conservative policy: every site offloaded
    "conservative": (set(OFFLOAD_SITES), set()),
}


def _setup(arch, B=2, S=16):
    cfg = C.get_reduced(arch)
    assert jnp.dtype(cfg.dtype) == jnp.float32
    params, _ = get_api(cfg).init(cfg, jax.random.PRNGKey(0))
    k1, k2 = jax.random.split(jax.random.PRNGKey(1))
    batch = {"tokens": jax.random.randint(k1, (B, S), 0, cfg.vocab_size),
             "labels": jax.random.randint(k2, (B, S), 0, cfg.vocab_size)}
    return cfg, params, batch


def _grads(cfg, params, batch, policy):
    step = jax.jit(make_grad_step(cfg, TrainConfig(), policy))
    loss, grads, finite = step(params, batch, jnp.float32(1.0))
    assert bool(finite)
    return float(loss), grads


@pytest.mark.parametrize("arch", ["llama2_paper", "qwen3_moe_30b_a3b"])
@pytest.mark.parametrize("sites", sorted(SITE_SETS))
def test_pipelined_grads_match_checkpoint_offload(arch, sites):
    """Loss and every gradient leaf of the pipelined stack match the
    ``jax.checkpoint(save_and_offload_only_these_names)`` path (float32)."""
    off, save = SITE_SETS[sites]
    cfg, params, batch = _setup(arch)
    pol = OffloadSites(frozenset(off), frozenset(save))
    loss, grads = _grads(cfg, params, batch, pol)
    ref_loss, ref = _grads(cfg, params, batch, jax_offload_policy(off, save))
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-6)
    leaves = jax.tree_util.tree_leaves_with_path(grads)
    assert len(leaves) == len(jax.tree_util.tree_leaves(ref))
    for (path, g), r in zip(leaves, jax.tree_util.tree_leaves(ref)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r), rtol=1e-6,
                                   atol=1e-7, err_msg=str(path))


def _jaxpr_text(cfg, params, batch, policy) -> str:
    """The grad step's jaxpr, with object addresses blanked."""
    step = make_grad_step(cfg, TrainConfig(), policy)
    text = str(jax.make_jaxpr(step)(params, batch, jnp.float32(1.0)))
    return re.sub(r" at 0x[0-9a-f]+", "", text)


def test_stack_runs_the_policy_sites():
    """The stack pipelines exactly the residuals the checkpoint policy
    keeps: for each site set, the stored values have the shapes of the
    residuals ``jax.checkpoint`` saves for one layer, split between host
    and HBM as the policy splits them."""
    from jax._src.ad_checkpoint import saved_residuals
    from repro.models.transformer import dense_block
    cfg, params, batch = _setup("llama2_paper")
    lp = jax.tree.map(lambda t: t[0], params["blocks"])
    B, S = batch["tokens"].shape
    x = jnp.ones((B, S, cfg.d_model), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def layer(x, lp, pos):
        return dense_block(cfg, lp, x, pos)

    for off, save in SITE_SETS.values():
        sites = OffloadSites(frozenset(off), frozenset(save))
        plan = pipelined._plan(layer, sites, x, lp, pos)
        ref = saved_residuals(jax.checkpoint(
            lambda x, lp: layer(x, lp, pos),
            policy=sites.checkpoint_policy), x, lp)
        ref = [(a.shape, a.memory_space == jax.memory.Space.Host)
               for a, why in ref if not why.startswith("from the argument")
               and not why.startswith("from a constant")]
        mine = [(plan.avals[i].shape, i in plan.off)
                for i in plan.off + plan.save]
        assert sorted(mine) == sorted(ref)


def test_baseline_and_raw_policies_keep_the_checkpoint_path():
    """A policy that offloads nothing is the bare ``save_only_these_names``
    policy, as before: the grad step's jaxpr is the one built from that
    policy directly, with no pinned-host transfer in it."""
    cfg, params, batch = _setup("llama2_paper")
    ex = Executor(ChameleonConfig())
    base = ex.baseline()
    assert not base.pipelined and not ex.raw().pipelined
    assert not isinstance(base.to_jax(), OffloadSites)
    text = _jaxpr_text(cfg, params, batch, base.to_jax())
    assert text == _jaxpr_text(cfg, params, batch,
                               jax_save_policy(set(OFFLOAD_SITES)))
    assert "custom_vjp" not in text and "device_put" not in text


def test_ssm_stack_keeps_the_checkpoint_path():
    """Outside the dense and MoE stacks an offload policy is still the
    ``jax.checkpoint`` policy: the ssm grad step's jaxpr is unchanged."""
    cfg, params, batch = _setup("mamba2_780m")
    pol = Executor(ChameleonConfig()).conservative().to_jax()
    assert isinstance(pol, OffloadSites)
    for off, save in (SITE_SETS["stable"], (pol.offload, pol.save)):
        assert _jaxpr_text(
            cfg, params, batch,
            OffloadSites(frozenset(off), frozenset(save))) == _jaxpr_text(
            cfg, params, batch, jax_offload_policy(off, save))


def test_offload_sites_without_gradient():
    """Equal site sets give equal, hashable values; with no gradient taken
    the stack is the plain layer scan."""
    cfg, params, batch = _setup("llama2_paper")
    off, save = SITE_SETS["stable"]
    pol = OffloadSites(frozenset(off), frozenset(save))
    assert pol == OffloadSites(frozenset(off), frozenset(save))
    assert hash(pol) == hash(OffloadSites(frozenset(off), frozenset(save)))

    def loss(policy):
        return get_api(cfg).loss_fn(cfg, params, batch, policy=policy)[0]

    np.testing.assert_allclose(float(loss(pol)), float(loss(None)),
                               rtol=1e-6)


def test_install_arg_and_counter_say_pipelined():
    """Each ``policy.install`` instant's arg is ``(reason, pipelined)``;
    ``offload_pipelined_installs`` counts the installs of an offloading
    policy and no other."""
    from repro.core.runtime import ChameleonRuntime
    old_t = obs.set_tracer(obs.SpanTracer(capacity=64))
    old_m = obs.set_metrics(obs.MetricsRegistry())
    try:
        rt = ChameleonRuntime(ChameleonConfig(), lambda pol: (lambda *a: a))
        ex = rt.executor
        stable = AppliedPolicy(None, set(STABLE_OFF), set(STABLE_SAVE),
                               set(), "off=stable")
        for applied, why in ((stable, "genpolicy"), (ex.baseline(), "prepare"),
                             (ex.conservative(), "ladder")):
            rt._install(applied, why)
        args = [r["arg"] for r in obs.tracer().records()
                if r["name"] == "policy.install"]
        assert args == [("genpolicy", True), ("prepare", False),
                        ("ladder", True)]
        counters = obs.metrics().snapshot()["counters"]
        assert counters["policy_installs"] == 3
        assert counters["offload_pipelined_installs"] == 2
    finally:
        obs.set_tracer(old_t)
        obs.set_metrics(old_m)

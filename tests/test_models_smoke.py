"""Per-architecture smoke tests (assigned requirement): each reduced config
runs one forward AND one train step on CPU; output shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.common.config import TrainConfig
from repro.distributed.steps import make_train_step
from repro.models.registry import get_api
from repro.optim.adamw import adamw_init


def _batch(cfg, B=2, S=16):
    rng = jax.random.PRNGKey(1)
    b = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}
    if cfg.family == "vlm":
        b["memory"] = jnp.ones((B, cfg.image_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        b["memory"] = jnp.ones((B, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return b


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_forward_and_train_step(arch):
    cfg = C.get_reduced(arch)
    api = get_api(cfg)
    params, axes = api.init(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    batch = _batch(cfg, B, S)
    logits, aux = api.forward(cfg, params, batch["tokens"],
                              memory=batch.get("memory"))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))

    step = jax.jit(make_train_step(cfg, TrainConfig(steps=10,
                                                    warmup_steps=0)))
    opt = adamw_init(params)
    new_p, new_opt, metrics = step(params, opt, batch, jnp.float32(1.0))
    assert np.isfinite(float(metrics["loss"]))
    assert int(new_opt.step) == 1
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a), np.asarray(b))
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(new_p)))
    assert moved


@pytest.mark.parametrize("arch", C.ARCH_IDS)
def test_decode_step(arch):
    cfg = C.get_reduced(arch)
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(0))
    B = 2
    batch = _batch(cfg, B, 4)
    state = api.init_decode_state(cfg, B, 32, memory=batch.get("memory"),
                                  params=params)
    logits, state2 = api.decode_step(cfg, params, batch["tokens"][:, :1],
                                     state)
    assert logits.shape == (B, 1, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))
    assert np.all(np.asarray(state2.pos) == 1)


def test_param_count_analytic_close():
    """ModelConfig.param_count (used for 6·N·D roofline flops) agrees with
    the real initialized tree within 2%."""
    for arch in C.ARCH_IDS:
        cfg = C.get_reduced(arch)
        api = get_api(cfg)
        params, _ = api.init(cfg, jax.random.PRNGKey(0))
        real = sum(int(np.prod(x.shape))
                   for x in jax.tree_util.tree_leaves(params))
        est = cfg.param_count()
        assert abs(est - real) / real < 0.02, (arch, est, real)


def test_decode_matches_forward_dense():
    """Token-by-token decode logits == full forward logits (dense)."""
    cfg = C.get_reduced("llama3_2_1b")
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(0))
    B, S = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    full, _ = api.forward(cfg, params, toks)
    state = api.init_decode_state(cfg, B, 16)
    outs = []
    for t in range(S):
        lg, state = api.decode_step(cfg, params, toks[:, t:t + 1], state)
        outs.append(np.asarray(lg[:, 0]))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), dec, rtol=2e-3, atol=2e-3)


def test_decode_matches_forward_ssm():
    cfg = C.get_reduced("mamba2_780m")
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(0))
    B, S = 1, 8
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0,
                              cfg.vocab_size)
    full, _ = api.forward(cfg, params, toks)
    state = api.init_decode_state(cfg, B, 16)
    outs = []
    for t in range(S):
        lg, state = api.decode_step(cfg, params, toks[:, t:t + 1], state)
        outs.append(np.asarray(lg[:, 0]))
    dec = np.stack(outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), dec, rtol=5e-3, atol=5e-3)


def test_cell_matrix_skips():
    m = C.cell_matrix()
    assert len(m) == 10
    total = sum(len(v) for v in m.values())
    assert total == 32  # 40 cells - 8 long_500k skips (full-attention archs)
    assert "long_500k" in m["mamba2_780m"]
    assert "long_500k" in m["zamba2_1_2b"]
    assert "long_500k" not in m["qwen2_7b"]


def test_grad_step_carries_named_scopes():
    """Attention, FFN and loss are named scopes in the lowered grad step's
    debug text; every offload site's ``name`` equation sits in the scope
    ``offload.<site>``."""
    import re
    from repro.core.executor import jax_offload_policy
    from repro.distributed.steps import abstract_params, make_grad_step
    cfg = C.get_reduced("llama2_paper")
    step = make_grad_step(cfg, TrainConfig(),
                          jax_offload_policy(["ffn_act", "ln_in"], []))
    batch = {k: jax.ShapeDtypeStruct((2, 16), jnp.int32)
             for k in ("tokens", "labels")}
    args = (abstract_params(cfg), batch, jax.ShapeDtypeStruct((), jnp.float32))
    text = jax.jit(step).lower(*args).as_text(debug_info=True)
    scopes = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        scopes.update(re.split(r"[/()]", loc))
    assert {"attention", "ffn", "loss"} <= scopes

    named = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "name":
                named.append((eqn.params["name"],
                              str(eqn.source_info.name_stack)))
            for v in eqn.params.values():
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    walk(inner)

    walk(jax.make_jaxpr(step)(*args).jaxpr)
    assert {n for n, _ in named} >= {"ffn_act", "ln_in", "attn_out"}
    assert all(f"offload.{n}" in re.split(r"[/()]", stack)
               for n, stack in named)

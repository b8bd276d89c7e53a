"""The benchmark's float32 reference against the program's own grad and
apply steps, on the CPU at a reduced size."""
import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from bench import check  # noqa: E402
from bench.data import Batches  # noqa: E402
from bench.reference import Reference, init_params  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
TINY = json.loads((DATA / "tiny-dense.json").read_text())
JOB = json.loads((DATA / "tiny-traffic.json").read_text())


def _program(dtype):
    from repro.common.config import ModelConfig, TrainConfig
    fields = set(ModelConfig.__dataclass_fields__)
    model = {k: v for k, v in TINY["model"].items() if k in fields}
    model.update(dtype=dtype, param_dtype=dtype)
    cfg = ModelConfig(name="tiny", **model)
    tcfg = TrainConfig(learning_rate=JOB["learning_rate"],
                       warmup_steps=JOB["warmup_steps"],
                       steps=JOB["total_steps"],
                       weight_decay=JOB["weight_decay"],
                       grad_clip=JOB["grad_clip"])
    return cfg, tcfg


def _close(a, b, rtol, atol=0.0):
    for (p, x), (_, y) in zip(jax.tree_util.tree_flatten_with_path(a)[0],
                              jax.tree_util.tree_flatten_with_path(b)[0]):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32), rtol=rtol,
                                   atol=atol, err_msg=str(p))


@pytest.mark.parametrize("seed", [0, 2**31 + 7])
def test_reference_weights_are_the_programs(seed):
    """Drawn again from the seed, the reference's weights are the
    program's, bit for bit."""
    from repro.models.registry import get_api
    cfg, _ = _program("bfloat16")
    api = get_api(cfg)
    prog = jax.jit(lambda k: api.init(cfg, k)[0])(jax.random.PRNGKey(seed))
    ref = init_params(TINY["model"], seed)
    assert (jax.tree_util.tree_structure(prog)
            == jax.tree_util.tree_structure(ref))
    _close(prog, ref, rtol=0.0)


def test_loss_and_grads_match_the_program_grad_step():
    """With the program in float32 and no remat policy, the two compute
    the same loss and gradients up to float32 rounding."""
    from repro.distributed import steps as S
    cfg, tcfg = _program("float32")
    model = dict(TINY["model"], dtype="float32", param_dtype="float32")
    params = init_params(model, 3)
    batch = Batches(cfg.vocab_size, JOB, 3).batch_at(0)
    with jax.default_matmul_precision("highest"):
        loss, grads, finite = jax.jit(S.make_grad_step(cfg, tcfg))(
            params, {k: jnp.asarray(v) for k, v in batch.items()},
            jnp.float32(1.0))
    rloss, rgrads = Reference(model, JOB).loss_and_grads(params, batch)
    assert bool(finite)
    assert float(loss) == pytest.approx(rloss, rel=1e-5)
    _close(grads, rgrads, rtol=2e-3, atol=1e-6)


def test_adamw_matches_the_program_apply_step():
    """One update past the warmup's zero learning rate: the program's
    donated apply step and the reference's AdamW agree."""
    from repro.distributed import steps as S
    from repro.optim.adamw import AdamWState
    cfg, tcfg = _program("float32")
    model = dict(TINY["model"], dtype="float32", param_dtype="float32")
    params = init_params(model, 4)
    key = jax.random.PRNGKey(1)
    grads = jax.tree.map(lambda p: jax.random.normal(key, p.shape), params)
    m = jax.tree.map(lambda p: 0.01 * jnp.ones_like(p), params)
    v = jax.tree.map(lambda p: 0.02 * jnp.ones_like(p), params)
    step = jnp.int32(5)
    ref = Reference(model, JOB)
    rp, rm, rv, _, _ = jax.jit(ref._adamw_impl)(params, grads, m, v, step)
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
    pp, state, _ = S.make_apply_step(cfg, tcfg)(
        copy(params), AdamWState(step, copy(m), copy(v), None), grads)
    _close(pp, rp, rtol=1e-5, atol=1e-7)
    _close(state.m, rm, rtol=1e-5, atol=1e-7)
    _close(state.v, rv, rtol=1e-5, atol=1e-9)


def test_leaf_norms_split_stacked_layers():
    tree = {"blocks": {"w": jnp.ones((3, 2, 2))}, "embed": {"tok": jnp.ones((4,))}}
    norms = check.leaf_norms(tree)
    assert norms == {"blocks/w[0]": 2.0, "blocks/w[1]": 2.0,
                     "blocks/w[2]": 2.0, "embed/tok": 2.0}
    assert check.diff_norms(tree, jax.tree.map(np.zeros_like, tree)) == norms


def test_attention_in_query_blocks_is_the_full_square(monkeypatch):
    """Attention one block of query rows at a time gives the full causal
    square's output and gradients."""
    from bench import reference as RF
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (1, 32, 2, 8))
               for i in range(3))

    def loss(q, k, v):
        return jnp.sum(jnp.sin(RF._attention(q, k, v, "f32")))

    with jax.default_matmul_precision("highest"):
        full = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
        monkeypatch.setattr(RF, "QUERY_BLOCK", 8)
        blocked = jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)
    _close(full, blocked, rtol=1e-5, atol=1e-6)

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
from bench import reference as RF  # noqa: E402
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


@pytest.mark.parametrize("where", ["device", "host"])
def test_adamw_matches_the_program_apply_step(where):
    """One update past the warmup's zero learning rate: the program's
    donated apply step and the reference's AdamW, its moments on the
    device or on the host, agree."""
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
    copy = lambda t: jax.tree.map(jnp.copy, t)  # noqa: E731
    put = copy if where == "device" else (  # noqa: E731
        lambda t: jax.tree.map(np.array, t))
    rp, rm, rv, _, _ = Reference(model, JOB)._adamw(
        copy(params), grads, put(m), put(v), step)
    assert all(isinstance(x, np.ndarray) == (where == "host")
               for x in jax.tree.leaves((rm, rv)))
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


def _whole_tree_adamw(params, grads, m, v, step):
    """AdamW over the whole tree at once, all of it on the device."""
    j = JOB
    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    scale = jnp.minimum(1.0, j["grad_clip"] / jnp.maximum(gnorm, 1e-12))
    s = step.astype(jnp.float32)
    lr = j["learning_rate"] * s / j["warmup_steps"]      # inside the warmup
    step = step + 1
    c1 = 1.0 - j["adam_b1"] ** step.astype(jnp.float32)
    c2 = 1.0 - j["adam_b2"] ** step.astype(jnp.float32)
    m = jax.tree.map(lambda m, g: j["adam_b1"] * m
                     + (1 - j["adam_b1"]) * g * scale, m, grads)
    v = jax.tree.map(lambda v, g: j["adam_b2"] * v
                     + (1 - j["adam_b2"]) * (g * scale) ** 2, v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / c1) / (jnp.sqrt(v / c2)
                                              + j["adam_eps"])
                                  + j["weight_decay"] * p), params, m, v)
    return params, m, v, step


@pytest.mark.parametrize("host_moments", [True, False])
def test_host_moments_follow_a_whole_tree_adamw(host_moments, monkeypatch):
    """Four steps with the moments on the host (the update one piece at a
    time) or on the device (a leaf at a time) give the losses, the moments
    before the last step and the final params of a whole-tree AdamW whose
    moments stay on the device.  Tolerance: relative 1e-6, a few float32
    roundings.  Each piece runs the same expressions, but as programs of
    their own XLA may fuse them otherwise (the global norm's sum, an
    update folded into one fusion), and four steps carry a rounding on
    into the next gradient."""
    monkeypatch.setattr(RF, "moments_fit", lambda p: not host_moments)
    fetched = []
    monkeypatch.setattr(Reference, "_fetch", staticmethod(
        lambda *a, f=Reference._fetch: fetched.append(1) or f(*a)))
    ref = Reference(TINY["model"], JOB)
    params = init_params(TINY["model"], 5)
    batches = [Batches(TINY["model"]["vocab_size"], JOB, 5).batch_at(i)
               for i in range(4)]
    got = ref.train(jax.tree.map(jnp.copy, params), batches,
                    check.leaf_norms, first=3, keep_state=True)
    assert all(isinstance(x, np.ndarray)
               for x in jax.tree.leaves(got["state"][:2]))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    step, losses, update = jnp.int32(0), [], jax.jit(_whole_tree_adamw)
    for i, batch in enumerate(batches):
        if i == 3:
            _close(got["state"][:2], (m, v), rtol=1e-6, atol=1e-12)
            assert int(got["state"][2]) == int(step) == 3
        loss, grads = ref.loss_and_grads(params, batch)
        losses.append(loss)
        params, m, v, step = update(params, grads, m, v, step)
    np.testing.assert_allclose(got["losses"], losses, rtol=1e-6)
    _close(got["params"], params, rtol=1e-6, atol=1e-9)
    assert bool(fetched) == host_moments


@pytest.mark.parametrize("host_moments", [True, False])
def test_a_state_handed_back_starts_another_run(host_moments, monkeypatch):
    """``train(state=...)`` copies the moments it is handed: two runs
    from one kept state give the same params, and the state is left as
    it was."""
    monkeypatch.setattr(RF, "moments_fit", lambda p: not host_moments)
    ref = Reference(TINY["model"], JOB)
    batches = [Batches(TINY["model"]["vocab_size"], JOB, 6).batch_at(i)
               for i in range(3)]
    kept = ref.train(init_params(TINY["model"], 6), batches,
                     check.leaf_norms, first=2, keep_state=True)
    before = jax.tree.map(np.copy, kept["state"][:2])
    runs = [ref.train(jax.device_put(kept["start"]), batches[2:],
                      check.leaf_norms, 0, kept["state"])["params"]
            for _ in range(2)]
    _close(runs[0], runs[1], rtol=0.0)
    _close(runs[0], kept["params"], rtol=0.0)
    _close(kept["state"][:2], before, rtol=0.0)


class _Device:
    def __init__(self, stats):
        self.stats = stats

    def memory_stats(self):
        return self.stats


def test_moments_go_to_the_host_only_where_they_do_not_fit(monkeypatch):
    """On a v5e's 15.75 GiB, qwen1.5-0.5b's float32 params, gradients and
    moments (9.9 GB) leave room for a row's activations, so the existing
    cells keep the moments on the device; qwen2-7b's widths at 4 of 28
    layers and vocab 19008 (17.1 GB) do not.  A device that states no
    limit keeps them."""
    small = json.loads((ROOT / "bench" / "configs" / "qwen1.5-0.5b.json"
                        ).read_text())["model"]
    big = dict(small, num_layers=4, d_model=3584, num_heads=28,
               num_kv_heads=4, head_dim=128, d_ff=18944, vocab_size=19008)
    small, big = (jax.eval_shape(lambda m=m: init_params(m, 0))
                  for m in (small, big))
    v5e = int(15.748 * 2**30)
    monkeypatch.setattr(jax, "devices",
                        lambda *a: [_Device({"bytes_limit": v5e})])
    assert RF.moments_fit(small) and not RF.moments_fit(big)
    monkeypatch.setattr(jax, "devices", lambda *a: [_Device(None)])
    assert RF.moments_fit(big)

"""Mesh-dependent tests — run in child processes with 8 host devices so the
main pytest process keeps seeing exactly 1 CPU device."""
import pytest

from tests.conftest import run_child


def test_sharded_train_step_matches_single_device():
    run_child("""
import jax, jax.numpy as jnp, numpy as np
import repro.configs as C
from repro.common.config import TrainConfig, ShapeConfig
from repro.distributed import sharding as shd, steps as S
from repro.launch import specs as SP
from repro.launch.mesh import make_test_mesh
from repro.models.registry import get_api
from repro.optim.adamw import adamw_init

cfg = C.get_reduced('llama2_paper')
api = get_api(cfg)
params, _ = api.init(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
batch = {'tokens': jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size),
         'labels': jax.random.randint(jax.random.PRNGKey(2), (4, 32), 0, cfg.vocab_size)}
tcfg = TrainConfig(warmup_steps=0)
step = S.make_train_step(cfg, tcfg)

# single-device reference
p1, o1, m1 = jax.jit(step)(params, opt, batch, jnp.float32(1.0))

# sharded on a (2,4) mesh
mesh = make_test_mesh((2, 4))
shape = ShapeConfig('t', 'train', 32, 4)
with shd.use_mesh(mesh):
    in_sh, _ = SP.train_shardings(cfg, shape, mesh, zero_stage=2)
    jf = jax.jit(step, in_shardings=in_sh)
    p2, o2, m2 = jf(params, opt, batch, jnp.float32(1.0))
np.testing.assert_allclose(float(m1['loss']), float(m2['loss']), rtol=1e-5)
for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-4)
print('SHARDED == SINGLE OK')
""")


def test_dryrun_cell_reduced_mesh():
    """The dry-run machinery end-to-end on a reduced config + small mesh:
    lower + compile + memory/cost/roofline extraction."""
    run_child("""
import repro.configs as C
from repro.common.config import ShapeConfig
from repro.launch.mesh import make_test_mesh
from repro.launch.dryrun import run_cell

mesh = make_test_mesh((2, 4))
cfg = C.get_reduced('qwen2_7b')
shape = ShapeConfig('train_4k', 'train', 64, 8)
rec = run_cell('qwen2_7b', 'train_4k', False, 'none', None, verbose=False,
               mesh=mesh, cfg=cfg, shape=shape)
assert rec['status'] == 'ok', rec
assert rec['roofline']['flops_per_chip'] > 0
assert rec['memory']['peak_per_chip'] > 0
rec2 = run_cell('qwen2_7b', 'decode_32k', False, 'none', None, verbose=False,
                mesh=mesh, cfg=cfg, shape=ShapeConfig('decode_32k', 'decode', 256, 8))
assert rec2['status'] == 'ok', rec2
print('DRYRUN REDUCED OK')
""")


def test_offload_policy_compiles_on_mesh():
    run_child("""
import jax, jax.numpy as jnp
import repro.configs as C
from repro.common.config import TrainConfig, ShapeConfig, ChameleonConfig
from repro.core.executor import Executor
from repro.distributed import sharding as shd, steps as S
from repro.launch import specs as SP
from repro.launch.mesh import make_test_mesh
from repro.models.registry import get_api
from repro.optim.adamw import adamw_init

cfg = C.get_reduced('llama2_paper')
api = get_api(cfg)
params, _ = api.init(cfg, jax.random.PRNGKey(0))
opt = adamw_init(params)
batch = {'tokens': jnp.ones((4, 32), jnp.int32), 'labels': jnp.ones((4, 32), jnp.int32)}
mesh = make_test_mesh((2, 4))
pol = Executor(ChameleonConfig()).conservative(None).to_jax()
step = S.make_train_step(cfg, TrainConfig(), pol)
with shd.use_mesh(mesh):
    in_sh, _ = SP.train_shardings(cfg, ShapeConfig('t','train',32,4), mesh, 2)
    c = jax.jit(step, in_shardings=in_sh).lower(params, opt, batch, jnp.float32(1.0)).compile()
    out = c(params, opt, batch, jnp.float32(1.0))
    jax.block_until_ready(out)
print('OFFLOAD ON MESH OK')
""")


def test_compressed_grad_sync_int8_on_wire():
    """Cross-pod int8 all-gather: s8 operands must appear in the HLO and
    EF-compressed sync must approximate the true mean."""
    run_child("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.compression import make_compressed_grad_sync
from jax import shard_map
from repro.launch.mesh import make_test_mesh

mesh = make_test_mesh((4, 2), ('pod', 'model'))
sync = make_compressed_grad_sync(mesh, 'pod')

def f(g, e):
    return sync({'w': g}, {'w': e})

sm = shard_map(f, mesh=mesh, in_specs=(P('pod', None), P('pod', None)),
               out_specs=(P('pod', None), P('pod', None)))
g = jnp.asarray(np.random.RandomState(0).randn(8, 64), jnp.float32)
e = jnp.zeros_like(g)
jf = jax.jit(sm)
txt = jf.lower(g, e).compile().as_text()
assert 's8[' in txt and 'all-gather' in txt, 'int8 all-gather missing from HLO'
synced, err = jf(g, e)
true_mean = np.mean(np.asarray(g).reshape(4, 2, 64), axis=0)
got = np.asarray(synced['w']).reshape(4, 2, 64)[0]
rel = np.abs(got - true_mean).max() / (np.abs(true_mean).max() + 1e-9)
assert rel < 0.05, rel
print('COMPRESSED SYNC OK')
""")


def test_elastic_restore_new_mesh(tmp_path):
    """Checkpoint written under one topology restores onto another mesh
    (elastic restart after excluding a failed node)."""
    run_child(f"""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.checkpointing.manager import CheckpointManager
from repro.launch.mesh import make_test_mesh

tree = {{'w': jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
mgr = CheckpointManager('{tmp_path}', keep=2)
mesh1 = make_test_mesh((4, 2))
sh1 = NamedSharding(mesh1, P('data', 'model'))
tree1 = {{'w': jax.device_put(tree['w'], sh1)}}
mgr.save(1, {{'params': tree1}}, extra={{'step': 1}}, block=True)

mesh2 = make_test_mesh((2, 2))   # 'smaller cluster' after failure
sh2 = NamedSharding(mesh2, P('data', 'model'))
restored, extra = mgr.restore(1, {{'params': tree}},
                              shardings={{'params': {{'w': sh2}}}})
np.testing.assert_array_equal(np.asarray(restored['params']['w']),
                              np.asarray(tree['w']))
assert restored['params']['w'].sharding == sh2
print('ELASTIC RESTORE OK')
""")

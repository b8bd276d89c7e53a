"""The HBM budget counts only the AdamW state held in device memory, every
policy pass of the runtime gets it, and a configuration file can name its
own reference (``bench/references/<config>.py``), which ``bench.run`` and
``bench.readings`` then use."""
import json
import shutil
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import readings  # noqa: E402
from bench import run as R  # noqa: E402
from bench import spec as SPEC  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**31 + 23


def _cell(root: Path = ROOT, config: str = "tiny") -> SPEC.Cell:
    return SPEC.Cell(
        name="tiny-dense.budget",
        entry={"name": "tiny", "config": config, "chips": 1},
        config=json.loads((DATA / "tiny-dense.json").read_text()),
        traffic=json.loads((DATA / "tiny-traffic.json").read_text()),
        limits=json.loads((DATA / "tiny-limits.json").read_text())["limits"],
        root=root)


def _placed(tree, kind: str):
    return jax.device_put(tree, jax.sharding.SingleDeviceSharding(
        jax.devices()[0], memory_kind=kind))


def _on_host(tree):
    return _placed(tree, "pinned_host")


def _state():
    from repro.distributed import steps as S
    from repro.optim.adamw import adamw_init
    params = jax.tree.map(lambda s: jax.numpy.zeros(s.shape, s.dtype),
                          S.abstract_params(R.model_config(_cell())))
    return adamw_init(params)


def _parents_budget(limit: int) -> int:
    """The budget as it was computed before the state's placement was
    read: the whole AdamW state from ``jax.eval_shape``."""
    from repro.distributed import steps as S
    from repro.optim.adamw import adamw_init
    opt = jax.eval_shape(adamw_init,
                         S.abstract_params(R.model_config(_cell())))
    return limit - R.tree_bytes(opt) - R.MARGIN_BYTES


LIMIT = 16 * 2**30


def test_state_in_hbm_gives_the_parents_budget():
    state = _state()
    assert R.placed_bytes(state) == (R.tree_bytes(state), 0)
    assert R.hbm_budget(LIMIT, state) == _parents_budget(LIMIT)


def test_host_moments_lower_the_subtracted_bytes_by_their_size():
    state = _state()
    moved = state._replace(m=_on_host(state.m), v=_on_host(state.v))
    moments = R.tree_bytes((state.m, state.v))
    assert moments > 0
    assert {x.sharding.memory_kind for x in
            jax.tree.leaves((moved.m, moved.v))} == {"pinned_host"}
    assert R.placed_bytes(moved) == (R.tree_bytes(state) - moments, moments)
    assert (R.hbm_budget(LIMIT, moved)
            == R.hbm_budget(LIMIT, state) + moments)


@pytest.fixture
def host_moments(monkeypatch):
    """Trainers built with their Adam moments in pinned host memory."""
    import repro.runtime.trainer as T

    class HostMoments(T.Trainer):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            st = self.opt_state
            self.opt_state = st._replace(m=_on_host(st.m), v=_on_host(st.v))
    monkeypatch.setattr(T, "Trainer", HostMoments)


def test_build_budget_is_the_parents_with_the_state_in_hbm(tmp_path):
    tr = R.build(_cell(), SEED, LIMIT, str(tmp_path))
    assert tr.rt.budget == tr.cham.hbm_budget_bytes == _parents_budget(LIMIT)


def test_build_budget_from_host_moments(host_moments, tmp_path, capsys):
    tr = R.build(_cell(), SEED, LIMIT, str(tmp_path))
    moments = R.tree_bytes((tr.opt_state.m, tr.opt_state.v))
    assert tr.rt.budget == _parents_budget(LIMIT) + moments
    assert f"({moments} B on the host)" in capsys.readouterr().err


def test_every_policy_pass_gets_the_runtime_budget(host_moments, tmp_path,
                                                   monkeypatch):
    """Built with host moments and a budget that binds, the runtime's
    budget differs from its config's; every policy generation and passive
    swap of set-up is handed the runtime's, none falls back to the
    config's ``hbm_budget_bytes``."""
    import repro.adapt.pipeline as P
    import repro.core.oom as O
    seen = []

    def recording(fn, at):
        def wrapped(*a, **kw):
            seen.append(a[at] if len(a) > at else kw.get("budget"))
            return fn(*a, **kw)
        return wrapped
    monkeypatch.setattr(P, "generate_policy", recording(P.generate_policy, 2))
    monkeypatch.setattr(O, "passive_swap_fit",
                        recording(O.passive_swap_fit, 2))
    device = R.tree_bytes(_state()) - R.tree_bytes((_state().m, _state().v))
    tr = R.build(_cell(), SEED, device + R.MARGIN_BYTES + 20_000,
                 str(tmp_path))
    assert tr.rt.budget == 20_000 != tr.cham.hbm_budget_bytes
    # the program's steps take their state in device memory
    tr.opt_state = _placed(tr.opt_state, "device")
    R.setup(tr, _cell())
    assert seen and set(seen) == {20_000}


STUB = '''
import jax.numpy as jnp

MADE = []


def init_params(model, seed):
    return {"w": jnp.full((3,), float(seed % 7))}


class Reference:
    def __init__(self, model, job, precision="f32"):
        MADE.append(precision)

    def train(self, params, batches, norms, first=0, state=None,
              keep_state=False):
        out = {"losses": [0.5] * len(batches), "grad": {"w": 1.0},
               "start": {"w": params["w"]}, "params": {"w": params["w"] + 2}}
        if keep_state:
            out["state"] = ("m", "v", first)
        return out
'''


@pytest.fixture
def stub_root(tmp_path):
    """A checkout with a reference of its own for the configuration
    ``stubbed``: ``bench/references/stubbed.py``."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench")
    (tmp_path / "bench" / "references").mkdir()
    (tmp_path / "bench" / "references" / "stubbed.py").write_text(STUB)
    return tmp_path


def test_a_config_finds_its_own_reference_by_name(stub_root):
    cell = _cell(stub_root, "stubbed")
    mod = cell.reference()
    assert mod.__file__ == str(stub_root / "bench" / "references"
                               / "stubbed.py")
    assert cell.reference() is mod
    import bench.reference
    assert _cell(stub_root).reference() is bench.reference
    assert _cell().reference() is bench.reference


def test_run_and_readings_use_the_configs_reference(stub_root):
    cell = _cell(stub_root, "stubbed")
    stub = cell.reference()
    got = R.reference_numbers(cell, SEED, [0, 1, 2], 1, keep_state=True)
    assert got["losses"] == [0.5] * 3 and got["state"] == ("m", "v", 1)
    assert got["update"] == {"w": pytest.approx(2 * 3 ** 0.5)}
    prog = {"first": 1, "batches": [0, 1, 2]}
    ctl = readings.planted(cell, SEED, prog, "control", got)
    assert ctl["losses"] == [0.5] * 3
    half = readings.planted(cell, SEED, prog, "half_batch", got)
    assert half["losses"] == [0.5] * 3
    assert stub.MADE == ["f32", "fp8", "f32"]

"""A run of the harness on the CPU at a reduced size, with the chip check
skipped: sound, it comes out correct; with the timed path broken
underneath (a step that leaves the state unchanged, half the batch left
out, a gradient altered where it is produced, in every step or only in
the Stable policy's grad step) it comes out not correct; and the float8
control and the planted faults of ``bench.readings`` fail the limits the
program passes."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

import jax  # noqa: E402

from bench import check, readings  # noqa: E402
from bench import run as R  # noqa: E402
from bench import spec as SPEC  # noqa: E402

DATA = Path(__file__).resolve().parent / "data"
SEED = 2**31 + 11


def _cell() -> SPEC.Cell:
    bench = SPEC.load()
    return SPEC.Cell(
        name="tiny-dense.drift", entry={"name": "tiny", "chips": 1},
        config=json.loads((DATA / "tiny-dense.json").read_text()),
        traffic=json.loads((DATA / "tiny-traffic.json").read_text()),
        limits=json.loads((DATA / "tiny-limits.json").read_text())["limits"],
        end_to_end=bench["end_to_end"], per_layer=[])


def _limit_bytes(cell) -> int:
    """A device whose budget binds: AdamW state + margin + 20 kB."""
    from repro.distributed import steps as S
    from repro.optim.adamw import adamw_init
    opt = jax.eval_shape(adamw_init, S.abstract_params(R.model_config(cell)))
    return R.tree_bytes(opt) + R.MARGIN_BYTES + 20_000


def _run() -> dict:
    cell = _cell()
    return R.run_cell(cell, SEED, 0.5, False, _limit_bytes(cell))


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == set(_cell().limits)
    assert {"tokens_per_s", "setup_s"} <= set(res["metrics"])


def _state_unchanged(S, monkeypatch):
    monkeypatch.setattr(S, "jit_apply_step",
                        lambda cfg, tcfg: (lambda p, o, g: (p, o, {})))


def _half_batch(S, monkeypatch):
    orig = S.make_grad_step

    def make(cfg, tcfg, policy=None):
        step = orig(cfg, tcfg, policy)

        def grad_step(params, batch, scale):
            n = batch["tokens"].shape[0] // 2
            return step(params, {k: v[:n] for k, v in batch.items()}, scale)
        return grad_step
    monkeypatch.setattr(S, "make_grad_step", make)


def _answer_altered(S, monkeypatch):
    orig = S.make_grad_step

    def make(cfg, tcfg, policy=None):
        step = orig(cfg, tcfg, policy)

        def grad_step(params, batch, scale):
            loss, grads, finite = step(params, batch, scale)
            wo = grads["blocks"]["mlp"]["wo"]
            grads["blocks"]["mlp"]["wo"] = wo.at[wo.shape[0] // 2].multiply(2)
            return loss, grads, finite
        return grad_step
    monkeypatch.setattr(S, "make_grad_step", make)


def _stable_step_altered(S, monkeypatch):
    """Only the grad step that the Stable policy runs is wrong: the steps
    before Stable, and every other program, are sound."""
    from repro.core import runtime as RT
    from repro.core.stages import Stage
    orig = RT.ChameleonRuntime.step_fn

    def step_fn(self):
        fn = orig(self)
        if self.machine.stage is not Stage.STABLE:
            return fn

        def grad_step(params, batch, scale):
            loss, grads, finite = fn(params, batch, scale)
            wo = grads["blocks"]["mlp"]["wo"]
            grads["blocks"]["mlp"]["wo"] = wo.at[wo.shape[0] // 2].multiply(2)
            return loss, grads, finite
        return grad_step
    monkeypatch.setattr(RT.ChameleonRuntime, "step_fn", step_fn)


@pytest.mark.parametrize("plant", [_state_unchanged, _half_batch,
                                   _answer_altered, _stable_step_altered])
def test_broken_step_is_not_correct(plant, monkeypatch):
    from repro.distributed import steps as S
    plant(S, monkeypatch)
    res = _run()
    assert not res["correct"], res["checks"]


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    """The program's set-up and the float32 reference with its state
    before the compared steps, on one seed."""
    cell = _cell()
    tr = R.build(cell, SEED, _limit_bytes(cell),
                 str(tmp_path_factory.mktemp("ckpt")))
    prog = R.setup(tr, cell)
    R.free_state(tr)
    ref = R.reference_numbers(cell, SEED, prog["batches"], prog["first"],
                              keep_state=True)
    return cell, prog, ref


def test_compared_steps_run_under_stable(sound):
    cell, prog, ref = sound
    assert prog["first"] > 0 and len(set(prog["policies"])) == 1
    assert len(prog["losses"]) == prog["first"] + R.REF_STEPS
    ok, checks = check.verdict(check.numbers(prog, ref), cell.limits)
    assert ok, checks


@pytest.mark.parametrize("kind", ["control", "half_batch", "answer_altered"])
def test_planted_case_fails_where_the_program_passes(sound, kind):
    """The float8 control and each fault of ``bench.readings``, in the
    program's place, fail a limit that the program keeps to, on the same
    seed and batches."""
    cell, prog, ref = sound
    got = readings.planted(cell, SEED, prog, kind, ref)
    assert len(got["losses"]) == len(prog["losses"])
    ok, checks = check.verdict(check.numbers(got, ref), cell.limits)
    assert not ok, checks


def test_policy_share_of_the_window(sound):
    """The share of window iterations under the compared steps' policy is
    printed as a reading beside the compared numbers."""
    cell, prog, ref = sound
    pol = prog["policies"][0]
    win = dict(prog, window_policies={pol: 3, "another policy": 1})
    share, where = check.numbers(win, ref)["policy_share"]
    assert share == 0.75 and pol in where
    assert "policy_share" not in check.numbers(prog, ref)


def _bench_run(cwd, env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, *SPEC.load()["command"][1:], "--workload",
         SPEC.load()["workloads"][0]["name"], "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = _bench_run(ROOT, env)
    assert out.returncode != 0 and "{" not in out.stdout
    assert "TPU" in out.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark's paths
    has no program to measure."""
    bench = SPEC.load()
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bench["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = _bench_run(tmp_path, dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0 and "{" not in out.stdout

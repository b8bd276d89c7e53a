"""Trainer + ChameleonRuntime integration: the paper's long-term-stability
scenario (Fig 7) at mini scale, fault tolerance, stragglers, serving."""
import shutil
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.common.config import ChameleonConfig, TrainConfig
from repro.core.stages import Stage
from repro.data.synthetic import SyntheticTokens
from repro.runtime.server import Server
from repro.runtime.straggler import StragglerDetector
from repro.runtime.trainer import Trainer


@pytest.fixture
def tmpdir():
    d = tempfile.mkdtemp()
    yield d
    shutil.rmtree(d, ignore_errors=True)


def _trainer(tmpdir, *, cham=False, eval_every=0, steps=30, seed=0,
             budget=1 << 60, seq=64, batch=4):
    cfg = C.get_reduced("llama2_paper")
    tcfg = TrainConfig(steps=steps, checkpoint_every=10,
                       checkpoint_dir=tmpdir, eval_every=eval_every,
                       warmup_steps=2, learning_rate=1e-3)
    data = SyntheticTokens(cfg.vocab_size, seq, batch, seed=seed)
    return Trainer(cfg, tcfg,
                   ChameleonConfig(enabled=cham, hbm_budget_bytes=budget),
                   data=data)


def test_trainer_refuses_mesh(tmpdir):
    """No training path shards state: a mesh must fail loudly, not be
    stored and ignored."""
    cfg = C.get_reduced("llama2_paper")
    with pytest.raises(NotImplementedError):
        Trainer(cfg, TrainConfig(checkpoint_dir=tmpdir), mesh=object())


def test_loss_decreases(tmpdir):
    tr = _trainer(tmpdir, steps=25)
    rep = tr.train(25)
    first = np.mean(rep.losses[:5])
    last = np.mean(rep.losses[-5:])
    assert last < first, (first, last)


def test_long_term_stability_with_sequence_changes(tmpdir):
    """Paper Fig 7: on-the-fly validation changes the operator sequence;
    Chameleon adapts (Capuchin crashes).  Loss must exactly track the
    no-chameleon baseline — swap never changes math."""
    tr = _trainer(tmpdir, cham=True, eval_every=13, steps=40,
                  budget=20 << 20)  # tight budget: policies really generate
    rep = tr.train(40)
    assert not rep.failures
    stages = set(rep.stages)
    assert "GenPolicy" in stages and "Stable" in stages
    # sequence change detected at the eval step -> WarmUp re-entry
    assert any(why == "seq-change" for _, why, _s in tr.rt.machine.transitions)

    d2 = tempfile.mkdtemp()
    try:
        base = _trainer(d2, cham=False, eval_every=13, steps=40)
        rep2 = base.train(40)
        np.testing.assert_allclose(rep.losses, rep2.losses, rtol=2e-4,
                                   atol=2e-4)
    finally:
        shutil.rmtree(d2, ignore_errors=True)


def test_resume_bitexact(tmpdir):
    tr = _trainer(tmpdir, steps=20, seed=7)
    tr.tcfg = tr.tcfg.__class__(**{**tr.tcfg.__dict__,
                                   "checkpoint_every": 0,
                                   "checkpoint_dir": tmpdir})
    tr.train(10)
    tr._checkpoint(block=True)     # single checkpoint at step 10
    cont = tr.train(10)
    ref_losses = cont.losses[:]

    tr2 = _trainer(tmpdir, steps=20, seed=7)
    assert tr2.resume()
    assert tr2.step == 10
    rep2 = tr2.train(10)
    np.testing.assert_allclose(ref_losses[10:], rep2.losses, rtol=1e-6)


def test_emergency_checkpoint_on_failure(tmpdir):
    tr = _trainer(tmpdir, steps=50)

    def bomb(step):
        if step == 7:
            raise RuntimeError("injected node failure")

    with pytest.raises(RuntimeError, match="injected"):
        tr.train(50, fault_hook=bomb)
    assert tr.report.failures
    # the emergency checkpoint carries post-step-7 state as step 8, so
    # resume does NOT replay the already-applied update
    assert tr.ckpt.latest_step() == 8

    tr2 = _trainer(tmpdir, steps=50)
    assert tr2.resume() and tr2.step == 8


def test_loss_scale_skip_changes_sequence(tmpdir):
    """Force a gradient overflow: the optimizer dispatch is skipped and the
    iteration's op sequence shortens (§2.3's primary cause)."""
    tr = _trainer(tmpdir, steps=6)
    tr.loss_scale = tr.loss_scale._replace(scale=jnp.float32(1e38))
    rep = tr.train(4)
    assert rep.skipped_steps, "overflow must skip an optimizer step"
    assert float(tr.loss_scale.scale) < 1e38


def test_straggler_detection():
    det = StragglerDetector(threshold_sigma=4.0, warmup=3)
    rng = np.random.RandomState(0)
    for s in range(30):
        det.observe(s, 0.10 + abs(rng.randn()) * 0.004)
    assert not det.events
    det.observe(30, 0.50)   # 5x outlier
    assert len(det.events) == 1 and det.events[0].step == 30
    w = det.skew_map({0: 0.1, 1: 0.2})
    assert w[0] > w[1]
    assert abs(sum(w.values()) - 1.0) < 1e-9


def test_server_matches_single_request():
    cfg = C.get_reduced("llama2_paper")
    from repro.models.registry import get_api
    api = get_api(cfg)
    params, _ = api.init(cfg, jax.random.PRNGKey(0))
    prompt = np.arange(6, dtype=np.int32) % cfg.vocab_size

    srv1 = Server(cfg, params, max_batch=1, max_len=32)
    r1 = srv1.submit(prompt, max_new_tokens=5)
    out1 = srv1.run_until_done()[r1]

    srv2 = Server(cfg, params, max_batch=3, max_len=32)
    ra = srv2.submit(prompt, max_new_tokens=5)
    rb = srv2.submit((np.arange(9) * 3) % cfg.vocab_size, max_new_tokens=4)
    out2 = srv2.run_until_done()
    assert out2[ra] == out1, "batched decode must match single-request"
    assert len(out2[rb]) == 4


def test_profiling_overhead_small(tmpdir):
    """Lightweight-mode bookkeeping must stay a small fraction of step time
    (paper Table 1: 0.9%).  CPU steps are ms-scale so allow generous 30%."""
    tr = _trainer(tmpdir, cham=True, steps=20)
    rep = tr.train(20)
    total = sum(rep.times[5:])
    assert tr.rt.profiling_overhead_s < 0.5 * total

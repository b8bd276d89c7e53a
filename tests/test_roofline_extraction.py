"""Unit tests for the roofline extraction machinery (HLO structural walk +
scan-aware jaxpr cost model) — these guard the numbers in EXPERIMENTS.md."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.autotune.device import DEVICE_SPECS
from repro.launch.roofline import (collective_bytes, jaxpr_cost,
                                   model_flops_train, RooflineTerms)


SYNTH_HLO = """
%add.clone (x: f32[], y: f32[]) -> f32[] {
  ROOT %r = f32[] add(%x, %y)
}

%cond.1 (arg: (s32[], f32[128,256])) -> pred[] {
  %iv = s32[] get-tuple-element(%arg), index=0
  %c = s32[] constant(24)
  ROOT %lt = pred[] compare(%iv, %c), direction=LT
}

%body.1 (arg: (s32[], f32[128,256])) -> (s32[], f32[128,256]) {
  %x = f32[128,256]{1,0} get-tuple-element(%arg), index=1
  %ar = f32[128,256]{1,0} all-reduce(%x), to_apply=%add.clone
  ROOT %t = (s32[], f32[128,256]) tuple(%iv, %ar)
}

ENTRY %main.1 (p0: f32[128,256]) -> f32[128,256] {
  %w = (s32[], f32[128,256]) while(%init), condition=%cond.1, body=%body.1
  %ag = f32[512,256]{1,0} all-gather(%p0), dimensions={0}
  ROOT %out = f32[128,256]{1,0} get-tuple-element(%w), index=1
}
"""


def test_collective_bytes_while_multiplier():
    out = collective_bytes(SYNTH_HLO)
    # all-reduce inside the 24-trip loop: 128*256*4 bytes * 2 (ring) * 24
    assert out["all-reduce"] == pytest.approx(128 * 256 * 4 * 2 * 24)
    # all-gather in main: result bytes * 1
    assert out["all-gather"] == pytest.approx(512 * 256 * 4)


def test_collective_bytes_no_collectives():
    assert collective_bytes("ENTRY %m (x: f32[4]) -> f32[4] {\n}") == {}


def test_jaxpr_cost_matmul():
    def f(a, b):
        return a @ b

    cj = jax.make_jaxpr(f)(jnp.ones((64, 128)), jnp.ones((128, 32)))
    fl, by = jaxpr_cost(cj)
    assert fl == pytest.approx(2 * 64 * 128 * 32)
    assert by == pytest.approx((64 * 128 + 128 * 32 + 64 * 32) * 4)


def test_jaxpr_cost_scan_multiplies():
    def f(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        return jax.lax.scan(body, x, ws)[0]

    cj = jax.make_jaxpr(f)(jnp.ones((16, 64)), jnp.ones((10, 64, 64)))
    fl, _ = jaxpr_cost(cj)
    assert fl == pytest.approx(10 * 2 * 16 * 64 * 64)


def test_jaxpr_cost_counts_remat_recompute():
    def f(x, w):
        def g(x):
            return jnp.sum(jnp.tanh(x @ w) ** 2)
        return jax.grad(jax.checkpoint(g))(x)

    x, w = jnp.ones((32, 32)), jnp.ones((32, 32))
    fl_remat, _ = jaxpr_cost(jax.make_jaxpr(f)(x, w))

    def f2(x, w):
        return jax.grad(lambda x: jnp.sum(jnp.tanh(x @ w) ** 2))(x)

    fl_plain, _ = jaxpr_cost(jax.make_jaxpr(f2)(x, w))
    assert fl_remat > fl_plain  # the recompute is visible

def test_roofline_terms_bottleneck():
    t = RooflineTerms(
        flops_per_chip=197e12,       # 1 s compute
        bytes_per_chip=819e9 / 2,    # 0.5 s memory
        wire_bytes_per_chip=50e9 * 2,  # 2 s collective
        collectives={}, chips=256,
        model_flops=0.8 * 197e12 * 256).finalize(DEVICE_SPECS["tpu_v5e"])
    assert t.bottleneck == "collective"
    assert t.step_time_bound_s == pytest.approx(2.0)
    assert t.mfu_bound == pytest.approx(0.4)
    assert t.useful_flops_ratio == pytest.approx(0.8)


def test_model_flops():
    assert model_flops_train(10 ** 9, 10 ** 6) == 6e15

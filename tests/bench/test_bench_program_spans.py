"""The readers of the program-span metrics on hand-built span rings: the
window keeps the roots stamped with its iterations and what hangs under
them, ``trainer.host_ms`` subtracts the waits, a complete window without
the span reads 0, and a ring that dropped part of the window (or a program
that records no span tree) reads None."""
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec as SPEC  # noqa: E402
from repro import obs  # noqa: E402

CELL = SPEC.resolve(SPEC.load()["workloads"][0]["name"])
HOST, MIRROR, OBS, MOVES = ("trainer.host_ms", "runtime.mirror_ms",
                            "runtime.obs_ms", "policy.moves")


def _iteration(tr, it):
    """One trainer.train call as the program records it: a mirror on odd
    iterations, a policy install on iteration 3."""
    tr.set_iteration(it)
    with tr.span(obs.LANE_HOST, "trainer.train"):
        with tr.span(obs.LANE_COMPUTE, "train_step"):
            with tr.span(obs.LANE_HOST, "trainer.grad_dispatch"):
                pass
            with tr.span(obs.LANE_HOST, "trainer.grad_wait"):
                sum(range(2000))
        with tr.span(obs.LANE_COMPUTE, "apply_step"):
            with tr.span(obs.LANE_HOST, "trainer.apply_wait"):
                sum(range(1000))
        with tr.span(obs.LANE_HOST, "runtime.end_iteration"):
            if it % 2:
                with tr.span(obs.LANE_HOST, "runtime.mirror"):
                    tr.record(obs.LANE_POLICY_SWAP, "swap_out", 0.0, 1.0)
            if it == 3:
                with tr.span(obs.LANE_HOST, "runtime.ladder"):
                    tr.instant(obs.LANE_ADAPT, "policy.install",
                               arg="ladder")
            with tr.span(obs.LANE_HOST, "runtime.obs_close"):
                pass
        with tr.span(obs.LANE_HOST, "trainer.finish"):
            pass


@pytest.fixture()
def ring():
    """A ring of six calls and an install outside any of them."""
    tr = obs.SpanTracer(capacity=1 << 10)
    old = obs.set_tracer(tr)
    try:
        for it in range(6):
            _iteration(tr, it)
        tr.instant(obs.LANE_ADAPT, "policy.install", arg="prepare")
        yield tr
    finally:
        obs.set_tracer(old)


def _read(name, first, n):
    return CELL.reader(name)(SimpleNamespace(
        window={"first_step": first, "iterations": n}))


def _ms(tr, first, n, name):
    return sum(r["t1"] - r["t0"] for r in tr.records()
               if r["name"] == name and first <= r["iter"] < first + n
               ) / n * 1e3


def test_host_time_is_the_roots_less_the_waits(ring):
    host = _ms(ring, 1, 3, "trainer.train") - sum(
        _ms(ring, 1, 3, w) for w in ("trainer.grad_wait",
                                     "trainer.apply_wait"))
    assert _read(HOST, 1, 3) == pytest.approx(host, rel=1e-9)
    assert 0 < host < _ms(ring, 1, 3, "trainer.train")


@pytest.mark.parametrize("name, span", [(MIRROR, "runtime.mirror"),
                                        (OBS, "runtime.obs_close")])
def test_span_time_per_iteration_of_the_window(ring, name, span):
    assert _read(name, 1, 3) == pytest.approx(_ms(ring, 1, 3, span),
                                              rel=1e-9)
    assert _read(name, 0, 6) == pytest.approx(_ms(ring, 0, 6, span),
                                              rel=1e-9)


def test_a_window_without_the_span_reads_zero(ring):
    assert _read(MIRROR, 2, 1) == 0.0
    assert _read(MOVES, 4, 2) == 0


def test_moves_count_installs_under_the_windows_roots(ring):
    assert _read(MOVES, 0, 6) == 1          # not the one outside any call
    assert _read(MOVES, 3, 1) == 1
    assert _read(MOVES, 0, 3) == 0


@pytest.mark.parametrize("name", [HOST, MIRROR, OBS, MOVES])
def test_none_without_every_root_of_the_window(ring, name):
    assert _read(name, 4, 3) is None        # no iteration 6
    obs.set_tracer(obs.SpanTracer(capacity=64))   # a program with no tree
    assert _read(name, 0, 1) is None


@pytest.mark.parametrize("name", [HOST, MIRROR, OBS, MOVES])
def test_none_when_the_ring_dropped_part_of_the_window(name):
    tr = obs.SpanTracer(capacity=16)        # fewer slots than two calls
    old = obs.set_tracer(tr)
    try:
        for it in range(4):
            _iteration(tr, it)
        assert tr.stats()["dropped"] > 0
        roots = {r["iter"] for r in tr.records()
                 if r["name"] == "trainer.train"}
        assert {2, 3} <= roots              # both roots still held ...
        assert _read(name, 2, 2) is None    # ... but not all under them
        assert _read(name, 3, 1) is not None
    finally:
        obs.set_tracer(old)

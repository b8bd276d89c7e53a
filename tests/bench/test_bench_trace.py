"""The reduction from a profiler trace to busy time, idle share, top ops,
idle gaps by host span and host-offload exposure: on hand-made intervals,
and on a small trace recorded on a TPU v5e and checked in."""
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace as T  # noqa: E402

FIXTURE = Path(__file__).resolve().parent / "data" / "v5e_offload.xplane.pb"


def test_interval_arithmetic():
    assert T.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert T.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    assert T.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert T.length([(0, 3), (5, 8)]) == 6


def test_reduce_hand_made():
    """Window 0-100 ns inside a while loop (a container, left out): two
    fusions compute over 10-40 and 60-90; a host read (S(5)) is in flight
    over 30-70 and the device waits on it over 40-60, which falls in an
    end_iteration span; the ends 0-10 and 90-100 in the iteration span."""
    tr = T.Trace(
        ops={"/device:TPU:0": [
            (0, 100, "%while.1 = (s32[]) while((s32[]) %t), body=%b"),
            (10, 40, "%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p)"),
            (40, 60, "%dynamic-slice-done = bf16[8]{0} async-done("
                     "((bf16[4,8]{1,0:S(5)}), bf16[8]{0}) %s)"),
            (60, 90, "%fusion.2 = bf16[8]{0} fusion(bf16[8]{0} %q)")]},
        inflight={"/device:TPU:0": [
            (30, 70, "%dynamic-slice-start = ((bf16[4,8]{1,0:S(5)}), "
                     "bf16[8]{0}) async-start(bf16[4,8]{1,0:S(5)} %h)")]},
        spans=[(0, 100, "bench.iteration"), (35, 65, "bench.end_iteration")])
    out = T.reduce(tr)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx(60e-9)
    assert out["idle_share"] == pytest.approx(0.4)
    assert out["offload_s"] == pytest.approx(40e-9)
    assert out["offload_exposed_s"] == pytest.approx(20e-9)
    assert dict(out["device_ops"]) == {
        "fusion.1 fusion bf16[8]{0}": pytest.approx(30e-9),
        "fusion.2 fusion bf16[8]{0}": pytest.approx(30e-9),
        "dynamic-slice-done async-done bf16[8]{0}": pytest.approx(20e-9)}
    assert dict(out["idle_gaps"]) == {
        "bench.iteration": pytest.approx(20e-9),
        "bench.end_iteration": pytest.approx(20e-9)}


def test_reduce_recorded_v5e_trace():
    """Two iterations of a jitted grad step that offloads a scanned
    activation to pinned host memory, traced on one TPU v5e chip."""
    tr = T.load(str(FIXTURE))
    assert list(tr.ops) == ["/device:TPU:0"]
    assert [n for _, _, n in tr.spans].count("bench.iteration") == 2
    out = T.reduce(tr)
    assert 0 < out["busy_s"] < out["window_s"]
    assert 0 < out["idle_share"] < 1
    assert 0 < out["offload_exposed_s"] <= out["offload_s"] < out["window_s"]
    ops = [t for _, t in out["device_ops"]]
    assert ops == sorted(ops, reverse=True) and len(ops) <= 10
    gaps = sum(t for _, t in out["idle_gaps"])
    assert gaps == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)

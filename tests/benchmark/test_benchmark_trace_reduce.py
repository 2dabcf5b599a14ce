"""The reduction from a profiler trace to numbers: its arithmetic on a
hand-made trace, and fixed numbers on a small trace recorded on the chip
(benchmark/fixtures), so every later PR computes them the same way."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import trace_reduce  # noqa: E402


def test_union_and_subtraction_of_intervals():
    assert trace_reduce.union_seconds([]) == 0
    assert trace_reduce.union_seconds([(0, 4), (2, 6), (10, 11)]) == 7
    assert trace_reduce.union_seconds([(0, 10), (2, 3)]) == 10
    assert trace_reduce.subtract_seconds([(0, 10)], [(2, 3), (8, 12)]) == 7


def test_reduce_planes_on_a_hand_made_trace():
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_a(1)", 1000.0, 3000.0), ("jit_b(2)", 6000.0, 2000.0),
                ("jit_a(1)", 8000.0, 1000.0)]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 1000.0, 1000.0), ("all-reduce.1", 2000.0, 2000.0),
                ("fusion.2", 3000.0, 500.0), ("copy.3", 6000.0, 2000.0),
                ("fusion.1", 8000.0, 1000.0)]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [("fusion.1", 0.0, 10000.0)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [("wait", 0.0, 10000.0)]}]},
    ]
    out = trace_reduce.reduce_planes(planes)
    assert out["device_count"] == 2
    assert out["window_s"] == pytest.approx(10e-6)
    d0 = out["devices"]["/device:TPU:0"]
    assert d0["busy_s"] == pytest.approx(6e-6)
    assert d0["collective_s"] == pytest.approx(2e-6)
    assert d0["collective_exposed_s"] == pytest.approx(1.5e-6)
    assert d0["modules"]["jit_a"] == [2, pytest.approx(4e-6)]
    assert d0["top_ops"][0] == ["fusion.1", pytest.approx(2e-6)]
    assert d0["top_gaps"][0] == ["unattributed:before:jit_b",
                                 pytest.approx(2e-6)]
    assert out["busy_s"] == pytest.approx(8e-6)
    assert out["idle_share"] == pytest.approx(0.2)


def test_a_trace_without_a_device_plane_reads_as_nothing():
    out = trace_reduce.reduce_planes([{"name": "/host:CPU", "lines": [
        {"name": "t", "events": [("x", 0.0, 5.0)]}]}])
    assert out["device_count"] == 0 and "idle_share" not in out


FIXTURES = os.path.join(REPO, "benchmark", "fixtures")


@pytest.fixture(scope="module")
def one_chip_trace():
    """1.2 s cut from the traced heavy-replay run of PR 22 on one v5e
    chip (events that straddle the cut's edges were dropped)."""
    return trace_reduce.reduce_planes(trace_reduce.load(os.path.join(
        FIXTURES, "heavy-replay-1chip.xplane.pb.gz")))


def test_recorded_one_chip_trace_reduces_to_fixed_numbers(one_chip_trace):
    out = one_chip_trace
    assert out["device_count"] == 1
    assert out["window_s"] == pytest.approx(1.12348837, rel=1e-9)
    assert out["busy_s"] == pytest.approx(0.717992779, rel=1e-9)
    assert out["idle_share"] == pytest.approx(0.3609254904881659, rel=1e-9)
    dev = out["devices"]["/device:TPU:0"]
    assert dev["collective_s"] == 0.0 and dev["collective_exposed_s"] == 0.0
    assert {k: v[0] for k, v in dev["modules"].items()} == {
        "jit__downsample_grid": 3, "jit__grid_tail": 1,
        "jit__group_pipeline": 1, "jit_convert_element_type": 15,
        "jit_gather": 3}
    assert dev["modules"]["jit_gather"][1] == pytest.approx(0.116690455)
    assert dev["modules"]["jit__grid_tail"][1] == pytest.approx(0.054623761)
    # the gathers from the pinned columns lead the device's time
    name, seconds = dev["top_ops"][0]
    assert "f32[67108864]" in name and seconds == pytest.approx(0.171161117)
    assert dev["top_gaps"][0] == ["unattributed:trace-end",
                                  pytest.approx(0.583206455)]

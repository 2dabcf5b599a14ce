"""tools/trace_gaps.py: the device's idle gaps split over the request
phases live in them — the arithmetic on hand-made planes, and the trace
recorded on the chip (benchmark/fixtures), which predates the tsd.phase
annotations."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark import trace_reduce  # noqa: E402
from tools import trace_gaps  # noqa: E402

FIXTURE = os.path.join(REPO, "benchmark", "fixtures",
                       "heavy-replay-1chip.xplane.pb.gz")
S = 1e9      # the planes' clock ticks in ns


def phase(start_s, end_s, name, **stats):
    return ("tsd.phase", start_s * S, (end_s - start_s) * S,
            dict(stats, phase=name))


def span(start_s, end_s, name):
    return ("tsd.span", start_s * S, (end_s - start_s) * S, {"name": name})


def planes(host_lines, ops=((2.0, 4.0), (7.0, 8.0))):
    """A device busy in `ops` (seconds) inside a window of 0..10 s that
    another host line's events span."""
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_b(%d)" % i, s * S, (e - s) * S)
                for i, (s, e) in enumerate(ops)]},
            {"name": "XLA Ops", "events": [
                ("fusion.%d" % i, s * S, (e - s) * S)
                for i, (s, e) in enumerate(ops)]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "loop", "events": [("epoll", 0.0, 10.0 * S)]}
        ] + [{"name": "responder", "events": ev} for ev in host_lines]},
    ]


ONE_REQUEST = [[phase(0.0, 1.0, "parse"), phase(1.0, 3.0, "plan"),
                phase(3.0, 5.0, "dispatch"), phase(5.0, 10.0, "serialize")]]
TWO_REQUESTS = ONE_REQUEST + [[phase(0.0, 6.0, "device_wait"),
                               phase(6.0, 10.0, "serialize")]]
NOBODY_AFTER_FIVE = [[phase(0.0, 1.0, "parse"), phase(1.0, 5.0, "plan")]]

# idle is 0-2, 4-7 and 8-10 s = 7 s in each case
CASES = {
    "one_request": (ONE_REQUEST, {
        "parse": 1.0, "plan": 1.0, "dispatch": 1.0, "serialize": 4.0}),
    # 1/k each: 0-1 parse/device_wait, 1-2 plan/device_wait, 4-5
    # dispatch/device_wait, 5-6 serialize/device_wait, then serialize twice
    "two_overlapping_requests": (TWO_REQUESTS, {
        "parse": 0.5, "plan": 0.5, "dispatch": 0.5, "device_wait": 2.0,
        "serialize": 3.5}),
    "a_gap_with_nobody_live": (NOBODY_AFTER_FIVE, {
        "parse": 1.0, "plan": 2.0, "no_request": 4.0}),
    "no_annotations": ([], {"no_request": 7.0}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_idle_seconds_go_to_the_phases_live_in_them(case):
    host_lines, want = CASES[case]
    out = trace_gaps.reduce_planes(planes(host_lines))
    assert out["window_s"] == pytest.approx(10.0)
    assert out["idle_s"] == pytest.approx(7.0)
    assert out["idle_share"] == pytest.approx(0.7)
    assert out["idle_by_phase_s"] == pytest.approx(want)
    assert sum(out["idle_by_phase_s"].values()) == pytest.approx(
        out["idle_s"])
    assert out["overlapping_phase_events"] == 0
    # the same window and idle share as the benchmark's reduction
    ref = trace_reduce.reduce_planes([
        {"name": p["name"], "lines": [
            {"name": ln["name"], "events": [ev[:3] for ev in ln["events"]]}
            for ln in p["lines"]]} for p in planes(host_lines)])
    assert out["idle_share"] == pytest.approx(ref["idle_share"])
    for gap in out["devices"]["/device:TPU:0"]["top_gaps"]:
        assert sum(gap["by_phase_s"].values()) == pytest.approx(
            gap["length_s"])


def test_the_longest_gaps_name_their_module_phases_and_innermost_spans():
    lines = [ONE_REQUEST[0] + [span(1.0, 9.0, "pipeline"),
                               span(4.5, 6.0, "extract")],
             TWO_REQUESTS[1] + [span(6.5, 9.5, "serialize")]]
    out = trace_gaps.reduce_planes(planes(lines), top=2)
    first, second = out["devices"]["/device:TPU:0"]["top_gaps"]
    assert (first["start_s"], first["length_s"], first["ended_by"]) == (
        pytest.approx(4.0), pytest.approx(3.0), "jit_b")
    assert first["by_phase_s"] == pytest.approx(
        {"dispatch": 0.5, "device_wait": 1.0, "serialize": 1.5})
    # innermost on each line, thread-seconds: pipeline 4-4.5 and 6-7,
    # extract 4.5-6 inside it; the other line's serialize from 6.5
    assert first["spans_s"] == pytest.approx(
        {"pipeline": 1.5, "extract": 1.5, "serialize": 0.5})
    assert list(first["spans_s"])[-1] == "serialize"     # ranked
    assert (second["length_s"], second["ended_by"]) == (
        pytest.approx(2.0), "jit_b")
    assert out["phase_s"]["serialize"] == pytest.approx(9.0)


def test_phase_cpu_seconds_and_overlaps_on_one_line_are_counted():
    line = [phase(0.0, 4.0, "plan", cpu_ms=1000.0, trace_id="t1"),
            phase(3.0, 5.0, "dispatch", cpu_ms=500.0)]    # overlaps
    out = trace_gaps.reduce_planes(planes([line]))
    assert out["phase_events"] == 2
    assert out["overlapping_phase_events"] == 1
    assert out["phase_cpu_s"] == pytest.approx(
        {"plan": 1.0, "dispatch": 0.5})


# the edges outside the handler: the parse event's queue_ms puts `queue`
# before it, the loop line's write event's resume_ms puts `resume` before
# the write
EDGED_REQUEST = [[phase(1.0, 2.0, "parse", queue_ms=1000.0),
                  phase(2.0, 3.0, "plan"), phase(3.0, 5.0, "dispatch"),
                  phase(5.0, 6.0, "serialize"), phase(6.0, 6.5, "flush")],
                 [phase(7.0, 9.0, "write", resume_ms=500.0)]]


def test_the_edges_outside_the_handler_take_their_idle_seconds():
    out = trace_gaps.reduce_planes(planes(EDGED_REQUEST))
    # idle 0-2, 4-7, 8-10: queue 0-1, parse 1-2, dispatch 4-5,
    # serialize 5-6, flush 6-6.5, resume 6.5-7, write 8-9, nobody 9-10
    assert out["idle_by_phase_s"] == pytest.approx({
        "queue": 1.0, "parse": 1.0, "dispatch": 1.0, "serialize": 1.0,
        "flush": 0.5, "resume": 0.5, "write": 1.0, "no_request": 1.0})
    assert sum(out["idle_by_phase_s"].values()) == pytest.approx(7.0)
    assert out["phase_s"]["queue"] == pytest.approx(1.0)
    assert out["phase_s"]["resume"] == pytest.approx(0.5)
    assert out["phase_s"]["write"] == pytest.approx(2.0)
    # six tsd.phase events; the edges placed from stats are not events
    assert out["phase_events"] == 6


def test_the_loops_write_events_overlap_without_counting():
    """The event loop serves many requests: two writes overlapping on
    its line are not a fault, two phases on a handler's line are."""
    loop_line = [phase(7.0, 9.0, "write", resume_ms=0.0),
                 phase(8.0, 9.5, "write", resume_ms=0.0)]
    out = trace_gaps.reduce_planes(planes(ONE_REQUEST + [loop_line]))
    assert out["overlapping_phase_events"] == 0
    # idle 8-9: both writes and serialize live, a third each; 9-9.5:
    # one write and serialize, a half each
    assert out["idle_by_phase_s"]["write"] == pytest.approx(
        2 / 3 + 0.25, rel=1e-9)


def test_a_device_with_no_op_line_is_read_from_its_modules():
    pl = planes(ONE_REQUEST)
    pl[0]["lines"] = [ln for ln in pl[0]["lines"]
                      if ln["name"] == "XLA Modules"]
    assert trace_gaps.reduce_planes(pl)["idle_s"] == pytest.approx(7.0)
    none = trace_gaps.reduce_planes(pl[1:])
    assert none["device_count"] == 0 and "idle_s" not in none


def test_the_recorded_one_chip_trace_has_no_phases_so_nobody_is_live(
        tmp_path, capsys):
    """PR 22's on-chip fixture predates the annotations: every idle
    second is no_request, and idle is what trace_reduce.py reads."""
    out_json = tmp_path / "gaps.json"
    assert trace_gaps.main([FIXTURE, "--top", "3",
                            "--json", str(out_json)]) == 0
    out = json.loads(out_json.read_text())
    ref = trace_reduce.reduce_planes(trace_reduce.load(FIXTURE))
    assert out["window_s"] == pytest.approx(ref["window_s"], rel=1e-12)
    assert out["idle_s"] == pytest.approx(
        ref["window_s"] - ref["busy_s"], rel=1e-9)
    assert out["idle_share"] == pytest.approx(ref["idle_share"], rel=1e-9)
    assert out["phase_events"] == 0
    assert out["idle_by_phase_s"] == {"no_request": pytest.approx(
        out["idle_s"])}
    gaps = out["devices"]["/device:TPU:0"]["top_gaps"]
    assert len(gaps) == 3
    assert gaps[0]["length_s"] >= gaps[1]["length_s"] >= gaps[2]["length_s"]
    assert all(g["ended_by"].startswith("jit_") or g["ended_by"]
               == "trace-end" for g in gaps)
    printed = capsys.readouterr().out
    assert "idle seconds by phase" in printed and "no_request" in printed

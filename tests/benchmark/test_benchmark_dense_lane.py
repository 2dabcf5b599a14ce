"""`dense_lane_share`: the per-layer metric that reads the program's
`tsd.query.contrib_lane{lane}` counter — a data file read by the
`counter_ratio` reader from two counter snapshots in the shape
benchmark/daemon.counters() gives them, one added `per_layer` entry, and
a traced CPU rehearsal in which the daemon's own counter feeds it (the
rehearsal fleet, like TSBS's, has no hole: 100)."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402

from benchmark import readers  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
NAME = "dense_lane_share"
DENSE = "tsd_query_contrib_lane_total{lane=dense}"
FULL = "tsd_query_contrib_lane_total{lane=full}"
OTHER = {"tsd_http_requests_total{route=api/query,status=200}": 40,
         "tsd_query_group_reduce_total{mode=sorted}": 40}


def value(before: dict, after: dict):
    spec = readers.load_layer(ROOT, NAME)
    assert spec["reader"]["kind"] == "counter_ratio"
    return readers.read(ROOT, spec, {"ctr_before": dict(OTHER, **before),
                                     "ctr_after": dict(OTHER, **after)})


@pytest.mark.parametrize("before,after,want", [
    ({DENSE: 10, FULL: 2}, {DENSE: 13, FULL: 3}, 75.0),
    ({}, {DENSE: 3, FULL: 1}, 75.0),         # born inside the window
    ({DENSE: 5}, {DENSE: 12}, 100.0),        # `full` never exported
    ({FULL: 5}, {FULL: 9}, 0.0),
], ids=["dense3_full1", "first_seen_in_window", "all_dense", "all_full"])
def test_the_share_is_dense_over_both_lanes(before, after, want):
    assert value(before, after) == pytest.approx(want)


@pytest.mark.parametrize("snap", [{}, {DENSE: 7, FULL: 1}],
                         ids=["no_such_counter", "no_dispatch_in_window"])
def test_nothing_is_reported_without_a_counted_dispatch(snap):
    """The parent commit has no such counter; a window may hold no
    grouped dispatch: None, never an exception, and the line leaves the
    metric out."""
    assert value(snap, dict(snap)) is None


@pytest.fixture(scope="module")
def index():
    return ic.load_index(REPO, "BENCHMARK.json")


@pytest.mark.parametrize("check", ic.CHECKS, ids=lambda c: c.__name__)
def test_the_live_index_with_the_new_entry_holds_to_every_rule(index,
                                                               check):
    check(index)


def test_the_entry_is_the_layer_files_and_lists_every_scan_cell(index):
    entry = index["per_layer"][-1]          # appended, nothing moved
    spec = readers.load_layer(ROOT, NAME)
    assert entry["name"] == spec["name"] == NAME
    for key in ("layer", "unit", "moves"):
        assert entry[key] == spec[key]
    assert (entry["source"], entry["better"]) == ("program_counter",
                                                  "higher")
    scan = next(m for m in index["end_to_end"]
                if m["name"] == "scan_mpts_per_s")
    assert entry["workloads"] == scan["workloads"]
    # the layer's name is the one its other metrics carry
    assert entry["layer"] in {m["layer"] for m in index["per_layer"][:-1]}


def test_a_traced_rehearsal_reads_100_from_the_daemons_counter(tmp_path):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "run.py"), "--out",
         str(tmp_path / "out"), "--workload", "heavy-replay-solo",
         "--seed", "2147483777", "--seconds", "4", "--trace", "1",
         "--rehearse", "hosts=40,hours=2"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # a rehearsal, no chip
    assert line["metrics"][NAME] == {"value": 100.0, "unit": "%"}

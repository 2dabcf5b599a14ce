"""`dense_lane_share` and `rate_shift_share`: the per-layer metrics that
read the program's lane counters, `tsd.query.contrib_lane{lane}` (PR 28)
and `tsd.query.rate_lane{lane}` (PR 33) — each a data file read by the
`counter_ratio` reader from two counter snapshots in the shape
benchmark/daemon.counters() gives them, one added `per_layer` entry found
by its name, wherever later PRs' entries put it, and a traced CPU
rehearsal in which the daemon's own counters feed both (the rehearsal
fleet, like TSBS's, has no hole: 100)."""

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
# metric -> the counter's sample of the lane it is the share of, and of
# the other lane
LANES = {
    "dense_lane_share": ("tsd_query_contrib_lane_total{lane=dense}",
                         "tsd_query_contrib_lane_total{lane=full}"),
    "rate_shift_share": ("tsd_query_rate_lane_total{lane=shift}",
                         "tsd_query_rate_lane_total{lane=scan}"),
}
SCAN_CELLS = ["heavy-replay", "heavy-replay-solo", "heavy-replay-mesh4",
              "fleet-replay-100k"]
OTHER = {"tsd_http_requests_total{route=api/query,status=200}": 40,
         "tsd_query_group_reduce_total{mode=sorted}": 40}
by_name = pytest.mark.parametrize("name", sorted(LANES))


def value(name: str, before: tuple, after: tuple):
    """The metric from (fast lane, other lane) counts at the window's
    two ends; None = the sample is not exported."""
    def snapshot(counts):
        return dict(OTHER, **{key: n for key, n in zip(LANES[name], counts)
                              if n is not None})
    spec = readers.load_layer(ROOT, name)
    assert spec["reader"]["kind"] == "counter_ratio"
    return readers.read(ROOT, spec, {"ctr_before": snapshot(before),
                                     "ctr_after": snapshot(after)})


@by_name
@pytest.mark.parametrize("before,after,want", [
    ((10, 2), (13, 3), 75.0),
    ((None, None), (3, 1), 75.0),           # born inside the window
    ((5, None), (12, None), 100.0),         # the other lane never exported
    ((None, 5), (None, 9), 0.0),
], ids=["fast3_other1", "first_seen_in_window", "all_fast", "all_other"])
def test_the_share_is_the_fast_lane_over_both_lanes(name, before, after,
                                                    want):
    assert value(name, before, after) == pytest.approx(want)


@by_name
@pytest.mark.parametrize("counts", [(None, None), (7, 1)],
                         ids=["no_such_counter", "no_dispatch_in_window"])
def test_nothing_is_reported_without_a_counted_dispatch(name, counts):
    """A program from before the counter has none; a window may hold no
    such dispatch: None, never an exception, and the line leaves the
    metric out."""
    assert value(name, counts, counts) is None


@pytest.fixture(scope="module")
def index():
    return ic.load_index(REPO, "BENCHMARK.json")


@pytest.mark.parametrize("check", ic.CHECKS, ids=lambda c: c.__name__)
def test_the_live_index_with_the_new_entry_holds_to_every_rule(index,
                                                               check):
    check(index)


@by_name
def test_the_entry_is_the_layer_files_and_lists_the_scan_cells(index,
                                                               name):
    entry = next(m for m in index["per_layer"] if m["name"] == name)
    others = [m for m in index["per_layer"] if m is not entry]
    spec = readers.load_layer(ROOT, name)
    assert entry["name"] == spec["name"] == name
    for key in ("layer", "unit", "moves"):
        assert entry[key] == spec[key]
    assert (entry["source"], entry["better"]) == ("program_counter",
                                                  "higher")
    scan = next(m for m in index["end_to_end"]
                if m["name"] == "scan_mpts_per_s")
    # the four cells that were there when the entries were made, first and
    # in the index's order; a cell a later PR adds is on the list if its
    # traced run prints the metric, and reports scan_mpts_per_s if so
    assert entry["workloads"][:len(SCAN_CELLS)] == SCAN_CELLS
    assert set(entry["workloads"]) <= set(scan["workloads"])
    # the layer's name is the one its other metrics carry
    assert entry["layer"] in {m["layer"] for m in others}


@pytest.fixture(scope="module")
def traced_line(tmp_path_factory):
    """One traced rehearsal: its profile is asked for with SIGUSR1 and
    ended with SIGUSR2 sent to the daemon's pid, which leads a session
    of its own (benchmark/daemon.py) and is signalled all the same."""
    out = tmp_path_factory.mktemp("lanes") / "out"
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "run.py"), "--out", str(out),
         "--workload", "heavy-replay-solo", "--seed", "2147483777",
         "--seconds", "4", "--trace", "1", "--rehearse", "hosts=40,hours=2"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    for marker in ("started", "stopped"):       # the profile was taken
        assert os.path.getsize(out / "trace" / marker) > 0
    return json.loads(proc.stdout.strip().splitlines()[-1])


@by_name
def test_a_traced_rehearsal_reads_100_from_the_daemons_counter(traced_line,
                                                               name):
    line = traced_line
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"      # a rehearsal, no chip
    assert line["metrics"][name] == {"value": 100.0, "unit": "%"}

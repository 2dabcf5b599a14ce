"""The ten-metric TSBS cpu-only deployment (`tsbs-cpu-4000-10m-12h`) and
its cell `tsbs10-double-groupby`: the live index with their entries holds
to every rule of form, the configuration is `tsbs-cpu-4000`'s fleet with
all ten cpu metrics and 12 h retained, its ten device-cache entries fit
the budget it sets, the cycle at full size is 15 sub-queries over 259.2M
points by the files' own arithmetic (no 4000-host fleet is generated
here), and the cell rehearses end to end on the CPU at 40 hosts and
12 h, untraced and traced, where the three metrics it brings read what
the program ran."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402

from benchmark import traffic, tsbs  # noqa: E402

CELL, CONFIG = "tsbs10-double-groupby", "tsbs-cpu-4000-10m-12h"
NEW_METRICS = ("subqueries_per_req", "subquery_ms_per_sub",
               "device_cache_builds_in_window")


@pytest.fixture(scope="module")
def index():
    return ic.load_index(REPO, "BENCHMARK.json")


@pytest.fixture(scope="module")
def config(index):
    entry = next(c for c in index["configs"] if c["name"] == CONFIG)
    with open(os.path.join(REPO, entry["file"])) as fh:
        return entry, json.load(fh)


@pytest.mark.parametrize("check", ic.CHECKS, ids=lambda c: c.__name__)
def test_the_live_index_with_the_new_entries_holds_to_every_rule(index,
                                                                 check):
    assert CELL in ic.cells_of(index)
    check(index)


def test_the_deployment_is_the_fleet_with_all_ten_metrics(index, config):
    entry, cfg = config
    assert entry["reduced"] == ["retention_hours"]
    assert cfg["hosts"] == cfg["source_scale"]["hosts"] == 4000
    assert cfg["metrics"] == cfg["source_scale"]["metrics"] == 10
    assert (cfg["chips"], cfg["retention_hours"]) == (1, 12)
    assert cfg["tsd"] == {"tsd.core.auto_create_metrics": True,
                          "tsd.query.device_cache.mb": 6144}
    # no guarantee weaker than the one-metric deployment's: word for word
    with open(os.path.join(REPO, "benchmark", "configs",
                           "tsbs-cpu-4000.json")) as fh:
        assert cfg["guarantees"] == json.load(fh)["guarantees"]
    cell = ic.find_cell([index], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)
    for name in NEW_METRICS:
        metric = next(m for m in index["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]


def test_ten_entries_fit_the_budget_the_configuration_sets(config):
    from opentsdb_tpu.ops.downsample import pad_pow2
    _, cfg = config
    points = cfg["hosts"] * cfg["retention_hours"] * 3600 // cfg["cadence_s"]
    assert points == 17_280_000
    # what storage/device_cache.py pins for one metric: 16 B a point of
    # the pow2-padded buffer
    entry = pad_pow2(points, 1024) * 16
    assert entry == 512 << 20
    budget = cfg["tsd"]["tsd.query.device_cache.mb"] << 20
    assert 10 * entry <= budget
    assert 10 * entry > 4096 << 20         # the default would not hold them


class FullSizeFleet:
    """What traffic.Generator reads of a fleet, at the configuration's
    own size, with no host generated."""
    hosts, retained = 4000, 4320
    metrics = ["cpu." + f for f in tsbs.CPU_FIELDS]
    metric = metrics[0]


def test_the_cycle_at_full_size_is_15_sub_queries_over_259M_points():
    mix = traffic.load_mix(os.path.join(REPO, "benchmark"), CELL)
    rd = mix["readers"]
    assert (rd["loop"], rd["clients"]) == ("closed", 1)
    assert "rate_per_s" not in rd
    cycle = traffic.Generator(FullSizeFleet(), rd, 4100000001).replay_list()
    assert [r["cls"] for r in sorted(cycle, key=lambda r: r["points"])] == [
        "double-groupby-5", "double-groupby-all"]
    assert sum(r["path"].count("&m=") for r in cycle) == 15
    assert sum(r["points"] for r in cycle) == 259_200_000
    for r in cycle:
        assert r["hosts"] is None           # every request: the whole fleet
        # TSBS's 12 h span is the whole retained range: one window
        assert (r["start"], r["end"]) == (tsbs.EPOCH_S,
                                          tsbs.EPOCH_S + 43200 - 1)
        assert r["metrics"] == FullSizeFleet.metrics[:len(r["metrics"])]
        assert r["points"] == len(r["metrics"]) * 17_280_000


def run_cell(tmp_path, trace: int):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--out", str(tmp_path / "out"), "--workload", CELL, "--seed",
         "2147483661", "--seconds", "4", "--trace", str(trace),
         "--rehearse", "hosts=40,hours=12"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_untraced(tmp_path):
    proc, line = run_cell(tmp_path, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_mpts_per_s", "setup_s"}
    assert "loaded %d points of 10 metrics" % (10 * 40 * 4320) in proc.stdout
    assert line["device"]["platform"] == "cpu"     # a rehearsal, no chip


def test_the_cell_rehearses_traced_and_prints_its_three_metrics(tmp_path):
    proc, line = run_cell(tmp_path, 1)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    for name in NEW_METRICS:
        assert name in metrics, name
    # one request of 5 metrics and one of 10 a cycle: 7.5, or 7.5 off by
    # the half cycle the window's edges may cut
    n = line["attempted"]
    assert abs(metrics["subqueries_per_req"]["value"] - 7.5) <= 2.5 / (n - 1)
    assert metrics["subquery_ms_per_sub"]["value"] > 0
    assert metrics["device_cache_builds_in_window"]["value"] == 0
    assert metrics["device_cache_hit_rate"]["value"] == 100
    assert metrics["compiles_in_window"]["value"] == 0
    assert line["compared"]["answers_wrong"]["value"] == 0

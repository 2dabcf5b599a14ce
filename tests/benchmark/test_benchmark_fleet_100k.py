"""The TSBS cpu-only deployment at scale 100 000 (`tsbs-cpu-100k-1h`) and
its cell `fleet-replay-100k`: the live index with their entries holds to
every rule of form, the cell rehearses end to end on the CPU at 300
hosts (the program's new counters feed the four per-layer metrics the
cell brings), and the cycle at full size is 7 requests over 90.0M points
by the files' own arithmetic — no 100 000-host fleet is generated here."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402

from benchmark import traffic  # noqa: E402

CELL, CONFIG = "fleet-replay-100k", "tsbs-cpu-100k-1h"
NEW_METRICS = ("series_k_per_req", "groups_k_per_req",
               "resolve_ms_per_kseries", "emit_ms_per_kgroup")


@pytest.fixture(scope="module")
def index():
    return ic.load_index(REPO, "BENCHMARK.json")


@pytest.mark.parametrize("check", ic.CHECKS, ids=lambda c: c.__name__)
def test_the_live_index_with_the_new_entries_holds_to_every_rule(index,
                                                                 check):
    assert CELL in ic.cells_of(index)
    check(index)


def test_the_deployment_is_the_sources_fleet_on_one_chip(index):
    entry = next(c for c in index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["metrics", "retention_hours"]
    with open(os.path.join(REPO, entry["file"])) as fh:
        cfg = json.load(fh)
    assert cfg["hosts"] == cfg["source_scale"]["hosts"] == 100000
    assert (cfg["chips"], cfg["metrics"], cfg["retention_hours"]) == (1, 1, 1)
    assert cfg["tsd"] == {"tsd.core.auto_create_metrics": True}
    # no guarantee weaker than the 4000-host deployment's: word for word
    with open(os.path.join(REPO, "benchmark", "configs",
                           "tsbs-cpu-4000.json")) as fh:
        assert cfg["guarantees"] == json.load(fh)["guarantees"]
    cell = ic.find_cell([index], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)


class FullSizeFleet:
    """What traffic.Generator reads of a fleet, at the configuration's
    own size, with no host generated."""
    hosts, retained, metric = 100000, 360, "cpu.usage_user"


def test_the_cycle_at_full_size_is_7_requests_over_90M_points():
    mix = traffic.load_mix(os.path.join(REPO, "benchmark"), CELL)
    rd = mix["readers"]
    assert (rd["loop"], rd["clients"]) == ("closed", 1)
    cycle = traffic.Generator(FullSizeFleet(), rd, 2700000001).replay_list()
    assert len(cycle) == 7
    assert sum(r["points"] for r in cycle) == 90_000_000
    by_class = {}
    for r in cycle:
        assert r["hosts"] is None           # every request: the whole fleet
        by_class.setdefault(r["cls"], []).append(r["points"])
    assert by_class == {"host-groupby-30m": [18_000_000],
                        "datacenter-p99-30m": [18_000_000] * 2,
                        "region-sum-15m": [9_000_000] * 2,
                        "region-rate-15m": [9_000_000] * 2}
    # all seven are over the streaming threshold's 8M points and the
    # host lane's 2M: they go to the device
    assert min(r["points"] for r in cycle) > 8_000_000


def run_cell(tmp_path, trace: int):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--out", str(tmp_path / "out"), "--workload", CELL, "--seed",
         "2147483659", "--seconds", "4", "--trace", str(trace),
         "--rehearse", "hosts=300"],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc, json.loads(proc.stdout.strip().splitlines()[-1])


def test_the_cell_rehearses_untraced(tmp_path):
    proc, line = run_cell(tmp_path, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_mpts_per_s", "setup_s"}
    assert "loaded %d points" % (300 * 360) in proc.stdout
    assert line["device"]["platform"] == "cpu"     # a rehearsal, no chip


def test_the_cell_rehearses_traced_and_prints_its_four_metrics(tmp_path):
    proc, line = run_cell(tmp_path, 1)
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    for name in NEW_METRICS:
        assert metrics[name]["value"] > 0, name
    # the width the cell claims is the width it ran: 300 rows a request
    assert metrics["series_k_per_req"]["value"] == pytest.approx(0.3)
    assert metrics["compiles_in_window"]["value"] == 0
    assert line["compared"]["answers_wrong"]["value"] == 0

"""The six per-layer metrics of a request's host chain (PR 37): the three
edges outside the handler (`queue_ms_per_req`, `resume_ms_per_req`,
`write_ms_per_req`: tsd_http_edge_ms_total of api/query) and three stages
inside plan and dispatch (`consult_ms_per_req`, `rewrite_ms_per_req`,
`enqueue_ms_per_req`: tsd_query_stage_ms_total), each over the api/query
requests.  The live index with their entries holds to every rule of form,
each reader is plain counter arithmetic, and a traced CPU rehearsal of a
cell of each route prints every metric listed for it."""

import json
import os
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402

from benchmark import readers  # noqa: E402

ALL = ["heavy-replay", "heavy-replay-solo", "heavy-replay-mesh4",
       "fleet-replay-100k", "heavy-cold-scan"]
NEW = {  # name: (layer, the counter sample it reads, its cells)
    "queue_ms_per_req": (
        "front end", "tsd_http_edge_ms_total{route=api/query,stage=queue}",
        ALL),
    "resume_ms_per_req": (
        "front end", "tsd_http_edge_ms_total{route=api/query,stage=resume}",
        ALL),
    "write_ms_per_req": (
        "front end", "tsd_http_edge_ms_total{route=api/query,stage=write}",
        ALL),
    "consult_ms_per_req": (
        "planner", "tsd_query_stage_ms_total{stage=consult}", ALL),
    "rewrite_ms_per_req": (
        "planner", "tsd_query_stage_ms_total{stage=rewrite}",
        ["heavy-replay", "heavy-replay-solo"]),
    "enqueue_ms_per_req": (
        "kernels, one device", "tsd_query_stage_ms_total{stage=enqueue}",
        ["fleet-replay-100k", "heavy-replay-mesh4"]),
}
REQUESTS = "tsd_http_requests_total{route=api/query,status=200}"


@pytest.fixture(scope="module")
def index():
    return ic.load_index(REPO, "BENCHMARK.json")


@pytest.mark.parametrize("check", ic.CHECKS, ids=lambda c: c.__name__)
def test_the_live_index_with_the_new_entries_holds_to_every_rule(index,
                                                                 check):
    assert set(NEW) <= {m["name"] for m in index["per_layer"]}
    check(index)


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_entry_names_its_layer_and_cells(index, name):
    layer, _sample, cells = NEW[name]
    (entry,) = [m for m in index["per_layer"] if m["name"] == name]
    assert entry == {"name": name, "unit": "ms", "better": "lower",
                     "source": "program_counter", "layer": layer,
                     "moves": "scan_mpts_per_s", "workloads": cells}


@pytest.mark.parametrize("name", sorted(NEW))
def test_each_reader_is_its_counter_over_the_query_requests(name):
    """Counter arithmetic on two hand-made scrapes; the other stages'
    and routes' samples beside it are not read."""
    _layer, sample, _cells = NEW[name]
    spec = readers.load_layer(os.path.join(REPO, "benchmark"), name)
    near = {"tsd_query_stage_ms_total{stage=extract}": 100.0,
            "tsd_query_stage_ms_total{stage=rw_pieces}": 100.0,
            "tsd_query_stage_ms_total{stage=rw_assemble}": 100.0,
            "tsd_query_stage_ms_total{stage=tail}": 100.0,
            "tsd_query_stage_ms_total{stage=fetch}": 100.0,
            "tsd_http_edge_ms_total{route=api/version,stage=queue}": 100.0,
            "tsd_http_edge_ms_total{route=api/version,stage=write}": 100.0}
    before = dict(near, **{sample: 10.0, REQUESTS: 4.0})
    after = dict({k: v * 3 for k, v in near.items()},
                 **{sample: 70.0, REQUESTS: 24.0})
    ctx = {"ctr_before": before, "ctr_after": after}
    assert readers.read(os.path.join(REPO, "benchmark"), spec, ctx) \
        == pytest.approx(3.0)
    # no query answered in the window: nothing to read
    assert readers.read(os.path.join(REPO, "benchmark"), spec, {
        "ctr_before": before, "ctr_after": before}) is None


REHEARSE = {"heavy-replay-solo": "hosts=40,hours=2",
            "fleet-replay-100k": "hosts=300"}


@pytest.mark.parametrize("cell", sorted(REHEARSE))
def test_a_traced_rehearsal_prints_every_metric_listed_for_its_cell(
        tmp_path, cell):
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--out", str(tmp_path / "out"), "--workload", cell, "--seed",
         "2147483659", "--seconds", "4", "--trace", "1",
         "--rehearse", REHEARSE[cell]],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    metrics = line["metrics"]
    for name, (_layer, _sample, cells) in NEW.items():
        if cell in cells:
            assert metrics[name]["value"] > 0, name
        else:
            assert name not in metrics, name

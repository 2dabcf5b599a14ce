"""A store of N TSBS cpu metrics and classes that ask for several of them:
what the accepted cells generate, load, ask and judge is pinned byte for
byte, a fleet of ten metrics is generated, loaded and asked as TSBS's
double-groupby-5 / -all ask it, an answer is judged per (metric, group),
and a ten-metric configuration is added by files alone."""

import hashlib
import json
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402
from test_benchmark_rehearsal import last_line, run_cell  # noqa: E402

from benchmark import loadgen, reference, run, traffic, tsbs  # noqa: E402

SEED = 2**31 + 11           # over 32 signed bits, as the driver's seeds are

# sha256 of what each accepted cell generates (tags, timestamps, values),
# asks (its replay cycle's requests), judges (the references) and loads
# (every /api/put body, in order) at a rehearsal's size, computed by the
# code of the commit before multi-metric stores existed: a cell whose
# digest moves measures something else than its ledger lines did
HEAVY_REPLAY = ("b7d05480948873abd3ac2bf38fd69d69"
                "4e0329141584d6980f575dc8f197c314")
PINNED = {
    "heavy-replay": (40, 2, HEAVY_REPLAY),
    "heavy-replay-solo": (40, 2, HEAVY_REPLAY),
    "heavy-replay-mesh4": (40, 2, HEAVY_REPLAY),
    "fleet-replay-100k": (40, 2, "ee16a197136c86bc082ef7fcd4f6e1a1"
                                 "cd62d5b38900d4d953f0ea6b4e26a083"),
    "heavy-cold-scan": (40, 12, "401cf210621831c5cf0460caf127a20b"
                                "2eac4ff5eccfa58478598648d9a28c70"),
}

DOUBLE_GROUPBY = [
    {"name": "double-groupby-5", "metrics": 5, "count": 1,
     "m": "avg:1h-avg:$metric{hostname=*}", "span_s": 43200,
     "group_by": "hostname", "interval_s": 3600, "ds_fn": "avg",
     "agg": "avg"},
    {"name": "double-groupby-all", "metrics": 10, "count": 1,
     "m": "avg:1h-avg:$metric{hostname=*}", "span_s": 43200,
     "group_by": "hostname", "interval_s": 3600, "ds_fn": "avg",
     "agg": "avg"}]


def _put(h, b: bytes) -> None:
    h.update(len(b).to_bytes(8, "little"))
    h.update(b)


def digest(cell: str, hosts: int, hours: int) -> str:
    c = run.Cell(os.path.join(REPO, "BENCHMARK.json"), cell)
    retained = hours * 3600 // tsbs.CADENCE_S
    fleet = tsbs.Fleet(hosts, retained, 0, SEED, c.config.get("metrics", 1))
    h = hashlib.sha256()
    _put(h, json.dumps(fleet.tags).encode())
    _put(h, fleet.ts.tobytes())
    _put(h, fleet.data.tobytes())
    refs = {}
    for req in traffic.Generator(fleet, c.mix["readers"], SEED).replay_list():
        _put(h, json.dumps(req, sort_keys=True).encode())
        if req["kind"] == "query" and req["path"] not in refs:
            want = refs[req["path"]] = reference.ref_query(fleet, req)
            for key in sorted(want):
                wts, vals = want[key]
                _put(h, repr(key).encode())
                _put(h, wts.dtype.str.encode() + wts.tobytes())
                _put(h, vals.dtype.str.encode() + vals.tobytes())
    values = fleet.data.astype(np.int8)         # as run.py saves them
    tails = loadgen.tag_tails(fleet.tags)
    for f, *job in loadgen.load_jobs(fleet.hosts, fleet.retained,
                                     len(fleet.metrics)):
        _put(h, loadgen.put_body(values[f], tails, fleet.metrics[f], *job))
    return h.hexdigest()


@pytest.mark.parametrize("cell", sorted(PINNED))
def test_an_accepted_cell_generates_loads_asks_and_judges_as_before(cell):
    hosts, hours, want = PINNED[cell]
    assert cell in ic.cells_of(ic.load_index(REPO, "BENCHMARK.json"))
    assert digest(cell, hosts, hours) == want


def test_a_fleet_of_n_metrics_holds_the_first_n_fields_in_tsbs_order():
    one, ten = tsbs.Fleet(12, 90, 30, 7), tsbs.Fleet(12, 90, 30, 7, 10)
    assert ten.metrics == ["cpu." + f for f in tsbs.CPU_FIELDS]
    assert one.metrics == [one.metric] == ["cpu.usage_user"]
    assert ten.data.shape == (10, 12, 120) and one.data.shape == (1, 12, 120)
    # field f's walk is the same whatever the store holds beside it, and
    # the first field is the one-metric store's, bit for bit
    for f in range(10):
        assert np.array_equal(ten.data[f], tsbs.make_values(12, 120, 7, f))
    assert np.array_equal(ten.values, one.values)
    assert ten.values.base is ten.data and ten.tags == one.tags
    assert tsbs.Fleet(12, 90, 0, 7, 3).metrics == ten.metrics[:3]
    for bad in (0, 11):
        with pytest.raises(ValueError):
            tsbs.Fleet(12, 90, 0, 7, bad)


def test_the_load_of_ten_metrics_writes_every_column_of_each_once():
    fleet = tsbs.Fleet(120, 1500, 0, 3, 10)
    jobs = loadgen.load_jobs(fleet.hosts, fleet.retained, 10)
    seen = np.zeros(fleet.data.shape, np.int64)
    for f, h0, h1, c0, c1 in jobs:
        assert h1 - h0 <= 50 and c1 - c0 <= 720
        seen[f, h0:h1, c0:c1] += 1
    assert (seen == 1).all()
    # one metric after another, and the first metric's bodies in the
    # one-metric store's order
    assert [j[0] for j in jobs] == sorted(j[0] for j in jobs)
    assert jobs[:len(jobs) // 10] == loadgen.load_jobs(120, 1500, 1)
    tails = loadgen.tag_tails(fleet.tags)
    values = fleet.data.astype(np.int8)
    body = json.loads(loadgen.put_body(values[4], tails, fleet.metrics[4],
                                       50, 52, 720, 723))
    assert [(p["metric"], p["tags"]["hostname"], p["timestamp"], p["value"])
            for p in body] == [
        ("cpu.usage_iowait", "host_%d" % h, int(fleet.ts[c]),
         int(fleet.data[4, h, c])) for h in (50, 51) for c in (720, 721, 722)]


def test_a_class_of_n_metrics_asks_for_each_and_counts_its_points():
    fleet = tsbs.Fleet(40, 720, 0, 5, 10)
    gen = traffic.Generator(fleet, {"loop": "closed", "clients": 1,
                                    "classes": DOUBLE_GROUPBY}, 5)
    by_cls = {r["cls"]: r for r in gen.replay_list()}
    for cls in DOUBLE_GROUPBY:
        req = by_cls[cls["name"]]
        n = cls["metrics"]
        assert req["metrics"] == fleet.metrics[:n]
        query = req["path"].split("?", 1)[1].split("&")
        subs = [q[2:] for q in query if q.startswith("m=")]
        assert subs == ["avg%3A1h-avg%3A" + name + "%7Bhostname%3D%2A%7D"
                        for name in fleet.metrics[:n]]
        # the span is cut to the retained 2 h, as every class's is
        assert req["end"] - req["start"] + 1 == 7200
        assert req["points"] == n * 40 * 720
        want = reference.ref_query(fleet, req)
        assert set(want) == {(name, "host_%d" % h)
                             for name in fleet.metrics[:n] for h in range(40)}
        for (name, host), (wts, vals) in want.items():
            row = fleet.data[fleet.metrics.index(name), fleet.index[host]]
            assert np.allclose(vals, row.reshape(2, 360).mean(axis=1))
    # a class of one metric asks as before, whatever the store holds
    one = dict(DOUBLE_GROUPBY[0], metrics=1)
    req = traffic.Generator(fleet, {"classes": [one]}, 5).instance(
        one, np.random.default_rng(0))
    assert "metrics" not in req and req["path"].count("&m=") == 1
    assert "cpu.usage_user" in req["path"]
    with pytest.raises(ValueError):
        traffic.Generator(tsbs.Fleet(40, 720, 0, 5, 4),
                          {"classes": DOUBLE_GROUPBY}, 5).replay_list()


def _answer(want: dict, group_by: str) -> list:
    """What the daemon would send for a reference answer of several
    metrics: one result per (metric, group)."""
    return [{"metric": name, "tags": {group_by: group},
             "aggregateTags": [],
             "dps": {str(t): float(v) for t, v in zip(wts, vals)}}
            for (name, group), (wts, vals) in want.items()]


def test_two_metrics_series_swapped_for_one_host_is_a_difference():
    fleet = tsbs.Fleet(40, 720, 0, 9, 10)
    gen = traffic.Generator(fleet, {"classes": DOUBLE_GROUPBY}, 9)
    req = gen.instance(DOUBLE_GROUPBY[0], np.random.default_rng(1))
    want = reference.ref_query(fleet, req)
    payload = _answer(want, "hostname")
    got = reference.parse_answer(payload, "hostname", by_metric=True)
    assert reference.compare(got, want) is None
    # the same answer with two metrics' series swapped for host_7
    a = next(r for r in payload if (r["metric"], r["tags"]["hostname"])
             == ("cpu.usage_user", "host_7"))
    b = next(r for r in payload if (r["metric"], r["tags"]["hostname"])
             == ("cpu.usage_system", "host_7"))
    assert a["dps"] != b["dps"]
    a["dps"], b["dps"] = b["dps"], a["dps"]
    why = reference.compare(reference.parse_answer(payload, "hostname",
                                                   by_metric=True), want)
    assert why is not None and "host_7" in why and "reference" in why
    # keyed by the group alone, two metrics' series of one host collide
    assert len(reference.parse_answer(payload, "hostname")) == 40


def test_a_ten_metric_configuration_is_added_by_files_alone(tmp_path):
    """The ten-metric fleet of TSBS cpu-only with its own multi-metric
    queries: one configs/, one traffic/ and one workloads/ file plus
    entries in an index elsewhere.  The run generates, loads (every
    metric acked and counted) and asks ten metrics, and judges both
    classes per (metric, group)."""
    root = tmp_path / "benchmark"
    ic.copy_data_files(str(tmp_path), indexes=False)
    with open(root / "configs" / "tsbs-cpu-4000.json") as fh:
        cfg = json.load(fh)
    source = ("TSBS (github.com/timescale/tsbs) DevOps cpu-only, "
              "scale=4000, 10s interval, all 10 cpu metrics")
    cfg.update(name="tsbs-cpu-4000-10m", source=source, metrics=10,
               retention_hours=12, stands_for="the ten-metric fleet")
    cfg["reduced"] = {"retention_hours": "12 h", "why": "set-up"}
    (root / "configs" / "tsbs-cpu-4000-10m.json").write_text(json.dumps(cfg))
    (root / "traffic" / "double-groupby.json").write_text(json.dumps({
        "readers": {"loop": "closed", "clients": 1,
                    "classes": DOUBLE_GROUPBY},
        "warmup": {"min_sends": 2, "max_sends": 4},
        "metrics": {"scan_mpts_per_s": {"stat": "points_rate"}},
        "trace_sample": 1,
        "rehearse": {"tsd": {"tsd.query.batch.enable": False}}}))
    cell = {"name": "double-groupby-10m", "config": "tsbs-cpu-4000-10m",
            "traffic": "double-groupby", "chips": 1,
            "why": "1 client asks TSBS's double-groupby-5 and -all"}
    (root / "workloads" / "double-groupby-10m.json").write_text(
        json.dumps(cell))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        b = json.load(fh)
    b["configs"].append({"name": cfg["name"], "source": source,
                         "file": "benchmark/configs/tsbs-cpu-4000-10m.json",
                         "reduced": ["retention_hours"],
                         "why": "all ten cpu metrics of the fleet"})
    b["workloads"].append(cell)
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("scan_mpts_per_s", "compiles_in_window",
                         "dispatch_ms_per_req"):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    index = ic.load_index(str(tmp_path), "BENCHMARK.json")
    for check in ic.CHECKS:
        check(index)
    proc = run_cell(tmp_path, "--benchmark-json",
                    str(tmp_path / "BENCHMARK.json"), "--workload",
                    cell["name"], "--seed", "5", "--seconds", "4",
                    "--trace", "1", "--rehearse", "hosts=40,hours=2")
    line = last_line(proc)
    assert line["correct"] is True and line["failed"] == 0, proc.stdout[-3000:]
    assert set(line["metrics"]) == {"compiles_in_window",
                                    "dispatch_ms_per_req"}
    assert line["compared"]["answers_wrong"]["value"] == 0
    assert "loaded %d points of 10 metrics" % (10 * 40 * 720) in proc.stdout
    for cls in DOUBLE_GROUPBY:
        n = next(int(ln.split("n=")[1].split()[0])
                 for ln in proc.stdout.splitlines()
                 if ln.startswith("# class %s " % cls["name"]))
        assert n > 0

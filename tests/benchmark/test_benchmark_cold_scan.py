"""The deployment `tsbs-cpu-4000-cold` and its cell `heavy-cold-scan`: the
TSBS cpu-only fleet behind a device cache that declines the metric, six
12 h scans a cycle on the streamed fold.

The live index with their entries holds to every rule of form; the
deployment is `tsbs-cpu-4000` in fleet and guarantees, word for word; the
cycle at full size is 6 requests over 103.68M points on grids of 128
padded windows or fewer, by the files' own arithmetic (no 4000-host fleet
is generated here, and the arithmetic is what keeps a later edit from
bringing back the 1024-window grid that does not compile in a run's
time: PERF.md section 7); the six per-layer metrics the cell brings are
data files of reader kinds the harness has; and the cell rehearses on the
CPU at 40 hosts x 12 h, untraced and traced, every sampled route
`streamed`, leaving no process behind."""

import json
import os
import socket
import subprocess
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402
from test_benchmark_no_orphans import family, processes  # noqa: E402

from benchmark import readers, traffic  # noqa: E402
from opentsdb_tpu.ops.downsample import pad_pow2  # noqa: E402  (the
# program's own padding of a window count and of a chunk's length)

CELL, CONFIG, BASE = "heavy-cold-scan", "tsbs-cpu-4000-cold", "tsbs-cpu-4000"
COUNTER_METRICS = ("stream_route_share", "stream_chunks_per_req",
                   "stream_pack_ms_per_mpt", "stream_upload_B_per_pt")
TRACE_METRICS = ("stream_fold_ms_per_req", "stream_fold_roofline")
# the accepted metrics the cell reports too, and the two it must not:
# this deployment holds both caches' hit rates at 0 by construction
ALSO_LISTED = ("serialize_ms_per_req", "dispatch_ms_per_req",
               "device_wait_ms_per_req", "kernel_ms_per_req",
               "heavy_kernels_roofline", "device_idle_share", "hbm_peak_GB",
               "compiles_in_window", "plan_cpu_share", "dispatch_cpu_share",
               "host_cpu_ms_per_req", "dense_lane_share", "rate_shift_share")
NOT_LISTED = ("device_cache_hit_rate", "agg_cache_hit_rate")


@pytest.fixture(scope="module")
def index():
    return ic.load_index(REPO, "BENCHMARK.json")


def config_file(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "configs",
                           name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("check", ic.CHECKS, ids=lambda c: c.__name__)
def test_the_live_index_with_the_new_entries_holds_to_every_rule(index,
                                                                 check):
    assert CELL in ic.cells_of(index)
    check(index)


def test_the_deployment_is_tsbs_cpu_4000_behind_a_cache_that_declines(index):
    entry = next(c for c in index["configs"] if c["name"] == CONFIG)
    assert entry["reduced"] == ["metrics", "retention_hours"]
    cold, base = config_file(CONFIG), config_file(BASE)
    differs = {k for k in set(cold) | set(base) if cold.get(k) != base.get(k)}
    assert differs == {"name", "source", "tsd", "tsd_defaults_relied_on",
                       "assumed", "stands_for"}
    # fleet and guarantees: word for word
    for key in ("hosts", "cadence_s", "metrics", "retention_hours", "chips",
                "source_scale", "guarantees", "reduced", "layout"):
        assert cold[key] == base[key], key
    # the one key the deployment sets beyond the base's: under the
    # store's points, so device_batch declines the metric
    assert cold["tsd"] == {
        "tsd.core.auto_create_metrics": True,
        "tsd.query.device_cache.build_max_points": 30_000_000}
    store = cold["hosts"] * cold["retention_hours"] * 3600 // cold["cadence_s"]
    assert store == 34_560_000 > 30_000_000
    # what it adds to the base's lists, it adds at their end
    relied = cold["tsd_defaults_relied_on"]
    assert {k: relied[k] for k in base["tsd_defaults_relied_on"]} == \
        base["tsd_defaults_relied_on"]
    assert relied["tsd.query.streaming.point_threshold"] == 8_000_000
    assert relied["tsd.query.streaming.chunk_points"] == 4_000_000
    assert relied["tsd.query.streaming.state_mb"] == 6144
    assert cold["assumed"][:len(base["assumed"])] == base["assumed"]
    assert len(cold["assumed"]) > len(base["assumed"])
    cell = ic.find_cell([index], CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, CELL, 1)


def test_the_defaults_relied_on_are_the_programs_defaults():
    from opentsdb_tpu.utils.config import Config
    conf = Config({})
    relied = config_file(CONFIG)["tsd_defaults_relied_on"]
    for key in ("tsd.query.streaming.point_threshold",
                "tsd.query.streaming.chunk_points",
                "tsd.query.streaming.state_mb",
                "tsd.query.host_lane.max_points"):
        assert conf.get_int(key) == relied[key], key


class FullSizeFleet:
    """What traffic.Generator reads of a fleet, at the configuration's
    own size, with no host generated."""
    hosts, retained, metric = 4000, 8640, "cpu.usage_user"


def test_the_cycle_at_full_size_is_6_requests_over_103M_points():
    mix = traffic.load_mix(os.path.join(REPO, "benchmark"), CELL)
    rd = mix["readers"]
    assert (rd["loop"], rd["clients"]) == ("closed", 1)
    cycle = traffic.Generator(FullSizeFleet(), rd, 3500000001).replay_list()
    assert len(cycle) == 6
    assert sum(r["points"] for r in cycle) == 103_680_000
    by_class = {}
    for r in cycle:
        assert r["hosts"] is None           # every request: the whole fleet
        assert r["end"] - r["start"] + 1 == 43200
        assert r["start"] % r["interval_s"] == 0
        by_class.setdefault(r["cls"], []).append(r["points"])
    assert by_class == {"double-groupby-1": [17_280_000],
                        "host-max-12h": [17_280_000],
                        "datacenter-p99-12h": [17_280_000],
                        "region-sum-12h": [17_280_000] * 2,
                        "region-rate-12h": [17_280_000]}
    # every one is over the streaming threshold's 8M points, and the
    # store they scan is over the cache's admission limit
    assert min(r["points"] for r in cycle) > 8_000_000
    # four series of 1024-point chunks: 4320 points a series fold in five
    conf = config_file(CONFIG)["tsd_defaults_relied_on"]
    n_chunk = pad_pow2(max(1024, conf["tsd.query.streaming.chunk_points"]
                           // FullSizeFleet.hosts))
    assert n_chunk == 1024 and -(-4320 // n_chunk) == 5


def test_the_mix_is_the_one_sized_against_an_empty_compile_cache():
    """Letter for letter: each new [S, W] grid shape costs 100-125 s on
    an empty compile cache and a 1024-window grid does not compile in
    the time a run has (PERF.md section 7), so no class, span or
    interval may change, and none may pad over 128 windows."""
    mix = traffic.load_mix(os.path.join(REPO, "benchmark"), CELL)
    keys = ("name", "m", "span_s", "interval_s", "ds_fn", "agg", "rate",
            "count")
    got = [tuple(c.get(k) for k in keys) for c in mix["readers"]["classes"]]
    assert got == [
        ("double-groupby-1", "avg:1h-avg:$metric{hostname=*}", 43200, 3600,
         "avg", "avg", None, 1),
        ("host-max-12h", "max:1h-max:$metric{hostname=*}", 43200, 3600,
         "max", "max", None, 1),
        ("datacenter-p99-12h", "p99:10m-avg:$metric{datacenter=*}", 43200,
         600, "avg", "p99", None, 1),
        ("region-sum-12h", "sum:10m-avg:$metric{region=*}", 43200, 600,
         "avg", "sum", None, 2),
        ("region-rate-12h", "sum:rate:10m-avg:$metric{region=*}", 43200,
         600, "avg", "sum", True, 1)]
    for cls in mix["readers"]["classes"]:
        windows = cls["span_s"] // cls["interval_s"]
        assert pad_pow2(windows) <= 128, cls["name"]
        assert "n_hosts" not in cls and "window_pool" not in cls
    assert {pad_pow2(c["span_s"] // c["interval_s"])
            for c in mix["readers"]["classes"]} == {16, 128}
    assert mix["warmup"] == {"min_sends": 2, "max_sends": 6}
    assert (mix["trace_sample"], mix["trace_seconds"],
            mix["load_processes"]) == (1, 8, 8)
    assert mix["metrics"] == {"scan_mpts_per_s": {"stat": "points_rate"}}
    # a 40-host rehearsal streams only under these
    assert set(mix["rehearse"]["tsd"]) == {
        "tsd.query.batch.enable", "tsd.query.device_cache.build_max_points",
        "tsd.query.streaming.point_threshold",
        "tsd.query.host_lane.max_points",
        "tsd.query.streaming.chunk_points"}


def test_the_cell_is_listed_where_its_traced_run_prints_a_value(index):
    by_name = {m["name"]: m for m in index["per_layer"]}
    for name in COUNTER_METRICS + TRACE_METRICS:
        assert by_name[name]["workloads"] == [CELL], name
        assert by_name[name]["moves"] == "scan_mpts_per_s"
    for name in ALSO_LISTED:
        assert by_name[name]["workloads"][-1] == CELL, name
    for name in NOT_LISTED:
        assert CELL not in by_name[name]["workloads"], name
    scan = next(m for m in index["end_to_end"]
                if m["name"] == "scan_mpts_per_s")
    assert scan["workloads"][-1] == CELL and scan["bound"] == 0.15


def test_the_six_metrics_are_data_files_of_kinds_the_harness_has():
    root = os.path.join(REPO, "benchmark")
    for name in COUNTER_METRICS + TRACE_METRICS:
        assert not os.path.exists(os.path.join(root, "layers", name + ".py"))
        kind = readers.load_layer(root, name)["reader"]["kind"]
        assert kind == ("trace" if name in TRACE_METRICS
                        else "counter_ratio"), name


class Rec:
    """A timed request as the readers see it."""
    def __init__(self, sent, done, req, groups):
        self.sent, self.done, self.req, self.groups = sent, done, req, groups
        self.ok = True


def test_the_two_trace_metrics_read_the_folds_modules_and_no_other():
    """A CPU rehearsal leaves trace metrics out, so the two readers are
    held to a reduced trace written by hand: 2.0 s of `jit__update*` and
    1.0 s of other modules in a window that holds four whole requests."""
    root = os.path.join(REPO, "benchmark")
    req = {"kind": "query", "points": 17_280_000, "start": 0, "end": 43199,
           "interval_s": 600}
    ctx = {
        "trace_window": (10.0, 18.0),
        "records": [Rec(10.0 + 2 * i, 12.0 + 2 * i, req, 9)
                    for i in range(4)],
        "peaks": {"hbm_bytes_per_s": 819e9},
        "trace": {"device_count": 1, "idle_share": 0.5, "devices": {"0": {
            "collective_s": 0.0, "modules": {
                "jit__update_sliced(123)": [20, 1.5],
                "jit__update(7)": [10, 0.5],
                "jit__grid_tail(9)": [4, 0.6],
                "jit__finish(3)": [4, 0.4]}}}}}
    ms = readers.read(root, readers.load_layer(
        root, "stream_fold_ms_per_req"), ctx)
    assert ms == pytest.approx(2.0e3 / 4)
    share = readers.read(root, readers.load_layer(
        root, "stream_fold_roofline"), ctx)
    need = 4 * (17_280_000 * 16 + 9 * 72 * 16)
    assert share == pytest.approx(100.0 * need / 819e9 / 2.0)
    assert 0 < share < 105
    # a trace without the fold's modules (the resident cells'): nothing
    ctx["trace"]["devices"]["0"]["modules"] = {"jit__grid_tail(9)": [4, 0.6]}
    assert readers.read(root, readers.load_layer(
        root, "stream_fold_roofline"), ctx) is None


# --------------------------------------------------------------------- #
# Rehearsals                                                            #
# --------------------------------------------------------------------- #

REHEARSAL_LIMIT_S = 420.0       # ~20 s alone; wide for the six-worker run


def rehearse(tmp_path, trace: int):
    """One rehearsal in a session of its own, watched through /proc;
    returns (stdout, last line, the processes it had, its daemon's
    port)."""
    out = str(tmp_path / "out")
    with open(tmp_path / "stdout.txt", "wb") as so, \
            open(tmp_path / "stderr.txt", "wb") as se:
        proc = subprocess.Popen(
            [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
             "--out", out, "--workload", CELL, "--seed", "2147483659",
             "--seconds", "4", "--trace", str(trace), "--rehearse",
             "hosts=40,hours=12"],
            cwd=REPO, stdout=so, stderr=se, start_new_session=True)
        seen, give_up = {}, time.monotonic() + REHEARSAL_LIMIT_S
        try:
            while proc.poll() is None:
                for pid, entry in family(proc.pid, processes()).items():
                    # a process on its way out reads an empty command
                    # line before it reads as gone: keep the one it had
                    if entry[2] or pid not in seen:
                        seen[pid] = entry
                assert time.monotonic() < give_up, "rehearsal over its limit"
                time.sleep(0.2)
        finally:
            if proc.poll() is None:
                os.killpg(proc.pid, 9)
    stdout = (tmp_path / "stdout.txt").read_text()
    assert proc.returncode == 0, stdout[-3000:] + (
        tmp_path / "stderr.txt").read_text()[-3000:]
    daemon = next(cmd for _, _, cmd in seen.values()
                  if os.path.join(out, "tsd.conf") in cmd).split()
    port = int(daemon[daemon.index("--port") + 1])
    return stdout, json.loads(stdout.strip().splitlines()[-1]), seen, port


def assert_nothing_is_left(seen: dict, tmp_path, port: int) -> None:
    sessions = {sid for _, sid, _ in seen.values()}
    left = {pid: entry for pid, entry in processes().items()
            if pid != os.getpid() and (
                pid in seen or entry[1] in sessions
                or str(tmp_path) in entry[2])}
    assert not left, left
    with socket.socket() as s:
        s.settimeout(2.0)
        assert s.connect_ex(("127.0.0.1", port)) != 0
    with open(tmp_path / "out" / "daemon.log") as fh:
        assert "Server shut down" in fh.read()


def sampled_routes(stdout: str) -> dict:
    line = next(ln for ln in stdout.splitlines()
                if ln.startswith("# routes of "))
    return json.loads(line.partition(": ")[2])


def test_the_cell_rehearses_untraced_and_leaves_nothing(tmp_path):
    stdout, line, seen, port = rehearse(tmp_path, 0)
    assert line["correct"] is True and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_mpts_per_s", "setup_s"}
    assert "loaded %d points" % (40 * 4320) in stdout
    assert line["device"]["platform"] == "cpu"     # a rehearsal, no chip
    assert "counter delta tsd_query_device_cache_hits = 0" in stdout
    assert_nothing_is_left(seen, tmp_path, port)


def test_the_cell_rehearses_traced_every_route_streamed(tmp_path):
    stdout, line, seen, port = rehearse(tmp_path, 1)
    assert line["correct"] is True and line["failed"] == 0
    routes = sampled_routes(stdout)
    assert set(routes) == {"streamed"} and routes["streamed"] > 0
    metrics = line["metrics"]
    assert metrics["stream_route_share"]["value"] == 100.0
    # 4320 points a series in [40, 1024] chunks, as at full size
    assert metrics["stream_chunks_per_req"]["value"] == 5.0
    assert metrics["stream_pack_ms_per_mpt"]["value"] > 0
    # int64 + float64 + bool, and the padding of five chunks a series
    # fills four and a fifth of: 17 x 5120 / 4320
    assert metrics["stream_upload_B_per_pt"]["value"] == pytest.approx(
        17 * 5120 / 4320)
    assert metrics["compiles_in_window"]["value"] == 0
    assert metrics["dense_lane_share"]["value"] == 100.0
    assert metrics["rate_shift_share"]["value"] == 100.0
    # no device metric from a CPU: the harness leaves trace and memory
    # readers out of a rehearsal; every other metric that lists the cell
    # is on the line, and neither cache's hit rate is
    for name in TRACE_METRICS + ("kernel_ms_per_req", "hbm_peak_GB",
                                 "heavy_kernels_roofline",
                                 "device_idle_share") + NOT_LISTED:
        assert name not in metrics, name
    for name in COUNTER_METRICS + tuple(
            n for n in ALSO_LISTED if n not in (
                "kernel_ms_per_req", "heavy_kernels_roofline",
                "device_idle_share", "hbm_peak_GB")):
        assert name in metrics, name
    assert line["compared"]["answers_wrong"]["value"] == 0
    assert_nothing_is_left(seen, tmp_path, port)

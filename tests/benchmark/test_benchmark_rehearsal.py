"""CPU rehearsals of the benchmark's one command: each cell end to end
at a tiny size (`--rehearse`, platform stamped "cpu", no device metric),
the refusal to run without a TPU, and a fifth cell and a configuration
of another fleet size added by files alone.
What a rehearsal times says nothing about a chip and is asserted nowhere."""

import json
import os
import shutil
import subprocess
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import index_checks as ic  # noqa: E402
from index_checks import REPO  # noqa: E402

RUN = os.path.join(REPO, "benchmark", "run.py")
KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}
DEVICE_METRICS = {"kernel_ms_per_req", "heavy_kernels_roofline",
                  "collective_ms_per_req", "device_idle_share",
                  "hbm_peak_GB", "dev0_mem_share"}


def run_cell(tmp_path, *args, check=True):
    proc = subprocess.run(
        [sys.executable, RUN, "--out", str(tmp_path / "out"), *args],
        cwd=REPO, capture_output=True, text=True, timeout=600)
    if check:
        assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    return proc


def last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


def bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        return json.load(fh)


def index_flag(cell):
    """`--benchmark-json <index>` for a cell that waits in another index
    than BENCHMARK.json (the command's default); nothing for the rest."""
    for rel in ic.present():
        index = ic.load_index(REPO, rel)
        if cell in ic.cells_of(index):
            return ["--benchmark-json", os.path.join(REPO, rel)], index
    raise KeyError(cell)


def names(section, cell):
    index = index_flag(cell)[1]
    return {m["name"] for m in index[section] if cell in ic.listed(m, index)}


# dash-live-writes at 47 hosts: 33 840 retained points sit as far above a
# power of two as the real store's 34.56M do, so the rehearsal's backfill
# stays inside the device cache's buffer like the real one
@pytest.mark.parametrize("cell,chips,size,trace", [
    ("dash-steady", 1, "hosts=40,hours=2", 0),
    ("heavy-replay", 1, "hosts=40,hours=2", 1),
    ("heavy-replay-solo", 1, "hosts=40,hours=2", 0),
    ("dash-live-writes", 1, "hosts=47,hours=2", 0),
    ("heavy-replay-mesh4", 4, "hosts=40,hours=2", 0),
])
def test_cell_rehearses_end_to_end(tmp_path, cell, chips, size, trace):
    # each cell is rehearsed through whichever index holds it
    proc = run_cell(tmp_path, *index_flag(cell)[0], "--workload", cell,
                    "--seed", "3",
                    "--seconds", "4", "--trace", str(trace),
                    "--rehearse", size)
    line = last_line(proc)
    assert set(line) == KEYS, line
    assert line["correct"] is True, proc.stdout[-3000:]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"] == {"platform": "cpu", "kind": "cpu",
                              "count": chips, "memory_peak_bytes": None}
    got = set(line["metrics"])
    if trace:
        assert got <= names("per_layer", cell) and got
        assert not got & DEVICE_METRICS     # nothing from a CPU
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert "unaccounted_ms_per_req" in proc.stdout
    else:
        assert got == names("end_to_end", cell)
        assert line["metrics"]["setup_s"]["value"] > 0
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] is not None
    # what decided `correct`, each number beside its limit: last in the
    # line and again as the last lines of standard error
    assert list(line)[-1] == "compared"
    assert 0 < line["compared"]["answers_judged"]["value"] <= line["attempted"]
    for name, c in line["compared"].items():
        assert c["limit"] is None or c["value"] <= c["limit"], name
    assert proc.stderr.strip().splitlines()[-1].startswith("compared ")


def test_without_a_tpu_it_exits_non_zero_before_ingest(tmp_path):
    proc = run_cell(tmp_path, "--workload", "heavy-replay", "--seed", "1",
                    "--seconds", "2", "--trace", "0", check=False)
    assert proc.returncode != 0
    assert "loaded" not in proc.stdout and "{" not in proc.stdout
    assert "this cell needs 1 x tpu" in proc.stderr
    # and the daemon it had started is stopped, not left holding a device
    left = subprocess.run(["pgrep", "-f", str(tmp_path / "out" / "tsd.conf")],
                          capture_output=True, text=True)
    assert not left.stdout.strip()


def test_outside_the_repository_it_exits_non_zero(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "heavy-replay",
         "--seed", "1", "--seconds", "2", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_a_fifth_cell_is_added_by_files_alone(tmp_path):
    """One workloads/, one traffic/ and one layers/ file plus entries in
    BENCHMARK.json: no file that was there is edited, run.py picks the
    cell, its mix and its metric up by name."""
    root = tmp_path / "benchmark"
    ic.copy_data_files(str(tmp_path), indexes=False)
    b = bench()
    cell = {"name": "p99-only", "config": "tsbs-cpu-4000",
            "traffic": "p99-only", "chips": 1,
            "why": "one client asks for datacenter p99 alone"}
    (root / "workloads" / "p99-only.json").write_text(json.dumps(cell))
    (root / "traffic" / "p99-only.json").write_text(json.dumps({
        "readers": {"loop": "closed", "clients": 1, "classes": [{
            "name": "datacenter-p99-1h", "count": 1,
            "m": "p99:10m-avg:$metric{datacenter=*}", "span_s": 3600,
            "group_by": "datacenter", "interval_s": 600,
            "ds_fn": "avg", "agg": "p99"}]},
        "warmup": {"min_sends": 2, "max_sends": 4},
        "metrics": {"scan_mpts_per_s": {"stat": "points_rate"}},
        "trace_sample": 1}))
    (root / "layers" / "p99_plan_ms.json").write_text(json.dumps({
        "name": "p99_plan_ms", "layer": "planner", "unit": "ms",
        "moves": "scan_mpts_per_s",
        "reader": {"kind": "latattr", "route": "api/query",
                   "phases": ["plan"]}}))
    b["workloads"].append(cell)
    # its name goes on the list of each metric it reports: the end-to-end
    # one, and the per-layer one every cell reports
    for m in b["end_to_end"] + b["per_layer"]:
        if m["name"] in ("scan_mpts_per_s", "compiles_in_window"):
            m["workloads"].append("p99-only")
    b["per_layer"].append({
        "name": "p99_plan_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "planner",
        "moves": "scan_mpts_per_s", "workloads": ["p99-only"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    common = ["--benchmark-json", str(tmp_path / "BENCHMARK.json"),
              "--workload", "p99-only", "--seed", "1", "--seconds", "3",
              "--rehearse", "hosts=40,hours=2"]
    line = last_line(run_cell(tmp_path, *common, "--trace", "0"))
    assert line["correct"] and set(line["metrics"]) == {"scan_mpts_per_s",
                                                        "setup_s"}
    line = last_line(run_cell(tmp_path, *common, "--trace", "1"))
    assert line["correct"] and line["metrics"]["p99_plan_ms"]["value"] > 0
    assert set(line["metrics"]) == {"p99_plan_ms", "compiles_in_window"}
    index = ic.load_index(str(tmp_path), "BENCHMARK.json")
    for check in ic.CHECKS:
        check(index)


def test_a_configuration_of_another_fleet_size_is_added_by_files_alone(
        tmp_path):
    """A deployment with its own source and host count, and one cell on
    it: one configs/ and one workloads/ file plus entries.  The index
    still holds to the rules of form (a configuration is held to its own
    source's fleet, not to the first one's) and the command loads that
    fleet, not another: `--rehearse hours=2` cuts the retention alone."""
    root = tmp_path / "benchmark"
    ic.copy_data_files(str(tmp_path), indexes=False)
    with open(root / "configs" / "tsbs-cpu-4000.json") as fh:
        cfg = json.load(fh)
    source = "TSBS (github.com/timescale/tsbs) DevOps cpu-only, scale=96, 10s interval"
    cfg.update(name="tsbs-cpu-96", source=source, hosts=96,
               stands_for="a small fleet on one chip")
    cfg["source_scale"]["hosts"] = 96
    (root / "configs" / "tsbs-cpu-96.json").write_text(json.dumps(cfg))
    cell = {"name": "heavy-replay-solo-96", "config": "tsbs-cpu-96",
            "traffic": "heavy-replay-solo", "chips": 1,
            "why": "heavy-replay-solo's cycle over a fleet of 96 hosts"}
    (root / "workloads" / "heavy-replay-solo-96.json").write_text(
        json.dumps(cell))
    b = bench()
    b["configs"].append({"name": "tsbs-cpu-96", "source": source,
                         "file": "benchmark/configs/tsbs-cpu-96.json",
                         "reduced": ["metrics", "retention_hours"],
                         "why": "a fleet of another size"})
    b["workloads"].append(cell)
    for m in b["end_to_end"] + b["per_layer"]:
        if "heavy-replay-solo" in m.get("workloads", []):
            m["workloads"].append(cell["name"])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    index = ic.load_index(str(tmp_path), "BENCHMARK.json")
    for check in ic.CHECKS:
        check(index)
    proc = run_cell(tmp_path, "--benchmark-json",
                    str(tmp_path / "BENCHMARK.json"), "--workload",
                    cell["name"], "--seed", "2", "--seconds", "3",
                    "--trace", "0", "--rehearse", "hours=2")
    line = last_line(proc)
    assert line["correct"] and line["failed"] == 0
    assert set(line["metrics"]) == {"scan_mpts_per_s", "setup_s"}
    assert "loaded %d points" % (96 * 720) in proc.stdout

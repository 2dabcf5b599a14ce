"""BENCHMARK.json against the contract's rules of form, and every data
file it names: each loads, each name and unit is made of the allowed
characters, and the per-cell files agree with the index."""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
ROOT = os.path.join(REPO, "benchmark")


# the accepted index, and the index of the designed cells that are not
# entered yet (benchmark/parked-cells.json: same format, no bounds)
INDEXES = {"BENCHMARK.json": REPO,
           "benchmark/parked-cells.json": os.path.join(REPO, "benchmark")}


@pytest.fixture(scope="module", params=sorted(INDEXES))
def bench(request):
    path = os.path.join(REPO, request.param)
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as fh:
        index = json.load(fh)
    index["parked"] = "what" in index
    index.pop("what", None)
    index["base"] = INDEXES[request.param]
    return index


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(bench):
    assert set(bench) - {"base", "parked"} == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(REPO, p))
    assert len(bench["command"]) <= 32
    assert all(one_line(w) for w in bench["command"])
    script = bench["command"][1]
    assert any(script.startswith(p + "/") for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs(bench):
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert PATH.match(c["file"])
        rel = os.path.relpath(os.path.join(bench["base"], c["file"]), REPO)
        assert any(rel.startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(bench["base"], c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert key in cfg and key in cfg["reduced"], key
        # the shapes of the source are never cut
        assert cfg["hosts"] == cfg["source_scale"]["hosts"] == 4000
        assert cfg["cadence_s"] == cfg["source_scale"]["cadence_s"] == 10
        for key in ("guarantees", "assumed", "stands_for", "tsd", "chips"):
            assert key in cfg
        assert not cfg["tsd"].get("tsd.storage.directory")


def test_workloads(bench):
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells) <= max(len(cells) // 2, 1)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        with open(os.path.join(ROOT, "workloads", w["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert {k: spec[k] for k in w} == w
        with open(os.path.join(ROOT, "configs", w["config"] + ".json")) as fh:
            assert json.load(fh)["chips"] == w["chips"]
        mix = traffic.load_mix(ROOT, w["traffic"])
        assert mix["readers"]["classes"]
        for cls in mix["readers"]["classes"]:
            assert NAME.match(cls["name"])


def both_indexes():
    out = []
    for rel in sorted(INDEXES):
        with open(os.path.join(REPO, rel)) as fh:
            out.append(json.load(fh))
    return out


def test_mesh_cell_reads_heavy_replays_traffic_file_letter_for_letter():
    accepted, parked = both_indexes()
    one = {w["name"]: w for w in accepted["workloads"]}["heavy-replay"]
    mesh = {w["name"]: w for w in parked["workloads"]}["heavy-replay-mesh4"]
    assert one["traffic"] == mesh["traffic"]
    assert (one["chips"], mesh["chips"]) == (1, 4)
    assert [w["chips"] for w in accepted["workloads"]
            + parked["workloads"]].count(4) == 1


def test_a_parked_cell_is_not_an_accepted_one_and_shares_its_definitions():
    accepted, parked = both_indexes()
    assert not ({w["name"] for w in accepted["workloads"]}
                & {w["name"] for w in parked["workloads"]})
    assert parked["run_seconds"] == accepted["run_seconds"]
    for section in ("configs", "end_to_end", "per_layer"):
        known = {m["name"]: m for m in accepted[section]}
        for m in parked[section]:
            # what both name, both define alike (a config's file is
            # relative to its index; cells and bounds are each one's own)
            rest = {k: v for k, v in m.items()
                    if k not in ("workloads", "file")}
            if m["name"] in known:
                assert rest == {k: known[m["name"]][k] for k in rest}


def test_metrics(bench):
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in bench["workloads"]}
    e2e_names = {m["name"] for m in e2e}
    assert "setup_s" in e2e_names
    for m in e2e:
        # nothing parked was measured, so nothing parked has a bound
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source"} | (
            set() if bench["parked"] else {"bound"})
        assert m["source"] in ("host_clock", "device_trace")
        assert bench["parked"] or 0.01 <= m["bound"] <= 0.25
    for m in layer:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e_names and one_line(m["layer"])
        spec = readers.load_layer(ROOT, m["name"])
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) \
            == (m["name"], m["layer"], m["unit"], m["moves"])
        assert spec["reader"]["kind"] in readers.KINDS
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= cells
    where = {m["name"]: set(m.get("workloads", cells)) for m in e2e}
    for m in layer:
        # a per-layer metric is reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= where[m["moves"]], m["name"]
    for cell in cells:
        mine = [m["name"] for m in e2e if cell in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in m.get("workloads", cells) for m in layer)


def test_every_traffic_mix_declares_its_end_to_end_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        mix = traffic.load_mix(ROOT, w["traffic"])
        want = {m["name"] for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", cells)} - {"setup_s"}
        assert want <= set(mix["metrics"]), w["name"]


def test_files_under_paths_are_named_from_name_characters(bench):
    for p in bench["paths"]:
        for base, _, files in os.walk(os.path.join(REPO, p)):
            if "__pycache__" in base:
                continue
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), REPO)
                assert PATH.match(rel), rel


def test_peaks_name_their_source_and_the_v5e():
    with open(os.path.join(ROOT, "peaks.json")) as fh:
        peaks = json.load(fh)
    assert "819" in peaks["source"]
    assert peaks["by_device_kind"]["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9


def test_importing_the_harness_imports_no_jax():
    code = ("import sys; sys.path.insert(0, %r); "
            "import benchmark.run, benchmark.readers, benchmark.loadgen; "
            "assert 'jax' not in sys.modules, 'jax imported'" % REPO)
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)

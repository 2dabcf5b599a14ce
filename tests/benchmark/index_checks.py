"""The contract's rules of form for an index (BENCHMARK.json, or
benchmark/parked-cells.json: same format, a `what`, no bounds) and the
data files it names, as functions of the index: the file tests run them
on the two committed indexes, and the tests that enter a parked cell or
add a configuration run them on what they built in a temporary directory.

Nothing here names a cell, a configuration or the index a cell is in."""

import json
import os
import re
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import readers, traffic, tsbs  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
SECTIONS = ("configs", "end_to_end", "per_layer")

# the accepted index, and the index of the designed cells that are not
# entered yet; each is found relative to a repository root, and an index
# whose last cell has been entered is simply no longer there
INDEXES = {"BENCHMARK.json": "", "benchmark/parked-cells.json": "benchmark"}


def load_index(repo: str, rel: str) -> dict:
    """The index at `repo`/`rel`, with what the checks need beside it:
    `parked` (it has a `what` and no bounds), `base` (its configuration
    files are relative to it), `repo` and `root` (the data files)."""
    path = os.path.join(repo, rel)
    assert os.path.getsize(path) <= 64 * 1024
    with open(path) as fh:
        index = json.load(fh)
    index["parked"] = "what" in index
    index.pop("what", None)
    index.update(base=os.path.join(repo, INDEXES[rel]), repo=repo,
                 root=os.path.join(repo, "benchmark"))
    return index


def present(repo: str = REPO) -> list[str]:
    return [rel for rel in sorted(INDEXES)
            if os.path.exists(os.path.join(repo, rel))]


def load_all(repo: str = REPO) -> list[dict]:
    return [load_index(repo, rel) for rel in present(repo)]


def one_line(text, limit=200):
    return 1 <= len(text) <= limit and "\n" not in text and "\t" not in text


def cells_of(index: dict) -> set[str]:
    return {w["name"] for w in index["workloads"]}


def listed(metric: dict, index: dict) -> set[str]:
    """The cells a metric is reported in (no list: every cell)."""
    return set(metric.get("workloads", cells_of(index)))


def check_top_level(bench: dict) -> None:
    assert set(bench) - {"base", "parked", "repo", "root"} == {
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


def check_configs(bench: dict) -> None:
    assert 1 <= len(bench["configs"]) <= 24
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    sources = [c["source"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    # two deployments from one public benchmark name sources that differ
    assert len(set(sources)) == len(sources)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert one_line(c["source"]) and one_line(c["why"])
        assert PATH.match(c["file"])
        rel = os.path.relpath(os.path.join(bench["base"], c["file"]),
                              bench["repo"])
        assert any(rel.startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(bench["base"], c["file"])) as fh:
            cfg = json.load(fh)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert key in cfg and key in cfg["reduced"], key
        # the shapes of a configuration's OWN source are never cut: its
        # fleet is the source's, whatever size that is
        assert cfg["hosts"] == cfg["source_scale"]["hosts"]
        assert "hosts" not in c["reduced"] and "cadence_s" not in c["reduced"]
        # the cadence is the harness's, not a configuration's to choose:
        # tsbs.timestamps, the reference's column arithmetic and the
        # writers' bodies are all built on this one constant
        assert (cfg["cadence_s"] == cfg["source_scale"]["cadence_s"]
                == tsbs.CADENCE_S)
        for key in ("guarantees", "assumed", "stands_for", "tsd", "chips"):
            assert key in cfg
        # a store directory is set exactly where durability is promised
        durable = not cfg["guarantees"]["durability"].startswith("none")
        assert bool(cfg["tsd"].get("tsd.storage.directory")) == durable


def check_workloads(bench: dict) -> None:
    cells, root = bench["workloads"], bench["root"]
    assert 1 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    # at most half of an index's cells, rounded down, and always one,
    # may ask for four chips
    assert sum(w["chips"] == 4 for w in cells) <= max(len(cells) // 2, 1)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and one_line(w["why"])
        with open(os.path.join(root, "workloads", w["name"] + ".json")) as fh:
            spec = json.load(fh)
        assert {k: spec[k] for k in w} == w
        with open(os.path.join(root, "configs", w["config"] + ".json")) as fh:
            assert json.load(fh)["chips"] == w["chips"]
        mix = traffic.load_mix(root, w["traffic"])
        assert mix["readers"]["classes"]
        for cls in mix["readers"]["classes"]:
            assert NAME.match(cls["name"])


def check_metrics(bench: dict) -> None:
    e2e, layer = bench["end_to_end"], bench["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(layer) <= 128
    names = [m["name"] for m in e2e + layer]
    assert len(set(names)) == len(names)
    cells = cells_of(bench)
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
        # a per-layer metric names its cells, whether the metric it moves
        # lists its own or is every cell's (setup_s): without a list every
        # cell a later PR adds would have to report it, and enter() could
        # append an entering cell's name to nothing
        assert isinstance(m.get("workloads"), list), m["name"]
        spec = readers.load_layer(bench["root"], m["name"])
        assert (spec["name"], spec["layer"], spec["unit"], spec["moves"]) \
            == (m["name"], m["layer"], m["unit"], m["moves"])
        assert spec["reader"]["kind"] in readers.KINDS
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%" and m["source"] == "device_trace"
    for m in e2e + layer:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert listed(m, bench) and listed(m, bench) <= cells, m["name"]
    where = {m["name"]: listed(m, bench) for m in e2e}
    for m in layer:
        # a per-layer metric is reported only where the metric it moves is
        assert listed(m, bench) <= where[m["moves"]], m["name"]
    for cell in cells:
        mine = [m["name"] for m in e2e if cell in listed(m, bench)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(cell in listed(m, bench) for m in layer)


def check_traffic_metrics(bench: dict) -> None:
    """Every mix declares the statistic behind each end-to-end metric
    its cells report."""
    for w in bench["workloads"]:
        mix = traffic.load_mix(bench["root"], w["traffic"])
        want = {m["name"] for m in bench["end_to_end"]
                if w["name"] in listed(m, bench)} - {"setup_s"}
        assert want <= set(mix["metrics"]), w["name"]


CHECKS = (check_top_level, check_configs, check_workloads, check_metrics,
          check_traffic_metrics)


def check_across(indexes: list[dict]) -> None:
    """A cell is in exactly one index, and what two indexes both name
    they define alike."""
    for i, a in enumerate(indexes):
        for b in indexes[i + 1:]:
            assert not cells_of(a) & cells_of(b)
            assert a["run_seconds"] == b["run_seconds"]
            assert (a["command"], a["paths"]) == (b["command"], b["paths"])
            for section in SECTIONS:
                known = {m["name"]: m for m in a[section]}
                for m in b[section]:
                    # a config's file is relative to its index; cells and
                    # bounds are each index's own
                    rest = {k: v for k, v in m.items()
                            if k not in ("workloads", "file", "bound")}
                    if m["name"] in known:
                        assert rest == {k: known[m["name"]].get(k)
                                        for k in rest}, m["name"]


def index_of(indexes: list[dict], name: str) -> dict | None:
    """The index that holds the cell, whichever it is."""
    return next((ix for ix in indexes if name in cells_of(ix)), None)


def find_cell(indexes: list[dict], name: str) -> dict:
    """The cell's entry, from whichever index holds it."""
    return next(w for w in index_of(indexes, name)["workloads"]
                if w["name"] == name)


def copy_data_files(repo: str, indexes: bool = True) -> str:
    """The data files (and the indexes) in a repository of their own:
    what a PR that only moves or adds entries works on."""
    src = os.path.join(REPO, "benchmark")
    for sub in ("configs", "workloads", "traffic", "layers"):
        shutil.copytree(os.path.join(src, sub),
                        os.path.join(repo, "benchmark", sub))
    shutil.copy(os.path.join(src, "peaks.json"),
                os.path.join(repo, "benchmark"))
    for rel in present() if indexes else []:
        shutil.copy(os.path.join(REPO, rel), os.path.join(repo, rel))
    return repo


# --------------------------------------------------------------------- #
# Entering a parked cell: a move of entries                             #
# --------------------------------------------------------------------- #

def _rebase(cfg: dict, src: dict, dst: dict) -> dict:
    """A configuration's entry with its file relative to `dst`'s base."""
    path = os.path.join(src["base"], cfg["file"])
    return dict(cfg, file=os.path.relpath(path, dst["base"]))


def enter(src: dict, dst: dict, cell: str, bounds: dict) -> None:
    """Moves `cell` from index `src` to index `dst`, in place: its entry,
    its configuration if `dst` lacks it, its name in the `workloads` list
    of every metric that listed it — the metric's whole entry where `dst`
    has none, with `bounds[name]` for an end-to-end metric that a parked
    index never had a bound for.  `src` loses the cell and whatever only
    it used.  This is all that entering a parked cell is; what was
    measured (the bound, a `why` rewritten from the chip) is the PR's."""
    entry = next(w for w in src["workloads"] if w["name"] == cell)
    src["workloads"].remove(entry)
    dst["workloads"].append(entry)
    if entry["config"] not in {c["name"] for c in dst["configs"]}:
        dst["configs"].append(_rebase(next(
            c for c in src["configs"] if c["name"] == entry["config"]),
            src, dst))
    still_used = {w["config"] for w in src["workloads"]}
    src["configs"] = [c for c in src["configs"] if c["name"] in still_used]
    for section in ("end_to_end", "per_layer"):
        known = {m["name"]: m for m in dst[section]}
        kept = []
        for m in src[section]:
            if "workloads" not in m:        # every cell's, in both indexes
                # (an end-to-end metric alone: check_metrics gives every
                # per-layer metric a list, so a cell is never left off one)
                assert section == "end_to_end", m["name"]
                kept.append(m)
                if m["name"] not in known:
                    dst[section].append(dict(m))
                continue
            if cell in m["workloads"]:
                m["workloads"].remove(cell)
                mine = known.get(m["name"])
                if mine is None:
                    mine = dict(m, workloads=[])
                    if section == "end_to_end" and not dst["parked"]:
                        mine["bound"] = bounds[m["name"]]
                    dst[section].append(mine)
                if "workloads" in mine:
                    mine["workloads"].append(cell)
            if m["workloads"]:
                kept.append(m)
        src[section] = kept


def enter_in(repo: str, cell: str, bound: float) -> None:
    """enter() on the indexes of `repo`, written back: the index that
    empties is deleted.  `bound` stands in for what the entering PR
    measures for each end-to-end metric the cell brings."""
    indexes = {rel: load_index(repo, rel) for rel in present(repo)}
    src = index_of(list(indexes.values()), cell)
    dst = next(ix for ix in indexes.values() if not ix["parked"])
    enter(src, dst, cell, {m["name"]: bound for m in src["end_to_end"]})
    for rel, index in indexes.items():
        path = os.path.join(repo, rel)
        if not index["workloads"]:
            os.remove(path)
            continue
        out = {k: v for k, v in index.items()
               if k not in ("base", "parked", "repo", "root")}
        if index["parked"]:
            out = {"what": "cells that are designed and not entered", **out}
        with open(path, "w") as fh:
            json.dump(out, fh, indent=1)

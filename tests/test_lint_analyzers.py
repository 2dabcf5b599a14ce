"""Unit tests for the tsdblint analyzers against the fixture corpus.

Every true-positive fixture line carries an `# EXPECT: <rule>` marker;
the tests assert the analyzer fires EXACTLY those (line, rule) pairs —
a fixture violation caught by the wrong rule, a missed line, or an
extra finding all fail.  True-negative fixtures must come back empty.
All fifteen analyzers run over every fixture, so each corpus also
proves the other fourteen stay silent on it.
"""

from __future__ import annotations

import json
import os
import re
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lint.core import (  # noqa: E402
    LintContext, apply_baseline, load_baseline, run_lint, save_baseline)

FIXTURES = os.path.join(REPO, "tests", "lint_fixtures")

# the miniature schema the config fixtures are written against (the
# tsd.good.* names are fixture-only, not real CONFIG_SCHEMA keys)
FIXTURE_SCHEMA = {
    "tsd.good.flag": "bool",    # tsdblint: disable=config-unknown-key
    "tsd.good.count": "int",    # tsdblint: disable=config-unknown-key
    "tsd.good.name": "str",     # tsdblint: disable=config-unknown-key
    "tsd.good.timeout_ms": "int",   # tsdblint: disable=config-unknown-key
}

# the miniature metrics schema the metrics fixtures are written against
# (name -> (kind, labels)); tsd.fixture.* names are fixture-only
FIXTURE_METRICS = {
    "tsd.fixture.count": ("counter", ("route",)),       # tsdblint: disable=config-unknown-key
    "tsd.fixture.level": ("gauge", ()),                 # tsdblint: disable=config-unknown-key
    "tsd.fixture.latency_ms": ("histogram", ()),        # tsdblint: disable=config-unknown-key
    "tsd.fixture.pushed": ("gauge", ("kind",)),         # tsdblint: disable=config-unknown-key
    "tsd.*.errors": ("gauge", ("type",)),               # tsdblint: disable=config-unknown-key
}

_EXPECT = re.compile(r"#\s*EXPECT:\s*([a-z0-9-]+)")


def _expected(path: str) -> set[tuple[int, str]]:
    out = set()
    with open(path, encoding="utf-8") as fh:
        for i, line in enumerate(fh, start=1):
            m = _EXPECT.search(line)
            if m:
                out.add((i, m.group(1)))
    return out


def _lint_fixture(name: str) -> list:
    ctx = LintContext(REPO)
    ctx.bucket("config")["schema"] = dict(FIXTURE_SCHEMA)
    ctx.bucket("config")["compat"] = set()
    ctx.bucket("metrics")["schema"] = dict(FIXTURE_METRICS)
    # the interprocedural analyzers scope their sinks to the serving
    # layers by default; fixtures opt their own directory in
    ctx.bucket("taint")["sink_paths"] = ("tests/lint_fixtures/",)
    ctx.bucket("shape")["paths"] = ("tests/lint_fixtures/",)
    ctx.bucket("leak")["paths"] = ("tests/lint_fixtures/",)
    ctx.bucket("blocking")["paths"] = ("tests/lint_fixtures/",)
    ctx.bucket("ordering")["paths"] = ("tests/lint_fixtures/",)
    ctx.bucket("effects")["paths"] = ("tests/lint_fixtures/",)
    ctx.bucket("effects")["entry_qnames"] = (
        "tests.lint_fixtures.effects_tp.explain_entry",
        "tests.lint_fixtures.effects_tp.permit_entry")
    path = os.path.join(FIXTURES, name)
    return run_lint([path], root=REPO, ctx=ctx)


TRUE_POSITIVE = ["jax_tp.py", "lock_tp.py", "config_tp.py", "except_tp.py",
                 "shape_tp.py", "taint_tp.py", "leak_tp.py",
                 "cache_tp.py", "install_tp.py", "span_tp.py",
                 "metrics_tp.py", "flightrec_tp.py", "explain_tp.py",
                 "batcher_tp.py", "blocking_tp.py", "ordering_tp.py",
                 "effects_tp.py"]
TRUE_NEGATIVE = ["jax_tn.py", "lock_tn.py", "config_tn.py", "except_tn.py",
                 "shape_tn.py", "taint_tn.py", "leak_tn.py",
                 "cache_tn.py", "install_tn.py", "span_tn.py",
                 "metrics_tn.py", "flightrec_tn.py", "explain_tn.py",
                 "batcher_tn.py", "blocking_tn.py", "ordering_tn.py",
                 "effects_tn.py"]


@pytest.mark.parametrize("name", TRUE_POSITIVE)
def test_true_positives_each_caught_by_exactly_the_intended_rule(name):
    path = os.path.join(FIXTURES, name)
    expected = _expected(path)
    assert expected, "fixture %s declares no EXPECT markers" % name
    got = {(f.line, f.rule) for f in _lint_fixture(name)}
    missed = expected - got
    extra = got - expected
    assert not missed, "rules that failed to fire in %s: %s" % (name, missed)
    assert not extra, "unexpected findings in %s: %s" % (name, extra)


@pytest.mark.parametrize("name", TRUE_NEGATIVE)
def test_true_negatives_stay_clean(name):
    findings = _lint_fixture(name)
    assert findings == [], [f.render() for f in findings]


def test_suppression_must_sit_on_or_above_the_line(tmp_path):
    src = tmp_path / "mod.py"
    src.write_text(
        "import threading\n"
        "\n\nclass C:\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self.n = 0  # guarded-by: _lock\n"
        "    def ok(self):\n"
        "        with self._lock:\n"
        "            self.n += 1\n"
        "    def racy(self):\n"
        "        # tsdblint: disable=lock-unguarded-mutation\n"
        "        self.n += 1\n"
        "    def still_racy(self):\n"
        "        self.n += 1\n")
    findings = run_lint([str(src)], root=str(tmp_path))
    assert [(f.rule, f.line) for f in findings] == \
        [("lock-unguarded-mutation", 15)]


class TestBaseline:
    def _findings(self, name="lock_tp.py"):
        return _lint_fixture(name)

    def test_round_trip_is_byte_stable(self, tmp_path):
        findings = self._findings()
        p1 = tmp_path / "b1.json"
        p2 = tmp_path / "b2.json"
        save_baseline(findings, str(p1))
        # re-running the suite and re-saving must reproduce the file
        # byte-for-byte (stable ordering, no churn)
        save_baseline(self._findings(), str(p2))
        assert p1.read_bytes() == p2.read_bytes()
        loaded = load_baseline(str(p1))
        assert sum(loaded.values()) == len(findings)

    def test_baseline_absorbs_exactly_its_count(self, tmp_path):
        findings = self._findings()
        path = tmp_path / "b.json"
        save_baseline(findings, str(path))
        baseline = load_baseline(str(path))
        # everything grandfathered -> nothing new
        assert apply_baseline(findings, baseline) == []
        # a NEW duplicate of a baselined shape still reports
        doubled = findings + [findings[0]]
        fresh = apply_baseline(sorted(doubled), baseline)
        assert len(fresh) == 1
        assert fresh[0].fingerprint == findings[0].fingerprint

    def test_baseline_is_line_number_free(self, tmp_path):
        path = tmp_path / "b.json"
        save_baseline(self._findings(), str(path))
        payload = json.loads(path.read_text())
        assert payload["version"] == 1
        for entry in payload["findings"]:
            assert set(entry) == {"path", "rule", "message", "count"}
            assert "line" not in entry


def test_checked_in_baseline_round_trips(tmp_path):
    """The committed baseline must be exactly what save_baseline emits
    for its own contents (stable ordering, no churn on re-run)."""
    committed = os.path.join(REPO, "tools", "lint", "baseline.json")
    baseline = load_baseline(committed)
    # reconstruct findings from the baseline and re-save
    from tools.lint.core import Finding
    findings = []
    for (path, rule, message), count in baseline.items():
        findings.extend([Finding(path, 1, rule, message)] * count)
    out = tmp_path / "roundtrip.json"
    save_baseline(findings, str(out))
    with open(committed, "rb") as fh:
        assert fh.read() == out.read_bytes()


# --------------------------------------------------------------------- #
# SARIF / changed-only CLI modes                                        #
# --------------------------------------------------------------------- #

# The structural core of the SARIF 2.1.0 schema (oasis-tcs/sarif-spec):
# required top-level version+runs, tool.driver.name, per-result message
# with a physical location.  Validated with jsonschema so a malformed
# emitter fails loudly, without vendoring the 300KB full schema.
SARIF_SUBSET_SCHEMA = {
    "type": "object",
    "required": ["version", "runs"],
    "properties": {
        "version": {"const": "2.1.0"},
        "$schema": {"type": "string"},
        "runs": {
            "type": "array",
            "minItems": 1,
            "items": {
                "type": "object",
                "required": ["tool", "results"],
                "properties": {
                    "tool": {
                        "type": "object",
                        "required": ["driver"],
                        "properties": {"driver": {
                            "type": "object",
                            "required": ["name"],
                            "properties": {
                                "name": {"type": "string"},
                                "rules": {"type": "array", "items": {
                                    "type": "object",
                                    "required": ["id"],
                                }},
                            },
                        }},
                    },
                    "results": {"type": "array", "items": {
                        "type": "object",
                        "required": ["ruleId", "message", "locations"],
                        "properties": {
                            "message": {
                                "type": "object",
                                "required": ["text"],
                            },
                            "locations": {
                                "type": "array",
                                "minItems": 1,
                                "items": {
                                    "type": "object",
                                    "required": ["physicalLocation"],
                                    "properties": {"physicalLocation": {
                                        "type": "object",
                                        "required": ["artifactLocation"],
                                        "properties": {
                                            "artifactLocation": {
                                                "type": "object",
                                                "required": ["uri"],
                                            },
                                            "region": {
                                                "type": "object",
                                                "properties": {
                                                    "startLine": {
                                                        "type": "integer",
                                                        "minimum": 1,
                                                    }},
                                            },
                                        },
                                    }},
                                },
                            },
                        },
                    }},
                },
            },
        },
    },
}


def test_sarif_output_validates_against_sarif_2_1_0():
    import jsonschema
    from tools.lint.core import get_analyzers
    from tools.lint.sarif import to_sarif
    findings = _lint_fixture("taint_tp.py")
    assert findings, "fixture findings expected for a non-trivial run"
    doc = to_sarif(findings, get_analyzers())
    jsonschema.validate(doc, SARIF_SUBSET_SCHEMA)
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "tsdblint"
    assert len(run["results"]) == len(findings)
    rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
    assert {r["ruleId"] for r in run["results"]} <= rule_ids
    # every location points at the fixture with a real line
    for res in run["results"]:
        loc = res["locations"][0]["physicalLocation"]
        assert loc["artifactLocation"]["uri"].endswith("taint_tp.py")
        assert loc["region"]["startLine"] >= 1


def test_sarif_cli_mode_emits_valid_empty_run():
    import json
    import subprocess
    import jsonschema
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "lint", "run.py"),
         "--sarif"],
        capture_output=True, text=True, cwd=REPO, timeout=300)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    jsonschema.validate(doc, SARIF_SUBSET_SCHEMA)
    assert doc["runs"][0]["results"] == []
    # rule metadata ships even on a clean run, so dashboards can show
    # what was checked
    assert len(doc["runs"][0]["tool"]["driver"]["rules"]) >= 18


def test_changed_only_filters_to_git_changed_files(monkeypatch, capsys):
    # jax_tp.py fires without any fixture scope injection, so it works
    # through the real CLI entry point
    from tools.lint import run as run_mod
    fixture = os.path.join("tests", "lint_fixtures", "jax_tp.py")
    # nothing changed -> nothing reported, even with raw findings
    monkeypatch.setattr(run_mod, "_changed_files", lambda: set())
    rc = run_mod.main(["--changed-only", "--no-baseline", fixture])
    assert rc == 0
    assert "clean" in capsys.readouterr().out
    # the fixture marked changed -> its findings come back
    monkeypatch.setattr(run_mod, "_changed_files",
                        lambda: {fixture.replace(os.sep, "/")})
    rc = run_mod.main(["--changed-only", "--no-baseline", fixture])
    assert rc == 1
    assert "jax-host-sync" in capsys.readouterr().out


# --------------------------------------------------------------------- #
# Acceptance pins for the v2 analyzers                                  #
# --------------------------------------------------------------------- #

def test_removing_the_budget_charge_fails_the_tree(tmp_path):
    """The taint analyzer's load-bearing check: query/planner.py's
    `budget.charge(points)` is THE sanitizer between request-sized
    window plans and the allocations they size.  Deleting it must turn
    the whole serving surface (handle_query, gexp, exp, graph) into
    findings — if this test fails, the analyzer has gone blind to the
    exact regression it exists to catch."""
    import shutil
    from tools.lint import taint
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    planner = dst / "query" / "planner.py"
    src = planner.read_text()
    assert "budget.charge(points)" in src
    planner.write_text(src.replace("budget.charge(points)",
                                   "pass  # charge removed", 1))
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[taint.ANALYZER], ctx=ctx)
    rules = {f.rule for f in findings}
    assert "taint-unsanitized-alloc" in rules, (
        "charge() removal went undetected")
    paths = {f.path for f in findings}
    assert "opentsdb_tpu/tsd/rpcs.py" in paths, (
        "the main /api/query route should be among the flagged entry "
        "points, got: %s" % sorted(paths))


def test_shape_contracts_catch_reintroduced_narrowing(tmp_path):
    """Un-clipping the pre-compacted re-base in ops/downsample.py
    (_window_ids_fast) must re-fire shape-dtype-narrowing — the int64
    ms-delta wrap this PR fixed stays caught."""
    import shutil
    from tools.lint import shape_dtype
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    ds = dst / "ops" / "downsample.py"
    src = ds.read_text()
    clipped = ("shift = jnp.clip(wargs[\"first\"] - wargs[\"ts_base\"],\n"
               "                             -_I32_BIG, _I32_BIG)"
               ".astype(jnp.int32)")
    assert clipped in src, "expected the clipped re-base from this PR"
    src = src.replace(
        clipped,
        "shift = (wargs[\"first\"] - wargs[\"ts_base\"])"
        ".astype(jnp.int32)")
    ds.write_text(src)
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[shape_dtype.ANALYZER], ctx=ctx)
    assert any(f.rule == "shape-dtype-narrowing"
               and f.path == "opentsdb_tpu/ops/downsample.py"
               for f in findings), [f.render() for f in findings]


def test_removing_the_cache_drop_fails_the_tree(tmp_path):
    """The cache_coherence analyzer's load-bearing checks, pinned on two
    contracts the served path lives by:

    (a) deleting the wholesale block drop inside `AggCache.invalidate` —
        THE registered invalidator of the partial-aggregate cache, which
        /api/dropcaches and every mutation notice route through — must
        fire cache-invalidator-gutted there;
    (b) deleting the log-buffer uninstall inside `TSDServer.stop` must
        re-fire the paired-install rule at the annotated install site.

    If this test fails, the analyzer has gone blind to the regression it
    exists to catch."""
    import shutil
    from tools.lint import cache_coherence

    # (a) gut AggCache.invalidate's drop of its backing store
    dst = tmp_path / "a" / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    ac = dst / "storage" / "agg_cache.py"
    src = ac.read_text()
    needle = ("                self._blocks = {}\n"
              "                self._family_index.clear()\n")
    assert src.count(needle) == 1, \
        "expected exactly one wholesale drop inside AggCache.invalidate"
    ac.write_text(src.replace(
        needle, "                self._family_index.clear()\n"))
    ctx = LintContext(str(tmp_path / "a"))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path / "a"),
                        analyzers=[cache_coherence.ANALYZER], ctx=ctx)
    assert any(f.rule == "cache-invalidator-gutted"
               and f.path == "opentsdb_tpu/storage/agg_cache.py"
               and "agg-blocks" in f.message
               for f in findings), (
        "gutting AggCache.invalidate went undetected:\n"
        + "\n".join(f.render() for f in findings))

    # (b) gut TSDServer.stop's log-buffer uninstall
    dst = tmp_path / "b" / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    srv = dst / "tsd" / "server.py"
    src = srv.read_text()
    needle = ("                from opentsdb_tpu.tsd.admin_rpcs import "
              "uninstall_log_buffer\n"
              "                uninstall_log_buffer()\n")
    assert src.count(needle) == 1
    srv.write_text(src.replace(needle, ""))
    ctx = LintContext(str(tmp_path / "b"))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path / "b"),
                        analyzers=[cache_coherence.ANALYZER], ctx=ctx)
    assert any(f.rule == "install-missing-uninstall"
               and f.path == "opentsdb_tpu/tsd/server.py"
               for f in findings), (
        "gutting stop's uninstall_log_buffer went undetected:\n"
        + "\n".join(f.render() for f in findings))


def test_gutting_the_rollup_lane_invalidator_fails_the_tree(tmp_path):
    """A lane block served after its points were rewritten is a wrong
    answer, not a slow one: deleting the wholesale drop inside
    `RollupLanes.invalidate` must fire cache-invalidator-gutted at the
    registered invalidator of `rollup-lanes`."""
    import shutil
    from tools.lint import cache_coherence
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    ru = dst / "storage" / "rollup.py"
    src = ru.read_text()
    needle = ("                self._blocks = {}\n"
              "                self._marks.clear()\n")
    assert src.count(needle) == 1, \
        "expected the wholesale drop inside RollupLanes.invalidate"
    ru.write_text(src.replace(needle,
                              "                self._marks.clear()\n"))
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[cache_coherence.ANALYZER], ctx=ctx)
    hits = [f for f in findings if f.rule == "cache-invalidator-gutted"
            and f.path == "opentsdb_tpu/storage/rollup.py"
            and "rollup-lanes" in f.message]
    assert hits, ("RollupLanes.invalidate without its drop went "
                  "undetected:\n" + "\n".join(f.render()
                                              for f in findings))


def test_removing_the_deadline_clamp_fails_the_tree(tmp_path):
    """The deadline_discipline analyzer's load-bearing checks, pinned on
    the two routes this PR bounded:

    (a) deleting the remainder clamp in cluster._fetch_peer — THE line
        that keeps a fan-out peer fetch inside the coordinator's
        deadline — must turn the urlopen below it into a
        blocking-unbounded finding;
    (b) stripping the `timeout=self._request_timeout_s()` kwarg from
        replication's urlopen calls must flag the ack-path ship
        (on_committed -> _ship) the same way.

    If this test fails, the analyzer has gone blind to the exact
    regression it exists to catch."""
    import shutil
    from tools.lint import blocking

    # (a) gut the peer-fetch clamp
    dst = tmp_path / "a" / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    cl = dst / "tsd" / "cluster.py"
    src = cl.read_text()
    needle = ("            timeout_s = min(timeout_s, "
              "max(remaining / 1e3, 0.05))\n")
    assert src.count(needle) == 1, \
        "expected exactly one remainder clamp in _fetch_peer"
    cl.write_text(src.replace(needle, ""))
    ctx = LintContext(str(tmp_path / "a"))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path / "a"),
                        analyzers=[blocking.DEADLINE_ANALYZER], ctx=ctx)
    hits = [f for f in findings if f.rule == "blocking-unbounded"
            and f.path == "opentsdb_tpu/tsd/cluster.py"
            and "_fetch_peer" in f.message]
    assert hits, ("un-clamping the peer fetch went undetected:\n"
                  + "\n".join(f.render() for f in findings))

    # (b) strip the replication request-timeout kwarg
    dst = tmp_path / "b" / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    rp = dst / "tsd" / "replication.py"
    src = rp.read_text()
    needle = ", timeout=self._request_timeout_s()"
    assert src.count(needle) >= 4, \
        "every replication urlopen should clamp through the helper"
    rp.write_text(src.replace(needle, ""))
    ctx = LintContext(str(tmp_path / "b"))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path / "b"),
                        analyzers=[blocking.DEADLINE_ANALYZER], ctx=ctx)
    ship = [f for f in findings if f.rule == "blocking-unbounded"
            and f.path == "opentsdb_tpu/tsd/replication.py"
            and "_ship" in f.message]
    assert ship, ("un-bounding the ack-path ship went undetected:\n"
                  + "\n".join(f.render() for f in findings))
    assert any("on_committed" in f.message for f in ship), (
        "the ship should be attributed to the on_committed ack route:\n"
        + "\n".join(f.render() for f in ship))


def test_swapping_write_and_mark_fails_the_tree(tmp_path):
    """The order_contract analyzer's load-bearing check, pinned on the
    PR 9 bug class: memstore.add_point must append the point BEFORE
    publishing the mutation mark — swapped, cache readers chase the
    mark, re-read, and serve the previous contents as fresh.  If this
    test fails, the analyzer has gone blind to the exact regression it
    exists to catch."""
    import shutil
    from tools.lint import ordering
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    ms = dst / "storage" / "memstore.py"
    src = ms.read_text()
    write_line = ("        series.append(ts_ms, value, is_int)"
                  "          # order-event: memstore-write\n")
    mark_line = ("        self.notify_mutation(key.metric, ts_ms, ts_ms)"
                 "  # order-event: memstore-mark\n")
    needle = write_line + mark_line
    assert src.count(needle) == 1, \
        "expected the tagged write/mark pair in add_point"
    ms.write_text(src.replace(needle, mark_line + write_line))
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[ordering.ORDER_ANALYZER], ctx=ctx)
    hits = [f for f in findings if f.rule == "order-violation"
            and f.path == "opentsdb_tpu/storage/memstore.py"
            and "memstore-write" in f.message]
    assert hits, ("swapping write and mark went undetected:\n"
                  + "\n".join(f.render() for f in findings))


def test_moving_ship_after_ack_fails_the_tree(tmp_path):
    """The PR 15 durability invariant as a checked contract: the bulk
    put route must ship to replicas (and journal) BEFORE acking the
    client — responding first un-does replicated sharded serving's
    no-ack-before-ship guarantee.  The reorder is transitive (neither
    moved line carries a tag; the events arrive through ingest_points
    and _respond_put), so this also pins the call-graph emission."""
    import shutil
    from tools.lint import ordering
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    rp = dst / "tsd" / "rpcs.py"
    src = rp.read_text()
    ingest_line = ("        success, errors = "
                   "self.ingest_points(tsdb, dps)\n")
    mark_line = '        latattr.mark("dispatch")\n'
    ack_line = ("        self._respond_put(tsdb, query, success, "
                "errors, lambda i: dps[i])\n")
    needle = ingest_line + mark_line + ack_line
    assert src.count(needle) == 1, \
        "expected the ingest-then-ack pair in process_data_points"
    rp.write_text(src.replace(
        needle,
        ack_line.replace("success, errors,", "[], [],")
        + ingest_line + mark_line))
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[ordering.ORDER_ANALYZER], ctx=ctx)
    hits = [f for f in findings if f.rule == "order-violation"
            and f.path == "opentsdb_tpu/tsd/rpcs.py"
            and "replica-ship" in f.message]
    assert hits, ("acking before the ship went undetected:\n"
                  + "\n".join(f.render() for f in findings))


def test_moving_demand_observation_out_of_the_gate_fails_the_tree(
        tmp_path):
    """The effect_contract analyzer's load-bearing check, pinned on the
    exact regression the observe gate exists for: RollupLanes.plan
    declares `# effects: observe-gated(observe)`, so forcing its
    demand/planned-gen accounting arm unconditional (a dry-run explain
    consult would then perturb real lane demand) must re-fire
    effect-observe-leak.  If this test fails, the analyzer has gone
    blind to the regression it exists to catch."""
    import shutil
    from tools.lint import effects
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    ru = dst / "storage" / "rollup.py"
    src = ru.read_text()
    needle = "            gen0 = self._gen\n            if observe:\n"
    assert src.count(needle) == 1, \
        "expected the gated accounting arm in RollupLanes.plan"
    ru.write_text(src.replace(
        needle, "            gen0 = self._gen\n            if True:\n"))
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[effects.EFFECT_ANALYZER], ctx=ctx)
    hits = [f for f in findings if f.rule == "effect-observe-leak"
            and f.path == "opentsdb_tpu/storage/rollup.py"
            and "RollupLanes.plan" in f.message]
    assert hits, ("un-gating the demand observation went undetected:\n"
                  + "\n".join(f.render() for f in findings))


def test_injected_dispatch_under_handle_explain_fails_the_tree(
        tmp_path):
    """The dispatch_purity analyzer's load-bearing check: a `jnp` call
    injected ANYWHERE under the /api/query/explain entry — here
    directly in handle_explain, a function nobody annotated — must
    re-fire dispatch-reachable.  The contracts guard the annotated
    consult arms; this reachability walk is what makes the whole
    subtree dispatch-free by construction."""
    import shutil
    from tools.lint import effects
    dst = tmp_path / "opentsdb_tpu"
    shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
    rp = dst / "tsd" / "rpcs.py"
    src = rp.read_text()
    needle = ("        ts_query.validate()\n"
              '        latattr.mark("parse")\n'
              "        try:\n"
              "            what_if = "
              "explain_mod.parse_what_if(raw_what_if)\n")
    assert src.count(needle) == 1, \
        "expected the validate-then-parse sequence in handle_explain"
    rp.write_text(src.replace(
        needle,
        "        ts_query.validate()\n"
        '        latattr.mark("parse")\n'
        "        jnp.zeros((1,))\n"
        "        try:\n"
        "            what_if = "
        "explain_mod.parse_what_if(raw_what_if)\n"))
    ctx = LintContext(str(tmp_path))
    findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                        analyzers=[effects.PURITY_ANALYZER], ctx=ctx)
    hits = [f for f in findings if f.rule == "dispatch-reachable"
            and f.path == "opentsdb_tpu/tsd/rpcs.py"]
    assert hits, ("an injected dispatch under handle_explain went "
                  "undetected:\n"
                  + "\n".join(f.render() for f in findings))
    assert any("handle_explain" in f.message for f in hits), (
        "the finding should name the explain entry:\n"
        + "\n".join(f.render() for f in hits))


def test_only_flag_restricts_the_run_to_the_named_analyzers(capsys):
    from tools.lint import run as run_mod
    fixture = os.path.join("tests", "lint_fixtures", "jax_tp.py")
    # a disjoint analyzer pair over the jax fixture: clean
    rc = run_mod.main(["--only", "effect_contract,dispatch_purity",
                       "--no-baseline", fixture])
    assert rc == 0
    assert "clean" in capsys.readouterr().out
    # the fixture's own analyzer named: findings come back, and
    # --timings composes
    rc = run_mod.main(["--only", "jax_hygiene", "--timings",
                       "--no-baseline", fixture])
    out = capsys.readouterr().out
    assert rc == 1
    assert "jax-host-sync" in out
    assert "jax_hygiene" in out          # the per-analyzer split
    assert "lock_discipline" not in out  # nothing else ran
    # unknown names are a usage error, not a silent no-op
    rc = run_mod.main(["--only", "nope", "--no-baseline", fixture])
    assert rc == 2


def test_full_tree_lint_stays_under_the_tier1_budget():
    """All fifteen analyzers over the package in under 30s — the bound
    that keeps tsdblint viable inside tier-1 (and the pre-commit hook
    tolerable).  The interprocedural fixpoints dominate; if this starts
    failing, parallelize the per-file check phase before relaxing the
    bound."""
    import time
    start = time.monotonic()
    run_lint(["opentsdb_tpu"], root=REPO)
    elapsed = time.monotonic() - start
    assert elapsed < 30.0, "full-tree lint took %.1fs" % elapsed


def test_dead_key_fires_despite_own_declaration_literal(tmp_path):
    """A schema key's own declaration literal in utils/config.py must
    not count as a read — otherwise config-dead-key could never fire."""
    pkg = tmp_path / "utils"
    pkg.mkdir()
    cfg = pkg / "config.py"
    cfg.write_text(
        'SCHEMA = {\n'
        '    "tsd.good.flag": None,\n'
        '    "tsd.good.count": None,\n'
        '    "tsd.good.name": None,\n'
        '}\n')
    reader = tmp_path / "reader.py"
    reader.write_text(
        'def read(config):\n'
        '    config.get_int("tsd.good.timeout_ms")\n'
        '    return config.get_bool("tsd.good.flag")\n')
    ctx = LintContext(str(tmp_path))
    ctx.bucket("config")["schema"] = dict(FIXTURE_SCHEMA)
    ctx.bucket("config")["compat"] = {"tsd.good.name"}
    findings = run_lint([str(cfg), str(reader)], root=str(tmp_path),
                        ctx=ctx)
    dead = {f.message.split("'")[1] for f in findings
            if f.rule == "config-dead-key"}
    # flag is read, name is compat -> only count is dead
    assert dead == {"tsd.good.count"}

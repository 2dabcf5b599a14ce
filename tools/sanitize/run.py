#!/usr/bin/env python3
"""tsdbsan CLI — one-shot sanitized runs + static<->dynamic cross-check.

    python tools/sanitize/run.py --subset tier1       # sanitized subset
    python tools/sanitize/run.py --subset tier1 --sarif out.sarif
    python tools/sanitize/run.py --cross-check state.json
    python tools/sanitize/run.py --subset tier1 --strict-tests

`--subset tier1` runs the sanitized tier-1 subset (the concurrency-
bearing test files) under `TSDBSAN=1` in a child pytest, collects the
findings report + the observed lock-order graph, then cross-checks the
observed graph against lock_discipline's static one.  Exit status:

    0  zero error-level sanitizer findings (cross-check notes —
       san-stale-static-edge / san-lint-gap / san-blocked-past-deadline
       — and pre-existing test failures do not fail the run)
    1  error-level findings (races / inversions / deadlocks / ...)
    2  usage or harness error

Pass `--strict-tests` to ALSO fail on child test failures (CI that has
a green baseline wants this; containers with known-failing mesh tests
do not).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# The sanitized tier-1 subset: every test file that exercises the
# threaded serving stack.  test_parallel.py rides along for the mesh
# kernels where the environment can import them (collection errors are
# tolerated exactly like tier-1's --continue-on-collection-errors).
SUBSET_TIER1 = [
    "tests/test_concurrency.py",
    "tests/test_cluster_serving.py",
    "tests/test_admission.py",
    "tests/test_batcher.py",
    "tests/test_flightrec.py",
    "tests/test_explain.py",
    "tests/test_agg_cache.py",
    "tests/test_rollup_lanes.py",
    "tests/test_tsd_server.py",
    "tests/test_replication.py",
    "tests/test_parallel.py",
    "tests/test_native_engine.py",
    "tests/test_sanitizer.py",
    "tests/test_sanitizer_steady.py",
]


def run_subset(subset: list[str], sarif: str | None, report: str | None,
               strict_tests: bool) -> int:
    tmpdir = tempfile.mkdtemp(prefix="tsdbsan_")
    # the gate always reads its own JSON artifact; a user --report is
    # written separately afterwards (so --report foo.sarif cannot blind
    # the gate to its own findings)
    report_path = os.path.join(tmpdir, "findings.json")
    state_path = os.path.join(tmpdir, "observed.json")
    env = dict(os.environ)
    env.update({
        "TSDBSAN": "1",
        "TSDBSAN_REPORT": report_path,
        "TSDBSAN_STATE": state_path,
        "JAX_PLATFORMS": "cpu",     # the sanitized subset is a CPU run
        "PYTHONPATH": _REPO + os.pathsep + env.get("PYTHONPATH", ""),
    })
    cmd = [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
           "--continue-on-collection-errors", "-p", "no:cacheprovider",
           *subset]
    print("tsdbsan: running sanitized subset: %s" % " ".join(subset),
          flush=True)
    proc = subprocess.run(cmd, cwd=_REPO, env=env)
    if not os.path.exists(report_path):
        # the child died before pytest_sessionfinish could write the
        # report — a crashed sanitized run must NOT read as clean
        # (chaos_soak.check_san_reports holds the same line)
        print("tsdbsan: findings report %s was never written (child "
              "exited %d) — cannot certify the run" %
              (report_path, proc.returncode))
        return 2
    findings = _load_report(report_path)
    errors = [f for f in findings if f.get("level") == "error"]
    notes = [f for f in findings if f.get("level") != "error"]

    if os.path.exists(state_path):
        print("tsdbsan: cross-checking observed lock-order graph "
              "against the static one", flush=True)
        notes.extend(cross_check(state_path))

    for f in errors:
        print("error: %(path)s:%(line)d: [%(rule)s] %(message)s" % f)
    for f in notes:
        print("note: %(path)s:%(line)d: [%(rule)s] %(message)s" % f)

    everything = errors + notes         # incl. cross-check notes
    if report:
        with open(report, "w", encoding="utf-8") as fh:
            json.dump(everything, fh, indent=1, sort_keys=True)
            fh.write("\n")
        print("tsdbsan: findings JSON written to %s" % report)
    if sarif:
        _write_sarif(everything, sarif)
        print("tsdbsan: SARIF written to %s" % sarif)

    if errors:
        print("tsdbsan: %d error-level finding(s)" % len(errors))
        return 1
    if strict_tests and proc.returncode not in (0,):
        print("tsdbsan: clean, but the subset exited %d and "
              "--strict-tests is set" % proc.returncode)
        return 1
    print("tsdbsan: clean (%d note(s))" % len(notes))
    return 0


def cross_check(state_path: str) -> list[dict]:
    """Offline static<->dynamic diff from a persisted observed graph."""
    from tools.sanitize import deadlock
    from tools.sanitize.report import SanReporter
    observed = deadlock.load_observed(state_path)
    static = deadlock.static_edges_with_sites()
    # a private reporter so the CLI never pollutes the process-global one
    reporter = SanReporter()
    deadlock.cross_check(static_edges=static, observed=observed,
                         reporter=reporter)
    return reporter.to_json()


def _load_report(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    with open(path, "r", encoding="utf-8") as fh:
        payload = json.load(fh)
    return payload if isinstance(payload, list) else []


def _write_sarif(findings: list[dict], path: str) -> None:
    """One serializer: seed a private SanReporter and reuse its
    to_sarif, so the CLI artifact cannot drift from the plugin's."""
    from tools.lint.core import Finding
    from tools.sanitize.report import SanReporter
    rep = SanReporter()
    rep.restore([Finding(f["path"], f["line"], f["rule"], f["message"])
                 for f in findings])
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(rep.to_sarif(), fh, indent=1, sort_keys=True)
        fh.write("\n")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(prog="tsdbsan", description=__doc__)
    ap.add_argument("--subset", choices=["tier1"],
                    help="run a named sanitized test subset")
    ap.add_argument("--cross-check", metavar="STATE_JSON",
                    help="diff a persisted observed lock-order graph "
                         "against the static one and exit")
    ap.add_argument("--sarif", metavar="PATH",
                    help="write findings as SARIF 2.1.0")
    ap.add_argument("--report", metavar="PATH",
                    help="write findings JSON to this path")
    ap.add_argument("--strict-tests", action="store_true",
                    help="also fail when the child pytest run fails")
    args = ap.parse_args(argv)

    if args.cross_check:
        notes = cross_check(args.cross_check)
        for f in notes:
            print("note: %(path)s:%(line)d: [%(rule)s] %(message)s" % f)
        print("tsdbsan cross-check: %d stale-edge/lint-gap note(s)"
              % len(notes))
        return 0
    if args.subset == "tier1":
        return run_subset(SUBSET_TIER1, args.sarif, args.report,
                          args.strict_tests)
    ap.print_usage()
    return 2


if __name__ == "__main__":
    sys.exit(main())

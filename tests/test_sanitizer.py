"""tsdbsan unit tests: seeded-bug fixtures, cross-check, SARIF.

Mirrors the lint fixture convention (tests/test_lint_analyzers.py):
every true-positive fixture line under tests/san_fixtures/ carries an
`# EXPECT: <rule>` marker and the tests assert the detector fires
EXACTLY those (line, rule) pairs; true-negative fixtures must come back
empty.  The corpus seeds one deliberate bug per detector:

    race_tp / race_tn            lockset detector (annotated +
                                 Eraser-on-unannotated, handoff TN,
                                 suppression TN)
    inversion_tp / inversion_tn  order-graph inversion detector
    recompile_tp / recompile_tn  JAX compile sanitizer (per-call jit
                                 TP, lru_cache builder TN)
    replication_tp / replication_tn
                                 lockset detector over the replication
                                 manager's shapes: ship-ack vs puller
                                 position race (tsd/replication.py)

The blocked-past-deadline watcher (deadlock.record_blocked_wait /
report_blocked_past_deadline) is staged inline rather than from file
fixtures: its inputs are real contended acquires under an ambient
request Deadline, which a test thread pair produces directly.

CPU-only (conftest pins JAX_PLATFORMS=cpu); nothing here touches the
mesh route.

Works standalone AND under a TSDBSAN=1 session: when the pytest plugin
already installed the sanitizer these tests borrow it, snapshotting and
restoring the global reporter + order-graph state so deliberate fixture
bugs never leak into the session's own verdict.
"""

from __future__ import annotations

import importlib.util
import os
import re
import sys
import threading

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import tools.sanitize as sanitize  # noqa: E402
from tools.sanitize import deadlock, effects, lockset, order  # noqa: E402
from tools.sanitize.jax_san import JaxSanitizer  # noqa: E402
from tools.sanitize.locks import SanLockBase  # noqa: E402
from tools.sanitize.report import REPORTER  # noqa: E402

FIXTURES = os.path.join(REPO, "tests", "san_fixtures")

_EXPECT = re.compile(r"#\s*EXPECT:\s*([a-z0-9-]+)")


@pytest.fixture(scope="module")
def san():
    """The installed sanitizer — ours if no TSDBSAN=1 plugin armed it.
    Global reporter/graph state is snapshotted and restored so the
    deliberate fixture bugs stay invisible to the enclosing session."""
    owned = not sanitize.installed()
    if owned:
        sanitize.install(extra_lock_prefixes=("san_fixtures",))
    saved_findings = REPORTER.raw_findings()
    saved_graph = deadlock.snapshot_state()
    saved_streams = order.snapshot_state()
    saved_effects = effects.snapshot_state()
    yield sanitize
    REPORTER.clear()
    REPORTER.restore(saved_findings)
    deadlock.restore_state(saved_graph)
    order.restore_state(saved_streams)
    effects.restore_state(saved_effects)
    if owned:
        sanitize.uninstall()


@pytest.fixture(autouse=True)
def _isolated(san):
    REPORTER.clear()
    deadlock.reset()
    order.reset()
    effects.reset()
    yield


def _load_fixture(name: str):
    """Import tests/san_fixtures/<name>.py as `san_fixtures.<name>`
    (the dotted prefix the lock-factory scoping matches) and instrument
    its classes."""
    modname = "san_fixtures." + name
    sys.modules.pop(modname, None)
    path = os.path.join(FIXTURES, name + ".py")
    spec = importlib.util.spec_from_file_location(modname, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[modname] = mod
    spec.loader.exec_module(mod)
    sanitize.instrument_module(mod)
    return mod


def _expected(name: str) -> set[tuple[int, str]]:
    out = set()
    with open(os.path.join(FIXTURES, name + ".py"),
              encoding="utf-8") as fh:
        for i, line in enumerate(fh, start=1):
            m = _EXPECT.search(line)
            if m:
                out.add((i, m.group(1)))
    return out


def _findings(name: str) -> set[tuple[int, str]]:
    rel = "tests/san_fixtures/%s.py" % name
    return {(f.line, f.rule) for f in REPORTER.findings()
            if f.path == rel}


# --------------------------------------------------------------------- #
# Lockset detector                                                      #
# --------------------------------------------------------------------- #

class TestLockset:
    def test_race_tp_fires_exactly_the_expected_lines(self, san):
        mod = _load_fixture("race_tp")
        mod.run()
        expected = _expected("race_tp")
        assert expected, "race_tp declares no EXPECT markers"
        got = _findings("race_tp")
        assert got == expected, (
            "missed: %s, extra: %s" % (expected - got, got - expected))

    def test_race_tn_stays_clean(self, san):
        mod = _load_fixture("race_tn")
        mod.run()
        assert _findings("race_tn") == set(), [
            f.render() for f in REPORTER.findings()]

    def test_race_tn_suppression_is_load_bearing(self, san):
        """The `# tsdblint: disable=san-lockset-race` in race_tn hides
        a REAL detection — remove the suppression filter and the racy
        write reports.  Guards against the TN passing because the
        detector went blind."""
        mod = _load_fixture("race_tn")
        mod.run()
        raw = {(f.line, f.rule)
               for f in REPORTER.findings(apply_suppressions=False)
               if f.path == "tests/san_fixtures/race_tn.py"}
        assert any(rule == "san-lockset-race" for _ln, rule in raw), raw

    def test_replication_tp_fires_exactly_the_expected_lines(self, san):
        """ISSUE 15 fixture pair: the ship-ack/puller shapes of
        tsd/replication.py, seeded racy — the detector must land on
        exactly the marked lines."""
        mod = _load_fixture("replication_tp")
        mod.run()
        expected = _expected("replication_tp")
        assert expected, "replication_tp declares no EXPECT markers"
        got = _findings("replication_tp")
        assert got == expected, (
            "missed: %s, extra: %s" % (expected - got, got - expected))

    def test_replication_tn_stays_clean(self, san):
        mod = _load_fixture("replication_tn")
        mod.run()
        assert _findings("replication_tn") == set(), [
            f.render() for f in REPORTER.findings()]

    def test_fixture_locks_are_instrumented(self, san):
        mod = _load_fixture("race_tp")
        c = mod.RacyCounter()
        assert isinstance(c._lock, SanLockBase)
        assert c._lock.label == ("RacyCounter", "_lock")

    def test_locks_outside_sanitized_packages_stay_real(self, san):
        lock = threading.Lock()      # this module is not sanitized
        assert not isinstance(lock, SanLockBase)

    def test_release_clears_ownership_before_freeing_the_real_lock(
            self, san):
        """Regression (review finding): release() used to free the real
        lock FIRST and update owner/count after — a waiter acquiring in
        that window had its fresh ownership clobbered, seeding false
        unguarded-mutation findings under contention.  A stub inner
        lock observes the wrapper's state at the exact instant the real
        lock frees: it must already be cleared."""
        from tools.sanitize.locks import SanLock
        lock = SanLock()
        seen_at_release = []

        class StubInner:
            def acquire(self, blocking=True, timeout=-1):
                return True

            def release(self):
                # the moment a real waiter could win the lock
                seen_at_release.append((lock.owner, lock.count))

        lock.acquire()
        lock._inner = StubInner()
        lock.release()
        assert seen_at_release == [(None, 0)], seen_at_release

    def test_id_reuse_does_not_inherit_stale_eraser_state(self, san):
        """Regression (review finding): __slots__ classes without
        __weakref__ (Series!) use the id-keyed state fallback; CPython
        reuses a freed instance's address, so a new object could
        inherit a dead one's SHARED Eraser state and report a false
        race on its very first writes.  instrument_class now purges the
        id entry at __init__."""
        from tools.lint.annotations import ClassAnnotations
        from tools.sanitize.locks import SanLock

        class Slotted:
            __slots__ = ("_lock", "n")

            def __init__(self):
                self._lock = SanLock()
                self.n = 0

        ann = ClassAnnotations("Slotted", "tests/test_sanitizer.py", 1)
        ann.locks["_lock"] = "Lock"
        assert lockset.instrument_class(Slotted, ann)
        try:
            for _ in range(64):
                a = Slotted()
                # drive a's `n` into SHARED state (unreported: only the
                # worker wrote post-handoff)
                a.n = 1
                t = threading.Thread(target=setattr, args=(a, "n", 2))
                t.start()
                t.join()
                dead_id = id(a)
                del a
                b = Slotted()
                if id(b) != dead_id:
                    del b
                    continue
                # address reused: without the purge, b would inherit
                # a's SHARED/empty-lockset state and this single-thread
                # write would close the false race
                REPORTER.clear()
                b.n = 5
                racy = [f.render() for f in REPORTER.raw_findings()
                        if "Slotted.n" in f.message]
                assert racy == [], racy
                return
            pytest.skip("CPython never reused the freed id")
        finally:
            lockset.uninstrument_class(Slotted)


# --------------------------------------------------------------------- #
# Deadlock watcher                                                      #
# --------------------------------------------------------------------- #

class TestDeadlockWatcher:
    def test_inversion_tp_fires_exactly_the_expected_lines(self, san):
        mod = _load_fixture("inversion_tp")
        mod.run()
        deadlock.detect_inversions()
        expected = _expected("inversion_tp")
        assert expected
        got = _findings("inversion_tp")
        assert got == expected, (
            "missed: %s, extra: %s" % (expected - got, got - expected))

    def test_inversion_tn_stays_clean(self, san):
        mod = _load_fixture("inversion_tn")
        mod.run()
        deadlock.detect_inversions()
        assert _findings("inversion_tn") == set(), [
            f.render() for f in REPORTER.findings()]

    def test_live_deadlock_wait_for_cycle(self, san):
        mod = _load_fixture("inversion_tp")
        left, right = mod.Left(), mod.Right()
        ev_l, ev_r = threading.Event(), threading.Event()

        def hold_left():
            with left._lock:
                ev_l.set()
                ev_r.wait(2)
                got = right._lock.acquire(timeout=1.0)
                if got:
                    right._lock.release()

        def hold_right():
            with right._lock:
                ev_r.set()
                ev_l.wait(2)
                got = left._lock.acquire(timeout=1.0)
                if got:
                    left._lock.release()

        t1 = threading.Thread(target=hold_left)
        t2 = threading.Thread(target=hold_right)
        t1.start()
        t2.start()
        ev_l.wait(2)
        ev_r.wait(2)
        import time
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            deadlock.scan_waiting_now()
            if any(f.rule == "san-deadlock"
                   for f in REPORTER.raw_findings()):
                break
            time.sleep(0.02)
        t1.join()
        t2.join()
        rules = {f.rule for f in REPORTER.raw_findings()}
        assert "san-deadlock" in rules, rules

    def test_nonreentrant_self_reacquire_reports(self, san):
        mod = _load_fixture("inversion_tp")
        left = mod.Left()
        left._lock.acquire()
        try:
            assert left._lock.acquire(timeout=0.05) is False
        finally:
            left._lock.release()
        rules = {f.rule for f in REPORTER.raw_findings()}
        assert "san-deadlock" in rules, rules


# --------------------------------------------------------------------- #
# Blocked-past-deadline watcher (ISSUE 17 satellite)                    #
# --------------------------------------------------------------------- #

class TestBlockedPastDeadline:
    """A blocked instrumented acquire whose wait outlasts the ambient
    request Deadline's remainder must surface as a note-level
    san-blocked-past-deadline finding, cross-referenced against
    deadline_discipline's static request-path set and tagged by any
    `# blocking: bounded-by` waiver on the acquire line."""

    def _stage(self, lock, do_acquire, timeout_ms=10.0, hold_s=0.1):
        """Contend `lock`: a holder thread owns it for `hold_s` while
        the calling thread runs `do_acquire()` under a bounded ambient
        Deadline that expires mid-wait."""
        import time
        from opentsdb_tpu.query.limits import (Deadline,
                                               activate_deadline,
                                               deactivate_deadline)
        held = threading.Event()

        def holder():
            lock.acquire()
            held.set()
            time.sleep(hold_s)
            lock.release()

        t = threading.Thread(target=holder)
        t.start()
        assert held.wait(2)
        activate_deadline(Deadline(timeout_ms=timeout_ms))
        try:
            got = do_acquire()
        finally:
            deactivate_deadline()
        assert got, "the holder never released within the timeout"
        lock.release()
        t.join()

    def test_blocked_acquire_past_deadline_reports_note(self, san):
        from tools.sanitize.locks import SanLock
        from tools.sanitize.report import SanReporter, rule_level
        lock = SanLock()
        lock.label = ("BlockedFixture", "_lock")
        self._stage(lock, lambda: lock.acquire(timeout=2.0))
        events = deadlock.blocked_waits()
        assert len(events) == 1, events
        (path, line, func, name), waited = next(iter(events.items()))
        assert path == "tests/test_sanitizer.py"
        assert name == "BlockedFixture._lock"
        assert waited >= 0.01
        # not on any static request path -> the lint-gap-shaped tag
        rep = SanReporter()
        emitted = deadlock.report_blocked_past_deadline(
            reporter=rep, static_paths=set())
        assert emitted == [(path, line, func, name)]
        (f,) = rep.raw_findings()
        assert f.rule == "san-blocked-past-deadline"
        assert rule_level(f.rule) == "note"
        assert "NOT in the static request-path set" in f.message
        # the same event against a static set that covers the site
        rep2 = SanReporter()
        deadlock.report_blocked_past_deadline(
            reporter=rep2, static_paths={(path, func)})
        (f2,) = rep2.raw_findings()
        assert "static request-path set — the route is covered" \
            in f2.message

    def test_waived_acquire_reports_the_bounded_by_reason(self, san):
        from tools.sanitize.locks import SanLock
        from tools.sanitize.report import SanReporter
        lock = SanLock()
        self._stage(
            lock,
            lambda: lock.acquire(timeout=2.0))  # blocking: bounded-by test hold window
        rep = SanReporter()
        deadlock.report_blocked_past_deadline(reporter=rep,
                                              static_paths=set())
        (f,) = rep.raw_findings()
        assert "bounded-by test hold window" in f.message
        assert "an unlabeled Lock" in f.message

    def test_unexpired_deadline_records_nothing(self, san):
        from tools.sanitize.locks import SanLock
        lock = SanLock()
        # a 10s budget comfortably outlives the 100ms hold
        self._stage(lock, lambda: lock.acquire(timeout=2.0),
                    timeout_ms=10_000.0)
        assert deadlock.blocked_waits() == {}
        assert deadlock.report_blocked_past_deadline() == []

    def test_no_ambient_deadline_records_nothing(self, san):
        import time
        from tools.sanitize.locks import SanLock
        lock = SanLock()
        held = threading.Event()

        def holder():
            lock.acquire()
            held.set()
            time.sleep(0.05)
            lock.release()

        t = threading.Thread(target=holder)
        t.start()
        assert held.wait(2)
        assert lock.acquire(timeout=2.0)
        lock.release()
        t.join()
        assert deadlock.blocked_waits() == {}

    def test_snapshot_restore_round_trips_blocked_waits(self, san):
        key = ("x.py", 12, "f", "C._lock")
        with deadlock._state_lock:
            deadlock._blocked_waits[key] = 0.25
        snap = deadlock.snapshot_state()
        deadlock.reset()
        assert deadlock.blocked_waits() == {}
        deadlock.restore_state(snap)
        assert deadlock.blocked_waits() == {key: 0.25}

    def test_static_request_path_set_is_cached_and_plausible(self, san):
        a = deadlock.static_request_paths_cached()
        b = deadlock.static_request_paths_cached()
        assert a is b, "second call must reuse the cached set"
        # the fan-out fetch and the ack-path ship are the two routes the
        # lint gut-pin tests un-bound; both must be in the static set
        assert ("opentsdb_tpu/tsd/cluster.py", "_fetch_peer") in a
        assert ("opentsdb_tpu/tsd/replication.py", "_ship") in a


# --------------------------------------------------------------------- #
# JAX compile sanitizer                                                 #
# --------------------------------------------------------------------- #

class TestJaxSanitizer:
    def _run_phases(self, name):
        import jax.numpy as jnp
        mod = _load_fixture(name)
        jsan = JaxSanitizer()
        jsan.start()
        try:
            x = jnp.ones(16)
            mod.run(x)           # warmup: compiles are expected
            jsan.mark_steady()
            mod.run(x)           # steady: any compile is a finding
        finally:
            jsan.stop()
        return jsan

    def test_per_call_jit_recompiles_in_steady_state(self, san):
        self._run_phases("recompile_tp")
        expected = _expected("recompile_tp")
        assert expected
        got = _findings("recompile_tp")
        assert got == expected, (
            "missed: %s, extra: %s" % (expected - got, got - expected))

    def test_lru_cached_builder_stays_clean(self, san):
        jsan = self._run_phases("recompile_tn")
        assert _findings("recompile_tn") == set(), [
            f.render() for f in REPORTER.findings()]
        # and the cache genuinely absorbed the steady call
        assert all(v["steady"] == 0 for v in jsan.compiles.values()), \
            jsan.compiles


# --------------------------------------------------------------------- #
# Static <-> dynamic cross-check                                        #
# --------------------------------------------------------------------- #

class TestCrossCheck:
    def test_static_graph_extraction_is_deterministic(self):
        a = deadlock.static_edges_with_sites()
        b = deadlock.static_edges_with_sites()
        assert a == b
        assert a, "the package should have at least one static edge"

    def test_diff_classifies_stale_and_gap_edges(self):
        static = {(("A", "_l"), ("B", "_m")): ("opentsdb_tpu/a.py", 10),
                  (("B", "_m"), ("C", "_n")): ("opentsdb_tpu/b.py", 20)}
        observed = {(("B", "_m"), ("C", "_n")): ("x.py", 5),
                    (("C", "_n"), ("D", "_o")): ("y.py", 7)}
        from tools.sanitize.report import SanReporter
        rep = SanReporter()
        diff = deadlock.cross_check(static_edges=static,
                                    observed=observed, reporter=rep)
        assert diff["stale"] == [(("A", "_l"), ("B", "_m"))]
        assert diff["gaps"] == [(("C", "_n"), ("D", "_o"))]
        rules = sorted((f.rule, f.path) for f in rep.raw_findings())
        assert rules == [("san-lint-gap", "y.py"),
                         ("san-stale-static-edge", "opentsdb_tpu/a.py")]
        # deterministic: a second pass reproduces the same findings
        rep2 = SanReporter()
        deadlock.cross_check(static_edges=static, observed=observed,
                             reporter=rep2)
        assert rep2.raw_findings() == rep.raw_findings()

    def test_observed_graph_round_trips_through_disk(self, tmp_path,
                                                     san):
        mod = _load_fixture("inversion_tn")
        mod.run()
        path = str(tmp_path / "observed.json")
        deadlock.save_observed(path)
        loaded = deadlock.load_observed(path)
        assert loaded == deadlock.observed_edges()

    def test_cross_check_notes_never_gate(self):
        from tools.sanitize.report import SanReporter, rule_level
        rep = SanReporter()
        deadlock.cross_check(
            static_edges={(("A", "_l"), ("B", "_m")): ("a.py", 1)},
            observed={}, reporter=rep)
        assert rep.raw_findings()
        assert all(rule_level(f.rule) == "note"
                   for f in rep.raw_findings())


# --------------------------------------------------------------------- #
# Runtime ordering recorder                                             #
# --------------------------------------------------------------------- #

class TestOrderRecorder:
    """tools/sanitize/order.py: per-stream event logs, patch-table
    instrumentation, snapshot/restore isolation, and the
    static<->dynamic happens-before cross-check."""

    @staticmethod
    def _my_stream() -> str:
        return "thread:%d" % threading.get_ident()

    def test_streams_key_by_trace_when_one_is_active(self, san):
        from opentsdb_tpu.obs import trace as obs_trace
        t = obs_trace.Trace("order-unit")
        obs_trace.activate(t)
        try:
            order.record("x-a")
        finally:
            obs_trace.deactivate()
        order.record("x-b")
        got = order.streams()
        assert "x-a" in got["trace:" + t.trace_id]
        assert "x-b" in got[self._my_stream()]
        assert "x-b" not in got["trace:" + t.trace_id]

    def test_first_occurrence_rank_survives_repeats(self, san):
        order.record("x-b")
        order.record("x-a")
        order.record("x-b")     # a repeat must not move the rank
        ev = order.streams()[self._my_stream()]
        assert ev["x-b"][0] < ev["x-a"][0]

    def test_snapshot_restore_round_trips_the_streams(self, san):
        order.record("x-a")
        order.record("x-b")
        snap = order.snapshot_state()
        before = order.streams()
        order.reset()
        order.record("x-c")
        assert order.streams() != before
        order.restore_state(snap)
        assert order.streams() == before

    def test_inverted_stream_is_a_violation_note(self, san):
        from tools.sanitize.report import SanReporter, rule_level
        order.record("x-b")
        order.record("x-a")
        table = {"contracts": {("x-a", "x-b")}, "events": {"x-a", "x-b"}}
        rep = SanReporter()
        diff = order.cross_check(static_table=table, reporter=rep)
        assert [v[1:] for v in diff["violations"]] == [("x-a", "x-b")]
        (f,) = rep.raw_findings()
        assert f.rule == "san-order-violation"
        assert rule_level(f.rule) == "note"
        assert "'x-b' before 'x-a'" in f.message
        # deterministic: a second pass reproduces the same findings
        rep2 = SanReporter()
        order.cross_check(static_table=table, reporter=rep2)
        assert rep2.raw_findings() == rep.raw_findings()

    def test_contract_order_and_one_sided_streams_stay_silent(self, san):
        from tools.sanitize.report import SanReporter
        order.record("x-a")
        order.record("x-b")     # declared order — clean
        order.record("x-only")  # no contract names it
        table = {"contracts": {("x-a", "x-b")},
                 "events": {"x-a", "x-b"}}
        rep = SanReporter()
        diff = order.cross_check(static_table=table, reporter=rep)
        assert diff == {"violations": [], "gaps": []}
        assert rep.raw_findings() == []

    def test_unobserved_instrumented_event_is_a_gap(self, san):
        from tools.sanitize.report import SanReporter, rule_level
        order.record("memstore-write")
        table = {"contracts": {("memstore-write", "memstore-mark")},
                 "events": {"memstore-write", "memstore-mark"}}
        rep = SanReporter()
        diff = order.cross_check(static_table=table, reporter=rep)
        assert diff["gaps"] == ["memstore-mark"]
        assert diff["violations"] == []
        (f,) = rep.raw_findings()
        assert f.rule == "san-order-gap"
        assert rule_level(f.rule) == "note"
        assert "memstore-mark" in f.message

    def test_uninstrumented_contract_events_never_gap(self, san):
        # catch-up-pull has no runtime probe: a normal session never
        # takes the rejoin path, so its absence must stay silent
        from tools.sanitize.report import SanReporter
        order.record("memstore-write")
        table = {"contracts": {("catch-up-pull", "rejoin-ready")},
                 "events": {"catch-up-pull", "rejoin-ready"}}
        rep = SanReporter()
        diff = order.cross_check(static_table=table, reporter=rep)
        assert diff == {"violations": [], "gaps": []}
        assert rep.raw_findings() == []

    def test_empty_session_cross_checks_without_a_tree_walk(self, san):
        from tools.sanitize.report import SanReporter
        rep = SanReporter()
        # static_table=None with nothing recorded must return empty
        # WITHOUT resolving the static table (no lint tree walk)
        diff = order.cross_check(static_table=None, reporter=rep)
        assert diff == {"violations": [], "gaps": []}
        assert rep.raw_findings() == []

    def test_instrumented_series_append_records_the_write_event(
            self, san):
        from opentsdb_tpu.storage import memstore
        assert getattr(memstore.Series.append, "_tsdbsan_order", False), \
            "install() should have wrapped the memstore-write probe"
        s = memstore.Series(memstore.SeriesKey.make(1, {2: 3}))
        s.append(1000, 1.5, False)
        ev = order.streams()[self._my_stream()]
        assert "memstore-write" in ev
        assert ev["memstore-write"][1] == "tests/test_sanitizer.py"

    def test_static_table_matches_the_lints_contract_set(self, san):
        table = order.static_table_cached()
        assert ("memstore-write", "memstore-mark") in table["contracts"]
        assert ("wal-append", "ingest-ack") in table["contracts"]
        # every instrumented event is a real tagged event in the tree
        missing = order.instrumented_events() - table["events"]
        assert not missing, \
            "probes without a tagged site drifted: %s" % sorted(missing)


# --------------------------------------------------------------------- #
# Explain effect sentinel                                               #
# --------------------------------------------------------------------- #

class TestEffectSentinel:
    """tools/sanitize/effects.py: the dynamic half of effect_contract.
    Explain-tagged requests arm write/dispatch/permit recording; events
    are diffed against the static `# effects:` contract table at finish.
    """

    @staticmethod
    def _armed_call(fn, *args, **kwargs):
        """Run fn under the same arming wrapper explain_query gets."""
        return effects._arming_wrap(fn)(*args, **kwargs)

    def test_install_wraps_the_arming_point_and_the_gateways(self, san):
        from opentsdb_tpu.ops import pipeline
        from opentsdb_tpu.query import explain as explain_mod
        from opentsdb_tpu.tsd import admission
        assert getattr(explain_mod.explain_query, "_tsdbsan_effects",
                       False), "install() should wrap explain_query"
        assert getattr(pipeline.run_pipeline, "_tsdbsan_effects", False)
        assert getattr(admission.AdmissionGate.acquire,
                       "_tsdbsan_effects", False)

    def test_unarmed_execution_records_nothing(self, san):
        sentinel = effects._sentinel_wrap(lambda: 7, "dispatch", "x.f")
        assert sentinel() == 7
        assert not effects.armed()
        assert effects.events() == {}

    def test_armed_gateway_entry_is_recorded_once(self, san):
        sentinel = effects._sentinel_wrap(lambda: 7, "dispatch", "x.f")

        def consult():
            assert effects.armed()
            sentinel()
            sentinel()          # dedup: one event per (kind, detail)
            return sentinel()

        assert self._armed_call(consult) == 7
        assert not effects.armed()   # disarmed on the way out
        ev = effects.events()
        assert set(ev) == {("dispatch", "x.f")}
        path, line = ev[("dispatch", "x.f")]
        assert path == "tests/test_sanitizer.py" and line > 0

    def test_armed_write_to_instrumented_class_is_recorded(self, san):
        mod = _load_fixture("race_tn")
        c = mod.DisciplinedCounter()
        self._armed_call(c.bump)
        assert ("write", "DisciplinedCounter.total") in effects.events()

    def test_cross_check_filters_writes_by_the_watched_set(self, san):
        from tools.sanitize.report import SanReporter, rule_level
        mod = _load_fixture("race_tn")
        c = mod.DisciplinedCounter()
        self._armed_call(c.bump)
        table = {"contracts": {}, "watched_classes": ["SomethingElse"]}
        rep = SanReporter()
        # the store is sanctioned (class not under a read-only contract)
        assert effects.cross_check(static_table=table, reporter=rep) \
            == {"violations": []}
        assert rep.raw_findings() == []
        # same event against a table that watches the class: violation
        rep2 = SanReporter()
        table["watched_classes"] = ["DisciplinedCounter"]
        diff = effects.cross_check(static_table=table, reporter=rep2)
        assert sorted(diff["violations"]) == [
            ("write", "DisciplinedCounter.approx"),
            ("write", "DisciplinedCounter.total")]
        found = rep2.raw_findings()
        assert {f.rule for f in found} == {"san-effect-violation"}
        assert rule_level("san-effect-violation") == "note"
        assert any("DisciplinedCounter.total" in f.message
                   for f in found)

    def test_dispatch_and_permit_always_violate(self, san):
        from tools.sanitize.report import SanReporter
        gw = effects._sentinel_wrap(lambda: None, "dispatch",
                                    "pipeline.run_pipeline")
        permit = effects._sentinel_wrap(lambda: True, "permit",
                                        "AdmissionGate.acquire")

        def consult():
            gw()
            permit()

        self._armed_call(consult)
        rep = SanReporter()
        diff = effects.cross_check(
            static_table={"contracts": {}, "watched_classes": []},
            reporter=rep)
        assert sorted(diff["violations"]) == [
            ("dispatch", "pipeline.run_pipeline"),
            ("permit", "AdmissionGate.acquire")]
        msgs = {f.message for f in rep.raw_findings()}
        assert any("dispatch gateway" in m for m in msgs)
        assert any("admission permit" in m for m in msgs)

    def test_empty_session_cross_checks_without_a_tree_walk(self, san):
        from tools.sanitize.report import SanReporter
        rep = SanReporter()
        # static_table=None with nothing recorded must return empty
        # WITHOUT resolving the static table (no lint tree walk)
        assert effects.cross_check(static_table=None, reporter=rep) \
            == {"violations": []}
        assert rep.raw_findings() == []

    def test_snapshot_restore_round_trips_the_events(self, san):
        sentinel = effects._sentinel_wrap(lambda: 0, "dispatch", "x.f")
        self._armed_call(sentinel)
        snap = effects.snapshot_state()
        before = effects.events()
        effects.reset()
        assert effects.events() == {}
        effects.restore_state(snap)
        assert effects.events() == before

    def test_static_table_matches_the_lints_contract_set(self, san):
        table = effects.static_table_cached()
        assert set(table["watched_classes"]) == {
            "AggregateCache", "DeviceSeriesCache", "RollupLanes",
            "_ExplainConsults"}
        contracts = table["contracts"]
        assert contracts[
            "opentsdb_tpu.storage.rollup.RollupLanes.plan"] == \
            ("observe-gated", "observe")
        assert contracts[
            "opentsdb_tpu.storage.device_cache.DeviceSeriesCache.peek"] \
            == ("reads-only", None)
        # canonicalize classes are deliberately NOT watched: Series
        # canonicalization during an explain consult is sanctioned
        assert "Series" not in table["watched_classes"]

    def test_real_explain_request_arms_and_cross_checks_clean(
            self, san):
        # end-to-end: a real /api/query/explain request through the
        # RPC layer must run ARMED (rpcs reaches explain_query via the
        # module attribute, so the wrapper is live) and the session
        # cross-check against the real static table must stay clean —
        # the acceptance run the dynamic twin exists for
        from tests.test_explain import BASE, _manager, ask, feed
        from tools.sanitize.report import SanReporter
        tsdb, mgr = _manager()
        feed(tsdb, "sys.san.explain", series=1, points=50)
        armed_seen = []
        orig_runner = tsdb.new_query_runner

        def probing(*a, **k):
            armed_seen.append(effects.armed())
            return orig_runner(*a, **k)

        tsdb.new_query_runner = probing
        uri = "/api/query/explain?start=%d&end=%d&m=sum:sys.san.explain" \
            % (BASE, BASE + 50 * 15)
        status, rep, _ = ask(mgr, uri)
        assert status == 200, rep
        assert armed_seen == [True], \
            "the consult should have run under the arming wrapper"
        assert not effects.armed()
        rep2 = SanReporter()
        diff = effects.cross_check(reporter=rep2)
        assert diff == {"violations": []}
        assert rep2.raw_findings() == []


# --------------------------------------------------------------------- #
# SARIF + shared grammar                                                #
# --------------------------------------------------------------------- #

class TestArtifacts:
    def test_sarif_output_validates_against_the_same_schema_as_lint(
            self, san):
        import jsonschema
        from tests.test_lint_analyzers import SARIF_SUBSET_SCHEMA
        mod = _load_fixture("race_tp")
        mod.run()
        deadlock.cross_check(
            static_edges={(("Z", "_l"), ("Q", "_m")): ("z.py", 3)},
            observed={})
        doc = REPORTER.to_sarif()
        jsonschema.validate(doc, SARIF_SUBSET_SCHEMA)
        run = doc["runs"][0]
        assert run["tool"]["driver"]["name"] == "tsdbsan"
        levels = {r["level"] for r in run["results"]}
        assert "error" in levels and "note" in levels
        rule_ids = {r["id"] for r in run["tool"]["driver"]["rules"]}
        assert {"san-lockset-race", "san-deadlock",
                "san-recompile-after-warmup"} <= rule_ids

    def test_report_json_written(self, tmp_path, san):
        mod = _load_fixture("race_tp")
        mod.run()
        path = str(tmp_path / "findings.json")
        REPORTER.write_report(path)
        import json
        payload = json.loads(open(path).read())
        assert any(e["rule"] == "san-lockset-race" for e in payload)
        assert all(set(e) == {"path", "line", "rule", "level", "message"}
                   for e in payload)

    def test_force_cooldown_helper_holds_the_breaker_lock(self, san):
        """Regression for the true positive tsdbsan surfaced on the
        sanitized tier-1 subset: tests/fault_fixtures.py's
        force_cooldown_elapsed rewound CircuitBreaker.opened_at
        (guarded-by _lock) WITHOUT the lock while responder threads can
        transition the breaker concurrently.  This test reproduces the
        exact multi-thread access shape and asserts the helper now
        mutates under the lock — it fails pre-fix under TSDBSAN=1."""
        from opentsdb_tpu.tsd.cluster import CircuitBreaker
        from tests.fault_fixtures import force_cooldown_elapsed
        breaker = CircuitBreaker(threshold=1, cooldown_s=30.0)
        # open it from a worker thread (so the instance is genuinely
        # shared and the pre-publication exemption does not apply)
        t = threading.Thread(target=breaker.record_failure)
        t.start()
        t.join()
        assert breaker.state == CircuitBreaker.OPEN
        force_cooldown_elapsed(breaker)
        assert breaker.allow()      # the probe path still works
        offending = [f.render() for f in REPORTER.raw_findings()
                     if f.rule == "san-unguarded-mutation"
                     and "opened_at" in f.message]
        assert offending == [], offending

    def test_lint_and_sanitizer_share_one_annotation_grammar(self):
        """The satellite contract: both layers parse guarded-by through
        tools/lint/annotations.py, so the fixture file reads back the
        same locks/annotations the lint analyzer would see."""
        from tools.lint.annotations import scan_module_file
        anns = scan_module_file(os.path.join(FIXTURES, "race_tp.py"))
        racy = anns["RacyCounter"]
        assert racy.locks == {"_lock": "Lock"}
        assert racy.guarded == {"guarded_total": "_lock"}
        assert "free_total" not in racy.guarded

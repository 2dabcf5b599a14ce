"""Overload resilience (ISSUE 8): the admission gate, the
request-scoped Deadline, and cooperative cancellation.

Covers the tentpole contracts deterministically:

  * the Deadline primitive (manual clock — no wall sleeps for expiry),
    the ambient per-thread activation, and QueryBudget deriving its
    clock + cancellation token from it;
  * AdmissionGate permits/queue/shed semantics, priority drain order,
    and queue-wait cancellation that releases WITHOUT dispatching;
  * CancellationHandle bind-before/after-cancel replay;
  * the degradation ladder (coarsen, then truncate);
  * end-to-end through RpcManager.handle_http: shed 503 + Retry-After,
    degraded 200 + partialResults, deadline minting from the header;
  * deadline PROPAGATION to fan-out peers: the coordinator forwards
    its remainder via x-tsdb-deadline-ms and a slow-body peer fetch
    aborts within it (this test FAILS without the clamp — the cluster
    budget alone is configured far beyond the asserted bound);
  * live-socket server behavior: a disconnected client's queued query
    releases without dispatching; TSDServer.stop force-cancels at
    tsd.network.drain_timeout_ms instead of blocking forever.

Runs under TSDBSAN=1 in the sanitized tier-1 subset
(tools/sanitize/run.py) — the gate's lock discipline is race-checked.
"""

import asyncio
import json
import socket
import threading
import time

import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.query import limits
from opentsdb_tpu.query.limits import (
    Deadline, QueryBudget, QueryCancelledException, QueryException)
from opentsdb_tpu.tsd import admission
from opentsdb_tpu.tsd.admission import (
    AdmissionGate, CancellationHandle, ShedError)
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils import faults
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400


def counter_value(name: str, **labels) -> float:
    """Current value of one labeled registry counter cell (0 when the
    family or cell does not exist yet)."""
    key = tuple(sorted((k, str(v)) for k, v in labels.items()))
    for fam in REGISTRY.families():
        if fam.name == name:
            for label_key, cell in fam.children():
                if label_key == key:
                    return cell.get()
    return 0.0


class ManualClock:
    def __init__(self, t: float = 1000.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# --------------------------------------------------------------------- #
# Deadline                                                              #
# --------------------------------------------------------------------- #

class TestDeadline:
    def test_remaining_and_expiry(self):
        clock = ManualClock()
        d = Deadline(500, clock=clock)
        assert d.bounded and d.remaining_ms() == 500
        clock.t += 0.3
        assert d.remaining_ms() == pytest.approx(200)
        assert not d.expired()
        d.check()                              # still alive
        clock.t += 0.3
        assert d.expired()
        with pytest.raises(QueryException) as ei:
            d.check()
        assert ei.value.status == 413
        assert not isinstance(ei.value, QueryCancelledException)

    def test_unbounded(self):
        d = Deadline(0)
        assert not d.bounded
        assert d.remaining_ms() == float("inf")
        assert not d.expired()
        d.check()

    def test_cancel_idempotent_first_reason_wins(self):
        d = Deadline(0)
        assert d.cancel("client disconnected")
        assert not d.cancel("drain")           # second flip: no-op
        assert d.is_cancelled()
        assert d.cancel_reason == "client disconnected"
        with pytest.raises(QueryCancelledException) as ei:
            d.check()
        assert ei.value.status == 503
        assert "client disconnected" in str(ei.value)

    def test_cancelled_beats_expired(self):
        """A cancelled deadline reports 503 (server gave up) even once
        also past its wall budget — disconnect must not read as 413."""
        clock = ManualClock()
        d = Deadline(100, clock=clock)
        d.cancel("client disconnected")
        clock.t += 10
        with pytest.raises(QueryCancelledException):
            d.check()

    def test_wait_cancelled_already_cancelled_returns_at_once(self):
        d = Deadline(0)
        d.cancel("client disconnected")
        start = time.monotonic()
        assert d.wait_cancelled(10.0) is True
        assert time.monotonic() - start < 1.0

    def test_wait_cancelled_serves_the_timeout_when_nothing_happens(self):
        d = Deadline(0)                        # unbounded, never flipped
        start = time.monotonic()
        assert d.wait_cancelled(0.02) is False
        assert time.monotonic() - start >= 0.015

    def test_wait_cancelled_clamps_to_the_remaining_budget(self):
        """Parking for 10s on a deadline with 30ms left must return
        within the remainder, not the requested timeout."""
        d = Deadline(30)
        start = time.monotonic()
        assert d.wait_cancelled(10.0) is False
        assert time.monotonic() - start < 5.0

    def test_wait_cancelled_wakes_on_cancel_from_another_thread(self):
        """The cancellation-token contract the retry backoff and the
        cluster probe loop build on: cancel() from the responder thread
        releases a parked waiter within one tick, not after its full
        timeout."""
        d = Deadline(0)
        t = threading.Timer(0.05, lambda: d.cancel("client disconnected"))
        t.start()
        start = time.monotonic()
        assert d.wait_cancelled(10.0) is True
        assert time.monotonic() - start < 5.0
        t.join()


class TestAmbientDeadline:
    def test_activate_deactivate(self):
        assert limits.active_deadline() is None
        d = Deadline(100)
        limits.activate_deadline(d)
        try:
            assert limits.active_deadline() is d
        finally:
            limits.deactivate_deadline()
        assert limits.active_deadline() is None

    def test_per_thread_isolation(self):
        d = Deadline(100)
        limits.activate_deadline(d)
        seen = {}

        def other():
            seen["deadline"] = limits.active_deadline()

        try:
            t = threading.Thread(target=other)
            t.start()
            t.join(5)
        finally:
            limits.deactivate_deadline()
        assert seen["deadline"] is None


class TestQueryBudgetDerivation:
    def test_budget_shares_request_clock(self):
        """A QueryBudget derived from the request deadline must expire
        on the REQUEST's clock — not restart tsd.query.timeout at
        planner time (the pre-PR behavior this test pins out)."""
        clock = ManualClock()
        d = Deadline(1000, clock=clock)
        clock.t += 0.9                          # 900ms burnt pre-planner
        # timeout_ms=0: the budget's own wall check reads the REAL
        # monotonic clock — only the derived deadline (manual clock)
        # may expire this budget
        budget = QueryBudget(None, "m", 0, deadline=d)
        budget.check_deadline()                 # 100ms left: alive
        clock.t += 0.2
        with pytest.raises(QueryException):
            budget.check_deadline()

    def test_budget_observes_cancellation(self):
        d = Deadline(0)
        budget = QueryBudget(None, "m", 0, deadline=d)
        budget.check_deadline()
        d.cancel("client disconnected")
        with pytest.raises(QueryCancelledException):
            budget.check_deadline()

    def test_budget_without_deadline_unchanged(self):
        budget = QueryBudget(None, "m", 60_000)
        budget.check_deadline()                 # fresh clock, no raise


# --------------------------------------------------------------------- #
# CancellationHandle                                                    #
# --------------------------------------------------------------------- #

class TestCancellationHandle:
    def test_cancel_after_bind_flips(self):
        h = CancellationHandle()
        d = Deadline(0)
        h.bind(d)
        assert h.cancel("client disconnected")
        assert d.is_cancelled() and h.is_cancelled()

    def test_cancel_before_bind_replays(self):
        """The responder loop may detect the disconnect before
        rpc_manager minted the deadline: the flip must not be lost."""
        h = CancellationHandle()
        assert h.cancel("client disconnected")
        assert h.is_cancelled()
        d = Deadline(0)
        h.bind(d)
        assert d.is_cancelled()
        assert d.cancel_reason == "client disconnected"

    def test_second_cancel_is_noop(self):
        h = CancellationHandle()
        assert h.cancel("a")
        assert not h.cancel("b")
        d = Deadline(0)
        h.bind(d)
        assert d.cancel_reason == "a"


# --------------------------------------------------------------------- #
# AdmissionGate                                                         #
# --------------------------------------------------------------------- #

def _gate(**over) -> AdmissionGate:
    props = {"tsd.query.admission.enable": "true",
             "tsd.query.admission.permits": "2",
             "tsd.query.admission.queue_limit": "4",
             "tsd.query.admission.max_wait_ms": "5000"}
    props.update({k: str(v) for k, v in over.items()})
    return AdmissionGate(Config(props))


class TestAdmissionGate:
    def test_disabled_gate_is_noop(self):
        gate = _gate(**{"tsd.query.admission.enable": "false"})
        with gate.acquire(None, "interactive"):
            assert gate.in_flight == 0

    def test_permits_bound_concurrency(self):
        gate = _gate()
        a = gate.acquire(None, "interactive")
        b = gate.acquire(None, "interactive")
        assert gate.in_flight == 2
        admitted = threading.Event()

        def third():
            with gate.acquire(None, "interactive"):
                admitted.set()

        t = threading.Thread(target=third)
        t.start()
        assert not admitted.wait(0.3)           # queued behind the bound
        a.release()
        assert admitted.wait(5)
        t.join(5)
        b.release()
        assert gate.in_flight == 0

    def test_release_is_idempotent(self):
        gate = _gate()
        permit = gate.acquire(None, "interactive")
        permit.release()
        permit.release()
        assert gate.in_flight == 0

    def test_queue_full_sheds_503_with_retry_after(self):
        gate = _gate(**{"tsd.query.admission.permits": "1",
                        "tsd.query.admission.queue_limit": "0"})
        before = counter_value("tsd.query.admission.shed",
                               reason="queue_full")
        with gate.acquire(None, "interactive"):
            with pytest.raises(ShedError) as ei:
                gate.acquire(None, "interactive")
        assert ei.value.status == 503
        assert ei.value.retry_after_s >= 1
        assert counter_value("tsd.query.admission.shed",
                             reason="queue_full") == before + 1

    def test_max_wait_sheds(self):
        gate = _gate(**{"tsd.query.admission.permits": "1",
                        "tsd.query.admission.max_wait_ms": "120"})
        before = counter_value("tsd.query.admission.shed",
                               reason="max_wait")
        t0 = time.monotonic()
        with gate.acquire(None, "interactive"):
            with pytest.raises(ShedError):
                gate.acquire(None, "interactive")
        assert time.monotonic() - t0 < 5.0
        assert counter_value("tsd.query.admission.shed",
                             reason="max_wait") == before + 1

    def test_cancel_while_queued_releases_without_permit(self):
        gate = _gate(**{"tsd.query.admission.permits": "1"})
        d = Deadline(0)
        outcome = {}

        def queued():
            try:
                gate.acquire(d, "interactive")
            except QueryException as e:
                outcome["exc"] = e

        with gate.acquire(None, "interactive"):
            admitted_before = gate.admitted
            t = threading.Thread(target=queued)
            t.start()
            deadline = time.time() + 5
            while time.time() < deadline and not gate._depth_locked():
                time.sleep(0.01)
            assert gate._depth_locked() == 1
            d.cancel("client disconnected")
            t.join(5)
        assert isinstance(outcome["exc"], QueryCancelledException)
        assert gate.admitted == admitted_before  # never dispatched
        assert gate._depth_locked() == 0         # left the queue
        assert gate.in_flight == 0

    def test_expired_deadline_while_queued(self):
        gate = _gate(**{"tsd.query.admission.permits": "1"})
        clock = ManualClock()
        d = Deadline(100, clock=clock)
        clock.t += 0.2                           # already past budget
        with gate.acquire(None, "interactive"):
            with pytest.raises(QueryException) as ei:
                gate.acquire(d, "interactive")
        assert ei.value.status == 413
        assert gate.in_flight == 0

    def test_interactive_drains_before_batch(self):
        gate = _gate(**{"tsd.query.admission.permits": "1"})
        order = []
        queued = []

        def waiter(cls):
            with gate.acquire(None, cls):
                order.append(cls)

        holder = gate.acquire(None, "interactive")
        for cls in ("batch", "interactive"):     # batch queues FIRST
            t = threading.Thread(target=waiter, args=(cls,))
            t.start()
            queued.append(t)
            deadline = time.time() + 5
            while time.time() < deadline \
                    and gate._depth_locked() < len(queued):
                time.sleep(0.01)
            assert gate._depth_locked() == len(queued)
        holder.release()
        for t in queued:
            t.join(5)
        assert order == ["interactive", "batch"]

    def test_unknown_priority_lands_interactive(self):
        gate = _gate()
        with gate.acquire(None, "nonsense"):
            assert gate.in_flight == 1


# --------------------------------------------------------------------- #
# Degradation ladder                                                    #
# --------------------------------------------------------------------- #

def _ts_query(m: str, span_s: int = 600) -> TSQuery:
    q = TSQuery(start=str(BASE), end=str(BASE + span_s),
                queries=[parse_m_subquery(m)])
    q.validate()
    return q


class TestDegradationLadder:
    def test_coarsens_downsample_first(self, monkeypatch):
        q = _ts_query("sum:10s-avg:adm.m")
        original_ms = q.queries[0].downsample_spec.interval_ms
        # fake cost: inversely proportional to the interval — fits once
        # coarsened x4
        monkeypatch.setattr(
            admission, "estimate_plan_cost_ms",
            lambda tsdb, tq: 4000.0 * original_ms
            / tq.queries[0].downsample_spec.interval_ms)
        note = admission.try_degrade(None, q, budget_ms=1000.0,
                                     queue_wait_ms=0.0)
        assert note == {"coarsenedIntervalFactor": 4,
                        "coarsenedIntervalMs": original_ms * 4}
        assert q.queries[0].downsample_spec.interval_ms == original_ms * 4
        # the string form (stats, duplicate detection, a re-validate)
        # stays in lockstep with the mutated spec
        assert q.queries[0].downsample == "%dms-avg" % (original_ms * 4)
        q.validate()                     # re-parse must NOT revert
        assert q.queries[0].downsample_spec.interval_ms == original_ms * 4

    def test_truncates_range_when_not_coarsenable(self, monkeypatch):
        q = _ts_query("sum:adm.m")               # no downsample to coarsen
        span = q.end_time - q.start_time
        monkeypatch.setattr(
            admission, "estimate_plan_cost_ms",
            lambda tsdb, tq: (tq.end_time - tq.start_time) / span * 2000.0)
        note = admission.try_degrade(None, q, budget_ms=1000.0,
                                     queue_wait_ms=0.0)
        assert note["truncatedKeepFraction"] == 0.5
        assert q.end_time - q.start_time == span // 2
        # the string form travels to fan-out peers: kept in lockstep
        assert q.start == str(q.start_time)

    def test_returns_none_when_nothing_fits(self, monkeypatch):
        q = _ts_query("sum:adm.m")
        monkeypatch.setattr(admission, "estimate_plan_cost_ms",
                            lambda tsdb, tq: 1e12)
        assert admission.try_degrade(None, q, budget_ms=1000.0,
                                     queue_wait_ms=0.0) is None


# --------------------------------------------------------------------- #
# End-to-end through RpcManager.handle_http                             #
# --------------------------------------------------------------------- #

def _manager(**cfg):
    # mesh pinned off: admission prices the single-device plan here,
    # and the suite's 8 virtual devices would turn grouped plans with
    # >= 8 series into mesh plans
    props = {"tsd.core.auto_create_metrics": True,
             "tsd.query.mesh.enable": "false"}
    props.update({k: str(v) for k, v in cfg.items()})
    tsdb = TSDB(Config(props))
    for k in range(20):
        tsdb.add_point("adm.m", BASE + k * 15, float(k), {"host": "a"})
    return tsdb, RpcManager(tsdb)


def ask(mgr, uri, headers=None):
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri,
                                    headers=headers or {}))
    body = q.response.body
    text = body.decode() if isinstance(body, (bytes, bytearray)) else body
    return q.response.status, json.loads(text), q.response.headers


QUERY_URI = "/api/query?start=%d&end=%d&m=sum:adm.m" % (BASE, BASE + 600)


class TestEndToEndAdmission:
    def test_full_queue_sheds_503_with_retry_after(self):
        tsdb, mgr = _manager(**{"tsd.query.admission.permits": "0",
                                "tsd.query.admission.queue_limit": "0"})
        status, payload, headers = ask(mgr, QUERY_URI)
        assert status == 503
        assert "Retry-After" in headers
        assert int(headers["Retry-After"]) >= 1
        assert "full" in payload["error"]["message"]

    def test_predicted_cost_sheds_when_degrade_denied(self, monkeypatch):
        tsdb, mgr = _manager(**{"tsd.query.timeout": "5000"})
        monkeypatch.setattr(admission, "estimate_plan_cost_ms",
                            lambda *_: 1e9)
        before = counter_value("tsd.query.admission.shed",
                               reason="predicted_cost")
        status, payload, headers = ask(mgr, QUERY_URI)
        assert status == 503
        assert "Retry-After" in headers
        assert "predicted cost" in payload["error"]["message"]
        assert counter_value("tsd.query.admission.shed",
                             reason="predicted_cost") == before + 1

    def test_degrade_allow_answers_200_partial(self, monkeypatch):
        tsdb, mgr = _manager(**{"tsd.query.degrade": "allow"})
        # predicted cost collapses once the ladder coarsens x4
        monkeypatch.setattr(
            admission, "estimate_plan_cost_ms",
            lambda tsdb_, tq: (1e9 if tq.queries[0].downsample_spec
                               .interval_ms < 40_000 else 1.0))
        before = counter_value("tsd.query.admission.degraded",
                               reason="predicted_cost")
        uri = ("/api/query?start=%d&end=%d&m=sum:10s-avg:adm.m"
               % (BASE, BASE + 600))
        status, payload, _ = ask(mgr, uri,
                                 headers={"x-tsdb-deadline-ms": "5000"})
        assert status == 200
        trailer = next((e for e in payload
                        if isinstance(e, dict) and e.get("partialResults")),
                       None)
        assert trailer is not None
        assert trailer["degraded"]["coarsenedIntervalFactor"] == 4
        series = [e for e in payload if isinstance(e, dict)
                  and "metric" in e]
        assert series and series[0]["dps"]
        assert counter_value("tsd.query.admission.degraded",
                             reason="predicted_cost") == before + 1

    def test_admitted_query_unaffected(self):
        tsdb, mgr = _manager()
        status, payload, headers = ask(
            mgr, QUERY_URI, headers={"x-tsdb-deadline-ms": "60000"})
        assert status == 200
        assert "Retry-After" not in headers
        assert not any(isinstance(e, dict) and e.get("partialResults")
                       for e in payload)

    def test_mint_deadline_takes_min_of_config_and_header(self):
        tsdb, mgr = _manager(**{"tsd.query.timeout": "10000"})
        req = HttpRequest(method="GET", uri=QUERY_URI,
                          headers={"x-tsdb-deadline-ms": "500"})
        assert mgr._mint_deadline(req).timeout_ms == 500
        req = HttpRequest(method="GET", uri=QUERY_URI, headers={})
        assert mgr._mint_deadline(req).timeout_ms == 10000
        tsdb2, mgr2 = _manager()                 # tsd.query.timeout = 0
        req = HttpRequest(method="GET", uri=QUERY_URI,
                          headers={"x-tsdb-deadline-ms": "700"})
        assert mgr2._mint_deadline(req).timeout_ms == 700
        req = HttpRequest(method="GET", uri=QUERY_URI,
                          headers={"x-tsdb-deadline-ms": "garbage"})
        assert not mgr2._mint_deadline(req).bounded

    def test_fanout_subrequest_sheds_instead_of_degrading(self,
                                                          monkeypatch):
        """A peer's raw-extraction sub-request (X-TSDB-Cluster header)
        must never degrade — the coordinator merges raw points
        verbatim and would drop the annotation, so a peer-side
        truncation becomes an unmarked wrong answer.  It sheds; the
        coordinator's own partial_results machinery marks the loss."""
        tsdb, mgr = _manager(**{"tsd.query.degrade": "allow"})
        monkeypatch.setattr(admission, "estimate_plan_cost_ms",
                            lambda *_: 1e9)
        uri = ("/api/query?start=%d&end=%d&m=sum:10s-avg:adm.m"
               % (BASE, BASE + 600))
        status, payload, headers = ask(
            mgr, uri, headers={"x-tsdb-deadline-ms": "5000",
                               "x-tsdb-cluster": "fanout"})
        assert status == 503
        assert "Retry-After" in headers

    def test_mint_deadline_rejects_non_finite_header(self):
        """'inf'/'1e309' parse to float inf — a bounded-looking
        deadline with an infinite remainder would overflow the peer
        header int; it must mint as absent instead."""
        tsdb, mgr = _manager()
        for bad in ("inf", "Infinity", "1e309", "nan", "-inf"):
            req = HttpRequest(method="GET", uri=QUERY_URI,
                              headers={"x-tsdb-deadline-ms": bad})
            assert not mgr._mint_deadline(req).bounded, bad

    def test_graph_route_is_gated_too(self):
        """/q dispatches the same device work as /api/query — the gate
        sheds it identically."""
        tsdb, mgr = _manager(**{"tsd.query.admission.permits": "0",
                                "tsd.query.admission.queue_limit": "0"})
        status, payload, headers = ask(
            mgr, "/q?start=%d&end=%d&m=sum:adm.m&json" % (BASE, BASE + 600))
        assert status == 503
        assert "Retry-After" in headers

    def test_ambient_deadline_cleared_after_request(self):
        tsdb, mgr = _manager()
        ask(mgr, QUERY_URI, headers={"x-tsdb-deadline-ms": "60000"})
        assert limits.active_deadline() is None


# --------------------------------------------------------------------- #
# Deadline propagation to fan-out peers                                 #
# --------------------------------------------------------------------- #

class TestDeadlinePropagation:
    @pytest.fixture()
    def peer(self):
        from tests.fault_fixtures import FaultyPeer, series_payload
        p = FaultyPeer(series_payload(
            "adm.m", {"host": "remote"},
            {str((BASE + 5) * 1000): 11.0}))
        yield p
        p.close()

    def test_remainder_forwarded_and_slow_peer_aborted(self, peer):
        """The coordinator forwards its remaining ms via
        x-tsdb-deadline-ms and the clamped fetch timeout ends a
        slow-body peer WITHIN the remainder.  Without the propagation
        this test fails on elapsed time: the cluster fetch budget below
        is 30s and the peer needs > 30s to finish its dribble."""
        from tests import fault_fixtures as ff
        peer.mode = ff.SLOW_BODY
        peer.slow_body_step_s = 5.0
        tsdb, mgr = _manager(**{
            "tsd.network.cluster.peers": peer.address,
            "tsd.network.cluster.timeout_ms": "30000",
            "tsd.network.cluster.retry.max_attempts": "1",
        })
        t0 = time.monotonic()
        status, payload, _ = ask(mgr, QUERY_URI,
                                 headers={"x-tsdb-deadline-ms": "1200"})
        elapsed = time.monotonic() - t0
        assert status >= 500                     # error mode: fail fast
        assert elapsed < 8.0, elapsed            # aborted ~at the remainder
        assert peer.requests >= 1
        forwarded = peer.seen_headers[0].get("x-tsdb-deadline-ms")
        assert forwarded is not None
        assert 0 < int(forwarded) <= 1200

    def test_peer_receiving_header_aborts_its_own_work(self):
        """The receiving side of the propagation: a TSD handed an
        already-tiny x-tsdb-deadline-ms refuses/aborts instead of doing
        the work — its minted deadline is checked at admission."""
        tsdb, mgr = _manager()
        status, payload, _ = ask(mgr, QUERY_URI,
                                 headers={"x-tsdb-deadline-ms": "1"})
        assert status in (413, 503)

    def test_expired_coordinator_never_contacts_peer(self, peer):
        """A fan-out whose deadline is already spent must not even
        connect (tsd/cluster.py checks before the request goes out)."""
        tsdb, mgr = _manager(**{
            "tsd.network.cluster.peers": peer.address,
            "tsd.network.cluster.retry.max_attempts": "1",
        })
        d = Deadline(0.5)                        # all but expired
        time.sleep(0.01)
        limits.activate_deadline(d)
        try:
            from opentsdb_tpu.tsd.cluster import run_clustered
            q = _ts_query("sum:adm.m")
            with pytest.raises(QueryException):
                run_clustered(tsdb, q)
        finally:
            limits.deactivate_deadline()
        assert peer.requests == 0

    def test_cancelled_unbounded_deadline_stops_fanout(self, peer):
        """The default config mints an UNBOUNDED deadline
        (tsd.query.timeout=0) — it is still a cancellation token, and
        a flipped token must stop peer fetches before they connect."""
        tsdb, mgr = _manager(**{
            "tsd.network.cluster.peers": peer.address,
            "tsd.network.cluster.retry.max_attempts": "1",
        })
        d = Deadline(0)                          # unbounded
        d.cancel("client disconnected")
        limits.activate_deadline(d)
        try:
            from opentsdb_tpu.tsd.cluster import run_clustered
            q = _ts_query("sum:adm.m")
            with pytest.raises(QueryCancelledException):
                run_clustered(tsdb, q)
        finally:
            limits.deactivate_deadline()
        assert peer.requests == 0


# --------------------------------------------------------------------- #
# Live server: disconnect cancellation + bounded drain                  #
# --------------------------------------------------------------------- #

def _spawn_server(cfg: dict):
    props = {"tsd.core.auto_create_metrics": True}
    props.update(cfg)
    tsdb = TSDB(Config(props))
    for k in range(20):
        tsdb.add_point("adm.m", BASE + k * 15, float(k), {"host": "a"})
    from opentsdb_tpu.tsd.server import TSDServer
    srv = TSDServer(tsdb, port=0, bind="127.0.0.1", worker_threads=4)
    started = threading.Event()
    stopped = threading.Event()
    holder = {}

    def run():
        async def main():
            await srv.start()
            holder["port"] = srv._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await srv.serve_forever()
            # set INSIDE the loop: asyncio.run's own teardown joins the
            # default executor, which a wedged-handler test would wait
            # on for the full wedge — stop() itself is what's bounded
            stopped.set()
        asyncio.run(main())
        stopped.set()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    srv.test_port = holder["port"]
    return srv, holder, stopped


def _http_get(port, path, timeout=30):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


class TestClientDisconnect:
    def test_disconnected_query_releases_without_dispatching(self):
        """Client B queues behind A's held permit, then hangs up: B's
        token flips, B leaves the queue WITHOUT being admitted, and
        only A dispatches."""
        faults.install([{"site": "rpc.slow_handler", "kind": "latency",
                         "ms": 2500, "times": 1}])
        srv, holder, stopped = _spawn_server({
            "tsd.query.admission.permits": "1",
            "tsd.query.admission.max_wait_ms": "30000",
        })
        gate = admission.gate_for(srv.tsdb)
        cancelled_before = counter_value("tsd.query.admission.cancelled",
                                         reason="client_disconnect")
        path = QUERY_URI
        a_result = {}

        def client_a():
            a_result["resp"] = _http_get(srv.test_port, path)

        try:
            ta = threading.Thread(target=client_a)
            ta.start()
            # wait until A holds the permit (inside its stall)
            deadline = time.time() + 5
            while time.time() < deadline and gate.in_flight < 1:
                time.sleep(0.01)
            assert gate.in_flight == 1
            # B: send the request, then hang up while queued
            sock = socket.create_connection(
                ("127.0.0.1", srv.test_port), timeout=10)
            sock.sendall(("GET %s HTTP/1.1\r\nHost: x\r\n\r\n"
                          % path).encode())
            deadline = time.time() + 5
            while time.time() < deadline and not gate._depth_locked():
                time.sleep(0.01)
            assert gate._depth_locked() == 1
            sock.close()                         # the hang-up
            deadline = time.time() + 5
            while time.time() < deadline and counter_value(
                    "tsd.query.admission.cancelled",
                    reason="client_disconnect") <= cancelled_before:
                time.sleep(0.02)
            assert counter_value(
                "tsd.query.admission.cancelled",
                reason="client_disconnect") > cancelled_before
            ta.join(15)
            assert a_result["resp"][0] == 200    # A unaffected
            # B never dispatched: one admission total (A's)
            assert gate.admitted == 1
            assert gate.in_flight == 0
        finally:
            faults.clear()
            holder["loop"].call_soon_threadsafe(srv._shutdown_event.set)
            stopped.wait(15)


class TestBoundedDrain:
    def test_stop_force_cancels_at_drain_timeout(self, monkeypatch):
        """One wedged responder thread must not block shutdown forever:
        at tsd.network.drain_timeout_ms every in-flight token flips
        (the cooperative queued query unwinds), and teardown proceeds
        after the short post-cancel grace even though the wedged
        handler never looks at its token."""
        from opentsdb_tpu.tsd import server as server_mod
        monkeypatch.setattr(server_mod, "POST_CANCEL_GRACE_S", 1.0)
        # A = deliberately stuck (non-cooperative sleep inside its
        # permit); B = cooperative, parked in the admission queue
        faults.install([{"site": "rpc.slow_handler", "kind": "latency",
                         "ms": 9000, "times": 1}])
        srv, holder, stopped = _spawn_server({
            "tsd.query.admission.permits": "1",
            "tsd.query.admission.max_wait_ms": "0",
            "tsd.network.drain_timeout_ms": "300",
        })
        gate = admission.gate_for(srv.tsdb)
        drain_before = counter_value("tsd.query.admission.cancelled",
                                     reason="drain_timeout")
        results = []

        def client(tag):
            try:
                results.append((tag, _http_get(srv.test_port, QUERY_URI)))
            except OSError:
                results.append((tag, None))

        try:
            ta = threading.Thread(target=client, args=("a",), daemon=True)
            ta.start()
            deadline = time.time() + 5
            while time.time() < deadline and gate.in_flight < 1:
                time.sleep(0.01)
            tb = threading.Thread(target=client, args=("b",), daemon=True)
            tb.start()
            deadline = time.time() + 5
            while time.time() < deadline and not gate._depth_locked():
                time.sleep(0.01)
            assert gate._depth_locked() == 1
            t0 = time.monotonic()
            holder["loop"].call_soon_threadsafe(srv._shutdown_event.set)
            assert stopped.wait(10), "stop() did not come back"
            stop_s = time.monotonic() - t0
            # bounded: 0.3s drain + 1s post-cancel grace + the <= 5s
            # reply-flush wait + teardown slack — well under the 9s
            # wedge (the old behavior: stop waits the whole wedge out)
            assert stop_s < 7.5, stop_s
            assert counter_value(
                "tsd.query.admission.cancelled",
                reason="drain_timeout") > drain_before
        finally:
            faults.clear()

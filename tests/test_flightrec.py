"""Flight recorder + health engine surface tests (ISSUE 12).

Pins the documented /api/diag, /api/diag/slow, /api/diag/health shapes
on a default-config daemon, the ring's bounded/incremental semantics,
tenant clamping + per-tenant accounting, slow-query capture, the
shutdown dump, health verdict transitions, and — the continuity
contract — ONE trace id carried through the admission queue, the
degradation ladder, the flight-recorder events, and the peer_fetch
child of a cluster fan-out.

Every TSDB here pins tsd.query.mesh.enable=false: the plan events under
test are the single-device routes'.
"""

from __future__ import annotations

import json
import os
import threading
import time

import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs.flightrec import FlightRecorder, clamp_tenant
from opentsdb_tpu.tsd import admission
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400


def _manager(**cfg):
    props = {"tsd.core.auto_create_metrics": True,
             "tsd.query.mesh.enable": "false"}
    props.update({k: str(v) for k, v in cfg.items()})
    tsdb = TSDB(Config(props))
    for k in range(20):
        tsdb.add_point("fr.m", BASE + k * 15, float(k), {"host": "a"})
    return tsdb, RpcManager(tsdb)


def ask(mgr, uri, headers=None):
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri,
                                    headers=headers or {}),
                        remote="127.0.0.1:9")
    body = q.response.body
    text = body.decode() if isinstance(body, (bytes, bytearray)) else body
    return q.response.status, json.loads(text), q.response.headers


QUERY_URI = ("/api/query?start=%d&end=%d&m=sum:30s-avg:fr.m"
             % (BASE, BASE + 600))


def find_spans(tree: dict, name: str) -> list[dict]:
    out = [tree] if tree.get("name") == name else []
    for child in tree.get("spans", []):
        out.extend(find_spans(child, name))
    return out


# --------------------------------------------------------------------- #
# The ring                                                              #
# --------------------------------------------------------------------- #

class TestRing:
    def test_bounded_with_monotonic_seqs(self):
        rec = FlightRecorder(Config({"tsd.diag.ring_size": "32"}))
        for i in range(100):
            rec.record("plan", i=i)
        events = rec.events()
        assert len(events) == 32
        assert rec.latest_seq() == 100
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs) and seqs[-1] == 100
        assert seqs[0] == 69          # oldest 68 dropped

    def test_since_is_incremental(self):
        rec = FlightRecorder(Config({}))
        for i in range(10):
            rec.record("plan", i=i)
        tail = rec.events(since=7)
        assert [e["seq"] for e in tail] == [8, 9, 10]
        assert rec.events(since=rec.latest_seq()) == []

    def test_ambient_trace_id_is_stamped(self):
        from opentsdb_tpu.obs import trace as obs_trace
        rec = FlightRecorder(Config({}))
        tr = obs_trace.Trace("t", trace_id="ab" * 8)
        obs_trace.activate(tr)
        try:
            rec.record("plan")
        finally:
            obs_trace.deactivate()
        rec.record("plan")           # untraced: no id
        traced, untraced = rec.events()
        assert traced["traceId"] == "ab" * 8
        assert "traceId" not in untraced

    def test_compile_subscription_pairs_with_shutdown(self):
        from opentsdb_tpu.obs import jaxprof
        rec = FlightRecorder(Config({}))
        rec.start()
        try:
            assert rec._on_compile in jaxprof.compile_capture._subscribers
            jaxprof.compile_capture._emit("jit__fr_test_kernel")
            assert any(e["kind"] == "compile"
                       and e["kernel"] == "jit__fr_test_kernel"
                       for e in rec.events())
        finally:
            rec.shutdown()
        assert rec._on_compile not in jaxprof.compile_capture._subscribers


# --------------------------------------------------------------------- #
# Tenant clamping                                                       #
# --------------------------------------------------------------------- #

class TestTenantClamp:
    def test_registered_kept_unregistered_hashed(self):
        cfg = Config({"tsd.diag.tenants": "acme, globex",
                      "tsd.diag.tenant_buckets": "8"})
        assert clamp_tenant(cfg, "acme") == "acme"
        assert clamp_tenant(cfg, "globex") == "globex"
        hashed = clamp_tenant(cfg, "evil-" + "x" * 500)
        assert hashed.startswith("tenant-")
        # stable: the same stranger hashes to the same bucket
        assert clamp_tenant(cfg, "evil-" + "x" * 500) == hashed
        assert clamp_tenant(cfg, None) == "default"
        assert clamp_tenant(cfg, "   ") == "default"

    def test_zero_buckets_collapse_to_other(self):
        cfg = Config({"tsd.diag.tenant_buckets": "0"})
        assert clamp_tenant(cfg, "anybody") == "other"

    def test_cardinality_is_bounded(self):
        cfg = Config({"tsd.diag.tenant_buckets": "4"})
        labels = {clamp_tenant(cfg, "t%d" % i) for i in range(100)}
        assert len(labels) <= 4

    def test_demand_counter_and_latency_label(self):
        from opentsdb_tpu.obs.registry import REGISTRY
        tsdb, mgr = _manager()
        fam = REGISTRY.counter("tsd.query.tenant.demand")
        cell = fam.labels(tenant="acme")
        # "acme" is unregistered here -> hashes; register it instead
        tsdb.config.override_config("tsd.diag.tenants", "acme")
        before = cell.get()
        status, _, _ = ask(mgr, QUERY_URI,
                           headers={"x-tsdb-tenant": "acme"})
        assert status == 200
        assert cell.get() == before + 1
        hist = REGISTRY.histogram("tsd.query.latency_ms")
        assert any(dict(labels).get("tenant") == "acme"
                   for labels, _ in hist.children())


# --------------------------------------------------------------------- #
# /api/diag* endpoint shapes (default config)                           #
# --------------------------------------------------------------------- #

class TestEndpoints:
    def test_diag_shape_and_incremental_poll(self):
        tsdb, mgr = _manager()
        status, _, _ = ask(mgr, QUERY_URI)
        assert status == 200
        status, payload, _ = ask(mgr, "/api/diag")
        assert status == 200
        assert set(payload) == {"seq", "ringSize", "events", "tenants",
                                "dropped", "droppedTotal", "device"}
        # where the daemon computes (obs/jaxprof.py device_report): what
        # chip_smoke.py reads to refuse a silent CPU
        device = payload["device"]
        assert set(device) == {"platform", "kind", "count", "memory"}
        assert device["platform"] == "cpu" and device["kind"]
        assert device["count"] == len(device["memory"]) >= 1
        for mem in device["memory"]:
            assert set(mem) == {"id", "bytesInUse", "peakBytesInUse",
                                "bytesLimit"}
        assert payload["seq"] >= 1
        kinds = {e["kind"] for e in payload["events"]}
        assert {"admission", "plan"} <= kinds
        for e in payload["events"]:
            assert isinstance(e["seq"], int)
            assert isinstance(e["tMs"], int)
        status, tail, _ = ask(mgr, "/api/diag?since=%d" % payload["seq"])
        assert status == 200 and tail["events"] == []
        # a trace-scoped fetch is one request's evidence, not the daemon's
        status, scoped, _ = ask(mgr, "/api/diag?trace_id=nonesuch")
        assert status == 200 and "device" not in scoped
        status, _, _ = ask(mgr, "/api/diag?since=bogus")
        assert status == 400

    def test_slow_shape(self):
        tsdb, mgr = _manager(**{"tsd.diag.slow_ms": "1"})
        status, _, _ = ask(mgr, QUERY_URI)
        assert status == 200
        status, payload, _ = ask(mgr, "/api/diag/slow")
        assert status == 200
        assert payload["queries"], "a >=1ms query must be captured"
        cap = payload["queries"][0]
        assert cap["elapsedMs"] >= 1
        assert cap["status"] == 200
        assert cap["tenant"] == "default"
        assert "trace" in cap and "traceId" in cap
        # the retained ring slice shares the capture's trace id
        assert all(e["traceId"] == cap["traceId"] for e in cap["events"])
        assert {"admission", "plan"} <= {e["kind"] for e in cap["events"]}
        assert "query" in cap

    def test_health_shape(self):
        tsdb, mgr = _manager()
        status, payload, _ = ask(mgr, "/api/diag/health")
        assert status == 200
        assert set(payload) == {"overall", "subsystems", "passes",
                                "evaluatedMs"}
        assert payload["overall"] == "ok"
        assert set(payload["subsystems"]) == {
            "admission", "compile", "agg_cache", "spill",
            "cluster", "tenant", "replication", "latency", "diag"}
        for verdict in payload["subsystems"].values():
            assert verdict["level"] in ("ok", "degraded", "failing")
            assert verdict["detail"]

    def test_disabled_diag_404s(self):
        tsdb, mgr = _manager(**{"tsd.diag.enable": "false",
                                "tsd.health.enable": "false"})
        assert tsdb.flightrec is None and tsdb.health is None
        for uri in ("/api/diag", "/api/diag/slow", "/api/diag/health"):
            status, _, _ = ask(mgr, uri)
            assert status == 404, uri

    def test_unknown_subpath_404s(self):
        tsdb, mgr = _manager()
        status, _, _ = ask(mgr, "/api/diag/nonsense")
        assert status == 404


# --------------------------------------------------------------------- #
# Slow capture policy                                                   #
# --------------------------------------------------------------------- #

class TestSlowCapture:
    def test_rolling_quantile_arm(self):
        from opentsdb_tpu.obs.flightrec import SLOW_MIN_SAMPLES
        rec = FlightRecorder(Config({"tsd.diag.slow_ms": "0",
                                     "tsd.diag.slow_quantile": "0.9"}))
        for _ in range(SLOW_MIN_SAMPLES):
            assert not rec.maybe_capture_slow(None, 1.0, 200, None)
        # far above the rolling p90 of ~1ms
        assert rec.maybe_capture_slow(None, 500.0, 200, None)
        assert rec.slow_queries()[0]["elapsedMs"] == 500.0

    def test_absolute_arm_and_bounded_store(self):
        rec = FlightRecorder(Config({"tsd.diag.slow_ms": "100",
                                     "tsd.diag.slow_quantile": "0",
                                     "tsd.diag.slow_keep": "3"}))
        assert not rec.maybe_capture_slow(None, 99.0, 200, None)
        for i in range(5):
            assert rec.maybe_capture_slow(None, 100.0 + i, 200, None)
        kept = rec.slow_queries()
        assert len(kept) == 3
        # newest first, oldest two dropped
        assert [c["elapsedMs"] for c in kept] == [104.0, 103.0, 102.0]

    def test_error_statuses_captured_too(self, monkeypatch):
        """A query that FAILS mid-serving is still capture-eligible —
        an anomalously-slow 413/500 is exactly the evidence a
        post-mortem wants (admission-refused queries never reach the
        serving path and are covered by admission/deadline events
        instead)."""
        from opentsdb_tpu.query.limits import QueryException
        from opentsdb_tpu.tsd import cluster

        def boom(*a, **kw):
            time.sleep(0.01)        # past the 1ms capture threshold
            raise QueryException("synthetic mid-serving failure",
                                 status=413)
        tsdb, mgr = _manager(**{"tsd.diag.slow_ms": "1"})
        monkeypatch.setattr(cluster, "serve_query", boom)
        status, _, _ = ask(mgr, QUERY_URI)
        assert status == 413
        _, payload, _ = ask(mgr, "/api/diag/slow")
        assert any(c["status"] == 413 for c in payload["queries"])


# --------------------------------------------------------------------- #
# Event producers                                                       #
# --------------------------------------------------------------------- #

class TestProducers:
    def test_deadline_expiry_event(self, monkeypatch):
        """A cooperative check site raising mid-serving (the planner's
        budget checks all route through Deadline.check) lands a
        `deadline` event in the ring."""
        from opentsdb_tpu.query import limits
        from opentsdb_tpu.tsd import cluster

        def slow_serve(*a, **kw):
            time.sleep(1.0)
            limits.active_deadline().check()
        tsdb, mgr = _manager()
        monkeypatch.setattr(cluster, "serve_query", slow_serve)
        status, _, _ = ask(mgr, QUERY_URI,
                           headers={"x-tsdb-deadline-ms": "800"})
        assert status == 413
        events = tsdb.flightrec.events()
        assert any(e["kind"] == "deadline"
                   and e["outcome"] == "expired" for e in events)

    def test_breaker_transition_events(self):
        from opentsdb_tpu.tsd import cluster
        tsdb, _ = _manager(**{
            "tsd.network.cluster.breaker.threshold": "2"})
        breaker = cluster._state(tsdb).breaker("10.9.9.9:4242")
        breaker.record_failure()
        breaker.record_failure()          # -> open
        breaker.record_success()          # -> closed
        transitions = [e for e in tsdb.flightrec.events()
                       if e["kind"] == "breaker"]
        assert [(e["before"], e["state"]) for e in transitions] == [
            ("closed", "open"), ("open", "closed")]
        assert all(e["peer"] == "10.9.9.9:4242" for e in transitions)

    def test_shed_event(self):
        tsdb, mgr = _manager(**{"tsd.query.admission.permits": "0",
                                "tsd.query.admission.queue_limit": "0"})
        status, _, _ = ask(mgr, QUERY_URI)
        assert status == 503
        sheds = [e for e in tsdb.flightrec.events()
                 if e["kind"] == "admission"
                 and e["decision"] == "shed"]
        assert sheds and sheds[0]["tenant"] == "default"

    def test_plan_event_fields(self):
        tsdb, mgr = _manager()
        ask(mgr, QUERY_URI)
        plans = [e for e in tsdb.flightrec.events()
                 if e["kind"] == "plan"]
        assert plans
        plan = plans[-1]
        assert plan["metric"] == "fr.m"
        assert plan["path"] in ("resident", "host_lane", "streamed",
                                "agg_rewrite", "batched")
        assert plan["series"] >= 1 and plan["windows"] >= 1


# --------------------------------------------------------------------- #
# Health engine                                                         #
# --------------------------------------------------------------------- #

class TestHealthEngine:
    def test_shed_burn_degrades_then_recovers(self):
        tsdb, mgr = _manager()
        engine = tsdb.health
        engine.evaluate()                      # baseline pass
        gate = admission.gate_for(tsdb)
        with gate._lock:
            gate.shed += 1000                  # a burst in this window
        verdicts = engine.evaluate()
        assert verdicts["admission"]["level"] in ("degraded", "failing")
        # the verdict CHANGE lands in the flight recorder
        assert any(e["kind"] == "health" and e["subsystem"] == "admission"
                   for e in tsdb.flightrec.events())
        status, payload, _ = ask(mgr, "/api/diag/health")
        assert payload["overall"] != "ok"
        # next window has no sheds: healed
        verdicts = engine.evaluate()
        assert verdicts["admission"]["level"] == "ok"

    def test_breaker_flap_degrades(self):
        from opentsdb_tpu.tsd import cluster
        tsdb, _ = _manager(**{
            "tsd.network.cluster.breaker.threshold": "1",
            "tsd.health.breaker_flap": "2"})
        engine = tsdb.health
        engine.evaluate()
        breaker = cluster._state(tsdb).breaker("10.8.8.8:4242")
        for _ in range(4):                     # 4 open transitions
            breaker.record_failure()           # closed -> open
            breaker.record_success()           # open -> closed
        verdicts = engine.evaluate()
        assert verdicts["cluster"]["level"] in ("degraded", "failing")

    def test_gauges_exported(self):
        from opentsdb_tpu.obs.registry import REGISTRY
        tsdb, _ = _manager()
        tsdb.health.evaluate()
        fam = REGISTRY.gauge("tsd.health.status")
        subsystems = {dict(labels).get("subsystem")
                      for labels, _ in fam.children()}
        assert set(tsdb.health.SUBSYSTEMS) <= subsystems

    def test_maintenance_tick_cadence(self):
        tsdb, _ = _manager(**{"tsd.health.interval": "5"})
        engine = tsdb.health
        assert not engine.tick(1000.0)         # arms the cadence
        assert not engine.tick(1004.0)
        assert engine.tick(1006.0)
        assert engine.passes == 1
        assert not engine.tick(1007.0)
        assert engine.tick(1011.5)

    def test_self_report_ingests_health_and_demand(self):
        tsdb, mgr = _manager(**{"tsd.stats.interval": "60"})
        ask(mgr, QUERY_URI)                    # mint demand
        tsdb.health.evaluate()
        from opentsdb_tpu.obs.selfreport import self_report
        written = self_report(tsdb)
        assert written > 0
        assert tsdb.metrics.get_id("tsd.health.status")
        assert tsdb.metrics.get_id("tsd.diag.tenant.demand")


# --------------------------------------------------------------------- #
# Shutdown dump                                                         #
# --------------------------------------------------------------------- #

class TestShutdownDump:
    def test_dump_written_once_at_shutdown(self, tmp_path):
        dump = str(tmp_path / "blackbox.json")
        tsdb, mgr = _manager(**{"tsd.diag.dump_path": dump})
        ask(mgr, QUERY_URI)
        tsdb.shutdown()
        assert os.path.exists(dump)
        with open(dump) as fh:
            payload = json.load(fh)
        assert set(payload) >= {"dumpedMs", "seq", "events",
                                "slowQueries"}
        kinds = [e["kind"] for e in payload["events"]]
        assert "shutdown" in kinds and "plan" in kinds
        mtime = os.path.getmtime(dump)
        tsdb.shutdown()                        # idempotent: no rewrite
        assert os.path.getmtime(dump) == mtime


# --------------------------------------------------------------------- #
# Trace-id continuity: queue -> ladder -> fan-out, one id everywhere    #
# --------------------------------------------------------------------- #

class TestTraceContinuity:
    @pytest.fixture()
    def peer(self):
        from tests.fault_fixtures import FaultyPeer, series_payload
        p = FaultyPeer(series_payload(
            "fr.m", {"host": "remote"},
            {str((BASE + 5) * 1000): 11.0}))
        yield p
        p.close()

    def test_one_trace_id_through_queue_ladder_and_peer(
            self, peer, monkeypatch):
        """A query that WAITS in the admission queue, degrades via the
        ladder, and fans out to a peer carries ONE trace id through
        the admission span, the flight-recorder events, and the
        peer_fetch child (mesh off — including the clustered scratch
        store's runner — so the plan events are the single-device
        routes' on the suite's 8 virtual devices)."""
        monkeypatch.setattr(TSDB, "query_mesh", lambda self: None)
        tsdb, mgr = _manager(**{
            "tsd.network.cluster.peers": peer.address,
            "tsd.network.cluster.partial_results": "allow",
            "tsd.query.degrade": "allow",
            "tsd.query.admission.permits": "1",
        })
        # ladder trigger: predicted cost collapses once coarsened x4
        monkeypatch.setattr(
            admission, "estimate_plan_cost_ms",
            lambda tsdb_, tq: (1e9 if tq.queries[0].downsample_spec
                               .interval_ms < 40_000 else 1.0))
        trace_id = "f00d" * 4
        uri = ("/api/query?start=%d&end=%d&m=sum:10s-avg:fr.m"
               "&show_stats" % (BASE, BASE + 600))
        headers = {"x-tsdb-trace-id": trace_id,
                   "x-tsdb-deadline-ms": "30000",
                   "x-tsdb-tenant": "team-red"}
        gate = admission.gate_for(tsdb)
        blocker = gate.acquire(None, "interactive")  # hold the permit
        result: dict = {}

        def run():
            result["status"], result["payload"], _ = ask(mgr, uri,
                                                         headers=headers)
        t = threading.Thread(target=run)
        t.start()
        time.sleep(0.4)                        # the query queues
        blocker.release()
        t.join(timeout=30)
        assert not t.is_alive()
        assert result["status"] == 200
        payload = result["payload"]
        trailer = next(e for e in payload if isinstance(e, dict)
                       and e.get("partialResults"))
        assert trailer["degraded"]["coarsenedIntervalFactor"] >= 2
        # 1. the inline span tree IS this trace id, and its admission
        #    span shows the queue wait + the ladder decision
        summary = next(e for e in payload if isinstance(e, dict)
                       and "statsSummary" in e)["statsSummary"]
        tree = summary["trace"]
        assert tree["traceId"] == trace_id
        adm = find_spans(tree, "admission")
        assert adm and adm[0]["tags"]["decision"] == "degraded"
        assert adm[0]["tags"]["wait_ms"] > 100
        # 2. the flight-recorder events carry the SAME id
        mine = tsdb.flightrec.events_for_trace(trace_id)
        kinds = {e["kind"] for e in mine}
        assert {"admission", "plan"} <= kinds
        adm_event = next(e for e in mine if e["kind"] == "admission")
        assert adm_event["decision"] == "degraded"
        assert adm_event["waitMs"] > 100
        # 3. the peer saw the SAME id — and the client's RAW tenant
        #    header — on its fan-out sub-request, and the tree has the
        #    peer_fetch child
        assert peer.requests >= 1
        assert peer.seen_headers[0].get("x-tsdb-trace-id") == trace_id
        assert peer.seen_headers[0].get("x-tsdb-tenant") == "team-red"
        assert find_spans(tree, "peer_fetch")

"""Query EXPLAIN & plan provenance (ISSUE 13).

The contract under test is STRUCTURAL parity: /api/query/explain
answers from the same ``plan_decision()`` the executor dispatches on
(query/plandecision.py), so for every planner routing path the
explained path + plan fingerprint must equal what the flight-recorder
``plan`` event records when the same query then executes — rollup
lane (plain and striped/host-fold), agg rewrite (cold populate AND
warm reuse), tiled, streamed, resident, host-lane, plus the
degradation preview and the structured-413 refusal.

Also pinned: explain performs ZERO device dispatches and ZERO
admission-permit acquisitions (every dispatch gateway booby-trapped,
gate counters asserted flat), the dry-run consult arms perturb no
subsystem state (repeat counts, lane demand, cache stats), the
what-if grammar, the /api/diag ``?trace_id=`` resolution satellite,
and the PLAN_CORPUS.json byte-pin (subprocess — routing changes must
surface as reviewed corpus diffs).

Every TSDB here pins tsd.query.mesh.enable=false: PLAN_CORPUS.json pins
the single-device routes, and the suite's 8 virtual devices would
otherwise turn every >= 8-series profile into a mesh plan.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.tsd import admission
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_356_998_400


def _manager(**cfg):
    props = {"tsd.core.auto_create_metrics": True,
             "tsd.query.mesh.enable": "false",
             "tsd.rollup.interval": "0",
             "tsd.stats.interval": "0",
             # this file pins the PRE-batching routing matrix; the
             # batched arm's parity + corpus entries live in
             # tests/test_batcher.py
             "tsd.query.batch.enable": "false"}
    props.update({k: str(v) for k, v in cfg.items()})
    tsdb = TSDB(Config(props))
    return tsdb, RpcManager(tsdb)


def feed(tsdb, metric, series=2, points=200, cadence_s=15):
    for h in range(series):
        tags = {"host": "h%d" % h}
        for k in range(points):
            tsdb.add_point(metric, BASE + k * cadence_s,
                           float((k * 7 + h) % 101), tags)


def feed_batch(tsdb, metric, series, points, cadence_s):
    """Columnar feed for the big shapes (per-point add is the slow
    part of these tests, not the queries)."""
    for h in range(series):
        key = tsdb._series_key(metric, {"host": "h%d" % h}, create=True)
        ts = (BASE + np.arange(points, dtype=np.int64) * cadence_s) * 1000
        vals = (np.arange(points, dtype=np.int64) * 7 + h) % 101
        tsdb.store.add_batch(key, ts, vals.astype(np.float64), False)


def ask(mgr, uri, method="GET", body=None, headers=None):
    req = HttpRequest(method=method, uri=uri, headers=headers or {},
                      body=body)
    q = mgr.handle_http(req, remote="127.0.0.1:9")
    raw = q.response.body
    text = raw.decode() if isinstance(raw, (bytes, bytearray)) else raw
    return q.response.status, json.loads(text), q.response.headers


def explain_seg(mgr, uri):
    status, rep, _ = ask(mgr, uri)
    assert status == 200, rep
    return rep, rep["subQueries"][0]["segments"][0]


def last_plan_event(tsdb):
    evs = [e for e in tsdb.flightrec.events() if e["kind"] == "plan"]
    assert evs, "no plan event recorded"
    return evs[-1]


def _uris(m, start, end):
    q = "start=%d&end=%d&m=%s" % (start, end, m)
    return "/api/query/explain?" + q, "/api/query?" + q


def assert_parity(tsdb, mgr, m, start, end, expect_path):
    """Explain first, execute second, compare path + fingerprint
    against the flight-recorder plan event."""
    exp_uri, run_uri = _uris(m, start, end)
    _rep, seg = explain_seg(mgr, exp_uri)
    assert seg["path"] == expect_path, seg
    status, _payload, _ = ask(mgr, run_uri)
    assert status == 200
    event = last_plan_event(tsdb)
    assert event["path"] == seg["path"] == expect_path
    assert event["fingerprint"] == seg["fingerprint"], (
        "explain-vs-actual fingerprint drift:\nexplained %s\nexecuted "
        "%s\nprovenance %s" % (seg["fingerprint"], event["fingerprint"],
                               seg["provenance"]))
    return seg, event


# --------------------------------------------------------------------- #
# Parity matrix: one test per routing path                              #
# --------------------------------------------------------------------- #

class TestParityMatrix:
    def test_resident(self):
        tsdb, mgr = _manager()
        feed(tsdb, "ex.res", series=2, points=300)
        try:
            seg, _ = assert_parity(tsdb, mgr, "sum:30s-avg:ex.res",
                                   BASE, BASE + 300 * 15, "resident")
            # device cache predicted warm (inline build) both sides
            assert seg["provenance"]["deviceCache"] is True
            assert seg["costmodel"]["scan"]["candidates"]
        finally:
            tsdb.shutdown()

    def test_host_lane(self):
        tsdb, mgr = _manager(**{
            "tsd.query.device_cache.enable": "false"})
        feed(tsdb, "ex.hl", series=2, points=100)
        try:
            seg, _ = assert_parity(tsdb, mgr, "sum:30s-avg:ex.hl",
                                   BASE, BASE + 100 * 15, "host_lane")
            assert seg["provenance"]["platform"] == "cpu"
        finally:
            tsdb.shutdown()

    def test_streamed(self):
        tsdb, mgr = _manager(**{
            "tsd.query.streaming.point_threshold": "500",
            "tsd.query.device_cache.enable": "false"})
        feed_batch(tsdb, "ex.str", 2, 2000, 1)
        try:
            assert_parity(tsdb, mgr, "sum:30s-avg:ex.str",
                          BASE, BASE + 2000, "streamed")
        finally:
            tsdb.shutdown()

    def test_tiled(self):
        tsdb, mgr = _manager(**{
            "tsd.query.streaming.point_threshold": "500",
            "tsd.query.streaming.state_mb": "1",
            "tsd.query.device_cache.enable": "false"})
        # [4, 16384] windows at 24 B/cell = 1.5 MB > 1 MB: over
        # budget; tile split fits (one row's grid is 426 KB)
        feed_batch(tsdb, "ex.tl", 4, 4096, 60)
        try:
            seg, _ = assert_parity(tsdb, mgr, "sum:15s-avg:ex.tl",
                                   BASE, BASE + 4096 * 60, "tiled")
            assert seg["tiling"]["spillBytes"] > 0
            assert seg["tiling"]["tiles"] >= 2
        finally:
            tsdb.shutdown()

    def test_refused_structured_413(self):
        tsdb, mgr = _manager(**{
            "tsd.query.streaming.point_threshold": "500",
            "tsd.query.streaming.state_mb": "1",
            "tsd.query.spill.enable": "false",
            "tsd.query.device_cache.enable": "false"})
        feed_batch(tsdb, "ex.rf", 4, 4096, 60)
        try:
            exp_uri, run_uri = _uris("sum:15s-avg:ex.rf", BASE,
                                     BASE + 4096 * 60)
            _rep, seg = explain_seg(mgr, exp_uri)
            assert seg["path"] == "refused"
            assert seg["refused"]["status"] == 413
            details = seg["refused"]["details"]
            status, payload, _ = ask(mgr, run_uri)
            assert status == 413
            actual = payload["error"]["details"]
            # the explained refusal IS the executor's envelope
            assert details == actual
            assert seg["refused"]["message"] == \
                payload["error"]["message"]
        finally:
            tsdb.shutdown()

    def test_agg_rewrite_cold_then_warm(self):
        tsdb, mgr = _manager(**{
            "tsd.query.cache.block_windows": 8,
            "tsd.query.cache.min_repeats": 1,
            "tsd.query.cache.dispatch_overhead_us": 0,
            "tsd.query.device_cache.enable": "false"})
        feed_batch(tsdb, "ex.agg", 2, 3000, 1)
        m = "sum:30s-avg:ex.agg"
        try:
            # COLD populate: min_repeats=1 admits on first sight
            seg, _ = assert_parity(tsdb, mgr, m, BASE, BASE + 3000,
                                   "agg_rewrite")
            assert seg["aggCache"]["reason"] == "cold_populate"
            assert seg["aggCache"]["coverage"] == 0.0
            # WARM reuse: the blocks the run above stored
            seg2, _ = assert_parity(tsdb, mgr, m, BASE, BASE + 3000,
                                    "agg_rewrite")
            assert seg2["aggCache"]["reason"] == "reuse"
            assert seg2["aggCache"]["coverage"] > 0.5
            assert seg2["fingerprint"] != seg["fingerprint"]
        finally:
            tsdb.shutdown()

    def _warm_lanes(self, tsdb, mgr, run_uri):
        status, _, _ = ask(mgr, run_uri)
        assert status == 200
        for _ in range(60):
            if not tsdb.rollup_lanes.refresh(tsdb.store,
                                             max_blocks=256):
                break

    def test_rollup_lane(self):
        tsdb, mgr = _manager(**{"tsd.rollup.enable": "true",
                                "tsd.rollup.intervals": "1m,1h"})
        feed_batch(tsdb, "ex.lane", 2, 3000, 15)
        m = "sum:60s-sum:ex.lane"
        start, end = BASE + 60, BASE + 2900 * 15
        try:
            self._warm_lanes(tsdb, mgr, _uris(m, start, end)[1])
            seg, event = assert_parity(tsdb, mgr, m, start, end,
                                       "rollup_lane")
            assert seg["rollup"]["decision"] == "lane"
            assert seg["rollup"]["coverage"] == 1.0
            assert seg["provenance"]["lane"]["striped"] is False
        finally:
            tsdb.shutdown()

    def test_rollup_lane_striped_host_fold(self):
        # [8, 16384] padded grid at 24 B/cell = 3.1 MB > the 1 MB
        # budget: the lane plan stripes; sum is moment-foldable and
        # the 1m-cadence grid is dense, so the executor serves the
        # host-dense fold — the explain fingerprint must carry
        # striped=True either way
        tsdb, mgr = _manager(**{
            "tsd.rollup.enable": "true",
            "tsd.rollup.intervals": "1m,1h",
            "tsd.query.streaming.state_mb": "1",
            "tsd.query.device_cache.enable": "false"})
        feed_batch(tsdb, "ex.lane7", 8, 10080, 60)
        m = "sum:60s-sum:ex.lane7"
        start, end = BASE + 60, BASE + 10080 * 60
        try:
            self._warm_lanes(tsdb, mgr, _uris(m, start, end)[1])
            seg, _ = assert_parity(tsdb, mgr, m, start, end,
                                   "rollup_lane")
            assert seg["provenance"]["lane"]["striped"] is True
        finally:
            tsdb.shutdown()

    def test_degraded_preview_matches_served_degradation(self,
                                                         monkeypatch):
        tsdb, mgr = _manager(**{"tsd.query.degrade": "allow"})
        feed(tsdb, "ex.deg", series=2, points=100, cadence_s=10)
        monkeypatch.setattr(
            admission, "estimate_plan_cost_ms",
            lambda tsdb_, tq: (1e9 if tq.queries[0].downsample_spec
                               .interval_ms < 40_000 else 1.0))
        try:
            uri = ("/api/query/explain?start=%d&end=%d"
                   "&m=sum:10s-avg:ex.deg&what_if=deadline_ms=5000"
                   % (BASE, BASE + 600))
            status, rep, _ = ask(mgr, uri)
            assert status == 200
            adm = rep["admission"]
            assert adm["verdict"] == "degrade"
            assert adm["degraded"]["coarsenedIntervalFactor"] == 4
            # the executor's ladder lands on the same rung
            status, payload, _ = ask(
                mgr, "/api/query?start=%d&end=%d&m=sum:10s-avg:ex.deg"
                % (BASE, BASE + 600),
                headers={"x-tsdb-deadline-ms": "5000"})
            assert status == 200
            trailer = next(e for e in payload if isinstance(e, dict)
                           and e.get("partialResults"))
            assert trailer["degraded"]["coarsenedIntervalFactor"] == 4
        finally:
            tsdb.shutdown()


# --------------------------------------------------------------------- #
# Zero dispatch, zero permits                                           #
# --------------------------------------------------------------------- #

class TestNoDispatchNoPermit:
    def test_explain_never_dispatches_or_takes_a_permit(self,
                                                        monkeypatch):
        tsdb, mgr = _manager(**{
            "tsd.rollup.enable": "true",
            "tsd.query.streaming.point_threshold": "500"})
        feed(tsdb, "ex.nd", series=2, points=300)
        feed_batch(tsdb, "ex.nd.big", 2, 2000, 1)
        try:
            def boom(*a, **k):
                raise AssertionError("explain dispatched device work")

            from opentsdb_tpu.ops import pipeline, tiling
            from opentsdb_tpu.ops import streaming as streaming_mod
            from opentsdb_tpu.storage import device_cache as dc_mod
            for target, name in (
                    (pipeline, "run_pipeline"),
                    (pipeline, "run_group_pipeline"),
                    (pipeline, "run_union_batch_pipeline"),
                    (pipeline, "run_grid_tail"),
                    (pipeline, "run_downsample_grid"),
                    (pipeline, "build_batch"),
                    (pipeline, "build_batch_direct"),
                    (tiling, "run_tiled"),
                    (dc_mod, "_gather_windows")):
                monkeypatch.setattr(target, name, boom)
            monkeypatch.setattr(streaming_mod.StreamAccumulator,
                                "create", boom)
            gate = admission.gate_for(tsdb)
            admitted0, shed0 = gate.admitted, gate.shed
            dc = tsdb.device_cache
            hits0, misses0 = dc.hits, dc.misses
            for uri in (
                    "/api/query/explain?start=%d&end=%d"
                    "&m=sum:30s-avg:ex.nd" % (BASE, BASE + 4500),
                    "/api/query/explain?start=%d&end=%d"
                    "&m=sum:30s-avg:ex.nd.big" % (BASE, BASE + 2000),
                    "/api/query/explain?start=%d&end=%d&m=sum:ex.nd"
                    % (BASE, BASE + 4500),
                    "/api/query/explain?start=%d&end=%d"
                    "&m=max:60s-max:ex.nd&what_if=assume_rollup=warm"
                    % (BASE, BASE + 4500)):
                status, rep, _ = ask(mgr, uri)
                assert status == 200, rep
            assert (gate.admitted, gate.shed) == (admitted0, shed0)
            assert (dc.hits, dc.misses) == (hits0, misses0)
        finally:
            tsdb.shutdown()

    def test_dry_run_consults_perturb_no_state(self):
        tsdb, mgr = _manager(**{
            "tsd.rollup.enable": "true",
            "tsd.query.cache.min_repeats": 2})
        feed(tsdb, "ex.dry", series=2, points=300)
        uri = ("/api/query/explain?start=%d&end=%d"
               "&m=sum:60s-sum:ex.dry" % (BASE, BASE + 4500))
        try:
            for _ in range(3):
                status, _, _ = ask(mgr, uri)
                assert status == 200
            # agg-cache repeat table never advanced: a later real run
            # still sees zero prior occurrences
            assert tsdb.agg_cache._repeats == {}
            # rollup demand corpus untouched (the maintenance selector
            # must not build lanes because someone explained)
            assert tsdb.rollup_lanes._demand == {}
            assert tsdb.rollup_lanes.misses == 0
            assert tsdb.device_cache.builds == 0
        finally:
            tsdb.shutdown()


# --------------------------------------------------------------------- #
# What-if grammar + endpoint surface                                    #
# --------------------------------------------------------------------- #

class TestWhatIf:
    def _mgr(self):
        tsdb, mgr = _manager()
        feed(tsdb, "ex.wi", series=2, points=300)
        return tsdb, mgr, ("/api/query/explain?start=%d&end=%d"
                           "&m=sum:30s-avg:ex.wi"
                           % (BASE, BASE + 4500))

    def test_unknown_key_is_400(self):
        tsdb, mgr, uri = self._mgr()
        try:
            status, payload, _ = ask(mgr, uri + "&what_if=bogus=1")
            assert status == 400
            assert "bogus" in payload["error"]["message"]
            status, _, _ = ask(mgr, uri + "&what_if=platform=gpu")
            assert status == 400
        finally:
            tsdb.shutdown()

    def test_assume_flags_flip_the_routing(self):
        tsdb, mgr, uri = self._mgr()
        try:
            _, seg = explain_seg(mgr, uri)
            assert seg["path"] == "resident"
            _, warm = explain_seg(
                mgr, uri + "&what_if=assume_agg_cache=warm")
            assert warm["path"] == "agg_rewrite"
            assert warm["aggCache"]["reason"] == "what_if_warm"
            _, cold = explain_seg(
                mgr, uri + "&what_if=assume_device_cache=cold")
            assert cold["provenance"]["deviceCache"] is False
        finally:
            tsdb.shutdown()

    def test_costmodel_whatifs_never_perturb_the_fingerprint(self):
        tsdb, mgr, uri = self._mgr()
        try:
            _, base_seg = explain_seg(mgr, uri)
            _, forced = explain_seg(
                mgr, uri + "&what_if=force_scan=flat")
            assert forced["fingerprint"] == base_seg["fingerprint"]
            assert forced["costmodelWhatIf"]["scan"]["mode"] == "flat"
            assert forced["costmodelWhatIf"]["scan"]["feasible"] is True
            assert base_seg["costmodel"]["scan"]["mode"] != "flat"
            assert "costmodelWhatIf" not in base_seg
        finally:
            tsdb.shutdown()

    def test_state_mb_whatif_previews_the_413(self):
        tsdb, mgr = _manager(**{
            "tsd.query.streaming.point_threshold": "500",
            "tsd.query.spill.enable": "false",
            "tsd.query.device_cache.enable": "false"})
        feed_batch(tsdb, "ex.smb", 4, 4096, 60)
        uri = ("/api/query/explain?start=%d&end=%d&m=sum:15s-avg:ex.smb"
               % (BASE, BASE + 4096 * 60))
        try:
            _, live = explain_seg(mgr, uri)
            assert live["path"] == "streamed"     # default 6 GB budget
            _, tight = explain_seg(mgr, uri + "&what_if=state_mb=1")
            assert tight["path"] == "refused"
            assert tight["refused"]["details"]["limitMb"] == 1
        finally:
            tsdb.shutdown()

    def test_disabled_explain_is_404(self):
        tsdb, mgr = _manager(**{"tsd.explain.enable": "false"})
        try:
            status, _, _ = ask(
                mgr, "/api/query/explain?start=%d&m=sum:x" % BASE)
            assert status == 404
        finally:
            tsdb.shutdown()

    def test_post_body_whatif(self):
        tsdb, mgr = _manager()
        feed(tsdb, "ex.post", series=1, points=50)
        try:
            body = json.dumps({
                "start": BASE, "end": BASE + 750,
                "queries": [{"aggregator": "sum",
                             "metric": "ex.post",
                             "downsample": "30s-avg"}],
                "whatIf": {"assume_agg_cache": "warm"},
            }).encode()
            status, rep, _ = ask(
                mgr, "/api/query/explain", method="POST", body=body,
                headers={"content-type": "application/json"})
            assert status == 200
            assert rep["whatIf"] == {"assume_agg_cache": "warm"}
            assert rep["subQueries"][0]["segments"][0]["path"] == \
                "agg_rewrite"
        finally:
            tsdb.shutdown()


# --------------------------------------------------------------------- #
# /api/diag trace_id resolution (satellite)                             #
# --------------------------------------------------------------------- #

class TestDiagTraceId:
    def test_fingerprint_resolves_to_its_ring_slice(self):
        tsdb, mgr = _manager(**{"tsd.diag.slow_ms": "1"})
        feed(tsdb, "ex.tid", series=1, points=60)
        trace_id = "ab" * 8
        try:
            exp_uri, run_uri = _uris("sum:30s-avg:ex.tid", BASE,
                                     BASE + 900)
            _, seg = explain_seg(mgr, exp_uri)
            status, _, _ = ask(mgr, run_uri,
                               headers={"x-tsdb-trace-id": trace_id})
            assert status == 200
            # the ring slice for ONE trace id, one request
            status, diag, _ = ask(mgr,
                                  "/api/diag?trace_id=%s" % trace_id)
            assert status == 200
            assert diag["traceId"] == trace_id
            assert diag["events"], "empty ring slice for the trace"
            assert all(e["traceId"] == trace_id for e in diag["events"])
            plan = next(e for e in diag["events"]
                        if e["kind"] == "plan")
            assert plan["fingerprint"] == seg["fingerprint"]
            # ?since composes with the filter
            status, tail, _ = ask(
                mgr, "/api/diag?trace_id=%s&since=%d"
                % (trace_id, plan["seq"]))
            assert all(e["seq"] > plan["seq"] for e in tail["events"])
            # slow capture lookup by the same id
            status, slow, _ = ask(
                mgr, "/api/diag/slow?trace_id=%s" % trace_id)
            assert status == 200
            assert len(slow["queries"]) == 1
            assert slow["queries"][0]["traceId"] == trace_id
            status, none_, _ = ask(mgr,
                                   "/api/diag/slow?trace_id=%s" % "cd" * 8)
            assert none_["queries"] == []
        finally:
            tsdb.shutdown()


# --------------------------------------------------------------------- #
# PLAN_CORPUS.json byte-pin                                             #
# --------------------------------------------------------------------- #

class TestPlanCorpusPin:
    def test_corpus_is_in_sync(self):
        """The committed PLAN_CORPUS.json is byte-for-byte what
        tools/plan_corpus.py generates — any planner-routing change
        must land as a reviewed corpus diff.  Subprocess: the corpus
        is generated by the tool's own command, as a reviewer would."""
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "plan_corpus.py"),
             "--check"],
            capture_output=True, text=True, env=env, cwd=REPO,
            timeout=560)
        assert proc.returncode == 0, (
            "PLAN_CORPUS.json drifted:\n%s\n%s"
            % (proc.stdout, proc.stderr))

"""tsdbobs surface tests: span trees, Prometheus exposition, histogram
quantiles, the self-report loop, and the stats-collector fixes.

Every TSDB here pins tsd.query.mesh.enable=false: the span trees and
the calibration ring under test describe the single-device dispatch
(the mesh route has its own suites, tests/test_mesh_query.py and
tests/test_parallel.py).
"""

from __future__ import annotations

import json
import math
import re
import time

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs.histogram import LogHistogram
from opentsdb_tpu.obs.registry import (MetricsRegistry, escape_label_value,
                                       sanitize_name)
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400


@pytest.fixture
def tsdb():
    t = TSDB(Config({"tsd.core.auto_create_metrics": True,
                     "tsd.query.mesh.enable": False,
                     # the suite pins the calibration-ring mechanics;
                     # batched executions are ring-excluded by design
                     # (tests/test_batcher.py owns that contract)
                     "tsd.query.batch.enable": False}))
    for host in ("web01", "web02"):
        for i in range(20):
            t.add_point("obs.cpu", BASE + i * 10, float(i), {"host": host})
    return t


@pytest.fixture
def manager(tsdb):
    return RpcManager(tsdb)


def http(manager, method, uri, body=None, headers=None):
    data = b"" if body is None else (
        body if isinstance(body, bytes) else json.dumps(body).encode())
    hdrs = {"content-type": "application/json"}
    hdrs.update(headers or {})
    return manager.handle_http(
        HttpRequest(method=method, uri=uri, body=data, headers=hdrs),
        remote="127.0.0.1:55").response


def span_names(tree: dict) -> set[str]:
    out = {tree["name"]}
    for child in tree.get("spans", []):
        out |= span_names(child)
    return out


def find_spans(tree: dict, name: str) -> list[dict]:
    out = [tree] if tree.get("name") == name else []
    for child in tree.get("spans", []):
        out.extend(find_spans(child, name))
    return out


class TestSpanTree:
    def _trace_of(self, response) -> dict:
        payload = json.loads(response.body)
        summaries = [e for e in payload
                     if isinstance(e, dict) and "statsSummary" in e]
        assert summaries, "show_stats must append a statsSummary entry"
        summary = summaries[0]["statsSummary"]
        assert "trace" in summary, "traced query must inline its span tree"
        return summary["trace"]

    def test_e2e_downsample_query_covers_every_stage(self, manager):
        r = http(manager, "GET",
                 "/api/query?start=%d&end=%d"
                 "&m=sum:30s-avg:obs.cpu{host=*}&show_stats"
                 % (BASE, BASE + 300))
        assert r.status == 200
        tree = self._trace_of(r)
        names = span_names(tree)
        for stage in ("scan", "count", "consult", "pipeline", "fetch",
                      "extract", "assemble", "serialize"):
            assert stage in names, "missing %s in %s" % (stage, names)
        # every span carries its wall time and nothing that claims to be
        # the device's: the tracer syncs nothing
        def walk(node):
            assert isinstance(node["wallMs"], float)
            assert "deviceMs" not in node
            assert "estimated" not in node.get("tags", {})
            for c in node.get("spans", []):
                walk(c)
        walk(tree)
        # the fused dispatch's stages are the costmodel's decisions on
        # the pipeline span, not children apportioned from a sync
        (pipe,) = find_spans(tree, "pipeline")
        assert {"search", "group"} <= set(pipe["tags"]["costmodel"])
        assert not {"downsample", "groupby", "aggregate"} & names
        assert re.fullmatch(r"[0-9a-f]{16}", tree["traceId"])

    def test_rate_query_takes_a_rate_lane(self, manager):
        from opentsdb_tpu.obs.registry import REGISTRY
        fam = REGISTRY.counter("tsd.query.rate_lane")
        before = sum(fam.labels(lane=lane).get()
                     for lane in ("shift", "scan"))
        r = http(manager, "GET",
                 "/api/query?start=%d&end=%d"
                 "&m=sum:30s-avg:rate:obs.cpu&show_stats"
                 % (BASE, BASE + 300))
        assert "pipeline" in span_names(self._trace_of(r))
        assert sum(fam.labels(lane=lane).get()
                   for lane in ("shift", "scan")) == before + 1

    def test_union_query_traces_pipeline(self, manager):
        r = http(manager, "GET",
                 "/api/query?start=%d&end=%d&m=sum:obs.cpu&show_stats"
                 % (BASE, BASE + 300))
        tree = self._trace_of(r)
        assert {"scan", "pipeline", "serialize"} <= span_names(tree)
        (pipe,) = find_spans(tree, "pipeline")
        assert pipe["tags"]["union"] is True and "spans" not in pipe

    def test_trace_id_header_is_adopted(self, manager):
        r = http(manager, "GET",
                 "/api/query?start=%d&m=sum:obs.cpu&show_stats" % BASE,
                 headers={"x-tsdb-trace-id": "cafe0123cafe0123"})
        assert self._trace_of(r)["traceId"] == "cafe0123cafe0123"

    def test_trace_lands_in_query_stats_ring(self, manager):
        http(manager, "GET",
             "/api/query?start=%d&m=sum:30s-avg:obs.cpu" % BASE)
        r = http(manager, "GET", "/api/stats/query")
        completed = json.loads(r.body)["completed"]
        assert completed and "trace" in completed[0]
        assert "scan" in span_names(completed[0]["trace"])

    def test_trace_disabled_serves_without_spans(self, tsdb, manager):
        tsdb.config.override_config("tsd.trace.enable", False)
        r = http(manager, "GET",
                 "/api/query?start=%d&m=sum:obs.cpu&show_stats" % BASE)
        assert r.status == 200
        payload = json.loads(r.body)
        summary = [e for e in payload if "statsSummary" in e][0]
        assert "trace" not in summary["statsSummary"]


class TestPrometheus:
    SAMPLE = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
        r'(\{[a-zA-Z0-9_]+="(\\.|[^"\\])*"(,[a-zA-Z0-9_]+="(\\.|[^"\\])*")*'
        r"(,le=\"[^\"]+\")?\})? (NaN|[-+]?Inf|[-+0-9.eE]+)$")

    def _scrape(self, manager):
        # serve a query first so latency histograms hold observations
        http(manager, "GET",
             "/api/query?start=%d&m=sum:30s-avg:obs.cpu" % BASE)
        r = http(manager, "GET", "/api/stats/prometheus")
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/plain")
        return r.body.decode()

    # OpenMetrics-style exemplar COMMENT lines (tsd.diag.exemplars):
    # `# exemplar: <bucket sample> {trace_id="..."} <value>` — a
    # comment, so the 0.0.4 text format stays parseable
    EXEMPLAR = re.compile(
        r'^# exemplar: [a-zA-Z_:][a-zA-Z0-9_:]*_bucket'
        r'\{[^}]*le="[^"]+"\} \{trace_id="[0-9a-f]{16}"\} '
        r"[-+0-9.eE]+$")

    def test_exposition_is_scrapeable(self, manager):
        text = self._scrape(manager)
        assert text.endswith("\n")
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            assert self.SAMPLE.match(line), "unscrapeable line: %r" % line

    def test_exemplars_link_buckets_to_trace_ids(self, tsdb, manager):
        """tsd.diag.exemplars surfaces per-bucket trace ids as comment
        lines; every NON-comment line stays 0.0.4-parseable, so a
        strict scraper sees the exact same sample set."""
        tsdb.config.override_config("tsd.diag.exemplars", True)
        text = self._scrape(manager)
        exemplars = [ln for ln in text.splitlines()
                     if ln.startswith("# exemplar: ")]
        assert exemplars, "traced serving must retain bucket exemplars"
        for ln in exemplars:
            assert self.EXEMPLAR.match(ln), "malformed exemplar: %r" % ln
        assert any("tsd_query_latency_ms_bucket" in ln
                   for ln in exemplars)
        for line in text.splitlines():
            if not line or line.startswith("#"):
                continue
            assert self.SAMPLE.match(line), "unscrapeable line: %r" % line

    def test_exemplars_off_by_default(self, manager):
        text = self._scrape(manager)
        assert not any(ln.startswith("# exemplar") for ln in
                       text.splitlines())

    def test_counters_gauges_histograms_present(self, tsdb, manager):
        from opentsdb_tpu.tsd import cluster
        cluster._state(tsdb).breaker("10.0.0.1:4242")  # surface breakers
        text = self._scrape(manager)
        assert "# TYPE tsd_http_requests_total counter" in text
        assert "# TYPE tsd_http_latency_ms histogram" in text
        assert "# TYPE tsd_query_device_cache_hits gauge" in text
        assert "tsd_cluster_breaker_state" in text
        assert 'peer="10.0.0.1:4242"' in text

    def test_histogram_triplets_are_consistent(self, manager):
        # tsd.query.latency_ms is tenant-labeled (ISSUE 12): the
        # bucket/_sum/_count triplet contract holds PER CELL — other
        # tests in the session may have minted more tenants into the
        # process-shared registry
        from collections import defaultdict
        text = self._scrape(manager)
        lines = text.splitlines()

        def cell_key(line):
            name = line.split(" ")[0]
            m = re.search(r"\{(.*)\}", name)
            return tuple(sorted(
                kv for kv in (m.group(1).split(",") if m else [])
                if not kv.startswith("le=")))

        buckets: dict = defaultdict(list)
        counts: dict = {}
        sums: dict = {}
        for ln in lines:
            if ln.startswith("tsd_query_latency_ms_bucket"):
                buckets[cell_key(ln)].append(ln)
            elif ln.startswith("tsd_query_latency_ms_count"):
                counts[cell_key(ln)] = int(ln.rsplit(" ", 1)[1])
            elif ln.startswith("tsd_query_latency_ms_sum"):
                sums[cell_key(ln)] = float(ln.rsplit(" ", 1)[1])
        assert buckets and counts and sums
        assert set(buckets) == set(counts) == set(sums)
        for key, blines in buckets.items():
            inf = [ln for ln in blines if 'le="+Inf"' in ln]
            assert inf, "+Inf bucket required in %r" % key
            assert int(inf[0].rsplit(" ", 1)[1]) == counts[key] >= 1
            # cumulative counts are non-decreasing within the cell
            values = [int(ln.rsplit(" ", 1)[1]) for ln in blines]
            assert values == sorted(values)
            assert sums[key] >= 0

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("odd.metric", "quotes").labels(
            tag='a"b\\c\nd').inc()
        text = reg.prometheus_text()
        assert 'tag="a\\"b\\\\c\\nd"' in text
        assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'
        assert sanitize_name("tsd.uid.cache-hit") == "tsd_uid_cache_hit"

    def test_kind_collision_rejected(self):
        reg = MetricsRegistry()
        reg.counter("x.y")
        with pytest.raises(ValueError):
            reg.gauge("x.y")

    def test_update_device_gauges_for_embedders(self, tsdb):
        """The registry-only export path (no TSD stats walk)."""
        from opentsdb_tpu.obs import jaxprof
        from opentsdb_tpu.obs.registry import REGISTRY
        jaxprof.update_device_gauges(tsdb)
        text = REGISTRY.prometheus_text()
        assert "tsd_query_device_cache_hits" in text


class TestLogHistogram:
    GROWTH = 2 ** 0.25

    def _check(self, values, qs=(0.5, 0.9, 0.99)):
        h = LogHistogram()
        for v in values:
            h.observe(float(v))
        tol = self.GROWTH * 1.001
        for q in qs:
            true = float(np.quantile(values, q, method="inverted_cdf"))
            est = h.quantile(q)
            if true <= h.lo:
                assert est <= h.lo * tol
                continue
            assert true / tol <= est <= true * tol, (
                "q=%s: est %g vs true %g" % (q, est, true))

    def test_lognormal_heavy_tail(self):
        rng = np.random.default_rng(7)
        self._check(rng.lognormal(0.0, 2.5, 20_000))

    def test_pareto_power_law(self):
        rng = np.random.default_rng(11)
        self._check(rng.pareto(0.7, 20_000) + 1e-2)

    def test_adversarial_bimodal_six_decades_apart(self):
        rng = np.random.default_rng(13)
        vals = np.concatenate([
            rng.uniform(0.002, 0.004, 10_000),
            rng.uniform(2_000.0, 4_000.0, 101),   # tail just past p99
        ])
        rng.shuffle(vals)
        self._check(vals, qs=(0.5, 0.9, 0.999))

    def test_constant_and_single_value(self):
        self._check(np.full(1000, 42.0))
        h = LogHistogram()
        assert math.isnan(h.quantile(0.5))
        h.observe(5.0)
        tol = self.GROWTH * 1.001
        assert 5.0 / tol <= h.quantile(0.5) <= 5.0 * tol

    def test_merge_equals_single_pass(self):
        rng = np.random.default_rng(3)
        vals = rng.lognormal(1.0, 2.0, 8_000)
        whole = LogHistogram()
        merged = LogHistogram()
        shards = [LogHistogram() for _ in range(4)]
        for i, v in enumerate(vals):
            whole.observe(float(v))
            shards[i % 4].observe(float(v))
        for s in shards:
            merged.merge(s)
        m_counts, m_count, m_total = merged.snapshot()
        w_counts, w_count, w_total = whole.snapshot()
        assert (m_counts, m_count) == (w_counts, w_count)
        assert m_total == pytest.approx(w_total)  # fp summation order

    def test_merge_layout_mismatch_rejected(self):
        with pytest.raises(ValueError):
            LogHistogram().merge(LogHistogram(buckets=12))

    def test_cumulative_is_aligned_and_bounded(self):
        h = LogHistogram()
        for v in (0.5, 3.0, 900.0, 1e9):
            h.observe(v)
        cum = h.cumulative(max_buckets=16)
        assert len(cum) <= 17
        assert cum[-1][0] == math.inf and cum[-1][1] == 4
        counts = [c for _, c in cum]
        assert counts == sorted(counts)


class TestSelfReport:
    def test_records_land_in_memstore_and_are_queryable(self, tsdb,
                                                        manager):
        from opentsdb_tpu.obs.selfreport import self_report
        from opentsdb_tpu.tsd import cluster
        cluster._state(tsdb).breaker("10.0.0.1:4242")  # ':' needs mapping
        before = tsdb.store.num_series
        n = self_report(tsdb)
        assert n > 10
        assert tsdb.store.num_series > before
        # queryable through the TSD's own pipeline
        r = http(manager, "GET",
                 "/api/query?start=%d&end=%d&m=sum:tsd.datapoints.added"
                 % (BASE, int(time.time()) + 60))
        assert r.status == 200
        series = json.loads(r.body)
        assert series and series[0]["metric"] == "tsd.datapoints.added"
        assert list(series[0]["dps"].values())[0] >= 40

    def test_read_only_daemon_skips(self):
        t = TSDB(Config({"tsd.mode": "ro",
                         "tsd.query.mesh.enable": False}))
        from opentsdb_tpu.obs.selfreport import self_report
        assert self_report(t) == 0

    def test_maintenance_cadence_gated_by_interval(self, tsdb):
        from opentsdb_tpu.core.maintenance import MaintenanceThread
        mt = MaintenanceThread(tsdb)      # interval 0: disabled
        mt._maybe_self_report(mt._next_self_report + 10)
        assert mt.self_reports == 0
        tsdb.config.override_config("tsd.stats.interval", 30)
        mt2 = MaintenanceThread(tsdb)
        mt2._maybe_self_report(mt2._next_self_report + 1)
        assert mt2.self_reports == 1 and mt2.self_report_points > 0
        assert mt2.self_report_errors == 0
        stats = mt2.collect_stats()
        assert stats["tsd.maintenance.self_reports"] == 1

    def test_stats_rpc_and_self_report_share_one_walk(self, tsdb,
                                                      manager):
        """The dogfooded series must be the records /api/stats serves."""
        from opentsdb_tpu.obs.selfreport import collect_all
        names = {r["metric"] for r in collect_all(tsdb).records}
        # the RpcManager hook's counters are in the shared walk
        assert "tsd.http.errors" in names
        assert "tsd.rpc.received" in names
        via_api = {r["metric"]
                   for r in json.loads(
                       http(manager, "GET", "/api/stats").body)}
        assert via_api == {r["metric"]
                           for r in collect_all(tsdb).records}


class TestCollectorXtratag:
    def test_multi_equals_rejected(self):
        from opentsdb_tpu.stats import StatsCollector
        c = StatsCollector("tsd", use_host_tag=False)
        with pytest.raises(ValueError, match="multiple '=' signs or none"):
            c.record("x", 1, "a=b=c")

    def test_no_equals_still_rejected(self):
        from opentsdb_tpu.stats import StatsCollector
        c = StatsCollector("tsd", use_host_tag=False)
        with pytest.raises(ValueError):
            c.record("x", 1, "ab")

    def test_single_equals_accepted(self):
        from opentsdb_tpu.stats import StatsCollector
        c = StatsCollector("tsd", use_host_tag=False)
        c.record("x", 1, "kind=put")
        assert c.records[0]["tags"] == {"kind": "put"}


class TestCompileCapture:
    def test_profiler_and_sanitizer_share_the_stream(self):
        """One compile event reaches BOTH subscribers — the can't-drift
        contract behind moving the capture into obs/jaxprof.py."""
        import jax
        from opentsdb_tpu.obs import jaxprof

        seen: list[str] = []
        cb = seen.append          # one object: unsubscribe must match
        jaxprof.compile_capture.subscribe(cb)
        jaxprof.start_compile_counting()
        try:
            before = dict(jaxprof.compile_counts())
            fresh = jax.jit(lambda x: x * 3 + 1)
            fresh(jax.numpy.arange(7))
            assert seen, "capture saw no compile for a fresh jit"
            grew = [k for k, v in jaxprof.compile_counts().items()
                    if v > before.get(k, 0)]
            assert grew, "counter subscriber missed the same event"
        finally:
            jaxprof.stop_compile_counting()
            jaxprof.compile_capture.unsubscribe(cb)

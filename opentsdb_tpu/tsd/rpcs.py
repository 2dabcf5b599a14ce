"""Core RPC handlers: put/rollup/histogram ingest + query + suggest +
annotation + uid endpoints.

Reference behavior: /root/reference/src/tsd/PutDataPointRpc.java (telnet
`put` :129 / POST /api/put :272, processDataPoint :309 with details/summary/
sync modes), RollupDataPointRpc.java (telnet grammar
`rollup interval-agg[:spatial] metric ts value tags` :95-150), QueryRpc.java
(:89 — GET query-string grammar, POST JSON, DELETE, /api/query/last :346),
SuggestRpc.java, AnnotationRpc.java, UniqueIdRpc.java (:63-77).
"""

from __future__ import annotations

import logging
import threading
import time

from opentsdb_tpu.models.tsquery import (
    TSQuery, parse_m_subquery, parse_tsuid_subquery)
from opentsdb_tpu.obs import latattr
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.storage.memstore import Annotation
from opentsdb_tpu.tsd import admission
from opentsdb_tpu.tsd.http import BadRequestError, HttpQuery
from opentsdb_tpu.uid import NoSuchUniqueName
from opentsdb_tpu.stats.query_stats import QueryStats, DuplicateQueryException
from opentsdb_tpu.utils import faults

LOG = logging.getLogger("tsd.rpcs")


class TelnetRpc:
    def execute_telnet(self, tsdb, conn, words: list[str]) -> str | None:
        raise NotImplementedError


class HttpRpc:
    def execute_http(self, tsdb, query: HttpQuery) -> None:
        raise NotImplementedError


def allowed_methods(query: HttpQuery, *methods: str) -> None:
    if query.method not in methods:
        raise BadRequestError(
            "Method not allowed", status=405,
            details="The HTTP method [%s] is not permitted for this endpoint"
                    % query.method)


def parse_tags(words: list[str]) -> dict[str, str]:
    """`tag=value` words -> dict (Tags.parse)."""
    tags: dict[str, str] = {}
    for w in words:
        if not w:
            continue
        if "=" not in w:
            raise ValueError("invalid tag: %s" % w)
        k, v = w.split("=", 1)
        if not k or not v:
            raise ValueError("invalid tag: %s" % w)
        if tags.get(k, v) != v:
            raise ValueError("duplicate tag: %s, tags so far: %s" % (w, tags))
        tags[k] = v
    return tags


class PutDataPointRpc(TelnetRpc, HttpRpc):
    """Telnet `put` + POST /api/put."""

    kind = "put"

    def __init__(self):
        # guarded-by: _lock
        self.requests = 0
        self.http_requests = 0  # guarded-by: _lock
        self.hbase_errors = 0  # guarded-by: _lock
        self.invalid_values = 0  # guarded-by: _lock
        self.illegal_arguments = 0  # guarded-by: _lock
        self.unknown_metrics = 0  # guarded-by: _lock
        self.writes_blocked = 0  # guarded-by: _lock
        # write requests (HTTP bodies / telnet blocks) by the parser
        # that served them — a missing native library shows up here
        # instead of only as a slower daemon
        self.native_parsed = 0  # guarded-by: _lock
        self.python_parsed = 0  # guarded-by: _lock
        self._lock = threading.Lock()

    def _count(self, attr: str) -> None:
        with self._lock:
            setattr(self, attr, getattr(self, attr) + 1)

    # -- telnet: put <metric> <ts> <value> <tag=v> [...] --

    def execute_telnet(self, tsdb, conn, words: list[str]) -> str | None:
        self._count("requests")
        try:
            self.import_telnet_point(tsdb, words)
            return None
        except NoSuchUniqueName as e:
            self._count("unknown_metrics")
            return "put: unknown metric: %s\n" % e
        except ValueError as e:
            self._count("illegal_arguments")
            return "put: %s\n" % e
        except Exception as e:
            self._count("hbase_errors")
            return "put: %s: %s\n" % (type(e).__name__, e)

    def import_telnet_point(self, tsdb, words: list[str]) -> None:
        if len(words) < 5:
            raise ValueError("not enough arguments (need least 4, got %d)"
                             % (len(words) - 1))
        metric = words[1]
        if not metric:
            raise ValueError("empty metric name")
        timestamp = parse_telnet_timestamp(words[2])
        value = words[3]
        if not value:
            raise ValueError("empty value")
        tags = parse_tags(words[4:])
        tsdb.add_point(metric, timestamp, value, tags)

    def execute_telnet_batch(self, tsdb, conn, block: bytes,
                             manager) -> str:
        """A block of consecutive telnet put lines in ONE call.

        The native parser lands every clean line columnar
        (TSDB.add_telnet_batch_native); lines it refuses replay through
        the per-line handler individually, so replies keep line order
        and per-line semantics exactly.  Without the native library the
        whole block walks the per-line path.
        """
        native = None
        if type(self).import_telnet_point \
                is PutDataPointRpc.import_telnet_point:
            native = tsdb.add_telnet_batch_native(block)
        if native is None:
            self._count("python_parsed")
            return self._telnet_lines_one_by_one(conn, block, manager)
        from opentsdb_tpu.storage.native_engine import LINE_FALLBACK
        tb, point_errors = native
        out: list[str] = []
        # tally counters locally: one lock round-trip per BATCH, not per
        # line (the per-line lock is exactly the overhead batching kills)
        requests = unknown = illegal = storage = 0
        for li in range(tb.n_lines):
            if tb.status[li] == LINE_FALLBACK:
                s, e = tb.spans[li]
                text = block[s:e].decode("utf-8", "replace").strip("\r\n")
                reply = manager.handle_telnet(conn, text)
                if reply:
                    out.append(reply)
                continue
            requests += 1
            exc = point_errors.get(int(tb.point_index[li]))
            if exc is None:
                continue
            if isinstance(exc, NoSuchUniqueName):
                unknown += 1
                out.append("put: unknown metric: %s\n" % exc)
            elif isinstance(exc, (ValueError, TypeError)):
                illegal += 1
                out.append("put: %s\n" % exc)
            else:
                storage += 1
                out.append("put: %s: %s\n" % (type(exc).__name__, exc))
        with self._lock:
            self.native_parsed += 1
            self.requests += requests
            self.unknown_metrics += unknown
            self.illegal_arguments += illegal
            self.hbase_errors += storage
        return "".join(out)

    @staticmethod
    def _telnet_lines_one_by_one(conn, block: bytes, manager) -> str:
        out = []
        for raw in block.splitlines():
            text = raw.decode("utf-8", "replace").strip("\r\n")
            if not text.strip():
                continue
            reply = manager.handle_telnet(conn, text)
            if reply:
                out.append(reply)
        return "".join(out)

    # -- HTTP --

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        self._count("http_requests")
        allowed_methods(query, "POST")
        if getattr(tsdb, "replication", None) is not None:
            from opentsdb_tpu.tsd.replication import ReplicationManager
            if ReplicationManager.is_routed_request(query):
                # a peer already routed this body here (one hop): this
                # node is the accepting member — apply locally, never
                # re-forward (the loop guard)
                with ReplicationManager.accepting():
                    return self._execute_put(tsdb, query)
        return self._execute_put(tsdb, query)

    def _execute_put(self, tsdb, query: HttpQuery) -> None:
        native = self._try_native_put(tsdb, query)
        if native is not None:
            # the native parser fuses decode + columnar ingest: the
            # write path's device-equivalent work counts as dispatch
            latattr.mark("dispatch")
            self._count("native_parsed")
            success, errors, spans = native
            if success == 0 and not errors:
                raise BadRequestError("No datapoints found in content")
            body = query.request.body

            def dp_at(i: int) -> dict:
                # original datapoint for details-mode error reporting,
                # recovered lazily from its recorded byte span
                import json
                s, e = spans[i]
                try:
                    return json.loads(body[int(s):int(e)])
                except Exception:
                    # a span the native parser mis-recorded: the error
                    # report ships without its datapoint, which is worth
                    # an operator trace (the ingest verdict itself is
                    # unaffected)
                    LOG.warning(
                        "could not recover datapoint %d (bytes %d:%d) "
                        "for details-mode error reporting", i, s, e)
                    return {}

            self._respond_put(tsdb, query, success, errors, dp_at)
            return
        self._count("python_parsed")
        dps = query.serializer.parse_put_v1()
        latattr.mark("parse")
        self.process_data_points(tsdb, query, dps)

    def _try_native_put(self, tsdb, query: HttpQuery):
        """The C++ body parser, when nothing needs per-point Python:
        base put RPC only (rollup/histogram subclasses parse their own
        records), the stock JSON serializer, and a TSDB without
        per-point hooks (checked inside add_points_bulk_native)."""
        from opentsdb_tpu.tsd.serializers import HttpJsonSerializer
        if (type(self).ingest_points is not PutDataPointRpc.ingest_points
                or type(query.serializer).parse_put_v1
                is not HttpJsonSerializer.parse_put_v1
                or not query.request.body):
            return None
        return tsdb.add_points_bulk_native(query.request.body)

    def store_point(self, tsdb, dp: dict) -> None:
        for field in ("metric", "timestamp", "value", "tags"):
            if field not in dp or dp[field] in (None, "", {}):
                raise ValueError("Missing required field: %s" % field)
        tsdb.add_point(dp["metric"], dp["timestamp"], dp["value"],
                       dict(dp["tags"]))

    def ingest_points(self, tsdb, dps: list[dict]
                      ) -> tuple[int, list[tuple[int, Exception]]]:
        """(success, [(index, exception)]).  Raw puts take the vectorized
        bulk path; rollup/histogram records override with the per-point
        loop through their own store_point."""
        return tsdb.add_points_bulk(dps)

    def _ingest_one_by_one(self, tsdb, dps: list[dict]
                           ) -> tuple[int, list[tuple[int, Exception]]]:
        success = 0
        errors: list[tuple[int, Exception]] = []
        for i, dp in enumerate(dps):
            try:
                self.store_point(tsdb, dp)
                success += 1
            except Exception as e:
                errors.append((i, e))
        return success, errors

    def process_data_points(self, tsdb, query: HttpQuery,
                            dps: list[dict]) -> None:
        """processDataPoint (:309) semantics over the vectorized bulk
        ingest: points validate individually (per-point error collection,
        204 on clean success, details/summary modes) but land as one
        columnar batch per series (TSDB.add_points_bulk)."""
        if not dps:
            raise BadRequestError("No datapoints found in content")
        success, errors = self.ingest_points(tsdb, dps)
        latattr.mark("dispatch")
        self._respond_put(tsdb, query, success, errors, lambda i: dps[i])

    # The ack-path durability contract (PR 15), checked at the tree
    # level by tools/lint/ordering.py: by the time either ack statement
    # below runs, the accepted points must have journaled and shipped.
    # order: wal-append before ingest-ack
    # order: replica-ship before ingest-ack
    def _respond_put(self, tsdb, query: HttpQuery, success: int,
                     errors: list, dp_at) -> None:
        """Shared response tail: per-error counters + SEH spillway +
        204/details/summary shaping (same for both ingest parsers)."""
        show_details = query.has_query_string_param("details")
        show_summary = query.has_query_string_param("summary")
        details: list[dict] = []
        failed = len(errors)
        for i, e in errors:
            dp = dp_at(i)
            if isinstance(e, NoSuchUniqueName):
                self._count("unknown_metrics")
                details.append({"error": "Unknown metric",
                                "datapoint": dp})
            elif isinstance(e, (ValueError, TypeError)):
                self._count("illegal_arguments")
                details.append({"error": str(e), "datapoint": dp})
            else:
                self._count("hbase_errors")
                if tsdb.storage_exception_handler is not None:
                    # Failed-write spillway (TSDB.storeIntoDB error
                    # callbacks -> StorageExceptionHandler.handleError).
                    tsdb.storage_exception_handler.handle_error(dp, e)
                details.append({"error": "Storage exception: %s" % e,
                                "datapoint": dp})
        if not show_details and not show_summary:
            if failed:
                raise BadRequestError(
                    "One or more data points had errors",
                    details="Please see the TSD logs or append \"details\" "
                            "to the put request")
            query.send_status_only(204)              # order-event: ingest-ack
            return
        summary = {"success": success, "failed": failed}
        if show_details:
            summary["errors"] = details
        status = 200 if failed == 0 else 400
        query.send_reply(query.serializer.format_put_v1(summary),  # order-event: ingest-ack
                         status=status)

    def collect_stats(self, collector) -> None:
        collector.record("rpc.received", self.requests,
                         "type=%s" % self.kind)
        collector.record("rpc.received", self.http_requests,
                         "type=%s_http" % self.kind)
        collector.record("%s.errors" % self.kind, self.hbase_errors,
                         "type=storage_errors")
        collector.record("%s.errors" % self.kind, self.illegal_arguments,
                         "type=illegal_arguments")
        collector.record("%s.errors" % self.kind, self.unknown_metrics,
                         "type=unknown_metrics")
        collector.record("%s.parser" % self.kind, self.native_parsed,
                         "parser=native")
        collector.record("%s.parser" % self.kind, self.python_parsed,
                         "parser=python")


class RollupDataPointRpc(PutDataPointRpc):
    """Telnet `rollup` + POST /api/rollup.

    Telnet grammar (RollupDataPointRpc.java:95-150):
    ``rollup <interval>-<agg>[:<spatial_agg>] metric ts value tag=v...``
    or ``rollup <spatial_agg> ...`` for interval-less pre-aggregates.
    """

    kind = "rollup"

    def ingest_points(self, tsdb, dps):
        return self._ingest_one_by_one(tsdb, dps)

    def import_telnet_point(self, tsdb, words: list[str]) -> None:
        if len(words) < 6:
            raise ValueError("not enough arguments (need least 5, got %d)"
                             % (len(words) - 1))
        interval_agg = words[1]
        if not interval_agg:
            raise ValueError("Missing interval or aggregator")
        interval, temporal_agg, spatial_agg = parse_interval_agg(interval_agg)
        metric = words[2]
        if not metric:
            raise ValueError("empty metric name")
        timestamp = parse_telnet_timestamp(words[3])
        value = words[4]
        if not value:
            raise ValueError("empty value")
        tags = parse_tags(words[5:])
        tsdb.add_aggregate_point(metric, timestamp, value, tags,
                                 spatial_agg is not None, interval,
                                 temporal_agg, spatial_agg)

    def store_point(self, tsdb, dp: dict) -> None:
        for field in ("metric", "timestamp", "value", "tags"):
            if field not in dp or dp[field] in (None, "", {}):
                raise ValueError("Missing required field: %s" % field)
        interval = dp.get("interval")
        agg = dp.get("aggregator") or dp.get("aggregate")
        groupby = dp.get("groupbyAggregator") or dp.get("groupby_aggregator")
        is_groupby = bool(dp.get("groupby", groupby is not None))
        tsdb.add_aggregate_point(dp["metric"], dp["timestamp"], dp["value"],
                                 dict(dp["tags"]), is_groupby, interval,
                                 agg, groupby or agg)


def parse_interval_agg(interval_agg: str
                       ) -> tuple[str | None, str | None, str | None]:
    """"1h-sum", "1h-sum:count", or bare "sum" (RollupDataPointRpc:108-123)."""
    parts = interval_agg.split(":")
    interval = temporal = spatial = None
    dash = parts[0].find("-")
    if dash > -1:
        interval = parts[0][:dash]
        temporal = parts[0][dash + 1:]
    elif len(parts) == 1:
        spatial = parts[0]
    if len(parts) > 1:
        spatial = parts[1]
    return interval, temporal, spatial


def parse_telnet_timestamp(text: str) -> float:
    if not text:
        raise ValueError("empty timestamp")
    ts = float(text) if "." in text else int(text)
    if ts <= 0:
        raise ValueError("invalid timestamp: %s" % text)
    return ts


class HistogramDataPointRpc(PutDataPointRpc):
    """Telnet `histogram` + POST /api/histogram."""

    kind = "histogram"

    def ingest_points(self, tsdb, dps):
        return self._ingest_one_by_one(tsdb, dps)

    def import_telnet_point(self, tsdb, words: list[str]) -> None:
        # histogram <codec_id> <metric> <ts> <base64 or json value> tag=v...
        if len(words) < 6:
            raise ValueError("not enough arguments (need least 5, got %d)"
                             % (len(words) - 1))
        if tsdb.histogram_manager is None:
            raise ValueError("histograms are not configured "
                             "(tsd.core.histograms.config)")
        codec_id = int(words[1])
        metric = words[2]
        timestamp = parse_telnet_timestamp(words[3])
        tags = parse_tags(words[5:])
        tsdb.add_histogram_point_raw(metric, timestamp, codec_id, words[4],
                                     tags)

    def store_point(self, tsdb, dp: dict) -> None:
        if tsdb.histogram_manager is None:
            raise ValueError("histograms are not configured "
                             "(tsd.core.histograms.config)")
        for field in ("metric", "timestamp", "tags"):
            if field not in dp or dp[field] in (None, "", {}):
                raise ValueError("Missing required field: %s" % field)
        tsdb.add_histogram_point_json(dp["metric"], dp["timestamp"], dp,
                                      dict(dp["tags"]))


class QueryRpc(HttpRpc):
    """/api/query + /last (+ gexp/exp once the expression engines mount)."""

    def __init__(self, stats_registry=None):
        self.stats_registry = stats_registry

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        sub = query.api_subpath()
        endpoint = sub[0] if sub else ""
        if endpoint == "last":
            return self.handle_last_query(tsdb, query)
        if endpoint == "gexp":
            return self.handle_gexp(tsdb, query)
        if endpoint == "exp":
            return self.handle_exp(tsdb, query)
        if endpoint == "explain":
            return self.handle_explain(tsdb, query)
        return self.handle_query(tsdb, query)

    # -- /api/query --

    def handle_query(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST", "DELETE")
        if query.method == "POST":
            ts_query = query.serializer.parse_query_v1()
        else:
            ts_query = self.parse_query_string(tsdb, query)
        if query.method == "DELETE" or ts_query.delete:
            if not tsdb.config.get_bool("tsd.http.query.allow_delete"):
                raise BadRequestError(
                    "Deleting data is not enabled",
                    details="Set tsd.http.query.allow_delete=true")
            ts_query.delete = True
        ts_query.validate()
        latattr.mark("parse")
        # Admission: concurrency permit + costmodel shedding/degrading
        # BEFORE any stats registration or device work.  May raise
        # ShedError (503 + Retry-After) or the deadline's own error;
        # may mutate ts_query down the degradation ladder
        # (permit.degrade_note annotates the 200 below).
        permit = admission.admit(tsdb, ts_query, query, route="api/query")
        # The permit must outlive the response write: releasing it first
        # would let the next queued query start while this one still
        # owns the serializer/socket (checked contract; the with-exit IS
        # the release event).
        # order: response-write before permit-release
        with permit:                                 # order-event: permit-release
            # injectable stall INSIDE the permit: tools/chaos_soak.py
            # --overload wedges the gate with it to prove the queue
            # bounds + sheds instead of stalling
            faults.check("rpc.slow_handler", route="api/query")
            self._serve_admitted(tsdb, query, ts_query, permit)

    def _serve_admitted(self, tsdb, query: HttpQuery, ts_query: TSQuery,
                        permit) -> None:
        """The admitted half of handle_query: stats registration,
        cluster-aware execution, serialization, response."""
        qs = QueryStats(query.remote, ts_query_json(ts_query),
                        query.request.headers)
        trace = obs_trace.active()
        if trace is not None:
            # the span tree rides the completed-query ring
            # (/api/stats/query) alongside the flat milestone marks
            qs.trace = trace
        if self.stats_registry is not None:
            try:
                self.stats_registry.start(qs)
            except DuplicateQueryException as e:
                if tsdb.config.get_bool("tsd.query.allow_simultaneous_duplicates"):
                    qs = None
                else:
                    raise BadRequestError(str(e))
        try:
            # one query, the whole cluster's data when peers are
            # configured (SaltScanner role:
            # /root/reference/src/core/SaltScanner.java:269); peers'
            # fan-out requests, deletes, and tsuid subqueries serve
            # purely locally — see cluster.serve_query
            from opentsdb_tpu.tsd.cluster import serve_query
            exec_stats: dict = {}
            results = serve_query(tsdb, ts_query, query,
                                  exec_stats=exec_stats)
            if ts_query.delete:
                deleted = self._delete(tsdb, ts_query)
            if permit.degrade_note:
                # the ladder coarsened/truncated this query at
                # admission: the 200 must say so out loud, through the
                # same partialResults trailer degraded cluster serving
                # uses (tsd/cluster.py partial_annotation)
                exec_stats["partialResults"] = True
                exec_stats["degraded"] = permit.degrade_note
            if qs is not None:
                qs.mark("aggregationTime")
                qs.stats.update(exec_stats)
            with obs_trace.stage("serialize") as ssp:
                payload = query.serializer.format_query_v1(ts_query,
                                                           results)
                obs_trace.annotate(ssp, results=len(payload))
            latattr.mark("serialize")
            from opentsdb_tpu.tsd.cluster import partial_annotation
            partial = partial_annotation(exec_stats)
            if partial:
                # degraded serving (tsd.network.cluster.partial_results=
                # allow): the 200 must say out loud that peers were
                # missing from the fold — a trailer entry (no "metric"
                # key, so fan-out receivers and statsSummary-aware
                # clients already skip it)
                payload.append(partial)
            if ts_query.show_summary or ts_query.show_stats:
                summary = {
                    "datapoints": sum(len(r.dps) for r in results),
                    "queryTime": round(query.elapsed_ms(), 3),
                }
                if partial:
                    summary.update(partial)
                if trace is not None and ts_query.show_stats:
                    # the span tree inline, as of this instant — the
                    # serialize span above is closed, the http root is
                    # still open and renders elapsed-so-far
                    summary["trace"] = trace.to_json()
                payload.append({"statsSummary": summary})
            query.send_reply(payload)                # order-event: response-write
            REGISTRY.counter(
                "tsd.query.count", "Queries served").labels(
                    status="200").inc()
            REGISTRY.histogram(
                "tsd.query.latency_ms",
                "End-to-end /api/query latency (ms), by tenant").labels(
                    tenant=permit.tenant).observe(
                        query.elapsed_ms(),
                        exemplar=trace.trace_id if trace is not None
                        else None)
            self._maybe_capture_slow(tsdb, query, trace, qs, 200,
                                     permit.tenant)
            if qs is not None and self.stats_registry is not None:
                qs.mark("serializationTime")
                self.stats_registry.finish(qs, 200)
        except Exception as e:
            from opentsdb_tpu.tsd.http import error_status
            status = error_status(e)
            REGISTRY.counter(
                "tsd.query.count", "Queries served").labels(
                    status=str(status)).inc()
            self._maybe_capture_slow(tsdb, query, trace, qs, status,
                                     permit.tenant)
            if qs is not None and self.stats_registry is not None:
                self.stats_registry.finish(qs, status, str(e))
            raise

    @staticmethod
    def _maybe_capture_slow(tsdb, query: HttpQuery, trace, qs,
                            status: int, tenant: str) -> None:
        """Flight-recorder slow-query capture (obs/flightrec.py): a
        query past the absolute/rolling-quantile latency threshold
        retains its span tree + ring slice at /api/diag/slow — no
        showStats required."""
        recorder = getattr(tsdb, "flightrec", None)
        if recorder is None:
            return
        recorder.maybe_capture_slow(
            trace, query.elapsed_ms(), status,
            qs.query if qs is not None else None, tenant)

    # -- /api/query/explain (docs/query_explain.md) --

    def handle_explain(self, tsdb, query: HttpQuery) -> None:
        """The no-dispatch what-if engine: the full /api/query request
        shape (+ what-if overrides) in, the complete routing decision
        tree out — admission preview, rollup/agg-cache/device-cache
        consult verdicts, grid-budget/tiling decision, per-axis
        costmodel pricing, and the stable plan fingerprint the
        executor stamps into flight-recorder ``plan`` events.

        Deliberately NOT behind the admission gate: an overloaded
        daemon must still be explainable (the ambient request deadline
        still bounds the planning walk, and the per-sub QueryBudget
        charges the same scan the executor would)."""
        allowed_methods(query, "GET", "POST")
        if not tsdb.config.get_bool("tsd.explain.enable"):
            raise BadRequestError(
                "The explain endpoint is disabled", status=404,
                details="Set tsd.explain.enable=true")
        from opentsdb_tpu.query import explain as explain_mod
        if query.method == "POST":
            ts_query = query.serializer.parse_query_v1()
            raw_what_if = (query.json_body() or {}).get("whatIf") or {}
        else:
            ts_query = self.parse_query_string(tsdb, query)
            raw_what_if = {}
            for spec in query.get_query_string_params("what_if"):
                if "=" not in spec:
                    raise BadRequestError(
                        "what_if must be key=value, got %r" % spec)
                k, v = spec.split("=", 1)
                raw_what_if[k.strip()] = v
        ts_query.validate()
        latattr.mark("parse")
        try:
            what_if = explain_mod.parse_what_if(raw_what_if)
        except explain_mod.WhatIfError as e:
            raise BadRequestError(str(e))
        start = time.perf_counter()
        try:
            with obs_trace.stage("explain") as span:
                report = explain_mod.explain_query(tsdb, ts_query,
                                                   what_if)
                obs_trace.annotate(
                    span, sub_queries=len(report["subQueries"]),
                    what_if=bool(what_if.active))
            # the whole no-dispatch planning walk is "plan" time
            latattr.mark("plan")
        except Exception:
            REGISTRY.counter(
                "tsd.query.explain.requests",
                "Explain requests served, by outcome").labels(
                    outcome="error").inc()
            raise
        query.send_reply(report)
        REGISTRY.counter(
            "tsd.query.explain.requests",
            "Explain requests served, by outcome").labels(
                outcome="ok").inc()
        REGISTRY.histogram(
            "tsd.query.explain.latency_ms",
            "Explain planning latency (ms) — the no-dispatch walk"
        ).observe((time.perf_counter() - start) * 1e3)

    def _delete(self, tsdb, ts_query: TSQuery) -> int:
        """Drop the matched datapoints after serving them (delete flag).

        Deletes from the stores the query actually read: the reference
        issues DeleteRequests for the scanned rows, which are rollup-table
        rows for rollup-served queries (TsdbQuery delete path)."""
        runner = tsdb.new_query_runner()
        fix_dups = tsdb.config.fix_duplicates
        deleted = 0
        for sub in ts_query.queries:
            for seg in runner._plan_segments(ts_query, sub):
                stores = []
                if seg.kind == "raw":
                    stores.append(tsdb.store)
                else:
                    stores.append(seg.lane)
                    if seg.count_lane is not None:
                        stores.append(seg.count_lane)
                for store in stores:
                    for series, _ in runner._resolve_series(sub, store):
                        deleted += series.delete_range(
                            seg.start_ms, seg.end_ms, fix_dups)
                        store.notify_mutation(series.key.metric,
                                              seg.start_ms, seg.end_ms)
        return deleted

    def parse_query_string(self, tsdb, query: HttpQuery) -> TSQuery:
        """GET grammar (QueryRpc.parseQuery :521-535)."""
        ts_query = TSQuery(
            start=query.required_query_string_param("start"),
            end=query.get_query_string_param("end"),
            timezone=query.get_query_string_param("tz"),
            ms_resolution=query.has_query_string_param("ms"),
            show_tsuids=query.has_query_string_param("show_tsuids"),
            no_annotations=query.has_query_string_param("no_annotations"),
            global_annotations=query.has_query_string_param(
                "global_annotations"),
            show_summary=query.has_query_string_param("show_summary"),
            show_stats=query.has_query_string_param("show_stats"),
            show_query=query.has_query_string_param("show_query"),
            padding=query.has_query_string_param("padding"),
            use_calendar=query.has_query_string_param("use_calendar"),
        )
        for m in query.get_query_string_params("m"):
            ts_query.queries.append(parse_m_subquery(m))
        for t in query.get_query_string_params("tsuid"):
            ts_query.queries.append(parse_tsuid_subquery(t))
        if not ts_query.queries:
            raise BadRequestError.missing_parameter("m or tsuid")
        return ts_query

    # -- /api/query/last (QueryRpc.handleLastDataPointQuery :346) --

    def handle_last_query(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        if query.method == "POST":
            body = query.json_body()
            specs = body.get("queries", [])
            resolve = bool(body.get("resolveNames", False))
            back_scan = int(body.get("backScan", 0))
        else:
            specs = []
            for ts_spec in query.get_query_string_params("timeseries"):
                specs.append({"metric": ts_spec})
            for t in query.get_query_string_params("tsuids"):
                specs.append({"tsuids": t.split(",")})
            resolve = query.has_query_string_param("resolve")
            back_scan = int(query.get_query_string_param("back_scan") or 0)
        if not specs:
            raise BadRequestError.missing_parameter("timeseries or tsuids")
        cutoff_ms = None
        if back_scan > 0:
            cutoff_ms = int(time.time() * 1000) - back_scan * 3_600_000
        results = []
        for spec in specs:
            results.extend(self._last_points(tsdb, spec, resolve, cutoff_ms))
        query.send_reply(
            query.serializer.format_last_point_query_v1(results))

    def _last_points(self, tsdb, spec: dict, resolve: bool,
                     cutoff_ms: int | None) -> list[dict]:
        from opentsdb_tpu.query.filters import parse_metric_with_filters
        out = []
        if spec.get("tsuids"):
            wanted = {t.upper() for t in spec["tsuids"]}
            chosen = [s for s in tsdb.store.all_series()
                      if tsdb.tsuid(s.key) in wanted]
        else:
            filters: list = []
            metric = parse_metric_with_filters(spec["metric"], filters)
            try:
                metric_uid = tsdb.metrics.get_id(metric)
            except NoSuchUniqueName:
                raise BadRequestError("No such name for 'metrics': '%s'"
                                      % metric, status=404)
            chosen = []
            for series in tsdb.store.series_for_metric(metric_uid):
                tags = tsdb.resolve_key_tags(series.key)
                if all(f.match(tags) for f in filters):
                    chosen.append(series)
        for series in chosen:
            ts, fv, iv, isint = series.arrays()
            if len(ts) == 0:
                continue
            last_ts = int(ts[-1])
            if cutoff_ms is not None and last_ts < cutoff_ms:
                continue
            value = int(iv[-1]) if isint[-1] else float(fv[-1])
            entry = {
                "timestamp": last_ts,
                "value": str(value),
                "tsuid": tsdb.tsuid(series.key),
            }
            if resolve or spec.get("metric"):
                entry["metric"] = tsdb.metrics.get_name(series.key.metric)
                entry["tags"] = tsdb.resolve_key_tags(series.key)
            out.append(entry)
        return out

    # -- expression endpoints (mounted by the expression engine) --

    def handle_gexp(self, tsdb, query: HttpQuery) -> None:
        try:
            from opentsdb_tpu.expression.gexp import handle_gexp_query
        except ImportError:
            raise BadRequestError("The gexp endpoint is not available",
                                  status=501)
        handle_gexp_query(tsdb, query)

    def handle_exp(self, tsdb, query: HttpQuery) -> None:
        try:
            from opentsdb_tpu.expression.executor import handle_exp_query
        except ImportError:
            raise BadRequestError("The exp endpoint is not available",
                                  status=501)
        handle_exp_query(tsdb, query)


def ts_query_json(ts_query: TSQuery) -> dict:
    return {
        "start": str(ts_query.start),
        "end": str(ts_query.end) if ts_query.end else None,
        "queries": [sub.to_json() for sub in ts_query.queries],
    }


class SuggestRpc(HttpRpc):
    """/api/suggest + /suggest (SuggestRpc.java)."""

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        if (query.method == "POST"
                and "json" in (query.request.header("content-type") or "")):
            body = query.serializer.parse_suggest_v1()
            stype = body.get("type")
            prefix = body.get("q", "")
            max_results = int(body.get("max", 25))
        else:
            stype = query.required_query_string_param("type")
            prefix = query.get_query_string_param("q") or ""
            mx = query.get_query_string_param("max")
            try:
                max_results = int(mx) if mx else 25
            except ValueError:
                raise BadRequestError("Unable to parse 'max' as a number")
        if stype == "metrics":
            results = tsdb.suggest_metrics(prefix, max_results)
        elif stype == "tagk":
            results = tsdb.suggest_tagk(prefix, max_results)
        elif stype == "tagv":
            results = tsdb.suggest_tagv(prefix, max_results)
        else:
            raise BadRequestError("Invalid 'type' parameter:" + str(stype))
        query.send_reply(query.serializer.format_suggest_v1(results))


class AnnotationRpc(HttpRpc):
    """/api/annotation + /api/annotations (AnnotationRpc.java)."""

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        sub = query.api_subpath()
        if query.path.startswith("api/annotations") or (
                sub and sub[0] == "bulk"):
            return self._bulk(tsdb, query)
        method = query.method
        if method == "GET":
            self._get(tsdb, query)
        elif method in ("POST", "PUT"):
            self._upsert(tsdb, query)
        elif method == "DELETE":
            self._delete(tsdb, query)
        else:
            raise BadRequestError("Method not allowed", status=405)

    def _params(self, query: HttpQuery) -> dict:
        if query.request.body:
            return query.serializer.parse_annotation_v1()
        out = {}
        for name in ("tsuid", "description", "notes"):
            v = query.get_query_string_param(name)
            if v is not None:
                out[name] = v
        for name in ("start_time", "end_time"):
            v = query.get_query_string_param(name)
            if v is not None:
                out["startTime" if name == "start_time" else "endTime"] = v
        return out

    @staticmethod
    def _note_from(params: dict) -> Annotation:
        start = params.get("startTime")
        if start in (None, ""):
            raise BadRequestError("Missing start time")
        return Annotation(
            start_time=int(start),
            end_time=int(params.get("endTime") or 0),
            tsuid=(params.get("tsuid") or "").upper(),
            description=params.get("description") or "",
            notes=params.get("notes") or "",
            custom=params.get("custom"))

    def _get(self, tsdb, query: HttpQuery) -> None:
        params = self._params(query)
        start = params.get("startTime")
        if start in (None, ""):
            raise BadRequestError("Missing start time")
        tsuid = (params.get("tsuid") or "").upper()
        notes = [a for a in tsdb.store.get_annotations(
                    tsuid, int(start), int(start))
                 if a.start_time == int(start)]
        if not notes:
            raise BadRequestError(
                "Unable to locate annotation in storage", status=404)
        query.send_reply(
            query.serializer.format_annotation_v1(notes[0].to_json()))

    def _upsert(self, tsdb, query: HttpQuery) -> None:
        note = self._note_from(self._params(query))
        tsdb.store.delete_annotation(note.tsuid, note.start_time)
        tsdb.add_annotation(note)
        query.send_reply(query.serializer.format_annotation_v1(
            note.to_json()))

    def _delete(self, tsdb, query: HttpQuery) -> None:
        params = self._params(query)
        start = params.get("startTime")
        if start in (None, ""):
            raise BadRequestError("Missing start time")
        tsuid = (params.get("tsuid") or "").upper()
        if tsdb.store.delete_annotation(tsuid, int(start)):
            if tsdb.search_plugin is not None:
                tsdb.search_plugin.delete_annotation(
                    Annotation(start_time=int(start), tsuid=tsuid))
            query.send_status_only(204)
        else:
            raise BadRequestError(
                "Unable to locate annotation in storage", status=404)

    def _bulk(self, tsdb, query: HttpQuery) -> None:
        method = query.method
        if method in ("POST", "PUT"):
            notes = [self._note_from(p)
                     for p in query.serializer.parse_annotation_bulk_v1()]
            for n in notes:
                tsdb.store.delete_annotation(n.tsuid, n.start_time)
                tsdb.add_annotation(n)
            query.send_reply(query.serializer.format_annotations_v1(
                [n.to_json() for n in notes]))
        elif method == "DELETE":
            start = query.get_query_string_param("start_time")
            end = query.get_query_string_param("end_time")
            if query.request.body:
                body = query.json_body()
                start = body.get("startTime", start)
                end = body.get("endTime", end)
                tsuids = body.get("tsuids")
                global_notes = bool(body.get("global", False))
            else:
                tsuids_param = query.get_query_string_param("tsuids")
                tsuids = tsuids_param.split(",") if tsuids_param else None
                global_notes = query.has_query_string_param("global")
            if start in (None, ""):
                raise BadRequestError("Missing start time")
            end_ms = int(end) if end not in (None, "") else int(
                time.time() * 1000)
            norm_tsuids = [t.upper() for t in tsuids] if tsuids else None
            if tsdb.search_plugin is not None:
                # De-index exactly what delete_annotation_range will drop —
                # its precedence is global > tsuids > everything.
                if global_notes:
                    pools = [""]
                elif norm_tsuids:
                    pools = norm_tsuids
                else:
                    pools = tsdb.store.annotation_keys()
                for t in pools:
                    for note in tsdb.store.get_annotations(
                            t, int(start), end_ms):
                        tsdb.search_plugin.delete_annotation(note)
            count = tsdb.store.delete_annotation_range(
                norm_tsuids, int(start), end_ms, global_notes)
            query.send_reply({"totalDeleted": count})
        else:
            raise BadRequestError("Method not allowed", status=405)


class UniqueIdRpc(HttpRpc):
    """/api/uid/{assign,rename,uidmeta,tsmeta} (UniqueIdRpc.java:63-77)."""

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        sub = query.api_subpath()
        endpoint = sub[0] if sub else ""
        if endpoint == "assign":
            self._assign(tsdb, query)
        elif endpoint == "rename":
            self._rename(tsdb, query)
        elif endpoint == "uidmeta":
            self._uidmeta(tsdb, query)
        elif endpoint == "tsmeta":
            self._tsmeta(tsdb, query)
        else:
            raise BadRequestError(
                "Other UID endpoints have not been implemented yet",
                status=501,
                details="Accessed endpoint: /api/uid/%s" % endpoint)

    def _assign(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        if query.method == "POST" and query.request.body:
            kinds = query.serializer.parse_uid_assign_v1()
        else:
            kinds = {}
            for kind in ("metric", "tagk", "tagv"):
                v = query.get_query_string_param(kind)
                if v:
                    kinds[kind] = v.split(",")
        if not kinds:
            raise BadRequestError("Missing values to assign UIDs")
        response: dict = {}
        any_errors = False
        for kind, names in kinds.items():
            good: dict[str, str] = {}
            errors: dict[str, str] = {}
            for name in names:
                try:
                    uid = tsdb.assign_uid(kind, name)
                    table = tsdb.uid_table(kind)
                    good[name] = table.uid_to_hex(uid)
                except ValueError as e:
                    errors[name] = str(e)
                    any_errors = True
            response[kind] = good
            response[kind + "_errors"] = errors
        query.send_reply(query.serializer.format_uid_assign_v1(response),
                         status=400 if any_errors else 200)

    def _rename(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "POST", "PUT")
        if query.request.body:
            body = query.serializer.parse_uid_rename_v1()
        else:
            body = {k: query.get_query_string_param(k)
                    for k in ("metric", "tagk", "tagv", "name")}
            body = {k: v for k, v in body.items() if v}
        name = body.pop("name", None)
        if not name:
            raise BadRequestError("Missing or empty new name")
        kinds = [(k, v) for k, v in body.items()
                 if k in ("metric", "tagk", "tagv")]
        if len(kinds) != 1:
            raise BadRequestError("Missing or invalid UID type/name to "
                                  "rename")
        kind, old_name = kinds[0]
        try:
            tsdb.rename_uid(kind, old_name, name)
        except ValueError as e:
            query.send_reply({"error": str(e), "result": "false"})
            return
        query.send_reply(query.serializer.format_uid_rename_v1(
            {"result": "true"}))

    def _uidmeta(self, tsdb, query: HttpQuery) -> None:
        try:
            from opentsdb_tpu.meta.rpc import handle_uidmeta
        except ImportError:
            raise BadRequestError("uidmeta is not available", status=501)
        handle_uidmeta(tsdb, query)

    def _tsmeta(self, tsdb, query: HttpQuery) -> None:
        try:
            from opentsdb_tpu.meta.rpc import handle_tsmeta
        except ImportError:
            raise BadRequestError("tsmeta is not available", status=501)
        handle_tsmeta(tsdb, query)

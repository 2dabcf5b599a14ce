"""RPC route table: command/path -> handler, per operation mode.

Reference behavior: /root/reference/src/tsd/RpcManager.java (:251-364
initializeBuiltinRpcs — the authoritative route list per READWRITE/READONLY/
WRITEONLY mode with tsd.core.enable_api / enable_ui / no_diediedie gates)
and RpcHandler.java dispatch.
"""

from __future__ import annotations

import logging
import math
import threading
import time

from opentsdb_tpu.obs import latattr
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.query import limits
from opentsdb_tpu.stats.query_stats import QueryStatsRegistry
from opentsdb_tpu.tsd import admin_rpcs, rpcs
from opentsdb_tpu.tsd.admission import DEADLINE_HEADER
from opentsdb_tpu.tsd.http import (BadRequestError, HttpQuery, HttpRequest,
                                   error_status)
from opentsdb_tpu.tsd.serializers import serializer_for

LOG = logging.getLogger("tsd.rpc")


class RpcManager:
    """Builds and owns the telnet + HTTP route tables."""

    def __init__(self, tsdb, server=None, shutdown_cb=None):
        self.tsdb = tsdb
        self.server = server
        self.shutdown_cb = shutdown_cb or (lambda: None)
        self.query_stats = QueryStatsRegistry()
        self.telnet_commands: dict[str, rpcs.TelnetRpc] = {}
        self.http_commands: dict[str, rpcs.HttpRpc] = {}
        self._initialize_builtin_rpcs()
        self.telnet_plugins: dict[str, rpcs.TelnetRpc] = {}
        self.http_plugins: dict[str, rpcs.HttpRpc] = {}
        # error-envelope accounting (surfaced as http.errors by
        # /api/stats): handler failures must leave an operator-visible
        # trail, not just a client-side status code
        self._err_lock = threading.Lock()
        # guarded-by: _err_lock
        self.client_errors = 0          # 4xx envelopes sent
        self.server_errors = 0          # 5xx envelopes sent  # guarded-by: _err_lock
        # register as a stats source on the TSDB so the self-report
        # loop (obs/selfreport.py) sees the same ingest/error counters
        # /api/stats serves; keyed so a replacement manager supersedes
        if not hasattr(tsdb, "stats_hooks"):
            tsdb.stats_hooks = {}
        tsdb.stats_hooks["rpc_manager"] = self._stats_hook

    def _count_error(self, status: int) -> None:
        with self._err_lock:
            if status >= 500:
                self.server_errors += 1
            else:
                self.client_errors += 1

    def collect_stats(self, collector) -> None:
        with self._err_lock:
            client, server = self.client_errors, self.server_errors
        collector.record("http.errors", client, "family=4xx")
        collector.record("http.errors", server, "family=5xx")

    def _stats_hook(self, collector) -> None:
        """The self-report view of this manager: ingest RPC counters,
        error envelopes, and the server's connection stats — exactly
        what StatsRpc folds in for /api/stats."""
        for rpc in self.ingest_rpcs:
            rpc.collect_stats(collector)
        self.collect_stats(collector)
        if self.server is not None:
            self.server.collect_stats(collector)

    def _initialize_builtin_rpcs(self) -> None:
        cfg = self.tsdb.config
        mode = self.tsdb.mode             # rw / ro / wo
        enable_api = cfg.get_bool("tsd.core.enable_api")
        enable_ui = cfg.get_bool("tsd.core.enable_ui")
        enable_die = not cfg.get_bool("tsd.no_diediedie")

        telnet = self.telnet_commands
        http = self.http_commands

        stats = admin_rpcs.StatsRpc(self.query_stats)
        aggregators = admin_rpcs.ListAggregators()
        dropcaches = admin_rpcs.DropCachesRpc()
        version = admin_rpcs.VersionRpc()

        telnet["stats"] = stats
        telnet["dropcaches"] = dropcaches
        telnet["version"] = version
        telnet["exit"] = admin_rpcs.ExitRpc()
        telnet["help"] = admin_rpcs.HelpRpc(lambda: self.telnet_commands)

        if enable_ui:
            http["aggregators"] = aggregators
            http["logs"] = admin_rpcs.LogsRpc()
            http["stats"] = stats
            http["version"] = version
        if enable_api:
            http["api/aggregators"] = aggregators
            http["api/config"] = admin_rpcs.ShowConfig()
            http["api/dropcaches"] = dropcaches
            http["api/stats"] = stats
            http["api/version"] = version
            http["api/serializers"] = admin_rpcs.SerializersRpc()
            # flight recorder + health engine (obs/flightrec.py,
            # obs/health.py): /api/diag, /api/diag/slow,
            # /api/diag/health — mounted in every mode like /api/stats
            http["api/diag"] = admin_rpcs.DiagRpc()
            if getattr(self.tsdb, "replication", None) is not None:
                # WAL-shipping replication wire (tsd/replication.py):
                # tail/ship/status, mounted in every mode — a ro
                # replica must still accept ships and serve tails.
                # Exempt from the query admission gate; bounded by its
                # own tsd.replication.max_inflight_mb byte gate.
                from opentsdb_tpu.tsd.replication import ReplicationRpc
                http["api/replication"] = ReplicationRpc()

        put = rpcs.PutDataPointRpc()
        rollups = rpcs.RollupDataPointRpc()
        histos = rpcs.HistogramDataPointRpc()
        suggest = rpcs.SuggestRpc()
        annotation = rpcs.AnnotationRpc()
        staticfile = admin_rpcs.StaticFileRpc()
        self.put_rpc = put
        self.ingest_rpcs = [put, rollups, histos]

        writes = mode in ("rw", "wo")
        reads = mode in ("rw", "ro")

        if writes:
            telnet["put"] = put
            telnet["rollup"] = rollups
            telnet["histogram"] = histos
            if enable_api:
                http["api/annotation"] = annotation
                http["api/annotations"] = annotation
                http["api/put"] = put
                http["api/rollup"] = rollups
                http["api/histogram"] = histos
                http["api/tree"] = admin_rpcs.TreeRpc()
                http["api/uid"] = rpcs.UniqueIdRpc()
        if reads:
            if enable_ui:
                http[""] = admin_rpcs.HomePage()
                http["s"] = staticfile
                http["favicon.ico"] = staticfile
                http["suggest"] = suggest
                try:
                    from opentsdb_tpu.tsd.graph import GraphHandler
                    http["q"] = GraphHandler()
                except ImportError:
                    pass
            if enable_api:
                http["api/query"] = rpcs.QueryRpc(self.query_stats)
                http["api/search"] = admin_rpcs.SearchRpc()
                http["api/suggest"] = suggest
                http.setdefault("api/uid", rpcs.UniqueIdRpc())
                http.setdefault("api/annotation", annotation)
                http.setdefault("api/annotations", annotation)

        if enable_die:
            die = admin_rpcs.DieDieDie(self.shutdown_cb)
            telnet["diediedie"] = die
            if enable_ui:
                http["diediedie"] = die

    # -- plugin registration (RpcManager.initializeRpcPlugins analog) --

    def register_telnet_plugin(self, command: str, handler) -> None:
        if command in self.telnet_commands:
            raise ValueError("Duplicate telnet command: %s" % command)
        self.telnet_commands[command] = handler

    def register_http_plugin(self, route: str, handler) -> None:
        route = route.strip("/")
        if route in self.http_plugins:
            raise ValueError("Duplicate HTTP plugin route: %s" % route)
        self.http_plugins[route] = handler

    # -- dispatch (RpcHandler.messageReceived :125) --

    def handle_telnet(self, conn, line: str) -> str | None:
        words = line.split()
        if not words:
            return None
        handler = self.telnet_commands.get(words[0])
        if handler is None:
            return "unknown command: %s.  Try `help'.\n" % words[0]
        return handler.execute_telnet(self.tsdb, conn, words)

    def handle_telnet_batch(self, conn, block: bytes) -> str:
        """Consecutive telnet put lines batched by the server loop.

        Dispatches to the put handler's batch arm (native columnar
        ingest) when one is installed; otherwise — e.g. read-only mode
        drops `put` from the table — each line walks handle_telnet so
        per-line replies ("unknown command: put") stay identical.
        """
        from opentsdb_tpu.tsd.rpcs import PutDataPointRpc
        handler = self.telnet_commands.get("put")
        if type(handler) is PutDataPointRpc:
            return handler.execute_telnet_batch(self.tsdb, conn, block,
                                                self)
        return PutDataPointRpc._telnet_lines_one_by_one(conn, block, self)

    def handle_http(self, request: HttpRequest,
                    remote: str = "unknown") -> "HttpQuery":
        """Trace + metrics envelope around the route dispatch.

        When tsd.trace.enable is on every request gets a span tree
        rooted here; an X-TSDB-Trace-Id header (a peer's fan-out, or
        an operator correlating across TSDs) is adopted as the trace
        id, so one clustered query is one id across every host.

        One request-scoped Deadline is minted here — from
        tsd.query.timeout and/or the client's X-TSDB-Deadline-Ms
        header (whichever is smaller; a coordinating TSD forwards its
        remainder so a peer aborts when the coordinator has already
        given up) — activated as the responder thread's ambient
        deadline (query/limits.py) for every QueryBudget, retry policy,
        and admission wait downstream, and bound to the server's
        cancellation handle so a client disconnect flips its token."""
        entered = time.perf_counter()
        # the loop's stamps of this request (tsd/server.py; None for a
        # caller that is not the event loop)
        edges = getattr(request, "edges", None)
        cfg = self.tsdb.config
        trace = None
        if cfg.get_bool("tsd.trace.enable"):
            trace = obs_trace.Trace(
                "http", trace_id=request.header(obs_trace.TRACE_HEADER))
            trace.root.tags["method"] = request.method
            trace.root.tags["path"] = request.path
            obs_trace.activate(trace)
        deadline = self._mint_deadline(request)
        limits.activate_deadline(deadline)
        handle = getattr(request, "cancel_handle", None)
        if handle is not None:
            handle.bind(deadline)
        # always-on latency attribution (obs/latattr.py): stamps on
        # EVERY request, independent of tsd.trace.enable — the engine
        # is per-TSDB so library/test managers without one just carry
        # inert ambient stamps
        stamps = None
        if getattr(self.tsdb, "latattr", None) is not None:
            stamps = latattr.PhaseStamps(
                trace_id=trace.trace_id if trace is not None else None)
            if edges is not None:
                stamps.queue_ms = (entered - edges.queued) * 1e3
            latattr.activate(stamps)
        start = time.perf_counter()
        try:
            query = self._dispatch_http(request, remote)
        finally:
            limits.deactivate_deadline()
            if stamps is not None:
                latattr.deactivate()
            if trace is not None:
                obs_trace.deactivate()
                trace.finish()
        # route label clamped to the registered table: client-chosen
        # paths must not mint unbounded label cardinality
        route = query.base_route()
        if route not in self.http_commands:
            route = "other"
        if stamps is not None:
            # the trailing mark absorbs the handler tail (reply
            # buffering, error envelope) so the phase deltas sum to
            # the handler wall time
            stamps.mark("flush", last=True)
            stamps.route = route
        if edges is not None:
            # the handler's part ends at its last mark; what follows it
            # here is the loop's `resume`
            edges.entered = entered
            edges.returned = time.perf_counter()
            edges.route = route
            edges.trace_id = trace.trace_id if trace is not None else None
        if stamps is not None:
            self.tsdb.latattr.observe(stamps)
        status = query.response.status if query.response is not None else 0
        REGISTRY.counter(
            "tsd.http.requests", "HTTP requests served").labels(
                route=route, status=str(status)).inc()
        if query.response is not None and query.response.body:
            REGISTRY.counter(
                "tsd.http.response_bytes", "Response body bytes").labels(
                    route=route).inc(len(query.response.body))
        REGISTRY.histogram(
            "tsd.http.latency_ms", "HTTP request latency (ms)").labels(
                route=route).observe(
                    (time.perf_counter() - start) * 1e3,
                    exemplar=trace.trace_id if trace is not None
                    else None)
        return query

    def _mint_deadline(self, request: HttpRequest) -> "limits.Deadline":
        """min(tsd.query.timeout, X-TSDB-Deadline-Ms); 0/absent on both
        sides mints an unbounded deadline — still the cancellation
        token every check site observes."""
        timeout_ms = float(self.tsdb.config.get_int("tsd.query.timeout"))
        raw = request.header(DEADLINE_HEADER)
        if raw:
            try:
                client_ms = float(raw)
            except ValueError:
                client_ms = 0.0
            if not math.isfinite(client_ms):
                # "inf"/"1e309" parse to float inf — a bounded deadline
                # must stay finite (int(remaining) travels to peers)
                client_ms = 0.0
            if client_ms > 0:
                timeout_ms = (min(timeout_ms, client_ms)
                              if timeout_ms > 0 else client_ms)
        return limits.Deadline(max(timeout_ms, 0.0))

    def _dispatch_http(self, request: HttpRequest,
                       remote: str = "unknown") -> "HttpQuery":
        query = HttpQuery(self.tsdb, request, remote)
        if request.method == "OPTIONS":
            # CORS preflight (RpcHandler.java:204-223): 200 + allow headers
            # when the origin is whitelisted, 400 without dispatching
            # otherwise; no-Origin OPTIONS falls through to a 405.
            if self._preflight(query):
                return query
            if query.request.header("origin"):
                self._count_error(400)
                query.send_error(BadRequestError(
                    "CORS domain not allowed",
                    details="Origin is not in tsd.http.request.cors_domains"))
                return query
        auth = self.tsdb.authentication
        if auth is not None:
            # Per-request HTTP auth (AuthenticationChannelHandler HTTP arm).
            from opentsdb_tpu.auth import AuthStatus
            try:
                state = auth.authenticate_http(None, request)
            except Exception:
                LOG.exception("Authentication plugin failed on HTTP "
                              "request from %s; failing closed", remote)
                state = None
            if state is None or state.status != AuthStatus.SUCCESS:
                self._count_error(401)
                query.send_error(BadRequestError(
                    "Authentication failed", status=401))
                return query
            query.auth_state = state
        try:
            query.serializer = serializer_for(query)
            # plugin routes live under /plugin/<route>
            parts = query.path.split("/")
            if parts and parts[0] == "plugin":
                # Longest registered prefix wins (HttpRpcPlugin routes may
                # span several path segments).
                plugin = None
                for depth in range(len(parts) - 1, 0, -1):
                    plugin = self.http_plugins.get("/".join(parts[1:depth + 1]))
                    if plugin is not None:
                        break
                if plugin is None:
                    raise BadRequestError("No plugin at route", status=404)
                plugin.execute_http(self.tsdb, query)
            else:
                handler = self.http_commands.get(query.base_route())
                if handler is None:
                    raise BadRequestError(
                        "Page not found", status=404,
                        details="The requested page [%s] was not found"
                                % request.path)
                handler.execute_http(self.tsdb, query)
            if query.response is None:
                raise RuntimeError("handler sent no response")
        except Exception as e:  # uniform error envelope
            status = error_status(e)
            self._count_error(status)
            recorder = getattr(self.tsdb, "flightrec", None)
            if recorder is not None:
                # deadline expiries/cancellations and 5xx envelopes are
                # flight-recorder events: a wedge's last moments must
                # be reconstructible from the ring alone
                if isinstance(e, limits.QueryDeadlineExpired):
                    recorder.record("deadline", outcome="expired",
                                    path=request.path, status=status)
                elif isinstance(e, limits.QueryCancelledException):
                    recorder.record("deadline", outcome="cancelled",
                                    path=request.path, status=status)
                if status >= 500:
                    recorder.record("http_error", status=status,
                                    path=request.path)
            if status >= 500 and not isinstance(e, limits.QueryException):
                # expected client mistakes (4xx) stay quiet, and so do
                # deliberate 5xx query verdicts (admission sheds,
                # cancellations — they carry their own status and are
                # counted on their own metrics); an internal failure
                # gets the full trace in the daemon log
                LOG.exception("handler for [%s] from %s failed with an "
                              "internal error", request.path, remote)
            query.send_error(e)
        self._apply_cors(query)
        return query

    def _origin_allowed(self, origin: str | None) -> bool:
        if not origin:
            return False
        domains = self.tsdb.config.get_string(
            "tsd.http.request.cors_domains").strip()
        if not domains:
            return False
        allowed = {d.strip().lower() for d in domains.split(",") if d.strip()}
        return "*" in allowed or origin.lower() in allowed

    def _preflight(self, query: HttpQuery) -> bool:
        """OPTIONS preflight; returns True when this produced the response."""
        origin = query.request.header("origin")
        if not self._origin_allowed(origin):
            return False
        query.send_status_only(200)
        self._apply_cors(query)
        return True

    def _apply_cors(self, query: HttpQuery) -> None:
        """tsd.http.request.cors_domains handling (RpcHandler :249-320)."""
        origin = query.request.header("origin")
        if query.response is None or not self._origin_allowed(origin):
            return
        query.response.headers["Access-Control-Allow-Origin"] = origin
        query.response.headers["Access-Control-Allow-Methods"] = \
            "GET, POST, PUT, DELETE"
        headers = self.tsdb.config.get_string(
            "tsd.http.request.cors_headers").strip()
        if headers:
            query.response.headers["Access-Control-Allow-Headers"] = headers

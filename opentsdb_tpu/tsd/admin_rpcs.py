"""Admin/observability RPC handlers: stats, version, config, aggregators,
serializers, dropcaches, logs, home page, static files.

Reference behavior: /root/reference/src/tsd/RpcManager.java (:585-740
builtin handlers: Version, ListAggregators, HomePage, Serializers, Help,
Exit, DieDieDie), StatsRpc.java (:86-97 threads/jvm/query/region_clients
sub-endpoints), DropCachesRpc.java, LogsRpc.java (:85 in-memory ring
buffer), StaticFileRpc.java.
"""

from __future__ import annotations

import collections
import logging
import os
import sys
import threading
import time

from opentsdb_tpu import build_data
from opentsdb_tpu.ops.aggregators import agg_names
from opentsdb_tpu.stats import StatsCollector
from opentsdb_tpu.tsd.http import BadRequestError, HttpQuery
from opentsdb_tpu.tsd.rpcs import HttpRpc, TelnetRpc, allowed_methods
from opentsdb_tpu.tsd.serializers import SERIALIZERS
from opentsdb_tpu.tsd.ui import UI_PAGE as _HOME_PAGE


class VersionRpc(TelnetRpc, HttpRpc):
    def execute_telnet(self, tsdb, conn, words) -> str:
        return build_data.revision_string() + "\n" + \
            build_data.build_string() + "\n"

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        version = build_data.version_map()
        if query.api_version > 0:
            query.send_reply(query.serializer.format_version_v1(version))
        elif query.request.uri.endswith("json"):
            query.send_reply(version)
        else:
            query.send_reply(build_data.revision_string() + "\n"
                             + build_data.build_string() + "\n",
                             content_type="text/plain")


class ListAggregators(HttpRpc):
    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        names = agg_names()
        if query.api_version > 0:
            query.send_reply(query.serializer.format_aggregators_v1(names))
        else:
            query.send_reply(names)


class SerializersRpc(HttpRpc):
    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        descriptors = [cls.descriptor() for cls in SERIALIZERS.values()]
        query.send_reply(
            query.serializer.format_serializers_v1(descriptors))


class ShowConfig(HttpRpc):
    """/api/config + /api/config/filters."""

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        sub = query.api_subpath()
        if sub and sub[0] == "filters":
            from opentsdb_tpu.query.filters import FILTER_TYPES
            out = {}
            for name, cls in sorted(FILTER_TYPES.items()):
                out[name] = {
                    "examples": getattr(cls, "examples", ""),
                    "description": (cls.__doc__ or "").strip(),
                }
            query.send_reply(out)
            return
        query.send_reply(query.serializer.format_config_v1(
            tsdb.config.as_map(obfuscate=True)))


class DropCachesRpc(TelnetRpc, HttpRpc):
    def _drop(self, tsdb) -> None:
        tsdb.store.drop_caches()
        if tsdb.device_cache is not None:
            tsdb.device_cache.invalidate()
        if tsdb.agg_cache is not None:
            tsdb.agg_cache.invalidate()
        if tsdb.rollup_lanes is not None:
            tsdb.rollup_lanes.invalidate()
        # UID cachs are authoritative dictionaries here (no backing store),
        # so unlike UniqueId.dropCaches they must NOT be emptied.

    def execute_telnet(self, tsdb, conn, words) -> str:
        self._drop(tsdb)
        return "Caches dropped.\n"

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET", "POST")
        self._drop(tsdb)
        if query.api_version > 0:
            query.send_reply(query.serializer.format_dropcaches_v1(
                {"status": "200", "message": "Caches dropped"}))
        else:
            query.send_reply("Caches dropped.\n", content_type="text/plain")


class StatsRpc(TelnetRpc, HttpRpc):
    """/api/stats (+/query, /jvm, /threads, /region_clients) + telnet stats."""

    def __init__(self, stats_registry=None):
        self.stats_registry = stats_registry

    def _collect(self, tsdb) -> StatsCollector:
        """One stats walk: TSDB counters, cluster breakers, rollup
        lanes, plus every registered stats hook (the RpcManager's hook
        covers ingest RPCs, error envelopes, and the server).  Shared
        with the self-report loop — obs/selfreport.py — so /api/stats
        and the dogfooded tsd.* series can never diverge."""
        from opentsdb_tpu.obs.selfreport import collect_all
        return collect_all(tsdb)

    def execute_telnet(self, tsdb, conn, words) -> str:
        return self._collect(tsdb).emit_ascii()

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        sub = query.api_subpath()
        endpoint = sub[0] if sub else ""
        if endpoint == "prometheus":
            # text exposition (version 0.0.4) beside the JSON surface:
            # registry counters/gauges/latency histograms first, then
            # every StatsCollector record (device cache, breakers,
            # compaction, ingest counters) as gauges — the records
            # already carry the host tag, so nothing re-registers them.
            # tsd.diag.exemplars additionally links histogram tail
            # buckets to flight-recorder trace ids via comment lines
            # (the format stays 0.0.4-parseable).
            from opentsdb_tpu.obs.registry import REGISTRY
            text = REGISTRY.prometheus_text(
                extra_records=self._collect(tsdb).records,
                exemplars=tsdb.config.get_bool("tsd.diag.exemplars"))
            query.send_reply(
                text,
                content_type="text/plain; version=0.0.4; charset=utf-8")
            return
        if endpoint == "query":
            if self.stats_registry is None:
                raise BadRequestError("Query stats are not enabled",
                                      status=404)
            payload = self.stats_registry.snapshot()
            query.send_reply(query.serializer.format_query_stats_v1(
                payload))
            return
        if endpoint == "threads":
            query.send_reply(self._threads())
            return
        if endpoint == "jvm":
            query.send_reply(self._runtime())
            return
        if endpoint == "region_clients":
            # No region servers: the storage engine is in-process.
            query.send_reply([])
            return
        collector = self._collect(tsdb)
        if query.api_version > 0:
            query.send_reply(
                query.serializer.format_stats_v1(collector.records))
        else:
            query.send_reply(collector.emit_ascii(),
                             content_type="text/plain")

    @staticmethod
    def _threads() -> list[dict]:
        out = []
        frames = sys._current_frames()
        for t in threading.enumerate():
            frame = frames.get(t.ident)
            out.append({
                "threadID": t.ident,
                "name": t.name,
                "state": "RUNNABLE" if t.is_alive() else "TERMINATED",
                "daemon": t.daemon,
                "stack": ([ "%s:%d" % (frame.f_code.co_filename,
                                       frame.f_lineno)] if frame else []),
            })
        return out

    @staticmethod
    def _runtime() -> dict:
        """Process runtime stats (the JVM-stats analog for CPython)."""
        import resource
        usage = resource.getrusage(resource.RUSAGE_SELF)
        return {
            "runtime": {
                "implementation": sys.implementation.name,
                "version": sys.version,
                "pid": os.getpid(),
            },
            "memory": {
                "maxRSSKb": usage.ru_maxrss,
            },
            "os": {
                "systemLoadAverage": os.getloadavg()[0],
            },
            "gc": {
                "collections": sum(
                    g["collections"]
                    for g in __import__("gc").get_stats()),
            },
        }


class DiagRpc(HttpRpc):
    """/api/diag (+ /slow, /health): the flight-recorder ring, the
    slow-query store, and the health-engine verdicts
    (obs/flightrec.py, obs/health.py; docs/observability.md).

      * ``/api/diag``              the event ring, oldest first, plus
        (full view only) the ``tenants`` fair-share audit and the
        ``device`` report — platform, device kind, count, per-device
        memory in use / peak.
        ``?since=<seq>`` returns only events newer than that sequence
        number — poll with the last ``seq`` you saw for an incremental
        feed.  ``?trace_id=<id>`` narrows to one request's ring slice
        (an explain fingerprint's plan event, a latency exemplar, or
        an X-TSDB-Trace-Id resolve in ONE request instead of paging
        the whole ring client-side); combinable with ``since``.
      * ``/api/diag/slow``         retained slow/anomalous queries
        (span tree + costmodel decisions + ring slice), newest first.
        ``?trace_id=<id>`` looks one capture up by its trace id.
      * ``/api/diag/health``       per-subsystem ok/degraded/failing
        verdicts (the chaos_soak post-heal gate).
      * ``/api/diag/latency``      always-on per-phase latency
        attribution (obs/latattr.py): streaming histograms keyed by
        (route, plan fingerprint, tenant) — populated with tracing
        OFF.  ``?since=<seq>`` returns only profiles touched after
        that sequence number; ``?fingerprint=`` / ``?tenant=`` narrow
        to one key.
    """

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        allowed_methods(query, "GET")
        sub = query.api_subpath()
        endpoint = sub[0] if sub else ""
        if endpoint == "latency":
            engine = getattr(tsdb, "latattr", None)
            if engine is None:
                raise BadRequestError(
                    "Latency attribution is disabled", status=404,
                    details="Set tsd.latattr.enable=true")
            raw = query.get_query_string_param("since")
            try:
                since = int(raw) if raw else 0
            except ValueError:
                raise BadRequestError("'since' must be an integer "
                                      "sequence number")
            query.send_reply(engine.report(
                since=since,
                fingerprint=query.get_query_string_param("fingerprint"),
                tenant=query.get_query_string_param("tenant")))
            return
        if endpoint == "health":
            engine = getattr(tsdb, "health", None)
            if engine is None:
                raise BadRequestError(
                    "The health engine is disabled", status=404,
                    details="Set tsd.health.enable=true")
            query.send_reply(engine.report())
            return
        recorder = getattr(tsdb, "flightrec", None)
        if recorder is None:
            raise BadRequestError(
                "The flight recorder is disabled", status=404,
                details="Set tsd.diag.enable=true")
        trace_id = query.get_query_string_param("trace_id")
        if endpoint == "slow":
            query.send_reply(
                {"queries": recorder.slow_queries(trace_id=trace_id)})
            return
        if endpoint:
            raise BadRequestError(
                "No such diag endpoint: %s" % endpoint, status=404)
        raw = query.get_query_string_param("since")
        try:
            since = int(raw) if raw else 0
        except ValueError:
            raise BadRequestError("'since' must be an integer sequence "
                                  "number")
        if trace_id:
            events = [e for e in recorder.events_for_trace(trace_id)
                      if e["seq"] > since]
        else:
            events = recorder.events(since=since)
        dropped, dropped_total = recorder.dropped()
        reply = {
            "seq": recorder.latest_seq(),
            "ringSize": recorder.ring_size,
            "events": events,
            # overflow accounting: events evicted from the ring before
            # anyone read them, tallied by the evicted event's kind —
            # a sustained climb means the ring is too small for the
            # event rate and diagnoses are losing history
            "dropped": dropped,
            "droppedTotal": dropped_total,
        }
        if trace_id:
            reply["traceId"] = trace_id
        else:
            # the fair-share audit view: per-tenant inflight/queued/
            # deficit plus the drained/refused split of the demand
            # counter (tsd/admission.py weighted DRR).  Only on the
            # full-ring view — a trace-scoped fetch is one request's
            # evidence, not the gate's
            gate = getattr(tsdb, "_admission_gate", None)
            if gate is not None:
                reply["tenants"] = gate.tenant_snapshot()
            # where this daemon computes: platform / device kind / count
            # and live per-device memory (obs/jaxprof.py device_report)
            from opentsdb_tpu.obs import jaxprof
            reply["device"] = jaxprof.device_report()
        query.send_reply(reply)


class LogBuffer(logging.Handler):
    """In-memory ring of recent log lines (LogsRpc.LogIterator :85)."""

    def __init__(self, capacity: int = 1024):
        super().__init__()
        self.ring = collections.deque(maxlen=capacity)
        self.setFormatter(logging.Formatter(
            "%(asctime)s %(levelname)s [%(threadName)s] "
            "%(name)s: %(message)s"))

    def emit(self, record: logging.LogRecord) -> None:
        try:
            self.ring.append(self.format(record))
        except Exception:
            # logging from inside the log handler would recurse; a
            # record the ring can't format is dropped by design
            pass  # tsdblint: disable=except-swallow


_LOG_BUFFER = LogBuffer()
# refcount, not a boolean: several servers in one process (tests, the
# chaos harness) each install on start and uninstall on stop — the
# handler leaves the root logger only when the LAST one stops, and a
# stopped server no longer pins log capture for the host program.
# Servers run on their own threads, so the count is lock-protected.
_LOG_BUFFER_LOCK = threading.Lock()
_LOG_BUFFER_INSTALLS = 0  # guarded-by: _LOG_BUFFER_LOCK


def install_log_buffer() -> None:
    """Attach the /logs ring buffer to the root logger (refcounted).

    Called by server startup, NOT at import time — importing the package
    must not mutate the host program's logging configuration.  Pair with
    `uninstall_log_buffer()` on shutdown.
    """
    global _LOG_BUFFER_INSTALLS
    with _LOG_BUFFER_LOCK:
        if _LOG_BUFFER_INSTALLS == 0:
            # global-install: removeHandler paired-with: uninstall_log_buffer
            logging.getLogger().addHandler(_LOG_BUFFER)
        _LOG_BUFFER_INSTALLS += 1


def uninstall_log_buffer() -> None:
    """Detach the /logs handler once the last installer stops."""
    global _LOG_BUFFER_INSTALLS
    with _LOG_BUFFER_LOCK:
        if _LOG_BUFFER_INSTALLS == 0:
            return
        _LOG_BUFFER_INSTALLS -= 1
        if _LOG_BUFFER_INSTALLS == 0:
            logging.getLogger().removeHandler(_LOG_BUFFER)


class LogsRpc(HttpRpc):
    def execute_http(self, tsdb, query: HttpQuery) -> None:
        lines = list(_LOG_BUFFER.ring)[::-1]  # newest first, like LogsRpc
        if query.has_query_string_param("json"):
            query.send_reply(lines)
        else:
            query.send_reply("\n".join(lines) + "\n",
                             content_type="text/plain")




class HomePage(HttpRpc):
    """The query UI (the GWT QueryUi.java replacement: a self-contained
    page driving /api/suggest and the /q SVG endpoint)."""

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        query.send_reply(_HOME_PAGE,
                         content_type="text/html; charset=UTF-8")


class StaticFileRpc(HttpRpc):
    """/s/<file> from tsd.http.staticroot (StaticFileRpc.java)."""

    CONTENT_TYPES = {
        ".html": "text/html; charset=UTF-8",
        ".js": "text/javascript",
        ".css": "text/css",
        ".png": "image/png",
        ".gif": "image/gif",
        ".ico": "image/x-icon",
        ".svg": "image/svg+xml",
        ".json": "application/json",
    }

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        root = tsdb.config.get_string("tsd.http.staticroot")
        if not root:
            raise BadRequestError("tsd.http.staticroot is not configured",
                                  status=404)
        parts = query.path.split("/")
        rel = "/".join(parts[1:]) if parts[0] == "s" else query.path
        path = os.path.realpath(os.path.join(root, rel))
        if not path.startswith(os.path.realpath(root) + os.sep):
            raise BadRequestError("Malformed path", status=403)
        if not os.path.isfile(path):
            raise BadRequestError("File not found", status=404)
        with open(path, "rb") as fh:
            body = fh.read()
        ext = os.path.splitext(path)[1].lower()
        ctype = self.CONTENT_TYPES.get(ext, "application/octet-stream")
        query.send_reply(body, content_type=ctype)


class SearchRpc(HttpRpc):
    def execute_http(self, tsdb, query: HttpQuery) -> None:
        try:
            from opentsdb_tpu.search.rpc import handle_search
        except ImportError:
            raise BadRequestError("Search is not available", status=501)
        handle_search(tsdb, query)


class TreeRpc(HttpRpc):
    def execute_http(self, tsdb, query: HttpQuery) -> None:
        try:
            from opentsdb_tpu.tree.rpc import handle_tree
        except ImportError:
            raise BadRequestError("Tree support is not available",
                                  status=501)
        handle_tree(tsdb, query)


class HelpRpc(TelnetRpc):
    def __init__(self, commands):
        self.commands = commands

    def execute_telnet(self, tsdb, conn, words) -> str:
        return ("available commands: "
                + " ".join(sorted(self.commands())) + "\n")


class ExitRpc(TelnetRpc):
    def execute_telnet(self, tsdb, conn, words) -> str | None:
        conn.close_after_write = True
        return "exiting\n"


class DieDieDie(TelnetRpc, HttpRpc):
    """Graceful shutdown trigger."""

    def __init__(self, shutdown_cb):
        self.shutdown_cb = shutdown_cb

    def execute_telnet(self, tsdb, conn, words) -> str:
        conn.close_after_write = True
        self.shutdown_cb()
        return "Cleaning up and exiting now.\n"

    def execute_http(self, tsdb, query: HttpQuery) -> None:
        query.send_reply("Cleaning up and exiting now.\n",
                         content_type="text/plain")
        self.shutdown_cb()

"""Flight recorder: an always-on, bounded ring of diagnostic events.

The r03-r05 chip-bench blackout stayed undiagnosable for three sessions
because nothing RETAINED what the daemon was doing when it mattered —
every decision the query-path subsystems make (admission verdicts,
cache/rollup consults, tile spills, breaker
transitions, deadline expiries, steady-state recompiles) was visible
only to a query that opted into showStats or an operator scraping at
the right instant.  This module is the retained-evidence layer:

  * **The ring** — a bounded deque of structured events, each stamped
    with a monotonic sequence number, a wall-clock timestamp, and the
    AMBIENT trace id (obs/trace.py) when one is active, so a recorded
    decision correlates with the span tree that made it.  Appends are
    lock-cheap (one short critical section, no I/O, no allocation
    beyond the event dict); overflow drops the OLDEST events by
    design.  Served at ``/api/diag`` (``?since=<seq>`` for incremental
    scrapes) and dumped to disk at shutdown/SIGTERM when
    ``tsd.diag.dump_path`` is set — a wedged bench session leaves a
    black box.
  * **Slow-query capture** — queries breaching a latency threshold
    (absolute ``tsd.diag.slow_ms``, or the rolling
    ``tsd.diag.slow_quantile`` of this recorder's own latency
    histogram) automatically retain their full span tree — which
    carries the costmodel decisions the planner annotated — plus the
    flight-recorder slice sharing their trace id, in a bounded store
    served at ``/api/diag/slow``.  No showStats required.
  * **Tenant clamping** — the ``X-TSDB-Tenant`` header value is
    clamped to a registered (``tsd.diag.tenants``) or hashed
    (``tsd.diag.tenant_buckets``) table before it mints a metric
    label, so a client cannot mint unbounded label cardinality.  The
    per-tenant demand counters this enables are the telemetry
    prerequisite for the fair-share scheduler (ROADMAP item 1).

One recorder per TSDB (``tsdb.flightrec``; ``tsd.diag.enable=false``
disables it and the /api/diag surface).  Event producers are the
EXISTING decision points — the wiring is wide but shallow; see
docs/observability.md for the event-kind catalog.

The recorder subscribes to the shared ``CompileLogCapture``
(obs/jaxprof.py) so steady-state recompiles land in the ring with the
trace id of the query that triggered them — the same single capture
tsdbsan and the compile counters use.
"""

from __future__ import annotations

import json
import logging
import threading
import time
import zlib
from collections import deque

from opentsdb_tpu.obs import latattr
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.histogram import LogHistogram
from opentsdb_tpu.obs.registry import REGISTRY

LOG = logging.getLogger("tsd.flightrec")

# Rolling-quantile slow capture needs this many observations before the
# quantile is trusted; below it only the absolute threshold applies.
SLOW_MIN_SAMPLES = 64


def clamp_tenant(config, raw: str | None) -> str:
    """Clamp a client-supplied tenant header to a bounded label table.

    A registered tenant (``tsd.diag.tenants``, comma-separated) keeps
    its name; anything else hashes into one of
    ``tsd.diag.tenant_buckets`` stable buckets (0 buckets collapses
    every unregistered tenant to "other").  An absent/empty header is
    "default".  This is the ONLY path from the header to a metric
    label — labels must never come from raw client strings.
    """
    raw = (raw or "").strip()
    if not raw:
        return "default"
    registered = config.get_string("tsd.diag.tenants")
    if registered:
        for name in registered.split(","):
            if raw == name.strip():
                return raw
    buckets = config.get_int("tsd.diag.tenant_buckets")
    if buckets <= 0:
        return "other"
    return "tenant-%02x" % (zlib.crc32(raw.encode("utf-8")) % buckets)


class FlightRecorder:
    """Bounded ring of structured diagnostic events + the slow store.

    ``record()`` is the one producer entry point; it must stay cheap
    enough for the query hot path (the tsdbobs 1.15x overhead pin
    measures it on by default).
    """

    def __init__(self, config):
        self.ring_size = max(config.get_int("tsd.diag.ring_size"), 16)
        self.dump_path = config.get_string("tsd.diag.dump_path")
        self.slow_ms = config.get_int("tsd.diag.slow_ms")
        self.slow_quantile = config.get_float("tsd.diag.slow_quantile")
        slow_keep = max(config.get_int("tsd.diag.slow_keep"), 1)
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._events: deque = deque(maxlen=self.ring_size)
        self._seq = 0  # guarded-by: _lock
        self._slow: deque = deque(maxlen=slow_keep)  # guarded-by: _lock
        self.slow_captured = 0  # guarded-by: _lock
        self._subscribed = False  # guarded-by: _lock
        self._dumped = False  # guarded-by: _lock
        # the recorder's OWN latency summary: the rolling-quantile slow
        # threshold must not depend on how the registry's histogram is
        # labeled (tenants split that one into many cells)
        self._latency = LogHistogram()
        # per-kind counter cells cached so the hot path skips the
        # registry's family/labels dict locks after first use
        self._event_family = REGISTRY.counter(
            "tsd.diag.events", "Flight-recorder events recorded, "
            "by event kind")
        self._cells: dict[str, object] = {}  # guarded-by: _lock
        # ring-overflow accounting: events evicted oldest-first, by the
        # EVICTED event's kind — a silent ring wrap hides exactly the
        # fault window the recorder exists for, so the drops themselves
        # are evidence (/api/diag "dropped", tsd.diag.dropped, and the
        # health engine's sustained-drop-rate invariant)
        self._dropped: dict[str, int] = {}  # guarded-by: _lock
        self._dropped_total = 0  # guarded-by: _lock
        self._drop_family = REGISTRY.counter(
            "tsd.diag.dropped", "Flight-recorder events dropped on "
            "ring overflow, by the evicted event's kind")
        self._drop_cells: dict[str, object] = {}  # guarded-by: _lock

    # -- lifecycle ------------------------------------------------------ #

    def start(self) -> None:
        """Arm the steady-state recompile feed: subscribe to the SHARED
        compile-log capture (one handler, one event stream — the same
        one the compile counters and tsdbsan use)."""
        from opentsdb_tpu.obs import jaxprof
        with self._lock:
            if self._subscribed:
                return
            self._subscribed = True
        # global-install: unsubscribe paired-with: shutdown
        jaxprof.compile_capture.subscribe(self._on_compile)

    def shutdown(self) -> None:
        """Mirror start(): drop the compile subscription, then write
        the shutdown dump (once) so a post-mortem has the ring even
        when nobody scraped /api/diag in time.  Reached from
        TSDB.shutdown on every exit path incl. SIGTERM."""
        from opentsdb_tpu.obs import jaxprof
        with self._lock:
            was_subscribed, self._subscribed = self._subscribed, False
        if was_subscribed:
            jaxprof.compile_capture.unsubscribe(self._on_compile)
        self.record("shutdown")
        with self._lock:
            if self._dumped:
                return
            self._dumped = True
        if self.dump_path:
            try:
                self.dump(self.dump_path)
            except OSError:
                LOG.exception("flight-recorder shutdown dump to %s "
                              "failed", self.dump_path)

    def _on_compile(self, kernel: str) -> None:
        # synchronous in the compiling thread: the ambient trace id (if
        # any) names the query whose dispatch forced the compile
        self.record("compile", kernel=kernel)

    # -- the ring -------------------------------------------------------- #

    def record(self, kind: str, trace_id: str | None = None,
               **fields) -> int:
        """Append one event; returns its sequence number.  The ambient
        trace id is stamped automatically when none is passed."""
        if trace_id is None:
            tr = obs_trace.active()
            if tr is not None:
                trace_id = tr.trace_id
        event = {"kind": kind, "tMs": int(time.time() * 1e3)}
        if trace_id:
            event["traceId"] = trace_id
        phase = latattr.phase_in_flight()
        if phase is not None:
            # the request phase in flight when this event was recorded
            # (obs/latattr.py) — "which phase was the daemon in when
            # the breaker opened" without needing a trace
            event["phase"] = phase
        if fields:
            event.update(fields)
        drop_cell = None
        with self._lock:
            self._seq += 1
            event["seq"] = self._seq
            if len(self._events) == self.ring_size:
                evicted = self._events[0]["kind"]
                self._dropped[evicted] = self._dropped.get(evicted, 0) + 1
                self._dropped_total += 1
                drop_cell = self._drop_cells.get(evicted)
                if drop_cell is None:
                    drop_cell = self._drop_cells[evicted] = \
                        self._drop_family.labels(kind=evicted)
            self._events.append(event)
            cell = self._cells.get(kind)
            if cell is None:
                cell = self._cells[kind] = \
                    self._event_family.labels(kind=kind)
        cell.inc()
        if drop_cell is not None:
            drop_cell.inc()
        return event["seq"]

    def latest_seq(self) -> int:
        with self._lock:
            return self._seq

    def dropped(self) -> tuple[dict[str, int], int]:
        """(per-kind dropped-oldest tallies, total) since start."""
        with self._lock:
            return dict(self._dropped), self._dropped_total

    def events(self, since: int = 0) -> list[dict]:
        """Ring snapshot, oldest first; ``since`` returns only events
        with a LARGER sequence number (the /api/diag?since= contract:
        poll with the last seq you saw)."""
        with self._lock:
            snap = list(self._events)
        if since > 0:
            snap = [e for e in snap if e["seq"] > since]
        return snap

    def events_for_trace(self, trace_id: str) -> list[dict]:
        with self._lock:
            snap = list(self._events)
        return [e for e in snap if e.get("traceId") == trace_id]

    # -- slow-query capture ---------------------------------------------- #

    def maybe_capture_slow(self, trace, elapsed_ms: float, status: int,
                           query_json: dict | None,
                           tenant: str = "default") -> bool:
        """Called per served query: observe the latency, and when it
        breaches the absolute or rolling-quantile threshold retain the
        full evidence bundle (span tree + the ring slice sharing the
        trace id) in the bounded slow store."""
        threshold = float("inf")
        if self.slow_ms > 0:
            threshold = float(self.slow_ms)
        if 0.0 < self.slow_quantile <= 1.0 \
                and self._latency.count >= SLOW_MIN_SAMPLES:
            threshold = min(threshold,
                            self._latency.quantile(self.slow_quantile))
        self._latency.observe(max(elapsed_ms, 0.0))
        if elapsed_ms < threshold:
            return False
        trace_id = trace.trace_id if trace is not None else None
        entry = {
            "capturedMs": int(time.time() * 1e3),
            "elapsedMs": round(elapsed_ms, 3),
            "thresholdMs": round(threshold, 3),
            "status": int(status),
            "tenant": tenant,
        }
        if trace_id:
            entry["traceId"] = trace_id
            entry["events"] = self.events_for_trace(trace_id)
        if query_json is not None:
            entry["query"] = query_json
        if trace is not None:
            # the tree carries the costmodel/agg_cache/rollup/tiling
            # decision tags the planner annotated — no showStats needed
            entry["trace"] = trace.to_json()
        with self._lock:
            self._slow.append(entry)
            self.slow_captured += 1
        REGISTRY.counter(
            "tsd.diag.slow_captures",
            "Slow/anomalous queries retained by the flight "
            "recorder").inc()
        self.record("slow_query", trace_id=trace_id,
                    elapsedMs=round(elapsed_ms, 3), status=int(status),
                    tenant=tenant)
        return True

    def slow_queries(self, trace_id: str | None = None) -> list[dict]:
        """The retained slow captures, newest first; with a trace id,
        only the captures for that trace (the one-request lookup an
        explain fingerprint's exemplar resolves through)."""
        with self._lock:
            snap = list(self._slow)[::-1]
        if trace_id:
            snap = [e for e in snap if e.get("traceId") == trace_id]
        return snap

    # -- shutdown dump ---------------------------------------------------- #

    def dump(self, path: str) -> None:
        """Write the black box: ring + slow store, one JSON document."""
        with self._lock:
            payload = {
                "dumpedMs": int(time.time() * 1e3),
                "seq": self._seq,
                "ringSize": self.ring_size,
                "dropped": dict(self._dropped),
                "droppedTotal": self._dropped_total,
                "events": list(self._events),
                "slowQueries": list(self._slow),
            }
        with open(path, "w") as fh:
            json.dump(payload, fh)
        LOG.info("flight recorder dumped %d events to %s",
                 len(payload["events"]), path)

    # -- stats ------------------------------------------------------------ #

    def stats_hook(self, collector) -> None:
        """The /api/stats + self-report view: ring volume, slow
        captures, and the per-tenant demand counters (read back from
        the registry family the admission gate increments) — so the
        TSD can query its own demand/health history through its own
        pipeline (obs/selfreport.py)."""
        with self._lock:
            seq = self._seq
            captured = self.slow_captured
            dropped_total = self._dropped_total
        collector.record("diag.ring.events", seq)
        collector.record("diag.ring.dropped", dropped_total)
        collector.record("diag.slow.captured", captured)
        def cells(fam):
            for labels, cell in fam.children():
                yield (dict(labels).get("tenant", "default"),
                       cell.get())

        for tenant, value in cells(REGISTRY.counter(
                "tsd.query.tenant.demand",
                "Queries arriving at admission, by clamped tenant")):
            collector.record("diag.tenant.demand", value,
                             "tenant=%s" % tenant)
        for tenant, value in cells(REGISTRY.counter(
                "tsd.query.tenant.admitted",
                "Queries admitted through the gate, by clamped "
                "tenant")):
            collector.record("diag.tenant.admitted", value,
                             "tenant=%s" % tenant)
        for tenant, value in cells(REGISTRY.counter(
                "tsd.query.tenant.refused",
                "Queries refused by the gate, by clamped tenant")):
            collector.record("diag.tenant.refused", value,
                             "tenant=%s" % tenant)

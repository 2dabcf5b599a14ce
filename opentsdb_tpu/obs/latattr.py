"""Always-on latency attribution: phase stamps + keyed profiles.

Tracing (obs/trace.py) answers "what is THIS query doing" for requests
that opted in; the flight recorder retains discrete events.  Neither
retains *aggregate* phase evidence — after the fact, nothing says where
the serving tier's milliseconds go at p50 vs p99.  This module does,
for EVERY request, tracing on or off:

* ``PhaseStamps`` — a per-request recorder the RPC layer attaches to
  every HTTP request.  Producers along the serving path call
  ``latattr.mark("plan")`` at phase boundaries; each mark attributes
  the monotonic time since the previous mark to that phase, and the
  handler thread's CPU time (``time.thread_time()``) beside it:
  cpu / wall of a phase is the share of it the thread spent on a core,
  the rest it waited (for the interpreter, a lock, the device).  A mark
  is two clock reads, two dict adds and one ``is_enabled()`` — no locks,
  no registry, no allocation beyond the first mark of a phase — so the
  always-on cost stays under the tests/test_latattr.py overhead pin.

* Profiler annotations — while a ``jax.profiler`` trace runs, every
  phase interval is also a ``tsd.phase`` event (stats ``phase``,
  ``cpu_ms``, ``trace_id``) on the handler thread's ``/host:CPU`` line,
  on the same clock as the device planes, and obs/trace.py's
  stack-managed spans are ``tsd.span`` events (stat ``name``) inside
  them.  tools/trace_gaps.py reads both: what the host did while the
  chip idled.  With no profile running nothing is created.

* ``Edges`` — the three stretches of an HTTP request outside its
  handler, stamped by the event loop (tsd/server.py) and the handler
  (RpcManager.handle_http), counted always into
  ``tsd.http.edge_ms{route, stage}``: ``queue`` (the whole body read to
  the handler's start), ``resume`` (the handler's last mark to the
  loop's resumption) and ``write`` (the response encoded, written and
  drained).  With the phases they cover a request from its last byte
  in to its last byte out.  Under a profile ``write`` is a ``tsd.phase``
  event on the loop thread (stat ``resume_ms``), and the request's
  first ``tsd.phase`` event carries ``queue_ms``.

* ``LatencyAttribution`` — the aggregation engine.  Finished stamps
  fold into bounded streaming per-phase ``LogHistogram``s keyed by
  (route arm, plan fingerprint, clamped tenant), with exemplar trace
  ids linking tail buckets to retained slow-query captures
  (/api/diag/slow).  Served at ``GET /api/diag/latency`` with
  ``?since=`` incremental polling and ``?fingerprint=``/``?tenant=``
  filters (tsd/admin_rpcs.py).

The phase set is FIXED — every request reports the full ordered tuple
exactly once, with unexercised phases zero-filled — so two captures
diff phase-by-phase without key reconciliation (tools/latency_report.py
builds the "where did the milliseconds move" table from exactly this
property).

Attribution model: time between two marks belongs to the LATER mark's
phase, and repeated marks accumulate (a multi-segment query folds every
segment's dispatch into one "dispatch" figure).  The trailing "flush"
mark in RpcManager.handle_http absorbs the unstamped handler tail
(response buffering, envelope metrics) — for routes that stamp nothing
(diag, stats), the whole handler lands there.  For batched dispatches
the rendezvous wait includes the leader's shared dispatch, so
"batch_rendezvous" carries the batching cost and the member's own
"dispatch" delta is ~0.
"""

from __future__ import annotations

import threading
import time

from opentsdb_tpu.obs.histogram import LogHistogram
from opentsdb_tpu.obs.registry import REGISTRY

# The fixed request phases, in serving order.  parse: request decode +
# query validation.  admission_wait: the admission gate (queueing).
# plan: series resolution + plan decision.  batch_rendezvous: the
# cross-request dispatch batcher (zero when unbatched).  dispatch:
# device dispatch + host compute.  device_wait: device->host result
# extraction.  serialize: response formatting.  flush: the handler
# tail after serialization (reply buffering, envelope metrics).
PHASES = ("parse", "admission_wait", "plan", "batch_rendezvous",
          "dispatch", "device_wait", "serialize", "flush")

# Profile-table overflow sentinel: once tsd.latattr.max_profiles
# distinct (route, fingerprint, tenant) keys exist, further keys fold
# here — the table is bounded no matter what fingerprints the query
# mix mints.
OVERFLOW_KEY = ("overflow", "-", "-")

# --------------------------------------------------------------------- #
# Profiler annotations (shared with obs/trace.py)                       #
# --------------------------------------------------------------------- #

_TraceAnnotation = None     # jax.profiler.TraceAnnotation, on first use


def open_annotation(event: str, **metadata):
    """An entered profiler annotation named ``event`` on the calling
    thread's line, or None (and nothing created) while no profile
    runs.  Close it with ``close_annotation`` on the SAME thread."""
    global _TraceAnnotation
    cls = _TraceAnnotation
    if cls is None:
        from jax.profiler import TraceAnnotation as cls
        _TraceAnnotation = cls
    if not cls.is_enabled():
        return None
    ann = cls(event, **metadata)
    ann.__enter__()
    return ann


def close_annotation(ann, **metadata) -> None:
    """End ``ann`` (None is fine), adding ``metadata`` to its stats:
    latattr names an interval at its end, the profiler at its start."""
    if ann is None:
        return
    if metadata:
        ann.set_metadata(**metadata)
    ann.__exit__(None, None, None)


class PhaseStamps:
    """Per-request phase recorder.  Owned and touched by the request's
    handler thread only (the batcher's rendezvous and the admission
    wait both block that same thread), so no lock — and so that ONE
    thread's CPU clock is the right one: a mark from another thread
    would read the wrong ``thread_time()``, as it would already miss
    the ambient ``_tls`` stamps."""

    __slots__ = ("t0", "_prev", "_prev_cpu", "deltas", "cpu", "phase",
                 "route", "fingerprint", "tenant", "trace_id", "queue_ms",
                 "_ann")

    def __init__(self, trace_id: str | None = None):
        now = time.perf_counter()
        self.t0 = now
        self._prev = now
        self._prev_cpu = time.thread_time()
        self.deltas: dict[str, float] = {}      # phase -> wall seconds
        self.cpu: dict[str, float] = {}         # phase -> CPU seconds
        self.phase = "recv"                     # last completed mark
        self.route = "other"
        self.fingerprint: str | None = None     # set by the planner
        self.tenant: str | None = None          # set by admission
        self.trace_id = trace_id
        # the executor queue's wait before the handler started (Edges):
        # a stat of the first tsd.phase event
        self.queue_ms: float | None = None
        # the open tsd.phase annotation; None while no profile runs
        self._ann = open_annotation("tsd.phase")

    def mark(self, phase: str, last: bool = False) -> None:
        """Attribute time since the previous mark to ``phase``.  The
        ``last`` mark of a request leaves no annotation open."""
        now = time.perf_counter()
        cpu = time.thread_time()
        spent = cpu - self._prev_cpu
        self.deltas[phase] = (self.deltas.get(phase, 0.0)
                              + (now - self._prev))
        self.cpu[phase] = self.cpu.get(phase, 0.0) + spent
        self._prev = now
        self._prev_cpu = cpu
        self.phase = phase
        if self._ann is not None:
            self.end_annotation(phase, spent)
        if not last:
            self._ann = open_annotation("tsd.phase")

    def end_annotation(self, phase: str, cpu_s: float = 0.0) -> None:
        """End the open tsd.phase event under its name."""
        meta = {"phase": phase, "cpu_ms": cpu_s * 1e3}
        if self.trace_id is not None:
            meta["trace_id"] = self.trace_id
        if self.queue_ms is not None:
            meta["queue_ms"] = self.queue_ms
            self.queue_ms = None
        close_annotation(self._ann, **meta)
        self._ann = None

    def phase_ms(self) -> dict[str, float]:
        """The full ordered phase set in milliseconds, zero-filled."""
        return {p: self.deltas.get(p, 0.0) * 1e3 for p in PHASES}

    def cpu_ms(self) -> dict[str, float]:
        """The handler thread's CPU milliseconds per phase, zero-filled."""
        return {p: self.cpu.get(p, 0.0) * 1e3 for p in PHASES}

    def total_ms(self) -> float:
        return (self._prev - self.t0) * 1e3


# --------------------------------------------------------------------- #
# The edges outside the handler                                         #
# --------------------------------------------------------------------- #

EDGES = ("queue", "resume", "write")


class Edges:
    """One HTTP request's stamps outside its handler.  The event loop
    makes it when it has the whole body (``queued``) and writes the
    response; the responder thread sets ``entered`` at the handler's
    start and ``returned`` at its last mark, with the clamped ``route``
    and the ``trace_id``.  Each field has one writer, and the loop reads
    the handler's fields only after awaiting the handler's future."""

    __slots__ = ("queued", "entered", "returned", "route", "trace_id")

    def __init__(self):
        self.queued = time.perf_counter()
        self.entered: float | None = None
        self.returned: float | None = None
        self.route = "other"
        self.trace_id: str | None = None

    def written(self, resumed: float, ann) -> None:
        """The response is out (or its write failed): close the loop's
        ``write`` event ``ann``, opened at ``resumed``, and add the three
        edges to tsd.http.edge_ms."""
        now = time.perf_counter()
        resume_ms = (resumed - self.returned) * 1e3
        meta = {"phase": "write", "resume_ms": resume_ms}
        if self.trace_id is not None:
            meta["trace_id"] = self.trace_id
        close_annotation(ann, **meta)
        fam = REGISTRY.counter(
            "tsd.http.edge_ms", "Cumulative wall milliseconds of a "
            "request outside its handler")
        for stage, ms in zip(EDGES, ((self.entered - self.queued) * 1e3,
                                     resume_ms, (now - resumed) * 1e3)):
            fam.labels(route=self.route, stage=stage).inc(ms)


# --------------------------------------------------------------------- #
# Ambient stamps: one per handler thread (mirrors obs/trace.py)         #
# --------------------------------------------------------------------- #

_tls = threading.local()


def activate(stamps: PhaseStamps) -> None:
    _tls.stamps = stamps


def deactivate() -> None:
    _tls.stamps = None


def active() -> PhaseStamps | None:
    return getattr(_tls, "stamps", None)


def mark(phase: str) -> None:
    """Phase boundary in the ambient request; free when none active."""
    st = getattr(_tls, "stamps", None)
    if st is not None:
        st.mark(phase)


def set_fingerprint(fingerprint: str) -> None:
    st = getattr(_tls, "stamps", None)
    if st is not None and st.fingerprint is None:
        # first plan decision wins: a multi-segment query keys its
        # profile by the segment that planned first
        st.fingerprint = fingerprint


def set_tenant(tenant: str) -> None:
    st = getattr(_tls, "stamps", None)
    if st is not None:
        st.tenant = tenant


def phase_in_flight() -> str | None:
    """The last completed phase of the ambient request, for the flight
    recorder's events ("recv" before any mark; None outside one)."""
    st = getattr(_tls, "stamps", None)
    return st.phase if st is not None else None


class _Profile:
    """One (route, fingerprint, tenant) key's streaming summary."""

    __slots__ = ("key", "count", "last_seq", "hists", "cpu_ms")

    def __init__(self, key: tuple[str, str, str]):
        self.key = key
        self.count = 0
        self.last_seq = 0
        self.hists = {p: LogHistogram() for p in PHASES}
        # cumulative CPU ms per phase, written under the engine's lock
        self.cpu_ms = {p: 0.0 for p in PHASES}

    def to_json(self) -> dict:
        route, fingerprint, tenant = self.key
        phases: dict[str, dict] = {}
        exemplars: dict[str, list] = {}
        for p in PHASES:
            h = self.hists[p]
            _counts, count, total = h.snapshot()
            phases[p] = _phase_json(h, self.cpu_ms[p])
            tail = [{"traceId": label, "ms": value}
                    for _bound, label, value in h.exemplar_entries()]
            if tail:
                # the tail-most exemplars are the diagnostic ones
                exemplars[p] = tail[-3:]
        out = {"route": route, "fingerprint": fingerprint,
               "tenant": tenant, "count": self.count,
               "lastSeq": self.last_seq, "phases": phases}
        if exemplars:
            out["exemplars"] = exemplars
        return out


def _finite(value: float) -> float:
    return value if value == value else 0.0      # NaN (empty) -> 0


def _phase_json(hist: LogHistogram, cpu_ms: float) -> dict:
    """One phase object of the report: wall time from its histogram,
    CPU time cumulative like ``totalMs`` (no CPU percentiles)."""
    _counts, count, total = hist.snapshot()
    return {"count": count, "totalMs": total, "cpuMs": cpu_ms,
            "p50Ms": _finite(hist.quantile(0.5)),
            "p99Ms": _finite(hist.quantile(0.99))}


class LatencyAttribution:
    """Folds finished PhaseStamps into bounded keyed profiles plus a
    global per-phase summary, and serves both as one JSON report."""

    def __init__(self, config):
        self.max_profiles = max(
            config.get_int("tsd.latattr.max_profiles"), 1)
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._profiles: dict[tuple[str, str, str], _Profile] = {}
        self._seq = 0          # guarded-by: _lock
        self._requests = 0     # guarded-by: _lock
        self._overflow = 0     # guarded-by: _lock
        # cumulative per-phase milliseconds — the health engine's
        # phase-share window deltas read this  # guarded-by: _lock
        self._phase_total_ms = {p: 0.0 for p in PHASES}
        self._phase_cpu_ms = {p: 0.0 for p in PHASES}  # guarded-by: _lock
        # global per-phase histograms (LogHistogram locks itself)
        self._overall = {p: LogHistogram() for p in PHASES}
        self._requests_cell = REGISTRY.counter(
            "tsd.latattr.requests",
            "Requests folded into the latency-attribution profiles")
        self._overflow_cell = REGISTRY.counter(
            "tsd.latattr.profile_overflow",
            "Requests folded into the overflow profile because "
            "tsd.latattr.max_profiles distinct keys already exist")
        self._profiles_gauge = REGISTRY.gauge(
            "tsd.latattr.profiles",
            "Distinct (route, fingerprint, tenant) profiles live")
        phase_fam = REGISTRY.counter(
            "tsd.latattr.phase_ms",
            "Cumulative milliseconds attributed to each request phase")
        self._phase_cells = {p: phase_fam.labels(phase=p)
                             for p in PHASES}
        cpu_fam = REGISTRY.counter(
            "tsd.latattr.phase_cpu_ms",
            "Cumulative handler-thread CPU milliseconds spent in each "
            "request phase")
        self._cpu_cells = {p: cpu_fam.labels(phase=p) for p in PHASES}

    def observe(self, stamps: PhaseStamps) -> None:
        """Fold one finished request.  Called by RpcManager.handle_http
        after the trailing flush mark, on the handler thread."""
        if stamps._ann is not None:
            # no trailing mark(last=True) closed it: time no phase names
            stamps.end_annotation("unmarked")
        deltas = stamps.phase_ms()
        cpu = stamps.cpu_ms()
        key = (stamps.route, stamps.fingerprint or "-",
               stamps.tenant or "default")
        overflowed = False
        with self._lock:
            self._seq += 1
            seq = self._seq
            self._requests += 1
            profile = self._profiles.get(key)
            if profile is None:
                if len(self._profiles) >= self.max_profiles \
                        and key != OVERFLOW_KEY:
                    overflowed = True
                    self._overflow += 1
                    key = OVERFLOW_KEY
                    profile = self._profiles.get(key)
                if profile is None:
                    profile = _Profile(key)
                    self._profiles[key] = profile
            profile.count += 1
            profile.last_seq = seq
            for p in PHASES:
                self._phase_total_ms[p] += deltas[p]
                self._phase_cpu_ms[p] += cpu[p]
                profile.cpu_ms[p] += cpu[p]
            live = len(self._profiles)
        exemplar = stamps.trace_id
        for p in PHASES:
            profile.hists[p].observe(deltas[p], exemplar=exemplar)
            self._overall[p].observe(deltas[p])
            self._phase_cells[p].inc(deltas[p])
            self._cpu_cells[p].inc(cpu[p])
        self._requests_cell.inc()
        if overflowed:
            self._overflow_cell.inc()
        self._profiles_gauge.set(live)

    def phase_totals(self) -> dict:
        """Cumulative per-phase ms + request count, for the health
        engine's windowed phase-share invariant."""
        with self._lock:
            out = dict(self._phase_total_ms)
            out["requests"] = float(self._requests)
            return out

    def report(self, since: int = 0, fingerprint: str | None = None,
               tenant: str | None = None) -> dict:
        """The /api/diag/latency payload.  ``since`` keeps only
        profiles touched after that sequence number (poll with the
        last ``seq`` you saw); the filters match profile keys exactly.
        Histograms are cumulative since daemon start — differential
        views belong to tools/latency_report.py."""
        with self._lock:
            seq = self._seq
            requests = self._requests
            overflow = self._overflow
            profiles = list(self._profiles.values())
            cpu_ms = dict(self._phase_cpu_ms)
        selected = []
        for profile in profiles:
            _route, key_fp, key_tenant = profile.key
            if profile.last_seq <= since:
                continue
            if fingerprint is not None and key_fp != fingerprint:
                continue
            if tenant is not None and key_tenant != tenant:
                continue
            selected.append(profile)
        selected.sort(key=lambda pr: (-pr.count, pr.key))
        overall = {p: _phase_json(self._overall[p], cpu_ms[p])
                   for p in PHASES}
        return {"seq": seq, "requests": requests,
                "phases": list(PHASES),
                "profileOverflow": overflow,
                "overall": overall,
                "profiles": [pr.to_json() for pr in selected]}

    def stats_hook(self, collector) -> None:
        """tsdb.stats_hooks entry: fold summary gauges into the
        standard stats walk (self-report + /api/stats)."""
        with self._lock:
            requests = self._requests
            live = len(self._profiles)
            totals = dict(self._phase_total_ms)
        collector.record("latattr.observed", requests)
        collector.record("latattr.live_profiles", live)
        for p in PHASES:
            collector.record("latattr.ms", totals[p], "phase=%s" % p)

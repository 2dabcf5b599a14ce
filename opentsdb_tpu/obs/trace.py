"""Span-tree tracer for query serving.

One `Trace` per request (started by RpcManager.handle_http when
`tsd.trace.enable` is on), a stack of nested `Span`s manipulated by the
request's handler thread, and explicit `child()` spans for work that
hops threads (the cluster fan-out pool).  The planner and RPC layers
annotate stages through the AMBIENT trace (`stage()` below), which
no-ops at near-zero cost when no trace is active — library callers of
QueryRunner.run() and the sanitizer's steady-state loops see no
behavior change.

Span times: ``wallMs``, start-to-finish wall time of the stage.  The
tracer syncs nothing: JAX dispatch stays asynchronous, and where a
request waits for the device is latattr's ``device_wait`` phase and the
planner's ``fetch`` stage (``timed_stage`` below); per-kernel device
time is the device trace's (benchmark `--trace 1`, tools/trace_gaps.py).

While a ``jax.profiler`` trace runs, every stack-managed span
(``Trace.span()``/``stage()``, ``begin()``/``end()``) is also a
``tsd.span`` event with a ``name`` stat on the handler thread's
``/host:CPU`` line, inside latattr's ``tsd.phase`` events
(obs/latattr.py; tools/trace_gaps.py reads both).  Cross-thread
``Span.child()`` handles are not: an annotation begins and ends on one
thread.

Trace ids propagate across the cluster fan-out via the
``X-TSDB-Trace-Id`` header (tsd/cluster.py attaches it; handle_http
adopts an incoming one), so one clustered query is one trace id across
every TSD that served a piece of it.
"""

from __future__ import annotations

import os
import struct
import threading
import time
from contextlib import contextmanager

from opentsdb_tpu.obs.latattr import close_annotation, open_annotation
from opentsdb_tpu.obs.registry import REGISTRY

TRACE_HEADER = "x-tsdb-trace-id"


def _new_trace_id() -> str:
    return struct.unpack("<Q", os.urandom(8))[0].__format__("016x")


class Span:
    """One named stage; a node in the trace tree."""

    __slots__ = ("name", "tags", "children", "start", "wall_ms", "_ann")

    def __init__(self, name: str, **tags):
        self.name = name
        self.tags = tags
        self.children: list[Span] = []
        self.start = time.perf_counter()
        self.wall_ms: float | None = None
        self._ann = None        # begin()'s open tsd.span annotation

    def finish(self) -> None:
        if self.wall_ms is None:
            self.wall_ms = (time.perf_counter() - self.start) * 1e3

    def child(self, name: str, **tags) -> "Span":
        """A new child span.  Create it on the thread that OWNS this
        span (children list is not locked); the child itself may then
        be finished/annotated by another thread."""
        sp = Span(name, **tags)
        self.children.append(sp)
        return sp

    def to_json(self) -> dict:
        wall = self.wall_ms
        if wall is None:        # still running: elapsed so far
            wall = (time.perf_counter() - self.start) * 1e3
        out: dict = {
            "name": self.name,
            "wallMs": round(wall, 3),
        }
        if self.tags:
            # a stats scrape can render while another thread (the
            # handler, or a straggling peer-fetch pool thread) is still
            # inserting tags; item writes are atomic under the GIL but
            # dict ITERATION mid-insert raises — retry the copy instead
            # of surfacing a 500 from the stats endpoint
            for _ in range(4):
                try:
                    out["tags"] = dict(self.tags)
                    break
                except RuntimeError:
                    continue
        if self.children:
            out["spans"] = [c.to_json() for c in self.children]
        return out


class Trace:
    """One request's span tree + the id that names it across hosts."""

    def __init__(self, name: str, trace_id: str | None = None):
        self.trace_id = trace_id or _new_trace_id()
        self.root = Span(name)
        # the span stack of the OWNING thread; cross-thread work uses
        # explicit Span.child() handles instead
        self._stack: list[Span] = [self.root]

    def current(self) -> Span:
        return self._stack[-1]

    @contextmanager
    def span(self, name: str, **tags):
        sp = self.current().child(name, **tags)
        self._stack.append(sp)
        ann = open_annotation("tsd.span", name=name)
        try:
            yield sp
        finally:
            close_annotation(ann)
            self._stack.pop()
            sp.finish()

    def finish(self) -> None:
        """Close the trace: every still-open span in the tree finishes
        NOW.  The trace outlives its request in the /api/stats/query
        ring, so a span left open by an error path (a 413 raised
        mid-dispatch between begin() and end(), an aborted fan-out)
        must stop accruing elapsed-so-far here — not render a
        forever-climbing wallMs at every later scrape."""
        self._finish_open(self.root)
        for sp in reversed(self._stack):
            close_annotation(sp._ann)
            sp._ann = None
        del self._stack[1:]

    @staticmethod
    def _finish_open(span: Span) -> None:
        for child in span.children:
            Trace._finish_open(child)
        span.finish()

    def to_json(self) -> dict:
        out = self.root.to_json()
        out["traceId"] = self.trace_id
        return out


# --------------------------------------------------------------------- #
# Ambient trace: one per handler thread                                 #
# --------------------------------------------------------------------- #

_tls = threading.local()


def activate(trace: Trace) -> None:
    _tls.trace = trace


def deactivate() -> None:
    _tls.trace = None


def active() -> Trace | None:
    return getattr(_tls, "trace", None)


@contextmanager
def stage(name: str, **tags):
    """`with stage("scan", kind="raw") as sp:` — a child span of the
    ambient trace's current span, or None (and no cost) untraced."""
    tr = active()
    if tr is None:
        yield None
        return
    with tr.span(name, **tags) as sp:
        yield sp


@contextmanager
def timed_stage(name: str, **tags):
    """A `stage()` whose wall time is also summed, traced or not, into
    the counter tsd.query.stage_ms{stage=name}: what the planner's
    host stages cost per series and per group is read from two scrapes
    of /api/stats/prometheus."""
    t0 = time.perf_counter()
    try:
        with stage(name, **tags) as sp:
            yield sp
    finally:
        REGISTRY.counter(
            "tsd.query.stage_ms", "Cumulative wall milliseconds of the "
            "planner's host stages").labels(stage=name).inc(
                (time.perf_counter() - t0) * 1e3)


def annotate(span: Span | None, **tags) -> None:
    if span is not None:
        span.tags.update(tags)


def begin(name: str, **tags) -> Span | None:
    """Non-context-manager stage start for long straight-line sections
    (the planner's dispatch chain).  Pair with `end()`.  An exception
    between the two leaves the span unfinished, which is safe: the
    trace is per-request and to_json renders unfinished spans with
    elapsed-so-far."""
    tr = active()
    if tr is None:
        return None
    sp = tr.current().child(name, **tags)
    tr._stack.append(sp)
    sp._ann = open_annotation("tsd.span", name=name)
    return sp


def end(span: Span | None) -> None:
    tr = active()
    if span is None or tr is None:
        return
    close_annotation(span._ann)
    span._ann = None
    if tr._stack and tr._stack[-1] is span:
        tr._stack.pop()
    span.finish()


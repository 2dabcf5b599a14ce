"""JAX profiling hooks: compile accounting, device gauges, the costmodel's
per-segment decisions.

Compile capture — THE shared source.  `jax_log_compiles` emits one
"Compiling <kernel> ..." log record per XLA compilation, synchronously
in the compiling thread.  `CompileLogCapture` owns the single logging
handler (and the flag save/restore) and fans each kernel name out to
subscribers; both this module's per-kernel counters AND tsdbsan's
JaxSanitizer (tools/sanitize/jax_san.py) subscribe to the same capture,
so the profiler and the sanitizer can never disagree about what
compiled — one regex, one handler, one event stream.

Costmodel decisions.  `segment_decisions()` recomputes the per-axis
strategy decisions through the same choosers the kernels consult (the
trace annotates them per segment, the explain engine reports them), and
`stage_breakdown()` predicts one grouped dispatch's seconds per logical
stage from ops/costmodel.py's table (admission, plan decision, rollup
and agg-cache pricing read it).  Nothing here times the device: that is
the device trace's.
"""

from __future__ import annotations

import logging
import re
import threading

from opentsdb_tpu.obs.registry import REGISTRY

COMPILING_RE = re.compile(r"Compiling (\S+) with global")
PXLA_LOGGER = "jax._src.interpreters.pxla"


class _CaptureHandler(logging.Handler):
    def __init__(self, capture: "CompileLogCapture") -> None:
        super().__init__(level=logging.DEBUG)
        self._capture = capture

    def emit(self, record: logging.LogRecord) -> None:
        try:
            msg = record.getMessage()
        except Exception:       # noqa: BLE001 — a malformed record must
            # never break the compiling thread; counted, not hidden
            self._capture.count_parse_error()
            return
        m = COMPILING_RE.match(msg)
        if m:
            self._capture._emit(m.group(1))


class CompileLogCapture:
    """Refcounted owner of the pxla compile-log handler.

    `subscribe(cb)` installs the handler (and turns jax_log_compiles on)
    on the first subscriber; `unsubscribe(cb)` restores both when the
    last one leaves.  Callbacks run synchronously in the compiling
    thread — the stack still shows who asked for the compile, which is
    what tsdbsan's attribution depends on.
    """

    def __init__(self) -> None:
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._subscribers: list = []
        self._handler: _CaptureHandler | None = None  # guarded-by: _lock
        self._prev_flag = None  # guarded-by: _lock
        # unparsable log records (diagnostic)  # guarded-by: _lock
        self.parse_errors = 0

    def count_parse_error(self) -> None:
        with self._lock:
            self.parse_errors += 1

    def subscribe(self, callback) -> None:
        import jax
        with self._lock:
            if self._handler is None:
                # all fallible work BEFORE the first state write: a
                # raise after `_prev_flag` was set but before
                # `_handler` would make the next subscribe() re-save
                # the already-overridden flag, so unsubscribe() could
                # never restore the user's original setting
                handler = _CaptureHandler(self)
                prev = jax.config.jax_log_compiles
                jax.config.update("jax_log_compiles", True)
                self._prev_flag = prev
                self._handler = handler
                logging.getLogger(PXLA_LOGGER).addHandler(handler)
            # registering the callback is the commit point: a failed
            # install must not leave a subscriber the caller never got
            # a working subscription for (it would pin the flag
            # override past the last real unsubscribe)
            self._subscribers.append(callback)

    def unsubscribe(self, callback) -> None:
        import jax
        with self._lock:
            try:
                self._subscribers.remove(callback)
            except ValueError:
                pass
            if not self._subscribers and self._handler is not None:
                logging.getLogger(PXLA_LOGGER).removeHandler(self._handler)
                self._handler = None
                if self._prev_flag is not None:
                    jax.config.update("jax_log_compiles", self._prev_flag)
                self._prev_flag = None

    def _emit(self, kernel: str) -> None:
        with self._lock:
            subs = list(self._subscribers)
        for cb in subs:
            cb(kernel)


compile_capture = CompileLogCapture()


# --------------------------------------------------------------------- #
# Per-kernel compile counters (the profiler's subscriber)               #
# --------------------------------------------------------------------- #

class _CompileCounter:
    def __init__(self) -> None:
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._refs = 0
        self.counts: dict[str, int] = {}  # guarded-by: _lock

    def start(self) -> None:
        with self._lock:
            self._refs += 1
            if self._refs > 1:
                return
        # global-install: unsubscribe paired-with: stop
        compile_capture.subscribe(self._on_compile)

    def stop(self) -> None:
        with self._lock:
            if self._refs == 0:
                return
            self._refs -= 1
            if self._refs:
                return
        compile_capture.unsubscribe(self._on_compile)

    def _on_compile(self, kernel: str) -> None:
        with self._lock:
            self.counts[kernel] = self.counts.get(kernel, 0) + 1
        REGISTRY.counter(
            "tsd.jax.compiles",
            "XLA compilations per jitted kernel").labels(
                kernel=kernel).inc()

    def snapshot(self) -> dict[str, int]:
        with self._lock:
            return dict(self.counts)


_COUNTER = _CompileCounter()


def start_compile_counting() -> None:
    """Arm per-kernel compile counting (refcounted; the daemon arms it
    when tsd.trace.enable is on)."""
    _COUNTER.start()


def stop_compile_counting() -> None:
    _COUNTER.stop()


def compile_counts() -> dict[str, int]:
    return _COUNTER.snapshot()


# --------------------------------------------------------------------- #
# Device-cache gauges                                                   #
# --------------------------------------------------------------------- #

def update_device_gauges(tsdb) -> None:
    """Mirror the device cache's hit/miss/build/eviction tallies into
    registry gauges.

    For EMBEDDERS exporting REGISTRY.prometheus_text() directly without
    a TSD stats walk.  The daemon's /api/stats/prometheus does NOT call
    this: its extra_records already carry the same values host-tagged,
    and registering them here would shadow that richer labeling."""
    cache = getattr(tsdb, "device_cache", None)
    if cache is None:
        return
    for name, value in cache.collect_stats().items():
        # forwarder: the names are the device cache's collect_stats()
        # keys (tsd.query.device_cache.*), declared in METRICS_SCHEMA
        # and walked, not minted  # tsdblint: disable=metrics-dynamic-name
        REGISTRY.gauge(name, "Device series cache (HBM) state").set(value)


def device_report() -> dict:
    """The devices this process computes on, as JAX reports them: the
    default backend's platform, device kind and count, plus per-device
    memory in use / peak / limit where the backend keeps
    ``memory_stats()`` (the CPU backend reports none -> nulls).

    Initializes the backend; a backend that cannot come up raises
    (tsd_main calls this once at start so a daemon never serves from a
    platform nobody chose).  Served as the ``device`` section of the
    full /api/diag view."""
    import jax
    devices = jax.devices()
    per_device = []
    for d in devices:
        stats = d.memory_stats() or {}
        per_device.append({
            "id": d.id,
            "bytesInUse": stats.get("bytes_in_use"),
            "peakBytesInUse": stats.get("peak_bytes_in_use"),
            "bytesLimit": stats.get("bytes_limit"),
        })
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory": per_device,
    }


# --------------------------------------------------------------------- #
# Costmodel decisions and predictions                                   #
# --------------------------------------------------------------------- #


def segment_decisions(platform: str, s: int, n: int, w: int, g: int,
                      ds_function: str | None,
                      aggregator: str | None = None,
                      row_groups: bool = False) -> dict[str, dict]:
    """The kernel strategy decisions one grouped dispatch of shape
    [s series, n points] -> [w windows, g groups] makes, per kernel
    axis — recomputed through the SAME `_effective_*` choosers the
    kernels consult at trace time, so the report cannot drift from the
    dispatched modes.  Keys: 'search', 'scan' OR 'extreme' (by the
    DOWNSAMPLE function — it picks the windowed-reduce kernel),
    'group'; values are decision reports (chosen mode, per-candidate
    predicted ms — see downsample.search_decision).

    The group axis's extremes flag comes from the CROSS-SERIES
    `aggregator` — that is what moment_group_reduce keys its kernel
    (and the matmul candidacy) on; a `max:10s-avg:` query downsamples
    with the scan path but group-reduces as an extreme.  When the
    aggregator is unknown (offline recomputation from a bare shape)
    the downsample function is the fallback."""
    from opentsdb_tpu.ops import downsample as ds
    from opentsdb_tpu.ops import group_agg as ga
    s = max(int(s), 1)
    n = max(int(n), 1)
    w = max(int(w), 1)
    g = max(int(g), 1)
    e = w + 1
    extremes = ds_function in ("min", "max", "mimmin", "mimmax")
    group_extremes = (aggregator in ("min", "max", "mimmin", "mimmax")
                      if aggregator is not None else extremes)
    out = {"search": ds.search_decision(s, n, e, platform)}
    if extremes:
        out["extreme"] = ds.extreme_decision(n, w, platform)
    else:
        out["scan"] = ds.scan_decision(s, n, e, platform)
    out["group"] = ga.group_decision(s, w, g, platform,
                                     extremes=group_extremes,
                                     row_groups=row_groups)
    return out


def stage_breakdown(platform: str, s: int, n: int, w: int, g: int,
                    ds_function: str | None, has_rate: bool,
                    decisions: dict[str, dict] | None = None
                    ) -> dict[str, float]:
    """Predicted seconds per logical pipeline stage for one grouped
    dispatch, using the costmodel's table under the modes the
    kernels actually chose (`decisions`; recomputed here when absent).
    Approximate by design: a prediction for pricing a route, not a
    timer."""
    from opentsdb_tpu.ops import costmodel as cm
    s = max(int(s), 1)
    n = max(int(n), 1)
    w = max(int(w), 1)
    g = max(int(g), 1)
    e = w + 1
    if decisions is None:
        decisions = segment_decisions(platform, s, n, w, g, ds_function)
    elem = cm.costs(platform)["elem_f64"]
    out: dict[str, float] = {}
    search = cm.predict_search(decisions["search"]["mode"], s, n, e,
                               platform)
    if "extreme" in decisions:
        reduce_cost = cm.predict_extreme(decisions["extreme"]["mode"],
                                         s, n, e, platform)
    else:
        reduce_cost = cm.predict_scan(decisions["scan"]["mode"],
                                      s, n, e, platform)
    out["downsample"] = search + reduce_cost
    if has_rate:
        out["rate"] = s * w * elem
    out["groupby"] = cm.predict_group(decisions["group"]["mode"],
                                      s, w, g, platform)
    out["aggregate"] = g * w * elem
    return out


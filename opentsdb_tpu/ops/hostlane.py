"""Small-query fast lane: run the SAME jitted kernels on the host CPU.

A 1M-point query once lost 11x to the reference's iterator loop because
every accelerator dispatch pays a fixed floor (launch + host->HBM
transfer) that dwarfs the compute at small scale — production TSDs serve
mostly small queries.  The reference never had this cliff because it
always computes on the serving host
(/root/reference/src/core/AggregationIterator.java:514 runs in the Netty
worker).

The fix keeps ONE implementation: below a configured point count the
planner executes the identical pipeline functions under
`jax.default_device(<cpu>)`, so XLA compiles the same program for the
host (vectorized, still beating the Java iterator) and the accelerator
is never touched.  No numpy re-implementation — the lane cannot diverge
semantically from the device path, and every existing kernel test covers
both lanes by construction.  The lane is a ROUTING decision
(tsd.query.host_lane.max_points), never a fallback: a query above the
threshold runs on the default backend or fails.

A TPU deployment may restrict JAX to the accelerator platform via
JAX_PLATFORMS; `ensure_cpu_platform` (called once at package import,
before any backend initializes) widens the restriction to keep the host
platform registered alongside.  Widening never hides a missing
accelerator: every platform named in jax_platforms must initialize, so
`tpu,cpu` without a TPU still raises at the first backend touch.  If the
backend already initialized without a CPU platform the lane degrades to
None and the planner keeps the accelerator path — routing is
best-effort, correctness never depends on it.

The kernel strategies (scan/search/extreme/group-reduce modes) are
process-global trace-time choices, but they are resolved PER EXECUTION
PLATFORM: an earlier chip session measured the dense edge-search forms —
chip winners — running 18x SLOWER than the binary search on the host
lane at a single-series 1M-point shape (they materialize their compare
matrix where the backend does not fuse it into the count), so the shape
guards in ops.downsample consult `execution_platform()` and demote dense
forms on CPU.  This is safe with one shared jit cache because
`jax.default_device` participates in the cache key (probed: two devices
-> two traces, re-entry hits the cache), so each lane's trace reads the
lane context that was active when IT was traced.
"""

from __future__ import annotations

import contextlib
import contextvars
import logging
import os

LOG = logging.getLogger("ops.hostlane")

_UNSET = object()
_CPU_DEVICE = _UNSET


def ensure_cpu_platform() -> None:
    """Keep the CPU platform registered when JAX_PLATFORMS restricts to an
    accelerator.  Must run before the first backend initialization; a
    no-op when platforms are unrestricted (cpu is always registered then)
    or already include cpu."""
    plats = os.environ.get("JAX_PLATFORMS", "").strip()
    if not plats or "cpu" in plats.split(","):
        return
    import jax
    jax.config.update("jax_platforms", plats + ",cpu")


def cpu_device():
    """The host CPU jax device, or None when unavailable (cached)."""
    global _CPU_DEVICE
    if _CPU_DEVICE is _UNSET:
        import jax
        try:
            _CPU_DEVICE = jax.devices("cpu")[0]
        except RuntimeError:
            # the backend came up without a CPU platform (jax_platforms
            # was fixed before this package could widen it)
            _CPU_DEVICE = None
            LOG.info("no CPU platform registered; small-query host lane "
                     "disabled (accelerator path serves all sizes)")
    return _CPU_DEVICE


# True while a host_lane() context is active on this thread/task: the
# planner routed this dispatch to the host CPU, so trace-time kernel-mode
# guards must pick host-friendly strategies (see module docstring).
_LANE_ACTIVE = contextvars.ContextVar("tsdb_host_lane_active",
                                      default=False)


@contextlib.contextmanager
def _lane_marked(inner):
    tok = _LANE_ACTIVE.set(True)
    try:
        with inner:
            yield
    finally:
        _LANE_ACTIVE.reset(tok)


def host_lane(enabled: bool):
    """Context manager: place this dispatch on the host CPU when enabled
    and a CPU device exists; otherwise a no-op.

    On a CPU-backend process the dispatch already executes on the host,
    so the context would only add per-dispatch overhead — measured 8ms
    per config-1 query (21.2ms with the redundant `jax.default_device`
    wrap vs 12.8 without, identical compiled program) — and
    execution_platform() already reports 'cpu' without the lane marker
    there."""
    dev = cpu_device() if enabled else None
    if dev is None:
        return contextlib.nullcontext()
    import jax
    if jax.default_backend() == "cpu":
        return contextlib.nullcontext()
    return _lane_marked(jax.default_device(dev))


def execution_platform() -> str:
    """Platform this thread's dispatches execute on — for trace-time
    kernel-mode guards.  'cpu' inside an active host_lane() (regardless
    of the process's accelerator), else the default backend's platform
    ('tpu', 'cpu', ...).  A backend that cannot initialize RAISES here:
    answering 'cpu' for it would turn a failed accelerator into host
    kernel modes instead of an error."""
    if _LANE_ACTIVE.get():
        return "cpu"
    import jax
    return jax.default_backend()

"""JAX/XLA kernels for the query-time numeric pipeline.

This package replaces the reference's per-datapoint iterator stack
(src/core/Aggregators.java, Downsampler.java, RateSpan.java,
AggregationIterator.java) with batched, jit-compiled array kernels:

  aggregators.py  registry + masked cross-series reductions
  downsample.py   windowed segment-reductions over [series, time] batches
  rate.py         first-difference / counter-rate kernels
  union_agg.py    LERP-at-union-timestamps cross-series merge
  percentile.py   sort-based percentile selection (LEGACY/R-3/R-7)
  pipeline.py     fused end-to-end query kernels (jit entry points)

float64/int64 precision is enabled process-wide to match the reference's
Java double/long arithmetic; kernels themselves are dtype-polymorphic so the
TPU fast path can run float32 batches.
"""

import os

import jax

jax.config.update("jax_enable_x64", True)

# Persistent compilation cache, placed by ONE rule: where
# JAX_COMPILATION_CACHE_DIR is set, JAX reads it and this code sets
# nothing; otherwise the cache lives in a fixed, git-ignored directory
# of the checkout (the path is part of the cache key, so it never
# carries a pid, a time or a temp name).  The tests keep their own
# directory (tests/conftest.py) so executables compiled for one
# machine's CPU are never picked up by a daemon on another.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)

# Keep the host CPU platform registered next to a restricted accelerator
# platform (JAX_PLATFORMS=tpu): the small-query fast lane places
# sub-threshold dispatches on the host, dodging the accelerator dispatch
# floor.  Must happen before the first backend initialization.
from opentsdb_tpu.ops.hostlane import ensure_cpu_platform  # noqa: E402

ensure_cpu_platform()

from opentsdb_tpu.ops import aggregators  # noqa: E402
from opentsdb_tpu.ops.aggregators import AGGREGATORS, get_agg, agg_names  # noqa: E402

__all__ = ["aggregators", "AGGREGATORS", "get_agg", "agg_names"]

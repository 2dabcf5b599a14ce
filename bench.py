"""Headline benchmark: PRODUCTION query pipeline throughput.

Measures the BASELINE.json primary metric — datapoints aggregated per second
per chip — through the exact jitted function `/api/query` dispatches
(`ops.pipeline.run_group_pipeline`: prefix-sum windowed downsample + grouped
cross-series reduce), replacing the reference's per-datapoint iterator stack
(/root/reference/src/core/AggregationIterator.java:514, Downsampler.java:292,
TsdbQuery.GroupByAndAggregateCB :981).

Shape: BASELINE config 3 scaled up — 1024 series in 100 tag groups, 65536
points each (67.1M datapoints), avg 1h downsample + sum group aggregation.

Methodology — designed so the bench CANNOT report a dispatch artifact:

  1. SYNC IS `jax.block_until_ready`: chip_smoke.py's k-scaling probe
     settles on each installation whether it waits for the device (k
     unique dispatches take k times one, and a host fetch of the outputs
     agrees); the smoke fails where it does not, and CHANGES.md records
     the probe's numbers.
  2. Every dispatch carries a NEVER-REPEATED operand: a per-process random
     base + a monotonic counter folded into the window origin (a traced
     int64 operand), so no two dispatches — within a run or across runs —
     replay an identical execution, guarding against any result-memoization
     layer as well.
  3. The headline number is a PER-SAMPLE-SYNCED median, and the total
     measured wall time must exceed 1s (more samples are taken until it
     does), so clock noise cannot dominate.
  4. Plausibility guard: the implied HBM traffic (>=13 bytes/datapoint
     touched at least once) must not exceed any real TPU's memory bandwidth
     (cap 3.5 TB/s, above v5p's 2.77 TB/s).  A number above the cap is
     physically impossible and the bench refuses to emit it.
  5. Cross-check: a pipelined run (k dispatches, one sync at the end) must
     agree with the per-sample median within 2x; the slower is reported
     otherwise.

A bench that cannot measure exits non-zero: no accelerator (unless
`--platform cpu` is given explicitly, and then every row says cpu), a failed
compile, an implausible number.  It never prints a placeholder row.

Baseline: BASELINE.json north star — 1B datapoints < 2s on v5e-8, i.e.
62.5M datapoints/sec/chip.  vs_baseline > 1.0 beats the target.

Prints exactly one JSON line on stdout:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "device": {...}}
"""

from __future__ import annotations

import json
import os
import sys
import time
from statistics import median as _median

def _note(msg: str) -> None:
    """Progress to stderr (stdout carries exactly the one JSON line)."""
    print("[bench] " + msg, file=sys.stderr, flush=True)


METRIC = ("datapoints aggregated/sec/chip through the production "
          "/api/query pipeline (avg 1h downsample + groupby "
          "100 groups, 67M pts device-resident, per-sample-"
          "synced median, unique operands every dispatch)")


def add_platform_arg(ap) -> None:
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="the platform this run must measure on; cpu is "
                         "an explicit dry run (a CPU timing is not a "
                         "device number, and every row says cpu)")


def require_device(platform: str) -> dict:
    """Touch the backend and return the device stamp every output row
    carries — or exit non-zero when the default backend is not
    `platform`.  A benchmark never falls back to the host silently."""
    import opentsdb_tpu.ops  # noqa: F401  (x64, platform set, cache dir)
    import jax
    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")
    devices = jax.devices()
    if devices[0].platform != platform:
        raise SystemExit(
            "this run must measure on %r but JAX's default backend is %r "
            "(pass --platform cpu for an explicit CPU dry run)"
            % (platform, devices[0].platform))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices)}


S = 1024          # series
N = 65_536        # points per series  (S*N = 67.1M datapoints)
GROUPS = 100
START = 1_356_998_400_000
INTERVAL_MS = 3_600_000   # 1h avg downsample
STEP_MEAN_MS = 15_500     # ~15.5s cadence -> ~11.8 days of data

MIN_WALL_S = 1.0          # guard 3: total measured time must exceed this
MIN_SAMPLES = 5
MAX_SAMPLES = 64
BYTES_PER_DP = 13         # ts int32 + val f64 + mask byte, touched >= once
#                           (cache-hit layout: int32 offset timestamps)
HBM_CAP_BYTES_S = 3.5e12  # guard 4: no TPU chip streams faster than this
PIPELINE_K = 8            # cross-check dispatch count


class _OriginSequence:
    """Never-repeating window-origin offsets (guard 1).

    A per-process random base plus a monotonic counter, mapped into
    [0, INTERVAL_MS) so the shifted origin stays representative of the
    production window layout.  7919 is prime to INTERVAL_MS, so the walk
    visits 3.6M distinct offsets before cycling — far beyond any run.
    """

    def __init__(self):
        self._base = int.from_bytes(os.urandom(4), "big")
        self._i = 0

    def next(self) -> int:
        self._i += 1
        return (self._base + self._i * 7919) % INTERVAL_MS


def make_batch(precompacted: bool = True):
    """Device-resident [S, N] batch via a jitted closed-form generator.

    Default layout: timestamps as int32 offsets from the first window's
    start — what the device cache's gather delivers for eligible fixed
    grids (storage/device_cache.py `ts_base`), so the measured dispatch
    is the production cache-hit dispatch: no per-point compaction pass.
    `precompacted=False` keeps absolute int64 timestamps (the host-build
    path's layout).
    """
    import opentsdb_tpu.ops  # noqa: F401  (enables jax x64 mode)
    import jax
    import jax.numpy as jnp

    first = START - (START % INTERVAL_MS)

    def gen():
        rows = jnp.arange(S, dtype=jnp.int64)
        cols = jnp.arange(N, dtype=jnp.int64)
        h = (rows[:, None] * 2_654_435_761 + cols[None, :] * 40_503) \
            & 0x7FFFFFFF
        ts = START + cols[None, :] * STEP_MEAN_MS + h % 5_000
        val = 100.0 + (h % 1_000).astype(jnp.float64) * 0.05
        mask = jnp.ones((S, N), dtype=bool)
        # contiguous group runs — the layout the planner actually emits
        # (planner.py:403 concatenates per-group member lists), so the
        # benched dispatch matches production row order and the sorted
        # reduce modes can skip their permute (spec.rows_sorted)
        gid = rows * GROUPS // S
        if precompacted:
            return (ts - first).astype(jnp.int32), val, mask, gid
        return ts, val, mask, gid

    out = jax.jit(gen)()
    jax.block_until_ready(out)
    return out


def build_spec(precompacted: bool = True):
    import jax.numpy as jnp
    from opentsdb_tpu.ops.downsample import FixedWindows, pad_pow2
    from opentsdb_tpu.ops.pipeline import PipelineSpec, DownsampleStep

    end = START + N * STEP_MEAN_MS + 5_000
    fixed = FixedWindows.for_range(START, end, INTERVAL_MS)
    window_spec, wargs = fixed.split()
    if precompacted:
        # the batch carries int32 offsets from the first window
        # (make_batch); ts_base tells the pipeline so only the [W+1]
        # edges re-base
        wargs["ts_base"] = jnp.asarray(fixed.first_window_ms, jnp.int64)
    spec = PipelineSpec(
        aggregator="sum",
        downsample=DownsampleStep("avg", window_spec, "none", 0.0),
        rows_sorted=True)
    return spec, wargs, pad_pow2(GROUPS)


def dispatch(spec, g_pad, batch, wargs, origin_offset: int):
    """One production dispatch with a unique traced window origin."""
    import jax.numpy as jnp
    from opentsdb_tpu.ops.pipeline import run_group_pipeline

    ts, val, mask, gid = batch
    w = dict(wargs)
    w["first"] = wargs["first"] - jnp.asarray(origin_offset, jnp.int64)
    return run_group_pipeline(spec, ts, val, mask, gid, g_pad, w)


def drain(out) -> None:
    """Wait until the device has produced `out` (guard 1: the k-scaling
    probe in chip_smoke.py is what says block_until_ready waits here)."""
    import jax
    jax.block_until_ready(out)


def measure_drained(spec, g_pad, batch, wargs, origins
                    ) -> tuple[list[float], int, float]:
    """Per-sample-synced times until MIN_WALL_S total (guards 1-3).

    A sample is k back-to-back unique dispatches ending in one sync; k
    adapts upward when dispatches are fast (so legitimately fast hardware
    accumulates wall time instead of hitting the sample cap).  Returns
    (per-DISPATCH times, final k, total wall)."""
    k = 1
    times: list[float] = []
    wall = 0.0
    while (wall < MIN_WALL_S or len(times) < MIN_SAMPLES) \
            and len(times) < MAX_SAMPLES:
        t0 = time.perf_counter()
        out = None
        for _ in range(k):
            out = dispatch(spec, g_pad, batch, wargs, origins.next())
        drain(out)
        t = time.perf_counter() - t0
        wall += t
        times.append(t / k)
        if t < 0.2:
            # too fast for the clock: sync more dispatches per sample
            k = min(k * 4, 4096)
    return times, k, wall


def measure_pipelined(spec, g_pad, batch, wargs, origins) -> float:
    """k dispatches, one sync at the end (guard 5 cross-check)."""
    t0 = time.perf_counter()
    out = None
    for _ in range(PIPELINE_K):
        out = dispatch(spec, g_pad, batch, wargs, origins.next())
    drain(out)
    return (time.perf_counter() - t0) / PIPELINE_K


def run(device: dict) -> None:
    _note("devices: %d (%s); pipeline dispatches single-device"
          % (device["count"], device["platform"]))
    batch = make_batch()
    spec, wargs, g_pad = build_spec()
    origins = _OriginSequence()

    # compile + warm (unique origins too — even warmup never replays)
    drain(dispatch(spec, g_pad, batch, wargs, origins.next()))
    _note("compiled")

    samples, k_final, total_wall = measure_drained(spec, g_pad, batch,
                                                   wargs, origins)
    per_iter = _median(samples)
    _note("synced: %d samples (final k=%d dispatches/sample), "
          "median=%.4fs/dispatch, total wall=%.2fs (min=%.4fs max=%.4fs)"
          % (len(samples), k_final, per_iter, total_wall,
             min(samples), max(samples)))
    if total_wall < MIN_WALL_S:
        raise SystemExit("could not accumulate %.1fs of measured wall time"
                         % MIN_WALL_S)

    dp_per_sec = S * N / per_iter
    implied_bw = dp_per_sec * BYTES_PER_DP
    _note("implied HBM traffic: %.1f GB/s (>= %d B/dp)"
          % (implied_bw / 1e9, BYTES_PER_DP))
    if implied_bw > HBM_CAP_BYTES_S:
        raise SystemExit(
            "implied bandwidth %.2e B/s exceeds the %.2e B/s plausibility "
            "cap — measurement artifact, refusing to emit"
            % (implied_bw, HBM_CAP_BYTES_S))

    per_iter_pipe = measure_pipelined(spec, g_pad, batch, wargs, origins)
    ratio = per_iter / max(per_iter_pipe, 1e-9)
    _note("pipelined cross-check: %.4fs/dispatch (synced/pipelined = %.2fx)"
          % (per_iter_pipe, ratio))
    if ratio > 2.0 or ratio < 0.5:
        # The two timing methods disagree — one of them is an artifact.
        # Report the SLOWER (conservative) per-dispatch time; a bench may
        # understate but must never overstate.
        _note("WARNING: pipelined and per-sample timings disagree by >2x — "
              "reporting the slower of the two")
        per_iter = max(per_iter, per_iter_pipe)
        dp_per_sec = S * N / per_iter

    baseline = 1e9 / 2.0 / 8.0  # north star: 1B pts < 2s on 8 chips
    print(json.dumps({
        "metric": METRIC,
        "value": round(dp_per_sec, 1),
        "unit": "datapoints/sec/chip",
        "vs_baseline": round(dp_per_sec / baseline, 4),
        "device": device,
    }), flush=True)


def main() -> None:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    add_platform_arg(ap)
    run(require_device(ap.parse_args().platform))


if __name__ == "__main__":
    main()

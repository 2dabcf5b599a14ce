"""BASELINE.md measurement configs 1-7 as runnable benchmarks.

`python bench_configs.py [--config N] [--scale F]` prints one JSON line per
config (bench.py stays the single-line headline bench the driver runs).

Configs (BASELINE.md / BASELINE.json):
  1. 1M pts, single series, avg 1h downsample          - correctness baseline
  2. 100M pts, sum/min/max/count multi-agg 10s         - multi-kernel fusion
  3. 10k-series group-by + avg downsample              - segment-reduce fan-out
  4. rate + p99 over 500M pts                          - non-associative kernels
  5. 1B pts -> 1m rollups, time-chunked                - offline batch pass
  6. bulk ingest points/sec (host write path)          - TSDB.add_points_bulk
  7. p50 end-to-end /api/query latency, 1B pts in-store - full served path

Timing methodology (same rules as bench.py — see its module docstring):
  * every timed run ends in `jax.block_until_ready` (bench.drain), which
    chip_smoke.py's k-scaling probe shows waits for the device here;
  * no dispatch is ever repeated with identical operands: repetitions
    shift the traced window origin / chunk base through a per-process
    random walk, so neither the runtime nor any future memoization layer
    can short-circuit a rep;
  * each config accumulates >= 1s of measured wall time where the scale
    allows, and reports a median over passes.

Configs 2/4/5 exceed device memory as one batch, so they run through the
streaming machinery (ops.streaming): chunks are generated on device by a
closed-form hash (the storage layer's role; generation is timed separately
with its own syncs and subtracted).  Config 5 chunks by TIME (rollup
output rows are emitted per chunk — the write-side shape of
TSDB.addAggregatePoint); the others by point index.

Use --platform cpu --scale 0.01 for a quick CPU dry run; without the
switch a run that finds no TPU exits non-zero, and every row names the
device it was measured on.

Deadline discipline (--deadline S): every loop that can run long — timed
passes, streamed chunk folds, config 7's ingest — checks a COOPERATIVE
per-config deadline between units of work and finalizes early with a
partial-but-honest row (the points actually processed over the seconds
actually measured) instead of being killed mid-dispatch by an outer timeout.  A single
dispatch that overruns is not interrupted: sizing dispatches so none can
is the product's job (ROADMAP A1), not this harness's.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

from bench import _median, add_platform_arg, drain, require_device

START = 1_356_998_400_000
STEP_MS = 10_000  # 10s cadence

MIN_WALL_S = 1.0
MIN_PASSES = 3
MAX_PASSES = 32

# Cooperative per-config deadline (monotonic seconds; None = unlimited).
_DEADLINE: float | None = None
# {"platform", "kind", "count"} of the backend, stamped on every row
_DEVICE: dict = {}


def _deadline_left() -> float:
    return math.inf if _DEADLINE is None else _DEADLINE - time.monotonic()


def _fits(estimated_s: float) -> bool:
    """Can another unit of work (estimated from the last one) finish
    before the deadline?  1.5x headroom: overrunning by one unit is the
    failure mode this exists to prevent."""
    return _deadline_left() > 1.5 * estimated_s


def _note(msg: str) -> None:
    print("[bench_configs] " + msg, file=sys.stderr, flush=True)


def _emit(config: int, label: str, points: int, seconds: float,
          n_dev: int, unit: str = "datapoints/sec/chip",
          baseline: float | None = None) -> None:
    rate = points / max(seconds, 1e-9) / n_dev
    if baseline is None:
        baseline = 1e9 / 2.0 / 8.0  # north star: 62.5M dp/s/chip
    rec = {
        "metric": "config %d: %s" % (config, label),
        "value": round(rate, 1),
        "unit": unit,
        "vs_baseline": round(rate / baseline, 4),
        "device": _DEVICE,
    }
    print(json.dumps(rec), flush=True)


class _Uniquifier:
    """Never-repeating int offsets (per-process random base + counter) —
    folded into window origins and chunk bases so no two dispatches are
    operand-identical, within or across runs."""

    def __init__(self):
        self._base = int.from_bytes(os.urandom(4), "big")
        self._i = 0

    def next(self, mod: int = 3_600_000) -> int:
        self._i += 1
        return (self._base + self._i * 7919) % mod


_UNIQ = _Uniquifier()


def _timed_passes(run_pass):
    """Median per-pass seconds over unique-operand passes, >= MIN_WALL_S
    total measured wall; each pass must end with its own sync inside."""
    times = []
    wall = 0.0
    while (wall < MIN_WALL_S or len(times) < MIN_PASSES) \
            and len(times) < MAX_PASSES:
        if times and not _fits(times[-1]):
            _note("deadline: stopping after %d passes (%.0fs left)"
                  % (len(times), _deadline_left()))
            break
        t0 = time.perf_counter()
        run_pass()
        dt = time.perf_counter() - t0
        wall += dt
        times.append(dt)
    return _median(times), len(times)


def _chunk_gen(s, n, base_col):
    """Closed-form [s, n] chunk (ts sorted per row, deterministic values)."""
    import jax.numpy as jnp
    rows = jnp.arange(s, dtype=jnp.int64)
    cols = base_col + jnp.arange(n, dtype=jnp.int64)
    h = (rows[:, None] * 2_654_435_761 + cols[None, :] * 40_503) & 0x7FFFFFFF
    ts = START + cols[None, :] * STEP_MS + h % 4_000
    val = 100.0 + (h % 1_000).astype(jnp.float64) * 0.05
    mask = jnp.ones((s, n), dtype=bool)
    return ts, val, mask


_GEN = None


def _gen_fn():
    """Module-level jitted chunk generator — one compile cache for every
    pass (a per-pass jax.jit wrapper would land its recompile inside the
    gen calibration that gets SUBTRACTED from measured time, inflating
    the reported throughput)."""
    global _GEN
    if _GEN is None:
        import jax
        _GEN = jax.jit(_chunk_gen, static_argnums=(0, 1))
    return _GEN


# ------------------------------------------------------------------ #

def _grouped_config(config: int, label: str, s: int, n: int, gid, g: int,
                    spec, fixed, n_dev: int, reps_points: int) -> None:
    """Shared shape of configs 1 and 3: one grouped dispatch per pass,
    window origin shifted uniquely each pass."""
    import jax.numpy as jnp
    from opentsdb_tpu.ops.pipeline import run_group_pipeline

    gen = _gen_fn()
    batch = gen(s, n, 0)
    drain(batch)
    wspec, wargs = fixed.split()
    ts, val, mask = batch

    def one_pass():
        w = dict(wargs)
        w["first"] = wargs["first"] - jnp.asarray(_UNIQ.next(), jnp.int64)
        drain(run_group_pipeline(spec, ts, val, mask, gid, g, w))

    w0 = dict(wargs)
    w0["first"] = wargs["first"] - jnp.asarray(_UNIQ.next(), jnp.int64)
    drain(run_group_pipeline(spec, ts, val, mask, gid, g, w0))  # compile
    per_pass, n_passes = _timed_passes(one_pass)
    _note("config %d: %d passes, median %.4fs" % (config, n_passes,
                                                  per_pass))
    _emit(config, label, reps_points, per_pass, n_dev)


def config1(scale: float, n_dev: int) -> None:
    """1M pts, one series, avg 1h — END TO END through the planner.

    r3 measured the bare device kernel and still lost 11x to the Java
    iterator (dispatch floor).  r4's fix is routing, so this config must
    measure what a client sees: TSQuery -> planner -> (host fast lane
    below tsd.query.host_lane.max_points | accelerator above) -> JSON
    dps.  Both lanes are reported; the default lane (host) is the
    headline config-1 number.
    """
    import numpy as np
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.models import TSQuery, parse_m_subquery
    from opentsdb_tpu.utils.config import Config

    n = max(int(1_000_000 * scale), 1024)

    def mk(host_lane_pts):
        t = TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.query.device_cache.enable": "false",
            "tsd.query.mesh.enable": False,
            "tsd.query.host_lane.max_points": str(host_lane_pts),
        }))
        key = t._series_key("bench.c1", {"h": "a"}, create=True)
        ts_ms = START + np.arange(n, dtype=np.int64) * STEP_MS
        vals = 100.0 + (np.arange(n) % 1_000) * 0.05
        t.store.add_batch(key, ts_ms, vals, np.zeros(n, bool))
        return t

    for label, host_pts in (("host-lane", 10_000_000), ("device-lane", 0)):
        t = mk(host_pts)

        def one_pass():
            # unique start SECOND per pass (within the hour before the
            # data, so every point stays in range and the epoch-aligned
            # window grid genuinely varies): no cache layer can
            # short-circuit a repeat (review r4 — a sub-second offset
            # was quantized away by the //1000)
            off_s = _UNIQ.next(3600)
            q = TSQuery(start=str(START // 1000 - 3600 + off_s),
                        end=str((START + n * STEP_MS) // 1000),
                        queries=[parse_m_subquery("sum:1h-avg:bench.c1")])
            q.validate()
            res = t.new_query_runner().run(q)
            assert res and res[0].dps   # host values: inherently synced

        one_pass()  # compile
        per_pass, n_passes = _timed_passes(one_pass)
        _note("config 1 (%s): %d passes, median %.4fs"
              % (label, n_passes, per_pass))
        _emit(1, "1M pts single-series avg-1h end-to-end (%s)" % label,
              n, per_pass, 1)


def config3(scale: float, n_dev: int) -> None:
    """Group-by over 10k tag-series + avg downsample — one dispatch."""
    import jax.numpy as jnp
    from opentsdb_tpu.ops.downsample import FixedWindows, pad_pow2
    from opentsdb_tpu.ops.pipeline import PipelineSpec, DownsampleStep

    s = max(int(10_240 * scale), 64)
    n = 2048
    fixed = FixedWindows.for_range(START, START + n * STEP_MS, 3_600_000)
    wspec, _ = fixed.split()
    spec = PipelineSpec("avg", DownsampleStep("avg", wspec, "none", 0.0))
    _grouped_config(3, "10k-series group-by avg downsample", s, n,
                    jnp.arange(s, dtype=jnp.int64), pad_pow2(s), spec,
                    fixed, n_dev, s * n)


def _stream_pass(s, n_chunk, chunks, wspec, wargs, finishes, base0: int,
                 sketch: bool = False):
    """Generate+accumulate up to `chunks` chunks starting at column
    base0; returns (elapsed_minus_gen, finish outputs, chunks_done).
    Every chunk base is unique (caller advances base0 per pass);
    generation is calibrated with its own syncs over a disjoint base
    range.  Both loops check the cooperative deadline BETWEEN chunks:
    a slow chip folds fewer chunks and the caller reports the partial
    point count honestly."""
    from opentsdb_tpu.ops.streaming import StreamAccumulator, lanes_for

    gen = _gen_fn()

    # Calibrate generation cost alone (disjoint bases; synced per chunk).
    # The per-chunk gen cost also feeds the fold loop's deadline estimate.
    cal0 = base0 + chunks * n_chunk
    t0 = time.perf_counter()
    cal_done = 0
    for k in range(chunks):
        if cal_done and not _fits((time.perf_counter() - t0) / cal_done):
            break
        drain(gen(s, n_chunk, cal0 + k * n_chunk))
        cal_done += 1
    gen_per_chunk = (time.perf_counter() - t0) / cal_done

    # Window-sliced folds: each chunk's window range is host-known, so
    # the accumulator merges an O(S*wc) slice instead of the full [S, W]
    # grid (full-grid fold traffic once cost 4.7s/chunk on config 2 in
    # an earlier chip session).
    first_ms = int(wargs["first"])
    interval = wspec.interval_ms
    wslice = (n_chunk * STEP_MS + 4_000) // interval + 2
    acc = StreamAccumulator.create(s, wspec, wargs, sketch=sketch,
                                   lanes=lanes_for(finishes),
                                   window_slice=wslice)
    # update() is async (returns at enqueue): without a sync the
    # between-chunk clock reads enqueue time and a slow chip is only
    # discovered inside the final sync.  With a deadline armed, each
    # chunk syncs on the accumulator so elapsed/done is true execution
    # time.
    pace = _DEADLINE is not None
    t0 = time.perf_counter()
    done = 0
    for k in range(chunks):
        if done and not _fits((time.perf_counter() - t0) / done):
            _note("deadline: folding stopped at chunk %d/%d (%.0fs left)"
                  % (done, chunks, _deadline_left()))
            break
        w0 = (START + (base0 + k * n_chunk) * STEP_MS - first_ms) \
            // interval
        acc.update(*gen(s, n_chunk, base0 + k * n_chunk), w0=w0)
        done += 1
        if pace:
            drain(acc.state)
        if done % 4 == 0:
            _note("stream: %d/%d chunks (%.2fs/chunk)"
                  % (done, chunks, (time.perf_counter() - t0) / done))
    outs = [acc.finish(f) for f in finishes]
    drain(outs)
    elapsed = time.perf_counter() - t0
    assert acc.oob_count() == 0, "streaming slice dropped points"
    return max(elapsed - gen_per_chunk * done, 1e-9), outs, done


def config2(scale: float, n_dev: int) -> None:
    """100M pts, multi-agg (sum/min/max/count) 10s downsample, streamed."""
    from opentsdb_tpu.ops.downsample import FixedWindows

    total = int(100_000_000 * scale)
    s = 128
    n_chunk = 65_536
    chunks = max(total // (s * n_chunk), 1)
    span = n_chunk * chunks * STEP_MS
    points = s * n_chunk * chunks

    def one_pass():
        # unique chunk base AND matching window origin per pass
        base0 = _UNIQ.next(1 << 26)
        pass_start = START + base0 * STEP_MS
        fixed = FixedWindows.for_range(pass_start, pass_start + span,
                                       10_000)
        wspec, wargs = fixed.split()
        secs, _, done = _stream_pass(s, n_chunk, chunks, wspec, wargs,
                                     ["sum", "min", "max", "count"], base0)
        return secs, s * n_chunk * done

    one_pass()  # compile (wspec is shape-stable across passes)
    passes = []     # (secs, points actually folded) — may be partial
    wall = 0.0
    t_loop = time.perf_counter()
    while (wall < MIN_WALL_S or len(passes) < MIN_PASSES) \
            and len(passes) < 8:
        if passes and not _fits((time.perf_counter() - t_loop)
                                / len(passes)):
            break
        secs, pts = one_pass()
        passes.append((secs, pts))
        wall += secs
    ranked = sorted(passes, key=lambda p: p[0] / p[1])
    secs_med, pts_med = ranked[len(ranked) // 2]   # median per-point time
    partial = pts_med < points
    _note("config 2: %d passes, median %.3fs over %d pts%s"
          % (len(passes), secs_med, pts_med,
             " (deadline-partial)" if partial else ""))
    _emit(2, "100M pts multi-agg 10s downsample (streamed)%s"
          % (" [partial: %d of %d pts before the deadline]"
             % (pts_med, points) if partial else ""),
          pts_med, secs_med, n_dev)


def config4(scale: float, n_dev: int) -> None:
    """rate + p99 over 500M pts: stream to grid, rate+percentile tail."""
    import jax.numpy as jnp
    from opentsdb_tpu.ops.downsample import FixedWindows
    from opentsdb_tpu.ops.pipeline import (
        PipelineSpec, DownsampleStep, run_grid_tail)
    from opentsdb_tpu.ops.rate import RateOptions

    total = int(500_000_000 * scale)
    s = 512
    n_chunk = 65_536
    chunks = max(total // (s * n_chunk), 1)
    span = n_chunk * chunks * STEP_MS
    fixed0 = FixedWindows.for_range(START, START + span, 60_000)
    wspec0, _ = fixed0.split()
    spec = PipelineSpec("p99", DownsampleStep("avg", wspec0, "none", 0.0),
                        rate=RateOptions())
    gid = jnp.zeros(s, jnp.int64)
    points = s * n_chunk * chunks

    def one_pass():
        base0 = _UNIQ.next(1 << 26) * 6  # keep origin 60s-aligned
        pass_start = START + base0 * STEP_MS
        fixed = FixedWindows.for_range(pass_start, pass_start + span,
                                       60_000)
        wspec, wargs = fixed.split()
        secs, outs, done = _stream_pass(s, n_chunk, chunks, wspec, wargs,
                                        ["avg"], base0)
        t0 = time.perf_counter()
        wts, v, m = outs[0]
        drain(run_grid_tail(spec, wts, v, m, gid, 1))
        tail_s = time.perf_counter() - t0
        return secs + tail_s, s * n_chunk * done

    one_pass()  # compile
    t1 = time.perf_counter()
    passes = [one_pass()]
    last_wall = time.perf_counter() - t1
    for _ in range(MIN_PASSES - 1):
        if not _fits(last_wall):
            break
        t1 = time.perf_counter()
        passes.append(one_pass())
        last_wall = time.perf_counter() - t1
    ranked = sorted(passes, key=lambda p: p[0] / p[1])
    secs_med, pts_med = ranked[len(ranked) // 2]
    partial = pts_med < points
    _note("config 4: %d passes, median %.3fs over %d pts%s"
          % (len(passes), secs_med, pts_med,
             " (deadline-partial)" if partial else ""))
    _emit(4, "rate+p99 over 500M pts (streamed grid + percentile tail)%s"
          % (" [partial: %d of %d pts before the deadline]"
             % (pts_med, points) if partial else ""),
          pts_med, secs_med, n_dev)


def config5(scale: float, n_dev: int) -> None:
    """1B pts -> 1m rollup lanes, time-chunked (write-side batch pass)."""
    from opentsdb_tpu.ops.downsample import FixedWindows
    from opentsdb_tpu.ops.streaming import StreamAccumulator, lanes_for

    total = int(1_000_000_000 * scale)
    s = 1024
    n_chunk = 65_536
    chunks = max(total // (s * n_chunk), 1)
    gen = _gen_fn()
    span = n_chunk * STEP_MS
    points = s * n_chunk * chunks

    def gen_calibration(base0):
        t0 = time.perf_counter()
        done = 0
        for k in range(chunks):
            if done and not _fits((time.perf_counter() - t0) / done):
                break
            drain(gen(s, n_chunk, base0 + k * n_chunk))
            done += 1
        return (time.perf_counter() - t0) / done            # per chunk

    # Each time chunk's 1m windows are disjoint from the next chunk's, so
    # rollup rows (sum/count/min/max lanes) emit per chunk — the write-side
    # shape of TSDB.addAggregatePoint (:1359-1457) batched per window.
    def one_chunk(k: int, base0: int) -> None:
        chunk_start = START + (base0 + k * n_chunk) * STEP_MS
        fixed = FixedWindows.for_range(chunk_start, chunk_start + span,
                                       60_000)
        wspec, wargs = fixed.split()
        acc = StreamAccumulator.create(
            s, wspec, wargs,
            lanes=lanes_for(("sum", "count", "min", "max")))
        acc.update(*gen(s, n_chunk, base0 + k * n_chunk))
        drain([acc.finish(f) for f in ("sum", "count", "min", "max")])

    # compile (same shapes every chunk).  Progress notes bracket every
    # potentially-slow phase so a hang is attributable from stderr.
    _note("config 5: compiling rollup chunk (%d chunks/pass)" % chunks)
    one_chunk(0, _UNIQ.next(1 << 28))
    _note("config 5: compile done")

    def one_pass():
        base0 = _UNIQ.next(1 << 28)
        gen_per_chunk = gen_calibration(base0 + chunks * n_chunk)
        _note("config 5: gen calibrated (%.3fs/chunk)" % gen_per_chunk)
        t0 = time.perf_counter()
        done = 0
        for k in range(chunks):
            # one_chunk syncs per chunk, so elapsed/done is real
            # execution time and the deadline check is meaningful
            if done and not _fits((time.perf_counter() - t0) / done):
                _note("deadline: rollup stopped at chunk %d/%d"
                      % (done, chunks))
                break
            one_chunk(k, base0)
            done += 1
            if done % 4 == 0:
                _note("config 5: %d/%d chunks (%.2fs/chunk)"
                      % (done, chunks, (time.perf_counter() - t0) / done))
        secs = max(time.perf_counter() - t0 - gen_per_chunk * done, 1e-9)
        return secs, s * n_chunk * done

    t1 = time.perf_counter()
    passes = [one_pass()]
    last_wall = time.perf_counter() - t1
    for _ in range(MIN_PASSES - 1):
        if not _fits(last_wall):
            break
        t1 = time.perf_counter()
        passes.append(one_pass())
        last_wall = time.perf_counter() - t1
    ranked = sorted(passes, key=lambda p: p[0] / p[1])
    secs_med, pts_med = ranked[len(ranked) // 2]
    partial = pts_med < points
    _note("config 5: %d passes, median %.3fs over %d pts%s"
          % (len(passes), secs_med, pts_med,
             " (deadline-partial)" if partial else ""))
    _emit(5, "1B pts -> 1m rollup lanes (time-chunked)%s"
          % (" [partial: %d of %d pts before the deadline]"
             % (pts_med, points) if partial else ""),
          pts_med, secs_med, n_dev)


def config6(scale: float, n_dev: int) -> None:
    """Host ingest: bulk /api/put path vs per-point, points/sec.

    Pure host-side (no device dispatch): honest wall clock.  The emitted
    vs_baseline is the speedup of the bulk path over the per-point path
    (the reference's only write-scale claim is qualitative, README:12-15).
    """
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.utils.config import Config

    n = max(int(400_000 * scale), 10_000)
    hosts = 64
    dps = [{"metric": "ingest.bench", "timestamp": 1_356_998_400 + i,
            "value": float(i % 97) + 0.5, "tags": {"host": "h%d"
                                                   % (i % hosts)}}
           for i in range(n)]

    t_bulk = TSDB(Config({"tsd.core.auto_create_metrics": True}))
    t0 = time.perf_counter()
    success, errors = t_bulk.add_points_bulk(dps)
    bulk_secs = time.perf_counter() - t0
    assert success == n and not errors

    # native C++ body parser (the path a real POST /api/put takes): raw
    # JSON bytes in, columnar batches out — includes the JSON parse the
    # pre-parsed python timing above gets for free
    body = json.dumps(dps).encode()
    t_native = TSDB(Config({"tsd.core.auto_create_metrics": True}))
    t0 = time.perf_counter()
    native = t_native.add_points_bulk_native(body)
    native_secs = time.perf_counter() - t0
    have_native = native is not None
    if have_native:
        assert native[0] == n and not native[1]

    t_single = TSDB(Config({"tsd.core.auto_create_metrics": True}))
    t0 = time.perf_counter()
    for dp in dps:
        t_single.add_point(dp["metric"], dp["timestamp"], dp["value"],
                           dp["tags"])
    single_secs = time.perf_counter() - t0

    _note("config 6: native %s, bulk %.3fs, per-point %.3fs for %d pts"
          % ("%.3fs" % native_secs if have_native else "unavailable",
             bulk_secs, single_secs, n))
    best_secs = native_secs if have_native else bulk_secs
    _emit(6, "bulk ingest points/sec via %s (vs_baseline = speedup over "
             "per-point add_point)"
          % ("the native C++ /api/put body parser" if have_native
             else "the python bulk path"),
          n, best_secs, 1, unit="points/sec ingested",
          baseline=n / max(single_secs, 1e-9))


def config7(scale: float, n_dev: int) -> None:
    """p50 end-to-end /api/query latency with 1B points IN THE STORE.

    The full served path: planner -> window_count budgeting -> streamed
    chunked reads straight out of the columnar store -> device accumulator
    -> grid tail -> JSON-able result.  Unlike configs 1-5 (device-resident
    batches), this includes host packing and host->device transfer, and
    the metric text says so.  The planner's result fetch (np.asarray) is
    a real sync, so wall clock here is honest by construction.

    vs_baseline: north star is 1B pts < 2s on EIGHT chips — a 16
    chip-second budget, so vs_baseline = 16 / (p50_seconds * n_dev).
    """
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.models import TSQuery, parse_m_subquery
    from opentsdb_tpu.utils.config import Config
    import numpy as np

    total = int(1_000_000_000 * scale)
    s = 1024
    per = max(total // s, 1024)
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    n_series = 0
    for i in range(s):
        # host-side ingest can dominate a slow box: a deadline cut here
        # still yields an honest row — the label carries the real
        # in-store point count and vs_baseline scales with it
        if i and not _fits((time.perf_counter() - t0) / i):
            _note("deadline: ingest stopped at series %d/%d" % (i, s))
            break
        ts = (START + np.arange(per, dtype=np.int64) * STEP_MS
              + int(rng.integers(0, 4000)))
        sk = tsdb._series_key("lat.m", {"host": "h%04d" % i,
                                        "dc": "d%d" % (i % 16)},
                              create=True)
        tsdb.store.add_batch(sk, ts, rng.normal(100, 25, per), False)
        n_series += 1
    in_store = n_series * per
    _note("config 7: ingested %d pts in %.1fs"
          % (in_store, time.perf_counter() - t0))

    end_s = (START + per * STEP_MS) // 1000 + 10

    def run_query():
        q = TSQuery(start=str(START // 1000), end=str(end_s),
                    queries=[parse_m_subquery("sum:1m-avg:lat.m{dc=*}")])
        q.validate()
        return tsdb.new_query_runner().run(q)

    # Production daemons run the maintenance thread, whose device-cache
    # refresh pins the metric's columns in HBM after the first (streamed)
    # query — the steady state a dashboard sees.  Metrics beyond the
    # cache's build budget keep streaming every pass (the honest
    # beyond-memory number).
    tsdb.start_maintenance()
    try:
        t1 = time.perf_counter()
        run_query()  # compile + queue the cache build
        first_query_s = time.perf_counter() - t1
        deadline = time.time() + min(60.0, max(_deadline_left() / 2, 5.0))
        while (tsdb.device_cache is not None and len(tsdb.device_cache) == 0
               and in_store <= tsdb.device_cache.build_max_points
               and time.time() < deadline):
            time.sleep(0.5)
        cached = (tsdb.device_cache is not None
                  and len(tsdb.device_cache) > 0)
        if cached and _fits(first_query_s):
            run_query()     # compile the cached-batch shape untimed
        lats = []
        last = first_query_s
        for _ in range(MIN_PASSES):
            if lats and not _fits(last):
                _note("deadline: stopping after %d latency passes"
                      % len(lats))
                break
            t0 = time.perf_counter()
            run_query()
            last = time.perf_counter() - t0
            lats.append(last)
    finally:
        if tsdb.maintenance is not None:
            tsdb.maintenance.stop(final_flush=False)
            tsdb.maintenance = None
    p50 = _median(lats)
    _note("config 7: latencies %s (device cache %s)"
          % ([round(x, 3) for x in lats],
             "warm" if cached else "not used"))
    # north star: 1B pts < 2s on 8 chips = a 16 chip-second budget PER
    # BILLION points; scale the budget to what is actually in the store
    # so smoke runs and deadline-partial ingests stay honest
    budget_s = 16.0 * in_store / 1e9
    print(json.dumps({
        "metric": "config 7: p50 /api/query latency, %d pts in-store, "
                  "%s; single-chip-equivalent budget %.2fs"
                  % (in_store,
                     "served from the device-resident series cache "
                     "(production steady state: maintenance thread "
                     "pinned the metric in HBM after the first streamed "
                     "pass)" if cached else
                     "streamed via chunked store reads (beyond the "
                     "device cache budget; includes host packing + "
                     "host->device transfer)", budget_s),
        "value": round(p50, 3),
        "unit": "seconds p50 latency",
        "vs_baseline": round(budget_s / max(p50, 1e-9) / n_dev, 4),
        "device": _DEVICE,
    }), flush=True)


CONFIGS = {1: config1, 2: config2, 3: config3, 4: config4, 5: config5,
           6: config6, 7: config7}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", type=int, default=0,
                    help="run one config (default: all)")
    ap.add_argument("--scale", type=float, default=1.0,
                    help="shrink factor for smoke runs (e.g. 0.01)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="cooperative per-config budget in seconds: each "
                         "config finalizes a partial-but-honest row "
                         "instead of overrunning (0 = unlimited)")
    add_platform_arg(ap)
    args = ap.parse_args()

    global _DEADLINE, _DEVICE
    _DEVICE = require_device(args.platform)
    n_dev = _DEVICE["count"]
    _note("devices: %d (%s, %s)" % (n_dev, _DEVICE["platform"],
                                    _DEVICE["kind"]))

    # a config that cannot measure raises: the run ends non-zero there,
    # with the rows already printed standing as measured
    for c in [args.config] if args.config else sorted(CONFIGS):
        _note("running config %d" % c)
        if args.deadline > 0:
            _DEADLINE = time.monotonic() + args.deadline
        CONFIGS[c](args.scale, n_dev)


if __name__ == "__main__":
    main()

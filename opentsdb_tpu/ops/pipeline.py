"""Fused query pipeline: downsample -> rate -> cross-series aggregation.

Composes the kernels in the reference's iterator-chain order
(AggregationIterator.create :253-380 wires Span -> Downsampler -> RateSpan ->
merge) as one jit-compiled function per static pipeline spec.  XLA fuses the
stages.  Compile churn is bounded: batch shapes and window counts pad to
powers of two, and time-range-dependent values (window origin, calendar
edges) are traced operands, so repeated dashboard queries hit the jit cache.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.ops.aggregators import get_agg, Aggregator, PREV
from opentsdb_tpu.ops.downsample import (
    downsample, apply_fill, WindowSpec, FixedWindows, EdgeWindows, AllWindow,
    window_timestamps, pad_pow2, FILL_NONE)
from opentsdb_tpu.ops.rate import rate, RateOptions
from opentsdb_tpu.ops.union_agg import union_aggregate, grid_aggregate

PAD_TS = np.iinfo(np.int64).max


@dataclass(frozen=True)
class DownsampleStep:
    """Static downsample config; traced window args travel separately."""
    function: str
    window_spec: WindowSpec
    fill_policy: str = FILL_NONE
    fill_value: float = 0.0


@dataclass(frozen=True)
class PipelineSpec:
    """Static (hashable) description of one group's numeric pipeline."""
    aggregator: str
    downsample: DownsampleStep | None = None
    rate: RateOptions | None = None
    int_mode: bool = False  # Java long arithmetic end-to-end
    # union-path tile budget override (<= 0: module default); the batched
    # union runner sets default/B so B vmapped groups share one envelope
    tile_cells: int = 0
    # caller guarantee: the batch's gid is non-decreasing (the planner
    # always emits groups as concatenated runs, planner.py:403) — the
    # sorted group-reduce modes then skip argsort + permute gathers
    rows_sorted: bool = False
    # caller guarantee: row i of the batch IS group i (one member a
    # group) and the whole batch travels in this one dispatch — the
    # group reduce is then a copy (ops/group_agg.py, form "rows")
    row_groups: bool = False


def _pipeline(spec: PipelineSpec, ts, val, mask, wargs):
    agg = get_agg(spec.aggregator)
    if spec.rate is not None:
        # Rates never LERP across series: a missing rate contributes the
        # previous rate value (AggregationIterator.java:744-752).
        agg = Aggregator(agg.name, PREV, agg.reduce)
    if spec.downsample is not None:
        step = spec.downsample
        wts, v, m = downsample(ts, val, mask, step.function, step.window_spec,
                               wargs, step.fill_policy, step.fill_value)
        grid = jnp.asarray(wts)
        if spec.rate is not None:
            grid_b = jnp.broadcast_to(grid[None, :], v.shape)
            _, v, m, _ = rate(grid_b, v, m, spec.rate, all_int=False)
        return grid_aggregate(grid, v, m, agg, int_mode=False)
    if spec.rate is not None:
        work_ts, work_val, work_mask, _ = rate(ts, val, mask, spec.rate,
                                               all_int=spec.int_mode)
        return union_aggregate(work_ts, work_val, work_mask, agg,
                               int_mode=False, tile_cells=spec.tile_cells)
    return union_aggregate(ts, val, mask, agg, int_mode=spec.int_mode,
                           tile_cells=spec.tile_cells)


_jitted = jax.jit(_pipeline, static_argnums=0)


def _union_batch_pipeline(spec: PipelineSpec, ts, val, mask):
    """B same-shaped union (no-downsample) groups in ONE dispatch.

    vmaps the union pipeline over a leading group axis [B, S, N]; the
    caller divides the union tile budget by B via spec.tile_cells so the
    total materialization envelope stays what a single group's would be.
    The per-group union grids are independent — outputs come back
    batched ([B, S*N] timestamps/values/mask), one row per group.
    """
    return jax.vmap(lambda t, v, m: _pipeline(spec, t, v, m, {}))(
        ts, val, mask)


_jitted_union_batch = jax.jit(_union_batch_pipeline, static_argnums=0)


# shape: ts[B,S,N] any, val[B,S,N] any, mask[B,S,N] bool
def run_union_batch_pipeline(spec: PipelineSpec, ts, val, mask):
    """Batched union pipeline -> per-group (u[B, U], out[B, U], mask[B, U])."""
    return _jitted_union_batch(spec, ts, val, mask)


# shape: ts[S,N] any, val[S,N] any, mask[S,N] bool
def run_pipeline(spec: PipelineSpec, ts, val, mask, wargs: dict | None = None):
    """Execute the pipeline; returns (out_ts, out_val, out_mask) on device."""
    return _jitted(spec, ts, val, mask, wargs or {})


def _rollup_avg_pipeline(spec: PipelineSpec, ts_s, val_s, mask_s,
                         ts_c, val_c, mask_c, wargs):
    """Rollup-average read: sum lane / count lane, then the normal tail.

    Reference behavior: Downsampler.java:155-210 — when reading an `avg`
    rollup the downsampler consumes paired sum and count cells and divides.
    Here both lanes downsample with segment-sum, the per-window quotient
    becomes the per-series value, then rate/fill/cross-series aggregation
    proceed exactly like the raw pipeline.
    """
    step = spec.downsample
    wts, sums, msum = downsample(ts_s, val_s, mask_s, "sum", step.window_spec,
                                 wargs, FILL_NONE)
    _, cnts, mcnt = downsample(ts_c, val_c, mask_c, "sum", step.window_spec,
                               wargs, FILL_NONE)
    ok = msum & mcnt & (cnts > 0)
    v = jnp.where(ok, sums / jnp.where(ok, cnts, 1.0), jnp.nan)
    # Fill policy over empty live windows (FillingDownsampler semantics).
    nwin = wargs["nwin"]
    live = jnp.arange(v.shape[-1]) < nwin
    v, m = apply_fill(v, ok, live[None, :], step.fill_policy,
                      step.fill_value)
    grid = jnp.asarray(wts)
    agg = get_agg(spec.aggregator)
    if spec.rate is not None:
        agg = Aggregator(agg.name, PREV, agg.reduce)
        grid_b = jnp.broadcast_to(grid[None, :], v.shape)
        _, v, m, _ = rate(grid_b, v, m, spec.rate, all_int=False)
    return grid_aggregate(grid, v, m, agg, int_mode=False)


_jitted_rollup_avg = jax.jit(_rollup_avg_pipeline, static_argnums=0)


def run_rollup_avg_pipeline(spec: PipelineSpec, ts_s, val_s, mask_s,
                            ts_c, val_c, mask_c, wargs: dict | None = None):
    """Execute the rollup-avg pipeline (sum lane + count lane batches)."""
    return _jitted_rollup_avg(spec, ts_s, val_s, mask_s, ts_c, val_c, mask_c,
                              wargs or {})


def _group_pipeline(spec: PipelineSpec, num_groups: int, ts, val, mask, gid,
                    wargs):
    """All-groups-at-once pipeline: one dispatch for any group count.

    Replaces the per-group Python loop of round 1 (one jit call per group-by
    bucket — 10k dispatches for a 10k-group query) with a single
    gid-segmented device call: downsample and rate are row-local, the
    cross-series reduce segments over (group, window) cells.
    """
    step = spec.downsample
    wts, v, m = downsample(ts, val, mask, step.function, step.window_spec,
                           wargs, step.fill_policy, step.fill_value)
    return _grid_tail(spec, num_groups, wts, v, m, gid)


# The fourth return of every grouped program: one int32 scalar whose
# bits say which lane each `lax.cond` of the tail took on the device.
LANE_DENSE = 1      # grid_contributions skipped interpolation
LANE_SHIFT = 2      # rate's previous point was a shift (0 without rate)


def _grid_tail(spec: PipelineSpec, num_groups: int, wts, v, m, gid):
    """Shared pipeline tail: (rate ->) grouped cross-series aggregation on
    an already-downsampled [S, W] grid.  Also the finish stage of the
    streaming executor (ops.streaming hands it the accumulated grid).
    Like every grouped program here it returns four: the answer's triple
    and `lanes`, the lanes the device took (LANE_DENSE:
    ops/group_agg.py::grid_contributions, LANE_SHIFT: ops/rate.py)."""
    from opentsdb_tpu.ops.group_agg import grid_group_aggregate
    agg = get_agg(spec.aggregator)
    grid = jnp.asarray(wts)
    lanes = jnp.int32(0)
    if spec.rate is not None:
        agg = Aggregator(agg.name, PREV, agg.reduce)
        grid_b = jnp.broadcast_to(grid[None, :], v.shape)
        _, v, m, shift = rate(grid_b, v, m, spec.rate, all_int=False)
        lanes = LANE_SHIFT * shift.astype(jnp.int32)
    wts, out, out_mask, dense = grid_group_aggregate(
        grid, v, m, gid, num_groups, agg, rows_sorted=spec.rows_sorted,
        row_groups=spec.row_groups)
    return wts, out, out_mask, lanes + LANE_DENSE * dense.astype(jnp.int32)


def _downsample_grid(step: DownsampleStep, ts, val, mask, wargs):
    """Downsample only — the block evaluator of the partial-aggregate
    cache (storage/agg_cache.py): per-(series, window) grids computed
    block-by-block, with rate/group/aggregate running later on the
    assembled grid via _grid_tail (they cross block boundaries)."""
    return downsample(ts, val, mask, step.function, step.window_spec,
                      wargs, step.fill_policy, step.fill_value)


# shape: grid_v[S,W] f64, grid_m[S,W] bool, piece_v[S,P] any, piece_m[S,P] bool, at[2] i32
def _place_piece(grid_v, grid_m, piece_v, piece_m, at):
    """Write the first at[1] columns of one [S, pw] piece into the
    [S, Wp] grid from column at[0] on; every other column keeps what it
    held.  Offset and count are traced, so the program compiles once
    per (S, Wp, pw) wherever the piece falls.  dynamic_update_slice
    clamps a window that would run past the grid's edge, which would
    shift the piece: the write goes through the window the clamp gives,
    the piece rolled by the clamp's distance.  Selects and copies only:
    the values keep their bits."""
    s, wp = grid_v.shape
    pw = piece_v.shape[1]
    off, count = at[0], at[1]
    start = jnp.minimum(off, wp - pw)
    shift = off - start
    col = jnp.arange(pw, dtype=at.dtype)
    take = ((col >= shift) & (col < shift + count))[None, :]
    at0 = (jnp.zeros((), at.dtype), start)
    old_v = jax.lax.dynamic_slice(grid_v, at0, (s, pw))
    old_m = jax.lax.dynamic_slice(grid_m, at0, (s, pw))
    new_v = jnp.where(take, jnp.roll(piece_v.astype(grid_v.dtype), shift,
                                     axis=1), old_v)
    new_m = jnp.where(take, jnp.roll(piece_m.astype(bool), shift, axis=1),
                      old_m)
    return (jax.lax.dynamic_update_slice(grid_v, new_v, at0),
            jax.lax.dynamic_update_slice(grid_m, new_m, at0))


def _blank_grid(s: int, wp: int):
    """The grid a rewrite's pieces are placed into: 0 and False."""
    return jnp.zeros((s, wp), jnp.float64), jnp.zeros((s, wp), bool)


def _lane_partials(spec: WindowSpec, ts, val, mask, wargs):
    """Mergeable per-(series, window) partials — the rollup-lane block
    builder (storage/rollup.py): one dispatch computes the sum, count,
    min and max of every cell, the four moments every lane-derivable
    downsample re-reduces from exactly.  Mirrors the segment path of
    ops.downsample.downsample cell-for-cell (same window ids, same
    NaN-skip rule, float64 accumulation), so a lane-derived window is
    bit-identical to the raw kernel's on integer data.  Empty cells
    hold (0, 0, +inf, -inf) — the mergeable identities — and mask
    derives as count > 0 at serve time."""
    s, n = ts.shape
    w = spec.count
    num = s * w + 1
    vf = val.astype(jnp.float64)
    nwin = wargs["nwin"]
    from opentsdb_tpu.ops.downsample import window_ids
    win = window_ids(ts, spec, wargs)
    valid = mask & (win >= 0) & (win < nwin.astype(win.dtype))
    rows = jnp.arange(s, dtype=jnp.int64)[:, None]
    seg = jnp.where(valid, rows * w + jnp.clip(win, 0, w - 1), s * w)
    seg = seg.reshape(-1)
    flat = vf.reshape(-1)
    ok = valid.reshape(-1) & ~jnp.isnan(flat)
    seg = jnp.where(ok, seg, s * w)
    counts = jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                 num_segments=num)[:-1].reshape(s, w)
    sums = jax.ops.segment_sum(jnp.where(ok, flat, 0.0), seg,
                               num_segments=num)[:-1].reshape(s, w)
    mins = jax.ops.segment_min(jnp.where(ok, flat, jnp.inf), seg,
                               num_segments=num)[:-1].reshape(s, w)
    maxs = jax.ops.segment_max(jnp.where(ok, flat, -jnp.inf), seg,
                               num_segments=num)[:-1].reshape(s, w)
    return sums, counts, mins, maxs


def _stacked_group_pipeline(spec: PipelineSpec, num_groups: int, ts, val,
                            mask, gid, wargs):
    """Q compatible grouped queries in ONE stacked [Q, S, N] dispatch.

    The fused multi-query batcher (query/batcher.py) buckets concurrent
    small plans by (static spec, padded shapes) and
    vmaps the SAME _group_pipeline over a leading member axis — each
    member keeps its own gid row map and its own traced window args
    (stacked along axis 0), and inside the vmap the kernels trace on
    the per-member [S, N] shapes, so the mode choosers pick exactly
    what a solo dispatch of the same member would.  Per-member results
    come back batched ([Q, W], [Q, G, W], [Q, G, W], lanes[Q]) for host-side
    unpack; on integer data a member's slice is bitwise what its solo
    dispatch would produce (integer-exact f64 accumulation is
    reassociation-proof — the same contract the rollup lanes pin).
    """
    return jax.vmap(
        lambda t, v, m, g, w: _group_pipeline(spec, num_groups, t, v,
                                              m, g, w))(
        ts, val, mask, gid, wargs)


_jitted_group = jax.jit(_group_pipeline, static_argnums=(0, 1))
_jitted_stacked_group = jax.jit(_stacked_group_pipeline,
                                static_argnums=(0, 1))
_jitted_grid_tail = jax.jit(_grid_tail, static_argnums=(0, 1))
_jitted_downsample_grid = jax.jit(_downsample_grid, static_argnums=0)
_jitted_place_piece = jax.jit(_place_piece)
_jitted_blank_grid = jax.jit(_blank_grid, static_argnums=(0, 1))
_jitted_lane_partials = jax.jit(_lane_partials, static_argnums=0)


def run_grid_tail(spec: PipelineSpec, wts, v, m, gid, num_groups: int):
    """Finish a streamed query: grid [S, W] -> (wts, out[G, W],
    mask[G, W], lanes[])."""
    return _jitted_grid_tail(spec, num_groups, wts, v, m, gid)


# shape: ts[Q,S,N] any, val[Q,S,N] any, mask[Q,S,N] bool, gid[Q,S] any
def run_stacked_group_pipeline(spec: PipelineSpec, ts, val, mask, gid,
                               num_groups: int, wargs: dict):
    """Q stacked grouped pipelines -> (wts[Q, W], out[Q, G, W],
    mask[Q, G, W], lanes[Q]) — the batcher's one-launch form of
    run_group_pipeline; `wargs` values carry a leading member axis.
    Under the vmap the tail's conds are selects (both branches run);
    lanes[q] still says which answers member q got."""
    if spec.downsample is None:
        raise ValueError("grouped pipeline requires a downsample step")
    return _jitted_stacked_group(spec, num_groups, ts, val, mask, gid,
                                 wargs)


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool
def run_downsample_grid(step: DownsampleStep, ts, val, mask, wargs: dict):
    """One downsample-only dispatch -> (wts[W], v[S, W], mask[S, W])."""
    return _jitted_downsample_grid(step, ts, val, mask, wargs)


def assemble_grid(pieces, s: int, wp: int):
    """The [S, Wp] grid of window-contiguous pieces, put together on
    the device: `pieces` is [(v[S, pw], mask[S, pw], count)] in window
    order, each piece's first `count` columns taken (a piece's width is
    its own padded one, never over Wp).  Columns past the pieces hold 0
    and False.  A device array never leaves the device, a host array is
    uploaded as it is.  A dispatch costs the handler a fraction of a
    millisecond on the chip's host, so there are as few as there can be:
    one for the blank grid, one upload of every piece's (offset, count),
    one placement a piece."""
    pieces = list(pieces)
    ats, col = [], 0
    for _pv, _pm, count in pieces:
        ats.append(np.array([col, count], np.int32))
        col += count
    v, m = _jitted_blank_grid(s, wp)
    for (pv, pm, _count), at in zip(pieces, jax.device_put(ats)):
        v, m = _jitted_place_piece(v, m, pv, pm, at)
    return v, m


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool
def run_lane_partials(spec: WindowSpec, ts, val, mask, wargs: dict):
    """One lane-partials dispatch -> (sum[S, W] f64, count[S, W] i32,
    min[S, W] f64, max[S, W] f64) — the rollup-lane block builder."""
    return _jitted_lane_partials(spec, ts, val, mask, wargs)


# shape: ts[S,N] any, val[S,N] any, mask[S,N] bool, gid[S] any
def run_group_pipeline(spec: PipelineSpec, ts, val, mask, gid,
                       num_groups: int, wargs: dict | None = None):
    """Execute the grouped pipeline -> (wts[W], out[G, W], out_mask[G, W],
    lanes[]).

    Requires a downsample step (the shared grid is what makes the segmented
    cross-series reduce possible); union-timestamp queries keep the
    per-group path.
    """
    if spec.downsample is None:
        raise ValueError("grouped pipeline requires a downsample step")
    return _jitted_group(spec, num_groups, ts, val, mask, gid, wargs or {})


def _group_rollup_avg(spec: PipelineSpec, num_groups: int, ts_s, val_s,
                      mask_s, ts_c, val_c, mask_c, gid, wargs):
    """Grouped rollup-avg read: sum/count lane division, then the grid tail."""
    step = spec.downsample
    wts, sums, msum = downsample(ts_s, val_s, mask_s, "sum", step.window_spec,
                                 wargs, FILL_NONE)
    _, cnts, mcnt = downsample(ts_c, val_c, mask_c, "sum", step.window_spec,
                               wargs, FILL_NONE)
    ok = msum & mcnt & (cnts > 0)
    v = jnp.where(ok, sums / jnp.where(ok, cnts, 1.0), jnp.nan)
    nwin = wargs["nwin"]
    live = jnp.arange(v.shape[-1]) < nwin
    v, m = apply_fill(v, ok, live[None, :], step.fill_policy,
                      step.fill_value)
    return _grid_tail(spec, num_groups, wts, v, m, gid)


_jitted_group_rollup_avg = jax.jit(_group_rollup_avg, static_argnums=(0, 1))


def run_group_rollup_avg_pipeline(spec: PipelineSpec, ts_s, val_s, mask_s,
                                  ts_c, val_c, mask_c, gid, num_groups: int,
                                  wargs: dict | None = None):
    """Grouped rollup-avg pipeline -> (wts[W], out[G, W], out_mask[G, W],
    lanes[])."""
    return _jitted_group_rollup_avg(spec, num_groups, ts_s, val_s, mask_s,
                                    ts_c, val_c, mask_c, gid, wargs or {})


# shape: -> ([S,N] i64, [S,N] f64, [S,N] bool, [] bool)
def build_batch_direct(series_list: list, start_ms: int, end_ms: int,
                       fix_duplicates: bool, pad_to_pow2: bool = True):
    """Single-copy batch build: size/type from window_stats, then each
    series copies its window STRAIGHT into its padded row under its own
    lock (Series.window_into) — no intermediate per-series arrays.
    build_batch + window() copies every point twice (25MB of transient
    copies on a 1M-point query, ~30%% of the host-lane query time);
    this is the same output contract (ts[S, N], val[S, N], mask[S, N],
    all_int) in one pass."""
    stats = [s.window_stats(start_ms, end_ms, fix_duplicates)
             for s in series_list]
    s = len(series_list)
    n_max = max((c for c, _ in stats), default=0)
    n = pad_pow2(max(n_max, 1)) if pad_to_pow2 else max(n_max, 1)
    all_int = s > 0 and all(isint for c, isint in stats if c)
    while True:
        ts = np.empty((s, n), dtype=np.int64)
        mask = np.empty((s, n), dtype=bool)
        val = np.empty((s, n), dtype=np.int64 if all_int else np.float64)
        retype = False
        for i, series in enumerate(series_list):
            k, ok_int = series.window_into(start_ms, end_ms,
                                           fix_duplicates, ts[i], val[i],
                                           mask[i], all_int)
            if not ok_int:
                # a float point landed in range between the sizing pass
                # and this row's fill (no snapshot isolation): the int64
                # batch can no longer represent the data — rebuild as
                # float.  At most one retype per build (float accepts
                # everything).
                retype = True
                break
            ts[i, k:] = PAD_TS
            val[i, k:] = 0
            mask[i, k:] = False
        if not retype:
            return ts, val, mask, all_int
        all_int = False


# shape: -> ([S,N] i64, [S,N] f64, [S,N] bool, [] bool)
def build_batch(windows: list, pad_to_pow2: bool = True):
    """Pack per-series (ts, fval, ival, is_int) windows into padded arrays.

    Returns (ts[S, N], val[S, N], mask[S, N], all_int).  When every series is
    integer-typed, `val` is an exact int64 array (Java-long-exact above 2^53);
    otherwise float64.  Padding timestamps are int64 max so rows stay sorted;
    shapes pad to powers of two to bound jit recompiles (SURVEY.md §7 (c)).
    """
    s = len(windows)
    n_max = max((len(w[0]) for w in windows), default=0)
    n = pad_pow2(max(n_max, 1)) if pad_to_pow2 else max(n_max, 1)
    all_int = s > 0
    for w in windows:
        isint = w[3]
        if len(w[0]) and not bool(np.all(isint)):
            all_int = False
            break
    # np.empty + per-row tail fill, not np.full/zeros: a dense batch
    # (the common case — one big series is the whole row) would pay a
    # full-array memset immediately overwritten by the copy
    ts = np.empty((s, n), dtype=np.int64)
    mask = np.empty((s, n), dtype=bool)
    val = np.empty((s, n), dtype=np.int64 if all_int else np.float64)
    for i, (t, fv, iv, isint) in enumerate(windows):
        k = len(t)
        ts[i, :k] = t
        ts[i, k:] = PAD_TS
        val[i, :k] = iv if all_int else fv
        val[i, k:] = 0
        mask[i, :k] = True
        mask[i, k:] = False
    return ts, val, mask, all_int

"""shard_map query kernels: cross-chip downsample + group-by aggregation.

Reference behavior being re-expressed (not translated): the group-by
aggregation fan-out of TsdbQuery.GroupByAndAggregateCB
(/root/reference/src/core/TsdbQuery.java:981-1114) over the salt-bucket
scatter/gather of SaltScanner (/root/reference/src/core/SaltScanner.java:269).
Each HBase salt bucket scanned concurrently becomes a series shard owned by
one chip; the TreeMap merge of per-bucket results becomes XLA collectives:
window moments (count/sum/sumsq/min/max) are computed per chip with segment
reductions, then combined over ICI inside `shard_map`: sums with `psum`,
extremes by gather + local reduce (`_pextreme` — the f64 `pmax`/`pmin`
all-reduce does not lower on TPU).  The time axis is additionally sharded (sequence parallelism)
— window moments are associative over time, so time shards combine with the
same collectives, no halo exchange needed.

The serving path (`sharded_query_pipeline`) runs the full /api/query
numeric pipeline — per-series downsample + rate + interpolation, then the
grouped cross-series reduce — with rows of the [S, N] batch spread over
every chip of the mesh.  Moment-decomposable aggregators combine partial
(count/sum/sumsq/min/max) moments over ICI; order/rank aggregators
(percentiles/median/first/last/mult) gather the already-downsampled [S, W]
grid to every chip and reduce replicated — gather-to-owner with W ≪ N, so
the transfer is the reduced grid, never the raw points.
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from opentsdb_tpu.ops.downsample import (
    WindowSpec, window_ids, window_timestamps)
from opentsdb_tpu.parallel.mesh import AXIS_SERIES, AXIS_TIME

_BOTH = (AXIS_SERIES, AXIS_TIME)


def _pextreme(x, axes, reduce):
    """Cross-chip min/max (`reduce` = jnp.min / jnp.max) of float64
    partials — in place of lax.pmin / lax.pmax, which XLA:TPU refuses for
    64-bit element types ("UNIMPLEMENTED: Supported lowering only of Sum
    all reduce": f64 is an emulated u32 pair there and only the sum
    combiner is lowered; first met by `max:1m-max` on a 2x2 v5e mesh).
    Every chip gathers the partials and reduces them locally: the same
    values in, the same extreme out, replicated like the all-reduce was.
    The partials are already-reduced [G, W] / [S, W] grids, so the
    gather moves n_chips small grids."""
    return reduce(lax.all_gather(x, axes), axis=0)

# Cross-chip aggregators expressible as psum/pmax/pmin-combinable moments.
# Scopes sharded_group_downsample (the offline rollup pass, which only ever
# needs moment lanes); the SERVING path (sharded_query_pipeline below)
# covers every registry aggregator — percentiles/median/first/last/mult run
# via gather-to-owner on the reduced grid.
SHARDED_AGGS = frozenset({
    "sum", "zimsum", "count", "avg", "min", "mimmin", "max", "mimmax",
    "dev", "squareSum"})


def _group_moments(ts, val, mask, gid, num_groups: int, spec: WindowSpec,
                   wargs: dict):
    """Per-chip (count, sum, min, max) over (group, window) cells + helpers.

    Returns (seg, ok_flat, flat_v, count, total) with count/total already
    psum-combined across the mesh; min/max are computed lazily by callers.
    """
    s, n = ts.shape
    w = spec.count
    num = num_groups * w + 1
    nwin = wargs["nwin"]

    win = window_ids(ts, spec, wargs)
    valid = mask & (win >= 0) & (win < nwin.astype(win.dtype))
    vf = val.astype(jnp.float64)
    ok = valid & ~jnp.isnan(vf)
    # int32 segment ids + counts: int64 is an emulated u32 pair on TPU
    from opentsdb_tpu.ops.group_agg import _seg_dtype
    dt = _seg_dtype(num)
    seg = jnp.where(ok, gid[:, None].astype(dt) * w
                    + jnp.clip(win, 0, w - 1).astype(dt),
                    jnp.asarray(num_groups * w, dt))
    seg = seg.reshape(-1)
    ok_flat = ok.reshape(-1)
    flat_v = jnp.where(ok_flat, vf.reshape(-1), 0.0)

    count = jax.ops.segment_sum(ok_flat.astype(jnp.int32), seg,
                                num_segments=num)[:-1].astype(jnp.int64)
    total = jax.ops.segment_sum(flat_v, seg, num_segments=num)[:-1]
    count = lax.psum(count, _BOTH)
    total = lax.psum(total, _BOTH)
    return seg, ok_flat, flat_v, count, total, num


def _finish(agg_name, seg, ok_flat, flat_v, count, total, num,
            num_groups, w):
    """Combine cross-chip moments into the final [G, W] aggregate."""
    g = num_groups
    cnt = count.reshape(g, w)
    tot = total.reshape(g, w)
    safe = jnp.maximum(cnt, 1)

    if agg_name in ("sum", "zimsum"):
        out = tot
    elif agg_name == "count":
        out = cnt.astype(jnp.float64)
    elif agg_name == "avg":
        out = tot / safe
    elif agg_name == "squareSum":
        sq = jax.ops.segment_sum(flat_v * flat_v, seg, num_segments=num)[:-1]
        out = lax.psum(sq, _BOTH).reshape(g, w)
    elif agg_name in ("min", "mimmin"):
        lo = jax.ops.segment_min(jnp.where(ok_flat, flat_v, jnp.inf), seg,
                                 num_segments=num)[:-1]
        out = _pextreme(lo, _BOTH, jnp.min).reshape(g, w)
    elif agg_name in ("max", "mimmax"):
        hi = jax.ops.segment_max(jnp.where(ok_flat, flat_v, -jnp.inf), seg,
                                 num_segments=num)[:-1]
        out = _pextreme(hi, _BOTH, jnp.max).reshape(g, w)
    elif agg_name == "dev":
        # Second pass with the *global* mean (ICI round-trip already paid by
        # the psum of count/total): numerically the two-pass scheme the
        # reference's Welford loop approximates (Aggregators.java:498).
        mean = (tot / safe).reshape(-1)
        mean_pp = mean[jnp.clip(seg, 0, g * w - 1)]
        centered = jnp.where(ok_flat, flat_v - mean_pp, 0.0)
        m2 = jax.ops.segment_sum(centered * centered, seg,
                                 num_segments=num)[:-1]
        m2 = lax.psum(m2, _BOTH).reshape(g, w)
        out = jnp.where(cnt >= 2, jnp.sqrt(m2 / jnp.maximum(cnt - 1, 1)), 0.0)
    else:
        raise KeyError("Aggregator %r has no cross-chip decomposition; "
                       "use the single-device path" % agg_name)
    return out, cnt


@lru_cache(maxsize=128)
def sharded_group_downsample(mesh: Mesh, agg_name: str, spec: WindowSpec,
                             num_groups: int):
    """Build the jitted sharded step: [S,N] batch -> [G,W] group aggregates.

    fn(ts, val, mask, gid, wargs) with ts/val/mask sharded (series, time),
    gid sharded (series,); returns replicated
    (window_ts[W], out[G, W], out_mask[G, W]).

    lru_cached (tsdblint jax-jit-per-call): every call used to build a
    fresh shard_map + jax.jit wrapper, recompiling per invocation.
    """
    if agg_name not in SHARDED_AGGS:
        raise KeyError("Aggregator %r has no cross-chip decomposition"
                       % agg_name)
    w = spec.count

    def step(ts, val, mask, gid, wargs):
        seg, ok_flat, flat_v, count, total, num = _group_moments(
            ts, val, mask, gid, num_groups, spec, wargs)
        out, cnt = _finish(agg_name, seg, ok_flat, flat_v, count, total,
                           num, num_groups, w)
        live = jnp.arange(w, dtype=jnp.int32)[None, :] \
            < wargs["nwin"].astype(jnp.int32)
        out_mask = (cnt > 0) & live
        out = jnp.where(out_mask, out, jnp.nan)
        wts = window_timestamps(spec, wargs)
        return wts, out, out_mask

    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(AXIS_SERIES, AXIS_TIME), P(AXIS_SERIES, AXIS_TIME),
                  P(AXIS_SERIES, AXIS_TIME), P(AXIS_SERIES), P()),
        out_specs=(P(), P(), P()),
        check_vma=False)
    return jax.jit(mapped)


@lru_cache(maxsize=32)
def sharded_rollup(mesh: Mesh, spec: WindowSpec):
    """Build the sharded offline rollup pass (BASELINE config 5).

    lru_cached (tsdblint jax-jit-per-call): the rollup job calls this
    per run, and an uncached builder meant a full recompile per pass.

    fn(ts, val, mask, wargs) -> per-series (window_ts[W], sum[S,W],
    count[S,W], min[S,W], max[S,W]) with the series axis still sharded on
    the way out (out_specs keep P(series)) — each chip materializes the
    rollup rows for the series it owns, the write-path analog of
    TSDB.addAggregatePoint (/root/reference/src/core/TSDB.java:1359-1457)
    batched over every interval at once.  Time shards combine with psum /
    _pextreme over the time axis only.
    """
    w = spec.count

    def step(ts, val, mask, wargs):
        s, n = ts.shape
        num = s * w + 1
        nwin = wargs["nwin"]
        win = window_ids(ts, spec, wargs)
        valid = mask & (win >= 0) & (win < nwin.astype(win.dtype))
        vf = val.astype(jnp.float64)
        ok = valid & ~jnp.isnan(vf)
        from opentsdb_tpu.ops.group_agg import _seg_dtype
        dt = _seg_dtype(num)
        rows = jnp.arange(s, dtype=dt)[:, None]
        seg = jnp.where(ok, rows * w + jnp.clip(win, 0, w - 1).astype(dt),
                        jnp.asarray(s * w, dt)).reshape(-1)
        okf = ok.reshape(-1)
        flat = jnp.where(okf, vf.reshape(-1), 0.0)

        cnt = jax.ops.segment_sum(okf.astype(jnp.int32), seg,
                                  num_segments=num)[:-1].astype(jnp.int64)
        tot = jax.ops.segment_sum(flat, seg, num_segments=num)[:-1]
        lo = jax.ops.segment_min(jnp.where(okf, flat, jnp.inf), seg,
                                 num_segments=num)[:-1]
        hi = jax.ops.segment_max(jnp.where(okf, flat, -jnp.inf), seg,
                                 num_segments=num)[:-1]
        cnt = lax.psum(cnt, AXIS_TIME).reshape(s, w)
        tot = lax.psum(tot, AXIS_TIME).reshape(s, w)
        lo = _pextreme(lo, AXIS_TIME, jnp.min).reshape(s, w)
        hi = _pextreme(hi, AXIS_TIME, jnp.max).reshape(s, w)
        wts = window_timestamps(spec, wargs)
        return wts, tot, cnt, lo, hi

    mapped = shard_map(
        step, mesh=mesh,
        in_specs=(P(AXIS_SERIES, AXIS_TIME), P(AXIS_SERIES, AXIS_TIME),
                  P(AXIS_SERIES, AXIS_TIME), P()),
        out_specs=(P(), P(AXIS_SERIES), P(AXIS_SERIES), P(AXIS_SERIES),
                   P(AXIS_SERIES)),
        check_vma=False)
    return jax.jit(mapped)


def _local_grid_tail(spec, num_groups: int, wts, v, m, gid):
    """Collective-aware pipeline tail for code running INSIDE shard_map:
    (rate ->) grouped cross-series aggregation on a row-sharded [S, W] grid.

    The mesh analog of ops.pipeline._grid_tail: moment-decomposable
    aggregators combine per-chip partial moments with psum/_pextreme;
    order/rank aggregators all-gather the reduced grid (gather-to-owner,
    W ≪ N) and reduce replicated.  Shared by the materialized serving path
    (sharded_query_pipeline) and the streamed finish (sharded stream
    accumulator) so both answer identically.
    """
    from opentsdb_tpu.ops.aggregators import Aggregator, get_agg, PREV
    from opentsdb_tpu.ops.group_agg import (
        _EXTREME_AGGS, grid_contributions, group_presence, is_moment_agg,
        moment_group_reduce, ordered_group_reduce)
    from opentsdb_tpu.ops.pipeline import LANE_DENSE, LANE_SHIFT
    from opentsdb_tpu.ops.rate import rate

    g = num_groups
    agg = get_agg(spec.aggregator)
    grid = jnp.asarray(wts)
    shift = jnp.bool_(False)
    if spec.rate is not None:
        agg = Aggregator(agg.name, PREV, agg.reduce)
        grid_b = jnp.broadcast_to(grid[None, :], v.shape)
        _, v, m, shift = rate(grid_b, v, m, spec.rate, all_int=False)
    vf = v.astype(jnp.float64)
    contrib, participate, dense = grid_contributions(grid, vf, m, agg)
    # each shard took the lanes its own rows allow; the answer's lane is
    # the cheaper one iff every shard's was (one psum for both)
    missed = lax.psum(jnp.stack([~dense, ~shift]).astype(jnp.int32), _BOTH)
    lanes = LANE_DENSE * (missed[0] == 0).astype(jnp.int32)
    if spec.rate is not None:
        lanes = lanes + LANE_SHIFT * (missed[1] == 0).astype(jnp.int32)
    if is_moment_agg(agg.name):
        out, _ = moment_group_reduce(
            agg.name, contrib, participate, gid, g,
            combine_sum=lambda x: lax.psum(x, _BOTH),
            combine_min=lambda x: _pextreme(x, _BOTH, jnp.min),
            combine_max=lambda x: _pextreme(x, _BOTH, jnp.max),
            # contiguous row sharding + end-padding preserve the
            # planner's non-decreasing gid on every shard
            rows_sorted=spec.rows_sorted)
    else:
        # Gather-to-owner on the reduced grid: every chip receives all
        # rows (global row order preserved — first/last follow series
        # order) and reduces replicated.
        c_all = lax.all_gather(contrib, _BOTH, axis=0, tiled=True)
        p_all = lax.all_gather(participate, _BOTH, axis=0, tiled=True)
        g_all = lax.all_gather(gid, _BOTH, axis=0, tiled=True)
        out, _ = ordered_group_reduce(agg.name, c_all, p_all, g_all, g)
    present = group_presence(m, gid, g, extremes=agg.name in _EXTREME_AGGS,
                             rows_sorted=spec.rows_sorted)
    out_mask = lax.psum(present.astype(jnp.int32), _BOTH) > 0
    return wts, out, out_mask, lanes


@lru_cache(maxsize=128)
def sharded_query_pipeline(mesh: Mesh, spec, num_groups: int):
    """Build the jitted mesh-serving step for one /api/query pipeline.

    fn(ts, val, mask, gid, wargs) with rows sharded over every chip
    (dim 0 split across both mesh axes, time dim intact so downsample/rate
    stay row-local); returns replicated (wts[W], out[G, W], out_mask[G, W],
    lanes[]) identical to ops.pipeline.run_group_pipeline's single-device
    answer (a lane bit is set iff every shard's rows took that lane).

    `spec` is a PipelineSpec (hashable) — the builder is lru_cached so a
    dashboard re-issuing the same query shape reuses the compiled program.
    """
    from opentsdb_tpu.ops.downsample import downsample

    step = spec.downsample

    def local(ts, val, mask, gid, wargs):
        wts, v, m = downsample(ts, val, mask, step.function, step.window_spec,
                               wargs, step.fill_policy, step.fill_value)
        return _local_grid_tail(spec, num_groups, wts, v, m, gid)

    mapped = shard_map(
        local, mesh=mesh,
        in_specs=(P(_BOTH, None), P(_BOTH, None), P(_BOTH, None), P(_BOTH),
                  P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)
    return jax.jit(mapped)


def n_devices(mesh: Mesh) -> int:
    """Total chips in the query mesh (single definition — padding widths
    derived from it must agree between the streamed and materialized
    paths)."""
    return mesh.shape[AXIS_SERIES] * mesh.shape[AXIS_TIME]


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool
def _pad_rows(s_pad: int, ts: np.ndarray, val: np.ndarray, mask: np.ndarray,
              gid: np.ndarray | None = None, pad_gid_value: int = 0):
    """Pad the series axis to `s_pad` with inert rows.

    The pad values are load-bearing: pad-sentinel timestamps keep rows
    sorted (I64_MAX, or the int32 clip ceiling for pre-compacted ts_base
    batches — the device-cache gather's pad value), mask False keeps
    points out of every window, and `pad_gid_value` must
    be an OUT-OF-RANGE group id (pass num_groups) — mask False alone is not
    enough, because fill policies other than "none" expose every live
    window after downsample, so a phantom row with a real gid would
    participate in count/avg.  JAX segment ops drop out-of-range ids.
    """
    s, n = ts.shape
    if s_pad == s:
        return ts, val, mask, gid
    from opentsdb_tpu.storage.device_cache import I32_PAD_TS
    sentinel = I32_PAD_TS if ts.dtype == np.int32 \
        else np.iinfo(np.int64).max
    pad_ts = np.full((s_pad, n), sentinel, ts.dtype)
    pad_val = np.zeros((s_pad, n), val.dtype)
    pad_mask = np.zeros((s_pad, n), bool)
    pad_ts[:s] = ts
    pad_val[:s] = val
    pad_mask[:s] = mask
    out_gid = None
    if gid is not None:
        out_gid = np.full(s_pad, pad_gid_value, gid.dtype)
        out_gid[:s] = gid
    return pad_ts, pad_val, pad_mask, out_gid


def padded_rows(mesh: Mesh, s: int) -> int:
    """Sharded row count: series padded up to a multiple of the mesh's
    device count (one source of truth for accumulator state and the
    planner's chunk-packing width)."""
    n_dev = n_devices(mesh)
    return -(-s // n_dev) * n_dev


def _leaf_spec(key: str):
    """shard_map spec per accumulator-state leaf: grids shard rows over
    the mesh; the 0-d oob audit counter stays replicated."""
    return P() if key == "oob" else P(_BOTH, None)


@lru_cache(maxsize=64)
def _stream_update_fn(mesh: Mesh, window_spec, state_keys=None):
    """Jitted shard_map'd accumulator fold: row-local, zero collectives.

    Each chip folds its own [S_local, n] chunk rows into its own
    [S_local, W] moment state — the SaltScanner concurrent-bucket scan
    (/root/reference/src/core/SaltScanner.java:269) with buckets = chips
    and the TreeMap merge deferred to finish().
    """
    from opentsdb_tpu.ops import streaming

    def upd(state, ts, val, mask, wargs):
        return streaming._update(window_spec, state, ts, val, mask, wargs)

    # state_keys is passed when the accumulator carries the 0-d "oob"
    # audit leaf (slice-enabled accumulators whose overflow chunks fall
    # back to this full fold): per-leaf specs keep the scalar replicated
    # while the grids shard
    state_specs = P(_BOTH, None) if state_keys is None else {
        k: _leaf_spec(k) for k in state_keys}
    mapped = shard_map(
        upd, mesh=mesh,
        in_specs=(state_specs, P(_BOTH, None), P(_BOTH, None),
                  P(_BOTH, None), P()),
        out_specs=state_specs,
        check_vma=False)
    # Donate the state (arg 0) for the same reason as streaming's
    # _jitted_update: the sharded grid can reach GBs per chip and the
    # caller replaces its reference at enqueue.
    return jax.jit(mapped, donate_argnums=0)


@lru_cache(maxsize=64)
def _stream_update_sliced_fn(mesh: Mesh, window_spec, wc: int,
                             state_keys: frozenset):
    """Sharded window-sliced fold (see streaming._update_sliced): each
    chip merges its row shard's chunk moments into the [w0, w0+wc) slice
    of its own [S_local, W] state — per-chunk cost O(S_local*wc), not
    O(S_local*W).  w0 is replicated; the 0-d oob audit counter psums
    over the mesh so it stays replicated."""
    from opentsdb_tpu.ops import streaming

    def upd(state, ts, val, mask, wargs, w0):
        prev_oob = state["oob"]
        new = streaming._update_sliced(window_spec, wc, state, ts, val,
                                       mask, wargs, w0)
        new["oob"] = prev_oob + lax.psum(new["oob"] - prev_oob, _BOTH)
        return new

    state_specs = {k: _leaf_spec(k) for k in state_keys}
    mapped = shard_map(
        upd, mesh=mesh,
        in_specs=(state_specs, P(_BOTH, None), P(_BOTH, None),
                  P(_BOTH, None), P(), P()),
        out_specs=state_specs,
        check_vma=False)
    return jax.jit(mapped, donate_argnums=0)


@lru_cache(maxsize=64)
def _stream_finish_fn(mesh: Mesh, window_spec, pipeline_spec,
                      num_groups: int):
    """Jitted shard_map'd stream finish: per-chip moment state -> replicated
    (wts[W], out[G, W], out_mask[G, W], lanes[]) via the collective grid
    tail."""
    from opentsdb_tpu.ops import streaming

    step = pipeline_spec.downsample

    def fin(state, gid, wargs):
        wts, v, m = streaming._finish(
            window_spec, step.function, step.fill_policy, state, wargs,
            step.fill_value)
        return _local_grid_tail(pipeline_spec, num_groups, wts, v, m, gid)

    mapped = shard_map(
        fin, mesh=mesh,
        in_specs=(P(_BOTH, None), P(_BOTH), P()),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)
    return jax.jit(mapped)


class ShardedStreamAccumulator:
    """Mesh-sharded streaming state: beyond-memory queries on ALL chips.

    Composes the two scale axes the reference's scan layer composes —
    concurrent salt-bucket scanners (SaltScanner.java:269) × incremental
    per-batch callbacks (:463-740).  Series rows are sharded over every
    chip of the mesh; each host chunk is device_put row-sharded and folded
    into per-chip [S_local, W] moments (associative, collective-free); the
    finish runs the sharded grid tail (psum/_pextreme for moment
    aggregators, gather-to-owner for order/rank) so the answer matches the
    single-device StreamAccumulator + run_grid_tail bit-for-bit up to
    psum reassociation.

    HBM per chip is O(S/n_chips * W + chunk), independent of total points.
    """

    def __init__(self, mesh: Mesh, num_series: int, window_spec, wargs,
                 sketch: bool = False, lanes: frozenset | None = None,
                 window_slice: int | None = None):
        from opentsdb_tpu.ops import streaming

        self.mesh = mesh
        self.window_spec = window_spec
        self.wargs = wargs
        self.num_series = num_series
        self.s_pad = padded_rows(mesh, num_series)
        self._row_sh = NamedSharding(mesh, P(_BOTH, None))
        self._rep_sh = NamedSharding(mesh, P())
        self._gid_sh = NamedSharding(mesh, P(_BOTH))
        self.window_slice = streaming.quantize_window_slice(window_slice,
                                                            window_spec)
        state = streaming._zero_state(self.s_pad, window_spec.count,
                                      sketch, lanes,
                                      with_oob=self.window_slice
                                      is not None)
        self.state = {k: jax.device_put(
            v, self._rep_sh if _leaf_spec(k) == P() else self._row_sh)
            for k, v in state.items()}
        keys = (frozenset(state) if self.window_slice is not None
                else None)
        self._update = _stream_update_fn(mesh, window_spec, keys)
        self._update_sliced = None
        if self.window_slice is not None:
            self._update_sliced = _stream_update_sliced_fn(
                mesh, window_spec, self.window_slice, keys)

    def update(self, ts: np.ndarray, val: np.ndarray,
               mask: np.ndarray, w0: int | None = None) -> tuple:
        """Fold one [num_series, n] host chunk (async — returns at enqueue)
        and hand back the three row-sharded device arrays uploaded for it
        (see StreamAccumulator.update).

        Rows are padded to the sharded row count (callers may pack chunks
        at `s_pad` rows directly to skip the copy); padding rows carry
        mask False so their moment state stays zero (n=0), which the
        finish's participate logic excludes (pad gid is out-of-range too).

        `w0` (with a window_slice-enabled accumulator) routes to the
        sliced fold — each chip merges an O(S_local * wc) state slice
        instead of its whole [S_local, W] grid; see
        StreamAccumulator.update for the contract.
        """
        ts, val, mask, _ = _pad_rows(self.s_pad, ts, val, mask)
        d_ts, d_val, d_mask = (jax.device_put(x, self._row_sh)
                               for x in (ts, val, mask))
        if w0 is not None and self._update_sliced is not None:
            self.state = self._update_sliced(self.state, d_ts, d_val,
                                             d_mask, self.wargs,
                                             jnp.asarray(w0, jnp.int64))
        else:
            self.state = self._update(self.state, d_ts, d_val, d_mask,
                                      self.wargs)
        return d_ts, d_val, d_mask

    def oob_count(self) -> int:
        """Valid points sliced folds missed (w0 contract violations);
        0 in correct use.  Host sync."""
        if "oob" not in self.state:
            return 0
        return int(np.asarray(self.state["oob"]))

    def finish_tail(self, pipeline_spec, gid: np.ndarray, num_groups: int):
        """Replicated (wts[W], out[G, W], out_mask[G, W], lanes[]) for the
        query."""
        fn = _stream_finish_fn(self.mesh, self.window_spec, pipeline_spec,
                               num_groups)
        pad_gid = np.full(self.s_pad, num_groups, np.int64)
        pad_gid[:self.num_series] = gid
        d_gid = jax.device_put(pad_gid, self._gid_sh)
        # the finish fn's state spec is rank-2 per leaf; the 0-d oob
        # audit counter is not part of the grid finish
        state = {k: v for k, v in self.state.items() if k != "oob"}
        return fn(state, d_gid, self.wargs)


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool, gid[S] any
def shard_rows(mesh: Mesh, ts: np.ndarray, val: np.ndarray, mask: np.ndarray,
               gid: np.ndarray, pad_gid_value: int):
    """Pad the series axis to device-count multiple and device_put row-sharded.

    The serving-path layout: dim 0 split over both mesh axes (each chip owns
    a block of whole rows), time dim intact.  Padding rows get mask False
    AND `pad_gid_value` — REQUIRED, pass num_groups: an out-of-range group
    id, whose segments JAX scatter drops.  mask False alone is NOT enough —
    fill policies other than "none" expose every live window after
    downsample, so a phantom row with an in-range gid would participate in
    count/avg (the r3 phantom-row bug).
    """
    s, n = ts.shape
    s_pad = padded_rows(mesh, s)
    ts, val, mask, gid = _pad_rows(s_pad, ts, val, mask, gid, pad_gid_value)
    return _put_row_sharded(mesh, ts, val, mask, gid)


def _put_row_sharded(mesh: Mesh, ts, val, mask, gid):
    """The shared layout tail: dim 0 over both mesh axes, time intact."""
    row_sh = NamedSharding(mesh, P(_BOTH, None))
    gid_sh = NamedSharding(mesh, P(_BOTH))
    return (jax.device_put(ts, row_sh), jax.device_put(val, row_sh),
            jax.device_put(mask, row_sh), jax.device_put(gid, gid_sh))


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool, gid[S] any
def shard_rows_device(mesh: Mesh, ts, val, mask, gid: np.ndarray,
                      pad_gid_value: int):
    """shard_rows for an already-device-resident batch (device-cache hit).

    Row padding happens ON DEVICE (tiny concats, same load-bearing pad
    rule as _pad_rows) and the device_put re-lays the single-device
    arrays out across the mesh — an ICI scatter on real hardware instead
    of a fresh host upload.  gid is host-side (the planner builds it per
    query) and pads exactly like shard_rows.
    """
    s, n = ts.shape
    s_pad = padded_rows(mesh, s)
    if s_pad != s:
        # pure pad ROWS from _pad_rows (empty data in, pads out), then
        # concatenated on device: one definition of the phantom-row rule
        # serves both layouts (incl. the int32 ts_base pad sentinel)
        pad_ts, pad_val, pad_mask, pad_gid = _pad_rows(
            s_pad - s, np.empty((0, n), np.dtype(str(ts.dtype))),
            np.empty((0, n), np.dtype(str(val.dtype))),
            np.empty((0, n), bool),
            np.empty(0, gid.dtype), pad_gid_value)
        ts = jnp.concatenate([ts, jnp.asarray(pad_ts)])
        val = jnp.concatenate([val, jnp.asarray(pad_val)])
        mask = jnp.concatenate([mask, jnp.asarray(pad_mask)])
        gid = np.concatenate([gid, pad_gid])
    return _put_row_sharded(mesh, ts, val, mask, gid)


def shard_series(mesh: Mesh, ts: np.ndarray, val: np.ndarray,
                 mask: np.ndarray, gid: np.ndarray):
    """Pad a host batch to mesh-divisible shape and device_put with shardings.

    Pads S up to a multiple of the series-axis size and N to the time-axis
    size (padding rows have mask False / group 0), then places each array
    with its NamedSharding so the jitted shard_map consumes it zero-copy.
    """
    n_s = mesh.shape[AXIS_SERIES]
    n_t = mesh.shape[AXIS_TIME]
    s, n = ts.shape
    s_pad = -(-s // n_s) * n_s
    n_pad = -(-n // n_t) * n_t
    if (s_pad, n_pad) != (s, n):
        pad_ts = np.full((s_pad, n_pad), np.iinfo(np.int64).max, np.int64)
        pad_val = np.zeros((s_pad, n_pad), val.dtype)
        pad_mask = np.zeros((s_pad, n_pad), bool)
        pad_gid = np.zeros(s_pad, gid.dtype)
        pad_ts[:s, :n] = ts
        pad_val[:s, :n] = val
        pad_mask[:s, :n] = mask
        pad_gid[:s] = gid
        ts, val, mask, gid = pad_ts, pad_val, pad_mask, pad_gid
    data_sh = NamedSharding(mesh, P(AXIS_SERIES, AXIS_TIME))
    gid_sh = NamedSharding(mesh, P(AXIS_SERIES))
    return (jax.device_put(ts, data_sh), jax.device_put(val, data_sh),
            jax.device_put(mask, data_sh), jax.device_put(gid, gid_sh))

"""Out-of-core tiled execution: series-tiled streaming past the HBM wall.

ROADMAP item 4.  The streaming executor (ops/streaming.py) already
bounds the POINT axis — chunks fold into a device-resident [S, W]
moment grid — but the grid itself is the remaining wall: a months-long
range at a fine interval times a high-cardinality group-by exceeds
``tsd.query.streaming.state_mb`` and the planner used to refuse it with
a 413 at three duplicated sites.  This module executes those plans
instead, in the spilled-window-aggregation stance (arXiv:2007.10385):

  1. **Series tiling.**  The series axis splits into costmodel-sized
     tiles; each tile's [S_tile, W] accumulator fits the device budget
     by construction.  Every tile streams its time-chunks through the
     existing ``StreamAccumulator`` — same kernels, same merges, same
     double-buffering (the host packs chunk k+1 while the device
     reduces chunk k; JAX async dispatch).  When the device series
     cache holds the metric's columns pinned, a tile whose padded
     batch fits serves in one on-device gather instead of chunking.

  2. **Row-local finish, then spill.**  Rate and per-series grid
     contributions (the interpolation + participation step of
     AggregationIterator's missing-point substitution) are ROW-LOCAL
     (`ops.group_agg.grid_contributions` docstring) — each tile holds
     complete rows, so both run per tile on the full-width grid with
     no cross-tile carries.  The finished per-tile (contrib,
     participate, actual-mask) grids spill to the bounded pool
     (storage/spill.py), pre-split into window stripes so the
     assembly pass reads ~its own bytes per stripe.

  3. **Window-striped tail replay.**  The remaining stage — the
     per-(group, window) cross-series reduce — is WINDOW-LOCAL, so the
     shared ``run_grid_tail`` (rate already applied; spec replayed with
     ``rate=None``) runs over [S_total, stripe] column bands: the full
     [S_total, W] grid never materializes anywhere, host or device.
     Replaying contributions through ``grid_contributions`` is exact:
     participation regions are contiguous per row, so the recomputation
     is the identity on every participating cell (and takes that
     function's dense lane: no row of a stripe has an interior hole),
     and group-by reduction over a stripe equals the same reduction
     over the full grid restricted to those columns (associative per
     cell).  The
     out-mask comes from the spilled ACTUAL mask (a cell is present
     only where a member holds a real value, not an interpolated one —
     the same rule the resident tail applies).

The tiled-vs-refuse decision and its price come from the
costmodel: ``costmodel.features_tiled`` / ``predict_tiled`` stay a dot
product against ``COST_TERMS`` (spill write/read MB, per-tile dispatch
overhead) per the linearity contract, `tsd/admission.py` prices the
tiled plan with the same vector instead of shedding it, and every
tiled pipeline span carries a ``tiling`` annotation (tile count, spill
bytes).  A tiled pipeline span carries no ``costmodel`` decisions, like
a partial-aggregate rewrite's: they describe the monolithic program,
which a tiled execution does not run (pinned by tests/test_tiling.py).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, replace

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.ops.downsample import pad_pow2
from opentsdb_tpu.ops.pipeline import run_grid_tail
from opentsdb_tpu.ops.streaming import StreamAccumulator
from opentsdb_tpu.storage.chunk_pack import ChunkPacker

# Per-cell byte weights for plan sizing.  Spill entries hold contrib
# (f64) + participate (bool) + actual mask (bool) per (series, window)
# cell; the tile's device working set holds the accumulator state plus
# the finished/contribution grids; an assembled stripe holds the three
# spill lanes for every series plus the [G, stripe] output.
SPILL_CELL_BYTES = 10
TILE_WORK_CELL_BYTES = 26
STRIPE_CELL_BYTES = 24


@dataclass(frozen=True)
class TilePlan:
    """A sized tiled execution: how the series/window axes split."""
    tile_rows: int       # series per tile (last tile may be smaller)
    n_tiles: int
    stripe_w: int        # windows per assembly stripe
    n_stripes: int
    spill_bytes: int     # total partial-grid bytes through the pool
    dispatches: int      # extra launches a tiled plan issues
    predicted_s: float   # tiled OVERHEAD prediction (costmodel)


def size_tiles(s: int, w: int, budget_bytes: int, acc_cell_bytes: int,
               g_pad: int, max_tiles: int,
               chunks_per_tile: int = 1) -> TilePlan | None:
    """Pure sizing: split [s, w] so every device-resident piece fits
    ``budget_bytes``.  None when no split can (a single row's [1, w]
    state, or a single-window stripe over all series, still busts the
    budget — the genuine refusal case)."""
    if s < 1 or w < 1 or budget_bytes <= 0:
        return None
    per_row = w * max(acc_cell_bytes, TILE_WORK_CELL_BYTES)
    tile_rows = budget_bytes // per_row
    if tile_rows < 1:
        return None
    tile_rows = min(int(tile_rows), s)
    n_tiles = -(-s // tile_rows)
    if max_tiles > 0 and n_tiles > max_tiles:
        return None
    stripe_w = budget_bytes // ((s + g_pad) * STRIPE_CELL_BYTES)
    if stripe_w < 1:
        return None
    if stripe_w >= w:
        stripe_w = w
    else:
        # pow2 stripe widths: one compiled tail shape per plan family
        stripe_w = 1 << max(int(stripe_w).bit_length() - 1, 0)
    n_stripes = -(-w // stripe_w)
    spill_bytes = s * w * SPILL_CELL_BYTES
    # launches beyond what a resident plan issues: per-tile chunk folds
    # + finish/contrib, per-stripe tail + presence
    dispatches = n_tiles * (chunks_per_tile + 2) + 2 * n_stripes
    return TilePlan(tile_rows, n_tiles, stripe_w, n_stripes, spill_bytes,
                    dispatches, 0.0)


def count_refusal(reason: str) -> None:
    """One over-budget plan the tiled path could not serve (still a
    413), counted by reason for the operator dashboard."""
    from opentsdb_tpu.obs.registry import REGISTRY
    REGISTRY.counter(
        "tsd.query.spill.refusals",
        "Over-budget plans the tiled path could not serve (still "
        "413), by reason").labels(reason=reason).inc()


# effects: observe-gated(observe)
def plan_tiled(tsdb, *, s: int, w: int, g_pad: int, acc_cell_bytes: int,
               total_points: int, platform: str,
               state_mb: int | None = None,
               observe: bool = True) -> TilePlan | None:
    """Size and price a tiled execution for an over-budget [s, w] plan.

    Returns None (with the refusal reason counted under
    ``tsd.query.spill.refusals``) when the pool is disabled, the spill
    bytes exceed the pool's combined budgets, or no tile split fits the
    device budget.  ``observe=False`` (the explain engine's dry-run)
    suppresses the refusal counters; ``state_mb`` overrides the
    configured device budget for what-if sizing."""
    from opentsdb_tpu.ops import costmodel as cm

    refuse = count_refusal if observe else (lambda reason: None)
    pool = getattr(tsdb, "spill_pool", None)
    if pool is None:
        refuse("disabled")
        return None
    if state_mb is None:
        state_mb = tsdb.config.get_int("tsd.query.streaming.state_mb")
    budget_bytes = state_mb * 2**20
    chunk_points = max(tsdb.config.get_int(
        "tsd.query.streaming.chunk_points"), 1)
    max_tiles = tsdb.config.get_int("tsd.query.spill.max_tiles")
    chunks_per_tile = max(int(math.ceil(total_points
                                        / max(chunk_points, 1))), 1)
    plan = size_tiles(s, w, budget_bytes, acc_cell_bytes, g_pad,
                      max_tiles, chunks_per_tile)
    if plan is None:
        refuse("no_fit")
        return None
    # one stripe-entry of slack: demotion is per-entry, so up to one
    # entry of disk headroom can go unusable at the boundary — a plan
    # admitted here must never die mid-query with a capacity error
    entry_bytes = plan.tile_rows * plan.stripe_w * SPILL_CELL_BYTES
    if plan.spill_bytes + entry_bytes \
            > pool.host_budget + pool.disk_budget:
        refuse("pool_budget")
        return None
    predicted = cm.predict_tiled(s, w, g_pad, plan.n_tiles,
                                 plan.n_stripes, plan.spill_bytes,
                                 plan.dispatches, platform)
    return replace(plan, predicted_s=predicted)


# --------------------------------------------------------------------- #
# Per-tile finish kernels                                                #
# --------------------------------------------------------------------- #

def _tile_contrib(spec, wts, v, m):
    """Row-local tail prefix on one tile's finished [S_tile, W] grid:
    rate (when the spec has one), then the per-series contribution +
    participation grids the cross-series reduce consumes.  Exactly the
    computation ``pipeline._grid_tail`` performs before its group
    reduce, so a striped replay of the remainder reproduces the
    resident tail."""
    from opentsdb_tpu.ops.aggregators import PREV, Aggregator, get_agg
    from opentsdb_tpu.ops.group_agg import grid_contributions
    from opentsdb_tpu.ops.rate import rate

    agg = get_agg(spec.aggregator)
    grid = jnp.asarray(wts)
    if spec.rate is not None:
        agg = Aggregator(agg.name, PREV, agg.reduce)
        grid_b = jnp.broadcast_to(grid[None, :], v.shape)
        _, v, m, _ = rate(grid_b, v, m, spec.rate, all_int=False)
    contrib, participate, _dense = grid_contributions(
        grid, v.astype(jnp.float64), m, agg)
    return contrib, participate, m


def _group_presence(num_groups: int, mask, gid):
    """[S, W] actual-value mask + gid[S] -> [G, W] any-member-present —
    the resident tail's out-mask rule, window-local."""
    from opentsdb_tpu.ops.group_agg import group_presence
    return group_presence(mask, gid, num_groups)


_jitted_tile_contrib = jax.jit(_tile_contrib, static_argnums=0)
_jitted_presence = jax.jit(_group_presence, static_argnums=0)


# Cross-series aggregators whose group reduce folds tile-by-tile into
# [G, W] partial moments (sum/count for the additive family, min/max
# for the extremes) — the same partial-moment decomposition
# moment_group_reduce's combine_* hooks use across mesh shards.
# Everything else (dev's two-pass, rank/order aggs) needs all rows at
# once and keeps the spill-pool stripe replay.
LANE_FOLDABLE = frozenset({"sum", "zimsum", "count", "avg",
                           "min", "mimmin", "max", "mimmax"})


def _lane_fold(spec, num_groups: int, extreme: bool, wts, v, m, gid):
    """One tile's [G, W] partial group moments from its finished grid.

    Runs the SAME row-local contribution step as the stripe replay
    (_tile_contrib: rate + interpolation/participation), then reduces
    this tile's rows straight to per-(group, window) partials — sum +
    count (additive) or min/max + count (extremes) plus the
    actual-value presence the out-mask derives from.  Partials merge
    across tiles by +/min/max/| and one host-side finish reproduces
    moment_group_reduce's arithmetic on identical operands, so the
    fold is exact (bitwise on integer data) while the full [S, W]
    grid never exists on the device."""
    from opentsdb_tpu.ops.group_agg import _seg_dtype
    contrib, participate, actual = _tile_contrib(spec, wts, v, m)
    s, w = contrib.shape
    num = num_groups * w
    dt = _seg_dtype(num + w)
    cols = jnp.arange(w, dtype=dt)[None, :]
    seg = (gid.astype(dt)[:, None] * w + cols).reshape(-1)
    vf = contrib.astype(jnp.float64)
    flat = vf.reshape(-1)
    ok2 = (participate & ~jnp.isnan(vf)).reshape(-1)
    cnt = jax.ops.segment_sum(ok2.astype(jnp.int32), seg,
                              num_segments=num).reshape(num_groups, w)
    present = jax.ops.segment_sum(
        actual.reshape(-1).astype(jnp.int32), seg,
        num_segments=num).reshape(num_groups, w)
    if extreme:
        lo = jax.ops.segment_min(jnp.where(ok2, flat, jnp.inf), seg,
                                 num_segments=num
                                 ).reshape(num_groups, w)
        hi = jax.ops.segment_max(jnp.where(ok2, flat, -jnp.inf), seg,
                                 num_segments=num
                                 ).reshape(num_groups, w)
        return lo, hi, cnt, present
    tot = jax.ops.segment_sum(jnp.where(ok2, flat, 0.0), seg,
                              num_segments=num).reshape(num_groups, w)
    return tot, cnt, present


_jitted_lane_fold = jax.jit(_lane_fold, static_argnums=(0, 1, 2))


def run_lane_fold(spec, num_groups: int, extreme: bool, wts, v, m,
                  gid_tile):
    """One tile's partial group moments (see _lane_fold)."""
    return _jitted_lane_fold(spec, num_groups, extreme, wts, v, m,
                             gid_tile)


# --------------------------------------------------------------------- #
# Executor                                                               #
# --------------------------------------------------------------------- #

def _stream_tile(tsdb, seg, tile_series, window_spec, wargs, lanes,
                 sketch: bool, fix: bool, store,
                 ds_function: str, fill_policy: str,
                 fill_value: float) -> tuple:
    """One tile's finished (wts, values, mask) downsample grid.

    Device-cache fast path first: a metric pinned in HBM whose padded
    [S_tile, N] batch fits the cache's batch budget serves in one
    on-device gather.  Otherwise the chunked streaming loop — chunks
    filled by the same packer as the resident streamed path's
    (storage/chunk_pack.py: bulk copies, the timestamp cursor for rows
    that moved mid-scan), one [S_tile, n_chunk] compile, async overlap,
    the same sliced-update sizing."""
    from opentsdb_tpu.ops.pipeline import run_downsample_grid

    s = len(tile_series)
    if tsdb.device_cache is not None and store is not None:
        batch = tsdb.device_cache.batch_for(
            store, tile_series[0].key.metric, tile_series,
            seg.start_ms, seg.end_ms, fix, build=False)
        if batch is not None:
            from opentsdb_tpu.ops.pipeline import DownsampleStep
            ts, val, mask = batch
            step = DownsampleStep(ds_function, window_spec, fill_policy,
                                  fill_value)
            return run_downsample_grid(step, ts, val, mask, wargs), 1

    chunk_points = max(tsdb.config.get_int(
        "tsd.query.streaming.chunk_points"), 1)
    n_chunk = pad_pow2(max(1024, chunk_points // max(s, 1)))
    use_slice = window_spec.kind == "fixed"
    first_ms = int(np.asarray(wargs["first"])) if use_slice else 0
    interval = window_spec.interval_ms
    packer = ChunkPacker(tile_series, seg.start_ms, seg.end_ms, n_chunk, s,
                         fix)
    n_chunks_total = -(-packer.max_len // n_chunk)
    acc = None
    for chunk_i in range(n_chunks_total):
        chunk = packer.fill()
        if chunk is None:
            continue
        ts, val, mask, tmin, tmax, _ = chunk
        if acc is None:
            wslice = None
            if use_slice:
                wslice = 2 * ((tmax - tmin) // interval + 2)
            acc = StreamAccumulator.create(s, window_spec, wargs,
                                           sketch=sketch, lanes=lanes,
                                           window_slice=wslice)
        w0 = None
        if acc.window_slice is not None \
                and (tmax - tmin) // interval + 2 <= acc.window_slice:
            w0 = (tmin - first_ms) // interval
        packer.uploaded(acc.update(ts, val, mask, w0=w0))
        if (chunk_i + 1) % 16 == 0:
            # backpressure: drain the async queue (see _stream_grouped)
            np.asarray(acc.state["n"][:1, :1])
    packer.close()
    if acc is None:
        acc = StreamAccumulator.create(s, window_spec, wargs,
                                       sketch=sketch, lanes=lanes)
    if acc.oob_count():
        raise RuntimeError(
            "internal: %d points fell outside their declared tiled "
            "streaming window slice" % acc.oob_count())
    return (acc.finish(ds_function, fill_policy, fill_value),
            max(n_chunks_total, 1))


def run_tiled(tsdb, spec, seg, series_list, gid, g_pad: int, window_spec,
              wargs, ds_function: str, lanes, sketch: bool, fix: bool,
              plan: TilePlan, budget, store=None, tile_grid_fn=None):
    """Execute an over-budget grouped downsample plan tiled.

    Returns ((out_ts, out_val[g_pad, W], out_mask[g_pad, W]) as numpy,
    stats dict for the span annotation).  Every spilled entry is
    released on every exit path; a pool failure surfaces as the 413/503
    query contract, never a leak.

    ``tile_grid_fn(row_lo, row_hi) -> (wts[W], v[S_tile, W],
    m[S_tile, W])`` substitutes the tile's finished downsample grid for
    the streamed build — the rollup-lane executor (storage/rollup.py)
    serves over-budget plans through the SAME spill + window-striped
    tail replay with grids derived from lane partials instead of raw
    points."""
    from opentsdb_tpu.obs.registry import REGISTRY
    from opentsdb_tpu.query.limits import QueryException
    from opentsdb_tpu.storage.spill import SpillError, SpillWriteError

    pool = tsdb.spill_pool
    step = spec.downsample
    s = len(series_list)
    w = window_spec.count
    spec_tail = replace(spec, rate=None)
    gid_dev = jnp.asarray(np.asarray(gid, np.int64))
    stripes = [(i * plan.stripe_w, min((i + 1) * plan.stripe_w, w))
               for i in range(plan.n_stripes)]
    keys: list = []           # every pooled key, released in finally
    # entry keys per (tile, stripe)
    grid_keys: list[list] = []
    tile_bounds = [(lo, min(lo + plan.tile_rows, s))
                   for lo in range(0, s, plan.tile_rows)]
    wts_full = None
    spilled_bytes = 0
    chunks_total = 0
    try:
        for t_i, (lo, hi) in enumerate(tile_bounds):
            budget.check_deadline()
            if tile_grid_fn is not None:
                wts, v, m = tile_grid_fn(lo, hi)
                n_chunks = 1
            else:
                (wts, v, m), n_chunks = _stream_tile(
                    tsdb, seg, series_list[lo:hi], window_spec, wargs,
                    lanes, sketch, fix, store, ds_function,
                    step.fill_policy, step.fill_value)
            chunks_total += n_chunks
            contrib, participate, actual = _jitted_tile_contrib(
                spec, wts, v, m)
            if wts_full is None:
                wts_full = np.asarray(wts)
            contrib = np.asarray(contrib)
            participate = np.asarray(participate)
            actual = np.asarray(actual)
            REGISTRY.counter(
                "tsd.query.spill.tiles",
                "Series tiles executed by the out-of-core path").inc()
            row = []
            for (w0, w1) in stripes:
                entry = (contrib[:, w0:w1], participate[:, w0:w1],
                         actual[:, w0:w1])
                try:
                    key = pool.put(entry)
                except SpillWriteError as e:
                    raise QueryException(
                        "Sorry, the spill pool backing this tiled "
                        "query failed to write (%s); please retry."
                        % e, status=503)
                except SpillError as e:
                    raise QueryException(
                        "Sorry, this query's partial aggregates "
                        "(%d series x %d windows, ~%dMB) exceed the "
                        "spill pool budget (tsd.query.spill.*): %s"
                        % (s, w, plan.spill_bytes // 2**20, e))
                keys.append(key)
                row.append(key)
                spilled_bytes += sum(a.nbytes for a in entry)
            grid_keys.append(row)
        # ---- window-striped tail replay ---------------------------- #
        out_val = np.zeros((g_pad, w), np.float64)
        out_mask = np.zeros((g_pad, w), bool)
        ws = plan.stripe_w
        for s_i, (w0, w1) in enumerate(stripes):
            budget.check_deadline()
            n = w1 - w0
            V = np.zeros((s, ws), np.float64)
            P = np.zeros((s, ws), bool)
            A = np.zeros((s, ws), bool)
            for t_i, (lo, hi) in enumerate(tile_bounds):
                key = grid_keys[t_i][s_i]
                cv, cp, ca = pool.get(key)
                V[lo:hi, :n] = cv
                P[lo:hi, :n] = cp
                A[lo:hi, :n] = ca
                pool.free(key)
            # stripe timestamps: pad short edge stripes by repeating
            # the last value (only read for non-participating cells)
            wts_s = np.empty(ws, wts_full.dtype)
            wts_s[:n] = wts_full[w0:w1]
            if n < ws:
                wts_s[n:] = wts_full[w1 - 1]
            _, ov, _om, _dense = run_grid_tail(
                spec_tail, jnp.asarray(wts_s), jnp.asarray(V),
                jnp.asarray(P), gid_dev, g_pad)
            pres = _jitted_presence(g_pad, jnp.asarray(A), gid_dev)
            out_val[:, w0:w1] = np.asarray(ov)[:, :n]
            out_mask[:, w0:w1] = np.asarray(pres)[:, :n]
        stats = {"tiles": plan.n_tiles, "stripes": plan.n_stripes,
                 "spillBytes": int(spilled_bytes),
                 "chunks": int(chunks_total),
                 "predictedMs": round(plan.predicted_s * 1e3, 3)}
        recorder = getattr(tsdb, "flightrec", None)
        if recorder is not None:
            # retained spill evidence: tile/stripe split + bytes
            # through the pool (host-ring demotions surface in the
            # tsd.query.spill.* gauges; the event ties the traffic to
            # the query's trace id)
            recorder.record("tiling", series=s, windows=w, **stats)
        return (wts_full, out_val, out_mask), stats
    finally:
        pool.release(keys)

"""Grouped cross-series aggregation on a shared downsample grid.

Reference behavior: TsdbQuery.GroupByAndAggregateCB
(/root/reference/src/core/TsdbQuery.java:981-1114) hands each group-by
bucket its own SpanGroup whose AggregationIterator merges member series one
datapoint at a time.  Round 1 mirrored that shape too literally: the planner
looped over buckets in Python, dispatching one jitted pipeline per group —
10k dispatches for a 10k-group query.

TPU-first form: ALL groups travel in one [S, W] batch with a group id per
row.  Per-series interpolation (the AggregationIterator missing-point
policies, :682/:735) is row-local and group-independent, so it runs over the
whole batch at once; the cross-series reduction becomes one segment
reduction over (group, window) cells — a single device dispatch regardless
of group count.

Cross-chip: moment-decomposable aggregators combine per-chip partial
moments with `psum`/`pmin`/`pmax` over ICI; order/rank-based aggregators
(percentiles, median, first/last/diff, mult, none) use gather-to-owner —
the [S, W] grid (already downsampled, so far smaller than the raw points)
is all-gathered and reduced identically on every chip.  The collectives are
injected by parallel/sharded.py; this module stays collective-free so the
same finish code serves both paths.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from opentsdb_tpu.ops.aggregators import Aggregator
from opentsdb_tpu.ops.downsample import parse_percentile_name
from opentsdb_tpu.ops.rate import _no_interior_hole, _prev_valid_index
from opentsdb_tpu.ops.union_agg import interpolate, _next_valid

_I64_MAX = jnp.iinfo(jnp.int64).max


def _seg_dtype(num: int):
    """Segment/scatter id dtype: int32 whenever the id range fits.
    int64 on TPU is an emulated u32 pair — scatter/gather index handling
    is native at 32 bits, and every feasible (group, window) or (row,
    window) id space here is far below 2^31."""
    return jnp.int32 if num < 2 ** 31 else jnp.int64

# Aggregators whose cross-series reduction decomposes into psum/pmin/pmax
# combinable per-chip moments (count/sum/sumsq/min/max + two-pass dev).
MOMENT_AGGS = frozenset({
    "sum", "zimsum", "pfsum", "count", "avg", "min", "mimmin", "max",
    "mimmax", "dev", "squareSum"})


_EXTREME_AGGS = ("min", "mimmin", "max", "mimmax")


def is_moment_agg(name: str) -> bool:
    """movingAverage<N> included: its cross-series step is a plain sum
    (psum-combinable); the temporal window pass runs on the already
    combined [G, W] grid."""
    from opentsdb_tpu.ops.aggregators import ma_window
    return name in MOMENT_AGGS or ma_window(name) is not None


def _identity(x):
    return x


# Cross-series moment reduction strategy: "segment" scatters per-cell
# partial moments with jax.ops.segment_sum (serializing on TPU), "matmul"
# computes the same sums as onehot[G, S] @ grid[S, W] contractions — dense
# MXU work, no scatter.  "sorted" permutes rows into group order on
# device (argsort of gid — S elements, trivial) so every group is a
# contiguous row run; group sums and extremes are short segmented
# reset-scans along the tiny [S, W] grid's row axis — no scatter, no
# one-hot, cost independent of the group count (PR 27's race on the
# chip: ops/costmodel.py's table).
# All are float64 (Java-double contract); the sum order differs so
# results can drift in the last ulp.  _effective_group_reduce_mode
# picks from platform and shape.
# "rows" is no form to rank: where the caller guarantees that row i IS
# group i (one member a group, the whole batch in one dispatch: a
# group-by over every host of a fleet), the reduction is a copy of the
# [S, W] grid into [G, W].  Raced on a v5e at [100 000, 8] -> G 131 072:
# 1.2 ms against sorted 38.6 and segment 128.3; at [4000, 16] -> G 4096:
# 1.1 against 2.4 and 11.4 (PERF.md section 6, PR 27).

# Shape gate for the matmul form: the dense one-hot is [S, G] f64, so a
# wide group-by (10k groups) would build GBs and burn O(S*G*W) FLOPs —
# those shapes never rank it.
_MATMUL_MAX_GROUPS = 512
_MATMUL_MAX_ONEHOT_BYTES = 1 << 25        # 32 MB


def _matmul_feasible(s: int, g: int) -> bool:
    return g <= _MATMUL_MAX_GROUPS and s * g * 8 <= _MATMUL_MAX_ONEHOT_BYTES


def _group_candidates(s: int, g: int, extremes: bool) -> list[str]:
    cands = ["segment", "sorted"]
    # extremes have no matmul form (min/max don't distribute over the
    # one-hot dot) — rank only the forms that exist for them
    if not extremes and _matmul_feasible(s, g):
        cands.append("matmul")
    return cands


def _effective_group_reduce_mode(s: int, w: int, g: int,
                                 extremes: bool = False,
                                 platform: str | None = None,
                                 row_groups: bool = False) -> str:
    """The group-combine form for this shape: a copy where the caller
    guarantees one member a group (`row_groups`), else segment / sorted /
    (feasible) matmul ranked by the cost table (ops.costmodel — chip
    anchors: PR 27's race, costmodel.py; CPU scatters are cheap so
    segment wins there).  `platform` defaults to the ambient execution
    platform; the planner's decision report passes its per-segment
    platform explicitly."""
    if row_groups:
        return "rows"
    from opentsdb_tpu.ops.hostlane import execution_platform
    from opentsdb_tpu.ops import costmodel
    return costmodel.choose_group(s, w, g, platform
                                  or execution_platform(),
                                  _group_candidates(s, g, extremes))


def group_decision(s: int, w: int, g: int, platform: str,
                   extremes: bool = False,
                   row_groups: bool = False) -> dict:
    """The group-reduce decision for one dispatch shape, as the trace
    annotates it (same report shape as downsample.search_decision)."""
    from opentsdb_tpu.ops import costmodel
    from opentsdb_tpu.ops.downsample import _decision_report
    mode = _effective_group_reduce_mode(s, w, g, extremes, platform,
                                        row_groups)
    cands = _group_candidates(s, g, extremes)
    if mode == "rows":
        cands = ["rows"] + cands        # a guarantee, not a ranking
    return _decision_report(
        "group", mode, cands,
        lambda m: costmodel.predict_group(m, s, w, g, platform))


class _SortedGroups:
    """Rows permuted into group order: the machinery behind mode "sorted".

    Group g's members occupy rows [bounds[g], bounds[g+1]) of the
    permuted grid; rows with gid outside [0, G) sort past bounds[G] and
    drop out.  Group sums AND extremes are segmented reset-scans over
    the permuted row order, gathered at each group's last row.
    Everything is [S, W]-sized vector work — no scatter.
    """

    def __init__(self, gid, num_groups: int, s: int,
                 presorted: bool = False):
        self.g = num_groups
        self.s = s
        if presorted:
            # Caller-guaranteed non-decreasing gid (the planner always
            # emits groups as concatenated runs, planner.py:403): skip
            # the argsort AND the [S, W] permute gather in every fold.
            self.perm = None
            self.sorted_gid = gid
        else:
            self.perm = jnp.argsort(gid, stable=True)
            self.sorted_gid = jnp.take(gid, self.perm)
        self.bounds = jnp.searchsorted(
            self.sorted_gid, jnp.arange(num_groups + 1,
                                        dtype=self.sorted_gid.dtype))
        # reset flags: row starts a new group run (for the reset-scan)
        self.flags = jnp.concatenate(
            [jnp.ones((1,), bool),
             self.sorted_gid[1:] != self.sorted_gid[:-1]])

    def sum(self, x2d):
        """[S, W] -> [G, W] per-group column sums via a segmented
        reset-scan (NOT a cumsum differenced at bounds: that computes a
        small group's sum as the difference of two large running totals,
        and the cancellation error scales with the GLOBAL total — a
        1e15-magnitude group next to a 1.0-magnitude group would break
        the 1e-9 parity contract.  The reset-scan restarts each group's
        accumulation at zero, so error scales with the group's own sum,
        same as segment_sum)."""
        from jax import lax
        xs = x2d if self.perm is None \
            else jnp.take(x2d, self.perm, axis=0)
        flags = jnp.broadcast_to(self.flags[:, None], xs.shape)

        def combine(a, b):
            av, af = a
            bv, bf = b
            return jnp.where(bf, bv, av + bv), af | bf

        scanned, _ = lax.associative_scan(combine, (xs, flags), axis=0)
        ends = jnp.clip(self.bounds[1:] - 1, 0, self.s - 1)
        out = jnp.take(scanned, ends, axis=0)            # [G, W]
        # empty groups gather a neighboring run's total: zero them
        empty = (self.bounds[1:] == self.bounds[:-1])[:, None]
        return jnp.where(empty, jnp.zeros_like(out), out)

    def extreme(self, x2d, want_max: bool):
        """[S, W] -> [G, W] per-group min or max via a reset-scan.

        Callers pre-fill non-participating cells with the identity
        (+inf for min / -inf for max); empty groups return the identity.
        """
        from jax import lax
        xs = x2d if self.perm is None \
            else jnp.take(x2d, self.perm, axis=0)
        flags = jnp.broadcast_to(self.flags[:, None], xs.shape)

        def combine(a, b):
            av, af = a
            bv, bf = b
            ext = jnp.maximum(av, bv) if want_max else jnp.minimum(av, bv)
            return jnp.where(bf, bv, ext), af | bf

        scanned, _ = lax.associative_scan(combine, (xs, flags), axis=0)
        # group g's run ends at row bounds[g+1]-1; empty groups gather a
        # clipped row and are masked by the caller's count grid
        ends = jnp.clip(self.bounds[1:] - 1, 0, self.s - 1)
        return jnp.take(scanned, ends, axis=0)

class _RowGroups:
    """_SortedGroups where row i IS group i (form "rows"): every fold of
    a run is the row itself, so each is a copy of [S, W] into [G, W]
    (zero rows up to G: they carry no count and are masked out)."""

    def __init__(self, num_groups: int):
        self.g = num_groups

    def sum(self, x2d):
        s = x2d.shape[0]
        return x2d[:self.g] if s >= self.g \
            else jnp.pad(x2d, ((0, self.g - s), (0, 0)))

    def extreme(self, x2d, want_max: bool):
        return self.sum(x2d)


def _run_folds(mode: str, gid, num_groups: int, s: int, rows_sorted: bool):
    """The fold machinery of the scatter-free forms."""
    return _RowGroups(num_groups) if mode == "rows" \
        else _SortedGroups(gid, num_groups, s, rows_sorted)


def grid_contributions(grid_ts, val, mask, agg: Aggregator):
    """Per-series contribution + participation at every grid slot.

    The batched form of AggregationIterator's missing-point substitution
    (nextDoubleValue :735): a series missing window w contributes the
    interpolated value per the aggregator's policy, participating only
    between its first and last present window.  Row-local — valid across
    any row sharding.  Returns (contrib[S, W], participate[S, W], dense[]).

    Grids without an INTERIOR hole — every row one contiguous run of
    present windows, or empty (`_no_interior_hole`) — take a lax.cond
    fast lane that skips the prev/next scans, the four gathers and the
    interpolation: a missing slot before a row's first value or after
    its last has no previous or no next, so participate == mask there,
    and every consumer masks contrib by participate first.  The lane's
    answer (val, mask) is therefore the full branch's on every slot
    anyone reads.  That covers the regular-cadence store whatever its
    padding: windows past the live count, rate's masked first column,
    series born late or ended early.  A row with a real hole (a host
    down mid-range) sends the grid through the full branch; the cond
    costs one elementwise pass and a row reduce over bool[S, W].

    The third return is the predicate itself, a bool scalar: the lane
    is decided on the device, and the served path hands it back beside
    the answer for `tsd.query.contrib_lane{lane}`.
    """
    from jax import lax

    def _full(operand):
        grid_ts_, val_, mask_ = operand
        w = val_.shape[1]
        prev_i = _prev_valid_index(mask_)
        next_i = _next_valid(mask_)
        has_prev = prev_i >= 0
        has_next = next_i < w
        safe_prev = jnp.clip(prev_i, 0, w - 1)
        safe_next = jnp.clip(next_i, 0, w - 1)

        x = grid_ts_[None, :]
        x0 = jnp.take(grid_ts_, safe_prev)
        x1 = jnp.take(grid_ts_, safe_next)
        y0 = jnp.take_along_axis(val_, safe_prev, axis=1)
        y1 = jnp.take_along_axis(val_, safe_next, axis=1)

        participate = has_prev & has_next | mask_
        interp = interpolate(agg.interpolation, False, x, x0, y0, x1, y1,
                             val_)
        contrib = jnp.where(mask_, val_, interp)
        return contrib, participate

    # both cond branches must agree on dtype, and the full branch's
    # depends on the agg's interpolation policy (LERP promotes f32 val
    # to f64 through the int64 timestamp division; ZIM keeps val's
    # dtype) — derive it from the full branch itself, abstractly
    out_dtype = jax.eval_shape(_full, (grid_ts, val, mask))[0].dtype

    def _dense(operand):
        _, val_, mask_ = operand
        return val_.astype(out_dtype), mask_

    dense = _no_interior_hole(mask)
    contrib, participate = lax.cond(dense, _dense, _full,
                                    (grid_ts, val, mask))
    return contrib, participate, dense


def _flat_segments(contrib, participate, gid, num_groups: int):
    """Flatten [S, W] to (seg, ok, v) over (group, window) cells."""
    s, w = contrib.shape
    dt = _seg_dtype(num_groups * w + w)
    cols = jnp.arange(w, dtype=dt)[None, :]
    seg = (gid.astype(dt)[:, None] * w + cols).reshape(-1)
    vf = contrib.astype(jnp.float64)
    ok = (participate & ~jnp.isnan(vf)).reshape(-1)
    v = jnp.where(ok, vf.reshape(-1), 0.0)
    return seg, ok, v


# shape: contrib[S,W] any, participate[S,W] bool, gid[S] any
def moment_group_reduce(agg_name: str, contrib, participate, gid,
                        num_groups: int, combine_sum=_identity,
                        combine_min=_identity, combine_max=_identity,
                        rows_sorted: bool = False,
                        row_groups: bool = False):
    """[S, W] -> ([G, W] out, [G, W] count) for moment-decomposable aggs.

    `combine_*` inject the cross-chip collectives (psum/pmin/pmax over the
    mesh) between the local partial moments and the finish arithmetic; the
    defaults make this the complete single-device reduction.  The dev
    aggregator's second (centered) pass re-uses `combine_sum`, costing one
    extra ICI round-trip — the two-pass scheme the reference's Welford loop
    approximates (Aggregators.java:498).
    """
    s, w = contrib.shape
    g = num_groups
    num = g * w
    extremes = agg_name in _EXTREME_AGGS
    mode = _effective_group_reduce_mode(s, w, g, extremes=extremes,
                                        row_groups=row_groups)

    if extremes:
        want_max = agg_name in ("max", "mimmax")
        if mode in ("sorted", "rows"):
            # contiguous-run reset-scan over group-sorted rows: no
            # scatter.  rows = every run is one row.
            sg = _run_folds(mode, gid, g, s, rows_sorted)
            vf0 = contrib.astype(jnp.float64)
            ok0 = participate & ~jnp.isnan(vf0)
            local_cnt = sg.sum(ok0.astype(jnp.float64))         # [G, W]
            cnt_grid = combine_sum(local_cnt.reshape(-1)) \
                .reshape(g, w).astype(jnp.int64)
            ident = -jnp.inf if want_max else jnp.inf
            filled = jnp.where(ok0, vf0, ident)
            ext = sg.extreme(filled, want_max)
            # a group empty on THIS shard must contribute the identity to
            # pmin/pmax, not the boundary gather's neighboring-run value
            ext = jnp.where(local_cnt > 0.5, ext, ident).reshape(-1)
            ext = (combine_max(ext) if want_max
                   else combine_min(ext)).reshape(g, w)
            out = jnp.where(cnt_grid > 0, ext, jnp.nan)
            return out, cnt_grid
        # segment: extremes have no matmul form — scatter ops
        seg, ok, v = _flat_segments(contrib, participate, gid, g)
        cnt = combine_sum(jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                              num_segments=num))
        cnt_grid = cnt.reshape(g, w).astype(jnp.int64)
        if agg_name in ("min", "mimmin"):
            ext = combine_min(jax.ops.segment_min(
                jnp.where(ok, v, jnp.inf), seg, num_segments=num))
        else:
            ext = combine_max(jax.ops.segment_max(
                jnp.where(ok, v, -jnp.inf), seg, num_segments=num))
        out = jnp.where(cnt_grid > 0, ext.reshape(g, w), jnp.nan)
        return out, cnt_grid

    # One finish, three group-sum primitives.  The matmul form is a
    # candidate only where the dense one-hot is cheap (small G relative
    # to S — the headline group-by shape, _matmul_feasible).
    vf = contrib.astype(jnp.float64)
    ok2 = participate & ~jnp.isnan(vf)
    v2 = jnp.where(ok2, vf, 0.0)
    if mode in ("sorted", "rows"):
        sg = _run_folds(mode, gid, g, s, rows_sorted)

        def gsum(x2d):   # [S, W] -> [G, W], cross-chip combined
            return combine_sum(sg.sum(x2d).reshape(-1)).reshape(g, w)
    elif mode == "matmul":
        # out[g, w] = Σ_s onehot[s, g] * grid[s, w] — dense MXU work, no
        # serializing scatter.  Counts are 0/1 sums (exact in f64 far
        # beyond any real S); value sums reassociate vs segment_sum, so
        # parity is to the last ulp, not bitwise.
        o_t = (gid[:, None]
               == jnp.arange(g, dtype=gid.dtype)[None, :]) \
            .astype(jnp.float64).T                             # [G, S]

        def gsum(x2d):   # [S, W] -> [G, W], cross-chip combined
            return combine_sum((o_t @ x2d).reshape(-1)).reshape(g, w)
    else:
        dt = _seg_dtype(num + w)     # pre-clamp ids reach num + w - 1
        cols = jnp.arange(w, dtype=dt)[None, :]
        seg = (jnp.clip(gid.astype(dt), 0, g)[:, None] * w
               + cols).reshape(-1)
        seg = jnp.where(seg < num, seg, jnp.asarray(num, dt))

        def gsum(x2d):
            return combine_sum(jax.ops.segment_sum(
                x2d.reshape(-1), seg, num_segments=num + 1)[:-1]) \
                .reshape(g, w)

    cnt_grid = gsum(ok2.astype(jnp.float64)).astype(jnp.int64)
    safe = jnp.maximum(cnt_grid, 1)

    if agg_name in ("sum", "zimsum", "pfsum"):
        out = gsum(v2)
    elif agg_name == "count":
        out = cnt_grid.astype(jnp.float64)
    elif agg_name == "avg":
        out = gsum(v2) / safe
    elif agg_name == "squareSum":
        out = gsum(v2 * v2)
    elif agg_name == "dev":
        # Two-pass centered moment with the GLOBAL mean (one extra
        # combine round-trip) — the scheme the reference's Welford loop
        # approximates (Aggregators.java:498).
        mean = gsum(v2) / safe                                  # [G, W]
        mean_pp = jnp.take(mean, jnp.clip(gid, 0, g - 1), axis=0)
        centered = jnp.where(ok2, vf - mean_pp, 0.0)
        m2 = gsum(centered * centered)
        out = jnp.where(cnt_grid >= 2,
                        jnp.sqrt(m2 / jnp.maximum(cnt_grid - 1, 1)), 0.0)
    else:
        from opentsdb_tpu.ops.aggregators import java_moving_average, \
            ma_window
        nw = ma_window(agg_name)
        if nw is None:
            raise KeyError("Aggregator %r is not moment-decomposable"
                           % agg_name)
        # Cross-series sum combines across chips; the Java window pass
        # then runs on the replicated [G, W] grid (live = windows with
        # data, matching the evaluation order the iterator would visit).
        out = java_moving_average(gsum(v2), cnt_grid > 0, nw)

    if agg_name != "count":
        out = jnp.where(cnt_grid > 0, out, jnp.nan)
    return out, cnt_grid


# shape: contrib[S,W] any, participate[S,W] bool, gid[S] any
def ordered_group_reduce(agg_name: str, contrib, participate, gid,
                         num_groups: int):
    """[S, W] -> ([G, W] out, [G, W] count) for rank/order-based aggs.

    Needs every member row present (no partial-moment form); the sharded
    path all-gathers the grid before calling.  first/last/diff follow row
    order — the order series entered the group, matching the reference's
    iteration order over spans (Aggregators.java:576-617, :810).
    """
    s, w = contrib.shape
    g = num_groups
    num = g * w
    if not (agg_name == "median" or agg_name.startswith(("p", "ep"))):
        seg, ok, v = _flat_segments(contrib, participate, gid, g)
        cnt = jax.ops.segment_sum(ok.astype(jnp.int32), seg,
                                  num_segments=num).reshape(g, w) \
            .astype(jnp.int64)

    if agg_name == "mult":
        out = jax.ops.segment_prod(jnp.where(ok, v, 1.0), seg,
                                   num_segments=num).reshape(g, w)
    elif agg_name in ("first", "last", "diff", "none"):
        rows = jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[:, None], (s, w)).reshape(-1)
        first_row = jax.ops.segment_min(
            jnp.where(ok, rows, jnp.asarray(s, jnp.int32)), seg,
            num_segments=num).reshape(g, w)
        last_row = jax.ops.segment_max(
            jnp.where(ok, rows, jnp.asarray(-1, jnp.int32)), seg,
            num_segments=num).reshape(g, w)
        vf = contrib.astype(jnp.float64)
        first_v = jnp.take_along_axis(vf, jnp.clip(first_row, 0, s - 1),
                                      axis=0)
        last_v = jnp.take_along_axis(vf, jnp.clip(last_row, 0, s - 1), axis=0)
        if agg_name in ("first", "none"):
            out = first_v
        elif agg_name == "last":
            out = last_v
        else:
            out = jnp.where(cnt >= 2, last_v - first_v, 0.0)
    elif agg_name == "median" or agg_name.startswith(("p", "ep")):
        # ONE column sort with (gid, value) lexicographic keys instead of
        # a global [S*W] lexsort: each window's column sorts its S values
        # independently (W tiny bitonic sorts — the natural vectorized
        # form), invalid rows keyed past every group.  The SAME sort
        # yields starts AND counts (per-column searchsorted of the
        # sorted keys at the group boundaries) — no scatter, no second
        # valid-mask definition, nothing but this one sort.
        from jax import lax
        from opentsdb_tpu.ops.percentile import column_run_percentile
        vf2 = contrib.astype(jnp.float64)
        ok2 = (participate & ~jnp.isnan(vf2))
        in_range = (gid >= 0) & (gid < g)
        gkey = jnp.broadcast_to(
            jnp.where(in_range, gid, g).astype(jnp.int32)[:, None], (s, w))
        gkey = jnp.where(ok2, gkey, g)
        vals = jnp.where(ok2, vf2, jnp.inf)
        sorted_keys, sorted_cols = lax.sort((gkey, vals), dimension=0,
                                            num_keys=2)
        bounds = jax.vmap(
            lambda col: jnp.searchsorted(
                col, jnp.arange(g + 1, dtype=sorted_keys.dtype)),
            in_axes=1, out_axes=1)(sorted_keys)              # [G+1, W]
        starts = bounds[:-1]
        cnt = (bounds[1:] - bounds[:-1]).astype(jnp.int64)
        if agg_name == "median":
            # Upper median sorted[n // 2] (Aggregators.Median :397-431).
            idx = jnp.clip(starts + (cnt // 2).astype(starts.dtype),
                           0, s - 1)
            out = jnp.where(
                cnt > 0,
                jnp.take_along_axis(sorted_cols, idx, axis=0), jnp.nan)
        else:
            q, est = parse_percentile_name(agg_name)
            out = column_run_percentile(sorted_cols, starts, cnt, q, est)
    else:
        raise KeyError("No such aggregator: " + agg_name)

    out = jnp.where(cnt > 0, out, jnp.nan)
    return out, cnt


# shape: mask[S,W] bool, gid[S] any
def group_presence(mask, gid, num_groups: int, extremes: bool = False,
                   rows_sorted: bool = False, row_groups: bool = False):
    """[S, W] actual-value mask + gid[S] -> bool[G, W]: group g has a
    member present in window w (the out-mask rule of every grouped
    tail; rows with gid outside [0, G) belong to no group).

    The pass rides the form the reduce beside it took — `extremes` is
    moment_group_reduce's own flag, so the chooser answers as it did
    there — or a pick of sorted / matmul, made to keep a dispatch
    scatter-free, would get the scatter back through its mask (review
    r5; on the chip a [S*W] -> [G*W] scatter was the longest operation
    of a sum tail, PERF.md section 6, PR 33).  sorted / rows: the run
    folds.  matmul: the reduce's one-hot contraction over 0/1 operands
    in float32, exact while a count stays under 2^24 (_matmul_feasible
    caps S far below).  segment, the CPU's pick: segment_sum.
    """
    s, w = mask.shape
    g = num_groups
    mode = _effective_group_reduce_mode(s, w, g, extremes=extremes,
                                        row_groups=row_groups)
    if mode in ("sorted", "rows"):
        # same fold machinery as the reduce (XLA CSEs the repeated
        # argsort/bounds)
        sg = _run_folds(mode, gid, g, s, rows_sorted)
        return sg.sum(mask.astype(jnp.float64)) > 0
    if mode == "matmul":
        onehot_t = (gid[None, :] == jnp.arange(g, dtype=gid.dtype)[:, None])
        present = jnp.dot(onehot_t.astype(jnp.float32),
                          mask.astype(jnp.float32),
                          preferred_element_type=jnp.float32)
        return present > 0
    dt = _seg_dtype(g * w + w)
    cols = jnp.arange(w, dtype=dt)[None, :]
    seg = (gid.astype(dt)[:, None] * w + cols).reshape(-1)
    present = jax.ops.segment_sum(mask.reshape(-1).astype(jnp.int32), seg,
                                  num_segments=g * w)
    return present.reshape(g, w) > 0


# shape: grid_ts[W] i64, val[S,W] any, mask[S,W] bool, gid[S] any
def grid_group_aggregate(grid_ts, val, mask, gid, num_groups: int,
                         agg: Aggregator, rows_sorted: bool = False,
                         row_groups: bool = False):
    """All-groups-at-once grid aggregation (single-device form).

    [S, W] batch + gid[S] -> (grid_ts[W], out[G, W], out_mask[G, W],
    dense[]).  out_mask marks (group, window) cells where at least one
    member holds an actual (non-interpolated) value — the union-timestamp
    rule restricted to the shared grid.  dense is grid_contributions'
    lane predicate (True: the grid had no interior hole).

    rows_sorted=True is a CALLER GUARANTEE that gid is non-decreasing
    (the planner always builds it that way, planner.py:403) — the sorted
    modes then skip the argsort and the [S, W] permute gathers.  A false
    claim silently misassigns rows to groups.  row_groups=True is the
    stronger guarantee that gid[i] == i for every row that carries data
    (one member a group, the whole batch in this one dispatch): the
    moment reductions and the mask pass are then copies.
    """
    vf = val.astype(jnp.float64)
    contrib, participate, dense = grid_contributions(grid_ts, vf, mask, agg)
    if is_moment_agg(agg.name):
        out, _ = moment_group_reduce(agg.name, contrib, participate, gid,
                                     num_groups, rows_sorted=rows_sorted,
                                     row_groups=row_groups)
    else:
        out, _ = ordered_group_reduce(agg.name, contrib, participate, gid,
                                      num_groups)
    out_mask = group_presence(mask, gid, num_groups,
                              extremes=agg.name in _EXTREME_AGGS,
                              rows_sorted=rows_sorted,
                              row_groups=row_groups)
    return grid_ts, out, out_mask, dense

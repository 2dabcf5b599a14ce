"""Chunked/streaming execution: beyond-memory queries on bounded HBM.

Reference behavior: the scan layer streams storage rows through overlapping
scanner callbacks (/root/reference/src/core/SaltScanner.java:463-740 —
ScannerCB fetches the next batch while span assembly digests the last) and
never holds more than the assembled spans; queries too big to assemble are
refused by byte budgets.  Round 1 materialized the whole [S, N] batch in
host memory (VERDICT missing #4) — a 1B-point query cannot fit.

TPU-first form: the time axis is chunked; each chunk is a bounded [S, n]
batch whose per-(series, window) moments are computed with the scatter-free
prefix-sum kernel and MERGED into device-resident accumulator state.  All
downsample functions with associative merges stream:

  * count/sum/sumsq -> additive; min/max -> pointwise min/max
  * dev -> Chan parallel-variance merge of (n, total, M2) — numerically the
    two-pass scheme, exact under chunking
  * first/last -> chunks arrive in time order, so first sticks and last
    overwrites; diff = last - first; mult -> running product

Rank-based window functions (median/p* as *downsample* functions) stream
through a mergeable fixed-size quantile summary (is_sketch_ds below): each
chunk's exact per-(series, window) K-point equi-rank grid folds into the
accumulated grid by weighted merge + re-interpolation.  Error is in RANK,
not value: one compaction to a K-grid moves a quantile's rank by at most
1/(2K), so a cell that receives data from C chunks drifts at most
~C/(2K) of its population in the worst case (K=64).  Two things keep C
small in practice: chunks partition TIME while windows partition time
too, so a window-sized cell only overlaps the few chunks that span it
(an empty-side merge is an exact no-op); and on stationary data the
per-merge errors are signed and largely cancel (random-walk, not
linear — see test_many_merges_drift_bounded).  The hazard case is a
window much wider than a chunk (e.g. "0all" over a huge range), where C
equals the chunk count; for those prefer the exact path via
tsd.query.streaming.sketch_percentiles=false + budgets.  The exact sort
path still serves materialized (sub-threshold) queries; the reference
would have refused big rank queries on budget instead
(Aggregators.java:657-708 sorts fully in memory).

JAX's async dispatch gives the ScannerCB overlap for free: `update()`
returns as soon as the device program is enqueued, so the host fetches and
packs chunk k+1 while the device reduces chunk k (double buffering without
explicit machinery).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.ops.downsample import (
    WindowSpec, apply_fill, window_ids, window_timestamps,
    _absolute_ts, _extreme_downsample,
    _window_scan_setup, _window_ids_fast, FILL_NONE)

# Summary points per (series, window) quantile sketch.
SKETCH_K = 64


# Extra state lanes each downsample function's finish needs ("n" is always
# present — it carries the output mask).  Restricting the accumulator to
# the needed lanes removes ALL segment scatters from the streamed hot loop
# for the additive family (lo/hi/first/last/prod are the scatter-heavy
# lanes) and shrinks state memory accordingly.
LANES_FOR = {
    "sum": {"total"}, "zimsum": {"total"}, "pfsum": {"total"},
    "count": set(), "avg": {"total"},
    "squareSum": {"total", "m2"}, "dev": {"total", "m2"},
    "min": {"lo"}, "mimmin": {"lo"}, "max": {"hi"}, "mimmax": {"hi"},
    "first": {"first"}, "last": {"last"}, "diff": {"first", "last"},
    "mult": {"prod"},
}
# Downsample functions whose window moments merge associatively (exact) —
# derived from LANES_FOR so the two can never drift.
STREAMABLE_DS = frozenset(LANES_FOR)
_ALL_LANES = frozenset(
    {"total", "m2", "lo", "hi", "first", "last", "prod"})


def lanes_for(ds_functions) -> frozenset:
    """Union of state lanes needed to finish the given ds functions.

    Rank-based (sketch) functions contribute NO moment lanes — their
    state is the sketch lane, enabled by the accumulators' `sketch` flag;
    unknown functions fall back to every lane (conservative).
    """
    out: set = set()
    for fn in ds_functions:
        if is_sketch_ds(fn):
            continue
        out |= LANES_FOR.get(fn, _ALL_LANES)
    if "m2" in out:
        out.add("total")   # the centered pass needs the mean
    return frozenset(out)


def is_sketch_ds(name: str) -> bool:
    """Rank-based downsample functions served by the mergeable quantile
    summary when streaming (median / p* / ep*r3 / ep*r7)."""
    if name == "median":
        return True
    if name.startswith(("p", "ep")) and name not in ("pfsum",):
        from opentsdb_tpu.ops.downsample import parse_percentile_name
        try:
            parse_percentile_name(name)
            return True
        except (KeyError, ValueError):   # non-percentile p*-named fn
            return False
    return False


def _zero_state(s: int, w: int, sketch: bool = False,
                lanes: frozenset | None = None,
                with_oob: bool = False) -> dict:
    """Zero accumulator state holding only the requested lanes
    (None = every lane, the conservative default).  `with_oob` adds the
    0-d audit counter sliced updates maintain — only slice-enabled
    accumulators carry it (the sharded accumulator's shard_map specs are
    rank-2 per leaf)."""
    if lanes is None:
        lanes = _ALL_LANES
    if "m2" in lanes and "total" not in lanes:
        raise ValueError("the m2 lane requires the total lane (use "
                         "lanes_for())")
    builders = {
        "total": lambda: jnp.zeros((s, w), jnp.float64),
        "m2": lambda: jnp.zeros((s, w), jnp.float64),
        "lo": lambda: jnp.full((s, w), jnp.inf, jnp.float64),
        "hi": lambda: jnp.full((s, w), -jnp.inf, jnp.float64),
        "first": lambda: jnp.zeros((s, w), jnp.float64),
        "last": lambda: jnp.zeros((s, w), jnp.float64),
        "prod": lambda: jnp.ones((s, w), jnp.float64),
    }
    state = {"n": jnp.zeros((s, w), jnp.int64)}
    if with_oob:
        # audit counter for window-sliced updates: valid points that
        # fell OUTSIDE the caller-declared window slice (a w0/slice
        # contract violation — see StreamAccumulator.update)
        state["oob"] = jnp.zeros((), jnp.int64)
    for name in lanes:
        state[name] = builders[name]()
    if sketch:
        # q[s, w, j] = value at fractional rank (j+0.5)/K of the cell's
        # population seen so far (midpoint convention); counts live in "n".
        # float32: the sketch's rank error (~chunks/2K) dwarfs f32 value
        # precision by orders of magnitude, and f64 is emulated on TPU.
        state["q"] = jnp.zeros((s, w, SKETCH_K), jnp.float32)
    return state


def _segment_chunk_moments(ts, val, mask, spec: WindowSpec, wargs: dict,
                           lanes: frozenset):
    """Chunk moments for wider-than-data grids: N-bounded sorted scatters.

    When a chunk's window grid has (far) more windows than the chunk has
    points (BASELINE config 2: a 64k-point chunk against a ~1M-window
    10s grid), every edge-search form costs O(W) or worse PER CHUNK —
    the r4 chip session burned its whole config-2 budget there.  Here
    the cost is bounded by the POINT count instead: per-point window ids
    (a division on fixed grids), then one segment reduction per lane
    with `indices_are_sorted=True` — the flattened (row, window) ids are
    genuinely sorted because rows are time-sorted, and invalid slots
    keep their clipped (monotone) id while contributing the lane's
    identity element, never a shuffled sentinel.

    Serves the n/total/m2/lo/hi lanes (the streamable moment family);
    callers keep the edge-search form for first/last/prod/sketch.
    """
    s, n = ts.shape
    w = spec.count
    num = s * w
    vf = val.astype(jnp.float64)
    ok = mask & ~jnp.isnan(vf)
    win = window_ids(ts, spec, wargs)
    nwin = wargs["nwin"]
    valid = ok & (win >= 0) & (win < nwin.astype(win.dtype))
    # int32 ids once clipped in-range: int64 scatter indices are
    # emulated u32 pairs on TPU (the id space s*w is far below 2^31)
    from opentsdb_tpu.ops.group_agg import _seg_dtype
    dt = _seg_dtype(s * w + w)
    winc = jnp.clip(win, 0, w - 1).astype(dt)
    rows = jnp.arange(s, dtype=dt)[:, None]
    seg = (rows * w + winc).reshape(-1)

    def reduce(data, ident, kind="sum"):
        flat = jnp.where(valid, data, ident).reshape(-1)
        fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
              "max": jax.ops.segment_max}[kind]
        return fn(flat, seg, num_segments=num,
                  indices_are_sorted=True).reshape(s, w)

    cnt = reduce(jnp.ones_like(vf, dtype=jnp.int32), 0).astype(jnp.int64)
    out = {"n": cnt}
    if "total" in lanes:
        tot = reduce(vf, 0.0)
        out["total"] = tot
        if "m2" in lanes:
            mean = tot / jnp.maximum(cnt, 1)
            mean_pp = jnp.take_along_axis(mean, winc, axis=1)
            centered = jnp.where(valid, vf - mean_pp, 0.0)
            out["m2"] = reduce(centered * centered, 0.0)
    if "lo" in lanes:
        out["lo"] = reduce(vf, jnp.inf, "min")
    if "hi" in lanes:
        out["hi"] = reduce(vf, -jnp.inf, "max")
    return out


# Segment-vs-dense routing threshold for streamed chunks: the segment
# form engages when W > ratio * N.  1.0 is the analytic crossover (per-
# edge search work vs per-point scatter work); no chip run has measured
# the real one (no cell streams: PERF.md section 4) — TPU scatters
# serialize, so it may sit well above 1.
_SEGMENT_CHUNK_RATIO = 1.0


def _use_segment_chunk(n: int, w: int, lanes: frozenset,
                       with_sketch: bool) -> bool:
    """Route chunks with more windows than points to the segment form:
    past W ~ ratio*N the edge search's per-edge work exceeds the segment
    form's per-point work (config 4 sits at exactly W = 4N; config 2 at
    W = 16N).  first/last/prod and the sketch keep the edge-search form
    (their reductions are position- or sort-based)."""
    return (w > _SEGMENT_CHUNK_RATIO * n and not with_sketch
            and not (lanes & {"first", "last", "prod"}))


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool, wargs.first[] i64
# shape: wargs.nwin[] i32
def _chunk_moments(ts, val, mask, spec: WindowSpec, wargs: dict,
                   lanes: frozenset = _ALL_LANES,
                   with_sketch: bool = False):
    """One chunk's per-(series, window) moments, restricted to `lanes`.

    The additive lanes (n/total/m2) ride the scatter-free prefix-sum
    kernel; lo/hi/first/last/prod need per-point window membership and
    cost one segment scatter each — skipped entirely when not requested,
    which is the common case (sum/avg/count queries stream scatter-free).
    Wider-than-data grids (W >> chunk points) take the N-bounded segment
    form instead — see _segment_chunk_moments.
    """
    s, n = ts.shape
    w = spec.count
    if _use_segment_chunk(n, w, lanes, with_sketch):
        return _segment_chunk_moments(ts, val, mask, spec, wargs, lanes)
    # ONE setup shared with the materialized path: same edge search
    # (incl. the search-mode toggle), same int32 compaction, and the
    # clean-batch count shortcut — streamed chunks are clean by
    # construction, so their count lane costs no scan at all.
    vf, ok, cts, idx, windowed, cnt = _window_scan_setup(ts, val, mask,
                                                         spec, wargs)
    out = {"n": cnt}

    need_win = ("m2" in lanes or with_sketch
                or lanes & {"first", "last", "prod"})
    raw_win = _window_ids_fast(ts, cts, spec, wargs) if need_win else None

    if "total" in lanes:
        v0 = jnp.where(ok, vf, 0.0)
        tot = windowed(v0)
        out["total"] = tot
        if "m2" in lanes:
            mean = tot / jnp.maximum(cnt, 1)
            win = jnp.clip(raw_win, 0, w - 1)
            mean_pp = jnp.take_along_axis(mean, win, axis=1)
            centered = jnp.where(ok, vf - mean_pp, 0.0)
            out["m2"] = windowed(centered * centered)

    # lo/hi ride the scatter-free segmented reset-scan — ONE fused scan
    # for both (XLA CSEs the edge-search it shares with the prefix lanes
    # inside this one jit); extreme mode "subblock" swaps in the
    # sub-block decomposition, same as the materialized path
    if lanes & {"lo", "hi"}:
        from opentsdb_tpu.ops import downsample as _ds
        extreme = _ds._extreme_subblock \
            if _ds._use_subblock_extreme(n, w) else _extreme_downsample
        lo, hi, _ = extreme(ts, val, mask, spec, wargs,
                            "lo" in lanes, "hi" in lanes)
        if lo is not None:
            out["lo"] = lo
        if hi is not None:
            out["hi"] = hi

    seg_lanes = lanes & {"first", "last", "prod"}
    if seg_lanes or with_sketch:
        from opentsdb_tpu.ops.group_agg import _seg_dtype
        num = s * w + 1
        dt = _seg_dtype(s * w + w)
        win = jnp.clip(raw_win, 0, w - 1).astype(dt)
        valid = ok & (raw_win >= 0) & (raw_win
                                       < jnp.asarray(w, raw_win.dtype))
        rows = jnp.arange(s, dtype=dt)[:, None]
        seg = jnp.where(valid, rows * w + win,
                        jnp.asarray(s * w, dt)).reshape(-1)
        flat = jnp.where(valid, vf, 0.0).reshape(-1)
        okf = valid.reshape(-1)
        if seg_lanes & {"first", "last"}:
            dtp = _seg_dtype(s * n + 1)      # positions span s*n, not s*w
            pos = jnp.arange(s * n, dtype=dtp)
            flat_v = vf.reshape(-1)
            if "first" in seg_lanes:
                first_i = jax.ops.segment_min(
                    jnp.where(okf, pos, jnp.iinfo(dtp).max), seg,
                    num_segments=num)[:-1]
                out["first"] = flat_v[
                    jnp.clip(first_i, 0, s * n - 1)].reshape(s, w)
            if "last" in seg_lanes:
                last_i = jax.ops.segment_max(
                    jnp.where(okf, pos, -1), seg,
                    num_segments=num)[:-1]
                out["last"] = flat_v[
                    jnp.clip(last_i, 0, s * n - 1)].reshape(s, w)
        if "prod" in seg_lanes:
            out["prod"] = jax.ops.segment_prod(
                jnp.where(okf, flat, 1.0), seg,
                num_segments=num)[:-1].reshape(s, w)
        if with_sketch:
            # Exact per-cell equi-rank grid for this chunk: ONE row sort
            # with (window, value) keys (windows partition each row's
            # points — S independent sorts, not a global [S*N] lexsort),
            # then interpolate K midpoint ranks per cell.
            from jax import lax
            wkey = jnp.where(valid, win.astype(jnp.int32), w)
            svals = jnp.where(valid, vf, jnp.inf)
            _, sorted_rows = lax.sort((wkey, svals), dimension=1,
                                      num_keys=2)
            row_starts = jnp.concatenate(
                [jnp.zeros((s, 1), jnp.int64),
                 jnp.cumsum(cnt, axis=1)], axis=1)[:, :-1]   # [S, W]
            out["q"] = _rank_grid(sorted_rows, row_starts, cnt) \
                .astype(jnp.float32)
    return out


def _rank_grid(sorted_rows, starts, cnt, k: int = SKETCH_K):
    """Exact K-point equi-rank grid per cell from row-sorted runs.

    sorted_rows[S, N] ascending within each (series, window) run (cell
    (s, w) occupies columns [starts[s, w], starts[s, w] + cnt[s, w]);
    non-members +inf past every run).  Returns q[S, W, k]: value at
    fractional rank (j+0.5)/k of each cell via linear interpolation
    between adjacent order statistics; empty cells yield zeros (their
    count is zero, so merges ignore them).
    """
    s, w = cnt.shape
    cf = cnt.astype(jnp.float64)[:, :, None]
    # fractional 0-based rank of target j: (j+0.5)/k * cnt - 0.5
    fr = (jnp.arange(k, dtype=jnp.float64)[None, None, :] + 0.5) / k \
        * cf - 0.5
    fr = jnp.clip(fr, 0.0, jnp.maximum(cf - 1.0, 0.0))
    lo = jnp.floor(fr)
    frac = fr - lo
    top = sorted_rows.shape[1] - 1
    base = starts[:, :, None].astype(jnp.int64)
    i_lo = jnp.clip(base + lo.astype(jnp.int64), 0, top)
    i_hi = jnp.clip(base + lo.astype(jnp.int64) + 1, 0, top)
    # never read past the cell's own run
    last = base + jnp.maximum(cnt[:, :, None].astype(jnp.int64) - 1, 0)
    i_hi = jnp.minimum(i_hi, last)
    v_lo = jnp.take_along_axis(sorted_rows, i_lo.reshape(s, w * k),
                               axis=1).reshape(s, w, k)
    v_hi = jnp.take_along_axis(sorted_rows, i_hi.reshape(s, w * k),
                               axis=1).reshape(s, w, k)
    q = v_lo + frac * (v_hi - v_lo)
    return jnp.where(cnt[:, :, None] > 0, q, 0.0)


def _interp_rows(t, xp, fp):
    """Row-wise linear interpolation, inf-safe.

    Unlike jnp.interp, equal-value brackets return the endpoint instead of
    computing a 0 * (fp_hi - fp_lo) slope — inf - inf would poison grids
    carrying legitimate infinite data values.  t[C, K], xp/fp[C, X].
    """
    x = xp.shape[1]
    idx = jax.vmap(lambda tr, xr: jnp.searchsorted(xr, tr, side="left"))(
        t, xp)
    lo = jnp.clip(idx - 1, 0, x - 1)
    hi = jnp.clip(idx, 0, x - 1)
    x_lo = jnp.take_along_axis(xp, lo, axis=1)
    x_hi = jnp.take_along_axis(xp, hi, axis=1)
    f_lo = jnp.take_along_axis(fp, lo, axis=1)
    f_hi = jnp.take_along_axis(fp, hi, axis=1)
    dx = x_hi - x_lo
    frac = jnp.where(dx > 0, (t - x_lo) / jnp.where(dx > 0, dx, 1.0), 0.0)
    same = (f_lo == f_hi) | (dx <= 0)
    return jnp.where(same, f_lo, f_lo + frac * (f_hi - f_lo))


def _merge_sketch(q1, n1, q2, n2, k: int = SKETCH_K):
    """Weighted merge of two per-cell equi-rank summaries -> one K-grid.

    Each summary point carries weight n/K at its midpoint rank; the merged
    grid re-reads the mixture's cumulative weight at the K new midpoint
    targets.  One compaction moves any quantile's rank by <= 1/(2K) of the
    cell population — the documented per-merge error bound.
    q1/q2: [C, K]; n1/n2: [C].  Returns [C, K].
    """
    nf1 = n1.astype(jnp.float64)[:, None]
    nf2 = n2.astype(jnp.float64)[:, None]
    v = jnp.concatenate([q1, q2], axis=1)                    # [C, 2K]
    wt = jnp.concatenate([jnp.broadcast_to(nf1 / k, q1.shape),
                          jnp.broadcast_to(nf2 / k, q2.shape)], axis=1)
    # Zero-weight points (an empty side) must not perturb interpolation:
    # sort them last via an inf key, then REPLACE them with the row's max
    # carried value — their cum ranks are flat at the total, so any target
    # interpolating into that region reads the max instead of poisoning
    # the grid (a 0-clamp would break sortedness and decay every
    # subsequent merge).  A sentinel FLAG (not isfinite) distinguishes
    # them from legitimate +inf data values, which must survive so the
    # streamed and exact paths agree on inf-bearing series.
    sentinel = wt <= 0
    key = jnp.where(sentinel, jnp.inf, v)
    order = jnp.argsort(key, axis=1)
    v = jnp.take_along_axis(v, order, axis=1)
    wt = jnp.take_along_axis(wt, order, axis=1)
    sentinel = jnp.take_along_axis(sentinel, order, axis=1)
    vmax = jnp.max(jnp.where(sentinel, -jnp.inf, v), axis=1, keepdims=True)
    v = jnp.where(sentinel, vmax, v)
    cum = jnp.cumsum(wt, axis=1) - 0.5 * wt                  # midpoint ranks
    total = nf1 + nf2
    targets = (jnp.arange(k, dtype=jnp.float64)[None, :] + 0.5) / k * total
    merged = _interp_rows(targets, cum, v)
    both_zero = (n1 + n2) <= 0
    return jnp.where(both_zero[:, None], 0.0, merged).astype(q1.dtype)


def sketch_quantile(q, n, pct):
    """Estimate the pct-quantile (0-100) from summaries q[..., K], n[...].

    Linear interpolation on the midpoint-rank grid (R-7-flavored); the
    ep*r3/r7 estimator distinction is below the sketch's rank error and is
    deliberately collapsed here (documented approximation).
    """
    k = q.shape[-1]
    nf = jnp.maximum(n.astype(jnp.float64), 1.0)
    lead = q.shape[:-1]
    qs = q.reshape(-1, k)
    nfs = nf.reshape(-1, 1)
    mid = (jnp.arange(k, dtype=jnp.float64)[None, :] + 0.5) / k * nfs
    target = jnp.asarray(pct, jnp.float64) / 100.0 * nfs[:, 0]
    out = _interp_rows(target[:, None], mid, qs)[:, 0]
    return out.reshape(lead)


def _merge(state: dict, chunk: dict) -> dict:
    """Associative merge of two moment sets, per present lane (Chan et al.
    for m2)."""
    n1, n2 = state["n"], chunk["n"]
    n = n1 + n2
    had = n1 > 0
    got = n2 > 0
    merged = {"n": n}
    if "total" in state:
        t1, t2 = state["total"], chunk["total"]
        merged["total"] = t1 + t2
        if "m2" in state:
            safe_n = jnp.maximum(n, 1).astype(jnp.float64)
            nf1 = n1.astype(jnp.float64)
            nf2 = n2.astype(jnp.float64)
            # delta = mean2 - mean1 with empty sides contributing zero.
            mean1 = t1 / jnp.maximum(nf1, 1.0)
            mean2 = t2 / jnp.maximum(nf2, 1.0)
            delta = jnp.where(had & got, mean2 - mean1, 0.0)
            merged["m2"] = (state["m2"] + chunk["m2"]
                            + delta * delta * nf1 * nf2 / safe_n)
    if "lo" in state:
        merged["lo"] = jnp.minimum(state["lo"], chunk["lo"])
    if "hi" in state:
        merged["hi"] = jnp.maximum(state["hi"], chunk["hi"])
    # Chunks arrive in time order: first sticks, last overwrites.
    if "first" in state:
        merged["first"] = jnp.where(had, state["first"], chunk["first"])
    if "last" in state:
        merged["last"] = jnp.where(got, chunk["last"], state["last"])
    if "prod" in state:
        merged["prod"] = state["prod"] * chunk["prod"]
    if "q" in state:
        s, w, k = state["q"].shape
        merged["q"] = _merge_sketch(
            state["q"].reshape(-1, k), n1.reshape(-1),
            chunk["q"].reshape(-1, k), n2.reshape(-1)).reshape(s, w, k)
    if "oob" in state:
        merged["oob"] = state["oob"] + chunk.get("oob", 0)
    return merged


def _update(spec: WindowSpec, state: dict, ts, val, mask, wargs: dict):
    lanes = frozenset(state) & _ALL_LANES
    return _merge(state, _chunk_moments(ts, val, mask, spec, wargs,
                                        lanes=lanes,
                                        with_sketch="q" in state))


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool, wargs.first[] i64
# shape: wargs.nwin[] i32
def _update_sliced(spec: WindowSpec, wc: int, state: dict, ts, val, mask,
                   wargs: dict, w0):
    """Fold a chunk whose windows live in [w0, w0 + wc) of the grid.

    The full-grid update computes and merges [S, W] moment grids PER
    CHUNK — for wider-than-data streams (BASELINE config 2: an 8.4M-pt
    chunk against a 721k-window grid) that is O(S*W) state traffic and a
    92M-segment scatter per chunk, which is where the measured
    4.7s/chunk went (chip, r04b).  A time-ordered chunk only ever
    touches a contiguous window range, so: compute the chunk's moments
    on a LOCAL wc-window grid (same kernels, wc static), merge them into
    the state's [w0, w0+wc) slice, and write the slice back —
    O(S*wc + points) per chunk, W-independent.

    w0 is caller-declared (the planner/bench know each chunk's time
    range on the host); valid points OUTSIDE the declared slice are
    counted into state["oob"] instead of being silently dropped, so a
    wrong w0 is detectable (StreamAccumulator.oob_count()).  Fixed
    grids only.
    """
    from jax import lax

    if spec.kind != "fixed":
        raise ValueError("sliced streaming updates require a fixed grid")
    w_total = spec.count
    lanes = frozenset(state) & _ALL_LANES
    w0 = jnp.clip(jnp.asarray(w0, jnp.int64), 0, max(w_total - wc, 0))

    spec_l = WindowSpec("fixed", wc, spec.interval_ms)
    wargs_l = dict(wargs)
    wargs_l["first"] = wargs["first"] + w0 * spec.interval_ms
    wargs_l["nwin"] = jnp.clip(
        wargs["nwin"] - w0.astype(jnp.int32), 0, wc).astype(jnp.int32)
    chunk = _chunk_moments(ts, val, mask, spec_l, wargs_l, lanes=lanes,
                           with_sketch="q" in state)

    # slice-merge: every lane is a per-cell associative merge, so merging
    # the slice equals merging the full grid (cells outside the slice
    # receive only identity contributions from this chunk)
    cur = {}
    for k in state:
        if k == "oob":
            continue
        if k == "q":
            s, _, kq = state["q"].shape
            cur["q"] = lax.dynamic_slice(state["q"], (0, w0, 0),
                                         (s, wc, kq))
        else:
            s = state[k].shape[0]
            cur[k] = lax.dynamic_slice(state[k], (0, w0), (s, wc))
    merged = _merge(cur, chunk)
    new_state = dict(state)
    for k, v in merged.items():
        starts = (0, w0, 0) if k == "q" else (0, w0)
        new_state[k] = lax.dynamic_update_slice(state[k], v, starts)

    # audit: valid in-grid points the declared slice missed.  No
    # per-point division: in-grid membership is a timestamp range
    # compare, and the points the slice DID fold are exactly the live
    # cells of the local count lane the kernels already computed.
    ok = mask & ~jnp.isnan(val.astype(jnp.float64))
    tsa = _absolute_ts(ts, wargs)
    lo = wargs["first"]
    hi = lo + wargs["nwin"].astype(jnp.int64) * spec.interval_ms
    in_grid_total = jnp.sum(ok & (tsa >= lo) & (tsa < hi))
    live_l = jnp.arange(wc, dtype=jnp.int32)[None, :] < wargs_l["nwin"]
    folded = jnp.sum(jnp.where(live_l, chunk["n"], 0))
    new_state["oob"] = state["oob"] + (in_grid_total - folded)
    return new_state


# State buffers are DONATED: the accumulator grid can reach GBs (config 2:
# [128, 2^20] x 4 lanes ~ 3.5 GB), and without donation every queued async
# update holds old state + chunk moments + new state — the r3 chip run
# crashed the TPU worker exactly there.  Donation lets XLA alias the
# state in/out buffers so the peak stays ~one state + one chunk.  The
# caller never touches the pre-update state again (StreamAccumulator
# replaces self.state at enqueue).
_jitted_update = jax.jit(_update, static_argnums=0, donate_argnums=1)
_jitted_update_sliced = jax.jit(_update_sliced, static_argnums=(0, 1),
                                donate_argnums=2)


def _finish(spec: WindowSpec, ds_function: str, fill_policy: str,
            state: dict, wargs: dict, fill_value):
    """Final per-series downsampled grid from accumulated moments."""
    missing = LANES_FOR.get(ds_function, frozenset()) - frozenset(state)
    if missing:
        raise KeyError(
            "accumulator lacks lane(s) %s for %s — create it with "
            "lanes=lanes_for([...]) covering every finish function"
            % (sorted(missing), ds_function))
    n = state["n"]
    safe = jnp.maximum(n, 1)
    if ds_function in ("sum", "zimsum", "pfsum"):
        out = state["total"]
    elif ds_function == "count":
        out = n.astype(jnp.float64)
    elif ds_function == "avg":
        out = state["total"] / safe
    elif ds_function == "squareSum":
        # sumsq = M2 + total^2/n (exact algebraic identity).
        out = state["m2"] + state["total"] * state["total"] / safe
    elif ds_function == "dev":
        out = jnp.where(n >= 2, jnp.sqrt(state["m2"]
                                         / jnp.maximum(n - 1, 1)), 0.0)
    elif ds_function in ("min", "mimmin"):
        out = state["lo"]
    elif ds_function in ("max", "mimmax"):
        out = state["hi"]
    elif ds_function == "first":
        out = state["first"]
    elif ds_function == "last":
        out = state["last"]
    elif ds_function == "diff":
        out = jnp.where(n >= 2, state["last"] - state["first"], 0.0)
    elif ds_function == "mult":
        out = state["prod"]
    elif "q" in state and is_sketch_ds(ds_function):
        # Approximate (rank error ~chunks/(2K), see module docstring);
        # median uses the 50th pct of the summary rather than the exact
        # upper-median convention — the gap is below the sketch error.
        if ds_function == "median":
            pct = 50.0
        else:
            from opentsdb_tpu.ops.downsample import parse_percentile_name
            pct, _est = parse_percentile_name(ds_function)
        out = sketch_quantile(state["q"], n, pct)
    else:
        raise KeyError("Downsample function does not stream: " + ds_function)
    w = spec.count
    live = jnp.arange(w, dtype=jnp.int32)[None, :] < wargs["nwin"]
    out_mask = (n > 0) & live
    out, out_mask = apply_fill(out, out_mask, live, fill_policy, fill_value,
                               jnp.float64)
    wts = window_timestamps(spec, wargs)
    return wts, out, out_mask


_jitted_finish = jax.jit(_finish, static_argnums=(0, 1, 2))


def quantize_window_slice(window_slice, spec: WindowSpec):
    """Static sliced-update width from a requested chunk window span.

    Quantized up for jit-cache stability across similar streams, but
    gently: full pow2 padding would double the slice (and every
    per-chunk fold) at just-past-a-power shapes.  None when slicing
    cannot help (non-fixed grid, or the slice would cover the grid)."""
    if window_slice is None or spec.kind != "fixed":
        return None
    ws = max(int(window_slice), 1)
    bucket = 1 << max(6, ws.bit_length() - 3)
    wc = min(-(-ws // bucket) * bucket, spec.count)
    return None if wc >= spec.count else wc


@dataclass
class StreamAccumulator:
    """Device-resident per-(series, window) moment state fed chunk by chunk.

    Usage::

        acc = StreamAccumulator.create(num_series, window_spec, wargs)
        for chunk in chunks:            # increasing time order
            acc.update(ts, val, mask)   # [S, n_chunk] padded batches
        wts, values, mask = acc.finish("avg")
    """
    spec: WindowSpec
    wargs: dict
    state: dict
    window_slice: int | None = None

    @staticmethod
    def create(num_series: int, spec: WindowSpec, wargs: dict,
               sketch: bool = False,
               lanes: frozenset | None = None,
               window_slice: int | None = None) -> "StreamAccumulator":
        """`sketch=True` adds the [S, W, K] quantile-summary lane so
        rank-based downsample functions can finish (approximate).
        `lanes` (from lanes_for()) restricts state to what the finish
        functions need — sum/avg/count stream scatter-free.
        `window_slice` (fixed grids only) enables O(S*wc)-per-chunk
        sliced updates for wider-than-data streams: the static count of
        windows any single chunk can span; callers then pass each
        chunk's first window index to update(w0=...)."""
        wc = quantize_window_slice(window_slice, spec)
        return StreamAccumulator(spec, wargs,
                                 _zero_state(num_series, spec.count,
                                             sketch, lanes,
                                             with_oob=wc is not None),
                                 wc)

    def update(self, ts, val, mask, w0: int | None = None) -> tuple:
        """Fold one [S, n] chunk in (async — returns at enqueue).

        `w0`: index of the first grid window this chunk's points can
        touch (host-known for time-ordered chunking).  With a
        window_slice-enabled accumulator this routes to the sliced
        update — the chunk must fit in [w0, w0 + window_slice); points
        outside are counted in oob_count() rather than folded.

        Host arrays are uploaded here; the three device arrays are
        handed back, so a caller that reuses its host buffers can wait
        for the transfers (storage/chunk_pack.py)."""
        ts, val, mask = jnp.asarray(ts), jnp.asarray(val), jnp.asarray(mask)
        if w0 is not None and self.window_slice is not None:
            self.state = _jitted_update_sliced(
                self.spec, self.window_slice, self.state, ts, val, mask,
                self.wargs, w0)
        else:
            self.state = _jitted_update(self.spec, self.state, ts, val,
                                        mask, self.wargs)
        return ts, val, mask

    def oob_count(self) -> int:
        """Valid points sliced updates missed (w0 contract violations);
        0 in correct use.  Host sync."""
        if "oob" not in self.state:
            return 0
        return int(np.asarray(self.state["oob"]))

    def finish(self, ds_function: str, fill_policy: str = FILL_NONE,
               fill_value: float = 0.0):
        """(window_ts[W], values[S, W], mask[S, W]) — the downsample output."""
        return _jitted_finish(self.spec, ds_function, fill_policy,
                              self.state, self.wargs, fill_value)

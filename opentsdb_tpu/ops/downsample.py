"""Windowed downsampling as segment reductions over [series, time] batches.

Reference behavior: /root/reference/src/core/Downsampler.java (ValuesInInterval
:292 — per-interval reduce with runDouble semantics, interval start as the
output timestamp :437-449, epoch-aligned ts - ts % interval :452),
DownsamplingSpecification.java (spec grammar "1h-avg[-fill][c]"), and
FillingDownsampler.java (emit empty intervals under non-NONE fill policies).
Downsampled values are always doubles (Downsampler.java:257).

TPU-first design: instead of an iterator per span, every series row maps its
timestamps to window ids; one flattened `segment_sum`-family reduction
computes all (series x window) cells at once.

Compile-stability: only the window *count* and interval are static — the
window origin (query start), calendar edges, and live window count are traced
operands, so a dashboard re-issuing the same query over a sliding time range
hits the jit cache.  Calendar windows arrive as a precomputed edge array
(host computes timezone math, device does searchsorted) — SURVEY.md §7 hard
part (d).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from opentsdb_tpu.ops.percentile import EST_LEGACY, EST_R3, EST_R7

# Fill policies (FillPolicy.java:22-27).
FILL_NONE = "none"
FILL_ZERO = "zero"
FILL_NAN = "nan"
FILL_NULL = "null"     # NaN internally; serializer emits nulls
FILL_SCALAR = "scalar"

_I64_MAX = np.iinfo(np.int64).max


def require_x64() -> None:
    """Refuse to plan int64 window math when x64 is disabled.

    The window kernels build jnp.int64 timestamp grids; with
    jax_enable_x64 off JAX silently lowers them to int32 and every ms
    timestamp past 2^31 (≈ Jan 1970 + 25 days) truncates — queries
    return wrong windows with no error.  The ops package __init__
    enables x64 process-wide and TSDB construction re-asserts it
    (tsd.tpu.precision.x64); this guard is the backstop for embedders
    that flip the flag afterwards.  Called from the host-side window
    planners (one attribute read per query plan, nothing on the device
    path)."""
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "jax_enable_x64 is disabled: int64 ms-timestamp window math "
            "would silently truncate to int32.  Re-enable x64 (or set "
            "tsd.tpu.precision.x64=true, the default, and construct the "
            "TSDB after any config that disables it).")


def pad_pow2(n: int, floor: int = 8) -> int:
    out = floor
    while out < n:
        out *= 2
    return out


@dataclass(frozen=True)
class WindowSpec:
    """Static window shape: kind + padded count (+ interval for fixed grids).

    The traced counterpart is a dict of device scalars/arrays built by the
    host-side planners below; together they describe the same windows the
    reference's ValuesInInterval walked.
    """
    kind: str           # "fixed" | "edges" | "all"
    count: int          # padded number of windows, static
    interval_ms: int = 0  # fixed grids only


@dataclass(frozen=True)
class FixedWindows:
    """Host plan: epoch-aligned fixed-interval windows over [start, end]."""
    interval_ms: int
    first_window_ms: int
    count: int  # real (unpadded) count

    @staticmethod
    def for_range(start_ms: int, end_ms: int, interval_ms: int) -> "FixedWindows":
        first = start_ms - (start_ms % interval_ms)
        last = end_ms - (end_ms % interval_ms)
        count = int((last - first) // interval_ms) + 1
        return FixedWindows(interval_ms, first, count)

    def split(self, pad: bool = True) -> tuple[WindowSpec, dict]:
        require_x64()
        padded = pad_pow2(self.count) if pad else self.count
        return (WindowSpec("fixed", padded, self.interval_ms),
                {"first": jnp.asarray(self.first_window_ms, jnp.int64),
                 "nwin": jnp.asarray(self.count, jnp.int32)})


@dataclass(frozen=True)
class EdgeWindows:
    """Host plan: calendar windows from precomputed edges[W+1]."""
    edges: tuple  # ints; window w spans [edges[w], edges[w+1])

    @property
    def count(self) -> int:
        return len(self.edges) - 1

    def split(self, pad: bool = True) -> tuple[WindowSpec, dict]:
        require_x64()
        w = self.count
        padded = pad_pow2(w) if pad else w
        edges = np.full(padded + 1, _I64_MAX, dtype=np.int64)
        edges[:w + 1] = self.edges
        return (WindowSpec("edges", padded),
                {"edges": jnp.asarray(edges),
                 "nwin": jnp.asarray(w, jnp.int32)})


@dataclass(frozen=True)
class AllWindow:
    """Host plan: the "0all" run-all window spanning [query_start, query_end)."""
    query_start_ms: int
    query_end_ms: int

    @property
    def count(self) -> int:
        return 1

    def split(self, pad: bool = True) -> tuple[WindowSpec, dict]:
        require_x64()
        return (WindowSpec("all", 1),
                {"qstart": jnp.asarray(self.query_start_ms, jnp.int64),
                 "qend": jnp.asarray(self.query_end_ms, jnp.int64),
                 "nwin": jnp.asarray(1, jnp.int32)})


# shape: ts[S,N] any, wargs.ts_base[] i64 -> [S,N] i64
def _absolute_ts(ts, wargs: dict):
    """Reconstruct absolute int64 timestamps from a pre-compacted batch.

    Device-cache hits can arrive as int32 offsets from wargs["ts_base"]
    (the per-point compaction pass moved into the cache's gather
    dispatch); paths that need absolute time (the segment fallback, edge
    grids) lift back to int64 here.  int64 batches pass through.
    """
    if ts.dtype == jnp.int32 and "ts_base" in wargs:
        return ts.astype(jnp.int64) + wargs["ts_base"]
    return ts


# shape: ts[S,N] any, wargs.first[] i64, wargs.edges[*] i64
# shape: wargs.qstart[] i64, wargs.qend[] i64 -> [S,N] i64
def window_ids(ts, spec: WindowSpec, wargs: dict):
    """Window index per point; negative / >= count means outside any window."""
    ts = _absolute_ts(ts, wargs)
    if spec.kind == "fixed":
        return ((ts - wargs["first"]) // spec.interval_ms).astype(jnp.int64)
    if spec.kind == "edges":
        edges = wargs["edges"]
        return jnp.searchsorted(edges, ts, side="right").astype(jnp.int64) - 1
    if spec.kind == "all":
        inside = (ts >= wargs["qstart"]) & (ts < wargs["qend"])
        return jnp.where(inside, 0, -1).astype(jnp.int64)
    raise ValueError("Unknown window kind: " + spec.kind)


# shape: wargs.first[] i64, wargs.edges[*] i64, wargs.qstart[] i64 -> [W] i64
def window_timestamps(spec: WindowSpec, wargs: dict):
    """Representative (start-of-interval) timestamp per window [count]."""
    if spec.kind == "fixed":
        return wargs["first"] + jnp.arange(spec.count, dtype=jnp.int64) \
            * spec.interval_ms
    if spec.kind == "edges":
        return wargs["edges"][:spec.count]
    if spec.kind == "all":
        return wargs["qstart"][None]
    raise ValueError("Unknown window kind: " + spec.kind)


# Downsample functions served by the sorted prefix-sum fast path (additive
# moments only; rank/order functions keep segment reductions).
PREFIX_AGGS = frozenset(
    {"sum", "zimsum", "pfsum", "count", "avg", "squareSum", "dev"})

# min/max ride a scatter-free segmented reset-scan (sorted rows make each
# window a contiguous run; an associative_scan that resets at run starts
# replaces the serializing segment scatter): form "scan".  "segment"
# keeps the scatter — faster on CPU where scatters are cheap.  "subblock"
# removes the full-length scan too (the subblock-sum idea applied to
# extremes): 32-point sub-block reduces, a reset-scan over the [S, N/32]
# sub-block extremes for each window's interior, and 32-wide masked
# reduces over the two boundary sub-blocks.  _effective_extreme_mode
# picks from platform and shape.
EXTREME_AGGS = frozenset({"min", "mimmin", "max", "mimmax"})


# shape: wargs.first[] i64, wargs.edges[*] i64 -> [W1] i64
def window_edges(ts_dtype, spec: WindowSpec, wargs: dict):
    """Edge timestamps e[W+1]; window w spans [e[w], e[w+1])."""
    if spec.kind == "fixed":
        return wargs["first"] + jnp.arange(
            spec.count + 1, dtype=jnp.int64) * spec.interval_ms
    if spec.kind == "edges":
        return wargs["edges"]
    if spec.kind == "all":
        return jnp.stack([wargs["qstart"], wargs["qend"]])
    raise ValueError("Unknown window kind: " + spec.kind)


# Prefix-scan forms of the hot path.  "flat" = one cumsum over the full
# time axis.  "subblock" = no full-length scan at all: exact f64 sums of
# 32-point sub-blocks (a tree reduce — one cheap pass), a cumsum over
# the [S, N/32] sub-block sums (1/32 the scan work), and per-edge
# remainders as 32-wide masked dots.  "subblock2" = the same with
# within-block prefixes and one element gather per edge.  Rationale
# (per-stage attribution from an earlier chip session, not re-measured
# on this installation): a full-length f64 cumsum cost 95ms/67M pts on
# the chip while an f64 elementwise pass cost 14ms — the emulated-f64
# SCAN is the bottleneck, not the data traffic, so the sub-block forms
# do 1/32 of it.  _effective_scan_mode picks from platform and shape.
_SUB_K = 32      # subblock scan / hier search granule (power of two)

_I32_BIG = np.int64(2**31 - 2)
# Pad sentinel for int32 batches — the exact value the device cache's
# ts_base gather writes (storage.device_cache.I32_PAD_TS mirrors this;
# a parity test pins the pair).  Clean-batch detection compares against
# it and pad sorting relies on it exceeding every re-based edge.
_I32_PAD = np.int32(2**31 - 2)


# Edge-position search forms.  "scan" = jnp.searchsorted's binary
# search: log2(N) rounds of gathers — TPU gathers serialize, so for the
# [S, W+1]-edges-into-[S, N] search this is a chain of ~17 gather passes.
# "compare_all" = one broadcasted compare + sum-reduce (idx[s, w] =
# #points < edge): O(N*W) VPU compares that XLA fuses into a streaming
# reduction over W-tiles — no gathers at all.  "hier" = two-level
# compare_all: count sub-block FIRST timestamps below each edge (rows are
# time-sorted, so every earlier sub-block is entirely below the edge),
# then resolve the one boundary sub-block with a 32-wide compare — the
# compare work drops from O(N*W) to O(N*W/32 + 32*W).  r3/r4 chip data:
# scan 182ms, compare_all ~116ms for the 65536x513 headline search.
# _effective_search_mode picks from platform and shape.


def _edge_prefix_builder(s: int, n: int, idx):
    """Returns windowed(data): per-window sums via prefix evaluation at the
    searched edge positions idx[S, W+1] (exclusive prefixes differenced):
    materialize cumsum[S, N+1], gather at idx (scan form "flat")."""
    def windowed(data):
        csum = jnp.concatenate(
            [jnp.zeros((s, 1), data.dtype),
             jnp.cumsum(data, axis=1)], axis=1)
        at = jnp.take_along_axis(csum, idx, axis=1)
        return at[:, 1:] - at[:, :-1]
    return windowed


def _edge_subblock_builder(s: int, n: int, idx):
    """windowed(data) with NO full-length scan (scan mode "subblock").

    prefix(p) decomposes at the 32-point sub-block containing p: the sum
    of every earlier sub-block (an exact f64 tree reduce + a cumsum over
    [S, N/32] sub-block sums — 1/32 of the flat form's scan work) plus a
    32-wide masked dot over the boundary sub-block, gathered as ONE
    contiguous [1, K] slice per edge (vector loads, not 32 scalar
    gathers).  Chip rationale: the emulated-f64 full-length cumsum costs
    ~7x an elementwise f64 pass (r4 chip session) — this form
    keeps the same f64 accumulation contract with 1/32 of the scan.
    """
    k = _SUB_K
    nb = n // k
    blk = idx // k                     # [S, W+1] boundary sub-block
    off = idx - blk * k                # position within it
    safe_blk = jnp.clip(blk, 0, nb - 1)
    lanes = jnp.arange(k, dtype=off.dtype)

    def windowed(data):
        d3 = data.reshape(s, nb, k)
        ssum = d3.sum(axis=2)                                   # [S, nb]
        scum = jnp.concatenate(
            [jnp.zeros((s, 1), data.dtype), jnp.cumsum(ssum, axis=1)],
            axis=1)                                             # [S, nb+1]
        base = jnp.take_along_axis(scum, blk, axis=1)
        bvals = jnp.take_along_axis(
            d3, safe_blk[:, :, None], axis=1)                   # [S, W+1, K]
        # blk == nb (edge past every point) has off == 0, so the masked
        # dot over the clipped gather contributes nothing there.
        rem = jnp.where(lanes[None, None, :] < off[:, :, None],
                        bvals, 0).sum(axis=2)
        at = base + rem
        return at[:, 1:] - at[:, :-1]
    return windowed


def _edge_subblock2_builder(s: int, n: int, idx):
    """subblock variant: within-block inclusive prefixes + ONE scalar
    gather per edge (scan mode "subblock2").

    Same decomposition as _edge_subblock_builder, but the boundary
    remainder is read from a precomputed within-block prefix
    (cumsum along the K axis — a depth-log2(K) scan over the full data,
    cheap and parallel) with a single element gather per edge, instead
    of gathering a [*, K] lane per edge and masked-dotting it.  Trades
    one extra full-size vector pass for 1/K of the per-edge gather
    volume and no [S, W+1, K] intermediate — so it has no
    _subblock_edges_fit constraint.  The chip race decides which wins.
    """
    k = _SUB_K
    nb = n // k
    blk = idx // k                     # [S, W+1] boundary sub-block
    off = idx - blk * k                # position within it
    safe_blk = jnp.clip(blk, 0, nb - 1)

    def windowed(data):
        d3 = data.reshape(s, nb, k)
        prefix3 = jnp.cumsum(d3, axis=2)            # within-block incl.
        ssum = prefix3[:, :, -1]                    # block sums for free
        scum = jnp.concatenate(
            [jnp.zeros((s, 1), data.dtype), jnp.cumsum(ssum, axis=1)],
            axis=1)                                             # [S, nb+1]
        base = jnp.take_along_axis(scum, blk, axis=1)
        prefix = prefix3.reshape(s, n)
        # off == 0 (edge at a block boundary, incl. blk == nb past every
        # point) contributes no remainder; otherwise prefix[blk*K+off-1]
        pos = jnp.clip(safe_blk * k + off - 1, 0, n - 1)
        rem = jnp.where(off > 0,
                        jnp.take_along_axis(prefix, pos, axis=1), 0)
        at = base + rem
        return at[:, 1:] - at[:, :-1]
    return windowed


def precompact_base(spec: WindowSpec, first_window_ms) -> int | None:
    """The int32 pre-compaction base for a batch source, or None.

    When a fixed grid provably spans < 2^31 ms, batch builders (the
    device cache's gather) may deliver timestamps as int32 offsets from
    this base — the per-point compaction pass then disappears from the
    query dispatch entirely (r4 chip attribution: 74ms of the headline
    dispatch was the ts - first sub+clip+cast over [S, N] int64).
    """
    if (spec.kind == "fixed" and first_window_ms is not None
            and (spec.count + 1) * spec.interval_ms < 2**31 - 2):
        return int(first_window_ms)
    return None


# shape: ts[S,N] any, wargs.first[] i64, wargs.ts_base[] i64
def _compact_ts(ts, spec: WindowSpec, wargs: dict):
    """(ts', edges') for the prefix path: int32 ms offsets when
    the whole fixed-window grid provably spans < 2^31 ms.

    TPUs have no native 64-bit integer ALU — every compare in the
    binary search and every window-id division runs emulated on int64.
    Fixed grids know their span statically (count * interval); offsets
    from the traced window origin fit int32, and clipping keeps the
    int64-max padding timestamps sorted (they land beyond the last edge,
    exactly like before).  Calendar/all grids keep int64.

    Pre-compacted batches (int32 offsets from wargs["ts_base"], built by
    the device cache's gather dispatch) skip the per-point pass: only
    the [W+1] edge vector is re-based here.
    """
    if ts.dtype == jnp.int32 and "ts_base" in wargs:
        edges64 = window_edges(jnp.int64, spec, wargs)
        edges32 = jnp.clip(edges64 - wargs["ts_base"],
                           -_I32_BIG, _I32_BIG).astype(jnp.int32)
        return ts, edges32
    edges64 = window_edges(ts.dtype, spec, wargs)
    if spec.kind != "fixed" or \
            (spec.count + 1) * spec.interval_ms >= 2**31 - 2:
        return ts, edges64
    first = wargs["first"]
    ts32 = jnp.clip(ts - first, -_I32_BIG, _I32_BIG).astype(jnp.int32)
    edges32 = jnp.clip(edges64 - first, -_I32_BIG, _I32_BIG).astype(jnp.int32)
    return ts32, edges32


# shape: ts[S,N] any, val[S,N] f64, mask[S,N] bool -> ([S,W] f64, [S,W] any)
def _prefix_downsample(ts, val, mask, agg_name: str, spec: WindowSpec,
                       wargs: dict):
    """Scatter-free windowed moments for sorted rows.

    TPU scatters (`segment_sum`) serialize; for the additive-moment family
    the batch layout contract (rows time-sorted, pads at int64 max) lets
    window reductions run as exclusive prefix sums differenced at
    binary-searched window edges — dense vector work the VPU streams
    through.  Non-participating slots (masked or NaN) contribute zero to
    every cumulative sum, so correctness needs only ts-sortedness.

    Hot-path dtypes: timestamps compact to int32 offsets when the grid
    span allows (no 64-bit emulation in the search), counts accumulate in
    int32 (N < 2^31 per row); VALUES stay float64 — the reference's Java
    double accumulation is the numeric contract (Downsampler.java:257).

    Returns (out[S, W], count[S, W]).
    """
    w = spec.count
    vf, ok, cts, _idx, windowed, count = _window_scan_setup(ts, val, mask,
                                                            spec, wargs)
    v0 = jnp.where(ok, vf, 0)
    if agg_name == "count":
        return count.astype(vf.dtype), count
    total = windowed(v0)
    safe = jnp.maximum(count, 1)
    if agg_name in ("sum", "zimsum", "pfsum"):
        return total, count
    if agg_name == "avg":
        return total / safe, count
    if agg_name == "squareSum":
        return windowed(v0 * v0), count
    if agg_name == "dev":
        # Two-pass centered moment (matches the segment path's numerics):
        # per-point window mean via the same edge-search, then one more
        # prefix pass over the centered squares.
        mean = total / safe
        win = jnp.clip(_window_ids_fast(ts, cts, spec, wargs), 0, w - 1)
        mean_pp = jnp.take_along_axis(mean, win, axis=1)
        centered = jnp.where(ok, vf - mean_pp, 0)
        m2 = windowed(centered * centered)
        return jnp.where(count >= 2,
                         jnp.sqrt(m2 / jnp.maximum(count - 1, 1)),
                         0.0), count
    raise KeyError("No prefix-sum path for: " + agg_name)


# shape: ts[S,N] any, cts[S,N] any, wargs.first[] i64, wargs.ts_base[] i64 -> [S,N] any
def _window_ids_fast(ts, cts, spec: WindowSpec, wargs: dict):
    """Per-point window ids, preferring the compacted int32 timestamps.

    On fixed grids the id is a division; doing it on the int32 offsets
    (cts, already relative to the window origin when compacted — dtype
    is the compaction marker) avoids a full [S, N] pass of emulated
    int64 arithmetic.  Non-fixed grids keep the generic search.
    """
    if spec.kind == "fixed" and cts.dtype == jnp.int32:
        if ts.dtype == jnp.int32 and "ts_base" in wargs:
            # pre-compacted batch: cts is relative to ts_base, not to the
            # window origin — re-base with one int32 scalar subtract.
            # The i64 difference is clipped before narrowing: today's
            # callers derive ts_base FROM first (delta 0), but a caller
            # handing a stale base from another query's grid would
            # otherwise wrap silently and scatter points into random
            # windows; saturated deltas land everything out-of-range
            # instead, which the valid-window mask then drops.
            shift = jnp.clip(wargs["first"] - wargs["ts_base"],
                             -_I32_BIG, _I32_BIG).astype(jnp.int32)
            return (cts - shift) // jnp.int32(spec.interval_ms)
        return cts // jnp.int32(spec.interval_ms)
    return window_ids(ts, spec, wargs)


# Dense-vs-binary search crossover.  Per edge, compare_all costs N
# compares, hier N/32 compares, the binary search log2(N) serialized
# gathers; every form is linear in the edge count, so the decision is a
# RATIO of per-edge costs, independent of W.  The r4 chip attribution
# measured ~20ns/gather (scan: 182ms / 8.9M gathers) vs ~3.4ps/compare
# (compare_all: 116ms / 34e9) — a ~5900x gap; 4096 is the conservative
# round-down, placing the compare_all crossover just past the headline's
# N=65536 (where compare_all measured faster) and well before a
# streaming chunk's N=1M (config 2's W~10M grid: a dense search there
# burned the whole 2400s chip budget in r4).
_SEARCH_DEMOTE_RATIO = 4096

# Sub-block remainder forms (hier search, subblock scan/extreme) gather
# one [*, K] lane per edge/window — an [S, W, K] intermediate.  For the
# intended shapes W*K << N (headline: 513 edges x 32 = 2.4% of N); when
# a grid is wider than the data (streaming config 2: W ~ N*10), that
# intermediate EXCEEDS the batch itself and can OOM (a 0.01-scale CPU
# smoke hit a 283GB allocation).  Cap it at this multiple of the data.
_SUBBLOCK_EDGE_FACTOR = 4


def _subblock_edges_fit(n: int, w_edges: int) -> bool:
    return w_edges * _SUB_K <= _SUBBLOCK_EDGE_FACTOR * n


# compare_all's [N, W+1] per-row compare can MATERIALIZE when the
# backend does not fuse the reduce (measured: CPU at N=65536 x 16385
# edges attempted a multi-TB buffer).  Cap the per-row compare matrix;
# the headline shape (65536 x 514 = 34M cells) stays comfortably under.
_COMPARE_ALL_CELL_CAP = 1 << 27

# hier's sub-block-firsts compare is a [N/K, W+1] per-row matrix — 32x
# smaller than compare_all's, but it still materializes where the
# backend does not fuse the compare into its count.  Measured at the
# config-1 shape (N=1M, W=3501: 109M cells/row): 18x slower than the
# binary search on the host lane, and a scoped-vmem compile failure on
# the chip (r04b session, config 1 device lane).  The headline shape
# (2048 x 286 = 0.6M cells/row) sits two orders of magnitude under this
# cap; shapes above it take the binary search.
_HIER_CELL_CAP = 1 << 23


def _search_feasible(mode: str, n: int, w_edges: int) -> bool:
    """Hard feasibility for the dense search forms: memory caps on the
    compare intermediates and the per-edge compare-vs-gather cost ratio.
    Shapes outside these bounds take the binary scan — a wrong choice
    here is an OOM or a scoped-vmem compile failure, not a slowdown."""
    logn = max(int(np.ceil(np.log2(max(n, 2)))), 1)
    if mode == "compare_all":
        return (n <= _SEARCH_DEMOTE_RATIO * logn
                and n * w_edges <= _COMPARE_ALL_CELL_CAP)
    if mode == "hier":
        return (n % _SUB_K == 0 and n > _SUB_K
                and n // _SUB_K <= _SEARCH_DEMOTE_RATIO * logn
                and (n // _SUB_K) * w_edges <= _HIER_CELL_CAP
                and _subblock_edges_fit(n, w_edges))
    return True


def _search_candidates(n: int, w_edges: int) -> list[str]:
    return [m for m in ("scan", "compare_all", "hier")
            if _search_feasible(m, n, w_edges)]


def _effective_search_mode(s: int, n: int, w_edges: int,
                           platform: str | None = None) -> str:
    """The search form for this shape: the cheapest feasible one by the
    cost table (ops.costmodel).  The dense forms are ACCELERATOR
    winners: on the chip their compare+count fuses into vmem (r04b: hier
    0.416s vs scan 0.590s on the headline dispatch), but on CPU the
    backend materializes the compare matrix — measured 70x slower than
    the binary search at [64, 65536] x 514 edges, and 18x end-to-end on
    the config-1 host lane.  So any trace executing on CPU — the
    planner's small-query host lane, or a CPU-only process — takes the
    binary search.  `platform` defaults to the ambient execution
    platform; the planner's decision report passes its per-segment
    platform explicitly."""
    from opentsdb_tpu.ops.hostlane import execution_platform
    if platform is None:
        platform = execution_platform()
    if platform == "cpu":
        return "scan"
    from opentsdb_tpu.ops import costmodel
    return costmodel.choose_search(s, n, w_edges, platform,
                                   _search_candidates(n, w_edges))


def _scan_candidates(n: int, w_edges: int) -> list[str]:
    sub_ok = n % _SUB_K == 0 and n > _SUB_K
    cands = ["flat"]
    if sub_ok and _subblock_edges_fit(n, w_edges):
        cands.append("subblock")
    if sub_ok:
        cands.append("subblock2")
    return cands


def _effective_scan_mode(s: int, n: int, w_edges: int,
                         platform: str | None = None) -> str:
    """The prefix-scan form for this shape: the cheapest feasible one
    by the cost table (the sub-block forms need K-divisible rows;
    "subblock" additionally needs the [S, W, K] boundary intermediate
    to fit)."""
    cands = _scan_candidates(n, w_edges)
    if len(cands) == 1:
        return "flat"
    from opentsdb_tpu.ops.hostlane import execution_platform
    from opentsdb_tpu.ops import costmodel
    return costmodel.choose_scan(
        s, n, w_edges, platform or execution_platform(), cands)


def _extreme_candidates(n: int, w_padded: int) -> list[str]:
    sub_ok = (n % _SUB_K == 0 and n > _SUB_K
              and _subblock_edges_fit(n, w_padded + 1))
    return ["scan", "segment"] + (["subblock"] if sub_ok else [])


def _effective_extreme_mode(n: int, w_padded: int,
                            platform: str | None = None) -> str:
    """The min/max form for this shape: scan vs segment vs (when
    eligible) subblock, ranked by the cost table — one rule for the
    materialized and streaming paths (they must never drift)."""
    from opentsdb_tpu.ops.hostlane import execution_platform
    from opentsdb_tpu.ops import costmodel
    return costmodel.choose_extreme(
        1, n, w_padded + 1, platform or execution_platform(),
        _extreme_candidates(n, w_padded))


def search_decision(s: int, n: int, w_edges: int, platform: str) -> dict:
    """The edge-search decision for one dispatch shape, as the trace
    annotates it: chosen form and per-candidate predicted ms.
    Recomputes exactly what the kernel's trace-time
    `_effective_search_mode` picks for this platform."""
    from opentsdb_tpu.ops import costmodel
    return _decision_report(
        "search", _effective_search_mode(s, n, w_edges, platform),
        _search_candidates(n, w_edges),
        lambda m: costmodel.predict_search(m, s, n, w_edges, platform))


def scan_decision(s: int, n: int, w_edges: int, platform: str) -> dict:
    """The prefix-scan decision for one dispatch shape (see
    `search_decision`)."""
    from opentsdb_tpu.ops import costmodel
    return _decision_report(
        "scan", _effective_scan_mode(s, n, w_edges, platform),
        _scan_candidates(n, w_edges),
        lambda m: costmodel.predict_scan(m, s, n, w_edges, platform))


def extreme_decision(n: int, w_padded: int, platform: str) -> dict:
    """The min/max decision for one dispatch shape (see
    `search_decision`)."""
    from opentsdb_tpu.ops import costmodel
    return _decision_report(
        "extreme", _effective_extreme_mode(n, w_padded, platform),
        _extreme_candidates(n, w_padded),
        lambda m: costmodel.predict_extreme(m, 1, n, w_padded + 1,
                                            platform))


def _decision_report(axis: str, chosen: str, candidates: list[str],
                     predict) -> dict:
    """Shared decision-report shape (group_agg uses it too).
    `feasible` is False only if a form outside the feasible candidate
    set would dispatch — the choosers pick among the candidates, so
    that is unreachable, and the planner counts any violation
    (tsd.costmodel.infeasible)."""
    return {
        "axis": axis,
        "mode": chosen,
        "candidates": {m: round(predict(m) * 1e3, 4)
                       for m in candidates},
        "feasible": chosen in candidates,
    }


def _edge_search(cts, cedges):
    """idx[S, W+1] = per-row count of points strictly below each edge.

    "hier" exploits row sortedness at sub-block granularity: if a
    sub-block's FIRST timestamp is below the edge, every point of every
    EARLIER sub-block is too (each is <= that first) — so one compare+
    count over the [S, N/32] sub-block firsts locates the boundary
    sub-block, and a 32-wide compare over that one (contiguous) sub-block
    finishes the count.  O(N*W/32) compares vs compare_all's O(N*W) and
    scan's log2(N) serialized gather rounds.
    """
    s, n = cts.shape
    mode = _effective_search_mode(s, n, cedges.shape[0])
    if mode == "hier":
        k = _SUB_K
        nb = n // k
        c3 = cts.reshape(s, nb, k)
        firsts = c3[:, :, 0]                                     # [S, nb]
        nfull = jnp.sum(firsts[:, :, None] < cedges[None, None, :],
                        axis=1)                                  # [S, W+1]
        blk = jnp.maximum(nfull - 1, 0)     # boundary sub-block (nfull>0)
        bvals = jnp.take_along_axis(c3, blk[:, :, None], axis=1)
        rem = jnp.sum(bvals < cedges[None, :, None], axis=2)
        idx = blk * k + rem
        # int32 like searchsorted's result (n < 2^31): int64 here would
        # push the subblock builder's edge arithmetic onto emulated ALUs
        return jnp.where(nfull == 0, 0, idx).astype(jnp.int32)
    method = ("compare_all" if mode == "compare_all" else "scan")
    return jax.vmap(lambda row: jnp.searchsorted(
        row, cedges, side="left", method=method))(cts)


def _window_scan_setup(ts, val, mask, spec: WindowSpec, wargs: dict):
    """Shared preamble of the sorted-row window kernels: float view, valid
    mask, edge positions, the edge-prefix evaluator, and per-window counts.
    One definition — the prefix and extreme paths must never drift on the
    edge search or the int32 compaction."""
    s, n = ts.shape
    fdtype = val.dtype if jnp.issubdtype(val.dtype, jnp.floating) \
        else jnp.float64
    vf = val.astype(fdtype)
    ok = mask & ~jnp.isnan(vf)
    cts, cedges = _compact_ts(ts, spec, wargs)
    idx = _edge_search(cts, cedges)
    smode = _effective_scan_mode(s, n, cedges.shape[0])
    if smode == "subblock":
        windowed = _edge_subblock_builder(s, n, idx)
    elif smode == "subblock2":
        # no edges-fit constraint: the remainder reads a same-size
        # prefix array, never an [S, W, K] intermediate
        windowed = _edge_subblock2_builder(s, n, idx)
    else:
        windowed = _edge_prefix_builder(s, n, idx)
    # Per-window counts: for a CLEAN batch — every unmasked slot is a pad
    # (ts at the pad sentinel, beyond the last edge) and no masked value
    # is NaN — the edge positions already count exactly the participating
    # points, so count = diff(idx) and the dedicated int32 cumsum pass (a
    # full [S, N] scan + gather, as expensive as the value scan it sits
    # next to) is skipped.  Batches from build_batch / the device cache
    # are clean by construction; NaN data or exotic masks take the scan.
    # Pre-compacted int32 batches pad at the clip ceiling, not int64 max.
    pad_sentinel = _I32_PAD if ts.dtype == jnp.int32 else _I64_MAX
    clean = ~jnp.any(ok ^ (ts != pad_sentinel))
    count = jax.lax.cond(
        clean,
        lambda: (idx[:, 1:] - idx[:, :-1]).astype(jnp.int64),
        lambda: windowed(ok.astype(jnp.int32)).astype(jnp.int64))
    return vf, ok, cts, idx, windowed, count


def _extreme_downsample(ts, val, mask, spec: WindowSpec, wargs: dict,
                        want_min: bool, want_max: bool):
    """Scatter-free windowed min/max for sorted rows.

    Windows are contiguous runs in a time-sorted row, so the per-window
    extreme is a segmented scan: an inclusive associative scan of
    (value..., new-run flag) where a set flag resets the accumulation —
    the classic segmented-reduce combinator — evaluated by gathering the
    scan at each window's last position (idx[w+1]-1).  No scatter: TPU
    scatters serialize, which is why the additive family left them first
    (VERDICT r1 weak #1); this extends the scatter-free family to the
    extremes.  min and max share ONE scan when both are wanted.

    Returns (lo[S, W] | None, hi[S, W] | None, count[S, W]).
    """
    from jax import lax

    s, n = ts.shape
    vf, ok, cts, idx, _windowed, count = _window_scan_setup(ts, val, mask,
                                                            spec, wargs)
    # run boundaries: window id changes between consecutive points
    win = _window_ids_fast(ts, cts, spec, wargs)
    flags = jnp.concatenate(
        [jnp.ones((s, 1), bool), win[:, 1:] != win[:, :-1]], axis=1)

    carry = ()
    if want_min:
        carry += (jnp.where(ok, vf, jnp.inf),)
    if want_max:
        carry += (jnp.where(ok, vf, -jnp.inf),)
    carry += (flags,)

    def combine(a, b):
        bf = b[-1]
        out = []
        i = 0
        if want_min:
            out.append(jnp.where(bf, b[i], jnp.minimum(a[i], b[i])))
            i += 1
        if want_max:
            out.append(jnp.where(bf, b[i], jnp.maximum(a[i], b[i])))
            i += 1
        return tuple(out) + (a[-1] | bf,)

    scanned = lax.associative_scan(combine, carry, axis=1)
    # window w's run ends at idx[w+1]-1 (the last point < its upper edge)
    last_pos = jnp.clip(idx[:, 1:] - 1, 0, n - 1)

    def at_ends(x, sentinel):
        out = jnp.take_along_axis(x, last_pos, axis=1)
        return jnp.where(count > 0, out, sentinel)

    i = 0
    lo = hi = None
    if want_min:
        lo = at_ends(scanned[i], jnp.inf)
        i += 1
    if want_max:
        hi = at_ends(scanned[i], -jnp.inf)
    return lo, hi, count


def _use_subblock_extreme(n: int, w_padded: int) -> bool:
    """ONE predicate for taking the subblock extreme form, shared by the
    materialized and streaming paths (they must never drift); ineligible
    shapes never see it on either path.  Eligibility (the edge-fit
    guard bounding the [S, W, K] boundary-lane intermediates) and the
    ranking both live in _effective_extreme_mode."""
    return _effective_extreme_mode(n, w_padded) == "subblock"


def _extreme_subblock(ts, val, mask, spec: WindowSpec, wargs: dict,
                      want_min: bool, want_max: bool):
    """Windowed min/max with no full-length scan (extreme mode "subblock").

    Decomposes each window at 32-point sub-block granularity: sub-blocks
    whose span [B*32, (B+1)*32) lies inside [idx[w], idx[w+1]) are
    entirely window w's, so the interior extreme is a segmented
    reset-scan over the [S, N/32] sub-block extremes (1/32 the scan
    work); the at-most-two boundary sub-blocks are resolved with 32-wide
    masked reduces over contiguous [1, 32] gathers.  Same decomposition
    as _edge_subblock_builder, reduced with min/max instead of sum.
    min and max share ONE scan when both are wanted (the carry holds
    both lanes), like the full-length scan form.

    Returns (lo[S, W] | None, hi[S, W] | None, count[S, W]).
    """
    from jax import lax

    s, n = ts.shape
    vf, ok, cts, idx, _windowed, count = _window_scan_setup(ts, val, mask,
                                                            spec, wargs)
    k = _SUB_K
    nb = n // k
    lo_e = idx[:, :-1]                     # [S, W] window start positions
    hi_e = idx[:, 1:]                      # window end positions
    b0 = jnp.clip(lo_e // k, 0, nb - 1)    # boundary sub-blocks
    b1 = jnp.clip(hi_e // k, 0, nb - 1)
    r0 = (lo_e + k - 1) // k               # first interior sub-block
    r1 = hi_e // k                         # one past last interior
    lanes = jnp.arange(k, dtype=idx.dtype)

    v3 = vf.reshape(s, nb, k)
    o3 = ok.reshape(s, nb, k)
    g0v = jnp.take_along_axis(v3, b0[:, :, None], axis=1)    # [S, W, K]
    g0o = jnp.take_along_axis(o3, b0[:, :, None], axis=1)
    g1v = jnp.take_along_axis(v3, b1[:, :, None], axis=1)
    g1o = jnp.take_along_axis(o3, b1[:, :, None], axis=1)
    pos0 = b0[:, :, None] * k + lanes[None, None, :]
    pos1 = b1[:, :, None] * k + lanes[None, None, :]
    in0 = (pos0 >= lo_e[:, :, None]) & (pos0 < hi_e[:, :, None]) & g0o
    in1 = (pos1 >= lo_e[:, :, None]) & (pos1 < hi_e[:, :, None]) & g1o

    # Interior reset flags: sub-block b starts some window's interior,
    # i.e. b appears in the (per-row sorted) r0 sequence — a searchsorted
    # membership test, O(nb log W), not an [S, W, nb] broadcast compare
    # (which would exceed the full-length scan this mode replaces).
    blocks = jnp.arange(nb, dtype=r0.dtype)
    w_pad = r0.shape[1]
    p = jax.vmap(lambda row: jnp.searchsorted(row, blocks,
                                              side="left"))(r0)
    at = jnp.take_along_axis(r0, jnp.clip(p, 0, w_pad - 1), axis=1)
    flags = (at == blocks[None, :]) & (p < w_pad)
    interior_pos = jnp.clip(r1 - 1, 0, nb - 1)
    has_interior = r1 > r0

    # one scan carries every wanted lane + the shared reset flag
    carry = ()
    if want_min:
        carry += (jnp.where(o3, v3, jnp.inf).min(axis=2),)
    if want_max:
        carry += (jnp.where(o3, v3, -jnp.inf).max(axis=2),)
    carry += (flags,)

    def combine(a, b):
        bf = b[-1]
        out = []
        i = 0
        if want_min:
            out.append(jnp.where(bf, b[i], jnp.minimum(a[i], b[i])))
            i += 1
        if want_max:
            out.append(jnp.where(bf, b[i], jnp.maximum(a[i], b[i])))
        return tuple(out) + (a[-1] | bf,)

    scanned = lax.associative_scan(combine, carry, axis=1)

    def finish(lane, is_min: bool):
        ident = jnp.inf if is_min else -jnp.inf
        op = jnp.minimum if is_min else jnp.maximum
        red = jnp.min if is_min else jnp.max
        interior = jnp.take_along_axis(lane, interior_pos, axis=1)
        interior = jnp.where(has_interior, interior, ident)
        rem0 = red(jnp.where(in0, g0v, ident), axis=2)
        rem1 = red(jnp.where(in1, g1v, ident), axis=2)
        out = op(op(interior, rem0), rem1)
        return jnp.where(count > 0, out, ident)

    i = 0
    lo = hi = None
    if want_min:
        lo = finish(scanned[i], True)
        i += 1
    if want_max:
        hi = finish(scanned[i], False)
    return lo, hi, count


# shape: ts[S,N] any, val[S,N] any, mask[S,N] bool, wargs.first[] i64
# shape: wargs.nwin[] i32 -> ([W] i64, [S,W] f64, [S,W] bool)
def downsample(ts, val, mask, agg_name: str, spec: WindowSpec, wargs: dict,
               fill_policy: str = FILL_NONE, fill_value: float = 0.0):
    """Downsample a [S, N] batch into (window_ts[W], values[S, W], mask[S, W]).

    `agg_name` follows the runDouble contract (NaN inputs skipped); output is
    always float (Downsampler.java:257).  With FILL_NONE empty windows are
    masked out; other policies emit every live window with the fill applied.

    Additive-moment functions take the sorted prefix-sum fast path (no
    scatter — the hot loop the reference walked per interval,
    Downsampler.java:292); the rest reduce via segment ops.
    """
    from opentsdb_tpu.ops.aggregators import java_moving_average, ma_window
    nw = ma_window(agg_name)
    if nw is not None:
        # Downsample-position movingAverage<N>: the reference Downsampler
        # would feed each window's values into the aggregator, whose
        # run{Long,Double} sums them and averages the PRECEDING N window
        # sums (Aggregators.MovingAverage:709) — so: window sums, then
        # the same Java loop across this series' data-bearing windows.
        wts, sums, sum_mask = downsample(ts, val, mask, "sum", spec, wargs,
                                         FILL_NONE, 0.0)
        out = java_moving_average(sums, sum_mask, nw)
        w = spec.count
        live = jnp.arange(w, dtype=jnp.int32)[None, :] < wargs["nwin"]
        fdtype = val.dtype if jnp.issubdtype(val.dtype, jnp.floating) \
            else jnp.float64
        out, out_mask = apply_fill(out.astype(fdtype), sum_mask, live,
                                   fill_policy, fill_value, fdtype)
        return wts, out, out_mask

    emode = (_effective_extreme_mode(ts.shape[1], spec.count)
             if agg_name in EXTREME_AGGS else None)
    if agg_name in PREFIX_AGGS or emode in ("scan", "subblock"):
        w = spec.count
        nwin = wargs["nwin"]
        if agg_name in PREFIX_AGGS:
            out, count_grid = _prefix_downsample(ts, val, mask, agg_name,
                                                 spec, wargs)
        else:
            is_min = agg_name in ("min", "mimmin")
            extreme = _extreme_subblock if emode == "subblock" \
                else _extreme_downsample
            lo, hi, count_grid = extreme(
                ts, val, mask, spec, wargs, is_min, not is_min)
            out = lo if is_min else hi
        live = jnp.arange(w, dtype=jnp.int32)[None, :] < nwin
        out_mask = (count_grid > 0) & live
        wts = window_timestamps(spec, wargs)
        fdtype = val.dtype if jnp.issubdtype(val.dtype, jnp.floating) \
            else jnp.float64
        out, out_mask = apply_fill(out, out_mask, live, fill_policy,
                                   fill_value, fdtype)
        return wts, out, out_mask

    s, n = ts.shape
    w = spec.count
    num = s * w + 1
    fdtype = val.dtype if jnp.issubdtype(val.dtype, jnp.floating) else jnp.float64
    vf = val.astype(fdtype)
    nwin = wargs["nwin"]

    win = window_ids(ts, spec, wargs)
    valid = mask & (win >= 0) & (win < nwin.astype(win.dtype))
    rows = jnp.arange(s, dtype=jnp.int64)[:, None]
    seg = jnp.where(valid, rows * w + jnp.clip(win, 0, w - 1), s * w)
    seg = seg.reshape(-1)
    ok = valid.reshape(-1) & ~jnp.isnan(vf.reshape(-1))
    seg = jnp.where(ok, seg, s * w)
    flat_v = jnp.where(ok, vf.reshape(-1), 0)

    def segsum(data):
        return jax.ops.segment_sum(data, seg, num_segments=num)[:-1]

    counts = segsum(ok.astype(jnp.int32))
    count_grid = counts.reshape(s, w)
    live = jnp.arange(w, dtype=jnp.int32)[None, :] < nwin
    out_mask = (count_grid > 0) & live

    if agg_name in ("sum", "zimsum", "pfsum"):
        out = segsum(flat_v).reshape(s, w)
    elif agg_name == "count":
        out = count_grid.astype(fdtype)
    elif agg_name == "squareSum":
        out = segsum(flat_v * flat_v).reshape(s, w)
    elif agg_name in ("min", "mimmin"):
        out = jax.ops.segment_min(
            jnp.where(ok, vf.reshape(-1), jnp.inf), seg, num_segments=num
        )[:-1].reshape(s, w)
    elif agg_name in ("max", "mimmax"):
        out = jax.ops.segment_max(
            jnp.where(ok, vf.reshape(-1), -jnp.inf), seg, num_segments=num
        )[:-1].reshape(s, w)
    elif agg_name == "avg":
        total = segsum(flat_v).reshape(s, w)
        out = total / jnp.maximum(count_grid, 1)
    elif agg_name == "dev":
        # Two-pass: mean per window, then centered second moment — avoids the
        # catastrophic cancellation of sumsq - n*mean^2 at large magnitudes
        # (matches the reference's Welford numerics, Aggregators.java:498).
        total = segsum(flat_v).reshape(s, w)
        cnt = jnp.maximum(count_grid, 1)
        mean = total / cnt
        mean_per_point = mean.reshape(-1)[jnp.clip(seg, 0, s * w - 1)]
        centered = jnp.where(ok, vf.reshape(-1) - mean_per_point, 0.0)
        m2 = segsum(centered * centered).reshape(s, w)
        out = jnp.where(count_grid >= 2,
                        jnp.sqrt(m2 / jnp.maximum(count_grid - 1, 1)), 0.0)
    elif agg_name == "mult":
        out = jax.ops.segment_prod(
            jnp.where(ok, vf.reshape(-1), 1.0), seg, num_segments=num
        )[:-1].reshape(s, w)
    elif agg_name in ("first", "last", "diff"):
        pos = jnp.arange(s * n, dtype=jnp.int64)
        first_idx = jax.ops.segment_min(jnp.where(ok, pos, _I64_MAX), seg,
                                        num_segments=num)[:-1]
        last_idx = jax.ops.segment_max(jnp.where(ok, pos, -1), seg,
                                       num_segments=num)[:-1]
        flat_vals = vf.reshape(-1)
        first_v = flat_vals[jnp.clip(first_idx, 0, s * n - 1)].reshape(s, w)
        last_v = flat_vals[jnp.clip(last_idx, 0, s * n - 1)].reshape(s, w)
        if agg_name == "first":
            out = first_v
        elif agg_name == "last":
            out = last_v
        else:
            out = jnp.where(count_grid >= 2, last_v - first_v, 0.0)
    elif agg_name == "median" or agg_name.startswith(("p", "ep")):
        # Row-wise (window, value) sort: windows partition each row's
        # points, so S independent row sorts replace the global [S*N]
        # lexsort (invalid slots keyed past every window); per-cell runs
        # follow from the count grid.
        from jax import lax
        from opentsdb_tpu.ops.percentile import row_run_percentile
        ok2 = ok.reshape(s, n)
        wkey = jnp.where(ok2, jnp.clip(win, 0, w - 1).astype(jnp.int32),
                         w)
        svals = jnp.where(ok2, vf, jnp.inf)
        _, sorted_rows = lax.sort((wkey, svals), dimension=1, num_keys=2)
        starts = jnp.concatenate(
            [jnp.zeros((s, 1), count_grid.dtype),
             jnp.cumsum(count_grid, axis=1)], axis=1)[:, :-1]
        if agg_name == "median":
            idx = jnp.clip(starts + count_grid // 2, 0, n - 1)
            out = jnp.where(
                count_grid > 0,
                jnp.take_along_axis(sorted_rows, idx, axis=1), jnp.nan)
        else:
            q, est = parse_percentile_name(agg_name)
            out = row_run_percentile(sorted_rows, starts, count_grid, q,
                                     est)
    else:
        raise KeyError("No such downsampling function: " + agg_name)

    wts = window_timestamps(spec, wargs)
    out, out_mask = apply_fill(out, out_mask, live, fill_policy, fill_value,
                               fdtype)
    return wts, out, out_mask


def apply_fill(out, out_mask, live, fill_policy: str, fill_value: float,
               fdtype=None):
    """Fill empty live windows per FillPolicy (FillingDownsampler semantics).

    `out_mask` marks windows holding data; `live` marks windows inside the
    query range.  Returns (values, mask) — under FILL_NONE empty windows stay
    masked out; other policies substitute a fill value and expose every live
    window.  Shared by the raw downsample above and the rollup-avg pipeline.
    """
    if fdtype is None:
        fdtype = out.dtype
    if fill_policy == FILL_NONE:
        return jnp.where(out_mask, out, jnp.nan), out_mask
    if fill_policy == FILL_ZERO:
        fill = jnp.asarray(0.0, fdtype)
    elif fill_policy in (FILL_NAN, FILL_NULL):
        fill = jnp.asarray(jnp.nan, fdtype)
    elif fill_policy == FILL_SCALAR:
        fill = jnp.asarray(fill_value, fdtype)
    else:
        raise ValueError("Unrecognized fill policy: " + fill_policy)
    out = jnp.where(out_mask, out, fill)
    return out, jnp.broadcast_to(live, out_mask.shape)


def parse_percentile_name(name: str) -> tuple[float, str]:
    """"p99" -> (99.0, legacy); "ep999r3" -> (99.9, r_3); "ep50r7" -> (50.0, r_7)."""
    est = EST_LEGACY
    digits = name
    if name.startswith("ep"):
        if name.endswith("r3"):
            est = EST_R3
        elif name.endswith("r7"):
            est = EST_R7
        else:
            raise KeyError("No such aggregator: " + name)
        digits = name[2:-2]
    elif name.startswith("p"):
        digits = name[1:]
    if digits == "999":
        return 99.9, est
    q = float(digits)
    if not 0 < q <= 100:
        raise KeyError("Invalid percentile: " + name)
    return q, est

"""Materialized partial aggregates: block-cached downsample grids.

ROADMAP item 2 (the overlapping-window reuse tentpole): millions of
dashboard users issue the SAME metrics on overlapping, sliding windows
all day, yet every `/api/query` used to recompute its full
scan->downsample->aggregate pipeline from scratch.  This module caches
the expensive middle of that pipeline — the per-(series, window)
downsample grid — in alignable, reusable factors, in the Factor Windows
stance (arXiv:2008.12379): decompose each fixed-interval downsample
plan into aligned sub-window blocks, reuse every cached block, and
dispatch only the uncovered delta ranges.  Which factors are worth
materializing is decided per plan by the costmodel
(`ops/costmodel.py` predict_* via obs.jaxprof.stage_breakdown) plus a
repeat-count admission rule, the Storyboard placement question
(arXiv:2002.03063) reduced to: populate once a plan family has proven
it repeats, serve from cache the moment anything is covered.

The cached unit
---------------

One **block** = B consecutive windows of one (store, metric, downsample
function, interval, fill, platform, series-set) plan family, aligned to
the ABSOLUTE window grid (block k covers windows [k*B, (k+1)*B) of the
epoch-anchored grid), holding the finished per-(series, window)
downsample values + mask exactly as `ops.downsample.downsample`
produced them for that block's sub-range.  Blocks are aligned, so every
overlapping/sliding query over the same plan family lands on the same
block keys — the Factor Windows alignment property.  Only windows fully
inside the query range are ever cached (edge windows see a partial
point population and are recomputed per query); rate / group-by /
cross-series aggregation always run fresh on the assembled grid (they
cross window and series boundaries), via the SAME `run_grid_tail`
program the streaming executor finishes with.

Bit-identity contract (the correctness gate)
--------------------------------------------

A cache hit must never change an answer: a warm query's result is
bit-identical to the same query against the same data with the cache
EMPTY, because a cold run executes the very same per-block compiled
programs whose outputs a warm run replays — same shapes, same kernels,
same platform (the execution platform is part of the block key, and
which kernel form runs is a pure function of platform and shape).
tests/test_agg_cache.py pins cold == warm == invalidated-
and-recomputed bitwise on random float data, and cache-enabled ==
cache-disabled bitwise on exactly-representable data; against the
monolithic (cache-disabled) pipeline on arbitrary floats the decomposed
evaluation carries the same last-ulp reassociation latitude as the
streamed path (same 1e-9 contract, docs/caching.md).

Invalidation (incremental, on ingest)
-------------------------------------

The memstore write path calls `note_mutation(metric, lo_ms, hi_ms)`
AFTER the point lands (write-then-mark): by the time a write is acked
its mark exists, so any block built from a pre-write read fails its
generation check — an acked write is never served stale.  (The
inverse order had a hole: a plan snapshotting between the mark and the
write would carry the mark's generation and dodge it forever; with
write-then-mark, a mark no newer than a plan's snapshot implies its
write landed before the plan's reads.)  Marks are (generation,
time-range) records per (store, metric); a block entry is valid only
when no mark newer than its build generation overlaps its window
range, so an append at `now` invalidates ONLY the block under `now` —
historical blocks keep serving, which is what makes the cache survive
continuous ingest.  The mark ring is bounded: overflow raises the
floor generation, which conservatively invalidates everything older
(never serves stale).  tsdblint's cache-coherence analyzer holds the
declared backing store to its registered invalidator (`invalidate`
below); gutting the invalidator fails the tree
(tests/test_agg_cache.py::test_gutting_the_agg_invalidator_fails_lint).

Two tiers
---------

Host tier: every cached block, numpy, byte-budgeted
(`tsd.query.cache.mb`, LRU).  Device tier: blocks that keep hitting
(>= `tsd.query.cache.promote_hits`) get an HBM mirror beside
storage/device_cache.py's column cache (`tsd.query.cache.device_mb`,
own LRU) — a device-tier block goes into the assembled grid without
leaving the device (ops/pipeline.py assemble_grid).

This module stays importable numpy-only (the device tier lazy-imports
jax), like the rest of storage/.
"""

from __future__ import annotations

import logging
import threading
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from opentsdb_tpu.obs.registry import REGISTRY

_LOG = logging.getLogger("agg_cache")

# bytes per cached grid cell: float64 value + bool mask
_BYTES_PER_CELL = 9

# bound on retained (generation, range) dirty marks per store: overflow
# raises the floor generation (conservative full invalidation for older
# entries), so the ring can never grow with ingest volume
_MARK_RING = 512

# host batch-build cost per point (build_batch_direct: per-series lock +
# columnar copy into the padded batch) charged to BOTH sides of the
# rewrite-vs-recompute decision — the monolithic path copies every
# point, the rewrite only the uncovered delta, and a warm hit none.
# A rough memcpy+locking figure, deliberately conservative; the device
# stages are priced by ops/costmodel.py's table, this host stage by
# this constant.
_HOST_BUILD_S_PER_POINT = 5e-9


def _pow2_at_least(n: int) -> int:
    p = 1
    while p < max(int(n), 1):
        p <<= 1
    return p


@dataclass
class _Block:
    """One cached block: the finished [S, B] downsample grid slice."""
    store: object            # strong ref — pins id(store)
    metric: int
    rows: dict               # Series object -> row index (identity keyed:
    #                          a deleted+recreated series never matches)
    val: np.ndarray          # [S, B] float64
    mask: np.ndarray         # [S, B] bool
    gen: int                 # build generation (mark-ring validation)
    lo_ms: int               # block window-range [lo_ms, hi_ms] inclusive
    hi_ms: int
    nbytes: int = 0
    # host-tier LRU order is the _blocks dict order (move-to-end on
    # every consult, evict from the front)
    hits: int = 0            # serves; promotion queues past the bar
    val_dev: object = None   # device-tier mirror (None = host only)
    mask_dev: object = None
    dev_tick: int = 0        # device-tier LRU clock


@dataclass
class PlanPiece:
    """One window-contiguous slice of a rewritten plan."""
    first_ms: int            # absolute ms of the piece's first window
    count: int               # windows in this piece
    fetch_lo: int            # inclusive point-fetch range
    fetch_hi: int
    block: int | None = None  # absolute block index (cacheable pieces)
    cached: tuple | None = None   # (val, mask) when served from cache
    tier: str = ""           # 'agg_host' | 'agg_device' for cache hits
    # device-tier hits carry the ENTRY's full row set; the planner
    # narrows to the query's rows with this index vector (on device)
    rows: object = None


@dataclass
class RewritePlan:
    """The executable decomposition `plan()` hands the planner."""
    pieces: list
    gen0: int                # generation snapshot taken at plan time
    family: tuple            # (store_id, metric, ds_fn, interval, fill...)
    store: object
    metric: int
    interval_ms: int
    platform: str
    decision: dict = field(default_factory=dict)

    @property
    def cached_windows(self) -> int:
        return sum(p.count for p in self.pieces if p.cached is not None)

    @property
    def computed_windows(self) -> int:
        return sum(p.count for p in self.pieces if p.cached is None)


class AggregateCache:
    """Two-tier block cache of per-(series, window) partial aggregates."""

    def __init__(self, config):
        block = config.get_int("tsd.query.cache.block_windows")
        # pow2 block span: block dispatch shapes stay jit-stable and the
        # padded window count equals the block count exactly
        self.block_windows = _pow2_at_least(block)
        self.max_bytes = config.get_int("tsd.query.cache.mb") * 2 ** 20
        self.device_max_bytes = config.get_int(
            "tsd.query.cache.device_mb") * 2 ** 20
        self.min_repeats = max(config.get_int(
            "tsd.query.cache.min_repeats"), 1)
        self.promote_hits = max(config.get_int(
            "tsd.query.cache.promote_hits"), 1)
        self.amortize_horizon = max(config.get_int(
            "tsd.query.cache.amortize_horizon"), 1)
        self.dispatch_overhead_s = config.get_int(
            "tsd.query.cache.dispatch_overhead_us") * 1e-6
        # flight recorder (obs/flightrec.py), attached by the TSDB
        # after construction: mark-ring overflows and device-tier
        # demotions are retained diagnostics
        self.recorder = None
        self._lock = threading.Lock()
        # the cached blocks — THE backing store of this cache; dropped
        # wholesale by `invalidate()` (targeted drops are generation-
        # based: see _marks below)
        # cache: agg-blocks invalidated-by: invalidate
        self._blocks = {}  # guarded-by: _lock
        # (store_id, metric, ds_fn, interval) -> {block keys}: the
        # admission estimate's coverage() walks one family, not the
        # whole store  # guarded-by: _lock
        self._family_index: dict[tuple, set] = {}
        # (store_id, metric) -> deque[(gen, lo_ms, hi_ms)] dirty marks
        self._marks: dict[tuple, deque] = {}  # guarded-by: _lock
        # (store_id, metric) -> floor generation: entries built before
        # it are unconditionally invalid (mark-ring overflow safety)
        self._floor: dict[tuple, int] = {}  # guarded-by: _lock
        self._gen = 0  # guarded-by: _lock
        # newest generation any plan() has snapshotted: marks younger
        # than it merge in place instead of appending (per-point ingest
        # would otherwise append one mark per write)  # guarded-by: _lock
        self._planned_gen = 0
        # ingest fast path: until the FIRST plan commits to this cache,
        # note_mutation returns without taking the lock — a deployment
        # whose queries never cache pays nothing per write.  Sticky
        # once set; written only under _lock (in plan(), strictly
        # BEFORE that plan's executor reads any store data), read
        # without it: a writer that sees False checked after its write
        # landed, so any later plan's reads see that write — no mark
        # needed.  GIL-ordered attribute access; never cleared.
        self._maybe_cached = False  # guarded-by: _lock (writes; reads race)
        self._host_bytes = 0  # guarded-by: _lock
        self._dev_tick = 0  # guarded-by: _lock
        self._dev_bytes = 0  # guarded-by: _lock
        # plan-family repeat counts (the Storyboard materialization
        # admission rule)  # guarded-by: _lock
        self._repeats: dict[tuple, int] = {}
        # block keys awaiting a device-tier mirror: served-enough
        # blocks queue here and the maintenance thread pays the
        # host->HBM upload (promote_pending), never the query path
        # guarded-by: _lock
        self._promote_pending: set = set()
        # stats (mirrored to /api/stats via collect_stats and to
        # prometheus via the tsd.query.cache.* registry families)
        # guarded-by: _lock
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.invalidations = 0
        self.rewrites = 0
        self.populated = 0

    # -- metrics helpers -------------------------------------------------

    @staticmethod
    def _count_hit(tier: str) -> None:
        REGISTRY.counter(
            "tsd.query.cache.hits",
            "Query-cache hits, by tier (device_series HBM columns, "
            "agg_host / agg_device partial-aggregate blocks)").labels(
                tier=tier).inc()

    @staticmethod
    def _count_miss(tier: str) -> None:
        REGISTRY.counter(
            "tsd.query.cache.misses",
            "Query-cache misses, by tier").labels(tier=tier).inc()

    @staticmethod
    def _count_eviction(tier: str) -> None:
        REGISTRY.counter(
            "tsd.query.cache.evictions",
            "Query-cache evictions, by tier").labels(tier=tier).inc()

    def _set_byte_gauges_locked(self) -> None:
        REGISTRY.gauge(
            "tsd.query.cache.bytes",
            "Query-cache resident bytes, by tier").labels(
                tier="agg_host").set(self._host_bytes)
        REGISTRY.gauge(
            "tsd.query.cache.bytes",
            "Query-cache resident bytes, by tier").labels(
                tier="agg_device").set(self._dev_bytes)
        REGISTRY.gauge(
            "tsd.query.cache.entries",
            "Query-cache resident entries, by tier").labels(
                tier="agg_host").set(len(self._blocks))

    # -- invalidation ----------------------------------------------------

    def note_mutation(self, metric: int, lo_ms: int | None,
                      hi_ms: int | None, store=None) -> None:
        """Ingest-side hook (memstore mutation listener): mark the
        affected (metric, sub-window) range dirty, called AFTER the
        write lands (write-then-mark — see the module docstring).
        Routes to `invalidate` — the registered invalidator the
        cache-coherence lint holds this cache to."""
        if not self._maybe_cached:
            # nothing has ever been (or is being) materialized: the
            # hot ingest path skips the cache lock entirely.  Sound
            # because this read happens after the caller's write
            # landed, and plan() raises the flag before its executor
            # reads any store data — see the flag's declaration.
            return
        self.invalidate(store=store, metric=metric, lo_ms=lo_ms,
                        hi_ms=hi_ms)

    def invalidate(self, store=None, metric: int | None = None,
                   lo_ms: int | None = None,
                   hi_ms: int | None = None) -> None:
        """THE invalidation entry point (registered in the `# cache:`
        declaration above `_blocks`).

        With a metric: record a dirty mark over [lo_ms, hi_ms] (None
        bounds = open) — block entries overlapping the range fail their
        generation check from now on, everything else keeps serving.
        Without a metric: drop everything (/api/dropcaches)."""
        overflowed = False
        with self._lock:
            if metric is None:
                self.invalidations += 1
                self._blocks = {}
                self._family_index.clear()
                self._marks.clear()
                self._floor.clear()
                self._promote_pending.clear()
                self._dev_bytes = 0
                self._host_bytes = 0
                self._gen += 1
                self._set_byte_gauges_locked()
            else:
                lo = -2 ** 62 if lo_ms is None else int(lo_ms)
                hi = 2 ** 62 if hi_ms is None else int(hi_ms)
                key = (id(store), metric)
                ring = self._marks.get(key)
                if ring is None:
                    ring = self._marks[key] = deque(maxlen=_MARK_RING)
                if ring and ring[-1][0] > self._planned_gen:
                    # no plan has snapshotted since the newest mark: no
                    # entry can carry a generation between it and now,
                    # so widening it in place invalidates exactly the
                    # same set — per-point ingest coalesces to one mark
                    # (and deliberately skips the counter: it IS the
                    # same mark)
                    g, plo, phi = ring[-1]
                    ring[-1] = (g, min(plo, lo), max(phi, hi))
                    return
                self.invalidations += 1
                self._gen += 1
                if len(ring) == _MARK_RING:
                    # overflow: everything at least as old as the
                    # evicted mark becomes unconditionally invalid
                    oldest = ring[0]
                    self._floor[key] = max(self._floor.get(key, 0),
                                           oldest[0])
                    overflowed = True
                ring.append((self._gen, lo, hi))
        if overflowed and self.recorder is not None:
            # diagnosable event: hot ingest outran the mark ring and a
            # floor generation now hides history for this metric —
            # warm repeats will recompute until blocks rebuild
            self.recorder.record("agg_mark_overflow", metric=metric)
        REGISTRY.counter(
            "tsd.query.cache.invalidations",
            "Query-cache invalidation marks (ingest dirty ranges, "
            "dropcaches), by tier").labels(tier="agg").inc()

    def _valid_locked(self, entry: _Block) -> bool:
        key = (id(entry.store), entry.metric)
        if entry.gen < self._floor.get(key, 0):
            return False
        ring = self._marks.get(key)
        if not ring:
            return True
        for gen, lo, hi in reversed(ring):
            if gen <= entry.gen:
                break
            if lo <= entry.hi_ms and hi >= entry.lo_ms:
                return False
        return True

    # -- planning --------------------------------------------------------

    # effects: observe-gated(observe)
    def plan(self, store, metric: int, series_list, windows,
             start_ms: int, end_ms: int, ds_fn: str,
             fill_policy: str, fill_value, platform: str,
             s: int, n_max: int, g_pad: int, has_rate: bool,
             total_points: int = 0, observe: bool = True):
        """Rewrite decision for one fixed-grid downsample segment.

        Returns (RewritePlan | None, decision dict).  None means
        recompute monolithically; the decision dict always comes back
        for the trace span (PR 6 contract: strategy decisions are
        visible per query).

        ``observe=False`` is the EXPLAIN engine's dry-run arm: the
        same verdict from the same state, with ZERO bookkeeping — the
        repeat count is read but not advanced (an explain must not
        walk a family toward ``min_repeats``), LRU recency and
        ``_planned_gen`` stay put, stale blocks are left for the real
        pass to reap, and no hit/miss/rewrite accounting fires.
        Because the executor's own ``plan()`` prices with the count
        BEFORE its increment, a dry-run at the same instant computes
        the identical decision (the explain-vs-actual parity pin)."""
        from opentsdb_tpu.obs import jaxprof
        from opentsdb_tpu.ops.downsample import pad_pow2
        interval = windows.interval_ms
        first = windows.first_window_ms
        w = windows.count
        decision = {"decision": "recompute", "reason": "",
                    "coverage": 0.0, "cachedWindows": 0,
                    "computedWindows": w}
        a0 = first // interval                      # absolute window idx
        wf_lo = 0 if start_ms <= first else 1
        last_start = first + (w - 1) * interval
        wf_hi = w - 1 if last_start + interval - 1 <= end_ms else w - 2
        bw = self.block_windows
        if wf_hi < wf_lo:
            decision["reason"] = "no_full_windows"
            return None, decision
        a_lo, a_hi = a0 + wf_lo, a0 + wf_hi
        k_lo = -(-a_lo // bw)                       # ceil div
        k_hi = (a_hi + 1) // bw - 1
        if k_hi < k_lo:
            decision["reason"] = "no_full_blocks"
            return None, decision

        sig = hash(tuple(sorted(id(srs) for srs in series_list)))
        family = (id(store), metric, ds_fn, interval, fill_policy,
                  float(fill_value), platform, sig)

        pieces: list[PlanPiece] = []
        head_count = k_lo * bw - a0
        if head_count > 0:
            pieces.append(PlanPiece(
                first_ms=first, count=head_count,
                fetch_lo=start_ms,
                fetch_hi=first + head_count * interval - 1))
        hits: list[PlanPiece] = []
        hit_entries: list[tuple] = []   # (block key, _Block) of hits
        missing: list[PlanPiece] = []
        with self._lock:
            gen0 = self._gen
            if observe:
                # stop mark-coalescing at this generation: entries
                # built from this plan must be invalidated by any
                # LATER write
                self._planned_gen = max(self._planned_gen, gen0)
                # pop-then-set keeps the dict in recency order, so the
                # overflow eviction drops the STALEST families — a
                # burst of one-off ad-hoc families must not wipe the
                # hot dashboards' repeat counts (that would re-impose
                # min_repeats on everything at once)
                repeats = self._repeats.pop(family, 0)
                self._repeats[family] = repeats + 1
                while len(self._repeats) > 4096:
                    self._repeats.pop(next(iter(self._repeats)))
            else:
                repeats = self._repeats.get(family, 0)
            for k in range(k_lo, k_hi + 1):
                piece = PlanPiece(
                    first_ms=k * bw * interval, count=bw,
                    fetch_lo=k * bw * interval,
                    fetch_hi=(k + 1) * bw * interval - 1, block=k)
                key = family + (k,)
                entry = self._blocks.get(key)
                if entry is not None and self._valid_locked(entry) and \
                        all(srs in entry.rows for srs in series_list):
                    rows = np.fromiter(
                        (entry.rows[srs] for srs in series_list),
                        np.int64, count=len(series_list))
                    if observe:
                        # LRU recency = dict order (move-to-end):
                        # eviction pops from the front in O(1) instead
                        # of a min() scan over every resident block
                        self._blocks.pop(key)
                        self._blocks[key] = entry
                    if entry.val_dev is not None:
                        if observe:
                            self._dev_tick += 1
                            entry.dev_tick = self._dev_tick
                        piece.cached = (entry.val_dev, entry.mask_dev)
                        piece.tier = "agg_device"
                    else:
                        # refs only under the lock — the fancy-index
                        # row copies happen after release (blocks are
                        # immutable once stored, and the copy is the
                        # expensive part a hot ingest path would
                        # otherwise wait on)
                        piece.cached = (entry.val, entry.mask)
                        piece.tier = "agg_host"
                    # device mirrors hold the FULL row set; narrow to
                    # the query's rows outside the lock (device gather)
                    piece.rows = rows
                    hits.append(piece)
                    hit_entries.append((key, entry))
                else:
                    if entry is not None and observe:
                        # stale or row-incomplete: drop so the rebuild
                        # below can take its slot
                        self._drop_locked(key)
                    missing.append(piece)
                pieces.append(piece)
        # hit pieces carry REFS + a row index; the executor narrows
        # them (outside this lock, only for plans that actually serve,
        # and not at all when the rows are the identity — the common
        # exact-repeat case serves blocks zero-copy)
        tail_off = (k_hi + 1) * bw - a0
        if tail_off < w:
            pieces.append(PlanPiece(
                first_ms=first + tail_off * interval,
                count=w - tail_off,
                fetch_lo=first + tail_off * interval,
                fetch_hi=end_ms))

        cached_windows = sum(p.count for p in hits)
        computed_windows = w - cached_windows
        decision.update(
            coverage=round(cached_windows / max(w, 1), 4),
            cachedWindows=cached_windows,
            computedWindows=computed_windows,
            blocks=k_hi - k_lo + 1, blockHits=len(hits),
            repeats=repeats)

        if hits and not missing and cached_windows >= w - 2:
            # full (or all-but-edge-window) coverage: nothing worth
            # pricing — serving the replay beats any recompute, and
            # the per-query stage_breakdown (~ms of pure decision
            # work) would tax exactly the hot path the cache exists
            # to shrink
            decision.update(decision="rewrite", reason="reuse")
            if observe:
                for p in hits:
                    self._count_hit(p.tier)
                with self._lock:
                    self._maybe_cached = True
                    self.rewrites += 1
                    self.hits += len(hits)
                    self._note_serves_locked(hit_entries)
            return RewritePlan(pieces=pieces, gen0=gen0, family=family,
                               store=store, metric=metric,
                               interval_ms=interval, platform=platform,
                               decision=decision), decision

        # costmodel: price the rewrite vs the monolithic recompute.
        # Both sides carry their device stages (ops/costmodel.py's
        # predict_* via stage_breakdown), their host batch-build cost
        # (proportional to the points each side copies), and one
        # dispatch-overhead charge per dispatch they issue.
        wp = pad_pow2(w)
        build_s = total_points * _HOST_BUILD_S_PER_POINT
        full_bd = jaxprof.stage_breakdown(platform, s, pad_pow2(n_max),
                                          wp, g_pad, ds_fn, has_rate)
        ds_s = full_bd.get("downsample", 0.0)
        tail_s = sum(full_bd.values()) - ds_s
        pred_full = sum(full_bd.values()) + build_s \
            + self.dispatch_overhead_s
        pred_rw = tail_s + self.dispatch_overhead_s
        for p in pieces:
            if p.cached is not None:
                continue
            # per-piece downsample/build cost approximated as the
            # window-proportional share of the full plan's (one
            # stage_breakdown per plan, not per piece — the decision
            # runs on every eligible query and must stay cheap)
            share = p.count / max(w, 1)
            pred_rw += (ds_s + build_s) * share \
                + self.dispatch_overhead_s
        # a fully-warm repeat costs roughly the tail plus the edge
        # pieces; what a hit SAVES per query is the monolithic
        # downsample + build share minus that
        pred_warm = tail_s + 2 * self.dispatch_overhead_s
        per_hit_saving = pred_full - pred_warm
        decision["predictedRewriteMs"] = round(pred_rw * 1e3, 3)
        decision["predictedFullMs"] = round(pred_full * 1e3, 3)
        decision["perHitSavingMs"] = round(per_hit_saving * 1e3, 3)

        if cached_windows == 0:
            if repeats + 1 < self.min_repeats:
                decision["reason"] = "below_min_repeats"
                return None, decision
            # Storyboard's materialization question, amortized: the
            # populate overhead must be recoverable within the horizon
            # of expected repeats.  Dispatch-floor-dominated plans
            # (per-hit saving <= 0) honestly never cache.
            if per_hit_saving <= 0.0 or \
                    pred_rw - pred_full > \
                    self.amortize_horizon * per_hit_saving:
                decision["reason"] = "populate_unamortizable"
                return None, decision
            decision["reason"] = "cold_populate"
        elif pred_rw <= pred_full * 1.25:
            # serving cached factors beats recompute outright (25%
            # slack keeps a populated family from flapping on
            # prediction noise)
            decision["reason"] = "reuse"
        elif per_hit_saving > 0.0 and \
                pred_rw - pred_full <= \
                self.amortize_horizon * per_hit_saving:
            # partially invalidated (ingest dirtied some blocks):
            # recomputing the missing blocks costs more than one
            # monolithic pass NOW but restores full coverage — the
            # same amortization rule that admitted the cold populate
            # admits the heal, otherwise a family that keeps taking
            # writes would recompute monolithically forever
            decision["reason"] = "heal_populate"
        else:
            decision["reason"] = "recompute_cheaper"
            return None, decision
        decision["decision"] = "rewrite"
        # hit/miss accounting only for plans that actually serve — a
        # consulted-but-recomputed plan must not inflate the hit rate
        if observe:
            for p in hits:
                self._count_hit(p.tier)
            for _p in missing:
                self._count_miss("agg_host")
            with self._lock:
                # committing to materialize/serve: arm the ingest-side
                # mark path BEFORE the executor reads any store data
                self._maybe_cached = True
                self.rewrites += 1
                self.hits += len(hits)
                self.misses += len(missing)
                self._note_serves_locked(hit_entries)
        return RewritePlan(pieces=pieces, gen0=gen0, family=family,
                           store=store, metric=metric,
                           interval_ms=interval, platform=platform,
                           decision=decision), decision

    # -- population ------------------------------------------------------

    def store_block(self, plan: RewritePlan, piece: PlanPiece,
                    series_list, val: np.ndarray,
                    mask: np.ndarray) -> None:
        """Insert one computed block, unless a dirty mark younger than
        the plan's generation snapshot overlaps it (the mark could have
        landed after the block's points were read — conservatively
        discard; the next query recomputes)."""
        rows = {srs: i for i, srs in enumerate(series_list)}
        entry = _Block(store=plan.store, metric=plan.metric, rows=rows,
                       val=val, mask=mask, gen=plan.gen0,
                       lo_ms=piece.fetch_lo, hi_ms=piece.fetch_hi,
                       nbytes=val.shape[0] * val.shape[1]
                       * _BYTES_PER_CELL)
        key = plan.family + (piece.block,)
        with self._lock:
            if not self._valid_locked(entry):
                return
            if entry.nbytes > self.max_bytes:
                return
            self._evict_for_locked(entry.nbytes)
            old = self._blocks.get(key)
            if old is not None:
                self._drop_locked(key)
            # insertion at the dict tail IS the LRU recency position
            self._blocks[key] = entry
            self._host_bytes += entry.nbytes
            self._family_index.setdefault(key[:4], set()).add(key)
            self.populated += 1
            self._set_byte_gauges_locked()

    def _note_serves_locked(self, hit_entries: list) -> None:
        """Record that these blocks actually SERVED an answer (plans
        that consult but recompute must not accrue hits — a never-
        serving block would otherwise earn a device mirror) and queue
        the ones past the promotion bar for the maintenance thread."""
        for key, entry in hit_entries:
            entry.hits += 1
            if entry.val_dev is None \
                    and entry.hits >= self.promote_hits \
                    and 0 < entry.nbytes <= self.device_max_bytes:
                # oversized blocks never queue: a mirror bigger than
                # the whole device budget would overcommit HBM and
                # then demote/re-upload forever
                self._promote_pending.add(key)

    def promote_pending(self, max_uploads: int = 8) -> int:
        """Mirror queued hot host-tier blocks into the device tier.

        Called from the maintenance thread (and directly by tests/
        benches standing in for it): the host->HBM uploads are paid
        OFF the query path, like the device series cache's refresh().
        Returns the number of blocks mirrored."""
        if self.device_max_bytes <= 0:
            return 0
        import jax
        done = 0
        for _ in range(max_uploads):
            with self._lock:
                if not self._promote_pending:
                    break
                key = self._promote_pending.pop()
                entry = self._blocks.get(key)
            if entry is None or entry.val_dev is not None:
                continue
            val_dev = jax.device_put(entry.val)
            mask_dev = jax.device_put(entry.mask)
            with self._lock:
                if self._blocks.get(key) is not entry:
                    continue        # evicted while uploading
                self._evict_device_for_locked(entry.nbytes)
                entry.val_dev = val_dev
                entry.mask_dev = mask_dev
                self._dev_tick += 1
                entry.dev_tick = self._dev_tick
                self._dev_bytes += entry.nbytes
                self._set_byte_gauges_locked()
                done += 1
        return done

    # -- eviction --------------------------------------------------------

    def _drop_locked(self, key: tuple) -> None:
        entry = self._blocks.pop(key, None)
        if entry is None:
            return
        self._host_bytes -= entry.nbytes
        if entry.val_dev is not None:
            self._dev_bytes -= entry.nbytes
        fam = self._family_index.get(key[:4])
        if fam is not None:
            fam.discard(key)
            if not fam:
                self._family_index.pop(key[:4], None)

    def _evict_for_locked(self, incoming: int) -> None:
        while self._blocks and \
                self._host_bytes + incoming > self.max_bytes:
            # dict order is LRU order (move-to-end on consult): the
            # front IS the least-recently-used block, O(1) per victim
            key = next(iter(self._blocks))
            self._drop_locked(key)
            self.evictions += 1
            self._count_eviction("agg_host")

    def _evict_device_for_locked(self, incoming: int) -> None:
        while self._dev_bytes + incoming > self.device_max_bytes:
            candidates = [(k, b) for k, b in self._blocks.items()
                          if b.val_dev is not None]
            if not candidates:
                break
            key, victim = min(candidates,
                              key=lambda kb: kb[1].dev_tick)
            victim.val_dev = None
            victim.mask_dev = None
            self._dev_bytes -= victim.nbytes
            self.evictions += 1
            self._count_eviction("agg_device")

    # -- admission-estimate support --------------------------------------

    def coverage(self, store, metric: int, interval_ms: int, ds_fn: str,
                 start_ms: int, end_ms: int) -> float:
        """Fraction of the plan's windows served from valid cached
        blocks, for tsd/admission.py's pre-admission cost estimate (the
        rewritten plan is what should be priced, not the original).
        Approximate: ignores fill/platform/series-set key components
        (scans every family of the (store, metric, ds_fn, interval))."""
        if interval_ms <= 0:
            return 0.0
        bw = self.block_windows
        first = start_ms - start_ms % interval_ms
        w = (end_ms - end_ms % interval_ms - first) // interval_ms + 1
        if w <= 0:
            return 0.0
        covered: set[int] = set()
        with self._lock:
            fam = self._family_index.get(
                (id(store), metric, ds_fn, interval_ms), ())
            for key in fam:
                entry = self._blocks.get(key)
                if entry is None:
                    continue
                k = key[-1]
                if k * bw * interval_ms >= first and \
                        (k + 1) * bw * interval_ms - 1 <= end_ms and \
                        self._valid_locked(entry):
                    covered.add(k)
        return min(len(covered) * bw / w, 1.0)

    # -- stats -----------------------------------------------------------

    def collect_stats(self) -> dict:
        with self._lock:
            host_bytes = self._host_bytes
            return {
                "tsd.query.agg_cache.hits": float(self.hits),
                "tsd.query.agg_cache.misses": float(self.misses),
                "tsd.query.agg_cache.evictions": float(self.evictions),
                "tsd.query.agg_cache.invalidations": float(
                    self.invalidations),
                "tsd.query.agg_cache.rewrites": float(self.rewrites),
                "tsd.query.agg_cache.populated": float(self.populated),
                "tsd.query.agg_cache.entries": float(len(self._blocks)),
                "tsd.query.agg_cache.bytes": float(host_bytes),
                "tsd.query.agg_cache.device_bytes": float(
                    self._dev_bytes),
            }

    def __len__(self) -> int:
        with self._lock:
            return len(self._blocks)

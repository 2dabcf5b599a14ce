"""Device-resident series cache: hot columnar data lives in HBM.

The TPU-native analog of the reference's storage-side block caching (the
HBase BlockCache that made repeated scans of hot rows memory-speed; the
reference leans on it implicitly — every SaltScanner pass re-reads the
same regions, SaltScanner.java:269).  Here the roles are inverted: the
store is host RAM, the accelerator is across a PCIe link, and the
dominant cost of a repeated `/api/query` is re-uploading the same raw
points every dispatch.  This cache pins each hot metric's columnar data
in device HBM once; subsequent queries assemble their [S, N] window batch
ON DEVICE in a single dispatch — zero host->device traffic for the data
itself (only the tiny per-series start/length vectors travel).  A row of
the batch is one contiguous run of the buffer, so the dispatch copies
whole 128-element tile rows and shifts each row to its start; it takes
no index per stored point (`_gather_windows`).

Design:

  * One entry per metric: every series' normalized (ts, val) columns
    concatenated whole into 1-D device buffers (padded to pow2 length,
    >= 1024, to bound the batch program's recompiles and to keep the
    buffer a whole number of tile rows), plus host-side row offsets.
    The tail padding guarantees nothing to a reader: a row that runs
    past the data's or the buffer's end does so beyond its length, under
    the mask.
  * What is pinned is what the chip reads.  A TPU has no 64-bit lanes:
    XLA:TPU holds an int64 as two uint32 and a float64 as two float32,
    and a program handed a 64-bit parameter cuts the WHOLE parameter
    into its halves at entry (`X64SplitHigh` / `X64SplitLow`).  With
    int64 + float64 buffers pinned, every batch assembly re-derived the
    halves of all 2^26 elements before it copied the few tile rows it
    came for: the four longest device operations of both one-chip
    benchmark cells, 0.80 / 0.94 s of a 5 s trace and 26-27 % of the
    chip's busy time (PERF_LEDGER.jsonl, PR 29, `breakdown.device_ops`;
    PERF.md section 6, PR 30).  So an entry pins the halves themselves,
    made once at build (`_pin_columns`): the timestamps' low and high
    words, cut on the host, and the values' two float32 parts, cut by
    the device's own arithmetic from one float64 upload that is then
    dropped.  The batch that leaves is the same (int32 | int64 ts,
    float64 val, bool mask), bit for bit.  Two float32 hold 48 bits of
    a double; on a backend with real 64-bit floats (the CPU) an entry
    with a value they cannot hold keeps the float64 buffer instead —
    decided per entry by the split program's own exactness check, and
    never true on a TPU, whose float64 is such a pair.  So does, on
    every backend, an entry with a NaN or a value under 2^-74, whose
    second half the TPU's arithmetic would flush (`_PAIR_FLOOR`).
  * Consistency is by content-version, not locks: `Series.snapshot()`
    captures (data, version) atomically; at query time every requested
    series' version is read and compared with the snapshot's, and the
    window bounds of all of them come from the snapshot's own
    timestamps (a host copy) in one vectorised binary search — so what
    is served is what each series held at one instant of the request.
    A version mismatch on ANY requested series is a miss — the planner
    falls back to the host build path, and the entry is queued for a
    background refresh (the maintenance thread calls `refresh()`), so
    ingest-heavy metrics never pay rebuild costs on the query path.
  * Byte-budgeted LRU (`tsd.query.device_cache.mb`): entries evict
    least-recently-used first; metrics larger than the whole budget (or
    `tsd.query.device_cache.build_max_points`) are never cached — the
    streaming path owns beyond-memory scans.

Only the float lane is cached: the grouped downsample pipeline (the hot
path this accelerates) always runs in float (Downsampler.java:257 —
downsampled values are doubles).  Queries needing the exact-int lane
(raw union aggregation of all-int series) take the host path unchanged.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field

import numpy as np

_LOG = logging.getLogger("device_cache")

# The padding contract (sentinel + pow2 growth) MUST stay bit-identical to
# build_batch's — the prefix downsample path relies on cached rows sorting
# exactly like host-built rows.  PAD_TS mirrors ops.pipeline.PAD_TS and
# pad_pow2 is lazy-imported from ops.downsample inside the functions that
# use it: a module-level import would pull jax into every storage import,
# and this module must stay importable numpy-only (tests assert the PAD_TS
# parity so the mirror cannot drift silently).
PAD_TS = np.iinfo(np.int64).max
# Pad sentinel for int32 pre-compacted batches (the ts_base gather):
# mirrors ops.downsample._I32_PAD under the same no-jax-import rule;
# the parity test pins the two (clean-batch detection and pad sorting
# both depend on the exact value).
I32_PAD_TS = np.int32(2**31 - 2)
# two uint32 words of the timestamp + two float32 parts of the value (or
# the one float64): what an entry pins, and what the budget counts
_BYTES_PER_POINT = 16


def _pad_pow2(n: int, floor: int = 8) -> int:
    from opentsdb_tpu.ops.downsample import pad_pow2
    return pad_pow2(n, floor)


@dataclass
class _Entry:
    store: object      # the MemStore snapshotted (raw store or a rollup
    #                    lane) — entries are keyed by (store, metric), and
    #                    the strong ref also keeps id(store) stable
    metric: int
    row: dict          # SeriesKey -> row index
    series_objs: list  # row -> the Series OBJECT snapshotted: identity is
    #                    part of validity — a deleted+recreated series has an
    #                    equal key and a restarted version counter, and must
    #                    not validate against the old snapshot
    versions: np.ndarray  # [S] int64, row -> version at snapshot
    offsets: np.ndarray  # [S+1] int64 start offsets into the buffers
    ts_host: np.ndarray  # host [P] int64: the timestamps as pinned, for
    #                      the window bounds of every row in one pass
    pinned: tuple      # what the chip reads (`_pin_columns`): device [P]
    #                    uint32 low words of the timestamps, [P] uint32
    #                    high words (pow2-padded, pads PAD_TS's own words
    #                    0xFFFFFFFF / 0x7FFFFFFF), and the values' parts:
    #                    [P] float32 first and second half of each double
    #                    as the device itself splits it — or the one [P]
    #                    float64 where those two would not give every
    #                    value back.  16 bytes a point either way.
    nbytes: int = 0
    tick: int = 0      # LRU clock
    stale: bool = field(default=False)
    # last few (series list, its rows): a planner that resolves a
    # selection once asks with the same list object every time
    rows_memo: list = field(default_factory=list)

    def find_rows(self, series_list) -> np.ndarray | None:
        """Row of every series, or None when one was born after the
        snapshot or deleted and recreated under its key (a fresh object
        with a restarted version counter): the snapshot is then invalid."""
        for known, rows in self.rows_memo:
            if known is series_list:
                return rows
        get, objs = self.row.get, self.series_objs
        rows = np.fromiter((get(s.key, -1) for s in series_list), np.int64,
                           len(series_list))
        if (rows < 0).any() or any(
                objs[r] is not s for r, s in zip(rows.tolist(), series_list)):
            return None
        return rows

    def rows_of(self, series_list) -> np.ndarray | None:
        """find_rows, remembered for the next request with this list."""
        rows = self.find_rows(series_list)
        if rows is not None and not any(
                known is series_list for known, _ in self.rows_memo):
            self.rows_memo = [(series_list, rows)] + self.rows_memo[:3]
        return rows


@dataclass
class WindowBounds:
    """Where each asked series' window lies in one entry's buffers."""
    entry: _Entry
    starts: np.ndarray   # [S] int64 buffer offsets
    lengths: np.ndarray  # [S] int64 points in the window

    def take(self, rows: np.ndarray) -> "WindowBounds":
        """The bounds of the rows picked by a mask or an index vector."""
        return WindowBounds(self.entry, self.starts[rows],
                            self.lengths[rows])


# effects: reads-only
def _bounds(entry: _Entry, rows: np.ndarray | None, series_list,
            start_ms: int, end_ms: int) -> WindowBounds | None:
    """The windows of `series_list` (entry rows `rows`) in the entry's
    buffers, or None when the entry cannot serve them.  Consistency is
    by content version: the entry serves a series only while that
    series' version is the snapshot's, and then the bounds come from the
    snapshot's own timestamps — what is served is what the series held
    at one instant of this request."""
    if rows is None:
        return None
    now = np.fromiter((s.version for s in series_list), np.int64,
                      len(series_list))
    if not np.array_equal(now, entry.versions[rows]):
        return None
    seg_lo, seg_hi = entry.offsets[rows], entry.offsets[rows + 1]
    lo = _search_segments(entry.ts_host, seg_lo, seg_hi, start_ms, False)
    hi = _search_segments(entry.ts_host, seg_lo, seg_hi, end_ms, True)
    return WindowBounds(entry, lo, hi - lo)


def _search_segments(ts: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                     key: int, right: bool) -> np.ndarray:
    """np.searchsorted(ts[lo[i]:hi[i]], key, side) + lo[i] for every i at
    once: a binary search whose steps run over all segments together."""
    lo, hi = lo.copy(), hi.copy()
    last = len(ts) - 1
    while True:
        open_ = lo < hi
        if not open_.any():
            return lo
        mid = (lo + hi) >> 1
        at = ts[np.minimum(mid, last)]
        up = open_ & ((at <= key) if right else (at < key))
        lo = np.where(up, mid + 1, lo)
        hi = np.where(open_ & ~up, mid, hi)


class DeviceSeriesCache:
    """Byte-budgeted, version-validated device cache of metric columns."""

    def __init__(self, max_bytes: int, build_max_points: int = 200_000_000,
                 fix_duplicates: bool = True,
                 batch_max_bytes: int = 6 << 30):
        self.max_bytes = int(max_bytes)
        self.build_max_points = int(build_max_points)
        # The gather EXPANDS the packed buffer to a padded [S, N] batch;
        # row-length skew can make that much larger than the entry itself.
        # Batches estimated beyond this bound decline (the streaming path
        # serves them chunked instead of OOMing the device).
        self.batch_max_bytes = int(batch_max_bytes)
        # The store-wide duplicate policy: snapshots must normalize with
        # EXACTLY the policy reads use — with fix_duplicates off, a build
        # touching duplicate data must fail (and never silently dedup the
        # live series out from under fsck).
        self.fix_duplicates = bool(fix_duplicates)
        # keyed by (id(store), metric): the raw store and every rollup
        # lane share the metric-uid space but hold different data
        # guarded-by: _lock
        self._entries: dict[tuple, _Entry] = {}
        self._stale: dict[tuple, object] = {}  # key -> store  # guarded-by: _lock
        self._building: set[tuple] = set()  # guarded-by: _lock
        # keys whose entry the byte budget evicted and nothing rebuilt
        # since: a miss on one reads `evicted`, not `cold`
        self._evicted: set[tuple] = set()  # guarded-by: _lock
        self._lock = threading.Lock()
        self._tick = 0  # guarded-by: _lock
        # stats (surfaced via /api/stats)
        # guarded-by: _lock
        self.hits = 0
        self.misses = 0
        self.builds = 0
        self.evictions = 0

    # -- sizing ----------------------------------------------------------

    @property
    def bytes_used(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values())

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    # -- query path ------------------------------------------------------

    def batch_for(self, store, metric: int, series_list, start_ms: int,
                  end_ms: int, fix_duplicates: bool = True,
                  build: bool = True, ts_base: int | None = None,
                  bounds: WindowBounds | None = None):
        """Device [S, N] (ts, val, mask) for the series' windows, or None.

        A None return means cold/stale/over-budget — the caller uses its
        host build path.  `build=False` declines to construct a cold entry
        inline and queues it for the maintenance-thread `refresh()`
        instead — callers pass it when they have a cheaper cold path (the
        streaming scan overlaps transfer with compute; a blocking full-
        metric upload first would be strictly worse).  Staleness likewise
        only ever queues a background rebuild.

        `ts_base` (from ops.downsample.precompact_base) asks the gather
        to emit timestamps as int32 offsets from that base — the query
        dispatch then skips its per-point compaction pass entirely.  The
        caller guarantees the window grid spans < 2^31 ms from the base;
        pads land at the int32 clip ceiling (sorted past every edge,
        mirroring the int64 PAD_TS contract).

        `bounds`, where the caller already asked `bounds_for` for this
        very request, is served as it stands: the entry it names is an
        immutable snapshot that was valid when the bounds were taken.
        """
        del fix_duplicates      # the snapshot was normalized at build
        ekey = (id(store), metric)
        if bounds is None:
            with self._lock:
                entry = self._entries.get(ekey)
                absent = None if entry is not None \
                    else self._absent_locked(ekey)
                if absent is not None and not build:
                    self._stale[ekey] = store
            if absent is not None:
                if not build or absent == "building":
                    self._miss(absent)
                    return None
                entry = self._build(store, metric)
                if entry is None:
                    self._miss(absent)
                    return None
            rows = entry.rows_of(series_list)
            bounds = _bounds(entry, rows, series_list, start_ms, end_ms)
            if bounds is None:
                self._mark_stale(ekey, entry)
                self._miss("rows" if rows is None else "stale")
                return None
        entry, starts, lengths = bounds.entry, bounds.starts, bounds.lengths
        s = len(series_list)
        n = _pad_pow2(max(int(lengths.max(initial=0)), 1))
        # ts8+val8+mask1, or ts4+val8+mask1 for int32 pre-compacted
        # batches — the budget must not decline batches the smaller
        # layout actually fits
        per_point = 13 if ts_base is not None else 17
        if s * n * per_point > self.batch_max_bytes:
            self._miss("batch")
            return None
        with self._lock:
            self._tick += 1
            entry.tick = self._tick
            self.hits += 1
        self._emit_hit()
        return _gather_windows(entry.pinned, starts, lengths, n, ts_base)

    def bounds_for(self, store, metric: int, series_list, start_ms: int,
                   end_ms: int) -> WindowBounds | None:
        """Every asked series' window in a valid entry's buffers, in one
        vectorised pass — or None (no entry, or one that went stale and
        is now queued for a rebuild: the caller walks the series).  No
        hit or miss is counted here."""
        ekey = (id(store), metric)
        with self._lock:
            entry = self._entries.get(ekey)
        if entry is None:
            return None
        bounds = _bounds(entry, entry.rows_of(series_list), series_list,
                         start_ms, end_ms)
        if bounds is None:
            self._mark_stale(ekey, entry)
        return bounds

    # effects: reads-only
    def peek_bounds(self, store, metric: int, series_list, start_ms: int,
                    end_ms: int) -> WindowBounds | None:
        """bounds_for for the explain engine: nothing is remembered
        and a stale entry is not queued."""
        with self._lock:
            entry = self._entries.get((id(store), metric))
        if entry is None:
            return None
        return _bounds(entry, entry.find_rows(series_list), series_list,
                       start_ms, end_ms)

    # effects: reads-only
    def peek(self, store, metric: int, series_list, start_ms: int,
             end_ms: int, fix_duplicates: bool = True,
             build: bool = True, ts_base: int | None = None) -> bool:
        """Would :meth:`batch_for` return a device batch for this
        request, as of now — READ-ONLY: no gather dispatch, no cold
        inline build, no staleness marks, no hit/miss accounting.  The
        EXPLAIN engine's arm of the routing decision
        (query/plandecision.py).

        The cold-with-``build`` arm predicts the inline snapshot build
        from its size/identity preconditions (series set, point
        budget, byte budget) without snapshotting; duplicate data that
        would only surface inside ``Series.snapshot`` is approximated
        by the same per-series ``window_bounds`` probe ``batch_for``
        itself uses."""
        ekey = (id(store), metric)
        with self._lock:
            entry = self._entries.get(ekey)
            building = ekey in self._building
        if entry is not None:
            bounds = _bounds(entry, entry.find_rows(series_list),
                             series_list, start_ms, end_ms)
            if bounds is None:
                return False
            max_len = int(bounds.lengths.max(initial=0))
        else:
            if not build or building:
                return False
            # the _build_guarded preconditions, probed without copying
            series_objs = store.series_for_metric(metric)
            if not series_objs:
                return False
            total = sum(len(s) for s in series_objs)
            nbytes = _pad_pow2(max(total, 1), floor=1024) \
                * _BYTES_PER_POINT
            if total > self.build_max_points or nbytes > self.max_bytes:
                return False
            rows = {s.key: s for s in series_objs}
            max_len = 0
            for series in series_list:
                if rows.get(series.key) is not series:
                    return False
                try:
                    lo, hi, _ = series.window_bounds(
                        start_ms, end_ms, fix_duplicates)
                except ValueError:
                    return False    # unresolved duplicates: host path
                max_len = max(max_len, hi - lo)
        n = _pad_pow2(max(int(max_len), 1))
        per_point = 13 if ts_base is not None else 17
        return len(series_list) * n * per_point <= self.batch_max_bytes

    # -- build / refresh -------------------------------------------------

    # tier-labeled prometheus families shared with the partial-
    # aggregate cache (storage/agg_cache.py): the same
    # tsd.query.cache.* names, tier="device_series" — so one scrape
    # shows every cache layer side by side (before this, the tallies
    # only lived in collect_stats()).

    @staticmethod
    def _emit_hit() -> None:
        from opentsdb_tpu.obs.registry import REGISTRY
        REGISTRY.counter(
            "tsd.query.cache.hits",
            "Query-cache hits, by tier").labels(
                tier="device_series").inc()

    @staticmethod
    def _emit_miss(reason: str) -> None:
        from opentsdb_tpu.obs.registry import REGISTRY
        REGISTRY.counter(
            "tsd.query.cache.misses",
            "Query-cache misses, by tier").labels(
                tier="device_series").inc()
        REGISTRY.counter(
            "tsd.query.device_cache.miss_reason",
            "Device-cache misses, by reason").labels(reason=reason).inc()

    @staticmethod
    def _emit_evictions(n: int) -> None:
        from opentsdb_tpu.obs.registry import REGISTRY
        REGISTRY.counter(
            "tsd.query.cache.evictions",
            "Query-cache evictions, by tier").labels(
                tier="device_series").inc(n)

    def _emit_bytes(self) -> None:
        from opentsdb_tpu.obs.registry import REGISTRY
        REGISTRY.gauge(
            "tsd.query.cache.bytes",
            "Query-cache resident bytes, by tier").labels(
                tier="device_series").set(self.bytes_used)
        REGISTRY.gauge(
            "tsd.query.cache.entries",
            "Query-cache resident entries, by tier").labels(
                tier="device_series").set(len(self))

    def _miss(self, reason: str) -> None:
        with self._lock:
            self.misses += 1
        self._emit_miss(reason)

    def _absent_locked(self, ekey: tuple) -> str:
        """Why a key has no entry: `building` (a build of it is under
        way), `evicted` (the budget evicted it and nothing rebuilt it
        since) or `cold` (never built, dropped by invalidate, or not
        admitted)."""
        if ekey in self._building:
            return "building"
        return "evicted" if ekey in self._evicted else "cold"

    def _mark_stale(self, ekey: tuple, entry: _Entry) -> None:
        with self._lock:
            entry.stale = True
            self._stale[ekey] = entry.store

    def _build(self, store, metric: int):
        """Snapshot every series of `metric` into device buffers.

        At most one build per (store, metric) runs at a time: concurrent
        queries on the same cold metric miss fast (host path) instead of
        each paying the snapshot + upload."""
        ekey = (id(store), metric)
        with self._lock:
            if ekey in self._building:
                return None
            self._building.add(ekey)
        try:
            return self._build_guarded(store, metric)
        finally:
            with self._lock:
                self._building.discard(ekey)

    def _build_guarded(self, store, metric: int):
        t0 = time.perf_counter()
        series_list = store.series_for_metric(metric)
        if not series_list:
            return None
        total = sum(len(s) for s in series_list)
        nbytes = _pad_pow2(max(total, 1), floor=1024) * _BYTES_PER_POINT
        if total > self.build_max_points or nbytes > self.max_bytes:
            return None
        parts_ts, parts_val, versions, row = [], [], [], {}
        offsets = np.zeros(len(series_list) + 1, np.int64)
        try:
            for i, series in enumerate(series_list):
                ts, val, version = series.snapshot(self.fix_duplicates)
                parts_ts.append(ts)
                parts_val.append(val)
                versions.append(version)
                row[series.key] = i
                offsets[i + 1] = offsets[i] + len(ts)
        except ValueError:
            return None     # duplicate data pending fsck: don't cache it
        total = int(offsets[-1])
        p = _pad_pow2(max(total, 1), floor=1024)
        ts_buf = np.full(p, PAD_TS, np.int64)
        val_buf = np.zeros(p, np.float64)
        if total:
            ts_buf[:total] = np.concatenate(parts_ts)
            val_buf[:total] = np.concatenate(parts_val)
        entry = _Entry(store=store, metric=metric, row=row,
                       series_objs=series_list,
                       versions=np.asarray(versions, np.int64),
                       offsets=offsets, ts_host=ts_buf,
                       pinned=_pin_columns(ts_buf, val_buf),
                       nbytes=p * _BYTES_PER_POINT)
        ekey = (id(store), metric)
        with self._lock:
            evicted_before = self.evictions
            self._evict_for_locked(entry.nbytes)
            evicted = self.evictions - evicted_before
            self._tick += 1
            entry.tick = self._tick
            self._entries[ekey] = entry
            self._stale.pop(ekey, None)
            self._evicted.discard(ekey)
            self.builds += 1
        if evicted:
            self._emit_evictions(evicted)
        self._emit_bytes()
        _LOG.info("pinned metric %d: %d series, %d points, %d MiB in "
                  "%.2f s (%d evicted)", metric, len(series_list), total,
                  entry.nbytes >> 20, time.perf_counter() - t0, evicted)
        return entry

    def _evict_for_locked(self, incoming_bytes: int) -> None:
        used = sum(e.nbytes for e in self._entries.values())
        while self._entries and used + incoming_bytes > self.max_bytes:
            victim = min(self._entries.values(), key=lambda e: e.tick)
            vkey = (id(victim.store), victim.metric)
            self._entries.pop(vkey)
            self._evicted.add(vkey)
            used -= victim.nbytes
            self.evictions += 1

    def refresh(self, store=None, max_rebuilds: int = 4) -> int:
        """Rebuild up to `max_rebuilds` stale entries (maintenance hook).

        Runs off the query path: the background thread pays the re-upload
        so queries only ever see a fast hit or a fast miss.  Each stale
        key remembers its own store (raw store or rollup lane); the
        `store` argument is accepted for call-site symmetry but unused.
        """
        del store
        with self._lock:
            pending = list(self._stale.items())[:max_rebuilds]
            for ekey, _ in pending:
                self._stale.pop(ekey, None)
            # a key another build already holds is left to that build;
            # the others are held as building from the moment their
            # entry is dropped, so a miss in between reads `building`
            pending = [(k, st) for k, st in pending
                       if k not in self._building]
            for ekey, _ in pending:
                self._entries.pop(ekey, None)
                self._building.add(ekey)
        done = 0
        for ekey, st in pending:
            try:
                if self._build_guarded(st, ekey[1]) is not None:
                    done += 1
            finally:
                with self._lock:
                    self._building.discard(ekey)
        return done

    def invalidate(self, metric: int | None = None) -> None:
        """Drop one metric's entry, or everything (/api/dropcaches)."""
        with self._lock:
            if metric is None:
                self._entries.clear()
                self._stale.clear()
                self._evicted.clear()
            else:
                for ekey in [k for k in self._entries if k[1] == metric]:
                    self._entries.pop(ekey, None)
                for ekey in [k for k in self._stale if k[1] == metric]:
                    self._stale.pop(ekey, None)
                self._evicted = {k for k in self._evicted if k[1] != metric}
        self._emit_bytes()

    def collect_stats(self) -> dict:
        return {
            "tsd.query.device_cache.hits": float(self.hits),
            "tsd.query.device_cache.misses": float(self.misses),
            "tsd.query.device_cache.builds": float(self.builds),
            "tsd.query.device_cache.evictions": float(self.evictions),
            "tsd.query.device_cache.entries": float(len(self)),
            "tsd.query.device_cache.bytes": float(self.bytes_used),
        }


def _to_device(arr: np.ndarray):
    import jax
    return jax.device_put(arr)


# One TPU tile row.  The 1-D [P] buffer viewed [P/128, 128] is a bitcast
# on the chip (a 256-wide or wider view compiles to a copy of the whole
# buffer), and a row of that view is the unit the chip copies in one
# piece.  Origin: PR 25's race on a v5e (PERF.md §6) — whole tile rows
# plus a shift won or tied at every shape against one index per point
# (4000 x 8192: 42 ms against 2930 ms) and against one loop step per
# series row, so there is no second form and no crossover.
_TILE = 128

# compiled programs of this module: the batch assembly keyed by (padded
# N, compaction flag), the build-time value split under "split" — the
# closures read only module constants (PAD_TS / I32_PAD_TS / _TILE), so
# there is nothing to invalidate
# cache: gather-programs invalidated-by: none
_GATHER_CACHE: dict = {}


def _join_values(parts):
    """float64 from the pinned parts of a value: the first part widened,
    each further part added where it is not zero (so a part of zeros
    changes no bit of the sum, the sign of a zero included).  The one
    recombination: the build's exactness check and the batch assembly
    both run it."""
    import jax.numpy as jnp

    val = parts[0].astype(jnp.float64)
    for part in parts[1:]:
        val = jnp.where(part == 0, val, val + part.astype(jnp.float64))
    return val


def _split_program():
    """The jitted float64[P] -> (hi float32[P], lo float32[P], exact)
    run once per build: the two 32-bit parts of every value as THIS
    backend's own arithmetic makes them, and whether `_join_values` gives
    every value back.  On a TPU a float64 IS such a pair, so `hi` is its
    first half as stored, `lo` its second, and `exact` holds; on a
    backend with real 64-bit floats it holds for values of 48 bits or
    fewer (every integer gauge) and fails for the rest."""
    import jax
    import jax.numpy as jnp

    fn = _GATHER_CACHE.get("split")
    if fn is not None:
        return fn

    def split(v):
        hi = v.astype(jnp.float32)
        lo = jnp.where(jnp.isfinite(hi),
                       (v - hi.astype(jnp.float64)).astype(jnp.float32), 0)
        return hi, lo, jnp.all(_join_values((hi, lo)) == v)
    # memoized in _GATHER_CACHE just above: constructed once a process
    fn = jax.jit(split)  # tsdblint: disable=jax-jit-per-call
    _GATHER_CACHE["split"] = fn
    return fn


# Below this magnitude a double's second float32 half can be a float32
# denormal, which the TPU's arithmetic flushes to zero where its copies
# do not (measured: PERF.md section 6, PR 30): 2^-74, since the half is
# a multiple of the double's last bit, 2^-52 of its leading one, and a
# float32 is normal down to 2^-126.
_PAIR_FLOOR = 2.0 ** -74


def _pin_values(val_buf: np.ndarray) -> tuple:
    """Host float64 values -> their pinned parts on the device: uploaded
    once as float64, split by the device's own program, and the upload
    dropped — unless the two parts would not give every value back bit
    for bit, where the upload itself stays as the only part: a backend
    with real 64-bit floats holding a value of more than 48 bits (the
    program's own check), a NaN, or a value under `_PAIR_FLOOR`."""
    val_dev = _to_device(np.ascontiguousarray(val_buf, np.float64))
    size = np.abs(val_buf)
    if size[size < _PAIR_FLOOR].any():      # one of them is not zero
        return (val_dev,)
    hi, lo, exact = _split_program()(val_dev)
    return (hi, lo) if bool(exact) else (val_dev,)


def _pin_columns(ts_buf: np.ndarray, val_buf: np.ndarray) -> tuple:
    """Host int64 timestamps and float64 values -> what is pinned:
    (ts_lo uint32, ts_hi uint32, value parts), each on the device and as
    long as the input.  The timestamps' two words are cut on the host:
    exact, and nothing 64 bits wide is uploaded for them."""
    parts = _pin_values(val_buf)
    words = np.ascontiguousarray(ts_buf, "<i8").view("<u4").reshape(-1, 2)
    return (_to_device(np.ascontiguousarray(words[:, 0])),
            _to_device(np.ascontiguousarray(words[:, 1])), parts)


def _gather_program(n: int, compact: bool):
    """The jitted (ts_lo, ts_hi, value parts, starts, lengths, base) ->
    (ts, val, mask) batch assembly for padded row length `n`, memoized
    per (n, compact); jit itself specializes it per buffer length, row
    count and the parts' types."""
    import jax
    import jax.numpy as jnp

    key = (n, compact)
    fn = _GATHER_CACHE.get(key)
    if fn is not None:
        return fn
    # tile rows that cover [start, start + n) wherever start falls in
    # its first tile: lane offset <= _TILE - 1
    tiles = (n + 2 * _TILE - 2) // _TILE

    def gather(tl, th, vals, st, ln, base):
        m = jnp.arange(n, dtype=jnp.int64)[None, :] < ln[:, None]
        st = st.astype(jnp.int32 if tl.shape[0] < 2**31 else jnp.int64)
        lane = st % _TILE
        # laid [tiles, S]: the [S, tiles] matrix's flatten takes XLA:TPU
        # tens of seconds to compile at S = 100 000
        rows = jnp.arange(tiles, dtype=st.dtype)[:, None] \
            + (st // _TILE)[None, :]

        def copy_rows(buf):
            if buf.shape[0] % _TILE:
                # never a cache entry (pow2 >= 1024 long): a caller's own
                # odd-length buffer, padded by a copy of it
                buf = jnp.pad(buf, (0, -buf.shape[0] % _TILE))
            # a row index past the buffer's end clamps on its own: what
            # it brings lies at or past start + length, under the mask
            x = jnp.take(buf.reshape(-1, _TILE), rows, axis=0, mode="clip")
            x = x.transpose(1, 0, 2).reshape(st.shape[0], tiles * _TILE)
            # out[i, j] = x[i, lane[i] + j]: a barrel shift, one bit of
            # the lane offset a step
            k = _TILE // 2
            while k:
                x = jnp.where(((lane & k) != 0)[:, None],
                              x[:, k:], x[:, :-k])
                k //= 2
            return x[:, :n]

        # the 64-bit forms exist on the [S, n] result alone
        stamps = (copy_rows(th).astype(jnp.int64) << 32) \
            | copy_rows(tl).astype(jnp.int64)
        if compact:
            off = jnp.clip(stamps - base, 0, I32_PAD_TS).astype(jnp.int32)
            ts = jnp.where(m, off, I32_PAD_TS)
        else:
            ts = jnp.where(m, stamps, PAD_TS)
        val = jnp.where(
            m, _join_values(tuple(copy_rows(v) for v in vals)), 0.0)
        return ts, val, m
    # memoized per (N, compaction) in _GATHER_CACHE just above — the
    # wrapper is constructed once per padded batch shape, not per call
    fn = jax.jit(gather)  # tsdblint: disable=jax-jit-per-call
    _GATHER_CACHE[key] = fn
    return fn


def _gather_windows(pinned: tuple, starts, lengths, n: int,
                    ts_base: int | None = None):
    """One-dispatch on-device batch assembly from what `_pin_columns`
    pinned.

    out[i, j] = buf[starts[i] + j] masked to j < lengths[i]; pads mirror
    build_batch (PAD_TS timestamps keep rows sorted for the prefix path).
    Every row is one contiguous run of the buffer (series are
    concatenated whole at build), so it is copied as whole 128-element
    tile rows and shifted to its start — no per-point index — from each
    32-bit buffer, and the int64 / float64 the caller gets is put
    together on the [S, n] result.  `starts` may be anything where
    `lengths` is 0, and a row may run past the buffer's end beyond its
    length: neither is read under the mask.  Compiled once per (buffer
    length, S, N) — buffer and N pow2-padded.

    With `ts_base`, timestamps come back as int32 offsets from the base
    (the compaction fused into this program — the query dispatch already
    paying for this data pass makes the sub+cast free, r4 attribution):
    pads sit at the int32 clip ceiling, past every window edge.
    """
    import jax.numpy as jnp

    base = jnp.asarray(0 if ts_base is None else ts_base, jnp.int64)
    return _gather_program(n, ts_base is not None)(
        *pinned, jnp.asarray(starts), jnp.asarray(lengths), base)

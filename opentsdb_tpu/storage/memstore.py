"""Columnar, chunk-aligned in-memory series store — the storage engine.

This plays the role HBase + the row-key/qualifier codec played for the
reference (schema contract: SURVEY.md §2.6; RowSeq/Span assembly:
/root/reference/src/core/RowSeq.java, Span.java).  Design differences are
deliberate and TPU-first:

  * Series are identified by (metric_uid, sorted (tagk,tagv) uid pairs) —
    the same logical row-key identity, without byte-encoded rows.
  * Data is columnar per series: int64 ms timestamps, float64 values and an
    int-ness bitmask in growable numpy buffers, so query assembly is a zero-
    copy slice + pad into device batches instead of per-cell decoding.
  * Out-of-order and duplicate points are normalized lazily at read time
    (sort + last-write-wins dedup), the job CompactionQueue.java (:340) and
    AppendDataPoints.java did at the storage layer.
  * A salt-equivalent shard id (hash of the series key, RowKey.java:141) is
    precomputed per series for mesh sharding.

Annotations (qualifier prefix 0x01, src/meta/Annotation.java:86) are stored
side-band per series key, collected during query assembly exactly like
SaltScanner collects them per row (SaltScanner.java:425-448).
"""

from __future__ import annotations

import itertools
import logging
import threading
import zlib

_LOG = logging.getLogger("storage")
from dataclasses import dataclass, field
from typing import Callable, Iterable, Sequence

import numpy as np

MAX_NUM_TAGS = 8        # Const.java:28
CHUNK_SPAN_MS = 3_600_000  # Const.java:95 — 3600s row span, kept for layout


@dataclass(frozen=True)
class SeriesKey:
    """Logical identity of one time series: metric UID + sorted tag UID pairs."""
    metric: int
    tags: tuple[tuple[int, int], ...]  # sorted (tagk_uid, tagv_uid)

    @staticmethod
    def make(metric: int, tags: dict[int, int]) -> "SeriesKey":
        return SeriesKey(metric, tuple(sorted(tags.items())))

    def tsuid(self, metric_width: int = 3, tagk_width: int = 3,
              tagv_width: int = 3) -> str:
        """Hex TSUID: metric + tagk/tagv pairs (UniqueId.getTSUIDFromKey)."""
        out = [self.metric.to_bytes(metric_width, "big").hex()]
        for k, v in self.tags:
            out.append(k.to_bytes(tagk_width, "big").hex())
            out.append(v.to_bytes(tagv_width, "big").hex())
        return "".join(out).upper()

    def salt(self, buckets: int = 20) -> int:
        """Deterministic shard id, the salt-bucket equivalent (RowKey.java:141)."""
        h = zlib.crc32(repr((self.metric, self.tags)).encode())
        return h % buckets


class Series:
    """One series' columnar data: growable timestamp/value/int-ness arrays.

    Values live in parallel float64 + int64 buffers: the int64 side keeps
    Java-long exactness above 2^53 for integer points (the reference stores
    VLE-encoded longs, Internal.vleEncodeLong :963); the float side feeds the
    TPU float pipeline without a per-query cast.
    """

    __slots__ = ("key", "_ts", "_val", "_ival", "_isint", "_n", "_sorted",
                 "_lock", "shard", "_version")

    INITIAL_CAPACITY = 64

    def __init__(self, key: SeriesKey, shard: int = 0):
        self.key = key
        self.shard = shard
        # guarded-by: _lock
        self._ts = np.empty(self.INITIAL_CAPACITY, dtype=np.int64)
        self._val = np.empty(self.INITIAL_CAPACITY, dtype=np.float64)  # guarded-by: _lock
        self._ival = np.zeros(self.INITIAL_CAPACITY, dtype=np.int64)  # guarded-by: _lock
        self._isint = np.empty(self.INITIAL_CAPACITY, dtype=bool)  # guarded-by: _lock
        self._n = 0  # guarded-by: _lock
        self._sorted = True  # guarded-by: _lock
        self._lock = threading.Lock()
        # Monotone content-version: bumped by every mutation that changes
        # visible data (appends, restore, deletes, dedup).  The device
        # series cache snapshots (data, version) atomically and treats any
        # later mismatch as staleness — see storage/device_cache.py.
        self._version = 0  # guarded-by: _lock

    def __len__(self) -> int:
        return self._n

    @property
    def dirty(self) -> bool:
        return not self._sorted

    @property
    def version(self) -> int:
        return self._version

    def _grow_locked(self, need: int) -> None:
        new_cap = max(need, len(self._ts) * 2, self.INITIAL_CAPACITY)
        self._ts = np.resize(self._ts, new_cap)
        self._val = np.resize(self._val, new_cap)
        self._ival = np.resize(self._ival, new_cap)
        self._isint = np.resize(self._isint, new_cap)

    def append(self, ts_ms: int, value, is_int: bool) -> None:
        with self._lock:
            if self._n == len(self._ts):
                self._grow_locked(self._n + 1)
            if self._sorted and self._n and ts_ms <= self._ts[self._n - 1]:
                self._sorted = False
            self._ts[self._n] = ts_ms
            self._val[self._n] = float(value)
            self._ival[self._n] = int(value) if is_int else 0
            self._isint[self._n] = is_int
            self._n += 1
            self._version += 1

    def append_batch(self, ts_ms: np.ndarray, values: np.ndarray,
                     is_int: np.ndarray | bool,
                     ival: np.ndarray | None = None) -> None:
        """Bulk ingest (TextImporter-style); arrays must be 1-D, same length.

        Pass `ival` (exact int64 values where is_int) for mixed batches
        whose integer points exceed 2^53 — a float64 `values` round-trip
        would lose them (Java-long exactness, Internal.vleEncodeLong :963).
        """
        m = len(ts_ms)
        if m == 0:
            return
        values = np.asarray(values)
        if np.isscalar(is_int) or isinstance(is_int, bool):
            isint = np.full(m, bool(is_int))
        else:
            isint = np.asarray(is_int, dtype=bool)
        if ival is not None:
            ival = np.asarray(ival, dtype=np.int64)
        elif np.issubdtype(values.dtype, np.integer):
            ival = values
        else:
            # Float-typed arrays may still carry integer points; the int
            # column must hold their exact values wherever isint is set.
            ival = np.where(isint, values.astype(np.int64), 0)
        # pure input-only work stays outside the lock — and outside the
        # write transition: a raise here must not interleave the column
        # writes below (failure_atomicity's all-writes-after-fallible)
        incoming_sorted = bool(m == 1 or bool(np.all(np.diff(ts_ms) > 0)))
        with self._lock:
            need = self._n + m
            if need > len(self._ts):
                self._grow_locked(need)
            self._ts[self._n:need] = ts_ms
            self._val[self._n:need] = values
            self._ival[self._n:need] = ival
            self._isint[self._n:need] = isint
            if self._sorted and (not incoming_sorted or
                                 (self._n and ts_ms[0] <= self._ts[self._n - 1])):
                self._sorted = False
            self._n = need
            self._version += 1

    def normalize(self, fix_duplicates: bool = True) -> None:
        """Sort by timestamp, resolving duplicates last-write-wins.

        The read-time equivalent of compaction's heap-merge + dedup
        (CompactionQueue.java:499 mergeDatapoints, policy
        tsd.storage.fix_duplicates).  With fix_duplicates False, duplicate
        timestamps raise like the reference's IllegalDataException.
        """
        with self._lock:
            self._normalize_locked(fix_duplicates)

    # effects: canonicalize
    def _normalize_locked(self, fix_duplicates: bool) -> None:
        # _sorted means strictly increasing (append flags <=-ties as dirty),
        # so a sorted series has no duplicates either — nothing to do.
        if self._sorted:
            return
        n = self._n
        # stable sort keeps insertion order within equal timestamps, so the
        # last write for a timestamp is the last element of its run.
        order = np.argsort(self._ts[:n], kind="stable")
        self._ts[:n] = self._ts[:n][order]
        self._val[:n] = self._val[:n][order]
        self._ival[:n] = self._ival[:n][order]
        self._isint[:n] = self._isint[:n][order]
        # Dedup BEFORE declaring the series clean: with fix_duplicates off
        # _dedup_sorted_locked raises, and the series must stay dirty so later reads
        # keep raising and fsck can still see + repair the duplicate.
        self._dedup_sorted_locked(fix_duplicates)
        self._sorted = True

    def _dedup_sorted_locked(self, fix_duplicates: bool) -> None:
        n = self._n
        if n < 2:
            return
        ts = self._ts[:n]
        dup = ts[1:] == ts[:-1]
        if not dup.any():
            return
        if not fix_duplicates:
            idx = int(np.argmax(dup))
            raise ValueError(
                "Duplicate timestamp %d in series %s (set "
                "tsd.storage.fix_duplicates=true to resolve)"
                % (int(ts[idx]), self.key))
        # sized by the series' own resident point count, not by any
        # request field  # tsdblint: disable=taint-unsanitized-alloc
        keep = np.ones(n, dtype=bool)
        keep[:-1] = ~dup  # keep the LAST point of each duplicate run
        m = int(keep.sum())
        self._ts[:m] = ts[keep]
        self._val[:m] = self._val[:n][keep]
        self._ival[:m] = self._ival[:n][keep]
        self._isint[:m] = self._isint[:n][keep]
        self._n = m
        self._version += 1

    def window(self, start_ms: int, end_ms: int, fix_duplicates: bool = True
               ) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Return copies of (ts, float_vals, int_vals, is_int) for
        start_ms <= ts <= end_ms.

        Copies, not views: normalize() mutates the buffers in place and a
        background compaction flush may run while a query thread reads.
        Normalization and the binary search happen under one lock hold so a
        concurrent out-of-order append cannot invalidate the sort mid-read.
        """
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            return (self._ts[lo:hi].copy(), self._val[lo:hi].copy(),
                    self._ival[lo:hi].copy(), self._isint[lo:hi].copy())

    def _window_bounds_locked(self, start_ms: int, end_ms: int,
                              fix_duplicates: bool) -> tuple[int, int]:
        """(lo, hi) buffer indexes of [start_ms, end_ms] — callers hold
        the lock.  The single definition of the window bound semantics
        shared by window(), window_count(), window_chunk() and
        delete_range()."""
        self._normalize_locked(fix_duplicates)
        n = self._n
        lo = int(np.searchsorted(self._ts[:n], start_ms, side="left"))
        hi = int(np.searchsorted(self._ts[:n], end_ms, side="right"))
        return lo, hi

    def window_bounds(self, start_ms: int, end_ms: int,
                      fix_duplicates: bool = True) -> tuple[int, int, int]:
        """(lo, hi, version) for [start_ms, end_ms] under one lock hold.

        The version lets the device cache validate that its snapshot still
        matches the live series AND that (lo, hi) index that snapshot: both
        are taken under the same lock, so no append can slip between them.
        """
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            return lo, hi, self._version

    def snapshot(self, fix_duplicates: bool = True
                 ) -> tuple[np.ndarray, np.ndarray, int]:
        """Normalized (ts, float_vals, version) copies under one lock hold.

        The device-cache build path: the returned version identifies
        exactly this content — any later mutation bumps it.
        """
        with self._lock:
            self._normalize_locked(fix_duplicates)
            n = self._n
            return (self._ts[:n].copy(), self._val[:n].copy(),
                    self._version)

    def window_count(self, start_ms: int, end_ms: int,
                     fix_duplicates: bool = True) -> int:
        """Points in [start_ms, end_ms] without materializing them
        (budget charging / streaming-path planning)."""
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            return hi - lo

    def window_views(self, start_ms: int, end_ms: int,
                     fix_duplicates: bool = True
                     ) -> tuple[np.ndarray, np.ndarray, int]:
        """(ts, float_vals, version) of [start_ms, end_ms] as VIEWS of the
        live buffers, taken under one lock hold — the streaming scan's
        bulk read (storage/chunk_pack.py), which copies out of them
        without the lock.

        Such a reader must re-read `version` AFTER each copy and drop
        what it copied when the version has moved: every mutation that
        moves stored points bumps the version BEFORE it moves them (an
        append writes past the views, and bumps it before any sort or
        dedup its point makes needful), so an unchanged version says
        the copy saw the points as they were when the bounds were taken.
        A grown buffer is a new array; the views keep the old one alive.
        """
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            return self._ts[lo:hi], self._val[lo:hi], self._version

    def window_stats(self, start_ms: int, end_ms: int,
                     fix_duplicates: bool = True) -> tuple[int, bool]:
        """(point count, every value integer-typed) for the range,
        without materializing it — the batch builder sizes and types the
        padded arrays from this before the single-copy fill
        (window_into)."""
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            return hi - lo, bool(np.all(self._isint[lo:hi]))

    def window_into(self, start_ms: int, end_ms: int, fix_duplicates: bool,
                    ts_row: np.ndarray, val_row: np.ndarray,
                    mask_row: np.ndarray, want_int: bool
                    ) -> tuple[int, bool]:
        """Copy this series' window STRAIGHT into pre-allocated batch row
        slices under one lock hold — the fused form of window() +
        build_batch's per-row pack, eliminating the intermediate copies
        (a 1M-point query pays ~25MB of window() copies it immediately
        repacks).  Returns (points written, int-contract held): the range
        can both grow AND change type between the caller's sizing pass
        and this one (no snapshot isolation, like the reference's scanner
        over live rows) — the count clamps to the row width, and when
        `want_int` but a float point has appeared in range, NOTHING is
        copied and ok_int=False tells the caller to rebuild its batch as
        float (reading _ival for a float point would silently yield 0).
        Tail padding is the CALLER's job."""
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            k = min(hi - lo, len(ts_row))
            if want_int and not bool(np.all(self._isint[lo:lo + k])):
                return 0, False
            ts_row[:k] = self._ts[lo:lo + k]
            src = self._ival if want_int else self._val
            val_row[:k] = src[lo:lo + k]
            mask_row[:k] = True
            return k, True

    def window_stride_timestamps(self, start_ms: int, end_ms: int,
                                 stride: int, fix_duplicates: bool = True
                                 ) -> np.ndarray:
        """Every stride-th timestamp in [start_ms, end_ms] — the streaming
        chunk-boundary positions, used by the planner's sketch-hazard
        estimate (O(points/stride), never materializes the window)."""
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            return self._ts[lo:hi:max(stride, 1)].copy()

    def window_chunk(self, start_ms: int, end_ms: int,
                     after_ts: int | None, limit: int,
                     fix_duplicates: bool = True
                     ) -> tuple[np.ndarray, np.ndarray]:
        """Copy up to `limit` window points with timestamp > `after_ts`
        (None = from the window start) — the streaming scan's cursor read,
        since storage/chunk_pack.py the lane for rows that moved mid-scan
        (a row whose version still is what it was when the scan took its
        bounds is copied in bulk out of window_views()).

        The cursor is a TIMESTAMP, not an index: concurrent out-of-order
        writes (or the dedup a normalize performs) shift buffer positions
        between calls, so an index cursor could double-read or skip
        pre-existing points.  Timestamp progression is monotone — each
        pre-existing point is returned at most once; a point landing
        behind the cursor mid-query is a new write, which the streaming
        pass's documented contract (like the reference's scanner over live
        rows, SaltScanner.java:269) already excludes from visibility
        guarantees.  Returns (ts, float_vals).
        """
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            n = self._n
            if after_ts is not None:
                lo = max(lo, int(np.searchsorted(self._ts[:n], after_ts,
                                                 side="right")))
            b = min(lo + max(limit, 0), hi)
            return self._ts[lo:b].copy(), self._val[lo:b].copy()

    def restore_arrays(self, ts: np.ndarray, val: np.ndarray,
                       ival: np.ndarray, isint: np.ndarray) -> None:
        """Load snapshot columns verbatim (persistence restore path).

        Replaces the series contents; the float and int columns are taken
        exactly as stored so no int<->float round trip occurs.
        """
        n = len(ts)
        # sortedness depends only on the incoming column: compute it
        # before the lock so the locked section is pure writes
        sorted_flag = bool(n <= 1 or bool(np.all(np.diff(ts) > 0)))
        with self._lock:
            if n > len(self._ts):
                self._grow_locked(n)
            # before the stored points move: window_views()' lock-free
            # readers tell a torn copy by it
            self._version += 1
            self._ts[:n] = ts
            self._val[:n] = val
            self._ival[:n] = ival
            self._isint[:n] = isint
            self._n = n
            self._sorted = sorted_flag

    def arrays(self) -> tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Copies of the full (ts, float_vals, int_vals, is_int) columns."""
        with self._lock:
            n = self._n
            return (self._ts[:n].copy(), self._val[:n].copy(),
                    self._ival[:n].copy(), self._isint[:n].copy())

    def delete_range(self, start_ms: int, end_ms: int,
                     fix_duplicates: bool = True) -> int:
        """Remove points with start_ms <= ts <= end_ms (query delete flag,
        TsdbQuery.setDelete / scanner DeleteRequest path)."""
        with self._lock:
            lo, hi = self._window_bounds_locked(start_ms, end_ms,
                                                fix_duplicates)
            n = self._n
            removed = hi - lo
            if removed <= 0:
                return 0
            keep = n - hi
            # before the stored points move (see restore_arrays)
            self._version += 1
            self._ts[lo:lo + keep] = self._ts[hi:n]
            self._val[lo:lo + keep] = self._val[hi:n]
            self._ival[lo:lo + keep] = self._ival[hi:n]
            self._isint[lo:lo + keep] = self._isint[hi:n]
            self._n = n - removed
            return removed

    @property
    def size_bytes(self) -> int:
        return self._n * (8 + 8 + 8 + 1)


@dataclass
class Annotation:
    """A note attached to a timespan, per-TSUID or global (meta/Annotation.java)."""
    start_time: int
    end_time: int = 0
    tsuid: str = ""
    description: str = ""
    notes: str = ""
    custom: dict[str, str] | None = None

    def to_json(self) -> dict:
        out = {
            "tsuid": self.tsuid,
            "startTime": self.start_time,
            "endTime": self.end_time,
            "description": self.description,
            "notes": self.notes,
            "custom": self.custom,
        }
        if not self.tsuid:
            out.pop("tsuid")
        return out


class CompactionQueue:
    """Tracks dirty (out-of-order) series and normalizes them in the background.

    Reference behavior: CompactionQueue.java (:57, flush :127) — a queue of
    dirty rows flushed by a background thread.  Here "compaction" is the
    sort+dedup normalization pass; data is already columnar.
    """

    def __init__(self, fix_duplicates: bool = True):
        self._dirty: dict[SeriesKey, Series] = {}  # guarded-by: _lock
        self._lock = threading.Lock()
        self.fix_duplicates = fix_duplicates
        self.compactions = 0
        self.errors = 0

    def add(self, series: Series) -> None:
        with self._lock:
            self._dirty[series.key] = series

    def flush(self, max_flushes: int | None = None) -> int:
        with self._lock:
            items = list(self._dirty.items())[:max_flushes]
            for key, _ in items:
                self._dirty.pop(key, None)
        for _, series in items:
            try:
                series.normalize(self.fix_duplicates)
                self.compactions += 1
            except ValueError as e:
                # Duplicate data with fix_duplicates off (CompactionQueue
                # error callback): log and move on; reads will surface the
                # error and fsck repairs it.
                self.errors += 1
                _LOG.error("Compaction failed: %s", e)
        return len(items)

    def __len__(self) -> int:
        with self._lock:
            return len(self._dirty)


class MemStore:
    """The series store: keyed columnar series + tag inverted index.

    Query-side role of SaltScanner/MultiGetQuery + the tsdb table: find series
    for a metric and tag constraints, hand back columnar windows.
    """

    def __init__(self, salt_buckets: int = 20, fix_duplicates: bool = True):
        self.salt_buckets = salt_buckets
        self.fix_duplicates = fix_duplicates
        # guarded-by: _lock
        self._series: dict[SeriesKey, Series] = {}
        self._by_metric: dict[int, set[SeriesKey]] = {}  # guarded-by: _lock
        # bumped whenever a series is born or deleted: the planner's
        # memoised series resolution is valid for one generation
        self.series_generation = 0  # guarded-by: _lock
        self._lock = threading.RLock()
        self.compaction_queue = CompactionQueue(fix_duplicates)
        # annotations: tsuid-keyed and global lists  # guarded-by: _lock
        self._annotations: dict[str, list[Annotation]] = {}
        self.datapoints_added = 0
        # data-mutation listeners, (metric, lo_ms, hi_ms) per write —
        # the partial-aggregate cache's incremental invalidation hook
        # (storage/agg_cache.py).  Notified AFTER the write lands
        # (write-then-mark): by the time the write is acked its mark
        # exists, so any cached artifact built from a pre-write read
        # fails its generation check — no acked write is ever served
        # stale.  (Mark-before-write had a hole: a snapshot taken
        # after the mark but before the write would carry the mark's
        # generation and dodge it forever.)  The ordering is a checked
        # contract: tools/lint/ordering.py fails the tree if any path
        # reaches a mark with its write undischarged.
        # order: memstore-write before memstore-mark
        # guarded-by: _lock
        self._mutation_listeners: list = []

    # -- write path --

    def add_mutation_listener(self, fn: Callable) -> None:
        """Register fn(metric_uid, lo_ms | None, hi_ms | None), called
        after every data mutation lands (None bounds = the whole
        metric; write-then-mark — see _mutation_listeners)."""
        with self._lock:
            self._mutation_listeners.append(fn)

    def notify_mutation(self, metric: int, lo_ms: int | None,
                        hi_ms: int | None) -> None:
        """Tell listeners a (metric, time-range) HAS changed — call
        after the mutation lands (see _mutation_listeners above).

        Also the public entry for out-of-band mutators (the query
        delete flag, fsck repairs) that bypass add_point/add_batch."""
        for fn in tuple(self._mutation_listeners):
            fn(metric, lo_ms, hi_ms)

    def get_or_create_series(self, key: SeriesKey) -> Series:
        with self._lock:
            return self._get_or_create_series_locked(key)

    def _get_or_create_series_locked(self, key: SeriesKey) -> Series:
        series = self._series.get(key)
        if series is None:
            series = Series(key, shard=key.salt(self.salt_buckets))
            self._series[key] = series
            self._by_metric.setdefault(key.metric, set()).add(key)
            self.series_generation += 1
        return series

    def add_point(self, key: SeriesKey, ts_ms: int, value: float,
                  is_int: bool) -> None:
        # counter bump shares the lookup's lock hold: one store-lock
        # acquisition per ingest call, not two
        with self._lock:
            series = self._get_or_create_series_locked(key)
            self.datapoints_added += 1
        series.append(ts_ms, value, is_int)          # order-event: memstore-write
        self.notify_mutation(key.metric, ts_ms, ts_ms)  # order-event: memstore-mark
        if series.dirty:
            self.compaction_queue.add(series)

    def add_batch(self, key: SeriesKey, ts_ms: np.ndarray, values: np.ndarray,
                  is_int: np.ndarray | bool,
                  ival: np.ndarray | None = None) -> None:
        with self._lock:
            series = self._get_or_create_series_locked(key)
            self.datapoints_added += len(ts_ms)
        series.append_batch(ts_ms, values, is_int, ival)  # order-event: memstore-write
        if len(ts_ms):
            self.notify_mutation(key.metric, int(np.min(ts_ms)),  # order-event: memstore-mark
                                 int(np.max(ts_ms)))
        if series.dirty:
            self.compaction_queue.add(series)

    # -- read path --

    def series_for_metric(self, metric: int) -> list[Series]:
        with self._lock:
            keys = self._by_metric.get(metric, set())
            return [self._series[k] for k in keys]

    def series_count_and_sample(self, metric: int,
                                limit: int) -> tuple[int, list[Series]]:
        """Series count + a bounded sample for a metric WITHOUT
        building the full per-metric list — the pre-admission
        cost-estimate path (tsd/admission.py) runs on every arrival
        and must hold the store lock for a bounded allocation, not an
        O(series-of-metric) copy."""
        with self._lock:
            keys = self._by_metric.get(metric, set())
            sample = [self._series[k]
                      for k in itertools.islice(keys, limit)]
            return len(keys), sample

    def select(self, metric: int,
               predicate: Callable[[SeriesKey], bool] | None = None) -> list[Series]:
        """All series of a metric passing a key predicate (tag-filter hook)."""
        out = []
        with self._lock:
            for key in self._by_metric.get(metric, ()):
                if predicate is None or predicate(key):
                    out.append(self._series[key])
        return out

    def get_series(self, key: SeriesKey) -> Series | None:
        with self._lock:
            return self._series.get(key)

    def all_series(self) -> list[Series]:
        with self._lock:
            return list(self._series.values())

    # -- annotations --

    def add_annotation(self, note: Annotation) -> None:
        with self._lock:
            self._annotations.setdefault(note.tsuid, []).append(note)

    def has_annotations(self) -> bool:
        """False while nothing was ever annotated: a wide query then
        skips its one lookup per tsuid."""
        with self._lock:
            return bool(self._annotations)

    def get_annotations(self, tsuid: str, start_ms: int, end_ms: int,
                        include_global: bool = False) -> list[Annotation]:
        out = []
        with self._lock:
            pools: list[list[Annotation]] = [self._annotations.get(tsuid, [])]
            if include_global and tsuid != "":
                pools.append(self._annotations.get("", []))
            for pool in pools:
                for note in pool:
                    if start_ms <= note.start_time <= end_ms:
                        out.append(note)
        out.sort(key=lambda a: a.start_time)
        return out

    def delete_annotation(self, tsuid: str, start_time: int) -> bool:
        with self._lock:
            pool = self._annotations.get(tsuid, [])
            before = len(pool)
            self._annotations[tsuid] = [a for a in pool
                                        if a.start_time != start_time]
            return len(self._annotations[tsuid]) != before

    def annotation_keys(self) -> list[str]:
        """Every tsuid holding annotations ("" = global)."""
        with self._lock:
            return list(self._annotations.keys())

    def delete_annotation_range(self, tsuids: Sequence[str] | None,
                                start_ms: int, end_ms: int,
                                global_notes: bool = False) -> int:
        deleted = 0
        with self._lock:
            keys: Iterable[str]
            if global_notes:
                keys = [""]
            elif tsuids:
                keys = tsuids
            else:
                keys = list(self._annotations.keys())
            for key in keys:
                pool = self._annotations.get(key, [])
                kept = [a for a in pool
                        if not (start_ms <= a.start_time <= end_ms)]
                deleted += len(pool) - len(kept)
                self._annotations[key] = kept
        return deleted

    # -- stats / admin --

    @property
    def num_series(self) -> int:
        with self._lock:
            return len(self._series)

    @property
    def total_datapoints(self) -> int:
        with self._lock:
            return sum(len(s) for s in self._series.values())

    @property
    def total_bytes(self) -> int:
        with self._lock:
            return sum(s.size_bytes for s in self._series.values())

    def drop_caches(self) -> None:
        pass  # no separate cache layer; present for /api/dropcaches parity

    def delete_series(self, key: SeriesKey) -> bool:
        with self._lock:
            series = self._series.pop(key, None)     # order-event: memstore-write
            if series is not None:
                self.series_generation += 1
                keys = self._by_metric.get(key.metric)
                if keys is not None:
                    keys.discard(key)
                    if not keys:
                        self._by_metric.pop(key.metric, None)
        if series is None:
            return False
        self.notify_mutation(key.metric, None, None)  # order-event: memstore-mark
        return True

"""The streamed scan's chunk packer: [rows, n] chunks out of the store by
bulk copies.

A streamed segment (query/planner.py::_stream_grouped, a tile of
ops/tiling.py::_stream_tile) folds a range too large to materialize as a
sequence of fixed-shape chunks: chunk k holds points [k*n, (k+1)*n) of
every series' window.  The packer takes each series' window bounds ONCE,
under that series' lock (Series.window_views), and then fills a chunk
with two np.concatenate calls over all rows instead of one locked cursor
read a series: the per-series Python, not the copying, was the cost.

The views are read WITHOUT the lock.  Every mutation of a series bumps
its version before it moves stored points (memstore.py), so a version
re-read after the copy tells exactly which rows may have been read torn
or stale; those rows are re-filled by the locked timestamp-cursor read
(Series.window_chunk) for this chunk and every later one.  For a row
that has not moved, index order and timestamp order are the same
sequence, so each pre-existing point is handed out at most once either
way — window_chunk's contract.  There is no snapshot isolation, as the
scan it models has none (SaltScanner.java:269).

Chunk buffers are reused: a packer fills _SETS buffer sets in turn and
refills one only after the device arrays uploaded from it are ready (the
caller hands them back through uploaded()); an upload may read the host
buffer after it returns.  close() leaves the sets to the next scan of
the same shape: a set that has to be faulted in anew costs as much as
three fills of it (70 MB at ~1 ms a MB on the serving hosts), and a
request either found the allocator's memory still mapped or paid that
twice.
"""

from __future__ import annotations

import threading
from typing import NamedTuple

import numpy as np

from opentsdb_tpu.storage.device_cache import PAD_TS

# Buffer sets per packer: the host fills one while the upload of the
# other drains.
_SETS = 2
# "Nothing handed out yet" as a cursor: no timestamp is at or under it.
_NO_CURSOR = np.iinfo(np.int64).min
# Bytes of idle buffer sets kept between scans, at most (the oldest go
# first): two scans' worth at the default chunk size (4M points a chunk,
# 17 B a point), and a set larger than all of it is never kept.
_IDLE_BYTES = 320 << 20


class Chunk(NamedTuple):
    """One filled chunk.  ts/val/mask are the packer's own buffers:
    valid until the fill after next."""
    ts: np.ndarray          # [rows, n] int64, PAD_TS where mask is False
    val: np.ndarray         # [rows, n] float64, 0 where mask is False
    mask: np.ndarray        # [rows, n] bool
    tmin: int               # first timestamp of the chunk
    tmax: int               # last timestamp of the chunk
    points: int             # mask-true cells


def _keeps_host_buffer(dev, host: np.ndarray) -> bool:
    """True when a shard of the uploaded array IS the host buffer: the
    CPU backend takes a suitably aligned numpy array without a copy, and
    refilling such a buffer would rewrite the "device" array under a
    fold still in flight."""
    lo = host.ctypes.data
    hi = lo + host.nbytes
    for shard in dev.addressable_shards:
        if shard.device.platform == "cpu" \
                and lo <= shard.data.unsafe_buffer_pointer() < hi:
            return True
    return False


class _BufferSet:
    __slots__ = ("ts", "val", "mask", "pending")

    def __init__(self, rows: int, n: int):
        self.ts = np.empty((rows, n), np.int64)
        self.val = np.empty((rows, n), np.float64)
        self.mask = np.empty((rows, n), bool)
        self.pending = None     # the device arrays last uploaded from it

    @property
    def nbytes(self) -> int:
        return self.ts.nbytes + self.val.nbytes + self.mask.nbytes

    def settle(self) -> bool:
        """Wait until the arrays uploaded from this set are ready (the
        transfers, not the folds that read them); False when an upload
        kept a buffer, which is then the device array's for good."""
        if self.pending is None:
            return True
        pending, self.pending = self.pending, None
        for dev in pending:
            dev.block_until_ready()
        return not any(_keeps_host_buffer(dev, host) for dev, host in
                       zip(pending, (self.ts, self.val, self.mask)))


_idle: list[_BufferSet] = []    # settled sets, the last given back last
_idle_lock = threading.Lock()


def _take_set(rows: int, n: int, s: int) -> _BufferSet:
    """An idle set of this shape, or a new one; rows past the `s` series
    are made padding here and stay so while the packer has it."""
    bufs = None
    with _idle_lock:
        for i in range(len(_idle) - 1, -1, -1):
            if _idle[i].ts.shape == (rows, n):
                bufs = _idle.pop(i)
                break
    if bufs is None:
        bufs = _BufferSet(rows, n)
    bufs.ts[s:] = PAD_TS
    bufs.val[s:] = 0.0
    bufs.mask[s:] = False
    return bufs


def _give_sets(sets: list[_BufferSet]) -> None:
    with _idle_lock:
        _idle.extend(sets)
        while _idle and sum(b.nbytes for b in _idle) > _IDLE_BYTES:
            del _idle[0]


class ChunkPacker:
    """Chunks of one streamed segment, in order.

    Usage::

        packer = ChunkPacker(series_list, start_ms, end_ms, n, rows, fix)
        for _ in range(n_chunks):
            packer.reclaim()            # waits for an upload, if it must
            chunk = packer.fill()       # Chunk, or None: no row had a point
            if chunk is not None:
                packer.uploaded(upload(chunk.ts, chunk.val, chunk.mask))
        packer.close()                  # the sets go to the next scan

    `rows` >= len(series_list); rows past the series are padding (mask
    False).  rows_bulk / rows_cursor count, over the chunks handed out,
    the series rows each lane filled.
    """

    def __init__(self, series_list, start_ms: int, end_ms: int, n: int,
                 rows: int, fix: bool):
        self._series = series_list
        self._start, self._end, self._fix = start_ms, end_ms, fix
        self.n = n
        self.rows = rows
        s = len(series_list)
        views = [sr.window_views(start_ms, end_ms, fix)
                 for sr in series_list]
        self._ts = [v[0] for v in views]
        self._val = [v[1] for v in views]
        self._version = np.fromiter((v[2] for v in views), np.int64, s)
        self._len = np.fromiter((len(t) for t in self._ts), np.int64, s)
        self.max_len = int(self._len.max()) if s else 0
        # rows whose series moved: the cursor lane's from then on (their
        # views are cut to nothing)
        self._moved = np.zeros(s, bool)
        # the last timestamp each row handed out
        self._cursor = np.full(s, _NO_CURSOR)
        self._cols = np.arange(n)
        self._k = 0
        self._sets: list[_BufferSet | None] = [None] * _SETS
        self._slot = 0
        self.rows_bulk = self.rows_cursor = 0

    # -- buffer sets ---------------------------------------------------- #

    def reclaim(self) -> None:
        """Make the next set safe to refill: wait until the arrays
        uploaded from it are ready (the transfer, not the fold that
        reads them)."""
        bufs = self._sets[self._slot]
        if bufs is not None and not bufs.settle():
            self._sets[self._slot] = None

    def uploaded(self, arrays) -> None:
        """The device arrays made from the chunk fill() last returned;
        its buffers are not refilled before they are ready."""
        self._sets[self._slot].pending = tuple(arrays)
        self._slot = (self._slot + 1) % _SETS

    def close(self) -> None:
        """Leave the buffer sets to the next scan, once no upload reads
        them any more.  A scan that ends on an error just drops them."""
        sets, self._sets = self._sets, [None] * _SETS
        _give_sets([b for b in sets if b is not None and b.settle()])

    # -- the fill ------------------------------------------------------- #

    def fill(self) -> Chunk | None:
        """The next chunk, or None when no row has a point in it (its
        buffers are then free for the next fill)."""
        self.reclaim()
        bufs = self._sets[self._slot]
        s, n = len(self._series), self.n
        if bufs is None:
            bufs = self._sets[self._slot] = _take_set(self.rows, n, s)
        ts, val, mask = bufs.ts[:s], bufs.val[:s], bufs.mask[:s]
        a = self._k * n
        self._k += 1
        m = np.clip(self._len - a, 0, n)
        if s:
            self._fill_bulk(ts, val, mask, m, a)
            self._check_versions(m)
        moved = np.flatnonzero(self._moved)
        for i in moved:
            m[i] = self._fill_cursor(i, ts[i], val[i], mask[i])
        rows = np.flatnonzero(m)
        if not len(rows):
            return None
        last = ts[rows, m[rows] - 1]
        self._cursor[rows] = last
        self.rows_cursor += len(moved)
        self.rows_bulk += s - len(moved)
        return Chunk(bufs.ts, bufs.val, bufs.mask, int(ts[rows, 0].min()),
                     int(last.max()), int(m.sum()))

    def _fill_bulk(self, ts, val, mask, m, a: int) -> None:
        """Points [a, a + n) of every row's views into the chunk, all
        rows at once."""
        n = self.n
        b = a + n
        ts_parts = [t[a:b] for t in self._ts]
        val_parts = [v[a:b] for v in self._val]
        if int(m.min()) == n:
            np.concatenate(ts_parts, out=ts.reshape(-1))
            np.concatenate(val_parts, out=val.reshape(-1))
            mask[:] = True
            return
        np.less(self._cols, m[:, None], out=mask)
        ts.fill(PAD_TS)
        val.fill(0.0)
        ts[mask] = np.concatenate(ts_parts)
        val[mask] = np.concatenate(val_parts)

    def _check_versions(self, m) -> None:
        """Rows whose series moved since their bounds were taken leave
        the bulk lane: what was just copied from their views is dropped
        (m = 0; the cursor lane re-fills the row)."""
        now = np.fromiter((sr.version for sr in self._series), np.int64,
                          len(self._series))
        for i in np.flatnonzero((now != self._version) & ~self._moved):
            self._moved[i] = True
            self._ts[i] = self._ts[i][:0]
            self._val[i] = self._val[i][:0]
            self._len[i] = m[i] = 0

    def _fill_cursor(self, i: int, ts_row, val_row, mask_row) -> int:
        """One moved row by the locked cursor read; points written."""
        t, fv = self._series[i].window_chunk(
            self._start, self._end, int(self._cursor[i]), self.n, self._fix)
        k = len(t)
        ts_row[:k] = t
        ts_row[k:] = PAD_TS
        val_row[:k] = fv
        val_row[k:] = 0.0
        mask_row[:k] = True
        mask_row[k:] = False
        return k

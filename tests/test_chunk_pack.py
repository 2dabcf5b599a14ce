"""The streamed scan's chunk packer (storage/chunk_pack.py) against the
per-series cursor fill it replaced, kept here as the plain reference."""

import numpy as np
import pytest

from opentsdb_tpu.storage import chunk_pack
from opentsdb_tpu.storage.chunk_pack import ChunkPacker, _SETS
from opentsdb_tpu.storage.device_cache import PAD_TS
from opentsdb_tpu.storage.memstore import Series, SeriesKey


@pytest.fixture(autouse=True)
def no_idle_sets():
    """Each test starts with no buffer set left by another scan."""
    chunk_pack._idle.clear()
    yield
    chunk_pack._idle.clear()


def make_series(lengths, step=10, seed=3):
    """One Series a length: timestamps i*step + row (so rows differ),
    float values."""
    rng = np.random.default_rng(seed)
    out = []
    for row, length in enumerate(lengths):
        sr = Series(SeriesKey.make(1, {1: row}))
        if length:
            sr.append_batch(np.arange(length, dtype=np.int64) * step + row,
                            rng.normal(50.0, 9.0, length), False)
        out.append(sr)
    return out


def cursor_fill(series_list, start_ms, end_ms, n, rows, fix, cursors):
    """The fill as it stood before the packer: one window_chunk a series,
    three slice copies, fresh arrays a chunk.  Advances `cursors`."""
    ts = np.full((rows, n), PAD_TS, np.int64)
    val = np.zeros((rows, n), np.float64)
    mask = np.zeros((rows, n), bool)
    tmin = tmax = None
    points = 0
    for i, series in enumerate(series_list):
        t, fv = series.window_chunk(start_ms, end_ms, cursors[i], n, fix)
        m = len(t)
        if m:
            ts[i, :m] = t
            val[i, :m] = fv
            mask[i, :m] = True
            points += m
            cursors[i] = int(t[-1])
            tmin = int(t[0]) if tmin is None else min(tmin, int(t[0]))
            tmax = int(t[-1]) if tmax is None else max(tmax, int(t[-1]))
    return None if tmin is None else (ts, val, mask, tmin, tmax, points)


class Upload:
    """A stub device array: ready when told, never the host buffer."""
    addressable_shards = ()

    def __init__(self, log=None, tag=None):
        self.log, self.tag = log, tag

    def block_until_ready(self):
        if self.log is not None:
            self.log.append(("ready", self.tag))
        return self


def uploads(log=None, tag=None):
    return tuple(Upload(log, tag) for _ in range(3))


def assert_same_chunk(got, want):
    assert (got is None) == (want is None)
    if got is None:
        return
    for name, g, w in zip(("ts", "val", "mask"), got[:3], want[:3]):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), name
    assert tuple(got[3:]) == tuple(want[3:])
    assert all(type(x) is int for x in got[3:])


SHAPES = {
    # name: (lengths, n, rows, start_ms, end_ms)
    "all_full": ([32] * 5, 8, 5, 0, 10**6),
    "ragged_last_chunk": ([29] * 5, 8, 5, 0, 10**6),
    "ragged_series": ([32, 3, 17, 8, 25, 1], 8, 6, 0, 10**6),
    "empty_series": ([20, 0, 12, 0], 8, 4, 0, 10**6),
    "all_empty": ([0, 0, 0], 8, 3, 0, 10**6),
    "range_ends_mid_chunk": ([40] * 4, 8, 4, 35, 231),
    "range_before_the_data": ([12] * 3, 8, 3, -500, -1),
    "rows_over_series": ([21, 9, 16], 8, 8, 0, 10**6),
    "one_series": ([19], 4, 1, 20, 150),
    "no_series": ([], 8, 4, 0, 10**6),
}


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_chunks_are_bit_equal_to_the_cursor_fill(shape):
    lengths, n, rows, start, end = SHAPES[shape]
    series = make_series(lengths)
    packer = ChunkPacker(series, start, end, n, rows, True)
    counts = [sr.window_count(start, end) for sr in series]
    assert packer.max_len == max(counts, default=0)
    cursors = [None] * len(series)
    n_chunks = -(-packer.max_len // n) + 2      # and two past the end
    handed = 0
    for _ in range(n_chunks):
        got = packer.fill()
        want = cursor_fill(series, start, end, n, rows, True, cursors)
        assert_same_chunk(got, want)
        if got is not None:
            handed += 1
            packer.uploaded(uploads())
    assert packer.rows_bulk == handed * len(series)
    assert packer.rows_cursor == 0


def test_a_reused_set_carries_nothing_over_from_the_chunk_before():
    """Chunk k+_SETS lands in chunk k's buffers: a full chunk followed
    by shorter ones must leave no stale cell behind."""
    series = make_series([40, 18, 33, 9])
    packer = ChunkPacker(series, 0, 10**6, 8, 6, True)
    cursors = [None] * 4
    seen = set()
    for _ in range(5):
        got = packer.fill()
        seen.add(got.ts.ctypes.data)
        assert_same_chunk(got, cursor_fill(series, 0, 10**6, 8, 6, True,
                                           cursors))
        packer.uploaded(uploads())
    assert len(seen) == _SETS


MUTATIONS = {
    # name: (what lands on row 1 between chunk 1 and chunk 2,
    #        the pre-existing timestamps row 1 may no longer hand out)
    "append": (lambda sr: sr.append(10**5, 1.0, False), ()),
    "append_batch": (lambda sr: sr.append_batch(
        np.array([10**5, 10**5 + 10], np.int64), np.array([1.0, 2.0]),
        False), ()),
    # behind the cursor: the in-place sort shifts every later point
    "out_of_order_append": (lambda sr: sr.append(26, 7.5, False), ()),
    # ahead of the cursor, inside the range still to scan
    "out_of_order_ahead": (lambda sr: sr.append(206, 7.5, False), ()),
    "delete_range": (lambda sr: sr.delete_range(191, 241),
                     (191, 201, 211, 221, 231, 241)),
    "delete_behind": (lambda sr: sr.delete_range(0, 60), ()),
    "overwrite_deduped": (lambda sr: sr.append(201, 9.0, False), ()),
}


@pytest.mark.parametrize("what", sorted(MUTATIONS))
def test_a_row_that_moves_mid_scan_goes_to_the_cursor_lane(what):
    mutate, gone = MUTATIONS[what]
    lengths = [40, 40, 40, 27]
    series = make_series(lengths)
    before = [sr.window(0, 10**6)[0].tolist() for sr in series]
    twin = make_series(lengths)              # the cursor fill's own store
    packer = ChunkPacker(series, 0, 10**6, 8, 4, True)
    cursors = [None] * 4
    handed = [[] for _ in series]
    lanes = []
    for k in range(7):
        if k == 2:
            mutate(series[1])
            mutate(twin[1])
        bulk, cursor = packer.rows_bulk, packer.rows_cursor
        got = packer.fill()
        want = cursor_fill(twin, 0, 10**6, 8, 4, True, cursors)
        assert_same_chunk(got, want)
        if got is None:
            continue
        packer.uploaded(uploads())
        lanes.append((packer.rows_bulk - bulk, packer.rows_cursor - cursor))
        for i in range(4):
            handed[i].extend(got.ts[i][got.mask[i]].tolist())
    # exactly the row that moved, from the chunk it moved before
    assert lanes[:2] == [(4, 0), (4, 0)]
    assert set(lanes[2:]) == {(3, 1)}
    assert np.flatnonzero(packer._moved).tolist() == [1]
    for i, row in enumerate(handed):
        assert row == sorted(set(row)), "a point handed out twice"
        old = [t for t in row if t in set(before[i])]
        want_old = [t for t in before[i] if t not in gone]
        if i != 1:
            assert row == before[i]
        else:
            # every pre-existing point still there: once, in order
            assert old == want_old


def test_a_row_that_moved_stays_on_the_cursor_lane_and_others_do_not():
    series = make_series([24] * 3)
    packer = ChunkPacker(series, 0, 10**6, 8, 3, True)
    packer.fill()
    packer.uploaded(uploads())
    series[0].append(10**5, 1.0, False)
    series[2].append(10**5, 1.0, False)
    packer.fill()
    packer.uploaded(uploads())
    assert np.flatnonzero(packer._moved).tolist() == [0, 2]
    packer.fill()
    assert (packer.rows_bulk, packer.rows_cursor) == (3 + 1 + 1, 2 + 2)


def test_a_point_appended_before_the_first_chunk_is_read_by_the_cursor():
    series = make_series([5, 5])
    packer = ChunkPacker(series, 0, 10**6, 8, 2, True)
    series[1].append(3, 4.0, False)          # out of order, before any fill
    got = packer.fill()
    assert got.ts[1][got.mask[1]].tolist() == [1, 3, 11, 21, 31, 41]
    assert got.ts[0][got.mask[0]].tolist() == [0, 10, 20, 30, 40]
    assert (packer.rows_bulk, packer.rows_cursor) == (1, 1)


class TestDuplicates:
    def _dup(self):
        series = make_series([12, 12])
        series[1].append(51, 99.0, False)    # row 1 holds 51 twice
        return series

    def test_duplicates_still_raise_without_fix_duplicates(self):
        with pytest.raises(ValueError, match="Duplicate timestamp 51"):
            ChunkPacker(self._dup(), 0, 10**6, 8, 2, False)

    def test_a_duplicate_landing_mid_scan_raises_from_the_cursor_lane(self):
        series = make_series([12, 12])
        packer = ChunkPacker(series, 0, 10**6, 8, 2, False)
        packer.fill()
        packer.uploaded(uploads())
        series[1].append(91, 99.0, False)
        with pytest.raises(ValueError, match="Duplicate timestamp 91"):
            packer.fill()

    def test_duplicates_resolve_last_write_wins_with_it(self):
        series = self._dup()
        packer = ChunkPacker(series, 0, 10**6, 16, 2, True)
        got = packer.fill()
        want = cursor_fill(series, 0, 10**6, 16, 2, True, [None, None])
        assert_same_chunk(got, want)
        assert got.val[1][5] == 99.0


class TestBufferGate:
    def test_a_set_is_not_refilled_before_its_upload_is_ready(self):
        """The order a stub uploader sees: chunk k's arrays are waited
        for before chunk k+_SETS is filled into the same buffers, and
        not before."""
        series = make_series([8 * 6] * 3)
        packer = ChunkPacker(series, 0, 10**6, 8, 3, True)
        log = []
        buffers = {}
        for k in range(6):
            packer.reclaim()
            chunk = packer.fill()
            log.append(("filled", k))
            key = chunk.ts.ctypes.data
            if key in buffers:
                # the buffers of chunk k - _SETS, whose upload was read
                assert buffers[key] == k - _SETS
                assert ("ready", k - _SETS) in log
            buffers[key] = k
            packer.uploaded(uploads(log, k))
        order = [e for e in log if e[0] == "filled" or e[1] is not None]
        for k in range(_SETS, 6):
            ready = order.index(("ready", k - _SETS))
            assert order.index(("filled", k - 1)) < ready \
                < order.index(("filled", k))
        # nothing waits for an upload whose buffers nobody asked for again
        assert ("ready", 5) not in log and ("ready", 4) not in log

    def test_fill_alone_gates_too(self):
        series = make_series([8 * 4] * 2)
        packer = ChunkPacker(series, 0, 10**6, 8, 2, True)
        log = []
        for k in range(4):
            packer.fill()
            log.append(("filled", k))
            packer.uploaded(uploads(log, k))
        assert log.index(("ready", 0)) < log.index(("filled", 2))
        assert log.index(("ready", 1)) < log.index(("filled", 3))

    def test_an_empty_chunk_keeps_its_set_for_the_next_fill(self):
        series = make_series([4, 4])
        packer = ChunkPacker(series, 0, 10**6, 4, 2, True)
        first = packer.fill()
        packer.uploaded(uploads())
        assert packer.fill() is None
        assert packer.fill() is None
        assert first.ts.ctypes.data != packer._sets[packer._slot].ts \
            .ctypes.data

    def test_a_buffer_the_upload_kept_is_never_refilled(self):
        """The CPU backend may take an aligned numpy array without a
        copy: the "device" array then IS the buffer, and the packer
        gives the set up instead of rewriting it."""
        class Shard:
            def __init__(self, host):
                self.device = type("D", (), {"platform": "cpu"})()
                self.data = self
                self._p = host.ctypes.data

            def unsafe_buffer_pointer(self):
                return self._p

        class Kept(Upload):
            def __init__(self, host):
                super().__init__()
                self.addressable_shards = (Shard(host),)

        series = make_series([8 * 5] * 2)
        packer = ChunkPacker(series, 0, 10**6, 8, 2, True)
        first = packer.fill()
        kept_ts = first.ts
        snapshot = kept_ts.copy()
        # only the timestamps' upload kept its buffer
        packer.uploaded((Kept(first.ts), Upload(), Upload()))
        for _ in range(4):
            chunk = packer.fill()
            assert chunk.ts is not kept_ts
            packer.uploaded(uploads())
        assert np.array_equal(kept_ts, snapshot)

    def test_jax_arrays_pass_the_gate_on_this_backend(self):
        """The real thing: whatever jnp.asarray does with the buffers on
        this backend (copy or keep), five reused-set chunks arrive as
        they were filled."""
        import jax.numpy as jnp
        import opentsdb_tpu.ops  # noqa: F401  (64-bit types on)
        series = make_series([64 * 5] * 8)
        packer = ChunkPacker(series, 0, 10**6, 64, 8, True)
        cursors = [None] * 8
        kept = []
        for _ in range(5):
            chunk = packer.fill()
            want = cursor_fill(series, 0, 10**6, 64, 8, True, cursors)
            dev = tuple(jnp.asarray(a) for a in chunk[:3])
            packer.uploaded(dev)
            kept.append((dev, want))
        for dev, want in kept:
            for d, w in zip(dev, want[:3]):
                assert np.array_equal(np.asarray(d), w)


class TestSetsOutliveTheScan:
    """close() leaves the buffer sets to the next scan of their shape:
    a set faulted in anew costs more than the fills that use it."""

    def _scan(self, series, rows, n=8, log=None):
        packer = ChunkPacker(series, 0, 10**6, n, rows, True)
        cursors = [None] * len(series)
        where = []
        for k in range(-(-packer.max_len // n)):
            chunk = packer.fill()
            assert_same_chunk(chunk, cursor_fill(series, 0, 10**6, n, rows,
                                                 True, cursors))
            where.append(chunk.ts.ctypes.data)
            packer.uploaded(uploads(log, k))
        if log is not None:
            log.append("closing")
        packer.close()
        return set(where)

    def test_the_next_scan_of_the_shape_fills_the_same_buffers(self):
        first = self._scan(make_series([30] * 6), 6)
        assert len(chunk_pack._idle) == _SETS
        # fewer series in as many rows: what the first scan left in rows
        # 4 and 5 must read as padding (assert_same_chunk in _scan)
        assert self._scan(make_series([22] * 4, seed=8), 6) == first
        assert len(chunk_pack._idle) == _SETS

    def test_another_shape_gets_sets_of_its_own(self):
        first = self._scan(make_series([30] * 6), 6)
        other = self._scan(make_series([30] * 6), 6, n=16)
        assert not first & other
        assert len(chunk_pack._idle) == 2 * _SETS

    def test_close_waits_for_the_uploads_still_reading_the_sets(self):
        log = []
        self._scan(make_series([8 * 3] * 2), 2, log=log)
        closing = log.index("closing")
        # chunk 0's upload was waited for when chunk 2 took its set
        assert log.index(("ready", 0)) < closing
        assert closing < log.index(("ready", 1))
        assert closing < log.index(("ready", 2))

    def test_idle_sets_are_bounded_in_bytes_oldest_first(self, monkeypatch):
        one = 6 * 8 * 17
        monkeypatch.setattr(chunk_pack, "_IDLE_BYTES", 3 * one)
        first = self._scan(make_series([30] * 6), 6)
        second = self._scan(make_series([30] * 6), 6, n=16)   # 2 * one each
        kept = {b.ts.ctypes.data for b in chunk_pack._idle}
        assert sum(b.nbytes for b in chunk_pack._idle) <= 3 * one
        assert kept and kept <= second | first and not kept <= first

    def test_a_set_the_upload_kept_and_an_unclosed_scan_leave_nothing(self):
        class Kept(Upload):
            def __init__(self, host):
                super().__init__()
                shard = type("S", (), {})()
                shard.device = type("D", (), {"platform": "cpu"})()
                shard.data = shard
                shard.unsafe_buffer_pointer = lambda p=host.ctypes.data: p
                self.addressable_shards = (shard,)

        packer = ChunkPacker(make_series([8] * 2), 0, 10**6, 8, 2, True)
        chunk = packer.fill()
        packer.uploaded((Upload(), Kept(chunk.val), Upload()))
        packer.close()
        assert chunk_pack._idle == []
        packer = ChunkPacker(make_series([8] * 2), 0, 10**6, 8, 2, True)
        packer.fill()
        del packer                              # a scan that raised
        assert chunk_pack._idle == []


class TestVersionMovesFirst:
    """window_views()' readers copy without the lock and tell a torn
    copy by the version: a mutation that moves stored points must have
    bumped it before the first of them moves."""

    @staticmethod
    def _spy(series):
        seen = []

        class Spy(np.ndarray):
            def __setitem__(self, key, value):
                seen.append(series.version)
                super().__setitem__(key, value)
        series._ts = series._ts.view(Spy)
        return seen

    def test_delete_range(self):
        sr = make_series([20])[0]
        v0 = sr.version
        seen = self._spy(sr)
        assert sr.delete_range(30, 60) == 4
        assert seen and seen[0] == v0 + 1 == sr.version

    def test_restore_arrays(self):
        sr = make_series([20])[0]
        v0 = sr.version
        seen = self._spy(sr)
        n = 6
        sr.restore_arrays(np.arange(n, dtype=np.int64), np.zeros(n),
                          np.zeros(n, np.int64), np.zeros(n, bool))
        assert seen and seen[0] == v0 + 1 == sr.version

    def test_an_append_writes_past_the_views_and_a_grown_buffer_is_new(self):
        sr = make_series([Series.INITIAL_CAPACITY])[0]
        ts, val, version = sr.window_views(0, 10**6)
        want = ts.copy()
        sr.append(10**5, 1.0, False)         # grows: a new buffer
        assert sr.version == version + 1
        assert np.array_equal(ts, want)
        assert not np.shares_memory(ts, sr.window_views(0, 10**6)[0])

    def test_window_views_are_views_under_the_window_bounds(self):
        sr = make_series([30])[0]
        ts, val, version = sr.window_views(45, 120)
        lo, hi, v = sr.window_bounds(45, 120)
        assert version == v and len(ts) == len(val) == hi - lo
        assert ts.tolist() == sr.window(45, 120)[0].tolist()
        assert ts.base is not None and val.base is not None


def test_a_writer_beside_the_scan_never_tears_a_row():
    """Appends (in and out of order), deletes and the sorts they force
    land while chunks fill: every row of every chunk is still strictly
    increasing in time, each value is its own timestamp's (val = ts / 2:
    a copy torn between the two columns or across a sort would pair a
    timestamp with another point's value), and no pre-existing point is
    handed out twice."""
    import threading
    n_series, length, n = 6, 4000, 64
    series = []
    for row in range(n_series):
        sr = Series(SeriesKey.make(1, {1: row}))
        t = np.arange(length, dtype=np.int64) * 10
        sr.append_batch(t, t / 2.0, False)
        series.append(sr)
    stop = threading.Event()

    def writer():
        rng = np.random.default_rng(9)
        k = 0
        while not stop.is_set():
            sr = series[int(rng.integers(0, n_series - 1))]   # last: quiet
            t = int(rng.integers(0, length * 10))
            kind = k % 3
            if kind == 0:
                sr.append(t * 10 + 5, (t * 10 + 5) / 2.0, False)   # new, ooo
            elif kind == 1:
                sr.delete_range(t, t + 25)
            else:
                sr.normalize(True)
            k += 1

    th = threading.Thread(target=writer)
    th.start()
    try:
        for _ in range(6):
            packer = ChunkPacker(series, 0, 10**9, n, n_series, True)
            last = np.full(n_series, -1, np.int64)
            for _ in range(-(-packer.max_len // n)):
                chunk = packer.fill()
                if chunk is None:
                    continue
                for i in range(n_series):
                    t = chunk.ts[i][chunk.mask[i]]
                    v = chunk.val[i][chunk.mask[i]]
                    if not len(t):
                        continue
                    assert np.all(np.diff(t) > 0)
                    assert t[0] > last[i]
                    assert np.array_equal(v, t / 2.0)
                    last[i] = t[-1]
                    assert np.all(chunk.ts[i][~chunk.mask[i]] == PAD_TS)
                packer.uploaded(uploads())
            assert not packer._moved[-1]
    finally:
        stop.set()
        th.join()


@pytest.mark.parametrize("form", ["one_device", "mesh"])
def test_both_accumulators_hand_back_what_they_uploaded(form):
    """update() returns the three device arrays it made of the host
    chunk — the packer's gate — and six chunks through two reused sets
    fold to the state that fresh arrays a chunk fold to."""
    import jax
    from opentsdb_tpu.ops.downsample import FixedWindows
    from opentsdb_tpu.ops.streaming import StreamAccumulator
    from opentsdb_tpu.parallel import ShardedStreamAccumulator, make_mesh
    from opentsdb_tpu.parallel.sharded import padded_rows
    s, n = 13, 64
    series = make_series([n * 6 - 7 * i for i in range(s)], step=1000)
    start, end = 0, n * 6 * 1000
    window_spec, wargs = FixedWindows.for_range(start, end, 60_000).split()
    if form == "mesh":
        mesh = make_mesh()
        rows = padded_rows(mesh, s)          # 16 over 8 devices
        acc = ShardedStreamAccumulator(mesh, s, window_spec, wargs)
    else:
        rows = s
        acc = StreamAccumulator.create(s, window_spec, wargs)
    want = StreamAccumulator.create(s, window_spec, wargs)
    packer = ChunkPacker(series, start, end, n, rows, True)
    cursors = [None] * s
    for _ in range(6):
        chunk = packer.fill()
        dev = acc.update(chunk.ts, chunk.val, chunk.mask)
        assert len(dev) == 3 and all(isinstance(d, jax.Array) for d in dev)
        assert [d.shape for d in dev] == [(rows, n)] * 3
        packer.uploaded(dev)
        want.update(*cursor_fill(series, start, end, n, s, True,
                                 cursors)[:3])
    for lane in ("n", "total", "hi"):
        assert np.array_equal(np.asarray(acc.state[lane])[:s],
                              np.asarray(want.state[lane])), lane

"""Regression tests for code-review findings on the core slice."""

import numpy as np
import pytest

from opentsdb_tpu.query.filters import build_filter
from opentsdb_tpu.utils import datetime_util as DT


class TestFilterSemantics:
    def test_not_literal_or_missing_key_passes(self):
        # TagVNotLiteralOrFilter.java:80-83 — absent tag key means included.
        f = build_filter("host", "not_literal_or", "web01")
        assert f.match({"dc": "east"}) is True
        assert f.match({"host": "web01"}) is False
        assert f.match({"host": "web02"}) is True

    def test_not_iliteral_case_insensitive(self):
        f = build_filter("host", "not_iliteral_or", "WEB01")
        assert f.match({"host": "web01"}) is False
        assert f.match({}) is True


class TestLongExactness:
    def test_int64_roundtrip_above_2_53(self):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.utils.config import Config
        big = (1 << 60) + 1
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
        tsdb.add_point("counter.metric", 1_356_998_400, big, {"host": "a"})
        q = TSQuery(start="1356998300", end="1356998500",
                    queries=[parse_m_subquery("sum:counter.metric")])
        q.validate()
        results = tsdb.new_query_runner().run(q)
        assert results[0].dps == [(1_356_998_400_000, big)]


class TestCalendarNonDividing:
    def test_45m_tiles_from_midnight(self):
        # DateTime.previousInterval: 60 % 45 != 0 -> base is top of day.
        # 01:10 UTC -> window start 00:45, not 01:00.
        ts = DT.parse_datetime_string("2015/06/01-01:10:00", "UTC")
        snapped = DT.previous_interval(ts, 45, "m", "UTC")
        assert snapped == DT.parse_datetime_string("2015/06/01-00:45:00", "UTC")

    def test_23s_tiles_from_top_of_hour(self):
        ts = DT.parse_datetime_string("2015/06/01-01:00:50", "UTC")
        snapped = DT.previous_interval(ts, 23, "s", "UTC")
        # 0, 23, 46, 69... -> 46s is the last boundary <= 50s.
        assert snapped == DT.parse_datetime_string("2015/06/01-01:00:46", "UTC")

    def test_dividing_interval_unchanged(self):
        ts = DT.parse_datetime_string("2015/06/01-12:31:00", "UTC")
        snapped = DT.previous_interval(ts, 15, "m", "UTC")
        assert snapped == DT.parse_datetime_string("2015/06/01-12:30:00", "UTC")


class TestTsuidWidths:
    def test_configured_widths_respected(self):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.utils.config import Config
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                            "tsd.storage.uid.width.metric": 4}))
        tsdb.add_point("m", 1_356_998_400, 1, {"host": "a"})
        series = tsdb.store.all_series()[0]
        # 4-byte metric + 3-byte tagk + 3-byte tagv = 20 hex chars.
        assert len(tsdb.tsuid(series.key)) == 20


class TestAppendBatchIntFlag:
    def test_float_dtype_with_int_flag_keeps_values(self):
        """Float-typed arrays of integral points must not zero the int column."""
        import numpy as np
        from opentsdb_tpu.storage.memstore import Series, SeriesKey
        s = Series(SeriesKey.make(1, {1: 1}))
        s.append_batch(np.array([1000, 2000], dtype=np.int64),
                       np.array([7.0, 9.0]), True)
        ts, fv, iv, isint = s.arrays()
        assert iv.tolist() == [7, 9]
        assert isint.all()

    def test_mixed_int_flags(self):
        import numpy as np
        from opentsdb_tpu.storage.memstore import Series, SeriesKey
        s = Series(SeriesKey.make(1, {1: 1}))
        s.append_batch(np.array([1000, 2000], dtype=np.int64),
                       np.array([7.0, 9.5]),
                       np.array([True, False]))
        ts, fv, iv, isint = s.arrays()
        assert iv.tolist() == [7, 0]
        assert fv.tolist() == [7.0, 9.5]
        assert isint.tolist() == [True, False]


class TestLiteralUidPruning:
    def test_unknown_tag_value_literal_returns_empty(self):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.utils.config import Config
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
        tsdb.add_point("m", 1_356_998_400, 1, {"host": "a"})
        q = TSQuery(start="1356998300", end="1356998500",
                    queries=[parse_m_subquery("sum:m{host=zzz}")])
        q.validate()
        assert tsdb.new_query_runner().run(q) == []


class TestNormalizeFailureStaysDirty:
    """VERDICT r2 #3: a failed dedup (fix_duplicates=false) must leave the
    series dirty — reads keep raising, fsck can still see and repair the
    duplicate.  Previously _normalize_locked set _sorted=True before the
    dedup raised, permanently hiding the duplicate (silent double-count)."""

    def _dup_series(self):
        from opentsdb_tpu.storage.memstore import Series, SeriesKey
        s = Series(SeriesKey.make(1, {1: 1}))
        s.append(1000, 1.0, True)
        s.append(1000, 2.0, True)
        return s

    def test_failed_normalize_leaves_dirty_and_reads_keep_raising(self):
        s = self._dup_series()
        assert s.dirty
        with pytest.raises(ValueError):
            s.normalize(fix_duplicates=False)
        assert s.dirty, "failed dedup must not mark the series clean"
        # reads surface the error, as documented, on every attempt
        with pytest.raises(ValueError):
            s.window(0, 10_000, fix_duplicates=False)
        with pytest.raises(ValueError):
            s.window(0, 10_000, fix_duplicates=False)

    def test_fsck_repairs_after_failed_flush(self):
        s = self._dup_series()
        with pytest.raises(ValueError):
            s.normalize(fix_duplicates=False)
        # fsck path: normalize(fix_duplicates=True) resolves last-write-wins
        s.normalize(fix_duplicates=True)
        assert not s.dirty
        ts, val, _, _ = s.window(0, 10_000, fix_duplicates=False)
        assert list(ts) == [1000]
        assert list(val) == [2.0]

    def test_compaction_flush_failure_then_repair(self):
        from opentsdb_tpu.storage.memstore import CompactionQueue
        s = self._dup_series()
        q = CompactionQueue(fix_duplicates=False)
        q.add(s)
        q.flush()
        assert q.errors == 1
        assert s.dirty
        s.normalize(fix_duplicates=True)
        ts, val, _, _ = s.window(0, 10_000, fix_duplicates=False)
        assert list(zip(ts, val)) == [(1000, 2.0)]


class TestNativeSnapshotDirtyRoundTrip:
    """A series persisted with unresolved duplicates must restore dirty:
    eng_window's last-write-wins dedup silently healed it (and hid it from
    fsck); the restore path must use the raw (dup-preserving) read."""

    def test_window_raw_preserves_duplicates(self):
        from opentsdb_tpu.storage import native_engine
        if not native_engine.available():
            pytest.skip("native engine unavailable")
        with native_engine.NativeEngine() as eng:
            sid = eng.series(b"k")
            eng.append_batch(
                sid, np.array([1000, 1000, 2000], np.int64),
                np.array([1.0, 2.0, 3.0]), np.array([1, 2, 3], np.int64),
                np.array([1, 1, 1], np.uint8))
            ts, fval, _, _ = eng.window_raw(sid)
            assert list(ts) == [1000, 1000, 2000]
            # stable: the later write for ts=1000 stays last
            assert list(fval) == [1.0, 2.0, 3.0]
            # the dedup'd view still resolves last-write-wins
            ts2, fval2, _, _ = eng.window(sid)
            assert list(ts2) == [1000, 2000]
            assert list(fval2) == [2.0, 3.0]


class TestWindowIntoTypeRace:
    """build_batch_direct sizes/types the batch in one lock hold and
    fills rows in another (review r5): a float point appended between
    the two must NOT be read from the int column (append() stores 0
    there) — the fill refuses and the builder retypes to float."""

    def _series(self):
        from opentsdb_tpu.storage.memstore import Series, SeriesKey
        import numpy as np
        s = Series(SeriesKey(1, ((1, 1),)))
        ts = np.arange(10, dtype=np.int64) * 1000
        s.append_batch(ts, np.arange(10, dtype=np.float64), True)
        return s

    def test_window_into_refuses_stale_int_contract(self):
        import numpy as np
        s = self._series()
        count, all_int = s.window_stats(0, 100_000)
        assert count == 10 and all_int
        s.append(5_500, 3.5, False)          # float lands in range
        ts_row = np.empty(16, np.int64)
        val_row = np.empty(16, np.int64)
        mask_row = np.empty(16, bool)
        k, ok = s.window_into(0, 100_000, True, ts_row, val_row,
                              mask_row, want_int=True)
        assert not ok and k == 0
        # the float view still serves everything
        fval = np.empty(16, np.float64)
        k, ok = s.window_into(0, 100_000, True, ts_row, fval, mask_row,
                              want_int=False)
        assert ok and k == 11
        assert 3.5 in fval[:k]

    def test_build_batch_direct_retypes_to_float(self):
        import numpy as np
        from opentsdb_tpu.ops.pipeline import build_batch_direct
        s = self._series()

        class Racy:
            """Looks all-int at sizing time, grows a float by fill time."""
            def window_stats(self, a, b, fix=True):
                return s.window_stats(a, b, fix)
            def window_into(self, a, b, fix, tr, vr, mr, want_int):
                if want_int:
                    s.append(5_500, 3.5, False)
                return s.window_into(a, b, fix, tr, vr, mr, want_int)

        ts, val, mask, all_int = build_batch_direct([Racy()], 0, 100_000,
                                                    True)
        assert not all_int and val.dtype == np.float64
        assert 3.5 in val[0][mask[0]]


class TestSegDtypeGuards:
    """int32 segment-id migration (r5 review): the dtype guard must test
    the quantity the ids actually span, and flip to int64 exactly at
    2^31."""

    def test_boundary(self):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.group_agg import _seg_dtype
        assert _seg_dtype(2 ** 31 - 1) == jnp.int32
        assert _seg_dtype(2 ** 31) == jnp.int64

    def test_first_last_positions_span_points_not_ids(self):
        """first/last lanes rank flat point positions (s*n of them);
        the review caught the guard testing the smaller s*w id space.
        Exercise the seg-lane path at n >> w and pin first/last values."""
        import numpy as np
        import jax.numpy as jnp
        from opentsdb_tpu.ops.streaming import _chunk_moments
        from opentsdb_tpu.ops.downsample import WindowSpec
        s, n, w = 2, 64, 4
        start = 1_356_998_400_000
        step = 1_000
        ts = start + np.arange(n, dtype=np.int64)[None, :] * step \
            + np.zeros((s, 1), np.int64)
        val = np.arange(s * n, dtype=np.float64).reshape(s, n)
        wspec = WindowSpec("fixed", w, 16_000)
        wargs = {"first": jnp.asarray(start, jnp.int64),
                 "nwin": jnp.asarray(w, jnp.int32)}
        out = _chunk_moments(jnp.asarray(ts), jnp.asarray(val),
                             jnp.ones((s, n), bool), wspec, wargs,
                             lanes=frozenset({"n", "first", "last"}))
        first = np.asarray(out["first"])
        last = np.asarray(out["last"])
        # window k of row r covers points [16k, 16(k+1)): first/last are
        # the row-flat values at those positions
        for r in range(s):
            for k in range(w):
                assert first[r, k] == r * n + 16 * k
                assert last[r, k] == r * n + 16 * (k + 1) - 1


class TestCacheCoherenceFixes:
    """PR 7 true positives surfaced by tools/lint/cache_coherence.py."""

    def test_log_buffer_uninstall_detaches_from_root_logger(self):
        """The /logs ring-buffer handler used to outlive every server:
        installed on start, never detached.  Fails pre-fix:
        uninstall_log_buffer did not exist and the handler stayed on
        the root logger forever.  The refcount keeps the handler while
        ANY server still runs."""
        import logging
        from opentsdb_tpu.tsd import admin_rpcs

        root = logging.getLogger()
        saved = admin_rpcs._LOG_BUFFER_INSTALLS
        if admin_rpcs._LOG_BUFFER in root.handlers:
            root.removeHandler(admin_rpcs._LOG_BUFFER)
        admin_rpcs._LOG_BUFFER_INSTALLS = 0
        try:
            admin_rpcs.install_log_buffer()
            admin_rpcs.install_log_buffer()   # a second server
            assert root.handlers.count(admin_rpcs._LOG_BUFFER) == 1
            admin_rpcs.uninstall_log_buffer()
            # first server stopped; the second still needs capture
            assert admin_rpcs._LOG_BUFFER in root.handlers
            admin_rpcs.uninstall_log_buffer()
            assert admin_rpcs._LOG_BUFFER not in root.handlers
            # over-uninstall must not go negative / raise
            admin_rpcs.uninstall_log_buffer()
            assert admin_rpcs._LOG_BUFFER_INSTALLS == 0
        finally:
            admin_rpcs._LOG_BUFFER_INSTALLS = 0
            if admin_rpcs._LOG_BUFFER in root.handlers:
                root.removeHandler(admin_rpcs._LOG_BUFFER)
            for _ in range(saved):
                admin_rpcs.install_log_buffer()


class TestOrderingAtomicityTruePositives:
    """PR 18 true positives surfaced by tools/lint/ordering.py."""

    def test_failed_wal_append_does_not_burn_a_sequence_number(
            self, tmp_path, monkeypatch):
        """A raise mid-journal() used to leave ``_next_seq`` bumped with
        nothing on disk — a permanent gap to every replica tailing the
        WAL.  Fails pre-fix: the retry lands on before+2."""
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.storage import persist
        from opentsdb_tpu.utils.config import Config

        t = TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.storage.directory": str(tmp_path / "data")}))
        p = t.persistence
        p.journal({"kind": "probe", "i": 1})
        before = p.last_seq

        real = persist.frame_line

        def boom(seq, crc, payload):
            raise OSError("disk full")

        monkeypatch.setattr(persist, "frame_line", boom)
        with pytest.raises(OSError):
            p.journal({"kind": "probe", "i": 2})
        assert p.last_seq == before      # seq handed back, no gap
        monkeypatch.setattr(persist, "frame_line", real)
        seq, _ = p.journal({"kind": "probe", "i": 3})
        assert seq == before + 1         # contiguous for the tail

    def test_failed_subscribe_preserves_the_compile_log_flag(
            self, monkeypatch):
        """A raise between subscribe()'s ``_prev_flag`` and ``_handler``
        writes made the NEXT subscribe re-save the already-overridden
        flag, so unsubscribe could never restore the user's setting.
        Fails pre-fix: _prev_flag holds the stale save and the jax flag
        is left flipped."""
        import jax
        from opentsdb_tpu.obs import jaxprof as jp

        cap = jp.CompileLogCapture()
        prior = jax.config.jax_log_compiles

        def boom(owner):
            raise RuntimeError("handler construction failed")

        try:
            monkeypatch.setattr(jp, "_CaptureHandler", boom)
            with pytest.raises(RuntimeError):
                cap.subscribe(lambda kernel: None)
            assert cap._handler is None
            assert cap._prev_flag is None          # nothing half-saved
            assert jax.config.jax_log_compiles == prior
            monkeypatch.undo()
            cb = lambda kernel: None
            cap.subscribe(cb)
            try:
                assert cap._prev_flag == prior     # true original saved
            finally:
                cap.unsubscribe(cb)
            assert jax.config.jax_log_compiles == prior
        finally:
            jax.config.update("jax_log_compiles", prior)

    def test_failed_root_branch_leaves_no_half_registered_tree(
            self, monkeypatch):
        """create_tree wrote ``_trees`` before constructing the root
        Branch; a raise there registered a tree with no root, wedging
        every later branch walk for that id.  Fails pre-fix: the
        aborted id stays in the store."""
        from opentsdb_tpu.tree import Tree, TreeStore
        from opentsdb_tpu.tree import store as tree_store_mod

        st = TreeStore()

        def boom(tree_id, path):
            raise RuntimeError("branch allocation failed")

        monkeypatch.setattr(tree_store_mod, "Branch", boom)
        with pytest.raises(RuntimeError):
            st.create_tree(Tree(name="t"))
        assert st.all_trees() == []
        monkeypatch.undo()
        tid = st.create_tree(Tree(name="t"))
        assert tid == 1
        assert st.get_branch(tid, ()) is not None

    def test_raise_in_sortedness_probe_cannot_scribble_columns(
            self, monkeypatch):
        """append_batch computed the incoming-sortedness probe BETWEEN
        the column writes and the ``_n`` commit; a raise there left the
        backing arrays scribbled past the commit point.  Fails pre-fix:
        the probe slot holds the aborted batch's timestamp."""
        from opentsdb_tpu.storage import memstore

        s = memstore.Series(memstore.SeriesKey.make(1, {}))
        probe = int(s._ts[0])

        def boom(arr):
            raise FloatingPointError("probe failed")

        monkeypatch.setattr(memstore.np, "diff", boom)
        with pytest.raises(FloatingPointError):
            s.append_batch(np.array([10_000, 20_000], dtype=np.int64),
                           np.array([1.0, 2.0]), False)
        assert len(s) == 0 and s._version == 0
        assert int(s._ts[0]) == probe      # columns untouched
        monkeypatch.undo()
        s.append_batch(np.array([10_000, 20_000], dtype=np.int64),
                       np.array([1.0, 2.0]), False)
        assert len(s) == 2 and s._sorted

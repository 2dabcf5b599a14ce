"""Native chunk engine: ctypes binding, roundtrip, compression, snapshots.

VERDICT round-1 item 8 / ADVICE medium: the C++ engine (native/engine.cpp)
must be wired and tested, the committed .so removed (it builds from source
on first use).  Covers binding roundtrip, last-write-wins dedup parity with
MemStore.Series.normalize, compression ratio on realistic cadenced data,
binary save/load, and the DiskPersistence native-codec snapshot.
"""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from opentsdb_tpu.storage import native_engine

pytestmark = pytest.mark.skipif(
    not native_engine.available(),
    reason="native engine library unavailable (g++/make missing)")


def _engine():
    return native_engine.NativeEngine()


class TestBinding:
    def test_series_ids_stable(self):
        with _engine() as eng:
            a = eng.series(b"metric-a")
            b = eng.series(b"metric-b")
            assert a != b
            assert eng.series(b"metric-a") == a
            assert eng.num_series() == 2
            assert eng.series_key(a) == b"metric-a"
            assert eng.series_key(b) == b"metric-b"

    def test_append_window_roundtrip(self):
        rng = np.random.default_rng(1)
        n = 2000
        ts = np.cumsum(rng.integers(1, 100, n)).astype(np.int64)
        fval = rng.normal(100, 25, n)
        ival = np.zeros(n, np.int64)
        isint = np.zeros(n, np.uint8)
        with _engine() as eng:
            sid = eng.series(b"k")
            eng.append_batch(sid, ts, fval, ival, isint)
            assert eng.series_len(sid) == n
            out_ts, out_fv, _, out_ii = eng.window(sid)
            np.testing.assert_array_equal(out_ts, ts)
            np.testing.assert_array_equal(out_fv, fval)
            assert not out_ii.any()

    def test_int_values_exact(self):
        # Java-long exactness: int64 bits survive (not via double).
        big = np.array([2**62 + 12345, 2**62 + 12346], np.int64)
        with _engine() as eng:
            sid = eng.series(b"ints")
            eng.append_batch(sid, np.array([10, 20], np.int64),
                             np.zeros(2), big, np.ones(2, np.uint8))
            _, _, out_iv, out_ii = eng.window(sid)
            np.testing.assert_array_equal(out_iv, big)
            assert out_ii.all()

    def test_out_of_order_and_dup_lww(self):
        # Merge + sort + last-write-wins, Series.normalize parity.
        with _engine() as eng:
            sid = eng.series(b"ooo")
            eng.append_batch(sid, np.array([30, 10], np.int64),
                             np.array([3.0, 1.0]), np.zeros(2, np.int64),
                             np.zeros(2, np.uint8))
            eng.append_batch(sid, np.array([20, 10], np.int64),
                             np.array([2.0, 9.0]), np.zeros(2, np.int64),
                             np.zeros(2, np.uint8))
            out_ts, out_fv, _, _ = eng.window(sid)
            np.testing.assert_array_equal(out_ts, [10, 20, 30])
            np.testing.assert_array_equal(out_fv, [9.0, 2.0, 3.0])

    def test_window_range_bounds(self):
        with _engine() as eng:
            sid = eng.series(b"r")
            ts = np.arange(0, 1000, 10, np.int64)
            eng.append_batch(sid, ts, ts.astype(np.float64),
                             np.zeros_like(ts), np.zeros(len(ts), np.uint8))
            out_ts, _, _, _ = eng.window(sid, 100, 199)
            np.testing.assert_array_equal(out_ts, np.arange(100, 200, 10))

    def test_delete_range(self):
        with _engine() as eng:
            sid = eng.series(b"d")
            ts = np.arange(0, 100, 10, np.int64)
            eng.append_batch(sid, ts, ts.astype(np.float64),
                             np.zeros_like(ts), np.zeros(len(ts), np.uint8))
            removed = eng.delete_range(sid, 20, 50)
            assert removed == 4
            out_ts, _, _, _ = eng.window(sid)
            np.testing.assert_array_equal(out_ts, [0, 10, 60, 70, 80, 90])

    def test_compression_ratio(self):
        # Realistic cadence (10s +/- jitter) + integer counter values (the
        # dominant monitoring shape): delta-of-delta timestamps + varint
        # values must beat raw 17B/point decisively.  Full-precision
        # random-walk doubles are Gorilla's worst case and stay ~raw size;
        # they must at least not expand.
        rng = np.random.default_rng(2)
        n = 50_000
        ts = 1_356_998_400_000 + np.cumsum(
            rng.integers(9_000, 11_000, n)).astype(np.int64)
        raw = n * 17  # 8B ts + 8B value + 1B flag
        with _engine() as eng:
            sid = eng.series(b"counters")
            iv = (100 + rng.integers(0, 50, n)).astype(np.int64)
            eng.append_batch(sid, ts, np.zeros(n), iv, np.ones(n, np.uint8))
            assert eng.series_bytes(sid) < raw / 3

            sid2 = eng.series(b"walk")
            val = 100.0 + np.cumsum(rng.normal(0, 0.1, n))
            eng.append_batch(sid2, ts, val, np.zeros(n, np.int64),
                             np.zeros(n, np.uint8))
            assert eng.series_bytes(sid2) <= raw

    def test_save_load_roundtrip(self, tmp_path):
        rng = np.random.default_rng(3)
        n = 3000
        ts = np.cumsum(rng.integers(1, 50, n)).astype(np.int64)
        val = rng.normal(0, 1, n)
        path = str(tmp_path / "snap.tsdb")
        with _engine() as eng:
            sid = eng.series(b"persist-me")
            eng.append_batch(sid, ts, val, np.zeros(n, np.int64),
                             np.zeros(n, np.uint8))
            eng.save(path)
        with native_engine.NativeEngine.load(path) as eng2:
            assert eng2.num_series() == 1
            sid2 = eng2.series(b"persist-me")
            out_ts, out_fv, _, _ = eng2.window(sid2)
            np.testing.assert_array_equal(out_ts, ts)
            np.testing.assert_array_equal(out_fv, val)

    def test_load_missing_file_raises(self, tmp_path):
        with pytest.raises(IOError):
            native_engine.NativeEngine.load(str(tmp_path / "nope.tsdb"))


class TestSnapshotIntegration:
    """DiskPersistence writes/reads the native binary codec."""

    def _tsdb(self, tmp_path, native=True):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.utils.config import Config
        return TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.storage.directory": str(tmp_path),
            "tsd.storage.native_snapshot": native,
        }))

    def test_native_snapshot_roundtrip(self, tmp_path):
        tsdb = self._tsdb(tmp_path)
        base = 1_356_998_400
        for h in range(3):
            for k in range(50):
                tsdb.add_point("sys.cpu", base + k * 10, k * h + 0.5,
                               {"host": "w%d" % h})
        tsdb.add_point("sys.int", base, 7, {"host": "w0"})
        tsdb.snapshot()
        assert os.path.exists(tmp_path / "series.tsdb")
        manifest = json.load(open(tmp_path / "snapshot.json"))
        assert manifest["series_codec"] == "native"
        assert manifest["series"] == []  # data lives in the binary file

        fresh = self._tsdb(tmp_path)
        assert fresh.store.num_series == 4
        q = fresh.store.all_series()
        total = sum(len(s.window(0, 1 << 62)[0]) for s in q)
        assert total == 151
        # int exactness survives the native roundtrip
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        tq = TSQuery(start=str(base - 10), end=str(base + 10),
                     queries=[parse_m_subquery("sum:sys.int")])
        tq.validate()
        out = fresh.new_query_runner().run(tq)[0].to_json()
        assert out["dps"][str(base)] == 7

    def test_npz_fallback_config(self, tmp_path):
        tsdb = self._tsdb(tmp_path, native=False)
        tsdb.add_point("sys.cpu", 1_356_998_400, 1.5, {"h": "a"})
        tsdb.snapshot()
        assert os.path.exists(tmp_path / "series.npz")
        assert not os.path.exists(tmp_path / "series.tsdb")
        fresh = self._tsdb(tmp_path, native=False)
        assert fresh.store.num_series == 1


_CHILD = """
import sys
from opentsdb_tpu.storage import native_engine
native_engine._NATIVE_DIR = sys.argv[1]
print(native_engine.available())
"""


def test_concurrent_first_build_never_loads_a_half_written_library(tmp_path):
    """pytest -n 6 on a fresh checkout: every worker finds no .so and
    builds.  Each builds under its own name and renames into place, so
    all of them load a whole library."""
    native = tmp_path / "native"
    native.mkdir()
    for name in ("Makefile", "engine.cpp"):
        shutil.copy(os.path.join(native_engine._NATIVE_DIR, name), native)
    env = dict(os.environ)
    env.pop("TSDB_NATIVE_LIB", None)
    env["PYTHONPATH"] = os.path.dirname(native_engine._NATIVE_DIR)
    procs = [subprocess.Popen([sys.executable, "-c", _CHILD, str(native)],
                              stdout=subprocess.PIPE, env=env, text=True)
             for _ in range(4)]
    outs = [p.communicate(timeout=300)[0].strip() for p in procs]
    assert outs == ["True"] * 4
    assert sorted(os.listdir(native)) == [
        "Makefile", "engine.cpp", native_engine._LIB_NAME]

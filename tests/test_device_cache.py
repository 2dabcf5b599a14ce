"""Device-resident series cache: correctness, staleness, eviction.

The cache must be INVISIBLE in results — every test asserts the cached
answer equals the host-built answer — and visible only in stats.  Models
the reference's storage-cache stance (repeat scans served memory-speed
without changing query semantics).
"""

import json
import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.storage.device_cache import DeviceSeriesCache
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400


# The cache under test serves the RESIDENT route.  Two arms sit in front
# of it in plan_decision and would take these small queries first: the
# fused-dispatch batcher (decided before the device-cache consult, by
# design) and, on the suite's 8 virtual devices, nothing else — 2 series
# stay under tsd.query.mesh.min_series.
BASE_CONF = {"tsd.core.auto_create_metrics": True,
             "tsd.query.batch.enable": "false"}


def make_tsdb(**cfg):
    conf = dict(BASE_CONF)
    conf.update(cfg)
    t = TSDB(Config(conf))
    for i in range(40):
        t.add_point("dc.m", BASE + i * 10, float(i), {"host": "a"})
        t.add_point("dc.m", BASE + i * 10, float(i * 2), {"host": "b"})
    return t


def run_group_query(tsdb, m="avg:1m-avg:dc.m{host=*}",
                    start=str(BASE), end=str(BASE + 400)):
    q = TSQuery(start=start, end=end, queries=[parse_m_subquery(m)])
    q.validate()
    runner = tsdb.new_query_runner()
    res = runner.run(q)
    return res, runner.exec_stats


def dps_map(results):
    return {tuple(sorted(r.tags.items())): r.dps for r in results}


def run_group_query_pre(tsdb, m, start=str(BASE), end=str(BASE + 400)):
    """Same grouped query with pre_aggregate=True on the subquery."""
    sub = parse_m_subquery(m)
    sub.pre_aggregate = True
    q = TSQuery(start=start, end=end, queries=[sub])
    q.validate()
    runner = tsdb.new_query_runner()
    return runner.run(q), runner.exec_stats


class TestDeviceCacheResults:
    def test_second_query_hits_and_matches(self):
        tsdb = make_tsdb()
        cold, stats1 = run_group_query(tsdb)
        warm, stats2 = run_group_query(tsdb)
        assert stats2.get("deviceCacheHit") == 1.0
        assert dps_map(cold) == dps_map(warm)
        assert tsdb.device_cache.hits >= 1
        assert tsdb.device_cache.builds == 1

    def test_subset_filter_hits_same_entry(self):
        tsdb = make_tsdb()
        run_group_query(tsdb)                       # builds the entry
        res, stats = run_group_query(tsdb, "sum:1m-avg:dc.m{host=a}")
        assert stats.get("deviceCacheHit") == 1.0
        assert tsdb.device_cache.builds == 1        # no second build
        (dps,) = dps_map(res).values()
        # host=a values are i=0..39 at 10s cadence: 1m windows avg 6 pts
        assert dps[0][1] == pytest.approx(np.mean([0, 1, 2, 3, 4, 5]))

    def test_window_narrowing_uses_cache(self):
        tsdb = make_tsdb()
        run_group_query(tsdb)
        res, stats = run_group_query(tsdb, start=str(BASE + 60),
                                     end=str(BASE + 180))
        assert stats.get("deviceCacheHit") == 1.0
        ref_tsdb = make_tsdb(**{"tsd.query.device_cache.enable": "false"})
        ref, ref_stats = run_group_query(ref_tsdb, start=str(BASE + 60),
                                         end=str(BASE + 180))
        assert "deviceCacheHit" not in ref_stats
        assert dps_map(res) == dps_map(ref)

    def test_disabled_by_config(self):
        tsdb = make_tsdb(**{"tsd.query.device_cache.enable": "false"})
        assert tsdb.device_cache is None
        _, stats = run_group_query(tsdb)
        assert "deviceCacheHit" not in stats


class TestStaleness:
    def test_append_invalidates_then_refresh_restores(self):
        tsdb = make_tsdb()
        run_group_query(tsdb)
        tsdb.add_point("dc.m", BASE + 400, 99.0, {"host": "a"})
        res, stats = run_group_query(tsdb, end=str(BASE + 401))
        # stale -> host fallback, still correct (fresh point included)
        assert "deviceCacheHit" not in stats
        (a_dps,) = (d for t, d in dps_map(res).items()
                    if dict(t)["host"] == "a")
        # final 1m window holds i=36..39 plus the fresh 99:
        # avg = (36+37+38+39+99)/5 — a stale serve would give 37.5
        assert a_dps[-1][1] == pytest.approx(49.8)
        # background refresh readmits the metric
        assert tsdb.device_cache.refresh(tsdb.store) == 1
        res2, stats2 = run_group_query(tsdb, end=str(BASE + 401))
        assert stats2.get("deviceCacheHit") == 1.0
        assert dps_map(res2) == dps_map(res)

    def test_new_series_invalidates(self):
        tsdb = make_tsdb()
        run_group_query(tsdb)
        tsdb.add_point("dc.m", BASE + 5, 7.0, {"host": "c"})
        res, stats = run_group_query(tsdb)
        assert "deviceCacheHit" not in stats
        assert len(res) == 3
        tsdb.device_cache.refresh(tsdb.store)
        res2, stats2 = run_group_query(tsdb)
        assert stats2.get("deviceCacheHit") == 1.0
        assert dps_map(res2) == dps_map(res)

    def test_deleted_and_recreated_series_never_validates(self):
        # A recreated series has an equal key and a RESTARTED version
        # counter — value-equality alone would let the old snapshot pass
        # validation and serve deleted data (review r3 finding #1).
        tsdb = make_tsdb()
        run_group_query(tsdb)
        metric = tsdb.metrics.get_id("dc.m")
        key_a = sorted((s.key for s in
                        tsdb.store.series_for_metric(metric)),
                       key=lambda k: k.tags)[0]
        old = tsdb.store.get_series(key_a)
        tsdb.store.delete_series(key_a)
        s2 = tsdb.store.get_or_create_series(key_a)
        for i in range(40):   # one append per point: reach the SAME version
            s2.append(BASE * 1000 + i * 10_000, 5.0, False)
        assert s2.version == old.version  # version alone cannot distinguish
        res, stats = run_group_query(tsdb)
        assert "deviceCacheHit" not in stats   # stale, NOT a false hit
        tsdb.device_cache.refresh(tsdb.store)
        res2, stats2 = run_group_query(tsdb)
        assert stats2.get("deviceCacheHit") == 1.0
        assert dps_map(res2) == dps_map(res)

    def test_build_respects_fix_duplicates_off(self):
        # With tsd.storage.fix_duplicates=false a build over duplicate data
        # must FAIL — never silently dedup the live series out from under
        # fsck (review r3 finding #2).
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                            "tsd.storage.fix_duplicates": "false"}))
        for v in (1.0, 2.0):
            tsdb.add_point("dup.m", BASE + 60, v, {"h": "x"})
        tsdb.add_point("dup.m", BASE + 10, 0.0, {"h": "x"})  # keep it dirty
        metric = tsdb.metrics.get_id("dup.m")
        (series,) = tsdb.store.series_for_metric(metric)
        cache = tsdb.device_cache
        assert cache.fix_duplicates is False
        got = cache.batch_for(tsdb.store, metric, [series],
                              BASE * 1000, (BASE + 100) * 1000,
                              fix_duplicates=False)
        assert got is None and cache.builds == 0
        # the duplicate is still there for fsck to find
        with pytest.raises(ValueError):
            series.normalize(fix_duplicates=False)

    def test_pad_contract_matches_pipeline(self):
        from opentsdb_tpu.ops.pipeline import PAD_TS as PIPE_PAD
        from opentsdb_tpu.storage.device_cache import PAD_TS as CACHE_PAD
        assert PIPE_PAD == CACHE_PAD

    def test_i32_pad_contract_matches_downsample(self):
        """The int32 pre-compacted pad sentinel is mirrored (the cache
        must stay importable without jax): clean-batch detection and pad
        sorting both break silently if the two ever drift."""
        import numpy as np
        from opentsdb_tpu.ops.downsample import _I32_PAD
        from opentsdb_tpu.storage.device_cache import I32_PAD_TS
        assert _I32_PAD == I32_PAD_TS
        assert I32_PAD_TS.dtype == np.int32

    def test_dropcaches_clears(self):
        tsdb = make_tsdb()
        run_group_query(tsdb)
        assert len(tsdb.device_cache) == 1
        tsdb.device_cache.invalidate()
        assert len(tsdb.device_cache) == 0
        _, stats = run_group_query(tsdb)    # rebuilds silently
        assert stats.get("deviceCacheHit") == 1.0


class TestBudget:
    def test_oversized_metric_never_cached(self):
        cache = DeviceSeriesCache(max_bytes=1024)   # 64 points worth
        tsdb = make_tsdb()
        metric = tsdb.metrics.get_id("dc.m")
        series = tsdb.store.series_for_metric(metric)
        got = cache.batch_for(tsdb.store, metric, series, BASE * 1000,
                              (BASE + 400) * 1000)
        assert got is None and cache.builds == 0

    def test_build_max_points_gate(self):
        cache = DeviceSeriesCache(max_bytes=1 << 30, build_max_points=10)
        tsdb = make_tsdb()
        metric = tsdb.metrics.get_id("dc.m")
        series = tsdb.store.series_for_metric(metric)
        assert cache.batch_for(tsdb.store, metric, series, BASE * 1000,
                               (BASE + 400) * 1000) is None

    def test_lru_eviction(self):
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
        for m in ("m.one", "m.two"):
            for i in range(16):
                tsdb.add_point(m, BASE + i * 10, float(i), {"h": "x"})
        # budget fits exactly one pow2-padded entry (1024 pts * 16B)
        cache = DeviceSeriesCache(max_bytes=1024 * 16)
        for name in ("m.one", "m.two"):
            metric = tsdb.metrics.get_id(name)
            series = tsdb.store.series_for_metric(metric)
            assert cache.batch_for(tsdb.store, metric, series, BASE * 1000,
                                   (BASE + 200) * 1000) is not None
        assert cache.evictions == 1 and len(cache) == 1

    def test_batch_expansion_guard(self):
        tsdb = make_tsdb()
        metric = tsdb.metrics.get_id("dc.m")
        series = tsdb.store.series_for_metric(metric)
        cache = DeviceSeriesCache(max_bytes=1 << 30, batch_max_bytes=64)
        got = cache.batch_for(tsdb.store, metric, series, BASE * 1000,
                              (BASE + 400) * 1000)
        assert got is None            # would expand past batch_max_bytes
        assert cache.builds == 1      # the entry itself was fine

    def test_cached_metric_preempts_streaming(self):
        # Over the streaming threshold a COLD metric streams (no blocking
        # inline build) and queues itself; after the maintenance-thread
        # build, the same query answers materialized from HBM — identical
        # values either way.
        tsdb = make_tsdb(**{"tsd.query.streaming.point_threshold": "10"})
        res_stream, s1 = run_group_query(tsdb)
        assert s1.get("streamedChunks", 0) > 0
        assert "deviceCacheHit" not in s1
        assert tsdb.device_cache.builds == 0     # cold build was deferred
        assert tsdb.device_cache.refresh(tsdb.store) == 1
        res_cached, s2 = run_group_query(tsdb)
        assert s2.get("deviceCacheHit") == 1.0
        assert "streamedChunks" not in s2
        assert dps_map(res_cached) == dps_map(res_stream)

    def test_rollup_lane_cached_separately(self):
        # raw store and a rollup lane share the metric-uid space: each
        # gets its own entry, and rollup queries hit from HBM too
        tsdb = TSDB(Config({
            **BASE_CONF,
            "tsd.rollups.enable": True,
            "tsd.rollups.config": json.dumps({
                "intervals": [{"interval": "1h", "table": "tsdb-rollup-1h",
                               "preAggregationTable": "tsdb-rollup-agg-1h",
                               "rowSpan": "1d"}],
                "aggregationIds": {"sum": 0, "count": 1, "min": 2,
                                   "max": 3}})}))
        for i in range(30):
            tsdb.add_point("rc.m", BASE + i * 10, float(i), {"h": "a"})
            tsdb.add_aggregate_point("rc.m", BASE + i * 3600, float(i),
                                     {"h": "a"}, False, "1h", "sum")
        raw_q = "sum:1m-avg:rc.m{h=*}"
        roll_q = "sum:1h-sum:rc.m{h=*}"
        run_group_query(tsdb, raw_q)
        res_r, s_r = run_group_query(
            tsdb, roll_q, end=str(BASE + 30 * 3600))
        res_r2, s_r2 = run_group_query(
            tsdb, roll_q, end=str(BASE + 30 * 3600))
        assert s_r2.get("deviceCacheHit") == 1.0
        assert dps_map(res_r2) == dps_map(res_r)
        assert tsdb.device_cache.builds == 2   # raw entry + lane entry
        _, s_raw = run_group_query(tsdb, raw_q)
        assert s_raw.get("deviceCacheHit") == 1.0   # raw entry intact

    def test_pre_aggregate_lane_uses_its_own_entry(self):
        # pre_aggregate=True resolves series from the pre-agg LANE even
        # on a raw segment: the cache must key on that lane, never build
        # (and then stale-mark) a raw-store entry for it (review r3)
        tsdb = TSDB(Config({
            **BASE_CONF,
            "tsd.rollups.enable": True,
            "tsd.rollups.config": json.dumps({
                "intervals": [{"interval": "1h", "table": "t",
                               "preAggregationTable": "tp",
                               "rowSpan": "1d"}],
                "aggregationIds": {"sum": 0, "count": 1}})}))
        for i in range(20):
            tsdb.add_point("pa.m", BASE + i * 10, float(i), {"host": "a"})
            tsdb.add_aggregate_point("pa.m", BASE + i * 10, float(i * 3),
                                     {"host": "a"}, True, None, None, "sum")
        q = "sum:1m-avg:pa.m{host=*}"
        run_group_query(tsdb, q)                      # raw entry
        res1, _ = run_group_query_pre(tsdb, q)        # pre-agg lane entry
        res2, s2 = run_group_query_pre(tsdb, q)
        assert s2.get("deviceCacheHit") == 1.0
        assert dps_map(res2) == dps_map(res1)
        assert tsdb.device_cache.builds == 2
        _, s_raw = run_group_query(tsdb, q)
        assert s_raw.get("deviceCacheHit") == 1.0     # raw entry untouched

    def test_stats_surface(self):
        tsdb = make_tsdb()
        run_group_query(tsdb)
        stats = tsdb.collect_stats()
        assert stats["tsd.query.device_cache.entries"] == 1.0
        assert stats["tsd.query.device_cache.builds"] == 1.0


class TestGatherCorrectness:
    def test_gather_matches_host_build(self):
        from opentsdb_tpu.ops.pipeline import build_batch, PAD_TS
        tsdb = make_tsdb()
        metric = tsdb.metrics.get_id("dc.m")
        series = sorted(tsdb.store.series_for_metric(metric),
                        key=lambda s: s.key.tags)
        cache = DeviceSeriesCache(max_bytes=1 << 30)
        lo_ms, hi_ms = (BASE + 60) * 1000, (BASE + 180) * 1000
        ts_d, val_d, mask_d = cache.batch_for(tsdb.store, metric, series,
                                              lo_ms, hi_ms)
        windows = [s.window(lo_ms, hi_ms) for s in series]
        ts_h, val_h, mask_h, _ = build_batch(windows)
        ts_d, val_d, mask_d = (np.asarray(ts_d), np.asarray(val_d),
                               np.asarray(mask_d))
        assert ts_d.shape == ts_h.shape
        np.testing.assert_array_equal(mask_d, mask_h)
        np.testing.assert_array_equal(ts_d[mask_d], ts_h[mask_h])
        np.testing.assert_array_equal(val_d[mask_d], val_h[mask_h])
        assert (ts_d[~mask_d] == PAD_TS).all()


def _gather_cases(p=8192):
    """name -> (buffer length, data length, N, starts, lengths).  The
    buffer holds `data` real points and pads behind them, as an entry's
    does; 2500 and 3000 are no multiple of the 128-element tile."""
    return {
        "zero_length_row": (p, 6000, 64, [100, -7, p + 900, 3000],
                            [40, 0, 0, 64]),
        "lengths_equal_n": (p, 6000, 256, [0, 513, 2900], [256, 256, 256]),
        "one_row": (p, 6000, 512, [1234], [300]),
        "n_8": (p, 6000, 8, [5, 1021, 127, 4090], [8, 3, 1, 7]),
        "n_4096": (p, 8000, 4096, [0, 3001, 3904], [4096, 2500, 4096]),
        "unequal_lengths": (p, 6000, 128, [0, 129, 700, 2049, 5000],
                            [1, 128, 77, 5, 100]),
        "row_passes_the_data_end": (p, 6000, 1024, [5900, 5000, 100],
                                    [100, 1000, 1024]),
        "row_passes_the_buffer_end": (p, p, 1024, [p - 1, p - 300, p - 1024],
                                      [1, 300, 1024]),
        "starts_off_the_tile": (p, 6000, 256, [1, 127, 129, 255, 383, 2500],
                                [256, 200, 256, 31, 256, 255]),
        "starts_on_the_tile": (p, 6000, 256, [0, 128, 1024, 4096],
                               [256, 256, 100, 1]),
        "buffer_off_the_tile": (3000, 3000, 512, [0, 2999, 2600, 1777],
                                [512, 1, 400, 512]),
    }


GATHER_CASES = _gather_cases()

T0 = 1_356_998_400_000


def _fill(kind, rng, data):
    """(timestamps, values) of `data` stored points of one kind: what the
    pinned 32-bit halves must carry without losing a bit."""
    ts = T0 + np.cumsum(rng.integers(0, 2_000_000, data))
    val = rng.normal(size=data) * 1e6
    if kind == "doubles":
        # 53-bit mantissas and signed zeros; some stamps lie before the
        # ts_base the test passes and some 2^31 ms past it, so the int32
        # clip is hit at both ends
        val[::97] = -0.0
    elif kind == "integer_gauges":
        val = rng.integers(0, 101, data).astype(np.float64)
        val[::97] = -0.0    # the two-float32 form keeps a zero's sign too
    elif kind == "negative_values":
        val = -np.abs(val)
        val[::3] = -rng.integers(1, 2**40, len(val[::3])) / 4.0
    elif kind == "magnitudes_to_1e15":
        val = rng.choice([-1.0, 1.0], data) \
            * 10.0 ** rng.uniform(-15, 15, data)
        val[::5] = rng.integers(-10**15, 10**15, len(val[::5]))
        val[:6] = [1e15, -1e15, 2.0**48, 2.0**48 + 1, 2.0**53 - 1, 1e-15]
    elif kind == "under_the_pair_floor":
        # magnitudes whose second float32 half would be a denormal, down
        # to the doubles' own denormals: such an entry keeps its float64
        val = rng.choice([-1.0, 1.0], data) \
            * 10.0 ** rng.uniform(-320, -22, data)
        val[:4] = [5e-324, -5e-324, 2.0 ** -75, -1e-30]
    elif kind == "stamps_across_2_32":
        # the low word wraps (a multiple of 2^32 ms) and changes sign as
        # an int32 (2^31 inside a word) in the middle of the data
        ts = 316 * 2**32 - data // 2 + np.arange(data)
        ts[data // 4:] += 2**31 - data // 4
        ts = np.sort(ts)
    elif kind == "stamps_around_the_epoch":
        # 2^32 ms itself (1970-02-19), a high word of 0 and of 1
        ts = 2**32 - data // 2 + np.arange(data) * 3
    else:
        raise AssertionError(kind)
    return ts.astype(np.int64), val


FILL_KINDS = ["doubles", "integer_gauges", "negative_values",
              "magnitudes_to_1e15", "under_the_pair_floor",
              "stamps_across_2_32", "stamps_around_the_epoch"]


def _join_on_host(pinned):
    """The pinned halves put together with numpy: (int64 ts, float64 val)."""
    ts_lo, ts_hi, parts = pinned
    ts = (np.asarray(ts_hi).astype(np.int64) << 32) \
        | np.asarray(ts_lo).astype(np.int64)
    val = np.asarray(parts[0]).astype(np.float64)
    for part in parts[1:]:
        part = np.asarray(part).astype(np.float64)
        val = np.where(part == 0, val, val + part)   # keeps a zero's sign
    return ts, val


class TestGatherParity:
    """_gather_windows against a plain per-element numpy reference,
    bit for bit on ts, val and mask, pads included."""

    @pytest.mark.parametrize("ts_base", [None, T0 + 1_234],
                             ids=["int64", "ts_base"])
    @pytest.mark.parametrize("kind", FILL_KINDS)
    @pytest.mark.parametrize("case", sorted(GATHER_CASES))
    def test_gather_equals_the_per_element_reference(self, case, kind,
                                                     ts_base):
        from opentsdb_tpu.storage.device_cache import (
            I32_PAD_TS, PAD_TS, _gather_windows, _pin_columns)
        p, data, n, starts, lengths = GATHER_CASES[case]
        starts = np.asarray(starts, np.int64)
        lengths = np.asarray(lengths, np.int64)
        rng = np.random.default_rng(sorted(GATHER_CASES).index(case))
        ts_buf = np.full(p, PAD_TS, np.int64)
        val_buf = np.zeros(p)
        ts_buf[:data], val_buf[:data] = _fill(kind, rng, data)
        if kind == "stamps_around_the_epoch" and ts_base is not None:
            ts_base = 2**32 - 1_000

        j = np.arange(n)
        mask = j[None, :] < lengths[:, None]
        at = np.clip(starts[:, None] + j[None, :], 0, p - 1)
        if ts_base is None:
            ts = np.where(mask, ts_buf[at], PAD_TS)
        else:
            ts = np.where(mask, np.clip(ts_buf[at] - ts_base, 0, I32_PAD_TS),
                          I32_PAD_TS).astype(np.int32)
        val = np.where(mask, val_buf[at], 0.0)

        got_ts, got_val, got_mask = (np.asarray(a) for a in _gather_windows(
            _pin_columns(ts_buf, val_buf), starts, lengths, n, ts_base))
        assert got_ts.dtype == ts.dtype and got_ts.shape == ts.shape
        assert got_val.dtype == np.float64 and got_mask.dtype == np.bool_
        np.testing.assert_array_equal(got_mask, mask)
        np.testing.assert_array_equal(got_ts, ts)
        # bit-equal, signed zeros included
        np.testing.assert_array_equal(got_val.view(np.int64),
                                      val.view(np.int64))


class TestPinnedHalves:
    """What `_pin_columns` pins gives the 64-bit columns back exactly."""

    def test_pad_stamps_pin_as_their_own_words(self):
        """PAD_TS is 0x7FFFFFFF / 0xFFFFFFFF in the pinned words, and a
        pad comes back from the gather as PAD_TS or, compacted, as
        I32_PAD_TS — the sentinels the prefix path sorts by."""
        from opentsdb_tpu.storage.device_cache import (
            I32_PAD_TS, PAD_TS, _gather_windows, _pin_columns)
        pinned = _pin_columns(np.full(1024, PAD_TS, np.int64),
                              np.zeros(1024))
        assert np.asarray(pinned[0]).dtype == np.uint32
        assert np.asarray(pinned[1]).dtype == np.uint32
        assert (np.asarray(pinned[0]) == 0xFFFFFFFF).all()
        assert (np.asarray(pinned[1]) == 0x7FFFFFFF).all()
        starts, lengths = np.array([0, 500]), np.array([8, 8])
        ts, _, _ = _gather_windows(pinned, starts, lengths, 8)
        assert np.asarray(ts).dtype == np.int64
        assert (np.asarray(ts) == PAD_TS).all()
        ts, _, _ = _gather_windows(pinned, starts, lengths, 8, ts_base=T0)
        assert np.asarray(ts).dtype == np.int32
        assert (np.asarray(ts) == I32_PAD_TS).all()

    @pytest.mark.parametrize("kind", FILL_KINDS)
    def test_pinned_columns_join_to_the_input(self, kind):
        from opentsdb_tpu.storage.device_cache import _pin_columns
        ts_buf, val_buf = _fill(kind, np.random.default_rng(7), 3000)
        pinned = _pin_columns(ts_buf, val_buf)
        assert all(len(np.asarray(b)) == 3000
                   for b in (*pinned[:2], *pinned[2]))
        ts, val = _join_on_host(pinned)
        np.testing.assert_array_equal(ts, ts_buf)
        np.testing.assert_array_equal(val.view(np.int64),
                                      val_buf.view(np.int64))

    def test_values_of_48_bits_pin_as_two_float32(self):
        """Integer gauges — every benchmark cell's data — take the form
        the chip reads on every backend; only a value that two float32
        cannot hold keeps the 64-bit buffer (never on a TPU, whose
        float64 is such a pair)."""
        from opentsdb_tpu.storage.device_cache import _pin_values
        parts = _pin_values(np.arange(-500, 524, dtype=np.float64) * 0.25)
        assert [np.asarray(b).dtype for b in parts] == [np.float32] * 2
        import jax
        if jax.default_backend() == "cpu":
            parts = _pin_values(np.full(1024, 0.1))
            assert [np.asarray(b).dtype for b in parts] == [np.float64]
        # a second half in float32's denormal range, which a TPU's
        # arithmetic would flush: the float64 stays, on every backend
        tiny = np.arange(1024, dtype=np.float64)
        tiny[7] = 2.0 ** -75
        parts = _pin_values(tiny)
        assert [np.asarray(b).dtype for b in parts] == [np.float64]

    @pytest.mark.parametrize("values", ["integers", "fractions"])
    def test_a_built_entry_joins_to_the_snapshot(self, values):
        """`_build_guarded`'s buffers, fetched and joined on the host, are
        every series' snapshot concatenated, bit for bit, with PAD_TS /
        0.0 behind; 16 bytes a point are accounted as before."""
        from opentsdb_tpu.storage.device_cache import PAD_TS
        tsdb = TSDB(Config(BASE_CONF))
        rng = np.random.default_rng(3)
        for host in "abc":
            for i in range(50):
                v = float(rng.integers(-100, 100)) if values == "integers" \
                    else float(rng.normal() * 1e3)
                tsdb.add_point("dc.m", BASE + i * 10, v, {"host": host})
        metric = tsdb.metrics.get_id("dc.m")
        cache = DeviceSeriesCache(max_bytes=1 << 30)
        entry = cache._build(tsdb.store, metric)
        snaps = [s.snapshot(True) for s in entry.series_objs]
        want_ts = np.concatenate([t for t, _, _ in snaps])
        want_val = np.concatenate([v for _, v, _ in snaps])
        ts, val = _join_on_host(entry.pinned)
        total = len(want_ts)
        assert len(ts) == len(val) == 1024 and entry.nbytes == 1024 * 16
        np.testing.assert_array_equal(ts[:total], want_ts)
        np.testing.assert_array_equal(ts, entry.ts_host)
        assert (ts[total:] == PAD_TS).all()
        np.testing.assert_array_equal(val[:total].view(np.int64),
                                      want_val.view(np.int64))
        assert (val[total:].view(np.int64) == 0).all()
        pinned_bytes = sum(np.asarray(b).nbytes
                           for b in (*entry.pinned[:2], *entry.pinned[2]))
        assert pinned_bytes == entry.nbytes

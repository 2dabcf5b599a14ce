"""Golden-value tests for downsample and rate kernels.

Reference semantics: test/core/TestDownsampler.java (interval align, fills),
TestRateSpan.java (per-second dv/dt, counters).
"""

import numpy as np

from opentsdb_tpu.ops.downsample import (
    downsample, FixedWindows, EdgeWindows, AllWindow,
    FILL_NONE, FILL_ZERO, FILL_NAN, FILL_SCALAR)
from opentsdb_tpu.ops.rate import rate, RateOptions
from tests.kernel_utils import batch, collect


def run_ds(series, agg, windows, fill=FILL_NONE, fill_value=0.0):
    ts, val, mask = batch(series)
    spec, wargs = windows.split()
    wts, out, omask = downsample(ts, val, mask, agg, spec, wargs, fill,
                                 fill_value)
    return collect(np.broadcast_to(np.asarray(wts), out.shape), out, omask)


def ds(series, agg, start, end, interval, fill=FILL_NONE, fill_value=0.0):
    return run_ds(series, agg, FixedWindows.for_range(start, end, interval),
                  fill, fill_value)


class TestDownsample:
    SERIES = [([0, 10_000, 20_000, 35_000, 45_000], [1, 2, 3, 4, 5])]

    def test_avg_30s(self):
        out = ds(self.SERIES, "avg", 0, 59_999, 30_000)
        assert out == [(0, 2.0), (30_000, 4.5)]

    def test_sum_min_max_count(self):
        assert ds(self.SERIES, "sum", 0, 59_999, 30_000) == [
            (0, 6.0), (30_000, 9.0)]
        assert ds(self.SERIES, "min", 0, 59_999, 30_000) == [
            (0, 1.0), (30_000, 4.0)]
        assert ds(self.SERIES, "max", 0, 59_999, 30_000) == [
            (0, 3.0), (30_000, 5.0)]
        assert ds(self.SERIES, "count", 0, 59_999, 30_000) == [
            (0, 3.0), (30_000, 2.0)]

    def test_interval_alignment_to_epoch(self):
        # Points at 95s and 105s with 60s interval -> windows 60 and 100... no:
        # epoch-aligned: 95_000 -> window 60_000; 105_000 -> window 60_000.
        out = ds([([95_000, 105_000], [1, 3])], "avg", 60_000, 119_999, 60_000)
        assert out == [(60_000, 2.0)]

    def test_fill_none_skips_empty(self):
        out = ds([([0, 60_000], [1, 2])], "sum", 0, 89_999, 30_000)
        assert out == [(0, 1.0), (60_000, 2.0)]  # window 30_000 absent

    def test_fill_zero(self):
        out = ds([([0, 60_000], [1, 2])], "sum", 0, 89_999, 30_000, FILL_ZERO)
        assert out == [(0, 1.0), (30_000, 0.0), (60_000, 2.0)]

    def test_fill_nan(self):
        out = ds([([0, 60_000], [1, 2])], "sum", 0, 89_999, 30_000, FILL_NAN)
        assert out[0] == (0, 1.0)
        assert np.isnan(out[1][1])
        assert out[2] == (60_000, 2.0)

    def test_fill_scalar(self):
        out = ds([([0, 60_000], [1, 2])], "sum", 0, 89_999, 30_000,
                 FILL_SCALAR, fill_value=42.0)
        assert out[1] == (30_000, 42.0)

    def test_dev(self):
        out = ds([([0, 1000, 2000], [2.0, 4.0, 6.0])], "avg", 0, 29_999, 30_000)
        assert out == [(0, 4.0)]
        out = ds([([0, 1000, 2000], [2.0, 4.0, 6.0])], "dev", 0, 29_999, 30_000)
        np.testing.assert_allclose(out[0][1], 2.0)

    def test_first_last_diff(self):
        series = [([0, 1000, 2000], [7.0, 1.0, 9.0])]
        assert ds(series, "first", 0, 29_999, 30_000) == [(0, 7.0)]
        assert ds(series, "last", 0, 29_999, 30_000) == [(0, 9.0)]
        assert ds(series, "diff", 0, 29_999, 30_000) == [(0, 2.0)]

    def test_median_and_percentile(self):
        series = [([i * 100 for i in range(10)],
                   [float(i + 1) for i in range(10)])]
        out = ds(series, "median", 0, 999, 1000)
        assert out == [(0, 6.0)]  # sorted[10//2]
        out = ds(series, "p50", 0, 999, 1000)
        np.testing.assert_allclose(out[0][1], 5.5)  # legacy pos=5.5

    def test_multi_series_independent(self):
        out = ds([([0, 1000], [1, 2]), ([0, 1000], [10, 20])],
                 "sum", 0, 29_999, 30_000)
        assert out == [(0, 3.0), (0, 30.0)]

    def test_nan_values_skipped(self):
        out = ds([([0, 1000, 2000], [1.0, np.nan, 3.0])], "avg", 0, 29_999,
                 30_000)
        assert out == [(0, 2.0)]

    def test_calendar_edges(self):
        # Two "days" delimited by an uneven DST-style edge set.
        got = run_ds([([10_000, 100_000], [1.0, 5.0])], "sum",
                     EdgeWindows((0, 90_000, 176_400_000)))
        assert got == [(0, 1.0), (90_000, 5.0)]

    def test_run_all(self):
        # Points in [500, 2500): 1000 and 2000 -> 5; ts==2500 excluded.
        got = run_ds([([0, 1000, 2000, 2500], [1, 2, 3, 9])], "sum",
                     AllWindow(500, 2500))
        assert got == [(500, 5.0)]

    def test_dev_large_magnitude(self):
        # Two-pass dev must survive catastrophic cancellation at high means.
        out = ds([([0, 1000], [1e8, 1e8 + 1])], "dev", 0, 29_999, 30_000)
        np.testing.assert_allclose(out[0][1], 0.7071067811865476, rtol=1e-9)

    def test_same_spec_different_range_no_recompile(self):
        # Sliding the query window must hit the jit cache (static parts equal).
        w1 = FixedWindows.for_range(0, 599_999, 60_000)
        w2 = FixedWindows.for_range(120_000, 719_999, 60_000)
        s1, _ = w1.split()
        s2, _ = w2.split()
        assert s1 == s2


class TestRate:
    def run_rate(self, series, options=RateOptions(), all_int=False):
        ts, val, mask = batch(series)
        rts, rout, rmask, _ = rate(ts, val, mask, options, all_int)
        return collect(rts, rout, rmask)

    def test_simple_rate(self):
        out = self.run_rate([([0, 10_000, 20_000], [0, 10, 40])])
        assert out == [(10_000, 1.0), (20_000, 3.0)]

    def test_first_point_dropped(self):
        out = self.run_rate([([5000], [100])])
        assert out == []

    def test_counter_rollover(self):
        opts = RateOptions(counter=True, counter_max=100)
        out = self.run_rate([([0, 10_000], [95, 5])], opts, all_int=True)
        # diff = 100 - 95 + 5 = 10 over 10s -> 1.0
        assert out == [(10_000, 1.0)]

    def test_counter_reset_suppression(self):
        opts = RateOptions(counter=True, counter_max=2**63 - 1, reset_value=10)
        out = self.run_rate([([0, 1000], [1_000_000, 5])], opts, all_int=True)
        # Rollover rate is astronomical > reset_value -> emit 0.
        assert out == [(1000, 0.0)]

    def test_drop_resets(self):
        opts = RateOptions(counter=True, drop_resets=True)
        out = self.run_rate([([0, 1000, 2000, 3000], [10, 20, 5, 15])], opts,
                            all_int=True)
        # Reset between 1000 and 2000 dropped; 2000->3000 rate = 10/1 = 10.
        assert out == [(1000, 10.0), (3000, 10.0)]

    def test_rate_with_gaps_in_mask(self):
        ts = np.array([[0, 1000, 2000, 3000]], dtype=np.int64)
        val = np.array([[0.0, 99.0, 20.0, 30.0]])
        mask = np.array([[True, False, True, True]])
        _, out, omask, _ = rate(ts, val, mask, RateOptions())
        got = collect(ts, out, omask)
        # Gap at 1000 skipped: rate at 2000 spans 0->2000 = 20/2 = 10.
        assert got == [(2000, 10.0), (3000, 10.0)]

    def test_ms_precision(self):
        out = self.run_rate([([0, 500], [0, 5])])
        assert out == [(500, 10.0)]  # 5 units / 0.5s


class TestX64Guard:
    """ops.downsample.require_x64 (tsdblint jax-int64-no-x64-guard
    satellite): with jax_enable_x64 off, jnp.int64 silently lowers to
    int32 and ms timestamps past 2^31 truncate — the window planners
    must refuse instead."""

    def test_planners_refuse_without_x64(self):
        import jax
        import pytest
        from opentsdb_tpu.ops.downsample import (
            AllWindow, EdgeWindows, FixedWindows)
        jax.config.update("jax_enable_x64", False)
        try:
            with pytest.raises(RuntimeError, match="x64"):
                FixedWindows.for_range(0, 60_000, 10_000).split()
            with pytest.raises(RuntimeError, match="x64"):
                EdgeWindows(edges=(0, 1000, 2000)).split()
            with pytest.raises(RuntimeError, match="x64"):
                AllWindow(0, 1000).split()
        finally:
            jax.config.update("jax_enable_x64", True)

    def test_planners_work_with_x64(self):
        from opentsdb_tpu.ops.downsample import FixedWindows
        spec, wargs = FixedWindows.for_range(0, 60_000, 10_000).split()
        assert spec.count >= 7

    def test_tsdb_construction_reasserts_x64(self):
        import jax
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.utils.config import Config
        jax.config.update("jax_enable_x64", False)
        try:
            TSDB(Config())          # default tsd.tpu.precision.x64=true
            assert jax.config.jax_enable_x64
        finally:
            jax.config.update("jax_enable_x64", True)

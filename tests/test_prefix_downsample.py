"""Prefix-sum downsample path vs segment-reduction path equivalence.

The additive-moment family (sum/count/avg/squareSum/dev/zimsum) now runs as
sorted prefix sums differenced at binary-searched window edges (no scatter —
TPU scatters serialize, VERDICT round-1 weak #1).  These property tests pin
it against an independent per-window numpy reduction on ragged random
batches across all three window kinds.
"""

import numpy as np
import pytest

from opentsdb_tpu.ops.downsample import (
    downsample, FixedWindows, EdgeWindows, AllWindow, PREFIX_AGGS,
    FILL_NONE)

START = 1_356_998_400_000


def _random_batch(rng, s=5, n_max=40):
    """Ragged sorted rows with pads at int64 max, occasional NaN values."""
    ts = np.full((s, 64), np.iinfo(np.int64).max, np.int64)
    val = np.zeros((s, 64), np.float64)
    mask = np.zeros((s, 64), bool)
    for i in range(s):
        k = int(rng.integers(0, n_max))
        t = START + np.sort(rng.choice(600_000, size=k, replace=False))
        v = rng.normal(100.0, 30.0, k)
        v[rng.random(k) < 0.05] = np.nan
        ts[i, :k] = t
        val[i, :k] = v
        mask[i, :k] = True
    return ts, val, mask


def _numpy_reference(ts, val, mask, agg, edges):
    """Independent per-window loop (the reference's ValuesInInterval shape)."""
    s = ts.shape[0]
    w = len(edges) - 1
    out = np.full((s, w), np.nan)
    cnt = np.zeros((s, w), np.int64)
    for i in range(s):
        for k in range(w):
            sel = mask[i] & (ts[i] >= edges[k]) & (ts[i] < edges[k + 1]) \
                & ~np.isnan(val[i])
            vals = val[i][sel]
            cnt[i, k] = len(vals)
            if not len(vals):
                continue
            if agg in ("sum", "zimsum", "pfsum"):
                out[i, k] = vals.sum()
            elif agg == "count":
                out[i, k] = len(vals)
            elif agg == "avg":
                out[i, k] = vals.mean()
            elif agg == "squareSum":
                out[i, k] = (vals * vals).sum()
            elif agg == "dev":
                out[i, k] = vals.std(ddof=1) if len(vals) >= 2 else 0.0
    return out, cnt


@pytest.mark.parametrize("agg", sorted(PREFIX_AGGS))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_fixed_windows_match_reference(agg, seed):
    rng = np.random.default_rng(seed)
    ts, val, mask = _random_batch(rng)
    windows = FixedWindows.for_range(START + 20_000, START + 520_000, 60_000)
    spec, wargs = windows.split()
    wts, out, omask = downsample(ts, val, mask, agg, spec, wargs, FILL_NONE)
    out = np.asarray(out)
    omask = np.asarray(omask)
    edges = windows.first_window_ms + np.arange(windows.count + 1) * 60_000
    want, want_cnt = _numpy_reference(ts, val, mask, agg, edges)
    np.testing.assert_array_equal(omask[:, :windows.count], want_cnt > 0)
    got = out[:, :windows.count][want_cnt > 0]
    np.testing.assert_allclose(got, want[want_cnt > 0], rtol=1e-11,
                               atol=1e-9)


@pytest.mark.parametrize("agg", ["sum", "avg", "dev"])
def test_edge_windows_match_reference(agg):
    rng = np.random.default_rng(3)
    ts, val, mask = _random_batch(rng)
    edges = [START, START + 100_000, START + 130_000, START + 400_000]
    windows = EdgeWindows(tuple(edges))
    spec, wargs = windows.split()
    wts, out, omask = downsample(ts, val, mask, agg, spec, wargs, FILL_NONE)
    want, want_cnt = _numpy_reference(ts, val, mask, agg, np.asarray(edges))
    got = np.asarray(out)[:, :windows.count]
    np.testing.assert_array_equal(np.asarray(omask)[:, :windows.count],
                                  want_cnt > 0)
    np.testing.assert_allclose(got[want_cnt > 0], want[want_cnt > 0],
                               rtol=1e-11, atol=1e-9)


@pytest.mark.parametrize("agg", ["sum", "count", "avg"])
def test_all_window_matches_reference(agg):
    rng = np.random.default_rng(4)
    ts, val, mask = _random_batch(rng)
    windows = AllWindow(START + 10_000, START + 500_000)
    spec, wargs = windows.split()
    wts, out, omask = downsample(ts, val, mask, agg, spec, wargs, FILL_NONE)
    want, want_cnt = _numpy_reference(
        ts, val, mask, agg, np.asarray([START + 10_000, START + 500_000]))
    got = np.asarray(out)[:, :1]
    np.testing.assert_array_equal(np.asarray(omask)[:, :1], want_cnt > 0)
    np.testing.assert_allclose(got[want_cnt > 0], want[want_cnt > 0],
                               rtol=1e-11, atol=1e-9)


class TestScanModesAndCompaction:
    """The prefix-scan and edge-search forms + int32 ts compaction.

    Rows of N=1024 make every sub-block form eligible; these pin the
    forms (each through the `kernel_forms` fixture: on the CPU backend
    the chooser alone never reaches most of them) and the int32 / int64
    timestamp compaction decision against the numpy reference and each
    other.
    """

    def _big_batch(self, rng, s=4, n=1024, spread_ms=40_000_000,
                   nan_rate=0.05):
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        for i in range(s):
            k = int(rng.integers(n // 2, n - 7))
            t = START + np.sort(rng.choice(spread_ms, size=k, replace=False))
            v = rng.normal(100.0, 30.0, k)
            if nan_rate:
                v[rng.random(k) < nan_rate] = np.nan
            ts[i, :k] = t
            val[i, :k] = v
            mask[i, :k] = True
        return ts, val, mask

    @staticmethod
    def _assert_matches_reference(ts, val, mask, agg, windows, out, omask):
        """One definition of the numpy-reference comparison (values AND
        output mask) shared by every test in this class."""
        edges = np.arange(windows.first_window_ms,
                          windows.first_window_ms
                          + (windows.count + 1) * 3_600_000, 3_600_000)
        want, want_cnt = _numpy_reference(ts, val, mask, agg, edges)
        got = np.asarray(out)[:, :windows.count]
        got_mask = np.asarray(omask)[:, :windows.count]
        np.testing.assert_array_equal(got_mask, want_cnt > 0)
        np.testing.assert_allclose(got[want_cnt > 0], want[want_cnt > 0],
                                   rtol=1e-11, atol=1e-9)

    @pytest.mark.parametrize("agg", sorted(PREFIX_AGGS))
    def test_scan_modes_agree_and_match_reference(self, agg,
                                                  kernel_forms):
        """flat / subblock / subblock2 scan forms index and sum
        identically (subblock replaces the full-length f64 cumsum with
        sub-block reduces + 32-wide remainder dots — r4 chip
        attribution)."""
        rng = np.random.default_rng(11)
        ts, val, mask = self._big_batch(rng)
        windows = FixedWindows.for_range(START, START + 40_000_000, 3_600_000)
        spec, wargs = windows.split()
        outs = {}
        for mode in ("flat", "subblock", "subblock2"):
            kernel_forms(scan=mode)
            _, out, omask = downsample(ts, val, mask, agg, spec, wargs,
                                       FILL_NONE)
            outs[mode] = (np.asarray(out), np.asarray(omask))
        for mode in ("subblock", "subblock2"):
            np.testing.assert_array_equal(outs["flat"][1], outs[mode][1])
            m = outs["flat"][1]
            np.testing.assert_allclose(outs[mode][0][m], outs["flat"][0][m],
                                       rtol=1e-12, atol=1e-12)
        self._assert_matches_reference(ts, val, mask, agg, windows,
                                       outs["subblock"][0],
                                       outs["subblock"][1])

    @pytest.mark.parametrize("agg", ["avg", "count", "dev"])
    def test_dirty_batches_take_the_counted_path(self, agg):
        """The clean-batch count shortcut (count = diff(idx), skipping the
        int32 cumsum) must never fire wrong: batches with NaN values or
        masked-out REAL slots (mask false but ts real — not a pad) answer
        identically to the numpy reference."""
        rng = np.random.default_rng(7)
        ts, val, mask = self._big_batch(rng)     # already has NaNs
        # masked-out real slots: valid timestamps the mask excludes
        drop = rng.random(mask.shape) < 0.1
        mask2 = mask & ~drop
        windows = FixedWindows.for_range(START, START + 40_000_000, 3_600_000)
        spec, wargs = windows.split()
        _, out, omask = downsample(ts, val, mask2, agg, spec, wargs,
                                   FILL_NONE)
        self._assert_matches_reference(ts, val, mask2, agg, windows, out,
                                       omask)

    @pytest.mark.parametrize("agg", sorted(PREFIX_AGGS))
    def test_clean_batches_take_the_diff_shortcut(self, agg):
        """CLEAN batches (no NaN, mask == real slots — the build_batch /
        device-cache construction) answer via count = diff(idx); pin that
        branch against the numpy reference (nothing else in the suite
        exercises it: every other batch has NaNs)."""
        rng = np.random.default_rng(13)
        ts, val, mask = self._big_batch(rng, nan_rate=0.0)
        # assert the batch really satisfies the clean predicate the
        # kernel tests (mask == realness AND no NaN under mask) — else a
        # regression disabling the shortcut would pass unnoticed (both
        # branches agree on counts)
        assert not np.isnan(val[mask]).any()
        np.testing.assert_array_equal(mask, ts != np.iinfo(np.int64).max)
        windows = FixedWindows.for_range(START, START + 40_000_000, 3_600_000)
        spec, wargs = windows.split()
        _, out, omask = downsample(ts, val, mask, agg, spec, wargs,
                                   FILL_NONE)
        self._assert_matches_reference(ts, val, mask, agg, windows, out,
                                       omask)

    @pytest.mark.parametrize("agg", ["avg", "sum", "count", "dev", "min",
                                     "max"])
    def test_search_modes_agree(self, agg, kernel_forms):
        """compare_all (fused compare+reduce) and hier (sub-block firsts +
        32-wide remainder compare) must index identically to the binary
        search — min/max included: the extreme reset-scan consumes the
        same edge positions."""
        rng = np.random.default_rng(23)
        ts, val, mask = self._big_batch(rng)
        windows = FixedWindows.for_range(START, START + 40_000_000, 3_600_000)
        spec, wargs = windows.split()
        outs = {}
        for mode in ("scan", "compare_all", "hier"):
            kernel_forms(search=mode)
            _, out, omask = downsample(ts, val, mask, agg, spec, wargs,
                                       FILL_NONE)
            outs[mode] = (np.asarray(out), np.asarray(omask))
        for mode in ("compare_all", "hier"):
            np.testing.assert_array_equal(outs["scan"][1], outs[mode][1])
            m = outs["scan"][1]
            np.testing.assert_allclose(outs[mode][0][m],
                                       outs["scan"][0][m],
                                       rtol=1e-12, atol=1e-12)

    def test_hier_search_tie_timestamps(self, kernel_forms):
        """Duplicate timestamps straddling sub-block boundaries: the hier
        search's strict-< decomposition must agree with searchsorted
        'left' when runs of equal timestamps cross the 32-point granule
        and when edges land exactly on a timestamp."""
        s, n = 2, 128
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        # row 0: one value repeated across 3 sub-blocks, edge == the value
        t0 = START + 60_000
        ts[0, :100] = t0
        val[0, :100] = 1.0
        mask[0, :100] = True
        # row 1: ties at a window edge exactly at a sub-block boundary
        ts[1, :64] = START
        ts[1, 64:96] = START + 120_000
        val[1, :96] = 2.0
        mask[1, :96] = True
        windows = FixedWindows.for_range(START, START + 300_000, 60_000)
        spec, wargs = windows.split()
        outs = {}
        for mode in ("scan", "hier"):
            kernel_forms(search=mode)
            _, out, omask = downsample(ts, val, mask, "sum", spec,
                                       wargs, FILL_NONE)
            outs[mode] = (np.asarray(out), np.asarray(omask))
        np.testing.assert_array_equal(outs["scan"][1], outs["hier"][1])
        np.testing.assert_allclose(outs["hier"][0][outs["scan"][1]],
                                   outs["scan"][0][outs["scan"][1]])

    def test_int64_fallback_for_wide_grids(self):
        """A grid spanning >= 2^31 ms must keep int64 timestamps and still
        answer correctly (the compaction guard, not the compaction)."""
        from opentsdb_tpu.ops.downsample import _compact_ts
        import jax.numpy as jnp
        rng = np.random.default_rng(12)
        ts, val, mask = self._big_batch(rng, spread_ms=200_000_000)
        # 1-day windows over ~7 years: span 2555 days > 2^31 ms (~24.8 days)
        windows = FixedWindows.for_range(
            START, START + 2555 * 86_400_000, 86_400_000)
        spec, wargs = windows.split()
        cts, _ = _compact_ts(jnp.asarray(ts), spec, wargs)
        assert cts.dtype == jnp.int64
        _, out, omask = downsample(ts, val, mask, "sum", spec, wargs,
                                   FILL_NONE)
        edges = np.arange(
            windows.first_window_ms,
            windows.first_window_ms + (windows.count + 1) * 86_400_000,
            86_400_000, dtype=np.int64)
        want, want_cnt = _numpy_reference(ts, val, mask, "sum", edges)
        got = np.asarray(out)[:, :windows.count]
        np.testing.assert_allclose(got[want_cnt > 0], want[want_cnt > 0],
                                   rtol=1e-11, atol=1e-9)

    def test_int32_compaction_active_for_narrow_grids(self):
        from opentsdb_tpu.ops.downsample import _compact_ts
        import jax.numpy as jnp
        rng = np.random.default_rng(13)
        ts, _, _ = self._big_batch(rng)
        windows = FixedWindows.for_range(START, START + 40_000_000, 3_600_000)
        spec, wargs = windows.split()
        cts, cedges = _compact_ts(jnp.asarray(ts), spec, wargs)
        assert cts.dtype == jnp.int32
        assert cedges.dtype == jnp.int32
        # pads (int64 max) stay at the sorted tail after clipping
        assert bool((np.diff(np.asarray(cts), axis=1) >= 0).all())


class TestExtremeScanPath:
    """r3: min/max downsample rides a segmented reset-scan, no scatter."""

    @pytest.mark.parametrize("agg", ["min", "max", "mimmin", "mimmax"])
    def test_matches_numpy_reference(self, agg):
        rng = np.random.default_rng(61)
        ts = np.full((4, 256), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((4, 256), np.float64)
        mask = np.zeros((4, 256), bool)
        for i in range(4):
            k = int(rng.integers(20, 250))
            ts[i, :k] = START + np.sort(
                rng.choice(9_000_000, size=k, replace=False))
            v = rng.normal(0, 50, k)
            v[rng.random(k) < 0.07] = np.nan
            val[i, :k] = v
            mask[i, :k] = True
            # also mask out some interior points
            mask[i, :k] &= rng.random(k) > 0.05
        windows = FixedWindows.for_range(START, START + 9_000_000,
                                         600_000)
        spec, wargs = windows.split()
        _, out, omask = downsample(ts, val, mask, agg, spec, wargs,
                                   FILL_NONE)
        out, omask = np.asarray(out), np.asarray(omask)
        fn = np.min if agg in ("min", "mimmin") else np.max
        edges = np.arange(windows.first_window_ms,
                          windows.first_window_ms
                          + (windows.count + 1) * 600_000, 600_000)
        for i in range(4):
            for w in range(windows.count):
                sel = (mask[i] & (ts[i] >= edges[w]) & (ts[i] < edges[w + 1])
                       & ~np.isnan(val[i]))
                if sel.sum():
                    assert omask[i, w]
                    assert out[i, w] == fn(val[i][sel]), (agg, i, w)
                else:
                    assert not omask[i, w]

    def test_materialized_and_streamed_minmax_have_no_scatter(
            self, kernel_forms):
        """The scan-form extreme kernel is scatter-free (TPU scatters
        serialize).  The form is pinned: the chooser correctly picks the
        segment scatter on CPU — where this suite runs and scatters are
        cheap — so the property being pinned is the scan KERNEL's, not
        the chooser's."""
        import jax
        import jax.numpy as jnp
        from opentsdb_tpu.ops import streaming
        windows = FixedWindows.for_range(0, 3_000_000, 60_000)
        spec, wargs = windows.split()
        ts = jnp.zeros((4, 128), jnp.int64)
        val = jnp.zeros((4, 128))
        mask = jnp.ones((4, 128), bool)
        kernel_forms(extreme="scan")
        hlo = jax.jit(downsample, static_argnums=(3, 4, 6)).lower(
            ts, val, mask, "min", spec, wargs, FILL_NONE).as_text()
        assert "scatter" not in hlo
        state = streaming._zero_state(
            4, spec.count, lanes=streaming.lanes_for(["min", "max"]))
        hlo = jax.jit(streaming._update, static_argnums=0).lower(
            spec, state, ts, val, mask, wargs).as_text()
        assert "scatter" not in hlo

    @pytest.mark.parametrize("agg", ["min", "max"])
    @pytest.mark.parametrize("seed,interval", [(62, 600_000), (63, 60_000),
                                               (64, 2_500_000)])
    def test_extreme_modes_agree(self, agg, seed, interval, kernel_forms):
        """scan / segment / subblock extreme forms answer identically —
        interval sweep covers windows smaller than, comparable to, and
        much wider than the 32-point sub-block granule."""
        rng = np.random.default_rng(seed)
        ts = np.full((3, 128), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((3, 128), np.float64)
        mask = np.zeros((3, 128), bool)
        for i in range(3):
            k = int(rng.integers(30, 120))
            ts[i, :k] = START + np.sort(
                rng.choice(5_000_000, size=k, replace=False))
            val[i, :k] = rng.normal(0, 9, k)
            mask[i, :k] = True
        windows = FixedWindows.for_range(START, START + 5_000_000, interval)
        spec, wargs = windows.split()
        kernel_forms(extreme="scan")
        _, want, wmask = downsample(ts, val, mask, agg, spec, wargs,
                                    FILL_NONE)
        for mode in ("segment", "subblock"):
            kernel_forms(extreme=mode)
            _, got, gmask = downsample(ts, val, mask, agg, spec, wargs,
                                       FILL_NONE)
            np.testing.assert_array_equal(np.asarray(gmask),
                                          np.asarray(wmask))
            m = np.asarray(wmask)
            np.testing.assert_array_equal(np.asarray(got)[m],
                                          np.asarray(want)[m])

    @pytest.mark.parametrize("agg", ["min", "max"])
    def test_subblock_extreme_dense_ties(self, agg, kernel_forms):
        """Dense rows where window edges land exactly on sub-block
        boundaries and all values equal in a window — boundary masks and
        the interior reset-scan must not double-count or miss lanes."""
        s, n = 2, 128
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        # row 0: 96 points, one per ms — windows of 32 points align with
        # sub-blocks exactly
        ts[0, :96] = START + np.arange(96)
        val[0, :96] = np.tile([5.0, -3.0, 7.0, 1.0], 24)
        mask[0, :96] = True
        # row 1: 100 points spanning sub-block boundaries unevenly
        ts[1, :100] = START + np.arange(100) * 7
        val[1, :100] = -np.arange(100, dtype=float)
        mask[1, :100] = True
        windows = FixedWindows.for_range(START, START + 700, 32)
        spec, wargs = windows.split()
        kernel_forms(extreme="scan")
        _, want, wmask = downsample(ts, val, mask, agg, spec, wargs,
                                    FILL_NONE)
        kernel_forms(extreme="subblock")
        _, got, gmask = downsample(ts, val, mask, agg, spec, wargs,
                                   FILL_NONE)
        np.testing.assert_array_equal(np.asarray(gmask), np.asarray(wmask))
        m = np.asarray(wmask)
        np.testing.assert_array_equal(np.asarray(got)[m],
                                      np.asarray(want)[m])


class TestPrecompactedBatches:
    """int32 pre-compacted batches (device-cache gather layout, r4): the
    query dispatch receives ts as int32 offsets from wargs["ts_base"] and
    must answer identically to the absolute-int64 batch on every path —
    prefix family, extremes, and the segment fallback (percentiles) that
    reconstructs absolute time."""

    I32_PAD = np.int32(2**31 - 2)

    def _pair(self, rng, s=4, n=512, spread_ms=40_000_000):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows, precompact_base
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        for i in range(s):
            k = int(rng.integers(n // 2, n - 7))
            t = START + np.sort(rng.choice(spread_ms, size=k, replace=False))
            ts[i, :k] = t
            val[i, :k] = rng.normal(100.0, 30.0, k)
            mask[i, :k] = True
        windows = FixedWindows.for_range(START, START + spread_ms, 3_600_000)
        spec, wargs = windows.split()
        base = precompact_base(spec, windows.first_window_ms)
        assert base is not None, "grid must be compaction-eligible"
        ts32 = np.where(mask, ts - base, self.I32_PAD).astype(np.int32)
        wargs32 = dict(wargs)
        wargs32["ts_base"] = jnp.asarray(base, jnp.int64)
        return ts, ts32, val, mask, spec, wargs, wargs32, windows

    @pytest.mark.parametrize("agg", ["avg", "sum", "count", "dev", "min",
                                     "max", "p90", "median", "first"])
    def test_int32_batch_equals_int64(self, agg):
        rng = np.random.default_rng(31)
        ts, ts32, val, mask, spec, wargs, wargs32, _ = self._pair(rng)
        _, want, want_m = downsample(ts, val, mask, agg, spec, wargs,
                                     FILL_NONE)
        _, got, got_m = downsample(ts32, val, mask, agg, spec, wargs32,
                                   FILL_NONE)
        np.testing.assert_array_equal(np.asarray(want_m), np.asarray(got_m))
        m = np.asarray(want_m)
        np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m],
                                   rtol=1e-12, atol=1e-12)

    def test_int32_batch_with_shifted_origin(self):
        """bench.py traces a shifted window origin (first' < ts_base):
        the window-id re-base and edge re-base must stay consistent."""
        import jax.numpy as jnp
        rng = np.random.default_rng(37)
        ts, ts32, val, mask, spec, wargs, wargs32, _ = self._pair(rng)
        for shift in (7_919, 1_800_000):
            w64 = dict(wargs)
            w64["first"] = wargs["first"] - jnp.asarray(shift, jnp.int64)
            w32 = dict(wargs32)
            w32["first"] = wargs32["first"] - jnp.asarray(shift, jnp.int64)
            for agg in ("avg", "dev", "min"):
                _, want, want_m = downsample(ts, val, mask, agg, spec, w64,
                                             FILL_NONE)
                _, got, got_m = downsample(ts32, val, mask, agg, spec, w32,
                                           FILL_NONE)
                np.testing.assert_array_equal(np.asarray(want_m),
                                              np.asarray(got_m))
                m = np.asarray(want_m)
                np.testing.assert_allclose(np.asarray(got)[m],
                                           np.asarray(want)[m],
                                           rtol=1e-12, atol=1e-12)

    def test_stale_base_saturates_instead_of_wrapping(self):
        """Regression (shape-dtype-narrowing fix): a window origin
        farther than int32 from ts_base must NOT wrap in the int32
        re-base of `_window_ids_fast` (used by the dev mean-per-point
        gather, the extreme scans, and streaming's window keys).
        Pre-fix, `(first - ts_base).astype(int32)` wrapped a
        2^32 + one-interval delta to exactly one interval — every point
        landed one window off IN RANGE, silently wrong; with the
        saturating clip the ids go far out of range and the validity
        masks drop them."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import (WindowSpec,
                                                 _window_ids_fast)
        interval = 3_600_000
        spec = WindowSpec("fixed", 8, interval)
        cts = jnp.asarray([[0, interval, 2 * interval]], jnp.int32)
        base = jnp.asarray(START, jnp.int64)
        # honest base: ids are the plain division
        ids = _window_ids_fast(cts, cts, spec,
                               {"first": base, "ts_base": base})
        np.testing.assert_array_equal(np.asarray(ids), [[0, 1, 2]])
        # stale base, 2^32 + interval away: int32 wrap would yield
        # shift == interval and ids [[-1, 0, 1]] — plausible, wrong.
        # The clip saturates the shift, pushing every id out of range.
        stale = {"first": base + 2**32 + interval, "ts_base": base}
        ids = np.asarray(_window_ids_fast(cts, cts, spec, stale))
        assert ((ids < 0) | (ids >= spec.count)).all(), (
            "stale re-base wrapped into plausible window ids: %r" % ids)

    def test_cache_gather_emits_int32_layout(self):
        """The device cache's ts_base gather must emit exactly this
        contract: int32 dtype, offsets from base, pads at the clip
        ceiling."""
        from opentsdb_tpu.storage.device_cache import (_gather_windows,
                                                       _pin_columns)
        buf_ts = np.array([START + 10, START + 20, START + 30, START + 40],
                          np.int64)
        buf_val = np.array([1.0, 2.0, 3.0, 4.0])
        ts, val, m = _gather_windows(_pin_columns(buf_ts, buf_val),
                                     np.array([0, 2]), np.array([2, 1]),
                                     4, ts_base=START)
        ts = np.asarray(ts)
        assert ts.dtype == np.int32
        np.testing.assert_array_equal(ts[0], [10, 20, self.I32_PAD,
                                              self.I32_PAD])
        np.testing.assert_array_equal(ts[1], [30, self.I32_PAD,
                                              self.I32_PAD, self.I32_PAD])
        np.testing.assert_array_equal(np.asarray(m),
                                      [[True, True, False, False],
                                       [True, False, False, False]])


class TestSearchModeShapeGuard:
    """Dense search forms must demote to the binary search on wide grids
    (streaming config 2's W ~ 10M edges would turn compare_all's O(N*W)
    into tens of seconds per chunk — the r4 chip session's config-2
    timeout)."""

    def test_long_rows_demote_dense_modes(self):
        from opentsdb_tpu.ops import downsample as ds_mod
        cases = {
            # (form, n) -> is it a candidate at 514 edges
            ("compare_all", 65536): True,      # headline: stays
            ("compare_all", 1 << 20): False,   # 1M-pt chunk: demote
            ("hier", 65536): True,
            # 1M-pt rows x 514 edges: 16.8M compare cells/row exceeds
            # _HIER_CELL_CAP — the config-1 shape (109M cells/row) ran
            # 18x slower on the host lane and failed scoped-vmem compile
            # on the chip (r04b), so wide hier matrices demote
            ("hier", 1 << 20): False,
            ("hier", 1 << 24): False,     # 16M-pt rows: demote
        }
        for (mode, n), want in cases.items():
            got = mode in ds_mod._search_candidates(n, 514)
            assert got == want, (mode, n, got, want)
        # and the chip's pick never leaves the candidates
        for n in (65536, 1 << 20, 1 << 24):
            assert ds_mod._effective_search_mode(1024, n, 514, "tpu") \
                in ds_mod._search_candidates(n, 514)
        assert ds_mod._effective_search_mode(1024, 1 << 20, 514,
                                             "tpu") == "scan"

    def test_demoted_search_still_correct(self, kernel_forms, monkeypatch):
        """A (tiny-N, huge-W) shape under compare_all answers identically
        to scan — through the demotion path."""
        from opentsdb_tpu.ops import downsample as ds_mod
        rng = np.random.default_rng(41)
        s, n = 2, 256
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        for i in range(s):
            k = 200
            t = START + np.sort(rng.choice(5_000_000, size=k, replace=False))
            ts[i, :k] = t
            val[i, :k] = rng.normal(10, 3, k)
            mask[i, :k] = True
        windows = FixedWindows.for_range(START, START + 5_000_000, 1_000)
        spec, wargs = windows.split()      # ~5000 windows, N=256
        # force demotion at this shape
        monkeypatch.setattr(ds_mod, "_SEARCH_DEMOTE_RATIO", 1)
        assert "compare_all" not in ds_mod._search_candidates(
            n, spec.count + 1)
        kernel_forms(search="compare_all")
        _, got, gm = downsample(ts, val, mask, "sum", spec, wargs,
                                FILL_NONE)
        kernel_forms(search="scan")
        _, want, wm = downsample(ts, val, mask, "sum", spec, wargs,
                                 FILL_NONE)
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
        m = np.asarray(wm)
        np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m])


class TestSearchPlatformRule:
    """Dense search forms are accelerator winners only: any CPU execution
    — the host lane or a CPU-only process — takes the binary search (r04b
    chip session: hier 18x slower than scan end-to-end on the config-1
    host lane).  The rule is part of the chooser, not a switch."""

    def test_cpu_backend_takes_the_binary_search(self):
        from opentsdb_tpu.ops import downsample as ds_mod
        # this suite runs on the CPU platform, so the ambient platform
        # is cpu even outside a host_lane context
        assert ds_mod._effective_search_mode(8, 65536, 514) == "scan"
        assert ds_mod._effective_search_mode(8, 65536, 514, "cpu") == "scan"

    def test_host_lane_context_reports_cpu(self):
        from opentsdb_tpu.ops import hostlane
        assert hostlane.execution_platform() == "cpu"  # cpu default backend
        with hostlane.host_lane(True):
            assert hostlane.execution_platform() == "cpu"

    def test_tpu_platform_keeps_dense_forms(self):
        from opentsdb_tpu.ops import downsample as ds_mod
        assert ds_mod._effective_search_mode(8, 65536, 514, "tpu") == "hier"

    def test_dense_form_answers_identically(self, kernel_forms):
        """End-to-end: the same downsample under the CPU backend's own
        pick (binary search) equals the hier answer (the platform rule
        changes strategy, never values)."""
        rng = np.random.default_rng(7)
        s, n = 2, 512
        ts = np.sort(rng.choice(10_000_000, size=(s, n), replace=False),
                     axis=1) + START
        val = rng.normal(50, 10, (s, n))
        mask = np.ones((s, n), bool)
        windows = FixedWindows.for_range(START, START + 10_000_001, 60_000)
        spec, wargs = windows.split()
        _, want, wm = downsample(ts, val, mask, "sum", spec, wargs,
                                 FILL_NONE)
        kernel_forms(search="hier")
        _, got, gm = downsample(ts, val, mask, "sum", spec, wargs,
                                FILL_NONE)
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
        m = np.asarray(wm)
        np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m],
                                   rtol=1e-12)


class TestWideGridGuards:
    """Wider-than-data grids (streaming config 2: W ~ 10x N) must not
    materialize [S, W, K] sub-block intermediates — the 0.01-scale CPU
    smoke hit a 283GB allocation before these guards existed."""

    def test_eligibility_predicates(self):
        from opentsdb_tpu.ops import downsample as ds_mod
        # headline shape: everything eligible
        assert ds_mod._subblock_edges_fit(65536, 514)
        assert "subblock" in ds_mod._extreme_candidates(65536, 513)
        # config-2 chunk: 64k-pt chunk against a 1M-window grid
        assert "subblock" not in ds_mod._extreme_candidates(65536, 1 << 20)
        assert ds_mod._effective_search_mode(1, 65536, 1 << 20,
                                             "tpu") == "scan"
        assert ds_mod._effective_search_mode(1, 65536, 514, "tpu") == "hier"

    def test_wide_grid_all_modes_answer(self, kernel_forms):
        """A wide sparse grid (W >> N) under every new mode at once must
        answer identically to the defaults — through the demotion/
        fallback paths, without blowing memory."""
        rng = np.random.default_rng(51)
        s, n = 2, 64
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        for i in range(s):
            k = 50
            ts[i, :k] = START + np.sort(
                rng.choice(40_000_000, size=k, replace=False))
            val[i, :k] = rng.normal(0, 5, k)
            mask[i, :k] = True
        # 10s windows over ~11 hours: 4000+ windows vs 64 points
        windows = FixedWindows.for_range(START, START + 40_000_000, 10_000)
        spec, wargs = windows.split()
        assert spec.count > 16 * n
        want = {}
        for agg in ("sum", "min", "max", "avg"):
            _, out, om = downsample(ts, val, mask, agg, spec, wargs,
                                    FILL_NONE)
            want[agg] = (np.asarray(out), np.asarray(om))
        kernel_forms(scan="subblock", search="hier", extreme="subblock",
                     group="sorted")
        for agg in ("sum", "min", "max", "avg"):
            _, out, om = downsample(ts, val, mask, agg, spec, wargs,
                                    FILL_NONE)
            np.testing.assert_array_equal(np.asarray(om), want[agg][1])
            m = want[agg][1]
            np.testing.assert_allclose(np.asarray(out)[m],
                                       want[agg][0][m],
                                       rtol=1e-12, atol=1e-12)
        # subblock2 has NO edges-fit constraint (its remainder reads a
        # same-size prefix, not an [S, W, K] lane) — it must answer the
        # wide grid identically with the sub-block path ACTIVE
        kernel_forms(scan="subblock2")
        for agg in ("sum", "avg"):
            _, out, om = downsample(ts, val, mask, agg, spec, wargs,
                                    FILL_NONE)
            np.testing.assert_array_equal(np.asarray(om), want[agg][1])
            m = want[agg][1]
            np.testing.assert_allclose(np.asarray(out)[m],
                                       want[agg][0][m],
                                       rtol=1e-12, atol=1e-12)


class TestNewModesAcrossWindowKinds:
    """subblock / hier / sorted-extreme against calendar-edge and 0all
    grids (the mode-equivalence sweeps above are fixed-grid only; the
    int32 compaction does NOT apply to these kinds, so the modes must
    work on raw int64 timestamps too)."""

    def _batch(self, rng, s=3, n=128):
        ts = np.full((s, n), np.iinfo(np.int64).max, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        for i in range(s):
            k = int(rng.integers(60, n - 5))
            ts[i, :k] = START + np.sort(
                rng.choice(5_000_000, size=k, replace=False))
            v = rng.normal(20, 8, k)
            v[rng.random(k) < 0.04] = np.nan
            val[i, :k] = v
            mask[i, :k] = True
        return ts, val, mask

    @pytest.mark.parametrize("agg", ["sum", "avg", "min", "max", "dev"])
    @pytest.mark.parametrize("kind", ["edges", "all"])
    @pytest.mark.parametrize("scan_mode", ["subblock", "subblock2"])
    def test_modes_agree_on_irregular_grids(self, agg, kind, scan_mode,
                                            kernel_forms):
        rng = np.random.default_rng(83)
        ts, val, mask = self._batch(rng)
        if kind == "edges":
            # deliberately irregular calendar-style edges
            windows = EdgeWindows((START, START + 700_000, START + 800_000,
                                   START + 2_000_000, START + 4_999_999))
        else:
            windows = AllWindow(START + 5_000, START + 4_500_000)
        spec, wargs = windows.split()
        _, want, wm = downsample(ts, val, mask, agg, spec, wargs, FILL_NONE)
        kernel_forms(scan=scan_mode, search="hier", extreme="subblock")
        _, got, gm = downsample(ts, val, mask, agg, spec, wargs,
                                FILL_NONE)
        np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm))
        m = np.asarray(wm)
        np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m],
                                   rtol=1e-12, atol=1e-12)


def test_compare_all_memory_cap_demotes():
    """compare_all must demote on shapes whose per-row [N, W+1] compare
    matrix would materialize huge (config 4's 64k-pt chunk against a
    16k-window grid attempted a multi-TB buffer on CPU)."""
    from opentsdb_tpu.ops import downsample as ds_mod
    # headline: 65536 x 514 cells — stays
    assert "compare_all" in ds_mod._search_candidates(65536, 514)
    # config-4 chunk grid: 65536 x 16385 cells — demote
    assert "compare_all" not in ds_mod._search_candidates(65536, 16385)
    assert ds_mod._effective_search_mode(512, 65536, 16385,
                                         "tpu") != "compare_all"

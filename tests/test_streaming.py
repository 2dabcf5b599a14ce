"""Streaming (chunked) execution vs the materialized path.

VERDICT round-1 missing #4: beyond-memory queries must stream through the
device in bounded chunks.  Kernel level: the chunked moment accumulator
must reproduce the one-shot downsample for every streamable function.
Planner level: a query over the streaming threshold must produce the same
JSON as the materialized path.
"""

import numpy as np
import pytest

from opentsdb_tpu.ops.downsample import (
    downsample, FixedWindows, FILL_NONE, FILL_ZERO)
from opentsdb_tpu.ops.streaming import StreamAccumulator, STREAMABLE_DS

START = 1_356_998_400_000
PAD = np.iinfo(np.int64).max


def _sorted_batch(rng, s=4, n=96):
    ts = np.full((s, 128), PAD, np.int64)
    val = np.zeros((s, 128), np.float64)
    mask = np.zeros((s, 128), bool)
    for i in range(s):
        k = int(rng.integers(n // 2, n))
        ts[i, :k] = START + np.sort(
            rng.choice(900_000, size=k, replace=False))
        v = rng.normal(50.0, 20.0, k)
        v[rng.random(k) < 0.04] = np.nan
        val[i, :k] = v
        mask[i, :k] = True
    return ts, val, mask


def _stream_in_chunks(ts, val, mask, windows, ds_fn, chunk=17,
                      fill=FILL_NONE):
    spec, wargs = windows.split()
    s, n = ts.shape
    acc = StreamAccumulator.create(s, spec, wargs)
    for k in range(0, n, chunk):
        w = min(chunk, n - k)
        cts = np.full((s, chunk), PAD, np.int64)
        cval = np.zeros((s, chunk), np.float64)
        cmask = np.zeros((s, chunk), bool)
        cts[:, :w] = ts[:, k:k + chunk]
        cval[:, :w] = val[:, k:k + chunk]
        cmask[:, :w] = mask[:, k:k + chunk]
        acc.update(cts, cval, cmask)
    return acc.finish(ds_fn, fill)


@pytest.mark.parametrize("ds_fn", sorted(STREAMABLE_DS))
def test_chunked_equals_one_shot(ds_fn):
    rng = np.random.default_rng(11)
    ts, val, mask = _sorted_batch(rng)
    windows = FixedWindows.for_range(START, START + 900_000, 60_000)
    spec, wargs = windows.split()

    wts_d, out_d, mask_d = downsample(ts, val, mask, ds_fn, spec, wargs,
                                      FILL_NONE)
    wts_s, out_s, mask_s = _stream_in_chunks(ts, val, mask, windows, ds_fn)

    np.testing.assert_array_equal(np.asarray(wts_d), np.asarray(wts_s))
    np.testing.assert_array_equal(np.asarray(mask_d), np.asarray(mask_s))
    got = np.asarray(out_s)[np.asarray(mask_s)]
    want = np.asarray(out_d)[np.asarray(mask_d)]
    np.testing.assert_allclose(got, want, rtol=1e-9, atol=1e-9)


def test_fill_policy_applies_at_finish():
    rng = np.random.default_rng(12)
    ts, val, mask = _sorted_batch(rng, s=2)
    windows = FixedWindows.for_range(START, START + 1_800_000, 60_000)
    spec, wargs = windows.split()
    wts_d, out_d, mask_d = downsample(ts, val, mask, "avg", spec, wargs,
                                      FILL_ZERO)
    wts_s, out_s, mask_s = _stream_in_chunks(ts, val, mask, windows, "avg",
                                             fill=FILL_ZERO)
    np.testing.assert_array_equal(np.asarray(mask_d), np.asarray(mask_s))
    np.testing.assert_allclose(np.asarray(out_s)[np.asarray(mask_s)],
                               np.asarray(out_d)[np.asarray(mask_d)],
                               rtol=1e-9, atol=1e-9)


def test_single_chunk_equals_full():
    rng = np.random.default_rng(13)
    ts, val, mask = _sorted_batch(rng)
    windows = FixedWindows.for_range(START, START + 900_000, 120_000)
    wts, out, omask = _stream_in_chunks(ts, val, mask, windows, "dev",
                                        chunk=ts.shape[1])
    spec, wargs = windows.split()
    _, out_d, mask_d = downsample(ts, val, mask, "dev", spec, wargs,
                                  FILL_NONE)
    np.testing.assert_allclose(np.asarray(out)[np.asarray(omask)],
                               np.asarray(out_d)[np.asarray(mask_d)],
                               rtol=1e-12, atol=1e-12)


class TestSlicedUpdates:
    """Window-sliced streaming updates (W-independent per-chunk cost)
    must match the full-grid fold bit-for-bit: merging a chunk into the
    [w0, w0+wc) state slice equals merging it into the whole grid when
    the chunk's windows all land in the slice — and points outside the
    declared slice are audited, never silently dropped."""

    @staticmethod
    def _stream_sliced(ts, val, mask, windows, ds_fn, chunk=17,
                       window_slice=None, w0_offset=0, sketch=False):
        spec, wargs = windows.split()
        s, n = ts.shape
        if window_slice is None:
            # widest chunk's window span (host-known, like the planner)
            window_slice = 1
            for k in range(0, n, chunk):
                cts = ts[:, k:k + chunk]
                real = cts[cts != PAD]
                if real.size:
                    span = int((real.max() - real.min())
                               // windows.interval_ms) + 2
                    window_slice = max(window_slice, span)
        acc = StreamAccumulator.create(s, spec, wargs, sketch=sketch,
                                       window_slice=window_slice)
        for k in range(0, n, chunk):
            w = min(chunk, n - k)
            cts = np.full((s, chunk), PAD, np.int64)
            cval = np.zeros((s, chunk), np.float64)
            cmask = np.zeros((s, chunk), bool)
            cts[:, :w] = ts[:, k:k + chunk]
            cval[:, :w] = val[:, k:k + chunk]
            cmask[:, :w] = mask[:, k:k + chunk]
            real = cts[cts != PAD]
            w0 = 0 if not real.size else int(
                (real.min() - windows.first_window_ms)
                // windows.interval_ms)
            acc.update(cts, cval, cmask, w0=w0 + w0_offset)
        return acc

    @pytest.mark.parametrize("ds_fn", sorted(STREAMABLE_DS))
    def test_sliced_equals_full_stream(self, ds_fn):
        rng = np.random.default_rng(29)
        ts, val, mask = _sorted_batch(rng)
        # wide grid relative to the data: 900s of data on 10s windows
        windows = FixedWindows.for_range(START, START + 900_000, 10_000)
        want = _stream_in_chunks(ts, val, mask, windows, ds_fn)
        acc = self._stream_sliced(ts, val, mask, windows, ds_fn)
        assert acc.window_slice is not None, "slice must be engaged"
        assert acc.oob_count() == 0
        gts, gout, gmask = (np.asarray(x) for x in acc.finish(ds_fn,
                                                              FILL_NONE))
        wts, wout, wmask = (np.asarray(x) for x in want)
        np.testing.assert_array_equal(gts, wts)
        np.testing.assert_array_equal(gmask, wmask)
        np.testing.assert_allclose(gout[gmask], wout[wmask],
                                   rtol=1e-12, atol=1e-12)

    def test_sliced_sketch_matches_full(self):
        rng = np.random.default_rng(31)
        ts, val, mask = _sorted_batch(rng, s=3)
        windows = FixedWindows.for_range(START, START + 900_000, 10_000)
        spec, wargs = windows.split()
        s, n = ts.shape
        acc_full = StreamAccumulator.create(s, spec, wargs, sketch=True)
        for k in range(0, n, 17):
            w = min(17, n - k)
            cts = np.full((s, 17), PAD, np.int64)
            cval = np.zeros((s, 17), np.float64)
            cmask = np.zeros((s, 17), bool)
            cts[:, :w] = ts[:, k:k + 17]
            cval[:, :w] = val[:, k:k + 17]
            cmask[:, :w] = mask[:, k:k + 17]
            acc_full.update(cts, cval, cmask)
        acc = self._stream_sliced(ts, val, mask, windows, "p90",
                                  sketch=True)
        assert acc.oob_count() == 0
        _, want, wmask = acc_full.finish("p90", FILL_NONE)
        _, got, gmask = acc.finish("p90", FILL_NONE)
        np.testing.assert_array_equal(np.asarray(gmask), np.asarray(wmask))
        m = np.asarray(wmask)
        np.testing.assert_allclose(np.asarray(got)[m], np.asarray(want)[m],
                                   rtol=1e-6, atol=1e-6)

    def test_wrong_w0_is_audited_not_silent(self):
        rng = np.random.default_rng(37)
        ts, val, mask = _sorted_batch(rng, s=2)
        windows = FixedWindows.for_range(START, START + 900_000, 10_000)
        acc = self._stream_sliced(ts, val, mask, windows, "sum",
                                  w0_offset=40)   # shift slices off target
        assert acc.oob_count() > 0

    def test_sharded_sliced_matches_full(self):
        """Mesh accumulator: sliced folds (per-chip state-slice merges,
        replicated oob psum) must reproduce the full-grid mesh fold and
        the slice must actually engage."""
        from opentsdb_tpu.parallel.mesh import make_mesh
        from opentsdb_tpu.parallel import ShardedStreamAccumulator
        from opentsdb_tpu.ops.pipeline import PipelineSpec, DownsampleStep
        from opentsdb_tpu.ops.streaming import lanes_for

        mesh = make_mesh()
        rng = np.random.default_rng(43)
        s = 11                               # pads to 16 sharded rows
        ts, val, mask = _sorted_batch(rng, s=s)
        windows = FixedWindows.for_range(START, START + 900_000, 10_000)
        spec, wargs = windows.split()
        gid = np.arange(s, dtype=np.int64) % 3
        pipe = PipelineSpec("sum",
                            DownsampleStep("avg", spec, "none", 0.0))

        def run(window_slice):
            acc = ShardedStreamAccumulator(
                mesh, s, spec, wargs, lanes=lanes_for(["avg"]),
                window_slice=window_slice)
            n = ts.shape[1]
            for k in range(0, n, 17):
                w = min(17, n - k)
                cts = np.full((s, 17), PAD, np.int64)
                cval = np.zeros((s, 17), np.float64)
                cmask = np.zeros((s, 17), bool)
                cts[:, :w] = ts[:, k:k + 17]
                cval[:, :w] = val[:, k:k + 17]
                cmask[:, :w] = mask[:, k:k + 17]
                real = cts[cts != PAD]
                w0 = None
                if acc.window_slice is not None and real.size:
                    span = int((real.max() - real.min())
                               // windows.interval_ms) + 2
                    if span <= acc.window_slice:
                        w0 = int((real.min() - windows.first_window_ms)
                                 // windows.interval_ms)
                acc.update(cts, cval, cmask, w0=w0)
            return acc, acc.finish_tail(pipe, gid, 4)

        acc_s, got = run(window_slice=64)
        assert acc_s.window_slice is not None
        assert acc_s.oob_count() == 0
        acc_f, want = run(window_slice=None)
        assert acc_f.window_slice is None
        for g, w in zip(got, want):
            g, w = np.asarray(g), np.asarray(w)
            if g.dtype == bool:
                np.testing.assert_array_equal(g, w)
            else:
                np.testing.assert_allclose(
                    np.where(np.isnan(g), 0.0, g),
                    np.where(np.isnan(w), 0.0, w), rtol=1e-12, atol=1e-12)
                np.testing.assert_array_equal(np.isnan(g), np.isnan(w))

    def test_slice_as_wide_as_grid_falls_back(self):
        rng = np.random.default_rng(41)
        ts, val, mask = _sorted_batch(rng, s=2)
        windows = FixedWindows.for_range(START, START + 900_000, 300_000)
        spec, wargs = windows.split()
        acc = StreamAccumulator.create(2, spec, wargs,
                                       window_slice=10_000)
        assert acc.window_slice is None     # wider than the grid: full path
        acc.update(ts, val, mask, w0=0)     # w0 accepted, full-grid fold
        assert acc.oob_count() == 0
        _, out, omask = acc.finish("sum", FILL_NONE)
        _, want, wm = downsample(ts, val, mask, "sum", spec, wargs,
                                 FILL_NONE)
        np.testing.assert_allclose(np.asarray(out)[np.asarray(omask)],
                                   np.asarray(want)[np.asarray(wm)],
                                   rtol=1e-12)


class TestPlannerStreaming:
    """E2e: a sub-threshold and an over-threshold run answer identically."""

    def _tsdb(self, threshold):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.utils.config import Config
        return TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.query.streaming.point_threshold": str(threshold),
            "tsd.query.streaming.chunk_points": "64",
            "tsd.query.mesh.enable": False,
        }))

    def _run(self, tsdb, m):
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        q = TSQuery(start=str(1_356_998_400), end=str(1_356_998_400 + 3600),
                    queries=[parse_m_subquery(m)])
        q.validate()
        return [r.to_json() for r in tsdb.new_query_runner().run(q)]

    @pytest.mark.parametrize("m", [
        "sum:2m-avg:sys.s{host=*}",
        "avg:5m-sum:sys.s",
        "max:2m-dev:sys.s{host=*}",
        "sum:rate:2m-avg:sys.s",
    ])
    def test_streamed_equals_materialized(self, m):
        import json
        streamed = self._tsdb(threshold=10)     # force streaming
        plain = self._tsdb(threshold=10**9)     # force materialized
        rng = np.random.default_rng(5)
        for tsdb in (streamed, plain):
            rng2 = np.random.default_rng(5)
            for h in range(3):
                base = 1_356_998_400
                for k in range(300):
                    tsdb.add_point("sys.s", base + k * 11 + h,
                                   float(rng2.normal(10, 3)),
                                   {"host": "h%d" % h})
        got = self._run(streamed, m)
        want = self._run(plain, m)
        assert json.dumps(got, sort_keys=True) == \
            json.dumps(want, sort_keys=True)


    def test_rows_are_counted_by_the_lane_that_filled_them(self):
        """tsd.query.stream.rows: on a store nobody writes to, every
        series row of every folded chunk is the bulk lane's."""
        from opentsdb_tpu.obs.registry import REGISTRY

        def rows(lane):
            return REGISTRY.counter("tsd.query.stream.rows", "").labels(
                lane=lane).get()

        def chunks():
            return REGISTRY.counter("tsd.query.stream.chunks",
                                    "").labels().get()
        tsdb = self._tsdb(threshold=10)
        for h in range(3):
            for k in range(2100 - 600 * h):     # ragged: 2100, 1500, 900
                tsdb.add_point("sys.s", 1_356_998_400 + k, float(k),
                               {"host": "h%d" % h})
        before = rows("bulk"), rows("cursor"), chunks()
        self._run(tsdb, "sum:2m-avg:sys.s{host=*}")
        folded = chunks() - before[2]
        assert folded == 3          # 2100 points in chunks of 1024
        assert rows("bulk") - before[0] == 3 * folded
        assert rows("cursor") - before[1] == 0


class TestMeshStreaming:
    """Streaming composes with the mesh (VERDICT r2 missing #3): a beyond-
    threshold query on the virtual 8-device mesh shards the accumulator
    rows over every chip and must answer exactly like the materialized
    single-device run."""

    def _tsdb(self, threshold, mesh):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.utils.config import Config
        return TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.query.streaming.point_threshold": str(threshold),
            "tsd.query.streaming.chunk_points": "64",
            "tsd.query.mesh.enable": mesh,
            "tsd.query.mesh.min_series": "0",
        }))

    def _run(self, tsdb, m):
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        q = TSQuery(start=str(1_356_998_400), end=str(1_356_998_400 + 3600),
                    queries=[parse_m_subquery(m)])
        q.validate()
        return [r.to_json() for r in tsdb.new_query_runner().run(q)]

    def _ingest(self, tsdb, n_hosts=11):
        # 11 hosts -> S=11 pads to 16 sharded rows: phantom rows exercised.
        rng = np.random.default_rng(9)
        for h in range(n_hosts):
            base = 1_356_998_400
            for k in range(200):
                tsdb.add_point("sys.ms", base + k * 17 + h,
                               float(rng.normal(20, 5)),
                               {"host": "h%02d" % h, "dc": "d%d" % (h % 2)})

    @pytest.mark.parametrize("m", [
        "sum:2m-avg:sys.ms{dc=*}",
        "avg:5m-sum:sys.ms{host=*}",
        "dev:2m-avg:sys.ms",
        "count:2m-avg-zero:sys.ms{dc=*}",   # fill + phantom-row regression
        "sum:rate:2m-avg:sys.ms{dc=*}",
        "max:2m-max:sys.ms{dc=*}",
    ])
    def test_mesh_streamed_equals_materialized(self, m):
        import json
        import math
        meshed = self._tsdb(threshold=10, mesh=True)    # stream + mesh
        plain = self._tsdb(threshold=10**9, mesh=False)  # materialized
        self._ingest(meshed)
        self._ingest(plain)
        assert meshed.query_mesh() is not None
        got = self._run(meshed, m)
        want = self._run(plain, m)
        assert len(got) == len(want)
        for g, w in zip(got, want):
            for key in w:
                if key != "dps":
                    assert g[key] == w[key], key
            assert set(g["dps"]) == set(w["dps"])
            for ts_key, wv in w["dps"].items():
                gv = g["dps"][ts_key]
                if isinstance(wv, float) and math.isnan(wv):
                    assert isinstance(gv, float) and math.isnan(gv)
                elif wv is None:
                    assert gv is None
                else:
                    assert math.isclose(gv, wv, rel_tol=1e-9, abs_tol=1e-9), \
                        (ts_key, gv, wv)

    def test_sharded_accumulator_direct(self):
        """Unit level: ShardedStreamAccumulator == StreamAccumulator."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows
        from opentsdb_tpu.ops.pipeline import (
            PipelineSpec, DownsampleStep, run_grid_tail)
        from opentsdb_tpu.ops.streaming import StreamAccumulator
        from opentsdb_tpu.parallel import make_mesh, ShardedStreamAccumulator

        mesh = make_mesh()
        assert mesh is not None
        s, n = 13, 256          # 13 rows -> padded to 16 over 8 devices
        start = 1_356_998_400_000
        rng = np.random.default_rng(3)
        ts = start + np.sort(rng.integers(0, 3_000_000, (s, n)), axis=1)
        ts = ts.astype(np.int64)
        val = rng.normal(50, 10, (s, n))
        mask = rng.random((s, n)) > 0.1
        gid = (np.arange(s) % 3).astype(np.int64)
        fixed = FixedWindows.for_range(start, start + 3_000_000, 60_000)
        window_spec, wargs = fixed.split()
        spec = PipelineSpec(
            aggregator="avg",
            downsample=DownsampleStep("avg", window_spec, "none", 0.0))

        acc = StreamAccumulator.create(s, window_spec, wargs)
        sacc = ShardedStreamAccumulator(mesh, s, window_spec, wargs)
        for k in range(0, n, 64):
            sl = slice(k, k + 64)
            acc.update(jnp.asarray(ts[:, sl]), jnp.asarray(val[:, sl]),
                       jnp.asarray(mask[:, sl]))
            sacc.update(ts[:, sl], val[:, sl], mask[:, sl])
        wts, v, m = acc.finish("avg")
        want = run_grid_tail(spec, wts, v, m, jnp.asarray(gid), 3)
        got = sacc.finish_tail(spec, gid, 3)
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]))
        np.testing.assert_array_equal(np.asarray(got[2]),
                                      np.asarray(want[2]))
        gm = np.asarray(got[2])
        np.testing.assert_allclose(np.asarray(got[1])[gm],
                                   np.asarray(want[1])[gm],
                                   rtol=1e-9, atol=1e-9)


class TestSketchPercentiles:
    """r3: rank-based downsample fns stream via the mergeable equi-rank
    quantile summary (STREAMABLE_DS hole, VERDICT r2 missing #4/next #6).
    Error is in rank (~chunks/(2K) worst case); tolerances below assert the
    estimate lands between the exact quantiles at q +/- 3 rank-percent."""

    def _exact_window_percentile(self, vals, q):
        import numpy as np
        if not len(vals):
            return np.nan
        sv = np.sort(vals)
        fr = np.clip(q / 100.0 * len(sv) - 0.5, 0, len(sv) - 1)
        lo = int(np.floor(fr))
        hi = min(lo + 1, len(sv) - 1)
        return sv[lo] + (fr - lo) * (sv[hi] - sv[lo])

    def test_accumulated_sketch_close_to_exact(self):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows
        from opentsdb_tpu.ops.streaming import StreamAccumulator
        rng = np.random.default_rng(31)
        s, n = 3, 4096
        start = 1_356_998_400_000
        span = 4 * 3_600_000
        ts = np.sort(rng.integers(0, span, (s, n)), axis=1) + start
        ts = ts.astype(np.int64)
        val = rng.normal(100, 25, (s, n))
        mask = np.ones((s, n), bool)
        fixed = FixedWindows.for_range(start, start + span, 3_600_000)
        spec, wargs = fixed.split()
        acc = StreamAccumulator.create(s, spec, wargs, sketch=True)
        for k in range(0, n, 512):      # 8 chunk merges
            sl = slice(k, k + 512)
            acc.update(jnp.asarray(ts[:, sl]), jnp.asarray(val[:, sl]),
                       jnp.asarray(mask[:, sl]))
        for q_name, q in [("p90", 90.0), ("median", 50.0), ("p99", 99.0)]:
            wts, out, omask = acc.finish(q_name)
            out = np.asarray(out)
            wts = np.asarray(wts)
            for i in range(s):
                for w in range(fixed.count):
                    w_lo = wts[w]
                    sel = (ts[i] >= w_lo) & (ts[i] < w_lo + 3_600_000)
                    vals = val[i][sel]
                    if len(vals) < 50:
                        continue
                    lo_b = self._exact_window_percentile(vals, max(q - 3, 0))
                    hi_b = self._exact_window_percentile(vals, min(q + 3,
                                                                   100))
                    assert lo_b - 1e-9 <= out[i, w] <= hi_b + 1e-9, \
                        (q_name, i, w, out[i, w], lo_b, hi_b)

    def test_planner_streamed_percentile_close_to_materialized(self):
        import json
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.utils.config import Config

        def mk(threshold):
            return TSDB(Config({
                "tsd.core.auto_create_metrics": True,
                "tsd.query.streaming.point_threshold": str(threshold),
                "tsd.query.streaming.chunk_points": "256",
                "tsd.query.mesh.enable": False,
            }))
        streamed, plain = mk(10), mk(10**9)
        for t in (streamed, plain):
            rng = np.random.default_rng(33)
            for h in range(2):
                base = 1_356_998_400
                for k in range(600):
                    t.add_point("sys.px", base + k * 6 + h,
                                float(rng.normal(40, 12)),
                                {"host": "h%d" % h})

        def run(t, m):
            q = TSQuery(start=str(1_356_998_400),
                        end=str(1_356_998_400 + 3600),
                        queries=[parse_m_subquery(m)])
            q.validate()
            return [r.to_json() for r in t.new_query_runner().run(q)]

        got = run(streamed, "sum:10m-p90:sys.px{host=*}")
        want = run(plain, "sum:10m-p90:sys.px{host=*}")
        assert len(got) == len(want) == 2
        for g, w in zip(got, want):
            assert set(g["dps"]) == set(w["dps"])
            for ts_key, wv in w["dps"].items():
                gv = g["dps"][ts_key]
                # ~300 pts/window: sketch within 8% of the exact p90
                assert abs(gv - wv) <= 0.08 * max(abs(wv), 1.0), \
                    (ts_key, gv, wv)

    def test_hazard_shape_auto_routes_exact(self):
        """VERDICT r3 #7: window span >> chunk span (the '0all over a huge
        range' shape) must NOT silently drift — the planner detects that a
        cell would absorb more than sketch_max_merges chunk merges and
        serves the exact materialized answer instead."""
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.utils.config import Config

        base = 1_356_998_400
        n_pts = 6000
        data = np.random.default_rng(77).normal(40, 12, n_pts)

        def mk(**extra):
            cfg = {"tsd.core.auto_create_metrics": True,
                   "tsd.query.streaming.point_threshold": "10",
                   "tsd.query.streaming.chunk_points": "512",
                   "tsd.query.device_cache.enable": "false",
                   "tsd.query.mesh.enable": False}
            cfg.update(extra)
            t = TSDB(Config(cfg))
            for k in range(n_pts):
                t.add_point("hz.m", base + k, float(data[k]), {"h": "a"})
            return t

        def run(t):
            # one giant window over everything: every chunk merges into
            # the same cell (n_chunk=1024 -> ~6 merges > the default 4)
            q = TSQuery(start=str(base - 1), end=str(base + n_pts + 1),
                        queries=[parse_m_subquery("sum:0all-p50:hz.m")])
            q.validate()
            runner = t.new_query_runner()
            res = [r.to_json() for r in runner.run(q)]
            return res, runner.exec_stats

        exact_t = mk(**{"tsd.query.streaming.point_threshold": "1000000000",
                        "tsd.query.streaming.sketch_percentiles": "false"})
        protected, stats = run(mk())
        exact, _ = run(exact_t)
        assert stats.get("sketchHazardExact") == 1.0
        assert protected[0]["dps"] == exact[0]["dps"]  # bit-exact, no drift

        # opt-out (max_merges=0) keeps the old sketched behavior, whose
        # rank error on this worst-case shape stays within the documented
        # C/(2K) bound
        sketched, st2 = run(mk(**{
            "tsd.query.streaming.sketch_max_merges": "0"}))
        assert "sketchHazardExact" not in st2
        got = list(sketched[0]["dps"].values())[0]
        vals = np.sort(data)
        rank = np.searchsorted(vals, got) / n_pts
        c_merges = -(-n_pts // 1024)
        assert abs(rank - 0.5) <= c_merges / (2 * 64) + 1 / 64, \
            (got, rank, c_merges)

    def test_hazard_estimate_is_skew_exact(self):
        """Points concentrated in ONE window of a wide fine-grained range
        (review r4): a per-series AVERAGE estimate sees ~1 merge/cell and
        keeps the sketch; the boundary-multiplicity estimate sees the
        real ~12 merges and routes exact."""
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.utils.config import Config

        base = 1_356_998_400
        t = TSDB(Config({"tsd.core.auto_create_metrics": True,
                         "tsd.query.streaming.point_threshold": "10",
                         "tsd.query.streaming.chunk_points": "512",
                         "tsd.query.device_cache.enable": "false",
                         "tsd.query.mesh.enable": False}))
        rng = np.random.default_rng(13)
        # 12k points inside one minute...
        for k in range(12_000):
            t.add_point("sk2.m", base * 1000 + k * 5, float(rng.normal()),
                        {"h": "a"})
        # ...then a sprinkle across a further week of 60s windows
        week = 7 * 86_400
        for k in range(200):
            t.add_point("sk2.m", base + 120 + k * (week // 200),
                        float(rng.normal()), {"h": "a"})
        q = TSQuery(start=str(base - 1), end=str(base + week),
                    queries=[parse_m_subquery("sum:60s-p90:sk2.m")])
        q.validate()
        runner = t.new_query_runner()
        res = runner.run(q)
        assert runner.exec_stats.get("sketchHazardExact") == 1.0
        assert res and res[0].dps

    def test_sharded_sketch_matches_single_device(self):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows
        from opentsdb_tpu.ops.streaming import StreamAccumulator
        from opentsdb_tpu.parallel import make_mesh, ShardedStreamAccumulator
        mesh = make_mesh()
        assert mesh is not None
        rng = np.random.default_rng(35)
        s, n = 11, 512
        start = 1_356_998_400_000
        span = 2 * 3_600_000
        ts = (np.sort(rng.integers(0, span, (s, n)), axis=1)
              + start).astype(np.int64)
        val = rng.normal(10, 3, (s, n))
        mask = rng.random((s, n)) > 0.05
        fixed = FixedWindows.for_range(start, start + span, 3_600_000)
        spec, wargs = fixed.split()
        acc = StreamAccumulator.create(s, spec, wargs, sketch=True)
        sacc = ShardedStreamAccumulator(mesh, s, spec, wargs, sketch=True)
        for k in range(0, n, 128):
            sl = slice(k, k + 128)
            acc.update(jnp.asarray(ts[:, sl]), jnp.asarray(val[:, sl]),
                       jnp.asarray(mask[:, sl]))
            sacc.update(ts[:, sl], val[:, sl], mask[:, sl])
        # row-local fold: per-series sketches must agree exactly
        q1 = np.asarray(acc.state["q"])
        q2 = np.asarray(sacc.state["q"])[:s]
        np.testing.assert_allclose(q2, q1, rtol=1e-12, atol=1e-12)

    def test_many_merges_drift_bounded(self):
        """64 sequential merges into ONE window cell (the hazard case:
        window far wider than a chunk).  On stationary data the signed
        per-merge errors largely cancel; assert the p90 estimate stays
        within 2 rank-percent of exact after all merges."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops import streaming as st
        rng = np.random.default_rng(41)
        K = st.SKETCH_K
        q = jnp.zeros((1, K))
        n = jnp.zeros(1, jnp.int64)
        everything = []
        for _ in range(64):
            vals = np.sort(rng.normal(100, 25, 256))
            everything.append(vals)
            grid = st._rank_grid(jnp.asarray(vals)[None, :],
                                 jnp.asarray([[0]]),
                                 jnp.asarray([[256]]))[0]
            q = st._merge_sketch(q, n, grid, jnp.asarray([256]))
            n = n + 256
        allv = np.concatenate(everything)
        est = float(st.sketch_quantile(q, n, 90.0)[0])
        lo = np.percentile(allv, 88)
        hi = np.percentile(allv, 92)
        assert lo <= est <= hi, (est, lo, hi)

    def test_inf_data_values_survive_merges(self):
        """A legitimate +inf datapoint must not be silently rewritten to
        the max finite value (the empty-side sentinel uses a flag, not
        isfinite), so streamed and exact paths agree on inf series."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops import streaming as st
        K = st.SKETCH_K
        vals = np.sort(np.concatenate([np.arange(100.0), [np.inf]]))
        grid = st._rank_grid(jnp.asarray(vals)[None, :],
                             jnp.asarray([[0]]),
                             jnp.asarray([[101]]))[0]
        q = st._merge_sketch(jnp.zeros((1, K)), jnp.asarray([0]),
                             grid, jnp.asarray([101]))
        # two empty merges after: inf must still be there
        q = st._merge_sketch(q, jnp.asarray([101]),
                             jnp.zeros((1, K)), jnp.asarray([0]))
        assert np.isinf(np.asarray(q)[0, -1])
        # ...and the p50 region is untouched
        est = float(st.sketch_quantile(q, jnp.asarray([101]), 50.0)[0])
        assert abs(est - 50.0) < 3.0


class TestLaneSelection:
    """r3: the accumulator carries only the lanes its finish functions
    need — sum/avg/count queries stream with NO segment scatters."""

    def test_minimal_lanes_answers_match_full(self):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows
        from opentsdb_tpu.ops.streaming import (
            StreamAccumulator, lanes_for)
        rng = np.random.default_rng(51)
        s, n = 4, 512
        start = 1_356_998_400_000
        ts = (np.sort(rng.integers(0, 3_000_000, (s, n)), axis=1)
              + start).astype(np.int64)
        val = rng.normal(10, 3, (s, n))
        mask = rng.random((s, n)) > 0.1
        fixed = FixedWindows.for_range(start, start + 3_000_000, 60_000)
        spec, wargs = fixed.split()
        for fns in (["sum"], ["avg", "count"], ["dev"], ["min", "max"],
                    ["first", "last", "diff"], ["mult"]):
            full = StreamAccumulator.create(s, spec, wargs)
            slim = StreamAccumulator.create(s, spec, wargs,
                                            lanes=lanes_for(fns))
            for k in range(0, n, 128):
                sl = slice(k, k + 128)
                for acc in (full, slim):
                    acc.update(jnp.asarray(ts[:, sl]),
                               jnp.asarray(val[:, sl]),
                               jnp.asarray(mask[:, sl]))
            for fn in fns:
                wf, of, mf = full.finish(fn)
                ws, os_, ms = slim.finish(fn)
                np.testing.assert_array_equal(np.asarray(mf),
                                              np.asarray(ms))
                m = np.asarray(mf)
                np.testing.assert_allclose(np.asarray(os_)[m],
                                           np.asarray(of)[m],
                                           rtol=1e-12, atol=1e-12)

    def test_sum_lanes_have_no_scatter(self):
        """The jitted update for sum-only lanes must contain no scatter
        ops (the segment lanes are the only scatter users)."""
        import jax
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows
        from opentsdb_tpu.ops import streaming
        fixed = FixedWindows.for_range(0, 3_000_000, 60_000)
        spec, wargs = fixed.split()
        state = streaming._zero_state(4, spec.count,
                                      lanes=streaming.lanes_for(["sum"]))
        ts = jnp.zeros((4, 128), jnp.int64)
        val = jnp.zeros((4, 128))
        mask = jnp.ones((4, 128), bool)
        hlo = jax.jit(streaming._update, static_argnums=0).lower(
            spec, state, ts, val, mask, wargs).as_text()
        assert "scatter" not in hlo, "sum-only stream update has a scatter"

    def test_missing_lane_raises_clearly(self):
        from opentsdb_tpu.ops.downsample import FixedWindows
        from opentsdb_tpu.ops.streaming import StreamAccumulator, lanes_for
        fixed = FixedWindows.for_range(0, 3_000_000, 60_000)
        spec, wargs = fixed.split()
        acc = StreamAccumulator.create(2, spec, wargs,
                                       lanes=lanes_for(["sum"]))
        with pytest.raises(KeyError, match="lacks lane"):
            acc.finish("max")


class TestStateBudget:
    def test_oversized_streaming_grid_refused_as_413(self):
        """A fine downsample over a huge range must refuse with the
        budget error shape, not OOM the device mid-query."""
        import pytest
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.query.limits import QueryException
        from opentsdb_tpu.utils.config import Config

        tsdb = TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.query.streaming.point_threshold": "10",
            "tsd.query.device_cache.enable": "false",
            "tsd.query.spill.enable": "false",
            "tsd.query.streaming.state_mb": "1",
        }))
        base = 1_356_998_400
        span = 40_000_000     # ~463 days
        for i in range(200):
            tsdb.add_point("big.m", base + i * (span // 200), float(i),
                           {"h": "a"})
        q = TSQuery(start=str(base), end=str(base + span),
                    queries=[parse_m_subquery("sum:10s-avg:big.m")])
        q.validate()
        with pytest.raises(QueryException, match="accelerator memory"):
            tsdb.new_query_runner().run(q)

    def test_sketch_lane_counted_and_mesh_divides(self):
        """Percentile sketches dominate the state estimate (review r3);
        the mesh divides the per-chip footprint so a sharded query under
        the per-chip budget still streams."""
        import pytest
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.query.limits import QueryException
        from opentsdb_tpu.utils.config import Config

        base = 1_356_998_400
        span = 400_000

        def mk(state_mb, mesh):
            t = TSDB(Config({
                "tsd.core.auto_create_metrics": True,
                "tsd.query.streaming.point_threshold": "10",
                "tsd.query.device_cache.enable": "false",
                "tsd.query.mesh.enable": mesh,
                "tsd.query.mesh.min_series": 0,
                "tsd.query.spill.enable": "false",
                "tsd.query.streaming.state_mb": str(state_mb),
            }))
            for h in range(8):
                for i in range(40):
                    t.add_point("sk.m", base + i * (span // 40) + h,
                                float(i), {"h": "h%d" % h})
            return t

        def q(t, m="p99:60s-p99:sk.m"):
            tq = TSQuery(start=str(base), end=str(base + span),
                         queries=[parse_m_subquery(m)])
            tq.validate()
            return t.new_query_runner().run(tq)

        # sketch bytes push this over a limit the plain-lane math passes:
        # 8 series x 8192 padded windows x ~272B/cell ~ 17MB > 10MB,
        # while a (lanes+1)*8 estimate would say well under 1MB
        with pytest.raises(QueryException, match="sketches"):
            q(mk(10, mesh=False))
        # the 8-device mesh divides the same footprint to ~2.2MB/chip
        res = q(mk(10, mesh=True))
        assert res and res[0].dps

    def test_materialized_grid_guard(self):
        """Sparse series over a huge range with a fine interval must
        refuse too — the [S, W] grid is points-independent."""
        import pytest
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.query.limits import QueryException
        from opentsdb_tpu.utils.config import Config

        base = 1_356_998_400
        span = 40_000_000
        tsdb = TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.query.device_cache.enable": "false",
            "tsd.query.spill.enable": "false",
            "tsd.query.streaming.state_mb": "2",
        }))
        for i in range(50):     # 50 points: far under any point budget
            tsdb.add_point("sp.m", base + i * (span // 50), float(i),
                           {"h": "a"})
        q = TSQuery(start=str(base), end=str(base + span),
                    queries=[parse_m_subquery("sum:10s-avg:sp.m")])
        q.validate()
        with pytest.raises(QueryException, match="downsample grid"):
            tsdb.new_query_runner().run(q)

    def test_materialized_grid_guard_divides_by_mesh(self):
        """The materialized-path grid guard is per-chip: the same query
        that 413s flat must be admitted when the 8-device mesh serves it
        (ADVICE r3 medium — the flat estimate made the per-chip streaming
        allowance unreachable)."""
        import pytest
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        from opentsdb_tpu.query.limits import QueryException
        from opentsdb_tpu.utils.config import Config

        base = 1_356_998_400
        span = 1_500_000      # 150k windows at 10s

        def mk(mesh):
            t = TSDB(Config({
                "tsd.core.auto_create_metrics": True,
                "tsd.query.device_cache.enable": "false",
                "tsd.query.mesh.enable": mesh,
                "tsd.query.mesh.min_series": 0,
                "tsd.query.spill.enable": "false",
                "tsd.query.streaming.state_mb": "8",
            }))
            for h in range(8):
                for i in range(50):
                    t.add_point("mg.m", base + i * (span // 50) + h,
                                float(i), {"h": "h%d" % h})
            return t

        def q(t):
            tq = TSQuery(start=str(base), end=str(base + span),
                         queries=[parse_m_subquery("sum:10s-avg:mg.m")])
            tq.validate()
            return t.new_query_runner().run(tq)

        # flat: 8 series x ~150k windows x 24B ~ 28MB > 8MB -> refuse
        with pytest.raises(QueryException, match="downsample grid"):
            q(mk(mesh=False))
        # mesh: ~3.6MB/chip across 8 devices -> admitted
        res = q(mk(mesh=True))
        assert res and res[0].dps


class TestSegmentChunkMoments:
    """Wider-than-data chunk grids (config 2's shape) take the N-bounded
    segment form: must merge to the same accumulated grid as the
    edge-search form, chunk by chunk."""

    def test_wide_grid_stream_equals_narrow_path(self):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows, FILL_NONE
        from opentsdb_tpu.ops import streaming
        rng = np.random.default_rng(71)
        s, n_chunk, chunks = 3, 64, 4
        # 10ms windows over the whole span: W ~ 40x the chunk size
        span = 200_000
        windows = FixedWindows.for_range(0, span, 70)
        spec, wargs = windows.split()
        assert streaming._use_segment_chunk(
            n_chunk, spec.count, frozenset({"total", "lo", "hi"}), False)
        ts = np.sort(rng.choice(span, size=(s, n_chunk * chunks),
                                replace=False), axis=1).astype(np.int64)
        val = rng.normal(50, 20, (s, n_chunk * chunks))
        val[rng.random(val.shape) < 0.04] = np.nan
        mask = rng.random(val.shape) < 0.95
        lanes = streaming.lanes_for(["sum", "min", "max", "count", "dev"])
        acc = streaming.StreamAccumulator.create(s, spec, wargs,
                                                 lanes=lanes)
        for c in range(chunks):
            sl = slice(c * n_chunk, (c + 1) * n_chunk)
            acc.update(ts[:, sl], val[:, sl], mask[:, sl])
        # reference: one-shot materialized downsample over the full batch
        from opentsdb_tpu.ops.downsample import downsample
        for fn in ("sum", "min", "max", "count", "dev", "avg"):
            wts, got, gm = acc.finish(fn)
            _, want, wm = downsample(ts, val, mask, fn, spec, wargs,
                                     FILL_NONE)
            np.testing.assert_array_equal(np.asarray(gm), np.asarray(wm),
                                          err_msg=fn)
            m = np.asarray(wm)
            np.testing.assert_allclose(np.asarray(got)[m],
                                       np.asarray(want)[m],
                                       rtol=1e-9, atol=1e-9, err_msg=fn)


class TestSketchDriftBound:
    """Direct coverage for the documented ~C/(2K) per-cell rank-drift
    bound of the mergeable quantile summary (module docstring of
    ops/streaming.py) under ADVERSARIAL chunking: every chunk folds
    into the SAME window cell (the "0all"-shaped hazard), and chunks
    arrive as sorted contiguous value ranges — the ordering that
    maximizes per-merge re-interpolation error (stationary data's
    signed-error cancellation is deliberately defeated)."""

    def _drift(self, n_chunks: int, per_chunk: int = 256) -> float:
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import AllWindow
        from opentsdb_tpu.ops.streaming import (StreamAccumulator,
                                                lanes_for)
        n = n_chunks * per_chunk
        span = n * 1000
        windows = AllWindow(0, span)
        spec, wargs = windows.split()
        acc = StreamAccumulator.create(1, spec, wargs, sketch=True,
                                       lanes=lanes_for(["p50"]))
        # values 0..n-1 in time order: chunk c holds the contiguous
        # ascending run [c*m, (c+1)*m) — every merge splices a disjoint
        # value range into the accumulated grid
        for c in range(n_chunks):
            vals = np.arange(c * per_chunk, (c + 1) * per_chunk,
                             dtype=np.float64)
            ts = (vals * 1000).astype(np.int64)
            acc.update(jnp.asarray(ts[None, :]), jnp.asarray(vals[None, :]),
                       jnp.ones((1, per_chunk), bool))
        worst = 0.0
        for pct in (10.0, 25.0, 50.0, 75.0, 90.0):
            _, out, mask = acc.finish("p%g" % pct if pct != 50.0
                                      else "median")
            assert np.asarray(mask).all()
            est = float(np.asarray(out).ravel()[0])
            # population is 0..n-1, so value/n IS the rank fraction
            true = pct / 100.0 * (n - 1)
            worst = max(worst, abs(est - true) / n)
        return worst

    def test_adversarial_chunking_stays_within_documented_bound(self):
        from opentsdb_tpu.ops.streaming import SKETCH_K
        for n_chunks in (4, 16):
            bound = n_chunks / (2.0 * SKETCH_K)
            drift = self._drift(n_chunks)
            assert drift <= 1.25 * bound + 1e-3, \
                "C=%d: rank drift %.4f exceeds ~C/(2K)=%.4f" \
                % (n_chunks, drift, bound)

    def test_single_chunk_is_rank_exact_within_grid(self):
        """C=1: no merges at all — the only error is the K-point
        equi-rank grid's own interpolation, far below one merge's
        1/(2K) allowance."""
        from opentsdb_tpu.ops.streaming import SKETCH_K
        assert self._drift(1) <= 0.5 / SKETCH_K


class TestStreamedProgramsPinned:
    """The streamed route's jitted programs — `_update`, `_update_sliced`,
    `_finish` and the shared `_grid_tail` — lower, at one small shape, to
    the text they lowered to at PR 34's tree (6ed9b6c; the hashes were
    taken there and on the tree that added this test, and were equal).
    XLA's compile-cache key is the program: while these hold, a daemon of
    this tree loads the cache entries a daemon of that one wrote, and a
    benchmark pair of the two compares like with like.  A PR that means
    to change one of these programs changes its hash here, and says so;
    a PR that only instruments the host side around them (PR 35's stages
    and counters in `_stream_grouped`) must leave them."""

    PINNED = {
        "_update":
            "cae13741bcaf18fc4d089d64cd7dfe9bc7fb17912daf6e2f5a554dd41de7b798",
        "_update_sliced":
            "269fb7a88518e33f421d90d31bc8dad3699b66d2fbc23012eab210666fd40689",
        "_finish":
            "8a13f21e24e69071d6d7ffa973efce99b65501005b7aafe2f7928e91b84584e2",
        "_grid_tail":
            "e327773d02797d3c96203d3eecd763cdc3c53cd0223bcf4e6f88a43ef9b37850",
    }

    @staticmethod
    def _lowered(name):
        from opentsdb_tpu.ops import pipeline, streaming
        from opentsdb_tpu.ops.streaming import lanes_for
        # heavy-cold-scan's region classes in small: 12 h of 10-minute
        # windows (72), 8 series, chunks of 64
        windows = FixedWindows.for_range(START, START + 43_200_000 - 1,
                                         600_000)
        spec, wargs = windows.split()
        s, n = 8, 64
        ts = np.full((s, n), PAD, np.int64)
        val = np.zeros((s, n), np.float64)
        mask = np.zeros((s, n), bool)
        lanes = lanes_for(["avg"])
        full = StreamAccumulator.create(s, spec, wargs, lanes=lanes)
        if name == "_update":
            return streaming._jitted_update.lower(
                full.spec, full.state, ts, val, mask, full.wargs)
        if name == "_update_sliced":
            sliced = StreamAccumulator.create(s, spec, wargs, lanes=lanes,
                                              window_slice=16)
            assert sliced.window_slice == 64 < spec.count
            return streaming._jitted_update_sliced.lower(
                sliced.spec, sliced.window_slice, sliced.state, ts, val,
                mask, sliced.wargs, 3)
        if name == "_finish":
            return streaming._jitted_finish.lower(
                full.spec, "avg", FILL_NONE, full.state, full.wargs, 0.0)
        wts, v, m = full.finish("avg")
        pspec = pipeline.PipelineSpec(
            aggregator="sum", downsample=pipeline.DownsampleStep("avg", spec))
        return pipeline._jitted_grid_tail.lower(
            pspec, 4, wts, v, m, np.arange(s, dtype=np.int32) % 3)

    @pytest.mark.parametrize("name", sorted(PINNED))
    def test_lowers_to_the_text_pinned_at_pr34(self, name):
        import hashlib
        text = self._lowered(name).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == \
            self.PINNED[name], (
                "%s lowers to another program than at 6ed9b6c (%d "
                "characters of text): a daemon of this tree compiles it "
                "anew where that one's cache entry was" % (name, len(text)))

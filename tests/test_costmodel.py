"""Cost-model mode selection (VERDICT r4 #4): the shape-driven chooser
must reproduce the r4 chip-race winners at the headline shape, pick the
safe host modes on CPU, and never hand an infeasible mode to a kernel —
for ANY of the 7 BASELINE config shapes."""

import numpy as np
import pytest

from opentsdb_tpu.ops import costmodel
from opentsdb_tpu.ops import downsample as ds
from opentsdb_tpu.ops import group_agg as ga


# (s, n, w_edges, g) per BASELINE config with a grouped-downsample shape;
# streamed configs use their per-chunk dispatch shape.
CONFIG_SHAPES = {
    "headline": (1024, 65_536, 514, 100),
    "config1": (1, 1_048_576, 3502, 1),
    "config2_chunk": (128, 65_536, 8195, 1),
    "config3": (10_240, 2048, 7, 10_240),
    "config4_chunk": (512, 65_536, 1367, 1),
    "config5_chunk": (1024, 65_536, 10_923, 1),
    "config7": (1024, 976_562, 162_761, 16),
}


class TestChipAnchors:
    """The chooser must reproduce the winners at the headline shape that
    the TPU cost constants were anchored to (from an earlier chip
    session, not re-measured on this installation — ROADMAP C1)."""

    def test_search_headline_tpu(self):
        s, n, e, _ = CONFIG_SHAPES["headline"]
        cands = [m for m in ("scan", "compare_all", "hier")
                 if ds._search_feasible(m, n, e)]
        assert costmodel.choose_search(s, n, e, "tpu", cands) == "hier"

    def test_scan_headline_tpu(self):
        # subblock is the CHIP-MEASURED winner (r4 race, 88ms); the
        # constants must not flip the pick to the unmeasured
        # subblock2 — only a chip race may do that
        s, n, e, _ = CONFIG_SHAPES["headline"]
        assert costmodel.choose_scan(
            s, n, e, "tpu", ["flat", "subblock", "subblock2"]) \
            == "subblock"

    def test_group_headline_tpu(self):
        # G=100 on the headline grid: sorted won the chip race (~90ms vs
        # matmul ~100ms vs segment 219ms)
        assert costmodel.choose_group(
            1024, 512, 100, "tpu", ["segment", "sorted", "matmul"]) \
            == "sorted"

    def test_extreme_headline_tpu(self):
        # chip race: scan 0.5245 < subblock 0.8282 << segment 7.161
        assert costmodel.choose_extreme(
            1024, 65_536, 514, "tpu",
            ["scan", "segment", "subblock"]) == "scan"

    def test_small_group_count_prefers_matmul(self):
        # matmul cost is linear in G; far below the sorted crossover it
        # must win on TPU
        assert costmodel.choose_group(
            1024, 512, 8, "tpu", ["segment", "sorted", "matmul"]) \
            == "matmul"

    def test_cpu_prefers_host_modes(self):
        s, n, e, g = CONFIG_SHAPES["headline"]
        # measured on the config-1 shape: XLA's CPU cumsum is a serial
        # scalar loop, so subblock's 1/32-length scan wins on the host
        # too (2.1ms vs flat 11.6 vs subblock2 9.4)
        assert costmodel.choose_scan(
            s, n, e, "cpu", ["flat", "subblock", "subblock2"]) \
            == "subblock"
        assert costmodel.choose_group(
            s, 512, g, "cpu", ["segment", "sorted", "matmul"]) == "segment"
        assert costmodel.choose_extreme(
            s, n, e, "cpu", ["scan", "segment", "subblock"]) == "segment"

    def test_cpu_config1_shape_picks_subblock(self):
        s, n, e, _ = CONFIG_SHAPES["config1"]
        got = costmodel.choose_scan(s, n, e, "cpu",
                                    ["flat", "subblock", "subblock2"])
        assert got == "subblock"
        # subblock2's serial-ish prefix pass keeps it well behind
        # subblock on the host (measured 9.4ms vs 2.1 at this shape)
        assert costmodel.predict_scan("subblock2", s, n, e, "cpu") > \
            costmodel.predict_scan("subblock", s, n, e, "cpu")


class TestFeasibilityComposition:
    """_effective_* must return a feasible mode for every BASELINE config
    shape on either platform — the r4 failure (config 1 rc=1: hier
    forced onto a [1, 1M] x 3502 shape) must be structurally
    impossible."""

    @pytest.mark.parametrize("shape", sorted(CONFIG_SHAPES))
    @pytest.mark.parametrize("platform", ["tpu", "cpu"])
    def test_search_always_feasible(self, shape, platform):
        s, n, e, _ = CONFIG_SHAPES[shape]
        got = ds._effective_search_mode(s, n, e, platform)
        assert got in ("scan", "compare_all", "hier")
        assert ds._search_feasible(got, n, e)

    @pytest.mark.parametrize("shape", sorted(CONFIG_SHAPES))
    def test_config1_shape_demotes_dense_search(self, shape):
        s, n, e, _ = CONFIG_SHAPES[shape]
        if n >= 1_000_000:
            # wide-N shapes: the dense compare matrices exceed their
            # caps; only the binary scan is feasible
            assert not ds._search_feasible("hier", n, e)
            assert not ds._search_feasible("compare_all", n, e)

    @pytest.mark.parametrize("shape", sorted(CONFIG_SHAPES))
    def test_scan_choice_valid(self, shape):
        s, n, e, _ = CONFIG_SHAPES[shape]
        got = ds._effective_scan_mode(s, n, e)
        assert got in ("flat", "subblock", "subblock2")
        if got == "subblock":
            assert n % ds._SUB_K == 0 and ds._subblock_edges_fit(n, e)

    @pytest.mark.parametrize("shape", sorted(CONFIG_SHAPES))
    def test_group_choice_valid(self, shape):
        s, n, e, g = CONFIG_SHAPES[shape]
        got = ga._effective_group_reduce_mode(s, e - 1, g)
        assert got in ("segment", "matmul", "sorted")
        if got == "matmul":
            assert ga._matmul_feasible(s, g)

    def test_extremes_never_choose_matmul(self):
        for s, n, e, g in CONFIG_SHAPES.values():
            assert ga._effective_group_reduce_mode(
                s, e - 1, g, extremes=True) != "matmul"

    def test_big_group_count_excluded_from_matmul(self):
        # 10k groups: the one-hot would be [10240, 10240] f64 > 32MB
        assert not ga._matmul_feasible(10_240, 10_240)


class TestCostTable:
    def test_unknown_platform_is_an_error(self):
        # a device the table does not know is never priced as a TPU
        for platform in ("gpu", "rocm", "", "TPU"):
            with pytest.raises(ValueError, match="no cost table"):
                costmodel.costs(platform)
        assert costmodel.costs("tpu") is not costmodel.costs("cpu")


class TestPredictionSanity:
    def test_predictions_positive_and_finite(self):
        for s, n, e, g in CONFIG_SHAPES.values():
            for plat in ("tpu", "cpu"):
                for m in ("scan", "compare_all", "hier"):
                    assert 0 < costmodel.predict_search(m, s, n, e, plat) \
                        < 1e6
                for m in ("flat", "subblock", "subblock2"):
                    assert 0 < costmodel.predict_scan(m, s, n, e, plat) \
                        < 1e6
                for m in ("segment", "matmul", "sorted"):
                    assert 0 < costmodel.predict_group(m, s, e - 1, g,
                                                       plat) < 1e6
                for m in ("scan", "segment", "subblock"):
                    assert 0 < costmodel.predict_extreme(m, s, n, e,
                                                         plat) < 1e6

    def test_headline_predictions_near_measurements(self):
        """The table must land within 3x of the chip anchors
        it was fitted to (a grossly wrong formula would still 'choose'
        something — this pins the magnitudes).  The group anchors are
        PR 27's race on a v5e at fleet-replay-100k's and heavy-replay's
        shapes (PERF.md section 6); sorted at its worst two."""
        s, n, e = 1024, 65_536, 514
        anchors = [
            (costmodel.predict_search("scan", s, n, e, "tpu"), 0.154),
            (costmodel.predict_search("compare_all", s, n, e, "tpu"),
             0.116),
            (costmodel.predict_search("hier", s, n, e, "tpu"), 0.020),
            (costmodel.predict_group("segment", 100_000, 16, 16, "tpu"),
             0.3475),
            (costmodel.predict_group("sorted", 100_000, 8, 131_072, "tpu"),
             0.0386),
            (costmodel.predict_group("matmul", 100_000, 16, 16, "tpu"),
             0.0177),
            (costmodel.predict_group("segment", 4000, 128, 16, "tpu"),
             0.0819),
            (costmodel.predict_group("sorted", 4000, 16, 4096, "tpu"),
             0.00241),
            (costmodel.predict_group("matmul", 4000, 128, 16, "tpu"),
             0.00594),
            (costmodel.predict_group("rows", 100_000, 8, 131_072, "tpu"),
             0.00119),
            (costmodel.predict_extreme("scan", s, n, e, "tpu"), 0.40),
        ]
        for got, want in anchors:
            assert want / 3 < got < want * 3, (got, want)


class TestAutoMatchesForcedResults:
    """End-to-end: a grouped downsample under the chooser's own picks
    answers bit-identically to every pinned form (the chooser only
    changes WHICH equivalence-tested kernel runs)."""

    def test_auto_equals_forced(self, kernel_forms):
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import FixedWindows, pad_pow2
        from opentsdb_tpu.ops.pipeline import (PipelineSpec,
                                               DownsampleStep,
                                               run_group_pipeline)
        rng = np.random.default_rng(7)
        s, n = 8, 256
        start = 1_356_998_400_000
        ts = start + np.sort(rng.integers(0, 3_600_000, (s, n)), axis=1)
        val = rng.normal(100, 10, (s, n))
        mask = rng.random((s, n)) < 0.9
        gid = np.arange(s) % 3
        fixed = FixedWindows.for_range(start, start + 3_600_000, 60_000)
        wspec, wargs = fixed.split()
        spec = PipelineSpec("sum", DownsampleStep("avg", wspec, "none",
                                                  0.0))

        def run():
            return [np.asarray(x) for x in run_group_pipeline(
                spec, jnp.asarray(ts), jnp.asarray(val),
                jnp.asarray(mask), jnp.asarray(gid), pad_pow2(3), wargs)]

        want = run()
        for scan in ("flat", "subblock", "subblock2"):
            for search in ("scan", "compare_all", "hier"):
                for group in ("segment", "matmul", "sorted"):
                    kernel_forms(scan=scan, search=search, group=group)
                    got = run()
                    for a, b in zip(want, got):
                        np.testing.assert_allclose(
                            a, b, rtol=1e-9, atol=1e-9,
                            err_msg="%s/%s/%s" % (scan, search, group))


class TestFeatureDecomposition:
    """predict_* must equal dot(features_*, costs) BY CONSTRUCTION — a
    constant re-anchored from a chip race then means exactly what the
    predictor consumes."""

    @pytest.mark.parametrize("plat", ["tpu", "cpu"])
    @pytest.mark.parametrize("shape", sorted(CONFIG_SHAPES))
    def test_predict_equals_feature_dot(self, plat, shape):
        s, n, e, g = CONFIG_SHAPES[shape]
        c = costmodel.costs(plat)

        def dot(fv):
            return sum(u * c[t] for t, u in fv.items())

        for m in ("scan", "compare_all", "hier"):
            assert costmodel.predict_search(m, s, n, e, plat) == \
                pytest.approx(dot(costmodel.features_search(m, s, n, e)))
        for m in ("flat", "subblock", "subblock2"):
            assert costmodel.predict_scan(m, s, n, e, plat) == \
                pytest.approx(dot(costmodel.features_scan(m, s, n, e)))
        for m in ("scan", "segment", "subblock"):
            assert costmodel.predict_extreme(m, s, n, e, plat) == \
                pytest.approx(dot(costmodel.features_extreme(m, s, n,
                                                             e)))
        for m in ("segment", "matmul", "sorted", "rows"):
            assert costmodel.predict_group(m, s, e - 1, g, plat) == \
                pytest.approx(dot(costmodel.features_group(m, s, e - 1,
                                                           g)))

    def test_every_feature_term_is_a_cost_term(self):
        s, n, e, g = CONFIG_SHAPES["headline"]
        vectors = (
            [costmodel.features_search(m, s, n, e)
             for m in ("scan", "compare_all", "hier")]
            + [costmodel.features_scan(m, s, n, e)
               for m in ("flat", "subblock", "subblock2")]
            + [costmodel.features_extreme(m, s, n, e)
               for m in ("scan", "segment", "subblock")]
            + [costmodel.features_group(m, s, e - 1, g)
               for m in ("segment", "matmul", "sorted", "rows")])
        for fv in vectors:
            for term in fv:
                assert term in costmodel.COST_TERMS

class TestArgminFlips:
    """choose_* must flip where the model says the crossover is."""

    def test_group_matmul_flips_to_sorted_as_g_grows(self):
        # matmul cost is linear in G (g*s*w*mxu_cell); sorted is
        # G-independent (s*w*sorted_grid) — the crossover sits at
        # G* = sorted_grid / mxu_cell
        c = costmodel.costs("tpu")
        crossover = c["sorted_grid"] / c["mxu_cell"]
        lo = max(int(crossover * 0.5), 1)
        hi = int(crossover * 2)
        cands = ["segment", "sorted", "matmul"]
        assert costmodel.choose_group(1024, 512, lo, "tpu",
                                      cands) == "matmul"
        assert costmodel.choose_group(1024, 512, hi, "tpu",
                                      cands) == "sorted"

    def test_search_compare_all_flips_to_scan_as_n_grows(self):
        # compare_all is O(S*N*E) vs the scan's O(S*E*log2 N): the
        # crossover sits at N/log2(N) = gather_round/cmp_cell — the
        # headline N=65536 sits on the compare side, N=2^22 well past
        cands = ["scan", "compare_all"]
        assert costmodel.choose_search(1024, 65_536, 514, "tpu",
                                       cands) == "compare_all"
        assert costmodel.choose_search(1024, 2 ** 22, 514, "tpu",
                                       cands) == "scan"


class TestMixedAggregatorDecisions:
    """The group axis keys its extremes flag on the CROSS-SERIES
    aggregator (what moment_group_reduce dispatches on), not the
    downsample function — a `max:10s-avg:` query downsamples with the
    scan path but group-reduces as an extreme, where the matmul form
    does not exist (review finding, PR 6)."""

    def test_max_of_avg_excludes_matmul_from_group_axis(self):
        from opentsdb_tpu.obs import jaxprof
        dec = jaxprof.segment_decisions("tpu", 64, 1024, 32, 8, "avg",
                                        aggregator="max")
        assert "scan" in dec          # downsample side: the scan path
        assert "matmul" not in dec["group"]["candidates"]
        assert dec["group"]["mode"] in ("segment", "sorted")

    def test_sum_of_max_keeps_matmul_candidacy(self):
        from opentsdb_tpu.obs import jaxprof
        dec = jaxprof.segment_decisions("tpu", 64, 1024, 32, 8, "max",
                                        aggregator="sum")
        assert "extreme" in dec       # downsample side: extreme reduce
        assert "matmul" in dec["group"]["candidates"]

    def test_aggregator_unknown_falls_back_to_ds_function(self):
        from opentsdb_tpu.obs import jaxprof
        dec = jaxprof.segment_decisions("tpu", 64, 1024, 32, 8, "max")
        assert "matmul" not in dec["group"]["candidates"]

"""Rollup lanes (storage/rollup.py, ISSUE 11).

The correctness gate: a lane-served answer is EXACT, not approximate —
lane-served == exact-fallback BITWISE on integer data for every
lane-derivable downsample function (sum/count/avg/min/max + aliases),
non-multiple intervals and non-derivable functions provably fall back,
and an acked write is never served stale (the planner falls back until
the maintenance pass rebuilds the dirty block).  Plus: the Storyboard
byte-budget selection, the over-budget window-striped serve path
(spill-pool replay reuse), admission pricing of warm lanes, and the
tree-level lint pin that gutting the lane invalidator fails the build.

Mesh disabled throughout: a rollup lane serves the single-device route
(plan_decision consults lanes only for non-mesh plans), and the suite's
8 virtual devices would otherwise turn every >= 8-series query into a
mesh plan.
"""

import os
import shutil
import sys

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_356_998_400


def make_tsdb(enable=True, **over):
    cfg = {
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": False,
        "tsd.storage.fix_duplicates": True,
        "tsd.rollup.enable": enable,
        "tsd.rollup.intervals": "1m,1h",
        "tsd.rollup.block_windows": 8,
        "tsd.rollup.delay_ms": 0,
    }
    cfg.update(over)
    return TSDB(Config(cfg))


def feed_int(tsdb, n=6000, hosts=("a", "b"), metric="lane.i"):
    for i, host in enumerate(hosts):
        key = tsdb._series_key(metric, {"host": host}, create=True)
        ts = (np.arange(n, dtype=np.int64) + BASE) * 1000
        vals = (np.arange(n, dtype=np.int64) * 7 + i * 13) % 101
        tsdb.store.add_batch(key, ts, vals, True)


def feed_float(tsdb, n=6000, hosts=("a", "b"), metric="lane.f", seed=3):
    rng = np.random.default_rng(seed)
    for host in hosts:
        key = tsdb._series_key(metric, {"host": host}, create=True)
        ts = (np.arange(n, dtype=np.int64) + BASE) * 1000
        tsdb.store.add_batch(key, ts, rng.standard_normal(n), False)


def run_q(tsdb, m, start=BASE + 7, end=BASE + 5923):
    q = TSQuery(start=str(start), end=str(end),
                queries=[parse_m_subquery(m)])
    q.validate()
    runner = tsdb.new_query_runner()
    out = [r.to_json() for r in runner.run(q)]
    return out, dict(runner.exec_stats)


def warm(tsdb, m, **kw):
    """Consult (records demand) + build the demanded lanes."""
    run_q(tsdb, m, **kw)
    for _ in range(20):
        if not tsdb.rollup_lanes.refresh(tsdb.store, max_blocks=256):
            break


class TestLaneExactness:
    @pytest.mark.parametrize("fn", ["sum", "count", "avg", "min", "max",
                                    "zimsum", "mimmax"])
    def test_lane_served_equals_exact_bitwise_on_ints(self, fn):
        """ISSUE 11 acceptance: every lane-derivable aggregator serves
        bit-identical to the exact fallback on integer data."""
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        m = "sum:60s-%s:lane.i{host=*}" % fn
        warm(on, m)
        served, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0, stats
        plain, pstats = run_q(off, m)
        assert "rollupLane" not in pstats
        assert served == plain      # float dps, bit-for-bit

    def test_rate_over_lane_grid_matches_exact(self):
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        m = "sum:rate:60s-sum:lane.i{host=*}"
        warm(on, m)
        served, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0
        plain, _ = run_q(off, m)
        assert served == plain

    def test_unaligned_edges_recompute_from_raw(self):
        """Partial edge windows always recompute from raw points;
        sliding ranges keep matching the exact path bitwise."""
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        m = "sum:60s-sum:lane.i{host=*}"
        warm(on, m, start=BASE, end=BASE + 5999)
        for start, end in ((BASE + 7, BASE + 5003),
                           (BASE + 607, BASE + 5603),
                           (BASE + 61, BASE + 5999)):
            served, stats = run_q(on, m, start, end)
            assert stats.get("rollupLane") == 1.0, (start, end, stats)
            plain, _ = run_q(off, m, start, end)
            assert served == plain, (start, end)

    def test_float_data_matches_within_reassociation(self):
        """Float sums re-reduce from lane partials — mathematically
        exact, within the same last-ulp reassociation latitude the
        streamed path carries (the int pins above are the bitwise
        gate)."""
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_float(on)
        feed_float(off)
        m = "sum:60s-sum:lane.f{host=*}"
        warm(on, m)
        served, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0
        plain, _ = run_q(off, m)
        a = served[0]["dps"]
        b = plain[0]["dps"]
        assert set(a) == set(b)
        for k in a:
            assert a[k] == pytest.approx(b[k], rel=1e-12, abs=1e-12)


class TestFallbacks:
    def test_non_multiple_interval_falls_back(self):
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        m = "sum:90s-sum:lane.i{host=*}"   # 90s % 60s != 0
        warm(on, "sum:60s-sum:lane.i{host=*}")   # lanes exist
        served, stats = run_q(on, m)
        assert "rollupLane" not in stats, stats
        plain, _ = run_q(off, m)
        assert served == plain

    @pytest.mark.parametrize("fn", ["p95", "dev", "last", "median"])
    def test_non_derivable_functions_fall_back(self, fn):
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        warm(on, "sum:60s-sum:lane.i{host=*}")
        m = "sum:60s-%s:lane.i{host=*}" % fn
        served, stats = run_q(on, m)
        assert "rollupLane" not in stats, (fn, stats)
        plain, _ = run_q(off, m)
        assert served == plain, fn

    def test_cold_lanes_fall_back_and_record_demand(self):
        on = make_tsdb()
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        _, stats = run_q(on, m)
        assert "rollupLane" not in stats
        walk = on.rollup_lanes.collect_stats()
        assert walk["tsd.query.rollup.misses"] >= 1
        assert walk["tsd.query.rollup.demand_entries"] >= 1


class TestInvalidation:
    def test_acked_write_is_never_served_stale(self):
        """ISSUE 11 acceptance: ingest-then-query never serves a stale
        lane block — the write's mark fails the block's generation
        check, the query falls back to the exact path, and after the
        maintenance rebuild the lane serves the NEW data."""
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        m = "sum:60s-sum:lane.i{host=*}"
        warm(on, m)
        _, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0
        # overwrite a point INSIDE a served window (last-write-wins)
        for t in (on, off):
            t.add_point("lane.i", BASE + 300, 9999, {"host": "a"})
        served, stats = run_q(on, m)
        assert "rollupLane" not in stats, "stale lane served a write"
        plain, _ = run_q(off, m)
        assert served == plain
        # maintenance rebuild: the lane serves again, with the write
        for _ in range(20):
            if not on.rollup_lanes.refresh(on.store, max_blocks=256):
                break
        served, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0
        assert served == plain

    def test_new_series_invalidates_row_incomplete_blocks(self):
        on, off = make_tsdb(), make_tsdb(enable=False)
        feed_int(on)
        feed_int(off)
        m = "sum:60s-sum:lane.i{host=*}"
        warm(on, m)
        feed_int(on, hosts=("c",))
        feed_int(off, hosts=("c",))
        served, stats = run_q(on, m)
        assert "rollupLane" not in stats
        plain, _ = run_q(off, m)
        assert served == plain
        warm(on, m)
        served, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0
        assert served == plain

    def test_dropcaches_invalidates_lanes(self):
        on = make_tsdb()
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        warm(on, m)
        assert len(on.rollup_lanes) > 0
        on.rollup_lanes.invalidate()
        assert len(on.rollup_lanes) == 0
        _, stats = run_q(on, m)
        assert "rollupLane" not in stats


class TestStripedServe:
    def _common(self):
        return {"tsd.query.streaming.state_mb": 1,
                "tsd.query.spill.host_mb": 4,
                "tsd.rollup.block_windows": 64,
                "tsd.query.streaming.point_threshold": 1000}

    def _feed_wide(self, tsdb, hosts=96, n=3000, metric="lane.w"):
        for h in range(hosts):
            key = tsdb._series_key(
                metric, {"h": "h%d" % h, "g": "g%d" % (h % 4)},
                create=True)
            ts = (np.arange(n, dtype=np.int64) * 10 + BASE) * 1000
            vals = (np.arange(n, dtype=np.int64) * 7 + h * 13) % 101
            tsdb.store.add_batch(key, ts, vals, True)

    def _warm_wide(self, tsdb, m, start, end):
        run_q(tsdb, m, start, end)
        for _ in range(20):
            if not tsdb.rollup_lanes.refresh(
                    tsdb.store, max_blocks=256):
                break

    def test_over_budget_dense_grid_serves_host_fold(self):
        """A lane-served grid past the device-state budget with every
        cell populated (regular-cadence telemetry) folds group partial
        moments host-side — bitwise vs the lane-disabled control on
        ints."""
        on = make_tsdb(**self._common())
        off = make_tsdb(enable=False, **self._common())
        self._feed_wide(on)
        self._feed_wide(off)
        m = "sum:60s-sum:lane.w{g=*}"
        self._warm_wide(on, m, BASE, BASE + 30000)
        served, stats = run_q(on, m, BASE, BASE + 30000)
        assert stats.get("rollupLane") == 1.0, stats
        assert stats.get("rollupLaneStriped") == 1.0, stats
        plain, _ = run_q(off, m, BASE, BASE + 30000)
        assert served == plain

    def test_over_budget_rate_query_applies_rate(self):
        """Review regression (ISSUE 11): the dense host fold must NOT
        swallow the rate stage — rate plans take the device fold whose
        row-local contribution pass applies it, and the answers match
        the lane-disabled control."""
        on = make_tsdb(**self._common())
        off = make_tsdb(enable=False, **self._common())
        self._feed_wide(on)
        self._feed_wide(off)
        m = "sum:rate:60s-sum:lane.w{g=*}"
        self._warm_wide(on, m, BASE, BASE + 30000)
        served, stats = run_q(on, m, BASE, BASE + 30000)
        assert stats.get("rollupLane") == 1.0, stats
        assert stats.get("rollupLaneStriped") == 1.0, stats
        plain, _ = run_q(off, m, BASE, BASE + 30000)
        assert len(served) == len(plain)
        for a, b in zip(served, plain):
            assert a["tags"] == b["tags"]
            assert set(a["dps"]) == set(b["dps"])
            for k in a["dps"]:
                assert a["dps"][k] == pytest.approx(
                    b["dps"][k], rel=1e-12, abs=1e-12)

    def _feed_sparse(self, tsdb, hosts=96, n=300, metric="lane.s"):
        """Holes: ~40% of the 60s windows have no points."""
        rng = np.random.default_rng(7)
        for h in range(hosts):
            secs = np.sort(rng.choice(30000, size=n, replace=False)
                           .astype(np.int64))
            vals = (np.arange(n, dtype=np.int64) * 7 + h * 13) % 101
            key = tsdb._series_key(
                metric, {"h": "h%d" % h, "g": "g%d" % (h % 4)},
                create=True)
            tsdb.store.add_batch(key, (BASE + secs) * 1000, vals, True)

    def test_over_budget_sparse_extreme_folds_on_device_bitwise(self):
        """Holes force the interpolation-aware DEVICE tile fold; for
        extreme aggregators the fold is a selection over identical
        contribution bits, so it stays bitwise even with fractional
        interpolated values."""
        on = make_tsdb(**self._common())
        off = make_tsdb(enable=False, **self._common())
        self._feed_sparse(on)
        self._feed_sparse(off)
        m = "max:60s-max:lane.s{g=*}"
        self._warm_wide(on, m, BASE, BASE + 30000)
        served, stats = run_q(on, m, BASE, BASE + 30000)
        assert stats.get("rollupLane") == 1.0, stats
        assert stats.get("rollupLaneStriped") == 1.0, stats
        plain, _ = run_q(off, m, BASE, BASE + 30000)
        assert served == plain

    def test_over_budget_sparse_sum_folds_within_reassociation(self):
        """Additive device fold over holes: interpolated contributions
        are fractional, so per-tile partial merges carry the same
        last-ulp reassociation latitude as the streamed path."""
        on = make_tsdb(**self._common())
        off = make_tsdb(enable=False, **self._common())
        self._feed_sparse(on)
        self._feed_sparse(off)
        m = "sum:60s-sum:lane.s{g=*}"
        self._warm_wide(on, m, BASE, BASE + 30000)
        served, stats = run_q(on, m, BASE, BASE + 30000)
        assert stats.get("rollupLane") == 1.0, stats
        plain, _ = run_q(off, m, BASE, BASE + 30000)
        assert len(served) == len(plain)
        for a, b in zip(served, plain):
            assert a["tags"] == b["tags"]
            assert set(a["dps"]) == set(b["dps"])
            for k in a["dps"]:
                assert a["dps"][k] == pytest.approx(
                    b["dps"][k], rel=1e-12, abs=1e-12)

    def test_over_budget_non_foldable_agg_replays_through_pool(self):
        """dev is not moment-mergeable across tiles: the striped serve
        falls back to the PR 10 spill-pool stripe replay — identical
        kernels over identical row sets, bitwise vs the control."""
        on = make_tsdb(**self._common())
        off = make_tsdb(enable=False, **self._common())
        self._feed_wide(on)
        self._feed_wide(off)
        m = "dev:60s-sum:lane.w{g=*}"
        self._warm_wide(on, m, BASE, BASE + 30000)
        served, stats = run_q(on, m, BASE, BASE + 30000)
        assert stats.get("rollupLane") == 1.0, stats
        assert stats.get("rollupLaneStriped") == 1.0, stats
        assert stats.get("spillBytes", 0) > 0, stats
        plain, _ = run_q(off, m, BASE, BASE + 30000)
        assert served == plain


class TestBudgetSelection:
    def test_zero_ish_budget_materializes_nothing(self):
        on = make_tsdb(**{"tsd.rollup.mb": 0})
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        run_q(on, m)
        built = on.rollup_lanes.refresh(on.store, max_blocks=256)
        assert built == 0
        _, stats = run_q(on, m)
        assert "rollupLane" not in stats

    def test_selection_refuses_targets_that_cannot_fit(self):
        """The Storyboard greedy never part-builds a target whose
        byte estimate exceeds the whole budget (a half-materialized
        lane would never reach full coverage and never serve)."""
        from opentsdb_tpu.storage.rollup import LANE_CELL_BYTES
        on = make_tsdb()
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        run_q(on, m)
        on.rollup_lanes.max_bytes = 2 * LANE_CELL_BYTES  # < one block
        assert on.rollup_lanes.refresh(on.store, max_blocks=256) == 0

    def test_eviction_keeps_bytes_under_budget(self):
        on = make_tsdb()
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        warm(on, m)
        lanes = on.rollup_lanes
        walk = lanes.collect_stats()
        b0 = walk["tsd.query.rollup.bytes"]
        assert b0 > 0
        with lanes._lock:
            lanes.max_bytes = int(b0) - 1
            lanes._evict_for_locked(0)
        walk = lanes.collect_stats()
        assert walk["tsd.query.rollup.bytes"] <= lanes.max_bytes
        assert walk["tsd.query.rollup.evictions"] >= 1


class TestAdmissionPricing:
    def test_warm_lane_prices_below_cold(self):
        """tsd/admission.py prices the lane-served plan: a warm lane
        drops the predicted cost so dashboards admit where the cold
        raw-priced estimate would shed."""
        from opentsdb_tpu.tsd.admission import estimate_plan_cost_ms
        on = make_tsdb()
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        q = TSQuery(start=str(BASE), end=str(BASE + 5999),
                    queries=[parse_m_subquery(m)])
        q.validate()
        cold = estimate_plan_cost_ms(on, q)
        warm(on, m, start=BASE, end=BASE + 5999)
        warm_est = estimate_plan_cost_ms(on, q)
        assert cold > 0
        assert warm_est < cold

    def test_lane_coverage_fraction(self):
        on = make_tsdb()
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        metric = on.metrics.get_id("lane.i")
        assert on.rollup_lanes.coverage(
            metric, 60_000, "sum", BASE * 1000,
            (BASE + 5999) * 1000) == 0.0
        warm(on, m, start=BASE, end=BASE + 5999)
        assert on.rollup_lanes.coverage(
            metric, 60_000, "sum", BASE * 1000,
            (BASE + 5999) * 1000) == 1.0
        # non-derivable function: no coverage claim
        assert on.rollup_lanes.coverage(
            metric, 60_000, "p95", BASE * 1000,
            (BASE + 5999) * 1000) == 0.0


class TestMaintenanceCadence:
    def test_maybe_rollup_ticks_refresh(self):
        on = make_tsdb(**{"tsd.rollup.interval": 1})
        feed_int(on)
        m = "sum:60s-sum:lane.i{host=*}"
        run_q(on, m)                       # record demand
        from opentsdb_tpu.core.maintenance import MaintenanceThread
        mt = MaintenanceThread(on)         # not started: tick directly
        mt._next_rollup = 0.0
        mt._maybe_rollup(1.0)
        assert mt.rollup_passes == 1
        assert mt.rollup_blocks_built > 0
        _, stats = run_q(on, m)
        assert stats.get("rollupLane") == 1.0


class TestCoherenceContract:
    def test_gutting_the_lane_invalidator_fails_lint(self, tmp_path):
        """ISSUE 11 satellite: the lane store rides the tsdblint
        cache-coherence contract — deleting the backing-store drop
        inside ``RollupLanes.invalidate`` must re-fire the analyzer
        (cache-invalidator-gutted)."""
        sys.path.insert(0, REPO)
        from tools.lint import cache_coherence
        from tools.lint.core import LintContext
        from tools.lint.run import run_lint
        dst = tmp_path / "opentsdb_tpu"
        shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
        mod = dst / "storage" / "rollup.py"
        src = mod.read_text()
        needle = ("            if metric is None:\n"
                  "                self.invalidations += 1\n"
                  "                self._blocks = {}\n")
        assert needle in src, "expected the full-drop inside invalidate"
        mod.write_text(src.replace(
            needle, "            if metric is None:\n"
                    "                self.invalidations += 1\n"))
        ctx = LintContext(str(tmp_path))
        findings = run_lint(["opentsdb_tpu"], root=str(tmp_path),
                            analyzers=[cache_coherence.ANALYZER],
                            ctx=ctx)
        assert any(f.rule == "cache-invalidator-gutted"
                   and "rollup-lanes" in f.message for f in findings), (
            "gutting the rollup-lane invalidator went undetected:\n"
            + "\n".join(f.render() for f in findings))


@pytest.mark.slow
def test_bench_rollup_ratio_pinned():
    """ISSUE 11 acceptance: the long-range group-by at the
    BENCH_TILING shape answers >= 10x faster from a lane than the
    tiled exact path (tools/bench_rollup.py, committed as
    BENCH_ROLLUP.json)."""
    import json
    import subprocess
    out = os.path.join(REPO, "BENCH_ROLLUP.ci.json")
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "bench_rollup.py"),
             "--platform", "cpu", "--out", out],
            capture_output=True, text=True, timeout=900, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stdout[-4000:] \
            + proc.stderr[-2000:]
        with open(out) as fh:
            doc = json.load(fh)
        assert doc["speedup_lane_vs_tiled_exact"] >= 10.0, doc
        assert doc["divergence"].startswith("zero")
    finally:
        if os.path.exists(out):
            os.unlink(out)

"""Partial-aggregate cache (storage/agg_cache.py, ISSUE 9).

The correctness gate is BIT-identity, not closeness: a cache hit
replays arrays a cold run computed with the very same per-block
compiled programs, so

  * cold == warm == invalidated-and-recomputed, bitwise, on random
    float data (the strongest transparency guarantee);
  * cache-enabled == cache-disabled, bitwise, on exactly-representable
    (integer) data — where the monolithic and block-decomposed
    summation orders are both exact;

plus eviction-under-budget, incremental ingest invalidation (an acked
write is never served stale), the degraded-query keying pins (ISSUE 9
small fix), concurrent ingest-vs-query races (TSDBSAN-armed when the
sanitized subset runs this file), and the lint pin that gutting the
ingest-side invalidator fails the tree.
"""

import os
import shutil
import threading

import numpy as np
import pytest

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_356_998_400


def make_tsdb(**over):
    cfg = {
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": False,
        "tsd.storage.fix_duplicates": True,
        "tsd.query.cache.block_windows": 8,
        "tsd.query.cache.min_repeats": 1,
        # CI-scale data sits at the dispatch floor where the honest
        # costmodel would (correctly) refuse to cache — zero the
        # per-dispatch charge so the decision reduces to the repeat
        # gate and the tests exercise the machinery
        "tsd.query.cache.dispatch_overhead_us": 0,
    }
    cfg.update(over)
    return TSDB(Config(cfg))


def feed_float(tsdb, n=6000, hosts=("a", "b"), seed=3):
    rng = np.random.default_rng(seed)
    for host in hosts:
        for i in range(n):
            tsdb.add_point("sys.f", BASE + i,
                           float(rng.standard_normal()), {"host": host})


def feed_int(tsdb, n=6000, hosts=("a", "b"), metric="sys.i"):
    for host in hosts:
        key = tsdb._series_key(metric, {"host": host}, create=True)
        ts = (np.arange(n, dtype=np.int64) + BASE) * 1000
        vals = (np.arange(n, dtype=np.int64) * 7) % 101
        tsdb.store.add_batch(key, ts, vals, True)


def run_q(tsdb, m, start=BASE, end=BASE + 6000):
    q = TSQuery(start=str(start), end=str(end),
                queries=[parse_m_subquery(m)])
    q.validate()
    runner = tsdb.new_query_runner()
    out = [r.to_json() for r in runner.run(q)]
    return out, dict(runner.exec_stats)


class TestBitIdentity:
    def test_cold_warm_and_recompute_bitwise_on_floats(self):
        tsdb = make_tsdb()
        feed_float(tsdb)
        m = "sum:60s-sum:sys.f{host=*}"
        cold, s_cold = run_q(tsdb, m)       # populates (min_repeats=1)
        warm, s_warm = run_q(tsdb, m)
        warm2, s_warm2 = run_q(tsdb, m)
        assert s_cold.get("aggCacheComputedWindows", 0) > 0
        assert s_warm.get("aggCacheHitWindows", 0) > 0
        assert cold == warm == warm2        # float dps, bit-for-bit
        # drop everything and recompute from the store: the fresh
        # per-block programs must reproduce the cached bits exactly
        tsdb.agg_cache.invalidate()
        recomputed, s_re = run_q(tsdb, m)
        assert s_re.get("aggCacheComputedWindows", 0) > 0
        assert recomputed == cold

    @pytest.mark.parametrize("m", [
        "sum:60s-sum:sys.i{host=*}",
        "sum:60s-count:sys.i",
        "max:60s-max:sys.i{host=*}",
        "min:60s-min:sys.i",
        "sum:60s-last:sys.i{host=*}",
        "sum:rate:60s-sum:sys.i{host=*}",
    ])
    def test_enabled_equals_disabled_bitwise_on_ints(self, m):
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on)
        feed_int(off)
        run_q(on, m)                         # populate
        warm, s = run_q(on, m)
        plain, _ = run_q(off, m)
        assert s.get("aggCacheHitWindows", 0) > 0
        assert warm == plain                 # integer sums: both exact

    def test_unaligned_and_sliding_ranges(self):
        """Partial edge windows recompute per query; interior blocks
        reuse across overlapping (sliding) ranges — and every answer
        matches a cache-disabled control on integer data."""
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on)
        feed_int(off)
        m = "sum:60s-sum:sys.i{host=*}"
        windows = [(BASE + 7, BASE + 5003),       # unaligned both ends
                   (BASE + 607, BASE + 5603),     # slid by 10 windows
                   (BASE + 1207, BASE + 5999)]
        run_q(on, m, *windows[0])                 # populate family
        for start, end in windows:
            got, stats = run_q(on, m, start, end)
            want, _ = run_q(off, m, start, end)
            assert got == want, (start, end)
        assert stats.get("aggCacheHitWindows", 0) > 0


class TestInvalidation:
    def test_acked_write_never_served_stale(self):
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on)
        feed_int(off)
        m = "sum:60s-sum:sys.i{host=*}"
        for _ in range(3):
            run_q(on, m)                     # fully warm
        # land a write in the MIDDLE of the cached range on both
        for t in (on, off):
            t.add_point("sys.i", BASE + 3000, 424242, {"host": "a"})
        got, stats = run_q(on, m)
        want, _ = run_q(off, m)
        assert got == want
        # only the dirtied block recomputed — history still serves
        assert stats.get("aggCacheHitWindows", 0) > 0
        assert stats.get("aggCacheComputedWindows", 0) > 0

    def test_delete_and_new_series_invalidate(self):
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on)
        feed_int(off)
        m = "sum:60s-sum:sys.i{host=*}"
        for _ in range(2):
            run_q(on, m)
        # a series born after the blocks were built must join the
        # answer (the block entries lack its row -> recompute)
        for t in (on, off):
            for i in range(0, 6000, 10):
                t.add_point("sys.i", BASE + i, 5, {"host": "c"})
        got, _ = run_q(on, m)
        want, _ = run_q(off, m)
        assert got == want
        # delete the series again: answers must drop it immediately
        for t in (on, off):
            key = t._series_key("sys.i", {"host": "c"}, create=False)
            t.store.delete_series(key)
        got, _ = run_q(on, m)
        want, _ = run_q(off, m)
        assert got == want

    def test_mark_ring_overflow_invalidates_conservatively(self):
        """When the per-(store, metric) mark ring overflows, the floor
        generation rises and entries older than the evicted marks are
        unconditionally invalid — the bound can hide history, never
        serve stale."""
        from opentsdb_tpu.storage.agg_cache import (AggregateCache,
                                                    _Block, _MARK_RING)
        cache = AggregateCache(Config({}))
        store = object()
        entry = _Block(store=store, metric=1, rows={}, val=np.zeros(
            (1, 8)), mask=np.zeros((1, 8), bool), gen=0,
            lo_ms=0, hi_ms=7999)
        with cache._lock:
            assert cache._valid_locked(entry)
        for i in range(_MARK_RING + 50):
            # distinct non-overlapping ranges far from the entry; a
            # plan snapshot between marks defeats coalescing
            with cache._lock:
                cache._planned_gen = cache._gen
            cache.invalidate(store=store, metric=1,
                             lo_ms=10_000_000 + i * 10,
                             hi_ms=10_000_000 + i * 10 + 5)
        with cache._lock:
            assert not cache._valid_locked(entry)

    def test_gutting_the_agg_invalidator_fails_lint(self, tmp_path):
        """ISSUE 9 acceptance: the ingest-side invalidation is a
        checked contract — deleting the backing-store drop inside
        `AggregateCache.invalidate` must re-fire the cache-coherence
        analyzer (cache-invalidator-gutted)."""
        import sys
        sys.path.insert(0, REPO)
        from tools.lint import cache_coherence
        from tools.lint.core import LintContext
        from tools.lint.run import run_lint
        dst = tmp_path / "opentsdb_tpu"
        shutil.copytree(os.path.join(REPO, "opentsdb_tpu"), dst)
        mod = dst / "storage" / "agg_cache.py"
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
                   and "agg-blocks" in f.message for f in findings), (
            "gutting the agg-cache invalidator went undetected:\n"
            + "\n".join(f.render() for f in findings))


class TestPolicy:
    def test_min_repeats_gates_materialization(self):
        tsdb = make_tsdb(**{"tsd.query.cache.min_repeats": 3})
        feed_int(tsdb)
        m = "sum:60s-sum:sys.i{host=*}"
        run_q(tsdb, m)
        run_q(tsdb, m)
        assert tsdb.agg_cache.collect_stats()[
            "tsd.query.agg_cache.populated"] == 0
        run_q(tsdb, m)                       # third occurrence: populate
        assert tsdb.agg_cache.collect_stats()[
            "tsd.query.agg_cache.populated"] > 0

    def test_dispatch_floor_plans_honestly_refuse(self):
        """With the real per-dispatch overhead charged, a tiny plan's
        per-hit saving goes non-positive and the costmodel refuses to
        materialize — the cache must not tax workloads it cannot
        help."""
        tsdb = make_tsdb(**{
            "tsd.query.cache.dispatch_overhead_us": 100000})
        feed_int(tsdb, n=600)
        m = "sum:60s-sum:sys.i{host=*}"
        for _ in range(3):
            _, stats = run_q(tsdb, m, BASE, BASE + 600)
        assert "aggCacheHitWindows" not in stats
        assert tsdb.agg_cache.collect_stats()[
            "tsd.query.agg_cache.populated"] == 0

    def test_eviction_under_byte_budget(self):
        tsdb = make_tsdb(**{"tsd.query.cache.mb": 1})
        # 64-series x 8-window blocks are ~4.6KB each; 24 metrics x 12
        # full blocks ~= 1.3MB, past the 1MB budget
        for g in range(24):
            metric = "evict.m%d" % g
            for host in range(64):
                key = tsdb._series_key(metric, {"h": str(host)},
                                       create=True)
                ts = (np.arange(2000, dtype=np.int64) + BASE) * 1000
                tsdb.store.add_batch(key, ts,
                                     np.arange(2000, dtype=np.int64),
                                     True)
            run_q(tsdb, "sum:20s-sum:%s{h=*}" % metric,
                  BASE, BASE + 2000)
        stats = tsdb.agg_cache.collect_stats()
        assert stats["tsd.query.agg_cache.bytes"] <= 2 ** 20
        assert stats["tsd.query.agg_cache.evictions"] > 0
        # evicted families still answer correctly (recompute)
        off = make_tsdb(**{"tsd.query.cache.enable": False})
        for host in range(64):
            key = off._series_key("evict.m0", {"h": str(host)},
                                  create=True)
            ts = (np.arange(2000, dtype=np.int64) + BASE) * 1000
            off.store.add_batch(key, ts,
                                np.arange(2000, dtype=np.int64), True)
        got, _ = run_q(tsdb, "sum:20s-sum:evict.m0{h=*}",
                       BASE, BASE + 2000)
        want, _ = run_q(off, "sum:20s-sum:evict.m0{h=*}",
                        BASE, BASE + 2000)
        assert got == want

    def test_device_tier_promotes_hot_blocks(self):
        tsdb = make_tsdb(**{"tsd.query.cache.promote_hits": 2})
        feed_int(tsdb)
        m = "sum:60s-sum:sys.i{host=*}"
        results = [run_q(tsdb, m)[0] for _ in range(3)]
        # served-enough blocks queue for the maintenance thread; the
        # upload is never paid on the query path (stand in for the
        # maintenance tick here)
        assert tsdb.agg_cache.promote_pending(max_uploads=64) > 0
        stats = tsdb.agg_cache.collect_stats()
        assert stats["tsd.query.agg_cache.device_bytes"] > 0
        # device-tier replays are still bit-identical
        got, s = run_q(tsdb, m)
        assert got == results[1] == results[2]
        assert s.get("aggCacheHitWindows", 0) > 0

    def test_consulted_but_recomputed_plans_never_promote(self):
        """Review pin: a plan that consults the cache but ends in
        recompute must not accrue serve-hits — never-serving blocks
        must not earn device mirrors."""
        tsdb = make_tsdb(**{"tsd.query.cache.promote_hits": 1})
        feed_int(tsdb)
        m = "sum:60s-sum:sys.i{host=*}"
        run_q(tsdb, m)                       # populate (serves: cold)
        # force every later plan to refuse via an absurd overhead
        tsdb.agg_cache.dispatch_overhead_s = 10.0
        for t in (tsdb,):
            t.add_point("sys.i", BASE + 3000, 1, {"host": "a"})
        for _ in range(3):
            _, s = run_q(tsdb, m)
        assert "aggCacheHitWindows" not in s   # plans recomputed
        assert tsdb.agg_cache.promote_pending(max_uploads=64) == 0

    def test_admission_estimate_prices_the_rewritten_plan(self):
        """ISSUE 9: estimate_plan_cost_ms must price the rewritten
        plan — a warm cache shrinks the predicted cost."""
        from opentsdb_tpu.tsd.admission import estimate_plan_cost_ms
        tsdb = make_tsdb()
        feed_int(tsdb)

        def parsed():
            q = TSQuery(start=str(BASE), end=str(BASE + 6000),
                        queries=[parse_m_subquery(
                            "sum:60s-sum:sys.i{host=*}")])
            q.validate()
            return q
        cold = estimate_plan_cost_ms(tsdb, parsed())
        run_q(tsdb, "sum:60s-sum:sys.i{host=*}")
        run_q(tsdb, "sum:60s-sum:sys.i{host=*}")
        warm = estimate_plan_cost_ms(tsdb, parsed())
        assert cold > 0
        assert warm < cold


class TestDegradedQueries:
    """ISSUE 9 small fix: the degradation ladder (PR 8) mutates the
    downsample spec in place — the cache must key on the MUTATED spec,
    and a truncated degraded run must never pollute the full-range
    answer."""

    def _query(self, start=BASE, end=BASE + 6000):
        q = TSQuery(start=str(start), end=str(end),
                    queries=[parse_m_subquery(
                        "sum:60s-sum:sys.i{host=*}")])
        q.validate()
        return q

    def test_coarsened_spec_is_its_own_family(self):
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on)
        feed_int(off)
        # the ladder's rung-1 mutation: interval x2, string in lockstep
        for _ in range(3):
            q = self._query()
            sub = q.queries[0]
            sub.downsample_spec.interval_ms *= 2
            sub.downsample = "120000ms-sum"
            out = [r.to_json() for r in on.new_query_runner().run(q)]
        # coarsened blocks are under the 120s family; the 60s query
        # must not hit them — and must answer exactly
        got, stats = run_q(on, "sum:60s-sum:sys.i{host=*}")
        want, _ = run_q(off, "sum:60s-sum:sys.i{host=*}")
        assert got == want
        assert "aggCacheHitWindows" not in stats    # first 60s sight
        # and the coarsened family answers exactly too
        qq = self._query()
        qq.queries[0].downsample_spec.interval_ms *= 2
        qq.queries[0].downsample = "120000ms-sum"
        got2 = [r.to_json() for r in on.new_query_runner().run(qq)]
        assert got2 == out

    def test_truncated_run_never_pollutes_the_full_range(self):
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on)
        feed_int(off)
        # the ladder's rung-2 mutation: range truncated toward now
        for _ in range(3):
            q = self._query(start=BASE + 3000)
            [r.to_json() for r in on.new_query_runner().run(q)]
        got, _ = run_q(on, "sum:60s-sum:sys.i{host=*}")
        want, _ = run_q(off, "sum:60s-sum:sys.i{host=*}")
        assert got == want      # full range: no truncated leftovers


class TestConcurrency:
    def test_ingest_vs_cached_query_race(self):
        """Concurrent writers against warm cached queries (TSDBSAN
        verifies the lock discipline when the sanitized subset runs
        this file): after the dust settles, the final answer must
        equal a cache-disabled control ingested identically — no
        stale window survives an acked append."""
        on, off = make_tsdb(), make_tsdb(**{
            "tsd.query.cache.enable": False})
        feed_int(on, n=4000)
        feed_int(off, n=4000)
        m = "sum:60s-sum:sys.i{host=*}"
        for _ in range(2):
            run_q(on, m, BASE, BASE + 4000)
        errors = []
        stop = threading.Event()

        def ingest(host):
            try:
                i = 0
                while not stop.is_set() and i < 400:
                    for t in (on, off):
                        t.add_point("sys.i", BASE + (i * 13) % 4000,
                                    i, {"host": host})
                    i += 1
            except Exception as e:  # pragma: no cover - fail the test
                errors.append(e)

        def reader():
            try:
                for _ in range(30):
                    run_q(on, m, BASE, BASE + 4000)
            except Exception as e:  # pragma: no cover - fail the test
                errors.append(e)

        threads = [threading.Thread(target=ingest, args=("a",)),
                   threading.Thread(target=ingest, args=("b",)),
                   threading.Thread(target=reader),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stop.set()
        assert not errors, errors
        got, _ = run_q(on, m, BASE, BASE + 4000)
        want, _ = run_q(off, m, BASE, BASE + 4000)
        assert got == want


class TestMetrics:
    def test_tier_labeled_families_scrapeable(self):
        """ISSUE 9 satellite: DeviceSeriesCache and the agg cache
        share the tsd.query.cache.* families, tier-labeled, on the
        prometheus registry."""
        from opentsdb_tpu.obs.registry import REGISTRY
        tsdb = make_tsdb()
        feed_int(tsdb)
        m = "sum:60s-sum:sys.i{host=*}"
        for _ in range(3):
            run_q(tsdb, m)
        text = REGISTRY.prometheus_text()
        assert 'tsd_query_cache_hits_total{tier="agg_host"' in text
        assert 'tier="device_series"' in text
        assert 'tsd_query_cache_bytes{tier="agg_host"' in text
        # the stats walk carries the agg-cache records too
        stats = tsdb.collect_stats()
        assert stats["tsd.query.agg_cache.rewrites"] > 0


@pytest.mark.slow
def test_cache_hit_speedup_at_scale():
    """ISSUE 9 acceptance: >= 5x wall reduction on cache-hit queries
    vs cold at a compute-dominated shape — the aligned dashboard
    repeat (full block coverage), the same measurement the committed
    BENCH_AGG_CACHE.json artifact records via
    tools/bench_agg_cache.py (which also reports the fetch stage's
    ms)."""
    import statistics
    import time
    tsdb = make_tsdb(**{"tsd.query.cache.min_repeats": 1,
                        "tsd.query.cache.block_windows": 32})
    rng = np.random.default_rng(5)
    t0_s = 84813 * 16000        # aligned to the 32x500s block grid
    points = 400_000
    for host in range(8):
        key = tsdb._series_key("bench.m", {"h": str(host)}, create=True)
        ts = (np.arange(points, dtype=np.int64) + t0_s) * 1000
        tsdb.store.add_batch(key, ts, rng.standard_normal(points),
                             False)
    m = "sum:500s-sum:bench.m{h=*}"
    end = t0_s + (points // 16000) * 16000
    run_q(tsdb, m, t0_s, end)          # jit warmup (not what we time)

    def timed():
        t0 = time.perf_counter()
        out, _ = run_q(tsdb, m, t0_s, end)
        return time.perf_counter() - t0, out

    colds, warms = [], []
    for _ in range(3):
        tsdb.agg_cache.invalidate()
        colds.append(timed())          # repopulates
        warms.append(timed())
        warms.append(timed())
    cold_s = statistics.median(c[0] for c in colds)
    warm_s = statistics.median(w[0] for w in warms)
    assert all(w[1] == colds[0][1] for w in warms)   # bit-identical
    assert cold_s / warm_s >= 5.0, (cold_s, warm_s)

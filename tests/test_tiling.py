"""Out-of-core tiled execution (ops/tiling.py + storage/spill.py).

ISSUE 10 acceptance: a group-by query whose [S, W] state exceeds
``tsd.query.streaming.state_mb`` — refused 413 at HEAD — answers 200
through the series-tiled spill-backed executor, numerically pinned
against a forced-resident run of the same plan (bitwise on
integer-valued data), with the tiling decision visible in its trace
span; the costmodel's new spill terms obey the linearity contract; and
tiled executions are deliberately excluded from the calibration ring
(the PR 9 rewrite precedent).

Mesh stays DISABLED in every query test here: tiling is the
single-device answer to an over-budget grid (a mesh plan divides the
grid budget by its chips instead).
"""

import json

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.utils.config import Config

BASE_S = 1_356_998_400
SPAN_S = 40_960          # 4096 windows at 10s


def _mk_tsdb(state_mb, spill="true", extra=None, seed=7, hosts=24,
             pts=60, metric="til.m", float_vals=False):
    cfg = {
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": "false",          # single-device route
        "tsd.query.device_cache.enable": "false",
        "tsd.query.cache.enable": "false",
        "tsd.query.streaming.point_threshold": "10",
        "tsd.query.streaming.chunk_points": "20000",
        "tsd.query.spill.enable": spill,
        "tsd.query.streaming.state_mb": str(state_mb),
    }
    cfg.update(extra or {})
    t = TSDB(Config(cfg))
    rng = np.random.default_rng(seed)
    for h in range(hosts):
        times = np.sort(rng.choice(SPAN_S, size=pts, replace=False))
        for i, ts in enumerate(times):
            v = (float(i) * 0.37 + h * 0.13 if float_vals
                 else float((i * 7 + h * 13) % 101))
            t.add_point(metric, BASE_S + int(ts), v,
                        {"h": "h%d" % h, "g": "g%d" % (h % 4)})
    return t


def _run(tsdb, m, start=BASE_S, end=BASE_S + SPAN_S):
    q = TSQuery(start=str(start), end=str(end),
                queries=[parse_m_subquery(m)])
    q.validate()
    runner = tsdb.new_query_runner()
    return runner.run(q), runner.exec_stats


class TestTiledExecution:
    """The acceptance pin: over-limit plans answer through tiling and
    match a forced-resident run of the same plan."""

    def test_over_limit_groupby_answers_and_matches_resident_bitwise(self):
        # 24 series x 4096 windows x 16B (sum lanes) ~ 1.5MB > 1MB:
        # refused 413 at HEAD, tiled now (3 tiles x 4 stripes)
        tiled = _mk_tsdb(1)
        resident = _mk_tsdb(6144)
        a, sa = _run(tiled, "sum:10s-sum:til.m{g=*}")
        b, sb = _run(resident, "sum:10s-sum:til.m{g=*}")
        assert sa.get("tiledExecution") == 1.0, sa
        assert sa.get("spillBytes", 0) > 0
        assert "tiledExecution" not in sb
        assert len(a) == len(b) == 4
        for ra, rb in zip(a, b):
            assert ra.tags == rb.tags
            # integer-valued data: f64 sums are exact -> bitwise
            assert ra.dps == rb.dps

    @pytest.mark.parametrize("m", [
        "sum:rate:10s-sum:til.m{g=*}",   # rate crosses stripe bounds
        "avg:10s-dev:til.m{g=*}",        # Chan-merge lanes + LERP holes
        "max:10s-max:til.m{g=*}",        # extreme lanes
    ])
    def test_modes_match_resident_within_float_contract(self, m):
        """Differing chunk boundaries (n_chunk depends on the batch's
        row count) carry the streamed path's pre-existing reassociation
        latitude — measured ~1e-12 worst on rate+sum here, far inside
        the house 1e-9 streaming contract.  The tiling machinery itself
        adds NOTHING: see the equal-chunking test below, which pins
        bitwise."""
        tiled = _mk_tsdb(1, float_vals=True)
        resident = _mk_tsdb(6144, float_vals=True)
        a, sa = _run(tiled, m)
        b, _sb = _run(resident, m)
        assert sa.get("tiledExecution") == 1.0, (m, sa)
        assert len(a) == len(b)
        for ra, rb in zip(a, b):
            assert ra.tags == rb.tags
            da, db = dict(ra.dps), dict(rb.dps)
            assert set(da) == set(db)
            for k in da:
                np.testing.assert_allclose(da[k], db[k], rtol=1e-12,
                                           atol=1e-12)

    @pytest.mark.parametrize("m", [
        "sum:rate:10s-sum:til.m{g=*}",
        "avg:10s-avg:til.m{g=*}",
    ])
    def test_equal_chunking_is_bitwise_on_floats(self, m):
        """The ISSUE's <=1e-15 float pin, enforced at its strongest:
        with chunk boundaries pinned equal (chunk_points=1000 puts both
        the 24-row resident batch and the 9-row tiles at the 1024-point
        chunk floor), the series-tiled spill-and-replay execution is
        BITWISE identical to the forced-resident run — rate, LERP
        interpolation, and the window-striped group reduce included."""
        extra = {"tsd.query.streaming.chunk_points": "1000"}
        a, sa = _run(_mk_tsdb(1, float_vals=True, extra=extra), m)
        b, _ = _run(_mk_tsdb(6144, float_vals=True, extra=extra), m)
        assert sa.get("tiledExecution") == 1.0, (m, sa)
        assert len(a) == len(b)
        for ra, rb in zip(a, b):
            assert ra.tags == rb.tags
            assert ra.dps == rb.dps

    def test_refused_structured_413_when_spill_disabled(self):
        from opentsdb_tpu.query.limits import QueryException
        t = _mk_tsdb(1, spill="false")
        with pytest.raises(QueryException) as exc:
            _run(t, "sum:10s-sum:til.m{g=*}")
        assert exc.value.status == 413
        d = exc.value.details
        assert d and d["limitKey"] == "tsd.query.streaming.state_mb"
        assert d["limitMb"] == 1 and d["gridMb"] >= 1
        assert "spill" in d["suggestion"]

    def test_tiling_decision_annotated_on_pipeline_span(self):
        from opentsdb_tpu.tsd.http import HttpRequest
        from opentsdb_tpu.tsd.rpc_manager import RpcManager
        t = _mk_tsdb(1)
        manager = RpcManager(t)
        r = manager.handle_http(HttpRequest(
            method="GET",
            uri="/api/query?start=%d&end=%d&m=sum:10s-sum:til.m"
                "{g=*}&show_stats" % (BASE_S, BASE_S + SPAN_S),
            headers={}, body=b""), remote="127.0.0.1:50").response
        assert r.status == 200
        payload = json.loads(r.body)
        summary = [e for e in payload if "statsSummary" in e][0]
        tree = summary["statsSummary"]["trace"]

        def find(node, name):
            out = [node] if node.get("name") == name else []
            for c in node.get("spans", []):
                out.extend(find(c, name))
            return out

        pipelines = find(tree, "pipeline")
        tiled = [p for p in pipelines if "tiling" in p.get("tags", {})]
        assert tiled, "pipeline span must carry the tiling annotation"
        tag = tiled[0]["tags"]["tiling"]
        assert tag["tiles"] >= 2 and tag["spillBytes"] > 0

    def test_tiled_pipeline_span_carries_no_monolithic_decisions(self):
        """PR 9 precedent, pinned: the costmodel's decisions describe
        the monolithic program, which a tiled execution does not run,
        so its pipeline span carries the tiling tag and no costmodel
        tag."""
        from opentsdb_tpu.obs import trace as obs_trace
        t = _mk_tsdb(1)
        tr = obs_trace.Trace("tiled")
        obs_trace.activate(tr)
        try:
            _, st = _run(t, "sum:10s-sum:til.m{g=*}")
        finally:
            obs_trace.deactivate()
            tr.finish()
        assert st.get("tiledExecution") == 1.0
        def walk(sp):
            yield sp
            for child in sp.children:
                yield from walk(child)
        # a sub-query's pipeline span sits under its subquery span
        pipes = [sp for sp in walk(tr.root) if sp.name == "pipeline"]
        assert pipes and all("tiling" in sp.tags for sp in pipes)
        assert not any("costmodel" in sp.tags for sp in pipes), \
            "a tiled execution must not carry the monolithic decisions"

    def test_spill_write_fault_surfaces_as_retryable_and_heals(self):
        from opentsdb_tpu.query.limits import QueryException
        from opentsdb_tpu.utils import faults
        t = _mk_tsdb(1, extra={"tsd.query.spill.host_mb": "1"})
        # the whole partial grid is ~24*4096*10B ~ 0.98MB; host_mb=1
        # with stripes landing one by one still overflows mid-query
        faults.install([{"site": "spill.write", "kind": "error",
                         "times": 1}])
        try:
            pool = t.spill_pool
            with pytest.raises(QueryException) as exc:
                _run(t, "avg:10s-avg:til.m{g=*}")
            assert exc.value.status == 503
            # per-query cleanup: nothing left pooled
            st = pool.stats()
            assert st["host_entries"] == 0 and st["disk_entries"] == 0
        finally:
            faults.FAULTS.clear()
        # fault exhausted: the very next attempt serves and matches
        a, sa = _run(t, "avg:10s-avg:til.m{g=*}")
        assert sa.get("tiledExecution") == 1.0
        b, _ = _run(_mk_tsdb(6144), "avg:10s-avg:til.m{g=*}")
        assert [r.dps for r in a] == [r.dps for r in b]


class TestStateBudgetTransitions:
    """Satellite: state_mb boundary behavior — just-under streams,
    just-over tiles, 0 disables the guard entirely."""

    def test_just_under_streams_just_over_tiles_zero_disables(self):
        # streaming estimate: 24 series x 4096 windows x 16B = 1.5MB
        under, _ = _run(_mk_tsdb(2), "sum:10s-sum:til.m{g=*}")
        t_over = _mk_tsdb(1)
        over, st_over = _run(t_over, "sum:10s-sum:til.m{g=*}")
        zero, st_zero = _run(_mk_tsdb(0), "sum:10s-sum:til.m{g=*}")
        assert st_over.get("tiledExecution") == 1.0
        assert "tiledExecution" not in st_zero
        assert st_zero.get("streamedChunks", 0) >= 1
        assert [r.dps for r in under] == [r.dps for r in over] \
            == [r.dps for r in zero]

    def test_all_three_guard_sites_share_the_structured_shape(self):
        from opentsdb_tpu.query.limits import grid_budget
        for kind in ("streaming", "grid", "histogram"):
            gbd = grid_budget(kind, 4, 5 * 2**20, 100, 1000)
            assert gbd.over
            exc = gbd.exception()
            assert exc.status == 413
            assert exc.details["limitKey"] \
                == "tsd.query.streaming.state_mb"
            assert exc.details["gridMb"] == 5
            assert exc.details["kind"] == kind
            assert "tsd.query.streaming.state_mb" in str(exc)
        assert not grid_budget("grid", 0, 10**12, 1, 1).over
        with pytest.raises(ValueError):
            grid_budget("nope", 1, 1, 1, 1)


class TestCostmodelTiled:
    """New COST_TERMS obey the linearity contract."""

    def test_terms_identical_across_platforms(self):
        from opentsdb_tpu.ops import costmodel as cm
        assert tuple(sorted(cm.DEFAULT_COSTS["cpu"])) == cm.COST_TERMS
        assert tuple(sorted(cm.DEFAULT_COSTS["tpu"])) == cm.COST_TERMS
        for term in ("spill_write_mb", "spill_read_mb", "tile_dispatch"):
            assert term in cm.COST_TERMS

    def test_predict_tiled_is_dot_of_features_and_costs(self):
        from opentsdb_tpu.ops import costmodel as cm
        args = dict(s=512, w=65536, g=16, n_tiles=7, n_stripes=5,
                    spill_bytes=3 * 2**30, dispatches=40)
        for platform in ("cpu", "tpu"):
            feats = cm.features_tiled(
                args["s"], args["w"], args["g"], args["n_tiles"],
                args["n_stripes"], args["spill_bytes"],
                args["dispatches"])
            want = sum(u * cm.costs(platform)[t]
                       for t, u in feats.items())
            got = cm.predict_tiled(args["s"], args["w"], args["g"],
                                   args["n_tiles"], args["n_stripes"],
                                   args["spill_bytes"],
                                   args["dispatches"], platform)
            assert got == want
            assert set(feats) <= set(cm.COST_TERMS)

    def test_admission_prices_tiled_plans_instead_of_zero(self):
        """The gate must see a finite, tiled-inflated estimate for an
        over-limit plan, not shed it as unpredictable."""
        from opentsdb_tpu.tsd.admission import estimate_plan_cost_ms
        t = _mk_tsdb(1)
        q = TSQuery(start=str(BASE_S), end=str(BASE_S + SPAN_S),
                    queries=[parse_m_subquery("sum:10s-sum:til.m{g=*}")])
        q.validate()
        with_tiling = estimate_plan_cost_ms(t, q)
        t2 = _mk_tsdb(1, spill="false")
        without = estimate_plan_cost_ms(t2, q)
        assert with_tiling > without > 0.0


class TestSpillPool:
    def _pool(self, tmp_path, host_mb=1, disk_mb=8):
        from opentsdb_tpu.storage.spill import SpillPool
        return SpillPool(host_mb * 2**20, disk_mb * 2**20,
                         directory=str(tmp_path / "spill"))

    def test_host_roundtrip_and_column_slices(self, tmp_path):
        pool = self._pool(tmp_path)
        v = np.arange(64, dtype=np.float64).reshape(4, 16)
        m = v % 3 == 0
        key = pool.put((v, m))
        gv, gm = pool.get(key)
        np.testing.assert_array_equal(gv, v)
        np.testing.assert_array_equal(gm, m)
        sv, sm = pool.get(key, 4, 12)
        np.testing.assert_array_equal(sv, v[:, 4:12])
        np.testing.assert_array_equal(sm, m[:, 4:12])
        pool.free(key)
        assert pool.stats()["host_entries"] == 0
        with pytest.raises(KeyError):
            pool.get(key)
        pool.close()

    def test_overflow_demotes_newest_to_disk_and_reads_back(self,
                                                            tmp_path):
        """Newest-first demotion: the stripe-major replay reads the
        OLDEST entries first, so they are the ones to keep in RAM."""
        from opentsdb_tpu.storage.spill import SpillPool
        pool = SpillPool(3000, 10 * 2**20,
                         directory=str(tmp_path / "spill"))
        a = np.full((4, 64), 1.5)          # 2048B
        b = np.full((4, 64), 2.5)
        ka = pool.put((a,))
        kb = pool.put((b,))                # over 3000B -> b demotes
        st = pool.stats()
        assert st["disk_entries"] == 1 and st["host_entries"] == 1
        # the older entry stayed in the host ring, the newer hit disk
        np.testing.assert_array_equal(pool.get(ka)[0], a)
        np.testing.assert_array_equal(pool.get(kb)[0], b)
        np.testing.assert_array_equal(pool.get(kb, 8, 16)[0],
                                      b[:, 8:16])
        assert pool.stats()["host_entries"] == 1
        pool.close()
        assert pool.stats() == {"host_bytes": 0, "disk_bytes": 0,
                                "host_entries": 0, "disk_entries": 0}
        assert not list((tmp_path / "spill").glob("*.npy"))

    def test_capacity_refusal_and_bounded_bytes(self, tmp_path):
        from opentsdb_tpu.storage.spill import (SpillCapacityError,
                                                SpillPool)
        pool = SpillPool(2048, 4096, directory=str(tmp_path / "spill"))
        with pytest.raises(SpillCapacityError):
            pool.put((np.zeros(4096, np.float64),))   # 32KB > both
        keys = [pool.put((np.zeros(128, np.float64),))
                for _ in range(6)]
        st = pool.stats()
        assert st["host_bytes"] <= 2048
        assert st["disk_bytes"] <= 4096
        pool.release(keys)
        pool.close()

    def test_disk_full_fault_raises_and_keeps_pool_consistent(
            self, tmp_path):
        from opentsdb_tpu.storage.spill import (SpillPool,
                                                SpillWriteError)
        from opentsdb_tpu.utils import faults
        pool = SpillPool(2048, 4096, directory=str(tmp_path / "spill"))
        k0 = pool.put((np.zeros(128, np.float64),))   # 1024B resident
        faults.install([{"site": "spill.write", "kind": "error",
                         "times": 1}])
        try:
            with pytest.raises(SpillWriteError):
                pool.put((np.zeros(256, np.float64),))  # forces demote
        finally:
            faults.FAULTS.clear()
        # k0 survived the failed demotion and still serves
        assert pool.get(k0)[0].shape == (128,)
        st = pool.stats()
        assert st["host_entries"] == 1 and st["disk_bytes"] == 0
        # healed: the same put succeeds once the fault is exhausted
        k2 = pool.put((np.zeros(256, np.float64),))
        assert pool.get(k2)[0].shape == (256,)
        pool.close()

"""Mesh-served /api/query equals the single-device answer.

VERDICT round-1 item 2: the sharded kernels must serve real queries, not
sit beside them.  These tests drive the full planner (and one HTTP handler
pass) on the virtual 8-device CPU mesh and compare against the same query
with the mesh disabled — covering moment-decomposable aggregators (psum
path), order/rank aggregators (gather-to-owner path), rate, fill policies,
and a wide group-by.

Values compare within 1e-9 relative: `psum` adds per-chip partials in a
different order than the single-device segment reduction, so the last ulp
may legitimately differ (floating-point reassociation).  Structure —
result count, tags, aggregateTags, timestamp keys, NaN placement — must be
identical.
"""

import json
import math

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.utils.config import Config

START = 1356998400  # seconds


def _mk_tsdb(mesh: bool, min_series: int = 0,
             device_cache: bool = True) -> TSDB:
    return TSDB(Config({
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": mesh,
        "tsd.query.mesh.min_series": min_series,
        "tsd.query.device_cache.enable": device_cache,
    }))


def _ingest(tsdb: TSDB, n_hosts: int = 12, n_points: int = 40,
            n_dcs: int = 3) -> None:
    rng = np.random.default_rng(7)
    for h in range(n_hosts):
        tags = {"host": "web%02d" % h, "dc": "dc%d" % (h % n_dcs)}
        base = START + int(rng.integers(0, 5))
        for k in range(n_points):
            ts = base + k * 10 + int(rng.integers(0, 3))
            tsdb.add_point("sys.cpu.user", ts,
                           float(rng.normal(50.0 + h, 10.0)), tags)


def _run(tsdb: TSDB, m: str, start=START, end=START + 600):
    q = TSQuery(start=str(start), end=str(end),
                queries=[parse_m_subquery(m)])
    q.validate()
    return [r.to_json() for r in tsdb.new_query_runner().run(q)]


@pytest.fixture(scope="module")
def pair():
    meshed = _mk_tsdb(True)
    plain = _mk_tsdb(False)
    _ingest(meshed)
    _ingest(plain)
    assert meshed.query_mesh() is not None, "virtual mesh missing"
    assert plain.query_mesh() is None
    return meshed, plain


def assert_equivalent(got: list, want: list) -> None:
    """Same structure everywhere; dps values equal within reassociation."""
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert set(g) == set(w)
        for key in w:
            if key != "dps":
                assert g[key] == w[key], key
        assert set(g["dps"]) == set(w["dps"])
        for ts_key, wv in w["dps"].items():
            gv = g["dps"][ts_key]
            if isinstance(wv, float) and math.isnan(wv):
                assert isinstance(gv, float) and math.isnan(gv), ts_key
            elif wv is None:
                assert gv is None, ts_key
            else:
                assert math.isclose(gv, wv, rel_tol=1e-9, abs_tol=1e-9), \
                    (ts_key, gv, wv)


MOMENT_QUERIES = [
    "sum:1m-avg:sys.cpu.user{dc=*}",
    "avg:30s-sum:sys.cpu.user{host=*}",
    "max:1m-min:sys.cpu.user{dc=*}",
    "dev:1m-avg:sys.cpu.user",
    "zimsum:1m-count:sys.cpu.user{dc=*}",
    "mimmax:1m-max:sys.cpu.user{dc=*}",
    "count:1m-avg:sys.cpu.user",
    "sum:1m-avg-zero:sys.cpu.user{dc=*}",
    # Phantom-row regression (r3): shard_rows pads S to a device-count
    # multiple; under a fill policy every live window is exposed, so a
    # padded row with an in-range gid would inflate count / drag avg.
    "count:1m-avg-zero:sys.cpu.user{dc=*}",
    "avg:1m-avg-zero:sys.cpu.user{dc=*}",
    "sum:rate:1m-avg:sys.cpu.user{dc=*}",
]

ORDERED_QUERIES = [
    "p95:1m-avg:sys.cpu.user{dc=*}",
    # BASELINE config 4 shape: rate + p99 across shards (VERDICT r1 item 5).
    "p99:rate:1m-avg:sys.cpu.user{dc=*}",
    "median:1m-avg:sys.cpu.user",
    "first:1m-avg:sys.cpu.user{dc=*}",
    "last:1m-avg:sys.cpu.user{dc=*}",
    "mult:2m-avg:sys.cpu.user{dc=literal_or(dc0)}",
    "ep99r7:1m-avg:sys.cpu.user",
]


@pytest.mark.parametrize("m", MOMENT_QUERIES + ORDERED_QUERIES)
def test_mesh_matches_single_device(pair, m):
    meshed, plain = pair
    assert_equivalent(_run(meshed, m), _run(plain, m))


def test_wide_groupby_matches(pair):
    meshed, plain = pair
    got = _run(meshed, "avg:1m-avg:sys.cpu.user{host=*}")
    want = _run(plain, "avg:1m-avg:sys.cpu.user{host=*}")
    assert len(got) == 12
    assert_equivalent(got, want)


def test_none_aggregator_per_series(pair):
    meshed, plain = pair
    got = _run(meshed, "none:1m-avg:sys.cpu.user{host=literal_or(web01)}")
    want = _run(plain, "none:1m-avg:sys.cpu.user{host=literal_or(web01)}")
    assert_equivalent(got, want)


def test_http_handler_served_from_mesh(pair):
    """Drive the HTTP /api/query handler end-to-end on the meshed TSDB."""
    from opentsdb_tpu.tsd.http import HttpRequest
    from opentsdb_tpu.tsd.rpc_manager import RpcManager

    meshed, plain = pair
    uri = ("/api/query?start=%d&end=%d&m=sum:1m-avg:sys.cpu.user%%7Bdc=*%%7D"
           % (START, START + 600))
    bodies = []
    for tsdb in (meshed, plain):
        q = RpcManager(tsdb).handle_http(
            HttpRequest(method="GET", uri=uri, body=b"", headers={}),
            remote="127.0.0.1:55")
        assert q.response.status == 200
        bodies.append(json.loads(q.response.body))
    assert_equivalent(bodies[0], bodies[1])
    assert len(bodies[0]) == 3


def test_mesh_host_path_without_device_cache(pair):
    """The host shard_rows path must stay covered on its own: with the
    device cache off, mesh answers still equal the single-device
    control (pins the _pad_rows phantom-row rule independently of the
    cache, which otherwise serves every warm raw query)."""
    _, plain = pair
    meshed_nocache = _mk_tsdb(True, device_cache=False)
    _ingest(meshed_nocache)
    m = "avg:1m-avg:sys.cpu.user{dc=*}"
    runner = meshed_nocache.new_query_runner()
    q = TSQuery(start=str(START), end=str(START + 600),
                queries=[parse_m_subquery(m)])
    q.validate()
    got = [r.to_json() for r in runner.run(q)]
    assert "deviceCacheHit" not in runner.exec_stats
    assert runner.exec_stats.get("meshDevices", 0) >= 8
    assert_equivalent(got, _run(plain, m))


def test_mesh_serves_from_device_cache(pair):
    """A cache hit under the mesh re-lays the device batch across the
    chips (shard_rows_device) — answers must equal a cache-DISABLED
    meshed control (the host shard_rows path) and the single-device
    control."""
    meshed, plain = pair
    meshed_nocache = _mk_tsdb(True, device_cache=False)
    _ingest(meshed_nocache)
    m = "sum:1m-avg:sys.cpu.user{dc=*}"
    _run(meshed, m)                       # build/warm the cache entry
    runner = meshed.new_query_runner()
    q = TSQuery(start=str(START), end=str(START + 600),
                queries=[parse_m_subquery(m)])
    q.validate()
    warm_res = runner.run(q)
    assert runner.exec_stats.get("deviceCacheHit") == 1.0
    assert runner.exec_stats.get("meshDevices", 0) >= 8
    warm = [r.to_json() for r in warm_res]
    assert_equivalent(warm, _run(meshed_nocache, m))
    assert_equivalent(warm, _run(plain, m))


class TestMatmulGroupReduce:
    """group-reduce form "matmul": the one-hot matmul moments must answer
    exactly like the segment-scatter moments, on and off the mesh, for
    every moment aggregator + movingAverage.  min/max have no matmul
    form: pinned to it they take segment ops and must keep working."""

    QUERIES = MOMENT_QUERIES + [
        "movingAverage3:1m-sum:sys.cpu.user{dc=*}",
        "min:1m-max:sys.cpu.user{dc=*}",     # segment fallback path
    ]

    @pytest.mark.parametrize("m", QUERIES)
    def test_matmul_equals_segment(self, kernel_forms, m):
        t = _mk_tsdb(False)
        _ingest(t)
        kernel_forms(group="matmul")
        got = _run(t, m)
        kernel_forms(group="segment")
        want = _run(t, m)
        assert_equivalent(got, want)

    def test_matmul_on_mesh(self, pair, kernel_forms):
        """Every matmul-form aggregator (incl. dev's second gsum pass and
        the min/max segment fallback) under the real mesh collectives —
        ONE pin and one meshed store for the whole sweep (cache
        clears + recompiles per pin are the expensive part)."""
        meshed, plain = pair
        kernel_forms(group="segment")
        wants = {m: _run(plain, m) for m in self.QUERIES}
        kernel_forms(group="matmul")
        for m in self.QUERIES:
            assert_equivalent(_run(meshed, m), wants[m])


class TestSortedGroupReduce:
    """group-reduce form "sorted" (r4 chip-attribution lever): rows are
    argsort-permuted into contiguous group runs, sums become axis-0
    cumsum-diffs and extremes a segmented reset-scan — no scatter, no
    one-hot.  Must answer exactly like the segment scatter, on and off
    the mesh, for every moment aggregator including the extremes (which,
    unlike matmul, have a native sorted form)."""

    QUERIES = MOMENT_QUERIES + [
        "movingAverage3:1m-sum:sys.cpu.user{dc=*}",
        "min:1m-max:sys.cpu.user{dc=*}",
        "max:1m-min:sys.cpu.user{host=*}",
    ]

    def test_sorted_equals_segment(self, kernel_forms):
        t = _mk_tsdb(False)
        _ingest(t)
        kernel_forms(group="segment")
        wants = {m: _run(t, m) for m in self.QUERIES}
        kernel_forms(group="sorted")
        for m in self.QUERIES:
            assert_equivalent(_run(t, m), wants[m])

    def test_sorted_on_mesh(self, pair, kernel_forms):
        """The sorted machinery runs per-shard inside shard_map (each chip
        sorts its local rows; psum/pmin/pmax combine across chips) — one
        pin for the whole sweep."""
        meshed, plain = pair
        kernel_forms(group="segment")
        wants = {m: _run(plain, m) for m in self.QUERIES}
        kernel_forms(group="sorted")
        for m in self.QUERIES:
            assert_equivalent(_run(meshed, m), wants[m])

    def test_presorted_skips_permute_same_answers(self):
        """rows_sorted=True (the planner's layout guarantee) must answer
        bit-for-bit like the argsort path on already-sorted gid, for
        every fold flavor."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.group_agg import _SortedGroups
        rng = np.random.default_rng(11)
        for s, g in [(8, 3), (33, 5), (128, 100)]:
            gid = jnp.asarray(np.sort(rng.integers(0, g, size=s)))
            x = jnp.asarray(rng.normal(size=(s, 3)))
            a = _SortedGroups(gid, g, s)
            b = _SortedGroups(gid, g, s, presorted=True)
            np.testing.assert_array_equal(np.asarray(a.sum(x)),
                                          np.asarray(b.sum(x)))
            np.testing.assert_array_equal(
                np.asarray(a.extreme(x, True)),
                np.asarray(b.extreme(x, True)))
            np.testing.assert_array_equal(
                np.asarray(a.extreme(x, False)),
                np.asarray(b.extreme(x, False)))

    def test_sorted_sum_magnitude_skew(self, kernel_forms):
        """Cross-group cancellation regression (r4 review): a 1.0-magnitude
        group next to a 1e15-magnitude group must keep 1e-9 relative
        accuracy — the reset-scan form restarts accumulation per group,
        where a cumsum differenced at group bounds would lose the small
        group entirely in the big group's running total."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops import group_agg
        s, w, g = 8, 4, 2
        contrib = np.ones((s, w))
        contrib[:4] = 1e15           # group 0 rows dwarf group 1's
        contrib[4:] = 0.25
        part = np.ones((s, w), bool)
        gid = np.array([0, 0, 0, 0, 1, 1, 1, 1])
        kernel_forms(group="sorted")
        out, cnt = group_agg.moment_group_reduce(
            "sum", jnp.asarray(contrib), jnp.asarray(part),
            jnp.asarray(gid), g)
        np.testing.assert_allclose(np.asarray(out)[0], 4e15, rtol=1e-12)
        np.testing.assert_allclose(np.asarray(out)[1], 1.0, rtol=1e-12)
        np.testing.assert_array_equal(np.asarray(cnt), 4)

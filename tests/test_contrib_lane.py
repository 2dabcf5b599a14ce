"""The contribution lane on the served path: a 120-window grouped query
and a rate query through the /api/query handler bump
`tsd.query.contrib_lane{lane=dense}` over a store with no hole and
`{lane=full}` over the same store with a gap planted in one series, on
every route that answers from one grouped device program; both answer
equal to a plain numpy reference written here.  The rate query also
bumps `tsd.query.rate_lane{lane=shift}` over the hole-free store and
`{lane=scan}` with the gap planted (ops/rate.py's two lanes); the sum
query, which runs no rate, bumps neither."""

import json

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs import METRICS_SCHEMA
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

HOSTS, POINTS, CADENCE_S, INTERVAL_S = 24, 800, 10, 60
REGIONS = 3
BASE = 1451606400
METRIC = "lane.cpu"
START, END = BASE + 60, BASE + 60 + 7200 - 1        # 120 windows of 128
GAP_HOST, GAP = 5, slice(300, 330)                  # windows 50..54

# route -> TSD config beside metric auto-creation; the first request of
# a shape takes the named route, its repeat may take `agg_rewrite`
ROUTES = {
    "batched": {"tsd.query.mesh.enable": "false"},
    "resident": {"tsd.query.mesh.enable": "false",
                 "tsd.query.batch.enable": "false"},
    "mesh": {},             # the suite's 8 virtual devices
}
QUERIES = {"sum": "sum:1m-avg:%s{region=*}",
           "rate": "sum:rate:1m-avg:%s{region=*}"}


def values() -> np.ndarray:
    return np.random.default_rng(28).integers(0, 101, (HOSTS, POINTS))


def build(route: str, gap: bool):
    conf = {"tsd.core.auto_create_metrics": True}
    conf.update(ROUTES[route])
    tsdb = TSDB(Config(conf))
    vals = values()
    ts_ms = (BASE + CADENCE_S * np.arange(POINTS, dtype=np.int64)) * 1000
    for h in range(HOSTS):
        keep = np.ones(POINTS, bool)
        if gap and h == GAP_HOST:
            keep[GAP] = False
        key = tsdb._series_key(
            METRIC, {"hostname": "h%d" % h, "region": "r%d" % (h % REGIONS)},
            create=True)
        tsdb.store.add_batch(key, ts_ms[keep],
                             vals[h, keep].astype(np.float64), True,
                             vals[h, keep])
    return tsdb, RpcManager(tsdb)


def reference(kind: str, gap: bool) -> dict:
    """{region: {timestamp s: value}}: window means, then per series the
    aggregator's substitution for a missing window between two present
    ones (sum: linear in time; a rate: the previous rate), then the sum
    of each region's rows."""
    vals = values().astype(np.float64)
    ts = BASE + CADENCE_S * np.arange(POINTS)
    wts = np.arange(START - START % INTERVAL_S, END + 1, INTERVAL_S)
    rows = []
    for h in range(HOSTS):
        keep = (ts >= START) & (ts <= END)
        if gap and h == GAP_HOST:
            keep[GAP] = False
        win = ts[keep] - ts[keep] % INTERVAL_S
        have = np.isin(wts, win)
        row = np.full(len(wts), np.nan)
        row[have] = [vals[h, keep][win == w].mean() for w in wts[have]]
        if kind == "rate":
            t, v = wts[have], row[have]
            row = np.full(len(wts), np.nan)
            row[np.flatnonzero(have)[1:]] = np.diff(v) / np.diff(t)
            have = ~np.isnan(row)
            last = np.maximum.accumulate(
                np.where(have, np.arange(len(wts)), -1))
            inside = (last >= 0) & (np.arange(len(wts))
                                    <= np.flatnonzero(have)[-1])
            row = np.where(inside, row[np.maximum(last, 0)], np.nan)
        else:
            row = np.where(
                (wts >= wts[have][0]) & (wts <= wts[have][-1]),
                np.interp(wts, wts[have], row[have]), np.nan)
        rows.append(row)
    grid = np.stack(rows)
    out = {}
    for r in range(REGIONS):
        part = grid[r::REGIONS]
        live = ~np.isnan(part).all(axis=0)
        out["r%d" % r] = dict(zip(wts[live].tolist(),
                                  np.nansum(part[:, live], axis=0)))
    return out


def lanes() -> dict:
    c = REGISTRY.counter("tsd.query.contrib_lane")
    r = REGISTRY.counter("tsd.query.rate_lane")
    return dict(
        {lane: c.labels(lane=lane).get() for lane in ("dense", "full")},
        **{lane: r.labels(lane=lane).get() for lane in ("shift", "scan")})


def ask(tsdb, mgr, kind: str):
    uri = "/api/query?start=%d&end=%d&m=%s" % (START, END,
                                               QUERIES[kind] % METRIC)
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri, headers={}),
                        remote="127.0.0.1:9")
    assert q.response.status == 200, q.response.body[:400]
    event = [e for e in tsdb.flightrec.events() if e["kind"] == "plan"][-1]
    return json.loads(q.response.body), event


def served_paths(tsdb) -> list:
    return tsdb.__dict__.setdefault("_test_paths", [])


@pytest.fixture(scope="module", params=sorted(ROUTES))
def route(request):
    return request.param


@pytest.fixture(scope="module", params=[False, True],
                ids=["hole_free", "gap_planted"])
def served(request, route):
    return (route, request.param) + build(route, request.param)


@pytest.mark.parametrize("name", ["tsd.query.contrib_lane",
                                  "tsd.query.rate_lane"])
def test_the_counter_is_declared(name):
    kind, labels, _ = METRICS_SCHEMA[name]
    assert (kind, tuple(labels)) == ("counter", ("lane",))


@pytest.mark.parametrize("repeat", [0, 1], ids=["first", "repeat"])
@pytest.mark.parametrize("kind", sorted(QUERIES))
def test_the_lane_is_counted_and_the_answer_is_the_reference(
        served, kind, repeat):
    route, gap, tsdb, mgr = served
    before = lanes()
    body, event = ask(tsdb, mgr, kind)
    # a store's first request takes the route its config names; later
    # ones may be rewritten over the partial aggregates it left (never
    # on the mesh, which does not consult that cache)
    first = not served_paths(tsdb)
    served_paths(tsdb).append(event["path"])
    assert event["path"] == route or (
        not first and route != "mesh"
        and event["path"] == "agg_rewrite"), served_paths(tsdb)
    assert event["windows"] == 128 and event["series"] == HOSTS
    bumped = {lane: n - before[lane] for lane, n in lanes().items()}
    ran_rate = int(kind == "rate")
    assert bumped == ({"dense": 0, "full": 1, "shift": 0, "scan": ran_rate}
                      if gap else
                      {"dense": 1, "full": 0, "shift": ran_rate, "scan": 0}
                      ), (event["path"], bumped)
    want = reference(kind, gap)
    assert sorted(r["tags"]["region"] for r in body) == sorted(want)
    for result in body:
        ref = want[result["tags"]["region"]]
        assert sorted(int(t) for t in result["dps"]) == sorted(ref)
        assert len(ref) == (119 if kind == "rate" else 120)
        for t, v in result["dps"].items():
            assert v == pytest.approx(ref[int(t)], rel=1e-9, abs=1e-12)


def test_both_lane_counters_are_exported_under_the_names_a_reader_keys_on():
    """What a `counter_ratio` layer file of the benchmark would read
    (benchmark/daemon.py parses this text): the counters' Prometheus
    names and their `lane` label."""
    tsdb, mgr = build("resident", gap=False)
    ask(tsdb, mgr, "rate")
    q = mgr.handle_http(HttpRequest(method="GET",
                                    uri="/api/stats/prometheus",
                                    headers={}), remote="127.0.0.1:9")
    assert q.response.status == 200
    text = q.response.body.decode()
    for name, lane in (("tsd_query_rate_lane_total", "shift"),
                       ("tsd_query_contrib_lane_total", "dense")):
        line = next(ln for ln in text.splitlines()
                    if ln.startswith('%s{lane="%s"}' % (name, lane)))
        assert float(line.split()[-1]) >= 1

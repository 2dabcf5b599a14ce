"""A request of several sub-queries, and why the device cache misses.

TSBS's double-groupby-all asks the hourly mean of all ten cpu metrics of
every host in one /api/query: ten `m=`, run one after another by
`QueryRunner.run`.  Here a seeded ten-metric fleet is loaded through
/api/put and asked through the daemon's own handler; the answer must
equal the harness's numpy reference per (metric, host), the runner must
count ten sub-queries and time ten `subquery` stages, and a store with
one host's series swapped between two metrics must fail the same
comparison.  Then each reason a device-cache miss can have is provoked
once and read back from `tsd.query.device_cache.miss_reason{reason}`,
and a store that nobody writes keeps its entries valid through the
maintenance thread's passes and the compaction queue, while one real
write still makes its entry stale."""

import json
import threading

import numpy as np
import pytest

from benchmark import loadgen, reference, traffic, tsbs
from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.storage.device_cache import DeviceSeriesCache
from opentsdb_tpu.storage.memstore import MemStore, SeriesKey
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

HOSTS, COLUMNS, METRICS, SEED = 24, 720, 10, 41
ALL = {"name": "double-groupby-all", "metrics": METRICS, "count": 1,
       "m": "avg:1h-avg:$metric{hostname=*}", "span_s": 43200,
       "group_by": "hostname", "interval_s": 3600, "ds_fn": "avg",
       "agg": "avg"}
REASONS = ("cold", "building", "evicted", "stale", "rows", "batch")


def served(swap_host: int | None = None):
    """A daemon's handler over the fleet, loaded through /api/put; with
    `swap_host`, that host's first two metrics trade series."""
    fleet = tsbs.Fleet(HOSTS, COLUMNS, 0, SEED, METRICS)
    values = fleet.data.copy()
    if swap_host is not None:
        values[[0, 1], swap_host] = values[[1, 0], swap_host]
    # mesh off: the suite's 8 virtual devices would take every grouped
    # plan of 8 or more series to the mesh; batcher off as in the cell
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                        "tsd.query.mesh.enable": "false",
                        "tsd.query.batch.enable": "false"}))
    mgr = RpcManager(tsdb)
    tails = loadgen.tag_tails(fleet.tags)
    for f, h0, h1, c0, c1 in loadgen.load_jobs(HOSTS, COLUMNS, METRICS):
        body = loadgen.put_body(values[f], tails, fleet.metrics[f], h0, h1,
                                c0, c1)
        q = mgr.handle_http(HttpRequest(method="POST", uri="/api/put",
                                        headers={}, body=body),
                            remote="127.0.0.1:9")
        assert q.response.status == 204, q.response.body[:300]
    return fleet, mgr


def ask(mgr, uri: str):
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri, headers={}),
                        remote="127.0.0.1:9")
    assert q.response.status == 200, q.response.body[:400]
    return json.loads(q.response.body)


def request_of(fleet) -> dict:
    gen = traffic.Generator(fleet, {"loop": "closed", "clients": 1,
                                    "classes": [ALL]}, SEED)
    (req,) = gen.replay_list()
    return req


def spans_named(tree: dict, name: str) -> list[dict]:
    out = [tree] if tree.get("name") == name else []
    for child in tree.get("spans", []):
        out += spans_named(child, name)
    return out


def subquery_counters() -> tuple[float, float]:
    return (REGISTRY.counter("tsd.query.subqueries").labels().get(),
            REGISTRY.counter("tsd.query.stage_ms").labels(
                stage="subquery").get())


@pytest.fixture(scope="module")
def fleet_and_handler():
    return served()


def test_ten_sub_queries_answer_as_the_reference(fleet_and_handler):
    fleet, mgr = fleet_and_handler
    req = request_of(fleet)
    assert req["path"].count("m=") == METRICS
    assert req["points"] == METRICS * HOSTS * COLUMNS
    got = reference.parse_answer(ask(mgr, req["path"]), "hostname",
                                 by_metric=True)
    want = reference.ref_query(fleet, req)
    assert len(want) == METRICS * HOSTS
    assert reference.compare(got, want) is None


def test_ten_sub_queries_are_counted_and_timed(fleet_and_handler):
    fleet, mgr = fleet_and_handler
    req = request_of(fleet)
    n0, ms0 = subquery_counters()
    payload = ask(mgr, req["path"] + "&show_stats")
    n1, ms1 = subquery_counters()
    assert n1 - n0 == METRICS
    assert ms1 > ms0
    trace = next(r for r in payload if "statsSummary" in r)[
        "statsSummary"]["trace"]
    subs = spans_named(trace, "subquery")
    assert sorted(sp["tags"]["index"] for sp in subs) == list(range(METRICS))
    # each sub-query's own plan runs inside its span
    for sp in subs:
        assert spans_named(sp, "scan") and spans_named(sp, "count")


def test_one_host_swapped_between_two_metrics_is_a_difference():
    fleet, mgr = served(swap_host=7)
    req = request_of(fleet)
    got = reference.parse_answer(ask(mgr, req["path"]), "hostname",
                                 by_metric=True)
    why = reference.compare(got, reference.ref_query(fleet, req))
    assert why is not None and "host_7" in why


# --------------------------------------------------------------------- #
# Why the device cache misses                                           #
# --------------------------------------------------------------------- #

BASE_MS = 1_356_998_400_000
POINTS = 100


def store_of(metrics=(1,), hosts=2) -> MemStore:
    store = MemStore()
    ts = BASE_MS + 10_000 * np.arange(POINTS, dtype=np.int64)
    for metric in metrics:
        for h in range(hosts):
            series = store.get_or_create_series(
                SeriesKey.make(metric, {1: h + 1}))
            series.append_batch(ts, np.arange(POINTS, dtype=np.float64) + h,
                                True)
    return store


def reasons() -> dict:
    fam = REGISTRY.counter("tsd.query.device_cache.miss_reason")
    return {r: fam.labels(reason=r).get() for r in REASONS}


def ask_cache(cache, store, metric=1, build=False, series=None):
    series = store.series_for_metric(metric) if series is None else series
    return cache.batch_for(store, metric, series, BASE_MS,
                           BASE_MS + 10_000 * POINTS, build=build)


def one_miss(expect: str, provoke) -> None:
    """`provoke()` misses exactly once, for the reason `expect`; the
    tier-labelled miss counter and the cache's own tally move with it."""
    tier = REGISTRY.counter("tsd.query.cache.misses").labels(
        tier="device_series")
    before, tier0 = reasons(), tier.get()
    cache = provoke()
    after = reasons()
    moved = {r: after[r] - before[r] for r in REASONS if after[r] != before[r]}
    assert moved == {expect: 1.0}
    assert tier.get() - tier0 == 1.0
    assert cache.misses >= 1


def test_no_entry_is_cold():
    def provoke():
        cache = DeviceSeriesCache(1 << 30)
        assert ask_cache(cache, store_of()) is None
        return cache
    one_miss("cold", provoke)


def test_an_entry_being_built_is_building():
    store = store_of()
    cache = DeviceSeriesCache(1 << 30)
    assert ask_cache(cache, store) is None          # cold: queued
    entered, release = threading.Event(), threading.Event()
    build = cache._build_guarded

    def slow_build(st, metric):
        entered.set()
        release.wait(30)
        return build(st, metric)

    cache._build_guarded = slow_build
    worker = threading.Thread(target=cache.refresh)
    worker.start()
    try:
        assert entered.wait(30)
        one_miss("building", lambda: (ask_cache(cache, store), cache)[1])
    finally:
        release.set()
        worker.join(30)
    assert ask_cache(cache, store) is not None      # pinned once built


def test_an_entry_the_budget_evicted_is_evicted():
    store = store_of(metrics=(1, 2))
    # one entry is pad_pow2(200 points, 1024) x 16 B = 16 KiB: one fits
    cache = DeviceSeriesCache(24 << 10)
    assert ask_cache(cache, store, 1, build=True) is not None
    assert ask_cache(cache, store, 2, build=True) is not None
    assert cache.evictions == 1
    one_miss("evicted", lambda: (ask_cache(cache, store, 1), cache)[1])
    # rebuilt, it is an entry again; dropped by invalidate, it is cold
    assert ask_cache(cache, store, 1, build=True) is not None
    cache.invalidate()
    one_miss("cold", lambda: (ask_cache(cache, store, 1), cache)[1])


def test_a_series_written_after_the_snapshot_is_stale():
    store = store_of()
    cache = DeviceSeriesCache(1 << 30)
    assert ask_cache(cache, store, build=True) is not None
    store.series_for_metric(1)[0].append(BASE_MS + 10_000 * POINTS, 7, True)
    one_miss("stale", lambda: (ask_cache(cache, store), cache)[1])


def test_a_series_born_after_the_snapshot_is_rows():
    store = store_of()
    cache = DeviceSeriesCache(1 << 30)
    assert ask_cache(cache, store, build=True) is not None
    born = store.get_or_create_series(SeriesKey.make(1, {1: 99}))
    born.append(BASE_MS, 1, True)
    one_miss("rows", lambda: (ask_cache(cache, store), cache)[1])


def test_a_batch_over_the_bound_is_batch():
    store = store_of()
    # S = 2 rows x n = 128 padded points x 17 B is over 1 KiB
    cache = DeviceSeriesCache(1 << 30, batch_max_bytes=1 << 10)
    assert cache._build(store, 1) is not None
    one_miss("batch", lambda: (ask_cache(cache, store), cache)[1])


def test_the_reasons_add_up_to_the_misses():
    tally = REGISTRY.counter("tsd.query.cache.misses").labels(
        tier="device_series")
    before, tier0 = sum(reasons().values()), tally.get()
    store = store_of()
    cache = DeviceSeriesCache(1 << 30)
    for _ in range(3):
        ask_cache(cache, store)
    assert cache.misses == 3
    assert sum(reasons().values()) - before == tally.get() - tier0 == 3


# --------------------------------------------------------------------- #
# A store nobody writes keeps its entries                               #
# --------------------------------------------------------------------- #

def test_without_writes_entries_stay_through_maintenance_and_compaction():
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
    ts = BASE_MS // 1000 + 10 * np.arange(POINTS)
    for h in range(3):
        for m in ("cpu.a", "cpu.b"):
            # written newest first: every series is left dirty for the
            # compaction queue to sort
            for t in ts[::-1]:
                tsdb.add_point(m, int(t), int(t % 97) + h, {"host": "h%d" % h})
    store, cache = tsdb.store, tsdb.device_cache
    metrics = [tsdb.metrics.get_id(m) for m in ("cpu.a", "cpu.b")]
    lo, hi = BASE_MS, BASE_MS + 10_000 * POINTS
    for metric in metrics:
        assert cache.batch_for(store, metric, store.series_for_metric(metric),
                               lo, hi) is not None
    builds = cache.builds
    for _ in range(3):
        store.compaction_queue.flush()
        assert cache.refresh(store) == 0
        for metric in metrics:
            assert cache.batch_for(store, metric,
                                   store.series_for_metric(metric), lo, hi,
                                   build=False) is not None
    assert cache.builds == builds and cache.misses == 0
    # one real write: that entry, and only that one, misses as stale
    tsdb.add_point("cpu.a", int(ts[-1]) + 10, 5, {"host": "h0"})
    before = reasons()
    a, b = metrics
    assert cache.batch_for(store, a, store.series_for_metric(a), lo, hi,
                           build=False) is None
    assert cache.batch_for(store, b, store.series_for_metric(b), lo, hi,
                           build=False) is not None
    assert reasons()["stale"] - before["stale"] == 1
    assert cache.refresh(store) == 1 and cache.builds == builds + 1


def test_reasons_hold_under_threads_that_ask_write_and_refresh():
    """More threads than cores ask, one writes and one refreshes, on a
    short switch interval: every miss is counted once with one reason,
    no key is left held as building, and the entry ends pinned."""
    import os
    import sys
    store = store_of(metrics=(1, 2), hosts=4)
    cache = DeviceSeriesCache(1 << 30)
    before, stop = reasons(), threading.Event()
    asks, errors = [], []

    def asker(metric):
        n = 0
        try:
            while not stop.is_set():
                ask_cache(cache, store, metric, build=n % 3 == 0)
                n += 1
        except Exception as e:          # failed below, with what it was
            errors.append(e)
        asks.append(n)

    def refresher():
        while not stop.is_set():
            cache.refresh()

    def writer():
        series = store.series_for_metric(2)[0]
        t = BASE_MS + 10_000 * POINTS
        while not stop.is_set():
            series.append(t, 1, True)
            t += 10_000

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=asker, args=(1 + i % 2,))
                   for i in range(2 * (os.cpu_count() or 2) + 2)]
        threads += [threading.Thread(target=refresher),
                    threading.Thread(target=writer)]
        for th in threads:
            th.start()
        stop.wait(1.5)
        stop.set()
        for th in threads:
            th.join(30)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors and sum(asks) > 0
    after = reasons()
    assert sum(after[r] - before[r] for r in REASONS) == cache.misses
    assert not cache._building
    cache.refresh()
    assert ask_cache(cache, store, 1) is not None

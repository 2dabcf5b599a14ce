"""A wide group-by on the program's normal query path: S = 3000 series
(not a power of two) through the /api/query handler, against a plain
numpy reference written here.  Group by hostname gives 3000 one-member
groups; the overview shapes reduce the same rows into 7 and 5 groups.
Each of the chooser's candidate group-reduce forms is forced in turn and
must give the reference's answer (integer-valued results exactly, the
rest to 1e-9 relative), the width counters must read what was run, and
explain's fingerprint must equal the executed one at this width."""

import json

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

S, POINTS, CADENCE_S = 3000, 180, 10
BASE = 1451606400
METRIC = "wide.cpu"
DCS, REGIONS = 7, 5
START, END = BASE + 300, BASE + 1500 - 1     # 20 min inside the half hour

# name -> (m, group tag, groups, interval s, aggregator, rate)
CLASSES = {
    "host-avg": ("avg:5m-avg:%s{hostname=*}", "hostname", S, 300, "avg",
                 False),
    "dc-p99": ("p99:1m-avg:%s{dc=*}", "dc", DCS, 60, "p99", False),
    "region-sum": ("sum:1m-avg:%s{region=*}", "region", REGIONS, 60, "sum",
                   False),
    "region-rate": ("sum:rate:1m-avg:%s{region=*}", "region", REGIONS, 60,
                    "sum", True),
}
MODES = ("auto", "segment", "sorted", "matmul")


def walks() -> np.ndarray:
    """[S, POINTS] seeded integer walks clamped to [0, 100]."""
    rng = np.random.default_rng(27)
    steps = np.rint(rng.normal(0.0, 1.0, (S, POINTS))).astype(np.int64)
    out = np.empty((S, POINTS), np.int64)
    cur = rng.integers(0, 101, S)
    for i in range(POINTS):
        cur = np.clip(cur + steps[:, i], 0, 100)
        out[:, i] = cur
    return out


def tags_of(h: int) -> dict:
    return {"hostname": "host_%d" % h, "dc": "dc%d" % (h % DCS),
            "region": "r%d" % (h % REGIONS)}


@pytest.fixture(scope="module")
def served():
    # mesh off: the suite's 8 virtual devices would turn every grouped
    # plan of 8 or more series into a mesh plan
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                        "tsd.query.mesh.enable": "false"}))
    values = walks()
    ts_ms = (BASE + CADENCE_S * np.arange(POINTS, dtype=np.int64)) * 1000
    for h in range(S):
        key = tsdb._series_key(METRIC, tags_of(h), create=True)
        tsdb.store.add_batch(key, ts_ms, values[h].astype(np.float64),
                             True, values[h])
    return tsdb, RpcManager(tsdb), values


def ask(mgr, uri: str):
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri, headers={}),
                        remote="127.0.0.1:9")
    assert q.response.status == 200, q.response.body[:400]
    return json.loads(q.response.body)


def uri_of(name: str, explain: bool = False) -> str:
    return "/api/query%s?start=%d&end=%d&m=%s" % (
        "/explain" if explain else "", START, END,
        CLASSES[name][0] % METRIC)


def legacy_percentile(col: np.ndarray, q: float) -> float:
    """commons-math3 LEGACY estimation, OpenTSDB's pNN."""
    s, n = np.sort(col), len(col)
    pos = q * (n + 1) / 100.0
    if pos < 1:
        return float(s[0])
    if pos >= n:
        return float(s[-1])
    k = int(np.floor(pos))
    return float(s[k - 1] + (pos - k) * (s[k] - s[k - 1]))


def reference(values: np.ndarray, name: str) -> dict:
    """{group: (timestamps s [W], values [W])}, straight from the
    definitions: window means, first difference per second, then the
    aggregate of each group's rows."""
    _, tag, _, interval, agg, rate = CLASSES[name]
    ts = BASE + CADENCE_S * np.arange(POINTS)
    cols = (ts >= START) & (ts <= END)
    ts, vals = ts[cols], values[:, cols].astype(np.float64)
    win = ts - ts % interval
    wts = np.unique(win)
    grid = np.stack([vals[:, win == w].mean(axis=1) for w in wts], axis=1)
    if rate:
        grid = np.diff(grid, axis=1) / np.diff(wts).astype(np.float64)
        wts = wts[1:]
    groups: dict[str, list[int]] = {}
    for h in range(S):
        groups.setdefault(tags_of(h)[tag], []).append(h)
    out = {}
    for g, rows in groups.items():
        part = grid[rows]
        if agg == "sum":
            val = part.sum(axis=0)
        elif agg == "avg":
            val = part.mean(axis=0)
        else:
            val = np.array([legacy_percentile(part[:, w], 99.0)
                            for w in range(part.shape[1])])
        out[g] = (wts, val)
    return out


def assert_answer(payload: list, want: dict, tag: str) -> None:
    got = {r["tags"][tag]: r["dps"] for r in payload}
    assert set(got) == set(want)
    for g, (wts, wval) in want.items():
        dps = got[g]
        assert [int(k) for k in sorted(dps, key=int)] == wts.tolist(), g
        gval = np.array([dps[str(t)] for t in wts.tolist()], np.float64)
        if np.array_equal(wval, np.rint(wval)):
            assert np.array_equal(gval, wval), g
        else:
            assert np.all(np.abs(gval - wval)
                          <= 1e-9 * np.maximum(np.abs(wval), 1.0)), g


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("name", sorted(CLASSES))
def test_every_group_form_answers_as_the_reference(served, name, mode,
                                                   kernel_forms):
    tsdb, mgr, values = served
    if mode != "auto":
        kernel_forms(group=mode)
    payload = ask(mgr, uri_of(name))
    _, tag, groups, _, _, _ = CLASSES[name]
    assert len(payload) == groups
    assert_answer(payload, reference(values, name), tag)


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_the_body_is_the_text_json_dumps_would_write(served, name):
    """A plain answer is encoded from pre-encoded heads and one format
    call a group: byte for byte what json.dumps makes of the same data."""
    tsdb, mgr, _ = served
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri_of(name),
                                    headers={}), remote="127.0.0.1:9")
    body = q.response.body.decode()
    assert json.dumps(json.loads(body)) == body
    shown = ask(mgr, uri_of(name) + "&show_tsuids=true")
    assert all(len(r["tsuids"]) >= 1 for r in shown)
    assert [r["dps"] for r in shown] == [r["dps"] for r in json.loads(body)]


def counter(name: str) -> float:
    return REGISTRY.counter(name).labels().get()


def stage_ms(stage: str) -> float:
    return REGISTRY.counter("tsd.query.stage_ms").labels(stage=stage).get()


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_width_counters_read_what_was_run(served, name):
    tsdb, mgr, _ = served
    before = (counter("tsd.query.series"), counter("tsd.query.groups"))
    stages = {s: stage_ms(s) for s in ("scan", "count", "extract",
                                       "assemble")}
    sent = REGISTRY.counter("tsd.http.response_bytes").labels(
        route="api/query").get()
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri_of(name),
                                    headers={}), remote="127.0.0.1:9")
    assert q.response.status == 200
    assert counter("tsd.query.series") - before[0] == S
    assert counter("tsd.query.groups") - before[1] == CLASSES[name][2]
    # stamped with tracing off as well as on, every request
    for s, was in stages.items():
        assert stage_ms(s) > was, s
    assert REGISTRY.counter("tsd.http.response_bytes").labels(
        route="api/query").get() - sent == len(q.response.body)


@pytest.mark.parametrize("name", sorted(CLASSES))
def test_explain_fingerprint_equals_the_executed_one(served, name):
    tsdb, mgr, _ = served
    seg = ask(mgr, uri_of(name, explain=True))["subQueries"][0][
        "segments"][0]
    assert seg["series"] == S and seg["groups"] == CLASSES[name][2]
    ask(mgr, uri_of(name))
    event = [e for e in tsdb.flightrec.events() if e["kind"] == "plan"][-1]
    assert (event["path"], event["fingerprint"]) == (
        seg["path"], seg["fingerprint"]), seg["provenance"]
    assert event["series"] == S and event["groups"] == CLASSES[name][2]


def test_a_series_born_later_is_in_the_next_answer(served):
    """The resolved selection is a memo of one store generation: a new
    host answers in the very next request, and leaves again when its
    series is deleted."""
    tsdb, mgr, values = served
    assert len(ask(mgr, uri_of("host-avg"))) == S
    key = tsdb._series_key(METRIC, {"hostname": "late", "dc": "dc0",
                                    "region": "r0"}, create=True)
    ts_ms = (BASE + CADENCE_S * np.arange(POINTS, dtype=np.int64)) * 1000
    tsdb.store.add_batch(key, ts_ms, np.full(POINTS, 7.0), True,
                         np.full(POINTS, 7, np.int64))
    payload = ask(mgr, uri_of("host-avg"))
    late = [r for r in payload if r["tags"]["hostname"] == "late"]
    assert len(payload) == S + 1 and len(late) == 1
    assert set(late[0]["dps"].values()) == {7.0}
    assert tsdb.store.delete_series(key)
    payload = ask(mgr, uri_of("host-avg"))
    assert len(payload) == S
    assert_answer(payload, reference(values, "host-avg"), "hostname")

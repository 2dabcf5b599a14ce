"""The streamed route against a plain numpy evaluation, through the
daemon's own entry.

`heavy-cold-scan`'s five request classes (read from its traffic file, so
the test follows the mix) are asked of a 40-host TSBS fleet through
`RpcManager.handle_http` — `POST /api/put` in, `GET /api/query` out, the
TSDB + planner path `tsd_main` serves — on a TSD whose device cache
declines the metric and whose thresholds are lowered the way the mix's
`rehearse.tsd` lowers them, so that every request takes
`query/planner.py::_stream_grouped` with five `[40, 1024]` chunks: the
1 h classes' 12-window grids fold whole (`_update`), the 10-minute
classes' 72-window grids by slices of 64 (`_update_sliced`).

The reference is `plain_eval` below: the same semantics written the
shortest way numpy allows, window by window, group by group, in one
floating-point type.  In float64 it must equal the answers; in float32 it
must NOT, or a fold computed in a lower precision than the configuration
states could pass the comparison that decides `correct`.

`tests/test_streaming.py::TestPlannerStreaming` compares the route with
the materialized route of the same program; this file compares it with
something that shares no code with the program."""

import json
import os

import numpy as np
import pytest

from benchmark import reference, traffic
from benchmark.tsbs import CADENCE_S, Fleet
from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOSTS, HOURS, SEED = 40, 14, 3500000077
POINTS = HOSTS * 12 * 3600 // CADENCE_S       # one 12 h request: 172 800
CLASSES = ("double-groupby-1", "host-max-12h", "datacenter-p99-12h",
           "region-sum-12h", "region-rate-12h")
# the mix's own rehearse.tsd, and one device: the suite's eight virtual
# devices would send every request down the mesh route
STREAMING = {"tsd.core.auto_create_metrics": True,
             "tsd.query.mesh.enable": False}


def _http(mgr, method, uri, body=b""):
    q = mgr.handle_http(HttpRequest(method=method, uri=uri, headers={},
                                    body=body), remote="127.0.0.1:9")
    return q.response.status, q.response.body


def _daemon(fleet, tsd: dict) -> RpcManager:
    """A TSD holding the fleet's retained columns, written through
    POST /api/put one host a body."""
    mgr = RpcManager(TSDB(Config(tsd)))
    for h, tags in enumerate(fleet.tags):
        body = json.dumps([
            {"metric": fleet.metric, "timestamp": int(t), "value": int(v),
             "tags": tags}
            for t, v in zip(fleet.ts[:fleet.retained],
                            fleet.values[h, :fleet.retained])]).encode()
        assert _http(mgr, "POST", "/api/put", body)[0] == 204
    return mgr


@pytest.fixture(scope="module")
def cold():
    """(fleet, the cold TSD, one request of each class)."""
    mix = traffic.load_mix(os.path.join(REPO, "benchmark"),
                           "heavy-cold-scan")
    fleet = Fleet(HOSTS, HOURS * 3600 // CADENCE_S, 0, SEED)
    requests = {}
    for req in traffic.Generator(fleet, mix["readers"], SEED).replay_list():
        requests.setdefault(req["cls"], req)
    assert tuple(sorted(requests)) == tuple(sorted(CLASSES))
    tsd = dict(STREAMING, **mix["rehearse"]["tsd"])
    return fleet, _daemon(fleet, tsd), requests


def _counter(name, **labels):
    return REGISTRY.counter(name, "").labels(**labels).get()


def stream_counters() -> dict:
    out = {k: _counter("tsd.query.stream." + k)
           for k in ("requests", "chunks", "points", "upload_bytes")}
    for lane in ("sliced", "full"):
        out[lane] = _counter("tsd.query.stream.fold", lane=lane)
    for lane in ("bulk", "cursor"):
        out[lane] = _counter("tsd.query.stream.rows", lane=lane)
    for stage in ("stream_pack", "stream_upload", "stream_wait"):
        out[stage] = _counter("tsd.query.stage_ms", stage=stage)
    return out


def ask(mgr, req) -> tuple[dict, dict]:
    """(the answer as the benchmark parses it, what the route's counters
    rose by)."""
    before = stream_counters()
    status, body = _http(mgr, "GET", req["path"])
    assert status == 200, body[:300]
    after = stream_counters()
    return (reference.parse_answer(json.loads(body), req["group_by"]),
            {k: after[k] - before[k] for k in after})


# --------------------------------------------------------------------- #
# The plain evaluation                                                  #
# --------------------------------------------------------------------- #

def plain_eval(fleet, req, dtype) -> dict:
    """{group: (window starts [W], values [W] float64)} of one request,
    every sum, mean, difference and quotient taken in `dtype`."""
    cols = np.flatnonzero((fleet.ts >= req["start"])
                          & (fleet.ts <= req["end"]))
    ts = fleet.ts[cols]
    vals = fleet.values[:, cols].astype(dtype)
    win = ts - ts % req["interval_s"]
    wts = np.unique(win)
    grid = np.empty((fleet.hosts, len(wts)), dtype)
    for w, start in enumerate(wts):
        inside = vals[:, win == start]
        if req["ds_fn"] == "avg":
            grid[:, w] = (inside.sum(axis=1, dtype=dtype)
                          / dtype(inside.shape[1]))
        else:
            assert req["ds_fn"] == "max"
            grid[:, w] = inside.max(axis=1)
    if req["rate"]:
        # per second, from the window before; the first has none
        grid = ((grid[:, 1:] - grid[:, :-1])
                / np.diff(wts).astype(dtype)[None, :])
        wts = wts[1:]
    out = {}
    for group in sorted({t[req["group_by"]] for t in fleet.tags}):
        rows = grid[[h for h, t in enumerate(fleet.tags)
                     if t[req["group_by"]] == group]]
        if req["agg"] == "sum":
            col = rows.sum(axis=0, dtype=dtype)
        elif req["agg"] == "avg":
            col = rows.sum(axis=0, dtype=dtype) / dtype(len(rows))
        elif req["agg"] == "max":
            col = rows.max(axis=0)
        else:
            assert req["agg"] == "p99"
            col = np.array([legacy_percentile(rows[:, w], 99, dtype)
                            for w in range(rows.shape[1])], dtype)
        out[group] = (wts, col.astype(np.float64))
    return out


def legacy_percentile(col, q, dtype):
    """commons-math3's LEGACY estimate, OpenTSDB's pNN: position
    q (n + 1) / 100 among the sorted values, interpolated, clamped."""
    s = np.sort(col)
    pos = q * (len(s) + 1) / 100.0
    if pos < 1:
        return s[0]
    if pos >= len(s):
        return s[-1]
    k = int(pos)
    return s[k - 1] + dtype(pos - k) * (s[k] - s[k - 1])


# --------------------------------------------------------------------- #
# Answers                                                               #
# --------------------------------------------------------------------- #

# `reference.compare` is the comparison that decides `correct` in the
# cell, with the configuration's two tolerances:
#  * a group whose reference is integer-valued (host-max-12h: maxima of
#    integer gauges) must be EQUAL: every such value is exact in float64
#    whatever order the fold merges its chunks in, so any difference is a
#    wrong answer, not rounding;
#  * the rest to 1e-9 relative, absolute below 1: a mean is total / n,
#    and the fold's total over 360 points and up to ~10 hosts of a group
#    differs from numpy's by the order of float64 additions, ~1e-14
#    relative; 1e-9 leaves five orders of room above that and stands 60
#    times under float32's 6e-8, which is what the control below needs.
#    A rate sum can cancel to zero, where no relative scale exists.

@pytest.mark.parametrize("cls", CLASSES)
def test_streamed_answer_equals_the_plain_evaluation(cold, cls):
    fleet, mgr, requests = cold
    req = requests[cls]
    got, rose = ask(mgr, req)
    assert rose["requests"] == 1 and rose["chunks"] >= 3
    want = plain_eval(fleet, req, np.float64)
    assert reference.compare(got, want) is None
    # and the benchmark's own reference agrees with the plain one
    assert reference.compare(reference.ref_query(fleet, req), want) is None


def test_both_folds_ran_across_the_five_classes(cold):
    """The 1 h classes fold whole grids, the 10-minute classes slices:
    the comparisons above covered `_update` and `_update_sliced`."""
    fleet, mgr, requests = cold
    rose = {cls: ask(mgr, requests[cls])[1] for cls in CLASSES}
    assert rose["double-groupby-1"]["full"] == 5
    assert rose["double-groupby-1"]["sliced"] == 0
    assert rose["region-sum-12h"]["sliced"] >= 1
    assert sum(r["full"] for r in rose.values()) >= 1
    assert sum(r["sliced"] for r in rose.values()) >= 1


@pytest.mark.parametrize("cls", ["region-sum-12h", "double-groupby-1"])
def test_the_plain_evaluation_in_float32_fails_the_comparison(cold, cls):
    """The control: were the fold computed in float32, its answer would
    be this one, and the comparison calls it wrong."""
    fleet, _, requests = cold
    req = requests[cls]
    low = plain_eval(fleet, req, np.float32)
    want = plain_eval(fleet, req, np.float64)
    why = reference.compare(low, want)
    assert why is not None and "got" in why


# --------------------------------------------------------------------- #
# Counters and stages                                                   #
# --------------------------------------------------------------------- #

def test_one_streamed_query_moves_its_counters_and_stages(cold):
    fleet, mgr, requests = cold
    _, rose = ask(mgr, requests["region-rate-12h"])
    assert rose["requests"] == 1
    # 4320 points a series in chunks of 1024: five, none empty
    assert rose["chunks"] == 5 == rose["sliced"] + rose["full"]
    assert rose["points"] == POINTS
    # int64 + float64 + bool of five [40, 1024] chunks, padding included
    assert rose["upload_bytes"] == 5 * HOSTS * 1024 * 17
    for stage in ("stream_pack", "stream_upload", "stream_wait"):
        assert rose[stage] > 0, stage
    text = REGISTRY.prometheus_text()
    for line in ('tsd_query_stage_ms_total{stage="stream_pack"}',
                 'tsd_query_stage_ms_total{stage="stream_upload"}',
                 'tsd_query_stage_ms_total{stage="stream_wait"}',
                 'tsd_query_stream_fold_total{lane="sliced"}',
                 'tsd_query_stream_fold_total{lane="full"}',
                 "tsd_query_stream_requests_total "):
        assert line in text, line


@pytest.mark.parametrize("cls", CLASSES)
def test_a_quiet_store_fills_every_row_by_the_bulk_lane(cold, cls):
    """Nothing writes while the request scans: each of its five chunks'
    rows is copied in bulk, none by the cursor read."""
    fleet, mgr, requests = cold
    _, rose = ask(mgr, requests[cls])
    assert rose["bulk"] == HOSTS * rose["chunks"] == HOSTS * 5
    assert rose["cursor"] == 0
    assert 'tsd_query_stream_rows_total{lane="bulk"}' in \
        REGISTRY.prometheus_text()


def test_the_stages_are_spans_under_the_pipeline_and_explain_is_unmoved(
        cold):
    fleet, mgr, requests = cold
    req = requests["region-sum-12h"]
    status, body = _http(mgr, "GET", req["path"] + "&show_stats")
    assert status == 200
    summary = next(r["statsSummary"] for r in json.loads(body)
                   if "statsSummary" in r)

    def names(span, under=None):
        yield span["name"], under
        for child in span.get("spans", []):
            yield from names(child, span["name"])
    seen = list(names(summary["trace"]))
    for stage in ("stream_pack", "stream_upload", "stream_wait"):
        assert (stage, "pipeline") in seen, seen
    # what the route printed before, it prints still
    status, body = _http(mgr, "GET", "/api/stats/query")
    assert status == 200
    last = json.loads(body)["completed"][-1]
    assert last["stats"]["streamedChunks"] == 5
    status, body = _http(mgr, "GET", req["path"].replace(
        "/api/query?", "/api/query/explain?"))
    assert status == 200
    seg = json.loads(body)["subQueries"][0]["segments"][0]
    assert seg["path"] == "streamed"


def test_a_resident_query_moves_none_of_them(cold):
    """The same fleet behind the default thresholds: the device cache
    admits the metric, nothing streams, no counter or stage moves."""
    fleet, _, requests = cold
    mgr = _daemon(fleet, dict(STREAMING, **{
        "tsd.query.host_lane.max_points": 1000,
        "tsd.query.batch.enable": False}))
    req = requests["region-sum-12h"]
    got, rose = ask(mgr, req)
    assert reference.compare(got, plain_eval(fleet, req, np.float64)) is None
    assert not any(rose.values()), rose

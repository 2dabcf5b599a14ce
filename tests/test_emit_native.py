"""A grouped answer's points written by the native encoder
(native/engine.cpp eng_emit_rows, through query/planner.py emit_texts)
against the Python text it stands in for: every value as float.__repr__
writes it, every result's text byte for byte what QueryResult.json_text
and json.dumps(to_json()) write, and every response body what the
serializer wrote with one json_text or to_json a result.  Without the
library every result takes the Python lane and the body is the same;
tsd.query.emit_groups{lane} counts which lane wrote each result."""

import json

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.models.tsquery import TSQuery, parse_m_subquery
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.query.planner import QueryResult, emit_texts
from opentsdb_tpu.storage import native_engine
from opentsdb_tpu.storage.memstore import Annotation
from opentsdb_tpu.tsd.http import HttpQuery, HttpRequest, RawJson
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.tsd.serializers import HttpJsonSerializer
from opentsdb_tpu.utils.config import Config

needs_native = pytest.mark.skipif(not native_engine.available(),
                                  reason="native engine unavailable")

BASE = 1356998400
HUGE = np.finfo(np.float64).max
EDGES = [0.0, -0.0, 1e16, np.nextafter(1e16, 0.0), -1e16, 1e-4,
         np.nextafter(1e-4, 0.0), -1e-4, 1e-5, 5e-324, -5e-324, HUGE, -HUGE,
         2.2250738585072014e-308, 99.99999999999999, 9999999999999998.0,
         1e15, 1e22, 1e23, 0.1, 0.30000000000000004, 1.0, 42.0, -7.0,
         123456.0, 1234.5678, -0.5, 1e100, 1.5e-300, 12345678901234567.0]


def values_of(kind: str, n: int, seed: int = 7) -> np.ndarray:
    """`n` finite float64 values of one kind."""
    rng = np.random.default_rng(seed)
    if kind == "edges":
        return np.resize(np.array(EDGES, np.float64), n)
    if kind == "means":     # windows of integer points, as a 1m-avg makes
        return rng.integers(0, 101, (n, 6)).mean(axis=1) \
            * rng.choice([1.0, -1.0], n)
    if kind == "decades":
        return rng.uniform(-1.0, 1.0, n) * 10.0 ** rng.uniform(-40, 40, n)
    if kind == "bits":      # every exponent and mantissa, the sign too
        v = rng.integers(0, 2 ** 64, n, dtype=np.uint64).view(np.float64)
        return np.where(np.isfinite(v), v, 1.0)
    raise ValueError(kind)


KINDS = ("edges", "means", "decades", "bits")


@needs_native
@pytest.mark.parametrize("kind", KINDS)
def test_every_value_reads_as_its_repr(kind):
    vals = values_of(kind, 40_000)
    block = np.ascontiguousarray(vals.reshape(-1, 8))
    texts = native_engine.emit_rows(block, np.arange(len(block)),
                                    ["<"] + [" "] * 7 + [">"])
    assert texts == ["<%s>" % " ".join(map(repr, row))
                     for row in block.tolist()]


def block_results(block: np.ndarray, stamps: list, metric: str = "m",
                  notes=None) -> list[QueryResult]:
    """One QueryResult a row of `block`, as _run_segment_grouped makes
    them: a head, the shared timestamp column, the row's values."""
    out = []
    for i, values in enumerate(block.tolist()):
        tags = {"host": "h%d" % i, "dc": "dé%d" % (i % 3)}
        agg = ["rack"] if i % 2 else []
        head = json.dumps({"metric": metric, "tags": tags,
                           "aggregateTags": agg})[:-1]
        out.append(QueryResult(
            metric, tags, agg, ["%06X" % i],
            annotations=(notes or {}).get(i, ()), head=head, stamps=stamps,
            values=values, block=block, row=i))
    return out


def stamps_of(w: int, step_ms: int = 60_000) -> list:
    return [BASE * 1000 + 1234 + step_ms * j for j in range(w)]


@needs_native
@pytest.mark.parametrize("ms_resolution", [False, True])
@pytest.mark.parametrize("kind", KINDS)
def test_a_block_row_is_json_text_and_json_dumps(kind, ms_resolution):
    block = np.ascontiguousarray(values_of(kind, 300 * 12).reshape(300, 12))
    results = block_results(block, stamps_of(12))
    texts = emit_texts(results, ms_resolution)
    for r, text in zip(results, texts):
        assert text == r.json_text({}, ms_resolution) == json.dumps(
            r.to_json(ms_resolution=ms_resolution))


def body_of(query: TSQuery, items: list) -> bytes:
    """The bytes HttpQuery.send_reply writes for a payload."""
    q = HttpQuery(None, HttpRequest(method="GET", uri="/api/query"))
    q.send_reply(items)
    return q.response.body


def python_lane(query: TSQuery, results: list) -> list:
    """format_query_v1 as it was with no native lane: json_text for a
    plain result, else to_json."""
    out, keys = [], {}
    plain = not (query.show_tsuids or query.show_query
                 or query.global_annotations)
    for r in results:
        text = (r.json_text(keys, query.ms_resolution)
                if plain and not r.annotations else None)
        if text is not None:
            out.append(RawJson(text))
            continue
        out.append(r.to_json(
            keys=keys, ms_resolution=query.ms_resolution,
            show_tsuids=query.show_tsuids,
            fill_policy=(query.queries[r.index].fill_policy
                         if r.index < len(query.queries) else "none"),
            show_query=query.show_query,
            sub_query=(query.queries[r.index]
                       if r.index < len(query.queries) else None),
            no_annotations=query.no_annotations,
            global_annotations=query.global_annotations))
    return out


def emitted(lane: str) -> float:
    return REGISTRY.counter("tsd.query.emit_groups").labels(
        lane=lane).get()


def mixed_answer() -> tuple[list, int]:
    """Results of two blocks interleaved out of block order, rows with a
    NaN or an infinity, an annotated row, block-less results (raw pairs,
    int values, no head) and a result whose points were replaced; and
    how many of them the native lane should write."""
    a = np.ascontiguousarray(values_of("means", 40 * 6).reshape(40, 6))
    a[3, 2] = np.nan
    a[5, 0] = np.inf
    a[6, 5] = -np.inf
    b = np.ascontiguousarray(values_of("decades", 25 * 12).reshape(25, 12))
    note = Annotation(start_time=BASE, description="deploy")
    ra = block_results(a, stamps_of(6, 300_000), "cpu", {7: [note]})
    rb = block_results(b, stamps_of(12), "mem")
    merged = rb[4]
    merged.dps = merged.dps + [(BASE * 1000 + 10 ** 8, 1.5)]
    raw = QueryResult("disk", {"host": "h"}, [], ["0A"],
                      dps=[(BASE * 1000, 1), (BASE * 1000 + 10, 2.5)])
    ints = QueryResult("disk", {"host": "i"}, [], ["0B"],
                       dps=[(BASE * 1000, 7), (BASE * 1000 + 10, -3)],
                       head='{"metric": "disk", "tags": {"host": "i"}, '
                            '"aggregateTags": []')
    headless = QueryResult("cpu", {"host": "x"}, [], ["0C"],
                           stamps=ra[0].stamps, values=ra[0].values,
                           block=a, row=0)
    results = (ra[::-1][:20] + rb[::2] + [raw] + ra[::-1][20:] + rb[1::2]
               + [ints, headless])
    return results, 40 - 4 + 25 - 1


@pytest.mark.parametrize("ms_resolution", [False, True])
def test_a_mixed_answer_is_the_python_lanes_body(ms_resolution):
    results, n_native = mixed_answer()
    query = TSQuery(start=str(BASE), ms_resolution=ms_resolution)
    before = emitted("native"), emitted("python")
    payload = HttpJsonSerializer().format_query_v1(query, results)
    assert body_of(query, payload) == body_of(
        query, python_lane(query, results))
    if native_engine.available():
        assert emitted("native") - before[0] == n_native
        assert emitted("python") - before[1] == len(results) - n_native


@pytest.mark.parametrize("flag", ["show_tsuids", "show_query",
                                  "global_annotations"])
def test_an_answer_that_is_not_plain_takes_the_python_lane(flag):
    results, _ = mixed_answer()
    query = TSQuery(start=str(BASE), **{flag: True})
    query.queries.append(parse_m_subquery("sum:1m-avg:cpu{host=*}"))
    before = emitted("native"), emitted("python")
    payload = HttpJsonSerializer().format_query_v1(query, results)
    assert body_of(query, payload) == body_of(
        query, python_lane(query, results))
    assert emitted("native") == before[0]
    assert emitted("python") - before[1] == len(results)


def test_without_the_library_every_group_takes_the_python_lane(
        monkeypatch):
    results, _ = mixed_answer()
    query = TSQuery(start=str(BASE))
    monkeypatch.setattr(native_engine, "_load_library", lambda: None)
    assert emit_texts(results) == [None] * len(results)
    before = emitted("native"), emitted("python")
    payload = HttpJsonSerializer().format_query_v1(query, results)
    assert body_of(query, payload) == body_of(
        query, python_lane(query, results))
    assert emitted("native") == before[0]
    assert emitted("python") - before[1] == len(results)


def test_replacing_the_points_lets_go_of_the_block():
    block = np.ascontiguousarray(values_of("means", 12).reshape(2, 6))
    r = block_results(block, stamps_of(6))[1]
    r.dps = r.dps[:3]
    assert r.block is None and emit_texts([r]) == [None]


@needs_native
def test_emit_rows_refuses_what_it_cannot_read():
    block = np.zeros((4, 6))
    pieces = [""] * 7
    with pytest.raises(ValueError):
        native_engine.emit_rows(block[:, ::2], np.arange(4), pieces[:4])
    with pytest.raises(ValueError):
        native_engine.emit_rows(block.astype(np.float32), np.arange(4),
                                pieces)
    with pytest.raises(ValueError):
        native_engine.emit_rows(block, np.arange(4), pieces[:6])
    with pytest.raises(ValueError):
        native_engine.emit_rows(block, np.array([0, 4]), pieces)
    block[2, 3] = np.nan
    with pytest.raises(ValueError):
        native_engine.emit_rows(block, np.arange(4), pieces)
    assert native_engine.emit_rows(block, np.arange(0), pieces) == []


# ----------------------------------------------------------------------- #
# through the /api/query handler                                          #
# ----------------------------------------------------------------------- #

HOSTS, POINTS, CADENCE_S = 24, 180, 10


def make_served():
    # mesh off: the suite's 8 virtual devices would take every grouped
    # plan of 8 or more series to the mesh route
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                        "tsd.query.mesh.enable": "false"}))
    rng = np.random.default_rng(42)
    ts_ms = (BASE + CADENCE_S * np.arange(POINTS, dtype=np.int64)) * 1000
    for h in range(HOSTS):
        ivals = rng.integers(0, 101, POINTS)
        key = tsdb._series_key("emit.cpu", {"host": "h%02d" % h,
                                            "dc": "dc%d" % (h % 3)},
                               create=True)
        tsdb.store.add_batch(key, ts_ms, ivals.astype(np.float64), True,
                             ivals)
    return tsdb, RpcManager(tsdb)


@pytest.fixture(scope="module")
def served():
    return make_served()


def ask(mgr, uri: str) -> bytes:
    q = mgr.handle_http(HttpRequest(method="GET", uri=uri, headers={}),
                        remote="127.0.0.1:9")
    assert q.response.status == 200, q.response.body[:400]
    return q.response.body


END = BASE + CADENCE_S * POINTS - 1
URIS = {
    "host-avg": ("avg:1m-avg:emit.cpu{host=*}", HOSTS),
    "dc-sum": ("sum:5m-avg:emit.cpu{dc=*}", 3),
    "host-rate": ("sum:rate:1m-avg:emit.cpu{host=*}", HOSTS),
    "two-subs": ("avg:1m-avg:emit.cpu{host=*}&m=max:2m-max:emit.cpu{dc=*}",
                 HOSTS + 3),
    "raw": ("sum:emit.cpu{dc=*}", 0),
    "nan-fill": ("sum:1m-avg-nan:emit.cpu{host=*}", 0),
}


@pytest.mark.parametrize("name", sorted(URIS))
def test_the_handlers_body_is_the_python_lanes(served, name, monkeypatch):
    tsdb, mgr = served
    m, n_native = URIS[name]
    # a window past the data: the nan fill gives every row a NaN
    end = END + (600 if name == "nan-fill" else 0)
    uri = "/api/query?start=%d&end=%d&m=%s" % (BASE, end, m)
    before = emitted("native")
    body = ask(mgr, uri)
    if native_engine.available():
        assert emitted("native") - before == n_native
    groups = len(json.loads(body))
    monkeypatch.setattr(native_engine, "_load_library", lambda: None)
    before = emitted("native"), emitted("python")
    assert ask(mgr, uri) == body
    assert emitted("native") == before[0]
    assert emitted("python") - before[1] == groups


def test_an_annotated_group_keeps_the_python_lane():
    tsdb, mgr = make_served()
    uri = "/api/query?start=%d&end=%d&m=avg:1m-avg:emit.cpu{host=*}" % (
        BASE, END)
    series = sorted(tsdb.store.all_series(),
                    key=lambda s: tsdb.resolve_key_tags(s.key)["host"])
    tsuid = tsdb.tsuid(series[5].key)
    tsdb.store.add_annotation(Annotation(start_time=(BASE + 60) * 1000,
                                         tsuid=tsuid,
                                         description="reboot"))
    before = emitted("native"), emitted("python")
    answer = json.loads(ask(mgr, uri))
    assert [a["annotations"][0]["description"] for a in answer
            if "annotations" in a] == ["reboot"]
    if native_engine.available():
        assert emitted("native") - before[0] == HOSTS - 1
        assert emitted("python") - before[1] == 1

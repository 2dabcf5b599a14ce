"""A request's host chain by counter (ISSUE 37): the three edges outside
the handler (`tsd.http.edge_ms{route, stage}`: queue, resume, write,
stamped by tsd/server.py and RpcManager.handle_http through
obs/latattr.Edges) and the stages inside plan and dispatch
(`tsd.query.stage_ms{stage}`: consult, rewrite with rw_pieces /
rw_assemble / tail, enqueue, fetch).

What is pinned: a served /api/query moves every one of them; a client
that reads slowly shows in `write` and a saturated executor in `queue`;
request by request the parts nest as claimed (edges + latattr phases
within the client's wall time, rewrite + enqueue within dispatch,
consult within plan); and under jax.profiler the loop thread holds
`write` events with `resume_ms` while a request's first phase event
holds `queue_ms`."""

from __future__ import annotations

import asyncio
import glob
import http.client
import json
import os
import socket
import sys
import threading
import time

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs import latattr
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.tsd.server import TSDServer
from opentsdb_tpu.utils.config import Config

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import trace_gaps  # noqa: E402

BASE = 1_356_998_400
HOSTS = tuple("h%02d" % i for i in range(16))
N = 6000
EDGES = latattr.EDGES
REWRITE_STAGES = ("consult", "rewrite", "rw_pieces", "rw_assemble", "tail",
                  "fetch", "extract")
REWRITE_URI = ("/api/query?start=%d&end=%d&m=sum:60s-sum:hc.i{host=*}"
               % (BASE, BASE + N))
# every stored point of every host back: a body of megabytes
RAW_URI = ("/api/query?start=%d&end=%d&m=sum:hc.i{host=*}"
           % (BASE, BASE + N))
# a tenth of it: a body the socket takes whole
RAW_SMALL_URI = ("/api/query?start=%d&end=%d&m=sum:hc.i{host=*}"
                 % (BASE, BASE + N // 10))


def make_tsdb(**over) -> TSDB:
    cfg = {"tsd.core.auto_create_metrics": True,
           "tsd.query.mesh.enable": False,
           "tsd.query.batch.enable": False,
           "tsd.storage.fix_duplicates": True,
           # the partial-aggregate rewrite from the second sight on
           # (tests/test_agg_cache.py's settings)
           "tsd.query.cache.block_windows": 8,
           "tsd.query.cache.min_repeats": 1,
           "tsd.query.cache.dispatch_overhead_us": 0}
    cfg.update(over)
    tsdb = TSDB(Config(cfg))
    ts = (np.arange(N, dtype=np.int64) + BASE) * 1000
    for i, host in enumerate(HOSTS):
        key = tsdb._series_key("hc.i", {"host": host}, create=True)
        tsdb.store.add_batch(key, ts, (np.arange(N, dtype=np.int64) * 7
                                       + i) % 101, True)
    return tsdb


@pytest.fixture(scope="module")
def daemon():
    """A TSDServer with ONE responder thread on an ephemeral port."""
    tsdb = make_tsdb()
    srv = TSDServer(tsdb, port=0, bind="127.0.0.1", worker_threads=1)
    started = threading.Event()
    holder = {}

    def run():
        async def main():
            await srv.start()
            holder["port"] = srv._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await srv.serve_forever()
        asyncio.run(main())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    srv.test_port = holder["port"]
    for _ in range(3):      # compiles, then the agg cache's blocks
        assert get(srv, REWRITE_URI)[0] == 200
    yield srv
    holder["loop"].call_soon_threadsafe(srv._shutdown_event.set)
    t.join(5)


def route_of(uri: str) -> str:
    return "/".join(uri.split("?")[0].strip("/").split("/")[:2])


def get(srv, uri, headers=None) -> tuple[int, bytes, float]:
    """(status, body, the client's wall seconds from the request's
    first byte out to the answer's last byte in).  Returns once the loop
    has counted the request's edges too: it counts them after its drain,
    which the client's last read may precede."""
    written = edge_ms(route_of(uri))["write"]
    conn = http.client.HTTPConnection("127.0.0.1", srv.test_port,
                                      timeout=60)
    try:
        conn.connect()
        t0 = time.perf_counter()
        conn.request("GET", uri, headers=headers or {})
        resp = conn.getresponse()
        body = resp.read()
        wall = time.perf_counter() - t0
    finally:
        conn.close()
    counted(route_of(uri), written)
    return resp.status, body, wall


def counted(route: str, written: float) -> None:
    """Waits until the route's `write` edge moved past `written`."""
    give_up = time.monotonic() + 10.0
    while edge_ms(route)["write"] == written:
        assert time.monotonic() < give_up, "the edges were never counted"
        time.sleep(0.001)


def edge_ms(route="api/query") -> dict[str, float]:
    fam = REGISTRY.counter("tsd.http.edge_ms")
    return {st: fam.labels(route=route, stage=st).get() for st in EDGES}


def stage_ms(*stages) -> dict[str, float]:
    fam = REGISTRY.counter("tsd.query.stage_ms")
    return {st: fam.labels(stage=st).get() for st in stages}


def phase_ms() -> dict[str, float]:
    fam = REGISTRY.counter("tsd.latattr.phase_ms")
    return {ph: fam.labels(phase=ph).get() for ph in latattr.PHASES}


def delta(after: dict, before: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def test_a_served_query_moves_the_three_edges_and_the_stages_inside(
        daemon):
    e0, s0 = edge_ms(), stage_ms(*REWRITE_STAGES)
    status, body, _wall = get(daemon, REWRITE_URI)
    assert status == 200 and json.loads(body)
    moved_e, moved_s = delta(edge_ms(), e0), delta(
        stage_ms(*REWRITE_STAGES), s0)
    for st in EDGES:
        assert moved_e[st] > 0, (st, moved_e)
    for st in REWRITE_STAGES:
        assert moved_s[st] > 0, (st, moved_s)
    # a route of its own: /api/version moves its own edges, not these
    v0, q0 = edge_ms("api/version"), edge_ms()
    assert get(daemon, "/api/version")[0] == 200
    assert all(v > 0 for v in delta(edge_ms("api/version"), v0).values())
    assert delta(edge_ms(), q0) == dict.fromkeys(EDGES, 0.0)


def test_the_resident_route_times_its_enqueue_and_no_rewrite():
    tsdb = make_tsdb(**{"tsd.query.cache.enable": False})
    manager = RpcManager(tsdb)
    try:
        before = stage_ms("consult", "enqueue", "rewrite", "fetch")
        response = manager.handle_http(
            HttpRequest(method="GET", uri=REWRITE_URI), "127.0.0.1:9"
        ).response
        assert response.status == 200
        moved = delta(stage_ms("consult", "enqueue", "rewrite", "fetch"),
                      before)
        assert moved["consult"] > 0 and moved["enqueue"] > 0
        assert moved["fetch"] > 0 and moved["rewrite"] == 0
        # a caller that is not the event loop has no edges to count
        assert not hasattr(HttpRequest(method="GET", uri="/"), "edges")
    finally:
        tsdb.shutdown()


def big_answer(tsdb) -> tuple[str, int]:
    """A metric whose raw answer is larger than twice the most the
    kernel's TCP send buffer may grow to, the URI that asks for it and
    that size."""
    try:
        with open("/proc/sys/net/ipv4/tcp_wmem") as fh:
            wmem_max = int(fh.read().split()[2])
    except (OSError, ValueError, IndexError):
        wmem_max = 4 << 20
    n = 12000                       # ~215 kB of JSON a host
    hosts = (2 * wmem_max + (1 << 20)) // (n * 17) + 1
    ts = (np.arange(n, dtype=np.int64) + BASE) * 1000
    for i in range(hosts):
        key = tsdb._series_key("hc.big", {"host": "b%04d" % i}, create=True)
        tsdb.store.add_batch(key, ts, (np.arange(n, dtype=np.int64) * 7
                                       + i) % 101, True)
    return ("/api/query?start=%d&end=%d&m=sum:hc.big{host=*}"
            % (BASE, BASE + n)), 2 * wmem_max


def test_a_client_that_reads_slowly_shows_in_write(daemon):
    """The answer outgrows every socket buffer on its way: the loop's
    drain waits for the client, and `write` holds the wait."""
    uri, at_least = big_answer(daemon.tsdb)
    assert get(daemon, uri)[0] == 200              # warm
    hold_s = 0.6
    e0 = edge_ms()
    with socket.socket() as sock:
        # a receive buffer of its own size: the kernel's autotuning
        # would otherwise let the client's side take tens of megabytes
        sock.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 1 << 16)
        sock.settimeout(60)
        sock.connect(("127.0.0.1", daemon.test_port))
        sock.sendall(("GET %s HTTP/1.1\r\nHost: x\r\nConnection: close"
                      "\r\n\r\n" % uri).encode())
        # the write has begun once its first bytes are here; from then
        # on the client reads nothing while it waits
        assert sock.recv(1, socket.MSG_PEEK)
        time.sleep(hold_s)
        got = 0
        while True:
            chunk = sock.recv(1 << 20)
            if not chunk:
                break
            got += len(chunk)
    counted("api/query", e0["write"])
    assert got > at_least
    moved = delta(edge_ms(), e0)
    # the handler's own time is in neither: write alone holds the wait
    assert moved["write"] > 0.8 * hold_s * 1e3, moved
    assert moved["queue"] + moved["resume"] < 0.5 * hold_s * 1e3, moved


def test_a_saturated_executor_shows_in_queue(daemon):
    """The one responder thread is busy: the request waits its turn in
    the executor's queue, and `queue` holds the wait."""
    hold_s = 0.5
    e0 = edge_ms()
    busy = daemon._executor.submit(time.sleep, hold_s)
    status, _body, wall = get(daemon, REWRITE_URI)
    busy.result()
    assert status == 200 and wall >= 0.9 * hold_s
    moved = delta(edge_ms(), e0)
    assert moved["queue"] > 0.8 * hold_s * 1e3, moved
    assert moved["write"] < 0.5 * hold_s * 1e3, moved


@pytest.mark.parametrize("uri", [REWRITE_URI, RAW_SMALL_URI],
                         ids=["rewrite", "raw"])
def test_each_request_adds_up_and_its_stages_nest(daemon, uri):
    """Request by request (one at a time, nothing else served): edges +
    latattr phases within the client's wall time; rewrite + enqueue
    within dispatch; consult within plan."""
    assert get(daemon, uri)[0] == 200              # warm
    for _ in range(5):
        e0, p0 = edge_ms(), phase_ms()
        s0 = stage_ms("consult", "rewrite", "enqueue")
        status, _body, wall = get(daemon, uri)
        assert status == 200
        edges = delta(edge_ms(), e0)
        phases = delta(phase_ms(), p0)
        stages = delta(stage_ms("consult", "rewrite", "enqueue"), s0)
        assert min(edges.values()) > 0
        assert sum(edges.values()) + sum(phases.values()) \
            <= wall * 1e3, (edges, phases, wall)
        assert stages["rewrite"] + stages["enqueue"] <= phases["dispatch"]
        assert 0 <= stages["consult"] <= phases["plan"]
        # a downsample plans its route; a raw union has none to plan
        assert (stages["consult"] > 0) == (uri == REWRITE_URI)


def test_under_a_profile_the_edges_are_on_the_trace(daemon, tmp_path,
                                                    monkeypatch):
    """The loop thread's `write` events carry `resume_ms` and a trace id,
    the handler's `parse` event carries `queue_ms`; two connections whose
    writes interleave on the loop each keep their own event; and
    tools/trace_gaps.py places all three edges."""
    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    ids = ("hc-prof-1", "hc-prof-2")
    # `get` waits for the route's write edge to move, which the OTHER
    # request's write may do first: wait for both ids' own writes
    done, real = [], latattr.Edges.written

    def written(self, resumed, ann):
        real(self, resumed, ann)
        done.append(self.trace_id)
    monkeypatch.setattr(latattr.Edges, "written", written)
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        threads = [threading.Thread(target=get, args=(
            daemon, RAW_URI, {"X-TSDB-Trace-Id": tid})) for tid in ids]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        give_up = time.monotonic() + 10.0
        while not set(ids) <= set(done):
            assert time.monotonic() < give_up, done
            time.sleep(0.001)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    (host,) = [plane for plane in ProfileData.from_file(path).planes
               if plane.name == "/host:CPU"]
    lines = [[(ev.start_ns, ev.duration_ns, dict(ev.stats))
              for ev in ln.events if ev.name == "tsd.phase"]
             for ln in host.lines]
    writes = {ev[2].get("trace_id"): ev for line in lines for ev in line
              if ev[2].get("phase") == "write"}
    parses = {ev[2].get("trace_id"): ev for line in lines for ev in line
              if ev[2].get("phase") == "parse"}
    (loop_line,) = [i for i, line in enumerate(lines)
                    if any(ev[2].get("phase") == "write" for ev in line)]
    for tid in ids:
        start, dur, stats = writes[tid]
        assert float(stats["resume_ms"]) >= 0 and dur > 0
        assert float(parses[tid][2]["queue_ms"]) >= 0
        # the parse event is the handler's, on a responder's line
        assert parses[tid] not in lines[loop_line]
        # a request's write starts after its parse ended
        assert start >= parses[tid][0] + parses[tid][1]
    summary = trace_gaps.reduce_planes(trace_gaps.load(path))
    assert set(EDGES) <= set(summary["phase_s"])
    # the loop's writes overlap no handler's phases in the count
    assert summary["overlapping_phase_events"] == 0

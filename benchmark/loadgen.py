"""The load generator: writers (processes that build and POST /api/put
bodies), an open loop over a pool of keep-alive connections, and
closed-loop replay clients.  All clocks are time.monotonic(), which is
one clock for every process of the machine.  Responses are kept as bytes
and judged after the window: the generator shares the host's cores with
the daemon."""

from __future__ import annotations

import json
import multiprocessing
import os
import threading
import time
from concurrent.futures import ProcessPoolExecutor

import numpy as np

from benchmark.daemon import BenchFailure, Client
from benchmark.tsbs import CADENCE_S, EPOCH_S, TAG_KEYS

# --------------------------------------------------------------------- #
# Writers                                                               #
# --------------------------------------------------------------------- #

_W: dict = {}


def _end_with(runner: int) -> None:
    """Ends this writer within half a second of its runner's end, however
    the runner ended (a SIGKILL leaves it nothing else to go by).  A look
    at the parent's pid, not the daemon's parent-death signal: the kernel
    ties that signal to the THREAD that started the process, the executor
    starts its workers inside submit() — from the loader thread, which
    ends with the load — and starting them all beforehand from the main
    thread cost every run 6 s of set-up on the chip (PR 34)."""
    while os.getppid() == runner:
        time.sleep(0.5)
    os._exit(1)


VSTR = [str(v) for v in range(101)]      # every value a gauge can take


def tag_tails(tags: list[dict]) -> list[str]:
    """Each host's tags as the end of one JSON point."""
    return [',"tags":{%s}}' % ",".join('"%s":"%s"' % (k, t[k])
                                       for k in TAG_KEYS) for t in tags]


def put_body(values, tails: list[str], metric: str, h0: int, h1: int,
             c0: int, c1: int) -> bytes:
    """One /api/put body of one metric: hosts [h0, h1) x columns [c0, c1)
    of its [hosts, points] `values`, host after host, each in time
    order."""
    head = ['{"metric":"%s","timestamp":%d,"value":'
            % (metric, EPOCH_S + CADENCE_S * c) for c in range(c0, c1)]
    parts = []
    for h in range(h0, h1):
        tail = tails[h]
        row = values[h, c0:c1].tolist()
        parts.append(",".join([a + VSTR[v] + tail
                               for a, v in zip(head, row)]))
    return ("[" + ",".join(parts) + "]").encode()


def load_jobs(hosts: int, columns: int, metrics: int) -> list[tuple]:
    """The retained store as (metric, h0, h1, c0, c1) bodies of 50 hosts
    x 720 columns (~36k points, ~8 MB), as PR 21 wrote them, one metric
    after another."""
    return [(f, h0, min(h0 + 50, hosts), c0, min(c0 + 720, columns))
            for f in range(metrics)
            for c0 in range(0, columns, 720)
            for h0 in range(0, hosts, 50)]


def _writer_init(runner: int, port: int, values_path: str,
                 tags: list[dict], metrics: list[str]) -> None:
    threading.Thread(target=_end_with, args=(runner,), daemon=True).start()
    _W["client"] = Client(port)
    _W["values"] = np.load(values_path, mmap_mode="r")  # [metric, host, col]
    _W["tail"] = tag_tails(tags)
    _W["metrics"] = metrics


def _put(f: int, h0: int, h1: int, c0: int, c1: int) -> int:
    """POST one body: metric f, hosts [h0, h1) x columns [c0, c1).
    Returns the points acked; anything but a full ack is a failure."""
    body = put_body(_W["values"][f], _W["tail"], _W["metrics"][f], h0, h1,
                    c0, c1)
    status, reply = _W["client"].request(
        "POST", "/api/put?summary", body,
        {"Content-Type": "application/json"})
    if status != 200:
        raise BenchFailure("/api/put -> %d: %s" % (status, reply[:300]))
    summary = json.loads(reply)
    if summary["failed"] or summary["success"] != (h1 - h0) * (c1 - c0):
        raise BenchFailure("/api/put acked %d of %d points" % (
            summary["success"], (h1 - h0) * (c1 - c0)))
    return summary["success"]


def _load_job(job: tuple[int, int, int, int, int]) -> int:
    return _put(*job)


def _backfill(h0: int, h1: int, c0: int, c_end: int, cols: int,
              start_at: float, stop_at: float) -> list[tuple]:
    """One closed-loop writer: its hosts' columns of the first metric
    from c0 on, `cols` per body, in time order, from `start_at` until
    `stop_at` or the data's end.  No body is issued after `stop_at`; the
    one in flight finishes.  Returns (issued, acked, points, next column)
    per body."""
    while time.monotonic() < start_at:
        time.sleep(min(0.002, max(start_at - time.monotonic(), 0)))
    out = []
    while c0 < c_end:
        issued = time.monotonic()
        if issued >= stop_at:
            break
        c1 = min(c0 + cols, c_end)
        points = _put(0, h0, h1, c0, c1)
        out.append((issued, time.monotonic(), points, c1))
        c0 = c1
    return out


def host_edges(hosts: int, writers: int) -> np.ndarray:
    """Writer w owns hosts [edges[w], edges[w + 1])."""
    return np.linspace(0, hosts, writers + 1).astype(int)


def end_workers() -> None:
    """Ends every multiprocessing child of this process: the writers,
    whether or not a pool has come to own them yet."""
    workers = multiprocessing.active_children()
    for proc in workers:
        proc.terminate()
    for proc in workers:
        proc.join(10.0)


class Writers:
    """A pool of writer processes (spawned: the parent has threads), each
    of which ends with the runner (_end_with)."""

    def __init__(self, processes: int, port: int, values_path: str,
                 tags: list[dict], metrics: list[str]):
        self.pool = ProcessPoolExecutor(
            processes, mp_context=multiprocessing.get_context("spawn"),
            initializer=_writer_init,
            initargs=(os.getpid(), port, values_path, tags, metrics))

    def load(self, hosts: int, columns: int, metrics: int) -> int:
        """The retained store of every metric, through POST /api/put
        (load_jobs)."""
        return sum(self.pool.map(_load_job, load_jobs(hosts, columns,
                                                      metrics), chunksize=4))

    def backfill(self, hosts: int, body_points: int, cursors: list[int],
                 c_end: int, start_at: float, stop_at: float):
        """Start one closed-loop writer per cursor, each owning a slice
        of the hosts (so every series is written in time order, as
        tsbs_load's hashed workers keep it) from its own cursor."""
        edges = host_edges(hosts, len(cursors))
        futures = []
        for w in range(len(cursors)):
            h0, h1 = int(edges[w]), int(edges[w + 1])
            cols = max(body_points // max(h1 - h0, 1), 1)
            futures.append(self.pool.submit(
                _backfill, h0, h1, cursors[w], c_end, cols, start_at,
                stop_at))
        return futures

    def close(self, cut: bool = False) -> None:
        """`cut`: the run is being ended from outside.  What is queued is
        cancelled either way; a cut run does not wait for a body that is
        in flight (to a daemon that may be gone) but ends the workers."""
        self.pool.shutdown(wait=not cut, cancel_futures=True)
        if cut:
            end_workers()


# --------------------------------------------------------------------- #
# Readers                                                               #
# --------------------------------------------------------------------- #

class Record:
    """One request as the load generator saw it (seconds from the
    window's start)."""
    __slots__ = ("req", "due", "sent", "done", "status", "body", "error",
                 "trace_id", "ok", "groups")

    def __init__(self, req: dict, due: float):
        self.req, self.due = req, due
        self.sent = self.done = None
        self.status, self.body, self.error = 0, b"", None
        self.trace_id = None
        self.ok, self.groups = False, 0     # set by the judge


def _send(client: Client, rec: Record, t0: float) -> None:
    headers = {"X-TSDB-Trace-Id": rec.trace_id} if rec.trace_id else {}
    rec.sent = time.monotonic() - t0
    try:
        rec.status, rec.body = client.request("GET", rec.req["path"],
                                              headers=headers)
    except Exception as e:             # judged after the window
        rec.error = "%s: %s" % (type(e).__name__, e)
    rec.done = time.monotonic() - t0


def run_open(port: int, connections: int, due: np.ndarray,
             records: list[Record], t0: float, drain_s: float) -> None:
    """Open loop: request i is due at t0 + due[i] whatever the daemon
    does.  `connections` keep-alive clients take requests in order; one
    that is due while all are busy waits, and is timed from its due
    time.  Stops `drain_s` after the last due time: what was never sent
    by then stays unsent (and counts as failed)."""
    lock = threading.Lock()
    nxt = [0]
    give_up = t0 + (float(due[-1]) if len(due) else 0.0) + drain_s

    def worker() -> None:
        client = Client(port, timeout=drain_s + 60.0)
        try:
            while True:
                with lock:
                    i = nxt[0]
                    nxt[0] += 1
                if i >= len(records):
                    return
                wait = t0 + due[i] - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                if time.monotonic() > give_up:
                    return
                _send(client, records[i], t0)
        finally:
            client.close()

    threads = [threading.Thread(target=worker, daemon=True)
               for _ in range(connections)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def run_closed(port: int, clients: int, cycle: list[dict], t0: float,
               seconds: float, trace_every: int) -> list[Record]:
    """Closed loop: `clients` replay one list, client k starting k/clients
    of a cycle in.  No request is issued after `seconds`; those in
    flight finish and count."""
    out: list[list[Record]] = [[] for _ in range(clients)]

    def worker(k: int) -> None:
        client = Client(port, timeout=600.0)
        i = k * len(cycle) // clients
        try:
            while time.monotonic() - t0 < seconds:
                rec = Record(cycle[i % len(cycle)], time.monotonic() - t0)
                if trace_every and len(out[k]) % trace_every == 0:
                    rec.trace_id = "bench%dn%d" % (k, len(out[k]))
                _send(client, rec, t0)
                out[k].append(rec)
                i += 1
        finally:
            client.close()

    threads = [threading.Thread(target=worker, args=(k,), daemon=True)
               for k in range(clients)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return [r for recs in out for r in recs]

#!/usr/bin/env python3
"""chip_smoke.py — does the served path come up on the chip, and is it right?

One TSD daemon is started the way users start it
(`python -m opentsdb_tpu.tools.tsd_main --port ... --config ...`, default
configuration plus port, bind and metric auto-creation), a TSBS DevOps
`cpu-only` fleet is written through `POST /api/put` and telnet `put`, and
the TSBS dashboard queries plus the heavy tail are sent twice each (cold =
compile, warm) and compared with a plain numpy evaluation of the same
semantics on the same generated data.  Every phase can fail the run.

Deployment (source: Time Series Benchmark Suite, github.com/timescale/tsbs,
use case `cpu-only`, scale 4000; the OpenTSDB query shapes follow its
predecessor influxdata/influxdb-comparisons): 4000 hosts, 10 s cadence,
integer cpu gauges walking in [0, 100], host tags hostname / region /
datacenter / rack / os / arch / team / service.  Width is never cut; the
cuts of depth are listed under `reduced` in the summary.

One process holds the chip: THIS process never imports jax (checked before
every spawn).  It generates data and reference with numpy, spawns the daemon
as its only JAX child, drives it over sockets and stops it with SIGTERM;
the k-scaling probe then runs in a second child, after the daemon is gone.

Exit code 0 and a last stdout line
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`
only when every phase passed on a TPU.  `--platform cpu` is the explicit
dry run for sandboxes and unit tests (tiny `--hosts/--hours`); it labels
every line `cpu` and is never the default.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.parse
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))

# TSBS DevOps cpu-only: the ten cpu gauges of one host.  `--metrics N`
# ingests the first N; the queries run on the first.
CPU_FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
              "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
              "usage_guest", "usage_guest_nice")
# TSBS host tag vocabulary (devops common generator).  Eight of TSBS's ten
# tags: the reference caps a series at 8 tags (Const.java:28,
# storage/memstore.py MAX_NUM_TAGS); service_version and
# service_environment are the two dropped.
REGIONS = {
    "us-east-1": "abcde", "us-west-1": "ab", "us-west-2": "abc",
    "eu-west-1": "abc", "eu-central-1": "ab", "ap-southeast-1": "ab",
    "ap-southeast-2": "ab", "ap-northeast-1": "ac", "sa-east-1": "abc",
}
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
TAG_KEYS = ("hostname", "region", "datacenter", "rack", "os", "arch",
            "team", "service")
EPOCH_S = 1451606400          # 2016-01-01T00:00:00Z, TSBS's default start
CADENCE_S = 10
HOST_LANE_MAX_POINTS = 2_000_000   # tsd.query.host_lane.max_points default

# Kernel names as jax's compile log prints them: the jitted entry points
# of ops/pipeline.py, ops/streaming.py and ops/tiling.py.  The summary
# says which of them this run's traffic compiled on the device.
ENTRY_POINTS = (
    "jit(_pipeline)", "jit(_union_batch_pipeline)",
    "jit(_rollup_avg_pipeline)", "jit(_group_pipeline)",
    "jit(_stacked_group_pipeline)", "jit(_grid_tail)",
    "jit(_downsample_grid)", "jit(_lane_partials)",
    "jit(_group_rollup_avg)", "jit(_update)", "jit(_update_sliced)",
    "jit(_finish)", "jit(_tile_contrib)", "jit(_group_presence)",
    "jit(_lane_fold)")


class SmokeFailure(Exception):
    """A phase failed; the run exits non-zero."""


def assert_no_jax() -> None:
    if "jax" in sys.modules or "opentsdb_tpu.ops" in sys.modules:
        raise SmokeFailure("the smoke's parent process imported jax — it "
                           "would hold the chip its children need")


# --------------------------------------------------------------------- #
# Data: generated from --seed, numpy only                               #
# --------------------------------------------------------------------- #

def make_fleet(hosts: int, seed: int) -> list[dict]:
    """Tag sets of `hosts` TSBS hosts, deterministic in `seed`."""
    rng = np.random.default_rng([seed, 0])
    regions = sorted(REGIONS)
    fleet = []
    for h in range(hosts):
        region = regions[int(rng.integers(len(regions)))]
        zones = REGIONS[region]
        fleet.append({
            "hostname": "host_%d" % h,
            "region": region,
            "datacenter": region + zones[int(rng.integers(len(zones)))],
            "rack": str(int(rng.integers(100))),
            "os": OSES[int(rng.integers(len(OSES)))],
            "arch": ARCHES[int(rng.integers(len(ARCHES)))],
            "team": TEAMS[int(rng.integers(len(TEAMS)))],
            "service": str(int(rng.integers(20))),
        })
    return fleet


def make_values(hosts: int, points: int, seed: int, field: int
                ) -> np.ndarray:
    """[hosts, points] int64 gauge values: TSBS's clamped random walk
    (start uniform in [0, 100], unit-normal steps rounded to integers,
    clamped to [0, 100] at every step)."""
    rng = np.random.default_rng([seed, 1, field])
    out = np.empty((hosts, points), np.int64)
    cur = rng.integers(0, 101, hosts)
    steps = np.rint(rng.normal(0.0, 1.0, (points, hosts))).astype(np.int64)
    for i in range(points):
        cur = np.clip(cur + steps[i], 0, 100)
        out[:, i] = cur
    return out


def timestamps(points: int) -> np.ndarray:
    return EPOCH_S + CADENCE_S * np.arange(points, dtype=np.int64)


# --------------------------------------------------------------------- #
# Reference: plain numpy evaluation of the query semantics              #
# --------------------------------------------------------------------- #

def ref_downsample(ts: np.ndarray, vals: np.ndarray, interval_s: int,
                   fn: str) -> tuple[np.ndarray, np.ndarray]:
    """Epoch-aligned fixed windows over sorted `ts` [N] and `vals`
    [S, N]: each window's timestamp is its start, its value the `fn` of
    the points inside.  Returns (window_ts [W], grid [S, W] float64)."""
    win = ts - ts % interval_s
    wts, first = np.unique(win, return_index=True)
    v = vals.astype(np.float64)
    if fn == "avg":
        counts = np.diff(np.append(first, len(ts)))
        grid = np.add.reduceat(v, first, axis=1) / counts
    elif fn == "max":
        grid = np.maximum.reduceat(v, first, axis=1)
    else:
        raise ValueError("reference has no downsample fn %r" % fn)
    return wts, grid


def ref_rate(wts: np.ndarray, grid: np.ndarray
             ) -> tuple[np.ndarray, np.ndarray]:
    """Per-second first difference; the first point of each series has
    no predecessor and is dropped (RateSpan semantics, no counter)."""
    return wts[1:], np.diff(grid, axis=1) / np.diff(wts).astype(np.float64)


def ref_percentile(col: np.ndarray, q: float) -> float:
    """commons-math3 LEGACY estimation (what OpenTSDB's pNN aggregators
    use): pos = q(n+1)/100, linear interpolation between the order
    statistics around pos, clamped to the extremes."""
    s = np.sort(col)
    n = len(s)
    pos = q * (n + 1) / 100.0
    if pos < 1:
        return float(s[0])
    if pos >= n:
        return float(s[-1])
    k = int(np.floor(pos))
    return float(s[k - 1] + (pos - k) * (s[k] - s[k - 1]))


def ref_aggregate(grid: np.ndarray, agg: str) -> np.ndarray:
    """Cross-series aggregate of a gap-free [S, W] grid -> [W]."""
    if agg == "sum":
        return grid.sum(axis=0)
    if agg == "avg":
        return grid.mean(axis=0)
    if agg == "max":
        return grid.max(axis=0)
    m = re.fullmatch(r"p(\d+)", agg)
    if m:
        return np.array([ref_percentile(grid[:, w], float(m.group(1)))
                         for w in range(grid.shape[1])])
    raise ValueError("reference has no aggregator %r" % agg)


def ref_query(fleet: list[dict], ts: np.ndarray, vals: np.ndarray,
              req: dict) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    """{group tag value: (timestamps [W], values [W])} for one request:
    filter hosts, cut the time range (end inclusive), downsample, rate,
    then aggregate each group across its series."""
    wanted = req.get("hosts")
    rows = [h for h, tags in enumerate(fleet)
            if wanted is None or tags["hostname"] in wanted]
    sel = (ts >= req["start"]) & (ts <= req["end"])
    wts, grid = ref_downsample(ts[sel], vals[np.asarray(rows)][:, sel],
                               req["interval_s"], req["ds_fn"])
    if req.get("rate"):
        wts, grid = ref_rate(wts, grid)
    members: dict[str, list[int]] = {}
    for i, h in enumerate(rows):
        members.setdefault(fleet[h][req["group_by"]], []).append(i)
    return {group: (wts, ref_aggregate(grid[np.asarray(idx)], req["agg"]))
            for group, idx in members.items()}


def compare(got: dict, want: dict) -> str | None:
    """None when the answer equals the reference, else what differs.
    Integer-valued references compare exactly; the rest to 1e-9
    relative (the README's numeric contract), absolute below 1 — a rate
    sum that cancels to zero has no relative scale."""
    if set(got) != set(want):
        return "groups differ: %d answered, %d expected (e.g. %s)" % (
            len(got), len(want),
            sorted(set(got) ^ set(want))[:3])
    for group, (wts, wval) in want.items():
        gts, gval = got[group]
        if len(gts) != len(wts) or not np.array_equal(gts, wts):
            return "group %s: timestamps differ (%d vs %d points)" % (
                group, len(gts), len(wts))
        if np.array_equal(wval, np.rint(wval)):
            bad = gval != wval
        else:
            bad = np.abs(gval - wval) > 1e-9 * np.maximum(np.abs(wval), 1.0)
        if bad.any():
            i = int(np.argmax(bad))
            return "group %s @%d: got %r, reference %r" % (
                group, int(wts[i]), float(gval[i]), float(wval[i]))
    return None


# --------------------------------------------------------------------- #
# The request table                                                     #
# --------------------------------------------------------------------- #

def request_table(hosts: int, hours: int, seed: int) -> list[dict]:
    """The TSBS dashboard queries and the heavy tail, sized from the
    fleet: time spans are the table's (1 h, 2 h, 5 h, 12 h, 24 h) cut to
    what was ingested."""
    rng = np.random.default_rng([seed, 2])
    pick = ["host_%d" % h for h in
            rng.choice(hosts, size=min(8, hosts), replace=False)]

    def span(h: float, offset_h: float = 0.0) -> tuple[int, int]:
        h = min(h, hours)
        start = EPOCH_S + int(min(offset_h, hours - h) * 3600)
        return start, start + int(h * 3600) - 1     # end is inclusive

    def req(name, m, hrs, **kw):
        start, end = span(hrs, kw.pop("offset_h", 0.0))
        pts = kw.get("n_hosts", hosts) * ((end - start) // CADENCE_S + 1)
        return dict(name=name, m=m, start=start, end=end, points=pts, **kw)

    one = pick[0]
    eight = "|".join(pick)
    # Order matters to the routes: the 12 h scan goes BEFORE any request
    # pins the metric in the device cache, because only a cold cache
    # leaves a streaming-size query on the streamed fold (a warm entry
    # diverts it to the resident kernel, query/plandecision.py).  The
    # scan queues the background pin, so everything after it — the warm
    # 12 h send included — meets the columns in HBM.  On a mesh even the
    # 8-host request pins (>= 8 series is a mesh plan, which the host
    # lane and the batcher decline), so it goes after the scans.
    table = [
        req("single-groupby-1-1-1",
            "max:1m-max:cpu.usage_user{hostname=%s}" % one, 1,
            offset_h=hours / 2, n_hosts=1, hosts={one},
            group_by="hostname", interval_s=60, ds_fn="max", agg="max"),
        req("double-groupby-1-12h",
            "avg:1h-avg:cpu.usage_user{hostname=*}", 12,
            group_by="hostname", interval_s=3600, ds_fn="avg", agg="avg"),
        req("double-groupby-1-24h",
            "avg:1h-avg:cpu.usage_user{hostname=*}", 24,
            group_by="hostname", interval_s=3600, ds_fn="avg", agg="avg"),
        req("single-groupby-1-8-1",
            "max:1m-max:cpu.usage_user{hostname=%s}" % eight, 1,
            offset_h=hours / 2, n_hosts=len(pick), hosts=set(pick),
            group_by="hostname", interval_s=60, ds_fn="max", agg="max"),
        req("region-sum-2h", "sum:1m-avg:cpu.usage_user{region=*}", 2,
            group_by="region", interval_s=60, ds_fn="avg", agg="sum"),
        req("region-rate-2h", "sum:rate:1m-avg:cpu.usage_user{region=*}",
            2, group_by="region", interval_s=60, ds_fn="avg", agg="sum",
            rate=True),
        req("datacenter-p99-5h",
            "p99:10m-avg:cpu.usage_user{datacenter=*}", 5,
            group_by="datacenter", interval_s=600, ds_fn="avg",
            agg="p99"),
    ]
    # a span cut by --hours can collapse two rows into one request: the
    # second would hit the first's caches and report a route it did not
    # earn, so keep the first of each distinct (m, start, end)
    seen, out = set(), []
    for r in table:
        key = (r["m"], r["start"], r["end"])
        if key not in seen:
            seen.add(key)
            out.append(r)
    return out


# --------------------------------------------------------------------- #
# Talking to the daemon                                                 #
# --------------------------------------------------------------------- #

class Client:
    """One keep-alive HTTP connection to the daemon."""

    def __init__(self, port: int, timeout: float = 900.0):
        self.conn = http.client.HTTPConnection("127.0.0.1", port,
                                               timeout=timeout)

    def request(self, method: str, path: str, body: bytes | None = None,
                headers: dict | None = None) -> tuple[int, bytes]:
        try:
            self.conn.request(method, path, body=body,
                              headers=headers or {})
            resp = self.conn.getresponse()
            return resp.status, resp.read()
        except (http.client.HTTPException, OSError):
            self.conn.close()       # next call reconnects
            raise

    def get_json(self, path: str):
        status, body = self.request("GET", path)
        if status != 200:
            raise SmokeFailure("GET %s -> %d: %s"
                               % (path, status, body[:400]))
        return json.loads(body)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start_daemon(port: int, out_dir: str, env: dict) -> subprocess.Popen:
    assert_no_jax()
    conf = os.path.join(out_dir, "tsd.conf")
    with open(conf, "w") as fh:
        fh.write("tsd.core.auto_create_metrics = true\n")
    log = open(os.path.join(out_dir, "daemon.log"), "wb")
    try:
        return subprocess.Popen(
            [sys.executable, "-m", "opentsdb_tpu.tools.tsd_main",
             "--port", str(port), "--bind", "127.0.0.1", "--config", conf],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT)
    finally:
        log.close()         # the child holds its own descriptor


def wait_ready(proc: subprocess.Popen, port: int, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise SmokeFailure("daemon exited with rc=%d before serving "
                               "(see daemon.log)" % proc.returncode)
        try:
            with socket.create_connection(("127.0.0.1", port), 1.0):
                return
        except OSError:
            time.sleep(0.25)
    raise SmokeFailure("daemon did not listen on :%d within %.0fs"
                       % (port, timeout))


def stop_daemon(proc: subprocess.Popen, out_dir: str) -> None:
    """SIGTERM -> graceful path: rc 0 and "Server shut down" logged."""
    if proc.poll() is not None:
        raise SmokeFailure("daemon was already dead (rc=%d) at shutdown"
                           % proc.returncode)
    proc.send_signal(signal.SIGTERM)
    try:
        rc = proc.wait(timeout=120)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        raise SmokeFailure("daemon ignored SIGTERM for 120s; killed")
    with open(os.path.join(out_dir, "daemon.log"), "rb") as fh:
        tail = fh.read()[-20000:].decode("utf-8", "replace")
    if rc != 0 or "Server shut down" not in tail:
        raise SmokeFailure("daemon shutdown was not graceful: rc=%d, "
                           "'Server shut down' logged: %s"
                           % (rc, "Server shut down" in tail))


def device_section(client: Client) -> dict:
    # since=<huge>: the full-ring view (which carries `device`) without
    # shipping the ring itself
    return client.get_json("/api/diag?since=999999999999")["device"]


def stat_values(records: list[dict], metric: str) -> dict[str, float]:
    """{"k=v,..." tag string (host tag dropped): value} of one metric in
    an /api/stats reply."""
    out = {}
    for rec in records:
        if rec["metric"] == metric:
            tags = ",".join("%s=%s" % kv for kv in sorted(
                rec["tags"].items()) if kv[0] != "host")
            out[tags] = float(rec["value"])
    return out


def compile_counts(client: Client) -> dict[str, int]:
    status, body = client.request("GET", "/api/stats/prometheus")
    if status != 200:
        raise SmokeFailure("/api/stats/prometheus -> %d" % status)
    out = {}
    for m in re.finditer(
            r'^tsd_jax_compiles_total\{kernel="([^"]+)"\} (\d+)',
            body.decode(), re.M):
        out[m.group(1)] = int(m.group(2))
    return out


# --------------------------------------------------------------------- #
# Phases                                                                #
# --------------------------------------------------------------------- #

def build_native() -> None:
    """Rebuild native/libtsdb_engine.so from the tracked engine.cpp (the
    .so is git-ignored; a checkout has none).  Without it every put
    silently takes the Python parser."""
    native = os.path.join(REPO, "native")
    proc = subprocess.run(["make", "-C", native, "-B"],
                          capture_output=True, text=True, timeout=300)
    if proc.returncode != 0 or not os.path.exists(
            os.path.join(native, "libtsdb_engine.so")):
        raise SmokeFailure("native build failed (rc=%d): %s"
                           % (proc.returncode, proc.stderr[-800:]))


def ingest(port: int, fleet: list[dict], ts: np.ndarray,
           values: list[np.ndarray], telnet_points: int, threads: int
           ) -> dict:
    """Write every point through the product's own entry points: HTTP
    `POST /api/put?summary` bodies (acked counts summed), and the last
    `telnet_points` points of every series through telnet `put` lines
    (no positive ack exists there; /api/stats and the read-back hold
    them to account)."""
    n_hosts, n_pts = values[0].shape
    http_pts = n_pts - telnet_points
    vstr = [str(v) for v in range(101)]
    tag_json = [",".join('"%s":"%s"' % (k, t[k]) for k in TAG_KEYS)
                for t in fleet]
    tag_telnet = [" ".join("%s=%s" % (k, t[k]) for k in TAG_KEYS)
                  for t in fleet]
    # ~36k points (~8 MB of JSON) per body: 50 hosts x 720 points
    col_chunk, host_chunk = 720, 50
    jobs = [(f, h0, c0)
            for f in range(len(values))
            for c0 in range(0, http_pts, col_chunk)
            for h0 in range(0, n_hosts, host_chunk)]
    acked = [0] * len(jobs)
    local = threading.local()
    clients: list[Client] = []

    def send(i: int) -> None:
        f, h0, c0 = jobs[i]
        c1 = min(c0 + col_chunk, http_pts)
        head = ['{"metric":"cpu.%s","timestamp":%d,"value":'
                % (CPU_FIELDS[f], t) for t in ts[c0:c1]]
        parts = []
        for h in range(h0, min(h0 + host_chunk, n_hosts)):
            tail = ',"tags":{%s}}' % tag_json[h]
            row = values[f][h, c0:c1].tolist()
            parts.append(",".join([a + vstr[v] + tail
                                   for a, v in zip(head, row)]))
        body = ("[" + ",".join(parts) + "]").encode()
        if not hasattr(local, "client"):
            local.client = Client(port, timeout=300.0)
            clients.append(local.client)
        status, reply = local.client.request(
            "POST", "/api/put?summary", body,
            {"Content-Type": "application/json"})
        if status != 200:
            raise SmokeFailure("/api/put -> %d: %s" % (status, reply[:300]))
        summary = json.loads(reply)
        if summary["failed"]:
            raise SmokeFailure("/api/put refused %d points" %
                               summary["failed"])
        acked[i] = summary["success"]

    t0 = time.monotonic()
    try:
        with ThreadPoolExecutor(threads) as pool:
            for fut in [pool.submit(send, i) for i in range(len(jobs))]:
                fut.result()
    finally:
        for c in clients:
            c.conn.close()
    http_s = time.monotonic() - t0
    http_sent = len(values) * n_hosts * http_pts
    if sum(acked) != http_sent:
        raise SmokeFailure("/api/put acked %d of %d points sent"
                           % (sum(acked), http_sent))

    # telnet: one connection, put lines in time order, then `version`
    # — its reply proves the daemon consumed everything before it
    t1 = time.monotonic()
    telnet_sent = 0
    with socket.create_connection(("127.0.0.1", port), 30.0) as sock:
        sock.settimeout(300.0)
        for f in range(len(values)):
            for h in range(n_hosts):
                row = values[f][h, http_pts:].tolist()
                sock.sendall("".join(
                    "put cpu.%s %d %d %s\n"
                    % (CPU_FIELDS[f], t, v, tag_telnet[h])
                    for t, v in zip(ts[http_pts:].tolist(), row)).encode())
                telnet_sent += len(row)
        sock.sendall(b"version\n")
        reply = b""
        while b"opentsdb_tpu" not in reply:
            chunk = sock.recv(65536)
            if not chunk:
                break
            reply += chunk
    if b"put:" in reply or b"opentsdb_tpu" not in reply:
        raise SmokeFailure("telnet put errors / no version reply: %r"
                           % reply[:400])
    telnet_s = time.monotonic() - t1
    return {"httpPoints": http_sent, "httpAcked": sum(acked),
            "httpSeconds": round(http_s, 3),
            "httpPointsPerSec": round(http_sent / http_s),
            "telnetPoints": telnet_sent,
            "telnetSeconds": round(telnet_s, 3),
            "sent": http_sent + telnet_sent, "bodies": len(jobs)}


def check_ingest_stats(client: Client, sent: int, ing: dict) -> dict:
    """acked == sent == stored, and the native parser — not the Python
    fallback — served every write request."""
    stats = client.get_json("/api/stats")
    added = stat_values(stats, "tsd.datapoints.added").get("", -1)
    stored = stat_values(stats, "tsd.storage.datapoints").get("", -1)
    parser = stat_values(stats, "tsd.put.parser")
    native = int(parser.get("parser=native", 0))
    fallback = int(parser.get("parser=python", 0))
    ing.update(added=int(added), stored=int(stored),
               nativeRequests=native, pythonRequests=fallback,
               parser="native" if native and not fallback else "python")
    if added != sent or stored != sent:
        raise SmokeFailure("sent %d points; daemon added %d, stores %d"
                           % (sent, added, stored))
    if fallback or native < ing["bodies"]:
        raise SmokeFailure(
            "the put path took the Python fallback parser (%d requests; "
            "native served %d of %d bodies) — is "
            "native/libtsdb_engine.so built and loadable?"
            % (fallback, native, ing["bodies"]))
    return ing


def parse_answer(payload: list, group_by: str) -> dict:
    out = {}
    for r in payload:
        if "metric" not in r:
            continue            # statsSummary trailer
        items = sorted((int(k), v) for k, v in r["dps"].items())
        out[r["tags"][group_by]] = (
            np.array([k for k, _ in items], np.int64),
            np.array([v for _, v in items], np.float64))
    return out


def read_back(client: Client, fleet, ts, values, seed: int) -> dict:
    """Raw (`none:` aggregator) read-back of a sample of series over the
    whole ingested range: every acked write, value for value."""
    n_hosts, n_pts = values[0].shape
    rng = np.random.default_rng([seed, 3])
    sample = sorted(int(h) for h in rng.choice(
        n_hosts, size=min(12, n_hosts), replace=False))
    # always hold the telnet-written tail and both ends of the fleet to it
    sample = sorted(set(sample) | {0, n_hosts - 1})
    checked = 0
    for f in range(len(values)):
        m = "none:cpu.%s{hostname=%s}" % (
            CPU_FIELDS[f], "|".join(fleet[h]["hostname"] for h in sample))
        path = "/api/query?start=%d&end=%d&m=%s" % (
            ts[0], ts[-1], urllib.parse.quote(m, safe=""))
        got = parse_answer(client.get_json(path), "hostname")
        for h in sample:
            gts, gval = got.get(fleet[h]["hostname"], ((), ()))
            if (len(gts) != n_pts or not np.array_equal(gts, ts)
                    or not np.array_equal(gval, values[f][h])):
                raise SmokeFailure(
                    "read-back of cpu.%s %s differs from what was "
                    "written (%d of %d points returned)"
                    % (CPU_FIELDS[f], fleet[h]["hostname"], len(gts),
                       n_pts))
            checked += n_pts
    return {"series": len(sample) * len(values), "points": checked}


def last_points(client: Client, fleet, ts, values, req_hosts) -> dict:
    """TSBS lastpoint through /api/query/last: no device work, must
    still answer with each host's final write."""
    spec = "cpu.usage_user{hostname=%s}" % "|".join(sorted(req_hosts))
    t0 = time.monotonic()
    got = client.get_json("/api/query/last?timeseries="
                          + urllib.parse.quote(spec, safe=""))
    seconds = time.monotonic() - t0
    by_host = {r["tags"]["hostname"]: r for r in got}
    index = {t["hostname"]: h for h, t in enumerate(fleet)}
    for name in req_hosts:
        r = by_host.get(name)
        want = int(values[0][index[name], -1])
        if (r is None or int(r["timestamp"]) != int(ts[-1]) * 1000
                or int(r["value"]) != want):
            raise SmokeFailure("lastpoint for %s: got %r, wrote %d @%d"
                               % (name, r, want, ts[-1]))
    return {"hosts": len(req_hosts), "seconds": round(seconds, 4),
            "correct": True}


def send_query(client: Client, req: dict, trace_id: str,
               want: dict) -> dict:
    """One send of one request: explain (the route the daemon will
    take), execute with show_stats under a known trace id, compare with
    the reference, then read the executed route back from the flight
    recorder and the query-stats ring."""
    qs = "start=%d&end=%d&m=%s" % (
        req["start"], req["end"], urllib.parse.quote(req["m"], safe=""))
    seg = client.get_json("/api/query/explain?" + qs
                          )["subQueries"][0]["segments"][0]
    prov = seg["provenance"]
    before = compile_counts(client)
    t0 = time.monotonic()
    status, body = client.request(
        "GET", "/api/query?" + qs + "&show_stats",
        headers={"X-TSDB-Trace-Id": trace_id})
    seconds = time.monotonic() - t0
    if status != 200:
        raise SmokeFailure("%s -> %d: %s"
                           % (req["name"], status, body[:600]))
    after = compile_counts(client)
    payload = json.loads(body)
    diff = compare(parse_answer(payload, req["group_by"]), want)
    events = client.get_json("/api/diag?trace_id=" + trace_id)["events"]
    plans = [e for e in events if e["kind"] == "plan"]
    if not plans:
        raise SmokeFailure("%s: no plan event for trace %s"
                           % (req["name"], trace_id))
    plan = plans[0]
    exec_stats = {}
    for done in client.get_json("/api/stats/query")["completed"]:
        if (done.get("trace") or {}).get("traceId") == trace_id:
            exec_stats = done.get("stats", {})
    compiled = {k: after[k] - before.get(k, 0) for k in after
                if after[k] > before.get(k, 0)}
    return {
        "seconds": round(seconds, 4),
        "correct": diff is None, "diff": diff,
        "path": plan["path"],
        "planMatchesExplain": plan["fingerprint"] == seg["fingerprint"],
        "platform": prov["platform"],
        "hostLane": bool(exec_stats.get("hostLane")),
        "batched": bool(exec_stats.get("batched")),
        "deviceCacheHit": bool(plan.get("deviceCacheHit")),
        "meshDevices": int(exec_stats.get("meshDevices", 0)),
        "aggCache": (plan.get("aggCache") or {}).get("reason"),
        "compiled": compiled,
    }


def run_requests(client: Client, fleet, ts, vals, table: list[dict],
                 device: dict, label: str, failures: list[str]
                 ) -> list[dict]:
    rows = []
    for i, req in enumerate(table):
        want = ref_query(fleet, ts, vals, req)
        row = {"name": req["name"], "m": req["m"],
               "points": req["points"],
               "spanSeconds": req["end"] - req["start"] + 1}
        # cold, then again until a send compiles nothing (a repeat can
        # change route — the partial-aggregate cache engages on the
        # second sight of a plan — and that route's first send compiles
        # too): `warm` is the last send, and says what it still compiled
        sends = [send_query(client, req, "smoke%02dsend0" % i, want)]
        while len(sends) < 2 or (sends[-1]["compiled"] and len(sends) < 4):
            sends.append(send_query(
                client, req, "smoke%02dsend%d" % (i, len(sends)), want))
        row.update(cold=sends[0], warm=sends[-1], sends=len(sends),
                   between=sends[1:-1],
                   correct=all(r["correct"] for r in sends))
        rows.append(row)
        say(label, "%-22s %9d pts  cold %8.3fs (%s)  warm %8.3fs (%s, "
            "send %d)  %s"
            % (req["name"], req["points"], row["cold"]["seconds"],
               route_text(row["cold"]), row["warm"]["seconds"],
               route_text(row["warm"]), len(sends),
               "correct" if row["correct"] else "WRONG"))
        for n, r in enumerate(sends):
            if not r["correct"]:
                failures.append("%s (send %d): %s"
                                % (req["name"], n, r["diff"]))
            if not r["planMatchesExplain"]:
                failures.append("%s (send %d): executed plan differs "
                                "from explain's" % (req["name"], n))
        # a query the host lane cannot take must run on the default
        # backend, and the cold send must have compiled something there
        if req["points"] > HOST_LANE_MAX_POINTS:
            for n, r in enumerate(sends):
                if r["platform"] != device["platform"] or r["hostLane"]:
                    failures.append(
                        "%s (send %d): %d points planned for platform=%s "
                        "hostLane=%s on a %s daemon"
                        % (req["name"], n, req["points"], r["platform"],
                           r["hostLane"], device["platform"]))
                if (device["count"] > 1
                        and r["meshDevices"] != device["count"]):
                    failures.append(
                        "%s (send %d): meshDevices=%d on %d devices"
                        % (req["name"], n, r["meshDevices"],
                           device["count"]))
            if not row["cold"]["compiled"]:
                failures.append("%s: the cold send compiled nothing"
                                % req["name"])
    return rows


def pick_hosts(table: list[dict]) -> list[str]:
    """The 8 hosts of single-groupby-1-8-1 (lastpoint asks for them too)."""
    return sorted(next(r for r in table
                       if r["name"] == "single-groupby-1-8-1")["hosts"])


def route_text(r: dict) -> str:
    bits = [r["path"], r["platform"]]
    for flag in ("hostLane", "batched", "deviceCacheHit"):
        if r[flag]:
            bits.append(flag)
    if r["meshDevices"]:
        bits.append("mesh%d" % r["meshDevices"])
    return "/".join(bits)


def check_device_memory(device: dict, pinned_bytes: int,
                        failures: list[str]) -> None:
    """After the warm full-width query: the pinned metric's columns must
    have lived in device memory (the only outside-visible proof), and on
    a multi-device host every device must have held some of the work."""
    peaks = [m["peakBytesInUse"] for m in device["memory"]]
    if any(p is None for p in peaks):
        if device["platform"] != "cpu":
            failures.append("device memory_stats() unavailable on %s"
                            % device["platform"])
        return
    if sum(peaks) < pinned_bytes:
        failures.append("peak device bytes %d < the pinned metric's %d: "
                        "the columns never lived on the device"
                        % (sum(peaks), pinned_bytes))
    idle = [m["id"] for m in device["memory"]
            if m["peakBytesInUse"] < (1 << 20)]
    if idle:
        failures.append("devices %s never held 1 MiB: the mesh left the "
                        "work on the others" % idle)


def kernel_report(rows: list[dict], device: dict) -> dict:
    """Which jitted entry points compiled during sends that ran on the
    default backend (the chip), and which this traffic did not reach."""
    reached: dict[str, int] = {}
    for row in rows:
        for r in [row["cold"], *row["between"], row["warm"]]:
            if r["platform"] == device["platform"] and not r["hostLane"]:
                for k, n in r["compiled"].items():
                    reached[k] = reached.get(k, 0) + n
    return {
        "entryPointsCompiled": sorted(k for k in ENTRY_POINTS
                                      if k in reached),
        "entryPointsNotReached": sorted(k for k in ENTRY_POINTS
                                        if k not in reached),
        "allCompiledOnDefaultBackend": dict(sorted(reached.items())),
    }


def run_kprobe(env: dict, out_dir: str, label: str) -> dict:
    """The k-scaling probe, in a fresh child after the daemon has gone:
    does jax.block_until_ready wait for the device on this installation?
    One fixed shape per platform: 16.8M points on the chip, 1M in the
    cpu dry run (whose timings say nothing about a device anyway)."""
    assert_no_jax()
    out = os.path.join(out_dir, "kprobe.json")
    points_per_series = 16384 if label == "tpu" else 1024
    with open(os.path.join(out_dir, "kprobe.log"), "wb") as log:
        rc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--child-kprobe",
             out, str(points_per_series)], cwd=REPO, env=env, stdout=log,
            stderr=subprocess.STDOUT, timeout=600).returncode
    if rc != 0:
        raise SmokeFailure("k-scaling probe child rc=%d (see kprobe.log)"
                           % rc)
    with open(out) as fh:
        probe = json.load(fh)
    for k, blk, fetch in zip(probe["k"], probe["blockSeconds"],
                             probe["fetchSeconds"]):
        say(label, "kprobe k=%d  block_until_ready %.4fs  host fetch %.4fs"
            % (k, blk, fetch))
    return probe


def kprobe_child(out_path: str, n: int) -> int:
    """Child process (the only place this file imports jax): k unique
    run_group_pipeline dispatches at one fixed shape ended by
    jax.block_until_ready, against the same k ended by a host fetch of
    one scalar per output leaf."""
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from opentsdb_tpu.ops.downsample import FixedWindows, pad_pow2
    from opentsdb_tpu.ops.pipeline import (DownsampleStep, PipelineSpec,
                                           run_group_pipeline)
    s, groups, interval = 1024, 64, 600_000
    start = EPOCH_S * 1000
    rows = jnp.arange(s, dtype=jnp.int64)
    cols = jnp.arange(n, dtype=jnp.int64)
    ts = jnp.broadcast_to(start + cols * (CADENCE_S * 1000), (s, n))
    val = ((rows[:, None] * 31 + cols[None, :] * 17) % 101
           ).astype(jnp.float64)
    mask = jnp.ones((s, n), bool)
    gid = rows * groups // s
    fixed = FixedWindows.for_range(start, start + n * CADENCE_S * 1000,
                                   interval)
    window_spec, wargs = fixed.split()
    spec = PipelineSpec(
        aggregator="sum",
        downsample=DownsampleStep("avg", window_spec, "none", 0.0),
        rows_sorted=True)
    g_pad = pad_pow2(groups)
    counter = [0]

    def dispatch():
        # a never-repeated window origin: no dispatch replays another
        counter[0] += 1
        w = dict(wargs)
        w["first"] = wargs["first"] - jnp.asarray(counter[0] * 7919,
                                                  jnp.int64)
        return run_group_pipeline(spec, ts, val, mask, gid, g_pad, w)

    def fetch(out):
        for leaf in jax.tree_util.tree_leaves(out):
            np.asarray(leaf.ravel()[0])

    fetch(dispatch())                      # compile + warm
    ks, blk, fet = [1, 2, 4, 8], [], []
    for sync, acc in ((jax.block_until_ready, blk), (fetch, fet)):
        for k in ks:
            samples = []
            for _ in range(3):
                t0 = time.perf_counter()
                outs = [dispatch() for _ in range(k)]
                sync(outs)
                samples.append(time.perf_counter() - t0)
            acc.append(sorted(samples)[1])
    dev = jax.devices()[0]
    waits = blk[-1] >= 0.5 * fet[-1]
    linear = blk[-1] >= 3.0 * blk[0]
    with open(out_path, "w") as fh:
        json.dump({"platform": dev.platform, "kind": dev.device_kind,
                   "shape": [s, n], "k": ks,
                   "blockSeconds": [round(x, 5) for x in blk],
                   "fetchSeconds": [round(x, 5) for x in fet],
                   "blockUntilReadyWaits": bool(waits and linear)}, fh)
    return 0


# --------------------------------------------------------------------- #
# Driver                                                                #
# --------------------------------------------------------------------- #

def say(label: str, msg: str) -> None:
    print("[chip_smoke %s] %s" % (label, msg), flush=True)


def run(args, out_dir: str, summary: dict) -> None:
    label = args.platform
    failures: list[str] = summary["failures"]
    phases: dict[str, float] = summary["phaseSeconds"]

    def timed(name: str, fn, *a):
        t0 = time.monotonic()
        try:
            return fn(*a)
        finally:
            phases[name] = round(time.monotonic() - t0, 3)

    env = dict(os.environ)
    if args.platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"     # the explicit dry run only
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")

    timed("buildNative", build_native)
    say(label, "native/libtsdb_engine.so rebuilt from engine.cpp")

    port = args.port or free_port()
    proc = start_daemon(port, out_dir, env)
    try:
        timed("daemonStart", wait_ready, proc, port, 300.0)
        client = Client(port)
        device = device_section(client)
        summary["device"] = {k: device[k]
                             for k in ("platform", "kind", "count")}
        say(label, "daemon computes on platform=%s kind=%s count=%d"
            % (device["platform"], device["kind"], device["count"]))
        if device["platform"] != args.platform:
            raise SmokeFailure(
                "the daemon computes on %r, this run requires %r — no "
                "accelerator, or JAX fell back to the host"
                % (device["platform"], args.platform))

        points = args.hours * 3600 // CADENCE_S
        fleet = make_fleet(args.hosts, args.seed)
        ts = timestamps(points)
        values = timed("generate", lambda: [
            make_values(args.hosts, points, args.seed, f)
            for f in range(args.metrics)])
        sent = args.metrics * args.hosts * points
        summary["sizes"] = {
            "hosts": args.hosts, "hours": args.hours,
            "cadenceSeconds": CADENCE_S, "metrics": args.metrics,
            "series": args.metrics * args.hosts, "points": sent,
            "pinnedMetricBytes": args.hosts * points * 16}

        telnet_pts = min(60, points // 2)
        ing = timed("ingest", ingest, port, fleet, ts, values, telnet_pts,
                    args.ingest_threads)
        summary["ingest"] = check_ingest_stats(client, sent, ing)
        say(label, "ingested %d points (%d via /api/put at %d points/s, "
            "%d via telnet), parser=%s"
            % (sent, ing["httpPoints"], ing["httpPointsPerSec"],
               ing["telnetPoints"], ing["parser"]))

        summary["readBack"] = timed("readBack", read_back, client, fleet,
                                    ts, values, args.seed)
        say(label, "read back %(points)d points of %(series)d series, "
            "value for value" % summary["readBack"])

        table = request_table(args.hosts, args.hours, args.seed)
        summary["requests"] = timed(
            "requests", run_requests, client, fleet, ts, values[0], table,
            device, label, failures)
        summary["lastpoint"] = timed(
            "lastpoint", last_points, client, fleet, ts, values,
            pick_hosts(table))
        say(label, "lastpoint for %d hosts answered"
            % summary["lastpoint"]["hosts"])

        device = device_section(client)
        summary["deviceMemory"] = device["memory"]
        check_device_memory(device, summary["sizes"]["pinnedMetricBytes"],
                            failures)
        summary["kernels"] = kernel_report(summary["requests"], device)
        say(label, "compiled on the default backend: %s; entry points "
            "not reached: %s"
            % (", ".join("%s x%d" % kv for kv in summary["kernels"][
                "allCompiledOnDefaultBackend"].items()),
               ", ".join(summary["kernels"]["entryPointsNotReached"])))
        client.conn.close()
    finally:
        # never leave the daemon holding the chip, whatever failed above;
        # a bad shutdown is a failure of its own, not a replacement
        try:
            timed("shutdown", stop_daemon, proc, out_dir)
        except SmokeFailure as e:
            failures.append(str(e))
    say(label, "daemon shut down gracefully (rc 0)")

    summary["kprobe"] = timed("kprobe", run_kprobe, env, out_dir, label)
    if summary["kprobe"]["platform"] != args.platform:
        failures.append("k-probe ran on %s" % summary["kprobe"]["platform"])
    if not summary["kprobe"]["blockUntilReadyWaits"]:
        failures.append("k-probe: jax.block_until_ready does not wait for "
                        "the device here (bench.py's sync rests on it)")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="platform the daemon must report; cpu is the "
                         "explicit dry run (never the default)")
    ap.add_argument("--hosts", type=int, default=4000)
    ap.add_argument("--hours", type=int, default=24)
    ap.add_argument("--metrics", type=int, default=1,
                    help="how many of TSBS's ten cpu metrics to ingest")
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--port", type=int, default=0)
    ap.add_argument("--ingest-threads", type=int, default=6)
    ap.add_argument("--out", default=os.path.join(REPO, "chiprun_out",
                                                  "chip_smoke"))
    ap.add_argument("--child-kprobe", nargs=2, metavar=("OUT", "N"),
                    default=None, help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child_kprobe:
        return kprobe_child(args.child_kprobe[0], int(args.child_kprobe[1]))
    if not 1 <= args.metrics <= len(CPU_FIELDS) or args.hosts < 1 \
            or args.hours < 1:
        ap.error("--metrics 1..10, --hosts >= 1, --hours >= 1")
    if not os.path.isdir(os.path.join(REPO, "opentsdb_tpu")):
        print("chip_smoke.py must sit at the root of the repository "
              "(no opentsdb_tpu/ beside it)", file=sys.stderr)
        return 1

    os.makedirs(args.out, exist_ok=True)
    reduced = ["metrics: %d of TSBS cpu-only's 10 (cpu.%s%s)" % (
        args.metrics, CPU_FIELDS[0],
        "" if args.metrics == 1 else " .. cpu." + CPU_FIELDS[args.metrics - 1]),
        "retention: %d h of data, one ingest pass (TSBS default 3 days)"
        % args.hours,
        "traffic: each request once cold and once warm, one client "
        "(TSBS runs thousands of each from parallel workers)"]
    if args.hosts != 4000:
        reduced.append("hosts: %d of scale 4000 (dry run only)" % args.hosts)
    summary = {
        "ok": False, "label": args.platform, "device": None,
        "source": "TSBS DevOps cpu-only, scale 4000, 10 s "
                  "(github.com/timescale/tsbs)",
        "seed": args.seed, "reduced": reduced,
        "assumed": ["tags: 8 of TSBS's 10 (reference cap of 8 tags per "
                    "series; service_version and service_environment "
                    "dropped)",
                    "integer gauge values (TSBS cpu fields are integers)",
                    "the last 60 points of every series arrive by telnet "
                    "put, the rest by /api/put bodies of ~36k points"],
        "sizes": None, "phaseSeconds": {}, "failures": [],
    }
    t0 = time.monotonic()
    try:
        run(args, args.out, summary)
    except SmokeFailure as e:
        summary["failures"].append(str(e))
    summary["totalSeconds"] = round(time.monotonic() - t0, 3)
    summary["ok"] = not summary["failures"]
    summary["claim"] = None
    with open(os.path.join(args.out, "summary.json"), "w") as fh:
        json.dump(summary, fh, indent=1)
    if not summary["ok"]:
        for f in summary["failures"]:
            print("[chip_smoke %s] FAILED: %s" % (args.platform, f),
                  file=sys.stderr, flush=True)
        return 1
    print(json.dumps(summary), flush=True)
    print(json.dumps({"ok": True, "device": summary["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

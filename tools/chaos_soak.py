"""Chaos soak: a live 2-TSD cluster under randomized peer faults.

The serving-path counterpart of tools/crash_soak.py (which proves WAL
durability under kill -9): this proves the CLUSTER fault-tolerance
contract of tsd/cluster.py against real daemons on real sockets.

Topology: a peer TSD and a receiver TSD (both real subprocesses), with
the receiver's `tsd.network.cluster.peers` pointed at a fault-injecting
TCP proxy in THIS process.  Each query round the proxy rolls a fault
for its next connections — clean pass-through, added latency beyond the
cluster budget, immediate reset, mid-body disconnect, or a garbage
body — and the soak asserts the mode contract:

  * partial_results=allow : NO query may answer 500.  Every 200 is
    either the full fold (local 1.0 + peer 2.0 = 3.0 per slot) or the
    local half (1.0) carrying the partialResults trailer.
  * partial_results=error : NO WRONG ANSWERS.  A query either answers
    the exact full fold or fails with >= 500 — never a 200 with
    partial/garbled data (the seed's semantics, preserved).

Both phases finish with the proxy clean and assert the cluster heals
(breaker half-open probe recovers) to a full answer.

    python tools/chaos_soak.py [--rounds 25] [--seed 7] [--port 14261]

Exit code 0 = both contracts held every round.
"""

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_356_998_400
SLOTS = 8          # datapoints per host
FAULTS = ["ok", "ok", "latency", "reset", "disconnect", "garbage"]


def wait_port(port, timeout=60):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=2):
                return True
        except OSError:
            time.sleep(0.2)
    return False


SAN_REPORTS: list = []      # (role, path) of every armed TSD's report


def spawn_tsd(port, extra_cfg: dict, san: bool = False, role: str = "tsd"):
    import tempfile
    conf_dir = tempfile.mkdtemp(prefix="chaos_soak_")
    cfg = os.path.join(conf_dir, "tsd.conf")
    with open(cfg, "w") as fh:
        fh.write("tsd.core.auto_create_metrics = true\n")
        if san:
            # --san: the daemon self-instruments (tsdbsan lockset +
            # deadlock detectors) and dumps its findings at SIGTERM —
            # fault-injection rounds double as a race check
            report = os.path.join(conf_dir, "tsdbsan_report.json")
            SAN_REPORTS.append((role, report))
            fh.write("tsd.sanitizer.enable = true\n")
            fh.write("tsd.sanitizer.report.path = %s\n" % report)
        for k, v in extra_cfg.items():
            fh.write("%s = %s\n" % (k, v))
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    # a CPU fleet by design: several daemons run at once, and a chip
    # belongs to one process — this harness checks contracts, not speed
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen(
        [sys.executable, "-m", "opentsdb_tpu.tools.tsd_main",
         "--port", str(port), "--bind", "127.0.0.1", "--config", cfg],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    if not wait_port(port):
        proc.kill()
        raise RuntimeError("TSD did not come up on %d" % port)
    return proc


class FaultProxy(threading.Thread):
    """TCP proxy to the peer TSD; `fault` picks what the NEXT
    connections endure.  Faults are applied per-connection, so every
    retry attempt in the client rolls through the current setting."""

    def __init__(self, upstream_port: int):
        super().__init__(daemon=True)
        self.upstream_port = upstream_port
        self.fault = "ok"
        self.closing = False
        self.sock = socket.socket()
        self.sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self.sock.bind(("127.0.0.1", 0))
        self.sock.listen(32)
        self.port = self.sock.getsockname()[1]
        self.start()

    def run(self):
        while not self.closing:
            try:
                conn, _ = self.sock.accept()
            except OSError:
                return
            threading.Thread(target=self._handle, args=(conn, self.fault),
                             daemon=True).start()

    def close(self):
        self.closing = True
        try:
            self.sock.close()
        except OSError:
            pass

    def _handle(self, conn, fault):
        try:
            conn.settimeout(10)
            if fault == "reset":
                conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                b"\x01\x00\x00\x00\x00\x00\x00\x00")
                conn.close()
                return
            if fault == "latency":
                time.sleep(1.6)          # beyond the 1s cluster budget
            # read the request head+body (single request per fan-out conn)
            req = b""
            while b"\r\n\r\n" not in req:
                chunk = conn.recv(65536)
                if not chunk:
                    return
                req += chunk
            head, _, body = req.partition(b"\r\n\r\n")
            length = 0
            for line in head.split(b"\r\n"):
                if line.lower().startswith(b"content-length:"):
                    length = int(line.split(b":", 1)[1])
            while len(body) < length:
                chunk = conn.recv(65536)
                if not chunk:
                    return
                body += chunk
            if fault == "garbage":
                junk = b"\x7f{{{chaos"
                conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Type: "
                             b"application/json\r\nContent-Length: %d"
                             b"\r\n\r\n%s" % (len(junk), junk))
                conn.close()
                return
            # forward to the real peer, relay the full response back
            with socket.create_connection(
                    ("127.0.0.1", self.upstream_port), timeout=10) as up:
                up.sendall(req)
                resp = b""
                up.settimeout(10)
                try:
                    while True:
                        chunk = up.recv(65536)
                        if not chunk:
                            break
                        resp += chunk
                        if self._complete(resp):
                            break
                except socket.timeout:
                    pass
            if fault == "disconnect":
                conn.sendall(resp[: max(len(resp) // 2, 1)])
                conn.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                b"\x01\x00\x00\x00\x00\x00\x00\x00")
            else:
                conn.sendall(resp)
            conn.close()
        except OSError:
            try:
                conn.close()
            except OSError:
                pass

    @staticmethod
    def _complete(resp: bytes) -> bool:
        if b"\r\n\r\n" not in resp:
            return False
        head, _, body = resp.partition(b"\r\n\r\n")
        for line in head.split(b"\r\n"):
            if line.lower().startswith(b"content-length:"):
                return len(body) >= int(line.split(b":", 1)[1])
        return False


def http_put(port, points):
    body = json.dumps(points).encode()
    req = urllib.request.Request(
        "http://127.0.0.1:%d/api/put?sync" % port, data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status == 204


def seed_host(port, host, value):
    pts = [{"metric": "chaos.m", "timestamp": BASE + k, "value": value,
            "tags": {"host": host}} for k in range(SLOTS)]
    assert http_put(port, pts)


def query(port):
    # show_stats: every response carries its span tree so the fault
    # rounds can assert the degraded trace is annotated (tsdbobs)
    url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d&m=sum:chaos.m"
           "&show_stats"
           % (port, BASE - 1, BASE + 600))
    try:
        with urllib.request.urlopen(url, timeout=30) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, None


def classify(payload):
    """-> ("full"|"partial"|"wrong", dps) against the seeded data."""
    series = [e for e in payload if isinstance(e, dict) and "metric" in e]
    trailer = any(isinstance(e, dict) and e.get("partialResults")
                  for e in payload)
    if len(series) != 1:
        return "wrong", {}
    dps = series[0]["dps"]
    vals = set(dps.values())
    if len(dps) == SLOTS and vals == {3.0} and not trailer:
        return "full", dps
    if len(dps) == SLOTS and vals == {1.0} and trailer:
        return "partial", dps
    return "wrong", dps


def degraded_trace_annotated(payload) -> bool:
    """True when the response's span tree holds a failed peer_fetch
    span annotated with retry count + breaker state — the trace
    contract for degraded serving (tsdbobs): a partial 200 must say in
    its own trace WHICH peer lost and what the fault stack did."""
    summary = next((e["statsSummary"] for e in payload
                    if isinstance(e, dict) and "statsSummary" in e), None)
    if not summary or "trace" not in summary:
        return False

    def walk(span):
        yield span
        for child in span.get("spans", []):
            yield from walk(child)

    for span in walk(summary["trace"]):
        tags = span.get("tags", {})
        if (span.get("name") == "peer_fetch" and tags.get("error")
                and "retries" in tags and "breaker" in tags):
            return True
    return False


def run_phase(mode: str, rounds: int, rng, peer_port: int,
              recv_port: int, san: bool = False) -> dict:
    proxy = FaultProxy(peer_port)
    recv = spawn_tsd(recv_port, {
        "tsd.network.cluster.peers": "127.0.0.1:%d" % proxy.port,
        "tsd.network.cluster.timeout_ms": "1000",
        "tsd.network.cluster.retry.max_attempts": "2",
        "tsd.network.cluster.breaker.threshold": "3",
        "tsd.network.cluster.breaker.cooldown_ms": "800",
        "tsd.network.cluster.partial_results": mode,
    }, san=san, role="receiver-%s" % mode)
    tally = {"full": 0, "partial": 0, "5xx": 0}
    annotated_partials = 0
    try:
        seed_host(recv_port, "local", 1)
        counts = []
        for i in range(rounds):
            proxy.fault = rng.choice(FAULTS)
            status, payload = query(recv_port)
            if status >= 500:
                if mode == "allow":
                    print("[allow] round %d (%s): got %d — CONTRACT "
                          "VIOLATION" % (i, proxy.fault, status),
                          flush=True)
                    raise SystemExit(1)
                tally["5xx"] += 1
                counts.append((proxy.fault, status))
                continue
            kind, dps = classify(payload)
            if kind == "wrong" or (mode == "error" and kind != "full"):
                print("[%s] round %d (%s): 200 with %s answer %s — "
                      "CONTRACT VIOLATION"
                      % (mode, i, proxy.fault, kind, dps), flush=True)
                raise SystemExit(1)
            tally[kind] += 1
            if kind == "partial" and degraded_trace_annotated(payload):
                annotated_partials += 1
            counts.append((proxy.fault, kind))
        if tally["partial"] and annotated_partials != tally["partial"]:
            print("[%s] only %d of %d partial responses carried an "
                  "annotated failed peer_fetch span (retries + breaker "
                  "state) — degraded traces are going dark"
                  % (mode, annotated_partials, tally["partial"]),
                  flush=True)
            raise SystemExit(1)
        # heal check: clean proxy, wait out the breaker cooldown, and
        # the cluster must answer FULL again
        proxy.fault = "ok"
        deadline = time.time() + 10
        healed = False
        while time.time() < deadline:
            status, payload = query(recv_port)
            if status == 200 and classify(payload)[0] == "full":
                healed = True
                break
            time.sleep(0.3)
        if not healed:
            print("[%s] cluster did not heal after faults cleared"
                  % mode, flush=True)
            raise SystemExit(1)
    finally:
        proxy.close()
        recv.send_signal(signal.SIGTERM)
        recv.wait()
    return tally


def run_cache_stage(port: int, rounds: int) -> None:
    """--cache: the partial-aggregate cache's standing gate.

    A cache-enabled TSD (tuned so the rewrite engages at soak scale)
    races a cache-disabled control through a mixed repeat/sliding-
    window query load with ingest running between rounds.  Gates:

      * ZERO answer divergence: every round's payloads must match the
        control byte-for-byte (integer-valued data, so monolithic and
        block-decomposed float sums are both exact — a mismatch means
        a stale window, a wrong block boundary, or a truncated range,
        never ulp noise);
      * the cache actually served: tsd_query_cache_hits_total > 0 on
        /api/stats/prometheus for an agg tier;
      * healing: the primary boots with a WAL-site fault burst armed
        (`wal.append` errors, times-limited).  Ingest during the burst
        may half-land (the point can be in the store with the journal
        write failed); after the burst both daemons take one
        idempotent full re-put (last-write-wins, identical values) and
        every later answer must STILL match — a cache that missed an
        invalidation during the fault window serves stale and fails
        here.
    """
    import tempfile
    wal_dir = tempfile.mkdtemp(prefix="chaos_cache_wal_")
    n_pts = 900
    shared_cfg = {
        "tsd.query.mesh.enable": "false",
        "tsd.storage.fix_duplicates": "true",
    }
    prim = spawn_tsd(port, {
        **shared_cfg,
        "tsd.query.cache.min_repeats": "1",
        "tsd.query.cache.block_windows": "8",
        "tsd.query.cache.dispatch_overhead_us": "0",
        "tsd.storage.directory": wal_dir,
        "tsd.faults.config": json.dumps([
            {"site": "wal.append", "kind": "error", "times": 6},
        ]),
        "tsd.health.interval": "2",
    }, role="cache")
    ctrl = spawn_tsd(port + 1, {
        **shared_cfg,
        "tsd.query.cache.enable": "false",
    }, role="cache-control")

    def points(lo, hi, salt=0, host="a"):
        # `salt` changes every value: re-puts and between-round
        # overwrites must DIFFER from what any cached block holds, or
        # the divergence gate cannot see a missed invalidation
        return [{"metric": "cache.m", "timestamp": BASE + k,
                 "value": (k * 7 + salt * 13) % 101,
                 "tags": {"host": host}} for k in range(lo, hi)]

    def q(p, start, end):
        url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
               "&m=sum:10s-sum:cache.m" % (p, start, end))
        with urllib.request.urlopen(url, timeout=30) as resp:
            return json.loads(resp.read())

    try:
        # burst phase: the primary's first journal writes fault —
        # puts may 500 with the points half-landed; the control only
        # receives what provably succeeded
        burst_failures = 0
        for lo in range(0, n_pts, 100):
            batch = points(lo, lo + 100)
            try:
                http_put(port, batch)
            except urllib.error.HTTPError:
                burst_failures += 1
                continue
            http_put(port + 1, batch)
        # prime the cache DURING the burst window so blocks exist that
        # a missed invalidation could serve stale
        for _ in range(3):
            q(port, BASE, BASE + 600)
        # heal: one full re-put on BOTH with DIFFERENT values
        # (last-write-wins) — every cached block from the fault window
        # MUST be dirtied, or the very first comparison diverges
        for lo in range(0, n_pts, 100):
            http_put(port, points(lo, lo + 100, salt=1))
            http_put(port + 1, points(lo, lo + 100, salt=1))
        divergences = 0
        for i in range(max(rounds, 10)):
            # repeat window + a sliding window, both compared exactly
            for start, end in ((BASE, BASE + 600),
                               (BASE + 20 * i, BASE + 600 + 20 * i)):
                a = q(port, start, end)
                b = q(port + 1, start, end)
                if a != b:
                    divergences += 1
                    print("[cache] round %d DIVERGED on [%d, %d]:\n"
                          "  cache:   %r\n  control: %r"
                          % (i, start, end, a, b), flush=True)
            # ingest between rounds, INSIDE the repeat window (an
            # overwrite with round-salted values: the next round's
            # repeat query serves wrong sums if the cached block
            # misses the mark) plus fresh tail points
            mid = points(100 + i * 7, 105 + i * 7, salt=i + 2)
            extra = points(n_pts + i * 3, n_pts + (i + 1) * 3)
            for p in (port, port + 1):
                assert http_put(p, mid)
                assert http_put(p, extra)
        if divergences:
            print("[cache] %d diverged answers vs the cache-disabled "
                  "control" % divergences, flush=True)
            raise SystemExit(1)
        scrape = _prom_scrape(port)
        agg_hits = sum(
            v for labels, v in scrape.get(
                "tsd_query_cache_hits_total", {}).items()
            if "agg" in labels)
        if agg_hits <= 0:
            print("[cache] no agg-tier cache hits on prometheus — the "
                  "rewrite never engaged (scrape: %r)"
                  % scrape.get("tsd_query_cache_hits_total"),
                  flush=True)
            raise SystemExit(1)
        # post-heal diagnostics: every subsystem ok (incl. the cache
        # hit-rate invariant under the round load) AND the WAL fault
        # burst's 500 envelopes retained in the ring
        check_diag_gate(port, "cache", [
            ("http_error 5xx (wal.append burst)",
             lambda e: e.get("kind") == "http_error"
             and e.get("status", 0) >= 500),
        ])
        # post-heal explain consistency: the warm rewrite path the
        # rounds exercised must be what explain predicts NOW
        check_explain_gate(port, "cache", [
            ("warm repeat", "start=%d&end=%d&m=sum:10s-sum:cache.m"
             % (BASE, BASE + n_pts)),
        ])
        print("[cache] %d rounds, zero divergence, %d agg-tier hits, "
              "%d faulted burst puts healed"
              % (max(rounds, 10), int(agg_hits), burst_failures),
              flush=True)
    finally:
        for proc in (prim, ctrl):
            proc.send_signal(signal.SIGTERM)
            proc.wait()


def run_rollup_stage(port: int, rounds: int) -> None:
    """--rollup: the rollup-lane subsystem's standing gate.

    A lane-enabled TSD (1m lanes, 1s maintenance cadence so blocks
    build between rounds) races a lane-disabled control through a
    long-range mixed query load with ingest OVERWRITING points inside
    the queried windows between rounds.  Gates:

      * ZERO answer divergence: every round's payloads match the
        control byte-for-byte (integer-valued data — lane-derivable
        re-reduction is exact, so a mismatch means a stale lane block
        or a wrong cell boundary, never ulp noise);
      * the lanes actually served: tsd_rollup_lane_hits_total > 0 on
        /api/stats/prometheus;
      * healing: the primary boots with a times-limited WAL-site
        fault burst armed; after the burst both daemons take one
        idempotent full re-put with CHANGED values — a lane block
        that missed an invalidation during the fault window serves
        stale sums and fails the divergence gate.
    """
    import tempfile
    wal_dir = tempfile.mkdtemp(prefix="chaos_rollup_wal_")
    n_pts = 1800
    shared_cfg = {
        "tsd.query.mesh.enable": "false",
        "tsd.storage.fix_duplicates": "true",
        # lanes are the ONLY cache under test: the agg cache answers
        # the same repeat shapes and would mask a lane bug
        "tsd.query.cache.enable": "false",
    }
    prim = spawn_tsd(port, {
        **shared_cfg,
        "tsd.rollup.enable": "true",
        "tsd.rollup.intervals": "1m",
        "tsd.rollup.block_windows": "8",
        "tsd.rollup.interval": "1",
        "tsd.rollup.delay_ms": "0",
        "tsd.storage.directory": wal_dir,
        "tsd.faults.config": json.dumps([
            {"site": "wal.append", "kind": "error", "times": 6},
        ]),
        "tsd.health.interval": "2",
    }, role="rollup")
    ctrl = spawn_tsd(port + 1, shared_cfg, role="rollup-control")

    def points(lo, hi, salt=0, host="a"):
        # `salt` changes every value: overwrites must DIFFER from
        # what any lane cell holds, or the divergence gate cannot see
        # a missed invalidation
        return [{"metric": "rollup.m", "timestamp": BASE + k,
                 "value": (k * 7 + salt * 13) % 101,
                 "tags": {"host": host}} for k in range(lo, hi)]

    def q(p, start, end):
        url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
               "&m=sum:60s-sum:rollup.m" % (p, start, end))
        with urllib.request.urlopen(url, timeout=30) as resp:
            return json.loads(resp.read())

    try:
        # burst phase: the primary's first journal writes fault
        burst_failures = 0
        for lo in range(0, n_pts, 200):
            batch = points(lo, lo + 200)
            try:
                http_put(port, batch)
            except urllib.error.HTTPError:
                burst_failures += 1
                continue
            http_put(port + 1, batch)
        # prime demand DURING the burst window so lane blocks exist
        # that a missed invalidation could serve stale, and give the
        # maintenance cadence a beat to build them
        for _ in range(3):
            q(port, BASE, BASE + 1500)
            time.sleep(0.7)
        # heal: one full re-put on BOTH with DIFFERENT values — every
        # lane block from the fault window MUST be dirtied
        for lo in range(0, n_pts, 200):
            http_put(port, points(lo, lo + 200, salt=1))
            http_put(port + 1, points(lo, lo + 200, salt=1))
        divergences = 0
        for i in range(max(rounds, 10)):
            for start, end in ((BASE, BASE + 1500),
                               (BASE + 60 * i, BASE + 1500 + 60 * i)):
                a = q(port, start, end)
                b = q(port + 1, start, end)
                if a != b:
                    divergences += 1
                    print("[rollup] round %d DIVERGED on [%d, %d]:\n"
                          "  lanes:   %r\n  control: %r"
                          % (i, start, end, a, b), flush=True)
            # overwrite INSIDE the queried window with round-salted
            # values + fresh tail points, then let the maintenance
            # cadence rebuild the dirtied blocks
            mid = points(200 + i * 11, 209 + i * 11, salt=i + 2)
            extra = points(n_pts + i * 3, n_pts + (i + 1) * 3)
            for p in (port, port + 1):
                assert http_put(p, mid)
                assert http_put(p, extra)
            time.sleep(0.6)
        if divergences:
            print("[rollup] %d diverged answers vs the lane-disabled "
                  "control" % divergences, flush=True)
            raise SystemExit(1)
        scrape = _prom_scrape(port)
        lane_hits = _prom_sum(scrape, "tsd_rollup_lane_hits_total")
        if lane_hits <= 0:
            print("[rollup] no lane hits on prometheus — the lanes "
                  "never served (scrape: %r)"
                  % scrape.get("tsd_rollup_lane_hits_total"),
                  flush=True)
            raise SystemExit(1)
        # post-heal diagnostics: health all-ok, the WAL burst's 500s
        # AND at least one lane-served plan retained in the ring
        check_diag_gate(port, "rollup", [
            ("http_error 5xx (wal.append burst)",
             lambda e: e.get("kind") == "http_error"
             and e.get("status", 0) >= 500),
            ("rollup-lane plan",
             lambda e: e.get("kind") == "plan"
             and e.get("path") == "rollup_lane"),
        ])
        # post-heal explain consistency: the lane-served path must be
        # what explain predicts after faults + ingest invalidation
        check_explain_gate(port, "rollup", [
            ("lane-served", "start=%d&end=%d&m=sum:60s-sum:rollup.m"
             % (BASE + 60, BASE + n_pts - 120)),
        ])
        print("[rollup] %d rounds, zero divergence, %d lane hits, "
              "%d faulted burst puts healed"
              % (max(rounds, 10), int(lane_hits), burst_failures),
              flush=True)
    finally:
        for proc in (prim, ctrl):
            proc.send_signal(signal.SIGTERM)
            proc.wait()


def run_spill_stage(port: int, rounds: int) -> None:
    """--spill: the out-of-core tiled executor's standing gate.

    A tiled TSD — state budget squeezed so every long-range group-by
    tiles through the spill pool (host ring deliberately tiny so the
    disk tier engages) — races a resident-capable control through the
    same mixed load with ingest running between rounds.  Gates:

      * ZERO byte divergence on shapes both can serve: integer-valued
        data, so tiled and resident folds are both exact — a mismatch
        means a lost tile, a mis-assembled stripe, or a stale spill
        entry, never ulp noise;
      * the tiled path actually engaged AND spilled: prometheus shows
        tsd_query_spill_tiles_total > 0 and a nonzero disk-tier
        spill/eviction count, with resident spill bytes BOUNDED by the
        configured host+disk budgets at every scrape;
      * healing after disk-full: the primary boots with an
        ``spill.write`` error fault armed (times-limited).  While the
        fault burns, tiled queries may answer the 413/503 spill
        contract but NEVER 500 and never a wrong answer; once it is
        exhausted, the very next round must match the control again.
    """
    import tempfile
    spill_dir = tempfile.mkdtemp(prefix="chaos_spill_")
    n_hosts = 24
    span = 163_840            # 16384 windows at 10s
    shared_cfg = {
        "tsd.query.mesh.enable": "false",
        "tsd.query.device_cache.enable": "false",
        "tsd.query.cache.enable": "false",
        "tsd.query.streaming.point_threshold": "100",
        # between-round ingest overwrites points with salted values
        "tsd.storage.fix_duplicates": "true",
    }
    prim = spawn_tsd(port, {
        **shared_cfg,
        "tsd.query.streaming.state_mb": "1",
        "tsd.query.spill.enable": "true",
        "tsd.query.spill.host_mb": "1",
        "tsd.query.spill.disk_mb": "64",
        "tsd.query.spill.dir": spill_dir,
        "tsd.faults.config": json.dumps([
            {"site": "spill.write", "kind": "error", "times": 3},
        ]),
        "tsd.health.interval": "2",
    }, role="spill")
    ctrl = spawn_tsd(port + 1, {
        **shared_cfg,
        "tsd.query.spill.enable": "false",
        "tsd.query.streaming.state_mb": "6144",
    }, role="spill-control")

    def points(lo, hi, salt=0):
        out = []
        for h in range(n_hosts):
            out.extend(
                {"metric": "spill.m", "timestamp": BASE + k * 512 + h,
                 "value": (k * 7 + h * 13 + salt * 29) % 101,
                 "tags": {"host": "h%d" % h, "g": "g%d" % (h % 4)}}
                for k in range(lo, hi))
        return out

    def q(p, start, end):
        url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
               "&m=sum:10s-sum:spill.m%%7Bg=*%%7D" % (p, start, end))
        with urllib.request.urlopen(url, timeout=120) as resp:
            return json.loads(resp.read())

    try:
        for lo in range(0, 300, 100):
            assert http_put(port, points(lo, lo + 100))
            assert http_put(port + 1, points(lo, lo + 100))
        # fault burn-down: the armed spill.write faults may 413/503 the
        # first tiled attempts — never 500, and the control stays up
        burned = 0
        for attempt in range(8):
            try:
                q(port, BASE, BASE + span)
                break
            except urllib.error.HTTPError as e:
                assert e.code in (413, 503), \
                    "spill fault produced a %d (want 413/503)" % e.code
                burned += 1
        else:
            raise SystemExit("tiled query never recovered from the "
                             "spill.write fault burst")
        divergences = 0
        budget_bytes = (1 + 64) * 2**20
        for i in range(max(rounds, 5)):
            for start, end in ((BASE, BASE + span),
                               (BASE + 512 * i, BASE + span)):
                a = q(port, start, end)
                b = q(port + 1, start, end)
                if a != b:
                    divergences += 1
                    print("[spill] round %d DIVERGED on [%d, %d]"
                          % (i, start, end), flush=True)
            scrape = _prom_scrape(port)
            resident = _prom_sum(scrape, "tsd_query_spill_bytes")
            if resident > budget_bytes:
                print("[spill] pool bytes %d exceed the %d budget"
                      % (resident, budget_bytes), flush=True)
                raise SystemExit(1)
            # ingest between rounds, inside the queried window
            assert http_put(port, points(100 + i, 103 + i, salt=i + 1))
            assert http_put(port + 1, points(100 + i, 103 + i,
                                             salt=i + 1))
        if divergences:
            print("[spill] %d diverged answers vs the resident control"
                  % divergences, flush=True)
            raise SystemExit(1)
        scrape = _prom_scrape(port)
        tiles = _prom_sum(scrape, "tsd_query_spill_tiles_total")
        disk = (_prom_sum(scrape, "tsd_query_spill_evictions_total")
                + sum(v for labels, v in scrape.get(
                    "tsd_query_spill_spills_total", {}).items()
                    if "disk" in labels))
        if tiles <= 0:
            print("[spill] tiled path never engaged (tiles=%r)"
                  % tiles, flush=True)
            raise SystemExit(1)
        if disk <= 0:
            print("[spill] disk tier never engaged (evictions/spills "
                  "all host)", flush=True)
            raise SystemExit(1)
        # post-heal diagnostics: health all-ok (incl. spill saturation
        # after per-query release) and the tiled executions retained
        check_diag_gate(port, "spill", [
            ("tiling event",
             lambda e: e.get("kind") == "tiling"),
        ])
        # post-heal explain consistency: the over-budget plan must
        # route (and explain) tiled after the disk-full burst healed
        check_explain_gate(port, "spill", [
            ("tiled group-by",
             "start=%d&end=%d&m=sum:10s-sum:spill.m%%7Bg=*%%7D"
             % (BASE, BASE + span)),
        ])
        print("[spill] %d rounds, zero divergence, %d tiles, %d disk "
              "demotions, %d faulted attempts healed"
              % (max(rounds, 5), int(tiles), int(disk), burned),
              flush=True)
    finally:
        for proc in (prim, ctrl):
            proc.send_signal(signal.SIGTERM)
            proc.wait()


def _prom_scrape(port: int, timeout: float = 10.0) -> dict:
    """Parse /api/stats/prometheus into {name: {label_str: value}}."""
    text = urllib.request.urlopen(
        "http://127.0.0.1:%d/api/stats/prometheus" % port,
        timeout=timeout).read().decode()
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        metric, _, value = line.rpartition(" ")
        name, _, labels = metric.partition("{")
        try:
            out.setdefault(name, {})["{" + labels] = float(value)
        except ValueError:
            continue
    return out


def _prom_sum(scrape: dict, name: str) -> float:
    return sum(scrape.get(name, {}).values())


def check_explain_gate(port: int, stage: str, specs: list) -> None:
    """Stage-level explain-consistency gate (ISSUE 13): for sampled
    live queries, the path /api/query/explain predicts must be the
    path the executor then stamps into its flight-recorder plan event
    — exercised while the stage's faults are armed/healed, so a
    consult arm that drifts under fault conditions fails the soak.
    PATH-level, not fingerprint-level: the stages ingest concurrently,
    and coverage may legitimately move between the two requests.

    ``specs`` is [(label, query_string_tail)] where the tail is the
    ``start=...&end=...&m=...`` part of a /api/query URI.  A mismatch
    retries a couple of times: the maintenance thread may move cache
    state between the explain and the execute (a legitimate flip, not
    drift); the SAME mismatch three times running is drift.
    """
    for label, qs in specs:
        for attempt in range(3):
            try:
                exp = json.loads(urllib.request.urlopen(
                    "http://127.0.0.1:%d/api/query/explain?%s"
                    % (port, qs), timeout=30).read())
            except urllib.error.HTTPError as e:
                print("[%s] explain gate: explain itself failed with "
                      "%d for %s" % (stage, e.code, label), flush=True)
                raise SystemExit(1)
            segs = [s for sub in exp.get("subQueries", [])
                    for s in sub.get("segments", [])]
            if not segs or "path" not in segs[0]:
                print("[%s] explain gate: no routed segment for %s: %r"
                      % (stage, label, exp), flush=True)
                raise SystemExit(1)
            predicted = segs[0]["path"]
            trace_id = "%032x" % random.getrandbits(128)
            req = urllib.request.Request(
                "http://127.0.0.1:%d/api/query?%s" % (port, qs),
                headers={"X-TSDB-Trace-Id": trace_id})
            try:
                with urllib.request.urlopen(req, timeout=120) as resp:
                    assert resp.status == 200
                diag = json.loads(urllib.request.urlopen(
                    "http://127.0.0.1:%d/api/diag?trace_id=%s"
                    % (port, trace_id), timeout=10).read())
            except OSError as e:
                # a straggler shed/restart right after heal is the
                # transient case the retry loop exists for — burn an
                # attempt instead of dying on a raw traceback
                print("[%s] explain gate: execute/diag fetch failed "
                      "for %s (attempt %d): %s — retrying"
                      % (stage, label, attempt + 1, e), flush=True)
                time.sleep(0.5)
                continue
            plans = [e for e in diag.get("events", [])
                     if e.get("kind") == "plan"]
            if not plans:
                print("[%s] explain gate: no plan event for trace %s "
                      "(%s)" % (stage, trace_id, label), flush=True)
                raise SystemExit(1)
            executed = plans[0].get("path")
            if executed == predicted:
                print("[%s] explain gate OK: %s -> %s"
                      % (stage, label, predicted), flush=True)
                break
            print("[%s] explain gate mismatch for %s (attempt %d): "
                  "predicted %r, ran %r — retrying"
                  % (stage, label, attempt + 1, predicted, executed),
                  flush=True)
            time.sleep(0.5)
        else:
            print("[%s] explain gate FAILED for %s: explain and the "
                  "executor disagree persistently" % (stage, label),
                  flush=True)
            raise SystemExit(1)


def check_diag_gate(port: int, stage: str, evidence: list,
                    timeout_s: float = 60.0) -> None:
    """Post-heal diagnostics gate (ISSUE 12): /api/diag/health must
    report EVERY subsystem ok, and the flight recorder's ring must
    still hold the injected fault's events — a daemon that "healed"
    while its recorder missed the fault window fails the stage (the
    black box exists precisely for that window).

    ``evidence`` is [(label, predicate)] over the /api/diag events.
    """
    deadline = time.time() + timeout_s
    last = None
    while time.time() < deadline:
        try:
            payload = json.loads(urllib.request.urlopen(
                "http://127.0.0.1:%d/api/diag/health" % port,
                timeout=10).read())
        except OSError as e:
            last = {"error": str(e)}
            time.sleep(1.0)
            continue
        subs = payload.get("subsystems", {})
        last = {k: v.get("level") for k, v in subs.items()}
        if subs and all(v.get("level") == "ok" for v in subs.values()):
            break
        time.sleep(1.0)
    else:
        print("[%s] health gate FAILED: subsystems never all ok "
              "within %.0fs: %r" % (stage, timeout_s, last), flush=True)
        raise SystemExit(1)
    diag = json.loads(urllib.request.urlopen(
        "http://127.0.0.1:%d/api/diag" % port, timeout=10).read())
    events = diag.get("events", [])
    for label, pred in evidence:
        if not any(pred(e) for e in events):
            print("[%s] flight recorder MISSED the injected fault: no "
                  "'%s' event among %d retained (kinds: %r)"
                  % (stage, label, len(events),
                     sorted({e.get("kind") for e in events})),
                  flush=True)
            raise SystemExit(1)
    print("[%s] diag gate OK: health all-ok, recorder holds: %s"
          % (stage, ", ".join(lb for lb, _ in evidence)), flush=True)


def run_overload_stage(port: int, rounds: int) -> None:
    """--overload: saturating mixed load against ONE TSD whose
    admission gate is tightly bounded, with an injected slow-handler
    fault (rpc.slow_handler latency INSIDE held permits) wedging the
    queue mid-burst.  The overload contract (ISSUE 8 / ROADMAP item 3):

      * zero 500s: every response is a 200 (full or degraded-with-
        partialResults) or a 503 carrying Retry-After — the daemon
        degrades, it never stalls or faults;
      * the in-flight permit gauge scraped from /api/stats/prometheus
        never exceeds tsd.query.admission.permits;
      * admitted-query p99 stays within tsd.query.timeout;
      * the daemon HEALS: once the fault lifts (its `times` budget
        exhausts), serial queries return to clean 200s and the shed
        counter stops growing.
    """
    permits = 2
    timeout_ms = 10_000
    fault = json.dumps([{"site": "rpc.slow_handler", "kind": "latency",
                         "ms": 900, "times": 10}])
    tsd = spawn_tsd(port, {
        "tsd.query.admission.permits": str(permits),
        "tsd.query.admission.queue_limit": "3",
        "tsd.query.admission.max_wait_ms": "1500",
        "tsd.query.timeout": str(timeout_ms),
        "tsd.query.degrade": "allow",
        "tsd.faults.config": fault,
        # a CPU fleet: every daemon is a one-device node
        "tsd.query.mesh.enable": "false",
        # fast health cadence so the post-heal diag gate converges
        "tsd.health.interval": "2",
    }, role="overload")
    try:
        for host, value in (("a", 1), ("b", 2)):
            seed_host(port, host, value)
        # one warm query pays the first jit compile OUTSIDE the burst
        # (and outside the fault: it fires only under concurrency? no —
        # times budget: spend one here deliberately, 9 remain armed)
        status, _ = query(port)
        if status != 200:
            print("[overload] warm query -> %d" % status, flush=True)
            raise SystemExit(1)

        metrics = ["sum:chaos.m", "max:10s-max:chaos.m",
                   "sum:30s-avg:chaos.m{host=*}"]
        results: list = []          # (status, latency_s, retry_after,
        results_lock = threading.Lock()  # partial)
        inflight_max = [0.0]
        sampling = [True]

        def sampler():
            while sampling[0]:
                try:
                    scrape = _prom_scrape(port, timeout=5)
                    inflight_max[0] = max(
                        inflight_max[0],
                        _prom_sum(scrape, "tsd_query_admission_inflight"))
                except OSError:
                    pass
                time.sleep(0.05)

        def client(worker: int, n: int) -> None:
            for i in range(n):
                mq = metrics[(worker + i) % len(metrics)]
                url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
                       "&m=%s" % (port, BASE - 1, BASE + 600,
                                  mq.replace("{", "%7B")
                                  .replace("}", "%7D")))
                t0 = time.monotonic()
                try:
                    with urllib.request.urlopen(url, timeout=30) as resp:
                        payload = json.loads(resp.read())
                        partial = any(isinstance(e, dict)
                                      and e.get("partialResults")
                                      for e in payload)
                        rec = (resp.status, time.monotonic() - t0,
                               None, partial)
                except urllib.error.HTTPError as e:
                    rec = (e.code, time.monotonic() - t0,
                           e.headers.get("Retry-After"), False)
                except OSError as e:
                    rec = (599, time.monotonic() - t0, None, False)
                with results_lock:
                    results.append(rec)

        sampler_t = threading.Thread(target=sampler, daemon=True)
        sampler_t.start()
        workers = [threading.Thread(target=client, args=(w, rounds))
                   for w in range(8)]
        for w in workers:
            w.start()
        for w in workers:
            w.join()
        sampling[0] = False
        sampler_t.join(5)

        tally = {"ok": 0, "degraded": 0, "shed": 0}
        admitted_lat: list = []
        for status, lat, retry_after, partial in results:
            if status == 200:
                tally["degraded" if partial else "ok"] += 1
                admitted_lat.append(lat)
            elif status == 503:
                if not retry_after or int(retry_after) < 1:
                    print("[overload] 503 WITHOUT Retry-After — "
                          "CONTRACT VIOLATION", flush=True)
                    raise SystemExit(1)
                tally["shed"] += 1
            else:
                print("[overload] status %d — CONTRACT VIOLATION "
                      "(only 200 or 503+Retry-After allowed)" % status,
                      flush=True)
                raise SystemExit(1)
        if inflight_max[0] > permits:
            print("[overload] in-flight gauge hit %.0f > %d permits — "
                  "the gate leaked" % (inflight_max[0], permits),
                  flush=True)
            raise SystemExit(1)
        if admitted_lat:
            admitted_lat.sort()
            p99 = admitted_lat[
                min(int(len(admitted_lat) * 0.99),
                    len(admitted_lat) - 1)]
            if p99 * 1e3 > timeout_ms:
                print("[overload] admitted p99 %.0fms exceeds "
                      "tsd.query.timeout %dms" % (p99 * 1e3, timeout_ms),
                      flush=True)
                raise SystemExit(1)
        else:
            p99 = 0.0
        if not tally["shed"]:
            print("[overload] the burst never shed — not an overload "
                  "(raise --rounds)", flush=True)
            raise SystemExit(1)

        # -- recovery: the fault's `times` budget is exhausted; serial
        # load must return to clean 200s and shedding must STOP
        shed_before = _prom_sum(_prom_scrape(port),
                                "tsd_query_admission_shed")
        deadline = time.time() + 30
        healed = False
        while time.time() < deadline:
            statuses = [query(port)[0] for _ in range(5)]
            shed_now = _prom_sum(_prom_scrape(port),
                                 "tsd_query_admission_shed")
            if statuses == [200] * 5 and shed_now == shed_before:
                healed = True
                break
            shed_before = shed_now
            time.sleep(0.5)
        if not healed:
            print("[overload] daemon did not heal after the fault "
                  "lifted (still shedding or failing)", flush=True)
            raise SystemExit(1)
        # post-heal diagnostics: every subsystem ok AND the burst's
        # sheds retained in the flight recorder
        check_diag_gate(port, "overload", [
            ("admission shed",
             lambda e: e.get("kind") == "admission"
             and e.get("decision") == "shed"),
        ])
        # post-heal explain consistency: explain needs no permit, and
        # its prediction must match the executed path once admitted
        check_explain_gate(port, "overload", [
            # downsampled: union plans don't emit plan events, grouped
            # plans do — the gate needs the fingerprinted path
            ("post-heal", "start=%d&end=%d&m=sum:30s-avg:chaos.m"
             % (BASE - 1, BASE + 600)),
        ])
        print("[overload] %d responses OK: %s, in-flight max %.0f/%d, "
              "admitted p99 %.0fms, healed (shed rate 0)"
              % (len(results), tally, inflight_max[0], permits,
                 p99 * 1e3), flush=True)
    finally:
        tsd.send_signal(signal.SIGTERM)
        tsd.wait()


# The fixed latency-attribution phase set (obs/latattr.py PHASES) —
# the stage pins the report's ordered keys against it
LATATTR_PHASES = ["parse", "admission_wait", "plan", "batch_rendezvous",
                  "dispatch", "device_wait", "serialize", "flush"]


def run_latattr_stage(port: int, rounds: int) -> None:
    """--latattr: attribution sanity under fault injection (ISSUE 20).

    A TSD with a slow-handler latency fault armed serves a traced query
    burst while a poller hammers /api/diag/latency the whole time.  The
    attribution contract:

      * /api/diag/latency NEVER answers 5xx mid-fault, and the folded
        request count never moves backwards between polls;
      * every profile reports the full ordered phase set with
        non-negative counts/totals/quantiles (no negative or missing
        phase deltas, fault or no fault);
      * the faulted (slow) requests' tail exemplar trace ids resolve
        to retained slow-query captures (/api/diag/slow?trace_id=).
    """
    fault_ms = 400
    fault = json.dumps([{"site": "rpc.slow_handler", "kind": "latency",
                         "ms": fault_ms, "times": max(rounds // 2, 3)}])
    tsd = spawn_tsd(port, {
        "tsd.query.mesh.enable": "false",
        "tsd.faults.config": fault,
        # the faulted requests cross this and get captured
        "tsd.diag.slow_ms": str(fault_ms // 2),
        "tsd.health.interval": "2",
    }, role="latattr")
    try:
        seed_host(port, "a", 1)
        status, _ = query(port)                       # warm compile
        violations: list = []
        poll_count = [0]
        stop = [False]

        def poller():
            last_requests = -1
            while not stop[0]:
                try:
                    with urllib.request.urlopen(
                            "http://127.0.0.1:%d/api/diag/latency"
                            % port, timeout=10) as resp:
                        payload = json.loads(resp.read())
                        poll_count[0] += 1
                        if resp.status != 200:
                            violations.append(
                                "poll status %d" % resp.status)
                        if payload["requests"] < last_requests:
                            violations.append(
                                "requests went backwards: %d -> %d"
                                % (last_requests, payload["requests"]))
                        last_requests = payload["requests"]
                except urllib.error.HTTPError as e:
                    poll_count[0] += 1
                    violations.append("poll -> HTTP %d mid-fault"
                                      % e.code)
                except OSError:
                    pass                  # daemon busy; not a 5xx
                time.sleep(0.05)

        poller_t = threading.Thread(target=poller, daemon=True)
        poller_t.start()
        statuses = []
        for i in range(rounds):
            req = urllib.request.Request(
                "http://127.0.0.1:%d/api/query?start=%d&end=%d"
                "&m=sum:chaos.m" % (port, BASE - 1, BASE + 600),
                headers={"X-TSDB-Trace-Id": "latattr-%03d" % i})
            try:
                with urllib.request.urlopen(req, timeout=60) as resp:
                    statuses.append(resp.status)
            except urllib.error.HTTPError as e:
                statuses.append(e.code)
        stop[0] = True
        poller_t.join(5)
        if statuses.count(200) == 0:
            print("[latattr] no query ever answered 200", flush=True)
            raise SystemExit(1)
        if not poll_count[0]:
            print("[latattr] the mid-fault poller never completed a "
                  "poll", flush=True)
            raise SystemExit(1)
        if violations:
            print("[latattr] mid-fault polling violations: %r"
                  % violations[:10], flush=True)
            raise SystemExit(1)

        # final report: full ordered phase set, non-negative everywhere
        report = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/api/diag/latency" % port,
            timeout=10).read())
        if report["phases"] != LATATTR_PHASES:
            print("[latattr] phase set drifted: %r" % report["phases"],
                  flush=True)
            raise SystemExit(1)
        exemplar_ids: set = set()
        for profile in report["profiles"]:
            if list(profile["phases"]) != LATATTR_PHASES:
                print("[latattr] profile %r missing phases: %r"
                      % (profile["route"], list(profile["phases"])),
                      flush=True)
                raise SystemExit(1)
            for phase, summary in profile["phases"].items():
                for field in ("count", "totalMs", "p50Ms", "p99Ms"):
                    if summary[field] < 0:
                        print("[latattr] NEGATIVE %s on %s/%s: %r"
                              % (field, profile["route"], phase,
                                 summary), flush=True)
                        raise SystemExit(1)
            for tail in profile.get("exemplars", {}).values():
                exemplar_ids.update(e["traceId"] for e in tail)

        # the slow (faulted) requests' exemplars resolve to retained
        # captures: tail trace ids and the slow store must intersect,
        # and the lookup endpoint must produce the capture
        slow = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/api/diag/slow" % port,
            timeout=10).read())
        slow_ids = {q.get("traceId") for q in slow.get("queries", [])}
        resolved = sorted(exemplar_ids & slow_ids)
        if not resolved:
            print("[latattr] no exemplar trace id resolves to a slow "
                  "capture (exemplars %d, captures %d)"
                  % (len(exemplar_ids), len(slow_ids)), flush=True)
            raise SystemExit(1)
        lookup = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/api/diag/slow?trace_id=%s"
            % (port, resolved[0]), timeout=10).read())
        if not lookup.get("queries"):
            print("[latattr] slow lookup for exemplar %s came back "
                  "empty" % resolved[0], flush=True)
            raise SystemExit(1)
        check_diag_gate(port, "latattr", [])
        print("[latattr] attribution sane under fault: %d polls clean, "
              "%d/%d queries 200, %d exemplar(s) resolve to captures"
              % (poll_count[0], statuses.count(200), len(statuses),
                 len(resolved)), flush=True)
    finally:
        tsd.send_signal(signal.SIGTERM)
        tsd.wait()


def run_tenants_stage(port: int, rounds: int) -> None:
    """--tenants: two tenants behind the fair-share gate (ISSUE 14),
    one storming.  The multi-tenant contract (ROADMAP item 1):

      * the victim tenant's p99 under the storm stays within a bound
        of its solo baseline, and the victim is never shed;
      * the storming tenant SHEDS (its own per-tenant queue bound +
        DRR deficit throttle it) with 503 + Retry-After — never a 500
        for anyone;
      * post-heal: /api/diag/health reads every subsystem ok
        (including the new cross-tenant starvation invariant) and the
        flight-recorder ring still holds the storm's shed evidence;
        explain still predicts the executed path.
    """
    permits = 2
    tsd = spawn_tsd(port, {
        "tsd.query.admission.permits": str(permits),
        "tsd.query.admission.queue_limit": "4",
        "tsd.query.admission.max_wait_ms": "6000",
        "tsd.query.timeout": "15000",
        "tsd.diag.tenants": "victim,storm",
        "tsd.query.mesh.enable": "false",
        "tsd.health.interval": "2",
    }, role="tenants")
    try:
        for host, value in (("a", 1), ("b", 2)):
            seed_host(port, host, value)

        def ask(tenant: str, timeout: float = 60.0):
            url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
                   "&m=sum:30s-avg:chaos.m" % (port, BASE - 1,
                                               BASE + 600))
            req = urllib.request.Request(
                url, headers={"X-TSDB-Tenant": tenant})
            t0 = time.monotonic()
            try:
                with urllib.request.urlopen(req, timeout=timeout) as r:
                    r.read()
                    return r.status, time.monotonic() - t0, None
            except urllib.error.HTTPError as e:
                return (e.code, time.monotonic() - t0,
                        e.headers.get("Retry-After"))
            except OSError:
                return 599, time.monotonic() - t0, None

        # solo baseline: the victim alone, serial — the bound the
        # storm must not break (warm query pays the compile first)
        ask("victim")
        baseline = []
        for _ in range(max(rounds, 10)):
            status, lat, _ = ask("victim")
            if status != 200:
                print("[tenants] baseline victim query -> %d" % status,
                      flush=True)
                raise SystemExit(1)
            baseline.append(lat)
        baseline.sort()
        base_p99 = baseline[min(int(len(baseline) * 0.99),
                                len(baseline) - 1)]

        # the storm: 6 threads of storm-tenant load; the victim keeps
        # its serial cadence through it
        stop = [False]
        storm_tally = {"ok": 0, "shed": 0, "bad": 0}
        lock = threading.Lock()

        def storm_client():
            while not stop[0]:
                status, _lat, retry_after = ask("storm")
                with lock:
                    if status == 200:
                        storm_tally["ok"] += 1
                    elif status == 503 and retry_after:
                        storm_tally["shed"] += 1
                    else:
                        storm_tally["bad"] += 1

        storm_threads = [threading.Thread(target=storm_client,
                                          daemon=True)
                         for _ in range(6)]
        for t in storm_threads:
            t.start()
        victim = []
        victim_shed = 0
        storm_until = time.time() + max(rounds * 0.5, 10.0)
        while time.time() < storm_until:
            status, lat, _ = ask("victim")
            if status == 200:
                victim.append(lat)
            elif status == 503:
                victim_shed += 1
            else:
                print("[tenants] victim got %d under storm — CONTRACT "
                      "VIOLATION" % status, flush=True)
                stop[0] = True
                raise SystemExit(1)
            time.sleep(0.05)
        stop[0] = True
        for t in storm_threads:
            t.join(10)

        if storm_tally["bad"]:
            print("[tenants] storm tenant saw %d non-200/503 "
                  "responses — CONTRACT VIOLATION" % storm_tally["bad"],
                  flush=True)
            raise SystemExit(1)
        if not storm_tally["shed"]:
            print("[tenants] the storm never shed — not a storm "
                  "(raise --rounds)", flush=True)
            raise SystemExit(1)
        if victim_shed:
            print("[tenants] victim was shed %d times while the gate "
                  "claims fair share" % victim_shed, flush=True)
            raise SystemExit(1)
        victim.sort()
        v_p99 = victim[min(int(len(victim) * 0.99), len(victim) - 1)]
        # bound: fair draining means the victim waits at most ~one
        # permit rotation behind in-flight storm queries (permits=2)
        # plus pure CPU contention from the storm's client threads —
        # well under the starvation line (max_wait 6s, where a victim
        # queued behind the storm's whole backlog would land).  The
        # allowance is generous for 2-core CI boxes where contention,
        # not the drain, dominates; shed-count 0 above is the strict
        # half of the fairness claim.
        bound = max(8 * base_p99, base_p99 + 3.0)
        if v_p99 > bound:
            print("[tenants] victim p99 %.3fs under storm exceeds "
                  "bound %.3fs (solo baseline %.3fs)"
                  % (v_p99, bound, base_p99), flush=True)
            raise SystemExit(1)

        # per-tenant accounting must show the split: storm refused,
        # victim not, demand for both
        s = _prom_scrape(port)

        def tenant_cell(name, tenant):
            return sum(v for k, v in s.get(name, {}).items()
                       if 'tenant="%s"' % tenant in k)

        if tenant_cell("tsd_query_tenant_refused_total", "storm") <= 0:
            print("[tenants] no per-tenant refused accounting for the "
                  "storm", flush=True)
            raise SystemExit(1)
        if tenant_cell("tsd_query_tenant_refused_total", "victim") > 0:
            print("[tenants] victim shows refused demand on "
                  "prometheus", flush=True)
            raise SystemExit(1)
        # the /api/diag audit view carries the drained/refused split
        diag = json.loads(urllib.request.urlopen(
            "http://127.0.0.1:%d/api/diag" % port, timeout=10).read())
        tenants = diag.get("tenants", {}).get("tenants", {})
        if "storm" not in tenants or tenants["storm"]["refused"] <= 0:
            print("[tenants] /api/diag tenant audit missing the "
                  "storm's refused split: %r" % tenants, flush=True)
            raise SystemExit(1)

        # heal: storm over — serial victim load returns to clean 200s
        deadline = time.time() + 30
        healed = False
        while time.time() < deadline:
            statuses = [ask("victim")[0] for _ in range(5)]
            if statuses == [200] * 5:
                healed = True
                break
            time.sleep(0.5)
        if not healed:
            print("[tenants] daemon did not heal after the storm",
                  flush=True)
            raise SystemExit(1)
        check_diag_gate(port, "tenants", [
            ("storm shed",
             lambda e: e.get("kind") == "admission"
             and e.get("decision") == "shed"
             and e.get("tenant") == "storm"),
        ])
        check_explain_gate(port, "tenants", [
            ("post-heal", "start=%d&end=%d&m=sum:30s-avg:chaos.m"
             % (BASE - 1, BASE + 600)),
        ])
        print("[tenants] storm %s; victim p99 %.3fs (solo %.3fs, "
              "bound %.3fs), victim sheds 0 — fair share held"
              % (storm_tally, v_p99, base_p99, bound), flush=True)
    finally:
        tsd.send_signal(signal.SIGTERM)
        tsd.wait()


def run_failover_stage(port: int, rounds: int) -> None:
    """--failover: the replicated-sharded-serving contract (ISSUE 15,
    tsd/replication.py + docs/replication.md) against a REAL 3-node
    rf=2 cluster under mixed ingest/query load, with a kill -9 of one
    peer mid-burst:

      * zero acked-write loss: every point that ever answered 204 is
        served after the kill AND after the heal, from every node;
      * zero 500s in allow mode and zero partialResults: the shard
        cover fails over to replicas, so serving continues with FULL
        data (rf=2 means any single death is survivable);
      * the killed peer REJOINS (same WAL directory): catch-up from
        peers' tails converges, per-(origin, shard) CRC chains agree
        across the cluster (anti-entropy's byte-level evidence);
      * post-heal /api/diag/health reads every invariant ok and
        the flight recorder retains the ownership epoch changes.
    """
    import tempfile
    ports = [port, port + 1, port + 2]
    dirs = [tempfile.mkdtemp(prefix="chaos_failover_%d_" % i)
            for i in range(3)]

    def node_cfg(i: int) -> dict:
        peers = ",".join("127.0.0.1:%d" % p
                         for j, p in enumerate(ports) if j != i)
        return {
            "tsd.storage.directory": dirs[i],
            "tsd.storage.fix_duplicates": "true",
            "tsd.query.mesh.enable": "false",
            "tsd.network.cluster.peers": peers,
            "tsd.network.cluster.self": "127.0.0.1:%d" % ports[i],
            "tsd.network.cluster.shard.enable": "true",
            "tsd.network.cluster.shard.count": "32",
            "tsd.network.cluster.shard.replicas": "2",
            "tsd.network.cluster.partial_results": "allow",
            "tsd.network.cluster.retry.max_attempts": "1",
            "tsd.network.cluster.timeout_ms": "4000",
            "tsd.network.cluster.breaker.threshold": "2",
            "tsd.network.cluster.breaker.cooldown_ms": "1000",
            "tsd.replication.pull_interval_ms": "300",
        }

    procs = [spawn_tsd(ports[i], node_cfg(i), role="fo%d" % i)
             for i in range(3)]
    acked: dict = {}            # (metric, host, ts) -> value
    fails: list = []
    partials = 0
    queries = 0
    victim = 1

    def write_round(r: int, nodes: list) -> None:
        metric = "fo.m%d" % (r % 6)
        host = "h%d" % (r % 3)
        dps = [{"metric": metric, "timestamp": BASE + r,
                "value": r + 1, "tags": {"host": host}}]
        for attempt, p in enumerate(nodes + nodes):
            try:
                req = urllib.request.Request(
                    "http://127.0.0.1:%d/api/put" % p,
                    data=json.dumps(dps).encode(),
                    headers={"Content-Type": "application/json"},
                    method="POST")
                with urllib.request.urlopen(req, timeout=20) as resp:
                    if resp.status in (200, 204):
                        acked[(metric, host, BASE + r)] = r + 1
                        return
            except urllib.error.HTTPError as e:
                if e.code >= 500:
                    fails.append(("write", r, e.code))
                    return
            except OSError:
                continue        # dead node: a real client rotates
        fails.append(("write-unplaced", r, None))

    def query_metric(p: int, metric: str):
        body = {"start": BASE - 600, "end": BASE + 3600,
                "queries": [{"aggregator": "none", "metric": metric}]}
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/query" % p,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"},
            method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    def query_round(r: int, nodes: list) -> None:
        nonlocal partials, queries
        metric = "fo.m%d" % (r % 6)
        if not any(m == metric for m, _h, _t in acked):
            return
        p = nodes[r % len(nodes)]
        try:
            payload = query_metric(p, metric)
        except urllib.error.HTTPError as e:
            if e.code >= 500:
                fails.append(("query", r, e.code))
            return
        except OSError:
            return              # dead node: a real client rotates
        queries += 1
        if any(isinstance(x, dict) and x.get("partialResults")
               for x in payload):
            partials += 1
            fails.append(("partial", r, None))

    try:
        live = list(ports)
        total = max(rounds, 6) * 4
        kill_at = total // 3
        rejoin_at = 2 * total // 3
        for r in range(total):
            if r == kill_at:
                print("[failover] kill -9 node %d (127.0.0.1:%d) "
                      "mid-burst after %d acked writes"
                      % (victim, ports[victim], len(acked)), flush=True)
                procs[victim].kill()        # SIGKILL: no drain, no
                procs[victim].wait()        # snapshot, WAL tail only
                live = [p for p in ports if p != ports[victim]]
            if r == rejoin_at:
                print("[failover] rejoining node %d on its original "
                      "WAL directory" % victim, flush=True)
                procs[victim] = spawn_tsd(
                    ports[victim], node_cfg(victim),
                    role="fo%d-rejoin" % victim)
                live = list(ports)
            write_round(r, live)
            query_round(r, live)
        if fails:
            print("[failover] FAILED: %d violations, first: %r"
                  % (len(fails), fails[:5]), flush=True)
            raise SystemExit(1)

        # -- zero acked-write loss: EVERY node serves EVERY acked point
        deadline = time.time() + 60
        missing = {"boot": True}
        while time.time() < deadline and missing:
            missing = {}
            for p in ports:
                got = {}
                for metric in {m for m, _h, _t in acked}:
                    try:
                        for item in query_metric(p, metric):
                            if not isinstance(item, dict) \
                                    or "metric" not in item:
                                continue
                            host = (item.get("tags") or {}).get("host")
                            for t, v in (item.get("dps") or {}).items():
                                got[(item["metric"], host, int(t))] = v
                    except (OSError, urllib.error.HTTPError):
                        pass
                lost = {k for k, v in acked.items()
                        if got.get(k) != v}
                if lost:
                    missing[p] = sorted(lost)[:3]
            if missing:
                time.sleep(1.0)
        if missing:
            print("[failover] FAILED: acked writes missing after heal: "
                  "%r" % missing, flush=True)
            raise SystemExit(1)
        print("[failover] %d acked writes audited on all 3 nodes, "
              "%d queries, 0 x 5xx, 0 partial" %
              (len(acked), queries), flush=True)

        # -- anti-entropy evidence: per-(origin, shard) chains agree
        deadline = time.time() + 60
        diverged = {"boot": True}
        while time.time() < deadline and diverged:
            diverged = {}
            statuses = {}
            for p in ports:
                try:
                    statuses[p] = json.loads(urllib.request.urlopen(
                        "http://127.0.0.1:%d/api/replication/status"
                        % p, timeout=10).read())
                except OSError as e:
                    diverged[p] = str(e)
            chains = {p: s.get("chains", {})
                      for p, s in statuses.items()}
            for pa in ports:
                for pb in ports:
                    if pb <= pa or pa in diverged or pb in diverged:
                        continue
                    for origin in set(chains[pa]) & set(chains[pb]):
                        a, b = chains[pa][origin], chains[pb][origin]
                        for shard in set(a) & set(b):
                            if a[shard] != b[shard]:
                                diverged[(pa, pb)] = (origin, shard,
                                                      a[shard],
                                                      b[shard])
            if diverged:
                time.sleep(1.0)
        if diverged:
            print("[failover] FAILED: CRC chains diverged after "
                  "rejoin: %r" % diverged, flush=True)
            raise SystemExit(1)
        print("[failover] rejoined peer converged: CRC chains agree "
              "pairwise across the cluster", flush=True)

        # -- post-heal gate: every invariant ok + epoch evidence
        check_diag_gate(
            ports[0], "failover",
            [("replication epoch change",
              lambda e: e.get("kind") == "replication")],
            timeout_s=90.0)
    finally:
        for proc in procs:
            try:
                proc.send_signal(signal.SIGTERM)
            except OSError:
                pass
        for proc in procs:
            try:
                proc.wait(timeout=30)
            except Exception:
                proc.kill()


def check_san_reports() -> int:
    """Error-level tsdbsan findings across every armed TSD's shutdown
    report.  Missing report = the daemon died before writing it — also
    a failure (a crashed sanitized TSD must not read as clean)."""
    bad = 0
    for role, path in SAN_REPORTS:
        if not os.path.exists(path):
            print("[san] %s: report %s missing — daemon did not shut "
                  "down cleanly" % (role, path), flush=True)
            bad += 1
            continue
        with open(path) as fh:
            findings = json.load(fh)
        errors = [f for f in findings if f.get("level") == "error"]
        for f in errors:
            print("[san] %s: %s:%d [%s] %s"
                  % (role, f["path"], f["line"], f["rule"],
                     f["message"]), flush=True)
        bad += len(errors)
        notes = len(findings) - len(errors)
        print("[san] %s: %d error(s), %d note(s)"
              % (role, len(errors), notes), flush=True)
    return bad


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=25)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--port", type=int, default=14261)
    ap.add_argument("--san", action="store_true",
                    help="arm tsdbsan in every spawned TSD and fail on "
                         "error-level race/inversion findings")
    ap.add_argument("--cache", action="store_true",
                    help="run the partial-aggregate cache stage: a "
                         "cache-enabled TSD must answer byte-identical "
                         "to a cache-disabled control under mixed "
                         "repeat/sliding load with ingest running, "
                         "show a nonzero agg hit rate, and heal after "
                         "a WAL-site fault burst")
    ap.add_argument("--rollup", action="store_true",
                    help="run the rollup-lane stage: a lane-enabled "
                         "TSD must answer byte-identical to a "
                         "lane-disabled control under long-range "
                         "load with ingest overwriting points inside "
                         "queried windows, show a nonzero lane hit "
                         "rate, and heal after a WAL-site fault "
                         "burst")
    ap.add_argument("--spill", action="store_true",
                    help="run the out-of-core tiling stage: a tiled "
                         "TSD (tiny state budget, disk-backed spill "
                         "pool) must answer byte-identical to a "
                         "resident-capable control under long-range "
                         "group-by load with ingest running, keep the "
                         "pool bytes bounded, and heal after an "
                         "injected spill.write disk-full fault")
    ap.add_argument("--overload", action="store_true",
                    help="run the admission-gate overload stage: "
                         "saturating load + an injected slow-handler "
                         "fault must produce only 200s or "
                         "503+Retry-After, a bounded in-flight count, "
                         "and full recovery once the fault lifts")
    ap.add_argument("--failover", action="store_true",
                    help="run the replicated-sharded-serving stage: a "
                         "3-node rf=2 cluster under mixed ingest/query "
                         "load with a kill -9 of one peer mid-burst "
                         "must lose zero acked writes, serve zero 500s "
                         "and zero partialResults, converge the "
                         "rejoined peer's CRC chains, and read every "
                         "health invariant ok post-heal")
    ap.add_argument("--tenants", action="store_true",
                    help="run the fair-share multi-tenant stage: one "
                         "tenant storming must shed on its own "
                         "backlog while the victim tenant's p99 holds "
                         "within its solo baseline bound; zero 500s; "
                         "heals after the storm with the shed "
                         "evidence retained in the flight recorder")
    ap.add_argument("--latattr", action="store_true",
                    help="run the latency-attribution sanity stage: "
                         "with a slow-handler fault armed, "
                         "/api/diag/latency must never 5xx, every "
                         "profile must report the full non-negative "
                         "phase set, and tail exemplar trace ids must "
                         "resolve to retained slow-query captures")
    ap.add_argument("--stages-only", action="store_true",
                    help="run only the requested stage(s) "
                         "(--overload/--cache/...), skipping the "
                         "standard 2-TSD fault-proxy phases — the CI "
                         "wrappers use this to gate stages separately")
    args = ap.parse_args()
    rng = random.Random(args.seed)
    if args.overload:
        run_overload_stage(args.port + 3, args.rounds)
    if args.failover:
        run_failover_stage(args.port + 13, args.rounds)
    if args.tenants:
        run_tenants_stage(args.port + 11, args.rounds)
    if args.latattr:
        run_latattr_stage(args.port + 15, args.rounds)
    if args.cache:
        run_cache_stage(args.port + 5, args.rounds)
    if args.spill:
        run_spill_stage(args.port + 7, args.rounds)
    if args.rollup:
        run_rollup_stage(args.port + 9, args.rounds)
    if args.stages_only:
        if not (args.overload or args.cache
                or args.spill or args.rollup or args.tenants
                or args.failover or args.latattr):
            ap.error("--stages-only needs --overload, "
                     "--cache, --spill, --rollup, --tenants, "
                     "--latattr and/or --failover")
        print("chaos soak stages PASSED (standard phases skipped: "
              "--stages-only)", flush=True)
        return
    peer = spawn_tsd(args.port, {}, san=args.san, role="peer")
    try:
        seed_host(args.port, "remote", 2)
        for mode in ("allow", "error"):
            tally = run_phase(mode, args.rounds, rng, args.port,
                              args.port + 1, san=args.san)
            print("[%s] %d rounds OK: %s (healed to full)"
                  % (mode, args.rounds, tally), flush=True)
    finally:
        peer.send_signal(signal.SIGTERM)
        peer.wait()
    if args.san and check_san_reports():
        print("chaos soak FAILED: tsdbsan found races/inversions under "
              "fault injection", flush=True)
        raise SystemExit(1)
    print("chaos soak PASSED: no 500s in allow mode, no wrong answers "
          "in error mode%s"
          % (" (tsdbsan clean)" if args.san else ""), flush=True)


if __name__ == "__main__":
    main()

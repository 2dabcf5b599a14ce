"""Sustained-QPS bench for the fused multi-query dispatcher
(query/batcher.py): a mixed small-query dashboard load, batching OFF
vs ON — end-to-end through a real TSD scraped from
/api/stats/prometheus, plus the isolated dispatch layer the batcher
actually amortizes.

Two sections in the output JSON, each naming the device it ran on:

  * ``endToEnd`` — a fleet of client threads firing small dashboard
    panel queries (distinct metrics, 30s-avg) at two sequentially
    spawned daemons (identical config except
    ``tsd.query.batch.enable``); sustained QPS = delta of
    ``tsd_query_count{status="200"}`` over the timed window, p99 from
    the ``tsd_query_latency_ms`` histogram bucket deltas, batch
    evidence from the ``tsd_query_batch_*`` families.  On a small CPU
    box the serving path is Python/GIL-bound, so the end-to-end ratio
    reads ~1x there — the floor the batcher amortizes is the
    accelerator's dispatch, not measured on this installation
    (ROADMAP A5).
  * ``dispatchLayer`` — the same panel plans driven straight through
    the daemon's kernels: solo ``run_group_pipeline`` dispatches vs
    the stacked ``run_stacked_group_pipeline`` at Q=16, wall-clocked
    per member.  This isolates exactly what coalescing removes (the
    per-dispatch floor) from what it cannot (per-query serving
    Python), and is where the >= 2x pin rides
    (tests/test_batcher.py).

One process per chip: THIS process never imports jax.  The two daemons
run one after the other, and the dispatch-layer section runs last in a
child of its own, so nothing ever waits for a chip its parent holds.
The platform is an explicit argument — without `--platform cpu` a
daemon or child that is not on a TPU fails the run — and the daemons'
output goes to files beside `--out`, never to /dev/null.

    python tools/bench_qps.py                      # needs the chip
    python tools/bench_qps.py --platform cpu --seconds 20
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

OUT_PATH = os.path.join(REPO, "chiprun_out", "bench_qps.json")

BASE = 1_356_998_400            # fixed epoch seconds

# The dashboard fleet: METRICS distinct panels, each SERIES series x
# POINTS points at CADENCE_S cadence, queried with a fixed 30s-avg
# over the full range.  Small enough that every plan prices as
# dispatch-bound (plan_decision path "batched").
METRICS = 16
SERIES = 4
POINTS = 128
CADENCE_S = 8

# Dispatch-layer panel shape: a single-series dashboard panel (one
# host's metric over a short range) — the floor-bound regime.
DL_S, DL_N, DL_W = 1, 128, 16
DL_Q = 16


def wait_port(port, timeout=90):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            with socket.create_connection(("127.0.0.1", port),
                                          timeout=2):
                return True
        except OSError:
            time.sleep(0.2)
    return False


def child_env(platform: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"     # the explicit dry run only
    return env


def spawn_tsd(port: int, batching: bool, platform: str, log_path: str):
    """Start one daemon and return (proc, device): its /api/diag device
    report must name `platform`, or the run fails."""
    assert "jax" not in sys.modules, "the parent must stay off the chip"
    conf_dir = tempfile.mkdtemp(prefix="bench_qps_")
    cfg = os.path.join(conf_dir, "tsd.conf")
    with open(cfg, "w") as fh:
        fh.write("tsd.core.auto_create_metrics = true\n")
        fh.write("tsd.query.mesh.enable = false\n")
        fh.write("tsd.stats.interval = 0\n")
        fh.write("tsd.rollup.interval = 0\n")
        # saturating fleet: permits must admit enough concurrency for
        # buckets to form; the queue absorbs the rest
        fh.write("tsd.query.admission.permits = 32\n")
        fh.write("tsd.query.admission.queue_limit = 256\n")
        fh.write("tsd.query.admission.max_wait_ms = 0\n")
        # both phases host-build their batches (the batched path never
        # consults the device cache; an off-phase cache hit would
        # compare column-gather serving against batch serving instead
        # of solo-dispatch against stacked-dispatch)
        fh.write("tsd.query.device_cache.enable = false\n")
        fh.write("tsd.query.batch.enable = %s\n"
                 % ("true" if batching else "false"))
        fh.write("tsd.query.batch.hold_ms = 10\n")
        fh.write("tsd.query.batch.max_q = 16\n")
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "opentsdb_tpu.tools.tsd_main",
             "--port", str(port), "--bind", "127.0.0.1", "--config", cfg],
            env=child_env(platform), cwd=REPO, stdout=log,
            stderr=subprocess.STDOUT)
    try:
        if not wait_port(port):
            raise RuntimeError("TSD did not come up on %d (see %s)"
                               % (port, log_path))
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/api/diag?since=999999999999" % port,
                timeout=30) as resp:
            device = json.loads(resp.read())["device"]
        if device["platform"] != platform:
            raise RuntimeError(
                "the daemon computes on %r, this run measures %r (pass "
                "--platform cpu for an explicit CPU dry run)"
                % (device["platform"], platform))
    except BaseException:
        proc.kill()
        proc.wait()
        raise
    return proc, {k: device[k] for k in ("platform", "kind", "count")}


def http_put(port, points):
    req = urllib.request.Request(
        "http://127.0.0.1:%d/api/put" % port,
        data=json.dumps(points).encode(),
        headers={"Content-Type": "application/json"})
    urllib.request.urlopen(req, timeout=30).read()


def seed(port: int) -> None:
    for m in range(METRICS):
        batch = []
        for h in range(SERIES):
            for k in range(POINTS):
                batch.append({
                    "metric": "qps.m%02d" % m,
                    "timestamp": BASE + k * CADENCE_S,
                    "value": float((k * 7 + h) % 101),
                    "tags": {"host": "h%02d" % h},
                })
                if len(batch) >= 2000:
                    http_put(port, batch)
                    batch = []
        if batch:
            http_put(port, batch)


def diag_latency(port: int) -> dict | None:
    """One /api/diag/latency capture (obs/latattr.py) — None when the
    daemon predates attribution or has it disabled."""
    try:
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/api/diag/latency" % port,
                timeout=10) as resp:
            return json.loads(resp.read())
    except (urllib.error.HTTPError, OSError, ValueError):
        return None


def scrape(port: int) -> dict:
    text = urllib.request.urlopen(
        "http://127.0.0.1:%d/api/stats/prometheus" % port,
        timeout=10).read().decode()
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


def _histo_cells(scrape_out: dict, name: str) -> dict[float, float]:
    """Cumulative bucket counts {le: count} summed across label cells
    (the latency histogram is tenant-labeled)."""
    cells: dict[float, float] = {}
    for labels, value in scrape_out.get(name + "_bucket", {}).items():
        le = None
        for part in labels.strip("{}").split(","):
            if part.startswith('le="'):
                raw = part[4:-1]
                le = float("inf") if raw == "+Inf" else float(raw)
        if le is not None:
            cells[le] = cells.get(le, 0.0) + value
    return cells


def p99_from_deltas(before: dict, after: dict, name: str) -> float:
    b0 = _histo_cells(before, name)
    b1 = _histo_cells(after, name)
    deltas = sorted((le, b1.get(le, 0.0) - b0.get(le, 0.0))
                    for le in b1)
    total = deltas[-1][1] if deltas else 0.0
    if total <= 0:
        return 0.0
    want = 0.99 * total
    for le, cum in deltas:
        if cum >= want:
            return le
    return deltas[-1][0]


def run_phase(port: int, clients: int, seconds: float,
              warmup_s: float) -> dict:
    stop = [False]
    errors = [0]
    lock = threading.Lock()

    def client(worker: int) -> None:
        i = worker
        while not stop[0]:
            m = "qps.m%02d" % (i % METRICS)
            i += clients
            url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
                   "&m=sum:30s-avg:%s"
                   % (port, BASE, BASE + POINTS * CADENCE_S, m))
            try:
                with urllib.request.urlopen(url, timeout=60) as resp:
                    resp.read()
                    if resp.status != 200:
                        with lock:
                            errors[0] += 1
            except (urllib.error.HTTPError, OSError):
                with lock:
                    errors[0] += 1

    threads = [threading.Thread(target=client, args=(w,), daemon=True)
               for w in range(clients)]
    for t in threads:
        t.start()
    time.sleep(warmup_s)                 # compiles + caches settle
    before = scrape(port)
    lat_before = diag_latency(port)
    t0 = time.time()
    time.sleep(seconds)
    after = scrape(port)
    lat_after = diag_latency(port)
    elapsed = time.time() - t0
    stop[0] = True
    for t in threads:
        t.join(10)

    def total(s, name, label=None):
        cells = s.get(name, {})
        if label is None:
            return sum(cells.values())
        return sum(v for k, v in cells.items() if label in k)

    served = (total(after, "tsd_query_count_total", 'status="200"')
              - total(before, "tsd_query_count_total", 'status="200"'))
    # where the window's milliseconds went, phase by phase — the
    # always-on attribution's timed-window delta
    # (tools/latency_report.py diffs two of these into the
    # "where did the milliseconds move" table)
    from tools.latency_report import window_delta
    decomposition = window_delta(lat_before, lat_after)
    return {
        "phaseDecomposition": decomposition,
        "servedQueries": int(served),
        "elapsedS": round(elapsed, 3),
        "qps": round(served / elapsed, 2),
        "p99Ms": round(p99_from_deltas(before, after,
                                       "tsd_query_latency_ms"), 3),
        "clientErrors": errors[0],
        "stackedDispatches": int(
            total(after, "tsd_query_batch_dispatches_total")),
        "stackedQueries": int(
            total(after, "tsd_query_batch_queries_total",
                  'outcome="stacked"')),
        "soloQueries": int(
            total(after, "tsd_query_batch_queries_total",
                  'outcome="solo"')),
    }


def bench_end_to_end(port: int, clients: int, seconds: float,
                     warmup_s: float, platform: str, out_path: str
                     ) -> dict:
    phases = {}
    device = None
    for label, batching in (("off", False), ("on", True)):
        proc, device = spawn_tsd(
            port, batching, platform,
            "%s.daemon_%s.log" % (os.path.splitext(out_path)[0], label))
        try:
            seed(port)
            phases[label] = run_phase(port, clients, seconds, warmup_s)
            print("[e2e %s] %s" % (label, phases[label]), flush=True)
        finally:
            proc.send_signal(signal.SIGTERM)
            proc.wait()
    uplift = (phases["on"]["qps"] / phases["off"]["qps"]
              if phases["off"]["qps"] else 0.0)
    return {
        "device": device,
        "workload": {"metrics": METRICS, "series": SERIES,
                     "points": POINTS, "cadenceS": CADENCE_S,
                     "clients": clients, "timedSeconds": seconds},
        "off": phases["off"],
        "on": phases["on"],
        "qpsUplift": round(uplift, 2),
    }


def bench_dispatch_layer(reps: int, platform: str) -> dict:
    """Solo vs stacked dispatch throughput for the panel plan — the
    layer the batcher optimizes, measured through the SAME kernels
    the executor runs (one warm program each; integer data).  Runs in
    the child process (`--child-dispatch-layer`): it imports jax."""
    import numpy as np
    from bench import require_device
    device = require_device(platform)
    from opentsdb_tpu.ops.downsample import FixedWindows
    from opentsdb_tpu.ops.pipeline import (
        DownsampleStep, PipelineSpec, run_group_pipeline,
        run_stacked_group_pipeline)
    rng = np.random.default_rng(7)
    win = FixedWindows(1000, 0, DL_W)
    wspec, wargs = win.split()
    spec = PipelineSpec(
        aggregator="sum",
        downsample=DownsampleStep("avg", wspec, "none", 0.0),
        rate=None, int_mode=False, rows_sorted=True)
    ts = np.sort(rng.integers(0, DL_W * 1000,
                              (DL_S, DL_N))).astype(np.int64)
    val = rng.integers(0, 100, (DL_S, DL_N)).astype(np.float64)
    mask = np.ones((DL_S, DL_N), bool)
    gid = np.zeros(DL_S, np.int64)
    out = run_group_pipeline(spec, ts, val, mask, gid, 1, wargs)
    np.asarray(out[1])                                   # warm compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run_group_pipeline(spec, ts, val, mask, gid, 1, wargs)
    np.asarray(out[1])
    solo_ms = (time.perf_counter() - t0) / reps * 1e3
    ts_q = np.stack([ts] * DL_Q)
    val_q = np.stack([val] * DL_Q)
    mask_q = np.stack([mask] * DL_Q)
    gid_q = np.stack([gid] * DL_Q)
    wargs_q = {k: np.stack([np.asarray(v)] * DL_Q)
               for k, v in wargs.items()}
    out = run_stacked_group_pipeline(spec, ts_q, val_q, mask_q, gid_q,
                                     1, wargs_q)
    np.asarray(out[1])                                   # warm compile
    t0 = time.perf_counter()
    for _ in range(max(reps // 2, 1)):
        out = run_stacked_group_pipeline(spec, ts_q, val_q, mask_q,
                                         gid_q, 1, wargs_q)
    np.asarray(out[1])
    stacked_ms = (time.perf_counter() - t0) / max(reps // 2, 1) * 1e3
    member_ms = stacked_ms / DL_Q
    result = {
        "device": device,
        "panelShape": {"series": DL_S, "points": DL_N,
                       "windows": DL_W, "q": DL_Q},
        "soloMsPerDispatch": round(solo_ms, 4),
        "stackedMsPerDispatch": round(stacked_ms, 4),
        "stackedMsPerMember": round(member_ms, 4),
        "upliftPerMember": round(solo_ms / member_ms, 2),
    }
    return result


def run_dispatch_layer_child(reps: int, platform: str) -> dict:
    """The dispatch-layer section in a process of its own: the parent
    never touches jax, so it never holds a chip a daemon needs."""
    assert "jax" not in sys.modules, "the parent must stay off the chip"
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--child-dispatch-layer", "--reps", str(reps),
         "--platform", platform],
        env=child_env(platform), cwd=REPO, stdout=subprocess.PIPE,
        text=True, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError("dispatch-layer child failed (rc=%d)"
                           % proc.returncode)
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    print("[dispatch layer] %s" % result, flush=True)
    return result


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=14291)
    ap.add_argument("--clients", type=int, default=16)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--warmup", type=float, default=15.0)
    ap.add_argument("--reps", type=int, default=400)
    ap.add_argument("--skip-e2e", action="store_true",
                    help="dispatch-layer section only")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="the platform every daemon and child must "
                         "compute on; cpu is an explicit dry run")
    ap.add_argument("--out", default=OUT_PATH)
    ap.add_argument("--child-dispatch-layer", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.child_dispatch_layer:
        print(json.dumps(bench_dispatch_layer(args.reps, args.platform)),
              flush=True)
        return 0
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    result = {
        "comment": ("tools/bench_qps.py — fused multi-query dispatch "
                    "(query/batcher.py): mixed small-query dashboard "
                    "load, batching off vs on; each section names its "
                    "device."),
    }
    if not args.skip_e2e:
        result["endToEnd"] = bench_end_to_end(
            args.port, args.clients, args.seconds, args.warmup,
            args.platform, args.out)
    # last: the daemons have exited, the chip is free for the child
    result["dispatchLayer"] = run_dispatch_layer_child(args.reps,
                                                       args.platform)
    with open(args.out, "w") as fh:
        json.dump(result, fh, indent=2, sort_keys=True)
        fh.write("\n")
    print("wrote %s" % args.out, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

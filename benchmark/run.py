#!/usr/bin/env python3
"""One run of one benchmark cell, on the served path.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process never imports jax.  It starts ONE `tsd_main` daemon (its
only JAX child), loads the cell's deployment through POST /api/put,
warms the cell's own request shapes, measures for --seconds with a load
generator outside the daemon, judges every answer against a numpy
reference after the window, stops the daemon with SIGTERM, and prints as
its last line the JSON object the driver reads.  Earlier lines (prefixed
`#`) say what that line has no key for.

Whatever ends a run, nothing it started outlives it: SIGTERM, SIGINT and
SIGHUP raise `Cut` in the main thread, so the same `finally` that ends a
failed run ends the daemon and the writers, and the runner exits with
128 + the signal's number, prints no result line and says on stderr
where it was cut; a SIGKILL, which nothing can handle, is covered by the
parent-death signal the daemon carries (daemon.die_with_parent) and the
look each writer takes at its parent's pid (loadgen._end_with).

Nothing about a cell, a request class or a metric is written here: the
cell is workloads/<cell>.json, its deployment configs/<config>.json, its
traffic traffic/<mix>.json, each per-layer metric layers/<metric>.json
(or .py), all found by the names in BENCHMARK.json.  See README.md.

Without a TPU (or with fewer chips than the cell asks for) the run exits
non-zero before ingest.  `--rehearse hosts=40,hours=2` is the explicit
CPU rehearsal for sandboxes and tests: tiny data, platform stamped
"cpu", device metrics left out.  It is never the default.
"""

from __future__ import annotations

import time

T_START = time.monotonic()          # set-up counts from process start

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

from benchmark import (daemon, loadgen, readers, reference, tracing,  # noqa: E402
                       traffic)
from benchmark.daemon import BenchFailure, say  # noqa: E402
from benchmark.tsbs import CADENCE_S, Fleet  # noqa: E402


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


class Cell:
    """Everything BENCHMARK.json and the data files say about one cell."""

    def __init__(self, benchmark_json: str, name: str):
        self.base = os.path.dirname(os.path.abspath(benchmark_json))
        self.bench = load_json(benchmark_json)
        entry = next((w for w in self.bench["workloads"]
                      if w["name"] == name), None)
        if entry is None:
            raise BenchFailure("BENCHMARK.json has no workload %r" % name)
        cfg = next(c for c in self.bench["configs"]
                   if c["name"] == entry["config"])
        self.root = os.path.dirname(os.path.dirname(
            os.path.join(self.base, cfg["file"])))
        self.name = name
        self.spec = load_json(os.path.join(self.root, "workloads",
                                           name + ".json"))
        for key in ("config", "traffic", "chips"):
            if self.spec[key] != entry[key]:
                raise BenchFailure(
                    "workloads/%s.json and BENCHMARK.json disagree on %s"
                    % (name, key))
        self.chips = entry["chips"]
        self.config = load_json(os.path.join(self.base, cfg["file"]))
        self.mix = traffic.load_mix(self.root, entry["traffic"])

    def metrics(self, section: str) -> list[dict]:
        return [m for m in self.bench[section]
                if self.name in m.get("workloads", [self.name])]


# --------------------------------------------------------------------- #
# Set-up                                                                #
# --------------------------------------------------------------------- #

def build_native() -> None:
    """native/libtsdb_engine.so is git-ignored: a checkout has none, and
    without it every put silently takes the Python parser."""
    proc = subprocess.run(["make", "-C", os.path.join(REPO, "native")],
                          capture_output=True, text=True, timeout=300)
    if proc.returncode != 0:
        raise BenchFailure("native build failed: " + proc.stderr[-800:])


def child_env(rehearse: bool, chips: int) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    # the product's own rule (opentsdb_tpu/ops/__init__.py), spelled out:
    # the compile cache sits at a fixed path inside the checkout
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   os.path.join(REPO, ".jax_cache"))
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%d"
                            % chips)
    return env


def check_device(device: dict, chips: int, rehearse: bool) -> None:
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want or device["count"] != chips:
        raise BenchFailure(
            "the daemon computes on %d x %s; this cell needs %d x %s — no "
            "accelerator, the wrong number of chips, or JAX fell back to "
            "the host" % (device["count"], device["platform"], chips, want))


class Warmer:
    """Sends the cell's own shapes until nothing compiles."""

    def __init__(self, client: daemon.Client):
        self.client = client
        self.compiles = daemon.compile_total(daemon.counters(client))
        # per class, what sizes a mix against an empty compile cache: the
        # seconds of its first send and of all, tsd.jax.compiles across them
        self.by_class: dict[str, dict] = {}

    def compiled(self) -> int:
        now = daemon.compile_total(daemon.counters(self.client))
        delta, self.compiles = now - self.compiles, now
        return int(delta)

    def send(self, req: dict) -> int:
        t = time.monotonic()
        status, body = self.client.request("GET", req["path"])
        took = time.monotonic() - t
        if status != 200:
            raise BenchFailure("warm-up %s -> %d: %s"
                               % (req["cls"], status, body[:400]))
        compiled = self.compiled()
        if req["cls"] not in self.by_class:     # said at once: a run that
            # is cut in warm-up has then said how far it came
            say("warm-up, first send of %s: %.3f s, %d compiles"
                % (req["cls"], took, compiled))
        seen = self.by_class.setdefault(req["cls"], {
            "first_s": round(took, 3), "first_compiles": compiled,
            "sends": 0, "all_s": 0.0, "compiles": 0})
        seen["sends"] += 1
        seen["all_s"] = round(seen["all_s"] + took, 3)
        seen["compiles"] += compiled
        return compiled


def warm_sequential(warmer: Warmer, gen: traffic.Generator, mix: dict,
                    cycle: list[dict] | None) -> dict:
    """Closed loop: the replay list itself, whole cycles, until a cycle
    compiles nothing.  Open loop: instances of each class, each sent
    `repeat` times in a row, until an instance compiles nothing.  The
    repeats matter: the second sight of a plan engages the partial-
    aggregate rewrite and the third serves from it, and each of those
    routes compiles on its own first send."""
    w = mix.get("warmup", {})
    lo, hi = w.get("min_sends", 2), w.get("max_sends", 6)
    sends = {}
    if cycle is not None:
        # smallest first: a scan that meets a cold device cache streams,
        # and the streamed fold is no part of the steady state
        ordered = sorted(cycle, key=lambda r: r["points"])
        for n in range(hi):
            compiled = sum(warmer.send(r) for r in ordered)
            sends["cycle%d" % n] = compiled
            if n + 1 >= lo and not compiled:
                break
        return sends
    for cls in gen.classes:
        n = 0
        for req in gen.warm_instances(cls, hi):
            n += 1
            compiled = sum(warmer.send(req)
                           for _ in range(w.get("repeat", 3)))
            if n >= lo and not compiled:
                break
        sends[cls["name"]] = n
    return sends


def warm_bursts(warmer: Warmer, gen: traffic.Generator, mix: dict,
                port: int) -> dict:
    """Open loop: requests that arrive together are stacked into one
    dispatch, compiled per padded stack size (4, 16).  Bursts of each
    size in `warmup.bursts`, class by class, until a pass over the sizes
    compiles nothing: what the window's coincidences will need."""
    w = mix.get("warmup", {})
    sizes, passes = w.get("bursts", []), {}
    rd = mix["readers"]
    for k, cls in enumerate(gen.classes if sizes else []):
        if not cls.get("stacks"):
            continue        # only small grouped queries are ever stacked
        n = 0
        while n < w.get("max_burst_passes", 6):
            n += 1
            compiled = 0
            for size in sizes:
                rng = np.random.default_rng([gen.seed, 17, k, n, size])
                recs = [loadgen.Record(gen.instance(cls, rng), 0.0)
                        for _ in range(size)]
                loadgen.run_open(port, rd["connections"], np.zeros(size),
                                 recs, time.monotonic(), 60.0)
                compiled += warmer.compiled()
            if not compiled and n >= w.get("min_burst_passes", 2):
                break
        passes[cls["name"]] = n
    return passes


# --------------------------------------------------------------------- #
# The window                                                            #
# --------------------------------------------------------------------- #

def collect_puts(futures, t0: float, cursors: list[int]) -> list[tuple]:
    """(issued, acked, points) per body, seconds from t0; moves each
    writer's cursor to where it stopped."""
    puts = []
    for w, fut in enumerate(futures or []):
        for issued, acked, points, nxt in fut.result():
            puts.append((issued - t0, acked - t0, points))
            cursors[w] = nxt
    return puts


# --------------------------------------------------------------------- #
# Judging                                                               #
# --------------------------------------------------------------------- #

def judge(records, fleet: Fleet, refs: dict) -> list[str]:
    """Sets .ok on every record; returns what was wrong (first 10)."""
    wrong = []
    for rec in records:
        req = rec.req
        if rec.sent is None:
            why = "never sent (the generator fell behind)"
        elif rec.error:
            why = rec.error
        elif rec.status != 200:
            why = "HTTP %d" % rec.status
        else:
            payload = json.loads(rec.body)
            if req["kind"] == "last":
                why = reference.check_last(fleet, req["hosts"], payload)
                rec.groups = len(payload)
            else:
                want = refs.get(req["path"])
                if want is None:
                    want = refs[req["path"]] = reference.ref_query(fleet,
                                                                   req)
                rec.groups = len(want)
                why = reference.compare(reference.parse_answer(
                    payload, req["group_by"], bool(req.get("metrics"))), want)
        rec.ok = why is None
        rec.body = b""
        if why and len(wrong) < 10:
            wrong.append("%s: %s" % (req["cls"], why))
    return wrong


def read_back(client, fleet: Fleet, edges, cursors, seed: int,
              n: int) -> str | None:
    """`n` seeded series over the backfilled range, value for value
    (`none:` = raw points)."""
    rng = np.random.default_rng([seed, 16])
    for h in sorted(int(x) for x in rng.choice(
            fleet.hosts, size=min(n, fleet.hosts), replace=False)):
        w = int(np.searchsorted(edges, h, side="right")) - 1
        c1 = cursors[w]
        if c1 <= fleet.retained:
            continue
        path = "/api/query?start=%d&end=%d&m=none:%s%%7Bhostname=host_%d%%7D" % (
            fleet.ts[fleet.retained], fleet.ts[c1 - 1], fleet.metric, h)
        got = reference.parse_answer(client.get_json(path),
                                     "hostname").get("host_%d" % h)
        want_ts = fleet.ts[fleet.retained:c1]
        if (got is None or not np.array_equal(got[0], want_ts)
                or not np.array_equal(got[1],
                                      fleet.values[h, fleet.retained:c1])):
            return ("read-back of host_%d differs from what was acked "
                    "(%d of %d points)" % (
                        h, 0 if got is None else len(got[0]), len(want_ts)))
    return None


# --------------------------------------------------------------------- #
# Driver                                                                #
# --------------------------------------------------------------------- #

def parse_rehearse(text: str | None) -> dict | None:
    if not text:
        return None
    return {k: int(v) for k, v in (kv.split("=") for kv in text.split(","))}


SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP)


class Cut(BaseException):
    """The run is being ended from outside.  Not an Exception: no
    `except Exception` on the way up may swallow it."""

    def __init__(self, signum: int):
        super().__init__(signal.Signals(signum).name)
        self.signum = signum


def ignore_signals() -> None:
    for signum in SIGNALS:
        signal.signal(signum, signal.SIG_IGN)


def raise_cut(signum: int, frame) -> None:
    ignore_signals()        # the way out is taken once, and to its end
    raise Cut(signum)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", default=None, metavar="hosts=N,hours=N",
                    help="explicit CPU rehearsal at a tiny size; stamps "
                         "platform cpu and prints no device metric")
    ap.add_argument("--benchmark-json",
                    default=os.path.join(REPO, "BENCHMARK.json"),
                    help="the BENCHMARK.json to read (its data files are "
                         "found relative to it)")
    ap.add_argument("--sweep", default=None, metavar="START,FACTOR,STEPS",
                    help="find the knee of an open-loop mix: one load, "
                         "then windows of --seconds at rising rates; "
                         "prints a table and no result line")
    ap.add_argument("--out", default=None,
                    help="scratch directory of this run (default "
                         "benchmark_out/<cell> in the checkout)")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(REPO, "opentsdb_tpu")):
        print("benchmark/run.py needs the repository around it (no "
              "opentsdb_tpu/ beside benchmark/)", file=sys.stderr)
        return 2
    for signum in SIGNALS:
        signal.signal(signum, raise_cut)
    try:
        return run(args)
    except BenchFailure as e:
        print("benchmark: " + str(e), file=sys.stderr, flush=True)
        return 1
    except Cut as cut:      # before there was a run to end
        print("benchmark: cut by %s before set-up began" % cut,
              file=sys.stderr, flush=True)
        return 128 + cut.signum


class Run:
    """One run: the state that set-up builds and the window reads."""

    def __init__(self, args, cell: Cell):
        self.args, self.cell, self.mix = args, cell, cell.mix
        self.rd, self.wr = cell.mix.get("readers"), cell.mix.get("writers")
        self.seconds = float(args.seconds if args.seconds is not None
                             else cell.bench["run_seconds"])
        self.rehearse = parse_rehearse(args.rehearse)
        self.out_dir = args.out or os.path.join(REPO, "benchmark_out",
                                                cell.name)
        self.env = child_env(bool(self.rehearse), cell.chips)
        self.phases: dict[str, float] = {}
        self.doing = "start"        # the step a cut run was in
        self.proc = self.writers = self.gen = self.cycle = self.due = None
        self.records: list = []
        self.refs: dict = {}
        self.cursors: list[int] = []

    def timed(self, name: str, fn, *a):
        t = time.monotonic()
        try:
            return fn(*a)
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.monotonic() - t)

    # -- set-up ------------------------------------------------------- #

    def start(self) -> None:
        """Daemon up on the right device, data generated meanwhile."""
        shutil.rmtree(self.out_dir, ignore_errors=True)
        os.makedirs(self.out_dir)
        self.timed("build_native", build_native)
        self.port = daemon.free_port()
        tsd_config = dict(self.cell.config["tsd"])
        if self.rehearse:       # what only a rehearsal's size makes needful
            tsd_config.update(self.mix.get("rehearse", {}).get("tsd", {}))
        self.proc = daemon.start(self.port, self.out_dir, self.env,
                                 tsd_config, traced=bool(self.args.trace))
        cfg = self.cell.config
        scale = {"hosts": cfg["hosts"], "hours": cfg["retention_hours"]}
        scale.update(self.rehearse or {})
        retained = scale["hours"] * 3600 // CADENCE_S
        self.extra = 0
        if self.wr:
            # a rehearsal scales the backfill with the retained range, so
            # the store grows by the same factor as at full size
            self.extra = int(
                (self.wr["backfill_hours"] + self.wr["warm_hours"]) * 3600
                * scale["hours"] / cfg["retention_hours"]) // CADENCE_S
        self.fleet = self.timed("generate", Fleet, scale["hosts"], retained,
                                self.extra, self.args.seed,
                                cfg.get("metrics", 1))
        self.values_path = os.path.join(self.out_dir, "values.npy")
        np.save(self.values_path, self.fleet.data.astype(np.int8))
        self.timed("daemon_start", daemon.wait_ready, self.proc, self.port,
                   600.0)
        self.client = daemon.Client(self.port)
        device = daemon.device_section(self.client)
        check_device(device, self.cell.chips, bool(self.rehearse))
        say("device: %d x %s (%s); host cpus: %s" % (
            device["count"], device["platform"], device["kind"],
            os.cpu_count()))

    def draw_traffic(self) -> None:
        """The window's requests and their references, from the seed."""
        if not self.rd:
            return
        self.gen = traffic.Generator(self.fleet, self.rd, self.args.seed)
        if self.rd["loop"] == "closed":
            reqs = self.cycle = self.gen.replay_list()
        else:
            self.due, reqs = self.gen.open_schedule(
                self.rd["rate_per_s"], self.seconds, 0)
            self.records = [loadgen.Record(r, float(d))
                            for r, d in zip(reqs, self.due)]
        for req in reqs:
            if req["kind"] == "query" and req["path"] not in self.refs:
                self.refs[req["path"]] = reference.ref_query(self.fleet, req)

    def load(self) -> None:
        """The retained store through POST /api/put, from writer
        processes; the parent meanwhile draws the traffic and computes
        its references."""
        fleet, procs = self.fleet, self.mix.get("load_processes", 6)
        self.writers = loadgen.Writers(
            max(procs, self.wr["processes"] if self.wr else 0), self.port,
            self.values_path, fleet.tags, fleet.metrics)
        t_load = time.monotonic()
        loaded: dict = {}

        def load_store() -> None:
            try:
                loaded.update(n=self.writers.load(fleet.hosts, fleet.retained,
                                                  len(fleet.metrics)),
                              s=time.monotonic() - t_load)
            except Exception as e:      # the main thread says it, below; a
                loaded["error"] = e     # cut run's pool breaks under it

        loader = threading.Thread(target=load_store, daemon=True)
        loader.start()
        self.timed("reference", self.draw_traffic)
        loader.join()
        if "n" not in loaded:
            raise BenchFailure("loading the store failed (see daemon.log): "
                               "%r" % loaded.get("error"))
        self.phases["ingest"] = loaded["s"]
        sent = len(fleet.metrics) * fleet.hosts * fleet.retained
        ctr = daemon.counters(self.client)
        added = daemon.counter_sum(ctr, "tsd_datapoints_added")
        if loaded["n"] != sent or added != sent:
            raise BenchFailure("sent %d points; acked %d, added %d"
                               % (sent, loaded["n"], added))
        if (daemon.counter_sum(ctr, "tsd_put_parser{parser=python}")
                or not daemon.counter_sum(ctr,
                                          "tsd_put_parser{parser=native}")):
            raise BenchFailure("the put path took the Python fallback "
                               "parser: is native/libtsdb_engine.so built?")
        say("loaded %d points of %d metrics in %.1f s (%.3f Mpts/s, %d "
            "writer processes, parser native)" % (
                sent, len(fleet.metrics), loaded["s"],
                sent / loaded["s"] / 1e6, procs))

    def backfill(self, c_end: int, t0: float, seconds: float):
        wr = self.wr
        return self.writers.backfill(
            self.fleet.hosts, wr["body_points"], self.cursors, c_end, t0,
            t0 + seconds)

    def warm_round(self, n: int, conc_s: float) -> list:
        """The cell's own concurrent pattern for `conc_s` seconds,
        writers included (they write the hours set aside for warm-up)."""
        rd, wr = self.rd, self.wr
        t0 = time.monotonic() + 0.05
        futs = None
        if wr:
            warm_end = self.fleet.retained + int(
                self.extra * wr["warm_hours"]
                / (wr["warm_hours"] + wr["backfill_hours"]))
            futs = self.backfill(warm_end, t0, conc_s)
        recs = []
        if rd and rd["loop"] == "closed":
            recs = loadgen.run_closed(self.port, rd["clients"], self.cycle,
                                      t0, conc_s, 0)
        elif rd:
            due, reqs = self.gen.open_schedule(rd["rate_per_s"], conc_s,
                                               1000 + n)
            recs = [loadgen.Record(r, float(d)) for r, d in zip(reqs, due)]
            loadgen.run_open(self.port, rd["connections"], due, recs, t0,
                             rd.get("drain_s", 30.0))
        collect_puts(futs, t0, self.cursors)
        return recs

    def warm(self) -> None:
        """Sequential sends, bursts, then concurrent rounds until
        `quiet_rounds` in a row compile nothing: stacked batches, cache
        misses under writes and re-pins only appear under concurrency."""
        w = self.mix.get("warmup", {})
        warmer = Warmer(self.client)
        self.cursors = [self.fleet.retained] * (
            self.wr["processes"] if self.wr else 0)
        sends = {}
        if self.rd:
            sends = warm_sequential(warmer, self.gen, self.mix, self.cycle)
            if self.rd["loop"] == "open":
                sends["burst passes"] = warm_bursts(warmer, self.gen,
                                                    self.mix, self.port)
        rounds = quiet = 0
        while w.get("concurrent_s") and rounds < w.get("max_rounds", 4):
            rounds += 1
            recs = self.warm_round(rounds, w["concurrent_s"])
            compiled = warmer.compiled()
            say("warm-up round %d: %d requests, %d not 200, %d compiles"
                % (rounds, len(recs),
                   sum(1 for r in recs if r.status != 200), compiled))
            quiet = 0 if compiled else quiet + 1
            if quiet >= w.get("quiet_rounds", 1):
                break
        say("warm-up sends: %s; concurrent rounds: %d" % (
            json.dumps(sends), rounds))
        say("warm-up by class (sequential sends; seconds and "
            "tsd.jax.compiles across them): %s"
            % json.dumps(warmer.by_class))

    # -- the window --------------------------------------------------- #

    def window(self) -> dict:
        """Baselines, the measured window, and what the daemon says
        after it.  Returns the readers' context."""
        args, client = self.args, self.client
        ctr_before = daemon.counters(client)
        lat_before = client.get_json("/api/diag/latency")
        trace_every = self.mix.get("trace_sample", 20) if args.trace else 0
        poller = tracer = None
        if args.trace:
            poller = tracing.Poller(self.port)
            poller.start()
        t0 = time.monotonic() + 0.05    # the first timed request
        self.setup_s = t0 - T_START
        try:
            if args.trace:
                tracer = tracing.Tracer(self.proc,
                                os.path.join(self.out_dir, "trace"), t0,
                                self.seconds,
                                self.mix.get("trace_seconds", 4.0))
                tracer.start()
            futures = (self.backfill(len(self.fleet.ts), t0, self.seconds)
                       if self.wr else None)
            self.records = run_readers(self, t0, trace_every)
            before = list(self.cursors)
            puts = collect_puts(futures, t0, self.cursors)
            window_s = time.monotonic() - t0
            if tracer is not None:
                tracer.join(420.0)
        finally:
            plans = poller.finish() if poller is not None else []
        ctx = {"records": self.records, "puts": puts, "puts_stored": True,
               "seconds": self.seconds, "window_s": window_s,
               "lat_before": lat_before, "ctr_before": ctr_before,
               "ctr_after": daemon.counters(client),
               "lat_after": client.get_json("/api/diag/latency"),
               "device": daemon.device_section(client), "plans": plans,
               "fleet": self.fleet, "write_error": None,
               "trace_window": tracer.window if tracer else None}
        if self.wr:
            self.check_writes(ctx, before)
        return ctx

    def check_writes(self, ctx: dict, before: list[int]) -> None:
        """What the window acked must be stored and read back."""
        wr, puts = self.wr, ctx["puts"]
        acked = sum(p[2] for p in puts)
        added = int(
            daemon.counter_sum(ctx["ctr_after"], "tsd_datapoints_added")
            - daemon.counter_sum(ctx["ctr_before"], "tsd_datapoints_added"))
        if acked != added:
            ctx["write_error"] = ("acked %d points, daemon added %d"
                                  % (acked, added))
        else:
            ctx["write_error"] = read_back(
                self.client, self.fleet,
                loadgen.host_edges(self.fleet.hosts, wr["processes"]),
                self.cursors, self.args.seed, wr.get("read_back", 16))
        ctx["puts_stored"] = ctx["write_error"] is None
        say("writers: %d bodies, %d points acked, added delta %d, read "
            "back %s; columns per writer %s -> %s of %d" % (
                len(puts), acked, added,
                "ok" if ctx["puts_stored"] else "FAILED", before,
                self.cursors, len(self.fleet.ts)))

    def close(self) -> None:
        """Never leave the daemon holding the chip or a writer its
        connection, whatever failed.  A run that is cut ends the daemon
        first (a writer's body in flight then fails at once) and gives
        it 20 s of its graceful path, not 120, before the kill."""
        cut = isinstance(sys.exc_info()[1], Cut)
        if self.writers is not None and not cut:
            self.writers.close()
        try:
            if self.proc is not None:
                rc = daemon.stop(self.proc, self.port,
                                 20.0 if cut else 120.0)
                if rc != 0 and sys.exc_info()[0] is None:
                    raise BenchFailure("daemon shutdown was not graceful: "
                                       "rc=%s" % rc)
        finally:
            if self.writers is not None and cut:
                self.writers.close(cut=True)
            elif cut:       # cut while the pool was starting them
                loadgen.end_workers()


def run_readers(run: Run, t0: float, trace_every: int) -> list:
    """The readers' side of the measured window, from t0."""
    rd = run.rd
    if rd is None:
        time.sleep(max(t0 + run.seconds - time.monotonic(), 0))
        return []
    if rd["loop"] == "closed":
        return loadgen.run_closed(run.port, rd["clients"], run.cycle, t0,
                                  run.seconds, trace_every)
    if trace_every:
        for i, rec in enumerate(run.records[::trace_every]):
            rec.trace_id = "bench%d" % i
    loadgen.run_open(run.port, rd["connections"], run.due, run.records, t0,
                     rd.get("drain_s", 30.0))
    return run.records


def result_line(run: Run, ctx: dict) -> dict:
    """Judge (after the window, off the daemon's cores), reduce the
    trace, read the metrics: the contract's last line."""
    args, cell, rehearse = run.args, run.cell, run.rehearse
    records, puts, device = ctx["records"], ctx["puts"], ctx["device"]
    wrong = judge(records, run.fleet, run.refs)
    if ctx["write_error"]:
        wrong.append(ctx["write_error"])
    new = {k: v - ctx["ctr_before"].get(k, 0)
           for k, v in ctx["ctr_after"].items()
           if k.startswith("tsd_jax_compiles_total")
           and v > ctx["ctr_before"].get(k, 0)}
    if new:
        wrong.append("%d compiles inside the window: %s"
                     % (sum(new.values()), new))
    for w in wrong:
        say("WRONG: " + w)
    # each number that decides `correct`, beside its limit (the limits are
    # the configuration's own guarantees: answers exact, no compile in
    # the window, every acked point stored and read back)
    compared = {
        "answers_judged": {"value": len(records), "limit": None},
        "answers_wrong": {"value": sum(1 for r in records if not r.ok),
                          "limit": 0},
        "compiles_in_window": {"value": int(sum(new.values())), "limit": 0},
        "acked_writes_not_read_back": {
            "value": 0 if ctx["puts_stored"] else len(puts), "limit": 0}}
    ctx["trace"] = None
    if args.trace and not rehearse:
        ctx["trace"] = tracing.reduce_trace(os.path.join(run.out_dir, "trace"),
                                    run.out_dir, run.env)
    ctx["peaks"] = load_json(os.path.join(cell.root, "peaks.json"))[
        "by_device_kind"].get(device["kind"])
    if ctx["peaks"] is None and not rehearse:
        raise BenchFailure("peaks.json has no device kind %r"
                           % device["kind"])
    report(cell, ctx, run.phases, run.setup_s)
    values = {}
    if args.trace:
        for m in cell.metrics("per_layer"):
            spec = readers.load_layer(cell.root, m["name"])
            if rehearse and spec["reader"].get("kind") in (
                    "trace", "device_memory"):
                continue            # no device metric from a CPU
            values[m["name"]] = (m, readers.read(cell.root, spec, ctx))
    else:
        for m in cell.metrics("end_to_end"):
            values[m["name"]] = (m, run.setup_s if m["name"] == "setup_s"
                                 else readers.stat(
                                     cell.mix["metrics"][m["name"]], ctx))
    metrics = {name: {"value": value, "unit": m["unit"]}
               for name, (m, value) in values.items() if value is not None}
    peak = [m["peakBytesInUse"] for m in device["memory"]]
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"],
           "memory_peak_bytes": None if None in peak else max(peak)}
    line = {"correct": not wrong, "attempted": len(records) + len(puts),
            "failed": sum(1 for r in records if not r.ok) + (
                0 if ctx["puts_stored"] else len(puts)),
            "metrics": metrics, "device": dev}
    trace = ctx["trace"]
    if trace and trace.get("device_count"):
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        line["breakdown"] = tracing.breakdown(trace)
    elif args.trace and not rehearse:
        raise BenchFailure("the traced run saw no operation on a device")
    line["compared"] = compared        # last in the line, and on stderr
    return line


def run(args) -> int:
    cell = Cell(args.benchmark_json, args.workload)
    if args.sweep:      # warm up at the sweep's first rate, not the cell's
        cell.mix["readers"]["rate_per_s"] = float(args.sweep.split(",")[0])
    this = Run(args, cell)
    try:
        try:
            this.start()
            this.doing = "load"
            this.load()
            this.doing = "warmup"
            this.timed("warmup", this.warm)
            this.doing = "window"
            if args.sweep:
                sweep(this)
                return 0
            ctx = this.window()
            this.client.close()
        finally:
            this.close()
        this.doing = "judging"
        line = result_line(this, ctx)
    except Cut as cut:
        print("benchmark: cut by %s %.1f s after its start, in %s; "
              "phases so far: %s; nothing it started is left running"
              % (cut, time.monotonic() - T_START, this.doing,
                 ", ".join("%s %.1f" % kv for kv in this.phases.items())
                 or "nothing"), file=sys.stderr, flush=True)
        return 128 + cut.signum
    ignore_signals()        # a result is printed whole or not at all
    print(json.dumps(line), flush=True)
    for name, c in line["compared"].items():
        print("compared %s = %s (limit %s)" % (name, c["value"], c["limit"]),
              file=sys.stderr, flush=True)
    return 0


# counter deltas over the window that go on earlier lines, beside the
# metrics that read them
REPORT_COUNTERS = (
    "tsd_query_admission_shed_total", "tsd_diag_ring_dropped",
    "tsd_query_batch_stacked_members", "tsd_query_batch_queries_total",
    "tsd_query_device_cache_hits", "tsd_query_device_cache_misses",
    "tsd_query_device_cache_builds", "tsd_query_agg_cache_hits",
    "tsd_query_agg_cache_misses", "tsd_put_parser{parser=native}")


def report(cell: Cell, ctx: dict, phases: dict, setup_s: float) -> None:
    """The earlier lines: what the last line has no key for."""
    say("set-up %.1f s: %s" % (setup_s, ", ".join(
        "%s %.1f" % kv for kv in phases.items())))
    records = ctx["records"]
    by_cls: dict[str, list] = {}
    for r in records:
        by_cls.setdefault(r.req["cls"], []).append(r)
    for name, recs in by_cls.items():
        lat = [(r.done - r.due) * 1e3 for r in recs if r.ok]
        say("class %-22s n=%-5d ok=%-5d median %.2f ms  mean %.2f ms" % (
            name, len(recs), len(lat),
            np.median(lat) if lat else float("nan"),
            np.mean(lat) if lat else float("nan")))
    say("window %.2f s measured (%.0f s asked); %d requests, %d bodies" % (
        ctx["window_s"], ctx["seconds"], len(records), len(ctx["puts"])))
    # read after the daemon and the writers were waited for: the
    # largest child is the daemon
    say("peak resident memory, MB: runner %.0f, largest child (the "
        "daemon) %.0f" % tuple(resource.getrusage(who).ru_maxrss * 1024 / 1e6
                              for who in (resource.RUSAGE_SELF,
                                          resource.RUSAGE_CHILDREN)))
    # the traced run's end-to-end numbers: set beside a --trace 0 run's,
    # their difference is what tracing costs
    for name, spec in cell.mix.get("metrics", {}).items():
        value = readers.stat(spec, ctx)
        if value is not None:
            say("end-to-end (this run) %s = %.4f" % (name, value))
    d = readers.latattr_delta(ctx["lat_before"], ctx["lat_after"],
                              "api/query")
    if d["requests"] > 0:
        per = {ph: d[ph] / d["requests"] for ph in readers.PHASES}
        say("latattr api/query, ms per request over %d requests: %s" % (
            d["requests"], ", ".join("%s %.3f" % kv for kv in per.items())))
        ok = [r for r in records if r.ok and r.req["kind"] == "query"]
        if ok:
            mean = float(np.mean([(r.done - r.sent) * 1e3 for r in ok]))
            say("client mean %.3f ms per request; unaccounted_ms_per_req "
                "= %.3f" % (mean, mean - sum(per.values())))
    for key in REPORT_COUNTERS:
        say("counter delta %s = %g" % (key, daemon.counter_sum(
            ctx["ctr_after"], key) - daemon.counter_sum(
                ctx["ctr_before"], key)))
    mem = ctx["device"]["memory"]
    if len(mem) > 1:        # where the pinned store sits, chip by chip
        def mb(value):
            return "?" if value is None else "%.1f" % (value / 1e6)
        say("device memory, MB in use / peak per device: %s" % ", ".join(
            "%s / %s" % (mb(m.get("bytesInUse")), mb(m.get("peakBytesInUse")))
            for m in mem))
    plans = ctx["plans"]
    if plans:
        paths: dict[str, int] = {}
        for p in plans:
            key = "%s%s%s" % (p["path"],
                              "+hostLane" if p.get("hostLane") else "",
                              "+mesh%d" % p["meshDevices"]
                              if p.get("meshDevices") else "")
            paths[key] = paths.get(key, 0) + 1
        say("routes of %d sampled requests: %s" % (len(plans),
                                                   json.dumps(paths)))
    tr = ctx.get("trace")
    if tr and tr.get("device_count"):
        n_req, nbytes = readers.traced_requests(ctx)
        say("trace: window %.3f s, busy %.4f s/device, %.2f requests and "
            "%.1f MB needed inside it" % (
                tr["window_s"], tr["busy_s"], n_req, nbytes / 1e6))
        for name, dev in tr["devices"].items():
            say("trace %s: busy %.4f s, collectives %.4f s (%.4f s with "
                "no compute running), modules %s" % (
                    name, dev["busy_s"], dev["collective_s"],
                    dev["collective_exposed_s"],
                    json.dumps({k: [v[0], round(v[1], 5)]
                                for k, v in dev["modules"].items()})))


def sweep(run: Run) -> None:
    """The knee of an open-loop mix: the highest rate at which >= 99 % of
    due requests complete (and are right), none is shed and the backlog
    at the window's end is under 1 s of arrivals."""
    args, cell, port, gen = run.args, run.cell, run.port, run.gen
    fleet, refs, client, seconds = (run.fleet, run.refs, run.client,
                                    run.seconds)
    start, factor, steps = args.sweep.split(",")
    rate, fails, knee = float(start), 0, None
    rd = cell.mix["readers"]
    warmer = Warmer(client)
    for i in range(int(steps)):
        due, reqs = gen.open_schedule(rate, seconds, 2000 + i)
        recs = [loadgen.Record(r, float(d)) for r, d in zip(reqs, due)]
        shed0 = daemon.counter_sum(daemon.counters(client),
                                   "tsd_query_admission_shed_total")
        lat0 = client.get_json("/api/diag/latency")
        t0 = time.monotonic() + 0.05
        loadgen.run_open(port, rd["connections"], due, recs, t0, 10.0)
        shed = daemon.counter_sum(daemon.counters(client),
                                  "tsd_query_admission_shed_total") - shed0
        judge(recs, fleet, refs)
        refs.clear()
        panel = cell.mix["metrics"]
        ctx = {"records": recs}
        done_in = sum(1 for r in recs if r.ok and r.done <= seconds + 1.0)
        backlog = sum(1 for r in recs
                      if r.sent is None or r.sent > seconds)
        ok = (done_in >= 0.99 * len(recs) and not shed
              and backlog < rate * 1.0)
        compiled = warmer.compiled()
        if not ok and not compiled:
            time.sleep(3.0)         # a compile may still be finishing
            compiled = warmer.compiled()
        if compiled:
            # a step that compiled measured the compiler: not judged
            say("sweep rate %.1f/s: %d compiles inside the step, not "
                "judged (%d of %d ok)" % (rate, compiled,
                                          sum(r.ok for r in recs), len(recs)))
            continue
        say("sweep rate %.1f/s: %d due, %d ok, %d done by window+1s, "
            "%d shed, backlog %d, late p99 %.1f ms, %s -> %s" % (
                rate, len(recs), sum(r.ok for r in recs), done_in, shed,
                backlog, readers.stat({"stat": "late_percentile", "q": 99},
                                      ctx) or -1,
                ", ".join("%s %.2f" % (k, readers.stat(v, ctx) or -1)
                          for k, v in panel.items()),
                "sustained" if ok else "NOT sustained"))
        d = readers.latattr_delta(lat0, client.get_json(
            "/api/diag/latency"), "api/query")
        say("   latattr ms/request over %d: %s" % (d["requests"], ", ".join(
            "%s %.1f" % (ph, d[ph] / max(d["requests"], 1))
            for ph in readers.PHASES)))
        say("   class medians, ms: " + ", ".join(
            "%s %.1f" % (c["name"], readers.stat(
                {"stat": "latency_percentile", "q": 50,
                 "classes": [c["name"]]}, ctx) or -1)
            for c in gen.classes))
        if ok:
            knee, fails = rate, 0
        else:
            fails += 1
            if fails >= 2:
                break
        rate *= float(factor)
    say("knee: %s requests/s" % knee)


if __name__ == "__main__":
    sys.exit(main())

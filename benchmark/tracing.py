"""What only a traced run does: sample the routes of the requests that
carry a trace id, ask the launcher (tsd_entry.py) for a profiler trace in
the middle of the window, and reduce it in a child once the daemon has
gone.  The parent process stays off jax throughout."""

from __future__ import annotations

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

from benchmark import daemon
from benchmark.daemon import REPO, BenchFailure, say


class Poller(threading.Thread):
    """Traced runs only: collects the routes of the requests that carry
    a trace id from the daemon's bounded rings while they are there."""

    def __init__(self, port: int):
        super().__init__(daemon=True)
        self.client = daemon.Client(port, timeout=30.0)
        self.stop_flag = threading.Event()
        self.routes: dict[str, dict] = {}
        self.seq = self.client.get_json("/api/diag?since=999999999999")[
            "seq"]

    def poll(self) -> None:
        for done in self.client.get_json("/api/stats/query")["completed"]:
            tid = (done.get("trace") or {}).get("traceId", "")
            if tid.startswith("bench"):
                st = done.get("stats", {})
                self.routes.setdefault(tid, {}).update(
                    hostLane=bool(st.get("hostLane")),
                    batched=bool(st.get("batched")),
                    meshDevices=int(st.get("meshDevices", 0)))
        reply = self.client.get_json("/api/diag?since=%d" % self.seq)
        self.seq = reply["seq"]
        for ev in reply["events"]:
            if ev["kind"] == "plan" and str(ev.get("traceId", "")
                                            ).startswith("bench"):
                self.routes.setdefault(ev["traceId"], {}).update(
                    path=ev["path"],
                    deviceCacheHit=bool(ev.get("deviceCacheHit")))

    def run(self) -> None:
        while not self.stop_flag.wait(0.5):
            try:
                self.poll()
            except Exception as e:      # a sample, not a result
                say("route poll failed: %s" % e)

    def finish(self) -> list[dict]:
        self.stop_flag.set()
        self.join()
        try:
            self.poll()
        finally:
            self.client.close()
        return [r for r in self.routes.values() if "path" in r]


class Tracer(threading.Thread):
    """Traced runs only: asks the launcher for a profiler trace of
    `trace_s` seconds in the middle of the window."""

    def __init__(self, proc, trace_dir: str, t0: float, seconds: float,
                 trace_s: float):
        super().__init__(daemon=True)
        self.proc, self.dir, self.t0 = proc, trace_dir, t0
        self.begin = max((seconds - trace_s) / 2.0, 0.0)
        self.trace_s = min(trace_s, seconds)
        self.window = None

    def _marker(self, name: str, timeout: float) -> float:
        path = os.path.join(self.dir, name)
        give_up = time.monotonic() + timeout
        while time.monotonic() < give_up:
            if os.path.exists(path):
                with open(path) as fh:
                    text = fh.read()
                if text:
                    return float(text)
            time.sleep(0.05)
        raise BenchFailure("no %r marker from the traced daemon" % name)

    def run(self) -> None:
        time.sleep(max(self.t0 + self.begin - time.monotonic(), 0))
        self.proc.send_signal(signal.SIGUSR1)
        started = self._marker("started", 120.0)
        time.sleep(max(started + self.trace_s - time.monotonic(), 0))
        self.proc.send_signal(signal.SIGUSR2)
        stopped_sent = time.monotonic()
        self._marker("stopped", 300.0)
        self.window = (started - self.t0, stopped_sent - self.t0)


def reduce_trace(trace_dir: str, out_dir: str, env: dict) -> dict | None:
    """In a child, after the daemon has gone: the parent stays off jax."""
    daemon.assert_no_jax()
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        return None
    out = os.path.join(out_dir, "trace_summary.json")
    env = dict(env, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "trace_reduce.py"),
         found[-1], out], cwd=REPO, env=env, capture_output=True, text=True,
        timeout=600)
    if proc.returncode != 0:
        raise BenchFailure("trace reduction failed: " + proc.stderr[-800:])
    with open(out) as fh:
        return json.load(fh)


def breakdown(trace: dict) -> dict:
    """The ops that took most device time and the longest idle gaps,
    over all devices (at most 10 each)."""
    ops: dict[str, float] = {}
    gaps = []
    for dev in trace["devices"].values():
        for name, s in dev["top_ops"]:
            name = name[:160]       # the trace names an op by its HLO text
            ops[name] = ops.get(name, 0.0) + s
        gaps += dev["top_gaps"]
    return {"device_ops": [[k, v] for k, v in sorted(
                ops.items(), key=lambda kv: -kv[1])[:10]],
            "idle_gaps": sorted(gaps, key=lambda g: -g[1])[:10]}

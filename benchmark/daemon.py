"""Starting, talking to and stopping the one TSD daemon — the benchmark's
only JAX child.  Client / start / wait_ready / stop are chip_smoke.py's
(PR 21), copied; the parent process never imports jax."""

from __future__ import annotations

import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def say(msg: str) -> None:
    """An earlier line of the run's output (the last line is the result)."""
    print("# " + msg, flush=True)


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print none."""


def assert_no_jax() -> None:
    if "jax" in sys.modules or "opentsdb_tpu.ops" in sys.modules:
        raise BenchFailure("the benchmark's parent process imported jax — "
                           "it would hold the chip its daemon needs")


class Client:
    """One keep-alive HTTP connection to the daemon."""

    def __init__(self, port: int, timeout: float = 1800.0):
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
            raise BenchFailure("GET %s -> %d: %s"
                               % (path, status, body[:400]))
        return json.loads(body)

    def close(self) -> None:
        self.conn.close()


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def start(port: int, out_dir: str, env: dict, tsd_config: dict,
          traced: bool) -> subprocess.Popen:
    """`python -m opentsdb_tpu.tools.tsd_main`, as users start it; a
    traced run starts the benchmark's launcher, which arms jax.profiler
    and then calls the same main() unchanged."""
    assert_no_jax()
    conf = os.path.join(out_dir, "tsd.conf")
    with open(conf, "w") as fh:
        for key, value in tsd_config.items():
            fh.write("%s = %s\n" % (key, json.dumps(value)
                                    if isinstance(value, bool) else value))
    entry = ([os.path.join(REPO, "benchmark", "tsd_entry.py"),
              os.path.join(out_dir, "trace")] if traced
             else ["-m", "opentsdb_tpu.tools.tsd_main"])
    with open(os.path.join(out_dir, "daemon.log"), "wb") as log:
        return subprocess.Popen(
            [sys.executable, *entry, "--port", str(port),
             "--bind", "127.0.0.1", "--config", conf],
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT)


def wait_ready(proc: subprocess.Popen, port: int, timeout: float) -> None:
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise BenchFailure("daemon exited with rc=%d before serving "
                               "(see daemon.log)" % proc.returncode)
        try:
            with socket.create_connection(("127.0.0.1", port), 1.0):
                return
        except OSError:
            time.sleep(0.1)
    raise BenchFailure("daemon did not listen on :%d within %.0fs"
                       % (port, timeout))


def stop(proc: subprocess.Popen) -> int | None:
    """SIGTERM (the graceful path), then wait until it has ended."""
    if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
        try:
            proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
    return proc.returncode


def device_section(client: Client) -> dict:
    # since=<huge>: the full-ring view (which carries `device`) without
    # shipping the ring itself
    return client.get_json("/api/diag?since=999999999999")["device"]


_SAMPLE = re.compile(r"^(tsd_[a-zA-Z0-9_]+)(?:\{([^}]*)\})? (\S+)$", re.M)


def counters(client: Client) -> dict[str, float]:
    """Every sample of /api/stats/prometheus as {"name{k=v,...}": value}
    (the `host` label dropped, labels sorted)."""
    status, body = client.request("GET", "/api/stats/prometheus")
    if status != 200:
        raise BenchFailure("/api/stats/prometheus -> %d" % status)
    out = {}
    for name, labels, value in _SAMPLE.findall(body.decode()):
        pairs = sorted(kv for kv in re.findall(r'(\w+)="([^"]*)"',
                                               labels or "")
                       if kv[0] != "host")
        key = name + ("{%s}" % ",".join("%s=%s" % kv for kv in pairs)
                      if pairs else "")
        try:
            out[key] = float(value)
        except ValueError:
            pass
    return out


def counter_sum(snapshot: dict[str, float], name: str) -> float:
    """Sum of a counter over its label sets: `name` alone, or
    `name{k=v}` to keep the samples that carry that label."""
    base, _, want = name.partition("{")
    want = want.rstrip("}")
    return sum(v for k, v in snapshot.items()
               if (k == base or k.startswith(base + "{"))
               and (not want or want in k))


def compile_total(snapshot: dict[str, float]) -> float:
    return counter_sum(snapshot, "tsd_jax_compiles_total")

"""Starting, talking to and stopping the one TSD daemon — the benchmark's
only JAX child.  Client / start / wait_ready are chip_smoke.py's (PR 21),
copied; the parent process never imports jax.

Whatever ends the runner, the daemon does not outlive it: it is started
in a session of its own with a parent-death signal, and stop() ends the
whole session's process group and then sees the port refuse."""

from __future__ import annotations

import ctypes
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


PR_SET_PDEATHSIG = 1        # <linux/prctl.h>


def die_with_parent(parent: int, libc) -> None:
    """A preexec_fn: SIGKILL for this process when the thread that
    created it ends, however it ends — a SIGKILL of the runner included,
    which no handler sees.  Not SIGTERM: the graceful path is stop()'s to
    take while the runner lives; a daemon whose runner is gone has no
    result to protect, and inside an XLA compile of minutes its graceful
    path outlasts any patience (the drain gives up after 35 s, the
    interpreter then joins the compiling thread), holding the chip and
    the machine for the runs that follow.  prctl(2) is Linux's, the only
    platform a cell runs on; the kernel counts the creating THREAD as the
    parent, so the daemon is started from the runner's main thread (the
    writers, which a thread that ends early starts, look at their
    parent's pid instead: loadgen._end_with).  `parent` is the creator's
    pid, to close the race with a creator that died before the call;
    `libc` was loaded before the fork, so that the child does nothing
    between fork and exec but call into it."""
    if libc.prctl(PR_SET_PDEATHSIG, signal.SIGKILL, 0, 0, 0) != 0:
        raise OSError(ctypes.get_errno(), "prctl(PR_SET_PDEATHSIG)")
    if os.getppid() != parent:
        os.kill(os.getpid(), signal.SIGKILL)


def start(port: int, out_dir: str, env: dict, tsd_config: dict,
          traced: bool) -> subprocess.Popen:
    """`python -m opentsdb_tpu.tools.tsd_main`, as users start it; a
    traced run starts the benchmark's launcher, which arms jax.profiler
    and then calls the same main() unchanged.  The daemon leads a session
    (and so a process group) of its own, which stop() ends as a whole,
    and carries the parent-death signal across its exec."""
    assert_no_jax()
    runner, libc = os.getpid(), ctypes.CDLL(None, use_errno=True)
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
            cwd=REPO, env=env, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
            preexec_fn=lambda: die_with_parent(runner, libc))


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


def _signal_group(proc: subprocess.Popen, signum: int) -> None:
    """The daemon leads its own process group (start_new_session)."""
    try:
        os.killpg(proc.pid, signum)
    except (ProcessLookupError, PermissionError):
        pass                # nothing is left of the group


def stop(proc: subprocess.Popen, port: int,
         patience: float = 120.0) -> int | None:
    """SIGTERM to the daemon's group (the graceful path), `patience`
    seconds for it to end, SIGKILL to whatever is left of the group, and
    then the port has to refuse a connection: nothing the run started
    is left to serve a later one.  Returns the daemon's exit code."""
    if proc.poll() is None:
        _signal_group(proc, signal.SIGTERM)
        try:
            proc.wait(timeout=patience)
        except subprocess.TimeoutExpired:
            pass
    _signal_group(proc, signal.SIGKILL)
    proc.wait()
    give_up = time.monotonic() + 10.0
    while True:
        try:
            with socket.create_connection(("127.0.0.1", port), 1.0):
                pass
        except OSError:
            return proc.returncode
        if time.monotonic() > give_up:
            raise BenchFailure("port %d still answers after the daemon's "
                               "group was killed" % port)
        time.sleep(0.1)


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

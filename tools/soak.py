"""Live-server soak: concurrent HTTP + telnet writers vs readers.

Spins the real asyncio daemon and hammers it for --seconds with mixed
load, then asserts ZERO write loss (every acknowledged point is in the
store) and zero errors.  The reference's scale claim is qualitative
(README:12-15, "tens of thousands of hosts ... every few seconds");
this is the repeatable harness for ours:

    python tools/soak.py [--seconds 90] [--port 14247]
"""

import argparse
import os, json, threading, time, asyncio, socket, urllib.request, urllib.error

_ap = argparse.ArgumentParser()
_ap.add_argument("--seconds", type=int, default=90)
_ap.add_argument("--port", type=int, default=14247)
_args = _ap.parse_args()
# CPU by design: a concurrency soak of the serving layer, not a device run
os.environ["JAX_PLATFORMS"] = "cpu"
import jax
jax.config.update("jax_platforms", "cpu")

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.utils.config import Config
from opentsdb_tpu.tsd.server import TSDServer

tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
tsdb.start_maintenance()
srv = TSDServer(tsdb, port=_args.port, bind="127.0.0.1")
threading.Thread(target=lambda: asyncio.run(srv.serve_forever()),
                 daemon=True).start()
time.sleep(1.2)
B = "http://127.0.0.1:%d" % _args.port
BASE = 1356998400
stop = time.time() + _args.seconds
errors = []
sent_http = [0]
sent_tel = [0]

def http_writer(tid):
    i = 0
    while time.time() < stop:
        i += 1
        body = json.dumps([
            {"metric": "soak.h", "timestamp": BASE + (i * 50 + k),
             "value": k, "tags": {"host": "w%d" % tid}}
            for k in range(50)]).encode()
        r = urllib.request.Request(B + "/api/put", data=body,
                                   headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(r, timeout=30) as resp:
                assert resp.status == 204
            sent_http[0] += 50
        except Exception as e:
            errors.append(("http_put", e)); return

def telnet_writer(tid):
    try:
        s = socket.create_connection(("127.0.0.1", _args.port), timeout=30)
        i = 0
        while time.time() < stop:
            i += 1
            lines = b"".join(
                b"put soak.t %d %d host=t%d\n" % (BASE + i * 50 + k, k, tid)
                for k in range(50))
            s.sendall(lines)
            sent_tel[0] += 50
            time.sleep(0.002)
        s.close()
    except Exception as e:
        errors.append(("telnet_put", e))

def reader():
    while time.time() < stop:
        try:
            with urllib.request.urlopen(
                    B + "/api/query?start=%d&m=sum:1m-count:soak.h%%7Bhost=*%%7D"
                    % BASE, timeout=180) as resp:
                json.loads(resp.read())
        except urllib.error.HTTPError as e:
            if e.code != 400:   # no data yet is fine early
                errors.append(("query", e.code)); return
        except Exception as e:
            errors.append(("query", e)); return
        time.sleep(0.05)

threads = ([threading.Thread(target=http_writer, args=(t,)) for t in range(3)]
           + [threading.Thread(target=telnet_writer, args=(t,)) for t in range(2)]
           + [threading.Thread(target=reader) for _ in range(2)])
for t in threads: t.start()
for t in threads: t.join(150)
time.sleep(2)
stored_h = sum(len(s) for s in tsdb.store.series_for_metric(
    tsdb.metrics.get_id("soak.h")))
stored_t = sum(len(s) for s in tsdb.store.series_for_metric(
    tsdb.metrics.get_id("soak.t")))
print("errors:", errors[:3] if errors else "none")
print("http sent=%d stored=%d; telnet sent=%d stored=%d"
      % (sent_http[0], stored_h, sent_tel[0], stored_t))
stats = tsdb.collect_stats()
print("cache:", {k.split(".")[-1]: v for k, v in stats.items()
                 if "device_cache" in k})
assert not errors
assert stored_h == sent_http[0]
# telnet is fire-and-forget: allow in-flight tail at stop time
assert stored_t >= sent_tel[0] * 0.98, (stored_t, sent_tel[0])
print("SOAK OK")

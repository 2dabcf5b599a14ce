"""Live-socket integration tests: real asyncio server, HTTP + telnet on one
port (the PipelineFactory first-byte sniff in action)."""

import asyncio
import json
import socket
import threading
import time

import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.tsd.server import TSDServer
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400


@pytest.fixture(scope="module")
def server():
    """A TSDServer running in a daemon thread on an ephemeral port."""
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
    srv = TSDServer(tsdb, port=0, bind="127.0.0.1", worker_threads=2)
    started = threading.Event()
    holder = {}

    def run():
        async def main():
            await srv.start()
            holder["port"] = srv._server.sockets[0].getsockname()[1]
            holder["loop"] = asyncio.get_running_loop()
            started.set()
            await srv.serve_forever()
        asyncio.run(main())

    t = threading.Thread(target=run, daemon=True)
    t.start()
    assert started.wait(10)
    srv.test_port = holder["port"]
    yield srv
    holder["loop"].call_soon_threadsafe(srv._shutdown_event.set)
    t.join(5)


def telnet(server, *lines, read_reply=True):
    with socket.create_connection(("127.0.0.1", server.test_port),
                                  timeout=10) as s:
        s.sendall(("".join(l + "\n" for l in lines)).encode())
        s.settimeout(1.0)
        out = b""
        if read_reply:
            try:
                while True:
                    chunk = s.recv(4096)
                    if not chunk:
                        break
                    out += chunk
            except socket.timeout:
                pass
        return out.decode()


def http_request(server, method, path, body=None, headers=None):
    import http.client
    conn = http.client.HTTPConnection("127.0.0.1", server.test_port,
                                      timeout=30)
    try:
        payload = json.dumps(body) if body is not None else None
        conn.request(method, path, body=payload,
                     headers=headers or {"Content-Type": "application/json"})
        resp = conn.getresponse()
        data = resp.read()
        return resp.status, data
    finally:
        conn.close()


class TestIntegration:
    def test_http_version(self, server):
        status, data = http_request(server, "GET", "/api/version")
        assert status == 200
        assert json.loads(data)["version"] == "3.0.0-tpu"

    def test_telnet_version(self, server):
        out = telnet(server, "version")
        assert "opentsdb_tpu" in out

    def test_telnet_put_then_http_query(self, server):
        out = telnet(server, *[
            "put it.metric %d %d host=a" % (BASE + i * 10, i)
            for i in range(5)])
        assert out == ""  # silent success
        deadline = time.time() + 5
        while time.time() < deadline:
            status, data = http_request(
                server, "GET",
                "/api/query?start=%d&end=%d&m=sum:it.metric"
                % (BASE, BASE + 100))
            if status == 200:
                break
            time.sleep(0.1)
        assert status == 200
        dps = json.loads(data)[0]["dps"]
        assert dps["%d" % (BASE + 40)] == 4

    def test_http_put(self, server):
        status, _ = http_request(server, "POST", "/api/put", {
            "metric": "http.metric", "timestamp": BASE, "value": 7,
            "tags": {"host": "x"}})
        assert status == 204
        status, data = http_request(
            server, "GET",
            "/api/query?start=%d&end=%d&m=sum:http.metric"
            % (BASE - 10, BASE + 10))
        assert json.loads(data)[0]["dps"]["%d" % BASE] == 7

    def test_http_404(self, server):
        status, data = http_request(server, "GET", "/api/bogus")
        assert status == 404

    def test_keep_alive_two_requests(self, server):
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", server.test_port,
                                          timeout=30)
        try:
            conn.request("GET", "/api/version")
            r1 = conn.getresponse()
            r1.read()
            conn.request("GET", "/api/aggregators")
            r2 = conn.getresponse()
            assert r1.status == 200 and r2.status == 200
            assert b"sum" in r2.read()
        finally:
            conn.close()

    def test_telnet_stats_and_help(self, server):
        out = telnet(server, "help")
        assert "available commands" in out
        out = telnet(server, "stats")
        assert "tsd.connectionmgr.connections" in out

    def test_telnet_bad_put_reports(self, server):
        out = telnet(server, "put only.metric")
        assert "put:" in out

    def test_telnet_pipelined_batch_with_errors(self, server):
        # 200 pipelined put lines with two bad ones: the server batches
        # buffered lines into one native dispatch; error replies keep
        # line order and the clean points all land
        lines = ["put pipe.m %d %d host=h%d" % (BASE + i, i, i % 4)
                 for i in range(200)]
        lines[50] = "put pipe.m notanum 1 host=x"
        lines[150] = "put pipe.m %d 1 badtag" % (BASE + 150)
        out = telnet(server, *lines)
        assert "invalid literal for int() with base 10: 'notanum'" in out
        assert "invalid tag: badtag" in out
        assert out.index("invalid literal") < out.index("invalid tag")
        deadline = time.time() + 5
        total = -1.0
        while time.time() < deadline:
            status, data = http_request(
                server, "GET",
                "/api/query?start=%d&end=%d&m=sum:1h-count:pipe.m"
                % (BASE - 10, BASE + 300))
            if status == 200:
                res = json.loads(data)
                if res:            # empty until the first batch lands
                    total = sum(res[0]["dps"].values())
                    if total == 198:   # poll covers the full assertion: a
                        break          # later batch may still be landing
            time.sleep(0.1)
        assert total == 198


class TestMalformedHttp:
    def test_bad_request_line_gets_400(self, server):
        """A malformed HTTP head answers 400 before close (ADVICE r1),
        not a bare socket reset."""
        with socket.create_connection(("127.0.0.1", server.test_port),
                                      timeout=10) as s:
            s.sendall(b"GET /incomplete-request-line\r\n\r\n")
            s.settimeout(3.0)
            out = b""
            try:
                while True:
                    chunk = s.recv(4096)
                    if not chunk:
                        break
                    out += chunk
            except socket.timeout:
                pass
        assert out.startswith(b"HTTP/1.1 400")
        assert b"Malformed request line" in out


class TestShutdownWithIdleClients:
    def test_idle_keepalive_connection_does_not_hold_shutdown(self):
        """A supervisor's stop must not wait out a dashboard's idle
        keep-alive socket: since Python 3.12 Server.wait_closed() waits
        for every connection handler, so stop() closes what the drain
        left open (chip_smoke.py's failing path found a 120 s hang)."""
        import http.client

        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
        srv = TSDServer(tsdb, port=0, bind="127.0.0.1", worker_threads=2)
        took = {}

        async def main():
            await srv.start()
            port = srv._server.sockets[0].getsockname()[1]
            loop = asyncio.get_running_loop()

            def client():
                conn = http.client.HTTPConnection("127.0.0.1", port,
                                                  timeout=10)
                conn.request("GET", "/api/version")
                resp = conn.getresponse()
                resp.read()
                return conn, resp.status
            conn, status = await loop.run_in_executor(None, client)
            assert status == 200 and len(srv._open_writers) == 1
            t0 = time.monotonic()
            await srv.stop()                # the connection is still open
            took["s"] = time.monotonic() - t0
            conn.close()
        asyncio.run(main())
        assert took["s"] < 3.0
        assert not srv._open_writers

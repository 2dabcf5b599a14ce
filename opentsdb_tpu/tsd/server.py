"""Asyncio server: one port, telnet line protocol + HTTP/1.1, sniffed from
the first bytes of each connection.

Reference behavior: /root/reference/src/tsd/PipelineFactory.java (:44) —
ConnectionManager -> DetectHttpOrRpc (:134, first-byte sniff: ASCII letters
'A'-'Z' mean an HTTP verb, anything else is the telnet line protocol) ->
framing -> timeout -> RpcHandler — and ConnectionManager.java (:37-41
connection limit).

Handlers run on a bounded thread pool (the "OpenTSDB Responder" analog,
RpcResponder.java) so jit-compiled query work never blocks the accept loop.
"""

from __future__ import annotations

import asyncio
import functools
import logging
import threading
import time
from concurrent.futures import ThreadPoolExecutor

from opentsdb_tpu.obs import latattr
from opentsdb_tpu.tsd.http import (
    BadRequestError, HttpQuery, HttpResponse, parse_http_head)
from opentsdb_tpu.tsd.rpc_manager import RpcManager

LOG = logging.getLogger("tsd.server")

MAX_REQUEST_BYTES = 64 * 1024 * 1024   # HttpRequestDecoder aggregator cap
MAX_TELNET_LINE = 1024 * 1024
# After the graceful drain window (tsd.network.drain_timeout_ms)
# expires, force-cancelled handlers get this long to observe their
# cancellation token and unwind before TSDB teardown proceeds anyway.
POST_CANCEL_GRACE_S = 5.0

# Telnet put batching peeks at asyncio.StreamReader's buffered bytes to
# decide whether another complete line can be consumed WITHOUT awaiting
# more input.  There is no public API for this; `_buffer` (a bytearray)
# has been the implementation since CPython 3.4.  The peek is isolated
# here so a future rename degrades loudly (one warning, correct
# unbatched behavior) instead of silently costing the 14x batching win.
_warned_no_buffer = False


def _has_buffered_line(reader: asyncio.StreamReader) -> bool:
    """True when a complete line is already in the reader's buffer."""
    buf = getattr(reader, "_buffer", None)
    if buf is None:
        global _warned_no_buffer
        if not _warned_no_buffer:
            _warned_no_buffer = True
            LOG.warning(
                "asyncio.StreamReader._buffer is gone in this CPython; "
                "telnet put batching disabled (correct but slower)")
        return False
    return b"\n" in buf


class ConnectionRefused(Exception):
    pass


class TelnetConn:
    """Handler-facing handle on one telnet connection."""

    def __init__(self, writer: asyncio.StreamWriter):
        self.writer = writer
        self.close_after_write = False


class TSDServer:
    """The daemon: TSDB + RpcManager + asyncio socket server."""

    def __init__(self, tsdb, port: int = 4242, bind: str = "0.0.0.0",
                 worker_threads: int = 8):
        self.tsdb = tsdb
        self.port = port
        self.bind = bind
        self.rpc_manager = RpcManager(tsdb, server=self,
                                      shutdown_cb=self.request_shutdown)
        self.connections_established = 0  # guarded-by: _conn_lock
        self.connections_rejected = 0  # guarded-by: _conn_lock
        self.exceptions_caught = 0
        self.telnet_rpcs = 0
        self.http_rpcs = 0
        # RPCs dispatched but whose reply has not hit the socket yet.
        # Touched only on the event-loop thread (no lock); stop() waits
        # on it so a drained handler's response still gets delivered
        # before the TSDB (and then the loop) tears down.
        self._inflight_rpcs = 0
        self._open_connections = 0  # guarded-by: _conn_lock
        self._conn_lock = threading.Lock()
        self.max_connections = tsdb.config.get_int(
            "tsd.core.connections.limit")
        self.idle_timeout = tsdb.config.get_int(
            "tsd.network.keep_alive_timeout") if tsdb.config.has_property(
            "tsd.network.keep_alive_timeout") else 300
        # graceful-shutdown budget for in-flight responder work:
        # generous enough for the longest legitimate request, bounded
        # so one wedged handler can't hold the daemon past its
        # supervisor's patience — at expiry every in-flight request's
        # cancellation token is force-flipped (stop() below)
        self.drain_grace_s = max(
            tsdb.config.get_int("tsd.network.drain_timeout_ms"), 0) / 1e3
        # cancellation handles of in-flight HTTP requests.  Touched
        # only on the event-loop thread, like _inflight_rpcs; stop()
        # (also on the loop) force-cancels them at drain expiry.
        self._active_handles: set = set()
        # writers of open connections; loop-thread only, like the above.
        # stop() closes whatever is still open once the drain is done.
        self._open_writers: set = set()
        self._executor = ThreadPoolExecutor(
            max_workers=worker_threads, thread_name_prefix="tsd-responder")
        self._server: asyncio.AbstractServer | None = None
        self._shutdown_event: asyncio.Event | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        # Process-global installs come LAST: everything fallible
        # (RpcManager construction, config reads) has already run, so a
        # failed construction never arms global state with no instance
        # left to stop().  _log_buffer_installed is this instance's
        # share of the refcount — a second stop() (owner finally +
        # shutdown-event path both reach stop) must not decrement on
        # behalf of another still-running server.
        self._compile_counting = tsdb.config.get_bool("tsd.trace.enable")
        self._log_buffer_installed = False
        # staged arming with ONE rollback path: a failure part-way in
        # must release exactly what already installed, newest first
        undo: list = []
        try:
            from opentsdb_tpu.tsd.admin_rpcs import (install_log_buffer,
                                                     uninstall_log_buffer)
            # global-install: uninstall_log_buffer paired-with: stop
            install_log_buffer()
            self._log_buffer_installed = True
            undo.append(uninstall_log_buffer)
            if self._compile_counting:
                # per-kernel XLA compile counters (tsd.jax.compiles at
                # /api/stats/prometheus) — the same capture tsdbsan uses
                from opentsdb_tpu.obs import jaxprof
                # global-install: stop_compile_counting paired-with: stop
                jaxprof.start_compile_counting()
                undo.append(jaxprof.stop_compile_counting)
            if tsdb.flightrec is not None:
                # steady-state recompile events into the flight
                # recorder, off the SAME shared capture — armed
                # REGARDLESS of tsd.trace.enable (the recorder is the
                # always-on black box; tracing only governs the span
                # surfaces).  The recorder unsubscribes in its own
                # shutdown (tsdb.shutdown, reached from stop()).
                tsdb.flightrec.start()
        except BaseException:
            self._compile_counting = False
            self._log_buffer_installed = False
            for release in reversed(undo):
                release()
            raise

    # -- lifecycle --

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._shutdown_event = asyncio.Event()
        self._server = await asyncio.start_server(
            self._handle_connection, self.bind, self.port,
            limit=MAX_TELNET_LINE)
        repl = getattr(self.tsdb, "replication", None)
        if repl is not None:
            # rejoin protocol (tsd/replication.py): catch up from
            # peers' WAL tails BEFORE re-accepting ownership, then keep
            # the pull cadence running.  Off the event loop — catch-up
            # is blocking HTTP against peers.
            await self._loop.run_in_executor(None, repl.catch_up)
            repl.start_puller()
        LOG.info("Ready to serve on %s:%d", self.bind, self.port)

    async def serve_forever(self) -> None:
        if self._server is None:
            await self.start()
        assert self._shutdown_event is not None
        await self._shutdown_event.wait()
        await self.stop()

    async def stop(self) -> None:
        server, self._server = self._server, None
        if server is not None:
            # stop accepting; connections already open keep being served
            # through the drain below and are closed after it
            server.close()
        # Drain in-flight responder work BEFORE tearing down the TSDB:
        # handlers may still be mid-write (a put landing, a query
        # serializing), and shutdown(wait=False) + tsdb.shutdown() would
        # snapshot/close the WAL underneath them.  cancel_futures drops
        # QUEUED requests (accepted but unstarted — shutdown owes them
        # nothing) while running ones finish; the drain runs in the
        # loop's default executor so the event loop stays live and the
        # draining handlers can still deliver their responses.  The wait
        # is bounded: one wedged handler must not hold the daemon
        # hostage past the grace period (the supervisor's SIGKILL would
        # land us in exactly the mid-write teardown this drain avoids).
        loop = asyncio.get_running_loop()
        try:
            drain = loop.run_in_executor(
                None, functools.partial(self._executor.shutdown, wait=True,
                                        cancel_futures=True))
            try:
                await asyncio.wait_for(asyncio.shield(drain),
                                       timeout=self.drain_grace_s)
            except asyncio.TimeoutError:
                # the drain is OUT of patience: force-flip every
                # in-flight request's cancellation token so cooperative
                # handlers (budget.check_deadline sites, admission
                # waits) unwind now, then give them a short bounded
                # window before tearing the TSDB down regardless
                from opentsdb_tpu.tsd import admission
                handles = list(self._active_handles)
                LOG.warning(
                    "responder drain exceeded %.1fs; force-cancelling "
                    "%d in-flight request(s)", self.drain_grace_s,
                    len(handles))
                for handle in handles:
                    if handle.cancel("server drain timeout"):
                        admission.count_cancelled("drain_timeout")
                try:
                    await asyncio.wait_for(asyncio.shield(drain),
                                           timeout=POST_CANCEL_GRACE_S)
                except asyncio.TimeoutError:
                    LOG.warning(
                        "responder drain still wedged after force-"
                        "cancel; proceeding with TSDB teardown (a "
                        "handler ignores its cancellation token)")
            # The drain guarantees the WORK finished; the handler
            # coroutines still need loop time to write their replies.
            # Yield until the last dispatched reply hits its socket
            # (bounded — a dead client can't block shutdown).
            deadline = loop.time() + 5.0
            while self._inflight_rpcs and loop.time() < deadline:
                await asyncio.sleep(0.02)
            # What is left are idle keep-alive connections.  Python 3.12's
            # Server.wait_closed() waits for every connection handler to
            # return, so an idle client would otherwise hold the daemon's
            # SIGTERM hostage until its idle timeout.
            for writer in list(self._open_writers):
                writer.close()
            if server is not None:
                try:
                    await asyncio.wait_for(server.wait_closed(), timeout=5.0)
                except asyncio.TimeoutError:
                    LOG.warning("%d connection handler(s) still open at "
                                "shutdown", len(self._open_writers))
        finally:
            # A cancelled drain must still release the process-global
            # installs — a CancelledError here would otherwise pin the
            # /logs handler on the root logger forever.
            if self._compile_counting:
                from opentsdb_tpu.obs import jaxprof
                jaxprof.stop_compile_counting()
                self._compile_counting = False
            if self._log_buffer_installed:
                self._log_buffer_installed = False
                from opentsdb_tpu.tsd.admin_rpcs import uninstall_log_buffer
                uninstall_log_buffer()
            self.tsdb.shutdown()
            LOG.info("Server shut down")

    def request_shutdown(self) -> None:
        """Thread-safe shutdown trigger (diediedie).

        Runs on a responder worker thread, so the server loop captured in
        start() is the only safe way back onto the event loop.
        """
        if self._shutdown_event is not None and self._loop is not None:
            self._loop.call_soon_threadsafe(self._shutdown_event.set)

    # -- connection handling --

    async def _handle_connection(self, reader: asyncio.StreamReader,
                                 writer: asyncio.StreamWriter) -> None:
        with self._conn_lock:
            if self.max_connections and \
                    self._open_connections >= self.max_connections:
                self.connections_rejected += 1
                writer.close()
                return
            self._open_connections += 1
            self.connections_established += 1
        self._open_writers.add(writer)
        peer = writer.get_extra_info("peername")
        remote = "%s:%s" % (peer[0], peer[1]) if peer else "unknown"
        try:
            # First-byte sniff (DetectHttpOrRpc :134): HTTP verbs start with
            # an uppercase ASCII letter; telnet commands are lowercase.
            first = await asyncio.wait_for(reader.read(1),
                                           timeout=self.idle_timeout)
            if not first:
                return
            if b"A" <= first <= b"Z":
                await self._serve_http(first, reader, writer, remote)
            else:
                await self._serve_telnet(first, reader, writer, remote)
        except (asyncio.TimeoutError, asyncio.IncompleteReadError,
                ConnectionResetError, BrokenPipeError):
            pass
        except Exception:
            self.exceptions_caught += 1
            LOG.exception("Unhandled connection error from %s", remote)
        finally:
            self._open_writers.discard(writer)
            with self._conn_lock:
                self._open_connections -= 1
            try:
                writer.close()
                await writer.wait_closed()
            except Exception:
                # best-effort close of an already-failed/finished
                # connection; nothing to serve and nothing to account
                pass  # tsdblint: disable=except-swallow

    # -- telnet path --

    async def _serve_telnet(self, first: bytes, reader: asyncio.StreamReader,
                            writer: asyncio.StreamWriter,
                            remote: str) -> None:
        conn = TelnetConn(writer)
        conn.auth_state = None
        buffer = first
        pending: bytes | None = None
        loop = asyncio.get_running_loop()
        while True:
            if pending is not None:
                line = pending
                pending = None
            else:
                try:
                    line = await asyncio.wait_for(reader.readline(),
                                                  timeout=self.idle_timeout)
                except asyncio.TimeoutError:
                    return
                except ValueError:
                    # StreamReader limit (MAX_TELNET_LINE) exceeded.
                    writer.write(b"error: line too long\n")
                    await writer.drain()
                    return
            data = buffer + line
            buffer = b""
            if len(data) > MAX_TELNET_LINE:
                writer.write(b"error: line too long\n")
                return
            if not line and not data:
                return
            text = data.decode("utf-8", "replace").strip("\r\n")
            if not text:
                if not line:
                    return
                continue
            self.telnet_rpcs += 1
            auth = self.tsdb.authentication
            if auth is not None and not auth.is_ready(self.tsdb, conn):
                # First-message auth (AuthenticationChannelHandler :87-124):
                # the opening command must authenticate the channel.
                from opentsdb_tpu.auth import AuthStatus
                try:
                    state = auth.authenticate_telnet(conn, text.split())
                except Exception:
                    LOG.exception("Authentication plugin failed on telnet "
                                  "command from %s; failing closed", remote)
                    state = None
                if state is not None and state.status == AuthStatus.SUCCESS:
                    conn.auth_state = state
                    writer.write(b"AUTH_SUCCESS\r\n")
                else:
                    # Channel stays open so the caller can retry
                    # (AuthenticationChannelHandler doc).
                    writer.write(b"AUTH_FAIL\r\n")
                await writer.drain()
                continue
            self._inflight_rpcs += 1
            try:
                if auth is None and data.split(None, 1)[:1] == [b"put"]:
                    # Batch consecutive already-buffered put lines into
                    # ONE executor dispatch (the native columnar
                    # ingest): a pipelined writer otherwise pays a
                    # Python parse AND a thread-pool hop PER LINE.  Only
                    # complete lines already in the reader's buffer join
                    # — this never waits for more input, so single-line
                    # latency is unchanged.
                    block = [data]
                    too_long = False
                    while len(block) < 4096 and _has_buffered_line(reader):
                        try:
                            nxt = await reader.readline()
                        except ValueError:
                            # buffered line beyond MAX_TELNET_LINE: land
                            # the lines collected so far, THEN reply the
                            # same error the unpipelined path would
                            too_long = True
                            break
                        if not nxt:
                            break
                        if (len(nxt) > MAX_TELNET_LINE
                                or nxt.split(None, 1)[:1] != [b"put"]):
                            pending = nxt     # main loop handles it next
                            break
                        block.append(nxt)
                    self.telnet_rpcs += len(block) - 1
                    reply = await loop.run_in_executor(
                        self._executor,
                        self.rpc_manager.handle_telnet_batch,
                        conn, b"".join(block))
                    if too_long:
                        if reply:
                            writer.write(reply.encode())
                        writer.write(b"error: line too long\n")
                        await writer.drain()
                        return
                else:
                    reply = await loop.run_in_executor(
                        self._executor, self.rpc_manager.handle_telnet,
                        conn, text)
                if reply:
                    writer.write(reply.encode())
                    await writer.drain()
            finally:
                self._inflight_rpcs -= 1
            if conn.close_after_write or not line:
                return

    # -- HTTP path --

    async def _serve_http(self, first: bytes, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter,
                          remote: str) -> None:
        loop = asyncio.get_running_loop()
        buffer = first
        while True:
            try:
                head = parse_http_head(buffer)
                while head is None:
                    chunk = await asyncio.wait_for(reader.read(65536),
                                                   timeout=self.idle_timeout)
                    if not chunk:
                        return
                    buffer += chunk
                    if len(buffer) > MAX_REQUEST_BYTES:
                        writer.write(HttpResponse(status=413).to_bytes(False))
                        return
                    head = parse_http_head(buffer)
            except BadRequestError as e:
                # Malformed request line/headers answer 400 before closing
                # instead of a bare socket reset (ADVICE round-1).
                writer.write(HttpResponse(
                    status=e.status,
                    body=e.message.encode()).to_bytes(False))
                await writer.drain()
                return
            request, offset = head
            length = int(request.headers.get("content-length", "0") or 0)
            if length > MAX_REQUEST_BYTES:
                writer.write(HttpResponse(status=413).to_bytes(False))
                return
            body = buffer[offset:offset + length]
            if len(body) < length:
                # One exact read instead of quadratic += accumulation.
                try:
                    body += await asyncio.wait_for(
                        reader.readexactly(length - len(body)),
                        timeout=self.idle_timeout)
                except asyncio.IncompleteReadError:
                    return
            request.body = body[:length]
            # Bytes past the body begin the next pipelined request: they sit
            # in `buffer` when the whole body arrived up front, or in `body`
            # when the completion loop over-read.  Exactly one is non-empty.
            buffer = buffer[offset + length:] + body[length:]

            self.http_rpcs += 1
            self._inflight_rpcs += 1
            from opentsdb_tpu.tsd import admission
            # cancellation lever: created HERE (the loop owns disconnect
            # detection), bound to the request's Deadline by
            # rpc_manager.handle_http on the responder thread
            handle = admission.CancellationHandle()
            request.cancel_handle = handle
            self._active_handles.add(handle)
            watcher = None
            # the edges outside the handler (obs/latattr.py): queued now
            edges = request.edges = latattr.Edges()
            try:
                fut = loop.run_in_executor(
                    self._executor, self.rpc_manager.handle_http, request,
                    remote)
                if not buffer:
                    # disconnect watcher: while the handler runs, a read
                    # on the (otherwise idle) connection detects the
                    # client going away — EOF flips the cancellation
                    # token so the query releases its permit without
                    # dispatching.  Skipped when pipelined bytes are
                    # already buffered (the client is clearly alive and
                    # the read would race the next request).
                    watcher = asyncio.ensure_future(reader.read(65536))
                    done, _ = await asyncio.wait(
                        {fut, watcher},
                        return_when=asyncio.FIRST_COMPLETED)
                    if watcher.done():
                        try:
                            chunk = watcher.result()
                        except (ConnectionError, OSError):
                            chunk = b""
                        watcher = None
                        if not chunk:
                            if not fut.done() and handle.cancel(
                                    "client disconnected"):
                                admission.count_cancelled(
                                    "client_disconnect")
                        else:
                            # the next pipelined request arrived while
                            # this one executed: keep its bytes
                            buffer = chunk
                query = await fut
                resumed = time.perf_counter()
                ann = latattr.open_annotation("tsd.phase")
                try:
                    keep_alive = (request.version != "HTTP/1.0"
                                  and (request.header("connection")
                                       or "").lower() != "close")
                    response = query.response or HttpResponse(status=500)
                    writer.write(response.to_bytes(keep_alive))
                    await writer.drain()
                finally:
                    edges.written(resumed, ann)
            finally:
                if watcher is not None:
                    buffer = await self._drain_watcher(watcher, buffer)
                self._active_handles.discard(handle)
                self._inflight_rpcs -= 1
            if not keep_alive:
                return
            if not buffer:
                try:
                    chunk = await asyncio.wait_for(
                        reader.read(65536), timeout=self.idle_timeout)
                except asyncio.TimeoutError:
                    return
                if not chunk:
                    return
                buffer = chunk

    @staticmethod
    async def _drain_watcher(watcher, buffer: bytes) -> bytes:
        """Retire a still-pending disconnect watcher without losing
        bytes: a read that completed in the race window between the
        handler finishing and this cancel holds the next pipelined
        request — prepend-order is preserved because the watcher only
        ever starts when `buffer` was empty."""
        if not watcher.done():
            watcher.cancel()
        try:
            chunk = await watcher
        except asyncio.CancelledError:
            return buffer
        except (ConnectionError, OSError):
            # the connection died under the watcher; the main loop's
            # own next read/write surfaces it
            return buffer
        return buffer + chunk if chunk else buffer

    # -- stats (ConnectionManager.collectStats :89) --

    def collect_stats(self, collector) -> None:
        collector.record("connectionmgr.connections",
                         self.connections_established, "type=total")
        with self._conn_lock:
            collector.record("connectionmgr.connections",
                             self._open_connections, "type=open")
        collector.record("connectionmgr.connections",
                         self.connections_rejected, "type=rejected")
        collector.record("connectionmgr.exceptions", self.exceptions_caught)
        collector.record("rpc.received", self.telnet_rpcs, "type=telnet")
        collector.record("rpc.received", self.http_rpcs, "type=http")

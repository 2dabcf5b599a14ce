"""Daemon entry point: `python -m opentsdb_tpu.tools.tsd_main`.

Reference behavior: /root/reference/src/tools/TSDMain.java (:71) — parse
flags + config, build the TSDB, load plugins, bind the server, serve until
shutdown.
"""

from __future__ import annotations

import argparse
import asyncio
import logging
import sys


def build_arg_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="tsdb tsd", description="Start the TSD (time series daemon)")
    p.add_argument("--port", type=int, default=None,
                   help="TCP port to listen on (tsd.network.port)")
    p.add_argument("--bind", default=None,
                   help="Address to bind to (tsd.network.bind)")
    p.add_argument("--config", default=None,
                   help="Path to a configuration file")
    p.add_argument("--mode", default=None, choices=["rw", "ro", "wo"],
                   help="Operation mode (tsd.mode)")
    p.add_argument("--auto-metric", action="store_true", default=None,
                   help="Automatically add metrics (tsd.core.auto_create_metrics)")
    p.add_argument("--staticroot", default=None,
                   help="Web root for static files (tsd.http.staticroot)")
    p.add_argument("--cachedir", default=None,
                   help="Directory for temporary files (tsd.http.cachedir)")
    p.add_argument("--worker-threads", type=int, default=8,
                   help="Responder thread pool size")
    p.add_argument("--verbose", action="store_true",
                   help="Print more logging messages")
    return p


def make_config_from_args(args) -> "Config":
    from opentsdb_tpu.utils.config import Config
    config = Config()
    if args.config:
        config.load_file(args.config)
    if args.mode:
        config.override_config("tsd.mode", args.mode)
    if args.auto_metric:
        config.override_config("tsd.core.auto_create_metrics", "true")
    if args.staticroot:
        config.override_config("tsd.http.staticroot", args.staticroot)
    if args.cachedir:
        config.override_config("tsd.http.cachedir", args.cachedir)
    if args.port is not None:
        config.override_config("tsd.network.port", str(args.port))
    if args.bind:
        config.override_config("tsd.network.bind", args.bind)
    return config


def make_tsdb_from_args(args) -> "TSDB":
    from opentsdb_tpu.core import TSDB
    config = make_config_from_args(args)
    # the sanitizer must arm BEFORE the TSDB exists: locks and classes
    # constructed from here on get the instrumented wrappers
    maybe_arm_sanitizer(config)
    return TSDB(config)


def maybe_arm_sanitizer(config) -> bool:
    """tsd.sanitizer.enable=true arms tsdbsan (tools/sanitize) for this
    daemon: instrumented locks, write interception on lock-holding
    classes, and the deadlock watchdog.  A chaos/testing surface (the
    --san mode of tools/chaos_soak.py rides it); deployments without
    the tools/ tree degrade LOUDLY to disarmed."""
    if not config.get_bool("tsd.sanitizer.enable"):
        return False
    try:
        from tools import sanitize
    except ImportError:
        logging.getLogger("tsd.sanitizer").warning(
            "tsd.sanitizer.enable is set but tools.sanitize is not "
            "importable (repo root not on sys.path?) — sanitizer "
            "DISARMED")
        return False
    sanitize.install(
        lockset=config.get_bool("tsd.sanitizer.lockset.enable"),
        deadlock_watch=config.get_bool("tsd.sanitizer.deadlock.enable"),
        jax=config.get_bool("tsd.sanitizer.jax.enable"),
        watchdog_ms=config.get_int("tsd.sanitizer.deadlock.watchdog_ms"))
    logging.getLogger("tsd.sanitizer").info("tsdbsan armed")
    return True


def write_sanitizer_report(config) -> None:
    """At shutdown: finalize inversion detection and write the findings
    artifact when tsd.sanitizer.report.path is set."""
    path = config.get_string("tsd.sanitizer.report.path")
    if not path:
        return
    try:
        from tools import sanitize
        from tools.sanitize import deadlock
    except ImportError:
        return
    if not sanitize.installed():
        return
    deadlock.detect_inversions()
    try:
        sanitize.REPORTER.write_report(path)
    except OSError as e:
        logging.getLogger("tsd.sanitizer").warning(
            "could not write sanitizer report to %s: %s", path, e)


def log_device() -> bool:
    """Touch the backend ONCE, before the store opens or the port binds,
    and say where this daemon computes.  False when the backend cannot come up: the daemon
    then exits instead of discovering it on the first query (or, worse,
    serving from a platform nobody chose)."""
    # the ops package fixes x64, the platform set and the compile-cache
    # directory at import — all of which must precede backend init
    from opentsdb_tpu import ops  # noqa: F401
    from opentsdb_tpu.obs import jaxprof
    log = logging.getLogger("tsd.device")
    try:
        report = jaxprof.device_report()
    except RuntimeError as e:
        log.error("JAX backend failed to initialize; not serving: %s", e)
        return False
    log.info(
        "computing on platform=%s device_kind=%s count=%d "
        "bytes_in_use=%s peak_bytes_in_use=%s",
        report["platform"], report["kind"], report["count"],
        [m["bytesInUse"] for m in report["memory"]],
        [m["peakBytesInUse"] for m in report["memory"]])
    return True


def main(argv: list[str] | None = None) -> int:
    args = build_arg_parser().parse_args(argv)
    logging.basicConfig(
        level=logging.DEBUG if args.verbose else logging.INFO,
        format="%(asctime)s %(levelname)s [%(threadName)s] "
               "%(name)s: %(message)s")
    if not log_device():
        return 1
    tsdb = make_tsdb_from_args(args)
    if tsdb.config.enable_compactions:
        # The compaction-thread analog (CompactionQueue.java:95-107): dirty
        # series normalize off the read path, WAL fsync + snapshots follow
        # their configured cadences.
        tsdb.start_maintenance()
    port_cfg = tsdb.config.get_string("tsd.network.port")
    if not port_cfg:
        print("Missing network port (--port or tsd.network.port)",
              file=sys.stderr)
        return 1
    from opentsdb_tpu.tsd.server import TSDServer
    server = TSDServer(
        tsdb, port=int(port_cfg),
        bind=tsdb.config.get_string("tsd.network.bind") or "0.0.0.0",
        worker_threads=args.worker_threads)

    async def run():
        await server.start()
        # SIGTERM/SIGINT take the GRACEFUL path (drain in-flight
        # responder work, then tsdb.shutdown -> final snapshot) instead
        # of the default instant kill — a supervisor's stop must not be
        # a crash.  request_shutdown is idempotent and thread-safe.
        import signal
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                loop.add_signal_handler(sig, server.request_shutdown)
            except (NotImplementedError, RuntimeError):
                pass         # non-main thread / platform without support
        await server.serve_forever()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    write_sanitizer_report(tsdb.config)
    return 0


if __name__ == "__main__":
    sys.exit(main())

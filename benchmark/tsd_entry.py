"""Launcher for a traced run: arms jax.profiler, then calls
opentsdb_tpu.tools.tsd_main.main(argv) unchanged.  Only the process that
holds the chip can trace it, and that process is the daemon.

    python benchmark/tsd_entry.py <trace dir> <tsd_main arguments...>

SIGUSR1 starts the trace, SIGUSR2 stops it.  Each is done off the main
thread (the asyncio loop keeps serving), and leaves a marker file holding
time.monotonic() in the trace directory when it is through."""

import os
import signal
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    trace_dir, argv = sys.argv[1], sys.argv[2:]
    os.makedirs(trace_dir, exist_ok=True)
    sys.path.insert(0, REPO)
    start, stop = threading.Event(), threading.Event()
    signal.signal(signal.SIGUSR1, lambda *_: start.set())
    signal.signal(signal.SIGUSR2, lambda *_: stop.set())

    def mark(name: str) -> None:
        with open(os.path.join(trace_dir, name), "w") as fh:
            fh.write(repr(time.monotonic()))

    def control() -> None:
        start.wait()
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # 8 busy threads: keep it small
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        mark("started")
        stop.wait()
        jax.profiler.stop_trace()
        mark("stopped")

    threading.Thread(target=control, daemon=True).start()
    from opentsdb_tpu.tools.tsd_main import main as tsd_main
    return tsd_main(argv)


if __name__ == "__main__":
    sys.exit(main())

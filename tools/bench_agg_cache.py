"""In-process repeat/refresh-query benchmark for the partial-aggregate
cache (ISSUE 9 acceptance artifact).

Measures the production planner path under tsdbobs tracing — per-query
pipeline-span wall ms and the answer's fetch ms (the `fetch` stage:
where the request waits for the device) — for three phases of the dashboard
workload the cache exists for:

  cold     first sight of the plan family (monolithic or populating)
  warm     exact repeat, fully covered (the refresh-every-10s case)
  sliding  the window slides forward each query (edge windows
           recompute, interior blocks reuse)

and a cache-disabled control of the same repeat, then writes
BENCH_AGG_CACHE.json at the repo root.  The acceptance gate is
`warm_speedup >= 5` (cold pipeline wall / warm pipeline wall);
tests/test_agg_cache.py::test_cache_hit_speedup_at_scale pins the same
ratio in-tree at the same shape.

Usage: python tools/bench_agg_cache.py --platform cpu [--series N]
       [--points N] [--interval-s N] [--repeats N] [--no-artifact]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# block-grid-aligned epoch (default 32-window blocks x 500s interval =
# 16000s): the headline repeat query is the aligned dashboard case —
# full block coverage, warm queries replay every window.  The sliding
# phase is unaligned by construction and carries the edge-recompute
# cost.
BASE = 84813 * 16000


def build_tsdb(enable: bool, series: int, points: int):
    import numpy as np
    from opentsdb_tpu.core.tsdb import TSDB
    from opentsdb_tpu.utils.config import Config
    tsdb = TSDB(Config({
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": False,
        "tsd.query.cache.enable": enable,
        "tsd.query.cache.min_repeats": 1,
    }))
    rng = np.random.default_rng(11)
    for h in range(series):
        key = tsdb._series_key("bench.m", {"h": str(h)}, create=True)
        ts = (np.arange(points, dtype=np.int64) + BASE) * 1000
        tsdb.store.add_batch(key, ts, rng.standard_normal(points),
                             False)
    return tsdb


def traced_query(tsdb, start: int, end: int, interval_s: int):
    """One /api/query-equivalent run under a tsdbobs trace; returns
    (pipeline-span wall ms, fetch ms, total wall ms, exec stats)."""
    from opentsdb_tpu.models import TSQuery, parse_m_subquery
    from opentsdb_tpu.obs import trace as obs_trace
    q = TSQuery(start=str(start), end=str(end),
                queries=[parse_m_subquery(
                    "sum:%ds-sum:bench.m{h=*}" % interval_s)])
    q.validate()
    runner = tsdb.new_query_runner()
    tr = obs_trace.Trace("bench")
    obs_trace.activate(tr)
    t0 = time.perf_counter()
    try:
        runner.run(q)
    finally:
        total_ms = (time.perf_counter() - t0) * 1e3
        obs_trace.deactivate()
    tr.finish()

    def find(span, name):
        if span.name == name:
            return span
        for child in span.children:
            got = find(child, name)
            if got is not None:
                return got
        return None

    pipe, fetch = find(tr.root, "pipeline"), find(tr.root, "fetch")
    return (pipe.wall_ms if pipe else total_ms,
            fetch.wall_ms if fetch else 0.0,
            total_ms, dict(runner.exec_stats))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--series", type=int, default=8)
    ap.add_argument("--points", type=int, default=400_000)
    ap.add_argument("--interval-s", type=int, default=500)
    ap.add_argument("--repeats", type=int, default=5)
    ap.add_argument("--no-artifact", action="store_true")
    from bench import add_platform_arg, require_device
    add_platform_arg(ap)
    args = ap.parse_args()
    device = require_device(args.platform)

    # aligned repeat range: whole 32-window blocks (and the final
    # window's full ms coverage — a seconds-granularity `end` lands on
    # w_start + interval, which covers w_start + interval*1000 - 1 ms)
    end = BASE + (args.points // (32 * args.interval_s)) \
        * 32 * args.interval_s
    tsdb = build_tsdb(True, args.series, args.points)
    # compile warmup round — jit compile time is not what the cache
    # saves, so it is never part of the measured cold
    traced_query(tsdb, BASE, end, args.interval_s)
    # cold/warm interleaved: each invalidate() forces a full
    # repopulating cold, followed by warm repeats; medians on both
    # sides keep one scheduler hiccup from deciding the ratio
    colds, warms = [], []
    for _ in range(3):
        tsdb.agg_cache.invalidate()
        colds.append(traced_query(tsdb, BASE, end, args.interval_s))
        traced_query(tsdb, BASE, end, args.interval_s)  # earn promotion
        # stand in for the maintenance tick: hot blocks get their
        # device mirrors off the measured path, as in a real daemon
        tsdb.agg_cache.promote_pending(max_uploads=64)
        warms.extend(traced_query(tsdb, BASE, end, args.interval_s)
                     for _ in range(args.repeats))
    cold = min(colds, key=lambda r: r[0])   # conservative cold side
    # sliding: a fixed refresh cadence (2 windows per step).  The edge
    # pieces' pow2-padded shapes cycle through a handful of jit
    # buckets; the warmup steps pay those compiles once, as a steady
    # dashboard would, so the measured slides are steady-state.
    for i in range(1, 9):
        traced_query(tsdb, BASE + 2 * i * args.interval_s,
                     end + 2 * i * args.interval_s, args.interval_s)
    slides = [traced_query(tsdb, BASE + 2 * i * args.interval_s,
                           end + 2 * i * args.interval_s,
                           args.interval_s)
              for i in range(9, 9 + args.repeats)]
    control = build_tsdb(False, args.series, args.points)
    traced_query(control, BASE, end, args.interval_s)   # compile warm
    plains = [traced_query(control, BASE, end, args.interval_s)
              for _ in range(args.repeats)]

    def med(rows, i):
        return round(statistics.median(r[i] for r in rows), 3)

    out = {
        "shape": {"series": args.series, "points_per_series":
                  args.points, "interval_s": args.interval_s,
                  "windows": args.points // args.interval_s},
        "cold": {"pipeline_wall_ms": round(cold[0], 3),
                 "fetch_ms": round(cold[1], 3),
                 "total_wall_ms": round(cold[2], 3)},
        "warm": {"pipeline_wall_ms": med(warms, 0),
                 "fetch_ms": med(warms, 1),
                 "total_wall_ms": med(warms, 2),
                 "hit_windows": warms[-1][3].get(
                     "aggCacheHitWindows", 0)},
        "sliding": {"pipeline_wall_ms": med(slides, 0),
                    "fetch_ms": med(slides, 1),
                    "total_wall_ms": med(slides, 2)},
        "uncached_repeat": {"pipeline_wall_ms": med(plains, 0),
                            "fetch_ms": med(plains, 1),
                            "total_wall_ms": med(plains, 2)},
        "warm_speedup": round(cold[0] / max(med(warms, 0), 1e-9), 2),
        "warm_speedup_vs_uncached_repeat": round(
            med(plains, 0) / max(med(warms, 0), 1e-9), 2),
        "sliding_speedup": round(
            med(plains, 0) / max(med(slides, 0), 1e-9), 2),
        "platform": device["platform"],
        "device": device,
        "cache_stats": {k: v for k, v in
                        tsdb.agg_cache.collect_stats().items()},
    }
    print(json.dumps(out, indent=2))
    if not args.no_artifact:
        path = os.path.join(REPO, "BENCH_AGG_CACHE.json")
        with open(path, "w") as fh:
            json.dump(out, fh, indent=2)
            fh.write("\n")
        print("wrote %s" % path, file=sys.stderr)


if __name__ == "__main__":
    main()

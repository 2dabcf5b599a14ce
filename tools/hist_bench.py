"""Histogram query throughput on the device path.

One JSON line: histogram points served/sec through the end-to-end
percentile query path — planner -> assemble_columnar -> ONE
[rows, B] segment-sum dispatch + vectorized percentiles
(opentsdb_tpu/histogram/kernels.py), replacing the reference's
per-datapoint histogram iterator chains
(/root/reference/src/core/HistogramAggregationIterator.java:319,
HistogramSpan.java:585, HistogramDownsampler.java:403).

vs_baseline here is the measured speedup over the kept numpy reference
implementation (histogram/store.py merge_group/downsample_counts/
percentiles_of — the r3 host path, still used as the differential-test
oracle) answering the SAME query on the SAME store.  When the numpy
pass exceeds its cap it reports a lower bound.

Run: python tools/hist_bench.py [--series N] [--slots K]
Without `--platform cpu` a run that finds no TPU exits non-zero; the row
names the device it was measured on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

BASE = 1_356_998_400
HIST_CONFIG = '{"SimpleHistogramDecoder": 0}'
NUMPY_CAP_S = 180.0


def _note(msg: str) -> None:
    print("[hist_bench] " + msg, file=sys.stderr, flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--series", type=int, default=10_240)
    ap.add_argument("--slots", type=int, default=16)
    ap.add_argument("--passes", type=int, default=5)
    from bench import add_platform_arg, require_device
    add_platform_arg(ap)
    args = ap.parse_args()
    device = require_device(args.platform)

    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.models import TSQuery, parse_m_subquery
    from opentsdb_tpu.utils.config import Config

    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                        "tsd.core.histograms.config": HIST_CONFIG}))
    t0 = time.perf_counter()
    # per-series bucket variety so the union vocabulary is non-trivial
    edges = (0, 5, 10, 25, 50, 100, 250, 1000)
    for s in range(args.series):
        buckets = {}
        for b in range(len(edges) - 1):
            if (s + b) % 3 != 0:
                buckets["%d,%d" % (edges[b], edges[b + 1])] = (s % 47) + b + 1
        for k in range(args.slots):
            tsdb.add_histogram_point_json(
                "hb.m", BASE + k * 60, {"buckets": buckets},
                {"host": "h%d" % s, "dc": "d%d" % (s % 8)})
    n_points = args.series * args.slots
    _note("ingested %d histogram points (%d series x %d slots) in %.1fs"
          % (n_points, args.series, args.slots, time.perf_counter() - t0))

    def run_query(off: int):
        # unique start per pass: no layer can short-circuit a repeat
        sub = parse_m_subquery("sum:percentiles[50,99]:hb.m{dc=*}")
        q = TSQuery(start=str(BASE - 300 - off),
                    end=str(BASE + args.slots * 60 + 60), queries=[sub])
        q.validate()
        res = tsdb.new_query_runner().run(q)
        assert res and res[0].dps       # host dict: inherently synced
        return res

    run_query(0)   # compile + warm
    lats = []
    for i in range(args.passes):
        t1 = time.perf_counter()
        run_query(i + 1)
        lats.append(time.perf_counter() - t1)
    lats.sort()
    p50 = lats[len(lats) // 2]
    _note("device path: %s s/query" % [round(x, 3) for x in lats])

    # numpy reference oracle on the same store/query (capped)
    from opentsdb_tpu.histogram.store import (merge_group,
                                              downsample_counts,
                                              percentiles_of)
    import numpy as np
    metric_uid = tsdb.metrics.get_id("hb.m")
    series = tsdb.histogram_store.series_for_metric(metric_uid)
    start_ms, end_ms = (BASE - 300) * 1000, (BASE + args.slots * 60 + 60) * 1000
    t1 = time.perf_counter()
    ref_done = True
    # one group (all series aggregate under dc=* group-by semantics of
    # this shape: single group per distinct dc -> 8 groups)
    by_dc: dict = {}
    for s in series:
        dc = None
        for tk, tv in tsdb.resolve_key_tags(s.key).items():
            if tk == "dc":
                dc = tv
        by_dc.setdefault(dc, []).append(s)
    for dc, members in by_dc.items():
        pts = []
        for s in members:
            for ts_ms, h in s.window(start_ms, end_ms):
                pts.append((ts_ms, h))
        merged = merge_group(pts)
        if merged:
            ts_arr, counts, bounds = merged
            percentiles_of(counts, bounds, np.asarray([50.0, 99.0]))
        if time.perf_counter() - t1 > NUMPY_CAP_S:
            ref_done = False
            break
    ref_s = time.perf_counter() - t1
    _note("numpy reference: %.2fs (%s)"
          % (ref_s, "complete" if ref_done else "capped — lower bound"))

    rate = n_points / p50
    print(json.dumps({
        "metric": "histogram percentile query p50 end-to-end "
                  "(%d series x %d slots, 8 groups, single [rows,B] "
                  "dispatch); vs_baseline = speedup over the numpy "
                  "reference host path%s"
                  % (args.series, args.slots,
                     "" if ref_done else " (lower bound, reference capped)"),
        "value": round(rate, 1),
        "unit": "histogram points served/sec",
        "p50_seconds": round(p50, 4),
        "vs_baseline": round(ref_s / max(p50, 1e-9), 2),
        "device": device,
    }), flush=True)


if __name__ == "__main__":
    main()

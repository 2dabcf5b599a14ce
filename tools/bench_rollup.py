"""Lane-served vs tiled-exact at the over-limit long-range shape.

ISSUE 11 acceptance evidence: the long-range group-by class PR 10
opened (BENCH_TILING.json: answered at 30.2k dp/s where HEAD refused)
converts to "answers at cache speed" once a rollup lane stands in
front of the tiled exact path.  Same [S, W] over-limit grid shape as
BENCH_TILING (64 series x 16384 windows, state_mb=4), time axis scaled
to 1h windows so the 1h lane serves it; integer-valued data so the
lane-served and tiled-exact answers must match BITWISE.

    python tools/bench_rollup.py --platform cpu [--out BENCH_ROLLUP.json]

Writes one JSON document naming the device it ran on (the one committed
at the repo root as BENCH_ROLLUP.json is a CPU run; not measured on the
chip yet).  Without `--platform cpu` a run that finds no TPU fails.  The
>= 10x ratio is pinned on CPU by tests/test_rollup_lanes.py (slow).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_S = 1_356_998_400
WINDOWS = 16_384          # 1h windows -> ~1.9 years of range
SPAN_S = WINDOWS * 3600
HOSTS = 64
PTS = 1_000_000           # per series -> 64M datapoints (1-min cadence)
STATE_MB = 4              # [64, 16384] streaming estimate 16MB >> 4MB


def _mk(rollup: bool):
    import numpy as np
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.utils.config import Config
    t = TSDB(Config({
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": "false",
        "tsd.query.device_cache.enable": "false",
        "tsd.query.cache.enable": "false",
        "tsd.query.streaming.point_threshold": "1000",
        "tsd.query.spill.enable": "true",
        "tsd.query.spill.host_mb": "32",
        "tsd.query.streaming.state_mb": str(STATE_MB),
        "tsd.rollup.enable": "true" if rollup else "false",
        "tsd.rollup.intervals": "1m,1h,1d",
        "tsd.rollup.block_windows": "64",
        "tsd.rollup.delay_ms": "0",
        "tsd.rollup.mb": "256",
    }))
    # regular-cadence telemetry (hosts report on a fixed stride, each
    # with its own phase) — the realistic dense long-range shape
    stride = SPAN_S // PTS
    for h in range(HOSTS):
        times = (np.arange(PTS, dtype=np.int64) * SPAN_S) // PTS \
            + (h * 97) % stride
        vals = (np.arange(PTS, dtype=np.int64) * 7 + h * 13) % 101
        key = t._series_key("bench.rollup",
                            {"h": "h%d" % h, "g": "g%d" % (h % 8)},
                            create=True)
        t.store.add_batch(key, (BASE_S + times) * 1000, vals, True)
    return t


def _query(tsdb):
    from opentsdb_tpu.models import TSQuery, parse_m_subquery
    q = TSQuery(start=str(BASE_S), end=str(BASE_S + SPAN_S - 1),
                queries=[parse_m_subquery(
                    "sum:1h-sum:bench.rollup{g=*}")])
    q.validate()
    runner = tsdb.new_query_runner()
    t0 = time.perf_counter()
    out = runner.run(q)
    wall = time.perf_counter() - t0
    return out, wall, runner.exec_stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_ROLLUP.json"))
    from bench import add_platform_arg, require_device
    add_platform_arg(ap)
    args = ap.parse_args()
    device = require_device(args.platform)
    dp = HOSTS * PTS

    # the tiled exact path (PR 10): lanes disabled, same over-limit plan
    tiled_tsdb = _mk(rollup=False)
    _query(tiled_tsdb)                                # compiles
    out_tiled, wall_tiled, tstats = _query(tiled_tsdb)
    assert tstats.get("tiledExecution") == 1.0, tstats
    tiled_dps = [(r.tags, r.dps) for r in out_tiled]
    tiled_tsdb.shutdown()
    del tiled_tsdb, out_tiled

    # the lane path: consult (records demand), build, serve
    lane_tsdb = _mk(rollup=True)
    _query(lane_tsdb)                                 # demand + compiles
    t0 = time.perf_counter()
    built = 0
    for _ in range(64):
        n = lane_tsdb.rollup_lanes.refresh(
            lane_tsdb.store, max_blocks=256)
        built += n
        if not n:
            break
    build_wall = time.perf_counter() - t0
    out_cold, wall_cold, _ = _query(lane_tsdb)        # lane compiles
    out_lane, wall_lane, lstats = _query(lane_tsdb)
    assert lstats.get("rollupLane") == 1.0, lstats

    lane_dps = [(r.tags, r.dps) for r in out_lane]
    assert lane_dps == tiled_dps, "lane answer diverged from tiled"

    ratio = wall_tiled / wall_lane
    doc = {
        "metric": "lane-served vs tiled-exact wall at the over-limit "
                  "long-range group-by shape (tsd.query.streaming."
                  "state_mb=%dMB, 1h lane)" % STATE_MB,
        "platform": device["platform"],
        "device": device,
        "shape": {"series": HOSTS, "windows": WINDOWS, "groups": 8,
                  "datapoints": dp, "lane": "1h",
                  "range_days": SPAN_S // 86400},
        "tiled_exact": {
            "wall_s_warm": round(wall_tiled, 3),
            "dp_per_s_warm": round(dp / wall_tiled, 1),
            "tiles": tstats.get("tiledTiles"),
        },
        "lane_served": {
            "wall_s_warm": round(wall_lane, 3),
            "wall_s_cold": round(wall_cold, 3),
            "dp_per_s_warm": round(dp / wall_lane, 1),
            "striped": lstats.get("rollupLaneStriped"),
            "blocks_built": built,
            "build_wall_s": round(build_wall, 3),
        },
        "speedup_lane_vs_tiled_exact": round(ratio, 2),
        "divergence": "zero (lane == tiled exact, integer-valued "
                      "data)",
    }
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(json.dumps(doc, indent=2))


if __name__ == "__main__":
    main()

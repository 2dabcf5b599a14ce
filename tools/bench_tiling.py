"""Answered-vs-refused throughput at an over-limit shape (BENCH_TILING).

ISSUE 10 acceptance evidence: at a group-by shape whose [S, W]
streaming state exceeds ``tsd.query.streaming.state_mb``, HEAD refused
with the 413 budget contract — worth exactly 0 datapoints/sec.  The
spill-backed tiled executor (ops/tiling.py) answers it.  This bench
records both sides plus a resident reference run of the SAME plan
under an uncapped budget, and pins zero answer divergence between the
tiled and resident executions.

    python tools/bench_tiling.py --platform cpu [--out BENCH_TILING.json]

Writes one JSON document naming the device it ran on (the one committed
at the repo root as BENCH_TILING.json is a CPU run; not measured on the
chip yet).  Without `--platform cpu` a run that finds no TPU fails.
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
SPAN_S = 163_840          # 16384 windows at 10s
HOSTS = 64
PTS = 2000                # per series -> 128k datapoints scanned
STATE_MB = 4              # streaming estimate 64*16384*16B = 16MB >> 4MB


def _mk(state_mb, spill: bool):
    import numpy as np
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.utils.config import Config
    t = TSDB(Config({
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": "false",
        "tsd.query.device_cache.enable": "false",
        "tsd.query.cache.enable": "false",
        "tsd.query.streaming.point_threshold": "1000",
        "tsd.query.spill.enable": "true" if spill else "false",
        "tsd.query.spill.host_mb": "8",
        "tsd.query.streaming.state_mb": str(state_mb),
    }))
    rng = np.random.default_rng(11)
    for h in range(HOSTS):
        times = np.sort(rng.choice(SPAN_S, size=PTS, replace=False))
        vals = (np.arange(PTS) * 7 + h * 13) % 101
        for ts, v in zip(times, vals):
            t.add_point("bench.tiling", BASE_S + int(ts), float(v),
                        {"h": "h%d" % h, "g": "g%d" % (h % 8)})
    return t


def _query(tsdb):
    from opentsdb_tpu.models import TSQuery, parse_m_subquery
    q = TSQuery(start=str(BASE_S), end=str(BASE_S + SPAN_S),
                queries=[parse_m_subquery(
                    "sum:10s-sum:bench.tiling{g=*}")])
    q.validate()
    runner = tsdb.new_query_runner()
    t0 = time.perf_counter()
    out = runner.run(q)
    wall = time.perf_counter() - t0
    return out, wall, runner.exec_stats


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(REPO,
                                                  "BENCH_TILING.json"))
    from bench import add_platform_arg, require_device
    add_platform_arg(ap)
    args = ap.parse_args()
    device = require_device(args.platform)
    dp = HOSTS * PTS

    # HEAD behavior: the same plan with the tiled path disabled
    refused = _mk(STATE_MB, spill=False)
    try:
        _query(refused)
        head = {"status": 200,
                "note": "UNEXPECTED: over-limit plan served resident"}
    except Exception as e:  # noqa: BLE001 — recording the verdict
        head = {"status": getattr(e, "status", 500),
                "error": str(e)[:200],
                "details": getattr(e, "details", None)}

    tiled_tsdb = _mk(STATE_MB, spill=True)
    out_cold, wall_cold, _ = _query(tiled_tsdb)       # includes compiles
    out_warm, wall_warm, stats = _query(tiled_tsdb)
    assert stats.get("tiledExecution") == 1.0, stats

    resident = _mk(1 << 20, spill=False)              # uncapped budget
    _query(resident)
    out_res, wall_res, rstats = _query(resident)
    assert "tiledExecution" not in rstats

    tiled_dps = [(r.tags, r.dps) for r in out_warm]
    res_dps = [(r.tags, r.dps) for r in out_res]
    assert tiled_dps == res_dps, "tiled answer diverged from resident"

    doc = {
        "metric": "answered-vs-refused throughput at an over-limit "
                  "[S, W] group-by shape (tsd.query.streaming."
                  "state_mb=%dMB)" % STATE_MB,
        "platform": device["platform"],
        "device": device,
        "shape": {"series": HOSTS, "windows": 32768, "groups": 8,
                  "datapoints": dp,
                  "streaming_state_mb_needed": 32},
        "head_behavior": head,
        "tiled": {
            "status": 200,
            "wall_s_cold": round(wall_cold, 3),
            "wall_s_warm": round(wall_warm, 3),
            "dp_per_s_warm": round(dp / wall_warm, 1),
            "tiles": stats.get("tiledTiles"),
            "spill_bytes": stats.get("spillBytes"),
        },
        "resident_reference_uncapped": {
            "wall_s_warm": round(wall_res, 3),
            "dp_per_s_warm": round(dp / wall_res, 1),
        },
        "divergence": "zero (tiled == resident, integer-valued data)",
        "answered_vs_refused_dp_per_s": [round(dp / wall_warm, 1), 0.0],
    }
    with open(args.out, "w") as fh:
        json.dump(doc, fh, indent=2)
        fh.write("\n")
    print(json.dumps(doc, indent=2))


if __name__ == "__main__":
    main()

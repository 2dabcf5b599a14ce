"""Pin the planner's routing: explain a canonical query matrix into
PLAN_CORPUS.json.

Every entry explains one query (optionally under what-if overrides)
against a deterministic in-process TSDB profile and records the
routing verdict — path, plan fingerprint, and the full discrete
provenance (shapes, chosen kernel modes, lane/cache verdicts;
never raw milliseconds) — via the SAME
plan_decision() the executor dispatches on (query/plandecision.py).

The committed PLAN_CORPUS.json is byte-pinned by a tier-1 test
(tests/test_explain.py) exactly like the generated docs: any change to
planner routing — a new eligibility gate, a reordered consult, a
costmodel flip at a pinned shape — surfaces as a reviewed corpus diff
instead of a silent perf regression.

    python tools/plan_corpus.py                  # rewrite the corpus
    python tools/plan_corpus.py --out /tmp/x     # write elsewhere
    python tools/plan_corpus.py --check          # exit 1 on drift

Deterministic by construction: fixed epoch timestamps, fixed data,
CPU platform (run under JAX_PLATFORMS=cpu), no wall-clock reads in
any recorded field, sorted-key JSON.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

CORPUS_PATH = os.path.join(REPO, "PLAN_CORPUS.json")

BASE = 1_356_998_400            # seconds; fixed epoch, never now()

# One profile = one deterministic daemon config + seeded dataset.
# mesh stays off everywhere: the corpus pins the single-device routes,
# and its fingerprints must not depend on how many devices the machine
# that regenerates it happens to show.
_COMMON = {
    "tsd.core.auto_create_metrics": "true",
    "tsd.query.mesh.enable": "false",
    "tsd.rollup.interval": "0",          # no maintenance cadence races
    "tsd.stats.interval": "0",
    # the legacy profiles pin the PRE-batching routing matrix; the
    # `batched` arm gets its own profile below so every older entry's
    # path/fingerprint stays a stable regression anchor
    "tsd.query.batch.enable": "false",
}

PROFILES: dict[str, dict] = {
    "base": {
        "tsd.query.host_lane.max_points": "4096",
    },
    # the host-lane path needs the device cache OUT of the way: with it
    # on, a small cold query inline-builds an entry and serves resident
    # (pinned by resident_small_inline_build below)
    "hostlane": {
        "tsd.query.host_lane.max_points": "4096",
        "tsd.query.device_cache.enable": "false",
    },
    "streaming": {
        "tsd.query.streaming.point_threshold": "1000",
    },
    "tiled": {
        "tsd.query.streaming.point_threshold": "1000",
        "tsd.query.streaming.state_mb": "8",
    },
    "refused": {
        "tsd.query.streaming.point_threshold": "1000",
        "tsd.query.streaming.state_mb": "8",
        "tsd.query.spill.enable": "false",
    },
    "rollup": {
        "tsd.rollup.enable": "true",
        "tsd.rollup.intervals": "1m,1h",
        "tsd.query.degrade": "allow",
    },
    # fused multi-query dispatch (query/batcher.py): the `batched`
    # routing arm + its costmodel-priced dispatch-now decline, with
    # the device cache out of the way so the declined arm resolves
    # cleanly
    "batched": {
        "tsd.query.batch.enable": "true",
        "tsd.query.device_cache.enable": "false",
    },
}


def _feed(tsdb, metric: str, series: int, points: int,
          cadence_s: int) -> None:
    for h in range(series):
        tags = {"host": "h%02d" % h}
        for k in range(points):
            tsdb.add_point(metric, BASE + k * cadence_s,
                           float((k * 7 + h) % 101), tags)


def _build_profile(name: str):
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.utils.config import Config
    props = dict(_COMMON)
    props.update(PROFILES[name])
    tsdb = TSDB(Config(props))
    if name == "base":
        _feed(tsdb, "corpus.small", 3, 64, 15)
        _feed(tsdb, "corpus.big", 4, 6000, 1)
    elif name == "hostlane":
        _feed(tsdb, "corpus.small", 3, 64, 15)
    elif name in ("streaming",):
        _feed(tsdb, "corpus.big", 4, 6000, 1)
    elif name in ("tiled", "refused"):
        _feed(tsdb, "corpus.wide", 8, 5760, 30)
    elif name == "batched":
        _feed(tsdb, "corpus.small", 3, 64, 15)
        _feed(tsdb, "corpus.big", 4, 6000, 1)
    elif name == "rollup":
        _feed(tsdb, "corpus.lane", 8, 5760, 15)
        # 7 days at 1m cadence: wide enough that a 60s-interval grid
        # ([8, 16384] padded) busts a 1 MB what-if budget -> the
        # striped lane serve engages
        _feed(tsdb, "corpus.lane7", 8, 10080, 60)
    return tsdb


def _warm_lanes(tsdb, m: str, start: int, end: int) -> None:
    """Consult (records demand) + build the demanded lane blocks —
    the tests' warm() idiom (tests/test_rollup_lanes.py)."""
    from opentsdb_tpu.models.tsquery import TSQuery, parse_m_subquery
    q = TSQuery(start=str(start), end=str(end),
                queries=[parse_m_subquery(m)])
    q.validate()
    tsdb.new_query_runner().run(q)
    for _ in range(40):
        if not tsdb.rollup_lanes.refresh(tsdb.store, max_blocks=256):
            break


# (name, profile, m, start, end, what_if, needs_warm_lanes)
ENTRIES = [
    ("host_lane_small", "hostlane", "sum:30s-avg:corpus.small",
     BASE, BASE + 64 * 15, {}, False),
    ("resident_small_inline_build", "base", "sum:30s-avg:corpus.small",
     BASE, BASE + 64 * 15, {}, False),
    ("resident_big", "base", "sum:30s-avg:corpus.big",
     BASE, BASE + 6000, {}, False),
    ("union_no_downsample", "base", "sum:corpus.small",
     BASE, BASE + 64 * 15, {}, False),
    ("agg_rewrite_whatif_warm", "base", "sum:30s-avg:corpus.big",
     BASE, BASE + 6000, {"assume_agg_cache": "warm"}, False),
    ("device_cache_whatif_cold", "base", "sum:30s-avg:corpus.big",
     BASE, BASE + 6000, {"assume_device_cache": "cold"}, False),
    # pins that costmodel what-ifs NEVER perturb the routing
    # fingerprint (must equal resident_big's)
    ("resident_big_forced_modes", "base", "sum:30s-avg:corpus.big",
     BASE, BASE + 6000,
     {"force_scan": "flat"}, False),
    ("rate_resident", "base", "sum:rate:30s-avg:corpus.big",
     BASE, BASE + 6000, {}, False),
    ("extreme_resident", "base", "max:30s-max:corpus.big",
     BASE, BASE + 6000, {}, False),
    ("streamed_big", "streaming", "sum:30s-avg:corpus.big",
     BASE, BASE + 6000, {}, False),
    ("tiled_wide", "tiled", "sum:1s-avg:corpus.wide",
     BASE, BASE + 5760 * 30, {}, False),
    ("refused_wide", "refused", "sum:1s-avg:corpus.wide",
     BASE, BASE + 5760 * 30, {}, False),
    ("rollup_lane_1m", "rollup", "sum:60s-sum:corpus.lane",
     BASE + 60, BASE + 5600 * 15, {}, True),
    ("rollup_lane_striped_whatif", "rollup",
     "sum:60s-sum:corpus.lane7", BASE + 60, BASE + 10080 * 60,
     {"assume_rollup": "warm", "state_mb": "1"}, False),
    ("degrade_preview", "rollup", "sum:15s-avg:corpus.lane",
     BASE, BASE + 5760 * 15, {"deadline_ms": "1"}, False),
    # fused multi-query dispatch: a dispatch-bound small query routes
    # through the batcher; a compute-heavy shape prices past the
    # amortize factor and DECLINES to dispatch-now (the cost-based
    # coalesce line, not a static batch size)
    ("batched_small", "batched", "sum:30s-avg:corpus.small",
     BASE, BASE + 64 * 15, {}, False),
    ("batched_declined_compute_bound", "batched",
     "sum:2s-avg:corpus.big", BASE, BASE + 6000, {}, False),
]


def build_corpus() -> dict:
    from opentsdb_tpu.models.tsquery import TSQuery, parse_m_subquery
    from opentsdb_tpu.query import explain as explain_mod

    corpus_entries = []
    tsdbs: dict[str, object] = {}
    try:
        for (name, profile, m, start, end, raw_wi, warm) in ENTRIES:
            tsdb = tsdbs.get(profile)
            if tsdb is None:
                tsdb = tsdbs[profile] = _build_profile(profile)
            if warm:
                _warm_lanes(tsdb, m, start, end)
            q = TSQuery(start=str(start), end=str(end),
                        queries=[parse_m_subquery(m)])
            q.validate()
            what_if = explain_mod.parse_what_if(raw_wi)
            report = explain_mod.explain_query(tsdb, q, what_if)
            segments = []
            for sub in report["subQueries"]:
                for seg in sub.get("segments", []):
                    rec = {"kind": seg["kind"], "path": seg["path"]}
                    if "fingerprint" in seg:
                        rec["fingerprint"] = seg["fingerprint"]
                        rec["provenance"] = seg["provenance"]
                    segments.append(rec)
            entry = {
                "name": name,
                "profile": profile,
                "query": m,
                "startOffsetS": start - BASE,
                "endOffsetS": end - BASE,
                "whatIf": report["whatIf"],
                "admission": {
                    "verdict": report["admission"]["verdict"],
                },
                "segments": segments,
            }
            degraded = report["admission"].get("degraded")
            if degraded is not None:
                entry["admission"]["degraded"] = degraded
            corpus_entries.append(entry)
    finally:
        for tsdb in tsdbs.values():
            tsdb.shutdown()
    return {
        "comment": ("Generated by tools/plan_corpus.py — byte-pinned "
                    "in tier-1 (tests/test_explain.py).  Regenerate "
                    "with: JAX_PLATFORMS=cpu python "
                    "tools/plan_corpus.py"),
        "entries": corpus_entries,
    }


def render(corpus: dict) -> str:
    return json.dumps(corpus, indent=2, sort_keys=True) + "\n"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=CORPUS_PATH)
    ap.add_argument("--check", action="store_true",
                    help="compare against the committed corpus; exit "
                         "1 on drift, write nothing")
    args = ap.parse_args()
    text = render(build_corpus())
    if args.check:
        try:
            with open(CORPUS_PATH, encoding="utf-8") as fh:
                committed = fh.read()
        except OSError:
            committed = ""
        if committed != text:
            sys.stderr.write(
                "PLAN_CORPUS.json is stale — planner routing changed; "
                "review the diff and regenerate with "
                "JAX_PLATFORMS=cpu python tools/plan_corpus.py\n")
            return 1
        print("PLAN_CORPUS.json is in sync")
        return 0
    with open(args.out, "w", encoding="utf-8") as fh:
        fh.write(text)
    print("wrote %s (%d entries)" % (args.out, len(ENTRIES)))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Offline costmodel fit: BENCH_CALIBRATION.json from a dumped ring.

The operator path when the chip is only reachable in bench sessions:
run traced traffic there, save the segment ring —

    curl tsd:4242/api/stats/query > ring.json        # ring rides the
                                                     # query-stats payload
    # ... or any JSON file holding a list of ring entries
    python tools/fit_costmodel.py ring.json          # writes repo-root
                                                     # BENCH_CALIBRATION.json

— and every later process (daemon or bench) starts from the fitted
constants via ops/costmodel.py's file override layer.  The online loop
(`tsd.costmodel.autotune.enable`, ops/calibrate.py) does the same fit
continuously from live traffic; this CLI is the one-shot equivalent
for hardware you can only visit.

Accepts either a raw JSON list of ring entries (obs.jaxprof.segments())
or a saved /api/stats/query response (entries under
"costmodelSegments").  Only entries with a feature vector and a
positive measured actualMs are fittable — serve with tsd.trace.enable
and tsd.trace.device_time on.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def load_entries(path: str) -> list[dict]:
    with open(path) as fh:
        payload = json.load(fh)
    if isinstance(payload, dict):
        payload = payload.get("costmodelSegments", [])
    if not isinstance(payload, list):
        raise SystemExit("%s: expected a JSON list of ring entries or "
                         "an /api/stats/query payload with "
                         "costmodelSegments" % path)
    return [e for e in payload if isinstance(e, dict)]


def main(argv: list[str] | None = None) -> int:
    from opentsdb_tpu.ops import calibrate, costmodel

    ap = argparse.ArgumentParser(
        description="Fit costmodel per-unit constants from a dumped "
                    "predicted-vs-actual segment ring")
    ap.add_argument("ring", help="JSON file: a segment-ring dump or a "
                                 "saved /api/stats/query response")
    ap.add_argument("--out", default=None,
                    help="calibration file to merge into (default: "
                         "repo-root BENCH_CALIBRATION.json)")
    ap.add_argument("--platform", action="append", default=None,
                    help="fit only this platform (repeatable; default: "
                         "every platform present in the ring)")
    ap.add_argument("--min-samples", type=int, default=16,
                    help="fittable entries required per platform "
                         "(default 16)")
    ap.add_argument("--max-step", type=float, default=0.0,
                    help="bound per-term movement to this factor of "
                         "the current table; 0 = unbounded (default — "
                         "a one-shot offline fit should land where the "
                         "measurements are)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the fit, write nothing")
    args = ap.parse_args(argv)

    entries = load_entries(args.ring)
    # the calibration file is keyed by cost-table name; a ring entry or
    # a --platform the cost model has no table for is an error
    # (costmodel._table_key raises), never silently another platform's
    try:
        for e in entries:
            if e.get("platform"):
                costmodel._table_key(e["platform"])
        platforms = sorted(
            {costmodel._table_key(p) for p in args.platform}
            if args.platform else
            {e.get("platform") for e in entries if e.get("platform")})
    except ValueError as e:
        print("refusing to fit: %s" % e, file=sys.stderr)
        return 1
    if not platforms:
        print("no fittable entries (need 'platform' + 'features' + "
              "measured actualMs: serve with tsd.trace.enable and "
              "tsd.trace.device_time on)", file=sys.stderr)
        return 1

    out_path = args.out or costmodel.calibration_file()
    fitted_all: dict[str, dict] = {}
    for plat in platforms:
        fitted, info = calibrate.fit_constants(
            entries, plat, min_samples=args.min_samples,
            max_step=args.max_step)
        if not fitted:
            print("%s: skipped (%s; %d fittable entries)"
                  % (plat, info.get("skipped", "nothing fitted"),
                     info["samples"]), file=sys.stderr)
            continue
        fitted_all[plat] = fitted
        print("%s: %d entries, residual %.4f, dispatch overhead "
              "%.3g s" % (plat, info["samples"], info["residual"],
                          info["overhead_s"]))
        for term in sorted(fitted):
            print("  %-18s %.6g" % (term, fitted[term]))

    if not fitted_all:
        print("nothing fitted; %s untouched" % out_path,
              file=sys.stderr)
        return 1
    if args.dry_run:
        print("--dry-run: not writing %s" % out_path)
        return 0
    calibrate.merge_calibration_file(out_path, fitted_all)
    print("wrote %s (platforms: %s)"
          % (out_path, ", ".join(sorted(fitted_all))))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

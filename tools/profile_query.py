"""jax.profiler harness for the production query pipeline, rebased onto
the EXPLAIN engine for its decision reporting.

Captures an XLA trace of the headline bench dispatch so the hot ops
(cumsum, searchsorted, gathers, segment reductions) can be attributed:

    python tools/profile_query.py [--outdir chiprun_out/profile] [--passes 3]
    python tools/profile_query.py --what-if calibration=default \\
                                  --what-if force_scan=flat

Only the process that holds the chip can trace it, and a run that finds
no TPU exits non-zero unless `--platform cpu` says the dry run is meant.

Before tracing, the tool prints the per-axis kernel-strategy decision
for the bench shape — chosen mode, per-candidate predicted ms,
calibration layer — through the SAME decision path the planner and
/api/query/explain consult (obs.jaxprof.segment_decisions + the
explain engine's what-if repricer; no parallel re-implementation of
the planner's choosers lives here).  ``--what-if KEY=VAL`` accepts the
explain grammar's costmodel keys (``platform``, ``calibration``,
``force_search/scan/extreme/group``) and prints the repriced view
beside the live one.

View traces with TensorBoard's profile plugin or xprof, or read them
with `jax.profiler.ProfileData.from_file`.  Each profiled pass uses a
unique window origin and ends in `jax.block_until_ready` (bench.py's
timing rules), so the trace window holds the passes' device work.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def _decision_lines(what_if) -> list[str]:
    """The bench shape's strategy decisions via the shared explain
    path: one line per axis, live pricing first, the what-if repriced
    view appended when overrides are active."""
    from bench import GROUPS, INTERVAL_MS, N, S, START, STEP_MEAN_MS
    from opentsdb_tpu.obs import jaxprof
    from opentsdb_tpu.ops.downsample import FixedWindows, pad_pow2
    from opentsdb_tpu.ops.hostlane import execution_platform
    from opentsdb_tpu.query.explain import _reprice_decisions

    end = START + N * STEP_MEAN_MS + 5_000
    wp = pad_pow2(FixedWindows.for_range(START, end, INTERVAL_MS).count)
    g_dec = pad_pow2(GROUPS)
    platform = what_if.platform or execution_platform()
    decisions = jaxprof.segment_decisions(platform, S, N, wp, g_dec,
                                          "avg", aggregator="sum")
    whatif = _reprice_decisions(decisions, what_if, S, N, wp, g_dec,
                                platform)

    def fmt(tag: str, axis: str, rep: dict) -> str:
        cands = ", ".join("%s=%.3fms" % (m, ms)
                          for m, ms in sorted(rep["candidates"].items()))
        return ("%s %s: mode=%s source=%s calibration=%s [%s]"
                % (tag, axis, rep["mode"], rep["source"],
                   rep["calibration"], cands))

    lines = [fmt("decision", axis, rep)
             for axis, rep in decisions.items()]
    if whatif is not None:
        lines.extend(fmt("what-if ", axis, rep)
                     for axis, rep in whatif.items())
    return lines


def main() -> None:
    import bench
    ap = argparse.ArgumentParser()
    ap.add_argument("--outdir", default="chiprun_out/profile")
    bench.add_platform_arg(ap)
    ap.add_argument("--passes", type=int, default=3)
    ap.add_argument("--what-if", action="append", default=[],
                    metavar="KEY=VAL",
                    help="explain-grammar costmodel override "
                         "(platform=, calibration=, force_<axis>=); "
                         "repeatable")
    ap.add_argument("--decisions-only", action="store_true",
                    help="print the strategy decisions and exit "
                         "without tracing")
    args = ap.parse_args()

    from opentsdb_tpu.query.explain import WhatIfError, parse_what_if
    raw = {}
    for spec in args.what_if:
        if "=" not in spec:
            ap.error("--what-if takes KEY=VAL, got %r" % spec)
        k, v = spec.split("=", 1)
        raw[k.strip()] = v
    try:
        what_if = parse_what_if(raw)
    except WhatIfError as e:
        ap.error(str(e))

    from bench import _note
    device = bench.require_device(args.platform)
    _note("device: %s" % device)
    for line in _decision_lines(what_if):
        _note(line)
    if args.decisions_only:
        return

    import jax
    from bench import (_OriginSequence, build_spec, dispatch, drain,
                       make_batch)

    batch = make_batch()
    spec, wargs, g_pad = build_spec()
    origins = _OriginSequence()
    drain(dispatch(spec, g_pad, batch, wargs, origins.next()))  # compile
    _note("compiled; tracing %d passes -> %s" % (args.passes, args.outdir))

    with jax.profiler.trace(args.outdir):
        for _ in range(args.passes):
            out = dispatch(spec, g_pad, batch, wargs, origins.next())
            drain(out)
    _note("trace written to %s" % args.outdir)


if __name__ == "__main__":
    main()

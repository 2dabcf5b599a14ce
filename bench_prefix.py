"""A/B harness for the downsample hot path.

Measures the production `/api/query` pipeline (same shape as bench.py)
under each combination of:
  * scan mode: flat one-pass cumsum  vs  blocked two-level scan
  * timestamp compaction: int64 ms  vs  int32 ms-offsets
  * value accumulation: float64 (default, Java-double parity)  vs  the
    float32 fast mode (set_value_precision('single'))

using bench.py's timing rules (unique operands per dispatch, every sample
ended by `jax.block_until_ready`, per-dispatch medians — see bench.py's
module docstring).  Without `--platform cpu` a run that finds no TPU exits
non-zero; a race row that fails prints an error row, the race continues,
and the run then exits non-zero.

The toggle setters clear every dependent jit cache themselves (the
toggles are read at trace time, so a stale cache would silently measure
the previous config).

The fitted calibration table is the PRIOR (ROADMAP item 1 leftover):
every race row carries the layered costmodel's predicted per-dispatch
seconds for its mode combo (`predicted_s`, priced through
DEFAULT_COSTS -> BENCH_CALIBRATION.json -> any live layer; the
`calibration` field names the winning layer), so a measurement session
can see at a glance where the fitted constants disagree with reality —
and `--prune N` races only the N best-predicted candidates per kernel
axis (each dropped candidate is announced, never silently skipped),
which is how a local CPU run prices candidates with live-fitted
constants instead of racing everything.

Prints one JSON line per config on stdout (stderr carries progress), e.g.
  {"config": "blocked+int32", "s_per_dispatch": 0.61, "dp_per_sec": 1.1e8,
   "device": {"platform": "tpu", ...}}
"""

from __future__ import annotations

import argparse
import json
import sys

import bench
from bench import (_OriginSequence, build_spec, dispatch, drain, make_batch,
                   measure_drained, _median, S, N, GROUPS)


def main() -> int:
    from opentsdb_tpu.ops import costmodel as cm
    from opentsdb_tpu.ops import downsample as ds
    from opentsdb_tpu.ops import group_agg as ga
    from opentsdb_tpu.ops.hostlane import execution_platform
    from opentsdb_tpu.ops.pipeline import PipelineSpec, DownsampleStep

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--prune", type=int, default=None,
                    help="race only the N best-predicted candidates per "
                         "kernel axis")
    bench.add_platform_arg(ap)
    args = ap.parse_args()
    prune = None if args.prune is None else max(args.prune, 1)
    device = bench.require_device(args.platform)
    failed: list[str] = []

    # This harness races EXPLICIT kernel modes: the platform guard (which
    # demotes dense search forms on CPU execution) would silently time
    # the scan kernel under a dense row's label on a CPU dev box.  A
    # no-op on the chip, where the race is meant to run.
    ds.set_platform_mode_guard(False)

    batch = make_batch()                       # int32 ts_base layout
    batch64 = make_batch(precompacted=False)   # absolute int64 layout
    bench._note("batches resident")
    spec, wargs, g_pad = build_spec()
    _spec64, wargs64, _g = build_spec(precompacted=False)
    spec_min = PipelineSpec(
        aggregator="sum",
        downsample=DownsampleStep("min", spec.downsample.window_spec,
                                  "none", 0.0))
    origins = _OriginSequence()

    def restore_defaults() -> None:
        ga.set_group_reduce_mode("segment")
        ds.set_extreme_mode("scan")
        ds.set_search_mode("scan")
        ds.set_scan_mode("flat")
        ds.set_ts_compaction(True)
        ds.set_value_precision("double")

    # the fitted-table prior: predicted per-dispatch seconds for one
    # explicit mode combo at the bench shape, priced through the
    # layered cost table (file/live calibration when present)
    platform = execution_platform()
    w_count = spec.downsample.window_spec.count
    edges = w_count + 1

    def predict_combo(scan=None, search=None, extreme=None,
                      group=None) -> float:
        parts = [cm.predict_search(search or "scan", S, N, edges,
                                   platform)]
        if extreme is not None:
            parts.append(cm.predict_extreme(extreme, S, N, edges,
                                            platform))
        else:
            parts.append(cm.predict_scan(scan or "flat", S, N, edges,
                                         platform))
        parts.append(cm.predict_group(group or "segment", S, w_count,
                                      GROUPS, platform))
        return sum(parts)

    def keep_best(axis: str, cands: list, key) -> list:
        """--prune: race only the prune best-predicted candidates of
        one kernel axis; announce every drop (no silent caps)."""
        if prune is None or len(cands) <= prune:
            return cands
        ordered = sorted(cands, key=lambda c: predict_combo(**key(c)))
        for dropped in ordered[prune:]:
            print(json.dumps({
                "config": "%s (pruned)" % dropped[0] if
                isinstance(dropped, tuple) else "%s (pruned)" % dropped,
                "axis": axis, "pruned_by_prior": True,
                "predicted_s": round(predict_combo(**key(dropped)), 4),
                "calibration": cm.calibration_source(platform),
                "device": device,
            }), flush=True)
            bench._note("%s: pruned by the fitted prior" % (dropped,))
        return ordered[:prune]

    def race(name: str, setup, pipeline_spec, use_batch=None,
             use_wargs=None, modes: dict | None = None) -> None:
        """One isolated race row: a candidate that fails to compile or
        dispatch prints an error row and the race continues — one bad
        candidate must not cost the remaining rows (the setters below
        always run from the restored default state) — but the run then
        exits non-zero."""
        restore_defaults()
        b = batch if use_batch is None else use_batch
        w = wargs if use_wargs is None else use_wargs
        prior = {}
        if modes is not None:
            prior = {"predicted_s": round(predict_combo(**modes), 4),
                     "calibration": cm.calibration_source(platform)}
        try:
            setup()
            drain(dispatch(pipeline_spec, g_pad, b, w,
                           origins.next()))           # compile + warm
            samples, _, _ = measure_drained(pipeline_spec, g_pad, b,
                                            w, origins)
            per = _median(samples)
        except Exception as e:   # noqa: BLE001 — the row boundary: the
            # failure is printed, counted, and fails the run at its end
            print(json.dumps({"config": name,
                              "error": "%s: %s" % (type(e).__name__, e),
                              "device": device, **prior}),
                  flush=True)
            bench._note("%s FAILED: %s" % (name, e))
            failed.append(name)
            return
        print(json.dumps({
            "config": name,
            "s_per_dispatch": round(per, 4),
            "dp_per_sec": round(S * N / per, 1),
            "device": device,
            **prior,
        }), flush=True)
        bench._note("%s: %.4fs/dispatch" % (name, per))

    # Batch-layout evidence rows on the ABSOLUTE-int64 batch (the
    # host-build layout): raw int64 end-to-end vs per-dispatch int32
    # compaction (the r3 production path).  These quantify what the
    # pre-compacted ts_base layout saves; the default rows below all
    # ride the pre-compacted int32 batch (the cache-hit layout bench.py
    # measures) where per-dispatch compaction is already gone.
    for name, compact in [("flat+int64raw", False),
                          ("flat+int64+dispatchcompact", True)]:
        def setup(c=compact):
            ds.set_ts_compaction(c)
        race(name, setup, spec, use_batch=batch64, use_wargs=wargs64,
             modes={"scan": "flat"})

    # scan mode x accumulation precision on the pre-compacted batch.
    # "subblock" is the r4 chip-attribution lever: no full-length f64
    # scan at all — sub-block f64 reduces + tiny cumsum + 32-wide
    # remainder dots.  The f32 row is evidence-only (breaks the
    # Java-double parity contract).
    scan_rows = keep_best(
        "scan",
        [("flat+int32", "flat", "double"),
         ("blocked+int32", "blocked", "double"),
         ("subblock+int32", "subblock", "double"),
         ("subblock2+int32", "subblock2", "double"),
         ("blocked+int32+f32", "blocked", "single")],
        key=lambda c: {"scan": c[1]})
    for name, mode, precision in scan_rows:
        def setup(m=mode, p=precision):
            ds.set_scan_mode(m)
            ds.set_value_precision(p)
        race(name, setup, spec, modes={"scan": mode})

    # edge-search strategy at the flat+int32 config: binary search
    # (log2(N) gather rounds) vs compare_all (fused compare+reduce) vs
    # hier (sub-block firsts + 32-wide remainder — 1/32 the compares).
    for smode in keep_best("search", ["scan", "compare_all", "hier"],
                           key=lambda m: {"search": m}):
        race("flat+int32+search_" + smode,
             lambda m=smode: ds.set_search_mode(m), spec,
             modes={"search": smode})

    # min/max strategy: full-length reset-scan vs segment scatter vs the
    # r4 sub-block decomposition.
    for emode in keep_best("extreme", ["scan", "segment", "subblock"],
                           key=lambda m: {"extreme": m}):
        race("min+extreme_" + emode,
             lambda m=emode: ds.set_extreme_mode(m), spec_min,
             modes={"extreme": emode})

    # group-reduce strategy: segment scatter vs one-hot matmul (MXU) vs
    # sorted contiguous-run reset-scans (r4) vs the r5 blocked
    # level-masked fold with int32 counts ("sorted2").
    for gmode in keep_best("group",
                           ["segment", "matmul", "sorted", "sorted2"],
                           key=lambda m: {"group": m}):
        race("flat+int32+group_" + gmode,
             lambda m=gmode: ga.set_group_reduce_mode(m), spec,
             modes={"group": gmode})

    # r4 compositions: the attribution-driven levers together and in
    # pairs — fusion can interact, and pick_winners only ever feeds
    # forward MEASURED rows, so the pairs are the fallbacks if the full
    # combo regresses on one member.
    def combo(scan=None, search=None, group=None):
        def setup():
            if scan:
                ds.set_scan_mode(scan)
            if search:
                ds.set_search_mode(search)
            if group:
                ga.set_group_reduce_mode(group)
        return setup

    race("subblock+int32+hier", combo("subblock", "hier"), spec,
         modes={"scan": "subblock", "search": "hier"})
    race("subblock+int32+sorted", combo("subblock", group="sorted"), spec,
         modes={"scan": "subblock", "group": "sorted"})
    race("flat+int32+hier+sorted", combo(search="hier", group="sorted"),
         spec, modes={"search": "hier", "group": "sorted"})
    race("subblock+int32+hier+sorted",
         combo("subblock", "hier", "sorted"), spec,
         modes={"scan": "subblock", "search": "hier", "group": "sorted"})
    race("subblock2+int32+hier+sorted",
         combo("subblock2", "hier", "sorted"), spec,
         modes={"scan": "subblock2", "search": "hier",
                "group": "sorted"})
    race("subblock+int32+hier+sorted2",
         combo("subblock", "hier", "sorted2"), spec,
         modes={"scan": "subblock", "search": "hier",
                "group": "sorted2"})
    race("subblock2+int32+hier+sorted2",
         combo("subblock2", "hier", "sorted2"), spec,
         modes={"scan": "subblock2", "search": "hier",
                "group": "sorted2"})

    # the shape-driven cost model's own pick (ops/costmodel.py "auto"):
    # racing it against the explicit rows shows on-chip whether the
    # chooser lands on the winner without being crowned
    race("auto+int32", combo("auto", "auto", "auto"), spec)

    restore_defaults()
    if failed:
        bench._note("%d race row(s) failed: %s"
                    % (len(failed), ", ".join(failed)))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

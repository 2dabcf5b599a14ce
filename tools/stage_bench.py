"""Stage-decomposition bench: attribute the headline dispatch's time.

The full production dispatch (bench.py shape: 1024x65536, avg-1h, 100
groups) ran ~0.59s in an earlier chip session while its theoretical
bandwidth cost is ~10ms — a ~300x gap that neither precision (f32 saved
8%) nor scan form (flat vs blocked within 5%) explained; none of it is
re-measured on this installation (ROADMAP A4/A6).  This bench times each
pipeline stage as its own jitted dispatch, plus raw primitives as
bandwidth yardsticks, with bench.py's timing rules (every sample ends in
`jax.block_until_ready`):

    python tools/stage_bench.py            # fails without a TPU
    python tools/stage_bench.py --platform cpu   # explicit CPU dry run

Prints one JSON line per stage, each naming the device; a stage that
fails is reported and the run exits non-zero.  Stage sum > full-pipeline time is
expected (XLA fuses across stage boundaries in the real program); the
value is the RANKING — whichever stage dominates is the rework target.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

import bench
from bench import (_OriginSequence, build_spec, drain, make_batch,
                   _median, S, N, INTERVAL_MS)


def _note(msg: str) -> None:
    print("[stages] " + msg, file=sys.stderr, flush=True)


def time_fn(fn, args, reps=3):
    """Median synced time of fn(*args) after a compile+warm call."""
    drain(fn(*args))            # compile
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        drain(fn(*args))
        times.append(time.perf_counter() - t0)
    return _median(times)


def main() -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    bench.add_platform_arg(ap)
    device = bench.require_device(ap.parse_args().platform)

    import jax
    import jax.numpy as jnp
    from opentsdb_tpu.ops import downsample as ds

    # Stages time EXPLICIT kernel forms; the platform guard would demote
    # the dense search forms on a CPU dev box and mislabel the rows (a
    # no-op on the chip).
    ds.set_platform_mode_guard(False)

    batch = make_batch()
    _note("batch resident")
    spec, wargs, g_pad = build_spec()
    origins = _OriginSequence()
    ts, val, mask, gid = batch
    window_spec = spec.downsample.window_spec
    w = window_spec.count

    # Host-computed fixtures reused by isolated stages
    first = wargs["first"]
    cts, cedges = jax.jit(lambda t: ds._compact_ts(t, window_spec, wargs))(ts)
    idx = jax.jit(lambda t, e: jax.vmap(
        lambda row: jnp.searchsorted(row, e, side="left"))(t))(cts, cedges)
    drain((cts, cedges, idx))

    recorded: dict[str, float] = {}
    failed = False

    def record(name, t, points=None):
        # one JSON line per stage, emitted IMMEDIATELY: a chip crash in a
        # later stage must not lose earlier attributions (the reason this
        # tool exists)
        pts = S * N if points is None else points
        recorded[name] = t
        print(json.dumps({"stage": name, "seconds": round(t, 4),
                          "dp_per_sec": round(pts / t, 1),
                          "device": device}), flush=True)
        _note("%s: %.4fs" % (name, t))

    # raw primitives: bandwidth yardsticks
    record("prim_f64_mul", time_fn(
        jax.jit(lambda v: v * 1.000001), (val,)))
    record("prim_f64_cumsum", time_fn(
        jax.jit(lambda v: jnp.cumsum(v, axis=1)), (val,)))
    record("prim_f32_cumsum", time_fn(
        jax.jit(lambda v: jnp.cumsum(v.astype(jnp.float32), axis=1)),
        (val,)))
    record("prim_i64_sub", time_fn(
        jax.jit(lambda t: t - first), (ts,)))
    record("prim_gather_edges", time_fn(
        jax.jit(lambda c, i: jnp.take_along_axis(c, i, axis=1)),
        (jnp.cumsum(val, axis=1), jnp.clip(idx, 0, N - 1))))

    # pipeline stages in production order
    record("compact_ts", time_fn(
        jax.jit(lambda t: ds._compact_ts(t, window_spec, wargs)), (ts,)))
    record("searchsorted", time_fn(
        jax.jit(lambda t, e: jax.vmap(
            lambda row: jnp.searchsorted(row, e, side="left"))(t)),
        (cts, cedges)))

    # r4 attribution-driven forms, timed beside the originals
    import contextlib

    @contextlib.contextmanager
    def forced_mode(module, attr, value):
        """Trace-time module-global kernel-mode swap with restore (the
        modes are read when jit traces, inside the with-block)."""
        prev = getattr(module, attr)
        setattr(module, attr, value)
        try:
            yield
        finally:
            setattr(module, attr, prev)

    def search_hier(t, e):
        with forced_mode(ds, "_SEARCH_MODE", "hier"):
            return ds._edge_search(t, e)

    record("searchsorted_hier", time_fn(
        jax.jit(search_hier), (cts, cedges)))

    def windowed_avg(v, m, i):
        builder = ds._edge_prefix_builder(S, N, i)
        ok = m & ~jnp.isnan(v)
        count = builder(ok.astype(jnp.int32))
        total = builder(jnp.where(ok, v, 0.0))
        return total / jnp.maximum(count, 1)

    record("windowed_avg_given_idx", time_fn(
        jax.jit(windowed_avg), (val, mask, idx)))

    def windowed_avg_subblock(v, m, i):
        builder = ds._edge_subblock_builder(S, N, i)
        ok = m & ~jnp.isnan(v)
        count = builder(ok.astype(jnp.int32))
        total = builder(jnp.where(ok, v, 0.0))
        return total / jnp.maximum(count, 1)

    record("windowed_avg_subblock", time_fn(
        jax.jit(windowed_avg_subblock), (val, mask, idx)))

    # Decompose the subblock windowed-sum (88ms r04b, the biggest
    # accurately-measured single stage): the [S, nb, K] tree reduce vs
    # the tiny cumsum vs the [S, W+1, K] boundary gather + masked dot.
    # Bandwidth yardstick: prim_f64_mul touches the same 537MB in ~18ms,
    # so whichever row exceeds that is compute/serialization, not HBM.
    k_sub = ds._SUB_K
    nb = N // k_sub
    reduce_fn = jax.jit(lambda v: v.reshape(S, nb, k_sub).sum(axis=2))
    record("subblock_reduce", time_fn(reduce_fn, (val,)))
    ssum0 = reduce_fn(val)
    drain((ssum0,))
    record("subblock_cumsum", time_fn(
        jax.jit(lambda x: jnp.cumsum(x, axis=1)), (ssum0,)))

    def subblock_remainder(v, i):
        blk = i // k_sub
        off = i - blk * k_sub
        safe_blk = jnp.clip(blk, 0, nb - 1)
        d3 = v.reshape(S, nb, k_sub)
        bvals = jnp.take_along_axis(d3, safe_blk[:, :, None], axis=1)
        lanes = jnp.arange(k_sub, dtype=off.dtype)
        return jnp.where(lanes[None, None, :] < off[:, :, None],
                         bvals, 0).sum(axis=2)

    record("subblock_remainder", time_fn(
        jax.jit(subblock_remainder), (val, idx)))

    def full_downsample(t, v, m):
        return ds.downsample(t, v, m, "avg", window_spec, wargs)

    record("downsample_full", time_fn(
        jax.jit(full_downsample), (ts, val, mask)))

    from opentsdb_tpu.ops.group_agg import grid_group_aggregate
    from opentsdb_tpu.ops.aggregators import get_agg
    wts0, dval, dmask = jax.jit(full_downsample)(ts, val, mask)
    drain((wts0, dval, dmask))
    agg_sum = get_agg("sum")
    record("group_tail", time_fn(
        jax.jit(lambda g, v, m, gi: grid_group_aggregate(
            g, v, m, gi, g_pad, agg_sum)),
        (wts0, dval, dmask, jnp.asarray(gid))))

    from opentsdb_tpu.ops import group_agg as ga

    def group_tail_sorted(g, v, m, gi):
        with forced_mode(ga, "_GROUP_REDUCE_MODE", "sorted"):
            return grid_group_aggregate(g, v, m, gi, g_pad, agg_sum)

    record("group_tail_sorted", time_fn(
        jax.jit(group_tail_sorted), (wts0, dval, dmask, jnp.asarray(gid))))

    # Decompose the group tail (~180ms measured r04b on [1024, 512]
    # grids whose raw traffic is ~2MB — three orders of magnitude above
    # bandwidth cost; these rows find where it actually goes):
    # interpolation machinery vs each reduce mode vs the raw reset-scan
    # primitive the sorted mode leans on.
    from opentsdb_tpu.ops.group_agg import (grid_contributions,
                                            moment_group_reduce,
                                            _SortedGroups)
    gid_arr = jnp.asarray(gid)
    # same f64 cast grid_group_aggregate applies before the call — the
    # stage must time the program the pipeline actually runs, including
    # under the single-precision A/B mode
    contrib_fn = jax.jit(lambda g, v, m: grid_contributions(
        g, v.astype(jnp.float64), m, agg_sum))
    record("group_contrib", time_fn(contrib_fn, (wts0, dval, dmask)))
    contrib, participate, _dense = contrib_fn(wts0, dval, dmask)
    drain((contrib, participate))

    def reduce_under(mode):
        def run(c, p, gi):
            with forced_mode(ga, "_GROUP_REDUCE_MODE", mode):
                return moment_group_reduce("sum", c, p, gi, g_pad)
        return run

    for mode in ("segment", "matmul", "sorted", "sorted2"):
        record("group_reduce_" + mode, time_fn(
            jax.jit(reduce_under(mode)), (contrib, participate, gid_arr)))

    def raw_reset_scan(c, gi):
        sg = _SortedGroups(gi, g_pad, c.shape[0])
        return sg.sum(c.astype(jnp.float64))

    record("group_raw_reset_scan", time_fn(
        jax.jit(raw_reset_scan), (contrib, gid_arr)))

    from bench import dispatch
    record("full_pipeline", time_fn(
        lambda *a: dispatch(spec, g_pad, batch, wargs, origins.next()),
        ()))

    # Streamed chunk fold at the config-2 slice shape: a [128, 65536]
    # chunk against its ~82k-window local slice (W ~ 1.25N).  The
    # _use_segment_chunk threshold routes W > N to segment reductions
    # (TPU scatters serialize) — these rows race that against the dense
    # edge-search form so the threshold gets chip data.
    from opentsdb_tpu.ops import streaming as st
    from opentsdb_tpu.ops.downsample import FixedWindows

    s2, n2 = 128, 65_536
    step2 = 10_000
    start2 = 1_356_998_400_000
    # The production sliced fold runs on an UNPADDED quantized local
    # grid (streaming.quantize_window_slice: 65,538-window chunk span ->
    # wc = 81,920); pow2-padding the spec here (131,072) would measure
    # 2N windows instead of the 1.25N the planner actually dispatches.
    fixed2 = FixedWindows.for_range(start2, start2 + n2 * step2 + step2,
                                    10_000)
    wc2 = st.quantize_window_slice(fixed2.count,
                                   ds.WindowSpec("fixed", 1 << 20,
                                                 10_000))
    wspec2 = ds.WindowSpec("fixed", wc2, 10_000)
    wargs2 = {"first": jnp.asarray(fixed2.first_window_ms, jnp.int64),
              "nwin": jnp.asarray(fixed2.count, jnp.int32)}
    rows2 = jnp.arange(s2, dtype=jnp.int64)
    cols2 = jnp.arange(n2, dtype=jnp.int64)
    h2 = (rows2[:, None] * 2_654_435_761 + cols2[None, :] * 40_503) \
        & 0x7FFFFFFF
    ts2 = start2 + cols2[None, :] * step2 + h2 % 4_000
    val2 = 100.0 + (h2 % 1_000).astype(jnp.float64) * 0.05
    mask2 = jnp.ones((s2, n2), bool)
    drain((ts2, val2, mask2))
    lanes2 = st.lanes_for(["sum", "min", "max", "count"])

    def chunk_segment(t, v, m):
        return st._segment_chunk_moments(t, v, m, wspec2, wargs2, lanes2)

    record("stream_chunk_segment", time_fn(
        jax.jit(chunk_segment), (ts2, val2, mask2)),
        points=s2 * n2)

    def chunk_dense_forced(t, v, m):
        # bypass _use_segment_chunk: same lanes through the edge-search
        # machinery (prefix sums + reset-scan extremes)
        vf, ok, cts_l, idx_l, windowed, cnt = ds._window_scan_setup(
            t, v, m, wspec2, wargs2)
        out = {"n": cnt, "total": windowed(jnp.where(ok, vf, 0.0))}
        lo, hi, _ = ds._extreme_downsample(t, v, m, wspec2, wargs2,
                                           True, True)
        out["lo"], out["hi"] = lo, hi
        return out

    record("stream_chunk_dense", time_fn(
        jax.jit(chunk_dense_forced), (ts2, val2, mask2)),
        points=s2 * n2)

    # FULL production sliced update at the config-2 shape — chunk
    # moments PLUS the donated-state slice merge, dynamic_update_slice
    # write-back, and oob audit the chunk rows above exclude.  If config
    # 2's observed per-chunk cost exceeds the winning chunk-moments row,
    # the difference lives here.  State is threaded (donation consumes
    # the input buffers), so each rep folds into the previous rep's
    # state exactly like the production loop.
    try:
        full_spec = ds.WindowSpec("fixed", 1 << 20, 10_000)
        full_wargs = {"first": jnp.asarray(start2 - (1 << 19) * 10_000,
                                           jnp.int64),
                      "nwin": jnp.asarray(1 << 20, jnp.int32)}
        acc2 = st.StreamAccumulator.create(
            s2, full_spec, full_wargs, lanes=lanes2,
            window_slice=fixed2.count)
        w0_mid = 1 << 19
        acc2.update(ts2, val2, mask2, w0=w0_mid)       # compile + warm
        acc2.oob_count()                               # force the queue
        reps, t0 = 3, time.perf_counter()
        for _ in range(reps):
            acc2.update(ts2, val2, mask2, w0=w0_mid)
            acc2.oob_count()
        per = (time.perf_counter() - t0) / reps
        record("stream_sliced_update", per, points=s2 * n2)
    except Exception as e:   # noqa: BLE001 — keep later stages alive;
        # the failure is reported and fails the run at its end
        _note("stream_sliced_update FAILED: %s" % e)
        failed = True

    # ---- cost-model calibration (ops/costmodel.py) -------------------
    # Convert THIS session's stage timings into the per-unit costs the
    # shape-driven mode chooser uses, so auto-selection follows the chip
    # actually measured rather than the constants in the source.  Never
    # emitted on CPU (a dry run must not masquerade as chip calibration).
    if device["platform"] != "cpu":
        import numpy as _np
        e_cnt = int(cedges.shape[0])
        logn = max(int(_np.ceil(_np.log2(max(N, 2)))), 1)
        denoms = {
            "gather_round": ("searchsorted", S * e_cnt * logn),
            "hier_cell": ("searchsorted_hier",
                          S * ((N // 32) + 32) * e_cnt),
            "scan_f64": ("prim_f64_cumsum", S * N),
            "elem_f64": ("prim_f64_mul", S * N),
            "win_gather": ("prim_gather_edges", S * e_cnt),
            "seg_scatter": ("group_reduce_segment", S * w),
            "mxu_cell": ("group_reduce_matmul", g_pad * S * w),
            "sorted_grid": ("group_reduce_sorted", S * w),
            "sorted2_grid": ("group_reduce_sorted2", S * w),
        }
        costs = {key: recorded[label] / denom
                 for key, (label, denom) in denoms.items()
                 if label in recorded and recorded[label] > 0}
        if costs:
            print(json.dumps({"stage": "calibration", "device": device,
                              "costs_tpu": {k: float("%.4g" % v)
                                            for k, v in costs.items()}}),
                  flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

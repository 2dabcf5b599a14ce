"""Shape-driven kernel-form selection: one static cost table.

For each kernel axis (edge search, prefix scan, extreme reduce, group
reduce) predict the per-dispatch cost of every feasible form from the
dispatch shape and the execution platform, and take the argmin.
Feasibility (memory caps, divisibility, platform hazards) stays with the
kernels in downsample.py/group_agg.py — this module only ranks the forms
those guards admit, so a wrong prediction can cost a few x, never an OOM
or a compile failure.

Which form runs is a pure function of (platform, shape): the table below
is the only input besides the shape, nothing overrides it at run time,
and no compiled program ever has to be dropped because a choice moved.
To change a constant: race the forms on the benchmark cells' own shapes
on the chip, edit the constant here, and tests/test_kernel_choice.py
says which cells' programs move (docs/costmodel.md).

The decisions this table reproduces (chip sessions named per constant
below; CPU anchors are the dev box):
  * search: hier (20ms) < compare_all (116ms) < binary scan (154ms) on
    the chip at the headline shape; binary everywhere on CPU (the dense
    compare materializes there — measured 18-70x slower).
  * prefix scan: subblock windowed-sum (88ms) < flat (130ms) on the
    chip (the full-length emulated-f64 cumsum is the cost, 100ms vs
    3ms for 1/32-length) — and subblock wins on CPU too, 5.5x: the XLA
    CPU cumsum is a SERIAL scalar loop (measured on the config-1 shape,
    [1, 2^20]: 8.8ms cumsum vs 0.97ms elementwise; full avg path 2.1ms
    subblock / 11.6 flat / 9.4 subblock2 — subblock2's within-block
    inclusive-prefix pass is flat-class on CPU, so it gets its own
    per-element constant).
  * extremes: reset-scan (0.5245s/dispatch) < subblock (0.8282 — its
    per-edge boundary-lane reduces outweigh the shorter scan at the
    headline W) << segment scatter (7.161) on the chip; the scatter is
    cheap on CPU.
  * group reduce: the serializing segment scatter (219ms) loses on the
    chip to the one-hot MXU matmul (~100ms at G=100) and the sorted
    reset-scan (~90ms, G-independent); matmul's cost grows linearly in
    G so large-G queries flip to sorted.  CPU keeps segment.

Every `predict_*` is a LINEAR form: a dot product of a per-form feature
vector (unit counts — gather rounds, scanned elements, scattered cells;
`features_*` below) with the per-unit cost table.  What a dispatch
really took on the device is the device trace's (benchmark `--trace 1`).

Reference being outperformed: the per-datapoint iterator stack
(/root/reference/src/core/AggregationIterator.java:514,
Downsampler.java:292) has exactly one "mode"; this module exists
because the TPU-first kernel space has several and the fastest one is
shape-dependent.
"""

from __future__ import annotations

import math

# --------------------------------------------------------------------- #
# Calibrated per-unit costs, seconds.  Anchors (headline shape S=1024
# N=65536 E=514 G=100 W=512; from an earlier chip session, not
# re-measured on this installation — ROADMAP C1):
#   gather_round  0.154s / (S*E*log2(N)=8.42e6)      binary search stage
#   cmp_cell      0.116s / (S*N*E=3.45e10)           compare_all stage
#   hier_cell     0.020s / (S*(N/32)*E=1.08e9)       hier stage
#   scan_f64      0.100s / (S*N=6.71e7)              f64 cumsum stage
#   elem_f64      0.018s / (S*N=6.71e7)              raw f64 elementwise
#   win_gather    (0.130-0.100)s / (S*E=5.26e5)      flat windowed-sum
#                                                    minus its cumsum
#   seg_scatter, mxu_cell, sorted_grid: re-anchored on this
#   installation's v5e by PR 27's race of ops/group_agg.py's
#   grid_group_aggregate (PERF.md section 6; median ms of 7 calls, the
#   whole tail at the shape, rows sorted by group):
#     [S, W] -> G (padded)       segment   sorted   matmul
#     [100 000, 16] -> 9 (16)     347.5     11.9     17.7   sum
#     [4000, 128]   -> 9 (16)      81.9      1.23     5.94  sum
#     [100 000, 8]  -> 10^5 (2^17) 128.3    38.6      -     avg
#     [4000, 16]    -> 4000 (2^12)  11.4     2.41     -     avg
#   seg_scatter 1.6-2.2e-7 per S*W at all four; mxu_cell 6.9-7.3e-10
#   per G*S*W at both; sorted 4.8e-8 and 3.8e-8 per S*W where G ~ S
#   and 7.5e-9 / 2.4e-9 at G = 9.  sorted beats segment at every raced
#   shape (3-67x) and, in run time, matmul at G = 9 (1.5x / 4.8x) —
#   but in the served pipeline its program took XLA:TPU ~10 minutes to
#   compile at [100 000, 16] (a cold cell's set-up 1339 s against 431
#   with matmul there, which compiles in 7 s), for 6 ms of a 250 ms
#   request.  So sorted_grid is anchored at its worst raced shape
#   ([100 000, 8] -> 131 072: 38.6 ms), which ranks it under segment
#   everywhere and keeps matmul below G ~ 68, as before.
#   ext_scan      0.52s/dispatch vs ext_segment 7.09s — modeled per
#                 grid element over S*N
# CPU anchors are this dev box (differential suite timings): searchsorted
# ~2e-8/unit, native cumsum ~1.5e-9/elem, scatters ~5e-9/elem; the
# dense-compare materialization hazard is handled by the search
# chooser itself (binary search on CPU), not by the model.
# --------------------------------------------------------------------- #

DEFAULT_COSTS: dict[str, dict[str, float]] = {
    "tpu": {
        "gather_round": 1.83e-8,
        "cmp_cell": 3.36e-12,
        "hier_cell": 1.87e-11,
        "scan_f64": 1.49e-9,
        "elem_f64": 2.7e-10,
        # within-block prefix pass: priced slightly ABOVE elem_f64 so
        # the chip-race-crowned subblock stays the pick on TPU wherever
        # its [S, W, K] intermediate fits, until a chip race measures
        # subblock2 faster (its CPU prefix pass is 8x elem-cost — the
        # chip may disappoint too)
        "sub2_elem": 3.5e-10,
        "win_gather": 5.7e-8,
        "seg_scatter": 1.8e-7,
        "mxu_cell": 7.0e-10,
        "sorted_grid": 4.8e-8,
        # one member a group (group_agg form "rows"): 1.19 ms at
        # [100 000, 8] in the same race, fixed costs included
        "rows_grid": 1.5e-9,
        "ext_scan_elem": 6.0e-9,
        "ext_seg_elem": 1.06e-7,
        "ext_boundary_cell": 4.0e-8,
        # out-of-core tiling (ops/tiling.py): partial-grid spill-pool
        # write/read seconds per MB (host memcpy + the disk-overflow
        # share at the default pool split) and the per-dispatch overhead of
        # a tiled plan's extra launches (chunk folds, finishes,
        # stripe tails).  ESTIMATES until a chip run records the
        # host<->device transfer reality; the tiled decision only ever
        # arbitrates tiled-vs-refuse, so a bad constant costs admission
        # accuracy, never a wrong answer.
        "spill_write_mb": 6.0e-4,
        "spill_read_mb": 4.0e-4,
        "tile_dispatch": 1.5e-3,
        # rollup lanes (storage/rollup.py): host-side lane-cell
        # assembly+re-reduce seconds per MB of cells touched, and the
        # per-(series, cell) cost of a maintenance block build (the
        # Storyboard selection prices build amortization with it).
        # ESTIMATES; a bad constant skews which lanes materialize, never
        # an answer.
        "lane_assemble_mb": 2.5e-4,
        "lane_build_cell": 2.0e-9,
        # fused multi-query dispatch (query/batcher.py): the per-
        # dispatch floor a stacked [Q, S, W] launch amortizes away
        # (host->device round trip + XLA launch — the quantity the batcher
        # exists to stop paying Q times), and the per-cell host cost
        # of stacking a member's [S, N] batch in + unpacking its
        # [G, W] slice out.  ESTIMATES; a bad constant skews the
        # coalesce-vs-dispatch-now line, never an answer.
        "stacked_dispatch": 1.5e-3,
        "stacked_cell": 1.0e-9,
    },
    "cpu": {
        "gather_round": 2.0e-8,
        "cmp_cell": 1.0e-9,      # materializes; feasibility-capped anyway
        "hier_cell": 1.0e-9,
        # XLA's CPU cumsum lowers to a SERIAL scalar loop: measured
        # 8.8ms over 2^20 f64 (8.4e-9/elem) while an elementwise pass
        # streams the same data in 0.97ms — the subblock form's
        # 1/32-length scan is therefore a ~6x win on the host as well
        "scan_f64": 8.4e-9,
        "elem_f64": 1.0e-9,
        # subblock2's within-block inclusive prefixes are flat-class on
        # CPU (measured 9.4ms vs subblock's 2.1 on the config-1 shape)
        "sub2_elem": 8.0e-9,
        "win_gather": 2.0e-8,
        "seg_scatter": 5.0e-9,   # CPU scatters are cheap
        "mxu_cell": 1.0e-9,      # no MXU: dense [G,S]x[S,W] is real FLOPs
        "sorted_grid": 1.0e-8,
        "rows_grid": 1.0e-9,     # a copy: elementwise class

        "ext_scan_elem": 4.0e-9,
        "ext_seg_elem": 2.0e-9,
        "ext_boundary_cell": 2.0e-8,
        # spill pool on the host platform: same memcpy, no device hop
        "spill_write_mb": 4.0e-4,
        "spill_read_mb": 3.0e-4,
        "tile_dispatch": 3.0e-4,
        # rollup lanes: same host memcpy either platform
        "lane_assemble_mb": 2.5e-4,
        "lane_build_cell": 2.0e-9,
        # stacked dispatch: the CPU jit-launch floor is smaller than
        # an accelerator's but still dwarfs a small query's compute
        # (~0.3 ms/dispatch measured on this dev box); stacking cells
        # is host memcpy either platform
        "stacked_dispatch": 3.0e-4,
        "stacked_cell": 1.0e-9,
    },
}

# The per-unit cost TERMS — identical key set on every platform
# (asserted at import so a new term cannot be priced on one platform
# and silently missing on the other).
COST_TERMS: tuple[str, ...] = tuple(sorted(DEFAULT_COSTS["tpu"]))
assert tuple(sorted(DEFAULT_COSTS["cpu"])) == COST_TERMS


def costs(platform: str) -> dict[str, float]:
    """Per-unit costs for a platform.  A platform without a table is an
    error: pricing an unknown device with the TPU constants would hide
    that the daemon is not running where it was deployed.  Callers must
    treat the result as read-only."""
    if platform not in DEFAULT_COSTS:
        raise ValueError(
            "no cost table for platform %r (known: %s)"
            % (platform, ", ".join(sorted(DEFAULT_COSTS))))
    return DEFAULT_COSTS[platform]


def _argmin(mode_costs: dict[str, float]) -> str:
    """The cheapest form; ties go to the earlier candidate."""
    return min(mode_costs, key=mode_costs.get)


def _log2(n: int) -> int:
    return max(int(math.ceil(math.log2(max(n, 2)))), 1)


def _dot(features: dict[str, float], platform: str) -> float:
    c = costs(platform)
    return sum(units * c[term] for term, units in features.items())


# --------------------------------------------------------------------- #
# Feature vectors: unit counts per (kernel axis, mode).                 #
#                                                                       #
# predict_* == dot(features_*, costs) BY CONSTRUCTION: a constant       #
# re-anchored from a chip race means exactly what the predictor         #
# consumes.  Keep every form LINEAR in the constants.                   #
# --------------------------------------------------------------------- #

_SUB_K = 32     # sub-block lane width, mirrored from ops.downsample


def features_search(mode: str, s: int, n: int, e: int
                    ) -> dict[str, float]:
    """Unit counts for one edge search: idx[S, E] from [S, N] sorted
    timestamps."""
    if mode == "scan":
        return {"gather_round": float(s * e * _log2(n))}
    if mode == "compare_all":
        return {"cmp_cell": float(s * n * e)}
    if mode == "hier":
        k = _SUB_K
        return {"hier_cell": float(s * ((n // k) + k) * e)}
    raise ValueError("unknown search mode: " + mode)


def features_scan(mode: str, s: int, n: int, e: int) -> dict[str, float]:
    """Unit counts for one windowed-sum pass over [S, N]."""
    if mode == "flat":
        return {"scan_f64": float(s * n), "win_gather": float(s * e)}
    if mode == "subblock":
        k = _SUB_K
        return {"elem_f64": float(s * n + s * e * k),  # reduce + remainder
                "scan_f64": float(s * (n // k)),       # 1/32-length cumsum
                "win_gather": float(s * e)}
    if mode == "subblock2":
        k = _SUB_K
        # within-block inclusive prefixes (block sums fall out of the
        # last lane) + ONE element gather per edge — no [S, E, K]
        # remainder intermediate, but the prefix pass has its own
        # platform-dependent cost (serial-ish on CPU)
        return {"sub2_elem": float(s * n),
                "scan_f64": float(s * (n // k)),
                "win_gather": float(s * e)}
    raise ValueError("unknown scan mode: " + mode)


def features_extreme(mode: str, s: int, n: int, e: int
                     ) -> dict[str, float]:
    """Unit counts for one min/max pass over [S, N]."""
    if mode == "scan":
        return {"ext_scan_elem": float(s * n)}
    if mode == "segment":
        return {"ext_seg_elem": float(s * n)}
    if mode == "subblock":
        k = _SUB_K
        # sub-block reduces + a 1/32-length reset-scan + per-edge
        # boundary-lane masked reduces (the term that loses it the
        # headline shape: measured 0.83 vs scan's 0.52 s/dispatch)
        return {"elem_f64": float(s * n),
                "ext_scan_elem": float(s * (n // k)),
                "ext_boundary_cell": float(s * e * k)}
    raise ValueError("unknown extreme mode: " + mode)


def features_group(mode: str, s: int, w: int, g: int
                   ) -> dict[str, float]:
    """Unit counts for one group reduce: [S, W] + gid[S] -> [G, W]."""
    if mode == "segment":
        return {"seg_scatter": float(s * w)}
    if mode == "matmul":
        return {"mxu_cell": float(g * s * w)}
    if mode == "sorted":
        return {"sorted_grid": float(s * w)}
    if mode == "rows":      # one member a group: a copy of the grid
        return {"rows_grid": float(s * w)}
    raise ValueError("unknown group mode: " + mode)


# -- edge search: idx[S, E] from [S, N] sorted timestamps -------------- #

def predict_search(mode: str, s: int, n: int, e: int,
                   platform: str) -> float:
    return _dot(features_search(mode, s, n, e), platform)


def choose_search(s: int, n: int, e: int, platform: str,
                  candidates: list[str]) -> str:
    return _argmin({m: predict_search(m, s, n, e, platform)
                    for m in candidates})


# -- prefix scan: windowed sums over [S, N] ---------------------------- #

def predict_scan(mode: str, s: int, n: int, e: int,
                 platform: str) -> float:
    return _dot(features_scan(mode, s, n, e), platform)


def choose_scan(s: int, n: int, e: int, platform: str,
                candidates: list[str]) -> str:
    return _argmin({m: predict_scan(m, s, n, e, platform)
                    for m in candidates})


# -- extreme (min/max) over [S, N] ------------------------------------- #

def predict_extreme(mode: str, s: int, n: int, e: int,
                    platform: str) -> float:
    return _dot(features_extreme(mode, s, n, e), platform)


def choose_extreme(s: int, n: int, e: int, platform: str,
                   candidates: list[str]) -> str:
    return _argmin({m: predict_extreme(m, s, n, e, platform)
                    for m in candidates})


# -- group reduce: [S, W] + gid[S] -> [G, W] --------------------------- #

def predict_group(mode: str, s: int, w: int, g: int,
                  platform: str) -> float:
    return _dot(features_group(mode, s, w, g), platform)


def choose_group(s: int, w: int, g: int, platform: str,
                 candidates: list[str]) -> str:
    return _argmin({m: predict_group(m, s, w, g, platform)
                    for m in candidates})


# -- out-of-core tiled execution (ops/tiling.py) ----------------------- #

def features_tiled(s: int, w: int, g: int, n_tiles: int, n_stripes: int,
                   spill_bytes: int, dispatches: int) -> dict[str, float]:
    """Unit counts for the tiled OVERHEAD of one [s, w] -> [g, w] plan:
    the spill-pool round trip of the full partial grid plus the extra
    launches a tiled plan issues (per-tile chunk folds + finishes, per-
    stripe tail dispatches).  The streamed compute itself is priced by
    the same stage features a resident plan uses (obs.jaxprof) — this
    vector is strictly the delta.  Linear in the constants
    by construction: `predict_tiled == dot(features_tiled, costs)`.
    """
    mb = spill_bytes / 2.0**20
    return {"spill_write_mb": mb,
            "spill_read_mb": mb,
            "tile_dispatch": float(max(dispatches,
                                       n_tiles + n_stripes))}


def predict_tiled(s: int, w: int, g: int, n_tiles: int, n_stripes: int,
                  spill_bytes: int, dispatches: int,
                  platform: str) -> float:
    """Predicted seconds of tiled-execution OVERHEAD (spill + extra
    dispatches) on top of the plan's ordinary compute prediction."""
    return _dot(features_tiled(s, w, g, n_tiles, n_stripes, spill_bytes,
                               dispatches), platform)


# -- rollup lanes (storage/rollup.py) ---------------------------------- #

# bytes per lane cell (sum f64 + count i32 + min f64 + max f64),
# mirrored from storage.rollup.LANE_CELL_BYTES without the import
# (storage stays numpy-only; a drift is a wrong estimate, not a wrong
# answer)
_LANE_CELL_BYTES = 28


def features_lane(s: int, w: int, k: int) -> dict[str, float]:
    """Unit counts for serving one [s series, w windows] grid from a
    rollup lane: the host assembly + k-cell re-reduce touches
    s * w * k cells.  The downsample/scan of the raw points — the term
    a lane hit ELIMINATES — is deliberately absent; the caller adds
    the tail stages (rate/group/aggregate) from the same
    stage_breakdown either side pays.  Linear in the constants:
    ``predict_lane == dot(features_lane, costs)``."""
    mb = s * w * max(k, 1) * _LANE_CELL_BYTES / 2.0 ** 20
    return {"lane_assemble_mb": mb}


def predict_lane(s: int, w: int, k: int, platform: str) -> float:
    """Predicted seconds of the lane-serve assembly for [s, w] at k
    cells per window."""
    return _dot(features_lane(s, w, k), platform)


def features_lane_build(s: int, cells: int) -> dict[str, float]:
    """Unit counts for one maintenance block build over s series x
    `cells` lane cells (the Storyboard selection's amortization
    side)."""
    return {"lane_build_cell": float(s * max(cells, 1))}


def predict_lane_build(s: int, cells: int, platform: str) -> float:
    return _dot(features_lane_build(s, cells), platform)


# -- fused multi-query dispatch (query/batcher.py) ---------------------- #

def features_stacked(q: int, s: int, n: int, w: int, g: int
                     ) -> dict[str, float]:
    """Unit counts for the batching OVERHEAD of one stacked [Q, S, W]
    dispatch: the single launch floor plus the host-side stack/unpack
    traffic (each member's [S, N] input cells copied into the stacked
    batch and its [G, W] output slice copied back out).  The members'
    compute itself is priced by the same stage features a solo plan
    uses (obs.jaxprof) — this vector is strictly the delta.  Linear in
    the constants by
    construction: ``predict_stacked == dot(features_stacked, costs)``.
    """
    return {"stacked_dispatch": 1.0,
            "stacked_cell": float(q * (s * n + g * w))}


def predict_stacked(q: int, s: int, n: int, w: int, g: int,
                    platform: str) -> float:
    """Predicted seconds of stacked-execution overhead (one launch
    floor + q members' stack/unpack traffic)."""
    return _dot(features_stacked(q, s, n, w, g), platform)


def coalesce_worthwhile(compute_s: float, s: int, n: int, w: int,
                        g: int, platform: str, factor: float) -> bool:
    """The coalesce-vs-dispatch-now verdict for ONE plan, from the
    table's constants (the Factor-Windows cost-based-rewrite framing:
    price the rewrite, don't hardcode a batch size).  A plan is
    DISPATCH-BOUND — worth stacking — when its predicted monolithic
    compute plus its per-member stack/unpack overhead stays within
    ``factor`` x the per-dispatch floor the stacking amortizes; a
    compute-bound plan gains nothing from sharing a launch and
    dispatches now.  Deterministic in (shape, cost table, factor), so
    the explain engine reaches the same verdict the executor does."""
    c = costs(platform)
    member_s = float(s * n + g * w) * c["stacked_cell"]
    return compute_s + member_s <= factor * c["stacked_dispatch"]

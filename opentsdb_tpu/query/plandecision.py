"""plan_decision(): the planner's routing verdict as ONE pure function.

Before this module the five-way fast-path arbitration (rollup lane vs
agg-cache rewrite vs tiled spill vs streamed vs resident, with the
mesh/host-lane/device-cache sub-choices) lived inline in
``QueryRunner._run_segment_grouped`` — executable, but not askable.
The EXPLAIN engine (query/explain.py, /api/query/explain) must answer
"which path would this query take, and why" WITHOUT dispatching, and
the only way report and execution provably cannot drift is the PR 6
convention applied to routing itself: one decision function, two
callers.

  * The EXECUTOR builds an ``ExecConsults``-style provider whose
    consult hooks do real work (``RollupLanes.plan`` with demand
    recording, ``AggCache.plan`` with repeat bookkeeping,
    ``DeviceSeriesCache.batch_for`` with the device gather) and
    dispatches on the returned :class:`PlanDecision`.
  * EXPLAIN builds a read-only provider (``observe=False`` consult
    arms, ``DeviceSeriesCache.peek``) and serializes the same
    :class:`PlanDecision` — same eligibility gates, same ordering,
    same ``grid_budget`` guard, same ``_effective_*`` choosers behind
    ``segment_decisions``.

Every decision carries a stable **plan fingerprint** — a hash over the
discrete routing facts (path, shapes, chosen kernel modes, lane/cache
verdicts; never raw milliseconds) — which the
executor stamps into the flight-recorder ``plan`` event and the
pipeline span, so explain-vs-actual parity is mechanically checkable
and ``PLAN_CORPUS.json`` can byte-pin the routing of a canonical query
matrix (tools/plan_corpus.py).
"""

from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass, field

from opentsdb_tpu.query.limits import GridBudgetDecision, grid_budget

# Paths whose dispatch runs the monolithic downsample/group kernels —
# the only paths whose per-axis kernel-mode decisions describe what
# actually executes (lane/tiled/agg-rewrite paths run their own
# programs); their fingerprints include the chosen modes.  "batched"
# is monolithic too: the stacked [Q, S, W] kernel vmaps the SAME
# grouped pipeline, and inside the vmap the mode choosers see the
# per-member [S, N] shapes a solo dispatch would.
MONOLITHIC_PATHS = frozenset(
    {"streamed", "resident", "host_lane", "mesh", "rollup_avg",
     "batched"})
# the paths that hand the whole [S, N] batch with its own gid to ONE
# grouped dispatch: where every group has one member, row i is group i
# there and the group reduce is a copy (PipelineSpec.row_groups)
ROW_GROUP_PATHS = frozenset({"resident", "host_lane"})


@dataclass(frozen=True)
class RouteContext:
    """Everything the routing verdict depends on, snapshotted once.

    The executor fills this from live config + the scan it just
    budgeted; explain fills the same fields from a read-only walk (and
    may override the config-derived ones — ``state_mb``, ``platform`` —
    for what-if analysis)."""
    seg_kind: str            # "raw" | "rollup" | "rollup_avg"
    ds_fn: str | None
    aggregator: str
    has_rate: bool
    s: int                   # series rows in the dispatch (len(gid))
    n_max: int               # max per-series point count, unpadded
    wp: int                  # padded window count (window_spec.count)
    groups: int              # group-by buckets kept (len(kept))
    g_pad: int               # padded group axis of the dispatch
    total_points: int
    sketchable: bool
    stream_ok: bool
    use_mesh: bool
    n_chips: int
    windows_fixed: bool      # isinstance(windows, FixedWindows)
    store_is_raw: bool       # store is tsdb.store
    has_store: bool
    platform: str            # execution_platform() (or a what-if)
    cpu_lane_ok: bool        # cpu_device() is not None
    state_mb: int
    point_threshold: int
    host_lane_max: int
    ts_base: int | None
    # fused multi-query dispatch (query/batcher.py): tsd.query.batch.*
    # enablement + the coalesce-pricing factor; the executor fills
    # these from live config, explain from the same keys, so the
    # `batched` arm cannot drift between them
    batch_ok: bool = False
    batch_factor: float = 0.0


@dataclass
class PlanDecision:
    """One grouped segment's complete routing verdict."""
    path: str
    would_stream: bool
    use_mesh: bool
    host_small: bool
    lane_small: bool
    gbd: GridBudgetDecision          # the governing budget decision
    grid_gbd: GridBudgetDecision     # the materialized-grid decision
    lane_plan: object = None
    lane_note: dict | None = None
    tiled_plan: object = None
    agg_plan: object = None
    agg_note: dict | None = None
    cached: object = None            # device batch (executor) / bool
    refusal: GridBudgetDecision | None = None
    decisions: dict | None = None    # per-axis kernel-mode decisions
    n_pad: int = 0
    g_dec: int = 0
    dec_platform: str = ""
    fp_fields: dict = field(default_factory=dict)
    fingerprint: str = ""


def acc_cell_bytes(ds_fn: str | None, sketchable: bool) -> int:
    """Streaming accumulator bytes per (series, window) cell — the ONE
    formula behind the streaming budget estimate, the tiled plan
    sizing, and admission's out-of-core pricing."""
    from opentsdb_tpu.ops.streaming import SKETCH_K, lanes_for
    return 8 + 8 * len(lanes_for([ds_fn])) \
        + (4 * SKETCH_K if sketchable else 0)


# effects: pure
def grid_budget_for(state_mb: int, s: int, wp: int, seg_kind: str,
                    n_chips: int) -> GridBudgetDecision:
    """The materialized-grid budget decision (the planner's
    ``grid_budget_decision`` closure, extracted): ~3 grid lanes live
    through a dispatch; per chip when the mesh shards the rows, except
    rollup_avg which never shards and carries a second count-lane
    grid."""
    lanes = 2 if seg_kind == "rollup_avg" else 1
    chips = 1 if seg_kind == "rollup_avg" else max(n_chips, 1)
    grid_bytes = s * wp * 24 * lanes // chips
    return grid_budget("grid", state_mb, grid_bytes, s, wp)


# effects: pure
def streaming_budget_for(state_mb: int, s: int, wp: int,
                         ds_fn: str | None, sketchable: bool,
                         n_chips: int) -> GridBudgetDecision:
    """The streaming-accumulator budget decision (the planner's
    ``streaming_budget_decision`` closure, extracted)."""
    per_cell = acc_cell_bytes(ds_fn, sketchable)
    est = s * wp * per_cell // max(n_chips, 1)
    return grid_budget("streaming", state_mb, est, s, wp,
                       sketch=sketchable)


def size_lane_stripes(tsdb, plan, s: int, wp: int, g_pad: int,
                      state_mb: int, aggregator: str):
    """Attach an over-budget serve sizing to a rollup lane plan (moved
    from the planner so explain sizes striping identically).

    Moment-decomposable cross-series aggregators fold tile by tile
    into [G, W] partial moments (no pool needed — only the tile split
    is sized here); everything else reuses the PR 10 spill-pool stripe
    replay and additionally requires the pool to hold the partials.
    None -> the caller falls back to the tiled-exact/413 path."""
    from opentsdb_tpu.ops import tiling
    tp = tiling.size_tiles(
        s, wp, state_mb * 2 ** 20, 9, g_pad,
        tsdb.config.get_int("tsd.query.spill.max_tiles"),
        chunks_per_tile=1)
    if tp is None:
        return None
    fold_ok = (aggregator in tiling.LANE_FOLDABLE
               and 5 * g_pad * wp * 8 <= state_mb * 2 ** 20)
    if not fold_ok:
        pool = getattr(tsdb, "spill_pool", None)
        if pool is None:
            return None
        entry_bytes = tp.tile_rows * tp.stripe_w \
            * tiling.SPILL_CELL_BYTES
        if tp.spill_bytes + entry_bytes \
                > pool.host_budget + pool.disk_budget:
            return None
    plan.striped = True
    plan.tile_plan = tp
    plan.decision["striped"] = True
    return plan


# effects: pure
def _fingerprint(fields: dict) -> str:
    """Stable hash over the discrete routing facts — canonical JSON,
    first 16 hex chars of sha256.  Deliberately excludes every raw
    millisecond so a cost-table edit alone cannot churn a
    fingerprint unless it actually flips a decision."""
    blob = json.dumps(fields, sort_keys=True, separators=(",", ":"))
    return "pf-" + hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def _finish(pd: PlanDecision, ctx: RouteContext) -> PlanDecision:
    """Fingerprint assembly shared by the refused and served arms."""
    fields = {
        "path": pd.path,
        "seg": ctx.seg_kind,
        "ds": ctx.ds_fn,
        "agg": ctx.aggregator,
        "rate": ctx.has_rate,
        "platform": pd.dec_platform,
        "s": ctx.s, "n": pd.n_pad, "w": ctx.wp,
        "g": pd.g_dec, "gPad": ctx.g_pad,
        "stream": pd.would_stream,
        "mesh": pd.use_mesh,
        "hostSmall": pd.host_small,
        "deviceCache": bool(pd.cached),
    }
    if pd.decisions is not None:
        fields["modes"] = {axis: d["mode"]
                           for axis, d in pd.decisions.items()}
    if pd.lane_plan is not None:
        fields["lane"] = {"lane": pd.lane_plan.lane,
                          "k": pd.lane_plan.k,
                          "striped": bool(pd.lane_plan.striped)}
    if pd.path == "agg_rewrite" and pd.agg_note is not None:
        fields["aggCache"] = {
            "reason": pd.agg_note.get("reason"),
            "cached": pd.agg_note.get("cachedWindows"),
            "computed": pd.agg_note.get("computedWindows")}
    if pd.tiled_plan is not None:
        fields["tiled"] = {"tiles": pd.tiled_plan.n_tiles,
                           "rows": pd.tiled_plan.tile_rows,
                           "stripes": pd.tiled_plan.n_stripes,
                           "stripeW": pd.tiled_plan.stripe_w}
    if pd.refusal is not None:
        fields["refused"] = {"kind": pd.refusal.kind,
                             "limitMb": pd.refusal.state_mb}
    pd.fp_fields = fields
    pd.fingerprint = _fingerprint(fields)
    return pd


def plan_decision(tsdb, ctx: RouteContext, consults) -> PlanDecision:
    """THE routing verdict for one grouped segment.

    ``consults`` provides the four stateful consult hooks —
    ``rollup_plan()``, ``tiled_plan(acc_cell)``, ``agg_plan(platform)``,
    ``device_batch(build, ts_base)`` — plus the accounting callbacks
    (``note_lane_served``/``note_lane_fallback``/``tiled_refusal``).
    The executor's arms do real work; explain's arms are read-only.
    Eligibility gates, consult ordering, budget guards, and the path
    derivation all live HERE, once.
    """
    from opentsdb_tpu.obs import jaxprof
    from opentsdb_tpu.ops.downsample import pad_pow2

    would_stream = (ctx.stream_ok
                    and ctx.total_points > ctx.point_threshold)
    grid_gbd = grid_budget_for(ctx.state_mb, ctx.s, ctx.wp,
                               ctx.seg_kind, ctx.n_chips)
    gbd = (streaming_budget_for(ctx.state_mb, ctx.s, ctx.wp, ctx.ds_fn,
                                ctx.sketchable, ctx.n_chips)
           if would_stream else grid_gbd)

    # Rollup-lane consult (storage/rollup.py): THE shared fast-path
    # hook — one eligibility gate, one verdict, consumed by both the
    # over-budget (tiled) decision and the resident cache chain.
    lane_plan = None
    lane_note = None
    lanes = getattr(tsdb, "rollup_lanes", None)
    if (lanes is not None and ctx.seg_kind == "raw"
            and ctx.store_is_raw and not ctx.use_mesh
            and ctx.s > 0 and ctx.windows_fixed):
        lane_plan, lane_note = consults.rollup_plan()
        if lane_plan is not None:
            # residency: the assembled [S, Wp] grid against the SAME
            # shared device-state allowance every other path honors
            lane_gbd = grid_budget("grid", ctx.state_mb,
                                   ctx.s * ctx.wp * 24, ctx.s, ctx.wp)
            if lane_gbd.over:
                lane_plan = size_lane_stripes(
                    tsdb, lane_plan, ctx.s, ctx.wp, ctx.g_pad,
                    ctx.state_mb, ctx.aggregator)
                if lane_plan is None:
                    lane_note = dict(lane_note, decision="fallback",
                                     reason="striping_unavailable")
                    consults.note_lane_fallback()
            if lane_plan is not None:
                consults.note_lane_served(lane_plan)

    # Over-budget plan: a tiled execution, or the structured 413.
    tiled_plan = None
    if gbd.over and lane_plan is None:
        if not ctx.stream_ok:
            consults.tiled_refusal("not_streamable")
        else:
            tiled_plan = consults.tiled_plan(
                acc_cell_bytes(ctx.ds_fn, ctx.sketchable))
        if tiled_plan is None:
            pd = PlanDecision(
                path="refused", would_stream=would_stream,
                use_mesh=ctx.use_mesh, host_small=False,
                lane_small=False, gbd=gbd, grid_gbd=grid_gbd,
                lane_note=lane_note, refusal=gbd,
                n_pad=pad_pow2(max(ctx.n_max, 1)),
                g_dec=pad_pow2(max(ctx.groups, 1)),
                dec_platform=ctx.platform)
            return _finish(pd, ctx)

    lane_small = (tiled_plan is None and lane_plan is None
                  and not ctx.use_mesh and not would_stream
                  and 0 < ctx.total_points <= ctx.host_lane_max
                  and ctx.cpu_lane_ok)

    # Partial-aggregate rewrite (storage/agg_cache.py), tried BEFORE
    # the device series cache: a warm rewrite skips the column gather
    # too.  ONE host-lane decision for this dispatch: the agg cache
    # keys blocks on the execution platform and the dispatch chain
    # picks its lane from the same value.
    agg_plan = None
    agg_note = None
    if (tiled_plan is None and lane_plan is None
            and getattr(tsdb, "agg_cache", None) is not None
            and not would_stream and not ctx.use_mesh
            and ctx.seg_kind == "raw" and ctx.store_is_raw
            and ctx.windows_fixed):
        agg_platform = "cpu" if lane_small else ctx.platform
        agg_plan, agg_note = consults.agg_plan(agg_platform)

    n_pad = pad_pow2(max(ctx.n_max, 1))
    g_dec = pad_pow2(max(ctx.groups, 1))

    # Fused multi-query dispatch (query/batcher.py), decided BEFORE
    # the device-cache consult: a dispatch-bound plan (predicted
    # compute within batch_factor x the fitted stacked-dispatch floor)
    # routes through the batcher, which coalesces concurrent
    # compatible plans into one stacked [Q, S, W] launch — the
    # per-dispatch floor, not FLOPs, is what caps dashboard-fleet QPS,
    # so amortizing ONE launch across Q members beats Q per-member
    # device-cache gathers.  Compute-bound plans price as dispatch-now
    # and keep the resident/device-cache chain below.  Deterministic
    # in (shape, cost table, factor): explain reaches the same verdict.
    batched = False
    batch_decisions = None
    price_platform = None
    if (tiled_plan is None and lane_plan is None and agg_plan is None
            and ctx.batch_ok and not would_stream and not ctx.use_mesh
            and ctx.seg_kind == "raw" and ctx.has_store
            and ctx.ds_fn is not None):
        from opentsdb_tpu.ops import costmodel as cm
        price_platform = "cpu" if lane_small else ctx.platform
        # ONE decision recomputation: these per-axis reports price the
        # coalesce line here and become pd.decisions below when the
        # batched arm wins (the batched path's dec_platform equals
        # price_platform by construction: cached stays None)
        batch_decisions = jaxprof.segment_decisions(
            price_platform, ctx.s, n_pad, ctx.wp, g_dec, ctx.ds_fn,
            aggregator=ctx.aggregator)
        compute_s = sum(jaxprof.stage_breakdown(
            price_platform, ctx.s, n_pad, ctx.wp, g_dec, ctx.ds_fn,
            ctx.has_rate, decisions=batch_decisions).values())
        batched = cm.coalesce_worthwhile(
            compute_s, ctx.s, n_pad, ctx.wp, g_dec, price_platform,
            ctx.batch_factor)

    # Device-cache fast path (BlockCache analog): cold entries build
    # inline only when the alternative is a full host materialization
    # anyway; a warm hit that would divert a streaming query onto an
    # over-budget materialized grid DECLINES the diversion.  Batched
    # plans skip the consult entirely: the stacked launch needs host
    # arrays to stack, and one shared upload amortizes better than
    # per-member pinned-column gathers.
    cached = None
    if (tiled_plan is None and lane_plan is None and agg_plan is None
            and not batched
            and getattr(tsdb, "device_cache", None) is not None
            and ctx.has_store
            and ctx.seg_kind in ("raw", "rollup")):
        cached = consults.device_batch(build=not would_stream,
                                       ts_base=ctx.ts_base)
        if cached is not None and would_stream and grid_gbd.over:
            cached = None
    host_small = cached is None and lane_small

    if lane_plan is not None:
        path = "rollup_lane"
    elif tiled_plan is not None:
        path = "tiled"
    elif agg_plan is not None:
        path = "agg_rewrite"
    elif batched:
        path = "batched"
    elif cached is None and would_stream:
        path = "streamed"
    elif ctx.seg_kind == "rollup_avg":
        path = "rollup_avg"
    elif ctx.use_mesh:
        path = "mesh"
    elif host_small:
        path = "host_lane"
    else:
        path = "resident"

    dec_platform = "cpu" if host_small else ctx.platform
    decisions = None
    row_groups = ctx.groups == ctx.s and path in ROW_GROUP_PATHS
    if path in MONOLITHIC_PATHS:
        if batch_decisions is not None and not row_groups \
                and dec_platform == price_platform:
            # the coalesce-pricing recomputation already produced this
            # platform's reports — reuse them on the batched arm AND
            # on the batch-declined fallthrough (dec_platform equals
            # price_platform whenever the device-cache consult missed)
            decisions = batch_decisions
        else:
            # per-axis kernel-mode decisions through the SAME
            # _effective_* choosers the kernels consult at trace time
            # (PR 6); computed only where the monolithic kernels
            # actually dispatch — lane/agg/tiled paths run their own
            # programs, and pricing 4 axes of candidates would tax the
            # warm fast paths the caches exist to shrink
            decisions = jaxprof.segment_decisions(
                dec_platform, ctx.s, n_pad, ctx.wp, g_dec, ctx.ds_fn,
                aggregator=ctx.aggregator, row_groups=row_groups)
    pd = PlanDecision(
        path=path, would_stream=would_stream, use_mesh=ctx.use_mesh,
        host_small=host_small, lane_small=lane_small, gbd=gbd,
        grid_gbd=grid_gbd, lane_plan=lane_plan, lane_note=lane_note,
        tiled_plan=tiled_plan, agg_plan=agg_plan, agg_note=agg_note,
        cached=cached, decisions=decisions, n_pad=n_pad, g_dec=g_dec,
        dec_platform=dec_platform)
    return _finish(pd, ctx)

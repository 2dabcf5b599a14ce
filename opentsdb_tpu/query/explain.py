"""The query EXPLAIN engine: /api/query/explain's no-dispatch what-if
planner (docs/query_explain.md).

Accepts the full ``/api/query`` request shape plus what-if overrides
and returns the complete routing decision tree — the admission
estimate vs the deadline with a shed/degrade-ladder preview, the
rollup-lane consult verdict with coverage, the agg-cache block
coverage, the grid-budget/tiling decision with predicted spill
traffic, and the per-axis costmodel pricing for every feasible
candidate — WITHOUT any device dispatch and without acquiring an
admission permit (explain is deadline-bounded but permit-exempt: an
overloaded daemon must still be explainable).

Drift-proofing is structural, not aspirational: the routing verdict
comes from the SAME ``plan_decision()`` the executor dispatches on
(query/plandecision.py), fed by read-only consult arms —
``RollupLanes.plan(observe=False)``, ``AggCache.plan(observe=False)``,
``DeviceSeriesCache.peek`` — so the explained path + fingerprint
equals what the flight-recorder ``plan`` event will record when the
same query executes (pinned per routing path by
tests/test_explain.py, and corpus-pinned by tools/plan_corpus.py ->
PLAN_CORPUS.json).

## What-if grammar

``what_if=key=value`` query-string params (repeatable) or a ``whatIf``
JSON object on POST:

  * ``assume_rollup=cold|warm``       lane store empty / fully covered
  * ``assume_agg_cache=cold|warm``    block cache empty / fully covered
  * ``assume_device_cache=cold|warm`` HBM column cache cold / pinned
  * ``state_mb=<int>``        hypothetical tsd.query.streaming.state_mb
  * ``rollup_mb=<int>``       hypothetical tsd.rollup.mb (0 = lanes off)
  * ``platform=cpu|tpu``      price for an alternate execution platform
  * ``deadline_ms=<int>``     admission preview against this budget
  * ``force_search|force_scan|force_extreme|force_group=<mode>``
                              forced kernel modes in the report

Cache/budget/platform what-ifs feed the routing decision itself;
forced modes produce a ``costmodelWhatIf`` report beside the actual
decision (per-candidate pricing is already part of every decision
report, so a forced mode is a reporting question: nothing the daemon
serves with can be forced).
"""

from __future__ import annotations

import copy
import math
from dataclasses import dataclass, field

from opentsdb_tpu.ops.downsample import (AllWindow, FixedWindows,
                                         WindowSpec, pad_pow2,
                                         precompact_base)
from opentsdb_tpu.query import plandecision as pdn
from opentsdb_tpu.query.limits import QueryException, active_deadline

_ASSUME = ("live", "cold", "warm")
_FORCE_AXES = ("search", "scan", "extreme", "group")


class WhatIfError(ValueError):
    """A what-if override the grammar refuses (400 at the endpoint)."""


@dataclass
class WhatIf:
    """Parsed what-if overrides; defaults = explain the live state."""
    assume_rollup: str = "live"
    assume_agg_cache: str = "live"
    assume_device_cache: str = "live"
    state_mb: int | None = None
    rollup_mb: int | None = None
    platform: str | None = None
    deadline_ms: int | None = None
    force: dict = field(default_factory=dict)   # axis -> mode

    @property
    def active(self) -> bool:
        return (self.assume_rollup != "live"
                or self.assume_agg_cache != "live"
                or self.assume_device_cache != "live"
                or self.state_mb is not None
                or self.rollup_mb is not None
                or self.platform is not None
                or self.deadline_ms is not None
                or bool(self.force))

    def to_json(self) -> dict:
        out: dict = {}
        for key, live in (("assume_rollup", "live"),
                          ("assume_agg_cache", "live"),
                          ("assume_device_cache", "live")):
            value = getattr(self, key)
            if value != live:
                out[key] = value
        for key in ("state_mb", "rollup_mb", "platform", "deadline_ms"):
            value = getattr(self, key)
            if value is not None:
                out[key] = value
        for axis, mode in self.force.items():
            out["force_%s" % axis] = mode
        return out


# effects: pure
def parse_what_if(raw: dict) -> WhatIf:
    """The what-if grammar above; raises :class:`WhatIfError` on an
    unknown key or a value outside the grammar."""
    wi = WhatIf()
    for key, value in (raw or {}).items():
        value = str(value).strip().lower()
        if key in ("assume_rollup", "assume_agg_cache",
                   "assume_device_cache"):
            if value not in _ASSUME:
                raise WhatIfError(
                    "%s must be one of %s" % (key, "|".join(_ASSUME)))
            setattr(wi, key, value)
        elif key in ("state_mb", "rollup_mb", "deadline_ms"):
            try:
                parsed = int(value)
            except ValueError:
                raise WhatIfError("%s must be an integer" % key)
            if parsed < 0:
                raise WhatIfError("%s must be >= 0" % key)
            setattr(wi, key, parsed)
        elif key == "platform":
            if value not in ("cpu", "tpu"):
                raise WhatIfError("platform must be cpu|tpu")
            wi.platform = value
        elif key.startswith("force_") and key[6:] in _FORCE_AXES:
            wi.force[key[6:]] = value
        else:
            raise WhatIfError("unknown what-if key: %r" % key)
    return wi


# --------------------------------------------------------------------- #
# Read-only consult arms                                                #
# --------------------------------------------------------------------- #

@dataclass
class _WhatIfLanePlan:
    """A hypothetical lane hit (assume_rollup=warm): just enough
    surface for plan_decision's striping sizer and the fingerprint."""
    lane: str
    lane_ms: int
    k: int
    striped: bool = False
    tile_plan: object = None
    decision: dict = field(default_factory=dict)


class _ExplainConsults:
    """plan_decision()'s READ-ONLY consult provider: dry-run subsystem
    calls (``observe=False``), a pure device-cache peek, no accounting
    callbacks — explaining a query must not perturb what the executor
    then decides (see the observe contracts on each subsystem)."""

    def __init__(self, tsdb, ctx, what_if: WhatIf, seg, sub, windows,
                 store, series_list, fix):
        self.tsdb = tsdb
        self.ctx = ctx
        self.what_if = what_if
        self.seg = seg
        self.sub = sub
        self.windows = windows
        self.store = store
        self.series_list = series_list
        self.fix = fix

    def _metric(self) -> int:
        return self.series_list[0].key.metric

    # -- rollup ---------------------------------------------------------

    # effects: reads-only
    def rollup_plan(self):
        wi = self.what_if
        assume = wi.assume_rollup
        if wi.rollup_mb == 0:
            assume = "cold"
        if assume == "cold":
            return None, {"decision": "fallback",
                          "reason": "what_if_cold", "lane": "",
                          "coverage": 0.0}
        lanes = self.tsdb.rollup_lanes
        if assume == "warm":
            # a hypothetical full lane hit — honest only where the
            # PURE eligibility holds (derivable fn + a dividing lane)
            note = {"decision": "fallback", "reason": "", "lane": "",
                    "coverage": 0.0, "whatIf": "warm"}
            if not lanes.derivable(self.ctx.ds_fn):
                note["reason"] = "not_derivable"
                return None, note
            picked = lanes.lane_for(self.windows.interval_ms,
                                    self.windows.first_window_ms)
            if picked is None:
                note["reason"] = "no_lane_divides"
                return None, note
            label, lane_ms = picked
            k = self.windows.interval_ms // lane_ms
            note.update(decision="lane", reason="what_if_warm",
                        lane=label, coverage=1.0)
            return _WhatIfLanePlan(lane=label, lane_ms=lane_ms, k=k,
                                   decision=note), note
        ctx = self.ctx
        return lanes.plan(
            self._metric(), self.series_list, self.windows,
            self.seg.start_ms, self.seg.end_ms, ctx.ds_fn,
            ctx.platform, ctx.s, ctx.n_max, ctx.g_pad, ctx.has_rate,
            total_points=ctx.total_points, observe=False)

    # effects: pure
    def note_lane_served(self, plan) -> None:
        pass

    # effects: pure
    def note_lane_fallback(self) -> None:
        pass

    # -- tiled ----------------------------------------------------------

    # effects: pure
    def tiled_refusal(self, reason: str) -> None:
        pass

    # effects: reads-only
    def tiled_plan(self, acc_cell: int):
        from opentsdb_tpu.ops import tiling
        ctx = self.ctx
        return tiling.plan_tiled(
            self.tsdb, s=ctx.s, w=ctx.wp, g_pad=ctx.g_pad,
            acc_cell_bytes=acc_cell, total_points=ctx.total_points,
            platform=ctx.platform, state_mb=ctx.state_mb,
            observe=False)

    # -- agg cache -------------------------------------------------------

    # effects: reads-only
    def agg_plan(self, platform: str):
        assume = self.what_if.assume_agg_cache
        w = self.windows.count
        if assume == "cold":
            return None, {"decision": "recompute",
                          "reason": "what_if_cold", "coverage": 0.0,
                          "cachedWindows": 0, "computedWindows": w}
        if assume == "warm":
            note = {"decision": "rewrite", "reason": "what_if_warm",
                    "coverage": 1.0, "cachedWindows": w,
                    "computedWindows": 0}
            return object(), note
        ctx = self.ctx
        ds = self.sub.downsample_spec
        return self.tsdb.agg_cache.plan(
            self.store, self._metric(), self.series_list, self.windows,
            self.seg.start_ms, self.seg.end_ms, ctx.ds_fn,
            ds.fill_policy, ds.fill_value, platform, ctx.s, ctx.n_max,
            ctx.g_pad, ctx.has_rate, total_points=ctx.total_points,
            observe=False)

    # -- device cache ----------------------------------------------------

    # effects: reads-only
    def device_batch(self, build: bool, ts_base: int | None):
        assume = self.what_if.assume_device_cache
        if assume == "cold":
            return None
        if assume == "warm":
            return True
        warm = self.tsdb.device_cache.peek(
            self.store, self._metric(), self.series_list,
            self.seg.start_ms, self.seg.end_ms, self.fix, build=build,
            ts_base=ts_base)
        return True if warm else None


# --------------------------------------------------------------------- #
# What-if repricing                                                     #
# --------------------------------------------------------------------- #

def _forced_decisions(decisions: dict, what_if: WhatIf) -> dict | None:
    """Forced-mode view of the per-axis decision reports: the same
    candidates at the same prices, with the forced form as the pick
    (`feasible` says whether the kernels could dispatch it at this
    shape).  None when no force_* what-if is active."""
    if not what_if.force:
        return None
    out: dict = {}
    for axis, report in decisions.items():
        rep = dict(report)
        forced = what_if.force.get(axis)
        if forced is not None:
            rep["mode"] = forced
            rep["feasible"] = forced in report["candidates"]
        out[axis] = rep
    return out


# --------------------------------------------------------------------- #
# Admission preview                                                     #
# --------------------------------------------------------------------- #

def _admission_preview(tsdb, ts_query, what_if: WhatIf) -> dict:
    """The admission verdict this query would get RIGHT NOW — the same
    ``estimate_plan_cost_ms`` + queue-wait estimate ``admit()``
    consults, with the degrade ladder run on a deep copy so the
    preview cannot mutate the request being explained.  No permit is
    acquired and no shed/degrade counters fire."""
    from opentsdb_tpu.tsd import admission
    gate = admission.gate_for(tsdb)
    predicted_ms = admission.estimate_plan_cost_ms(tsdb, ts_query)
    queue_ms = gate.queue_wait_estimate_ms()
    if what_if.deadline_ms is not None:
        remaining_ms = float(what_if.deadline_ms)
    else:
        deadline = active_deadline()
        if deadline is not None and deadline.bounded:
            remaining_ms = deadline.remaining_ms()
        else:
            remaining_ms = float(tsdb.config.get_int(
                "tsd.query.timeout"))
    bounded = remaining_ms > 0 and math.isfinite(remaining_ms)
    out = {
        "enabled": gate.enabled,
        "predictedMs": round(predicted_ms, 3),
        "queueWaitEstimateMs": round(queue_ms, 3),
        "remainingMs": round(remaining_ms, 3) if bounded else None,
        "verdict": "admit",
    }
    if gate.enabled and bounded \
            and predicted_ms + queue_ms > remaining_ms:
        note = None
        if tsdb.config.get_string(
                "tsd.query.degrade").strip().lower() == "allow":
            preview = copy.deepcopy(ts_query)
            note = admission.try_degrade(tsdb, preview, remaining_ms,
                                         queue_ms)
        if note is None:
            out["verdict"] = "shed"
            out["retryAfterS"] = gate.retry_after_s()
        else:
            out["verdict"] = "degrade"
            out["degraded"] = note
    return out


# --------------------------------------------------------------------- #
# The engine                                                            #
# --------------------------------------------------------------------- #

def explain_query(tsdb, ts_query, what_if: WhatIf) -> dict:
    """The complete decision tree for one parsed, validated TSQuery —
    zero device dispatches, zero admission permits, deadline-bounded
    (the per-sub QueryBudget charges the same scan the executor
    would, so an over-limit explain reports the 413 it predicts
    instead of doing unbounded planning work)."""
    runner = tsdb.new_query_runner()
    include_candidates = tsdb.config.get_bool(
        "tsd.explain.include_candidates")
    out = {
        "whatIf": what_if.to_json(),
        "admission": _admission_preview(tsdb, ts_query, what_if),
        "subQueries": [],
    }
    cluster = _explain_cluster(tsdb)
    if cluster is not None:
        out["cluster"] = cluster
    for sub in ts_query.queries:
        out["subQueries"].append(
            _explain_sub(tsdb, runner, ts_query, sub, what_if,
                         include_candidates))
    return out


def _explain_cluster(tsdb) -> dict | None:
    """The shard-scoped fan-out arm: WHICH peers a clustered query
    would fetch from, and which shards each would serve.  Same pure
    ``plan_cover`` the executor dispatches on (tsd/replication.py —
    the plan_decision convention applied to fan-out routing), consumed
    read-only: no epoch bump, no flight-recorder event, no breaker
    churn."""
    from opentsdb_tpu.tsd.cluster import cluster_peers
    peers = cluster_peers(tsdb.config)
    if not peers:
        return None
    repl = getattr(tsdb, "replication", None)
    if repl is None:
        return {"mode": "fanout", "peers": sorted(peers)}
    from opentsdb_tpu.tsd.replication import plan_cover
    cover, uncovered = plan_cover(repl.preferences, repl._healthy)
    return {
        "mode": "sharded",
        "epoch": repl.current_epoch(),
        "rf": repl.rf,
        "shardCount": repl.shard_count,
        "fanout": [
            {"node": node, "shards": len(shards),
             "role": "self" if node == repl.self_id else "peer"}
            for node, shards in sorted(cover.items())],
        "uncoveredShards": sorted(uncovered),
    }


def _explain_sub(tsdb, runner, query, sub, what_if: WhatIf,
                 include_candidates: bool) -> dict:
    report: dict = {"index": sub.index, "metric": sub.metric or None,
                    "aggregator": sub.aggregator, "segments": []}
    if sub.percentiles or sub.show_histogram_buckets:
        report["note"] = ("histogram plans are one bucket-scatter "
                          "dispatch and are not routed through "
                          "plan_decision")
        return report
    try:
        budget = runner._new_budget(sub)
        segments = runner._plan_segments(query, sub)
    except QueryException as e:
        report["refused"] = _refusal_json(e)
        return report
    for seg in segments:
        try:
            report["segments"].append(
                _explain_segment(tsdb, runner, query, sub, seg,
                                 what_if, budget, include_candidates))
        except QueryException as e:
            # the budget/deadline refusal the executor would raise —
            # reported, not served (the explain response itself is 200)
            report["segments"].append({
                "kind": seg.kind, "startMs": seg.start_ms,
                "endMs": seg.end_ms, "path": "refused",
                "refused": _refusal_json(e)})
            break
    return report


def _refusal_json(e: QueryException) -> dict:
    out = {"status": getattr(e, "status", 413), "message": str(e)}
    details = getattr(e, "details", None)
    if details:
        out["details"] = details
    return out


def _explain_segment(tsdb, runner, query, sub, seg, what_if: WhatIf,
                     budget, include_candidates: bool) -> dict:
    # series resolution + grouping + counts: the executor's scan,
    # read-only (QueryRunner methods shared, not re-implemented)
    if seg.kind == "raw":
        store = tsdb.store
        if sub.pre_aggregate and tsdb.rollup_store is not None:
            pre = tsdb.rollup_store.peek_lane("", sub.aggregator, True)
            store = pre if pre is not None else store
    else:
        store = seg.lane
    sel = runner._selection(sub, store)
    windows = runner._windows_for(sub, query)
    base = {"kind": seg.kind, "startMs": seg.start_ms,
            "endMs": seg.end_ms, "series": len(sel.series_tags),
            "groups": len(sel.groups)}
    if windows is None:
        # union-timestamp aggregation: per-group fused dispatches, no
        # downsample grid — not routed through plan_decision
        base.update(path="union",
                    note="union plans dispatch per shape bucket and "
                         "are not routed through plan_decision")
        return base
    fix = tsdb.config.fix_duplicates
    scan = runner._scan(sel, seg, store, observe=False)
    if scan is None:
        base.update(path="empty", note="no datapoints in range")
        return base
    total_points = int(scan.counts.sum())
    budget.charge(total_points)
    budget.check_deadline()
    ds = sub.downsample_spec
    ds_fn = seg.ds_function or ds.function
    series_list, n_groups = scan.series, len(scan.groups)
    n_rows = len(series_list)
    n_max = int(scan.counts.max())
    g_pad = pad_pow2(n_groups)
    sketchable, hazard = runner._sketch_eligible(
        seg, ds_fn, windows, series_list, scan.counts, fix)
    from opentsdb_tpu.ops.streaming import STREAMABLE_DS
    stream_ok = (seg.kind != "rollup_avg"
                 and (ds_fn in STREAMABLE_DS or sketchable))
    wp = 1 if isinstance(windows, AllWindow) else pad_pow2(windows.count)
    mesh = tsdb.query_mesh()
    use_mesh = (mesh is not None and n_rows >= tsdb.config.get_int(
        "tsd.query.mesh.min_series"))
    n_chips = 1
    if use_mesh:
        from opentsdb_tpu.parallel.sharded import n_devices
        n_chips = n_devices(mesh)
    ts_base = None
    if isinstance(windows, FixedWindows):
        ts_base = precompact_base(
            WindowSpec("fixed", wp, windows.interval_ms),
            windows.first_window_ms)
    from opentsdb_tpu.ops.hostlane import cpu_device, execution_platform
    platform = what_if.platform or execution_platform()
    state_mb = (what_if.state_mb if what_if.state_mb is not None
                else tsdb.config.get_int("tsd.query.streaming.state_mb"))
    ctx = pdn.RouteContext(
        seg_kind=seg.kind, ds_fn=ds_fn, aggregator=sub.aggregator,
        has_rate=bool(sub.rate), s=n_rows, n_max=int(n_max), wp=wp,
        groups=n_groups, g_pad=g_pad, total_points=total_points,
        sketchable=sketchable, stream_ok=stream_ok, use_mesh=use_mesh,
        n_chips=n_chips, windows_fixed=isinstance(windows, FixedWindows),
        store_is_raw=store is tsdb.store, has_store=store is not None,
        platform=platform, cpu_lane_ok=cpu_device() is not None,
        state_mb=state_mb,
        point_threshold=tsdb.config.get_int(
            "tsd.query.streaming.point_threshold"),
        host_lane_max=tsdb.config.get_int(
            "tsd.query.host_lane.max_points"),
        ts_base=ts_base,
        batch_ok=(getattr(tsdb, "dispatch_batcher", None) is not None
                  and tsdb.dispatch_batcher.enabled),
        batch_factor=tsdb.config.get_float(
            "tsd.query.batch.amortize_factor"))
    pd = pdn.plan_decision(
        tsdb, ctx, _ExplainConsults(tsdb, ctx, what_if, seg, sub,
                                    windows, store, series_list, fix))
    base.update(
        path=pd.path,
        fingerprint=pd.fingerprint,
        provenance=pd.fp_fields,
        shape={"series": ctx.s, "pointsMax": ctx.n_max,
               "nPad": pd.n_pad, "windows": ctx.wp,
               "groups": ctx.groups, "gPad": ctx.g_pad,
               "totalPoints": ctx.total_points,
               "platform": pd.dec_platform},
        budget={"kind": pd.gbd.kind, "gridMb": pd.gbd.grid_mb,
                "limitMb": pd.gbd.state_mb, "over": pd.gbd.over,
                "wouldStream": pd.would_stream},
        deviceCache={"warm": bool(pd.cached)},
        sketch={"sketchable": sketchable, "hazardFallback": hazard})
    if pd.lane_note is not None:
        base["rollup"] = pd.lane_note
    if pd.agg_note is not None:
        base["aggCache"] = pd.agg_note
    if pd.tiled_plan is not None:
        tp = pd.tiled_plan
        base["tiling"] = {
            "tiles": tp.n_tiles, "tileRows": tp.tile_rows,
            "stripes": tp.n_stripes, "stripeWindows": tp.stripe_w,
            "spillBytes": tp.spill_bytes, "dispatches": tp.dispatches,
            "predictedOverheadMs": round(tp.predicted_s * 1e3, 3)}
    if pd.refusal is not None:
        base["refused"] = _refusal_json(pd.refusal.exception())
    # per-axis costmodel pricing for the report: plan_decision computes
    # the decisions only on monolithic paths (the hot-path rule);
    # explain is cold-path and always reports them
    from opentsdb_tpu.obs import jaxprof
    decisions = pd.decisions
    if decisions is None:
        decisions = jaxprof.segment_decisions(
            pd.dec_platform, ctx.s, pd.n_pad, ctx.wp, pd.g_dec,
            ctx.ds_fn, aggregator=ctx.aggregator)
    whatif_decisions = _forced_decisions(decisions, what_if)
    if not include_candidates:
        decisions = {axis: {k: v for k, v in rep.items()
                            if k != "candidates"}
                     for axis, rep in decisions.items()}
        if whatif_decisions is not None:
            whatif_decisions = {
                axis: {k: v for k, v in rep.items()
                       if k != "candidates"}
                for axis, rep in whatif_decisions.items()}
    base["costmodel"] = decisions
    if whatif_decisions is not None:
        base["costmodelWhatIf"] = whatif_decisions
    return base

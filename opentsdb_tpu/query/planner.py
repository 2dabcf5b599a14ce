"""Query planner/executor: TSQuery -> series selection -> TPU kernels -> results.

Reference behavior: /root/reference/src/core/TsdbQuery.java — UID resolution
(configureFromQuery :490), tag-filter evaluation + group-by discovery
(findGroupBys :675, GroupByAndAggregateCB :981-1114), span windowing, and the
SpanGroup tag intersection rules (SpanGroup.computeTags :348: keys with one
distinct value stay `tags`, conflicting keys become `aggregateTags`).

The per-datapoint iterator merge is replaced by ops.pipeline: each group-by
bucket becomes one padded [series, time] batch pushed through jit-compiled
downsample/rate/union kernels.
"""

from __future__ import annotations

import json
import threading
from dataclasses import dataclass, replace

import jax
import numpy as np

from opentsdb_tpu.models.tsquery import TSQuery, TSSubQuery
from opentsdb_tpu.obs import latattr
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.ops.downsample import (
    FixedWindows, EdgeWindows, AllWindow, pad_pow2)
from opentsdb_tpu.ops.pipeline import (
    PipelineSpec, DownsampleStep, run_pipeline, run_group_pipeline,
    run_union_batch_pipeline,
    run_group_rollup_avg_pipeline, run_grid_tail, build_batch,
    build_batch_direct, LANE_DENSE, LANE_SHIFT)
from opentsdb_tpu.ops.streaming import (
    StreamAccumulator, STREAMABLE_DS, is_sketch_ds, lanes_for)
from opentsdb_tpu.query import filters as query_filters
from opentsdb_tpu.rollup.config import NoSuchRollupForInterval, RollupQuery
from opentsdb_tpu.storage import native_engine
from opentsdb_tpu.storage.chunk_pack import ChunkPacker
from opentsdb_tpu.storage.memstore import Series, SeriesKey
from opentsdb_tpu.uid import NoSuchUniqueName
from opentsdb_tpu.utils import datetime_util as DT

_NO_MATCH = object()  # sentinel: a literal filter can never match
_INF = float("inf")

# Downsample function -> (rollup lane, function applied over lane cells).
# Counts re-reduce with SUM; min/max/sum re-reduce with themselves
# (RollupUtils qualifiers hold one aggregator's cells per lane).
_ROLLUP_LANES = {
    "sum": ("sum", "sum"),
    "zimsum": ("sum", "zimsum"),
    "count": ("count", "sum"),
    "min": ("min", "min"),
    "mimmin": ("min", "mimmin"),
    "max": ("max", "max"),
    "mimmax": ("max", "mimmax"),
}


@dataclass
class Segment:
    """One data-source slice of a sub query's time range.

    The split-rollup machinery (SplitRollupQuery.java) reduced to data: a
    rollup table serves [start, boundary) under its SLA, raw data serves the
    blackout tail.  kind: "raw" | "rollup" | "rollup_avg".
    """
    kind: str
    start_ms: int
    end_ms: int
    lane: object = None        # MemStore: rollup lane (sum lane for rollup_avg)
    count_lane: object = None  # MemStore: count lane for rollup_avg
    ds_function: str | None = None   # downsample fn override over lane cells
    rollup_query: RollupQuery | None = None


class QueryResult:
    """One output object of /api/query (HttpJsonSerializer.java:742-815).

    The points are (ts_ms, value) pairs, value int or float or NaN, read
    as `dps`; a downsampled answer holds them as two columns instead —
    `stamps`, one list shared by every group of the answer, and this
    group's `values` — and pairs them up only for a reader that asks.
    Where the values are row `row` of the answer's [G, W] float64 grid,
    `block` is that grid (extract_grid), for emit_texts() to write.
    `tags`, `aggregate_tags` and `tsuids` may be a memoised selection's
    own objects: replace them, never write into them.
    """

    __slots__ = ("metric", "tags", "aggregate_tags", "tsuids",
                 "annotations", "global_annotations", "index", "head",
                 "stamps", "values", "_dps", "block", "row")

    def __init__(self, metric: str, tags: dict[str, str],
                 aggregate_tags: list[str], tsuids: list[str],
                 dps: list[tuple[int, object]] | None = None,
                 annotations=(), global_annotations=(), index: int = 0,
                 head: str | None = None, stamps: list | None = None,
                 values: list | None = None, block=None, row: int = 0):
        self.metric = metric
        self.tags = tags
        self.aggregate_tags = aggregate_tags
        self.tsuids = tsuids
        self.annotations = annotations
        self.global_annotations = global_annotations
        self.index = index
        # json.dumps of {"metric", "tags", "aggregateTags"} less its
        # closing brace, where the planner has it from a memoised
        # selection; whoever replaces one of the three sets it to None
        self.head = head
        self.stamps, self.values, self._dps = stamps, values, dps
        self.block, self.row = block, row

    @property
    def dps(self) -> list[tuple[int, object]]:
        if self._dps is None:
            self._dps = list(zip(self.stamps, self.values))
        return self._dps

    @dps.setter
    def dps(self, pairs: list[tuple[int, object]]) -> None:
        self.stamps = self.values = self.block = None
        self._dps = pairs

    def _columns(self) -> tuple:
        if self.stamps is None:
            return tuple(zip(*self._dps)) if self._dps else ((), ())
        return self.stamps, self.values

    @staticmethod
    def _memo(keys: dict | None, what: str, stamps, make):
        """`make(stamps)`, remembered in `keys` under `what` for the
        other results of the response: by identity for a shared column,
        else by value."""
        if keys is None:
            return make(stamps)
        key = stamps if isinstance(stamps, tuple) else id(stamps)
        hit = keys.get((what, key))
        if hit is None:     # the column is kept: its id stays its own
            hit = keys[(what, key)] = (stamps, make(stamps))
        return hit[1]

    def to_json(self, ms_resolution: bool = False, show_tsuids: bool = False,
                fill_policy: str = "none", show_query: bool = False,
                sub_query: TSSubQuery | None = None,
                no_annotations: bool = False,
                global_annotations: bool = False,
                keys: dict | None = None) -> dict:
        """`keys` is a memo of the timestamps' key strings that the
        caller shares between the results of one response: the groups
        of a downsampled answer all carry the same timestamps."""
        stamps, values = self._columns()
        scale = 1 if ms_resolution else 1000
        names = self._memo(keys, "names", stamps,
                           lambda col: [str(t // scale) for t in col])
        total = sum(values)
        if total != total:      # a NaN among them (or inf - inf)
            null = None if fill_policy == "null" else float("nan")
            values = [null if isinstance(v, float) and v != v else v
                      for v in values]
        out = {
            "metric": self.metric,
            "tags": self.tags,
            "aggregateTags": self.aggregate_tags,
        }
        if show_query and sub_query is not None:
            out["query"] = sub_query.to_json()
        if show_tsuids:
            out["tsuids"] = sorted(self.tsuids)
        if not no_annotations and self.annotations:
            out["annotations"] = [a.to_json() for a in self.annotations]
        if global_annotations and self.global_annotations:
            out["globalAnnotations"] = [a.to_json()
                                        for a in self.global_annotations]
        out["dps"] = dict(zip(names, values))
        return out

    def json_text(self, keys: dict, ms_resolution: bool = False
                  ) -> str | None:
        """to_json() of a plain answer (no tsuids, query echo or
        annotations asked for) as the text json.dumps would make of it,
        from the pre-encoded head and one format call for the points —
        or None where that text cannot be made so (no head, no points,
        a NaN or an infinity among the values): the caller then takes
        to_json()."""
        stamps, values = self._columns()
        if self.head is None or not stamps:
            return None
        total = sum(values)
        if total != total or total in (_INF, -_INF):
            return None
        scale = 1 if ms_resolution else 1000
        form = self._memo(keys, "form", stamps, lambda col: ', "dps": {%s}}' % (
            ", ".join('"%d": %%r' % (t // scale) for t in col)))
        return self.head + form % tuple(values)


def emit_texts(results: list, ms_resolution: bool = False) -> list:
    """json_text() of each result that holds a row of a value block, has
    a head and no annotations, and whose row is finite: the rows of one
    block written by one native call (storage/native_engine.py
    emit_rows).  None for every other result, and for all of them where
    the native library is unavailable."""
    texts = [None] * len(results)
    blocks: dict[int, tuple] = {}
    for i, r in enumerate(results):
        if r.block is not None and r.head is not None and not r.annotations:
            entry = blocks.get(id(r.block))
            if entry is None:
                entry = blocks[id(r.block)] = (r, [], [])
            entry[1].append(i)
            entry[2].append(r.row)
    scale = 1 if ms_resolution else 1000
    for first, at, rows in blocks.values():
        block, stamps = first.block, first.stamps
        if not stamps:
            continue
        rows = np.asarray(rows, np.int64)
        finite = np.isfinite(block).all(axis=1)[rows]
        if not finite.all():
            at = [i for i, ok in zip(at, finite.tolist()) if ok]
            rows = rows[finite]
        keys = ['"%d": ' % (t // scale) for t in stamps]
        pieces = ([', "dps": {' + keys[0]] + [", " + k for k in keys[1:]]
                  + ["}}"])
        dps = native_engine.emit_rows(block, rows, pieces)
        if dps is None:
            return texts
        for i, text in zip(at, dps):
            texts[i] = results[i].head + text
    return texts


class _Selection:
    """What one (store, metric, filters, group-by) selects: resolved and
    grouped once, and served again until a series is born or deleted in
    the store or a UID is renamed (`stamp`).  Nothing here depends on a
    query's time range; a request reads it and never writes to it."""

    def __init__(self, store, stamp: tuple, series_tags: list,
                 groups: dict):
        self.store = store      # the strong ref keeps id(store) stable
        self.stamp = stamp
        self.series_tags = series_tags
        self.groups = groups    # as QueryRunner._group returns it
        # the groups in the order they are answered, and their rows
        self.keys = sorted(groups, key=lambda k: tuple(map(str, k)))
        self.members = [groups[k] for k in self.keys]
        self.str_keys = [tuple(map(str, k)) for k in self.keys]
        sizes = np.fromiter(map(len, self.members), np.int64,
                            len(self.members))
        self.starts = np.concatenate([[0], np.cumsum(sizes)])
        self.series = [s for m in self.members for s, _ in m]
        self.gid = np.repeat(np.arange(len(sizes), dtype=np.int64), sizes)
        self._lock = threading.Lock()
        self._meta = None  # guarded-by: _lock

    def meta(self, tsdb, metric: str) -> list:
        """Per group (tags, aggregateTags, tsuids, head): what an answer
        says of a group besides its points, and — for a memoised
        selection, which is asked again — QueryResult.head, the three
        of them as JSON text."""
        with self._lock:
            if self._meta is None:
                self._meta = []
                for members in self.members:
                    tags, agg_tags = QueryRunner._compute_tags(members)
                    head = None
                    if self.stamp is not None:
                        head = json.dumps({"metric": metric, "tags": tags,
                                           "aggregateTags": agg_tags})[:-1]
                    self._meta.append(
                        (tags, agg_tags,
                         [tsdb.tsuid(s.key) for s, _ in members], head))
            return self._meta


class SelectionMemo:
    """The last few selections of one TSDB, by what was asked; shared by
    its handler threads."""

    SIZE = 32

    def __init__(self):
        self._lock = threading.Lock()
        self._by_key: dict = {}  # guarded-by: _lock

    def get(self, key: tuple, store, stamp: tuple) -> _Selection | None:
        with self._lock:
            sel = self._by_key.get(key)
        if sel is not None and sel.stamp == stamp and sel.store is store:
            return sel
        return None

    def put(self, key: tuple, sel: _Selection) -> None:
        with self._lock:
            self._by_key.pop(key, None)
            while len(self._by_key) >= self.SIZE:
                self._by_key.pop(next(iter(self._by_key)))
            self._by_key[key] = sel


@dataclass
class _Scan:
    """One segment's scan of a selection: the groups that hold points in
    the segment's range, row by row (a row is one member series)."""
    groups: np.ndarray      # [G] index into the selection's groups
    series: list            # [S] rows, group by group
    gid: np.ndarray         # [S] int64 position in `groups` of each row
    counts: np.ndarray      # [S] int64 points of each row in the range
    bounds: object          # the device cache's WindowBounds of the rows,
    #                         where a valid entry answered the counts


class QueryRunner:
    """Executes TSQueries against a TSDB."""

    def __init__(self, tsdb):
        self.tsdb = tsdb
        # numeric execution telemetry for the last run() — merged into the
        # query's QueryStats and served at /api/stats/query (the
        # scanner-level stats of QueryStats.java:132, re-expressed for
        # batch execution: points scanned, streamed chunks, mesh devices)
        self.exec_stats: dict[str, float] = {}
        self._fetch_notes = True    # set per sub query by run_sub

    def _bump(self, key: str, value: float) -> None:
        self.exec_stats[key] = self.exec_stats.get(key, 0.0) + value

    # -- series selection ------------------------------------------------

    def _resolve_series(self, sub: TSSubQuery, store=None
                        ) -> list[tuple[Series, dict]]:
        """All series matching the sub query, with resolved tag maps."""
        tsdb = self.tsdb
        if store is None:
            store = tsdb.store
        if sub.tsuids:
            wanted = {t.upper() for t in sub.tsuids}
            out = []
            for series in store.all_series():
                if tsdb.tsuid(series.key) in wanted:
                    out.append((series, tsdb.resolve_key_tags(series.key)))
            return out

        metric_uid = tsdb.metrics.get_id(sub.metric)
        candidates = store.series_for_metric(metric_uid)
        uid_constraints = self._literal_uid_constraints(sub.filters)
        if uid_constraints is _NO_MATCH:
            return []
        out = []
        filter_tagks = {f.tagk for f in sub.filters}
        for series in candidates:
            if uid_constraints:
                key_tags = dict(series.key.tags)
                if any(key_tags.get(ku) not in vuids
                       for ku, vuids in uid_constraints):
                    continue
            tags = tsdb.resolve_key_tags(series.key)
            if sub.explicit_tags and set(tags) != filter_tagks:
                continue
            if all(f.match(tags) for f in sub.filters):
                out.append((series, tags))
        return out

    def _literal_uid_constraints(self, filters):
        """Compile literal filters to (tagk_uid, tagv_uid_set) pre-filters.

        The UID-space pruning role of the reference's in-scan row regex
        (TsdbQuery.createAndSetFilter :1683): series failing a literal_or
        constraint are skipped before any UID->string resolution.  Returns
        _NO_MATCH when a constraint cannot match anything (unknown tagk, or
        no listed value exists in the tagv dictionary).
        """
        tsdb = self.tsdb
        out = []
        for f in filters:
            values = f.literal_values()
            if values is None:
                continue
            try:
                ku = tsdb.tag_names.get_id(f.tagk)
            except NoSuchUniqueName:
                return _NO_MATCH
            vuids = set()
            for v in values:
                try:
                    vuids.add(tsdb.tag_values.get_id(v))
                except NoSuchUniqueName:
                    pass
            if not vuids:
                return _NO_MATCH
            out.append((ku, vuids))
        return out

    @staticmethod
    def _group(series_tags: list[tuple[Series, dict]], sub: TSSubQuery):
        """Group-by bucketing (TsdbQuery.GroupByAndAggregateCB :981)."""
        group_tagks = sub.group_by_tags()
        if sub.aggregator == "none":
            # NONE: no aggregation, each series is its own group.
            return {("__series__", i): [st]
                    for i, st in enumerate(series_tags)}
        if not group_tagks:
            return {(): series_tags} if series_tags else {}
        groups: dict[tuple, list] = {}
        for series, tags in series_tags:
            key_vals = tuple(tags.get(k) for k in group_tagks)
            if any(v is None for v in key_vals):
                continue  # series lacks a group-by tag -> excluded
            groups.setdefault(key_vals, []).append((series, tags))
        return groups

    @staticmethod
    def _compute_tags(members: list[tuple[Series, dict]]):
        """SpanGroup.computeTags (:348): single-valued keys -> tags,
        conflicting keys -> aggregateTags."""
        from opentsdb_tpu.expression.series import compute_tags
        return compute_tags([tags for _, tags in members])

    def _selection(self, sub: TSSubQuery, store) -> _Selection:
        """Resolve and group, or the memo of it: a dashboard asks the
        same (metric, filters, group-by) over and over, and at 10^5
        series the walk costs seconds.  Memoised per store generation
        for the built-in filter types (a plugin's match may read state
        this cannot see) and never for tsuid queries."""
        tsdb = self.tsdb
        memo = key = stamp = None
        if (not sub.tsuids and hasattr(store, "series_generation")
                and all(type(f).__module__ == query_filters.__name__
                        for f in sub.filters)):
            memo = tsdb.selections
            key = (id(store), sub.metric, tuple(map(repr, sub.filters)),
                   sub.explicit_tags, tuple(sub.group_by_tags()),
                   sub.aggregator == "none")
            # read BEFORE the walk: a series born meanwhile leaves the
            # memo a generation behind, and the next request walks again
            stamp = (store.series_generation, tsdb.metrics.renames,
                     tsdb.tag_names.renames, tsdb.tag_values.renames)
            sel = memo.get(key, store, stamp)
            if sel is not None:
                return sel
        with obs_trace.stage("scan.resolve"):
            series_tags = self._resolve_series(sub, store)
        with obs_trace.stage("scan.group"):
            sel = _Selection(store, stamp, series_tags,
                             self._group(series_tags, sub))
        if memo is not None:
            memo.put(key, sel)
        return sel

    def _scan(self, sel: _Selection, seg: Segment, store,
              observe: bool = True) -> _Scan | None:
        """Count every row's points in the segment's range and keep the
        groups that hold any (no datapoints in range -> no SpanGroup at
        all: the scanner returns no spans, TsdbQuery.findSpans -> empty
        group map); the caller charges its budget with the counts.  A
        valid device-cache entry answers the counts for all rows in one
        pass; else each series is asked (lock + binary search, no copy).
        `observe` off is the explain engine's read-only walk."""
        tsdb = self.tsdb
        fix = tsdb.config.fix_duplicates
        series, bounds = sel.series, None
        if not series:
            return None
        cache = tsdb.device_cache
        if cache is not None and store is not None:
            ask = cache.bounds_for if observe else cache.peek_bounds
            bounds = ask(store, series[0].key.metric, series,
                         seg.start_ms, seg.end_ms)
        if bounds is not None:
            counts = bounds.lengths
        else:
            counts = np.fromiter(
                (s.window_count(seg.start_ms, seg.end_ms, fix)
                 for s in series), np.int64, len(series))
        live = np.add.reduceat(counts, sel.starts[:-1]) > 0
        if live.all():
            return _Scan(np.arange(len(live)), series, sel.gid, counts,
                         bounds)
        if not live.any():
            return None
        rows = np.repeat(live, np.diff(sel.starts))
        groups = np.flatnonzero(live)
        if bounds is not None:
            bounds = bounds.take(rows)
        return _Scan(groups,
                     [s for s, k in zip(series, rows.tolist()) if k],
                     np.repeat(np.arange(len(groups), dtype=np.int64),
                               np.diff(sel.starts)[live]),
                     counts[rows], bounds)

    # -- execution -------------------------------------------------------

    def _windows_for(self, sub: TSSubQuery, query: TSQuery):
        spec = sub.downsample_spec
        if spec is None:
            return None
        if spec.run_all:
            return AllWindow(query.start_time, query.end_time)
        if spec.use_calendar:
            edges = DT.calendar_window_edges(
                query.start_time, query.end_time, spec.calendar_interval,
                spec.calendar_unit, spec.timezone)
            return EdgeWindows(tuple(edges))
        return FixedWindows.for_range(query.start_time, query.end_time,
                                      spec.interval_ms)

    # -- rollup source selection (TsdbQuery.transformDownSamplerToRollupQuery
    #    :1733, ROLLUP_USAGE :197, SplitRollupQuery) ----------------------

    def _rollup_candidates(self, sub: TSSubQuery):
        """Rollup intervals able to serve this sub query, best first."""
        tsdb = self.tsdb
        ds = sub.downsample_spec
        usage = (sub.rollup_usage or "ROLLUP_NOFALLBACK").upper()
        if (tsdb.rollup_config is None or tsdb.rollup_store is None
                or ds is None or ds.run_all or ds.use_calendar
                or ds.interval_ms <= 0 or usage == "ROLLUP_RAW"
                or sub.tsuids):
            return [], usage
        if ds.function != "avg" and ds.function not in _ROLLUP_LANES:
            return [], usage
        try:
            matches = tsdb.rollup_config.get_best_matches_ms(ds.interval_ms)
        except (NoSuchRollupForInterval, ValueError):
            return [], usage
        matches = [m for m in matches if not m.default_interval]
        if not matches:
            return [], usage
        if usage == "ROLLUP_NOFALLBACK":
            matches = matches[:1]
        return matches, usage

    def _segment_for_interval(self, sub: TSSubQuery, interval,
                              start_ms: int, end_ms: int) -> Segment | None:
        """A rollup Segment over [start, end] if the lanes hold data."""
        tsdb = self.tsdb
        ds = sub.downsample_spec
        try:
            metric_uid = tsdb.metrics.get_id(sub.metric)
        except NoSuchUniqueName:
            return None
        pre = sub.pre_aggregate
        if ds.function == "avg":
            sum_lane = tsdb.rollup_store.peek_lane(interval.interval, "sum",
                                                   pre)
            cnt_lane = tsdb.rollup_store.peek_lane(interval.interval, "count",
                                                   pre)
            if (sum_lane is None or cnt_lane is None
                    or not sum_lane.series_for_metric(metric_uid)
                    or not cnt_lane.series_for_metric(metric_uid)):
                return None
            rq = RollupQuery(interval, "avg", ds.interval_ms, sub.aggregator)
            return Segment("rollup_avg", start_ms, end_ms, lane=sum_lane,
                           count_lane=cnt_lane, ds_function="sum",
                           rollup_query=rq)
        lane_agg, ds_fn = _ROLLUP_LANES[ds.function]
        lane = tsdb.rollup_store.peek_lane(interval.interval, lane_agg, pre)
        if lane is None or not lane.series_for_metric(metric_uid):
            return None
        rq = RollupQuery(interval, ds.function, ds.interval_ms,
                         sub.aggregator)
        return Segment("rollup", start_ms, end_ms, lane=lane,
                       ds_function=ds_fn, rollup_query=rq)

    def _plan_segments(self, query: TSQuery, sub: TSSubQuery) -> list[Segment]:
        start_ms, end_ms = query.start_time, query.end_time
        raw = Segment("raw", start_ms, end_ms)
        candidates, usage = self._rollup_candidates(sub)
        chosen = None
        for interval in candidates:
            chosen = self._segment_for_interval(sub, interval, start_ms,
                                                end_ms)
            if chosen is not None:
                break
        if chosen is None:
            if not candidates or usage == "ROLLUP_FALLBACK_RAW":
                return [raw]
            # NOFALLBACK/FALLBACK with empty rollup lanes -> empty result,
            # never a silent raw scan (ROLLUP_USAGE :197-201).
            return []
        rq = chosen.rollup_query
        tsdb = self.tsdb
        if (tsdb.config.get_bool("tsd.rollups.split_query.enable")
                and rq.rollup_interval.delay_sla_ms > 0):
            now_ms = DT.current_time_millis()
            boundary = rq.last_guaranteed_ms(now_ms)
            ds = sub.downsample_spec
            # Align down to the downsample grid so no window spans sources.
            boundary -= boundary % ds.interval_ms
            if boundary <= start_ms:
                return [raw]            # whole range is blacked out
            if boundary <= end_ms:
                chosen.end_ms = boundary - 1
                return [chosen,
                        Segment("raw", boundary, end_ms)]
        return [chosen]

    # -- segment execution ----------------------------------------------

    def _run_segment(self, query: TSQuery, sub: TSSubQuery, seg: Segment,
                     global_notes: list, budget) -> dict[tuple, QueryResult]:
        tsdb = self.tsdb
        if seg.kind == "raw":
            store = tsdb.store
            if sub.pre_aggregate and tsdb.rollup_store is not None:
                pre = tsdb.rollup_store.peek_lane("", sub.aggregator, True)
                store = pre if pre is not None else store
        else:
            store = seg.lane
        with obs_trace.timed_stage("scan", kind=seg.kind) as sp:
            sel = self._selection(sub, store)
            obs_trace.annotate(sp, series=len(sel.series_tags),
                               groups=len(sel.groups))
        windows = self._windows_for(sub, query)
        if windows is not None:
            return self._run_segment_grouped(query, sub, seg, sel,
                                             windows, global_notes, budget,
                                             store)
        return self._run_segment_union(query, sub, seg, sel.groups,
                                       global_notes, budget)

    def _assemble_result(self, query: TSQuery, sub: TSSubQuery, members,
                         dps, global_notes, meta=None, stamps=None,
                         values=None, block=None,
                         row: int = 0) -> QueryResult:
        """`meta` is the selection's (tags, aggregateTags, tsuids, head)
        of the group where it has them: the answer shares them.  The
        points are `dps`, or the columns `stamps` and `values` (row
        `row` of `block`, where extract_grid kept one)."""
        tsdb = self.tsdb
        if meta is None:
            group_tags, agg_tags = self._compute_tags(members)
            tsuids = [tsdb.tsuid(s.key) for s, _ in members]
            head = None
        else:
            group_tags, agg_tags, tsuids, head = meta
        annotations = ()
        if self._fetch_notes:
            annotations = [
                note for t in tsuids for note in tsdb.store.get_annotations(
                    t, query.start_time, query.end_time)]
        return QueryResult(
            metric=sub.metric or (
                tsdb.metrics.get_name(members[0][0].key.metric)
                if members else ""),
            tags=group_tags,
            aggregate_tags=agg_tags,
            tsuids=tsuids,
            dps=dps,
            annotations=annotations,
            global_annotations=global_notes,
            index=sub.index,
            head=head, stamps=stamps, values=values, block=block, row=row,
        )

    def _run_segment_grouped(self, query: TSQuery, sub: TSSubQuery,
                             seg: Segment, sel: _Selection, windows,
                             global_notes: list, budget,
                             store=None) -> dict[tuple, QueryResult]:
        """All group-by buckets in ONE device dispatch (downsample queries).

        Round 1 looped over buckets in Python — one jitted call per group,
        10k dispatches for BASELINE config 3.  Every bucket now travels in a
        single [S_total, N] batch with a group id per row; on a multi-device
        topology the batch rows are sharded over the mesh (the SaltScanner
        fan-out, TsdbQuery.java:981-1114 reduced to one shard_map call).
        """
        tsdb = self.tsdb
        ds = sub.downsample_spec

        fix = tsdb.config.fix_duplicates
        # Counts first (no copy): budget charging and the streaming
        # decision must not force the whole range into host memory — a
        # 1B-pt query would otherwise materialize twice (full window
        # copies AND chunk buffers).
        with obs_trace.timed_stage("count"):
            scan = self._scan(sel, seg, store)
        if scan is None:
            return {}
        series_list, gid, counts = scan.series, scan.gid, scan.counts
        n_groups = len(scan.groups)
        total_points = int(counts.sum())
        budget.charge(total_points)
        budget.check_deadline()
        # one "pipeline" span covers batch build + the fused dispatch;
        # begin/end (not a with-block) keeps the 5-path dispatch chain
        # un-reindented, and an exception simply leaves the span
        # unfinished inside a request-scoped trace
        psp = obs_trace.begin("pipeline", aggregator=sub.aggregator,
                              downsample=seg.ds_function or ds.function)
        # The window plan materializes ONLY after the budget accepted the
        # scan: EdgeWindows.split builds a [W+1] edge vector sized by the
        # query's range/interval (calendar grids over a year at fine
        # intervals run to millions of edges) — a query the budget
        # refuses, or one that matches no data at all, must never build
        # it.
        window_spec, wargs = windows.split()

        g_pad = pad_pow2(n_groups)
        spec = PipelineSpec(
            aggregator=sub.aggregator,
            downsample=DownsampleStep(
                seg.ds_function or ds.function, window_spec,
                ds.fill_policy, ds.fill_value),
            rate=sub.rate_options if sub.rate else None,
            int_mode=False,
            # gid above is concatenated group runs — non-decreasing by
            # construction; lets sorted reduce modes skip the permute
            rows_sorted=True)

        n_max = int(counts.max())
        ds_fn = seg.ds_function or ds.function
        sketchable, hazard = self._sketch_eligible(seg, ds_fn, windows,
                                                   series_list, counts, fix)
        if hazard:
            self.exec_stats["sketchHazardExact"] = 1.0
        stream_ok = (seg.kind != "rollup_avg"
                     and (ds_fn in STREAMABLE_DS or sketchable))
        self._bump("pointsScanned", total_points)
        self._bump("seriesScanned", len(gid))
        mesh = tsdb.query_mesh()
        use_mesh = (mesh is not None and len(gid) >= tsdb.config.get_int(
            "tsd.query.mesh.min_series"))
        n_chips = 1
        if use_mesh:
            from opentsdb_tpu.parallel.sharded import n_devices
            n_chips = n_devices(mesh)
        # ONE routing verdict for the whole fast-path arbitration
        # (rollup lane -> tiled -> agg rewrite -> device cache ->
        # streamed/mesh/host-lane/resident), computed by the SAME pure
        # plan_decision() the EXPLAIN engine consults — eligibility
        # gates, consult ordering, the shared grid_budget guard, and
        # the path derivation live once (query/plandecision.py), so
        # /api/query/explain and the dispatch below cannot drift.  The
        # decision's stable fingerprint is stamped into the pipeline
        # span and the flight-recorder plan event.
        from opentsdb_tpu.ops.downsample import precompact_base
        from opentsdb_tpu.ops.hostlane import cpu_device, execution_platform
        from opentsdb_tpu.query import plandecision as pdn
        ts_base = precompact_base(
            window_spec, getattr(windows, "first_window_ms", None))
        batcher = getattr(tsdb, "dispatch_batcher", None)
        ctx = pdn.RouteContext(
            seg_kind=seg.kind, ds_fn=ds_fn, aggregator=sub.aggregator,
            has_rate=bool(sub.rate), s=len(gid), n_max=n_max,
            wp=window_spec.count, groups=n_groups, g_pad=g_pad,
            total_points=int(total_points), sketchable=sketchable,
            stream_ok=stream_ok, use_mesh=use_mesh, n_chips=n_chips,
            windows_fixed=isinstance(windows, FixedWindows),
            store_is_raw=store is tsdb.store,
            has_store=store is not None,
            platform=execution_platform(),
            cpu_lane_ok=cpu_device() is not None,
            state_mb=tsdb.config.get_int("tsd.query.streaming.state_mb"),
            point_threshold=tsdb.config.get_int(
                "tsd.query.streaming.point_threshold"),
            host_lane_max=tsdb.config.get_int(
                "tsd.query.host_lane.max_points"),
            ts_base=ts_base,
            batch_ok=batcher is not None and batcher.enabled,
            batch_factor=tsdb.config.get_float(
                "tsd.query.batch.amortize_factor"))
        with obs_trace.timed_stage("consult"):
            pd = pdn.plan_decision(
                tsdb, ctx, _ExecConsults(tsdb, ctx, seg, sub, windows,
                                         store, series_list, fix,
                                         scan.bounds))
        if pd.lane_note is not None:
            obs_trace.annotate(psp, rollup=pd.lane_note)
        if pd.agg_note is not None:
            obs_trace.annotate(psp, agg_cache=pd.agg_note)
        obs_trace.annotate(psp, fingerprint=pd.fingerprint)
        if pd.decisions is not None:
            REGISTRY.counter(
                "tsd.query.group_reduce", "Dispatches by group-reduce "
                "form").labels(mode=pd.decisions["group"]["mode"]).inc()
        # phase boundary: scan + batch shaping + the routing verdict
        # all land in "plan"; the fingerprint keys this request's
        # latency-attribution profile (first segment wins)
        latattr.mark("plan")
        latattr.set_fingerprint(pd.fingerprint)
        if pd.path == "refused":
            # over-budget and untileable: the shared structured 413
            # (the span is left unfinished inside the request trace,
            # exactly as the pre-extraction code did)
            self.exec_stats["tiledRefused"] = 1.0
            raise pd.refusal.exception()
        lane_plan, tiled_plan = pd.lane_plan, pd.tiled_plan
        agg_plan, agg_note, cached = pd.agg_plan, pd.agg_note, pd.cached
        would_stream, host_small = pd.would_stream, pd.host_small
        if cached is not None:
            self.exec_stats["deviceCacheHit"] = 1.0
            if ts_base is not None:
                import jax.numpy as jnp
                wargs = dict(wargs)
                wargs["ts_base"] = jnp.asarray(ts_base, jnp.int64)
        if host_small:
            self.exec_stats["hostLane"] = 1.0
        from opentsdb_tpu.ops.hostlane import host_lane

        batch_info = None
        if lane_plan is not None:
            # Standing fast path: serve the downsample grid from the
            # rollup lane's mergeable partials (storage/rollup.py) —
            # the raw points are never fetched, never streamed.  Exact
            # by derivation; annotated on the span's `rollup` tag.
            out_ts, out_val, out_mask, lanes = self._run_lane_serve(
                spec, seg, lane_plan, series_list, gid, g_pad, windows,
                window_spec, budget, fix, psp)
            self.exec_stats["rollupLane"] = 1.0
            if lane_plan.striped:
                self.exec_stats["rollupLaneStriped"] = 1.0
        elif tiled_plan is not None:
            # Out-of-core: series-tiled streaming with partial-grid
            # spill, window-striped tail replay (ops/tiling.py).  The
            # decision + pool traffic ride the span's `tiling` tag.
            from opentsdb_tpu.ops import tiling
            (out_ts, out_val, out_mask), tile_stats = tiling.run_tiled(
                tsdb, spec, seg, series_list, gid, g_pad, window_spec,
                wargs, ds_fn, lanes_for([ds_fn]), sketchable, fix,
                tiled_plan, budget, store=store)
            lanes = None    # no one lane: a contribution program a tile
            obs_trace.annotate(psp, tiling=tile_stats)
            self.exec_stats["tiledExecution"] = 1.0
            self._bump("spillBytes", float(tile_stats["spillBytes"]))
            self._bump("tiledTiles", float(tile_stats["tiles"]))
        elif agg_plan is not None:
            out_ts, out_val, out_mask, lanes = self._run_agg_rewrite(
                spec, agg_plan, series_list, gid, g_pad, windows,
                window_spec, host_small, budget)
        elif pd.path == "batched":
            # Fused multi-query dispatch (query/batcher.py): this
            # dispatch-bound plan rendezvouses with concurrent
            # compatible plans and executes as one stacked [Q, S, N]
            # kernel with host-side unpack — the per-dispatch floor is
            # paid once per bucket instead of once per query.  The span
            # carries the decisions with the batch.
            from opentsdb_tpu.query.limits import active_deadline
            ts, val, mask, _ = build_batch_direct(
                series_list, seg.start_ms, seg.end_ms, fix)
            (out_ts, out_val, out_mask, lanes), batch_info = \
                tsdb.dispatch_batcher.submit(
                    spec, ts, val, mask, gid, g_pad, wargs,
                    host_small, deadline=active_deadline())
            obs_trace.annotate(psp, batch=batch_info,
                               costmodel=pd.decisions)
            self.exec_stats["batched"] = 1.0
            if batch_info["stacked"]:
                self.exec_stats["batchedStacked"] = 1.0
                self._bump("batchedQ", float(batch_info["q"]))
        elif cached is None and would_stream:
            # Beyond the threshold the batch never materializes: bounded
            # chunks are copied straight out of the store into the device
            # accumulator (SaltScanner overlap analog, VERDICT r1 #4).
            out_ts, out_val, out_mask, lanes = self._stream_grouped(
                spec, seg, series_list, n_max, gid, g_pad, window_spec,
                wargs, sketch=sketchable)
        elif seg.kind == "rollup_avg":
            all_windows = self._materialize_windows(series_list, seg, fix)
            ts, val, mask, _ = build_batch(all_windows)
            cnt_windows = []
            for s in series_list:
                cs = seg.count_lane.get_series(s.key)
                if cs is None:
                    cnt_windows.append(
                        (np.empty(0, np.int64), np.empty(0, np.float64),
                         np.empty(0, np.int64), np.empty(0, bool)))
                else:
                    cnt_windows.append(cs.window(
                        seg.start_ms, seg.end_ms,
                        tsdb.config.fix_duplicates))
            tc, vc, mc, _ = build_batch(cnt_windows)
            with host_lane(host_small):
                out_ts, out_val, out_mask, lanes = \
                    run_group_rollup_avg_pipeline(
                        spec, ts, val, mask, tc, vc, mc, gid, g_pad,
                        wargs)
        else:
            if cached is not None:
                ts, val, mask = cached
            else:
                # single-copy fill straight out of the store buffers
                # (build_batch_direct): a 1M-pt query's window()+pack
                # double copy was ~30% of the host-lane query time
                ts, val, mask, _ = build_batch_direct(
                    series_list, seg.start_ms, seg.end_ms, fix)
            if use_mesh:
                from opentsdb_tpu.parallel import (
                    sharded_query_pipeline, shard_rows)
                from opentsdb_tpu.parallel.sharded import (
                    n_devices, shard_rows_device)
                self.exec_stats["meshDevices"] = float(n_devices(mesh))
                with obs_trace.timed_stage("enqueue"):
                    fn = sharded_query_pipeline(mesh, spec, g_pad)
                    if cached is not None:
                        # cache hit under the mesh: re-lay the device
                        # batch out across the chips (ICI scatter)
                        # instead of a fresh host upload
                        d_ts, d_val, d_mask, d_gid = shard_rows_device(
                            mesh, ts, val, mask, gid, pad_gid_value=g_pad)
                    else:
                        d_ts, d_val, d_mask, d_gid = shard_rows(
                            mesh, ts, val, mask, gid, pad_gid_value=g_pad)
                    out_ts, out_val, out_mask, lanes = fn(
                        d_ts, d_val, d_mask, d_gid, wargs)
            else:
                if n_groups == len(gid) and pd.path in pdn.ROW_GROUP_PATHS:
                    # one member a group, the whole batch in this one
                    # dispatch: row i is group i
                    spec = replace(spec, row_groups=True)
                with obs_trace.timed_stage("enqueue"), host_lane(host_small):
                    out_ts, out_val, out_mask, lanes = run_group_pipeline(
                        spec, ts, val, mask, gid, g_pad, wargs)

        # the arm above returned (dispatch enqueued; results may still
        # be device-resident) — the wait lands in fetch, below
        latattr.mark("dispatch")
        if psp is not None and pd.decisions is not None \
                and pd.path != "batched":
            # the decisions describe the monolithic program (rewritten,
            # tiled and lane-served segments ran others and have none),
            # and a batched one annotated its own
            self._annotate_decisions(psp, pd.decisions)
        obs_trace.end(psp)
        recorder = getattr(tsdb, "flightrec", None)
        if recorder is not None:
            # ONE flight-recorder event per executed pipeline: which
            # path served it and what the fast-path consults decided —
            # the retained form of the span annotations above, so a
            # post-mortem reads routing decisions without any client
            # having asked for showStats.  The fingerprint is the
            # explain-vs-actual parity handle (query/plandecision.py).
            fields = {"path": pd.path, "metric": sub.metric,
                      "series": len(gid), "windows": window_spec.count,
                      "groups": n_groups, "points": total_points,
                      "deviceCacheHit": cached is not None,
                      "fingerprint": pd.fingerprint}
            if tsdb.rollup_lanes is not None:
                fields["rollup"] = ("hit" if lane_plan is not None
                                    else "miss")
            if agg_note is not None:
                fields["aggCache"] = agg_note
            if batch_info is not None:
                fields["batch"] = batch_info
            recorder.record("plan", **fields)
        with obs_trace.timed_stage("fetch"):
            out_ts, out_val, out_mask, lanes = self._materialize_answer(
                out_ts, out_val, out_mask, lanes)
        with obs_trace.timed_stage("extract"):
            if lanes is not None:
                # which lanes the device took (ops/pipeline.py's word)
                REGISTRY.counter(
                    "tsd.query.contrib_lane", "Grouped dispatches by "
                    "the contribution lane the device took").labels(
                        lane="dense" if lanes & LANE_DENSE
                        else "full").inc()
                if spec.rate is not None:
                    REGISTRY.counter(
                        "tsd.query.rate_lane", "Grouped rate dispatches "
                        "by the lane that found the previous points"
                    ).labels(lane="shift" if lanes & LANE_SHIFT
                             else "scan").inc()
            # device->host materialization (fetch, above) is where an
            # async dispatch actually blocks
            latattr.mark("device_wait")
            stamps, rows, block = extract_grid(
                out_ts, out_val[:n_groups], out_mask[:n_groups],
                seg.start_ms, seg.end_ms,
                keep_nans=sub.fill_policy != "none")
        with obs_trace.timed_stage("assemble"):
            members = sel.members
            meta = sel.meta(tsdb, sub.metric or (
                tsdb.metrics.get_name(series_list[0].key.metric)))
            results: dict[tuple, QueryResult] = {}
            for i, (g, ts_col, values) in enumerate(
                    zip(scan.groups.tolist(), stamps, rows)):
                results[sel.str_keys[g]] = self._assemble_result(
                    query, sub, members[g], None, global_notes, meta[g],
                    ts_col, values, block, i)
        REGISTRY.counter(
            "tsd.query.series", "Rows (member series) dispatched by "
            "grouped downsample queries").inc(len(gid))
        REGISTRY.counter(
            "tsd.query.groups", "Groups answered by grouped downsample "
            "queries").inc(n_groups)
        return results

    @staticmethod
    def _annotate_decisions(span, decisions: dict) -> None:
        """The pipeline span's `costmodel` tag: every kernel-axis
        decision (chosen form, per-candidate predicted ms) of the plan's
        one recomputation (plan_decision), which the fingerprint and
        explain describe too."""
        obs_trace.annotate(span, costmodel=decisions)
        for axis, report in decisions.items():
            if not report["feasible"]:
                # the kernels' feasibility guards make this unreachable;
                # a nonzero counter means a guard regressed and an
                # OOM-class mode is about to dispatch
                REGISTRY.counter(
                    "tsd.costmodel.infeasible",
                    "Strategy decisions outside the feasible candidate "
                    "set (must stay 0)").labels(axis=axis).inc()

    @staticmethod
    def _host_window_ids(windows, tsb):
        """Window id per timestamp, host-side, for every window plan."""
        if isinstance(windows, FixedWindows):
            return (np.asarray(tsb, np.int64)
                    - windows.first_window_ms) // windows.interval_ms
        if isinstance(windows, EdgeWindows):
            return np.searchsorted(np.asarray(windows.edges, np.int64),
                                   tsb, "right") - 1
        return np.zeros(len(tsb), np.int64)    # AllWindow: one cell

    @staticmethod
    def _materialize_windows(series_list, seg, fix):
        """Full window copies for the sub-threshold (one-batch) paths."""
        return [s.window(seg.start_ms, seg.end_ms, fix) for s in series_list]

    @staticmethod
    def _materialize_agg_piece(v, m, count: int):
        """Host copies of one computed piece's [S, count] grid slice
        (`_materialize` prefix: this is a sanctioned device->host
        result materialization, like the extract stage's)."""
        return (np.asarray(v)[:, :count], np.asarray(m)[:, :count])

    @staticmethod
    def _materialize_answer(out_ts, out_val, out_mask, lanes):
        """Host copies of a grouped dispatch's answer and of the lanes
        word its program made, in ONE fetch: device_get starts every
        copy before it waits for the first, where an np.asarray each in
        turn pays a blocking transfer's latency (~0.4 ms on the chip,
        PERF.md section 6, PR 28).  Host arrays and None pass through.
        (`_materialize` prefix: the sanctioned device->host result
        materialization of the extract stage.)"""
        return jax.device_get((out_ts, out_val, out_mask, lanes))

    def _run_agg_rewrite(self, spec, plan, series_list, gid, g_pad,
                         windows, window_spec, host_small, budget):
        """Execute a partial-aggregate rewrite (storage/agg_cache.py).

        Cached blocks replay their stored [S, B] downsample grids;
        uncovered pieces dispatch the SAME downsample-only program a
        cold run uses (run_downsample_grid) over exactly their
        sub-range, so a warm answer is bit-identical to a cold one by
        construction.  The assembled [S, W] grid then runs the shared
        tail (rate -> group -> aggregate) — the streaming executor's
        finish program — and freshly computed full blocks are stored
        back (generation-guarded: a dirty mark that landed since
        planning discards the insert)."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.hostlane import host_lane
        from opentsdb_tpu.ops.pipeline import (
            DownsampleStep, assemble_grid, build_batch_direct,
            run_downsample_grid, run_grid_tail)
        tsdb = self.tsdb
        fix = tsdb.config.fix_duplicates
        step0 = spec.downsample
        interval = windows.interval_ms
        s = len(series_list)
        pieces_v: list = []
        pieces_m: list = []
        with obs_trace.timed_stage("rewrite"), host_lane(host_small):
            with obs_trace.timed_stage("rw_pieces"):
                for piece in plan.pieces:
                    if piece.cached is not None:
                        # cached entries hold their FULL row set; narrow
                        # to this query's rows unless they already match
                        # (the exact-repeat hot path serves zero-copy)
                        v, m = piece.cached
                        rows = piece.rows
                        identity = (v.shape[0] == len(rows)
                                    and np.array_equal(
                                        rows, np.arange(len(rows))))
                        if not identity and piece.tier == "agg_device":
                            rdev = jnp.asarray(rows)
                            v = jnp.take(v, rdev, axis=0)
                            m = jnp.take(m, rdev, axis=0)
                        elif not identity:
                            v = v[rows]
                            m = m[rows]
                        pieces_v.append(v)
                        pieces_m.append(m)
                        self._bump("aggCacheHitWindows", piece.count)
                        continue
                    budget.check_deadline()
                    # delta fetch composes with the device series cache:
                    # pinned HBM columns serve the piece's [S, n] batch as
                    # an on-device gather (zero host copy); cold/stale
                    # falls back to the host build.  Either source hands
                    # the SAME values at the same pow2-padded shape to the
                    # same program, so the block's bits do not depend on
                    # which one answered.
                    batch = None
                    if tsdb.device_cache is not None:
                        batch = tsdb.device_cache.batch_for(
                            plan.store, plan.metric, series_list,
                            piece.fetch_lo, piece.fetch_hi, fix,
                            build=False)
                    if batch is not None:
                        ts, val, mask = batch
                    else:
                        ts, val, mask, _ = build_batch_direct(
                            series_list, piece.fetch_lo, piece.fetch_hi,
                            fix)
                    sub_win = FixedWindows(interval, piece.first_ms,
                                           piece.count)
                    wspec, wargs = sub_win.split()
                    sub_step = DownsampleStep(step0.function, wspec,
                                              step0.fill_policy,
                                              step0.fill_value)
                    _wts, v, m = run_downsample_grid(sub_step, ts, val,
                                                     mask, wargs)
                    self._bump("aggCacheComputedWindows", piece.count)
                    if piece.block is not None:
                        # a fresh full block goes to the host tier once
                        vn, mn = self._materialize_agg_piece(v, m,
                                                             piece.count)
                        tsdb.agg_cache.store_block(plan, piece,
                                                   series_list, vn, mn)
                    # pieces stay padded: the assembly takes each one's
                    # first piece.count columns
                    pieces_v.append(v)
                    pieces_m.append(m)
            n_cached = sum(p.cached is not None for p in plan.pieces)
            pieces_c = REGISTRY.counter(
                "tsd.query.rewrite.pieces", "Pieces of partial-aggregate "
                "rewrites, by kind")
            pieces_c.labels(kind="cached").inc(n_cached)
            pieces_c.labels(kind="computed").inc(
                len(plan.pieces) - n_cached)
            wp = window_spec.count
            counts = [p.count for p in plan.pieces]
            with obs_trace.timed_stage("rw_assemble"):
                if host_small:
                    # the host lane's device is the CPU, where the
                    # device-tier blocks are not: copy every piece into
                    # a host grid
                    v_full = np.zeros((s, wp), np.float64)
                    m_full = np.zeros((s, wp), bool)
                    col = 0
                    for v, m, count in zip(pieces_v, pieces_m, counts):
                        v_full[:, col:col + count], \
                            m_full[:, col:col + count] = \
                            self._materialize_agg_piece(v, m, count)
                        col += count
                else:
                    # one placement program a piece width, traced
                    # offsets: no compile per slide, no host round trip
                    v_full, m_full = assemble_grid(
                        zip(pieces_v, pieces_m, counts), s, wp)
            REGISTRY.counter(
                "tsd.query.rewrite.assembly", "Partial-aggregate "
                "rewrites, by the lane that assembled the grid").labels(
                    lane="host" if host_small else "device").inc()
            with obs_trace.timed_stage("tail"):
                # the monolithic grid's timestamps: first + i * interval
                # over the padded window count, int64 (window_timestamps)
                wts = (windows.first_window_ms
                       + np.arange(wp, dtype=np.int64) * interval)
                out = run_grid_tail(spec, jnp.asarray(wts), v_full, m_full,
                                    jnp.asarray(gid), g_pad)
        if plan.cached_windows:
            self.exec_stats["aggCacheHit"] = 1.0
        return out

    def _sketch_eligible(self, seg: Segment, ds_fn: str, windows,
                         series_list, counts: np.ndarray, fix: bool
                         ) -> tuple[bool, bool]:
        """(sketchable, hazard_fallback) for one grouped segment —
        shared by the executor and the explain engine (read-only store
        walk, no dispatch).

        Auto-protect (VERDICT r3 #7): a (series, window) cell drifts
        ~merges/(2K) of its population in rank; when the densest cell
        would absorb more chunk merges than the configured bound
        (window span >> chunk span — the "0all over a year" shape),
        fall back to the exact path, which the scan budgets either
        serve materialized or refuse with the 413 contract.  The
        estimate is skew-exact (review r4): per series, the window ids
        of the streaming CHUNK BOUNDARIES (every n_chunk-th point,
        O(points/chunk) to fetch) are counted — a cell's merge count
        is that window's boundary multiplicity + 1, so points
        concentrated in one window are seen as the many merges they
        cause, not averaged away."""
        tsdb = self.tsdb
        sketchable = (is_sketch_ds(ds_fn) and tsdb.config.get_bool(
            "tsd.query.streaming.sketch_percentiles"))
        if not sketchable:
            return False, False
        max_merges = tsdb.config.get_int(
            "tsd.query.streaming.sketch_max_merges")
        if max_merges <= 0:
            return True, False
        chunk_points = max(tsdb.config.get_int(
            "tsd.query.streaming.chunk_points"), 1)
        n_chunk = pad_pow2(max(1024, chunk_points // max(len(counts), 1)))
        worst = 0
        # a row of a single chunk has no merges at all
        for row in np.flatnonzero(counts > n_chunk).tolist():
            tsb = series_list[row].window_stride_timestamps(
                seg.start_ms, seg.end_ms, n_chunk, fix)
            wids = self._host_window_ids(windows, tsb)
            if len(wids):
                worst = max(worst, int(np.max(
                    np.unique(wids, return_counts=True)[1])))
        if worst + 1 > max_merges:
            return False, True
        return True, False

    def _run_lane_serve(self, spec, seg, plan, series_list, gid,
                        g_pad: int, windows, window_spec,
                        budget, fix: bool, psp):
        """Serve a lane-derivable plan from materialized rollup lanes.

        Interior full windows re-reduce from the lane's mergeable
        partials (storage/rollup.py derive_grid — exact; bitwise vs
        the raw kernel on integer data); the <= 2 edge windows with
        partial point populations recompute from raw points via the
        SAME downsample-only program the agg cache's delta pieces use;
        the assembled [S, Wp] grid runs the shared tail.  Over-budget
        grids reuse the PR 10 spill pool's window-striped tail replay
        with lane-derived tile grids (run_tiled tile_grid_fn)."""
        import jax.numpy as jnp
        from opentsdb_tpu.ops.downsample import (FILL_NONE, FILL_SCALAR,
                                                 FILL_ZERO)
        from opentsdb_tpu.ops.hostlane import cpu_device, host_lane
        from opentsdb_tpu.ops.pipeline import (
            DownsampleStep, build_batch_direct, run_downsample_grid,
            run_grid_tail)
        tsdb = self.tsdb
        ds_step = spec.downsample
        ds_fn = ds_step.function
        interval = windows.interval_ms
        first = windows.first_window_ms
        w = windows.count
        wp = window_spec.count
        s = len(series_list)
        # windows the lane cannot serve: the <= 2 partial edge windows
        edges = []
        if plan.wf_lo > 0:
            edges.append((0, seg.start_ms,
                          min(first + interval - 1, seg.end_ms)))
        if plan.wf_hi < w - 1:
            edges.append((w - 1, first + (w - 1) * interval,
                          seg.end_ms))
        # the grid's padded-column content under this fill policy,
        # mirroring apply_fill over non-live windows (values under a
        # False mask are never consumed; matching them keeps the grid
        # byte-comparable to the monolithic one)
        if ds_step.fill_policy == FILL_NONE:
            pad_val = np.nan
        elif ds_step.fill_policy == FILL_ZERO:
            pad_val = 0.0
        elif ds_step.fill_policy == FILL_SCALAR:
            pad_val = float(ds_step.fill_value)
        else:
            pad_val = np.nan

        def edge_cols(row_lo: int, row_hi: int):
            """[(window idx, vals[rows, 1], mask[rows, 1])] computed
            fresh from raw points — identical program to a cold run's."""
            out = []
            for (w_i, lo_ms, hi_ms) in edges:
                ts, val, mask, _ = build_batch_direct(
                    series_list[row_lo:row_hi], lo_ms, hi_ms, fix)
                sub_win = FixedWindows(interval,
                                       first + w_i * interval, 1)
                wspec2, wargs2 = sub_win.split()
                sub_step = DownsampleStep(ds_fn, wspec2,
                                          ds_step.fill_policy,
                                          ds_step.fill_value)
                _wt, v, m = run_downsample_grid(sub_step, ts, val,
                                                mask, wargs2)
                out.append((w_i, np.asarray(v)[:, :1],
                            np.asarray(m)[:, :1]))
            return out

        def assemble(row_lo: int, row_hi: int):
            rows = row_hi - row_lo
            v = np.full((rows, wp), pad_val, np.float64)
            m = np.zeros((rows, wp), bool)
            iv, im = tsdb.rollup_lanes.derive_grid(
                plan, ds_fn, ds_step.fill_policy, ds_step.fill_value,
                row_lo, row_hi)
            v[:, plan.wf_lo:plan.wf_hi + 1] = iv
            m[:, plan.wf_lo:plan.wf_hi + 1] = im
            for (w_i, ev, em) in edge_cols(row_lo, row_hi):
                v[:, w_i:w_i + 1] = ev
                m[:, w_i:w_i + 1] = em
            return v, m

        wts = first + np.arange(wp, dtype=np.int64) * interval
        budget.check_deadline()
        if not plan.striped:
            # small-grid fast lane: the serve's work is the [S, Wp]
            # grid (the raw points are never touched), so host-lane
            # eligibility keys on CELLS against the same threshold
            # the point-count paths use
            lane_host_small = (cpu_device() is not None
                               and 0 < s * wp <= tsdb.config.get_int(
                                   "tsd.query.host_lane.max_points"))
            with host_lane(lane_host_small):
                v_full, m_full = assemble(0, s)
                out = run_grid_tail(spec, jnp.asarray(wts), v_full,
                                    m_full, jnp.asarray(gid), g_pad)
            if lane_host_small:
                self.exec_stats["hostLane"] = 1.0
            return out
        # over-budget: the full [S, Wp] grid never goes to the device.
        # Moment-decomposable cross-series aggregators FOLD tile by
        # tile — each [S_tile, Wp] lane grid runs the row-local
        # contribution step + a straight-to-[G, W] partial reduce on
        # device, partials merge by +/min/max/| on the host, and one
        # finish reproduces moment_group_reduce's arithmetic on
        # identical operands (the mesh's combine_* decomposition
        # applied to tiles).  Everything else (dev, rank/order aggs)
        # keeps the PR 10 spill pool's window-striped tail replay:
        # contributions are row-local over the FULL width, so tiles
        # compute them on [S_tile, Wp] grids and the pool re-orders
        # their stripes for the window-local tail.
        from opentsdb_tpu.ops import tiling
        tp = plan.tile_plan
        agg_name = spec.aggregator
        # one HOST assembly feeds every striped mode: lane cells are
        # host-resident anyway, and [S, Wp] at 9 B/cell is smaller
        # than the lane partials backing it (28 B/cell)
        v_full, m_full = assemble(0, s)
        gid_np = np.asarray(gid, np.int64)
        extreme = agg_name in ("min", "mimmin", "max", "mimmax")
        foldable = agg_name in tiling.LANE_FOLDABLE
        # the device fold holds one tile's grid AND the [G, W]
        # partial-moment outputs on device — it sizes its OWN tiles
        # against what the budget leaves after the partials (the
        # replay path's tile sizing reserves stripe space instead).
        # The host-dense fold below holds NOTHING on device (pure
        # numpy) and needs no budget at all.
        budget_bytes = self.tsdb.config.get_int(
            "tsd.query.streaming.state_mb") * 2 ** 20
        fold_rows = (budget_bytes - 3 * g_pad * wp * 8) // (wp * 19)
        fold_dev_ok = foldable and fold_rows >= 1
        if foldable and spec.rate is None \
                and bool(np.all(m_full[:, :w])):
            # DENSE rate-free grid (every live cell populated — the
            # regular-cadence common case): grid_contributions is the
            # identity (contrib == values, participate == mask,
            # exactly).  Its own lax.cond fast lane asks less (no row
            # with a hole BETWEEN two present windows), which this
            # test implies.  There is no
            # rate pass, so the per-tile device work degenerates to
            # group-partial sums the host computes directly at memcpy
            # speed.  Rate queries take the device fold below, whose
            # _tile_contrib applies rate row-locally per tile.
            # Arithmetic mirrors moment_group_reduce's finish on
            # identical operands — bit-identical on integer data; gid
            # is non-decreasing group runs (rows_sorted), so reduceat
            # folds each run.
            ok = m_full & ~np.isnan(v_full)
            starts = np.flatnonzero(np.diff(gid_np, prepend=-1))
            kg = len(starts)
            cnt = np.zeros((g_pad, wp), np.int64)
            present = np.zeros((g_pad, wp), np.int64)
            cnt[:kg] = np.add.reduceat(ok.astype(np.int64), starts,
                                       axis=0)
            present[:kg] = np.add.reduceat(m_full.astype(np.int64),
                                           starts, axis=0)
            if extreme:
                want_min = agg_name in ("min", "mimmin")
                ident = np.inf if want_min else -np.inf
                red = np.minimum.reduceat if want_min \
                    else np.maximum.reduceat
                out_val = np.full((g_pad, wp), ident, np.float64)
                out_val[:kg] = red(np.where(ok, v_full, ident),
                                   starts, axis=0)
            elif agg_name == "count":
                out_val = cnt.astype(np.float64)
            elif agg_name == "avg":
                tot = np.zeros((g_pad, wp), np.float64)
                tot[:kg] = np.add.reduceat(
                    np.where(ok, v_full, 0.0), starts, axis=0)
                out_val = tot / np.maximum(cnt, 1)
            else:
                out_val = np.zeros((g_pad, wp), np.float64)
                out_val[:kg] = np.add.reduceat(
                    np.where(ok, v_full, 0.0), starts, axis=0)
            if agg_name != "count":
                out_val = np.where(cnt > 0, out_val, np.nan)
            obs_trace.annotate(psp, rollup_fold="host_dense")
            return wts, out_val, present > 0, None
        if fold_dev_ok:
            # holes in the grid: interpolation/participation must run
            # (row-local, full-width) — fold tile by tile on device
            # into [G, W] partial moments (the mesh's combine_*
            # decomposition applied to tiles); merged partials finish
            # with moment_group_reduce's arithmetic
            cnt = np.zeros((g_pad, wp), np.int64)
            present = np.zeros((g_pad, wp), np.int64)
            tot = np.zeros((g_pad, wp), np.float64)
            lo_acc = np.full((g_pad, wp), np.inf, np.float64)
            hi_acc = np.full((g_pad, wp), -np.inf, np.float64)
            wts_dev = jnp.asarray(wts)
            fold_rows = min(int(fold_rows), s)
            for t_lo in range(0, s, fold_rows):
                t_hi = min(t_lo + fold_rows, s)
                budget.check_deadline()
                parts = tiling.run_lane_fold(
                    spec, g_pad, extreme, wts_dev,
                    v_full[t_lo:t_hi], m_full[t_lo:t_hi],
                    jnp.asarray(gid_np[t_lo:t_hi]))
                if extreme:
                    plo, phi, pc, pp = (np.asarray(a) for a in parts)
                    lo_acc = np.minimum(lo_acc, plo)
                    hi_acc = np.maximum(hi_acc, phi)
                else:
                    pt, pc, pp = (np.asarray(a) for a in parts)
                    tot += pt
                cnt += pc
                present += pp
            safe = np.maximum(cnt, 1)
            if extreme:
                out_val = lo_acc if agg_name in ("min", "mimmin") \
                    else hi_acc
            elif agg_name == "count":
                out_val = cnt.astype(np.float64)
            elif agg_name == "avg":
                out_val = tot / safe
            else:
                out_val = tot
            if agg_name != "count":
                out_val = np.where(cnt > 0, out_val, np.nan)
            obs_trace.annotate(psp, rollup_fold=True)
            return wts, out_val, present > 0, None

        def tile_grid(row_lo: int, row_hi: int):
            return (wts, v_full[row_lo:row_hi], m_full[row_lo:row_hi])

        (out_ts, out_val, out_mask), tile_stats = tiling.run_tiled(
            tsdb, spec, seg, series_list, gid, g_pad, window_spec,
            {}, ds_fn, (), False, fix, plan.tile_plan, budget,
            store=tsdb.store, tile_grid_fn=tile_grid)
        obs_trace.annotate(psp, tiling=tile_stats)
        self._bump("spillBytes", float(tile_stats["spillBytes"]))
        return out_ts, out_val, out_mask, None

    def _stream_grouped(self, spec: PipelineSpec, seg, series_list,
                        max_len: int, gid, g_pad: int, window_spec, wargs,
                        sketch: bool = False):
        """Chunked execution: fold bounded [S, n] slices into the device
        accumulator, then run the shared grid tail.

        Chunks are per-series point-index ranges (each series' own chunks
        are time-ordered, which is all the associative moment merge needs),
        so every chunk has the same [S, n_chunk] shape — one compile.  The
        host packs chunk k+1 while the device reduces chunk k (JAX async
        dispatch = the ScannerCB overlap, SaltScanner.java:463).

        Each chunk is copied straight out of the store by the chunk
        packer (storage/chunk_pack.py): every series' window bounds are
        taken once, under its lock, and a chunk is two bulk copies over
        all rows into buffers the packer reuses; a row whose series
        moved mid-scan falls back to the locked timestamp-cursor read
        (window_chunk).  The full range is NEVER materialized on the
        host, so host RAM stays O(store + chunk).  Like the reference's
        scanner over live HBase rows, the pass has no snapshot
        isolation: writes landing mid-query may or may not be seen
        (SaltScanner.java:269).
        """
        import jax.numpy as jnp
        tsdb = self.tsdb
        fix = tsdb.config.fix_duplicates
        s = len(series_list)
        chunk_points = max(tsdb.config.get_int(
            "tsd.query.streaming.chunk_points"), 1)
        n_chunk = pad_pow2(max(1024, chunk_points // max(s, 1)))

        # Streaming composes with the mesh (VERDICT r2 missing #3): beyond-
        # memory queries shard the accumulator rows over every chip, so the
        # per-chip footprint is O(S/n_chips * W + chunk) and the finish
        # combines over ICI — concurrent salt buckets × incremental
        # callbacks (SaltScanner.java:269 × :463) in one composition.
        lanes = lanes_for([spec.downsample.function])
        mesh = tsdb.query_mesh()
        use_sharded = (mesh is not None and s >= tsdb.config.get_int(
            "tsd.query.mesh.min_series"))
        # The accumulator grid is O(S x W x lane bytes): a fine downsample
        # over a huge range (10s windows x a year -> millions of windows)
        # would OOM the device mid-query.  The caller already routed
        # over-budget plans to the tiled executor (or raised); this
        # re-check through the SAME shared guard is defense in depth
        # for direct callers.  The limit is PER CHIP: the sharded path
        # splits rows over the mesh, so its estimate divides by the
        # device count.  The sketch lane dominates when present (K
        # float32 summary points + the count lane per cell).
        from opentsdb_tpu.ops.streaming import SKETCH_K
        from opentsdb_tpu.query.limits import grid_budget
        per_cell = 8 + 8 * len(lanes) + (4 * SKETCH_K if sketch else 0)
        n_chips = 1
        if use_sharded:
            from opentsdb_tpu.parallel.sharded import n_devices
            n_chips = n_devices(mesh)
        gbd = grid_budget(
            "streaming",
            tsdb.config.get_int("tsd.query.streaming.state_mb"),
            s * window_spec.count * per_cell // n_chips,
            s, window_spec.count, sketch=sketch)
        if gbd.over:
            raise gbd.exception()
        # Both accumulators are created AFTER the first chunk is packed:
        # its observed window span sizes the sliced-update window
        # (wider-than-data grids fold each chunk into an O(S*wc) state
        # slice instead of touching the whole [S, W] grid — the r04b
        # chip session measured 4.7s/chunk on config 2's 721k-window
        # grid with full-grid folds; the sharded form slices each chip's
        # [S_local, W] state the same way).
        acc = None          # StreamAccumulator | ShardedStreamAccumulator
        if use_sharded:
            from opentsdb_tpu.parallel.sharded import (n_devices,
                                                       padded_rows)
            s_rows = padded_rows(mesh, s)    # pack padded: no re-copy
            self.exec_stats["meshDevices"] = float(n_devices(mesh))
        else:
            s_rows = s

        def make_acc(wslice):
            if use_sharded:
                from opentsdb_tpu.parallel import ShardedStreamAccumulator
                return ShardedStreamAccumulator(
                    mesh, s, window_spec, wargs, sketch=sketch,
                    lanes=lanes, window_slice=wslice)
            return StreamAccumulator.create(
                s, window_spec, wargs, sketch=sketch, lanes=lanes,
                window_slice=wslice)

        n_chunks_total = -(-max_len // n_chunk)
        self._bump("streamedChunks", n_chunks_total)
        use_slice = window_spec.kind == "fixed"
        first_ms = int(np.asarray(wargs["first"])) if use_slice else 0
        interval = window_spec.interval_ms
        # what the route did, for /api/stats/prometheus: chunks folded (a
        # skipped empty chunk is not one) by the fold each took, the
        # mask-true points handed to the device, and the bytes uploaded
        # for them, padding included
        folded = {"sliced": 0, "full": 0}
        points = upload_bytes = 0
        with obs_trace.timed_stage("stream_pack"):
            # every series' window bounds, once, under its lock
            packer = ChunkPacker(series_list, seg.start_ms, seg.end_ms,
                                 n_chunk, s_rows, fix)
        for chunk_i in range(n_chunks_total):
            with obs_trace.timed_stage("stream_wait"):
                # the buffers about to be refilled: their upload is done
                packer.reclaim()
            with obs_trace.timed_stage("stream_pack"):
                chunk = packer.fill()
            if chunk is None:
                # a pointless chunk folds nothing: skip it — and, when
                # the accumulator doesn't exist yet, WITHOUT creating
                # it, so the window_slice sizing below sees the first
                # chunk that actually has points (ADVICE r4: an empty
                # first chunk used to pin window_slice=None and every
                # later chunk paid the full-grid O(S*W) fold)
                continue
            ts, val, mask, tmin, tmax, m = chunk
            points += m
            if acc is None:
                wslice = None
                if use_slice:
                    # 2x the first chunk's span: headroom for later
                    # chunks (series advance on their own cursors, so
                    # spans vary); a chunk that still overflows just
                    # takes the full-grid fold below
                    wslice = 2 * ((tmax - tmin) // interval + 2)
                acc = make_acc(wslice)
            w0 = None
            if acc.window_slice is not None \
                    and (tmax - tmin) // interval + 2 <= acc.window_slice:
                w0 = (tmin - first_ms) // interval
            with obs_trace.timed_stage("stream_upload"):
                # both accumulators upload the host chunk themselves and
                # hand the device arrays back: the packer's gate
                packer.uploaded(acc.update(ts, val, mask, w0=w0))
            folded["full" if w0 is None else "sliced"] += 1
            upload_bytes += ts.nbytes + val.nbytes + mask.nbytes
            if (chunk_i + 1) % 16 == 0:
                # Backpressure: updates enqueue asynchronously, and a long
                # scan would otherwise stage hundreds of chunk transfers
                # (GBs) ahead of the device.  Fetching one scalar of the
                # accumulator state every 16th chunk waits for the folds
                # enqueued so far, which bounds what is in flight; the
                # cadence keeps the host-pack / device-fold overlap in
                # between.
                with obs_trace.timed_stage("stream_wait"):
                    np.asarray(acc.state["n"][:1, :1])

        if acc is None:     # zero chunks (empty range): empty state
            acc = make_acc(None)
        with obs_trace.timed_stage("stream_wait"):
            # the one read every sliced scan ends on: it waits for the
            # folds still in flight
            oob = acc.oob_count()
            # and the chunk buffers go to the next scan once the last
            # uploads have read them
            packer.close()
        self._count_stream(folded, points, upload_bytes,
                           {"bulk": packer.rows_bulk,
                            "cursor": packer.rows_cursor})
        if oob:
            # w0 = floor((chunk_min - first)/interval) with wc >= the
            # chunk's span makes this impossible; a nonzero count means
            # dropped points, never serve a wrong answer
            raise RuntimeError(
                "internal: %d points fell outside their declared "
                "streaming window slice" % oob)
        if use_sharded:
            return acc.finish_tail(spec, gid, g_pad)
        step = spec.downsample
        wts, v, m = acc.finish(step.function, step.fill_policy,
                               step.fill_value)
        return run_grid_tail(spec, wts, v, m, jnp.asarray(gid), g_pad)

    @staticmethod
    def _count_stream(folded: dict[str, int], points: int,
                      upload_bytes: int, rows: dict[str, int]) -> None:
        """One streamed request's counters (tsd.query.stream.*)."""
        REGISTRY.counter(
            "tsd.query.stream.requests", "Grouped segments answered by "
            "the streamed fold").inc()
        REGISTRY.counter(
            "tsd.query.stream.chunks", "Chunks the streamed fold folded "
            "into its device state").inc(sum(folded.values()))
        REGISTRY.counter(
            "tsd.query.stream.points", "Stored points the streamed fold "
            "handed to the device").inc(points)
        REGISTRY.counter(
            "tsd.query.stream.upload_bytes", "Bytes of the chunk arrays "
            "the streamed fold uploaded, padding included").inc(
                upload_bytes)
        for lane, n in folded.items():
            REGISTRY.counter(
                "tsd.query.stream.fold", "Chunks of the streamed fold, "
                "by the update each took").labels(lane=lane).inc(n)
        for lane, n in rows.items():
            REGISTRY.counter(
                "tsd.query.stream.rows", "Series rows of the folded "
                "chunks, by the lane that filled each").labels(
                    lane=lane).inc(n)

    # Cap on groups fused into one batched union dispatch (the tile
    # budget divides by the batch size, so bigger fusions trade tile
    # granularity for dispatch count).
    _UNION_BATCH_MAX = 64

    def _run_segment_union(self, query: TSQuery, sub: TSSubQuery,
                           seg: Segment, groups, global_notes: list,
                           budget) -> dict[tuple, QueryResult]:
        """Union-timestamp aggregation (no downsample step).

        Union timestamps differ per bucket (AggregationIterator semantics
        at the union of member timestamps, with int_mode preserving Java
        long arithmetic), but groups whose padded [S, N] batch shapes
        match fuse into ONE vmapped dispatch — a 10k-host fleet of
        same-cadence series answers in a handful of dispatches instead of
        10k (round 1's per-group loop, the last per-group dispatch path).
        """
        from opentsdb_tpu.ops.hostlane import cpu_device, host_lane
        from opentsdb_tpu.ops.union_agg import _UNION_TILE_CELLS

        tsdb = self.tsdb
        fix = tsdb.config.fix_duplicates
        results: dict[tuple, QueryResult] = {}
        host_max = tsdb.config.get_int("tsd.query.host_lane.max_points")

        def flush(int_mode: bool, chunk: list) -> None:
            """Dispatch up to _UNION_BATCH_MAX same-shaped groups and
            assemble their results (releases the held batches)."""
            psp = obs_trace.begin("pipeline", aggregator=sub.aggregator,
                                  union=True, groups=len(chunk))
            # fast lane per dispatch: the flush's real point count is the
            # summed mask (padding excluded)
            host_small = (host_max > 0 and cpu_device() is not None
                          and sum(int(c[4].sum()) for c in chunk)
                          <= host_max)
            if host_small:
                self.exec_stats["hostLane"] = 1.0
            spec = PipelineSpec(
                aggregator=sub.aggregator,
                downsample=None,
                rate=sub.rate_options if sub.rate else None,
                int_mode=int_mode)
            if len(chunk) == 1:
                _, _, ts, val, mask = chunk[0]
                with host_lane(host_small):
                    outs = [run_pipeline(spec, ts, val, mask, None)]
            else:
                bspec = PipelineSpec(
                    aggregator=spec.aggregator, downsample=None,
                    rate=spec.rate, int_mode=int_mode,
                    tile_cells=max(_UNION_TILE_CELLS // len(chunk), 1))
                with host_lane(host_small):
                    bt, bv, bm = run_union_batch_pipeline(
                        bspec,
                        np.stack([c[2] for c in chunk]),
                        np.stack([c[3] for c in chunk]),
                        np.stack([c[4] for c in chunk]))
                bt, bv, bm = (np.asarray(bt), np.asarray(bv),
                              np.asarray(bm))
                outs = [(bt[i], bv[i], bm[i]) for i in range(len(chunk))]
            obs_trace.end(psp)
            for (group_key, members, *_), (o_ts, o_val, o_mask) \
                    in zip(chunk, outs):
                dps = extract_dps(np.asarray(o_ts), np.asarray(o_val),
                                  np.asarray(o_mask), seg.start_ms,
                                  seg.end_ms,
                                  int_mode and not sub.rate,
                                  keep_nans=sub.fill_policy != "none")
                results[tuple(map(str, group_key))] = \
                    self._assemble_result(query, sub, members, dps,
                                          global_notes)

        # materialize + budget-charge per group, bucketing by the shape
        # class (padded dims + int_mode) one dispatch can serve; full
        # buckets flush IMMEDIATELY so host memory holds at most
        # _UNION_BATCH_MAX batches per shape class (not the whole fleet)
        # and the deadline keeps interleaving with the dispatches.
        buckets: dict = {}
        for group_key in sorted(groups, key=lambda k: tuple(map(str, k))):
            members = groups[group_key]
            batch_windows = [
                s.window(seg.start_ms, seg.end_ms, fix)
                for s, _ in members]
            points = sum(len(w[0]) for w in batch_windows)
            if not points:
                continue
            budget.charge(points)
            budget.check_deadline()
            ts, val, mask, all_int = build_batch(batch_windows)
            int_mode = all_int and seg.kind == "raw"
            key = (ts.shape, int_mode)
            bucket = buckets.setdefault(key, [])
            bucket.append((group_key, members, ts, val, mask))
            if len(bucket) >= self._UNION_BATCH_MAX:
                flush(int_mode, buckets.pop(key))
                budget.check_deadline()
        for (_, int_mode), chunk in buckets.items():
            flush(int_mode, chunk)
            budget.check_deadline()
        return results

    # -- histogram queries (TsdbQuery.isHistogramQuery :806-812 routes
    #    percentiles/show_histogram_buckets to runHistogramAsync :788) ----

    def _run_histogram_sub(self, query: TSQuery, sub: TSSubQuery,
                           budget=None) -> list[QueryResult]:
        from opentsdb_tpu.histogram.kernels import (accumulate_rows,
                                                    percentile_rows)
        from opentsdb_tpu.histogram.store import assemble_columnar
        from opentsdb_tpu.ops.hostlane import cpu_device, host_lane
        tsdb = self.tsdb
        if tsdb.histogram_store is None:
            raise ValueError("histograms are not configured "
                             "(tsd.core.histograms.config)")
        metric_uid = tsdb.metrics.get_id(sub.metric)
        filter_tagks = {f.tagk for f in sub.filters}
        matched = []
        for series in tsdb.histogram_store.series_for_metric(metric_uid):
            tags = tsdb.resolve_key_tags(series.key)
            if sub.explicit_tags and set(tags) != filter_tagks:
                continue
            if all(f.match(tags) for f in sub.filters):
                matched.append((series, tags))
        groups = self._group(matched, sub)
        interval_ms = (sub.downsample_spec.interval_ms
                       if sub.downsample_spec is not None else 0)
        ordered = [(gk, [s for s, _ in groups[gk]]) for gk in
                   sorted(groups, key=lambda k: tuple(map(str, k)))]
        results: list[QueryResult] = []
        # budget/deadline BEFORE any assembly work, like the scalar path
        # (the limit must bound work done, review r4)
        total_points = 0
        for _, members in ordered:
            pts = sum(s.count_in_range(query.start_time, query.end_time)
                      for s in members)
            if pts and budget is not None:
                budget.charge(pts)
                budget.check_deadline()
            total_points += pts
        if not total_points:
            return results
        batch = assemble_columnar(ordered, query.start_time,
                                  query.end_time, interval_ms)
        if batch is None:
            return results
        # grid budget: rows x buckets cells of int64 must fit the same
        # device-state allowance the scalar paths honor (shared guard;
        # histograms never tile — the bucket scatter is one dispatch)
        from opentsdb_tpu.query.limits import grid_budget
        gbd = grid_budget(
            "histogram",
            tsdb.config.get_int("tsd.query.streaming.state_mb"),
            batch["n_rows"] * batch["n_buckets"] * 8,
            batch["n_rows"], batch["n_buckets"])
        if gbd.over:
            raise gbd.exception()

        # ONE dispatch for every group (VERDICT r3 #4): scatter entries
        # onto the [rows, B] grid, percentile-extract on device.  Small
        # queries take the host lane like the scalar paths.
        host_small = (cpu_device() is not None
                      and 0 < total_points <= tsdb.config.get_int(
                          "tsd.query.host_lane.max_points"))
        if host_small:
            self.exec_stats["hostLane"] = 1.0
        percs = [float(p) for p in (sub.percentiles or [])]
        with host_lane(host_small):
            grid = accumulate_rows(batch["seg"], batch["cnt"],
                                   batch["n_rows"], batch["n_buckets"])
            pvals = (percentile_rows(grid, batch["mid"],
                                     np.asarray(percs, np.float64))
                     if percs else None)
        counts_all = np.asarray(grid)
        pvals = None if pvals is None else np.asarray(pvals)

        for group_key, row_lo, row_hi, ts, used, _pts in batch["groups"]:
            members = groups[group_key]
            group_tags, agg_tags = self._compute_tags(members)
            tsuids = [tsdb.tsuid(s.key) for s, _ in members]
            if percs:
                for i, p in enumerate(sub.percentiles):
                    # metric_pct_<p> naming per the DataPoints adaptor
                    # (HistogramDataPointsToDataPointsAdaptor.java:42-44).
                    results.append(QueryResult(
                        metric="%s_pct_%s" % (sub.metric, _fmt_pct(p)),
                        tags=dict(group_tags),
                        aggregate_tags=list(agg_tags),
                        tsuids=list(tsuids),
                        dps=[(int(t), float(v)) for t, v in
                             zip(ts, pvals[i, row_lo:row_hi])],
                        index=sub.index))
            if sub.show_histogram_buckets:
                for b in used:
                    lo, hi = batch["bounds"][b]
                    results.append(QueryResult(
                        metric="%s_bucket_%g_%g" % (sub.metric, lo, hi),
                        tags=dict(group_tags),
                        aggregate_tags=list(agg_tags),
                        tsuids=list(tsuids),
                        dps=[(int(t), int(c)) for t, c in
                             zip(ts, counts_all[row_lo:row_hi, b])],
                        index=sub.index))
        return results

    def _new_budget(self, sub: TSSubQuery):
        """Scan budget + deadline for one sub query (QueryLimitOverride).

        Derived from the AMBIENT request deadline when one is active
        (rpc_manager minted it at request arrival): every sub query
        shares the request's clock and cancellation token instead of
        restarting tsd.query.timeout at planner time."""
        from opentsdb_tpu.query.limits import QueryBudget, active_deadline
        tsdb = self.tsdb
        limits = tsdb.query_limits
        limits.maybe_reload()
        return QueryBudget(limits, sub.metric or "",
                           tsdb.config.get_int("tsd.query.timeout"),
                           deadline=active_deadline())

    def run_sub(self, query: TSQuery, sub: TSSubQuery) -> list[QueryResult]:
        budget = self._new_budget(sub)
        # nothing annotated, nothing to look up: one lookup a tsuid is
        # seconds at 10^5 groups
        self._fetch_notes = (not query.no_annotations
                             and self.tsdb.store.has_annotations())
        if sub.percentiles or sub.show_histogram_buckets:
            return self._run_histogram_sub(query, sub, budget)
        segments = self._plan_segments(query, sub)
        # Query-scoped: fetch once, shared by every segment and group.
        global_notes = (self.tsdb.store.get_annotations(
            "", query.start_time, query.end_time)
            if query.global_annotations else [])
        merged: dict[tuple, QueryResult] = {}
        for seg in segments:
            for gk, qr in self._run_segment(query, sub, seg, global_notes,
                                            budget).items():
                cur = merged.get(gk)
                if cur is None:
                    merged[gk] = qr
                    continue
                # Split stitch (SplitRollupSpanGroup): segments are time-
                # disjoint, so concatenation in segment order is sorted.
                # (replaced, not extended: what a result holds may be a
                # memoised selection's own list)
                cur.dps = cur.dps + qr.dps
                cur.head = None
                cur.tsuids = cur.tsuids + [t for t in qr.tsuids
                                           if t not in cur.tsuids]
                seen_notes = {id(a) for a in cur.annotations}
                cur.annotations = list(cur.annotations) + [
                    a for a in qr.annotations if id(a) not in seen_notes]
                cur.tags = {k: v for k, v in cur.tags.items()
                            if qr.tags.get(k) == v}
                cur.aggregate_tags = sorted(
                    set(cur.aggregate_tags) | set(qr.aggregate_tags))
        return [merged[k] for k in sorted(merged)]

    def run(self, query: TSQuery) -> list[QueryResult]:
        """Every sub-query in turn, each one a `subquery` stage: its
        wall time goes to tsd.query.stage_ms{stage=subquery} and, in a
        traced request, to a span of its own around its plan, dispatch
        and extraction."""
        self.exec_stats = {}
        out = []
        subs = REGISTRY.counter(
            "tsd.query.subqueries",
            "Sub-queries (m= / tsuid=) run by the query runner").labels()
        for i, sub in enumerate(query.queries):
            subs.inc()
            with obs_trace.timed_stage("subquery", index=i):
                out.extend(self.run_sub(query, sub))
        return out


class _ExecConsults:
    """plan_decision()'s consult provider for the EXECUTOR: each hook
    does the real, stateful work (demand recording, repeat-count
    bookkeeping, the device gather) — the explain engine supplies the
    read-only twin (query/explain.py).  The routing logic itself lives
    in query/plandecision.py; this class only binds the planner's
    per-segment context onto the subsystem calls."""

    def __init__(self, tsdb, ctx, seg, sub, windows, store,
                 series_list, fix, bounds=None):
        self.tsdb = tsdb
        self.bounds = bounds    # the scan's, from a valid cache entry
        self.ctx = ctx
        self.seg = seg
        self.sub = sub
        self.windows = windows
        self.store = store
        self.series_list = series_list
        self.fix = fix

    def _metric(self) -> int:
        return self.series_list[0].key.metric

    def rollup_plan(self):
        ctx = self.ctx
        return self.tsdb.rollup_lanes.plan(
            self._metric(), self.series_list, self.windows,
            self.seg.start_ms, self.seg.end_ms, ctx.ds_fn,
            ctx.platform, ctx.s, ctx.n_max, ctx.g_pad, ctx.has_rate,
            total_points=ctx.total_points)

    def note_lane_served(self, plan) -> None:
        self.tsdb.rollup_lanes.note_served(plan)

    def note_lane_fallback(self) -> None:
        self.tsdb.rollup_lanes.note_striping_fallback()

    def tiled_refusal(self, reason: str) -> None:
        from opentsdb_tpu.ops import tiling
        tiling.count_refusal(reason)

    def tiled_plan(self, acc_cell: int):
        from opentsdb_tpu.ops import tiling
        ctx = self.ctx
        return tiling.plan_tiled(
            self.tsdb, s=ctx.s, w=ctx.wp, g_pad=ctx.g_pad,
            acc_cell_bytes=acc_cell, total_points=ctx.total_points,
            platform=ctx.platform)

    def agg_plan(self, platform: str):
        ctx = self.ctx
        ds = self.sub.downsample_spec
        return self.tsdb.agg_cache.plan(
            self.store, self._metric(), self.series_list, self.windows,
            self.seg.start_ms, self.seg.end_ms, ctx.ds_fn,
            ds.fill_policy, ds.fill_value, platform, ctx.s, ctx.n_max,
            ctx.g_pad, ctx.has_rate, total_points=ctx.total_points)

    def device_batch(self, build: bool, ts_base: int | None):
        return self.tsdb.device_cache.batch_for(
            self.store, self._metric(), self.series_list,
            self.seg.start_ms, self.seg.end_ms, self.fix, build=build,
            ts_base=ts_base, bounds=self.bounds)


def _fmt_pct(p: float) -> str:
    """Float.toString parity: 99 -> "99.0", 99.9 -> "99.9"."""
    return "%s" % float(p)


def extract_grid(out_ts: np.ndarray, out_val: np.ndarray,
                 out_mask: np.ndarray, start_ms: int, end_ms: int,
                 keep_nans: bool = False) -> tuple[list, list, object]:
    """extract_dps for every row of a [G, W] float grid over one [W]
    timestamp vector, as columns: ([G] timestamp lists, [G] value
    lists, the [G, W'] float64 block of the values or None).  Where
    every row keeps the same columns (a grid with no gap: the common
    case) all rows share ONE timestamp list, the values convert at C
    speed in one call and their block is kept for the serializer; rows
    that differ are extracted one by one and have no block."""
    ts = out_ts.ravel()
    val = out_val.astype(np.float64, copy=False)
    keep = out_mask & ((ts >= start_ms) & (ts <= end_ms))[None, :]
    if not keep_nans:
        keep = keep & ~np.isnan(val)
    cols = keep.any(axis=0)
    if not keep[:, cols].all():
        pairs = [extract_dps(ts, val[i], out_mask[i], start_ms, end_ms,
                             False, keep_nans) for i in range(len(val))]
        return ([[t for t, _ in row] for row in pairs],
                [[v for _, v in row] for row in pairs], None)
    block = np.ascontiguousarray(val[:, cols])
    return [ts[cols].tolist()] * len(val), block.tolist(), block


def extract_dps(out_ts: np.ndarray, out_val: np.ndarray, out_mask: np.ndarray,
                start_ms: int, end_ms: int, int_mode: bool,
                keep_nans: bool = False) -> list[tuple[int, object]]:
    """Device output -> (ts_ms, python value) pairs trimmed to the query range.

    The serializer-level trim mirrors HttpJsonSerializer (:848-852): points
    outside [start, end] are dropped.  NaNs survive only under fill policies
    that emit them.
    """
    ts = out_ts.ravel()
    val = out_val.ravel()
    mask = out_mask.ravel()
    keep = mask & (ts >= start_ms) & (ts <= end_ms)
    if not keep_nans:
        with np.errstate(invalid="ignore"):
            keep = keep & ~np.isnan(val.astype(np.float64))
    ts = ts[keep]
    val = val[keep]
    if not (int_mode and not np.issubdtype(val.dtype, np.floating)):
        val = val.astype(np.float64)
    # .tolist() converts at C speed (native ints/floats); a per-point
    # Python int()/float() loop costs ~0.5s per million output points
    return list(zip(ts.tolist(), val.tolist()))

"""tsdbobs: end-to-end query tracing, metrics registry, JAX profiling.

Three layers, one package (docs/observability.md):

  * obs/trace.py     span-tree tracer threaded through rpc_manager ->
                     QueryRpc -> planner -> cluster fan-out; spans carry
                     wall time and ride /api/stats/query plus the
                     inline showStats summary.
  * obs/registry.py  thread-safe counters / gauges / log-bucketed
                     latency histograms (obs/histogram.py) with a
                     Prometheus text-exposition endpoint
                     (/api/stats/prometheus).
  * obs/jaxprof.py   per-kernel compile accounting (the SHARED
                     compile-log capture tsdbsan's JaxSanitizer also
                     subscribes to), device-cache gauges, and the
                     costmodel's per-segment decisions.

obs/selfreport.py closes the dogfooding loop: the daemon ingests its own
tsd.* metrics into its own memstore every tsd.stats.interval seconds, so
the TSD is queryable about itself through its own pipeline.

METRICS_SCHEMA (below) is the declared universe of metric names this
codebase emits through the registry families or StatsCollector.record —
tools/lint/metrics_schema.py holds every emission site to it (an
undeclared name is a lint failure), and docs/metrics.md is generated
from it via `python tools/lint/run.py --update-doc` (byte-pinned by
test, same contract as docs/configuration.md).
"""

from __future__ import annotations

from typing import NamedTuple

from opentsdb_tpu.obs.histogram import LogHistogram
from opentsdb_tpu.obs.registry import REGISTRY, MetricsRegistry

__all__ = ["LogHistogram", "REGISTRY", "MetricsRegistry",
           "METRICS_SCHEMA", "MetricSpec", "generate_metrics_doc"]


class MetricSpec(NamedTuple):
    kind: str            # counter | gauge | histogram
    labels: tuple        # label keys minted at the emission sites
    doc: str


def _m(kind: str, labels: tuple, doc: str) -> MetricSpec:
    return MetricSpec(kind, labels, doc)


# The declared metric-name universe.  Names are the FULL dotted form
# (StatsCollector.record's "tsd." prefix included); a `*` segment
# matches one %-formatted hole at an emission site that builds its name
# from a template ("%s.errors" % kind declares as "tsd.*.errors").
# Every StatsCollector record is exposed as a gauge on
# /api/stats/prometheus, so record-emitted names declare kind "gauge";
# every record additionally carries the collector's ambient tags
# (`host`, plus any context tags) on top of the labels listed here.
METRICS_SCHEMA: dict[str, MetricSpec] = {
    # -- HTTP / RPC serving (tsd/rpc_manager.py, tsd/rpcs.py) ---------- #
    "tsd.http.requests": _m(
        "counter", ("route", "status"),
        "HTTP requests served, by registered route and status code."),
    "tsd.http.latency_ms": _m(
        "histogram", ("route",),
        "End-to-end HTTP request latency in milliseconds."),
    "tsd.http.edge_ms": _m(
        "counter", ("route", "stage"),
        "Cumulative wall milliseconds of a request outside its handler, "
        "added on the event loop: queue (the whole body read to the "
        "handler's start on a responder thread), resume (the handler's "
        "last latattr mark to the loop's resumption) and write (the "
        "response encoded, written and drained).  With latattr's phases "
        "they cover a request from its last byte in to its last byte "
        "out."),
    "tsd.http.errors": _m(
        "gauge", ("family",),
        "HTTP error responses by family (4xx client / 5xx server)."),
    "tsd.query.count": _m(
        "counter", ("status",),
        "/api/query requests served, by response status."),
    "tsd.query.series": _m(
        "counter", (),
        "Rows (member series) dispatched by grouped downsample queries: "
        "over tsd.http.requests of api/query, the width of a request."),
    "tsd.query.groups": _m(
        "counter", (),
        "Groups answered by grouped downsample queries."),
    "tsd.query.emit_groups": _m(
        "counter", ("lane",),
        "Results written into /api/query answers (tsd/serializers.py "
        "format_query_v1), by the lane that wrote their points: native "
        "= the finite rows of a grouped answer's [G, W] value block, "
        "every row of one block in one call of the native library "
        "(query/planner.py emit_texts); python = every other result, "
        "through QueryResult.json_text or to_json."),
    "tsd.query.subqueries": _m(
        "counter", (),
        "Sub-queries (one m= or tsuid= each) the query runner ran: over "
        "tsd.http.requests of api/query, the fan-out of a request."),
    "tsd.query.stage_ms": _m(
        "counter", ("stage",),
        "Cumulative wall milliseconds of the planner's host stages, "
        "tracing on or off: subquery (one sub-query whole, the stages "
        "below included), scan (resolve + group, or their memo), "
        "count (per-row point counts, budget), consult (the routing "
        "verdict and its cache consults); inside latattr's dispatch "
        "rewrite (a partial-aggregate rewrite whole: rw_pieces, the "
        "per-piece narrowing and delta dispatches, rw_assemble, the "
        "grid put together by placement programs or, on the host lane, "
        "host copies, and tail, the "
        "grid tail's enqueue) and enqueue (the resident and mesh "
        "programs' calls, row sharding included); fetch (the answer's "
        "device-to-host copy, where a request waits for the device), "
        "extract, assemble; on "
        "the streamed route, inside latattr's dispatch and summed over "
        "a request's chunks, stream_pack (the chunk packer: every "
        "series' window bounds once a request, then the host's fill "
        "of each [S, n] chunk out of the store by bulk copies), "
        "stream_upload (the chunk's three arrays handed to the device "
        "and its fold enqueued) and stream_wait (the waits on the "
        "device: for the upload out of a chunk buffer before it is "
        "refilled, and the reads that wait for folds in flight, the "
        "every-16th-chunk backpressure and the out-of-slice audit)."),
    "tsd.query.rewrite.assembly": _m(
        "counter", ("lane",),
        "Partial-aggregate rewrites (query/planner.py _run_agg_rewrite), "
        "by the lane that put the [S, Wp] grid together: device = one "
        "placement program a piece on the device (ops/pipeline.py "
        "assemble_grid), the pieces never copied to the host; host = "
        "the host lane's copies of every piece into a host grid."),
    "tsd.query.rewrite.pieces": _m(
        "counter", ("kind",),
        "Pieces of partial-aggregate rewrites, by kind: cached = a block "
        "served from either tier of the cache; computed = an edge piece "
        "or a missing block downsampled for the request."),
    "tsd.query.group_reduce": _m(
        "counter", ("mode",),
        "Grouped dispatches of the monolithic pipeline, by the "
        "group-reduce form the chooser took for their shape (segment, "
        "sorted, matmul, rows)."),
    "tsd.query.contrib_lane": _m(
        "counter", ("lane",),
        "Grouped dispatches answered by one device program, by the "
        "contribution lane that program took (ops/group_agg.py "
        "grid_contributions): dense = no row of the [S, W] grid had a "
        "hole between two present windows, so interpolation was "
        "skipped; full = at least one had.  On the mesh dense means "
        "every shard's rows.  Tiled and lane-folded executions run "
        "one contribution program a tile and count nothing here."),
    "tsd.query.rate_lane": _m(
        "counter", ("lane",),
        "Grouped rate dispatches answered by one device program, by "
        "the lane that program's rate took to find each window's "
        "previous value (ops/rate.py): shift = no row of the [S, W] "
        "grid had a hole between two present windows, so the previous "
        "value is the window before; scan = at least one had, and a "
        "prefix scan and two per-cell gathers skip it.  Counted where "
        "tsd.query.contrib_lane is, from the same fetch; on the mesh "
        "shift means every shard's rows."),
    # -- the streamed fold (query/planner.py _stream_grouped) ----------- #
    "tsd.query.stream.requests": _m(
        "counter", (),
        "Grouped segments answered by the streamed fold: the batch was "
        "never materialized, bounded [S, n] chunks went from the store "
        "into a device-resident [S, W] moment state.  Over "
        "tsd.http.requests of api/query, the share of requests on the "
        "route."),
    "tsd.query.stream.chunks": _m(
        "counter", (),
        "Chunks the streamed fold folded into its device state (a "
        "chunk no series had a point for is skipped and not counted)."),
    "tsd.query.stream.points": _m(
        "counter", (),
        "Stored points (mask-true cells of the chunks) the streamed "
        "fold handed to the device."),
    "tsd.query.stream.upload_bytes": _m(
        "counter", (),
        "Bytes of the chunk arrays (int64 timestamps, float64 values, "
        "bool mask) the streamed fold uploaded, padding included: over "
        "tsd.query.stream.points, 17 is the floor."),
    "tsd.query.stream.fold": _m(
        "counter", ("lane",),
        "Chunks of the streamed fold by the update each took "
        "(ops/streaming.py): sliced = merged into the [w0, w0 + wc) "
        "window slice its points span, O(S*wc); full = its span "
        "overflowed the slice sized from the first chunk (or the grid "
        "is not fixed), so the whole [S, W] state was merged."),
    "tsd.query.stream.rows": _m(
        "counter", ("lane",),
        "Series rows of the chunks the streamed fold folded, by the "
        "lane that filled each (storage/chunk_pack.py): bulk = the "
        "series' version still was what it was when the scan took its "
        "window bounds, so the row was copied with all the others, "
        "out of views, without its lock; cursor = the series moved "
        "mid-scan (an append, a delete, a dedup) and the row was read "
        "by the locked timestamp cursor (Series.window_chunk) from "
        "then on.  bulk + cursor = series x tsd.query.stream.chunks; "
        "a store nobody writes to reads bulk only."),
    "tsd.http.response_bytes": _m(
        "counter", ("route",),
        "Response body bytes written, by registered route."),
    "tsd.query.latency_ms": _m(
        "histogram", ("tenant",),
        "End-to-end /api/query latency in milliseconds, by clamped "
        "tenant (X-TSDB-Tenant against the tsd.diag.tenants table)."),
    "tsd.query.tenant.demand": _m(
        "counter", ("tenant",),
        "Queries arriving at the admission gate, by clamped tenant — "
        "the per-tenant demand telemetry the fair-share scheduler "
        "(tsd.query.tenant.fair_share) drains against."),
    "tsd.query.tenant.admitted": _m(
        "counter", ("tenant",),
        "Queries admitted through the gate, by clamped tenant — the "
        "drained half of the demand split (tsd/admission.py weighted "
        "DRR; auditable at /api/diag)."),
    "tsd.query.tenant.refused": _m(
        "counter", ("tenant",),
        "Queries refused (shed) by the gate, by clamped tenant — the "
        "refused half of the demand split."),
    # -- fused multi-query dispatch (query/batcher.py) ------------------ #
    "tsd.query.batch.queries": _m(
        "counter", ("outcome",),
        "Batch-routed queries, by outcome: 'stacked' (member of a "
        "multi-query dispatch) or 'solo' (no sibling arrived within "
        "the coalesce window; ordinary single dispatch)."),
    "tsd.query.batch.dispatches": _m(
        "counter", (),
        "Stacked multi-query device dispatches (one launch serving "
        ">= 2 member queries)."),
    "tsd.query.batch.q": _m(
        "histogram", (),
        "Member queries per stacked dispatch."),
    "tsd.query.batch.wait_ms": _m(
        "histogram", (),
        "Coalesce wait before the stacked/solo dispatch, in "
        "milliseconds (bounded by tsd.query.batch.hold_ms)."),
    "tsd.query.batch.stacked_dispatches": _m(
        "gauge", (),
        "Stats-walk mirror of the stacked-dispatch total "
        "(TSDB.collect_stats)."),
    "tsd.query.batch.stacked_members": _m(
        "gauge", (),
        "Stats-walk mirror of member queries served by stacked "
        "dispatches."),
    "tsd.query.batch.solo_dispatches": _m(
        "gauge", (),
        "Stats-walk mirror of batch-routed queries that dispatched "
        "solo."),
    "tsd.query.explain.requests": _m(
        "counter", ("outcome",),
        "/api/query/explain requests served, by outcome (ok/error).  "
        "Explain acquires no admission permit and dispatches no "
        "device work (query/explain.py)."),
    "tsd.query.explain.latency_ms": _m(
        "histogram", (),
        "Explain planning latency in milliseconds — the no-dispatch "
        "decision walk, including the admission preview."),
    # -- admission control (tsd/admission.py) -------------------------- #
    "tsd.query.admission.queue_depth": _m(
        "gauge", ("priority",),
        "Admission wait-queue depth, by priority class."),
    "tsd.query.admission.wait_ms": _m(
        "histogram", ("priority",),
        "Admission queue wait in milliseconds, by priority class."),
    "tsd.query.admission.inflight": _m(
        "gauge", (),
        "Queries currently holding an admission permit (bounded by "
        "tsd.query.admission.permits)."),
    "tsd.query.admission.shed": _m(
        "counter", ("reason",),
        "Queries refused by the admission gate (503 + Retry-After), "
        "by reason: queue_full, max_wait, predicted_cost."),
    "tsd.query.admission.degraded": _m(
        "counter", ("reason",),
        "Queries served degraded by the admission ladder "
        "(coarsened/truncated, 200 + partialResults)."),
    "tsd.query.admission.cancelled": _m(
        "counter", ("reason",),
        "Queries cancelled cooperatively, by reason: "
        "client_disconnect, drain_timeout, queued."),
    "tsd.query.limits.reload_errors": _m(
        "counter", (),
        "Query-limit overrides loads that failed (the daemon kept "
        "the last good config; logged once per distinct error)."),
    "tsd.rpc.received": _m(
        "gauge", ("type",),
        "RPCs received, by transport/command type."),
    "tsd.*.errors": _m(
        "gauge", ("type",),
        "Per-RPC-kind error tallies (put.errors, rollup.errors, ...) "
        "by error type."),
    "tsd.*.parser": _m(
        "gauge", ("parser",),
        "Write requests (HTTP bodies + telnet put blocks) served by "
        "each ingest parser, per RPC kind: 'native' (the C++ columnar "
        "parser, native/engine.cpp) or 'python' (the per-point "
        "fallback — what every put takes when libtsdb_engine.so is "
        "missing or the TSDB needs per-point hooks)."),
    "tsd.connectionmgr.connections": _m(
        "gauge", ("type",),
        "Connection manager totals: established/open/rejected."),
    "tsd.connectionmgr.exceptions": _m(
        "gauge", (),
        "Exceptions caught by the connection manager."),
    # -- auth (auth/core.py) ------------------------------------------- #
    "tsd.authentication.telnet.allowed": _m(
        "gauge", (), "Telnet connections allowed by the auth plugin."),
    "tsd.authentication.http.allowed": _m(
        "gauge", (), "HTTP connections allowed by the auth plugin."),
    "tsd.authorization.queries.allowed": _m(
        "gauge", (), "Queries allowed by the authorization plugin."),
    # -- cluster fan-out (tsd/cluster.py) ------------------------------ #
    "tsd.cluster.fetch.retries": _m(
        "gauge", (), "Peer-fetch retry attempts."),
    "tsd.cluster.fetch.failures": _m(
        "gauge", (), "Peer fetches that exhausted their retries."),
    "tsd.cluster.queries": _m(
        "gauge", ("result",),
        "Clustered queries by outcome (partial / failed)."),
    "tsd.cluster.breaker.state": _m(
        "gauge", ("peer",),
        "Per-peer circuit-breaker state (0 closed, 1 half-open, "
        "2 open)."),
    "tsd.cluster.breaker.opens": _m(
        "gauge", ("peer",), "Circuit-breaker open transitions."),
    "tsd.cluster.breaker.fast_fails": _m(
        "gauge", ("peer",),
        "Requests fast-failed by an open breaker."),
    # -- sharded replication (tsd/replication.py, docs/replication.md): #
    #    registry families ---------------------------------------------#
    "tsd.replication.ship.records": _m(
        "counter", ("peer",),
        "WAL records synchronously shipped to a replica on the ingest "
        "ack path, by replica peer."),
    "tsd.replication.ship.errors": _m(
        "counter", ("peer",),
        "Synchronous ship attempts that failed (the pull cadence "
        "fills the gap), by replica peer."),
    "tsd.replication.tail.requests": _m(
        "counter", (),
        "/api/replication/tail pages served to catching-up peers."),
    "tsd.replication.tail.records": _m(
        "counter", (),
        "WAL records served through /api/replication/tail."),
    "tsd.replication.catch_up.records": _m(
        "counter", ("peer",),
        "Peer WAL records applied from pulled tails (the catch-up "
        "path), by origin peer."),
    "tsd.replication.forwarded": _m(
        "counter", ("peer",),
        "Ingest writes forwarded to the shard's accepting member, by "
        "destination peer."),
    "tsd.replication.divergence": _m(
        "counter", ("peer",),
        "Anti-entropy CRC-chain divergences detected (position reset "
        "to the last agreed record + re-pull), by peer."),
    "tsd.replication.inflight_rejected": _m(
        "counter", (),
        "Replication ship/tail requests refused by the "
        "tsd.replication.max_inflight_mb byte gate (503; the sender "
        "falls back to the pull cadence)."),
    # -- sharded replication stats walk (ReplicationManager.stats_hook #
    #    -> /api/stats + the self-report loop) ------------------------- #
    "tsd.replication.epoch": _m(
        "gauge", (),
        "Ownership epoch: bumps on every shard-cover change (failover, "
        "rejoin); the flight recorder retains the transition."),
    "tsd.replication.last_seq": _m(
        "gauge", (), "This node's newest assigned WAL sequence number."),
    "tsd.replication.under_replicated": _m(
        "gauge", (),
        "Shards with fewer healthy members than the replication "
        "factor (the eighth health invariant's input)."),
    "tsd.replication.lag": _m(
        "gauge", (),
        "Worst replica's unacknowledged backlog in this node's WAL "
        "stream, records."),
    "tsd.replication.peer_position": _m(
        "gauge", ("peer",),
        "Per-replica acknowledged position in this node's WAL stream "
        "(ship acks + tail since marks)."),
    # -- JAX / costmodel (obs/jaxprof.py, query/planner.py) ------------- #
    "tsd.jax.compiles": _m(
        "counter", ("kernel",), "XLA compilations per jitted kernel."),
    "tsd.costmodel.infeasible": _m(
        "counter", ("axis",),
        "Strategy decisions outside the feasible candidate set "
        "(must stay 0)."),
    # -- query caches: shared tier-labeled families (tier values:      #
    #    device_series = storage/device_cache.py HBM columns,          #
    #    agg_host / agg_device = storage/agg_cache.py partial-         #
    #    aggregate blocks, agg = tier-less agg-cache events) ---------- #
    "tsd.query.cache.hits": _m(
        "counter", ("tier",),
        "Query-cache hits, by tier."),
    "tsd.query.cache.misses": _m(
        "counter", ("tier",),
        "Query-cache misses, by tier."),
    "tsd.query.cache.evictions": _m(
        "counter", ("tier",),
        "Query-cache evictions, by tier."),
    "tsd.query.cache.invalidations": _m(
        "counter", ("tier",),
        "Query-cache invalidation marks (ingest dirty ranges, "
        "dropcaches), by tier."),
    "tsd.query.cache.bytes": _m(
        "gauge", ("tier",),
        "Query-cache resident bytes, by tier."),
    "tsd.query.cache.entries": _m(
        "gauge", ("tier",),
        "Query-cache resident entries, by tier."),
    # -- out-of-core tiled execution (ops/tiling.py,                    #
    #    storage/spill.py) --------------------------------------------- #
    "tsd.query.spill.bytes": _m(
        "gauge", ("tier",),
        "Spill-pool resident bytes, by tier (host ring / disk "
        "overflow) — bounded by tsd.query.spill.host_mb/disk_mb."),
    "tsd.query.spill.entries": _m(
        "gauge", ("tier",),
        "Spill-pool resident entries, by tier."),
    "tsd.query.spill.tiles": _m(
        "counter", (),
        "Series tiles executed by the out-of-core tiled path."),
    "tsd.query.spill.spills": _m(
        "counter", ("tier",),
        "Partial grids written to the spill pool, by landing tier."),
    "tsd.query.spill.reads": _m(
        "counter", (),
        "Spill entries read back from the disk tier."),
    "tsd.query.spill.evictions": _m(
        "counter", (),
        "Spill-pool host-ring entries demoted to the disk tier."),
    "tsd.query.spill.invalidations": _m(
        "counter", (),
        "Spill entries released back to the pool (per-query cleanup "
        "and shutdown)."),
    "tsd.query.spill.refusals": _m(
        "counter", ("reason",),
        "Over-budget plans the tiled path could not serve (still "
        "413), by reason: disabled, not_streamable, no_fit, "
        "pool_budget."),
    "tsd.query.spill.write_errors": _m(
        "counter", (),
        "Spill-pool disk writes that failed (disk full / injected "
        "spill.write fault)."),
    # -- partial-aggregate cache stats walk (storage/agg_cache.py       #
    #    collect_stats -> /api/stats + prometheus gauges) -------------- #
    "tsd.query.agg_cache.hits": _m(
        "gauge", (), "Aggregate-block cache hits (blocks served)."),
    "tsd.query.agg_cache.misses": _m(
        "gauge", (), "Aggregate-block cache misses (blocks computed)."),
    "tsd.query.agg_cache.evictions": _m(
        "gauge", (), "Aggregate-block cache evictions (both tiers)."),
    "tsd.query.agg_cache.invalidations": _m(
        "gauge", (), "Aggregate-block dirty marks recorded."),
    "tsd.query.agg_cache.rewrites": _m(
        "gauge", (), "Plans served via the partial-aggregate rewrite."),
    "tsd.query.agg_cache.populated": _m(
        "gauge", (), "Aggregate blocks materialized into the cache."),
    "tsd.query.agg_cache.entries": _m(
        "gauge", (), "Aggregate blocks resident (host tier)."),
    "tsd.query.agg_cache.bytes": _m(
        "gauge", (), "Aggregate-block host-tier resident bytes."),
    "tsd.query.agg_cache.device_bytes": _m(
        "gauge", (), "Aggregate-block device-tier resident bytes."),
    # -- rollup lanes (storage/rollup.py): registry families ----------- #
    "tsd.rollup.lane.hits": _m(
        "counter", ("lane",),
        "Plans answered from a rollup lane, by lane interval."),
    "tsd.rollup.lane.misses": _m(
        "counter", ("reason",),
        "Lane-eligible plans that fell back to the exact paths, by "
        "reason (cold, striping)."),
    "tsd.rollup.lane.builds": _m(
        "counter", ("lane",),
        "Lane blocks materialized from the memstore by the "
        "maintenance thread, by lane interval."),
    "tsd.rollup.lane.build_errors": _m(
        "counter", (),
        "Lane block builds that raised (caught + counted; retried "
        "next pass)."),
    "tsd.rollup.lane.evictions": _m(
        "counter", (),
        "Lane blocks evicted by the tsd.rollup.mb LRU."),
    "tsd.rollup.lane.invalidations": _m(
        "counter", (),
        "Rollup-lane invalidation marks (ingest dirty ranges, "
        "dropcaches)."),
    "tsd.rollup.lane.bytes": _m(
        "gauge", (),
        "Rollup-lane store resident bytes (tsd.rollup.mb budget)."),
    "tsd.rollup.lane.blocks": _m(
        "gauge", (), "Rollup-lane blocks resident."),
    # -- rollup-lane stats walk (storage/rollup.py collect_stats ->     #
    #    /api/stats + prometheus gauges) ------------------------------- #
    "tsd.query.rollup.hits": _m(
        "gauge", (), "Plans served from rollup lanes."),
    "tsd.query.rollup.misses": _m(
        "gauge", (), "Lane-eligible plans that fell back."),
    "tsd.query.rollup.builds": _m(
        "gauge", (), "Lane blocks materialized."),
    "tsd.query.rollup.build_errors": _m(
        "gauge", (), "Lane block builds that raised."),
    "tsd.query.rollup.blocks": _m(
        "gauge", (), "Lane blocks resident."),
    "tsd.query.rollup.bytes": _m(
        "gauge", (), "Lane store resident bytes."),
    "tsd.query.rollup.evictions": _m(
        "gauge", (), "Lane blocks evicted (byte-budget LRU)."),
    "tsd.query.rollup.invalidations": _m(
        "gauge", (), "Lane invalidation marks recorded."),
    "tsd.query.rollup.served_windows": _m(
        "gauge", (), "Downsample windows answered from lane cells."),
    "tsd.query.rollup.demand_entries": _m(
        "gauge", (),
        "Tracked (metric, lane) demand candidates (the Storyboard "
        "selection corpus)."),
    # -- flight recorder + health engine (obs/flightrec.py,             #
    #    obs/health.py, served at /api/diag*) -------------------------- #
    # -- WAL integrity (storage/persist.py) ----------------------------- #
    "tsd.storage.wal.corrupt_records": _m(
        "counter", (),
        "WAL records whose CRC32/frame failed verification at "
        "replay/tail time (interior corruption; replay stops at the "
        "last valid record and truncates the hole)."),
    "tsd.diag.events": _m(
        "counter", ("kind",),
        "Flight-recorder events recorded, by event kind (admission, "
        "plan, tiling, breaker, deadline, compile, health, "
        "...)."),
    "tsd.diag.slow_captures": _m(
        "counter", (),
        "Slow/anomalous queries whose span tree + flight-recorder "
        "slice were retained at /api/diag/slow."),
    "tsd.diag.dropped": _m(
        "counter", ("kind",),
        "Flight-recorder events dropped on ring overflow, by the "
        "evicted event's kind — evidence lost before any reader saw "
        "it (the health engine's diag subsystem judges the rate)."),
    "tsd.health.status": _m(
        "gauge", ("subsystem",),
        "Health-engine verdict per subsystem: 0 ok, 1 degraded, "
        "2 failing (chaos_soak's post-heal gate)."),
    # -- latency attribution (obs/latattr.py, served at                  #
    #    /api/diag/latency) -------------------------------------------- #
    "tsd.latattr.requests": _m(
        "counter", (),
        "Requests folded into the always-on latency-attribution "
        "profiles (every HTTP request, tracing on or off)."),
    "tsd.latattr.phase_ms": _m(
        "counter", ("phase",),
        "Cumulative milliseconds attributed to each fixed request "
        "phase (parse, admission_wait, plan, batch_rendezvous, "
        "dispatch, device_wait, serialize, flush) across all "
        "requests."),
    "tsd.latattr.phase_cpu_ms": _m(
        "counter", ("phase",),
        "Cumulative handler-thread CPU milliseconds (time.thread_time) "
        "spent in each fixed request phase across all requests; over "
        "tsd.latattr.phase_ms of the same phase it is the share of the "
        "phase the thread worked on a core rather than waited."),
    "tsd.latattr.profiles": _m(
        "gauge", (),
        "Distinct (route, plan fingerprint, tenant) latency-"
        "attribution profiles live (bounded by "
        "tsd.latattr.max_profiles)."),
    "tsd.latattr.profile_overflow": _m(
        "counter", (),
        "Requests folded into the overflow profile because the "
        "profile table was already at tsd.latattr.max_profiles "
        "distinct keys."),
    # -- diagnostics stats walk (flight recorder + health stats hooks   #
    #    -> /api/stats + the self-report loop) ------------------------- #
    "tsd.diag.ring.events": _m(
        "gauge", (), "Flight-recorder events recorded since startup "
        "(the ring's latest sequence number)."),
    "tsd.diag.slow.captured": _m(
        "gauge", (), "Slow-query captures retained since startup."),
    "tsd.diag.ring.dropped": _m(
        "gauge", (), "Flight-recorder events dropped on ring overflow "
        "since startup (all kinds), re-walked for /api/stats and the "
        "self-report loop."),
    "tsd.latattr.observed": _m(
        "gauge", (), "Latency-attribution requests folded since "
        "startup, re-walked for /api/stats and the self-report loop."),
    "tsd.latattr.live_profiles": _m(
        "gauge", (), "Distinct latency-attribution profiles live, "
        "re-walked for /api/stats and the self-report loop."),
    "tsd.latattr.ms": _m(
        "gauge", ("phase",),
        "Cumulative per-phase attributed milliseconds, re-walked for "
        "/api/stats and the self-report loop."),
    "tsd.diag.tenant.demand": _m(
        "gauge", ("tenant",),
        "Per-tenant demand counters re-walked for /api/stats and the "
        "self-report loop."),
    "tsd.diag.tenant.admitted": _m(
        "gauge", ("tenant",),
        "Per-tenant admitted counters (the drained half of the "
        "demand split) re-walked for /api/stats and the self-report "
        "loop."),
    "tsd.diag.tenant.refused": _m(
        "gauge", ("tenant",),
        "Per-tenant refused counters (the shed half of the demand "
        "split) re-walked for /api/stats and the self-report loop."),
    "tsd.health.passes": _m(
        "gauge", (), "Health-engine evaluation passes completed."),
    # -- device cache (storage/device_cache.py collect_stats, mirrored  #
    #    by obs/jaxprof.py update_device_gauges) ----------------------- #
    "tsd.query.device_cache.hits": _m(
        "gauge", (), "Device-cache batch gathers served from HBM."),
    "tsd.query.device_cache.misses": _m(
        "gauge", (), "Device-cache misses (cold/stale/over-budget)."),
    "tsd.query.device_cache.miss_reason": _m(
        "counter", ("reason",),
        "Device-cache misses by their reason, one per miss: cold (no "
        "entry, none built or the metric not admitted), building (no "
        "entry, one being built), evicted (no entry, the last one was "
        "evicted for the byte budget), stale (a requested series' "
        "version moved since the snapshot), rows (a requested series "
        "is not in the snapshot), batch (the [S, n] batch is over "
        "tsd.query.device_cache.batch_mb).  They sum to "
        "tsd.query.device_cache.misses."),
    "tsd.query.device_cache.builds": _m(
        "gauge", (), "Device-cache entry builds."),
    "tsd.query.device_cache.evictions": _m(
        "gauge", (), "Device-cache LRU evictions."),
    "tsd.query.device_cache.entries": _m(
        "gauge", (), "Device-cache resident entries."),
    "tsd.query.device_cache.bytes": _m(
        "gauge", (), "Device-cache resident bytes."),
}


def generate_metrics_doc() -> str:
    """Render docs/metrics.md from METRICS_SCHEMA (one table per
    top-level prefix).  tests/test_lint_clean.py pins the committed
    file to this output."""
    groups: dict[str, list[tuple[str, MetricSpec]]] = {}
    for name, spec in sorted(METRICS_SCHEMA.items()):
        segs = name.split(".")
        if "*" in segs[:2]:
            # templated names (tsd.*.errors) get their own section
            # instead of a literal '## `tsd.*.*`' heading
            prefix = "templated"
        else:
            prefix = ".".join(segs[:2])
        groups.setdefault(prefix, []).append((name, spec))
    lines = [
        "# Metrics reference",
        "",
        "<!-- GENERATED FILE — do not edit by hand.",
        "     Regenerate with: python tools/lint/run.py --update-doc",
        "     Source of truth: opentsdb_tpu/obs/__init__.py "
        "METRICS_SCHEMA. -->",
        "",
        "Every metric name emitted through the obs/registry.py families "
        "or `StatsCollector.record` is declared here; "
        "tools/lint/metrics_schema.py fails the build on an undeclared "
        "name or a kind collision.  A `*` segment stands for a value "
        "interpolated at the emission site (RPC kind, platform).  "
        "Record-emitted metrics are exposed as gauges on "
        "`/api/stats/prometheus` and additionally carry the collector's "
        "ambient tags (`host`, plus any context tags) on top of the "
        "labels listed.",
        "",
    ]
    for prefix in sorted(groups):
        lines.append("## `%s.*`" % prefix)
        lines.append("")
        lines.append("| metric | kind | labels | description |")
        lines.append("|---|---|---|---|")
        for name, spec in groups[prefix]:
            lines.append("| `%s` | %s | %s | %s |" % (
                name, spec.kind,
                ", ".join("`%s`" % k for k in spec.labels) or "—",
                spec.doc))
        lines.append("")
    return "\n".join(lines)

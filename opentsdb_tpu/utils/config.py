"""Flat `tsd.*` properties configuration with typed getters and a schema.

Reference behavior: /root/reference/src/utils/Config.java (:53, setDefaults
:560) — a properties file of tsd.* keys with hardcoded defaults, typed
accessors, and hot access from every layer.  TPU additions live under the
`tsd.tpu.*` prefix.

Every key the codebase reads is declared in ``CONFIG_SCHEMA`` (key ->
type, default, doc); ``DEFAULTS`` is derived from it.  The tsdblint
config analyzer (tools/lint/config_schema.py) holds every ``tsd.*``
literal in the package to this registry — unknown keys, typed-getter
mismatches, and dead entries all fail tier-1 — and
``generate_config_doc()`` renders docs/configuration.md from it, so the
reference doc cannot drift from the code.

Keys marked ``compat=True`` are accepted from reference opentsdb.conf
files but not (yet) read by this codebase; they are excluded from the
dead-key check and flagged in the generated doc.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass
from typing import Any


@dataclass(frozen=True)
class ConfigEntry:
    """One declared key: accessor type, default (always a string — the
    properties file is untyped), one-line doc, and the compat flag."""
    type: str           # "str" | "int" | "float" | "bool" | "dir"
    default: str
    doc: str
    compat: bool = False


def _e(type: str, default: Any, doc: str, compat: bool = False
       ) -> ConfigEntry:
    if isinstance(default, bool):
        default = "true" if default else "false"
    return ConfigEntry(type, str(default), doc, compat)


CONFIG_SCHEMA: dict[str, ConfigEntry] = {
    # -- daemon -------------------------------------------------------- #
    "tsd.mode": _e("str", "rw",
                   "Operation mode: rw, ro (reads only) or wo (writes "
                   "only); gates which RPC routes mount."),
    "tsd.no_diediedie": _e("bool", False,
                           "Disable the telnet/HTTP diediedie shutdown "
                           "command."),
    "tsd.network.bind": _e("str", "0.0.0.0",
                           "Address the TSD listens on."),
    "tsd.network.port": _e("int", "",
                           "TCP port to serve on (telnet + HTTP on one "
                           "socket); empty defers to the CLI --port."),
    "tsd.network.keep_alive_timeout": _e(
        "int", "300",
        "Idle seconds before an open connection is dropped."),
    "tsd.network.drain_timeout_ms": _e(
        "int", "30000",
        "Graceful-shutdown budget for in-flight responder work; at "
        "expiry every in-flight request's cancellation token is "
        "force-flipped so cooperative handlers unwind, then teardown "
        "proceeds regardless after a short grace."),
    "tsd.network.worker_threads": _e(
        "int", "", "Responder thread count (reference compat; the "
        "daemon takes --worker-threads).", compat=True),
    "tsd.network.async_io": _e("bool", True,
                               "Reference compat; I/O is always async "
                               "here.", compat=True),
    "tsd.network.tcp_no_delay": _e("bool", True,
                                   "Reference compat socket flag.",
                                   compat=True),
    "tsd.network.keep_alive": _e("bool", True,
                                 "Reference compat socket flag.",
                                 compat=True),
    "tsd.network.reuse_address": _e("bool", True,
                                    "Reference compat socket flag.",
                                    compat=True),
    # -- multi-host mesh (parallel/distributed.py) --------------------- #
    "tsd.network.distributed.coordinator": _e(
        "str", "", "Coordinator host:port of process 0; setting it (plus "
        "num_processes/process_id) enables jax.distributed."),
    "tsd.network.distributed.num_processes": _e(
        "int", "0", "Process count of the distributed mesh."),
    "tsd.network.distributed.process_id": _e(
        "int", "", "This process's rank in the distributed mesh."),
    # -- request-driven cluster serving (tsd/cluster.py) --------------- #
    "tsd.network.cluster.peers": _e(
        "str", "", "Comma-separated host:port of the OTHER TSDs whose "
        "stores /api/query fans out to (empty = single-host serving)."),
    "tsd.network.cluster.timeout_ms": _e(
        "int", "15000", "Overall per-peer-fetch budget, shared across "
        "every retry attempt."),
    "tsd.network.cluster.partial_results": _e(
        "str", "error", "Peer-failure stance after retries/breakers: "
        "'error' fails the query; 'allow' answers 200 with surviving "
        "peers' data plus partialResults annotations."),
    "tsd.network.cluster.retry.max_attempts": _e(
        "int", "3", "Attempts per peer raw-series fetch "
        "(utils/retry.py capped exponential backoff)."),
    "tsd.network.cluster.retry.attempt_timeout_ms": _e(
        "int", "0", "Per-attempt deadline; 0 = each attempt may use the "
        "full remaining budget."),
    "tsd.network.cluster.breaker.threshold": _e(
        "int", "5", "Consecutive fetch failures that open a peer's "
        "circuit breaker (0 disables breakers)."),
    "tsd.network.cluster.breaker.cooldown_ms": _e(
        "int", "5000", "Open -> half-open probe delay; breaker state "
        "surfaces via /api/stats (cluster.breaker.*)."),
    # -- sharded ownership + replication (tsd/replication.py) ---------- #
    "tsd.network.cluster.self": _e(
        "str", "", "host:port identity of THIS node on the shard ring "
        "(how peers reach it).  Required when shard.enable is true."),
    "tsd.network.cluster.shard.enable": _e(
        "bool", False, "Consistent-hash series ownership across the "
        "cluster: each (metric, tags) series gets an owner + replica "
        "set, ingest routes to the owner, and queries fan out only to "
        "the owning shards' healthy members (docs/replication.md)."),
    "tsd.network.cluster.shard.count": _e(
        "int", "64", "Logical shards the series key space hashes into; "
        "the unit of ownership, failover, and anti-entropy comparison."),
    "tsd.network.cluster.shard.virtual_nodes": _e(
        "int", "32", "Virtual nodes per peer on the consistent-hash "
        "ring — evens shard placement and bounds rebalance movement to "
        "~1/n of the shards when a peer joins or leaves."),
    "tsd.network.cluster.shard.replicas": _e(
        "int", "2", "Replication factor: copies of each shard "
        "(owner included).  1 = unreplicated single-copy serving (the "
        "pre-replication behavior)."),
    "tsd.replication.max_inflight_mb": _e(
        "int", "64", "Byte bound on concurrently-processing "
        "replication ship/tail bodies.  Replication traffic is exempt "
        "from the query admission gate; this is its own backpressure "
        "(excess requests answer 503 and the sender falls back to the "
        "pull cadence)."),
    "tsd.replication.pull_interval_ms": _e(
        "int", "1000", "Replica catch-up cadence: how often each node "
        "pulls peers' WAL tails (/api/replication/tail) to fill gaps "
        "the synchronous ship path missed."),
    "tsd.replication.ship_timeout_ms": _e(
        "int", "5000", "Per-replica budget for the synchronous WAL "
        "ship on the ingest ack path; a replica that cannot answer "
        "within it is served by the pull cadence instead."),
    "tsd.replication.tail_batch_mb": _e(
        "int", "4", "Payload bound per /api/replication/tail page; a "
        "catching-up replica iterates pages until it reaches the "
        "owner's last sequence number."),
    # -- fault injection (utils/faults.py) ----------------------------- #
    "tsd.faults.config": _e(
        "str", "", "Fault-injection spec: inline JSON list or @path. "
        "A testing/chaos surface — NEVER arm in production.  Specs are "
        "validated against the registered hook sites at startup."),
    # -- runtime sanitizer (tools/sanitize, armed by tsd_main) --------- #
    "tsd.sanitizer.enable": _e(
        "bool", False, "Arm the tsdbsan runtime sanitizer (instrumented "
        "locks, write interception, deadlock watchdog) at daemon "
        "startup.  A testing/chaos surface — adds per-write overhead; "
        "never arm in production."),
    "tsd.sanitizer.lockset.enable": _e(
        "bool", True, "Lockset race detector: verify guarded-by "
        "annotations at runtime and run Eraser-style lockset "
        "intersection on unannotated shared attributes."),
    "tsd.sanitizer.deadlock.enable": _e(
        "bool", True, "Deadlock watcher: runtime lock-order graph, "
        "inversion detection, and the live wait-for-cycle watchdog."),
    "tsd.sanitizer.deadlock.watchdog_ms": _e(
        "int", "200", "Wait-for-cycle watchdog scan period in ms "
        "(0 disables the background thread; order-graph recording "
        "stays on)."),
    "tsd.sanitizer.jax.enable": _e(
        "bool", False, "JAX compile/sync accounting in the daemon "
        "(compile events per kernel; steady-phase gating is driven by "
        "the test harness, not the daemon)."),
    "tsd.sanitizer.report.path": _e(
        "str", "", "Write the sanitizer findings report here at "
        "daemon shutdown (JSON, or SARIF when the path ends in "
        ".sarif).  Empty = no report artifact."),
    # -- observability (opentsdb_tpu/obs/, docs/observability.md) ------ #
    "tsd.trace.enable": _e(
        "bool", True, "Trace query serving: a span tree per request "
        "(scan/pipeline stages, cluster fan-out with retry/breaker "
        "annotations) surfaced inline via showStats and in the "
        "/api/stats/query ring."),
    "tsd.stats.interval": _e(
        "int", "0", "Seconds between self-report passes writing the "
        "daemon's own tsd.* metrics into its local store through the "
        "normal ingest path (0 = disabled).  The TSD becomes queryable "
        "about itself via ordinary /api/query."),
    # -- flight recorder + diagnostics (obs/flightrec.py) --------------- #
    "tsd.diag.enable": _e(
        "bool", True, "Arm the always-on flight recorder: a bounded "
        "ring of structured diagnostic events (admission verdicts, "
        "cache/rollup consults, spills, breaker "
        "transitions, deadline expiries, recompiles) served at "
        "/api/diag and dumped at shutdown.  Also gates /api/diag/slow."),
    "tsd.diag.ring_size": _e(
        "int", "4096", "Flight-recorder ring capacity in events; "
        "overflow drops the oldest."),
    "tsd.diag.dump_path": _e(
        "str", "", "Write the flight-recorder black box (ring + slow "
        "captures, JSON) here at shutdown/SIGTERM.  Empty = no dump "
        "artifact."),
    # -- latency attribution (obs/latattr.py) --------------------------- #
    "tsd.latattr.enable": _e(
        "bool", True, "Always-on latency attribution: the RPC layer "
        "stamps every request at fixed phases (parse, admission wait, "
        "plan, batch rendezvous, dispatch, device wait, serialize, "
        "flush) and folds the deltas into bounded streaming histograms "
        "keyed by (route, plan fingerprint, clamped tenant), served at "
        "/api/diag/latency.  Independent of tracing — answers 'where "
        "did the milliseconds go' with tsd.trace.enable off."),
    "tsd.latattr.max_profiles": _e(
        "int", "256", "Bound on distinct (route, fingerprint, tenant) "
        "latency-attribution profiles held in memory; requests beyond "
        "it collapse into a single overflow profile (counted by "
        "tsd.latattr.profile_overflow) so cardinality storms cannot "
        "grow the table."),
    "tsd.diag.slow_ms": _e(
        "int", "0", "Absolute slow-query capture threshold in ms: a "
        "query at least this slow retains its span tree + "
        "flight-recorder slice at /api/diag/slow without showStats.  "
        "0 disables the absolute arm."),
    "tsd.diag.slow_quantile": _e(
        "float", "0.99", "Rolling-quantile slow-capture arm: capture "
        "queries above this quantile of the recorder's own latency "
        "histogram (active once enough samples accrue).  0 disables."),
    "tsd.diag.slow_keep": _e(
        "int", "32", "Bounded slow-query store capacity; overflow "
        "drops the oldest capture."),
    "tsd.diag.exemplars": _e(
        "bool", False, "Emit OpenMetrics-style exemplar COMMENT lines "
        "(trace ids per histogram bucket) on /api/stats/prometheus, "
        "linking tail-latency buckets to flight-recorder traces.  The "
        "text format stays 0.0.4-parseable."),
    "tsd.diag.tenants": _e(
        "str", "", "Comma-separated registered tenant names for the "
        "X-TSDB-Tenant header.  Registered tenants keep their name as "
        "a metric label; everything else hashes into "
        "tsd.diag.tenant_buckets buckets (cardinality clamp)."),
    "tsd.diag.tenant_buckets": _e(
        "int", "16", "Hash buckets for unregistered tenant header "
        "values (0 collapses them all to 'other')."),
    # -- query explain (query/explain.py, docs/query_explain.md) -------- #
    "tsd.explain.enable": _e(
        "bool", True, "Mount /api/query/explain: the no-dispatch "
        "what-if engine returning the complete routing decision tree "
        "(admission preview, rollup/agg-cache/device-cache consults, "
        "grid-budget/tiling verdict, per-axis costmodel pricing) plus "
        "the stable plan fingerprint executed queries stamp into "
        "flight-recorder plan events."),
    "tsd.explain.include_candidates": _e(
        "bool", True, "Include the per-candidate predicted-ms tables "
        "in explain's costmodel decision reports.  False keeps only "
        "the chosen mode + provenance (smaller payloads for "
        "dashboard-driven polling)."),
    # -- health engine (obs/health.py) ---------------------------------- #
    "tsd.health.enable": _e(
        "bool", True, "Evaluate the declared health invariants "
        "(shed burn, steady-state recompiles, cache hit collapse, "
        "spill saturation, breaker flap) into "
        "per-subsystem ok/degraded/failing verdicts at "
        "/api/diag/health and tsd.health.* gauges."),
    "tsd.health.interval": _e(
        "int", "10", "Seconds between health-engine passes on the "
        "maintenance cadence (each pass judges the window since the "
        "previous one)."),
    "tsd.health.shed_rate": _e(
        "float", "0.5", "Admission sheds per second over the window "
        "above which the admission subsystem reads degraded "
        "(failing at 4x)."),
    "tsd.health.recompile_warmup": _e(
        "int", "120", "Seconds after startup before the steady-state "
        "recompile invariant arms (first-touch compiles are "
        "legitimate)."),
    "tsd.health.recompile_limit": _e(
        "int", "0", "XLA compilations tolerated per window once "
        "warmed up; beyond it the compile subsystem reads degraded "
        "(failing past limit+4)."),
    "tsd.health.cache_hit_floor": _e(
        "float", "0.05", "Aggregate-cache hit fraction under which a "
        "busy window (>= 16 consults) reads degraded — the hit-rate-"
        "collapse invariant."),
    "tsd.health.spill_saturation": _e(
        "float", "0.9", "Spill-pool resident fraction of the combined "
        "host+disk budget above which the spill subsystem reads "
        "degraded (failing at 100%)."),
    "tsd.health.breaker_flap": _e(
        "int", "3", "Circuit-breaker open transitions per window "
        "above which the cluster subsystem reads degraded (failing "
        "at 2x); any breaker currently open is at least degraded."),
    "tsd.health.tenant_share_ratio": _e(
        "float", "10", "Cross-tenant starvation bound: among tenants "
        "with meaningful window demand, the max/min admitted-share "
        "ratio above which the tenant subsystem reads degraded "
        "(failing when a demanding tenant was admitted NOTHING while "
        "others were served)."),
    "tsd.health.replication_lag": _e(
        "int", "500", "Replication-lag burn bound: growth of the "
        "worst replica's unacknowledged WAL backlog (records) per "
        "window above which the replication subsystem reads degraded "
        "(failing at 4x); any under-replicated shard is at least "
        "degraded."),
    "tsd.health.phase_share": _e(
        "float", "0.5", "Phase-share burn budget: the serialize "
        "phase's share of the window's total attributed request time "
        "(obs/latattr.py) above which the latency subsystem reads "
        "degraded (failing at 2x).  Serialize is pure host-side "
        "overhead — the continuous production form of tsdbsan's "
        "serialize pin."),
    "tsd.health.diag_drop_rate": _e(
        "float", "50", "Evidence-loss bound: flight-recorder ring "
        "overflow drops per second over the window above which the "
        "diag subsystem reads degraded (failing at 4x) — a steadily "
        "overflowing ring means the next incident's history is "
        "already gone."),
    # -- core ---------------------------------------------------------- #
    "tsd.core.authentication.enable": _e(
        "bool", False, "Require telnet/HTTP authentication."),
    "tsd.core.authentication.plugin": _e(
        "str", "", "Authentication plugin class path."),
    "tsd.core.auto_create_metrics": _e(
        "bool", False, "Assign UIDs to unseen metric names on ingest "
        "instead of rejecting the point."),
    "tsd.core.auto_create_tagks": _e(
        "bool", True, "Assign UIDs to unseen tag keys on ingest."),
    "tsd.core.auto_create_tagvs": _e(
        "bool", True, "Assign UIDs to unseen tag values on ingest."),
    "tsd.core.connections.limit": _e(
        "int", "0", "Max concurrent open connections (0 = unlimited)."),
    "tsd.core.enable_api": _e("bool", True, "Mount the /api routes."),
    "tsd.core.enable_ui": _e("bool", True,
                             "Mount the built-in UI routes."),
    "tsd.core.histograms.config": _e(
        "str", "", "Histogram codec config: inline JSON or @path."),
    "tsd.core.meta.enable_realtime_ts": _e(
        "bool", False, "Track TSMeta objects in real time."),
    "tsd.core.meta.enable_realtime_uid": _e(
        "bool", False, "Track UIDMeta objects in real time."),
    "tsd.core.meta.enable_tsuid_incrementing": _e(
        "bool", False, "Increment a counter per TSUID on ingest."),
    "tsd.core.meta.enable_tsuid_tracking": _e(
        "bool", False, "Track last-write per TSUID on ingest."),
    "tsd.core.meta.cache.enable": _e(
        "bool", False, "Reference compat meta-cache toggle.",
        compat=True),
    "tsd.core.meta.cache.plugin": _e(
        "str", "", "Reference compat meta-cache plugin.", compat=True),
    "tsd.core.plugin_path": _e(
        "dir", "", "Directory added to the import path for plugin "
        "discovery."),
    "tsd.core.response.async": _e(
        "bool", True, "Reference compat; responses are always async.",
        compat=True),
    "tsd.core.socket.timeout": _e(
        "int", "0", "Reference compat socket timeout.", compat=True),
    "tsd.core.tree.enable_processing": _e(
        "bool", False, "Run tree rules against incoming TSMeta."),
    "tsd.core.preload_uid_cache": _e(
        "bool", False, "Reference compat UID-cache preload.",
        compat=True),
    "tsd.core.preload_uid_cache.max_entries": _e(
        "int", "300000", "Reference compat UID-cache preload bound.",
        compat=True),
    "tsd.core.storage_exception_handler.enable": _e(
        "bool", False, "Enable the failed-write spillway plugin."),
    "tsd.core.storage_exception_handler.plugin": _e(
        "str", "", "Storage exception handler plugin class path."),
    "tsd.core.uid.random_metrics": _e(
        "bool", False, "Assign metric UIDs randomly instead of "
        "sequentially."),
    "tsd.core.bulk.allow_out_of_order_timestamps": _e(
        "bool", False, "Reference compat bulk-import flag.",
        compat=True),
    "tsd.core.timezone": _e(
        "str", "UTC", "Reference compat default timezone (queries carry "
        "their own tz).", compat=True),
    "tsd.core.stats_with_port": _e(
        "bool", False, "Reference compat: tag stats with the TSD port.",
        compat=True),
    # -- query --------------------------------------------------------- #
    "tsd.query.filter.expansion_limit": _e(
        "int", "4096", "Reference compat filter-expansion bound.",
        compat=True),
    "tsd.query.skip_unresolved_tagvs": _e(
        "bool", False, "Reference compat unresolved-tagv stance.",
        compat=True),
    "tsd.query.allow_simultaneous_duplicates": _e(
        "bool", True, "Allow identical queries to run concurrently "
        "instead of rejecting the second."),
    "tsd.query.enable_fuzzy_filter": _e(
        "bool", True, "Reference compat fuzzy-row-filter toggle.",
        compat=True),
    "tsd.query.limits.bytes.default": _e(
        "int", "0", "Per-query scanned-bytes budget (0 = unlimited); "
        "exceeding answers 413."),
    "tsd.query.limits.bytes.allow_override": _e(
        "bool", False, "Reference compat per-query override toggle.",
        compat=True),
    "tsd.query.limits.data_points.default": _e(
        "int", "0", "Per-query scanned-datapoints budget (0 = "
        "unlimited)."),
    "tsd.query.limits.data_points.allow_override": _e(
        "bool", False, "Reference compat per-query override toggle.",
        compat=True),
    "tsd.query.limits.overrides.config": _e(
        "str", "", "Per-metric budget overrides: inline JSON or @path."),
    "tsd.query.limits.overrides.interval": _e(
        "int", "60000", "Override-config reload interval (ms)."),
    "tsd.query.mesh.enable": _e(
        "bool", True, "Serve wide /api/query batches via the sharded "
        "device mesh (the salt-scanner fan-out analog)."),
    "tsd.query.mesh.min_series": _e(
        "int", "8", "Min series per batch before the mesh path engages "
        "(amortizes collective latency)."),
    "tsd.query.host_lane.max_points": _e(
        "int", "2000000", "Below this many scanned points the jitted "
        "pipeline runs on the host CPU platform — the accelerator "
        "dispatch floor dwarfs the compute at this scale.  0 disables."),
    "tsd.query.streaming.point_threshold": _e(
        "int", "8000000", "Queries past this many datapoints stream "
        "through the device in chunks instead of materializing one "
        "[S, N] batch."),
    "tsd.query.streaming.chunk_points": _e(
        "int", "4000000", "Streaming chunk size in points."),
    "tsd.query.streaming.sketch_percentiles": _e(
        "bool", True, "Rank-based downsample fns stream via the "
        "mergeable quantile sketch (approximate); false materializes "
        "subject to the scan budgets."),
    "tsd.query.streaming.sketch_max_merges": _e(
        "int", "4", "Max chunk merges per (series, window) cell before "
        "the planner routes to the exact materialized path (0 trusts "
        "the sketch unconditionally)."),
    "tsd.query.streaming.state_mb": _e(
        "int", "6144", "Refuse queries whose streaming accumulator grid "
        "would exceed this many MB of device memory (0 = unlimited)."),
    "tsd.query.device_cache.enable": _e(
        "bool", True, "Pin hot metrics' columns in device HBM (the "
        "BlockCache analog); repeat queries assemble batches on-device."),
    "tsd.query.device_cache.mb": _e(
        "int", "4096", "Device cache byte budget (LRU eviction)."),
    "tsd.query.device_cache.build_max_points": _e(
        "int", "200000000", "Metrics beyond this many points are never "
        "cached (the streaming path owns beyond-memory scans)."),
    "tsd.query.device_cache.batch_mb": _e(
        "int", "6144", "Decline cached-batch gathers whose padded "
        "[S, N] expansion exceeds this bound."),
    "tsd.query.spill.enable": _e(
        "bool", True, "Serve group-by plans whose [series, windows] "
        "state exceeds tsd.query.streaming.state_mb via series-tiled "
        "streaming with partial-aggregate spill (docs/tiling.md) "
        "instead of refusing with a 413."),
    "tsd.query.spill.host_mb": _e(
        "int", "1024", "Host-RAM ring budget for spilled partial "
        "grids; overflow demotes the oldest entries to disk."),
    "tsd.query.spill.disk_mb": _e(
        "int", "16384", "Disk-overflow budget for spilled partial "
        "grids (0 disables the disk tier; plans whose partials exceed "
        "host+disk refuse)."),
    "tsd.query.spill.dir": _e(
        "str", "", "Directory for disk-tier spill files (empty: a "
        "private tempdir, removed at shutdown)."),
    "tsd.query.spill.max_tiles": _e(
        "int", "1024", "Refuse tiled plans needing more series tiles "
        "than this (0 = unlimited) — a runaway-shape backstop."),
    "tsd.query.cache.enable": _e(
        "bool", True, "Cache per-(series, window) partial aggregates "
        "of fixed-interval downsample plans in aligned blocks and "
        "rewrite overlapping queries to reuse them, dispatching only "
        "the uncovered delta ranges (docs/caching.md)."),
    "tsd.query.cache.mb": _e(
        "int", "256", "Host-tier byte budget for cached aggregate "
        "blocks (LRU eviction)."),
    "tsd.query.cache.device_mb": _e(
        "int", "64", "Device/HBM-tier byte budget for hot aggregate "
        "blocks (0 disables the device mirrors)."),
    "tsd.query.cache.block_windows": _e(
        "int", "32", "Windows per cached block (rounded up to a power "
        "of two; blocks align to the absolute window grid so "
        "overlapping queries share them).  Smaller blocks waste fewer "
        "edge windows per query, larger ones cost fewer dispatches "
        "to populate."),
    "tsd.query.cache.min_repeats": _e(
        "int", "2", "Plan-family occurrences before a cold plan is "
        "worth materializing (1 = populate on first sight)."),
    "tsd.query.cache.promote_hits": _e(
        "int", "2", "Block hits before a host-tier block earns a "
        "device/HBM mirror."),
    "tsd.query.cache.amortize_horizon": _e(
        "int", "32", "Cold-populate admission: the populate overhead "
        "(rewrite minus monolithic predicted cost) must be "
        "recoverable within this many repeat queries' per-hit "
        "savings; plans whose per-hit saving is non-positive "
        "(dispatch-floor regime) never cache."),
    "tsd.query.cache.dispatch_overhead_us": _e(
        "int", "150", "Per-dispatch overhead (microseconds) the "
        "rewrite-vs-recompute costmodel decision charges each "
        "dispatch either side issues."),
    "tsd.query.multi_get.enable": _e(
        "bool", False, "Reference compat multigets toggle.", compat=True),
    "tsd.query.multi_get.limit": _e(
        "int", "131072", "Reference compat multigets bound.",
        compat=True),
    "tsd.query.multi_get.batch_size": _e(
        "int", "1024", "Reference compat multigets batch size.",
        compat=True),
    "tsd.query.multi_get.concurrent": _e(
        "int", "20", "Reference compat multigets concurrency.",
        compat=True),
    "tsd.query.multi_get.get_all_salts": _e(
        "bool", False, "Reference compat multigets salt stance.",
        compat=True),
    "tsd.query.timeout": _e(
        "int", "0", "Per-query wall-clock timeout in ms (0 = none).  "
        "Minted ONCE per request (min with the client's "
        "X-TSDB-Deadline-Ms header) and threaded end-to-end: planner "
        "sub-queries, cluster retries, and fan-out peers all run "
        "under the one remainder."),
    # -- admission control (tsd/admission.py, docs/admission.md) ------- #
    "tsd.query.admission.enable": _e(
        "bool", True,
        "Gate device-dispatching queries (/api/query, /q) behind "
        "bounded concurrency permits + priority wait queues; excess "
        "load sheds 503 + Retry-After instead of stalling the "
        "responder pool."),
    "tsd.query.admission.permits": _e(
        "int", "8",
        "Queries allowed to dispatch device work concurrently; "
        "arrivals beyond this wait in the admission queue."),
    "tsd.query.admission.queue_limit": _e(
        "int", "64",
        "Bound on queued queries across priority classes; a full "
        "queue sheds new arrivals with 503 + Retry-After.  With "
        "tsd.query.tenant.fair_share on, the bound applies PER "
        "clamped tenant (a storming tenant saturates its own backlog "
        "without shedding the rest); off, it is the global total."),
    "tsd.query.admission.max_wait_ms": _e(
        "int", "5000",
        "Longest a query may wait for a permit before being shed "
        "(0 = wait bounded only by the request deadline)."),
    # -- fused multi-query dispatch (query/batcher.py,
    #    docs/batching.md) ---------------------------------------------- #
    "tsd.query.batch.enable": _e(
        "bool", True,
        "Coalesce concurrent dispatch-bound queries (plan_decision "
        "path 'batched') into one stacked [Q, S, N] device kernel "
        "with host-side unpack — the per-dispatch floor is paid once "
        "per bucket instead of once per query.  Uncontended queries "
        "dispatch solo with zero hold."),
    "tsd.query.batch.hold_ms": _e(
        "int", "2",
        "Longest a bucket leader holds the coalesce window open for "
        "joiners.  Applied only while the admission gate shows other "
        "queries in flight — an idle daemon never pays coalesce "
        "latency."),
    "tsd.query.batch.max_q": _e(
        "int", "16",
        "Member queries per stacked dispatch; a full bucket seals and "
        "dispatches immediately."),
    "tsd.query.batch.max_mb": _e(
        "int", "64",
        "Byte bound on one bucket's stacked operands (members' padded "
        "[S, N] batches); a bucket at the bound seals and dispatches "
        "immediately."),
    "tsd.query.batch.amortize_factor": _e(
        "float", "4.0",
        "Coalesce-vs-dispatch-now line: a plan routes through the "
        "batcher when its costmodel-predicted compute plus stack/"
        "unpack overhead stays within this factor x the fitted "
        "stacked-dispatch floor (COST_TERMS stacked_dispatch/"
        "stacked_cell).  Compute-bound plans dispatch now."),
    # -- per-tenant fair share (tsd/admission.py) ----------------------- #
    "tsd.query.tenant.fair_share": _e(
        "bool", True,
        "Drain the admission queues by weighted deficit round robin "
        "across clamped tenants (X-TSDB-Tenant via tsd.diag.tenants) "
        "inside each priority class, so one tenant's dashboard storm "
        "cannot starve the rest.  Off: every query shares one FIFO "
        "identity (the PR 8 behavior)."),
    "tsd.query.tenant.weights": _e(
        "str", "",
        "Per-tenant DRR weights as 'tenant:weight,...' (default "
        "weight 1).  A tenant with weight 2 drains twice the "
        "predicted-cost share per round."),
    "tsd.query.tenant.quantum_ms": _e(
        "int", "50",
        "Deficit-round-robin quantum: predicted-cost milliseconds "
        "credited to each backlogged tenant per virtual drain round, "
        "scaled by its weight."),
    "tsd.query.tenant.max_inflight": _e(
        "int", "0",
        "Cap on admission permits any one tenant may hold "
        "concurrently (0 = no per-tenant cap; the global permit "
        "bound still applies)."),
    "tsd.query.degrade": _e(
        "str", "error",
        "Stance when a query's predicted cost cannot fit its "
        "remaining deadline: 'error' sheds with 503; 'allow' runs the "
        "degradation ladder first (coarsen the downsample interval, "
        "then truncate the range toward the present) and answers 200 "
        "with the partialResults annotation."),
    # -- rpc / rollups / plugins --------------------------------------- #
    "tsd.rpc.plugins": _e(
        "str", "", "Reference compat RPC plugin list.", compat=True),
    "tsd.rpc.telnet.return_errors": _e(
        "bool", True, "Reference compat telnet error stance.",
        compat=True),
    "tsd.rollup.enable": _e(
        "bool", False, "Enable rollup lanes: maintenance-built "
        "multi-resolution pre-aggregation serving any fixed-interval "
        "query whose interval is an integer multiple of a lane "
        "exactly from mergeable sum/count/min/max partials "
        "(docs/rollup.md)."),
    "tsd.rollup.intervals": _e(
        "str", "1m,1h,1d", "Comma-separated lane granularities the "
        "maintenance thread may materialize; the coarsest lane "
        "dividing a query's interval serves it."),
    "tsd.rollup.mb": _e(
        "int", "256", "Byte budget for materialized lane blocks "
        "(Storyboard-style precompute-under-budget: candidates are "
        "selected by costmodel-priced saving per byte; LRU eviction "
        "enforces the budget at insert)."),
    "tsd.rollup.block_windows": _e(
        "int", "64", "Lane cells per materialized block (rounded up "
        "to a power of two; blocks align to the absolute lane "
        "grid)."),
    "tsd.rollup.interval": _e(
        "int", "5", "Seconds between rollup-lane maintenance passes "
        "(demand selection + block builds; 0 disables the cadence — "
        "lanes then only build via explicit refresh() calls)."),
    "tsd.rollup.refresh_blocks": _e(
        "int", "32", "Maximum lane blocks (re)built per maintenance "
        "pass — bounds the per-tick build work."),
    "tsd.rollup.delay_ms": _e(
        "int", "0", "Skip building lane blocks whose range ends "
        "within this many ms of now (the actively-written head would "
        "be invalidated by the next ingest anyway; 0 builds "
        "everything)."),
    "tsd.rollups.enable": _e("bool", False,
                             "Enable rollup/pre-aggregate ingest and "
                             "query serving."),
    "tsd.rollups.config": _e(
        "str", "", "Rollup interval table: inline JSON or @path."),
    "tsd.rollups.tag_raw": _e(
        "bool", False, "Tag raw datapoints with the agg tag on ingest."),
    "tsd.rollups.agg_tag_key": _e(
        "str", "_aggregate", "Tag key marking pre-aggregated series."),
    "tsd.rollups.raw_agg_tag_value": _e(
        "str", "RAW", "Agg-tag value marking raw series."),
    "tsd.rollups.block_derived": _e(
        "bool", True, "Reject queries for derived aggregates with no "
        "stored lane."),
    "tsd.rollups.split_query.enable": _e(
        "bool", False, "Serve query head from rollups and tail from raw "
        "(SplitRollupQuery)."),
    "tsd.rtpublisher.enable": _e(
        "bool", False, "Publish ingested points to a real-time plugin."),
    "tsd.rtpublisher.plugin": _e(
        "str", "", "Real-time publisher plugin class path."),
    "tsd.search.enable": _e("bool", False,
                            "Index meta/annotations into a search "
                            "plugin."),
    "tsd.search.plugin": _e("str", "", "Search plugin class path."),
    "tsd.stats.canonical": _e(
        "bool", False, "Reference compat canonical-stats naming.",
        compat=True),
    "tsd.startup.enable": _e("bool", False, "Run a startup plugin."),
    "tsd.startup.plugin": _e("str", "", "Startup plugin class path."),
    # -- storage ------------------------------------------------------- #
    "tsd.storage.fix_duplicates": _e(
        "bool", False, "Resolve duplicate timestamps at read (last "
        "write wins) instead of raising."),
    "tsd.storage.flush_interval": _e(
        "int", "1000", "Reference compat HBase flush interval.",
        compat=True),
    "tsd.storage.data_table": _e(
        "str", "tsdb", "Reference compat table name.", compat=True),
    "tsd.storage.uid_table": _e(
        "str", "tsdb-uid", "Reference compat table name.", compat=True),
    "tsd.storage.tree_table": _e(
        "str", "tsdb-tree", "Reference compat table name.", compat=True),
    "tsd.storage.meta_table": _e(
        "str", "tsdb-meta", "Reference compat table name.", compat=True),
    "tsd.storage.enable_appends": _e(
        "bool", False, "Reference compat append-write mode.",
        compat=True),
    "tsd.storage.repair_appends": _e(
        "bool", False, "Reference compat append repair mode.",
        compat=True),
    "tsd.storage.enable_compaction": _e(
        "bool", True, "Background-compact dirty series rows."),
    "tsd.storage.compaction.flush_interval": _e(
        "int", "10", "Seconds between compaction flush passes."),
    "tsd.storage.compaction.min_flush_threshold": _e(
        "int", "100", "Backlog size that triggers an early flush pass."),
    "tsd.storage.compaction.max_concurrent_flushes": _e(
        "int", "10000", "Max series flushed per pass."),
    "tsd.storage.compaction.flush_speed": _e(
        "int", "2", "Backlog-pressure multiplier on the per-pass flush "
        "slice."),
    "tsd.storage.wal_sync_interval": _e(
        "int", "0", "Seconds between WAL fsync passes (0 = disabled; "
        "line buffering still survives process crashes)."),
    "tsd.storage.wal.segment_mb": _e(
        "int", "64", "WAL segment rotation size; segments are named by "
        "their first sequence number so a replica can catch up from an "
        "arbitrary offset without the owner rescanning one unbounded "
        "file."),
    "tsd.storage.wal.fsync": _e(
        "bool", False, "fsync the WAL per journaled record: "
        "crash-consistent at ingest cost (default rides the "
        "wal_sync_interval cadence)."),
    "tsd.storage.snapshot_interval": _e(
        "int", "0", "Seconds between full state snapshots (0 = "
        "disabled)."),
    "tsd.storage.native_snapshot": _e(
        "bool", True, "Snapshot series via the compressed native chunk "
        "engine; falls back to npz when the library can't build."),
    "tsd.storage.salt.width": _e(
        "int", "0", "Row-key salt width (reference parity; affects "
        "TSUID shape)."),
    "tsd.storage.salt.buckets": _e(
        "int", "20", "Salt bucket count."),
    "tsd.storage.uid.width.metric": _e(
        "int", "3", "Metric UID byte width."),
    "tsd.storage.uid.width.tagk": _e(
        "int", "3", "Tag-key UID byte width."),
    "tsd.storage.uid.width.tagv": _e(
        "int", "3", "Tag-value UID byte width."),
    "tsd.storage.max_tags": _e(
        "int", "8", "Reference compat max tags per point (enforced as a "
        "constant here).", compat=True),
    "tsd.storage.directory": _e(
        "dir", "", "Directory for snapshots + the WAL; empty disables "
        "persistence."),
    # -- uid / filters ------------------------------------------------- #
    "tsd.timeseriesfilter.enable": _e(
        "bool", False, "Enable the per-point write filter plugin."),
    "tsd.timeseriesfilter.plugin": _e(
        "str", "", "Write filter plugin class path."),
    "tsd.uid.use_mode": _e(
        "bool", False, "Reference compat UID mode flag.", compat=True),
    "tsd.uid.lru.enable": _e(
        "bool", False, "Reference compat UID LRU cache toggle.",
        compat=True),
    "tsd.uid.lru.name.size": _e(
        "int", "5000000", "Reference compat UID LRU bound.", compat=True),
    "tsd.uid.lru.id.size": _e(
        "int", "5000000", "Reference compat UID LRU bound.", compat=True),
    "tsd.uidfilter.enable": _e(
        "bool", False, "Enable the UID-assignment filter plugin."),
    "tsd.uidfilter.plugin": _e(
        "str", "", "UID filter plugin class path."),
    "tsd.uidfilter.metric_whitelist": _e(
        "str", "", "Comma-separated regexes a new metric name must "
        "match (UniqueIdWhitelistFilter)."),
    "tsd.uidfilter.metric_blacklist": _e(
        "str", "", "Comma-separated regexes that reject a new metric "
        "name."),
    "tsd.uidfilter.tagk_whitelist": _e(
        "str", "", "Whitelist regexes for new tag keys."),
    "tsd.uidfilter.tagk_blacklist": _e(
        "str", "", "Blacklist regexes for new tag keys."),
    "tsd.uidfilter.tagv_whitelist": _e(
        "str", "", "Whitelist regexes for new tag values."),
    "tsd.uidfilter.tagv_blacklist": _e(
        "str", "", "Blacklist regexes for new tag values."),
    # -- http ---------------------------------------------------------- #
    "tsd.http.show_stack_trace": _e(
        "bool", True, "Include the stack trace in error envelopes."),
    "tsd.http.query.allow_delete": _e(
        "bool", False, "Allow DELETE /api/query (and the delete query "
        "flag) to drop matched datapoints."),
    "tsd.http.header_tag": _e(
        "str", "", "Reference compat header-to-tag mapping.",
        compat=True),
    "tsd.http.request.enable_chunked": _e(
        "bool", True, "Reference compat chunked-request toggle.",
        compat=True),
    "tsd.http.request.max_chunk": _e(
        "int", "1048576", "Reference compat chunk size bound.",
        compat=True),
    "tsd.http.request.cors_domains": _e(
        "str", "", "Comma-separated origins allowed CORS access "
        "(* allows any)."),
    "tsd.http.request.cors_headers": _e(
        "str", ("Authorization, Content-Type, Accept, Origin, "
                "User-Agent, DNT, Cache-Control, X-Mx-ReqToken, "
                "Keep-Alive, X-Requested-With, If-Modified-Since"),
        "Headers returned in Access-Control-Allow-Headers."),
    "tsd.http.cachedir": _e(
        "dir", "", "Graph/cache scratch directory."),
    "tsd.http.staticroot": _e(
        "dir", "", "Static UI file root."),
    # -- TPU-native knobs (no reference equivalent) -------------------- #
    "tsd.tpu.enable": _e(
        "bool", True, "Reserved master toggle for accelerator serving.",
        compat=True),
    "tsd.tpu.mesh.shards": _e(
        "int", "0", "Device-mesh shard count (0 = all visible devices).",
        compat=True),
    "tsd.tpu.batch.max_series": _e(
        "int", "4096", "Reserved batch-width bound.", compat=True),
    "tsd.tpu.batch.pad_pow2": _e(
        "bool", True, "Reserved pow2-padding toggle.", compat=True),
    "tsd.tpu.precision.x64": _e(
        "bool", True, "Require 64-bit JAX arithmetic (Java double/long "
        "parity; int64 ms timestamps).  True (default): TSDB "
        "construction re-enables jax_enable_x64 if something turned it "
        "off.  False: x64 is left alone and the downsample planners "
        "refuse int64 window math while it is off rather than silently "
        "truncate ms timestamps."),
}

# Defaults mirror Config.setDefaults (Config.java:560-659) plus TPU-native
# keys; derived from the schema so the two can never diverge.
DEFAULTS: dict[str, str] = {k: e.default for k, e in CONFIG_SCHEMA.items()}

_SECRET_MARKERS = ("pass", "key", "secret", "token")


def generate_config_doc() -> str:
    """Render docs/configuration.md from CONFIG_SCHEMA (one table per
    top-level prefix).  tests/test_lint_clean.py pins the committed file
    to this output."""
    groups: dict[str, list[tuple[str, ConfigEntry]]] = {}
    for key, entry in sorted(CONFIG_SCHEMA.items()):
        prefix = ".".join(key.split(".")[:2])
        groups.setdefault(prefix, []).append((key, entry))
    lines = [
        "# Configuration reference",
        "",
        "<!-- GENERATED FILE — do not edit by hand.",
        "     Regenerate with: python tools/lint/run.py --update-doc",
        "     Source of truth: opentsdb_tpu/utils/config.py "
        "CONFIG_SCHEMA. -->",
        "",
        "All keys live in a flat Java-properties file "
        "(`./opentsdb.conf` or `/etc/opentsdb/opentsdb.conf`, or any "
        "path passed to `Config`).  Types are enforced by tsdblint "
        "against the accessor used at every read site.  Keys marked "
        "*compat* are accepted from reference OpenTSDB config files but "
        "not read by this codebase yet.",
        "",
    ]
    for prefix in sorted(groups):
        lines.append("## `%s.*`" % prefix)
        lines.append("")
        lines.append("| key | type | default | description |")
        lines.append("|---|---|---|---|")
        for key, entry in groups[prefix]:
            default = entry.default if len(entry.default) <= 40 \
                else entry.default[:37] + "..."
            doc = entry.doc + (" *(compat)*" if entry.compat else "")
            lines.append("| `%s` | %s | `%s` | %s |"
                         % (key, entry.type,
                            default.replace("|", "\\|") or " ",
                            doc.replace("|", "\\|")))
        lines.append("")
    return "\n".join(lines)


class Config:
    """Typed accessor over a flat key->string map, file- and dict-loadable."""

    def __init__(self, properties: dict[str, Any] | None = None,
                 config_file: str | None = None, auto_load: bool = False):
        self._map: dict[str, str] = dict(DEFAULTS)
        self.config_location: str | None = None
        if auto_load and config_file is None:
            for candidate in ("./opentsdb.conf", "/etc/opentsdb/opentsdb.conf"):
                if os.path.isfile(candidate):
                    config_file = candidate
                    break
        if config_file:
            self.load_file(config_file)
        if properties:
            for k, v in properties.items():
                self._map[k] = self._stringify(v)

    @staticmethod
    def _stringify(value: Any) -> str:
        if isinstance(value, bool):
            return "true" if value else "false"
        return str(value)

    def load_file(self, path: str) -> None:
        with open(path, "r") as fh:
            for line in fh:
                line = line.strip()
                if not line or line.startswith("#") or line.startswith("!"):
                    continue
                if "=" not in line:
                    continue
                key, _, value = line.partition("=")
                self._map[key.strip()] = value.strip()
        self.config_location = path

    # -- typed getters (Config.java getString/getInt/getBoolean...) --

    def has_property(self, key: str) -> bool:
        return key in self._map

    def get_string(self, key: str) -> str:
        if key not in self._map:
            raise KeyError(key)
        return self._map[key]

    def get_int(self, key: str) -> int:
        return int(self.get_string(key))

    def get_float(self, key: str) -> float:
        return float(self.get_string(key))

    def get_bool(self, key: str) -> bool:
        value = self.get_string(key).strip().lower()
        return value in ("1", "true", "yes")

    def get_directory_name(self, key: str) -> str:
        path = self.get_string(key)
        if path and not path.endswith(os.sep):
            path += os.sep
        return path

    def override_config(self, key: str, value: Any) -> None:
        self._map[key] = self._stringify(value)

    def as_map(self, obfuscate: bool = True) -> dict[str, str]:
        """Full config dump for /api/config; secrets hidden like the reference."""
        out = {}
        for key, value in sorted(self._map.items()):
            if obfuscate and any(m in key.lower() for m in _SECRET_MARKERS):
                out[key] = "********"
            else:
                out[key] = value
        return out

    def dump_json(self) -> str:
        return json.dumps(self.as_map(), indent=2)

    # -- convenience flags used on hot paths --

    @property
    def auto_metric(self) -> bool:
        return self.get_bool("tsd.core.auto_create_metrics")

    @property
    def enable_compactions(self) -> bool:
        return self.get_bool("tsd.storage.enable_compaction")

    @property
    def fix_duplicates(self) -> bool:
        return self.get_bool("tsd.storage.fix_duplicates")

    @property
    def salt_width(self) -> int:
        return self.get_int("tsd.storage.salt.width")

    @property
    def salt_buckets(self) -> int:
        return self.get_int("tsd.storage.salt.buckets")

"""TSDB facade: write path, UID administration, query entry.

Reference behavior: /root/reference/src/core/TSDB.java (:87) — the god object
owning the storage client, the three UID dictionaries (:297-302), plugins and
the write path `addPoint` (:1051-1136) with timestamp/tag validation (:1313).
The HBase client + row-key codec are replaced by the columnar MemStore; the
3-byte UID scheme, validation rules, and second/millisecond timestamp
heuristic (Const.SECOND_MASK: ts >= 2^32 means milliseconds) are kept.
"""

from __future__ import annotations

import threading
import time

from opentsdb_tpu import __version__, SHORT_VERSION
from opentsdb_tpu.storage import MemStore
from opentsdb_tpu.storage.memstore import Annotation, SeriesKey, MAX_NUM_TAGS
from opentsdb_tpu.uid import (UniqueId, UniqueIdType, NoSuchUniqueName)
from opentsdb_tpu.utils.config import Config

SECOND_MASK = 0xFFFFFFFF00000000  # Const.java:19 — set bits mean milliseconds

_UNSET = object()  # lazily-built query_mesh sentinel


def normalize_timestamp_ms(timestamp: int | float) -> int:
    """Seconds-or-milliseconds heuristic (TSDB.addPointInternal).

    Values below 2^32 are treated as Unix seconds, larger as milliseconds.
    """
    ts = int(timestamp)
    if ts < 0:
        raise ValueError(
            "The timestamp must be positive and within the extent of a "
            "64-bit integer: %s" % timestamp)
    if ts & SECOND_MASK:
        return ts
    return ts * 1000


class TSDB:
    """The top-level handle: storage + UID dictionaries + write/query APIs."""

    def __init__(self, config: Config | None = None):
        self.config = config or Config()
        self._mesh_lock = threading.Lock()
        self._query_mesh = _UNSET  # guarded-by: _mesh_lock
        self._query_limits = None
        self.maintenance = None
        # extra stats sources keyed by owner (RpcManager registers the
        # ingest/error/server counters); walked by /api/stats AND the
        # self-report loop through obs.selfreport.collect_all.
        # Initialized BEFORE initialize_plugins so a plugin may
        # register its own hook during startup.
        self.stats_hooks: dict = {}
        self._apply_precision_config()
        # chaos/failure-testing hooks (tsd.faults.config; no-op unless
        # armed) — installed before any storage or network touchpoint so
        # WAL-replay faults inject from the very first restore
        from opentsdb_tpu.utils import faults
        faults.install_from_config(self.config)
        self.metrics = UniqueId(
            UniqueIdType.METRIC,
            width=self.config.get_int("tsd.storage.uid.width.metric"),
            random_ids=self.config.get_bool("tsd.core.uid.random_metrics"))
        self.tag_names = UniqueId(
            UniqueIdType.TAGK,
            width=self.config.get_int("tsd.storage.uid.width.tagk"))
        self.tag_values = UniqueId(
            UniqueIdType.TAGV,
            width=self.config.get_int("tsd.storage.uid.width.tagv"))
        self.store = MemStore(
            salt_buckets=self.config.salt_buckets,
            fix_duplicates=self.config.fix_duplicates)
        # the planner's memo of resolved and grouped series selections
        # (query/planner.py::_Selection), valid per store generation
        from opentsdb_tpu.query.planner import SelectionMemo
        self.selections = SelectionMemo()
        from opentsdb_tpu.storage.device_cache import DeviceSeriesCache
        self.device_cache = (
            DeviceSeriesCache(
                self.config.get_int("tsd.query.device_cache.mb") * 2**20,
                self.config.get_int(
                    "tsd.query.device_cache.build_max_points"),
                fix_duplicates=self.config.fix_duplicates,
                batch_max_bytes=self.config.get_int(
                    "tsd.query.device_cache.batch_mb") * 2**20)
            if self.config.get_bool("tsd.query.device_cache.enable")
            else None)
        # partial-aggregate block cache (ROADMAP item 2): overlapping
        # sliding-window queries reuse per-(series, window) downsample
        # factors; the memstore write path marks the affected
        # (metric, sub-window) keys dirty as each write lands
        # (write-then-mark — see storage/memstore.py)
        from opentsdb_tpu.storage.agg_cache import AggregateCache
        self.agg_cache = (AggregateCache(self.config)
                          if self.config.get_bool("tsd.query.cache.enable")
                          else None)
        if self.agg_cache is not None:
            cache = self.agg_cache
            store = self.store
            self.store.add_mutation_listener(
                lambda metric, lo, hi: cache.note_mutation(
                    metric, lo, hi, store=store))
        # bounded partial-aggregate spill pool (ROADMAP item 4): backs
        # the out-of-core tiled executor (ops/tiling.py) so group-by
        # plans past the tsd.query.streaming.state_mb wall answer
        # instead of refusing; closed (files unlinked) at shutdown
        from opentsdb_tpu.storage.spill import SpillPool
        self.spill_pool = (
            SpillPool(
                self.config.get_int("tsd.query.spill.host_mb") * 2**20,
                self.config.get_int("tsd.query.spill.disk_mb") * 2**20,
                directory=self.config.get_string("tsd.query.spill.dir")
                or None)
            if self.config.get_bool("tsd.query.spill.enable") else None)
        # rollup lanes (ROADMAP item 2): maintenance-built coarse-
        # interval aggregate lanes (mergeable sum/count/min/max
        # partials) serve any fixed-interval query whose interval is a
        # multiple of a lane EXACTLY, in front of the agg-cache/tiled/
        # streamed exact paths; ingest-side invalidation rides the same
        # write-then-mark listener contract as the agg cache
        from opentsdb_tpu.storage.rollup import RollupLanes
        self.rollup_lanes = (RollupLanes(self.config)
                             if self.config.get_bool("tsd.rollup.enable")
                             else None)
        if self.rollup_lanes is not None:
            lanes = self.rollup_lanes
            self.store.add_mutation_listener(
                lambda metric, lo, hi: lanes.note_mutation(
                    metric, lo, hi))
        # flight recorder (obs/flightrec.py): the always-on diagnostics
        # ring every query-path subsystem feeds — admission verdicts,
        # cache/rollup consults, spills, breaker
        # transitions, deadline expiries, recompiles — served at
        # /api/diag and dumped at shutdown so a wedged session leaves
        # a black box
        from opentsdb_tpu.obs.flightrec import FlightRecorder
        self.flightrec = (FlightRecorder(self.config)
                          if self.config.get_bool("tsd.diag.enable")
                          else None)
        if self.flightrec is not None:
            # the compile-event feed (flightrec.start) is armed by the
            # SERVER, not here: subscribing flips jax_log_compiles
            # process-wide, which a bare library TSDB must not do —
            # same split as jaxprof.start_compile_counting
            self.stats_hooks["diag"] = self.flightrec.stats_hook
            if self.agg_cache is not None:
                self.agg_cache.recorder = self.flightrec
            if self.rollup_lanes is not None:
                self.rollup_lanes.recorder = self.flightrec
            if self.spill_pool is not None:
                self.spill_pool.recorder = self.flightrec
        # always-on latency attribution (obs/latattr.py): per-phase
        # stamps the RPC layer attaches to EVERY request fold into
        # bounded profiles keyed by (route, plan fingerprint, tenant),
        # served at /api/diag/latency — where the milliseconds went,
        # with tracing off
        from opentsdb_tpu.obs.latattr import LatencyAttribution
        self.latattr = (LatencyAttribution(self.config)
                        if self.config.get_bool("tsd.latattr.enable")
                        else None)
        if self.latattr is not None:
            self.stats_hooks["latattr"] = self.latattr.stats_hook
        # fused multi-query dispatch (query/batcher.py, ROADMAP item
        # 1): concurrent dispatch-bound plans (plan_decision path
        # "batched") coalesce into one stacked [Q, S, N] kernel with
        # host-side unpack; uncontended queries fall through as solo
        # dispatches with zero hold
        from opentsdb_tpu.query.batcher import DispatchBatcher
        self.dispatch_batcher = (
            DispatchBatcher(self.config, tsdb=self)
            if self.config.get_bool("tsd.query.batch.enable") else None)
        from opentsdb_tpu.rollup import RollupConfig, RollupStore
        self.rollup_config = RollupConfig.from_config(self.config)
        self.rollup_store = (
            RollupStore(self.rollup_config, self.config.salt_buckets)
            if self.rollup_config is not None else None)
        self.agg_tag_key = self.config.get_string("tsd.rollups.agg_tag_key")
        self.raw_agg_tag_value = self.config.get_string(
            "tsd.rollups.raw_agg_tag_value")
        self.tag_raw_data = self.config.get_bool("tsd.rollups.tag_raw")
        self.rollups_block_derived = self.config.get_bool(
            "tsd.rollups.block_derived")
        from opentsdb_tpu.histogram import (HistogramCodecManager,
                                            HistogramStore)
        self.histogram_manager = HistogramCodecManager.from_config(
            self.config)
        self.histogram_store = (HistogramStore()
                                if self.histogram_manager else None)
        from opentsdb_tpu.meta import MetaStore
        from opentsdb_tpu.tree import TreeStore
        self.meta_store = MetaStore()
        self.tree_store = TreeStore()
        self.tree_processing = self.config.get_bool(
            "tsd.core.tree.enable_processing")
        self.rt_publisher = None    # RTPublisher plugin
        self.storage_exception_handler = None
        self.search_plugin = None   # wired by plugins.initialize_plugins
        self.enable_tsuid_tracking = (
            self.config.get_bool("tsd.core.meta.enable_tsuid_tracking")
            or self.config.get_bool(
                "tsd.core.meta.enable_tsuid_incrementing"))
        self.enable_realtime_ts = self.config.get_bool(
            "tsd.core.meta.enable_realtime_ts")
        self.enable_realtime_uid = self.config.get_bool(
            "tsd.core.meta.enable_realtime_uid")
        if self.enable_realtime_uid:
            for kind, table in (("metric", self.metrics),
                                ("tagk", self.tag_names),
                                ("tagv", self.tag_values)):
                table.on_create = self._make_uid_meta_hook(kind, table)
        self.write_filter = None    # WriteableDataPointFilterPlugin
        self.authentication = None
        self.startup_plugin = None
        self.mode = self.config.get_string("tsd.mode")  # rw / ro / wo
        # health engine (obs/health.py): declared invariants evaluated
        # on the maintenance cadence into per-subsystem verdicts at
        # /api/diag/health — the chaos_soak post-heal gate.  Needs
        # start_time, so it initializes below after the clock is set.
        self.health = None
        from opentsdb_tpu.plugins import initialize_plugins
        initialize_plugins(self)
        self.start_time = time.time()
        if self.config.get_bool("tsd.health.enable"):
            from opentsdb_tpu.obs.health import HealthEngine
            self.health = HealthEngine(self)
            self.stats_hooks["health"] = self.health.stats_hook
        self._stats_lock = threading.Lock()
        # Serializes ingest against snapshots: writers hold it briefly per
        # record; snapshot() holds it for its stop-the-world walk so no
        # journaled write can fall between the state capture and WAL reset.
        self._ingest_lock = threading.RLock()
        # guarded-by: _stats_lock
        self.datapoints_added = 0
        self.illegal_arguments = 0  # guarded-by: _stats_lock
        self.unknown_metrics = 0  # guarded-by: _stats_lock
        # Restore LAST: WAL replay drives the full _apply_* paths, which
        # touch stats/meta/tree state initialized above.
        # _replaying is a property: the process-wide flag (startup WAL
        # replay) OR a per-thread flag (replication apply — concurrent
        # ingest on other threads must keep journaling)
        self._replay_tls = threading.local()
        self._replaying = False   # WAL replay bypasses the ro-mode gate
        # sharded ownership + WAL-shipping replication
        # (tsd/replication.py, docs/replication.md) — constructed
        # BEFORE the restore below so replayed "rr" records can rebuild
        # the per-origin catch-up positions
        self.replication = None
        if self.config.get_bool("tsd.network.cluster.shard.enable"):
            from opentsdb_tpu.tsd.replication import ReplicationManager
            self.replication = ReplicationManager(self)
            self.stats_hooks["replication"] = self.replication.stats_hook
        self.persistence = None
        storage_dir = self.config.get_string("tsd.storage.directory")
        if storage_dir:
            from opentsdb_tpu.storage.persist import DiskPersistence
            self.persistence = DiskPersistence(self, storage_dir)
            self.persistence.restore()

    @property
    def _replaying(self) -> bool:
        return self._replaying_flag or getattr(self._replay_tls, "on",
                                               False)

    @_replaying.setter
    def _replaying(self, value: bool) -> None:
        self._replaying_flag = value

    # ------------------------------------------------------------------ #
    # Write path (TSDB.addPoint :1051)                                   #
    # ------------------------------------------------------------------ #

    def _apply_precision_config(self) -> None:
        """Enforce tsd.tpu.precision.x64 (default true): ms-resolution
        timestamps are int64, and with jax_enable_x64 off jnp.int64
        silently degrades to int32 — every timestamp past 2^31 ms
        truncates.  The ops package enables x64 at import; with the key
        true this RE-ENABLES it per TSDB construction (flipping the
        process-global flag back on if an embedder turned it off), so
        queries never run in the silently-truncating state.  With the
        key false nothing is re-asserted and the downsample planners'
        require_x64 guard raises at query-plan time instead (the
        operator owns that choice and gets a warning here)."""
        import jax

        from opentsdb_tpu import ops  # noqa: F401  (enables x64 on import)
        if self.config.get_bool("tsd.tpu.precision.x64"):
            if not jax.config.jax_enable_x64:
                jax.config.update("jax_enable_x64", True)
        else:
            import logging
            logging.getLogger("tsdb").warning(
                "tsd.tpu.precision.x64=false: x64 is not re-asserted for "
                "this TSDB; if jax_enable_x64 is turned off the "
                "downsample planners refuse int64 window math "
                "(ops.downsample.require_x64) rather than truncate "
                "ms timestamps")

    def check_timestamp_and_tags(self, metric: str, timestamp: int | float,
                                 value, tags: dict[str, str]) -> None:
        """Validation rules of TSDB.checkTimestampAndTags (:1313)."""
        if not tags:
            raise ValueError(
                "Need at least one tag (metric=%s, ts=%s)" % (metric, timestamp))
        if len(tags) > MAX_NUM_TAGS:
            raise ValueError(
                "Too many tags: %d maximum allowed: %d" %
                (len(tags), MAX_NUM_TAGS))
        if int(timestamp) < 0:
            raise ValueError("Invalid timestamp: %s" % timestamp)

    def add_point(self, metric: str, timestamp: int | float, value,
                  tags: dict[str, str]) -> None:
        """Store one datapoint; value may be int, float, or numeric string.

        With sharded replication armed the point first routes to its
        shard's accepting member (forwarded in one hop when that is a
        peer); a locally-accepted point journals with its shard id and
        ships synchronously to the shard's replicas before returning —
        the ack-path durability contract (tsd/replication.py)."""
        repl = self.replication
        if repl is not None and not self._replaying:
            if repl.should_route() \
                    and repl.route_point(metric, timestamp, value, tags):
                return
            # accepting member (owner, failover member, or the routed
            # hop's receiver): apply + journal with the shard id, then
            # ship to the shard's replicas before acking
            shard = repl.shard_of(metric, tags)
            entry = None
            with self._ingest_lock:
                self._apply_point(metric, timestamp, value, tags)
                if self.persistence is not None:
                    rec = {"k": "p", "m": metric, "t": timestamp,
                           "v": value, "g": dict(tags), "sh": shard}
                    seq, crc = self.persistence.journal(rec)  # order-event: wal-append
                    entry = (seq, crc, shard, rec)
            if entry is not None:
                # order: wal-append before replica-ship
                repl.on_committed([entry])
            return
        with self._ingest_lock:
            self._apply_point(metric, timestamp, value, tags)
            if self.persistence is not None:
                self.persistence.journal({"k": "p", "m": metric,  # order-event: wal-append
                                          "t": timestamp, "v": value,
                                          "g": dict(tags)})

    def _validate_put_dp(self, dp: dict):
        """Per-point /api/put validation, storage-free (no UID creation):
        required fields, value parse + Java-long range, timestamp/tags.
        Returns (metric, tags, is_int, num); raises the same error the
        stored path would."""
        for field in ("metric", "timestamp", "value", "tags"):
            if field not in dp or dp[field] in (None, "", {}):
                raise ValueError("Missing required field: %s" % field)
        metric = dp["metric"]
        tags = dict(dp["tags"])
        is_int, num = parse_value(dp["value"])
        if is_int and not (-(1 << 63) <= num < (1 << 63)):
            # beyond Java long (the reference's parseLong rejects it per
            # point); without this check the group's int64 column build
            # would fail EVERY point of the series
            raise ValueError("Invalid value, out of long range: %r"
                             % dp["value"])
        self.check_timestamp_and_tags(metric, dp["timestamp"], num, tags)
        return metric, tags, is_int, num

    def add_points_bulk(self, dps: list[dict]
                        ) -> tuple[int, list[tuple[int, Exception]]]:
        """Vectorized bulk ingest for POST /api/put bodies.

        The reference writes each point through one addPoint call
        (PutDataPointRpc.processDataPoint :309 -> TSDB.addPoint :1051);
        per-point that costs a parse, a validation, a key resolution, a
        lock and a journal write.  Here points validate individually (so
        per-point error reporting survives) but group by series, and each
        series takes ONE lock + ONE columnar append_batch; the WAL gets
        one record per request.  Returns (success_count,
        [(index, exception), ...]) with indexes into `dps`.

        With sharded replication armed the body partitions by accepting
        member first (tsd/replication.py ingest_bulk): remote groups
        forward in one POST each, local groups land per shard so every
        WAL record carries one shard id and ships to that shard's
        replicas.
        """
        repl = self.replication
        if repl is not None and not self._replaying:
            return repl.ingest_bulk(dps)
        return self._add_points_bulk_local(dps)

    def _add_points_bulk_local(self, dps: list[dict], shard: int | None
                               = None) -> tuple[int, list]:
        """The locally-accepted bulk path.  ``shard`` (replication only)
        stamps the journaled record and ships it to the shard's
        replicas after commit."""
        import numpy as np

        if self.mode == "ro" and not self._replaying:
            # Validation errors first, RO for the rest — matching the
            # per-point path, where parsing reports before add_point hits
            # the RO gate (ADVICE r3): error classes and the RPC layer's
            # accounting (illegal_arguments vs hbase_errors, SEH spillway,
            # 400 + summary) must not depend on the ingest path taken.
            exc = RuntimeError("TSD is in read-only mode, writes rejected")
            ro_errors: list[tuple[int, Exception]] = []
            for i, dp in enumerate(dps):
                try:
                    self._validate_put_dp(dp)
                except Exception as e:
                    ro_errors.append((i, e))
                else:
                    ro_errors.append((i, exc))
            return 0, ro_errors
        errors: list[tuple[int, Exception]] = []
        # key -> (ts_ms, float, exact-int, is_int, dp index, raw dp,
        #         publish args) column lists
        groups: dict = {}
        key_cache: dict = {}
        success = 0
        for i, dp in enumerate(dps):
            try:
                metric, tags, is_int, num = self._validate_put_dp(dp)
                if self.write_filter is not None and \
                        not self.write_filter.allow(metric, dp["timestamp"],
                                                    num, tags):
                    success += 1   # silently dropped, like _apply_point
                    continue
                ts_ms = normalize_timestamp_ms(dp["timestamp"])
                if self.rollup_store is not None and self.tag_raw_data:
                    tags[self.agg_tag_key] = self.raw_agg_tag_value
                ck = (metric, tuple(sorted(tags.items())))
                key = key_cache.get(ck)
                if key is None:
                    key = self._series_key(metric, tags, create=True)
                    key_cache[ck] = key
                bucket = groups.get(key)
                if bucket is None:
                    bucket = groups[key] = ([], [], [], [], [], [], [])
                bucket[0].append(ts_ms)
                bucket[1].append(float(num))
                bucket[2].append(int(num) if is_int else 0)
                bucket[3].append(is_int)
                bucket[4].append(i)
                bucket[5].append(dp)
                if self.rt_publisher is not None:
                    bucket[6].append((metric, ts_ms, num, tags, key))
                success += 1
            except Exception as e:
                errors.append((i, e))
        stored: list[dict] = []    # journal only what actually landed
        publish: list = []
        entry = None
        with self._ingest_lock:
            for key, (tss, fvals, ivals, isints, idxs, raw,
                      pubs) in groups.items():
                try:
                    ts_arr = np.asarray(tss, np.int64)
                    self.store.add_batch(
                        key, ts_arr, np.asarray(fvals, np.float64),
                        np.asarray(isints, bool),
                        ival=np.asarray(ivals, np.int64))
                except Exception as e:
                    # storage failure: every point of this series batch
                    # reports it (SEH spillway parity with the per-point
                    # path's storeIntoDB error callbacks)
                    errors.extend((i, e) for i in idxs)
                    success -= len(idxs)
                    continue
                with self._stats_lock:
                    self.datapoints_added += len(tss)
                self._track_meta(key, int(ts_arr.max()), n=len(tss))
                stored.extend(raw)
                publish.extend(pubs)
            if self.persistence is not None and stored \
                    and not self._replaying:
                rec = {"k": "pb", "d": stored}
                if shard is not None:
                    rec["sh"] = shard
                seq, crc = self.persistence.journal(rec)  # order-event: wal-append
                if shard is not None:
                    entry = (seq, crc, shard, rec)
        if entry is not None and self.replication is not None:
            # order: wal-append before replica-ship
            self.replication.on_committed([entry])
        for metric, ts_ms, num, tags, key in publish:
            self.rt_publisher.publish_data_point(metric, ts_ms, num, tags,
                                                 key.tsuid())
        errors.sort(key=lambda t: t[0])
        return success, errors

    def add_points_bulk_native(self, body: bytes):
        """Native-parser fast path for a raw /api/put JSON body.

        The C++ parser (native/engine.cpp eng_put_parse) does the per-point
        work — JSON walk, validation with the Python path's exact error
        strings, value classification, timestamp normalization, series-key
        canonicalization — in one pass over the body bytes; Python cost
        drops to O(distinct series).  Returns
        (success, [(index, exception)], spans[n, 2]) or None when the fast
        path does not apply: native library absent, malformed JSON (the
        Python path owns the user-visible parse error), a construct the
        parser refuses to mirror, or a TSDB feature that needs per-point
        Python hooks (write filter, real-time publisher, raw-data rollup
        tagging).  With persistence on, the raw body journals as one
        "pj" WAL record; replay re-parses it through this same path.
        """
        if not self._native_ingest_eligible():
            return None
        body_text = None
        if self.persistence is not None and not self._replaying:
            try:
                # journaled verbatim as a "pj" record; replay re-parses
                # through this same path (deterministic per-point outcome)
                body_text = body.decode("utf-8")
            except UnicodeDecodeError:
                return None
        from opentsdb_tpu.storage.native_engine import parse_put_body
        parsed = parse_put_body(body)
        if parsed is None:
            return None
        success, errors = self._ingest_parsed_columns(
            parsed, {"k": "pj", "b": body_text}
            if body_text is not None else None)
        return success, errors, parsed.spans

    def _native_ingest_eligible(self) -> bool:
        """True when no TSDB feature needs per-point Python hooks.
        Sharded replication needs per-point shard routing, so its
        daemons take the Python bulk path (which partitions by owner)."""
        return (self.write_filter is None and self.rt_publisher is None
                and self.replication is None
                and not (self.rollup_store is not None
                         and self.tag_raw_data))

    def _ingest_parsed_columns(self, parsed, journal_record
                               ) -> tuple[int, list]:
        """Land a native-parsed column batch: per-group key resolution,
        columnar appends, stats/meta, WAL.  Shared by the JSON-body and
        telnet-block fast paths.  Returns (success, [(index, exc)])."""
        import numpy as np

        if self.mode == "ro" and not self._replaying:
            # Per-point path parity: points whose parse already failed
            # report their ValueError/TypeError (validation runs before
            # the RO gate there); only parseable points get the RO error
            # (ADVICE r3).
            exc = RuntimeError("TSD is in read-only mode, writes rejected")
            ro_errors: dict[int, Exception] = {
                i: ValueError(msg) if kind == "ValueError"
                else TypeError(msg)
                for i, kind, msg in parsed.errors}
            return 0, [(i, ro_errors.get(i, exc)) for i in range(parsed.n)]
        errors: list[tuple[int, Exception]] = [
            (i, ValueError(msg) if kind == "ValueError" else TypeError(msg))
            for i, kind, msg in parsed.errors]
        success = parsed.n - len(errors)

        # one key resolution per DISTINCT series; a resolution failure
        # (e.g. unknown metric with auto-create off) fails every point of
        # that group, exactly like the per-point path would
        keys: list = []
        for metric, tags in parsed.group_keys:
            try:
                keys.append(self._series_key(metric, tags, create=True))
            except Exception as e:
                keys.append(e)

        order = np.argsort(parsed.group, kind="stable")
        order = order[parsed.group[order] >= 0]
        bounds = np.searchsorted(parsed.group[order],
                                 np.arange(len(keys) + 1))
        with self._ingest_lock:
            for g in range(len(keys)):
                idx = order[bounds[g]:bounds[g + 1]]
                if not len(idx):
                    continue
                key = keys[g]
                if isinstance(key, Exception):
                    if isinstance(key, NoSuchUniqueName):
                        # stat parity: the per-point path increments
                        # unknown_metrics once per failing POINT; the
                        # one resolution above already counted 1
                        with self._stats_lock:
                            self.unknown_metrics += len(idx) - 1
                    errors.extend((int(i), key) for i in idx)
                    success -= len(idx)
                    continue
                ts_arr = parsed.ts[idx]
                try:
                    self.store.add_batch(key, ts_arr, parsed.fval[idx],
                                         parsed.isint[idx],
                                         ival=parsed.ival[idx])
                except Exception as e:
                    errors.extend((int(i), e) for i in idx)
                    success -= len(idx)
                    continue
                with self._stats_lock:
                    self.datapoints_added += len(idx)
                self._track_meta(key, int(ts_arr.max()), n=len(idx))
            if journal_record is not None and success > 0:
                # inside the ingest lock: a snapshot cannot slip between
                # the appends above and this journal line
                self.persistence.journal(journal_record)  # order-event: wal-append
        errors.sort(key=lambda t: t[0])
        return success, errors

    def add_telnet_batch_native(self, block: bytes):
        """Native fast path for a block of telnet `put` lines.

        Returns (telnet_batch, point_errors: dict[index, Exception]) or
        None when ineligible (same gates as add_points_bulk_native; the
        caller then walks lines through the per-line handler).  Lines the
        parser refuses (non-ASCII, exotic grammar) are marked FALLBACK in
        the returned batch and cost only themselves.  With persistence
        on, the raw block journals as one "pt" record.
        """
        if not self._native_ingest_eligible():
            return None
        from opentsdb_tpu.storage.native_engine import (parse_telnet_block,
                                                        LINE_FALLBACK)
        tb = parse_telnet_block(block)
        if tb is None:
            return None
        record = None
        if self.persistence is not None and not self._replaying:
            # journal only the natively-handled lines: FALLBACK lines
            # journal their own per-point "p" records when the per-line
            # handler lands them, so including them here would double-
            # ingest on a library-less replay
            data = block
            if (tb.status == LINE_FALLBACK).any():
                data = b"\n".join(
                    bytes(block[int(s):int(e)])
                    for st, (s, e) in zip(tb.status, tb.spans)
                    if st != LINE_FALLBACK)
            try:
                record = {"k": "pt", "b": data.decode("utf-8")}
            except UnicodeDecodeError:
                return None
        _, errors = self._ingest_parsed_columns(tb.points, record)
        return tb, dict(errors)

    def _apply_point(self, metric: str, timestamp: int | float, value,
                     tags: dict[str, str]) -> None:
        is_int, num = parse_value(value)
        self.check_timestamp_and_tags(metric, timestamp, num, tags)
        if self.mode == "ro" and not self._replaying:
            # WAL replay must restore data even when the daemon was
            # restarted read-only; the gate applies to new writes only.
            # Gate AFTER validation: every ingest path (per-point, bulk,
            # native columnar) must classify a malformed point the same
            # way regardless of mode (ADVICE r3).
            raise RuntimeError("TSD is in read-only mode, writes rejected")
        if self.write_filter is not None and not self.write_filter.allow(
                metric, timestamp, num, tags):
            return
        ts_ms = normalize_timestamp_ms(timestamp)
        if self.rollup_store is not None and self.tag_raw_data:
            # tsd.rollups.tag_raw: mark raw series with the agg tag so they
            # coexist with pre-aggregates (TSDB.addPointInternal :1471-1480).
            tags = dict(tags)
            tags[self.agg_tag_key] = self.raw_agg_tag_value
        key = self._series_key(metric, tags, create=True)
        self.store.add_point(key, ts_ms, num, is_int)
        with self._stats_lock:
            self.datapoints_added += 1
        self._track_meta(key, ts_ms)
        if self.rt_publisher is not None:
            self.rt_publisher.publish_data_point(metric, ts_ms, num, tags,
                                                 key.tsuid())

    def _series_key(self, metric: str, tags: dict[str, str],
                    create: bool) -> SeriesKey:
        if create:
            if self.config.auto_metric:
                metric_uid = self.metrics.get_or_create_id(metric)
            else:
                try:
                    metric_uid = self.metrics.get_id(metric)
                except NoSuchUniqueName:
                    with self._stats_lock:
                        self.unknown_metrics += 1
                    raise
            auto_tagk = self.config.get_bool("tsd.core.auto_create_tagks")
            auto_tagv = self.config.get_bool("tsd.core.auto_create_tagvs")
            uid_tags = {}
            for k, v in tags.items():
                ku = (self.tag_names.get_or_create_id(k) if auto_tagk
                      else self.tag_names.get_id(k))
                vu = (self.tag_values.get_or_create_id(v) if auto_tagv
                      else self.tag_values.get_id(v))
                uid_tags[ku] = vu
        else:
            metric_uid = self.metrics.get_id(metric)
            uid_tags = {self.tag_names.get_id(k): self.tag_values.get_id(v)
                        for k, v in tags.items()}
        return SeriesKey.make(metric_uid, uid_tags)

    # ------------------------------------------------------------------ #
    # Histogram write path (TSDB.addHistogramPoint :1171)                #
    # ------------------------------------------------------------------ #

    def add_histogram_point_raw(self, metric: str, timestamp: int | float,
                                codec_id: int, payload: str,
                                tags: dict[str, str]) -> None:
        """Base64 binary histogram ingest (telnet `histogram`,
        HistogramPojo.getBytes)."""
        if self.histogram_manager is None:
            raise ValueError("histograms are not configured "
                             "(tsd.core.histograms.config)")
        import base64
        codec = self.histogram_manager.get_codec(codec_id)
        hist = codec.decode(base64.b64decode(payload), includes_id=False)
        with self._ingest_lock:
            self._store_histogram(metric, timestamp, hist, tags)
            if self.persistence is not None:
                self.persistence.journal({"k": "h", "m": metric,
                                          "t": timestamp,
                                          "d": hist.to_json(),
                                          "g": dict(tags)})

    def add_histogram_point_json(self, metric: str, timestamp: int | float,
                                 dp: dict, tags: dict[str, str]) -> None:
        with self._ingest_lock:
            self._apply_histogram_json(metric, timestamp, dp, tags)
            if self.persistence is not None:
                journal_dp = {k: v for k, v in dp.items()
                              if k in ("id", "value", "buckets",
                                       "underflow", "overflow")}
                self.persistence.journal({"k": "h", "m": metric,
                                          "t": timestamp,
                                          "d": journal_dp,
                                          "g": dict(tags)})

    def _apply_histogram_json(self, metric: str, timestamp: int | float,
                              dp: dict, tags: dict[str, str]) -> None:
        """JSON histogram ingest (POST /api/histogram, HistogramPojo):
        either base64 `value` or explicit `buckets` {"lo,hi": count}."""
        if self.histogram_manager is None:
            raise ValueError("histograms are not configured "
                             "(tsd.core.histograms.config)")
        from opentsdb_tpu.histogram import SimpleHistogram
        codec_id = int(dp.get("id", 0))
        self.histogram_manager.get_codec(codec_id)  # validate the id
        if dp.get("value"):
            hist = SimpleHistogram.from_base64(str(dp["value"]),
                                               include_id=False)
            hist.id = codec_id
        elif "buckets" in dp:
            # Empty bucket maps are valid: the mass may sit entirely in
            # underflow/overflow.
            hist = SimpleHistogram.from_pojo(dp, codec_id)
        else:
            raise ValueError("Missing histogram value or buckets")
        self._store_histogram(metric, timestamp, hist, tags)

    def _store_histogram(self, metric: str, timestamp: int | float, hist,
                         tags: dict[str, str]) -> None:
        self.check_timestamp_and_tags(metric, timestamp, None, tags)
        if self.mode == "ro" and not self._replaying:
            # WAL replay must restore data even when the daemon was
            # restarted read-only; the gate applies to new writes only.
            # Gate after validation, like _apply_point (ADVICE r3).
            raise RuntimeError("TSD is in read-only mode, writes rejected")
        if self.write_filter is not None:
            # WriteableDataPointFilterPlugin gate (TSDB.java:1301-1306,
            # allowHistogramPoint; filters without a histogram hook use the
            # scalar gate).
            allow = getattr(self.write_filter, "allow_histogram",
                            self.write_filter.allow)
            if not allow(metric, timestamp, hist, tags):
                return
        ts_ms = normalize_timestamp_ms(timestamp)
        key = self._series_key(metric, tags, create=True)
        self.histogram_store.add_point(key, ts_ms, hist)
        with self._stats_lock:
            self.datapoints_added += 1
        self._track_meta(key, ts_ms)
        if self.rt_publisher is not None:
            publish = getattr(self.rt_publisher, "publish_histogram_point",
                              None)
            if publish is not None:
                publish(metric, ts_ms, hist, tags, key.tsuid())

    # ------------------------------------------------------------------ #
    # Rollup write path (TSDB.addAggregatePoint :1359-1457)              #
    # ------------------------------------------------------------------ #

    def add_aggregate_point(self, metric: str, timestamp: int | float, value,
                            tags: dict[str, str], is_groupby: bool,
                            interval: str | None, rollup_aggregator: str | None,
                            groupby_aggregator: str | None = None) -> None:
        with self._ingest_lock:
            self._apply_aggregate_point(metric, timestamp, value, tags,
                                        is_groupby, interval,
                                        rollup_aggregator,
                                        groupby_aggregator)
            if self.persistence is not None:
                self.persistence.journal({
                    "k": "r", "m": metric, "t": timestamp, "v": value,
                    "g": dict(tags), "gb": is_groupby, "i": interval,
                    "a": rollup_aggregator, "ga": groupby_aggregator})

    def _apply_aggregate_point(self, metric: str, timestamp: int | float,
                               value, tags: dict[str, str], is_groupby: bool,
                               interval: str | None,
                               rollup_aggregator: str | None,
                               groupby_aggregator: str | None = None) -> None:
        """Store one rolled-up and/or pre-aggregated datapoint.

        Reference behavior (TSDB.addAggregatePointInternal): with `interval`
        the value goes to that interval's rollup lane under
        `rollup_aggregator`; with `is_groupby` it goes to a pre-agg lane and
        the aggregate tag (tsd.rollups.agg_tag_key) is forced to the
        uppercased group-by aggregator.  NaN/Inf floats are rejected.
        """
        if self.rollup_store is None:
            raise RuntimeError("Rollups are not enabled "
                               "(tsd.rollups.enable=false)")
        is_int, num = parse_value(value)
        if interval:
            # Raises NoSuchRollupForInterval for unconfigured intervals.
            self.rollup_config.get_rollup_interval(interval)
            if not rollup_aggregator:
                raise ValueError("Missing rollup aggregator for interval %s"
                                 % interval)
            if (self.rollups_block_derived
                    and rollup_aggregator.upper() in ("AVG", "DEV")):
                # tsd.rollups.block_derived (TSDB.java:1562-1569)
                raise ValueError(
                    "Derived rollup aggregations are not allowed: %s"
                    % rollup_aggregator)
            self.rollup_config.get_id_for_aggregator(rollup_aggregator)
        elif not is_groupby:
            raise ValueError(
                "Either an interval or the groupby flag is required")
        tags = dict(tags)
        if is_groupby:
            if not groupby_aggregator:
                raise ValueError("Missing group-by aggregator")
            from opentsdb_tpu.ops.aggregators import AGGREGATORS
            if groupby_aggregator.lower() not in AGGREGATORS:
                raise ValueError("Invalid group by aggregator: %s"
                                 % groupby_aggregator)
            if (self.rollups_block_derived
                    and groupby_aggregator.upper() in ("AVG", "DEV")):
                # TSDB.java:1543-1550
                raise ValueError(
                    "Derived group by aggregations are not allowed: %s"
                    % groupby_aggregator)
            tags[self.agg_tag_key] = groupby_aggregator.upper()
        self.check_timestamp_and_tags(metric, timestamp, num, tags)
        if self.mode == "ro" and not self._replaying:
            # WAL replay must restore data even when the daemon was
            # restarted read-only; the gate applies to new writes only.
            # Gate after validation, like _apply_point (ADVICE r3).
            raise RuntimeError("TSD is in read-only mode, writes rejected")
        ts_ms = normalize_timestamp_ms(timestamp)
        key = self._series_key(metric, tags, create=True)
        lane_agg = (rollup_aggregator if interval else groupby_aggregator)
        self.rollup_store.add_point(
            key, interval or "", lane_agg.lower(), ts_ms, num, is_int,
            pre_agg=is_groupby)
        with self._stats_lock:
            self.datapoints_added += 1

    # ------------------------------------------------------------------ #
    # Read helpers                                                       #
    # ------------------------------------------------------------------ #

    def resolve_key_tags(self, key: SeriesKey) -> dict[str, str]:
        """UID tag pairs -> {tagk_name: tagv_name}."""
        return {self.tag_names.get_name(k): self.tag_values.get_name(v)
                for k, v in key.tags}

    def tsuid(self, key: SeriesKey) -> str:
        """Hex TSUID honoring the configured UID byte widths."""
        return key.tsuid(self.metrics.width, self.tag_names.width,
                         self.tag_values.width)

    def new_query_runner(self):
        from opentsdb_tpu.query.planner import QueryRunner
        return QueryRunner(self)

    @property
    def query_limits(self):
        """Scan-budget registry (QueryLimitOverride.java), built lazily."""
        if self._query_limits is None:
            from opentsdb_tpu.query.limits import QueryLimitOverride
            self._query_limits = QueryLimitOverride(self.config)
        return self._query_limits

    def query_mesh(self):
        """The device mesh serving /api/query, or None when single-device.

        Built lazily from every visible device — the TPU-native counterpart
        of the salt-bucket scanner fan-out (SaltScanner.java:269): instead of
        one concurrent HBase scanner per salt bucket, each chip owns a shard
        of the query batch's rows.  Disable with tsd.query.mesh.enable.
        """
        if not self.config.get_bool("tsd.query.mesh.enable"):
            return None
        if self._query_mesh is _UNSET:
            # built once: two first queries arriving together must not
            # both build (and publish) a mesh
            with self._mesh_lock:
                if self._query_mesh is _UNSET:
                    from opentsdb_tpu.parallel import make_mesh
                    from opentsdb_tpu.parallel.distributed import (
                        maybe_init_distributed, host_major_devices)
                    maybe_init_distributed(self.config)
                    devices = host_major_devices()
                    self._query_mesh = (
                        make_mesh(len(devices), devices=devices)
                        if len(devices) > 1 else None)
        return self._query_mesh

    # ------------------------------------------------------------------ #
    # UID admin (TSDB.assignUid :1901, renameUid :1968, suggest :1825)   #
    # ------------------------------------------------------------------ #

    def uid_table(self, kind: str) -> UniqueId:
        t = UniqueIdType.from_string(kind)
        return {UniqueIdType.METRIC: self.metrics,
                UniqueIdType.TAGK: self.tag_names,
                UniqueIdType.TAGV: self.tag_values}[t]

    def assign_uid(self, kind: str, name: str) -> int:
        table = self.uid_table(kind)
        if table.has_name(name):
            raise ValueError("Name already exists with UID: %s"
                             % table.uid_to_hex(table.get_id(name)))
        return table.get_or_create_id(name)

    def rename_uid(self, kind: str, old_name: str, new_name: str) -> None:
        self.uid_table(kind).rename(old_name, new_name)

    def delete_uid(self, kind: str, name: str) -> int:
        return self.uid_table(kind).delete(name)

    def suggest_metrics(self, prefix: str = "", max_results: int = 25):
        return self.metrics.suggest(prefix, max_results)

    def suggest_tagk(self, prefix: str = "", max_results: int = 25):
        return self.tag_names.suggest(prefix, max_results)

    def suggest_tagv(self, prefix: str = "", max_results: int = 25):
        return self.tag_values.suggest(prefix, max_results)

    # ------------------------------------------------------------------ #
    # Annotations                                                        #
    # ------------------------------------------------------------------ #

    def _track_meta(self, key, ts_ms: int, n: int = 1) -> None:
        """TSMeta maintenance on the write path (TSDB.java:1259-1285):
        counters only under enable_tsuid_tracking; realtime_ts creates and
        indexes the TSMeta once per new series (TSMeta.storeIfNecessary).
        `n` > 1 counts a whole bulk batch (ts_ms = the batch max)."""
        if not (self.enable_tsuid_tracking or self.enable_realtime_ts
                or self.tree_processing):
            return
        tsuid = self.tsuid(key)
        created = self.meta_store.record_datapoint(
            tsuid, ts_ms, count=self.enable_tsuid_tracking, n=n)
        if created and (self.tree_processing or (
                self.enable_realtime_ts
                and self.search_plugin is not None)):
            from opentsdb_tpu.meta.rpc import resolve_tsmeta
            meta = resolve_tsmeta(self, tsuid)
            if self.enable_realtime_ts and self.search_plugin is not None:
                self.search_plugin.index_tsmeta(meta)
            if self.tree_processing:
                # Realtime tree materialization (TSMeta.storeIfNecessary ->
                # TreeBuilder.processAllTrees when
                # tsd.core.tree.enable_processing).
                for tree in self.tree_store.all_trees():
                    if tree.enabled:
                        self.tree_store.process_tsmeta(
                            tree, meta,
                            metric=self.metrics.get_name(key.metric),
                            tags=self.resolve_key_tags(key))

    def _make_uid_meta_hook(self, kind: str, table):
        def hook(name: str, uid: int) -> None:
            meta = self.meta_store.ensure_uidmeta(
                kind, table.uid_to_hex(uid), name)
            if self.search_plugin is not None:
                self.search_plugin.index_uidmeta(meta)
        return hook

    def add_annotation(self, note: Annotation) -> None:
        with self._ingest_lock:
            self.store.add_annotation(note)
            if self.search_plugin is not None:
                self.search_plugin.index_annotation(note)
            if self.persistence is not None:
                self.persistence.journal({"k": "a", "n": {
                    "start_time": note.start_time,
                    "end_time": note.end_time,
                    "tsuid": note.tsuid, "description": note.description,
                    "notes": note.notes, "custom": note.custom}})

    # ------------------------------------------------------------------ #
    # Stats (TSDB.collectStats :785)                                     #
    # ------------------------------------------------------------------ #

    def collect_stats(self) -> dict[str, float]:
        now = time.time()
        out = {
            "tsd.uid.cache-hit metrics": self.metrics.cache_hits,
            "tsd.uid.cache-miss metrics": self.metrics.cache_misses,
            "tsd.uid.ids-used metrics": len(self.metrics),
            "tsd.uid.cache-hit tagk": self.tag_names.cache_hits,
            "tsd.uid.cache-miss tagk": self.tag_names.cache_misses,
            "tsd.uid.ids-used tagk": len(self.tag_names),
            "tsd.uid.cache-hit tagv": self.tag_values.cache_hits,
            "tsd.uid.cache-miss tagv": self.tag_values.cache_misses,
            "tsd.uid.ids-used tagv": len(self.tag_values),
            "tsd.datapoints.added": self.datapoints_added,
            "tsd.storage.series": self.store.num_series,
            "tsd.storage.datapoints": self.store.total_datapoints,
            "tsd.storage.bytes": self.store.total_bytes,
            "tsd.compaction.count": self.store.compaction_queue.compactions,
            # Operator-visible duplicate-data failures (fix_duplicates off):
            # surfaced here instead of only as the first reader's 400.
            "tsd.compaction.errors": self.store.compaction_queue.errors,
            "tsd.compaction.queue": len(self.store.compaction_queue),
            "tsd.uptime": now - self.start_time,
        }
        if self.maintenance is not None:
            out.update(self.maintenance.collect_stats())
        if self.device_cache is not None:
            out.update(self.device_cache.collect_stats())
        if self.agg_cache is not None:
            out.update(self.agg_cache.collect_stats())
        if self.rollup_lanes is not None:
            out.update(self.rollup_lanes.collect_stats())
        if self.dispatch_batcher is not None:
            out.update(self.dispatch_batcher.collect_stats())
        return out

    @staticmethod
    def version() -> str:
        return __version__

    @staticmethod
    def short_version() -> str:
        return SHORT_VERSION

    def flush(self) -> None:
        self.store.compaction_queue.flush()

    def snapshot(self) -> None:
        """Persist full state to tsd.storage.directory.

        Holds the ingest lock for the walk (stop-the-world checkpoint) so a
        concurrent write can never land after the state capture but before
        the WAL truncation."""
        if self.persistence is None:
            raise RuntimeError("tsd.storage.directory is not configured")
        with self._ingest_lock:
            self.persistence.snapshot()

    def start_maintenance(self):
        """Start the background maintenance thread (compaction flush + WAL
        fsync + snapshot cadence; CompactionQueue.java:95-107).

        Called by the daemon main; library embedders opt in explicitly so a
        bare TSDB() stays thread-free (the reference's tests mock the
        compaction thread out for the same reason).
        """
        if self.maintenance is None:
            from opentsdb_tpu.core.maintenance import MaintenanceThread
            self.maintenance = MaintenanceThread(self)
            self.maintenance.start()
        return self.maintenance

    def shutdown(self) -> None:
        if self.maintenance is not None:
            self.maintenance.stop(final_flush=False)
            self.maintenance = None
        if self.replication is not None:
            # before the snapshot: no pull may apply (and journal) a
            # peer record while the WAL is being reset
            self.replication.stop_puller()
        self.flush()
        if self.persistence is not None:
            with self._ingest_lock:
                self.persistence.snapshot()
            self.persistence.close()                 # order-event: wal-close
        if self.spill_pool is not None:
            # after the query path is quiesced: drops every entry and
            # the private tempdir (in-flight tiled queries have their
            # own per-query release in ops/tiling.py)
            self.spill_pool.close()                  # order-event: spill-close
        if self.flightrec is not None:
            # LAST, so teardown events above still land in the ring
            # before the shutdown dump writes the black box; idempotent
            # (a server stop + an explicit shutdown both reach here)
            # order: wal-close before flightrec-shutdown
            # order: spill-close before flightrec-shutdown
            self.flightrec.shutdown()                # order-event: flightrec-shutdown


def parse_value(value) -> tuple[bool, int | float]:
    """Classify a put value as integer or float (Tags.parseLong / fixFloat).

    Strings follow the telnet `put` rules: "42" is an integer, "42.0" and
    "4e2" are floats.  Integers stay exact Python ints (Java-long parity up
    to 2^63); NaN/Infinity are rejected like the reference
    (TSDB.addPointInternal IllegalArgumentException).
    """
    import math
    if isinstance(value, bool):
        raise ValueError("Invalid value: %r" % value)
    if isinstance(value, int):
        return True, value
    if isinstance(value, float):
        if math.isnan(value) or math.isinf(value):
            raise ValueError("Invalid value: %r" % value)
        return False, value
    text = str(value).strip()
    if not text:
        raise ValueError("Empty value")
    try:
        return True, int(text)
    except ValueError:
        pass
    try:
        out = float(text)
    except ValueError:
        raise ValueError("Invalid value: %r" % value)
    if math.isnan(out) or math.isinf(out):
        raise ValueError("Invalid value: %r" % value)
    return False, out

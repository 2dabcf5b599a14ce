"""Background maintenance: compaction flush, WAL fsync, snapshot cadence.

Reference behavior: CompactionQueue.java:95-165 — a daemon thread started
with the queue ("Start its own thread" :95) flushes dirty rows every
``tsd.storage.compaction.flush_interval`` seconds, at most
``max_concurrent_flushes`` per pass, speeding up by ``flush_speed``× when
the backlog exceeds ``min_flush_threshold`` (the throttle-on-backlog rule).
Errors land in an operator-visible counter, not on the next reader.

TPU-native extensions (ADVICE round-1 lows): the JSONL WAL gets a real
fsync cadence (``tsd.storage.wal_sync_interval``; line buffering alone
survives process crashes but not OS crashes), and full snapshots run off
the request path on ``tsd.storage.snapshot_interval``.
"""

from __future__ import annotations

import logging
import threading
import time

LOG = logging.getLogger(__name__)


class MaintenanceThread(threading.Thread):
    """One daemon thread driving all periodic storage upkeep."""

    TICK_SECONDS = 0.5

    def __init__(self, tsdb):
        super().__init__(name="TSDB-maintenance", daemon=True)
        self.tsdb = tsdb
        cfg = tsdb.config
        self.flush_interval = cfg.get_int(
            "tsd.storage.compaction.flush_interval")
        self.min_flush_threshold = cfg.get_int(
            "tsd.storage.compaction.min_flush_threshold")
        self.max_concurrent_flushes = cfg.get_int(
            "tsd.storage.compaction.max_concurrent_flushes")
        self.flush_speed = max(cfg.get_int(
            "tsd.storage.compaction.flush_speed"), 1)
        self.wal_sync_interval = cfg.get_int(
            "tsd.storage.wal_sync_interval")
        self.snapshot_interval = cfg.get_int(
            "tsd.storage.snapshot_interval")
        self.stats_interval = cfg.get_int("tsd.stats.interval")
        self.rollup_interval = cfg.get_int("tsd.rollup.interval")
        self._stop_event = threading.Event()
        self._next_flush = time.monotonic() + self.flush_interval
        self._next_sync = time.monotonic() + max(self.wal_sync_interval, 1)
        self._next_snapshot = time.monotonic() + max(
            self.snapshot_interval, 1)
        self._next_self_report = time.monotonic() + max(
            self.stats_interval, 1)
        self._next_rollup = time.monotonic() + max(
            self.rollup_interval, 1)
        self.flush_passes = 0
        self.rollup_passes = 0
        self.rollup_blocks_built = 0
        self.wal_syncs = 0
        self.snapshots = 0
        self.snapshot_errors = 0
        self.device_cache_refreshes = 0
        self.self_reports = 0
        self.self_report_errors = 0
        self.self_report_points = 0
        self.health_passes = 0

    # ------------------------------------------------------------------ #

    def run(self) -> None:
        while not self._stop_event.wait(self.TICK_SECONDS):
            now = time.monotonic()
            try:
                self._maybe_flush(now)
                self._maybe_sync_wal(now)
                self._maybe_snapshot(now)
                self._maybe_refresh_device_cache()
                self._maybe_self_report(now)
                self._maybe_rollup(now)
                self._maybe_health(now)
            except Exception:
                LOG.exception("maintenance pass failed")

    def stop(self, final_flush: bool = True) -> None:
        self._stop_event.set()
        if self.is_alive():
            self.join(timeout=5.0)
        if final_flush:
            self.tsdb.store.compaction_queue.flush()

    # ------------------------------------------------------------------ #

    def _maybe_flush(self, now: float) -> None:
        queue = self.tsdb.store.compaction_queue
        backlog = len(queue)
        if now >= self._next_flush:
            self._next_flush = now + self.flush_interval
        elif backlog < self.min_flush_threshold:
            return
        if backlog == 0:
            return
        # Throttle-on-backlog (CompactionQueue.java:133-141): a backlog past
        # the threshold flushes a flush_speed-times bigger slice per pass.
        max_flushes = self.max_concurrent_flushes
        if backlog > self.min_flush_threshold:
            max_flushes *= self.flush_speed
        queue.flush(max_flushes)
        self.flush_passes += 1

    def _maybe_sync_wal(self, now: float) -> None:
        if self.wal_sync_interval <= 0 or now < self._next_sync:
            return
        self._next_sync = now + self.wal_sync_interval
        persistence = self.tsdb.persistence
        if persistence is not None:
            persistence.sync_wal()
            self.wal_syncs += 1

    def _maybe_refresh_device_cache(self) -> None:
        """Rebuild device-cache entries invalidated by ingest.

        Off the query path by design: queries on a stale metric fall back
        to the host build (fast miss) and queue it here; this thread pays
        the re-upload so ingest-heavy metrics regain device-cache hits
        without ever blocking a request."""
        cache = self.tsdb.device_cache
        if cache is not None:
            self.device_cache_refreshes += cache.refresh(self.tsdb.store)
        agg = self.tsdb.agg_cache
        if agg is not None:
            # hot aggregate blocks earn their device/HBM mirrors here,
            # off the query path (storage/agg_cache.py promote_pending)
            agg.promote_pending()

    def _maybe_self_report(self, now: float) -> None:
        """tsd.stats.interval cadence of the self-report loop
        (obs/selfreport.py): the daemon ingests its own tsd.* metrics
        so it is queryable about itself through its own pipeline."""
        if self.stats_interval <= 0 or now < self._next_self_report:
            return
        self._next_self_report = now + self.stats_interval
        from opentsdb_tpu.obs.selfreport import self_report
        try:
            self.self_report_points += self_report(self.tsdb)
            self.self_reports += 1
        except Exception:
            self.self_report_errors += 1
            LOG.exception("self-report pass failed")

    def _maybe_rollup(self, now: float) -> None:
        """tsd.rollup.interval cadence: one rollup-lane maintenance
        pass (storage/rollup.py refresh — Storyboard selection under
        the byte budget, then block builds over the demanded ranges,
        with the spill pool bounding over-wall builds)."""
        lanes = getattr(self.tsdb, "rollup_lanes", None)
        if lanes is None or self.rollup_interval <= 0 \
                or now < self._next_rollup:
            return
        self._next_rollup = now + self.rollup_interval
        built = lanes.refresh(self.tsdb.store)
        self.rollup_passes += 1
        self.rollup_blocks_built += built

    def _maybe_health(self, now: float) -> None:
        """tsd.health.interval cadence: one health-engine pass
        (obs/health.py) judging the window since the previous pass.
        The engine rate-limits itself; this forwards the heartbeat."""
        engine = getattr(self.tsdb, "health", None)
        if engine is not None and engine.tick(now):
            self.health_passes += 1

    def _maybe_snapshot(self, now: float) -> None:
        if self.snapshot_interval <= 0 or now < self._next_snapshot:
            return
        self._next_snapshot = now + self.snapshot_interval
        if self.tsdb.persistence is None:
            return
        try:
            self.tsdb.snapshot()
            self.snapshots += 1
        except Exception:
            self.snapshot_errors += 1
            LOG.exception("periodic snapshot failed")

    # ------------------------------------------------------------------ #

    def collect_stats(self) -> dict[str, float]:
        return {
            "tsd.maintenance.flush_passes": self.flush_passes,
            "tsd.maintenance.wal_syncs": self.wal_syncs,
            "tsd.maintenance.snapshots": self.snapshots,
            "tsd.maintenance.snapshot_errors": self.snapshot_errors,
            "tsd.maintenance.device_cache_refreshes":
                self.device_cache_refreshes,
            "tsd.maintenance.self_reports": self.self_reports,
            "tsd.maintenance.self_report_errors": self.self_report_errors,
            "tsd.maintenance.self_report_points": self.self_report_points,
            "tsd.maintenance.health_passes": self.health_passes,
            "tsd.maintenance.rollup_passes": self.rollup_passes,
            "tsd.maintenance.rollup_blocks_built":
                self.rollup_blocks_built,
        }

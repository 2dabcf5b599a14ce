"""Health engine: declared invariants -> per-subsystem verdicts.

The flight recorder (obs/flightrec.py) retains WHAT happened; this
module judges whether it is FINE.  On the maintenance cadence
(``tsd.health.interval``) the engine evaluates a fixed set of declared
invariants — each a burn-rate/ratio check over the window since the
last pass, never a point-in-time glance — and folds each into an
``ok | degraded | failing`` verdict per subsystem:

  * **admission** — shed burn: queries refused per second over the
    window vs ``tsd.health.shed_rate``.  A daemon shedding steadily
    after a burst lifted has NOT healed.
  * **compile** — steady-state recompiles: XLA compilations per window
    (via the shared compile counters) past ``tsd.health.recompile_limit``
    once the daemon is older than ``tsd.health.recompile_warmup``
    seconds.  Steady-state serving must be compile-clean (the tsdbsan
    contract, now judged continuously).
  * **agg_cache** — hit-rate collapse: consults in the window with a
    hit fraction under ``tsd.health.cache_hit_floor`` (volume-gated:
    a handful of cold misses is not a collapse).
  * **spill** — pool saturation: resident bytes vs the combined
    host+disk budget past ``tsd.health.spill_saturation``.
  * **cluster** — breaker flap: open transitions in the window past
    ``tsd.health.breaker_flap``, and any breaker currently open is at
    least degraded.
  * **tenant** — cross-tenant starvation: among tenants with
    meaningful window demand, the max/min admitted-share ratio past
    ``tsd.health.tenant_share_ratio`` (failing when a demanding
    tenant was admitted NOTHING while others were served).  Judges
    the fair-share drain (tsd/admission.py weighted DRR) — a healthy
    storm sheds the storming tenant's excess, it never zeroes anyone
    out.
  * **replication** — under-replicated shards / lag burn: any shard
    with fewer healthy members than the replication factor is at
    least degraded (one more failure loses data), and growth of the
    worst replica's unacknowledged WAL backlog past
    ``tsd.health.replication_lag`` records per window is degraded
    (failing at 4x) — a replica that stops draining has NOT healed
    just because ships stop erroring.
  * **latency** — phase-share burn: the serialize phase's share of
    the window's total attributed request time (obs/latattr.py
    always-on phase stamps) past ``tsd.health.phase_share``
    (volume-gated).  Serialize time is pure host-side overhead — a
    daemon spending a growing fraction of every request JSON-encoding
    replies is burning its latency budget outside the device, the
    precise regression tsdbsan's serialize pin guards at test time,
    now judged continuously in production.
  * **diag** — evidence loss: flight-recorder ring overflow (events
    evicted before any reader saw them) past
    ``tsd.health.diag_drop_rate`` drops/second over the window.  A
    steadily-overflowing ring means the next incident's history is
    already gone.

Verdicts are exported as ``tsd.health.status`` gauges (0 ok /
1 degraded / 2 failing), served at ``/api/diag/health``, recorded into
the flight recorder on every level CHANGE, walked into /api/stats and
the self-report loop via the stats-hook registry, and consumed by
``tools/chaos_soak.py`` as the post-heal gate: after a fault window
clears, every subsystem must read ``ok``.

A subsystem that is disabled, cold, or below the volume gate reports
``ok`` — the engine judges violated invariants, it does not punish
idleness.
"""

from __future__ import annotations

import threading
import time

from opentsdb_tpu.obs.registry import REGISTRY

LEVELS = ("ok", "degraded", "failing")
_LEVEL_NUM = {lvl: i for i, lvl in enumerate(LEVELS)}

# Volume gates: below these per-window totals a ratio check abstains.
_CACHE_MIN_CONSULTS = 16
_CACHE_FAIL_CONSULTS = 64
_TENANT_MIN_DEMAND = 16.0
_LATENCY_MIN_REQUESTS = 32.0
_LATENCY_MIN_TOTAL_MS = 50.0


def _worst(a: str, b: str) -> str:
    return a if _LEVEL_NUM[a] >= _LEVEL_NUM[b] else b


class HealthEngine:
    """Evaluates the declared invariants against one TSDB instance."""

    SUBSYSTEMS = ("admission", "compile", "agg_cache", "spill",
                  "cluster", "tenant", "replication", "latency",
                  "diag")

    def __init__(self, tsdb):
        cfg = tsdb.config
        self.tsdb = tsdb
        self.interval = cfg.get_int("tsd.health.interval")
        self.shed_rate = cfg.get_float("tsd.health.shed_rate")
        self.recompile_warmup = cfg.get_int("tsd.health.recompile_warmup")
        self.recompile_limit = cfg.get_int("tsd.health.recompile_limit")
        self.cache_hit_floor = cfg.get_float("tsd.health.cache_hit_floor")
        self.spill_saturation = cfg.get_float(
            "tsd.health.spill_saturation")
        self.breaker_flap = cfg.get_int("tsd.health.breaker_flap")
        self.tenant_share_ratio = cfg.get_float(
            "tsd.health.tenant_share_ratio")
        self.replication_lag = cfg.get_int("tsd.health.replication_lag")
        self.phase_share = cfg.get_float("tsd.health.phase_share")
        self.diag_drop_rate = cfg.get_float("tsd.health.diag_drop_rate")
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._verdicts: dict[str, dict] = {}
        self.passes = 0  # guarded-by: _lock
        self._evaluated_ms = 0  # guarded-by: _lock
        # previous pass's cumulative counters (deltas = the window)
        # guarded-by: _lock
        self._last: dict[str, float] = {}
        self._last_eval_t: float | None = None  # guarded-by: _lock
        # maintenance-thread cadence state: only that thread's tick
        # touches it (same discipline as OnlineCalibrator._next_fit)
        self._next_eval: float | None = None

    # -- cadence --------------------------------------------------------- #

    def tick(self, now: float | None = None) -> bool:
        """One maintenance heartbeat; evaluates when the interval
        elapsed.  Returns True when a pass ran."""
        if now is None:
            now = time.monotonic()
        if self.interval <= 0:
            return False
        if self._next_eval is None:
            self._next_eval = now + max(self.interval, 1)
            return False
        if now < self._next_eval:
            return False
        self._next_eval = now + max(self.interval, 1)
        self.evaluate()
        return True

    # -- evaluation ------------------------------------------------------ #

    def evaluate(self) -> dict[str, dict]:
        """One pass over every invariant.  Window = time since the
        previous pass (since construction on the first)."""
        tsdb = self.tsdb
        now = time.monotonic()
        with self._lock:
            last = dict(self._last)
            last_t = self._last_eval_t
        window_s = max(now - last_t, 1e-3) if last_t is not None \
            else max(time.time() - tsdb.start_time, 1e-3)
        window_s = min(window_s, 3600.0)
        cur: dict[str, float] = {}
        verdicts: dict[str, dict] = {}

        def delta(key: str, value: float) -> float:
            cur[key] = float(value)
            return max(float(value) - last.get(key, 0.0), 0.0)

        # admission: shed burn rate over the window
        gate = getattr(tsdb, "_admission_gate", None)
        shed = delta("shed", gate.shed if gate is not None else 0.0)
        rate = shed / window_s
        level = "ok"
        if rate > self.shed_rate > 0:
            level = "failing" if rate > 4 * self.shed_rate else "degraded"
        verdicts["admission"] = {
            "level": level,
            "detail": "%.2f sheds/s over %.0fs window (limit %.2f/s)"
                      % (rate, window_s, self.shed_rate)}

        # compile: steady-state recompiles per window after warmup.
        # Source is whichever shared-capture subscriber is armed: the
        # flight recorder's compile events (server-armed regardless of
        # tracing) or jaxprof's per-kernel counters (tracing on) — max
        # of two cumulative counts of the same event stream stays
        # monotone when either is dark.
        from opentsdb_tpu.obs import jaxprof
        diag_compiles = REGISTRY.counter(
            "tsd.diag.events", "Flight-recorder events recorded, "
            "by event kind").labels(kind="compile").get()
        compiles = delta("compiles",
                         max(sum(jaxprof.compile_counts().values()),
                             diag_compiles))
        uptime = time.time() - tsdb.start_time
        level = "ok"
        if uptime >= self.recompile_warmup > 0:
            excess = compiles - self.recompile_limit
            if excess > 0:
                level = "failing" if excess > 4 else "degraded"
        verdicts["compile"] = {
            "level": level,
            "detail": "%d compiles in window (limit %d; warmup %s)"
                      % (compiles, self.recompile_limit,
                         "done" if uptime >= self.recompile_warmup
                         else "%.0fs left"
                         % (self.recompile_warmup - uptime))}

        # agg_cache: hit-rate collapse (volume-gated)
        cache = getattr(tsdb, "agg_cache", None)
        level, detail = "ok", "cache disabled"
        if cache is not None:
            hits = delta("cache_hits", cache.hits)
            misses = delta("cache_misses", cache.misses)
            consults = hits + misses
            detail = "%.0f/%.0f hits/consults in window" \
                % (hits, consults)
            if consults >= _CACHE_MIN_CONSULTS \
                    and hits / consults < self.cache_hit_floor:
                level = ("failing" if hits == 0
                         and consults >= _CACHE_FAIL_CONSULTS
                         else "degraded")
        verdicts["agg_cache"] = {"level": level, "detail": detail}

        # spill: pool saturation
        pool = getattr(tsdb, "spill_pool", None)
        level, detail = "ok", "spill pool disabled"
        if pool is not None:
            budget = pool.host_budget + pool.disk_budget
            resident = pool.host_bytes + pool.disk_bytes
            util = resident / budget if budget > 0 else 0.0
            detail = "%.0f%% of %.0fMB pool resident" \
                % (util * 100, budget / 2**20)
            if util >= 1.0:
                level = "failing"
            elif util > self.spill_saturation > 0:
                level = "degraded"
        verdicts["spill"] = {"level": level, "detail": detail}

        # cluster: breaker flap + currently-open breakers
        state = getattr(tsdb, "_cluster_state", None)
        level, detail = "ok", "no clustered serving yet"
        if state is not None:
            breakers = state.breakers()
            opens = delta("breaker_opens",
                          sum(b.opens for b in breakers.values()))
            open_now = [p for p, b in breakers.items()
                        if b.state != b.CLOSED]
            detail = "%d open transitions in window; open now: %s" \
                % (opens, ",".join(sorted(open_now)) or "none")
            if opens > self.breaker_flap > 0:
                level = "failing" if opens > 2 * self.breaker_flap \
                    else "degraded"
            if open_now:
                level = _worst(level, "degraded")
        verdicts["cluster"] = {"level": level, "detail": detail}

        # tenant: cross-tenant starvation — among tenants with
        # meaningful window demand, admitted-share (admitted/demand
        # deltas) must stay within tsd.health.tenant_share_ratio of
        # each other; a demanding tenant admitted NOTHING while
        # another was served is failing.  Every cell's delta is taken
        # every pass (even below the volume gate) so the window
        # baselines stay aligned.
        def _tenant_cells(name: str, doc: str) -> dict[str, float]:
            fam = REGISTRY.counter(name, doc)  # tsdblint: disable=metrics-dynamic-name
            return {dict(labels).get("tenant", "default"): cell.get()
                    for labels, cell in fam.children()}

        demand_cells = _tenant_cells(
            "tsd.query.tenant.demand",
            "Queries arriving at admission, by clamped tenant")
        admit_cells = _tenant_cells(
            "tsd.query.tenant.admitted",
            "Queries admitted through the gate, by clamped tenant")
        d_deltas: dict[str, float] = {}
        a_deltas: dict[str, float] = {}
        for t in set(demand_cells) | set(admit_cells):
            d_deltas[t] = delta("tenant_demand:%s" % t,
                                demand_cells.get(t, 0.0))
            a_deltas[t] = delta("tenant_admitted:%s" % t,
                                admit_cells.get(t, 0.0))
        shares = {t: a_deltas.get(t, 0.0) / d
                  for t, d in d_deltas.items()
                  if d >= _TENANT_MIN_DEMAND}
        level, detail = "ok", (
            "%d tenant(s) above the demand gate in window"
            % len(shares))
        if len(shares) >= 2:
            hi_t = max(shares, key=shares.get)
            lo_t = min(shares, key=shares.get)
            hi, lo = shares[hi_t], shares[lo_t]
            detail = ("admitted-share %s=%.2f vs %s=%.2f in window "
                      "(ratio limit x%.1f)"
                      % (hi_t, hi, lo_t, lo, self.tenant_share_ratio))
            if lo <= 0.0 and hi > 0.0:
                level = "failing"
            elif self.tenant_share_ratio > 0 \
                    and hi / max(lo, 1e-9) > self.tenant_share_ratio:
                level = "degraded"
        verdicts["tenant"] = {"level": level, "detail": detail}

        # replication: under-replicated shards + lag burn.  The lag
        # judged is the GROWTH of the worst replica's backlog over the
        # window — a standing-but-draining backlog after a burst is
        # healing, a growing one is not.
        repl = getattr(tsdb, "replication", None)
        level, detail = "ok", "replication disabled"
        if repl is not None:
            snap = repl.health_snapshot()
            lag_growth = delta("repl_lag_hwm", snap["lag"])
            detail = ("%d under-replicated shard(s); backlog %d "
                      "records (+%d in window, limit +%d)"
                      % (snap["under_replicated"], snap["lag"],
                         lag_growth, self.replication_lag))
            if snap["under_replicated"] > 0:
                level = "degraded"
            if self.replication_lag > 0 \
                    and lag_growth > self.replication_lag:
                level = _worst(
                    level,
                    "failing" if lag_growth > 4 * self.replication_lag
                    else "degraded")
        verdicts["replication"] = {"level": level, "detail": detail}

        # latency: phase-share burn — serialize's share of the
        # window's total attributed ms (obs/latattr.py).  Every phase
        # counter's delta is taken every pass so window baselines stay
        # aligned even while the volume gate abstains.
        latattr_engine = getattr(tsdb, "latattr", None)
        level, detail = "ok", "latency attribution disabled"
        if latattr_engine is not None:
            totals = latattr_engine.phase_totals()
            requests = delta("latattr_requests", totals["requests"])
            phase_win = {p: delta("latattr_ms:%s" % p, ms)
                         for p, ms in totals.items() if p != "requests"}
            total_ms = sum(phase_win.values())
            serialize_ms = phase_win.get("serialize", 0.0)
            detail = "%.0f request(s), %.0fms attributed in window" \
                % (requests, total_ms)
            if requests >= _LATENCY_MIN_REQUESTS \
                    and total_ms >= _LATENCY_MIN_TOTAL_MS:
                share = serialize_ms / total_ms
                detail = ("serialize %.0f%% of %.0fms attributed over "
                          "%.0f requests (budget %.0f%%)"
                          % (share * 100, total_ms, requests,
                             self.phase_share * 100))
                if share > self.phase_share > 0:
                    level = "failing" if share > 2 * self.phase_share \
                        else "degraded"
        verdicts["latency"] = {"level": level, "detail": detail}

        # diag: evidence loss — ring-overflow drop rate over the window
        recorder = getattr(tsdb, "flightrec", None)
        level, detail = "ok", "flight recorder disabled"
        if recorder is not None:
            _by_kind, dropped_total = recorder.dropped()
            drops = delta("diag_dropped", dropped_total)
            drop_rate = drops / window_s
            detail = "%.2f ring drops/s over %.0fs window (limit %.2f/s)" \
                % (drop_rate, window_s, self.diag_drop_rate)
            if drop_rate > self.diag_drop_rate > 0:
                level = "failing" if drop_rate > 4 * self.diag_drop_rate \
                    else "degraded"
        verdicts["diag"] = {"level": level, "detail": detail}

        self._publish(verdicts, cur, now)
        return verdicts

    def _publish(self, verdicts: dict[str, dict], cur: dict[str, float],
                 now: float) -> None:
        gauge = REGISTRY.gauge(
            "tsd.health.status",
            "Health-engine verdict per subsystem (0 ok, 1 degraded, "
            "2 failing)")
        with self._lock:
            previous = {k: v["level"] for k, v in self._verdicts.items()}
            self._verdicts = verdicts
            self._last = cur
            self._last_eval_t = now
            self.passes += 1
            self._evaluated_ms = int(time.time() * 1e3)
        changed = []
        for name, verdict in verdicts.items():
            gauge.labels(subsystem=name).set(
                _LEVEL_NUM[verdict["level"]])
            before = previous.get(name, "ok")
            if verdict["level"] != before:
                changed.append((name, before, verdict))
        recorder = getattr(self.tsdb, "flightrec", None)
        if recorder is not None:
            for name, before, verdict in changed:
                recorder.record("health", subsystem=name,
                                before=before, level=verdict["level"],
                                detail=verdict["detail"])

    # -- reporting ------------------------------------------------------- #

    def report(self) -> dict:
        """The /api/diag/health payload.  Evaluates inline when no
        maintenance pass has run yet, so a freshly-started (or
        maintenance-less library) daemon still answers with real
        verdicts instead of an empty shell."""
        with self._lock:
            passes = self.passes
        if passes == 0:
            self.evaluate()
        with self._lock:
            verdicts = {k: dict(v) for k, v in self._verdicts.items()}
            passes = self.passes
            evaluated = self._evaluated_ms
        overall = "ok"
        for v in verdicts.values():
            overall = _worst(overall, v["level"])
        return {"overall": overall, "subsystems": verdicts,
                "passes": passes, "evaluatedMs": evaluated}

    # -- stats ----------------------------------------------------------- #

    def stats_hook(self, collector) -> None:
        """The /api/stats + self-report view of the verdicts — the TSD
        can query its own health history (obs/selfreport.py ingests
        these through the same walk, ro-skip preserved)."""
        with self._lock:
            verdicts = {k: v["level"] for k, v in self._verdicts.items()}
            passes = self.passes
        collector.record("health.passes", passes)
        for name, level in verdicts.items():
            collector.record("health.status", _LEVEL_NUM[level],
                             "subsystem=%s" % name)

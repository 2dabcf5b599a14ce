"""Online costmodel calibration: close the predicted-vs-actual loop.

ops/costmodel.py ranks kernel strategy modes with a LINEAR model —
every prediction is dot(feature vector, per-unit constants) — and
obs/jaxprof.py records each executed query segment's feature vector
(under the modes the kernels actually chose) beside its measured
device time.  This module solves the inverse problem: regress the
measured seconds onto the feature vectors by non-negative least
squares, and install the solution as the costmodel's live override
layer.  A daemon serving traffic thereby converges its `choose_*`
argmins to whatever its own hardware measures — reproducing an
offline chip A/B's winners without a bench session, and beating them
on shapes the A/B never visited.  The hash- vs
sort-style group-by crossover this tunes is the one the focused
empirical study measures (PAPERS.md, arXiv:2411.13245); the shared-
aggregation adaptivity mirrors Enthuse (arXiv:2405.18168).

Numerical shape of the fit.  Unit counts span ~10 orders of magnitude
(one gather round vs 3e10 compare cells), so the design matrix is
column-scaled by the CURRENT constants: the solver sees multipliers,
x_j ~ "how wrong is constant j", conditioned near 1.  An intercept
column absorbs the fixed per-dispatch overhead (real on both CPU and
chip) so it cannot corrupt the per-unit terms.  Three guards keep a
noisy batch from destabilizing serving:

  * minimum-sample window — no fit below `min_samples` ring entries,
    and a term must appear in `MIN_TERM_ROWS` entries to move;
  * bounded step — each fit moves a constant by at most a factor of
    `max_step` (multipliers clipped into [1/max_step, max_step]), so
    convergence is geometric and a wild batch is bounded;
  * ridge prior centered on the current constants — terms whose
    priced contribution sits below ~`ridge_frac` of the actuals' RMS
    are unidentifiable from this window (any multiplier fits equally;
    bare NNLS would collapse them toward the clip, fit after fit);
    the prior pins them at their current value while terms with real
    signal override it freely;
  * hysteresis — costmodel.set_hysteresis arms the sticky argmin: a
    challenger mode must beat a shape bucket's incumbent by the band
    before the choice (and the jit caches behind it) flips.

Epsilon exploration.  The ring only holds actuals for modes that WON
the argmin; constants for losing modes would never re-fit.  With
`tsd.costmodel.autotune.epsilon` > 0 the calibrator occasionally
forces one losing-but-feasible mode globally for one interval (via the
set_*_mode setters, which clear the jit caches — per-query exploration
would be silently ignored by the compiled-program cache), observes its
actuals, then restores 'auto'.  Off by default: exploration dispatches
deliberately-slower kernels.

Everything is wired behind `tsd.costmodel.autotune.*` (utils/config.py
CONFIG_SCHEMA, docs/costmodel.md); the maintenance thread drives
`OnlineCalibrator.tick()` and TSDB.shutdown persists the fitted
constants to BENCH_CALIBRATION.json so calibration survives restarts.
"""

from __future__ import annotations

import json
import logging
import math
import os
import random
import threading
import time

import numpy as np

LOG = logging.getLogger("tsd.costmodel.autotune")

# a term must appear (with nonzero units) in at least this many ring
# entries before a fit may move it
MIN_TERM_ROWS = 3

# deterministic exploration stream: reproducible soak runs
_EXPLORE_SEED = 0xC057


def nnls(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Non-negative least squares: argmin ||a @ x - b|| s.t. x >= 0.

    scipy's Lawson-Hanson when available; otherwise a small active-set
    implementation of the same algorithm (the problems here are tiny —
    a handful of columns — so the pure-numpy path is plenty)."""
    try:
        from scipy.optimize import nnls as _scipy_nnls
        return _scipy_nnls(a, b)[0]
    except ImportError:  # pragma: no cover - scipy is in the base image
        return _nnls_numpy(a, b)


def _nnls_numpy(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Lawson-Hanson active-set NNLS (Solving Least Squares Problems,
    ch. 23) in plain numpy."""
    m, n = a.shape
    x = np.zeros(n)
    passive = np.zeros(n, dtype=bool)
    w = a.T @ (b - a @ x)
    tol = 10 * np.finfo(float).eps * np.linalg.norm(a, 1) * (max(m, n) + 1)
    it, max_it = 0, 3 * n
    while (~passive).any() and (w[~passive] > tol).any() and it < max_it:
        it += 1
        j = int(np.argmax(np.where(~passive, w, -np.inf)))
        passive[j] = True
        while True:
            z = np.zeros(n)
            cols = np.where(passive)[0]
            z[cols] = np.linalg.lstsq(a[:, cols], b, rcond=None)[0]
            if (z[cols] > tol).all():
                x = z
                break
            # step back to the boundary, drop newly-zero columns
            neg = cols[z[cols] <= tol]
            steps = [x[k] / (x[k] - z[k]) for k in neg if x[k] > z[k]]
            if not steps:
                # degenerate (collinear) columns: the just-added column
                # solved to exactly 0 with x already 0 — no boundary to
                # step back to; drop the offenders and re-solve
                passive[neg] = False
                if not passive.any():
                    return np.zeros(n)
                continue
            alpha = min(steps)
            x = x + alpha * (z - x)
            passive &= x > tol
            if not passive.any():
                return np.zeros(n)
        w = a.T @ (b - a @ x)
    return np.clip(x, 0.0, None)


def fittable_entries(entries: list[dict], platform: str) -> list[dict]:
    """Ring entries the fitter can use for one platform: a feature
    vector AND a positive measured actual (device timing on)."""
    return [e for e in entries
            if e.get("platform") == platform and e.get("features")
            and float(e.get("actualMs", 0.0)) > 0.0]


def fit_constants(entries: list[dict], platform: str,
                  current: dict[str, float] | None = None,
                  min_samples: int = 64,
                  max_step: float = 4.0,
                  ridge_frac: float = 0.01) -> tuple[dict | None, dict]:
    """One NNLS fit of the per-unit constants from ring entries.

    Returns (constants, info): `constants` maps every fitted term to
    its new value (bounded to a factor of `max_step` around `current`),
    or None when the window holds fewer than `min_samples` fittable
    entries.  Terms without MIN_TERM_ROWS covering entries are left
    untouched (absent from the result).  `ridge_frac` sets the prior
    strength (as a fraction of the actuals' RMS) pulling each
    multiplier toward 1 — the identifiability floor; 0 disables it
    (pure NNLS).  The returned constants are finite and positive BY
    CONSTRUCTION: NNLS gives x >= 0 and the step clip keeps every
    multiplier in [1/max_step, max_step].
    """
    from opentsdb_tpu.ops import costmodel
    if current is None:
        current = dict(costmodel.costs(platform))
    rows = fittable_entries(entries, platform)
    info: dict = {"platform": platform, "samples": len(rows)}
    if len(rows) < max(int(min_samples), 1):
        info["skipped"] = "min_samples"
        return None, info
    coverage: dict[str, int] = {}
    for e in rows:
        for term, units in e["features"].items():
            if units > 0.0 and term in current:
                coverage[term] = coverage.get(term, 0) + 1
    terms = sorted(t for t, c in coverage.items() if c >= MIN_TERM_ROWS)
    info["terms"] = terms
    if not terms:
        info["skipped"] = "no_covered_terms"
        return None, info
    # columns scaled by the current constants -> x is a multiplier;
    # final intercept column absorbs the fixed per-dispatch overhead
    a = np.array([[e["features"].get(t, 0.0) * current[t] for t in terms]
                  + [1.0] for e in rows], dtype=float)
    b = np.array([float(e["actualMs"]) / 1e3 for e in rows], dtype=float)
    if ridge_frac > 0.0:
        # prior rows: lam * (x_j - 1) per term (and lam * x_intercept
        # toward 0).  Terms whose priced signal clears lam override
        # the prior; sub-lam terms hold their current value
        lam = float(ridge_frac) * float(np.sqrt(np.mean(b * b)))
        if lam > 0.0:
            k = len(terms)
            a = np.vstack([a, lam * np.eye(k + 1)])
            b = np.concatenate([b, lam * np.ones(k), [0.0]])
    x = nnls(a, b)
    info["overhead_s"] = float(x[-1])
    # residual over the DATA rows only (not the prior rows)
    nd = len(rows)
    resid = a[:nd] @ x - b[:nd]
    denom = float(np.sum(b[:nd] * b[:nd])) or 1.0
    info["residual"] = float(np.sqrt(np.sum(resid * resid) / denom))
    # max_step <= 0 means unbounded (the offline CLI's single-shot fit);
    # the online loop always passes a finite bound
    step = math.inf if float(max_step) <= 0.0 \
        else max(float(max_step), 1.0 + 1e-9)
    fitted: dict[str, float] = {}
    for t, mult in zip(terms, x[:-1]):
        mult = min(max(float(mult), 1.0 / step), step)
        if not math.isfinite(mult) or mult <= 0.0:
            # unbounded step + an NNLS zero: the term lost all its
            # cost in this window — keep the current constant instead
            # of installing 0
            info.setdefault("rejected", []).append(t)
            continue
        value = current[t] * mult
        if not math.isfinite(value) or value <= 0.0:
            # unreachable given the clip; belt-and-suspenders so a
            # poisoned value can never reach install_live_calibration
            info.setdefault("rejected", []).append(t)
            continue
        fitted[t] = value
    return fitted, info


def merge_calibration_file(path: str,
                           per_platform: dict[str, dict]) -> None:
    """Merge fitted constants into a calibration file (atomic replace;
    existing platforms/terms not in `per_platform` are preserved).
    Shared by the online loop's shutdown persistence and the offline
    CLI (tools/fit_costmodel.py)."""
    existing: dict = {}
    try:
        with open(path) as fh:
            loaded = json.load(fh)
        if isinstance(loaded, dict):
            existing = loaded
    except (OSError, ValueError):
        pass    # absent/corrupt file: start fresh
    for plat, constants in per_platform.items():
        table = existing.setdefault(plat, {})
        if isinstance(table, dict):
            table.update(constants)
        else:
            existing[plat] = dict(constants)
    tmp = "%s.tmp.%d" % (path, os.getpid())
    with open(tmp, "w") as fh:
        json.dump(existing, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)


# --------------------------------------------------------------------- #
# The online loop                                                       #
# --------------------------------------------------------------------- #

def _axis_setters() -> dict:
    from opentsdb_tpu.ops import downsample as ds
    from opentsdb_tpu.ops import group_agg as ga
    return {
        "search": ds.set_search_mode,
        "scan": ds.set_scan_mode,
        "extreme": ds.set_extreme_mode,
        "group": ga.set_group_reduce_mode,
    }


class OnlineCalibrator:
    """The self-tuning loop: fit from the live segment ring on the
    maintenance cadence, install bounded-step live constants, optionally
    explore losing modes, persist at shutdown.

    Driven by MaintenanceThread._maybe_autotune; constructed by TSDB
    when ``tsd.costmodel.autotune.enable`` is true.  All mutable state
    is guarded by ``_lock`` (the maintenance thread ticks; stats walks
    read from request threads)."""

    def __init__(self, tsdb):
        cfg = tsdb.config
        self.tsdb = tsdb
        self.interval = cfg.get_int("tsd.costmodel.autotune.interval")
        self.min_samples = cfg.get_int(
            "tsd.costmodel.autotune.min_samples")
        self.max_step = cfg.get_float("tsd.costmodel.autotune.max_step")
        self.epsilon = cfg.get_float("tsd.costmodel.autotune.epsilon")
        self.persist_on_shutdown = cfg.get_bool(
            "tsd.costmodel.autotune.persist")
        path = cfg.get_string("tsd.costmodel.autotune.calibration_file")
        from opentsdb_tpu.ops import costmodel
        # remember what construction installs process-globally so
        # shutdown() can restore it: a LATER TSDB in the same process
        # with autotune off must not inherit this instance's band,
        # live constants, or calibration-file redirect
        self._prior_calibration_file = costmodel.calibration_file()
        self._prior_hysteresis = costmodel.hysteresis()
        if path:
            # global-install: set_calibration_file paired-with: shutdown
            costmodel.set_calibration_file(path)
        try:
            self.calibration_path = path or costmodel.calibration_file()
            # PROCESS-GLOBAL, like _apply_kernel_modes: the sticky-argmin
            # band lives with the module-level choosers
            # global-install: set_hysteresis paired-with: shutdown
            costmodel.set_hysteresis(cfg.get_float(
                "tsd.costmodel.autotune.hysteresis"))
            self._lock = threading.Lock()
            self._rng = random.Random(_EXPLORE_SEED)
            # guarded-by: _lock
            self.fits = 0
            self.fit_errors = 0  # guarded-by: _lock
            self.samples_used = 0  # guarded-by: _lock
            self.explorations = 0  # guarded-by: _lock
            self.last_residual = 0.0  # guarded-by: _lock
            # active exploration: {"axis": ..., "mode": ...} while a
            # losing mode is forced  # guarded-by: _lock
            self.exploring: dict | None = None

            # NOT under _lock: only the maintenance thread's tick
            # touches it.  Armed by the first heartbeat (one full
            # interval after startup) rather than here: tick() accepts
            # an injected clock, and a monotonic-anchored init would
            # never fire under one.
            self._next_fit: float | None = None
            tsdb.stats_hooks["costmodel_autotune"] = self._stats_hook
        except BaseException:
            # a failed construction leaves no instance whose shutdown()
            # could restore the process-global redirect — undo it here
            costmodel.set_calibration_file(self._prior_calibration_file)
            costmodel.set_hysteresis(self._prior_hysteresis)
            raise

    # -- cadence ------------------------------------------------------- #

    def tick(self, now: float | None = None) -> bool:
        """One maintenance heartbeat: no-op until the interval elapses,
        then end any active exploration, fit, maybe start a new
        exploration.  Returns True when a pass ran."""
        if now is None:
            now = time.monotonic()
        if self.interval <= 0:
            return False
        if self._next_fit is None:
            self._next_fit = now + max(self.interval, 1)
            return False
        if now < self._next_fit:
            return False
        self._next_fit = now + max(self.interval, 1)
        self._end_exploration()
        try:
            self.fit_once()
        except Exception:
            with self._lock:
                self.fit_errors += 1
            LOG.exception("costmodel autotune fit failed")
        self._maybe_explore()
        return True

    # -- fitting ------------------------------------------------------- #

    def fit_once(self) -> int:
        """Fit every platform with fittable ring entries; install the
        results as live calibration.  Returns platforms installed."""
        from opentsdb_tpu.obs import jaxprof
        from opentsdb_tpu.obs.registry import REGISTRY
        from opentsdb_tpu.ops import costmodel
        entries = jaxprof.segments()
        platforms = sorted({e.get("platform") for e in entries
                            if e.get("platform")})
        installed = 0
        for plat in platforms:
            fitted, info = fit_constants(
                entries, plat, min_samples=self.min_samples,
                max_step=self.max_step)
            if not fitted:
                continue
            # global-install: clear_live_calibration paired-with: shutdown
            costmodel.install_live_calibration(plat, fitted)
            installed += 1
            with self._lock:
                self.fits += 1
                self.samples_used = info["samples"]
                self.last_residual = info["residual"]
            REGISTRY.counter(
                "tsd.costmodel.calibration.fits",
                "Online costmodel fits installed").labels(
                    platform=plat).inc()
            REGISTRY.gauge(
                "tsd.costmodel.calibration.samples",
                "Ring entries consumed by the last fit").labels(
                    platform=plat).set(info["samples"])
            REGISTRY.gauge(
                "tsd.costmodel.calibration.residual",
                "Relative residual of the last fit").labels(
                    platform=plat).set(info["residual"])
            for term, value in fitted.items():
                REGISTRY.gauge(
                    "tsd.costmodel.calibration.constant",
                    "Live-fitted per-unit cost, seconds").labels(
                        platform=plat, term=term).set(value)
            LOG.info("costmodel fit installed for %s: %d samples, "
                     "residual %.3f, %d terms", plat, info["samples"],
                     info["residual"], len(fitted))
            recorder = getattr(self.tsdb, "flightrec", None)
            if recorder is not None:
                recorder.record("autotune", action="fit", platform=plat,
                                samples=int(info["samples"]),
                                residual=round(float(info["residual"]),
                                               4))
        return installed

    # -- exploration --------------------------------------------------- #

    def _maybe_explore(self) -> None:
        """With probability epsilon, force one losing-but-feasible mode
        for one interval so the ring collects actuals for it.  Only
        explores decisions the argmin owns (source == 'auto'): an
        operator-forced mode is never overridden."""
        if self.epsilon <= 0.0 or self._rng.random() >= self.epsilon:
            return
        from opentsdb_tpu.obs import jaxprof
        candidates = [e for e in jaxprof.segments()
                      if e.get("modes") and e.get("platform")]
        if not candidates:
            return
        entry = self._rng.choice(candidates)
        extremes = "extreme" in entry["modes"]
        decisions = jaxprof.segment_decisions(
            entry["platform"], entry["series"], entry["points"],
            entry["windows"], entry["groups"],
            "min" if extremes else "avg",
            aggregator=entry.get("aggregator"))
        axes = [a for a, rep in decisions.items()
                if rep["source"] == "auto"
                and len(rep["candidates"]) > 1]
        from opentsdb_tpu.ops import downsample as ds
        if entry["platform"] == "cpu" and ds._PLATFORM_MODE_GUARD:
            # the CPU platform guard demotes the dense search forms at
            # dispatch: forcing one would flush every jit cache twice
            # and record zero new data — spend this epsilon draw on an
            # axis that can actually be explored here
            axes = [a for a in axes if a != "search"]
        if not axes:
            return
        axis = self._rng.choice(axes)
        report = decisions[axis]
        losers = [m for m in report["candidates"]
                  if m != report["mode"]]
        if not losers:
            return
        mode = self._rng.choice(losers)
        _axis_setters()[axis](mode)     # clears the dependent jit caches
        with self._lock:
            self.exploring = {"axis": axis, "mode": mode}
            self.explorations += 1
        from opentsdb_tpu.obs.registry import REGISTRY
        REGISTRY.counter(
            "tsd.costmodel.calibration.explorations",
            "Epsilon-exploration intervals dispatched").labels(
                axis=axis).inc()
        LOG.info("costmodel exploration: forcing %s mode %r for one "
                 "interval", axis, mode)
        recorder = getattr(self.tsdb, "flightrec", None)
        if recorder is not None:
            # a mode flip clears the dependent jit caches — exactly the
            # event a "why did serving recompile at 14:32" post-mortem
            # needs retained
            recorder.record("autotune", action="explore", axis=axis,
                            mode=mode)

    def _end_exploration(self) -> None:
        with self._lock:
            active = self.exploring
            self.exploring = None
        if active is None:
            return
        _axis_setters()[active["axis"]]("auto")
        recorder = getattr(self.tsdb, "flightrec", None)
        if recorder is not None:
            recorder.record("autotune", action="restore",
                            axis=active["axis"], mode=active["mode"])

    # -- persistence --------------------------------------------------- #

    def persist(self) -> bool:
        """Merge the live-fitted constants into the calibration file
        (atomic replace) so the next process starts from them.  Returns
        True when something was written."""
        from opentsdb_tpu.ops import costmodel
        live = {p: costmodel.live_calibration(p) for p in ("tpu", "cpu")}
        live = {p: v for p, v in live.items() if v}
        if not live:
            return False
        merge_calibration_file(self.calibration_path, live)
        LOG.info("persisted live costmodel calibration to %s "
                 "(platforms: %s)", self.calibration_path,
                 ", ".join(sorted(live)))
        return True

    def shutdown(self) -> None:
        """Mirror construction: restore any forced exploration mode,
        persist the fitted constants (config-gated), then un-install
        the process-global state this instance set up — the live
        layer (safe to drop once persisted: the file layer serves it
        from `calibration_path`), the hysteresis band, and the
        calibration-file redirect.  Called from TSDB.shutdown."""
        self._end_exploration()
        if self.persist_on_shutdown:
            try:
                self.persist()
            except OSError:
                LOG.exception("could not persist costmodel calibration")
        from opentsdb_tpu.ops import costmodel
        costmodel.clear_live_calibration()
        costmodel.set_hysteresis(self._prior_hysteresis)
        if costmodel.calibration_file() != self._prior_calibration_file:
            costmodel.set_calibration_file(self._prior_calibration_file)

    # -- stats --------------------------------------------------------- #

    def collect_stats(self) -> dict[str, float]:
        with self._lock:
            return {
                "costmodel.autotune.fits": float(self.fits),
                "costmodel.autotune.fit_errors": float(self.fit_errors),
                "costmodel.autotune.samples_used":
                    float(self.samples_used),
                "costmodel.autotune.explorations":
                    float(self.explorations),
                "costmodel.autotune.residual": float(self.last_residual),
                "costmodel.autotune.exploring":
                    1.0 if self.exploring else 0.0,
            }

    def _stats_hook(self, collector) -> None:
        """/api/stats + self-report view: loop counters plus the live
        constants themselves (term-tagged), so an operator — and the
        chaos gate — can read the installed calibration off any stats
        surface."""
        from opentsdb_tpu.ops import costmodel
        for name, value in self.collect_stats().items():
            # forwarder: the names are this class's collect_stats()
            # keys (tsd.costmodel.autotune.*), declared in
            # METRICS_SCHEMA  # tsdblint: disable=metrics-dynamic-name
            collector.record(name, value)
        for plat in ("tpu", "cpu"):
            for term, value in costmodel.live_calibration(plat).items():
                collector.record("costmodel.calibration.%s" % plat,
                                 value, "term=%s" % term)

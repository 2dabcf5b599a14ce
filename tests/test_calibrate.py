"""The online costmodel calibration loop (ops/calibrate.py).

Unit layer: the NNLS fitter recovers known constants from synthetic
ring entries, respects the minimum-sample window, the bounded step,
and the per-term coverage floor, and can never emit a non-positive or
NaN constant.  CLI layer: tools/fit_costmodel.py round-trips a dumped
ring (both the raw-list and the saved-/api/stats/query forms) into a
BENCH_CALIBRATION.json that the costmodel's file layer then serves.

Convergence layer (the acceptance test): a daemon whose cpu constants
are deliberately wrong serves a synthetic mixed query load (CPU
platform; mesh off, because only monolithic single-device dispatches
enter the calibration ring) with the
autotune loop armed, epsilon-exploration on so losing strategies get
measured too, and must re-fit from its own segment ring until
choose_scan / choose_group / choose_search / choose_extreme return the
platform's measured winners.  "Measured" is pinned deterministically:
the test intercepts record_segment and replaces each segment's actual
with the ground-truth cost of its feature vector (the default cpu
table + dispatch overhead + small deterministic jitter) — real timing
at unit-test shapes is dispatch-overhead noise, which would make the
winner assertions flaky while testing nothing extra; every other part
of the loop (decisions, feature vectors, ring, fitter, install,
exploration, hysteresis, persistence) runs live.
"""

from __future__ import annotations

import json
import math

import numpy as np
import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.obs import jaxprof
from opentsdb_tpu.ops import calibrate, costmodel
from opentsdb_tpu.ops import downsample as ds
from opentsdb_tpu.ops import group_agg as ga
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400

TRUE_CPU = dict(costmodel.DEFAULT_COSTS["cpu"])
# synthetic per-dispatch overhead: small enough that the traffic
# shapes' per-term signals clear the fitter's ridge floor (real
# dispatch overhead at unit-test shapes would drown them — which is a
# statement about the shapes, not the loop)
OVERHEAD_S = 3e-5


@pytest.fixture(autouse=True)
def _reset_costmodel_state():
    """Every test leaves the process-global costmodel state pristine:
    later files (the obs overhead pin) assert the defaults."""
    prior_file = costmodel.calibration_file()
    prior_modes = (ds._SCAN_MODE, ds._SEARCH_MODE, ds._EXTREME_MODE,
                   ga._GROUP_REDUCE_MODE)
    yield
    costmodel.set_hysteresis(0.0)
    costmodel.clear_live_calibration()
    if costmodel.calibration_file() != prior_file:
        costmodel.set_calibration_file(prior_file)
    for setter, mode in zip((ds.set_scan_mode, ds.set_search_mode,
                             ds.set_extreme_mode,
                             ga.set_group_reduce_mode), prior_modes):
        setter(mode)
    jaxprof.clear_segments()


def synth_entry(s: int, n: int, w: int, g: int,
                scan_mode: str = "flat", group_mode: str = "segment",
                search_mode: str = "scan",
                extreme_mode: str | None = None,
                true_costs: dict | None = None,
                jitter: float = 1.0) -> dict:
    """One fittable ring entry whose actual is the ground-truth cost of
    its feature vector (+ dispatch overhead, scaled by jitter)."""
    true_costs = true_costs or TRUE_CPU
    e = w + 1
    features: dict[str, float] = {}

    def add(fv):
        for t, u in fv.items():
            features[t] = features.get(t, 0.0) + u

    add(costmodel.features_search(search_mode, s, n, e))
    if extreme_mode is not None:
        add(costmodel.features_extreme(extreme_mode, s, n, e))
    else:
        add(costmodel.features_scan(scan_mode, s, n, e))
    add(costmodel.features_group(group_mode, s, w, g))
    add({"elem_f64": float(g * w)})
    actual_s = sum(u * true_costs[t] for t, u in features.items()) \
        + OVERHEAD_S
    return {"kind": "raw", "series": s, "points": n, "windows": w,
            "groups": g, "platform": "cpu",
            "modes": {"search": search_mode,
                      ("extreme" if extreme_mode else "scan"):
                          extreme_mode or scan_mode,
                      "group": group_mode},
            "features": features,
            "predictedMs": 1.0,
            "actualMs": actual_s * 1e3 * jitter}


def mixed_entries(jittered: bool = False) -> list[dict]:
    """A varied synthetic mix: every scan/group/extreme form appears,
    shapes span the classes, so every cpu term the platform can
    exercise is covered."""
    out = []
    shapes = [(4, 1024, 32, 2), (8, 4096, 64, 4), (2, 512, 16, 2),
              (16, 2048, 128, 8), (4, 8192, 256, 2), (8, 1024, 8, 8),
              # grid-heavy shapes: [S, W] much wider than [S, N], so
              # the group-reduce terms carry a dominant share of their
              # entries' totals and stay well-conditioned under noise
              (4, 1024, 4096, 64), (2, 512, 8192, 256)]
    for s, n, w, g in shapes:
        for scan in ("flat", "subblock", "subblock2"):
            for group in ("segment", "sorted", "matmul"):
                out.append(synth_entry(s, n, w, g, scan_mode=scan,
                                       group_mode=group))
        for ext in ("scan", "segment", "subblock"):
            out.append(synth_entry(s, n, w, g, extreme_mode=ext,
                                   group_mode="segment"))
    if jittered:
        # alternating +-2% per entry: unbiased measurement noise, not
        # a per-shape systematic skew
        for i, e in enumerate(out):
            e["actualMs"] *= 1.02 if i % 2 else 0.98
    return out


class TestNNLS:
    def test_numpy_fallback_matches_scipy(self):
        rng = np.random.default_rng(11)
        a = rng.random((40, 5))
        x_true = np.array([0.5, 0.0, 2.0, 0.0, 1.2])
        b = a @ x_true
        got = calibrate._nnls_numpy(a, b)
        np.testing.assert_allclose(got, x_true, atol=1e-8)
        scipy = pytest.importorskip("scipy.optimize")
        np.testing.assert_allclose(got, scipy.nnls(a, b)[0], atol=1e-8)

    def test_nonnegative_on_adversarial_target(self):
        rng = np.random.default_rng(13)
        a = rng.random((30, 4))
        b = -np.ones(30)    # best fit would want negative x
        got = calibrate._nnls_numpy(a, b)
        assert (got >= 0).all()

    def test_collinear_columns_do_not_crash(self):
        # the ring produces exactly-proportional columns when two cost
        # terms always appear in a fixed ratio (one shape class); the
        # fallback's degenerate step-back path must terminate, not
        # raise on an empty boundary-step set
        rng = np.random.default_rng(17)
        col = rng.random(24)
        a = np.column_stack([col, 2.0 * col, rng.random(24)])
        b = 3.0 * col + 0.5 * a[:, 2]
        got = calibrate._nnls_numpy(a, b)
        assert got.shape == (3,) and (got >= 0).all()
        assert np.isfinite(got).all()
        np.testing.assert_allclose(a @ got, b, atol=1e-8)


class TestFitConstants:
    def test_recovers_true_constants_from_wrong_start(self):
        entries = mixed_entries()
        wrong = {t: v * (50.0 if i % 2 else 0.02)
                 for i, (t, v) in enumerate(sorted(TRUE_CPU.items()))}
        fitted, info = calibrate.fit_constants(
            entries, "cpu", current=wrong, min_samples=8, max_step=0.0,
            ridge_frac=0.0)
        assert fitted, info
        assert info["overhead_s"] == pytest.approx(OVERHEAD_S, rel=0.05)
        for term, value in fitted.items():
            assert value == pytest.approx(TRUE_CPU[term], rel=1e-3), \
                term
        # every cpu-exercisable term is covered by the mix (but the
        # one-member-groups copy, "rows": no entry of it here); the spill,
        # rollup-lane, and stacked-dispatch terms never appear in ring
        # features (tiled, lane-served, AND batched executions are
        # ring-excluded by design, tests/test_tiling.py /
        # test_rollup_lanes.py / test_batcher.py — their constants fit
        # offline / from a future dedicated-measurement path)
        assert set(fitted) == set(TRUE_CPU) - {
            "cmp_cell", "hier_cell", "sorted2_grid", "rows_grid",
            "spill_write_mb", "spill_read_mb", "tile_dispatch",
            "lane_assemble_mb", "lane_build_cell",
            "stacked_dispatch", "stacked_cell"}

    def test_recovery_survives_jitter(self):
        """+-2% measurement noise: well-constrained terms land near
        truth; terms whose signal is a small share of their entries'
        totals (mxu_cell at tiny grids) wander more — what must
        survive is the DECISION: the fitted table reproduces the true
        table's argmin at the reference shapes."""
        fitted, _ = calibrate.fit_constants(
            mixed_entries(jittered=True), "cpu",
            current=dict(TRUE_CPU), min_samples=8, max_step=0.0,
            ridge_frac=0.0)
        for term, value in fitted.items():
            assert value == pytest.approx(TRUE_CPU[term], rel=0.75), \
                term
        table = dict(TRUE_CPU)
        table.update(fitted)

        def argmin(predict, modes):
            return min(modes, key=lambda m: sum(
                u * table[t]
                for t, u in predict(m).items()))

        s, n, e, g = 1024, 65_536, 514, 100
        assert argmin(lambda m: costmodel.features_scan(m, s, n, e),
                      ("flat", "subblock", "subblock2")) == "subblock"
        assert argmin(lambda m: costmodel.features_group(m, s, 512, g),
                      ("segment", "sorted", "matmul")) == "segment"
        assert argmin(lambda m: costmodel.features_extreme(m, s, n, e),
                      ("scan", "segment", "subblock")) == "segment"

    def test_min_samples_window(self):
        entries = mixed_entries()[:4]
        fitted, info = calibrate.fit_constants(entries, "cpu",
                                               min_samples=8)
        assert fitted is None and info["skipped"] == "min_samples"

    def test_bounded_step(self):
        wrong = {t: v * 1000.0 for t, v in TRUE_CPU.items()}
        fitted, _ = calibrate.fit_constants(
            mixed_entries(), "cpu", current=wrong, min_samples=8,
            max_step=4.0)
        for term, value in fitted.items():
            ratio = value / wrong[term]
            assert 1 / 4.0 - 1e-9 <= ratio <= 4.0 + 1e-9, (term, ratio)
            # and the step moves DOWN toward truth
            assert ratio < 1.0, term

    def test_ridge_pins_unidentifiable_terms(self):
        """A term whose priced contribution sits below the ridge floor
        must HOLD its current value — bare NNLS would collapse it
        toward zero fit after fit (any multiplier fits the data
        equally when the signal is sub-noise)."""
        entries = mixed_entries()
        current = dict(TRUE_CPU)
        # make win_gather's current price nearly free: its priced
        # column becomes negligible against every entry's total
        current["win_gather"] = TRUE_CPU["win_gather"] * 1e-6
        fitted, _ = calibrate.fit_constants(
            entries, "cpu", current=current, min_samples=8,
            max_step=0.0)
        assert fitted["win_gather"] == pytest.approx(
            current["win_gather"], rel=0.5)
        # pure NNLS on the same window shows the collapse the ridge
        # prevents is real: the unidentifiable multiplier runs away
        bare, _ = calibrate.fit_constants(
            entries, "cpu", current=current, min_samples=8,
            max_step=0.0, ridge_frac=0.0)
        assert "win_gather" not in bare or \
            bare["win_gather"] != pytest.approx(
                current["win_gather"], rel=0.5)

    def test_term_coverage_floor(self):
        # sub2_elem appears in fewer than MIN_TERM_ROWS entries -> the
        # fit must leave it alone
        entries = [e for e in mixed_entries()
                   if e["features"].get("sub2_elem", 0) == 0]
        entries += [synth_entry(4, 1024, 32, 2, scan_mode="subblock2")
                    ] * (calibrate.MIN_TERM_ROWS - 1)
        fitted, _ = calibrate.fit_constants(entries, "cpu",
                                            min_samples=8,
                                            max_step=0.0,
                                            ridge_frac=0.0)
        assert fitted and "sub2_elem" not in fitted

    def test_constants_always_positive_finite(self):
        # adversarial: all-zero actuals still cannot produce a
        # non-positive constant (multiplier clip floors at 1/step)
        entries = mixed_entries()
        for e in entries:
            e["actualMs"] = 1e-9
        fitted, _ = calibrate.fit_constants(entries, "cpu",
                                            min_samples=8,
                                            max_step=8.0)
        for term, value in fitted.items():
            assert math.isfinite(value) and value > 0.0

    def test_unfittable_entries_filtered(self):
        entries = mixed_entries()
        stripped = [{k: v for k, v in e.items() if k != "features"}
                    for e in entries]
        assert calibrate.fittable_entries(stripped, "cpu") == []
        zeroed = [dict(e, actualMs=0.0) for e in entries]
        assert calibrate.fittable_entries(zeroed, "cpu") == []
        assert len(calibrate.fittable_entries(entries, "tpu")) == 0


class TestOfflineCLIRoundTrip:
    """tools/fit_costmodel.py: dumped ring -> BENCH_CALIBRATION.json ->
    costmodel file layer serves the fitted constants."""

    def _run(self, tmp_path, payload, extra_args=()):
        import tools.fit_costmodel as cli
        ring = tmp_path / "ring.json"
        ring.write_text(json.dumps(payload))
        out = tmp_path / "BENCH_CALIBRATION.json"
        rc = cli.main([str(ring), "--out", str(out), "--min-samples",
                       "8", *extra_args])
        return rc, out

    def test_raw_list_round_trip(self, tmp_path):
        rc, out = self._run(tmp_path, mixed_entries())
        assert rc == 0 and out.exists()
        written = json.loads(out.read_text())
        assert written["cpu"]["seg_scatter"] == pytest.approx(
            TRUE_CPU["seg_scatter"], rel=1e-3)
        # the costmodel file layer now serves the fitted table
        costmodel.set_calibration_file(str(out))
        assert costmodel.calibration_source("cpu") == "file"
        assert costmodel.costs("cpu")["seg_scatter"] == pytest.approx(
            TRUE_CPU["seg_scatter"], rel=1e-3)

    def test_stats_query_payload_round_trip(self, tmp_path):
        payload = {"running": [], "completed": [],
                   "costmodelSegments": mixed_entries()}
        rc, out = self._run(tmp_path, payload)
        assert rc == 0
        assert "cpu" in json.loads(out.read_text())

    def test_merge_preserves_other_platforms(self, tmp_path):
        out = tmp_path / "BENCH_CALIBRATION.json"
        out.write_text(json.dumps({"tpu": {"mxu_cell": 7e-9},
                                   "cpu": {"cmp_cell": 3e-9}}))
        rc, _ = self._run(tmp_path, mixed_entries())
        assert rc == 0
        written = json.loads(out.read_text())
        assert written["tpu"]["mxu_cell"] == 7e-9      # untouched
        assert written["cpu"]["cmp_cell"] == 3e-9      # uncovered term
        assert written["cpu"]["seg_scatter"] == pytest.approx(
            TRUE_CPU["seg_scatter"], rel=1e-3)

    def test_unknown_platform_ring_is_refused(self, tmp_path, capsys):
        # A ring recorded on a platform the cost model has no table
        # for must not be fitted onto another platform's constants.
        entries = mixed_entries()
        for e in entries:
            e["platform"] = "gpu"
        rc, out = self._run(tmp_path, entries)
        assert rc == 1 and not out.exists()
        assert "no cost table for platform 'gpu'" in capsys.readouterr().err

    def test_dry_run_writes_nothing(self, tmp_path):
        rc, out = self._run(tmp_path, mixed_entries(),
                            extra_args=("--dry-run",))
        assert rc == 0 and not out.exists()

    def test_empty_ring_fails_loudly(self, tmp_path):
        rc, out = self._run(tmp_path, [])
        assert rc == 1 and not out.exists()


def serve(manager, uri):
    r = manager.handle_http(HttpRequest(method="GET", uri=uri),
                            remote="127.0.0.1:77").response
    assert r.status == 200, r.status
    return r


TRAFFIC = [
    # the synthetic mix: grouped avg downsamples (scan+group axes),
    # extreme downsamples (extreme axis), varied shape classes.  The
    # extreme queries appear twice: one epsilon-exploration interval
    # must put >= MIN_TERM_ROWS segment-extreme entries in the ring
    "/api/query?start=%d&end=%d&m=sum:30s-avg:conv.cpu{host=*}"
    % (BASE, BASE + 2400),
    "/api/query?start=%d&end=%d&m=max:30s-max:conv.cpu{host=*}"
    % (BASE, BASE + 2400),
    "/api/query?start=%d&end=%d&m=sum:10s-avg:conv.cpu{host=*}"
    % (BASE, BASE + 1200),
    "/api/query?start=%d&end=%d&m=min:60s-min:conv.cpu"
    % (BASE, BASE + 2400),
    "/api/query?start=%d&end=%d&m=max:10s-max:conv.cpu"
    % (BASE, BASE + 1800),
    "/api/query?start=%d&end=%d&m=min:20s-min:conv.cpu{host=*}"
    % (BASE, BASE + 1200),
    "/api/query?start=%d&end=%d&m=sum:20s-avg:conv.cpu"
    % (BASE, BASE + 1800),
]


class TestConvergence:
    """The acceptance criterion: wrong constants in, platform winners
    out — driven by the daemon's own ring under synthetic traffic."""

    # deliberately-wrong cpu constants: every term the platform can
    # exercise is off by 100-1000x IN THE DIRECTION that flips its
    # axis's winner.  cmp_cell / hier_cell stay default: the CPU
    # platform guard forbids the dense search forms, so no cpu
    # measurement could ever correct them (and they must not be made
    # artificially cheap, or the un-correctable lie would win forever).
    WRONG_CPU = {
        "gather_round": 2e-5,     # truth 2e-8: search flips to hier
        "elem_f64": 1e-6,         # truth 1e-9: scan flips off subblock
        "seg_scatter": 5e-6,      # truth 5e-9: group flips off segment
        "ext_seg_elem": 2e-6,     # truth 2e-9: extreme flips off
                                  # segment
    }

    def _assert_winners(self, expect_wrong: bool):
        s, n, e, g = 1024, 65_536, 514, 100
        scan = costmodel.choose_scan(s, n, e, "cpu",
                                     ["flat", "subblock", "subblock2"])
        group = costmodel.choose_group(s, 512, g, "cpu",
                                       ["segment", "sorted", "matmul"])
        search = costmodel.choose_search(s, n, e, "cpu",
                                         ["scan", "compare_all",
                                          "hier"])
        extreme = costmodel.choose_extreme(s, n, e, "cpu",
                                           ["scan", "segment",
                                            "subblock"])
        winners = (scan, group, search, extreme)
        if expect_wrong:
            assert scan != "subblock" and group != "segment" \
                and search != "scan" and extreme != "segment", winners
        else:
            assert winners == ("subblock", "segment", "scan",
                               "segment"), winners

    def test_daemon_refits_to_platform_winners(self, tmp_path,
                                               monkeypatch):
        cal = tmp_path / "BENCH_CALIBRATION.json"
        cal.write_text(json.dumps({"cpu": self.WRONG_CPU}))
        tsdb = TSDB(Config({
            "tsd.core.auto_create_metrics": True,
            "tsd.query.mesh.enable": False,
            # the convergence proof needs every served query in the
            # calibration ring; partial-aggregate rewrites AND batched
            # executions skip the predicted-vs-actual ledger by design
            # (their stage breakdown doesn't describe a
            # block-decomposed or stacked-multi-member execution)
            "tsd.query.cache.enable": False,
            "tsd.query.batch.enable": False,
            "tsd.costmodel.autotune.enable": True,
            "tsd.costmodel.autotune.interval": 1,
            "tsd.costmodel.autotune.min_samples": 16,
            "tsd.costmodel.autotune.max_step": 32,
            # exploration ON: segment-group/segment-extreme lose under
            # the wrong table, so only forced exploration intervals can
            # put their terms in the ring
            "tsd.costmodel.autotune.epsilon": 1.0,
            "tsd.costmodel.autotune.calibration_file": str(cal),
        }))
        assert tsdb.autotuner is not None
        assert costmodel.calibration_source("cpu") == "file"
        self._assert_winners(expect_wrong=True)

        # ground-truth actuals: dispatch overhead + the TRUE cpu cost
        # of the recorded feature vector, with a deterministic +-2%
        # jitter (see module docstring)
        real_record = jaxprof.record_segment
        count = [0]

        def pinned_record(kind, s, n, w, g, predicted_s, actual_ms,
                          platform=None, modes=None, features=None,
                          aggregator=None):
            count[0] += 1
            truth_s = sum(u * TRUE_CPU[t]
                          for t, u in (features or {}).items()) \
                + OVERHEAD_S
            jitter = 1.02 if count[0] % 2 else 0.98
            real_record(kind, s, n, w, g, predicted_s,
                        truth_s * 1e3 * jitter, platform=platform,
                        modes=modes, features=features,
                        aggregator=aggregator)

        monkeypatch.setattr(jaxprof, "record_segment", pinned_record)

        for host in ("web01", "web02", "web03", "web04"):
            for i in range(256):
                tsdb.add_point("conv.cpu", BASE + i * 10, float(i),
                               {"host": host})
        manager = RpcManager(tsdb)
        jaxprof.clear_segments()

        now = 0.0
        for _ in range(13):
            for uri in TRAFFIC:
                serve(manager, uri)
            now += 2.0
            tsdb.autotuner.tick(now)

        assert tsdb.autotuner.fits >= 4
        assert tsdb.autotuner.fit_errors == 0
        assert tsdb.autotuner.explorations >= 4
        assert costmodel.calibration_source("cpu") == "live"
        self._assert_winners(expect_wrong=False)

        # every wrong constant moved decisively toward truth (the
        # winner assertions above are the hard contract; the constants
        # themselves are identifiability-limited at test shapes —
        # entries where W ~ N leave the s*n and s*w columns partially
        # collinear — so this is an order-of-magnitude band, far
        # tighter than the 100-1000x starting error)
        live = costmodel.live_calibration("cpu")
        for term in self.WRONG_CPU:
            assert term in live, (term, live)
            assert TRUE_CPU[term] / 8 < live[term] < TRUE_CPU[term] * 8, \
                (term, live[term], TRUE_CPU[term])
            assert abs(math.log10(live[term] / TRUE_CPU[term])) < \
                abs(math.log10(self.WRONG_CPU[term]
                               / TRUE_CPU[term])) / 2, term

        # every traced segment exposes its strategy decision in the
        # span tree: mode, per-candidate predicted cost, source
        r = serve(manager,
                  TRAFFIC[0] + "&show_stats")
        payload = json.loads(r.body)
        summary = [e for e in payload if "statsSummary" in e][0]
        trace = summary["statsSummary"]["trace"]

        def find_decisions(node):
            found = []
            tags = node.get("tags", {})
            if "costmodel" in tags:
                found.append(tags["costmodel"])
            for c in node.get("spans", []):
                found.extend(find_decisions(c))
            return found

        decisions = find_decisions(trace)
        assert decisions, "pipeline span must carry the decision tags"
        for dec in decisions:
            for axis, report in dec.items():
                assert report["mode"] in report["candidates"]
                assert report["feasible"] is True
                assert report["source"] in ("auto", "forced")
                assert report["calibration"] == "live"
                assert all(v >= 0 for v in
                           report["candidates"].values())

        # shutdown persists the fitted constants (merge into the
        # configured calibration file)
        tsdb.shutdown()
        persisted = json.loads(cal.read_text())["cpu"]
        for term in self.WRONG_CPU:
            assert persisted[term] == pytest.approx(live[term])
        # exploration override restored at shutdown
        assert ds._SCAN_MODE == "auto" and ds._EXTREME_MODE == "auto"
        assert ds._SEARCH_MODE == "auto"
        assert ga._GROUP_REDUCE_MODE == "auto"
        # ...and the process-global installs are torn down: a later
        # TSDB in this process with autotune off must not inherit the
        # band, the live layer, or the calibration-file redirect
        assert costmodel.hysteresis() == 0.0
        assert costmodel.live_calibration("cpu") == {}
        assert costmodel.calibration_file() != str(cal)


class TestExploration:
    def test_off_by_default_and_restores(self, tmp_path):
        tsdb = TSDB(Config({
            "tsd.query.mesh.enable": False,
            "tsd.costmodel.autotune.enable": True,
            "tsd.costmodel.autotune.interval": 1,
            "tsd.costmodel.autotune.calibration_file":
                str(tmp_path / "cal.json"),
        }))
        cal = tsdb.autotuner
        assert cal.epsilon == 0.0      # off unless asked
        jaxprof.clear_segments()
        for e in mixed_entries()[:8]:
            jaxprof.record_segment(
                e["kind"], e["series"], e["points"], e["windows"],
                e["groups"], 1e-3, e["actualMs"],
                platform=e["platform"], modes=e["modes"],
                features=e["features"])
        cal.tick(1e9)
        assert cal.explorations == 0 and cal.exploring is None

    def test_epsilon_one_forces_then_restores(self, tmp_path):
        tsdb = TSDB(Config({
            "tsd.query.mesh.enable": False,
            "tsd.costmodel.autotune.enable": True,
            "tsd.costmodel.autotune.interval": 1,
            "tsd.costmodel.autotune.min_samples": 4,
            "tsd.costmodel.autotune.epsilon": 1.0,
            "tsd.costmodel.autotune.calibration_file":
                str(tmp_path / "cal.json"),
        }))
        cal = tsdb.autotuner
        jaxprof.clear_segments()
        for e in mixed_entries()[:12]:
            jaxprof.record_segment(
                e["kind"], e["series"], e["points"], e["windows"],
                e["groups"], 1e-3, e["actualMs"],
                platform=e["platform"], modes=e["modes"],
                features=e["features"])
        assert not cal.tick(1.0)       # first heartbeat arms the timer
        assert cal.tick(10.0)
        assert cal.exploring is not None
        axis, mode = cal.exploring["axis"], cal.exploring["mode"]
        current = {"search": lambda: ds._SEARCH_MODE,
                   "scan": lambda: ds._SCAN_MODE,
                   "extreme": lambda: ds._EXTREME_MODE,
                   "group": lambda: ga._GROUP_REDUCE_MODE}[axis]
        assert current() == mode != "auto"
        assert cal.tick(20.0)          # next interval restores first
        if cal.exploring is None or cal.exploring["axis"] != axis:
            assert current() in ("auto",) or cal.exploring is not None
        cal.shutdown()
        for get in (lambda: ds._SEARCH_MODE, lambda: ds._SCAN_MODE,
                    lambda: ds._EXTREME_MODE,
                    lambda: ga._GROUP_REDUCE_MODE):
            assert get() == "auto"

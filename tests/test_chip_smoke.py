"""chip_smoke.py's rules, checked on the CPU (tier-1).

The chip run itself belongs to the chip; what CAN be pinned here is the
contract around it: the explicit `--platform cpu` dry run passes end to
end at a tiny size, the default run refuses a daemon that is not on a
TPU before it ingests anything, the numpy reference agrees with the
library on every request shape in the table, the generator is
deterministic in `--seed`, a failed phase (native build, a fallback
parser) fails the run, and the compile cache sits where the rule says.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _run_smoke(tmp_path, *args, env=None):
    out = tmp_path / "out"
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"),
         "--out", str(out), *args],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, **(env or {})))
    summary = json.loads((out / "summary.json").read_text())
    return proc, summary, out


class TestDryRun:
    def test_cpu_dry_run_passes_end_to_end(self, tmp_path):
        proc, summary, out = _run_smoke(
            tmp_path, "--platform", "cpu", "--hosts", "24", "--hours", "1")
        assert proc.returncode == 0, proc.stderr[-2000:]
        lines = proc.stdout.strip().splitlines()
        assert json.loads(lines[-1]) == {
            "ok": True,
            "device": {"platform": "cpu", "kind": "cpu",
                       "count": summary["device"]["count"]}}
        # every progress line carries the cpu label: a dry run can never
        # be mistaken for a chip run
        assert all(line.startswith("[chip_smoke cpu]")
                   for line in lines[:-2])
        assert json.loads(lines[-2]) == summary
        assert list(summary)[-1] == "claim" and summary["claim"] is None
        assert summary["ok"] and not summary["failures"]
        assert summary["ingest"]["parser"] == "native"
        assert summary["ingest"]["added"] == summary["sizes"]["points"] \
            == 24 * 360
        names = [r["name"] for r in summary["requests"]]
        assert names == ["single-groupby-1-1-1", "double-groupby-1-12h",
                         "single-groupby-1-8-1", "region-sum-2h",
                         "region-rate-2h", "datacenter-p99-5h"]
        for row in summary["requests"]:
            for send in (row["cold"], *row["between"], row["warm"]):
                assert send["correct"]
                assert send["planMatchesExplain"]
                assert send["platform"] == "cpu"
            assert row["cold"]["compiled"], row["name"]
            # the last send is the warm one: it compiled nothing
            assert 2 <= row["sends"] <= 4 and not row["warm"]["compiled"]
        assert summary["lastpoint"]["correct"]
        assert summary["kprobe"]["blockUntilReadyWaits"]
        for phase in ("buildNative", "daemonStart", "ingest", "readBack",
                      "requests", "shutdown", "kprobe"):
            assert summary["phaseSeconds"][phase] > 0
        log = (out / "daemon.log").read_text()
        assert "computing on platform=cpu" in log
        assert "Server shut down" in log

    def test_default_run_refuses_a_cpu_daemon_before_ingest(self, tmp_path):
        # no accelerator here: without the explicit switch the smoke must
        # fail, print no result, and never reach the ingest phase
        proc, summary, _ = _run_smoke(tmp_path, "--hosts", "8",
                                      "--hours", "1",
                                      env={"JAX_PLATFORMS": "cpu"})
        assert proc.returncode != 0
        assert '"ok"' not in proc.stdout
        assert not summary["ok"]
        assert "requires 'tpu'" in summary["failures"][0]
        assert "ingest" not in summary["phaseSeconds"]
        assert "shutdown" in summary["phaseSeconds"]   # and left no daemon

    def test_outside_the_repo_it_fails_without_a_result(self, tmp_path):
        lone = tmp_path / "chip_smoke.py"
        lone.write_text(open(os.path.join(REPO, "chip_smoke.py")).read())
        proc = subprocess.run([sys.executable, str(lone)],
                              capture_output=True, text=True, timeout=60,
                              cwd=str(tmp_path))
        assert proc.returncode != 0 and proc.stdout == ""


class TestFailingPhases:
    def test_failed_native_build_fails_the_run(self, tmp_path, monkeypatch):
        real_run = subprocess.run

        def broken_make(cmd, **kw):
            if cmd[0] == "make":
                return subprocess.CompletedProcess(cmd, 2, "", "g++: boom")
            return real_run(cmd, **kw)
        monkeypatch.setattr(chip_smoke.subprocess, "run", broken_make)
        rc = chip_smoke.main(["--platform", "cpu", "--out",
                              str(tmp_path / "out")])
        assert rc == 1
        summary = json.loads((tmp_path / "out" / "summary.json").read_text())
        assert "native build failed" in summary["failures"][0]
        assert "daemonStart" not in summary["phaseSeconds"]

    def _fake_client(self, native, python, sent):
        stats = [
            {"metric": "tsd.datapoints.added", "value": sent,
             "tags": {"host": "h"}},
            {"metric": "tsd.storage.datapoints", "value": sent,
             "tags": {"host": "h"}},
            {"metric": "tsd.put.parser", "value": native,
             "tags": {"host": "h", "parser": "native"}},
            {"metric": "tsd.put.parser", "value": python,
             "tags": {"host": "h", "parser": "python"}}]
        return types.SimpleNamespace(get_json=lambda path: stats)

    def test_python_fallback_parser_fails_the_ingest_phase(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="fallback parser"):
            chip_smoke.check_ingest_stats(
                self._fake_client(native=0, python=3, sent=100), 100,
                {"bodies": 3})
        ing = chip_smoke.check_ingest_stats(
            self._fake_client(native=4, python=0, sent=100), 100,
            {"bodies": 3})
        assert ing["parser"] == "native"

    def test_lost_points_fail_the_ingest_phase(self):
        with pytest.raises(chip_smoke.SmokeFailure, match="daemon added 99"):
            chip_smoke.check_ingest_stats(
                self._fake_client(native=3, python=0, sent=99), 100,
                {"bodies": 3})

    def test_heavy_query_on_the_host_is_a_failure(self, monkeypatch):
        device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
        send = {"seconds": 1.0, "correct": True, "diff": None,
                "path": "resident", "planMatchesExplain": True,
                "platform": "cpu", "hostLane": True, "batched": False,
                "deviceCacheHit": False, "meshDevices": 0,
                "aggCache": None, "compiled": {}}
        monkeypatch.setattr(chip_smoke, "send_query",
                            lambda client, req, trace_id, want: dict(send))
        failures: list = []
        req = dict(chip_smoke.request_table(4, 1, 1)[3], points=3_000_000)
        chip_smoke.run_requests(
            None, chip_smoke.make_fleet(4, 1), chip_smoke.timestamps(360),
            chip_smoke.make_values(4, 360, 1, 0), [req], device, "tpu",
            failures)
        text = "\n".join(failures)
        assert "planned for platform=cpu hostLane=True" in text
        assert "meshDevices=0 on 4 devices" in text
        assert "compiled nothing" in text

    def test_device_memory_must_hold_the_pinned_metric(self):
        def device(peaks):
            return {"platform": "tpu", "kind": "k", "count": len(peaks),
                    "memory": [{"id": i, "peakBytesInUse": p}
                               for i, p in enumerate(peaks)]}
        failures: list = []
        chip_smoke.check_device_memory(device([600 << 20]), 553 << 20,
                                       failures)
        assert not failures
        chip_smoke.check_device_memory(device([100 << 20]), 553 << 20,
                                       failures)
        assert "never lived on the device" in failures[-1]
        chip_smoke.check_device_memory(
            device([600 << 20, 0, 5 << 20, 5 << 20]), 553 << 20, failures)
        assert "devices [1] never held" in failures[-1]


class TestGenerator:
    def test_deterministic_in_seed(self):
        assert chip_smoke.make_fleet(50, 7) == chip_smoke.make_fleet(50, 7)
        assert chip_smoke.make_fleet(50, 7) != chip_smoke.make_fleet(50, 8)
        a = chip_smoke.make_values(50, 200, 7, 0)
        assert np.array_equal(a, chip_smoke.make_values(50, 200, 7, 0))
        assert not np.array_equal(a, chip_smoke.make_values(50, 200, 8, 0))
        assert not np.array_equal(a, chip_smoke.make_values(50, 200, 7, 1))
        assert chip_smoke.request_table(50, 24, 7) \
            == chip_smoke.request_table(50, 24, 7)

    def test_tsbs_shape(self):
        fleet = chip_smoke.make_fleet(300, 1)
        assert all(tuple(t) == chip_smoke.TAG_KEYS for t in fleet)
        assert [t["hostname"] for t in fleet[:2]] == ["host_0", "host_1"]
        assert all(t["datacenter"].startswith(t["region"]) for t in fleet)
        vals = chip_smoke.make_values(300, 500, 1, 0)
        assert vals.dtype == np.int64
        assert vals.min() >= 0 and vals.max() <= 100
        assert np.abs(np.diff(vals, axis=1)).max() <= 5   # a walk, not noise
        ts = chip_smoke.timestamps(500)
        assert ts[0] == chip_smoke.EPOCH_S and set(np.diff(ts)) == {10}

    def test_full_size_table_matches_the_issue(self):
        table = {r["name"]: r for r in chip_smoke.request_table(4000, 24, 1)}
        assert table["single-groupby-1-1-1"]["points"] == 360
        assert table["single-groupby-1-8-1"]["points"] == 2880
        assert table["region-sum-2h"]["points"] == 2_880_000
        assert table["region-rate-2h"]["points"] == 2_880_000
        assert table["datacenter-p99-5h"]["points"] == 7_200_000
        assert table["double-groupby-1-12h"]["points"] == 17_280_000
        assert table["double-groupby-1-24h"]["points"] == 34_560_000


class TestReferenceAgreesWithTheLibrary:
    """Every request shape of the table, tiny fleet, in-process: the
    smoke's numpy reference against the library's own answer."""

    HOSTS, HOURS = 30, 3

    @pytest.fixture(scope="class")
    def loaded(self):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.utils.config import Config
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
        fleet = chip_smoke.make_fleet(self.HOSTS, 3)
        ts = chip_smoke.timestamps(self.HOURS * 360)
        vals = chip_smoke.make_values(self.HOSTS, len(ts), 3, 0)
        for h, tags in enumerate(fleet):
            tsdb.add_points_bulk([
                {"metric": "cpu.usage_user", "timestamp": int(t),
                 "value": int(v), "tags": tags}
                for t, v in zip(ts, vals[h])])
        return tsdb, fleet, ts, vals

    def test_every_request_shape(self, loaded):
        from opentsdb_tpu.models import TSQuery, parse_m_subquery
        tsdb, fleet, ts, vals = loaded
        table = chip_smoke.request_table(self.HOSTS, self.HOURS, 3)
        assert len(table) == 6      # 12 h and 24 h collapse at 3 h
        for req in table:
            q = TSQuery(start=str(req["start"]), end=str(req["end"]),
                        queries=[parse_m_subquery(req["m"])])
            q.validate()
            got = chip_smoke.parse_answer(
                [r.to_json() for r in tsdb.new_query_runner().run(q)],
                req["group_by"])
            want = chip_smoke.ref_query(fleet, ts, vals, req)
            assert chip_smoke.compare(got, want) is None, req["name"]
            assert len(want) >= 1

    def test_compare_catches_a_wrong_answer(self, loaded):
        _, fleet, ts, vals = loaded
        req = chip_smoke.request_table(self.HOSTS, self.HOURS, 3)[3]
        assert req["name"] == "region-sum-2h"
        want = chip_smoke.ref_query(fleet, ts, vals, req)
        group = sorted(want)[0]
        wts, wval = want[group]
        off = dict(want)
        off[group] = (wts, wval * (1 + 1e-8))
        assert "reference" in chip_smoke.compare(off, want)
        off[group] = (wts[:-1], wval[:-1])
        assert "timestamps differ" in chip_smoke.compare(off, want)
        off.pop(group)
        assert "groups differ" in chip_smoke.compare(off, want)

    def test_percentile_is_commons_math_legacy(self):
        col = np.array([1.0, 2.0, 3.0, 4.0, 5.0])
        assert chip_smoke.ref_percentile(col, 50) == 3.0
        assert chip_smoke.ref_percentile(col, 99) == 5.0    # pos >= n
        assert chip_smoke.ref_percentile(col, 10) == 1.0    # pos < 1
        assert chip_smoke.ref_percentile(col, 40) == pytest.approx(2.4)


class TestCompileCachePlacement:
    """One rule, one place (opentsdb_tpu/ops/__init__.py): env set ->
    code sets nothing; unset -> a fixed directory in the checkout; the
    tests' directory is never the product's nor in the copied tree."""

    PROBE = ("import opentsdb_tpu.ops as ops, jax; "
             "print(jax.config.jax_compilation_cache_dir); "
             "print(ops.COMPILE_CACHE_DIR)")

    def _probe(self, env_dir):
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env["JAX_PLATFORMS"] = "cpu"
        if env_dir is not None:
            env["JAX_COMPILATION_CACHE_DIR"] = env_dir
        out = subprocess.run([sys.executable, "-c", self.PROBE], cwd=REPO,
                             env=env, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr[-1000:]
        return out.stdout.split()

    def test_unset_env_uses_the_fixed_checkout_directory(self):
        active, product = self._probe(None)
        assert active == product == os.path.join(REPO, ".jax_cache")

    def test_set_env_wins_and_code_sets_nothing(self, tmp_path):
        active, product = self._probe(str(tmp_path / "elsewhere"))
        assert active == str(tmp_path / "elsewhere") != product

    def test_tests_keep_their_own_directory(self):
        import jax

        from opentsdb_tpu import ops
        mine = jax.config.jax_compilation_cache_dir
        assert mine == os.environ["JAX_COMPILATION_CACHE_DIR"]
        # never the product's, and never inside the tree the chip tool
        # copies to the chip machine
        assert os.path.realpath(mine) != os.path.realpath(
            ops.COMPILE_CACHE_DIR)
        assert not os.path.realpath(mine).startswith(
            os.path.realpath(REPO) + os.sep)
        assert ".jax_cache/" in open(
            os.path.join(REPO, ".gitignore")).read().split()
        assert ".jax_cache/" in open(
            os.path.join(REPO, ".chiprunignore")).read().split()


class TestDaemonNamesItsDevice:
    def test_device_report_shape(self):
        from opentsdb_tpu.obs import jaxprof
        report = jaxprof.device_report()
        assert report["platform"] == "cpu"
        assert report["kind"] and report["count"] == len(report["memory"])
        assert set(report["memory"][0]) == {
            "id", "bytesInUse", "peakBytesInUse", "bytesLimit"}

    def test_daemon_logs_the_device_and_dies_without_a_backend(
            self, monkeypatch, caplog):
        import logging

        from opentsdb_tpu.obs import jaxprof
        from opentsdb_tpu.tools import tsd_main
        with caplog.at_level(logging.INFO, logger="tsd.device"):
            assert tsd_main.log_device() is True
        assert "platform=cpu device_kind=" in caplog.text
        assert "bytes_in_use=" in caplog.text

        def no_backend():
            raise RuntimeError("Unable to initialize backend 'tpu'")
        monkeypatch.setattr(jaxprof, "device_report", no_backend)
        # the daemon exits before it opens a store or binds a port
        monkeypatch.setattr(tsd_main, "make_tsdb_from_args",
                            lambda args: pytest.fail("TSDB was built"))
        assert tsd_main.main(["--port", "1"]) == 1

    def test_put_parser_stat_tells_native_from_fallback(self):
        from opentsdb_tpu.core import TSDB
        from opentsdb_tpu.tsd.http import HttpRequest
        from opentsdb_tpu.tsd.rpc_manager import RpcManager
        from opentsdb_tpu.utils.config import Config
        tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True}))
        mgr = RpcManager(tsdb)

        def http(method, uri, body=b""):
            q = mgr.handle_http(HttpRequest(method=method, uri=uri,
                                            headers={}, body=body),
                                remote="127.0.0.1:9")
            return q.response.status, q.response.body

        def put(host):
            return http("POST", "/api/put", json.dumps([
                {"metric": "m", "timestamp": 1451606400 + i, "value": i,
                 "tags": {"h": host}} for i in range(3)]).encode())[0]

        def parsers():
            return {r["tags"]["parser"]: r["value"]
                    for r in json.loads(http("GET", "/api/stats")[1])
                    if r["metric"] == "tsd.put.parser"}
        assert put("a") == 204
        assert parsers() == {"native": 1, "python": 0}
        tsdb.add_points_bulk_native = lambda body: None     # library gone
        assert put("b") == 204
        assert parsers() == {"native": 1, "python": 1}

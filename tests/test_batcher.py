"""Fused multi-query dispatch + per-tenant fair share (ISSUE 14).

Pins the batching contract end to end:

  * batched == solo BITWISE per member on integer data, across the
    kernel families the stacked program serves (downsample fns, rate,
    grouped), at Q > 1 through the real rendezvous;
  * bucket keying: shape/dtype mismatches land in different buckets;
  * one member's deadline expiry leaves the batch without poisoning
    its siblings;
  * weighted deficit-round-robin fairness in the admission gate
    (weights honored, per-tenant inflight caps, per-tenant queue
    bounds, single-tenant FIFO preserved, audit snapshot);
  * explain parity + fingerprint for the `batched` routing arm (the
    corpus pin rides tests/test_explain.py over PLAN_CORPUS.json);
  * batched executions stay OUT of the predicted-vs-actual ring;
  * the health engine's cross-tenant starvation invariant;
  * tools/bench_qps.py: >= 2x dispatch-layer uplift (slow re-measure).

Mesh stays off throughout: the batcher serves the single-device route
(plan_decision never batches a mesh plan).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from opentsdb_tpu.core import TSDB                       # noqa: E402
from opentsdb_tpu.models.tsquery import (                # noqa: E402
    TSQuery, parse_m_subquery)
from opentsdb_tpu.ops.downsample import FixedWindows    # noqa: E402
from opentsdb_tpu.ops.pipeline import (                  # noqa: E402
    DownsampleStep, PipelineSpec, run_group_pipeline)
from opentsdb_tpu.query.batcher import (                 # noqa: E402
    DispatchBatcher, bucket_key)
from opentsdb_tpu.query.limits import (                  # noqa: E402
    Deadline, QueryException)
from opentsdb_tpu.tsd.admission import AdmissionGate     # noqa: E402
from opentsdb_tpu.tsd.http import HttpRequest            # noqa: E402
from opentsdb_tpu.tsd.rpc_manager import RpcManager      # noqa: E402
from opentsdb_tpu.utils.config import Config             # noqa: E402

BASE = 1_356_998_400_000


# --------------------------------------------------------------------- #
# Rendezvous harness                                                    #
# --------------------------------------------------------------------- #

class _FakeGate:
    """Concurrent-demand stub: the batcher holds its coalesce window
    only when the admission gate shows other queries in flight."""

    def __init__(self, in_flight=8):
        self._lock = threading.Lock()
        self.in_flight = in_flight

    def _depth_locked(self):
        return 0


def make_batcher(hold_ms=100, max_q=16, demand=8, enable=True):
    cfg = Config({"tsd.query.batch.enable": str(enable).lower(),
                  "tsd.query.batch.hold_ms": str(hold_ms),
                  "tsd.query.batch.max_q": str(max_q)})

    class _Tsdb:
        pass

    tsdb = _Tsdb()
    tsdb._admission_gate = _FakeGate(demand)
    return DispatchBatcher(cfg, tsdb=tsdb)


def member_operands(rng, s, n, w, gid_groups=1, int_vals=True):
    ts = np.sort(rng.integers(0, w * 1000, (s, n))).astype(np.int64)
    if int_vals:
        val = rng.integers(-50, 50, (s, n)).astype(np.float64)
    else:
        val = rng.standard_normal((s, n))
    mask = np.ones((s, n), bool)
    gid = np.sort(rng.integers(0, gid_groups, s)).astype(np.int64)
    return ts, val, mask, gid


def spec_for(ds_fn, rate, w):
    win = FixedWindows(1000, 0, w)
    wspec, wargs = win.split()
    from opentsdb_tpu.ops.rate import RateOptions
    return PipelineSpec(
        aggregator="sum",
        downsample=DownsampleStep(ds_fn, wspec, "none", 0.0),
        rate=RateOptions() if rate else None,
        int_mode=False, rows_sorted=True), wargs


def submit_concurrently(batcher, spec, members, g_pad, wargs,
                        deadlines=None):
    """Drive Q members through the rendezvous from Q threads; returns
    ([result | exception per member], infos)."""
    results = [None] * len(members)
    infos = [None] * len(members)

    def worker(i):
        ts, val, mask, gid = members[i]
        dl = deadlines[i] if deadlines else None
        try:
            out, info = batcher.submit(spec, ts, val, mask, gid,
                                       g_pad, wargs, False, dl)
            results[i] = tuple(np.asarray(x) for x in out)
            infos[i] = info
        except Exception as e:              # noqa: BLE001 — test capture
            results[i] = e

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(members))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    return results, infos


class TestStackedBitwise:
    """Batched == solo bitwise per member on integer data, per kernel
    family (the rollup-lane integer-exactness contract applied to the
    stacked member axis)."""

    @pytest.mark.parametrize("ds_fn,rate,groups", [
        ("avg", False, 1),
        ("sum", False, 1),
        ("max", False, 1),          # extreme kernel axis
        ("count", False, 1),
        ("avg", True, 1),           # rate over the grid
        ("avg", False, 4),          # grouped cross-series reduce
    ])
    def test_family_bitwise(self, ds_fn, rate, groups):
        rng = np.random.default_rng(42)
        s, n, w = 4, 256, 16
        spec, wargs = spec_for(ds_fn, rate, w)
        members = [member_operands(rng, s, n, w, gid_groups=groups)
                   for _ in range(4)]
        solos = [tuple(np.asarray(x) for x in run_group_pipeline(
            spec, m[0], m[1], m[2], m[3], groups, wargs))
            for m in members]
        batcher = make_batcher()
        results, infos = submit_concurrently(batcher, spec, members,
                                             groups, wargs)
        assert all(i and i["q"] == 4 for i in infos), infos
        for got, ref in zip(results, solos):
            assert not isinstance(got, Exception), got
            for a, b in zip(got, ref):
                assert a.dtype == b.dtype
                assert np.array_equal(a, b, equal_nan=True)

    def test_q1_falls_back_to_the_solo_program(self):
        rng = np.random.default_rng(1)
        spec, wargs = spec_for("avg", False, 16)
        m = member_operands(rng, 2, 128, 16)
        batcher = make_batcher(demand=1)     # uncontended: no hold
        t0 = time.monotonic()
        out, info = batcher.submit(spec, m[0], m[1], m[2], m[3], 1,
                                   wargs, False, None)
        assert info == {"q": 1, "stacked": False,
                        "waitMs": info["waitMs"]}
        # zero hold for an uncontended query (well under the 100 ms
        # window; generous bound for slow CI)
        assert time.monotonic() - t0 < 5.0
        ref = run_group_pipeline(spec, m[0], m[1], m[2], m[3], 1,
                                 wargs)
        for a, b in zip(out, ref):
            assert np.array_equal(np.asarray(a), np.asarray(b),
                                  equal_nan=True)


class TestBucketKeying:
    def test_shape_and_dtype_split_buckets(self):
        spec, wargs = spec_for("avg", False, 16)
        rng = np.random.default_rng(3)
        a = member_operands(rng, 2, 128, 16)
        b = member_operands(rng, 4, 128, 16)          # different S
        c = member_operands(rng, 2, 128, 16, int_vals=False)
        c = (a[0], a[1].astype(np.int64), a[2], a[3])  # different dtype
        keys = {bucket_key(spec, 1, m[0], m[1], np.asarray(m[3]),
                           wargs, False)
                for m in (a, b, c)}
        assert len(keys) == 3

    def test_dispatch_events_and_metrics(self):
        rng = np.random.default_rng(4)
        spec, wargs = spec_for("avg", False, 16)
        members = [member_operands(rng, 2, 128, 16) for _ in range(3)]
        batcher = make_batcher()
        _results, infos = submit_concurrently(batcher, spec, members,
                                              1, wargs)
        assert all(i["q"] == 3 for i in infos)
        stats = batcher.collect_stats()
        assert stats["tsd.query.batch.stacked_dispatches"] == 1.0
        assert stats["tsd.query.batch.stacked_members"] == 3.0


class TestDeadlines:
    def test_expired_member_leaves_without_poisoning_siblings(self):
        rng = np.random.default_rng(5)
        spec, wargs = spec_for("avg", False, 16)
        members = [member_operands(rng, 2, 128, 16) for _ in range(3)]
        dead = Deadline(timeout_ms=0.0001)
        time.sleep(0.01)
        assert dead.expired()
        deadlines = [None, dead, None]
        solos = [tuple(np.asarray(x) for x in run_group_pipeline(
            spec, m[0], m[1], m[2], m[3], 1, wargs))
            for m in members]
        batcher = make_batcher(hold_ms=200)
        results, infos = submit_concurrently(
            batcher, spec, members, 1, wargs, deadlines=deadlines)
        assert isinstance(results[1], QueryException)
        for i in (0, 2):
            assert not isinstance(results[i], Exception), results[i]
            assert infos[i]["q"] == 2       # the dead member dropped
            for a, b in zip(results[i], solos[i]):
                assert np.array_equal(a, b, equal_nan=True)

    def test_expired_leader_is_dropped_and_still_serves_followers(self):
        """The race the concurrent test only sometimes lands on, pinned
        deterministically: the EXPIRED member submits first and becomes
        the bucket leader.  Winning the submit race must not outrank
        the deadline — the leader dispatches for its live followers,
        then raises its own 413/503 instead of serving an answer the
        deadline already refused."""
        rng = np.random.default_rng(6)
        spec, wargs = spec_for("avg", False, 16)
        members = [member_operands(rng, 2, 128, 16) for _ in range(3)]
        dead = Deadline(timeout_ms=0.0001)
        time.sleep(0.01)
        assert dead.expired()
        solos = [tuple(np.asarray(x) for x in run_group_pipeline(
            spec, m[0], m[1], m[2], m[3], 1, wargs))
            for m in members]
        batcher = make_batcher(hold_ms=500)
        results = [None] * 3
        infos = [None] * 3

        def worker(i, dl):
            ts, val, mask, gid = members[i]
            try:
                out, info = batcher.submit(spec, ts, val, mask, gid,
                                           1, wargs, False, dl)
                results[i] = tuple(np.asarray(x) for x in out)
                infos[i] = info
            except Exception as e:          # noqa: BLE001 — test capture
                results[i] = e

        # the dead member first, ALONE, so it owns the bucket as leader
        t0 = threading.Thread(target=worker, args=(0, dead))
        t0.start()
        for _ in range(500):
            with batcher._lock:
                if batcher._buckets:
                    break
            time.sleep(0.002)
        with batcher._lock:
            assert batcher._buckets, "leader never opened a bucket"
        rest = [threading.Thread(target=worker, args=(i, None))
                for i in (1, 2)]
        for t in rest:
            t.start()
        for t in [t0] + rest:
            t.join(60)
        assert isinstance(results[0], QueryException)
        for i in (1, 2):
            assert not isinstance(results[i], Exception), results[i]
            for a, b in zip(results[i], solos[i]):
                assert np.array_equal(a, b, equal_nan=True)


# --------------------------------------------------------------------- #
# Fair share (weighted DRR)                                             #
# --------------------------------------------------------------------- #

def make_gate(**over):
    props = {"tsd.query.admission.permits": "1",
             "tsd.query.admission.queue_limit": "64",
             "tsd.query.admission.max_wait_ms": "0"}
    props.update({k: str(v) for k, v in over.items()})
    return AdmissionGate(Config(props))


def drain_order(gate, plan, cost_ms=50.0):
    """Enqueue (tenant, n) entries behind a held permit, release, and
    observe the drain order."""
    order = []
    lock = threading.Lock()
    blocker = gate.acquire(None, "interactive")

    def worker(tenant):
        p = gate.acquire(None, "interactive", tenant=tenant,
                         cost_ms=cost_ms)
        with lock:
            order.append(tenant)
        time.sleep(0.002)
        p.release()

    threads = []
    for tenant, n in plan:
        for _ in range(n):
            th = threading.Thread(target=worker, args=(tenant,))
            th.start()
            threads.append(th)
            time.sleep(0.005)        # deterministic enqueue order
    time.sleep(0.2)
    blocker.release()
    for th in threads:
        th.join(30)
    return order


class TestFairShare:
    def test_weighted_drain_ratio(self):
        gate = make_gate(**{"tsd.query.tenant.weights": "a:2,b:1"})
        order = drain_order(gate, [("a", 9), ("b", 9)])
        # weight 2 drains ~2 'a' entries per 'b' while both are
        # backlogged: in the first 9 drains 'a' gets a strict majority
        first = order[:9]
        assert first.count("a") >= 5, order
        assert set(order) == {"a", "b"} and len(order) == 18

    def test_single_tenant_reduces_to_fifo(self):
        gate = make_gate()
        order = []
        lock = threading.Lock()
        blocker = gate.acquire(None, "interactive")
        seq = list(range(8))

        def worker(i):
            p = gate.acquire(None, "interactive", cost_ms=10.0)
            with lock:
                order.append(i)
            p.release()

        threads = []
        for i in seq:
            th = threading.Thread(target=worker, args=(i,))
            th.start()
            threads.append(th)
            time.sleep(0.01)
        time.sleep(0.1)
        blocker.release()
        for th in threads:
            th.join(30)
        assert order == seq

    def test_per_tenant_inflight_cap(self):
        gate = make_gate(**{"tsd.query.admission.permits": "4",
                            "tsd.query.tenant.max_inflight": "1",
                            "tsd.query.admission.max_wait_ms": "200"})
        p1 = gate.acquire(None, "interactive", tenant="a")
        # 'a' is at its cap: a second 'a' queues and sheds at max_wait
        # even though global permits are free
        from opentsdb_tpu.tsd.admission import ShedError
        with pytest.raises(ShedError):
            gate.acquire(None, "interactive", tenant="a")
        # another tenant admits immediately
        p2 = gate.acquire(None, "interactive", tenant="b")
        p2.release()
        p1.release()
        # cap freed: 'a' admits again
        gate.acquire(None, "interactive", tenant="a").release()

    def test_per_tenant_queue_bound_sheds_storm_not_victim(self):
        gate = make_gate(**{"tsd.query.admission.queue_limit": "2",
                            "tsd.query.admission.max_wait_ms": "0"})
        from opentsdb_tpu.tsd.admission import ShedError
        blocker = gate.acquire(None, "interactive")
        storm_waiters = []
        for _ in range(2):
            th = threading.Thread(
                target=lambda: gate.acquire(None, "interactive",
                                            tenant="storm").release())
            th.start()
            storm_waiters.append(th)
        time.sleep(0.2)              # both queued
        with pytest.raises(ShedError):
            gate.acquire(None, "interactive", tenant="storm")
        # the victim's own backlog is empty: it still queues (and
        # drains once the blocker releases)
        got = []
        th = threading.Thread(
            target=lambda: got.append(gate.acquire(
                None, "interactive", tenant="victim")))
        th.start()
        time.sleep(0.1)
        blocker.release()
        th.join(30)
        for w in storm_waiters:
            w.join(30)
        assert got and got[0] is not None
        got[0].release()
        snap = gate.tenant_snapshot()
        assert snap["tenants"]["storm"]["refused"] == 1
        assert snap["tenants"]["victim"]["refused"] == 0
        assert snap["tenants"]["victim"]["admitted"] == 1

    def test_fair_share_off_collapses_identities(self):
        gate = make_gate(**{"tsd.query.tenant.fair_share": "false"})
        p = gate.acquire(None, "interactive", tenant="alice")
        assert p.tenant == "alice"           # public label preserved
        assert gate._tenant_inflight == {"default": 1}
        p.release()
        assert gate._tenant_inflight == {}

    def test_snapshot_shape(self):
        gate = make_gate(**{"tsd.query.tenant.weights": "a:3"})
        p = gate.acquire(None, "interactive", tenant="a")
        snap = gate.tenant_snapshot()
        assert snap["fairShare"] is True
        assert snap["tenants"]["a"]["weight"] == 3.0
        assert snap["tenants"]["a"]["inflight"] == 1
        p.release()


# --------------------------------------------------------------------- #
# Routing, parity, ring exclusion                                       #
# --------------------------------------------------------------------- #

def _manager(**cfg):
    props = {"tsd.core.auto_create_metrics": True,
             "tsd.query.mesh.enable": "false",
             "tsd.rollup.interval": "0",
             "tsd.stats.interval": "0",
             "tsd.query.device_cache.enable": "false"}
    props.update({k: str(v) for k, v in cfg.items()})
    tsdb = TSDB(Config(props))
    return tsdb, RpcManager(tsdb)


def feed(tsdb, metric, series=2, points=100, cadence_s=15):
    for h in range(series):
        tags = {"host": "h%d" % h}
        for k in range(points):
            tsdb.add_point(metric, BASE // 1000 + k * cadence_s,
                           float((k * 7 + h) % 101), tags)


def ask(mgr, uri):
    req = HttpRequest(method="GET", uri=uri, headers={})
    q = mgr.handle_http(req, remote="127.0.0.1:9")
    raw = q.response.body
    text = raw.decode() if isinstance(raw, (bytes, bytearray)) else raw
    return q.response.status, json.loads(text)


class TestBatchedRouting:
    def test_explain_parity_and_fingerprint(self):
        """The `batched` arm cannot drift: explain's path/fingerprint
        equals the executed plan event's (the test_explain
        assert_parity pattern, applied to the new arm)."""
        tsdb, mgr = _manager()
        feed(tsdb, "bt.small")
        try:
            q = "start=%d&end=%d&m=sum:30s-avg:bt.small" % (
                BASE // 1000, BASE // 1000 + 100 * 15)
            status, rep = ask(mgr, "/api/query/explain?" + q)
            assert status == 200, rep
            seg = rep["subQueries"][0]["segments"][0]
            assert seg["path"] == "batched"
            assert seg["costmodel"]                 # modes still priced
            status, _ = ask(mgr, "/api/query?" + q)
            assert status == 200
            plans = [e for e in tsdb.flightrec.events()
                     if e["kind"] == "plan"]
            assert plans
            event = plans[-1]
            assert event["path"] == "batched"
            assert event["fingerprint"] == seg["fingerprint"]
            assert event["batch"]["q"] == 1         # uncontended: solo
            assert event["batch"]["stacked"] is False
        finally:
            tsdb.shutdown()

    def test_compute_bound_plan_declines_to_dispatch_now(self):
        """The coalesce line is costmodel-priced, not a static batch
        size: a compute-heavy shape prices past the amortize factor
        and keeps the ordinary path."""
        tsdb, mgr = _manager()
        feed(tsdb, "bt.big", series=2, points=6000, cadence_s=1)
        try:
            q = "start=%d&end=%d&m=sum:2s-avg:bt.big" % (
                BASE // 1000, BASE // 1000 + 6000)
            status, rep = ask(mgr, "/api/query/explain?" + q)
            assert status == 200, rep
            seg = rep["subQueries"][0]["segments"][0]
            assert seg["path"] in ("host_lane", "resident"), seg["path"]
        finally:
            tsdb.shutdown()

    def test_disabled_config_restores_pre_batching_routing(self):
        tsdb, mgr = _manager(**{"tsd.query.batch.enable": "false"})
        feed(tsdb, "bt.off")
        try:
            q = "start=%d&end=%d&m=sum:30s-avg:bt.off" % (
                BASE // 1000, BASE // 1000 + 100 * 15)
            status, rep = ask(mgr, "/api/query/explain?" + q)
            seg = rep["subQueries"][0]["segments"][0]
            assert seg["path"] == "host_lane"
        finally:
            tsdb.shutdown()

    def test_a_batched_pipeline_span_carries_its_decisions(self):
        """The batched arm annotates the plan's costmodel decisions
        itself, beside its batch tag, and no tracer sync runs."""
        tsdb, mgr = _manager(**{"tsd.trace.enable": "true"})
        feed(tsdb, "bt.ring")
        try:
            q = "start=%d&end=%d&m=sum:30s-avg:bt.ring&show_stats" % (
                BASE // 1000, BASE // 1000 + 100 * 15)
            status, rep = ask(mgr, "/api/query?" + q)
            assert status == 200
            tree = [e for e in rep if "statsSummary" in e][0][
                "statsSummary"]["trace"]

            def find(node):
                out = [node] if node["name"] == "pipeline" else []
                for c in node.get("spans", []):
                    out += find(c)
                return out
            (pipe,) = find(tree)
            assert "batch" in pipe["tags"]
            assert "group" in pipe["tags"]["costmodel"]
            assert "deviceMs" not in pipe
        finally:
            tsdb.shutdown()


# --------------------------------------------------------------------- #
# Health: cross-tenant starvation                                       #
# --------------------------------------------------------------------- #

class TestTenantHealth:
    def test_starved_tenant_reads_failing(self):
        from opentsdb_tpu.obs.registry import REGISTRY
        tsdb, _mgr = _manager()
        try:
            engine = tsdb.health
            assert "tenant" in engine.SUBSYSTEMS
            engine.evaluate()                       # baseline pass
            demand = REGISTRY.counter(
                "tsd.query.tenant.demand",
                "Queries arriving at admission, by clamped tenant")
            admitted = REGISTRY.counter(
                "tsd.query.tenant.admitted",
                "Queries admitted through the gate, by clamped tenant")
            for _ in range(100):
                demand.labels(tenant="ht-served").inc()
                demand.labels(tenant="ht-starved").inc()
                admitted.labels(tenant="ht-served").inc()
            verdicts = engine.evaluate()
            assert verdicts["tenant"]["level"] == "failing", verdicts
            # a later balanced window heals the verdict
            for _ in range(100):
                demand.labels(tenant="ht-served").inc()
                demand.labels(tenant="ht-starved").inc()
                admitted.labels(tenant="ht-served").inc()
                admitted.labels(tenant="ht-starved").inc()
            verdicts = engine.evaluate()
            assert verdicts["tenant"]["level"] == "ok", verdicts
        finally:
            tsdb.shutdown()


# --------------------------------------------------------------------- #
# Bench artifact                                                        #
# --------------------------------------------------------------------- #

class TestBenchArtifact:
    @pytest.mark.slow
    def test_dispatch_layer_uplift_reproduces(self, tmp_path):
        """ISSUE 14 acceptance: >= 2x sustained throughput uplift at
        the dispatch layer the batcher amortizes (the end-to-end HTTP
        phases are Python-bound on 2-core CI boxes — see the artifact
        note — and run in the standing soak, not here)."""
        out = tmp_path / "bench_qps.json"
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools",
                                          "bench_qps.py"),
             "--skip-e2e", "--platform", "cpu", "--reps", "200",
             "--out", str(out)],
            capture_output=True, text=True, timeout=600, cwd=REPO,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
        assert proc.returncode == 0, proc.stdout + proc.stderr
        bench = json.loads(out.read_text())
        assert bench["dispatchLayer"]["upliftPerMember"] >= 2.0, bench

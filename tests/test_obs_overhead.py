"""Traced-serving overhead pin: observability must stay nearly free.

The pattern of tests/test_sanitizer_overhead.py, pointed at tsdbobs: the
SAME RpcManager serves the same warmed query stream with tracing +
metrics off (tsd.trace.enable=false) and on (the default, device timing
included), in-process so jit caches, data, and the interpreter state are
identical.  Traced wall time must stay within 1.15x of untraced.

Measurement discipline for a 15% bound on a shared runner: both arms
warm up first, then run as alternating batches and compare the MINIMUM
batch time per arm — scheduler noise only ever adds time, so min-of-3
is the stable estimator — with a small absolute floor so a
microsecond-level baseline cannot fail on jitter alone.

If this starts failing, profile obs/trace.py's stage()/timed_stage()
before even thinking about relaxing the bound: a tracer nobody can
afford to leave on observes nothing.
"""

from __future__ import annotations

import time

import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.tsd.http import HttpRequest
from opentsdb_tpu.tsd.rpc_manager import RpcManager
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400
MAX_RATIO = 1.15
NOISE_FLOOR_S = 0.25
QUERIES_PER_BATCH = 30
BATCHES = 4
WARMUP = 5


@pytest.fixture
def served():
    tsdb = TSDB(Config({"tsd.core.auto_create_metrics": True,
                        "tsd.query.mesh.enable": False}))
    for host in ("web01", "web02", "web03", "web04"):
        for i in range(500):
            tsdb.add_point("ovh.cpu", BASE + i * 10, float(i),
                           {"host": host})
    return tsdb, RpcManager(tsdb)


URI = ("/api/query?start=%d&end=%d&m=sum:30s-avg:ovh.cpu{host=*}"
       % (BASE, BASE + 5_000))


def _serve(manager) -> None:
    response = manager.handle_http(
        HttpRequest(method="GET", uri=URI), remote="127.0.0.1:9").response
    assert response.status == 200


def _batch(manager) -> float:
    start = time.perf_counter()
    for _ in range(QUERIES_PER_BATCH):
        _serve(manager)
    return time.perf_counter() - start


def test_traced_serving_stays_within_1_15x_of_untraced(served):
    tsdb, manager = served
    # warm both arms: jit compiles and lazy imports must not bill
    # either side
    for enabled in (False, True, False, True):
        tsdb.config.override_config("tsd.trace.enable", enabled)
        for _ in range(WARMUP):
            _serve(manager)
    plain = []
    traced = []
    for _ in range(BATCHES):        # alternate: shared noise cancels
        tsdb.config.override_config("tsd.trace.enable", False)
        plain.append(_batch(manager))
        tsdb.config.override_config("tsd.trace.enable", True)
        traced.append(_batch(manager))
    best_plain = min(plain)
    best_traced = min(traced)
    budget = MAX_RATIO * max(best_plain, NOISE_FLOOR_S)
    assert best_traced < budget, (
        "traced+metered serving took %.3fs vs %.3fs untraced per "
        "%d-query batch (budget %.3fs) — tsdbobs overhead blew the "
        "1.15x pin" % (best_traced, best_plain, QUERIES_PER_BATCH,
                       budget))

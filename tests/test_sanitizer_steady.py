"""Steady-state query serving under the JAX compile/sync sanitizer.

The acceptance check behind tsdbsan's third detector: once a query
shape has been served (warmup), serving the SAME workload again must
trigger ZERO kernel compiles and ZERO unsanctioned device->host
transfers — the "as fast as the hardware allows" north star dies the
day a hot path quietly recompiles or syncs per request.

Runs in plain tier-1 (self-contained: it arms its own JaxSanitizer
instance, no TSDBSAN env needed) and doubles as the jax leg of the
`tools/sanitize/run.py --subset tier1` sanitized run.  CPU-only; the
mesh path is disabled so the steady-state compile counts describe one
single-device program per shape.
"""

from __future__ import annotations

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from opentsdb_tpu.core import TSDB  # noqa: E402
from opentsdb_tpu.models import TSQuery, parse_m_subquery  # noqa: E402
from opentsdb_tpu.utils.config import Config  # noqa: E402
from tools.sanitize.jax_san import (  # noqa: E402
    JaxSanitizer, check_cache_growth, snapshot_kernel_caches)
from tools.sanitize.report import REPORTER  # noqa: E402

BASE = 1_356_998_400


@pytest.fixture
def tsdb():
    t = TSDB(Config({
        "tsd.core.auto_create_metrics": True,
        # one single-device program per shape: the recompile detector
        # counts those, not the mesh route's per-shard programs
        "tsd.query.mesh.enable": False,
    }))
    for host in ("web01", "web02", "web03", "web04"):
        for i in range(60):
            t.add_point("steady.cpu", BASE + i * 10, float(i),
                        {"host": host})
    return t


def _serve(tsdb, m="sum:10s-avg:steady.cpu"):
    q = TSQuery(start=str(BASE), end=str(BASE + 600),
                queries=[parse_m_subquery(m)])
    q.validate()
    return tsdb.new_query_runner().run(q)


@pytest.fixture
def clean_reporter():
    saved = REPORTER.raw_findings()
    REPORTER.clear()
    yield REPORTER
    REPORTER.clear()
    REPORTER.restore(saved)


class TestSteadyStateServing:
    def test_steady_serving_has_zero_recompiles_and_syncs(
            self, tsdb, clean_reporter):
        jsan = JaxSanitizer()
        jsan.start()
        try:
            for _ in range(3):          # warmup: compiles expected
                _serve(tsdb)
            jsan.mark_steady()
            snap = snapshot_kernel_caches()
            for _ in range(5):          # steady: zero tolerance
                results = _serve(tsdb)
                assert results, "steady query must keep answering"
            grown = check_cache_growth(snap)
        finally:
            jsan.stop()
        steady_compiles = {k: v["steady"]
                          for k, v in jsan.compiles.items()
                          if v["steady"]}
        bad = [f.render() for f in clean_reporter.findings()
               if f.rule in ("san-recompile-after-warmup",
                             "san-host-sync")]
        assert not steady_compiles and not grown and not bad, (
            "steady-state serving is not compile/sync clean:\n"
            "compiles=%s grown=%s\n%s"
            % (steady_compiles, grown, "\n".join(bad)))

    def test_detector_is_alive_a_new_shape_in_steady_fires(
            self, tsdb, clean_reporter):
        """Anti-blindness control: serving a NEVER-SEEN query shape in
        the steady phase MUST produce compile events — proves the
        previous test's zero is a real zero, not a dead detector."""
        jsan = JaxSanitizer()
        jsan.start()
        try:
            _serve(tsdb)
            jsan.mark_steady()
            # a different downsample window -> different static args ->
            # the pipeline must recompile
            _serve(tsdb, "sum:30s-max:steady.cpu")
        finally:
            jsan.stop()
        steady = sum(v["steady"] for v in jsan.compiles.values())
        assert steady > 0, (
            "no compile events observed for a brand-new query shape — "
            "the recompile detector has gone blind")
        REPORTER.clear()        # the control's findings are expected

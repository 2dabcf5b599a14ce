"""The three per-layer metrics that read latattr's CPU-time counter
(`tsd_latattr_phase_cpu_ms_total`): data files only, read by the
`counter_ratio` reader from two counter snapshots in the shape
benchmark/daemon.counters() gives them."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark import readers  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")
PHASES = readers.PHASES

# cumulative ms per phase at the window's start and end: (cpu, wall)
BEFORE = {"parse": (10, 11), "admission_wait": (0, 1), "plan": (400, 500),
          "batch_rendezvous": (0, 0), "dispatch": (300, 400),
          "device_wait": (5, 900), "serialize": (80, 90),
          "flush": (700, 800)}
AFTER = {"parse": (30, 33), "admission_wait": (0, 2), "plan": (1000, 2500),
         "batch_rendezvous": (0, 0), "dispatch": (1200, 6400),
         "device_wait": (15, 5900), "serialize": (380, 400),
         "flush": (7000, 9000)}


def snapshot(phases: dict, queries: int) -> dict:
    snap = {"tsd_http_requests_total{route=api/query,status=200}": queries,
            "tsd_http_requests_total{route=api/diag,status=200}": 3 * queries,
            "tsd_latattr_requests_total": 4 * queries}
    for phase, (cpu, wall) in phases.items():
        snap["tsd_latattr_phase_cpu_ms_total{phase=%s}" % phase] = cpu
        snap["tsd_latattr_phase_ms_total{phase=%s}" % phase] = wall
    return snap


def value(name: str, before: dict, after: dict):
    spec = readers.load_layer(ROOT, name)
    assert spec["reader"]["kind"] == "counter_ratio"
    return readers.read(ROOT, spec, {"ctr_before": before,
                                     "ctr_after": after})


# plan: (1000-400) cpu over (2500-500) wall; dispatch: 900 over 6000;
# six phases (flush left out): 20+600+0+900+10+300 = 1830 ms over 10
EXPECTED = {"plan_cpu_share": 30.0, "dispatch_cpu_share": 15.0,
            "host_cpu_ms_per_req": 183.0}


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_layer_file_reads_its_value_from_two_snapshots(name):
    got = value(name, snapshot(BEFORE, 5), snapshot(AFTER, 15))
    assert got == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_nothing_is_reported_when_the_denominator_did_not_move(name):
    """No request in the window, or a program without the counter
    (the parent commit): None, and the line leaves the metric out."""
    still = snapshot(BEFORE, 5)
    assert value(name, still, dict(still)) is None
    no_counter = {k: v for k, v in snapshot(AFTER, 5).items()
                  if "phase_cpu_ms" not in k and "phase_ms" not in k}
    assert value(name, no_counter, dict(no_counter)) is None


def test_a_program_without_the_cpu_counter_raises_nothing():
    """The parent commit exports wall ms and requests but no CPU ms:
    the readers return a number (0) or None, and never raise."""
    def strip(snap):
        return {k: v for k, v in snap.items() if "phase_cpu_ms" not in k}
    for name in EXPECTED:
        got = value(name, strip(snapshot(BEFORE, 5)),
                    strip(snapshot(AFTER, 15)))
        assert got in (None, 0.0)

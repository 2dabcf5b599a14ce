"""Overhead guard: the sanitizer must stay cheap enough for tier-1.

Pins TSDBSAN=1 wall time at < 2x the unsanitized run over the most
concurrency-intensive subset file (tests/test_concurrency.py — real
threads, real locks, the densest instrumented-write traffic in the
tree).  If this starts failing, the write-interception fast path in
tools/sanitize/lockset.py has regressed: profile `_track` before even
thinking about relaxing the bound — a sanitizer nobody can afford to
run catches nothing.

A small absolute floor keeps the ratio stable on noisy runners: a
3-second baseline dominated by scheduler jitter must not fail a 5.9s
sanitized run that would pass on an idle machine.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SLICE = ["tests/test_concurrency.py"]
MAX_RATIO = 2.0
NOISE_FLOOR_S = 3.0


def _timed_run(sanitized: bool) -> float:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("TSDBSAN", None)
    if sanitized:
        env["TSDBSAN"] = "1"
    start = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-m", "not slow",
         "--continue-on-collection-errors", "-p", "no:cacheprovider",
         *SLICE],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    elapsed = time.monotonic() - start
    # the guard compares wall time, not verdicts (a failing test in the
    # slice is its own suite's business) — but a crash/usage
    # error (rc >= 2 without the plugin's findings-exit 3) would make
    # the timing meaningless
    assert proc.returncode in (0, 1, 3), proc.stdout + proc.stderr
    return elapsed


def test_sanitized_subset_wall_time_stays_under_2x():
    plain = _timed_run(sanitized=False)
    sanitized = _timed_run(sanitized=True)
    budget = MAX_RATIO * max(plain, NOISE_FLOOR_S)
    assert sanitized < budget, (
        "sanitized run took %.1fs vs %.1fs plain (budget %.1fs) — "
        "tsdbsan overhead blew the 2x tier-1 bound"
        % (sanitized, plain, budget))

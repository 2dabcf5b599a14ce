"""Test harness: force an 8-device virtual CPU platform before JAX loads.

Mirrors the reference's test stance (SURVEY.md §4): deterministic in-memory
storage + golden-value numeric tests, with multi-chip sharding validated on a
virtual device mesh (the driver separately dry-runs the real multi-chip path).
"""

import os

# Tests run on the CPU platform whatever the ambient environment points
# JAX at: a chip belongs to one process, and the suite must never be it.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
# Persistent compile cache: kernel tests compile ~4800 small programs, and
# the tier-1 time limit (ROADMAP.md) only holds with them cached — a cold
# run measured 1140 s against a warm 633 s.  Same rule as the product
# (opentsdb_tpu/ops/__init__.py): where JAX_COMPILATION_CACHE_DIR is set
# nothing here sets another.  Otherwise the tests keep their OWN fixed
# directory, never the product's <repo>/.jax_cache, and outside the tree
# the chip tool copies: what this machine's CPU compiled is never loaded
# by a daemon's host lane on the chip machine.  It stays at this path
# because the path is part of JAX's cache key (a copy elsewhere never
# hits) and the CI image ships it warm here.
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", "/tmp/jax_pytest_cache")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")

import sys  # noqa: E402

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# TSDBSAN=1 arms the runtime sanitizer (tools/sanitize) for the whole
# session: instrumented locks + write interception + deadlock watchdog.
# The plugin fails the session on error-level findings.
if os.environ.get("TSDBSAN", "") == "1":
    pytest_plugins = ["tools.sanitize.plugin"]

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); the "
        "standing CI soak runs these")


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def kernel_forms(monkeypatch):
    """Pin a kernel axis to one form for a test: `kernel_forms(search=
    "hier", scan="subblock2", extreme="subblock", group="sorted")`.

    Which form runs is a pure function of platform and shape
    (ops/downsample.py, ops/group_agg.py), and on the CPU backend that
    function never picks hier, compare_all, sorted, ... — yet every kept
    form needs its numeric tests here.  So the pin is made from the test
    side only, by patching the axis's chooser to a constant; a form the
    shape cannot take falls back as the chooser's own candidates say
    (scan / flat / scan / segment).  Compiled programs bake the form in
    at trace time: they are dropped when a pin is made and again when
    the test ends, so no program traced under one pin serves another
    test."""
    from opentsdb_tpu.ops import downsample as ds
    from opentsdb_tpu.ops import group_agg as ga

    def pin(search=None, scan=None, extreme=None, group=None):
        if search is not None:
            monkeypatch.setattr(
                ds, "_effective_search_mode",
                lambda s, n, w_edges, platform=None: search
                if search in ds._search_candidates(n, w_edges) else "scan")
        if scan is not None:
            monkeypatch.setattr(
                ds, "_effective_scan_mode",
                lambda s, n, w_edges, platform=None: scan
                if scan in ds._scan_candidates(n, w_edges) else "flat")
        if extreme is not None:
            monkeypatch.setattr(
                ds, "_effective_extreme_mode",
                lambda n, w_padded, platform=None: extreme
                if extreme in ds._extreme_candidates(n, w_padded)
                else "scan")
        if group is not None:
            monkeypatch.setattr(
                ga, "_effective_group_reduce_mode",
                lambda s, w, g, extremes=False, platform=None,
                row_groups=False: group
                if group in ga._group_candidates(s, g, extremes)
                else "segment")
        jax.clear_caches()

    yield pin
    monkeypatch.undo()
    jax.clear_caches()

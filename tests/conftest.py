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

# The platform guard demotes the dense (accelerator-winner) search forms
# to the binary search whenever execution lands on CPU — which is every
# test in this suite.  Disable it suite-wide so CPU CI keeps exercising
# the dense kernels' correctness; tests of the guard itself re-enable it
# locally (tests/test_prefix_downsample.py::TestPlatformModeGuard).
from opentsdb_tpu.ops import downsample as _ds  # noqa: E402

_ds.set_platform_mode_guard(False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: excluded from the tier-1 gate (-m 'not slow'); the "
        "standing CI soak runs these")


@pytest.fixture
def rng():
    return np.random.default_rng(42)

"""Driver entry-point contract: entry() compiles, dryrun_multichip passes.

The dryrun once inherited the ambient accelerator platform and died in
its backend init.  It must run on a virtual CPU mesh no matter what the
environment points JAX at.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import __graft_entry__ as graft


def test_entry_compiles_and_runs():
    fn, args = graft.entry()
    out = jax.jit(fn)(*args)
    jax.block_until_ready(out)
    for o in jax.tree_util.tree_leaves(out):
        assert np.all(np.isfinite(np.asarray(o, dtype=np.float64))
                      | np.isnan(np.asarray(o, dtype=np.float64)))


def test_dryrun_multichip_in_process():
    # pytest env is forced-CPU with 8 virtual devices (conftest.py), so this
    # exercises the in-process fast path on the full 8-way mesh.
    assert graft._forced_cpu_device_count() >= 8
    graft.dryrun_multichip(8)


def test_dryrun_multichip_subprocess_ignores_ambient_platform(monkeypatch):
    # Make the current env look like a non-CPU accelerator session; the
    # dryrun must re-exec with a forced CPU platform rather than inherit it.
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    assert graft._forced_cpu_device_count() == 0
    graft.dryrun_multichip(4)

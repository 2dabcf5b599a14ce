"""The mesh programs must LOWER for a TPU, checked without one.

libtpu can compile for a described topology with no chip attached
(jax.experimental.topologies), so a collective or dtype XLA:TPU refuses
shows up here, on the CPU, instead of as a 500 on the four-chip host.
First catch: `lax.pmax`/`lax.pmin` over float64 partials — "UNIMPLEMENTED:
Supported lowering only of Sum all reduce" on a 2x2 v5e mesh, while the
virtual CPU mesh the rest of the suite uses lowers it happily.

The compiles run in ONE child process (this file run as a script): libtpu
takes a machine-wide lock, the TPU compiler must not meet whatever kernel
modes or calibrations earlier tests left in the session (inside a full
tier-1 session the same compile once ran for minutes), and a compile that
does not come back is a timeout here, not a hung suite.

Tiny shapes: this pins what lowers, not how fast or into how much vmem.
The default backend is the CPU, so `auto` kernel modes are priced from
the CPU table — collectives and dtypes are what is under test.

The device cache's gather is the exception: it compiles on ONE described
chip at the benchmark cells' own shapes (2^26-point buffers, 4000 rows,
N = 256 / 2048 / 8192, both timestamp layouts), and the compiled text is
read for its structure — whole 128-element tile rows, no gather that
takes one index per stored point (PR 25: that form held the chip 83-97 %
of its busy time).  A CPU run cannot time the chip; it can read what the
chip's compiler emitted.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
S, N, START = 32, 256, 1_451_606_400_000

# (aggregator, downsample fn, rate)
QUERY_CASES = [
    ("max", "max", False),      # the 500 on the four-chip host
    ("min", "min", False),
    ("sum", "avg", False),
    ("sum", "avg", True),
    ("avg", "avg", False),
    ("dev", "avg", False),
    ("p99", "avg", False),      # gather-to-owner branch
]
CASE_NAMES = ["query:%s:%s%s" % (a, d, ":rate" if r else "")
              for a, d, r in QUERY_CASES] + [
    "rollup", "group_downsample:min", "group_downsample:max",
    "group_downsample:sum"]

# the device cache's gather at the benchmark cells' shapes
GATHER_BUFFER, GATHER_ROWS = 1 << 26, 4000
GATHER_CASES = [(n, compact) for n in (256, 2048, 8192)
                for compact in (False, True)]
GATHER_NAMES = ["gather:n%d:%s" % (n, "ts_base" if compact else "int64")
                for n, compact in GATHER_CASES]

# a rewritten request's grid placement at the 4000-host cells' [S, Wp]
PLACE_WIDTHS = (8, 16, 32)
PLACE_NAMES = ["place:pw%d" % pw for pw in PLACE_WIDTHS]


def _compile_all() -> None:
    """Child: compile every case for v5e 2x2, one JSON line each."""
    sys.path.insert(0, REPO)
    import re

    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from opentsdb_tpu.ops.downsample import FixedWindows
    from opentsdb_tpu.ops.pipeline import DownsampleStep, PipelineSpec
    from opentsdb_tpu.ops.rate import RateOptions
    from opentsdb_tpu.parallel import make_mesh, sharded
    from opentsdb_tpu.parallel.mesh import AXIS_SERIES, AXIS_TIME
    from opentsdb_tpu.storage.device_cache import _gather_program

    try:
        topo = topologies.get_topology_desc(topology_name="v5e:2x2",
                                            platform="tpu")
    except Exception as e:      # noqa: BLE001 — no libtpu, no check
        print(json.dumps({"skip": str(e)[:300]}), flush=True)
        return
    mesh = make_mesh(4, devices=topo.devices)

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    def windows(interval_ms):
        wspec, wargs = FixedWindows.for_range(
            START, START + N * 10_000 - 1, interval_ms).split()
        return wspec, {k: jax.ShapeDtypeStruct(np.shape(v),
                                               np.asarray(v).dtype)
                       for k, v in wargs.items()}

    def batch(spec):
        return (sds((S, N), jnp.int64, spec), sds((S, N), jnp.float64, spec),
                sds((S, N), jnp.bool_, spec))

    jobs = {}
    rows = P(sharded._BOTH, None)
    wspec, wargs = windows(60_000)
    for name, (agg, ds_fn, rate) in zip(CASE_NAMES, QUERY_CASES):
        spec = PipelineSpec(
            aggregator=agg,
            downsample=DownsampleStep(ds_fn, wspec, "none", 0.0),
            rate=RateOptions() if rate else None, int_mode=False,
            rows_sorted=True)
        jobs[name] = (sharded.sharded_query_pipeline(mesh, spec, 8),
                      (*batch(rows), sds((S,), jnp.int64,
                                         P(sharded._BOTH)), wargs))
    hspec, hargs = windows(3_600_000)
    grid = P(AXIS_SERIES, AXIS_TIME)
    jobs["rollup"] = (sharded.sharded_rollup(mesh, hspec),
                      (*batch(grid), hargs))
    for agg in ("min", "max", "sum"):
        jobs["group_downsample:" + agg] = (
            sharded.sharded_group_downsample(mesh, agg, hspec, 4),
            (*batch(grid), sds((S,), jnp.int64, P(AXIS_SERIES)), hargs))
    assert list(jobs) == CASE_NAMES
    for name, (fn, args) in jobs.items():
        try:
            fn.lower(*args).compile()
            print(json.dumps({"case": name, "ok": True}), flush=True)
        except Exception as e:  # noqa: BLE001 — the verdict under test
            print(json.dumps({"case": name, "ok": False,
                              "error": str(e)[:400]}), flush=True)

    chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    for name, (n, compact) in zip(GATHER_NAMES, GATHER_CASES):
        try:
            # what `_pin_columns` pins on a TPU: two uint32 words of each
            # timestamp, the two float32 halves of each value
            text = _gather_program(n, compact).lower(
                on_chip((GATHER_BUFFER,), jnp.uint32),
                on_chip((GATHER_BUFFER,), jnp.uint32),
                (on_chip((GATHER_BUFFER,), jnp.float32),
                 on_chip((GATHER_BUFFER,), jnp.float32)),
                on_chip((GATHER_ROWS,), jnp.int64),
                on_chip((GATHER_ROWS,), jnp.int64),
                on_chip((), jnp.int64)).compile().as_text()
            sizes = re.findall(r" gather\(.*slice_sizes=\{([0-9,]*)\}", text)
            # every X64Split / X64Combine by the shape of its result
            x64 = re.findall(
                r"= \w+\[([0-9,]*)\]\S* custom-call\(.*"
                r"custom_call_target=\"(X64\w+)\"", text)
            print(json.dumps({"case": name, "ok": True,
                              "gather_slice_sizes": sorted(sizes),
                              "whiles": len(re.findall(r" while\(", text)),
                              "x64_calls": sorted(
                                  "%s[%s]" % (t, dims) for dims, t in x64)}),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — the verdict under test
            print(json.dumps({"case": name, "ok": False,
                              "error": str(e)[:400]}), flush=True)

    from opentsdb_tpu.ops.pipeline import _jitted_place_piece
    for name, pw in zip(PLACE_NAMES, PLACE_WIDTHS):
        try:
            text = _jitted_place_piece.lower(
                on_chip((GATHER_ROWS, 128), jnp.float64),
                on_chip((GATHER_ROWS, 128), jnp.bool_),
                on_chip((GATHER_ROWS, pw), jnp.float64),
                on_chip((GATHER_ROWS, pw), jnp.bool_),
                on_chip((2,), jnp.int32)).compile().as_text()
            print(json.dumps({
                "case": name, "ok": True,
                "whiles": len(re.findall(r" while\(", text)),
                "gathers": len(re.findall(r" gather\(", text))}),
                flush=True)
        except Exception as e:  # noqa: BLE001 — the verdict under test
            print(json.dumps({"case": name, "ok": False,
                              "error": str(e)[:400]}), flush=True)


@pytest.fixture(scope="module")
def verdicts():
    try:
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__)], cwd=REPO,
            capture_output=True, text=True, timeout=600,
            env=dict(os.environ, JAX_PLATFORMS="cpu"))
    except subprocess.TimeoutExpired:
        pytest.fail("the TPU compiles did not come back in 600 s")
    out = [json.loads(line) for line in proc.stdout.splitlines()
           if line.startswith("{")]
    if out and "skip" in out[0]:
        pytest.skip("no TPU compiler for a described topology: %s"
                    % out[0]["skip"])
    assert proc.returncode == 0, proc.stderr[-2000:]
    return {v["case"]: v for v in out}


@pytest.mark.parametrize("case", CASE_NAMES)
def test_mesh_program_lowers_for_v5e_2x2(verdicts, case):
    assert verdicts[case]["ok"], verdicts[case].get("error")


@pytest.mark.parametrize("case", GATHER_NAMES)
def test_cache_gather_copies_tile_rows_on_a_v5e(verdicts, case):
    """Four gathers (the timestamps' two words, the values' two halves),
    every one of whole 128-element tile rows; none with one index per
    point, and no loop with a step per series row."""
    verdict = verdicts[case]
    assert verdict["ok"], verdict.get("error")
    assert verdict["gather_slice_sizes"] == ["1,128"] * 4, verdict
    assert verdict["whiles"] == 0, verdict


@pytest.mark.parametrize("case", GATHER_NAMES)
def test_cache_gather_splits_no_pinned_buffer_on_a_v5e(verdicts, case):
    """No 64-bit form of a pinned buffer exists in the program: every
    X64Split is of the [rows] start / length vectors or the scalar base,
    every X64Combine of the [rows, N] batch that leaves — none has
    GATHER_BUFFER elements, and at least one was seen (the pattern still
    reads this compiler's text)."""
    verdict = verdicts[case]
    assert verdict["ok"], verdict.get("error")
    calls = verdict["x64_calls"]
    assert any(c.startswith("X64Split") for c in calls), verdict
    assert any(c.startswith("X64Combine") for c in calls), verdict
    n = int(case.split(":")[1][1:])
    allowed = {"[]", "[%d]" % GATHER_ROWS, "[%d,%d]" % (GATHER_ROWS, n)}
    assert {c[c.index("["):] for c in calls} <= allowed, verdict
    assert not any(str(GATHER_BUFFER) in c for c in calls), verdict


@pytest.mark.parametrize("case", PLACE_NAMES)
def test_grid_placement_compiles_for_a_v5e(verdicts, case):
    """The placement program at the 4000-host cells' [4000, 128] grid
    and each edge-piece width: slices, selects and updates, with no
    loop and no gather."""
    verdict = verdicts[case]
    assert verdict["ok"], verdict.get("error")
    assert verdict["whiles"] == 0 and verdict["gathers"] == 0, verdict


if __name__ == "__main__":
    _compile_all()

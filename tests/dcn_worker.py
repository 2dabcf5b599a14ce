"""One process of the REAL 2-process DCN integration test (VERDICT r3 #5).

Launched by test_distributed.py::TestTwoProcessDCN with
  python dcn_worker.py <coordinator> <num_processes> <process_id>
Each process owns 4 virtual CPU devices; `maybe_init_distributed` joins
them into one 8-device JAX runtime (the compute-mesh analog of the
reference's RegionServer+ZooKeeper substrate, TSDB.java:235-253).  The
worker runs the production sharded query pipeline over the global mesh
and asserts bit-equality with the single-host answer; any assertion
failure exits nonzero and fails the wrapper test.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import numpy as np  # noqa: E402

from opentsdb_tpu.parallel.distributed import (  # noqa: E402
    host_major_devices, maybe_init_distributed)
from opentsdb_tpu.utils.config import Config  # noqa: E402


def main() -> None:
    coordinator, num, pid = sys.argv[1], int(sys.argv[2]), int(sys.argv[3])
    conf = Config({
        "tsd.network.distributed.coordinator": coordinator,
        "tsd.network.distributed.num_processes": str(num),
        "tsd.network.distributed.process_id": str(pid),
    })
    assert maybe_init_distributed(conf) is True
    assert jax.process_count() == num, jax.process_count()
    devs = host_major_devices()
    assert len(devs) == 4 * num, devs
    # host-major contract: each host's devices contiguous on the series
    # axis, so dense combines stay intra-host
    keys = [(d.process_index, d.id) for d in devs]
    assert keys == sorted(keys), keys
    assert [d.process_index for d in devs] == \
        sorted([d.process_index for d in devs]), keys

    # deterministic batch, identical in every process
    from opentsdb_tpu.ops.downsample import FixedWindows, pad_pow2
    from opentsdb_tpu.ops.pipeline import (DownsampleStep, PipelineSpec,
                                           run_group_pipeline)
    from opentsdb_tpu.parallel.mesh import make_mesh
    from opentsdb_tpu.parallel.sharded import (shard_rows,
                                               sharded_query_pipeline)

    s, n, g = 16, 256, 4
    start = 1_356_998_400_000
    rng = np.random.default_rng(99)
    ts = start + np.sort(rng.integers(0, 3_600_000, (s, n)), axis=1)
    ts = np.asarray(ts, np.int64)
    val = rng.normal(50.0, 15.0, (s, n))
    mask = rng.random((s, n)) < 0.9
    gid = np.arange(s, dtype=np.int64) % g

    fixed = FixedWindows.for_range(start, start + 3_600_000, 60_000)
    window_spec, wargs = fixed.split()
    g_pad = pad_pow2(g)
    spec = PipelineSpec(
        aggregator="sum",
        downsample=DownsampleStep("avg", window_spec, "none", 0.0))

    # single-host reference on this process's local devices
    ref_ts, ref_val, ref_mask, _ = run_group_pipeline(
        spec, ts, val, mask, gid, g_pad, wargs)
    ref_ts, ref_val, ref_mask = (np.asarray(ref_ts), np.asarray(ref_val),
                                 np.asarray(ref_mask))

    # global mesh across BOTH processes; same production entry points
    mesh = make_mesh(devices=host_major_devices())
    assert mesh.devices.size == 4 * num
    fn = sharded_query_pipeline(mesh, spec, g_pad)
    d_ts, d_val, d_mask, d_gid = shard_rows(mesh, ts, val, mask, gid,
                                            pad_gid_value=g_pad)
    out_ts, out_val, out_mask, _ = fn(d_ts, d_val, d_mask, d_gid, wargs)
    out_ts, out_val, out_mask = (np.asarray(out_ts), np.asarray(out_val),
                                 np.asarray(out_mask))

    assert np.array_equal(out_ts, ref_ts)
    assert np.array_equal(out_mask, ref_mask)
    live = ref_mask[:g]
    np.testing.assert_allclose(out_val[:g][live], ref_val[:g][live],
                               rtol=1e-12)

    # a second aggregator exercises the gather-to-owner (ordered) branch
    # across DCN
    spec2 = PipelineSpec(
        aggregator="p90",
        downsample=DownsampleStep("avg", window_spec, "none", 0.0))
    ref2 = np.asarray(run_group_pipeline(
        spec2, ts, val, mask, gid, g_pad, wargs)[1])
    fn2 = sharded_query_pipeline(mesh, spec2, g_pad)
    out2 = np.asarray(fn2(d_ts, d_val, d_mask, d_gid, wargs)[1])
    np.testing.assert_allclose(out2[:g][live], ref2[:g][live], rtol=1e-12)

    print("DCN_WORKER_OK process=%d devices=%d" % (pid, len(devs)),
          flush=True)


if __name__ == "__main__":
    main()

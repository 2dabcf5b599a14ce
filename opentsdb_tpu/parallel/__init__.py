"""Distributed execution: device meshes + shard_map query kernels.

The reference scales scans by salting row keys across HBase regions and
running one scanner per bucket concurrently (SaltScanner.java:269,
RowKey.prefixKeyWithSalt :141); its distributed backend is asynchbase RPC +
ZooKeeper (SURVEY.md §2.7).  The TPU-native equivalent: a
`jax.sharding.Mesh` with a *series* axis (the salt-bucket analog — each chip
owns a shard of series) and a *time* axis (sequence-parallel analog — long
series split across chips), with XLA collectives (`psum`, `all_gather`)
combining partial window moments over ICI.
"""

from opentsdb_tpu.parallel.mesh import (
    make_mesh, mesh_shape_for, AXIS_SERIES, AXIS_TIME)
from opentsdb_tpu.parallel.sharded import (
    sharded_group_downsample, sharded_rollup, shard_series,
    sharded_query_pipeline, shard_rows, SHARDED_AGGS,
    ShardedStreamAccumulator)

__all__ = [
    "make_mesh", "mesh_shape_for", "AXIS_SERIES", "AXIS_TIME",
    "sharded_group_downsample", "sharded_rollup", "shard_series",
    "sharded_query_pipeline", "shard_rows", "SHARDED_AGGS",
    "ShardedStreamAccumulator",
]

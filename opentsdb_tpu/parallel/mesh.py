"""Device mesh construction for the sharded query/rollup kernels.

Axes:
  * ``series`` — data-parallel over time series (the salt-bucket analog,
    SaltScanner.java:269: one concurrent scanner per hash bucket becomes one
    chip per series shard).
  * ``time``   — sequence-parallel over the time axis for long series
    (the 3600s row-chunking analog, Const.java:95).

Collectives ride ICI within a slice: additive window moments combine with
`psum` over both axes; min/max by all-gather + local reduce
(parallel/sharded.py `_pextreme`).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh

AXIS_SERIES = "series"
AXIS_TIME = "time"


def mesh_shape_for(n_devices: int) -> tuple[int, int]:
    """Pick a (series, time) grid for n devices, series-major.

    Series parallelism is the cheaper axis (no halo/overlap concerns), so it
    gets the larger factor: 8 -> (4, 2), 4 -> (2, 2), 2 -> (2, 1), 1 -> (1, 1).
    """
    if n_devices < 1:
        raise ValueError("need at least one device")
    time = 1
    series = n_devices
    while series % 2 == 0 and series > 2 * time:
        series //= 2
        time *= 2
    return series, time


def make_mesh(n_devices: int | None = None,
              shape: tuple[int, int] | None = None,
              devices=None) -> Mesh:
    """Build a 2-D (series, time) mesh over the first n devices."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if shape is None:
        shape = mesh_shape_for(n)
    if shape[0] * shape[1] != n:
        raise ValueError("mesh shape %r does not cover %d devices"
                         % (shape, n))
    grid = np.asarray(devices).reshape(shape)
    return Mesh(grid, (AXIS_SERIES, AXIS_TIME))

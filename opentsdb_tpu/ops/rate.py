"""Rate-of-change kernels with counter rollover handling.

Reference behavior: /root/reference/src/core/RateSpan.java (populateNextRate
:121 — per-second dv/dt between adjacent points, long arithmetic when both
values are integers, counter rollover diff = counter_max - prev + next,
reset_value spike suppression -> 0, drop_resets skips negative diffs) and
RateOptions.java (:27).  Rates are emitted at the timestamp of the latter
point; the first point of a span yields no output, matching how
AggregationIterator consumes the synthetic time-zero rate as interpolation
state only (AggregationIterator.java:448-459).

Vectorized form: for each row of a [S, N] sorted batch, slot k needs its
"previous valid point".  Two lanes find it, chosen on the device by the
mask itself (one `lax.cond`, no key or mode), and everything after is one
shared body:

* shift — every row's valid slots are one contiguous run, or none
  (`_no_interior_hole`: a regular-cadence grid whatever its padding, a
  series born late or ended early).  The previous valid point of a valid
  slot is then the slot before it or nothing, so `ts`, `val` and `mask`
  move one column right: no scan, no gather.
* scan — a row with a hole between two valid slots (FILL_NONE
  downsampling of a host down mid-range): a prefix-max scan over masked
  positions and two per-slot gathers skip the gap exactly like the
  iterator would.

On a mask that passes the predicate the lanes agree bit for bit in the
rate and its mask on every slot.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax import lax

LONG_MAX = 2**63 - 1


@dataclass(frozen=True)
class RateOptions:
    """Counter options (RateOptions.java:27-62).

    Parsing of the "rate{counter[,max[,reset]]}" URI form lives in
    models.tsquery.parse_rate_options.
    """
    counter: bool = False
    counter_max: int = LONG_MAX
    reset_value: int = 0
    drop_resets: bool = False


def _prev_valid_index(mask):
    """prev[k] = largest j < k with mask[j], else -1; per row, via cummax.

    Indices ride int32: any axis length fits, int32 scans are native TPU
    ALU work (int64 lowers to emulated u32-pair reduce-windows — ~7x
    slower, and the u32-pair lowering trips an XLA scoped-vmem compile
    bug at some [1, N] shapes: "Ran out of memory in memory space vmem
    ... reduce-window u32[1,2,128]", seen on configs 1/4).
    """
    s, n = mask.shape
    pos = jnp.where(mask, jnp.arange(n, dtype=jnp.int32)[None, :], -1)
    running = lax.associative_scan(jnp.maximum, pos, axis=1)
    prev = jnp.concatenate(
        [jnp.full((s, 1), -1, dtype=jnp.int32), running[:, :-1]], axis=1)
    return prev


def _no_interior_hole(mask):
    """mask[S, W] -> bool[]: every row is one contiguous run of True, or
    all False.  A run starts where a True follows a False (or sits in
    column 0); a row with at most one start has no hole between two
    present windows.  One fused elementwise pass and a row reduction —
    no scan, no gather, no 64-bit arithmetic."""
    starts = mask[:, 1:] & ~mask[:, :-1]
    rises = mask[:, 0].astype(jnp.int32) \
        + jnp.sum(starts, axis=1, dtype=jnp.int32)
    return jnp.all(rises <= 1)


def _prev_by_scan(operand):
    """The scan lane: (prev_ts, prev_val, has_prev) on any mask."""
    ts, val, mask = operand
    n = ts.shape[1]
    prev = _prev_valid_index(mask)
    safe_prev = jnp.clip(prev, 0, n - 1)
    return (jnp.take_along_axis(ts, safe_prev, axis=1),
            jnp.take_along_axis(val, safe_prev, axis=1), prev >= 0)


def _prev_by_shift(operand):
    """The shift lane: the same three where `_no_interior_hole(mask)`
    holds.  Column 0 has no previous point and repeats itself, which is
    what the scan lane's clipped index reads there."""
    def right(x):
        return jnp.concatenate([x[:, :1], x[:, :-1]], axis=1)

    ts, val, mask = operand
    has_prev = jnp.concatenate(
        [jnp.zeros_like(mask[:, :1]), mask[:, :-1]], axis=1)
    return right(ts), right(val), has_prev


# shape: ts[S,N] any, val[S,N] any, mask[S,N] bool
def rate(ts, val, mask, options: RateOptions, all_int: bool = False):
    """Compute rates over a [S, N] sorted batch.

    Returns (ts, rate_values[S, N] float, mask[S, N], shift[]): slot k
    holds the rate between point k and its previous valid point, masked
    off for first points (and dropped resets).  Timestamps are unchanged
    (rate sits at the latter point's timestamp).  `shift` is the lane the
    device took to find the previous points (module docstring), a bool
    scalar: the served path hands it back beside the answer for
    `tsd.query.rate_lane{lane}`.
    """
    shift = _no_interior_hole(mask)
    prev = lax.cond(shift, _prev_by_shift, _prev_by_scan, (ts, val, mask))
    out, out_mask = _rate_from_prev(ts, val, mask, prev, options, all_int)
    return ts, out, out_mask, shift


def _rate_from_prev(ts, val, mask, prev, options: RateOptions,
                    all_int: bool):
    """Both lanes' shared body: (rate[S, N], mask[S, N]) from each slot's
    previous valid point (prev_ts, prev_val, has_prev)."""
    prev_ts, prev_val, has_prev = prev
    dt_sec = (ts - prev_ts).astype(jnp.float64) / 1000.0
    dt_sec = jnp.where(dt_sec == 0, jnp.inf, dt_sec)

    if all_int:
        # Long-typed difference first, then divide — avoids double rounding
        # of large longs (RateSpan.java:140-147).
        diff = (val.astype(jnp.int64) - prev_val.astype(jnp.int64)).astype(
            jnp.float64)
        rolled = (jnp.asarray(options.counter_max, jnp.int64)
                  - prev_val.astype(jnp.int64)
                  + val.astype(jnp.int64)).astype(jnp.float64)
    else:
        diff = val.astype(jnp.float64) - prev_val.astype(jnp.float64)
        rolled = (jnp.asarray(options.counter_max, jnp.float64)
                  - prev_val.astype(jnp.float64) + val.astype(jnp.float64))

    out_mask = mask & has_prev
    if options.counter:
        negative = diff < 0
        if options.drop_resets:
            out = diff / dt_sec
            out_mask = out_mask & ~negative
        else:
            roll_rate = rolled / dt_sec
            suppressed = (options.reset_value > 0) & (
                roll_rate > options.reset_value)
            out = jnp.where(negative,
                            jnp.where(suppressed, 0.0, roll_rate),
                            diff / dt_sec)
    else:
        out = diff / dt_sec

    return jnp.where(out_mask, out, jnp.nan), out_mask

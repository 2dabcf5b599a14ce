"""rate's two lanes (ops/rate.py): on a grid whose every row is one
contiguous run of present windows the previous point of a window is the
window before it, so the shift lane answers bit for bit what the scan
lane does; a row with a hole between two present windows sends the grid
through the scan lane, answering as it always did.  The mask decides, on
the device, in one `lax.cond`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from opentsdb_tpu.ops.rate import (
    RateOptions, _prev_by_scan, _prev_by_shift, _rate_from_prev, rate)
from tests.kernel_utils import collect, primitives

S, N = 6, 16


def _mask(kind: str) -> np.ndarray:
    m = np.zeros((S, N), bool)
    if kind == "full":
        m[:] = True
    elif kind == "padded_tail":
        m[:, :11] = True
    elif kind == "late_start":
        for r in range(S):
            m[r, 2 * r:] = True
    elif kind == "early_end":
        for r in range(S):
            m[r, :N - 2 * r] = True
    elif kind == "empty_rows":
        m[::2, 3:12] = True
    elif kind == "single_window":
        m[np.arange(S), np.arange(S) * 3 % N] = True
    else:
        assert kind == "all_empty"
    return m


MASKS = ("full", "padded_tail", "late_start", "early_end", "empty_rows",
         "single_window", "all_empty")
OPTIONS = {
    "plain": RateOptions(),
    "counter": RateOptions(counter=True, counter_max=1000),
    "counter_reset": RateOptions(counter=True, counter_max=1000,
                                 reset_value=50),
    "drop_resets": RateOptions(counter=True, drop_resets=True),
}
DATA = ("float", "float_nan", "int")


def _batch(data: str):
    """A sawtooth with resets (negative steps for the counter options);
    `float_nan` puts NaN under a True mask, `int` is the all_int path."""
    rng = np.random.default_rng(33)
    ts = np.cumsum(rng.integers(1, 4, (S, N)) * 1000, axis=1) \
        .astype(np.int64)
    val = rng.integers(0, 1000, (S, N))
    if data == "int":
        return ts, val.astype(np.int64), True
    val = val + rng.integers(0, 4, (S, N)) / 4.0
    if data == "float_nan":
        val[rng.random((S, N)) < 0.15] = np.nan
    return ts, val, False


def _bits(x) -> np.ndarray:
    return np.asarray(x).view(np.uint64)


@pytest.mark.parametrize("data", DATA)
@pytest.mark.parametrize("opts", sorted(OPTIONS))
@pytest.mark.parametrize("kind", MASKS)
def test_the_shift_lane_is_the_scan_lane_bit_for_bit(kind, opts, data):
    ts, val, all_int = _batch(data)
    mask = _mask(kind)
    options = OPTIONS[opts]
    got_ts, out, out_mask, shift = rate(ts, val, mask, options,
                                        all_int=all_int)
    assert bool(shift)
    operand = (jnp.asarray(ts), jnp.asarray(val), jnp.asarray(mask))
    want, want_mask = _rate_from_prev(*operand, _prev_by_scan(operand),
                                      options, all_int)
    assert np.array_equal(got_ts, ts)
    assert np.array_equal(out_mask, want_mask)
    assert np.array_equal(_bits(out), _bits(want))
    # no first point of a run answers, nothing outside the mask does
    first = mask & ~np.concatenate(
        [np.zeros((S, 1), bool), mask[:, :-1]], axis=1)
    assert not np.asarray(out_mask)[first | ~mask].any()
    assert np.isnan(np.asarray(out)[~np.asarray(out_mask)]).all()
    if opts == "plain" and data != "float_nan":
        assert np.array_equal(out_mask, mask & ~first)


@pytest.mark.parametrize("opts", sorted(OPTIONS))
def test_one_interior_hole_takes_the_scan_lane(opts):
    ts, val, all_int = _batch("float")
    mask = _mask("padded_tail")
    mask[3, 5] = False                      # one hole, one row
    options = OPTIONS[opts]
    _, out, out_mask, shift = rate(ts, val, mask, options)
    assert not bool(shift)
    operand = (jnp.asarray(ts), jnp.asarray(val), jnp.asarray(mask))
    want, want_mask = _rate_from_prev(*operand, _prev_by_scan(operand),
                                      options, all_int)
    assert np.array_equal(out_mask, want_mask)
    assert np.array_equal(_bits(out), _bits(want))
    # the window after the hole looks back across it
    if opts == "plain":
        assert bool(out_mask[3, 6])
        assert float(out[3, 6]) == pytest.approx(
            (val[3, 6] - val[3, 4]) / ((ts[3, 6] - ts[3, 4]) / 1000.0))
    # ... which the shift lane, wrongly taken, would not
    shifted, shifted_mask = _rate_from_prev(
        *operand, _prev_by_shift(operand), options, all_int)
    assert not bool(shifted_mask[3, 6])


def test_a_gap_answers_as_the_reference_says():
    """tests/test_downsample_rate.py::test_rate_with_gaps_in_mask's
    grid and reference, with the lane read back."""
    ts = np.array([[0, 1000, 2000, 3000]], dtype=np.int64)
    val = np.array([[0.0, 99.0, 20.0, 30.0]])
    mask = np.array([[True, False, True, True]])
    _, out, omask, shift = rate(ts, val, mask, RateOptions())
    assert not bool(shift)
    assert collect(ts, out, omask) == [(2000, 10.0), (3000, 10.0)]
    # the same points without the masked slot between them: the shift
    # lane, the same answer
    keep = np.array([0, 2, 3])
    _, out, omask, shift = rate(ts[:, keep], val[:, keep], mask[:, keep],
                                RateOptions())
    assert bool(shift)
    assert collect(ts[:, keep], out, omask) == [(2000, 10.0), (3000, 10.0)]


SHAPES = (jax.ShapeDtypeStruct((S, N), jnp.int64),
          jax.ShapeDtypeStruct((S, N), jnp.float64),
          jax.ShapeDtypeStruct((S, N), jnp.bool_))


def test_the_shift_lane_holds_no_gather_scan_or_sort():
    names = primitives(jax.make_jaxpr(_prev_by_shift)(SHAPES).jaxpr)
    assert names and not [
        n for n in names if "gather" in n or "scatter" in n or "sort" in n
        or n.startswith("cum") or n in ("while", "scan", "reduce_window")]
    # the scan lane is where they live
    scan = primitives(jax.make_jaxpr(_prev_by_scan)(SHAPES).jaxpr)
    assert "gather" in scan


@pytest.mark.parametrize("opts", sorted(OPTIONS))
def test_rate_holds_one_cond(opts):
    jaxpr = jax.make_jaxpr(
        lambda t, v, m: rate(t, v, m, OPTIONS[opts]))(*SHAPES).jaxpr
    assert [e.primitive.name for e in jaxpr.eqns].count("cond") == 1
    # and outside its branches nothing indexes a cell
    outside = [e.primitive.name for e in jaxpr.eqns]
    assert "gather" not in outside and "scatter" not in outside

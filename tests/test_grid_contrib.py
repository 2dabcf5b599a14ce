"""grid_contributions: the dense lax.cond fast lane — taken by every
grid without an INTERIOR hole — must be exactly the full interpolation
branch's answer on every slot a consumer reads, and the full branch must
be unchanged for grids with a real hole."""

import numpy as np
import pytest

from opentsdb_tpu.ops import group_agg
from opentsdb_tpu.ops.aggregators import PREV, Aggregator, get_agg
from opentsdb_tpu.ops.group_agg import (_no_interior_hole,
                                        grid_contributions,
                                        grid_group_aggregate)
from opentsdb_tpu.ops.rate import _prev_valid_index
from opentsdb_tpu.ops.union_agg import interpolate, _next_valid
from tests.kernel_utils import primitives


def _full_reference(grid_ts, val, mask, agg):
    """The pre-cond straight-line implementation, kept as the oracle."""
    import jax.numpy as jnp
    w = val.shape[1]
    prev_i = _prev_valid_index(mask)
    next_i = _next_valid(mask)
    has_prev = prev_i >= 0
    has_next = next_i < w
    safe_prev = jnp.clip(prev_i, 0, w - 1)
    safe_next = jnp.clip(next_i, 0, w - 1)
    x = grid_ts[None, :]
    x0 = jnp.take(grid_ts, safe_prev)
    x1 = jnp.take(grid_ts, safe_next)
    y0 = jnp.take_along_axis(val, safe_prev, axis=1)
    y1 = jnp.take_along_axis(val, safe_next, axis=1)
    participate = has_prev & has_next | mask
    interp = interpolate(agg.interpolation, False, x, x0, y0, x1, y1, val)
    return jnp.where(mask, val, interp), participate


@pytest.mark.parametrize("aggname", ["sum", "min", "zimsum", "mimmax"])
@pytest.mark.parametrize("holey", [False, True])
def test_cond_matches_full_reference(aggname, holey):
    import jax.numpy as jnp
    rng = np.random.default_rng(17)
    s, w = 6, 48
    grid_ts = jnp.asarray(np.arange(w, dtype=np.int64) * 60_000)
    val = jnp.asarray(rng.normal(20, 5, (s, w)))
    if holey:
        mask = jnp.asarray(rng.random((s, w)) > 0.25)
    else:
        mask = jnp.ones((s, w), bool)
    agg = get_agg(aggname)
    got_c, got_p, dense = grid_contributions(grid_ts, val, mask, agg)
    assert bool(dense) == (not holey)
    want_c, want_p = _full_reference(grid_ts, val, mask, agg)
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    gp = np.asarray(want_p)
    np.testing.assert_allclose(np.asarray(got_c)[gp],
                               np.asarray(want_c)[gp], rtol=0, atol=0)


def test_f32_values_keep_working():
    """Both cond branches must agree on dtype, which depends on the
    agg's interpolation policy (LERP promotes f32 through the int64
    timestamp division; ZIM keeps f32) — a latent trace-time TypeError
    before the eval_shape-derived cast."""
    import jax.numpy as jnp
    rng = np.random.default_rng(19)
    s, w = 3, 16
    grid_ts = jnp.asarray(np.arange(w, dtype=np.int64) * 1000)
    val = jnp.asarray(rng.normal(0, 1, (s, w)).astype(np.float32))
    for aggname, want_dtype in (("sum", jnp.float64),    # LERP promotes
                                ("zimsum", jnp.float32)):  # ZIM keeps
        agg = get_agg(aggname)
        for mask in (jnp.ones((s, w), bool),
                     jnp.asarray(rng.random((s, w)) > 0.5)):
            c, p, _ = grid_contributions(grid_ts, val, mask, agg)
            assert c.dtype == want_dtype, (aggname, c.dtype)
            assert p.shape == (s, w)


# ---- grids whose only missing slots are at the EDGES of their rows ---- #

S_EDGE, W_EDGE, LIVE = 12, 16, 12
AGGS = ("sum", "avg", "min", "max", "zimsum", "mimmax", "dev", "count",
        "p99", "median", "first", "last")


def _edge_mask(case: str) -> np.ndarray:
    """bool[S_EDGE, W_EDGE] with no hole between two present windows."""
    m = np.zeros((S_EDGE, W_EDGE), bool)
    if case == "all_false":
        return m
    m[:, :LIVE] = True                      # padded tail: live < W
    if case == "rate_first_column":
        m[:, 0] = False
    elif case == "born_late_ended_early":
        m[2, :5] = False                    # born late
        m[7, 9:] = False                    # ended early
        m[9, :3] = False                    # both
        m[9, 10:] = False
    elif case == "empty_row":
        m[4] = False
    elif case == "everything_at_once":
        m[:, 0] = False
        m[1, :6] = False
        m[4] = False
        m[10, 7:] = False
        m[11] = False
        m[11, 3] = True                     # a run of one window
    else:
        assert case == "padded_tail", case
    return m


EDGE_CASES = ("padded_tail", "rate_first_column", "born_late_ended_early",
              "empty_row", "all_false", "everything_at_once")


def _edge_grid(case: str):
    import jax.numpy as jnp
    rng = np.random.default_rng(28)
    grid_ts = jnp.asarray(1_451_606_400_000
                          + np.arange(W_EDGE, dtype=np.int64) * 60_000)
    val = rng.normal(50, 20, (S_EDGE, W_EDGE))
    mask = _edge_mask(case)
    # what a downsample leaves under a False mask is not a value
    val = np.where(mask, val, np.nan)
    gid = jnp.asarray(np.arange(S_EDGE, dtype=np.int64) // 4)
    return grid_ts, jnp.asarray(val), jnp.asarray(mask), gid


def _agg(name: str, interp: str) -> Aggregator:
    agg = get_agg(name)
    return agg if interp == "own" else Aggregator(agg.name, PREV,
                                                  agg.reduce)


@pytest.mark.parametrize("interp", ["own", "prev"])
@pytest.mark.parametrize("aggname", AGGS)
@pytest.mark.parametrize("case", EDGE_CASES)
def test_edge_holes_take_the_dense_lane_exactly(case, aggname, interp):
    grid_ts, val, mask, _ = _edge_grid(case)
    agg = _agg(aggname, interp)
    got_c, got_p, dense = grid_contributions(grid_ts, val, mask, agg)
    assert bool(dense)
    want_c, want_p = _full_reference(grid_ts, val, mask, agg)
    assert got_c.dtype == want_c.dtype
    np.testing.assert_array_equal(np.asarray(got_p), np.asarray(want_p))
    gp = np.asarray(want_p)
    np.testing.assert_array_equal(np.asarray(got_c)[gp],
                                  np.asarray(want_c)[gp])


def _forced_full(monkeypatch):
    import jax.numpy as jnp
    monkeypatch.setattr(group_agg, "_no_interior_hole",
                        lambda mask: jnp.asarray(False))


def _same_bits(got, want):
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)     # NaN == NaN here


@pytest.mark.parametrize("interp", ["own", "prev"])
@pytest.mark.parametrize("aggname", AGGS)
@pytest.mark.parametrize("case", EDGE_CASES)
def test_group_aggregate_equals_the_full_branch_forced(
        case, aggname, interp, monkeypatch):
    grid_ts, val, mask, gid = _edge_grid(case)
    agg = _agg(aggname, interp)
    got = grid_group_aggregate(grid_ts, val, mask, gid, 4, agg)
    assert bool(got[3])
    _forced_full(monkeypatch)
    want = grid_group_aggregate(grid_ts, val, mask, gid, 4, agg)
    assert not bool(want[3])
    _same_bits(got[:3], want[:3])


@pytest.mark.parametrize("interp", ["own", "prev"])
@pytest.mark.parametrize("aggname", AGGS)
def test_one_interior_hole_takes_the_full_lane(aggname, interp,
                                               monkeypatch):
    """One missing window between two present ones, in one row: the
    whole grid goes through the full branch and answers as it did."""
    import jax.numpy as jnp
    grid_ts, val, mask, gid = _edge_grid("everything_at_once")
    mask = np.asarray(mask).copy()
    mask[6, 5] = False
    val = jnp.asarray(np.where(mask, np.asarray(val), np.nan))
    mask = jnp.asarray(mask)
    agg = _agg(aggname, interp)
    got_c, got_p, dense = grid_contributions(grid_ts, val, mask, agg)
    assert not bool(dense)
    want_c, want_p = _full_reference(grid_ts, val, mask, agg)
    _same_bits((got_c, got_p), (want_c, want_p))
    assert bool(np.asarray(got_p)[6, 5])        # the hole is interpolated
    got = grid_group_aggregate(grid_ts, val, mask, gid, 4, agg)
    _forced_full(monkeypatch)
    _same_bits(got, grid_group_aggregate(grid_ts, val, mask, gid, 4, agg))


@pytest.mark.parametrize("case", EDGE_CASES + (
    "interior_hole", "two_runs_at_the_edges", "one_column", "no_rows"))
def test_predicate_is_the_row_run_count(case):
    import jax.numpy as jnp
    if case in EDGE_CASES:
        m = _edge_mask(case)
    elif case == "interior_hole":
        m = _edge_mask("padded_tail")
        m[3, 6] = False
    elif case == "two_runs_at_the_edges":
        m = np.zeros((3, 8), bool)
        m[1, 0] = m[1, 7] = True
    elif case == "one_column":
        m = np.array([[True], [False]])
    else:
        m = np.zeros((0, 8), bool)
    runs = [len(np.flatnonzero(np.diff(np.r_[False, row].astype(int)) == 1))
            for row in m]
    assert bool(_no_interior_hole(jnp.asarray(m))) == all(
        r <= 1 for r in runs)


def test_predicate_holds_no_gather_scan_or_sort():
    """What the lane's condition costs a grid WITH a hole: one
    elementwise pass and a row reduction over bool[S, W], in 32 bits."""
    import jax
    import jax.numpy as jnp
    jaxpr = jax.make_jaxpr(_no_interior_hole)(
        jnp.zeros((4000, 128), bool))
    prims = primitives(jaxpr.jaxpr)
    for p in prims:
        assert not any(word in p for word in (
            "gather", "scatter", "scan", "while", "sort", "cum")), prims
    for v in jaxpr.jaxpr.eqns:
        for o in v.outvars:
            assert o.aval.dtype.itemsize <= 4, (v.primitive.name,
                                                o.aval.dtype)


def test_one_cond_and_the_lane_under_jit():
    """Still one lax.cond whose predicate is computed from the mask
    alone; jitted, the same program serves both kinds of grid."""
    import jax
    grid_ts, val, mask, _ = _edge_grid("padded_tail")
    agg = get_agg("sum")
    fn = jax.jit(lambda g, v, m: grid_contributions(g, v, m, agg))
    jaxpr = jax.make_jaxpr(
        lambda g, v, m: grid_contributions(g, v, m, agg))(
            grid_ts, val, mask)
    assert [e.primitive.name for e in jaxpr.jaxpr.eqns].count("cond") == 1
    assert bool(fn(grid_ts, val, mask)[2])
    holed = np.asarray(mask).copy()
    holed[0, 3] = False
    assert not bool(fn(grid_ts, val, holed)[2])


class TestSubblock2Boundaries:
    """_edge_subblock2_builder at adversarial edge positions: edges
    exactly ON block boundaries (off == 0 -> no remainder), idx == 0,
    idx == N (past every point) — pinned against the flat prefix
    builder, which shares the idx contract."""

    def test_boundary_edge_positions(self):
        import jax.numpy as jnp
        from opentsdb_tpu.ops import downsample as ds
        s, n, k = 2, 128, ds._SUB_K
        rng = np.random.default_rng(7)
        data = jnp.asarray(rng.normal(0, 10, (s, n)))
        # idx rows hit: 0, exact block boundaries, mid-block, n
        idx = jnp.asarray(np.array([
            [0, k, 2 * k, 2 * k + 1, 3 * k - 1, n, n],
            [0, 1, k - 1, k, k + 1, n - 1, n]], dtype=np.int32))
        want = ds._edge_prefix_builder(s, n, idx)(data)
        got = ds._edge_subblock2_builder(s, n, idx)(data)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-12, atol=1e-12)
        # int32 data (the count lane's dtype) must work too
        di = jnp.asarray(rng.integers(0, 5, (s, n)).astype(np.int32))
        np.testing.assert_array_equal(
            np.asarray(ds._edge_subblock2_builder(s, n, idx)(di)),
            np.asarray(ds._edge_prefix_builder(s, n, idx)(di)))


# --------------------------------------------------------------------- #
# The out-mask: "group g has a member present in window w", in the form #
# the reduce beside it took                                             #
# --------------------------------------------------------------------- #

PRESENCE_GRIDS = ("empty_groups", "out_of_range_gid", "nan_under_mask",
                  "padded_groups", "unsorted_gid")


def _presence_grid(case: str):
    """(grid_ts, val, mask, gid, G, rows_sorted) with values integer so
    every form sums them exactly."""
    rng = np.random.default_rng(33)
    s, w, g = 24, 16, 8
    grid_ts = np.arange(w, dtype=np.int64) * 60_000
    val = rng.integers(0, 100, (s, w)).astype(np.float64)
    mask = np.zeros((s, w), bool)
    for r in range(s):                      # hole-free rows, ragged edges
        lo = int(rng.integers(0, 6))
        mask[r, lo:int(rng.integers(lo + 1, w + 1))] = True
    mask[:, 11] = False                     # a window nobody holds
    mask[:, 12:] &= (np.arange(s) % 3 == 0)[:, None]
    gid = np.sort(rng.integers(0, 5, s)).astype(np.int64)
    sorted_ = True
    if case == "empty_groups":
        gid = np.where(gid >= 2, gid + 2, gid)      # 2 and 3 have no member
    elif case == "out_of_range_gid":
        gid[-5:] = g                        # the planner's padding rows
        mask[-5:] = True                    # ... present, and in no group
    elif case == "nan_under_mask":
        val[mask & (rng.random((s, w)) < 0.3)] = np.nan
        val[gid == 1] = np.nan              # a group holding only NaN
    elif case == "padded_groups":
        g = 16                              # G padded past the live groups
    else:
        assert case == "unsorted_gid"
        gid = rng.permutation(gid)
        sorted_ = False
    return grid_ts, val, mask, gid, g, sorted_


def _present_reference(mask, gid, g):
    want = np.zeros((g, mask.shape[1]), bool)
    for row, grp in zip(mask, gid):
        if 0 <= grp < g:
            want[grp] |= row
    return want


@pytest.mark.parametrize("aggname", ["sum", "min", "p99", "median"])
@pytest.mark.parametrize("case", PRESENCE_GRIDS)
@pytest.mark.parametrize("form", ["matmul", "sorted", "segment"])
def test_out_mask_is_any_member_present_in_every_form(form, case, aggname,
                                                      kernel_forms):
    import jax.numpy as jnp
    if form != "segment":                   # segment: the CPU's own pick
        kernel_forms(group=form)
    grid_ts, val, mask, gid, g, sorted_ = _presence_grid(case)
    agg = get_agg(aggname)
    extremes = aggname == "min"
    s, w = mask.shape
    took = group_agg._effective_group_reduce_mode(s, w, g,
                                                  extremes=extremes)
    # extremes have no matmul form: their pin falls back to segment
    assert took == ("segment" if form == "matmul" and extremes else form)
    _, _, out_mask, _ = grid_group_aggregate(
        jnp.asarray(grid_ts), jnp.asarray(val), jnp.asarray(mask),
        jnp.asarray(gid), g, agg, rows_sorted=sorted_)
    want = _present_reference(mask, gid, g)
    np.testing.assert_array_equal(np.asarray(out_mask), want)
    alone = group_agg.group_presence(
        jnp.asarray(mask), jnp.asarray(gid), g, extremes=extremes,
        rows_sorted=sorted_)
    np.testing.assert_array_equal(np.asarray(alone), want)


@pytest.mark.parametrize("aggname", ["sum", "p99"])
def test_matmul_out_mask_holds_no_scatter(aggname, kernel_forms):
    """Under the form the chip picks for a narrow group-by the whole
    grouped tail of a moment aggregator indexes no cell: no scatter, and
    outside grid_contributions' full branch no gather."""
    import jax
    import jax.numpy as jnp
    kernel_forms(group="matmul")
    grid_ts, val, mask, gid, g, _ = _presence_grid("empty_groups")
    presence = primitives(jax.make_jaxpr(
        lambda m, i: group_agg.group_presence(m, i, g))(
            jnp.asarray(mask), jnp.asarray(gid)).jaxpr)
    assert "dot_general" in presence
    assert not [p for p in presence if "scatter" in p or "gather" in p
                or "sort" in p or p.startswith("cum")], presence
    whole = primitives(jax.make_jaxpr(
        lambda t, v, m, i: grid_group_aggregate(
            t, v, m, i, g, get_agg(aggname), rows_sorted=True))(
                jnp.asarray(grid_ts), jnp.asarray(val), jnp.asarray(mask),
                jnp.asarray(gid)).jaxpr)
    assert not [p for p in whole if "scatter" in p], whole


def test_presence_counts_stay_exact_at_the_widest_matmul_shape():
    """0/1 sums in float32 are exact below 2^24 terms; the matmul form's
    own gate keeps S under it at every G."""
    assert all(
        not group_agg._matmul_feasible(1 << 24, g)
        for g in (1, 2, 16, group_agg._MATMUL_MAX_GROUPS))

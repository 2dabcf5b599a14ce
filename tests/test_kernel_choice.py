"""What the benchmark's cells run, pinned: which kernel form each of their
dispatches takes on the chip.

Which form of edge search / prefix scan / extreme reduce / group reduce
runs is a pure function of (platform, shape) — `ops/downsample.py`'s and
`ops/group_agg.py`'s choosers over `ops/costmodel.py`'s one static table —
so it can be evaluated here, on the CPU backend, with the platform as an
argument.  DISPATCHES is data recorded AT THE PARENT of the PR that removed
every way to override the pick (PR 29, parent 79ea372): every distinct
chooser call made at trace time by the daemon of each cell of
BENCHMARK.json on a v5e (`TPU v5 lite`; one chip, and one 2x2 host for
`heavy-replay-mesh4`, whose shard programs see S = 4000 / 4), with the form
that call returned.  A case that fails says: this change makes that cell
trace another program on the chip — race the forms on the chip at that
shape before moving it (docs/costmodel.md).

No cell downsamples with min/max, so no cell calls the extreme chooser;
the partial-aggregate rewrite's piece shapes (`_downsample_grid`) depend
on where a seed's windows start inside a 32-window block, so the two
one-chip 4000-host cells, which replay one traffic file, carry the union
of what their two runs showed.
"""

import pytest

from opentsdb_tpu.ops import costmodel
from opentsdb_tpu.ops import downsample as ds
from opentsdb_tpu.ops import group_agg as ga
from opentsdb_tpu.ops import streaming

HEAVY = ("heavy-replay", "heavy-replay-solo")
MESH4 = ("heavy-replay-mesh4",)
FLEET = ("fleet-replay-100k",)

# (cells, program, S, N, W+1, G, extremes, row_groups,
#  search pick, scan pick, group pick) — None: the program does not
# consult that axis (a grid tail searches nothing, a downsample-only
# piece reduces no group; on the mesh the p99's rank-based reduce is
# gather-to-owner and asks no group chooser).
DISPATCHES = [
    (HEAVY, "pipeline._group_pipeline", 4000, 8192, 17, 4096, False, True,
     "hier", "subblock", "rows"),
    (HEAVY, "pipeline._group_pipeline", 4000, 2048, 33, 32, False, False,
     "hier", "subblock2", "matmul"),
    (HEAVY, "pipeline._group_pipeline", 4000, 1024, 129, 16, False, False,
     "compare_all", "subblock2", "matmul"),
    (HEAVY, "pipeline._grid_tail", 4000, None, 129, 16, False, False,
     None, None, "matmul"),
    (HEAVY, "pipeline._downsample_grid", 4000, 256, 33, None, False, False,
     "compare_all", "subblock2", None),
    (HEAVY, "pipeline._downsample_grid", 4000, 128, 33, None, False, False,
     "compare_all", "subblock2", None),
    (HEAVY, "pipeline._downsample_grid", 4000, 128, 17, None, False, False,
     "compare_all", "subblock2", None),
    (HEAVY, "pipeline._downsample_grid", 4000, 64, 17, None, False, False,
     "compare_all", "subblock2", None),     # heavy-replay's run only
    (HEAVY, "pipeline._downsample_grid", 4000, 64, 9, None, False, False,
     "compare_all", "subblock2", None),
    (HEAVY, "pipeline._downsample_grid", 4000, 32, 9, None, False, False,
     "compare_all", "flat", None),          # heavy-replay-solo's run only
    (HEAVY, "pipeline._downsample_grid", 4000, 8, 9, None, False, False,
     "compare_all", "flat", None),
    (MESH4, "sharded.local", 1000, 8192, 17, 4096, False, False,
     "hier", "subblock", "sorted"),
    (MESH4, "sharded.local", 1000, 2048, 33, None, False, False,
     "hier", "subblock2", None),
    (MESH4, "sharded.local", 1000, 1024, 129, 16, False, False,
     "compare_all", "subblock2", "matmul"),
    (FLEET, "pipeline._group_pipeline", 100000, 256, 9, 131072, False,
     True, "hier", "subblock2", "rows"),
    (FLEET, "pipeline._group_pipeline", 100000, 256, 33, 32, False, False,
     "compare_all", "subblock2", "matmul"),
    (FLEET, "pipeline._group_pipeline", 100000, 128, 17, 16, False, False,
     "compare_all", "subblock2", "matmul"),
    # the first request of a cold metric streams, before the pin lands
    (FLEET, "streaming._update", 100000, 1024, 17, None, False, False,
     "hier", "subblock2", None),
    (FLEET, "pipeline._grid_tail", 100000, None, 17, 16, False, False,
     None, None, "matmul"),
]

AXES = ("search", "scan", "group")


def _cases():
    for (cells, program, s, n, w1, g, extremes, row_groups,
         *picks) in DISPATCHES:
        for cell in cells:
            for axis, pick in zip(AXES, picks):
                if pick is not None:
                    yield pytest.param(
                        axis, s, n, w1, g, extremes, row_groups, pick,
                        id="%s-%s-S%sxN%sxE%s-G%s-%s" % (
                            cell, program.split(".")[1].lstrip("_"),
                            s, n, w1, g, axis))


def _decide(axis, s, n, w1, g, extremes, row_groups, platform):
    if axis == "search":
        return ds.search_decision(s, n, w1, platform)
    if axis == "scan":
        return ds.scan_decision(s, n, w1, platform)
    return ga.group_decision(s, w1 - 1, g, platform, extremes=extremes,
                             row_groups=row_groups)


@pytest.mark.parametrize(
    "axis,s,n,w1,g,extremes,row_groups,pick", list(_cases()))
def test_cell_dispatch_takes_the_recorded_form(axis, s, n, w1, g, extremes,
                                               row_groups, pick):
    report = _decide(axis, s, n, w1, g, extremes, row_groups, "tpu")
    assert report["mode"] == pick
    assert report["feasible"]


# --------------------------------------------------------------------- #
# The chooser is a pure function: nothing to set, nothing that moves     #
# --------------------------------------------------------------------- #

MODULES = (ds, ga, streaming, costmodel)


def _module_state():
    """Every module-level value of the kernel modules that is plain data
    (what a mode global, a live table or a memo would be)."""
    plain = (str, int, float, bool, tuple, frozenset, dict, list, set,
             type(None))
    return {(m.__name__, k): repr(v) for m in MODULES
            for k, v in vars(m).items()
            if isinstance(v, plain) and not k.startswith("__")}


PURE_SHAPES = [(4000, 8192, 17, 4096), (100000, 256, 33, 32),
               (1, 1048576, 3502, 1), (1024, 65536, 514, 100)]


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
@pytest.mark.parametrize("axis", AXES + ("extreme",))
def test_the_chooser_is_pure(axis, platform):
    """Same arguments, same answer, whatever was asked in between, and
    no module global moves: there is no state for a choice to live in."""
    before = _module_state()

    def ask(s, n, w1, g):
        if axis == "extreme":
            return ds.extreme_decision(n, w1 - 1, platform)
        return _decide(axis, s, n, w1, g, False, False, platform)

    first = [ask(*shape) for shape in PURE_SHAPES]
    again = [ask(*shape) for shape in reversed(PURE_SHAPES)]
    assert first == list(reversed(again))
    assert all(r["feasible"] for r in first)
    assert _module_state() == before


@pytest.mark.parametrize("module", MODULES,
                         ids=[m.__name__.rsplit(".", 1)[1]
                              for m in MODULES])
def test_no_hook_overrides_the_pick(module):
    """The kernel modules export nothing that sets, installs or reloads
    a choice; tests pin a form from their own side (conftest's
    `kernel_forms`)."""
    hooks = [name for name in vars(module)
             if name.startswith(("set_", "install_", "reload_"))]
    assert hooks == []


def test_decision_reports_say_what_and_at_what_price():
    report = ds.scan_decision(4000, 8192, 17, "tpu")
    assert set(report) == {"axis", "mode", "candidates", "feasible"}
    assert report["mode"] == min(report["candidates"],
                                 key=report["candidates"].get)

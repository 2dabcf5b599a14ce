"""A rewritten request's [S, Wp] grid put together on the device.

`query/planner.py::_run_agg_rewrite` hands the grid tail one grid made
of window-contiguous pieces: cached blocks (host or device tier, rows
narrowed when the query's order is not the entry's) and freshly
downsampled edge pieces and missing blocks.  On the device lane the
pieces go into the grid through `ops/pipeline.py::assemble_grid`, one
placement program a piece width with the offset and count traced; on
the host lane every piece is copied into a host grid.  The gate is
bit-identity: the device grid equals the host grid bit for bit, for
every head and tail width of a 32-window block, and the answer equals
the cache-disabled run's on integer data.  And the placement programs
stay few: a range sliding over 64 start positions compiles at most one
a piece width, and a second sweep compiles nothing.
"""

import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from opentsdb_tpu.core.tsdb import TSDB
from opentsdb_tpu.models import TSQuery, parse_m_subquery
from opentsdb_tpu.obs import jaxprof
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.ops import pipeline
from opentsdb_tpu.ops.downsample import pad_pow2
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400        # a multiple of a 32 x 10 s block
BW = 32
STEP_S = 10                 # the queries' downsample interval
B0 = BASE // STEP_S         # absolute index of the window at BASE
HOSTS = ("c", "a", "b")     # store order differs from group order
# every head width 0..31 once, paired with every tail width 0..31 once
# (13 is odd, so the tails are a permutation), over 1-3 whole blocks
WIDTHS = [(h, (13 * h + 5) % BW, 1 + h % 3) for h in range(BW)]
SUM = "sum:10s-sum:sys.i{host=*}"
RATE = "sum:rate:10s-sum:sys.i{host=*}"
HOST_LANE_ALL = 1 << 40
HOST_LANE_NONE = 0


def make_tsdb(**over):
    cfg = {
        "tsd.core.auto_create_metrics": True,
        "tsd.query.mesh.enable": False,
        "tsd.storage.fix_duplicates": True,
        "tsd.query.cache.block_windows": BW,
        "tsd.query.cache.min_repeats": 1,
        "tsd.query.cache.dispatch_overhead_us": 0,
        "tsd.query.host_lane.max_points": HOST_LANE_NONE,
    }
    cfg.update(over)
    return TSDB(Config(cfg))


def feed(tsdb, n=2000):
    for i, host in enumerate(HOSTS):
        key = tsdb._series_key("sys.i", {"host": host}, create=True)
        ts = (np.arange(n, dtype=np.int64) + BASE) * 1000
        tsdb.store.add_batch(key, ts, (np.arange(n) * (7 + i)) % 101, True)


def bounds(head, tail, first_block=2, blocks=2):
    """(start_ms, end_ms) whose rewrite is a `head`-window edge piece,
    `blocks` whole blocks from block B0 / 32 + first_block on, and a
    `tail`-window edge piece, every window fully inside the range."""
    lo = B0 + first_block * BW - head
    hi = B0 + (first_block + blocks) * BW + tail
    return lo * STEP_S * 1000, hi * STEP_S * 1000 - 1


def run_q(tsdb, m, start_ms, end_ms):
    q = TSQuery(start=str(start_ms), end=str(end_ms),
                queries=[parse_m_subquery(m)])
    q.validate()
    runner = tsdb.new_query_runner()
    return [r.to_json() for r in runner.run(q)], dict(runner.exec_stats)


def assembly_count(lane):
    return REGISTRY.counter("tsd.query.rewrite.assembly").labels(
        lane=lane).get()


def piece_count(kind):
    return REGISTRY.counter("tsd.query.rewrite.pieces").labels(
        kind=kind).get()


def bits(a):
    a = np.asarray(a)
    return a.view(np.uint64) if a.dtype == np.float64 else a


@pytest.fixture
def tail_grids(monkeypatch):
    """The [S, Wp] grids each rewrite hands the grid tail, as host
    arrays (the tail itself runs unchanged)."""
    seen = []
    real = pipeline.run_grid_tail

    def spy(spec, wts, v, m, gid, num_groups):
        seen.append((np.asarray(v).copy(), np.asarray(m).copy()))
        return real(spec, wts, v, m, gid, num_groups)
    monkeypatch.setattr(pipeline, "run_grid_tail", spy)
    return seen


def blocks_of(tsdb):
    cache = tsdb.agg_cache
    with cache._lock:
        return sorted(cache._blocks.items(), key=lambda kv: kv[0][-1])


def mirror_odd_blocks(tsdb):
    """Give every odd block a device-tier mirror: the grids then mix
    device-tier and host-tier blocks."""
    for key, entry in blocks_of(tsdb):
        if key[-1] % 2 and entry.val_dev is None:
            entry.val_dev = jax.device_put(entry.val)
            entry.mask_dev = jax.device_put(entry.mask)


def permute_block_rows(tsdb):
    """Re-lay every block's rows in another order (the entry's rows
    dict moves with them), so the query's rows are not the identity."""
    cache = tsdb.agg_cache
    with cache._lock:
        for entry in cache._blocks.values():
            n = entry.val.shape[0]
            perm = np.roll(np.arange(n), 1)
            val = np.empty_like(entry.val)
            mask = np.empty_like(entry.mask)
            val[perm] = entry.val
            mask[perm] = entry.mask
            entry.val, entry.mask = val, mask
            entry.rows = {srs: int(perm[i]) for srs, i in entry.rows.items()}
            if entry.val_dev is not None:
                entry.val_dev = jax.device_put(val)
                entry.mask_dev = jax.device_put(mask)


def drop_first_block(tsdb, first_block=2):
    cache = tsdb.agg_cache
    with cache._lock:
        for key in list(cache._blocks):
            if key[-1] == B0 // BW + first_block:
                cache._drop_locked(key)


class TestPlacementProgram:
    @pytest.mark.parametrize("blocks", [1, 2, 3])
    @pytest.mark.parametrize("source", ["device", "host"])
    def test_every_head_and_tail_width_bit_identical(self, source, blocks):
        """Every (head, tail) width pair at a 32-window block, the edge
        pieces padded to their own pow2 widths and holding garbage past
        their counts: the placed grid is the host copy's, bit for bit,
        NaN and -0.0 included.  One and three blocks put a padded tail
        piece past the grid's edge (58 windows in 64 columns, a 9-window
        tail at column 49 in a 16-column piece), where the write must
        not be shifted."""
        rng = np.random.default_rng(7)
        s = 3
        for head in range(BW):
            for tail in range(BW):
                counts = ([head] if head else []) + [BW] * blocks \
                    + ([tail] if tail else [])
                wp = pad_pow2(sum(counts))
                pieces = []
                for count in counts:
                    pw = pad_pow2(count)
                    v = rng.standard_normal((s, pw))
                    v[0, 0], v[1, -1] = -0.0, np.nan
                    m = rng.random((s, pw)) < 0.7
                    if source == "device":
                        v, m = jnp.asarray(v), jnp.asarray(m)
                    pieces.append((v, m, count))
                want_v = np.zeros((s, wp))
                want_m = np.zeros((s, wp), bool)
                col = 0
                for v, m, count in pieces:
                    want_v[:, col:col + count] = np.asarray(v)[:, :count]
                    want_m[:, col:col + count] = np.asarray(m)[:, :count]
                    col += count
                got_v, got_m = pipeline.assemble_grid(pieces, s, wp)
                assert np.array_equal(bits(got_v), bits(want_v)), \
                    (head, tail)
                assert np.array_equal(np.asarray(got_m), want_m), \
                    (head, tail)


class TestRewriteAssembly:
    @pytest.mark.parametrize("m", [SUM, RATE], ids=["sum", "rate"])
    @pytest.mark.parametrize("case", ["tiers", "rows", "fresh"])
    def test_device_grid_is_the_host_grid(self, case, m, tail_grids):
        """Each head and tail width: the device lane's grid equals the
        host lane's bit for bit (one cache, the lane flipped between the
        two requests), and both answers equal the cache-disabled run's.
        `tiers`: odd blocks device-tier, even ones host-tier; `rows`: the
        same, with every block's rows re-laid; `fresh`: the first block
        dropped before each request, so it is recomputed and stored
        back (a miss beside a hit)."""
        on = make_tsdb()
        off = make_tsdb(**{"tsd.query.cache.enable": False})
        feed(on)
        feed(off)
        run_q(on, m, *bounds(0, 0, first_block=1, blocks=5))  # populate
        mirror_odd_blocks(on)
        if case == "rows":
            permute_block_rows(on)
        computed0 = piece_count("computed")
        for head, tail, blocks in WIDTHS:
            start, end = bounds(head, tail, blocks=blocks)
            grids, answers = [], []
            for lane_max in (HOST_LANE_NONE, HOST_LANE_ALL):
                on.config.override_config(
                    "tsd.query.host_lane.max_points", lane_max)
                if case == "fresh":
                    drop_first_block(on)
                lane = "device" if lane_max == HOST_LANE_NONE else "host"
                before = assembly_count(lane)
                got, stats = run_q(on, m, start, end)
                assert assembly_count(lane) == before + 1, (head, tail)
                assert stats.get("aggCacheHitWindows", 0) == BW * (
                    blocks - (case == "fresh")), (head, tail)
                grids.append(tail_grids[-1])
                answers.append(got)
            (dv, dm), (hv, hm) = grids
            assert np.array_equal(bits(dv), bits(hv)), (head, tail)
            assert np.array_equal(dm, hm), (head, tail)
            want, _ = run_q(off, m, start, end)
            assert answers[0] == answers[1] == want, (head, tail)
        if case == "fresh":
            # the dropped block and every edge piece were computed
            assert piece_count("computed") - computed0 >= 2 * len(WIDTHS)

    def test_host_lane_keeps_the_host_branch(self):
        tsdb = make_tsdb(**{
            "tsd.query.host_lane.max_points": HOST_LANE_ALL})
        feed(tsdb)
        start, end = bounds(3, 5)
        run_q(tsdb, SUM, start, end)
        host0, dev0 = assembly_count("host"), assembly_count("device")
        _, stats = run_q(tsdb, SUM, start, end)
        assert stats.get("hostLane") == 1.0
        assert stats.get("aggCacheHitWindows", 0) == 2 * BW
        assert assembly_count("host") == host0 + 1
        assert assembly_count("device") == dev0


class TestBoundedCompiles:
    def test_sliding_range_compiles_one_program_a_piece_width(
            self, monkeypatch):
        """A 120-window range slid over 64 start positions at one
        (S, Wp): the placement program compiles at most once per piece
        width the sweep used, and a second sweep compiles nothing at
        all."""
        tsdb = make_tsdb()
        feed(tsdb)
        widths = set()
        real = pipeline.assemble_grid

        def spy(pieces, s, wp):
            pieces = list(pieces)
            widths.update((wp, v.shape[1]) for v, _m, _c in pieces)
            return real(pieces, s, wp)
        monkeypatch.setattr(pipeline, "assemble_grid", spy)

        def sweep():
            for i in range(64):
                lo = (B0 + i) * STEP_S * 1000
                _, stats = run_q(tsdb, SUM, lo, lo + 120 * STEP_S * 1000 - 1)
                assert stats.get("aggCacheHitWindows", 0) > 0 or i == 0

        me = threading.get_ident()
        compiled = []

        def on_compile(kernel):
            # this thread's compiles only: another test's leftover
            # thread may compile its own programs meanwhile
            if threading.get_ident() == me:
                compiled.append(kernel)
        device0 = assembly_count("device")
        jaxprof.compile_capture.subscribe(on_compile)
        try:
            sweep()
            first = list(compiled)
            del compiled[:]
            sweep()
        finally:
            jaxprof.compile_capture.unsubscribe(on_compile)
        assert assembly_count("device") - device0 == 128
        assert {wp for wp, _pw in widths} == {128}
        assert first.count("jit(_place_piece)") <= len(widths) <= 3
        assert compiled == []

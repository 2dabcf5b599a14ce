"""The benchmark's generator and reference: deterministic in --seed,
equal to chip_smoke's for the same seed (they were copied from it), and
a backfill continues the same walk."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402
from benchmark import reference, traffic, tsbs  # noqa: E402

ROOT = os.path.join(REPO, "benchmark")


@pytest.mark.parametrize("seed", [1, 7])
def test_generator_equals_chip_smokes(seed):
    assert tsbs.make_fleet(60, seed) == chip_smoke.make_fleet(60, seed)
    assert np.array_equal(tsbs.make_values(60, 360, seed, 0),
                          chip_smoke.make_values(60, 360, seed, 0))
    assert np.array_equal(tsbs.timestamps(360), chip_smoke.timestamps(360))
    assert (tsbs.EPOCH_S, tsbs.CADENCE_S, tsbs.TAG_KEYS) == (
        chip_smoke.EPOCH_S, chip_smoke.CADENCE_S, chip_smoke.TAG_KEYS)


def test_generator_is_deterministic_and_seeded():
    a, b = tsbs.Fleet(30, 180, 60, 3), tsbs.Fleet(30, 180, 60, 3)
    assert a.tags == b.tags and np.array_equal(a.values, b.values)
    assert not np.array_equal(a.values, tsbs.Fleet(30, 180, 60, 4).values)
    # a backfill continues the retained walk: the prefix is the same data
    assert np.array_equal(a.values[:, :180],
                          tsbs.make_values(30, 180, 3, 0))
    assert a.values.min() >= 0 and a.values.max() <= 100


@pytest.mark.parametrize("mix", ["dash-steady", "heavy-replay",
                                 "heavy-replay-solo", "dash-live-writes"])
def test_traffic_is_deterministic_in_the_seed(mix):
    fleet = tsbs.Fleet(40, 720, 0, 5)
    rd = traffic.load_mix(ROOT, mix)["readers"]

    def draw(seed):
        gen = traffic.Generator(fleet, rd, seed)
        if rd["loop"] == "closed":
            return [r["path"] for r in gen.replay_list()]
        due, reqs = gen.open_schedule(rd["rate_per_s"], 5.0, 0)
        return [round(float(d), 9) for d in due] + [r["path"] for r in reqs]

    assert draw(5) == draw(5)
    assert draw(5) != draw(6)


def test_open_schedule_follows_rate_and_shares():
    fleet = tsbs.Fleet(40, 720, 0, 1)
    rd = traffic.load_mix(ROOT, "dash-steady")["readers"]
    gen = traffic.Generator(fleet, rd, 1)
    due, reqs = gen.open_schedule(200.0, 30.0, 0)
    assert 0.9 * 6000 < len(due) < 1.1 * 6000 and due[-1] < 30.0
    assert np.all(np.diff(due) >= 0)
    # the overview row is refreshed every 10 s, whatever the rate
    for name in ("region-sum-2h", "region-rate-2h"):
        at = [d for d, r in zip(due, reqs) if r["cls"] == name]
        assert len(at) == 3 and np.allclose(np.diff(at), 10.0)
    share = sum(r["cls"] == "single-groupby-1-1-1" for r in reqs) / len(reqs)
    assert 0.55 < share < 0.65
    # Zipf: the most asked host is asked far more often than 1 in 40
    hosts = [r["hosts"][0] for r in reqs if r["cls"] == "single-groupby-1-1-1"]
    top = max(hosts.count(h) for h in set(hosts))
    assert top / len(hosts) > 0.15
    # a dashboard's overview row re-asks a few fixed windows
    pool = {r["start"] for r in reqs if r["cls"] == "region-sum-2h"}
    assert 1 <= len(pool) <= 4
    # every window starts on a multiple of its class's own downsample
    # interval and, over a run, on more than the multiples of any block
    # size inside the program (32 windows is the partial-aggregate cache's)
    starts = [r["start"] - tsbs.EPOCH_S for r in reqs if r["kind"] == "query"]
    assert all(s % 60 == 0 for s in starts)
    assert len({s % 1920 for s in starts}) > 16


def test_replay_list_is_one_cycle_of_eleven():
    fleet = tsbs.Fleet(4000, 8640, 0, 1)
    rd = traffic.load_mix(ROOT, "heavy-replay")["readers"]
    cycle = traffic.Generator(fleet, rd, 1).replay_list()
    counts = {}
    for r in cycle:
        counts[r["cls"]] = counts.get(r["cls"], 0) + 1
    assert counts == {"double-groupby-1": 1, "datacenter-p99-5h": 2,
                      "region-sum-2h": 4, "region-rate-2h": 4}
    assert sum(r["points"] for r in cycle) == 54_720_000
    big = next(r for r in cycle if r["cls"] == "double-groupby-1")
    assert big["points"] == 17_280_000 and big["start"] % 3600 == 0
    # windows start where their own interval puts them, seed by seed: not
    # on the blocks of a cache inside the program
    offsets = {(r["start"] - tsbs.EPOCH_S) % 1920
               for seed in range(1, 9)
               for r in traffic.Generator(fleet, rd, seed).replay_list()
               if r["interval_s"] == 60}
    assert len(offsets) > 16 and all(o % 60 == 0 for o in offsets)


def _smoke_request(req):
    return dict(req, hosts=set(req["hosts"]) if req["hosts"] else None)


@pytest.mark.parametrize("mix", ["dash-steady", "heavy-replay"])
def test_reference_equals_chip_smokes(mix):
    fleet = tsbs.Fleet(40, 720, 0, 2)
    rd = traffic.load_mix(ROOT, mix)["readers"]
    gen = traffic.Generator(fleet, rd, 2)
    rng = np.random.default_rng(0)
    for cls in rd["classes"]:
        if cls.get("kind") == "last":
            continue
        req = gen.instance(cls, rng)
        mine = reference.ref_query(fleet, req)
        theirs = chip_smoke.ref_query(fleet.tags, fleet.ts, fleet.values,
                                      _smoke_request(req))
        assert reference.compare(mine, theirs) is None
        assert chip_smoke.compare(mine, theirs) is None


def test_compare_tells_what_differs():
    fleet = tsbs.Fleet(8, 360, 0, 1)
    rd = traffic.load_mix(ROOT, "heavy-replay")["readers"]
    req = traffic.Generator(fleet, rd, 1).replay_list()[0]
    want = reference.ref_query(fleet, req)
    assert reference.compare(want, want) is None
    group = sorted(want)[0]
    off = dict(want)
    off[group] = (want[group][0], want[group][1] + 1e-6)
    assert "reference" in reference.compare(off, want)
    del off[group]
    assert "groups differ" in reference.compare(off, want)


def test_check_last_accepts_a_backfilled_point_and_refuses_a_wrong_one():
    fleet = tsbs.Fleet(8, 360, 60, 1)

    def answer(col, value):
        return [{"tags": {"hostname": "host_3"}, "value": str(value),
                 "timestamp": int(fleet.ts[col]) * 1000}]
    assert reference.check_last(
        fleet, ["host_3"], answer(359, fleet.values[3, 359])) is None
    assert reference.check_last(
        fleet, ["host_3"], answer(400, fleet.values[3, 400])) is None
    assert reference.check_last(
        fleet, ["host_3"], answer(300, fleet.values[3, 300])) is not None
    assert reference.check_last(
        fleet, ["host_3"], answer(359, fleet.values[3, 359] + 1)) is not None
    assert reference.check_last(fleet, ["host_4"], answer(359, 0)) is not None

"""Plain numpy evaluation of the query semantics, and the comparison that
decides `correct`.  ref_* and compare are chip_smoke.py's (PR 21), copied;
the row and column selection is rewritten for thousands of small requests
(index lookups, not scans of the fleet)."""

from __future__ import annotations

import re

import numpy as np

from benchmark.tsbs import CADENCE_S, EPOCH_S, Fleet


def ref_downsample(ts: np.ndarray, vals: np.ndarray, interval_s: int,
                   fn: str) -> tuple[np.ndarray, np.ndarray]:
    """Epoch-aligned fixed windows over sorted `ts` [N] and `vals`
    [S, N]: each window's timestamp is its start, its value the `fn` of
    the points inside.  Returns (window_ts [W], grid [S, W] float64)."""
    win = ts - ts % interval_s
    wts, first = np.unique(win, return_index=True)
    v = vals.astype(np.float64)
    if fn == "avg":
        counts = np.diff(np.append(first, len(ts)))
        grid = np.add.reduceat(v, first, axis=1) / counts
    elif fn == "max":
        grid = np.maximum.reduceat(v, first, axis=1)
    else:
        raise ValueError("reference has no downsample fn %r" % fn)
    return wts, grid


def ref_rate(wts: np.ndarray, grid: np.ndarray
             ) -> tuple[np.ndarray, np.ndarray]:
    """Per-second first difference; the first point of each series has
    no predecessor and is dropped (RateSpan semantics, no counter)."""
    return wts[1:], np.diff(grid, axis=1) / np.diff(wts).astype(np.float64)


def ref_percentile(col: np.ndarray, q: float) -> float:
    """commons-math3 LEGACY estimation (what OpenTSDB's pNN aggregators
    use): pos = q(n+1)/100, linear interpolation between the order
    statistics around pos, clamped to the extremes."""
    s = np.sort(col)
    n = len(s)
    pos = q * (n + 1) / 100.0
    if pos < 1:
        return float(s[0])
    if pos >= n:
        return float(s[-1])
    k = int(np.floor(pos))
    return float(s[k - 1] + (pos - k) * (s[k] - s[k - 1]))


def ref_aggregate(grid: np.ndarray, agg: str) -> np.ndarray:
    """Cross-series aggregate of a gap-free [S, W] grid -> [W]."""
    if agg == "sum":
        return grid.sum(axis=0)
    if agg == "avg":
        return grid.mean(axis=0)
    if agg == "max":
        return grid.max(axis=0)
    m = re.fullmatch(r"p(\d+)", agg)
    if m:
        return np.array([ref_percentile(grid[:, w], float(m.group(1)))
                         for w in range(grid.shape[1])])
    raise ValueError("reference has no aggregator %r" % agg)


def ref_query(fleet: Fleet, req: dict) -> dict:
    """{group tag value: (timestamps [W], values [W])} for a request of
    one metric; for one of several (`req["metrics"]`, the fleet's first
    n), the same per sub-query under (metric, group tag value)."""
    if not req.get("metrics"):
        return _ref_sub(fleet, req, fleet.values)
    return {(name, group): answer for name in req["metrics"]
            for group, answer in _ref_sub(
                fleet, req, fleet.data[fleet.metrics.index(name)]).items()}


def _ref_sub(fleet: Fleet, req: dict, values: np.ndarray
             ) -> dict[str, tuple[np.ndarray, np.ndarray]]:
    """One sub-query over one metric's [hosts, points] `values`, retained
    columns only: filter hosts, cut the time range (end inclusive),
    downsample, rate, then aggregate each group."""
    c0 = max(-(-(req["start"] - EPOCH_S) // CADENCE_S), 0)
    c1 = min((req["end"] - EPOCH_S) // CADENCE_S + 1, fleet.retained)
    ts = fleet.ts[c0:c1]
    if req.get("hosts"):
        rows = np.asarray([fleet.index[h] for h in req["hosts"]])
        groups: dict[str, list[int]] = {}
        for i, h in enumerate(rows):
            groups.setdefault(fleet.tags[h][req["group_by"]], []).append(i)
        vals = values[rows, c0:c1]
        members = {g: np.asarray(i) for g, i in groups.items()}
    else:
        vals = values[:, c0:c1]
        members = fleet.members(req["group_by"])
    wts, grid = ref_downsample(ts, vals, req["interval_s"], req["ds_fn"])
    if req.get("rate"):
        wts, grid = ref_rate(wts, grid)
    return {g: (wts, ref_aggregate(grid[idx], req["agg"]))
            for g, idx in members.items()}


def parse_answer(payload: list, group_by: str, by_metric: bool = False
                 ) -> dict:
    """An /api/query answer as ref_query keys it: by group tag value, or
    with `by_metric` (a request of several metrics) by (metric, group)."""
    out = {}
    for r in payload:
        if "metric" not in r:
            continue            # statsSummary trailer
        items = sorted((int(k), v) for k, v in r["dps"].items())
        group = r["tags"][group_by]
        out[(r["metric"], group) if by_metric else group] = (
            np.array([k for k, _ in items], np.int64),
            np.array([v for _, v in items], np.float64))
    return out


def compare(got: dict, want: dict) -> str | None:
    """None when the answer equals the reference, else what differs.
    Integer-valued references compare exactly; the rest to 1e-9
    relative (the README's numeric contract), absolute below 1 — a rate
    sum that cancels to zero has no relative scale."""
    if set(got) != set(want):
        return "groups differ: %d answered, %d expected (e.g. %s)" % (
            len(got), len(want), sorted(set(got) ^ set(want))[:3])
    for group, (wts, wval) in want.items():
        gts, gval = got[group]
        if len(gts) != len(wts) or not np.array_equal(gts, wts):
            return "group %s: timestamps differ (%d vs %d points)" % (
                group, len(gts), len(wts))
        if np.array_equal(wval, np.rint(wval)):
            bad = gval != wval
        else:
            bad = np.abs(gval - wval) > 1e-9 * np.maximum(np.abs(wval), 1.0)
        if bad.any():
            i = int(np.argmax(bad))
            return "group %s @%d: got %r, reference %r" % (
                group, int(wts[i]), float(gval[i]), float(wval[i]))
    return None


def check_last(fleet: Fleet, hosts: list[str], payload: list) -> str | None:
    """A lastpoint answer is right when every asked host answers with a
    timestamp at or past the retained range's last point and the value
    the generator gives that host at that timestamp (a backfill may have
    moved the last point on)."""
    by_host = {r["tags"]["hostname"]: r for r in payload}
    for name in hosts:
        r = by_host.get(name)
        if r is None:
            return "lastpoint: no answer for %s" % name
        col, rem = divmod(int(r["timestamp"]) - EPOCH_S * 1000,
                          CADENCE_S * 1000)
        if rem or not fleet.retained - 1 <= col < len(fleet.ts):
            return "lastpoint %s: timestamp %s is no written point" % (
                name, r["timestamp"])
        want = int(fleet.values[fleet.index[name], col])
        if int(r["value"]) != want:
            return "lastpoint %s @%d: got %s, wrote %d" % (
                name, col, r["value"], want)
    return None

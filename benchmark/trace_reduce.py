"""Reduction of a profiler trace (.xplane.pb) to numbers, kept with the
benchmark so that every PR computes them the same way.  Runs in a child
process after the daemon has exited: it imports jax for
jax.profiler.ProfileData, which the benchmark's parent must never do.

    python benchmark/trace_reduce.py <file.xplane.pb[.gz]> <out.json>

Per device plane (/device:TPU:n): busy seconds (union of the intervals
in which an op ran), seconds per XLA module (the jitted entry points, by
the name the trace prints), seconds in collectives and the part of them
with no compute running on that device, the ops that took most time and
the longest idle gaps.  The traced window is the extent of all events on
all planes, host threads included."""

from __future__ import annotations

import gzip
import json
import re
import sys

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter|"
    r"collective-broadcast", re.I)
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals, in their unit."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def subtract_seconds(a: list[tuple[float, float]],
                     b: list[tuple[float, float]]) -> float:
    """Length of union(a) not covered by union(b)."""
    return union_seconds(a + b) - union_seconds(b)


def _top(table: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(table.items(),
                                      key=lambda kv: -kv[1])[:n]]


def module_name(event_name: str) -> str:
    """`jit__group_pipeline(1234567)` -> `jit__group_pipeline`."""
    return re.sub(r"\(\d+\)$", "", event_name)


def reduce_planes(planes: list[dict]) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}] -> the summary.  Pure arithmetic (tested on its
    own); load() builds `planes` from an .xplane.pb."""
    lo, hi = None, None
    for plane in planes:
        for line in plane["lines"]:
            for _, s, d in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
    devices = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        ops = lines.get(OPS_LINE, [])
        if not ops and not lines.get(MODULES_LINE):
            continue
        # a device with no op-level line is read from its modules line
        busy_src = ops or lines[MODULES_LINE]
        busy = [(s, s + d) for _, s, d in busy_src]
        coll = [(s, s + d) for n, s, d in ops if COLLECTIVE.search(n)]
        compute = [(s, s + d) for n, s, d in ops
                   if not COLLECTIVE.search(n)]
        by_op: dict[str, float] = {}
        for n, _, d in ops:
            by_op[n] = by_op.get(n, 0.0) + d * 1e-9
        by_module: dict[str, list] = {}
        for n, _, d in lines.get(MODULES_LINE, []):
            m = by_module.setdefault(module_name(n), [0, 0.0])
            m[0] += 1
            m[1] += d * 1e-9
        # idle gaps, each named by the module that ends it (the host was
        # preparing that dispatch); "unattributed" until the program
        # writes TraceAnnotations at its own phase marks
        marks = sorted((s, s + d, module_name(n))
                       for n, s, d in lines.get(MODULES_LINE, busy_src))
        gaps, edge = [], lo
        for s, e, name in marks:
            if s > edge:
                gaps.append(["unattributed:before:" + name,
                             (s - edge) * 1e-9])
            edge = max(edge, e)
        if hi > edge:
            gaps.append(["unattributed:trace-end", (hi - edge) * 1e-9])
        devices[plane["name"]] = {
            "busy_s": union_seconds(busy) * 1e-9,
            "collective_s": union_seconds(coll) * 1e-9,
            "collective_exposed_s": subtract_seconds(coll, compute) * 1e-9,
            "modules": {k: v for k, v in sorted(by_module.items())},
            "top_ops": _top(by_op),
            "top_gaps": sorted(gaps, key=lambda g: -g[1])[:10],
        }
    n = len(devices)
    window_s = (hi - lo) * 1e-9 if n else 0.0
    out = {"window_s": window_s, "device_count": n, "devices": devices}
    if n:
        out["busy_s"] = sum(d["busy_s"] for d in devices.values()) / n
        out["idle_share"] = 1.0 - out["busy_s"] / window_s
    return out


def load(path: str) -> list[dict]:
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, float(e.start_ns),
                                    float(e.duration_ns))
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def inventory(planes: list[dict]) -> list[dict]:
    """What the trace holds, for a reader who has not seen one."""
    return [{"plane": p["name"],
             "lines": [{"line": ln["name"], "events": len(ln["events"]),
                        "first": [e[0] for e in ln["events"][:3]]}
                       for ln in p["lines"]]} for p in planes]


def main(argv: list[str]) -> int:
    planes = load(argv[0])
    summary = reduce_planes(planes)
    summary["inventory"] = inventory(planes)
    with open(argv[1], "w") as fh:
        json.dump(summary, fh)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""What the host did while the chip idled: the device's idle gaps of a
profiler trace, split over the request phases live in them.

    python tools/trace_gaps.py <file.xplane.pb[.gz]> [--top 10] [--json out.json]

For any jax.profiler trace of a serving daemon (the benchmark's
`--trace 1` run leaves one under
benchmark_out/<cell>/trace/plugins/profile/*/).  While a profile runs
the daemon writes a `tsd.phase` event per latattr phase interval (stats
`phase`, `cpu_ms`, `trace_id`) and a `tsd.span` event per tracer span
(stat `name`) on the handler threads' /host:CPU lines, on the device
planes' clock (opentsdb_tpu/obs/latattr.py, obs/trace.py).  The edges
outside the handler come in too: the event loop's `write` events (stat
`resume_ms`: the `resume` before it ends where the write starts), and a
request's first event's `queue_ms` (the `queue` before the handler
started ends where that event starts).

Per device plane: busy = the union of the `XLA Ops` intervals (else
`XLA Modules`), the window = the extent of all events on all planes,
idle = window - busy — the arithmetic of benchmark/trace_reduce.py, so
idle_s / window_s is that run's `device_idle_share`.  Every idle instant
goes to the phases live at that instant, 1/k each when k requests are
live, and to `no_request` when none is (the client's turnaround, the
response write), so `idle_by_phase_s` sums to `idle_s`.  A phase that
the profile's start or stop cut in two is not in the trace (an
annotation is written when it ends, and only if it began inside the
profile), so its idle instants read `no_request` too: look at the
first gap and at the one ended by `trace-end`.  The longest gaps are listed with
the module that ended them, their seconds by phase and the innermost
`tsd.span`s live in them (thread-seconds, over all handler threads)."""

from __future__ import annotations

import argparse
import gzip
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
# the stats that place an edge outside the handler: it ends where the
# event that carries it starts
EDGE_STATS = {"queue_ms": "queue", "resume_ms": "resume"}
# phases of the event loop's thread, which serves many requests at once
LOOP_PHASES = {"write"}
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
NO_REQUEST = "no_request"


def merged(intervals: list[tuple[float, float]]) -> list[list[float]]:
    """The union of [start, end) intervals as sorted disjoint ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _add(table: dict[str, float], key: str, value: float) -> None:
    table[key] = table.get(key, 0.0) + value


def split_by_phase(gaps: list[tuple[float, float]],
                   phases: list[tuple[float, float, str]]) -> list[dict]:
    """One {phase: time} per gap: each instant of a gap shared equally
    by the phase events live at it, `no_request` when none is."""
    edges = []                      # (time, order, gap index | phase)
    for i, (s, e) in enumerate(gaps):
        edges += [(s, 1, i), (e, 0, i)]     # at one instant: end first
    for s, e, phase in phases:
        edges += [(s, 2, phase), (e, 3, phase)]
    edges.sort(key=lambda ev: ev[:2])
    out: list[dict] = [{} for _ in gaps]
    live: dict[str, int] = {}
    gap, prev = None, 0.0
    for t, kind, what in edges:
        if gap is not None and t > prev:
            k = sum(live.values())
            if not k:
                _add(out[gap], NO_REQUEST, t - prev)
            for phase, n in live.items():
                if n:
                    _add(out[gap], phase, (t - prev) * n / k)
        prev = t
        if kind < 2:
            gap = what if kind == 1 else None
        else:
            live[what] = live.get(what, 0) + (1 if kind == 2 else -1)
    return out


def innermost_spans(gap: tuple[float, float],
                    lines: list[list[tuple[float, float, str]]]) -> dict:
    """{span name: thread-seconds it was the innermost live span of its
    line inside `gap`}; spans of one line nest, so the innermost live
    one is the one that started last."""
    lo, hi = gap
    out: dict[str, float] = {}
    for spans in lines:
        inside = [sp for sp in spans if sp[0] < hi and sp[1] > lo]
        cuts = sorted({lo, hi} | {min(max(t, lo), hi)
                                  for sp in inside for t in sp[:2]})
        for a, b in zip(cuts, cuts[1:]):
            live = [sp for sp in inside if sp[0] <= a and sp[1] >= b]
            if live:
                _add(out, max(live)[2], b - a)
    return out


def reduce_planes(planes: list[dict], top: int = 10) -> dict:
    """`planes`: [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns[, stats dict])]}]}] -> the summary, in seconds.  Pure
    arithmetic (tested on its own); load() builds `planes`."""
    lo = hi = None
    phases, loop, edges, span_lines, overlaps = [], [], [], [], 0
    phase_s: dict[str, float] = {}
    phase_cpu_s: dict[str, float] = {}
    for plane in planes:
        for line in plane["lines"]:
            mine, spans = [], []
            for name, s, d, *rest in line["events"]:
                lo = s if lo is None else min(lo, s)
                hi = s + d if hi is None else max(hi, s + d)
                if plane["name"] != HOST_PLANE or not rest:
                    continue
                if name == "tsd.phase" and "phase" in rest[0]:
                    stats, ph = rest[0], str(rest[0]["phase"])
                    (loop if ph in LOOP_PHASES else mine).append(
                        (s, s + d, ph))
                    _add(phase_s, ph, d * 1e-9)
                    _add(phase_cpu_s, ph,
                         float(stats.get("cpu_ms", 0.0)) * 1e-3)
                    for key, edge in EDGE_STATS.items():
                        if key in stats:
                            ns = float(stats[key]) * 1e6
                            edges.append((s - ns, s, edge))
                            _add(phase_s, edge, ns * 1e-9)
                elif name == "tsd.span" and "name" in rest[0]:
                    spans.append((s, s + d, str(rest[0]["name"])))
            mine.sort()
            # a handler thread serves one request at a time
            overlaps += sum(1 for a, b in zip(mine, mine[1:]) if a[1] > b[0])
            phases += mine
            if spans:
                span_lines.append(spans)
    phases += loop
    n_events = len(phases)
    phases += edges
    devices = {}
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        busy_src = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        if not busy_src:
            continue
        busy = merged([(ev[1], ev[1] + ev[2]) for ev in busy_src])
        gaps = [(a, b) for a, b in zip(
            [lo] + [e for _, e in busy], [s for s, _ in busy] + [hi])
            if b > a]
        modules = sorted((ev[1], ev[1] + ev[2], ev[0])
                         for ev in lines.get(MODULES_LINE) or busy_src)
        by_gap = split_by_phase(gaps, phases)
        by_phase: dict[str, float] = {}
        for table in by_gap:
            for phase, ns in table.items():
                _add(by_phase, phase, ns * 1e-9)
        longest = sorted(range(len(gaps)),
                         key=lambda i: gaps[i][0] - gaps[i][1])[:top]
        devices[plane["name"]] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "idle_s": sum(b - a for a, b in gaps) * 1e-9,
            "idle_by_phase_s": _ranked(by_phase),
            "top_gaps": [{
                "start_s": (gaps[i][0] - lo) * 1e-9,
                "length_s": (gaps[i][1] - gaps[i][0]) * 1e-9,
                # the module running when the gap ends, else the next
                "ended_by": next(
                    (re.sub(r"\(\d+\)$", "", name)
                     for _s, e, name in modules if e > gaps[i][1]),
                    "trace-end"),
                "by_phase_s": _ranked(
                    {p: ns * 1e-9 for p, ns in by_gap[i].items()}),
                "spans_s": _ranked(
                    {n: ns * 1e-9 for n, ns in innermost_spans(
                        gaps[i], span_lines).items()}),
            } for i in longest],
        }
    n = len(devices)
    out = {"window_s": (hi - lo) * 1e-9 if n else 0.0, "device_count": n,
           "phase_events": n_events, "overlapping_phase_events": overlaps,
           "phase_s": _ranked(phase_s), "phase_cpu_s": _ranked(phase_cpu_s),
           "devices": devices}
    if n:       # the mean over devices, as trace_reduce.py's busy_s
        out["idle_s"] = sum(d["idle_s"] for d in devices.values()) / n
        out["idle_share"] = out["idle_s"] / out["window_s"]
        mean: dict[str, float] = {}
        for d in devices.values():
            for phase, s in d["idle_by_phase_s"].items():
                _add(mean, phase, s / n)
        out["idle_by_phase_s"] = _ranked(mean)
    return out


def _ranked(table: dict[str, float]) -> dict[str, float]:
    return dict(sorted(table.items(), key=lambda kv: -kv[1]))


def load(path: str) -> list[dict]:
    """As benchmark/trace_reduce.load (copied: tools/ imports nothing
    from the benchmark), with the stats of the tsd.* events."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = ProfileData.from_serialized_xspace(fh.read())
    else:
        data = ProfileData.from_file(path)
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [
                            (e.name, float(e.start_ns), float(e.duration_ns))
                            + ((dict(e.stats),)
                               if e.name in ("tsd.phase", "tsd.span") else ())
                            for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def _table(table: dict[str, float]) -> str:
    return ", ".join("%s %.3f" % kv for kv in table.items()) or "-"


def main(argv: list[str]) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", help="an .xplane.pb or .xplane.pb.gz")
    ap.add_argument("--top", type=int, default=10,
                    help="how many of the longest gaps to list")
    ap.add_argument("--json", default=None, help="write the summary here")
    args = ap.parse_args(argv)
    summary = reduce_planes(load(args.trace), args.top)
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(summary, fh, indent=1)
    if not summary["device_count"]:
        print("no device plane with an operation in %s" % args.trace)
        return 1
    print("window %.3f s, %d device(s), idle %.3f s (%.1f %%); %d tsd.phase "
          "events (%d overlapping on one line)" % (
              summary["window_s"], summary["device_count"],
              summary["idle_s"], 100.0 * summary["idle_share"],
              summary["phase_events"], summary["overlapping_phase_events"]))
    print("phase seconds in the trace:  " + _table(summary["phase_s"]))
    print("of them on a core (cpu_ms):  " + _table(summary["phase_cpu_s"]))
    print("idle seconds by phase:       " + _table(
        summary["idle_by_phase_s"]))
    for name, dev in summary["devices"].items():
        print("%s: the %d longest idle gaps" % (name, len(dev["top_gaps"])))
        for gap in dev["top_gaps"]:
            print("  %.3f s at %.3f s, ended by %s | phases: %s | spans: %s"
                  % (gap["length_s"], gap["start_s"], gap["ended_by"],
                     _table(gap["by_phase_s"]), _table(gap["spans_s"])))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

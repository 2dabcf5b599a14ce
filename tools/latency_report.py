"""Differential latency-attribution reports: where did the
milliseconds go — and where did they MOVE.

Input is any two latency windows, from either source:

  * ``GET /api/diag/latency`` captures (obs/latattr.py) — the whole
    capture is one window (cumulative since daemon start);
  * ``bench_qps.json`` artifacts (tools/bench_qps.py) — each embeds a
    proper timed-window decomposition per phase
    (``endToEnd.{off,on}.phaseDecomposition``).

Because every request reports the SAME fixed ordered phase set
(latattr.PHASES, zero-filled), two windows diff phase-by-phase with no
key reconciliation: the report is one table of per-request
milliseconds per phase, before vs after, with the delta and each
phase's share of the after-window.

    # two capture files (curl /api/diag/latency > a.json ... > b.json)
    python tools/latency_report.py a.json b.json

    # two bench artifacts (e.g. before/after an optimisation)
    python tools/latency_report.py bench_qps.old.json bench_qps.json

    # one bench artifact: batching off vs on
    python tools/latency_report.py bench_qps.json
"""

from __future__ import annotations

import argparse
import json
import sys

PHASES = ("parse", "admission_wait", "plan", "batch_rendezvous",
          "dispatch", "device_wait", "serialize", "flush")


def window_delta(before: dict | None, after: dict | None) -> dict | None:
    """One timed window from two /api/diag/latency captures of the
    SAME daemon: per-phase count/totalMs deltas, per-request mean, and
    share of the window's total attributed time.  bench_qps.py embeds
    exactly this as ``phaseDecomposition``."""
    if not before or not after:
        return None
    requests = after.get("requests", 0) - before.get("requests", 0)
    deltas: dict[str, dict] = {}
    window_ms = 0.0
    for phase in PHASES:
        b = before.get("overall", {}).get(phase, {})
        a = after.get("overall", {}).get(phase, {})
        total = a.get("totalMs", 0.0) - b.get("totalMs", 0.0)
        window_ms += max(total, 0.0)
        deltas[phase] = {
            "count": a.get("count", 0) - b.get("count", 0),
            "totalMs": round(total, 3),
            # cumulative quantiles from the after capture — the window
            # dominates them on a freshly-spawned daemon
            "p50Ms": a.get("p50Ms", 0.0),
            "p99Ms": a.get("p99Ms", 0.0),
        }
    for phase, entry in deltas.items():
        entry["msPerRequest"] = round(
            entry["totalMs"] / requests, 4) if requests > 0 else 0.0
        entry["share"] = round(
            entry["totalMs"] / window_ms, 4) if window_ms > 0 else 0.0
    return {"requests": requests, "windowMs": round(window_ms, 3),
            "phases": deltas}


def _normalize(payload: dict, label: str) -> dict:
    """One window as {requests, phases: {phase: {msPerRequest,
    p99Ms}}} from either a diag capture or a bench decomposition."""
    if "overall" in payload:                    # /api/diag/latency
        requests = payload.get("requests", 0)
        phases = {}
        for phase in PHASES:
            entry = payload["overall"].get(phase, {})
            total = entry.get("totalMs", 0.0)
            phases[phase] = {
                "msPerRequest": total / requests if requests else 0.0,
                "p99Ms": entry.get("p99Ms", 0.0),
            }
        return {"label": label, "requests": requests, "phases": phases}
    if "phases" in payload:                     # a window_delta dict
        requests = payload.get("requests", 0)
        phases = {p: {"msPerRequest": e.get("msPerRequest", 0.0),
                      "p99Ms": e.get("p99Ms", 0.0)}
                  for p, e in payload["phases"].items()}
        return {"label": label, "requests": requests, "phases": phases}
    raise SystemExit(
        "%s: not a /api/diag/latency capture or phase decomposition "
        "(expected an 'overall' or 'phases' section)" % label)


def _bench_windows(artifact: dict, path: str) -> list[dict]:
    """The windows a bench_qps.json artifact carries (off/on arms)."""
    out = []
    e2e = artifact.get("endToEnd", {})
    for arm in ("off", "on"):
        decomposition = e2e.get(arm, {}).get("phaseDecomposition")
        if decomposition:
            out.append(_normalize(decomposition,
                                  "%s[%s]" % (path, arm)))
    return out


def load_windows(path: str) -> list[dict]:
    with open(path) as fh:
        payload = json.load(fh)
    if "endToEnd" in payload or "dispatchLayer" in payload:
        windows = _bench_windows(payload, path)
        if not windows:
            raise SystemExit(
                "%s: bench artifact has no phaseDecomposition — "
                "re-run tools/bench_qps.py (without --skip-e2e)" % path)
        return windows
    return [_normalize(payload, path)]


def render(before: dict, after: dict) -> str:
    """The per-phase 'where did the milliseconds move' table."""
    total_b = sum(e["msPerRequest"] for e in before["phases"].values())
    total_a = sum(e["msPerRequest"] for e in after["phases"].values())
    lines = [
        "latency attribution: %s (%d req) -> %s (%d req)"
        % (before["label"], before["requests"],
           after["label"], after["requests"]),
        "",
        "%-17s %12s %12s %12s %8s %10s" % (
            "phase", "before ms/q", "after ms/q", "delta ms/q",
            "share", "p99 after"),
    ]
    for phase in PHASES:
        b = before["phases"].get(phase, {"msPerRequest": 0.0})
        a = after["phases"].get(phase, {"msPerRequest": 0.0,
                                        "p99Ms": 0.0})
        delta = a["msPerRequest"] - b["msPerRequest"]
        share = a["msPerRequest"] / total_a if total_a > 0 else 0.0
        lines.append("%-17s %12.3f %12.3f %+12.3f %7.1f%% %10.3f" % (
            phase, b["msPerRequest"], a["msPerRequest"], delta,
            share * 100, a.get("p99Ms", 0.0)))
    lines.append("%-17s %12.3f %12.3f %+12.3f %8s" % (
        "TOTAL", total_b, total_a, total_a - total_b, ""))
    mover = max(
        PHASES,
        key=lambda p: abs(after["phases"].get(p, {}).get("msPerRequest",
                                                         0.0)
                          - before["phases"].get(p, {}).get(
                              "msPerRequest", 0.0)))
    moved = (after["phases"].get(mover, {}).get("msPerRequest", 0.0)
             - before["phases"].get(mover, {}).get("msPerRequest", 0.0))
    lines.append("")
    lines.append("biggest mover: %s (%+.3f ms/query)" % (mover, moved))
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(
        description="Diff two latency-attribution windows "
                    "(/api/diag/latency captures or bench_qps.json "
                    "artifacts) into a per-phase delta table.")
    ap.add_argument("before", help="first capture/artifact")
    ap.add_argument("after", nargs="?",
                    help="second capture/artifact (omit to diff a "
                         "single bench artifact's off vs on arms)")
    ap.add_argument("--json", action="store_true",
                    help="emit the normalized windows as JSON instead "
                         "of the table")
    args = ap.parse_args(argv)
    if args.after is None:
        windows = load_windows(args.before)
        if len(windows) < 2:
            raise SystemExit(
                "%s: need two windows to diff — pass a second file or "
                "a bench artifact with both off/on arms" % args.before)
        before, after = windows[0], windows[1]
    else:
        before = load_windows(args.before)[0]
        after = load_windows(args.after)[-1]
    if args.json:
        print(json.dumps({"before": before, "after": after}, indent=2,
                         sort_keys=True))
    else:
        print(render(before, after))
    return 0


if __name__ == "__main__":
    sys.exit(main())

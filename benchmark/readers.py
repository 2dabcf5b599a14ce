"""Readers: how a number is taken from what a run observed.

`stat()` evaluates a statistic of the load generator's own records (the
end-to-end metrics, declared in the traffic file, and the per-layer
metrics of kind `loadgen`).  `read()` evaluates one per-layer metric
from its layers/<metric>.json — or calls `read(ctx)` of
layers/<metric>.py where a reader needs code.  A reader that finds
nothing to read returns None and the metric is left out of the line."""

from __future__ import annotations

import importlib.util
import json
import os
import re

import numpy as np

from benchmark import bytes as bytes_model
from benchmark.daemon import counter_sum

PHASES = ("parse", "admission_wait", "plan", "batch_rendezvous",
          "dispatch", "device_wait", "serialize", "flush")


def _of_classes(records, classes):
    return [r for r in records
            if r.ok and (not classes or r.req["cls"] in classes)]


def _last_ack(ctx: dict) -> float:
    return max((p[1] for p in ctx.get("puts") or []), default=0.0)


def stat(spec: dict, ctx: dict) -> float | None:
    kind, records = spec["stat"], ctx["records"]
    if kind in ("latency_percentile", "latency_mean"):
        # `while_writing`: only requests answered before the last ack
        # (writers that reach the end of their data stop early)
        lat = [(r.done - r.due) * 1e3
               for r in _of_classes(records, spec.get("classes"))
               if not spec.get("while_writing") or r.done <= _last_ack(ctx)]
        if not lat:
            return None
        return float(np.mean(lat) if kind == "latency_mean"
                     else np.percentile(lat, spec["q"]))
    if kind == "late_percentile":
        late = [(r.sent - r.due) * 1e3 for r in records
                if r.sent is not None]
        return float(np.percentile(late, spec["q"])) if late else None
    if kind == "points_rate":
        # stored points covered by the requests issued inside the window
        # that completed and were right, over the seconds from the
        # window's start to the last of those completions
        good = _of_classes(records, spec.get("classes"))
        if not good:
            return None
        return (sum(r.req["points"] for r in good) / 1e6
                / max(r.done for r in good))
    puts = ctx.get("puts") or []
    if kind == "ingest_rate":
        if not puts or not ctx.get("puts_stored"):
            return None
        return sum(p[2] for p in puts) / 1e6 / max(p[1] for p in puts)
    if kind == "put_ms_per_body":
        return (float(np.mean([(p[1] - p[0]) * 1e3 for p in puts]))
                if puts else None)
    raise ValueError("unknown stat %r" % kind)


# --------------------------------------------------------------------- #
# latattr: the delta arithmetic of tools/latency_report.window_delta,   #
# per route (copied; percentiles there are bucket edges and not read)   #
# --------------------------------------------------------------------- #

def latattr_delta(before: dict, after: dict, route: str) -> dict:
    """{"requests": n, phase: total ms} of `route` between two
    /api/diag/latency captures of one daemon."""
    def totals(capture):
        out = dict.fromkeys(PHASES, 0.0)
        n = 0
        for prof in capture.get("profiles", []):
            if prof["route"] == route:
                n += prof["count"]
                for ph in PHASES:
                    out[ph] += prof["phases"].get(ph, {}).get("totalMs", 0.0)
        out["requests"] = n
        return out
    b, a = totals(before), totals(after)
    return {k: a[k] - b[k] for k in a}


def _latattr(r: dict, ctx: dict) -> float | None:
    d = latattr_delta(ctx["lat_before"], ctx["lat_after"], r["route"])
    if d["requests"] <= 0:
        return None
    return sum(d[ph] for ph in r["phases"]) / d["requests"]


def _delta(ctx: dict, names: list[str]) -> float:
    return sum(counter_sum(ctx["ctr_after"], n)
               - counter_sum(ctx["ctr_before"], n) for n in names)


def _counter_ratio(r: dict, ctx: dict) -> float | None:
    den = _delta(ctx, r["den"])
    return None if den <= 0 else r.get("scale", 1.0) * _delta(
        ctx, r["num"]) / den


def _counter_delta(r: dict, ctx: dict) -> float | None:
    return _delta(ctx, r["names"])


def _plan_share(r: dict, ctx: dict) -> float | None:
    plans = ctx.get("plans") or []
    if not plans:
        return None
    return 100.0 * sum(1 for p in plans if p.get(r["field"])) / len(plans)


def _device_memory(r: dict, ctx: dict) -> float | None:
    mem = ctx["device"]["memory"]
    if any(m.get(r["field"]) is None for m in mem):
        return None
    if r["stat"] == "max_gb":
        return max(m[r["field"]] for m in mem) / 1e9
    if r["stat"] == "dev0_share":
        total = sum(m[r["field"]] for m in mem)
        return 100.0 * mem[0][r["field"]] / total if total else None
    raise ValueError("unknown device_memory stat %r" % r["stat"])


def traced_requests(ctx: dict) -> tuple[float, float]:
    """(requests, bytes they need) inside the traced window, a request
    that straddles an edge counting by the share of it inside."""
    t0, t1 = ctx["trace_window"]
    n = nbytes = 0.0
    for rec in ctx["records"]:
        if not rec.ok or rec.done <= t0 or rec.sent >= t1:
            continue
        share = ((min(rec.done, t1) - max(rec.sent, t0))
                 / max(rec.done - rec.sent, 1e-9))
        n += share
        nbytes += share * bytes_model.request_bytes(rec.req, rec.groups)
    return n, nbytes


def _trace(r: dict, ctx: dict) -> float | None:
    tr = ctx.get("trace")
    if not tr or not tr.get("device_count"):
        return None
    devs = list(tr["devices"].values())
    if r["stat"] == "idle_share":
        return 100.0 * tr["idle_share"]
    n_req, nbytes = traced_requests(ctx)
    if n_req <= 0:
        return None
    pat = re.compile(r.get("pattern", ".*"))
    if r["stat"] == "collective_ms_per_req":
        return 1e3 * np.mean([d["collective_s"] for d in devs]) / n_req
    kernel_s = float(np.mean([
        sum(s for name, (_, s) in d["modules"].items() if pat.search(name))
        for d in devs]))
    if r["stat"] == "module_ms_per_req":
        return 1e3 * kernel_s / n_req
    if r["stat"] == "roofline":
        if kernel_s <= 0:
            return None
        # the traffic is spread over the chips, so is the bandwidth
        floor_s = bytes_model.roofline_seconds(nbytes, ctx["peaks"]) / len(
            devs)
        return 100.0 * floor_s / kernel_s
    raise ValueError("unknown trace stat %r" % r["stat"])


KINDS = {"loadgen": stat, "latattr": _latattr,
         "counter_ratio": _counter_ratio, "counter_delta": _counter_delta,
         "plan_share": _plan_share, "device_memory": _device_memory,
         "trace": _trace}


def load_layer(root: str, name: str) -> dict:
    with open(os.path.join(root, "layers", name + ".json")) as fh:
        return json.load(fh)


def read(root: str, spec: dict, ctx: dict) -> float | None:
    """`spec` is load_layer()'s; layers/<name>.py, where there is one,
    takes the reader's place."""
    name = spec["name"]
    code = os.path.join(root, "layers", name + ".py")
    if os.path.exists(code):
        spec = importlib.util.spec_from_file_location(
            "benchmark_layer_" + re.sub(r"\W", "_", name), code)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read(ctx)
    return KINDS[spec["reader"]["kind"]](spec["reader"], ctx)

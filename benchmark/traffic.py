"""The one general traffic generator: a mix is a data file of parameters
(traffic/<mix>.json), and every request, arrival time and host choice is
drawn here from --seed.  A new mix over the existing operators needs no
code: classes name their reference by the fields chip_smoke.request_table
used (group_by, interval_s, ds_fn, agg, rate) and how many of the fleet's
metrics they ask for (metrics)."""

from __future__ import annotations

import json
import os
import urllib.parse

import numpy as np

from benchmark.tsbs import CADENCE_S, EPOCH_S, Fleet


def load_mix(root: str, name: str) -> dict:
    """traffic/<name>.json; `readers.from` borrows another mix's reader
    classes at `rate_scale` times its rate."""
    with open(os.path.join(root, "traffic", name + ".json")) as fh:
        mix = json.load(fh)
    readers = mix.get("readers") or {}
    if "from" in readers:
        base = load_mix(root, readers["from"])["readers"]
        merged = dict(base)
        merged.update({k: v for k, v in readers.items()
                       if k not in ("from", "rate_scale")})
        if "rate_per_s" in base:
            merged["rate_per_s"] = (base["rate_per_s"]
                                    * readers.get("rate_scale", 1.0))
        mix["readers"] = merged
    return mix


class Generator:
    """Requests of one mix over one fleet, deterministic in the seed."""

    def __init__(self, fleet: Fleet, readers: dict, seed: int):
        self.fleet = fleet
        self.classes = readers["classes"]
        self.seed = seed
        self.retained_s = fleet.retained * CADENCE_S
        choice = readers.get("host_choice", {"kind": "uniform"})
        rng = np.random.default_rng([seed, 11])
        self.perm = rng.permutation(fleet.hosts)
        if choice["kind"] == "zipf":
            w = 1.0 / np.arange(1, fleet.hosts + 1) ** choice["s"]
        elif choice["kind"] == "uniform":
            w = np.ones(fleet.hosts)
        else:
            raise ValueError("unknown host_choice %r" % choice["kind"])
        self.host_p = w / w.sum()
        # a class with `window_pool` re-asks a few fixed windows (the
        # same panels of a dashboard), drawn once from the seed
        self.pools = {}
        for i, cls in enumerate(self.classes):
            if cls.get("window_pool"):
                prng = np.random.default_rng([seed, 12, i])
                self.pools[cls["name"]] = [
                    self._draw_start(cls, prng)
                    for _ in range(cls["window_pool"])]

    def _span(self, cls: dict) -> int:
        return min(cls["span_s"], self.retained_s)

    def _draw_start(self, cls: dict, rng) -> int:
        """A window start drawn uniformly inside the retained range, as
        TSBS draws it, on a multiple of the class's own downsample
        interval: the only alignment a mix has."""
        align = cls["interval_s"]
        slots = (self.retained_s - self._span(cls)) // align
        return EPOCH_S + align * int(rng.integers(slots + 1))

    def instance(self, cls: dict, rng) -> dict:
        """One request of class `cls`: hosts and window from `rng`."""
        n = min(cls.get("n_hosts", 0), self.fleet.hosts)
        hosts = None
        if n:
            ranks = rng.choice(self.fleet.hosts, size=n, replace=False,
                               p=self.host_p)
            hosts = ["host_%d" % self.perm[r] for r in ranks]
        req = {"cls": cls["name"], "kind": cls.get("kind", "query"),
               "hosts": hosts}
        if req["kind"] == "last":
            spec = "%s{hostname=%s}" % (self.fleet.metric, "|".join(hosts))
            req["path"] = ("/api/query/last?timeseries="
                           + urllib.parse.quote(spec, safe=""))
            req["points"] = n
            return req
        pool = self.pools.get(cls["name"])
        start = (pool[int(rng.integers(len(pool)))] if pool
                 else self._draw_start(cls, rng))
        end = start + self._span(cls) - 1           # end is inclusive
        # a class of `metrics` n > 1 asks for the fleet's first n metrics
        # in one request, one m= each (TSBS's double-groupby-5 / -all)
        count = cls.get("metrics", 1)
        names = ([self.fleet.metric] if count == 1
                 else self.fleet.metrics[:count])
        if len(names) != count:
            raise ValueError("class %s asks for %d metrics; the fleet has %d"
                             % (cls["name"], count, len(names)))
        if count > 1:
            req["metrics"] = names
        subs = "".join("&m=" + urllib.parse.quote(cls["m"].replace(
            "$metric", name).replace("$hosts", "|".join(hosts or [])),
            safe="") for name in names)
        req.update(
            start=start, end=end,
            path="/api/query?start=%d&end=%d%s" % (start, end, subs),
            points=count * (n or self.fleet.hosts) * (self._span(cls)
                                                      // CADENCE_S),
            **{k: cls[k] for k in ("group_by", "interval_s", "ds_fn", "agg")},
            rate=bool(cls.get("rate")))
        return req

    def open_schedule(self, rate_per_s: float, seconds: float, phase: int
                      ) -> tuple[np.ndarray, list[dict]]:
        """Arrivals of an open loop over `seconds`: (due times [n] in
        seconds from the window's start, one request each).  Classes with
        a `share` arrive as one Poisson stream of `rate_per_s`, the class
        of each arrival drawn by share; a class with `period_s` is asked
        once per period from a seeded offset (a dashboard refreshing its
        overview row), whatever the rate."""
        rng = np.random.default_rng([self.seed, 13, phase])
        gaps = rng.exponential(1.0 / rate_per_s,
                               int(rate_per_s * seconds * 1.5) + 16)
        due = np.cumsum(gaps)
        due = due[due < seconds]
        shared = [c for c in self.classes if "share" in c]
        shares = np.array([c["share"] for c in shared], float)
        picks = rng.choice(len(shared), size=len(due),
                           p=shares / shares.sum())
        arrivals = [(float(d), shared[k]) for d, k in zip(due, picks)]
        for cls in self.classes:
            if "period_s" in cls:
                first = float(rng.uniform(0.0, cls["period_s"]))
                arrivals += [(float(d), cls) for d in np.arange(
                    first, seconds, cls["period_s"])]
        arrivals.sort(key=lambda a: a[0])
        return (np.array([d for d, _ in arrivals]),
                [self.instance(cls, rng) for _, cls in arrivals])

    def replay_list(self) -> list[dict]:
        """One cycle of a closed-loop mix: each class `count` times,
        windows drawn per request, order shuffled from the seed."""
        rng = np.random.default_rng([self.seed, 14])
        cycle = [self.instance(cls, rng)
                 for cls in self.classes for _ in range(cls["count"])]
        return [cycle[i] for i in rng.permutation(len(cycle))]

    def warm_instances(self, cls: dict, n: int) -> list[dict]:
        rng = np.random.default_rng(
            [self.seed, 15, self.classes.index(cls)])
        return [self.instance(cls, rng) for _ in range(n)]

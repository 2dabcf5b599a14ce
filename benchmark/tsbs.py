"""The TSBS DevOps cpu-only fleet, generated from --seed with numpy alone.

Copied from chip_smoke.py (PR 21), where it was proven on the chip; the
benchmark keeps its own copy because a later PR may change chip_smoke.py
and none may change the yardstick.  tests/benchmark pins the two equal.
"""

from __future__ import annotations

import numpy as np

CPU_FIELDS = ("usage_user", "usage_system", "usage_idle", "usage_nice",
              "usage_iowait", "usage_irq", "usage_softirq", "usage_steal",
              "usage_guest", "usage_guest_nice")
# Eight of TSBS's ten host tags: the reference caps a series at 8 tags
# (Const.java:28); service_version and service_environment are dropped.
REGIONS = {
    "us-east-1": "abcde", "us-west-1": "ab", "us-west-2": "abc",
    "eu-west-1": "abc", "eu-central-1": "ab", "ap-southeast-1": "ab",
    "ap-southeast-2": "ab", "ap-northeast-1": "ac", "sa-east-1": "abc",
}
OSES = ("Ubuntu16.10", "Ubuntu16.04LTS", "Ubuntu15.10")
ARCHES = ("x64", "x86")
TEAMS = ("SF", "NYC", "LON", "CHI")
TAG_KEYS = ("hostname", "region", "datacenter", "rack", "os", "arch",
            "team", "service")
EPOCH_S = 1451606400          # 2016-01-01T00:00:00Z, TSBS's default start
CADENCE_S = 10


def make_fleet(hosts: int, seed: int) -> list[dict]:
    """Tag sets of `hosts` TSBS hosts, deterministic in `seed`."""
    rng = np.random.default_rng([seed, 0])
    regions = sorted(REGIONS)
    fleet = []
    for h in range(hosts):
        region = regions[int(rng.integers(len(regions)))]
        zones = REGIONS[region]
        fleet.append({
            "hostname": "host_%d" % h,
            "region": region,
            "datacenter": region + zones[int(rng.integers(len(zones)))],
            "rack": str(int(rng.integers(100))),
            "os": OSES[int(rng.integers(len(OSES)))],
            "arch": ARCHES[int(rng.integers(len(ARCHES)))],
            "team": TEAMS[int(rng.integers(len(TEAMS)))],
            "service": str(int(rng.integers(20))),
        })
    return fleet


def make_values(hosts: int, points: int, seed: int, field: int
                ) -> np.ndarray:
    """[hosts, points] int64 gauge values: TSBS's clamped random walk
    (start uniform in [0, 100], unit-normal steps rounded to integers,
    clamped to [0, 100] at every step).  The first `p` columns of a
    longer walk equal the walk of length `p`: a backfill continues it."""
    rng = np.random.default_rng([seed, 1, field])
    out = np.empty((hosts, points), np.int64)
    cur = rng.integers(0, 101, hosts)
    steps = np.rint(rng.normal(0.0, 1.0, (points, hosts))).astype(np.int64)
    for i in range(points):
        cur = np.clip(cur + steps[i], 0, 100)
        out[:, i] = cur
    return out


def timestamps(points: int) -> np.ndarray:
    return EPOCH_S + CADENCE_S * np.arange(points, dtype=np.int64)


class Fleet:
    """The generated deployment: tags, timestamps and values of the first
    `metrics` cpu fields in TSBS's own order (its GetCPUMetricsSlice(n)
    takes them so), `retained` columns of which are loaded before the
    window (the rest is what a backfill writes).  Field f's walk is
    make_values(..., f) whatever `metrics` is, so the first field's data
    is the same in a store of one metric or of ten."""

    def __init__(self, hosts: int, retained: int, extra: int, seed: int,
                 metrics: int = 1):
        if not 1 <= metrics <= len(CPU_FIELDS):
            raise ValueError("a fleet holds 1 to %d cpu metrics, not %r"
                             % (len(CPU_FIELDS), metrics))
        self.metrics = ["cpu." + f for f in CPU_FIELDS[:metrics]]
        self.metric = self.metrics[0]
        self.tags = make_fleet(hosts, seed)
        self.retained = retained
        self.ts = timestamps(retained + extra)
        # [metrics, hosts, points]; `values` is the first metric's
        # [hosts, points], which lastpoint and the backfill read
        self.data = np.empty((metrics, hosts, retained + extra), np.int64)
        for f in range(metrics):
            self.data[f] = make_values(hosts, retained + extra, seed, f)
        self.values = self.data[0]
        self.index = {t["hostname"]: h for h, t in enumerate(self.tags)}
        self._members: dict[str, dict[str, np.ndarray]] = {}

    @property
    def hosts(self) -> int:
        return len(self.tags)

    def members(self, tag: str) -> dict[str, np.ndarray]:
        """{tag value: host rows} over the whole fleet."""
        if tag not in self._members:
            groups: dict[str, list[int]] = {}
            for h, t in enumerate(self.tags):
                groups.setdefault(t[tag], []).append(h)
            self._members[tag] = {g: np.asarray(r)
                                  for g, r in groups.items()}
        return self._members[tag]

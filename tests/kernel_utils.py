"""Helpers to build padded [series, time] batches for kernel tests, and
to read what a traced kernel holds."""

import numpy as np

PAD_TS = np.iinfo(np.int64).max


def batch(series):
    """series: list of (ts_list, val_list). Returns ts[S,N], val[S,N], mask[S,N]."""
    n = max((len(ts) for ts, _ in series), default=1)
    n = max(n, 1)
    s = len(series)
    ts = np.full((s, n), PAD_TS, dtype=np.int64)
    val = np.zeros((s, n), dtype=np.float64)
    mask = np.zeros((s, n), dtype=bool)
    for i, (t, v) in enumerate(series):
        k = len(t)
        ts[i, :k] = t
        val[i, :k] = v
        mask[i, :k] = True
    return ts, val, mask


def collect(ts, val, mask):
    """Extract (ts, value) pairs where mask, as plain Python lists."""
    ts = np.asarray(ts)
    val = np.asarray(val)
    mask = np.asarray(mask)
    return [(int(t), float(v)) for t, v, m in zip(ts.ravel(), val.ravel(),
                                                  mask.ravel()) if m]


def primitives(jaxpr) -> set:
    """Every primitive name of a jaxpr, sub-jaxprs (cond branches, scans,
    nested jits) included."""
    out = set()
    for eqn in jaxpr.eqns:
        out.add(eqn.primitive.name)
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    out |= primitives(inner)
    return out

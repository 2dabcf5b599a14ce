"""Runtime ordering recorder: the dynamic half of order_contract.

The static analyzer (tools/lint/ordering.py) verifies declared
happens-before contracts (`# order: <a> before <b>`) against the call
tree; this module verifies them against EXECUTIONS.  A small patch
table wraps the product methods that realise tagged order events —
`Series.append` is the memstore-write, `DiskPersistence.journal` is
the wal-append, and so on — and every wrapped call appends the event
to a per-stream log.  A stream is one request trace when the ambient
obs.trace is active (`trace:<id>`), else the recording thread
(`thread:<ident>`): ordering contracts are per-request properties, so
events from different requests must never be compared against each
other.

Only the FIRST occurrence of each event per stream is retained — the
cross-check compares first-occurrence ranks, so a million appends cost
one dict entry, not a million tuples.

`cross_check()` diffs the streams against the lint's static contract
table (tools.lint.ordering.static_order_table, resolved lazily and
cached so a session pays for one tree walk at most):

  san-order-violation   a stream emitted b before a for a declared
                        contract `a before b` — the static verifier
                        missed an interleaving that really happened
                        (or an unannotated call path sequences the
                        pair).  Note level: the static analyzer gates;
                        the runtime check reports.
  san-order-gap         an instrumented, contracted event was never
                        observed all session — uncovered path or a
                        probe left behind after the tagged site moved.
                        Events with no probe (catch-up-pull,
                        rejoin-ready, wal-close, spill-close,
                        flightrec-shutdown,
                        permit-release) are exempt: they fire on
                        rejoin/shutdown paths a normal session never
                        takes, and an always-on gap report is noise.

Both are deterministic given the same run: streams and contracts are
sorted before reporting, and messages carry no stream ids (fingerprint
dedup collapses the same inversion across ten thousand requests into
one finding).
"""

from __future__ import annotations

import threading

from tools.sanitize.report import REPORTER, caller_site

# captured before tools/sanitize/locks.py patches the factories
_RealLock = threading.Lock

_state_lock = _RealLock()
# stream key -> {event -> (rank, path, line)}; rank is the stream's
# event counter at first occurrence
_streams: dict[str, dict[str, tuple[int, str, int]]] = {}
# stream key -> events recorded so far (including repeats)
_counts: dict[str, int] = {}

_enabled = False
_static_table: dict | None = None

# module -> ((class, method, event, when), ...); `when` is "after" for
# the write side (the event happened only if the call returned) and
# "before" for the publish side (recording the ack/mark at entry keeps
# its rank earliest — conservative for b-before-a detection).
PATCH_TABLE: dict[str, tuple[tuple[str, str, str, str], ...]] = {
    "opentsdb_tpu.storage.memstore": (
        ("Series", "append", "memstore-write", "after"),
        ("Series", "append_batch", "memstore-write", "after"),
        ("MemStore", "notify_mutation", "memstore-mark", "before"),
    ),
    "opentsdb_tpu.storage.persist": (
        ("DiskPersistence", "journal", "wal-append", "after"),
    ),
    "opentsdb_tpu.tsd.replication": (
        ("ReplicationManager", "_ship", "replica-ship", "before"),
    ),
    "opentsdb_tpu.tsd.rpcs": (
        ("PutDataPointRpc", "_respond_put", "ingest-ack", "before"),
    ),
    "opentsdb_tpu.tsd.http": (
        ("HttpQuery", "send_reply", "response-write", "after"),
    ),
}

# (cls, method name, original function) for unpatch_all()
_patched: list[tuple[type, str, object]] = []


def configure(enabled: bool) -> None:
    global _enabled
    _enabled = enabled


def reset() -> None:
    with _state_lock:
        _streams.clear()
        _counts.clear()


def snapshot_state() -> tuple:
    """Copy of the accumulated per-stream event logs; fixture tests
    that seed deliberate inversions snapshot/restore around themselves
    so a TSDBSAN=1 session's real streams survive them."""
    with _state_lock:
        return ({k: dict(v) for k, v in _streams.items()},
                dict(_counts))


def restore_state(snapshot: tuple) -> None:
    streams, counts = snapshot
    with _state_lock:
        _streams.clear()
        for k, v in streams.items():
            _streams[k] = dict(v)
        _counts.clear()
        _counts.update(counts)


# --------------------------------------------------------------------- #
# Recording                                                             #
# --------------------------------------------------------------------- #

_obs_trace = None   # resolved lazily; False when the import failed


def _stream_key() -> str:
    global _obs_trace
    if _obs_trace is None:
        try:
            from opentsdb_tpu.obs import trace as obs_trace
            _obs_trace = obs_trace
        except Exception:       # noqa: BLE001 — recording must not raise
            _obs_trace = False
    t = _obs_trace.active() if _obs_trace else None
    if t is not None:
        return "trace:" + t.trace_id
    return "thread:%d" % threading.get_ident()


def record(event: str) -> None:
    """Append `event` to the calling stream's log (first occurrence
    only; repeats just advance the rank counter).  The stack walk for
    the anchor site only happens on first occurrence — this sits on
    the per-append hot path of the sanitized tier-1 run, and the 2x
    overhead pin (tests/test_sanitizer_overhead.py) holds it there."""
    if not _enabled:
        return
    key = _stream_key()
    with _state_lock:
        rank = _counts.get(key, 0)
        _counts[key] = rank + 1
        ev = _streams.setdefault(key, {})
        known = event in ev
    if known:
        return
    path, line, _fn = caller_site()
    with _state_lock:
        ev.setdefault(event, (rank, path, line))


def observed_events() -> set[str]:
    with _state_lock:
        out: set[str] = set()
        for ev in _streams.values():
            out.update(ev)
    return out


def streams() -> dict[str, dict[str, tuple[int, str, int]]]:
    with _state_lock:
        return {k: dict(v) for k, v in _streams.items()}


# --------------------------------------------------------------------- #
# Instrumentation                                                       #
# --------------------------------------------------------------------- #

def instrumented_events() -> set[str]:
    return {entry[2] for entries in PATCH_TABLE.values()
            for entry in entries}


def instrument_module(mod) -> int:
    """Wrap this module's patch-table methods (idempotent).  Returns
    the number of methods newly wrapped; patches are tracked module-
    globally and undone by `unpatch_all()`."""
    entries = PATCH_TABLE.get(getattr(mod, "__name__", ""), ())
    wrapped = 0
    for cls_name, meth, event, when in entries:
        cls = getattr(mod, cls_name, None)
        if not isinstance(cls, type):
            continue
        orig = cls.__dict__.get(meth)
        if orig is None or getattr(orig, "_tsdbsan_order", False):
            continue
        setattr(cls, meth, _wrap(orig, event, when))
        _patched.append((cls, meth, orig))
        wrapped += 1
    return wrapped


def _wrap(orig, event: str, when: str):
    if when == "before":
        def wrapper(*args, **kwargs):
            record(event)
            return orig(*args, **kwargs)
    else:
        def wrapper(*args, **kwargs):
            out = orig(*args, **kwargs)
            record(event)
            return out
    wrapper._tsdbsan_order = True
    wrapper.__name__ = getattr(orig, "__name__", event)
    wrapper.__doc__ = getattr(orig, "__doc__", None)
    return wrapper


def unpatch_all() -> None:
    while _patched:
        cls, meth, orig = _patched.pop()
        setattr(cls, meth, orig)


# --------------------------------------------------------------------- #
# Static <-> dynamic cross-check                                        #
# --------------------------------------------------------------------- #

def static_table_cached() -> dict:
    """The lint's {contracts, events} table, computed at most once per
    process (the tree walk is ~2s — fine at session finish, not per
    test)."""
    global _static_table
    if _static_table is None:
        from tools.lint.ordering import static_order_table
        _static_table = static_order_table()
    return _static_table


def cross_check(static_table: dict | None = None,
                reporter=None) -> dict[str, list]:
    """Diff recorded streams against the declared contracts.  Emits
    note-level findings (into `reporter`, default the process-global
    one) and returns the diff for callers that render it themselves.
    A session that recorded nothing returns empty WITHOUT walking the
    tree for the static table."""
    local = streams()
    if not local:
        return {"violations": [], "gaps": []}
    if static_table is None:
        static_table = static_table_cached()
    rep = reporter if reporter is not None else REPORTER
    contracts = sorted(static_table.get("contracts", ()))
    violations: list[tuple[str, str, str]] = []
    for a, b in contracts:
        for key in sorted(local):
            ev = local[key]
            if a in ev and b in ev and ev[b][0] < ev[a][0]:
                _rank, path, line = ev[b]
                rep.add(
                    path, line, "san-order-violation",
                    "a runtime stream emitted '%s' before '%s' — the "
                    "declared contract '%s before %s' was violated on "
                    "a real execution the static verifier did not "
                    "derive (unannotated call path, or the reorder "
                    "lives outside the lint's scope)" % (b, a, a, b))
                violations.append((key, a, b))
    observed = set()
    for ev in local.values():
        observed.update(ev)
    instr = instrumented_events()
    gaps: list[str] = []
    for name in sorted({n for c in contracts for n in c}):
        if name in instr and name not in observed:
            rep.add(
                "<runtime>", 0, "san-order-gap",
                "contracted order event '%s' is instrumented but was "
                "never observed this session — uncovered path, or the "
                "tagged site moved away from its probe" % name)
            gaps.append(name)
    return {"violations": violations, "gaps": gaps}

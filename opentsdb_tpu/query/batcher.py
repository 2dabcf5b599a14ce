"""Fused multi-query dispatch: coalesce concurrent small queries into
one stacked device kernel (docs/batching.md, ROADMAP item 1).

Every hot-path subsystem so far accelerates one query at a time; at
dashboard-fleet QPS the per-dispatch floor — not FLOPs — caps
throughput, because each admitted plan still pays its own jitted
launch.  This module is the Enthuse-style shared-aggregation answer
(arXiv:2405.18168): concurrent plans that the routing verdict priced
as DISPATCH-BOUND (query/plandecision.py path ``batched``) rendezvous
here, bucket by compatibility, and execute as ONE stacked ``[Q, S, N]``
kernel (ops/pipeline.py run_stacked_group_pipeline) with host-side
unpack — Q queries, one launch floor.

Compatibility = one jit program: plans share a bucket only when they
would trace the SAME kernel — identical static ``PipelineSpec``,
identical padded batch shapes and dtypes, identical window-arg
structure and the same host-lane verdict.  Within a bucket each member
keeps its own
mask plane, its own gid row map, and its own traced window args
(stacked along the member axis), so on integer data a member's
unpacked slice is bitwise what its solo dispatch would produce
(integer-exact f64 accumulation is reassociation-proof — the same
contract the rollup lanes pin).

Coalesce-vs-dispatch-now is COSTMODEL-priced, not a static batch size
(the Factor-Windows cost-based-rewrite framing, arXiv:2008.12379):
the routing verdict already gated on ``coalesce_worthwhile`` (new
linear COST_TERMS ``stacked_dispatch`` + ``stacked_cell``), and the
rendezvous itself holds a bucket open only while there is concurrent
demand to coalesce — the first member of a bucket becomes the LEADER,
waits up to ``tsd.query.batch.hold_ms`` for joiners (zero wait when
the admission gate shows no other query in flight: an uncontended
query never pays coalesce latency), seals the bucket at
``tsd.query.batch.max_q`` members / ``tsd.query.batch.max_mb`` of
stacked operands, dispatches once, and distributes the host-unpacked
slices.  A batched pipeline span carries the plan's ``costmodel``
decisions beside its ``batch`` tag.

Deadlines stay per-member: a member whose deadline expires or cancels
while waiting leaves the bucket WITHOUT poisoning its siblings — the
leader drops expired members (its own included: winning the submit
race does not outrank the deadline) before stacking, and a member that
expires after sealing simply abandons its slice.  Each member keeps
its own trace span; the planner annotates it with the batch verdict
(q, waited ms, stacked vs solo).

One instance per TSDB (``tsdb.dispatch_batcher``); every stacked
dispatch lands a ``batch`` event in the flight recorder and the
``tsd.query.batch.*`` metric families.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from opentsdb_tpu.obs import latattr
from opentsdb_tpu.obs.registry import REGISTRY
from opentsdb_tpu.ops.pipeline import (run_group_pipeline,
                                       run_stacked_group_pipeline)

# Waiting members re-check their own deadline on this cadence even
# without a bucket notification (cancellation flips a token without
# notifying the batcher's condition) — same discipline as the
# admission gate's queue wait.
_WAIT_TICK_S = 0.05


class _Member:
    """One submitted plan: operands in, an unpacked slice (or error)
    out.  State transitions are guarded by the batcher lock; `done`
    flips exactly once, under it."""

    __slots__ = ("ts", "val", "mask", "gid", "wargs", "deadline",
                 "done", "result", "error", "abandoned")

    def __init__(self, ts, val, mask, gid, wargs, deadline):
        self.ts = ts
        self.val = val
        self.mask = mask
        self.gid = gid
        self.wargs = wargs
        self.deadline = deadline
        self.done = False        # guarded-by: DispatchBatcher._lock
        self.result = None       # guarded-by: DispatchBatcher._lock
        self.error = None        # guarded-by: DispatchBatcher._lock
        self.abandoned = False   # guarded-by: DispatchBatcher._lock

    def nbytes(self) -> int:
        return (self.ts.nbytes + self.val.nbytes + self.mask.nbytes
                + self.gid.nbytes)


class _Bucket:
    """One open coalesce window: members compatible enough to share a
    single stacked jit program."""

    __slots__ = ("key", "members", "sealed", "nbytes")

    def __init__(self, key):
        self.key = key
        self.members: list[_Member] = []  # guarded-by: DispatchBatcher._lock
        self.sealed = False               # guarded-by: DispatchBatcher._lock
        self.nbytes = 0                   # guarded-by: DispatchBatcher._lock


def _wargs_signature(wargs: dict) -> tuple:
    """Structural identity of the traced window args: keys, shapes,
    dtypes — two members stack only when their wargs trees match."""
    out = []
    for k in sorted(wargs):
        v = np.asarray(wargs[k])
        out.append((k, v.shape, v.dtype.str))
    return tuple(out)


def bucket_key(spec, g_pad: int, ts, val, gid, wargs: dict,
               host_small: bool) -> tuple:
    """The compatibility key: everything the stacked jit program bakes
    in at trace time.  PipelineSpec is frozen/hashable (it IS the
    static argument); shapes/dtypes cover the operand layout."""
    return (spec, g_pad, ts.shape, val.dtype.str, gid.dtype.str,
            _wargs_signature(wargs), bool(host_small))


class DispatchBatcher:
    """The rendezvous: submit() blocks until this plan's slice (or its
    bucket's error) is ready, and internally elects one submitting
    thread per bucket as the dispatch leader."""

    def __init__(self, config, tsdb=None):
        self.enabled = config.get_bool("tsd.query.batch.enable")
        self.hold_ms = max(config.get_int("tsd.query.batch.hold_ms"), 0)
        self.max_q = max(config.get_int("tsd.query.batch.max_q"), 1)
        self.max_bytes = max(
            config.get_int("tsd.query.batch.max_mb"), 1) * 2 ** 20
        self._tsdb = tsdb
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # open buckets by compatibility key
        self._buckets: dict[tuple, _Bucket] = {}  # guarded-by: _lock
        self.stacked_dispatches = 0  # guarded-by: _lock
        self.stacked_members = 0     # guarded-by: _lock
        self.solo_dispatches = 0     # guarded-by: _lock

    # -- demand hint ---------------------------------------------------- #

    def _concurrent_demand(self) -> int:
        """Queries currently holding admission permits — the leader's
        evidence that a sibling may arrive within the hold window.  An
        uncontended query (demand <= 1: only itself) never waits."""
        gate = getattr(self._tsdb, "_admission_gate", None)
        if gate is None:
            return 0
        with gate._lock:
            return gate.in_flight + gate._depth_locked()

    # -- the rendezvous -------------------------------------------------- #

    def submit(self, spec, ts, val, mask, gid, g_pad: int, wargs: dict,
               host_small: bool, deadline=None):
        """Execute one batch-routed plan; returns ((out_ts, out_val,
        out_mask, lanes), info) where the outputs are the member's own
        host-unpacked slice (np arrays when stacked, device arrays on
        the solo fallback) and ``info`` carries the batch verdict for
        span annotation.  Raises the member's own deadline error if it
        expires while coalescing — siblings are unaffected."""
        member = _Member(ts, val, mask, np.asarray(gid), wargs, deadline)
        t0 = time.monotonic()
        key = bucket_key(spec, g_pad, ts, val, member.gid, wargs,
                         host_small)
        with self._lock:
            bucket = self._buckets.get(key)
            leader = bucket is None
            if leader:
                bucket = _Bucket(key)
                self._buckets[key] = bucket
            bucket.members.append(member)
            bucket.nbytes += member.nbytes()
            full = (len(bucket.members) >= self.max_q
                    or bucket.nbytes >= self.max_bytes)
            if full and not bucket.sealed:
                bucket.sealed = True
                del self._buckets[bucket.key]
                self._cv.notify_all()
        if leader:
            self._lead(spec, g_pad, bucket, host_small, full, t0)
            if member.abandoned:
                # the leader's OWN deadline died while the window held:
                # it already dispatched for its live followers above,
                # but its answer would arrive past the deadline — same
                # exit as a dropped follower (413/503, siblings keep
                # their results)
                member.deadline.check()
                from opentsdb_tpu.query.limits import QueryException
                raise QueryException(
                    "Sorry, your query's deadline expired while "
                    "batched.")
        else:
            self._follow(bucket, member, t0)
        with self._lock:
            if member.error is not None:
                raise member.error
            result = member.result
        waited_ms = (time.monotonic() - t0) * 1e3
        # attribution boundary: the coalesce wait (which for followers
        # includes the leader's shared dispatch) is batch time; the
        # planner's own "dispatch" mark right after submit() returns
        # then reads ~0 for stacked members
        latattr.mark("batch_rendezvous")
        q = result[4]
        outcome = "stacked" if q > 1 else "solo"
        REGISTRY.counter(
            "tsd.query.batch.queries",
            "Batch-routed queries, by outcome").labels(
                outcome=outcome).inc()
        REGISTRY.histogram(
            "tsd.query.batch.wait_ms",
            "Coalesce wait before the stacked/solo dispatch "
            "(ms)").observe(waited_ms)
        return result[:4], {"q": q, "stacked": q > 1,
                            "waitMs": round(waited_ms, 3)}

    def _follow(self, bucket: _Bucket, member: _Member,
                t0: float) -> None:
        """Wait for the leader's dispatch; leave alone on own expiry."""
        with self._lock:
            while not member.done:
                deadline = member.deadline
                if deadline is not None and (deadline.is_cancelled()
                                             or deadline.expired()):
                    if not bucket.sealed:
                        # still coalescing: step out of the bucket so
                        # the leader never stacks a dead member
                        bucket.members.remove(member)
                        bucket.nbytes -= member.nbytes()
                    member.abandoned = True
                    member.done = True
                    break
                self._cv.wait(_WAIT_TICK_S)
        if member.abandoned and member.error is None \
                and member.result is None:
            # raises the deadline's own 413/503 — the member leaves
            # WITHOUT an answer, its siblings keep theirs
            member.deadline.check()
            from opentsdb_tpu.query.limits import QueryException
            raise QueryException(
                "Sorry, your query's deadline expired while batched.")

    def _lead(self, spec, g_pad: int, bucket: _Bucket,
              host_small: bool, already_full: bool, t0: float) -> None:
        """Hold the coalesce window, seal, stack, dispatch ONCE,
        distribute host-unpacked slices."""
        if not already_full:
            hold_s = self.hold_ms / 1e3 if self.hold_ms > 0 \
                and self._concurrent_demand() > 1 else 0.0
            deadline_t = t0 + hold_s
            with self._lock:
                while not bucket.sealed:
                    remaining = deadline_t - time.monotonic()
                    if remaining <= 0:
                        bucket.sealed = True
                        self._buckets.pop(bucket.key, None)
                        break
                    self._cv.wait(min(remaining, _WAIT_TICK_S))
        with self._lock:
            members = [m for m in bucket.members if not m.abandoned]
            # drop members whose deadline died while the window held —
            # the leader's own member included (it submitted first, but
            # first-in-line does not outrank the deadline; submit()
            # raises its 413/503 after this dispatch serves the rest)
            live: list[_Member] = []
            for m in members:
                d = m.deadline
                if d is not None and (d.is_cancelled() or d.expired()):
                    m.abandoned = True
                    m.done = True
                    continue
                live.append(m)
            self._cv.notify_all()
        try:
            outs = self._dispatch(spec, g_pad, live, host_small)
        except BaseException as e:
            with self._lock:
                for m in live:
                    m.error = e
                    m.done = True
                self._cv.notify_all()
            if isinstance(e, Exception):
                return      # the leader re-raises via submit()'s check
            raise
        with self._lock:
            for m, out in zip(live, outs):
                m.result = out
                m.done = True
            self._cv.notify_all()

    def _dispatch(self, spec, g_pad: int, live: list[_Member],
                  host_small: bool) -> list:
        """One launch for the sealed bucket.  Q == 1 short-circuits to
        the ordinary solo program (zero extra compile variants, and
        trivially bitwise-identical to an unbatched run); Q > 1 stacks
        along the member axis and unpacks HOST-SIDE — one np.asarray
        per output, microsecond row slices per member."""
        from opentsdb_tpu.ops.hostlane import host_lane
        q = len(live)
        if q == 0:
            return []
        if q == 1:
            m = live[0]
            with host_lane(host_small):
                out = run_group_pipeline(spec, m.ts, m.val, m.mask,
                                         m.gid, g_pad, m.wargs)
            with self._lock:
                self.solo_dispatches += 1
            return [(*out, 1)]
        # The member axis pads to a power of FOUR (replicating the
        # first member; its extra slices are dropped after unpack), so
        # the stacked program compiles once per (bucket key, quantum)
        # instead of once per exact arrival count — without this, a
        # fleet whose bucket sizes jitter 2..16 recompiles on nearly
        # every dispatch and the batcher LOSES throughput (measured;
        # pow2 still left 4 live variants churning mid-burst).  The
        # padding waste is bounded (< 4x member cells) and members are
        # dispatch-bound by routing, so cells are cheap by definition.
        q_pad = 1
        while q_pad < q:
            q_pad *= 4
        q_pad = min(max(q_pad, 1), max(self.max_q, 1))
        padded = live + [live[0]] * (q_pad - q)
        ts = np.stack([m.ts for m in padded])
        val = np.stack([m.val for m in padded])
        mask = np.stack([m.mask for m in padded])
        gid = np.stack([m.gid for m in padded])
        wargs = {k: np.stack([np.asarray(m.wargs[k]) for m in padded])
                 for k in live[0].wargs}
        with host_lane(host_small):
            wts, out_val, out_mask, lanes = run_stacked_group_pipeline(
                spec, ts, val, mask, gid, g_pad, wargs)
        # host-side unpack: one transfer per output, then row views
        wts = np.asarray(wts)
        out_val = np.asarray(out_val)
        out_mask = np.asarray(out_mask)
        lanes = np.asarray(lanes)
        with self._lock:
            self.stacked_dispatches += 1
            self.stacked_members += q
        REGISTRY.counter(
            "tsd.query.batch.dispatches",
            "Stacked multi-query device dispatches").inc()
        REGISTRY.histogram(
            "tsd.query.batch.q",
            "Member queries per stacked dispatch").observe(float(q))
        recorder = getattr(self._tsdb, "flightrec", None)
        if recorder is not None:
            recorder.record("batch", q=q,
                            series=int(ts.shape[1]),
                            points=int(ts.shape[2]),
                            groups=int(g_pad),
                            hostSmall=bool(host_small))
        return [(wts[i], out_val[i], out_mask[i], lanes[i], q)
                for i in range(q)]

    # -- stats ----------------------------------------------------------- #

    def collect_stats(self) -> dict:
        with self._lock:
            return {
                "tsd.query.batch.stacked_dispatches": float(
                    self.stacked_dispatches),
                "tsd.query.batch.stacked_members": float(
                    self.stacked_members),
                "tsd.query.batch.solo_dispatches": float(
                    self.solo_dispatches),
            }

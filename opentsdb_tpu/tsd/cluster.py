"""Cross-host request serving: one /api/query, the whole cluster's data.

Reference behavior being matched: a single TSD answers a query by
fanning scanners out across every storage node that holds a salt bucket
and aggregating the returned rows itself (SaltScanner — one scanner per
bucket across RegionServers, /root/reference/src/core/SaltScanner.java:269;
the TSD is the aggregation point).  The TPU-native equivalent keeps the
same shape: the TSD that receives a query asks every peer TSD for the
RAW matching series (aggregator "none", no downsample/rate — each peer
runs its own planner over its own store and chips), folds the returned
series together with its local ones into a scratch store, and runs the
ORIGINAL query against that — so downsampling, rate, interpolation,
group-by, and percentiles all execute once, locally, with exactly the
single-host semantics the test suite pins.  DCN traffic is the raw
matching points, as in the reference's scanner model.

This is the REQUEST-DRIVEN serving path for data partitioned across
independent TSD processes (each ingesting its own series).  It is
complementary to the SPMD path (`tsd.network.distributed.*` +
`jax.distributed.initialize`), where every process holds a shard of one
logical store and executes lock-step collectives — that path has the
higher throughput ceiling but needs all processes in one JAX runtime;
this one needs only HTTP reachability.

Fault tolerance (the asynchbase role — the reference TSD survives
RegionServer flaps because its storage client retries internally;
direct HTTP fan-out needs its own layer):

  * every peer fetch runs under capped-exponential-backoff retries
    (utils/retry.py) with the overall budget from
    `tsd.network.cluster.timeout_ms`;
  * each peer has a circuit breaker: after
    `tsd.network.cluster.breaker.threshold` consecutive fetch failures
    it opens and fetches fail fast (no network) until
    `tsd.network.cluster.breaker.cooldown_ms` elapses, then ONE
    half-open probe decides (success closes it, failure re-opens);
    state is surfaced through collect_stats -> /api/stats;
  * `tsd.network.cluster.partial_results` picks the stance when a peer
    still fails after all that: "error" (default — the reference's
    scanner-error stance, a partial answer is worse than an error)
    fails the query; "allow" folds whatever peers answered, marks
    `exec_stats["partialResults"]`/`["clusterPeersFailed"]`, and the
    query answers 200 with the surviving data (tsd/rpcs.py annotates
    the response body).

Config:
  tsd.network.cluster.peers       comma-separated "host:port" of the
                                  OTHER TSDs (empty = single-host serving)
  tsd.network.cluster.timeout_ms  overall per-fetch budget (all retries)
  tsd.network.cluster.partial_results           "error" | "allow"
  tsd.network.cluster.retry.max_attempts        attempts per peer fetch
  tsd.network.cluster.retry.attempt_timeout_ms  per-attempt deadline
                                  (0 = the full remaining budget)
  tsd.network.cluster.breaker.threshold         consecutive failures
                                  that open a peer's breaker (0 = off)
  tsd.network.cluster.breaker.cooldown_ms       open -> half-open delay

Loop prevention: fan-out requests carry the `X-TSDB-Cluster: fanout`
header; a TSD answering one serves purely from its local store.
"""

from __future__ import annotations

import copy
import json
import logging
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from opentsdb_tpu.models.tsquery import TSQuery, TSSubQuery
from opentsdb_tpu.obs import trace as obs_trace
from opentsdb_tpu.query.limits import (Deadline, QueryException,
                                       active_deadline)
from opentsdb_tpu.uid import NoSuchUniqueName
from opentsdb_tpu.utils import faults
from opentsdb_tpu.utils.retry import RetryPolicy, call_with_retries

LOG = logging.getLogger(__name__)

CLUSTER_HEADER = "x-tsdb-cluster"


def cluster_peers(config) -> list[str]:
    raw = config.get_string("tsd.network.cluster.peers") or ""
    return [p.strip() for p in raw.split(",") if p.strip()]


def is_fanout_request(http_query) -> bool:
    """True for requests issued by a peer's fan-out (serve locally)."""
    return bool(http_query.request.headers.get(CLUSTER_HEADER))


# --------------------------------------------------------------------- #
# Circuit breakers                                                      #
# --------------------------------------------------------------------- #

class BreakerOpenError(ConnectionError):
    """A peer's circuit is open: failing fast without a network call."""


class CircuitBreaker:
    """closed -> (threshold consecutive failures) -> open ->
    (cooldown) -> half-open probe -> closed | open.

    ``threshold`` counts whole fetches (post-retry), not attempts:
    retries absorb transients, the breaker reacts to persistent ones.
    ``clock`` is injectable so tests drive the cooldown without sleeps.
    """

    CLOSED, OPEN, HALF_OPEN = "closed", "open", "half_open"

    def __init__(self, threshold: int, cooldown_s: float, clock=time.monotonic,
                 listener=None):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        # state-transition callback `listener(old, new)`, invoked
        # OUTSIDE _lock (the flight recorder takes its own lock; a
        # callback under ours would order the two) — may observe a
        # state that already moved on, never a torn one
        self._listener = listener
        self._lock = threading.Lock()
        # guarded-by: _lock
        self.state = self.CLOSED
        self.consecutive_failures = 0  # guarded-by: _lock
        self.opened_at = 0.0  # guarded-by: _lock
        self._probing = False  # guarded-by: _lock
        # lifetime open transitions (stats)  # guarded-by: _lock
        self.opens = 0
        # calls refused while open (stats)  # guarded-by: _lock
        self.fast_fails = 0

    def _notify(self, old: str, new: str) -> None:
        if self._listener is not None and old != new:
            self._listener(old, new)

    def allow(self) -> bool:
        """True if a fetch may proceed now.  While open, the first call
        after the cooldown becomes the single half-open probe."""
        if self.threshold <= 0:
            return True
        with self._lock:
            if self.state == self.CLOSED:
                return True
            if self.state == self.OPEN:
                if self._clock() - self.opened_at >= self.cooldown_s:
                    self.state = self.HALF_OPEN
                    self._probing = True
                    transition = (self.OPEN, self.HALF_OPEN)
                else:
                    self.fast_fails += 1
                    return False
            # half-open: exactly one probe in flight.  Not counted as a
            # fast fail — callers may WAIT on the probe's verdict
            # (probe_pending) instead of failing.
            elif self._probing:
                return False
            else:
                self._probing = True
                return True
        self._notify(*transition)
        return True

    def probe_pending(self) -> bool:
        """True while a half-open probe is in flight — a sibling fetch
        (another subquery of the same clustered query) should await its
        verdict rather than fast-fail; the probe's success must not
        fail the very query that triggered it."""
        with self._lock:
            return self.state == self.HALF_OPEN and self._probing

    def record_success(self) -> None:
        with self._lock:
            old = self.state
            self.state = self.CLOSED
            self.consecutive_failures = 0
            self._probing = False
        self._notify(old, self.CLOSED)

    def record_failure(self) -> None:
        if self.threshold <= 0:
            return
        old = new = None
        with self._lock:
            if self.state == self.HALF_OPEN:
                # failed probe: back to a full cooldown
                old, new = self.state, self.OPEN
                self.state = self.OPEN
                self.opened_at = self._clock()
                self.opens += 1
                self._probing = False
            else:
                self.consecutive_failures += 1
                if (self.state == self.CLOSED
                        and self.consecutive_failures >= self.threshold):
                    old, new = self.state, self.OPEN
                    self.state = self.OPEN
                    self.opened_at = self._clock()
                    self.opens += 1
        if new is not None:
            self._notify(old, new)


class ClusterState:
    """Per-TSDB fault-tolerance state: one breaker per peer plus the
    counters /api/stats surfaces.  Lives across queries (attached to the
    TSDB instance by _state below)."""

    def __init__(self, config, recorder=None):
        self.threshold = config.get_int(
            "tsd.network.cluster.breaker.threshold")
        self.cooldown_s = config.get_int(
            "tsd.network.cluster.breaker.cooldown_ms") / 1e3
        # flight recorder (obs/flightrec.py): breaker transitions are
        # retained diagnostics — an operator reading /api/diag after a
        # partial-results burst sees WHICH peer flapped and when
        self.recorder = recorder
        self._lock = threading.Lock()
        # guarded-by: _lock
        self._breakers: dict[str, CircuitBreaker] = {}
        self.fetch_retries = 0  # guarded-by: _lock
        self.fetch_failures = 0  # guarded-by: _lock
        self.partial_queries = 0  # guarded-by: _lock
        self.failed_queries = 0  # guarded-by: _lock

    def _transition_listener(self, peer: str):
        recorder = self.recorder
        if recorder is None:
            return None

        def on_transition(old: str, new: str) -> None:
            recorder.record("breaker", peer=peer, before=old, state=new)
        return on_transition

    def breaker(self, peer: str) -> CircuitBreaker:
        with self._lock:
            b = self._breakers.get(peer)
            if b is None:
                b = self._breakers[peer] = CircuitBreaker(
                    self.threshold, self.cooldown_s,
                    listener=self._transition_listener(peer))
            return b

    def count(self, attr: str, n: int = 1) -> None:
        with self._lock:
            setattr(self, attr, getattr(self, attr) + n)

    def breakers(self) -> dict[str, CircuitBreaker]:
        with self._lock:
            return dict(self._breakers)


_STATE_LOCK = threading.Lock()

# Probe-verdict poll cadence (_guarded_fetch_inner): each tick parks on
# the request deadline's cancellation token, never a bare sleep.
_PROBE_TICK_S = 0.02


def _state(tsdb) -> ClusterState:
    state = getattr(tsdb, "_cluster_state", None)
    if state is None:
        with _STATE_LOCK:
            state = getattr(tsdb, "_cluster_state", None)
            if state is None:
                state = ClusterState(tsdb.config,
                                     recorder=getattr(tsdb, "flightrec",
                                                      None))
                tsdb._cluster_state = state
    return state


def partial_annotation(exec_stats: dict) -> dict | None:
    """The degraded-serving annotation every query-shaped endpoint
    attaches to a 200 that is missing peers — or that the admission
    ladder coarsened/truncated (tsd/admission.py) — None when the
    answer is the full one.  One definition so the contract can't
    diverge per endpoint."""
    if not exec_stats.get("partialResults"):
        return None
    out = {
        "partialResults": True,
        "clusterPeersFailed": exec_stats.get("clusterPeersFailed", 0),
        "clusterPeers": exec_stats.get("clusterPeers", 0),
    }
    if exec_stats.get("degraded"):
        out["degraded"] = exec_stats["degraded"]
    return out


def collect_stats(tsdb, collector) -> None:
    """Cluster fault-tolerance telemetry for /api/stats + telnet stats.
    Nothing is recorded on a TSD that never served clustered (the state
    attaches on first fan-out), keeping single-host stats unchanged."""
    state = getattr(tsdb, "_cluster_state", None)
    if state is None:
        return
    collector.record("cluster.fetch.retries", state.fetch_retries)
    collector.record("cluster.fetch.failures", state.fetch_failures)
    collector.record("cluster.queries", state.partial_queries,
                     "result=partial")
    collector.record("cluster.queries", state.failed_queries,
                     "result=failed")
    numeric = {CircuitBreaker.CLOSED: 0, CircuitBreaker.HALF_OPEN: 1,
               CircuitBreaker.OPEN: 2}
    for peer, b in sorted(state.breakers().items()):
        collector.record("cluster.breaker.state", numeric[b.state],
                         "peer=%s" % peer)
        collector.record("cluster.breaker.opens", b.opens,
                         "peer=%s" % peer)
        collector.record("cluster.breaker.fast_fails", b.fast_fails,
                         "peer=%s" % peer)


# --------------------------------------------------------------------- #
# Fan-out plumbing                                                      #
# --------------------------------------------------------------------- #

def _raw_query(ts_query: TSQuery) -> TSQuery:
    """The per-series extraction query: same range/filters, NO
    aggregation, downsampling, or rate — peers ship raw matching points
    and every cross-series semantic runs once at the receiver."""
    raw = TSQuery(start=ts_query.start, end=ts_query.end)
    raw.ms_resolution = True
    for i, sub in enumerate(ts_query.queries):
        if not sub.metric:
            # TSUIDs are per-process surrogate keys here (the reference's
            # are cluster-global via the shared HBase uid table) — a
            # tsuid doesn't name the same series on a peer
            raise ValueError("cluster serving requires metric-named "
                             "subqueries (tsuids are host-local)")
        r = TSSubQuery(aggregator="none", metric=sub.metric, index=i)
        r.filters = copy.deepcopy(sub.filters)
        r.explicit_tags = sub.explicit_tags
        raw.queries.append(r)
    raw.validate()
    return raw


def _sub_json(raw: TSQuery, index: int) -> dict:
    """One-subquery POST body for a peer (one request per subquery keeps
    the result->subquery mapping trivial, like one scanner per bucket)."""
    sub = raw.queries[index]
    body = {
        "start": raw.start,
        "msResolution": True,
        "queries": [{
            "aggregator": "none",
            "metric": sub.metric,
            "explicitTags": sub.explicit_tags,
            "filters": [f.to_json() for f in (sub.filters or [])],
        }],
    }
    if raw.end:
        body["end"] = raw.end
    return body


def _fetch_peer(peer: str, body: dict, timeout_s: float,
                trace_id: str | None = None,
                deadline=None, tenant_header: str | None = None,
                extra_headers: dict | None = None) -> list[dict]:
    faults.check("cluster.peer_fetch", peer=peer)
    headers = {"Content-Type": "application/json",
               "X-TSDB-Cluster": "fanout"}
    if extra_headers:
        # sharded serving scopes each peer fetch to its shard cover
        # (X-TSDB-Shards — tsd/replication.py)
        headers.update(extra_headers)
    if trace_id:
        # the receiving TSD adopts this id for ITS trace of the raw
        # fetch — one clustered query, one trace id across every host
        headers["X-TSDB-Trace-Id"] = trace_id
    if tenant_header:
        # the client's RAW tenant header travels with the fan-out (each
        # peer clamps against its own registered table, like the
        # coordinator did) — peer-side per-tenant demand/latency
        # accounting must attribute the load to the real tenant, not
        # "default"
        headers["X-TSDB-Tenant"] = tenant_header
    if deadline is not None:
        # don't even connect when done for — an UNBOUNDED deadline is
        # still a cancellation token (client disconnect, server drain),
        # and each retry attempt re-enters here
        deadline.check()
        if deadline.bounded:
            # forward the coordinator's REMAINDER so the peer aborts
            # its own planning/dispatch once we've given up (it mints
            # its Deadline from this header —
            # rpc_manager._mint_deadline)
            remaining = deadline.remaining_ms()
            headers["X-TSDB-Deadline-Ms"] = str(max(int(remaining), 1))
            timeout_s = min(timeout_s, max(remaining / 1e3, 0.05))
    req = urllib.request.Request(
        "http://%s/api/query" % peer,
        data=json.dumps(body).encode(),
        headers=headers,
        method="POST")
    with urllib.request.urlopen(req, timeout=timeout_s) as resp:
        data = resp.read()
    data = faults.mangle("cluster.peer_body", data, peer=peer)
    return json.loads(data.decode())


def _retry_policy(config, deadline=None) -> RetryPolicy:
    budget_s = max(config.get_int("tsd.network.cluster.timeout_ms"),
                   1000) / 1e3
    if deadline is not None and deadline.bounded:
        # the whole retry stack (attempts + backoff sleeps) is clamped
        # to the request's remainder: a peer fetch must never outlive
        # the deadline the coordinator is serving under
        budget_s = max(min(budget_s, deadline.remaining_ms() / 1e3), 0.05)
    attempt_ms = config.get_int(
        "tsd.network.cluster.retry.attempt_timeout_ms")
    return RetryPolicy(
        max_attempts=max(
            config.get_int("tsd.network.cluster.retry.max_attempts"), 1),
        budget_s=budget_s,
        attempt_timeout_s=attempt_ms / 1e3 if attempt_ms > 0 else 0.0)


class PeerRejectedError(RuntimeError):
    """The peer answered a deterministic 4xx: reachable and responsive,
    so neither retried (same request, same answer) nor a breaker
    failure (availability is fine; the REQUEST is what it rejects)."""


class PeerUnknownNameError(PeerRejectedError):
    """The peer answered 404 — a name-lookup miss (http.error_status
    maps NoSuchUniqueName there): it never assigned a UID for the
    metric, which in a sharded cluster is routine, not a fault.  The
    sharded arm walks the shard's preference list on this; a shard
    whose every live member answers 404 holds nothing for the metric
    (empty contribution), where a plain failure would mean lost data."""


def _guarded_fetch(state: ClusterState, policy: RetryPolicy, peer: str,
                   body: dict, span=None,
                   trace_id: str | None = None,
                   deadline=None,
                   tenant_header: str | None = None,
                   extra_headers: dict | None = None) -> list[dict]:
    """One peer fetch under the full fault-tolerance stack: breaker
    fast-fail, then retries with backoff inside the overall budget
    (already clamped to the request deadline's remainder).

    `span` (an obs.trace.Span created by the submitting thread) records
    the fetch's fate: retry count, final breaker state, and the error
    when the peer lost — the annotations the degraded response's trace
    carries so an operator can see WHY a 200 is partial."""
    try:
        return _guarded_fetch_inner(state, policy, peer, body, span,
                                    trace_id, deadline, tenant_header,
                                    extra_headers)
    finally:
        if span is not None:
            span.tags["breaker"] = state.breaker(peer).state
            span.finish()


def _guarded_fetch_inner(state: ClusterState, policy: RetryPolicy,
                         peer: str, body: dict, span,
                         trace_id: str | None,
                         deadline=None,
                         tenant_header: str | None = None,
                         extra_headers: dict | None = None) -> list[dict]:
    breaker = state.breaker(peer)
    if span is not None:
        span.tags.setdefault("retries", 0)
    start = time.monotonic()
    allowed = breaker.allow()
    if not allowed and breaker.probe_pending():
        # a sibling subquery of this same query is the half-open probe:
        # wait for its verdict instead of fast-failing — the probe's
        # success must not fail the query that triggered it.  The tick
        # parks on the deadline's cancellation token (a throwaway
        # unbounded Deadline when the caller passed none) so a client
        # disconnect releases this wait within one tick instead of
        # polling out the whole fetch budget
        dl = deadline if deadline is not None else Deadline()
        wait_until = start + policy.budget_s
        while (not allowed and breaker.probe_pending()
               and time.monotonic() < wait_until):
            if dl.wait_cancelled(_PROBE_TICK_S):
                dl.check()
            allowed = breaker.allow()
        # the wait spent part of THIS fetch's overall budget — the
        # retries below get only the remainder, keeping timeout_ms the
        # true per-fetch ceiling
        waited = time.monotonic() - start
        if waited > 0.01:
            import dataclasses
            policy = dataclasses.replace(
                policy, budget_s=max(policy.budget_s - waited, 0.1))
    if not allowed:
        state.count("fetch_failures")
        err = BreakerOpenError(
            "peer %s circuit is open (%d consecutive failures; retry "
            "after cooldown)" % (peer, breaker.consecutive_failures))
        obs_trace.annotate(span, error=str(err))
        raise err

    def fetch(timeout_s: float) -> list[dict]:
        try:
            return _fetch_peer(peer, body, timeout_s, trace_id, deadline,
                               tenant_header=tenant_header,
                               extra_headers=extra_headers)
        except urllib.error.HTTPError as e:
            if e.code == 404:
                raise PeerUnknownNameError(
                    "peer %s has no UID for the queried name (404)"
                    % peer) from e
            if 400 <= e.code < 500:
                raise PeerRejectedError(
                    "peer %s rejected the raw-series fetch: HTTP %d"
                    % (peer, e.code)) from e
            raise

    def on_retry(n: int, e: Exception) -> None:
        state.count("fetch_retries")
        if span is not None:
            span.tags["retries"] = span.tags.get("retries", 0) + 1
        LOG.warning("retrying peer %s (attempt %d failed: %s)",
                    peer, n, e)

    try:
        # deadline passed EXPLICITLY: this runs on a fan-out executor
        # worker, where the ambient TLS deadline (responder thread) is
        # not visible — without it the backoff sleeps would be blind to
        # cancellation again
        result = call_with_retries(
            fetch, policy,
            no_retry_on=(PeerRejectedError, QueryException),
            on_retry=on_retry, deadline=deadline)
    except QueryException as e:
        # the COORDINATOR gave up (request deadline expired / cancelled
        # mid-fetch) — the peer did not fail, so its breaker is not
        # charged.  Except as the half-open probe: a probe with no
        # verdict must settle (re-open) or _probing wedges and every
        # sibling busy-waits on a verdict that never comes.
        if breaker.state == CircuitBreaker.HALF_OPEN:
            breaker.record_failure()
        state.count("fetch_failures")
        obs_trace.annotate(span, error=str(e))
        raise
    except PeerUnknownNameError as e:
        # routine in sharded serving (the peer holds nothing for the
        # name): settles the breaker like any responsive answer, and
        # does NOT count as a fetch failure
        breaker.record_success()
        obs_trace.annotate(span, unknown_name=True)
        raise
    except PeerRejectedError as e:
        # responsive peer: availability-wise a SUCCESS — crucially this
        # settles a half-open probe (otherwise _probing would stay set
        # forever and wedge the breaker half-open with every later
        # fetch busy-waiting on a verdict that never comes)
        breaker.record_success()
        state.count("fetch_failures")
        obs_trace.annotate(span, error=str(e))
        raise
    except Exception as e:
        breaker.record_failure()
        state.count("fetch_failures")
        obs_trace.annotate(span, error=str(e))
        raise
    breaker.record_success()
    return result


def _ingest_series(scratch, metric: str, tags: dict,
                   dps_items) -> int:
    """Fold one raw series into the scratch store; returns point count.
    dps_items: iterable of (ts_ms int, value int|float)."""
    pts = [(int(t), v) for t, v in dps_items
           if not (isinstance(v, float) and v != v)]      # drop NaN fills
    if not pts:
        return 0
    pts.sort()
    ts = np.fromiter((t for t, _ in pts), np.int64, len(pts))
    vals = np.fromiter((float(v) for _, v in pts), np.float64, len(pts))
    is_int = np.fromiter(
        (isinstance(v, int) or (isinstance(v, float) and v.is_integer()
                                and abs(v) < 2 ** 53)
         for _, v in pts), bool, len(pts))
    key = scratch._series_key(metric, tags, create=True)
    scratch.store.add_batch(key, ts, vals, is_int)
    return len(pts)


def serve_query(tsdb, ts_query: TSQuery, http_query=None,
                exec_stats: dict | None = None):
    """The single front door for every query-shaped endpoint (/api/query,
    /api/query/exp metric extraction, /api/query/gexp): clustered when
    peers are configured and the request is eligible, local otherwise.
    Eligibility: not a peer's own fan-out (loop guard), not a delete,
    and every subquery metric-named (tsuids are host-local).

    With sharded replication armed (tsd/replication.py) the clustered
    arm fans out only to the owning shards' healthy members, and the
    local arm honors a coordinator's X-TSDB-Shards scope — a node
    holding both owned and replicated copies serves exactly the shards
    it was asked for, so the fold never double-counts a series."""
    if cluster_peers(tsdb.config) \
            and (http_query is None or not is_fanout_request(http_query)) \
            and not getattr(ts_query, "delete", False) \
            and all(sub.metric for sub in ts_query.queries):
        from opentsdb_tpu.tsd.admission import TENANT_HEADER
        tenant_header = (http_query.request.header(TENANT_HEADER)
                         if http_query is not None else None)
        if getattr(tsdb, "replication", None) is not None:
            return run_sharded(tsdb, ts_query, exec_stats=exec_stats,
                               tenant_header=tenant_header)
        return run_clustered(tsdb, ts_query, exec_stats=exec_stats,
                             tenant_header=tenant_header)
    runner = tsdb.new_query_runner()
    out = runner.run(ts_query)
    repl = getattr(tsdb, "replication", None)
    if repl is not None and http_query is not None \
            and is_fanout_request(http_query):
        from opentsdb_tpu.tsd.replication import (SHARDS_HEADER,
                                                  series_shard)
        raw = http_query.request.headers.get(SHARDS_HEADER)
        if raw:
            keep = {int(x) for x in raw.split(",") if x.strip()}
            out = [qr for qr in out
                   if series_shard(qr.metric, qr.tags,
                                   repl.shard_count) in keep]
    if exec_stats is not None:
        exec_stats.update(runner.exec_stats)
    return out


def _scratch_store(tsdb):
    """The per-query aggregation buffer both clustered arms fold raw
    series into before running the ORIGINAL query once, locally."""
    from opentsdb_tpu.core import TSDB
    from opentsdb_tpu.utils.config import Config
    scratch = TSDB(Config({
        "tsd.core.auto_create_metrics": True,
        # a failover refetch can re-fold a series a half-answered member
        # already contributed — identical replicated points, resolved
        # last-write-wins instead of raising
        "tsd.storage.fix_duplicates": "true",
        # serving knobs only — the scratch is a per-query aggregation
        # buffer, not a daemon: no flight recorder or health engine of
        # its own (constructing one per clustered query would be waste,
        # and its ring would be discarded with the scratch)
        "tsd.query.device_cache.enable": "false",
        "tsd.diag.enable": "false",
        "tsd.health.enable": "false",
        # the final fold runs on THIS box: a coordinator whose operator
        # disabled the mesh must not have the scratch re-enable it
        # behind their back
        "tsd.query.mesh.enable": tsdb.config.get_string(
            "tsd.query.mesh.enable"),
    }))
    # the scratch runner's planner events must land in the SERVING
    # daemon's flight recorder — they carry the request's trace id, so
    # a clustered query's plan decisions stay reconstructible from the
    # coordinator's /api/diag ring
    scratch.flightrec = getattr(tsdb, "flightrec", None)
    return scratch


def _local_raw_series(tsdb, raw: TSQuery, unknown_subs: set | None = None):
    """This host's raw-series extraction for the fan-out fold, one
    subquery at a time.  A metric with no local UID contributes nothing
    instead of failing the extraction: in a cluster — sharded routing
    especially, where whole series land on other owners — a node
    routinely coordinates queries over metrics it never ingested.
    ``unknown_subs``, when given, collects the indexes of subqueries
    with no local UID so the caller can tell "empty here" from "no
    such name anywhere"."""
    runner = tsdb.new_query_runner()
    runner.exec_stats = {}
    for i, sub in enumerate(raw.queries):
        try:
            yield from runner.run_sub(raw, sub)
        except NoSuchUniqueName:
            if unknown_subs is not None:
                unknown_subs.add(i)
            continue


def _fold_payload(scratch, payload: list[dict]) -> int:
    """Fold one peer's raw-series response into the scratch store."""
    total = 0
    for item in payload:
        if "metric" not in item:
            continue        # statsSummary etc.
        total += _ingest_series(
            scratch, item["metric"], item.get("tags") or {},
            ((int(t), v)
             for t, v in (item.get("dps") or {}).items()))
    return total


def run_sharded(tsdb, ts_query: TSQuery, exec_stats: dict | None = None,
                tenant_header: str | None = None):
    """The shard-scoped clustered arm (tsd/replication.py): fan out
    only to the owning shards' healthy members — each peer fetch
    carries its shard cover in X-TSDB-Shards, the local extraction is
    filtered the same way, and a peer that fails mid-query has its
    shards REFETCHED from the next healthy preference member, so a
    single peer death serves full (non-partial) results.  Only a shard
    with no live member left degrades to the partial_results stance."""
    from opentsdb_tpu.tsd.replication import series_shard

    repl = tsdb.replication
    state = _state(tsdb)
    deadline = active_deadline()
    policy = _retry_policy(tsdb.config, deadline)
    allow_partial = (tsdb.config.get_string(
        "tsd.network.cluster.partial_results").strip().lower() == "allow")
    raw = _raw_query(ts_query)
    cover, uncovered = repl.query_plan()
    scratch = _scratch_store(tsdb)
    total = 0
    lost_shards: set[int] = set(uncovered)
    failed_nodes: set[str] = set()
    local_shards = set(cover.get(repl.self_id, set()))
    remote = {peer: shards for peer, shards in cover.items()
              if peer != repl.self_id}

    tr = obs_trace.active()
    parent = tr.current() if tr is not None else None
    trace_id = tr.trace_id if tr is not None else None

    def shards_header(shards: set[int]) -> dict:
        return {"X-TSDB-Shards": ",".join(str(s) for s in
                                          sorted(shards))}

    local_series: list | None = None

    def ingest_local(shards: set[int]) -> None:
        # extract once, reuse across failover rounds — each round would
        # otherwise re-scan the whole local store on the degraded path
        nonlocal total, local_series
        if local_series is None:
            local_series = list(_local_raw_series(tsdb, raw))
        for qr in local_series:
            if series_shard(qr.metric, qr.tags,
                            repl.shard_count) in shards:
                total += _ingest_series(scratch, qr.metric, qr.tags,
                                        qr.dps)

    pool = None
    futures: dict = {}
    if remote:
        pool = ThreadPoolExecutor(
            max_workers=min(len(remote) * len(raw.queries), 16))
        for peer, shards in remote.items():
            hdr = shards_header(shards)
            for i in range(len(raw.queries)):
                span = (parent.child("peer_fetch", peer=peer,
                                     subquery=i, shards=len(shards))
                        if parent is not None else None)
                futures[pool.submit(
                    _guarded_fetch, state, policy, peer,
                    _sub_json(raw, i), span, trace_id, deadline,
                    tenant_header, hdr)] = (peer, i, span)
    def local_knows_all() -> bool:
        for sub in raw.queries:
            try:
                tsdb.metrics.get_id(sub.metric)
            except NoSuchUniqueName:
                return False
        return True

    try:
        # consulted[shard]: members already asked for this shard this
        # query — failed OR healthy-but-404 — so the preference walk
        # below never re-asks one
        consulted: dict[int, set[str]] = {}
        todo: set[int] = set()
        if local_shards:
            # contribute whatever is locally known either way; if SOME
            # queried metric has no local UID, additionally walk the
            # covered shards' preference lists like a remote 404 would
            # — a replica may hold series for a metric this node has
            # not caught up to (re-folds of the locally-known metrics
            # resolve as duplicates)
            ingest_local(local_shards)
            if not local_knows_all():
                for shard in local_shards:
                    consulted.setdefault(shard, set()).add(repl.self_id)
                    todo.add(shard)
        if futures:
            for fut, (peer, i, _span) in futures.items():
                try:
                    payload = fut.result()
                except PeerUnknownNameError:
                    # healthy peer, no UID for the metric: walk on to
                    # the shard's next preference member (a replica may
                    # hold series the assigned member has not caught up
                    # to); NOT a breaker/partial event
                    for shard in remote.get(peer, set()):
                        consulted.setdefault(shard, set()).add(peer)
                        todo.add(shard)
                    continue
                except Exception as e:
                    if peer not in failed_nodes:
                        failed_nodes.add(peer)
                        LOG.warning(
                            "sharded peer %s failed; refetching its %d "
                            "shard(s) from replicas: %s",
                            peer, len(remote.get(peer, ())), e)
                    for shard in remote.get(peer, set()):
                        consulted.setdefault(shard, set()).add(peer)
                        todo.add(shard)
                    continue
                total += _fold_payload(scratch, payload)
        # failover walk: reassign every pending shard to its next
        # healthy unconsulted preference member (serving continues with
        # FULL data; a refetch re-folding an already-answered subquery
        # is safe — the scratch resolves identical duplicate points).
        # A shard exhausting its members is LOST (partial stance) only
        # if some consulted member actually failed; members that merely
        # answered 404 prove the shard holds nothing for the metric.
        # Breaker charges from the failed fetches feed the next
        # query_plan's epoch bump.
        while todo:
            reassign: dict[str, set[int]] = {}
            for shard in list(todo):
                nxt = repl.next_member(
                    shard, exclude=consulted[shard] | failed_nodes)
                if nxt is None:
                    # a healthy member's 404 is authoritative — the
                    # replica set is caught up on the ack path, so "no
                    # UID here" proves the shard holds nothing for the
                    # metric; the shard is lost only when NOT ONE
                    # member gave a healthy answer
                    if consulted[shard] <= failed_nodes:
                        lost_shards.add(shard)
                    todo.discard(shard)
                else:
                    reassign.setdefault(nxt, set()).add(shard)
            extra_local = reassign.pop(repl.self_id, set())
            if extra_local:
                # contribute what this node knows; a metric with no
                # local UID walks on like a remote 404 would (a replica
                # may hold series this node has not caught up to)
                ingest_local(extra_local)
                if local_knows_all():
                    todo -= extra_local
                else:
                    for shard in extra_local:
                        consulted[shard].add(repl.self_id)
            for node, shards in reassign.items():
                hdr = shards_header(shards)
                served = True
                for i in range(len(raw.queries)):
                    span = (parent.child("peer_fetch", peer=node,
                                         subquery=i, failover=True,
                                         shards=len(shards))
                            if parent is not None else None)
                    try:
                        payload = _guarded_fetch(
                            state, policy, node, _sub_json(raw, i),
                            span, trace_id, deadline, tenant_header,
                            hdr)
                    except PeerUnknownNameError:
                        served = False
                        for shard in shards:
                            consulted[shard].add(node)
                        break
                    except Exception as e:
                        LOG.warning("sharded failover fetch from %s "
                                    "failed too: %s", node, e)
                        served = False
                        failed_nodes.add(node)
                        for shard in shards:
                            consulted[shard].add(node)
                        break
                    total += _fold_payload(scratch, payload)
                if served:
                    todo -= shards
    finally:
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        for fut, (_peer, _i, span) in futures.items():
            if span is not None and span.wall_ms is None:
                if fut.cancelled():
                    span.tags.setdefault(
                        "error", "cancelled: query aborted before "
                                 "this fetch ran")
                span.finish()
    if lost_shards:
        state.count("failed_queries" if not allow_partial
                    else "partial_queries")
        if not allow_partial:
            raise RuntimeError(
                "shard(s) %s have no live member (cover epoch %d)"
                % (sorted(lost_shards), repl.current_epoch()))
    runner = scratch.new_query_runner()
    out = runner.run(ts_query)
    for qr in out:
        qr.tsuids = []      # scratch-store surrogate uids (see
        #                     run_clustered)
    if exec_stats is not None:
        exec_stats.update(runner.exec_stats)
        exec_stats["clusterPeers"] = len(remote)
        exec_stats["clusterRawPoints"] = total
        exec_stats["shardEpoch"] = repl.current_epoch()
        exec_stats["shardCover"] = {node: len(shards)
                                    for node, shards in cover.items()}
        if failed_nodes:
            exec_stats["clusterPeersFailed"] = len(failed_nodes)
        if lost_shards:
            exec_stats["clusterShardsFailed"] = len(lost_shards)
            exec_stats["partialResults"] = True
    return out


def run_clustered(tsdb, ts_query: TSQuery, exec_stats: dict | None = None,
                  tenant_header: str | None = None):
    """Fan the query's raw-series extraction across this host and every
    peer, fold everything into a scratch store, run the ORIGINAL query
    against it.  Returns the planner's QueryResult list (drop-in for
    QueryRunner.run).  `exec_stats`, when given, receives the scratch
    runner's execution telemetry plus cluster counters (the /api/stats/
    query surface must not go dark for clustered queries).

    Peer failures (after retries/breakers): with
    tsd.network.cluster.partial_results=error the first one fails the
    query; with "allow" the surviving peers' data still answers and the
    failed-peer count rides out in exec_stats."""
    peers = cluster_peers(tsdb.config)
    state = _state(tsdb)
    # the ambient deadline is read HERE, on the handler thread that
    # owns it — the pool threads below only carry the object
    deadline = active_deadline()
    policy = _retry_policy(tsdb.config, deadline)
    allow_partial = (tsdb.config.get_string(
        "tsd.network.cluster.partial_results").strip().lower() == "allow")
    raw = _raw_query(ts_query)
    scratch = _scratch_store(tsdb)
    total = 0

    # peer fetches submit FIRST so they overlap the local extraction
    # below (the two are independent; serializing them would make the
    # extraction phase local_scan + max(peer_fetch) instead of the max)
    jobs = [(peer, i) for peer in peers for i in range(len(raw.queries))]
    pool = futures = None
    # per-peer child spans are created HERE, on the thread that owns the
    # trace (children lists are unlocked); the pool threads only finish
    # and annotate their own span.  The trace id travels with every
    # fetch so the peers' traces correlate.
    tr = obs_trace.active()
    parent = tr.current() if tr is not None else None
    trace_id = tr.trace_id if tr is not None else None
    if jobs:
        # no context manager: in "error" mode a peer failure must return
        # its error NOW, not after every straggling in-flight fetch
        # drains its timeout (shutdown(wait=False, cancel_futures=True)
        # drops the queued ones; already-running urllib calls finish in
        # the background)
        pool = ThreadPoolExecutor(max_workers=min(len(jobs), 16))
        futures = {}
        for peer, i in jobs:
            span = (parent.child("peer_fetch", peer=peer, subquery=i)
                    if parent is not None else None)
            futures[pool.submit(_guarded_fetch, state, policy, peer,
                                _sub_json(raw, i), span,
                                trace_id, deadline,
                                tenant_header)] = (peer, i, span)

    failed_peers: set[str] = set()
    unknown_local: set[int] = set()
    unknown_peers: dict[int, int] = {}
    # local extraction: straight off this host's store/planner (objects,
    # no JSON round-trip), concurrent with the in-flight peer fetches
    try:
        for qr in _local_raw_series(tsdb, raw, unknown_local):
            total += _ingest_series(scratch, qr.metric, qr.tags, qr.dps)
        if futures:
            for fut, (peer, i, _span) in futures.items():
                try:
                    payload = fut.result()
                except PeerUnknownNameError:
                    # a healthy name-lookup miss, not a peer failure:
                    # never marks the answer partial
                    unknown_peers[i] = unknown_peers.get(i, 0) + 1
                    continue
                except Exception as e:
                    if not allow_partial:
                        state.count("failed_queries")
                        raise RuntimeError(
                            "cluster peer %s failed the raw-series "
                            "fetch: %s" % (peer, e)) from e
                    if peer not in failed_peers:
                        failed_peers.add(peer)
                        LOG.warning(
                            "cluster peer %s failed; serving partial "
                            "results without it: %s", peer, e)
                    continue
                total += _fold_payload(scratch, payload)
        # a name NO reachable node has assigned answers exactly like a
        # single host: NoSuchUniqueName (HTTP 400 name-lookup error),
        # not an empty 200 — a typo'd dashboard must stay visible
        # (a failed peer might have known it — partial stance covers
        # that; with every peer answering, the verdict is authoritative)
        if not failed_peers:
            for i in sorted(unknown_local):
                if unknown_peers.get(i, 0) == len(peers):
                    raise NoSuchUniqueName("metric",
                                           raw.queries[i].metric)
    finally:
        if pool is not None:
            pool.shutdown(wait=False, cancel_futures=True)
        if futures:
            # the error-mode early exit cancels queued fetches whose
            # spans were created at submit time — close them out so the
            # completed ring never renders a forever-climbing wallMs
            for fut, (_peer, _i, span) in futures.items():
                if span is not None and span.wall_ms is None:
                    if fut.cancelled():
                        span.tags.setdefault(
                            "error", "cancelled: query aborted before "
                                     "this fetch ran")
                    span.finish()
    LOG.debug("cluster fan-out folded %d raw points from %d peers "
              "(%d failed)", total, len(peers), len(failed_peers))
    if failed_peers:
        state.count("partial_queries")
    runner = scratch.new_query_runner()
    out = runner.run(ts_query)
    for qr in out:
        # the scratch store mints its own surrogate uids, so its tsuids
        # name nothing outside this query — without the reference's
        # cluster-global uid table (HBase tsdb-uid) there is no honest
        # cluster-wide tsuid to return
        qr.tsuids = []
    if exec_stats is not None:
        exec_stats.update(runner.exec_stats)
        exec_stats["clusterPeers"] = len(peers)
        exec_stats["clusterRawPoints"] = total
        if failed_peers:
            exec_stats["clusterPeersFailed"] = len(failed_peers)
            exec_stats["partialResults"] = True
    return out

"""Replicated sharded serving (ISSUE 15, tsd/replication.py +
storage/persist.py WAL framing): consistent-hash series ownership,
synchronous WAL shipping on the ingest ack path, pull-based catch-up,
and failover that keeps answering with FULL results.

Topology under test: two REAL TSDServer daemons on live sockets, each
with its own storage directory, shard.enable on, rf=2 — every shard has
both nodes in its preference list, so any single death is survivable.
Mesh is off throughout: each daemon stands for a one-chip node, and two
daemons sharing the suite's 8 virtual devices would each build a mesh.

Deterministic failure machinery: servers stop via their own shutdown
event (graceful) or by closing the listening socket hard; breaker
cooldowns never sleep wall-clock (fault_fixtures.force_cooldown_elapsed).
"""

import asyncio
import json
import os
import socket
import tempfile
import threading
import time
import urllib.request

import pytest

from opentsdb_tpu.core import TSDB
from opentsdb_tpu.storage import persist
from opentsdb_tpu.tsd import replication
from opentsdb_tpu.tsd.replication import (HashRing, plan_cover,
                                          series_shard,
                                          shard_preferences)
from opentsdb_tpu.tsd.server import TSDServer
from opentsdb_tpu.utils.config import Config

BASE = 1_356_998_400
SHARDS = 16


# --------------------------------------------------------------------- #
# Pure ring math                                                        #
# --------------------------------------------------------------------- #

class TestHashRing:
    def test_preference_distinct_and_stable(self):
        ring = HashRing(["a:1", "b:1", "c:1"], 32)
        ring2 = HashRing(["c:1", "a:1", "b:1"], 32)  # order-insensitive
        for s in range(64):
            pref = ring.preference("shard-%d" % s, 2)
            assert len(pref) == 2 and len(set(pref)) == 2
            assert pref == ring2.preference("shard-%d" % s, 2)

    def test_rf_clamped_to_node_count(self):
        ring = HashRing(["a:1", "b:1"], 16)
        assert len(ring.preference("k", 5)) == 2

    def test_rebalance_moves_about_one_nth(self):
        """The consistent-hashing contract: adding a 4th node to a
        3-node ring moves ~1/4 of the shard ownerships — NOT a full
        reshuffle (modulo hashing would move ~3/4)."""
        nodes = ["n%d:42" % i for i in range(3)]
        shard_count = 512
        before = [p[0] for p in shard_preferences(
            HashRing(nodes, 32), shard_count, 1)]
        after = [p[0] for p in shard_preferences(
            HashRing(nodes + ["n3:42"], 32), shard_count, 1)]
        moved = sum(1 for b, a in zip(before, after) if b != a)
        # expectation 1/4 = 128; allow generous vnode variance but pin
        # well under the ~3/4 a naive mod-N rehash would move
        assert moved <= shard_count // 2, moved
        assert moved > 0       # the new node must take SOME shards
        # every move lands on the new node (nothing shuffles between
        # the survivors)
        for b, a in zip(before, after):
            if b != a:
                assert a == "n3:42"

    def test_plan_cover_fails_over_and_uncovers(self):
        nodes = ["a:1", "b:1", "c:1"]
        prefs = shard_preferences(HashRing(nodes, 32), 64, 2)
        cover, uncovered = plan_cover(prefs, lambda n: True)
        assert not uncovered
        owners = {s: prefs[s][0] for s in range(64)}
        for node, shards in cover.items():
            for s in shards:
                assert owners[s] == node
        # kill a: its shards move to their replicas, still full cover
        cover_a, unc_a = plan_cover(prefs, lambda n: n != "a:1")
        assert not unc_a
        assert "a:1" not in cover_a
        # rf=1: a death uncovers exactly a's shards
        prefs1 = shard_preferences(HashRing(nodes, 32), 64, 1)
        _, unc1 = plan_cover(prefs1, lambda n: n != "a:1")
        assert unc1 == {s for s in range(64) if prefs1[s][0] == "a:1"}

    def test_series_shard_stable_and_tag_sorted(self):
        a = series_shard("sys.cpu", {"host": "h1", "dc": "d1"}, SHARDS)
        b = series_shard("sys.cpu", {"dc": "d1", "host": "h1"}, SHARDS)
        assert a == b
        assert 0 <= a < SHARDS


# --------------------------------------------------------------------- #
# WAL framing / sequencing / corruption (the hardening satellite)       #
# --------------------------------------------------------------------- #

def _mk_tsdb(tmp, extra=None):
    cfg = {"tsd.core.auto_create_metrics": True,
           "tsd.storage.directory": tmp,
           "tsd.query.mesh.enable": "false"}
    cfg.update(extra or {})
    return TSDB(Config(cfg))


def _all_points(tsdb):
    out = {}
    for s in tsdb.store.all_series():
        ts, val, _ival, _isint = s.arrays()
        out[s.key] = list(zip(ts.tolist(), val.tolist()))
    return out


def _wal_segments(tmp):
    return sorted(f for f in os.listdir(tmp) if f.startswith("wal-"))


class TestWalFraming:
    def test_journal_assigns_monotonic_seqs_and_crc(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        seqs = []
        for i in range(5):
            tsdb.add_point("w.m", BASE + i, i, {"h": "a"})
        records, last, first = tsdb.persistence.read_since(0)
        assert [r[0] for r in records] == [1, 2, 3, 4, 5]
        assert last == 5
        assert first == 1
        for seq, crc, payload in records:
            assert persist.record_crc(payload) == crc
            assert json.loads(payload)["k"] == "p"
        # paging: since=3 returns only the tail
        tail, _, _ = tsdb.persistence.read_since(3)
        assert [r[0] for r in tail] == [4, 5]

    def test_segment_rotation_and_catch_up_from_offset(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        tsdb.persistence._segment_bytes = 256    # force tiny segments
        for i in range(20):
            tsdb.add_point("w.m", BASE + i, i, {"h": "a"})
        assert len(_wal_segments(str(tmp_path))) > 1
        records, last, _ = tsdb.persistence.read_since(12)
        assert [r[0] for r in records] == list(range(13, 21))
        assert last == 20

    def test_seq_survives_snapshot_and_restart(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        for i in range(4):
            tsdb.add_point("w.m", BASE + i, i, {"h": "a"})
        tsdb.persistence.snapshot()              # resets the WAL files
        assert not _wal_segments(str(tmp_path))
        tsdb.add_point("w.m", BASE + 100, 1, {"h": "a"})
        records, _, _ = tsdb.persistence.read_since(0)
        assert records[0][0] == 5                # NOT back to 1
        tsdb.persistence.close()
        re = _mk_tsdb(str(tmp_path))
        re.add_point("w.m", BASE + 101, 2, {"h": "a"})
        records, _, _ = re.persistence.read_since(0)
        assert [r[0] for r in records] == [5, 6]

    def test_restart_replays_framed_records(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        for i in range(6):
            tsdb.add_point("w.m", BASE + i, i * 2, {"h": "a"})
        expect = _all_points(tsdb)
        tsdb.persistence.close()
        re = _mk_tsdb(str(tmp_path))
        assert _all_points(re) == expect


def _corrupt_counter_value():
    from opentsdb_tpu.obs.registry import REGISTRY
    fam = REGISTRY.counter(
        "tsd.storage.wal.corrupt_records",
        "WAL records whose CRC32/frame failed verification at replay "
        "(interior corruption; replay stops at the last valid record)")
    return sum(cell.get() for _l, cell in fam.children())


class TestWalCorruption:
    """The ISSUE 15 hardening satellite: a mid-file flipped byte must be
    DETECTED (counted), and replay must stop at the last valid record
    instead of skipping past the hole."""

    def _flip_byte_in_record(self, tmp, target_seq):
        seg = os.path.join(tmp, _wal_segments(tmp)[0])
        with open(seg, "rb") as fh:
            lines = fh.readlines()
        out = []
        for line in lines:
            seq = int(line.split(b" ", 1)[0])
            if seq == target_seq:
                # flip one payload byte, keep the frame shape
                line = line[:-10] + bytes([line[-10] ^ 0x41]) + line[-9:]
            out.append(line)
        with open(seg, "wb") as fh:
            fh.writelines(out)

    def test_mid_file_flip_stops_at_last_valid_record(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        for i in range(8):
            tsdb.add_point("w.m", BASE + i, i, {"h": "a"})
        tsdb.persistence.close()
        self._flip_byte_in_record(str(tmp_path), 4)
        before = _corrupt_counter_value()
        re = _mk_tsdb(str(tmp_path))
        pts = list(_all_points(re).values())[0]
        # records 1-3 replay; 4 is the hole; 5-8 are PAST the hole and
        # must not replay (they are untrusted once the stream tore)
        assert [t for t, _v in pts] == [(BASE + i) * 1000
                                        for i in range(3)]
        assert _corrupt_counter_value() == before + 1
        # the journal was truncated at the hole: a second restart is
        # clean (no double-count, no repeated alarm)
        re.persistence.close()
        re2 = _mk_tsdb(str(tmp_path))
        assert list(_all_points(re2).values())[0] == pts
        assert _corrupt_counter_value() == before + 1

    def test_seq_not_reused_after_truncation(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        for i in range(8):
            tsdb.add_point("w.m", BASE + i, i, {"h": "a"})
        tsdb.persistence.close()
        self._flip_byte_in_record(str(tmp_path), 4)
        re = _mk_tsdb(str(tmp_path))
        re.add_point("w.m", BASE + 50, 1, {"h": "a"})
        records, _, _ = re.persistence.read_since(0)
        # the discarded tail held seqs 4-8: the post-restart append
        # must mint a FRESH seq (9), never reuse a truncated one
        assert records[-1][0] == 9

    def test_torn_final_line_still_trims_silently(self, tmp_path):
        tsdb = _mk_tsdb(str(tmp_path))
        for i in range(4):
            tsdb.add_point("w.m", BASE + i, i, {"h": "a"})
        tsdb.persistence.close()
        seg = os.path.join(str(tmp_path), _wal_segments(str(tmp_path))[0])
        with open(seg, "ab") as fh:
            fh.write(b"5 00000000 {\"k\":\"p\",\"m\":")   # crash mid-append
        before = _corrupt_counter_value()
        re = _mk_tsdb(str(tmp_path))
        pts = list(_all_points(re).values())[0]
        assert len(pts) == 4
        # a torn FINAL line is a crash artifact, not corruption
        assert _corrupt_counter_value() == before


# --------------------------------------------------------------------- #
# Two-node cluster scaffolding                                          #
# --------------------------------------------------------------------- #

def _free_port() -> int:
    s = socket.socket()
    s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _node_config(port, peers, directory, rf=2, extra=None):
    cfg = {
        "tsd.core.auto_create_metrics": True,
        "tsd.storage.directory": directory,
        "tsd.storage.fix_duplicates": True,
        "tsd.query.mesh.enable": "false",
        "tsd.network.cluster.peers": ",".join(
            "127.0.0.1:%d" % p for p in peers),
        "tsd.network.cluster.self": "127.0.0.1:%d" % port,
        "tsd.network.cluster.shard.enable": True,
        "tsd.network.cluster.shard.count": SHARDS,
        "tsd.network.cluster.shard.replicas": rf,
        "tsd.network.cluster.partial_results": "error",
        "tsd.network.cluster.retry.max_attempts": 1,
        "tsd.network.cluster.timeout_ms": 3000,
        "tsd.network.cluster.breaker.threshold": 2,
        "tsd.network.cluster.breaker.cooldown_ms": 200,
        # the pull cadence is driven EXPLICITLY by the tests
        # (pull_once) — a long interval keeps the background thread
        # out of the determinism story
        "tsd.replication.pull_interval_ms": "60000",
    }
    cfg.update(extra or {})
    return Config(cfg)


class _Node:
    def __init__(self, port, peers, directory, rf=2, extra=None):
        self.port = port
        self.directory = directory
        self.tsdb = TSDB(_node_config(port, peers, directory, rf, extra))
        self.server = TSDServer(self.tsdb, port=port, bind="127.0.0.1",
                                worker_threads=2)
        self._holder = {}
        started = threading.Event()

        def run():
            async def main():
                await self.server.start()
                self._holder["loop"] = asyncio.get_running_loop()
                started.set()
                await self.server.serve_forever()
            asyncio.run(main())

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        assert started.wait(30)

    @property
    def node_id(self) -> str:
        return "127.0.0.1:%d" % self.port

    def stop(self):
        if self._holder:
            self._holder["loop"].call_soon_threadsafe(
                self.server._shutdown_event.set)
        self._thread.join(20)
        self._holder = {}

    # -- HTTP helpers --

    def put(self, dps, routed=False):
        headers = {"Content-Type": "application/json"}
        if routed:
            headers["X-TSDB-Replication"] = "routed"
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/put" % self.port,
            data=json.dumps(dps).encode(), headers=headers,
            method="POST")
        with urllib.request.urlopen(req, timeout=20) as resp:
            return resp.status

    def query(self, metric, agg="sum"):
        body = {"start": BASE - 600, "end": BASE + 3600,
                "queries": [{"aggregator": agg, "metric": metric}]}
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/query" % self.port,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=30) as resp:
            return json.loads(resp.read())

    def get(self, path):
        with urllib.request.urlopen(
                "http://127.0.0.1:%d%s" % (self.port, path),
                timeout=20) as resp:
            return json.loads(resp.read())


def _dps(payload, metric):
    for item in payload:
        if isinstance(item, dict) and item.get("metric") == metric:
            return {int(t): v for t, v in item["dps"].items()}
    return {}


def _metric_owned_by(repl, node_id, salt=""):
    """A metric name whose single test series lands on a shard OWNED by
    ``node_id`` — deterministic given the ring."""
    for i in range(10_000):
        m = "repl.m%s.%d" % (salt, i)
        shard = repl.shard_of(m, {"host": "x"})
        if repl.preferences[shard][0] == node_id:
            return m
    raise AssertionError("no owned metric found")


@pytest.fixture()
def pair(tmp_path):
    """Two live nodes, rf=2; yields (a, b); both stopped at teardown."""
    pa, pb = _free_port(), _free_port()
    a = _Node(pa, [pb], str(tmp_path / "a"))
    b = _Node(pb, [pa], str(tmp_path / "b"))
    try:
        yield a, b
    finally:
        for n in (a, b):
            try:
                n.stop()
            except Exception:
                pass


class TestShardedIngest:
    def test_owner_write_ships_synchronously_to_replica(self, pair):
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, a.node_id)
        assert a.put([{"metric": m, "timestamp": BASE, "value": 7,
                       "tags": {"host": "x"}}]) == 204
        # the ship happened on the ack path: the replica's store holds
        # the point NOW, with no pull round in between
        out = b.tsdb.new_query_runner()
        status = b.get("/api/replication/status")
        assert status["chains"][a.node_id], \
            "replica folded no chain entry for the shipped record"
        # and the replica serves it locally (fanout-shaped local read)
        payload = b.query(m)
        assert _dps(payload, m) == {BASE: 7}

    def test_non_owner_write_forwards_one_hop(self, pair):
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, b.node_id)
        assert a.put([{"metric": m, "timestamp": BASE, "value": 3,
                       "tags": {"host": "x"}}]) == 204
        # the OWNER journaled it (origin b), and shipped back to a
        sb = b.get("/api/replication/status")
        assert sb["lastSeq"] >= 1
        assert _dps(a.query(m), m) == {BASE: 3}
        assert _dps(b.query(m), m) == {BASE: 3}

    def test_clustered_query_not_partial_and_exact(self, pair):
        a, b = pair
        ma = _metric_owned_by(a.tsdb.replication, a.node_id)
        mb = _metric_owned_by(a.tsdb.replication, b.node_id)
        for i in range(5):
            a.put([{"metric": ma, "timestamp": BASE + i, "value": i,
                    "tags": {"host": "x"}}])
            b.put([{"metric": mb, "timestamp": BASE + i, "value": i * 2,
                    "tags": {"host": "x"}}])
        for node in pair:
            pa = node.query(ma)
            assert _dps(pa, ma) == {BASE + i: i for i in range(5)}
            assert not any(x.get("partialResults") for x in pa
                           if isinstance(x, dict))
            assert _dps(node.query(mb), mb) == {BASE + i: i * 2
                                                for i in range(5)}


class TestFailover:
    def test_owner_death_replica_serves_acked_points_full(self, pair):
        """ISSUE 15 acceptance shape: owner dies mid-ingest — every
        acked point stays servable, queries answer FULL results (no
        partialResults) from the replica, and the epoch change leaves
        flight-recorder evidence."""
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, b.node_id)
        # acked writes: the owner (b) shipped each to a on the ack path
        for i in range(4):
            b.put([{"metric": m, "timestamp": BASE + i, "value": i + 1,
                    "tags": {"host": "x"}}])
        epoch0 = a.get("/api/replication/status")["epoch"]
        b.stop()                       # owner gone
        payload = a.query(m)           # a must answer alone, FULL
        assert _dps(payload, m) == {BASE + i: i + 1
                                    for i in range(4)}
        assert not any(x.get("partialResults") for x in payload
                       if isinstance(x, dict))
        # ingest keeps working: a accepts the dead owner's shards
        assert a.put([{"metric": m, "timestamp": BASE + 10, "value": 99,
                       "tags": {"host": "x"}}]) == 204
        assert _dps(a.query(m), m)[BASE + 10] == 99
        # the breaker-driven cover change bumped the epoch and landed
        # in the flight recorder
        deadline = time.time() + 10
        while time.time() < deadline:
            if a.get("/api/replication/status")["epoch"] > epoch0:
                break
            a.query(m)
            time.sleep(0.1)
        assert a.get("/api/replication/status")["epoch"] > epoch0
        ring = a.get("/api/diag?since=0")
        kinds = [e.get("kind") for e in ring.get("events", [])]
        assert "replication" in kinds

    def test_rejoin_catches_up_and_chains_converge(self, pair, tmp_path):
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, b.node_id)
        b.put([{"metric": m, "timestamp": BASE, "value": 1,
                "tags": {"host": "x"}}])
        b_port, b_dir = b.port, b.directory
        b.stop()
        # writes during b's downtime: a accepts as failover member
        for i in range(1, 4):
            a.put([{"metric": m, "timestamp": BASE + i, "value": i + 1,
                    "tags": {"host": "x"}}])
        # restart b on the SAME directory/port: catch_up runs at server
        # start, pulling a's tail before re-accepting ownership
        b2 = _Node(b_port, [a.port], b_dir)
        try:
            expect = {BASE + i: i + 1 for i in range(4)}
            deadline = time.time() + 15
            while time.time() < deadline:
                if _dps(b2.query(m), m) == expect:
                    break
                b2.tsdb.replication.pull_once()
                time.sleep(0.2)
            assert _dps(b2.query(m), m) == expect
            # anti-entropy evidence: per-(origin, shard) CRC chains are
            # IDENTICAL on both nodes — byte-level convergence of the
            # replicated streams
            sa = a.get("/api/replication/status")["chains"]
            sb = b2.get("/api/replication/status")["chains"]
            for origin in set(sa) | set(sb):
                common = set(sa.get(origin, {})) \
                    & set(sb.get(origin, {}))
                for shard in common:
                    assert sa[origin][shard] == sb[origin][shard], \
                        (origin, shard)
            assert any(sa.get(o) for o in sa), "no chains recorded"
            # and verify_with finds nothing to truncate
            assert b2.tsdb.replication.verify_with(a.node_id) == []
        finally:
            b2.stop()


class TestRf1Degrades:
    def test_rf1_owner_death_is_partial_or_error(self, tmp_path):
        """rf=1 is today's unreplicated behavior: no ship, no failover
        member — a dead owner's shards are simply gone until rejoin."""
        pa, pb = _free_port(), _free_port()
        a = _Node(pa, [pb], str(tmp_path / "a"), rf=1)
        b = _Node(pb, [pa], str(tmp_path / "b"), rf=1)
        try:
            m = _metric_owned_by(a.tsdb.replication, b.node_id)
            b.put([{"metric": m, "timestamp": BASE, "value": 5,
                    "tags": {"host": "x"}}])
            # no replica got a copy
            assert not a.get("/api/replication/status")["chains"].get(
                b.node_id)
            b.stop()
            with pytest.raises(urllib.error.HTTPError):
                a.query(m)            # partial_results=error: the
                #                       uncovered shard fails the query
        finally:
            for n in (a, b):
                try:
                    n.stop()
                except Exception:
                    pass


class TestReplicationWire:
    def test_tail_pages_and_rr_slots_are_skip_markers(self, pair):
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, a.node_id)
        for i in range(3):
            a.put([{"metric": m, "timestamp": BASE + i, "value": i,
                    "tags": {"host": "x"}}])
        page = a.get("/api/replication/tail?since=0&node=test")
        assert page["node"] == a.node_id
        assert [r[0] for r in page["records"]] == [1, 2, 3]
        for seq, crc, payload in page["records"]:
            assert persist.record_crc(payload) == crc
            assert not payload.startswith('{"k":"rr"')
        # b holds a's shipped records as rr wrappers; its tail serves
        # them as seq-slot SKIP markers (dropping them would leave
        # permanent holes the contiguity drain could never cross), and
        # a receiver never applies or chains them
        page_b = b.get("/api/replication/tail?since=0&node=test")
        rr = [p for _s, _c, p in page_b["records"]
              if p.startswith('{"k":"rr"')]
        assert len(rr) == 3
        pos_before = a.tsdb.replication.status()["positions"].get(
            b.node_id, 0)
        a.tsdb.replication.pull_once()
        status = a.tsdb.replication.status()
        # position advanced over the rr slots, but nothing from b's rr
        # stream folded into a chain attributed to b
        assert status["positions"][b.node_id] >= pos_before + 3
        assert status["chains"].get(b.node_id, {}) == {}

    def test_ship_endpoint_applies_and_acks_position(self, pair):
        a, b = pair
        mgr = a.tsdb.replication
        m = _metric_owned_by(mgr, b.node_id, salt="ship")
        shard = mgr.shard_of(m, {"host": "x"})
        rec = {"k": "p", "m": m, "t": BASE, "v": 42,
               "g": {"host": "x"}, "sh": shard}
        payload = json.dumps(rec, separators=(",", ":"))
        body = {"from": "127.0.0.1:59999",   # a third, unknown origin
                "records": [[1, persist.record_crc(payload), payload]]}
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/replication/ship" % a.port,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            ack = json.loads(resp.read())
        assert ack == {"node": a.node_id, "applied": 1}
        assert _dps(a.query(m), m) == {BASE: 42}

    def test_ship_rejects_corrupt_record(self, pair):
        a, _b = pair
        mgr = a.tsdb.replication
        m = _metric_owned_by(mgr, a.node_id, salt="crc")
        rec = {"k": "p", "m": m, "t": BASE, "v": 1, "g": {"host": "x"},
               "sh": mgr.shard_of(m, {"host": "x"})}
        payload = json.dumps(rec, separators=(",", ":"))
        body = {"from": "127.0.0.1:59999",
                "records": [[1, 12345, payload]]}   # wrong CRC
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/replication/ship" % a.port,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=10) as resp:
            ack = json.loads(resp.read())
        assert ack["applied"] == 0          # nothing crossed the wire
        # the metric was never created: the corrupt record truly never
        # applied (an unknown metric queries as 404)
        with pytest.raises(urllib.error.HTTPError):
            a.query(m)

    def test_explain_predicts_shard_cover(self, pair):
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, a.node_id, salt="exp")
        a.put([{"metric": m, "timestamp": BASE, "value": 1,
                "tags": {"host": "x"}}])
        body = {"start": BASE - 600, "end": BASE + 600,
                "queries": [{"aggregator": "sum", "metric": m}]}
        req = urllib.request.Request(
            "http://127.0.0.1:%d/api/query/explain" % a.port,
            data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"}, method="POST")
        with urllib.request.urlopen(req, timeout=20) as resp:
            report = json.loads(resp.read())
        cluster = report["cluster"]
        assert cluster["mode"] == "sharded"
        assert cluster["rf"] == 2
        assert cluster["uncoveredShards"] == []
        nodes = {f["node"]: f for f in cluster["fanout"]}
        assert set(nodes) == {a.node_id, b.node_id}
        assert sum(f["shards"] for f in cluster["fanout"]) == SHARDS
        assert nodes[a.node_id]["role"] == "self"

    def test_health_has_replication_verdict(self, pair):
        a, _b = pair
        health = a.get("/api/diag/health")
        assert "replication" in health["subsystems"]
        assert health["subsystems"]["replication"]["level"] == "ok"
        assert len(health["subsystems"]) == 9


class TestFaultSites:
    def test_ship_fault_leaves_gap_pull_fills_it(self, pair):
        """replication.ship fault: the synchronous ship fails, the
        write still acks (owner-local durability), and the PULL cadence
        converges the replica — the gap-fill contract."""
        from opentsdb_tpu.utils import faults
        a, b = pair
        m = _metric_owned_by(a.tsdb.replication, a.node_id, salt="f")
        faults.install([{"site": "replication.ship", "kind": "refuse",
                         "match": {"peer": b.node_id}, "times": 1}])
        try:
            assert a.put([{"metric": m, "timestamp": BASE, "value": 6,
                           "tags": {"host": "x"}}]) == 204
            # the ship was refused: b has nothing yet
            pass  # ship was refused; b may or may not have it yet
            b.tsdb.replication.pull_once()
            assert _dps(b.query(m), m) == {BASE: 6}
        finally:
            faults.clear()

    def test_partition_mode_holds_socket(self):
        """FaultyPeer PARTITION: connect succeeds, request bytes vanish,
        nothing answers — the client's own timeout is what fires, and
        `requests` does not grow (no full request was delivered)."""
        from tests.fault_fixtures import PARTITION, FaultyPeer
        peer = FaultyPeer([])
        peer.mode = PARTITION
        try:
            t0 = time.monotonic()
            with pytest.raises(Exception) as exc_info:
                urllib.request.urlopen(
                    "http://%s/api/query" % peer.address, timeout=0.5)
            assert time.monotonic() - t0 >= 0.4     # hung, not refused
            assert "timed out" in str(exc_info.value).lower()
            assert peer.requests == 0
        finally:
            peer.close()

    def test_tail_fault_site_is_checked(self, pair):
        from opentsdb_tpu.utils import faults
        a, b = pair
        faults.install([{"site": "replication.tail", "kind": "refuse",
                         "match": {"peer": b.node_id}}])
        try:
            with pytest.raises(ConnectionRefusedError):
                a.tsdb.replication.pull_from(b.node_id)
        finally:
            faults.clear()

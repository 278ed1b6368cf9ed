"""Sharding + hot-shard replication tests (ISSUE 7, docs/SHARDING.md).

Three layers:

* unit tests for the replication pieces in ``runtime/replica.py``
  (hot tracking, routing, the holder store's version watermark, the
  controller's sticky promotion policy and per-ROUND decay) plus the
  small infrastructure they ride on (``Waiter.add_waits``, the
  ``Samples`` percentile reservoirs, the REPLICA_SLOT markers);
* routing property tests: the same op sequence against 1-server and
  N-server clusters must produce element-wise identical results across
  Array / Matrix / KV / sparse tables — including row ids sitting
  exactly on shard boundaries and row counts that do not divide evenly
  (the off-by-one class the worker-side partition audit covers);
* replica consistency integration: a write-through Add followed by a
  replica-routed Get never observes a version older than the client's
  read-your-writes floor, owner version bumps invalidate (repair)
  rather than serve stale, and demotion prunes holder stores.
"""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.message import (Message, MsgType,
                                         mark_replica_reply,
                                         replica_row_count)
from multiverso_tpu.runtime import replica as rm
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.sharding.rows import row_offsets
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.dashboard import Dashboard, Samples
from multiverso_tpu.util.waiter import Waiter


@pytest.fixture
def env():
    mv.init([])
    yield
    mv.shutdown()


# ---------------------------------------------------------------------------
# unit: replica building blocks
# ---------------------------------------------------------------------------

class TestHotTracker:
    def test_report_counts_and_decay(self):
        t = rm.HotTracker(cadence=4)
        for _ in range(4):
            t.note(np.array([7, 7, 3], np.int32))
        assert t.due
        rows, counts = t.take_report(top_k=2)
        # Duplicate ids within one request overweight (documented), but
        # ordering by count must hold: 7 hotter than 3.
        assert rows.tolist()[0] == 7
        assert counts[0] >= counts[1]
        assert not t.due
        # Decay: a row that stops being read ages out of the dict.
        for _ in range(16):
            t.note(np.array([1], np.int32))
            if t.due:
                t.take_report(top_k=4)
        assert 7 not in t._counts or t._counts[7] < 1.0

    def test_window_deferred_not_counted_per_get(self):
        t = rm.HotTracker(cadence=100)
        t.note(np.arange(5, dtype=np.int32))
        assert t._counts == {}  # fold deferred to take_report


class TestReplicaRouter:
    def test_mask_and_stale_epoch(self):
        r = rm.ReplicaRouter(4, salt=0)
        assert not r.active
        assert r.apply(3, np.array([5, 9], np.int32))
        assert r.active and r.epoch == 3
        # Reordered (stale) broadcast must be ignored.
        assert not r.apply(2, np.array([1], np.int32))
        mask = r.replicated_mask(np.array([1, 5, 8, 9], np.int32))
        assert mask.tolist() == [False, True, False, True]

    def test_route_stripes_and_prefers_local(self):
        r = rm.ReplicaRouter(4, salt=0)
        rows = np.arange(16, dtype=np.int32)
        assert sorted(set(r.route(rows).tolist())) == [0, 1, 2, 3]
        pref = rm.ReplicaRouter(4, salt=0, preferred=2)
        assert set(pref.route(rows).tolist()) == {2}

    def test_dead_holder_routes_to_owner_sentinel(self):
        # A holder declared dead must not keep receiving striped rows:
        # route() returns -1 for its picks (partition falls back to the
        # owner) until a reply from it re-includes it.
        r = rm.ReplicaRouter(4, salt=0)
        rows = np.arange(16, dtype=np.int32)
        r.mark_dead(2)
        out = r.route(rows)
        assert 2 not in set(out.tolist())
        assert (out[rows % 4 == 2] == -1).all()
        r.mark_alive(2)
        assert 2 in set(r.route(rows).tolist())

    def test_empty_map_deactivates(self):
        r = rm.ReplicaRouter(2)
        r.apply(1, np.array([3], np.int32))
        assert r.active
        r.apply(2, np.empty(0, np.int32))
        assert not r.active
        assert not r.replicated_mask(np.array([3], np.int32)).any()


class TestReplicaStore:
    def _vals(self, rows, fill):
        return np.full((len(rows), 2), fill, np.float32)

    def test_sync_never_moves_backward(self):
        s = rm.ReplicaStore()
        rows = np.array([1, 2], np.int32)
        s.apply_sync(rows, self._vals(rows, 5.0), owner_sid=0, version=5)
        s.apply_sync(rows, self._vals(rows, 3.0), owner_sid=0, version=3)
        groups, keys, vals = s.serve(rows, 2, np.float32)
        assert groups == [(0, 5, 2)]
        np.testing.assert_array_equal(vals, self._vals(rows, 5.0))

    def test_watermark_recertifies_untouched_rows(self):
        # The defect the watermark exists for: a row pushed at version 2
        # and never touched by later Adds must not read as stale once the
        # owner's version advances — a flush that drained every dirty row
        # certifies ALL of the owner's entries at its version.
        s = rm.ReplicaStore()
        s.apply_sync(np.array([1], np.int32), self._vals([1], 1.0),
                     owner_sid=0, version=2)
        s.apply_sync(np.array([9], np.int32), self._vals([9], 4.0),
                     owner_sid=0, version=40, watermark=True)
        groups, _, _ = s.serve(np.array([1, 9], np.int32), 2, np.float32)
        assert groups == [(0, 40, 2)]  # floor = watermark, not 2

    def test_watermark_scoped_to_owner(self):
        s = rm.ReplicaStore()
        s.apply_sync(np.array([1], np.int32), self._vals([1], 1.0),
                     owner_sid=0, version=2)
        s.apply_sync(np.array([9], np.int32), self._vals([9], 4.0),
                     owner_sid=1, version=40, watermark=True)
        groups, _, _ = s.serve(np.array([1, 9], np.int32), 2, np.float32)
        assert (0, 2, 1) in groups and (1, 40, 1) in groups

    def test_seq_gap_drops_owner_entries(self):
        # A lost sync chunk (dead holder writer) must not be papered
        # over by a later watermark: the holder detects the per-owner
        # seq gap and drops that owner's entries before applying — the
        # dropped rows miss and repair instead of serving values a lost
        # refresh should have replaced.
        s = rm.ReplicaStore()
        s.apply_sync(np.array([1], np.int32), self._vals([1], 1.0),
                     owner_sid=0, version=2, seq=0)
        # seq 1 lost; seq 2 arrives with a watermark.
        s.apply_sync(np.array([9], np.int32), self._vals([9], 4.0),
                     owner_sid=0, version=40, watermark=True, seq=2)
        groups, keys, _ = s.serve(np.array([1, 9], np.int32), 2,
                                  np.float32)
        assert keys.tolist() == [9]  # row 1 dropped, not certified
        assert groups == [(0, 40, 1)]

    def test_seq_gap_scoped_to_owner(self):
        s = rm.ReplicaStore()
        s.apply_sync(np.array([1], np.int32), self._vals([1], 1.0),
                     owner_sid=0, version=2, seq=0)
        s.apply_sync(np.array([9], np.int32), self._vals([9], 4.0),
                     owner_sid=1, version=7, seq=5)  # other owner's gap
        _, keys, _ = s.serve(np.array([1, 9], np.int32), 2, np.float32)
        assert keys.tolist() == [1, 9]  # owner 0 untouched

    def test_redirty_refills_dirty_set(self):
        # The communicator's failure echo: lost chunk rows re-enter the
        # dirty set (promoted rows only) so the next flush re-pushes.
        set_flag("replica_hot_rows", 4)
        st = rm.ServerReplicaState(row_offset=0, my_rows=16)
        st.apply_map(1, np.array([2, 3], np.int32))
        st._dirty.clear()  # the initial push drained them
        st.redirty(np.array([2, 3, 9], np.int32))  # 9 not promoted
        assert st._dirty == {2, 3}

    def test_prune_and_missing_rows_absent(self):
        s = rm.ReplicaStore()
        rows = np.array([1, 2, 3], np.int32)
        s.apply_sync(rows, self._vals(rows, 1.0), owner_sid=0, version=1)
        s.prune_to(np.array([2], np.int32))
        groups, keys, _ = s.serve(np.array([1, 2, 3], np.int32), 2,
                                  np.float32)
        assert keys.tolist() == [2]
        assert groups == [(0, 1, 1)]
        assert len(s) == 1


class TestReplicaCoordinator:
    def _ingest(self, c, tid, rows, counts, reporter=0):
        return c.ingest(tid, np.asarray(rows, np.int32),
                        np.asarray(counts, np.int32), reporter=reporter)

    def test_promotes_above_threshold_only(self):
        set_flag("replica_hot_rows", 2)
        set_flag("replica_min_gets", 4)
        c = rm.ReplicaCoordinator()
        assert self._ingest(c, 0, [5, 6, 7], [10, 9, 1])
        assert c.promoted[0].tolist() == [5, 6]  # 7 below threshold

    def test_sticky_full_budget_no_eviction_by_noise(self):
        set_flag("replica_hot_rows", 2)
        set_flag("replica_min_gets", 4)
        c = rm.ReplicaCoordinator()
        self._ingest(c, 0, [5, 6], [10, 10])
        # A hotter challenger does not evict while incumbents stay warm:
        # boundary swaps cost a map broadcast + a full value push each.
        assert not self._ingest(c, 0, [5, 6, 8], [10, 10, 30], reporter=1)
        assert sorted(c.promoted[0].tolist()) == [5, 6]

    def test_demotion_when_cooled(self):
        set_flag("replica_hot_rows", 2)
        set_flag("replica_min_gets", 4)
        c = rm.ReplicaCoordinator()
        self._ingest(c, 0, [5, 6], [32, 32])
        # Same reporter again and again = new ROUND each time -> decay;
        # row 6 stops being reported and must eventually fall out.
        changed = False
        for _ in range(8):
            changed = self._ingest(c, 0, [5], [32]) or changed
        assert changed
        assert c.promoted[0].tolist() == [5]

    def test_round_decay_not_per_report(self):
        # 4 servers reporting once each is ONE round: counts must decay
        # once, not 4 times — a per-report decay would scale the decay
        # rate with the server count and crush every row toward the
        # threshold exactly on big clusters (a regression seen
        # at N=4).
        set_flag("replica_hot_rows", 4)
        set_flag("replica_min_gets", 4)
        c = rm.ReplicaCoordinator()
        for rep in range(4):
            self._ingest(c, 0, [rep], [8], reporter=rep)
        assert all(v == 8.0 for v in c._counts[0].values())
        self._ingest(c, 0, [0], [8], reporter=0)  # round 2 begins
        assert c._counts[0][1] == 4.0  # decayed exactly once

    def test_budget_zero_disables(self):
        set_flag("replica_hot_rows", 0)
        c = rm.ReplicaCoordinator()
        assert not self._ingest(c, 0, [1], [100])
        assert c.promoted == {}


class TestReplicaMapWire:
    def test_pack_unpack_roundtrip(self):
        promoted = {0: np.array([1, 5], np.int32),
                    3: np.array([7], np.int32),
                    4: np.empty(0, np.int32)}
        blobs = rm.pack_replica_map(12, promoted)
        epoch, got = rm.unpack_replica_map(blobs)
        assert epoch == 12
        assert sorted(got) == [0, 3, 4]
        for tid in promoted:
            np.testing.assert_array_equal(got[tid], promoted[tid])

    def test_replica_slot_markers(self):
        msg = Message(src=0, dst=1, msg_type=MsgType.Reply_Get)
        assert replica_row_count(msg) == 0  # unmarked / legacy peer
        mark_replica_reply(msg, 0)
        assert replica_row_count(msg) == 0
        mark_replica_reply(msg, 17)
        assert replica_row_count(msg) == 17


class TestWaiterAddWaits:
    def test_extends_pending_count(self):
        w = Waiter(num_wait=1)
        w.add_waits(2)
        w.notify()
        w.notify()
        assert not w.wait(timeout=0.05)
        w.notify()
        assert w.wait(timeout=1.0)

    def test_completed_waiter_not_rearmed(self):
        w = Waiter(num_wait=1)
        w.notify()
        w.add_waits(3)  # abort/completion raced the repair: must drop
        assert w.wait(timeout=1.0)


class TestSamples:
    def test_percentiles_and_snapshot(self):
        s = Samples("t", cap=100)
        for v in range(1, 101):
            s.add(float(v))
        assert s.count == 100
        assert 45 <= s.percentile(50) <= 55
        snap = s.snapshot()
        assert snap["count"] == 100 and snap["max"] == 100.0
        assert snap["p50"] <= snap["p90"] <= snap["p99"] <= snap["max"]

    def test_ring_overwrite_bounds_memory(self):
        s = Samples("t2", cap=4)
        for v in range(100):
            s.add(float(v))
        assert len(s._buf) == 4
        assert s.count == 100
        assert s.percentile(0) >= 96.0  # only the newest cap retained


# ---------------------------------------------------------------------------
# property: 1-server vs N-server element-wise equivalence (satellite 2)
# ---------------------------------------------------------------------------

def _matrix_workload(num_row, num_col, sparse=False):
    """Deterministic add/get script touching every boundary row."""
    def body(rank):
        rng = np.random.default_rng(7)
        table = mv.create_matrix_table(num_row, num_col,
                                       is_sparse=sparse)
        if table is None:  # server-only rank: host the shard, then wait
            mv.current_zoo().barrier()
            return None
        outs = []
        for step in range(6):
            ids = np.unique(rng.integers(0, num_row, 12).astype(np.int32))
            table.add_rows(ids, rng.standard_normal(
                (ids.size, num_col)).astype(np.float32))
            # Boundary sweep: every shard edge and its neighbors, for
            # every POSSIBLE server count exercised by the test matrix
            # (off-by-one splits were the audit target).
            edge = []
            for n in (1, 2, 3, 4):
                for off in row_offsets(num_row, n):
                    edge.extend((off - 1, off, off + 1))
            edge = np.unique(np.clip(np.asarray(edge, np.int32), 0,
                                     num_row - 1))
            outs.append(table.get_rows(edge).copy())
            outs.append(table.get().copy())
        mv.current_zoo().barrier()
        return outs

    return body


def _run_sizes(body, sizes, argv=None):
    results = {}
    for n in sizes:
        roles = None if n == 1 else ["all"] + ["server"] * (n - 1)
        cluster = LocalCluster(n, argv=list(argv or []), roles=roles)
        cluster.timeout = 180.0
        results[n] = cluster.run(body)[0]
    return results


class TestShardEquivalence:
    @pytest.mark.parametrize("num_row", [16, 17, 3])
    def test_matrix_dense_1_vs_n(self, num_row):
        # 17 rows does not divide by 2 or 3 (remainder goes to the last
        # shard); 3 rows < 4 servers degenerates to one row per server.
        res = _run_sizes(_matrix_workload(num_row, 3), (1, 2, 3))
        for n in (2, 3):
            for a, b in zip(res[1], res[n]):
                np.testing.assert_allclose(a, b, rtol=0, atol=0,
                                           err_msg=f"n={n}")

    def test_matrix_dense_1_vs_n_with_replication(self):
        # Same equivalence with hot-shard replication ON: a single
        # worker's read-your-writes floor makes replica routing exact
        # for its own adds, so results must stay bit-identical.
        res = _run_sizes(
            _matrix_workload(16, 3), (1, 2, 3),
            argv=["-replica_hot_rows=8", "-replica_report_gets=4",
                  "-replica_min_gets=1", "-replica_sync_every=2"])
        for n in (2, 3):
            for a, b in zip(res[1], res[n]):
                np.testing.assert_allclose(a, b, rtol=0, atol=0,
                                           err_msg=f"n={n}")

    def test_matrix_sparse_1_vs_n(self):
        def body(rank):
            rng = np.random.default_rng(3)
            table = mv.create_matrix_table(10, 2, is_sparse=True)
            if table is None:
                mv.current_zoo().barrier()
                return None
            outs = [table.get().copy()]
            for _ in range(4):
                ids = np.unique(rng.integers(0, 10, 4).astype(np.int32))
                table.add_rows(ids, rng.standard_normal(
                    (ids.size, 2)).astype(np.float32))
                outs.append(table.get().copy())
            mv.current_zoo().barrier()
            return outs

        res = _run_sizes(body, (1, 2, 3))
        for n in (2, 3):
            for a, b in zip(res[1], res[n]):
                np.testing.assert_allclose(a, b, err_msg=f"n={n}")

    def test_array_1_vs_n(self):
        def body(rank):
            rng = np.random.default_rng(11)
            table = mv.create_array_table(13)  # 13 % 2, 13 % 3 != 0
            if table is None:
                mv.current_zoo().barrier()
                return None
            outs = []
            for _ in range(4):
                table.add(rng.standard_normal(13).astype(np.float32))
                outs.append(table.get().copy())
            mv.current_zoo().barrier()
            return outs

        res = _run_sizes(body, (1, 2, 3))
        for n in (2, 3):
            for a, b in zip(res[1], res[n]):
                np.testing.assert_allclose(a, b, err_msg=f"n={n}")

    def test_kv_1_vs_n(self):
        def body(rank):
            table = mv.create_kv_table()
            if table is None:
                mv.current_zoo().barrier()
                return None
            keys = np.array([0, 1, 7, 100, 101, 10**6], np.int64)
            for step in range(3):
                table.add(keys, np.arange(keys.size, dtype=np.float32)
                          + step)
            got = table.get(keys)
            mv.current_zoo().barrier()
            return sorted(got.items())

        res = _run_sizes(body, (1, 2, 3))
        assert res[1] == res[2] == res[3]


# ---------------------------------------------------------------------------
# integration: replica consistency (satellite 3)
# ---------------------------------------------------------------------------

_REPL_ARGS = ["-replica_hot_rows=8", "-replica_report_gets=4",
              "-replica_min_gets=1", "-replica_sync_every=2"]


def _drive_until(pred, table, ids, limit=400):
    for _ in range(limit):
        table.get_rows(ids)
        if pred():
            return True
    return False


class TestReplicaConsistency:
    # Topology note for all tests here: both ranks are worker+server
    # (LocalCluster default role "all"), so each rank's worker routes
    # replicated rows to its LOCAL shard. Head rows 0..k live in server
    # 0's range — rank 1 is therefore THE replica reader (its local
    # shard serves them from the replica store), and rank 1's own adds
    # to the head (acked by owner server 0) are exactly what the
    # read-your-writes floor must protect. Rank 0's head reads hit the
    # owner directly and are trivially fresh; a rank reading rows
    # another rank writes is only promised BOUNDED staleness, so a
    # passive reader asserts per-row monotonicity, not equality.
    def test_read_your_writes_and_hits(self):
        def body(rank):
            Dashboard.reset()
            table = mv.create_matrix_table(32, 4)
            base = np.arange(128, dtype=np.float32).reshape(32, 4)
            shadow = base.copy()
            if rank == 0:
                table.add(base.copy())
            mv.current_zoo().barrier()
            head = np.arange(6, dtype=np.int32)
            router = table._replica_router
            assert router is not None
            ok = _drive_until(lambda: router.active, table, head)
            mismatch = 0
            prev = None
            for step in range(60):
                got = table.get_rows(head)
                if rank == 1:
                    # The adder: read-your-writes makes every one of
                    # its reads exact, replica-served or repaired.
                    if not np.array_equal(got, shadow[head]):
                        mismatch += 1
                    if step % 10 == 0:
                        table.add_rows(head, np.ones((6, 4), np.float32))
                        shadow[head] += 1.0
                else:
                    # Passive reader: bounded staleness — values must
                    # never move BACKWARD (store version ordering).
                    if prev is not None and np.any(got < prev - 1e-6):
                        mismatch += 1
                    prev = got.copy()
            mv.current_zoo().barrier()
            hits = Dashboard.get(rm.REPLICA_HIT).count
            mv.current_zoo().barrier()
            return ok, mismatch, hits

        results = LocalCluster(2, argv=list(_REPL_ARGS)).run(body)
        assert all(r[0] for r in results), "promotion never happened"
        assert all(r[1] == 0 for r in results), \
            f"stale replica reads observed: {results}"
        # Replica stores actually served rows somewhere in the run.
        assert sum(r[2] for r in results) > 0

    def test_owner_bump_invalidates_stale_replica(self):
        # Between rank 1's Add ack (which raises its RYW floor) and the
        # owner's next write-through flush, rank 1's local replica rows
        # are BELOW the floor: its Get must repair to the owner (stale /
        # repair counters fire), never serve the pre-add value.
        def body(rank):
            Dashboard.reset()
            table = mv.create_matrix_table(32, 4)
            shadow = np.zeros((32, 4), np.float32)
            if rank == 0:
                table.add(np.zeros((32, 4), np.float32))
            mv.current_zoo().barrier()
            head = np.arange(4, dtype=np.int32)
            router = table._replica_router
            _drive_until(lambda: router.active, table, head)
            bad = 0
            for step in range(30):
                if rank == 1:
                    table.add_rows(head,
                                   np.full((4, 4), 1.0, np.float32))
                    shadow[head] += 1.0
                    got = table.get_rows(head)  # immediately post-add
                    if not np.array_equal(got, shadow[head]):
                        bad += 1
                else:
                    table.get_rows(head)
            mv.current_zoo().barrier()
            stale = Dashboard.get(rm.REPLICA_STALE).count
            repairs = Dashboard.get(rm.REPLICA_REPAIR).count
            mv.current_zoo().barrier()
            return bad, stale, repairs

        results = LocalCluster(2, argv=list(_REPL_ARGS)).run(body)
        assert all(r[0] == 0 for r in results), f"stale read: {results}"
        # The invalidation path actually fired somewhere in the run.
        assert sum(r[1] + r[2] for r in results) > 0

    def test_demotion_prunes_holder_store(self, env):
        # Server-side demotion: adopting a map that drops a row prunes
        # the holder's store entry (the worker stops routing on the same
        # epoch; a racing Get would miss and repair — never serve a
        # demoted ghost).
        set_flag("replica_hot_rows", 4)
        st = rm.ServerReplicaState(row_offset=16, my_rows=16)
        st.apply_map(1, np.array([2, 3], np.int32))  # foreign rows
        st.store.apply_sync(np.array([2, 3], np.int32),
                            np.ones((2, 2), np.float32), owner_sid=0,
                            version=1)
        assert len(st.store) == 2
        st.apply_map(2, np.array([2], np.int32))  # 3 demoted
        assert len(st.store) == 1
        _, keys, _ = st.store.serve(np.array([2, 3], np.int32), 2,
                                    np.float32)
        assert keys.tolist() == [2]

    def test_owner_promotion_pushes_initial_values(self):
        # MatrixServer.apply_replica_map on the OWNER must emit
        # Request_ReplicaSync messages carrying the CURRENT values of
        # newly promoted own rows toward every holder, chunked at
        # -replica_sync_rows with the watermark flag on the LAST chunk
        # only (an early-chunk watermark would certify rows still in
        # flight behind it).
        def body(rank):
            from multiverso_tpu.runtime import actor as actors
            table = mv.create_matrix_table(8, 2)
            base = np.arange(16, dtype=np.float32).reshape(8, 2)
            if rank == 0:
                table.add(base.copy())
            mv.current_zoo().barrier()
            if rank != 0:
                mv.current_zoo().barrier()
                return None
            srv = mv.current_zoo()._actors[actors.SERVER] \
                ._store[table.table_id]
            # Quiesced cluster: driving the server table from here
            # cannot race its actor (no requests are in flight).
            msgs = srv.apply_replica_map(
                epoch=5, rows=np.array([0, 1, 2, 42], np.int32))
            mv.current_zoo().barrier()
            return [(m.type_int, m.dst,
                     m.data[0].as_array(np.int32).tolist(),
                     m.data[1].as_array(np.float32).tolist(),
                     m.data[2].as_array(np.int32).tolist())
                    for m in msgs]

        args = ["-replica_hot_rows=4", "-replica_sync_rows=2"]
        msgs = LocalCluster(2, argv=args).run(body)[0]
        # Rows 0..2 are own (server 0 owns rows 0..3 of 8); 42 is out of
        # range and ignored by the own-row filter. 3 rows at cap 2 = 2
        # chunks, each to the single holder (rank 1 / server 1).
        assert len(msgs) == 2
        for type_int, dst, rows, vals, meta in msgs:
            assert type_int == int(MsgType.Request_ReplicaSync)
            assert dst == 1
            assert meta[0] == 0  # owner server id
            np.testing.assert_allclose(
                np.asarray(vals),
                np.arange(16, dtype=np.float32)[
                    np.repeat(np.asarray(rows), 2) * 2
                    + np.tile([0, 1], len(rows))])
        (r1, m1), (r2, m2) = [(m[2], m[4]) for m in msgs]
        assert r1 + r2 == [0, 1, 2]
        assert (m1[2], m2[2]) == (0, 1)  # watermark on the LAST chunk

    def test_sync_mode_disables_replication(self):
        def body(rank):
            table = mv.create_matrix_table(16, 2)
            active = table._replica_router is not None
            mv.current_zoo().barrier()
            return active

        results = LocalCluster(
            2, argv=["-sync=true"] + list(_REPL_ARGS)).run(body)
        assert results == [False, False]

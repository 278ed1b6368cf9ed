"""Runtime tests: zoo bootstrap, registration, barrier, vector clocks.

Mirrors the reference's in-process PS environment trick
(ref: Test/unittests/multiverso_env.h:9-31) and multi-rank integration
tests run under mpirun (ref: deploy/docker/Dockerfile:100-110), here on an
in-process virtual cluster.
"""

import threading
import time

import pytest

import multiverso_tpu as mv
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.runtime.server import _VectorClock


def test_init_shutdown_single_rank():
    mv.init([])
    assert mv.rank() == 0
    assert mv.size() == 1
    assert mv.num_workers() == 1
    assert mv.num_servers() == 1
    assert mv.worker_id() == 0
    assert mv.server_id() == 0
    mv.barrier()
    mv.shutdown()


def test_init_parses_flags_and_returns_rest():
    rest = mv.init(["prog", "-sync=true", "-other_thing=1"])
    assert rest == ["prog", "-other_thing=1"]
    from multiverso_tpu.util.configure import get_flag
    assert get_flag("sync") is True
    mv.shutdown()


def test_multirank_registration_assigns_dense_ids():
    def body(rank):
        zoo = mv.current_zoo()
        assert zoo.size == 4
        assert zoo.num_workers == 4
        assert zoo.num_servers == 4
        assert zoo.worker_id == zoo.rank  # dense, rank order
        assert zoo.server_rank(zoo.server_id) == zoo.rank
        zoo.barrier()
        return zoo.rank

    assert LocalCluster(4).run(body) == [0, 1, 2, 3]


def test_worker_only_and_server_only_roles():
    # Heterogeneous roles: rank0=all, rank1=worker-only, rank2=server-only.
    # Dense id assignment in rank order (ref: src/controller.cpp:46-66).
    def body(rank):
        zoo = mv.current_zoo()
        assert zoo.num_workers == 2
        assert zoo.num_servers == 2
        assert zoo.worker_rank(0) == 0 and zoo.worker_rank(1) == 1
        assert zoo.server_rank(0) == 0 and zoo.server_rank(1) == 2
        return (zoo.worker_id, zoo.server_id)

    result = LocalCluster(3, roles=["all", "worker", "server"]).run(body)
    assert result == [(0, 0), (1, -1), (-1, 1)]


def test_barrier_actually_blocks():
    arrived = []

    def body(rank):
        if rank == 1:
            time.sleep(0.2)
        arrived.append(rank)
        zoo = mv.current_zoo()
        zoo.barrier()
        # After barrier, every rank must have arrived.
        assert sorted(arrived) == [0, 1]
        return True

    assert LocalCluster(2).run(body) == [True, True]


class TestVectorClock:
    def test_update_levels_when_all_tick(self):
        clock = _VectorClock(3)
        assert not clock.update(0)
        assert not clock.update(1)
        assert clock.update(2)  # all at 1 -> global catches max
        assert clock.global_clock == 1

    def test_faster_worker_does_not_level(self):
        clock = _VectorClock(2)
        assert not clock.update(0)
        assert not clock.update(0)  # worker 0 at 2, worker 1 at 0
        assert not clock.update(1)  # min=1 -> global 1, max=2 -> not level
        assert clock.global_clock == 1
        assert clock.update(1)  # both at 2
        assert clock.global_clock == 2

    def test_finish_train_releases(self):
        clock = _VectorClock(2)
        clock.update(0)
        assert clock.finish_train(1)  # worker 1 retires; global -> max(1)
        assert clock.global_clock == 1


def test_error_on_one_rank_surfaces_quickly():
    # A failing rank must abort the cluster (unblocking sibling barriers),
    # not hang until the join timeout.
    def body(rank):
        if rank == 1:
            raise ZeroDivisionError("boom")
        mv.current_zoo().barrier()  # would mispair without abort
        return rank

    cluster = LocalCluster(2)
    cluster.timeout = 15
    start = time.monotonic()
    with pytest.raises(ZeroDivisionError):
        cluster.run(body)
    assert time.monotonic() - start < 10


def test_ma_mode_skips_ps():
    mv.init(["-ma=true"])
    zoo = mv.current_zoo()
    assert zoo.num_workers == 0  # no PS actors
    with pytest.raises(RuntimeError):
        zoo.send_to("worker", None)
    mv.shutdown()


class TestAddCoalescing:
    """Deterministic coverage of the worker's shard-message coalescing
    (the TCP two-process flavor in test_net_integration.py exercises it
    end to end but cannot control mailbox timing)."""

    class _FakeNet:
        in_process = False

    class _FakeZoo:
        def __init__(self):
            self.rank = 1
            self.num_servers = 2
            self.net = TestAddCoalescing._FakeNet()
            self.sent = []
            self._actors = {}

        def register_actor(self, actor):
            self._actors[actor.name] = actor

        def deregister_actor(self, actor):
            self._actors.pop(actor.name, None)

        def send_to(self, name, msg):
            self.sent.append((name, msg))

        def server_rank(self, server_id):
            return server_id  # server 0 remote (rank 0), server 1 local

        def rank_to_server_id(self, rank):
            return rank  # dense map, mirroring server_rank above

    class _FakeTable:
        def __init__(self):
            self.events = []

        def partition(self, blobs, msg_type):
            return {0: list(blobs), 1: list(blobs)}

        def reset(self, msg_id, n):
            self.events.append(("reset", msg_id, n))

        def notify(self, msg_id):
            self.events.append(("notify", msg_id))

        def fail(self, msg_id, reason, count=True):
            self.events.append(("fail", msg_id, reason))

        def note_version(self, server_id, version):
            self.events.append(("version", server_id, version))

        def note_add_ack(self, server_id, version):
            # Add acks carry the version AND raise the RYW floor
            # (table_interface.note_add_ack); the fake only records.
            self.events.append(("version", server_id, version))

        def abort(self, reason):
            self.events.append(("abort", reason))

    def _worker(self):
        import numpy as np

        from multiverso_tpu.core.blob import Blob
        from multiverso_tpu.core.message import Message, MsgType
        from multiverso_tpu.runtime.worker import Worker
        from multiverso_tpu.util.configure import set_flag
        set_flag("sync", False)
        set_flag("coalesce_adds", True)
        zoo = self._FakeZoo()
        worker = Worker(zoo)  # thread never started: drive handlers
        table = self._FakeTable()
        worker.register_table(table)
        def add(msg_id):
            msg = Message(src=1, dst=-1, msg_type=MsgType.Request_Add,
                          table_id=0, msg_id=msg_id)
            msg.push(Blob(np.ones(4, np.float32)))
            return msg
        return worker, zoo, table, add, Message, MsgType

    def test_remote_shards_stage_local_shards_send(self):
        worker, zoo, table, add, Message, MsgType = self._worker()
        assert worker._coalesce
        worker._process_add(add(1))
        worker._process_add(add(2))
        # Local (dst == own rank) shards went straight out; remote
        # shards are staged for dst rank 0.
        assert [m.dst for _, m in zoo.sent] == [1, 1]
        assert all(m.type == MsgType.Request_Add for _, m in zoo.sent)
        assert len(worker._pending[0]) == 2
        # A Get flushes the staged adds FIRST (add-before-get order on
        # the wire), as one Request_BatchAdd.
        get = Message(src=1, dst=-1, msg_type=MsgType.Request_Get,
                      table_id=0, msg_id=3)
        worker._process_get(get)
        types = [m.type for _, m in zoo.sent]
        batch_at = types.index(MsgType.Request_BatchAdd)
        first_get_at = types.index(MsgType.Request_Get)
        assert batch_at < first_get_at
        assert not worker._pending
        from multiverso_tpu.core.message import unpack_add_batch
        batch = next(m for _, m in zoo.sent
                     if m.type == MsgType.Request_BatchAdd)
        subs = unpack_add_batch(batch)
        assert [s.msg_id for s in subs] == [1, 2]
        assert batch.dst == 0

    def test_count_cap_flushes(self):
        worker, zoo, table, add, Message, MsgType = self._worker()
        for i in range(worker._max_batch_msgs):
            worker._process_add(add(i))
        batches = [m for _, m in zoo.sent
                   if m.type == MsgType.Request_BatchAdd]
        assert len(batches) == 1  # cap reached -> flushed mid-burst
        assert not worker._pending

    def test_single_staged_shard_sends_plain(self):
        worker, zoo, table, add, Message, MsgType = self._worker()
        worker._process_add(add(7))
        worker._flush_pending()
        remote = [m for _, m in zoo.sent if m.dst == 0]
        assert len(remote) == 1 and remote[0].type == MsgType.Request_Add

    def test_sync_mode_disables_coalescing(self):
        import numpy as np

        from multiverso_tpu.runtime.worker import Worker
        from multiverso_tpu.util.configure import set_flag
        set_flag("sync", True)
        try:
            zoo = self._FakeZoo()
            worker = Worker(zoo)
            assert not worker._coalesce
        finally:
            set_flag("sync", False)

    def test_malformed_batch_still_acks_every_sub(self):
        # The reply must go out in EVERY path: a truncated batch (blob
        # count disagrees with the descriptor) acks each sub the
        # descriptor names as FAILED, so no waiter strands (same
        # invariant as the per-message handlers' finally-send).
        import numpy as np

        from multiverso_tpu.core.blob import Blob
        from multiverso_tpu.core.message import (Message, MsgType,
                                                 pack_add_batch)
        from multiverso_tpu.runtime.server import Server
        zoo = self._FakeZoo()
        server = Server(zoo)
        subs = []
        for i in range(2):
            sub = Message(src=1, dst=0, msg_type=MsgType.Request_Add,
                          table_id=0, msg_id=50 + i)
            sub.push(Blob(np.ones(4, np.float32)))
            subs.append(sub)
        batch = pack_add_batch(subs)
        batch.data = batch.data[:-1]  # truncate a payload blob
        server._process_batch_add(batch)
        replies = [m for _, m in zoo.sent
                   if m.type == MsgType.Reply_BatchAdd]
        assert len(replies) == 1
        desc = replies[0].data[0].as_array(np.int32)
        assert desc[0] == 2
        # Stride-4 descriptor: (table_id, msg_id, err, version); the
        # unpack-failure path cannot resolve versions (-1).
        assert list(desc[1:9]) == [0, 50, 1, -1, 0, 51, 1, -1]

    def test_batched_reply_notifies_and_fails_per_sub(self):
        import numpy as np

        from multiverso_tpu.core.blob import Blob
        from multiverso_tpu.core.message import Message, MsgType
        worker, zoo, table, add, _, _ = self._worker()
        reply = Message(src=0, dst=1, msg_type=MsgType.Reply_BatchAdd)
        reply.push(Blob(np.array([2, 0, 11, 0, 7, 0, 12, 1, 7],
                                 np.int32)))
        reply.push(Blob(np.frombuffer(b"ValueError: boom", np.uint8)
                        .copy()))
        worker._process_reply_batch_add(reply)
        assert ("notify", 11) in table.events
        assert ("notify", 12) in table.events
        # The per-sub version stamp reaches the table's tracker (the
        # client cache's read-your-writes resolution depends on it).
        assert ("version", 0, 7) in table.events
        fails = [e for e in table.events if e[0] == "fail"]
        assert len(fails) == 1 and fails[0][1] == 12
        assert "boom" in fails[0][2]

    def test_byte_cap_flushes_exactly_at_limit(self):
        # Staged bytes crossing the -coalesce_max_kb cap must flush
        # mid-burst, exactly when the cap is reached — not one message
        # later.
        import numpy as np

        from multiverso_tpu.core.blob import Blob
        from multiverso_tpu.core.message import Message, MsgType
        worker, zoo, table, add, _, _ = self._worker()
        chunk = worker._max_batch_bytes // 4  # 4 shards hit the cap
        def big_add(msg_id):
            msg = Message(src=1, dst=-1, msg_type=MsgType.Request_Add,
                          table_id=0, msg_id=msg_id)
            msg.push(Blob(np.ones(chunk // 4, np.float32)))
            return msg
        for i in range(3):
            worker._process_add(big_add(i))
        assert not [m for _, m in zoo.sent
                    if m.type == MsgType.Request_BatchAdd]
        assert worker._pending_bytes[0] == 3 * chunk  # under the cap
        worker._process_add(big_add(3))  # reaches the cap exactly
        batches = [m for _, m in zoo.sent
                   if m.type == MsgType.Request_BatchAdd]
        assert len(batches) == 1
        assert not worker._pending and not worker._pending_bytes
        from multiverso_tpu.core.message import unpack_add_batch
        assert [s.msg_id for s in unpack_add_batch(batches[0])] \
            == [0, 1, 2, 3]

    def test_count_cap_flushes_exactly_at_limit(self):
        # The 64th staged shard (not the 65th) must trigger the flush.
        from multiverso_tpu.core.message import unpack_add_batch
        worker, zoo, table, add, Message, MsgType = self._worker()
        cap = worker._max_batch_msgs
        for i in range(cap - 1):
            worker._process_add(add(i))
        assert not [m for _, m in zoo.sent
                    if m.type == MsgType.Request_BatchAdd]
        assert len(worker._pending[0]) == cap - 1
        worker._process_add(add(cap - 1))
        batches = [m for _, m in zoo.sent
                   if m.type == MsgType.Request_BatchAdd]
        assert len(batches) == 1
        assert len(unpack_add_batch(batches[0])) == cap
        assert not worker._pending

    def test_staged_batch_survives_abort_and_drain_exit(self):
        # A staged batch interleaved with an abort must still hit the
        # wire on drain-exit: no stranded waiters (every sub keeps its
        # reset bookkeeping), no lost adds (the flush happens even
        # though the tables were just aborted).
        from multiverso_tpu.core.message import unpack_add_batch
        worker, zoo, table, add, Message, MsgType = self._worker()
        worker._process_add(add(1))
        worker._process_add(add(2))
        assert len(worker._pending[0]) == 2  # staged, not on the wire
        worker.abort_tables("peer died mid-burst")
        assert ("abort", "peer died mid-burst") in table.events
        # Drain-exit: mailbox closes, _main's exit path must flush.
        worker.mailbox.exit()
        worker._main()
        batches = [m for _, m in zoo.sent
                   if m.type == MsgType.Request_BatchAdd]
        assert len(batches) == 1
        assert [s.msg_id for s in unpack_add_batch(batches[0])] == [1, 2]
        assert not worker._pending


class TestServerLockScoping:
    def test_two_servers_progress_concurrently_on_host_paths(self,
                                                             monkeypatch):
        # Regression (two servers in one process slower than one):
        # the process-wide table lock exists for multi-device jitted
        # dispatch; two LocalFabric servers doing HOST-side control
        # work (KV tables) must not serialize on it. Each server's
        # process_get waits for the OTHER server to enter its own
        # process_get: if the old global lock still covered KV logic,
        # one server would hold it while waiting and the other could
        # never enter — the waits time out and the flags read False.
        import multiverso_tpu as mv
        from multiverso_tpu.runtime.cluster import LocalCluster
        from multiverso_tpu.tables.kv_table import KVServer

        entered = [threading.Event(), threading.Event()]
        overlapped = [False, False]
        orig = KVServer.process_get

        def coordinated(self, blobs):
            sid = self._zoo.server_id
            entered[sid].set()
            overlapped[sid] = entered[1 - sid].wait(timeout=15)
            return orig(self, blobs)

        monkeypatch.setattr(KVServer, "process_get", coordinated)

        def body(rank):
            table = mv.create_kv_table()
            if rank == 0:
                # keys 0 and 1 hash to servers 0 and 1: one request,
                # one concurrently-processed shard per server.
                table.get([0, 1])
            mv.current_zoo().barrier()
            return True

        assert LocalCluster(2).run(body) == [True, True]
        assert overlapped == [True, True], overlapped

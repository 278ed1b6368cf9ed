"""Server-side request fusion tests (ISSUE 19, docs/SERVER_ENGINE.md).

Three layers:

* unit tests for ``MtQueue.pop_batch`` (bounded atomic drain: item/byte
  caps, the one-message fallback, watermark and depth-sampling
  interaction) and the pure planner in ``runtime/fusion.py``
  (classification, barriers, per-table op exclusivity, BatchAdd
  all-or-nothing);
* server-level dispatch tests driving ``Server._dispatch_fused``
  directly against stub tables: fused group shapes, arrival-order reply
  emission around barriers (including a shard-migration message
  mid-batch), post-batch version stamping (monotone + RYW-safe),
  per-entry error isolation, the ``PartialFuseError`` replay-the-tail
  accounting, and the SyncServer force-disable;
* integration: the same workload against fusion-off (``-server_fuse_max
  =1``) and fusion-on clusters must produce bit-identical Gets and
  exact sums across Matrix (dense + sparse), Array and KV tables —
  integer-valued float32 deltas keep every fold order exact — plus a
  chaos smoke (reordered/delayed data frames) with zero wrong reads.
"""

import threading

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import (Message, MsgType,
                                         pack_add_batch, reply_version,
                                         take_error)
from multiverso_tpu.runtime import actor as actors
from multiverso_tpu.runtime import fusion
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.runtime.server import Server, SyncServer
from multiverso_tpu.tables.table_interface import ServerTable
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.mt_queue import MtQueue


# ---------------------------------------------------------------------------
# unit: MtQueue.pop_batch
# ---------------------------------------------------------------------------

class TestPopBatch:
    def test_drains_in_order_up_to_item_cap(self):
        q = MtQueue()
        for i in range(10):
            q.push(i)
        assert q.pop_batch(4) == [0, 1, 2, 3]
        assert q.pop_batch(100) == [4, 5, 6, 7, 8, 9]

    def test_byte_budget_bounds_the_tail(self):
        q = MtQueue()
        for v in (100, 1, 1, 50):
            q.push(v)
        # 100 pops unconditionally, then 1 + 1 fit the remaining
        # budget; 50 does not and stays queued.
        assert q.pop_batch(10, max_bytes=102,
                           size_of=lambda v: v) == [100, 1, 1]
        assert q.pop_batch(10, max_bytes=102, size_of=lambda v: v) == [50]

    def test_oversized_first_item_pops_alone(self):
        q = MtQueue()
        q.push(500)
        q.push(1)
        # The one-message fallback: a request larger than the whole
        # byte cap still pops (alone), or the mailbox would wedge.
        assert q.pop_batch(10, max_bytes=10, size_of=lambda v: v) == [500]
        assert q.pop_batch(10, max_bytes=10, size_of=lambda v: v) == [1]

    def test_timeout_on_empty_returns_empty(self):
        q = MtQueue()
        assert q.pop_batch(4, timeout=0.01) == []

    def test_exit_drains_remainder_then_returns_empty(self):
        q = MtQueue()
        q.push("a")
        q.push("b")
        q.exit()
        assert q.pop_batch(8) == ["a", "b"]
        assert q.pop_batch(8) == []

    def test_blocked_pop_batch_wakes_on_push(self):
        q = MtQueue()
        got = []

        def consume():
            got.extend(q.pop_batch(4, timeout=5.0))

        t = threading.Thread(target=consume)
        t.start()
        q.push(7)
        t.join(timeout=5.0)
        assert not t.is_alive() and got == [7]

    def test_watermark_survives_a_batch_drain(self):
        # The depth high watermark is a push-side observable
        # (docs/OBSERVABILITY.md MAILBOX_DEPTH): draining five at once
        # must read exactly like five serial pops did.
        q = MtQueue()
        for i in range(5):
            q.push(i)
        assert q.depth_high_watermark == 5
        q.pop_batch(5)
        assert q.depth_high_watermark == 5
        q.reset_depth_watermark()
        assert q.depth_high_watermark == 0

    def test_depth_sampling_stays_push_side(self):
        # track_depth appends one reservoir sample per PUSH; a batched
        # drain must not add pop-side samples (the reservoir would
        # double-count under fusion).
        from multiverso_tpu.util.dashboard import samples
        name = "MAILBOX_DEPTH[fusion-test]"
        q = MtQueue()
        q.track_depth(name)
        before = samples(name).snapshot()["count"]
        for i in range(6):
            q.push(i)
        q.pop_batch(6)
        assert samples(name).snapshot()["count"] - before == 6

    # -- racing interleavings (real threads; the systematic-schedule
    #    twin of each lives in tools/mvchk specs) ---------------------

    def test_producer_races_greedy_drain_at_byte_cap(self):
        # A producer streams sized items while the consumer drains in
        # byte-capped batches. Whatever the interleaving: nothing is
        # lost or duplicated, concatenated batches are the push order
        # (single producer => global FIFO), and every batch TAIL
        # respects the cap (the first item is the one-message
        # fallback and may exceed it).
        sizes = [7, 120, 3, 40, 40, 40, 9, 200, 1, 1, 1, 55] * 25
        cap = 100
        q = MtQueue()
        batches = []

        def consume():
            taken = 0
            while taken < len(sizes):
                batch = q.pop_batch(8, max_bytes=cap,
                                    size_of=lambda v: v, timeout=5.0)
                assert batch, "drain starved with items outstanding"
                batches.append(batch)
                taken += len(batch)

        t = threading.Thread(target=consume)
        t.start()
        for v in sizes:
            q.push(v)
        t.join(timeout=30.0)
        assert not t.is_alive()
        flat = [v for b in batches for v in b]
        assert flat == sizes
        for batch in batches:
            assert sum(batch[1:]) <= cap - batch[0] or len(batch) == 1

    def test_exit_races_block_for_first(self):
        # stop() (queue exit) racing the block-for-first wait: the
        # parked pop_batch must always wake and return [] — a lost
        # exit wakeup here is exactly the mvchk `mtqueue-exit-wakes`
        # deadlock, reproduced on real threads across many races.
        for _ in range(50):
            q = MtQueue()
            got = []
            started = threading.Event()

            def consume():
                started.set()
                got.append(q.pop_batch(4, timeout=5.0))

            t = threading.Thread(target=consume)
            t.start()
            started.wait(timeout=5.0)
            q.exit()
            t.join(timeout=5.0)
            assert not t.is_alive(), "pop_batch missed the exit wakeup"
            assert got == [[]]

    def test_exit_races_drain_never_hides_items(self):
        # Exit-drain ordering under a live race: everything pushed
        # BEFORE exit() must come out of post-exit drains, in order,
        # before the terminal [] — exit is a close, not a discard.
        for _ in range(25):
            q = MtQueue()
            items = list(range(40))
            recovered = []

            def consume():
                while True:
                    batch = q.pop_batch(7)
                    if not batch:
                        return
                    recovered.extend(batch)

            t = threading.Thread(target=consume)
            t.start()
            for v in items:
                q.push(v)
            q.exit()
            t.join(timeout=10.0)
            assert not t.is_alive()
            # The consumer may legitimately observe [] the instant
            # exit lands only AFTER the buffer is empty.
            assert recovered == items


# ---------------------------------------------------------------------------
# server-level: stub zoo/tables driving the real dispatch machinery
# ---------------------------------------------------------------------------

class _StubZoo:
    """The minimum surface Server/ServerTable construction touches."""

    def __init__(self, num_workers: int = 2):
        self.rank = 0
        self.num_servers = 1
        self.num_workers = num_workers
        self.sent = []  # (actor name, message), in send order
        self._actors = {}
        self._server = None

    def register_actor(self, actor):
        self._actors[actor.name] = actor

    def deregister_actor(self, actor):
        self._actors.pop(actor.name, None)

    def send_to(self, name, msg):
        self.sent.append((name, msg))

    def register_server_table(self, table) -> int:
        return self._server.register_table(table)


class _StubTable(ServerTable):
    """Host-only table recording every dispatch shape it sees."""

    needs_device_lock = False

    def __init__(self, zoo, eligible: bool = True):
        super().__init__(zoo=zoo)
        self.eligible = eligible
        self.calls = []  # ("get"|"add"|"fused_get"|"fused_add"|"pump", n)
        self.fail_on = None  # value whose serial add raises

    def fuse_eligible(self, blobs, is_get) -> bool:
        return self.eligible

    def process_get(self, blobs):
        self.calls.append(("get", 1))
        return [blobs[0], Blob(np.array([41.0], np.float32))]

    def process_add(self, blobs):
        self.calls.append(("add", 1))
        v = int(blobs[0].as_array(np.int32)[0])
        if self.fail_on is not None and v == self.fail_on:
            raise ValueError(f"poisoned add {v}")

    def process_fused_get(self, requests):
        self.calls.append(("fused_get", len(requests)))
        return [[blobs[0], Blob(np.array([41.0], np.float32))]
                for blobs in requests]

    def process_fused_add(self, requests):
        self.calls.append(("fused_add", len(requests)))
        for i, blobs in enumerate(requests):
            v = int(blobs[0].as_array(np.int32)[0])
            if self.fail_on is not None and v == self.fail_on:
                raise fusion.PartialFuseError(i, ValueError(
                    f"poisoned add {v}"))

    def shard_pump(self):
        self.calls.append(("pump", 0))
        return [], False


def _server_env():
    zoo = _StubZoo()
    server = Server(zoo)
    zoo._server = server
    return zoo, server


def _get(table_id: int, msg_id: int, key: int = 3) -> Message:
    msg = Message(src=1, dst=0, msg_type=MsgType.Request_Get,
                  table_id=table_id, msg_id=msg_id)
    msg.push(Blob(np.array([key], np.int32)))
    return msg


def _add(table_id: int, msg_id: int, key: int = 3) -> Message:
    msg = Message(src=1, dst=0, msg_type=MsgType.Request_Add,
                  table_id=table_id, msg_id=msg_id)
    msg.push(Blob(np.array([key], np.int32)))
    msg.push(Blob(np.array([1.0], np.float32)))
    return msg


def _replies(zoo):
    return [m for name, m in zoo.sent if name == actors.COMMUNICATOR]


class TestPlanner:
    def test_same_table_gets_form_one_group(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        batch = [_get(t.table_id, i) for i in range(3)]
        infos = [fusion.classify(server, i, m)
                 for i, m in enumerate(batch)]
        plan = fusion.split_plan(batch, infos)
        assert len(plan) == 1 and plan[0][0] == "fused"
        (table, is_get, entries), = plan[0][1]
        assert table is t and is_get and len(entries) == 3

    def test_control_and_shard_messages_are_barriers(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        for barrier_type in (MsgType.Server_Shard_Pump,
                             MsgType.Request_ShardData,
                             MsgType.Request_ShardAck,
                             MsgType.Request_FwdGet,
                             MsgType.Request_ReplicaSync):
            msg = Message(src=1, dst=0, msg_type=barrier_type,
                          table_id=t.table_id, msg_id=99)
            assert fusion.classify(server, 0, msg) is None

    def test_empty_payload_get_is_a_barrier(self):
        # Sync-mode clock-tick shards carry no blobs; the serial
        # handler owns their empty-reply protocol.
        zoo, server = _server_env()
        t = _StubTable(zoo)
        msg = Message(src=1, dst=0, msg_type=MsgType.Request_Get,
                      table_id=t.table_id, msg_id=5)
        assert fusion.classify(server, 0, msg) is None

    def test_unknown_table_is_a_barrier(self):
        zoo, server = _server_env()
        assert fusion.classify(server, 0, _get(7, 1)) is None

    def test_ineligible_request_is_a_barrier(self):
        zoo, server = _server_env()
        t = _StubTable(zoo, eligible=False)
        assert fusion.classify(server, 0, _get(t.table_id, 1)) is None

    def test_raising_eligibility_probe_is_a_barrier(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        t.fuse_eligible = None  # not callable: the probe raises
        assert fusion.classify(server, 0, _get(t.table_id, 1)) is None

    def test_barrier_splits_the_window(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        batch = [_get(t.table_id, 1), _get(t.table_id, 2),
                 Message(src=1, dst=0,
                         msg_type=MsgType.Server_Shard_Pump,
                         table_id=t.table_id, msg_id=0),
                 _get(t.table_id, 3)]
        infos = [fusion.classify(server, i, m)
                 for i, m in enumerate(batch)]
        plan = fusion.split_plan(batch, infos)
        assert [step[0] for step in plan] == ["fused", "serial", "fused"]
        assert len(plan[0][1][0][2]) == 2  # first window: two gets
        assert plan[1][1] == 2             # the barrier's batch index
        assert len(plan[2][1][0][2]) == 1

    def test_opposite_op_flushes_the_window(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        batch = [_add(t.table_id, 1), _add(t.table_id, 2),
                 _get(t.table_id, 3), _get(t.table_id, 4)]
        infos = [fusion.classify(server, i, m)
                 for i, m in enumerate(batch)]
        plan = fusion.split_plan(batch, infos)
        assert [step[0] for step in plan] == ["fused", "fused"]
        assert plan[0][1][0][1] is False and len(plan[0][1][0][2]) == 2
        assert plan[1][1][0][1] is True and len(plan[1][1][0][2]) == 2

    def test_two_tables_share_a_window(self):
        zoo, server = _server_env()
        a, b = _StubTable(zoo), _StubTable(zoo)
        batch = [_get(a.table_id, 1), _add(b.table_id, 2),
                 _get(a.table_id, 3)]
        infos = [fusion.classify(server, i, m)
                 for i, m in enumerate(batch)]
        plan = fusion.split_plan(batch, infos)
        # No per-table conflict: one window, two groups, arrival order.
        assert [step[0] for step in plan] == ["fused"]
        groups = plan[0][1]
        assert [(g[0], g[1], len(g[2])) for g in groups] == \
            [(a, True, 2), (b, False, 1)]

    def test_batch_add_is_all_or_nothing(self):
        zoo, server = _server_env()
        good, bad = _StubTable(zoo), _StubTable(zoo, eligible=False)
        subs = [_add(good.table_id, 10), _add(bad.table_id, 11)]
        batch_msg = pack_add_batch(subs)
        assert fusion.classify(server, 0, batch_msg) is None
        all_good = pack_add_batch(
            [_add(good.table_id, 10), _add(good.table_id, 11)])
        entries = fusion.classify(server, 0, all_good)
        assert entries is not None and len(entries) == 2
        assert [e.msg_id for e in entries] == [10, 11]


class TestFusedDispatch:
    def test_fused_execution_and_reply_order_around_a_barrier(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        barrier = _get(t.table_id, 3)  # serial via ineligibility below
        batch = [_get(t.table_id, 1), _get(t.table_id, 2), barrier,
                 _get(t.table_id, 4)]
        orig = t.fuse_eligible
        t.fuse_eligible = \
            lambda blobs, is_get: int(blobs[0].as_array(np.int32)[0]) != 9
        batch[2].data = [Blob(np.array([9], np.int32))]
        server._dispatch_fused(batch)
        t.fuse_eligible = orig
        # One fused program per multi-entry window; the barrier ran
        # serially between them, and the trailing singleton window
        # took the exact serial path (nothing to amortize the fused
        # machinery over — Server._run_fused_group).
        assert t.calls == [("fused_get", 2), ("get", 1), ("get", 1)]
        # Global reply order is arrival order: the deferred fused
        # replies for msgs 1-2 leave BEFORE the barrier's serial reply.
        assert [m.msg_id for m in _replies(zoo)] == [1, 2, 3, 4]
        assert all(take_error(m) is None for m in _replies(zoo))

    def test_shard_pump_mid_batch_executes_between_windows(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        pump = Message(src=0, dst=0,
                       msg_type=MsgType.Server_Shard_Pump,
                       table_id=t.table_id, msg_id=0)
        server._dispatch_fused(
            [_get(t.table_id, 1), pump, _get(t.table_id, 2)])
        # Both windows are singletons (serial path); the pump ran as a
        # barrier between them.
        assert t.calls == [("get", 1), ("pump", 0), ("get", 1)]
        assert [m.msg_id for m in _replies(zoo)] == [1, 2]

    def test_versions_are_monotone_and_post_batch(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        batch = [_add(t.table_id, 1), _add(t.table_id, 2),
                 _get(t.table_id, 3), _add(t.table_id, 4),
                 _get(t.table_id, 5)]
        server._dispatch_fused(batch)
        versions = [reply_version(m) for m in _replies(zoo)]
        # Fused adds stamp the POST-batch version (conservatively late
        # = RYW-safe, docs/SERVER_ENGINE.md): both window-1 adds carry
        # 2; the get between the windows observes exactly those adds.
        assert versions == [2, 2, 2, 3, 3]
        assert versions == sorted(versions)
        assert t.version == 3

    def test_fused_batch_add_reassembles_one_ack(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        parent = pack_add_batch(
            [_add(t.table_id, 20), _add(t.table_id, 21)])
        server._dispatch_fused([parent, _add(t.table_id, 22)])
        assert t.calls == [("fused_add", 3)]
        replies = _replies(zoo)
        assert [m.type for m in replies] == [MsgType.Reply_BatchAdd,
                                             MsgType.Reply_Add]
        desc = replies[0].data[0].as_array(np.int32)
        # [n, (table_id, msg_id, err, version)...] — post-batch
        # version 3 on every sub (core/message.py batch layout).
        assert desc[0] == 2
        assert list(desc[1:9]) == [t.table_id, 20, 0, 3,
                                   t.table_id, 21, 0, 3]
        assert reply_version(replies[1]) == 3

    def test_entry_failure_is_isolated_and_tail_replays(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        t.fail_on = 13
        batch = [_add(t.table_id, 1, key=7), _add(t.table_id, 2, key=13),
                 _add(t.table_id, 3, key=8)]
        server._dispatch_fused(batch)
        # The fused apply stopped at the poisoned entry
        # (PartialFuseError applied=1); the tail replayed serially and
        # the poisoned entry alone failed again there.
        assert t.calls == [("fused_add", 3), ("add", 1), ("add", 1)]
        replies = _replies(zoo)
        assert take_error(replies[0]) is None
        assert "poisoned add 13" in take_error(replies[1])
        assert take_error(replies[2]) is None
        # Version accounting: fused prefix (1) + one serial replay
        # bump; the failed entry bumps nothing.
        assert t.version == 2
        assert reply_version(replies[0]) == 1
        assert reply_version(replies[2]) == 2

    def test_plain_fused_failure_replays_everything(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)

        def explode(requests):
            t.calls.append(("fused_add", len(requests)))
            raise RuntimeError("no prefix applied")

        t.process_fused_add = explode
        server._dispatch_fused(
            [_add(t.table_id, 1), _add(t.table_id, 2)])
        assert t.calls == [("fused_add", 2), ("add", 1), ("add", 1)]
        assert [reply_version(m) for m in _replies(zoo)] == [1, 2]
        assert t.version == 2

    def test_single_message_batches_skip_the_fuse_metric(self):
        from multiverso_tpu.util.dashboard import samples
        zoo, server = _server_env()
        t = _StubTable(zoo)
        before = samples("SERVER_FUSE_BATCH").snapshot()["count"]
        server.receive(_get(t.table_id, 1))
        server.mailbox.exit()
        server._main()
        assert [m.msg_id for m in _replies(zoo)] == [1]
        assert samples("SERVER_FUSE_BATCH").snapshot()["count"] == before

    def test_main_loop_drains_and_fuses(self):
        zoo, server = _server_env()
        t = _StubTable(zoo)
        for i in range(1, 5):
            server.receive(_get(t.table_id, i))
        server.mailbox.exit()
        server._main()
        assert t.calls == [("fused_get", 4)]
        assert [m.msg_id for m in _replies(zoo)] == [1, 2, 3, 4]


    def test_fused_window_is_one_device_dispatch(self):
        # The dispatch cut, counted: four Gets queued on a device-backed
        # table are ONE program where the serial server runs four.
        from multiverso_tpu.util.dashboard import Dashboard

        class DeviceStub(_StubTable):
            needs_device_lock = True

        def dispatches(fuse_max):
            set_flag("server_fuse_max", fuse_max)
            zoo, server = _server_env()
            t = DeviceStub(zoo)
            before = Dashboard.get("SERVER_DEVICE_DISPATCHES").count
            for i in range(1, 5):
                server.receive(_get(t.table_id, i))
            server.mailbox.exit()
            server._main()
            assert [m.msg_id for m in _replies(zoo)] == [1, 2, 3, 4]
            return Dashboard.get("SERVER_DEVICE_DISPATCHES").count - before

        assert dispatches(1) == 4
        assert dispatches(16) == 1


def test_fused_get_dedups_shared_rows_bit_identically():
    # The real table's fused gather: rows asked for by more than one
    # request are gathered once (SERVER_FUSE_DEDUP_ROWS counts them) and
    # every request still gets the bits its own serial Get returns.
    from multiverso_tpu.util.dashboard import Dashboard
    mv.init([])
    try:
        table = mv.create_matrix_table(64, 8, np.float32)
        rows = np.arange(64, dtype=np.int32)
        table.add_rows(rows, np.arange(64 * 8, dtype=np.float32)
                       .reshape(64, 8))
        server_table = mv.current_zoo()._server_tables[0]
        asks = [np.array([3, 9, 9, 40], np.int32),
                np.array([9, 3, 63], np.int32),
                np.array([0, 40], np.int32)]

        def request(ids):
            return [Blob(ids.copy())]

        for ids in asks:
            assert server_table.fuse_eligible(request(ids), True)
        serial = [server_table.process_get(request(ids)) for ids in asks]
        before = Dashboard.get("SERVER_FUSE_DEDUP_ROWS").count
        fused = server_table.process_fused_get(
            [request(ids) for ids in asks])
        # 9 positions asked for, 5 distinct rows
        assert Dashboard.get("SERVER_FUSE_DEDUP_ROWS").count - before == 4
        for one, many in zip(serial, fused):
            np.testing.assert_array_equal(
                np.asarray(many[1].as_array(np.float32)),
                np.asarray(one[1].as_array(np.float32)))
    finally:
        mv.shutdown()


class TestSyncForceDisable:
    def test_sync_server_pins_fuse_max_to_one(self):
        set_flag("server_fuse_max", 16)
        set_flag("sync", True)
        try:
            zoo = _StubZoo()
            server = SyncServer(zoo)
            assert server._fuse_max == 1
            assert isinstance(Server.get_server(zoo), SyncServer)
        finally:
            set_flag("sync", False)

    def test_async_server_honors_the_flag(self):
        set_flag("server_fuse_max", 5)
        zoo = _StubZoo()
        assert Server(zoo)._fuse_max == 5


# ---------------------------------------------------------------------------
# integration: fused == serial across the table types
# ---------------------------------------------------------------------------

_N_ADDS = 24  # async adds per worker — enough mailbox pressure to fuse


def _run_cluster(body, fuse_max, extra_argv=()):
    argv = [f"-server_fuse_max={fuse_max}", *extra_argv]
    cluster = LocalCluster(2, argv=argv, roles=["all", "worker"])
    cluster.timeout = 180.0
    return cluster.run(body)


def _matrix_body(is_sparse):
    def body(rank):
        rng = np.random.default_rng(17 + rank)
        table = mv.create_matrix_table(48, 4, np.float32,
                                       is_sparse=is_sparse)
        ids = [rng.integers(0, 48, size=6).astype(np.int32)
               for _ in range(_N_ADDS)]
        # Integer-valued deltas: float32 sums are exact, so any fold
        # order must produce identical bits.
        deltas = [rng.integers(1, 4, size=(6, 4)).astype(np.float32)
                  for _ in range(_N_ADDS)]
        pend = [table.add_rows_async(i, d) for i, d in zip(ids, deltas)]
        for msg_id in pend:
            table.wait(msg_id)
        mv.current_zoo().barrier()
        # Full get FIRST: a sparse whole-table get serves only rows
        # still dirty for this worker, and a row get marks its rows
        # up-to-date (matrix_table.py _up_to_date).
        full = np.array(table.get(), copy=True)
        # Duplicate ids in one request: per-position placement.
        probe = np.array([5, 5, 0, 47, 11], np.int32)
        rows = np.array(table.get_rows(probe), copy=True)
        mv.current_zoo().barrier()
        return full, rows, ids, deltas

    return body


@pytest.mark.parametrize("is_sparse", [False, True],
                         ids=["dense", "sparse"])
def test_matrix_fused_matches_serial_and_exact_sum(is_sparse):
    serial = _run_cluster(_matrix_body(is_sparse), fuse_max=1)
    fused = _run_cluster(_matrix_body(is_sparse), fuse_max=16)
    expected = np.zeros((48, 4), np.float32)
    for _, _, ids, deltas in serial:
        for i, d in zip(ids, deltas):
            np.add.at(expected, i, d)
    for results in (serial, fused):
        for full, rows, _, _ in results:
            np.testing.assert_array_equal(full, expected)
            probe = np.array([5, 5, 0, 47, 11], np.int32)
            np.testing.assert_array_equal(rows, expected[probe])


def test_array_fused_matches_serial_and_exact_sum():
    def body(rank):
        rng = np.random.default_rng(5 + rank)
        table = mv.create_array_table(32, np.float32)
        deltas = [rng.integers(1, 4, size=32).astype(np.float32)
                  for _ in range(_N_ADDS)]
        pend = [table.add_async(d) for d in deltas]
        for msg_id in pend:
            table.wait(msg_id)
        mv.current_zoo().barrier()
        out = np.array(table.get(), copy=True)
        mv.current_zoo().barrier()
        return out, deltas

    serial = _run_cluster(body, fuse_max=1)
    fused = _run_cluster(body, fuse_max=16)
    expected = np.zeros(32, np.float32)
    for _, deltas in serial:
        expected += np.sum(deltas, axis=0)
    for results in (serial, fused):
        for out, _ in results:
            np.testing.assert_array_equal(out, expected)


def test_kv_fused_matches_serial_and_exact_sum():
    def body(rank):
        rng = np.random.default_rng(29 + rank)
        table = mv.create_kv_table()
        keys = [rng.integers(0, 40, size=5).astype(np.int64)
                for _ in range(_N_ADDS)]
        vals = [rng.integers(1, 6, size=5).astype(np.float32)
                for _ in range(_N_ADDS)]
        pend = [table.add_async(k, v) for k, v in zip(keys, vals)]
        for msg_id in pend:
            table.wait(msg_id)
        mv.current_zoo().barrier()
        got = table.get(np.arange(40, dtype=np.int64))
        mv.current_zoo().barrier()
        return got, keys, vals

    serial = _run_cluster(body, fuse_max=1)
    fused = _run_cluster(body, fuse_max=16)
    expected = {k: 0.0 for k in range(40)}
    for _, keys, vals in serial:
        for ks, vs in zip(keys, vals):
            for k, v in zip(ks, vs):
                expected[int(k)] += float(v)
    for results in (serial, fused):
        for got, _, _ in results:
            assert {k: float(v) for k, v in got.items()} == expected


def test_read_your_writes_under_fused_interleaving():
    # Each worker alternates waited Adds with Gets of its own rows: a
    # Get issued after an acked Add must observe AT LEAST that add
    # (fused replies stamp the post-batch version — conservatively
    # late, never early).
    def body(rank):
        table = mv.create_matrix_table(16, 2, np.float32)
        my_row = np.array([rank * 3], np.int32)
        floors = []
        for step in range(1, 9):
            table.add_rows(my_row, np.full((1, 2), 1.0, np.float32))
            rows = table.get_rows(my_row)
            # Own-row sum grows by exactly 1 per waited add; observing
            # less would be a read BEFORE our acked write.
            floors.append(float(rows[0, 0]) >= step)
        mv.current_zoo().barrier()
        return floors

    for floors in _run_cluster(body, fuse_max=16):
        assert all(floors)


def test_chaos_smoke_no_wrong_reads():
    # Reorder + delay data frames while fused traffic flows: every
    # read must still come back exact (fusion is a scheduling change;
    # arrival-order permutations are its everyday input).
    from multiverso_tpu.util import chaos

    def body(rank):
        table = mv.create_matrix_table(24, 2, np.float32)
        ids = np.arange(24, dtype=np.int32)
        pend = [table.add_rows_async(
            ids, np.full((24, 2), 1.0, np.float32))
            for _ in range(_N_ADDS)]
        for msg_id in pend:
            table.wait(msg_id)
        mv.current_zoo().barrier()
        out = np.array(table.get(), copy=True)
        mv.current_zoo().barrier()
        return out

    try:
        results = _run_cluster(
            body, fuse_max=16,
            extra_argv=["-chaos_frames=reorder=0.3,delay_ms=2,"
                        "classes=data,seed=11"])
    finally:
        set_flag("chaos_frames", "")
        chaos._frames_spec = None
    expected = np.full((24, 2), 2.0 * _N_ADDS, np.float32)
    for out in results:
        np.testing.assert_array_equal(out, expected)

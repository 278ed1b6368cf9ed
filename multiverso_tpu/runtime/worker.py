"""Worker actor: routes table requests to server shards.

TPU-native equivalent of the reference's ``Worker``
(ref: include/multiverso/worker.h:12-25, src/worker.cpp:12-89). On Get/Add
it asks the table to ``partition`` the request into per-server-shard blob
lists, re-arms the table's waiter to the shard count, and sends one message
per shard through the communicator; on replies it hands the payload back to
the table and counts down the waiter.

Extension over the reference: SHARD-MESSAGE COALESCING. Over a real wire
every message pays a per-message round trip (not measured on the current
machine), so Add shards bound for the same server are staged and
flushed as ONE ``Request_BatchAdd`` wire message. The window is the actor
mailbox itself: while more requests are queued the batch grows (bounded by
count/byte caps); the moment the mailbox drains — i.e. the trainer thread
is about to wait on a reply — everything pending flushes. Gets flush first
(per-connection FIFO keeps add-before-get ordering only if the adds are
actually on the wire), and BSP sync mode disables coalescing outright (the
sync server's vector clocks count one request per worker per step).
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from ..core.message import (PEER_LOST_MARK, Message, MsgType,
                            pack_add_batch, replica_row_count,
                            reply_version, take_error)
from ..util import mt_queue
from ..util.configure import (define_bool, define_double, define_int,
                              get_flag, register_tunable_hook)
from ..util.dashboard import count as count_event
from ..util.dashboard import monitor
from . import actor as actors
from . import device_lock
from . import replica as replica_mod
from .actor import Actor
from .server import Server

define_bool("coalesce_adds", True,
            "batch pending Add shards to the same server into one wire "
            "message (async mode over a wire transport only)")
define_double("rpc_timeout_s", 0.0,
              "diagnostic timeout on table request waiters: a Get/Add "
              "whose replies do not all arrive within this many seconds "
              "raises RpcTimeoutError naming the table, msg_id and the "
              "peer ranks still pending — instead of blocking forever "
              "on a reply that a silently-failed peer will never send. "
              "0 (default) = wait without bound (the reference's "
              "behavior)")

define_int("coalesce_max_msgs", 64,
           "flush a server's staged coalesced-Add batch at this many "
           "messages even while the mailbox is still busy — an "
           "unbounded batch would trade latency for no extra win. "
           "Live-retunable (docs/AUTOTUNE.md): the autotune "
           "controller backs this off when outbound send queues sit "
           "deep")
define_int("coalesce_max_kb", 4096,
           "flush a server's staged coalesced-Add batch at this many "
           "KILOBYTES of payload (the byte twin of "
           "-coalesce_max_msgs). Live-retunable (docs/AUTOTUNE.md)")


class Worker(Actor):
    def __init__(self, zoo) -> None:
        super().__init__(actors.WORKER, zoo)
        # Depth samples feed the serving tier's pressure surface
        # (docs/SERVING.md); gated so a
        # training-only run pays nothing per push.
        if mt_queue.depth_sampling_enabled():
            self.mailbox.track_depth("MAILBOX_DEPTH[worker]")
        self._cache: List = []  # registered WorkerTables, indexed by table id
        self.register_handler(MsgType.Request_Get, self._process_get)
        self.register_handler(MsgType.Request_Add, self._process_add)
        self.register_handler(MsgType.Reply_Get, self._process_reply_get)
        self.register_handler(MsgType.Reply_Add, self._process_reply_add)
        self.register_handler(MsgType.Reply_BatchAdd,
                              self._process_reply_batch_add)
        # Coalescing only pays where messages pay: a wire transport in
        # async mode. In-process fabrics move object references (zero
        # per-message wire cost) and the BSP sync server counts one
        # request per worker per step on its vector clocks.
        self._coalesce = (bool(get_flag("coalesce_adds"))
                          and not self._zoo.net.in_process
                          and not get_flag("sync", False))
        self._pending: Dict[int, List[Message]] = {}  # dst rank -> shards
        self._pending_bytes: Dict[int, int] = {}
        # Flush caps, cached here off the hot staging path and
        # live-retunable through the dynamic-flag layer
        # (docs/AUTOTUNE.md): plain int rebinds, GIL-atomic against
        # the actor thread's reads.
        self._max_batch_msgs = max(int(get_flag("coalesce_max_msgs")),
                                   1)
        self._max_batch_bytes = \
            max(int(get_flag("coalesce_max_kb")), 1) << 10
        register_tunable_hook("coalesce_max_msgs",
                              self._retune_batch_msgs)
        register_tunable_hook("coalesce_max_kb",
                              self._retune_batch_kb)
        # In-flight shard requests: (dst, table_id, msg_id) tracked when
        # a shard is sent (or staged), untracked when its reply lands.
        # Written only on this actor's thread; read from requester
        # threads for timeout diagnostics (GIL-atomic dict ops; a torn
        # read only costs diagnostic precision). Kept as a MULTISET
        # (key -> outstanding count): a replica REPAIR deliberately
        # reuses the original
        # request's (dst, table, msg_id) toward the rows' owner, and
        # with a plain set the original reply's discard would untrack
        # the still-outstanding repair — the dead-peer sweep could then
        # no longer fail its waiter (a crash mid-repair would hang
        # wait() forever). The count is also what the sweep owes in
        # notifies.
        self._inflight: Dict[tuple, int] = {}
        self.register_handler(MsgType.Control_Dead_Peer,
                              self._process_dead_peer)
        self.register_handler(MsgType.Control_Replica_Map,
                              self._process_replica_map)
        # Elastic resharding (runtime/shard_map.py, docs/SHARDING.md):
        # the epoch-stamped shard-map broadcast re-routes this worker's
        # tables on THIS thread (the same thread that partitions).
        self.register_handler(MsgType.Control_Shard_Map,
                              self._process_shard_map)

    def register_table(self, worker_table) -> int:
        self._cache.append(worker_table)
        return len(self._cache) - 1

    def abort_tables(self, reason: str) -> None:
        for table in self._cache:
            table.abort(reason)

    # -- main loop: drain mailbox, flush staged adds on idle --
    def _main(self) -> None:
        while True:
            msg = self.mailbox.pop()
            if msg is None:
                # Drain-exit: whatever is still staged must hit the wire
                # — a worker stopping with unsent adds would lose them.
                self._flush_pending()
                break
            self._popped(msg)
            self._safe_dispatch(msg)
            if self._pending and self.mailbox.empty():
                # The mailbox just went idle: the requester is (or is
                # about to be) blocked in wait(); holding the batch any
                # longer adds latency without adding batch members.
                self._flush_pending()

    # ref: src/worker.cpp:30-51
    def _process_get(self, msg: Message) -> None:
        with monitor("WORKER_PROCESS_GET", msg_id=msg.msg_id,
                     table=msg.table_id):
            # Per-connection FIFO only orders what is actually ON the
            # wire: staged adds must flush before a Get so the server
            # observes add-before-get program order.
            self._flush_pending()
            self._partition_and_send(msg, MsgType.Request_Get)

    # ref: src/worker.cpp:53-76
    def _process_add(self, msg: Message) -> None:
        with monitor("WORKER_PROCESS_ADD", msg_id=msg.msg_id,
                     table=msg.table_id):
            self._partition_and_send(msg, MsgType.Request_Add)

    def _process_replica_map(self, msg: Message) -> None:
        """Promoted-row map broadcast from the controller: each table's
        router adopts its row set ON THIS THREAD (the same thread that
        partitions), so routing decisions never race the map."""
        try:
            epoch, promoted, alive = replica_mod.unpack_replica_map_alive(
                [b.as_array(np.int32) for b in msg.data])
        except Exception:  # noqa: BLE001 - a malformed map must not
            # kill the worker loop; the next broadcast replaces it.
            from ..util import log
            log.error("worker: undecodable replica map %r", msg)
            return
        for table_id, rows in promoted.items():
            if 0 <= table_id < len(self._cache):
                self._cache[table_id].apply_replica_map(epoch, rows)
        if alive is not None:
            # Reconcile every router's dead marks against the
            # controller's authoritative live-server view: a rejoined
            # server resumes serving replicas without waiting for
            # organic reply traffic (docs/SHARDING.md).
            for table in self._cache:
                table.replica_reconcile(alive)

    def _process_shard_map(self, msg: Message) -> None:
        """Epoch-stamped shard-map broadcast from the controller: the
        named table adopts the new row->server layout, invalidates
        client caches for moved ranges (the PR-6 generation-change
        path) and reconciles its replica router's liveness marks
        against the controller's authoritative view."""
        from . import shard_map as shard_map_mod
        try:
            table_id, smap, alive = shard_map_mod.ShardMap.unpack(
                [b.as_array(np.int64) for b in msg.data])
        except Exception:  # noqa: BLE001 - a malformed broadcast must
            # not kill the worker loop; the next broadcast replaces it.
            from ..util import log
            log.error("worker: undecodable shard map %r", msg)
            return
        if 0 <= table_id < len(self._cache):
            self._cache[table_id].apply_shard_map(smap.epoch, smap,
                                                  alive)

    def _partition_and_send(self, msg: Message, msg_type: MsgType) -> None:
        table = self._cache[msg.table_id]
        # Partition context: tables that record per-shard routing (the
        # replica router's repair bookkeeping) key it by request id.
        table._partition_msg_id = msg.msg_id
        try:
            # Partitions of DEVICE-carrying requests dispatch eager
            # device ops (per-server delta slices). Those must
            # serialize on the same process-wide lock as server table
            # logic: a worker actor's eager dispatch interleaving a
            # sibling zoo's server jit deadlocks XLA's CPU runtime
            # exactly like the server-vs-server case the lock was
            # introduced for (observed: stack parked in partition's
            # device slice while a server holds a jitted gather).
            # Pure-host partitions — the wire hot path — skip the lock
            # entirely, mirroring needs_device_lock on the server side.
            lock = Server._table_lock \
                if any(b.on_device for b in msg.data) else Server._no_lock
            with lock:
                partitions = table.partition(msg.data, msg_type)
                # Multi-zoo mode: per-server device slices must land
                # before the lock releases (device_lock.py) — an
                # in-flight slice escaping here overlaps a sibling
                # rank's server jit and can wedge XLA's CPU pool.
                # (active() gate: don't build the blob list on the
                # production hot path, where it can never matter.)
                if device_lock.active():
                    device_lock.settle([b.data
                                        for blobs in partitions.values()
                                        for b in blobs if b.on_device])
            table._partition_msg_id = -1
        except Exception as exc:
            table._partition_msg_id = -1
            # Record the failure on the request and release the caller's
            # waiter — wait() raises instead of returning 'success' over
            # an untouched destination buffer (the actor loop only logs).
            if get_flag("sync", False):
                # BSP: the sync servers must still observe one request
                # from this worker or its vector clock falls permanently
                # behind and the gate caches every OTHER worker's
                # requests forever. Send an empty shard to every server:
                # it takes the server's tick-only path (benign reply,
                # no table logic) and the sync server's finally-tick
                # keeps the clocks level; the caller still raises from
                # the failure recorded here.
                table.fail(msg.msg_id, f"partition failed: {exc}",
                           count=False)
                table.reset(msg.msg_id, self._zoo.num_servers)
                for server_id in range(self._zoo.num_servers):
                    shard = Message(src=self._zoo.rank,
                                    dst=self._zoo.server_rank(server_id),
                                    msg_type=msg_type,
                                    table_id=msg.table_id,
                                    msg_id=msg.msg_id)
                    self.send_to(actors.COMMUNICATOR, shard)
            else:
                table.fail(msg.msg_id, f"partition failed: {exc}")
            raise
        # BSP full coverage: the sync server counts ONE request per
        # worker per step on its vector clocks, but a hash/range
        # partition may touch only a subset of servers (a kv add to a
        # single key reaches one shard). Every uncovered server gets an
        # EMPTY clock-tick shard — no table logic runs (the server's
        # tick-only path), the benign reply just counts down this
        # waiter — so no server's clock falls permanently behind and
        # gates the other workers' requests forever. The
        # partition-failure path below has always ticked this way; this
        # is its success-path twin.
        num_servers = self._zoo.num_servers
        pad_sync = (get_flag("sync", False)
                    and len(partitions) < num_servers)
        table.reset(msg.msg_id,
                    num_servers if pad_sync else len(partitions))
        targets = range(num_servers) if pad_sync else partitions.keys()
        for server_id in targets:
            dst = self._zoo.server_rank(server_id)
            shard = Message(src=self._zoo.rank, dst=dst,
                            msg_type=msg_type,
                            table_id=msg.table_id, msg_id=msg.msg_id)
            blobs = partitions.get(server_id)
            if blobs is not None:
                shard.data = list(blobs)
            self._track((dst, msg.table_id, msg.msg_id))
            if (self._coalesce and msg_type == MsgType.Request_Add
                    and dst != self._zoo.rank):
                self._stage_add(dst, shard)
            else:
                self.send_to(actors.COMMUNICATOR, shard)

    # -- coalescing --
    def _retune_batch_msgs(self, value) -> None:
        self._max_batch_msgs = max(int(value), 1)

    def _retune_batch_kb(self, value) -> None:
        self._max_batch_bytes = max(int(value), 1) << 10

    def _stage_add(self, dst: int, shard: Message) -> None:
        staged = self._pending.setdefault(dst, [])
        staged.append(shard)
        self._pending_bytes[dst] = self._pending_bytes.get(dst, 0) \
            + sum(b.size for b in shard.data)
        if (len(staged) >= self._max_batch_msgs
                or self._pending_bytes[dst] >= self._max_batch_bytes):
            self._flush_dst(dst)

    def _flush_pending(self) -> None:
        for dst in list(self._pending):
            self._flush_dst(dst)

    def _flush_dst(self, dst: int) -> None:
        staged = self._pending.pop(dst, None)
        self._pending_bytes.pop(dst, None)
        if not staged:
            return
        if len(staged) == 1:
            # A lone shard skips the batch framing (no descriptor
            # overhead, and the server's plain-Add path stays hot).
            self.send_to(actors.COMMUNICATOR, staged[0])
            return
        with monitor("WORKER_COALESCE_FLUSH"):
            batch = pack_add_batch(staged)
            self.send_to(actors.COMMUNICATOR, batch)

    def _reply_server_id(self, msg: Message) -> int:
        """Server id of the shard a reply came from (version stamps are
        per server shard)."""
        return self._zoo.rank_to_server_id(msg.src)

    def _track(self, key: tuple) -> None:
        self._inflight[key] = self._inflight.get(key, 0) + 1

    def _untrack(self, key: tuple) -> None:
        n = self._inflight.get(key, 0)
        if n <= 1:
            self._inflight.pop(key, None)
        else:
            self._inflight[key] = n - 1

    def pending_peers(self, table_id: int, msg_id: int) -> List[int]:
        """Destination ranks a request is still awaiting replies from
        (timeout diagnostics; best-effort read from requester threads)."""
        return sorted(d for d, t, m in list(self._inflight)
                      if t == table_id and m == msg_id)

    def forget_request(self, table_id: int, msg_id: int) -> None:
        """Drop a timed-out (abandoned) request's in-flight entries so
        they don't accumulate or pollute later diagnostics. Called from
        the REQUESTER thread: per-element discard is GIL-atomic, and a
        racing reply on the actor thread discards the same tuples
        harmlessly."""
        for key in [k for k in list(self._inflight)
                    if k[1] == table_id and k[2] == msg_id]:
            self._inflight.pop(key, None)  # abandoned: drop ALL counts

    def _process_dead_peer(self, msg: Message) -> None:
        """A peer rank died (zoo.peer_lost): every in-flight shard
        request toward it will never be answered — fail each one NOW
        with a retryable marker so blocked wait() calls raise
        PeerLostError instead of hanging. Runs on the actor thread, so
        it serializes with sends and replies: no notify can race the
        sweep."""
        dead = int(msg.data[0].as_array(np.int32)[0])
        # Staged (coalesced, not yet sent) shards toward the dead rank
        # would fail at send time anyway; fail them here in one place.
        staged = self._pending.pop(dead, None) or []
        self._pending_bytes.pop(dead, None)
        for shard in staged:
            self._untrack((dead, shard.table_id, shard.msg_id))
            table = self._cache[shard.table_id]
            table.fail(shard.msg_id,
                       f"{PEER_LOST_MARK} rank {dead} died with this Add "
                       f"staged", count=False)
            table.notify(shard.msg_id)
        # list() copy: forget_request on a requester thread may discard
        # concurrently, and bare set iteration would raise on a resize.
        # Replica routing must stop striping hot rows to the corpse
        # (fall back to owners) — otherwise every retry re-routes to
        # the dead holder and replicated reads hard-fail while their
        # owners are alive.
        dead_sid = self._zoo.rank_to_server_id(dead)
        if dead_sid >= 0:
            for table in self._cache:
                table.replica_server_dead(dead_sid)
        lost = [(key, n) for key, n in list(self._inflight.items())
                if key[0] == dead]
        for key, n in lost:
            self._inflight.pop(key, None)
            _dst, table_id, msg_id = key
            table = self._cache[table_id]
            table.fail(msg_id,
                       f"{PEER_LOST_MARK} rank {dead} died before "
                       f"replying (table {table_id}, msg {msg_id})",
                       count=False)
            for _ in range(n):  # one notify per outstanding shard
                table.notify(msg_id)

    # ref: src/worker.cpp:78-84
    def _process_reply_get(self, msg: Message) -> None:
        table = self._cache[msg.table_id]
        self._untrack((msg.src, msg.table_id, msg.msg_id))
        # Every shard reply — error or not — counts exactly one notify
        # (the finally), so the waiter completes only after ALL shards
        # report; wait() then raises on any recorded failure. Releasing
        # early on the first error would hand the caller a buffer that a
        # late sibling reply still writes. EXCEPTION:
        # a replica-routed shard that came back short (holder missing
        # rows / below a read-your-writes floor) TRANSFERS its notify
        # onto the repair request(s) it stages — the waiter then
        # completes only when the repaired rows landed too.
        handoff = False
        try:
            error = take_error(msg)
            if error is not None:
                table.fail(msg.msg_id, error, count=False)
            elif not msg.data:
                # Benign tick reply (sync-mode full-coverage padding):
                # nothing to hand to the table — just count it down.
                pass
            else:
                # Reply context (origin server, version stamp, replica
                # row count, request id): lets the table attribute the
                # payload to a shard version for the client cache and
                # route prefetch replies — single worker thread, so
                # plain attributes.
                table._begin_reply(self._reply_server_id(msg),
                                   reply_version(msg), msg.msg_id,
                                   replica_row_count(msg))
                try:
                    # NOT under the table lock: reply handling may
                    # MATERIALIZE device payloads (host-buffer gets),
                    # which blocks on server-produced computations —
                    # holding the lock across that wait starves the
                    # producing side.
                    with monitor("WORKER_REPLY_GET", msg_id=msg.msg_id,
                                 table=msg.table_id):
                        table.process_reply_get(msg.data)
                finally:
                    table._end_reply()
                handoff = self._send_repairs(table, msg)
        except Exception as exc:
            table.fail(msg.msg_id, f"reply handling failed: {exc}",
                       count=False)
            raise
        finally:
            if not handoff:
                table.notify(msg.msg_id)

    def _send_repairs(self, table, msg: Message) -> bool:
        """Drain the repairs ``process_reply_get`` staged (rows a
        replica holder could not serve validly) into follow-up shard
        requests toward the rows' OWNERS, under the SAME request id.
        Returns True when the caller must skip this reply's notify —
        it was transferred onto the repairs (extended by
        ``extend_request`` when several owners are involved)."""
        repairs = table.take_repairs()
        if not repairs:
            return False
        table.extend_request(msg.msg_id, len(repairs) - 1)
        for server_id, blobs in repairs:
            dst = self._zoo.server_rank(server_id)
            shard = Message(src=self._zoo.rank, dst=dst,
                            msg_type=MsgType.Request_Get,
                            table_id=msg.table_id, msg_id=msg.msg_id)
            shard.data = list(blobs)
            self._track((dst, msg.table_id, msg.msg_id))
            count_event(replica_mod.REPLICA_REPAIR)
            self.send_to(actors.COMMUNICATOR, shard)
        return True

    # ref: src/worker.cpp:86-88
    def _process_reply_add(self, msg: Message) -> None:
        with monitor("WORKER_REPLY_ADD", msg_id=msg.msg_id,
                     table=msg.table_id):
            table = self._cache[msg.table_id]
            self._untrack((msg.src, msg.table_id, msg.msg_id))
            # The piggybacked version bump must land BEFORE the notify:
            # the adder's completion callback reads the tracker to
            # resolve its self-invalidated cache slots (read-your-
            # writes); it also raises this worker's read-your-writes
            # floor for the shard (replica groups below the floor repair
            # to the owner).
            table.note_add_ack(self._reply_server_id(msg),
                               reply_version(msg))
            error = take_error(msg)
            if error is not None:
                table.fail(msg.msg_id, error, count=False)
            table.notify(msg.msg_id)

    def _process_reply_batch_add(self, msg: Message) -> None:
        """One coalesced ack: notify every sub-add's waiter, surfacing
        per-sub server errors through the same fail-then-wait path an
        individual Reply_Add would take."""
        error = take_error(msg)
        if error is not None:
            # Whole-batch failure with no descriptor: the server could
            # not even parse which subs the batch carried, so the
            # waiters cannot be mapped to acks. A stranded waiter is
            # the one unacceptable outcome — abort the table layer so
            # every blocked wait() raises instead of hanging (this only
            # happens on frame corruption, where transport integrity is
            # gone anyway).
            from ..util import log
            log.error("worker: batch add rejected wholesale by the "
                      "server (%s); aborting table waits", error)
            self.abort_tables(
                f"batch add rejected wholesale by rank {msg.src}: "
                f"{error}")
            return
        desc = msg.data[0].as_array(np.int32)
        if desc.size != 1 + 4 * int(desc[0]):
            # A stride mismatch is a pre-version peer's stride-3 ack
            # (or frame corruption): parsing it would notify the WRONG
            # requests' waiters and crash mid-loop, stranding the rest.
            # Same escape hatch as the whole-batch-error path above —
            # loud abort over silent ack misrouting.
            from ..util import log
            log.error("worker: batch ack descriptor stride mismatch "
                      "(%d ints for %d subs) — mixed-build coalesced "
                      "cluster? (docs/WIRE_FORMAT.md)", desc.size,
                      int(desc[0]))
            self.abort_tables(
                f"unparseable batch ack from rank {msg.src}: "
                f"{desc.size} descriptor ints for {int(desc[0])} subs")
            return
        err_blobs = msg.data[1:]
        err_idx = 0
        server_id = self._reply_server_id(msg)
        for i in range(int(desc[0])):
            table_id, msg_id, failed, version = (
                int(v) for v in desc[1 + 4 * i:5 + 4 * i])
            self._untrack((msg.src, table_id, msg_id))
            table = self._cache[table_id]
            # Per-sub version stamp, noted before the notify (the
            # adder's cache-resolution callback reads it; the
            # read-your-writes floor rises with it).
            table.note_add_ack(server_id, version)
            if failed:
                # Error texts are blobs 1..k of the batch reply; the
                # helper decodes straight off the wire view.
                text = msg.text_payload(1 + err_idx) \
                    if err_idx < len(err_blobs) \
                    else "batched add failed on the server"
                err_idx += 1
                table.fail(msg_id, text, count=False)
            table.notify(msg_id)

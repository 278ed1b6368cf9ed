"""Server actor: owns table shards and applies updates.

TPU-native equivalent of the reference's ``Server``/``SyncServer``
(ref: include/multiverso/server.h:13-24, src/server.cpp:23-233). The async
server invokes table logic directly and replies; the BSP ``SyncServer``
gates requests behind per-worker vector clocks so that every worker's i-th
Get observes exactly the state after all workers' j-th Adds — the same
contract as the reference (ref: src/server.cpp:60-66). The table storage the
server fronts is a sharded ``jax.Array`` in device HBM; the per-message work
here is host-side control only, with the arithmetic jit-dispatched.
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Deque, List, Optional

import numpy as np

from ..core.blob import Blob
from ..core.message import (PEER_LOST_MARK, Message, MsgType, mark_error,
                            mark_replica_reply, stamp_version,
                            unpack_add_batch)
from ..util import log, mt_queue
from ..util.configure import define_double, get_flag
from ..util.dashboard import count, monitor, samples
from . import actor as actors
from . import device_lock
# Imported eagerly so the -server_fuse_* flag definitions are
# registered before Zoo.start parses the command line.
from . import fusion
from . import replica as replica_mod
# Imported eagerly so the -snapshot_* flag definitions are registered
# before Zoo.start parses the command line (a lazily-imported module's
# flags would silently fail to parse).
from . import snapshot as snapshot_mod
from .actor import Actor

define_double("backup_worker_ratio", 0.0,
              "straggler cutoff for the BSP sync server: this share of "
              "workers ('set 20 means 20%'; fractional 0.2 accepted "
              "too) are treated as BACKUPS — the global vector clock "
              "advances once the fastest (1 - ratio) of workers have "
              "ticked, so an epoch finishes despite a straggling or "
              "dead worker (its late ticks still apply, they just no "
              "longer gate anyone). 0 (default) = strict BSP, the "
              "reference's semantics (where this flag existed but was "
              "unused, ref: src/server.cpp:21)")

_INF = float("inf")


def backup_worker_count(num_workers: int) -> int:
    """-backup_worker_ratio as a worker count: 'set 20 means 20%' (the
    reference's convention) with fractional values (0.2) accepted too;
    clamped so at least one worker always gates the clock."""
    ratio = float(get_flag("backup_worker_ratio"))
    if ratio >= 1.0:
        ratio = ratio / 100.0
    if ratio <= 0 or num_workers <= 1:
        return 0
    return min(int(ratio * num_workers), num_workers - 1)


class Server(Actor):
    #: Process-wide: table logic dispatches jitted programs over the
    #: process's (shared) device mesh, and TWO server actor threads —
    #: virtual ranks on a LocalFabric — interleaving multi-device
    #: executions deadlock inside XLA's CPU runtime (observed: both
    #: threads parked in pxla __call__ forever). One server per process
    #: (the real deployment) never contends; RLock because the sync
    #: server's drain paths re-enter through Server._process_*.
    #: SCOPED to device-backed tables only (``needs_device_lock``):
    #: host-only table logic (KV control plane) must not serialize two
    #: in-process server shards against each other.
    #: The lock object itself is the process-wide device-dispatch lock
    #: (runtime/device_lock.py): in multi-zoo mode trainer and worker
    #: dispatch sites serialize on the SAME lock.
    _table_lock = device_lock.TABLE_LOCK
    _no_lock = contextlib.nullcontext()

    def _lock_for(self, table):
        """Device-backed tables serialize on the process-wide device
        lock — but only while multi-device serialization is ACTIVE
        (``device_lock.active()``): on a single-device process the
        wedge class the lock exists for cannot occur (no inter-device
        rendezvous to deadlock the execution pool), and process-wide
        serialization of sibling server actors was the bulk of what
        two servers in one process lost to one. Inactive mode
        falls back to the table's per-instance state lock, which still
        pairs (state, version) against the async snapshotter. Host-only
        tables always take their own state lock — cheap (uncontended
        except versus the snapshotter, since the actor thread is the
        only writer) but required so the snapshotter's capture cannot
        tear against a concurrent host-side add."""
        if getattr(table, "needs_device_lock", True) \
                and device_lock.active():
            return self._table_lock
        return getattr(table, "_state_lock", self._no_lock)

    def __init__(self, zoo) -> None:
        super().__init__(actors.SERVER, zoo)
        # Mailbox pressure is the admission-control signal of the
        # serving tier (serving/admission.py sheds over the high
        # watermark; docs/SERVING.md) — record
        # per-push depth into the MAILBOX_DEPTH[*] Samples family.
        # Gated: a training-only deployment must not pay a reservoir
        # append per message for samples nobody reads.
        if mt_queue.depth_sampling_enabled():
            self.mailbox.track_depth("MAILBOX_DEPTH[server]")
        self._store: List = []  # registered ServerTables, indexed by table id
        self.register_handler(MsgType.Request_Get, self._process_get)
        self.register_handler(MsgType.Request_Add, self._process_add)
        self.register_handler(MsgType.Request_BatchAdd,
                              self._process_batch_add)
        # Hot-shard read replication (runtime/replica.py,
        # docs/SHARDING.md): owner refresh pushes land here; the
        # promoted-row map broadcast arrives via the communicator's
        # per-actor clone routing.
        self.register_handler(MsgType.Request_ReplicaSync,
                              self._process_replica_sync)
        self.register_handler(MsgType.Control_Replica_Map,
                              self._process_replica_map)
        # Live elastic resharding (runtime/shard_map.py,
        # docs/SHARDING.md): controller-ordered range migration between
        # live servers + the dual-read/forwarding window.
        self.register_handler(MsgType.Request_ShardBegin,
                              self._process_shard_begin)
        self.register_handler(MsgType.Server_Shard_Pump,
                              self._process_shard_pump)
        self.register_handler(MsgType.Request_ShardData,
                              self._process_shard_data)
        self.register_handler(MsgType.Request_ShardAck,
                              self._process_shard_ack)
        self.register_handler(MsgType.Request_ShardAbort,
                              self._process_shard_abort)
        self.register_handler(MsgType.Request_FwdGet,
                              self._process_fwd_get)
        self.register_handler(MsgType.Request_FwdAdd,
                              self._process_fwd_add)
        self.register_handler(MsgType.Control_Shard_Map,
                              self._process_shard_map)
        # Fault tolerance: periodic async snapshots + rejoin restore
        # (runtime/snapshot.py), enabled by -snapshot_dir.
        self._snapshots = None
        if str(get_flag("snapshot_dir", "")):
            self._snapshots = snapshot_mod.SnapshotManager(
                zoo, self._table_lock)
        # Rejoin readiness gate: on a RESTARTED rank, surviving workers
        # start retrying requests the moment the communicator is up —
        # before the application has re-created (and restored) the
        # tables. Registration runs inside the table base constructor,
        # so a registered-but-unready table must NACK retryably, not
        # serve a half-constructed shard.
        self._gate_unready = bool(get_flag("rejoin"))
        self._ready_ids: set = set()
        # Server-side request fusion (runtime/fusion.py,
        # docs/SERVER_ENGINE.md): when the mailbox holds more than one
        # message, drain a bounded batch and execute one device
        # program per (table, op) group. Read at construction, like
        # -sparse_compress; SyncServer forces max to 1 — the BSP
        # vector clocks count one request per worker per step.
        self._fuse_max = max(int(get_flag("server_fuse_max")), 1)
        self._fuse_bytes = max(int(get_flag("server_fuse_bytes")), 1)

    def start(self) -> None:
        super().start()
        if self._snapshots is not None:
            self._snapshots.start()

    def stop(self) -> None:
        if self._snapshots is not None:
            self._snapshots.stop()
        super().stop()

    @staticmethod
    def get_server(zoo) -> "Server":
        """Factory on the -sync flag (ref: src/server.cpp:224-231)."""
        if get_flag("sync", False):
            log.info("Create a sync server")
            return SyncServer(zoo)
        log.debug("Create a async server")
        return Server(zoo)

    def register_table(self, server_table) -> int:
        self._store.append(server_table)
        table_id = len(self._store) - 1
        if not self._gate_unready:
            self._ready_ids.add(table_id)
        if self._snapshots is not None:
            # Track for the periodic cut. Restore (rejoin) and the
            # snapshot-readiness mark wait for table_ready —
            # registration runs inside the base constructor, before
            # the shard's storage exists.
            self._snapshots.track(table_id, server_table)
        return table_id

    def table_ready(self, server_table) -> None:
        """A server table finished construction (table factory hook):
        on a rejoining rank, restore it from the latest snapshot before
        it serves its first request; in all cases, open it to the
        snapshotter and (under the rejoin gate) to requests."""
        if self._snapshots is not None:
            self._snapshots.restore_if_pending(server_table)
        try:
            table_id = self._store.index(server_table)
        except ValueError:
            return
        self._ready_ids.add(table_id)

    def _table(self, table_id: int):
        """The registered-and-ready table, or a RETRYABLE error: on a
        rejoining restarted rank, requests can land after the server
        actor starts but before the application re-created (or
        finished constructing) this table — the requester must back
        off and re-issue, not treat it as a fatal table-logic
        failure."""
        if 0 <= table_id < len(self._store) \
                and table_id in self._ready_ids:
            return self._store[table_id]
        raise RuntimeError(
            f"{PEER_LOST_MARK} table {table_id} not (yet) registered "
            f"on rank {self._zoo.rank} — rejoin in progress?")

    # -- server-side request fusion (runtime/fusion.py,
    #    docs/SERVER_ENGINE.md) --
    def _main(self) -> None:
        if self._fuse_max <= 1:
            return super()._main()
        while True:
            batch = self.mailbox.pop_batch(
                self._fuse_max, self._fuse_bytes,
                size_of=fusion.message_nbytes)
            if not batch:
                break
            for msg in batch:   # every message of a fused batch waited
                self._popped(msg)
            if len(batch) == 1:
                self._safe_dispatch(batch[0])
                continue
            samples("SERVER_FUSE_BATCH").add(len(batch))
            try:
                self._dispatch_fused(batch)
            except Exception:  # noqa: BLE001 - the actor must not die
                # silently (same contract as _safe_dispatch); per-entry
                # errors were already captured into error replies, so
                # reaching here means the planner/reply layer itself
                # broke — log loudly.
                log.error("server: fused batch dispatch raised")
                import traceback
                traceback.print_exc()

    def _dispatch_fused(self, batch: List[Message]) -> None:
        """Execute one drained batch: eligible Get/Add/BatchAdd units
        fuse into (table, op) groups (one device program each);
        everything else is a barrier that dispatches through the
        ordinary serial handler. Replies are deferred and emitted in
        arrival order at each barrier and at batch end."""
        infos = [fusion.classify(self, i, m)
                 for i, m in enumerate(batch)]
        plan = fusion.split_plan(batch, infos)
        cursor = 0

        def emit(upto: int) -> None:
            nonlocal cursor
            while cursor < upto:
                if infos[cursor] is not None:
                    self._send_fused_reply(batch[cursor], infos[cursor])
                cursor += 1

        for kind, payload in plan:
            if kind == "serial":
                # Every fusable message before the barrier has fully
                # executed (split_plan flushes windows first): its
                # replies must leave before the barrier's handler can
                # send anything, preserving global reply order.
                emit(payload)
                self._safe_dispatch(batch[payload])
                cursor = payload + 1
            else:
                self._run_fused_step(payload)
        emit(len(batch))

    def _run_fused_step(self, groups) -> None:
        touched = []
        for table, is_get, entries in groups:
            self._run_fused_group(table, is_get, entries)
            touched.append(table)
        for table in touched:
            try:
                self._replica_flush(table)
            except Exception:  # noqa: BLE001 - replica traffic is
                # best-effort; the served entries' replies must still
                # go out.
                log.error("server: replica flush after fused group "
                          "failed")
                import traceback
                traceback.print_exc()

    def _run_fused_group(self, table, is_get: bool, entries) -> None:
        """One (table, op) group, ONE device program. A failure falls
        back to per-entry serial replay — exact serial semantics, with
        per-entry errors captured into the deferred replies."""
        name = "SERVER_PROCESS_GET" if is_get else "SERVER_PROCESS_ADD"
        if len(entries) == 1:
            # Singleton "group": the fused paths would only add
            # overhead (a forced host materialization of the gather,
            # dedup bookkeeping) with nothing to amortize it over —
            # run the exact serial path; replies, stamps and metrics
            # are identical to an unfused dispatch.
            with monitor(name, msg_id=entries[0].msg_id,
                         table=entries[0].table_id):
                self._replay_serial(table, is_get, entries)
            return
        try:
            # one span for the group: the first request's id, and how
            # many requests the one program serves
            with monitor(name, msg_id=entries[0].msg_id,
                         table=entries[0].table_id, fused=len(entries)):
                if is_get:
                    with self._lock_for(table):
                        results = table.process_fused_get(
                            [e.blobs for e in entries])
                        if device_lock.active():
                            device_lock.settle(
                                [b.data for blobs in results
                                 for b in blobs if b.on_device])
                        v = table.version
                    for e, blobs in zip(entries, results):
                        e.result = blobs
                        e.version = v
                else:
                    with self._lock_for(table):
                        table.process_fused_add(
                            [e.blobs for e in entries])
                        device_lock.settle(
                            getattr(table, "_data", None))
                        # One bump per fused Add, all inside the lock
                        # (snapshot consistency — see _process_add);
                        # every reply carries the POST-BATCH version.
                        # Conservatively LATER than the serial stamp,
                        # which keeps read-your-writes sound: a floor
                        # can only over-demand freshness, never admit
                        # a stale read (docs/SERVER_ENGINE.md).
                        table.version += len(entries)
                        v = table.version
                    for e in entries:
                        e.version = v
            if table.needs_device_lock:
                count("SERVER_DEVICE_DISPATCHES", 1)
        except fusion.PartialFuseError as err:
            # The fused apply folded a prefix into table state before
            # failing: account the prefix (version bump + stamps),
            # then replay only the unapplied tail — replaying an
            # applied request would double-count its delta.
            log.error("server: fused add group failed after %d of %d "
                      "— replaying the tail serially",
                      err.applied, len(entries))
            import traceback
            traceback.print_exc()
            if err.applied:
                with self._lock_for(table):
                    device_lock.settle(getattr(table, "_data", None))
                    table.version += err.applied
                    v = table.version
                for e in entries[:err.applied]:
                    e.version = v
                if table.needs_device_lock:
                    count("SERVER_DEVICE_DISPATCHES", 1)
            self._replay_serial(table, is_get, entries,
                                start=err.applied)
        except Exception:  # noqa: BLE001
            log.error("server: fused %s group failed — replaying "
                      "serially", "get" if is_get else "add")
            import traceback
            traceback.print_exc()
            self._replay_serial(table, is_get, entries)

    def _replay_serial(self, table, is_get: bool, entries,
                       start: int = 0) -> None:
        """Per-entry fallback with exact serial semantics; failures
        travel back per entry in the deferred replies."""
        for e in entries[start:]:
            try:
                if is_get:
                    with self._lock_for(table):
                        e.result = table.process_get(e.blobs)
                        if device_lock.active():
                            device_lock.settle(
                                [b.data for b in e.result
                                 if b.on_device])
                    e.version = table.version
                else:
                    with self._lock_for(table):
                        table.process_add(e.blobs)
                        device_lock.settle(
                            getattr(table, "_data", None))
                        table.version += 1
                    e.version = table.version
                if table.needs_device_lock:
                    count("SERVER_DEVICE_DISPATCHES", 1)
            except Exception as exc:  # noqa: BLE001
                e.error = exc
                e.version = getattr(table, "version", -1)
                log.error("server: serial replay of fused entry "
                          "failed (error travels in the reply)")
                import traceback
                traceback.print_exc()

    def _send_fused_reply(self, msg: Message, entries) -> None:
        """Emit the deferred reply for one fully-executed message:
        the per-message Reply_Get/Reply_Add twin of the serial
        handlers, or the reassembled Reply_BatchAdd descriptor
        [n, (table_id, msg_id, err, version)...] + one utf-8 text
        blob per failed sub (core/message.py pack_add_batch)."""
        if msg.type_int == int(MsgType.Request_BatchAdd):
            reply = msg.create_reply_message()
            desc: List[int] = [len(entries)]
            err_blobs: List[Blob] = []
            for e in entries:
                failed = e.error is not None
                desc.extend((e.table_id, e.msg_id,
                             1 if failed else 0, e.version))
                if failed:
                    text = f"{type(e.error).__name__}: {e.error}" \
                        .encode(errors="replace")
                    err_blobs.append(
                        Blob(np.frombuffer(text, np.uint8).copy()))
            reply.push(Blob(np.asarray(desc, dtype=np.int32)))
            reply.data.extend(err_blobs)
            self.send_to(actors.COMMUNICATOR, reply)
            return
        e = entries[0]
        reply = msg.create_reply_message()
        if e.error is not None:
            mark_error(reply, e.error)
        else:
            if e.is_get:
                reply.data = e.result
            stamp_version(reply, e.version)
        self.send_to(actors.COMMUNICATOR, reply)

    # ref: src/server.cpp:36-46
    def _process_get(self, msg: Message) -> None:
        with monitor("SERVER_PROCESS_GET", msg_id=msg.msg_id,
                     table=msg.table_id):
            reply = msg.create_reply_message()
            # The reply goes out even if table logic raises — a swallowed
            # reply would deadlock the requester's waiter forever — and a
            # failure travels back as an error reply so the requester's
            # wait() RAISES instead of consuming an empty payload (the
            # actor loop only logs; without this, every server-side CHECK
            # degrades to silent garbage at the caller).
            forwarded = False
            try:
                if not msg.data:
                    # Sync-mode clock-tick shard (worker full-coverage
                    # padding): no table logic, no payload — the empty
                    # reply only counts down the requester's waiter
                    # (on a SyncServer the wrapper already ticked the
                    # vector clock).
                    return
                table = self._table(msg.table_id)
                # Dual-read window (docs/SHARDING.md): rows this shard
                # handed off forward to their new owner, which replies
                # to the requester directly (with OUR still-owned rows
                # piggybacked) — no reply leaves from here.
                outs = table.shard_forward_get(msg)
                if outs is not None:
                    forwarded = True
                    for out in outs:
                        self.send_to(actors.COMMUNICATOR, out)
                    return
                with self._lock_for(table):
                    reply.data = table.process_get(msg.data)
                    # Multi-zoo mode: the gather must finish before the
                    # lock releases, or its execution overlaps a sibling
                    # rank's next program (device_lock.py). active()
                    # gate keeps the list build off the production hot
                    # path.
                    if device_lock.active():
                        device_lock.settle([b.data for b in reply.data
                                            if b.on_device])
                if table.needs_device_lock:
                    # One gather program per serial Get — the count
                    # fusion divides down (docs/SERVER_ENGINE.md).
                    count("SERVER_DEVICE_DISPATCHES", 1)
                # Version stamp: the shard state this Get observed
                # (client-cache freshness anchor). Error replies stay
                # unstamped — the worker checks the error flag first.
                stamp_version(reply, table.version)
                # Replica-served trailing rows (docs/SHARDING.md): the
                # worker needs the count to find the reply's replica
                # descriptor blob.
                replica_rows = table.take_reply_replica_rows()
                if replica_rows:
                    mark_replica_reply(reply, replica_rows)
            except Exception as exc:  # noqa: BLE001
                mark_error(reply, exc)
                raise
            finally:
                if not forwarded:
                    self.send_to(actors.COMMUNICATOR, reply)
            self._replica_flush(table)

    def _replica_flush(self, table) -> None:
        """Send whatever replica/reshard traffic the served request
        made due: write-through refreshes of dirty promoted rows
        toward the holders, the hot-row report toward the controller,
        and any pending migration re-announcements (a lost
        Control_Shard_Done resends on traffic)."""
        for out in table.replica_flush_if_due():
            self.send_to(actors.COMMUNICATOR, out)
        for out in table.shard_announce():
            self.send_to(actors.COMMUNICATOR, out)

    def _process_replica_sync(self, msg: Message) -> None:
        """An owner server's refresh push for promoted rows this rank
        holds replicas of. Fire-and-forget: no waiter exists, so no
        reply — and no lock either, the replica store is touched only
        from this actor thread (serve in process_get, refresh here).
        A sync whose src is THIS rank is the communicator's failure
        echo (the push toward a dead holder never left): re-dirty its
        rows so the next flush re-pushes them, keeping the version
        watermark sound."""
        try:
            table = self._table(msg.table_id)
        except RuntimeError:
            return  # rejoin gap — replica content rebuilds on the
            # next flush cadence; nothing to NACK
        if msg.src == self._zoo.rank:
            table.replica_redirty(msg.data)
            return
        table.apply_replica_sync(msg.data)

    def _process_replica_map(self, msg: Message) -> None:
        """Promoted-row map broadcast (cloned to this actor by the
        communicator's routing): each named table adopts its row set —
        owner shards reply with the initial value push for their newly
        promoted rows, holders prune demoted entries."""
        try:
            epoch, promoted = replica_mod.unpack_replica_map(
                [b.as_array(np.int32) for b in msg.data])
        except Exception:  # noqa: BLE001 - a malformed map must not
            # kill the server loop; the next broadcast replaces it.
            log.error("server: undecodable replica map %r", msg)
            return
        for table_id, rows in promoted.items():
            if not (0 <= table_id < len(self._store)) \
                    or table_id not in self._ready_ids:
                continue
            for out in self._store[table_id].apply_replica_map(epoch,
                                                               rows):
                self.send_to(actors.COMMUNICATOR, out)

    # -- live elastic resharding (runtime/shard_map.py,
    #    docs/SHARDING.md; all on this actor thread) --
    def _process_shard_begin(self, msg: Message) -> None:
        """Controller's move order: the source table starts streaming,
        driven by local pump messages so serving traffic interleaves
        between chunks; an unsupported table (sparse bitmap, stateful
        updater, range not owned) NACKs and the controller rolls the
        move back."""
        from .zoo import CONTROLLER_RANK
        desc = msg.data[0].as_array(np.int64)
        epoch = int(desc[5])
        try:
            table = self._table(msg.table_id)
            ok = table.shard_begin_out(desc)
        except Exception:  # noqa: BLE001 - unready table / bad desc
            ok = False
        if not ok:
            log.error("rank %d: refusing shard migration of table %d "
                      "(epoch %d) — unsupported or not owned",
                      self._zoo.rank, msg.table_id, epoch)
            nack = Message(src=self._zoo.rank, dst=CONTROLLER_RANK,
                           msg_type=MsgType.Control_Shard_Done,
                           table_id=msg.table_id)
            nack.push(Blob(np.asarray([epoch, 0, self._zoo.server_id],
                                      dtype=np.int64)))
            self.send_to(actors.COMMUNICATOR, nack)
            return
        self.receive(Message(src=self._zoo.rank, dst=self._zoo.rank,
                             msg_type=MsgType.Server_Shard_Pump,
                             table_id=msg.table_id))

    def _process_shard_pump(self, msg: Message) -> None:
        try:
            table = self._table(msg.table_id)
        except RuntimeError:
            return
        outs, more = table.shard_pump()
        for out in outs:
            self.send_to(actors.COMMUNICATOR, out)
        if more:
            # Re-enqueue so queued serving requests interleave with
            # the stream — a migration must not starve the shard.
            self.receive(Message(src=self._zoo.rank, dst=self._zoo.rank,
                                 msg_type=MsgType.Server_Shard_Pump,
                                 table_id=msg.table_id))

    def _process_shard_data(self, msg: Message) -> None:
        try:
            table = self._table(msg.table_id)
        except RuntimeError:
            return  # rejoin gap: the source retransmits on the ack path
        for out in table.shard_import_chunk(msg):
            self.send_to(actors.COMMUNICATOR, out)

    def _process_shard_ack(self, msg: Message) -> None:
        try:
            table = self._table(msg.table_id)
        except RuntimeError:
            return
        for out in table.shard_ack(msg):
            self.send_to(actors.COMMUNICATOR, out)

    def _process_shard_abort(self, msg: Message) -> None:
        try:
            table = self._table(msg.table_id)
        except RuntimeError:
            return
        for out in table.shard_abort(
                int(msg.data[0].as_array(np.int64)[0])):
            self.send_to(actors.COMMUNICATOR, out)

    def _process_shard_map(self, msg: Message) -> None:
        """Epoch-stamped shard-map broadcast (cloned to this actor by
        the communicator, like Control_Replica_Map): the named table
        commits/prunes its migration state."""
        from . import shard_map as shard_map_mod
        try:
            table_id, smap, alive = shard_map_mod.ShardMap.unpack(
                [b.as_array(np.int64) for b in msg.data])
        except Exception:  # noqa: BLE001 - malformed broadcast must
            # not kill the server loop; the next broadcast replaces it.
            log.error("server: undecodable shard map %r", msg)
            return
        if not (0 <= table_id < len(self._store)) \
                or table_id not in self._ready_ids:
            return
        for out in self._store[table_id].apply_shard_map_server(
                smap.epoch, smap, alive):
            self.send_to(actors.COMMUNICATOR, out)

    def _process_fwd_get(self, msg: Message) -> None:
        """A source-forwarded Get (dual-read window): serve the moved
        rows here, merge the source's piggybacked rows, and reply
        IMPERSONATING the source rank — the requester's in-flight
        accounting keys on the shard it actually sent to, and the
        moved rows ride the reply as a replica group attributed to
        THIS shard (core/message.py Request_FwdGet)."""
        with monitor("SERVER_PROCESS_GET", msg_id=msg.msg_id,
                     table=msg.table_id):
            src_rank = int(msg.data[0].as_array(np.int64)[0]) \
                if msg.data else msg.src
            reply = Message(src=src_rank, dst=msg.src,
                            msg_type=MsgType.Reply_Get,
                            table_id=msg.table_id, msg_id=msg.msg_id)
            try:
                table = self._table(msg.table_id)
                with self._lock_for(table):
                    blobs, n_rep, src_rank2, src_version = \
                        table.process_forward_get(msg.data)
                    if device_lock.active():
                        device_lock.settle([b.data for b in blobs
                                            if b.on_device])
                reply.src = src_rank2
                reply.data = blobs
                stamp_version(reply, src_version)
                if n_rep:
                    mark_replica_reply(reply, n_rep)
            except Exception as exc:  # noqa: BLE001
                mark_error(reply, exc)
                raise
            finally:
                self.send_to(actors.COMMUNICATOR, reply)
            # A grow destination may see ONLY forwarded traffic until
            # the commit lands — the pending-Done re-announce must ride
            # it (docs/SHARDING.md).
            self._replica_flush(table)

    def _process_fwd_add(self, msg: Message) -> None:
        """A source-forwarded Add subset: apply, then ack the
        requester impersonating the source rank — version-UNSTAMPED
        (the moved rows' versions now come from THIS shard's counter;
        stamping it under the source's identity would fire the
        generation-regression guard spuriously). msg_id < 0 marks a
        secondary-window forward: applied, never acked."""
        with monitor("SERVER_PROCESS_ADD", msg_id=msg.msg_id,
                     table=msg.table_id):
            src_rank = int(msg.data[0].as_array(np.int64)[0]) \
                if msg.data else msg.src
            reply = None
            if msg.msg_id >= 0:
                reply = Message(src=src_rank, dst=msg.src,
                                msg_type=MsgType.Reply_Add,
                                table_id=msg.table_id,
                                msg_id=msg.msg_id)
            try:
                table = self._table(msg.table_id)
                with self._lock_for(table):
                    table.process_add(msg.data[1:])
                    device_lock.settle(getattr(table, "_data", None))
                    table.version += 1
            except Exception as exc:  # noqa: BLE001
                if reply is not None:
                    mark_error(reply, exc)
                raise
            finally:
                if reply is not None:
                    self.send_to(actors.COMMUNICATOR, reply)
            self._replica_flush(table)

    # ref: src/server.cpp:48-58
    def _process_add(self, msg: Message) -> None:
        with monitor("SERVER_PROCESS_ADD", msg_id=msg.msg_id,
                     table=msg.table_id):
            reply = msg.create_reply_message()
            silent = False
            try:
                if not msg.data:
                    # Clock-tick shard: see _process_get. No version
                    # bump — nothing was applied.
                    return
                table = self._table(msg.table_id)
                # Dual-write window (docs/SHARDING.md): moved rows'
                # deltas forward to the new owner, which acks the
                # requester; the full add ALSO applies here without an
                # ack (both-apply — exactly one copy survives the
                # commit-or-rollback outcome).
                route = table.shard_forward_add(msg)
                if route is not None:
                    silent = True
                    local_msg, outs = route
                    for out in outs:
                        self.send_to(actors.COMMUNICATOR, out)
                    if local_msg is not None:
                        with self._lock_for(table):
                            # Both-apply exemption: this deliberate
                            # write into the handoff copy must bypass
                            # the own-window NACK.
                            table._in_both_apply = True
                            try:
                                table.process_add(local_msg.data)
                            finally:
                                table._in_both_apply = False
                            device_lock.settle(
                                getattr(table, "_data", None))
                            table.version += 1
                    return
                with self._lock_for(table):
                    table.process_add(msg.data)
                    # Multi-zoo mode: the update program (new table
                    # state) must land before the lock releases.
                    device_lock.settle(getattr(table, "_data", None))
                    # One bump per APPLIED Add; the ack carries the
                    # post-add version so the adder can resolve its
                    # self-invalidated cache slots (read-your-writes).
                    # INSIDE _lock_for(table): the snapshotter's
                    # capture acquires the same lock (device lock or
                    # the table's state lock) around each state cut and
                    # version read, so a restore can never restore
                    # state ahead of (or behind) its recorded version.
                    table.version += 1
                if table.needs_device_lock:
                    count("SERVER_DEVICE_DISPATCHES", 1)
                stamp_version(reply, table.version)
            except Exception as exc:  # noqa: BLE001
                mark_error(reply, exc)
                raise
            finally:
                if not silent:
                    self.send_to(actors.COMMUNICATOR, reply)
            self._replica_flush(table)

    def _process_batch_add(self, msg: Message) -> None:
        """Coalesced adds: apply every sub-add, ack them all in ONE
        Reply_BatchAdd (descriptor [n, (table_id, msg_id, err,
        version)...] + one utf-8 text blob per failed sub; version is
        the shard version after the sub applied, the batched twin of
        the per-message VERSION_SLOT stamp). A sub failure must not
        stop the siblings: each waiter still gets its notify, failed
        ones with the error recorded so the caller's wait() raises.
        The reply goes out in EVERY path — a swallowed reply would
        strand every sub-add's waiter forever (same invariant as
        _process_get/_process_add above) — so a batch whose payload
        blobs fail to unpack still acks each sub the descriptor names,
        all marked failed."""
        with monitor("SERVER_PROCESS_BATCH_ADD"):
            reply = msg.create_reply_message()
            desc: List[int] = [0]
            err_blobs: List[Blob] = []
            touched: dict = {}  # table_id -> table (replica flush)

            def record(table_id: int, msg_id: int,
                       exc: Optional[BaseException],
                       version: int = -1) -> None:
                desc.extend((table_id, msg_id,
                             0 if exc is None else 1, version))
                desc[0] += 1
                if exc is not None:
                    text = f"{type(exc).__name__}: {exc}" \
                        .encode(errors="replace")
                    err_blobs.append(
                        Blob(np.frombuffer(text, np.uint8).copy()))

            try:
                try:
                    subs = unpack_add_batch(msg)
                except Exception as exc:  # noqa: BLE001 - malformed
                    # batch: the descriptor (blob 0) usually still
                    # parses even when the payload blobs are short —
                    # ack every sub it names as failed so no waiter
                    # hangs; a garbage descriptor leaves only the
                    # error-marked empty reply (worker logs it).
                    log.error("server: batch add unpack failed")
                    import traceback
                    traceback.print_exc()
                    try:
                        raw = msg.data[0].as_array(np.int32)
                        for i in range(int(raw[0])):
                            record(int(raw[1 + 3 * i]),
                                   int(raw[2 + 3 * i]), exc)
                    except Exception:  # noqa: BLE001
                        mark_error(reply, exc)
                        return
                    return
                for sub in subs:
                    try:
                        table = self._table(sub.table_id)
                        route = table.shard_forward_add(sub)
                        if route is not None:
                            # Dual-write window: the destination acks
                            # this sub under its own Reply_Add — it
                            # must NOT appear in this batch ack too.
                            local_msg, outs = route
                            for out in outs:
                                self.send_to(actors.COMMUNICATOR, out)
                            if local_msg is not None:
                                with self._lock_for(table):
                                    table._in_both_apply = True
                                    try:
                                        table.process_add(
                                            local_msg.data)
                                    finally:
                                        table._in_both_apply = False
                                    device_lock.settle(
                                        getattr(table, "_data", None))
                                    table.version += 1
                            touched[sub.table_id] = table
                            continue
                        with self._lock_for(table):
                            table.process_add(sub.data)
                            device_lock.settle(
                                getattr(table, "_data", None))
                            # Inside the lock for snapshot consistency
                            # (see _process_add).
                            table.version += 1
                        if table.needs_device_lock:
                            count("SERVER_DEVICE_DISPATCHES", 1)
                        record(sub.table_id, sub.msg_id, None,
                               table.version)
                        touched[sub.table_id] = table
                    except Exception as exc:  # noqa: BLE001 - per-sub
                        # failure travels back in the batch ack
                        try:
                            at = self._store[sub.table_id].version
                        except Exception:  # noqa: BLE001 - bad table id
                            at = -1
                        record(sub.table_id, sub.msg_id, exc, at)
                        log.error("server: batched add failed "
                                  "(error travels in the batch ack)")
                        import traceback
                        traceback.print_exc()
            finally:
                if not reply.data:  # mark_error path already has payload
                    reply.push(Blob(np.asarray(desc, dtype=np.int32)))
                    reply.data.extend(err_blobs)
                self.send_to(actors.COMMUNICATOR, reply)
            for table in touched.values():
                self._replica_flush(table)


class _VectorClock:
    """SyncServer's specialized vector clock (ref: src/server.cpp:81-137).

    ``update(i)`` ticks worker i's local clock and returns True exactly when
    the global clock catches up to the max local clock (all workers level).
    ``finish_train(i)`` retires worker i (clock -> +inf).

    **Backup-worker straggler cutoff** (``num_backup`` > 0, from
    ``-backup_worker_ratio``): the global clock follows the
    ``num_backup``-th smallest local clock instead of the strict
    minimum — i.e. the slowest ``num_backup`` workers no longer gate
    anyone. Their late ticks still count (a straggler's Adds apply when
    they arrive; a DEAD worker simply never contributes), the fast
    workers just stop waiting for them. With ``num_backup == 0`` every
    code path below is the reference's strict-BSP logic, unchanged."""

    def __init__(self, n: int, num_backup: int = 0):
        self._local = [0.0] * n
        self.global_clock = 0.0
        self._num_backup = min(max(int(num_backup), 0), max(n - 1, 0))

    @property
    def num_backup(self) -> int:
        return self._num_backup

    def local_clock(self, i: int) -> float:
        return self._local[i]

    def _max_finite(self) -> float:
        finite = [v for v in self._local if v != _INF]
        return max([self.global_clock] + finite)

    def _cutoff_min(self) -> float:
        """The clock the global follows: the (num_backup+1)-th smallest
        local clock — retired (+inf) workers sort fastest and never
        hold anything back; the num_backup slowest are skipped."""
        return sorted(self._local)[self._num_backup]

    def update(self, i: int) -> bool:
        self._local[i] += 1
        if self._num_backup == 0:
            if self.global_clock < min(self._local):
                self.global_clock += 1
                if self.global_clock == self._max_finite():
                    return True
            return False
        advanced = False
        # A straggler's late tick can move the cutoff several steps at
        # once (its clock stops being the skipped one); catch up fully.
        target = min(self._cutoff_min(), self._max_finite())
        while self.global_clock < target:
            self.global_clock += 1
            advanced = True
        return advanced and self.global_clock == self._max_finite()

    def finish_train(self, i: int) -> bool:
        self._local[i] = _INF
        if self._num_backup == 0:
            if self.global_clock < min(self._local):
                self.global_clock = min(self._local)
                if self.global_clock == self._max_finite():
                    return True
            return False
        target = self._cutoff_min()
        if self.global_clock < target:
            self.global_clock = min(target, max(self._max_finite(),
                                                self.global_clock))
            if self.global_clock == self._max_finite():
                return True
        return False


class SyncServer(Server):
    """BSP server (ref: src/server.cpp:67-222).

    Assumes all workers issue the same number of Adds/Gets per iteration.
    Faster workers' requests are cached and drained when the global clock
    advances; ``Server_Finish_Train`` releases stragglers at shutdown.
    """

    def __init__(self, zoo) -> None:
        super().__init__(zoo)
        # Request fusion is force-disabled in BSP mode regardless of
        # -server_fuse_max: the vector clocks count ONE request per
        # worker per step, and the clock-gated caching below reorders
        # requests in ways the fusion planner must never see
        # (docs/SERVER_ENGINE.md).
        if self._fuse_max > 1:
            log.debug("sync server: request fusion force-disabled "
                      "(BSP clock accounting)")
        self._fuse_max = 1
        self.register_handler(MsgType.Server_Finish_Train,
                              self._process_finish_train)
        n = zoo.num_workers
        # Straggler cutoff (-backup_worker_ratio): the slowest
        # num_backup workers stop gating the clocks — an epoch
        # finishes despite a straggling or dead worker; its late
        # requests still serve/apply when they arrive.
        self._num_backup = backup_worker_count(n)
        if self._num_backup:
            log.info("sync server: %d of %d workers treated as "
                     "backups (straggler cutoff)", self._num_backup, n)
        self._get_clocks = _VectorClock(n, self._num_backup)
        self._add_clocks = _VectorClock(n, self._num_backup)
        self._num_waited_add = [0] * n
        self._add_cache: Deque[Message] = collections.deque()
        self._get_cache: Deque[Message] = collections.deque()

    # ref: src/server.cpp:141-163
    def _process_add(self, msg: Message) -> None:
        worker = self._zoo.rank_to_worker_id(msg.src)
        if (self._get_clocks.local_clock(worker)
                > self._get_clocks.global_clock):
            self._add_cache.append(msg)
            self._num_waited_add[worker] += 1
            return
        # The clock MUST tick even when table logic raises (the error
        # reply went out and the worker sees a recoverable failure) —
        # skipping it would leave this worker's clock permanently behind
        # and the BSP gate would cache every other worker's requests
        # forever: a cluster-wide hang from one bad request.
        try:
            super()._process_add(msg)
        finally:
            if self._add_clocks.update(worker):
                # Strict BSP invariant: at add-level no add can be
                # cached. With a straggler cutoff the skipped worker's
                # requests may still sit cached at leveling — the
                # tolerant alternating drain handles both caches.
                if self._num_backup == 0:
                    assert not self._add_cache
                self._drain_caches(gets=True)

    def _process_batch_add(self, msg: Message) -> None:
        """Defense in depth: workers never coalesce in sync mode (the
        vector clocks count one request per worker per step), but a
        batch that arrives anyway unpacks through the clock-gated
        per-add path — each sub ticks the clocks and acks itself, so
        BSP accounting stays exact."""
        for sub in unpack_add_batch(msg):
            self._process_add(sub)

    # ref: src/server.cpp:165-188
    def _process_get(self, msg: Message) -> None:
        worker = self._zoo.rank_to_worker_id(msg.src)
        if (self._add_clocks.local_clock(worker)
                > self._add_clocks.global_clock
                or self._num_waited_add[worker] > 0):
            self._get_cache.append(msg)
            return
        try:
            super()._process_get(msg)
        finally:
            if self._get_clocks.update(worker):
                self._drain_caches(adds=True)

    # ref: src/server.cpp:190-213
    def _process_finish_train(self, msg: Message) -> None:
        worker = self._zoo.rank_to_worker_id(msg.src)
        if self._add_clocks.finish_train(worker):
            if self._num_backup == 0:
                assert not self._add_cache
            self._drain_caches(gets=True)
        if self._get_clocks.finish_train(worker):
            if self._num_backup == 0:
                assert not self._get_cache
            self._drain_caches(adds=True)

    def _drain_caches(self, gets: bool = False, adds: bool = False) -> None:
        """Drain the requested cache(s); when a drained request levels
        the OTHER clock (possible only under a straggler cutoff, where
        a late tick can move the global clock several steps), alternate
        into the other cache until both settle. Strict BSP keeps the
        reference's single-pass behavior and its no-releveling
        invariant."""
        while gets or adds:
            if gets:
                gets = False
                while self._get_cache:
                    get_msg = self._get_cache.popleft()
                    worker = self._zoo.rank_to_worker_id(get_msg.src)
                    # A raising drained request already sent its error
                    # reply; swallow here (with the log line
                    # Server._process_* emitted via its raise path
                    # unavailable, log directly) so the rest of the
                    # cache still drains and the clocks stay level.
                    try:
                        Server._process_get(self, get_msg)
                    except Exception:  # noqa: BLE001
                        log.error("sync server: drained get failed "
                                  "(error reply sent)")
                    leveled = self._get_clocks.update(worker)
                    if self._num_backup == 0:
                        assert not leveled
                    elif leveled:
                        adds = True
            elif adds:
                adds = False
                while self._add_cache:
                    add_msg = self._add_cache.popleft()
                    worker = self._zoo.rank_to_worker_id(add_msg.src)
                    try:
                        Server._process_add(self, add_msg)
                    except Exception:  # noqa: BLE001
                        log.error("sync server: drained add failed "
                                  "(error reply sent)")
                    leveled = self._add_clocks.update(worker)
                    if self._num_backup == 0:
                        assert not leveled
                    elif leveled:
                        gets = True
                    self._num_waited_add[worker] -= 1

"""Epoch-stamped dynamic shard maps + live row migration (ISSUE 12).

Extension over the reference: Multiverso freezes the row→server layout
at table creation (``row_offsets`` in sharding/rows.py, ref:
matrix_table.cpp:23-45) — a production PS must absorb a new server or
drain a retiring one without a stop-the-world. This module supplies the
three coordinated pieces (full protocol spec in docs/SHARDING.md,
"Elastic resharding"):

* :class:`ShardMap` — an epoch-stamped interval map ``row →
  owner server id``. Epoch 0 reproduces the frozen ``row_offsets``
  layout bit-for-bit (so a never-resharded cluster routes exactly as
  before); every committed migration bumps the epoch and the rank-0
  controller broadcasts the whole map (``Control_Shard_Map``, the
  PR-7 ``Control_Replica_Map`` pattern — stale epochs are ignored by
  every consumer).
* :class:`MigrationOut` / :class:`MigrationIn` — the per-table source/
  destination state machines for one live range move: the source
  streams the range in seq-numbered chunks (the point-to-point
  schedule of the portable-collective redistribution formulation,
  arxiv 2112.01075) while still serving; rows an Add touches after
  their chunk left re-stream inside the FINAL chunk, whose send
  atomically flips the source into a dual-read/forwarding window
  (single actor thread — no lock needed). The destination detects
  chunk loss by seq gap at the final chunk and requests retransmits;
  only a complete range commits.
Concurrency note (mvlint pass 10): this module carries NO
``guarded_by`` annotations on purpose — the map and both migration
state machines are confined to their owning actor thread (map applies
on the worker/server actor, migrations run on the server actor,
planning on the controller actor), so the discipline here is
single-thread confinement, not locking.

* :class:`ReshardManager` — the controller-side coordinator: plans a
  minimal move list toward an even spread over the requested active
  servers (or, with ``-reshard_auto``, splits skewed ranges from the
  PR-7 ``HotTracker`` load reports), drives one move at a time,
  commits an epoch on the destination's ``Control_Shard_Done``, and
  rolls back (``Request_ShardAbort``) when either endpoint dies
  mid-handoff — the map never advances past a partial move, so every
  failure lands in a consistent epoch.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..sharding.rows import row_offsets
from ..util import log
from ..util.configure import define_bool, define_double, define_int, get_flag

define_int("reshard_chunk_rows", 4096,
           "rows per Request_ShardData chunk while a live migration "
           "streams a range between servers (smaller = finer "
           "interleaving with serving traffic, more per-chunk overhead)")
define_bool("reshard_auto", False,
            "closed-loop rebalancing: dense matrix servers report their "
            "HotTracker load windows to the controller even without "
            "replication, and the controller moves the hottest half of "
            "an overloaded server's hottest range to the coldest server "
            "whenever one server carries more than -reshard_skew times "
            "the mean load (docs/SHARDING.md)")
define_double("reshard_skew", 2.0,
              "load-skew trigger for -reshard_auto: a server whose "
              "decayed Get load exceeds this multiple of the mean "
              "across servers gets a range split off")
define_int("shard_initial_servers", 0,
           "create row/bucket-sharded tables over only the FIRST this "
           "many servers; the rest start as standbys that own no rows "
           "until a reshard migrates ranges onto them (the elastic "
           "grow story, docs/SHARDING.md). 0 (default) = all servers, "
           "the frozen reference layout")

def initial_active_servers(num_servers: int) -> int:
    """How many servers newly created elastic tables spread over
    (``-shard_initial_servers``, clamped; 0 = all)."""
    k = int(get_flag("shard_initial_servers", 0))
    if k <= 0:
        return num_servers
    return min(k, num_servers)


class ShardMap:
    """Interval map ``item id -> owner server id`` with an epoch stamp.

    ``bounds`` is a sorted int64 vector ``[0, b1, ..., num_items]``;
    ``owners[i]`` serves ``[bounds[i], bounds[i+1])``. Immutable —
    ``move`` returns a new map with the next epoch.
    """

    def __init__(self, bounds: np.ndarray, owners: np.ndarray,
                 epoch: int = 0):
        self.bounds = np.asarray(bounds, dtype=np.int64)
        self.owners = np.asarray(owners, dtype=np.int64)
        self.epoch = int(epoch)
        assert self.bounds.size == self.owners.size + 1

    @property
    def num_items(self) -> int:
        return int(self.bounds[-1])

    @classmethod
    def initial(cls, num_items: int, num_servers: int,
                active: Optional[int] = None) -> "ShardMap":
        """Epoch-0 map reproducing the frozen ``row_offsets`` layout
        over the first ``active`` servers (default: all) — a
        never-resharded cluster routes bit-identically to the
        reference's static split."""
        n = int(num_servers) if active is None \
            else min(int(active), int(num_servers))
        offsets = row_offsets(int(num_items), max(n, 1))
        bounds = np.asarray(offsets, dtype=np.int64)
        owners = np.arange(bounds.size - 1, dtype=np.int64)
        return cls(bounds, owners, epoch=0)

    def owner_of(self, keys: np.ndarray) -> np.ndarray:
        """Vectorized item ids -> owner server ids."""
        keys = np.asarray(keys)
        idx = np.searchsorted(self.bounds, keys, side="right") - 1
        idx = np.clip(idx, 0, self.owners.size - 1)
        return self.owners[idx]

    def intervals_of(self, sid: int) -> List[Tuple[int, int]]:
        return [(int(self.bounds[i]), int(self.bounds[i + 1]))
                for i in range(self.owners.size)
                if int(self.owners[i]) == int(sid)]

    def owner_sids(self) -> List[int]:
        return sorted({int(s) for s in self.owners})

    def move(self, lo: int, hi: int, dst: int) -> "ShardMap":
        """New map (epoch+1) with ``[lo, hi)`` owned by ``dst``;
        adjacent same-owner intervals coalesce so the map stays small
        over many migrations."""
        lo, hi = int(lo), int(hi)
        assert 0 <= lo < hi <= self.num_items
        cuts = np.unique(np.concatenate(
            [self.bounds, np.asarray([lo, hi], dtype=np.int64)]))
        owners = self.owner_of(cuts[:-1]).copy()
        owners[(cuts[:-1] >= lo) & (cuts[:-1] < hi)] = int(dst)
        keep = np.concatenate(
            [[True], owners[1:] != owners[:-1]])
        bounds = np.concatenate([cuts[:-1][keep], cuts[-1:]])
        return ShardMap(bounds, owners[keep], epoch=self.epoch + 1)

    def diff_moved(self, newer: "ShardMap") -> List[Tuple[int, int, int, int]]:
        """Intervals whose owner changed between self and ``newer``:
        ``[(lo, hi, old_sid, new_sid), ...]`` (consumers invalidate
        caches / prune replicas for exactly these)."""
        cuts = np.unique(np.concatenate([self.bounds, newer.bounds]))
        old = self.owner_of(cuts[:-1])
        new = newer.owner_of(cuts[:-1])
        out: List[Tuple[int, int, int, int]] = []
        for i in range(cuts.size - 1):
            if old[i] != new[i]:
                lo, hi = int(cuts[i]), int(cuts[i + 1])
                if out and out[-1][1] == lo \
                        and out[-1][2] == int(old[i]) \
                        and out[-1][3] == int(new[i]):
                    out[-1] = (out[-1][0], hi, int(old[i]), int(new[i]))
                else:
                    out.append((lo, hi, int(old[i]), int(new[i])))
        return out

    # -- wire payload (Control_Shard_Map; docs/WIRE_FORMAT.md) --
    def pack(self, table_id: int, alive_sids: List[int]) -> List[np.ndarray]:
        """``[desc, bounds, owners, alive]`` int64 blobs; desc =
        [table_id, epoch, n_intervals, num_items, n_alive]. The alive
        vector is the controller's authoritative live-server view —
        workers reconcile their replica routers' dead marks against it
        on every broadcast (docs/SHARDING.md)."""
        alive = np.asarray(sorted(alive_sids), dtype=np.int64)
        desc = np.asarray([int(table_id), self.epoch, self.owners.size,
                           self.num_items, alive.size], dtype=np.int64)
        return [desc, self.bounds, self.owners, alive]

    @classmethod
    def unpack(cls, blobs) -> Tuple[int, "ShardMap", np.ndarray]:
        desc = np.asarray(blobs[0], dtype=np.int64)
        table_id, epoch = int(desc[0]), int(desc[1])
        bounds = np.asarray(blobs[1], dtype=np.int64)
        owners = np.asarray(blobs[2], dtype=np.int64)
        alive = np.asarray(blobs[3], dtype=np.int64) \
            if len(blobs) >= 4 else np.empty(0, np.int64)
        return table_id, cls(bounds, owners, epoch=epoch), alive


def plan_moves(current: ShardMap,
               active_sids: List[int]) -> List[Tuple[int, int, int, int]]:
    """Minimal move list ``[(lo, hi, src_sid, dst_sid)]`` carrying
    ``current`` to an even contiguous spread over ``active_sids`` (in
    sid order — the target layout is ``row_offsets`` over the active
    set, so growing back to the full fleet restores the frozen
    reference layout exactly)."""
    sids = sorted({int(s) for s in active_sids})
    if not sids:
        return []
    offsets = row_offsets(current.num_items, len(sids))
    target = ShardMap(np.asarray(offsets, dtype=np.int64),
                      np.asarray([sids[i] for i in range(len(offsets) - 1)],
                                 dtype=np.int64))
    return [(lo, hi, src, dst)
            for lo, hi, src, dst in current.diff_moved(target)]


# ---------------------------------------------------------------------------
# migration state machines (server actor thread only — no locking)
# ---------------------------------------------------------------------------

class MigrationOut:
    """Source-side state for one outbound range move.

    The source keeps serving while chunks stream; Adds landing on rows
    whose chunk already left go into ``dirty`` and ride the FINAL
    chunk, so the handoff instant (final chunk composed and sent on
    the actor thread) hands the destination a value set that includes
    every Add the source ever applied to the range."""

    def __init__(self, table_id: int, lo: int, hi: int, src_sid: int,
                 dst_sid: int, dst_rank: int, epoch: int):
        self.table_id = int(table_id)
        self.lo, self.hi = int(lo), int(hi)
        self.src_sid, self.dst_sid = int(src_sid), int(dst_sid)
        self.dst_rank = int(dst_rank)
        self.epoch = int(epoch)
        chunk = max(int(get_flag("reshard_chunk_rows")), 1)
        #: seq -> (chunk_lo, chunk_hi); the final dirty-drain chunk is
        #: appended at handoff (row list, not a range).
        self.chunks: List[Tuple[int, int]] = [
            (c_lo, min(c_lo + chunk, self.hi))
            for c_lo in range(self.lo, self.hi, chunk)]
        self.next_seq = 0
        self.sent_hi = self.lo      # rows < sent_hi have left
        self.dirty: set = set()     # re-dirtied already-sent rows
        self.final_sent = False
        self.final_rows: Optional[np.ndarray] = None  # retransmit rows
        #: Set when the controller re-sends Begin AFTER the handoff —
        #: its view of the move is stalled (a lost Control_Shard_Done,
        #: with no destination traffic to ride the re-announce on):
        #: the next pump re-sends the FINAL chunk from the frozen
        #: snapshot, which re-triggers the destination's Done.
        self.resend_final = False
        #: Handoff-time value snapshot of the WHOLE range, captured in
        #: the same actor step that composes the final chunk:
        #: retransmits must re-send exactly what the destination's
        #: ledger expects — the source's live copy keeps moving after
        #: the handoff (forwarded Adds both-apply there), and a
        #: re-gather from it would double-apply every Add the
        #: destination already ledgered against the lost chunk. Keyed
        #: storage is table-specific; the table sets it at handoff and
        #: serves chunk values from it in ``shard_ack``.
        self.frozen = None

    @property
    def streaming(self) -> bool:
        return not self.final_sent

    def note_add(self, keys: np.ndarray) -> None:
        """Rows in the moving range that an Add touched after their
        chunk left must re-stream in the final chunk."""
        if self.final_sent:
            return
        sent = keys[(keys >= self.lo) & (keys < self.sent_hi)]
        if sent.size:
            self.dirty.update(int(k) for k in sent.tolist())

    def next_chunk(self) -> Optional[Tuple[int, np.ndarray, bool]]:
        """``(seq, rows, is_final)`` for the next chunk to send, or
        None when the final already left. The final chunk drains the
        dirty set — the caller flips into forwarding the moment it is
        handed out (same actor-thread step)."""
        if self.final_sent:
            return None
        if self.next_seq < len(self.chunks):
            c_lo, c_hi = self.chunks[self.next_seq]
            seq = self.next_seq
            self.next_seq += 1
            self.sent_hi = c_hi
            return seq, np.arange(c_lo, c_hi, dtype=np.int64), False
        rows = np.asarray(sorted(self.dirty), dtype=np.int64)
        self.dirty.clear()
        self.final_sent = True
        self.final_rows = rows
        return len(self.chunks), rows, True

    def rows_of_seq(self, seq: int) -> Optional[np.ndarray]:
        """Row set of a chunk, for retransmission (the source's values
        are frozen once the final left, so a regather is exact)."""
        if 0 <= seq < len(self.chunks):
            c_lo, c_hi = self.chunks[seq]
            return np.arange(c_lo, c_hi, dtype=np.int64)
        if seq == len(self.chunks) and self.final_rows is not None:
            return self.final_rows
        return None


class MigrationIn:
    """Destination-side state for one inbound range move: seq
    bookkeeping (loss detection by gap at the final chunk), and the
    pending-commit resend loop (the ``Control_Shard_Done`` toward the
    controller re-announces on traffic until the committed map
    broadcast confirms it landed — a chaos-dropped commit must not
    strand a completed migration)."""

    def __init__(self, epoch: int, src_sid: int, src_rank: int,
                 lo: int, hi: int):
        self.epoch = int(epoch)
        self.src_sid, self.src_rank = int(src_sid), int(src_rank)
        self.lo, self.hi = int(lo), int(hi)
        self.applied: set = set()
        self.n_chunks: Optional[int] = None  # known at the final chunk
        #: Items the FINAL chunk delivered: they carry the handoff-time
        #: values of every dirty row/bucket, which are NEWER than any
        #: base chunk's copy — a reorder-delayed base chunk arriving
        #: after the final must not overwrite them (seq dedup only
        #: protects exact retransmits, not this overlap).
        self.final_items: Optional[set] = None
        self.src_version = -1
        self.complete = False
        self.last_announce = 0.0

    def note_applied(self, seq: int) -> bool:
        """True when this seq is new (duplicates/retransmits of an
        already-applied chunk are dropped — a late copy must not
        overwrite forwarded Adds applied since)."""
        if seq in self.applied:
            return False
        self.applied.add(seq)
        return True

    def missing_seqs(self) -> List[int]:
        if self.n_chunks is None:
            return []
        return [s for s in range(self.n_chunks + 1)
                if s not in self.applied]

    def check_complete(self) -> bool:
        self.complete = (self.n_chunks is not None
                         and not self.missing_seqs())
        return self.complete


class ElasticServerMixin:
    """The table-type-independent half of the server-side migration
    protocol, shared by MatrixServer and KVServer (the item space —
    rows vs hash buckets — and the storage moves are table-specific;
    everything that is pure protocol lives here exactly once, so a
    protocol fix cannot drift between the two).

    Expects on self: ``_zoo``, ``table_id``, ``server_id``, ``_fwd``
    (list of ``(lo, hi, dst_sid, dst_rank)`` windows), ``_mig_out``,
    ``_mig_in`` and ``_fwd_inflight`` (initialized by the table), plus
    a ``_shard_data_message(mig, seq, items, is_final)`` builder."""

    def _fwd_route(self, items: np.ndarray):
        """Per-item dual-read window lookup: (mask, dst_sid, dst_rank)
        with -1 where an item is not inside any forwarding window."""
        mask = np.zeros(items.size, dtype=bool)
        dst_sid = np.full(items.size, -1, dtype=np.int64)
        dst_rank = np.full(items.size, -1, dtype=np.int64)
        for lo, hi, sid, rank in self._fwd:
            m = (items >= lo) & (items < hi)
            mask |= m
            dst_sid[m] = sid
            dst_rank[m] = rank
        return mask, dst_sid, dst_rank

    def _note_fwd_inflight(self, src_rank: int, msg_id: int,
                           is_get: bool) -> List:
        """Returns error replies for entries EVICTED past the cap: a
        silently dropped entry whose request is still waiting when the
        window's destination dies would hang forever (the ledger's
        whole reason to exist). A spurious error reply for a request
        the destination already answered is a no-op at the requester,
        so failing evictees retryably is always safe."""
        if msg_id < 0:
            return []
        self._fwd_inflight.append((int(src_rank), int(msg_id), is_get))
        if len(self._fwd_inflight) <= 4096:
            return []
        evicted = self._fwd_inflight[:2048]
        del self._fwd_inflight[:2048]
        return self._fail_fwd_entries(evicted)

    def _drain_fwd_inflight(self) -> List:
        """Retryable error replies for every request forwarded into a
        window that just rolled back: the destination died holding
        them, and the requester's in-flight accounting keys on THIS
        rank (the impersonation contract) — without these replies its
        waiters block forever. Replies for requests the destination
        already answered are no-ops at the requester (completed
        waiters ignore late notifies)."""
        drained, self._fwd_inflight = self._fwd_inflight, []
        return self._fail_fwd_entries(drained)

    def _fail_fwd_entries(self, entries) -> List:
        from ..core.message import (Message, MsgType, PEER_LOST_MARK,
                                    mark_error)
        out: List = []
        for src_rank, msg_id, is_get in entries:
            reply = Message(src=self._zoo.rank, dst=src_rank,
                            msg_type=MsgType.Reply_Get if is_get
                            else MsgType.Reply_Add,
                            table_id=self.table_id, msg_id=msg_id)
            mark_error(reply, RuntimeError(
                f"{PEER_LOST_MARK} forwarded into a migration window "
                f"that cannot confirm delivery — re-issue"))
            out.append(reply)
        return out

    def _announce_done(self, mig) -> List:
        import time
        from ..core.blob import Blob
        from ..core.message import Message, MsgType
        from .zoo import CONTROLLER_RANK
        mig.last_announce = time.monotonic()
        msg = Message(src=self._zoo.rank, dst=CONTROLLER_RANK,
                      msg_type=MsgType.Control_Shard_Done,
                      table_id=self.table_id)
        msg.push(Blob(np.asarray([mig.epoch, 1, self.server_id],
                                 dtype=np.int64)))
        return [msg]

    def _retransmit_request(self, mig) -> List:
        import time
        from ..core.blob import Blob
        from ..core.message import Message, MsgType
        mig.last_announce = time.monotonic()
        missing = mig.missing_seqs()
        log.error("rank %d: migration epoch %d missing chunk seq(s) "
                  "%s — requesting retransmit", self._zoo.rank,
                  mig.epoch, missing)
        msg = Message(src=self._zoo.rank, dst=mig.src_rank,
                      msg_type=MsgType.Request_ShardAck,
                      table_id=self.table_id)
        msg.push(Blob(np.asarray(
            [mig.epoch, self.server_id] + missing, dtype=np.int64)))
        return [msg]

    def shard_announce(self) -> List:
        """Traffic-driven resend of a pending commit / retransmit
        request (a chaos-dropped Control_Shard_Done must not strand a
        completed migration; docs/SHARDING.md)."""
        import time
        out: List = []
        now = time.monotonic()
        for mig in self._mig_in.values():
            if now - mig.last_announce < 1.0:
                continue
            if mig.complete:
                out.extend(self._announce_done(mig))
            elif mig.n_chunks is not None:
                out.extend(self._retransmit_request(mig))
        return out

    def shard_ack(self, msg) -> List:
        """Retransmit from the HANDOFF-TIME frozen snapshot, never the
        live copy: forwarded Adds keep both-applying to the source
        after the handoff, and a live re-gather would double-apply
        every Add the destination ledgered against the lost chunk."""
        desc = msg.data[0].as_array(np.int64)
        mig = self._mig_out
        if mig is None or mig.epoch != int(desc[0]):
            return []
        out: List = []
        for seq in (int(x) for x in desc[2:]):
            items = mig.rows_of_seq(seq)
            if items is not None:
                from ..util.dashboard import count as _count
                _count("SHARD_RETRANSMIT")
                out.append(self._shard_data_message(
                    mig, seq, items, seq == len(mig.chunks)))
        return out

    def _freeze_range(self, mig):
        """Handoff-time value snapshot of the whole range (table-
        specific storage gather)."""
        raise NotImplementedError

    def shard_pump(self):
        """One streaming step: ``(outbound messages, more)``. The
        server actor re-enqueues a pump message while ``more`` so
        serving traffic interleaves between chunks. After the handoff,
        a pump only fires to re-send the final chunk when the
        controller's Begin-resend flagged the move as stalled."""
        from ..util import chaos
        mig = self._mig_out
        if mig is None:
            return [], False
        if mig.final_sent:
            if mig.resend_final:
                mig.resend_final = False
                items = mig.rows_of_seq(len(mig.chunks))
                if items is not None:
                    return [self._shard_data_message(
                        mig, len(mig.chunks), items, True)], False
            return [], False
        seq, items, is_final = mig.next_chunk()
        if is_final:
            chaos.kill_point("shard_source_final")
        else:
            chaos.kill_point("shard_source_chunk")
        if is_final:
            # Snapshot BEFORE the final chunk is built (same actor
            # step — nothing interleaves): retransmits and stalled-
            # commit re-sends serve from it, never the live copy.
            frozen = self._freeze_range(mig)
        msg = self._shard_data_message(mig, seq, items, is_final)
        if is_final:
            # HANDOFF, atomically with composing the final chunk: from
            # the next message on, Adds for the range both-apply and
            # forward, Gets forward — per-destination FIFO orders
            # everything after the final chunk at the destination.
            mig.frozen = frozen
            self._fwd.append((mig.lo, mig.hi, mig.dst_sid,
                              mig.dst_rank))
        return [msg], not is_final

    def _prune_fwd_windows(self, lo: int, hi: int) -> None:
        """Items in [lo, hi) came (back) to this shard: clip every
        forwarding window out of the range (partial overlaps split)."""
        pruned: List = []
        for flo, fhi, fsid, frank in self._fwd:
            if fhi <= lo or flo >= hi:
                pruned.append((flo, fhi, fsid, frank))
                continue
            if flo < lo:
                pruned.append((flo, lo, fsid, frank))
            if fhi > hi:
                pruned.append((hi, fhi, fsid, frank))
        self._fwd = pruned


# ---------------------------------------------------------------------------
# controller-side coordinator (controller actor thread only)
# ---------------------------------------------------------------------------

class PendingMove:
    def __init__(self, table_id: int, lo: int, hi: int, src_sid: int,
                 dst_sid: int, epoch: int):
        self.table_id = int(table_id)
        self.lo, self.hi = int(lo), int(hi)
        self.src_sid, self.dst_sid = int(src_sid), int(dst_sid)
        self.epoch = int(epoch)


class ReshardManager:
    """Controller-side elastic-resharding coordinator.

    Owns the authoritative per-table :class:`ShardMap`, a queue of
    planned moves, and at most ONE in-flight move cluster-wide (the
    dual-read window and the rollback story are per-move; serializing
    keeps every failure mode a single-migration failure). All entry
    points run on the controller ACTOR thread — the heartbeat monitor
    nudges via a local ``Control_Shard_Tick`` message, never directly
    (the ``Control_Check_Barriers`` precedent)."""

    def __init__(self, zoo):
        self._zoo = zoo
        self.maps: Dict[int, ShardMap] = {}
        self._queue: List[Tuple[int, int, int, int, int]] = []
        self._pending: Optional[PendingMove] = None
        #: decayed per-(table, sid) load + hottest row per table
        #: (-reshard_auto; fed from Control_Replica_Report windows).
        self._loads: Dict[int, Dict[int, float]] = {}
        self._hot_rows: Dict[int, Dict[int, int]] = {}
        self._report_rounds: Dict[int, int] = {}
        self._num_items: Dict[int, int] = {}
        self._last_begin = 0.0
        self._last_broadcast = 0.0

    # -- planning --
    def request(self, table_id: int, num_items: int,
                active_sids: List[int]) -> None:
        """An application asked for this table spread over
        ``active_sids`` (``Zoo.reshard_table``): plan the move list
        from the current map and start draining it."""
        if get_flag("sync", False):
            log.error("controller: reshard of table %d refused — BSP "
                      "sync mode pins the frozen shard map (the sync "
                      "server's vector clocks count requests per "
                      "server)", table_id)
            return
        current = self.maps.get(int(table_id))
        if current is None:
            current = ShardMap.initial(
                int(num_items), self._zoo.num_servers,
                active=initial_active_servers(self._zoo.num_servers))
            self.maps[int(table_id)] = current
        self._num_items[int(table_id)] = current.num_items
        # Plan from the PROJECTED map — the committed state plus every
        # move still queued or in flight for this table: a second
        # request arriving mid-plan must extend the schedule, not fight
        # it (stale-source moves would be refused and roll the whole
        # plan back).
        projected = current
        for t, lo, hi, src, dst in self._queue:
            if t == int(table_id):
                projected = projected.move(lo, hi, dst)
        p = self._pending
        if p is not None and p.table_id == int(table_id):
            projected = projected.move(p.lo, p.hi, p.dst_sid)
        n = 0
        for lo, hi, src, dst in plan_moves(projected, active_sids):
            self._queue.append((int(table_id), lo, hi, src, dst))
            n += 1
        log.info("controller: reshard table %d over %s: %d move(s) "
                 "queued", table_id, sorted(active_sids), n)
        self.kick()

    def note_report(self, table_id: int, src_sid: int,
                    rows: np.ndarray, counts: np.ndarray,
                    num_items: int = -1) -> None:
        """A server's HotTracker window (-reshard_auto): decayed
        per-server load; a skew past -reshard_skew plans a split of
        the overloaded server's hottest range toward the coldest
        server."""
        if not bool(get_flag("reshard_auto")) or get_flag("sync", False):
            return
        table_id, src_sid = int(table_id), int(src_sid)
        if num_items > 0:
            self._num_items.setdefault(table_id, int(num_items))
        loads = self._loads.setdefault(table_id, {})
        loads[src_sid] = loads.get(src_sid, 0.0) / 2.0 \
            + float(counts.sum())
        if rows.size:
            hot = self._hot_rows.setdefault(table_id, {})
            hot[src_sid] = int(rows[int(np.argmax(counts))])
        self._report_rounds[table_id] = \
            self._report_rounds.get(table_id, 0) + 1
        self._maybe_split(table_id)

    def _maybe_split(self, table_id: int) -> None:
        if self._pending is not None or self._queue:
            return
        if self._report_rounds.get(table_id, 0) < 3:
            # One early window must not trigger a migration: silent
            # servers read as zero load by design (standbys ARE
            # zero-load), so wait until a few windows establish the
            # shape before acting.
            return
        loads = self._loads.get(table_id, {})
        if len(loads) < 2:
            # One reporter so far: compare against the full fleet (a
            # silent server carries zero load by definition).
            for sid in range(self._zoo.num_servers):
                loads.setdefault(sid, 0.0)
            if len(loads) < 2:
                return
        mean = sum(loads.values()) / len(loads)
        hot_sid = max(loads, key=loads.get)
        if mean <= 0 or loads[hot_sid] < float(
                get_flag("reshard_skew")) * mean:
            return
        num_items = self._num_items.get(table_id)
        if num_items is None:
            return
        current = self.maps.get(table_id)
        if current is None:
            current = self.maps[table_id] = ShardMap.initial(
                num_items, self._zoo.num_servers,
                active=initial_active_servers(self._zoo.num_servers))
        intervals = current.intervals_of(hot_sid)
        if not intervals:
            return
        hot_row = self._hot_rows.get(table_id, {}).get(hot_sid)
        # The interval holding the hottest row (fallback: the widest).
        pick = max(intervals, key=lambda iv: iv[1] - iv[0])
        if hot_row is not None:
            for lo, hi in intervals:
                if lo <= hot_row < hi:
                    pick = (lo, hi)
                    break
        lo, hi = pick
        if hi - lo < 2:
            return
        cold_sid = min(loads, key=loads.get)
        if cold_sid == hot_sid:
            return
        mid = (lo + hi) // 2
        # Keep the half holding the hottest row AT the (tracked) hot
        # server and move the other half: ownership moves the load the
        # reports cannot attribute, the hot head stays put.
        move = (mid, hi) if (hot_row is None or hot_row < mid) \
            else (lo, mid)
        log.info("controller: auto-reshard table %d — server %d load "
                 "%.0f > %.1fx mean %.0f, moving [%d,%d) to server %d",
                 table_id, hot_sid, loads[hot_sid],
                 float(get_flag("reshard_skew")), mean,
                 move[0], move[1], cold_sid)
        self._queue.append((table_id, move[0], move[1], hot_sid,
                            cold_sid))
        self.kick()

    # -- drive --
    def kick(self) -> None:
        """Start the next queued move if none is in flight."""
        if self._pending is not None or not self._queue:
            return
        table_id, lo, hi, src, dst = self._queue.pop(0)
        current = self.maps[table_id]
        self._pending = PendingMove(table_id, lo, hi, src, dst,
                                    current.epoch + 1)
        self._send_begin()

    def _send_begin(self) -> None:
        import time
        from ..core.blob import Blob
        from ..core.message import Message, MsgType
        from . import actor as actors
        p = self._pending
        src_rank = self._zoo.server_rank(p.src_sid)
        dst_rank = self._zoo.server_rank(p.dst_sid)
        if src_rank < 0 or dst_rank < 0:
            log.error("controller: reshard move for table %d names "
                      "unknown server ids (%d -> %d) — abandoned",
                      p.table_id, p.src_sid, p.dst_sid)
            self._abandon("unknown server id")
            return
        msg = Message(src=self._zoo.rank, dst=src_rank,
                      msg_type=MsgType.Request_ShardBegin,
                      table_id=p.table_id)
        msg.push(Blob(np.asarray(
            [p.lo, p.hi, p.src_sid, p.dst_sid, dst_rank, p.epoch,
             self.maps[p.table_id].num_items], dtype=np.int64)))
        self._last_begin = time.monotonic()
        self._zoo.send_to(actors.COMMUNICATOR, msg)

    def on_done(self, table_id: int, epoch: int, ok: bool) -> None:
        """The destination committed (ok) or either endpoint refused
        (not ok): advance the map + broadcast, or roll the whole plan
        back to the current (consistent) epoch."""
        p = self._pending
        if p is None or p.table_id != int(table_id) \
                or p.epoch != int(epoch):
            return  # stale/duplicate Done (the dest re-announces)
        if not ok:
            log.error("controller: migration of table %d [%d,%d) -> "
                      "server %d refused/failed — rolled back at epoch "
                      "%d", p.table_id, p.lo, p.hi, p.dst_sid,
                      self.maps[p.table_id].epoch)
            self._abandon("endpoint refused")
            return
        self.maps[p.table_id] = self.maps[p.table_id].move(
            p.lo, p.hi, p.dst_sid)
        log.info("controller: table %d shard map epoch %d — [%d,%d) "
                 "now on server %d", p.table_id,
                 self.maps[p.table_id].epoch, p.lo, p.hi, p.dst_sid)
        self._pending = None
        self.broadcast(p.table_id)
        self.kick()

    def _abandon(self, reason: str) -> None:
        p, self._pending = self._pending, None
        if p is not None:
            self._queue = [m for m in self._queue if m[0] != p.table_id]

    def on_peer_dead(self, rank: int) -> None:
        """A rank was declared dead. If the in-flight move touches it,
        the move rolls back: the survivor gets a Request_ShardAbort
        (the source resumes ownership / the destination drops partial
        state) and the map stays at the pre-move epoch."""
        p = self._pending
        if p is None:
            return
        dead_sid = self._zoo.rank_to_server_id(rank)
        if dead_sid not in (p.src_sid, p.dst_sid):
            return
        survivor_sid = p.dst_sid if dead_sid == p.src_sid else p.src_sid
        log.error("controller: server %d died mid-migration of table "
                  "%d [%d,%d) — rolling back to epoch %d, aborting at "
                  "server %d", dead_sid, p.table_id, p.lo, p.hi,
                  self.maps[p.table_id].epoch, survivor_sid)
        self._send_abort(p, survivor_sid)
        self._abandon("endpoint died")
        # Re-broadcast the (unchanged) map: every rank re-anchors on
        # the pre-move epoch — the 'rolled back' consistent state.
        self.broadcast(p.table_id)

    def _send_abort(self, p: PendingMove, sid: int) -> None:
        from ..core.blob import Blob
        from ..core.message import Message, MsgType
        from . import actor as actors
        rank = self._zoo.server_rank(sid)
        if rank < 0:
            return
        msg = Message(src=self._zoo.rank, dst=rank,
                      msg_type=MsgType.Request_ShardAbort,
                      table_id=p.table_id)
        msg.push(Blob(np.asarray([p.epoch], dtype=np.int64)))
        self._zoo.send_to(actors.COMMUNICATOR, msg)

    def tick(self) -> None:
        """Heartbeat-driven nudge (controller actor thread): re-send a
        possibly-lost Begin, and re-broadcast current maps so workers
        partitioned away from a commit converge (broadcasts are
        idempotent — stale epochs are ignored; throttled so a chatty
        tick never floods the cluster)."""
        import time
        if self._pending is not None \
                and time.monotonic() - self._last_begin > max(
                    float(get_flag("heartbeat_interval_s", 0.0)), 1.0):
            self._send_begin()  # idempotent at the source
        if time.monotonic() - self._last_broadcast >= 2.0:
            for table_id in list(self.maps):
                self.broadcast(table_id)

    def broadcast(self, table_id: int) -> None:
        """Fan the table's current map to every live rank (the
        Control_Replica_Map pattern: cloned to worker AND server actors
        by the communicator's routing; stale epochs ignored).

        Remote copies ride ``net.send_async`` — the PR-6 liveness-frame
        lesson, now lint-enforced: a BLOCKING send toward a dead or
        restarting rank parks the sender up to ``-connect_timeout_s``,
        and broadcasts from the controller actor would wedge every
        later control message behind it. Declared-dead ranks are
        skipped outright (their rejoin re-register gets a fresh
        broadcast); the local rank delivers through the communicator's
        forward path (a mailbox push, never blocks)."""
        import time
        from ..core.blob import Blob
        from ..core.message import Message, MsgType
        from . import actor as actors
        smap = self.maps.get(int(table_id))
        if smap is None:
            return
        self._last_broadcast = time.monotonic()
        alive = self.alive_sids()
        dead_ranks = self._dead_ranks()
        blobs = smap.pack(table_id, alive)
        for dst in range(self._zoo.net_size):
            if dst in dead_ranks:
                continue
            msg = Message(src=self._zoo.rank, dst=dst,
                          msg_type=MsgType.Control_Shard_Map,
                          table_id=int(table_id))
            for arr in blobs:
                msg.push(Blob(arr.copy()))
            if dst == self._zoo.rank:
                self._zoo.send_to(actors.COMMUNICATOR, msg)
                continue
            try:
                self._zoo.net.send_async(msg)
            except Exception as exc:  # noqa: BLE001 - an unreachable
                # rank re-anchors from the next broadcast or its
                # rejoin; its failure must not kill the controller.
                log.debug("controller: shard-map broadcast to rank %d "
                          "failed: %s", dst, exc)

    def broadcast_all(self) -> None:
        for table_id in list(self.maps):
            self.broadcast(table_id)

    def _dead_ranks(self) -> set:
        from . import actor as actors
        controller = self._zoo._actors.get(actors.CONTROLLER)
        if controller is None:
            return set()
        with controller._live_lock:
            return set(controller._declared_dead)

    def alive_sids(self) -> List[int]:
        """Server ids the controller currently believes alive — the
        authoritative liveness view the broadcast carries so replica
        routers re-validate their dead marks (docs/SHARDING.md)."""
        from . import actor as actors
        controller = self._zoo._actors.get(actors.CONTROLLER)
        dead_ranks: set = set()
        if controller is not None:
            with controller._live_lock:
                dead_ranks = set(controller._declared_dead)
        return [s for s in range(self._zoo.num_servers)
                if self._zoo.server_rank(s) not in dead_ranks]

"""Hot-shard read replication (worker/server/controller shared pieces).

Extension over the reference: the paper's row-sharded tables pay a
coordination cost per additional server, yet word2vec Get traffic is
Zipf-skewed ("Sparse Allreduce for Power-Law Data", arxiv 1312.3020, and
SparCML, arxiv 1802.08021 — PAPERS.md), so a handful of HEAD rows
dominate load. This module implements the standard fix: replicate the
head rows for reads.

Protocol (full spec in docs/SHARDING.md):

* every dense matrix server tracks per-row Get rates (``HotTracker``)
  and reports its top rows to the rank-0 controller every
  ``-replica_report_gets`` row-Get requests (``Control_Replica_Report``);
* the controller aggregates the reports with exponential decay,
  promotes the globally hottest ``-replica_hot_rows`` rows (per table)
  and broadcasts a versioned promoted-row map to every rank
  (``Control_Replica_Map``) whenever the set changes — rows that cool
  below the threshold fall out of the map (demotion);
* OWNER servers push value refreshes for their promoted rows to every
  other server (``Request_ReplicaSync``, write-through: Adds apply at
  the owner as always, and the touched promoted rows fan out on the
  next flush), stamped with the owner shard's version;
* holder servers keep the pushed rows in a HOST-side ``ReplicaStore`` —
  serving a replica hit is a numpy gather, no device program and no
  device lock, which is what makes scale-out win on read-heavy
  traffic;
Concurrency note (mvlint pass 10): this module carries NO
``guarded_by`` annotations on purpose — every mutable structure here
is confined to exactly one actor thread (tracker + store on the server
actor, router map on the worker actor, aggregator on the controller
actor; per-class notes below), so there is no lock to annotate
against.

* workers route the replicated subset of a row Get to holders
  (``ReplicaRouter``): a worker co-located with a server prefers its
  LOCAL shard, a pure worker stripes per-row across all servers —
  merged into each holder's own shard request; rows a holder cannot
  serve (sync not yet landed, demotion race) or serves below the
  caller's read-your-writes floor come back short and the worker
  REPAIRS them with a follow-up request to the owner — the protocol is
  self-healing, never wrong.

Staleness is bounded and observable: every replica-served group carries
the owner-version floor of its rows (``REPLICA_SLOT`` + the reply's
replica descriptor, core/message.py), which feeds the same
``VersionTracker``/client-cache machinery as direct replies
(docs/CLIENT_CACHE.md).

BSP sync mode force-disables replication: the sync server's vector
clocks count one request per worker per step PER SERVER, and replica
routing changes which servers observe a Get.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..util.configure import (define_int, get_flag,
                              register_tunable_hook)

define_int("replica_hot_rows", 0,
           "hot-shard read replication budget: the controller promotes "
           "up to this many of the hottest rows PER TABLE to read "
           "replicas on every server (docs/SHARDING.md). 0 (default) "
           "disables replication entirely; BSP sync mode force-disables "
           "it (replica routing would desync the vector clocks)")
define_int("replica_report_gets", 256,
           "a server table reports its hot-row counters to the "
           "controller every this many row-Get requests (smaller = "
           "faster promotion, more control traffic)")
define_int("replica_min_gets", 8,
           "a row must log at least this many Gets (decayed) to be "
           "promotable — keeps one-off rows out of the replica map")
define_int("replica_sync_rows", 8192,
           "max rows per Request_ReplicaSync refresh message (larger "
           "refreshes split)")
define_int("replica_sync_every", 8,
           "write-through flush cadence: an owner fans refreshed values "
           "of its dirty promoted rows to the replica holders every "
           "this many served requests (bounds replica staleness in "
           "requests; the version floors make the actual staleness "
           "observable)")
def replication_enabled() -> bool:
    """Hot-row replication active for this process (read at table
    construction time, like -sparse_compress)."""
    if bool(get_flag("sync", False)):
        return False
    try:
        return int(get_flag("replica_hot_rows", 0)) > 0
    except (TypeError, ValueError):
        return False


#: Dashboard counter/sample names (util/dashboard.py).
REPLICA_HIT = "REPLICA_HIT"          # rows served from a replica store
REPLICA_MISS = "REPLICA_MISS"        # rows a holder could not serve
REPLICA_REPAIR = "REPLICA_REPAIR"    # repair requests issued
REPLICA_STALE = "REPLICA_STALE"      # groups rejected below a RYW floor
REPLICA_SYNC = "REPLICA_SYNC"        # write-through refreshes fanned out


class HotTracker:
    """Per-row Get-rate tracking on a server table.

    ``note`` is O(1) on the serving hot path — it only appends the
    request's id vector to the current window; the per-row counting is
    deferred to ``take_report`` (one vectorized ``np.unique`` per
    cadence), which drains the window, folds it into the decayed
    running counts (halving — exponential decay, so a row that stops
    being read ages out) and returns the hottest rows."""

    def __init__(self, cadence: Optional[int] = None):
        self._counts: Dict[int, float] = {}
        self._window: list = []
        self._gets = 0
        self._cadence = int(cadence if cadence is not None
                            else get_flag("replica_report_gets"))

    def note(self, rows: np.ndarray) -> None:
        self._gets += 1
        # Reference append only — request key vectors are never
        # mutated downstream. A request counts each row once (dedup at
        # fold time would cost here; duplicate ids inside one request
        # are rare and only overweight a row that is hot anyway).
        self._window.append(rows)

    @property
    def due(self) -> bool:
        return self._gets >= max(self._cadence, 1)

    def take_report(self, top_k: int) -> Tuple[np.ndarray, np.ndarray]:
        """(rows, counts) of the hottest ``top_k`` rows this window;
        decays the counters and re-arms the cadence."""
        self._gets = 0
        if self._window:
            uniq, cnt = np.unique(np.concatenate(self._window),
                                  return_counts=True)
            self._window = []
            counts = self._counts
            for r, c in zip(uniq.tolist(), cnt.tolist()):
                counts[r] = counts.get(r, 0.0) + float(c)
        items = sorted(self._counts.items(), key=lambda kv: -kv[1])[:top_k]
        rows = np.array([r for r, _ in items], dtype=np.int32)
        counts_arr = np.array([c for _, c in items], dtype=np.int32)
        # Exponential decay; fully cooled rows leave the dict so the
        # tracker's memory follows the working set, not history.
        self._counts = {r: c / 2.0 for r, c in self._counts.items()
                        if c >= 1.0}
        return rows, counts_arr


class ReplicaStore:
    """Holder-side host store of replicated rows: row id ->
    (value row, owner version, owner sid). Served rows carry per-owner
    version FLOORS (the oldest version among the group's rows) so the
    client's staleness machinery sees replica reads exactly like direct
    reads."""

    def __init__(self):
        self._values: Dict[int, np.ndarray] = {}
        self._version: Dict[int, int] = {}
        self._owner: Dict[int, int] = {}
        #: Last applied sync sequence per owner sid (gap detection).
        self._seq: Dict[int, int] = {}
        #: Lazily rebuilt packed view for ``serve`` — the per-request
        #: hot path must be numpy gathers, not per-row dict loops; the
        #: mutation paths (sync apply, prune, drop) just invalidate and
        #: the rebuild amortizes over the flush cadence.
        self._packed = None

    def __len__(self) -> int:
        return len(self._values)

    def _pack(self, num_col: int, dtype) -> tuple:
        ids = np.asarray(sorted(self._values), dtype=np.int64)
        if ids.size:
            id_list = ids.tolist()
            vals = np.stack([self._values[i] for i in id_list]) \
                .astype(dtype, copy=False)
            ver = np.asarray([self._version[i] for i in id_list],
                             np.int64)
            own = np.asarray([self._owner[i] for i in id_list],
                             np.int64)
        else:
            vals = np.empty((0, num_col), dtype)
            ver = own = np.empty(0, np.int64)
        self._packed = (ids, vals, ver, own)
        return self._packed

    def apply_sync(self, rows: np.ndarray, values: np.ndarray,
                   owner_sid: int, version: int,
                   watermark: bool = False, seq: int = -1) -> None:
        """An owner's refresh push. ``values`` is [len(rows), num_col].
        A refresh must never move a row BACKWARD in version (the owner
        serializes sends per holder). ``watermark=True`` rides the LAST
        chunk of a flush that drained EVERY row the owner dirtied since
        its previous flush: applying it makes every entry of this owner
        current as of ``version`` — without it, a row the adds never
        touch would keep its push-time version forever and read as
        stale against any later read-your-writes floor, even though its
        value is exact.

        ``seq`` is the owner's per-holder send counter. A GAP means a
        chunk toward this holder was lost (dead writer, restart): every
        entry of that owner is dropped BEFORE applying, because a later
        watermark must never certify values a lost chunk should have
        refreshed — dropped rows simply miss and repair to the owner
        (never wrong, at worst repaired). The owner also re-dirties the
        lost chunk's rows (communicator failure path), so the next
        flush restores the entries."""
        self._packed = None
        owner_sid = int(owner_sid)
        if seq >= 0:
            expected = self._seq.get(owner_sid, -1) + 1
            if seq != expected:
                self.drop_owner(owner_sid)
            self._seq[owner_sid] = int(seq)
        for i, r in enumerate(rows.tolist()):
            if self._version.get(r, -1) <= version:
                self._values[r] = np.array(values[i], copy=True)
                self._version[r] = int(version)
                self._owner[r] = owner_sid
        if watermark:
            for r, owner in self._owner.items():
                if owner == owner_sid and self._version[r] < version:
                    self._version[r] = int(version)

    def drop_owner(self, owner_sid: int) -> None:
        self._packed = None
        for r in [r for r, o in self._owner.items() if o == owner_sid]:
            del self._values[r], self._version[r], self._owner[r]

    def prune_to(self, promoted: np.ndarray) -> None:
        """Demotion: drop rows no longer in the map (the worker stops
        routing them on the same map epoch; a racing in-flight Get just
        repairs to the owner)."""
        self._packed = None
        keep = set(promoted.tolist())
        for r in [r for r in self._values if r not in keep]:
            del self._values[r], self._version[r], self._owner[r]

    def serve(self, rows: np.ndarray, num_col: int, dtype
              ) -> Tuple[List[Tuple[int, int, np.ndarray]], np.ndarray,
                         np.ndarray]:
        """Serve ``rows`` (unique ids) from the store.

        Returns ``(groups, served_keys, served_values)`` where groups is
        ``[(owner_sid, floor_version, n_rows), ...]`` (owners ascending)
        and the keys / [n, num_col] values are ordered group-by-group;
        ids not present are simply absent (the worker repairs them to
        the owner). Pure numpy on the packed view — this runs once per
        replica-routed request on the server actor thread."""
        empty = ([], np.empty(0, np.int32), np.empty((0, num_col), dtype))
        packed = self._packed
        if packed is None:
            packed = self._pack(num_col, dtype)
        ids, vals, ver, own = packed
        if ids.size == 0 or rows.size == 0:
            return empty
        pos = np.minimum(np.searchsorted(ids, rows), ids.size - 1)
        hit = ids[pos] == rows
        if not bool(hit.any()):
            return empty
        pos = pos[hit]
        keys = np.asarray(rows[hit], dtype=np.int32)
        owners, versions = own[pos], ver[pos]
        order = np.argsort(owners, kind="stable")  # input order kept
        owners, versions = owners[order], versions[order]
        uniq, starts = np.unique(owners, return_index=True)
        floors = np.minimum.reduceat(versions, starts)
        counts = np.diff(np.append(starts, owners.size))
        groups = [(int(o), int(f), int(c))
                  for o, f, c in zip(uniq, floors, counts)]
        return groups, keys[order], vals[pos[order]]


class ReplicaRouter:
    """Worker-side promoted-row map + holder choice.

    Applied on the worker actor thread (``Control_Replica_Map``
    handler) and read on the same thread (``partition``) — no locking.

    Holder choice (``route``): a worker CO-LOCATED with a server sends
    every replicated row to its local shard — the head then never
    touches the wire at all. A pure worker STRIPES the replicated rows
    across all servers by row id (every server holds every promoted
    row), which balances the Zipf head's bytes across the servers'
    links WITHIN each request — the per-request latency is the slowest
    shard's paced link, so an all-to-one-holder choice would leave the
    request gated by whichever server got the whole head. The chosen
    server's own rows ride the same shard message, so replica routing
    adds at most the messages a uniform tail already required."""

    def __init__(self, num_servers: int, salt: int = 0,
                 preferred: Optional[int] = None):
        self.epoch = -1
        self._rows: Optional[np.ndarray] = None  # sorted promoted rows
        self._num_servers = max(int(num_servers), 1)
        self._salt = int(salt)
        self._preferred = preferred if preferred is not None \
            and 0 <= int(preferred) < self._num_servers else None
        # Holders declared dead (Control_Dead_Peer): ``route`` returns
        # -1 for rows striped to them and the partition falls back to
        # the rows' OWNERS — a dead holder must not turn replicated
        # reads into retry loops against a corpse while the owner is
        # alive. A server is re-included when any reply from it lands
        # (``mark_alive`` via the reply context), and — the
        # authoritative path — whenever an epoch-stamped map broadcast
        # carries the controller's live-server view (``reconcile``):
        # before that, a rejoined server that got no organic reply
        # traffic stayed dead-marked indefinitely and its replicas
        # went unserved. After a rejoin its replica store is empty, so
        # resumed routing just misses and repairs until the owner's
        # pushes rebuild it — self-healing.
        self._dead: set = set()
        #: Resharding supersedes replication for a table: once its
        #: shard map goes dynamic, ownership moves absorb the skew and
        #: the static row->owner arithmetic the replica protocol
        #: assumes is gone (docs/SHARDING.md). A deactivated router
        #: ignores later promoted-row broadcasts.
        self._disabled = False

    @property
    def active(self) -> bool:
        return self._rows is not None and self._rows.size > 0

    @property
    def rows(self) -> Optional[np.ndarray]:
        return self._rows

    def apply(self, epoch: int, rows: np.ndarray) -> bool:
        """Adopt a broadcast map; stale epochs (reordered delivery) are
        ignored."""
        if self._disabled or epoch <= self.epoch:
            return False
        self.epoch = int(epoch)
        rows = np.asarray(rows, dtype=np.int32).reshape(-1)
        self._rows = np.sort(rows) if rows.size else None
        return True

    def replicated_mask(self, keys: np.ndarray) -> np.ndarray:
        if not self.active:
            return np.zeros(keys.shape, dtype=bool)
        idx = np.searchsorted(self._rows, keys)
        idx = np.minimum(idx, self._rows.size - 1)
        return self._rows[idx] == keys

    def mark_dead(self, sid: int) -> None:
        if 0 <= int(sid) < self._num_servers:
            self._dead.add(int(sid))

    def mark_alive(self, sid: int) -> None:
        self._dead.discard(int(sid))

    def deactivate(self) -> None:
        """Permanently retire this router (the table's shard map went
        dynamic — ownership moves supersede read replicas)."""
        self._disabled = True
        self._rows = None

    def reconcile(self, alive_sids) -> None:
        """Re-validate the dead marks against the controller's
        authoritative live-server view (carried on every epoch-stamped
        map broadcast): servers the controller considers alive resume
        receiving striped reads WITHOUT waiting for organic reply
        traffic, and servers it declared dead are marked even if no
        local send ever failed toward them."""
        alive = {int(s) for s in alive_sids}
        if not alive:
            return  # pre-liveness broadcast: keep local knowledge
        self._dead = {s for s in range(self._num_servers)
                      if s not in alive}

    def route(self, rows: np.ndarray) -> np.ndarray:
        """Holder server id per (replicated) row, or -1 where the
        chosen holder is declared dead (the caller falls back to the
        row's owner): the co-located shard when this rank hosts one,
        else a per-row stripe (salted so sibling workers shift
        phase)."""
        if self._preferred is not None:
            # The preferred holder is this rank's own shard — it cannot
            # be dead while this worker runs.
            return np.full(rows.shape, self._preferred, dtype=np.int64)
        out = (rows.astype(np.int64) + self._salt) % self._num_servers
        if self._dead:
            out[np.isin(out, np.asarray(sorted(self._dead)))] = -1
        return out


class ServerReplicaState:
    """Per-server-table replica bookkeeping (server actor thread only;
    built by dense matrix shards when ``replication_enabled()``).

    Combines the three server roles of the protocol: every server
    TRACKS the Get rate of the rows it serves (owned or replica-held —
    each request for a row lands on exactly one server, so the
    controller's aggregation over all reports preserves global counts
    and promotion cannot flap when routing moves the head to holders);
    a HOLDER keeps the pushed rows in ``store``; an OWNER remembers
    which of its rows are promoted and which of those an Add dirtied
    since the last write-through flush."""

    def __init__(self, row_offset: int, my_rows: int):
        self._row_offset = int(row_offset)
        self._my_rows = int(my_rows)
        self.tracker = HotTracker()
        self.store = ReplicaStore()
        self.epoch = -1
        self._own_promoted = np.empty(0, np.int32)  # sorted global ids
        self._dirty: set = set()  # dirty own promoted rows (global ids)
        self._served = 0
        self._sync_every = max(int(get_flag("replica_sync_every")), 1)
        self._report_top = max(2 * int(get_flag("replica_hot_rows")), 16)
        #: Owner shard version as of the last watermark-carrying sync
        #: (the table compares against its live version to decide
        #: whether a watermark-only refresh is worth a message).
        self.last_sync_version = -1
        #: Per-holder Request_ReplicaSync send counters (gap detection
        #: on the holder side; see ``next_sync_seq``).
        self._sync_seq: Dict[int, int] = {}
        # Live retuning (docs/AUTOTUNE.md): the controller-side budget
        # (ReplicaCoordinator) reads -replica_hot_rows fresh per
        # report, but this reporter cached its window size here — the
        # hook re-sizes it so a grown budget sees enough candidates.
        register_tunable_hook("replica_hot_rows",
                              self._retune_budget)

    def _retune_budget(self, value) -> None:
        self._report_top = max(2 * int(value), 16)

    def note_get(self, rows: np.ndarray) -> None:
        if rows.size:
            self.tracker.note(rows)

    def note_add(self, rows: np.ndarray) -> None:
        """Host row Add applied at this owner: promoted rows among them
        go dirty (refreshed to the holders on the next flush)."""
        if not self._own_promoted.size or not rows.size:
            return
        idx = np.searchsorted(self._own_promoted, rows)
        idx = np.minimum(idx, self._own_promoted.size - 1)
        self._dirty.update(
            rows[self._own_promoted[idx] == rows].tolist())

    def note_add_all(self) -> None:
        """Whole-table or device-key Add (ids unenumerable on the
        host): conservatively dirty every own promoted row."""
        self._dirty.update(self._own_promoted.tolist())

    def redirty(self, rows: np.ndarray) -> None:
        """A sync chunk toward some holder was lost (communicator
        failure echo, server actor thread): its rows go back in the
        dirty set so the next flush re-pushes them toward EVERY holder
        (redundant for healthy ones, restorative for the one that
        missed). Rows demoted since the send stay out."""
        keep = set(self._own_promoted.tolist())
        self._dirty.update(r for r in rows.tolist() if r in keep)

    def next_sync_seq(self, holder_sid: int) -> int:
        """Per-holder send counter for Request_ReplicaSync (the holder
        drops this owner's entries on a gap — a lost chunk must not be
        papered over by a later watermark)."""
        seq = self._sync_seq.get(int(holder_sid), 0)
        self._sync_seq[int(holder_sid)] = seq + 1
        return seq

    def apply_map(self, epoch: int, rows: np.ndarray) -> np.ndarray:
        """Adopt a promoted-row map broadcast. Returns the rows the
        owner must push NOW: the newly promoted own rows UNION the
        drained dirty set — the push carries a version watermark, which
        is only sound when no dirtied row is left out of it. Prunes
        holder entries for demoted rows."""
        rows = np.asarray(rows, dtype=np.int32).reshape(-1)
        if epoch <= self.epoch:
            return np.empty(0, np.int32)
        self.epoch = int(epoch)
        lo = self._row_offset
        own = np.sort(rows[(rows >= lo) & (rows < lo + self._my_rows)])
        new = np.setdiff1d(own, self._own_promoted)
        self._own_promoted = own
        keep = set(own.tolist())
        pending = np.asarray(sorted(r for r in self._dirty if r in keep),
                             dtype=np.int32)
        self._dirty.clear()
        self.store.prune_to(rows)
        return np.union1d(new, pending)

    def take_due_sync(self) -> Optional[np.ndarray]:
        """Every ``-replica_sync_every`` served requests: the dirty own
        promoted rows to refresh (drained; possibly EMPTY — the caller
        still sends a watermark-only refresh when its shard version
        advanced past ``last_sync_version``), else None."""
        self._served += 1
        if self._served % self._sync_every or not self._own_promoted.size:
            return None
        rows = np.asarray(sorted(self._dirty), dtype=np.int32)
        self._dirty.clear()
        return rows

    def take_due_report(self) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        if not self.tracker.due:
            return None
        rows, counts = self.tracker.take_report(self._report_top)
        if rows.size == 0:
            return None
        return rows, counts


# -- Control_Replica_Report / Control_Replica_Map payload helpers --
#
# Report: msg.table_id names the table; blob 0 = int32 rows, blob 1 =
# int32 counts (same length). Map: blob 0 = int32
# [epoch, n_tables, (table_id, n_rows) * n]; blobs 1..n = one int32 row
# vector per table, in descriptor order.

def pack_replica_map(epoch: int, promoted: Dict[int, np.ndarray],
                     alive_sids=None) -> List[np.ndarray]:
    """``alive_sids`` (trailing blob, absent on older payloads) is the
    controller's authoritative live-server view: routers reconcile
    their dead marks against it on every broadcast, so a rejoined
    server resumes serving replicas without waiting for organic
    traffic (docs/SHARDING.md)."""
    desc = [int(epoch), len(promoted)]
    rows_blobs: List[np.ndarray] = []
    for table_id in sorted(promoted):
        rows = np.asarray(promoted[table_id], dtype=np.int32).reshape(-1)
        desc.extend((int(table_id), int(rows.size)))
        rows_blobs.append(rows)
    blobs = [np.asarray(desc, dtype=np.int32)] + rows_blobs
    if alive_sids is not None:
        blobs.append(np.asarray(sorted(int(s) for s in alive_sids),
                                dtype=np.int32))
    return blobs


def unpack_replica_map(blobs) -> Tuple[int, Dict[int, np.ndarray]]:
    epoch, promoted, _alive = unpack_replica_map_alive(blobs)
    return epoch, promoted


def unpack_replica_map_alive(blobs):
    """(epoch, promoted, alive_sids-or-None) — the alive vector is the
    trailing blob when the sender packed one."""
    desc = blobs[0]
    epoch, n_tables = int(desc[0]), int(desc[1])
    promoted: Dict[int, np.ndarray] = {}
    for i in range(n_tables):
        table_id = int(desc[2 + 2 * i])
        promoted[table_id] = np.asarray(blobs[1 + i],
                                        dtype=np.int32).reshape(-1)
    alive = None
    if len(blobs) > 1 + n_tables:
        alive = np.asarray(blobs[1 + n_tables],
                           dtype=np.int32).reshape(-1)
    return epoch, promoted, alive


class ReplicaCoordinator:
    """Controller-side aggregation of hot-row reports into the
    promoted-row map (runs on the rank-0 controller actor thread).

    Per table the coordinator keeps decayed global counts; every
    ingested report decays the table's counts and merges the server's
    window. The promoted set is the hottest ``-replica_hot_rows`` rows
    with a decayed count of at least ``-replica_min_gets``; any CHANGE
    to any table's set bumps the epoch and triggers a fresh broadcast
    (the caller sends it)."""

    def __init__(self):
        self._counts: Dict[int, Dict[int, float]] = {}
        self._promoted: Dict[int, np.ndarray] = {}
        self._reporters: Dict[int, set] = {}
        self.epoch = 0

    def ingest(self, table_id: int, rows: np.ndarray,
               counts: np.ndarray, reporter: int = -1) -> bool:
        """Returns True when the promoted map changed (re-broadcast)."""
        budget = int(get_flag("replica_hot_rows"))
        if budget <= 0:
            return False
        table = self._counts.setdefault(int(table_id), {})
        # Decay once per report ROUND, not per report: each server
        # reports independently, so a per-report decay would halve a
        # row's count num_servers times between consecutive reports
        # from its serving server — the effective decay rate would
        # scale with the server count, crushing every row toward the
        # promotion threshold exactly when there are many servers (a
        # repeat reporter marks the next round).
        seen = self._reporters.setdefault(int(table_id), set())
        if reporter in seen:
            seen.clear()
            for r in list(table):
                table[r] /= 2.0
                if table[r] < 0.5:
                    del table[r]
        seen.add(reporter)
        for r, c in zip(rows.tolist(), counts.tolist()):
            table[r] = table.get(r, 0.0) + float(c)
        threshold = float(get_flag("replica_min_gets"))
        old_set = set(self._promoted.get(int(table_id),
                                         np.empty(0, np.int32)).tolist())
        # Promotion is deliberately STICKY, two ways: an incumbent stays
        # promotable at HALF the admission threshold, and when the
        # budget is full a hotter challenger does NOT evict — rows leave
        # only by cooling below the retention threshold. Without both,
        # boundary rows swap in and out on per-report count noise, and
        # every swap costs a map broadcast plus the owner's initial
        # value push to every holder.
        incumbents = sorted(
            (r for r, c in table.items()
             if r in old_set and c >= threshold / 2.0),
            key=lambda r: -table[r])[:budget]
        challengers = sorted(
            (r for r, c in table.items()
             if r not in old_set and c >= threshold),
            key=lambda r: -table[r])[:max(budget - len(incumbents), 0)]
        new = np.sort(np.asarray(incumbents + challengers,
                                 dtype=np.int32))
        old = self._promoted.get(int(table_id))
        if old is not None and np.array_equal(old, new):
            return False
        if old is None and new.size == 0:
            return False
        self._promoted[int(table_id)] = new
        self.epoch += 1
        return True

    @property
    def promoted(self) -> Dict[int, np.ndarray]:
        return self._promoted

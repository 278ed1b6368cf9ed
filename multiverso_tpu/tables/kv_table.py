"""Distributed key-value table (hash-sharded map).

TPU-native equivalent of the reference's ``KVWorkerTable/KVServerTable``
(ref: include/multiverso/table/kv_table.h:18-124). Semantics preserved:

- partition by ``key % num_servers`` (ref: kv_table.h:48-65);
- server ``process_add`` does ``table[k] += v`` (ref: kv_table.h:99-106);
- the worker keeps a local ``raw`` dict refreshed by Get
  (ref: kv_table.h:40, 68-75).

KV state is host-side (it backs control-plane things like WordEmbedding's
word counts, ref: Applications/WordEmbedding/src/communicator.cpp:251-259);
numeric bulk state belongs in Array/Matrix tables in HBM. Unlike the
reference we also implement Store/Load (the reference raises
"Not implemented", ref: kv_table.h:108-114).

Elastic resharding (docs/SHARDING.md): KV tables reshard at HASH-BUCKET
granularity — ``bucket = key % (16 * num_servers)``; the bucket count is
a multiple of the server count so the frozen layout's
``(key % B) % num_servers`` equals the reference's ``key %
num_servers`` bit-for-bit. A dynamic :class:`ShardMap` over bucket ids
then reassigns bucket intervals between live servers through the same
controller-coordinated stream/forward/commit protocol as dense matrix
rows (runtime/shard_map.py); the dict state of a bucket moves as one
pickled chunk.
"""

from __future__ import annotations

import pickle
import struct
from typing import Dict, List, Optional

import numpy as np

from ..core.blob import Blob
from ..core.message import PEER_LOST_MARK, Message, MsgType
from ..runtime import shard_map as shard_map_mod
from ..util import chaos, log
from ..util.dashboard import count as count_event
from ..util.log import CHECK
from . import client_cache
from .client_cache import SnapshotCache
from .table_interface import ServerTable, WorkerTable


def _kv_buckets(num_servers: int) -> int:
    """Bucket-space size: a multiple of the server count, so the
    frozen modulo layout reproduces ``key % num_servers`` exactly."""
    return 16 * max(int(num_servers), 1)


def _modulo_map(num_buckets: int, active: int) -> shard_map_mod.ShardMap:
    """Epoch-0 bucket map: bucket b -> server ``b % active`` (the
    frozen hash layout over the first ``active`` servers)."""
    bounds = np.arange(num_buckets + 1, dtype=np.int64)
    owners = np.arange(num_buckets, dtype=np.int64) % max(active, 1)
    return shard_map_mod.ShardMap(bounds, owners, epoch=0)


class KVWorker(WorkerTable):
    def __init__(self, key_dtype=np.int64, val_dtype=np.float32, zoo=None):
        super().__init__(zoo=zoo)
        self.key_dtype = np.dtype(key_dtype)
        self.val_dtype = np.dtype(val_dtype)
        self._num_server = self._zoo.num_servers
        self._num_buckets = _kv_buckets(self._num_server)
        # Frozen layout: plain modulo (byte-identical to the
        # reference) unless -shard_initial_servers narrows the active
        # set, in which case an epoch-0 bucket map routes over it.
        active = shard_map_mod.initial_active_servers(self._num_server)
        self._bucket_map: Optional[shard_map_mod.ShardMap] = \
            _modulo_map(self._num_buckets, active) \
            if active < self._num_server else None
        self.raw: Dict[int, float] = {}
        # Client cache (-max_get_staleness > 0): whole-request
        # snapshots keyed by the exact requested key set, versioned per
        # contributing server shard.
        bound = client_cache.staleness_bound()
        self._snap_cache: Optional[SnapshotCache] = None
        if bound > 0:
            self._snap_cache = SnapshotCache(bound, self._version_tracker)
            self._caches.append(self._snap_cache)
        self._collect_versions: Optional[Dict[int, int]] = None

    def _owner_of_keys(self, keys: np.ndarray) -> np.ndarray:
        buckets = (keys.astype(np.int64) % self._num_buckets)
        if self._bucket_map is not None:
            return self._bucket_map.owner_of(buckets)
        return buckets % self._num_server

    # -- elastic resharding: worker side --
    def apply_shard_map(self, epoch: int, smap, alive_sids) -> None:
        old = self._bucket_map
        if old is not None and epoch <= old.epoch:
            return
        if old is None:
            old = _modulo_map(self._num_buckets, self._num_server)
        moved = old.diff_moved(smap)
        for old_sid in sorted({m[2] for m in moved}):
            # Snapshot-cache entries record multi-shard version
            # vectors; a moved bucket's versions now come from another
            # counter — the generation-change sweep clears them.
            self.note_shard_moved(old_sid)
        self._bucket_map = smap

    def shard_epoch(self) -> int:
        return self._bucket_map.epoch if self._bucket_map is not None \
            else -1

    def shard_owner_sids(self):
        return self._bucket_map.owner_sids() \
            if self._bucket_map is not None else None

    def shard_layout(self):
        smap = self._bucket_map
        if smap is None:
            return None
        return (smap.bounds.tolist(), smap.owners.tolist())

    def reshard_space(self) -> int:
        return self._num_buckets

    def reshard_kind(self) -> int:
        return 1  # modulo initial layout (runtime/shard_map.py)

    def get(self, keys) -> Dict[int, float]:
        """Refresh ``raw`` for the requested keys and return it."""
        keys = np.ascontiguousarray(keys, dtype=self.key_dtype).reshape(-1)
        if self._snap_cache is not None:
            sids = np.unique(self._owner_of_keys(keys))
            snap = self._snap_cache.fetch(keys.tobytes(), sids)
            if snap is not None:
                self.raw.update(snap)
                return self.raw
            # Collect per-shard version stamps as the replies land (the
            # worker actor's reply context carries them).
            self._collect_versions = {}
        self.retrying_wait(
            lambda: self.get_async_raw(Blob(keys.view(np.uint8))))
        if self._snap_cache is not None:
            versions, self._collect_versions = self._collect_versions, None
            if versions is not None and \
                    {int(s) for s in sids} <= set(versions):
                self._snap_cache.store(
                    keys.tobytes(), versions,
                    {int(k): self.raw.get(int(k), 0.0) for k in keys})
        return self.raw

    def add(self, keys, values) -> None:
        self.retrying_wait(lambda: self.add_async(keys, values))

    def add_async(self, keys, values) -> int:
        keys = np.ascontiguousarray(keys, dtype=self.key_dtype).reshape(-1)
        values = np.ascontiguousarray(values,
                                      dtype=self.val_dtype).reshape(-1)
        CHECK(keys.size == values.size, "keys/values size mismatch")
        if self._snap_cache is not None:
            # Self-invalidation until the ack's version resolves it.
            self._snap_cache.begin_add()
        mid = self.add_async_raw(Blob(keys.view(np.uint8)),
                                 Blob(values.view(np.uint8)))
        if self._snap_cache is not None:
            self.add_completion(
                mid, lambda _mid: self._snap_cache.finish_add())
        return mid

    # ref: kv_table.h:48-65
    def partition(self, blobs, msg_type) -> Dict[int, List[Blob]]:
        keys = blobs[0].as_array(self.key_dtype)
        values = blobs[1].as_array(self.val_dtype) \
            if len(blobs) >= 2 else None
        out: Dict[int, List[Blob]] = {}
        dest = self._owner_of_keys(keys).astype(np.int64)
        for sid in np.unique(dest):
            mask = dest == sid
            shard = [Blob(np.ascontiguousarray(keys[mask]).view(np.uint8))]
            if values is not None:
                shard.append(
                    Blob(np.ascontiguousarray(values[mask]).view(np.uint8)))
            out[int(sid)] = shard
        return out

    # ref: kv_table.h:68-75
    def process_reply_get(self, reply_blobs: List[Blob]) -> None:
        keys = reply_blobs[0].as_array(self.key_dtype)
        values = reply_blobs[1].as_array(self.val_dtype)
        for k, v in zip(keys, values):
            self.raw[int(k)] = v.item()
        if (self._collect_versions is not None
                and self._reply_version >= 0):
            self._collect_versions[self._reply_server] = \
                self._reply_version


class KVServer(shard_map_mod.ElasticServerMixin, ServerTable):
    #: KV state is a host-side dict — pure control-plane work that must
    #: not serialize two in-process server shards on the device lock.
    needs_device_lock = False

    def __init__(self, key_dtype=np.int64, val_dtype=np.float32, zoo=None):
        super().__init__(zoo=zoo)
        self.key_dtype = np.dtype(key_dtype)
        self.val_dtype = np.dtype(val_dtype)
        self._store: Dict[int, float] = {}
        self.server_id = self._zoo.server_id
        self._num_buckets = _kv_buckets(self._zoo.num_servers)
        active = shard_map_mod.initial_active_servers(
            self._zoo.num_servers)
        self._smap: Optional[shard_map_mod.ShardMap] = \
            _modulo_map(self._num_buckets, active) \
            if active < self._zoo.num_servers else None
        #: dual-read windows over BUCKET intervals
        self._fwd: List[tuple] = []
        self._mig_out: Optional[shard_map_mod.MigrationOut] = None
        self._mig_in: Dict[int, shard_map_mod.MigrationIn] = {}
        #: forwarded adds whose bucket's base chunk is still in flight
        self._pending: Dict[int, float] = {}
        #: requests forwarded into a window since the last map apply
        #: (see MatrixServer._fwd_inflight): drained into retryable
        #: error replies on rollback.
        self._fwd_inflight: List[tuple] = []
        #: both-apply exemption flag (see MatrixServer._in_both_apply)
        self._in_both_apply = False
        #: buckets of incomplete inbound migrations whose chunk landed
        self._based: set = set()

    def _buckets_of(self, keys: np.ndarray) -> np.ndarray:
        return keys.astype(np.int64) % self._num_buckets

    def _unbased_mask(self, buckets: np.ndarray) -> np.ndarray:
        """Buckets of an incomplete inbound migration whose base chunk
        has not landed (retransmit window): serving them would hand
        back values missing their base."""
        mask = np.zeros(buckets.size, dtype=bool)
        for mig in self._mig_in.values():
            if mig.complete:
                continue
            mask |= ((buckets >= mig.lo) & (buckets < mig.hi)
                     & ~np.isin(buckets, np.asarray(sorted(self._based),
                                                   dtype=np.int64)))
        return mask

    # ref: kv_table.h:99-106
    def process_add(self, blobs: List[Blob]) -> None:
        keys = blobs[0].as_array(self.key_dtype)
        values = blobs[1].as_array(self.val_dtype)
        if self._mig_out is not None and self._mig_out.streaming \
                and keys.size:
            self._mig_out.note_add(self._buckets_of(keys))
        if self._fwd and keys.size and not self._in_both_apply:
            # Keys in this shard's OWN forwarding windows live at the
            # new owner now; applying (and acking) into the dead copy
            # here would silently lose the write — a chained move
            # (A->B->C) can land a stale-routed add at the dead middle
            # hop. VALIDATE before any mutation (at-least-once).
            fwd_mask, _, _ = self._fwd_route(self._buckets_of(keys))
            if bool(fwd_mask.any()):
                raise RuntimeError(
                    f"{PEER_LOST_MARK} rank {self._zoo.rank}: add to "
                    f"moved bucket(s) (shard map in motion) — "
                    f"re-issue")
        unbased = None
        if self._mig_in and keys.size:
            unbased = self._unbased_mask(self._buckets_of(keys))
        for i, (k, v) in enumerate(zip(keys, values)):
            if unbased is not None and unbased[i]:
                # Base chunk still in flight: ledger the delta, merged
                # when the (retransmitted) chunk lands.
                self._pending[int(k)] = \
                    self._pending.get(int(k), 0.0) + v.item()
            else:
                self._store[int(k)] = \
                    self._store.get(int(k), 0) + v.item()

    # ref: kv_table.h:88-97
    def process_get(self, blobs: List[Blob]) -> List[Blob]:
        keys = blobs[0].as_array(self.key_dtype)
        if self._mig_in and keys.size:
            unbased = self._unbased_mask(self._buckets_of(keys))
            if bool(unbased.any()):
                raise RuntimeError(
                    f"{PEER_LOST_MARK} rank {self._zoo.rank}: bucket "
                    f"base still in retransmit — re-issue")
        # NOTE: keys in this shard's own forwarding windows never reach
        # here from Server._process_get (shard_forward_get intercepts);
        # process_forward_get applies its own check below.
        values = np.array([self._store.get(int(k), 0) for k in keys],
                          dtype=self.val_dtype)
        return [blobs[0], Blob(values.view(np.uint8))]

    # -- server-side request fusion (runtime/fusion.py) --
    def fuse_eligible(self, blobs: List[Blob], is_get: bool) -> bool:
        """Host-dict table: fusion is just the base-class serial loop
        under one dispatch, so any steady-state request qualifies.
        Opt out whenever elastic state is live — forwarding windows,
        in/out migrations or a pending-delta ledger re-route or defer
        individual requests, and those paths must keep their serial
        retryable-NACK semantics."""
        if blobs and blobs[0].on_device:
            return False
        return not (self._fwd or self._mig_in
                    or self._mig_out is not None or self._pending)

    # -- elastic resharding: server side (runtime/shard_map.py) --
    def shard_begin_out(self, desc) -> bool:
        lo, hi, src_sid, dst_sid, dst_rank, epoch = (
            int(v) for v in np.asarray(desc)[:6])
        if self._mig_out is not None:
            if self._mig_out.epoch == epoch:
                # Stalled-commit recovery: see MatrixServer.
                self._mig_out.resend_final = self._mig_out.final_sent
                return True
            if self._mig_out.final_sent and epoch > self._mig_out.epoch:
                # A Begin for a NEWER epoch proves the previous move
                # committed (the controller serializes moves) — its
                # broadcast lost a race with this Begin. Retire it;
                # the handoff's forwarding window stays.
                self._mig_out = None
            else:
                return False
        if src_sid != self.server_id:
            return False
        buckets = np.arange(lo, hi, dtype=np.int64)
        mask, _, _ = self._fwd_route(buckets)
        if bool(mask.any()):
            return False
        self._mig_out = shard_map_mod.MigrationOut(
            self.table_id, lo, hi, src_sid, dst_sid, dst_rank, epoch)
        chaos.kill_point("shard_begin_accepted")
        return True

    def _bucket_items(self, buckets: np.ndarray) -> Dict[int, float]:
        wanted = set(int(b) for b in buckets.tolist())
        B = self._num_buckets
        return {k: v for k, v in self._store.items()
                if (k % B) in wanted}

    def _shard_data_message(self, mig, seq: int, buckets: np.ndarray,
                            is_final: bool) -> Message:
        if mig.frozen is not None:
            # Post-handoff retransmit: serve from the handoff snapshot
            # (the live dict keeps moving — both-applied forwarded
            # Adds; see ElasticServerMixin.shard_ack).
            wanted = set(int(b) for b in buckets.tolist())
            B = self._num_buckets
            items = {k: v for k, v in mig.frozen.items()
                     if (k % B) in wanted}
        else:
            items = self._bucket_items(buckets)
        payload = pickle.dumps(items)
        desc = np.asarray(
            [mig.epoch, mig.src_sid, mig.dst_sid, self._zoo.rank,
             mig.lo, mig.hi, seq, 1 if is_final else 0,
             self.version + 1, len(mig.chunks)], dtype=np.int64)
        msg = Message(src=self._zoo.rank, dst=mig.dst_rank,
                      msg_type=MsgType.Request_ShardData,
                      table_id=self.table_id)
        msg.push(Blob(desc))
        msg.push(Blob(buckets.astype(np.int64)))
        msg.push(Blob(np.frombuffer(payload, np.uint8).copy()))
        count_event("SHARD_MIGRATE_ROWS", int(buckets.size))
        return msg

    def _freeze_range(self, mig):
        return self._bucket_items(
            np.arange(mig.lo, mig.hi, dtype=np.int64))

    def shard_import_chunk(self, msg: Message):
        desc = msg.data[0].as_array(np.int64)
        (epoch, src_sid, dst_sid, src_rank, lo, hi, seq, is_final,
         wire_version, _n_chunks) = (int(v) for v in desc[:10])
        if dst_sid != self.server_id:
            return []
        mig = self._mig_in.get(epoch)
        if mig is None:
            mig = self._mig_in[epoch] = shard_map_mod.MigrationIn(
                epoch, src_sid, src_rank, lo, hi)
        if not mig.complete and mig.note_applied(seq):
            buckets = msg.data[1].as_array(np.int64)
            items = pickle.loads(bytes(msg.data[2].as_array(np.uint8)))
            if is_final:
                mig.final_items = set(int(b) for b in buckets.tolist())
            elif mig.final_items is not None:
                # Reorder-delayed base chunk after the final: the
                # final re-exported every dirty BUCKET wholesale, so
                # its copies are newer — skip those buckets entirely.
                B = self._num_buckets
                items = {k: v for k, v in items.items()
                         if (k % B) not in mig.final_items}
            for k, v in items.items():
                # REPLACE with the source's value plus any forwarded
                # adds that beat this chunk (the pending ledger).
                self._store[int(k)] = float(v) \
                    + self._pending.pop(int(k), 0.0)
            self._based.update(int(b) for b in buckets.tolist())
            # Pending deltas for keys the source held no entry for
            # still resolve once their bucket is based.
            B = self._num_buckets
            based = set(int(b) for b in buckets.tolist())
            for k in [k for k in self._pending if (k % B) in based]:
                self._store[k] = self._store.get(k, 0) \
                    + self._pending.pop(k)
        if is_final and not mig.complete:
            mig.n_chunks = seq
            mig.src_version = wire_version - 1
            chaos.kill_point("shard_dest_final")
        if mig.n_chunks is None:
            return []
        if mig.check_complete():
            chaos.kill_point("shard_dest_complete")
            return self._announce_done(mig)
        if is_final:
            return self._retransmit_request(mig)
        return []

    def shard_abort(self, epoch: int):
        epoch = int(epoch)
        out: List[Message] = []
        mig = self._mig_out
        if mig is not None and mig.epoch == epoch:
            if mig.final_sent:
                self._fwd = [f for f in self._fwd
                             if not (f[0] == mig.lo and f[1] == mig.hi
                                     and f[2] == mig.dst_sid)]
                out.extend(self._drain_fwd_inflight())
            self._mig_out = None
        mig_in = self._mig_in.pop(epoch, None)
        if mig_in is not None:
            B = self._num_buckets
            for k in [k for k in self._store
                      if mig_in.lo <= (k % B) < mig_in.hi]:
                del self._store[k]
            for k in [k for k in self._pending
                      if mig_in.lo <= (k % B) < mig_in.hi]:
                del self._pending[k]
            self._based -= {b for b in self._based
                            if mig_in.lo <= b < mig_in.hi}
        return out

    def apply_shard_map_server(self, epoch: int, smap, alive_sids):
        if self._smap is not None and epoch <= self._smap.epoch:
            return []
        old = self._smap if self._smap is not None else \
            _modulo_map(self._num_buckets, self._zoo.num_servers)
        moved = old.diff_moved(smap)
        B = self._num_buckets
        for lo, hi, old_sid, new_sid in moved:
            if old_sid == self.server_id:
                # Committed away: drop the moved buckets' entries and
                # keep the forwarding window for stale routers.
                for k in [k for k in self._store
                          if lo <= (k % B) < hi]:
                    del self._store[k]
                if not any(f[0] <= lo and hi <= f[1] and f[2] == new_sid
                           for f in self._fwd):
                    self._fwd.append(
                        (lo, hi, new_sid,
                         self._zoo.server_rank(new_sid)))
            if new_sid == self.server_id:
                self._prune_fwd_windows(lo, hi)
        if self._mig_out is not None \
                and self._mig_out.epoch <= epoch \
                and int(smap.owner_of(np.asarray(
                    [self._mig_out.lo]))[0]) == self._mig_out.dst_sid:
            self._mig_out = None
        for e in [e for e, m in self._mig_in.items()
                  if m.complete and e <= epoch]:
            m = self._mig_in.pop(e)
            self._based -= {b for b in self._based
                            if m.lo <= b < m.hi}
        self._fwd_inflight = []  # window destination proven alive
        self._smap = smap
        return []

    def shard_forward_get(self, msg: Message):
        if not self._fwd or not msg.data:
            return None
        keys = msg.data[0].as_array(self.key_dtype)
        if keys.size == 0:
            return None
        buckets = self._buckets_of(keys)
        mask, dst_sid, dst_rank = self._fwd_route(buckets)
        if not bool(mask.any()):
            return None
        count_event("SHARD_FWD")
        dsts = sorted({int(d) for d in dst_sid[mask]})
        if len(dsts) > 1:
            raise RuntimeError(
                f"{PEER_LOST_MARK} keys span {len(dsts)} forwarding "
                f"windows — re-issue after the next shard-map "
                f"broadcast")
        overflow = self._note_fwd_inflight(msg.src, msg.msg_id, True)
        pig_keys = np.ascontiguousarray(keys[~mask])
        pig_vals = np.array([self._store.get(int(k), 0)
                             for k in pig_keys], dtype=self.val_dtype)
        meta = np.asarray([self._zoo.rank, 0], dtype=np.int64)
        fwd = Message(src=msg.src, dst=int(dst_rank[mask][0]),
                      msg_type=MsgType.Request_FwdGet,
                      table_id=self.table_id, msg_id=msg.msg_id)
        fwd.push(Blob(meta))
        fwd.push(Blob(np.ascontiguousarray(keys[mask]).view(np.uint8)))
        fwd.push(Blob(pig_keys.view(np.uint8)))
        fwd.push(Blob(pig_vals.view(np.uint8)))
        return [fwd] + overflow

    def process_forward_get(self, blobs: List[Blob]):
        meta = blobs[0].as_array(np.int64)
        src_rank, src_version = int(meta[0]), int(meta[1]) - 1
        fwd_keys = blobs[1].as_array(self.key_dtype)
        pig_keys = blobs[2].as_array(self.key_dtype)
        pig_vals = blobs[3].as_array(self.val_dtype)
        if self._mig_in and fwd_keys.size:
            unbased = self._unbased_mask(self._buckets_of(fwd_keys))
            if bool(unbased.any()):
                raise RuntimeError(
                    f"{PEER_LOST_MARK} forwarded bucket base still in "
                    f"retransmit — re-issue")
        if self._fwd and fwd_keys.size:
            # Chained move: these buckets moved on from here too —
            # serving the dead copy would be silently stale.
            fwd_mask, _, _ = self._fwd_route(self._buckets_of(fwd_keys))
            if bool(fwd_mask.any()):
                raise RuntimeError(
                    f"{PEER_LOST_MARK} forwarded bucket moved on from "
                    f"this shard (chained migration) — re-issue")
        vals = np.array([self._store.get(int(k), 0) for k in fwd_keys],
                        dtype=self.val_dtype)
        keys_out = np.ascontiguousarray(
            np.concatenate([pig_keys, fwd_keys]))
        vals_out = np.concatenate([pig_vals, vals])
        # KV forward replies stay version-UNSTAMPED (src_version is -1
        # by construction): the snapshot cache must not record a
        # cross-shard mixture under one shard's counter — mid-window
        # KV gets simply don't cache (self-correcting once the
        # requester adopts the committed map).
        return ([Blob(keys_out.view(np.uint8)),
                 Blob(vals_out.view(np.uint8))], 0, src_rank,
                src_version)

    def shard_forward_add(self, msg: Message):
        if not self._fwd or len(msg.data) < 2:
            return None
        keys = msg.data[0].as_array(self.key_dtype)
        if keys.size == 0:
            return None
        values = msg.data[1].as_array(self.val_dtype)
        buckets = self._buckets_of(keys)
        mask, dst_sid, dst_rank = self._fwd_route(buckets)
        if not bool(mask.any()):
            return None
        count_event("SHARD_FWD")
        # BOTH-APPLY (see MatrixServer.shard_forward_add): the full add
        # applies locally without an ack; the destination acks the
        # forwarded moved-bucket subset under the real msg_id.
        outs: List[Message] = list(
            self._note_fwd_inflight(msg.src, msg.msg_id, False))
        first = True
        for d in sorted({int(x) for x in dst_sid[mask]}):
            m = mask & (dst_sid == d)
            fwd = Message(src=msg.src, dst=int(dst_rank[m][0]),
                          msg_type=MsgType.Request_FwdAdd,
                          table_id=self.table_id,
                          msg_id=msg.msg_id if first else -1)
            fwd.push(Blob(np.asarray([self._zoo.rank], dtype=np.int64)))
            fwd.push(Blob(np.ascontiguousarray(keys[m]).view(np.uint8)))
            fwd.push(Blob(np.ascontiguousarray(values[m])
                          .view(np.uint8)))
            outs.append(fwd)
            first = False
        return msg, outs

    def store(self, stream) -> None:
        payload = pickle.dumps(self._store)
        stream.write(struct.pack("<Q", len(payload)))
        stream.write(payload)

    # -- async snapshot split (runtime/snapshot.py) --
    def snapshot_state(self):
        """Consistent capture: ``dict(d)`` copies at C level without
        releasing the GIL, so it is atomic against the server actor's
        concurrent adds (KV tables run without the device table lock)."""
        return dict(self._store)

    def snapshot_meta(self):
        if self._smap is None and not self._fwd:
            return None
        return {"elastic": 1,
                "shard_epoch": self._smap.epoch
                if self._smap is not None else -1,
                "fwd": [[int(lo), int(hi), int(sid)]
                        for lo, hi, sid, _rank in self._fwd]}

    def write_snapshot(self, state, stream) -> None:
        payload = pickle.dumps(state)
        stream.write(struct.pack("<Q", len(payload)))
        stream.write(payload)

    def load_with_meta(self, stream, meta) -> None:
        self.load(stream)
        if meta and meta.get("elastic"):
            self._fwd = [(int(lo), int(hi), int(sid),
                          self._zoo.server_rank(int(sid)))
                         for lo, hi, sid, *_ in meta.get("fwd", [])]
            log.info("rank %d: KV table %d restored elastic state "
                     "(%d forwarding window(s), recorded shard epoch "
                     "%s)", self._zoo.rank, self.table_id,
                     len(self._fwd), meta.get("shard_epoch"))

    def load(self, stream) -> None:
        (length,) = struct.unpack("<Q", stream.read(8))
        self._store = pickle.loads(stream.read(length))

    @property
    def raw(self) -> Dict[int, float]:
        return self._store

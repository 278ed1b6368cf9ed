"""1-D dense distributed tensor table.

TPU-native equivalent of the reference's ``ArrayWorker/ArrayServer``
(ref: include/multiverso/table/array_table.h:13-73,
src/table/array_table.cpp:10-156). Semantics preserved:

- element-range partition over servers: server i owns
  ``[i*length, (i+1)*length)`` with the last server absorbing the
  remainder (ref: array_table.cpp:14-20, 98-108);
- Get uses the whole-table sentinel key -1 (ref: array_table.cpp:29-35);
- Get replies are ``[server_id, values]`` and land at the server's offset
  (ref: array_table.cpp:95-106, 130-141).

The TPU redesign is on the server side: the shard is a ``jax.Array``
sharded over the local device mesh (padded to the shard count), and the
updater is a jit-compiled donated-buffer op — the reference's OpenMP
element loop (ref: src/updater/updater.cpp:24-31) becomes one fused XLA
update in HBM.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional

import jax
import numpy as np

from ..core.blob import Blob, is_device_array
from ..core.message import MsgType
from ..runtime import device_lock
from ..sharding import mesh as meshlib
from ..updater import AddOption, UpdateEngine, create_rule
from ..util.log import CHECK
from . import client_cache
from .client_cache import BlobCache
from .table_interface import (CacheOnlySink, DeviceSink, ServerTable,
                              TableSink, WorkerTable, issues_add,
                              issues_get)

_ALL_KEY = np.array([-1], dtype=np.int32)


def server_offsets(size: int, num_servers: int) -> List[int]:
    """Element ranges per server (ref: array_table.cpp:14-20)."""
    length = size // num_servers
    offsets = [i * length for i in range(num_servers)]
    offsets.append(size)
    return offsets


class ArrayWorker(WorkerTable):
    def __init__(self, size: int, dtype=np.float32, zoo=None):
        super().__init__(zoo=zoo)
        CHECK(size >= self._zoo.num_servers,
              "array table smaller than server count")
        self.size = int(size)
        self.dtype = np.dtype(dtype)
        self._num_server = self._zoo.num_servers
        self._offsets = server_offsets(self.size, self._num_server)
        # The sink of the last device Get issued, for get_device.
        self._last_device: Optional[DeviceSink] = None
        # Client cache (-max_get_staleness > 0): whole-blob — one entry
        # per server shard, a hit requires every shard fresh (array Gets
        # are whole-table). Device gets bypass (live jax.Array replies).
        bound = client_cache.staleness_bound()
        self._blob_cache: Optional[BlobCache] = None
        if bound > 0:
            self._blob_cache = BlobCache(bound, self._num_server,
                                         self._version_tracker)
            self._caches.append(self._blob_cache)
        self._pf_id: Optional[int] = None  # in-flight whole-table prefetch

    # -- public API (ref: array_table.cpp:29-66) --
    def get(self, out: Optional[np.ndarray] = None) -> np.ndarray:
        if out is None:
            out = np.empty(self.size, self.dtype)
        self.retrying_wait(lambda: self.get_async(out))
        return out

    @issues_get
    def get_async(self, out: Optional[np.ndarray] = None) -> int:
        if out is None:
            out = np.empty(self.size, self.dtype)
        CHECK(out.size == self.size, "output buffer size mismatch")
        if self._blob_cache is not None:
            shards = self._blob_cache.fetch_all()
            if shards is not None:
                # Same write form as the uncached reply path
                # (out[lo:hi] = values): reshape(-1) would silently
                # COPY a non-contiguous buffer and drop the fill.
                for sid, values in shards.items():
                    out[self._offsets[sid]:self._offsets[sid + 1]] = \
                        values
                return self._local_done()
        return self._get_to(TableSink(out, self._offsets),
                            [Blob(_ALL_KEY.view(np.uint8))])

    def prefetch_async(self) -> int:
        """Warm the whole-blob client cache: a Get whose sink is the
        cache alone; identical in-flight prefetches dedup to one wire
        request. No-op when the cache is disabled."""
        if self._blob_cache is None:
            return self._local_done()
        if self._pf_id is not None:
            return self._pf_id  # dedup: join the outstanding fetch
        if self._blob_cache.fresh_all():  # counter-free planning check
            return self._local_done()
        msg_id = self._new_request()
        self._sinks[msg_id] = CacheOnlySink()
        self._pf_id = msg_id
        self.add_completion(msg_id, self._on_prefetch_done)
        self._send_request(MsgType.Request_Get,
                           [Blob(_ALL_KEY.view(np.uint8))], msg_id)
        return msg_id

    def _on_prefetch_done(self, msg_id: int) -> None:
        if self._pf_id == msg_id:
            self._pf_id = None

    def add(self, delta: np.ndarray,
            option: Optional[AddOption] = None) -> None:
        self.retrying_wait(lambda: self.add_async(delta, option))

    @issues_add
    def add_async(self, delta, option: Optional[AddOption] = None) -> int:
        """Accepts host or device arrays; a device delta rides the whole
        stack without touching the host (the TPU-native hot path)."""
        if not is_device_array(delta):
            delta = np.ascontiguousarray(delta,
                                         dtype=self.dtype).reshape(-1)
        CHECK(int(np.prod(delta.shape)) == self.size, "delta size mismatch")
        delta_blob = Blob(delta.reshape(-1))
        if self._blob_cache is not None:
            # Self-invalidation: block the cache until the ack's version
            # stamp resolves it (read-your-writes).
            self._blob_cache.begin_add()
        mid = self.add_async_raw(
            Blob(_ALL_KEY.view(np.uint8)), delta_blob,
            option.to_blob() if option is not None else None)
        if self._blob_cache is not None:
            self.add_completion(
                mid, lambda _mid: self._blob_cache.finish_add())
        return mid

    # -- partition (ref: array_table.cpp:68-86) --
    def partition(self, blobs, msg_type) -> Dict[int, List[Blob]]:
        out: Dict[int, List[Blob]] = {}
        # typed() keeps device payloads on device — the per-server slice is
        # then a lazy device slice, not a host copy.
        values = blobs[1].typed(self.dtype) if len(blobs) >= 2 else None
        for server_id in range(self._num_server):
            shard = [blobs[0]]
            if values is not None:
                lo, hi = self._offsets[server_id], self._offsets[server_id + 1]
                shard.append(Blob(values[lo:hi]))
                if len(blobs) == 3:
                    shard.append(blobs[2])
            out[server_id] = shard
        return out

    # -- device-resident Get: shards stay in HBM end to end --
    def get_device(self):
        """Whole-table Get returning a device array (no host transfer).
        The reply shards are the servers' jitted snapshots in HBM."""
        self.wait(self.get_device_async())
        sink, self._last_device = self._last_device, None
        shards = sink.ordered()
        if len(shards) == 1:
            return shards[0]
        import jax.numpy as jnp
        # Worker-thread reassembly dispatch: guarded like any other
        # multi-device program (multi-zoo mode only; no-op otherwise).
        with device_lock.guard():
            return device_lock.settle(jnp.concatenate(shards))

    @issues_get
    def get_device_async(self) -> int:
        self._last_device = DeviceSink()
        return self._get_to(self._last_device,
                            [Blob(_ALL_KEY.view(np.uint8))])

    # -- reply (ref: array_table.cpp:95-106) --
    def process_reply_get(self, reply_blobs: List[Blob]) -> None:
        """One server's whole shard, to the sink its request
        registered: a device sink takes it still in HBM, a host one
        after the client cache (every host Get refreshes it, a prefetch
        does nothing else)."""
        sink = self._reply_sink()
        server_id = int(reply_blobs[0].as_array(np.int32)[0])
        if sink.device:
            values = reply_blobs[1].typed(self.dtype)
        else:
            values = reply_blobs[1].as_array(self.dtype)
            lo, hi = self._offsets[server_id], self._offsets[server_id + 1]
            CHECK(values.size == hi - lo, "reply shard size mismatch")
            if self._blob_cache is not None:
                self._blob_cache.store(server_id, values,
                                       self._reply_version)
        sink.place(None, values, self._reply_version, server_id)


class ArrayServer(ServerTable):
    def __init__(self, size: int, dtype=np.float32, zoo=None,
                 updater_type: Optional[str] = None, fill: float = 0.0):
        """``fill`` is the value every element starts at (a norm's
        scale starts at 1), written on the devices; an array of ``size``
        values gives each element its own (this server's part of it)."""
        super().__init__(zoo=zoo)
        self.dtype = np.dtype(dtype)
        num_servers = self._zoo.num_servers
        server_id = self._zoo.server_id
        # ref: array_table.cpp:98-108 — size/num_servers, last takes the
        # remainder.
        my_size = size // num_servers
        if server_id == num_servers - 1:
            my_size += size % num_servers
        self.size = my_size
        self.server_id = server_id
        mesh = meshlib.local_mesh()
        self._sharding = meshlib.sharded_1d(mesh)
        padded = meshlib.padded_size(my_size, meshlib.device_count(mesh))
        self._data = meshlib.zeros_sharded((padded,), self.dtype,
                                           self._sharding)
        if np.ndim(fill):
            first = server_id * (size // num_servers)
            host = np.zeros((padded,), self.dtype)
            host[:my_size] = np.asarray(fill)[first:first + my_size]
            with device_lock.guard():
                self._data = device_lock.settle(
                    jax.device_put(host, self._sharding))
        elif fill:
            with device_lock.guard():
                self._data = device_lock.settle(
                    self._data + self.dtype.type(fill))
        rule = None if updater_type is None \
            else create_rule(updater_type, dtype)
        self._engine = UpdateEngine(
            rule, (padded,), self.dtype, max(self._zoo.num_workers, 1),
            self._sharding)
        # Host twin of the rule's linearity: only a stateless rule
        # lets fused adds fold deltas before ONE apply
        # (docs/SERVER_ENGINE.md; the MatrixServer precedent). No rule
        # means plain accumulation — linear by construction.
        self._updater_stateless = True if rule is None else rule.stateless

    # -- server logic (ref: array_table.cpp:116-141) --
    def process_add(self, blobs: List[Blob]) -> None:
        CHECK(len(blobs) in (2, 3), "add needs [keys, values(, option)]")
        option = AddOption.from_blob(blobs[2]) if len(blobs) == 3 else None
        delta = blobs[1].typed(self.dtype)  # device deltas stay on device
        CHECK(int(np.prod(delta.shape)) == self.size,
              "add delta shard size mismatch")
        self._data = self._engine.apply_dense(self._data, delta, option)

    def process_get(self, blobs: List[Blob]) -> List[Blob]:
        key = int(blobs[0].as_array(np.int32)[0])
        CHECK(key == -1, "array table only serves whole-table gets")
        return [Blob(np.array([self.server_id], dtype=np.int32)),
                Blob(self._values())]

    # -- server-side request fusion (runtime/fusion.py,
    #    docs/SERVER_ENGINE.md; always entered under Server._lock_for)
    def fuse_eligible(self, blobs: List[Blob], is_get: bool) -> bool:
        """Whole-table host requests only: a Get must carry the -1
        sentinel (anything else raises in process_get — keep that on
        the serial path), an Add must carry a host delta and a
        stateless rule (fused adds FOLD deltas before one apply, which
        is only sum-equivalent for linear updates)."""
        if not blobs or blobs[0].on_device:
            return False
        if is_get:
            return blobs[0].size >= 4 \
                and int(blobs[0].as_array(np.int32)[0]) == -1
        if len(blobs) not in (2, 3) or blobs[1].on_device:
            return False
        return self._updater_stateless

    def process_fused_get(self, requests: List[List[Blob]]
                          ) -> List[List[Blob]]:
        """N whole-table Gets, ONE snapshot program: every reply
        shares the fresh copy (read-only on the reply path).
        Bit-identical to serial — the serial loop copies the same
        device state N times."""
        values = self._values()
        return [[Blob(np.array([self.server_id], dtype=np.int32)),
                 Blob(values)] for _ in requests]

    def process_fused_add(self, requests: List[List[Blob]]) -> None:
        """N dense Adds, ONE apply per option sub-group: left-fold the
        host deltas in arrival order, then apply once — linear for
        stateless rules, so sum-equivalent to the serial loop.
        Parse-first contract (table_interface.py): every delta is
        validated before the first apply."""
        runs: List[tuple] = []  # (option bytes, option, [deltas])
        for blobs in requests:
            CHECK(len(blobs) in (2, 3),
                  "add needs [keys, values(, option)]")
            option = AddOption.from_blob(blobs[2]) \
                if len(blobs) == 3 else None
            okey = blobs[2].as_array(np.uint8).tobytes() \
                if len(blobs) == 3 else None
            delta = np.asarray(blobs[1].typed(self.dtype)).ravel()
            CHECK(delta.size == self.size,
                  "add delta shard size mismatch")
            if not runs or runs[-1][0] != okey:
                runs.append((okey, option, []))
            runs[-1][2].append(delta)
        applied = 0
        for _, option, deltas in runs:
            try:
                acc = deltas[0].astype(self.dtype, copy=True)
                for d in deltas[1:]:
                    acc += d
                self._data = self._engine.apply_dense(self._data, acc,
                                                      option)
            except Exception as exc:  # noqa: BLE001
                from ..runtime.fusion import PartialFuseError
                raise PartialFuseError(applied, exc) from exc
            applied += len(deltas)

    def _values(self):
        """Logical-size snapshot of the padded device shard. Always a fresh
        buffer (jitted copy): the live storage gets donated away by the next
        update, which would invalidate a reply still holding a reference."""
        return self._snapshot(self._data)

    @functools.cached_property
    def _snapshot(self):
        n = self.size

        def snapshot(x):
            with jax.named_scope("mv.table.snapshot"):
                return jax.numpy.copy(x[:n])

        return jax.jit(snapshot)

    # -- checkpoint (ref: array_table.cpp:143-151) --
    def store(self, stream) -> None:
        stream.write(np.asarray(self._values()).tobytes())

    # -- async snapshot split (runtime/snapshot.py) --
    def snapshot_state(self):
        """Consistent capture under the caller's table lock: a jitted
        copy into a FRESH device buffer. Holding the live ``self._data``
        reference is NOT enough — the updater donates it away on the
        next add (``donate_argnums``), deleting the captured buffer
        under the snapshotter's feet. The copy stays on device; the
        host transfer + serialization run off the lock in
        ``write_snapshot``."""
        return device_lock.settle(self._snapshot(self._data))

    def write_snapshot(self, state, stream) -> None:
        """Off-lock serialization of a captured shard (store-format)."""
        stream.write(np.asarray(state).tobytes())

    def load(self, stream) -> None:
        raw = stream.read(self.size * self.dtype.itemsize)
        values = np.frombuffer(raw, dtype=self.dtype)
        CHECK(values.size == self.size, "checkpoint size mismatch")
        padded = self._data.shape[0]
        if padded != self.size:
            values = np.concatenate(
                [values, np.zeros(padded - self.size, self.dtype)])
        with device_lock.guard():
            self._data = device_lock.settle(
                jax.device_put(values, self._sharding))

    @property
    def raw(self):
        return self._values()

"""2-D row-sharded distributed matrix table (dense + sparse).

TPU-native equivalent of the reference's matrix tables — the row-sharded
``MatrixWorkerTable/MatrixServerTable``
(ref: include/multiverso/table/matrix_table.h:16-127,
src/table/matrix_table.cpp:13-468) unified with the sparse variant's
per-worker dirty-row tracking (ref: src/table/sparse_matrix_table.cpp:14-314,
include/multiverso/table/matrix.h:14-123). Semantics preserved:

- row-range partition: each server owns ``num_row/num_servers`` rows, last
  takes the remainder; degenerate one-row-per-server layout when
  ``num_row < num_servers`` (ref: matrix_table.cpp:23-45);
- request keys: sentinel -1 = whole table, else an int32 row-id vector;
  row -> server by ``row / (num_row/num_servers)`` clamped to the last
  server (ref: matrix_table.cpp:267-276);
- whole-table Get replies carry ``[keys, values, server_id]`` so the worker
  places the shard; row Gets reply ``[row_ids, values]``
  (ref: matrix_table.cpp:317-341, 420-454);
- sparse mode: the server keeps an ``up_to_date[worker][row]`` bitmap —
  an Add dirties the row for every *other* worker, a Get (whose GetOption
  names the worker) returns only that worker's dirty rows and marks them
  clean (ref: sparse_matrix_table.cpp:200-258); with pipelining each
  worker counts as two logical consumers (ref: sparse_matrix_table.cpp:
  184-197).

TPU redesign: each server shard is a row-sharded ``jax.Array``; row
Gets/Adds are XLA gather/scatter jitted over power-of-two row buckets, and
whole-table ops are single fused device ops.
"""

from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import jax
import numpy as np

from ..core.blob import Blob, is_device_array
from ..core.message import PEER_LOST_MARK, Message, MsgType
from ..runtime import device_lock
from ..runtime import replica as replica_mod
from ..runtime import shard_map as shard_map_mod
from ..runtime.zoo import CONTROLLER_RANK
from ..util import chaos
from ..util.dashboard import count as count_event
from ..util.dashboard import laps, monitor
from . import client_cache
from .client_cache import RowCache
from ..sharding import mesh as meshlib
from ..sharding.rows import row_offsets
from ..updater import AddOption, GetOption, UpdateEngine, create_rule
from ..updater.engine import DEVICE_KEYS_REFUSED, bucket_size, pad_ids
from ..util import log, wire_codec
from ..util.configure import define_bool, get_flag
from ..util.log import CHECK
from ..util.quantization import OneBitFilter
from .table_interface import (CacheOnlySink, DeviceSink, RpcTimeoutError,
                              ServerTable, TableRequestError, TableSink,
                              WorkerTable, issues_add, issues_get)
from ..runtime.net import PeerLostError

define_bool("sparse_compress", True,
            "run sparse-matrix wire traffic through the compact wire "
            "codec (ref: sparse_matrix_table.cpp:148-153; float64-pair "
            "format replaced by int32-index + typed-value frames)")
define_bool("verify_device_ids", False,
            "debug: on the first fused add+dirty-get, read the "
            "row_ids_device mirror back to the host and CHECK it "
            "matches the host ids (turns the documented silent-"
            "corruption mode of a disagreeing mirror into a loud "
            "failure; costs one device->host transfer)")
define_bool("one_bit_push", False,
            "1-bit quantize matrix Add traffic (sign bitmap + per-sign "
            "means, worker-side error feedback) — ~32x smaller pushes "
            "over cross-process transports; completes the reference's "
            "empty OneBitsFilter stub (quantization_util.h:160-161)")

_ALL_KEY = np.array([-1], dtype=np.int32)
# Sentinel -2: whole-table dirty get with a DEVICE-resident reply
# (in-process extension; -1 keeps the reference's host-reply semantics,
# ref: matrix_table.cpp:267-276 sentinel handling).
_ALL_KEY_DEVICE_REPLY = np.array([-2], dtype=np.int32)
# Sentinel -4: FUSED sparse add + dirty get — semantically the exact
# composition of add_rows and get_dirty_device, executed as ONE device
# program server-side (the 2-program roundtrip pays two per-dispatch
# launch costs, not measured on the current machine; fusing halves
# the launches).
_ADD_GET_DIRTY_KEY = np.array([-4], dtype=np.int32)


def _onebit_blobs(chunk: np.ndarray):
    """Encode one server's (error-feedback-adjusted) delta chunk as
    [sign bits, meta]; meta = [pos_mean, neg_mean, element count].
    Returns (blobs, residual) — the caller accumulates the residual into
    its feedback buffer."""
    encoded, residual = OneBitFilter().encode(chunk)
    bits, pos_mean, neg_mean, size = encoded
    meta = np.array([pos_mean, neg_mean, float(size)], np.float64)
    return [Blob(bits), Blob(meta)], residual


def _onebit_decode(bits_blob: Blob, meta_blob: Blob) -> np.ndarray:
    meta = meta_blob.as_array(np.float64)
    return OneBitFilter().decode(
        (bits_blob.as_array(np.uint8), float(meta[0]),
         float(meta[1]), int(meta[2])))


def _compress_values(values: np.ndarray, lossy: bool = False):
    """values -> ([codec frame blob], residual). One self-describing
    frame replaces the old [float64 pairs, size_record] two-blob layout
    (ref layout: quantization_util.h:37-137) — int32 indices + typed
    values, 8 bytes/pair lossless instead of 16. ``residual`` is the
    error-feedback vector when a lossy tier was chosen, else None."""
    frame, residual = wire_codec.encode_blob(
        np.asarray(values).reshape(-1), lossy=lossy)
    return [Blob(np.frombuffer(frame, np.uint8))], residual


def _decompress_values(values_blob: Blob, dtype) -> np.ndarray:
    full = wire_codec.decode_blob(values_blob.as_array(np.uint8))
    return full.astype(dtype, copy=False)


def _is_codec_blob(blob: Blob) -> bool:
    """True when the blob carries a codec frame. Receivers with
    ``_compress`` set sniff before decoding so a peer sending RAW
    values (cross-rank -sparse_compress flag mismatch) degrades to the
    uncompressed layout instead of raising inside the actor loop and
    stranding the requester's waiter. NOTE this does NOT extend to the
    REMOVED float64-pair format: a pre-codec build's compressed
    traffic is a declared wire break (docs/WIRE_FORMAT.md) — its
    3-blob pair layout fails the blob-count/size CHECKs loudly rather
    than being decoded."""
    return not blob.on_device \
        and wire_codec.is_codec_frame(blob.as_array(np.uint8))


def _shaped_rows(values, n_rows: int, num_col: int):
    """Reshape to [n_rows, num_col] only when needed (a no-op reshape on
    a device array still dispatches a device op)."""
    if tuple(np.shape(values)) != (n_rows, num_col):
        values = values.reshape(n_rows, num_col)
    return values


def _shard_cuts(dest: Optional[np.ndarray], n: int) -> List:
    """How a host row-id request of ``n`` keys is cut into per-server
    shards: ``[(server id, index)]``, decided from ``dest`` (each key's
    server, None = one server holds them all) alone. Where ``dest`` is
    non-decreasing — sorted keys under the division rule, or under a
    shard map whose owners rise with the row — each server's shard is
    one run of the request and the index is a ``slice``: indexing the
    request's keys and values with it gives VIEWS, nothing is copied.
    Anything else (unsorted keys over several servers, a map that
    interleaves owners, re-routed replica rows) gets a boolean mask per
    server, in server order: the gathered copy. Both forms give each
    server the same bytes."""
    if n == 0:
        return []
    if dest is None:
        return [(0, slice(0, n))]
    step = np.diff(dest)
    if step.size == 0 or int(step.min()) >= 0:
        edges = [0, *(np.flatnonzero(step) + 1).tolist(), n]
        return [(int(dest[lo]), slice(lo, hi))
                for lo, hi in zip(edges[:-1], edges[1:])]
    return [(int(sid), dest == sid) for sid in np.unique(dest)]


def _trim_rows(values, n_rows: int):
    """Slice gather output down to the real row count only when padding
    added rows (full-range device slices still dispatch)."""
    if values.shape[0] != n_rows:
        values = values[:n_rows]
    return values


class _RowsSink:
    """A host row Get: every position of ``row_ids`` whose id a reply
    shard carries gets that row in ``out``. Ids may repeat (power-of-two
    padded row sets repeat the last id thousands of times), and a shard
    carries one server's key subset, possibly only the rows a partial
    cache hit still missed: ``place_rows`` picks the form, one copy where
    the shard is the request or a run of a sorted one. That form is
    known from the keys alone (``run_start``), before any value is on
    the host, so a shard placed so may also arrive in row-range pieces
    (``place_run``)."""

    device = False
    __slots__ = ("row_ids", "out")

    def __init__(self, row_ids: np.ndarray, out: np.ndarray):
        self.row_ids = row_ids
        self.out = out

    def place(self, keys, values, version, server) -> None:
        with monitor("CLIENT_PLACE_ROWS"):
            client_cache.place_rows(keys, values, self.row_ids, self.out)

    def run_start(self, keys) -> int:
        """Where a shard of ``keys`` is copied straight in, or -1."""
        return client_cache.run_start(keys, self.row_ids)

    def place_run(self, start: int, pieces) -> None:
        """``place`` for a shard ``run_start`` found at ``start``, its
        rows handed over piece by piece: CLIENT_PLACE_ROWS still counts
        one entry a shard, the placing alone and none of the time the
        iterator took to produce a piece."""
        placing = laps("CLIENT_PLACE_ROWS")

        def timed():
            for piece in pieces:
                with placing:  # from the hand-over to the next ask
                    yield piece

        try:
            client_cache.place_run(start, timed(), self.out)
        finally:
            placing.close()


class _ScatterRead:
    """One in-flight scatter-gather serving read (docs/SERVING.md), the
    sink of each of its sub-requests: ``rows`` is the SORTED UNIQUE
    requested id vector; reply shards (worker actor thread) place values
    and per-row fetch versions at ``searchsorted`` positions. Requester
    threads read the buffers only after every sub-request's waiter
    completed, so no locking is needed — each reply writes a disjoint
    position set."""

    device = False
    __slots__ = ("rows", "out", "versions")

    def __init__(self, rows: np.ndarray, out: np.ndarray,
                 versions: np.ndarray):
        self.rows = rows
        self.out = out
        self.versions = versions

    def place(self, keys, values, version, server) -> None:
        if keys.size == 0:
            return
        with monitor("CLIENT_PLACE_ROWS"):
            pos = np.minimum(np.searchsorted(self.rows, keys),
                             self.rows.size - 1)
            ok = self.rows[pos] == keys  # repairs may widen to
            pos = pos[ok]          # rows outside this read's set
            self.out[pos] = values[ok]
            if version >= 0:
                self.versions[pos] = np.maximum(
                    self.versions[pos], int(version))


@dataclass
class MatrixTableOption:
    """ref: include/multiverso/table/matrix.h:116-123."""
    num_row: int
    num_col: int
    dtype: object = np.float32
    is_sparse: bool = False
    is_pipeline: bool = False
    updater_type: Optional[str] = None


class MatrixWorker(WorkerTable):
    def __init__(self, num_row: int, num_col: int, dtype=np.float32,
                 is_sparse: bool = False, is_pipeline: bool = False,
                 zoo=None, updater_type: Optional[str] = None):
        super().__init__(zoo=zoo)
        self.num_row = int(num_row)
        self.num_col = int(num_col)
        self.dtype = np.dtype(dtype)
        self.is_sparse = bool(is_sparse)
        # Consumer-slot count, mirroring the server's bitmap height —
        # lets caller-side CHECKs reject a bad consumer id instead of
        # hanging on a reply the server actor will never send.
        self._num_consumers = max(self._zoo.num_workers, 1) \
            * (2 if is_pipeline else 1)
        # Device-key row adds may carry duplicate ids, which only sum
        # correctly under stateless rules. The server-side engine CHECK
        # fires inside the server actor, where _safe_dispatch swallows it
        # and the Add ack never comes — so a misconfigured trainer hangs
        # in wait() instead of raising. Validate here, in the CALLER's
        # thread (the factory passes the table's updater_type along),
        # deriving statelessness from the rule registry so this cannot
        # drift from the engine's actual state handling (e.g. int tables
        # and unknown names both resolve to the stateless default adder).
        rule = create_rule(updater_type, self.dtype)
        self._updater_stateless = rule.stateless
        self._updater_name = rule.name
        self._device_keys_ok = rule.sums_duplicates
        # Wire compression for sparse traffic, both directions, as the
        # reference does unconditionally (sparse_matrix_table.cpp:148-153);
        # here behind a flag read at table-construction time — and only
        # when there IS a wire: an in-process fabric moves object
        # references, so filtering would only burn CPU and force device
        # payloads through host bytes.
        self._compress = (self.is_sparse
                          and not self._zoo.net.in_process
                          and bool(get_flag("sparse_compress")))
        # Lossy value tiers (fp16 / int8-with-per-chunk-scale) for Add
        # pushes only, with worker-side error feedback; pulls stay
        # lossless (the server keeps no per-consumer residual state).
        self._lossy = (self._compress and self.dtype == np.float32
                       and bool(get_flag("wire_codec_lossy")))
        # 1-bit push quantization (dense float32 tables; sparse traffic
        # already rides the wire codec). Pulls stay full precision — only
        # gradient pushes quantize. The worker-side error-feedback buffer
        # is table-shaped (1-bit SGD's standard memory cost).
        self._one_bit = (not self.is_sparse
                         and self.dtype == np.float32
                         and bool(get_flag("one_bit_push")))
        self._residual: Optional[np.ndarray] = None
        # Frozen creation-time layout, possibly over only the first
        # -shard_initial_servers servers (the rest are standbys a
        # later reshard can grow onto — docs/SHARDING.md).
        self._init_active = shard_map_mod.initial_active_servers(
            self._zoo.num_servers)
        self._offsets = row_offsets(self.num_row, self._init_active)
        self._num_server = len(self._offsets) - 1  # actual servers used
        self._row_length = max(self.num_row // self._num_server, 1)
        # Live elastic resharding (runtime/shard_map.py): the adopted
        # epoch-stamped map replaces the frozen division rule; None =
        # never resharded, byte-identical routing to the reference.
        # Worker actor thread swaps it; requester threads read it —
        # one attribute, GIL-atomic.
        self._shard_map: Optional[shard_map_mod.ShardMap] = None
        # The sink of the last device Get issued, for take_device_rows
        # (the trainers call it after ``wait``).
        self._last_device: Optional[DeviceSink] = None
        self._mirror_verified = False  # -verify_device_ids: once per table
        # Client cache (-max_get_staleness > 0): row-granular, DENSE
        # host-path row Gets only. Sparse tables are excluded — their
        # dirty-row protocol IS a server-tracked staleness cache, and a
        # client copy on top would double-apply the bookkeeping. Device
        # replies (live jax.Arrays) bypass too: the host cache cannot
        # hold them without forcing a device->host copy per hit.
        bound = client_cache.staleness_bound()
        self._row_cache: Optional[RowCache] = None
        if not self.is_sparse and not get_flag("sync", False):
            # ALWAYS constructed on the dense host path (bound 0 =
            # inactive pass-through, byte-identical behavior to the
            # old no-cache construction) so the autotune layer can
            # widen -max_get_staleness on a LIVE table — the cache's
            # apply hooks rebind the bound; _live_cache() below keeps
            # every hot path on the old code shape while inactive
            # (docs/AUTOTUNE.md). Sync mode stays construction-time
            # disabled: a locally served Get would bypass the vector
            # clocks, so no hook may ever activate it.
            self._row_cache = RowCache(
                bound, self._server_of_rows,
                max(self._zoo.num_servers, self._num_server),
                self._version_tracker,
                server_of_rises=lambda: self._shard_map is None)
            self._caches.append(self._row_cache)
        # In-flight prefetch registry (+ dedup/join): msg_id -> sorted
        # unique ids being fetched; _pf_by_key dedups identical
        # prefetches; _pf_joined holds the ids of Gets deferred onto an
        # in-flight prefetch (served from the cache — or forwarded to
        # the wire — when it completes; their buffers are their sinks').
        # Guarded by _pf_lock: prefetches/joins issue on the requester's
        # thread, completion runs on the worker actor's.
        self._pf_lock = threading.Lock()
        self._pf_rows: Dict[int, np.ndarray] = {}
        self._pf_by_key: Dict[bytes, int] = {}
        self._pf_joined: Dict[int, List[int]] = {}
        # Hot-shard read replication routing (runtime/replica.py,
        # docs/SHARDING.md): the promoted-row map re-routes the
        # replicated subset of a host row Get to holder servers
        # (per-row stripe, or the co-located shard when this rank
        # hosts one); Adds always go to the owners (write-through).
        # Dense multi-server tables only, matching the server side.
        # _replica_sent records, per request id, which foreign rows
        # went to which holder so each holder's reply can be diffed
        # for repairs. Worker actor thread only.
        self._replica_router = None
        self._replica_sent: Dict[int, Dict[int, np.ndarray]] = {}
        if (not self.is_sparse and self._num_server > 1
                and replica_mod.replication_enabled()):
            local_sid = self._zoo.rank_to_server_id(self._zoo.rank)
            self._replica_router = replica_mod.ReplicaRouter(
                self._num_server, salt=max(self._zoo.rank, 0),
                preferred=local_sid if local_sid >= 0 else None)

    def _live_cache(self) -> Optional[RowCache]:
        """The row cache when ACTIVE (live bound > 0), else None — the
        gate every read-path use site goes through, so an inactive
        cache costs exactly one attribute check and the control flow
        matches the pre-dynamic-flag no-cache path (the store/fetch
        self-guards in RowCache cover mid-request deactivation)."""
        cache = self._row_cache
        if cache is not None and cache.active:
            return cache
        return None

    def _server_of_rows(self, rows: np.ndarray) -> np.ndarray:
        """Vectorized row ids -> owning server ids (the one sharding
        rule; shared by partition routing, the client cache's
        freshness checks, the replica protocol's owner attribution and
        the serving tier's version attribution). The frozen division
        rule until an epoch-stamped shard map is adopted
        (docs/SHARDING.md elastic resharding)."""
        smap = self._shard_map
        if smap is not None:
            return smap.owner_of(rows)
        return np.minimum(rows // self._row_length, self._num_server - 1)

    # -- elastic resharding: worker side (runtime/shard_map.py) --
    def apply_shard_map(self, epoch: int, smap, alive_sids) -> None:
        """Epoch-stamped map broadcast (worker actor thread — the same
        thread that partitions, so routing never races the swap).
        Moved intervals invalidate client caches through the PR-6
        generation-change path BEFORE the swap (``note_shard_moved``,
        table_interface.py), and the replica router reconciles its
        dead marks against the broadcast's live-server view — or
        retires outright once the map is truly dynamic."""
        old = self._shard_map
        if old is not None and epoch <= old.epoch:
            return
        if old is None:
            old = shard_map_mod.ShardMap.initial(
                self.num_row, self._zoo.num_servers,
                active=self._init_active)
        moved = old.diff_moved(smap)
        for old_sid in sorted({m[2] for m in moved}):
            self.note_shard_moved(old_sid)
        self._shard_map = smap
        if self._replica_router is not None:
            if moved or (old is not None and old.epoch > 0) \
                    or smap.epoch > 0:
                self._replica_router.deactivate()
            else:
                self._replica_router.reconcile(alive_sids)

    def shard_epoch(self) -> int:
        return self._shard_map.epoch if self._shard_map is not None \
            else -1

    def shard_owner_sids(self):
        return self._shard_map.owner_sids() \
            if self._shard_map is not None else None

    def shard_layout(self):
        smap = self._shard_map
        if smap is None:
            return None
        return (smap.bounds.tolist(), smap.owners.tolist())

    def reshard_space(self) -> int:
        """Dense host-path matrix tables reshard at row granularity;
        sparse tables do not (their per-consumer dirty bitmaps are
        keyed to the frozen layout — the server NACKs a Begin and the
        controller rolls the move back)."""
        return 0 if self.is_sparse else self.num_row

    def observed_versions(self) -> Dict[int, int]:
        """Latest shard version this worker has OBSERVED, per server id
        (-1 before any reply). Serving-tier metadata (docs/SERVING.md):
        staleness is measured against these, exactly as the client
        cache measures it."""
        sids = range(self._num_server) if self._shard_map is None \
            else self._shard_map.owner_sids()
        return {int(s): self._version_tracker.latest(int(s))
                for s in sids}

    def _check_row_ids(self, row_ids: np.ndarray
                       ) -> Optional[Tuple[int, int]]:
        """Fail fast in the CALLER on out-of-range ids. partition() runs
        inside the worker actor, where an exception is swallowed after
        reset(msg_id, 0) — the caller would see a 'successful' request
        backed by uninitialized memory (stray negative) or block forever
        on a shard routed to server -1 (negative id in a vector).
        Returns the ids' smallest and largest value (None when there
        are no ids): what a row Add's cache fence is named from."""
        if not row_ids.size:
            return None
        lo, hi = int(row_ids.min()), int(row_ids.max())
        CHECK(lo >= 0 and hi < self.num_row,
              "row ids out of range [0, num_row)")
        return lo, hi

    def _check_frozen_layout(self, what: str) -> None:
        """Device-resident fast paths bake the frozen per-server
        layout into shapes and program caches (per-server segments,
        broadcast masks, fused jits) — they cannot follow a live map.
        Elastic clusters use the host row path; fail in the CALLER."""
        CHECK(self._shard_map is None,
              f"{what} needs the frozen shard layout — this table "
              f"adopted a dynamic shard map (docs/SHARDING.md)")

    # -- Get API (ref: matrix_table.cpp:58-105) --
    def get(self, out: Optional[np.ndarray] = None) -> np.ndarray:
        out = self._table_out(out)
        self.retrying_wait(lambda: self.get_async(out))
        return out

    def _table_out(self, out: Optional[np.ndarray]) -> np.ndarray:
        if out is None:
            # Sparse whole-table gets return only dirty rows, so a fresh
            # destination must be zeroed or the clean rows would surface
            # uninitialized memory; callers wanting incremental semantics
            # should pass a persistent out buffer.
            alloc = np.zeros if self.is_sparse else np.empty
            out = alloc((self.num_row, self.num_col), self.dtype)
        CHECK(out.shape == (self.num_row, self.num_col), "bad output shape")
        return out

    @issues_get
    def get_async(self, out: Optional[np.ndarray] = None) -> int:
        out = self._table_out(out)
        if self._shard_map is not None and not self.is_sparse:
            # Dynamic map: the whole-table sentinel's reply placement
            # assumes the frozen per-server offsets — route as an
            # all-rows row Get instead (replies carry keys, placement
            # is layout-free). Costs the id vector on the wire; full-
            # table pulls on an elastically resharded table are not a
            # hot path (docs/SHARDING.md).
            return MatrixWorker.get_rows_async.__wrapped__(  # one span
                self, np.arange(self.num_row, dtype=np.int32), out)
        return self._request_get(Blob(_ALL_KEY.view(np.uint8)),
                                 TableSink(out, self._offsets))

    def get_rows(self, row_ids, out: Optional[np.ndarray] = None
                 ) -> np.ndarray:
        row_ids, out = self._rows_out(row_ids, out)
        self.retrying_wait(lambda: self.get_rows_async(row_ids, out))
        return out

    def _rows_out(self, row_ids, out: Optional[np.ndarray]):
        row_ids = np.ascontiguousarray(row_ids, dtype=np.int32).reshape(-1)
        if out is None:
            out = np.empty((row_ids.size, self.num_col), self.dtype)
        CHECK(out.shape == (row_ids.size, self.num_col), "bad output shape")
        return row_ids, out

    @issues_get
    def get_rows_async(self, row_ids,
                       out: Optional[np.ndarray] = None) -> int:
        sink = _RowsSink(*self._rows_out(row_ids, out))
        self._check_row_ids(sink.row_ids)
        if self._live_cache() is not None:
            # Partial-hit serve: fresh rows fill their positions
            # locally; only the MISSING unique rows go to the wire (the
            # reply placement already handles subset keys). A fully
            # fresh request never leaves the process.
            missing = self._row_cache.fetch_into(sink.row_ids, sink.out)
            if missing.size == 0:
                return self._local_done()
            # Dedup: missing rows already being fetched by an in-flight
            # prefetch — defer onto its completion instead of issuing a
            # second wire message for the same rows.
            joined = self._join_inflight(missing, sink)
            if joined is not None:
                return joined
            return self._request_get(Blob(missing.view(np.uint8)), sink)
        return self._request_get(Blob(sink.row_ids.view(np.uint8)), sink)

    # -- serving-tier read (serving/frontend.py, docs/SERVING.md) --
    def read_rows_versioned(self, row_ids, out: Optional[np.ndarray]
                            = None):
        """``get_rows`` plus the version metadata an inference response
        must carry: ``(values, meta)`` where meta holds

        - ``served_version``: the MINIMUM fetch version among the
          requested rows (how old the oldest byte served is);
        - ``latest_version``: the newest shard version this worker has
          observed among the shards the request touched;
        - ``max_staleness``: the largest per-row (shard latest - row
          fetch version) gap — by the cache's freshness invariant this
          never exceeds ``staleness_bound`` at serve time;
        - ``staleness_bound``: the active ``-max_get_staleness`` bound
          (0 = cache disabled, every row crossed the wire);
        - ``cache_hit``: True when the whole request was served locally
          (no wire message at all);
        - ``rows_requested`` / ``rows_cached``: unique rows asked for
          and how many of them the cache covered (row-granular
          coverage — a partial hit fetches only the remainder).

        The shard latests are read BEFORE the get and the per-row
        versions AFTER it: versions only ever grow, so every served
        row passed its freshness check against a latest AT LEAST the
        pre-read (``v >= latest_at_lookup - bound >= pre_latest -
        bound``), and a wire-fetched row's version postdates the
        pre-read entirely — the reported ``max_staleness <=
        staleness_bound`` invariant is race-free even while a trainer
        pushes Adds concurrently. (Reading latest AFTER the get would
        measure rows against observations the serve never saw and
        overshoot the bound spuriously.)

        The hit counters are read around the Get, so the serving
        frontend serializes calls per table.
        """
        row_ids = np.ascontiguousarray(row_ids,
                                       dtype=np.int32).reshape(-1)
        uniq = np.unique(row_ids)
        sids = self._server_of_rows(uniq)
        latest_by_sid = {int(s): self._version_tracker.latest(int(s))
                         for s in np.unique(sids)}
        cache = self._live_cache()
        hits_before = cache.hits if cache is not None else 0
        rows_hit_before = cache.rows_hit if cache is not None else 0
        values = self.get_rows(row_ids, out)
        cache_hit = (cache is not None
                     and cache.hits == hits_before + 1)
        # Row-granular coverage: how many of the requested unique rows
        # the cache served locally (the miss fetched only the rest).
        # Exact under the serving frontend's per-table serialization —
        # fetch_into is the only rows_hit writer and only get paths
        # call it.
        rows_cached = (cache.rows_hit - rows_hit_before
                       if cache is not None else 0)
        latest = max(latest_by_sid.values(), default=-1)
        served = latest
        max_stale = 0
        if cache is not None:
            versions = cache.versions_of(uniq)
            for r, s in zip(uniq, sids):
                v = versions.get(int(r))
                if v is None:
                    continue  # wire-fetched fresh / evicted: staleness 0
                served = min(served, v)
                max_stale = max(max_stale,
                                latest_by_sid[int(s)] - v)
                latest = max(latest, v)  # a fetch newer than the
                # pre-read keeps served <= latest consistent
        return values, {
            "served_version": int(served),
            "latest_version": int(latest),
            "max_staleness": int(max(max_stale, 0)),
            "staleness_bound": int(cache.bound
                                   if cache is not None else 0),
            "cache_hit": bool(cache_hit),
            "rows_requested": int(uniq.size),
            "rows_cached": int(rows_cached)}

    def read_rows_scatter(self, row_ids):
        """Concurrent scatter-gather serving read (docs/SERVING.md
        fleet section): one read is several Gets, one per owning
        server shard, all into the one ``_ScatterRead``; like every Get
        it owns its buffers end to end, so any number of serving
        threads may read concurrently while a trainer Adds.

        The missing (cache-cold) rows fan out as ONE sub-request per
        owning server shard; ``partition`` routes each exactly as a
        normal Get (replica striping, repair machinery, version
        stamps all apply), but a failure — dead shard owner, RPC
        timeout — is contained to that sub-request's row group
        instead of failing the whole read.

        Returns ``(values, info)``: ``values`` is ``[n, num_col]``
        over the SORTED UNIQUE requested rows ``info["rows"]``;
        ``info`` additionally carries per-row ``versions`` (fetch
        version, -1 = failed/unstamped), ``owners`` (owning server
        ids at issue time), ``cached`` (served locally), the
        pre-fetch ``latest_by_sid`` snapshot (read BEFORE any fetch,
        the ``read_rows_versioned`` anchoring rule, so per-row
        ``latest_by_sid[owner] - version <= staleness bound`` is
        race-free under concurrent Adds), ``failed`` (sorted unique
        row ids whose sub-request failed — their positions in
        ``values`` are UNDEFINED), ``failed_fatal`` (the subset whose
        failure was NOT a typed retryable one — callers map per-row:
        retryable rows back off and re-issue, e.g. HTTP 503 +
        Retry-After) and ``retryable`` (no fatal rows at all)."""
        CHECK(not self.is_sparse,
              "scatter reads are for dense host-path tables")
        rows = np.unique(np.ascontiguousarray(
            row_ids, dtype=np.int32).reshape(-1))
        self._check_row_ids(rows)
        n = rows.size
        out = np.empty((n, self.num_col), self.dtype)
        owners = self._server_of_rows(rows)
        # Generation AND shard latests are read BEFORE any fetch (the
        # read_rows_versioned anchoring rule): values fetched across a
        # concurrent reshard/rejoin get tagged with the OLD generation,
        # so a derived cache storing them invalidates — tagging after
        # the fetch could certify pre-move values as current.
        generation = self.cache_generation()
        latest_by_sid = {int(s): self._version_tracker.latest(int(s))
                         for s in np.unique(owners)}
        versions = np.full(n, -1, np.int64)
        cached = np.zeros(n, bool)
        cache = self._live_cache()
        missing = rows
        if cache is not None:
            missing = cache.fetch_into(rows, out)
            if missing.size < n:
                hit_pos = np.flatnonzero(~np.isin(rows, missing))
                cached[hit_pos] = True
                vmap = cache.versions_of(rows[hit_pos])
                for p in hit_pos:
                    # A row evicted between fetch_into and versions_of
                    # reports the shard latest (staleness 0) — the
                    # read_rows_versioned precedent.
                    versions[p] = vmap.get(
                        int(rows[p]), latest_by_sid[int(owners[p])])
        failed_groups: List[np.ndarray] = []
        fatal_groups: List[np.ndarray] = []
        if missing.size:
            entry = _ScatterRead(rows, out, versions)
            group_sids = self._server_of_rows(missing)
            groups = []
            for sid in np.unique(group_sids):
                grp = np.ascontiguousarray(missing[group_sids == sid])
                groups.append((self._get_to(
                    entry, [Blob(grp.view(np.uint8))]), grp))
            for msg_id, grp in groups:
                try:
                    self.wait(msg_id)
                except (PeerLostError, RpcTimeoutError):
                    failed_groups.append(grp)
                except TableRequestError:
                    # Non-retryable: kept SEPARATE from the
                    # retryable groups so a caller can decide per
                    # ROW — one fatal group must not turn another
                    # group's transient failure into a hard error.
                    failed_groups.append(grp)
                    fatal_groups.append(grp)
        failed = np.unique(np.concatenate(failed_groups)) \
            .astype(np.int32) if failed_groups \
            else np.empty(0, np.int32)
        failed_fatal = np.unique(np.concatenate(fatal_groups)) \
            .astype(np.int32) if fatal_groups \
            else np.empty(0, np.int32)
        return out, {
            "rows": rows, "versions": versions, "owners": owners,
            "cached": cached, "latest_by_sid": latest_by_sid,
            "failed": failed, "failed_fatal": failed_fatal,
            "retryable": failed_fatal.size == 0,
            "generation": generation}

    # -- client-cache prefetch + in-flight Get dedup --
    def prefetch_rows_async(self, row_ids) -> int:
        """Warm the client cache for ``row_ids``: a Get whose sink is
        the cache alone, so a later ``get_rows`` for (a subset of) these
        rows hits locally or joins the in-flight fetch. Double-buffering
        trainers call this for step i+1's rows while step i computes,
        overlapping wire latency with device work. Returns a request id
        (``wait`` is optional — the trainer usually never waits).
        No-op when the cache is inactive (``-max_get_staleness=0`` or
        BSP sync mode, where an extra Get would desync vector clocks)."""
        if self._live_cache() is None:
            return self._local_done()
        rows = np.unique(np.ascontiguousarray(
            row_ids, dtype=np.int32).reshape(-1))
        self._check_row_ids(rows)
        # Fetch only what the cache is actually missing — prefetching
        # already-fresh rows would waste the wire it exists to save.
        rows = self._row_cache.missing_of(rows)
        if rows.size == 0:
            return self._local_done()
        key = rows.tobytes()
        with self._pf_lock:
            existing = self._pf_by_key.get(key)
            if existing is not None:
                return existing  # identical prefetch already in flight
            msg_id = self._new_request()
            self._sinks[msg_id] = CacheOnlySink()
            self._pf_rows[msg_id] = rows
            self._pf_by_key[key] = msg_id
            # Registered BEFORE the send: the completion sweep must be
            # able to find this prefetch however fast the reply lands.
            self.add_completion(msg_id, self._on_prefetch_done)
        count_event(client_cache.PREFETCH)
        self._send_request(MsgType.Request_Get,
                           [Blob(rows.view(np.uint8))], msg_id)
        return msg_id

    def _join_inflight(self, missing: np.ndarray,
                       sink: _RowsSink) -> Optional[int]:
        """If an in-flight prefetch covers every MISSING row, defer
        this Get onto it: completion re-serves from the cache, fetching
        over the wire only what still isn't there. Either way the
        returned id completes."""
        with self._pf_lock:
            if not self._pf_rows:
                return None
            for pf_id, pf_rows in self._pf_rows.items():
                if np.isin(missing, pf_rows).all():
                    msg_id = self._new_request()
                    self._sinks[msg_id] = sink
                    self._pf_joined.setdefault(pf_id, []).append(msg_id)
                    count_event(client_cache.JOIN)
                    return msg_id
        return None

    def _on_prefetch_done(self, pf_id: int) -> None:
        """Prefetch completion (worker actor thread): retire the
        registry entry and settle every joined Get — from the cache for
        whatever landed/survived, forwarding a wire request only for
        rows still missing (invalidation raced the prefetch)."""
        with self._pf_lock:
            rows = self._pf_rows.pop(pf_id, None)
            if rows is not None:
                self._pf_by_key.pop(rows.tobytes(), None)
            joined = self._pf_joined.pop(pf_id, [])
        for msg_id in joined:
            sink = self._sinks.get(msg_id)
            if sink is None:
                continue  # timed out meanwhile: nobody waits for it
            # count_stats=False: the joined Get already counted its
            # miss at issue time — the re-serve must not double-count.
            missing = self._row_cache.fetch_into(sink.row_ids, sink.out,
                                                 count_stats=False)
            if missing.size == 0:
                self.notify(msg_id)
            else:
                self._send_request(MsgType.Request_Get,
                                   [Blob(missing.view(np.uint8))],
                                   msg_id)

    def get_rows_device(self, row_ids):
        """Device-resident row pull: returns ``[k, num_col]`` as a
        ``jax.Array`` assembled from per-server device shards — zero host
        copies when the servers share the process (the TPU-native hot
        path: the reference's RequestParameter row pull,
        communicator.cpp:117-155, without ever leaving HBM)."""
        self.wait(self.get_rows_device_async(row_ids))
        return self.take_device_rows()

    @issues_get
    def get_rows_device_async(self, row_ids) -> int:
        """Async device row pull.

        HOST ids must be non-decreasing so each server's reply is one
        contiguous segment and the result reassembles by concatenation
        (sorted-unique row sets — possibly tail-padded by repeating the
        last id — satisfy this).

        DEVICE ids (a ``jax.Array``) pass through the stack without ever
        touching the host: any shape, any order, duplicates welcome —
        the reply is the XLA gather ``table[row_ids]`` with shape
        ``row_ids.shape + (num_col,)``. This is the key enabler for
        trainers whose row sets are computed on device
        (models/wordembedding/device_train.py PS mode).

        Multi-server: splitting device ids into per-server subsets
        would need data-dependent shapes (a host sync), so instead the
        SAME id blob goes to every server; each gathers only its own
        rows (foreign rows fill 0) and the worker SUMS the replies —
        every row is owned by exactly one server, so the sum
        reassembles the exact gather. Costs one extra [k, C] pass per
        additional server, all in HBM."""
        self._check_frozen_layout("device row gets")
        if is_device_array(row_ids):
            CHECK(self._zoo.servers_in_process,
                  "device-key row gets need the servers in this "
                  "process (a serializing transport flattens the "
                  "keys to host bytes and the reply shape contract "
                  "breaks)")
            CHECK(not self._compress, "device gets bypass wire compression")
            return self._request_get_device(
                Blob(row_ids), sums=self._num_server > 1)
        row_ids = np.ascontiguousarray(row_ids, dtype=np.int32).reshape(-1)
        CHECK(row_ids.size > 0, "empty device row get")
        self._check_row_ids(row_ids)
        CHECK(not self._compress, "device gets bypass wire compression")
        if self._num_server > 1:
            CHECK(bool(np.all(np.diff(row_ids) >= 0)),
                  "device row gets need sorted row ids")
        return self._request_get_device(Blob(row_ids.view(np.uint8)))

    def take_device_rows(self):
        """Assembled result of the last ``get_rows_device_async`` (call
        after ``wait``); clears the reply slot. Device-key multi-server
        replies SUM (each server zero-fills foreign rows); host-key
        multi-server replies concatenate (each server returned its
        contiguous sorted segment)."""
        sink = self._take_device()
        ordered = sink.ordered()
        if len(ordered) == 1:
            return ordered[0]
        import jax.numpy as jnp
        # Worker-thread reassembly dispatch: guarded like any other
        # multi-device program (multi-zoo mode only; no-op otherwise).
        with device_lock.guard():
            if sink.sums:
                return device_lock.settle(
                    functools.reduce(jnp.add, ordered))
            return device_lock.settle(jnp.concatenate(ordered, axis=0))

    def take_device_row_parts(self):
        """The raw per-server reply shards of the last device get
        WITHOUT assembling them — a consumer that feeds them into its
        own jit can fold the multi-server sum into that program instead
        of paying a separate device op (one more per-dispatch launch
        cost). Replies carry the origin server id, so parts return in
        SERVER order (the broadcast sum is order-independent)."""
        return self._take_device().ordered()

    def _take_device(self) -> DeviceSink:
        sink, self._last_device = self._last_device, None
        CHECK(sink is not None and len(sink.parts) > 0,
              "no device row get outstanding")
        return sink

    def _request_get(self, keys: Blob, sink) -> int:
        blobs = [keys]
        if self.is_sparse:
            # Sparse gets carry the asking worker's id
            # (ref: sparse_matrix_table.h:27-43).
            blobs.append(GetOption(self._zoo.worker_id).to_blob())
        return self._get_to(sink, blobs)

    def _request_get_device(self, keys: Blob, sums: bool = False) -> int:
        """A Get whose reply parts stay in HBM, for ``take_device_rows``
        after ``wait``."""
        self._last_device = DeviceSink(
            sums, row_length=self._row_length, num_server=self._num_server)
        return self._request_get(keys, self._last_device)

    # -- Add API (ref: matrix_table.cpp:110-147) --
    def add(self, delta, option: Optional[AddOption] = None) -> None:
        self.retrying_wait(lambda: self.add_async(delta, option))

    @issues_add
    def add_async(self, delta, option: Optional[AddOption] = None) -> int:
        """Whole-table add; device arrays stay on device end to end."""
        if not is_device_array(delta):
            delta = np.ascontiguousarray(delta, self.dtype).reshape(-1)
        CHECK(int(np.prod(delta.shape)) == self.num_row * self.num_col,
              "bad delta size")
        if self._shard_map is not None and not self.is_sparse \
                and not is_device_array(delta):
            # Dynamic map: the sentinel add slices per the frozen
            # offsets — route as an all-rows row Add instead (keys
            # travel, the partition buckets by the live map).
            return MatrixWorker.add_rows_async.__wrapped__(  # one span
                self, np.arange(self.num_row, dtype=np.int32),
                delta.reshape(self.num_row, self.num_col), option)
        CHECK(self._shard_map is None or self.is_sparse
              or not is_device_array(delta),
              "whole-table device adds need the frozen shard layout "
              "(live resharding serves the host row path)")
        tok = self._cache_begin_add(None)
        mid = self.add_async_raw(Blob(_ALL_KEY.view(np.uint8)),
                                 Blob(delta),
                                 self._option_blob(option))
        self._cache_resolve_on(mid, tok)
        return mid

    def _cache_begin_add(self, row_ids: Optional[np.ndarray],
                         ends: Optional[Tuple[int, int]] = None):
        """Block the client-cache slots this Add dirties (None = whole
        table) until its ack resolves them — read-your-writes. NOT
        gated on _live_cache(): an INACTIVE cache still needs the ack
        to fence its shard floors, or a live activation racing an
        in-flight add could serve the pre-add value afterwards
        (RowCache.begin_add's fence token, named from ``ends``, the
        ids' smallest and largest value)."""
        cache = self._row_cache
        if cache is None:
            return None
        return cache.begin_add(row_ids, ends)

    def _cache_resolve_on(self, msg_id: int, token) -> None:
        if token is not None:
            self.add_completion(
                msg_id,
                lambda _mid, tok=token: self._row_cache.finish_add(tok))

    def add_rows(self, row_ids, delta,
                 option: Optional[AddOption] = None) -> None:
        self.retrying_wait(
            lambda: self.add_rows_async(row_ids, delta, option))

    @issues_add
    def add_rows_async(self, row_ids, delta,
                       option: Optional[AddOption] = None) -> int:
        """Row-delta push. A ``jax.Array`` delta stays on device end to
        end when the servers share the process (scatter-add straight from
        HBM — the device twin of the reference's AddDeltaParameter,
        communicator.cpp:157-249). DEVICE row_ids (single-server,
        in-process tables) keep the ids in HBM too: any shape; delta
        must be shaped ``row_ids.shape + (num_col,)``. Duplicate ids
        sum under default, sgd and adam (``UpdaterRule.sums_duplicates``)
        — the engine rejects momentum, adagrad and dcasgd on this
        path."""
        if is_device_array(row_ids):
            # Multi-server: the same ids+delta blobs go to every server;
            # each scatter-adds only its own rows (foreign rows masked
            # out-of-range and dropped), so the union applies the full
            # delta exactly once.
            self._check_frozen_layout("device-key row adds")
            CHECK(self._zoo.servers_in_process,
                  "device-key row adds need the servers in this "
                  "process")
            CHECK(self._device_keys_ok,
                  DEVICE_KEYS_REFUSED % self._updater_name)
            CHECK(is_device_array(delta),
                  "device-key adds need a device delta")
            CHECK(tuple(delta.shape) ==
                  tuple(row_ids.shape) + (self.num_col,),
                  "bad device delta shape")
            # Device-resident ids cannot be enumerated without a host
            # sync — block the whole cache until the ack.
            tok = self._cache_begin_add(None)
            mid = self.add_async_raw(Blob(row_ids), Blob(delta),
                                     self._option_blob(option))
            self._cache_resolve_on(mid, tok)
            return mid
        row_ids = np.ascontiguousarray(row_ids, dtype=np.int32).reshape(-1)
        ends = self._check_row_ids(row_ids)
        if self._one_bit or self._lossy:
            # The error-feedback gather/write-back breaks on duplicates;
            # the chunk encoder's own CHECK fires inside the worker
            # actor — raise here in the caller instead.
            CHECK(np.unique(row_ids).size == row_ids.size,
                  "error-feedback row pushes need unique row ids")
        if not is_device_array(delta):
            delta = np.ascontiguousarray(delta, self.dtype).reshape(-1)
        CHECK(int(np.prod(delta.shape)) == row_ids.size * self.num_col,
              "bad delta size")
        tok = self._cache_begin_add(row_ids, ends)
        mid = self.add_async_raw(Blob(row_ids.view(np.uint8)),
                                 Blob(delta),
                                 self._option_blob(option))
        self._cache_resolve_on(mid, tok)
        return mid

    def _option_blob(self, option: Optional[AddOption]) -> Blob:
        if option is None:
            option = AddOption(worker_id=max(self._zoo.worker_id, 0))
        return option.to_blob()

    def _feedback_chunk(self, chunk, lo: int, hi: int,
                        rows: Optional[np.ndarray], encode) -> List[Blob]:
        """Shared error-feedback discipline for every lossy encoder
        (1-bit and the codec's quantized tiers): the previous
        quantization error for these slots is folded into the delta
        before encoding, and the new error replaces it. Row pushes need
        UNIQUE row ids — a duplicated row would gather its residual once
        per occurrence and keep only the last write-back, so the bounded-
        error invariant would silently break. ``encode`` maps a flat
        fp32 vector to (blobs, residual); residual None means the
        encoder went lossless this time (nothing remains to carry)."""
        if self._residual is None:
            self._residual = np.zeros((self.num_row, self.num_col),
                                      np.float32)
        chunk2d = np.asarray(chunk).reshape(-1, self.num_col)
        if rows is None:
            res = self._residual[lo:hi]
        else:
            CHECK(np.unique(rows).size == rows.size,
                  "error-feedback row pushes need unique row ids")
            res = self._residual[rows]
        blobs, residual = encode((chunk2d + res).reshape(-1))
        if residual is None:
            residual = np.zeros(chunk2d.size, np.float32)
        residual = residual.reshape(chunk2d.shape)
        if rows is None:
            self._residual[lo:hi] = residual
        else:
            self._residual[rows] = residual
        return blobs

    def _onebit_chunk(self, chunk: np.ndarray, lo: int, hi: int,
                      rows: Optional[np.ndarray] = None) -> List[Blob]:
        return self._feedback_chunk(chunk, lo, hi, rows, _onebit_blobs)

    def _codec_chunk(self, chunk: np.ndarray, lo: int, hi: int,
                     rows: Optional[np.ndarray] = None) -> List[Blob]:
        """Wire-codec Add chunk: lossless passthrough by default, the
        quantized tiers + error feedback under ``-wire_codec_lossy``."""
        if not self._lossy:
            return _compress_values(np.asarray(chunk))[0]
        return self._feedback_chunk(
            chunk, lo, hi, rows,
            lambda flat: _compress_values(flat, lossy=True))

    # -- partition (ref: matrix_table.cpp:234-315) --
    def partition(self, blobs, msg_type) -> Dict[int, List[Blob]]:
        if blobs[0].on_device:
            # Device-key requests: the same blob list goes to EVERY
            # server (object references — zero copies in-process); each
            # server masks foreign rows on device. Splitting the ids
            # here would need their values on the host.
            return {sid: list(blobs) for sid in range(self._num_server)}
        keys = blobs[0].as_array(np.int32)
        out: Dict[int, List[Blob]] = {}
        if keys.size == 1 and keys[0] == -4 \
                and msg_type == MsgType.Request_Get:
            # Fused add+dirty-get (a Get — it replies): single-server
            # (enforced in the caller) — the whole blob list goes to
            # server 0. A Request_Add carrying -4 falls through to the
            # stray-negative fail-fast below.
            CHECK(self._num_server == 1 and len(blobs) in (5, 6),
                  "fused add+dirty-get: [marker, rows, delta, "
                  "add_option, get_option(, device rows)] to one "
                  "server")
            return {0: list(blobs)}
        if keys.size == 1 and keys[0] < 0:
            # Only the defined sentinels may go negative; a stray
            # negative row id must fail fast here, not fan out as a
            # whole-table request with undefined server-side handling.
            CHECK(keys[0] in (-1, -2),
                  "negative key must be a whole-table sentinel (-1/-2)")
            is_add = msg_type == MsgType.Request_Add
            compress = is_add and self._compress
            values = blobs[1].typed(self.dtype) if is_add else None
            if compress and is_device_array(values):
                values = np.asarray(values)  # host bytes at the wire
            # Values may arrive flat [R*C] (host callers) or row-shaped
            # [R, C] (device deltas skip the flatten — a device reshape
            # still dispatches); slice in whichever layout they came.
            row_shaped = values is not None and np.ndim(values) == 2
            one_bit = (is_add and self._one_bit and values is not None
                       and not is_device_array(values))
            for sid in range(self._num_server):
                shard = [blobs[0]]
                if values is not None:
                    lo, hi = self._offsets[sid], self._offsets[sid + 1]
                    chunk = values[lo:hi] if row_shaped \
                        else values[lo * self.num_col:hi * self.num_col]
                    if compress:
                        shard.extend(self._codec_chunk(
                            np.asarray(chunk), lo, hi))
                    elif one_bit:
                        shard.extend(self._onebit_chunk(
                            np.asarray(chunk), lo, hi))
                    else:
                        shard.append(Blob(chunk))
                    if len(blobs) == 3:
                        shard.append(blobs[2])
                elif len(blobs) == 2:  # sparse Get: GetOption rides along
                    shard.append(blobs[1])
                out[sid] = shard
            return out

        # Row-id requests: bucket rows by owning server
        # (ref: matrix_table.cpp:267-276). Defense in depth for raw-API
        # callers: a negative id in a VECTOR would bucket to server -1
        # (misrouted shard, silent wrap or a hang) — reject here too.
        CHECK(keys.size == 0 or (int(keys.min()) >= 0
                                 and int(keys.max()) < self.num_row),
              "row ids out of range [0, num_row)")
        is_add = msg_type == MsgType.Request_Add
        # One server under the frozen layout: the shard is the request.
        # (A shard map, even over one active server, may name others.)
        dest = None
        if self._num_server > 1 or self._shard_map is not None:
            dest = self._server_of_rows(keys)
        if (not is_add and self._replica_router is not None
                and self._replica_router.active):
            # Replicated (hot) rows re-route to holder servers — the
            # co-located shard when this rank hosts one, else a
            # per-row stripe across all servers (docs/SHARDING.md);
            # each holder's own rows ride the same shard message.
            # Adds never re-route — write-through keeps the owner
            # authoritative.
            rep_mask = self._replica_router.replicated_mask(keys)
            if bool(rep_mask.any()):
                dest = np.asarray(dest).copy()
                holders = self._replica_router.route(keys[rep_mask])
                # -1 = chosen holder declared dead: fall back to the
                # row's OWNER (the original range dest) — correct by
                # construction, merely unbalanced until rejoin.
                dest[rep_mask] = np.where(holders >= 0, holders,
                                          dest[rep_mask])
                self._note_replica_routed(keys, dest, rep_mask)
        values = dev_values = None
        if is_add:
            if blobs[1].on_device and not self._compress:
                # Device delta: slice per-server segments in HBM (keys
                # must be in server order so segments are contiguous;
                # a lone shard passes whole: a full-range device slice
                # would still dispatch).
                dev_values = _shaped_rows(blobs[1].typed(self.dtype),
                                          keys.size, self.num_col)
            else:
                values = blobs[1].as_array(self.dtype).reshape(
                    keys.size, self.num_col)
        cuts = _shard_cuts(dest, keys.size)
        for sid, cut in cuts:
            # A slice is a VIEW of the request's keys and values (the
            # caller's memory until the ack, docs/MEMORY.md "send side
            # of an Add"); a mask gathers a copy.
            run = isinstance(cut, slice)
            shard_keys = keys[cut]
            shard = [Blob(shard_keys.view(np.uint8))]
            if dev_values is not None:
                CHECK(run, "device row adds need sorted row ids")
                shard.append(Blob(dev_values if len(cuts) == 1
                                  else dev_values[cut]))
                if len(blobs) == 3:
                    shard.append(blobs[2])
            elif values is not None:
                count_event("ADD_ROWS_SHARD_VIEW" if run
                            else "ADD_ROWS_SHARD_COPIED")
                chunk = values[cut]
                if self._compress:
                    shard.extend(self._codec_chunk(chunk, 0, 0,
                                                   rows=shard_keys))
                elif self._one_bit:
                    shard.extend(self._onebit_chunk(chunk, 0, 0,
                                                    rows=shard_keys))
                else:
                    shard.append(Blob(chunk))
                if len(blobs) == 3:
                    shard.append(blobs[2])
            elif len(blobs) == 2:  # sparse GetOption
                shard.append(blobs[1])
            out[sid] = shard
        return out

    def get_dirty_device(self):
        """Sparse dirty-row pull with a DEVICE-resident reply: returns
        ``(row_ids, values)`` where values is a ``jax.Array`` in HBM —
        the staleness bookkeeping stays host-side (it is a bitmap), but
        the row payload never crosses the host boundary. This is the
        TPU-native form of the reference's dirty-only Get
        (ref: sparse_matrix_table.cpp:226-258), whose host-buffer reply
        is otherwise bounded by host<->device bandwidth."""
        CHECK(self.is_sparse, "dirty gets are for sparse tables")
        CHECK(self._zoo.servers_in_process,
              "device dirty gets need the servers in this process "
              "(the reply payload is a live device array)")
        sink = DeviceSink(keep_ids=True)
        self.wait(self._request_get(
            Blob(_ALL_KEY_DEVICE_REPLY.view(np.uint8)), sink))
        shards, ids = sink.parts, sink.ids
        CHECK(len(shards) == self._num_server,
              "dirty get: one reply per server")
        if self._num_server == 1:
            return ids[0], shards[0]
        # Each server's dirty set is sorted within its own row range and
        # ranges are ordered by server id, so concatenation in server
        # order is globally sorted — same contract as the single-server
        # reply (ref: sparse_matrix_table.cpp:226-258 per-server dirty
        # scan; reassembly is the worker's).
        import jax.numpy as jnp
        order = sorted(shards)
        with device_lock.guard():
            joined = device_lock.settle(
                jnp.concatenate([shards[s] for s in order], axis=0))
        return np.concatenate([ids[s] for s in order]), joined

    def add_get_dirty_device(self, row_ids, delta,
                             option: Optional[AddOption] = None,
                             get_worker: Optional[int] = None,
                             row_ids_device=None):
        """FUSED add + dirty pull: apply a row delta, then return THIS
        worker's dirty rows — the exact composition of ``add_rows`` and
        ``get_dirty_device``, but one request and ONE device program
        server-side (the separate pair pays two per-dispatch launch
        costs, not measured on the current machine). Single in-process
        server, async mode (a hidden add inside a Get would bypass the
        BSP vector clocks). ``option`` names the adder as usual;
        ``get_worker`` the dirty-set consumer (default: this worker).

        ``row_ids_device``: optional DEVICE mirror of ``row_ids`` — a
        caller pushing the same (or precomputed) row set repeatedly
        keeps the ids in HBM, skipping the per-call host-to-device id
        upload (host ids are still required for
        the dirty bookkeeping, which is a host bitmap). Stateless
        updaters only, as with device-key adds."""
        CHECK(self.is_sparse, "fused add+dirty-get is for sparse tables")
        CHECK(self._num_server == 1 and self._zoo.servers_in_process,
              "fused add+dirty-get is a single-server extension with "
              "the server in this process (multi-server callers "
              "compose add_rows + get_dirty_device)")
        CHECK(not bool(get_flag("sync", False)),
              "fused add+dirty-get is async-only: the embedded add "
              "would bypass the BSP vector clocks")
        row_ids = np.ascontiguousarray(row_ids,
                                       dtype=np.int32).reshape(-1)
        self._check_row_ids(row_ids)
        CHECK(is_device_array(delta), "fused add needs a device delta")
        CHECK(tuple(delta.shape) == (row_ids.size, self.num_col),
              "bad delta shape")
        if get_worker is None:
            get_worker = max(self._zoo.worker_id, 0)
        CHECK(0 <= int(get_worker) < self._num_consumers,
              "get_worker out of the consumer-slot range (the "
              "server-side CHECK would fire inside the actor and the "
              "caller would hang)")
        blobs = [Blob(_ADD_GET_DIRTY_KEY.view(np.uint8)),
                 Blob(row_ids.view(np.uint8)), Blob(delta),
                 self._option_blob(option),
                 GetOption(int(get_worker)).to_blob()]
        if row_ids_device is not None:
            CHECK(is_device_array(row_ids_device),
                  "row_ids_device must be a device array")
            # The mirror must arrive PRE-PADDED to the same power-of-two
            # bucket the host path uses (``pad_ids(row_ids, num_row)``
            # then ``jnp.asarray``): the server feeds it straight into
            # the fused jit, so an exact-k mirror would compile one
            # program per distinct k instead of once per bucket width.
            # Padding ids must be >= num_row: they scatter zero rows
            # into dead storage and are dropped by every gather.
            bucket = bucket_size(row_ids.size)
            CHECK(tuple(row_ids_device.shape) == (bucket,)
                  and np.dtype(row_ids_device.dtype) == np.int32,
                  "row_ids_device must mirror row_ids padded to the "
                  "host bucket ([bucket_size(k)] int32; build it as "
                  "jnp.asarray(pad_ids(row_ids, num_row)))")
            CHECK(self._updater_stateless,
                  "device-id fused adds need a stateless updater")
            if get_flag("verify_device_ids") and not self._mirror_verified:
                # A mirror that disagrees with the host ids would mark
                # one row set dirty and scatter the delta at ANOTHER —
                # silent corruption. Opt-in first-call readback turns
                # that into a loud failure (one device->host transfer).
                host_mirror = np.asarray(row_ids_device)
                CHECK(np.array_equal(host_mirror[:row_ids.size], row_ids),
                      "-verify_device_ids: row_ids_device disagrees "
                      "with the host row ids")
                CHECK(row_ids.size == bucket
                      or int(host_mirror[row_ids.size:].min())
                      >= self.num_row,
                      "-verify_device_ids: mirror padding ids must be "
                      ">= num_row (in-range padding would scatter into "
                      "live rows)")
                self._mirror_verified = True
            blobs.append(Blob(row_ids_device))
        sink = DeviceSink(keep_ids=True)
        self.wait(self._get_to(sink, blobs))
        shards, ids = sink.parts, sink.ids
        CHECK(len(shards) == 1, "fused dirty get: one reply")
        return ids[0], shards[0]

    # -- device-resident whole-table Get (shards stay in HBM) --
    def get_device(self):
        self.wait(self.get_device_async())
        return self.take_device_rows()

    @issues_get
    def get_device_async(self) -> int:
        self._check_frozen_layout("device whole-table gets")
        CHECK(not self.is_sparse,
              "device get is for dense tables (sparse replies are ragged)")
        return self._request_get_device(Blob(_ALL_KEY.view(np.uint8)))

    # -- replies (ref: matrix_table.cpp:317-341) --
    def process_reply_get(self, reply_blobs: List[Blob]) -> None:
        """One server's reply shard: decoded here, placed by the sink
        its request registered. A whole shard has ``keys`` None; a
        device sink takes the payload as it is, still in HBM (a shard's
        server rides in blob 2 where the reply names it); host rows go
        through the client cache and the replica bookkeeping on the way
        to the sink."""
        sink = self._reply_sink()
        keys = None
        if not reply_blobs[0].on_device:
            keys = reply_blobs[0].as_array(np.int32)
            if keys.size == 1 and keys[0] == -1:
                keys = None
        if sink.device:
            values = reply_blobs[1].typed(self.dtype)
            if keys is not None:
                values = _shaped_rows(values, keys.size, self.num_col)
            sink.place(keys, values, self._reply_version,
                       int(reply_blobs[2].as_array(np.int32)[0])
                       if len(reply_blobs) >= 3 else None)
            return
        if keys is None:
            server_id = int(reply_blobs[2].as_array(np.int32)[0])
            n_rows = self._offsets[server_id + 1] - self._offsets[server_id]
            sink.place(None, reply_blobs[1].as_rows(self.dtype, n_rows,
                                                    self.num_col),
                       self._reply_version, server_id)
            return
        start = self._pieced_start(sink, keys, reply_blobs[1])
        if start >= 0:
            count_event("GET_REPLY_ROWS_PIECED")
            sink.place_run(start, reply_blobs[1].host_row_pieces(
                self.dtype, keys.size, self.num_col))
            return
        count_event("GET_REPLY_ROWS_WHOLE")
        if self._compress and _is_codec_blob(reply_blobs[1]):
            values = _decompress_values(
                reply_blobs[1],
                self.dtype).reshape(keys.size, self.num_col)
        else:
            # A 3-blob non-codec reply here is the REMOVED float64-pair
            # layout ([keys, pairs, size_record] from a pre-codec
            # build) — fail loudly; reshaping pair bytes as raw values
            # could silently corrupt when the byte counts coincide.
            CHECK(not self._compress or len(reply_blobs) == 2,
                  "legacy float64-pair reply: the pre-codec wire "
                  "format was removed (docs/WIRE_FORMAT.md)")
            values = reply_blobs[1].as_rows(self.dtype, keys.size,
                                            self.num_col)
        requested = None
        ent = self._replica_sent.get(self._reply_msg_id)
        if ent is not None:
            # This may be a holder shard of a replica-routed request —
            # even a reply with ZERO replica rows (the holder missed
            # everything) must diff against what was routed to it, or
            # the missing positions would silently stay unfilled.
            requested = ent.pop(self._reply_server, None)
            if not ent:
                del self._replica_sent[self._reply_msg_id]
        self._serve_reply_groups(keys, values, reply_blobs, requested,
                                 sink)

    def _pieced_start(self, sink, keys: np.ndarray, payload: Blob) -> int:
        """Where in its request's buffer this host row reply shard may
        be placed piece by piece while the rest of it is still leaving
        the device, or -1: the whole array then goes the way it always
        has. Pieces need every row to have ONE reader, the copy into
        the caller's buffer, known before a value is on the host; each
        condition is read off the reply itself. The payload is a large
        device array (``pieced_rows``; a codec frame never is); the
        shard carries no replica groups and is no holder's answer to
        routed rows (those are cut by group, attributed and repaired);
        the row cache stores nothing (a store reads every row again);
        the sink is a host row Get's and the placement is the direct
        one (a searched shard, a scatter read and a prefetch read the
        whole)."""
        if (not payload.pieced_rows(self.dtype, keys.size, self.num_col)
                or not isinstance(sink, _RowsSink)
                or self._reply_replica_rows
                or self._reply_msg_id in self._replica_sent
                or self._live_cache() is not None):
            return -1
        return sink.run_start(keys)

    # -- hot-shard replication: worker side (runtime/replica.py,
    #    docs/SHARDING.md; all on the worker actor thread) --
    def apply_replica_map(self, epoch: int, rows) -> None:
        if self._replica_router is not None:
            self._replica_router.apply(epoch, rows)

    def replica_server_dead(self, server_id: int) -> None:
        if self._replica_router is not None:
            self._replica_router.mark_dead(server_id)

    def replica_server_alive(self, server_id: int) -> None:
        if self._replica_router is not None and server_id >= 0:
            self._replica_router.mark_alive(server_id)

    def replica_reconcile(self, alive_sids) -> None:
        if self._replica_router is not None:
            self._replica_router.reconcile(alive_sids)

    def _note_replica_routed(self, keys: np.ndarray, dest: np.ndarray,
                             rep_mask: np.ndarray) -> None:
        """Record which FOREIGN rows (owner != holder) the current
        request routed to which holder — keyed by the request id the
        worker actor set around ``partition`` — so each holder's reply
        can be diffed for repairs. Rows a holder itself owns need no
        bookkeeping (an owner always serves its rows). Entries for
        requests that error out before their reply are reaped by the
        size cap."""
        if self._partition_msg_id < 0:
            return
        owners = self._server_of_rows(keys)
        foreign = rep_mask & (dest != owners)
        if not bool(foreign.any()):
            return
        by_holder: Dict[int, np.ndarray] = {}
        for sid in np.unique(dest[foreign]):
            by_holder[int(sid)] = np.unique(
                keys[foreign & (dest == sid)]).astype(np.int32)
        while len(self._replica_sent) > 256:
            self._replica_sent.pop(next(iter(self._replica_sent)))
        self._replica_sent[self._partition_msg_id] = by_holder

    def _replica_groups(self, keys: np.ndarray, values: np.ndarray,
                        reply_blobs: List[Blob]) -> List:
        """Decode the current reply's replica descriptor (last blob)
        into ``[(owner_sid, floor_version, group_keys, group_values)]``
        — empty when the reply carries no replica rows."""
        if not self._reply_replica_rows:
            return []
        desc = reply_blobs[-1].as_array(np.int32)
        n_groups = int(desc[0])
        total = int(desc[3::3][:n_groups].sum())
        pos = keys.size - total
        out = []
        for g in range(n_groups):
            owner = int(desc[1 + 3 * g])
            floor = int(desc[2 + 3 * g]) - 1
            n_rows = int(desc[3 + 3 * g])
            out.append((owner, floor, keys[pos:pos + n_rows],
                        values[pos:pos + n_rows]))
            pos += n_rows
        return out

    def _serve_reply_groups(self, keys: np.ndarray, values: np.ndarray,
                            reply_blobs: List[Blob],
                            requested: Optional[np.ndarray],
                            sink) -> None:
        """A host reply shard's rows, group by group, into the client
        cache (every host row reply refreshes it: prefetch is an
        accelerant, not a requirement, for hits) and then to ``sink``,
        the request's.
        Owned rows attribute to the replying shard at the header
        version; each replica group attributes to its OWNER at the
        group's version floor. Groups below this worker's read-
        your-writes floor are discarded (their values may predate an
        Add the owner already acked to us) and — together with routed
        rows the holder did not serve at all — REPAIR to their owners
        under the same request id, hence to the same sink (the worker
        actor transfers this reply's notify onto the repairs, so wait()
        completes only when they landed)."""
        def place(gkeys, gvals, version, owner):
            if self._row_cache is not None:
                self._row_cache.store(gkeys, gvals, version, owner)
            sink.place(gkeys, gvals, version, owner)

        n_own = keys.size - self._reply_replica_rows
        place(keys[:n_own], values[:n_own], self._reply_version,
              self._reply_server)
        served: List[np.ndarray] = []
        stale: List[np.ndarray] = []
        for owner, floor, gkeys, gvals in \
                self._replica_groups(keys, values, reply_blobs):
            if floor < self.add_floor(owner):
                count_event(replica_mod.REPLICA_STALE, int(gkeys.size))
                stale.append(gkeys)
                continue
            served.append(gkeys)
            # Tracker note(), NOT note_version(): a floor below the
            # owner's latest observed version is normal replica lag,
            # not the generation-change regression signal that
            # invalidates caches.
            self._version_tracker.note(owner, floor)
            place(gkeys, gvals, floor, owner)
        repair = list(stale)
        if requested is not None:
            got = np.concatenate(served + stale) if (served or stale) \
                else np.empty(0, np.int32)
            missing = np.setdiff1d(requested, got)
            if missing.size:
                repair.append(missing)
        if not repair:
            return
        rows = np.unique(np.concatenate(repair)).astype(np.int32)
        owners = self._server_of_rows(rows)
        for sid in np.unique(owners):
            chunk = np.ascontiguousarray(rows[owners == sid])
            self._stage_repair(int(sid), [Blob(chunk.view(np.uint8))])


class MatrixServer(shard_map_mod.ElasticServerMixin, ServerTable):
    """One server's shard of a matrix table (ref: matrix_table.cpp):
    ``my_rows`` consecutive rows, stored as one array row-sharded over
    this process's devices (``local_mesh()``), rows padded to a multiple
    of the devices and columns to the 128 lanes where that costs at most
    4x.

    ``random_init=(lo, hi)`` (the reference's random-init server ctor)
    is drawn ON THE DEVICES by one program, each shard writing its own
    rows (``meshlib.uniform_sharded``): float32 draws in ``[lo, hi)``
    cast to ``dtype`` in ``[:my_rows, :num_col]``, zero in the padding
    rows and lanes. The values are a function of ``(seed, server id)``
    and the element's position only: the same table on one device, on
    four and on the CPU, and never numpy's stream. No host array of the
    table's size is made at any point, so a shard larger than the
    host's free memory still starts (tests/test_table_init.py holds all
    of this). Monitor ``TABLE_INIT``, scope ``mv.table.init``."""

    def __init__(self, num_row: int, num_col: int, dtype=np.float32,
                 is_sparse: bool = False, is_pipeline: bool = False,
                 zoo=None, updater_type: Optional[str] = None,
                 random_init: Optional[tuple] = None, seed: int = 0):
        super().__init__(zoo=zoo)
        self.dtype = np.dtype(dtype)
        self.num_col = int(num_col)
        self.is_sparse = bool(is_sparse)
        self._compress = (self.is_sparse
                          and not self._zoo.net.in_process
                          and bool(get_flag("sparse_compress")))
        self._one_bit = (not self.is_sparse
                         and np.dtype(dtype) == np.float32
                         and bool(get_flag("one_bit_push")))
        self.num_row = int(num_row)
        offsets = row_offsets(
            int(num_row),
            shard_map_mod.initial_active_servers(self._zoo.num_servers))
        sid = self._zoo.server_id
        self.server_id = sid
        if sid >= len(offsets) - 1:
            self.row_offset, self.my_rows = 0, 0  # idle server (rows<servers)
        else:
            self.row_offset = offsets[sid]
            self.my_rows = offsets[sid + 1] - offsets[sid]

        mesh = meshlib.local_mesh()
        self._sharding = meshlib.row_sharded(mesh)
        padded = meshlib.padded_size(max(self.my_rows, 1),
                                     meshlib.device_count(mesh))
        # Column storage pads to the 128-lane tile width: sub-lane rows
        # scatter ~25x slower on v5e (measured round 4: [1M, 50] row
        # scatter-adds ran at 2.2 GB/s vs 86 GB/s at 128 cols). Bounded
        # to a 4x memory blowup so skinny tables keep compact storage.
        self._col_store = self.num_col
        if self.num_col % 128:
            col_padded = ((self.num_col + 127) // 128) * 128
            if col_padded <= 4 * self.num_col:
                self._col_store = col_padded
        if random_init is None:
            self._data = meshlib.zeros_sharded(
                (padded, self._col_store), self.dtype, self._sharding)
        else:
            # Server ctor variant with uniform random init
            # (ref: matrix_table.cpp:372-384), drawn on the devices.
            lo, hi = random_init
            # Table construction (CreateTable barrier) can overlap a
            # sibling rank's in-flight program in multi-zoo mode.
            with monitor("TABLE_INIT"), device_lock.guard():
                self._data = jax.block_until_ready(meshlib.uniform_sharded(
                    (padded, self._col_store), self.dtype, self._sharding,
                    self.my_rows, self.num_col, lo, hi, seed, sid))
        rule = None if updater_type is None \
            else create_rule(updater_type, dtype)
        num_workers = max(self._zoo.num_workers, 1)
        self._engine = UpdateEngine(rule, (padded, self._col_store),
                                    self.dtype, num_workers, self._sharding)
        # Sparse staleness bitmap: one slot per logical consumer; pipelined
        # workers count twice (ref: sparse_matrix_table.cpp:184-197).
        consumers = num_workers * (2 if is_pipeline else 1)
        self._up_to_date = np.zeros((consumers, self.my_rows), dtype=bool) \
            if is_sparse else None
        # (dirty_ids, padded device ids) of the last fused dirty get —
        # an unchanged dirty set skips the per-call id upload.
        self._dirty_dev_cache = None
        # Hot-shard read replication (runtime/replica.py,
        # docs/SHARDING.md): dense multi-server tables only — the
        # sparse dirty protocol is already a per-consumer staleness
        # tracker, and a single server owns every row. Flag read at
        # construction time, like -sparse_compress.
        self._replica = None
        self._reply_replica_rows_out = 0
        if (not self.is_sparse and self._zoo.num_servers > 1
                and replica_mod.replication_enabled()):
            self._replica = replica_mod.ServerReplicaState(
                self.row_offset, self.my_rows)
        # -- live elastic resharding state (runtime/shard_map.py,
        #    docs/SHARDING.md; server actor thread only) --
        #: adopted epoch-stamped map (None = frozen creation layout)
        self._smap: Optional[shard_map_mod.ShardMap] = None
        #: migrated-IN rows: global row id -> host value row. The
        #: destination side of a move keeps acquired rows host-side
        #: (a numpy gather serves them, like the replica store) — the
        #: device base array keeps its creation-time shape.
        self._overlay: Dict[int, np.ndarray] = {}
        #: forwarded adds for rows whose base chunk is still in flight
        #: (retransmit window only): row -> accumulated signed delta,
        #: merged when the chunk lands.
        self._pending_delta: Dict[int, np.ndarray] = {}
        #: dual-read/forwarding windows this shard is the OLD owner
        #: of: (lo, hi, dst_sid, dst_rank). Kept indefinitely — a
        #: stale router may send moved rows here long after commit.
        self._fwd: List[tuple] = []
        self._mig_out: Optional[shard_map_mod.MigrationOut] = None
        self._mig_in: Dict[int, shard_map_mod.MigrationIn] = {}
        #: requests forwarded into a dual-read/write window since the
        #: last map apply: (requester rank, msg_id, is_get). The
        #: requester tracks them against THIS rank, so if the window's
        #: DESTINATION dies, only this shard can fail their waiters —
        #: shard_abort drains the list into retryable error replies.
        #: Bounded; error replies for long-completed ids are no-ops.
        self._fwd_inflight: List[tuple] = []
        #: True while the server applies the BOTH-APPLY half of a
        #: forwarded add to this (source) shard's handoff copy —
        #: exempts the own-forwarding-window NACK (server actor
        #: thread only).
        self._in_both_apply = False
        #: host twin of the (stateless) update rule for overlay rows:
        #: default adds, sgd subtracts. Stateful rules refuse to
        #: migrate (shard_begin_out).
        self._updater_sign = -1.0 if updater_type == "sgd" else 1.0
        self._updater_stateless = create_rule(updater_type,
                                              dtype).stateless
        #: -reshard_auto load tracking without replication: the same
        #: HotTracker windows feed the controller's skew-split planner
        #: (runtime/shard_map.py ReshardManager.note_report).
        self._hot: Optional[replica_mod.HotTracker] = None
        if (not self.is_sparse and self._zoo.num_servers > 1
                and self._replica is None
                and bool(get_flag("reshard_auto"))):
            self._hot = replica_mod.HotTracker()

    # -- Add (ref: matrix_table.cpp:386-418, sparse_matrix_table.cpp:200-223)
    def process_add(self, blobs: List[Blob]) -> None:
        if blobs[0].on_device:
            # Device-key scatter-add: ids and delta never touch the
            # host. Dense tables only (sparse staleness bookkeeping
            # needs host ids). Multi-server: every server receives the
            # full request; foreign rows are masked out-of-range here
            # and dropped by the scatter.
            CHECK(self._up_to_date is None,
                  "device-key adds are for dense tables")
            option = AddOption.from_blob(blobs[2]) \
                if len(blobs) == 3 else None
            self._data = self._engine.apply_rows(
                self._data, blobs[0].typed(np.int32),
                blobs[1].typed(self.dtype), option,
                bounds=self._shard_bounds)
            if self._replica is not None:
                # Device-resident ids cannot be enumerated without a
                # host sync: conservatively dirty every own promoted
                # row for the next write-through flush.
                self._replica.note_add_all()
            if self._mig_out is not None and self._mig_out.streaming:
                # Unenumerable device ids: conservatively re-stream
                # every already-sent row of the moving range.
                self._mig_out.note_add(np.arange(
                    self._mig_out.lo, self._mig_out.sent_hi,
                    dtype=np.int64))
            return
        keys = blobs[0].as_array(np.int32)
        if self._compress and len(blobs) in (2, 3) \
                and _is_codec_blob(blobs[1]):
            # Compressed wire layout: [keys, codec frame(, option)] —
            # the frame is self-describing (tier + counts in its header;
            # ref decompression on receive: sparse_matrix_table.cpp:
            # 148-153). Magic-sniffed: a peer running without the
            # table-level codec falls through to the raw layouts below.
            option = AddOption.from_blob(blobs[2]) \
                if len(blobs) == 3 else None
            delta = _decompress_values(blobs[1], self.dtype)
        elif self._one_bit and len(blobs) == 4 \
                and not blobs[1].on_device:
            # 1-bit wire layout: exactly [keys, sign bits, meta, option]
            # (matrix adds always carry an option blob). Device-origin
            # deltas stay full precision and arrive as 3 blobs — after a
            # TCP hop they are host bytes, so the blob COUNT, not the
            # device marker, is what distinguishes the layouts.
            option = AddOption.from_blob(blobs[3])
            delta = _onebit_decode(blobs[1], blobs[2])
        else:
            CHECK(len(blobs) in (2, 3), "add needs [keys, values(, option)]")
            option = AddOption.from_blob(blobs[2]) \
                if len(blobs) == 3 else None
            delta = blobs[1].typed(self.dtype)
        if keys.size == 1 and keys[0] == -1:
            CHECK(int(np.prod(delta.shape)) == self.my_rows * self.num_col,
                  "whole-table add size mismatch")
            self._data = self._engine.apply_dense(
                self._data,
                _shaped_rows(delta, self.my_rows, self.num_col), option)
            if self._up_to_date is not None:
                self._mark_dirty(slice(None), option)
            if self._replica is not None:
                self._replica.note_add_all()
            if self._mig_out is not None and self._mig_out.streaming:
                # Whole-shard add while a range streams out: every
                # already-sent row goes dirty (re-streams in the final
                # chunk).
                self._mig_out.note_add(np.arange(
                    self._mig_out.lo, self._mig_out.sent_hi,
                    dtype=np.int64))
            return
        if is_device_array(delta):
            delta = _shaped_rows(delta, keys.size, self.num_col)
        else:
            delta = np.asarray(delta).reshape(keys.size, self.num_col)
        if self._elastic_active():
            self._elastic_row_add(keys, delta, option)
        else:
            local_rows = keys - self.row_offset
            self._data = self._engine.apply_rows(self._data, local_rows,
                                                 delta, option)
            if self._up_to_date is not None:
                self._mark_dirty(local_rows, option)
        if self._replica is not None:
            # Write-through: promoted rows this Add touched refresh to
            # the holders on the next flush cadence.
            self._replica.note_add(keys)

    def _mark_dirty(self, rows, option: Optional[AddOption]) -> None:
        """An Add invalidates the rows for every consumer except the adder,
        whose existing flags are left untouched — only Gets may mark a row
        up-to-date (ref: sparse_matrix_table.cpp:200-223). Setting the
        adder's flag True here would erase a pending dirty mark another
        worker's Add left on the same row, so the adder would read stale
        values on its next dirty-only Get."""
        adder = option.worker_id if option is not None else -1
        if 0 <= adder < self._up_to_date.shape[0]:
            saved = self._up_to_date[adder, rows].copy()
            self._up_to_date[:, rows] = False
            self._up_to_date[adder, rows] = saved
        else:
            self._up_to_date[:, rows] = False

    # -- Get (ref: matrix_table.cpp:420-454, sparse_matrix_table.cpp:226-309)
    def process_get(self, blobs: List[Blob]) -> List[Blob]:
        if blobs[0].on_device:
            # Dense device-key gather: reply values shaped
            # ids.shape + (C,), all in HBM. Multi-server: foreign rows
            # mask out-of-range and gather as 0 — the worker sums the
            # per-server replies (each row owned by exactly one server).
            CHECK(self._up_to_date is None,
                  "device-key gets are for dense tables (sparse dirty "
                  "gets use the -2 host sentinel)")
            rows = blobs[0].typed(np.int32)
            gather = self._gather if self._shard_bounds is None \
                else self._gather_bounded
            with monitor("TABLE_GATHER_DISPATCH"):
                values = gather(self._data, rows)
            # The server id rides along so the worker can key the reply
            # shard by ORIGIN server, not by arrival order.
            return [blobs[0], Blob(values),
                    Blob(np.array([self.server_id], dtype=np.int32))]
        keys = blobs[0].as_array(np.int32)
        if keys.size == 1 and keys[0] == -4:
            return self._fused_add_get_dirty(blobs)
        if keys.size == 1 and keys[0] == -2:
            CHECK(self._up_to_date is not None and len(blobs) >= 2,
                  "-2 sentinel is the sparse dirty device-reply get")
            return self._sparse_get_all_device(
                GetOption.from_blob(blobs[1]))
        if keys.size == 1 and keys[0] == -1:
            if self._up_to_date is not None and len(blobs) >= 2:
                return self._sparse_get_all(GetOption.from_blob(blobs[1]))
            return [blobs[0], Blob(self._values()),
                    Blob(np.array([self.server_id], dtype=np.int32))]
        if self._hot is not None:
            self._hot.note(keys)
        if self._elastic_active():
            # Dynamic ownership: rows serve from the device base range
            # or the migrated-in overlay; a row that is neither NACKs
            # retryably (the requester's map is in motion).
            return [blobs[0],
                    Blob(self._gather_rows_elastic(
                        keys.astype(np.int64)))]
        if self._replica is not None:
            # Hot tracking counts every row REQUESTED here — owned or
            # replica-routed; each row request lands on exactly one
            # server, so the controller's aggregation stays exact and
            # promotion cannot flap when routing moves the head to a
            # holder.
            self._replica.note_get(keys)
            own_mask = (keys >= self.row_offset) \
                & (keys < self.row_offset + self.my_rows)
            if not bool(own_mask.all()):
                return self._replica_row_get(keys, own_mask)
        local_rows = keys - self.row_offset
        with monitor("TABLE_GATHER_DISPATCH"):  # the trim's slice too
            padded_rows = pad_ids(local_rows, self._data.shape[0])
            values = _trim_rows(self._gather(self._data, padded_rows),
                                keys.size)
        if self._up_to_date is not None and len(blobs) >= 2:
            opt = GetOption.from_blob(blobs[1])
            if 0 <= opt.worker_id < self._up_to_date.shape[0]:
                self._up_to_date[opt.worker_id, local_rows] = True
        return [blobs[0]] + self._reply_values(values)

    # -- server-side request fusion (runtime/fusion.py,
    #    docs/SERVER_ENGINE.md; always entered under Server._lock_for,
    #    like process_add/process_get above) --
    def fuse_eligible(self, blobs: List[Blob], is_get: bool) -> bool:
        """Plain row-keyed host requests only. Every excluded layout
        carries per-request semantics the fused paths do not
        reproduce: device-key blobs (masking + device replies),
        sentinel protocols (-1/-2/-4 whole-table and dirty gets),
        codec frames and 1-bit pushes (per-request decode), elastic
        windows (row-level routing/NACKs), replica-routed foreign
        rows (host-store serve + repair descriptors), and stateful
        updaters (duplicate ids across requests must SUM inside one
        program — only stateless rules guarantee that,
        updater/engine.py apply_rows)."""
        if not blobs or blobs[0].on_device or self._elastic_active():
            return False
        keys = blobs[0].as_array(np.int32)
        if keys.size == 0 or int(keys[0]) < 0:
            return False
        if is_get:
            if self._replica is None:
                return True
            own = (keys >= self.row_offset) \
                & (keys < self.row_offset + self.my_rows)
            return bool(own.all())
        if not self._updater_stateless:
            return False
        if len(blobs) not in (2, 3) or blobs[1].on_device:
            return False
        if self._compress and _is_codec_blob(blobs[1]):
            return False
        return True

    def process_fused_get(self, requests: List[List[Blob]]
                          ) -> List[List[Blob]]:
        """N row Gets, ONE gather: concatenate the keys, dedup rows
        requested by more than one client (each gathers once —
        SERVER_FUSE_DEDUP_ROWS counts the savings), pad to the bucket
        grid and run the SAME cached gather program the serial path
        uses, then slice per request through the dedup inverse.
        Bit-identical to serial: gather-with-fill over identical row
        ids yields identical bits, and the per-request bookkeeping
        (hot tracking, replica read notes, the sparse staleness
        bitmap) replays per request below, in arrival order."""
        keys_list = [blobs[0].as_array(np.int32) for blobs in requests]
        local = np.concatenate(keys_list) - self.row_offset
        uniq, inverse = np.unique(local, return_inverse=True)
        count_event("SERVER_FUSE_DEDUP_ROWS",
                    int(local.size) - int(uniq.size))
        padded = pad_ids(uniq, self._data.shape[0])
        values = np.asarray(_trim_rows(self._gather(self._data, padded),
                                       uniq.size))
        out: List[List[Blob]] = []
        pos = 0
        for blobs, keys in zip(requests, keys_list):
            sel = inverse[pos:pos + keys.size]
            pos += keys.size
            if self._hot is not None:
                self._hot.note(keys)
            if self._replica is not None:
                self._replica.note_get(keys)
            if self._up_to_date is not None and len(blobs) >= 2:
                opt = GetOption.from_blob(blobs[1])
                if 0 <= opt.worker_id < self._up_to_date.shape[0]:
                    self._up_to_date[opt.worker_id,
                                     keys - self.row_offset] = True
            out.append([blobs[0]] + self._reply_values(values[sel]))
        return out

    def process_fused_add(self, requests: List[List[Blob]]) -> None:
        """N row Adds, ONE scatter per option sub-group: stateless
        rules SUM duplicate ids inside one program (updater/engine.py
        apply_rows), so concatenation is sum-equivalent to the serial
        left fold; requests carrying different option bytes (the rule
        scales the delta by per-request hyperparameters, and the
        dirty bitmap keys on the adder's worker id) split into
        ordered sub-groups. Parse-first contract
        (table_interface.py): every request decodes and reshapes
        before the first apply; a later apply failing raises
        PartialFuseError with the applied request count."""
        runs: List[tuple] = []  # (option bytes, option, [(keys, delta)])
        for blobs in requests:
            keys = blobs[0].as_array(np.int32)
            option = AddOption.from_blob(blobs[2]) \
                if len(blobs) == 3 else None
            okey = blobs[2].as_array(np.uint8).tobytes() \
                if len(blobs) == 3 else None
            delta = np.asarray(blobs[1].typed(self.dtype)).reshape(
                keys.size, self.num_col)
            if not runs or runs[-1][0] != okey:
                runs.append((okey, option, []))
            runs[-1][2].append((keys, delta))
        applied = 0
        for _, option, items in runs:
            try:
                all_keys = np.concatenate([k for k, _ in items])
                local = (all_keys - self.row_offset).astype(np.int32)
                delta = np.ascontiguousarray(
                    np.concatenate([d for _, d in items]))
                self._data = self._engine.apply_rows(
                    self._data, local, delta, option)
            except Exception as exc:  # noqa: BLE001
                from ..runtime.fusion import PartialFuseError
                raise PartialFuseError(applied, exc) from exc
            for keys, _ in items:
                applied += 1
                if self._up_to_date is not None:
                    self._mark_dirty(keys - self.row_offset, option)
                if self._replica is not None:
                    self._replica.note_add(keys)

    # -- hot-shard replication: holder/owner server sides
    #    (runtime/replica.py, docs/SHARDING.md) --
    def _replica_row_get(self, keys: np.ndarray,
                         own_mask: np.ndarray) -> List[Blob]:
        """Holder-side row Get carrying FOREIGN (replica-routed) rows:
        own rows gather as usual, foreign rows serve from the host-side
        replica store — a numpy gather, no device program. Rows the
        store lacks are simply absent from the reply (the worker
        repairs them to their owners). Reply layout: ``[keys = own
        rows + group rows, values, int32 replica descriptor]`` with
        REPLICA_SLOT stamped by the server actor."""
        own = np.ascontiguousarray(keys[own_mask])
        own_values = np.empty((0, self.num_col), self.dtype)
        if own.size:
            local = own - self.row_offset
            padded = pad_ids(local, self._data.shape[0])
            own_values = np.asarray(_trim_rows(
                self._gather(self._data, padded), own.size))
        foreign = np.unique(keys[~own_mask])
        groups, rkeys, rvalues = self._replica.store.serve(
            foreign, self.num_col, self.dtype)
        count_event(replica_mod.REPLICA_HIT, int(rkeys.size))
        count_event(replica_mod.REPLICA_MISS,
                    int(foreign.size) - int(rkeys.size))
        if not groups:
            # Every foreign row missed (the owner's initial push has
            # not landed, or a demotion raced the routing): reply the
            # own part only; the worker repairs the rest.
            return [Blob(own.view(np.uint8)), Blob(own_values)]
        desc = [len(groups)]
        for owner_sid, floor, n_rows in groups:
            desc.extend((int(owner_sid), int(floor) + 1, int(n_rows)))
        keys_out = np.ascontiguousarray(
            np.concatenate([own.astype(np.int32), rkeys]))
        values_out = np.concatenate([own_values, rvalues])
        self._reply_replica_rows_out = int(rkeys.size)
        return [Blob(keys_out.view(np.uint8)), Blob(values_out),
                Blob(np.asarray(desc, dtype=np.int32))]

    def take_reply_replica_rows(self) -> int:
        n, self._reply_replica_rows_out = self._reply_replica_rows_out, 0
        return n

    def apply_replica_map(self, epoch: int, rows) -> List[Message]:
        if self._replica is None:
            return []
        newly_promoted = self._replica.apply_map(epoch, rows)
        # Owner side: newly promoted own rows get their initial value
        # push NOW — until it lands, holders miss and workers repair.
        return self._replica_sync_messages(newly_promoted)

    def apply_replica_sync(self, blobs: List[Blob]) -> None:
        if self._replica is None:
            return
        rows = blobs[0].as_array(np.int32)
        values = blobs[1].as_array(self.dtype).reshape(rows.size,
                                                       self.num_col)
        meta = blobs[2].as_array(np.int32)
        self._replica.store.apply_sync(rows, values,
                                       owner_sid=int(meta[0]),
                                       version=int(meta[1]) - 1,
                                       watermark=bool(meta[2]),
                                       seq=int(meta[3]))

    def replica_redirty(self, blobs: List[Blob]) -> None:
        if self._replica is not None and blobs:
            self._replica.redirty(blobs[0].as_array(np.int32))

    def replica_flush_if_due(self) -> List[Message]:
        if self._replica is None:
            if self._hot is not None and self._hot.due:
                # -reshard_auto without replication: ship the load
                # window so the controller's skew planner sees it
                # (runtime/shard_map.py ReshardManager.note_report).
                rows, counts = self._hot.take_report(top_k=16)
                if rows.size == 0:
                    return []
                msg = Message(src=self._zoo.rank, dst=CONTROLLER_RANK,
                              msg_type=MsgType.Control_Replica_Report,
                              table_id=self.table_id)
                msg.push(Blob(rows))
                msg.push(Blob(counts))
                msg.push(Blob(np.asarray(
                    [self.num_row, self.server_id], dtype=np.int64)))
                return [msg]
            return []
        out: List[Message] = []
        dirty = self._replica.take_due_sync()
        if dirty is not None and (dirty.size or self.version
                                  > self._replica.last_sync_version):
            # An empty drain still refreshes when the shard version
            # advanced (adds landed on NON-promoted rows): the
            # watermark-only message re-certifies the holders' entries
            # at the new version, or every later read-your-writes floor
            # would read them as stale forever.
            out.extend(self._replica_sync_messages(dirty))
        report = self._replica.take_due_report()
        if report is not None:
            msg = Message(src=self._zoo.rank, dst=CONTROLLER_RANK,
                          msg_type=MsgType.Control_Replica_Report,
                          table_id=self.table_id)
            msg.push(Blob(report[0]))
            msg.push(Blob(report[1]))
            out.append(msg)
        return out

    def _replica_sync_messages(self, rows: np.ndarray) -> List[Message]:
        """Write-through fan-out: Request_ReplicaSync carrying current
        values + this shard's version for own promoted ``rows``, one
        message per holder server (chunked at -replica_sync_rows; the
        LAST chunk carries the watermark flag — ``rows`` must be the
        complete drained dirty set for the watermark to be sound, and
        an empty ``rows`` sends one watermark-only message). Runs on
        the server actor thread OUTSIDE the table lock — the gather
        dispatch takes the device guard itself."""
        cap = max(int(get_flag("replica_sync_rows")), 1)
        self._replica.last_sync_version = self.version
        out: List[Message] = []
        n_chunks = max((int(rows.size) + cap - 1) // cap, 1)
        chunks: List[tuple] = []
        for c in range(n_chunks):
            chunk = np.ascontiguousarray(rows[c * cap:(c + 1) * cap])
            if chunk.size:
                local = chunk - self.row_offset
                padded = pad_ids(local, self._data.shape[0])
                with device_lock.guard():
                    gathered = device_lock.settle(
                        self._gather(self._data, padded))
                values = np.asarray(_trim_rows(gathered, chunk.size))
            else:
                values = np.empty((0, self.num_col), self.dtype)
            chunks.append((chunk, values))
            count_event(replica_mod.REPLICA_SYNC)
        for sid in range(self._zoo.num_servers):
            if sid == self.server_id:
                continue
            for c, (chunk, values) in enumerate(chunks):
                # meta: [owner_sid, version+1, watermark, seq]. The
                # per-HOLDER seq is consecutive; a holder seeing a gap
                # drops this owner's entries before applying (a lost
                # chunk must not be papered over by this watermark).
                meta = np.asarray(
                    [self.server_id, self.version + 1,
                     1 if c == n_chunks - 1 else 0,
                     self._replica.next_sync_seq(sid)], dtype=np.int32)
                msg = Message(src=self._zoo.rank,
                              dst=self._zoo.server_rank(sid),
                              msg_type=MsgType.Request_ReplicaSync,
                              table_id=self.table_id)
                msg.push(Blob(chunk.view(np.uint8)))
                msg.push(Blob(values))
                msg.push(Blob(meta))
                out.append(msg)
        return out

    # -- live elastic resharding: server side (runtime/shard_map.py,
    #    docs/SHARDING.md; everything on the server actor thread) --
    def _elastic_active(self) -> bool:
        """Any dynamic-ownership state at all: the static fast paths
        stay byte-identical until the first migration touches this
        shard."""
        return bool(self._overlay or self._pending_delta or self._fwd
                    or self._mig_in or self._mig_out is not None
                    or self._smap is not None)

    def _gather_rows_elastic(self, keys: np.ndarray) -> np.ndarray:
        """Serve rows from the migrated-in overlay (host gather, like
        the replica store) or the device base range; a row that is
        neither — routed here by a map the cluster moved past, or its
        base chunk still in retransmit — NACKs retryably so the
        requester re-issues instead of consuming garbage."""
        keys = np.asarray(keys, dtype=np.int64)
        values = np.empty((keys.size, self.num_col), self.dtype)
        ov = self._overlay
        in_base = (keys >= self.row_offset) \
            & (keys < self.row_offset + self.my_rows)
        # Rows of an INCOMPLETE inbound migration must not fall through
        # to the base range: a range that left this shard and is coming
        # BACK still has its pre-first-move values in the device base —
        # serving them mid-retransmit would be silently stale. The same
        # goes for rows inside one of THIS shard's own forwarding
        # windows (a chained move A->B->C can land a stale-routed
        # request at the dead middle hop; its base copy must NACK, not
        # serve).
        in_mig = np.zeros(keys.size, dtype=bool)
        for mig in self._mig_in.values():
            if not mig.complete:
                in_mig |= (keys >= mig.lo) & (keys < mig.hi)
        fwd_mask, _, _ = self._fwd_route(keys)
        in_mig |= fwd_mask
        base_pos: List[int] = []
        for i, k in enumerate(keys.tolist()):
            row = ov.get(k)
            if row is not None:
                values[i] = row
            elif in_base[i] and not in_mig[i]:
                base_pos.append(i)
            else:
                raise RuntimeError(
                    f"{PEER_LOST_MARK} rank {self._zoo.rank}: row {k} "
                    f"not serveable on server {self.server_id} (shard "
                    f"map in motion) — re-issue")
        if base_pos:
            pos = np.asarray(base_pos, dtype=np.int64)
            local = (keys[pos] - self.row_offset).astype(np.int32)
            padded = pad_ids(local, self._data.shape[0])
            with device_lock.guard():
                gathered = device_lock.settle(
                    self._gather(self._data, padded))
            values[pos] = np.asarray(_trim_rows(gathered, local.size))
        return values

    def _elastic_row_add(self, keys: np.ndarray, delta,
                         option: Optional[AddOption]) -> None:
        """Row add under dynamic ownership: base rows batch through
        the jitted engine, overlay rows apply host-side via the
        stateless rule twin (+/- delta), rows whose base chunk is
        still in flight accumulate in the pending-delta ledger (merged
        when the retransmitted chunk lands). Rows a range move is
        streaming out re-dirty for the final chunk."""
        if self._mig_out is not None and self._mig_out.streaming:
            self._mig_out.note_add(keys.astype(np.int64))
        delta = np.asarray(delta, dtype=self.dtype).reshape(
            keys.size, self.num_col)
        ov, pend = self._overlay, self._pending_delta
        sign = self.dtype.type(self._updater_sign)
        in_base = (keys >= self.row_offset) \
            & (keys < self.row_offset + self.my_rows)
        in_mig = np.zeros(keys.size, dtype=bool)
        for mig in self._mig_in.values():
            if not mig.complete:
                in_mig |= (keys >= mig.lo) & (keys < mig.hi)
        # Rows in this shard's OWN forwarding windows are not appliable
        # here — EXCEPT on the both-apply path, where the server
        # deliberately applies the full add to the handoff copy so a
        # rollback keeps it (Server._process_add route branch).
        if not self._in_both_apply:
            fwd_mask, _, _ = self._fwd_route(keys)
        else:
            fwd_mask = np.zeros(keys.size, dtype=bool)
        # VALIDATE everything before mutating anything: a partial
        # apply followed by the retryable error would double-apply the
        # applied prefix when the caller re-issues (at-least-once).
        for i, k in enumerate(keys.tolist()):
            if k in ov:
                continue
            if fwd_mask[i] or not (in_base[i] or in_mig[i]):
                raise RuntimeError(
                    f"{PEER_LOST_MARK} rank {self._zoo.rank}: add to "
                    f"row {k} not owned by server {self.server_id} "
                    f"(shard map in motion) — re-issue")
        base_pos: List[int] = []
        for i, k in enumerate(keys.tolist()):
            row = ov.get(k)
            if row is not None:
                ov[k] = row + sign * delta[i]
            elif in_base[i] and not in_mig[i]:
                base_pos.append(i)
            else:
                prev = pend.get(k)
                pend[k] = sign * delta[i].copy() if prev is None \
                    else prev + sign * delta[i]
        if base_pos:
            pos = np.asarray(base_pos, dtype=np.int64)
            local = (keys[pos] - self.row_offset).astype(np.int32)
            self._data = self._engine.apply_rows(
                self._data, local, np.ascontiguousarray(delta[pos]),
                option)

    def shard_begin_out(self, desc) -> bool:
        lo, hi, src_sid, dst_sid, dst_rank, epoch = (
            int(v) for v in np.asarray(desc)[:6])
        if self.is_sparse or not self._updater_stateless:
            return False  # dirty bitmaps / stateful optimizer rows
            # cannot migrate live — the controller rolls the move back
        if self._mig_out is not None:
            if self._mig_out.epoch == epoch:
                # Duplicate Begin (the controller re-sent it): if the
                # handoff already happened, the controller's view is
                # STALLED — a lost Done with no destination traffic to
                # ride the re-announce on. Re-send the final chunk
                # (the destination dedups the seq and re-announces).
                self._mig_out.resend_final = self._mig_out.final_sent
                return True
            if self._mig_out.final_sent and epoch > self._mig_out.epoch:
                # The controller serializes moves, so a Begin for a
                # NEWER epoch proves the previous move committed — its
                # broadcast merely lost a race with this Begin (they
                # travel different connections, so nothing orders one
                # before the other). Retire it; the forwarding window
                # installed at its handoff stays.
                self._mig_out = None
            else:
                return False
        if src_sid != self.server_id:
            return False
        rows = np.arange(lo, hi, dtype=np.int64)
        mask, _, _ = self._fwd_route(rows)
        if bool(mask.any()):
            return False  # part of the range already moved away
        in_base = (rows >= self.row_offset) \
            & (rows < self.row_offset + self.my_rows)
        if any(not b and r not in self._overlay
               for r, b in zip(rows.tolist(), in_base.tolist())):
            return False  # not (fully) owned here
        self._mig_out = shard_map_mod.MigrationOut(
            self.table_id, lo, hi, src_sid, dst_sid, dst_rank, epoch)
        chaos.kill_point("shard_begin_accepted")
        return True

    def _shard_data_message(self, mig, seq: int, rows: np.ndarray,
                            is_final: bool) -> Message:
        if mig.frozen is not None:
            # Post-handoff retransmit: values come from the handoff
            # snapshot, never the live copy (forwarded Adds keep
            # both-applying there — see ElasticServerMixin.shard_ack).
            values = mig.frozen[rows - mig.lo] if rows.size else \
                np.empty((0, self.num_col), self.dtype)
        else:
            values = self._gather_rows_elastic(rows) if rows.size else \
                np.empty((0, self.num_col), self.dtype)
        desc = np.asarray(
            [mig.epoch, mig.src_sid, mig.dst_sid, self._zoo.rank,
             mig.lo, mig.hi, seq, 1 if is_final else 0,
             self.version + 1, len(mig.chunks)], dtype=np.int64)
        msg = Message(src=self._zoo.rank, dst=mig.dst_rank,
                      msg_type=MsgType.Request_ShardData,
                      table_id=self.table_id)
        msg.push(Blob(desc))
        msg.push(Blob(rows.astype(np.int64)))
        msg.push(Blob(values))
        count_event("SHARD_MIGRATE_ROWS", int(rows.size))
        return msg

    def _freeze_range(self, mig):
        whole = np.arange(mig.lo, mig.hi, dtype=np.int64)
        return self._gather_rows_elastic(whole) if whole.size \
            else np.empty((0, self.num_col), self.dtype)

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
            rows = msg.data[1].as_array(np.int64)
            values = msg.data[2].as_array(self.dtype).reshape(
                rows.size, self.num_col)
            if is_final:
                mig.final_items = set(int(r) for r in rows.tolist())
            pend = self._pending_delta
            for i, r in enumerate(rows.tolist()):
                if not is_final and mig.final_items is not None \
                        and r in mig.final_items:
                    # A reorder-delayed base chunk landing AFTER the
                    # final: the final's copy of this dirty row is
                    # newer — never overwrite it.
                    continue
                v = np.array(values[i], copy=True)
                extra = pend.pop(r, None)
                if extra is not None:
                    # Forwarded Adds that beat this (retransmitted)
                    # chunk merged into the ledger — fold them in.
                    v = v + extra
                self._overlay[r] = v
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
                # Post-handoff rollback: drop the forwarding window
                # and resume serving from the (still present) base
                # copy — Adds forwarded since the handoff are the
                # documented at-least-once loss of a dead destination.
                self._fwd = [f for f in self._fwd
                             if not (f[0] == mig.lo and f[1] == mig.hi
                                     and f[2] == mig.dst_sid)]
                log.error("rank %d: migration [%d,%d) -> server %d "
                          "rolled back — resuming ownership from the "
                          "handoff copy", self._zoo.rank, mig.lo,
                          mig.hi, mig.dst_sid)
                out.extend(self._drain_fwd_inflight())
            self._mig_out = None
        mig_in = self._mig_in.pop(epoch, None)
        if mig_in is not None:
            for r in [r for r in self._overlay
                      if mig_in.lo <= r < mig_in.hi]:
                del self._overlay[r]
            for r in [r for r in self._pending_delta
                      if mig_in.lo <= r < mig_in.hi]:
                del self._pending_delta[r]
            log.error("rank %d: inbound migration epoch %d aborted — "
                      "partial [%d,%d) state dropped", self._zoo.rank,
                      epoch, mig_in.lo, mig_in.hi)
        return out

    def apply_shard_map_server(self, epoch: int, smap, alive_sids):
        if self.is_sparse:
            return []
        if self._smap is not None and epoch <= self._smap.epoch:
            return []
        old = self._smap if self._smap is not None else \
            shard_map_mod.ShardMap.initial(
                self.num_row, self._zoo.num_servers,
                active=shard_map_mod.initial_active_servers(
                    self._zoo.num_servers))
        moved = old.diff_moved(smap)
        for lo, hi, old_sid, new_sid in moved:
            if old_sid == self.server_id:
                # Committed away: prune overlay copies; (re)install the
                # forwarding window for routers still behind this epoch.
                for r in [r for r in self._overlay if lo <= r < hi]:
                    del self._overlay[r]
                if not any(f[0] <= lo and hi <= f[1] and f[2] == new_sid
                           for f in self._fwd):
                    self._fwd.append(
                        (lo, hi, new_sid,
                         self._zoo.server_rank(new_sid)))
            if new_sid == self.server_id:
                # Committed to me: stale windows pointing away clear
                # (a range that came back must serve here again).
                self._prune_fwd_windows(lo, hi)
        if self._mig_out is not None \
                and self._mig_out.epoch <= epoch \
                and int(smap.owner_of(np.asarray(
                    [self._mig_out.lo]))[0]) == self._mig_out.dst_sid:
            self._mig_out = None  # committed
        for e in [e for e, m in self._mig_in.items()
                  if m.complete and e <= epoch]:
            self._mig_in.pop(e)
        if moved and self._replica is not None:
            log.info("rank %d: table %d shard map went dynamic — "
                     "retiring hot-row replication for it (ownership "
                     "moves supersede read replicas)", self._zoo.rank,
                     self.table_id)
            self._replica = None
        # A commit broadcast proves the forwarded requests' window
        # destination is alive and serving: the rollback ledger resets.
        self._fwd_inflight = []
        self._smap = smap
        return []

    def shard_forward_get(self, msg: Message):
        if not self._fwd or not msg.data:
            return None
        blob0 = msg.data[0]
        if blob0.on_device:
            return None
        keys = blob0.as_array(np.int32)
        if keys.size == 0 or (keys.size == 1 and keys[0] < 0):
            # Sentinel ops from routers still on the frozen layout keep
            # the frozen path (they see the handoff-time snapshot of
            # moved rows until their map catches up — bounded by the
            # broadcast cadence; docs/SHARDING.md).
            return None
        k64 = keys.astype(np.int64)
        mask, dst_sid, dst_rank = self._fwd_route(k64)
        if not bool(mask.any()):
            return None
        count_event("SHARD_FWD")
        dsts = sorted({int(d) for d in dst_sid[mask]})
        if len(dsts) > 1:
            raise RuntimeError(
                f"{PEER_LOST_MARK} rows span {len(dsts)} forwarding "
                f"windows (router several epochs behind) — re-issue "
                f"after the next shard-map broadcast")
        if self._hot is not None:
            self._hot.note(keys[~mask])
        overflow = self._note_fwd_inflight(msg.src, msg.msg_id, True)
        pig_keys = np.ascontiguousarray(keys[~mask])
        pig_vals = self._gather_rows_elastic(pig_keys) if pig_keys.size \
            else np.empty((0, self.num_col), self.dtype)
        meta = np.asarray([self._zoo.rank, self.version + 1],
                          dtype=np.int64)
        fwd = Message(src=msg.src, dst=int(dst_rank[mask][0]),
                      msg_type=MsgType.Request_FwdGet,
                      table_id=self.table_id, msg_id=msg.msg_id)
        fwd.push(Blob(meta))
        fwd.push(Blob(np.ascontiguousarray(keys[mask]).view(np.uint8)))
        fwd.push(Blob(pig_keys.view(np.uint8)))
        fwd.push(Blob(pig_vals))
        return [fwd] + overflow

    def process_forward_get(self, blobs: List[Blob]):
        meta = blobs[0].as_array(np.int64)
        src_rank, src_version = int(meta[0]), int(meta[1]) - 1
        fwd_keys = blobs[1].as_array(np.int32)
        pig_keys = blobs[2].as_array(np.int32)
        pig_vals = blobs[3].as_array(self.dtype).reshape(
            pig_keys.size, self.num_col)
        if self._hot is not None:
            self._hot.note(fwd_keys)
        vals = self._gather_rows_elastic(fwd_keys.astype(np.int64))
        keys_out = np.ascontiguousarray(
            np.concatenate([pig_keys, fwd_keys]).astype(np.int32))
        vals_out = np.concatenate([pig_vals, vals]) if pig_keys.size \
            else vals
        # The source's piggybacked rows are the reply's MAIN body (the
        # reply impersonates the source rank, version-stamped with the
        # source's shard version); this shard's rows ride as one
        # replica group at OUR version floor — the PR-7 reply contract
        # reused verbatim, so the requester's attribution, RYW floors
        # and repair machinery apply unchanged.
        desc = np.asarray([1, self.server_id, self.version + 1,
                           int(fwd_keys.size)], dtype=np.int32)
        return ([Blob(keys_out.view(np.uint8)), Blob(vals_out),
                 Blob(desc)], int(fwd_keys.size), src_rank, src_version)

    def _decode_add_values(self, blobs: List[Blob],
                           n: int) -> Optional[np.ndarray]:
        """Host decode of a row add's delta for window splitting; None
        when the layout cannot be split (unknown framing)."""
        if len(blobs) >= 2 and blobs[1].on_device:
            return np.asarray(blobs[1].typed(self.dtype)).reshape(
                n, self.num_col)
        if self._one_bit and len(blobs) == 4:
            return _onebit_decode(blobs[1], blobs[2]).reshape(
                n, self.num_col)
        if len(blobs) in (2, 3):
            if self._compress and _is_codec_blob(blobs[1]):
                return _decompress_values(blobs[1], self.dtype).reshape(
                    n, self.num_col)
            return blobs[1].as_array(self.dtype).reshape(
                n, self.num_col)
        return None

    def shard_forward_add(self, msg: Message):
        if not self._fwd or not msg.data:
            return None
        blobs = msg.data
        if blobs[0].on_device:
            return None  # device-key adds are frozen-layout only
        keys = blobs[0].as_array(np.int32)
        if keys.size == 0:
            return None
        if keys.size == 1 and keys[0] < 0:
            if int(keys[0]) != -1:
                return None
            keys_eff = np.arange(self.row_offset,
                                 self.row_offset + self.my_rows,
                                 dtype=np.int64)
        else:
            keys_eff = keys.astype(np.int64)
        mask, dst_sid, dst_rank = self._fwd_route(keys_eff)
        if not bool(mask.any()):
            return None
        delta = self._decode_add_values(blobs, keys_eff.size)
        if delta is None:
            raise RuntimeError(
                f"{PEER_LOST_MARK} cannot split this add layout "
                f"across a forwarding window — re-issue")
        option_blob = None
        if len(blobs) == 3:
            option_blob = blobs[2]
        elif self._one_bit and len(blobs) == 4:
            option_blob = blobs[3]
        count_event("SHARD_FWD")
        # BOTH-APPLY: the full add also applies locally (silently, no
        # ack) — exactly one copy survives: on commit the destination's
        # (which got the forwarded subset), on rollback the source's
        # (which applied everything). The ONE ack the requester's
        # waiter needs comes from the destination carrying the real
        # msg_id; additional windows (router several epochs behind)
        # forward with msg_id=-1 — applied, never acked (their Adds'
        # visibility is the documented at-least-once window).
        outs: List[Message] = list(
            self._note_fwd_inflight(msg.src, msg.msg_id, False))
        first = True
        for d in sorted({int(x) for x in dst_sid[mask]}):
            m = mask & (dst_sid == d)
            rank = int(dst_rank[m][0])
            fwd = Message(src=msg.src, dst=rank,
                          msg_type=MsgType.Request_FwdAdd,
                          table_id=self.table_id,
                          msg_id=msg.msg_id if first else -1)
            fwd.push(Blob(np.asarray([self._zoo.rank], dtype=np.int64)))
            fwd.push(Blob(np.ascontiguousarray(
                keys_eff[m].astype(np.int32)).view(np.uint8)))
            fwd.push(Blob(np.ascontiguousarray(delta[m])))
            if option_blob is not None:
                fwd.push(option_blob)
            outs.append(fwd)
            first = False
        return msg, outs

    def _reply_values(self, values) -> List[Blob]:
        """Get replies run through the wire filter for sparse tables
        (ref: sparse_matrix_table.cpp:261-308). Always lossless — the
        server keeps no per-consumer error-feedback state."""
        if self._compress:
            return _compress_values(np.asarray(values))[0]
        return [Blob(values)]

    # Always entered under Server._lock_for (process_add/process_get
    # server paths) — the guard is one call layer up, not lexical here.
    def _fused_add_get_dirty(self, blobs: List[Blob]) -> List[Blob]:  # mvlint: ignore[device-dispatch]
        """-4: apply a row add, then reply the get-worker's dirty rows
        gathered from the UPDATED table — ONE compiled program instead
        of the separate scatter + gather pair (two per-dispatch launch
        costs, not measured on the current machine). Exact
        composition of process_add(rows) + _sparse_get_all_device:
        same dirty bookkeeping, same reply layout. Host-transfer
        trims: the caller may ship a device mirror of the add ids
        (blob 5), and an unchanged dirty set reuses its cached device
        id vector instead of re-uploading ~0.5 MB per call."""
        CHECK(self._up_to_date is not None and len(blobs) in (5, 6),
              "-4 is the fused sparse add+dirty-get")
        rows = blobs[1].as_array(np.int32)
        delta = blobs[2].typed(self.dtype)
        add_opt = AddOption.from_blob(blobs[3])
        get_opt = GetOption.from_blob(blobs[4])
        local = rows - self.row_offset
        self._mark_dirty(local, add_opt)
        dirty = self._dirty_ids(get_opt.worker_id)
        if len(blobs) == 6:
            # Device mirror of the add ids — single server owns row
            # offset 0, so global ids ARE local ids. Arrives BUCKET-
            # PADDED (caller contract), matching the host path below so
            # the fused program compiles once per bucket width.
            add_ids = blobs[5].typed(np.int32)
        else:
            add_ids = pad_ids(local, self._data.shape[0])
        cached = self._dirty_dev_cache
        if cached is not None and np.array_equal(cached[0], dirty):
            get_ids = cached[1]
        else:
            import jax.numpy as jnp
            get_ids = jnp.asarray(pad_ids(dirty, self._data.shape[0]))
            self._dirty_dev_cache = (dirty, get_ids)
        self._data, values = self._engine.apply_rows_gather(
            self._data, add_ids,
            _shaped_rows(delta, rows.size, self.num_col), add_opt,
            get_ids, self.num_col)
        return [Blob(dirty + self.row_offset),
                Blob(_trim_rows(values, dirty.size)),
                Blob(np.array([self.server_id], dtype=np.int32))]

    def _sparse_get_all(self, opt: GetOption) -> List[Blob]:
        """Return only this worker's dirty rows
        (ref: sparse_matrix_table.cpp:226-258)."""
        dirty, values = self._dirty_rows(opt)
        return [Blob(dirty + self.row_offset)] + self._reply_values(values)

    def _sparse_get_all_device(self, opt: GetOption) -> List[Blob]:
        """Dirty rows with the values left in HBM (host ids, device
        payload; no wire filter — this path never crosses a wire). The
        server id rides along: a server with ZERO dirty rows replies an
        empty id vector, which the worker could not attribute by key
        range (multi-server replies would collide on a guessed id)."""
        dirty, values = self._dirty_rows(opt)
        return [Blob(dirty + self.row_offset), Blob(values),
                Blob(np.array([self.server_id], dtype=np.int32))]

    def _dirty_ids(self, wid: int) -> np.ndarray:
        """The consumer's dirty row set, flipped clean on read — the
        ONE copy of the bookkeeping shared by the composed and fused
        dirty paths (they must never diverge)."""
        CHECK(0 <= wid < self._up_to_date.shape[0], "bad worker id")
        dirty = np.nonzero(~self._up_to_date[wid])[0].astype(np.int32)
        self._up_to_date[wid, dirty] = True
        return dirty

    def _dirty_rows(self, opt: GetOption):
        dirty = self._dirty_ids(opt.worker_id)
        padded_rows = pad_ids(dirty, self._data.shape[0])
        values = _trim_rows(self._gather(self._data, padded_rows),
                            dirty.size)
        return dirty, values

    @functools.cached_property
    def _gather(self):
        n_col = self.num_col
        # The scope names the gather's operations in a device trace;
        # the program keeps the name its lambda gives it.
        return jax.jit(jax.named_scope("mv.table.gather")(
            lambda data, rows: data.at[rows].get(
                mode="fill", fill_value=0)[..., :n_col]))

    @property
    def _shard_bounds(self):
        """(row_offset, my_rows) when global row ids need masking to
        this shard — multi-server only. A single server owns every row,
        and the extra in-jit compare/offset would cost nothing, but a
        SEPARATE program variant would recompile the engine's scatter;
        None keeps the round-3 single-server program byte-identical."""
        if self._zoo.num_servers > 1:
            return (self.row_offset, self.my_rows)
        return None

    @functools.cached_property
    def _gather_bounded(self):
        """Masked gather in ONE jitted program (multi-server device
        keys): global ids -> local indices, foreign rows -> the padded
        row count, which gather-fills 0. NOTE: simply subtracting the
        offset is NOT enough — a foreign row could land inside this
        shard's padding and read whatever a scatter left there."""
        ofs, n = self.row_offset, self.my_rows
        padded = self._data.shape[0]
        n_col = self.num_col
        import jax.numpy as jnp

        @jax.named_scope("mv.table.gather")
        def gather(data, rows):
            local = jnp.where((rows >= ofs) & (rows < ofs + n),
                              rows - ofs, padded)
            return data.at[local].get(mode="fill",
                                      fill_value=0)[..., :n_col]

        return jax.jit(gather)

    def _values(self):
        """Fresh-buffer snapshot of the logical rows (see ArrayServer._values
        — the live storage is donated away by the next update)."""
        return self._snapshot(self._data)

    @functools.cached_property
    def _snapshot(self):
        n, n_col = self.my_rows, self.num_col

        def snapshot(x):
            with jax.named_scope("mv.table.snapshot"):
                return jax.numpy.copy(x[:n, :n_col])

        return jax.jit(snapshot)

    # -- checkpoint (ref: matrix_table.cpp:456-464) --
    def store(self, stream) -> None:
        stream.write(np.asarray(self._values()).tobytes())

    # -- async snapshot split (runtime/snapshot.py) --
    def snapshot_state(self):
        """Capture under the caller's table lock (see
        ArrayServer.snapshot_state: the updater DONATES the live
        storage away on the next add, so the capture must copy into a
        fresh device buffer; host transfer happens off-lock). Under
        dynamic ownership the cut additionally copies the migrated-in
        overlay, the pending-delta ledger and the forwarding windows —
        the elastic half of the shard's state."""
        base = device_lock.settle(self._snapshot(self._data))
        if not self._elastic_active():
            return base
        return (base,
                {k: v.copy() for k, v in self._overlay.items()},
                {k: v.copy() for k, v in self._pending_delta.items()},
                list(self._fwd))

    def snapshot_meta(self):
        """Manifest sidecar (runtime/snapshot.py): the shard-map epoch
        and this shard's elastic inventory, so a rejoining server
        restores into the RIGHT map — its payload parses as
        elastic-format and the controller's re-register re-broadcast
        re-anchors the epoch (docs/SHARDING.md)."""
        if not self._elastic_active():
            return None
        return {"elastic": 1,
                "shard_epoch": self._smap.epoch
                if self._smap is not None else -1,
                "overlay_rows": len(self._overlay),
                "fwd": [[int(lo), int(hi), int(sid)]
                        for lo, hi, sid, _rank in self._fwd]}

    def write_snapshot(self, state, stream) -> None:
        if isinstance(state, tuple):
            import pickle
            import struct
            base, overlay, pending, fwd = state
            side = pickle.dumps({"overlay": overlay,
                                 "pending": pending, "fwd": fwd})
            stream.write(struct.pack("<Q", len(side)))
            stream.write(side)
            stream.write(np.asarray(base).tobytes())
            return
        stream.write(np.asarray(state).tobytes())

    def load_with_meta(self, stream, meta) -> None:
        if not meta or not meta.get("elastic"):
            self.load(stream)
            return
        import pickle
        import struct
        (length,) = struct.unpack("<Q", stream.read(8))
        side = pickle.loads(stream.read(length))
        self._overlay = dict(side.get("overlay", {}))
        self._pending_delta = dict(side.get("pending", {}))
        self._fwd = [(int(lo), int(hi), int(sid),
                      self._zoo.server_rank(int(sid)))
                     for lo, hi, sid, *_ in side.get("fwd", [])]
        self.load(stream)
        log.info("rank %d: table %d restored elastic state — %d "
                 "overlay rows, %d forwarding window(s), recorded "
                 "shard epoch %s (the controller re-broadcasts the "
                 "live map on re-register)", self._zoo.rank,
                 self.table_id, len(self._overlay), len(self._fwd),
                 meta.get("shard_epoch"))

    def load(self, stream) -> None:
        raw = stream.read(self.my_rows * self.num_col * self.dtype.itemsize)
        values = np.frombuffer(raw, dtype=self.dtype).reshape(
            self.my_rows, self.num_col)
        padded = self._data.shape[0]
        host = np.zeros((padded, self._col_store), self.dtype)
        host[:self.my_rows, :self.num_col] = values
        with device_lock.guard():
            self._data = device_lock.settle(
                jax.device_put(host, self._sharding))

    @property
    def raw(self):
        return self._values()

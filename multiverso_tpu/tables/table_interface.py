"""Worker/Server table bases: the async Get/Add plumbing.

TPU-native equivalent of the reference's table interface
(ref: include/multiverso/table_interface.h:24-75, src/table.cpp:13-112).
Contract preserved exactly:

- ``get_async``/``add_async`` allocate a per-request ``Waiter``, build a
  request message and hand it to the worker actor (ref: src/table.cpp:41-82);
- the worker actor calls ``partition`` to split the request into
  per-server-shard blob lists and re-arms the waiter via ``reset(msg_id, n)``
  (ref: src/worker.cpp:30-76);
- each server reply triggers ``process_reply_get`` + ``notify`` until the
  waiter releases ``wait(msg_id)`` (ref: src/worker.cpp:78-88,
  src/table.cpp:84-111).

Where the reference keeps one Get's destination in registers of the
table, a Get here carries a SINK: ``_get_to`` registers it under the
request's id before the request is sent, ``process_reply_get`` decodes a
reply shard and hands it to ``_reply_sink().place``, and the entry goes
where the id retires.

``ServerTable`` is ``Serializable`` — ``store``/``load`` stream the shard
state for checkpointing (ref: include/multiverso/table_interface.h:61-75).
"""

from __future__ import annotations

import functools
import io
import itertools
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

from ..core.blob import Blob
from ..core.message import PEER_LOST_MARK, Message, MsgType
from ..runtime import actor as actors
from ..runtime.net import PeerLostError
from ..runtime.zoo import current_zoo
from ..util import log
from ..util.configure import get_flag
from ..util.dashboard import Dashboard, monitor
from ..util.lock_witness import named_lock
from ..util.waiter import Waiter
from .client_cache import VersionTracker

#: Completed-request errors retained for late ``wait`` calls. Beyond
#: this, the oldest completed entries are reaped — fire-and-forget async
#: requests that fail are otherwise a slow leak over a long run.
_MAX_RETAINED_ERRORS = 128

#: Per-instance serial for ServerTable state-lock names: the lock-order
#: witness keys its graph by NAME, so instances must not share one
#: (client_cache.py precedent).
_state_lock_serial = itertools.count()


def _issuing(span):
    """Decorator for a table's public async entry points: the entry's
    whole body, id checks and copies to the message in the worker
    actor's mailbox, is one span of the CALLER's thread."""
    def decorate(entry):
        @functools.wraps(entry)
        def issue(table, *args, **kwargs):
            with span(table):
                return entry(table, *args, **kwargs)
        return issue
    return decorate


issues_get = _issuing(
    lambda table: monitor("CLIENT_ISSUE_GET", table=table.table_id))
issues_add = _issuing(
    lambda table: monitor("CLIENT_ISSUE_ADD", table=table.table_id))


class TableRequestError(RuntimeError):
    """A table request failed remotely (server-side table logic or
    worker-side partition); raised by ``wait`` in the REQUESTER's thread.
    The actor runtime can only log — this carries the failure to the code
    that can actually handle it."""


class RpcTimeoutError(TableRequestError):
    """A table request's replies did not all arrive within
    ``-rpc_timeout_s``; the message names the peer ranks still pending,
    the table and the msg_id (mirroring the allreduce engine's
    ``-allreduce_timeout_s`` rich errors)."""


class TableSink:
    """A whole-table Get into a host buffer: a server's shard goes to
    its offsets (``keys`` None), a sparse table's dirty rows each to
    their own index."""

    device = False

    def __init__(self, out, offsets):
        self.out = out
        self.offsets = offsets

    def place(self, keys, values, version, server) -> None:
        if keys is None:
            self.out[self.offsets[server]:self.offsets[server + 1]] = values
        else:
            self.out[keys] = values


class DeviceSink:
    """A Get whose reply stays in HBM: ``parts`` by server id, for the
    caller to add up (``sums``: device keys go to every server alike and
    each fills the rows of the others with zeros) or to concatenate in
    server order; ``ids`` keeps each part's row ids where the caller asks
    for them (a dirty-row pull)."""

    device = True

    def __init__(self, sums: bool = False, keep_ids: bool = False,
                 row_length: int = 1, num_server: int = 1):
        self.parts: Dict[int, object] = {}
        self.sums = sums
        self.ids: Optional[Dict[int, object]] = {} if keep_ids else None
        self._row_length = row_length
        self._num_server = num_server

    def place(self, keys, values, version, server) -> None:
        if server is None:
            # A two-blob reply (host row ids, their rows) names no
            # server: it is one server's run of a sorted request, and its
            # first key names the server under the division rule.
            server = 0 if keys.size == 0 else int(
                min(keys[0] // self._row_length, self._num_server - 1))
        self.parts[server] = values
        if self.ids is not None:
            self.ids[server] = keys

    def ordered(self) -> List:
        return [self.parts[sid] for sid in sorted(self.parts)]


class CacheOnlySink:
    """A prefetch: the table stores every host reply shard in its client
    cache before the sink sees it, and no buffer waits for this one."""

    device = False

    def place(self, keys, values, version, server) -> None:
        pass


class WorkerTable:
    """Client-side handle; lives on every worker rank."""

    def __init__(self, zoo=None):
        self._zoo = zoo if zoo is not None else current_zoo()
        self.table_id: int = self._zoo.register_worker_table(self)
        self._msg_id = 0
        self._waitings: Dict[int, Waiter] = {}
        self._errors: Dict[int, str] = {}
        self._mutex = threading.Lock()
        # Client-cache plumbing: per-server latest-version tracking and
        # the reply context the worker actor sets around
        # process_reply_get (server id, version stamp, request id) so
        # subclasses can attribute replies without a signature change.
        self._version_tracker = VersionTracker()
        #: Client caches registered by subclasses — invalidated when a
        #: serving shard changes GENERATION (server restart + snapshot
        #: restore resets its version counter; docs/FAULT_TOLERANCE.md).
        self._caches: List = []
        #: Data-generation counter for DERIVED read-side caches (the
        #: serving tier's neighbors index and hot-response cache,
        #: docs/SERVING.md): bumped on every event that makes version
        #: arithmetic against the old shard counters meaningless — a
        #: server-generation regression (PR-6 rejoin) and a shard-map
        #: epoch change (PR-12 elastic resharding). Version staleness
        #: alone misses both: a restored/remapped shard's counter can
        #: sit BELOW a derived cache's anchor version forever, so
        #: ``latest - anchor <= bound`` would hold while the underlying
        #: rows changed arbitrarily. Derived caches record the value at
        #: build/store time and treat any mismatch as forced
        #: invalidation. Written on the worker actor thread, read from
        #: serving threads — int assignment, GIL-atomic.
        self._data_generation = 0
        self._on_complete: Dict[int, List[Callable]] = {}
        # Where each Get's reply goes: msg_id -> sink, an object with
        # ``place(keys, values, version, server)`` and ``device``.
        # Written on the requester's thread BEFORE the request is pushed
        # to the worker actor's mailbox (the push is the happens-before
        # edge), read on the worker actor's thread, dropped where the id
        # retires: completion, RPC timeout, abort. Plain dict
        # operations, GIL-atomic. Any number of Gets may be in flight.
        self._sinks: Dict[int, object] = {}
        self._reply_server = -1
        self._reply_version = -1
        self._reply_msg_id = -1
        self._reply_replica_rows = 0
        # Read-your-writes floors per server shard: the latest version
        # OUR OWN Add acks carried. A replica-served group whose floor
        # is below this would hand back pre-write values of rows this
        # worker already saw acknowledged — those rows repair to the
        # owner instead (docs/SHARDING.md). Written/read on the worker
        # actor thread only.
        self._add_floor: Dict[int, int] = {}
        # Replica repair staging: process_reply_get (worker actor
        # thread) records (owner_server_id, request_blobs) follow-ups
        # for rows a replica holder could not serve validly; the worker
        # actor drains them via take_repairs and transfers the reply's
        # notify onto the follow-up requests.
        self._pending_repairs: List = []
        # Request id of the partition in progress (set by the worker
        # actor around ``partition``): replica-routing tables key their
        # per-request routing bookkeeping by it.
        self._partition_msg_id = -1

    # -- public sync API (ref: src/table.cpp:29-38) --
    def get_raw(self, keys: Blob, extra: Sequence[Blob] = ()) -> None:
        with monitor("WORKER_TABLE_SYNC_GET"):
            self.retrying_wait(lambda: self.get_async_raw(keys, extra))

    def add_raw(self, keys: Blob, values: Blob,
                option_blob: Optional[Blob] = None) -> None:
        with monitor("WORKER_TABLE_SYNC_ADD"):
            self.retrying_wait(
                lambda: self.add_async_raw(keys, values, option_blob))

    def retrying_wait(self, issue: Callable[[], int]) -> None:
        """Issue a request and wait; on a retryable PeerLostError
        re-issue with bounded exponential backoff (``-rpc_retry_max`` /
        ``-rpc_backoff_ms``). With retries disabled (the default) this
        is exactly ``wait(issue())``.

        Semantics are AT-LEAST-ONCE for Adds: the dead server may have
        applied the original before crashing, or — multi-server — the
        shards on surviving servers applied while the lost shard did
        not, so a retry re-applies them. For the additive updates the
        PS serves this is bounded noise, the same order as what async
        staleness already admits; exactly-once callers must build
        idempotency above this layer (docs/FAULT_TOLERANCE.md).

        BSP (``-sync``) force-disables the re-issue: the sync servers
        count exactly one request per worker per step on their vector
        clocks, so a retried request double-ticks the surviving
        servers' clocks and permanently skews this worker ahead (the
        leveling invariant breaks and cached peers strand). Sync-mode
        fault tolerance is backup workers for dead WORKERS and a loud
        abort for dead servers (zoo.peer_lost)."""
        retry_max = int(get_flag("rpc_retry_max", 0))
        if retry_max and get_flag("sync", False):
            retry_max = 0
        backoff = max(float(get_flag("rpc_backoff_ms", 50.0)), 1.0) / 1e3
        attempt = 0
        while True:
            try:
                self.wait(issue())
                return
            except PeerLostError:
                attempt += 1
                if attempt > retry_max:
                    raise
                delay = min(backoff * (2 ** (attempt - 1)), 5.0)
                log.error("table %d: request lost its peer; retry "
                          "%d/%d in %.0f ms", self.table_id, attempt,
                          retry_max, delay * 1e3)
                time.sleep(delay)

    # -- async API (ref: src/table.cpp:41-82) --
    def get_async_raw(self, keys: Blob, extra: Sequence[Blob] = ()) -> int:
        return self.request_async_raw(MsgType.Request_Get, [keys, *extra])

    def add_async_raw(self, keys: Blob, values: Blob,
                      option_blob: Optional[Blob] = None) -> int:
        blobs = [keys, values]
        if option_blob is not None:
            blobs.append(option_blob)
        return self.request_async_raw(MsgType.Request_Add, blobs)

    def request_async_raw(self, msg_type: MsgType,
                          blobs: Sequence[Blob]) -> int:
        """Generic async request with an arbitrary blob layout — the
        table subclass's ``partition`` defines what the blobs mean
        (e.g. the matrix table's fused add + dirty-get request)."""
        msg_id = self._new_request()
        self._send_request(msg_type, blobs, msg_id)
        return msg_id

    def _get_to(self, sink, blobs: Sequence[Blob]) -> int:
        """Issue a Get whose reply shards go to ``sink``."""
        msg_id = self._new_request()
        self._sinks[msg_id] = sink
        self._send_request(MsgType.Request_Get, blobs, msg_id)
        return msg_id

    def _reply_sink(self):
        """The sink of the request whose reply is being processed
        (worker actor thread, between ``_begin_reply`` and
        ``_end_reply``). A reply that outlived its request touches no
        buffer."""
        sink = self._sinks.get(self._reply_msg_id)
        log.CHECK(sink is not None,
                  "Get reply with no outstanding destination: its "
                  "request timed out or was aborted")
        return sink

    def _send_request(self, msg_type: MsgType, blobs: Sequence[Blob],
                      msg_id: int) -> None:
        """Build and route a request message for an ALREADY-allocated
        id — the prefetch/dedup machinery allocates first (so reply
        routing state can be registered before anything is in flight)
        and sends later (possibly from a completion callback)."""
        msg = Message(src=self._zoo.rank, dst=-1, msg_type=msg_type,
                      table_id=self.table_id, msg_id=msg_id)
        for blob in blobs:
            msg.push(blob)
        self._zoo.send_to(actors.WORKER, msg)

    def _local_done(self) -> int:
        """A request satisfied locally (cache hit / no-op prefetch):
        allocate a normal request id and complete it immediately, so
        async callers get an id whose ``wait`` returns at once."""
        msg_id = self._new_request()
        self.notify(msg_id)
        return msg_id

    def _new_request(self) -> int:
        # Requests issued AFTER an abort would wait on a reply that can
        # never come (their waiter postdates abort()'s release sweep) —
        # refuse up front.
        self._check_aborted()
        with self._mutex:
            self._msg_id += 1
            msg_id = self._msg_id
            self._waitings[msg_id] = Waiter(1)
        return msg_id

    # -- waiter plumbing, driven by the worker actor
    #    (ref: src/table.cpp:84-111) --
    def wait(self, msg_id: int, timeout: Optional[float] = None) -> bool:
        self._check_aborted()
        with self._mutex:
            waiter = self._waitings.get(msg_id)
        if waiter is None:
            self._raise_if_failed(msg_id)
            return True  # already completed
        # -rpc_timeout_s turns an unbounded wait into a DIAGNOSTIC one:
        # an explicit caller timeout keeps the boolean contract, but a
        # flag-sourced expiry raises, naming what never replied — the
        # difference between "a knob the caller handles" and "a lost
        # reply that would otherwise block this thread forever".
        flag_timeout = None
        if timeout is None:
            configured = float(get_flag("rpc_timeout_s", 0.0))
            if configured > 0:
                flag_timeout = configured
        # Only the blocking itself: a request already complete (the
        # early return above) never counts.
        with monitor("TABLE_WAIT", msg_id=msg_id, table=self.table_id):
            ok = waiter.wait(timeout=timeout if timeout is not None
                             else flag_timeout)
        if waiter.woke_after_ms is not None:
            # The hand-off back: the worker actor's completing notify
            # to this thread running again (two threads, one GIL).
            Dashboard.get("TABLE_WAKE").add(waiter.woke_after_ms)
        self._check_aborted()
        if ok:
            with self._mutex:
                self._waitings.pop(msg_id, None)
            self._raise_if_failed(msg_id)
        elif flag_timeout is not None:
            worker = self._zoo._actors.get(actors.WORKER)
            has_pending = (worker is not None
                           and hasattr(worker, "pending_peers"))
            peers = worker.pending_peers(self.table_id, msg_id) \
                if has_pending else []
            pending = waiter.pending
            # The request is ABANDONED: reap its waiter, sink, recorded
            # error, and the worker's in-flight entries, or repeated
            # timeouts (the flag's target scenario is a peer that
            # never replies) leak one of each per request and pollute
            # later pending_peers diagnostics; its completion callbacks
            # run, or a prefetch that never lands keeps every later Get
            # of its rows joined to it. A late straggler reply finds no
            # sink, and no waiter in notify().
            self._retire(msg_id)
            with self._mutex:
                self._errors.pop(msg_id, None)
            if has_pending:
                worker.forget_request(self.table_id, msg_id)
            raise RpcTimeoutError(
                f"table {self.table_id} request {msg_id}: "
                f"{pending} shard replies still missing after "
                f"{flag_timeout}s (peers pending: "
                f"{peers if peers else 'unknown'})")
        return ok

    def _raise_if_failed(self, msg_id: int) -> None:
        with self._mutex:
            error = self._errors.pop(msg_id, None)
        if error is not None:
            if PEER_LOST_MARK in error:
                # Typed retryable failure: the serving rank died; a
                # restarted replacement can serve a re-issue.
                raise PeerLostError(error)
            raise TableRequestError(error)

    def _check_aborted(self) -> None:
        reason = getattr(self, "_abort_reason", None)
        if reason is not None:
            from ..runtime.zoo import ClusterAborted
            raise ClusterAborted(reason)

    def abort(self, reason: str) -> None:
        """Release every outstanding waiter; subsequent/blocked ``wait``
        calls raise ClusterAborted (peer-failure path — without this a
        request to a dead rank blocks forever; the reference has no
        failure detection at all, SURVEY.md section 5.3)."""
        self._abort_reason = reason
        self._sinks.clear()  # nor will their replies be placed
        with self._mutex:
            waiters = list(self._waitings.values())
        for waiter in waiters:
            waiter.release()

    def fail(self, msg_id: int, reason: str, count: bool = True) -> None:
        """Record a remote failure for a request; the requester's
        ``wait(msg_id)`` raises TableRequestError once the request
        completes. With ``count`` the failure also counts as one shard
        reply (notify) — it must NOT release the waiter outright: a
        multi-shard request with sibling replies still in flight would
        otherwise unblock early, over a buffer its siblings still write
        (the request's sink stays until every shard is counted).
        Callers whose control flow already notifies (the reply
        handlers' finally blocks) pass ``count=False``. At most
        ``_MAX_RETAINED_ERRORS`` completed-request entries are retained
        for late ``wait`` calls; past that the oldest completed ones are
        reaped so never-waited fire-and-forget failures don't accumulate
        over a long run."""
        with self._mutex:
            # First error wins: follow-up failures of the same request
            # (e.g. the empty BSP clock-tick shards sent after a
            # partition failure) must not mask the root cause.
            self._errors.setdefault(msg_id, reason)
            if len(self._errors) > _MAX_RETAINED_ERRORS:
                # Insertion order = age; entries still in _waitings are
                # in flight (their requester may yet wait) — keep those.
                for stale in list(self._errors):
                    if stale != msg_id and stale not in self._waitings:
                        del self._errors[stale]
                        if len(self._errors) <= _MAX_RETAINED_ERRORS:
                            break
        if count:
            self.notify(msg_id)

    def reset(self, msg_id: int, num_wait: int) -> None:
        with self._mutex:
            waiter = self._waitings.get(msg_id)
        if waiter is not None:
            waiter.reset(num_wait)
            if num_wait <= 0:
                # Re-armed to zero (empty partition): completion
                # callbacks must still fire or cache blocks strand.
                self._complete_if_done(msg_id, waiter)

    def notify(self, msg_id: int) -> None:
        with self._mutex:
            waiter = self._waitings.get(msg_id)
        if waiter is not None:
            waiter.notify()
            if waiter.done:
                self._complete_if_done(msg_id, waiter)

    def _complete_if_done(self, msg_id: int, waiter: Waiter) -> None:
        if not waiter.done:
            return
        self._retire(msg_id, waiter)

    def _retire(self, msg_id: int, waiter: Optional[Waiter] = None) -> None:
        """The id is done with, completed or abandoned: drop its sink,
        reap its waiter (fire-and-forget async adds would otherwise
        leak one per request) and run any registered completion
        callbacks exactly once."""
        self._sinks.pop(msg_id, None)
        with self._mutex:
            if waiter is None or self._waitings.get(msg_id) is waiter:
                self._waitings.pop(msg_id, None)
            callbacks = self._on_complete.pop(msg_id, None)
        for fn in callbacks or ():
            try:
                fn(msg_id)
            except Exception:  # noqa: BLE001 - a callback must not
                # poison the worker actor's reply loop
                log.error("table %d: completion callback for request "
                          "%d raised", self.table_id, msg_id)
                import traceback
                traceback.print_exc()

    def add_completion(self, msg_id: int,
                       fn: Callable[[int], None]) -> None:
        """Run ``fn(msg_id)`` when the request completes (all shard
        replies in). If it already completed, run immediately — the
        check and the registration share the mutex with the completion
        sweep, so a callback can never be orphaned by a racing reply."""
        run_now = False
        with self._mutex:
            if msg_id in self._waitings:
                self._on_complete.setdefault(msg_id, []).append(fn)
            else:
                run_now = True
        if run_now:
            fn(msg_id)

    # -- client-cache version plumbing (driven by the worker actor) --
    def note_version(self, server_id: int, version: int) -> None:
        """Record a version stamp observed on a reply from a server.
        A version REGRESSION (reply below the shard's latest observed)
        means the server restarted and restored an older snapshot:
        re-anchor the tracker and invalidate every registered cache for
        that shard — entries stamped against the previous generation's
        counter must not serve against the restored one."""
        if self._version_tracker.regressed(server_id, version):
            log.error("table %d: server shard %d version regressed "
                      "(%d -> %d): server generation change, "
                      "invalidating client caches for that shard",
                      self.table_id, server_id,
                      self._version_tracker.latest(server_id), version)
            self._version_tracker.reset(server_id, version)
            self._data_generation += 1
            for cache in self._caches:
                cache.invalidate_server(server_id)
        self._version_tracker.note(server_id, version)

    def note_add_ack(self, server_id: int, version: int) -> None:
        """An Add ack from a server shard: raises this worker's
        read-your-writes floor for that shard (replica-served groups
        below the floor repair to the owner) in addition to the normal
        version observation."""
        if version >= 0:
            floor = self._add_floor.get(server_id, -1)
            if version > floor:
                self._add_floor[server_id] = version
        self.replica_server_alive(server_id)
        self.note_version(server_id, version)

    def add_floor(self, server_id: int) -> int:
        return self._add_floor.get(server_id, -1)

    def _begin_reply(self, server_id: int, version: int,
                     msg_id: int, replica_rows: int = 0) -> None:
        """Reply context for ``process_reply_get`` (single worker-actor
        thread — plain attributes, no lock needed). ``replica_rows``
        is the REPLICA_SLOT count: how many trailing rows of the reply
        were served from a replica store (their versions ride the
        reply's replica descriptor, not the header version slot)."""
        self._reply_server = server_id
        self._reply_version = version
        self._reply_msg_id = msg_id
        self._reply_replica_rows = int(replica_rows)
        self.replica_server_alive(server_id)
        self.note_version(server_id, version)

    def _end_reply(self) -> None:
        self._reply_server = self._reply_version = self._reply_msg_id = -1
        self._reply_replica_rows = 0

    # -- elastic resharding plumbing (runtime/shard_map.py,
    #    docs/SHARDING.md; worker actor thread) --
    def apply_shard_map(self, epoch: int, smap, alive_sids) -> None:
        """Epoch-stamped shard-map broadcast (Control_Shard_Map).
        Default: tables that don't reshard ignore it."""

    def shard_epoch(self) -> int:
        """The shard-map epoch this worker has adopted (-1 = still on
        the frozen creation-time layout). Poll target for
        ``Zoo.reshard_table``."""
        return -1

    def shard_owner_sids(self):
        """Server ids currently owning any of this table's items, or
        None for tables on the frozen layout."""
        return None

    def shard_layout(self):
        """``(bounds, owners)`` lists of the adopted map (None on the
        frozen layout) — the exact-layout poll target for
        ``Zoo.reshard_table``."""
        return None

    def reshard_space(self) -> int:
        """Size of this table's reshardable item space (rows for
        matrix tables, hash buckets for KV), or 0 when the table type
        does not support live resharding."""
        return 0

    def note_shard_moved(self, old_sid: int) -> None:
        """Rows moved OFF ``old_sid`` in an adopted map: a moved row's
        version stamps now come from a DIFFERENT shard counter, which
        is exactly the server-generation change the PR-6
        ``VersionTracker.regressed`` machinery invalidates on — reuse
        that path (drop every cache entry attributed to the old
        owner; entries compared against the new owner's counter would
        be meaningless). Called BEFORE the router swaps maps, so the
        caches' ``server_of`` still attributes the moved rows to the
        old owner and drops exactly them (plus the old owner's
        unmoved rows — conservative, and resharding is rare)."""
        log.info("table %d: rows moved off server shard %d (shard-map "
                 "epoch change) — treating as a generation change, "
                 "invalidating client caches for that shard",
                 self.table_id, old_sid)
        self._data_generation += 1
        for cache in self._caches:
            cache.invalidate_server(old_sid)

    def cache_generation(self) -> int:
        """Current data generation (see ``_data_generation``): derived
        read-side caches compare this against the value they recorded
        at build time and rebuild on any difference."""
        return self._data_generation

    # -- hot-shard replication plumbing (runtime/replica.py) --
    def apply_replica_map(self, epoch: int, rows) -> None:
        """Promoted-row map broadcast (worker actor thread). Default:
        tables that don't participate in replication ignore it."""

    def replica_server_dead(self, server_id: int) -> None:
        """Control_Dead_Peer for a server rank (worker actor thread):
        replica routing must stop striping hot rows to the corpse and
        fall back to owners. Default no-op."""

    def replica_server_alive(self, server_id: int) -> None:
        """A reply from this server landed — re-include it in replica
        routing (rejoin recovery). Default no-op."""

    def replica_reconcile(self, alive_sids) -> None:
        """An epoch-stamped map broadcast carried the controller's
        authoritative live-server view: re-validate the router's dead
        marks against it (a rejoined server resumes serving replicas
        without waiting for organic traffic). Default no-op."""

    def reshard_kind(self) -> int:
        """Initial-layout kind for the controller's planner: 0 =
        contiguous ranges (matrix ``row_offsets``), 1 = modulo hash
        buckets (KV)."""
        return 0

    def _stage_repair(self, server_id: int, blobs: List[Blob]) -> None:
        """Record a follow-up shard request toward ``server_id`` for
        rows the current reply could not serve validly (replica miss /
        stale floor). Called from ``process_reply_get``; the worker
        actor drains the staged repairs and transfers the reply's
        notify onto them, so the request's waiter completes only when
        the repaired rows landed too."""
        self._pending_repairs.append((int(server_id), list(blobs)))

    def take_repairs(self) -> List:
        repairs, self._pending_repairs = self._pending_repairs, []
        return repairs

    def extend_request(self, msg_id: int, extra: int) -> None:
        """Raise a request's expected reply count by ``extra`` (repair
        fan-out to several owners replaces ONE reply's notify)."""
        if extra <= 0:
            return
        with self._mutex:
            waiter = self._waitings.get(msg_id)
        if waiter is not None:
            waiter.add_waits(extra)

    # -- virtuals (ref: table_interface.h:44-51) --
    def partition(self, blobs: List[Blob],
                  msg_type: MsgType) -> Dict[int, List[Blob]]:
        """Split a request's blobs into {server_id: [blobs]}."""
        raise NotImplementedError

    def process_reply_get(self, reply_blobs: List[Blob]) -> None:
        raise NotImplementedError

    @property
    def zoo(self):
        return self._zoo


class ServerTable:
    """Storage-side shard; lives on every server rank. Serializable
    (ref: table_interface.h:61-75)."""

    #: Both-apply exemption flag for the dual-write window (set by
    #: the server actor around the deliberate handoff-copy apply;
    #: tables without elastic support never read it).
    _in_both_apply = False

    #: Whether this table's process_add/process_get dispatch jitted
    #: device programs — those must serialize under the server actor's
    #: process-wide table lock (two in-process server threads
    #: interleaving multi-device XLA executions deadlock the CPU
    #: runtime). Host-only tables (KV) opt out so two LocalFabric
    #: servers doing control-plane work don't serialize on each other.
    needs_device_lock = True

    def __init__(self, zoo=None):
        self._zoo = zoo if zoo is not None else current_zoo()
        self.table_id: int = self._zoo.register_server_table(self)
        #: Monotonically increasing shard version: bumped by the server
        #: actor once per successfully applied Add and stamped on every
        #: reply (client-cache staleness tracking).
        self.version = 0
        #: Guards this shard's (state, version) PAIR for host-only
        #: tables (``needs_device_lock=False``): their adds bypass the
        #: process-wide device lock (by design — KV control plane must
        #: not serialize two in-process servers), so without a
        #: per-table lock the async snapshotter could capture state N
        #: paired with version N+1, and a restore would then claim a
        #: version whose add it lacks — defeating the client caches'
        #: regression-based generation guard. Device-backed tables
        #: never contend on it (their adds hold the device lock the
        #: snapshotter also takes); acquired per-instance, so sibling
        #: shards stay concurrent.
        self._state_lock = named_lock(
            f"server_table[{next(_state_lock_serial)}].state")

    def process_add(self, blobs: List[Blob]) -> None:
        raise NotImplementedError

    def process_get(self, blobs: List[Blob]) -> List[Blob]:
        raise NotImplementedError

    # -- server-side request fusion hooks (runtime/fusion.py,
    #    docs/SERVER_ENGINE.md; server actor thread only, always
    #    entered under Server._lock_for) --
    def fuse_eligible(self, blobs: List[Blob], is_get: bool) -> bool:
        """May this request join a fused (table, op) group? Default
        NO: a table type must opt in per request — sentinel keys,
        device-key blobs, wire-codec frames, elastic windows and
        replica-routed rows all carry per-request semantics the fused
        paths do not reproduce. Called on the server actor thread at
        batch-classification time; nothing else touches table state
        between the check and the fused execution."""
        return False

    def process_fused_get(self, requests: List[List[Blob]]
                          ) -> List[List[Blob]]:
        """Serve N eligible Gets as one unit — ONE device program
        where the table type supports it. Returns one reply blob-list
        per request, in request order; MUST be bit-identical to
        serving each request through ``process_get`` serially.
        Default: the serial loop (host-only tables lose nothing)."""
        return [self.process_get(blobs) for blobs in requests]

    def process_fused_add(self, requests: List[List[Blob]]) -> None:
        """Apply N eligible Adds as one unit — sum-equivalent (left
        fold in request order) to serial ``process_add``. The caller
        bumps ``version`` by len(requests) and stamps every reply
        with the post-batch version. Contract: either parse/validate
        every request BEFORE the first state mutation (so a plain
        exception means nothing applied and the caller replays the
        whole group serially), or raise ``fusion.PartialFuseError``
        naming the applied prefix — the caller then replays only the
        tail. The default serial loop keeps that accounting exact."""
        for i, blobs in enumerate(requests):
            try:
                self.process_add(blobs)
            except Exception as exc:  # noqa: BLE001
                from ..runtime.fusion import PartialFuseError
                raise PartialFuseError(i, exc) from exc

    # -- elastic resharding hooks (runtime/shard_map.py,
    #    docs/SHARDING.md; server actor thread only). Default: table
    #    types that do not support live migration refuse/ignore —
    #    the controller rolls the move back on a refusal. --
    def shard_begin_out(self, desc) -> bool:
        """Controller's Request_ShardBegin: start streaming
        ``[desc.lo, desc.hi)`` to the destination. False = this table
        type cannot migrate live (sparse dirty bitmaps, stateful
        updaters, element-range arrays) — the server NACKs and the
        controller abandons the move."""
        return False

    def shard_pump(self):
        """One streaming step: ``(outbound messages, more)``. The
        server actor re-enqueues a pump message to itself while
        ``more`` — serving traffic interleaves between chunks."""
        return [], False

    def shard_import_chunk(self, msg):
        """Destination side of Request_ShardData; returns outbound
        messages (retransmit request / Control_Shard_Done)."""
        return []

    def shard_ack(self, msg):
        """Source side of Request_ShardAck (retransmit request);
        returns the re-sent chunks."""
        return []

    def shard_abort(self, epoch: int):
        """Controller rollback order: source resumes ownership (drops
        the forwarding window if the final chunk already left),
        destination drops partial state. The map never moved, so the
        pre-migration epoch is the consistent state. Returns outbound
        messages — the source synthesizes retryable error replies for
        requests it FORWARDED into the now-dead window (the requester
        tracked them against THIS rank, so the destination's death
        sweep can never fail them; without these replies a waiter
        blocks forever)."""
        return []

    def shard_announce(self):
        """Traffic-driven resend hook (destination): re-announce a
        pending Control_Shard_Done / retransmit request whose last
        copy may have been lost. Returns outbound messages."""
        return []

    def apply_shard_map_server(self, epoch: int, smap, alive_sids):
        """Epoch-stamped map broadcast on the server side: a commit
        clears migration state (the source KEEPS its forwarding
        entries — stale routers may still send moved rows here),
        prunes replica entries for moved rows. Returns outbound
        messages. Default: ignore."""
        return []

    def shard_forward_get(self, msg):
        """Dual-read window routing for an inbound Get: None = serve
        locally as usual; else a list of outbound messages that fully
        handle the request (the reply reaches the requester from the
        destination, carrying this shard's piggybacked rows as a
        replica group — docs/SHARDING.md)."""
        return None

    def shard_forward_add(self, msg):
        """Dual-write routing for an inbound Add: None = apply locally
        as usual; else ``(local_apply_blobs_or_None, outbound)`` — the
        moved rows' sub-add forwards to the destination (which acks
        the requester), any still-owned remainder applies HERE with no
        ack of its own (the destination's single ack completes the
        waiter; per-request FIFO toward the destination orders the
        forwarded add before any later forwarded read)."""
        return None

    def process_forward_get(self, blobs):
        """Destination side of Request_FwdGet: serve the forwarded
        rows, append the piggybacked source rows, and return
        ``(reply_blobs, n_replica_rows, src_rank, src_version)`` — the
        server actor builds a Reply_Get IMPERSONATING the source rank
        (so the requester's in-flight accounting matches the shard it
        sent) with this shard's rows as the replica group."""
        raise NotImplementedError

    # -- hot-shard replication hooks (runtime/replica.py; server actor
    #    thread only — no locking on the replica state) --
    def apply_replica_map(self, epoch: int, rows) -> List[Message]:
        """Promoted-row map broadcast: owners start/stop the
        write-through fan-out for their rows, holders prune demoted
        entries. Returns outbound messages for the server actor to
        send (the initial value push for newly promoted own rows).
        Default: table types that don't replicate ignore it."""
        return []

    def apply_replica_sync(self, blobs: List[Blob]) -> None:
        """An owner's Request_ReplicaSync refresh push; default drop
        (a non-replicating table should never receive one)."""

    def replica_redirty(self, blobs: List[Blob]) -> None:
        """The communicator's failure echo for a sync push that never
        left this rank: the owner must re-dirty the chunk's rows so the
        next flush re-pushes them (the version watermark is only sound
        when no dirtied row is silently lost). Default no-op."""

    def replica_flush_if_due(self) -> List[Message]:
        """Cadence hook, called by the server actor after each served
        request: returns the due outbound messages — write-through
        refreshes of dirty promoted rows toward the holders and/or the
        hot-row window report toward the controller. Default no-op."""
        return []

    def take_reply_replica_rows(self) -> int:
        """How many trailing rows of the reply just built by
        ``process_get`` were replica-served (the server actor stamps
        REPLICA_SLOT with it); self-clearing. Default 0."""
        return 0

    def store(self, stream) -> None:
        raise NotImplementedError

    def load(self, stream) -> None:
        raise NotImplementedError

    # -- async snapshot split (runtime/snapshot.py) --
    #
    # The periodic snapshotter wants a CONSISTENT cut without holding
    # the server's table lock for the whole serialize+write:
    # ``snapshot_state`` runs under the lock and must be cheap (capture
    # a reference to the immutable device array / copy a small dict);
    # ``write_snapshot`` runs OFF the lock, possibly much later, and
    # must produce bytes that ``load`` accepts (i.e. store()-format).

    def snapshot_state(self):
        """Capture this shard's state for snapshotting. Fallback:
        serialize eagerly (correct for any table, but does the full
        store under the caller's lock — subclasses override with an
        O(1) capture)."""
        buf = io.BytesIO()
        self.store(buf)
        return buf.getvalue()

    def write_snapshot(self, state, stream) -> None:
        """Serialize a ``snapshot_state`` capture into ``stream`` in
        ``store``-compatible format."""
        stream.write(state)

    def snapshot_meta(self):
        """JSON-able sidecar recorded in the snapshot MANIFEST entry
        (runtime/snapshot.py) alongside the payload: reshardable
        tables record their adopted shard-map epoch + owned intervals
        here, so a rejoining server restores into the RIGHT map
        instead of its frozen creation-time layout. None (default) =
        no sidecar, legacy restore path."""
        return None

    def load_with_meta(self, stream, meta) -> None:
        """Restore from a snapshot payload plus its manifest sidecar
        (``snapshot_meta`` round trip). Default: sidecar-less legacy
        ``load``."""
        self.load(stream)

    @property
    def zoo(self):
        return self._zoo

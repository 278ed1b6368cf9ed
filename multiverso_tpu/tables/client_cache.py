"""Worker-side versioned parameter cache (the client cache).

Extension over the reference: Multiverso's workers re-issue a full
server roundtrip for every ``Get`` even when the rows were fetched one
step earlier and nothing changed (ref: src/worker.cpp:30-51 always
partitions and sends). Every such roundtrip pays the transport's
per-request cost (not measured on the current machine), and the
wordembedding workload's power-law row popularity (SparCML's observation, PAPERS.md) means a small hot-row
cache absorbs most of that traffic.

Versioning model
----------------
* every ``ServerTable`` shard keeps a monotonically increasing
  ``version``, bumped once per successfully applied Add (the server
  actor owns the bump, runtime/server.py);
* Get/Add/BatchAdd replies carry the serving shard's version
  (``core.message.VERSION_SLOT`` on per-message replies, a descriptor
  column on batch acks);
* each worker table tracks, per server shard, the LATEST version it has
  observed (``VersionTracker``);
* a cache entry fetched at version ``v`` may serve a Get only while
  ``v >= latest_observed - max_get_staleness``.

``-max_get_staleness=0`` (the default) disables the cache outright —
every Get takes today's wire path, byte-identical. BSP sync mode
force-disables it regardless of the flag: a locally served Get is a Get
the sync server's vector clocks never count, which would break the
every-i-th-Get-sees-every-i-th-Add contract.

Read-your-writes
----------------
The staleness bound alone would let a worker read back a PRE-write value
of a row it just pushed a delta to. So issuing an Add immediately
*blocks* the touched slots (they neither serve nor accept stores), and
the Add's ack — which carries the post-add version — resolves the block
and raises the slots' floor to the latest observed version: only values
fetched at-or-after the worker's own write can serve again. This is the
piggybacked self-invalidation the Add-ack version stamp exists for.

Staleness is measured against the latest version THIS worker has
observed, not the server's true head: a worker that never hears from the
server (no Gets, no Add acks) cannot age its entries. The wire-path
population of the cache (every real Get refreshes entries AND the
tracker) keeps the two converged in any workload that misses
occasionally; workloads needing a hard recency guarantee set the bound
to 0 for the critical read or call the table's uncached device/sync
paths.
"""

from __future__ import annotations

import itertools
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..util.configure import (define_int, get_flag,
                              register_tunable_hook)
from ..util.dashboard import count
from ..util.lock_witness import named_lock

# Per-INSTANCE witness names: the lock-order graph is keyed by name,
# so two tables' caches sharing one name would hide real cross-table
# cycles and manufacture false ones (same reason mt_queue/waiter/tcp
# use serial/rank names).
_lock_serial = itertools.count()

define_int("max_get_staleness", 0,
           "client-side parameter-cache staleness bound, in server-shard "
           "versions (one version = one applied Add): a cached Get may "
           "serve while its fetch version is within this many versions "
           "of the latest version observed from the owning shard. "
           "0 (default) disables the cache; BSP sync mode force-disables "
           "it (a locally served Get would bypass the vector clocks)")
define_int("client_cache_rows", 65536,
           "row capacity of the matrix client cache (oldest entries "
           "evicted past this; bounds worker memory at rows * num_col * "
           "itemsize)")

#: Dashboard counter names (util/dashboard.py `count`).
HIT = "CLIENT_CACHE_HIT"
MISS = "CLIENT_CACHE_MISS"
JOIN = "CLIENT_CACHE_JOIN"
PREFETCH = "CLIENT_CACHE_PREFETCH"
DIRECT = "GET_REPLY_ROWS_DIRECT"
PLACED = "GET_REPLY_ROWS_PLACED"


def staleness_bound() -> int:
    """The active staleness bound; 0 = cache disabled. Read at table
    construction time (matching ``-sparse_compress`` and friends)."""
    if bool(get_flag("sync", False)):
        return 0
    try:
        bound = int(get_flag("max_get_staleness", 0))
    except (TypeError, ValueError):
        return 0
    return max(bound, 0)


def cache_enabled() -> bool:
    return staleness_bound() > 0


def run_start(keys: np.ndarray, req: np.ndarray) -> int:
    """Where ``keys`` lies in ``req`` as ONE run that holds every
    position of its ids, or -1. Two shapes qualify: the shard is the
    whole request in its order (any ``req``, repeats included — one
    server's reply to ``partition``'s ``keys[mask]``), or ``req`` is
    non-decreasing and the shard is a maximal slice of it (one server's
    bucket of a sorted request). Maximal matters: ``[5, 7]`` is a slice
    of ``[5, 7, 7]`` (a de-duplicated partial hit) that would leave the
    third position unfilled."""
    k, n = keys.size, req.size
    if k == n:
        return 0 if np.array_equal(keys, req) else -1
    if k > n:
        return -1
    # Meaningful only for a sorted req; that is the last (O(n)) test,
    # made only once the O(k) ones have passed.
    a = int(np.searchsorted(req, keys[0], side="left"))
    b = a + k
    if b > n or not np.array_equal(keys, req[a:b]):
        return -1
    if b < n and req[b] == keys[-1]:
        return -1
    if not bool((req[1:] >= req[:-1]).all()):
        return -1
    return a


def place_run(start: int, pieces, out) -> None:
    """The direct form: a shard that ``run_start`` found at ``start`` is
    copied straight into ``out``, one ``(first_row, rows)`` piece after
    the other as ``pieces`` hands them over (the whole shard is one
    piece at row 0; ``Blob.host_row_pieces`` gives a large device reply
    as several, each while the next is still on its way)."""
    count(DIRECT)
    for first, rows in pieces:
        out[start + first:start + first + len(rows)] = rows


def place_rows(keys: np.ndarray, values, req: np.ndarray, out) -> None:
    """Subset placement: every position of ``req`` whose row id appears
    in ``keys`` receives that id's row of ``values``; positions for
    absent ids are left untouched. Shared by the cache's partial-hit
    fill and the table reply path — ``req`` may repeat ids thousands of
    times (power-of-two padded row sets), so per-position Python loops
    are pathological here.

    The cheapest form is chosen from ``keys`` and ``req`` alone. A shard
    that is the request, or a run of a sorted request (``run_start``),
    is one copy of ``values`` into ``out``, read through whatever
    strides ``values`` has (``place_run``, GET_REPLY_ROWS_DIRECT).
    Anything else — subset keys of a partial hit, replica groups, an
    unsorted request over several servers — is sorted, searched,
    gathered and scattered (GET_REPLY_ROWS_PLACED)."""
    if len(keys) == 0 or len(req) == 0:
        return
    start = run_start(keys, req)
    if start >= 0:
        place_run(start, [(0, values)], out)
        return
    count(PLACED)
    sorter = np.argsort(keys, kind="stable")
    sorted_keys = keys[sorter]
    slot = np.searchsorted(sorted_keys, req)
    slot = np.minimum(slot, sorted_keys.size - 1)
    hit = sorted_keys[slot] == req
    out[hit] = values[sorter[slot[hit]]]


class VersionTracker:
    """Latest table-shard version observed per server id (-1 before any
    observation). Fed by the worker actor from reply version stamps."""

    def __init__(self) -> None:
        self._lock = named_lock(
            f"client_cache.VersionTracker[{next(_lock_serial)}]")
        self._latest: Dict[int, int] = {}  # guarded_by: _lock

    def note(self, server_id: int, version: int) -> None:
        if version < 0:
            return
        with self._lock:
            if version > self._latest.get(server_id, -1):
                self._latest[server_id] = version

    def latest(self, server_id: int) -> int:
        # Under the lock like every other reader: a torn read is not
        # possible for one dict probe, but the freshness math in
        # RowCache._fresh must not see a version OLDER than one a
        # concurrent note() already published to another field.
        with self._lock:
            return self._latest.get(server_id, -1)

    def regressed(self, server_id: int, version: int) -> bool:
        """True when a stamped reply carries a LOWER version than the
        latest observed from that shard. Versions per shard only ever
        grow within one server generation (monotonic counter, FIFO
        reply stream), so a regression means the server RESTARTED and
        reset/restored its counter — the generation-change signal the
        caches invalidate on (docs/CLIENT_CACHE.md)."""
        return 0 <= version < self.latest(server_id)

    def reset(self, server_id: int, version: int) -> None:
        """Re-anchor a shard's latest-observed version downward after a
        server generation change (``note`` only moves it up)."""
        with self._lock:
            self._latest[server_id] = version

    def known_servers(self) -> List[int]:
        with self._lock:
            return list(self._latest)


class RowCache:
    """Row-granular cache for dense matrix worker tables.

    Every public method is thread-safe: lookups/invalidation run on the
    requester's thread, stores and add-resolution on the worker actor's
    reply thread.
    """

    def __init__(self, bound: int, server_of: Callable, num_servers: int,
                 tracker: VersionTracker,
                 capacity: Optional[int] = None,
                 server_of_rises: Optional[Callable[[], bool]] = None
                 ) -> None:
        self._bound = int(bound)
        self._server_of = server_of  # vectorized row ids -> server ids
        # True while server_of never falls as the row id grows (the
        # frozen division rule; a live shard map need not). None: no
        # such promise, ever.
        self._server_of_rises = server_of_rises
        self._num_servers = int(num_servers)
        self._tracker = tracker
        self._capacity = int(capacity if capacity is not None  # guarded_by: _lock
                             else get_flag("client_cache_rows"))
        self._lock = named_lock(
            f"client_cache.RowCache[{next(_lock_serial)}]")
        # _bound stays unannotated by choice: the hot read path probes
        # it lock-free (one int, GIL-atomic) and _retune_bound rebinds
        # it under the lock — a stale read is one Get at the old bound.
        self._rows: Dict[int, Tuple[int, np.ndarray]] = {}  # guarded_by: _lock
        # _floor: per-row min fetch version; _floor_all: per-server
        # floor; _pending: row -> outstanding own-adds; _pending_all:
        # whole-table own-adds.
        self._floor: Dict[int, int] = {}      # guarded_by: _lock
        self._floor_all: Dict[int, int] = {}  # guarded_by: _lock
        self._pending: Dict[int, int] = {}    # guarded_by: _lock
        self._pending_all = 0                 # guarded_by: _lock
        # hits/misses: whole-Get accounting (full-local vs needed the
        # wire); rows_hit/rows_missed: row-granular across both.
        self.hits = 0        # guarded_by: _lock
        self.misses = 0      # guarded_by: _lock
        self.rows_hit = 0    # guarded_by: _lock
        self.rows_missed = 0  # guarded_by: _lock
        #: test hook: fn(row, entry_version, latest_observed, bound),
        #: called under the cache lock for every row actually SERVED.
        self.on_hit = None
        # Live retuning (docs/AUTOTUNE.md): the bound and capacity
        # were cached above at construction, so a Control_Config
        # broadcast must land through these hooks — bound methods held
        # weakly by the registry, so a dropped table unregisters
        # itself. Registered LAST: a broadcast may fire them from the
        # recv thread the instant they register, and they touch the
        # lock and row dicts above.
        register_tunable_hook("max_get_staleness", self._retune_bound)
        register_tunable_hook("client_cache_rows",
                              self._retune_capacity)

    # -- freshness core (caller holds the lock) --
    def _fresh(self, row: int, sid: int,
               record: bool = True) -> Optional[np.ndarray]:
        if self._pending_all or self._pending.get(row):
            return None
        ent = self._rows.get(row)
        if ent is None:
            return None
        version, value = ent
        if version < max(self._floor.get(row, -1),
                         self._floor_all.get(sid, -1)):
            return None
        latest = self._tracker.latest(sid)
        if latest - version > self._bound:
            return None
        if record and self.on_hit is not None:
            self.on_hit(row, version, latest, self._bound)
        return value

    # -- read side --
    def missing_of(self, row_ids: np.ndarray) -> np.ndarray:
        """The sorted unique requested rows that would NOT hit (no
        copies, no counter bumps) — the prefetch planning check; an
        empty result means full coverage."""
        uniq = np.unique(row_ids)
        if self._bound <= 0:  # inactive: everything misses
            return uniq.astype(np.int32)
        sids = self._server_of(uniq)
        with self._lock:
            return np.asarray(
                [int(r) for r, s in zip(uniq, sids)
                 if self._fresh(int(r), int(s), record=False) is None],
                dtype=np.int32)

    def fetch_into(self, row_ids: np.ndarray, out: np.ndarray,
                   count_stats: bool = True) -> np.ndarray:
        """Partial-hit fill: copy every fresh row into its requested
        positions (duplicates welcome) and return the sorted unique
        MISSING rows — empty = full local hit. The caller fetches only
        the missing set over the wire; its reply placement fills the
        remaining positions (reply keys are a subset of the request's,
        which the placement path already supports). The join-completion
        re-serve passes ``count_stats=False`` so one logical Get
        contributes exactly one hit-or-miss."""
        uniq = np.unique(row_ids)
        if self._bound <= 0:
            # Inactive (live-deactivated mid-flight): everything
            # misses, nothing is counted — the old no-cache path.
            return uniq.astype(np.int32)
        sids = self._server_of(uniq)
        fresh_vals: List[np.ndarray] = []
        fresh_keys: List[int] = []
        missing: List[int] = []
        with self._lock:
            for r, s in zip(uniq, sids):
                v = self._fresh(int(r), int(s),
                                record=count_stats)
                if v is None:
                    missing.append(int(r))
                else:
                    fresh_keys.append(int(r))
                    fresh_vals.append(v)
            if count_stats:
                self.rows_hit += len(fresh_keys)
                self.rows_missed += len(missing)
                if missing:
                    self.misses += 1
                else:
                    self.hits += 1
        if count_stats:
            count(MISS if missing else HIT)
        if fresh_keys:
            place_rows(np.asarray(fresh_keys, dtype=np.int64),
                       np.stack(fresh_vals), row_ids, out)
        return np.asarray(missing, dtype=np.int32)

    # -- write side (worker actor reply thread) --
    def store(self, row_ids: np.ndarray, values: np.ndarray,
              version: int, server_id: int) -> None:
        """Record one reply shard's rows at the version it was served.
        Slots blocked by an outstanding own-add, or whose floor exceeds
        the fetch version, are skipped — never silently resurrected."""
        if version < 0:  # unstamped legacy peer
            return
        if self._bound <= 0:  # inactive: store nothing (a reply
            # racing a live deactivation must not leave entries)
            return
        with self._lock:
            if self._pending_all:
                return
            if version < self._floor_all.get(int(server_id), -1):
                return
            for i, r in enumerate(row_ids):
                r = int(r)
                if self._pending.get(r):
                    continue
                floor = self._floor.get(r, -1)
                if version < floor:
                    continue
                # Replies per server connection arrive version-ordered
                # (FIFO socket, monotonic server counter), so a passed
                # floor never needs re-checking.
                self._floor.pop(r, None)
                self._rows[r] = (version, np.array(values[i], copy=True))
            while len(self._rows) > self._capacity:
                self._rows.pop(next(iter(self._rows)))

    # -- own-add self-invalidation --
    def _fence_servers(self, row_ids, ends) -> List[int]:
        """The servers an inactive cache's Add fences: the owners of
        ``row_ids`` or a superset of them. One server is the answer
        whatever the ids; under a ``server_of`` that rises with the
        row id, every server from the smallest id's to the largest's
        (one call on the two ends); otherwise (a live shard map, whose
        owners may interleave, or no promise given) every server, as
        for a whole-table Add."""
        everyone = list(range(self._num_servers))
        if row_ids is None:
            return everyone
        if ends is None and np.size(row_ids) == 0:
            return []
        if self._num_servers == 1:
            return [0]
        if self._server_of_rises is None:
            return everyone
        if ends is None:
            ids = np.asarray(row_ids)
            ends = (ids.min(), ids.max())
        first, last = self._server_of(np.asarray(ends, dtype=np.int64))
        # Asked AFTER the call: a map adopted on the worker's thread
        # meanwhile (one is never dropped again) voids what the two
        # ends said.
        if not self._server_of_rises():
            return everyone
        return list(range(int(first), int(last) + 1))

    def begin_add(self, row_ids: Optional[np.ndarray] = None,
                  ends: Optional[Tuple[int, int]] = None):
        """Block the slots an own Add is about to dirty (None = whole
        table). Returns a token for ``finish_add``. ``ends`` is the
        ids' smallest and largest value where the caller has them
        (``MatrixWorker._check_row_ids``); without it they are taken
        here.

        While INACTIVE there are no entries to block, but the ack must
        still FENCE the owning shards' floors: a Get reply served
        before this add could land after a live activation, store the
        pre-add value, and serve it within the widened bound — a
        read-your-writes violation across the activation edge. The
        fence token holds server ids and costs O(servers): it is named
        from the ids' two ends and never reads the ids (no copy, no
        sort, no ``server_of`` over the rows; ``_fence_servers``), so
        it may name a server that owns none of them. That is sound: a
        fence only raises ``_floor_all[sid]`` to the version seen at
        the ack, floors only ever make serving stricter (a fenced
        server's older entries are refetched, never served), and while
        the cache is inactive nothing is served or stored at all."""
        if self._bound <= 0:
            return ("fence", self._fence_servers(row_ids, ends))
        if row_ids is None:
            with self._lock:
                self._pending_all += 1
            return (None, None)
        rows = np.unique(np.asarray(row_ids,
                                    dtype=np.int64).reshape(-1))
        sids = self._server_of(rows)
        rows = [int(r) for r in rows]
        with self._lock:
            for r in rows:
                self._pending[r] = self._pending.get(r, 0) + 1
                self._rows.pop(r, None)
        return (rows, [int(s) for s in sids])

    def finish_add(self, token) -> None:
        """Resolve a ``begin_add`` once its ack arrived: unblock the
        slots and raise their floor to the latest observed version (the
        ack was noted before this runs), so only values fetched at-or-
        after the write serve again."""
        if token is None:
            return
        if token[0] == "fence":
            # Inactive-mode ack fence: raise the per-shard floor to
            # the latest version observed at ack (the ack was noted
            # before this runs). _fresh and store() both honor
            # _floor_all, so a pre-add reply landing after a live
            # activation can neither store nor serve.
            with self._lock:
                for sid in token[1]:
                    self._floor_all[sid] = max(
                        self._floor_all.get(sid, -1),
                        self._tracker.latest(sid))
            return
        rows, sids = token
        with self._lock:
            if rows is None:
                self._pending_all -= 1
                if self._pending_all == 0:
                    self._rows.clear()
                    for sid in range(self._num_servers):
                        self._floor_all[sid] = max(
                            self._floor_all.get(sid, -1),
                            self._tracker.latest(sid))
                return
            for r, s in zip(rows, sids):
                remaining = self._pending.get(r, 0) - 1
                if remaining > 0:
                    self._pending[r] = remaining
                else:
                    self._pending.pop(r, None)
                self._floor[r] = max(self._floor.get(r, -1),
                                     self._tracker.latest(int(s)))

    @property
    def bound(self) -> int:
        """The LIVE staleness bound (serving tier response metadata,
        docs/SERVING.md; retunable via the dynamic-flag layer)."""
        return self._bound

    @property
    def active(self) -> bool:
        """False while the bound is 0: the cache object exists (so a
        live config broadcast can activate it) but serves nothing and
        stores nothing — the table's ``_live_cache`` treats it exactly
        like the old no-cache construction path."""
        return self._bound > 0

    # -- live retuning (dynamic-flag apply hooks, docs/AUTOTUNE.md) --
    def _retune_bound(self, value) -> None:
        """``-max_get_staleness`` landed live. Widening/narrowing just
        rebinds the freshness check; a FLIP (activation 0 -> n or
        deactivation -> 0) also drops every entry — the cache must
        start from scratch, never from state recorded across the
        edge. Floors are KEPT on a flip: they only ever make serving
        stricter, and the inactive-mode ack fences recorded in
        ``_floor_all`` are exactly what protects read-your-writes
        against a pre-activation reply landing late. BSP sync mode
        keeps its force-disable (a locally served Get would bypass
        the vector clocks)."""
        if bool(get_flag("sync", False)):
            value = 0
        new = max(int(value), 0)
        with self._lock:
            flipped = (new > 0) != (self._bound > 0)
            self._bound = new
            if flipped:
                self._rows.clear()

    def _retune_capacity(self, value) -> None:
        with self._lock:
            self._capacity = max(int(value), 0)
            while len(self._rows) > self._capacity:
                self._rows.pop(next(iter(self._rows)))

    def versions_of(self, row_ids) -> Dict[int, int]:
        """Fetch version per requested row currently present (rows
        absent — evicted, never fetched, or blocked by a pending
        own-add — are simply omitted). Serving-tier metadata read: the
        frontend reports the minimum served version and the per-row
        staleness against the tracker on every response."""
        out: Dict[int, int] = {}
        with self._lock:
            for r in np.unique(np.asarray(row_ids).reshape(-1)):
                ent = self._rows.get(int(r))
                if ent is not None:
                    out[int(r)] = ent[0]
        return out

    def invalidate_server(self, server_id: int) -> None:
        """Drop every row owned by a shard whose server changed
        generation (restart + snapshot restore): entries and floors
        recorded against the old generation's version counter are
        meaningless against the restored one."""
        sid = int(server_id)
        with self._lock:
            touched = set(self._rows) | set(self._floor)
            if touched:
                rows = np.asarray(sorted(touched), dtype=np.int64)
                sids = self._server_of(rows)
                for r, s in zip(rows, sids):
                    if int(s) == sid:
                        self._rows.pop(int(r), None)
                        self._floor.pop(int(r), None)
            self._floor_all.pop(sid, None)

    @property
    def stats(self) -> Dict[str, float]:
        # One consistent cut under the lock: the counters move together
        # in fetch_into, and a rate computed from a half-updated pair
        # can exceed 1.0.
        with self._lock:
            hits, misses = self.hits, self.misses
            rows_hit, rows_missed = self.rows_hit, self.rows_missed
            nrows = len(self._rows)
        total = hits + misses
        rows_total = rows_hit + rows_missed
        return {"hits": hits, "misses": misses,
                "hit_rate": hits / total if total else 0.0,
                "rows_hit": rows_hit,
                "rows_missed": rows_missed,
                "row_hit_rate": rows_hit / rows_total
                if rows_total else 0.0,
                "rows": nrows}


class BlobCache:
    """Whole-shard cache for Array worker tables: one entry per server
    shard; a hit requires EVERY shard fresh (array Gets are whole-table)."""

    def __init__(self, bound: int, num_servers: int,
                 tracker: VersionTracker) -> None:
        self._bound = int(bound)
        self._num_servers = int(num_servers)
        self._tracker = tracker
        self._lock = named_lock(
            f"client_cache.BlobCache[{next(_lock_serial)}]")
        self._shards: Dict[int, Tuple[int, np.ndarray]] = {}  # guarded_by: _lock
        self._floor: Dict[int, int] = {}  # guarded_by: _lock
        self._pending = 0  # guarded_by: _lock
        self.hits = 0  # guarded_by: _lock
        self.misses = 0  # guarded_by: _lock
        self.on_hit = None  # fn(server_id, entry_version, latest, bound)

    def fresh_all(self) -> bool:
        """Counter-free freshness probe (the prefetch planning check —
        hit/miss accounting must reflect Get serving only)."""
        with self._lock:
            if self._pending:
                return False
            for sid in range(self._num_servers):
                ent = self._shards.get(sid)
                if ent is None:
                    return False
                version, _ = ent
                if version < self._floor.get(sid, -1) \
                        or self._tracker.latest(sid) - version \
                        > self._bound:
                    return False
        return True

    def fetch_all(self) -> Optional[Dict[int, np.ndarray]]:
        with self._lock:
            if self._pending:
                out = None
            else:
                out = {}
                for sid in range(self._num_servers):
                    ent = self._shards.get(sid)
                    if ent is None:
                        out = None
                        break
                    version, value = ent
                    if version < self._floor.get(sid, -1):
                        out = None
                        break
                    latest = self._tracker.latest(sid)
                    if latest - version > self._bound:
                        out = None
                        break
                    if self.on_hit is not None:
                        self.on_hit(sid, version, latest, self._bound)
                    out[sid] = value
            if out is None:
                self.misses += 1
            else:
                self.hits += 1
        count(HIT if out is not None else MISS)
        return out

    def store(self, server_id: int, values: np.ndarray,
              version: int) -> None:
        if version < 0:
            return
        with self._lock:
            if self._pending:
                return
            if version < self._floor.get(int(server_id), -1):
                return
            self._floor.pop(int(server_id), None)
            self._shards[int(server_id)] = (version,
                                            np.array(values, copy=True))

    def begin_add(self) -> None:
        with self._lock:
            self._pending += 1
            self._shards.clear()

    def finish_add(self) -> None:
        with self._lock:
            self._pending -= 1
            if self._pending == 0:
                for sid in range(self._num_servers):
                    self._floor[sid] = max(self._floor.get(sid, -1),
                                           self._tracker.latest(sid))

    def invalidate_server(self, server_id: int) -> None:
        """Server generation change: the shard's entry and floor are
        stamped against a counter that no longer exists."""
        with self._lock:
            self._shards.pop(int(server_id), None)
            self._floor.pop(int(server_id), None)


class SnapshotCache:
    """Request-granular snapshot cache for KV worker tables: keyed by
    the exact requested key bytes; an entry records the version of every
    server shard that contributed."""

    def __init__(self, bound: int, tracker: VersionTracker,
                 capacity: int = 256) -> None:
        self._bound = int(bound)
        self._tracker = tracker
        self._capacity = int(capacity)
        self._lock = named_lock(
            f"client_cache.SnapshotCache[{next(_lock_serial)}]")
        self._entries: Dict[bytes, Tuple[Dict[int, int], dict]] = {}  # guarded_by: _lock
        self._floor: Dict[int, int] = {}  # guarded_by: _lock
        self._pending = 0  # guarded_by: _lock
        self.hits = 0  # guarded_by: _lock
        self.misses = 0  # guarded_by: _lock

    def fetch(self, key: bytes, server_ids) -> Optional[dict]:
        with self._lock:
            snap = None
            if not self._pending:
                ent = self._entries.get(key)
                if ent is not None:
                    versions, values = ent
                    ok = True
                    for sid in server_ids:
                        sid = int(sid)
                        v = versions.get(sid)
                        if (v is None or v < self._floor.get(sid, -1)
                                or self._tracker.latest(sid) - v
                                > self._bound):
                            ok = False
                            break
                    if ok:
                        snap = dict(values)
            if snap is None:
                self.misses += 1
            else:
                self.hits += 1
        count(HIT if snap is not None else MISS)
        return snap

    def store(self, key: bytes, versions: Dict[int, int],
              values: dict) -> None:
        with self._lock:
            if self._pending:
                return
            for sid, v in versions.items():
                if v < 0 or v < self._floor.get(int(sid), -1):
                    return
            self._entries[key] = (dict(versions), dict(values))
            while len(self._entries) > self._capacity:
                self._entries.pop(next(iter(self._entries)))

    def begin_add(self) -> None:
        with self._lock:
            self._pending += 1
            self._entries.clear()

    def finish_add(self) -> None:
        with self._lock:
            self._pending -= 1
            if self._pending == 0:
                for sid in self._tracker.known_servers():
                    self._floor[sid] = max(self._floor.get(sid, -1),
                                           self._tracker.latest(sid))

    def invalidate_server(self, server_id: int) -> None:
        """Server generation change: snapshots record multi-shard
        version vectors, so any entry touching the restarted shard is
        stale — clearing all is the simple safe sweep (rare event)."""
        with self._lock:
            self._entries.clear()
            self._floor.pop(int(server_id), None)

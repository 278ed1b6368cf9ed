"""Request batching and hot-response caching for the serving frontend.

Two layers between the HTTP handler threads and the worker table's
scatter-gather read path (docs/SERVING.md fleet section):

**BatchedTableReader** — concurrent HTTP reads landing within a
``-serving_batch_window_ms`` window fold into ONE merged
``read_rows_scatter`` call: one device gather per shard per BATCH
instead of per request (the gather program is jitted per bucket
width, so folding N requests into one id set also folds N program
launches into one). A batch flushes on its window deadline or when
its merged row count reaches ``-serving_batch_max_rows``, whichever
first — a lone request therefore never waits longer than the window.
Failures are row-scoped end to end: a sub-request that died (dead
shard owner, RPC timeout) fails only the batch members whose rows it
carried, as a typed retryable ``UpstreamReadError`` the frontend maps
to ``503 + Retry-After``; every other member serves normally.

**HotRowCache** — rendered per-row response payloads keyed on
``(table, row, served_version)``: the Zipf head of a read workload is
a handful of rows requested thousands of times per second, and while
a row's fetch version is within the staleness bound of the owner's
latest OBSERVED version there is nothing to recompute — not even the
``ndarray -> list`` JSON prep. Freshness rides the existing
``VersionTracker`` machinery (``observed_versions``); a data-
generation change (elastic reshard, server rejoin — events that make
version arithmetic against the old shard counters meaningless) is a
FORCED invalidation via ``WorkerTable.cache_generation``.
"""

from __future__ import annotations

import itertools
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from ..util import log
from ..util.configure import get_flag, register_tunable_hook
from ..util.dashboard import samples
from ..util.lock_witness import named_condition, named_lock

#: Metric names (util/dashboard.py METRIC_NAMES).
BATCH_SIZE = "SERVING_BATCH_SIZE"

_serial = itertools.count()


class UpstreamReadError(RuntimeError):
    """A serving read failed upstream (dead shard owner, timeout,
    table error) for ``rows``. ``retryable`` mirrors the table
    layer's typed-failure split: True maps to 503 + Retry-After (the
    client backs off and re-issues), False to 500."""

    def __init__(self, reason: str, rows: List[int],
                 retryable: bool = True):
        super().__init__(reason)
        self.rows = [int(r) for r in rows]
        self.retryable = bool(retryable)


def request_meta(info: dict, pos: np.ndarray, bound: int) -> dict:
    """Per-request serving metadata from a (possibly merged) scatter
    read's ``info`` arrays at positions ``pos`` — the same fields and
    anchoring rule as ``read_rows_versioned`` (shard latests read
    BEFORE the fetch, so ``max_staleness <= bound`` is race-free
    under concurrent Adds)."""
    versions = info["versions"][pos]
    owners = info["owners"][pos]
    latest_map = info["latest_by_sid"]
    row_latest = np.asarray([latest_map[int(o)] for o in owners],
                            dtype=np.int64)
    # -1 = wire-fresh-but-unstamped/absent: staleness 0 by the
    # read_rows_versioned precedent.
    eff = np.where(versions >= 0, versions, row_latest)
    latest = int(max(row_latest.max(initial=-1), eff.max(initial=-1)))
    served = int(eff.min()) if eff.size else latest
    max_stale = int(np.maximum(row_latest - eff, 0).max(initial=0))
    cached = info["cached"][pos]
    return {"served_version": served, "latest_version": latest,
            "max_staleness": max_stale,
            "staleness_bound": int(bound),
            "cache_hit": bool(cached.all()) if cached.size else False,
            "rows_requested": int(pos.size),
            "rows_cached": int(cached.sum())}


class _PendingRead:
    __slots__ = ("ids", "uniq", "done", "values", "meta", "detail",
                 "error")

    def __init__(self, ids: np.ndarray):
        import threading
        self.ids = ids
        self.uniq = np.unique(ids)
        self.done = threading.Event()
        self.values = None
        self.meta = None
        self.detail = None
        self.error: Optional[Exception] = None


class BatchedTableReader:
    """Per-served-table read batcher. ``bound_of`` injects the active
    staleness bound (the frontend already owns that probe). Flags are
    read at construction, like every other serving knob."""

    def __init__(self, name: str, table,
                 bound_of: Callable[[], int],
                 window_ms: Optional[float] = None,
                 max_rows: Optional[int] = None):
        import threading
        self._name = name
        self._table = table
        self._bound_of = bound_of
        self._window = (float(get_flag("serving_batch_window_ms", 2.0))
                        if window_ms is None else float(window_ms)) \
            / 1e3
        self._max_rows = int(get_flag("serving_batch_max_rows", 1024)
                             if max_rows is None else max_rows)
        serial = next(_serial)
        self._lock = named_lock(f"serving.batch[{serial}]")
        self._cond = named_condition(f"serving.batch[{serial}].arrive",
                                     self._lock)
        self._pending: List[_PendingRead] = []  # guarded_by: _lock
        #: MERGED unique rows of the open batch (the documented
        #: -serving_batch_max_rows unit): counting the per-request sum
        #: would flush early exactly in the high-overlap regime where
        #: folding pays most.
        self._pending_row_set: set = set()  # guarded_by: _lock
        self._open_t = 0.0  # guarded_by: _lock
        self._stopping = False  # guarded_by: _lock
        self.batches = 0      # observability (tests)
        self.requests = 0
        self._thread = None
        if self._window > 0:
            from ..runtime import thread_roles
            self._thread = thread_roles.spawn(
                thread_roles.BACKGROUND, target=self._run,
                name=f"mv-serving-batch-{name}")
        # Live retuning (docs/AUTOTUNE.md): the batcher thread reads
        # _window/_max_rows fresh per batch, so rebinding them is
        # picked up on the next window (a live window change cannot
        # START a batcher constructed with window 0 — the serve-single
        # path stays). Registered LAST: a broadcast may fire the hooks
        # from the recv thread immediately, and they take self._lock.
        register_tunable_hook("serving_batch_window_ms",
                              self._retune_window)
        register_tunable_hook("serving_batch_max_rows",
                              self._retune_max_rows)

    # -- live retuning (dynamic-flag apply hooks) --
    def _retune_window(self, value) -> None:
        with self._lock:
            self._window = max(float(value), 0.0) / 1e3
            self._cond.notify_all()  # an open window re-reads its
            # deadline against the new value immediately

    def _retune_max_rows(self, value) -> None:
        with self._lock:
            self._max_rows = max(int(value), 1)
            self._cond.notify_all()

    # -- the handler-thread API --
    def read(self, ids: np.ndarray):
        """Blocking read for one request's id vector (duplicates and
        order preserved in the returned values). Returns ``(values,
        meta, detail)`` — ``detail`` feeds the hot-response cache.
        Raises ``UpstreamReadError`` for row-scoped failures."""
        if self._thread is None:
            return self._serve_single(ids)
        req = _PendingRead(ids)
        with self._lock:
            if self._stopping:
                raise UpstreamReadError(
                    f"table {self._name!r}: reader stopped", [],
                    retryable=False)
            if not self._pending:
                self._open_t = time.monotonic()
            self._pending.append(req)
            self._pending_row_set.update(int(r) for r in req.uniq)
            self._cond.notify_all()
        # Generous bound: the scatter read itself raises on
        # -rpc_timeout_s; this only guards a dead batcher thread.
        if not req.done.wait(timeout=120.0):
            raise UpstreamReadError(
                f"table {self._name!r}: batched read timed out",
                req.uniq.tolist())
        if req.error is not None:
            raise req.error
        return req.values, req.meta, req.detail

    def _serve_single(self, ids: np.ndarray):
        req = _PendingRead(ids)
        self._execute([req])
        if req.error is not None:
            raise req.error
        return req.values, req.meta, req.detail

    # -- the batcher thread --
    def _run(self) -> None:
        while True:
            with self._lock:
                while not self._pending and not self._stopping:
                    self._cond.wait(timeout=0.5)
                if self._stopping and not self._pending:
                    return
                # Window open: collect until the deadline or the size
                # cap, whichever first (the lone-request bound IS the
                # window). The deadline re-reads _window each pass so
                # a live retune (apply hook) re-times an OPEN window,
                # not just the next one.
                while (not self._stopping
                       and len(self._pending_row_set)
                       < self._max_rows):
                    remaining = self._open_t + self._window \
                        - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(timeout=remaining)
                batch = self._pending
                self._pending = []
                self._pending_row_set = set()
            self._execute(batch)

    def _execute(self, batch: List[_PendingRead]) -> None:
        merged = np.unique(np.concatenate([r.uniq for r in batch])) \
            if len(batch) > 1 else batch[0].uniq
        try:
            values, info = self._table.read_rows_scatter(merged)
        except Exception as exc:  # noqa: BLE001 - a failed merged
            # read must resolve every member (a stranded waiter is
            # the one unacceptable outcome), typed non-retryable.
            log.error("serving: batched read on table %r failed: %s",
                      self._name, exc)
            for req in batch:
                req.error = UpstreamReadError(
                    f"read failed: {exc}", req.uniq.tolist(),
                    retryable=False)
                req.done.set()
            return
        self.batches += 1
        self.requests += len(batch)
        samples(BATCH_SIZE).add(float(len(batch)))
        failed = set(int(r) for r in info["failed"])
        fatal = set(int(r) for r in info.get("failed_fatal", ()))
        bound = self._bound_of()
        uniq = info["rows"]
        for req in batch:
            touched = [int(r) for r in req.uniq if int(r) in failed]
            if touched:
                # Retryability decided per MEMBER: only rows whose own
                # failure was fatal make this response a hard error —
                # an unrelated group's table error in the same merged
                # batch must not demote a transient (503) failure.
                req.error = UpstreamReadError(
                    f"{len(touched)} of {req.uniq.size} requested "
                    f"rows failed upstream", touched,
                    retryable=not any(r in fatal for r in touched))
                req.done.set()
                continue
            pos = np.searchsorted(uniq, req.uniq)
            req.values = values[np.searchsorted(uniq, req.ids)]
            req.meta = request_meta(info, pos, bound)
            req.detail = {
                "rows": req.uniq, "values": values[pos],
                "versions": info["versions"][pos],
                "owners": info["owners"][pos],
                "generation": info["generation"]}
            req.done.set()

    def stop(self) -> None:
        if self._thread is None:
            return
        with self._lock:
            self._stopping = True
            self._cond.notify_all()
        self._thread.join(timeout=5)


class HotRowCache:
    """Rendered per-row response cache (see module docstring). All
    methods thread-safe: lookups on handler threads, stores on
    handler or batcher threads."""

    def __init__(self, table, bound_of: Callable[[], int],
                 capacity: Optional[int] = None):
        self._table = table
        self._bound_of = bound_of
        self._capacity = int(get_flag("serving_hot_rows", 4096)
                             if capacity is None else capacity)
        self._lock = named_lock(f"serving.hot_rows[{next(_serial)}]")
        #: row -> (fetch version, owner sid, data generation,
        #:         rendered value list)
        self._rows: Dict[int, tuple] = {}
        self.hits = 0
        self.misses = 0
        # Live retuning (docs/AUTOTUNE.md): capacity was cached at
        # construction; the hook resizes a running cache.
        register_tunable_hook("serving_hot_rows",
                              self._retune_capacity)

    def _retune_capacity(self, value) -> None:
        with self._lock:
            self._capacity = max(int(value), 0)
            while len(self._rows) > self._capacity:
                self._rows.pop(next(iter(self._rows)))

    def lookup(self, ids: np.ndarray):
        """All-or-nothing: every requested row fresh under the bound
        AND the current generation -> ``(values_lists, meta)`` built
        entirely from cached rendered rows (the worker table is never
        touched); else None."""
        generation = self._table.cache_generation()
        latests = self._table.observed_versions()
        bound = self._bound_of()
        uniq = np.unique(ids)
        found: Dict[int, tuple] = {}
        with self._lock:
            for r in uniq:
                ent = self._rows.get(int(r))
                if ent is None:
                    break
                version, owner, gen, rendered = ent
                latest = latests.get(owner)
                if (gen != generation or latest is None
                        or latest - version > bound):
                    break
                found[int(r)] = ent
            hit = len(found) == uniq.size
            if hit:
                self.hits += 1
                # LRU promote: dict order is eviction order, and a hot
                # row served from the cache never re-stores — without
                # promotion the Zipf head stays oldest and capacity
                # overflows evict exactly the rows the cache exists
                # to hold.
                for r, ent in found.items():
                    self._rows.pop(r, None)
                    self._rows[r] = ent
            else:
                self.misses += 1
        if not hit:
            return None
        versions = [found[int(r)][0] for r in uniq]
        row_latest = [latests[found[int(r)][1]] for r in uniq]
        meta = {"served_version": int(min(versions)),
                "latest_version": int(max(max(row_latest),
                                          max(versions))),
                "max_staleness": int(max(
                    max(lt - v for lt, v in zip(row_latest, versions)),
                    0)),
                "staleness_bound": int(bound),
                "cache_hit": True,
                "rows_requested": int(uniq.size),
                "rows_cached": int(uniq.size)}
        return [found[int(r)][3] for r in ids], meta

    def store(self, detail: dict) -> None:
        """Record one read's per-row results (a ``BatchedTableReader``
        ``detail``). Rows with no version stamp are skipped — an
        unstamped row cannot age against the tracker."""
        if detail is None:
            return
        rows = detail["rows"]
        values = detail["values"]
        versions = detail["versions"]
        owners = detail["owners"]
        gen = detail["generation"]
        with self._lock:
            for i, r in enumerate(rows):
                v = int(versions[i])
                if v < 0:
                    continue
                # pop-then-insert: a refreshed row moves to the END of
                # the eviction order instead of keeping its original
                # (oldest) slot.
                self._rows.pop(int(r), None)
                self._rows[int(r)] = (v, int(owners[i]), gen,
                                      values[i].tolist())
            while len(self._rows) > self._capacity:
                self._rows.pop(next(iter(self._rows)))

    @property
    def stats(self) -> dict:
        with self._lock:
            return {"hits": self.hits, "misses": self.misses,
                    "rows": len(self._rows)}

"""Admission control and backpressure for the serving frontend.

The serving tier's survival-under-load half (docs/SERVING.md): a
Zipf-skewed user-read flood must degrade into FAST TYPED REJECTIONS,
never into an unbounded queue. Three gates, checked in order per
request:

1. **drain gate** — a frontend shutting down rejects new work (503)
   while in-flight requests finish (``begin_drain`` waits for them,
   bounded by ``-serving_drain_s``);
2. **mailbox-pressure gate** — the actor mailboxes behind the reads
   (server/worker, ``MtQueue.track_depth``) are the real queue; when
   the observed depth exceeds the ``-serving_shed_depth`` high
   watermark, admitting more reads only lengthens every queued
   trainer Add and user read, so the request sheds (429);
3. **per-endpoint in-flight cap** — ``-serving_max_inflight``
   concurrent requests per endpoint; the cap bounds the frontend's own
   thread/table-lock convoy so the p99 of ACCEPTED requests stays flat
   under overload instead of collapsing.

A shed is a ``ShedError``: typed, retryable, carrying the machine
fields the HTTP layer maps to ``429/503 + Retry-After``
(``-serving_retry_after_s``). Shed decisions never block and never
allocate — under overload the reject path IS the hot path.
"""

from __future__ import annotations

import itertools
import time
from typing import Callable, Dict, Optional

from ..util.configure import (define_bool, define_double, define_int,
                              get_flag, register_tunable_hook)
from ..util.dashboard import count as count_event
from ..util.lock_witness import named_condition, named_lock

# ALL serving flags are registered here (not in frontend.py): the zoo
# imports this module eagerly so -serving_* parse at init, and this
# module is the one corner of the serving package that imports neither
# the HTTP stack nor anything under io/ or runtime/ — the frontend
# would cycle (io/__init__ -> stream -> runtime.zoo).
define_int("serving_port", 0,
           "start the online serving frontend (docs/SERVING.md) on "
           "this port on every rank hosting a worker actor; 0 "
           "(default) = serving off. Port 0 is never ephemeral here — "
           "tests construct ServingFrontend directly for that")
define_int("serving_max_rows", 4096,
           "per-request row cap on the serving frontend's rows "
           "endpoint: larger id lists answer 400 (one request must "
           "not monopolize the table lock)")
define_int("serving_max_inflight", 64,
           "per-endpoint cap on concurrently admitted serving-frontend "
           "requests: arrivals past it shed with a retryable 429 + "
           "Retry-After instead of convoying on the table lock. "
           "0 disables the cap")
define_int("serving_shed_depth", 256,
           "actor-mailbox depth high watermark for the serving "
           "frontend's load shedding: requests arriving while the "
           "deepest local server/worker mailbox exceeds this shed with "
           "429 + Retry-After (admitting more reads would only "
           "lengthen every queued request). 0 disables depth shedding")
define_double("serving_retry_after_s", 0.05,
              "the retry hint a shed serving request carries: rounded "
              "up to whole seconds in the Retry-After header (HTTP "
              "grammar), exact in the JSON body's retry_after_s")
define_double("serving_drain_s", 5.0,
              "graceful-drain bound at serving-frontend shutdown: new "
              "requests are rejected (503) immediately, in-flight ones "
              "get up to this many seconds to finish before the HTTP "
              "server closes")
define_bool("serving_scatter", True,
            "serve multi-row reads through the concurrent scatter-"
            "gather read path (read_rows_scatter: per-shard-owner "
            "sub-requests, partial-failure containment, request "
            "batching). false = the serialized PR-10 per-request "
            "read_rows_versioned path (A/B escape hatch)")
define_double("serving_batch_window_ms", 2.0,
              "request-batching window on the serving frontend's rows "
              "endpoint: concurrent reads arriving within this many "
              "ms fold into ONE scatter-gather table read (one device "
              "gather per shard per batch instead of per request). "
              "0 = no batching, each request issues its own scatter "
              "read (still concurrent-safe)")
define_int("serving_batch_max_rows", 1024,
           "size cap on one serving read batch, in merged unique "
           "rows: a batch reaching it flushes immediately instead of "
           "waiting out the window (bounds per-gather payload and "
           "worst-case head-of-line latency)")
define_int("serving_hot_rows", 4096,
           "row capacity of the serving frontend's hot-response "
           "cache: per-row rendered responses keyed on (table, row, "
           "served_version), served without touching the worker "
           "table while fresh within the staleness bound (and the "
           "data generation — reshard/rejoin force-invalidate). "
           "0 disables it")
define_double("serving_fleet_interval_s", 2.0,
              "how often a serving frontend reports its admission "
              "pressure to the controller and refreshes the fleet-"
              "aggregate view /v1/status exposes (rank identity + "
              "fleet-wide in-flight/shed counters, for external load "
              "balancers). 0 disables fleet reporting")
define_int("ann_nlist", 0,
           "IVF coarse-quantizer cluster count for the serving "
           "neighbors endpoint: > 0 replaces the O(rows x dims) "
           "linear cosine scan with an inverted-file search over the "
           "same staleness-bounded snapshot (k-means over unit "
           "vectors, rebuilt with the index). 0 (default) keeps the "
           "exact brute-force scan")
define_int("ann_nprobe", 8,
           "how many IVF clusters a neighbors query scans (recall/"
           "latency knob; per-request override via ?nprobe=). Clamped "
           "to -ann_nlist; brute=1 on the query string bypasses the "
           "index entirely")

#: Metric names (util/dashboard.py METRIC_NAMES).
SHED = "SERVING_SHED"

_serial = itertools.count()


class ShedError(RuntimeError):
    """A request the frontend refused to admit. Retryable by
    construction — the client backs off ``retry_after_s`` and
    re-issues; nothing about the request itself was wrong."""

    def __init__(self, reason: str, retry_after_s: float,
                 status: int = 429):
        super().__init__(reason)
        self.retry_after_s = float(retry_after_s)
        self.status = int(status)


class AdmissionController:
    """Bounded admission over named endpoints.

    ``depth_of`` is the mailbox-pressure probe (max depth across the
    rank's server/worker actor mailboxes, injected by the frontend so
    this module stays runtime-import-free). ``admit``/``release``
    bracket every admitted request; ``begin_drain`` flips the drain
    gate and waits (bounded) for in-flight work.
    """

    def __init__(self, depth_of: Optional[Callable[[], int]] = None,
                 max_inflight: Optional[int] = None,
                 shed_depth: Optional[int] = None,
                 retry_after_s: Optional[float] = None):
        self._depth_of = depth_of
        self._max_inflight = int(
            get_flag("serving_max_inflight", 64)
            if max_inflight is None else max_inflight)
        self._shed_depth = int(
            get_flag("serving_shed_depth", 256)
            if shed_depth is None else shed_depth)
        self._retry_after = float(
            get_flag("serving_retry_after_s", 0.05)
            if retry_after_s is None else retry_after_s)
        serial = next(_serial)
        self._lock = named_lock(f"serving.admission[{serial}]")
        self._idle = named_condition(
            f"serving.admission[{serial}].idle", self._lock)
        self._inflight: Dict[str, int] = {}
        self._total = 0
        self._draining = False
        self.admitted = 0
        self.shed = 0
        # Live retuning (docs/AUTOTUNE.md): both watermarks were
        # cached above at construction — a Control_Config broadcast
        # lands through these hooks (weakly held; a stopped frontend's
        # controller unregisters itself via GC).
        register_tunable_hook("serving_max_inflight",
                              self._retune_max_inflight)
        register_tunable_hook("serving_shed_depth",
                              self._retune_shed_depth)

    def _retune_max_inflight(self, value) -> None:
        self.configure(max_inflight=int(value))

    def _retune_shed_depth(self, value) -> None:
        self.configure(shed_depth=int(value))

    def configure(self, max_inflight: Optional[int] = None,
                  shed_depth: Optional[int] = None,
                  retry_after_s: Optional[float] = None) -> None:
        """Re-knob a live controller (autotune's apply hook and tests;
        production sets the flags before init)."""
        with self._lock:
            if max_inflight is not None:
                self._max_inflight = int(max_inflight)
            if shed_depth is not None:
                self._shed_depth = int(shed_depth)
            if retry_after_s is not None:
                self._retry_after = float(retry_after_s)

    @property
    def retry_after_s(self) -> float:
        return self._retry_after

    # -- the per-request bracket --
    def admit(self, endpoint: str) -> None:
        """Admit or raise ``ShedError``; a successful admit MUST be
        paired with ``release(endpoint)`` (the frontend's finally)."""
        # Depth probe outside the admission lock: it reads other locks
        # (mailbox mutexes) and must not nest under ours.
        if self._depth_of is not None and self._shed_depth > 0:
            depth = self._depth_of()
            if depth > self._shed_depth:
                self._note_shed()
                raise ShedError(
                    f"mailbox depth {depth} over the "
                    f"{self._shed_depth} shed watermark "
                    f"(-serving_shed_depth)", self._retry_after)
        with self._lock:
            if self._draining:
                reason, status = "serving frontend draining", 503
            elif 0 < self._max_inflight \
                    <= self._inflight.get(endpoint, 0):
                reason, status = (
                    f"{endpoint}: {self._inflight[endpoint]} requests "
                    f"already in flight (-serving_max_inflight="
                    f"{self._max_inflight})", 429)
            else:
                self._inflight[endpoint] = \
                    self._inflight.get(endpoint, 0) + 1
                self._total += 1
                self.admitted += 1
                return
        self._note_shed()
        raise ShedError(reason, self._retry_after, status=status)

    def release(self, endpoint: str) -> None:
        with self._lock:
            n = self._inflight.get(endpoint, 0) - 1
            if n > 0:
                self._inflight[endpoint] = n
            else:
                self._inflight.pop(endpoint, None)
            self._total = max(self._total - 1, 0)
            if self._total == 0:
                self._idle.notify_all()

    def _note_shed(self) -> None:
        with self._lock:
            self.shed += 1
        count_event(SHED)

    # -- graceful drain (frontend shutdown) --
    def begin_drain(self, timeout_s: Optional[float] = None) -> bool:
        """Reject new requests from now on; wait (bounded) for the
        in-flight ones. True when the frontend drained clean."""
        if timeout_s is None:
            timeout_s = float(get_flag("serving_drain_s", 5.0))
        deadline = time.monotonic() + max(float(timeout_s), 0.0)
        with self._lock:
            self._draining = True
            while self._total > 0:
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                self._idle.wait(timeout=min(remaining, 0.5))
            return True

    @property
    def draining(self) -> bool:
        with self._lock:
            return self._draining

    def stats(self) -> dict:
        with self._lock:
            return {"admitted": self.admitted, "shed": self.shed,
                    "inflight": dict(self._inflight),
                    "draining": self._draining,
                    "max_inflight": self._max_inflight,
                    "shed_depth": self._shed_depth,
                    "retry_after_s": self._retry_after}

"""Thread-role registry, the ``spawn`` wrapper, and the blocking
watchdog — the runtime twin of mvlint pass 9 (``thread-role``).

Every thread the package starts carries a declared **role**:

* ``EVENTLOOP`` — the transport's selector loop (one per endpoint):
  every socket accept/connect/read/write, retry and pacing timers,
  and the shm doorbell multiplex onto it. The ONLY call that may park
  it is ``selector.select(timeout)`` in its entry frame — pass 9
  proves nothing else blocking is reachable from a handler.
* ``DISPATCH`` — the communicator's receive loop. A blocked dispatch
  thread starves every control/liveness frame behind it (the PR-6/
  PR-9/PR-12 failure class, ROADMAP item 3).
* ``LIVENESS`` — the heartbeat monitor. Blocking here turns a healthy
  cluster into a false-positive death sentence.
* ``ACTOR`` — worker/server/controller run loops. May block on their
  own mailbox and on bounded table work.
* ``WRITER`` — the shm ring writers, the one queue-drainer class left:
  a full ring blocks the producer by design (bounded backpressure),
  which the event loop must never do. Blocking on the transport is
  their *job*: they exist so nothing latency-critical has to.
* ``BACKGROUND`` — everything else (metrics, snapshots, autotune,
  serving, prefetchers). Bounded-blocking by design, no budget
  enforced.

Threads register their role at spawn through :func:`spawn` (mvlint
pass 9 bans raw ``threading.Thread`` in the package), and the literal
:data:`THREAD_ROLES` table below is the canonical inventory — pass 9
cross-checks it BOTH directions against the spawn sites it discovers
through the call graph, and against the ``docs/THREADS.md`` table
(the WIRE_FORMAT.md registry precedent). Keys are
``<path-under-multiverso_tpu>::<qualname>`` of the *bound* entry
point: ``Actor._main`` spawned by a ``Server`` registers as
``runtime/server.py::Server._main`` — the role follows the
receiver's class, not where the ``def`` lexically lives.

The first ``spawn`` also starts the **heartbeat**, the process's one
always-on sampler (a daemon with no role, never started at import): it
sleeps ``BEAT_S``, notes how late it woke and the CPU clocks of the
process and of every registered thread, and records every slow stretch
as a *stall* classed frozen, held or blocked (``Stalls`` below;
docs/OBSERVABILITY.md "Stalls"). Under ``-debug_locks`` the same thread
is the blocking watchdog: it samples ``sys._current_frames()`` and
reports any DISPATCH/LIVENESS/EVENTLOOP thread whose innermost frame
has not moved for ``-role_block_budget_ms``, with the stack — the
dynamic confirmation of pass 9's static claim, exercised by the chaos
harness. A thread parked in its own entry frame or in the mailbox
(``mt_queue.py``) is *idle*, not blocked — idling in the run loop is
the healthy state the budget must not flag.
"""

from __future__ import annotations

import collections
import functools
import gc
import json
import os
import resource
import sys
import threading
import time
import traceback
from typing import Callable, Dict, List, Optional, Tuple

from ..util import dashboard, log
from ..util.configure import define_double, get_flag

define_double("role_block_budget_ms", 250.0,
              "blocking-watchdog budget for DISPATCH/LIVENESS threads "
              "(-debug_locks only): a latency-critical thread whose "
              "stack sits still longer than this is reported with the "
              "stack and stamped into ROLE_BLOCKED_MS[role]")

DISPATCH = "DISPATCH"
ACTOR = "ACTOR"
LIVENESS = "LIVENESS"
WRITER = "WRITER"
BACKGROUND = "BACKGROUND"
EVENTLOOP = "EVENTLOOP"

ROLES = (DISPATCH, ACTOR, LIVENESS, WRITER, BACKGROUND, EVENTLOOP)

#: Roles the watchdog budgets (and pass 9 proves non-blocking).
CRITICAL_ROLES = (DISPATCH, LIVENESS, EVENTLOOP)

#: Canonical thread inventory: entry point -> role. mvlint pass 9
#: derives the same table from the spawn sites + call graph and
#: fails on any disagreement in either direction; docs/THREADS.md
#: mirrors it for humans (also cross-checked). Literal on purpose —
#: the linter parses, never imports.
THREAD_ROLES = {
    "runtime/actor.py::Actor._main": ACTOR,
    "runtime/worker.py::Worker._main": ACTOR,
    "runtime/server.py::Server._main": ACTOR,
    "runtime/server.py::SyncServer._main": ACTOR,
    "runtime/controller.py::Controller._main": ACTOR,
    "runtime/communicator.py::Communicator._recv_main": DISPATCH,
    "runtime/controller.py::HeartbeatMonitor._main": LIVENESS,
    "runtime/tcp.py::_EventLoop._main": EVENTLOOP,
    "runtime/shm.py::_ShmPeerWriter._main": WRITER,
    "runtime/metrics.py::MetricsReporter._main": BACKGROUND,
    "runtime/snapshot.py::SnapshotManager._main": BACKGROUND,
    "runtime/autotune.py::AutotuneManager._main": BACKGROUND,
    "runtime/cluster.py::LocalCluster._run.rank_main": BACKGROUND,
    "util/async_buffer.py::ASyncBuffer._prefetch.run": BACKGROUND,
    "parallel/ma.py::model_average_async.run": BACKGROUND,
    "parallel/ma.py::sharded_model_average_async.run": BACKGROUND,
    "models/logreg/reader.py::PrefetchReader._fill": BACKGROUND,
    "models/wordembedding/data.py::BlockLoader._fill": BACKGROUND,
    "serving/frontend.py::ServingFrontend._fleet_main": BACKGROUND,
    "serving/batch.py::BatchedTableReader._run": BACKGROUND,
    "io/http_server.py::serve_forever": BACKGROUND,
}


# -- live registry ----------------------------------------------------

class _Entry:
    __slots__ = ("role", "thread", "entry_code", "cpu_clock")

    def __init__(self, role: str, thread: threading.Thread,
                 entry_code, cpu_clock: Optional[int] = None) -> None:
        self.role = role
        self.thread = thread
        self.entry_code = entry_code
        self.cpu_clock = cpu_clock


_registry: Dict[int, _Entry] = {}
_registry_lock = threading.Lock()
#: Bumped at every registration and exit, so that the heartbeat takes a
#: new list of threads only when there is one.
_registry_version = 0
_heartbeat: Optional[threading.Thread] = None

#: Watchdog diagnostics, in order (tests assert on this — its own
#: list, separate from lock_witness.reports(), so lock-order
#: assertions stay unpolluted).
_reports: List[str] = []


def _cpu_clock(ident: int) -> Optional[int]:
    """The CPU clock of the LIVE thread ``ident``: a thread asks for its
    own (a ``pthread_t`` of a thread that has exited must not be handed
    to ``pthread_getcpuclockid``; the clock id outlives its thread
    safely, reading it then raises ``OSError``), the main thread's
    outlives every other."""
    clock_of = getattr(time, "pthread_getcpuclockid", None)
    if clock_of is None:  # pragma: no cover - not a Linux
        return None
    try:
        return clock_of(ident)
    except OSError:  # pragma: no cover
        return None


def spawn(role: str, target, *, name: Optional[str] = None,
          args: Tuple = (), kwargs: Optional[dict] = None,
          daemon: bool = True) -> threading.Thread:
    """``threading.Thread`` with a declared role: the only sanctioned
    way to start a thread inside ``multiverso_tpu`` (pass 9 enforces
    this). Registers the thread with the heartbeat, which it starts the
    first time any thread is spawned."""
    if role not in ROLES:
        raise ValueError(f"unknown thread role {role!r} "
                         f"(choose from {ROLES})")
    entry_code = getattr(target, "__code__", None)

    def _main(*a, **k):
        global _registry_version
        ident = threading.get_ident()
        with _registry_lock:
            _registry[ident] = _Entry(role, threading.current_thread(),
                                      entry_code, _cpu_clock(ident))
            _registry_version += 1
        try:
            target(*a, **k)
        finally:
            with _registry_lock:
                _registry.pop(ident, None)
                _registry_version += 1

    thread = threading.Thread(target=_main, name=name, daemon=daemon,
                              args=args, kwargs=kwargs or {})
    ensure_heartbeat()
    thread.start()
    return thread


def roles_alive() -> Dict[str, int]:
    """Live thread count per role (observability/tests)."""
    out: Dict[str, int] = {}
    with _registry_lock:
        for entry in _registry.values():
            out[entry.role] = out.get(entry.role, 0) + 1
    return out


def reports() -> List[str]:
    with _registry_lock:
        return list(_reports)


def reset_reports() -> None:
    with _registry_lock:
        _reports.clear()


# -- stall records: opening rules, merging, classes --------------------

#: The heartbeat's period. Chip readings (PR 52, a TPU v5 lite machine,
#: 13 cores, a sandboxed kernel on which a clock read of ANOTHER thread
#: or of the process is a system call of 6 us; CHANGES.md has the calls):
#: one beat costs 17 to 19 us of the interpreter's lock with the threads'
#: clocks read every fourth beat (46 us read every beat: call 2), so 100
#: beats a second are 0.18% of it; an ordinary beat wakes 0.22 ms late in
#: `sgns8m.ps`, 0.55 to 0.68 in `mperf16m.rows`, 0.5 to 0.9 in the model
#: cells (HOST_BEAT_LATE over its count), and three parent/change pairs
#: of `sgns8m.ps` read -0.0000, -0.0003, -0.0009%. Those processes have
#: five watched threads (four registered and the main one). Each further
#: registered thread is one more clock read every fourth beat, 1.6 us a
#: beat, 0.016% of the lock: 15 us a beat with 4 registered threads, 29
#: with 12, 47 with 24 (call 11), so a serving or shm process of
#: twenty-five threads pays 0.47% there.
BEAT_S = 0.010
#: A beat later than this opens a record. The stalls on record are 112 ms
#: and more (the chip's own, PR 52: 97 to 122); four Python threads taking
#: turns at the interpreter's 5 ms switch interval keep an ordinary beat
#: under 20, and no record of the hunt's 27 measured windows lay between
#: 40 and 97. Stays over twice the period.
LATE_FLOOR_MS = 40.0
#: A WORKING monitor's entry opens a record when, less what late beats
#: already cover of it, it is over dashboard.LONG_ENTRY_MS, this many
#: times the monitor's own mean before it, once the monitor has a mean,
#: and BLOCKED_OVER_LONG times the median of that monitor's last long
#: entries: a monitor of two kinds of entry (`xing29b.ps-4k`'s
#: UPDATE_DISPATCH: most under 1 ms, the widest table's 40 ms, every
#: step) opens one record, the first time, and none after (call 4 read 14
#: such records in one window under the mean's rule alone).
BLOCKED_RATIO = 8.0
BLOCKED_MIN_ENTRIES = 32
BLOCKED_OVER_LONG = 2.0
#: A record stays open this long after its last opening, for the entries
#: that were open across it to end and be listed.
GRACE_S = 0.25
#: Late beats whose process CPU is under this share of the stretch:
#: frozen; over it: held, by the registered thread whose own CPU clock
#: ran over HELD_CPU_SHARE of the stretch, if one did.
FROZEN_CPU_SHARE = 0.25
HELD_CPU_SHARE = 0.5
#: The process-wide files are read this often, and once more at every
#: opening.
SLOW_READ_S = 1.0
#: The threads' CPU clocks are read at every beat of this many, and at
#: every late one: a record's bracket for a thread starts up to 40 ms
#: before it, which the thread's usual share over that part corrects.
THREAD_CLOCKS_EVERY = 4
MAIN = "MAIN"


class _Record:
    __slots__ = ("began", "end", "late", "entries", "opener", "frames",
                 "last", "counted")

    def __init__(self, began: float, end: float) -> None:
        self.began, self.end, self.last = began, end, end
        self.late: List[Tuple[float, float]] = []   # (wake, late ms)
        self.entries: list = []
        self.opener = None      # the working entry that opened it
        self.frames: Optional[Dict[int, str]] = None
        self.counted: set = set()   # the monitors that have its entry


#: One opening: the record it opened or joined, its class taken alone,
#: and the milliseconds it adds (a late beat's lateness; a working
#: entry's length less what late beats cover of it).
_Opening = Tuple[_Record, str, float]


class Stalls:
    """What opens a stall record, what merges and how one is classed.
    It owns no clock and reads no file: the heartbeat feeds it beats
    (``beat``), the long monitor entries (``entry``), the process-wide
    readings (``reading``) and collections (``collected``) on
    ``time.monotonic()``'s scale, and takes the closed records from
    ``closed``; the tests feed it a written timeline."""

    def __init__(self, wall_ns_at: Callable[[float], int],
                 describe: Callable[[int], Tuple[str, str]],
                 ring: int = 512) -> None:
        self._wall_ns_at = wall_ns_at
        self._describe = describe
        self._ring = ring
        self._t = [0.0] * ring          # preallocated: a beat writes
        self._late = [0.0] * ring       # four slots and allocates its
        self._cpu = [0] * ring          # threads' dict alone
        self._thr: list = [None] * ring
        self._n = 0
        self._readings: collections.deque = collections.deque(maxlen=16)
        self._collections: collections.deque = collections.deque(maxlen=64)
        self._pending: List[_Record] = []
        #: late stretches of records already closed: (began, end)
        self._covered: collections.deque = collections.deque(maxlen=16)
        #: a working monitor's last long entries, ms, by its name
        self._long: Dict[str, collections.deque] = {}

    # -- what the heartbeat feeds ------------------------------------
    def beat(self, t: float, late_ms: float, cpu_ns: int,
             threads: Optional[Dict[int, int]]) -> Optional[_Opening]:
        """``threads``: each watched thread's CPU clock, or None for a
        beat that did not read them."""
        i = self._n % self._ring
        self._t[i], self._late[i] = t, late_ms
        self._cpu[i], self._thr[i] = cpu_ns, threads
        self._n += 1
        if late_ms <= LATE_FLOOR_MS:
            return None
        began = t - late_ms / 1e3
        cpu_ms, _ = self._cpu_ms(_Record(began, t),
                                 lambda k: self._cpu[k % self._ring])
        record = self._open(began, t)
        record.late.append((t, late_ms))
        return (record, "frozen" if cpu_ms <= FROZEN_CPU_SHARE * late_ms
                else "held", late_ms)

    def entry(self, name: str, ident: int, end: float, ms: float,
              count: int, total_ms: float) -> Optional[_Opening]:
        if name.startswith("HOST_"):    # the heartbeat's own monitors
            return None
        began = end - ms / 1e3
        item = (name, ident, began, end, ms)
        opened = None
        if not dashboard.only_waits(name) \
                and name not in dashboard.BUILDS \
                and count > BLOCKED_MIN_ENTRIES:
            own = ms - 1e3 * sum(
                _overlap(began, end, b, e) for b, e in self._late_spans())
            mean = (total_ms - ms) / (count - 1)
            if own > dashboard.LONG_ENTRY_MS:
                recent = self._long.setdefault(
                    name, collections.deque(maxlen=8))
                usual = sorted(recent)[len(recent) // 2] if recent else 0.0
                recent.append(own)
                if own > BLOCKED_RATIO * mean \
                        and own > BLOCKED_OVER_LONG * usual:
                    record = self._open(began, end)
                    if record.opener is None:
                        record.opener = item
                    opened = (record, "blocked", own)
        for record in self._pending:
            if _overlap(began, end, record.began, record.end) > 0.0:
                record.entries.append(item)
        return opened

    def reading(self, t: float, values: Dict[str, int]) -> None:
        self._readings.append((t, values))

    def collected(self, began: float, end: float) -> None:
        self._collections.append((began, end))

    @property
    def pending(self) -> bool:
        return bool(self._pending)

    def closed(self, now: float) -> List[dict]:
        """The records whose grace has run out, as plain dicts."""
        due = [r for r in self._pending if now >= r.last + GRACE_S]
        if not due:
            return []
        self._pending = [r for r in self._pending if r not in due]
        return [self._close(r) for r in due]

    # -- inside ------------------------------------------------------
    def _late_spans(self):
        yield from self._covered
        for record in self._pending:
            for wake, late_ms in record.late:
                yield wake - late_ms / 1e3, wake

    def _open(self, began: float, end: float) -> _Record:
        """A new record, or the pending one this opening overlaps (two
        beats late in a row lie a period apart: that is overlapping)."""
        for record in self._pending:
            if began <= record.end + 2 * BEAT_S \
                    and end >= record.began - 2 * BEAT_S:
                record.began = min(record.began, began)
                record.end = max(record.end, end)
                record.last = max(record.last, end)
                return record
        record = _Record(began, end)
        self._pending.append(record)
        return record

    def _beat_at(self, t: float, after: bool) -> Optional[int]:
        """Index (a count, not a slot) of the last beat at or before
        ``t``, or with ``after`` of the first at or after it; the nearest
        the ring still has otherwise."""
        if not self._n:
            return None
        oldest = max(0, self._n - self._ring)
        k = self._n - 1
        while k > oldest and self._t[k % self._ring] > t:
            k -= 1
        if after and self._t[k % self._ring] < t and k < self._n - 1:
            k += 1
        return k

    def _cpu_ms(self, record: _Record, series) -> Tuple[float, float]:
        """CPU milliseconds of ``series`` (a beat's index -> ns, None
        where the beat lacks it) inside the record, and its usual share
        of a core before it: what ran between the two beats that bracket
        the record, less the usual share over the part of the bracket
        that lies outside it."""
        a = self._beat_at(record.began, after=False)
        b = self._beat_at(record.end, after=True)
        if a is None:
            return 0.0, 0.0
        # the threads' clocks are read every few beats: the nearest
        # beats outward that have a reading
        oldest = max(0, self._n - self._ring)
        while a > oldest and series(a) is None:
            a -= 1
        while b < self._n - 1 and series(b) is None:
            b += 1
        if b <= a or series(a) is None or series(b) is None:
            return 0.0, 0.0
        ring, t = self._ring, self._t
        back = max(oldest, a - 32)
        while back < a and series(back) is None:
            back += 1
        usual = 0.0
        if t[a % ring] > t[back % ring]:
            usual = (series(a) - series(back)) / 1e9 \
                / (t[a % ring] - t[back % ring])
        outside = max(0.0, (t[b % ring] - t[a % ring])
                      - (record.end - record.began))
        used = (series(b) - series(a)) / 1e6 - usual * outside * 1e3
        return max(0.0, used), usual

    def _close(self, record: _Record) -> dict:
        ring = self._ring
        ms = (record.end - record.began) * 1e3
        late_ms = sum(late for _, late in record.late)
        cpu_ms, usual = self._cpu_ms(
            record, lambda k: self._cpu[k % ring])
        first = self._beat_at(record.began, after=False) or 0
        before = [self._late[k % ring] for k in
                  range(max(0, self._n - ring, first - 32), first)]
        last = self._beat_at(record.end, after=True) or 0
        while last < self._n - 1 and self._thr[last % ring] is None:
            last += 1
        threads = []
        for ident in self._thr[last % ring] or ():
            name, role = self._describe(ident)
            used, share = self._cpu_ms(
                record, lambda k: (self._thr[k % ring] or {}).get(ident))
            threads.append({
                "name": name, "role": role, "cpu_ms": round(used, 3),
                "usual_cpu_share": round(share, 3),
                "at": (record.frames or {}).get(ident)})
        threads.sort(key=lambda th: -th["cpu_ms"])
        # the process's threads that are not the registry's: the device
        # runtime's, the heartbeat
        other_ms = max(0.0, cpu_ms - sum(th["cpu_ms"] for th in threads))
        if record.late and late_ms >= 0.5 * ms:
            if cpu_ms <= FROZEN_CPU_SHARE * ms:
                kind, by = "frozen", None
            else:
                kind = "held"
                by = threads[0]["name"] if threads and \
                    threads[0]["cpu_ms"] > HELD_CPU_SHARE * ms else None
            for wake, late in record.late:
                self._covered.append((wake - late / 1e3, wake))
        else:
            kind = "blocked"
            by = record.opener[0] if record.opener else None
        out = {
            "class": kind, "ms": round(ms, 3),
            "began_wall_ns": self._wall_ns_at(record.began),
            "by": by,
            "late_beats": [[round((wake - record.began) * 1e3, 3),
                            round(late, 3)] for wake, late in record.late],
            "cpu_ms": round(cpu_ms, 3), "usual_cpu_share": round(usual, 3),
            "other_cpu_ms": round(other_ms, 3),
            "usual_late_ms": round(sum(before) / max(len(before), 1), 3),
            "threads": threads,
            "entries": [{"name": name, "thread": self._describe(ident)[0],
                         "ms": round(entry_ms, 3),
                         "ended_ms": round((end - record.began) * 1e3, 3),
                         "waits": dashboard.only_waits(name)}
                        for name, ident, _, end, entry_ms in record.entries],
            "deltas": self._deltas(record),
            "gc_ms": round(1e3 * sum(
                _overlap(record.began, record.end, b, e)
                for b, e in self._collections), 3)}
        if kind == "blocked" and record.opener:
            out["thread"] = self._describe(record.opener[1])[0]
        return out

    def _deltas(self, record: _Record) -> dict:
        """What the process-wide readings that bracket the record
        counted between them; a key missing from either is left out."""
        before = after = None
        for t, values in self._readings:
            if t <= record.began:
                before = (t, values)
            elif t >= record.end and after is None:
                after = (t, values)
        if before is None or after is None:
            return {}
        out = {key: after[1][key] - value
               for key, value in before[1].items() if key in after[1]}
        out["bracket_s"] = round(after[0] - before[0], 3)
        return out


def _overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


# -- the process-wide readings ------------------------------------------

def cgroup_cpu_stat(proc_cgroup: str = "/proc/self/cgroup",
                    root: str = "/sys/fs/cgroup") -> Optional[str]:
    """The ``cpu.stat`` of this process's cgroup, v2 or v1, or None."""
    candidates = []
    try:
        with open(proc_cgroup) as f:
            for line in f:
                _, controllers, path = line.rstrip("\n").split(":", 2)
                if not controllers:                       # v2
                    candidates += [(root, path), (root, "")]
                elif "cpu" in controllers.split(","):     # v1
                    for mount in (controllers, "cpu"):
                        candidates += [(os.path.join(root, mount), path),
                                       (os.path.join(root, mount), "")]
    except (OSError, ValueError):
        pass
    candidates.append((root, ""))
    for mount, path in candidates:
        found = os.path.join(mount, path.lstrip("/"), "cpu.stat")
        if os.path.isfile(found):
            return found
    return None


def read_process(cpu_stat: Optional[str],
                 proc_stat: str = "/proc/stat") -> Dict[str, int]:
    """Throttling (the cgroup's ``cpu.stat``), steal (``/proc/stat``),
    context switches and page faults (``getrusage``), whatever of them
    this machine has: a file that is absent leaves its keys out."""
    usage = resource.getrusage(resource.RUSAGE_SELF)
    out = {"vol_switches": usage.ru_nvcsw,
           "invol_switches": usage.ru_nivcsw,
           "major_faults": usage.ru_majflt,
           "minor_faults": usage.ru_minflt}
    if cpu_stat is not None:
        try:
            with open(cpu_stat) as f:
                stat = dict(line.split() for line in f)
            out["throttled_periods"] = int(stat["nr_throttled"])
            out["throttled_usec"] = int(stat["throttled_usec"]) \
                if "throttled_usec" in stat \
                else int(stat["throttled_time"]) // 1000     # v1: ns
        except (OSError, ValueError, KeyError):
            pass
    try:
        with open(proc_stat) as f:
            out["steal_ticks"] = int(f.readline().split()[8])
    except (OSError, ValueError, IndexError):
        pass
    return out


# -- the heartbeat thread -----------------------------------------------

def ensure_heartbeat() -> None:
    """Start the heartbeat unless it runs. ``spawn`` calls it; so does a
    trainer that runs with no actor (the local word2vec trainer's
    epoch)."""
    global _heartbeat
    if _heartbeat is not None and _heartbeat.is_alive():
        return
    with _registry_lock:
        if _heartbeat is not None and _heartbeat.is_alive():
            return
        _heartbeat = threading.Thread(  # the heartbeat itself carries
            target=_heartbeat_main,     # no role: it must outlive budgets
            name="mv-heartbeat", daemon=True)
        _heartbeat.start()


def _budget_ms() -> float:
    try:
        return float(get_flag("role_block_budget_ms"))
    except Exception:  # noqa: BLE001 - unparsed flags must not kill it
        return 250.0


def _idle(entry: _Entry, frame) -> bool:
    """Parked-not-blocked: the innermost package frame is the thread's
    own entry function (a run loop waiting for work), or any frame
    sits in the mailbox (``mt_queue.pop`` is the idle state of every
    actor)."""
    innermost_pkg = None
    f = frame
    while f is not None:
        fname = f.f_code.co_filename
        if fname.endswith("mt_queue.py"):
            return True
        if innermost_pkg is None and "multiverso_tpu" in fname:
            innermost_pkg = f.f_code
        f = f.f_back
    return innermost_pkg is None or innermost_pkg is entry.entry_code


def _package_frames(idents) -> Dict[int, str]:
    """``file:line function`` of each thread's innermost package frame
    (its innermost frame where the package is nowhere on its stack)."""
    out = {}
    frames = sys._current_frames()
    for ident in idents:
        frame = inner = frames.get(ident)
        while frame is not None \
                and "multiverso_tpu" not in frame.f_code.co_filename:
            frame = frame.f_back
        frame = frame or inner
        if frame is not None:
            out[ident] = (f"{frame.f_code.co_filename}:{frame.f_lineno} "
                          f"{frame.f_code.co_name}")
    return out


class _Budgets:
    """The blocking budgets of the critical roles (-debug_locks only):
    signature -> first-seen monotonic time; reported signatures."""

    def __init__(self) -> None:
        self.first_seen: Dict[Tuple[int, str, int], float] = {}
        self.reported: Dict[Tuple[int, str, int], bool] = {}
        self.next_check = 0.0
        self.armed = False      # -debug_locks, as last read

    def check(self, now: float) -> None:
        if now < self.next_check:
            return
        try:
            self.armed = bool(get_flag("debug_locks"))
        except Exception:  # noqa: BLE001 - unparsed flags
            self.armed = False
        if not self.armed:
            self.next_check = now + 0.1
            return
        budget_ms = _budget_ms()
        self.next_check = now + max(budget_ms / 4000.0, BEAT_S)
        with _registry_lock:
            critical = {ident: entry for ident, entry
                        in _registry.items()
                        if entry.role in CRITICAL_ROLES}
        if not critical:
            return
        frames = sys._current_frames()
        live: set = set()
        for ident, entry in critical.items():
            frame = frames.get(ident)
            if frame is None or _idle(entry, frame):
                continue
            sig = (ident, frame.f_code.co_filename, frame.f_lineno)
            live.add(sig)
            start = self.first_seen.setdefault(sig, now)
            blocked_ms = (now - start) * 1000.0
            if blocked_ms > budget_ms and not self.reported.get(sig):
                self.reported[sig] = True
                _report(entry, frame, blocked_ms)
        for sig in list(self.first_seen):
            if sig not in live:
                self.first_seen.pop(sig, None)
                self.reported.pop(sig, None)


def _registered(seen: int):
    """The registry's version and ``(ident, CPU clock, name, role)`` of
    every registered thread and of the main one; None while the version
    is still ``seen``."""
    if seen == _registry_version:
        return None
    with _registry_lock:
        version = _registry_version
        threads = [(ident, entry.cpu_clock, entry.thread.name, entry.role)
                   for ident, entry in _registry.items()]
    main = threading.main_thread()
    threads.append((main.ident, _cpu_clock(main.ident), main.name, MAIN))
    return version, threads


class Heartbeat:
    """The sampler, one beat a call (``once``), on the clocks it is
    given: the thread gives it the machine's, the tests a written
    timeline."""

    def __init__(self, monotonic=time.monotonic, sleep=time.sleep,
                 process_ns=time.process_time_ns,
                 thread_ns=time.clock_gettime_ns, wall_ns=time.time_ns,
                 threads=_registered, read=None, publish=None) -> None:
        self._monotonic, self._sleep = monotonic, sleep
        self._process_ns, self._thread_ns = process_ns, thread_ns
        self._threads = threads
        self._read = read or functools.partial(read_process,
                                               cgroup_cpu_stat())
        self._publish = publish or _publish
        self._version = -1
        self._watched: List[Tuple[int, int]] = []
        self._who: Dict[int, Tuple[str, str]] = {}
        self._next_read = 0.0
        self._woke = monotonic()
        self._beats = 0
        self.stalls = Stalls(
            lambda t: wall_ns() - int((monotonic() - t) * 1e9),
            lambda ident: self._who.get(ident, (f"thread-{ident}", "?")))
        self._budgets = _Budgets()

    def _watch(self) -> None:
        found = self._threads(self._version)
        if found is None:
            return
        self._version, threads = found
        self._watched = [(ident, clock) for ident, clock, _, _ in threads
                         if clock is not None]
        if len(self._who) > 256:    # the names of threads long gone
            self._who.clear()
        self._who.update({ident: (name, role)
                          for ident, _, name, role in threads})

    def once(self) -> None:
        monotonic, stalls = self._monotonic, self.stalls
        # due a period after the last WAKE, not after the last beat's
        # work: the beats keep their pace, and a stop that falls into
        # that work makes this beat late like any other
        due = self._woke + BEAT_S
        delay = due - monotonic()
        if delay > 0.0:
            self._sleep(delay)
        now = self._woke = monotonic()
        late_ms = (now - due) * 1e3
        self._watch()
        clocks = None
        self._beats += 1
        if late_ms > LATE_FLOOR_MS \
                or self._beats % THREAD_CLOCKS_EVERY == 0:
            clocks = {}
            for ident, clock in self._watched:
                try:
                    clocks[ident] = self._thread_ns(clock)
                except OSError:     # the thread has gone
                    pass
        openings = [stalls.beat(now, late_ms, self._process_ns(), clocks)]
        dashboard.Dashboard.get("HOST_BEAT_LATE").add(late_ms)
        long_entries = dashboard.long_entries
        while long_entries:
            openings.append(stalls.entry(*long_entries.popleft()))
        openings = [opening for opening in openings if opening]
        for opening in openings:
            _count(*opening)
        if openings or now >= self._next_read:
            # once a second, and once more right after an opening: a
            # stall lies between two readings at most a second apart
            stalls.reading(monotonic(), self._read())
            self._next_read = now + SLOW_READ_S
            # Where each thread stands, under -debug_locks alone:
            # sys._current_frames() makes frame OBJECTS of the running
            # threads' frames, and a frame that has one keeps its locals
            # (a trainer's gradients, on the device) until the object
            # dies, by the collector where a cycle holds it. Read at every
            # stall it raised `xing29b.ps-4k`'s peak from 14.30 to 15.24
            # GB and ran it out of device memory (PR 52, calls 3 and 8).
            if self._budgets.armed:
                for record, _, _ in openings:
                    if record.frames is None:
                        record.frames = _package_frames(self._who)
        if stalls.pending:
            for record in stalls.closed(now):
                self._publish(record)
        self._budgets.check(now)

    def run(self) -> None:
        began = [0.0]

        def on_gc(phase, info):
            if phase == "start":
                began[0] = time.monotonic()
            elif began[0] and time.monotonic() - began[0] > 0.005:
                self.stalls.collected(began[0], time.monotonic())

        gc.callbacks.append(on_gc)
        try:
            while True:
                self.once()
        finally:
            gc.callbacks.remove(on_gc)


def _heartbeat_main() -> None:
    Heartbeat().run()


def _count(record: _Record, kind: str, ms: float) -> None:
    """An opening is counted as it is seen, not when its record closes a
    quarter of a second later: whoever reads the monitors at a window's
    two edges (the benchmark) must not find in the window a stall that
    ended before it (the harness's own full collection, 100 ms in the
    model cells, ends 2 ms before every window). A record is ONE entry of
    HOST_STALL, and of HOST_STALL_FROZEN if a late beat of its was one
    over which the process used next to no CPU (the machine's part; the
    other classes are read from the records, which no counter repeats);
    its openings add their milliseconds."""
    names = ("HOST_STALL", "HOST_STALL_FROZEN") if kind == "frozen" \
        else ("HOST_STALL",)
    for name in names:
        dashboard.Dashboard.get(name).add(
            ms, 0 if name in record.counted else 1)
        record.counted.add(name)


def _publish(record: dict) -> None:
    """A closed record: kept, logged as one JSON line, and under an open
    profiler session dropped on the host plane as an ``mv:HOST_STALL``
    annotation."""
    dashboard.keep_stall(record)
    # A warning is what nobody in the program accounts for: the process
    # off the CPU, the interpreter held by no thread of the registry, a
    # working call that stood still. Held by a thread that is named is
    # that thread's own work (an import, a lowering, a compile, the
    # harness's full collection: a dozen a process at set-up), at debug
    # level: not info, which goes to standard output, where the last
    # line is the main program's (the benchmark's result).
    # The last lines a process writes on standard error are its main
    # program's too (the benchmark prints there what its check compared):
    # a record that closes once that has ended is kept and not logged.
    if threading.main_thread().is_alive():
        ordinary = record["class"] == "held" and record["by"] is not None
        (log.debug if ordinary else log.warning)(
            "host stall: %s", json.dumps(record))
    dashboard.mark("HOST_STALL", ms=record["ms"],
                   began_wall_ns=record["began_wall_ns"],
                   **{"class": record["class"]})


def _report(entry: _Entry, frame, blocked_ms: float) -> None:
    stack = "".join(traceback.format_stack(frame))
    text = (f"{entry.role} thread {entry.thread.name!r} blocked "
            f"{blocked_ms:.0f}ms (budget "
            f"{_budget_ms():.0f}ms) at "
            f"{frame.f_code.co_filename}:{frame.f_lineno}\n{stack}")
    with _registry_lock:
        _reports.append(text)
    dashboard.samples(f"ROLE_BLOCKED_MS[{entry.role}]").add(blocked_ms)
    log.error("role watchdog: %s", text)

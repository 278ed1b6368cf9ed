"""Shared-memory transport for co-located ranks: the zero-copy story
*below* the socket.

PR 15 removed every Python-level copy from the wire path, but same-host
peers still pushed each frame through kernel loopback — two syscalls
and two kernel copies per frame. This module finishes the job: frames
between co-located ranks travel through a per-directed-pair ring of
fixed slots in POSIX shared memory (``multiprocessing.shared_memory``),
written by the sender's writer thread and consumed in place by the
receiver's event loop — **one** ``memoryview`` copy total (producer
side, into the slot) and **zero** syscalls on the data path. A doorbell
FIFO per receiver (one nonblocking byte after each frame, drained on
the selector) replaces the old busy-polling consumer thread: an idle
pair costs a parked ``selector.select``, not CPU.

Architecture (see docs/MEMORY.md "Below the socket"):

- :class:`ShmNet` wraps a :class:`~.tcp.TcpNet`. TCP stays fully live:
  bootstrap, the ``Control_Register`` handshake, frames to remote or
  non-shm peers, and — critically — peer-death detection (the TCP
  reader's dirty-close path is the doorbell that retires rings).
- Transport selection is negotiated at registration exactly like the
  PR-1 codec-capability bit: each rank advertises :data:`CAP_SHM` plus
  a host fingerprint in its register blob; the controller broadcasts
  the per-rank host ids and a cluster-wide random *token*, and the zoo
  calls :meth:`ShmNet.enable_shm` with the set of same-host capable
  peers. A ``-shm=0`` rank advertises nothing and is simply never
  ring-addressed — mixed clusters interoperate frame for frame.
- The **send side** is negotiated; the **receive side** is
  announce-driven and needs no negotiation state at all. The sender
  creates its outbound segment lazily on its writer thread at first
  ring send, then sends a ``Control_Shm_Announce`` frame *over TCP*
  carrying ``[nonce, token]``. ``TcpNet.send`` flushes the
  destination's TCP writer first, so the announce orders after every
  frame already queued — the receiver attaches the segment when the
  announce arrives and nothing can overtake the transport switch.
  This asymmetry matters: a later-registering rank must be able to
  consume the controller's ring-borne ``Control_Reply_Register``
  *before* its own negotiation completes.

Ring layout (one segment per directed pair, name
``mvshm-{token:08x}-{src}-{dst}``)::

    [ring header 64B: magic, version, nslots, slot_bytes, nonce]
    [slot control x nslots, 64B stride: state | flags, nbytes, total]
    [slot payloads x nslots, 64-byte aligned, slot_bytes each]

A slot's control word is ``state`` (0=FREE, 1=READY) packed *last* on
write and read *first* on consume; the metadata (flags/nbytes/total/
seq) lands before the state flips. CPython's eval loop plus x86-TSO
store ordering make the plain packs sufficient — there is no torn-read
window a peer can observe. Slots do NOT recycle in FIFO order: the
writer claims any FREE slot and the consumer locates the next frame by
its ``seq`` stamp, so a slot pinned by a consumer-held frame is walked
around instead of waited on (without this, one long-held frame would
stall the whole ring at wraparound).

Ownership reuses the PR-15 ``BufferPool`` lease discipline unchanged:
a frame that fits one slot is parsed in place —
``tcp._deserialize_frame`` cuts read-only Blob views straight into the
shared slot, with a :class:`_SlotLease` riding the Blobs. When the
last Blob dies the lease checks its *weak references* to the frame's
backing numpy arrays; a survivor (a user-held view pins its base
array) makes the slot *park* instead of freeing (the ring
service re-probes), so a blob outliving everything can never alias a recycled
slot. A blob outliving the whole segment is safe too: ``shm.close()``
with live exports raises ``BufferError`` and the mapping moves to a
module graveyard instead of unmapping.

Ring exhaustion degrades, never deadlocks: the writer blocks with the
same ``-send_queue_mb`` bounded backpressure as the TCP writer, spins
with escalating sleeps on a full ring, logs once a second, and raises
:class:`~.net.PeerLostError` the moment the ring is closed under it.
Frames larger than one slot stream as chunked slot sequences (CONT
flag) and are reassembled into a pooled lease on the receive side —
one extra copy, counted in ``SHM_BYTES_COPIED``, never a stall. And a
consumer that sits on delivered frames (an out-of-order stash, a slow
actor) can pin at most HALF the ring: past that, ``consume`` copies
frames out through the pool (``SHM_PIN_COPIES``) so the writer always
progresses.
"""

from __future__ import annotations

import atexit
import collections
import os
import selectors
import socket as _socket
import struct
import threading
import time
import weakref
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.blob import Blob
from ..core.message import Message, MsgType
from ..util import chaos, log
from ..util.configure import define_bool, define_int, get_flag
from ..util.dashboard import count, monitor
from ..util.lock_witness import named_condition, named_lock
from . import thread_roles
from .net import NetInterface, PeerLostError
from .tcp import _LEN, TcpNet, _deserialize_frame, serialize_views

try:  # POSIX shared memory; absent on exotic builds — gate, don't crash
    from multiprocessing import resource_tracker, shared_memory
except ImportError:  # pragma: no cover - non-POSIX fallback
    resource_tracker = None
    shared_memory = None

try:
    import _posixshmem  # the raw unlink syscall, without tracker side effects
except ImportError:  # pragma: no cover - non-POSIX fallback
    _posixshmem = None

define_bool("shm", True,
            "shared-memory transport for co-located ranks: frames "
            "between same-host peers travel through per-pair shm rings "
            "(one memoryview copy, zero syscalls) instead of kernel "
            "loopback; negotiated per peer at registration like the "
            "wire-codec capability bit, TCP kept for remote peers. "
            "0 = advertise nothing and stay on TCP everywhere")
define_int("shm_ring_slots", 16,
           "slots per outbound shm ring (per directed peer pair); a "
           "full ring blocks the writer thread with bounded "
           "backpressure, it never deadlocks or drops")
define_int("shm_slot_kb", 512,
           "payload bytes per shm ring slot (KB); a frame that fits "
           "one slot is consumed zero-copy in place, a larger frame "
           "streams across slots and is reassembled through the "
           "receive pool (one extra copy, counted in SHM_BYTES_COPIED)")

#: Capability bit advertised in the Control_Register blob (PR-1 codec
#: negotiation precedent: util/wire_codec.py CAP_WIRE_CODEC = 1).
CAP_SHM = 2

_RING_MAGIC = 0x4D565348  # "MVSH"
_RING_VERSION = 1
#: Segment header: magic, version, nslots, slot_bytes, nonce.
_RING_HDR = struct.Struct("<IIIIQ")
#: Per-slot control, split on purpose: the metadata struct (flags,
#: nbytes, total, seq — at control offset +4) is packed BEFORE the
#: state word (at +0) flips to READY, and consumers read state first.
#: ``seq`` is the writer's absolute slot counter: a slot can sit READY
#: long after the consumer moved past it (an in-place Blob view holds
#: it until the lease dies), so on wraparound READY alone is
#: ambiguous — the consumer only takes a slot whose seq matches its
#: own absolute position.
_SLOT_STATE = struct.Struct("<I")
_SLOT_META = struct.Struct("<IQQQ")
_SLOT_STRIDE = 64
_ALIGN = 64
_CTRL_OFF = 64  # header rounded up to one cache line

_FREE = 0
_READY = 1
_F_CONT = 1  # more chunks of this frame follow in later slots


def supported() -> bool:
    """POSIX shared memory available on this build?"""
    return shared_memory is not None and _posixshmem is not None


def host_fingerprint() -> int:
    """Same-host detector for the register handshake: hostname plus the
    kernel boot id (two containers sharing a hostname but not /dev/shm
    differ in boot id on distinct kernels; same-kernel containers with
    private shm namespaces are out of scope — ``-shm=0`` is the
    escape hatch). Fits an int32 register slot."""
    ident = _socket.gethostname()
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            ident += f.read().strip()
    except OSError:  # pragma: no cover - no procfs
        pass
    return zlib.crc32(ident.encode()) & 0x7FFFFFFF


def _seg_name(token: int, src: int, dst: int) -> str:
    return f"mvshm-{token & 0xFFFFFFFF:08x}-{src}-{dst}"


def _bell_name(token: int, rank: int) -> str:
    """Doorbell FIFO name for ``rank``'s receive side. The mvshm-
    prefix keeps it inside the lifecycle-hygiene sweep (tests scan
    /dev/shm for leftovers by that prefix), and ``_unlink_name``'s raw
    shm_unlink removes /dev/shm entries regardless of file type."""
    return f"mvshm-bell-{token & 0xFFFFFFFF:08x}-{rank}"


def _untrack(shm) -> None:
    """Opt this mapping out of the multiprocessing resource tracker.
    The tracker would unlink every registered segment at interpreter
    exit *and* print leak warnings — but segment lifetime is OURS
    (creator unlinks on retire/finalize; survivors reap a dead peer's
    names), and the tracker registers on attach too, so a reader
    exiting first would unlink a ring its peer still writes. Exactly
    one unregister per create/attach — a second one trips tracker
    KeyError noise on stderr."""
    if resource_tracker is not None:
        try:
            resource_tracker.unregister(shm._name, "shared_memory")
        except Exception:  # noqa: BLE001 - tracker noise must not kill IO
            pass


def _unlink_name(name: str) -> None:
    """Unlink a segment by name without touching the tracker (the
    ``SharedMemory.unlink`` method would double-unregister)."""
    _created_names.discard(name)
    if _posixshmem is None:  # pragma: no cover - non-POSIX fallback
        return
    try:
        _posixshmem.shm_unlink("/" + name)
    except (FileNotFoundError, OSError):
        pass


#: Segment names THIS process created and has not yet unlinked. The
#: atexit reap below is the last line of the lifecycle-hygiene defence:
#: a process that dies by unhandled exception never reaches
#: ``ShmNet.finalize``, and with the resource tracker opted out
#: (:func:`_untrack`) nothing else would unlink its rings. atexit does
#: not run under ``os._exit``/SIGKILL — those cases are covered by the
#: survivor/rejoin reaps (``drop_connection``/``finalize``/
#: ``_OutRing.create``'s FileExistsError path). GIL-atomic set ops;
#: no lock needed for add/discard of interned names.
_created_names: set = set()


def _atexit_reap() -> None:  # pragma: no cover - exercised in tests
    for name in list(_created_names):
        _unlink_name(name)


atexit.register(_atexit_reap)


#: Mappings that could not unmap because a Blob still views them (a
#: consumer kept a zero-copy view past transport teardown). Parking
#: the SharedMemory object keeps the pages mapped, so the view stays
#: valid forever instead of faulting — the memory-safety half of the
#: "blob outlives the segment" contract. Bounded in practice by how
#: many rings a process tears down while holding live views.
_graveyard: List = []


def _pay_off(nslots: int) -> int:
    off = _CTRL_OFF + nslots * _SLOT_STRIDE
    return (off + _ALIGN - 1) & ~(_ALIGN - 1)


class _SlotLease:
    """Slot ownership token riding the Blobs cut from one in-place
    frame — the shared-segment twin of ``buffer_pool.FrameLease``.

    A ``memoryview.release()`` probe cannot prove liveness here: numpy
    acquires the buffer through its *own* internal memoryview, so
    releasing the parsed body never raises even while Blob arrays are
    alive. Instead the lease weak-tracks the numpy arrays backing the
    frame's Blobs (:meth:`watch`, armed by ``consume`` right after
    ``_deserialize_frame``). Every user-held view derives from one of
    those arrays and pins it through its ``base`` chain, so a dead
    weakref set proves no export survives. Release with a survivor
    parks the slot (the ring service re-probes) instead of freeing it, so a
    long-lived Blob never aliases a recycled slot."""

    __slots__ = ("_ring", "_slot", "_watch")

    def __init__(self, ring: "_InRing", slot: int):
        self._ring = ring
        self._slot = slot
        self._watch: Tuple = ()

    def watch(self, arrays) -> None:
        """Arm the liveness probe over the frame's backing arrays."""
        self._watch = tuple(weakref.ref(a) for a in arrays)

    def exports_alive(self) -> bool:
        return any(r() is not None for r in self._watch)

    def release(self) -> None:
        ring, self._ring = self._ring, None
        if ring is None:
            return  # idempotent
        if self.exports_alive():
            # A Blob array (or a user view pinning it) is still alive:
            # the slot must not recycle under it. Park; the ring service
            # frees it once the last weakref clears.
            ring._park(self._slot, self)
            return
        self._watch = ()
        ring._free_inplace(self._slot)

    def __del__(self):
        self.release()


class _OutRing:
    """The sender's half of one directed ring: created on the writer
    thread at first ring send, unlinked by the creator on retire."""

    def __init__(self, name: str, shm, nslots: int, slot_bytes: int,
                 nonce: int):
        self.name = name
        self.nonce = nonce
        self._shm = shm
        self._nslots = nslots
        self._slot_bytes = slot_bytes
        pay = _pay_off(nslots)
        self._pay = [shm.buf[pay + i * slot_bytes:
                             pay + (i + 1) * slot_bytes]
                     for i in range(nslots)]
        self._head = 0  # absolute frame/chunk seq (writer-thread only)
        self._scan = 0  # round-robin slot-scan start (writer-thread only)
        # Closed flag: flipped by retire/finalize (any thread), polled
        # by the writer inside _acquire_slot. A plain bool — one racy
        # read at worst delays the PeerLostError by one spin iteration.
        self._closed = False

    @classmethod
    def create(cls, token: int, src: int, dst: int) -> "_OutRing":
        nslots = max(2, int(get_flag("shm_ring_slots")))
        slot_bytes = max(4096, int(get_flag("shm_slot_kb")) << 10)
        name = _seg_name(token, src, dst)
        size = _pay_off(nslots) + nslots * slot_bytes
        try:
            shm = shared_memory.SharedMemory(name=name, create=True,
                                             size=size)
        except FileExistsError:
            # Stale leftover from a SIGKILL'd predecessor of this rank:
            # reap it and claim the name (the rejoin path). Receivers
            # match segments by announced nonce, never by name alone.
            _unlink_name(name)
            shm = shared_memory.SharedMemory(name=name, create=True,
                                             size=size)
        _untrack(shm)
        _created_names.add(name)  # atexit reap if we die before destroy
        # A fresh POSIX segment is zero-filled: every slot starts FREE.
        nonce = int.from_bytes(os.urandom(8), "little") >> 1
        _RING_HDR.pack_into(shm.buf, 0, _RING_MAGIC, _RING_VERSION,
                            nslots, slot_bytes, nonce)
        return cls(name, shm, nslots, slot_bytes, nonce)

    def _acquire_slot(self) -> int:
        """Claim ANY free slot, round-robin preferred — slots do NOT
        recycle in FIFO order: a slot pinned by a consumer-held frame
        is skipped, not waited on (the ``seq`` stamp in the metadata
        carries delivery order, and the consumer's pin valve bounds
        pins to half the ring, so a FREE slot always reappears). When
        every slot is busy this blocks — the bounded-backpressure half
        of the no-deadlock contract: a slow reader stalls this writer
        thread (never a caller; callers are already capped by
        -send_queue_mb in submit), with a once-a-second log and a
        typed PeerLostError if the ring closes under the wait (peer
        declared dead)."""
        buf = self._shm.buf
        spins = 0
        waited = False
        next_warn = 0.0
        while True:
            if self._closed:
                raise PeerLostError(
                    f"shm ring {self.name}: peer ring closed while "
                    f"waiting for a free slot")
            for probe in range(self._nslots):
                slot = (self._scan + probe) % self._nslots
                off = _CTRL_OFF + slot * _SLOT_STRIDE
                (state,) = _SLOT_STATE.unpack_from(buf, off)
                if state == _FREE:
                    self._scan = (slot + 1) % self._nslots
                    return slot
            if not waited:
                waited = True
                count("SHM_RING_FULL_WAITS")
                next_warn = time.monotonic() + 1.0
            elif time.monotonic() >= next_warn:
                next_warn = time.monotonic() + 1.0
                log.info("shm ring %s full: backpressure on a slow "
                         "reader (%d slots x %d KB)", self.name,
                         self._nslots, self._slot_bytes >> 10)
            spins += 1
            if spins < 20:
                time.sleep(0)  # reader is usually one GIL slice away
            else:
                time.sleep(min(0.00005 * spins, 0.001))

    def write_frame(self, views: List[memoryview], nbytes: int) -> None:
        """Copy one serialized frame into ring slots — THE one copy of
        the shm data path. ``views`` is the ``serialize_views`` list;
        the wire length prefix is dropped (slot metadata carries
        sizes), so the slot body is exactly the TCP frame body and
        ``tcp._deserialize_frame`` parses it unchanged. Frames larger
        than one slot stream as CONT-chained chunks; the reader frees
        chunk slots as it copies them out, so even a frame larger than
        the whole ring flows."""
        total = nbytes - _LEN.size
        slot_bytes = self._slot_bytes
        nchunks = max(1, -(-total // slot_bytes))
        flat: List[memoryview] = []
        head = views[0][_LEN.size:]
        if head.nbytes:
            flat.append(head)
        for v in views[1:]:
            if not (v.format == "B" and v.ndim == 1):
                v = v.cast("B")
            flat.append(v)
        buf = self._shm.buf
        vi = 0
        vo = 0
        for chunk in range(nchunks):
            slot = self._acquire_slot()
            off = _CTRL_OFF + slot * _SLOT_STRIDE
            pay = self._pay[slot]
            room = min(slot_bytes, total - chunk * slot_bytes)
            woff = 0
            while woff < room:
                v = flat[vi]
                take = min(room - woff, v.nbytes - vo)
                pay[woff:woff + take] = v[vo:vo + take]
                woff += take
                vo += take
                if vo == v.nbytes:
                    vi += 1
                    vo = 0
            flags = _F_CONT if chunk < nchunks - 1 else 0
            # Metadata first, READY last: the consumer's load of READY
            # is its license to read the metadata and the payload.
            _SLOT_META.pack_into(buf, off + 4, flags, room, total,
                                 self._head)
            _SLOT_STATE.pack_into(buf, off, _READY)
            self._head += 1
        if nchunks > 1:
            count("SHM_CHUNKED_FRAMES")
        count("SHM_FRAMES")
        count("SHM_BYTES", total)

    def request_close(self) -> None:
        self._closed = True

    def destroy(self, unmap: bool = True) -> None:
        """Unlink the segment (creator's duty) and drop the mapping.
        ``unmap=False`` when the writer thread could still be touching
        the buffer (failed join): the mapping parks on the graveyard
        and the fields stay intact so a straggling write faults
        nowhere."""
        self._closed = True
        _unlink_name(self.name)
        if not unmap:
            _graveyard.append(self._shm)
            return
        shm, self._shm = self._shm, None
        if shm is None:
            return
        self._pay = None
        try:
            shm.close()
        except BufferError:  # pragma: no cover - sender keeps no exports
            _graveyard.append(shm)


class _InRing:
    """The receiver's half: attached by the ring service when the announce
    arrives, consumed in place, closed (never unlinked — the creator
    owns the name) on retire."""

    def __init__(self, name: str, shm, nslots: int, slot_bytes: int,
                 nonce: int):
        self.name = name
        self.nonce = nonce
        self._shm = shm
        self._nslots = nslots
        self._slot_bytes = slot_bytes
        pay = _pay_off(nslots)
        self._pay = [shm.buf[pay + i * slot_bytes:
                             pay + (i + 1) * slot_bytes]
                     for i in range(nslots)]
        self._tail = 0  # next slot to consume (loop-thread only)
        self._lock = named_lock(f"shm.in[{name}]")
        self._closed = False  # guarded_by: _lock
        self._parked: List[Tuple[int, "_SlotLease"]] = []  # guarded_by: _lock
        self._inplace = 0  # outstanding in-place leases; guarded_by: _lock
        self._chunk = None  # chunked-frame assembly lease (loop only)
        self._chunk_off = 0

    @classmethod
    def attach(cls, name: str, nonce: int) -> Optional["_InRing"]:
        """Attach by name, validating magic/version/nonce — None on any
        mismatch (caller retries: the announce always postdates the
        create, so a miss is a dead peer or a superseded segment)."""
        try:
            shm = shared_memory.SharedMemory(name=name, create=False)
        except (FileNotFoundError, ValueError, OSError):
            return None
        _untrack(shm)
        if shm.size < _RING_HDR.size:
            shm.close()
            return None
        magic, version, nslots, slot_bytes, seg_nonce = \
            _RING_HDR.unpack_from(shm.buf, 0)
        if (magic != _RING_MAGIC or version != _RING_VERSION
                or seg_nonce != nonce or nslots < 1
                or shm.size < _pay_off(nslots) + nslots * slot_bytes):
            shm.close()
            return None
        return cls(name, shm, nslots, slot_bytes, nonce)

    def consume(self, pool, deliver, budget: int = 32) -> int:
        """Drain up to ``budget`` READY frames, delivering parsed
        Messages through ``deliver`` (the inner TcpNet inbox — one
        queue keeps blocking recv and per-src FIFO intact). Single-slot
        frames parse IN PLACE: the Blob views alias the slot and a
        _SlotLease holds it READY until they die. Chunked frames copy
        out into a pooled lease (SHM_BYTES_COPIED).

        Pinned-slot pressure valve: once live consumer-held frames pin
        half the ring (a stashing consumer — the allreduce engine's
        out-of-order stash is the canonical case — or a slow actor),
        further frames COPY out through the pool instead of parsing in
        place (SHM_PIN_COPIES). Copied slots free immediately, so the
        writer always makes progress — without this, a consumer that
        stashes ``nslots`` undelivered frames pins every slot and
        deadlocks the pair."""
        done = 0
        buf = self._shm.buf
        while done < budget:
            # The writer claims ANY free slot (_acquire_slot), so the
            # next frame in delivery order — seq == _tail — can sit in
            # any slot: scan for it, starting at the FIFO guess (the
            # hit on the first probe whenever nothing is pinned). A
            # READY slot with an older seq is a still-pinned in-place
            # frame; skip it.
            guess = self._tail % self._nslots
            slot = None
            for probe in range(self._nslots):
                cand = (guess + probe) % self._nslots
                off = _CTRL_OFF + cand * _SLOT_STRIDE
                (state,) = _SLOT_STATE.unpack_from(buf, off)
                if state != _READY:
                    continue
                flags, nbytes, total, seq = _SLOT_META.unpack_from(
                    buf, off + 4)
                if seq == self._tail:
                    slot = cand
                    break
            if slot is None:
                break
            self._tail += 1
            if (flags & _F_CONT) or self._chunk is not None:
                # Oversize frame: reassemble through the receive pool.
                if self._chunk is None:
                    self._chunk = pool.lease(total)
                    self._chunk_off = 0
                lease = self._chunk
                view = lease.view(total)
                view[self._chunk_off:self._chunk_off + nbytes] = \
                    self._pay[slot][:nbytes]
                view = None
                count("SHM_BYTES_COPIED", nbytes)
                self._chunk_off += nbytes
                self._free(slot)  # copied out: recycle immediately
                if not (flags & _F_CONT):
                    self._chunk = None
                    with monitor("shm_recv"):
                        msg = _deserialize_frame(lease.view(total), lease)
                    deliver(msg)
                    done += 1
                continue
            with self._lock:
                crowded = self._inplace >= max(1, self._nslots // 2)
                if not crowded:
                    self._inplace += 1
            if crowded:
                # Pressure valve: copy out so the slot frees now and
                # the writer keeps flowing (docstring above).
                lease = pool.lease(nbytes)
                view = lease.view(nbytes)
                view[:] = self._pay[slot][:nbytes]
                view = None
                count("SHM_PIN_COPIES")
                count("SHM_BYTES_COPIED", nbytes)
                self._free(slot)
                with monitor("shm_recv"):
                    msg = _deserialize_frame(lease.view(nbytes), lease)
                lease = None
                deliver(msg)
                done += 1
                continue
            # In-place path: _deserialize_frame cuts numpy views
            # straight into the slot body; the lease weak-tracks their
            # backing arrays, and the slot stays READY until every one
            # (and every user view pinning one) is dead.
            body = self._pay[slot][:nbytes]
            lease = _SlotLease(self, slot)
            with monitor("shm_recv"):
                msg = _deserialize_frame(body, lease)
            lease.watch([b._data for b in msg.data])
            body = None
            lease = None
            deliver(msg)
            done += 1
        return done

    def _free(self, slot: int) -> None:
        with self._lock:
            if self._closed:
                return
            _SLOT_STATE.pack_into(self._shm.buf,
                                  _CTRL_OFF + slot * _SLOT_STRIDE, _FREE)

    def _free_inplace(self, slot: int) -> None:
        """Free from a dying _SlotLease: also retires its pinned-slot
        count (parked slots stay counted — still pinned)."""
        with self._lock:
            self._inplace -= 1
            if self._closed:
                return
            _SLOT_STATE.pack_into(self._shm.buf,
                                  _CTRL_OFF + slot * _SLOT_STRIDE, _FREE)

    def _park(self, slot: int, lease: "_SlotLease") -> None:
        count("SHM_SLOT_PARKED")
        with self._lock:
            if self._closed:
                return  # retire already moved the mapping to safety
            self._parked.append((slot, lease))

    def reprobe_parked(self) -> None:
        """Poller duty: retry parked slots — once the last Blob array
        dies its weakref clears and the slot frees."""
        with self._lock:
            if self._closed or not self._parked:
                return
            still: List[Tuple[int, "_SlotLease"]] = []
            for slot, lease in self._parked:
                if lease.exports_alive():
                    still.append((slot, lease))
                    continue
                self._inplace -= 1
                _SLOT_STATE.pack_into(self._shm.buf,
                                      _CTRL_OFF + slot * _SLOT_STRIDE,
                                      _FREE)
            self._parked = still

    def retire(self) -> None:
        """Close the mapping (the creator unlinks the name). A live
        Blob view makes ``close`` raise BufferError — the mapping then
        parks on the graveyard so the view stays valid forever."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
            self._pay = None
            self._parked = []
            chunk, self._chunk = self._chunk, None
            shm, self._shm = self._shm, None
        if chunk is not None:
            chunk.release()
        try:
            shm.close()
        except BufferError:
            _graveyard.append(shm)


class _ShmPeerWriter:
    """Per-destination ring writer thread + bounded frame queue (same
    queue discipline, -send_queue_mb backpressure, and parked-error
    contract as the TCP transport's ``_Peer`` queues — but a dedicated
    WRITER thread, because a full ring legitimately BLOCKS the producer
    in ``_acquire_slot``'s spin, which the event loop must never do).
    The ring segment is created lazily on THIS thread at the first
    frame, and the TCP-borne announce goes out just before it — so
    ring frames can never overtake the pre-ring TCP stream. After each
    frame the writer rings the receiver's doorbell FIFO, which wakes
    the peer's event loop out of ``selector.select`` — no busy-polling
    consumer on the other side."""

    def __init__(self, net: "ShmNet", dst: int):
        self._net = net
        self._dst = dst
        self._cond = named_condition(f"shm[r{net.rank}].writer[d{dst}]")
        self._frames: collections.deque = collections.deque()  # guarded_by: _cond
        self._queued_bytes = 0  # guarded_by: _cond
        self._writing = False  # guarded_by: _cond
        self._closed = False  # guarded_by: _cond
        self.error: Optional[BaseException] = None  # guarded_by: _cond
        self._ring: Optional[_OutRing] = None  # writer thread; read post-join
        self._thread = thread_roles.spawn(
            thread_roles.WRITER, target=self._main,
            name=f"mv-shm-write-r{net.rank}-d{dst}")

    def submit(self, views: List[memoryview], nbytes: int) -> None:
        cap = max(1, int(get_flag("send_queue_mb"))) << 20
        with self._cond:
            while (self._queued_bytes >= cap and self.error is None
                   and not self._closed):
                self._cond.wait(timeout=1.0)
            if self.error is not None:
                raise PeerLostError(
                    f"send to rank {self._dst} failed: peer shm ring "
                    f"is dead ({self.error})") from self.error
            if self._closed:
                raise RuntimeError("ShmNet finalized")
            self._frames.append((views, nbytes))
            self._queued_bytes += nbytes
            self._cond.notify_all()

    def flush(self, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while (self._frames or self._writing) and self.error is None:
                remaining = None if deadline is None \
                    else deadline - time.monotonic()
                if remaining is not None and remaining <= 0:
                    raise RuntimeError(
                        f"flush_sends: {self._queued_bytes} bytes to rank "
                        f"{self._dst} not drained within {timeout}s")
                self._cond.wait(timeout=1.0 if remaining is None
                                else min(remaining, 1.0))
            if self.error is not None:
                raise PeerLostError(
                    f"send to rank {self._dst} failed: peer shm ring "
                    f"is dead ({self.error})") from self.error

    @property
    def queued_bytes(self) -> int:
        with self._cond:
            return self._queued_bytes

    def retire(self, timeout: float = 2.0) -> None:
        """Stop accepting frames, unblock a ring-full wait, join, and
        destroy the out-ring (unlink; unmap only if the thread really
        finished — else the mapping parks on the graveyard)."""
        with self._cond:
            self._closed = True
            self._cond.notify_all()
        ring = self._ring
        if ring is not None:
            ring.request_close()
        if self._thread is not threading.current_thread():
            self._thread.join(timeout=timeout)
        ring = self._ring
        if ring is not None:
            ring.destroy(unmap=not self._thread.is_alive())

    def _main(self) -> None:
        while True:
            with self._cond:
                while not self._frames and not self._closed:
                    self._cond.wait()
                if not self._frames:  # closed and drained
                    return
                views, nbytes = self._frames.popleft()
                self._writing = True
            try:
                ring = self._ring
                if ring is None:
                    ring = self._ring = self._net._open_ring(self._dst)
                with monitor("shm_send"):
                    ring.write_frame(views, nbytes)
                self._net._count_sent(nbytes)
                self._net._ding(self._dst)
            except BaseException as exc:  # noqa: BLE001 - no caller to
                # raise into: park the error, wake waiters — submit()
                # and flush() turn it into PeerLostError.
                with self._cond:
                    self.error = exc
                    self._frames.clear()
                    self._queued_bytes = 0
                    self._writing = False
                    self._cond.notify_all()
                return
            # Drop the views BEFORE parking: they alias payload buffers
            # (possibly a pooled frame being forwarded) and an idle
            # writer must not pin them until the next send.
            views = None
            with self._cond:
                self._queued_bytes -= nbytes
                self._writing = False
                self._cond.notify_all()


class _ShmBell:
    """Receiver-side doorbell: a named FIFO in /dev/shm that senders
    write one byte to after stamping a ring slot READY. Registered on
    the inner TcpNet's event loop, so a co-located peer's frame wakes
    this rank's loop out of ``selector.select`` — the ring consumer
    went from a busy-polling BACKGROUND thread to an fd on the same
    selector every socket lives on. The payload is meaningless; the
    readiness edge is the signal, and a full FIFO just means a ding is
    already pending."""

    def __init__(self, net: "ShmNet", name: str):
        self._net = net
        self.name = name
        path = "/dev/shm/" + name
        try:
            os.mkfifo(path)
        except FileExistsError:
            # Stale leftover from a SIGKILL'd predecessor of this rank
            # (the rejoin path): reap it and claim the name.
            _unlink_name(name)
            os.mkfifo(path)
        _created_names.add(name)  # atexit reap if we die before finalize
        # O_RDWR (not O_RDONLY) on our own FIFO: the Linux trick that
        # keeps one writer reference alive forever, so a sender closing
        # its end can never leave the read side at EOF (a persistently
        # readable fd would spin the selector).
        self.fd = os.open(path, os.O_RDWR | os.O_NONBLOCK)

    def on_misc_io(self, mask: int) -> None:
        while True:
            try:
                chunk = os.read(self.fd, 4096)
            except (BlockingIOError, OSError):
                break
            if not chunk:
                break
        self._net._ring_service()

    def close(self) -> None:
        try:
            os.close(self.fd)
        except OSError:
            pass


class ShmNet(NetInterface):
    """A TcpNet wrapped with per-peer shared-memory rings for
    co-located ranks. Remote and non-shm peers, bootstrap, control
    handshakes and peer-death detection all stay on the inner TCP
    mesh; only negotiated same-host data frames switch transports."""

    def __init__(self, tcp: TcpNet):
        self._tcp = tcp
        self._loop = tcp._loop  # ring service rides the TCP event loop
        rank = tcp.rank
        self._lifecycle = named_lock(f"shm[r{rank}].lifecycle")
        self._stats_lock = named_lock(f"shm[r{rank}].stats")
        self._writers: Dict[int, _ShmPeerWriter] = {}  # guarded_by: _lifecycle
        self._closed = False  # guarded_by: _lifecycle
        self._token: Optional[int] = None  # guarded_by: _lifecycle
        self._shm_bytes = 0  # guarded_by: _stats_lock
        # Negotiated co-located peer set (static after enable_shm) and
        # the live ring-send target set. GIL-atomic set/dict ops by
        # design: the one race — a send routed to TCP right as a ring
        # peer (re)appears, or to a ring right as a peer dies — is
        # benign either way (TCP always works; a dead ring raises the
        # same PeerLostError the TCP path would).
        self._shm_peers: frozenset = frozenset()
        self._ring_peers: set = set()
        self._announced: Dict[int, Tuple[int, int]] = {}  # src -> (nonce, token)
        self._attached: Dict[int, _InRing] = {}  # loop-thread only
        self._dead: set = set()  # srcs whose in-ring the service must retire
        self._reaped: Dict[int, str] = {}  # dead peers' segment names
        self._reaped_bells: Dict[int, str] = {}  # dead peers' bell names
        # Doorbell state. _bell and the service bookkeeping below are
        # loop-thread only; _bell_fds maps dst -> cached O_WRONLY fd of
        # the PEER's bell, touched by that dst's writer thread (and
        # closed by drop_connection only after the writer is joined).
        self._bell: Optional[_ShmBell] = None
        self._bell_fds: Dict[int, int] = {}
        self._attach_retry: Dict[int, float] = {}  # loop-thread only
        self._svc_stopped = False  # loop-thread only
        self._timer_armed = False  # loop-thread only
        self._idle_delay = 0.001  # loop-thread only

    # -- NetInterface delegation --
    @property
    def rank(self) -> int:
        return self._tcp.rank

    @property
    def size(self) -> int:
        return self._tcp.size

    @property
    def bytes_sent(self) -> int:
        with self._stats_lock:
            mine = self._shm_bytes
        return mine + self._tcp.bytes_sent

    def _count_sent(self, nbytes: int) -> None:
        with self._stats_lock:
            self._shm_bytes += nbytes

    @property
    def on_peer_lost(self):
        # The inner TCP readers are the death detector; the hook lives
        # there so dirty closes fire it directly.
        return self._tcp.on_peer_lost

    @on_peer_lost.setter
    def on_peer_lost(self, hook) -> None:
        self._tcp.on_peer_lost = hook

    # -- negotiation --
    def enable_shm(self, token: int, peers) -> None:
        """Zoo callback after the register reply: ``peers`` is the set
        of same-host ranks that advertised CAP_SHM; ``token`` is the
        controller-chosen cluster constant naming every segment.
        Configures the SEND side only — receiving is announce-driven
        and needs no state here (a later-registering rank consumes the
        controller's ring before its own negotiation completes)."""
        mine = frozenset(int(p) for p in peers if int(p) != self.rank)
        with self._lifecycle:
            if self._closed:
                return
            self._token = int(token)
            self._shm_peers = mine
        for p in mine:
            self._ring_peers.add(p)
        if mine:
            # Kick the ring service so our doorbell FIFO exists before
            # the first peer ding (a miss is covered by the fallback
            # timer, but the bell makes delivery latency selector-fast
            # from frame one).
            self._loop.call_soon(self)
            log.info("shm transport enabled: rank %d ring-sends to %s "
                     "(token %08x)", self.rank, sorted(mine),
                     int(token) & 0xFFFFFFFF)

    def is_shm_peer(self, dst: int) -> bool:
        """Is traffic toward ``dst`` currently ring-routed? (The
        communicator skips the wire codec below the socket.)"""
        return dst in self._ring_peers

    # -- send path --
    def send(self, msg: Message) -> int:
        dst = msg.dst
        if dst not in self._ring_peers:
            return self._tcp.send(msg)
        writer = self._writer(dst)
        with monitor("tcp_serialize"):
            views, nbytes = serialize_views(msg)
        # One queue per destination keeps sync frames FIFO with queued
        # async ones; the flush makes this blocking like TcpNet.send.
        writer.submit(views, nbytes)
        writer.flush(timeout=60.0)
        return nbytes

    def send_async(self, msg: Message) -> int:
        dst = msg.dst
        if dst not in self._ring_peers:
            return self._tcp.send_async(msg)
        # Chaos harness parity (-chaos_frames): ring sends pass the
        # same fault filter as TCP ones — the inner send_async applies
        # it for delegated frames, so filter only on the ring branch.
        faulted = chaos.filter_frames(msg)
        if faulted is not None:
            total = 0
            for m in faulted:
                total += self._submit_ring(m)
            return total
        return self._submit_ring(msg)

    def _submit_ring(self, msg: Message) -> int:
        dst = msg.dst
        if dst not in self._ring_peers:  # a held chaos frame may outlive
            return self._tcp.send_async(msg)  # the peer's ring
        with monitor("tcp_serialize"):
            views, nbytes = serialize_views(msg)
        self._writer(dst).submit(views, nbytes)
        return nbytes

    def _writer(self, dst: int) -> _ShmPeerWriter:
        writer = self._writers.get(dst)  # mvlint: ignore[guarded-by]
        if writer is None:
            with self._lifecycle:
                if self._closed:
                    raise RuntimeError("ShmNet finalized")
                writer = self._writers.get(dst)
                if writer is None:
                    writer = self._writers[dst] = _ShmPeerWriter(self, dst)
        return writer

    def _open_ring(self, dst: int) -> _OutRing:
        """Writer-thread duty: create the outbound segment and send
        the TCP-borne announce. ``TcpNet.send`` flushes the
        destination's TCP writer first, so the announce — and with it
        the transport switch — orders after every frame already queued
        toward ``dst`` over TCP."""
        with self._lifecycle:
            if self._closed:
                raise RuntimeError("ShmNet finalized")
            token = self._token
        if token is None:
            raise RuntimeError("shm ring send before negotiation")
        ring = _OutRing.create(token, self.rank, dst)
        try:
            ann = Message(src=self.rank, dst=dst,
                          msg_type=MsgType.Control_Shm_Announce)
            ann.push(Blob(np.array([ring.nonce, token], dtype=np.int64)))
            self._tcp.send(ann)
        except BaseException:
            ring.destroy(unmap=True)
            raise
        log.debug("shm ring %s created (%d -> %d)", ring.name,
                  self.rank, dst)
        return ring

    def flush_sends(self, dst: Optional[int] = None,
                    timeout: Optional[float] = None) -> None:
        with self._lifecycle:
            writers = [self._writers[dst]] if dst is not None \
                and dst in self._writers else \
                (list(self._writers.values()) if dst is None else [])
        for writer in writers:
            writer.flush(timeout)
        self._tcp.flush_sends(dst, timeout)

    def queue_depths(self) -> Dict[int, int]:
        """Outbound frames queued per destination, ring and TCP paths
        combined (the same introspection port TcpNet exposes)."""
        with self._lifecycle:
            writers = list(self._writers.items())
        depths = self._tcp.queue_depths()
        for dst, writer in writers:
            with writer._cond:
                depths[dst] = depths.get(dst, 0) + len(writer._frames) \
                    + (1 if writer._writing else 0)
        return depths

    # -- receive path --
    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        deadline = None if timeout is None \
            else time.monotonic() + timeout
        while True:
            remaining = None if deadline is None \
                else max(0.0, deadline - time.monotonic())
            msg = self._tcp.recv(timeout=remaining)
            if msg is None:
                return None
            if msg.type_int == int(MsgType.Control_Shm_Announce):
                # Transport-internal: consumed here, below the
                # communicator — actor routing never sees it.
                self._on_announce(msg)
                continue
            return msg

    def deliver(self, msg: Message) -> None:
        """Poller delivery port (LocalFabric precedent): ring frames
        join the same inbox TCP frames land in, preserving blocking
        recv and per-source FIFO."""
        self._tcp.deliver(msg)

    def _on_announce(self, msg: Message) -> None:
        src = msg.src
        vals = msg.data[0].as_array(np.int64)
        nonce, token = int(vals[0]), int(vals[1])
        self._announced[src] = (nonce, token)
        # The announce proves the peer's send side is enabled — after a
        # rejoin this is what re-adds it to OUR ring-send set (the
        # negotiated set is static; membership in it is the consent).
        if src in self._shm_peers:
            self._ring_peers.add(src)
        self._reaped.pop(src, None)  # it rejoined: nothing to reap
        self._loop.call_soon(self)  # service attaches the new ring

    # -- ring service (event-loop thread) --
    def on_misc_timer(self) -> None:
        """Loop-job entry: announce kicks and enable_shm land here via
        call_soon(self)."""
        self._ring_service()

    def _timer_fire(self) -> None:
        self._timer_armed = False
        self._ring_service()

    def _ring_service(self) -> None:
        """One service pass on the event loop — the old poller's loop
        body: attach announced rings (with per-src backoff), retire
        dead ones, consume READY frames in place, re-probe parked
        slots. Normally woken by the doorbell FIFO; an adaptive
        fallback timer (1ms busy, decaying to 50ms idle) covers what no
        doorbell announces — attach retries, parked-slot lease deaths,
        and dings lost before the bell existed."""
        if self._svc_stopped:
            return
        busy = False
        self._ensure_bell()
        now = time.monotonic()
        # Attach newly announced (or re-announced after rejoin) rings.
        # The announce postdates the create, so a miss means a dead
        # peer or a superseded segment — retry with backoff until the
        # announce table says otherwise.
        for src, (nonce, token) in list(self._announced.items()):
            ring = self._attached.get(src)
            if ring is not None and ring.nonce == nonce:
                continue
            if ring is not None:  # peer rebuilt its segment
                self._attached.pop(src, None)
                ring.retire()
            if now < self._attach_retry.get(src, 0.0):
                continue
            new = _InRing.attach(_seg_name(token, src, self.rank), nonce)
            if new is None:
                self._attach_retry[src] = now + 0.02
                continue
            self._attach_retry.pop(src, None)
            self._attached[src] = new
            busy = True
        while self._dead:
            src = self._dead.pop()
            self._announced.pop(src, None)
            ring = self._attached.pop(src, None)
            if ring is not None:
                ring.retire()
        for src, ring in list(self._attached.items()):
            if ring.consume(self._tcp._pool, self._tcp.deliver):
                busy = True
            ring.reprobe_parked()
        self._idle_delay = 0.001 if busy \
            else min(self._idle_delay * 2, 0.05)
        if not self._timer_armed and (self._announced or self._attached
                                      or self._dead):
            self._timer_armed = True
            self._loop.call_later(self._idle_delay, self._timer_fire)

    def _ensure_bell(self) -> None:
        if self._bell is not None:
            return
        with self._lifecycle:
            token = self._token
        if token is None:
            # Receive side enabled by an inbound announce alone (our
            # own enable_shm still in flight): any announced token IS
            # the cluster token.
            for _nonce, t in self._announced.values():
                token = t
                break
        if token is None:
            return
        try:
            bell = _ShmBell(self, _bell_name(token, self.rank))
        except OSError:  # pragma: no cover - no FIFO support in
            return  # /dev/shm: the fallback timer alone serves rings
        self._bell = bell
        self._loop.register(bell.fd, selectors.EVENT_READ, bell)

    def _ding(self, dst: int) -> None:
        """Writer-thread duty, right after a frame's slots flip READY:
        one byte into the receiver's doorbell FIFO so its event loop
        wakes now instead of at the next fallback tick. Every failure
        mode is quietly survivable — the receiver's timer covers a
        missing or torn-down bell, and a full FIFO means a ding is
        already pending."""
        fd = self._bell_fds.get(dst)
        if fd is None:
            with self._lifecycle:
                token = self._token
            if token is None:
                return
            path = "/dev/shm/" + _bell_name(token, dst)
            try:
                fd = os.open(path, os.O_WRONLY | os.O_NONBLOCK)
            except OSError:
                return  # bell not up (yet): ENXIO/ENOENT
            self._bell_fds[dst] = fd
        try:
            os.write(fd, b"\0")
        except BlockingIOError:
            pass  # FIFO full: the pending ding covers this frame too
        except OSError:
            # Receiver closed its bell (teardown or rejoin): drop the
            # cached fd so the next frame re-opens the new one.
            stale = self._bell_fds.pop(dst, None)
            if stale is not None:
                try:
                    os.close(stale)
                except OSError:
                    pass

    def interrupt_recv(self) -> None:
        self._tcp.interrupt_recv()

    # -- peer death / lifecycle --
    def drop_connection(self, dst: int) -> None:
        """Peer declared dead: retire its ring state on both sides and
        fall back to TCP-only toward it until a fresh announce proves
        it rejoined. The dead peer's own inbound segment is NOT
        unlinked here — a rejoining replacement recreates the same
        name, and racing its create is worse than deferring the reap
        to finalize (only peers that never rejoin are reaped then)."""
        self._ring_peers.discard(dst)
        ann = self._announced.pop(dst, None)
        with self._lifecycle:
            writer = self._writers.pop(dst, None)
        if writer is not None:
            writer.retire(timeout=1.0)
        # The writer is joined: its cached doorbell fd toward the dead
        # peer is safe to close here, and the dead peer's bell name is
        # recorded for the finalize reap (it never unlinks here — a
        # rejoining replacement recreates the same name).
        fd = self._bell_fds.pop(dst, None)
        if fd is not None:
            try:
                os.close(fd)
            except OSError:
                pass
        if ann is not None:
            self._reaped[dst] = _seg_name(ann[1], dst, self.rank)
            self._reaped_bells[dst] = _bell_name(ann[1], dst)
        self._dead.add(dst)  # the ring service retires the in-ring
        self._loop.call_soon(self)
        self._tcp.drop_connection(dst)

    def finalize(self) -> None:
        with self._lifecycle:
            already = self._closed
            self._closed = True
            writers, self._writers = dict(self._writers), {}
        if already:
            self._tcp.finalize()  # inner finalize is idempotent too
            return
        for writer in writers.values():
            pending = writer.queued_bytes
            drain = 2.0 + pending / (4 << 20)
            try:
                writer.flush(timeout=drain)
            except (RuntimeError, PeerLostError):
                pass
            writer.retire()
        # Writers are joined: the cached doorbell fds are dead weight.
        for fd in list(self._bell_fds.values()):
            try:
                os.close(fd)
            except OSError:
                pass
        self._bell_fds.clear()
        # Retire the attached rings and our own bell ON the loop (they
        # are loop-thread state; the inner TcpNet is not finalized yet,
        # so the loop is still serving).
        self._loop.run_sync(self._teardown_rings, timeout=5.0)
        # Reap every inbound segment we know of — both the recorded
        # dead-peer names AND every announced name. A peer that died
        # without ever reaching drop_connection (the abort path raises
        # ClusterAborted straight into shutdown) left its out-segment
        # linked with nobody else to unlink it; a live peer's own
        # destroy turns our unlink into a handled FileNotFoundError
        # (whichever side unlinks first wins, the name is dead either
        # way, and unlink never invalidates an established mapping). A
        # leaked /dev/shm entry outliving the cluster is the one
        # failure mode the lifecycle-hygiene tests treat as fatal.
        # Dead peers' doorbell FIFOs are reaped the same way.
        for src, (nonce, token) in list(self._announced.items()):
            _unlink_name(_seg_name(token, src, self.rank))
            # The announcer's doorbell FIFO too: a SIGKILL'd peer (no
            # atexit) reaches finalize via the abort path, which never
            # calls drop_connection — without this the dead rank's
            # bell outlives the cluster. Unlinking a LIVE peer's bell
            # is as survivable as unlinking its segment: the owner
            # keeps its O_RDWR fd, cached sender fds stay valid, and
            # new opens fall back to the service timer.
            _unlink_name(_bell_name(token, src))
        self._announced.clear()
        for name in self._reaped.values():
            _unlink_name(name)
        self._reaped.clear()
        for name in self._reaped_bells.values():
            _unlink_name(name)
        self._reaped_bells.clear()
        self._tcp.finalize()

    def _teardown_rings(self) -> None:
        """Finalize, on the loop: stop the ring service, detach every
        in-ring (live Blob views park mappings on the graveyard), and
        retire the doorbell."""
        self._svc_stopped = True
        for ring in list(self._attached.values()):
            ring.retire()
        self._attached.clear()
        bell, self._bell = self._bell, None
        if bell is not None:
            try:
                self._loop.unregister(bell.fd)
            except (KeyError, ValueError):
                pass
            bell.close()
            _unlink_name(bell.name)

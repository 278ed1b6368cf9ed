"""Cross-process TCP message-stream transport.

TPU-native replacement for the reference's point-to-point backends — the
functional equivalent of its ZeroMQ DEALER mesh
(ref: include/multiverso/net/zmq_net.h:23-270) and of the MPI wrapper's
serialized send/recv (ref: include/multiverso/net/mpi_net.h:195-344),
implemented over plain TCP sockets so a multi-rank cluster needs no MPI
and no libzmq:

- every rank binds one listening socket and lazily opens one outbound
  connection per peer (full mesh, like the reference's per-peer DEALER
  sockets, ref: zmq_net.h:25-61);
- messages travel as length-prefixed frames: ``[total u64][header 10xi32]
  [nblobs u32][blob sizes u64 x n][blob bytes ...]`` — the same frame
  LAYOUT as the reference's MPI path (ref: mpi_net.h:289-317), but built
  zero-copy: the send side never joins the frame into one flat buffer
  (``serialize_views`` emits a small header buffer plus one view per
  blob payload, drained by ``socket.sendmsg`` vectored writes straight
  out of the Blobs' own memory), and the receive side leases a pooled
  buffer (``util/buffer_pool.py``), fills it with ``recv_into``, and
  cuts read-only Blob views directly from the frame. Device blobs still
  materialize to host bytes at the wire boundary;
- all socket I/O — accepts, nonblocking connects, frame reads, frame
  writes — multiplexes onto ONE ``selectors`` event-loop thread per
  endpoint (``_EventLoop``). Each destination is a ``_Peer`` state
  machine (CONNECTING → HANDSHAKE → READY → DRAINING → DEAD) with a
  bounded outbound frame queue (``-send_queue_mb`` backpressure, same
  contract the per-peer writer threads used to enforce); each inbound
  connection is a ``_Conn`` read state machine filling the same pooled
  lease buffers the old reader threads did. Transport thread count is
  O(1) in peer count, a dead peer costs retry timers instead of a
  blocked thread, and dead-peer detection unifies onto
  selector-observed EOF/ECONNRESET plus the heartbeat path;
- bootstrap is machine-file driven (one ``host[:port]`` per line, own rank
  found by local-address match or the ``-rank`` flag,
  ref: zmq_net.h:20-28,25-61) or app-driven via ``net_bind``/
  ``net_connect`` (``MV_NetBind``/``MV_NetConnect`` parity,
  ref: include/multiverso/multiverso.h:55-64, zmq_net.h:63-109).

On TPU this is the *control/table plane* across hosts (DCN); tensor traffic
inside a jitted step rides XLA collectives and never sees this layer.
"""

from __future__ import annotations

import collections
import errno
import heapq
import os
import selectors
import socket
import struct
import threading
import time
import traceback
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..core.blob import Blob
from ..core.message import HEADER_SIZE, Message
from ..util import chaos, log
from ..util.buffer_pool import BufferPool
from ..util.configure import (define_double, define_int, define_string,
                              get_flag)
from ..util.dashboard import count, monitor, samples
from ..util.lock_witness import named_condition, named_lock
from ..util.mt_queue import MtQueue
from ..util.net_util import local_addresses
from . import thread_roles
from .net import NetInterface, PeerLostError

define_string("machine_file", "", "path: one host[:port] per rank line")
define_int("port", 55555, "default TCP port when a machine-file line has none")
define_int("rank", -1, "explicit rank override for machine-file bootstrap")
define_int("send_queue_mb", 32,
           "per-peer async send queue cap (MB): send_async blocks "
           "(backpressure) once this many serialized bytes are in flight "
           "to one destination — the transport twin of the worker "
           "coalescer's 4MB flush cap")
define_double("connect_timeout_s", 30.0,
              "seconds to keep retrying an outbound connection to a "
              "peer that is not (yet) listening — covers both bootstrap "
              "races and, with the fault-tolerance retry path, the "
              "restart window of a crashed peer (a send toward a dead "
              "rank waits in connect-retry until the replacement "
              "process binds, then delivers). The retries are "
              "nonblocking timers on the event loop: an unreachable "
              "peer costs zero blocked threads")
_HDR = struct.Struct(f"<{HEADER_SIZE}i")
_LEN = struct.Struct("<Q")
_NBLOBS = struct.Struct("<I")

_RECV_INTERRUPT = object()

#: _Peer connection states (peer.state; NET_PEER_STATE[*] counts every
#: transition). CONNECTING covers both "not dialed yet" and the timer
#: wait between nonblocking connect retries; HANDSHAKE is a connect_ex
#: in flight (EINPROGRESS, waiting for writability); DRAINING is READY
#: with a goodbye frame queued behind the remaining traffic (finalize);
#: DEAD peers are retired from the peer table — the next send toward
#: that rank starts a fresh state machine.
_ST_CONNECTING = "CONNECTING"
_ST_HANDSHAKE = "HANDSHAKE"
_ST_READY = "READY"
_ST_DRAINING = "DRAINING"
_ST_DEAD = "DEAD"

#: connect_ex return codes that mean "in progress, wait for the
#: selector" rather than "failed".
_EX_PENDING = frozenset(
    {errno.EINPROGRESS, errno.EWOULDBLOCK, errno.EAGAIN, errno.EALREADY})


def _parse_endpoint(line: str, default_port: int) -> Tuple[str, int]:
    line = line.strip()
    if ":" in line and not line.startswith("["):  # host:port (IPv4/name)
        host, port = line.rsplit(":", 1)
        return host, int(port)
    return line, default_port


def _read_exact(sock: socket.socket, n: int) -> Optional[bytearray]:
    """Read exactly n bytes; None on orderly EOF. Returns the filled
    ``bytearray`` itself — a ``bytes(buf)`` copy here used to tax every
    inbound frame once for nothing (struct unpacks and numpy views read
    a bytearray directly)."""
    buf = bytearray(n)
    return buf if _recv_into_exact(sock, memoryview(buf)) else None


def _recv_into_exact(sock: socket.socket, view: memoryview) -> bool:
    """Fill ``view`` completely from the socket; False on orderly EOF.
    The zero-copy twin of ``_read_exact``: the caller owns the buffer
    (a pooled frame lease), so nothing is allocated here."""
    n = view.nbytes
    got = 0
    while got < n:
        k = sock.recv_into(view[got:], n - got)
        if k == 0:
            return False
        got += k
    return True


def serialize_views(msg: Message) -> Tuple[List[memoryview], int]:
    """Scatter-gather framer: the wire frame as ``(views, nbytes)``
    where ``views[0]`` is the length-prefix + header + blob-size table
    (the only bytes this function builds) and every following view
    reads straight through ``Blob.wire_views()`` into the payload's own
    memory — no per-blob ``tobytes``, no ``b"".join``, no prefix
    concat. Drained by ``sendmsg`` vectored writes; joining the views
    reproduces ``_serialize``'s frame byte for byte (golden-tested)."""
    views: List[memoryview] = [memoryview(b"")]  # head placeholder
    sizes: List[int] = []
    payload = 0
    for blob in msg.data:
        # Device payloads cross the wire as host bytes (the reference's
        # serialize step; ref: mpi_net.h:289-317). Codec-filtered blobs
        # (header slot CODEC_SLOT set by the communicator) are already
        # uint8 frames — possibly in scatter-gather parts — and pass
        # through unchanged.
        nbytes = 0
        for view in blob.wire_views():
            nbytes += view.nbytes
            if view.nbytes:  # zero-length views would stall sendmsg
                views.append(view)
        sizes.append(nbytes)
        payload += nbytes
    body = _HDR.size + _NBLOBS.size + _LEN.size * len(sizes) + payload
    head = bytearray(_LEN.size + _HDR.size + _NBLOBS.size
                     + _LEN.size * len(sizes))
    _LEN.pack_into(head, 0, body)
    _HDR.pack_into(head, _LEN.size, *[int(v) for v in msg.header])
    off = _LEN.size + _HDR.size
    _NBLOBS.pack_into(head, off, len(sizes))
    off += _NBLOBS.size
    for sz in sizes:
        _LEN.pack_into(head, off, sz)
        off += _LEN.size
    views[0] = memoryview(head)
    # Copy accounting (docs/MEMORY.md): only the framing bytes are
    # built here; payload bytes go to the wire without a host copy.
    count("WIRE_BYTES_COPIED", len(head))
    count("WIRE_PAYLOAD_BYTES", payload)
    return views, _LEN.size + body


#: Buffers per sendmsg call — conservatively under IOV_MAX (1024 on
#: Linux); a frame with more views loops.
_IOV_CAP = 64


def _sendmsg_all(sock: socket.socket, views: List[memoryview]) -> None:
    """Drain ``views`` through vectored writes, handling partial sends
    (sendmsg may stop mid-view under backpressure). Views must be
    non-empty (``serialize_views`` filters zero-length ones). Blocking
    -socket helper for out-of-loop senders (the shm announce path and
    tests); ``_Peer._drain`` is the nonblocking event-loop twin of this
    arithmetic."""
    i = 0
    off = 0
    n = len(views)
    while i < n:
        if off:
            batch = [views[i][off:]]
            batch.extend(views[i + 1:i + _IOV_CAP])
        else:
            batch = views[i:i + _IOV_CAP]
        sent = sock.sendmsg(batch)
        while i < n and sent:
            remaining = views[i].nbytes - off
            if sent >= remaining:
                sent -= remaining
                i += 1
                off = 0
            else:
                off += sent
                sent = 0


def _serialize(msg: Message) -> bytes:
    """Flat-buffer serializer: the golden reference that
    tests/test_zero_copy.py byte-compares ``serialize_views`` against;
    no transport path calls it. Each payload byte is copied ~3x here
    (per-blob tobytes, the join, the length-prefix concat)."""
    parts: List[bytes] = []
    blobs: List[bytes] = []
    payload = 0
    for blob in msg.data:
        blobs.append(blob.wire_bytes().tobytes())  # mvlint: ignore[copy-lint]
        payload += len(blobs[-1])
    header = _HDR.pack(*[int(v) for v in msg.header])
    parts.append(header)
    parts.append(_NBLOBS.pack(len(blobs)))
    for b in blobs:
        parts.append(_LEN.pack(len(b)))
    parts.extend(blobs)
    body = b"".join(parts)  # mvlint: ignore[copy-lint]
    frame = _LEN.pack(len(body)) + body
    count("WIRE_BYTES_COPIED", payload + len(body) + len(frame))
    count("WIRE_PAYLOAD_BYTES", payload)
    return frame


def _deserialize(body) -> Message:
    """Flat-buffer parser: the golden reference that
    tests/test_zero_copy.py compares ``_deserialize_frame`` against; no
    transport path calls it. Every payload byte is copied out of the
    frame into a private Blob array."""
    header = _HDR.unpack_from(body, 0)
    msg = Message()
    msg.header = list(header)
    off = _HDR.size
    (nblobs,) = _NBLOBS.unpack_from(body, off)
    off += _NBLOBS.size
    sizes = []
    payload = 0
    for _ in range(nblobs):
        (sz,) = _LEN.unpack_from(body, off)
        sizes.append(sz)
        off += _LEN.size
    for sz in sizes:
        msg.data.append(Blob(np.frombuffer(body, np.uint8, sz, off).copy()))
        off += sz
        payload += sz
    count("WIRE_BYTES_COPIED", payload)
    count("WIRE_PAYLOAD_BYTES", payload)
    return msg


def _deserialize_frame(body: memoryview, lease) -> Message:
    """Zero-copy parser: Blobs are READ-ONLY numpy views straight into
    the leased receive frame; ``lease`` rides every Blob and returns
    the buffer to the pool when the last one dies
    (util/buffer_pool.py). Mutating consumers must
    ``Blob.materialize()`` first — the copy-on-write contract
    (docs/MEMORY.md)."""
    header = _HDR.unpack_from(body, 0)
    msg = Message()
    msg.header = list(header)
    off = _HDR.size
    (nblobs,) = _NBLOBS.unpack_from(body, off)
    off += _NBLOBS.size
    sizes = []
    payload = 0
    for _ in range(nblobs):
        (sz,) = _LEN.unpack_from(body, off)
        sizes.append(sz)
        off += _LEN.size
    for sz in sizes:
        arr = np.frombuffer(body, np.uint8, sz, off)
        arr.flags.writeable = False
        msg.data.append(Blob.from_lease(arr, lease))
        off += sz
        payload += sz
    count("WIRE_PAYLOAD_BYTES", payload)
    return msg


class _EventLoop:
    """One ``selectors``-based I/O loop thread per endpoint.

    Everything the transport does with a socket — accepting, the
    nonblocking connect handshakes, frame reads, frame writes, retry
    and pacing timers, the shm ring doorbell — runs as handlers on this
    single EVENTLOOP thread. The pass-9 blocking-reachability proof
    (tools/mvlint/role_lint.py) pins the contract: the ONLY call that
    may park this thread is the ``selector.select(timeout)`` in
    ``_main``; every handler runs against nonblocking fds and timed
    waits, so no dead peer can ever strand the loop.

    Three thread-safe entry points exist for the rest of the process:
    ``call_soon(job)`` (enqueue a job and wake the loop), ``wake()``
    (self-pipe), and ``run_sync(fn)`` (call_soon + bounded wait —
    finalize uses it to run teardown ON the loop). ``call_later`` and
    the selector registration helpers are loop-thread-only.

    Jobs and timer payloads dispatch by object type — ``_Peer`` ticks,
    handler objects with ``on_misc_timer`` (TcpNet housekeeping, the
    shm ring service), or plain callables. The explicit isinstance
    chain is deliberate: it keeps every hot dispatch target statically
    resolvable for the blocking-reachability proof (a single dynamic
    ``job()`` would hide the transport behind an opaque call)."""

    def __init__(self, rank: int):
        self._rank = rank
        self._sel = selectors.DefaultSelector()
        self._pending: collections.deque = collections.deque()  # guarded_by: _pending_lock
        self._pending_lock = named_lock(f"tcp[r{rank}].loop.pending")
        self._timers: list = []  # heap of (when, seq, job); loop-thread only
        self._tseq = 0
        # Racy-by-design wake gate: worst case is one redundant
        # self-pipe byte; the loop resets it before draining jobs so a
        # racing call_soon can never be missed.
        self._woken = False
        self._stopped = False
        self._fds_closed = False
        rfd, wfd = os.pipe()
        os.set_blocking(rfd, False)
        os.set_blocking(wfd, False)
        self._rfd, self._wfd = rfd, wfd
        self._sel.register(rfd, selectors.EVENT_READ, _WakePipe(rfd))
        self._tick_gauge = samples("EVENTLOOP_TICK_MS")
        self._ready_gauge = samples("EVENTLOOP_READY_FDS")
        self._thread = thread_roles.spawn(
            thread_roles.EVENTLOOP, target=self._main,
            name=f"mv-net-loop-r{rank}")

    # -- thread-safe entry points --
    def on_loop(self) -> bool:
        return threading.current_thread() is self._thread

    def wake(self) -> None:
        if self._woken:
            return
        self._woken = True
        try:
            os.write(self._wfd, b"\0")
        except OSError:
            pass  # pipe full (a wake is already pending) or torn down

    def call_soon(self, job) -> None:
        """Enqueue ``job`` for the next loop iteration (any thread)."""
        with self._pending_lock:
            self._pending.append(job)
        self.wake()

    def run_sync(self, fn, timeout: float = 5.0) -> bool:
        """Run ``fn`` on the loop and wait (bounded) for it to finish.
        Runs inline when called from the loop itself or after the loop
        thread has exited (teardown stragglers must still run)."""
        if self.on_loop() or not self._thread.is_alive():
            fn()
            return True
        done = threading.Event()

        def job():
            try:
                fn()
            finally:
                done.set()

        self.call_soon(job)
        return done.wait(timeout=timeout)

    def stop(self, timeout: float = 5.0) -> None:
        self._stopped = True
        self.wake()
        if not self.on_loop():
            self._thread.join(timeout=timeout)
        if not self._thread.is_alive() and not self._fds_closed:
            self._fds_closed = True
            try:
                self._sel.close()
            except OSError:
                pass
            for fd in (self._rfd, self._wfd):
                try:
                    os.close(fd)
                except OSError:
                    pass

    # -- loop-thread-only helpers --
    def call_later(self, delay: float, job) -> None:
        self._tseq += 1
        heapq.heappush(self._timers,
                       (time.monotonic() + max(0.0, delay),
                        self._tseq, job))

    def register(self, fileobj, events: int, data) -> None:
        self._sel.register(fileobj, events, data)

    def modify(self, fileobj, events: int, data) -> None:
        self._sel.modify(fileobj, events, data)

    def unregister(self, fileobj) -> None:
        self._sel.unregister(fileobj)

    # -- the loop --
    def _dispatch_job(self, job) -> None:
        try:
            if isinstance(job, _Peer):
                job.on_peer_timer()
            elif hasattr(job, "on_misc_timer"):
                # Housekeeping handler objects (TcpNet gauge tick, the
                # shm ring service) — object dispatch, so the blocking
                # proof can resolve the targets.
                job.on_misc_timer()
            else:
                job()  # plain callable (call_soon/run_sync closures)
        except Exception:  # noqa: BLE001 - a handler bug must not take
            # the whole transport's I/O loop down with it
            log.error("event loop r%d: job %r raised:\n%s",
                      self._rank, job, traceback.format_exc())

    def _main(self) -> None:
        sel = self._sel
        select_errors = 0
        while True:
            # Re-arm the wake latch BEFORE the stop/pending checks and
            # the park. The pipe drain below swallows every byte queued
            # at drain time — including one written by a wake() racing
            # this iteration — so a latch cleared mid-iteration could
            # read True with an EMPTY pipe, suppressing every later
            # wake: a stop() landing in that state never wakes the
            # park and leaks this thread. Ordered this way, any wake
            # after the re-arm writes a real byte (select returns) and
            # any wake before it published its stop/pending state
            # before the checks below run.
            self._woken = False
            if self._stopped:
                return
            timeout = None
            if self._timers:
                timeout = max(0.0, self._timers[0][0] - time.monotonic())
                if timeout > 0.0015:
                    # epoll ceils its wait to whole milliseconds, so a
                    # timer parked for exactly `timeout` wakes up to
                    # 1 ms LATE — and the pacing bucket's busy-until
                    # arithmetic accumulates that drift per frame. Aim
                    # one quantum early; the residual re-select lands
                    # on time. (Sub-1.5 ms waits keep the ceil: a 0-
                    # timeout here would busy-spin the core instead.)
                    timeout -= 0.001
            with self._pending_lock:
                if self._pending:
                    timeout = 0.0
            try:
                # The ONLY blocking call an EVENTLOOP thread may make
                # (pass-9 pins this; the -debug_locks watchdog reads a
                # thread parked here as idle because this is the entry
                # frame).
                events = sel.select(timeout)
            except OSError:
                # An fd died under the selector (should be unreachable:
                # every close is preceded by unregister). Log and keep
                # serving; bail if it persists so a bug cannot hot-spin.
                select_errors += 1
                if select_errors > 100:
                    raise
                log.error("event loop r%d: select failed:\n%s",
                          self._rank, traceback.format_exc())
                events = []
            if self._stopped:
                return
            t0 = time.perf_counter()
            worked = bool(events)
            jobs = None
            with self._pending_lock:
                if self._pending:
                    jobs = list(self._pending)
                    self._pending.clear()
            if jobs:
                worked = True
                for job in jobs:
                    self._dispatch_job(job)
            now = time.monotonic()
            while self._timers and self._timers[0][0] <= now:
                _when, _seq, job = heapq.heappop(self._timers)
                worked = True
                self._dispatch_job(job)
            for key, mask in events:
                data = key.data
                try:
                    if isinstance(data, _Peer):
                        data.on_peer_io(mask)
                    elif isinstance(data, _Conn):
                        data.on_conn_io(mask)
                    elif isinstance(data, _Listener):
                        data.on_accept_io(mask)
                    else:
                        data.on_misc_io(mask)
                except Exception:  # noqa: BLE001 - ditto: one broken
                    # handler must not stop every other fd's service
                    log.error("event loop r%d: handler %r raised:\n%s",
                              self._rank, data, traceback.format_exc())
            if events:
                self._ready_gauge.add(len(events))
            if worked:
                self._tick_gauge.add((time.perf_counter() - t0) * 1e3)


class _WakePipe:
    """Self-pipe read end: drains wake bytes so the selector can park
    again. The payload is meaningless — the readiness edge is the
    signal."""

    def __init__(self, rfd: int):
        self._rfd = rfd

    def on_misc_io(self, mask: int) -> None:
        while True:
            try:
                chunk = os.read(self._rfd, 4096)
            except (BlockingIOError, OSError):
                return
            if not chunk:
                return


class _Listener:
    """Accept handler: the listening socket is nonblocking and
    registered on the loop; each accepted connection becomes a
    ``_Conn`` read state machine on the same selector (the old model
    spawned a blocking reader thread per connection here)."""

    def __init__(self, net: "TcpNet"):
        self._net = net

    def on_accept_io(self, mask: int) -> None:
        while True:
            try:
                conn, _addr = self._net._listener.accept()  # mvlint: ignore[thread-role] - nonblocking listener: EAGAIN ends the burst, never parks the loop
            except BlockingIOError:
                return
            except OSError:
                return  # listener closed (finalize)
            conn.setblocking(False)
            conn.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            self._net._register_conn(_Conn(self._net, conn))


class _Conn:
    """One inbound connection's receive state machine (loop-thread
    only). Buffers and protocol are exactly the old reader thread's:
    an 8-byte length prefix, then a pooled lease filled by
    ``recv_into``; a length-0 frame is the peer's goodbye (graceful
    close), EOF
    without one is a dirty close and reports the peer. The difference
    is shape: the fill tolerates partial reads and resumes whenever the
    selector reports readability instead of parking a thread in
    ``recv``."""

    #: Frames parsed per readiness event before yielding the loop
    #: (level-triggered epoll re-arms, so a firehose connection gets
    #: re-served next tick without starving the other fds).
    _FRAME_BUDGET = 32

    def __init__(self, net: "TcpNet", sock: socket.socket):
        self._net = net
        self._sock: Optional[socket.socket] = sock
        self._head = memoryview(bytearray(_LEN.size))
        self._head_got = 0
        self._total = 0
        self._lease = None  # pooled frame lease
        self._body: Optional[memoryview] = None  # fill target
        self._body_got = 0
        self.peer: Optional[int] = None  # rank learned from frames

    def on_conn_io(self, mask: int) -> None:
        if self._sock is None:
            return  # stale event: torn down earlier in this batch
        try:
            self._read_burst()
        except BlockingIOError:
            pass  # socket drained mid-frame; resumes on next readiness
        except OSError:
            self._close(clean=False)

    def _read_burst(self) -> None:
        frames = 0
        while frames < self._FRAME_BUDGET:
            if self._body is None:
                # Header phase: accumulate the 8-byte length prefix.
                k = self._sock.recv_into(self._head[self._head_got:])  # mvlint: ignore[thread-role] - nonblocking fd: EAGAIN raises, never parks
                if k == 0:
                    self._close(clean=False)  # EOF without goodbye
                    return
                self._head_got += k
                if self._head_got < _LEN.size:
                    continue
                (total,) = _LEN.unpack(self._head)
                self._head_got = 0
                if total == 0:  # goodbye frame: graceful peer close
                    self._close(clean=True)
                    return
                self._total = total
                self._lease = self._net._pool.lease(total)
                self._body = self._lease.view(total)
                self._body_got = 0
            # Body phase: progressive fill of the leased buffer.
            with monitor("tcp_recv"):
                k = self._sock.recv_into(self._body[self._body_got:])  # mvlint: ignore[thread-role] - nonblocking fd: EAGAIN raises, never parks
            if k == 0:
                self._close(clean=False)  # EOF mid-frame
                return
            self._body_got += k
            if self._body_got < self._total:
                continue
            self._finish_frame()
            frames += 1

    def _finish_frame(self) -> None:
        total = self._total
        lease, self._lease = self._lease, None
        self._body = None
        self._total = 0
        with monitor("tcp_deserialize"):
            msg = _deserialize_frame(lease.view(total), lease)
        # Every inbound frame names its sender; remembering it lets a
        # dirty close report WHICH peer died (the zoo's rejoin path
        # fails only that rank's in-flight requests instead of aborting
        # the whole cluster).
        if 0 <= msg.src < self._net.size and msg.src != self._net.rank:
            self.peer = msg.src
        self._net._inbox.push(msg)

    def close_for_teardown(self) -> None:
        self._close(clean=True)

    def _close(self, clean: bool) -> None:
        if self._sock is None:
            return
        self._net._unregister_conn(self)
        sock, self._sock = self._sock, None
        try:
            sock.close()
        except OSError:
            pass
        lease, self._lease = self._lease, None
        self._body = None
        if lease is not None:
            lease.release()  # mid-frame teardown: recycle the buffer
        # Racy teardown check by design: worst case is one spurious
        # peer-lost report during finalize, which abort ignores.
        if not clean and not self._net._closed:
            # A peer hung up while the mesh is live: report it so the
            # zoo can abort blocked waits (the reference has no such
            # detection — a dead MPI rank hangs the cluster).
            self._net._conn_died(self.peer)


class _Peer:
    """Per-destination connection state machine + bounded outbound
    frame queue (CONNECTING → HANDSHAKE → READY → DRAINING → DEAD).

    Replaces the per-destination writer THREAD: ``submit`` enqueues
    ``(views, nbytes)`` scatter-gather frames under the same
    ``-send_queue_mb`` backpressure contract, and the event loop drains
    them with nonblocking ``sendmsg`` vectored writes — partial-send
    resume included — so the views alias the payload's own buffers
    until the write completes (the ``send_async`` contract: callers
    must not mutate a queued payload before ``flush_sends``). A wire
    error parks in ``error`` and re-raises from the next submit/flush
    as ``PeerLostError``; the dead machine retires itself from the peer
    table, so the next send toward this rank dials fresh.

    Locking: the queue fields are caller-shared under ``_cond``;
    everything about the socket and connection state is loop-thread
    only."""

    #: Frames written per drain pass before yielding the loop (WRITE
    #: readiness re-kicks immediately; the budget just interleaves
    #: other fds' service between bursts — and keeps the watchdog's
    #: same-line stack heuristic from mistaking a long burst for a
    #: parked thread).
    _DRAIN_FRAMES = 64

    def __init__(self, net: "TcpNet", dst: int):
        self._net = net
        self._loop = net._loop
        self._dst = dst
        self._cond = named_condition(f"tcp[r{net.rank}].peer[d{dst}]")
        self._frames: collections.deque = collections.deque()  # guarded_by: _cond
        self._queued_bytes = 0  # guarded_by: _cond
        self._inflight = False  # guarded_by: _cond
        self._kicked = False  # guarded_by: _cond
        self.error: Optional[BaseException] = None  # guarded_by: _cond
        self.closed = False  # guarded_by: _cond
        # Loop-thread-only connection state:
        self.state = _ST_CONNECTING
        self._sock: Optional[socket.socket] = None
        self._registered = False
        self._want_write = False
        self._cur: Optional[list] = None  # [views, i, off, nbytes, t0, bye]
        self._deadline = 0.0  # connect-epoch deadline (0 = not dialing)
        self._retry_at = 0.0
        self._retry_delay = 0.02
        self._eof_scratch = memoryview(bytearray(256))
        self._depth_gauge = samples(f"DISPATCH_QUEUE_DEPTH[d{dst}]")
        self._lat_gauge = samples(f"DISPATCH_MS[d{dst}]")
        count(f"NET_PEER_STATE[{_ST_CONNECTING}]")

    # -- caller-side API (any thread) --
    def submit(self, views: List[memoryview], nbytes: int,
               goodbye: bool = False) -> None:
        cap = max(1, int(get_flag("send_queue_mb"))) << 20
        # The loop itself must never park on backpressure (it IS the
        # drain); loop-side submits (the finalize goodbye) enqueue
        # unconditionally.
        on_loop = self._loop.on_loop()
        kick = False
        with self._cond:
            while (not on_loop and self._queued_bytes >= cap
                   and self.error is None and not self.closed):
                self._cond.wait(timeout=1.0)
            if self.error is not None:
                # The endpoint is DEAD: typed so callers can tell a
                # lost peer — retryable after a rejoin — from a local
                # programming error.
                raise PeerLostError(
                    f"send to rank {self._dst} failed: peer connection "
                    f"is dead ({self.error})") from self.error
            if self.closed and not goodbye:
                raise RuntimeError("TcpNet finalized")
            self._frames.append(
                (views, nbytes, time.perf_counter(), goodbye))
            self._queued_bytes += nbytes
            depth = len(self._frames)
            if not self._kicked:
                self._kicked = True
                kick = True
            self._cond.notify_all()
        self._depth_gauge.add(depth)
        if kick:
            self._loop.call_soon(self)

    def flush(self, timeout: Optional[float] = None) -> None:
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while (self._frames or self._inflight) and self.error is None:
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
                    f"send to rank {self._dst} failed: peer connection "
                    f"is dead ({self.error})") from self.error

    @property
    def queued_bytes(self) -> int:
        with self._cond:
            return self._queued_bytes

    def depth(self) -> int:
        with self._cond:
            return len(self._frames) + (1 if self._inflight else 0)

    # -- loop-side state machine --
    def _set_state(self, state: str) -> None:
        self.state = state
        count(f"NET_PEER_STATE[{state}]")

    def on_peer_timer(self) -> None:
        """Loop tick: advance whatever the current state allows. Kicks
        from submit, connect-retry and pacing timers, and drain-budget
        yields all funnel here — a tick is idempotent, so over-kicking
        is harmless."""
        with self._cond:
            self._kicked = False
        if self.state in (_ST_READY, _ST_DRAINING):
            self._drain()
        elif (self.state == _ST_CONNECTING and self._sock is None
                and time.monotonic() >= self._retry_at):
            self._dial()

    def on_peer_io(self, mask: int) -> None:
        if self._sock is None or self.state == _ST_DEAD:
            return  # stale event: torn down earlier in this batch
        if self.state == _ST_HANDSHAKE:
            err = self._sock.getsockopt(socket.SOL_SOCKET,
                                        socket.SO_ERROR)
            if err:
                self._teardown_socket()
                self._connect_failed(OSError(err, os.strerror(err)))
            else:
                self._on_connected()
            return
        if mask & selectors.EVENT_READ and not self._probe_eof():
            return  # died on the read edge
        if mask & selectors.EVENT_WRITE:
            self._drain()

    def _dial(self) -> None:
        """Nonblocking connect attempt: connect_ex + selector-observed
        completion, with per-peer exponential backoff timers between
        attempts — the replacement for the old blocking dial loop that
        parked a writer thread for up to -connect_timeout_s per dead
        peer."""
        now = time.monotonic()
        if not self._deadline:
            self._deadline = now + float(get_flag("connect_timeout_s"))
        host, port = self._net._peers[self._dst]
        sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        sock.setblocking(False)
        try:
            err = sock.connect_ex((host, port))
        except OSError as exc:  # e.g. name resolution failure
            try:
                sock.close()
            except OSError:
                pass
            self._connect_failed(exc)
            return
        if err != 0 and err not in _EX_PENDING:
            try:
                sock.close()
            except OSError:
                pass
            self._connect_failed(OSError(err, os.strerror(err)))
            return
        # Connected-immediately (err 0, loopback) still goes through
        # HANDSHAKE: the socket is instantly writable, so the selector
        # confirms it on the next tick — one uniform path.
        self._sock = sock
        self._set_state(_ST_HANDSHAKE)
        self._register(selectors.EVENT_READ | selectors.EVENT_WRITE)

    def _on_connected(self) -> None:
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._deadline = 0.0
        self._retry_delay = 0.02
        self._want_write = True  # force the modify down to READ-only
        self._set_want_write(False)
        # A peer that finished its handshake after finalize began goes
        # straight to DRAINING: the queued frames (goodbye included)
        # still flush, but the state never reads READY.
        self._set_state(_ST_DRAINING if self.closed else _ST_READY)  # mvlint: ignore[guarded-by] - closed is loop-written after __init__; the cond only orders it for caller-side reads
        self._drain()

    def _connect_failed(self, exc: BaseException) -> None:
        now = time.monotonic()
        if now >= self._deadline:
            host, port = self._net._peers[self._dst]
            timeout_s = float(get_flag("connect_timeout_s"))
            # Typed as a lost peer: unreachable-within-timeout is
            # exactly the retryable condition (bootstrap race or a
            # crashed rank whose replacement has not bound yet). No
            # peer-lost report — parity with the old blocking dialer,
            # whose deadline raised into the sender without declaring
            # the peer dead.
            self._die(PeerLostError(
                f"rank {self._net.rank}: cannot reach rank {self._dst} "
                f"at {host}:{port} within {timeout_s}s"), report=False)
            return
        if self.state != _ST_CONNECTING:
            self._set_state(_ST_CONNECTING)
        self._retry_at = now + self._retry_delay
        self._retry_delay = min(self._retry_delay * 2, 0.5)
        self._loop.call_later(self._retry_at - now, self)

    def _probe_eof(self) -> bool:
        """READ readiness on the outbound socket. The protocol never
        sends bytes back on this direction, so readability means EOF or
        an error — the selector-observed half of dead-peer detection.
        EOF with frames queued is a mid-send death (report it); EOF on
        an idle peer is the remote side's own graceful close racing
        ours — retire quietly and let the next send dial fresh."""
        try:
            k = self._sock.recv_into(self._eof_scratch)  # mvlint: ignore[thread-role] - nonblocking fd: EAGAIN raises, never parks
        except BlockingIOError:
            return True
        except OSError as exc:
            self._die(exc)
            return False
        if k:
            return True  # stray bytes: not ours to interpret
        with self._cond:
            busy = bool(self._frames) or self._inflight
        self._die(ConnectionResetError(
            errno.ECONNRESET,
            f"rank {self._dst} closed the connection"), report=busy)
        return False

    def _drain(self) -> None:
        """Write queued frames with nonblocking vectored sends — the
        same partial-send arithmetic as ``_sendmsg_all``, suspended on
        EAGAIN (WRITE interest re-arms it) instead of blocking."""
        sock = self._sock
        if sock is None or self.state not in (_ST_READY, _ST_DRAINING):
            return
        budget = self._DRAIN_FRAMES
        while True:
            cur = self._cur
            if cur is None:
                with self._cond:
                    if not self._frames:
                        break
                    views, nbytes, t_submit, goodbye = \
                        self._frames.popleft()
                    self._inflight = True
                cur = self._cur = [views, 0, 0, nbytes, t_submit, goodbye]
            views, i, off, nbytes, t_submit, goodbye = cur
            n = len(views)
            try:
                while i < n:
                    if off:
                        batch = [views[i][off:]]
                        batch.extend(views[i + 1:i + _IOV_CAP])
                    else:
                        batch = views[i:i + _IOV_CAP]
                    with monitor("tcp_send"):
                        sent = sock.sendmsg(batch)
                    while i < n and sent:
                        remaining = views[i].nbytes - off
                        if sent >= remaining:
                            sent -= remaining
                            i += 1
                            off = 0
                        else:
                            off += sent
                            sent = 0
            except BlockingIOError:
                cur[1], cur[2] = i, off  # resume exactly here
                self._set_want_write(True)
                return
            except OSError as exc:
                self._die(exc)
                return
            # Frame complete (kernel accepted every byte). Drop the
            # view list before anything else: the views alias payload
            # buffers (possibly a pooled receive frame being
            # forwarded), and holding them would pin that memory.
            self._cur = None
            views = cur = None
            self._net._count_sent(nbytes)
            self._lat_gauge.add((time.perf_counter() - t_submit) * 1e3)
            with self._cond:
                self._queued_bytes -= nbytes
                self._inflight = False
                self._cond.notify_all()
            if goodbye:
                self._finish_close()
                return
            budget -= 1
            if budget <= 0:
                # Yield the tick: WRITE interest re-fires immediately
                # while the socket stays writable, so the remaining
                # frames interleave with other fds' service.
                self._set_want_write(True)
                return
        self._set_want_write(False)

    def _finish_close(self) -> None:
        """Goodbye frame fully written: the graceful half of DRAINING →
        DEAD. No error parks — flush() returns normally."""
        self._teardown_socket()
        self._set_state(_ST_DEAD)
        self._net._retire_peer(self)
        with self._cond:
            self._cond.notify_all()

    def _die(self, exc: BaseException, report: bool = True) -> None:
        """Peer death on the loop: close the socket, park the error for
        submit/flush waiters, clear the queue (the old writer threads
        did the same — zoo.peer_lost fails the stranded requests), and
        retire this machine from the peer table."""
        if self.state == _ST_DEAD:
            return
        self._teardown_socket()
        self._cur = None
        self._set_state(_ST_DEAD)
        with self._cond:
            if self.error is None:
                self.error = exc
            self._frames.clear()
            self._queued_bytes = 0
            self._inflight = False
            self._cond.notify_all()
        self._net._retire_peer(self)
        if report and not self.closed:  # mvlint: ignore[guarded-by] - loop-side read; closed only transitions False->True, worst case a report during finalize that abort ignores
            self._net._report_send_death(self._dst, exc)

    def kill(self, exc: BaseException) -> None:
        """Teardown entry for drop_connection/finalize: death without a
        peer-lost report."""
        self._die(exc, report=False)

    def _teardown_socket(self) -> None:
        self._unregister()
        sock, self._sock = self._sock, None
        if sock is not None:
            try:
                sock.close()
            except OSError:
                pass

    def _register(self, mask: int) -> None:
        self._loop.register(self._sock, mask, self)
        self._registered = True
        self._want_write = bool(mask & selectors.EVENT_WRITE)

    def _unregister(self) -> None:
        if self._registered and self._sock is not None:
            try:
                self._loop.unregister(self._sock)
            except (KeyError, ValueError):
                pass
        self._registered = False

    def _set_want_write(self, want: bool) -> None:
        if want == self._want_write or not self._registered:
            return
        self._want_write = want
        mask = selectors.EVENT_READ
        if want:
            mask |= selectors.EVENT_WRITE
        self._loop.modify(self._sock, mask, self)


class TcpNet(NetInterface):
    """One endpoint of a full-mesh TCP cluster."""

    #: Optional callback fired when a peer connection dies while the
    #: mesh is still supposed to be up (set by Zoo.start -> Zoo.abort).
    on_peer_lost = None

    #: Live instances (the test-suite leak guard scopes its FD baseline
    #: check to tests that actually built an endpoint).
    instances_created = 0

    def __init__(self, rank: int, endpoints: List[str],
                 default_port: Optional[int] = None):
        if not 0 <= rank < len(endpoints):
            raise ValueError(f"rank {rank} not in endpoint list "
                             f"of size {len(endpoints)}")
        port = default_port if default_port is not None \
            else int(get_flag("port"))
        self._rank = rank
        self._peers = [_parse_endpoint(e, port) for e in endpoints]
        self._inbox: MtQueue = MtQueue()
        self._lifecycle = named_lock(f"tcp[r{rank}].lifecycle")
        self._out_peers: Dict[int, _Peer] = {}  # guarded_by: _lifecycle
        self._closed = False  # guarded_by: _lifecycle
        self._stats_lock = named_lock(f"tcp[r{rank}].stats")
        self._bytes_sent = 0  # guarded_by: _stats_lock
        # Receive-frame pool shared by every inbound connection of this
        # endpoint (the leases are what recycle the buffers; the pool
        # itself only caps what is RETAINED, so reads never block).
        self._pool = BufferPool()
        self._conns: set = set()  # loop-thread only: live inbound conns
        self._transport_gauge = samples("TRANSPORT_THREADS")

        self._listener = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._listener.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._listener.bind(("", self._peers[rank][1]))
        self._listener.listen(len(endpoints) + 4)
        self._listener.setblocking(False)
        self._loop = _EventLoop(rank)
        self._loop.call_soon(self._start_on_loop)
        TcpNet.instances_created += 1
        log.debug("TcpNet rank %d listening on %s:%d", rank,
                  self._peers[rank][0], self._peers[rank][1])

    def _start_on_loop(self) -> None:
        self._loop.register(self._listener, selectors.EVENT_READ,
                            _Listener(self))
        self.on_misc_timer()

    def on_misc_timer(self) -> None:
        """Housekeeping tick (~2s on the loop): record the transport
        thread gauge — O(1) in peer count is the point of the
        event-loop core, and TRANSPORT_THREADS is the gauge that shows
        it on a live rank."""
        alive = thread_roles.roles_alive()
        self._transport_gauge.add(
            alive.get(thread_roles.EVENTLOOP, 0)
            + alive.get(thread_roles.WRITER, 0))
        # Racy re-arm guard by design: one extra tick after finalize at
        # worst — the loop exits right after.
        if not self._closed:  # mvlint: ignore[guarded-by]
            self._loop.call_later(2.0, self)

    # -- NetInterface --
    @property
    def rank(self) -> int:
        return self._rank

    @property
    def size(self) -> int:
        return len(self._peers)

    def send(self, msg: Message) -> int:
        """Serialize + send, each under a Dashboard monitor (the
        reference instruments exactly these wire phases,
        ref: mpi_net.h:292-342 MVA_NET_SERIALIZE/SEND sites). The
        blocking path is submit + flush on the destination's queue:
        FIFO with earlier async frames for free, and the caller parks
        in a timed wait, never on a socket."""
        dst = msg.dst
        if not 0 <= dst < self.size:
            raise ValueError(f"bad dst rank {dst}")
        with monitor("tcp_serialize"):
            views, nbytes = serialize_views(msg)
        peer = self._peer(dst)
        peer.submit(views, nbytes)
        peer.flush(timeout=60.0)
        return nbytes

    def send_async(self, msg: Message) -> int:
        """Queue one serialized frame on the destination's peer state
        machine and return immediately (the non-blocking half of the
        chunked allreduce pipeline: multiple frames in flight per
        peer)."""
        dst = msg.dst
        if not 0 <= dst < self.size:
            raise ValueError(f"bad dst rank {dst}")
        # Chaos harness (-chaos_frames, util/chaos.py): direct async
        # senders (liveness/metrics frames) bypass the communicator's
        # choke point, so the fault filter hooks here too (one flag
        # probe when disarmed).
        faulted = chaos.filter_frames(msg)
        if faulted is not None:
            total = 0
            for m in faulted:
                total += self._send_async_real(m)
            return total
        return self._send_async_real(msg)

    def _send_async_real(self, msg: Message) -> int:
        dst = msg.dst
        with monitor("tcp_serialize"):
            views, nbytes = serialize_views(msg)
        self._peer(dst).submit(views, nbytes)
        return nbytes

    def flush_sends(self, dst: Optional[int] = None,
                    timeout: Optional[float] = None) -> None:
        # Snapshot under the lock (a concurrent drop_connection must
        # not mutate the dict mid-iteration); flush OUTSIDE it — flush
        # blocks, and _peer() needs the lock to register new peers.
        with self._lifecycle:
            peers = [self._out_peers[dst]] if dst is not None \
                and dst in self._out_peers else \
                (list(self._out_peers.values()) if dst is None else [])
        for peer in peers:
            peer.flush(timeout)

    def queue_depths(self) -> Dict[int, int]:
        """Outbound frames queued (or mid-write) per destination — the
        live-introspection port autotune reads."""
        with self._lifecycle:
            peers = list(self._out_peers.items())
        return {dst: peer.depth() for dst, peer in peers}

    @property
    def bytes_sent(self) -> int:
        with self._stats_lock:
            return self._bytes_sent

    def _peer(self, dst: int) -> _Peer:
        # Double-checked probe: the hot async-send path skips the
        # lifecycle lock; the slow path below re-reads under it.
        peer = self._out_peers.get(dst)  # mvlint: ignore[guarded-by]
        if peer is None:
            with self._lifecycle:
                if self._closed:
                    raise RuntimeError("TcpNet finalized")
                peer = self._out_peers.get(dst)
                if peer is None:
                    peer = self._out_peers[dst] = _Peer(self, dst)
        return peer

    # -- peer-death bookkeeping --
    def drop_connection(self, dst: int) -> None:
        """Forget the outbound connection state for ``dst``: retire the
        (possibly dead) peer state machine. The next send toward
        ``dst`` reconnects from scratch — the fault-tolerance retry
        path calls this when a peer is declared dead so a restarted
        replacement process is actually reachable instead of every
        retry hitting the broken socket."""
        with self._lifecycle:
            peer = self._out_peers.pop(dst, None)
        if peer is None:
            return
        exc = PeerLostError(f"connection to rank {dst} dropped")
        if self._loop.on_loop():
            peer.kill(exc)
        else:
            self._loop.call_soon(lambda: peer.kill(exc))

    def _retire_peer(self, peer: _Peer) -> None:
        with self._lifecycle:
            if self._out_peers.get(peer._dst) is peer:
                del self._out_peers[peer._dst]

    def _report_send_death(self, dst: int, exc: BaseException) -> None:
        """A connection toward ``dst`` broke while the mesh is live
        (inbound conns report via their own dirty-close path; this
        covers the SEND side, where the rank is known)."""
        # Racy loop-guard read by design: a teardown racing a peer
        # death at worst reports a peer that finalize already forgot.
        if self._closed:  # mvlint: ignore[guarded-by]
            return
        log.error("TcpNet rank %d: connection to rank %d died: %s",
                  self._rank, dst, exc)
        hook = self.on_peer_lost
        if hook is not None:
            try:
                hook(dst)
            except Exception:  # noqa: BLE001 - failure handling must
                # not take the transport down with it
                pass

    def _conn_died(self, peer: Optional[int]) -> None:
        """Dirty close of an inbound connection: the send side toward
        that peer is stale too — drop it so retries reconnect rather
        than write into the corpse, then report the loss."""
        if peer is not None:
            self.drop_connection(peer)
        hook = self.on_peer_lost
        if hook is not None:
            try:
                hook(peer)
            except Exception:  # noqa: BLE001 - abort must not die
                pass

    def _count_sent(self, nbytes: int) -> None:
        with self._stats_lock:
            self._bytes_sent += nbytes

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        item = self._inbox.pop(timeout=timeout)
        if item is _RECV_INTERRUPT:
            return None
        return item

    def deliver(self, msg: Message) -> None:
        """Inject a locally received message into the inbox — the
        delivery port of the shm ring service (runtime/shm.py), so
        ring-borne and socket-borne frames share one queue and recv
        keeps its blocking semantics and per-source FIFO."""
        self._inbox.push(msg)

    # -- inbound-conn bookkeeping (loop thread) --
    def _register_conn(self, conn: _Conn) -> None:
        self._conns.add(conn)
        self._loop.register(conn._sock, selectors.EVENT_READ, conn)

    def _unregister_conn(self, conn: _Conn) -> None:
        self._conns.discard(conn)
        try:
            self._loop.unregister(conn._sock)
        except (KeyError, ValueError):
            pass

    def finalize(self) -> None:
        with self._lifecycle:
            if self._closed:
                return
            self._closed = True
            peers = dict(self._out_peers)
        # Stop accepting, then queue a goodbye frame (length 0 — tells
        # each peer's receive side this close is GRACEFUL) behind every
        # destination's remaining traffic. DRAINING peers flush queued
        # frames first, so a goodbye can never truncate the stream
        # mid-payload — a ring allreduce returns once it has RECEIVED
        # everything, so its final-step sends may still be queued here,
        # and a peer's collective depends on them.
        self._loop.run_sync(self._teardown_listener, timeout=2.0)
        self._loop.run_sync(
            lambda: [self._begin_drain(p) for p in peers.values()],
            timeout=5.0)
        # Bounded drain per peer, scaled by what is queued; a wedged or
        # dead peer is force-killed below.
        for peer in peers.values():
            pending = peer.queued_bytes
            drain = 2.0 + pending / (4 << 20)  # >=4 MB/s of real wire
            try:
                peer.flush(timeout=drain)
            except (PeerLostError, RuntimeError):
                pass
        self._loop.run_sync(self._teardown_links, timeout=5.0)
        self._loop.stop(timeout=5.0)
        self._inbox.exit()

    def _teardown_listener(self) -> None:
        try:
            self._loop.unregister(self._listener)
        except (KeyError, ValueError):
            pass
        try:
            self._listener.close()
        except OSError:
            pass

    def _begin_drain(self, peer: _Peer) -> None:
        """Finalize, on the loop: refuse new frames, queue the goodbye.
        READY → DRAINING; a peer still connecting keeps its backoff
        machine (the goodbye flushes if the handshake completes within
        the drain bound, else the force-kill reaps it)."""
        with peer._cond:
            already = peer.closed
            peer.closed = True
            peer._cond.notify_all()
        if already or peer.state == _ST_DEAD:
            return
        if peer.state == _ST_READY:
            peer._set_state(_ST_DRAINING)
        try:
            peer.submit([memoryview(_LEN.pack(0))], _LEN.size,
                        goodbye=True)
        except (PeerLostError, RuntimeError):
            pass  # already dead: nothing to say goodbye on

    def _teardown_links(self) -> None:
        with self._lifecycle:
            stragglers = list(self._out_peers.values())
        for peer in stragglers:
            peer.kill(RuntimeError("TcpNet finalized"))
        for conn in list(self._conns):
            conn.close_for_teardown()

    def interrupt_recv(self) -> None:
        self._inbox.push(_RECV_INTERRUPT)

    # -- bootstrap --
    @classmethod
    def from_flags(cls) -> "TcpNet":
        """Machine-file bootstrap (ref: zmq_net.h:25-61): one host[:port]
        per line; own rank from -rank or by unique local-address match."""
        path = get_flag("machine_file")
        if not path:
            raise RuntimeError("machine_file flag not set")
        with open(path) as f:
            endpoints = [ln.strip() for ln in f if ln.strip()
                         and not ln.lstrip().startswith("#")]
        if not endpoints:
            raise RuntimeError(f"machine file {path!r} is empty")
        rank = int(get_flag("rank"))
        if rank < 0:
            port = int(get_flag("port"))
            local = local_addresses()
            matches = [i for i, e in enumerate(endpoints)
                       if _parse_endpoint(e, port)[0] in local]
            if len(matches) != 1:
                raise RuntimeError(
                    f"cannot determine own rank from {path!r}: "
                    f"{len(matches)} lines match local addresses; "
                    "pass -rank=N (required when ranks share a host)")
            rank = matches[0]
        return cls(rank, endpoints)


# -- app-driven deployment (MV_NetBind / MV_NetConnect parity) --

_pending_bind: Optional[Tuple[int, str]] = None
_pending_net: Optional[TcpNet] = None


def net_bind(rank: int, endpoint: str) -> None:
    """MV_NetBind (ref: multiverso.h:55-59, zmq_net.h:63-80): declare this
    process's rank and listening endpoint before ``mv.init``."""
    global _pending_bind
    _pending_bind = (rank, endpoint)


def net_connect(ranks: List[int], endpoints: List[str]) -> None:
    """MV_NetConnect (ref: multiverso.h:60-64, zmq_net.h:82-109): supply
    the full rank -> endpoint table and build the transport; ``mv.init``
    consumes it."""
    global _pending_net, _pending_bind
    if _pending_bind is None:
        raise RuntimeError("call net_bind(rank, endpoint) before "
                           "net_connect")
    if len(ranks) != len(endpoints):
        raise ValueError(f"net_connect: {len(ranks)} ranks but "
                         f"{len(endpoints)} endpoints")
    my_rank, my_endpoint = _pending_bind
    table = dict(zip(ranks, endpoints))
    table[my_rank] = my_endpoint
    if sorted(table) != list(range(len(table))):
        raise RuntimeError(f"net_connect needs a dense rank set, got "
                           f"{sorted(table)}")
    ordered = [table[r] for r in range(len(table))]
    _pending_net = TcpNet(my_rank, ordered)
    _pending_bind = None


def take_pending_net() -> Optional[TcpNet]:
    """Consume the transport prepared by net_bind/net_connect (called by
    Zoo.start)."""
    global _pending_net
    net, _pending_net = _pending_net, None
    return net

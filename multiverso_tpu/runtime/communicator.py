"""Bridge between local actors and the wire.

TPU-native equivalent of the reference's ``Communicator``
(ref: include/multiverso/communicator.h:11-28, src/communicator.cpp:31-107).
The reference gives the communicator its own actor thread because its ZMQ
sockets are single-threaded; this port's transports are thread-safe, and
outbound frames land in per-destination queues drained by the transport's
event loop — so there is no communicator thread to serialize behind.
``receive`` routes ON THE CALLER'S THREAD: a remote-bound message is
encoded and submitted to its destination's peer queue right there (the
queue's ``-send_queue_mb`` cap is the backpressure, felt by the producer
that is actually overrunning the wire), and a loop-back message is
forwarded to the right local actor by message type — requests to the
server, replies to the worker, control requests to the controller,
control replies to the Zoo mailbox (ref: src/communicator.cpp:13-29,
93-105). One dedicated receive thread drains the net endpoint
(ref: src/communicator.cpp:42-48,77-91); it is the only thread this
class owns.
"""

from __future__ import annotations

import threading
from typing import Optional

import numpy as np

from ..core.blob import Blob
from ..util import chaos
from ..core.message import (PEER_LOST_MARK, Message, MsgType,
                            is_controller_bound, is_server_bound,
                            is_wire_encoded, is_worker_bound, mark_error)
from ..util import log
from ..util.configure import get_flag
from ..util.wire_codec import (CAP_WIRE_CODEC, decode_message,
                               encode_message)
from . import actor as actors
from . import thread_roles


class Communicator:
    """Message router between this rank's actors and the transport.

    Deliberately NOT an ``Actor``: it owns no mailbox and no dispatch
    thread. The old single communicator thread was the repo's most
    persistent failure class (dispatch starvation behind a dead or slow
    peer), and the per-destination WRITER threads that cured it cost
    O(peers) threads; both collapsed into the transport's event loop,
    leaving ``receive`` a plain synchronous call."""

    def __init__(self, zoo) -> None:
        self.name = actors.COMMUNICATOR
        self._zoo = zoo
        self._net = zoo.net
        self._recv_thread: Optional[threading.Thread] = None
        # Filter stage: encode only over a real wire (in-process blobs
        # move by reference — filtering would burn CPU and flatten
        # device payloads to host bytes for nothing), only when this
        # rank runs with the codec, and — checked per message — only
        # toward peers that ADVERTISED it during registration.
        self._codec = (not self._net.in_process
                       and bool(get_flag("wire_codec")))
        # Shm-transport probe (runtime/shm.py): frames toward a
        # ring-routed peer skip the codec filter — compressing below
        # the socket buys no syscalls or kernel copies, so the codec
        # CPU is pure loss there (the codec is lossless by default, so
        # results are identical either way).
        self._shm_probe = getattr(self._net, "is_shm_peer", None)
        zoo.register_actor(self)

    def start(self) -> None:
        self._net.acquire_recv_owner()
        # DISPATCH: the recv thread routes inbound frames into actor
        # mailboxes — anything blocking it starves replies.
        self._recv_thread = thread_roles.spawn(
            thread_roles.DISPATCH, target=self._recv_main,
            name=f"mv-comm-recv-r{self._zoo.rank}")

    def stop(self, finalize_net: bool = True) -> None:
        # Callers route straight into the transport, so there is no
        # actor mailbox to drain first: any reply another actor queued
        # is already sitting in a peer queue, and finalize flushes
        # those (goodbye-after-traffic) before closing — the peer's
        # final barrier still gets its frames.
        if finalize_net:
            self._net.finalize()
        else:
            self._net.interrupt_recv()
        if self._recv_thread is not None:
            self._recv_thread.join(timeout=30)
        self._net.release_recv_owner()
        self._zoo.deregister_actor(self)

    def queue_depths(self) -> dict:
        """Live per-destination outbound queue depths (monitor
        observability; empty on transports without peer queues)."""
        return getattr(self._net, "queue_depths", lambda: {})()

    # -- messaging (zoo.route/send_to call this like any actor's) --
    def receive(self, msg: Message) -> None:
        self._safe_dispatch(msg)

    def _safe_dispatch(self, msg: Message) -> None:
        """Dispatch one message; a routing failure must not kill the
        calling actor's loop (same contract as Actor._safe_dispatch)."""
        try:
            self._dispatch(msg)
        except Exception:  # noqa: BLE001
            log.error("actor %s: handling message type %d raised",
                      self.name, msg.type_int)
            import traceback
            traceback.print_exc()

    # Outbound path: caller's thread -> wire (or loop back locally);
    # every message type goes through the same route-or-send dispatch.
    # The codec filter stage runs here — per message, gated on the
    # PEER's advertised capability so a passthrough peer keeps getting
    # plain frames (mixed-version clusters stay correct, merely
    # uncompressed).
    def _dispatch(self, msg: Message) -> None:
        if msg.dst != self._zoo.rank:
            self._encode_and_send(msg)
        else:
            self._local_forward(msg)

    def _encode_and_send(self, msg: Message) -> None:
        """Outbound tail: settle in-process device payloads, run the
        codec filter for capable peers, submit to the destination's
        peer queue, and route any transport failure into the
        synthesized-error path. The chaos harness's frame faults
        (-chaos_frames, util/chaos.py) hook HERE — one message-level
        choke point for every communicator-routed frame on either
        transport; a dropped frame counts as sent."""
        faulted = chaos.filter_frames(msg)
        if faulted is not None:
            for m in faulted:
                self._encode_and_send_real(m)
            return
        self._encode_and_send_real(msg)

    def _encode_and_send_real(self, msg: Message) -> None:
        if self._net.in_process and self._net.size > 1 \
                and any(b.on_device for b in msg.data):
            # Materialize device payloads BEFORE they cross into a
            # sibling virtual rank (LocalFabric multi-rank = tests
            # and single-host multi-rank runs only; real one-zoo-
            # per-process deployments never take this branch). A
            # sibling's jit consuming a still-in-flight foreign
            # array can wedge XLA's CPU runtime on a small host:
            # the consumer occupies the execution pool waiting for
            # a producer that needs the pool to run (the cross-rank
            # twin of the Server._table_lock deadlock, observed as
            # a server gather parked forever on a worker-produced
            # id array in test_ps_device_pipeline_two_workers).
            import jax
            for blob in msg.data:
                if blob.on_device:
                    jax.block_until_ready(blob.data)
        if self._codec and \
                self._zoo.peer_caps(msg.dst) & CAP_WIRE_CODEC and \
                not (self._shm_probe is not None
                     and self._shm_probe(msg.dst)):
            encode_message(msg)
        try:
            # send_async: enqueue on the destination's peer state
            # machine and return. The call blocks only under that
            # peer's -send_queue_mb backpressure (timed waits), never
            # on a socket; a peer already marked dead raises the
            # parked PeerLostError immediately.
            self._net.send_async(msg)
        except Exception as exc:  # noqa: BLE001 - a dead peer must
            # not strand the requester's waiter (the actor loop
            # would only log): synthesize the error reply the peer
            # can no longer send, so wait() raises a retryable
            # PeerLostError instead of blocking forever.
            self._on_send_failed(msg, exc)

    def _on_send_failed(self, msg: Message, exc: BaseException) -> None:
        log.error("rank %d: send of %r to rank %d failed: %s",
                  self._zoo.rank, msg, msg.dst, exc)
        if msg.type_int == int(MsgType.Request_ReplicaSync):
            # Best-effort fire-and-forget refresh: no waiter exists to
            # strand, and a dead HOLDER must not escalate into aborting
            # the owner. But the lost chunk's rows must be RE-DIRTIED at
            # the owner — a later watermark-carrying flush would
            # otherwise certify the holder's un-refreshed entries as
            # current, and the worker's read-your-writes floor would
            # accept pre-write values (the holder's sync-seq gap guard
            # is the backstop; this echo is the proactive heal). A real
            # inbound sync always carries the OWNER's src rank, so the
            # server actor recognizes the echo by src == own rank.
            if is_wire_encoded(msg):
                decode_message(msg)
            if self._zoo._actors.get(actors.SERVER) is not None:
                self._zoo.route(actors.SERVER, msg)
            return
        reason = f"{PEER_LOST_MARK} rank {msg.dst} unreachable: {exc}"
        if msg.type_int in (int(MsgType.Request_FwdGet),
                            int(MsgType.Request_FwdAdd)):
            # A FORWARDED request's requester lives on another rank
            # (this rank relayed it into a dual-read window,
            # docs/SHARDING.md): synthesize the retryable error toward
            # THAT rank's worker, and report the dead destination so
            # the controller's monitor rolls the move back.
            reply_type = MsgType.Reply_Get \
                if msg.type_int == int(MsgType.Request_FwdGet) \
                else MsgType.Reply_Add
            if msg.msg_id >= 0:
                reply = Message(src=self._zoo.rank, dst=msg.src,
                                msg_type=reply_type,
                                table_id=msg.table_id,
                                msg_id=msg.msg_id)
                mark_error(reply, RuntimeError(reason))
                if reply.dst != self._zoo.rank:
                    self._dispatch(reply)
                else:
                    self._local_forward(reply)
            self._zoo.peer_lost(msg.dst, f"send failed: {exc}")
            return
        reply = self._synth_error_reply(msg, reason)
        if reply is not None:
            self._local_forward(reply)
            return
        # Control traffic (or a reply toward the dead peer): nothing to
        # synthesize locally — report the peer so the zoo can decide
        # (abort, or fail that rank's in-flight work).
        self._zoo.peer_lost(msg.dst, f"send failed: {exc}")

    def _synth_error_reply(self, msg: Message,
                           reason: str) -> Optional[Message]:
        """The error reply a request's server can no longer (or not
        yet) send, built locally so the requester's waiter completes
        with a retryable failure instead of hanging. None for
        non-request messages."""
        msg_type = msg.type_int
        if msg_type in (int(MsgType.Request_Get), int(MsgType.Request_Add)):
            reply = msg.create_reply_message()
            mark_error(reply, RuntimeError(reason))
            return reply
        if msg_type == int(MsgType.Request_BatchAdd):
            # Per-sub failed acks from the request's own descriptor
            # (blob 0: [n, (table_id, msg_id, n_blobs)...]) — a
            # whole-batch error reply would make the worker abort every
            # table, which is the wrong severity for a retryable peer
            # loss.
            reply = msg.create_reply_message()
            try:
                req = msg.data[0].as_array(np.int32)
                desc = [int(req[0])]
                text = np.frombuffer(reason.encode(errors="replace"),
                                     np.uint8).copy()
                err_blobs = []
                for i in range(int(req[0])):
                    desc.extend((int(req[1 + 3 * i]), int(req[2 + 3 * i]),
                                 1, -1))
                    err_blobs.append(Blob(text.copy()))
                reply.push(Blob(np.asarray(desc, dtype=np.int32)))
                reply.data.extend(err_blobs)
            except Exception:  # noqa: BLE001 - undecodable batch (e.g.
                # already codec-encoded): fall back to the whole-batch
                # error; the worker's loud-abort path is still better
                # than a silent hang.
                mark_error(reply, RuntimeError(reason))
            return reply
        return None

    # Inbound path: wire -> local actor mailboxes
    # (ref: src/communicator.cpp:77-91).
    def _recv_main(self) -> None:
        codec_in = bool(get_flag("wire_codec"))
        while True:
            msg = self._net.recv()
            if msg is None:
                break
            # Traffic from a declared-dead rank means its restarted
            # process is back: clear the death mark so a SECOND death
            # of the same rank is reported fresh (peer_lost dedups on
            # the mark) — cheap set probe on the common path.
            self._zoo.notice_peer_alive(msg.src)
            if is_wire_encoded(msg):
                if not codec_in:
                    # A peer encoded toward a rank that never advertised
                    # the codec: negotiation bug. Fail loudly instead of
                    # routing garbage bytes into table logic.
                    log.error("rank %d: codec frame received but "
                              "-wire_codec is off; dropping message %r",
                              self._zoo.rank, msg)
                    continue
                try:
                    decode_message(msg)
                except Exception:  # noqa: BLE001 - poison frame must
                    # not kill the recv thread (every later message
                    # would silently vanish)
                    log.error("rank %d: undecodable codec frame %r",
                              self._zoo.rank, msg)
                    import traceback
                    traceback.print_exc()
                    continue
            self._safe_dispatch(msg)

    # Routing rule (ref: src/communicator.cpp:13-29).
    def _local_forward(self, msg: Message) -> None:
        msg_type = int(msg.type_int)
        # Fault-tolerance control frames are intercepted BY NAME before
        # the band rules: both are < -32, so the fallthrough would park
        # them in the Zoo mailbox where a blocked barrier() would
        # consume them and trip its reply-type assert.
        if msg_type == int(MsgType.Control_Reply_Heartbeat):
            self._zoo.note_controller_alive()
            return
        if msg_type == int(MsgType.Control_Reply_Serving):
            # Fleet-aggregate serving pressure from the controller
            # (docs/SERVING.md fleet section): parsed here and stored
            # on the zoo for /v1/status — like the heartbeat reply, it
            # must not fall through to the Zoo mailbox.
            try:
                import json
                doc = json.loads(msg.text_payload())
            except Exception:  # noqa: BLE001 - a malformed aggregate
                # must not kill the recv thread; the next report
                # replaces it
                log.error("rank %d: undecodable serving-fleet reply",
                          self._zoo.rank)
                return
            self._zoo.note_serving_fleet(doc)
            return
        if msg_type == int(MsgType.Control_Dead_Peer):
            dead = int(msg.data[0].as_array(np.int32)[0]) if msg.data \
                else -1
            self._zoo.peer_lost(dead, "declared dead by the controller's "
                                      "liveness monitor")
            return
        if msg_type == int(MsgType.Control_Shard_Map):
            # Epoch-stamped shard-map broadcast (elastic resharding,
            # docs/SHARDING.md): the worker's tables re-route, the
            # server's tables commit/prune migration state — cloned to
            # each actor like Control_Replica_Map below.
            for name in (actors.WORKER, actors.SERVER):
                if self._zoo._actors.get(name) is not None:
                    copy = Message(src=msg.src, dst=msg.dst,
                                   msg_type=MsgType.Control_Shard_Map,
                                   table_id=msg.table_id)
                    copy.data = list(msg.data)
                    self._zoo.route(name, copy)
            return
        if msg_type == int(MsgType.Control_Config):
            # Epoch-stamped live-config broadcast (closed-loop
            # autotune, docs/AUTOTUNE.md): applied HERE through the
            # dynamic-flag layer — set_flag + per-flag apply hooks so
            # construction-time caches re-knob — then acked back to
            # the controller so its gauges show per-rank convergence.
            # Like Control_Shard_Map it must not fall through to the
            # Zoo mailbox.
            self._apply_config(msg)
            return
        if msg_type == int(MsgType.Control_Replica_Map):
            # Promoted-row map broadcast: both sides of this rank need
            # it — the worker's tables re-route their Gets, the
            # server's tables start/stop the owner-side write-through
            # fan-out and prune demoted replica entries. Forward a
            # clone to each actor so each applies it on its own thread
            # (payload blobs are shared read-only).
            for name in (actors.WORKER, actors.SERVER):
                if self._zoo._actors.get(name) is not None:
                    copy = Message(src=msg.src, dst=msg.dst,
                                   msg_type=MsgType.Control_Replica_Map)
                    copy.data = list(msg.data)
                    self._zoo.route(name, copy)
            return
        if is_server_bound(msg_type):
            try:
                self._zoo.route(actors.SERVER, msg)
            except RuntimeError as exc:
                # A REJOINING restarted rank serves its communicator
                # before its server actor and tables exist; a request
                # landing in that window must NACK retryably (the
                # requester backs off and re-issues), not vanish into a
                # log line while its waiter blocks forever.
                reply = self._synth_error_reply(
                    msg, f"{PEER_LOST_MARK} rank {self._zoo.rank}: "
                         f"server not ready ({exc})")
                if reply is None:
                    raise
                log.error("rank %d: NACKing %r — server actor not "
                          "ready", self._zoo.rank, msg)
                self._dispatch(reply)
        elif is_worker_bound(msg_type):
            self._zoo.route(actors.WORKER, msg)
        elif is_controller_bound(msg_type):
            self._zoo.route(actors.CONTROLLER, msg)
        else:
            self._zoo.mailbox.push(msg)

    def _apply_config(self, msg: Message) -> None:
        """Apply one ``Control_Config`` broadcast through the dynamic-
        flag layer (util/configure.py ``apply_config``: epoch
        regression ignored, non-tunable flags rejected whole) and ack
        the applied watermark back to the controller. Runs on the recv
        thread — hooks must stay cheap (their contract)."""
        import json
        from ..util import configure
        try:
            doc = json.loads(msg.text_payload())
            epoch = int(doc["epoch"])
            flags = dict(doc["flags"])
        except Exception:  # noqa: BLE001 - a malformed broadcast must
            # not kill the recv thread; the controller's next broadcast
            # supersedes it
            log.error("rank %d: undecodable Control_Config broadcast",
                      self._zoo.rank)
            return
        try:
            applied = configure.apply_config(epoch, flags)
        except Exception as exc:  # noqa: BLE001 - a refused broadcast
            # (non-tunable flag, garbage value: controller bug or
            # version skew) was rejected WHOLE and must not kill the
            # recv thread — say so loudly, and ack the UNCHANGED
            # watermark so the controller sees this rank not
            # converging.
            log.error("rank %d: Control_Config refused: %s",
                      self._zoo.rank, exc)
            applied = False
        reply = msg.create_reply_message()
        reply.push(Blob(np.array(
            [self._zoo.rank, configure.applied_config_epoch(),
             1 if applied else 0], dtype=np.int64)))
        if reply.dst == self._zoo.rank:
            self._zoo.route(actors.CONTROLLER, reply)
            return
        try:
            # send_async, like every control-plane frame: this thread
            # must never block toward a dead controller.
            self._zoo.net.send_async(reply)
        except Exception as exc:  # noqa: BLE001 - an unreachable
            # controller re-broadcasts; the ack is observability, not
            # correctness
            log.debug("rank %d: config ack failed: %s",
                      self._zoo.rank, exc)

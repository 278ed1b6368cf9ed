"""Zoo: per-rank runtime singleton — bootstrap, routing, barrier.

TPU-native equivalent of the reference's ``Zoo``
(ref: include/multiverso/zoo.h:19-85, src/zoo.cpp:41-188). One Zoo per rank;
a process normally hosts exactly one (the TPU deployment: one JAX process,
role=ALL, tables sharded over the local device mesh), but may host several
*virtual ranks* on a shared ``LocalFabric`` — the moral equivalent of the
reference's ``mpirun -np N`` single-host tests, without MPI.

Start order mirrors the reference (ref: src/zoo.cpp:73-102): controller on
rank 0, communicator, register with the controller to learn the global
rank→worker_id/server_id map, then server and worker actors, then a barrier.
The ``-ma`` flag skips the PS entirely (model-average mode,
ref: src/zoo.cpp:49).
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, Optional

import numpy as np

from ..core.blob import Blob
from ..core.message import Message, MsgType, take_error
from ..core.node import Node, Role, is_server, is_worker, role_from_string
# Imported eagerly so the -serving_* flag definitions are registered
# before Zoo.start parses the command line (the -snapshot_* precedent
# in runtime/server.py). Only the admission half: it is io-/runtime-
# import-free, while the frontend pulls in io/ (-> stream -> this
# module — a cycle at import time) and is therefore loaded lazily in
# _start_serving.
from ..serving import admission as _serving_admission  # noqa: F401
from ..sharding.rows import row_offsets
from ..util import log
from ..util.configure import (define_bool, define_double, define_int,
                              define_string, get_flag, parse_cmd_flags)
from ..util.mt_queue import MtQueue
from . import actor as actors
from .communicator import Communicator
from .controller import Controller
from .net import LocalFabric, NetInterface, PeerLostError
from .server import Server, backup_worker_count
# Imported eagerly so the -shm* flag definitions are registered before
# Zoo.start parses the command line (same reason as the admission
# import above): a lazy import inside _maybe_wrap_shm would register
# them only *after* parse_cmd_flags has already discarded -shm=0.
from . import shm as _shm
from .tcp import TcpNet, take_pending_net
from .worker import Worker

define_string("ps_role", "default", "none / worker / server / default(all)")
define_bool("ma", False, "model-average mode: skip the parameter server")
define_bool("sync", False, "BSP sync server")
define_bool("rejoin", False,
            "this process is a RESTARTED rank rejoining a live cluster: "
            "registration takes the controller's solo-reply path, the "
            "start barrier and table-creation barriers are skipped "
            "(the survivors are long past them), and — with "
            "-snapshot_dir set — server tables restore from the latest "
            "manifest-consistent snapshot as they register")
define_int("rpc_retry_max", 0,
           "how many times a failed sync table Get/Add is re-issued "
           "after a PeerLostError (bounded exponential backoff from "
           "-rpc_backoff_ms). 0 (default) disables the retry path AND "
           "the peer-loss containment that feeds it: a lost peer then "
           "aborts the whole zoo, the pre-fault-tolerance behavior")
define_double("rpc_backoff_ms", 50.0,
              "initial backoff before a PeerLostError retry; doubles "
              "per attempt, capped at 5s")

CONTROLLER_RANK = 0

_ABORT = object()  # mailbox sentinel: unblocks control waits on abort


class ClusterAborted(RuntimeError):
    """Raised out of blocking control calls after Zoo.abort()."""


_tls = threading.local()
_default_zoo: Optional["Zoo"] = None


def current_zoo() -> "Zoo":
    zoo = getattr(_tls, "zoo", None) or _default_zoo
    if zoo is None:
        raise RuntimeError("multiverso not initialized: call mv.init() first")
    return zoo


def set_thread_zoo(zoo: Optional["Zoo"]) -> None:
    _tls.zoo = zoo


class Zoo:
    def __init__(self) -> None:
        self._net: Optional[NetInterface] = None
        self._actors: Dict[str, object] = {}
        self.mailbox: MtQueue = MtQueue()
        self._nodes: List[Node] = []
        self._num_workers = 0
        self._num_servers = 0
        self._started = False
        self._aborted = False
        self._role_override: Optional[str] = None
        self._worker_table_count = 0
        self._server_table_count = 0
        self._server_tables: List = []  # owned for cleanup + checkpoint
        # -- fault tolerance --
        self._rejoining = False
        self._dead_peers: set = set()
        self._heartbeat = None  # HeartbeatMonitor when enabled
        self._last_controller_reply = 0.0
        # -- observability (runtime/metrics.py, io/metrics_http.py) --
        self._metrics_reporter = None
        self._metrics_http = None
        # -- online serving tier (serving/frontend.py, docs/SERVING.md) --
        self._serving = None
        # Last fleet-aggregate serving-pressure view received from the
        # controller (Control_Reply_Serving; written by the
        # communicator recv thread or the controller actor, read by
        # /v1/status handler threads — tuple assignment, GIL-atomic).
        self._serving_fleet: Optional[tuple] = None

    # -- lifecycle (ref: src/zoo.cpp:41-60) --
    def start(self, argv: Optional[List[str]] = None,
              net: Optional[NetInterface] = None,
              role: Optional[str] = None) -> List[str]:
        """``role`` overrides the -ps_role flag for this zoo (the flag
        registry is process-global; virtual ranks with heterogeneous roles
        need a per-zoo override)."""
        remaining = parse_cmd_flags(argv)
        self._rejoining = bool(get_flag("rejoin"))
        self._net = net if net is not None else self._resolve_net()
        if hasattr(self._net, "on_peer_lost"):
            # Failure detection (absent in the reference, SURVEY.md
            # section 5.3): a TCP peer dying mid-run reports through
            # peer_lost — with the retry path off that aborts this zoo
            # so blocked barriers/registrations/table waits raise
            # instead of hanging; with -rpc_retry_max set, only the
            # dead rank's in-flight requests fail (retryably).
            self._net.on_peer_lost = \
                lambda rank=None: self.peer_lost(rank, "connection died")
        self._role_override = role
        if not get_flag("ma"):
            try:
                self._start_ps()
                self._last_controller_reply = time.monotonic()
                interval = float(get_flag("heartbeat_interval_s", 0.0))
                if interval > 0:
                    from .controller import HeartbeatMonitor
                    self._heartbeat = HeartbeatMonitor(self)
                    self._heartbeat.start()
                self._start_observability()
                self._start_serving()
            except BaseException:
                # A sibling rank's abort can land while this rank is
                # still inside the start barrier: the caller never sees
                # _started and skips stop(), which would leave the
                # actor threads spawned above idling in their mailboxes
                # forever. Reap them before surfacing the error.
                try:
                    self._teardown_partial_start()
                except Exception:  # noqa: BLE001 - keep the cause
                    log.error("Rank %d: partial-start teardown raised",
                              self.rank)
                raise
        self._started = True
        log.debug("Rank %d: multiverso started", self.rank)
        return remaining

    def _teardown_partial_start(self) -> None:
        """Stop whatever a failed start() already brought up, in the
        same reverse order stop() uses. Only reached on the error path
        out of start(); barriers/drains are skipped — peers may already
        be gone."""
        for attr in ("_serving", "_metrics_reporter", "_heartbeat",
                     "_metrics_http"):
            obj = getattr(self, attr)
            if obj is not None:
                obj.stop()
                setattr(self, attr, None)
        controller = self._actors.get(actors.CONTROLLER)
        if controller is not None:
            controller.autotune.stop()
        for name in (actors.WORKER, actors.SERVER, actors.CONTROLLER):
            actor = self._actors.get(name)
            if actor is not None:
                actor.stop()
        comm = self._actors.get(actors.COMMUNICATOR)
        if comm is not None:
            comm.stop()
        elif self._net is not None:
            self._net.finalize()
        self._actors.clear()

    def _start_observability(self) -> None:
        """Metrics export (-metrics_interval_s) + the controller-rank
        scrape surface (-metrics_port). After registration, so reports
        can route; no-ops at the default flag values."""
        if float(get_flag("metrics_interval_s", 0.0)) > 0:
            from .metrics import MetricsReporter
            self._metrics_reporter = MetricsReporter(self)
            self._metrics_reporter.start()
        controller = self._actors.get(actors.CONTROLLER)
        if controller is not None \
                and float(get_flag("autotune_interval_s", 0.0)) > 0:
            # Closed-loop self-tuning (runtime/autotune.py,
            # docs/AUTOTUNE.md): controller rank only, after
            # registration — the first broadcast must be routable.
            controller.autotune.start()
        port = int(get_flag("metrics_port", 0))
        if port > 0 and self.rank == CONTROLLER_RANK \
                and controller is not None:
            from ..io.metrics_http import (MetricsHttpServer,
                                           prometheus_route)
            self._metrics_http = MetricsHttpServer(port, {
                "/metrics": prometheus_route(
                    lambda c=controller:
                    c.metrics.prometheus_text()
                    + c.autotune.prometheus_text()),
            })

    def metrics_flush(self) -> None:
        """One immediate metrics report from this rank (deterministic
        final cut before a scrape — pair with a barrier); no-op when
        the reporter is off."""
        if self._metrics_reporter is not None:
            self._metrics_reporter.flush()

    def _start_serving(self) -> None:
        """The online serving frontend (-serving_port,
        docs/SERVING.md) on ranks hosting a worker actor — serving
        reads route through worker tables, so a pure-server rank has
        nothing to serve from. No-op at the default flag value."""
        port = int(get_flag("serving_port", 0))
        if port > 0 and self._actors.get(actors.WORKER) is not None:
            from ..serving.frontend import ServingFrontend
            self._serving = ServingFrontend(self, port)

    @property
    def serving(self):
        """The live ServingFrontend, or None (flag off / no worker)."""
        return self._serving

    def serve_table(self, name: str, worker_table,
                    vocab: Optional[dict] = None) -> None:
        """Expose a worker table on the serving frontend under
        ``/v1/tables/<name>`` (``vocab``: word -> row id, enables the
        neighbors endpoint's word lookups). Safe to call with serving
        off — the registration is simply skipped, so application code
        need not fork on the flag."""
        if self._serving is None:
            log.debug("Rank %d: serve_table(%r) ignored — serving "
                      "frontend off (-serving_port)", self.rank, name)
            return
        self._serving.register_table(name, worker_table, vocab)

    def stop(self, finalize_net: bool = True) -> None:
        """ref: src/zoo.cpp:52-60,104-114."""
        if not self._started:
            return
        if self._serving is not None:
            # FIRST: the frontend's graceful drain needs the worker/
            # communicator stack still alive to finish in-flight reads;
            # once drained, no new HTTP work can reach the actors.
            self._serving.stop()
            self._serving = None
        if self._metrics_reporter is not None:
            self._metrics_reporter.stop()
            self._metrics_reporter = None
        controller = self._actors.get(actors.CONTROLLER)
        if controller is not None:
            # The autotune thread broadcasts through the actors; it
            # must stop before the actor teardown below.
            controller.autotune.stop()
        if self._metrics_http is not None:
            self._metrics_http.stop()
            self._metrics_http = None
        if self._heartbeat is not None:
            self._heartbeat.stop()
            self._heartbeat = None
        if not get_flag("ma"):
            self._stop_ps(finalize_net)
        if finalize_net:
            self._net.finalize()
        self._actors.clear()
        self._server_tables.clear()
        self._started = False
        log.debug("Rank %d: multiverso shut down", self.rank)

    def _resolve_net(self) -> NetInterface:
        """Transport selection after flag parsing: an endpoint prepared by
        net_bind/net_connect wins, then a -machine_file TCP mesh
        (ref: zmq_net.h:25-61), else the single-rank in-process default."""
        pending = take_pending_net()
        if pending is not None:
            return self._maybe_wrap_shm(pending)
        if get_flag("machine_file"):
            return self._maybe_wrap_shm(TcpNet.from_flags())
        return LocalFabric(1).endpoint(0)

    @staticmethod
    def _maybe_wrap_shm(net: NetInterface) -> NetInterface:
        """Layer the shared-memory ring transport over a TCP mesh when
        ``-shm`` is on (runtime/shm.py): co-located peers negotiate
        per-pair rings at registration; everything else stays TCP."""
        if (bool(get_flag("shm")) and _shm.supported()
                and isinstance(net, TcpNet)):
            return _shm.ShmNet(net)
        return net

    def _start_ps(self) -> None:
        role = int(role_from_string(self._role_override
                                    or get_flag("ps_role")))
        self._nodes = [Node(rank=r, role=int(Role.NONE))
                       for r in range(self.net_size)]
        self._nodes[self.rank].role = role
        # Start order is non-trivial (ref: src/zoo.cpp:83-99): the
        # controller must be routable before any register traffic lands.
        if self.rank == CONTROLLER_RANK:
            Controller(self).start()
        Communicator(self).start()
        self._register_node(role)
        if is_server(role):
            Server.get_server(self).start()
        if is_worker(role):
            Worker(self).start()
        if not self._rejoining:
            # A rejoining restarted rank must not enter the start
            # barrier: the survivors passed it long ago, and a fresh
            # Control_Barrier from one rank would poison the NEXT
            # full-cluster barrier's count.
            self.barrier()

    def _stop_ps(self, finalize_net: bool = True) -> None:
        # After an abort the graceful drain (finish_train + barrier) would
        # block on peers that are gone; tear the actors down directly.
        if not self._aborted:
            if get_flag("sync"):
                self.finish_train()
            self.barrier()
        # Reverse start order (ref: src/zoo.cpp:104-113); communicator last
        # so in-flight replies still route.
        for name in (actors.WORKER, actors.SERVER, actors.CONTROLLER):
            actor = self._actors.get(name)
            if actor is not None:
                actor.stop()
        comm = self._actors.get(actors.COMMUNICATOR)
        if comm is not None:
            comm.stop(finalize_net=finalize_net)

    # -- registration protocol (ref: src/zoo.cpp:116-145) --
    def _register_node(self, role: int) -> None:
        from ..util.wire_codec import CAP_WIRE_CODEC
        caps = CAP_WIRE_CODEC if get_flag("wire_codec") else 0
        shm_ok = (bool(get_flag("shm"))
                  and hasattr(self._net, "enable_shm"))
        if shm_ok:
            caps |= _shm.CAP_SHM
        msg = Message(src=self.rank, dst=CONTROLLER_RANK,
                      msg_type=MsgType.Control_Register)
        # Third int advertises wire capabilities (codec negotiation);
        # the fourth a host fingerprint (shm co-location detection).
        # A controller that only reads [:2] still registers this rank,
        # it just never learns the capability — which degrades to
        # passthrough/TCP, the safe direction.
        msg.push(Blob(np.array([self.rank, role, caps,
                                _shm.host_fingerprint()],
                               dtype=np.int32)))
        self.send_to(actors.COMMUNICATOR, msg)
        reply = self._pop_control()
        assert reply is not None and reply.type == MsgType.Control_Reply_Register
        table = reply.data[0].as_array(np.int32).reshape(-1, 4)
        counts = reply.data[1].as_array(np.int32)
        for rank, node_role, worker_id, server_id in table:
            node = self._nodes[rank]
            node.role = int(node_role)
            node.worker_id = int(worker_id)
            node.server_id = int(server_id)
        self._num_workers = int(counts[0])
        self._num_servers = int(counts[1])
        # Per-rank capability vector (reply blob 2). An older controller
        # that doesn't broadcast it leaves every peer at 0 = passthrough.
        if len(reply.data) >= 3:
            self._peer_caps = reply.data[2].as_array(np.int32).copy()
        else:
            self._peer_caps = np.zeros(self.net_size, dtype=np.int32)
        # Shm negotiation (reply blobs 3+4, runtime/shm.py): the
        # controller's per-rank host-id vector plus the cluster-wide
        # segment-naming token. Peers on MY host that advertised
        # CAP_SHM become ring-send targets; an older controller (or a
        # -shm=0 cluster) simply never ships the blobs — TCP stays.
        if shm_ok and len(reply.data) >= 5:
            host_ids = reply.data[3].as_array(np.int32)
            token = int(reply.data[4].as_array(np.int32)[0])
            me = _shm.host_fingerprint()
            peers = [r for r in range(self.net_size)
                     if r != self.rank and r < len(host_ids)
                     and int(host_ids[r]) == me
                     and self.peer_caps(r) & _shm.CAP_SHM]
            if peers:
                self._net.enable_shm(token, peers)
        log.debug("Rank %d registered: workers=%d servers=%d caps=%s",
                  self.rank, self._num_workers, self._num_servers,
                  self._peer_caps.tolist())

    def peer_caps(self, rank: int) -> int:
        """Wire capabilities the peer advertised at registration
        (0 before registration completes / for pre-codec peers)."""
        caps = getattr(self, "_peer_caps", None)
        if caps is None or not 0 <= rank < len(caps):
            return 0
        return int(caps[rank])

    # -- identity --
    @property
    def net(self) -> NetInterface:
        return self._net

    @property
    def rank(self) -> int:
        return self._net.rank if self._net is not None else 0

    @property
    def size(self) -> int:
        return self.net_size

    @property
    def net_size(self) -> int:
        return self._net.size if self._net is not None else 1

    @property
    def num_workers(self) -> int:
        return self._num_workers

    @property
    def num_servers(self) -> int:
        return self._num_servers

    def rank_to_worker_id(self, rank: int) -> int:
        return self._nodes[rank].worker_id

    def rank_to_server_id(self, rank: int) -> int:
        return self._nodes[rank].server_id

    def worker_rank(self, worker_id: int) -> int:
        for node in self._nodes:
            if node.worker_id == worker_id:
                return node.rank
        return -1

    def server_rank(self, server_id: int) -> int:
        for node in self._nodes:
            if node.server_id == server_id:
                return node.rank
        return -1

    @property
    def servers_in_process(self) -> bool:
        """True when EVERY server shard lives in this process — the
        zero-copy device data plane (live ``jax.Array`` blobs in
        requests and replies) is then valid even when the cluster's
        transport is a real wire to other ranks. This is the locality
        rule that lets a co-located worker+server rank keep the fast
        device pipeline in a multi-process deployment (the reference's
        -ps_role split runs such mixed topologies; remote workers use
        the host-batch paths)."""
        if self.net.in_process:
            return True
        return self._num_servers > 0 and all(
            self.server_rank(s) == self.rank
            for s in range(self._num_servers))

    @property
    def worker_id(self) -> int:
        return self.rank_to_worker_id(self.rank)

    @property
    def server_id(self) -> int:
        return self.rank_to_server_id(self.rank)

    # -- actor registry / routing (ref: src/zoo.cpp:64-71,146-149) --
    def register_actor(self, actor) -> None:
        self._actors[actor.name] = actor

    def deregister_actor(self, actor) -> None:
        self._actors.pop(actor.name, None)

    def send_to(self, name: str, msg: Message) -> None:
        actor = self._actors.get(name)
        if actor is None:
            raise RuntimeError(f"no actor named {name!r} on rank {self.rank}")
        actor.receive(msg)

    route = send_to  # alias used by the communicator's inbound path

    # -- abort: unblock every control wait after a peer failure --
    def abort(self) -> None:
        """Mark this zoo dead and wake any thread blocked in barrier(),
        registration, or a table wait. Used by LocalCluster when a
        sibling rank errors and by the TCP transport when a peer
        disconnects — without it, mispaired barriers and requests to the
        dead rank hang forever."""
        self._aborted = True
        self.mailbox.push(_ABORT)
        worker = self._actors.get(actors.WORKER)
        if worker is not None:
            worker.abort_tables(f"rank {self.rank}: cluster aborted")

    # -- fault containment: a lost peer need not kill the zoo --
    @property
    def rejoining(self) -> bool:
        """True while this zoo is a restarted rank rejoining a live
        cluster (-rejoin): collective-creation barriers are skipped."""
        return self._rejoining

    def note_controller_alive(self) -> None:
        """A heartbeat reply arrived (communicator routing)."""
        self._last_controller_reply = time.monotonic()

    # -- serving-fleet pressure (serving/frontend.py, docs/SERVING.md)
    def note_serving_fleet(self, doc: dict) -> None:
        """A fleet-aggregate view arrived (Control_Reply_Serving via
        the communicator's by-name routing, or directly from a
        co-located controller actor)."""
        self._serving_fleet = (doc, time.monotonic())

    def serving_fleet(self) -> Optional[dict]:
        """The last fleet-aggregate serving-pressure view, stamped
        with its local age — None until a report round-trips."""
        ent = self._serving_fleet
        if ent is None:
            return None
        doc, ts = ent
        return {**doc, "age_s": round(time.monotonic() - ts, 3)}

    def controller_silent_for(self) -> float:
        return time.monotonic() - self._last_controller_reply

    def peer_lost(self, rank: Optional[int], reason: str) -> None:
        """A peer died (broken connection, or declared dead by the
        controller's liveness monitor). With the retry path enabled
        (-rpc_retry_max > 0) and the dead rank identified — and not the
        controller, whose loss is unrecoverable — only that rank's
        in-flight table requests fail, with a retryable PeerLostError;
        everything else keeps serving so the rank can restart and
        rejoin. Otherwise this degrades to ``abort()``: the
        pre-fault-tolerance kill-the-zoo behavior.

        BSP (``-sync``) narrows containment: the sync servers count
        exactly one request per worker per step on their vector
        clocks, so a lost SERVER cannot be papered over by re-issuing
        requests (the surviving servers would double-count the step —
        see ``retrying_wait``) and a lost WORKER permanently stalls
        the clocks unless backup workers (-backup_worker_ratio) cover
        its ticks. Only the covered-dead-worker case stays contained
        in sync mode; everything else aborts."""
        if rank == self.rank or self._aborted:
            return
        if rank is not None and rank in self._dead_peers:
            # Already swept (a TCP writer death and the controller's
            # monitor often both report the same corpse); re-running
            # would drop_connection a REPLACEMENT's fresh socket if the
            # rank already rejoined between the two reports.
            return
        retryable = (int(get_flag("rpc_retry_max")) > 0
                     and rank is not None and rank != CONTROLLER_RANK)
        if retryable and get_flag("sync", False):
            node = self._nodes[rank] if rank < len(self._nodes) else None
            retryable = (node is not None
                         and not is_server(node.role)
                         and backup_worker_count(self._num_workers) > 0)
        if not retryable:
            log.error("Rank %d: peer %s lost (%s) — aborting this zoo",
                      self.rank, "?" if rank is None else rank, reason)
            self.abort()
            return
        log.error("Rank %d: peer %d lost (%s) — failing its in-flight "
                  "requests, cluster keeps serving", self.rank, rank,
                  reason)
        self._dead_peers.add(rank)
        if hasattr(self._net, "drop_connection"):
            # Stale outbound state toward the dead peer must go: a
            # restarted process on the same endpoint is a NEW socket.
            self._net.drop_connection(rank)
        worker = self._actors.get(actors.WORKER)
        if worker is not None:
            notice = Message(src=self.rank, dst=self.rank,
                             msg_type=MsgType.Control_Dead_Peer)
            notice.push(Blob(np.array([rank], dtype=np.int32)))
            worker.receive(notice)

    def notice_peer_alive(self, rank: int) -> None:
        """Inbound traffic from a previously-declared-dead rank: its
        restarted process is talking again — clear the death mark so a
        SECOND death of the same rank sweeps again instead of being
        swallowed by peer_lost's idempotency guard."""
        if rank in self._dead_peers:
            self._dead_peers.discard(rank)
            log.info("Rank %d: peer %d is back (traffic resumed)",
                     self.rank, rank)

    def _pop_control(self):
        reply = self.mailbox.pop()
        if reply is _ABORT or self._aborted:
            raise ClusterAborted(f"rank {self.rank}: cluster aborted")
        return reply

    # -- collective control (ref: src/zoo.cpp:152-176) --
    def barrier(self) -> None:
        msg = Message(src=self.rank, dst=CONTROLLER_RANK,
                      msg_type=MsgType.Control_Barrier)
        self.send_to(actors.COMMUNICATOR, msg)
        reply = self._pop_control()
        assert reply is not None and reply.type == MsgType.Control_Reply_Barrier
        error = take_error(reply)
        if error is not None:
            # The controller failed the round: a declared-dead rank
            # stayed gone past -rejoin_grace_s, so the barrier could
            # never have completed. Retryable — a later rejoin lets
            # the next barrier() succeed.
            raise PeerLostError(error)

    # -- live elastic resharding (runtime/shard_map.py,
    #    docs/SHARDING.md) --
    def reshard_table(self, table, server_ids,
                      wait_s: float = 60.0) -> None:
        """Ask the controller to respread ``table`` over exactly
        ``server_ids`` (grow onto standbys / drain a retiring server)
        with live row migration — no stop-the-world. Fire-and-forget
        toward the controller; with ``wait_s`` > 0 this then POLLS the
        worker table's adopted map until its owner set matches (the
        commit broadcast is the only completion signal — there is
        nothing to block on, traffic keeps flowing throughout).

        BSP sync mode refuses (the vector clocks count requests per
        server); tables whose type cannot migrate (sparse matrix,
        array) are NACKed by their server and the move rolls back."""
        if get_flag("sync", False):
            raise RuntimeError("reshard_table: BSP sync mode pins the "
                               "frozen shard map")
        space = table.reshard_space()
        if space <= 0:
            raise ValueError(
                f"table {table.table_id} does not support live "
                f"resharding (docs/SHARDING.md support matrix)")
        target = sorted({int(s) for s in server_ids})
        if not target or target[-1] >= self._num_servers or target[0] < 0:
            raise ValueError(f"bad server id set {target} "
                             f"(num_servers={self._num_servers})")
        msg = Message(src=self.rank, dst=CONTROLLER_RANK,
                      msg_type=MsgType.Control_Shard_Request,
                      table_id=table.table_id)
        msg.push(Blob(np.asarray(
            [space, int(table.reshard_kind())] + target,
            dtype=np.int64)))
        self.send_to(actors.COMMUNICATOR, msg)
        if wait_s <= 0:
            return
        # Poll for the EXACT target layout, not just the owner set —
        # a multi-move plan passes through intermediate maps whose
        # owner set already matches (the first grow move creates the
        # new server's first interval long before the spread evens).
        offsets = row_offsets(space, len(target))
        expected = (list(offsets),
                    [target[i] for i in range(len(offsets) - 1)])
        deadline = time.monotonic() + wait_s
        while time.monotonic() < deadline:
            if self._aborted:
                raise ClusterAborted(
                    f"rank {self.rank}: cluster aborted mid-reshard")
            if table.shard_layout() == expected:
                return
            time.sleep(0.05)
        raise TimeoutError(
            f"reshard of table {table.table_id} to servers {target} "
            f"did not commit within {wait_s}s (layout now: "
            f"{table.shard_layout()}, wanted {expected})")

    def finish_train(self) -> None:
        """Retire this rank's worker from the BSP clocks on all servers."""
        if self.worker_id < 0:
            return
        for server_id in range(self._num_servers):
            msg = Message(src=self.rank, dst=self.server_rank(server_id),
                          msg_type=MsgType.Server_Finish_Train)
            self.send_to(actors.COMMUNICATOR, msg)

    # -- table registration (ref: src/zoo.cpp:178-186) --
    def register_worker_table(self, worker_table) -> int:
        worker = self._actors.get(actors.WORKER)
        if worker is None:
            raise RuntimeError("no worker actor on this rank")
        tid = worker.register_table(worker_table)
        self._worker_table_count = tid + 1
        return tid

    def register_server_table(self, server_table) -> int:
        server = self._actors.get(actors.SERVER)
        if server is None:
            raise RuntimeError("no server actor on this rank")
        tid = server.register_table(server_table)
        self._server_tables.append(server_table)
        self._server_table_count = tid + 1
        return tid

    def server_table_ready(self, server_table) -> None:
        """Table-factory hook: the server table is fully constructed —
        a rejoining rank restores it from the latest snapshot now."""
        server = self._actors.get(actors.SERVER)
        if server is not None:
            server.table_ready(server_table)

    @property
    def server_tables(self) -> List:
        return self._server_tables


def set_default_zoo(zoo: Optional[Zoo]) -> None:
    global _default_zoo
    _default_zoo = zoo

"""Transport layer: abstract NetInterface + in-process fabric.

TPU-native re-design of the reference's transport stack
(ref: include/multiverso/net.h:15-49, src/net.cpp:13-24). The reference
selects MPI or ZeroMQ point-to-point backends at compile time; on TPU the
*data plane* (tensor traffic) rides XLA collectives over ICI inside jitted
programs and never touches this layer — what remains is the *control plane*
(registration, barriers, table-request routing between ranks), for which we
provide:

- ``LocalFabric``/``LocalNet``: an in-process mesh of mailbox queues. One
  Python process hosts N virtual ranks (threads), which is both the
  single-process degenerate mode (rank 0 = worker+server, the reference's
  key testing trick, ref: Test/unittests/multiverso_env.h:9-31) and the
  equivalent of the reference's ``mpirun -np N`` single-host integration
  tests — without needing MPI.
- Multi-host deployment maps to ``jax.distributed`` + one LocalFabric per
  host; cross-host tensor traffic is XLA-over-DCN inside the jitted step,
  so a cross-host control transport is only needed for table RPC: the TCP
  message-stream backend (``tcp.py``) implements this interface, and
  ``shm.py`` wraps it so frames between same-host peers travel through
  per-pair shared-memory rings instead of kernel loopback (negotiated per
  peer at registration; docs/MEMORY.md "Below the socket").

Messages are delivered whole (no serialization needed in-process; device
arrays ride inside Blobs with zero copies).
"""

from __future__ import annotations

from typing import List, Optional

from ..core.message import Message
from ..util.lock_witness import named_condition, named_lock
from ..util.mt_queue import MtQueue


class PeerLostError(RuntimeError):
    """A peer endpoint died while the mesh was supposed to be up: a
    writer thread hit a broken connection, a reader saw a dirty close,
    or the controller's liveness monitor declared the rank dead.
    Raised to senders blocked on that peer (instead of leaving them
    enqueueing into a dead connection) and to table ``wait`` calls whose
    request was in flight toward it. RETRYABLE: with ``-rpc_retry_max``
    set, sync table calls back off and re-issue — a restarted peer that
    rejoins then serves the retry."""


class NetInterface:
    """Abstract transport (ref: include/multiverso/net.h:15-49).

    Transports that can detect peer death (tcp.py) expose an
    ``on_peer_lost`` callback attribute: called with the dead peer's
    rank when known, or ``None`` when a connection died before
    identifying itself. The Zoo installs its failure handler there at
    start."""

    #: True when every rank shares this OS process (messages pass by
    #: reference, so Blob payloads — including device arrays — arrive
    #: zero-copy). Transports that serialize to a wire set this False.
    in_process = False

    @property
    def rank(self) -> int:
        raise NotImplementedError

    @property
    def size(self) -> int:
        raise NotImplementedError

    def send(self, msg: Message) -> int:
        """Dispatch a message toward ``msg.dst``; returns bytes queued."""
        raise NotImplementedError

    def send_async(self, msg: Message) -> int:
        """Queue a message for delivery and return immediately; returns
        bytes queued. Per-destination FIFO order is preserved, both among
        async sends and relative to later blocking ``send`` calls to the
        same peer. The caller must not mutate the message's payload until
        the frame is on the wire (``flush_sends``) — the allreduce engine
        satisfies this by never rewriting a segment it has queued.

        Default: alias of the blocking ``send`` (correct on any
        transport; in-process delivery is already instantaneous).
        Transports with real wire time override this with a writer
        thread so multiple frames can be in flight (tcp.py)."""
        return self.send(msg)

    def flush_sends(self, dst: Optional[int] = None,
                    timeout: Optional[float] = None) -> None:
        """Block until queued async sends (to ``dst``, or all peers) are
        on the wire. No-op on transports whose send is synchronous."""

    #: Total payload bytes this endpoint has pushed toward peers
    #: (wire-framing included where the transport serializes). Tests count
    #: bytes on the wire with it; transports that care override/maintain it.
    @property
    def bytes_sent(self) -> int:
        return 0

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        """Block for the next inbound message; None once finalized."""
        raise NotImplementedError

    def finalize(self) -> None:
        raise NotImplementedError

    def interrupt_recv(self) -> None:
        """Make one pending/future ``recv`` return None without tearing the
        endpoint down (used for non-finalizing shutdown)."""
        self.finalize()

    # -- recv ownership: exactly one consumer may drain the endpoint --
    def acquire_recv_owner(self) -> None:
        """Mark this endpoint as drained by an actor (the communicator's
        recv thread). While owned, the default transport-level allreduce
        must refuse to run: it would race the recv thread for messages and
        corrupt both streams."""
        self._recv_owned = True

    def release_recv_owner(self) -> None:
        self._recv_owned = False

    def allreduce(self, array: "np.ndarray",
                  slot: Optional[int] = None) -> "np.ndarray":
        """Sum-allreduce a host array across ranks (the transport-level
        collective behind MV_Aggregate, ref: mpi_net.h:147-151). The
        default drives the AllreduceEngine over this endpoint's raw
        send/recv (ma mode only — the PS actors must not own the endpoint);
        transports with a native collective override this (LocalNet uses
        shared memory, an MPI-like transport would use its own).

        One engine is cached per endpoint: its stash of early-arriving
        messages must survive across calls, since in back-to-back
        allreduces a fast peer's next-call message can be drained during
        the previous call and would otherwise be lost, deadlocking the
        next collective (per-call generation stamps in the msg_id keep
        such early frames from ever cross-matching).

        FIFO-serialized per endpoint: collectives are matched
        POSITIONALLY across ranks, so execution order must equal
        application call order on every rank. Each call runs in turn
        behind a ticket — taken here on the calling thread, or
        reserved earlier via ``reserve_collective_slot`` and passed as
        ``slot`` (how model_average_async pins its place in line from
        the submitting thread while the work happens on a worker)."""
        if getattr(self, "_recv_owned", False):
            raise RuntimeError(
                "transport-level allreduce (mv.aggregate) requires ma mode "
                "on this transport: the PS actors own the endpoint's recv "
                "stream (start with -ma=true, ref: src/net.cpp:27-35)")
        from .allreduce_engine import AllreduceEngine

        def run():
            engine = getattr(self, "_allreduce_engine", None)
            if engine is None:
                engine = self._allreduce_engine = AllreduceEngine(self)
            return engine.allreduce(array)

        return self._run_collective(run, slot)

    def sharded_average(self, array: "np.ndarray",
                        slot: Optional[int] = None) -> "np.ndarray":
        """Cross-rank MEAN with sharded reduce state: each rank
        reduce-scatters sparse codec frames for the shard it owns,
        divides that shard locally, and allgathers the averaged
        segments (AllreduceEngine.sharded_average — the model-average
        fast path; docs/ALLREDUCE.md). Same ma-mode contract and
        per-endpoint FIFO ticketing as ``allreduce``: sharded averages
        and allreduces issued on one endpoint are matched positionally
        across ranks in call order."""
        if getattr(self, "_recv_owned", False):
            raise RuntimeError(
                "transport-level sharded_average requires ma mode on "
                "this transport: the PS actors own the endpoint's recv "
                "stream (start with -ma=true, ref: src/net.cpp:27-35)")
        from .allreduce_engine import AllreduceEngine

        def run():
            engine = getattr(self, "_allreduce_engine", None)
            if engine is None:
                engine = self._allreduce_engine = AllreduceEngine(self)
            return engine.sharded_average(array)

        return self._run_collective(run, slot)

    # -- per-endpoint collective FIFO --
    def _collective_fifo(self) -> dict:
        # Lazily created; the instance-dict setdefault is atomic under
        # the GIL. The fast-path get avoids building a throwaway
        # dict + Condition per call once initialized (setdefault
        # evaluates its default eagerly).
        state = self.__dict__.get("_coll_fifo")
        if state is None:
            state = self.__dict__.setdefault(
                "_coll_fifo",
                {"next": 0, "serving": 0,
                 "cond": named_condition(f"{self.name}.collective_fifo")})
        return state

    def reserve_collective_slot(self) -> int:
        """Take the next FIFO ticket on THIS thread. Pass it to a later
        ``allreduce(..., slot=...)`` call (possibly from another
        thread) to run that collective in the order the slot was
        reserved rather than the order workers get scheduled."""
        state = self._collective_fifo()
        with state["cond"]:
            slot = state["next"]
            state["next"] += 1
        return slot

    def _run_collective(self, fn, slot: Optional[int] = None):
        state = self._collective_fifo()
        if slot is None:
            slot = self.reserve_collective_slot()
        with state["cond"]:
            state["cond"].wait_for(lambda: state["serving"] == slot)
        try:
            return fn()
        finally:
            with state["cond"]:
                state["serving"] += 1
                state["cond"].notify_all()

    @property
    def name(self) -> str:
        return type(self).__name__


_RECV_INTERRUPT = object()  # sentinel: unblocks recv without finalizing


class LocalFabric:
    """Shared in-process wire: one inbox queue per virtual rank."""

    def __init__(self, size: int):
        if size < 1:
            raise ValueError("fabric needs >= 1 rank")
        self._size = size
        self._inboxes: List[MtQueue] = [
            MtQueue(name=f"fabric.inbox[{r}]") for r in range(size)]
        self._lock = named_lock("fabric.lock")
        # Shared-memory allreduce state (one in-flight collective at a time,
        # like the reference's serialized MPI_Allreduce).
        self._ar_cond = named_condition("fabric.allreduce")
        self._ar_parts = {}  # rank -> contribution for the open collective
        self._ar_result = None
        self._ar_generation = 0

    @property
    def size(self) -> int:
        return self._size

    def endpoint(self, rank: int) -> "LocalNet":
        if not 0 <= rank < self._size:
            raise ValueError(f"rank {rank} out of range [0,{self._size})")
        return LocalNet(self, rank)

    def deliver(self, msg: Message) -> None:
        self._inboxes[msg.dst].push(msg)

    def inbox(self, rank: int) -> MtQueue:
        return self._inboxes[rank]

    def allreduce(self, array, rank: int = -1) -> "np.ndarray":
        import numpy as np
        contribution = np.asarray(array)
        with self._ar_cond:
            generation = self._ar_generation
            # Contributions are kept per rank and summed in RANK order at
            # completion: summing in thread-arrival order would make the
            # float result depend on scheduling, and the MA overlap tests
            # assert sync-vs-async trainer runs are bit-identical.
            self._ar_parts[len(self._ar_parts) if rank < 0 else rank] = \
                contribution
            if len(self._ar_parts) == self._size:
                acc = None
                for r in sorted(self._ar_parts):
                    part = self._ar_parts[r]
                    acc = part.copy() if acc is None else acc + part
                self._ar_result = acc
                self._ar_parts = {}
                self._ar_generation += 1
                self._ar_cond.notify_all()
            else:
                if not self._ar_cond.wait_for(
                        lambda: self._ar_generation > generation,
                        timeout=120):
                    raise TimeoutError(
                        "allreduce: peers never joined the collective")
            # Per-rank copy: a caller mutating its result in place must not
            # corrupt what sibling ranks see.
            return self._ar_result.copy()


class LocalNet(NetInterface):
    in_process = True

    def __init__(self, fabric: LocalFabric, rank: int):
        self._fabric = fabric
        self._rank = rank

    @property
    def rank(self) -> int:
        return self._rank

    @property
    def size(self) -> int:
        return self._fabric.size

    def send(self, msg: Message) -> int:
        if not 0 <= msg.dst < self.size:
            raise ValueError(f"bad dst rank {msg.dst}")
        self._fabric.deliver(msg)
        return sum(b.size for b in msg.data) + 32

    def recv(self, timeout: Optional[float] = None) -> Optional[Message]:
        item = self._fabric.inbox(self._rank).pop(timeout=timeout)
        if item is _RECV_INTERRUPT:
            return None
        return item

    def finalize(self) -> None:
        self._fabric.inbox(self._rank).exit()

    def interrupt_recv(self) -> None:
        self._fabric.inbox(self._rank).push(_RECV_INTERRUPT)

    def allreduce(self, array, slot=None):
        return self._run_collective(
            lambda: self._fabric.allreduce(array, self._rank), slot)

    def sharded_average(self, array, slot=None):
        # Shared memory has no wire to save and no per-rank memory
        # budget to shard (every virtual rank is one process): the
        # native rank-ordered fabric sum + divide is the same
        # deterministic math with none of the frame round trips.
        return self._run_collective(
            lambda: self._fabric.allreduce(array, self._rank)
            / self.size, slot)

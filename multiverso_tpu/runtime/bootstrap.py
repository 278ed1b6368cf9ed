"""Multi-host bootstrap: one call wires both planes.

On a TPU pod each host runs one process; two meshes must come up:

- the DATA plane — ``jax.distributed.initialize`` so XLA sees every
  host's chips and collectives ride ICI/DCN inside jitted steps;
- the CONTROL plane — this framework's TCP message mesh (registration,
  barriers, table RPC), which needs every process's endpoint.

The reference leaves placement to mpirun/machine files
(ref: include/multiverso/net/zmq_net.h:20-28). Here the data plane
jax.distributed already brought up doubles as the rendezvous: the
processes all-gather their control endpoints over it — no machine file,
no second launcher.

    import multiverso_tpu as mv
    mv.init_distributed(coordinator_address="host0:9777",
                        num_processes=16, process_id=rank)
    ...                      # tables, barriers, jitted steps
    mv.shutdown()

With ``num_processes == 1`` (coordinator still required — jax's
cluster auto-detection only fills the arguments inside managed
environments) the call degenerates to the single-process worker+server
mode after initializing jax.distributed, so one launch script scales
from a single host to a pod by changing its arguments.
"""

from __future__ import annotations

import socket
from typing import List, Optional

from ..util import log
from ..util.net_util import outbound_address, reserve_listen_port
from .tcp import net_bind, net_connect

_ENDPOINT_BYTES = 256  # fixed-width slot per process in the allgather


def _reachable_address() -> str:
    """Outbound-interface address (see net_util.outbound_address), with
    hostname/loopback fallbacks for isolated hosts."""
    addr = outbound_address()
    if addr is not None:
        return addr
    try:
        return socket.gethostbyname(socket.gethostname())
    except OSError:
        return "127.0.0.1"


def exchange_endpoints(my_endpoint: str) -> List[str]:
    """All-gather of control endpoints over the data plane
    jax.distributed just brought up: each process contributes its
    ``host:port`` as a fixed-width byte row and
    ``multihost_utils.process_allgather`` returns every row in process
    order."""
    import numpy as np
    from jax.experimental import multihost_utils

    raw = my_endpoint.encode()
    if len(raw) > _ENDPOINT_BYTES:
        raise ValueError(f"control endpoint too long: {my_endpoint!r}")
    row = np.zeros(_ENDPOINT_BYTES, np.uint8)
    row[:len(raw)] = np.frombuffer(raw, np.uint8)
    rows = np.asarray(multihost_utils.process_allgather(row))
    return [bytes(r).rstrip(b"\0").decode() for r in rows]


def init_distributed(coordinator_address: Optional[str] = None,
                     num_processes: Optional[int] = None,
                     process_id: Optional[int] = None,
                     argv: Optional[List[str]] = None,
                     control_port: Optional[int] = None) -> List[str]:
    """Initialize jax.distributed (data plane), rendezvous the TCP
    control mesh with an all-gather over it, and mv.init. Arguments default
    to jax's own cluster-environment auto-detection (TPU pods fill them
    from the runtime). Returns the argv remainder from mv.init."""
    import jax

    if not jax.distributed.is_initialized():
        jax.distributed.initialize(coordinator_address=coordinator_address,
                                   num_processes=num_processes,
                                   process_id=process_id)
    num_processes = jax.process_count()
    process_id = jax.process_index()
    from .. import init as mv_init

    if num_processes <= 1:
        # Single process: worker+server degenerate mode, no TCP needed.
        return mv_init(list(argv or []))

    addr = _reachable_address()
    # Hold the bound reservation socket through the (possibly slow)
    # rendezvous so a sibling process on this host cannot be handed the
    # same port; release it just before TcpNet's listener bind.
    reserved = None
    if control_port is not None:
        port = control_port
    else:
        reserved, port = reserve_listen_port()
    try:
        my_endpoint = f"{addr}:{port}"
        endpoints = exchange_endpoints(my_endpoint)
        log.info("control mesh (%d processes): %s", num_processes,
                 endpoints)
        net_bind(process_id, my_endpoint)
    finally:
        if reserved is not None:
            # Release the reservation only now: net_connect constructs
            # the TCP endpoint (binding the listener) immediately, so
            # the unsafe window is microseconds, not the rendezvous.
            reserved.close()
    net_connect(list(range(num_processes)), endpoints)
    return mv_init(list(argv or []))

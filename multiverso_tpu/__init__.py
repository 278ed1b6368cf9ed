"""multiverso_tpu: a TPU-native parameter-server framework.

Brand-new implementation of the capabilities of Microsoft Multiverso
(the DMTK parameter server) designed for JAX/XLA on TPU. Distributed tables
live as sharded ``jax.Array``s in HBM; server-side optimizers are
jit-compiled donated-buffer updates; model-average mode maps to
``lax.psum`` over the device mesh.

Public API mirrors the reference's ``MV_*`` surface
(ref: include/multiverso/multiverso.h:9-65).
"""

from __future__ import annotations

from typing import List, Optional

from .runtime.net import PeerLostError
from .runtime.zoo import (ClusterAborted, Zoo, current_zoo,
                          set_default_zoo, set_thread_zoo)
from .sharding.mesh import describe_backend
from .tables import (ArrayTableOption, KVTableOption, MatrixTableOption,
                     create_array_table, create_kv_table,
                     create_matrix_table, create_table)
from .tables.table_interface import RpcTimeoutError, TableRequestError
from .updater import AddOption, GetOption
from .util import log
from .util.configure import set_flag as _set_flag

__version__ = "0.1.0"


def init(argv: Optional[List[str]] = None) -> List[str]:
    """MV_Init (ref: src/multiverso.cpp:11-14). Returns remaining argv."""
    log.info("jax backend: %s", describe_backend())
    zoo = Zoo()
    set_default_zoo(zoo)
    return zoo.start(argv)


def shutdown(finalize_net: bool = True) -> None:
    """MV_ShutDown (ref: src/multiverso.cpp:20-23)."""
    from .runtime import zoo as zoo_mod
    zoo = current_zoo()
    zoo.stop(finalize_net)
    # Clear only the slot this zoo actually occupies.
    if getattr(zoo_mod._tls, "zoo", None) is zoo:
        set_thread_zoo(None)
    if zoo_mod._default_zoo is zoo:
        set_default_zoo(None)


def barrier() -> None:
    """MV_Barrier (ref: src/multiverso.cpp:16-18)."""
    current_zoo().barrier()


def reshard_table(worker_table, server_ids,
                  wait_s: float = 60.0) -> None:
    """Respread a table over exactly ``server_ids`` with live row
    migration (grow onto standby servers / drain a retiring one) —
    traffic keeps flowing throughout (docs/SHARDING.md elastic
    resharding)."""
    current_zoo().reshard_table(worker_table, server_ids,
                                wait_s=wait_s)


def serve_table(name: str, worker_table, vocab=None) -> None:
    """Expose a worker table on this rank's online serving frontend
    (``-serving_port``, docs/SERVING.md) under ``/v1/tables/<name>``;
    ``vocab`` (word -> row id) enables the nearest-neighbor endpoint's
    word lookups. No-op when serving is off."""
    current_zoo().serve_table(name, worker_table, vocab)


def rank() -> int:
    return current_zoo().rank


def size() -> int:
    return current_zoo().size


def num_workers() -> int:
    return current_zoo().num_workers


def num_servers() -> int:
    return current_zoo().num_servers


def worker_id() -> int:
    return current_zoo().worker_id


def server_id() -> int:
    return current_zoo().server_id


def set_flag(name: str, value) -> None:
    """MV_SetFlag (ref: src/multiverso.cpp:48-51)."""
    _set_flag(name, value)


def aggregate(data):
    """MV_Aggregate: sum-allreduce a host array across ranks
    (ref: src/multiverso.cpp:53-56, net::Allreduce src/net.cpp:27-35)."""
    return current_zoo().net.allreduce(data)


def net_bind(rank: int, endpoint: str) -> None:
    """MV_NetBind (ref: include/multiverso/multiverso.h:55-59): declare
    this process's rank and ``host:port`` endpoint before ``init``."""
    from .runtime.tcp import net_bind as _net_bind
    _net_bind(rank, endpoint)


def init_distributed(coordinator_address=None, num_processes=None,
                     process_id=None, argv=None, control_port=None):
    """Multi-host bootstrap: jax.distributed (data plane) + the TCP
    control mesh rendezvoused by an all-gather over it + init. See
    runtime/bootstrap.py."""
    from .runtime.bootstrap import init_distributed as _impl
    return _impl(coordinator_address, num_processes, process_id,
                 argv, control_port)


def net_connect(ranks, endpoints) -> None:
    """MV_NetConnect (ref: include/multiverso/multiverso.h:60-64): supply
    peer endpoints and build the TCP mesh consumed by the next ``init``."""
    from .runtime.tcp import net_connect as _net_connect
    _net_connect(list(ranks), list(endpoints))

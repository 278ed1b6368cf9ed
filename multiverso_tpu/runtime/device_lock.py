"""Process-wide serialization of multi-device dispatch (multi-zoo mode).

XLA's CPU runtime executes dispatched computations on a small shared
thread pool (sized to the host's cores — possibly ONE in a container).
A multi-device program (8 virtual CPU shards) can partially occupy the
pool; two such programs in flight from different threads can each hold
resources the other needs and wedge forever. One zoo per process (the
real deployment) serializes naturally through the actor mailboxes and
never hits this; a LocalFabric process hosting SEVERAL virtual ranks
(tests, single-host multi-rank runs) does — observed as a server-side
jitted gather parked forever while a sibling rank's trainer program was
still in flight (test_ps_device_pipeline_two_workers, and the
server-vs-server variant PR 1 fixed with ``Server._table_lock``).

The fix generalizes PR 1's lock: while serialization is ``active()``,
EVERY multi-device dispatch site — server table jits, worker partition
slicing, trainer step programs — takes the ONE process lock and
``settle``s its outputs before releasing it, so at most one device
program is in flight at any moment and none escapes its critical
section still executing. Otherwise ``guard()`` is a no-op context and
``settle`` returns its argument untouched, and dispatch stays fully
asynchronous.

Serialization is active in two cases. ``LocalCluster.run`` turns it on
with ``enable()`` for n > 1 on any multi-device platform. And it is
always on where the platform is XLA's CPU client with more than one
device: there ONE zoo is enough, because its trainer thread and its
server actor thread both dispatch sharded programs (the trainer's step
while the server still applies the previous block's fire-and-forget
Adds). chip_smoke's PS arm on 8 virtual CPU devices died that way in
XLA's rendezvous ("Expected 8 threads to join the rendezvous, but only
7 of them arrived"). The same two threads dispatching concurrently on
the four chips of a real v5e host run to completion without the lock
(CHANGES.md, PR 21), so on an accelerator one zoo keeps its pipelining.
"""

from __future__ import annotations

import contextlib
import functools

from ..util.lock_witness import named_lock, named_rlock

#: The one process-wide device-dispatch lock. ``Server._table_lock`` is
#: this object (kept as a class attribute for its existing callers).
#: RLock: the sync server's drain paths re-enter through Server._process_*.
#: Witnessed only when -debug_locks is set before this module first
#: imports (module-level singleton; see util/lock_witness.py).
TABLE_LOCK = named_rlock("device_lock.TABLE_LOCK")

_NULL = contextlib.nullcontext()
_serialized = 0  # nesting count of active multi-zoo contexts
_state_lock = named_lock("device_lock.state")


@functools.lru_cache(maxsize=None)
def _local_devices() -> tuple:
    """(count, platform) of this process's devices. Computed lazily (jax
    import cost) and cached: neither changes mid-process."""
    import jax
    devices = jax.local_devices()
    return len(devices), devices[0].platform


def _single_device() -> bool:
    """The wedge class this lock exists for is CONCURRENT MULTI-DEVICE
    programs: each such program partially occupies XLA's shared CPU
    execution pool waiting on inter-device rendezvous, and two in
    flight can each hold resources the other needs. A process whose
    platform exposes exactly ONE device never builds those programs —
    its dispatches are ordinary single-device executions, which JAX
    supports from concurrent threads — so serializing (and settling,
    which kills async pipelining) would only cost throughput. Tests run
    under the 8-virtual-device conftest mesh and therefore KEEP the
    lock; a plain one-device process (CPU or one chip) drops it."""
    return _local_devices()[0] == 1


def _cpu_mesh() -> bool:
    """More than one device on XLA's CPU client: every thread that
    dispatches shares one execution pool, so even a single zoo must
    serialize (module docstring)."""
    count, platform = _local_devices()
    return count > 1 and platform == "cpu"


def enable() -> None:
    """Enter multi-zoo mode: serialize + settle all device dispatch
    (no-op on single-device processes — see ``_single_device``)."""
    global _serialized
    if _single_device():
        return
    with _state_lock:
        _serialized += 1


def disable() -> None:
    global _serialized
    if _single_device():
        return
    with _state_lock:
        _serialized -= 1


def active() -> bool:
    return _serialized > 0 or _cpu_mesh()


def guard():
    """Context manager for a device-dispatch site: the process lock
    while serialization is active, a no-op otherwise."""
    return TABLE_LOCK if active() else _NULL


def settle(tree):
    """Block until every device array in ``tree`` has materialized
    (while serialization is active; identity otherwise). Call INSIDE
    the guarded region, on its outputs, so no execution escapes the
    lock."""
    if active():
        import jax
        for leaf in jax.tree_util.tree_leaves(tree):
            if hasattr(leaf, "block_until_ready"):
                leaf.block_until_ready()
    return tree

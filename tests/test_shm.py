"""Shared-memory transport suite (runtime/shm.py, docs/MEMORY.md
"Below the socket").

In-process pairs drive two real TcpNet endpoints wrapped in ShmNet
through the full negotiate/announce/attach cycle; subprocess clusters
prove mixed-transport interop and lifecycle hygiene. A `/dev/shm`
entry — or a resource_tracker warning on stderr — surviving any test
here is a failure, not a flake.
"""

import hashlib
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import Message, MsgType
from multiverso_tpu.runtime import shm
from multiverso_tpu.runtime.shm import ShmNet, _OutRing
from multiverso_tpu.runtime.tcp import TcpNet
from multiverso_tpu.util.configure import get_flag, set_flag
from multiverso_tpu.util.dashboard import Dashboard
from multiverso_tpu.util.net_util import free_listen_port

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    not shm.supported(), reason="POSIX shared memory unavailable")

TOKEN = 0x5EED


def cnt(name):
    return Dashboard.get(name).count


def shm_entries():
    try:
        return sorted(f for f in os.listdir("/dev/shm")
                      if f.startswith("mvshm-"))
    except FileNotFoundError:  # pragma: no cover - non-tmpfs /dev/shm
        return []


#: What a cluster's rank prints so that its segments can be told from
#: those of other test files' clusters, which run beside this file's
#: under xdist (every segment is named by its cluster's token).
PRINT_TOKEN = ('print("SHM_TOKEN %08x" % ((getattr(mv.current_zoo().net, '
               '"_token", None) or 0) & 0xFFFFFFFF))')


def entries_of(outs):
    """The entries of /dev/shm that belong to the clusters whose ranks
    printed ``outs``."""
    tokens = {line.split()[1] for o in outs for line in o.splitlines()
              if line.startswith("SHM_TOKEN")} - {"00000000"}
    assert tokens, outs
    return [f for f in shm_entries() if any(t in f for t in tokens)]


class _Pair:
    """Two loopback TcpNet endpoints wrapped in ShmNet, shm-negotiated
    both ways — the whole transport stack minus the actor layer."""

    def __init__(self, ring_slots=None, slot_kb=None):
        self._saved = {}
        for flag, value in (("shm_ring_slots", ring_slots),
                            ("shm_slot_kb", slot_kb)):
            if value is not None:
                self._saved[flag] = get_flag(flag)
                set_flag(flag, value)
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        self.nets = [ShmNet(TcpNet(r, eps)) for r in range(2)]
        for net in self.nets:
            net.enable_shm(TOKEN, [1 - net.rank])

    def close(self):
        for net in self.nets:
            net.finalize()
        for flag, value in self._saved.items():
            set_flag(flag, value)


@pytest.fixture
def pair(request):
    kwargs = getattr(request, "param", {})
    p = _Pair(**kwargs)
    yield p
    p.close()


def data_msg(src, dst, msg_id, payload):
    msg = Message(src=src, dst=dst, msg_type=MsgType.Request_Get,
                  msg_id=msg_id)
    msg.push(Blob(payload))
    return msg


def test_ring_roundtrip_byte_identical_and_in_place(pair):
    """A single-slot frame crosses the ring byte-identical, lands as a
    read-only view INTO the shared segment (no receive copy), and the
    ring frame counters move while the chunk-copy counter does not."""
    n0, n1 = pair.nets
    payload = np.arange(1024, dtype=np.float32)
    frames_before = cnt("SHM_FRAMES")
    copied_before = cnt("SHM_BYTES_COPIED")
    n0.send(data_msg(0, 1, 7, payload))
    msg = n1.recv(timeout=30)
    assert msg is not None and msg.msg_id == 7
    arr = msg.data[0].as_array(np.float32)
    np.testing.assert_array_equal(arr, payload)
    # In-place contract: pool-backed (a lease rides the blob) and
    # read-only (writing through a shared slot would corrupt the ring).
    assert msg.data[0].pool_backed
    assert not arr.flags.writeable
    assert cnt("SHM_FRAMES") > frames_before
    assert cnt("SHM_BYTES_COPIED") == copied_before
    assert n0.is_shm_peer(1) and n1.is_shm_peer(0)


def test_sync_and_async_sends_stay_fifo(pair):
    """Interleaved sync/async sends arrive FIFO. A reader thread
    drains concurrently: undelivered in-place frames hold their slots,
    so 200 frames through a 16-slot ring NEED a live consumer — the
    production shape (the communicator's recv thread always drains)."""
    n0, n1 = pair.nets
    total = 200
    got, errors = [], []

    def reader():
        try:
            for _ in range(total):
                msg = n1.recv(timeout=30)
                assert msg is not None
                got.append((msg.msg_id,
                            float(msg.data[0].as_array(np.float32)[0])))
                msg = None  # release the slot lease before the ring wraps
        except BaseException as exc:  # pragma: no cover - surfaced below
            errors.append(exc)

    t = threading.Thread(target=reader, daemon=True)
    t.start()
    for i in range(total):
        msg = data_msg(0, 1, i, np.full(64, float(i), np.float32))
        if i % 3 == 0:
            n0.send(msg)
        else:
            n0.send_async(msg)
    n0.flush_sends(timeout=30)
    t.join(timeout=60)
    assert not t.is_alive() and not errors, errors
    assert got == [(i, float(i)) for i in range(total)]


@pytest.mark.parametrize("pair", [{"ring_slots": 2}], indirect=True)
def test_ring_saturation_blocks_writer_not_caller(pair):
    """Satellite 1: a slow reader saturates the 2-slot ring; the writer
    thread blocks with bounded backpressure (counted once per episode),
    every frame still arrives, in order."""
    n0, n1 = pair.nets
    waits_before = cnt("SHM_RING_FULL_WAITS")
    total = 40
    for i in range(total):
        n0.send_async(data_msg(0, 1, i, np.full(256, float(i),
                                                np.float32)))
    # The inbox holds slot leases, so with nobody receiving the ring
    # must fill and the writer must park in _wait_free.
    deadline = time.monotonic() + 20
    while cnt("SHM_RING_FULL_WAITS") == waits_before:
        assert time.monotonic() < deadline, "writer never saturated"
        time.sleep(0.01)
    for i in range(total):
        msg = n1.recv(timeout=30)
        assert msg is not None and msg.msg_id == i, (i, msg)
        msg = None
    n0.flush_sends(timeout=30)
    assert cnt("SHM_RING_FULL_WAITS") > waits_before


@pytest.mark.parametrize("pair", [{"ring_slots": 4}], indirect=True)
def test_pinned_slots_degrade_to_copies_not_deadlock(pair):
    """A consumer sitting on delivered frames (the allreduce engine's
    out-of-order stash is the real-world shape) pins at most half the
    ring: past that, frames copy out through the pool (SHM_PIN_COPIES)
    and the writer keeps flowing — 3x the ring's worth of frames all
    held live, nothing released, no deadlock."""
    n0, n1 = pair.nets
    pins_before = cnt("SHM_PIN_COPIES")
    held = []
    for i in range(12):
        n0.send(data_msg(0, 1, i, np.full(64, float(i), np.float32)))
        msg = n1.recv(timeout=30)
        assert msg is not None and msg.msg_id == i
        held.append(msg)
    assert cnt("SHM_PIN_COPIES") > pins_before
    for i, msg in enumerate(held):
        np.testing.assert_array_equal(msg.data[0].as_array(np.float32),
                                      np.full(64, float(i), np.float32))


@pytest.mark.parametrize("pair", [{"ring_slots": 4}], indirect=True)
def test_parked_slot_recycles_after_view_dies(pair):
    """A numpy view held past its Message parks the slot (the lease's
    weakref probe sees the backing array still alive); once the view
    dies the poller's re-probe frees it and the ring keeps flowing."""
    n0, n1 = pair.nets
    parked_before = cnt("SHM_SLOT_PARKED")
    n0.send(data_msg(0, 1, 0, np.arange(32, dtype=np.float32)))
    msg = n1.recv(timeout=30)
    held = msg.data[0].as_array(np.float32)  # pins the backing array
    msg = None  # lease release sees a live weakref -> park
    deadline = time.monotonic() + 20
    while cnt("SHM_SLOT_PARKED") == parked_before:
        assert time.monotonic() < deadline, "slot never parked"
        time.sleep(0.01)
    np.testing.assert_array_equal(held,
                                  np.arange(32, dtype=np.float32))
    held = None  # now the re-probe can free the slot
    # More frames than remaining slots: delivery proves the parked
    # slot really recycled (the writer would otherwise block forever
    # at wraparound).
    for i in range(1, 9):
        n0.send(data_msg(0, 1, i, np.full(32, float(i), np.float32)))
        msg = n1.recv(timeout=30)
        assert msg is not None and msg.msg_id == i
        msg = None


@pytest.mark.parametrize("pair", [{"ring_slots": 2, "slot_kb": 1}],
                         indirect=True)
def test_oversize_frame_chunks_through_the_pool(pair):
    """A frame bigger than the whole ring streams as chunk slots and
    reassembles through the receive pool — the one counted copy below
    the socket."""
    n0, n1 = pair.nets
    chunked_before = cnt("SHM_CHUNKED_FRAMES")
    copied_before = cnt("SHM_BYTES_COPIED")
    payload = np.random.default_rng(3).random(16384).astype(np.float32)
    # Async submit: the frame is bigger than the whole ring, so the
    # WRITER thread must stall mid-frame until this thread's recv
    # processes the announce and the poller starts freeing chunk slots.
    n0.send_async(data_msg(0, 1, 11, payload))
    msg = n1.recv(timeout=30)
    assert msg is not None and msg.msg_id == 11
    np.testing.assert_array_equal(msg.data[0].as_array(np.float32),
                                  payload)
    n0.flush_sends(timeout=30)
    assert cnt("SHM_CHUNKED_FRAMES") > chunked_before
    assert cnt("SHM_BYTES_COPIED") >= copied_before + payload.nbytes


def test_chaos_frames_apply_to_ring_sends(pair):
    """Satellite 3: -chaos_frames reaches shm sends — a drop=1 spec
    swallows ring-routed data frames exactly as it would TCP ones."""
    n0, n1 = pair.nets
    # Prime the ring so the announce/attach cycle is done before chaos
    # arms (the announce is ctrl-band and must not be dropped here).
    n0.send(data_msg(0, 1, 0, np.zeros(16, np.float32)))
    assert n1.recv(timeout=30) is not None
    dropped_before = cnt("CHAOS_DROPPED")
    set_flag("chaos_frames", "drop=1,classes=data,seed=3")
    try:
        n0.send_async(data_msg(0, 1, 1, np.ones(16, np.float32)))
        n0.flush_sends(timeout=30)
        assert cnt("CHAOS_DROPPED") > dropped_before
        assert n1.recv(timeout=0.4) is None
    finally:
        set_flag("chaos_frames", "")


def test_finalize_unlinks_segments(pair):
    n0, n1 = pair.nets
    for src, dst in ((0, 1), (1, 0)):
        pair.nets[src].send(data_msg(src, dst, 5,
                                     np.zeros(64, np.float32)))
        msg = pair.nets[dst].recv(timeout=30)
        assert msg is not None
        msg = None
    names = {shm._seg_name(TOKEN, 0, 1), shm._seg_name(TOKEN, 1, 0)}
    assert names <= set(shm_entries()), shm_entries()
    pair.close()
    assert not names & set(shm_entries()), shm_entries()


def test_blob_outlives_segment_via_graveyard(pair):
    """Satellite 2 memory-safety half: a zero-copy view kept past
    transport teardown stays valid (the mapping parks on the module
    graveyard instead of unmapping) while the NAME is still unlinked."""
    n0, n1 = pair.nets
    payload = np.arange(128, dtype=np.float32)
    n0.send(data_msg(0, 1, 9, payload))
    msg = n1.recv(timeout=30)
    blob = msg.data[0]
    msg = None
    pair.close()
    assert shm._seg_name(TOKEN, 0, 1) not in shm_entries()
    np.testing.assert_array_equal(blob.as_array(np.float32), payload)


def test_rejoin_create_reaps_stale_segment():
    """Satellite 2: a SIGKILL'd rank's replacement reclaims its own
    stale segment name at create (FileExistsError path) instead of
    failing or leaking."""
    stale = _OutRing.create(TOKEN, 97, 98)  # "dies" without destroy
    name = stale.name
    assert name in shm_entries()
    fresh = _OutRing.create(TOKEN, 97, 98)
    assert fresh.name == name and fresh.nonce != stale.nonce
    fresh.destroy()
    assert name not in shm_entries()
    stale.destroy()  # unmap the simulated-dead mapping; unlink is a no-op


def test_atexit_reap_covers_crashed_process():
    """A process that dies by unhandled exception never reaches
    finalize; the atexit hook unlinks whatever it created."""
    ring = _OutRing.create(TOKEN, 95, 96)
    assert ring.name in shm_entries()
    shm._atexit_reap()
    assert ring.name not in shm_entries()
    ring.destroy()  # unmap; the unlink half is a handled no-op


# ---------------------------------------------------------------------------
# Subprocess clusters: interop + lifecycle hygiene
# ---------------------------------------------------------------------------

PRELUDE = """
import os, sys
import faulthandler
faulthandler.dump_traceback_later(200, exit=True)
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
import multiverso_tpu as mv
rank = int(os.environ["MV_RANK"])
"""


def run_cluster(bodies, timeout=240, expect_rc=None):
    """run_cluster twin (test_net_integration) that also returns
    stderr: every shm cluster test asserts no resource_tracker noise."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=REPO)
    procs = [subprocess.Popen(
        [sys.executable, "-c", PRELUDE.format(repo=REPO) + body],
        env=dict(env, MV_RANK=str(rank)),
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
        for rank, body in enumerate(bodies)]
    outs, errs, failures = [], [], []
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out, err = p.communicate()
            failures.append(f"rank {rank} TIMED OUT:\n{err[-1500:]}")
            continue
        outs.append(out)
        errs.append(err)
        want = 0 if expect_rc is None else expect_rc.get(rank, 0)
        if p.returncode != want and want is not None:
            failures.append(f"rank {rank} rc={p.returncode}:"
                            f"\n{err[-1500:]}")
    assert not failures, "\n---\n".join(failures)
    for rank, err in enumerate(errs):
        assert "resource_tracker" not in err, (
            f"rank {rank} leaked resource_tracker noise:\n{err[-1500:]}")
    return outs


def write_machine_file(tmp_path, n):
    ports = [free_listen_port() for _ in range(n)]
    mf = tmp_path / "machines"
    mf.write_text("".join(f"127.0.0.1:{p}\n" for p in ports))
    return str(mf)


_TABLE_BODY = """
mv.init(["-machine_file={mf}", "-rank=" + str(rank){extra}])
{token}
table = mv.create_array_table(16)
table.add((np.arange(16, dtype=np.float32) + 1.0) * (rank + 1))
mv.barrier()
out = table.get()
mv.barrier()
import hashlib
print("DIGEST", hashlib.sha256(out.astype("<f4").tobytes()).hexdigest())
from multiverso_tpu.util.dashboard import Dashboard
print("SHM_FRAMES", Dashboard.get("SHM_FRAMES").count)
mv.shutdown()
print("TABLE_OK")
"""


def _digests(outs):
    return [line.split()[1] for o in outs for line in o.splitlines()
            if line.startswith("DIGEST")]


def test_mixed_transport_cluster_byte_identical(tmp_path):
    """Satellite 3: 2 shm ranks + 1 -shm=0 TCP rank produce results
    byte-identical to an all-TCP cluster, and the shm pair really does
    ride the rings."""
    n = 3
    mixed = [_TABLE_BODY.format(mf=write_machine_file(tmp_path, n),
                                token=PRINT_TOKEN,
                                extra=', "-shm=0"' if r == 2 else "")
             for r in range(n)]
    outs_mixed = run_cluster(mixed)
    all_tcp = [_TABLE_BODY.format(mf=write_machine_file(tmp_path, n),
                                  token=PRINT_TOKEN, extra=', "-shm=0"')
               for _ in range(n)]
    outs_tcp = run_cluster(all_tcp)
    assert all("TABLE_OK" in o for o in outs_mixed + outs_tcp)
    dig_mixed, dig_tcp = _digests(outs_mixed), _digests(outs_tcp)
    assert len(set(dig_mixed)) == 1 and len(set(dig_tcp)) == 1
    assert dig_mixed[0] == dig_tcp[0], (dig_mixed, dig_tcp)
    frames = {r: int(line.split()[1])
              for r, o in enumerate(outs_mixed) for line in o.splitlines()
              if line.startswith("SHM_FRAMES")}
    # The co-located shm pair used its rings; the -shm=0 rank did not.
    assert frames[0] > 0 or frames[1] > 0, frames
    assert frames[2] == 0, frames
    assert all(int(line.split()[1]) == 0 for o in outs_tcp
               for line in o.splitlines()
               if line.startswith("SHM_FRAMES"))
    assert not entries_of(outs_mixed), shm_entries()


def test_sigkill_and_survivor_reap(tmp_path):
    """Satellite 2: a rank SIGKILLs itself mid-run (no goodbye, no
    atexit); the survivor aborts cleanly and reaps the dead rank's
    segment at finalize — /dev/shm ends empty."""
    mf = write_machine_file(tmp_path, 2)
    survivor = f"""
from multiverso_tpu.runtime.zoo import ClusterAborted
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
{PRINT_TOKEN}
table = mv.create_array_table(4)
table.add(np.ones(4, np.float32))
mv.barrier()
try:
    mv.barrier()
except ClusterAborted:
    print("ABORTED_OK")
mv.shutdown(finalize_net=True)
"""
    dier = f"""
import signal
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
table = mv.create_array_table(4)
table.add(np.ones(4, np.float32))
mv.barrier()
os.kill(os.getpid(), signal.SIGKILL)
"""
    outs = run_cluster([survivor, dier],
                       expect_rc={0: 0, 1: -9})
    assert "ABORTED_OK" in outs[0], outs[0]
    assert not entries_of(outs), shm_entries()

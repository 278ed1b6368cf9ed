"""Multi-process integration tests over the TCP transport.

The reference gates distributed correctness by really running
``mpirun -np 4 multiverso.test kv|array|net|allreduce``
(ref: deploy/docker/Dockerfile:100-110, Test/main.cpp:12-25). The moral
equivalent here: N OS processes over localhost TCP, machine-file
bootstrapped, running the same actor/table stack end to end —
raw transport ping-pong (ref: Test/test_net.cpp:9-90), sync-mode BSP adds
and gets (ref: Test/test_array_table.cpp:11-47), and ``-ma`` allreduce
(ref: Test/test_allreduce.cpp:10-19).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, REPO)
from multiverso_tpu.util.net_util import free_listen_port  # noqa: E402

# Children force the CPU platform before their first jax import, like
# every test, and need a small virtual device mesh.
PRELUDE = """
import os, sys
import faulthandler
faulthandler.dump_traceback_later(200, exit=True)  # self-report hangs
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
import multiverso_tpu as mv
rank = int(os.environ["MV_RANK"])
"""


def run_cluster(bodies, timeout=240):
    """Spawn one python per body; body i runs with MV_RANK=i. Returns
    the stdout of each after asserting all exited cleanly."""
    procs = []
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
        PYTHONPATH=REPO,
    )
    for rank, body in enumerate(bodies):
        code = PRELUDE.format(repo=REPO) + body
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code],
            env=dict(env, MV_RANK=str(rank)),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    outs = []
    failures = []
    timed_out = False
    for rank, p in enumerate(procs):
        try:
            out, err = p.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
            for q in procs:
                q.kill()
            out, err = p.communicate()
            failures.append(f"rank {rank} TIMED OUT:\n{err[-1500:]}")
            continue
        outs.append(out)
        if p.returncode != 0:
            state = "killed after sibling timeout" if timed_out \
                else f"rc={p.returncode}"
            failures.append(f"rank {rank} {state}:\n{err[-1500:]}")
    assert not failures, "\n---\n".join(failures)
    return outs


def write_machine_file(tmp_path, n):
    ports = [free_listen_port() for _ in range(n)]
    mf = tmp_path / "machines"
    mf.write_text("".join(f"127.0.0.1:{p}\n" for p in ports))
    return str(mf), ports


def test_raw_transport_pingpong(tmp_path):
    # ref: Test/test_net.cpp:9-90 — multi-blob message send/recv without
    # the actor stack.
    mf, ports = write_machine_file(tmp_path, 2)
    eps = [f"127.0.0.1:{p}" for p in ports]
    common = f"""
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import Message, MsgType
from multiverso_tpu.runtime.tcp import TcpNet
net = TcpNet(rank, {eps!r})
"""
    body0 = common + """
msg = Message(src=0, dst=1, msg_type=MsgType.Request_Get, msg_id=7)
msg.push(Blob(np.arange(5, dtype=np.int32).view(np.uint8)))
msg.push(Blob(np.linspace(0, 1, 6, dtype=np.float32)))
net.send(msg)
reply = net.recv(timeout=60)
assert reply is not None and reply.msg_id == 7, reply
assert reply.type == MsgType.Reply_Get
np.testing.assert_array_equal(reply.data[0].as_array(np.int32),
                              np.arange(5, dtype=np.int32))
np.testing.assert_allclose(reply.data[1].as_array(np.float32),
                           np.linspace(0, 1, 6, dtype=np.float32))
net.finalize()
print("PINGPONG_OK")
"""
    body1 = common + """
msg = net.recv(timeout=60)
assert msg is not None and msg.src == 0 and msg.dst == 1
reply = msg.create_reply_message()
reply.data = list(msg.data)
net.send(reply)
net.recv(timeout=10)  # drain until peer closes (returns None)
net.finalize()
print("ECHO_OK")
"""
    outs = run_cluster([body0, body1])
    assert "PINGPONG_OK" in outs[0] and "ECHO_OK" in outs[1]


def test_four_process_bsp_sync(tmp_path):
    # The mpirun -np 4 array-table gate, BSP flavor: every worker's i-th
    # get sees exactly all workers' i-th adds
    # (ref: Test/test_array_table.cpp:11-47, src/server.cpp:61-222).
    n = 4
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
mv.init(["-machine_file={mf}", "-rank=" + str(rank), "-sync=true"])
table = mv.create_array_table(8)
seen = []
for it in range(3):
    table.add(np.full(8, 1.0, np.float32))
    out = table.get()
    seen.append(float(out[0]))
assert seen == [{n}.0, {2 * n}.0, {3 * n}.0], seen
mv.shutdown()
print("BSP_OK", seen)
"""
    outs = run_cluster([body] * n)
    assert all("BSP_OK" in o for o in outs)


def test_four_process_matrix_and_kv(tmp_path):
    # Row-sharded matrix + kv over 4 real processes (async mode with
    # barriers, ref: Test/test_matrix_table.cpp, test_kv.cpp).
    n = 4
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
matrix = mv.create_matrix_table(10, 3)
if rank == 0:
    matrix.add_rows(np.array([0, 9], np.int32), np.ones((2, 3), np.float32))
kv = mv.create_kv_table()
kv.add([rank], [float(rank + 1)])
mv.barrier()
out = matrix.get()
assert out.sum() == 6.0, out
got = kv.get([0, 1, 2, 3])
assert [got[k] for k in range(4)] == [1.0, 2.0, 3.0, 4.0], got
mv.barrier()
mv.shutdown()
print("TABLES_OK")
"""
    outs = run_cluster([body] * n)
    assert all("TABLES_OK" in o for o in outs)


def test_ma_allreduce_over_tcp(tmp_path):
    # -ma mode: no PS actors; MV_Aggregate drives the hand-rolled
    # allreduce engine over raw TCP send/recv
    # (ref: Test/test_allreduce.cpp:10-19). Small (<4KB allgather path)
    # and large (reduce-scatter path) payloads, back to back — the
    # persistent engine stash must carry between calls.
    n = 4
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
mv.init(["-machine_file={mf}", "-rank=" + str(rank), "-ma=true"])
small = mv.aggregate(np.full(4, float(rank + 1), np.float32))
np.testing.assert_allclose(small, np.full(4, 10.0))
big = mv.aggregate(np.full(4096, 1.0, np.float32) * (rank + 1))
np.testing.assert_allclose(big, np.full(4096, 10.0))
again = mv.aggregate(np.arange(3, dtype=np.float32))
np.testing.assert_allclose(again, np.arange(3) * {n})
mv.shutdown()
print("MA_OK")
"""
    outs = run_cluster([body] * n)
    assert all("MA_OK" in o for o in outs)


def test_ma_ring_allreduce_over_tcp(tmp_path):
    # The chunked pipelined ring path over real OS processes: 3 ranks
    # (non-power-of-two, so no surplus fold), forced ring with small
    # chunks so the sliding window and the writer threads actually
    # carry multiple frames in flight; then the int8 lossy tier with
    # its error-feedback residual across back-to-back calls.
    n = 3
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
mv.init(["-machine_file={mf}", "-rank=" + str(rank), "-ma=true",
         "-allreduce_algo=ring", "-allreduce_chunk_kb=64"])
big = mv.aggregate(np.full(300000, 1.0, np.float32) * (rank + 1))
np.testing.assert_allclose(big, np.full(300000, 6.0), rtol=1e-5)
rng = np.random.default_rng(rank)
odd = mv.aggregate(np.arange(120001, dtype=np.float32))
np.testing.assert_allclose(odd, np.arange(120001) * {n}, rtol=1e-5)
mv.set_flag("allreduce_lossy", True)
vals = (np.sign(np.random.default_rng(7).standard_normal(200000))
        * np.random.default_rng(8).uniform(0.5, 1.5, 200000)
        ).astype(np.float32)
lossy = mv.aggregate(vals)
np.testing.assert_allclose(lossy, vals * {n}, rtol=0.05, atol=0.2)
lossy2 = mv.aggregate(vals)
np.testing.assert_allclose(lossy2, vals * {n}, rtol=0.05, atol=0.2)
mv.shutdown()
print("MA_RING_OK")
"""
    outs = run_cluster([body] * n)
    assert all("MA_RING_OK" in o for o in outs)


def test_aggregate_refused_while_ps_owns_endpoint(tmp_path):
    # Outside ma mode the communicator's recv thread owns the endpoint;
    # a transport-level allreduce would race it for inbound messages, so
    # mv.aggregate must refuse loudly instead of corrupting both streams.
    n = 2
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
try:
    mv.aggregate(np.ones(4, np.float32))
except RuntimeError as e:
    assert "ma mode" in str(e), e
    print("GUARD_OK")
else:
    print("GUARD_MISSING")
mv.barrier()
mv.shutdown()
"""
    outs = run_cluster([body] * n)
    assert all("GUARD_OK" in o for o in outs)


def test_net_bind_connect_bootstrap(tmp_path):
    # App-driven deployment without a machine file: MV_NetBind +
    # MV_NetConnect parity (ref: include/multiverso/multiverso.h:55-64).
    ports = [free_listen_port(), free_listen_port()]
    eps = [f"127.0.0.1:{p}" for p in ports]
    body = f"""
eps = {eps!r}
peer = 1 - rank
mv.net_bind(rank, eps[rank])
mv.net_connect([peer], [eps[peer]])
mv.init([])
table = mv.create_array_table(6)
table.add(np.full(6, float(rank + 1), np.float32))
mv.barrier()
np.testing.assert_allclose(table.get(), np.full(6, 3.0))
mv.barrier()
mv.shutdown()
print("BINDCONNECT_OK")
"""
    outs = run_cluster([body] * 2)
    assert all("BINDCONNECT_OK" in o for o in outs)


def test_mixed_version_codec_negotiation(tmp_path):
    # Rank 0 runs with the wire codec, rank 1 emulates a pre-codec peer
    # (-wire_codec=false: advertises nothing, encodes nothing, and will
    # NOT decode). Negotiation must keep every frame toward rank 1
    # plain, so the cluster works end to end — merely uncompressed in
    # that direction — with exact values both ways.
    n = 2
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
flags = ["-machine_file={mf}", "-rank=" + str(rank)]
if rank == 1:
    flags.append("-wire_codec=false")
mv.init(flags)
zoo = mv.current_zoo()
from multiverso_tpu.util.wire_codec import CAP_WIRE_CODEC
assert zoo.peer_caps(0) & CAP_WIRE_CODEC, zoo._peer_caps
assert not zoo.peer_caps(1) & CAP_WIRE_CODEC, zoo._peer_caps
matrix = mv.create_matrix_table(64, 33, is_sparse=True)
if rank == 0:
    delta = np.zeros((3, 33), np.float32)
    delta[:, 5] = [1.5, -2.0, 3.25]
    matrix.add_rows(np.array([0, 31, 63], np.int32), delta)
mv.barrier()
out = matrix.get()
assert out[0, 5] == 1.5 and out[31, 5] == -2.0 and out[63, 5] == 3.25, out
assert abs(out.sum() - 2.75) < 1e-6, out.sum()
mv.barrier()
mv.shutdown()
print("MIXED_CODEC_OK")
"""
    outs = run_cluster([body] * n)
    assert all("MIXED_CODEC_OK" in o for o in outs)


def test_coalesced_adds_over_tcp(tmp_path):
    # Async-mode burst of Adds: the worker must coalesce shards bound
    # for the same server into Request_BatchAdd frames (observable via
    # the server-side dashboard monitor), every ack must arrive (the
    # final wait() returns), and the summed result must be exact. One
    # sub-add carries bad row ids: its error must come back through the
    # batched ack without poisoning the siblings.
    n = 2
    mf, _ = write_machine_file(tmp_path, n)
    body = f"""
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
table = mv.create_array_table(32)
matrix = mv.create_matrix_table(8, 4)  # collective: servers on BOTH ranks
if rank == 1:
    ids = [table.add_async(np.full(32, 1.0, np.float32))
           for _ in range(20)]
    for i in ids:
        table.wait(i)
    from multiverso_tpu.tables.table_interface import TableRequestError
    ok1 = matrix.add_rows_async(np.array([2], np.int32),
                                np.ones((1, 4), np.float32))
    # A doomed whole-table add rides the same burst: 5 floats against a
    # 4x4 shard passes partition (host-side slicing is silent) and
    # fails the SERVER-side size CHECK — its error must come back
    # through the (possibly batched) ack without poisoning siblings.
    from multiverso_tpu.core.blob import Blob
    doomed = matrix.add_async_raw(
        Blob(np.array([-1], np.int32).view(np.uint8)),
        Blob(np.ones(5, np.float32)))
    ok2 = matrix.add_rows_async(np.array([3], np.int32),
                                np.full((1, 4), 2.0, np.float32))
    matrix.wait(ok1)
    try:
        matrix.wait(doomed)
        raise SystemExit("BATCH_ERROR_LOST")
    except TableRequestError:
        pass
    matrix.wait(ok2)
    buf = matrix.get()
    assert np.allclose(buf[2], 1.0) and np.allclose(buf[3], 2.0), buf
    from multiverso_tpu.util.dashboard import Dashboard
    flushes = Dashboard.get("WORKER_COALESCE_FLUSH").count
    # The 20-add burst outruns the worker actor (it serializes and
    # ships each shard over a real socket), so at least one multi-add
    # batch must have formed — without this assert, a regression that
    # silently disables staging would leave the test green via the
    # plain per-shard path.
    assert flushes >= 1, flushes
    print("BATCH_FLUSHES", flushes)
mv.barrier()
out = table.get()
assert np.allclose(out, 20.0), out
mv.barrier()
mv.shutdown()
print("COALESCE_OK", rank)
"""
    outs = run_cluster([body] * n)
    assert all("COALESCE_OK" in o for o in outs)


def test_peer_death_aborts_instead_of_hanging(tmp_path):
    # Failure detection (absent in the reference — a dead MPI rank hangs
    # the cluster, SURVEY.md section 5.3): when a peer process dies
    # mid-run, survivors blocked in barrier() or a table wait must raise
    # ClusterAborted instead of blocking forever.
    mf, _ = write_machine_file(tmp_path, 2)
    survivor = f"""
import multiverso_tpu as mv
from multiverso_tpu.runtime.zoo import ClusterAborted
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
table = mv.create_array_table(4)
table.add(np.ones(4, np.float32))
mv.barrier()  # both ranks alive here
try:
    mv.barrier()  # rank 1 dies instead of joining this one
    print("BARRIER_RETURNED")
except ClusterAborted:
    print("ABORTED_OK")
mv.shutdown(finalize_net=True)
"""
    dier = f"""
import os
import multiverso_tpu as mv
mv.init(["-machine_file={mf}", "-rank=" + str(rank)])
table = mv.create_array_table(4)
table.add(np.ones(4, np.float32))
mv.barrier()
os._exit(1)  # crash without goodbye frames
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=REPO)
    procs = [subprocess.Popen([sys.executable, "-c",
                               PRELUDE.format(repo=REPO) + body],
                              env=dict(env, MV_RANK=str(rank)),
                              stdout=subprocess.PIPE,
                              stderr=subprocess.PIPE, text=True)
             for rank, body in enumerate([survivor, dier])]
    try:
        out0, err0 = procs[0].communicate(timeout=180)
        procs[1].communicate(timeout=60)
    except subprocess.TimeoutExpired:
        for p in procs:
            p.kill()
        out0, err0 = procs[0].communicate()
    assert "ABORTED_OK" in out0, out0 + err0[-1000:]


def test_colocated_device_path_over_tcp(tmp_path):
    # Locality rule (r5): a worker co-located with EVERY server shard
    # keeps the zero-copy device pipeline even on a TCP cluster, while
    # the remote worker crosses the wire with host batches — the
    # reference's -ps_role mixed deployment (src/zoo.cpp:29-35), with
    # the data plane picked per rank by locality.
    mf, _ = write_machine_file(tmp_path, 2)
    corpus = tmp_path / "corpus.txt"
    rng = np.random.default_rng(0)
    topics = [[f"a{i}" for i in range(8)], [f"b{i}" for i in range(8)]]
    with open(corpus, "w") as f:
        for _ in range(200):
            topic = topics[rng.integers(0, 2)]
            f.write(" ".join(rng.choice(topic, size=10)) + "\n")
    common = f"""
from multiverso_tpu.models.wordembedding import (
    BlockLoader, Dictionary, PSDeviceCorpusTrainer, PSWord2Vec,
    TokenizedCorpus, Word2VecConfig, iter_pair_batches)
corpus = {str(corpus)!r}
d = Dictionary.build(corpus, min_count=1)
role = "all" if rank == 0 else "worker"
mv.init(["-machine_file=" + {mf!r}, "-rank=" + str(rank),
         "-ps_role=" + role])
config = Word2VecConfig(embedding_size=8, window=3, epochs=2,
                        init_learning_rate=0.02, batch_size=256,
                        sample=0, use_ps=True)
model = PSWord2Vec(config, d)
"""
    body0 = common + """
assert model._device_path, "co-located rank must keep the device path"
tok = TokenizedCorpus.build(d, corpus)
trainer = PSDeviceCorpusTrainer(model, tok, centers_per_step=64)
loss, pairs = trainer.train_epoch(seed=0)  # ends with one barrier
assert pairs > 0 and loss == loss
mv.barrier()
mv.shutdown()
print("RANK0_DEVICE_OK")
"""
    body1 = common + """
assert not model._device_path, "remote worker must take host batches"
loss_sum = 0.0
for b in iter_pair_batches(d, corpus, batch_size=256, window=3,
                           subsample=0, seed=0):
    loss_sum += model.train_batch(b)
model._drain_pushes()
mv.barrier()  # pairs rank 0's epoch-end barrier
mv.barrier()
mv.shutdown()
print("RANK1_HOSTBATCH_OK")
"""
    outs = run_cluster([body0, body1])
    assert "RANK0_DEVICE_OK" in outs[0], outs
    assert "RANK1_HOSTBATCH_OK" in outs[1], outs


def test_init_distributed_two_processes(tmp_path):
    # Multi-host bootstrap: jax.distributed.initialize gives the data
    # plane; the TCP control mesh rendezvouses with an all-gather of
    # the endpoints over it — no machine file (runtime/bootstrap.py).
    from multiverso_tpu.util.net_util import free_listen_port
    coord = f"127.0.0.1:{free_listen_port()}"
    body = f"""
import multiverso_tpu as mv
mv.init_distributed(coordinator_address={coord!r}, num_processes=2,
                    process_id=rank)
table = mv.create_array_table(6)
table.add(np.full(6, float(rank + 1), np.float32))
mv.barrier()
out = table.get()
mv.barrier()
assert np.allclose(out, 3.0), out  # 1 + 2 from both processes
mv.shutdown()
print("DISTRIBUTED_BOOTSTRAP_OK")
"""
    outs = run_cluster([body, body])
    assert all("DISTRIBUTED_BOOTSTRAP_OK" in o for o in outs), outs

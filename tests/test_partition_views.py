"""A host row-id request is cut into per-server shards as VIEWS where a
shard is the request or a run of it (``MatrixWorker.partition``,
``_shard_cuts``), and an acknowledged Add no longer reads the caller's
delta (docs/MEMORY.md "Send side of an Add")."""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import MsgType
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.runtime.shard_map import ShardMap
from multiverso_tpu.updater import AddOption, GetOption
from multiverso_tpu.util.dashboard import Dashboard

ROWS, COLS = 40, 6
COUNTERS = ("ADD_ROWS_SHARD_VIEW", "ADD_ROWS_SHARD_COPIED")


def _counts():
    return {name: Dashboard.get(name).count for name in COUNTERS}


def _on_cluster(servers, body, argv=()):
    """``body(rank)`` on every rank of an in-process cluster of
    ``servers`` ranks (each a worker and a server); rank 0's result."""
    if servers == 1:
        mv.init(list(argv))
        try:
            return body(0)
        finally:
            mv.shutdown()
    return LocalCluster(servers, argv=list(argv)).run(body)[0]


def _interleaved_map(servers):
    """Eight intervals of five rows whose owners go round the servers:
    with more than one server no sorted request that spans the table has
    its owners in order."""
    return ShardMap(np.arange(0, ROWS + 1, 5),
                    np.arange(ROWS // 5) % servers, epoch=1)


def _masked_shards(table, keys, values):
    """The form ``partition`` had before views, kept here as the
    reference: every server's keys and rows gathered with a mask."""
    dest = table._server_of_rows(keys)
    shards = {}
    for sid in np.unique(dest):
        mask = dest == sid
        shards[int(sid)] = (
            np.ascontiguousarray(keys[mask]),
            None if values is None else np.ascontiguousarray(values[mask]))
    return shards


def _encoded(table, encoder, chunk, rows):
    """What the encoder makes of one server's chunk, from a clean
    error-feedback state."""
    table._residual = None
    if encoder == "one_bit":
        return table._onebit_chunk(chunk, 0, 0, rows=rows)
    if encoder == "codec":
        return table._codec_chunk(chunk, 0, 0, rows=rows)
    return [Blob(chunk)]


def _bytes(blob):
    return blob.as_array(np.uint8).tobytes()


@pytest.mark.parametrize("layout", ["division", "map"])
@pytest.mark.parametrize("encoder", ["plain", "one_bit", "codec"])
@pytest.mark.parametrize("op", ["add", "get"])
@pytest.mark.parametrize("order", ["sorted", "unsorted"])
@pytest.mark.parametrize("servers", [1, 2, 4])
def test_partition_cuts_views_where_the_shard_is_a_run(servers, order, op,
                                                       encoder, layout):
    """The shards are, byte for byte, what the masked form gives; they
    are views of the request's keys and values exactly where one server
    holds everything or each key's server rises along the request; the
    two counters count the Add's shards accordingly."""
    ids = np.array([1, 3, 4, 7, 12, 17, 21, 22, 33, 38], np.int32)
    if order == "unsorted":
        ids = ids[[5, 0, 7, 2, 9, 1, 8, 3, 6, 4]]
    delta = (np.arange(ids.size * COLS, dtype=np.float32)
             .reshape(ids.size, COLS) - 20.0) / 8
    is_add = op == "add"
    # What the rule predicts: one server holds every key whatever the
    # order; several servers get runs only of a request in their order.
    view = servers == 1 or (order == "sorted" and layout == "division")

    def body(rank):
        table = mv.create_matrix_table(ROWS, COLS,
                                       is_sparse=encoder == "codec")
        zoo = mv.current_zoo()
        zoo.barrier()
        if rank == 0:
            if encoder == "codec":
                table._compress = True      # as over a wire
            if layout == "map":
                table.apply_shard_map(1, _interleaved_map(servers),
                                      list(range(servers)))
            option = AddOption(worker_id=0).to_blob()
            if is_add:
                blobs = [Blob(ids.view(np.uint8)), Blob(delta), option]
            elif encoder == "codec":        # a sparse Get's option
                blobs = [Blob(ids.view(np.uint8)),
                         GetOption(0).to_blob()]
            else:
                blobs = [Blob(ids.view(np.uint8))]
            reference = _masked_shards(table, ids,
                                       delta if is_add else None)
            before = _counts()
            shards = table.partition(
                blobs, MsgType.Request_Add if is_add
                else MsgType.Request_Get)
            moved = {k: v - before[k] for k, v in _counts().items()}

            assert sorted(shards) == sorted(reference)
            assert len(shards) == min(servers, 4)
            for sid, (ref_keys, ref_chunk) in reference.items():
                shard = shards[sid]
                assert _bytes(shard[0]) == ref_keys.tobytes()
                assert np.shares_memory(shard[0].data, ids) == view
                if not is_add:
                    assert len(shard) == len(blobs)
                    assert all(a is b for a, b in zip(shard[1:], blobs[1:]))
                    continue
                assert shard[-1] is blobs[2]
                expected = _encoded(table, encoder, ref_chunk, ref_keys)
                assert [_bytes(b) for b in shard[1:-1]] \
                    == [_bytes(b) for b in expected]
                if encoder == "plain":
                    assert np.shares_memory(shard[1].data, delta) == view
            n = len(shards) if is_add else 0
            assert moved == {"ADD_ROWS_SHARD_VIEW": n if view else 0,
                             "ADD_ROWS_SHARD_COPIED": 0 if view else n}
        zoo.barrier()
        return True

    argv = ["-one_bit_push=true"] if encoder == "one_bit" else []
    assert _on_cluster(servers, body, argv)


@pytest.mark.parametrize("dest, n, expected", [
    (None, 5, [(0, slice(0, 5))]),
    (None, 0, []),
    ([2, 2, 2], 3, [(2, slice(0, 3))]),
    ([0, 0, 1, 3, 3], 5, [(0, slice(0, 2)), (1, slice(2, 3)),
                          (3, slice(3, 5))]),
    ([1], 1, [(1, slice(0, 1))]),
])
def test_shard_cuts_runs(dest, n, expected):
    from multiverso_tpu.tables.matrix_table import _shard_cuts
    dest = None if dest is None else np.asarray(dest)
    assert _shard_cuts(dest, n) == expected


def test_shard_cuts_masks_when_servers_do_not_rise():
    from multiverso_tpu.tables.matrix_table import _shard_cuts
    cuts = _shard_cuts(np.array([1, 0, 1, 2]), 4)
    assert [sid for sid, _ in cuts] == [0, 1, 2]
    np.testing.assert_array_equal(
        np.stack([mask for _, mask in cuts]),
        [[False, True, False, False], [True, False, True, False],
         [False, False, False, True]])


# -- the guarantee: after the ack the caller may overwrite its delta ---------

BIG_ROWS, BIG_COLS = 4096, 512


@pytest.mark.parametrize("form", ["sync", "async"])
@pytest.mark.parametrize("servers", [1, 2])
@pytest.mark.parametrize("updater", ["default", "sgd"])
@pytest.mark.parametrize("k", [1000, 1024], ids=["padded", "bucket"])
def test_an_acknowledged_add_no_longer_reads_the_callers_delta(
        k, updater, servers, form):
    """``add_rows`` returns (or ``wait`` does), the caller overwrites
    its delta at once, and the table holds the old bytes. Each server's
    shard is a view of ``delta``; k = 1000 is padded into a fresh array
    by ``pad_rows`` before the dispatch, k = 1024 is a bucket (512 a
    server with two) and is owned by a copy there. (On this tier's
    eight-device CPU platform the device lock also waits for each
    program; the one-device test below is the one that fails without
    the copy.)"""
    per = k // servers
    half = BIG_ROWS // servers
    ids = np.concatenate([np.arange(per, dtype=np.int32) * 2 + s * half
                          for s in range(servers)])
    rng = np.random.default_rng(k + servers)
    old = rng.standard_normal((ids.size, BIG_COLS)).astype(np.float32)
    sign = -1.0 if updater == "sgd" else 1.0

    def body(rank):
        table = mv.create_matrix_table(BIG_ROWS, BIG_COLS)
        zoo = mv.current_zoo()
        zoo.barrier()
        got = None
        if rank == 0:
            # programs built, so that the measured Add is dispatched
            # without a compile between the call and the overwrite
            table.add_rows(ids, np.zeros_like(old))
            before = _counts()
            for _ in range(3):
                delta = old.copy()
                if form == "sync":
                    table.add_rows(ids, delta)
                else:
                    table.wait(table.add_rows_async(ids, delta))
                delta[:] = np.nan
            moved = {k_: v - before[k_] for k_, v in _counts().items()}
            assert moved == {"ADD_ROWS_SHARD_VIEW": 3 * servers,
                             "ADD_ROWS_SHARD_COPIED": 0}
            got = table.get_rows(ids)
        zoo.barrier()
        return got

    got = _on_cluster(servers, body, [f"-updater_type={updater}"])
    np.testing.assert_array_equal(got, sign * 3 * old)


ONE_DEVICE = """
import sys
import numpy as np
import multiverso_tpu as mv
k, cols, form = 16384, 128, sys.argv[1]
mv.init([])
table = mv.create_matrix_table(4 * k, cols)
ids = np.arange(k, dtype=np.int32) * 3
old = np.random.default_rng(0).standard_normal((k, cols)).astype(np.float32)
table.add_rows(ids, np.zeros_like(old))
for _ in range(3):
    delta = old.copy()
    if form == "sync":
        table.add_rows(ids, delta)
    else:
        table.wait(table.add_rows_async(ids, delta))
    delta[:] = np.nan
got = table.get_rows(ids)
print("ROWS_THAT_DIFFER", int((got != 3 * old).any(axis=1).sum()))
mv.shutdown()
"""


@pytest.mark.parametrize("form", ["sync", "async"])
def test_a_bucket_sized_delta_is_owned_where_the_runtime_reads_late(form):
    """On a one-device CPU platform nothing waits for the server's
    program (the device lock is off, as on a chip), and the runtime
    reads a jitted call's numpy argument after the call returns: an
    8 MB bucket-sized delta (k = 16384, nothing to pad) overwritten
    after the ack would reach the table as NaN in every row if
    ``pad_rows`` handed the caller's array on. It copies."""
    import os
    import subprocess
    import sys
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", ONE_DEVICE, form], capture_output=True,
        text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1",
                 PYTHONPATH=os.pathsep.join(
                     p for p in (repo, os.environ.get("PYTHONPATH", ""))
                     if p)))
    assert "ROWS_THAT_DIFFER 0\n" in out.stdout, \
        (out.stdout[-300:], out.stderr[-600:])

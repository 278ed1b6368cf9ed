"""Table tests: ports of the reference's table test suite.

Mirrors Test/unittests/test_array.cpp:27-68 (partition as a unit + in-process
add/get roundtrips), Test/test_array_table.cpp:11-47 (multi-rank sync loop),
Test/unittests/test_kv.cpp, Test/test_matrix_table.cpp (row adds/gets), and
the sparse dirty-row semantics of src/table/sparse_matrix_table.cpp:200-258.
"""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import MsgType
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.sharding.rows import row_offsets
from multiverso_tpu.tables import server_offsets
from multiverso_tpu.updater import AddOption


@pytest.fixture
def env():
    """Single-process worker+server environment
    (ref: Test/unittests/multiverso_env.h:9-31)."""
    mv.init([])
    yield
    mv.shutdown()


@pytest.fixture
def sync_env():
    mv.init(["-sync=true"])
    yield
    mv.shutdown()


class TestPartitionMath:
    def test_array_offsets_match_reference(self):
        # ref: array_table.cpp:14-20 — i*length, last absorbs remainder.
        assert server_offsets(10, 3) == [0, 3, 6, 10]
        assert server_offsets(9, 3) == [0, 3, 6, 9]
        assert server_offsets(5, 1) == [0, 5]

    def test_matrix_row_offsets_match_reference(self):
        # ref: matrix_table.cpp:24-41.
        assert row_offsets(10, 2) == [0, 5, 10]
        assert row_offsets(5, 3) == [0, 1, 2, 5]
        # Degenerate: fewer rows than servers -> one row per server.
        assert row_offsets(3, 8) == [0, 1, 2, 3]

    def test_array_partition_unit(self, env):
        # ref: Test/unittests/test_array.cpp:27-47 exercises Partition
        # directly as a unit.
        from multiverso_tpu.tables.array_table import ArrayWorker
        worker = ArrayWorker(10)  # one server in env
        values = np.arange(10, dtype=np.float32)
        parts = worker.partition(
            [Blob(np.array([-1], np.int32)), Blob(values)],
            MsgType.Request_Add)
        assert set(parts.keys()) == {0}
        np.testing.assert_array_equal(
            parts[0][1].as_array(np.float32), values)


class TestArrayTable:
    def test_add_get_roundtrip(self, env):
        table = mv.create_array_table(100)
        out = table.get()
        np.testing.assert_array_equal(out, np.zeros(100, np.float32))
        delta = np.arange(100, dtype=np.float32)
        table.add(delta)
        table.add(delta)
        np.testing.assert_array_equal(table.get(), 2 * delta)

    def test_async_add_then_wait(self, env):
        table = mv.create_array_table(16)
        ids = [table.add_async(np.ones(16, np.float32)) for _ in range(8)]
        for msg_id in ids:
            assert table.wait(msg_id, timeout=30)
        np.testing.assert_array_equal(table.get(), 8 * np.ones(16))

    def test_sgd_updater_subtracts(self, env):
        table = mv.create_array_table(8, updater_type="sgd")
        table.add(np.full(8, 2.5, np.float32))
        np.testing.assert_array_equal(table.get(),
                                      np.full(8, -2.5, np.float32))

    def test_get_into_user_buffer(self, env):
        table = mv.create_array_table(32)
        table.add(np.ones(32, np.float32))
        buf = np.zeros(32, np.float32)
        ret = table.get(out=buf)
        assert ret is buf
        np.testing.assert_array_equal(buf, np.ones(32))


class TestMatrixTable:
    def test_whole_table_roundtrip(self, env):
        table = mv.create_matrix_table(20, 5)
        out = table.get()
        assert out.shape == (20, 5)
        assert out.sum() == 0
        delta = np.ones((20, 5), np.float32)
        table.add(delta)
        np.testing.assert_array_equal(table.get(), delta)

    def test_row_add_get(self, env):
        table = mv.create_matrix_table(10, 4)
        rows = np.array([2, 7], np.int32)
        delta = np.stack([np.full(4, 1.0), np.full(4, 2.0)]).astype(np.float32)
        table.add_rows(rows, delta)
        got = table.get_rows(rows)
        np.testing.assert_array_equal(got, delta)
        whole = table.get()
        assert whole.sum() == delta.sum()

    def test_random_init_server(self, env):
        from multiverso_tpu.tables.matrix_table import MatrixServer, \
            MatrixWorker
        MatrixServer(6, 3, random_init=(-0.1, 0.1), seed=7)
        worker = MatrixWorker(6, 3)
        mv.barrier()
        vals = worker.get()
        assert (np.abs(vals) <= 0.1).all()
        assert np.abs(vals).sum() > 0

    def test_adagrad_matrix(self, env):
        table = mv.create_matrix_table(4, 2, updater_type="adagrad")
        opt = AddOption(worker_id=0, learning_rate=0.1, rho=0.1)
        table.add_rows(np.array([1], np.int32),
                       np.full((1, 2), 0.05, np.float32), option=opt)
        got = table.get()
        assert got[1, 0] < 0  # adagrad descends
        assert got[0].sum() == 0


class TestSparseMatrix:
    def test_dirty_row_tracking(self, env):
        table = mv.create_matrix_table(8, 2, is_sparse=True)
        # Initial get: everything dirty -> full table lands.
        out = table.get()
        assert out.shape == (8, 2)
        # Worker 0 adds rows 1,3 -> for itself they are now clean.
        table.add_rows(np.array([1, 3], np.int32),
                       np.ones((2, 2), np.float32),
                       option=AddOption(worker_id=0))
        stale = np.full((8, 2), -7.0, np.float32)
        table.get(out=stale)
        # Nothing dirty for worker 0 -> buffer untouched.
        np.testing.assert_array_equal(stale, np.full((8, 2), -7.0))

    def test_adder_does_not_clean_others_dirty_mark(self, env):
        # Regression (round-1 advice): worker B dirties a row, then worker
        # A adds to that same row. A's pending dirty mark must survive A's
        # own add — only Gets clean flags (ref: sparse_matrix_table.cpp
        # UpdateAddState skips just the adder) — so A's next dirty-only get
        # still returns the row with B's update folded in.
        table = mv.create_matrix_table(4, 2, is_sparse=True)
        table.get()  # worker 0: everything clean
        table.add_rows(np.array([2], np.int32),
                       np.ones((1, 2), np.float32),
                       option=AddOption(worker_id=1))  # B's add
        table.add_rows(np.array([2], np.int32),
                       np.ones((1, 2), np.float32),
                       option=AddOption(worker_id=0))  # A's add
        buf = np.full((4, 2), -1.0, np.float32)
        table.get(out=buf)  # A's dirty-only get
        np.testing.assert_array_equal(buf[2], [2.0, 2.0])

    def test_sparse_get_zeroed_when_out_omitted(self, env):
        # Regression (round-1 advice): a sparse whole-table get with no out
        # buffer must not surface uninitialized memory in clean rows.
        table = mv.create_matrix_table(4, 2, is_sparse=True)
        table.get()  # clean all for worker 0
        out = table.get()  # nothing dirty -> all rows must read as zeros
        np.testing.assert_array_equal(out, np.zeros((4, 2), np.float32))

    def test_wire_compression_roundtrip_and_shrink(self, env):
        # Sparse traffic runs through the wire codec both directions
        # (ref: sparse_matrix_table.cpp:148-153): a mostly-zero row delta
        # must round-trip exactly AND shrink on the wire. In-process
        # tables skip the filter automatically (no wire), so force it on
        # both endpoints to exercise the cross-process machinery.
        from multiverso_tpu.core.message import MsgType

        cols = 64
        table = mv.create_matrix_table(8, cols, is_sparse=True)
        table._compress = True
        mv.current_zoo()._server_tables[table.table_id]._compress = True
        table.get()  # clean all for worker 0
        delta = np.zeros((2, cols), np.float32)
        delta[0, 3] = 7.0
        delta[1, 60] = -2.5
        rows = np.array([1, 5], np.int32)
        # Wire-size proof: partition output IS the wire payload.
        from multiverso_tpu.core.blob import Blob
        from multiverso_tpu.updater import AddOption
        blobs = [Blob(rows.view(np.uint8)), Blob(delta.reshape(-1)),
                 AddOption(worker_id=1).to_blob()]
        shards = table.partition(blobs, MsgType.Request_Add)
        wire = sum(b.size for shard in shards.values() for b in shard)
        uncompressed = rows.nbytes + delta.nbytes + blobs[2].size
        assert wire < uncompressed, (wire, uncompressed)

        # Full-stack roundtrip: worker 1 adds, worker 0's dirty-only get
        # returns the exact values through the compressed path.
        table.add_rows(rows, delta, option=AddOption(worker_id=1))
        buf = np.full((8, cols), -1.0, np.float32)
        table.get(out=buf)
        np.testing.assert_array_equal(buf[1], delta[0])
        np.testing.assert_array_equal(buf[5], delta[1])

    def test_wire_compression_dense_payload_uncompressed(self, env):
        # >50% non-zero values must ride uncompressed (the filter's
        # break-even rule) and still round-trip.
        table = mv.create_matrix_table(6, 4, is_sparse=True)
        table.get()
        dense = np.arange(8, dtype=np.float32).reshape(2, 4) + 1
        table.add_rows(np.array([0, 3], np.int32), dense,
                       option=AddOption(worker_id=1))
        buf = np.zeros((6, 4), np.float32)
        table.get(out=buf)
        np.testing.assert_array_equal(buf[0], dense[0])
        np.testing.assert_array_equal(buf[3], dense[1])

    def test_compress_mismatch_degrades_to_raw(self, env):
        # A peer running WITHOUT the table-level codec (-sparse_compress
        # mismatch or a pre-codec build) sends raw [keys, values] — a
        # compress-enabled server must sniff the frame magic and take
        # the raw path instead of raising inside the actor loop (which
        # would strand the requester's waiter forever).
        table = mv.create_matrix_table(8, 16, is_sparse=True)
        server = mv.current_zoo()._server_tables[table.table_id]
        server._compress = True
        table._compress = True
        table.get()  # clean all for worker 0 (codec reply path)
        delta = np.zeros((2, 16), np.float32)
        delta[0, 1], delta[1, 15] = 3.0, -4.0
        table._compress = False  # emulate a plain-sending peer's Add
        table.add_rows(np.array([2, 6], np.int32), delta,
                       option=AddOption(worker_id=1))
        table._compress = True
        buf = np.zeros((8, 16), np.float32)
        table.get(out=buf)  # codec reply decodes exactly
        np.testing.assert_array_equal(buf[2], delta[0])
        np.testing.assert_array_equal(buf[6], delta[1])

    def test_wire_compression_lossy_error_feedback(self, env):
        # -wire_codec_lossy: quantized Add pushes with worker-side error
        # feedback. Repeating the same push must converge to the exact
        # accumulated sum (residual folding), not drift by one
        # quantization step per iteration.
        table = mv.create_matrix_table(8, 64, is_sparse=True)
        table._compress = True
        table._lossy = True
        mv.current_zoo()._server_tables[table.table_id]._compress = True
        table.get()  # clean all for worker 0
        rows = np.array([1, 5], np.int32)
        delta = np.zeros((2, 64), np.float32)
        delta[0, 3], delta[1, 60] = 0.731, -0.292
        steps = 16
        for _ in range(steps):
            table.add_rows(rows, delta, option=AddOption(worker_id=1))
        buf = np.zeros((8, 64), np.float32)
        table.get(out=buf)
        np.testing.assert_allclose(buf[1], steps * delta[0],
                                   rtol=0, atol=0.02)
        np.testing.assert_allclose(buf[5], steps * delta[1],
                                   rtol=0, atol=0.02)

    def test_row_get_marks_clean(self, env):
        table = mv.create_matrix_table(6, 2, is_sparse=True)
        table.get()  # clean all
        table.add_rows(np.array([2], np.int32),
                       np.full((1, 2), 5.0, np.float32),
                       option=AddOption(worker_id=1))  # dirty for worker 0
        buf = np.zeros((6, 2), np.float32)
        table.get(out=buf)
        np.testing.assert_array_equal(buf[2], [5.0, 5.0])
        assert buf[0].sum() == 0


class TestDonationSafety:
    def test_async_get_then_add_keeps_reply_alive(self, env):
        # A Get reply snapshot must survive the next donated update: the
        # sync-server drain pattern is get-reply-then-cached-adds
        # (regression: "Array has been deleted" on materialize).
        table = mv.create_array_table(64)  # 64 == padded size on 8 devices
        out = np.full(64, -1.0, np.float32)
        msg_id = table.get_async(out)
        for _ in range(4):
            table.add(np.ones(64, np.float32))
        assert table.wait(msg_id, timeout=30)
        # Reply content is a consistent snapshot (0..4 adds may have landed
        # first in async mode), not garbage from a deleted buffer.
        assert float(out[0]) in {0.0, 1.0, 2.0, 3.0, 4.0}


class TestDeviceResidentPath:
    def test_array_device_add_get(self, env):
        import jax.numpy as jnp
        table = mv.create_array_table(64)
        delta = jnp.ones(64, jnp.float32)
        table.add(delta)  # device delta, no host roundtrip
        out = table.get_device()
        assert hasattr(out, "addressable_shards")
        np.testing.assert_array_equal(np.asarray(out), np.ones(64))
        # host path still agrees
        np.testing.assert_array_equal(table.get(), np.ones(64))

    def test_matrix_device_add_get(self, env):
        import jax.numpy as jnp
        table = mv.create_matrix_table(16, 4)
        table.add(jnp.full((16, 4), 2.0, jnp.float32))
        out = table.get_device()
        assert out.shape == (16, 4)
        np.testing.assert_array_equal(np.asarray(out), np.full((16, 4), 2.0))

    def test_matrix_device_rows_roundtrip(self, env):
        # Device row pull + device delta push: nothing leaves HBM in
        # process; results must match the host-path row APIs exactly.
        import jax.numpy as jnp
        table = mv.create_matrix_table(32, 4)
        table.add(np.arange(32 * 4, dtype=np.float32).reshape(32, 4))
        rows = np.array([1, 5, 5, 31], np.int32)  # dups allowed
        dev = table.get_rows_device(rows)
        assert hasattr(dev, "addressable_shards")
        np.testing.assert_array_equal(np.asarray(dev),
                                      table.get_rows(rows))
        # device delta push (incl. a duplicated row id: both add)
        table.add_rows(rows, jnp.ones((4, 4), jnp.float32))
        got = table.get_rows(np.array([1, 5, 31], np.int32))
        base = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        np.testing.assert_array_equal(got[0], base[1] + 1)
        np.testing.assert_array_equal(got[1], base[5] + 2)  # dup summed
        np.testing.assert_array_equal(got[2], base[31] + 1)

    def test_matrix_device_KEYS_roundtrip(self, env):
        # Device-RESIDENT id vectors (any shape, unsorted, duplicated)
        # pull and push without the ids ever touching the host — the
        # enabler for device-computed row sets (PS device pipeline).
        import jax.numpy as jnp
        table = mv.create_matrix_table(32, 4)
        base = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        table.add(base)
        ids = jnp.asarray(np.array([[3, 1], [1, 31], [7, 7]], np.int32))
        out = table.get_rows_device(ids)
        assert out.shape == (3, 2, 4)
        np.testing.assert_array_equal(np.asarray(out),
                                      base[np.asarray(ids)])
        # device-key push: duplicates sum (ids 1 and 7 appear twice)
        table.add_rows(ids, jnp.ones((3, 2, 4), jnp.float32))
        got = table.get_rows(np.array([3, 1, 31, 7], np.int32))
        np.testing.assert_array_equal(got[0], base[3] + 1)
        np.testing.assert_array_equal(got[1], base[1] + 2)
        np.testing.assert_array_equal(got[2], base[31] + 1)
        np.testing.assert_array_equal(got[3], base[7] + 2)

    def test_sparse_dirty_device_roundtrip(self, env):
        # Device-reply dirty gets: same staleness semantics as the host
        # path (ref: sparse_matrix_table.cpp:226-258), payload in HBM.
        import jax.numpy as jnp
        table = mv.create_matrix_table(16, 4, is_sparse=True)
        ids0, vals0 = table.get_dirty_device()  # initial: all dirty
        assert ids0.size == 16 and vals0.shape == (16, 4)
        rows = np.array([2, 9], np.int32)
        table.add_rows(rows, jnp.ones((2, 4), jnp.float32),
                       option=AddOption(worker_id=1))
        ids, vals = table.get_dirty_device()
        assert hasattr(vals, "addressable_shards")
        np.testing.assert_array_equal(ids, rows)
        np.testing.assert_array_equal(np.asarray(vals),
                                      np.ones((2, 4), np.float32))
        ids2, _ = table.get_dirty_device()  # now clean
        assert ids2.size == 0

    def test_fused_add_get_dirty_matches_composed(self, env):
        # The -4 fused add+dirty-get must be the exact composition of
        # add_rows + get_dirty_device (same bookkeeping, one program):
        # interleaving fused and composed iterations stays consistent.
        import jax.numpy as jnp
        table = mv.create_matrix_table(16, 4, is_sparse=True)
        table.get_dirty_device()  # worker 0 starts clean
        rows = np.array([2, 9], np.int32)
        one = jnp.ones((2, 4), jnp.float32)
        ids, vals = table.add_get_dirty_device(
            rows, one, option=AddOption(worker_id=1), get_worker=0)
        np.testing.assert_array_equal(ids, rows)
        np.testing.assert_array_equal(np.asarray(vals),
                                      np.ones((2, 4), np.float32))
        ids2, vals2 = table.add_get_dirty_device(
            rows, one, option=AddOption(worker_id=1), get_worker=0)
        np.testing.assert_array_equal(ids2, rows)
        np.testing.assert_array_equal(np.asarray(vals2),
                                      2 * np.ones((2, 4), np.float32))
        # Device-mirror ids (the upload-skipping form) and the cached
        # dirty device vector produce the same result. The mirror must
        # be bucket-padded like the host path (compile-per-bucket, not
        # per distinct k).
        from multiverso_tpu.updater.engine import pad_ids
        ids_m, vals_m = table.add_get_dirty_device(
            rows, one, option=AddOption(worker_id=1), get_worker=0,
            row_ids_device=jnp.asarray(pad_ids(rows, 16)))
        np.testing.assert_array_equal(ids_m, rows)
        np.testing.assert_array_equal(np.asarray(vals_m),
                                      3 * np.ones((2, 4), np.float32))
        # The composed pair continues from the fused state seamlessly.
        table.add_rows(rows, one, option=AddOption(worker_id=1))
        ids3, vals3 = table.get_dirty_device()
        np.testing.assert_array_equal(ids3, rows)
        np.testing.assert_array_equal(np.asarray(vals3),
                                      4 * np.ones((2, 4), np.float32))

    def test_device_keys_rejected_stateful_updater(self, env):
        # Duplicate device ids only SUM correctly under rules that sum
        # them (default, sgd, adam: UpdaterRule.sums_duplicates);
        # the misconfiguration must raise in the CALLER (the server-side
        # CHECK fires inside the actor, which swallows it and the ack
        # never comes — a silent hang).
        import jax.numpy as jnp
        table = mv.create_matrix_table(16, 4, updater_type="momentum")
        with pytest.raises(Exception, match="updater_type=momentum"):
            table.add_rows(jnp.asarray(np.array([1, 2], np.int32)),
                           jnp.ones((2, 4), jnp.float32))

    def test_stray_negative_key_fails_fast(self, env):
        # Only -1/-2 are whole-table sentinels; any other negative id
        # must raise in the CALLER (partition runs inside the worker
        # actor, where an exception degrades to a silent bad reply).
        table = mv.create_matrix_table(16, 4)
        with pytest.raises(Exception, match="out of range"):
            table.get_rows(np.array([-3], np.int32))
        with pytest.raises(Exception, match="out of range"):
            table.add_rows(np.array([-3, 5], np.int32),
                           np.ones((2, 4), np.float32))
        with pytest.raises(Exception, match="out of range"):
            table.get_rows(np.array([16], np.int32))
        # Defense in depth: partition itself also rejects non-sentinels
        # (-4 is the fused-dirty marker; -3 is no longer a sentinel).
        for stray in (-5, -3):
            with pytest.raises(Exception, match="sentinel"):
                table.partition(
                    [Blob(np.array([stray], np.int32).view(np.uint8))],
                    MsgType.Request_Get)

    def test_sync_server_ticks_clock_on_error(self):
        # BSP: a failed add must still tick the vector clock — otherwise
        # the failed worker's clock stays behind and the gate caches
        # every other worker's requests forever (cluster-wide hang).
        from multiverso_tpu.tables.table_interface import TableRequestError

        def body(rank):
            table = mv.create_matrix_table(8, 2)
            if rank == 0:  # bad add: wrong-sized whole-table delta
                mid = table.add_async_raw(
                    Blob(np.array([-1], np.int32).view(np.uint8)),
                    Blob(np.ones(3, np.float32)))
                failed = False
                try:
                    table.wait(mid)
                except TableRequestError:
                    failed = True
            else:
                table.add(np.ones((8, 2), np.float32))
                failed = None
            got = table.get()  # would hang without the clock tick
            # Round 2: WORKER-side failure (partition raises before any
            # shard is sent) — the empty clock-tick shards must keep the
            # BSP clocks level for the other worker.
            if rank == 0:
                mid = table.add_async_raw(
                    Blob(np.array([-9], np.int32).view(np.uint8)),
                    Blob(np.ones(2, np.float32)))
                try:
                    table.wait(mid)
                    failed = False
                except TableRequestError as exc:
                    failed = failed and "partition" in str(exc)
            else:
                table.add(np.ones((8, 2), np.float32))
            got2 = table.get()  # would hang without the tick shards
            mv.current_zoo().barrier()
            return failed, float(got[0, 0]), float(got2[0, 0])

        results = LocalCluster(2, argv=["-sync=true"]).run(body)
        assert results[0][0] is True
        assert results[0][1] == results[1][1] == 1.0
        assert results[0][2] == results[1][2] == 2.0

    def test_remote_failures_raise_in_caller(self, env):
        # Failures inside the actor runtime must surface as
        # TableRequestError in the REQUESTER's wait(), not degrade to a
        # log line plus garbage/empty results (the actor loop swallows).
        from multiverso_tpu.tables.table_interface import TableRequestError
        table = mv.create_matrix_table(16, 4)
        # Worker-side: partition rejects the stray sentinel (raw API
        # bypasses the caller-side range CHECK).
        mid = table.get_async_raw(
            Blob(np.array([-3], np.int32).view(np.uint8)))
        with pytest.raises(TableRequestError, match="partition"):
            table.wait(mid)
        # Server-side: a wrong-sized whole-table add fails in
        # process_add; the error reply must carry the text back.
        mid = table.add_async_raw(
            Blob(np.array([-1], np.int32).view(np.uint8)),
            Blob(np.ones(7, np.float32)))
        with pytest.raises(TableRequestError, match="size mismatch"):
            table.wait(mid)
        # The table stays usable afterwards.
        table.add(np.ones((16, 4), np.float32))
        np.testing.assert_array_equal(table.get(),
                                      np.ones((16, 4), np.float32))

    def test_matrix_device_keys_multi_server_roundtrip(self):
        # Device keys broadcast to every server; each masks foreign
        # rows (gather fills 0, scatter drops) and the worker SUMS the
        # replies — exact gather/scatter semantics across 2 servers,
        # duplicates included, without the ids ever touching the host.
        def body(rank):
            import jax.numpy as jnp
            table = mv.create_matrix_table(10, 3)
            base = np.arange(30, dtype=np.float32).reshape(10, 3)
            if rank == 0:
                table.add(base)
            mv.current_zoo().barrier()
            # ids span both servers' row ranges (0-4 / 5-9), unsorted,
            # with a duplicate
            ids = jnp.asarray(np.array([[7, 1], [1, 9]], np.int32))
            got = np.asarray(table.get_rows_device(ids))
            ok_get = np.array_equal(got, base[np.asarray(ids)])
            if rank == 0:
                table.add_rows(ids, jnp.ones((2, 2, 3), jnp.float32))
            mv.current_zoo().barrier()
            after = table.get_rows(np.array([7, 1, 9, 0], np.int32))
            ok_add = (np.array_equal(after[0], base[7] + 1)
                      and np.array_equal(after[1], base[1] + 2)  # dup
                      and np.array_equal(after[2], base[9] + 1)
                      and np.array_equal(after[3], base[0]))
            mv.current_zoo().barrier()
            return ok_get and ok_add

        assert all(LocalCluster(2).run(body))

    def test_matrix_device_rows_two_servers(self):
        # Sorted row ids spanning both servers' ranges reassemble in
        # order; device push partitions into per-server device segments.
        def body(rank):
            import jax.numpy as jnp
            table = mv.create_matrix_table(10, 3)
            if rank == 0:
                table.add_rows(np.array([1, 4, 8], np.int32),
                               jnp.ones((3, 3), jnp.float32) * 2.0)
            mv.current_zoo().barrier()
            rows = np.array([1, 4, 8], np.int32)
            out = np.asarray(table.get_rows_device(rows))
            host = table.get_rows(rows)
            mv.current_zoo().barrier()
            return out.tolist(), host.tolist()

        for dev, host in LocalCluster(2).run(body):
            assert dev == host == [[2.0] * 3] * 3

    def test_device_path_multi_server(self):
        def body(rank):
            import jax.numpy as jnp
            table = mv.create_array_table(32)
            if rank == 0:
                table.add(jnp.ones(32, jnp.float32))
            mv.current_zoo().barrier()
            out = np.asarray(table.get_device())
            mv.current_zoo().barrier()
            return out.tolist()

        r0, r1 = LocalCluster(2).run(body)
        assert r0 == r1 == [1.0] * 32


class TestKVTable:
    def test_add_get(self, env):
        table = mv.create_kv_table()
        table.add([1, 5, 9], [1.0, 2.0, 3.0])
        table.add([1], [10.0])
        got = table.get([1, 5, 9, 42])
        assert got[1] == pytest.approx(11.0)
        assert got[5] == pytest.approx(2.0)
        assert got[42] == 0


class TestMultiRank:
    def test_array_table_two_ranks(self):
        # ref: Test/test_array_table.cpp:11-47 — every worker adds, then
        # everyone sees the combined result (async mode; barrier between).
        def body(rank):
            table = mv.create_array_table(10)
            table.add(np.full(10, rank + 1, np.float32))
            zoo = mv.current_zoo()
            zoo.barrier()
            out = table.get()
            zoo.barrier()
            return out.tolist()

        r0, r1 = LocalCluster(2).run(body)
        assert r0 == r1 == [3.0] * 10  # 1 + 2

    def test_matrix_table_two_servers_partition(self):
        def body(rank):
            table = mv.create_matrix_table(10, 3)
            if rank == 0:
                table.add_rows(np.array([0, 7], np.int32),
                               np.ones((2, 3), np.float32))
            mv.current_zoo().barrier()
            out = table.get()
            mv.current_zoo().barrier()
            return out.sum()

        results = LocalCluster(2).run(body)
        assert results == [6.0, 6.0]

    def test_sync_mode_bsp_contract(self):
        # BSP: the i-th Get sees exactly all workers' i-th Adds
        # (ref: src/server.cpp:60-66, Test/test_array_table sync loop).
        def body(rank):
            table = mv.create_array_table(4)
            seen = []
            for it in range(3):
                table.add(np.full(4, 1.0, np.float32))
                out = table.get()
                seen.append(float(out[0]))
            return seen

        results = LocalCluster(2, argv=["-sync=true"]).run(body)
        for seen in results:
            assert seen == [2.0, 4.0, 6.0]  # both workers' adds, per round

    def test_sparse_dirty_device_two_servers(self):
        # Device-reply dirty pulls across a 2-server partition (the
        # reference's dirty tracking works for any server count,
        # ref: sparse_matrix_table.cpp:226-258): per-server dirty sets
        # concatenate globally sorted; a server with zero dirty rows
        # contributes an empty segment (attributed by the server-id
        # blob, not by guessing from keys).
        def body(rank):
            import jax.numpy as jnp
            table = mv.create_matrix_table(16, 4, is_sparse=True)
            zoo = mv.current_zoo()
            ids0, vals0 = table.get_dirty_device()  # initial: all dirty
            ok0 = ids0.size == 16 and vals0.shape == (16, 4)
            zoo.barrier()
            rows = np.array([2, 9, 13], np.int32)  # spans both ranges
            if rank == 0:
                table.add_rows(rows, jnp.ones((3, 4), jnp.float32),
                               option=AddOption(worker_id=0))
            zoo.barrier()
            ids, vals = table.get_dirty_device()
            zoo.barrier()
            return ok0, ids.tolist(), float(np.asarray(vals).sum())

        r0, r1 = LocalCluster(2).run(body)
        # The adder's own flags stay clean; the other worker sees the
        # dirty rows from both servers, in global order.
        assert r0 == (True, [], 0.0)
        assert r1 == (True, [2, 9, 13], 12.0)

    def test_kv_two_servers(self):
        def body(rank):
            table = mv.create_kv_table()
            table.add([rank, 100 + rank], [1.0, 2.0])
            mv.current_zoo().barrier()
            got = table.get([0, 1, 100, 101])
            mv.current_zoo().barrier()
            return got

        for got in LocalCluster(2).run(body):
            assert got[0] == 1.0 and got[1] == 1.0
            assert got[100] == 2.0 and got[101] == 2.0


class TestOneBitPush:
    """-one_bit_push: 1-bit quantized Add traffic with worker-side error
    feedback (completes the reference's empty OneBitsFilter stub,
    ref: quantization_util.h:160-161)."""

    def test_wire_shrinks(self):
        from multiverso_tpu.util.configure import reset_flags, set_flag
        mv.init([])
        try:
            set_flag("one_bit_push", True)
            table = mv.create_matrix_table(16, 64)
            delta = np.linspace(-1.0, 1.0, 16 * 64,
                                dtype=np.float32).reshape(16, 64)
            shards = table.partition(
                [Blob(np.array([-1], np.int32).view(np.uint8)),
                 Blob(delta.reshape(-1))], MsgType.Request_Add)
            wire_bytes = sum(b.size for b in shards[0][1:])
            # sign bits (1/32 of float bytes) + tiny meta blob
            assert wire_bytes < delta.nbytes / 8, wire_bytes
        finally:
            reset_flags()
            mv.shutdown()

    def test_error_feedback_bounds_drift(self):
        from multiverso_tpu.util.configure import reset_flags, set_flag
        mv.init([])
        try:
            set_flag("one_bit_push", True)
            table = mv.create_matrix_table(16, 64)
            delta = np.linspace(-1.0, 1.0, 16 * 64,
                                dtype=np.float32).reshape(16, 64)
            # One push is lossy (just signs + means)...
            table.add(delta)
            assert not np.allclose(table.get(), delta, atol=1e-3)
            # ...but the feedback residual keeps the accumulated error
            # BOUNDED: the max error after 40 pushes must not be ~4x the
            # error after 10 (which unquantized drift-free error would
            # also satisfy, and feedback-free quantization would not).
            for _ in range(9):
                table.add(delta)
            err10 = np.abs(table.get() - 10 * delta).max()
            for _ in range(30):
                table.add(delta)
            err40 = np.abs(table.get() - 40 * delta).max()
            assert err40 < 2.5 * err10, (err10, err40)
            # and the RELATIVE per-push error shrinks with the horizon
            assert err40 / 40 < err10 / 10
        finally:
            reset_flags()
            mv.shutdown()


# -- a host Get's reply is written once (tables/client_cache.place_rows) -----

def _counts():
    from multiverso_tpu.util.dashboard import Dashboard
    return {name: Dashboard.get(name).count for name in (
        "CLIENT_PLACE_ROWS", "GET_REPLY_ROWS_DIRECT",
        "GET_REPLY_ROWS_PLACED", "WORKER_REPLY_GET")}


@pytest.mark.parametrize("out_given", [True, False], ids=["out", "no-out"])
@pytest.mark.parametrize("servers, order, direct", [
    (1, "sorted", 1),      # the one shard is the request
    (1, "shuffled", 1),    # ... in whatever order it was asked
    (2, "sorted", 2),      # each server's bucket is a run of the request
    (2, "shuffled", 0),    # a bucket of an unsorted request is searched
])
def test_get_rows_places_each_reply_shard_once(servers, order, direct,
                                               out_given):
    """``get_rows`` with host ids and a host buffer: the values are the
    table's, CLIENT_PLACE_ROWS counts one a shard, and the direct
    counter says how many shards were copied straight in."""
    rows, cols = 40, 3
    base = np.arange(rows * cols, dtype=np.float32).reshape(rows, cols)
    ids = np.array([1, 3, 4, 17, 21, 22, 38, 38], np.int32)  # both halves
    if order == "shuffled":
        ids = ids[[5, 0, 7, 2, 6, 1, 4, 3]]

    def body(rank):
        table = mv.create_matrix_table(rows, cols)
        zoo = mv.current_zoo()
        if rank == 0:
            table.add(base)
        zoo.barrier()
        moved = got = None
        if rank == 0:
            table.get_rows(ids)                 # programs built
            out = np.full((ids.size, cols), -1.0, np.float32) \
                if out_given else None
            before = _counts()
            got = table.get_rows(ids, out)
            moved = {k: v - before[k] for k, v in _counts().items()}
            assert out is None or got is out
        zoo.barrier()
        return got, moved

    if servers == 1:
        mv.init([])
        try:
            got, moved = body(0)
        finally:
            mv.shutdown()
    else:
        got, moved = LocalCluster(servers).run(body)[0]
    np.testing.assert_array_equal(got, base[ids])
    assert moved == {"CLIENT_PLACE_ROWS": servers,
                     "WORKER_REPLY_GET": servers,
                     "GET_REPLY_ROWS_DIRECT": direct,
                     "GET_REPLY_ROWS_PLACED": servers - direct}


def test_blob_as_rows_shares_a_2d_host_payload():
    """A payload that already is the rows is handed back in place;
    ``as_array`` on the same blob is still the flat typed view, and a
    flat payload comes back reshaped."""
    rows = np.arange(12, dtype=np.float32).reshape(4, 3)
    blob = Blob(rows)
    got = blob.as_rows(np.float32, 4, 3)
    assert got.shape == (4, 3) and np.shares_memory(got, rows)
    np.testing.assert_array_equal(got, rows)
    flat = blob.as_array(np.float32)
    assert flat.shape == (12,) and np.shares_memory(flat, rows)
    np.testing.assert_array_equal(flat, rows.reshape(-1))
    # bytes off the wire, or another shape of the same bytes: reshaped
    wire = Blob(rows.tobytes())
    np.testing.assert_array_equal(wire.as_rows(np.float32, 4, 3), rows)
    np.testing.assert_array_equal(blob.as_rows(np.float32, 2, 6),
                                  rows.reshape(2, 6))
    assert not wire.as_rows(np.float32, 4, 3).flags.writeable

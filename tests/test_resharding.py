"""Live elastic resharding tests (ISSUE 12, docs/SHARDING.md).

Four layers:

* unit tests for the shard-map algebra (epoch-0 equivalence to the
  frozen layout, move/coalesce, diff, planning), the migration state
  machines (dirty re-streaming, seq-gap detection, duplicate-chunk
  drops), the chaos harness's frame filter, and the auto-reshard skew
  planner;
* mid-stream equivalence: the 1-server element-wise equality checks of
  ``tests/test_sharding.py`` re-run ACROSS a live shard-map change —
  grow onto a standby server and drain it back, for matrix and KV
  tables with array/sparse siblings riding in the same cluster;
* a property test: no (Get, Add) interleaving across the handoff
  window observes a version regression without a generation change;
* the chaos matrix (``-m slow``, subprocess TCP clusters): SIGKILL the
  migration destination and the migration source mid-handoff, and
  partition the controller's shard control plane mid-move — every
  case ends in a consistent epoch (committed or rolled back) with
  element-wise table equality against the unperturbed expectation.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.message import Message, MsgType
from multiverso_tpu.runtime import replica as rm
from multiverso_tpu.runtime import shard_map as sm
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.sharding.rows import row_offsets
from multiverso_tpu.util import chaos
from multiverso_tpu.util.configure import set_flag

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def env():
    mv.init([])
    yield
    mv.shutdown()


# ---------------------------------------------------------------------------
# unit: shard-map algebra
# ---------------------------------------------------------------------------

class TestShardMap:
    @pytest.mark.parametrize("rows,servers", [(16, 2), (17, 3), (3, 4)])
    def test_initial_reproduces_frozen_layout(self, rows, servers):
        smap = sm.ShardMap.initial(rows, servers)
        offsets = row_offsets(rows, servers)
        assert smap.bounds.tolist() == offsets
        # The frozen division rule and the map agree on every row.
        keys = np.arange(rows, dtype=np.int64)
        length = max(rows // (len(offsets) - 1), 1)
        frozen = np.minimum(keys // length, len(offsets) - 2)
        np.testing.assert_array_equal(smap.owner_of(keys), frozen)

    def test_initial_active_subset(self):
        smap = sm.ShardMap.initial(16, 4, active=2)
        assert smap.bounds.tolist() == [0, 8, 16]
        assert smap.owner_sids() == [0, 1]

    def test_move_coalesces_and_bumps_epoch(self):
        smap = sm.ShardMap.initial(16, 2)  # [0,8)->0, [8,16)->1
        moved = smap.move(8, 12, 0)
        assert moved.epoch == 1
        # [8,12) joined server 0's adjacent range: coalesced.
        assert moved.bounds.tolist() == [0, 12, 16]
        assert moved.owners.tolist() == [0, 1]

    def test_diff_moved_merges_runs(self):
        a = sm.ShardMap.initial(12, 2)
        b = a.move(2, 6, 1)
        assert a.diff_moved(b) == [(2, 6, 0, 1)]
        assert b.diff_moved(b) == []

    def test_plan_moves_grow_shrink_roundtrip(self):
        smap = sm.ShardMap.initial(16, 3, active=2)
        grow = sm.plan_moves(smap, [0, 1, 2])
        assert grow  # something must move onto the standby
        for lo, hi, src, dst in grow:
            smap = smap.move(lo, hi, dst)
        assert smap.bounds.tolist() == row_offsets(16, 3)
        shrink = sm.plan_moves(smap, [0, 1])
        for lo, hi, src, dst in shrink:
            smap = smap.move(lo, hi, dst)
        assert smap.bounds.tolist() == row_offsets(16, 2)
        assert smap.owner_sids() == [0, 1]
        assert sm.plan_moves(smap, [0, 1]) == []  # already there

    def test_pack_unpack_roundtrip(self):
        smap = sm.ShardMap.initial(16, 2).move(3, 7, 1)
        blobs = smap.pack(table_id=4, alive_sids=[0, 1])
        table_id, got, alive = sm.ShardMap.unpack(blobs)
        assert table_id == 4 and got.epoch == smap.epoch
        np.testing.assert_array_equal(got.bounds, smap.bounds)
        np.testing.assert_array_equal(got.owners, smap.owners)
        assert alive.tolist() == [0, 1]


class TestMigrationState:
    def _mig(self, lo=0, hi=10, chunk=4):
        set_flag("reshard_chunk_rows", chunk)
        return sm.MigrationOut(0, lo, hi, src_sid=0, dst_sid=1,
                               dst_rank=1, epoch=1)

    def test_chunks_then_final_drains_dirty(self):
        mig = self._mig()
        seq0, rows0, fin0 = mig.next_chunk()
        assert (seq0, fin0) == (0, False) and rows0.tolist() == [0, 1, 2, 3]
        # An Add touching an already-sent row re-streams it; unsent
        # rows do not (their chunk will carry the new value anyway).
        mig.note_add(np.asarray([1, 9], dtype=np.int64))
        assert mig.dirty == {1}
        seq1, rows1, fin1 = mig.next_chunk()
        seq2, rows2, fin2 = mig.next_chunk()
        assert not fin1 and not fin2
        seqf, rowsf, finf = mig.next_chunk()
        assert finf and seqf == 3 and rowsf.tolist() == [1]
        assert mig.next_chunk() is None
        # Retransmission regathers any chunk, including the final.
        assert mig.rows_of_seq(1).tolist() == [4, 5, 6, 7]
        assert mig.rows_of_seq(3).tolist() == [1]

    def test_no_dirty_tracking_after_handoff(self):
        mig = self._mig(chunk=100)
        mig.next_chunk()  # the whole range
        mig.next_chunk()  # final
        mig.note_add(np.asarray([2], dtype=np.int64))
        assert mig.dirty == set()

    def test_in_gap_detection_and_duplicate_drop(self):
        mig = sm.MigrationIn(epoch=1, src_sid=0, src_rank=0, lo=0, hi=10)
        assert mig.note_applied(0)
        assert not mig.note_applied(0)  # duplicate/retransmit raced
        mig.n_chunks = 2  # final seq
        assert mig.note_applied(2)
        assert not mig.check_complete()
        assert mig.missing_seqs() == [1]
        assert mig.note_applied(1)
        assert mig.check_complete()


class TestChaosFilter:
    def _arm(self, spec):
        set_flag("chaos_frames", spec)
        # force the module to re-read the flag
        chaos._frames_spec = None

    def teardown_method(self):
        set_flag("chaos_frames", "")
        chaos._frames_spec = None

    def _msg(self, t=MsgType.Request_ShardData, dst=1):
        return Message(src=0, dst=dst, msg_type=t)

    def test_off_is_none(self):
        self._arm("")
        assert chaos.filter_frames(self._msg()) is None

    def test_drop_is_deterministic_and_scoped(self):
        self._arm("drop=1.0,classes=shard,seed=3")
        assert chaos.filter_frames(self._msg()) == []
        # Data-plane frames are out of scope for classes=shard.
        assert chaos.filter_frames(
            self._msg(MsgType.Request_Get)) is None

    def test_dst_scope(self):
        self._arm("drop=1.0,classes=all,dst=2")
        assert chaos.filter_frames(self._msg(dst=1)) is None
        assert chaos.filter_frames(self._msg(dst=2)) == []

    def test_reorder_holds_then_swaps(self):
        self._arm("reorder=1.0,classes=shard,seed=1")
        a, b = self._msg(), self._msg()
        assert chaos.filter_frames(a) == []      # held
        out = chaos.filter_frames(b)
        assert out == [b, a]                     # newer jumps the queue

    def test_window_closes(self):
        self._arm("drop=1.0,classes=shard,for_s=0.05")
        assert chaos.filter_frames(self._msg()) == []
        time.sleep(0.1)
        assert chaos.filter_frames(self._msg()) is None

    def test_kill_point_countdown_is_safe_below_target(self):
        set_flag("chaos_kill_on", "some_point:99")
        try:
            chaos.kill_point("other_point")  # no match: no-op
            chaos.kill_point("some_point")   # hit 1 of 99: survives
        finally:
            set_flag("chaos_kill_on", "")


class TestAutoReshardPlanner:
    class _FakeZoo:
        num_servers = 3
        net_size = 1
        rank = 0
        _actors: dict = {}

        def __init__(self):
            self.sent = []

        def server_rank(self, sid):
            return int(sid)

        def rank_to_server_id(self, rank):
            return int(rank)

        def send_to(self, name, msg):
            self.sent.append(msg)

    def test_skew_triggers_a_split_toward_the_coldest(self):
        set_flag("reshard_auto", True)
        set_flag("reshard_skew", 2.0)
        try:
            zoo = self._FakeZoo()
            mgr = sm.ReshardManager(zoo)
            hot_rows = np.asarray([1, 2], dtype=np.int32)
            counts = np.asarray([500, 400], dtype=np.int32)
            mgr.note_report(0, 0, hot_rows, counts, num_items=30)
            mgr.note_report(0, 1, np.asarray([12], np.int32),
                            np.asarray([3], np.int32), num_items=30)
            mgr.note_report(0, 2, np.asarray([22], np.int32),
                            np.asarray([2], np.int32), num_items=30)
            # Server 0 carries ~99% of the load: a move must be in
            # flight, sourced at 0, keeping the hottest row (1) at 0.
            assert mgr._pending is not None
            assert mgr._pending.src_sid == 0
            assert mgr._pending.dst_sid in (1, 2)
            assert not (mgr._pending.lo <= 1 < mgr._pending.hi)
            # The Begin actually left toward the source rank.
            assert any(m.type_int == int(MsgType.Request_ShardBegin)
                       for m in zoo.sent)
        finally:
            set_flag("reshard_auto", False)

    def test_balanced_load_plans_nothing(self):
        set_flag("reshard_auto", True)
        try:
            zoo = self._FakeZoo()
            mgr = sm.ReshardManager(zoo)
            for sid in range(3):
                mgr.note_report(0, sid, np.asarray([sid], np.int32),
                                np.asarray([100], np.int32),
                                num_items=30)
            assert mgr._pending is None and not mgr._queue
        finally:
            set_flag("reshard_auto", False)


class TestReplicaReconcile:
    def test_reconcile_revives_and_marks(self):
        # Satellite: dead-server marks are re-validated against the
        # controller's authoritative node table on every map broadcast
        # — a rejoined server resumes serving replicas WITHOUT waiting
        # for organic traffic.
        r = rm.ReplicaRouter(3, salt=0)
        r.apply(1, np.asarray([1, 2], np.int32))
        r.mark_dead(2)
        assert 2 in r._dead
        r.reconcile([0, 1, 2])
        assert r._dead == set()
        r.reconcile([0])  # controller says 1 and 2 are dead
        assert r._dead == {1, 2}

    def test_deactivated_router_ignores_later_maps(self):
        r = rm.ReplicaRouter(2)
        r.apply(1, np.asarray([3], np.int32))
        r.deactivate()
        assert not r.active
        assert not r.apply(2, np.asarray([4], np.int32))
        assert not r.active


class TestBeginRefusal:
    def test_sparse_and_stateful_refuse(self, env):
        sparse = mv.create_matrix_table(8, 2, is_sparse=True)
        momentum = mv.create_matrix_table(8, 2, updater_type="momentum")
        assert sparse.reshard_space() == 0  # worker-side guard
        zoo = mv.current_zoo()
        with pytest.raises(ValueError):
            zoo.reshard_table(sparse, [0])
        srv = zoo._actors["server"]
        desc = np.asarray([0, 4, 0, 1, 1, 1, 8], dtype=np.int64)
        assert not srv._store[sparse.table_id].shard_begin_out(desc)
        assert not srv._store[momentum.table_id].shard_begin_out(desc)
        arr = mv.create_array_table(16)
        with pytest.raises(ValueError):
            zoo.reshard_table(arr, [0])


class TestSnapshotElasticMeta:
    def test_matrix_meta_roundtrip(self, env):
        import io
        table = mv.create_matrix_table(8, 2)
        srv = mv.current_zoo()._actors["server"]._store[table.table_id]
        srv._overlay = {9: np.asarray([1.0, 2.0], np.float32)}
        srv._fwd = [(4, 6, 1, 1)]
        srv._smap = sm.ShardMap.initial(8, 1).move(4, 6, 1)
        meta = srv.snapshot_meta()
        assert meta["elastic"] == 1 and meta["shard_epoch"] == 1
        state = srv.snapshot_state()
        buf = io.BytesIO()
        srv.write_snapshot(state, buf)
        srv._overlay, srv._fwd = {}, []
        srv.load_with_meta(io.BytesIO(buf.getvalue()), meta)
        assert 9 in srv._overlay
        np.testing.assert_allclose(srv._overlay[9], [1.0, 2.0])
        assert srv._fwd == [(4, 6, 1, -1)]  # rank re-resolved (1 shard)


# ---------------------------------------------------------------------------
# mid-stream equivalence: 1-vs-N across a live shard-map change
# ---------------------------------------------------------------------------

def _elastic_workload(reshard: bool):
    """Matrix + KV + array + sparse in ONE cluster; the matrix and KV
    tables reshard mid-stream when asked (grow onto a standby, then
    drain back) while the array/sparse siblings keep trading — their
    results must be untouched by their neighbors' migrations."""
    def body(rank):
        rng = np.random.default_rng(21)
        matrix = mv.create_matrix_table(17, 3)
        kv = mv.create_kv_table()
        arr = mv.create_array_table(13)
        sparse = mv.create_matrix_table(10, 2, is_sparse=True)
        if matrix is None:
            mv.current_zoo().barrier()
            return None
        outs = []
        kv_keys = np.array([0, 1, 7, 100, 101, 10**6], np.int64)
        for step in range(6):
            ids = np.unique(rng.integers(0, 17, 10).astype(np.int32))
            matrix.add_rows(ids, rng.standard_normal(
                (ids.size, 3)).astype(np.float32))
            kv.add(kv_keys, rng.standard_normal(
                kv_keys.size).astype(np.float32))
            arr.add(rng.standard_normal(13).astype(np.float32))
            sids = np.unique(rng.integers(0, 10, 4).astype(np.int32))
            sparse.add_rows(sids, rng.standard_normal(
                (sids.size, 2)).astype(np.float32))
            if reshard and step == 2:
                mv.reshard_table(matrix, [0, 1, 2], wait_s=60.0)
                mv.reshard_table(kv, [0, 1, 2], wait_s=60.0)
            if reshard and step == 4:
                mv.reshard_table(matrix, [0, 1], wait_s=60.0)
            outs.append(matrix.get_rows(
                np.arange(17, dtype=np.int32)).copy())
            outs.append(matrix.get().copy())
            outs.append(np.asarray(
                [kv.get(kv_keys)[int(k)] for k in kv_keys]))
            outs.append(arr.get().copy())
            outs.append(sparse.get().copy())
        mv.current_zoo().barrier()
        return outs

    return body


class TestMidStreamEquivalence:
    def test_all_table_types_across_a_live_reshard(self):
        baseline = LocalCluster(1).run(_elastic_workload(False))[0]
        cluster = LocalCluster(3, argv=["-shard_initial_servers=2"],
                               roles=["all", "server", "server"])
        cluster.timeout = 240.0
        live = cluster.run(_elastic_workload(True))[0]
        assert len(baseline) == len(live)
        for i, (a, b) in enumerate(zip(baseline, live)):
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6,
                                       err_msg=f"output {i}")

    def test_unsupported_table_nacks_and_rolls_back(self):
        # A reshard request aimed at an ARRAY table's id (crafted at
        # the controller: array tables never send one themselves) must
        # be refused by the server and rolled back without touching
        # anything — the rollback path proven without any process
        # death.
        def body(rank):
            from multiverso_tpu.core.blob import Blob
            from multiverso_tpu.runtime import actor as actors
            arr = mv.create_array_table(12)
            zoo = mv.current_zoo()
            if rank != 0:
                zoo.barrier()
                zoo.barrier()
                return None
            arr.add(np.ones(12, np.float32))
            zoo.barrier()
            msg = Message(src=zoo.rank, dst=0,
                          msg_type=MsgType.Control_Shard_Request,
                          table_id=arr.table_id)
            msg.push(Blob(np.asarray([12, 0, 0], dtype=np.int64)))
            zoo.send_to(actors.COMMUNICATOR, msg)
            deadline = time.monotonic() + 20
            ctrl = zoo._actors.get(actors.CONTROLLER)
            while time.monotonic() < deadline:
                if ctrl is not None and ctrl.reshards._pending is None \
                        and not ctrl.reshards._queue \
                        and ctrl.reshards.maps:
                    break
                time.sleep(0.05)
            # The map never advanced and the table still serves.
            assert ctrl.reshards.maps[arr.table_id].epoch == 0
            got = arr.get()
            mv.current_zoo().barrier()
            return got

        res = LocalCluster(2).run(body)
        np.testing.assert_allclose(res[0], np.full(12, 1.0, np.float32))


# ---------------------------------------------------------------------------
# property: version regressions only with a generation change
# ---------------------------------------------------------------------------

class TestHandoffVersionProperty:
    def test_no_regression_without_generation_change(self):
        """Across random (Get, Add) interleavings spanning two live
        migrations, every version stamp a worker observes per shard is
        monotone — the ONLY sanctioned discontinuity is the shard-map
        generation-change invalidation (note_shard_moved), and
        forwarded replies/acks are constructed so the tracker never
        sees a regression at all."""
        def body(rank):
            table = mv.create_matrix_table(16, 2)
            if table is None:
                mv.current_zoo().barrier()
                return None
            regressions = []
            gen_changes = []
            tracker = table._version_tracker
            orig_note = table.note_version

            def spy_note(sid, version):
                if tracker.regressed(sid, version):
                    regressions.append((sid, version,
                                        tracker.latest(sid)))
                orig_note(sid, version)

            orig_moved = table.note_shard_moved

            def spy_moved(old_sid):
                gen_changes.append(old_sid)
                orig_moved(old_sid)

            table.note_version = spy_note
            table.note_shard_moved = spy_moved
            rng = np.random.default_rng(9)
            did = [False, False]
            for step in range(120):
                ids = np.unique(rng.integers(0, 16, 6).astype(np.int32))
                if rng.random() < 0.5:
                    table.add_rows(ids, np.ones((ids.size, 2),
                                                np.float32))
                else:
                    table.get_rows(ids)
                if step == 40 and not did[0]:
                    did[0] = True
                    mv.reshard_table(table, [0, 1, 2], wait_s=60.0)
                if step == 80 and not did[1]:
                    did[1] = True
                    mv.reshard_table(table, [0, 2], wait_s=60.0)
            mv.current_zoo().barrier()
            return regressions, gen_changes

        cluster = LocalCluster(3, argv=["-shard_initial_servers=2"],
                               roles=["all", "server", "server"])
        cluster.timeout = 240.0
        regressions, gen_changes = cluster.run(body)[0]
        assert gen_changes, "the reshards never adopted a map"
        assert not regressions, \
            f"version regression without a generation change: " \
            f"{regressions}"


# ---------------------------------------------------------------------------
# chaos: controller partition mid-handoff (in-process; kills are slow)
# ---------------------------------------------------------------------------

class TestControllerPartition:
    def test_commit_survives_a_dropped_control_plane(self):
        """Partition the controller's shard control plane mid-handoff:
        every shard-class frame toward rank 0 drops for a window that
        opens at the destination's first Control_Shard_Done. The
        dual-read window carries traffic meanwhile (zero failed
        requests), the destination re-announces on traffic, and the
        commit lands once the partition heals — the migration
        COMPLETES rather than rolling back."""
        def body(rank):
            from multiverso_tpu.util.dashboard import Dashboard
            table = mv.create_matrix_table(16, 2)
            if table is None:
                mv.current_zoo().barrier()
                return None
            shadow = np.zeros((16, 2), np.float32)
            rng = np.random.default_rng(3)
            for _ in range(3):
                ids = np.unique(rng.integers(0, 16, 8).astype(np.int32))
                d = rng.standard_normal((ids.size, 2)).astype(np.float32)
                table.add_rows(ids, d)
                shadow[ids] += d
            failed = 0
            # Fire the reshard WITHOUT waiting, then keep reading
            # through the partitioned window.
            mv.current_zoo().reshard_table(table, [0, 1, 2], wait_s=0)
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    got = table.get_rows(np.arange(16, dtype=np.int32))
                    if not np.allclose(got, shadow):
                        failed += 1
                except Exception:  # noqa: BLE001
                    failed += 1
                if table.shard_owner_sids() == [0, 1, 2]:
                    break
                time.sleep(0.02)
            dropped = Dashboard.get(chaos.CHAOS_DROPPED).count
            mv.current_zoo().barrier()
            return (failed, table.shard_owner_sids(),
                    table.shard_epoch(), dropped)

        cluster = LocalCluster(
            3,
            argv=["-shard_initial_servers=2",
                  "-chaos_frames=drop=1.0,classes=shard,dst=0,for_s=3,"
                  "seed=5"],
            roles=["all", "server", "server"])
        cluster.timeout = 240.0
        failed, owners, epoch, dropped = cluster.run(body)[0]
        set_flag("chaos_frames", "")
        chaos._frames_spec = None
        assert failed == 0, f"{failed} wrong/failed reads mid-partition"
        assert owners == [0, 1, 2], "commit never landed"
        assert epoch >= 1
        assert dropped > 0, "the partition never actually dropped"


# ---------------------------------------------------------------------------
# chaos matrix: kill the migration endpoints (subprocess TCP; slow)
# ---------------------------------------------------------------------------

_PRELUDE = """
import os, sys, time
import faulthandler
faulthandler.dump_traceback_later(500, exit=True)
import jax
jax.config.update("jax_platforms", "cpu")
sys.path.insert(0, {repo!r})
import numpy as np
import multiverso_tpu as mv
"""


def _spawn(body, log_path, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=REPO)
    env.update(extra_env or {})
    out = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, "-c", _PRELUDE.format(repo=REPO) + body],
        env=env, stdout=out, stderr=subprocess.STDOUT, text=True)
    out.close()
    proc.log_path = log_path
    return proc


def _wait_logged(proc, timeout):
    try:
        proc.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
    with open(proc.log_path) as f:
        return f.read()


def _free_port():
    import socket
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


#: Shared cluster script: rank 0 = worker + controller + server 0,
#: rank 1 = server 1, rank 2 = standby server 2 (the destination of a
#: grow). The worker seeds deterministic values, triggers the grow,
#: and reads the whole table with retries until the outcome settles.
#: Chaos processes coordinate through a DONE file, not barriers or
#: timers: a 3-way barrier can never complete with a SIGKILLed rank in
#: the set (the rejoin-grace failure would then tear down healthy
#: servers mid-test), and fixed timers drift under this one-core box's
#: 60-90s cluster/jit startup. The worker writes the file when its
#: verdict is printed; servers poll it and exit hard (a kill-matrix
#: harness has nothing graceful left to drain).
_CHAOS_COMMON = """
from multiverso_tpu.runtime.net import PeerLostError
rank = int(os.environ["MV_RANK"])
done_file = {done!r}
roles = {{0: "default", 1: "server", 2: "server"}}
flags = ["-machine_file={mf}", "-rank=" + str(rank),
         "-ps_role=" + roles[rank],
         "-shard_initial_servers=2",
         "-reshard_chunk_rows=4",
         "-heartbeat_interval_s=0.5", "-heartbeat_timeout_s=3",
         "-rejoin_grace_s=300",
         "-rpc_retry_max=60", "-rpc_backoff_ms=50",
         "-connect_timeout_s=5"] + {extra_flags!r}
mv.init(flags)
table = mv.create_matrix_table(16, 2)
"""

_CHAOS_WORKER_TAIL = """
expect = np.arange(32, dtype=np.float32).reshape(16, 2)
table.add(expect.copy())
got = table.get_rows(np.arange(16, dtype=np.int32))
assert np.array_equal(got, expect)
time.sleep({presleep})
mv.current_zoo().reshard_table(table, {target}, wait_s=0)
t0 = time.monotonic()
failed = 0
reads = 0
while time.monotonic() - t0 < {window}:
    try:
        got = table.get_rows(np.arange(16, dtype=np.int32))
        reads += 1
        if not np.array_equal(got, expect):
            failed += 1
            print("WRONG_VALUE", flush=True)
    except PeerLostError:
        time.sleep(0.2)  # retryable: the dead rank is restarting
    time.sleep(0.05)
final = table.get_rows(np.arange(16, dtype=np.int32))
print("READS", reads, "FAILED", failed, flush=True)
print("OWNERS", table.shard_owner_sids(), flush=True)
print("FINAL_EQUAL", bool(np.array_equal(final, expect)), flush=True)
print("WORKER_DONE", flush=True)
open(done_file, "w").write("done")
os._exit(0)
"""

_CHAOS_SERVER_TAIL = """
deadline = time.monotonic() + 400
while time.monotonic() < deadline and not os.path.exists(done_file):
    time.sleep(0.3)
print("SERVER_DONE", flush=True)
os._exit(0)
"""


def _chaos_cluster(tmp_path, per_rank_flags, window=25,
                   target=(0, 1, 2), presleep=0.0):
    target = list(target)
    ports = [_free_port() for _ in range(3)]
    mf = tmp_path / "machines"
    mf.write_text("".join(f"127.0.0.1:{p}\n" for p in ports))
    done = str(tmp_path / "worker.done")
    procs = []
    for r in range(3):
        body = _CHAOS_COMMON.format(mf=str(mf), done=done,
                                    extra_flags=per_rank_flags.get(r, []))
        body += _CHAOS_WORKER_TAIL.format(
            window=window, target=target, presleep=presleep) \
            if r == 0 else _CHAOS_SERVER_TAIL
        procs.append(_spawn(body, str(tmp_path / f"rank{r}.log"),
                            extra_env={"MV_RANK": str(r)}))
    return procs


@pytest.mark.slow
class TestChaosKillMatrix:
    def test_kill_migration_destination_rolls_back(self, tmp_path):
        """SIGKILL the DESTINATION the moment it applies the final
        chunk (pre-commit): the controller declares it dead, aborts
        the move at the source (which resumes ownership from its
        handoff copy), and the map stays at the pre-move epoch — with
        ZERO wrong-value reads throughout (the dest was a standby, so
        every row keeps serving)."""
        procs = _chaos_cluster(
            tmp_path,
            {2: ["-chaos_kill_on=shard_dest_final"]})
        out0 = _wait_logged(procs[0], 420)
        procs[2].wait()  # chaos SIGKILLed itself
        _wait_logged(procs[1], 60)
        assert "FAILED 0" in out0 and "WRONG_VALUE" not in out0, \
            out0[-3000:]
        assert "FINAL_EQUAL True" in out0, out0[-3000:]
        assert "rolling back" in out0, out0[-3000:]  # controller log
        # Rolled back before any interval reached server 2: the
        # committed prefix of the plan may have moved rows between the
        # two SURVIVORS, but 2 never owns anything.
        assert "OWNERS [0, 1]" in out0 or "OWNERS None" in out0, \
            out0[-3000:]

    def test_kill_migration_source_post_handoff_rolls_back(
            self, tmp_path):
        """SIGKILL the SOURCE at the instant it composes the final
        chunk (the handoff step itself): the chunk never reaches the
        destination, the controller declares the source dead and
        aborts the move at the destination (partial overlay dropped).
        The worker's reads of the dead source's rows fail RETRYABLY
        until it restarts with -rejoin and restores from its snapshot
        — after which every value is exact again. The reshard target
        [0, 2] makes the plan a SINGLE move ([8,16) from server 1 to
        server 2), so the kill deterministically lands on rank 1's
        handoff instant."""
        snap = tmp_path / "snaps"
        common = ["-snapshot_dir=" + str(snap),
                  "-snapshot_interval_s=0.5"]
        procs = _chaos_cluster(
            tmp_path,
            {0: common,
             1: common + ["-chaos_kill_on=shard_source_final"],
             2: common},
            window=30, target=[0, 2], presleep=3.0)
        # Wait for rank 1 to kill itself mid-handoff, then restart it
        # with -rejoin (the PR-6 machinery; its snapshot restores the
        # pre-kill state and the controller's re-register re-broadcast
        # re-anchors the map).
        procs[1].wait(timeout=260)
        restart = _CHAOS_COMMON.format(
            mf=str(tmp_path / "machines"),
            done=str(tmp_path / "worker.done"),
            extra_flags=common + ["-rejoin=true"])
        restart += _CHAOS_SERVER_TAIL
        replacement = _spawn(restart, str(tmp_path / "rank1b.log"),
                             extra_env={"MV_RANK": "1"})
        out0 = _wait_logged(procs[0], 420)
        _wait_logged(replacement, 120)
        _wait_logged(procs[2], 60)
        assert "WRONG_VALUE" not in out0, out0[-3000:]
        assert "FINAL_EQUAL True" in out0, out0[-3000:]
        assert "READS" in out0, out0[-3000:]
        # CONSISTENT epoch, either arm of the acceptance: ROLLED BACK
        # to the pre-move layout (owners [0,1] / frozen None), or —
        # when the replacement rejoins fast enough for the
        # controller's idempotent Begin-resend to re-drive the move
        # against its snapshot-restored shard — COMPLETED ([0,2]).
        # Both end element-wise exact; a half-moved layout would fail
        # here.
        assert ("OWNERS [0, 1]" in out0 or "OWNERS None" in out0
                or "OWNERS [0, 2]" in out0), out0[-3000:]

"""Utility-layer unit tests (mirrors reference Test/unittests tier 1,
ref: Test/unittests/test_blob.cpp, test_message.cpp, test_node.cpp plus
flag/queue/waiter/dashboard coverage)."""

import threading
import time

import numpy as np
import pytest

from multiverso_tpu.core import Blob, Message, MsgType, Node, Role, is_server, is_worker
from multiverso_tpu.util import (ASyncBuffer, Dashboard, MtQueue, OneBitFilter,
                                 SparseFilter, Timer, Waiter, configure, monitor)
from multiverso_tpu.util.log import CHECK, FatalError


class TestBlob:
    def test_alloc_and_view(self):
        b = Blob(size=12)
        assert b.size == 12
        f = b.as_array(np.float32)
        assert f.size == 3
        f[:] = [1.0, 2.0, 3.0]
        assert b.as_array(np.float32)[1] == 2.0

    def test_wrap_shares_memory(self):
        arr = np.arange(4, dtype=np.float32)
        b = Blob(arr)
        b2 = Blob(b)  # shallow copy shares storage like ref copy-ctor
        b2.as_array(np.float32)[0] = 42.0
        assert arr[0] == 42.0

    def test_copy_is_deep(self):
        arr = np.arange(4, dtype=np.int32)
        b = Blob(arr).copy()
        b.as_array(np.int32)[0] = 9
        assert arr[0] == 0

    def test_typed_count(self):
        b = Blob(np.zeros(10, dtype=np.float64))
        assert b.count(np.float64) == 10
        assert b.count(np.float32) == 20


class TestMessage:
    def test_header_roundtrip(self):
        m = Message(src=3, dst=5, msg_type=MsgType.Request_Add, table_id=2, msg_id=7)
        assert (m.src, m.dst, m.type, m.table_id, m.msg_id) == \
            (3, 5, MsgType.Request_Add, 2, 7)

    def test_reply_flips(self):
        m = Message(src=3, dst=5, msg_type=MsgType.Request_Get, table_id=1, msg_id=9)
        r = m.create_reply_message()
        assert r.src == 5 and r.dst == 3
        assert r.type == MsgType.Reply_Get
        assert r.table_id == 1 and r.msg_id == 9

    def test_payload(self):
        m = Message()
        m.push(np.arange(3, dtype=np.float32))
        m.push(np.arange(5, dtype=np.int32))
        assert m.size() == 2
        assert m.data[0].count(np.float32) == 3


class TestNode:
    def test_roles(self):
        assert is_worker(Role.WORKER) and not is_server(Role.WORKER)
        assert is_server(Role.SERVER) and not is_worker(Role.SERVER)
        assert is_worker(Role.ALL) and is_server(Role.ALL)
        assert not is_worker(Role.NONE) and not is_server(Role.NONE)

    def test_default_node(self):
        n = Node()
        assert n.rank == -1 and n.role == Role.ALL


# The registry-machinery tests exercise define/get/set on deliberately
# synthetic flag names — the one place unregistered names are the point.
class TestConfigure:  # mvlint: ignore[flag-lint]
    def test_parse_cmd_flags(self):
        configure.define_int("test_port", 9999)
        configure.define_bool("test_sync", False)
        configure.define_string("test_name", "x")
        argv = ["prog", "-test_port=1234", "keepme", "-test_sync=true",
                "-test_name=hello"]
        rest = configure.parse_cmd_flags(argv)
        assert rest == ["prog", "keepme"]
        assert configure.get_flag("test_port") == 1234
        assert configure.get_flag("test_sync") is True
        assert configure.get_flag("test_name") == "hello"

    def test_set_flag_coerces(self):
        configure.define_double("test_lr", 0.1)
        configure.set_flag("test_lr", "0.5")
        assert configure.get_flag("test_lr") == 0.5

    def test_unknown_flag_left_in_argv(self):
        # Reference parity: ParseCMDFlags only consumes registered flags
        # (configure.cpp:19-53); unknown entries stay for downstream parsers.
        rest = configure.parse_cmd_flags(["-brandnew=abc"])
        assert rest == ["-brandnew=abc"]
        # Programmatic set_flag (the reference's SetCMDFlag/MV_SetFlag)
        # still registers implicitly.
        configure.set_flag("brandnew", "abc")
        assert configure.get_flag("brandnew") == "abc"

    def test_bad_value_names_flag(self):
        configure.define_int("test_badval", 1)
        with pytest.raises(ValueError, match="test_badval"):
            configure.parse_cmd_flags(["-test_badval=abc"])

    def test_unknown_flag_warns_once_with_suggestion(self, capsys):
        # A typo'd get_flag must not silently return the caller's
        # default: one loud line per process, naming the nearest
        # registered flag (difflib), value still the caller's default.
        configure._warned_unknown.discard("allreduce_windw")
        assert configure.get_flag("allreduce_windw", 7) == 7
        err = capsys.readouterr().err
        assert "allreduce_windw" in err
        assert "did you mean -allreduce_window?" in err
        assert "IGNORED" in err
        # Second read: same value, no second warning.
        assert configure.get_flag("allreduce_windw", 7) == 7
        assert "allreduce_windw" not in capsys.readouterr().err

    def test_canonical_but_unloaded_flag_stays_quiet(self, capsys):
        # A canonical flag whose defining module is not imported reads
        # as the caller default silently (legitimate late binding).
        # 'debug_locks' may already be registered in this process; use
        # a canonical name guaranteed unregistered via a fresh check.
        reg = configure.FlagRegister.get()
        name = next((n for n in configure.CANONICAL_FLAGS
                     if not reg.has(n)), None)
        if name is None:
            pytest.skip("every canonical flag already registered")
        configure.get_flag(name, configure.CANONICAL_FLAGS[name])
        assert name not in capsys.readouterr().err

    def test_define_drift_warns(self, capsys):
        # Registering a canonical flag with a different default is
        # default drift — mvlint catches it statically, the runtime
        # warns on dynamic paths.
        reg = configure.FlagRegister.get()
        fresh = not reg.has("send_queue_mb")
        configure.define_int("send_queue_mb", 99)
        assert "canonical default" in capsys.readouterr().err
        if fresh:  # don't leave the drifted default behind
            reg._flags.pop("send_queue_mb", None)


class TestMtQueue:
    def test_fifo(self):
        q = MtQueue()
        for i in range(5):
            q.push(i)
        assert q.size() == 5
        assert [q.pop() for _ in range(5)] == [0, 1, 2, 3, 4]

    def test_blocking_pop(self):
        q = MtQueue()
        result = []

        def consumer():
            result.append(q.pop())

        t = threading.Thread(target=consumer)
        t.start()
        time.sleep(0.05)
        q.push("item")
        t.join(timeout=2)
        assert result == ["item"]

    def test_exit_unblocks(self):
        q = MtQueue()
        result = []

        def consumer():
            result.append(q.pop())

        t = threading.Thread(target=consumer)
        t.start()
        q.exit()
        t.join(timeout=2)
        assert result == [None]
        ok, _ = q.try_pop()
        assert not ok


class TestWaiter:
    def test_countdown(self):
        w = Waiter(2)
        done = []

        def waiter_thread():
            w.wait()
            done.append(True)

        t = threading.Thread(target=waiter_thread)
        t.start()
        w.notify()
        time.sleep(0.02)
        assert not done
        w.notify()
        t.join(timeout=2)
        assert done

    def test_reset(self):
        w = Waiter(1)
        w.notify()
        assert w.wait(timeout=0.1)
        w.reset(1)
        assert not w.wait(timeout=0.05)


class TestAsyncBuffer:
    def test_prefetch_sequence(self):
        counter = {"n": 0}

        def fill(buf):
            counter["n"] += 1
            buf[0] = counter["n"]

        ab = ASyncBuffer([0], [0], fill)
        first = ab.get()
        assert first[0] == 1
        second = ab.get()
        assert second[0] == 2
        ab.stop()


class TestSparseFilter:
    def test_compress_roundtrip(self):
        f = SparseFilter(clip_value=0.0)
        dense = np.zeros(100, dtype=np.float32)
        dense[[3, 50, 99]] = [1.5, -2.0, 3.0]
        blobs, sizes = f.filter_in([dense])
        assert sizes[0] == 100
        # Compact codec frame (float64-pair format removed): 24-byte
        # header + u32 first idx + 2 u16 gaps + 3 fp32 values = 44 B,
        # vs 48 B of float64 pairs.
        assert blobs[0].dtype == np.uint8 and blobs[0].size == 44
        out = f.filter_out(blobs, sizes)
        np.testing.assert_array_equal(out[0], dense)

    def test_lossy_residual_exposed(self):
        f = SparseFilter(lossy=True)
        dense = np.zeros(4096, dtype=np.float32)
        rng = np.random.default_rng(3)
        hot = rng.choice(4096, 200, replace=False)
        dense[hot] = rng.standard_normal(200).astype(np.float32)
        blobs, sizes = f.filter_in([dense])
        out = f.filter_out(blobs, sizes)[0]
        residual = f.last_residuals[0]
        if residual is None:  # heuristic picked a lossless tier
            np.testing.assert_array_equal(out, dense)
        else:
            np.testing.assert_allclose(out + residual, dense,
                                       rtol=0, atol=1e-5)

    def test_dense_passthrough(self):
        f = SparseFilter()
        dense = np.arange(1, 11, dtype=np.float32)
        blobs, sizes = f.filter_in([dense])
        assert sizes[0] == -1
        out = f.filter_out(blobs, sizes)
        np.testing.assert_array_equal(out[0], dense)

    def test_one_bit(self):
        f = OneBitFilter()
        arr = np.array([1.0, 2.0, -1.0, -3.0], dtype=np.float32)
        enc, residual = f.encode(arr)
        dec = f.decode(enc)
        np.testing.assert_allclose(dec, [1.5, 1.5, -2.0, -2.0])
        np.testing.assert_allclose(arr - dec, residual)


class TestDashboardAndTimer:
    def test_monitor_counts(self):  # mvlint: ignore[metric-name]
        Dashboard.reset()
        with monitor("unit_test_region"):
            time.sleep(0.01)
        with monitor("unit_test_region"):
            pass
        mon = Dashboard.get("unit_test_region")
        assert mon.count == 2
        assert mon.elapse >= 10.0
        assert "unit_test_region" in Dashboard.display()

    def test_timer(self):
        t = Timer()
        time.sleep(0.01)
        assert t.elapse() >= 9.0
        t.start()
        assert t.elapse() < 9.0


class TestCheck:
    def test_check_raises(self):
        with pytest.raises(FatalError):
            CHECK(False, "boom")
        CHECK(True)


class TestTraceTo:
    def test_trace_capture_holds_the_monitors_span(self, tmp_path):
        # Whole-program xprof capture (the TPU-side tracing complement
        # to the Dashboard counters, SURVEY.md section 5.1): every
        # monitor is an mv:<name> span in it, with no option to set.
        import glob

        import jax.numpy as jnp
        from jax.profiler import ProfileData

        from multiverso_tpu.util import monitor, trace_to
        with trace_to(str(tmp_path)):
            with monitor("TRACE_REGION"):  # mvlint: ignore[metric-name]
                jnp.ones((32, 32)) @ jnp.ones((32, 32))
        path, = glob.glob(str(tmp_path) + "/**/*.xplane.pb", recursive=True)
        names = {e.name for plane in ProfileData.from_file(path).planes
                 if plane.name == "/host:CPU"
                 for line in plane.lines for e in line.events}
        assert "mv:TRACE_REGION" in names

    def test_monitor_outside_a_capture_counts_and_times(self):
        # "Tracing off" is "no profiler session": the same monitor, no
        # trace, the Dashboard's count and milliseconds as ever.
        from multiverso_tpu.util import monitor
        Dashboard.reset()
        with monitor("UNTRACED_REGION") as mon:  # mvlint: ignore[metric-name]
            time.sleep(0.01)
        assert mon is Dashboard.get("UNTRACED_REGION")
        assert mon.count == 1 and mon.elapse >= 10.0
        Dashboard.reset()


class TestMonitorResetRegression:
    def test_monitor_ctx_survives_dashboard_reset(self):
        # Regression (ISSUE 9 satellite): the context manager used to
        # cache its Monitor at CONSTRUCTION, so a Dashboard.reset()
        # (tests do one between cases) left long-lived monitor(...)
        # instances writing to unregistered orphans invisible to
        # display()/snapshots.
        ctx = monitor("reset_survivor")  # mvlint: ignore[metric-name]
        with ctx:
            pass
        assert Dashboard.get("reset_survivor").count == 1
        Dashboard.reset()
        with ctx:  # must re-resolve into the FRESH registry
            pass
        assert Dashboard.get("reset_survivor").count == 1
        assert "reset_survivor" in Dashboard.display()

    def test_display_sorted_with_samples_section(self):
        from multiverso_tpu.util.dashboard import reset_samples, samples
        Dashboard.reset()
        reset_samples()
        with monitor("zz_late"):  # mvlint: ignore[metric-name]
            pass
        with monitor("aa_early"):  # mvlint: ignore[metric-name]
            pass
        samples("mm_samples").add(2.0)  # mvlint: ignore[metric-name]
        samples("mm_samples").add(4.0)  # mvlint: ignore[metric-name]
        report = Dashboard.display()
        # Monitors sorted by name regardless of registration order,
        # and the Samples registry is part of the report.
        assert report.index("[aa_early]") < report.index("[zz_late]")
        assert "[mm_samples]" in report and "p99" in report
        # Deterministic: two successive dumps diff clean.
        assert report == Dashboard.display()
        Dashboard.reset()
        reset_samples()


class TestSamplesEdges:
    def _fresh(self, cap):
        from multiverso_tpu.util.dashboard import Samples
        return Samples("edge_test", cap=cap)

    def test_ring_wraparound_keeps_most_recent_cap(self):
        s = self._fresh(cap=8)
        for v in range(30):
            s.add(float(v))
        assert s.count == 30
        # Exactly the newest 8 retained, in order.
        assert s.export_recent(100) == [float(v) for v in range(22, 30)]
        assert s.percentile(0) == 22.0
        assert s.percentile(100) == 29.0

    def test_export_recent_limit_and_prewrap_order(self):
        s = self._fresh(cap=8)
        for v in range(5):
            s.add(float(v))
        assert s.export_recent(100) == [0.0, 1.0, 2.0, 3.0, 4.0]
        assert s.export_recent(2) == [3.0, 4.0]

    def test_nearest_rank_one_element_window(self):
        s = self._fresh(cap=4)
        s.add(7.5)
        for p in (0, 1, 50, 99, 100):
            assert s.percentile(p) == 7.5
        snap = s.snapshot()
        assert snap["p50"] == snap["p99"] == snap["max"] == 7.5
        assert snap["count"] == 1

    def test_nearest_rank_two_element_window(self):
        s = self._fresh(cap=4)
        s.add(10.0)
        s.add(20.0)
        # Nearest-rank: ceil(p/100 * 2) -> p50 is the LOWER value,
        # p51+ the upper; p0 clamps to the min.
        assert s.percentile(0) == 10.0
        assert s.percentile(50) == 10.0
        assert s.percentile(51) == 20.0
        assert s.percentile(99) == 20.0
        assert s.percentile(100) == 20.0

    def test_empty_window(self):
        s = self._fresh(cap=4)
        assert s.percentile(50) == 0.0
        assert s.snapshot() == {"count": 0}
        assert s.export_recent() == []

    def test_concurrent_add_under_debug_locks(self):
        # The reservoir's lock goes through the lock_witness factory;
        # with -debug_locks on, witnessed concurrent adds must neither
        # deadlock nor lose counts, and the ring bound must hold.
        from multiverso_tpu.util.configure import set_flag
        set_flag("debug_locks", True)
        try:
            s = self._fresh(cap=64)
            n_threads, per_thread = 8, 500

            def pound(seed):
                for k in range(per_thread):
                    s.add(float(seed * per_thread + k))

            threads = [threading.Thread(target=pound, args=(t,))
                       for t in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
            assert s.count == n_threads * per_thread
            assert len(s.export_recent(1000)) == 64
            snap = s.snapshot()
            assert snap["p50"] <= snap["p99"] <= snap["max"]
        finally:
            set_flag("debug_locks", False)

"""The bench harness's loss-proof properties (VERDICT r4 #1).

Round 4's entire performance story was erased by a driver timeout
because bench.py printed its one JSON line only at the very end. These
tests pin the defenses: cumulative emission after every merge, the
wall-budget skip, failure isolation, and the baseline cache's
source-sensitivity. They run bench.py's HARNESS only — no corpus, no
device work (bench imports jax lazily inside phase functions)."""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import bench  # noqa: E402


def _last_json(capsys):
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.strip()]
    assert lines, "nothing emitted"
    return json.loads(lines[-1])


class TestResultEmission:
    def test_every_merge_emits_a_complete_line(self, capsys):
        r = bench._Result()
        r.merge(alpha=1)
        r.merge(beta={"x": [1, 2]})
        doc = _last_json(capsys)
        assert doc["detail"]["alpha"] == 1
        assert doc["detail"]["beta"] == {"x": [1, 2]}
        assert "elapsed_sec" in doc["detail"]["wall_budget"]

    def test_phase_failure_is_recorded_and_does_not_propagate(
            self, capsys):
        r = bench._Result()

        def boom():
            raise RuntimeError("phase exploded")

        out = r.run("exploding_phase", boom)
        assert out is None
        doc = _last_json(capsys)
        assert "phase exploded" in doc["detail"]["exploding_phase_error"]
        # ... and counted: main() returns len(failed) as the exit code.
        assert r.run("fine_phase", lambda: 1) == 1
        assert r.failed == ["exploding_phase"]

    def test_wall_budget_skips_instead_of_starting(self, capsys,
                                                   monkeypatch):
        monkeypatch.setattr(bench, "WALL_BUDGET_SEC", 1.0)
        monkeypatch.setattr(bench, "_BENCH_T0", time.monotonic() - 10)
        r = bench._Result()
        ran = []
        out = r.run("local_train", lambda: ran.append(1))
        assert out is None and not ran
        doc = _last_json(capsys)
        assert "local_train" in doc["detail"]["wall_budget"]["skipped"]

    def test_est_override_admits_cheap_cached_phase(self, capsys,
                                                    monkeypatch):
        # A cached baseline costs seconds; the skip check must honor
        # the caller's estimate override instead of the worst case.
        monkeypatch.setattr(bench, "WALL_BUDGET_SEC", 60.0)
        monkeypatch.setattr(bench, "_BENCH_T0", time.monotonic() - 45)
        r = bench._Result()
        assert r.run("cpu_baseline", lambda: "hit", est=10) == "hit"
        assert r.run("cpu_baseline_2", lambda: "never") is None

    def test_sigterm_handler_emits_interrupted_record(self, capsys,
                                                      monkeypatch):
        # Drive the real kill handler (os._exit neutered): it must
        # print a complete line carrying the interrupt marker.
        import signal
        exits = []
        monkeypatch.setattr(os, "_exit", exits.append)
        r = bench._Result()
        r.merge(gamma=3)
        saved_term = signal.getsignal(signal.SIGTERM)
        saved_int = signal.getsignal(signal.SIGINT)
        try:
            bench._install_kill_emitter(r)
            handler = signal.getsignal(signal.SIGTERM)
            capsys.readouterr()
            handler(signal.SIGTERM, None)
        finally:
            signal.signal(signal.SIGTERM, saved_term)
            signal.signal(signal.SIGINT, saved_int)
        doc = _last_json(capsys)
        assert doc["detail"]["gamma"] == 3
        assert doc["detail"]["wall_budget"]["interrupted"] == "SIGTERM"
        assert exits == [98]

    def test_sigterm_handler_falls_back_to_last_serialized_line(
            self, capsys, monkeypatch):
        # If a fresh serialization fails (mid-merge dict mutation),
        # the handler must reprint the LAST complete emitted line
        # rather than die with nothing on stdout.
        import signal
        monkeypatch.setattr(os, "_exit", lambda code: None)
        r = bench._Result()
        r.merge(delta=4)
        monkeypatch.setattr(
            r, "emit",
            lambda: (_ for _ in ()).throw(RuntimeError("torn")))
        saved_term = signal.getsignal(signal.SIGTERM)
        saved_int = signal.getsignal(signal.SIGINT)
        try:
            bench._install_kill_emitter(r)
            handler = signal.getsignal(signal.SIGTERM)
            capsys.readouterr()
            handler(signal.SIGTERM, None)
        finally:
            signal.signal(signal.SIGTERM, saved_term)
            signal.signal(signal.SIGINT, saved_int)
        doc = _last_json(capsys)
        assert doc["detail"]["delta"] == 4  # the pre-serialized line


class TestBaselineCache:
    def test_key_tracks_source_files(self, tmp_path):
        src = tmp_path / "dep.py"
        src.write_text("A = 1\n")
        p1 = bench._baseline_cache_path("cpu_baseline", [str(src)])
        src.write_text("A = 2\n")
        p2 = bench._baseline_cache_path("cpu_baseline", [str(src)])
        assert p1 != p2  # edited dependency invalidates
        src.write_text("A = 1\n")
        assert bench._baseline_cache_path(
            "cpu_baseline", [str(src)]) == p1  # content-addressed

    def test_roundtrip_and_cached_marker(self, tmp_path, monkeypatch,
                                         capsys):
        # Point the cache dir at tmp by relocating bench's notion of
        # its own file.
        monkeypatch.setattr(bench, "__file__",
                            str(tmp_path / "bench.py"))
        src = tmp_path / "dep.py"
        src.write_text("A = 1\n")
        calls = []

        def fake_baseline():
            calls.append(1)
            return {"wps": 123.0, "epoch_losses": [1.0]}

        out1 = bench._cached_baseline("cpu_baseline", [str(src)],
                                      fake_baseline)
        out2 = bench._cached_baseline("cpu_baseline", [str(src)],
                                      fake_baseline)
        assert len(calls) == 1  # second call served from disk
        assert "cached" not in out1 and out2["cached"] is True
        assert out2["wps"] == 123.0
        est = bench._baseline_est("cpu_baseline", [str(src)])
        assert est == 10  # cache hit -> seconds, not the worst case
        src.write_text("A = 2\n")
        assert bench._baseline_est(
            "cpu_baseline", [str(src)]) == bench._PHASE_EST[
                "cpu_baseline"]


class TestFlagGuard:
    def test_restores_values_set_inside(self):
        from multiverso_tpu.util.configure import get_flag, set_flag
        before = get_flag("max_get_staleness")
        with bench.flag_guard():
            set_flag("max_get_staleness", 42)
            set_flag("trace_sample_rate", 0.5)
            assert get_flag("max_get_staleness") == 42
        assert get_flag("max_get_staleness") == before
        assert get_flag("trace_sample_rate") == 0.0

    def test_restores_on_exception(self):
        from multiverso_tpu.util.configure import get_flag, set_flag

        @bench.flag_guarded
        def phase():
            set_flag("net_pace_mbps", 99.0)
            raise RuntimeError("mid-phase failure")

        try:
            phase()
        except RuntimeError:
            pass
        assert get_flag("net_pace_mbps") == 0.0

    def test_implicit_registration_restores_canonical_default(self):
        # A tunable applied (e.g. via Control_Config) before its
        # defining module imported is implicitly registered with
        # default == the applied value; the guard must restore the
        # CANONICAL default, not that accidental one — or the tuned
        # knob would leak into every later phase's default numbers.
        from multiverso_tpu.util.configure import (CANONICAL_FLAGS,
                                                   FlagRegister,
                                                   get_flag, set_flag)
        reg = FlagRegister.get()
        name = "serving_batch_window_ms"
        saved = reg._flags.pop(name, None)
        try:
            with bench.flag_guard():
                set_flag(name, 9.5)  # implicit registration
                assert get_flag(name) == 9.5
            assert get_flag(name) == CANONICAL_FLAGS[name]
        finally:
            if saved is not None:
                reg._flags[name] = saved

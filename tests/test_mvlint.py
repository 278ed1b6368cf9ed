"""Self-tests for the mvlint static-analysis suite (tools/mvlint).

Each pass runs over a fixture file with seeded violations
(tools/mvlint/fixtures/) so the analyzers themselves are
regression-protected: a pass that silently stops firing breaks these
counts, and a pass that starts over-firing breaks the clean-tree gate.
"""

from __future__ import annotations

import subprocess
import sys
from pathlib import Path

import pytest

from tools.mvlint import REPO_ROOT, build_passes, run
from tools.mvlint.framework import ModuleInfo, run_passes
from tools.mvlint.wire_slot_lint import WireSlotLint, parse_doc_slots

FIXTURES = Path(__file__).parent.parent / "tools" / "mvlint" / "fixtures"


def _fixture_result(name: str):
    return run_passes(build_passes(REPO_ROOT),
                      [str(FIXTURES / name)], REPO_ROOT)


class TestFixtures:
    def test_flag_lint_seeded(self):
        result = _fixture_result("bad_flags.py")
        found = [v for v in result.violations
                 if v.pass_name == "flag-lint"]
        assert len(found) == 4, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        # The typo diagnostic names the nearest real flag.
        assert "did you mean 'allreduce_window'" in messages
        assert "default drift" in messages
        assert "drifts from the canonical default 32" in messages
        assert result.per_pass_suppressed["flag-lint"] == 1

    def test_wire_slot_seeded(self):
        result = _fixture_result("bad_wire_slots.py")
        found = [v for v in result.violations
                 if v.pass_name == "wire-slot"]
        assert len(found) == 3, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert "raw header[5]" in messages
        assert "'MY_SLOT'" in messages
        assert "computed header index" in messages
        assert result.per_pass_suppressed["wire-slot"] == 1

    def test_device_dispatch_seeded(self):
        result = _fixture_result("bad_device_train.py")
        found = [v for v in result.violations
                 if v.pass_name == "device-dispatch"]
        # Exactly the three unguarded eager sites; everything guarded,
        # traced (decorated / jit-by-name / called-from-traced), or
        # pragma'd stays silent.
        assert len(found) == 3, [v.render() for v in found]
        lines = sorted(v.line for v in found)
        src = (FIXTURES / "bad_device_train.py").read_text().splitlines()
        for line in lines:
            assert "# A" in src[line - 1] or "# B" in src[line - 1] \
                or "# C" in src[line - 1], src[line - 1]
        assert result.per_pass_suppressed["device-dispatch"] == 1

    def test_fused_device_dispatch_seeded(self):
        # PR-19 regression fixture: fused dispatch sites (one device
        # program for MANY requests, runtime/fusion.py) are ordinary
        # call sites to the pass — an unguarded fused concat+gather is
        # flagged exactly like a serial one, and the _lock_for guard
        # Server._run_fused_group holds keeps the real path silent.
        result = _fixture_result("bad_fused_device_train.py")
        found = [v for v in result.violations
                 if v.pass_name == "device-dispatch"]
        assert len(found) == 2, [v.render() for v in found]
        src = (FIXTURES / "bad_fused_device_train.py") \
            .read_text().splitlines()
        for line in sorted(v.line for v in found):
            assert "# D" in src[line - 1] or "# E" in src[line - 1], \
                src[line - 1]
        assert result.per_pass_suppressed["device-dispatch"] == 1

    def test_lock_discipline_seeded(self):
        result = _fixture_result("bad_locks.py")
        found = [v for v in result.violations
                 if v.pass_name == "lock-discipline"]
        assert len(found) == 7, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert "bare .acquire()" in messages
        assert "bare .release()" in messages
        assert "blocking call .pop" in messages
        assert "blocking call .join" in messages
        assert "blocking call .wait(" in messages
        # wait_for's mandatory predicate must not read as a timeout.
        assert "blocking call .wait_for" in messages
        # socket.recv's bufsize must not read as a timeout either.
        assert "blocking call .recv" in messages
        assert result.per_pass_suppressed["lock-discipline"] == 1

    def test_metric_name_seeded(self):
        result = _fixture_result("bad_metrics.py")
        found = [v for v in result.violations
                 if v.pass_name == "metric-name"]
        assert len(found) == 3, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        # The typo diagnostic names the nearest real metric.
        assert "did you mean 'SERVER_PROCESS_GET'" in messages
        assert "DISPATCH_MS[q9]" in messages
        assert "TOTALLY_MADE_UP_COUNTER" in messages
        # The family instance and the str.count attribute call in the
        # fixture stay silent; the pragma'd site counts as suppressed.
        assert result.per_pass_suppressed["metric-name"] == 1

    def test_send_discipline_seeded(self):
        result = _fixture_result("bad_sends.py")
        found = [v for v in result.violations
                 if v.pass_name == "send-discipline"]
        assert len(found) == 2, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert "send_async" in messages
        # send_async, socket.send and generator.send stay silent; the
        # pragma'd site counts as suppressed.
        assert result.per_pass_suppressed["send-discipline"] == 1

    def test_tunable_lint_seeded(self):
        result = _fixture_result("bad_tunables.py")
        found = [v for v in result.violations
                 if v.pass_name == "tunable-lint"]
        assert len(found) == 2, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert "did you mean 'max_get_staleness'" in messages
        assert "'port'" in messages

    def test_copy_lint_seeded(self):
        result = _fixture_result("bad_copies.py")
        found = [v for v in result.violations
                 if v.pass_name == "copy-lint"]
        assert len(found) == 3, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert ".tobytes() copies the whole payload" in messages
        assert "bytes-join builds a flat frame copy" in messages
        assert "bytes(...) copies its buffer" in messages
        # memoryview/frombuffer view reads and no-arg bytes() stay
        # silent; the pragma'd legacy-path site counts as suppressed.
        assert result.per_pass_suppressed["copy-lint"] == 1

    def test_copy_lint_out_of_scope_module_is_silent(self):
        # The ban applies to the wire-path modules only: the same
        # patterns in a fixture scanned under a non-wire rel path stay
        # silent for every OTHER fixture (which all use bytes/joins
        # freely in their own seeded content).
        result = _fixture_result("bad_flags.py")
        assert not [v for v in result.violations
                    if v.pass_name == "copy-lint"]

    def test_thread_role_seeded(self):
        result = _fixture_result("bad_roles.py")
        found = [v for v in result.violations
                 if v.pass_name == "thread-role"]
        assert len(found) == 5, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        # The PR-6 regression, interprocedurally: the blocking send
        # sits two helpers below the LIVENESS entry, and the chain
        # names every hop.
        assert "blocking net.send() reachable" in messages
        assert "LIVENESS" in messages
        assert "_hb_main -> bad_roles.py:SeededMonitor._emit" \
            in messages
        assert "raw threading.Thread()" in messages
        assert "not a literal role constant" in messages
        assert "without a role" in messages
        assert "does not resolve" in messages
        assert result.per_pass_suppressed["thread-role"] == 1

    def test_guarded_by_seeded(self):
        result = _fixture_result("bad_guards.py")
        found = [v for v in result.violations
                 if v.pass_name == "guarded-by"]
        assert len(found) == 3, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert "registers no such lock with the witness" in messages
        # Off-lock direct access, and the helper whose caller holds
        # nothing; the caller-holds helper (_bump) stays silent.
        assert "in SeededCache.bad_read()" in messages
        assert "in SeededCache._store()" in messages
        assert "_bump" not in messages
        assert result.per_pass_suppressed["guarded-by"] == 1

    def test_msg_flow_seeded(self):
        result = _fixture_result("bad_msg_flow.py")
        found = [v for v in result.violations
                 if v.pass_name == "msg-flow"]
        assert len(found) == 4, [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        # Duplicate registration names the shadowed first site.
        assert "duplicate register_handler" in messages
        assert "first at tools/mvlint/fixtures/bad_msg_flow.py:24" \
            in messages
        # Reply handler that never counts the waiter down.
        assert "never reaches Waiter.notify/release" in messages
        # Reply handler that ignores the error path.
        assert "never inspects take_error()" in messages
        # Request nobody answers.
        assert "none reaches create_reply_message()" in messages
        assert result.per_pass_suppressed["msg-flow"] == 1

    def test_wake_protocol_seeded(self):
        result = _fixture_result("bad_wake_protocol.py")
        found = [v for v in result.violations
                 if v.pass_name == "wake-protocol"]
        assert len(found) == 3, [v.render() for v in found]
        lines = sorted(v.line for v in found)
        assert lines == [39, 58, 74], [v.render() for v in found]
        messages = "\n".join(v.message for v in found)
        assert "re-armed AFTER a state check" in messages
        assert "re-armed AFTER the park" in messages
        assert "never re-arms wake latch" in messages
        # Every diagnostic teaches the fix, not just the fault.
        assert "re-arm first, then check state, then park" in messages
        assert result.per_pass_suppressed["wake-protocol"] == 1

    def test_fixture_dir_fails_as_a_whole(self):
        result = run_passes(build_passes(REPO_ROOT), [str(FIXTURES)],
                            REPO_ROOT)
        assert result.failed
        assert len(result.violations) == 44
        assert len(result.suppressed) == 13


class TestCleanTree:
    def test_final_tree_is_clean(self):
        # The acceptance gate: the shipped tree has zero non-pragma'd
        # violations across all ten passes.
        result = run(("multiverso_tpu", "tests"), REPO_ROOT)
        assert not result.failed, \
            "\n".join(v.render() for v in result.violations)

    def test_doc_slot_table_matches_registry(self):
        doc = parse_doc_slots(REPO_ROOT / "docs" / "WIRE_FORMAT.md")
        from multiverso_tpu.core.message import WIRE_SLOTS
        assert doc == WIRE_SLOTS

    def test_doc_msg_type_table_matches_registry(self):
        from multiverso_tpu.core.message import MsgType
        from tools.mvlint.wire_slot_lint import parse_doc_msg_types
        doc = parse_doc_msg_types(REPO_ROOT / "docs" / "WIRE_FORMAT.md")
        enum = {t.name: int(t) for t in MsgType if t.name != "Default"}
        assert doc == enum

    def test_msg_type_doc_drift_is_a_violation(self, tmp_path):
        drifted = tmp_path / "WIRE_FORMAT.md"
        drifted.write_text("| 5 | `ERROR_SLOT` |\n"
                           "| `Request_Get` | 1 |\n"
                           "| `Ghost_Type` | 99 |\n")
        lint = WireSlotLint({"ERROR_SLOT": 5}, drifted,
                            msg_types={"Request_Get": 1,
                                       "Request_Add": 2})
        module = ModuleInfo(FIXTURES / "bad_flags.py", REPO_ROOT)
        messages = [v.message for v in lint.check(module)]
        assert any("Request_Add=2 missing" in m for m in messages)
        assert any("Ghost_Type" in m for m in messages)

    def test_doc_flow_table_covers_every_msg_type(self):
        from multiverso_tpu.core.message import MsgType
        from tools.mvlint.msg_flow_lint import load_flow_table
        flow = load_flow_table(REPO_ROOT / "docs" / "WIRE_FORMAT.md")
        assert set(flow) == {t.name for t in MsgType}
        for name, (kind, paired, _handlers, _line) in flow.items():
            assert kind in {"request", "reply", "fire-and-forget"}, name
            if kind == "request":
                # Every request names its reply, and the reply row
                # agrees — pairing is by table, not value arithmetic
                # (Request_FwdGet=9 pairs Reply_Get=-1).
                assert paired in flow, name
                assert flow[paired][0] == "reply", name

    def test_flow_table_doc_drift_is_a_violation(self):
        # Both directions fire: a MsgType with no flow row, and a
        # stale flow row naming no MsgType member.
        lint = next(p for p in build_passes(REPO_ROOT)
                    if p.name == "msg-flow")
        lint.flow = dict(lint.flow)
        del lint.flow["Request_Get"]
        lint.flow["Ghost_Message"] = ("fire-and-forget", None, (), 1)
        messages = [v.message for v in lint._doc_checks()]
        assert any("MsgType.Request_Get" in m and "no row" in m
                   for m in messages)
        assert any("Ghost_Message" in m and "no MsgType member" in m
                   for m in messages)

    def test_flow_table_handler_drift_is_a_violation(self):
        # The table's handler column is checked against the COMPUTED
        # register_handler/intercept sites, both directions.
        lint = next(p for p in build_passes(REPO_ROOT)
                    if p.name == "msg-flow")
        lint.flow = dict(lint.flow)
        kind, paired, _handlers, line = lint.flow["Control_Heartbeat"]
        lint.flow["Control_Heartbeat"] = (kind, paired, ("shm",), line)
        messages = [v.message for v in lint._doc_checks()]
        assert any("Control_Heartbeat" in m
                   and "declares handlers [shm]" in m
                   and "computes [controller]" in m
                   for m in messages)

    def test_doc_metric_table_matches_registry(self):
        from tools.mvlint.metric_lint import (load_metric_names,
                                             parse_doc_metrics)
        doc = parse_doc_metrics(REPO_ROOT / "docs" / "OBSERVABILITY.md")
        registry = load_metric_names(
            REPO_ROOT / "multiverso_tpu" / "util" / "dashboard.py")
        assert set(doc) == set(registry)

    def test_metric_doc_drift_is_a_violation(self, tmp_path):
        from tools.mvlint.metric_lint import MetricNameLint
        drifted = tmp_path / "OBSERVABILITY.md"
        drifted.write_text(
            "| `SERVER_PROCESS_GET` | monitor | fine |\n"
            "| `GHOST_METRIC` | counter | stale doc row |\n")
        lint = MetricNameLint({"SERVER_PROCESS_GET": "x",
                               "NEVER_DOCUMENTED": "y"}, drifted)
        module = ModuleInfo(FIXTURES / "bad_flags.py", REPO_ROOT)
        found = list(lint.check(module))
        messages = "\n".join(v.message for v in found)
        assert "GHOST_METRIC" in messages          # doc-only row
        assert "NEVER_DOCUMENTED" in messages      # registry-only name
        assert len(found) == 2

    def test_doc_thread_table_matches_registry(self):
        from tools.mvlint.role_lint import (load_doc_roles,
                                            load_thread_roles)
        doc = load_doc_roles(REPO_ROOT)
        registry, _ = load_thread_roles(REPO_ROOT)
        assert {e: r for e, (r, _) in doc.items()} == registry

    def test_thread_doc_drift_is_a_violation(self):
        # _doc_direction fires both ways: a registry entry with no
        # docs/THREADS.md row, and a stale doc row with no entry.
        lint = next(p for p in build_passes(REPO_ROOT)
                    if p.name == "thread-role")
        lint.doc_roles = dict(lint.doc_roles)
        entry = sorted(lint.doc_roles)[0]
        del lint.doc_roles[entry]
        lint.doc_roles["runtime/ghost.py::Ghost._main"] = ("ACTOR", 999)
        messages = [v.message for v in lint._doc_direction()]
        assert any(entry in m and "no row" in m for m in messages)
        assert any("Ghost._main" in m and "stale" in m
                   for m in messages)

    def test_doc_wire_path_table_matches_lint(self):
        from tools.mvlint.copy_lint import (WIRE_PATH_MODULES,
                                            parse_doc_modules)
        doc = parse_doc_modules(REPO_ROOT / "docs" / "MEMORY.md")
        assert set(doc) == set(WIRE_PATH_MODULES)

    def test_copy_lint_doc_drift_is_a_violation(self, tmp_path):
        from tools.mvlint.copy_lint import CopyLint
        drifted = tmp_path / "MEMORY.md"
        drifted.write_text(
            "| `multiverso_tpu/runtime/tcp.py` | wire-path | fine |\n"
            "| `multiverso_tpu/ghost.py` | wire-path | stale row |\n")
        lint = CopyLint(drifted)
        module = ModuleInfo(FIXTURES / "bad_flags.py", REPO_ROOT)
        found = list(lint.check(module))
        messages = "\n".join(v.message for v in found)
        assert "ghost.py" in messages                 # doc-only row
        assert "core/blob.py" in messages             # missing row
        # both directions fire: 1 stale + 7 missing modules
        assert len(found) == 8

    def test_doc_drift_is_a_violation(self, tmp_path):
        drifted = tmp_path / "WIRE_FORMAT.md"
        drifted.write_text("| 5 | `ERROR_SLOT` |\n"
                           "| 9 | `CODEC_SLOT` |\n"
                           "| 7 | `STALE_SLOT` |\n")
        lint = WireSlotLint({"ERROR_SLOT": 5, "CODEC_SLOT": 6,
                             "VERSION_SLOT": 7}, drifted)
        module = ModuleInfo(FIXTURES / "bad_flags.py", REPO_ROOT)
        findings = [v.message for v in lint.check(module)]
        assert any("drifted from the wire" in m for m in findings)
        assert any("VERSION_SLOT=7 missing" in m for m in findings)
        assert any("stale doc entry" in m for m in findings)


class TestFramework:
    def test_pragma_inside_string_is_inert(self, tmp_path):
        path = tmp_path / "mod.py"
        path.write_text(
            'X = "# mvlint: ignore[flag-lint]"\n'
            'from multiverso_tpu.util.configure import get_flag\n'
            'Y = get_flag("not_a_flag_at_all")\n')
        result = run_passes(build_passes(REPO_ROOT), [str(path)],
                            tmp_path)
        assert any(v.pass_name == "flag-lint"
                   for v in result.violations)

    def test_aliased_lock_is_registered(self, tmp_path):
        # Server._table_lock = device_lock.TABLE_LOCK carries no
        # factory call; the alias must still register or server.py's
        # critical sections go unchecked.
        path = tmp_path / "mod.py"
        path.write_text(
            "from x import device_lock\n"
            "class S:\n"
            "    _table_lock = device_lock.TABLE_LOCK\n"
            "    def bad(self, q):\n"
            "        with self._table_lock:\n"
            "            return q.pop()\n")
        result = run_passes(build_passes(REPO_ROOT), [str(path)],
                            tmp_path)
        assert any(v.pass_name == "lock-discipline"
                   and ".pop" in v.message
                   for v in result.violations), \
            [v.render() for v in result.violations]

    def test_doc_drift_not_suppressible_by_module_pragma(self, tmp_path):
        # Doc findings carry the doc's path; a pragma in whatever file
        # happens to be scanned first must not swallow them.
        drifted = tmp_path / "WIRE_FORMAT.md"
        drifted.write_text("| 9 | `ERROR_SLOT` |\n")
        mod = tmp_path / "first.py"
        mod.write_text("X = 1  # mvlint: ignore[wire-slot]\n")
        lint = WireSlotLint({"ERROR_SLOT": 5}, drifted)
        result = run_passes([lint], [str(mod)], tmp_path)
        assert any("drifted from the wire" in v.message
                   for v in result.violations)
        assert not result.suppressed

    def test_syntax_error_is_reported_not_crashed(self, tmp_path):
        path = tmp_path / "broken.py"
        path.write_text("def oops(:\n")
        result = run_passes(build_passes(REPO_ROOT), [str(path)],
                            tmp_path)
        assert result.failed
        assert result.violations[0].pass_name == "parse"


class TestCli:
    """The acceptance-criterion entry point, end to end."""

    def test_clean_tree_exits_zero(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.mvlint",
             "multiverso_tpu", "tests"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        assert "mvlint: OK" in proc.stdout

    def test_fixtures_exit_nonzero_with_file_line(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.mvlint",
             "tools/mvlint/fixtures"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 1
        # file:line:col diagnostics
        assert "tools/mvlint/fixtures/bad_flags.py:18:" in proc.stdout
        assert "FAILED" in proc.stderr

    def test_nonexistent_path_is_a_hard_error(self):
        # A drifted path in ci.sh must not let the gate pass vacuously.
        proc = subprocess.run(
            [sys.executable, "-m", "tools.mvlint", "no_such_dir_xyz"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 2
        assert "no_such_dir_xyz" in proc.stderr

    def test_baseline_mode_never_fails(self):
        proc = subprocess.run(
            [sys.executable, "-m", "tools.mvlint", "--baseline",
             "tools/mvlint/fixtures"],
            cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0
        assert "violations" in proc.stdout

"""The heartbeat (runtime/thread_roles.py, docs/OBSERVABILITY.md
"Stalls"): the process's one always-on sampler and the stall records it
makes. The opening rules and the classes are held to WRITTEN timelines
(``Machine``: the clocks a ``Heartbeat`` reads, advanced by its own
sleeps), so that nothing here depends on how loaded the machine is; two
tests run in real time, with wide limits: a child stopped by SIGSTOP, and
the count of sampler threads with and without ``-debug_locks``."""

import glob
import json
import os
import signal
import subprocess
import sys
import threading
import time

import pytest

from multiverso_tpu.runtime import thread_roles
from multiverso_tpu.runtime.thread_roles import BEAT_S, Heartbeat
from multiverso_tpu.util import dashboard
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.dashboard import Dashboard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAIN, ACTOR, CALLER = 1, 2, 3        # thread idents; clock ids 11, 12, 13
THREADS = [(MAIN, 11, "MainThread", "MAIN"),
           (ACTOR, 12, "mv-server", "ACTOR"),
           (CALLER, 13, "mv-caller", "BACKGROUND")]
WALL0_NS = 1_790_000_000_000_000_000


class Machine:
    """A written timeline. ``late[n]`` seconds is how late beat ``n``
    wakes; over that stretch every clock in ``burn[n]`` runs (a share of
    the stretch), and over every ordinary period each thread runs
    ``usual`` of a core. ``readings`` is what the once-a-second files
    say, changed by the test as its story goes."""

    def __init__(self, late=None, burn=None, usual=0.05, process_extra=None,
                 publish=None):
        self.now, self.beats = 1000.0, 0
        self.late, self.burn = late or {}, burn or {}
        self.process_extra = process_extra or {}
        self.usual = usual
        self.cpu = {11: 0, 12: 0, 13: 0}
        self.process = 0
        self.reads, self.thread_reads = [], 0
        self.values = {"vol_switches": 0, "throttled_periods": 0,
                       "throttled_usec": 0}
        self.published = []
        self.heart = Heartbeat(
            monotonic=lambda: self.now, sleep=self.sleep,
            process_ns=lambda: self.process,
            thread_ns=self.thread_ns,
            wall_ns=lambda: WALL0_NS + int((self.now - 1000.0) * 1e9),
            threads=lambda seen: None if seen == 7 else (7, THREADS),
            read=self.read, publish=publish or self.published.append)

    def sleep(self, seconds):
        late = self.late.get(self.beats, 0.0)
        for clock in self.cpu:
            ran = int(self.usual * seconds * 1e9) + int(
                self.burn.get(self.beats, {}).get(clock, 0.0) * late * 1e9)
            self.cpu[clock] += ran
            self.process += ran
        self.process += int(self.process_extra.get(self.beats, 0.0) * 1e9)
        self.now += seconds + late
        self.beats += 1

    def thread_ns(self, clock):
        self.thread_reads += 1
        return self.cpu[clock]

    def read(self):
        self.reads.append(self.now)
        return dict(self.values)

    def entry(self, name, ident, ms, count, total_ms):
        """A monitor's entry of ``ms`` that ends now, as ``Monitor.add``
        hands a long one over."""
        dashboard.long_entries.append(
            (name, ident, self.now, ms, count, total_ms))

    def run(self, beats):
        for _ in range(beats):
            self.heart.once()
        return self.published


@pytest.fixture(autouse=True)
def _clean():
    dashboard.reset_stalls()
    yield
    dashboard.reset_stalls()


# -- the written timelines: one case a function -------------------------

def frozen():
    """A beat 112 ms late while no clock of the process ran."""
    (record,) = Machine(late={50: 0.112}).run(120)
    assert record["class"] == "frozen" and record["by"] is None
    assert 110 <= record["ms"] <= 125 and record["cpu_ms"] < 10
    assert record["late_beats"] == [[pytest.approx(112, abs=0.01),
                                     pytest.approx(112, abs=0.01)]]
    assert {t["name"] for t in record["threads"]} \
        == {"MainThread", "mv-server", "mv-caller"}
    assert all(t["cpu_ms"] < 2 for t in record["threads"])


def held_names_the_thread():
    """The same lateness while the server actor's clock ran the whole
    stretch: that thread held the interpreter."""
    (record,) = Machine(late={50: 0.112}, burn={50: {12: 1.0}}).run(120)
    assert record["class"] == "held" and record["by"] == "mv-server"
    top = record["threads"][0]
    assert (top["name"], top["role"]) == ("mv-server", "ACTOR")
    assert 105 <= top["cpu_ms"] <= 120 and 105 <= record["cpu_ms"] <= 125


def held_by_no_registered_thread():
    """The process's clock ran, no registered thread's did (the
    runtime's own threads): held, and nobody named."""
    (record,) = Machine(late={50: 0.112},
                        process_extra={50: 0.110}).run(120)
    assert record["class"] == "held" and record["by"] is None
    assert record["other_cpu_ms"] == pytest.approx(110.0, abs=2.0)
    assert all(t["usual_cpu_share"] == pytest.approx(0.05, abs=0.01)
               for t in record["threads"])


def blocked_names_the_monitor():
    """Every beat on time while a working monitor's entry ran 150 times
    its mean."""
    machine = Machine()
    machine.run(60)
    machine.entry("SERVER_PROCESS_ADD", ACTOR, 300.0, 101, 500.0)
    (record,) = machine.run(60)
    assert record["class"] == "blocked" and record["late_beats"] == []
    assert (record["by"], record["thread"]) \
        == ("SERVER_PROCESS_ADD", "mv-server")
    assert record["ms"] == pytest.approx(300.0, abs=0.01)
    (entry,) = record["entries"]
    assert entry["name"] == "SERVER_PROCESS_ADD" and not entry["waits"]


def a_bimodal_wait_opens_nothing():
    """TABLE_WAIT of 1 ms and of 100 ms by turns (a backward program
    behind every other one), and a mailbox's: monitors that only wait."""
    machine = Machine()
    total = 0.0
    for n in range(1, 81):
        machine.run(3)
        ms = 100.0 if n % 2 else 1.0
        total += ms
        if ms > dashboard.LONG_ENTRY_MS:
            machine.entry("TABLE_WAIT", CALLER, ms, n, total)
            machine.entry("MAILBOX_WAIT[server]", ACTOR, 5 * ms, n, total)
    assert machine.run(60) == []


def a_long_entry_of_a_slow_monitor_opens_nothing():
    """150 ms where the monitor's mean is 100: long, and ordinary."""
    machine = Machine()
    machine.run(20)
    machine.entry("TRAINER_EPOCH_PREP", CALLER, 150.0, 50, 5050.0)
    assert machine.run(60) == []


def a_monitor_of_two_kinds_of_entry_opens_once():
    """A server's dispatch: under a millisecond for most tables, 40.5 ms
    for the widest, every step. The first such entry is a record; the
    later ones are that monitor's ordinary long entries, and one of 300
    ms is a record again."""
    machine = Machine()
    count, total = 100, 30.0
    for step in range(12):
        machine.run(36)
        count, total = count + 100, total + 30.0 + 40.5
        machine.entry("UPDATE_DISPATCH", ACTOR, 40.5, count, total)
    assert [(r["class"], r["by"], r["ms"]) for r in machine.run(60)] \
        == [("blocked", "UPDATE_DISPATCH", pytest.approx(40.5, abs=0.01))]
    machine.entry("UPDATE_DISPATCH", ACTOR, 300.0, count + 1, total + 300.0)
    assert [r["ms"] for r in machine.run(60)] \
        == [pytest.approx(40.5, abs=0.01), pytest.approx(300.0, abs=0.01)]


def no_mean_yet_opens_nothing():
    """A working monitor's 32nd entry: a first compile, a first epoch."""
    machine = Machine()
    machine.run(20)
    machine.entry("SERVER_PROCESS_ADD", ACTOR, 900.0, 32, 931.0)
    assert machine.run(60) == []


def overlapping_openings_are_one_record():
    """Two beats late in a row and a working entry that spans both: one
    record, HOST_STALL counted once."""
    Dashboard.reset()
    machine = Machine(late={50: 0.070, 51: 0.060},
                      publish=thread_roles._publish)
    machine.run(52)
    machine.entry("SERVER_PROCESS_ADD", ACTOR, 400.0, 101, 600.0)
    machine.run(80)
    (record,) = dashboard.stalls()
    assert len(record["late_beats"]) == 2
    assert record["ms"] == pytest.approx(400.0, abs=0.5)
    # most of it no beat was late for: the entry's own length decides
    assert record["class"] == "blocked"
    # counted as each opening was seen: two late beats, then what they
    # do not cover of the entry; one entry of HOST_STALL, and one of
    # HOST_STALL_FROZEN for the beats the process spent off the CPU
    assert Dashboard.get("HOST_STALL").count == 1
    assert Dashboard.get("HOST_STALL").elapse \
        == pytest.approx(130.0 + 270.0, abs=0.5)
    assert Dashboard.get("HOST_STALL_FROZEN").count == 1
    assert Dashboard.get("HOST_STALL_FROZEN").elapse \
        == pytest.approx(130.0, abs=0.01)


def an_entry_a_stall_lengthened_is_context():
    """The Add in flight when the process froze reads 118 ms: it opens
    no record of its own, the frozen one lists it, and the wait beside
    it is marked as one."""
    machine = Machine(late={50: 0.112})
    machine.run(51)
    machine.entry("SERVER_PROCESS_ADD", ACTOR, 118.0, 101, 318.0)
    machine.entry("TABLE_WAIT", CALLER, 119.0, 101, 300.0)
    (record,) = machine.run(80)
    assert record["class"] == "frozen" and 110 <= record["ms"] <= 125
    assert [(e["name"], e["thread"], e["waits"]) for e in record["entries"]] \
        == [("SERVER_PROCESS_ADD", "mv-server", False),
            ("TABLE_WAIT", "mv-caller", True)]


def a_long_build_opens_nothing():
    """A program whose lowering takes 900 ms where the mean is 5: the
    build monitors' entries are 1 ms or seconds by the program they make,
    and none opens a record."""
    machine = Machine()
    machine.run(60)
    for name in dashboard.BUILDS:
        machine.entry(name, MAIN, 900.0, 201, 1900.0)
    assert machine.run(60) == []


def a_build_inside_a_stall_is_context_by_name():
    """A program built in a measured window is on the record of the
    stall it lies under, by its monitor's name, as working time."""
    machine = Machine(late={50: 0.112})
    machine.run(51)
    machine.entry("PROGRAM_LOWER", MAIN, 118.0, 201, 1118.0)
    (record,) = machine.run(80)
    assert record["class"] == "frozen"
    assert [(e["name"], e["thread"], e["waits"]) for e in record["entries"]] \
        == [("PROGRAM_LOWER", "MainThread", False)]


def two_stalls_apart_are_two_records():
    first, second = Machine(late={50: 0.112, 150: 0.2}).run(260)
    assert (first["class"], second["class"]) == ("frozen", "frozen")
    assert second["began_wall_ns"] - first["began_wall_ns"] \
        == pytest.approx((100 * BEAT_S + 0.112) * 1e9, rel=1e-3)


def the_ring_keeps_the_last_64():
    machine = Machine(late={50 + 40 * k: 0.050 for k in range(70)},
                      publish=thread_roles._publish)
    machine.run(50 + 40 * 70 + 40)
    kept = dashboard.stalls()
    assert len(kept) == 64 and Dashboard.get("HOST_STALL").count >= 70
    began = [r["began_wall_ns"] for r in kept]
    assert began == sorted(began) and began[0] > WALL0_NS + 6 * 40 * 1e7
    # the snapshot that goes to the controller carries no record
    assert dashboard.metrics_snapshot()["stalls"] == {
        "count": 70, "last_began_wall_ns": began[-1]}
    assert Dashboard.display().count("[stall] frozen") == 64


def the_slow_readings_bracket_a_late_beat():
    """The process-wide files are read once a second, and once more at
    the first beat after a stall: the record's deltas are what the two
    readings around it counted, at most a second apart."""
    machine = Machine(late={250: 0.112})
    machine.run(250)
    assert len(machine.reads) == 3      # 2.5 s: at 0, 1 and 2 s
    machine.values.update(throttled_periods=1, throttled_usec=104_000,
                          vol_switches=9)
    (record,) = machine.run(60)
    assert len(machine.reads) == 4
    assert machine.reads[3] - machine.reads[2] <= 1.0 + 0.112
    assert record["deltas"] == {
        "throttled_periods": 1, "throttled_usec": 104_000,
        "vol_switches": 9, "bracket_s": pytest.approx(0.6, abs=0.05)}
    machine.run(100)
    assert len(machine.reads) == 5      # and on at a second's pace


def absent_cgroup_files_leave_their_keys_out(tmp_path):
    missing = str(tmp_path / "nothing")
    assert thread_roles.cgroup_cpu_stat(missing, missing) is None
    values = thread_roles.read_process(None, proc_stat=missing)
    assert set(values) == {"vol_switches", "invol_switches",
                           "major_faults", "minor_faults"}
    machine = Machine(late={50: 0.112})
    machine.values = values
    (record,) = machine.run(120)
    assert "throttled_periods" not in record["deltas"]
    assert record["deltas"]["major_faults"] == 0


def cgroup_files_of_both_versions_are_found(tmp_path):
    root = tmp_path / "cgroup"
    (root / "cpu,cpuacct" / "pod" / "c1").mkdir(parents=True)
    (root / "cpu,cpuacct" / "pod" / "c1" / "cpu.stat").write_text(
        "nr_periods 40\nnr_throttled 3\nthrottled_time 250000000\n")
    (root / "unified").mkdir()
    (root / "unified" / "cpu.stat").write_text(
        "usage_usec 5\nnr_periods 9\nnr_throttled 2\nthrottled_usec 777\n")
    proc = tmp_path / "cgroup.v1"
    proc.write_text("11:memory:/pod/c1\n4:cpu,cpuacct:/pod/c1\n")
    found = thread_roles.cgroup_cpu_stat(str(proc), str(root))
    assert found == str(root / "cpu,cpuacct" / "pod" / "c1" / "cpu.stat")
    stat = tmp_path / "stat"
    stat.write_text("cpu  1 2 3 4 5 6 7 8 9 10\ncpu0 1 2 3 4 5 6 7 8 9\n")
    values = thread_roles.read_process(found, proc_stat=str(stat))
    assert (values["throttled_periods"], values["throttled_usec"],
            values["steal_ticks"]) == (3, 250_000, 8)
    proc.write_text("0::/unified\n")
    found = thread_roles.cgroup_cpu_stat(str(proc), str(root))
    values = thread_roles.read_process(found, proc_stat=str(stat))
    assert (values["throttled_periods"], values["throttled_usec"]) == (2, 777)


def monitor_add_appends_nothing_under_the_floor():
    Dashboard.reset()
    mon = Dashboard.get("SERVER_PROCESS_GET")
    for _ in range(100):
        mon.add(dashboard.LONG_ENTRY_MS)
    with dashboard.monitor("SERVER_PROCESS_GET"):
        pass
    assert not dashboard.long_entries
    before = time.monotonic()
    mon.add(dashboard.LONG_ENTRY_MS + 0.001)
    (entry,) = dashboard.long_entries
    name, ident, end, ms, count, total = entry
    assert (name, ident, count) \
        == ("SERVER_PROCESS_GET", threading.get_ident(), 102)
    assert before <= end <= time.monotonic()
    assert ms == dashboard.LONG_ENTRY_MS + 0.001
    assert total == pytest.approx(mon.elapse)
    lap = dashboard.laps("BLOB_D2H")
    lap._ms = 55.0
    lap.close()
    assert [e[0] for e in dashboard.long_entries] \
        == ["SERVER_PROCESS_GET", "BLOB_D2H"]


def a_collection_that_overlapped_is_on_the_record():
    machine = Machine(late={50: 0.112}, burn={50: {11: 1.0}})
    machine.run(51)
    machine.heart.stalls.collected(machine.now - 0.100, machine.now - 0.010)
    (record,) = machine.run(80)
    assert record["class"] == "held" and record["by"] == "MainThread"
    assert record["gc_ms"] == pytest.approx(90.0, abs=0.01)


def a_beat_late_under_the_floor_is_counted_and_opens_nothing():
    Dashboard.reset()
    machine = Machine(late={10: 0.030, 20: 0.039})
    assert machine.run(100) == []
    beats = Dashboard.get("HOST_BEAT_LATE")
    assert beats.count == 100
    assert beats.elapse == pytest.approx(69.0, abs=0.01)
    assert thread_roles.LATE_FLOOR_MS > 2 * BEAT_S * 1e3


def the_threads_clocks_are_read_every_fourth_beat_and_at_a_late_one():
    """A clock read of another thread is a system call (6 us each on the
    chip machine's kernel): the process's clock every beat, the threads'
    every fourth, and at the beat a stall ends."""
    machine = Machine()
    machine.run(100)
    assert machine.thread_reads == 25 * len(THREADS)
    machine = Machine(late={49: 0.112}, burn={49: {13: 1.0}})
    (record,) = machine.run(100)
    assert machine.thread_reads == 26 * len(THREADS)
    assert (record["class"], record["by"]) == ("held", "mv-caller")
    assert 100 <= record["threads"][0]["cpu_ms"] <= 120


def a_stall_is_counted_at_the_beat_that_saw_it():
    """The monitors move at the late beat's own wake, a quarter of a
    second before the record closes: a window that opens 2 ms after a
    stall's end (the benchmark's, after its full collection) reads 0."""
    Dashboard.reset()
    machine = Machine(late={50: 0.112}, burn={50: {11: 1.0}})
    machine.run(50)
    assert Dashboard.get("HOST_STALL").count == 0
    machine.run(1)
    assert machine.published == []
    assert (Dashboard.get("HOST_STALL").count,
            Dashboard.get("HOST_STALL_FROZEN").count) == (1, 0)
    assert Dashboard.get("HOST_STALL").elapse == pytest.approx(112, abs=0.01)
    (record,) = machine.run(60)
    assert record["class"] == "held"
    assert Dashboard.get("HOST_STALL").count == 1


def frames_are_read_under_debug_locks_alone(monkeypatch=None):
    """``sys._current_frames()`` makes frame objects, which keep their
    frames' locals alive past the function's return: never on the
    always-on path (it cost a model cell 0.9 GB of device memory)."""
    calls = []
    real = thread_roles._package_frames
    thread_roles._package_frames = lambda who: calls.append(1) or {}
    try:
        (record,) = Machine(late={50: 0.112}).run(120)
        assert calls == [] and all(t["at"] is None
                                   for t in record["threads"])
        set_flag("debug_locks", True)
        (record,) = Machine(late={50: 0.112}).run(120)
        assert calls == [1]
    finally:
        thread_roles._package_frames = real


def a_record_lies_on_the_wall_clock():
    """``began_wall_ns`` is ``time.time_ns()`` at the stretch's start,
    whenever the record is closed."""
    (record,) = Machine(late={50: 0.112}).run(300)
    assert record["began_wall_ns"] == pytest.approx(
        WALL0_NS + 51 * BEAT_S * 1e9, abs=2e3)
    assert record["usual_late_ms"] == 0.0
    assert record["usual_cpu_share"] == pytest.approx(0.15, abs=0.01)


CASES = [frozen, held_names_the_thread, held_by_no_registered_thread,
         blocked_names_the_monitor, a_bimodal_wait_opens_nothing,
         a_long_entry_of_a_slow_monitor_opens_nothing,
         a_monitor_of_two_kinds_of_entry_opens_once,
         no_mean_yet_opens_nothing, overlapping_openings_are_one_record,
         an_entry_a_stall_lengthened_is_context,
         a_long_build_opens_nothing, a_build_inside_a_stall_is_context_by_name,
         two_stalls_apart_are_two_records, the_ring_keeps_the_last_64,
         the_slow_readings_bracket_a_late_beat,
         monitor_add_appends_nothing_under_the_floor,
         a_collection_that_overlapped_is_on_the_record,
         a_beat_late_under_the_floor_is_counted_and_opens_nothing,
         the_threads_clocks_are_read_every_fourth_beat_and_at_a_late_one,
         a_stall_is_counted_at_the_beat_that_saw_it,
         frames_are_read_under_debug_locks_alone,
         a_record_lies_on_the_wall_clock]


@pytest.mark.parametrize("case", CASES, ids=lambda case: case.__name__)
def test_written_timeline(case):
    case()


@pytest.mark.parametrize("case", [absent_cgroup_files_leave_their_keys_out,
                                  cgroup_files_of_both_versions_are_found],
                         ids=lambda case: case.__name__)
def test_process_wide_readings(case, tmp_path):
    case(tmp_path)


# -- a closed record's way out --------------------------------------------

RECORD = {"class": "frozen", "ms": 112.5, "began_wall_ns": WALL0_NS,
          "by": None, "late_beats": [[112.5, 112.5]], "cpu_ms": 0.4,
          "usual_cpu_share": 1.1, "other_cpu_ms": 0.4,
          "usual_late_ms": 0.1, "threads": [],
          "entries": [], "deltas": {}, "gc_ms": 0.0}


def test_a_closed_record_is_one_json_line_on_standard_error(capsys):
    Dashboard.reset()
    thread_roles._publish(dict(RECORD))
    err = capsys.readouterr().err
    (line,) = [ln for ln in err.splitlines() if "host stall: " in ln]
    assert line.startswith("[WARNING]")
    assert json.loads(line.split("host stall: ", 1)[1]) == RECORD
    assert dashboard.stalls() == [RECORD]


@pytest.mark.parametrize("kind, by, level", [
    ("frozen", None, "WARNING"), ("held", None, "WARNING"),
    ("blocked", "SERVER_PROCESS_ADD", "WARNING"),
    ("held", "MainThread", None), ("held", "mv-server", None)])
def test_a_warning_is_what_no_named_thread_accounts_for(
        capsys, kind, by, level):
    """Held by a thread that is named is that thread's own work (an
    import, a compile, a full collection: a dozen a process at set-up):
    kept, and logged at debug level alone."""
    record = dict(RECORD, **{"class": kind, "by": by})
    thread_roles._publish(record)
    out, err = capsys.readouterr()
    assert "host stall" not in out
    assert [ln.split("]")[0][1:] for ln in err.splitlines()
            if "host stall: " in ln] == ([level] if level else [])
    assert dashboard.stalls() == [record]


@pytest.mark.parametrize("raw, level", [
    ("0", "Debug"), ("1", "Info"), ("2", "Error"), ("3", "Fatal"),
    ("warning", "Warning"), ("Error", "Error"), ("", "Info"),
    ("4", "Info")])
def test_a_log_level_by_number_means_what_it_did(monkeypatch, raw, level):
    """Warning came between Info and Error: ``MV_LOG_LEVEL=2`` is still
    Error and 3 Fatal, as before it."""
    from multiverso_tpu.util import log
    monkeypatch.setenv("MV_LOG_LEVEL", raw)
    assert log._env_level() is log.LogLevel[level]
    assert log.LogLevel.Info < log.LogLevel.Warning < log.LogLevel.Error


def test_the_monitors_that_only_wait_are_registered_names():
    """``dashboard.WAITING`` names monitors of ``METRIC_NAMES``, letter
    for letter (a family by its ``[*]``): one renamed there and not here
    would open records as a working monitor."""
    assert set(dashboard.WAITING) <= set(dashboard.METRIC_NAMES)
    assert dashboard.only_waits("MAILBOX_WAIT[server]")
    assert not dashboard.only_waits("SERVER_PROCESS_ADD")


def test_a_record_closed_after_the_main_program_is_kept_not_logged(
        capsys, monkeypatch):
    """A process's last lines on standard error are its main program's
    (the benchmark's are what its check compared)."""
    class Ended:
        @staticmethod
        def is_alive():
            return False
    monkeypatch.setattr(thread_roles.threading, "main_thread", Ended)
    thread_roles._publish(dict(RECORD))
    assert "host stall" not in capsys.readouterr().err
    assert dashboard.stalls() == [RECORD]


def test_under_a_profiler_session_a_record_is_a_span(tmp_path):
    from jax.profiler import ProfileData
    with dashboard.trace_to(str(tmp_path)):
        thread_roles._publish(dict(RECORD))
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    found = [dict(e.stats) for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for e in line.events if e.name == "mv:HOST_STALL"]
    (stats,) = found
    assert stats["class"] == "frozen" and stats["ms"] == 112.5
    assert int(stats["began_wall_ns"]) == WALL0_NS


# -- real time, with wide limits ---------------------------------------------

def _heartbeats():
    return [t for t in threading.enumerate() if t.name == "mv-heartbeat"]


@pytest.mark.parametrize("debug_locks", [False, True])
def test_the_sampler_is_one_thread(debug_locks):
    set_flag("debug_locks", debug_locks)
    release = threading.Event()
    threads = [thread_roles.spawn(role, target=release.wait, args=(10,))
               for role in (thread_roles.DISPATCH, thread_roles.BACKGROUND,
                            thread_roles.ACTOR)]
    try:
        thread_roles.ensure_heartbeat()
        (heart,) = _heartbeats()
        assert heart.daemon and heart.is_alive()
        beats = Dashboard.get("HOST_BEAT_LATE").count
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline \
                and Dashboard.get("HOST_BEAT_LATE").count < beats + 3:
            time.sleep(0.02)
        assert Dashboard.get("HOST_BEAT_LATE").count >= beats + 3
    finally:
        release.set()
        for thread in threads:
            thread.join(timeout=10)
    assert _heartbeats() == [heart]


CHILD = r"""
import json, sys, threading, time
import multiverso_tpu
from multiverso_tpu.runtime import thread_roles
from multiverso_tpu.util import dashboard
print(json.dumps(sorted(t.name for t in threading.enumerate())), flush=True)
worker = thread_roles.spawn(thread_roles.BACKGROUND, target=time.sleep,
                            args=(30,), name="mv-test-sleeper")
time.sleep(0.5)
print("READY", flush=True)
deadline = time.monotonic() + 20
while time.monotonic() < deadline:
    if sys.stdin.readline():
        break
time.sleep(2 * thread_roles.GRACE_S + 0.2)
print(json.dumps(dashboard.stalls()), flush=True)
print(json.dumps(sorted(t.name for t in threading.enumerate())), flush=True)
"""


def test_a_stopped_process_reads_one_frozen_record():
    """SIGSTOP for 200 ms: the kernel takes the whole process off the
    CPU, which is what a throttled cgroup looks like from inside."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    child = subprocess.Popen(
        [sys.executable, "-c", CHILD], env=env, stdin=subprocess.PIPE,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True)
    try:
        at_import = json.loads(child.stdout.readline())
        assert child.stdout.readline().strip() == "READY"
        stopped_ns = time.time_ns()
        os.kill(child.pid, signal.SIGSTOP)
        time.sleep(0.2)
        os.kill(child.pid, signal.SIGCONT)
        resumed_ns = time.time_ns()
        out, err = child.communicate("go\n", timeout=60)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait(timeout=10)
    records, threads = [json.loads(line) for line in out.splitlines()]
    # importing the package starts no thread; running one starts ONE more
    assert "mv-heartbeat" not in at_import
    assert threads.count("mv-heartbeat") == 1
    across = [r for r in records
              if r["began_wall_ns"] < resumed_ns
              and r["began_wall_ns"] + r["ms"] * 1e6 > stopped_ns]
    (record,) = across
    assert record["class"] == "frozen", record
    assert 150 <= record["ms"] <= 600 and record["cpu_ms"] < 30, record
    assert {t["name"] for t in record["threads"]} \
        >= {"MainThread", "mv-test-sleeper"}
    assert f'"began_wall_ns": {record["began_wall_ns"]}' in err

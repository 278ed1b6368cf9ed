"""Set-up seen from inside (docs/OBSERVABILITY.md "Program builds"): the
listeners that ``compile_cache.enable()`` registers with
``jax.monitoring`` turn every program's trace, lowering, cache read and
compile into the four exclusive monitors ``PROGRAM_TRACE``,
``PROGRAM_LOWER``, ``PROGRAM_CACHE_READ`` and ``PROGRAM_COMPILE``, into
``mv:PROGRAM_*`` spans under a profiler session, and into one row a
program of ``dashboard.program_builds()``."""

import glob
import os
import threading

import jax
import jax.monitoring
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from multiverso_tpu.models.lm import ps_train
from multiverso_tpu.models.wordembedding import device_train
from multiverso_tpu.util import compile_cache, dashboard
from multiverso_tpu.util.dashboard import Dashboard

STAGES = dashboard.BUILDS
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND = "/jax/core/compile/backend_compile_duration"
HIT = "/jax/compilation_cache/cache_hits"
X = np.ones(4, np.float32)      # numpy: no eager program to place it


class _Counted:
    """What ``benchmark/lib/builds.py`` ``ProgramBuilds`` counts, from
    listeners of its own that are taken off again."""

    def __init__(self):
        self.built = self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._took)
        jax.monitoring.register_event_listener(self._happened)

    def _took(self, event, seconds, **kw):
        self.built += event == BACKEND

    def _happened(self, event, **kw):
        self.hits += event == HIT

    def stop(self):
        jax.monitoring.unregister_event_duration_listener(self._took)
        jax.monitoring.unregister_event_listener(self._happened)


@pytest.fixture
def listening(tmp_path, monkeypatch):
    """The listeners on, over a compile cache of this test's own; both
    as they were afterwards."""
    cache = str(tmp_path / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    kept = {name: getattr(jax.config, name) for name in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    compilation_cache.reset_cache()
    jax.clear_caches()
    Dashboard.reset()
    dashboard.reset_program_builds()
    assert compile_cache.enable() == cache
    yield cache
    dashboard.stop_listening_to_program_builds()
    for name, value in kept.items():
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    Dashboard.reset()
    dashboard.reset_program_builds()


def _counts():
    return {name: Dashboard.get(name).count for name in STAGES}


def _ms():
    return {name: Dashboard.get(name).elapse for name in STAGES}


def _programs():
    """An outer jitted function that calls two jitted functions, each
    of which calls ``jax.numpy``'s own."""
    @jax.jit
    def first(x):
        return jnp.sin(x) * 2

    @jax.jit
    def second(x):
        return jnp.cos(x) + 1

    @jax.jit
    def outer(x):
        return first(x) + second(x)

    return outer


def test_enable_creates_the_four_monitors_at_zero(listening):
    assert _counts() == dict.fromkeys(STAGES, 0)
    assert dashboard.program_builds() == {}
    assert dashboard.metrics_snapshot(0)["program_builds"] == {"programs": 0}


def test_nested_traces_are_one_entry_of_the_outermost_s_length(listening):
    traced = []

    def heard(event, seconds, fun_name="", **kw):
        if event == TRACE:
            traced.append((fun_name, seconds))

    jax.monitoring.register_event_duration_secs_listener(heard)
    try:
        _programs()(X)
    finally:
        jax.monitoring.unregister_event_duration_listener(heard)
    names = [name for name, _ in traced]
    # JAX's events nest: the inner functions and jnp's are all there
    assert {"outer", "first", "second", "sin", "cos"} <= set(names)
    assert _counts()["PROGRAM_TRACE"] == 1
    outer_s, = [s for name, s in traced if name == "outer"]
    assert 0 < _ms()["PROGRAM_TRACE"] <= outer_s * 1e3 + 1e-6
    assert _ms()["PROGRAM_TRACE"] < sum(s for _, s in traced) * 1e3
    assert _counts()["PROGRAM_LOWER"] == 1


@pytest.mark.parametrize("cached", [False, True], ids=["cold", "warm"])
def test_a_backend_stage_goes_to_exactly_one_monitor(listening, cached):
    outer = _programs()
    if cached:      # the cold pass fills the cache; then from the top
        outer(X)
        jax.clear_caches()
    counted = _Counted()
    before, before_ms = _counts(), _ms()
    try:
        outer(X)
    finally:
        counted.stop()
    moved = {name: _counts()[name] - before[name] for name in STAGES}
    took = {name: _ms()[name] - before_ms[name] for name in STAGES}
    here, other = ("PROGRAM_CACHE_READ", "PROGRAM_COMPILE") if cached \
        else ("PROGRAM_COMPILE", "PROGRAM_CACHE_READ")
    assert counted.built == 1 and counted.hits == int(cached)
    assert moved[here] == counted.built and took[here] > 0
    assert moved[other] == 0 and took[other] == 0
    assert moved["PROGRAM_TRACE"] == moved["PROGRAM_LOWER"] == 1
    row = dashboard.program_builds()["jit_outer"]
    assert row["compiles"] == 1 and row["cache_reads"] == int(cached)
    assert row["traces"] == row["lowerings"] == 1 + int(cached)


def test_enable_twice_registers_once(listening):
    assert compile_cache.enable() == listening
    assert dashboard.listen_to_program_builds() is False
    _programs()(X)
    assert _counts() == {"PROGRAM_TRACE": 1, "PROGRAM_LOWER": 1,
                         "PROGRAM_CACHE_READ": 0, "PROGRAM_COMPILE": 1}


def test_two_threads_building_at_once_keep_separate_stacks(listening):
    """Each thread is held INSIDE its outer trace until the other is
    there too: with one stack for the process the second's outermost
    trace would be a nested one and count nothing."""
    both_inside = threading.Barrier(2, timeout=60)
    failed = []

    def build(tag):
        inner = jax.jit(lambda x: jnp.tanh(x) * tag)

        def program(x):
            y = inner(x)
            both_inside.wait()
            return inner(y) + tag

        program.__name__ = f"built_by_thread_{tag}"
        try:
            jax.jit(program)(X)
        except BaseException as e:  # noqa: BLE001 - shown by the assert
            failed.append(e)

    threads = [threading.Thread(target=build, args=(tag,)) for tag in (1, 2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not failed, failed
    assert _counts()["PROGRAM_TRACE"] == 2
    assert _counts()["PROGRAM_LOWER"] == 2
    assert _counts()["PROGRAM_COMPILE"] + _counts()["PROGRAM_CACHE_READ"] == 2
    rows = dashboard.program_builds()
    assert rows["jit_built_by_thread_1"]["traces"] == 1
    assert rows["jit_built_by_thread_2"]["traces"] == 1


@pytest.mark.parametrize("fun_name, key", [
    ("backward", "jit_backward"), ("jit(backward)", "jit_backward"),
    ("_prep", "jit__prep"), ("jit(_prep)", "jit__prep"),
    ("<lambda>", "jit__lambda"), ("jit(<lambda>)", "jit__lambda"),
    ("pmap(step)", "pmap_step")])
def test_a_program_s_key_is_the_device_trace_s_name_for_it(fun_name, key):
    from benchmark.lib import xplane
    assert dashboard.program_key(fun_name) == key
    # what `stem` leaves of the name a trace prints the program under
    assert xplane.stem(f"{key}_(1234567890123)") == key \
        or xplane.stem(f"{key}(1234567890123)") == key


def test_the_table_joins_a_function_s_stages_in_one_row(listening):
    _programs()(X)
    rows = dashboard.program_builds()
    assert set(rows) == {"jit_outer"}
    row = rows["jit_outer"]
    assert (row["traces"], row["lowerings"], row["compiles"],
            row["cache_reads"]) == (1, 1, 1, 0)
    assert row["trace_ms"] == pytest.approx(_ms()["PROGRAM_TRACE"])
    assert row["lower_ms"] == pytest.approx(_ms()["PROGRAM_LOWER"])
    assert row["compile_ms"] == pytest.approx(_ms()["PROGRAM_COMPILE"])
    assert dashboard.program_ms(row) == pytest.approx(sum(_ms().values()))
    # a copy: the caller cannot write the table
    row["traces"] = 99
    assert dashboard.program_builds()["jit_outer"]["traces"] == 1
    assert dashboard.metrics_snapshot(0)["program_builds"] == {"programs": 1}


def _feed(events):
    """Written events, as JAX's contexts would hand them over."""
    for kind, event, value, name in events:
        if kind == "begin":
            dashboard._stage_begins(event, 0.0, fun_name=name)
        elif kind == "end":
            dashboard._stage_ends(event, value, fun_name=name)
        else:
            dashboard._cache_event(event)


def test_the_table_is_bounded(listening, monkeypatch):
    monkeypatch.setattr(dashboard, "MAX_PROGRAMS", 3)
    for i in range(6):
        _feed([("begin", LOWER, 0, f"jit(f{i})"),
               ("end", LOWER, 0.010 * (i + 1), f"jit(f{i})")])
    rows = dashboard.program_builds()
    assert set(rows) == {"jit_f0", "jit_f1", "jit_f2",
                         dashboard.OTHER_PROGRAMS}
    assert rows[dashboard.OTHER_PROGRAMS]["lowerings"] == 3
    assert rows[dashboard.OTHER_PROGRAMS]["lower_ms"] == pytest.approx(150.0)
    # a row that is there keeps counting under its own name
    _feed([("begin", LOWER, 0, "jit(f1)"), ("end", LOWER, 0.5, "jit(f1)")])
    assert dashboard.program_builds()["jit_f1"]["lowerings"] == 2
    assert _counts()["PROGRAM_LOWER"] == 7


def test_display_prints_the_ten_largest_programs_after_the_monitors(
        listening):
    for i in range(12):
        _feed([("begin", TRACE, 0, f"f{i:02d}"),
               ("end", TRACE, 0.001 * (i + 1), f"f{i:02d}")])
    lines = Dashboard.display().splitlines()
    printed = [line for line in lines if line.startswith("[program ")]
    assert len(printed) == dashboard.DISPLAYED_PROGRAMS == 10
    assert printed[0].startswith("[program jit_f11] total = 12.0ms")
    assert "traces = 1 trace_ms = 12.0" in printed[0]
    assert not any("jit_f00]" in line or "jit_f01]" in line
                   for line in printed)
    first = lines.index(printed[0])
    assert all(line.startswith("[PROGRAM_") for line in lines[:first])
    assert len(dashboard.program_lines()) == 12


def test_a_program_made_inside_a_trace_is_an_entry_of_its_own(listening):
    """An eager operation while ``outer`` is traced: its cache read is
    counted and timed as one, and ``outer``'s trace is shorter by it, so
    the four stay exclusive; the trace and the lowering of the eager
    program, entered inside another stage, add nothing of their own."""
    _feed([("begin", TRACE, 0, "outer"),
           ("begin", TRACE, 0, "sin"), ("end", TRACE, 0.05, "sin"),
           ("begin", TRACE, 0, "eager"), ("end", TRACE, 0.01, "eager"),
           ("begin", LOWER, 0, "jit(eager)"),
           ("end", LOWER, 0.02, "jit(eager)"),
           ("begin", BACKEND, 0, "jit(eager)"), ("event", HIT, 0, ""),
           ("end", BACKEND, 0.2, "jit(eager)"),
           ("end", TRACE, 1.0, "outer"),
           ("begin", LOWER, 0, "jit(outer)"),
           ("end", LOWER, 0.3, "jit(outer)"),
           ("begin", BACKEND, 0, "jit(outer)"),
           ("end", BACKEND, 2.0, "jit(outer)")])
    assert _counts() == {"PROGRAM_TRACE": 1, "PROGRAM_LOWER": 1,
                         "PROGRAM_CACHE_READ": 1, "PROGRAM_COMPILE": 1}
    took = _ms()
    assert took["PROGRAM_TRACE"] == pytest.approx(800.0)
    assert took["PROGRAM_CACHE_READ"] == pytest.approx(200.0)
    assert took["PROGRAM_LOWER"] == pytest.approx(300.0)
    assert took["PROGRAM_COMPILE"] == pytest.approx(2000.0)
    assert sum(took.values()) == pytest.approx(1000.0 + 300.0 + 2000.0)
    rows = dashboard.program_builds()
    assert rows["jit_eager"]["cache_reads"] == 1
    assert rows["jit_eager"]["traces"] == rows["jit_eager"]["lowerings"] == 0
    assert rows["jit_outer"]["trace_ms"] == pytest.approx(800.0)


def test_what_is_not_a_stage_s_own_is_left_alone(listening):
    # a stage that began before the listeners were there
    _feed([("end", LOWER, 1.0, "jit(early)")])
    # durations and events of other kinds, inside a backend stage too
    _feed([("begin", BACKEND, 0, "jit(f)"),
           ("end", "/jax/compilation_cache/cache_retrieval_time_sec", 5.0, ""),
           ("event", "/jax/compilation_cache/compile_requests_use_cache",
            0, ""),
           ("begin", "/jax/some/other_scalar", 0, "g"),
           ("end", BACKEND, 0.1, "jit(f)")])
    assert _counts() == {"PROGRAM_TRACE": 0, "PROGRAM_LOWER": 0,
                         "PROGRAM_CACHE_READ": 0, "PROGRAM_COMPILE": 1}
    assert _ms()["PROGRAM_COMPILE"] == pytest.approx(100.0)
    # and the thread's stack is empty again: the next trace is outermost
    _feed([("begin", TRACE, 0, "h"), ("end", TRACE, 0.1, "h")])
    assert _counts()["PROGRAM_TRACE"] == 1


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host, = [plane for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats))
            for line in host.lines for e in line.events
            if e.name.startswith(dashboard.SPAN_PREFIX)]


def test_a_build_under_a_profiler_session_is_three_spans_with_its_name(
        listening, tmp_path):
    outer = _programs()
    with dashboard.trace_to(str(tmp_path / "trace")):
        outer(X)
    spans = [s for s in _host_spans(str(tmp_path / "trace"))
             if s[0].startswith("mv:PROGRAM_")]
    assert sorted(name for name, *_ in spans) == [
        "mv:PROGRAM_BACKEND", "mv:PROGRAM_LOWER", "mv:PROGRAM_TRACE"]
    for name, start, end, stats in spans:
        program = stats["program"]
        if isinstance(program, bytes):
            program = program.decode()
        assert program == "jit_outer" and end > start
    # one after the other on the thread's line, in the order of the stages
    by_name = {name: (start, end) for name, start, end, _ in spans}
    assert by_name["mv:PROGRAM_TRACE"][1] <= by_name["mv:PROGRAM_LOWER"][0]
    assert by_name["mv:PROGRAM_LOWER"][1] <= by_name["mv:PROGRAM_BACKEND"][0]


def test_with_no_session_no_annotation_is_built(listening, monkeypatch):
    class Disabled:
        @staticmethod
        def is_enabled():
            return False

        def __init__(self, *args, **kw):
            raise AssertionError("an annotation was built with tracing off")

    monkeypatch.setattr(dashboard, "_trace_annotation", Disabled)
    _programs()(X)
    assert _counts()["PROGRAM_TRACE"] == 1


def test_stopping_takes_the_listeners_off(listening):
    dashboard.stop_listening_to_program_builds()
    _programs()(X)
    assert _counts() == dict.fromkeys(STAGES, 0)
    assert dashboard.listen_to_program_builds() is True


@pytest.mark.parametrize("trainer", [ps_train.PSLMTrainer,
                                     device_train.DeviceCorpusTrainer,
                                     device_train.PSDeviceCorpusTrainer],
                         ids=lambda cls: cls.__name__)
def test_a_trainer_s_constructor_enters_trainer_build_by_hand(trainer):
    """No decorator stands between the caller and the constructor (its
    frame made the tables' init programs lower slower on the chip's
    host): the body enters the monitor first and leaves it last."""
    import inspect
    assert not hasattr(trainer.__init__, "__wrapped__")
    body = inspect.getsource(trainer.__init__)
    entered = body.index('building = monitor("TRAINER_BUILD")')
    assert body.index("building.__enter__()") > entered
    assert body.rstrip().endswith("building.__exit__(None, None, None)")
    # a constructor that fails is no trainer built: nothing is counted
    Dashboard.reset()
    with pytest.raises(Exception):
        trainer(None, None)
    assert Dashboard.get("TRAINER_BUILD").count == 0
    Dashboard.reset()


def test_a_trainer_built_is_one_trainer_build_entry(tmp_path):
    from multiverso_tpu.models.wordembedding import (
        DeviceCorpusTrainer, Dictionary, TokenizedCorpus, Word2Vec,
        Word2VecConfig)
    Dashboard.reset()
    rng = np.random.default_rng(0)
    dictionary = Dictionary()
    dictionary.counts = np.sort(rng.integers(1, 50, 64))[::-1].astype(
        np.int64)
    dictionary.words = range(64)
    model = Word2Vec(Word2VecConfig(embedding_size=8, window=2, negative=2,
                                    min_count=1, sample=0.0), dictionary)
    assert Dashboard.get("DICT_ALIAS_BUILD").count == 1
    flat = rng.integers(0, 64, 256).astype(np.int32)
    offsets = np.arange(0, 257, 16, dtype=np.int64)
    DeviceCorpusTrainer(model, TokenizedCorpus(flat, offsets),
                        centers_per_step=64, steps_per_dispatch=2)
    built = Dashboard.get("TRAINER_BUILD")
    assert built.count == 1 and built.elapse > 0
    Dashboard.reset()


def test_the_names_are_in_the_registry_and_the_stall_rule():
    for name in STAGES + ("TRAINER_BUILD", "DICT_ALIAS_BUILD"):
        assert name in dashboard.METRIC_NAMES
    assert not any(dashboard.only_waits(name) for name in STAGES)

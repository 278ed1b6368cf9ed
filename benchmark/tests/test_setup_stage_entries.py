"""The four readers of set-up's stages (PR 68), each on a made-up
``Observations``: the monitor's milliseconds as they stood when the
measured window opened come out as seconds, a monitor that is there and
counted nothing reads 0.0 (a warm cache compiles nothing, and the cell
still reports the metric), and a program that does not listen (the parent
commit, which the driver also runs them on) reads nothing and raises
nothing. Found in the list by NAME, on the repo and on the copy a later PR
appended to."""

import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

MONITOR = {"setup.trace_s": "PROGRAM_TRACE",
           "setup.lower_s": "PROGRAM_LOWER",
           "setup.cache_read_s": "PROGRAM_CACHE_READ",
           "setup.compile_s": "PROGRAM_COMPILE"}
ORDER = ["setup.trace_s", "setup.lower_s", "setup.cache_read_s",
         "setup.compile_s"]
AT_OPEN = {"PROGRAM_TRACE": {"count": 209, "elapsed_ms": 7250.0},
           "PROGRAM_LOWER": {"count": 209, "elapsed_ms": 9500.5},
           "PROGRAM_CACHE_READ": {"count": 205, "elapsed_ms": 4125.0},
           "PROGRAM_COMPILE": {"count": 4, "elapsed_ms": 61000.0},
           "TABLE_INIT": {"count": 88, "elapsed_ms": 5100.0}}


class _Window:
    def __init__(self, at_open, counters=None):
        self.at_open, self.counters = at_open, counters or {}


def _read(name, at_open):
    return load_module("metrics", name).read(
        Observations(window=_Window(at_open)))


@pytest.mark.parametrize("name", ORDER)
def test_reader_gives_the_monitor_s_seconds_at_the_window_s_opening(name):
    assert _read(name, AT_OPEN) == pytest.approx(
        AT_OPEN[MONITOR[name]]["elapsed_ms"] / 1e3)
    # what the WINDOW counted is not read: set-up is what stood at its start
    moved = {MONITOR[name]: {"count": 3, "ms": 999.0}}
    obs = Observations(window=_Window(AT_OPEN, moved))
    assert load_module("metrics", name).read(obs) == pytest.approx(
        AT_OPEN[MONITOR[name]]["elapsed_ms"] / 1e3)


@pytest.mark.parametrize("name", ORDER)
def test_a_monitor_that_counted_nothing_reads_zero(name):
    idle = dict(AT_OPEN, **{MONITOR[name]: {"count": 0, "elapsed_ms": 0.0}})
    assert _read(name, idle) == 0.0


@pytest.mark.parametrize("name", ORDER)
def test_a_program_that_does_not_listen_reads_nothing(name):
    assert _read(name, {"TABLE_INIT": AT_OPEN["TABLE_INIT"]}) is None
    assert _read(name, {}) is None
    # one stage's monitor alone is there: the others still read nothing
    for other in ORDER:
        if other != name:
            assert _read(other, {MONITOR[name]: AT_OPEN[MONITOR[name]]}) \
                is None


def test_the_four_are_exclusive_parts_of_build_and_warm():
    """What the acceptance holds a chip run to, on the made-up numbers."""
    phases = {"setup.build_s": 24.0, "setup.warm_s": 60.0}
    assert sum(_read(name, AT_OPEN) for name in ORDER) \
        <= phases["setup.build_s"] + phases["setup.warm_s"]


@pytest.mark.parametrize("name", ORDER)
def test_the_four_are_entries_of_the_benchmark(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    assert metric == {"name": name, "unit": "s", "better": "lower",
                      "source": "program_span",
                      "layer": "entry and set-up", "moves": "setup_s"}
    entries.check_entry(root, bench, "per_layer", metric)
    # every cell reports them, as it reports the harness's own phases
    assert "workloads" not in metric
    assert "workloads" not in entries.named(bench, "per_layer",
                                            "setup.build_s")
    assert "workloads" not in entries.named(bench, "end_to_end", "setup_s")


def test_the_four_stand_in_the_issue_s_order_after_what_was_there(root):
    names = [m["name"] for m in entries.bench_of(root)["per_layer"]]
    places = [names.index(name) for name in ORDER]
    assert places == sorted(places)
    assert places == list(range(places[0], places[0] + 4))
    assert places[0] > names.index("trainer.ssd_scan_kernel_share.lm")

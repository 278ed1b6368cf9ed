"""Every cell rehearsed end to end at a tiny size on the CPU (four
virtual devices for the four-chip cell), on the repo and on the copy a
later PR appended a cell to (`later_pr.py`), which is made only of new
files and new entries. Each run is the real command in a process of its
own, as the driver runs it, plus --rehearse."""

import copy
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib.harness import Observations
from benchmark.tests import entries, later_pr
from benchmark.tests.later_pr import ROOT, ROOTS, bench_at
from benchmark.tests.test_program_span_metrics import WANT as THE_TEN

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _run(cell, trace, tmp_path, root=ROOT, rehearse=True, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               BENCH_RUN="1")
    command = [sys.executable] + entries.bench_of(root)["command"][1:] + [
        "--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1",
        "--trace", str(trace)] + (["--rehearse"] if rehearse else [])
    return subprocess.run(command, cwd=root, env=env, text=True,
                          capture_output=True, timeout=600)


def _last_line(done):
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("which, cell", [
    (which, w["name"]) for which in ROOTS
    for w in bench_at(which)["workloads"]])
def test_cell_rehearsed_end_to_end(which, cell, root_of, tmp_path):
    root = root_of(which)
    bench = entries.bench_of(root)
    chips = entries.named(bench, "workloads", cell)["chips"]
    done = _run(cell, 1, tmp_path, root=root, devices=chips)
    result = _last_line(done)
    assert RESULT_KEYS <= set(result)
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["failed"] == 0 and result["attempted"] > 0
    # each number compared beside its limit: last in the line, and the
    # last lines on standard error
    assert list(result)[-1] == "compared" and result["compared"]
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    said = done.stderr.strip().splitlines()[-len(result["compared"]):]
    assert [line.split()[2] for line in said] == list(result["compared"])
    assert all(line.startswith("[bench] compared ") for line in said)
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == chips
    # the phases of set-up are on an earlier line of every run
    setup = [l for l in done.stdout.splitlines()
             if l.startswith("[bench] setup ")]
    assert len(setup) == 1
    phases = json.loads(setup[0].split("setup ", 1)[1])
    assert {"setup.import_s", "setup.attach_s", "setup.build_s",
            "setup.warm_s"} <= set(phases)
    # no time, rate or share is written from a CPU run: only counts
    counters = {m["name"] for m in bench["per_layer"]
                if m["source"] == "program_counter"}
    assert set(result["metrics"]) <= counters
    assert result["metrics"]["setup.programs_compiled"]["value"] >= 0
    assert "busy_s" not in result["device"]


def test_untraced_run_writes_no_device_metric_from_the_cpu(tmp_path):
    result = _last_line(_run("mperf16m.rows", 0, tmp_path))
    assert RESULT_KEYS <= set(result) and result["metrics"] == {}


def test_without_a_tpu_the_command_fails_and_prints_no_result(tmp_path):
    done = _run("mperf16m.rows", 0, tmp_path, rehearse=False)
    assert done.returncode != 0
    assert not any(line.startswith("{") for line in done.stdout.splitlines())


def _taken_out_and_put_back(tmp_path, root, name):
    """A checkout whose BENCHMARK.json has lost a cell (as before the PR
    that entered it) and gets it back as a later PR would add it: its
    entry, its configuration and the metrics that only it reports, each
    appended last, and its name last in every list it was in. The
    entries are the repo's own, found by name."""
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", name)
    taken = {"workloads": [cell], "configs": [], "per_layer": [
        m for m in bench["per_layer"] if m.get("workloads") == [name]]}
    if not any(w["config"] == cell["config"] and w is not cell
               for w in bench["workloads"]):
        taken["configs"] = [entries.named(bench, "configs", cell["config"])]
    for key, gone in taken.items():
        bench[key] = [e for e in bench[key] if e not in gone]
    was_in = [m for m in bench["end_to_end"] + bench["per_layer"]
              if name in m.get("workloads", [])]
    for metric in was_in:
        metric["workloads"].remove(name)
    assert name not in json.dumps(bench)
    for metric in was_in:
        metric["workloads"].append(name)
    for key, gone in taken.items():
        bench[key].extend(gone)
    checkout = later_pr.copy_of_the_benchmark(tmp_path, root)
    (checkout / "BENCHMARK.json").write_text(json.dumps(bench))
    entries.check_all(str(checkout))
    return str(checkout)


@pytest.mark.parametrize("name, devices, runs", [
    ("sgns21m-x4.ps", 4, True), ("sgns21m-x4.ps", 1, False),
    ("sgns8m.local", 1, True)])
def test_a_cell_needs_only_its_entries(name, devices, runs, root, tmp_path):
    """`sgns21m-x4.ps` and `sgns8m.local` waited for a later PR with
    their configuration, traffic mix and metric readers here already (PR
    27 entered them). With only its entries added, last, such a cell
    runs, and the four-chip one fails on fewer devices."""
    checkout = _taken_out_and_put_back(tmp_path, root, name)
    done = _run(name, 1 if runs else 0, tmp_path, root=checkout,
                devices=devices)
    if runs:
        result = _last_line(done)
        assert result["correct"] is True, done.stdout[-3000:]
        assert result["device"]["count"] == devices
        assert "trainer.programs_built_in_window.train" in result["metrics"]
    else:
        assert done.returncode != 0
        assert not any(line.startswith("{")
                       for line in done.stdout.splitlines())


def test_a_cell_added_as_files_runs_with_no_edit_to_an_existing_file(
        tmp_path):
    """A later PR's view, made here as `conftest.py` makes it for every
    structural test: a copy of the benchmark, plus a configuration, a
    traffic mix and two metrics as NEW files and NEW entries, appended
    last. Every structural check holds on it, its cell runs, and no file
    that was there changed."""
    root = later_pr.copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}
    later_pr.append_to(root)
    bench = later_pr.appended_bench()
    copy_, without_entry = entries.check_all(str(root))
    assert copy_ == bench and without_entry == set()
    entries.check_the_ten(copy_, sorted(THE_TEN))
    scoped = entries.reader_of(str(root), "table.unscoped_share.rows")
    assert scoped.read(Observations(trace={"scopes": {
        "jit_rows_padded": {"mv.update.scatter_add": 0.9, "no-scope": 0.05},
        "jit__lambda": {"mv.table.gather": 0.25,
                        "no-scope": 0.05}}})) == pytest.approx(8.0)
    assert scoped.read(Observations()) is None

    done = _run(later_pr.APPENDED_CELL, 1, tmp_path, root=str(root))
    result = _last_line(done)
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["metrics"]["client.adds_per_get.rows"]["value"] == 2.0
    # a share of the device's time is never written from a CPU run
    assert "table.unscoped_share.rows" not in result["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing file of the benchmark was edited"


# -- what a later PR may not do: pin a place, or break a rule ------------------

PINS = {
    "the last cell": lambda b, last: b["workloads"][-1]["name"]
    == last["workloads"],
    "the last configuration": lambda b, last: b["configs"][-1]["name"]
    == last["configs"],
    "the last per-layer entries": lambda b, last: [
        m["name"] for m in b["per_layer"]][-len(last["per_layer"]):]
    == last["per_layer"],
    "the last name in a metric's list of cells": lambda b, last: entries.named(
        b, "end_to_end", "peak_hbm_gb")["workloads"][-1] == last["workloads"],
    "how many cells": lambda b, last: len(b["workloads"]) == last["cells"]}


@pytest.mark.parametrize("pin", sorted(PINS))
def test_a_pin_on_a_place_fails_on_the_appended_copy(pin, appended_root):
    """PR 32's test asserted that its entries were the LAST of their
    lists, and so no later PR could append. Such a pin, written for
    whatever the repo's file holds last today, holds on the repo and
    fails on the copy: a test that carries one fails in the run of the
    PR that writes it. (Only this demonstration counts from an end.)"""
    repo = entries.bench_of(ROOT)
    last = {"workloads": repo["workloads"][-1]["name"],
            "configs": repo["configs"][-1]["name"],
            "per_layer": [m["name"] for m in repo["per_layer"][-3:]],
            "cells": len(repo["workloads"])}
    assert PINS[pin](repo, last)
    assert not PINS[pin](entries.bench_of(appended_root), last)


def _a_four_chip_cell_over_the_quarter(bench):
    """One more than the quarter of the cells (rounded down, one always)
    that may ask for four chips, whatever the count of cells has grown to."""
    cells = bench["workloads"]
    room = max(1, len(cells) // 4) - sum(c["chips"] == 4 for c in cells)
    for cell in [c for c in cells if c["chips"] == 1][:room + 1]:
        cell["chips"] = 4


def _a_long_why(bench):
    entries.named(bench, "workloads", "sgns8m.ps")["why"] = "w" * 201


def _a_name_with_a_space(bench):
    entries.named(bench, "workloads", "sgns8m.ps")["name"] = "sgns8m ps"


def _a_name_twice(bench):
    bench["workloads"].append(copy.deepcopy(
        entries.named(bench, "workloads", "sgns8m.ps")))


def _a_mix_that_is_no_file(bench):
    entries.named(bench, "workloads", "sgns8m.ps")["traffic"] = "none-such"


def _a_configuration_no_cell_uses(bench):
    bench["configs"].append(dict(
        entries.named(bench, "configs", "sgns-8m-d128"), name="unused",
        file="benchmark/configs/unused.json"))


def _twenty_five_cells(bench):
    cell = entries.named(bench, "workloads", "sgns8m.ps")
    while len(bench["workloads"]) < 25:
        n = len(bench["workloads"])
        bench["workloads"].append(dict(cell, name=f"cell{n}",
                                       traffic=f"mix{n}"))


@pytest.mark.parametrize("breach", [
    _a_four_chip_cell_over_the_quarter, _a_long_why, _a_name_with_a_space,
    _a_name_twice, _a_mix_that_is_no_file, _a_configuration_no_cell_uses,
    _twenty_five_cells], ids=lambda f: f.__name__.strip("_"))
def test_the_rules_for_cells_refuse(breach, root):
    """`entries.check_cells` holds for every entry what PR 32's pin meant
    for its own: the room a `why` has, names, at most 24 cells, a
    quarter of them on four chips (one always), files that exist."""
    bench = entries.bench_of(root)
    entries.check_cells(root, bench)
    breach(bench)
    with pytest.raises(AssertionError):
        entries.check_cells(root, bench)


@pytest.mark.parametrize("cell", ["mperf16m.rows", "mperf16m.rows-dev"])
def test_a_lost_add_underneath_a_run_comes_out_not_correct(
        cell, monkeypatch, capfd, tmp_path):
    """The harness past its look for a chip, driven in this process with
    the timed path broken underneath: every fifth Add is acknowledged
    and lost (host buffers in one cell, device arrays in the other: both
    are `add_rows`). The run ends, and says it is not correct."""
    import multiverso_tpu as mv
    from benchmark import run
    real = mv.create_matrix_table

    def lossy(*args, **kw):
        table = real(*args, **kw)
        add_rows, adds = table.add_rows, []

        def losing(ids, deltas, *rest, **more):
            adds.append(ids)
            if len(adds) % 5:
                add_rows(ids, deltas, *rest, **more)
        table.add_rows = losing
        return table

    monkeypatch.setattr(mv, "create_matrix_table", lossy)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    code = run.main(["--workload", cell, "--seed", "5",
                     "--seconds", "1", "--trace", "0", "--rehearse"])
    out = capfd.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert code == 0 and result["correct"] is False, out[-2000:]
    assert result["attempted"] > 10 and result["failed"] == 0
    differ = result["compared"]["replies_and_tables_that_differ"]
    assert differ["value"] > differ["limit"] == 0

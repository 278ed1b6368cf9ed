"""Every cell rehearsed end to end at a tiny size on the CPU (four
virtual devices for the four-chip cell), and one cell made only of new
files and new entries. Each run is the real command in a process of its
own, as the driver runs it, plus --rehearse."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib.harness import Observations
from benchmark.tests import entries
from benchmark.tests.test_program_span_metrics import WANT as THE_TEN

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _bench(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _run(cell, trace, tmp_path, root=ROOT, rehearse=True, devices=1):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               BENCH_RUN="1")
    command = [sys.executable] + _bench(root)["command"][1:] + [
        "--workload", cell, "--seed", str(2**31 + 11), "--seconds", "1",
        "--trace", str(trace)] + (["--rehearse"] if rehearse else [])
    return subprocess.run(command, cwd=root, env=env, text=True,
                          capture_output=True, timeout=600)


def _last_line(done):
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_cell_rehearsed_end_to_end(cell, tmp_path):
    bench = _bench()
    chips = next(w["chips"] for w in bench["workloads"] if w["name"] == cell)
    done = _run(cell, 1, tmp_path, devices=chips)
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


def _copy_of_the_benchmark(tmp_path):
    """A checkout of the benchmark's own files, for a test to add to."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "multiverso_tpu"), root / "multiverso_tpu")
    return root


X4 = {"configs": {"name": "sgns-21m-d128-x4", "source": "x", "reduced": [],
                  "file": "benchmark/configs/sgns-21m-d128-x4.json",
                  "why": "kept for later"},
      "workloads": {"name": "sgns21m-x4.ps", "config": "sgns-21m-d128-x4",
                    "traffic": "sgns-ps-block", "chips": 4,
                    "why": "kept for later"},
      "per_layer": {"name": "device.collective_share.train", "unit": "%",
                    "better": "lower", "source": "device_trace",
                    "layer": "device", "moves": "words_per_s",
                    "workloads": ["sgns21m-x4.ps"]}}
LOCAL = {"workloads": {"name": "sgns8m.local", "config": "sgns-8m-d128",
                       "traffic": "sgns-local", "chips": 1,
                       "why": "kept for later"}}


def _with_kept_cell(tmp_path, entries):
    """A checkout whose BENCHMARK.json has lost a cell (as before PR 27
    entered it) and gets it back as a later PR would add it: the entries
    above, and the cell's name in every metric that `sgns8m.ps` reports
    and the cell can."""
    root = _copy_of_the_benchmark(tmp_path)
    bench = _bench()
    name = entries["workloads"]["name"]
    for key, entry in entries.items():
        bench[key] = [e for e in bench[key] if e["name"] != entry["name"]]
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if name in metric.get("workloads", []):
            metric["workloads"].remove(name)
    assert name not in json.dumps(bench)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "sgns8m.ps" in metric.get("workloads", []):
            metric["workloads"].append(name)
    for key, entry in entries.items():
        bench[key].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return str(root), name


@pytest.mark.parametrize("entries, devices, runs", [
    (X4, 4, True), (X4, 1, False), (LOCAL, 1, True)])
def test_a_cell_kept_for_later_needs_only_its_entries(
        entries, devices, runs, tmp_path):
    """`sgns21m-x4.ps` and `sgns8m.local` were kept for a later PR with
    their configuration, traffic mix and metric readers here already (PR
    27 entered them). With only its entries added such a cell runs, and
    the four-chip one fails on fewer devices."""
    root, name = _with_kept_cell(tmp_path, entries)
    done = _run(name, 1 if runs else 0, tmp_path, root=root, devices=devices)
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
    """A later PR's view: a copy of the benchmark, plus a configuration,
    a traffic mix and two metrics as NEW files and NEW entries, appended
    last; every structural check holds on the copy too."""
    root = _copy_of_the_benchmark(tmp_path)
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*")
              if p.is_file()}

    with open(root / "benchmark" / "configs" / "mperf-16m-c50.json") as f:
        config = json.load(f)
    config.update(name="mperf-tiny-c20", cols=20)
    config["rehearsal"] = {"rows": 5000}
    with open(root / "benchmark" / "traffic" / "rows-host-100k.json") as f:
        mix = json.load(f)
    mix.update(name="rows-uniform-add2", ops=["get", "add", "add"],
               id_distribution={"kind": "uniform"}, id_order="drawn")
    (root / "benchmark" / "configs" / "mperf-tiny-c20.json").write_text(
        json.dumps(config))
    (root / "benchmark" / "traffic" / "rows-uniform-add2.json").write_text(
        json.dumps(mix))
    (root / "benchmark" / "metrics" / "client.adds_per_get.rows.py") \
        .write_text('"""Adds over Gets the caller made."""\n\n\n'
                    'def read(obs):\n'
                    '    s = obs.window.samples\n'
                    '    return len(s["add_ms"]) / len(s["get_ms"])\n')
    (root / "benchmark" / "metrics" / "table.unscoped_share.rows.py") \
        .write_text('"""Percent of the programs\' device time under no '
                    '`mv.` scope."""\n\n\ndef read(obs):\n'
                    '    if obs.trace is None:\n        return None\n'
                    '    by = [s for p in obs.trace["scopes"].values()\n'
                    '          for s in p.items()]\n'
                    '    whole = sum(t for _, t in by)\n'
                    '    bare = sum(t for s, t in by if s == "no-scope")\n'
                    '    return 100.0 * bare / whole if whole else None\n')
    bench = _bench()
    bench["configs"].append({
        "name": "mperf-tiny-c20", "source": config["source"],
        "file": "benchmark/configs/mperf-tiny-c20.json",
        "reduced": ["rows"], "why": "a test's configuration"})
    bench["workloads"].append({
        "name": "tiny.add2", "config": "mperf-tiny-c20",
        "traffic": "rows-uniform-add2", "chips": 1, "why": "a test's cell"})
    for metric in bench["end_to_end"]:     # what the rows cell reports
        if "mperf16m.rows" in metric.get("workloads", []):
            metric["workloads"].append("tiny.add2")
    bench["per_layer"].append({
        "name": "client.adds_per_get.rows", "unit": "adds/get",
        "better": "lower", "source": "program_counter",
        "layer": "worker actor and client", "moves": "rows_per_s",
        "workloads": ["tiny.add2"]})
    bench["per_layer"].append({
        "name": "table.unscoped_share.rows", "unit": "%",
        "better": "lower", "source": "device_trace",
        "layer": "table programs", "moves": "rows_per_s",
        "workloads": ["tiny.add2", "mperf16m.rows"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    copy, without_entry = entries.check_all(str(root))
    assert copy == bench and without_entry == set()
    entries.check_the_ten(copy, sorted(THE_TEN))
    scoped = entries.reader_of(str(root), "table.unscoped_share.rows")
    assert scoped.read(Observations(trace={"scopes": {
        "jit_rows_padded": {"mv.update.scatter_add": 0.9, "no-scope": 0.05},
        "jit__lambda": {"mv.table.gather": 0.25,
                        "no-scope": 0.05}}})) == pytest.approx(8.0)
    assert scoped.read(Observations()) is None

    done = _run("tiny.add2", 1, tmp_path, root=str(root))
    result = _last_line(done)
    assert result["correct"] is True, done.stdout[-3000:]
    assert result["metrics"]["client.adds_per_get.rows"]["value"] == 2.0
    # a share of the device's time is never written from a CPU run
    assert "table.unscoped_share.rows" not in result["metrics"]
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing file of the benchmark was edited"


def test_a_lost_add_underneath_a_run_comes_out_not_correct(
        monkeypatch, capfd, tmp_path):
    """The harness past its look for a chip, driven in this process with
    the timed path broken underneath: every fifth Add is acknowledged
    and lost. The run ends, and says it is not correct."""
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
    code = run.main(["--workload", "mperf16m.rows", "--seed", "5",
                     "--seconds", "1", "--trace", "0", "--rehearse"])
    out = capfd.readouterr().out
    result = json.loads(out.strip().splitlines()[-1])
    assert code == 0 and result["correct"] is False, out[-2000:]
    assert result["attempted"] > 10 and result["failed"] == 0
    differ = result["compared"]["replies_and_tables_that_differ"]
    assert differ["value"] > differ["limit"] == 0

"""A later PR's view of the benchmark, which every structural test also
runs on: the repo's `BENCHMARK.json` with a configuration, a cell, a
traffic mix and two metrics appended last (and the cell's name last in
the `workloads` lists of what it reports), as new files beside a copy of
`benchmark/`. `conftest.py` makes the tree once a session (`root`,
`appended_root`); `bench_at` gives the lists while tests are collected.

What a later PR does NOT append: an entry for a question the list already
asks. A new family of language model brings `benchmark/lib/<family>shapes.py`
(a row of `benchmark/lib/families.py`'s table: `COUNTERS`, `step_flops`,
`ATTENTION_SCOPES`, `attention_step_flops`), a driver that writes
`family="<family>"` into `ctx.shapes`, and its cell's name appended to the
`workloads` of `trainer.mfu.lm` and `trainer.attn_roofline.lm`: not two new
entries. `per_layer` holds at most 128 (`entries.MOST_PER_LAYER`), and
`entries.check_all` refuses a fuller list on this copy too."""

import json
import os
import shutil

from benchmark.tests import entries

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

ROOTS = ("repo", "appended")
APPENDED_CELL = "tiny.add2"
APPENDED_CONFIG = "mperf-tiny-c20"
APPENDED_MIX = "rows-uniform-add2"
APPENDED_METRICS = {
    "client.adds_per_get.rows":
        '"""Adds over Gets the caller made."""\n\n\n'
        'def read(obs):\n'
        '    s = obs.window.samples\n'
        '    return len(s["add_ms"]) / len(s["get_ms"])\n',
    "table.unscoped_share.rows":
        '"""Percent of the programs\' device time under no '
        '`mv.` scope."""\n\n\ndef read(obs):\n'
        '    if obs.trace is None:\n        return None\n'
        '    by = [s for p in obs.trace["scopes"].values()\n'
        '          for s in p.items()]\n'
        '    whole = sum(t for _, t in by)\n'
        '    bare = sum(t for s, t in by if s == "no-scope")\n'
        '    return 100.0 * bare / whole if whole else None\n'}


def appended_bench() -> dict:
    """The repo's `BENCHMARK.json` as a later PR would leave it: a rows
    cell on a configuration and a mix of its own, reporting what
    `mperf16m.rows` reports and, as a trainer's cell would, `words_per_s`
    and `peak_hbm_gb`; two per-layer metrics. Everything appended."""
    bench = entries.bench_of(ROOT)
    source = next(c["source"] for c in bench["configs"]
                  if c["name"] == "mperf-16m-c50")
    bench["configs"].append({
        "name": APPENDED_CONFIG, "source": source,
        "file": f"benchmark/configs/{APPENDED_CONFIG}.json",
        "reduced": ["rows"], "why": "a test's configuration"})
    bench["workloads"].append({
        "name": APPENDED_CELL, "config": APPENDED_CONFIG,
        "traffic": APPENDED_MIX, "chips": 1, "why": "a test's cell"})
    for metric in bench["end_to_end"]:
        cells = metric.get("workloads", [])
        if "mperf16m.rows" in cells or metric["name"] == "words_per_s":
            if APPENDED_CELL not in cells:
                cells.append(APPENDED_CELL)
    bench["per_layer"].append({
        "name": "client.adds_per_get.rows", "unit": "adds/get",
        "better": "lower", "source": "program_counter",
        "layer": "worker actor and client", "moves": "rows_per_s",
        "workloads": [APPENDED_CELL]})
    bench["per_layer"].append({
        "name": "table.unscoped_share.rows", "unit": "%",
        "better": "lower", "source": "device_trace",
        "layer": "table programs", "moves": "rows_per_s",
        "workloads": [APPENDED_CELL, "mperf16m.rows"]})
    return bench


def bench_at(which: str) -> dict:
    """The lists a test is parametrised over while it is collected."""
    return entries.bench_of(ROOT) if which == "repo" else appended_bench()


def copy_of_the_benchmark(where, source=ROOT):
    """A checkout of the benchmark's own files (``source``'s), for a test
    to add to; the program itself is the repo's."""
    root = where / "checkout"
    shutil.copytree(os.path.join(source, "benchmark"), root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(source, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "multiverso_tpu"), root / "multiverso_tpu")
    return root


def append_to(root) -> None:
    """Makes ``root`` (a copy of the benchmark) the later PR's tree: new
    files and `appended_bench`, and no edit to a file that was there."""
    files = root / "benchmark"
    with open(files / "configs" / "mperf-16m-c50.json") as f:
        config = json.load(f)
    config.update(name=APPENDED_CONFIG, cols=20)
    config["rehearsal"] = {"rows": 5000}
    with open(files / "traffic" / "rows-host-100k.json") as f:
        mix = json.load(f)
    mix.update(name=APPENDED_MIX, ops=["get", "add", "add"],
               id_distribution={"kind": "uniform"}, id_order="drawn")
    (files / "configs" / f"{APPENDED_CONFIG}.json").write_text(
        json.dumps(config))
    (files / "traffic" / f"{APPENDED_MIX}.json").write_text(json.dumps(mix))
    for name, text in APPENDED_METRICS.items():
        (files / "metrics" / f"{name}.py").write_text(text)
    (root / "BENCHMARK.json").write_text(json.dumps(appended_bench()))

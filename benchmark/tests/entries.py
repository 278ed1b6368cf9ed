"""What holds for every metric entry of a `BENCHMARK.json`, wherever in
its list it stands; the tests run it on the repo's file and on a copy
that a later PR's additions were made to."""

import importlib.util
import json
import os

SOURCES = {"end_to_end": {"host_clock", "device_trace"},
           "per_layer": {"host_clock", "device_trace", "program_span",
                         "program_counter"}}


def bench_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def entries(bench):
    return [(kind, m) for kind in ("end_to_end", "per_layer")
            for m in bench[kind]]


def reader_of(root, name):
    """The metric's module, loaded as benchmark/run.py loads it."""
    path = os.path.join(root, "benchmark", "metrics", f"{name}.py")
    assert os.path.isfile(path), f"{name} has no reader file"
    spec = importlib.util.spec_from_file_location(
        f"metric_{name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def check_entry(root, bench, kind, metric):
    name = metric["name"]
    assert callable(getattr(reader_of(root, name), "read", None)), name
    assert metric["source"] in SOURCES[kind], (name, metric["source"])
    assert metric["better"] in ("lower", "higher") and metric["unit"]
    cells = {w["name"] for w in bench["workloads"]}
    assert set(metric.get("workloads", [])) <= cells, name
    if kind == "end_to_end":
        return
    moved, = [e for e in bench["end_to_end"] if e["name"] == metric["moves"]]
    # only cells that report the end-to-end metric it should move
    assert set(metric.get("workloads", cells)) <= \
        set(moved.get("workloads", cells)), name
    assert metric["layer"] and "\n" not in metric["layer"]


def check_the_ten(bench, names):
    """PR 24's readers of the program's monitors: `program_span`, under a
    layer other metrics name too, in cells that report what they move."""
    cells = {w["name"] for w in bench["workloads"]}
    for name in names:
        metric, = [m for m in bench["per_layer"] if m["name"] == name]
        assert metric["source"] == "program_span"
        assert metric["layer"] in {m["layer"] for m in bench["per_layer"]
                                   if m["name"] != name}
        moved, = [e for e in bench["end_to_end"]
                  if e["name"] == metric["moves"]]
        assert set(metric["workloads"]) <= cells
        assert set(metric["workloads"]) <= set(moved["workloads"])


def check_all(root):
    bench = bench_of(root)
    names = [m["name"] for _, m in entries(bench)]
    assert len(names) == len(set(names))
    for kind, metric in entries(bench):
        check_entry(root, bench, kind, metric)
    on_disk = {f[:-3] for f in os.listdir(
        os.path.join(root, "benchmark", "metrics")) if f.endswith(".py")}
    return bench, on_disk - set(names)

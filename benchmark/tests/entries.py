"""What holds for every entry of a `BENCHMARK.json`, wherever in its list
it stands; the tests run it on the repo's file and on a copy that a
later PR's additions were made to (`conftest.py`). Entries are found by
name (`named`), never by place: every list grows at its end."""

import importlib.util
import json
import os
import re

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}\Z")
MOST_CELLS = 24
MOST_PER_LAYER = 128    # the contract's; the chip tool refuses a 129th

SOURCES = {"end_to_end": {"host_clock", "device_trace"},
           "per_layer": {"host_clock", "device_trace", "program_span",
                         "program_counter"}}


def bench_of(root):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def named(bench, key, name):
    """The one entry of ``bench[key]`` with that name."""
    found, = [e for e in bench[key] if e["name"] == name]
    return found


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
    moved = named(bench, "end_to_end", metric["moves"])
    # only cells that report the end-to-end metric it should move
    assert set(metric.get("workloads", cells)) <= \
        set(moved.get("workloads", cells)), name
    assert metric["layer"] and "\n" not in metric["layer"]


def check_the_ten(bench, names):
    """PR 24's readers of the program's monitors: `program_span`, under a
    layer other metrics name too, in cells that report what they move."""
    cells = {w["name"] for w in bench["workloads"]}
    for name in names:
        metric = named(bench, "per_layer", name)
        assert metric["source"] == "program_span"
        assert metric["layer"] in {m["layer"] for m in bench["per_layer"]
                                   if m["name"] != name}
        moved = named(bench, "end_to_end", metric["moves"])
        assert set(metric["workloads"]) <= cells
        assert set(metric["workloads"]) <= set(moved["workloads"])


def check_cells(root, bench):
    """The cells and configurations: names, the room a `why` has, how
    many cells and how many of them on four chips, and that each cell's
    configuration, traffic mix and driver are files."""
    cells, configs = bench["workloads"], bench["configs"]
    for group in (cells, configs):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names)), names
        assert all(NAME.match(n) for n in names), names
        assert all(0 < len(e["why"]) <= 200 and "\n" not in e["why"]
                   for e in group)
    assert 1 <= len(cells) <= MOST_CELLS
    assert all(c["chips"] in (1, 4) for c in cells)
    # a quarter of the cells, rounded down, and one always
    assert sum(c["chips"] == 4 for c in cells) <= max(1, len(cells) // 4)
    pairs = [(c["config"], c["traffic"]) for c in cells]
    assert len(pairs) == len(set(pairs)), pairs
    assert {c["config"] for c in cells} == {c["name"] for c in configs}
    files = [c["file"] for c in configs]
    assert len(files) == len(set(files))
    for cell in cells:
        assert NAME.match(cell["traffic"]), cell
        config = named(bench, "configs", cell["config"])
        assert config["file"].startswith("benchmark/configs/")
        assert os.path.isfile(os.path.join(
            root, "benchmark", "traffic", f"{cell['traffic']}.json")), cell
        with open(os.path.join(root, config["file"])) as f:
            driver = json.load(f)["driver"]
        assert os.path.isfile(os.path.join(
            root, "benchmark", "drivers", f"{driver}.py")), cell


def check_all(root):
    bench = bench_of(root)
    # a full list fails here, on the CPU, before a chip call is spent on it
    assert 1 <= len(bench["per_layer"]) <= MOST_PER_LAYER, \
        len(bench["per_layer"])
    names = [m["name"] for _, m in entries(bench)]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names), names
    for kind, metric in entries(bench):
        check_entry(root, bench, kind, metric)
    check_cells(root, bench)
    on_disk = {f[:-3] for f in os.listdir(
        os.path.join(root, "benchmark", "metrics")) if f.endswith(".py")}
    return bench, on_disk - set(names)

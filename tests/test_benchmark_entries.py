"""Tier-1's guard that the benchmark can still be added to: every rule
of the contract that `benchmark/tests/entries.py` holds (`check_all`:
names, sources, units, each metric's cells and the end-to-end metric it
moves, a reader file for every entry, each cell's configuration, traffic
mix and driver, the share of four-chip cells) on the repo's
`BENCHMARK.json` and on the copy a later PR appended a configuration, a
cell, a mix and two metrics to (`benchmark/tests/later_pr.py`). The
driver's own test run never enters `benchmark/tests`, so the cases of
`benchmark/tests/test_families.py` (the two readers that are one metric
for every family of language model, the retired names, the list's room)
are taken in here by name and run on this file's `root`."""

import json
import os

import pytest

from benchmark.tests import entries, later_pr, test_families

ROOT = later_pr.ROOT
globals().update({name: case for name, case in vars(test_families).items()
                  if name.startswith("test_")})


@pytest.fixture(scope="module")
def appended_root(tmp_path_factory):
    root = later_pr.copy_of_the_benchmark(tmp_path_factory.mktemp("later_pr"))
    later_pr.append_to(root)
    return str(root)


@pytest.fixture(params=later_pr.ROOTS)
def root(request):
    return ROOT if request.param == "repo" \
        else request.getfixturevalue("appended_root")


def test_every_entry_holds_the_contract_s_rules(root):
    bench, unlisted = entries.check_all(root)
    assert bench["workloads"] and bench["per_layer"]
    # a reader file no entry names is a metric nobody reports
    assert not unlisted, unlisted


def test_the_appended_copy_really_is_appended_to(appended_root):
    repo, copy = entries.bench_of(ROOT), entries.bench_of(appended_root)
    for key in ("configs", "workloads", "per_layer"):
        names = [e["name"] for e in repo[key]]
        assert [e["name"] for e in copy[key]][:len(names)] == names
        assert len(copy[key]) > len(names)
    assert copy["workloads"][-1]["name"] == later_pr.APPENDED_CELL


def test_every_configuration_lists_what_it_reduced(root):
    bench = entries.bench_of(root)
    for entry in bench["configs"]:
        with open(os.path.join(root, entry["file"])) as f:
            config = json.load(f)
        assert len(entry["reduced"]) <= 16
        assert all(entries.NAME.match(k) for k in entry["reduced"])
        if isinstance(config.get("reduced"), dict) \
                and entry["name"] == config.get("name"):
            assert sorted(entry["reduced"]) == sorted(config["reduced"])


@pytest.mark.parametrize("cell", ["st21b.ps-8k", "sdar30b.ps-bd4k",
                                  "mperf16m.rows-dev"])
def test_a_cell_is_found_by_name_on_both_roots(root, cell):
    bench = entries.bench_of(root)
    found = entries.named(bench, "workloads", cell)
    assert found["chips"] == 1
    entries.named(bench, "configs", found["config"])
    assert cell in entries.named(bench, "end_to_end",
                                 "peak_hbm_gb")["workloads"]

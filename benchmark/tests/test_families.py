"""The two readers that are ONE metric for every family of language model
(`trainer.mfu.lm`, `trainer.attn_roofline.lm`, PR 67): given a family's
shapes and a window, each returns what that family's own counting module
gives through `lmshapes.share_of_peak`, and nothing for shapes of no family;
the table's rows (`benchmark/lib/families.py`) are the drivers' own; the
names the merge retired are in no list and have no file; and the list of
per-layer metrics has room again, held on both roots."""

import importlib
import json
import os
import re

import pytest

from benchmark.lib import families, lmshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import (entries, test_lm_bd_cell, test_lm_cell,
                             test_lm_glm_cell, test_lm_granite_cell,
                             test_lm_kda_cell, test_lm_lfm2_cell,
                             test_lm_mixed_cell, test_lm_mla_cell,
                             test_lm_solar_cell, test_lm_sparse_cell)

ROOT = test_lm_cell.ROOT
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# family -> the shapes its cell's driver fills (each cell's own test file's)
SHAPES = {"lm": test_lm_cell.SHAPES, "bd": test_lm_bd_cell.SHAPES,
          "mla": test_lm_mla_cell.SHAPES, "mixed": test_lm_mixed_cell.SHAPES,
          "sparse": test_lm_sparse_cell.SHAPES, "kda": test_lm_kda_cell.SHAPES,
          "solar": test_lm_solar_cell.SHAPES, "conv": test_lm_lfm2_cell.SHAPES,
          "ssd": test_lm_granite_cell.SHAPES}
WITH_A_KERNEL = sorted(set(SHAPES) - {"kda"})
RETIRED = {
    "trainer.mfu_blockdiff.lm": "trainer.mfu.lm",
    "trainer.mfu_mla.lm": "trainer.mfu.lm",
    "trainer.mfu_mixed.lm": "trainer.mfu.lm",
    "trainer.mfu_sparse.lm": "trainer.mfu.lm",
    "trainer.mfu_kda.lm": "trainer.mfu.lm",
    "trainer.mfu_solar.lm": "trainer.mfu.lm",
    "trainer.mfu_lfm2.lm": "trainer.mfu.lm",
    "trainer.mfu_granite.lm": "trainer.mfu.lm",
    "trainer.attn_blockdiff_roofline.lm": "trainer.attn_roofline.lm",
    "trainer.attn_mla_roofline.lm": "trainer.attn_roofline.lm",
    "trainer.attn_mixed_roofline.lm": "trainer.attn_roofline.lm",
    "trainer.attn_sparse_roofline.lm": "trainer.attn_roofline.lm",
    "trainer.attn_full_roofline_held.lm": "trainer.attn_roofline.lm",
    "trainer.attn_full_roofline_d64.lm": "trainer.attn_roofline.lm",
    "trainer.heads_held_share.lm": None,
    "trainer.mixers_conv_share.lm": None,
    "trainer.noise_ms_per_step.lm": None}
MODEL_CELLS = ["st21b.ps-8k", "sdar30b.ps-bd4k", "xing29b.ps-4k",
               "laguna33b.ps-8k", "keye30b.ps-16k", "kimi48b.ps-8k",
               "glm30b.ps-8k", "solar250b.ps-8k", "lfm8b.ps-8k",
               "granite3b.ps-8k"]
STEPS, RUNS, SECONDS, TOOK = 3, 17, 20.5, 0.21


class _Window:
    def __init__(self, rounds, seconds, counters):
        self.rounds, self.seconds, self.counters = rounds, seconds, counters


def _obs(shapes, scopes=()):
    counts = {"LM_STEP": {"count": RUNS, "ms": 0.0},
              "LM_HELD_ASSIGNMENTS": {"count": RUNS * 40000, "ms": 0.0}}
    # the kernels' time split over the family's scopes, in two programs
    by = {scope: TOOK / (2 * len(scopes)) for scope in scopes}
    trace = {"window_s": 3.5, "programs": {},
             "scopes": {"jit_forward": dict(by, **{"mv.lm.head": 0.1}),
                        "jit_backward": dict(by)}}
    return Observations(trace=trace, traced=_Window(STEPS, 3.5, counts),
                        window=_Window(RUNS, SECONDS, counts), shapes=shapes,
                        peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


@pytest.mark.parametrize("family", sorted(SHAPES))
def test_the_whole_step_s_share_is_the_family_s_own_count(family):
    module = importlib.import_module(f"benchmark.lib.{family}shapes")
    shapes = SHAPES[family]
    assert shapes["family"] == family
    assert families.counting(shapes) is module
    counts = [RUNS if c == "LM_STEP" else RUNS * 40000
              for c in module.COUNTERS]
    want = lmshapes.share_of_peak(module.step_flops(*counts, shapes),
                                  SECONDS, 197e12)
    assert _read("trainer.mfu.lm", _obs(shapes)) == pytest.approx(want)
    assert 0 < want < 100
    # a program without the family's counters (a parent commit): nothing
    bare = _obs(shapes)
    bare.window.counters = {}
    assert _read("trainer.mfu.lm", bare) is None


@pytest.mark.parametrize("family", WITH_A_KERNEL)
def test_the_attention_kernels_share_is_the_family_s_own_count(family):
    module = importlib.import_module(f"benchmark.lib.{family}shapes")
    shapes = SHAPES[family]
    want = lmshapes.share_of_peak(
        STEPS * module.attention_step_flops(shapes), TOOK, 197e12)
    obs = _obs(shapes, module.ATTENTION_SCOPES)
    assert _read("trainer.attn_roofline.lm", obs) == pytest.approx(want)
    assert 0 < want < 100
    # another family's scopes alone in the trace: no time to set it against
    other = ("mv.lm.attn.kda.scan",)
    assert _read("trainer.attn_roofline.lm", _obs(shapes, other)) is None
    obs.trace = None
    assert _read("trainer.attn_roofline.lm", obs) is None


def test_a_family_without_a_kernel_scope_has_no_attention_roofline():
    kda = importlib.import_module("benchmark.lib.kdashapes")
    assert not hasattr(kda, "ATTENTION_SCOPES")
    assert _read("trainer.attn_roofline.lm",
                 _obs(SHAPES["kda"], ("mv.lm.attn.mla.kernel",))) is None


@pytest.mark.parametrize("shapes", [
    {}, {"rows": 16_000_000, "cols": 50},                   # no family
    dict(test_lm_cell.SHAPES, family="none_such"),          # no such module
    dict(test_lm_cell.SHAPES, family=""),       # lib/shapes.py is no row
    dict(test_lm_cell.SHAPES, family="../drivers/lm"),      # no path
    dict(test_lm_cell.SHAPES, family=7),
    {k: v for k, v in test_lm_cell.SHAPES.items() if k != "family"}],
    ids=["empty", "rows", "unknown", "blank", "path", "number", "no-key"])
def test_shapes_of_no_family_read_nothing(shapes):
    assert families.counting(shapes) is None
    scopes = lmshapes.ATTENTION_SCOPES
    for name in ("trainer.mfu.lm", "trainer.attn_roofline.lm"):
        assert _read(name, _obs(shapes, scopes)) is None


def test_the_choice_is_exclusive_whatever_other_keys_the_shapes_share():
    """The old readers' guards (`"window_layout" in shapes`, `"conv_taps"`,
    `"heads_all"`, ...) were kept apart by `workloads` alone: shapes that
    hold several families' keys read ONE family's count, the one named."""
    both = dict(SHAPES["lm"], **SHAPES["bd"])      # lm_bd.py updates lm's
    assert "window_layout" in both and both["family"] == "bd"
    bd = importlib.import_module("benchmark.lib.bdshapes")
    assert _read("trainer.mfu.lm", _obs(both)) == pytest.approx(
        lmshapes.share_of_peak(bd.step_flops(RUNS, RUNS * 40000, both),
                               SECONDS, 197e12))


def test_every_driver_names_a_row_of_the_table():
    """Each `benchmark/drivers/lm*.py` writes one `family=` into
    `ctx.shapes`, every name is a counting module with the row's columns,
    and the configurations' drivers reach all nine rows."""
    drivers = os.path.join(ROOT, "benchmark", "drivers")
    named = {}
    for file in sorted(os.listdir(drivers)):
        if re.match(r"lm(_\w+)?\.py\Z", file):
            with open(os.path.join(drivers, file)) as f:
                found = re.findall(r'\bfamily="(\w+)"', f.read())
            assert len(found) == 1, (file, found)
            named[file[:-3]] = found[0]
    assert set(named.values()) == set(SHAPES)
    for family in named.values():
        module = families.counting({"family": family})
        assert callable(module.step_flops) and module.COUNTERS[0] == "LM_STEP"
        if hasattr(module, "ATTENTION_SCOPES"):
            assert callable(module.attention_step_flops)
            assert all(s.startswith("mv.lm.attn.") and s.endswith(".kernel")
                       for s in module.ATTENTION_SCOPES)
    bench = entries.bench_of(ROOT)
    used = set()
    for config in bench["configs"]:
        with open(os.path.join(ROOT, config["file"])) as f:
            used.add(json.load(f)["driver"])
    assert set(named) <= used


@pytest.mark.parametrize("name", sorted(RETIRED))
def test_a_retired_name_is_in_no_list_and_has_no_file(name, root):
    bench = entries.bench_of(root)
    assert name not in [m["name"] for _, m in entries.entries(bench)]
    assert not os.path.exists(os.path.join(
        root, "benchmark", "metrics", f"{name}.py"))
    kept = RETIRED[name]
    if kept:    # its history continues under the kept name
        entries.named(bench, "per_layer", kept)


def test_the_merged_entries_list_the_model_cells_in_their_order(root):
    bench = entries.bench_of(root)
    order = [w["name"] for w in bench["workloads"]]
    assert [c for c in order if c in MODEL_CELLS] == MODEL_CELLS
    mfu = entries.named(bench, "per_layer", "trainer.mfu.lm")
    roofline = entries.named(bench, "per_layer", "trainer.attn_roofline.lm")
    assert mfu["workloads"][:10] == MODEL_CELLS
    assert roofline["workloads"][:9] == [c for c in MODEL_CELLS
                                         if c != "kimi48b.ps-8k"]
    assert (mfu["source"], roofline["source"]) == ("host_clock",
                                                   "device_trace")
    for metric in (mfu, roofline):
        entries.check_entry(root, bench, "per_layer", metric)
        assert (metric["unit"], metric["better"], metric["layer"],
                metric["moves"]) == ("%", "higher", "trainer", "words_per_s")


def test_a_list_over_the_limit_is_refused_where_the_repo_s_is_not(
        root, tmp_path):
    bench, unlisted = entries.check_all(root)
    assert not unlisted, unlisted
    assert len(bench["per_layer"]) <= entries.MOST_PER_LAYER
    # a copy filled past the limit fails on the CPU, before a chip call
    full = tmp_path / "full"
    (full / "benchmark").mkdir(parents=True)
    os.symlink(os.path.join(root, "benchmark", "metrics"),
               full / "benchmark" / "metrics")
    extra = dict(entries.named(bench, "per_layer", "trainer.mfu.lm"))
    room = entries.MOST_PER_LAYER - len(bench["per_layer"])
    bench["per_layer"] += [dict(extra, name=f"trainer.filler{i}.lm")
                           for i in range(room + 1)]
    (full / "BENCHMARK.json").write_text(json.dumps(bench))
    with pytest.raises(AssertionError, match=str(entries.MOST_PER_LAYER + 1)):
        entries.check_all(str(full))

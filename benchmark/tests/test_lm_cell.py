"""The language-model cell `st21b.ps-8k`: its counting functions by hand,
its thirteen readers on hand-built ``Observations``, its entries, its
configuration against the catalog's numbers, and that a checkout without
the trainer fails the cell at once. (Its rehearsal end to end is
test_rehearse.py's, which runs every cell of BENCHMARK.json.)"""

import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import lmshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "st21b.ps-8k"
CONFIG = "smallthinker-21ba3b-l4"
SHAPES = {"family": "lm", "sequences": 2, "seq_len": 8192, "hidden": 2560,
          "heads": 28,
          "kv_heads": 4, "head_dim": 128, "router_outputs": 64, "held": 16,
          "expert_width": 768, "vocab": 37984, "layers": 4, "window": 4096,
          "window_layout": [0, 1, 1, 1], "parameters": 656529920}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.attn_full_ms_per_step.lm", "trainer.attn_window_ms_per_step.lm",
       "trainer.router_ms_per_step.lm", "trainer.experts_ms_per_step.lm",
       "trainer.head_ms_per_step.lm", "table.adam_ms_per_step.lm",
       "table.snapshot_ms_per_step.lm",
       "trainer.expert_load_max_over_mean.lm", "table.embed_rows_per_step.lm",
       "trainer.mfu.lm", "trainer.experts_roofline.lm",
       "trainer.attn_roofline.lm", "table.adam_roofline.lm"]


# -- the counting functions, by hand ------------------------------------------

def test_attention_pairs():
    assert lmshapes.attention_pairs(4, 0) == 10            # 1 + 2 + 3 + 4
    assert lmshapes.attention_pairs(4, 2) == 7             # 1 + 2 + 2 + 2
    assert lmshapes.attention_pairs(4, 4) == 10            # the causal mask
    assert lmshapes.attention_pairs(4, 9) == 10
    assert lmshapes.attention_pairs(8192, 0) == 8192 * 8193 // 2
    assert lmshapes.attention_pairs(8192, 4096) \
        == 4096 * 4097 // 2 + 4096 * 4096


def test_attention_flops_count_unmasked_pairs_three_passes():
    # a pair costs 2 operations in the scores and 2 in the product with v,
    # a lane of the head, a head; backward twice the forward
    assert lmshapes.attention_flops(1, 4, 1, 1, 0) == 3 * 4 * 10
    assert lmshapes.attention_flops(2, 4, 28, 128, 2) \
        == 3 * 4 * 128 * 28 * 2 * 7
    full = lmshapes.attention_flops(2, 8192, 28, 128, 0)
    window = lmshapes.attention_flops(2, 8192, 28, 128, 4096)
    assert 0.74 < window / full < 0.76      # the window layer costs less


def test_expert_flops_count_assignments_and_no_padding_row():
    assert lmshapes.expert_flops(1, 2560, 768) == 3 * 3 * 2 * 2560 * 768
    # a step's mean load: 16384 tokens x 6 / 64 experts x 16 held
    mean = 16384 * 6 * 16 // 64
    assert mean == 24576
    assert lmshapes.expert_flops(mean, 2560, 768) == 24576 * 35389440


def test_step_flops_are_about_the_issue_s_count():
    """About 31 TFLOP a step was ISSUE 32's count from parameters; by
    shapes, with the window's masked pairs left out."""
    flops = lmshapes.step_flops(1, 4 * 24576, SHAPES)
    dense = lmshapes.dense_flops(16384, SHAPES)
    q, kv = 28 * 128, 4 * 128
    assert dense == 3 * 16384 * (4 * 2 * 2560 * (2 * q + 2 * kv + 64)
                                 + 2 * 2560 * 37984)
    assert 25e12 < flops < 33e12
    assert lmshapes.step_flops(2, 8 * 24576, SHAPES) == 2 * flops


def test_adam_is_28_bytes_a_parameter():
    whole = 656529920 - 37984 * 2560
    assert lmshapes.adam_bytes(1, 0, SHAPES) == 28 * whole
    assert lmshapes.adam_bytes(3, 3 * 7000, SHAPES) \
        == 28 * (3 * whole + 21000 * 2560)
    assert lmshapes.share_of_peak(819e9, 2.0, 819e9) == 50.0


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


TRACE = {"window_s": 3.0, "programs": {
    "jit_snapshot": {"seconds": 0.060, "count": 336},
    "jit_forward": {"seconds": 0.454, "count": 32}}, "scopes": {
    "jit_forward": {"mv.lm.attn.full": 0.020, "mv.lm.attn.full.kernel": 0.060,
                    "mv.lm.attn.window": 0.050,
                    "mv.lm.attn.window.kernel": 0.120,
                    "mv.lm.router": 0.004, "mv.lm.experts": 0.200},
    "jit_backward": {"mv.lm.attn.full": 0.040,
                     "mv.lm.attn.full.kernel": 0.180,
                     "mv.lm.attn.window": 0.100,
                     "mv.lm.attn.window.kernel": 0.360,
                     "mv.lm.router": 0.008, "mv.lm.experts": 0.600},
    "jit_head_step": {"mv.lm.head": 0.240},
    "jit_dense_padded": {"mv.update.rule": 0.150, "mv.update.pad": 0.002},
    "jit_rows_padded": {"mv.update.rule": 0.010, "mv.update.dedup": 0.004,
                        "mv.update.scatter_add": 0.014},
    "jit_snapshot": {"no-scope": 0.060}}}
STEPS = 8
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 4 * 24576,
                LM_EMBED_ROWS=STEPS * 7000, LM_EXPERT_MAX_TOKENS=STEPS * 8 * 900)
WINDOW = _count(LM_STEP=50, LM_HELD_ASSIGNMENTS=50 * 4 * 24576,
                LM_EMBED_ROWS=50 * 7000, LM_EXPERT_MAX_TOKENS=50 * 8 * 960)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.0, traced),
        window=_Window(50, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


def _attention(steps):
    return steps * (lmshapes.attention_flops(2, 8192, 28, 128, 0)
                    + 3 * lmshapes.attention_flops(2, 8192, 28, 128, 4096))


WANT = {
    "trainer.attn_full_ms_per_step.lm": 300.0 / STEPS,
    "trainer.attn_window_ms_per_step.lm": 630.0 / STEPS,
    "trainer.router_ms_per_step.lm": 12.0 / STEPS,
    "trainer.experts_ms_per_step.lm": 800.0 / STEPS,
    "trainer.head_ms_per_step.lm": 240.0 / STEPS,
    "table.adam_ms_per_step.lm": 180.0 / STEPS,
    "table.snapshot_ms_per_step.lm": 60.0 / STEPS,
    # the fullest of 16 held experts over the mean: 8 groups a step
    # (4 layers x 2 sequences) of 960 against 4 x 24576 / 8 / 16 = 768
    "trainer.expert_load_max_over_mean.lm": 960 / 768,
    "table.embed_rows_per_step.lm": 7000.0,
    "trainer.mfu.lm": 100 * lmshapes.step_flops(50, 50 * 4 * 24576, SHAPES)
    / 197e12 / 20.0,
    "trainer.experts_roofline.lm": 100 * lmshapes.expert_flops(
        STEPS * 4 * 24576, 2560, 768) / 197e12 / 0.8,
    "trainer.attn_roofline.lm": 100 * _attention(STEPS) / 197e12 / 0.72,
    "table.adam_roofline.lm": 100 * lmshapes.adam_bytes(
        STEPS, STEPS * 7000, SHAPES) / 819e9 / 0.18,
}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW)


@pytest.mark.parametrize("name", NEW)
def test_reader(name):
    value = _read(name, _obs())
    assert value == pytest.approx(WANT[name])
    if "roofline" in name or "mfu" in name:
        assert 0 < value < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too: no scope, no counter, no
    shape of this trainer, and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    bare = _obs(trace=bare_trace, traced={}, window={}, shapes={})
    assert _read(name, bare) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None


def test_the_experts_roofline_takes_the_larger_bound():
    """With next to no assignments the weights' bytes bound the time."""
    few = dict(TRACED, LM_HELD_ASSIGNMENTS={"count": 16, "ms": 0.0})
    value = _read("trainer.experts_roofline.lm", _obs(traced=few))
    assert value == pytest.approx(
        100 * lmshapes.expert_bytes(STEPS, 16, SHAPES) / 819e9 / 0.8)


# -- the entries, the configuration, the parent ----------------------------------

@pytest.mark.parametrize("name", NEW)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    # by membership: later cells were appended to these readers' lists
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] in ("trainer", "table programs")
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    """Wherever they stand: a later PR appends after them, so nothing
    here counts from either end of a list (`entries.check_all` holds
    what the old pin on the last places meant for every entry)."""
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-step-8k"
    entries.named(bench, "configs", CONFIG)
    for name in ("words_per_s", "peak_hbm_gb"):
        assert CELL in entries.named(bench, "end_to_end", name)["workloads"]
    entries.check_cells(root, bench)


def test_the_configuration_holds_the_catalog_s_numbers(root):
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    published = {
        "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64, "num_attention_heads": 28,
        "num_hidden_layers": 52, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06, "rope_theta": 1500000,
        "sliding_window_size": 4096, "vocab_size": 151936}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert config["rope_layout"] == [0, 1, 1, 1] * 13
    assert config["sliding_window_layout"] == [0, 1, 1, 1] * 13
    assert config["router_outputs"] == 64
    assert config["parameters"]["total"] == SHAPES["parameters"]
    assert entry["source"] == config["source"]


def test_a_checkout_without_the_trainer_fails_the_cell_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit
    code than 0 and no result line."""
    import shutil
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        "lm", ".pytest_cache"))
    assert not (root / "multiverso_tpu" / "models" / "lm").exists()
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483700", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=300)
    assert done.returncode not in (0, 124, 137)
    assert "ModuleNotFoundError" in done.stderr
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]

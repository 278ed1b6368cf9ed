"""The cell `keye30b.ps-16k` (attention over a learned selection): its
counting functions by hand, its eight readers on hand-built
``Observations``, its entries, its configuration against the catalog's
numbers, that its controls fail in the rehearsal (on the repo and on the
copy a later PR appended to) and that a checkout without the selection
fails the cell at once. (Its rehearsal end to end is test_rehearse.py's,
which runs every cell of BENCHMARK.json.)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import lmshapes, sparseshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_sparse_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "keye30b.ps-16k"
CONFIG = "keye-vl2-30b-a3b-lm"
SHAPES = {"family": "sparse", "sequences": 1, "seq_len": 16384,
          "hidden": 2048, "heads": 32,
          "kv_heads": 4, "head_dim": 128, "router_outputs": 128, "top_k": 8,
          "held": 16, "expert_width": 768, "vocab": 18992, "layers": 5,
          "index_heads": 16, "index_dim": 64, "index_topk": 2048,
          "index_tile": 512, "parameters": 562290560}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.attn_sparse_ms_per_step.lm",
       "trainer.indexer_ms_per_step.lm", "trainer.indexer_roofline.lm",
       "trainer.select_ms_per_step.lm", "trainer.selected_share.lm",
       "trainer.select_tiles_live_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_sparse_roofline.lm` and
# `trainer.mfu_sparse.lm` until then
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "trainer.router_ms_per_step.lm",
         "trainer.experts_ms_per_step.lm", "trainer.head_ms_per_step.lm",
         "trainer.experts_roofline.lm", "trainer.experts_short_share.lm",
         "trainer.expert_load_max_over_mean.lm",
         "trainer.router_load_max_over_mean.lm", "table.adam_ms_per_step.lm",
         "table.adam_roofline.lm", "table.snapshot_ms_per_step.lm",
         "table.embed_rows_per_step.lm", "worker.ms_per_req.train",
         "server.ms_per_req.train", "server.dispatches_per_round.train",
         "client.wait_ms.train", "server.mailbox_wait_ms.train",
         "worker.mailbox_wait_ms.train", "table.device_ms_per_round.train",
         "table.gather_ms_per_round.train",
         "table.scatter_ms_per_round.train", "table.update_fast_share.train",
         "device.idle_share.train", "trainer.block_ms.train",
         "trainer.programs_built_in_window.train", "setup.table_init_s"]
# other masks' and families' readers: they must find nothing to read here
NOT_THIS_CELL = ["trainer.attn_full_ms_per_step.lm",
                 "trainer.attn_blockdiff_ms_per_step.lm",
                 "trainer.attn_mla_ms_per_step.lm",
                 "trainer.attn_gate_ms_per_step.lm"]
SELECTED = 2048 * 2049 // 2 + (16384 - 2048) * 2048     # a head, a layer
CAUSAL = 16384 * 16385 // 2


# -- the counting functions, by hand ------------------------------------------

def test_the_pairs():
    # four positions, two keys a query: 1 + 2 + 2 + 2 of 1 + 2 + 3 + 4
    assert sparseshapes.selected_pairs(4, 2) == 7
    assert sparseshapes.causal_pairs(4) == 10
    assert sparseshapes.selected_pairs(4, 8) == 10      # below topk: causal
    assert sparseshapes.selected_pairs(16384, 2048) == SELECTED == 31458304
    assert sparseshapes.causal_pairs(16384) == CAUSAL == 134225920
    assert 0.234 < SELECTED / CAUSAL < 0.235


def test_attention_flops_count_the_selected_pairs_three_passes():
    tiny = dict(SHAPES, seq_len=4, index_topk=2, heads=1, head_dim=1)
    assert sparseshapes.attention_flops(tiny) == 3 * 4 * 7
    assert sparseshapes.attention_flops(SHAPES) \
        == 3 * 4 * 128 * 32 * SELECTED


def test_indexer_flops():
    lanes = 16 * 64
    projections = 3 * 16384 * 2 * 2048 * (lanes + 64 + 16)
    scores = 2 * lanes * (CAUSAL + 2 * SELECTED)
    target = 2 * 128 * 32 * SELECTED
    assert sparseshapes.indexer_flops(SHAPES) == projections + scores + target
    # the forward scores of every causal pair are its largest part
    assert 2 * lanes * CAUSAL > 0.3 * sparseshapes.indexer_flops(SHAPES)
    assert 2 * lanes * CAUSAL > max(projections, target)


def test_step_flops():
    mean = 16384 * 8 * 16 // 128        # a layer's even share of assignments
    flops = sparseshapes.step_flops(1, 5 * mean, SHAPES)
    assert flops == (
        5 * (sparseshapes.attention_flops(SHAPES)
             + sparseshapes.indexer_flops(SHAPES))
        + lmshapes.dense_flops(16384, SHAPES)
        + lmshapes.expert_flops(5 * mean, 2048, 768))
    assert 22e12 < flops < 30e12
    assert sparseshapes.step_flops(2, 10 * mean, SHAPES) == 2 * flops


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


STEPS = 2
TRACE = {"window_s": 3.0, "programs": {}, "scopes": {
    "jit_forward": {"mv.lm.attn.sparse": 0.050,
                    "mv.lm.attn.sparse.kernel": 0.300,
                    "mv.lm.indexer": 0.060, "mv.lm.select": 0.100,
                    "mv.lm.router": 0.004, "mv.lm.experts": 0.200},
    "jit_backward": {
        "mv.lm.attn.sparse": 0.150, "mv.lm.attn.sparse.kernel": 0.900,
        "mv.lm.indexer": 0.080, "mv.lm.indexer.loss": 0.600,
        "mv.lm.select": 0.110, "mv.lm.experts": 0.400},
    "jit_head_step": {"mv.lm.head": 0.080}}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 5 * 16384)
WINDOW = _count(LM_STEP=12, LM_HELD_ASSIGNMENTS=12 * 5 * 16384,
                LM_TOKENS=12 * 16384, LM_SELECTED_PAIRS=12 * 5 * SELECTED,
                LM_CAUSAL_PAIRS=12 * 5 * CAUSAL,
                LM_SELECT_TILES_LIVE=12 * 5 * 500,
                LM_SELECT_TILES=12 * 5 * 528)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.0, traced),
        window=_Window(12, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.attn_sparse_ms_per_step.lm": 1400.0 / STEPS,
    "trainer.attn_roofline.lm":
        100 * STEPS * 5 * sparseshapes.attention_flops(SHAPES) / 197e12 / 1.2,
    "trainer.indexer_ms_per_step.lm": 740.0 / STEPS,
    "trainer.indexer_roofline.lm":
        100 * STEPS * 5 * sparseshapes.indexer_flops(SHAPES) / 197e12 / 0.95,
    "trainer.select_ms_per_step.lm": 210.0 / STEPS,
    "trainer.selected_share.lm": 100 * SELECTED / CAUSAL,
    "trainer.select_tiles_live_share.lm": 100 * 500 / 528,
    "trainer.mfu.lm":
        100 * sparseshapes.step_flops(12, 12 * 5 * 16384, SHAPES)
        / 197e12 / 20.0,
}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    value = _read(name, _obs())
    assert value == pytest.approx(WANT[name])
    if "roofline" in name or "mfu" in name:
        assert 0 < value < 100


def test_the_selected_share_is_twenty_three_in_a_hundred():
    assert _read("trainer.selected_share.lm", _obs()) \
        == pytest.approx(23.437, abs=1e-3)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too, and so do the other
    language-model cells: no scope, no counter, no shape of this
    selection, and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    if name in MERGED:
        return      # one reader for every cell: it reads sdar30b.ps-bd4k's too
    # sdar30b.ps-bd4k: the same block, its own scopes, counters and shapes
    other = {k: v for k, v in SHAPES.items() if not k.startswith("index_")}
    other.update(family="bd", block_length=4)
    blockdiff = {"window_s": 3.0, "programs": {}, "scopes": {"jit_forward": {
        "mv.lm.attn.blockdiff.kernel": 0.06, "mv.lm.experts": 0.2}}}
    counts = _count(LM_STEP=8, LM_HELD_ASSIGNMENTS=8 * 98304,
                    LM_TOKENS=8 * 8192)
    assert _read(name, _obs(trace=blockdiff, traced=counts, window=counts,
                            shapes=other)) is None


@pytest.mark.parametrize("name", NOT_THIS_CELL)
def test_the_other_families_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


# -- the entries, the configuration, the controls, the parent -----------------

@pytest.mark.parametrize("name", NEW + MERGED)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    # by membership: later cells were appended to these readers' lists
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] == "trainer"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-step-16k"
    entries.named(bench, "configs", CONFIG)
    for name in OLDER:
        kind = "end_to_end" if name in ("words_per_s", "peak_hbm_gb") \
            else "per_layer"
        assert CELL in entries.named(bench, kind, name)["workloads"], name
    for name in NOT_THIS_CELL:
        assert CELL not in entries.named(bench, "per_layer",
                                         name)["workloads"], name
    entries.check_cells(root, bench)
    with open(os.path.join(root, "benchmark", "traffic",
                           "lm-ps-step-16k.json")) as f:
        traffic = json.load(f)
    assert (traffic["seq_len"], traffic["sequences_per_step"],
            traffic["warm_steps"]) == (16384, 1, 2)
    assert traffic["token_distribution"]["exponent"] == 1.0


def test_the_configuration_holds_the_catalog_s_numbers(root):
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    published = {     # the catalog's `config`, every key
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "KeyeVL2",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "num_local_experts": 128,
        "rms_norm_eps": 1e-06,
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"},
        "rope_theta": 10000000,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "sliding_window": None, "tie_word_embeddings": False,
        "use_sliding_window": False, "vocab_size": 151936}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert "vision_tower" in config["not_held"]
    assert config["num_hidden_layers"] in (4, 5, 6)
    assert str(config["num_hidden_layers"]) in config["reduced"][
        "num_hidden_layers"] and config["size_that_ran"]
    assert (config["num_experts"], config["vocab_size"]) == (16, 18992)
    assert config["router_outputs"] == 128
    assert config["deployment"]["chips_per_layer"] == 8
    parts = config["parameters"]
    assert parts["total"] == SHAPES["parameters"] == (
        config["num_hidden_layers"] * (
            parts["per_layer_outside_experts"] + parts["per_layer_in_indexer"]
            + parts["per_layer_in_experts"])
        + parts["embedding_and_head"] + parts["final_norm"])
    assert {"indexer_form", "indexer_objective", "index_key_norm", "chunks",
            "qk_norm", "mrope_layout", "tie_rule", "optimizer",
            "init"} <= set(config["assumed"])
    assert entry["source"] == config["source"]
    assert {"loss", "loss.indexer", "gradient.table", "gradient.indexer",
            "gradient.scores", "adam.moments", "adam.update", "adds.extra",
            "selection.differs", "selection.inexact", "layer.output",
            "routing.differs"} <= set(config["limits"])
    assert "approximate" in config["guarantees"]["selection"]


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_sparse_controls.py", what,
         "--seconds", "0.2", "--seed", str(2 ** 31 + 7), "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_every_control_is_named_a_limit_of_the_configuration(root):
    assert set(controls.CHANGES) == set(controls.CAUGHT_BY) | {"none"}
    with open(os.path.join(root, "benchmark", "configs",
                           CONFIG + ".json")) as f:
        config = json.load(f)
    for limits in (config["limits"], config["rehearsal"]["limits"]):
        assert set(controls.CAUGHT_BY.values()) <= set(limits)


@pytest.mark.parametrize("what", sorted(controls.CAUGHT_BY))
def test_a_control_fails_the_limit_named_for_it(what, root, tmp_path):
    result = _control(root, what, tmp_path)
    assert result["correct"] is False
    caught = result["compared"][controls.CAUGHT_BY[what]]
    assert caught["value"] > caught["limit"]
    if what == "bfloat16_moments":      # whatever the model computed
        for name in ("loss", "loss.indexer", "gradient.table",
                     "gradient.indexer"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]


def test_the_unchanged_program_passes_beside_the_controls(root, tmp_path):
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    # the exact selection of the program's own index inputs is the program's
    assert result["compared"]["selection.inexact"]["value"] == 0
    assert {"loss", "loss.indexer", "gradient.table", "gradient.indexer",
            "gradient.scores", "adam.moments", "adam.update", "adds.extra",
            "selection.differs", "selection.differs.layer0",
            "selection.inexact", "selection.inexact.layer0",
            "layer.output", "layer.output.layer0",
            "routing.differs.layer0",
            "routing.held_share.layer0"} <= set(result["compared"])


def test_a_checkout_without_the_selection_fails_the_cell_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit
    code than 0 and no result line. The parent has the trainer but no
    ``models/lm/sparse.py``: the driver asks for it first."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    for name in ("sparse.py", "sparse_kernels.py"):
        os.remove(root / "multiverso_tpu" / "models" / "lm" / name)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483700", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=300)
    assert done.returncode not in (0, 124, 137)
    assert "ImportError" in done.stderr
    assert "mv.init" not in done.stdout and "jax backend" not in done.stdout
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]

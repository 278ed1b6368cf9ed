"""The block-diffusion cell `sdar30b.ps-bd4k`: its counting functions by
hand, its five readers on hand-built ``Observations``, its entries, its
configuration against the catalog's numbers, that its controls fail (on
the repo and on the copy a later PR appended to) and that a checkout
without the objective fails the cell at once. (Its rehearsal end to end
is test_rehearse.py's, which runs every cell of BENCHMARK.json.)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import bdshapes, lmshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "sdar30b.ps-bd4k"
CONFIG = "sdar-30b-a3b-l6"
SHAPES = {"family": "bd", "sequences": 2, "seq_len": 4096, "hidden": 2048,
          "heads": 32,
          "kv_heads": 4, "head_dim": 128, "router_outputs": 128, "held": 16,
          "expert_width": 768, "vocab": 18992, "layers": 6,
          "block_length": 4, "parameters": 645623296}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.attn_blockdiff_ms_per_step.lm", "trainer.masked_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_blockdiff_roofline.lm` and
# `trainer.mfu_blockdiff.lm` until then
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "trainer.router_ms_per_step.lm",
         "trainer.experts_ms_per_step.lm", "trainer.head_ms_per_step.lm",
         "trainer.experts_roofline.lm",
         "trainer.expert_load_max_over_mean.lm", "table.adam_ms_per_step.lm",
         "table.adam_roofline.lm", "table.snapshot_ms_per_step.lm",
         "table.embed_rows_per_step.lm", "worker.ms_per_req.train",
         "server.ms_per_req.train", "server.dispatches_per_round.train",
         "client.wait_ms.train", "server.mailbox_wait_ms.train",
         "worker.mailbox_wait_ms.train", "table.device_ms_per_round.train",
         "table.gather_ms_per_round.train",
         "table.scatter_ms_per_round.train", "table.update_fast_share.train",
         "device.idle_share.train", "trainer.block_ms.train",
         "trainer.programs_built_in_window.train", "setup.table_init_s"]
# causal and window pairs: they must find nothing to read here
NOT_THIS_CELL = ["trainer.attn_full_ms_per_step.lm",
                 "trainer.attn_window_ms_per_step.lm"]


# -- the counting functions, by hand ------------------------------------------

def test_attention_pairs_are_l_squared_plus_l_b():
    # L = 4, b = 2: the noised copy's two blocks see themselves (2 x 4
    # pairs) and block 1 the clean block 0 (2 x 2); the clean copy block 0
    # sees itself (4), block 1 both (8): 8 + 4 + 12 = 24 = 16 + 8
    assert bdshapes.attention_pairs(4, 2) == 24
    assert bdshapes.attention_pairs(4096, 4) == 4096 * 4096 + 4096 * 4
    # a quarter of the 4 L^2, and a little
    assert 0.25 < bdshapes.attention_pairs(4096, 4) / 8192 ** 2 < 0.2503


def test_attention_flops_count_unmasked_pairs_three_passes():
    assert bdshapes.attention_flops(1, 4, 1, 1, 2) == 3 * 4 * 24
    assert bdshapes.attention_flops(2, 4096, 32, 128, 4) \
        == 3 * 4 * 128 * 32 * 2 * (4096 * 4096 + 4096 * 4)


def test_dense_flops_take_both_copies_through_the_layers_and_one_to_the_head():
    q, kv = 32 * 128, 4 * 128
    layer = 2 * 2048 * (2 * q + 2 * kv + 128)
    assert bdshapes.dense_flops(SHAPES) == 3 * 8192 * (
        2 * 6 * layer + 2 * 2048 * 18992)


def test_step_flops():
    # a step's mean load: 16384 positions x 8 / 128 experts x 16 held, a layer
    mean = 16384 * 8 * 16 // 128
    assert mean == 16384
    flops = bdshapes.step_flops(1, 6 * mean, SHAPES)
    assert flops == (6 * bdshapes.attention_flops(2, 4096, 32, 128, 4)
                     + bdshapes.dense_flops(SHAPES)
                     + lmshapes.expert_flops(6 * mean, 2048, 768))
    assert 24e12 < flops < 28e12      # 25.9 TFLOP a step
    assert bdshapes.step_flops(2, 12 * mean, SHAPES) == 2 * flops


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


STEPS = 5
TRACE = {"window_s": 3.0, "programs": {}, "scopes": {
    "jit_prepare": {"mv.lm.noise": 0.002, "mv.lm.embed": 0.001},
    "jit_forward": {"mv.lm.attn.blockdiff": 0.100,
                    "mv.lm.attn.blockdiff.kernel": 0.300,
                    "mv.lm.router": 0.004, "mv.lm.experts": 0.400},
    "jit_backward": {"mv.lm.attn.blockdiff": 0.200,
                     "mv.lm.attn.blockdiff.kernel": 0.900,
                     "mv.lm.router": 0.008, "mv.lm.experts": 0.900},
    "jit_head_step": {"mv.lm.head": 0.080}}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 6 * 16384)
WINDOW = _count(LM_STEP=30, LM_HELD_ASSIGNMENTS=30 * 6 * 16384,
                LM_TOKENS=30 * 8192, LM_MASKED_TOKENS=30 * 4100,
                LM_POSITIONS=30 * 16384)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.0, traced),
        window=_Window(30, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.attn_blockdiff_ms_per_step.lm": 1500.0 / STEPS,
    "trainer.attn_roofline.lm":
        100 * STEPS * 6 * bdshapes.attention_flops(2, 4096, 32, 128, 4)
        / 197e12 / 1.2,
    "trainer.masked_share.lm": 100 * 4100 / 8192,
    "trainer.mfu.lm":
        100 * bdshapes.step_flops(30, 30 * 6 * 16384, SHAPES) / 197e12 / 20.0,
}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    value = _read(name, _obs())
    assert value == pytest.approx(WANT[name])
    if "roofline" in name or "mfu" in name:
        assert 0 < value < 100


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too, and so does the other
    language-model cell: no scope, no counter, no shape of this
    objective, and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    # st21b.ps-8k: the trainer's older counters and scopes, its own shapes
    if name in MERGED:
        return      # one reader for both cells: it reads st21b.ps-8k's too
    other = dict(SHAPES, family="lm", window=4096, window_layout=[0, 1, 1, 1])
    other.pop("block_length")
    causal = {"window_s": 3.0, "programs": {}, "scopes": {"jit_forward": {
        "mv.lm.attn.full.kernel": 0.06, "mv.lm.experts": 0.2}}}
    counts = _count(LM_STEP=8, LM_HELD_ASSIGNMENTS=8 * 98304,
                    LM_TOKENS=8 * 16384)
    assert _read(name, _obs(trace=causal, traced=counts, window=counts,
                            shapes=other)) is None


@pytest.mark.parametrize("name", NOT_THIS_CELL)
def test_the_causal_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


# -- the entries, the configuration, the controls, the parent -----------------

@pytest.mark.parametrize("name", NEW + MERGED)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    # by membership: a later cell may be appended to any reader's list
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] == "trainer"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-blockdiff-4k"
    entries.named(bench, "configs", CONFIG)
    for name in OLDER:
        kind = "end_to_end" if name in ("words_per_s", "peak_hbm_gb") \
            else "per_layer"
        assert CELL in entries.named(bench, kind, name)["workloads"], name
    for name in NOT_THIS_CELL:
        assert CELL not in entries.named(bench, "per_layer",
                                         name)["workloads"], name
    entries.check_cells(root, bench)


def test_the_configuration_holds_the_catalog_s_numbers(root):
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    published = {     # the catalog's `config`, every key
        "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 32768, "max_window_layers": 48,
        "mlp_only_layers": [], "model_type": "sdar_moe",
        "moe_intermediate_size": 768, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    assert config["router_outputs"] == 128
    assert config["deployment"]["chips_per_layer"] == 8
    assert config["parameters"]["total"] == SHAPES["parameters"] == (
        6 * (config["parameters"]["per_layer_outside_experts"]
             + config["parameters"]["per_layer_in_experts"])
        + config["parameters"]["embedding_and_head"]
        + config["parameters"]["final_norm"])
    assert {"block_length", "noise_schedule", "prediction", "qk_norm",
            "mask_token", "optimizer", "init"} <= set(config["assumed"])
    assert config["objective"]["block_length"] == 4
    assert config["objective"]["t_min"] == 0.001
    assert entry["source"] == config["source"]
    assert {"loss", "gradient.table", "gradient.gate", "adam.moments",
            "adam.update"} <= set(config["limits"])


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_bd_controls.py", what,
         "--seconds", "0.2", "--seed", str(2 ** 31 + 7), "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=600)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


CAUGHT_BY = {"causal_mask": "gradient.table",
             "shifted_positions": "gradient.table",
             "unweighted_loss": "loss", "no_qk_norm": "gradient.table",
             "float8_experts": "gradient.router",
             "bfloat16_moments": "adam.moments"}


@pytest.mark.parametrize("what", sorted(CAUGHT_BY))
def test_a_control_fails_a_limit(what, root, tmp_path):
    """Each control, in the rehearsal's tiny twin, is outside at least
    the limit named for it; on the repo and on the appended copy."""
    result = _control(root, what, tmp_path)
    assert result["correct"] is False
    caught = result["compared"][CAUGHT_BY[what]]
    assert caught["value"] > caught["limit"]
    if what == "bfloat16_moments":      # whatever the model computed
        for name in ("loss", "gradient.table", "gradient.gate"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]


def test_the_unchanged_program_passes_beside_the_controls(root, tmp_path):
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.gate", "adam.moments",
            "adam.update", "routing.differs.layer0",
            "routing.held_share.layer0"} <= set(result["compared"])


def test_a_checkout_without_the_objective_fails_the_cell_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit
    code than 0 and no result line. The parent has the trainer but no
    block diffusion: ``model.noise`` is what the driver asks for first."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    model = root / "multiverso_tpu" / "models" / "lm" / "model.py"
    text = model.read_text()
    assert "\ndef noise(" in text
    model.write_text(text.replace("\ndef noise(", "\ndef _no_noise("))
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

"""The cell `xing29b.ps-4k`: its counting functions by hand, its eight
readers on hand-built ``Observations``, its entries by name, its
configuration against the catalog's numbers, that each control fails the
limit named for it (on the repo and on the copy a later PR appended to)
and that a checkout without the streams fails the cell at once. (Its
rehearsal end to end is test_rehearse.py's, which runs every cell of
BENCHMARK.json.)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import lmshapes, mlashapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_mla_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "xing29b.ps-4k"
CONFIG = "xing4-29b-a4b-l5"
with open(os.path.join(ROOT, "benchmark", "configs", f"{CONFIG}.json")) as f:
    MODULES = int(json.load(f)["num_nextn_predict_layers"])
SHAPES = {"family": "mla", "sequences": 2, "seq_len": 4096, "hidden": 3584,
          "heads_held": 4,
          "qk_dim": 192, "v_dim": 128, "q_rank": 768, "kv_rank": 512,
          "rope_dim": 64, "router_outputs": 64, "top_k": 4, "held": 8,
          "expert_width": 1024, "shared_width": 1024, "dense_width": 9216,
          "vocab": 16384, "layers": 4 + MODULES, "sparse_layers": 4,
          "dense_layers": 1, "modules": MODULES, "streams": 4,
          "parameters": 656127246 + MODULES * 133483382}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.attn_mla_ms_per_step.lm",
       "trainer.hc_ms_per_step.lm", "trainer.hc_roofline.lm",
       "trainer.shared_expert_ms_per_step.lm", "trainer.mtp_ms_per_step.lm",
       "trainer.router_load_max_over_mean.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_mla_roofline.lm` and
# `trainer.mfu_mla.lm` until then
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "trainer.router_ms_per_step.lm",
         "trainer.experts_ms_per_step.lm", "trainer.head_ms_per_step.lm",
         "trainer.experts_roofline.lm",
         "trainer.expert_load_max_over_mean.lm", "table.adam_ms_per_step.lm",
         "table.adam_roofline.lm", "table.snapshot_ms_per_step.lm",
         "table.embed_rows_per_step.lm", "worker.ms_per_req.train",
         "server.ms_per_req.train", "server.dispatches_per_round.train",
         "client.wait_ms.train", "client.issue_ms_per_round.train",
         "client.wake_ms.train", "server.dispatch_ms.train",
         "server.mailbox_wait_ms.train", "worker.mailbox_wait_ms.train",
         "table.device_ms_per_round.train", "table.gather_ms_per_round.train",
         "table.scatter_ms_per_round.train", "table.update_fast_share.train",
         "device.idle_share.train", "trainer.block_ms.train",
         "trainer.programs_built_in_window.train", "setup.table_init_s"]
# causal-with-a-window and block-diffusion counts: nothing to read here
NOT_THIS_CELL = ["trainer.attn_full_ms_per_step.lm",
                 "trainer.attn_window_ms_per_step.lm",
                 "trainer.attn_blockdiff_ms_per_step.lm",
                 "trainer.masked_share.lm"]
TOKENS = 2 * 4096
BLOCKS = 5 + MODULES


# -- the counting functions, by hand ------------------------------------------

def test_attention_counts_causal_pairs_at_the_published_lanes():
    pairs = 4096 * 4097 // 2
    assert mlashapes.attention_flops(SHAPES) \
        == 3 * 2 * (192 + 128) * 4 * 2 * pairs
    tiny = dict(SHAPES, seq_len=4, sequences=1, heads_held=1)
    assert mlashapes.attention_flops(tiny) == 3 * 2 * 320 * 10


def test_the_latent_projections_a_token():
    want = 2 * (3584 * 768 + 768 * 4 * 192 + 3584 * (512 + 64)
                + 512 * 4 * (128 + 128) + 4 * 128 * 3584)
    assert mlashapes.attention_dense_flops(SHAPES) == want
    assert mlashapes.mixer_flops(SHAPES) == 2 * 4 * 3584 * 24


def test_token_flops_take_every_block_and_both_head_passes():
    every = mlashapes.attention_dense_flops(SHAPES) \
        + 2 * mlashapes.mixer_flops(SHAPES)
    sparse = 2 * 3584 * 64 + 6 * 3584 * 1024
    want = (BLOCKS * every + 6 * 3584 * 9216 + (4 + MODULES) * sparse
            + MODULES * 4 * 3584 * 3584 + (1 + MODULES) * 2 * 3584 * 16384)
    assert mlashapes.token_flops(SHAPES) == want
    assert mlashapes.blocks(SHAPES) == BLOCKS


def test_step_flops():
    # a step's mean load: 8192 tokens x 4 / 64 experts x 8 held, a layer
    mean = TOKENS * 4 * 8 // 64
    assert mean == 4096
    layers = 4 + MODULES
    flops = mlashapes.step_flops(1, layers * mean, SHAPES)
    assert flops == (BLOCKS * mlashapes.attention_flops(SHAPES)
                     + 3 * TOKENS * mlashapes.token_flops(SHAPES)
                     + lmshapes.expert_flops(layers * mean, 3584, 1024))
    assert (15e12 if MODULES else 12e12) < flops < 21e12
    assert mlashapes.step_flops(2, 2 * layers * mean, SHAPES) == 2 * flops


def test_stream_bytes_are_one_read_and_one_write_a_sublayer_a_pass():
    tensor = TOKENS * 4 * 3584 * 4
    assert mlashapes.stream_bytes(1, SHAPES) == BLOCKS * 2 * 3 * 2 * tensor
    assert mlashapes.stream_bytes(3, SHAPES) \
        == 3 * mlashapes.stream_bytes(1, SHAPES)


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
    "jit_forward_streams": {
        "mv.lm.attn.mla": 0.050, "mv.lm.attn.mla.kernel": 0.040,
        "mv.lm.hc": 0.200, "mv.lm.router": 0.004, "mv.lm.experts": 0.150,
        "mv.lm.shared_expert": 0.030, "mv.lm.dense_mlp": 0.020},
    "jit_backward_streams": {
        "mv.lm.attn.mla": 0.150, "mv.lm.attn.mla.kernel": 0.110,
        "mv.lm.hc": 0.600, "mv.lm.router": 0.008, "mv.lm.experts": 0.400,
        "mv.lm.shared_expert": 0.090, "mv.lm.dense_mlp": 0.060},
    "jit_mtp_forward": {"mv.lm.mtp": 0.010, "mv.lm.hc": 0.040,
                        "mv.lm.attn.mla.kernel": 0.010},
    "jit_mtp_head": {"mv.lm.mtp.head": 0.060},
    "jit_mtp_backward": {"mv.lm.mtp": 0.030, "mv.lm.hc": 0.120,
                         "mv.lm.attn.mla.kernel": 0.030, "no-scope": 0.010},
    "jit_head_step": {"mv.lm.head": 0.060}}}
LAYERS = 4 + MODULES
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * LAYERS * 4096)
WINDOW = _count(LM_STEP=30, LM_HELD_ASSIGNMENTS=30 * LAYERS * 4096,
                LM_TOKENS=30 * TOKENS, LM_ROUTER_LOAD_MAX=30 * LAYERS * 1280)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.0, traced),
        window=_Window(30, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.attn_mla_ms_per_step.lm": 390.0 / STEPS,
    "trainer.attn_roofline.lm":
        100 * STEPS * BLOCKS * mlashapes.attention_flops(SHAPES)
        / 197e12 / 0.190,
    "trainer.hc_ms_per_step.lm": 960.0 / STEPS,
    "trainer.hc_roofline.lm":
        100 * mlashapes.stream_bytes(STEPS, SHAPES) / 819e9 / 0.960,
    "trainer.shared_expert_ms_per_step.lm": 200.0 / STEPS,
    "trainer.mtp_ms_per_step.lm": 310.0 / STEPS,
    "trainer.router_load_max_over_mean.lm": 1280 / 512,
    "trainer.mfu.lm":
        100 * mlashapes.step_flops(30, 30 * LAYERS * 4096, SHAPES)
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


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too, and so do the other
    language-model cells: no scope, no counter, no shape of this model,
    and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    if name in MERGED:
        return      # one reader for every cell: it reads st21b.ps-8k's too
    # st21b.ps-8k: the trainer's older counters and scopes, its own shapes
    other = {"family": "lm", "sequences": 2, "seq_len": 8192, "hidden": 2560,
             "heads": 28,
             "kv_heads": 4, "head_dim": 128, "router_outputs": 64, "held": 16,
             "expert_width": 768, "vocab": 37984, "layers": 4, "window": 4096,
             "window_layout": [0, 1, 1, 1], "parameters": 656500000}
    causal = {"window_s": 3.0, "programs": {}, "scopes": {"jit_forward": {
        "mv.lm.attn.full.kernel": 0.06, "mv.lm.experts": 0.2}}}
    counts = _count(LM_STEP=8, LM_HELD_ASSIGNMENTS=8 * 98304,
                    LM_TOKENS=8 * 16384)
    assert _read(name, _obs(trace=causal, traced=counts, window=counts,
                            shapes=other)) is None


def test_the_module_s_reader_reads_zero_on_a_rank_without_the_module():
    """The cell's rank holds no module (`modules` 0) and runs none of its
    programs: 0 ms a step, a number. Shapes that claim a module whose
    programs did not run read nothing."""
    trace = dict(TRACE, scopes={k: v for k, v in TRACE["scopes"].items()
                                if "mtp" not in k})
    without = dict(SHAPES, modules=0)
    assert _read("trainer.mtp_ms_per_step.lm",
                 _obs(trace=trace, shapes=without)) == 0.0
    assert _read("trainer.mtp_ms_per_step.lm",
                 _obs(trace=trace, shapes=dict(SHAPES, modules=1))) is None


@pytest.mark.parametrize("name", NOT_THIS_CELL)
def test_the_other_models_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


def test_the_shared_readers_count_this_cell_s_layers():
    """`layers` is the layers with routed experts (the module's among
    them), which is what the experts' least bytes count by; the load over
    the held experts reads the counters alone."""
    took = TRACE["scopes"]["jit_forward_streams"]["mv.lm.experts"] \
        + TRACE["scopes"]["jit_backward_streams"]["mv.lm.experts"]
    least = max(
        lmshapes.expert_flops(STEPS * LAYERS * 4096, 3584, 1024) / 197e12,
        lmshapes.expert_bytes(STEPS, STEPS * LAYERS * 4096, SHAPES) / 819e9)
    assert _read("trainer.experts_roofline.lm", _obs()) \
        == pytest.approx(100 * least / took)
    assert lmshapes.expert_bytes(1, 0, SHAPES) \
        == LAYERS * 8 * 3 * 3584 * 1024 * 10
    assert _read("trainer.head_ms_per_step.lm", _obs()) \
        == pytest.approx(60.0 / STEPS)    # the main pass alone


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
    assert cell["traffic"] == "lm-ps-step-4k"
    entries.named(bench, "configs", CONFIG)
    for name in OLDER:
        kind = "end_to_end" if name in ("words_per_s", "peak_hbm_gb") \
            else "per_layer"
        assert CELL in entries.named(bench, kind, name)["workloads"], name
    for name in NOT_THIS_CELL:
        assert CELL not in entries.named(bench, "per_layer",
                                         name)["workloads"], name
    entries.check_cells(root, bench)
    entries.check_all(root)


def test_the_configuration_holds_the_catalog_s_numbers(root):
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    published = {     # the catalog's `config`, every key
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:
            assert config[key] == value, key
    cuts = ["first_k_dense_replace", "n_routed_experts",
            "num_attention_heads", "num_hidden_layers", "vocab_size"] \
        + ([] if MODULES else ["num_nextn_predict_layers"])
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == sorted(cuts)
    assert (config["num_hidden_layers"], config["first_k_dense_replace"],
            config["n_routed_experts"], config["num_attention_heads"],
            config["vocab_size"]) == (5, 1, 8, 4, 16384)
    assert config["router_outputs"] == 64 and config["attention_heads"] == 32
    assert config["deployment"]["chips_per_layer"] == 8
    sizes = config["parameters"]
    assert sizes["total"] == SHAPES["parameters"] == (
        sizes["dense_layer"] + 4 * sizes["sparse_layer"]
        + MODULES * sizes["module"] + sizes["embedding_and_head"]
        + sizes["final_norm"])
    assert {"streams_sum", "mixer_form", "mixer_init", "router_bias_rate",
            "mtp_loss_weight", "rotary_pairs", "optimizer",
            "init"} <= set(config["assumed"])
    assert config["router_bias_rate"] == 0.001
    assert config["mtp_loss_weight"] == 0.3
    assert entry["source"] == config["source"]
    assert set(controls.CAUGHT_BY.values()) | {
        "loss", "gradient.gate", "gradient.router", "gradient.mixer",
        "adam.update", "bias.differs"} <= set(config["limits"])


def test_the_program_builds_the_published_model_from_the_file(root):
    from multiverso_tpu.models.lm import LMConfig
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    config.pop("rehearsal")
    cfg = LMConfig.from_dict(config)
    assert cfg.parameters() == config["parameters"]["total"]
    assert (cfg.hidden, cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_dim,
            cfg.qk_rope_dim, cfg.v_head_dim, cfg.dense_width,
            cfg.expert_width, cfg.shared_width, cfg.n_experts, cfg.top_k,
            cfg.hc_mult, cfg.hc_iters) == (3584, 768, 512, 128, 64, 128,
                                           9216, 1024, 1024, 64, 4, 4, 20)
    assert cfg.ffn_layout == (0, 1, 1, 1, 1) and cfg.mtp_layers == MODULES
    assert cfg.heads_held == (0, 4) and cfg.experts_held == (0, 8)
    tables = 3 + len(cfg.layer_shapes(0)) + (4 + MODULES) * len(
        cfg.layer_shapes(1)) + MODULES * len(cfg.mtp_shapes())
    assert tables == config["parameters"]["tables"]


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_mla_controls.py", what,
         "--seconds", "0.2", "--seed", str(2 ** 31 + 7), "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=900)
    assert done.returncode == 0, done.stderr[-3000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("what", sorted(controls.CAUGHT_BY))
def test_a_control_fails_the_limit_named_for_it(what, root, tmp_path):
    """Each control, in the rehearsal's tiny twin, is outside at least
    the limit named for it; on the repo and on the appended copy."""
    result = _control(root, what, tmp_path)
    assert result["correct"] is False
    caught = result["compared"][controls.CAUGHT_BY[what]]
    assert caught["value"] > caught["limit"]
    if what == "bfloat16_moments":      # whatever the model computed
        for name in ("loss", "gradient.table", "gradient.gate"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]


def test_the_unchanged_program_passes_beside_the_controls(root, tmp_path):
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.gate", "gradient.scores",
            "gradient.mixer", "gradient.router", "adam.moments",
            "adam.update", "bias.differs", "adds.extra", "routing.differs",
            "routing.held_share.layer0"} <= set(result["compared"])


def test_a_checkout_without_the_streams_fails_the_cell_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit
    code than 0 and no result line. The parent has the trainer but no
    streams module: it is what the driver asks for first."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    (root / "multiverso_tpu" / "models" / "lm" / "streams.py").unlink()
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

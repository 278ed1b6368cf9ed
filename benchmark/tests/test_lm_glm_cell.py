"""The cell `glm30b.ps-8k`: its four readers on hand-built ``Observations``
(a reduced trace as benchmark/lib/xplane.py leaves it), the older readers'
counts at this cell's shapes, its entries by name, its configuration against
the catalog's numbers, its rehearsal, that each control fails the limit named
for it (on the repo and on the copy a later PR appended to) and that a
checkout which cannot describe the model fails the cell at once."""

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
from benchmark.tools import lm_glm_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "glm30b.ps-8k"
CONFIG = "glm47-flash-30b-a3b-l5"
SHAPES = {"family": "mla", "sequences": 2, "seq_len": 8192, "hidden": 2048,
          "heads_held": 20,
          "qk_dim": 256, "v_dim": 256, "q_rank": 768, "kv_rank": 512,
          "rope_dim": 64, "router_outputs": 64, "top_k": 4, "held": 8,
          "expert_width": 1536, "shared_width": 1536, "dense_width": 10240,
          "vocab": 19360, "layers": 5, "sparse_layers": 4, "dense_layers": 1,
          "modules": 1, "streams": 0, "parameters": 706518848}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.mtp_head_ms_per_step.lm", "trainer.mtp_step_share.lm",
       "trainer.mtp_dispatch_ms_per_step.lm",
       "trainer.mtp_positions_share.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "trainer.attn_mla_ms_per_step.lm",
         "trainer.attn_roofline.lm", "trainer.mtp_ms_per_step.lm",
         "trainer.mfu.lm", "trainer.shared_expert_ms_per_step.lm",
         "trainer.router_load_max_over_mean.lm",
         "trainer.router_ms_per_step.lm", "trainer.experts_ms_per_step.lm",
         "trainer.head_ms_per_step.lm", "trainer.experts_roofline.lm",
         "trainer.experts_short_share.lm",
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
         "trainer.programs_built_in_window.train",
         "host.stall_ms_per_s.train", "host.frozen_ms_per_s.train",
         "host.beat_late_ms.train"]
# the streams', other attentions' and block diffusion's: nothing to read
NOT_THIS_CELL = ["trainer.hc_ms_per_step.lm", "trainer.hc_roofline.lm",
                 "trainer.attn_full_ms_per_step.lm",
                 "trainer.attn_window_ms_per_step.lm",
                 "trainer.attn_blockdiff_ms_per_step.lm",
                 "trainer.attn_pass_fused_share.lm",
                 "trainer.attn_kda_ms_per_step.lm",
                 "trainer.attn_sparse_ms_per_step.lm",
                 "trainer.attn_gate_ms_per_step.lm"]
TOKENS = 2 * 8192
BLOCKS = 6


# -- the older counting functions at this cell's shapes, by hand ---------------

def test_attention_counts_causal_pairs_at_256_beside_256_lanes():
    pairs = 8192 * 8193 // 2
    assert mlashapes.attention_flops(SHAPES) \
        == 3 * 2 * (256 + 256) * 20 * 2 * pairs
    # forward, a token a layer: the issue's 83.9 MFLOP
    assert mlashapes.attention_flops(SHAPES) / (3 * TOKENS) \
        == pytest.approx(83.9e6, rel=1e-3)


def test_no_streams_no_mixer_and_six_blocks():
    assert mlashapes.mixer_flops(SHAPES) == 0
    assert mlashapes.blocks(SHAPES) == BLOCKS
    want = 2 * (2048 * 768 + 768 * 20 * 256 + 2048 * (512 + 64)
                + 512 * 20 * (192 + 256) + 20 * 256 * 2048)
    assert mlashapes.attention_dense_flops(SHAPES) == want == 2 * 21757952
    sparse = 2 * 2048 * 64 + 6 * 2048 * 1536
    assert mlashapes.token_flops(SHAPES) == (
        BLOCKS * want + 6 * 2048 * 10240 + 5 * sparse + 4 * 2048 * 2048
        + 2 * 2 * 2048 * 19360)


def test_step_flops_at_an_even_load():
    mean = TOKENS * 4 * 8 // 64     # assignments on held experts a layer
    assert mean == 8192
    flops = mlashapes.step_flops(1, 5 * mean, SHAPES)
    assert flops == (BLOCKS * mlashapes.attention_flops(SHAPES)
                     + 3 * TOKENS * mlashapes.token_flops(SHAPES)
                     + lmshapes.expert_flops(5 * mean, 2048, 1536))
    assert 50e12 < flops < 65e12    # the issue's ~59 TFLOP a step
    # attention over the causal pairs is most of a step's operations
    assert BLOCKS * mlashapes.attention_flops(SHAPES) > 0.4 * flops


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(ms=None, **kw):
    return {name: {"count": n, "ms": (ms or {}).get(name, 0.0)}
            for name, n in kw.items()}


STEPS = 4
# a reduced trace of four steps (xplane.reduce): seconds a program, and a
# program's seconds by scope
SCOPES = {
    "jit_forward": {
        "mv.lm.attn.mla": 0.165, "mv.lm.attn.mla.kernel": 0.236,
        "mv.lm.router": 0.005, "mv.lm.experts": 0.060,
        "mv.lm.shared_expert": 0.050, "mv.lm.dense_mlp": 0.040},
    "jit_backward": {
        "mv.lm.attn.mla": 0.464, "mv.lm.attn.mla.kernel": 0.916,
        "mv.lm.router": 0.012, "mv.lm.experts": 0.165,
        "mv.lm.shared_expert": 0.110, "mv.lm.dense_mlp": 0.122,
        "no-scope": 0.196},
    "jit_mtp_forward": {"mv.lm.mtp": 0.010, "mv.lm.attn.mla": 0.033,
                        "mv.lm.attn.mla.kernel": 0.047,
                        "mv.lm.experts": 0.012},
    "jit_mtp_head": {"mv.lm.mtp.head": 0.115},
    "jit_mtp_backward": {"mv.lm.mtp": 0.030, "mv.lm.attn.mla": 0.093,
                         "mv.lm.attn.mla.kernel": 0.183,
                         "mv.lm.experts": 0.033, "no-scope": 0.040},
    "jit_head_step": {"mv.lm.head": 0.115},
    "jit_update": {"mv.update.rule": 0.150}}
TRACE = {"window_s": 3.9, "scopes": SCOPES,
         "programs": {stem: {"seconds": sum(by.values()), "count": 4}
                      for stem, by in SCOPES.items()}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 5 * 8192)
WINDOW = _count(
    ms={"LM_MTP_STEP": 860.0, "LM_STEP": 20000.0},
    LM_STEP=22, LM_MTP_STEP=22, LM_TOKENS=22 * TOKENS,
    LM_MTP_TOKENS=22 * TOKENS, LM_HELD_ASSIGNMENTS=22 * 5 * 8192,
    LM_ROUTER_LOAD_MAX=22 * 5 * 2560)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.9, traced),
        window=_Window(22, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


MODULE_S = sum(sum(SCOPES[stem].values()) for stem in (
    "jit_mtp_forward", "jit_mtp_head", "jit_mtp_backward"))
WANT = {
    "trainer.mtp_head_ms_per_step.lm": 115.0 / STEPS,
    "trainer.mtp_step_share.lm":
        100 * MODULE_S / sum(sum(by.values()) for by in SCOPES.values()),
    "trainer.mtp_dispatch_ms_per_step.lm": 860.0 / 22,
    "trainer.mtp_positions_share.lm": 100.0}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW)


@pytest.mark.parametrize("name", NEW)
def test_reader(name):
    assert _read(name, _obs()) == pytest.approx(WANT[name])


def test_the_module_s_share_is_its_three_programs_of_all():
    share = _read("trainer.mtp_step_share.lm", _obs())
    assert 100 / 6 * 0.8 < share < 100 / 6 * 1.3   # one block of six
    assert share == pytest.approx(
        100 * _read("trainer.mtp_ms_per_step.lm", _obs()) * STEPS / 1e3
        / sum(p["seconds"] for p in TRACE["programs"].values()))


def test_a_module_that_skipped_positions_reads_under_100():
    window = dict(WINDOW, LM_MTP_TOKENS={"count": 11 * TOKENS, "ms": 0.0})
    assert _read("trainer.mtp_positions_share.lm",
                 _obs(window=window)) == pytest.approx(50.0)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too, and so could another cell: no
    module's program, no module's scope, no module's counter, and no
    exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    # xing29b.ps-4k: the same family's trainer on a rank without the module
    without = {"window_s": 3.0, "programs": {
        "jit_forward_streams": {"seconds": 0.6, "count": 10}},
        "scopes": {"jit_forward_streams": {"mv.lm.attn.mla": 0.05,
                                           "mv.lm.hc": 0.2}}}
    counts = _count(LM_STEP=8, LM_TOKENS=8 * 8192,
                    LM_HELD_ASSIGNMENTS=8 * 4 * 4096)
    assert _read(name, _obs(trace=without, traced=counts, window=counts,
                            shapes=dict(SHAPES, modules=0))) is None


@pytest.mark.parametrize("name", NOT_THIS_CELL)
def test_the_other_models_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


def test_the_shared_readers_count_this_cell():
    """The older readers at this cell's shapes: the kernel's share of the
    bf16 peak over six blocks at 256 | 256 lanes, the module's three
    programs, both attention scopes in every program, the main head's pass
    alone under ``mv.lm.head``."""
    kernel = 0.236 + 0.916 + 0.047 + 0.183
    assert _read("trainer.attn_roofline.lm", _obs()) == pytest.approx(
        100 * STEPS * BLOCKS * mlashapes.attention_flops(SHAPES) / 197e12
        / kernel)
    assert 0 < _read("trainer.attn_roofline.lm", _obs()) < 100
    assert _read("trainer.attn_mla_ms_per_step.lm", _obs()) \
        == pytest.approx(1e3 * (kernel + 0.165 + 0.464 + 0.033 + 0.093)
                         / STEPS)
    assert _read("trainer.mtp_ms_per_step.lm", _obs()) \
        == pytest.approx(1e3 * MODULE_S / STEPS)
    assert _read("trainer.head_ms_per_step.lm", _obs()) \
        == pytest.approx(115.0 / STEPS)
    assert _read("trainer.mfu.lm", _obs()) == pytest.approx(
        100 * mlashapes.step_flops(*(WINDOW[c]["count"]
                                     for c in mlashapes.COUNTERS), SHAPES)
        / 197e12 / _obs().window.seconds)
    assert 0 < _read("trainer.mfu.lm", _obs()) < 100
    assert _read("trainer.router_load_max_over_mean.lm", _obs()) \
        == pytest.approx(2560 / 1024)
    assert lmshapes.expert_bytes(1, 0, SHAPES) \
        == 5 * 8 * 3 * 2048 * 1536 * 10


# -- the entries, the configuration, the controls, the parent -----------------

@pytest.mark.parametrize("name", NEW)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] == "trainer"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-step-8k"
    entry = entries.named(bench, "configs", CONFIG)
    assert sorted(entry["reduced"]) == [
        "n_routed_experts", "num_hidden_layers", "vocab_size"]
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
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    # each cut at the guide's floor; the module held
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (5, 8, 154880 // 8)
    assert config["num_nextn_predict_layers"] == 1
    assert config["router_outputs"] == 64
    assert config["deployment"]["chips_per_layer"] == 8
    sizes = config["parameters"]
    assert sizes["total"] == SHAPES["parameters"] == (
        sizes["dense_layer"] + 4 * sizes["sparse_layer"] + sizes["module"]
        + sizes["embedding_and_head"] + sizes["final_norm"])
    assert {"scoring_func", "router_bias_rate", "mtp_loss_weight", "module",
            "rotary_pairs", "sequence_and_batch", "optimizer",
            "init"} <= set(config["assumed"])
    assert config["router_bias_rate"] == 0.001
    assert config["mtp_loss_weight"] == 0.3
    assert entry["source"] == config["source"]
    assert set(controls.CAUGHT_BY.values()) | {
        "loss", "gradient.gate", "adam.update", "bias.differs",
        "layer.output"} <= set(config["limits"])
    assert set(config["limits"]) == set(config["rehearsal"]["limits"])


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
            cfg.n_heads_held) == (2048, 768, 512, 192, 64, 256, 10240, 1536,
                                  1536, 64, 4, 20)
    assert cfg.residual == "plain" and cfg.yarn == () and cfg.mtp_layers == 1
    assert cfg.ffn_layout == (0, 1, 1, 1, 1) and cfg.experts_held == (0, 8)
    tables = 3 + len(cfg.layer_shapes(0)) + 5 * len(cfg.layer_shapes(1)) \
        + len(cfg.mtp_shapes())
    assert tables == config["parameters"]["tables"]


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_glm_controls.py", what,
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
        for name in ("loss", "gradient.table", "gradient.gate",
                     "layer.output"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]


def test_the_rehearsal_passes_beside_the_controls(root, tmp_path):
    """The driver's rehearsal on the CPU, end to end with the module held:
    `correct`, every limit compared."""
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.gate", "gradient.scores",
            "gradient.router", "adam.moments", "adam.update", "bias.differs",
            "adds.extra", "routing.differs", "layer.output",
            "routing.differs.layer1", "layer.output.layer1",
            "routing.held_share.layer0"} <= set(result["compared"])


def test_a_checkout_that_cannot_describe_the_model_fails_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit code
    than 0 and no result line. The parent's ``_from_mla`` demands a
    ``scoring_func`` and YaRN: the configuration cannot be built, and the
    driver builds it before ``mv.init``."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    model = root / "multiverso_tpu" / "models" / "lm" / "model.py"
    text = model.read_text()
    mine = 'c.get("scoring_func", "sigmoid") == "sigmoid"'
    assert mine in text
    model.write_text(text.replace(mine, 'c["scoring_func"] == "sigmoid"'))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483700", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=300)
    assert done.returncode not in (0, 124, 137)
    assert "KeyError" in done.stderr
    assert "mv.init" not in done.stdout and "jax backend" not in done.stdout
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]

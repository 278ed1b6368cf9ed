"""The cell `lfm8b.ps-8k`: its six readers on hand-built ``Observations`` (a
reduced trace as benchmark/lib/xplane.py leaves it), the counting functions
at this cell's shapes by hand (BY LAYER KIND: six convolution layers, two of
attention at 64 lanes), the older readers' counts there, its entries by
name, its configuration against the catalog's numbers, its rehearsal, that
each control fails the limit named for it (on the repo and on the copy a
later PR appended to) and that a checkout which has no convolution layer
fails the cell at once."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import convshapes, lmshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_lfm2_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "lfm8b.ps-8k"
CONFIG = "lfm2-8b-a1b-l8"
LAYOUT = ["conv", "conv", "gqa", "conv"] * 2
# what benchmark/drivers/lm_lfm2.py fills: ``layers`` the SPARSE ones
SHAPES = {"family": "conv", "sequences": 2, "seq_len": 8192, "hidden": 2048,
          "attention_layout": LAYOUT, "conv_taps": 3, "heads": 32,
          "kv_heads": 8, "head_dim": 64, "router_outputs": 32, "top_k": 4,
          "held": 8, "expert_width": 1792, "dense_width": 7168,
          "vocab": 16384, "layers": 6, "sparse_layers": 6, "dense_layers": 2,
          "parameters": 772217280}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.shortconv_ms_per_step.lm", "trainer.shortconv_roofline.lm",
       "trainer.attn_lanes_used_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_full_roofline_d64.lm` and
# `trainer.mfu_lfm2.lm` until then (`trainer.mixers_conv_share.lm`, 75 from
# the configuration, was retired)
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "setup.table_init_s",
         "trainer.attn_full_ms_per_step.lm", "trainer.router_ms_per_step.lm",
         "trainer.experts_ms_per_step.lm", "trainer.experts_roofline.lm",
         "trainer.experts_short_share.lm",
         "trainer.shared_expert_ms_per_step.lm",
         "trainer.head_ms_per_step.lm",
         "trainer.expert_load_max_over_mean.lm",
         "trainer.router_load_max_over_mean.lm",
         "trainer.attn_blocks_fitted_share.lm",
         "trainer.attn_pass_fused_share.lm", "table.adam_ms_per_step.lm",
         "table.adam_roofline.lm", "table.snapshot_ms_per_step.lm",
         "table.embed_rows_per_step.lm", "worker.ms_per_req.train",
         "server.ms_per_req.train", "server.dispatches_per_round.train",
         "client.wait_ms.train", "client.issue_ms_per_round.train",
         "client.wake_ms.train", "server.dispatch_ms.train",
         "server.mailbox_wait_ms.train", "worker.mailbox_wait_ms.train",
         "table.device_ms_per_round.train", "table.gather_ms_per_round.train",
         "device.idle_share.train", "trainer.block_ms.train",
         "trainer.programs_built_in_window.train",
         "host.stall_ms_per_s.train", "host.frozen_ms_per_s.train",
         "host.beat_late_ms.train"]
# they count other kinds of layer; the last two read the rows form's writes,
# and the one table's Add is dense
NOT_JOINED = ["trainer.kda_conv_ms_per_step.lm",
              "trainer.attn_window_ms_per_step.lm",
              "table.scatter_ms_per_round.train",
              "table.update_fast_share.train"]
# of those, the ones whose reader finds nothing in this cell's observations
NOTHING_TO_READ = ["trainer.kda_conv_ms_per_step.lm",
                   "trainer.attn_window_ms_per_step.lm",
                   "table.scatter_ms_per_round.train",
                   "table.update_fast_share.train"]
TOKENS = 2 * 8192
PAIRS = 8192 * 8193 // 2
EVEN = TOKENS * 4 * 8 // 32     # assignments on held experts a layer a step


# -- the counting functions at this cell's shapes, by hand ----------------------

def test_a_convolution_layer_counts_two_products_and_its_chain():
    assert convshapes.layers_of(SHAPES, "conv") == 6
    assert convshapes.layers_of(SHAPES, "gqa") == 2
    # W_in [2048, 6144] and W_out [2048, 2048]: 33.5 MFLOP a token
    assert convshapes.conv_dense_flops(SHAPES) == 2 * (
        2048 * 6144 + 2048 * 2048) == 33554432
    # two gates and three taps a channel a position, three passes
    assert convshapes.chain_flops(SHAPES) == 3 * (2 + 6) * 2048 * TOKENS
    assert convshapes.mixer_flops(SHAPES) == 3 * TOKENS * 33554432 \
        + convshapes.chain_flops(SHAPES)
    # the chain is a two-thousandth of the mixer's operations: it is bytes
    assert convshapes.chain_flops(SHAPES) < 1e-3 * convshapes.mixer_flops(
        SHAPES)


def test_an_attention_layer_counts_causal_pairs_at_64_lanes():
    assert convshapes.attention_flops(SHAPES) \
        == 3 * 2 * (64 + 64) * 32 * 2 * PAIRS
    # W_q, W_o [2048, 2048] and W_k, W_v [2048, 512]: 21 MFLOP a token
    assert convshapes.gqa_dense_flops(SHAPES) == 2 * (
        2 * 2048 * 2048 + 2 * 2048 * 512) == 20971520
    # the kernel's pairs a token at 8,192 positions: 33.5 MFLOP forward
    assert convshapes.attention_flops(SHAPES) / 3 / TOKENS \
        == pytest.approx(33.56e6, rel=1e-3)


def test_every_token_s_products_by_layer_kind():
    assert convshapes.token_flops(SHAPES) == (
        6 * 33554432 + 2 * 20971520 + 2 * 6 * 2048 * 7168
        + 6 * 2 * 2048 * 32 + 2 * 2048 * 16384)
    # the head is ONE product of the one table: 67 MFLOP a token
    assert 2 * 2048 * 16384 == 67108864


def test_step_flops_at_an_even_load():
    assert EVEN == 16384
    flops = convshapes.step_flops(1, 6 * EVEN, SHAPES)
    assert flops == (
        6 * convshapes.chain_flops(SHAPES)
        + 2 * convshapes.attention_flops(SHAPES)
        + 3 * TOKENS * convshapes.token_flops(SHAPES)
        + lmshapes.expert_flops(6 * EVEN, 2048, 1792))
    assert 30e12 < flops < 38e12        # the issue's ~34 TFLOP a step
    # the six convolution mixers' products: 29% of it; the two attention
    # layers' kernels under 10%
    mixers = 3 * TOKENS * 6 * convshapes.conv_dense_flops(SHAPES)
    assert 0.27 < mixers / flops < 0.31
    assert 2 * convshapes.attention_flops(SHAPES) < 0.10 * flops


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


STEPS = 4
SCOPES = {
    "jit_forward": {
        "mv.lm.attn.shortconv": 0.120, "mv.lm.attn.shortconv.taps": 0.012,
        "mv.lm.attn.full": 0.040, "mv.lm.attn.full.kernel": 0.036,
        "mv.lm.router": 0.010, "mv.lm.experts": 0.150,
        "mv.lm.dense_mlp": 0.050},
    "jit_backward": {
        "mv.lm.attn.shortconv": 0.330, "mv.lm.attn.shortconv.taps": 0.036,
        "mv.lm.attn.full": 0.100, "mv.lm.attn.full.kernel": 0.144,
        "mv.lm.router": 0.030, "mv.lm.experts": 0.400,
        "mv.lm.dense_mlp": 0.140, "no-scope": 0.100},
    "jit_head_step": {"mv.lm.head": 0.120},
    "jit__tie_gradients": {"mv.lm.embed": 0.010},
    "jit_update": {"mv.update.rule": 0.200}}
TRACE = {"window_s": 3.4, "scopes": SCOPES,
         "programs": {stem: {"seconds": sum(by.values()), "count": 4}
                      for stem, by in SCOPES.items()}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 6 * EVEN,
                LM_EMBED_ROWS=STEPS * 5000)
RUNS = 33
WINDOW = _count(
    LM_STEP=RUNS, LM_TOKENS=RUNS * TOKENS,
    LM_HELD_ASSIGNMENTS=RUNS * 6 * EVEN, LM_EXPERT_MAX_TOKENS=RUNS * 6 * 2300,
    LM_ROUTER_LOAD_MAX=RUNS * 6 * 2400, LM_EMBED_ROWS=RUNS * 5000,
    LM_MIXERS_CONV=RUNS * 6 * 2, LM_MIXERS=RUNS * 8 * 2,
    LM_ATTN_LANES=RUNS * 2 * 2 * 64, LM_ATTN_LANES_TILED=RUNS * 2 * 2 * 64,
    LM_TIED_ADDS=RUNS, LM_EXPERTS_SHORT=RUNS * 12,
    LM_ATTN_PASS_PLAIN=RUNS * 4, LM_ATTN_BLOCKS_FITTED=RUNS * 4)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.4, traced),
        window=_Window(RUNS, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.mfu.lm": 100 * convshapes.step_flops(
        RUNS, RUNS * 6 * EVEN, SHAPES) / 197e12 / 20.0,
    "trainer.shortconv_ms_per_step.lm": (120 + 12 + 330 + 36) / STEPS,
    "trainer.shortconv_roofline.lm":
        100 * STEPS * 6 * convshapes.mixer_flops(SHAPES) / 197e12 / 0.498,
    "trainer.attn_roofline.lm":
        100 * STEPS * 2 * convshapes.attention_flops(SHAPES) / 197e12 / 0.180,
    "trainer.attn_lanes_used_share.lm": 100.0}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    assert _read(name, _obs()) == pytest.approx(WANT[name])
    if name.endswith("roofline.lm") or "mfu" in name:
        assert 0 < WANT[name] < 100


def test_heads_padded_to_a_tile_would_read_fifty():
    window = dict(WINDOW, **_count(LM_ATTN_LANES_TILED=RUNS * 2 * 2 * 128))
    assert _read("trainer.attn_lanes_used_share.lm",
                 _obs(window=window)) == 50.0


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too, and so could another cell: no
    such scope, no such counter, no such shape, and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    # solar250b.ps-8k on the parent: attention under mv.lm.attn.full at 128
    # lanes, delta layers' convolutions, none of the new counters or shapes
    solar = {"sequences": 2, "seq_len": 8192, "hidden": 4096,
             "attention_layout": ["gqa", "kda", "kda", "kda"], "heads": 8,
             "heads_all": 64, "kv_heads": 1, "head_dim": 128}
    counts = _count(LM_STEP=8, LM_TOKENS=8 * TOKENS,
                    LM_HELD_ASSIGNMENTS=8 * 4 * 3276)
    other = {"window_s": 3.0, "scopes": {"jit_backward": {
        "mv.lm.attn.full.kernel": 0.05, "mv.lm.attn.kda.conv": 0.04}},
        "programs": {}}
    assert _read(name, _obs(trace=other, traced=counts, window=counts,
                            shapes=solar)) is None


@pytest.mark.parametrize("name", NOTHING_TO_READ)
def test_the_other_models_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


def test_the_shared_readers_count_this_cell_by_its_sparse_layers():
    """The older readers at this cell's shapes: ``layers`` is the six layers
    with routed experts (the two dense layers bring no router and no
    expert), the dense MLPs are read with the shared experts' scope, the
    attention's scopes are the full layers'."""
    assert _read("trainer.router_load_max_over_mean.lm", _obs()) \
        == pytest.approx(2400 / (TOKENS * 4 / 32))
    assert _read("trainer.expert_load_max_over_mean.lm", _obs()) \
        == pytest.approx(2300 * 8 / EVEN)
    assert lmshapes.expert_bytes(1, 0, SHAPES) \
        == 6 * 8 * 3 * 2048 * 1792 * 10
    took = 0.550
    least = max(
        lmshapes.expert_flops(STEPS * 6 * EVEN, 2048, 1792) / 197e12,
        lmshapes.expert_bytes(STEPS, STEPS * 6 * EVEN, SHAPES) / 819e9)
    assert _read("trainer.experts_roofline.lm", _obs()) \
        == pytest.approx(100 * least / took)
    assert _read("trainer.shared_expert_ms_per_step.lm", _obs()) \
        == pytest.approx(190.0 / STEPS)
    assert _read("trainer.attn_full_ms_per_step.lm", _obs()) \
        == pytest.approx((40 + 36 + 100 + 144) / STEPS)
    assert _read("trainer.experts_short_share.lm", _obs()) == 100.0
    assert _read("trainer.attn_pass_fused_share.lm", _obs()) == 0.0
    assert _read("trainer.attn_blocks_fitted_share.lm", _obs()) == 100.0
    # Adam: every whole table but the one that is embedding and head, and
    # that one by the rows a step names: the reader understates the dense
    # step of the 16,384 rows by what the unnamed rows move (under 3%)
    counted = lmshapes.adam_bytes(STEPS, STEPS * 5000, SHAPES)
    dense = 28 * STEPS * SHAPES["parameters"]
    assert 0.96 * dense < counted < dense
    assert _read("table.adam_roofline.lm", _obs()) == pytest.approx(
        100 * counted / 819e9 / 0.200)


# -- the entries, the configuration, the controls, the parent -----------------

@pytest.mark.parametrize("name", NEW + MERGED)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] == "trainer"
    assert metric["unit"] == ("ms" if "_ms_" in name else "%")
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-step-8k"
    entry = entries.named(bench, "configs", CONFIG)
    assert sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers",
                                        "vocab_size"]
    for name in OLDER:
        kind = "end_to_end" if name in ("words_per_s", "peak_hbm_gb") \
            else "per_layer"
        assert CELL in entries.named(bench, kind, name)["workloads"], name
    for name in NOT_JOINED:
        assert CELL not in entries.named(bench, "per_layer",
                                         name)["workloads"], name
    entries.check_cells(root, bench)
    entries.check_all(root)


def test_the_configuration_holds_the_catalog_s_numbers(root):
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    types = ["conv", "conv", "full_attention", "conv"] * 5 + [
        "conv", "full_attention", "conv", "conv"]
    published = {     # the catalog's `config`, every key
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": types,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 8, 65536 // 4)
    assert config["router_outputs"] == 32 and config["head_dim"] == 64
    assert config["tie_word_embeddings"] is True
    assert config["deployment"]["chips_per_layer"] == 4
    sizes = config["parameters"]
    assert sizes["conv_mixer"] == 12582912 + 4194304 + 6144
    assert sizes["attention_mixer"] == 2 * 4194304 + 2 * 1048576 + 128
    assert sizes["sparse_feed_forward"] == 8 * 11010048 + 65536 + 32
    assert sizes["dense_conv_layer"] == sizes["conv_mixer"] \
        + sizes["layer_norms"] + sizes["dense_mlp"]
    assert sizes["sparse_conv_layer"] == sizes["conv_mixer"] \
        + sizes["layer_norms"] + sizes["sparse_feed_forward"]
    assert sizes["sparse_attention_layer"] == sizes["attention_mixer"] \
        + sizes["layer_norms"] + sizes["sparse_feed_forward"]
    assert sizes["eight_layers"] == 2 * sizes["dense_conv_layer"] \
        + 4 * sizes["sparse_conv_layer"] + 2 * sizes["sparse_attention_layer"]
    assert sizes["total"] == SHAPES["parameters"] == sizes["eight_layers"] \
        + sizes["table"] + sizes["final_norm"]
    assert {"head_dim", "tie_word_embeddings", "router", "hidden_act",
            "conv", "attention", "sequence_and_batch", "optimizer",
            "init"} <= set(config["assumed"])
    assert config["router_bias_rate"] == 0.001
    assert config["init_std"] == config["embedding_init_std"] == 0.02
    assert entry["source"] == config["source"]
    assert "ran" in config["size_that_ran"]
    assert "one_add_a_table_a_step" in config["guarantees"]
    assert set(controls.CAUGHT_BY.values()) | {
        "loss", "gradient.table", "gradient.router", "adam.update",
        "bias.differs", "routing.differs", "layer.output"} \
        <= set(config["limits"])
    assert set(config["limits"]) == set(config["rehearsal"]["limits"])


def test_the_program_builds_the_published_model_from_the_file(root):
    from multiverso_tpu.models.lm import LMConfig
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    config.pop("rehearsal")
    cfg = LMConfig.from_dict(config)
    assert cfg.parameters() == config["parameters"]["total"]
    assert (cfg.hidden, cfg.head_dim, cfg.conv_taps, cfg.expert_width,
            cfg.dense_width, cfg.shared_width, cfg.n_experts, cfg.top_k,
            cfg.n_heads, cfg.n_kv_heads) == (
        2048, 64, 3, 1792, 7168, 0, 32, 4, 32, 8)
    assert list(cfg.attention_layout) == LAYOUT and cfg.tied
    assert cfg.ffn_layout == (0, 0) + (1,) * 6 and cfg.experts_held == (0, 8)
    tables = 2 + sum(len(cfg.layer_shapes(i)) for i in range(8))
    assert tables == config["parameters"]["tables"]


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_lfm2_controls.py", what,
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
    if what == "bfloat16_moments":
        for name in ("loss", "gradient.table", "gradient.conv",
                     "gradient.tied", "layer.output"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]
    if what == "conv_where_attention":  # nothing else can be compared
        assert set(result["compared"]) == {"non_finite_losses",
                                           "layout.differs"}


def test_the_rehearsal_passes_beside_the_controls(root, tmp_path):
    """The driver's rehearsal on the CPU, end to end: `correct`, every
    limit compared."""
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.conv", "gradient.scores",
            "gradient.router", "gradient.experts", "gradient.tied",
            "adam.moments", "adam.update", "bias.differs", "adds.extra",
            "layout.differs", "routing.differs", "layer.output",
            "routing.differs.layer0", "layer.output.layer7",
            "routing.held_share.layer5"} <= set(result["compared"])


def test_a_checkout_that_has_no_convolution_layer_fails_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit code
    than 0 and no result line. The parent has no ``models/lm/shortconv.py``:
    the driver imports it before ``mv.init``."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    os.remove(root / "multiverso_tpu" / "models" / "lm" / "shortconv.py")
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

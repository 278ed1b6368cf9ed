"""The cell `solar250b.ps-8k`: its five readers on hand-built
``Observations`` (a reduced trace as benchmark/lib/xplane.py leaves it), the
counting functions at this cell's shapes by hand (over the HELD heads), the
older readers' counts there, its entries by name, its configuration against
the catalog's numbers, its rehearsal, that each control fails the limit named
for it (on the repo and on the copy a later PR appended to) and that a
checkout which cannot describe the model fails the cell at once."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import kdashapes, lmshapes, solarshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_solar_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "solar250b.ps-8k"
CONFIG = "solar-open2-250b-a15b-l4"
# what benchmark/drivers/lm_solar.py fills: the heads are the HELD ones
SHAPES = {"family": "solar", "sequences": 2, "seq_len": 8192, "hidden": 4096,
          "attention_layout": ["gqa", "kda", "kda", "kda"],
          "kda_heads": 8, "kda_heads_all": 64, "kda_head_dim": 128,
          "kda_conv": 4, "heads": 8, "heads_all": 64, "kv_heads": 1,
          "head_dim": 128, "router_outputs": 320, "top_k": 8, "held": 8,
          "expert_width": 1280, "shared_width": 1280, "vocab": 24576,
          "layers": 4, "sparse_layers": 4, "parameters": 840872600}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.kda_beta_over_one_share.lm",
       "trainer.gate_lanes_open_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_full_roofline_held.lm` and
# `trainer.mfu_solar.lm` until then (`trainer.heads_held_share.lm`, 12.5 from
# the configuration, was retired)
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "setup.table_init_s",
         "trainer.attn_kda_ms_per_step.lm", "trainer.kda_conv_ms_per_step.lm",
         "trainer.kda_scan_ms_per_step.lm", "trainer.kda_scan_roofline.lm",
         "trainer.kda_decay_deep_share.lm",
         "trainer.kda_scan_kernel_share.lm",
         "trainer.attn_full_ms_per_step.lm",
         "trainer.attn_gate_ms_per_step.lm",
         "trainer.shared_expert_ms_per_step.lm",
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
# they count a latent layer or 288 gated heads: not joined
NOT_JOINED = ["trainer.gate_open_share.lm", "trainer.attn_mla_ms_per_step.lm",
              "trainer.mla_pass_fused_share.lm",
              "trainer.attn_window_ms_per_step.lm"]
# of those, the ones whose reader finds nothing in this cell's observations
NOTHING_TO_READ = ["trainer.gate_open_share.lm",
                   "trainer.attn_mla_ms_per_step.lm",
                   "trainer.attn_window_ms_per_step.lm"]
TOKENS = 2 * 8192
PAIRS = 8192 * 8193 // 2


# -- the counting functions at this cell's shapes, by hand ----------------------

def test_the_softmax_layer_counts_causal_pairs_over_its_held_heads():
    assert solarshapes.attention_flops(SHAPES) \
        == 3 * 2 * (128 + 128) * 8 * 2 * PAIRS
    # a share of 16 heads would count twice that, all 64 eight times
    assert solarshapes.attention_flops(dict(SHAPES, heads=64)) \
        == 8 * solarshapes.attention_flops(SHAPES)


def test_a_layer_s_projections_are_its_own_kind_s_over_held_heads():
    # W_q, W_g, W_o [4096, 8 x 128] and W_k, W_v [4096, 128]
    assert solarshapes.gqa_dense_flops(SHAPES) \
        == 2 * (3 * 4096 * 1024 + 2 * 4096 * 128)
    lanes = 8 * 128
    assert kdashapes.kda_dense_flops(SHAPES) == 2 * (
        4 * 4096 * lanes + 2 * 4096 * 128 + 2 * 128 * lanes + 4096 * 8)
    sparse = 2 * 4096 * 320 + 6 * 4096 * 1280
    assert solarshapes.token_flops(SHAPES) == (
        3 * kdashapes.kda_dense_flops(SHAPES)
        + solarshapes.gqa_dense_flops(SHAPES) + 4 * sparse
        + 2 * 4096 * 24576)


def test_step_flops_at_an_even_load():
    mean = TOKENS * 8 * 8 // 320    # assignments on held experts a layer
    assert mean == 3276
    flops = solarshapes.step_flops(1, 4 * mean, SHAPES)
    assert flops == (
        3 * (kdashapes.scan_flops(SHAPES) + kdashapes.conv_flops(SHAPES))
        + solarshapes.attention_flops(SHAPES)
        + 3 * TOKENS * solarshapes.token_flops(SHAPES)
        + lmshapes.expert_flops(4 * mean, 4096, 1280))
    assert 25e12 < flops < 40e12
    # the experts' products: a fortieth of a deployment's load
    assert lmshapes.expert_flops(4 * mean, 4096, 1280) < 0.1 * flops


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
        "mv.lm.attn.full": 0.020, "mv.lm.attn.full.kernel": 0.012,
        "mv.lm.attn.gate": 0.004, "mv.lm.attn.kda": 0.090,
        "mv.lm.attn.kda.conv": 0.012, "mv.lm.attn.kda.scan": 0.060,
        "mv.lm.router": 0.030, "mv.lm.experts": 0.100,
        "mv.lm.shared_expert": 0.080},
    "jit_backward": {
        "mv.lm.attn.full": 0.050, "mv.lm.attn.full.kernel": 0.048,
        "mv.lm.attn.gate": 0.010, "mv.lm.attn.kda": 0.260,
        "mv.lm.attn.kda.conv": 0.040, "mv.lm.attn.kda.scan": 0.240,
        "mv.lm.router": 0.070, "mv.lm.experts": 0.250,
        "mv.lm.shared_expert": 0.180, "no-scope": 0.150},
    "jit_head_step": {"mv.lm.head": 0.120},
    "jit_update": {"mv.update.rule": 0.200}}
TRACE = {"window_s": 3.4, "scopes": SCOPES,
         "programs": {stem: {"seconds": sum(by.values()), "count": 4}
                      for stem, by in SCOPES.items()}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 4 * 3276)
RUNS = 30
WINDOW = _count(
    LM_STEP=RUNS, LM_TOKENS=RUNS * TOKENS,
    LM_HELD_ASSIGNMENTS=RUNS * 4 * 3276,
    LM_ROUTER_LOAD_MAX=RUNS * 4 * 1200,
    LM_KDA_BETA=RUNS * 3 * TOKENS * 8,
    LM_KDA_BETA_OVER_ONE=RUNS * 3 * TOKENS * 8 // 2 + 77,
    LM_GATE_LANES=RUNS * TOKENS * 8 * 128,
    LM_GATE_LANES_OPEN=RUNS * TOKENS * 8 * 128 // 2 - 1000,
    LM_HEADS_HELD=RUNS * 4 * 2 * 8, LM_HEADS=RUNS * 4 * 2 * 64,
    LM_KDA_SCAN_KERNEL=RUNS * 3 * 2, LM_KDA_DECAY_CHANNELS=RUNS * 1000)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.4, traced),
        window=_Window(RUNS, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.mfu.lm": 100 * solarshapes.step_flops(
        RUNS, RUNS * 4 * 3276, SHAPES) / 197e12 / 20.0,
    "trainer.attn_roofline.lm":
        100 * STEPS * solarshapes.attention_flops(SHAPES) / 197e12 / 0.060,
    "trainer.kda_beta_over_one_share.lm":
        100 * (RUNS * 3 * TOKENS * 8 // 2 + 77) / (RUNS * 3 * TOKENS * 8),
    "trainer.gate_lanes_open_share.lm":
        100 * (RUNS * TOKENS * 1024 // 2 - 1000) / (RUNS * TOKENS * 1024)}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    assert _read(name, _obs()) == pytest.approx(WANT[name])
    assert 0 < WANT[name] < 100


def test_a_beta_that_never_passes_one_reads_zero_not_nothing():
    window = {n: c for n, c in WINDOW.items()
              if n != "LM_KDA_BETA_OVER_ONE"}
    assert _read("trainer.kda_beta_over_one_share.lm",
                 _obs(window=window)) == 0.0


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
    # kimi48b.ps-8k on the parent: delta layers, whole heads, no new counter
    kimi = {"sequences": 2, "seq_len": 8192, "hidden": 2304,
            "attention_layout": ["kda", "kda", "kda", "mla", "kda"],
            "kda_heads": 32, "kda_head_dim": 128, "kda_conv": 4}
    counts = _count(LM_STEP=8, LM_TOKENS=8 * TOKENS,
                    LM_HELD_ASSIGNMENTS=8 * 4 * 4096, LM_KDA_TOKENS=8 * 4)
    assert _read(name, _obs(traced=counts, window=counts,
                            shapes=kimi)) is None


@pytest.mark.parametrize("name", NOTHING_TO_READ)
def test_the_other_models_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


def test_the_shared_readers_count_this_cell_over_its_held_heads():
    """The older readers at this cell's shapes: the scan's least time over
    EIGHT heads a layer (``kdashapes`` reads ``kda_heads``, which the driver
    gives as the held ones), the softmax layer's scopes, the lane gate's."""
    layers = STEPS * 3
    least = max(layers * kdashapes.scan_flops(SHAPES) / 197e12,
                layers * kdashapes.scan_bytes(SHAPES) / 819e9)
    assert kdashapes.scan_flops(SHAPES) == 3 * 7 * 128 ** 2 * 8 * TOKENS
    assert _read("trainer.kda_scan_roofline.lm", _obs()) == pytest.approx(
        100 * least / 0.300)
    assert 0 < _read("trainer.kda_scan_roofline.lm", _obs()) < 100
    assert _read("trainer.kda_scan_ms_per_step.lm", _obs()) \
        == pytest.approx(300.0 / STEPS)
    assert _read("trainer.attn_kda_ms_per_step.lm", _obs()) > 0
    assert _read("trainer.attn_full_ms_per_step.lm", _obs()) \
        == pytest.approx((20 + 12 + 50 + 48) / STEPS)
    assert _read("trainer.attn_gate_ms_per_step.lm", _obs()) \
        == pytest.approx(14.0 / STEPS)
    assert _read("trainer.kda_scan_kernel_share.lm", _obs()) == 100.0
    assert _read("trainer.kda_decay_deep_share.lm", _obs()) == 0.0
    assert _read("trainer.router_load_max_over_mean.lm", _obs()) \
        == pytest.approx(1200 / (TOKENS * 8 / 320))
    assert lmshapes.expert_bytes(1, 0, SHAPES) \
        == 4 * 8 * 3 * 4096 * 1280 * 10


# -- the entries, the configuration, the controls, the parent -----------------

@pytest.mark.parametrize("name", NEW + MERGED)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] == "trainer" and metric["unit"] == "%"
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-step-8k"
    entry = entries.named(bench, "configs", CONFIG)
    assert sorted(entry["reduced"]) == [
        "linear_attn_config", "n_routed_experts", "num_attention_heads",
        "num_hidden_layers", "num_key_value_heads", "vocab_size"]
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
    published = {     # the catalog's `config`, every key
        "model_type": "solar_open2", "partial_rotary_factor": 1,
        "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 128,
                               "num_heads": 64, "num_kv_heads": None},
        "hidden_size": 4096, "num_hidden_layers": 48,
        "num_attention_heads": 64, "head_dim": 128,
        "num_key_value_heads": 8, "vocab_size": 196608,
        "intermediate_size": 10240, "moe_intermediate_size": 1280,
        "rms_norm_eps": 1e-05, "rope_theta": 10000,
        "tie_word_embeddings": False, "max_position_embeddings": 1048576,
        "first_k_dense_replace": 0, "use_rope": False, "gqa_interval": 3,
        "gqa_layers": [0, 4, 8, 12, 16, 20, 24, 28, 32, 36, 40, 44],
        "use_gqa_gate": True, "kda_use_full_proj": False,
        "kda_allow_neg_eigval": True, "n_routed_experts": 320,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    # the group that was cut keeps every width: its head count alone moved
    linear = config["linear_attn_config"]
    assert {k: v for k, v in linear.items() if k != "num_heads"} == {
        k: v for k, v in published["linear_attn_config"].items()
        if k != "num_heads"}
    held = config["num_attention_heads"]
    assert held in (16, 8) and linear["num_heads"] == held
    assert config["num_key_value_heads"] == held // 8
    assert (config["attention_heads"], config["key_value_heads"],
            config["linear_attention_heads"]) == (64, 8, 64)
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 8, 196608 // 8)
    assert config["router_outputs"] == 320
    assert config["deployment"]["chips_per_layer"] == 40
    sizes = config["parameters"]
    assert sizes["total"] == SHAPES["parameters"] == (
        sizes["softmax_attention"] + 3 * sizes["delta_attention"]
        + 4 * sizes["feed_forward"] + sizes["embedding_and_head"]
        + sizes["final_norm"])
    assert sizes["four_layers"] == sizes["total"] - sizes[
        "embedding_and_head"] - sizes["final_norm"]
    assert {"lane_gate", "router", "hidden_act", "low_rank_widths",
            "beta_times_2", "decay_init", "sequence_and_batch", "optimizer",
            "init"} <= set(config["assumed"])
    assert config["router_bias_rate"] == 0.001
    assert entry["source"] == config["source"]
    assert "ran" in config["size_that_ran"]
    assert set(controls.CAUGHT_BY.values()) | {
        "loss", "gradient.gate", "gradient.router", "adam.update",
        "bias.differs", "adds.extra", "routing.differs",
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
    assert (cfg.hidden, cfg.head_dim, cfg.kda_head_dim, cfg.kda_conv,
            cfg.expert_width, cfg.shared_width, cfg.n_experts, cfg.top_k,
            cfg.n_heads, cfg.n_kv_heads, cfg.kda_heads) == (
        4096, 128, 128, 4, 1280, 1280, 320, 8, 64, 8, 64)
    assert cfg.n_heads_held == cfg.kda_heads_held == SHAPES["heads"]
    assert cfg.n_kv_heads_held == SHAPES["kv_heads"]
    assert cfg.attention_layout == ("gqa", "kda", "kda", "kda")
    assert cfg.kda_beta_scale == 2 and cfg.attn_gate == "lane"
    assert cfg.ffn_layout == (1,) * 4 and cfg.experts_held == (0, 8)
    tables = 3 + sum(len(cfg.layer_shapes(i)) for i in range(4))
    assert tables == config["parameters"]["tables"]


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_solar_controls.py", what,
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
    if what in ("bfloat16_moments", "bfloat16_state"):
        for name in ("loss", "gradient.table", "gradient.gate",
                     "gradient.scan", "layer.output"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]


def test_the_rehearsal_passes_beside_the_controls(root, tmp_path):
    """The driver's rehearsal on the CPU, end to end: `correct`, every
    limit compared, both sets of ``scan.carry``'s inputs reported."""
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.gate", "gradient.scores",
            "gradient.scan", "gradient.router", "adam.moments", "adam.update",
            "bias.differs", "adds.extra", "routing.differs", "layer.output",
            "scan.carry", "scan.carry.state", "scan.carry.beta",
            "routing.differs.layer0", "layer.output.layer3",
            "routing.held_share.layer0"} <= set(result["compared"])


def test_a_checkout_that_cannot_describe_the_model_fails_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit code
    than 0 and no result line. The parent's ``LMConfig`` holds no share of a
    delta layer's heads (no ``kda_heads_held``): the driver asks for it
    before ``mv.init``."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    model = root / "multiverso_tpu" / "models" / "lm" / "model.py"
    text = model.read_text()
    mine = "    def kda_heads_held(self)"
    assert mine in text
    model.write_text(text.replace(mine, "    def kda_heads_kept(self)"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483700", "--seconds", "1", "--trace", "0", "--rehearse"],
        cwd=root, env=env, text=True, capture_output=True, timeout=300)
    assert done.returncode not in (0, 124, 137)
    assert "AttributeError" in done.stderr
    assert "mv.init" not in done.stdout and "jax backend" not in done.stdout
    assert not [line for line in done.stdout.splitlines()
                if line.startswith("{")]

"""The cell `laguna33b.ps-8k`: its counting functions by hand, its four
readers on hand-built ``Observations``, its entries by name, its
configuration against the catalog's numbers, that each control fails the
limit named for it (on the repo and on the copy a later PR appended to)
and that a checkout whose model has one rotary kind fails the cell at
once. (Its rehearsal end to end is also test_rehearse.py's, which runs
every cell of BENCHMARK.json.)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import lmshapes, mixedshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_mixed_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "laguna33b.ps-8k"
CONFIG = "laguna-xs2-33b-a3b-l5"
SHAPES = {"family": "mixed", "sequences": 2, "seq_len": 8192, "hidden": 2048,
          "heads_layout": [48, 64, 64, 64, 48], "kv_heads": 8,
          "head_dim": 128, "window": 512, "windowed": [0, 1, 1, 1, 0],
          "ffn_layout": [0, 1, 1, 1, 1], "gate_heads": 288,
          "router_outputs": 256, "top_k": 8, "held": 32, "expert_width": 512,
          "shared_width": 512, "dense_width": 8192, "vocab": 12544,
          "layers": 4, "sparse_layers": 4, "dense_layers": 1,
          "parameters": 691623936}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.attn_gate_ms_per_step.lm", "trainer.gate_open_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_mixed_roofline.lm` and
# `trainer.mfu_mixed.lm` until then
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "trainer.attn_full_ms_per_step.lm",
         "trainer.attn_window_ms_per_step.lm",
         "trainer.router_ms_per_step.lm", "trainer.experts_ms_per_step.lm",
         "trainer.head_ms_per_step.lm", "trainer.experts_roofline.lm",
         "trainer.expert_load_max_over_mean.lm",
         "trainer.shared_expert_ms_per_step.lm",
         "trainer.router_load_max_over_mean.lm", "table.adam_ms_per_step.lm",
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
# latent attention, streams, block diffusion
NOT_THIS_CELL = ["trainer.attn_mla_ms_per_step.lm", "trainer.hc_ms_per_step.lm",
                 "trainer.hc_roofline.lm", "trainer.mtp_ms_per_step.lm",
                 "trainer.attn_blockdiff_ms_per_step.lm",
                 "trainer.masked_share.lm"]
TOKENS = 2 * 8192
CAUSAL, WINDOWED = 33_558_528, 4_063_488


# -- the counting functions, by hand ------------------------------------------

def test_pairs_by_kind_at_8192():
    assert mixedshapes.pairs(SHAPES, 0) == CAUSAL == 8192 * 8193 // 2
    assert mixedshapes.pairs(SHAPES, 1) == WINDOWED \
        == sum(min(p + 1, 512) for p in range(8192))
    assert 0.12 < WINDOWED / CAUSAL < 0.122
    tiny = dict(SHAPES, seq_len=4, window=2)
    assert (mixedshapes.pairs(tiny, 0), mixedshapes.pairs(tiny, 1)) == (10, 7)
    # a window no shorter than the sequence is the causal mask
    assert mixedshapes.pairs(dict(tiny, window=4), 1) == 10


def test_attention_counts_each_kind_s_own_heads_and_pairs():
    a_head = 3 * 2 * (128 + 128) * 2
    assert mixedshapes.attention_flops(SHAPES) \
        == a_head * (2 * 48 * CAUSAL + 3 * 64 * WINDOWED)
    one = dict(SHAPES, heads_layout=[64], windowed=[1], ffn_layout=[1])
    assert mixedshapes.attention_flops(one) == a_head * 64 * WINDOWED


def test_a_layer_s_products_a_token_by_kind():
    full = 2 * 2048 * (2 * 48 * 128 + 2 * 8 * 128 + 48)
    sliding = 2 * 2048 * (2 * 64 * 128 + 2 * 8 * 128 + 64)
    assert mixedshapes.layer_token_flops(SHAPES, 48, 0) \
        == full + 6 * 2048 * 8192
    sparse = 2 * 2048 * 256 + 6 * 2048 * 512
    assert mixedshapes.layer_token_flops(SHAPES, 64, 1) == sliding + sparse
    assert mixedshapes.token_flops(SHAPES) == (
        2 * full + 3 * sliding + 6 * 2048 * 8192 + 4 * sparse
        + 2 * 2048 * 12544)


def test_step_flops():
    # a step's mean load: 16384 tokens x 8 / 256 experts x 32 held, a layer
    mean = TOKENS * 8 * 32 // 256
    assert mean == 16384
    flops = mixedshapes.step_flops(1, 4 * mean, SHAPES)
    assert flops == (mixedshapes.attention_flops(SHAPES)
                     + 3 * TOKENS * mixedshapes.token_flops(SHAPES)
                     + lmshapes.expert_flops(4 * mean, 2048, 512))
    assert 25e12 < flops < 40e12
    assert mixedshapes.step_flops(2, 8 * mean, SHAPES) == 2 * flops


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


STEPS = 4
TRACE = {"window_s": 3.0, "programs": {}, "scopes": {
    "jit_forward": {
        "mv.lm.attn.full": 0.030, "mv.lm.attn.full.kernel": 0.050,
        "mv.lm.attn.window": 0.060, "mv.lm.attn.window.kernel": 0.020,
        "mv.lm.attn.gate": 0.008, "mv.lm.router": 0.010,
        "mv.lm.experts": 0.150, "mv.lm.shared_expert": 0.020,
        "mv.lm.dense_mlp": 0.030},
    "jit_backward": {
        "mv.lm.attn.full": 0.100, "mv.lm.attn.full.kernel": 0.150,
        "mv.lm.attn.window": 0.200, "mv.lm.attn.window.kernel": 0.080,
        "mv.lm.attn.gate": 0.032, "mv.lm.router": 0.030,
        "mv.lm.experts": 0.450, "mv.lm.shared_expert": 0.060,
        "mv.lm.dense_mlp": 0.090},
    "jit_head_step": {"mv.lm.head": 0.100}}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 4 * 16384)
# a gate a little over half open: 146 of 288 heads' worth, in thousandths
WINDOW = _count(LM_STEP=25, LM_HELD_ASSIGNMENTS=25 * 4 * 16384,
                LM_TOKENS=25 * TOKENS, LM_ROUTER_LOAD_MAX=25 * 4 * 1024,
                LM_GATE_OPEN=25 * 146_000)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.0, traced),
        window=_Window(25, 20.0, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.attn_gate_ms_per_step.lm": 40.0 / STEPS,
    "trainer.attn_roofline.lm":
        100 * STEPS * mixedshapes.attention_flops(SHAPES) / 197e12 / 0.300,
    "trainer.gate_open_share.lm": 100 * 146 / 288,
    "trainer.mfu.lm":
        100 * mixedshapes.step_flops(25, 25 * 4 * 16384, SHAPES)
        / 197e12 / 20.0,
}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    value = _read(name, _obs())
    assert value == pytest.approx(WANT[name])
    if "roofline" in name or "mfu" in name or "share" in name:
        assert 0 < value < 100


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too: no scope, no counter, no
    shape of this model, and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    if name in MERGED:
        return      # one reader for every cell: it reads st21b.ps-8k's too
    # st21b.ps-8k: both kernel scopes, the trainer's older counters, its
    # own shapes: one head count a model, no gate
    other = {"family": "lm", "sequences": 2, "seq_len": 8192, "hidden": 2560,
             "heads": 28,
             "kv_heads": 4, "head_dim": 128, "router_outputs": 64, "held": 16,
             "expert_width": 768, "vocab": 37984, "layers": 4, "window": 4096,
             "window_layout": [0, 1, 1, 1], "parameters": 656500000}
    older = {"window_s": 3.0, "programs": {}, "scopes": {"jit_forward": {
        "mv.lm.attn.full.kernel": 0.06, "mv.lm.attn.window.kernel": 0.09,
        "mv.lm.experts": 0.2}}}
    counts = _count(LM_STEP=8, LM_HELD_ASSIGNMENTS=8 * 98304,
                    LM_TOKENS=8 * 16384)
    assert _read(name, _obs(trace=older, traced=counts, window=counts,
                            shapes=other)) is None


@pytest.mark.parametrize("name", NOT_THIS_CELL)
def test_the_other_models_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


def test_the_shared_readers_count_this_cell_s_layers():
    """`layers` is the layers with routed experts, which is what the
    experts' least bytes and the routers' mean load count by; the two
    kinds of attention and the two whole feed-forwards read their own
    scopes."""
    took = 0.150 + 0.450
    least = max(
        lmshapes.expert_flops(STEPS * 4 * 16384, 2048, 512) / 197e12,
        lmshapes.expert_bytes(STEPS, STEPS * 4 * 16384, SHAPES) / 819e9)
    assert _read("trainer.experts_roofline.lm", _obs()) \
        == pytest.approx(100 * least / took)
    assert lmshapes.expert_bytes(1, 0, SHAPES) \
        == 4 * 32 * 3 * 2048 * 512 * 10
    assert _read("trainer.attn_full_ms_per_step.lm", _obs()) \
        == pytest.approx(330.0 / STEPS)
    assert _read("trainer.attn_window_ms_per_step.lm", _obs()) \
        == pytest.approx(360.0 / STEPS)
    assert _read("trainer.shared_expert_ms_per_step.lm", _obs()) \
        == pytest.approx(200.0 / STEPS)
    # 16384 x 8 / 256 = 512 a router output when even
    assert _read("trainer.router_load_max_over_mean.lm", _obs()) \
        == pytest.approx(1024 / 512)
    assert _read("trainer.expert_load_max_over_mean.lm", _obs(
        window=dict(WINDOW, **_count(LM_EXPERT_MAX_TOKENS=25 * 4 * 2 * 700)))
    ) == pytest.approx(2 * 700 * 32 / 16384)
    assert lmshapes.adam_bytes(1, 0, SHAPES) == 28 * (691623936
                                                      - 12544 * 2048)


# -- the entries, the configuration, the controls, the parent -----------------

@pytest.mark.parametrize("name", NEW + MERGED)
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
    assert cell["traffic"] == "lm-ps-step-8k" and len(cell["why"]) <= 200
    entries.named(bench, "configs", CONFIG)
    for name in OLDER:      # membership, not equality: the lists grow
        kind = "end_to_end" if name in ("words_per_s", "peak_hbm_gb") \
            else "per_layer"
        assert CELL in entries.named(bench, kind, name)["workloads"], name
    for name in NOT_THIS_CELL:
        assert CELL not in entries.named(bench, "per_layer",
                                         name)["workloads"], name
    entries.check_cells(root, bench)
    entries.check_all(root)


def _catalog():
    """The catalog's `config` for the model, where the guide is here."""
    path = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.isfile(path):
        pytest.skip("no catalog beside the model-configs guide here")
    with open(path) as f:
        rows = [json.loads(line) for line in f if "Laguna-XS.2" in line]
    return rows[0]


def test_the_configuration_holds_the_catalog_s_numbers(root):
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    row = _catalog()
    assert entry["source"] == config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:       # lists and the rope group whole, as published
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) \
        == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"], config["router_outputs"]) \
        == (5, 32, 12544, 256)
    assert (config["hidden_size"], config["head_dim"],
            config["num_key_value_heads"], config["sliding_window"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["shared_expert_intermediate_size"],
            config["num_experts_per_tok"],
            config["moe_routed_scaling_factor"]) \
        == (2048, 128, 8, 512, 8192, 512, 512, 8, 2.5)
    assert config["deployment"]["chips_per_layer"] == 8
    sizes = config["parameters"]
    assert sizes["total"] == SHAPES["parameters"] == (
        sizes["layer_0_full_dense"] + 3 * sizes["sliding_sparse_layer"]
        + sizes["full_sparse_layer"] + sizes["embedding_and_head"]
        + sizes["final_norm"])
    assert sizes["sliding_sparse_layer"] == (
        sizes["sliding_attention"] + sizes["experts_32"]
        + sizes["shared_expert"] + sizes["router"] + sizes["two_norms"])
    assert {"gating", "no_qk_norm_no_bias", "hidden_act", "router", "yarn",
            "optimizer", "init"} <= set(config["assumed"])
    assert "size_that_ran" in config and "guarantees" in config
    assert set(controls.CAUGHT_BY.values()) | {
        "loss", "gradient.gate", "gradient.router", "gradient.attn_gate",
        "gradient.scores", "adam.update", "routing.differs",
        "routing.differs.later"} <= set(config["limits"])
    assert set(config["limits"]) == set(config["rehearsal"]["limits"])


def _noted(errors, norms):
    """drivers/lm_mixed.py's pooling of a step's gradient errors, table
    by table, on hand-made numbers: layer 0 dense, 1 and 2 sparse."""
    import types
    from benchmark.drivers import lm_mixed
    check = object.__new__(lm_mixed._Check)
    check.cfg = types.SimpleNamespace(ffn_layout=(0, 1, 1))
    check.worst, check.pooled, check.norm2 = {}, {}, dict(norms)
    for table, error in errors.items():
        check.note(lm_mixed.lm._kind(table), error, table)
    return check


def test_the_dense_layer_s_mlp_is_not_pooled_with_the_routed_experts():
    """The dense MLP goes by the routed experts' names and its gradient
    is hundreds of times theirs: together, the experts' error would not
    be read."""
    errors = {"layer0.w_down": 0.005, "layer1.w_down": 0.02,
              "layer2.w_down": 0.02, "layer0.w_gate": 0.005,
              "layer1.w_gate": 0.03, "head": 0.004}
    norms = {"layer0.w_down": 600.0, "layer1.w_down": 1.0,
             "layer2.w_down": 1.0, "layer0.w_gate": 600.0,
             "layer1.w_gate": 1.0, "head": 5.0}
    check = _noted(errors, norms)
    assert check.worst["gradient.table"] == (pytest.approx(0.02), "w_down")
    assert check.worst["gradient.gate"] == (pytest.approx(0.03), "w_gate")
    assert set(check.pooled) == {"dense.w_down", "dense.w_gate", "w_down",
                                 "w_gate", "head"}


def test_the_later_layers_share_is_the_mean_after_the_first_sparse_layer():
    from benchmark.drivers import lm_mixed
    check = object.__new__(lm_mixed._Check)
    check.worst = {}
    check._reference = lambda tokens, chosen: (1.0, [0.03, 0.04, 0.05, 0.06])
    assert check.reference(None, None) == (1.0, [0.03, 0.04, 0.05, 0.06])
    assert check.worst["routing.differs"][0] == 0.06
    assert check.worst["routing.differs.later"][0] == pytest.approx(0.05)


def test_the_program_builds_the_published_model_from_the_file(root):
    from multiverso_tpu.models.lm import LMConfig
    entry = entries.named(entries.bench_of(root), "configs", CONFIG)
    with open(os.path.join(root, entry["file"])) as f:
        config = json.load(f)
    config.pop("rehearsal")
    cfg = LMConfig.from_dict(config)
    assert cfg.parameters() == config["parameters"]["total"]
    assert (cfg.hidden, cfg.head_dim, cfg.n_kv_heads, cfg.window,
            cfg.dense_width, cfg.expert_width, cfg.shared_width,
            cfg.n_experts, cfg.top_k, cfg.routed_scale) \
        == (2048, 128, 8, 512, 8192, 512, 512, 256, 8, 2.5)
    assert cfg.heads_layout == (48, 64, 64, 64, 48)
    assert cfg.window_layout == (0, 1, 1, 1, 0)
    assert cfg.ffn_layout == (0, 1, 1, 1, 1) and cfg.experts_held == (0, 32)
    full, sliding = cfg.rotary_kinds
    assert (full.theta, full.lanes, full.yarn, round(full.factor, 7)) \
        == (500000.0, 64, (64.0, 64.0, 1.0, 4096.0), 1.4158883)
    assert (sliding.theta, sliding.lanes, sliding.yarn, sliding.factor) \
        == (10000.0, 128, (), 1.0)
    tables = 3 + sum(len(cfg.layer_shapes(i)) for i in range(5))
    assert tables == config["parameters"]["tables"] == 69


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_mixed_controls.py", what,
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
    caught = result["compared"][controls.IN_REHEARSAL[what]]
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
            "gradient.attn_gate", "gradient.router", "adam.moments",
            "adam.update", "routing.differs", "routing.differs.later",
            "routing.held_share.layer0"} <= set(result["compared"])
    assert result["metrics"] == {}      # no device number from a CPU run


def test_a_checkout_with_one_rotary_kind_a_model_fails_the_cell_at_once(
        tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit
    code than 0 and no result line. The parent has the trainer and the
    streams but no ``Rotary``: it is what the driver imports first."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    model = root / "multiverso_tpu" / "models" / "lm" / "model.py"
    model.write_text(model.read_text().replace("class Rotary:",
                                               "class _Rotary:"))
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

"""The cell `kimi48b.ps-8k` (layers that mix the sequence by the gated delta
rule's scan, one of latent attention without positions): its counting
functions by hand, its six readers on hand-built ``Observations``, its
entries, its configuration against the catalog's numbers, that its controls
fail in the rehearsal (on the repo and on the copy a later PR appended to)
and that a checkout without the scan fails the cell at once. (Its rehearsal
end to end is test_rehearse.py's, which runs every cell of BENCHMARK.json.)"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import kdashapes, lmshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_kda_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "kimi48b.ps-8k"
CONFIG = "kimi-linear-48b-a3b-l5"
SHAPES = {"family": "kda", "sequences": 2, "seq_len": 8192, "hidden": 2304,
          "attention_layout": ["kda", "kda", "kda", "mla", "kda"],
          "kda_heads": 32, "kda_head_dim": 128, "kda_conv": 4,
          "mla_heads": 32, "qk_dim": 192, "v_dim": 128, "kv_rank": 512,
          "rope_dim": 64, "ffn_layout": [0, 1, 1, 1, 1],
          "router_outputs": 256, "top_k": 8, "held": 8, "expert_width": 1024,
          "shared_width": 1024, "dense_width": 9216, "vocab": 20480,
          "layers": 4, "sparse_layers": 4, "dense_layers": 1,
          "parameters": 602434432}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ["trainer.attn_kda_ms_per_step.lm", "trainer.kda_conv_ms_per_step.lm",
       "trainer.kda_scan_ms_per_step.lm", "trainer.kda_scan_roofline.lm",
       "trainer.kda_decay_deep_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of it was `trainer.mfu_kda.lm` until then. The family names
# no kernel scope, so `trainer.attn_roofline.lm` is not this cell's
MERGED = ["trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "trainer.router_ms_per_step.lm",
         "trainer.experts_ms_per_step.lm", "trainer.head_ms_per_step.lm",
         "trainer.experts_roofline.lm", "trainer.experts_short_share.lm",
         "trainer.expert_load_max_over_mean.lm",
         "trainer.router_load_max_over_mean.lm",
         "trainer.shared_expert_ms_per_step.lm",
         "trainer.attn_mla_ms_per_step.lm", "table.adam_ms_per_step.lm",
         "table.adam_roofline.lm", "table.snapshot_ms_per_step.lm",
         "table.embed_rows_per_step.lm", "worker.ms_per_req.train",
         "server.ms_per_req.train", "server.dispatches_per_round.train",
         "client.wait_ms.train", "server.mailbox_wait_ms.train",
         "worker.mailbox_wait_ms.train", "table.device_ms_per_round.train",
         "table.gather_ms_per_round.train",
         "table.scatter_ms_per_round.train", "table.update_fast_share.train",
         "device.idle_share.train", "trainer.block_ms.train",
         "trainer.programs_built_in_window.train",
         "host.stall_ms_per_s.train", "host.frozen_ms_per_s.train",
         "host.beat_late_ms.train"]
# other families' readers: they must find nothing to read here
NOT_THIS_CELL = ["trainer.attn_roofline.lm", "trainer.attn_full_ms_per_step.lm",
                 "trainer.attn_blockdiff_ms_per_step.lm",
                 "trainer.attn_gate_ms_per_step.lm",
                 "trainer.hc_ms_per_step.lm", "trainer.indexer_ms_per_step.lm"]
FINDS_NOTHING = NOT_THIS_CELL
TOKENS = 2 * 8192


# -- the counting functions, by hand ------------------------------------------

def test_the_layers_by_kind():
    assert kdashapes.layers_of(SHAPES, "kda") == 4
    assert kdashapes.layers_of(SHAPES, "mla") == 1
    assert kdashapes.tokens(SHAPES) == TOKENS


def test_the_scan_is_counted_as_the_recurrence_a_position_a_head():
    tiny = dict(SHAPES, sequences=1, seq_len=1, kda_heads=1, kda_head_dim=2)
    # decay 4, S^T k 8, the write 8, S^T q 8; three passes
    assert kdashapes.scan_flops(tiny) == 3 * 28
    # q, k, g [2], v [2], beta [1] in, o [2] out, float32, three passes
    assert kdashapes.scan_bytes(tiny) == 3 * 4 * 11
    assert kdashapes.scan_flops(SHAPES) == 3 * 7 * 128 * 128 * 32 * TOKENS
    assert kdashapes.scan_bytes(SHAPES) == 3 * 4 * 641 * 32 * TOKENS
    # the bytes decide: 4.9 ms of them a layer, 0.9 ms of operations
    assert kdashapes.scan_bytes(SHAPES) / 819e9 \
        > 4 * kdashapes.scan_flops(SHAPES) / 197e12


def test_the_projections_a_token():
    lanes = 32 * 128
    assert kdashapes.kda_dense_flops(SHAPES) == 2 * (
        4 * 2304 * lanes + 2 * 2304 * 128 + 2 * 128 * lanes + 2304 * 32)
    # the parameters of the nine matrices, twice
    assert kdashapes.kda_dense_flops(SHAPES) == 2 * 39460864
    assert kdashapes.mla_dense_flops(SHAPES) == 2 * (
        2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304)
    assert kdashapes.mla_dense_flops(SHAPES) == 2 * (29117184 - 2304 - 512)
    assert kdashapes.conv_flops(SHAPES) == 3 * 3 * 2 * 4 * lanes * TOKENS


def test_step_flops_are_the_issue_s_arithmetic():
    even = 4 * TOKENS * 8 * 8 // 256     # four sparse layers' even share
    flops = kdashapes.step_flops(1, even, SHAPES)
    through = 3 * TOKENS * kdashapes.token_flops(SHAPES)
    assert flops == (
        4 * (kdashapes.scan_flops(SHAPES) + kdashapes.conv_flops(SHAPES))
        + kdashapes.mla_attention_flops(SHAPES) + through
        + lmshapes.expert_flops(even, 2304, 1024))
    assert 32.2e12 < through < 32.4e12      # 32.3 TFLOP every token's
    assert 0.47 < 4 * kdashapes.kda_dense_flops(SHAPES) \
        / kdashapes.token_flops(SHAPES) < 0.49          # 48% of them
    assert 4.1e12 < kdashapes.mla_attention_flops(SHAPES) < 4.2e12
    assert 0.18 < flops / 197e12 < 0.20     # 0.19 s at the chip's peak
    assert kdashapes.step_flops(2, 2 * even, SHAPES) == 2 * flops


# -- the readers ---------------------------------------------------------------

class _Window:
    def __init__(self, rounds=0, seconds=0.0, counters=None):
        self.rounds, self.seconds = rounds, seconds
        self.counters = counters or {}
        self.at_open = {}


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


STEPS = 3
TRACE = {"window_s": 4.7, "programs": {}, "scopes": {
    "jit_forward": {"mv.lm.attn.kda": 0.126,
                    "mv.lm.attn.kda.conv": 0.040,
                    "mv.lm.attn.kda.scan": 0.288,
                    "mv.lm.attn.mla.kernel": 0.060,
                    "mv.lm.experts": 0.040},
    "jit_backward": {"mv.lm.attn.kda": 0.474, "mv.lm.attn.kda.conv": 0.180,
                     "mv.lm.attn.kda.scan": 1.459,
                     "mv.lm.attn.mla.kernel": 0.180, "mv.lm.experts": 0.090},
    "jit_head_step": {"mv.lm.head": 0.110}}}
TRACED = _count(LM_STEP=STEPS, LM_HELD_ASSIGNMENTS=STEPS * 16000)
CHANNELS = 4 * 2 * 128 * 32 * 128       # a step: layers, sequences, chunks..
WINDOW = _count(LM_STEP=15, LM_HELD_ASSIGNMENTS=15 * 16000,
                LM_TOKENS=15 * TOKENS, LM_KDA_TOKENS=15 * 4 * TOKENS,
                LM_KDA_CHUNKS=15 * 4 * 2 * 128,
                LM_KDA_DECAY_CHANNELS=15 * CHANNELS,
                LM_KDA_DECAY_DEEP=15 * CHANNELS // 8)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 4.7, traced),
        window=_Window(15, 21.9, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.attn_kda_ms_per_step.lm": 600.0 / STEPS,
    "trainer.kda_conv_ms_per_step.lm": 220.0 / STEPS,
    "trainer.kda_scan_ms_per_step.lm": 1747.0 / STEPS,
    "trainer.kda_scan_roofline.lm":
        100 * STEPS * 4 * kdashapes.scan_bytes(SHAPES) / 819e9 / 1.747,
    "trainer.kda_decay_deep_share.lm": 12.5,
    "trainer.mfu.lm":
        100 * kdashapes.step_flops(15, 15 * 16000, SHAPES) / 197e12 / 21.9,
}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    value = _read(name, _obs())
    assert value == pytest.approx(WANT[name])
    if "roofline" in name or "mfu" in name:
        assert 0 < value < 100


def test_the_scan_s_share_cannot_pass_a_hundred_whatever_the_chunk():
    """The least time is the model's bytes at the memory's peak: a scan
    that took no longer than that would read 100."""
    least = STEPS * 4 * kdashapes.scan_bytes(SHAPES) / 819e9
    trace = {"window_s": 1.0, "programs": {}, "scopes": {
        "jit_backward": {"mv.lm.attn.kda.scan": least}}}
    assert _read("trainer.kda_scan_roofline.lm", _obs(trace=trace)) \
        == pytest.approx(100.0)


def test_no_deep_channel_reads_zero_and_not_nothing():
    window = {k: v for k, v in WINDOW.items() if k != "LM_KDA_DECAY_DEEP"}
    assert _read("trainer.kda_decay_deep_share.lm", _obs(window=window)) == 0.0


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_spans(name):
    """A parent commit runs the readers too, and so do the other
    language-model cells: no scope, no counter, no shape of this family,
    and no exception."""
    bare_trace = {"window_s": 3.0, "scopes": {"jit_step": {"mv.sgns.step": 1}},
                  "programs": {"jit_step": {"seconds": 1.0, "count": 9}}}
    assert _read(name, _obs(trace=bare_trace, traced={}, window={},
                            shapes={})) is None
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    if name in MERGED:
        return      # one reader for every cell: it reads xing29b.ps-4k's too
    # xing29b.ps-4k: latent attention in every layer, its own shapes
    other = {"family": "mla", "sequences": 2, "seq_len": 4096, "hidden": 3584,
             "heads_held": 4, "qk_dim": 192, "v_dim": 128, "vocab": 16384}
    latent = {"window_s": 3.0, "programs": {}, "scopes": {"jit_forward": {
        "mv.lm.attn.mla.kernel": 0.06, "mv.lm.experts": 0.2}}}
    counts = _count(LM_STEP=8, LM_HELD_ASSIGNMENTS=8 * 16384,
                    LM_TOKENS=8 * 8192)
    assert _read(name, _obs(trace=latent, traced=counts, window=counts,
                            shapes=other)) is None


@pytest.mark.parametrize("name", FINDS_NOTHING)
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
    assert cell["traffic"] == "lm-ps-step-8k"       # no mix of its own
    config = entries.named(bench, "configs", CONFIG)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
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
        "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
        "linear_attn_config": {
            "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
            "kda_layers": [1, 2, 3, 5, 6, 7, 9, 10, 11, 13, 14, 15, 17, 18,
                           19, 21, 22, 23, 25, 26],
            "num_heads": 32, "short_conv_kernel_size": 4},
        "mla_use_nope": True, "model_max_length": 1048576,
        "model_type": "kimi_linear", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "moe_renormalize": True,
        "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
        "num_expert_group": 1, "num_experts": 256,
        "num_experts_per_token": 8, "num_hidden_layers": 27,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 0,
        "num_shared_experts": 1, "q_lora_rank": None,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
        "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
        "vocab_size": 163840}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] < value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"]) == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (5, 8, 20480)
    assert config["router_outputs"] == 256 and config["size_that_ran"]
    assert config["deployment"]["chips_per_layer"] == 32
    parts = config["parameters"]
    assert parts["total"] == SHAPES["parameters"] == (
        parts["dense_delta_layer"] + 3 * parts["sparse_delta_layer"]
        + parts["sparse_latent_layer"] + parts["embedding_and_head"]
        + parts["final_norm"])
    assert {"low_rank_widths", "decay_form", "conv_activation", "qk_l2_norm",
            "output_gate", "router", "decay_init", "optimizer",
            "init"} <= set(config["assumed"])
    assert entry["source"] == config["source"]
    for limits in (config["limits"], config["rehearsal"]["limits"]):
        assert {"loss", "gradient.table", "gradient.gate", "gradient.router",
                "gradient.scores", "gradient.scan", "adam.moments",
                "adam.update", "bias.differs", "adds.extra",
                "routing.differs", "scan.carry", "layer.output",
                "what"} == set(limits)
    assert "float32" in config["guarantees"]["arithmetic"]


def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_kda_controls.py", what,
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
    if what in ("bfloat16_moments", "bfloat16_state"):
        # whatever the model computed: no gradient's limit is passed
        for name in ("loss", "gradient.table", "gradient.scan",
                     "layer.output"):
            assert result["compared"][name]["value"] \
                <= result["compared"][name]["limit"]


def test_the_unchanged_program_passes_beside_the_controls(root, tmp_path):
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.gate", "gradient.router",
            "gradient.scores", "gradient.scan", "adam.moments",
            "adam.update", "bias.differs", "adds.extra", "routing.differs",
            "scan.carry", "layer.output", "layer.output.layer0",
            "routing.differs.layer0",
            "routing.held_share.layer0"} <= set(result["compared"])


def test_a_checkout_without_the_scan_fails_the_cell_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit
    code than 0 and no result line. The parent has the trainer but no
    ``models/lm/delta.py``: the driver asks for it first."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    os.remove(root / "multiverso_tpu" / "models" / "lm" / "delta.py")
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

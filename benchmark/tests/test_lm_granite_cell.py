"""The cell `granite3b.ps-8k`: its readers on hand-built ``Observations``, the
counting functions at this cell's shapes by hand (BY LAYER KIND: nine
state-space layers, one of attention at 64 lanes, ten dense MLPs, no
experts), the older readers' counts there, its entries by name, its
configuration against the catalog's numbers, its rehearsal, that each control
fails the limit named for it (on the repo and on the copy a later PR appended
to) and that a checkout which has no state-space layer fails the cell at
once."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark.lib import lmshapes, ssdshapes
from benchmark.lib.harness import Observations
from benchmark.run import load_module
from benchmark.tests import entries
from benchmark.tools import lm_granite_controls as controls

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "granite3b.ps-8k"
CONFIG = "granite-4.0-h-micro-l10"
LAYOUT = ["ssd"] * 5 + ["gqa"] + ["ssd"] * 4
# what benchmark/drivers/lm_granite.py fills: no layer has routed experts
SHAPES = {"family": "ssd", "sequences": 2, "seq_len": 8192, "hidden": 2048,
          "attention_layout": LAYOUT, "ssd_heads": 64, "ssd_head_dim": 64,
          "ssd_state": 128, "ssd_chunk": 256, "heads": 32,
          "kv_heads": 8, "head_dim": 64, "router_outputs": 0, "top_k": 0,
          "held": 0, "expert_width": 0, "dense_width": 8192, "vocab": 12544,
          "layers": 0, "sparse_layers": 0, "dense_layers": 10,
          "parameters": 772160448}
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# the state-space layer's eight readers (PR 67: PR 65 wrote them, read them
# once on the chip and took them out of a list that was full)
NEW = ["trainer.ssd_ms_per_step.lm", "trainer.ssd_scan_ms_per_step.lm",
       "trainer.ssd_scan_roofline.lm", "trainer.ssd_conv_ms_per_step.lm",
       "trainer.ssd_gate_ms_per_step.lm", "trainer.ssd_gate_roofline.lm",
       "trainer.ssd_decay_deep_share.lm", "trainer.ssd_scan_kernel_share.lm"]
# ONE reader for every family since PR 67 (benchmark/lib/families.py): this
# cell's share of them was `trainer.attn_full_roofline_d64.lm` and
# `trainer.mfu_granite.lm` until then
MERGED = ["trainer.attn_roofline.lm", "trainer.mfu.lm"]
# the older readers the cell reports unedited
OLDER = ["words_per_s", "peak_hbm_gb", "setup.table_init_s",
         "trainer.attn_full_ms_per_step.lm",
         "trainer.attn_lanes_used_share.lm",
         "trainer.attn_blocks_fitted_share.lm",
         "trainer.shared_expert_ms_per_step.lm",
         "trainer.head_ms_per_step.lm", "table.adam_ms_per_step.lm",
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
# nothing to read here: no router, no experts, no pass between the
# projections and the kernel, other kinds of layer
NOT_JOINED = ["trainer.router_ms_per_step.lm", "trainer.experts_ms_per_step.lm",
              "trainer.experts_roofline.lm", "trainer.experts_short_share.lm",
              "trainer.expert_load_max_over_mean.lm",
              "trainer.router_load_max_over_mean.lm",
              "trainer.attn_pass_fused_share.lm",
              "trainer.shortconv_ms_per_step.lm",
              "trainer.kda_scan_ms_per_step.lm",
              "table.scatter_ms_per_round.train",
              "table.update_fast_share.train"]
NOTHING_TO_READ = ["trainer.kda_scan_roofline.lm",
                   "trainer.kda_scan_kernel_share.lm",
                   "trainer.kda_decay_deep_share.lm",
                   "trainer.shortconv_ms_per_step.lm",
                   "trainer.shortconv_roofline.lm"]
TOKENS = 2 * 8192
PAIRS = 8192 * 8193 // 2


# -- the counting functions, by hand -------------------------------------------------

def test_a_state_space_layer_counts_two_products_and_its_scan():
    # W_in [2048, 8512] and W_out [4096, 2048]
    assert ssdshapes.ssd_dense_flops(SHAPES) == 2 * 2048 * 8512 \
        + 2 * 4096 * 2048
    # a position forward at chunks of 256: 128.5 pairs x 2 (128 + 4096), the
    # state read and written 2 x 2 x 4096 x 128
    position = 128.5 * 2 * (128 + 4096) + 4 * 4096 * 128
    assert ssdshapes.scan_flops(SHAPES) == int(3 * position * TOKENS)
    # the function at another chunk: fewer pairs, the same states
    half = dict(SHAPES, ssd_chunk=128)
    assert ssdshapes.scan_flops(half) == int(
        3 * (64.5 * 2 * 4224 + 4 * 4096 * 128) * TOKENS)


def test_the_attention_layer_counts_causal_pairs_at_64_lanes():
    assert ssdshapes.attention_flops(SHAPES) == 3 * 2 * 128 * 32 * 2 * PAIRS
    assert ssdshapes.gqa_dense_flops(SHAPES) == 2 * 2048 * 64 * (64 + 16)


def test_every_token_s_products_and_a_step():
    mlp = 3 * 2 * 2048 * 8192
    token = (9 * ssdshapes.ssd_dense_flops(SHAPES)
             + ssdshapes.gqa_dense_flops(SHAPES) + 10 * mlp
             + 2 * 2048 * 12544)
    assert ssdshapes.token_flops(SHAPES) == token
    step = 9 * ssdshapes.scan_flops(SHAPES) \
        + ssdshapes.attention_flops(SHAPES) + 3 * TOKENS * token
    assert ssdshapes.step_flops(4, SHAPES) == 4 * step
    assert 78e12 < step < 80e12     # ISSUE 65's ~79 TFLOP a step
    # the nine mixers are near a third of it, the MLPs over three fifths
    mixers = 9 * (ssdshapes.scan_flops(SHAPES)
                  + 3 * TOKENS * ssdshapes.ssd_dense_flops(SHAPES))
    assert 0.28 < mixers / step < 0.34
    assert 0.60 < 3 * TOKENS * 10 * mlp / step < 0.65


# -- the reader -------------------------------------------------------------------------

class _Window:
    def __init__(self, rounds, seconds, counters):
        self.rounds, self.seconds, self.counters = rounds, seconds, counters


def _count(**kw):
    return {name: {"count": n, "ms": 0.0} for name, n in kw.items()}


STEPS, RUNS = 3, 19
SCOPES = {"jit_forward": {"mv.lm.attn.ssd": 0.38, "mv.lm.attn.ssd.scan": 0.06,
                          "mv.lm.attn.ssd.conv": 0.05,
                          "mv.lm.attn.ssd.gate": 0.03,
                          "mv.lm.attn.full.kernel": 0.05,
                          "mv.lm.dense_mlp": 0.28},
          "jit_backward": {"mv.lm.attn.ssd": 0.39, "mv.lm.attn.ssd.scan": 0.20,
                           "mv.lm.attn.ssd.conv": 0.21,
                           "mv.lm.attn.ssd.gate": 0.06,
                           "mv.lm.attn.full.kernel": 0.11,
                           "mv.lm.dense_mlp": 0.77}}
TRACE = {"window_s": 3.6, "scopes": SCOPES, "programs": {}}
TRACED = _count(LM_STEP=STEPS, LM_TOKENS=STEPS * TOKENS)
WINDOW = _count(LM_STEP=RUNS, LM_TOKENS=RUNS * TOKENS,
                LM_MIXERS_SSD=RUNS * 18, LM_MIXERS=RUNS * 20,
                LM_SSD_CHUNKS=RUNS * 18 * 32, LM_SSD_DEEP=RUNS * 18 * 32 * 33,
                LM_SSD_SCAN_KERNEL=RUNS * 18,
                LM_ATTN_LANES=RUNS * 2 * 64, LM_ATTN_LANES_TILED=RUNS * 2 * 64)


def _obs(trace=TRACE, traced=TRACED, window=WINDOW, shapes=SHAPES):
    return Observations(
        trace=trace, traced=_Window(STEPS, 3.6, traced),
        window=_Window(RUNS, 20.9, window), shapes=shapes, peaks=PEAKS)


def _read(name, obs):
    return load_module("metrics", name).read(obs)


WANT = {
    "trainer.ssd_ms_per_step.lm": (380 + 60 + 50 + 30 + 390 + 200 + 210 + 60)
    / STEPS,
    "trainer.ssd_scan_ms_per_step.lm": 260.0 / STEPS,
    # X in and Y out, B, C and dt a position a pass: 12 x 8,512 bytes
    "trainer.ssd_scan_roofline.lm":
        100 * STEPS * 9 * 12 * 8512 * TOKENS / 819e9 / 0.26,
    "trainer.ssd_conv_ms_per_step.lm": 260.0 / STEPS,
    "trainer.ssd_gate_ms_per_step.lm": 90.0 / STEPS,
    # eight float32 arrays of 4,096 lanes a position
    "trainer.ssd_gate_roofline.lm":
        100 * STEPS * 9 * 32 * 4096 * TOKENS / 819e9 / 0.09,
    "trainer.ssd_decay_deep_share.lm": 100 * 33 / 64,
    "trainer.ssd_scan_kernel_share.lm": 100.0,
    "trainer.attn_roofline.lm":
        100 * STEPS * ssdshapes.attention_flops(SHAPES) / 197e12 / 0.16,
    "trainer.mfu.lm": 100 * ssdshapes.step_flops(RUNS, SHAPES) / 197e12 / 20.9,
}


def test_the_wanted_values_are_all_the_new_metrics():
    assert sorted(WANT) == sorted(NEW + MERGED)
    assert 30 < WANT["trainer.mfu.lm"] < 40     # my chip run, PR 65: 36.4


@pytest.mark.parametrize("name", NEW + MERGED)
def test_reader(name):
    assert _read(name, _obs()) == pytest.approx(WANT[name])
    if "roofline" in name or "mfu" in name:
        assert 0 < WANT[name] < 100


def test_the_byte_counts_by_hand():
    assert ssdshapes.scan_bytes(SHAPES) == 3 * 4 * (2 * 4096 + 2 * 128 + 64) \
        * TOKENS
    assert ssdshapes.gate_bytes(SHAPES) == 4 * 8 * 4096 * TOKENS
    # the chunk is the implementation's: it does not move what the layer needs
    assert ssdshapes.scan_bytes(dict(SHAPES, ssd_chunk=128)) \
        == ssdshapes.scan_bytes(SHAPES)
    # memory-bound: the bytes need 2.5 times what the operations do
    bytes_s = ssdshapes.scan_bytes(SHAPES) / 819e9
    assert 2.4 < bytes_s / (ssdshapes.scan_flops(SHAPES) / 197e12) < 2.7


def test_a_scan_at_its_bytes_reads_a_hundred_and_no_more():
    """The least time is the layers' bytes at the memory's peak: a scan or a
    gate that took no longer would read 100."""
    for scope, least in (
            ("mv.lm.attn.ssd.scan", ssdshapes.scan_bytes(SHAPES)),
            ("mv.lm.attn.ssd.gate", ssdshapes.gate_bytes(SHAPES))):
        trace = {"window_s": 1.0, "programs": {}, "scopes": {
            "jit_backward": {scope: STEPS * 9 * least / 819e9}}}
        name = f"trainer.ssd_{scope.rsplit('.', 1)[1]}_roofline.lm"
        assert _read(name, _obs(trace=trace)) == pytest.approx(100.0)


def test_no_deep_pair_reads_zero_and_the_plain_scan_reads_zero_not_nothing():
    window = {k: v for k, v in WINDOW.items() if k != "LM_SSD_DEEP"}
    assert _read("trainer.ssd_decay_deep_share.lm", _obs(window=window)) == 0.0
    plain = dict(window, **_count(LM_SSD_SCAN_PLAIN=RUNS * 18))
    del plain["LM_SSD_SCAN_KERNEL"]
    assert _read("trainer.ssd_scan_kernel_share.lm", _obs(window=plain)) == 0.0


@pytest.mark.parametrize("name", NEW + MERGED)
def test_a_reader_reads_nothing_from_a_program_without_its_shapes(name):
    """A parent commit runs the readers too, and so could another cell: no
    such scope, no such counter, no such shape, and no exception."""
    assert _read(name, _obs(trace=None, traced={}, window={}, shapes={})) \
        is None
    if name in MERGED:
        return      # one reader for every cell: it reads lfm8b.ps-8k's too
    # lfm8b.ps-8k: attention at 64 lanes too, its own mixers and counters
    lfm = {"family": "conv", "sequences": 2, "seq_len": 8192, "hidden": 2048,
           "conv_taps": 3, "heads": 32, "kv_heads": 8, "head_dim": 64,
           "attention_layout": ["conv", "conv", "gqa", "conv"] * 2}
    other = {"window_s": 3.0, "programs": {}, "scopes": {"jit_backward": {
        "mv.lm.attn.full.kernel": 0.05, "mv.lm.attn.shortconv": 0.3}}}
    counts = _count(LM_STEP=8, LM_TOKENS=8 * TOKENS, LM_MIXERS=8 * 16,
                    LM_HELD_ASSIGNMENTS=8 * 6 * 16384)
    assert _read(name, _obs(trace=other, traced=counts, window=counts,
                            shapes=lfm)) is None


@pytest.mark.parametrize("name", NOTHING_TO_READ)
def test_the_other_models_readers_find_nothing_in_this_cell(name):
    assert _read(name, _obs()) is None


def test_the_shared_readers_count_this_cell_by_its_own_shapes():
    """The older readers at this cell's shapes: the attention kernel's share
    counts ONE layer's causal pairs at 32 heads of 64 lanes, the dense MLPs'
    reader every layer's, the lanes' share 100, Adam's bytes every parameter
    but the table's rows, which the steps' counted rows bring."""
    obs = _obs()
    assert _read("trainer.shared_expert_ms_per_step.lm", obs) \
        == pytest.approx(1050 / STEPS)
    assert _read("trainer.attn_lanes_used_share.lm", obs) == 100.0
    assert lmshapes.adam_bytes(1, 4000, SHAPES) == 28 * (
        772160448 - 12544 * 2048 + 4000 * 2048)


# -- the entries, by name --------------------------------------------------------------

@pytest.mark.parametrize("name", NEW + MERGED)
def test_entry(name, root):
    bench = entries.bench_of(root)
    metric = entries.named(bench, "per_layer", name)
    entries.check_entry(root, bench, "per_layer", metric)
    assert CELL in metric["workloads"] and metric["moves"] == "words_per_s"
    assert metric["layer"] == "trainer"
    assert metric["unit"] == ("ms" if "_ms_" in name else "%")
    if name in NEW:     # a traced or a counted number
        assert metric["source"] == ("program_counter" if "share" in name
                                    else "device_trace")
    assert set(metric) == {"name", "unit", "better", "source", "layer",
                           "moves", "workloads"}


def test_the_cell_and_its_configuration_are_found_by_name(root):
    bench = entries.bench_of(root)
    cell = entries.named(bench, "workloads", CELL)
    assert cell["chips"] == 1 and cell["config"] == CONFIG
    assert cell["traffic"] == "lm-ps-step-8k"
    entry = entries.named(bench, "configs", CONFIG)
    assert sorted(entry["reduced"]) == ["num_hidden_layers", "vocab_size"]
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
    types = (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    published = {     # the catalog's `config`, every key
        "attention_bias": False, "attention_multiplier": 0.015625,
        "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "layer_types": types,
        "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64,
        "mamba_d_state": 128, "mamba_expand": 2, "mamba_n_groups": 1,
        "mamba_n_heads": 64, "mamba_proj_bias": False,
        "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid",
        "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40,
        "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
        "shared_intermediate_size": 8192, "tie_word_embeddings": True,
        "vocab_size": 100352}
    for key, value in published.items():
        if key in entry["reduced"]:
            assert config["published"][key] == value
            assert config[key] != value and key in config["reduced"]
        else:
            assert config[key] == value, key
    assert sorted(entry["reduced"]) == sorted(config["reduced"])
    assert (config["num_hidden_layers"], config["vocab_size"]) == (
        10, 100352 // 8)
    assert "head_dim" not in config     # hidden / heads: ``assumed``
    assert config["deployment"]["chips_per_layer"] == 1
    sizes = config["parameters"]
    assert sizes["ssd_mixer"] == 17432576 + 8388608 + 17408 + 4352 \
        + 3 * 64 + 4096 == 25847232
    assert sizes["attention_mixer"] == 2 * 4194304 + 2 * 1048576
    assert sizes["mlp"] == 3 * 2048 * 8192
    assert sizes["ssd_layer"] == sizes["ssd_mixer"] + sizes["layer_norms"] \
        + sizes["mlp"] == 76182976
    assert sizes["attention_layer"] == sizes["attention_mixer"] \
        + sizes["layer_norms"] + sizes["mlp"] == 60821504
    assert sizes["ten_layers"] == 9 * sizes["ssd_layer"] \
        + sizes["attention_layer"] == 746468288
    assert sizes["total"] == SHAPES["parameters"] == sizes["ten_layers"] \
        + sizes["table"] + sizes["final_norm"]
    assert {"head_dim", "ssd_init", "scan_chunk", "conv", "attention",
            "sequence_and_batch", "optimizer", "init"} <= set(
                config["assumed"])
    assert config["scan_chunk"] in (128, 256)
    assert config["init_std"] == config["embedding_init_std"] == 0.02
    assert entry["source"] == config["source"]
    assert "ran" in config["size_that_ran"]
    assert "one_add_a_table_a_step" in config["guarantees"]
    assert set(controls.CAUGHT_BY.values()) | {
        "loss", "gradient.table", "gradient.ssd", "gradient.ssd_small",
        "gradient.decay", "gradient.attention", "gradient.mlp",
        "gradient.tied", "adam.update", "layer.output", "scan.carry"} \
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
    assert (cfg.hidden, cfg.head_dim, cfg.dense_width, cfg.n_experts,
            cfg.top_k, cfg.n_heads, cfg.n_kv_heads, cfg.ssd_heads,
            cfg.ssd_head_dim, cfg.ssd_state, cfg.ssd_conv) == (
        2048, 64, 8192, 0, 0, 32, 8, 64, 64, 128, 4)
    assert list(cfg.attention_layout) == LAYOUT and cfg.tied
    assert not any(cfg.ffn_layout) and not any(cfg.rope_layout)
    assert cfg.ssd_chunk == config["scan_chunk"]
    tables = 2 + sum(len(cfg.layer_shapes(i)) for i in range(10))
    assert tables == config["parameters"]["tables"] == 128


# -- the rehearsal and the controls ---------------------------------------------------------

def _control(root, what, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    done = subprocess.run(
        [sys.executable, "benchmark/tools/lm_granite_controls.py", what,
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
    assert not caught["value"] <= caught["limit"]
    if what in ("bfloat16_moments", "bfloat16_state"):  # by ONE limit
        over = [name for name, c in result["compared"].items()
                if not c["value"] <= c["limit"]]
        assert over == [controls.CAUGHT_BY[what]]
    if what == "ssd_where_attention":   # nothing else can be compared
        assert set(result["compared"]) == {"non_finite_losses",
                                           "layout.differs"}


def test_the_rehearsal_passes_beside_the_controls(root, tmp_path):
    """The driver's rehearsal on the CPU, end to end: `correct`, every
    limit compared."""
    result = _control(root, "none", tmp_path)
    assert result["correct"] is True and result["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in result["compared"].values())
    assert {"loss", "gradient.table", "gradient.ssd", "gradient.ssd_small",
            "gradient.decay", "gradient.attention", "gradient.mlp",
            "gradient.tied", "adam.moments", "adam.update", "adds.extra",
            "layout.differs", "layer.output", "scan.carry",
            "layer.output.layer9"} <= set(result["compared"])


def test_a_checkout_that_has_no_state_space_layer_fails_at_once(tmp_path):
    """The driver tries each new cell on the parent commit with this
    benchmark laid over it: the run has to end soon, with another exit code
    than 0 and no result line. The parent has no ``models/lm/ssd.py``: the
    driver imports it before ``mv.init``."""
    root = tmp_path / "parent"
    shutil.copytree(ROOT, root, ignore=shutil.ignore_patterns(
        ".git", ".jax_cache", "chiprun_out", "__pycache__", ".chipwork",
        ".pytest_cache"))
    os.remove(root / "multiverso_tpu" / "models" / "lm" / "ssd.py")
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

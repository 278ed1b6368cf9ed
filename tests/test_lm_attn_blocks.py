"""The tile sizes ``model._splash`` gives the library's splash attention
kernels (``model.attention_blocks``: a function of the mask's kind and
reach, the length, the heads' lanes and the query heads a group): the
kernels at the CHOSEN sizes, interpreted on the CPU, against
``blockwise_attention`` (outputs and the three gradients); the rule's
fall-back where a length is not a whole number of a size; the trainer's
counters, which ask the same function; and the benchmark's reader of the
pair. ``tests/test_row_scatter_tpu_compile.py`` compiles every chosen size
for a described v5e at the cells' real shapes."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.lm import LMConfig, PSLMTrainer, model as lm, \
    ps_train
from multiverso_tpu.util import dashboard

F32, BF16 = jnp.float32, jnp.bfloat16

# what ``attention_core`` asks ``_splash`` in the cells (BENCHMARK.json):
# the mask, the positions, q . k's and v's lanes, the query heads a group
KINDS = {
    "causal_128_lanes": (0, 8192, 128, 128, 6),           # laguna
    "causal_128_lanes_7": (0, 8192, 128, 128, 7),         # st21b (solar: 8)
    "window_512": (512, 8192, 128, 128, 8),               # laguna
    "window_4096": (4096, 8192, 128, 128, 7),             # st21b
    "causal_256_lanes": (0, 8192, 256, 256, 1),           # glm
    "causal_192_lanes": (0, 8192, 192, 128, 1),           # kimi
    "causal_192_lanes_4k": (0, 4096, 192, 128, 1),        # xing
    "block_diffusion": (lm.Mask.blockdiff(4096, 4), 8192, 128, 128, 8)}  # sdar


def _short(mask, t):
    """The kind's mask at ``t`` positions (a block-diffusion mask is of its
    length)."""
    mask = lm.Mask.of(mask)
    return lm.Mask.blockdiff(t // 2, mask.block) \
        if mask.kind == "blockdiff" else mask


# -- the kernels at the chosen sizes ----------------------------------------------

@pytest.mark.parametrize("kind", sorted(KINDS))
def test_the_kernels_at_the_chosen_sizes_are_blockwise_attention(kind):
    """2048 positions (the cells' sizes cut to it: a wanted 2048 stays, so
    the eight fields still differ from each other where the rule's do),
    two query heads a key-value head, the kernel interpreted: the output
    and the gradients of q, k and v against the ``jax.numpy`` sum over the
    same unmasked blocks."""
    mask, t_cell, qk, v_lanes, _ = KINDS[kind]
    t, per = 2048, 2
    mask = _short(mask, t)
    blocks = lm._blocks_within(lm._wanted_blocks(
        lm.Mask.of(KINDS[kind][0]), t_cell, qk, v_lanes, KINDS[kind][4]), t)
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, per, t, qk)) * qk ** -0.5, BF16)
    k = jnp.asarray(rng.normal(size=(1, t, qk)), BF16)
    v = jnp.asarray(rng.normal(size=(1, t, v_lanes)), BF16)
    do = jnp.asarray(rng.normal(size=(1, per, t, v_lanes)), BF16)
    kernel = lm._splash_at(t, per, mask, blocks, interpret=True)
    got, pull = jax.vjp(jax.vmap(kernel), q, k, v)
    want, pull_want = jax.vjp(
        lambda q, k, v: lm.blockwise_attention(q, k, v, mask), q, k, v)
    for a, b in zip((got,) + pull(do), (want,) + pull_want(do)):
        a, b = (np.asarray(x, np.float64) for x in (a, b))
        assert np.linalg.norm(a - b) <= 1e-2 * np.linalg.norm(b)


# -- the rule -----------------------------------------------------------------------

def _holds(blocks, t):
    return (all(t % b == 0 and b % 128 == 0 for b in blocks)
            and blocks.block_kv % blocks.block_kv_compute == 0
            and blocks.block_kv_dkv % blocks.block_kv_dkv_compute == 0)


@pytest.mark.parametrize("t", [
    128, 256, 384,              # under every size
    640, 1152, 3072, 6144, 8192 + 256,      # no whole number of the sizes
    4096, 8192, 16384])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_every_size_divides_the_length(kind, t):
    mask, _, qk, v_lanes, per = KINDS[kind]
    if t % 8:
        pytest.skip("a block-diffusion sequence is two copies of blocks")
    blocks = lm.attention_blocks(_short(mask, t), t, qk, v_lanes, per)
    assert _holds(blocks, t), blocks
    if t <= 384:    # one block, as ``min(512, t)`` was
        assert set(blocks) == {t}


def test_xing_s_4096_positions_get_sizes_that_divide_them():
    blocks = lm.attention_blocks(0, 4096, 192, 128, 1)
    assert _holds(blocks, 4096) and max(blocks) <= 4096


def test_the_rule_sees_its_arguments_alone():
    """The same window at the same length and lanes gets the same sizes
    whoever asks, short form or long; and the fused backward kernel stays
    off in every ``BlockSizes`` the package builds."""
    assert lm.attention_blocks(512, 8192, 128, 128, 8) \
        == lm.attention_blocks(lm.Mask("window", window=512), 8192, 128,
                               128, 8)
    kernel = lm._splash_at(256, 1, 0, lm._blocks_within(lm.PLAIN_BLOCKS, 256),
                           interpret=True)
    assert not kernel.kwargs["block_sizes"].use_fused_bwd_kernel


# -- the counters -------------------------------------------------------------------

def _counted():
    monitors = dashboard.metrics_snapshot(max_samples=0)["monitors"]
    return [monitors.get(n, {"count": 0})["count"]
            for n in ("LM_ATTN_BLOCKS_FITTED", "LM_ATTN_BLOCKS_PLAIN")]


def test_the_counter_s_name_is_the_rule_s_answer(monkeypatch):
    """FITTED where ``attention_blocks`` gives other sizes than 512
    everywhere, PLAIN where it keeps them, nothing where no kernel runs
    (no TPU, no whole 128 positions)."""
    assert lm.attention_blocks_name(512, 8192, 128, 128, 8) is None
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lm.attention_blocks_name(0, 96) is None
    for kind, (mask, t, qk, v_lanes, per) in KINDS.items():
        plain = lm.attention_blocks(mask, t, qk, v_lanes, per) \
            == lm._blocks_within(lm.PLAIN_BLOCKS, t)
        assert lm.attention_blocks_name(mask, t, qk, v_lanes, per) == (
            "LM_ATTN_BLOCKS_PLAIN" if plain else "LM_ATTN_BLOCKS_FITTED"), kind
    # a rule that keeps 512 for everything counts PLAIN: the counter calls
    # the function the program calls
    monkeypatch.setattr(lm, "_wanted_blocks", lambda *a: lm.PLAIN_BLOCKS)
    assert lm.attention_blocks_name(512, 8192, 128, 128, 8) \
        == "LM_ATTN_BLOCKS_PLAIN"
    monkeypatch.setattr(lm, "_wanted_blocks",
                        lambda *a: lm.Blocks(*[256] * 8))
    assert lm.attention_blocks_name(0, 8192, 256, 256, 1) \
        == "LM_ATTN_BLOCKS_FITTED"


@pytest.mark.parametrize("names, sequences, fitted, plain", [
    (["LM_ATTN_BLOCKS_FITTED"] * 5, 2, 10, 0),
    (["LM_ATTN_BLOCKS_PLAIN"] * 4, 2, 0, 8),
    (["LM_ATTN_BLOCKS_PLAIN"] + ["LM_ATTN_BLOCKS_FITTED"] * 3, 2, 6, 2),
    ([None] * 5, 2, 0, 0)])     # delta layers, a selected attention, no TPU
def test_the_trainer_counts_one_a_layer_a_sequence(names, sequences, fitted,
                                                   plain):
    trainer = PSLMTrainer.__new__(PSLMTrainer)
    trainer.cfg = _config("smallthinker-21ba3b-l4")
    trainer._sparse, trainer._experts_cap = [1] * len(names), 1 << 30
    trainer._attn_pass, trainer._heads = [], (1, 1)
    trainer._attn_blocks = names
    before = _counted()
    trainer._count_stats(([np.zeros((sequences, 2), int)] * len(names), 5, 7))
    assert [a - b for a, b in zip(_counted(), before)] == [fitted, plain]


def _config(name):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        return LMConfig.from_dict(json.load(f))


# a cell's file, its tokens a sequence, whether it holds the module: what
# ``attention_core`` is asked a layer, as ``KINDS`` names it (None: the
# layer's attention is not ``attention_core``'s)
CELLS = {
    "smallthinker-21ba3b-l4": (8192, False, [
        "causal_128_lanes_7", "window_4096", "window_4096", "window_4096"]),
    "sdar-30b-a3b-l6": (4096, False, ["block_diffusion"] * 6),
    "xing4-29b-a4b-l5": (4096, False, ["causal_192_lanes_4k"] * 5),
    "laguna-xs2-33b-a3b-l5": (8192, False, None),
    "keye-vl2-30b-a3b-lm": (16384, False, [None] * 5),
    "kimi-linear-48b-a3b-l5": (8192, False, None),
    "glm47-flash-30b-a3b-l5": (8192, True, ["causal_256_lanes"] * 6),
    "solar-open2-250b-a15b-l4": (8192, False, None)}


@pytest.mark.parametrize("config", sorted(CELLS))
def test_a_cell_s_layers_ask_the_rule_what_their_kernels_are_asked(
        config, monkeypatch):
    """``attn_blocks_names`` hands ``attention_blocks_name`` a layer's mask,
    positions, lanes and heads a group as ``attention_core`` hands them to
    ``_splash`` (the cells' kinds, by the files), and skips a delta layer
    and a selected attention."""
    tokens, module, kinds = CELLS[config]
    cfg = _config(config)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    asked = []
    monkeypatch.setattr(lm, "attention_blocks_name",
                        lambda *a: asked.append(a) or "LM_ATTN_BLOCKS_PLAIN")
    names = ps_train.attn_blocks_names(cfg, tokens, module)
    assert len(names) == cfg.n_layers + module
    if kinds is not None:
        # the module's layer is of the last layer's kind: asked once
        want = [KINDS[k] for k in kinds[:cfg.n_layers] if k]
        assert [(lm.Mask.of(a[0]),) + a[1:] for a in asked] \
            == [(lm.Mask.of(w[0]),) + w[1:] for w in want]
        assert [bool(n) for n in names] == [bool(k) for k in kinds]
        return
    gqa = [a for a in asked if a[2] == a[3] == 128]
    if config == "laguna-xs2-33b-a3b-l5":
        assert sorted(set((lm.Mask.of(a[0]).window, a[4]) for a in gqa)) \
            == [(0, 6), (512, 8)] and len(asked) == 5
    elif config == "kimi-linear-48b-a3b-l5":    # one latent layer of five
        assert asked == [(0, 8192, 192, 128, 1)]
        assert sum(map(bool, names)) == 1
    else:                                       # solar: one softmax layer
        assert asked == [(lm.Mask(), 8192, 128, 128, 8)] \
            or asked == [(0, 8192, 128, 128, 8)]
        assert sum(map(bool, names)) == 1


# -- the benchmark's reader ---------------------------------------------------------

@pytest.mark.parametrize("fitted, plain, want", [
    (10, None, 100.0), (None, 8, 0.0), (6, 2, 75.0), (None, None, None)])
def test_the_reader_of_the_pair(fitted, plain, want):
    """``trainer.attn_blocks_fitted_share.lm``: the share, and nothing
    (no exception) from a program that has no such counter."""
    from benchmark.lib.harness import Observations
    from benchmark.run import load_module

    class Window:
        rounds, seconds = 23, 20.0
        counters = {"LM_STEP": {"count": 23, "ms": 20000.0}}

    for name, n in (("LM_ATTN_BLOCKS_FITTED", fitted),
                    ("LM_ATTN_BLOCKS_PLAIN", plain)):
        if n is not None:
            Window.counters[name] = {"count": n, "ms": 0.0}
    got = load_module("metrics", "trainer.attn_blocks_fitted_share.lm").read(
        Observations(window=Window()))
    assert got is None if want is None else got == pytest.approx(want)

"""The tenth family of multiverso_tpu/models/lm (the block of ``model_type:
granitemoehybrid`` as Granite 4.0-H Micro has it: a selective state-space
mixer in nine layers of ten, one grouped-query layer with no positions under
a softmax scale of 1/64, a dense MLP in every layer and NO router, four
scalar multipliers, ONE table for embedding and head) against the plain
reference (benchmark/reference/lm_granite_step.py: the state as the
RECURRENCE position by position, the convolution as four shifted sums,
attention as a masked matrix) at the configuration's rehearsal widths on the
CPU with four chunks a sequence: each kind of layer with every product in
float32 (the equations) and in bfloat16 (the rounding), heads of the
published sizes once, the chunked form at two chunk sizes and under deep
decay, causality and the convolution's reach, the layout, the multipliers, a
model with no router, and one step of ``PSLMTrainer`` through the tables."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_granite_step as ref
from multiverso_tpu.models.lm import PSLMTrainer, ssd
from multiverso_tpu.models.lm import model as lm, ps_train, zipf_tokens
from multiverso_tpu.util import dashboard
from tests.test_lm_kda import (_draw, _relative, _state,
                               float32_products)    # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
with open(os.path.join(CONFIGS, "granite-4.0-h-micro-l10.json")) as f:
    FILE = json.load(f)
CONFIG = {**FILE, **FILE["rehearsal"]}      # the rehearsal's widths
PUBLISHED = {k: v for k, v in FILE.items() if k != "rehearsal"}
T, B = 32, 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8
EXACT = 3e-4        # float32 products against the reference's: rounding
ROUNDED = 1e-1      # bfloat16 products at these widths
CFG = lm.LMConfig.from_dict(CONFIG)
C = ref.sizes(CONFIG)
KINDS = CFG.layer_kinds()
LAYERS = (0, 5)     # a layer of each kind of program: state-space, attention
#: the published heads: a state of 64 x 128 a head, attention heads of 64
WIDE = dict(CONFIG, mamba_d_head=64, mamba_d_state=128, mamba_n_heads=2,
            head_dim=64, num_attention_heads=4, num_key_value_heads=2)


@pytest.fixture(autouse=True)
def four_chunks_a_sequence(monkeypatch):
    """The rehearsal's ``scan_chunk`` is 8 positions; in runs of 2 a
    sequence of 32 is four chunks in two runs, so the state crosses chunks
    and runs in every test."""
    assert CFG.ssd_chunk == 8
    monkeypatch.setattr(ssd, "CHUNKS_AT_ONCE", 2)


def _split(p, layer, dtype=jnp.float32, cfg=CFG):
    mats = {n: p[n].astype(dtype) for n in cfg.matrices(layer)}
    return mats, {n: p[n] for n in p if n not in mats}


def _size(shapes):
    return sum(int(np.prod(s)) for s in shapes.values())


# -- the description ------------------------------------------------------------

def test_the_tenth_family_is_told_by_its_model_type():
    assert CFG.attention_layout == ("ssd",) * 5 + ("gqa",) + ("ssd",) * 4
    assert CFG.rope_layout == (0,) * 10 == CFG.ffn_layout
    assert set(KINDS) == {(0, 0, 0, "ssd"), (0, 0, 0, "gqa")}
    assert CFG.tied and not CFG.qk_norm and CFG.one_ffn_input
    assert (CFG.n_experts, CFG.top_k, CFG.experts_held) == (0, 0, (0, 0))
    assert (CFG.residual_scale, CFG.attn_scale, CFG.logits_scale,
            CFG.embed_scale) == (0.22, 0.015625, 8.0, 12.0)
    assert (CFG.ssd_heads, CFG.ssd_head_dim, CFG.ssd_state, CFG.ssd_groups,
            CFG.ssd_conv) == (8, 16, 32, 1, 4)
    assert [CFG.heads_of(i) for i in (0, 5)] == [(8, 8), (4, 4)]
    mixer, gqa = CFG.layer_shapes(0), CFG.layer_shapes(5)
    assert (mixer["w_in"], mixer["w_out"], mixer["conv_w"], mixer["conv_b"]) \
        == ((64, 128 + 192 + 8), (128, 64), (192, 4), (192,))
    assert [mixer[n] for n in ("dt_bias", "a_log", "d", "norm_g")] \
        == [(8,), (8,), (8,), (128,)]
    assert (gqa["wq"], gqa["wk"], gqa["wo"]) == ((64, 64), (64, 32), (64, 64))
    for shapes in (mixer, gqa):     # no router, no head norm, a dense MLP
        assert not {"router", "router_bias", "norm_q"} & set(shapes)
        assert shapes["w_gate"] == (64, 96)
    assert CFG.matrices(0) == ssd.MATRICES + lm.DENSE
    assert CFG.matrices(5) == lm.GQA_MATRICES + lm.DENSE


@pytest.mark.parametrize("layers", [10, 16, 40])
def test_the_layout_is_layer_types_as_published(layers):
    """Attention in layers 5, 15, 25, 35 and a state-space mixer in every
    other; layer 5 of the held ten is attention and the other nine ssd."""
    config = dict(CONFIG, num_hidden_layers=layers)
    cfg = lm.LMConfig.from_dict(config)
    assert [i for i, k in enumerate(cfg.attention_layout) if k == "gqa"] \
        == [i for i in (5, 15, 25, 35) if i < layers]
    assert set(cfg.attention_layout) == {"ssd", "gqa"}
    assert list(cfg.attention_layout) == ref.kinds(config)


def test_the_published_cut_counts_the_issue_s_parameters():
    cfg = lm.LMConfig.from_dict(PUBLISHED)
    assert _size(ssd.shapes(cfg)) == 25847232
    assert [_size(cfg.layer_shapes(i)) for i in (0, 5)] == [76182976,
                                                           60821504]
    assert cfg.parameters() == 772160448 == FILE["parameters"]["total"]
    assert 2 + sum(len(cfg.layer_shapes(i)) for i in range(10)) \
        == FILE["parameters"]["tables"]
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state) == (64, 64, 128)
    assert (cfg.vocab, cfg.hidden, cfg.dense_width) == (12544, 2048, 8192)
    assert ref.sizes(PUBLISHED)["head_dim"] == 64


@pytest.mark.parametrize("change", [
    {"num_local_experts": 8}, {"mamba_n_groups": 2}, {"mamba_proj_bias": True},
    {"mamba_conv_bias": False}, {"position_embedding_type": "rope"},
    {"attention_bias": True}, {"mamba_expand": 4},
    {"layer_types": ["mamba", "conv"] * 5}, {"layer_types": ["mamba"] * 9}])
def test_a_block_that_is_not_written_down_is_refused(change):
    with pytest.raises(Exception):
        lm.LMConfig.from_dict(dict(CONFIG, **change))


@pytest.mark.parametrize("name", [
    "smallthinker-21ba3b-l4", "sdar-30b-a3b-l6", "xing4-29b-a4b-l5",
    "laguna-xs2-33b-a3b-l5", "keye-vl2-30b-a3b-lm", "kimi-linear-48b-a3b-l5",
    "glm47-flash-30b-a3b-l5", "solar-open2-250b-a15b-l4", "lfm2-8b-a1b-l8"])
def test_an_older_file_keeps_the_four_scalars_at_what_it_had(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        config = json.load(f)
    cfg = lm.LMConfig.from_dict(config)
    assert (cfg.residual_scale, cfg.attn_scale, cfg.logits_scale,
            cfg.embed_scale) == (1.0, 0.0, 1.0, 1.0)
    assert "ssd" not in cfg.attention_layout and cfg.ssd_heads == 0
    assert cfg.n_experts > 0


# -- a layer of each kind against the reference -----------------------------------------

def _layer_both(layer, dtype, seed=0, cfg=CFG, c=C):
    rng = np.random.default_rng(seed)
    p = _draw(cfg.layer_shapes(layer), rng)
    x = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    mats, small = _split(p, layer, dtype, cfg)
    kind = cfg.attention_of(layer)
    with ref.PRECISION:
        y, stats, ids = jax.jit(lambda mats, small, x: lm.layer_forward(
            cfg, 0, 0, mats, small, x, None, 0, kind))(mats, small, x)
        dx, d_mats, d_small = jax.jit(
            lambda mats, small, x, dy: lm.layer_grads(
                cfg, 0, 0, mats, small, x, dy, None, 0, kind))(
                    mats, small, x, dy)
        want_y = jax.jit(lambda p, x: ref.layer(c, kind, p, x))(p, x)
        d_p, want_dx = jax.jit(lambda p, x, dy: jax.vjp(
            lambda p, x: ref.layer(c, kind, p, x), p, x)[1](dy))(p, x, dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": ids,
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p)}


LAYER_TENSORS = [(layer, name) for layer in LAYERS
                 for name in CFG.layer_shapes(layer)]


@pytest.fixture(scope="module")
def exact_layers():
    saved = lm.BF16, ssd.CHUNKS_AT_ONCE
    from multiverso_tpu.models.lm import delta
    lm.BF16 = delta.BF16 = jnp.float32
    ssd.CHUNKS_AT_ONCE = 2
    try:
        return {layer: _layer_both(layer, jnp.float32) for layer in LAYERS}
    finally:
        lm.BF16 = delta.BF16 = saved[0]
        ssd.CHUNKS_AT_ONCE = saved[1]


@pytest.fixture(scope="module")
def rounded_layers():
    saved = ssd.CHUNKS_AT_ONCE
    ssd.CHUNKS_AT_ONCE = 2
    try:
        return {layer: _layer_both(layer, jnp.bfloat16) for layer in LAYERS}
    finally:
        ssd.CHUNKS_AT_ONCE = saved


@pytest.mark.parametrize("layer", LAYERS)
def test_a_layer_s_result_is_the_reference_s(layer, exact_layers):
    both = exact_layers[layer]
    assert _relative(*both["y"]) < EXACT
    assert _relative(*both["dx"]) < EXACT
    # no experts: no ids, two zeros of counts and, a state-space layer, the
    # deep (chunk, head) pairs last
    assert both["ids"].shape == (0, 0)
    assert both["stats"].shape == ((3,) if layer == 0 else (2,))
    assert not np.any(np.asarray(both["stats"][:2]))


@pytest.mark.parametrize("layer,name", LAYER_TENSORS)
def test_a_tensor_s_gradient_is_the_reference_s(layer, name, exact_layers):
    got, want = exact_layers[layer]["grads"]
    assert got[name].shape == want[name].shape
    assert _relative(got[name], want[name]) < 4 * EXACT, name


@pytest.mark.parametrize("layer,name", LAYER_TENSORS)
def test_in_bfloat16_a_tensor_s_gradient_is_the_reference_s_rounded(
        layer, name, rounded_layers):
    both = rounded_layers[layer]
    got, want = both["grads"]
    assert _relative(got[name], want[name]) < ROUNDED, name
    assert _relative(*both["y"]) < ROUNDED


@pytest.mark.parametrize("layer", LAYERS)
def test_the_published_heads_are_the_reference_s_too(layer, float32_products):
    """A state of 64 x 128 a head, and an attention head of 64 lanes under
    the scale 1/64: every tensor of the layer."""
    cfg, c = lm.LMConfig.from_dict(WIDE), ref.sizes(WIDE)
    assert (cfg.ssd_head_dim, cfg.ssd_state, cfg.head_dim) == (64, 128, 64)
    assert cfg.layer_shapes(5)["wq"] == (64, 256)
    both = _layer_both(layer, jnp.float32, seed=7, cfg=cfg, c=c)
    assert _relative(*both["y"]) < EXACT
    assert _relative(*both["dx"]) < EXACT
    got, want = both["grads"]
    for name in want:
        assert _relative(got[name], want[name]) < 4 * EXACT, name


# -- the scan: the chunked form against the recurrence ------------------------------------

def _scan_inputs(seed, t=T, heads=8, lanes=16, n=32, dt=(1e-2, 0.5)):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(t, heads, lanes)), jnp.float32)
    steps = jnp.asarray(np.exp(rng.uniform(*np.log(dt), (t, heads))),
                        jnp.float32)
    a_log = jnp.asarray(np.log(rng.uniform(1, 16, heads)), jnp.float32)
    b, c = (jnp.asarray(rng.normal(size=(t, n)), jnp.float32)
            for _ in range(2))
    return x, steps, a_log, b, c


@pytest.mark.parametrize("chunk", [4, 16])
def test_the_chunked_form_is_the_recurrence_at_two_chunk_sizes(
        chunk, float32_products):
    x, dt, a_log, b, c = _scan_inputs(3)
    with ref.PRECISION:
        got, deep = jax.jit(lambda *a: ssd.scan(*a, chunk=chunk))(
            x, dt, a_log, b, c)
        want = ref.recurrence(x, dt, -jnp.exp(a_log), b, c, block=8)
        pull = jax.jit(lambda *a: jax.vjp(
            lambda *a: ssd.scan(*a, chunk=chunk)[0], *a)[1](jnp.ones_like(x)))
        d_got = pull(x, dt, a_log, b, c)
        d_want = jax.vjp(lambda x, dt, a_log, b, c: ref.recurrence(
            x, dt, -jnp.exp(a_log), b, c, block=8), x, dt, a_log, b, c)[1](
                jnp.ones_like(x))
    assert _relative(got, want) < EXACT
    for g, w in zip(d_got, d_want):
        assert _relative(g, w) < 4 * EXACT


def test_a_deep_decay_within_a_chunk_stays_finite():
    """``dt A`` summing under -20 within a chunk (steps of 4 to 8 under A
    of 1 to 16 over 16 positions: -64 at the mildest): zeros where a
    factored form gives ``0 * inf``, forward and backward, and the
    recurrence's numbers."""
    x, dt, a_log, b, c = _scan_inputs(5, dt=(4.0, 8.0))
    args = (x, dt, a_log, b, c)
    got, deep = jax.jit(lambda *a: ssd.scan(*a, chunk=16))(*args)
    assert int(deep) == 2 * 8       # every (chunk, head) pair
    grads = jax.jit(lambda *a: jax.grad(
        lambda *a: jnp.sum(ssd.scan(*a, chunk=16)[0] ** 2),
        argnums=(0, 1, 2, 3, 4))(*a))(*args)
    for value in (got,) + grads:
        assert np.all(np.isfinite(np.asarray(value)))
    with ref.PRECISION:
        want = ref.recurrence(x, dt, -jnp.exp(a_log), b, c)
    assert _relative(got, want) < ROUNDED


# -- which form the scan takes (ssd.scan_in_kernels; the kernels themselves:
# tests/test_lm_ssd_kernels.py) ---------------------------------------------------

@pytest.mark.parametrize("t,heads,lanes,state,chunk,carry,decay,want", [
    (8192, 64, 64, 128, 256, jnp.float32, jnp.float32, True),   # the cell's
    (16384, 64, 64, 128, 256, jnp.float32, jnp.float32, True),  # scan.carry's
    (8192, 64, 64, 128, 0, jnp.float32, jnp.float32, True),
    (8192, 64, 64, 128, 256, jnp.bfloat16, jnp.float32, False),     # the
    (8192, 64, 64, 128, 256, jnp.float32, jnp.bfloat16, False),     # control
    (8192, 1, 64, 128, 256, jnp.float32, jnp.float32, False),   # b_c_a_head
    (8192 + 128, 64, 64, 128, 0, jnp.float32, jnp.float32, False),
    (8192, 64, 64, 64, 256, jnp.float32, jnp.float32, False),
    (8192, 32, 128, 128, 256, jnp.float32, jnp.float32, False),
    (32, 8, 16, 32, 8, jnp.float32, jnp.float32, False),    # the rehearsal's
])
def test_the_scan_s_kernels_take_the_cell_s_shapes_on_a_tpu_alone(
        t, heads, lanes, state, chunk, carry, decay, want, monkeypatch):
    monkeypatch.setattr(ssd, "CARRY", carry)
    monkeypatch.setattr(ssd, "DECAY", decay)
    assert not ssd.scan_in_kernels(t, heads, lanes, state, chunk)  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd.scan_in_kernels(t, heads, lanes, state, chunk) is want


@pytest.mark.parametrize("config,t,carry,name", [
    (PUBLISHED, 8192, jnp.float32, "LM_SSD_SCAN_KERNEL"),
    (PUBLISHED, 8192, jnp.bfloat16, "LM_SSD_SCAN_PLAIN"),
    (PUBLISHED, 8192 + 128, jnp.float32, "LM_SSD_SCAN_PLAIN"),
    (CONFIG, 32, jnp.float32, "LM_SSD_SCAN_PLAIN")])
def test_the_scan_s_counter_names_the_form_the_scan_takes(config, t, carry,
                                                          name, monkeypatch):
    cfg = lm.LMConfig.from_dict(config)
    monkeypatch.setattr(ssd, "CARRY", carry)
    assert ssd.scan_counter(cfg, t) == "LM_SSD_SCAN_PLAIN"      # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssd.scan_counter(cfg, t) == name
    assert name in dashboard.METRIC_NAMES


def test_on_the_cpu_the_scan_is_the_plain_one(monkeypatch):
    from multiverso_tpu.models.lm import ssd_kernels

    def never(*args):
        raise AssertionError("the kernels were taken on the CPU")

    monkeypatch.setattr(ssd_kernels, "scan", never)
    x, dt, a_log, b, c = _scan_inputs(9, t=512, heads=2, lanes=64, n=128)
    y, deep = ssd.scan(x, dt, a_log, b, c)
    assert y.shape == x.shape and int(deep) >= 0


# -- causality: the convolution's reach, the state past it ---------------------------------

def _mixer(seed):
    rng = np.random.default_rng(seed)
    p = _draw(CFG.layer_shapes(0), rng)
    mats, small = _split(p, 0)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    return p, mats, small, x


def test_the_mixer_is_the_reference_s(float32_products):
    p, mats, small, x = _mixer(31)
    with ref.PRECISION:
        out, counts, _ = ssd.attention_vjp(
            CFG, mats, lm._zeros_like_f32(mats), small, x)
        want = ref.mamba_f(C, p, x)
    assert set(counts) == {"decay_deep"}
    assert _relative(out, want) < EXACT


@pytest.mark.parametrize("at", [0, 1, 13, T - 2])
def test_the_convolution_s_reach_is_exactly_four(at):
    """Perturbing position ``at`` of the convolution's input changes its
    result at ``at .. at + 3`` and nowhere else; through the whole mixer
    nothing before ``at`` changes and the STATE carries the change past the
    convolution's reach, to the sequence's end."""
    _, mats, small, x = _mixer(33)
    rng = np.random.default_rng(at)
    xbc = jnp.asarray(rng.normal(size=(T, 192)), jnp.float32)
    w, b = small["conv_w"], small["conv_b"]
    y, moved = (np.asarray(ssd.conv(v, w, b))
                for v in (xbc, xbc.at[at].add(1.0)))
    assert list(np.flatnonzero(np.any(y != moved, axis=-1))) == [
        t for t in range(at, at + 4) if t < T]

    def mixed(x):
        return np.asarray(ssd.mix(CFG, mats, lm._zeros_like_f32(mats),
                                  small, x))

    y, moved = mixed(x), mixed(x.at[at].add(1.0))
    changed = np.flatnonzero(np.any(y != moved, axis=-1))
    assert list(changed) == list(range(at, T))


def _programs(layer):
    kind = KINDS[layer]
    return (ps_train.forward_program(CFG, *kind[:2], T, kind[2],
                                     attention=kind[3]),
            ps_train.backward_program(CFG, *kind[:2], T, kind[2],
                                      attention=kind[3]))


@pytest.mark.parametrize("layer", LAYERS)
def test_a_token_changes_nothing_before_it_nor_in_the_other_sequence(layer):
    rng = np.random.default_rng(21 + layer)
    p = _draw(CFG.layer_shapes(layer), rng)
    mats, small = _split(p, layer)
    x = jnp.asarray(rng.normal(size=(B, T, CFG.hidden)), jnp.float32)
    forward, backward = _programs(layer)
    y, stats, _, ids = forward(mats, small, x)
    moved = forward(mats, small, x.at[0, 20].add(1.0))[0]
    assert np.array_equal(np.asarray(y[0, :20]), np.asarray(moved[0, :20]))
    assert np.array_equal(np.asarray(y[1]), np.asarray(moved[1]))
    assert np.all(np.any(np.asarray(y[0, 20:] != moved[0, 20:]), axis=-1))
    assert ids.shape == (B, 0, 0)       # no router: nobody's experts
    # and a cotangent at position 20 reaches no position after it
    dy = jnp.zeros_like(x).at[0, 20].set(1.0)
    bf16 = {n: w.astype(jnp.bfloat16) for n, w in mats.items()}
    dx = np.asarray(backward(bf16, small, x, dy)[0])
    assert not np.any(dx[0, 21:]) and not np.any(dx[1]) and np.any(dx[0, :21])


# -- the four multipliers ---------------------------------------------------------------

def _params(cfg, seed):
    rng = np.random.default_rng(seed)
    layers = [_draw(cfg.layer_shapes(i), rng) for i in range(cfg.n_layers)]
    return {"embedding": jnp.asarray(
        rng.normal(0, 0.02, (cfg.vocab, cfg.hidden)), jnp.float32),
        "final_norm": jnp.ones((cfg.hidden,), jnp.float32), "layers": layers}


def _program_loss(cfg, params, tokens):
    """The step's loss through model.py's own functions, one table."""
    ids, targets = tokens[:, :-1], tokens[:, 1:].reshape(-1)
    x = cfg.embed_scale * params["embedding"][ids]
    for i, p in enumerate(params["layers"]):
        mats, small = _split(p, i, cfg=cfg)
        x = jax.vmap(lambda seq: lm.layer_forward(
            cfg, 0, 0, mats, small, seq, None, 0, cfg.attention_of(i))[0])(x)
    return lm.head_loss_and_grads(
        cfg, params["embedding"], params["final_norm"],
        x.reshape(-1, cfg.hidden), targets)[0]


SHORT = dict(CONFIG, num_hidden_layers=6)   # five ssd layers and attention


def test_the_loss_is_the_reference_s_and_each_multiplier_is_live(
        float32_products):
    cfg, c = lm.LMConfig.from_dict(SHORT), ref.sizes(SHORT)
    params = _params(cfg, 41)
    tokens = zipf_tokens(jax.random.PRNGKey(9), (B, T + 1), cfg.vocab)
    with ref.PRECISION:
        got = float(_program_loss(cfg, params, tokens))
        want = float(ref.step_loss(c, params, tokens))
        assert abs(got - want) < EXACT * want
        for name in ("residual_scale", "attn_scale", "logits_scale",
                     "embed_scale"):
            # attn_scale: 0 is the default, head_dim^-0.5; the others 1
            off = dataclasses.replace(
                cfg, **{name: 0.0 if name == "attn_scale" else 1.0})
            moved = float(_program_loss(off, params, tokens))
            assert abs(moved - got) > 2e-5 * got, name   # float32: 1e-6


def test_eight_row_slices_logits_together_give_the_uncut_loss(
        float32_products):
    """The slice tied to the model: the table cut 8 ways by rows, each
    chip's logits over its own rows, their log-sum-exps combined: the uncut
    reference's loss over the whole vocabulary."""
    whole = dict(SHORT, vocab_size=208)
    cfg, c = lm.LMConfig.from_dict(whole), ref.sizes(whole)
    params = _params(cfg, 43)
    tokens = zipf_tokens(jax.random.PRNGKey(11), (B, T + 1), cfg.vocab)
    with ref.PRECISION:
        want = float(ref.step_loss(c, params, tokens))
        ids, targets = tokens[:, :-1], tokens[:, 1:].reshape(-1)
        x = ref.embed(c, params["embedding"], ids)
        for kind, p in zip(c["kinds"], params["layers"]):
            x = jax.vmap(lambda seq, p=p, k=kind: ref.layer(c, k, p, seq))(x)
        h = lm.rmsnorm(x.reshape(-1, cfg.hidden), params["final_norm"],
                       cfg.eps) * (1.0 / cfg.logits_scale)
        rows = cfg.vocab // 8
        parts, picked = [], 0.0
        for first in range(0, cfg.vocab, rows):
            logits = h @ params["embedding"][first:first + rows].T
            parts.append(jax.nn.logsumexp(logits, axis=-1))
            held = (targets >= first) & (targets < first + rows)
            picked += jnp.where(held, jnp.take_along_axis(
                logits, jnp.clip(targets - first, 0, rows - 1)[:, None],
                axis=-1)[:, 0], 0.0)
        got = float(jnp.mean(jax.nn.logsumexp(jnp.stack(parts), axis=0)
                             - picked))
    assert abs(got - want) < EXACT * want


# -- one step of the trainer through the tables ---------------------------------------------

def _shape_of(name):
    tensor = name.rsplit(".", 1)[-1]
    if name.startswith("layer"):
        return CFG.layer_shapes(int(name[5:name.index(".")]))[tensor]
    return (CFG.hidden,) if name == "final_norm" else (CFG.vocab, CFG.hidden)


def _as_reference(values):
    layers = {}
    for name, value in values.items():
        if name.startswith("layer"):
            layer, part = name.split(".")
            layers.setdefault(int(layer[5:]), {})[part] = value
    return {"embedding": values["embedding"],
            "final_norm": values["final_norm"],
            "layers": [layers[i] for i in sorted(layers)]}


@pytest.fixture(scope="module")
def run():
    """One step through the tables, and the reference's beside it from the
    same start."""
    from multiverso_tpu.util import configure
    saved = ssd.CHUNKS_AT_ONCE
    ssd.CHUNKS_AT_ONCE = 2
    mv.init(["-updater_type=adam"])
    try:
        trainer = PSLMTrainer(CFG, T, B, seed=3, lr=LR, beta1=B1, beta2=B2,
                              eps=EPS, embedding_std=FILE[
                                  "embedding_init_std"])
        tables = trainer.tables()
        start = {n: jnp.asarray(_state(t)[0]).reshape(_shape_of(n))
                 for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        results = []
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program):
                results.append(_program(*args))
                return results[-1]
            trainer._forward[kind] = spy
        adds = []
        for name, table in tables.items():
            for method in ("add_async", "add_rows_async"):
                send = getattr(table, method, None)
                if send is None:
                    continue

                def counted(*args, _name=name, _send=send, _method=method):
                    adds.append((_name, _method))
                    return _send(*args)

                setattr(table, method, counted)
        tokens = zipf_tokens(jax.random.PRNGKey(5), (B, T + 1), CFG.vocab)
        loss = float(trainer.step(tokens))
        trainer.sync()
        trainer.flush_stats()
        after = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        got = {n: _state(t) for n, t in tables.items()}
        with ref.PRECISION:
            want_loss, grads = jax.jit(jax.value_and_grad(
                lambda p: ref.step_loss(C, p, tokens)))(_as_reference(start))
        flat = {n: grads[n] for n in ("embedding", "final_norm")}
        for i, layer in enumerate(grads["layers"]):
            flat.update({f"layer{i}.{n}": v for n, v in layer.items()})
        yield {"loss": loss, "want_loss": float(want_loss), "got": got,
               "start": start, "grads": flat, "results": results,
               "adds": adds, "counters": (before, after),
               "names": list(tables), "same": trainer.head
               is trainer.embedding}
    finally:
        mv.shutdown()
        configure.reset_flags()
        ssd.CHUNKS_AT_ONCE = saved


def _names():
    return ["embedding", "final_norm"] + [
        f"layer{i}.{n}" for i in range(CFG.n_layers)
        for n in CFG.layer_shapes(i)]


def test_zero_experts_build_no_router_table_and_push_no_bias_add(run):
    """Exactly one Add a table a step, the one table's WHOLE and last; no
    table is a router's or a bias's, no forward program returned a bias's
    step, and every table is under Adam."""
    assert sorted(run["names"]) == sorted(_names())
    assert "head" not in run["names"] and run["same"]
    assert not [n for n in run["names"] if "router" in n]
    assert sorted(name for name, _ in run["adds"]) == sorted(run["names"])
    assert ("embedding", "add_async") in run["adds"]
    assert not any(method == "add_rows_async" for _, method in run["adds"])
    assert run["adds"][-1][0] == "embedding"        # the step's last
    assert all(len(result) == 4 for result in run["results"])
    for name, (w, state) in run["got"].items():
        assert state and int(state[2]) == 1, name
    assert CFG.parameters() == sum(w.size for w, _ in run["got"].values())


def test_the_step_s_loss_is_the_reference_s(run):
    assert abs(run["loss"] - run["want_loss"]) < 2e-3 * run["want_loss"]


@pytest.mark.parametrize("name", _names())
def test_a_table_s_first_moment_is_the_reference_s_gradient(run, name):
    """After one step of Adam from zero moments ``m = (1 - beta1) g``: the
    gradient that reached the table against the reference's, at bfloat16's
    rounding; and the table moved. The one table's is ``jax.grad``'s of a
    loss that uses it twice, its rows times 12."""
    w, (m, v, t) = run["got"][name]
    want = np.asarray(run["grads"][name])
    m = np.asarray(m)
    m = m[tuple(slice(0, n) for n in w.shape)] if m.ndim == w.ndim \
        else m.ravel()[:w.size].reshape(w.shape)
    got = m.reshape(want.shape) / (1 - B1)
    assert np.linalg.norm(got - want) < 1.5 * ROUNDED * np.linalg.norm(want), \
        name
    assert np.any(w.reshape(want.shape) != np.asarray(run["start"][name]))


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def counted(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]

    assert counted("LM_STEP") == 1 and counted("LM_TOKENS") == B * T
    assert counted("LM_TIED_ADDS") == 1
    for name in ("LM_ROUTER_BIAS_ADDS", "LM_HELD_ASSIGNMENTS",
                 "LM_EXPERTS_SHORT", "LM_EXPERTS_FULL", "LM_ROUTER_LOAD_MAX",
                 "LM_MIXERS_CONV", "LM_SSD_SCAN_KERNEL"):
        assert counted(name) == 0, name
    # nine mixers of ten are state-space layers, a layer a sequence
    assert counted("LM_MIXERS_SSD") == 9 * B
    assert counted("LM_MIXERS") == 10 * B
    assert counted("LM_SSD_SCAN_PLAIN") == 9 * B
    assert counted("LM_SSD_CHUNKS") == 9 * B * (T // 8)
    # the one attention layer's heads go to the kernel at their own lanes
    assert counted("LM_ATTN_LANES") == B * 16 == counted("LM_ATTN_LANES_TILED")
    assert counted("LM_HEADS_HELD") == B * (9 * 8 + 4) == counted("LM_HEADS")
    assert counted("LM_ATTN_PASS_PLAIN") == B   # no norm, no turn: the chain
    assert counted("LM_ADD_BYTES") == 4 * CFG.parameters()

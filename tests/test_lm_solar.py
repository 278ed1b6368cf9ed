"""The eighth family of multiverso_tpu/models/lm (the block of ``model_type:
solar_open2``: of every four layers the first grouped-query softmax
attention with no positions under a gate a LANE, the other three the gated
delta rule's scan with beta up to 2, both kinds' heads HELD AS A SHARE, every
layer sparse under a router of 320 outputs) against the plain reference
(benchmark/reference/lm_solar_step.py: the recurrence position by position,
attention as a masked matrix) at the configuration's rehearsal widths on the
CPU: each kind of layer with every product in float32 (the equations) and in
bfloat16 (the rounding), beta over 1, the chunked scan and both kernels
(interpreted) where ``beta k . k'`` is near 2 throughout a chunk, the solve by
halves against the series it replaced, the shares (four of heads for both
kinds, forty of experts), the experts' fallback in slabs, the description,
and one step of ``PSLMTrainer`` through the tables."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_solar_step as ref
from multiverso_tpu.models.lm import PSLMTrainer, delta, delta_kernels
from multiverso_tpu.models.lm import model as lm, ps_train, zipf_tokens
from multiverso_tpu.util import dashboard
from tests.test_lm_kda import (_as_reference, _draw, _relative, _state,
                               float32_products)    # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "benchmark", "configs",
                       "solar-open2-250b-a15b-l4.json")) as f:
    FILE = json.load(f)
CONFIG = {**FILE, **FILE["rehearsal"]}      # the rehearsal's widths
T, B = 32, 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8
EXACT = 3e-4        # float32 products against the reference's: rounding
ROUNDED = 1e-1      # bfloat16 products at these widths
CFG = lm.LMConfig.from_dict(CONFIG)
C = ref.sizes(CONFIG)
KINDS = CFG.layer_kinds()
#: the uncut layer: every head of both kinds, every expert
WHOLE = dict(CONFIG, num_attention_heads=16, num_key_value_heads=8,
             linear_attn_config=dict(CONFIG["linear_attn_config"],
                                     num_heads=16), n_routed_experts=80)


def _split(p, layer, dtype=jnp.float32, cfg=CFG):
    mats = {n: p[n].astype(dtype) for n in cfg.matrices(layer)}
    return mats, {n: p[n] for n in p if n not in mats}


# -- the description ------------------------------------------------------------

def test_the_eighth_family_is_told_by_its_model_type():
    assert CFG.attention_layout == ("gqa", "kda", "kda", "kda")
    assert KINDS == ((0, 0, 1, "gqa"),) + ((0, 0, 1, "kda"),) * 3
    assert (CFG.n_heads, CFG.n_kv_heads, CFG.kda_heads) == (16, 8, 16)
    assert CFG.heads_held == (0, 4)
    assert (CFG.n_heads_held, CFG.n_kv_heads_held, CFG.kda_heads_held) \
        == (4, 2, 4)
    assert [CFG.heads_of(i) for i in range(4)] == [(4, 16)] * 4
    assert CFG.kda_beta_scale == 2 and CFG.attn_gate == "lane"
    assert CFG.scoring == "sigmoid_bias" and CFG.one_ffn_input
    assert (CFG.n_experts, CFG.experts_held, CFG.top_k) == (80, (0, 2), 3)
    assert not any(CFG.rope_layout) and CFG.ffn_layout == (1,) * 4
    gqa, kda = CFG.layer_shapes(0), CFG.layer_shapes(1)
    assert (gqa["wq"], gqa["wk"], gqa["wv"], gqa["wo"], gqa["w_attn_gate"]) \
        == ((64, 64), (64, 32), (64, 32), (64, 64), (64, 64))
    assert (kda["wq"], kda["w_fa"], kda["w_fb"], kda["w_beta"], kda["wo"],
            kda["conv_k"], kda["a_log"], kda["dt_bias"], kda["norm_o"]) == (
        (64, 64), (64, 16), (16, 64), (64, 4), (64, 64), (64, 4), (4,),
        (64,), (16,))
    assert CFG.matrices(0)[:5] == ("wq", "wk", "wv", "wo", "w_attn_gate")
    assert "router_bias" in gqa and gqa["router"] == (64, 80)


def _size(shapes):
    return sum(int(np.prod(s)) for s in shapes.values())


def test_the_published_cut_counts_the_issue_s_parameters():
    """The size that ran (the issue's fall-back: heads 8 ways), to the
    parameter."""
    config = dict(FILE)
    config.pop("rehearsal")
    cfg = lm.LMConfig.from_dict(config)
    feed_forward = 142872896
    assert _size(delta.shapes(cfg)) + cfg.hidden == 18138248
    assert [_size(cfg.layer_shapes(i)) for i in range(4)] == [
        13635584 + feed_forward] + [18138248 + feed_forward] * 3
    assert cfg.parameters() == 840872600 == FILE["parameters"]["total"]
    assert 3 + sum(len(cfg.layer_shapes(i)) for i in range(4)) \
        == FILE["parameters"]["tables"]
    assert (cfg.n_heads_held, cfg.n_kv_heads_held, cfg.kda_heads_held) \
        == (8, 1, 8) and cfg.experts_held == (0, 8)
    assert (cfg.n_experts, cfg.vocab, cfg.hidden) == (320, 24576, 4096)
    # a fortieth of the load: the short buffer, and a fallback in slabs
    assert lm.experts_capacity(cfg, 8192) == 3584
    assert 8192 * 8 > lm.FALLBACK_SLABS_OVER * 3584


def test_the_issue_s_first_size_counts_its_parameters_too():
    """Heads 4 ways, which did not fit the chip beside a step."""
    config = dict(FILE, num_attention_heads=16, num_key_value_heads=2,
                  linear_attn_config=dict(FILE["linear_attn_config"],
                                          num_heads=16))
    config.pop("rehearsal")
    cfg = lm.LMConfig.from_dict(config)
    assert _size(delta.shapes(cfg)) + cfg.hidden == 35223696
    assert _size(cfg.layer_shapes(0)) == 27267072 + 142872896
    assert cfg.parameters() == 905760432


@pytest.mark.parametrize("change", [
    {"use_rope": True}, {"first_k_dense_replace": 1},
    {"gqa_layers": [1, 5, 9]}, {"gqa_layers": [0, 3, 6]},
    {"kda_use_full_proj": True}, {"norm_topk_prob": False},
    {"scoring_func": "softmax"}, {"num_key_value_heads": 1},
    {"first_head_held": 1}, {"num_attention_heads": 3},
    {"linear_attention_heads": 8},
    {"linear_attn_config": dict(CONFIG["linear_attn_config"],
                                num_kv_heads=4)},
    {"linear_attn_config": dict(CONFIG["linear_attn_config"], num_heads=2)}])
def test_a_block_that_is_not_written_down_is_refused(change):
    with pytest.raises(Exception):
        lm.LMConfig.from_dict(dict(CONFIG, **change))


def test_a_kimi_file_still_goes_its_own_way():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-l5.json")) as f:
        cfg = lm.LMConfig.from_dict(json.load(f))
    assert cfg.kda_beta_scale == 1 and cfg.heads_held == (0, 0)
    assert cfg.kda_heads_held == cfg.kda_heads == 32
    assert set(cfg.attention_layout) == {"kda", "mla"}


# -- a layer of each kind against the reference -----------------------------------------

def _layer_both(layer, dtype, seed=0, cfg=CFG, c=C):
    rng = np.random.default_rng(seed)
    p = _draw(cfg.layer_shapes(layer), rng)
    # two of 80 experts are held: a bias that has half the tokens choose one
    p["router_bias"] = p["router_bias"].at[:2].add(0.3)
    x = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    mats, small = _split(p, layer, dtype, cfg)
    kind = cfg.attention_of(layer)
    with ref.PRECISION:     # one program each: op by op the scans crawl
        y, stats, ids = jax.jit(lambda mats, small, x: lm.layer_forward(
            cfg, False, 0, mats, small, x, None, 1, kind))(mats, small, x)
        dx, d_mats, d_small = jax.jit(
            lambda mats, small, x, dy: lm.layer_grads(
                cfg, False, 0, mats, small, x, dy, None, 1, kind))(
                    mats, small, x, dy)
        want_y, own = jax.jit(
            lambda p, x: ref.layer(c, p, x, ids, own=True))(p, x)
        d_p, want_dx = jax.jit(lambda p, x, dy: jax.vjp(
            lambda p, x: ref.layer(c, p, x, ids), p, x)[1](dy))(p, x, dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": (ids, own),
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p)}


LAYER_TENSORS = [(layer, name) for layer in (0, 1)
                 for name in CFG.layer_shapes(layer) if name != "router_bias"]


@pytest.fixture(scope="module")
def exact_layers():
    saved = lm.BF16, delta.BF16
    lm.BF16 = delta.BF16 = jnp.float32
    try:
        return {layer: _layer_both(layer, jnp.float32) for layer in (0, 1)}
    finally:
        lm.BF16, delta.BF16 = saved


@pytest.fixture(scope="module")
def rounded_layers():
    return {layer: _layer_both(layer, jnp.bfloat16) for layer in (0, 1)}


@pytest.mark.parametrize("layer", [0, 1])
def test_a_layer_s_result_is_the_reference_s(layer, exact_layers):
    both = exact_layers[layer]
    assert _relative(*both["y"]) < EXACT
    assert _relative(*both["dx"]) < EXACT
    ids, own = both["ids"]
    assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))


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


def test_a_layer_s_stats_end_in_its_attention_s_counts(exact_layers):
    # [held, fullest] + the router's outputs + the open lanes
    softmax, scanned = exact_layers[0]["stats"], exact_layers[1]["stats"]
    assert softmax.shape == (2 + 80 + 1,)
    assert 0 < int(softmax[-1]) < T * 4 * 16
    # ... + beta over 1, then the deep triples
    assert scanned.shape == (2 + 80 + 2,)
    assert 0 < int(scanned[-2]) < T * 4


def test_the_other_reading_of_the_gate_is_a_gate_a_head(float32_products):
    cfg = lm.LMConfig.from_dict(dict(CONFIG, attn_gate="head"))
    assert cfg.layer_shapes(0)["w_attn_gate"] == (64, 4)
    both = _layer_both(0, jnp.float32, cfg=cfg)
    assert _relative(*both["y"]) < EXACT
    got, want = both["grads"]
    assert _relative(got["w_attn_gate"], want["w_attn_gate"]) < 4 * EXACT
    assert both["stats"].shape == (2 + 80 + 1,)     # the gates' sum, there


# -- beta over 1 ------------------------------------------------------------------

def test_a_beta_that_stays_under_one_is_not_the_model(float32_products):
    """The control: ``kda_beta_scale`` 1 (beta = sigmoid) differs from the
    reference by far more than the check's limit; 2 is the reference."""
    limit = CONFIG["limits"]["layer.output"]
    under = dataclasses.replace(CFG, kda_beta_scale=1)
    rng = np.random.default_rng(4)
    p = _draw(CFG.layer_shapes(1), rng)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    mats, small = _split(p, 1)
    with ref.PRECISION:
        want = ref.delta_f(C, p, x)
        got, wrong = (delta.attention_vjp(
            cfg, mats, lm._zeros_like_f32(mats), small, x) for cfg in
            (CFG, under))
    assert _relative(got[0], want) < EXACT
    assert _relative(wrong[0], want) > 5 * limit
    assert "beta_over_one" not in wrong[1]
    beta = 2 * jax.nn.sigmoid(
        ref.rmsnorm(x, p["norm_attn"], C["eps"]) @ p["w_beta"])
    assert int(got[1]["beta_over_one"]) == int(jnp.sum(beta > 1)) > 0


def _near_keys(seed, t, heads=2, lanes=16, beta=(1.9, 2.0), near=0.05,
               decay=(0.0, 0.02)):
    """Inputs on which ``beta k . k'`` is near 2 throughout: every key the
    head's one direction plus ``near`` of its own, beta on ``beta``."""
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(t, heads, lanes))) * lanes ** -0.5
    k = unit(unit(rng.normal(size=(1, heads, lanes)))
             + near * unit(rng.normal(size=(t, heads, lanes))))
    v = rng.normal(size=(t, heads, lanes))
    g = -rng.uniform(*decay, size=(t, heads, lanes))
    b = rng.uniform(*beta, size=(t, heads))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, b))


@pytest.mark.parametrize("chunk,block", [(64, 16), (32, 16), (16, 8), (8, 4)])
def test_the_chunked_scan_is_the_recurrence_where_beta_k_k_is_near_two(
        chunk, block, float32_products):
    args = _near_keys(0, 128)
    k, beta = args[1], args[4]
    pairs = beta[1:, 0] * jnp.sum(k[1:, 0] * k[:-1, 0], -1)
    assert float(pairs.min()) > 1.85
    with ref.PRECISION:
        want = ref.recurrence(*args)
        got, _ = delta.scan(*args, chunk, block)
    assert _relative(got, want) < EXACT


@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_the_chunked_scan_s_gradient_is_the_recurrence_s_there(
        wrt, float32_products):
    args = _near_keys(1, 128)
    cot = jnp.asarray(np.random.default_rng(2).normal(size=args[2].shape),
                      jnp.float32)
    with ref.PRECISION:
        want = jax.grad(lambda *a: jnp.sum(ref.recurrence(*a) * cot), wrt)(
            *args)
        got = jax.grad(lambda *a: jnp.sum(delta.scan(*a, 64)[0] * cot),
                       wrt)(*args)
    assert _relative(got, want) < 4 * EXACT


def test_in_bfloat16_products_the_scan_there_is_the_recurrence_rounded():
    args = _near_keys(3, 256, beta=(1.0, 2.0), near=0.5)
    with ref.PRECISION:
        want = ref.recurrence(*args)
    assert _relative(delta.scan(*args, 64)[0], want) < ROUNDED


def _series(a):
    """The solve this PR replaced: ``(I - a)(I + a^2)(I + a^4)..``."""
    n = a.shape[-1]
    inverse = jnp.eye(n, dtype=jnp.float32) - a
    power, covered = a, 2
    while covered < n:
        power = delta._highest(power, power)
        inverse = inverse + delta._highest(inverse, power)
        covered *= 2
    return inverse


@pytest.mark.parametrize("n", [64, 48, 16, 5])
@pytest.mark.parametrize("below", [2.0, 1.8, 1.0, 0.3])
def test_the_solve_by_halves_holds_where_the_series_cancels(n, below):
    """``(I + a)^-1`` for ``a`` = ``below`` everywhere under the diagonal
    (one key at every position of a chunk, beta = ``below``): the inverse's
    entries stay under 2 in size, the series' powers pass 1e20 at 64."""
    a = below * np.tril(np.ones((n, n)), -1)
    want = np.linalg.inv(np.eye(n) + a)
    got = np.asarray(delta.unit_lower_inverse(jnp.asarray(a, jnp.float32)))
    assert np.abs(got - want).max() < 2e-5 * max(1.0, np.abs(want).max())
    if n == 64 and below >= 1.0:    # what it replaced is no inverse there
        series = np.asarray(_series(jnp.asarray(a, jnp.float32)))
        assert not np.abs(series - want).max() < 1.0


def test_the_solve_by_halves_pulls_as_the_inverse_does():
    rng = np.random.default_rng(0)
    a = jnp.asarray(np.tril(rng.normal(size=(3, 24, 24)), -1) * 0.6,
                    jnp.float32)
    g = jnp.asarray(rng.normal(size=a.shape), jnp.float32)
    want = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(
        jnp.eye(24) + a) * g))(a)
    got = jax.grad(lambda a: jnp.sum(delta.unit_lower_inverse(a) * g))(a)
    assert _relative(got, want) < 1e-4


@pytest.fixture
def interpreted(monkeypatch):
    monkeypatch.setattr(delta_kernels, "INTERPRET", True)


def _kernel_inputs(seed):
    return _near_keys(seed, 2 * delta.CHUNK, heads=4, lanes=128,
                      beta=(1.7, 2.0), near=0.2)


def test_the_kernels_are_the_recurrence_where_beta_k_k_is_near_two(
        interpreted, monkeypatch):
    for module in (lm, delta, delta_kernels):
        monkeypatch.setattr(module, "BF16", jnp.float32)
    args = _kernel_inputs(5)
    with ref.PRECISION:
        want = ref.recurrence(*args)
        got, _ = delta_kernels.scan(*args, delta.DEEP)
    assert _relative(got.reshape(want.shape), want) < EXACT


@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_the_kernels_gradient_is_the_recurrence_s_there(wrt, interpreted,
                                                        monkeypatch):
    for module in (lm, delta, delta_kernels):
        monkeypatch.setattr(module, "BF16", jnp.float32)
    args = _kernel_inputs(6)
    cot = jnp.asarray(np.random.default_rng(7).normal(size=args[2].shape),
                      jnp.float32)

    def through(scan):
        return jax.grad(lambda *a: jnp.sum(
            scan(*a).reshape(cot.shape) * cot), wrt)(*args)

    with ref.PRECISION:
        want = through(ref.recurrence)
        got = through(lambda *a: delta_kernels.scan(*a, delta.DEEP)[0])
    assert _relative(got, want) < 4 * EXACT


# -- the shares add up ---------------------------------------------------------------

def _heads_cut(whole, kind, first, count, per_kv):
    """The tensors of heads ``first .. first + count - 1`` out of the uncut
    attention's ``whole``, as the server's tables of a share hold them."""
    d = CFG.head_dim

    def columns(a, first, count, width):
        return a[..., first * width:(first + count) * width]

    def rows(a, first, count, width):
        return a[first * width:(first + count) * width]

    p = dict(whole)
    if kind == "kda":
        for n in ("wq", "wk", "wv", "w_fb", "w_gb"):
            p[n] = columns(whole[n], first, count, d)
        p["w_beta"] = columns(whole["w_beta"], first, count, 1)
        for n in ("wo", "conv_q", "conv_k", "conv_v", "dt_bias"):
            p[n] = rows(whole[n], first, count, d)
        p["a_log"] = rows(whole["a_log"], first, count, 1)
        return p
    for n in ("wq", "w_attn_gate"):
        p[n] = columns(whole[n], first, count, d)
    for n in ("wk", "wv"):
        p[n] = columns(whole[n], first // per_kv, count // per_kv, d)
    p["wo"] = rows(whole["wo"], first, count, d)
    return p


@pytest.mark.parametrize("layer", [0, 1], ids=["gqa", "kda"])
def test_four_shares_of_heads_add_up_to_the_uncut_attention(
        layer, float32_products):
    """The guide's test of a share, for both kinds of attention: the four
    chips' parts of ``W_o``'s sum, each over its own heads, against the
    reference's attention over all sixteen."""
    uncut = lm.LMConfig.from_dict(WHOLE)
    c = ref.sizes(WHOLE)
    kind = CFG.attention_of(layer)
    rng = np.random.default_rng(11 + layer)
    whole = _draw(uncut.layer_shapes(layer), rng)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    total = jnp.zeros_like(x)
    for first in range(0, 16, 4):
        share = lm.LMConfig.from_dict(dict(CONFIG, first_head_held=first))
        assert share.heads_held == (first, 4)
        p = _heads_cut(whole, kind, first, 4, 2)
        assert all(p[n].shape == tuple(s)      # the attention's tensors
                   for n, s in share.layer_shapes(layer).items()
                   if n not in lm.DENSE)
        mats, small = _split(p, layer, cfg=share)
        with ref.PRECISION:
            a, _, _ = lm.attention_vjp(
                share, False, 0, mats, lm._zeros_like_f32(mats), small, x,
                None, kind)
        total = total + (a - x)
    with ref.PRECISION:
        want = (ref.delta_f if kind == "kda" else ref.softmax_f)(c, whole, x)
    assert _relative(total, want) < EXACT
    # and one share alone is not it
    assert _relative(a - x, want) > 0.3


def test_forty_shares_of_experts_add_up_to_the_uncut_feed_forward(
        float32_products):
    uncut = lm.LMConfig.from_dict(WHOLE)
    c = ref.sizes(WHOLE)
    rng = np.random.default_rng(13)
    whole = _draw(uncut.layer_shapes(1), rng)
    u = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    h, w = CFG.hidden, CFG.expert_width
    h_normed = lm.rmsnorm(u, whole["norm_ffn"], CFG.eps)
    ids, weights = lm.route(uncut, whole["router"], h_normed,
                            whole["router_bias"])
    with ref.PRECISION:
        shared = lm.gated_mlp(CFG, whole, lm._zeros_like_f32(
            {n: whole[n] for n in lm.SHARED}), lm.SHARED, h_normed)
        total = shared              # the shared expert counted ONCE
        for first in range(0, 80, 2):
            share = dataclasses.replace(CFG, experts_held=(first, 2))
            mats = {"w_gate": whole["w_gate"][first * h:(first + 2) * h],
                    "w_up": whole["w_up"][first * h:(first + 2) * h],
                    "w_down": whole["w_down"][first * w:(first + 2) * w]}
            total = total + lm.routed_experts(
                share, mats, lm._zeros_like_f32(mats), h_normed, ids,
                weights)[0]
        want = ref.feed_forward(c, whole, u)
    assert _relative(total, want) < EXACT


# -- the experts' fallback in slabs ------------------------------------------------

SLABS = dataclasses.replace(CFG, n_experts=160, experts_held=(3, 2),
                            hidden=16, expert_width=8)
SLAB_T = 4096


def _routed(live, seed=0):
    """A sequence of which ``live`` assignments fall on the two held
    experts, with tensors and a cotangent."""
    rng = np.random.default_rng(seed)
    k = SLABS.top_k
    ids = rng.integers(5, 160, size=(SLAB_T * k)).astype(np.int32)
    ids[rng.choice(SLAB_T * k, live, replace=False)] = rng.integers(
        3, 5, size=live)
    shapes = SLABS.layer_shapes(0)
    mats = {n: jnp.asarray(rng.normal(0, 0.3, shapes[n]), jnp.bfloat16)
            for n in lm.DENSE}
    return (jnp.asarray(ids.reshape(SLAB_T, k)), mats,
            jnp.asarray(rng.normal(size=(SLAB_T, 16)), jnp.bfloat16),
            jnp.asarray(rng.uniform(size=(SLAB_T, k)), jnp.float32),
            jnp.asarray(rng.normal(size=(SLAB_T, 16)), jnp.float32))


def test_the_fallback_walks_slabs_where_the_full_buffer_is_over_sixteen():
    cap = lm.experts_capacity(SLABS, SLAB_T)
    assert cap == 512
    assert SLAB_T * SLABS.top_k > lm.FALLBACK_SLABS_OVER * cap


@pytest.mark.parametrize("live", [300, 512, 513, 1024, 1500, 5000, 12288])
def test_the_experts_in_slabs_are_the_experts_in_one_buffer(live):
    """Whatever the routing no assignment is dropped: the sum and its pull
    where the held assignments overflow the short buffer (two slabs, three,
    ten, all 24) against ONE buffer of all ``T * k`` rows by XLA's grouped
    product; forward to float32's last places (a token's assignments in two
    slabs meet in another order), the pull within the rounding of a slab's
    bfloat16 ``dh``."""
    ids, mats, h, weights, g = _routed(live, seed=live)

    def both(fn):
        out, pull = jax.vjp(fn, lm._zeros_like_f32(mats), h, weights)
        return out, pull(g)

    got, (d_mats, dh, dw) = both(
        lambda s, h, w: lm.routed_experts(SLABS, mats, s, h, ids, w)[0])
    order, sizes = lm.held_groups(SLABS, ids)
    assert int(sizes.sum()) == live
    back = jnp.argsort(order).astype(jnp.int32)
    want, (want_mats, want_dh, want_dw) = both(
        lambda s, h, w: lm._experts_in(
            SLABS, SLAB_T * SLABS.top_k, mats, s, h, w, None, order, back,
            sizes, lm.grouped_mm_xla))
    assert _relative(got, want) < 1e-6
    for name in lm.DENSE:
        assert _relative(d_mats[name], want_mats[name]) < 1e-5, name
    assert _relative(dw, want_dw) < 1e-5
    assert _relative(dh.astype(jnp.float32),
                     want_dh.astype(jnp.float32)) < 4e-3


# -- causality, positions -------------------------------------------------------------

def _programs(layer):
    kind = KINDS[layer]
    return (ps_train.forward_program(CFG, *kind[:2], T, kind[2],
                                     attention=kind[3]),
            ps_train.backward_program(CFG, *kind[:2], T, kind[2],
                                      attention=kind[3]))


@pytest.mark.parametrize("layer", [0, 1], ids=["gqa", "kda"])
def test_a_token_changes_nothing_before_it_nor_in_the_other_sequence(layer):
    rng = np.random.default_rng(21 + layer)
    p = _draw(CFG.layer_shapes(layer), rng)
    mats, small = _split(p, layer)
    x = jnp.asarray(rng.normal(size=(B, T, CFG.hidden)), jnp.float32)
    forward, _ = _programs(layer)
    y = forward(mats, small, x)[0]
    moved = forward(mats, small, x.at[0, 20].add(1.0))[0]
    assert np.array_equal(np.asarray(y[0, :20]), np.asarray(moved[0, :20]))
    assert np.array_equal(np.asarray(y[1]), np.asarray(moved[1]))
    assert not np.array_equal(np.asarray(y[0, 20:]), np.asarray(moved[0, 20:]))


def test_the_softmax_layer_uses_no_position():
    """A layer without a turn gives every position's keys the same numbers
    wherever they stand: two tokens that swap places under the causal mask
    change no later position's output."""
    rng = np.random.default_rng(23)
    p = _draw(CFG.layer_shapes(0), rng)
    mats, small = _split(p, 0)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    swapped = x.at[3].set(x[7]).at[7].set(x[3])

    def attention(x):
        return lm.attention_vjp(CFG, False, 0, mats, lm._zeros_like_f32(mats),
                                small, x, None, "gqa")[0] - x

    got, want = attention(swapped), attention(x)
    assert _relative(got[8:], want[8:]) < 1e-5
    assert _relative(got[3], want[3]) > 1e-2


# -- one step of the trainer through the tables ---------------------------------------------

def _shape_of(name):
    tensor = name.rsplit(".", 1)[-1]
    if name.startswith("layer"):
        return CFG.layer_shapes(int(name[5:name.index(".")]))[tensor]
    return (CFG.hidden,) if name == "final_norm" else (CFG.vocab, CFG.hidden)


@pytest.fixture(scope="module")
def run():
    """One step through the tables, and the reference's beside it from the
    same start, given the step's chosen experts."""
    from multiverso_tpu.util import configure
    mv.init(["-updater_type=adam"])
    try:
        trainer = PSLMTrainer(CFG, T, B, seed=3, lr=LR, beta1=B1, beta2=B2,
                              eps=EPS)
        tables = trainer.tables()
        start = {n: jnp.asarray(_state(t)[0]).reshape(_shape_of(n))
                 for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        chosen, stats = {}, {}
        layers = iter(range(CFG.n_layers))
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program):
                out = _program(*args)
                i = next(layers)
                chosen[i], stats[i] = out[3], np.asarray(out[1])
                return out
            trainer._forward[kind] = spy
        adds = {}
        for name, table in tables.items():
            for method in ("add_async", "add_rows_async"):
                send = getattr(table, method, None)
                if send is None:
                    continue

                def counted(*args, _name=name, _send=send):
                    adds[_name] = adds.get(_name, 0) + 1
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
                lambda p: ref.step_loss(
                    C, p, tokens, [chosen[i] for i in range(CFG.n_layers)])))(
                        _as_reference(start))
        flat = {n: grads[n] for n in ("embedding", "head", "final_norm")}
        for i, layer in enumerate(grads["layers"]):
            flat.update({f"layer{i}.{n}": v for n, v in layer.items()})
        yield {"loss": loss, "want_loss": float(want_loss), "got": got,
               "start": start, "grads": flat, "stats": stats, "adds": adds,
               "chosen": chosen, "counters": (before, after),
               "names": list(tables)}
    finally:
        mv.shutdown()
        configure.reset_flags()


def _names():
    names = ["embedding", "head", "final_norm"]
    return names + [f"layer{i}.{n}" for i in range(CFG.n_layers)
                    for n in CFG.layer_shapes(i)]


def test_one_add_a_table_a_step_and_the_bias_under_the_plain_rule(run):
    assert sorted(run["names"]) == sorted(_names())
    assert run["adds"] == {name: 1 for name in run["names"]}
    biases = [n for n in run["names"] if n.endswith("router_bias")]
    assert biases == [f"layer{i}.router_bias" for i in range(4)]
    for name, (w, state) in run["got"].items():
        if name in biases:
            assert not state, name      # no rule's state: the plain rule
        else:
            assert state and int(state[2]) == 1, name
    assert CFG.parameters() == sum(w.size for w, _ in run["got"].values())


@pytest.mark.parametrize("layer", range(4))
def test_a_bias_moves_by_its_rate_against_the_load(run, layer):
    """Over all 80 outputs: two and a half times what the rehearsal's other
    routers count."""
    name = f"layer{layer}.router_bias"
    load = np.bincount(np.asarray(run["chosen"][layer]).ravel(),
                       minlength=80)
    want = CFG.bias_rate * np.sign(load.mean() - load)
    got = run["got"][name][0] - np.asarray(run["start"][name])
    assert np.array_equal(got.astype(np.float32), want.astype(np.float32))
    assert np.any(want != 0)


def test_the_step_s_loss_is_the_reference_s(run):
    assert abs(run["loss"] - run["want_loss"]) < 2e-3 * run["want_loss"]


@pytest.mark.parametrize("name", [n for n in _names()
                                  if not n.endswith("router_bias")])
def test_a_table_s_first_moment_is_the_reference_s_gradient(run, name):
    """After one step of Adam from zero moments ``m = (1 - beta1) g``: the
    gradient that reached the table against the reference's, at
    bfloat16's rounding; and the table moved."""
    w, (m, v, t) = run["got"][name]
    want = np.asarray(run["grads"][name])
    m = np.asarray(m)
    m = m[tuple(slice(0, n) for n in w.shape)] if m.ndim == w.ndim \
        else m.ravel()[:w.size].reshape(w.shape)
    got = m.reshape(want.shape) / (1 - B1)
    assert np.linalg.norm(got - want) < 1.5 * ROUNDED * np.linalg.norm(want), \
        name
    assert np.any(w.reshape(want.shape) != np.asarray(run["start"][name]))


def test_the_delta_layers_decays_start_from_their_own_draws(run):
    logs = [np.asarray(run["start"][f"layer{i}.a_log"]) for i in (1, 2, 3)]
    assert all(a.shape == (4,) and np.all((a >= 0) & (a <= np.log(16)))
               for a in logs)
    assert not np.array_equal(logs[0], logs[1])
    dt = np.log1p(np.exp(np.asarray(run["start"]["layer2.dt_bias"])))
    assert np.all((dt > 0.9e-3) & (dt < 0.11)) and dt.std() > 0
    assert "layer0.a_log" not in run["start"]       # the softmax layer


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def counted(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]

    assert counted("LM_STEP") == 1 and counted("LM_TOKENS") == B * T
    stats = run["stats"]
    assert [stats[i].shape for i in range(4)] == [(B, 83)] + [(B, 84)] * 3
    assert counted("LM_ROUTER_BIAS_ADDS") == 4
    assert counted("LM_HELD_ASSIGNMENTS") == sum(
        int(s[:, 0].sum()) for s in stats.values()) > 0
    fullest = sum(int(stats[i][:, 2:82].sum(0).max()) for i in range(4))
    assert counted("LM_ROUTER_LOAD_MAX") == fullest
    # three delta layers over their four HELD heads
    assert counted("LM_KDA_TOKENS") == 3 * B * T
    assert counted("LM_KDA_DECAY_CHANNELS") == 3 * B * 4 * 16
    assert counted("LM_KDA_DECAY_DEEP") == sum(
        int(stats[i][:, -1].sum()) for i in (1, 2, 3))
    assert counted("LM_KDA_BETA") == 3 * B * T * 4
    over = sum(int(stats[i][:, -2].sum()) for i in (1, 2, 3))
    assert counted("LM_KDA_BETA_OVER_ONE") == over
    assert 0.3 < over / (3 * B * T * 4) < 0.7       # untrained: near half
    # the one gated layer's lanes: a position a held head a lane
    assert counted("LM_GATE_LANES") == B * T * 4 * 16
    lanes = int(stats[0][:, -1].sum())
    assert counted("LM_GATE_LANES_OPEN") == lanes
    assert 0.4 < lanes / (B * T * 4 * 16) < 0.6
    # a quarter of every layer's heads, a layer a sequence
    assert counted("LM_HEADS_HELD") == 4 * B * 4
    assert counted("LM_HEADS") == 4 * B * 16
    assert counted("LM_KDA_SCAN_PLAIN") == 3 * B
    assert counted("LM_ATTN_PASS_PLAIN") == B and \
        counted("LM_ATTN_PASS_FUSED") == 0
    assert counted("LM_GATE_OPEN") == 0     # the per-head gate's: not here


# -- Adam's rows form on a row the compiler refuses fused ---------------------------

@pytest.mark.parametrize("cols", [8, 6, 7])
def test_a_wide_row_written_behind_a_barrier_is_the_row_written_fused(
        cols, monkeypatch):
    """``AdamRule.rows`` keeps the formula out of the writes of a stored row
    of ``WIDE_ROW_BYTES`` or more (the TPU's compiler refuses the fused
    write at 4,096 float32 columns: tests/test_row_scatter_tpu_compile.py):
    the same table, the same moments."""
    from multiverso_tpu.updater import rules
    rng = np.random.default_rng(cols)
    rule = rules.AdamRule()
    data = jnp.asarray(rng.normal(size=(12, cols)), jnp.float32)
    state = (jnp.asarray(rng.normal(size=(12, cols)), jnp.float32),
             jnp.asarray(rng.uniform(size=(12, cols)), jnp.float32),
             jnp.int32(3))
    ids = jnp.asarray([[3, 7, 3], [0, 11, 7]], jnp.int32)
    delta = jnp.asarray(rng.normal(size=(2, 3, cols)), jnp.float32)
    hyp = jnp.asarray([B1, LR, B2, EPS], jnp.float32)
    whole = rule.rows(data, state, ids, delta, hyp, 0)
    monkeypatch.setattr(rules, "WIDE_ROW_BYTES", 4 * cols)
    apart = rule.rows(data, state, ids, delta, hyp, 0)
    for got, want in zip(jax.tree_util.tree_leaves(apart),
                         jax.tree_util.tree_leaves(whole)):
        assert np.array_equal(np.asarray(got), np.asarray(want))
    assert not np.array_equal(np.asarray(whole[0]), np.asarray(data))

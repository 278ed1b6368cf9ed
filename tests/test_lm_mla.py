"""The third family of multiverso_tpu/models/lm (DeepSeek-V3's block as
Xing4.0 has it: latent attention, dense and sparse layers with a shared
expert, a sigmoid router chosen through a bias, four constrained residual
streams, a multi-token module) against the plain reference
(benchmark/reference/lm_mla_step.py) at small widths on the CPU: each new
kind's result and gradients with every product in float32 (the equations)
and in bfloat16 (the rounding), the share tests, Sinkhorn, and one step of
``PSLMTrainer`` through the tables."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_mla_step as ref
from multiverso_tpu.models.lm import PSLMTrainer, latent, model as lm
from multiverso_tpu.models.lm import mtp, streams, zipf_tokens
from multiverso_tpu.util import dashboard

CONFIG = {
    "hidden_size": 32, "num_attention_heads": 2, "attention_heads": 2,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 8, "v_head_dim": 8, "rope_theta": 10000,
    "rope_scaling": {"type": "yarn", "factor": 64, "beta_fast": 32,
                     "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 64},
    "router_outputs": 8, "n_routed_experts": 4, "first_expert_held": 2,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "n_shared_experts": 1, "intermediate_size": 48,
    "first_k_dense_replace": 1, "num_hidden_layers": 2, "vocab_size": 97,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "n_group": 1, "norm_topk_prob": True,
    "moe_layer_freq": 1, "routed_scaling_factor": 2.0,
    "router_bias_rate": 0.001, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3, "loss_block": 16}
T, B = 32, 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8
# float32 products against the reference's: rounding alone
EXACT = 2e-4
# bfloat16 products at these widths (tests/test_lm_model.py's reasons);
# what feeds the scores reads up to 0.16: at nearly flat softmaxes a
# query's or key's gradient is a sum over keys of terms that nearly cancel
ROUNDED, ROUNDED_SCORES = 1e-1, 2.5e-1
FEEDS_SCORES = ("wq_a", "wq_b", "norm_q_a", "wkv_a", "wkv_b", "norm_kv_a",
                "norm_attn")


def _limit(name):
    return ROUNDED_SCORES if name.rsplit(".", 1)[-1] in FEEDS_SCORES \
        else ROUNDED


def _relative(a, b):
    a, b = jnp.ravel(a), jnp.ravel(b)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _draw(shapes, rng):
    """Seeded tensors, every mechanism awake: mixers of order 1, norms
    near 1, a bias that moves the choice."""
    out = {}
    for name, shape in shapes.items():
        if name.endswith("_phi"):
            value = rng.normal(0, shape[1] ** -0.5, shape)
        elif name.endswith("_a"):
            value = np.ones(shape)
        elif name.endswith("_b"):
            value = rng.normal(0, 0.3, shape)
        elif name == "router_bias":
            value = rng.normal(0, 0.1, shape)
        elif len(shape) == 1:
            value = 1 + 0.1 * rng.normal(size=shape)
        else:
            value = rng.normal(0, 0.08, shape)
        out[name] = jnp.asarray(value, jnp.float32)
    return out


def _split(cfg, p, layer, dtype=jnp.float32):
    mats = {n: p[n].astype(dtype) for n in cfg.matrices(layer)}
    return mats, {n: p[n] for n in p if n not in mats}


@pytest.fixture
def float32_products(monkeypatch):
    """Every product of the program in float32: what is left against the
    reference is the equations."""
    monkeypatch.setattr(lm, "BF16", jnp.float32)


def _layer_both(cfg, c, sparse, dtype, seed=0):
    rng = np.random.default_rng(seed)
    p = _draw(cfg.layer_shapes(sparse), rng)
    x = jnp.asarray(rng.normal(size=(T, cfg.hc_mult * cfg.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, cfg.hc_mult * cfg.hidden)), jnp.float32)
    mats, small = _split(cfg, p, sparse, dtype)
    with ref.PRECISION:     # the program's streams: a column a token
        y, stats, ids = streams.layer_forward(cfg, sparse, mats, small, x.T)
        dx, d_mats, d_small = streams.layer_grads(cfg, sparse, mats, small,
                                                  x.T, dy.T)
        y, dx = y.T, dx.T
        chosen = ids if sparse else None
        want_y, own = ref.layer(c, p, x, chosen, own=True)
        d_p, want_dx = jax.vjp(lambda p, x: ref.layer(c, p, x, chosen),
                               p, x)[1](dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": (ids, own),
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p)}


CFG = lm.LMConfig.from_dict(CONFIG)
LAYER_TENSORS = [(sparse, name) for sparse in (0, 1)
                 for name in CFG.layer_shapes(sparse) if name != "router_bias"]


# -- the description ------------------------------------------------------------

def test_the_third_family_is_told_by_its_keys():
    assert CFG.attention == "mla" and CFG.residual == "mhc"
    assert CFG.scoring == "sigmoid_bias" and CFG.ffn_layout == (0, 1)
    assert CFG.heads_held == (0, 2) and CFG.experts_held == (2, 4)
    assert CFG.layer_kinds() == ((1, 0, 0), (1, 0, 1))
    assert CFG.shared_width == 16 and CFG.dense_width == 48
    assert CFG.mtp_layers == 1 and CFG.hc_mult * CFG.hidden == 128
    assert "router" not in CFG.layer_shapes(0)
    assert CFG.layer_shapes(1)["router_bias"] == (8,)
    assert CFG.layer_shapes(1)["hc_ffn_phi"] == (24, 128)
    sizes = [sum(int(np.prod(s)) for s in CFG.layer_shapes(i).values())
             for i in range(2)]
    module = sum(int(np.prod(s)) for s in {
        **CFG.mtp_shapes(), **CFG.layer_shapes(1)}.values())
    assert CFG.parameters() == sum(sizes) + module + 2 * 97 * 32 + 32


def test_the_older_families_keep_their_kinds_and_names():
    from tests.test_lm_model import CONFIG as older
    cfg = lm.LMConfig.from_dict(older)
    assert cfg.layer_kinds() == tuple(zip(cfg.rope_layout, cfg.window_layout))
    assert cfg.matrices() == lm.LAYER_MATRICES
    assert cfg.attention == "gqa" and cfg.residual == "plain"


# -- each new kind against the reference -----------------------------------------

@pytest.mark.parametrize("sparse", (0, 1))
def test_a_layer_equals_the_reference_in_float32(float32_products, sparse):
    both = _layer_both(CFG, ref.sizes(CONFIG), sparse, jnp.float32)
    assert _relative(*both["y"]) < EXACT and _relative(*both["dx"]) < EXACT
    grads, want = both["grads"]
    assert sorted(grads) == sorted(n for n in want if n != "router_bias")
    for name, grad in grads.items():
        assert _relative(grad.reshape(want[name].shape), want[name]) < EXACT, \
            name
    if sparse:      # its own input, no rounding: the reference's own choice
        ids, own = both["ids"]
        assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
        assert not np.any(np.asarray(want["router_bias"]))  # no gradient


@pytest.fixture(scope="module")
def rounded():
    c = ref.sizes(CONFIG)
    return {sparse: _layer_both(CFG, c, sparse, jnp.bfloat16, seed=1)
            for sparse in (0, 1)}


@pytest.mark.parametrize("sparse,name", LAYER_TENSORS)
def test_a_gradient_in_bfloat16_is_the_reference_s_rounded(rounded, sparse,
                                                           name):
    grads, want = rounded[sparse]["grads"]
    assert grads[name].dtype == jnp.float32
    assert _relative(grads[name].reshape(want[name].shape),
                     want[name]) < _limit(name), name


@pytest.mark.parametrize("sparse", (0, 1))
def test_a_layer_in_bfloat16_is_the_reference_s_rounded(rounded, sparse):
    assert _relative(*rounded[sparse]["y"]) < 3e-2
    assert _relative(*rounded[sparse]["dx"]) < ROUNDED
    stats = np.asarray(rounded[sparse]["stats"])
    if sparse:      # held, fullest held, then every output's assignments
        first, count = CFG.experts_held
        assert stats.shape == (2 + CFG.n_experts,)
        assert stats[2:].sum() == T * CFG.top_k
        assert stats[0] == stats[2 + first:2 + first + count].sum()
        assert stats[1] == stats[2 + first:2 + first + count].max()
    else:
        assert stats.tolist() == [0, 0]


def test_latent_attention_equals_the_reference(float32_products):
    c = ref.sizes(CONFIG)
    rng = np.random.default_rng(2)
    p = _draw(CFG.layer_shapes(0), rng)
    u = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    dv = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    mats, small = _split(CFG, p, 0)
    sinks = {n: jnp.zeros_like(w) for n, w in mats.items()}
    with ref.PRECISION:
        v, pull = latent.attention_vjp(CFG, mats, sinks, small, u)
        du, d_mats, d_small = pull(dv)
        want_v, back = jax.vjp(lambda p, u: ref.attention_f(c, p, u), p, u)
        want_p, want_du = back(dv)
    assert _relative(v, want_v) < EXACT and _relative(du, want_du) < EXACT
    for name, grad in {**d_mats, **d_small}.items():
        assert _relative(grad, want_p[name]) < EXACT, name


def test_yarn_s_frequencies_and_scale():
    inv = lm.yarn_frequencies(CFG.rope_theta, CFG.qk_rope_dim, *CFG.yarn[:4])
    own = 1.0 / 10000 ** (np.arange(0, 8, 2) / 8)
    # the fastest pair turns more than beta_fast times in 64 positions and
    # keeps its frequency; the slowest is divided by the factor
    assert inv[0] == own[0] and np.isclose(inv[-1], own[-1] / 64)
    assert np.all(inv <= own) and np.all(inv >= own / 64)
    np.testing.assert_allclose(inv, ref.yarn_frequencies(ref.sizes(CONFIG)),
                               rtol=1e-6)
    m = 0.1 * np.log(64) + 1
    assert np.isclose(latent.softmax_scale(CFG), m * m / 4.0)


def test_the_router_chooses_by_score_plus_bias_and_weighs_by_score():
    rng = np.random.default_rng(3)
    router = jnp.asarray(rng.normal(0, 0.3, (32, 8)), jnp.float32)
    h = jnp.asarray(rng.normal(size=(T, 32)), jnp.float32)
    bias = jnp.zeros(8).at[5].set(10.0)     # output 5 always chosen
    ids, weights = lm.route(CFG, router, h, bias)
    assert np.all(np.any(np.asarray(ids) == 5, axis=-1))
    score = jax.nn.sigmoid(h @ router)
    picked = jnp.take_along_axis(score, ids, axis=-1)
    np.testing.assert_allclose(
        weights, 2.0 * picked / picked.sum(-1, keepdims=True), rtol=1e-5)
    plain, _ = lm.route(CFG, router, h, jnp.zeros(8))
    assert np.array_equal(np.sort(plain, -1), np.sort(
        jax.lax.top_k(score, 2)[1], -1))
    # and the bias gets no gradient
    grad = jax.grad(lambda b: lm.route(CFG, router, h, b)[1].sum())(bias)
    assert not np.any(np.asarray(grad))


# -- the streams -------------------------------------------------------------------

def test_sinkhorn_s_rows_and_columns_sum_to_one():
    logits = jnp.asarray(np.random.default_rng(4).normal(0, 1, (4, 4, 64)),
                         jnp.float32)       # [row, column, token]
    m = streams.sinkhorn(logits, 20, 1e-6)
    assert np.all(np.asarray(m) > 0)
    assert np.abs(np.asarray(m.sum(1)) - 1).max() < 1e-4
    assert np.abs(np.asarray(m.sum(0)) - 1).max() < 1e-4
    one = streams.sinkhorn(logits, 1, 1e-6)     # one round is not enough
    assert np.abs(np.asarray(one.sum(1)) - 1).max() > 1e-2


def test_identity_streams_are_the_plain_residual():
    """H_res the identity, H_pre and H_post picking stream 0: that stream
    is ``x + F(x)`` and the others pass through."""
    n, c = CFG.hc_mult, CFG.hidden
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(T, n * c)), jnp.float32)
    first = np.where(np.arange(n) == 0, 40.0, -40.0)
    hc = {"phi": jnp.zeros((2 * n + n * n, n * c)), "a": jnp.ones(3),
          "b": jnp.asarray(np.concatenate([
              first, np.where(np.arange(n) == 0, 0.0, -40.0),
              np.where(np.eye(n) > 0, 30.0, -30.0).ravel()]), jnp.float32)}

    def f_vjp(u):
        return jnp.tanh(u), None, None

    y = streams.sublayer_vjp(CFG, hc, x.T, f_vjp)[0].T
    np.testing.assert_allclose(y[:, :c], x[:, :c] + jnp.tanh(x[:, :c]),
                               atol=1e-5)
    np.testing.assert_allclose(y[:, c:], x[:, c:], atol=1e-5)


def test_expand_and_collapse():
    h = jnp.arange(6.0).reshape(2, 3)
    cfg = dataclasses.replace(CFG, hidden=3, hc_mult=4)
    x = streams.expand(cfg, h)      # [n C, T]: a column a token
    assert x.shape == (12, 2) and np.array_equal(x[3:6], h.T)
    assert np.array_equal(streams.collapse(cfg, x), 4 * h)
    assert streams.expand(cfg, jnp.stack([h, h])).shape == (2, 12, 2)


# -- the mixers' pull, written out -------------------------------------------------

def _plain_sinkhorn(logits, iters, eps):
    """The rounds as the configuration words them: [row, column, token]."""
    m = jnp.exp(logits)
    for _ in range(iters):
        m = m / (jnp.sum(m, 1, keepdims=True) + eps)
        m = m / (jnp.sum(m, 0, keepdims=True) + eps)
    return m


def _plain_sublayer(cfg, hc, x, f):
    """streams.py's docstring, line for line, for ``x`` [n C, T] and an
    ``F`` that takes and gives [C, T]: ``(x', v)``."""
    n, c = cfg.hc_mult, cfg.hidden
    r = x * jax.lax.rsqrt(jnp.mean(x * x, 0, keepdims=True) + cfg.eps)
    raw = jnp.dot(hc["phi"], r, precision="highest")
    b, a = hc["b"][:, None], hc["a"]
    pre = jax.nn.sigmoid(a[0] * raw[:n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[n:2 * n] + b[n:2 * n])
    res = _plain_sinkhorn(
        jnp.clip(a[2] * raw[2 * n:] + b[2 * n:], *cfg.hc_clamp).reshape(
            n, n, -1), cfg.hc_iters, cfg.hc_eps)
    xs = x.reshape(n, c, -1)
    v = f(jnp.einsum("jt,jct->ct", pre, xs))
    return (jnp.einsum("ijt,jct->ict", res, xs)
            + post[:, None] * v[None]).reshape(x.shape), v


TIGHT = dataclasses.replace(CFG, hc_clamp=(-0.5, 0.5))
PULLED = ("dx", "phi", "b", "a", "dv", "w")


@pytest.fixture(scope="module", params=streams.SUBLAYERS)
def pulled(request):
    """One sublayer's pull both ways, under a clamp that binds on some
    entries: ``{tensor: (streams.sublayer_vjp's, jax.vjp's of the plain
    formulas)}``; ``F`` is a stand-in with a weight of its own, another
    one a kind."""
    cfg, n, c = TIGHT, TIGHT.hc_mult, TIGHT.hidden
    kind = streams.SUBLAYERS.index(request.param)
    rng = np.random.default_rng(20 + kind)
    hc = {k: v for k, v in zip(streams.MIXER, (
        jnp.asarray(rng.normal(0, (n * c) ** -0.5, (2 * n + n * n, n * c)),
                    jnp.float32),
        jnp.asarray(rng.normal(0, 0.3, 2 * n + n * n), jnp.float32),
        jnp.asarray([0.9, 1.1, 1.3], jnp.float32)))}
    x = jnp.asarray(rng.normal(size=(n * c, T)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(n * c, T)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(c, 1)), jnp.float32)

    def f(w, u):        # [C, T] -> [C, T]
        return jnp.tanh(w * u) if kind else w * u * jax.nn.sigmoid(u)

    seen = {}

    def f_vjp(u):       # the program's F takes and gives [T, C]
        v, pull = jax.vjp(lambda w, u: f(w, u.T).T, w, u)

        def pull_f(dv):
            seen["dv"] = dv.T
            d_w, du = pull(dv)
            return du, d_w

        return v, None, pull_f

    y, _, pull = streams.sublayer_vjp(cfg, hc, x, f_vjp)
    dx, d_hc, d_w = pull(dy)
    (want_y, v), pull_plain = jax.vjp(
        lambda hc, x, w: _plain_sublayer(cfg, hc, x,
                                         functools.partial(f, w)), hc, x, w)
    want_hc, want_dx, want_w = pull_plain((dy, jnp.zeros_like(v)))
    # dv: the same formulas with v an input of its own
    want_dv = jax.grad(lambda v: jnp.sum(
        dy * _plain_sublayer(cfg, hc, x, lambda u: v)[0]))(v)
    kept = streams.coefficients(cfg, hc, x)[1]
    return {"y": (y, want_y), "dx": (dx, want_dx), "dv": (seen["dv"],
                                                          want_dv),
            "w": (d_w, want_w), "inside": kept[2],
            **{k: (d_hc[k], want_hc[k]) for k in streams.MIXER}}


@pytest.mark.parametrize("name", PULLED)
def test_the_pull_written_out_is_the_formulas_differentiated(pulled, name):
    """``streams.sublayer_vjp``'s pull against ``jax.vjp`` of the module
    docstring's formulas written plainly, float32, each tensor against its
    own norm; the clamp holds some entries and lets others through."""
    inside = np.asarray(pulled["inside"])
    assert 0.1 < inside.mean() < 0.9
    assert _relative(*pulled["y"]) < 1e-5
    assert _relative(*pulled[name]) < 1e-5


@pytest.mark.parametrize("stacked", ("x", "into"))
def test_a_sequence_is_read_and_written_where_it_lies(stacked):
    """Handed sequence ``b`` of a stack (``streams.Of``) a sublayer gives
    what it gives that sequence alone, and told to leave its results in a
    stack it changes that sequence of it and no other."""
    cfg, n, c = CFG, CFG.hc_mult, CFG.hidden
    rng = np.random.default_rng(31)
    p = _draw({f"hc_{k}": s for k, s in zip(
        streams.MIXER, ((2 * n + n * n, n * c), (2 * n + n * n,), (3,)))},
        rng)
    hc = {k: p[f"hc_{k}"] for k in streams.MIXER}
    xs, dys, other = (jnp.asarray(rng.normal(size=(3, n * c, T)),
                                  jnp.float32) for _ in range(3))

    def f_vjp(u):
        v, pull = jax.vjp(jnp.tanh, u)
        return v, None, lambda dv: (pull(dv)[0], ())

    y, _, pull = streams.sublayer_vjp(cfg, hc, xs[1], f_vjp)
    dx, d_hc, _ = pull(dys[2])
    b = jnp.int32(1)
    if stacked == "x":
        got_y, _, got_pull = streams.sublayer_vjp(
            cfg, hc, streams.Of(xs, b), f_vjp)
        got_dx, got_hc, _ = got_pull(streams.Of(dys, b + 1))
    else:
        got_y, _, got_pull = streams.sublayer_vjp(
            cfg, hc, xs[1], f_vjp, into=streams.Of(other, b),
            pull_into=streams.Of(other, b - 1))
        got_dx, got_hc, _ = got_pull(dys[2])
        assert np.array_equal(got_y[0], other[0])
        assert np.array_equal(got_y[2], other[2])
        assert np.array_equal(got_dx[1:], other[1:])
        got_y, got_dx = got_y[1], got_dx[0]
    np.testing.assert_allclose(got_y, y, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(got_dx, dx, rtol=1e-6, atol=1e-6)
    for k in streams.MIXER:
        np.testing.assert_allclose(got_hc[k], d_hc[k], rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("iters", (0, 1, 20))
@pytest.mark.parametrize("what", ("rounds", "pull"))
def test_sinkhorn_by_slices_is_the_plain_loop(what, iters):
    """The rounds as adds of slices and products with inverses against
    ``sum(.., keepdims)`` and a quotient, and their hand-written pull
    against autodiff through that loop, for the ``iters`` it is given."""
    rng = np.random.default_rng(6)
    logits = jnp.asarray(rng.normal(0, 1.5, (4, 4, 64)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(4, 4, 64)), jnp.float32)
    got, pull = jax.vjp(lambda z: streams.sinkhorn(z, iters, 1e-6), logits)
    want, pull_plain = jax.vjp(lambda z: _plain_sinkhorn(z, iters, 1e-6),
                               logits)
    if what == "rounds":
        np.testing.assert_allclose(got, want, rtol=2e-6)
    else:
        assert _relative(pull(g)[0], pull_plain(g)[0]) < 1e-5


@pytest.mark.parametrize("seed", (0, 1))
def test_the_norm_s_pull_needs_no_pass(seed):
    """``g . r = d_raw . raw`` for ``g = phi^T d_raw``, ``r`` the normed
    streams and ``raw = phi r``: the mean that RMSNorm's pull wants is a
    sum over 2n + n^2 rows, not over the n C of a token's column."""
    rng = np.random.default_rng(seed)
    phi, x, d_raw = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                     for shape in ((24, 128), (128, T), (24, T)))
    r = x * jax.lax.rsqrt(jnp.mean(x * x, 0, keepdims=True) + 1e-6)
    with ref.PRECISION:
        g, raw = phi.T @ d_raw, phi @ r
    np.testing.assert_allclose(jnp.sum(g * r, 0), jnp.sum(d_raw * raw, 0),
                               rtol=1e-4, atol=1e-4)


def test_sinkhorn_lowers_to_no_reduction():
    """Neither the rounds nor their pull holds a ``reduce``: a sum over
    ``n`` slices is ``n - 1`` adds, which fuse with what is around them."""
    z = jnp.ones((4, 4, 64))
    rounds = jax.jit(lambda z: streams.sinkhorn(z, 20, 1e-6)).lower(z)
    pull = jax.jit(lambda z: jax.vjp(
        lambda z: streams.sinkhorn(z, 20, 1e-6), z)[1](z)).lower(z)
    for text in (rounds.as_text(), pull.as_text()):
        assert "stablehlo.reduce" not in text
        assert "stablehlo.exponential" in text


def test_a_sublayer_makes_three_arrays_as_large_as_the_streams():
    """Of the operations of one sublayer, forward and pulled, three give
    a whole [n C, T] float32 result: ``x'`` (the concatenation of its
    streams), ``g = phi^T d`` (off the TPU a product's result; on it made
    block by block inside the last pass) and ``dx`` (the concatenation of
    its streams). Neither ``r = RMSNorm(X)`` nor any cotangent of it, nor
    a stream padded out to the streams' size, is an array."""
    n, c = CFG.hc_mult, CFG.hidden
    k = 2 * n + n * n
    hc = {"phi": jnp.ones((k, n * c)), "b": jnp.ones((k,)),
          "a": jnp.ones((3,))}

    def f_vjp(u):
        v, pull = jax.vjp(jnp.tanh, u)
        return v, None, lambda dv: (pull(dv)[0], ())

    def both(hc, x, dy):
        y, _, pull = streams.sublayer_vjp(CFG, hc, x, f_vjp)
        return y, pull(dy)[:2]

    x = jnp.ones((n * c, T))
    text = jax.jit(both).lower(hc, x, x).as_text()
    whole = f"tensor<{n * c}x{T}xf32>"
    made = [line for line in text.splitlines()
            if "stablehlo." in line and line.rstrip().endswith(whole)
            and ("-> " + whole in line or ": " + whole in line)]
    assert len(made) <= 3, made
    assert sum("dot_general" in line for line in made) == 1
    assert sum("concatenate" in line for line in made) == 2


KERNELS = ("sinkhorn", "sinkhorn_pull", "stats", "write", "write_into",
           "weighted", "sums", "dx", "dx_into")


@pytest.fixture(scope="module")
def interpreted():
    """Every kernel of streams_kernels.py run by Pallas' interpreter on
    one small block grid (2 x 2 blocks, 3 sequences a stack) beside the
    same sums in ``jax.numpy``: ``{kernel: [(got, want)]}``."""
    from jax.experimental.pallas import tpu as pltpu
    from multiverso_tpu.models.lm import streams_kernels as kernels
    n, c, t, k = 4, 2 * kernels.ROWS, 2 * kernels.TOKENS, 24
    rng = np.random.default_rng(0)

    def drawn(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    z, g, phi = drawn(n, n, t), drawn(n, n, t), drawn(k, n * c) * 0.05
    xs, dys, into = drawn(2, n, c, t), drawn(3, n, c, t), drawn(2, n, c, t)
    v, du, res, post = drawn(c, t), drawn(c, t), drawn(n * n, t), drawn(n, t)
    pre, shrink, d = drawn(n, t), drawn(1, t), drawn(k, t)
    x3, dy3, x = xs[1], dys[2], xs[1].reshape(n * c, t)
    of_x, of_dy = (xs, jnp.int32(1)), (dys, jnp.int32(2))
    rounds, pull = jax.vjp(lambda z: streams.sinkhorn(z, 20, 1e-6), z)
    with ref.PRECISION:
        y = jnp.einsum("ijt,jct->ict", res.reshape(n, n, t), x3) \
            + post[:, None] * v[None]
        dx = jnp.einsum("ijt,ict->jct", res.reshape(n, n, t), dy3) \
            + pre[:, None] * du[None] + (phi.T @ d).reshape(n, c, t) \
            - x3 * shrink[0]
        all_sums = jnp.concatenate([
            jnp.einsum("ct,jct->jt", du, x3),
            jnp.einsum("ict,ct->it", dy3, v),
            jnp.einsum("ict,jct->ijt", dy3, x3).reshape(n * n, t)])
        product, d_phi = phi @ x, d @ x.T
    spread = kernels.spread
    with pltpu.force_tpu_interpret_mode():
        got_product, squares = kernels.stats(phi, of_x)
        written = kernels.write(of_x, v, spread(res), spread(post),
                                (into, jnp.int32(0)))
        got_dx, got_phi = kernels.dx(of_x, of_dy, du, phi, d, spread(res),
                                     spread(pre), spread(shrink))
        placed, _ = kernels.dx(of_x, of_dy, du, phi, d, spread(res),
                               spread(pre), spread(shrink),
                               (into, jnp.int32(1)))
        return {
            "sinkhorn": [(kernels.sinkhorn(z, 20, 1e-6), rounds)],
            "sinkhorn_pull": [(kernels.sinkhorn_pull(z, g, 20, 1e-6),
                               pull(g)[0])],
            "stats": [(got_product, product),
                      (squares.sum(0), (x * x).sum(0))],
            "write": [(kernels.write(of_x, v, spread(res), spread(post))[0],
                       y)],
            "write_into": [(written[0], y), (written[1], into[1])],
            "weighted": [(kernels.weighted(of_dy, spread(post)),
                          jnp.einsum("it,ict->ct", post, dy3))],
            "sums": [(kernels.sums(of_x, of_dy, v, du), all_sums)],
            "dx": [(got_dx[0], dx), (got_phi, d_phi)],
            "dx_into": [(placed[1], dx), (placed[0], into[0])]}


@pytest.mark.parametrize("name", KERNELS)
def test_a_kernel_of_the_mixers_is_the_same_sums(interpreted, name):
    for got, want in interpreted[name]:
        assert _relative(got, want) < 2e-6


# -- the shares add up to the uncut layer -------------------------------------------

def test_the_eight_head_shares_add_up_to_the_uncut_attention(
        float32_products):
    config = dict(CONFIG, num_attention_heads=8, attention_heads=8)
    whole, c = lm.LMConfig.from_dict(config), ref.sizes(config)
    rng = np.random.default_rng(6)
    p = _draw(whole.layer_shapes(0), rng)
    u = jnp.asarray(rng.normal(size=(T, whole.hidden)), jnp.float32)
    with ref.PRECISION:
        want = ref.attention_f(c, p, u)
        total = 0.0
        for k in range(8):
            share = dataclasses.replace(whole, heads_held=(k, 1))
            cut = dict(p)       # a head's columns and rows lie together
            for name, per in (("wq_b", 16), ("wkv_b", 16)):
                cut[name] = p[name][:, k * per:(k + 1) * per]
            cut["wo"] = p["wo"][k * 8:(k + 1) * 8]
            assert {n: cut[n].shape for n in cut} == share.layer_shapes(0)
            mats, small = _split(share, cut, 0)
            sinks = {n: jnp.zeros_like(w) for n, w in mats.items()}
            total = total + latent.attention_vjp(share, mats, sinks, small,
                                                 u)[0]
    assert _relative(total, want) < EXACT


def test_the_eight_expert_shares_add_up_to_the_uncut_feed_forward(
        float32_products):
    """Each share's routed part, and the shared expert counted once."""
    config = dict(CONFIG, n_routed_experts=8, first_expert_held=0)
    whole, c = lm.LMConfig.from_dict(config), ref.sizes(config)
    rng = np.random.default_rng(7)
    p = _draw(whole.layer_shapes(1), rng)
    u = jnp.asarray(rng.normal(size=(T, whole.hidden)), jnp.float32)
    h, w = whole.hidden, whole.expert_width
    with ref.PRECISION:
        want = ref.feed_forward(c, p, u)
        shared = ref.gated(ref.rmsnorm(u, p["norm_ffn"], 1e-6),
                           p["ws_gate"], p["ws_up"], p["ws_down"])
        total, seen = shared, 0
        for k in range(8):
            share = dataclasses.replace(whole, experts_held=(k, 1))
            cut = dict(p)
            cut["w_gate"] = p["w_gate"][k * h:(k + 1) * h]
            cut["w_up"] = p["w_up"][k * h:(k + 1) * h]
            cut["w_down"] = p["w_down"][k * w:(k + 1) * w]
            assert {n: cut[n].shape for n in cut} == share.layer_shapes(1)
            mats, small = _split(share, cut, 1)
            sinks = {n: jnp.zeros_like(w_) for n, w_ in mats.items()}
            y, (_, sizes, load), _ = lm.sparse_vjp(share, mats, sinks,
                                                        small, u)
            total = total + (y - shared)
            seen += int(sizes.sum())
            assert int(load.sum()) == T * whole.top_k
    assert seen == T * whole.top_k      # every assignment on one share
    assert _relative(total, want) < EXACT


# -- the multi-token module -------------------------------------------------------------

def test_the_module_equals_the_reference(float32_products):
    c = ref.sizes(CONFIG)
    rng = np.random.default_rng(8)
    p = _draw({**CFG.layer_shapes(1), **CFG.mtp_shapes()}, rng)
    xs = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    e = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    names = CFG.matrices(1) + mtp.MATRICES
    mats = {n: p[n] for n in names}
    small = {n: p[n] for n in p if n not in names and n != "final_norm"}
    with ref.PRECISION:
        y, (_, ids), pull = mtp.module_vjp(CFG, mats, small, xs, e)
        dxs, de, d_mats, d_small = pull(dy)
        want_y, back = jax.vjp(
            lambda p, xs, e: ref.mtp(c, p, xs, e, ids), p, xs, e)
        want_p, want_dxs, want_de = back(dy)
    assert _relative(y, want_y) < EXACT
    assert _relative(dxs, want_dxs) < EXACT and _relative(de, want_de) < EXACT
    grads = {**d_mats, **d_small}
    assert sorted(grads) == sorted(
        n for n in p if n not in ("router_bias", "final_norm"))
    for name, grad in grads.items():
        assert _relative(grad.reshape(want_p[name].shape),
                         want_p[name]) < EXACT, name


# -- one step of the trainer through the tables ---------------------------------------

def _state(table):
    """The server's side of a table: (weights, the rule's state)."""
    server = table.zoo.server_tables[table.table_id]
    return np.asarray(table.get_device()), server._engine.state


def _shape_of(name):
    """A table's tensor's shape, from the table's name."""
    tensor = name.rsplit(".", 1)[-1]
    if name.startswith("mtp.layer."):
        return CFG.layer_shapes(1)[tensor]
    if name.startswith("mtp."):
        return CFG.mtp_shapes()[tensor]
    if name.startswith("layer"):
        return CFG.layer_shapes(int(name[5:name.index(".")]))[tensor]
    return (CFG.hidden,) if name == "final_norm" else (CFG.vocab, CFG.hidden)


def _as_reference(values):
    """The tables' values in ``ref.step_loss``'s tree."""
    layers, module = {}, {}
    for name, value in values.items():
        if name.startswith("layer"):
            layer, part = name.split(".")
            layers.setdefault(int(layer[5:]), {})[part] = value
        elif name.startswith("mtp."):
            module[name.rsplit(".", 1)[-1]] = value
    return {"embedding": values["embedding"], "head": values["head"],
            "final_norm": values["final_norm"], "mtp": module,
            "layers": [layers[i] for i in sorted(layers)]}


def _flat(tree, names):
    out = {n: tree[n] for n in ("embedding", "head", "final_norm")}
    for i, layer in enumerate(tree["layers"]):
        out.update({f"layer{i}.{n}": v for n, v in layer.items()})
    for name in names:
        if name.startswith("mtp."):
            out[name] = tree["mtp"][name.rsplit(".", 1)[-1]]
    return out


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
        start = {n: jnp.asarray(_state(t)[0]).reshape(
            np.asarray(t.get_device()).shape) for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        chosen = {"layers": [], "mtp": None}
        stats = []

        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program, _sparse=kind[2]):
                out = _program(*args)
                chosen["layers"].append(out[3] if _sparse else None)
                stats.append(np.asarray(out[1]))
                return out
            trainer._forward[kind] = spy
        forward, head, backward = trainer._module

        def spy_module(*args):
            out = forward(*args)
            chosen["mtp"] = out[3]
            stats.append(np.asarray(out[1]))
            return out
        trainer._module = (spy_module, head, backward)

        tokens = zipf_tokens(jax.random.PRNGKey(5), (B, T + 2), CFG.vocab)
        loss = float(trainer.step(tokens))
        trainer.sync()
        trainer.flush_stats()
        after = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        got = {n: _state(t) for n, t in tables.items()}

        c = ref.sizes(CONFIG)
        shaped = {n: v.reshape(_shape_of(n)) for n, v in start.items()}
        params = _as_reference(shaped)
        with ref.PRECISION:
            (want_loss, parts), grads = jax.jit(jax.value_and_grad(
                lambda p: ref.step_loss(c, p, tokens, chosen),
                has_aux=True))(params)
        grads = _flat(grads, tables)
        yield {"loss": loss, "want_loss": float(want_loss), "parts": parts,
               "got": got, "start": start, "grads": grads, "stats": stats,
               "chosen": chosen, "counters": (before, after),
               "names": list(tables), "c": c}
    finally:
        mv.shutdown()
        configure.reset_flags()


def test_without_the_module_the_trainer_steps_on_one_target():
    """``num_nextn_predict_layers`` 0 (the module on a further rank): the
    same model without the module's tables, [B, T+1] tokens, the main
    loss alone."""
    from multiverso_tpu.util import configure
    cfg = lm.LMConfig.from_dict(dict(CONFIG, num_nextn_predict_layers=0))
    assert cfg.mtp_layers == 0
    assert cfg.parameters() == CFG.parameters() - sum(
        int(np.prod(s)) for s in {**CFG.mtp_shapes(),
                                  **CFG.layer_shapes(1)}.values())
    mv.init(["-updater_type=adam"])
    try:
        trainer = PSLMTrainer(cfg, T, B, seed=4)
        tables = trainer.tables()
        assert not [n for n in tables if n.startswith("mtp")]
        values = {n: jnp.asarray(t.get_device()) for n, t in tables.items()}
        chosen = []
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program, _sparse=kind[2]):
                out = _program(*args)
                chosen.append(out[3] if _sparse else None)
                return out
            trainer._forward[kind] = spy
        tokens = zipf_tokens(jax.random.PRNGKey(6), (B, T + 1), cfg.vocab)
        loss = float(trainer.step(tokens))
        trainer.sync()
        params = _as_reference(values)
        del params["mtp"]
        with ref.PRECISION:     # the reference wants both targets' room
            want, _ = jax.jit(lambda p: ref.step_loss(
                ref.sizes(CONFIG), p, jnp.pad(tokens, ((0, 0), (0, 1))),
                {"layers": chosen}))(params)
        assert abs(loss - float(want)) < 2e-3 * float(want)
        assert int(_state(tables["head"])[1][2]) == 1
    finally:
        mv.shutdown()
        configure.reset_flags()


def test_sixty_two_tables_of_which_two_under_the_plain_rule(run):
    # embedding, head, final norm; 20 (dense) + 29 (sparse); the module's
    # 4 and its sparse layer's 29... counted from the shapes
    want = 3 + len(CFG.layer_shapes(0)) + len(CFG.layer_shapes(1)) \
        + len(CFG.mtp_shapes()) + len(CFG.layer_shapes(1))
    assert len(run["names"]) == want
    plain = [n for n in run["names"] if n.endswith("router_bias")]
    assert plain == ["layer1.router_bias", "mtp.layer.router_bias"]
    for name, (w, state) in run["got"].items():
        assert (not state) == (name in plain), name
    assert CFG.parameters() == sum(w.size for w, _ in run["got"].values())


def test_the_step_s_loss_is_both_losses(run):
    main, second = run["parts"]
    assert run["want_loss"] == pytest.approx(
        float(main) + 0.3 * float(second), rel=1e-6)
    assert abs(run["loss"] - run["want_loss"]) < 2e-3 * run["want_loss"]


@pytest.mark.parametrize("name", ["embedding", "head"])
def test_a_table_read_twice_gets_one_add(run, name):
    """Embedding and head are read by the main model and by the module;
    their two gradients reach the server as one Add: the moments' step
    count is 1 after one step."""
    _, (m, v, t) = run["got"][name]
    assert int(t) == 1


def test_every_table_under_adam_took_one_step(run):
    for name, (_, state) in run["got"].items():
        if state:
            assert int(state[2]) == 1, name


def _names():
    names = ["embedding", "head", "final_norm"]
    names += [f"layer{i}.{n}" for i in range(2) for n in CFG.layer_shapes(i)]
    names += [f"mtp.{n}" for n in CFG.mtp_shapes()]
    names += [f"mtp.layer.{n}" for n in CFG.layer_shapes(1)]
    return [n for n in names if not n.endswith("router_bias")]


@pytest.mark.parametrize("name", _names())
def test_a_table_s_first_moment_is_the_reference_s_gradient(run, name):
    """After one step of Adam from zero moments ``m = (1 - beta1) g``: the
    gradient that reached the table, against the reference's, at
    bfloat16's rounding; and the table moved."""
    w, (m, v, t) = run["got"][name]
    want = np.asarray(run["grads"][name])
    m = np.asarray(m)
    m = m[tuple(slice(0, n) for n in w.shape)] if m.ndim == w.ndim \
        else m.ravel()[:w.size].reshape(w.shape)
    got = m.reshape(want.shape) / (1 - B1)
    assert np.linalg.norm(got - want) < _limit(name) * np.linalg.norm(want), \
        name
    assert np.any(w != np.asarray(run["start"][name]))


@pytest.mark.parametrize("name", ["layer1.router_bias",
                                  "mtp.layer.router_bias"])
def test_the_bias_moved_by_the_load_s_sign_exactly(run, name):
    chosen = run["chosen"]["mtp"] if name.startswith("mtp") \
        else run["chosen"]["layers"][1]
    load = ref.load_of(run["c"], chosen)
    want = ref.bias_step(run["c"], run["start"][name], load)
    w, state = run["got"][name]
    assert np.array_equal(w, np.asarray(want))
    assert np.any(w != 0) and set(np.unique(w)) <= {
        np.float32(-0.001), np.float32(0.0), np.float32(0.001)}
    assert not state            # the plain rule keeps nothing


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def delta(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]

    assert delta("LM_STEP") == 1 and delta("LM_TOKENS") == B * T
    assert delta("LM_MTP_TOKENS") == B * T
    assert delta("LM_POSITIONS") == B * (T + 1)
    assert delta("LM_ROUTER_BIAS_ADDS") == 2
    fullest = sum(int(s[:, 2:].sum(0).max()) for s in run["stats"]
                  if s.shape[1] > 2)
    assert delta("LM_ROUTER_LOAD_MAX") == fullest >= 2 * B * T * 2 / 8
    held = sum(int(s[:, 0].sum()) for s in run["stats"])
    assert delta("LM_HELD_ASSIGNMENTS") == held > 0
    # the sparse layer's and the module's layer's sequences; not the dense
    assert delta("LM_EXPERTS_SHORT") == 2 * B
    assert delta("LM_EXPERTS_FULL") == 0
    tables = len(run["names"])
    # a Get and an Add a table, and the closing row Get
    assert delta("WORKER_PROCESS_GET") == tables + 1
    assert delta("WORKER_PROCESS_ADD") == tables


# -- the layer programs' loop over sequences carries scopes ----------------------

def _locations(program, *args):
    return program.lower(*args).as_text(debug_info=True)


@pytest.mark.parametrize("sparse", [0, 1])
def test_the_loop_over_sequences_is_named(sparse):
    """A sequence's streams go in and out of the layer programs' loop
    under ``mv.lm.hc`` (slices of the step's widest arrays) and the
    backward program sums a layer's gradients under ``mv.lm.grad_sum``:
    neither is left to ``no-scope`` (PERF.md section 5)."""
    from multiverso_tpu.models.lm import ps_train
    shapes = CFG.layer_shapes(sparse)
    mats = {n: jnp.zeros(shapes[n], jnp.bfloat16)
            for n in CFG.matrices(sparse)}
    small = {n: jnp.ones(shape) for n, shape in shapes.items()
             if n not in mats}
    x = jnp.ones((B, CFG.hc_mult * CFG.hidden, T))
    forward = _locations(ps_train.forward_program(CFG, 1, 0, T, sparse),
                         {n: w.astype(jnp.float32)
                          for n, w in mats.items()}, small, x)
    backward = _locations(ps_train.backward_program(CFG, 1, 0, T, sparse),
                          mats, small, x, x)
    assert "mv.lm.hc/while" in forward and "mv.lm.hc/while" in backward
    assert "mv.lm.grad_sum" in backward and "mv.lm.grad_sum" not in forward


def test_the_older_families_loop_is_as_it_was():
    """The first two families' backward program names nothing of its
    loop: its operations, and so its compiled form, are the parent's."""
    from multiverso_tpu.models.lm import ps_train
    config = {"hidden_size": 32, "num_attention_heads": 2,
              "num_key_value_heads": 1, "head_dim": 16,
              "moe_num_primary_experts": 4, "router_outputs": 8,
              "moe_num_active_primary_experts": 2,
              "moe_ffn_hidden_size": 16, "vocab_size": 97,
              "num_hidden_layers": 1, "rope_layout": [1],
              "sliding_window_layout": [0], "sliding_window_size": 0,
              "rope_theta": 10000.0, "rms_norm_eps": 1e-6}
    cfg = lm.LMConfig.from_dict(config)
    shapes = cfg.layer_shapes()
    mats = {n: jnp.zeros(shapes[n], jnp.bfloat16) for n in cfg.matrices()}
    small = {n: jnp.ones(shapes[n]) for n in cfg.small_names}
    x = jnp.ones((B, T, cfg.hidden))
    text = _locations(ps_train.backward_program(cfg, 1, 0, T), mats, small,
                      x, x)
    assert "mv.lm.grad_sum" not in text and "mv.lm.hc" not in text
    assert "mv.lm.experts" in text

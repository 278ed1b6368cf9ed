"""The ninth family of multiverso_tpu/models/lm (the block of ``model_type:
lfm2_moe``: a doubly gated short convolution of three taps as the mixer of
three layers in four, grouped-query attention under head norms and a rotary
turn in the fourth, two leading dense layers, sparse ones without a shared
expert under a router that chooses through a bias, ONE table for embedding
and head) against the plain reference (benchmark/reference/lm_lfm2_step.py:
the taps as three shifted sums written out, attention as a masked matrix) at
the configuration's rehearsal widths on the CPU: each kind of layer with
every product in float32 (the equations) and in bfloat16 (the rounding),
heads of 16 lanes and once of 64, the convolution's reach, the layout as
published and the fall-back's, the four shares of experts, the description,
and one step of ``PSLMTrainer`` through the tables: ONE Add to the one table,
whose gradient is the sum of both of its uses."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_lfm2_step as ref
from multiverso_tpu.models.lm import PSLMTrainer, shortconv
from multiverso_tpu.models.lm import model as lm, ps_train, zipf_tokens
from multiverso_tpu.util import dashboard
from tests.test_lm_kda import (_draw, _relative, _state,
                               float32_products)    # noqa: F401

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(ROOT, "benchmark", "configs")
with open(os.path.join(CONFIGS, "lfm2-8b-a1b-l8.json")) as f:
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
#: a layer of each kind of program: dense convolution, sparse attention,
#: sparse convolution
LAYERS = (0, 2, 3)
#: heads of 64 lanes, the published head: half a tile
WIDE = dict(CONFIG, head_dim=64, num_attention_heads=4,
            num_key_value_heads=2)


def _split(p, layer, dtype=jnp.float32, cfg=CFG):
    mats = {n: p[n].astype(dtype) for n in cfg.matrices(layer)}
    return mats, {n: p[n] for n in p if n not in mats}


# -- the description ------------------------------------------------------------

def test_the_ninth_family_is_told_by_its_model_type():
    assert CFG.attention_layout == ("conv", "conv", "gqa", "conv") * 2
    assert CFG.rope_layout == (0, 0, 1, 0) * 2
    assert CFG.ffn_layout == (0, 0) + (1,) * 6
    assert set(KINDS) == {(0, 0, 0, "conv"), (0, 0, 1, "conv"),
                          (1, 0, 1, "gqa")}
    assert CFG.tied and CFG.conv_taps == 3 and CFG.qk_norm
    assert CFG.scoring == "sigmoid_bias" and CFG.one_ffn_input
    assert CFG.shared_width == 0 and CFG.attn_gate == "none"
    assert (CFG.n_experts, CFG.experts_held, CFG.top_k) == (8, (0, 2), 2)
    assert (CFG.n_heads, CFG.n_kv_heads, CFG.head_dim) == (4, 2, 16)
    assert [CFG.heads_of(i) for i in (0, 2)] == [(0, 0), (4, 4)]
    conv, gqa = CFG.layer_shapes(0), CFG.layer_shapes(2)
    assert (conv["w_in"], conv["w_out"], conv["conv_w"]) == (
        (64, 192), (64, 64), (64, 3))
    assert "norm_q" not in conv and "router" not in conv
    assert conv["w_gate"] == (64, 96)       # the dense MLP
    assert (gqa["wq"], gqa["wk"], gqa["wo"], gqa["norm_q"], gqa["norm_k"]) \
        == ((64, 64), (64, 32), (64, 64), (16,), (16,))
    assert gqa["router"] == (64, 8) and gqa["router_bias"] == (8,)
    assert "ws_gate" not in gqa and gqa["w_gate"] == (2 * 64, 32)
    assert CFG.matrices(0) == ("w_in", "w_out") + lm.DENSE
    assert CFG.matrices(2) == lm.GQA_MATRICES + lm.DENSE


@pytest.mark.parametrize("layers,want", [
    (8, ["conv", "conv", "gqa", "conv"] * 2),
    (6, ["conv", "conv", "gqa", "conv", "conv", "conv"]),   # the fall-back
    (24, ["conv", "conv", "gqa", "conv"] * 5 + ["conv", "gqa", "conv",
                                                 "conv"])])
def test_the_layout_is_layer_types_as_published(layers, want):
    """No period is assumed: the published list is not one period repeated
    (its last attention layer is 21, not 22)."""
    cfg = lm.LMConfig.from_dict(dict(CONFIG, num_hidden_layers=layers))
    assert list(cfg.attention_layout) == want == ref.kinds(
        dict(CONFIG, num_hidden_layers=layers))
    assert [i for i, k in enumerate(want) if k == "gqa"] == [
        i for i in (2, 6, 10, 14, 18, 21) if i < layers]
    assert cfg.rope_layout == tuple(int(k == "gqa") for k in want)
    assert cfg.ffn_layout == (0, 0) + (1,) * (layers - 2)


def _size(shapes):
    return sum(int(np.prod(s)) for s in shapes.values())


@pytest.mark.parametrize("layers,total,tables", [
    (8, 772217280, 84), (6, 568647936, 61)])
def test_the_published_cut_counts_the_issue_s_parameters(layers, total,
                                                         tables):
    """The size that ran and the issue's one fall-back, to the parameter."""
    cfg = lm.LMConfig.from_dict(dict(PUBLISHED, num_hidden_layers=layers))
    sizes = [_size(cfg.layer_shapes(i)) for i in range(layers)]
    assert sizes[:4] == [60827648, 60827648, 98635936, 104933408]
    assert _size(shortconv.shapes(cfg)) == 16783360
    assert cfg.parameters() == total
    assert 2 + sum(len(cfg.layer_shapes(i)) for i in range(layers)) == tables
    if layers == 8:
        assert total == FILE["parameters"]["total"]
        assert tables == FILE["parameters"]["tables"]
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) == (32, 8, 64)
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (32, (0, 8), 4)
    assert (cfg.vocab, cfg.hidden, cfg.rope_theta) == (16384, 2048, 1e6)
    # a quarter of the load: a short buffer of twice the even share
    assert lm.experts_capacity(cfg, 8192) == 16384 < 8192 * 4
    # two tables would count 33.5M more
    assert dataclasses.replace(cfg, tied=False).parameters() \
        == total + 16384 * 2048


def test_a_head_s_lanes_are_hidden_over_heads_where_the_file_has_none():
    config = {k: v for k, v in PUBLISHED.items() if k != "head_dim"}
    assert lm.LMConfig.from_dict(config).head_dim == 64
    assert ref.sizes(config)["head_dim"] == 64


@pytest.mark.parametrize("change", [
    {"conv_bias": True}, {"norm_topk_prob": False},
    {"use_expert_bias": False}, {"num_dense_layers": 9},
    {"layer_types": ["conv", "sliding_attention"] * 4},
    {"layer_types": ["conv"] * 7}])
def test_a_block_that_is_not_written_down_is_refused(change):
    with pytest.raises(Exception):
        lm.LMConfig.from_dict(dict(CONFIG, **change))


@pytest.mark.parametrize("name", [
    "smallthinker-21ba3b-l4", "sdar-30b-a3b-l6", "xing4-29b-a4b-l5",
    "laguna-xs2-33b-a3b-l5", "keye-vl2-30b-a3b-lm", "kimi-linear-48b-a3b-l5",
    "glm47-flash-30b-a3b-l5", "solar-open2-250b-a15b-l4"])
def test_an_older_file_still_goes_its_own_way(name):
    """Two tables, no convolution layer, the parameters its file states."""
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        config = json.load(f)
    cfg = lm.LMConfig.from_dict(config)
    assert not cfg.tied and cfg.conv_taps == 0
    assert "conv" not in cfg.attention_layout
    stated = config["parameters"]["total"]
    if not cfg.mtp_layers:      # a held module is counted apart in its file
        assert cfg.parameters() == stated
    for i in range(cfg.n_layers):
        assert ("norm_q" in cfg.layer_shapes(i)) == cfg.qk_norm


# -- a layer of each kind against the reference -----------------------------------------

def _layer_both(layer, dtype, seed=0, cfg=CFG, c=C):
    rng = np.random.default_rng(seed)
    p = _draw(cfg.layer_shapes(layer), rng)
    if "router_bias" in p:  # two of 8 experts held: the bias moves the choice
        p["router_bias"] = p["router_bias"].at[:2].add(0.1)
    x = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    mats, small = _split(p, layer, dtype, cfg)
    rope, _, sparse, kind = cfg.layer_kinds()[layer]
    with ref.PRECISION:
        y, stats, ids = jax.jit(lambda mats, small, x: lm.layer_forward(
            cfg, rope, 0, mats, small, x, None, sparse, kind))(mats, small, x)
        dx, d_mats, d_small = jax.jit(
            lambda mats, small, x, dy: lm.layer_grads(
                cfg, rope, 0, mats, small, x, dy, None, sparse, kind))(
                    mats, small, x, dy)
        given = ids if sparse else None
        want_y, own = jax.jit(lambda p, x: ref.layer(
            c, kind, p, x, given, own=True))(p, x)
        d_p, want_dx = jax.jit(lambda p, x, dy: jax.vjp(
            lambda p, x: ref.layer(c, kind, p, x, given), p, x)[1](dy))(
                p, x, dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": (ids, own),
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p)}


LAYER_TENSORS = [(layer, name) for layer in LAYERS
                 for name in CFG.layer_shapes(layer) if name != "router_bias"]


@pytest.fixture(scope="module")
def exact_layers():
    saved = lm.BF16
    lm.BF16 = jnp.float32
    try:
        return {layer: _layer_both(layer, jnp.float32) for layer in LAYERS}
    finally:
        lm.BF16 = saved


@pytest.fixture(scope="module")
def rounded_layers():
    return {layer: _layer_both(layer, jnp.bfloat16) for layer in LAYERS}


@pytest.mark.parametrize("layer", LAYERS)
def test_a_layer_s_result_is_the_reference_s(layer, exact_layers):
    both = exact_layers[layer]
    assert _relative(*both["y"]) < EXACT
    assert _relative(*both["dx"]) < EXACT
    ids, own = both["ids"]
    if own is None:     # a dense layer: no experts, two zeros of counts
        assert ids.shape == (0, CFG.top_k) and both["stats"].shape == (2,)
    else:
        assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
        assert both["stats"].shape == (2 + CFG.n_experts,)


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


def test_heads_of_64_lanes_are_the_reference_s_too(float32_products):
    """The published head, half a 128-lane tile, off the chip: the chain and
    ``blockwise_attention`` at 64 lanes, every tensor of the layer."""
    cfg, c = lm.LMConfig.from_dict(WIDE), ref.sizes(WIDE)
    assert cfg.head_dim == 64 and cfg.layer_shapes(2)["wq"] == (64, 256)
    assert cfg.layer_shapes(2)["norm_q"] == (64,)
    assert not lm.attention_pass_fused(cfg, T, True)    # whole tiles alone
    both = _layer_both(2, jnp.float32, seed=7, cfg=cfg, c=c)
    assert _relative(*both["y"]) < EXACT
    assert _relative(*both["dx"]) < EXACT
    got, want = both["grads"]
    for name in want:
        if name != "router_bias":
            assert _relative(got[name], want[name]) < 4 * EXACT, name


# -- the convolution: its equations, its reach --------------------------------------------

def _mixer(seed):
    rng = np.random.default_rng(seed)
    p = _draw(CFG.layer_shapes(0), rng)
    mats, small = _split(p, 0)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    return p, mats, small, x


def test_the_mixer_is_two_gates_round_three_taps(float32_products):
    p, mats, small, x = _mixer(31)
    with ref.PRECISION:
        got = shortconv.mix(CFG, mats, lm._zeros_like_f32(mats), small, x)
        out, counts, _ = shortconv.attention_vjp(
            CFG, mats, lm._zeros_like_f32(mats), small, x)
        want = ref.conv_f(C, p, x)
        # written out once more, position by position
        h = np.asarray(ref.rmsnorm(x, p["norm_attn"], C["eps"]) @ p["w_in"],
                       np.float64)
        b, c, into = np.split(h, 3, axis=-1)
        z, w = b * into, np.asarray(p["conv_w"], np.float64)
        taps = np.zeros_like(z)
        for t in range(T):
            for j in range(3):
                if t - 2 + j >= 0:
                    taps[t] += w[:, j] * z[t - 2 + j]
        by_hand = (c * taps) @ np.asarray(p["w_out"], np.float64)
    assert counts == {}
    assert _relative(got, want) < EXACT and _relative(out, want) < EXACT
    assert _relative(jnp.asarray(by_hand, jnp.float32), want) < EXACT


@pytest.mark.parametrize("at", [0, 1, 13, T - 2, T - 1])
def test_the_convolution_s_reach_is_exactly_three(at):
    """Perturbing position ``at`` changes the mixer's result at ``at``, ``at
    + 1`` and ``at + 2`` and at no other position, before or after."""
    _, mats, small, x = _mixer(33)

    def mixed(x):
        return np.asarray(shortconv.mix(CFG, mats, lm._zeros_like_f32(mats),
                                        small, x))

    y, moved = mixed(x), mixed(x.at[at].add(1.0))
    changed = np.flatnonzero(np.any(y != moved, axis=-1))
    assert list(changed) == [t for t in (at, at + 1, at + 2) if t < T]


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
    y = forward(mats, small, x)[0]
    moved = forward(mats, small, x.at[0, 20].add(1.0))[0]
    assert np.array_equal(np.asarray(y[0, :20]), np.asarray(moved[0, :20]))
    assert np.array_equal(np.asarray(y[1]), np.asarray(moved[1]))
    assert not np.array_equal(np.asarray(y[0, 20:]), np.asarray(moved[0, 20:]))
    if KINDS[layer][3] == "conv":   # a convolution layer's reach: 3 exactly
        assert np.array_equal(np.asarray(y[0, 23:]), np.asarray(moved[0, 23:]))
    # and a cotangent at position 20 reaches no position after it
    dy = jnp.zeros_like(x).at[0, 20].set(1.0)
    bf16 = {n: w.astype(jnp.bfloat16) for n, w in mats.items()}
    dx = np.asarray(backward(bf16, small, x, dy)[0])
    assert not np.any(dx[0, 21:]) and not np.any(dx[1]) and np.any(dx[0, :21])
    if KINDS[layer][3] == "conv":
        assert not np.any(dx[0, :18])


# -- the shares add up ---------------------------------------------------------------

def test_four_shares_of_experts_add_up_to_the_uncut_feed_forward(
        float32_products):
    """The guide's test of a share: the four chips' routed parts, each over
    its own two experts of the eight, against the reference's feed-forward
    over all of them (no shared expert: nothing is counted once)."""
    whole = dict(CONFIG, num_experts=8)
    uncut, c = lm.LMConfig.from_dict(whole), ref.sizes(whole)
    rng = np.random.default_rng(13)
    p = _draw(uncut.layer_shapes(3), rng)
    u = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    h, w = CFG.hidden, CFG.expert_width
    normed = lm.rmsnorm(u, p["norm_ffn"], CFG.eps)
    ids, weights = lm.route(uncut, p["router"], normed, p["router_bias"])
    with ref.PRECISION:
        total, parts = jnp.zeros_like(u), []
        for first in range(0, 8, 2):
            share = lm.LMConfig.from_dict(dict(CONFIG,
                                               first_expert_held=first))
            assert share.experts_held == (first, 2)
            mats = {"w_gate": p["w_gate"][first * h:(first + 2) * h],
                    "w_up": p["w_up"][first * h:(first + 2) * h],
                    "w_down": p["w_down"][first * w:(first + 2) * w]}
            parts.append(lm.routed_experts(
                share, mats, lm._zeros_like_f32(mats), normed, ids,
                weights)[0])
            total = total + parts[-1]
        want = ref.feed_forward(c, p, u)
    assert _relative(total, want) < EXACT
    assert _relative(parts[0], want) > 0.3      # one share alone is not it


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
    same start, given the step's chosen experts."""
    from multiverso_tpu.util import configure
    mv.init(["-updater_type=adam"])
    try:
        trainer = PSLMTrainer(CFG, T, B, seed=3, lr=LR, beta1=B1, beta2=B2,
                              eps=EPS, embedding_std=FILE[
                                  "embedding_init_std"])
        tables = trainer.tables()
        start = {n: jnp.asarray(_state(t)[0]).reshape(_shape_of(n))
                 for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        chosen, stats = {}, {}
        calls = iter(range(CFG.n_layers))
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program):
                out = _program(*args)
                i = next(calls)
                chosen[i], stats[i] = out[3], np.asarray(out[1])
                return out
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
        given = [chosen[i] if CFG.sparse(i) else None
                 for i in range(CFG.n_layers)]
        with ref.PRECISION:
            want_loss, grads = jax.jit(jax.value_and_grad(
                lambda p: ref.step_loss(C, p, tokens, given)))(
                    _as_reference(start))
        flat = {n: grads[n] for n in ("embedding", "final_norm")}
        for i, layer in enumerate(grads["layers"]):
            flat.update({f"layer{i}.{n}": v for n, v in layer.items()})
        yield {"loss": loss, "want_loss": float(want_loss), "got": got,
               "start": start, "grads": flat, "stats": stats, "adds": adds,
               "chosen": chosen, "counters": (before, after),
               "names": list(tables), "same": trainer.head
               is trainer.embedding}
    finally:
        mv.shutdown()
        configure.reset_flags()


def _names():
    return ["embedding", "final_norm"] + [
        f"layer{i}.{n}" for i in range(CFG.n_layers)
        for n in CFG.layer_shapes(i)]


SPARSE = [i for i in range(CFG.n_layers) if CFG.sparse(i)]


def test_one_add_to_the_one_table_and_none_to_a_second(run):
    """Exactly one Add a table a step; the table that is embedding and head
    gets it WHOLE (dense Adam), there is no second table of its shape, and
    every bias is under the plain rule."""
    assert sorted(run["names"]) == sorted(_names())
    assert "head" not in run["names"] and run["same"]
    assert sorted(name for name, _ in run["adds"]) == sorted(run["names"])
    assert ("embedding", "add_async") in run["adds"]
    assert not any(method == "add_rows_async" for _, method in run["adds"])
    assert run["adds"][-1][0] == "embedding"        # the step's last
    biases = [n for n in run["names"] if n.endswith("router_bias")]
    assert biases == [f"layer{i}.router_bias" for i in SPARSE]
    for name, (w, state) in run["got"].items():
        if name in biases:
            assert not state, name      # no rule's state: the plain rule
        else:
            assert state and int(state[2]) == 1, name
    assert CFG.parameters() == sum(w.size for w, _ in run["got"].values())


@pytest.mark.parametrize("layer", SPARSE)
def test_a_bias_moves_by_its_rate_against_the_load(run, layer):
    name = f"layer{layer}.router_bias"
    load = np.bincount(np.asarray(run["chosen"][layer]).ravel(),
                       minlength=CFG.n_experts)
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
    gradient that reached the table against the reference's, at bfloat16's
    rounding; and the table moved. The one table's is ``jax.grad``'s of a
    loss that uses it twice: the sum of both uses by construction."""
    w, (m, v, t) = run["got"][name]
    want = np.asarray(run["grads"][name])
    m = np.asarray(m)
    m = m[tuple(slice(0, n) for n in w.shape)] if m.ndim == w.ndim \
        else m.ravel()[:w.size].reshape(w.shape)
    got = m.reshape(want.shape) / (1 - B1)
    assert np.linalg.norm(got - want) < 1.5 * ROUNDED * np.linalg.norm(want), \
        name
    assert np.any(w.reshape(want.shape) != np.asarray(run["start"][name]))


def test_the_one_table_s_gradient_is_neither_use_s_alone(run):
    """Every row moved (the head's gradient reaches every row: dense Adam),
    and the rows the step read carry more than the head's part."""
    w, (m, _, _) = run["got"]["embedding"]
    m = np.asarray(m)[:CFG.vocab, :CFG.hidden] / (1 - B1)
    assert np.all(np.any(m != 0, axis=-1))
    want = np.asarray(run["grads"]["embedding"])
    assert np.linalg.norm(m - want) < 1.5 * ROUNDED * np.linalg.norm(want)


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def counted(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]

    assert counted("LM_STEP") == 1 and counted("LM_TOKENS") == B * T
    assert counted("LM_TIED_ADDS") == 1
    assert counted("LM_ROUTER_BIAS_ADDS") == 6
    stats = run["stats"]
    assert [stats[i].shape for i in range(8)] == [(B, 2)] * 2 \
        + [(B, 2 + CFG.n_experts)] * 6
    assert counted("LM_HELD_ASSIGNMENTS") == sum(
        int(s[:, 0].sum()) for s in stats.values()) > 0
    # six mixers of eight are convolutions, a layer a sequence
    assert counted("LM_MIXERS_CONV") == 6 * B
    assert counted("LM_MIXERS") == 8 * B
    # the two attention layers' heads go to the kernel at their own lanes
    assert counted("LM_ATTN_LANES") == 2 * B * 16 \
        == counted("LM_ATTN_LANES_TILED")
    assert counted("LM_HEADS_HELD") == 2 * B * 4 == counted("LM_HEADS")
    # off the chip: the chain, and no kernel's tile sizes to count
    assert counted("LM_ATTN_PASS_PLAIN") == 2 * B
    assert counted("LM_ATTN_PASS_FUSED") == 0
    assert counted("LM_ATTN_BLOCKS_FITTED") == 0
    assert counted("LM_EXPERTS_SHORT") + counted("LM_EXPERTS_FULL") == 6 * B
    # whole-table traffic: every parameter, the one table among them
    assert counted("LM_ADD_BYTES") == 4 * CFG.parameters()


def test_an_untied_trainer_is_what_it_was():
    """The older families' path: two tables, the embedding's Add by rows."""
    cfg = lm.LMConfig.from_dict(json.load(open(os.path.join(
        CONFIGS, "solar-open2-250b-a15b-l4.json"))))
    assert not cfg.tied
    assert cfg.parameters() == 840872600

"""The fourth family of multiverso_tpu/models/lm (the block of
``model_type: laguna``: grouped-query attention of two kinds, each with its
own query heads and rotary positions, a per-head output gate, a dense
layer and sparse ones with a shared expert under a sigmoid router with no
bias, on the plain residual) against the plain reference
(benchmark/reference/lm_mixed_step.py) at small widths on the CPU: each
kind's result and gradients with every product in float32 (the equations)
and in bfloat16 (the rounding), the rotary, the window mask, the share
test through the feed-forward both residuals call, the description, one
step of ``PSLMTrainer`` through the tables, and the three older families'
programs against the text they lowered to before this family came."""

import dataclasses
import functools
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_mixed_step as ref
from multiverso_tpu.models.lm import PSLMTrainer, model as lm
from multiverso_tpu.models.lm import ps_train, zipf_tokens
from multiverso_tpu.util import dashboard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# full layers: 6 query heads a key-value head, YaRN over the first half of
# a head's lanes, its factor on cos and sin; sliding layers: 8 a key-value
# head, plain rotary over every lane, a window of 8
CONFIG = {
    "hidden_size": 32, "num_attention_heads": 12, "num_key_value_heads": 2,
    "head_dim": 16, "num_attention_heads_per_layer": [12, 16, 12],
    "layer_types": ["full_attention", "sliding_attention", "full_attention"],
    "mlp_layer_types": ["dense", "sparse", "sparse"], "sliding_window": 8,
    "rope_parameters": {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 16, "beta_slow": 1,
            "beta_fast": 4, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                              "partial_rotary_factor": 1}},
    "gating": True, "attention_bias": False,
    "moe_apply_router_weight_on_input": False, "intermediate_size": 48,
    "router_outputs": 8, "num_experts": 4, "first_expert_held": 2,
    "num_experts_per_tok": 2, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "moe_routed_scaling_factor": 2.5,
    "num_hidden_layers": 3, "vocab_size": 97, "rms_norm_eps": 1e-6,
    "loss_block": 16}
T, B = 32, 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8
EXACT = 2e-4        # float32 products against the reference's: rounding
# bfloat16 products at these widths (tests/test_lm_model.py's reasons); what
# feeds the scores reads higher (tests/test_lm_mla.py's reason)
ROUNDED, ROUNDED_SCORES = 1e-1, 2.5e-1
FEEDS_SCORES = ("wq", "wk", "norm_attn")
CFG = lm.LMConfig.from_dict(CONFIG)
C = ref.sizes(CONFIG)
LAYERS = {"full_dense": 0, "sliding_sparse": 1, "full_sparse": 2}


def _limit(name):
    return ROUNDED_SCORES if name.rsplit(".", 1)[-1] in FEEDS_SCORES \
        else ROUNDED


def _relative(a, b):
    a, b = jnp.ravel(a), jnp.ravel(b)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _draw(shapes, rng):
    """Seeded tensors, every mechanism awake: norms near 1, gates and
    routers that differ from token to token."""
    return {name: jnp.asarray(
        1 + 0.1 * rng.normal(size=shape) if len(shape) == 1
        else rng.normal(0, 0.3 if name in ("w_attn_gate", "router") else 0.08,
                        shape), jnp.float32)
        for name, shape in shapes.items()}


def _split(cfg, p, layer, dtype=jnp.float32):
    mats = {n: p[n].astype(dtype) for n in cfg.matrices(layer)}
    return mats, {n: p[n] for n in p if n not in mats}


def _kind(cfg, layer):
    rope, window, sparse, _ = cfg.layer_kinds()[layer]
    return cfg.rotary(rope, window), cfg.window if window else 0, sparse


@pytest.fixture
def float32_products(monkeypatch):
    monkeypatch.setattr(lm, "BF16", jnp.float32)


def _layer_both(layer, dtype, seed=0):
    """A layer through the program and through the reference (kept: the
    tests of one layer read one run)."""
    return _layer_both_of(layer, dtype, seed, lm.BF16)


@functools.lru_cache(maxsize=None)
def _layer_both_of(layer, dtype, seed, products):
    del products    # the key: what ``float32_products`` replaced
    rng = np.random.default_rng(seed)
    p = _draw(CFG.layer_shapes(layer), rng)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    mats, small = _split(CFG, p, layer, dtype)
    rope, window, sparse = _kind(CFG, layer)
    windowed = CFG.window_layout[layer]
    with ref.PRECISION:
        y, stats, ids = lm.layer_forward(CFG, rope, window, mats, small, x,
                                         sparse=sparse)
        dx, d_mats, d_small = lm.layer_grads(CFG, rope, window, mats, small,
                                             x, dy, sparse=sparse)
        chosen = ids if sparse else None
        want_y, own = ref.layer(C, windowed, p, x, chosen, own=True)
        d_p, want_dx = jax.vjp(
            lambda p, x: ref.layer(C, windowed, p, x, chosen), p, x)[1](dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": (ids, own),
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p), "p": p,
            "x": x}


# -- the description ------------------------------------------------------------

def test_the_fourth_family_is_told_by_its_keys():
    assert CFG.attention == "gqa" and CFG.residual == "plain"
    assert CFG.scoring == "sigmoid" and CFG.attn_gate == "head"
    assert CFG.one_ffn_input and CFG.routed_scale == 2.5
    assert CFG.ffn_layout == (0, 1, 1) and CFG.window_layout == (0, 1, 0)
    assert CFG.heads_layout == (12, 16, 12) and CFG.experts_held == (2, 4)
    assert CFG.layer_kinds() == ((1, 0, 0, 12), (1, 1, 1, 16), (1, 0, 1, 12))
    full, sliding = CFG.rotary_kinds
    assert full == lm.Rotary(500000.0, 8, (64.0, 4.0, 1.0, 16.0),
                             1.4158883083359672)
    assert sliding == lm.Rotary(10000.0, 16)
    assert CFG.rotary(1, 1) is sliding and CFG.rotary(1, 0) is full


def test_a_layer_s_shapes_follow_its_kinds():
    dense, sliding, full = (CFG.layer_shapes(i) for i in range(3))
    assert dense["wq"] == (32, 12 * 16) and sliding["wq"] == (32, 16 * 16)
    assert dense["wo"] == (12 * 16, 32) and sliding["wo"] == (16 * 16, 32)
    assert dense["w_attn_gate"] == (32, 12)
    assert sliding["w_attn_gate"] == (32, 16)
    assert dense["wk"] == sliding["wk"] == (32, 2 * 16)
    assert dense["w_gate"] == (32, 48) and "router" not in dense
    assert sliding["router"] == (32, 8) and "router_bias" not in sliding
    assert sliding["w_gate"] == (4 * 32, 16) and full["ws_down"] == (16, 32)
    assert CFG.matrices(0) == ("wq", "wk", "wv", "wo", "w_attn_gate",
                               "w_gate", "w_up", "w_down")
    assert CFG.matrices(1) == CFG.matrices(0) + ("ws_gate", "ws_up",
                                                 "ws_down")


def _published():
    with open(os.path.join(
            ROOT, "benchmark/configs/laguna-xs2-33b-a3b-l5.json")) as f:
        return json.load(f)


def test_the_configuration_s_count():
    """691,623,936 parameters in 69 tables at the configuration's sizes:
    48 heads on the full layers, 64 on the sliding ones; and the whole
    model's count, which decides what ``gating`` gates (``assumed``)."""
    config = _published()
    cfg = lm.LMConfig.from_dict(config)
    assert cfg.layer_kinds() == (
        (1, 0, 0, 48), (1, 1, 1, 64), (1, 1, 1, 64), (1, 1, 1, 64),
        (1, 0, 1, 48))
    assert cfg.layer_shapes(0)["wq"] == (2048, 48 * 128)
    assert cfg.layer_shapes(1)["wq"] == (2048, 64 * 128)
    assert cfg.layer_shapes(1)["w_gate"] == (32 * 2048, 512)
    assert cfg.parameters() == 691_623_936 \
        == config["parameters"]["total"]
    tables = 3 + sum(len(cfg.layer_shapes(i)) for i in range(5))
    assert tables == 69 == config["parameters"]["tables"]
    full, sliding = cfg.rotary_kinds
    assert (full.lanes, sliding.lanes, cfg.window) == (64, 128, 512)
    whole = dict(config, **config["published"], router_outputs=256)
    assert lm.LMConfig.from_dict(whole).parameters() == 33_442_596_864


# -- the rotary ---------------------------------------------------------------------

def test_a_rotated_prefix_leaves_the_other_lanes_bit_equal():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.normal(size=(T, 3, 16)), jnp.float32)
    full, _ = CFG.rotary_kinds
    got = lm._rotary(x, full.theta, **full.how())
    assert np.array_equal(got[..., 8:], x[..., 8:])
    assert not np.any(np.asarray(got[1:, :, :8]) == np.asarray(x[1:, :, :8]))
    with ref.PRECISION:
        want = ref.rotary(x, C["rotary"][0])
    assert _relative(got, want) < 1e-6


def test_the_factor_is_on_cos_and_sin():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(T, 2, 16)), jnp.float32)
    full, _ = CFG.rotary_kinds
    plain = dataclasses.replace(full, factor=1.0)
    got = lm._rotary(x, full.theta, **full.how())
    bare = lm._rotary(x, plain.theta, **plain.how())
    np.testing.assert_allclose(got[..., :8], full.factor * bare[..., :8],
                               rtol=1e-5, atol=1e-6)
    # position 0 is turned by nothing: the factor alone
    np.testing.assert_allclose(got[0, :, :8], full.factor * x[0, :, :8],
                               rtol=1e-6)


def test_yarn_blends_between_theta_s_frequencies_and_theirs_over_the_factor():
    inv = lm.yarn_frequencies(500000.0, 64, 64.0, 64.0, 1.0, 4096.0)
    own = 1.0 / 500000.0 ** (np.arange(0, 64, 2) / 64)
    assert inv.shape == (32,) and inv[0] == own[0]
    np.testing.assert_allclose(inv[-1], own[-1] / 64)
    assert np.all(inv <= own) and np.all(inv >= own / 64 * (1 - 1e-12))
    r = {"theta": 500000.0, "lanes": 64, "yarn": True, "factor": 64.0,
         "beta_fast": 64.0, "beta_slow": 1.0, "original": 4096.0}
    np.testing.assert_allclose(inv, ref.yarn_frequencies(r), rtol=1e-5)


def test_the_sliding_layers_rotary_is_the_older_families():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(T, 2, 16)), jnp.float32)
    _, sliding = CFG.rotary_kinds
    assert np.array_equal(lm._rotary(x, sliding.theta, **sliding.how()),
                          lm._rotary(x, 10000.0))


# -- the window mask around the kernel's block ------------------------------------------

@pytest.mark.parametrize("window", [16, 8, 24, 1])
def test_a_window_equal_to_under_and_over_the_block(window):
    """``blockwise_attention`` at a block of 16 queries under a window
    equal to the block, half of it, one and a half blocks, and one key:
    the sum over the key ranges ``Mask.key_ranges`` names against the
    dense predicate."""
    rng = np.random.default_rng(window)
    t, groups, per, d = 64, 2, 3, 16
    q = jnp.asarray(rng.normal(size=(groups, per, t, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(groups, t, d)), jnp.float32)
            for _ in range(2))
    with ref.PRECISION:
        got = lm.blockwise_attention(q, k, v, window, block=16)
        i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
        seen = (j <= i) & (j > i - window)
        s = jnp.where(seen, jnp.einsum("ghqd,gkd->ghqk", q, k), -jnp.inf)
        want = jnp.einsum("ghqk,gkd->ghqd", jax.nn.softmax(s, -1), v)
    assert _relative(got, want) < 1e-2      # the probabilities' bfloat16
    mask = lm.Mask.of(window)
    for lo in range(0, t, 16):
        (first, last), = mask.key_ranges(lo, lo + 16)
        assert first == max(lo - window + 1, 0) and last == lo + 16
    assert np.array_equal(np.asarray(mask.visible(i, j)), np.asarray(seen))


# -- a layer of each kind against the reference -----------------------------------------

@pytest.mark.parametrize("kind", list(LAYERS))
def test_a_layer_is_the_reference_s_in_float32(kind, float32_products):
    out = _layer_both(LAYERS[kind], jnp.float32)
    assert _relative(*out["y"]) < EXACT and _relative(*out["dx"]) < EXACT
    grads, want = out["grads"]
    assert sorted(grads) == sorted(want)
    for name, grad in grads.items():
        assert _relative(grad.reshape(want[name].shape),
                         want[name]) < EXACT, name
    if LAYERS[kind]:
        ids, own = out["ids"]
        assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))


@pytest.mark.parametrize("kind", list(LAYERS))
def test_a_layer_is_the_reference_s_at_bfloat16_rounding(kind):
    out = _layer_both(LAYERS[kind], jnp.bfloat16, seed=1)
    assert _relative(*out["y"]) < 2e-2 and _relative(*out["dx"]) < ROUNDED
    grads, want = out["grads"]
    for name, grad in grads.items():
        assert _relative(grad.reshape(want[name].shape),
                         want[name]) < _limit(name), name


@pytest.mark.parametrize("kind", list(LAYERS))
def test_a_layer_s_stats(kind, float32_products):
    layer = LAYERS[kind]
    out = _layer_both(layer, jnp.float32)
    stats, (ids, _) = np.asarray(out["stats"]), out["ids"]
    heads = CFG.heads(layer)
    # the gates' sum over heads of their mean over tokens, in thousandths
    assert 0 < stats[-1] < 1000 * heads
    p, x = out["p"], out["x"]
    gate = jax.nn.sigmoid(
        ref.rmsnorm(x, p["norm_attn"], 1e-6) @ p["w_attn_gate"])
    assert abs(stats[-1] - 1000 * float(gate.mean(0).sum())) <= 1
    if not layer:
        assert stats.shape == (3,) and ids.shape == (0, 2)
        return
    assert stats.shape == (2 + 8 + 1,)
    load = np.bincount(np.asarray(ids).ravel(), minlength=8)
    assert np.array_equal(stats[2:10], load)
    assert stats[0] == load[2:6].sum() and stats[1] == load[2:6].max()


def test_without_its_gate_a_layer_differs():
    """The gate is in the result: the same tensors through a model that
    has none give another layer (and a gate of zeros logits halves the
    attention's part)."""
    rng = np.random.default_rng(0)
    p = _draw(CFG.layer_shapes(1), rng)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    bare = dataclasses.replace(CFG, attn_gate="none")
    mats, small = _split(CFG, p, 1)
    rope, window, sparse = _kind(CFG, 1)
    sinks = {n: jnp.zeros(w.shape) for n, w in mats.items()}
    with ref.PRECISION:
        a = lm.attention_block(CFG, rope, window, mats, sinks,
                               small["norm_attn"], x)
        a_bare = lm.attention_block(bare, rope, window, mats, sinks,
                                    small["norm_attn"], x)
        mats["w_attn_gate"] = jnp.zeros_like(mats["w_attn_gate"])
        a_half = lm.attention_block(CFG, rope, window, mats, sinks,
                                    small["norm_attn"], x)
    assert _relative(a - x, a_bare - x) > 0.2
    assert _relative(a_half - x, 0.5 * (a_bare - x)) < 1e-2


# -- the share: eight shares and the shared expert once are the uncut layer -----------------

@pytest.mark.parametrize("residual", ["plain", "mhc"])
def test_the_expert_shares_add_up_to_the_uncut_feed_forward(
        residual, float32_products):
    """The feed-forward that the plain residual's layers and the streams'
    sublayers both call (``model.feed_forward_vjp``), as each describes it
    (the plain model's sigmoid scores; the streams' chosen through a
    bias): two shares of four experts each, the shared expert counted
    once, against the reference's uncut layer."""
    rng = np.random.default_rng(7)
    uncut = dataclasses.replace(CFG, experts_held=(0, 8))
    if residual == "mhc":
        uncut = dataclasses.replace(uncut, scoring="sigmoid_bias")
    p = _draw(uncut.layer_shapes(1), rng)
    if residual == "mhc":
        p["router_bias"] = jnp.zeros(8)
    u = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    c = dict(C, held=8, first_held=0)
    with ref.PRECISION:
        want = ref.feed_forward(c, p, u)
        shared = want - ref.feed_forward(c, p, u, shared=False)
        total, seen = 0.0, 0
        h, w = CFG.hidden, CFG.expert_width
        for first in (0, 4):
            share = dataclasses.replace(uncut, experts_held=(first, 4))
            cut = dict(p)
            for name, rows in (("w_gate", h), ("w_up", h), ("w_down", w)):
                cut[name] = p[name][first * rows:(first + 4) * rows]
            mats, small = _split(share, cut, 1)
            sinks = {n: jnp.zeros_like(m) for n, m in mats.items()}
            y, (_, sizes, load), _ = lm.feed_forward_vjp(
                share, 1, mats, sinks, small, u)
            total = total + (y - shared)
            seen += int(jnp.sum(sizes))
            assert int(jnp.sum(load)) == T * 2
        assert seen == T * 2
        assert _relative(total + shared, want) < EXACT


# -- one step of the trainer through the tables ---------------------------------------------

def _state(table):
    server = table.zoo.server_tables[table.table_id]
    return np.asarray(table.get_device()), server._engine.state


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
    return {"embedding": values["embedding"], "head": values["head"],
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
                              eps=EPS)
        tables = trainer.tables()
        start = {n: jnp.asarray(_state(t)[0]).reshape(_shape_of(n))
                 for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        chosen, stats = [], []
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program, _sparse=kind[2]):
                out = _program(*args)
                chosen.append(out[3] if _sparse else None)
                stats.append(np.asarray(out[1]))
                return out
            trainer._forward[kind] = spy
        tokens = zipf_tokens(jax.random.PRNGKey(5), (B, T + 1), CFG.vocab)
        loss = float(trainer.step(tokens))
        trainer.sync()
        trainer.flush_stats()
        after = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        got = {n: _state(t) for n, t in tables.items()}
        with ref.PRECISION:
            want_loss, grads = jax.jit(jax.value_and_grad(
                lambda p: ref.step_loss(C, p, tokens, chosen)))(
                    _as_reference(start))
        flat = {n: grads[n] for n in ("embedding", "head", "final_norm")}
        for i, layer in enumerate(grads["layers"]):
            flat.update({f"layer{i}.{n}": v for n, v in layer.items()})
        yield {"loss": loss, "want_loss": float(want_loss), "got": got,
               "start": start, "grads": flat, "stats": stats,
               "counters": (before, after), "names": list(tables)}
    finally:
        mv.shutdown()
        configure.reset_flags()


def _names():
    names = ["embedding", "head", "final_norm"]
    return names + [f"layer{i}.{n}" for i in range(3)
                    for n in CFG.layer_shapes(i)]


def test_every_table_is_under_adam_and_none_under_the_plain_rule(run):
    assert run["names"] == ["embedding"] + _names()[3:] + ["final_norm",
                                                          "head"]
    assert not [n for n in run["names"] if n.endswith("router_bias")]
    for name, (w, state) in run["got"].items():
        assert state and int(state[2]) == 1, name
    assert CFG.parameters() == sum(w.size for w, _ in run["got"].values())
    before, after = run["counters"]
    assert "LM_ROUTER_BIAS_ADDS" not in after or \
        after["LM_ROUTER_BIAS_ADDS"] == before.get("LM_ROUTER_BIAS_ADDS")


def test_the_step_s_loss_is_the_reference_s(run):
    assert abs(run["loss"] - run["want_loss"]) < 2e-3 * run["want_loss"]


@pytest.mark.parametrize("name", _names())
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
    assert np.linalg.norm(got - want) < _limit(name) * np.linalg.norm(want), \
        name
    assert np.any(w.reshape(want.shape) != np.asarray(run["start"][name]))


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def delta(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]

    assert delta("LM_STEP") == 1 and delta("LM_TOKENS") == B * T
    stats = run["stats"]        # by layer: [B, 3], [B, 11], [B, 11]
    assert [s.shape for s in stats] == [(B, 3), (B, 11), (B, 11)]
    fullest = sum(int(s[:, 2:10].sum(0).max()) for s in stats[1:])
    assert delta("LM_ROUTER_LOAD_MAX") == fullest >= 2 * B * T * 2 / 8
    assert delta("LM_HELD_ASSIGNMENTS") == sum(
        int(s[:, 0].sum()) for s in stats) > 0
    # one a sparse layer a sequence, the dense layer's zeros not among
    # them; at these widths there is one buffer
    assert delta("LM_EXPERTS_SHORT") == 2 * B
    assert delta("LM_EXPERTS_FULL") == 0
    # a fresh gate is half open: 12 + 16 + 12 heads, in thousandths
    opened = delta("LM_GATE_OPEN")
    assert opened == int(round(sum(s[:, -1].mean() for s in stats)))
    assert abs(opened / (1000 * 40) - 0.5) < 0.02
    tables = len(run["names"])
    assert delta("WORKER_PROCESS_GET") == tables + 1
    assert delta("WORKER_PROCESS_ADD") == tables


# -- the gate's backward pass carries its scope ---------------------------------------------

def test_the_gate_s_two_passes_are_named():
    shapes = CFG.layer_shapes(1)
    mats = {n: jnp.zeros(shapes[n], jnp.bfloat16) for n in CFG.matrices(1)}
    small = {n: jnp.ones(s) for n, s in shapes.items() if n not in mats}
    x = jnp.ones((B, T, CFG.hidden))
    program = ps_train.backward_program(CFG, 1, 1, T, 1)
    text = program.lower(mats, small, x, x).as_text(debug_info=True)
    for scope in ("mv.lm.attn.gate", "mv.lm.attn.window.kernel",
                  "mv.lm.shared_expert", "mv.lm.router", "mv.lm.experts"):
        assert scope in text, scope
    assert "transpose(jvp(mv.lm.attn.gate))" not in text
    dense = ps_train.backward_program(CFG, 1, 0, T, 0).lower(
        {n: jnp.zeros(CFG.layer_shapes(0)[n], jnp.bfloat16)
         for n in CFG.matrices(0)},
        {n: jnp.ones(s) for n, s in CFG.layer_shapes(0).items()
         if n not in CFG.matrices(0)}, x, x).as_text(debug_info=True)
    assert "mv.lm.dense_mlp" in dense and "mv.lm.attn.full" in dense
    assert "mv.lm.router" not in dense


# -- the older families' programs are the text they were ------------------------------------

# sha256 (16 hex digits) of ``lower(..).as_text()`` of each kind's forward
# and backward program at the configuration's rehearsal widths, made from
# the commit before this family (8b1e93e) by this file's ``_digests`` under
# the same JAX: a change to what the three older cells run shows here, on
# the CPU, before any chip is asked. After a change that is MEANT to move
# them, run ``_digests`` on the parent and replace these.
PARENT_TEXT = {
    ("smallthinker-21ba3b-l4", "lm-ps-step-8k"): {
        (0, 0): ("aeddbfffe7535c6a", "3ea1159826c81c4f"),
        (1, 1): ("c6a3e7faed290248", "7d8290e858ea20bb")},
    ("sdar-30b-a3b-l6", "lm-ps-blockdiff-4k"): {
        (1, 0): ("e69ad622ed870da4", "fdfd380af55bc60e")},
    ("xing4-29b-a4b-l5", "lm-ps-step-4k"): {
        (1, 0, 0): ("431bf4dbc52fa4dc", "186d4f0a9fc791f5"),
        (1, 0, 1): ("7a2a627f49c34e43", "a073891544c7dca7")}}


def _rehearsal(*parts):
    with open(os.path.join(ROOT, "benchmark", *parts)) as f:
        params = json.load(f)
    params.update(params.pop("rehearsal"))
    return params


def _digests(config, traffic):
    cfg = lm.LMConfig.from_dict(_rehearsal("configs", f"{config}.json"))
    sizes = _rehearsal("traffic", f"{traffic}.json")
    t, b = sizes["seq_len"], sizes["sequences_per_step"]
    kinds, out = cfg.layer_kinds(), {}
    for kind in sorted(set(kinds)):
        layer = kinds.index(kind)
        shapes = cfg.layer_shapes(layer)
        mats = {n: jnp.zeros(shapes[n], jnp.bfloat16)
                for n in cfg.matrices(layer)}
        small = {n: jnp.ones(s) for n, s in shapes.items() if n not in mats}
        x = jnp.ones((b, cfg.hc_mult * cfg.hidden, t)) \
            if cfg.residual == "mhc" else jnp.ones(
                (b, t * (1 + (cfg.objective == "block_diffusion")),
                 cfg.hidden))
        texts = (
            ps_train.forward_program(cfg, *kind[:2], t, *kind[2:]).lower(
                {n: w.astype(jnp.float32) for n, w in mats.items()}, small,
                x).as_text(),
            ps_train.backward_program(cfg, *kind[:2], t, *kind[2:]).lower(
                mats, small, x, x).as_text())
        out[kind] = tuple(hashlib.sha256(text.encode()).hexdigest()[:16]
                          for text in texts)
    return out


@pytest.mark.parametrize("config,traffic", list(PARENT_TEXT))
def test_an_older_family_s_programs_lower_to_the_parent_s_text(config,
                                                                traffic):
    assert _digests(config, traffic) == PARENT_TEXT[(config, traffic)]

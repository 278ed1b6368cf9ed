"""The one pass between the attention's projections and its kernel
(multiverso_tpu/models/lm/attn_kernels.py), interpreted on the CPU, against
the ``jax.numpy`` chain of ``model.attention_inputs`` that runs everywhere
but on a TPU and is the pass's definition: forward and pull over the four
families' cases, through ``attention_vjp``, and that the plain chain runs,
and is counted, where a sequence is no whole number of the pass's blocks or
a layer has neither head norms nor a turn."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.lm import (LMConfig, PSLMTrainer, attn_kernels,
                                      model as lm)
from multiverso_tpu.util import dashboard

F32, BF16 = jnp.float32, jnp.bfloat16
T, G, D = 1024, 2, 128
EPS, THETA = 1e-6, 10000.0
# name: query heads a key-value head, head norms, and what ``_rotary`` takes
# beside theta (None: no rotary), as the four families have them
CASES = {
    "no_norms_no_rotary": (7, False, None),                 # smallthinker, layer 0
    "rotary_alone": (7, False, {}),                         # smallthinker
    "head_norms_and_rotary": (8, True, {}),                 # sdar
    "partial_rotary_with_yarn_s_factor": (6, False, {       # laguna, full layers
        "lanes": 64, "factor": 1.2079,
        "inv": lm.yarn_frequencies(500000.0, 64, 32.0, 64.0, 1.0, 4096.0)}),
    "three_sections": (8, True, {                           # keye
        "lanes": D, "sections": (16, 24, 24),
        "pos": np.stack([np.arange(T), np.arange(T) // 3,
                         (np.arange(T) * 7) % 501])}),
    "block_diffusion_positions": (8, True, {                # sdar's two copies
        "pos": lm.Mask.blockdiff(T // 2, 4).positions(T)})}


def _inputs(per, norm, seed=0):
    rng = np.random.default_rng(seed)
    products = tuple(jnp.asarray(rng.normal(size=(T, G * n * D)), F32)
                     for n in (per, 1, 1))
    scales = tuple(jnp.asarray(1 + 0.1 * rng.normal(size=D), F32)
                   for _ in range(2)) if norm else ()
    return products, scales


def _chain(per, how, dtype, qf, kf, vf, scales):
    """``model.attention_inputs``' lines after the products."""
    q, k, v = (a.reshape(T, -1, D) for a in (qf, kf, vf))
    if scales:
        q, k = lm.rmsnorm(q, scales[0], EPS), lm.rmsnorm(k, scales[1], EPS)
    if how is not None:
        q, k = lm._rotary(q, THETA, **how), lm._rotary(k, THETA, **how)
    q = (q * (1.0 / math.sqrt(D))).astype(dtype)
    return (q.reshape(T, G, per, D).transpose(1, 2, 0, 3),
            k.astype(dtype).transpose(1, 0, 2),
            v.astype(dtype).transpose(1, 0, 2))


def _pass(per, how, dtype, qf, kf, vf, scales):
    tables = () if how is None else tuple(
        jnp.asarray(table, F32) for table in lm.rotary_tables(
            T, D, THETA, **how))
    return attn_kernels.heads_in(
        attn_kernels.Pass(per, D, 0 if how is None else how.get("lanes", D),
                          bool(scales), EPS, 1.0 / math.sqrt(D), dtype),
        qf, kf, vf, scales, tables)


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    monkeypatch.setattr(attn_kernels, "INTERPRET", True)


def _ties(got, want):
    """How many entries differ, each by no more than a step of bfloat16
    (or, where a turn's two terms cancel, by float32's rounding of terms
    the size of the inputs)."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    off = got != want
    assert np.all(np.abs(got - want)[off] <= 2.0 ** -7 * np.abs(want)[off]
                  + 1e-6), "more than a rounding"
    return int(off.sum())


@pytest.mark.parametrize("case", list(CASES))
def test_the_pass_gives_the_chain_s_heads(case):
    """Equal to float32 rounding before the rounding to bfloat16, and the
    same bfloat16 after it but for a counted handful of ties."""
    per, norm, how = CASES[case]
    products, scales = _inputs(per, norm)
    for got, want in zip(_pass(per, how, F32, *products, scales),
                         _chain(per, how, F32, *products, scales)):
        assert got.shape == want.shape and got.dtype == F32
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    for got, want in zip(_pass(per, how, BF16, *products, scales),
                         _chain(per, how, BF16, *products, scales)):
        assert got.dtype == BF16
        assert _ties(got, want) <= 2e-4 * want.size
    if how is None:     # nothing but the scale, the rounding and the turn
        assert all(_ties(a, b) == 0 for a, b in zip(
            _pass(per, how, BF16, *products, scales),
            _chain(per, how, BF16, *products, scales)))


def test_the_unturned_lanes_of_a_partial_rotary_pass_bit_for_bit():
    per, norm, how = CASES["partial_rotary_with_yarn_s_factor"]
    (qf, kf, vf), scales = _inputs(per, norm)
    qf = qf.at[:, 64:128].set(-0.0)     # a sign that a sum with zero loses
    q, k, _ = _pass(per, how, F32, qf, kf, vf, scales)
    want_q, want_k, _ = _chain(per, how, F32, qf, kf, vf, scales)
    for got, want in ((q, want_q), (k, want_k)):
        assert np.array_equal(np.asarray(got)[..., 64:].view(np.uint32),
                              np.asarray(want)[..., 64:].view(np.uint32))
    assert np.array_equal(np.asarray(k)[..., 64:],
                          np.asarray(kf).reshape(T, G, D).transpose(
                              1, 0, 2)[..., 64:])


@pytest.mark.parametrize("case", list(CASES))
def test_the_pull_gives_the_chain_s_cotangents(case):
    """``dq``, ``dk``, ``dv`` back to the products' results, rounded to
    bfloat16 as ``mm``'s backward rule rounds them, and the two head norms'
    scale gradients."""
    per, norm, how = CASES[case]
    products, scales = _inputs(per, norm)
    rng = np.random.default_rng(1)
    heads, pull = jax.vjp(lambda *a: _pass(per, how, BF16, *a), *products,
                          scales)
    cotangents = tuple(jnp.asarray(rng.normal(size=h.shape), BF16)
                       for h in heads)
    *got, got_scales = pull(cotangents)
    *want, want_scales = jax.vjp(
        lambda *a: _chain(per, how, BF16, *a), *products, scales)[1](
            cotangents)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == F32
        # what the pass wrote is already the bfloat16 ``mm`` would make
        assert np.array_equal(np.asarray(g), np.asarray(g.astype(BF16), F32))
        assert _ties(g, w.astype(BF16)) <= 2e-4 * w.size
    assert len(got_scales) == len(want_scales) == 2 * norm
    for g, w in zip(got_scales, want_scales):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-5
                                   * float(jnp.abs(w).max()))


# -- through ``attention_vjp``: a layer's attention, forward and pulled ---------

def _family(case, gate=False):
    """A configuration of 128-lane heads whose attention is ``case``'s
    (with ``gate``, under laguna's per-head output gate), its layer's
    tensors, and what ``attention_vjp`` takes of rotary, mask and
    positions."""
    per, norm, how = CASES[case]
    base = {"hidden_size": 64, "num_attention_heads": G * per,
            "num_key_value_heads": G, "head_dim": D, "vocab_size": 64,
            "rope_theta": THETA, "rms_norm_eps": EPS, "loss_block": 16}
    if norm:
        cfg = LMConfig.from_dict({
            **base, "model_type": "sdar_moe", "num_hidden_layers": 1,
            "router_outputs": 4, "num_experts": 2, "num_experts_per_tok": 2,
            "moe_intermediate_size": 16, "hidden_act": "silu",
            "norm_topk_prob": True})
    else:
        cfg = LMConfig.from_dict({
            **base, "num_hidden_layers": 1, "router_outputs": 4,
            "moe_num_primary_experts": 2,
            "moe_num_active_primary_experts": 2, "moe_ffn_hidden_size": 16,
            "rope_layout": [1], "sliding_window_layout": [1],
            "sliding_window_size": 64})
    if gate:
        cfg = dataclasses.replace(cfg, attn_gate="head")
    how = dict(how or {})
    pos = how.pop("pos", None)
    rope = 0 if CASES[case][2] is None else lm.Rotary(
        theta=THETA, lanes=how.get("lanes", D),
        sections=how.get("sections", ()), factor=how.get("factor", 1.0))
    rng = np.random.default_rng(2)
    shapes = cfg.layer_shapes()
    mats = {n: jnp.asarray(rng.normal(size=shapes[n]) * shapes[n][0] ** -0.5,
                           BF16)
            for n in lm.GQA_MATRICES + ((lm.ATTN_GATE,) if gate else ())}
    small = {n: jnp.asarray(1 + 0.1 * rng.normal(size=s), F32)
             for n, s in shapes.items() if len(s) == 1}
    x = jnp.asarray(rng.normal(size=(T, cfg.hidden)), F32)
    mask = lm.Mask.blockdiff(T // 2, 4) if case.startswith("block") else 64
    return cfg, rope, mask, mats, small, x, pos


def _attention(cfg, rope, mask, mats, small, x, pos):
    a, _, pull = lm.attention_vjp(cfg, rope, mask, mats,
                                  lm._zeros_like_f32(mats), small, x, pos)
    da = jnp.asarray(np.random.default_rng(3).normal(size=a.shape), F32)
    return (a,) + tuple(pull(da))


def _relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("case, gate", [(case, False) for case in CASES] + [
    ("partial_rotary_with_yarn_s_factor", True), ("rotary_alone", True)])
def test_a_layer_s_attention_through_the_pass_is_the_chain_s(case, gate,
                                                              monkeypatch):
    """``a``, ``dx`` and every gradient of ``attention_vjp`` with the pass
    in it against the plain chain's: a few ties of the bfloat16 roundings
    apart; under laguna's gate too, which reads the normed input beside the
    heads."""
    family = _family(case, gate)
    want = _attention(*family)
    monkeypatch.setattr(attn_kernels, "fits", lambda t, d: True)
    # with neither norm nor turn the chain stays (and the two agree exactly)
    assert lm.attention_pass_fused(family[0], T, family[1]) == (
        case != "no_norms_no_rotary")
    got = _attention(*family)
    leaves = jax.tree_util.tree_leaves
    assert [g.shape for g in leaves(got)] == [w.shape for w in leaves(want)]
    for g, w in zip(leaves(got), leaves(want)):
        assert _relative(g, w) < 2e-3


def test_a_replaced_rotary_takes_the_chain_that_calls_it(monkeypatch):
    """The checks' controls put their own ``_rotary`` in the module: the
    pass would not call it, so the chain runs."""
    cfg = _family("rotary_alone")[0]
    monkeypatch.setattr(attn_kernels, "fits", lambda t, d: True)
    assert lm.attention_pass_fused(cfg, T)
    monkeypatch.setattr(lm, "_rotary", lambda x, theta: x)
    assert not lm.attention_pass_fused(cfg, T)


# -- where the plain chain runs, and the counters -----------------------------------

@pytest.mark.parametrize("t, d, fused", [
    (8192, 128, True), (16384, 128, True), (512, 256, True),
    (8192 + 256, 128, False), (96, 128, False), (8192, 64, False)])
def test_the_pass_takes_whole_blocks_of_tokens_and_whole_tiles_of_lanes(
        t, d, fused, monkeypatch):
    assert not attn_kernels.fits(t, d)      # here: no TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert attn_kernels.fits(t, d) == fused
    cfg = dataclasses.replace(_family("rotary_alone")[0], head_dim=d)
    assert lm.attention_pass_fused(cfg, t) == fused


def _counted():
    monitors = dashboard.metrics_snapshot(max_samples=0)["monitors"]
    return [monitors.get(n, {"count": 0})["count"]
            for n in ("LM_ATTN_PASS_FUSED", "LM_ATTN_PASS_PLAIN")]


@pytest.mark.parametrize("forms, sequences, fused, plain", [
    (["LM_ATTN_PASS_FUSED"] * 5, 2, 10, 0),
    (["LM_ATTN_PASS_PLAIN"] * 4, 2, 0, 8),
    # smallthinker's layer 0 is not turned: a quarter plain
    (["LM_ATTN_PASS_PLAIN"] + ["LM_ATTN_PASS_FUSED"] * 3, 2, 6, 2),
    ([None] * 5, 2, 0, 0)])     # latent attention: neither
def test_the_trainer_counts_one_a_layer_a_sequence(forms, sequences, fused,
                                                   plain):
    trainer = PSLMTrainer.__new__(PSLMTrainer)
    trainer.cfg = _family("rotary_alone")[0]
    trainer._sparse, trainer._experts_cap = [1] * len(forms), 1 << 30
    trainer._attn_pass, trainer._heads = forms, (1, 1)
    trainer._attn_blocks = []
    before = _counted()
    trainer._count_stats(([np.zeros((sequences, 2), int)] * len(forms), 5, 7))
    assert [a - b for a, b in zip(_counted(), before)] == [fused, plain]


def test_a_layer_with_neither_norm_nor_turn_keeps_the_chain(monkeypatch):
    """The scale, the rounding and the layout alone go into the products'
    own fusions: the pass would only add a read and a write to them."""
    monkeypatch.setattr(attn_kernels, "fits", lambda t, d: True)
    plain, normed = _family("rotary_alone")[0], _family(
        "head_norms_and_rotary")[0]
    assert lm.attention_pass_fused(plain, T, 1)
    assert not lm.attention_pass_fused(plain, T, 0)
    assert lm.attention_pass_fused(normed, T, 0)
    assert lm.attention_pass_name(plain, T, 0) == "LM_ATTN_PASS_PLAIN"
    cfg, _, _, mats, small, x, pos = _family("no_norms_no_rotary")
    jaxpr = jax.make_jaxpr(lambda x: lm.attention_inputs(
        cfg, 0, mats, lm._zeros_like_f32(mats), small["norm_attn"], x,
        pos))(x)
    assert "pallas_call" not in str(jaxpr)


def test_a_sequence_of_no_whole_block_runs_the_plain_chain(monkeypatch):
    """As if on a TPU, 96 positions: ``attention_inputs`` makes no kernel's
    call (none is interpreted: it would fail to trace for the backend), its
    numbers are the chain's, and a trainer of that length counts PLAIN."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attn_kernels, "INTERPRET", False)
    cfg, rope, _, mats, small, x, pos = _family("rotary_alone")
    assert not lm.attention_pass_fused(cfg, 96)
    jaxpr = jax.make_jaxpr(lambda x: lm.attention_inputs(
        cfg, rope, mats, lm._zeros_like_f32(mats), small["norm_attn"], x,
        pos))(x[:96])
    assert "pallas_call" not in str(jaxpr) and "custom_vjp" in str(jaxpr)
    assert lm.attention_pass_name(cfg, 96) == "LM_ATTN_PASS_PLAIN"
    assert lm.attention_pass_name(cfg, 1024) == "LM_ATTN_PASS_FUSED"
    latent = dataclasses.replace(cfg, attention="mla")
    assert lm.attention_pass_name(latent, 1024) is None

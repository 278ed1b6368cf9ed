"""What stands between a delta layer's projections, its scan and ``W_o`` as
Pallas passes (multiverso_tpu/models/lm/delta_passes.py), interpreted on the
CPU, against ``delta.gates`` and ``delta.output``'s gated norm, the
``jax.numpy`` chain that runs everywhere but on a TPU and is the passes'
definition: each of the four passes and ``jax.vjp`` of the chain at kimi's
and solar's head counts and beta scales over several blocks of tokens, a
zero row, ``attention_vjp`` whole with and without them, which form runs
where, and what counts it. The same for a short convolution alone
(``mv_kda_conv`` and its pull against ``delta.short_conv`` and its
``jax.vjp``: a block's first rows read the tile before it, its last rows'
cotangent the tile after it, the weights' gradient a partial sum a block)."""

import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib.harness import Observations
from benchmark.run import load_module, with_rehearsal
from multiverso_tpu.models.lm import (PSLMTrainer, delta, delta_passes,
                                      model as lm)
from multiverso_tpu.util import dashboard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32 = jnp.float32
T, D, EPS = 3 * delta_passes.TOKENS, 128, 1e-6
#: heads held and beta's scale: ``kimi48b.ps-8k``'s, ``solar250b.ps-8k``'s,
#: and a count that four does not divide (two heads a grid step)
MODELS = {"kimi": (32, 1), "solar": (8, 2), "six_heads": (6, 2)}
ROUNDED = 2e-3      # a result that leaves in bfloat16: ties of its rounding
SUMMED = 2e-6       # a float32 result: the order of a head's sum


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setattr(delta_passes, "INTERPRET", True)


def _relative(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _cfg(heads, scale, d=D):
    return type("Cfg", (), {"kda_heads_held": heads, "kda_head_dim": d,
                            "kda_beta_scale": scale, "eps": EPS})()


def _drawn(heads, seed=0, t=T):
    rng = np.random.default_rng(seed)

    def of(*shape):
        return jnp.asarray(rng.normal(size=shape), F32)

    named = {"a_log": of(heads), "dt_bias": of(heads * D),
             "norm_o": 1 + 0.1 * of(D), "b": of(t, heads)}
    named.update({n: of(t, heads * D) for n in (
        "q", "k", "f", "o", "gate", "dq", "dk", "dg", "dy")})
    named["dbeta"] = of(t, heads)
    return named


def _gates_chain(cfg, t=T):
    def chain(a_log, dt_bias, q, k, f, b):
        q, k, _, g, beta = delta.gates(cfg, a_log, dt_bias, q, k, q, f, b)
        return q.reshape(t, -1), k.reshape(t, -1), g.reshape(t, -1), beta
    return chain


def _norm_chain(cfg, t=T):
    def chain(norm_o, o, gate):
        return lm.rmsnorm(delta.heads_apart(o, cfg.kda_heads_held), norm_o,
                          cfg.eps).reshape(t, -1) * jax.nn.sigmoid(gate)
    return chain


GATES = ("q", "k", "g", "beta")
GATES_PULL = ("d_a_log", "d_dt_bias", "dq", "dk", "df", "db")
NORM_PULL = ("d_norm_o", "do", "d_gate")
CONV = ("conv_y", "conv_dx", "conv_dw")
#: what leaves a pass in bfloat16
IN_BF16 = {"df", "db", "d_gate", "y", "conv_dx"}


def _taps(heads, taps=4, seed=9):
    return jnp.asarray(np.random.default_rng(seed).uniform(
        -0.5, 0.5, (heads * D, taps)), F32)


@pytest.fixture(scope="module")
def both_forms():
    """Every result of the four passes and of the chain, a model: ``{model:
    {result: (the pass's, the chain's)}}``, made once."""
    made = {}

    def of(model):
        if model in made:
            return made[model]
        heads, scale = MODELS[model]
        cfg, x = _cfg(heads, scale), _drawn(heads)
        how = delta_passes.Pass(heads, float(scale), EPS)
        delta_passes.INTERPRET = True
        try:
            ins = tuple(x[n] for n in ("a_log", "dt_bias", "q", "k", "f", "b"))
            cots = tuple(x[n] for n in ("dq", "dk", "dg", "dbeta"))
            got, pull = jax.vjp(lambda *a: delta_passes.gates(how, *a), *ins)
            want, pull_chain = jax.vjp(_gates_chain(cfg), *ins)
            out = dict(zip(GATES, zip(got, want)))
            out.update(zip(GATES_PULL, zip(pull(cots), pull_chain(cots))))
            ins = tuple(x[n] for n in ("norm_o", "o", "gate"))
            got, pull = jax.vjp(
                lambda *a: delta_passes.gated_norm(how, *a), *ins)
            want, pull_chain = jax.vjp(_norm_chain(cfg), *ins)
            out["y"] = (got, want)
            out.update(zip(NORM_PULL, zip(pull(x["dy"]),
                                          pull_chain(x["dy"]))))
            ins = x["q"], _taps(heads)
            got, pull = jax.vjp(lambda *a: delta_passes.conv(how, *a), *ins)
            want, pull_chain = jax.vjp(delta.short_conv, *ins)
            out.update(zip(CONV, zip((got, *pull(x["dq"])),
                                     (want, *pull_chain(x["dq"])))))
        finally:
            delta_passes.INTERPRET = False
        made[model] = out
        return out

    return of


@pytest.mark.parametrize("result",
                         GATES + GATES_PULL + ("y",) + NORM_PULL + CONV)
@pytest.mark.parametrize("model", list(MODELS))
def test_a_pass_s_result_is_the_chain_s(model, result, both_forms):
    """Forward results and every cotangent, the small tensors' partial sums
    (``d a_log``, ``d dt_bias``, ``d norm_o``, a convolution's weights')
    among them, over three blocks of tokens: the float32 ones to the order
    of a head's sum, the bfloat16 ones to their rounding."""
    got, want = both_forms(model)[result]
    assert got.shape == want.shape and got.dtype == want.dtype == F32
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _relative(got, want) < (ROUNDED if result in IN_BF16 else SUMMED)


@pytest.mark.parametrize("model", list(MODELS))
def test_what_leaves_in_bfloat16_is_the_chain_s_rounded(model, both_forms):
    """``mm`` rounds ``W_o``'s input and the projections' cotangents first
    thing: the passes' values ARE bfloat16 values, and nearly all of them
    the chain's own rounding."""
    for result in sorted(IN_BF16):
        got, want = both_forms(model)[result]
        assert bool(jnp.all(got.astype(jnp.bfloat16).astype(F32) == got))
        same = jnp.mean(got == want.astype(jnp.bfloat16).astype(F32))
        assert float(same) > 0.98, result


@pytest.mark.parametrize("result", GATES)
@pytest.mark.parametrize("model", list(MODELS))
def test_the_gates_with_the_convolutions_in_them_are_the_chain_s(model,
                                                                 result):
    """``conv_gates`` on the products' results against ``delta.gates`` of
    ``delta.short_conv``: three blocks of tokens, so a block's first rows
    read the tile before it, and the sequence's first rows zeros."""
    heads, scale = MODELS[model]
    cfg, x = _cfg(heads, scale), _drawn(heads)
    rng = np.random.default_rng(9)
    wq, wk = (jnp.asarray(rng.uniform(-0.5, 0.5, (heads * D, 4)), F32)
              for _ in range(2))
    got = delta_passes.conv_gates(
        delta_passes.Pass(heads, float(scale), EPS), wq, wk,
        *(x[n] for n in ("a_log", "dt_bias", "q", "k", "f", "b")))
    want = _gates_chain(cfg)(
        x["a_log"], x["dt_bias"], delta.short_conv(x["q"], wq),
        delta.short_conv(x["k"], wk), x["f"], x["b"])
    at = GATES.index(result)
    assert got[at].shape == want[at].shape
    assert _relative(got[at], want[at]) < SUMMED
    edges = np.r_[0:8, delta_passes.TOKENS - 4:delta_passes.TOKENS + 8]
    np.testing.assert_allclose(got[at][edges], want[at][edges], rtol=2e-5,
                               atol=1e-6)


@pytest.mark.parametrize("taps", [2, 3, 4])
def test_a_convolution_of_any_taps_reads_the_rows_before_it(taps):
    heads, t = 2, 2 * delta_passes.TOKENS
    cfg, x = _cfg(heads, 1), _drawn(heads, 6, t)
    rng = np.random.default_rng(taps)
    wq, wk = (jnp.asarray(rng.uniform(-0.5, 0.5, (heads * D, taps)), F32)
              for _ in range(2))
    got = delta_passes.conv_gates(
        delta_passes.Pass(heads, 1.0, EPS), wq, wk,
        *(x[n] for n in ("a_log", "dt_bias", "q", "k", "f", "b")))
    want = _gates_chain(cfg, t)(
        x["a_log"], x["dt_bias"], delta.short_conv(x["q"], wq),
        delta.short_conv(x["k"], wk), x["f"], x["b"])
    for mine, theirs in zip(got, want):
        assert _relative(mine, theirs) < SUMMED


def _conv_by_hand(x, w, g):
    """``short_conv``'s pull in float64: ``(dx, gu, the lagged inputs a
    weight)`` for x, g [T, C] and w [C, n]."""
    x, w, g = (np.asarray(a, np.float64) for a in (x, w, g))
    t, n = x.shape[0], w.shape[1]
    padded = np.concatenate([np.zeros((n - 1, x.shape[1])), x])
    lagged = [padded[j:j + t] for j in range(n)]
    u = sum(lag * w[:, j] for j, lag in enumerate(lagged))
    s = 1 / (1 + np.exp(-u))
    gu = g * s * (1 + u * (1 - s))
    ahead = np.concatenate([gu, np.zeros((n - 1, x.shape[1]))])
    dx = sum(ahead[n - 1 - j:n - 1 - j + t] * w[:, j] for j in range(n))
    return dx, gu, lagged


@pytest.mark.parametrize("taps", [2, 3, 4])
@pytest.mark.parametrize("model", ["kimi", "solar"])
def test_a_convolution_alone_reads_the_tiles_beside_its_block(model, taps):
    """Three blocks of tokens at kimi's 32 heads and solar's 8: the rows at
    a block's start read the tile before it (zeros before the sequence's
    first position), the cotangent at a block's end reads ``g silu'(u)`` of
    the tile after it (zeros past the sequence's end), and the weights'
    gradient leaves as one [8, lanes] partial sum a weight a block, each
    position counted in its own block."""
    heads = MODELS[model][0]
    x = _drawn(heads, taps)
    w = _taps(heads, taps, taps)
    how = delta_passes.Pass(heads, 1.0, EPS, taps)
    y = delta_passes.conv(how, x["q"], w)
    dx, dw, sums = delta_passes._conv_pull(how, x["q"], w, x["dq"], True)
    tokens, blocks = delta_passes.TOKENS, T // delta_passes.TOKENS
    assert sums.shape == (blocks, taps, 8, heads * D)
    want_dx, gu, lagged = _conv_by_hand(x["q"], w, x["dq"])
    edges = np.r_[0:8, tokens - 8:tokens + 8, 2 * tokens - 8:2 * tokens + 8,
                  T - 8:T]
    np.testing.assert_allclose(
        y[edges], delta.short_conv(x["q"], w)[edges], rtol=2e-5, atol=1e-6)
    assert bool(jnp.all(dx.astype(jnp.bfloat16).astype(F32) == dx))
    np.testing.assert_allclose(dx[edges], want_dx[edges], rtol=1e-2,
                               atol=1e-2)
    assert _relative(dx, want_dx) < ROUNDED
    by_block = np.stack([np.stack([
        (gu * lagged[j])[b * tokens:(b + 1) * tokens].sum(0)
        for j in range(taps)]) for b in range(blocks)])
    assert _relative(np.asarray(sums, np.float64).sum(2), by_block) < SUMMED
    assert _relative(dw, by_block.sum(0).T) < SUMMED


def test_a_convolution_s_zero_rows_are_the_chain_s_answer_too():
    """A position whose product is zero and one whose cotangent is: silu
    of the rows before it alone, and nothing pulled from it."""
    heads, t = 4, delta_passes.TOKENS
    x, w = _drawn(heads, 2, t), _taps(heads)
    q, g = x["q"].at[7].set(0.0), x["dq"].at[t - 1].set(0.0)
    how = delta_passes.Pass(heads, 1.0, EPS)
    got, pull = jax.vjp(lambda *a: delta_passes.conv(how, *a), q, w)
    want, pull_chain = jax.vjp(delta.short_conv, q, w)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _relative(got, want) < SUMMED
    for mine, theirs, limit in zip(pull(g), pull_chain(g),
                                   (ROUNDED, SUMMED)):
        assert bool(jnp.all(jnp.isfinite(mine)))
        assert _relative(mine, theirs) < limit


@pytest.mark.parametrize("scale", [1, 2])
def test_beta_is_the_scale_times_the_sigmoid(scale):
    heads = 4
    x = _drawn(heads, 1, delta_passes.TOKENS)
    how = delta_passes.Pass(heads, float(scale), EPS)
    beta = delta_passes.gates(how, *(x[n] for n in (
        "a_log", "dt_bias", "q", "k", "f", "b")))[3]
    np.testing.assert_allclose(beta, scale / (1 + np.exp(-np.asarray(
        x["b"], np.float64))), rtol=1e-6)
    assert (float(beta.max()) > 1) == (scale == 2)


def test_a_zero_row_is_the_chain_s_answer_too():
    """|q| = 0: the chain divides by it and so does the pass: that head's
    row is not finite in either, forward and pulled, and every other row
    and head is as it was."""
    heads, t = 4, delta_passes.TOKENS
    cfg, x = _cfg(heads, 1), _drawn(heads, 2, t)
    how = delta_passes.Pass(heads, 1.0, EPS)
    x["q"] = x["q"].at[7, D:2 * D].set(0.0)     # head 1 of position 7
    ins = tuple(x[n] for n in ("a_log", "dt_bias", "q", "k", "f", "b"))
    cots = tuple(x[n][:t] for n in ("dq", "dk", "dg", "dbeta"))
    got, pull = jax.vjp(lambda *a: delta_passes.gates(how, *a), *ins)
    want, pull_chain = jax.vjp(_gates_chain(cfg, t), *ins)
    bad = np.zeros((t, heads * D), bool)
    bad[7, D:2 * D] = True
    for mine, theirs in ((got[0], want[0]), (pull(cots)[2],
                                             pull_chain(cots)[2])):
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        assert not np.isfinite(mine[bad]).any()
        assert not np.isfinite(theirs[bad]).any()
        assert np.isfinite(mine[~bad]).all()
        assert _relative(mine[~bad], theirs[~bad]) < SUMMED


def test_the_kernels_hold_no_derived_transpose():
    """The pulls are written out: no ``jax.vjp`` in a kernel's body (PR 55:
    a derived transpose in a kernel cost seconds of traces on the chip
    machine's host), and the rules keep their inputs alone."""
    import inspect
    source = inspect.getsource(delta_passes)
    assert "jax.vjp" not in source.split('"""', 2)[2]
    heads = 4
    x = _drawn(heads, 3, delta_passes.TOKENS)
    how = delta_passes.Pass(heads, 1.0, EPS)
    ins = tuple(x[n] for n in ("a_log", "dt_bias", "q", "k", "f", "b"))
    _, kept = delta_passes._gates_fwd(how, *ins)
    assert all(a is b for a, b in zip(kept, ins))
    ins = tuple(x[n] for n in ("norm_o", "o", "gate"))
    _, kept = delta_passes._gated_norm_fwd(how, *ins)
    assert all(a is b for a, b in zip(kept, ins))
    ins = x["q"], _taps(heads)
    _, kept = delta_passes._conv_fwd(how, *ins)
    assert all(a is b for a, b in zip(kept, ins))


# -- a sublayer whole ----------------------------------------------------------------

CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "mla_use_nope": True, "rope_scaling": None, "rope_theta": 10000,
    "linear_attn_config": {"full_attn_layers": [3, 7], "head_dim": D,
                           "kda_layers": [1, 2, 4, 5, 6], "num_heads": 2,
                           "short_conv_kernel_size": 4},
    "first_k_dense_replace": 1, "hidden_act": "silu",
    "intermediate_size": 48, "moe_intermediate_size": 16,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_expert_group": 1,
    "topk_group": 1, "use_grouped_topk": True, "num_experts": 4,
    "router_outputs": 8, "first_expert_held": 2, "num_experts_per_token": 2,
    "num_shared_experts": 1, "routed_scaling_factor": 2.446,
    "router_bias_rate": 0.001, "num_nextn_predict_layers": 0,
    "num_hidden_layers": 4, "vocab_size": 97, "rms_norm_eps": 1e-5,
    "loss_block": 16}
CFG = lm.LMConfig.from_dict(CONFIG)
SUBLAYER_T = delta_passes.TOKENS


def _sublayer(cfg, seed=5):
    rng = np.random.default_rng(seed)
    shapes = delta.shapes(cfg)
    mats = {n: jnp.asarray(rng.normal(0, shapes[n][0] ** -0.5, shapes[n]),
                           jnp.bfloat16) for n in delta.MATRICES}
    small = {n: jnp.asarray(rng.uniform(-0.5, 0.5, s), F32)
             for n, s in shapes.items() if n not in mats}
    small["a_log"] = jnp.asarray(np.log(rng.uniform(1, 16, shapes["a_log"])),
                                 F32)
    small["norm_o"] = 1 + 0.1 * small["norm_o"]
    small["norm_attn"] = jnp.asarray(1 + 0.1 * rng.normal(size=cfg.hidden),
                                     F32)
    x, d = (jnp.asarray(rng.normal(size=(SUBLAYER_T, cfg.hidden)), F32)
            for _ in range(2))

    def run(mats, small, x, d):
        out, counts, pull = delta.attention_vjp(
            cfg, mats, lm._zeros_like_f32(mats), small, x)
        return out, counts, pull(d)

    return jax.jit(run)(mats, small, x, d)


@pytest.fixture(scope="module")
def sublayers():
    """``attention_vjp`` and its pull with the passes and with the chain, a
    beta scale: ``{scale: (with, without)}``."""
    out = {}
    for scale in (1, 2):
        cfg = dataclasses.replace(CFG, kda_beta_scale=scale)
        kept = delta.passes_fused, delta_passes.INTERPRET
        without = _sublayer(cfg)
        delta.passes_fused, delta_passes.INTERPRET = (lambda cfg, t: True,
                                                      True)
        try:
            out[scale] = (_sublayer(cfg), without)
        finally:
            delta.passes_fused, delta_passes.INTERPRET = kept
    return out


def _flat(tree):
    return dict(jax.tree_util.tree_flatten_with_path(tree)[0])


LEAVES = [jax.tree_util.keystr(path) for path in _flat((
    0, {"decay_deep": 0}, (0, dict.fromkeys(delta.MATRICES, 0),
                           dict.fromkeys(delta.CONVS + (
                               "a_log", "dt_bias", "norm_attn", "norm_o"),
                               0))))]


@pytest.mark.parametrize("leaf", LEAVES)
@pytest.mark.parametrize("scale", [1, 2])
def test_a_sublayer_through_the_passes_is_the_chain_s(scale, leaf, sublayers):
    """``F(x)``, the deep count, ``dx`` and every gradient of
    ``attention_vjp`` with the passes in it against the chain's, within the
    tolerance the softmax layers' pass is held to
    (tests/test_lm_attn_pass.py): ties of the bfloat16 roundings."""
    got, want = ({jax.tree_util.keystr(p): v for p, v in _flat(side).items()}
                 for side in sublayers[scale])
    if "decay_deep" in leaf:
        assert int(got[leaf]) == int(want[leaf])
        return
    assert got[leaf].shape == want[leaf].shape
    assert _relative(got[leaf], want[leaf]) < ROUNDED


def test_beta_over_one_is_counted_from_the_pass_s_beta(sublayers):
    (got, _), (_, _) = sublayers[2], sublayers[1]
    with_passes, without = sublayers[2]
    assert int(with_passes[1]["beta_over_one"]) \
        == int(without[1]["beta_over_one"]) > 0
    assert "beta_over_one" not in sublayers[1][0][1]


def test_the_sublayer_s_passes_are_the_kernels(monkeypatch):
    """Forward: the gates with q's and k's convolutions in them, v's
    convolution, and the gated norm; with the pull the same once more (for
    the scan's transpose), the three convolutions and behind them the gates
    (whose forward nothing reads), their pulls and the norm's."""
    monkeypatch.setattr(delta, "passes_fused", lambda cfg, t: True)
    shapes = delta.shapes(CFG)
    mats = {n: jnp.zeros(shapes[n], jnp.bfloat16) for n in delta.MATRICES}
    small = {n: jnp.ones(s, F32) for n, s in shapes.items() if n not in mats}
    small["norm_attn"] = jnp.ones(CFG.hidden, F32)
    x = jnp.ones((SUBLAYER_T, CFG.hidden), F32)

    def forward(x):
        return delta.attention_vjp(CFG, mats, lm._zeros_like_f32(mats),
                                   small, x)[0]

    def pulled(x):
        return delta.attention_vjp(CFG, mats, lm._zeros_like_f32(mats),
                                   small, x)[2](x)

    def calls(fn):
        """The jitted callers' calls (a jaxpr prints a shared body once)."""
        text = str(jax.make_jaxpr(fn)(x))
        return [len(re.findall(rf"\bname={name}\b", text)) for name in (
            "_gates", "_gates_pull", "_gated_norm", "_gated_norm_pull",
            "_conv", "_conv_pull")]

    assert calls(forward) == [1, 0, 1, 0, 1, 0]
    assert calls(pulled) == [3, 1, 1, 1, 5, 3]


# -- which form runs where, and the counters --------------------------------------------

@pytest.mark.parametrize("t, d, fused", [
    (8192, 128, True), (512, 128, True), (8192 + 256, 128, False),
    (96, 128, False), (32, 8, False), (8192, 64, False), (8192, 256, False)])
def test_the_passes_take_whole_blocks_and_heads_of_one_tile(t, d, fused,
                                                            monkeypatch):
    cfg = _cfg(4, 1, d)
    assert not delta.passes_fused(cfg, t)       # here: no TPU
    assert delta.pass_counter(cfg, t) == "LM_KDA_PASS_PLAIN"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta.passes_fused(cfg, t) is fused
    assert delta.pass_counter(cfg, t) == (
        "LM_KDA_PASS_FUSED" if fused else "LM_KDA_PASS_PLAIN")


@pytest.mark.parametrize("name", ["kimi-linear-48b-a3b-l5",
                                  "solar-open2-250b-a15b-l4"])
def test_both_cells_take_the_passes_and_their_rehearsals_the_chain(
        name, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for rehearse, fused in ((False, True), (True, False)):
        with open(os.path.join(ROOT, "benchmark", "configs",
                               f"{name}.json")) as f:
            cfg = lm.LMConfig.from_dict(with_rehearsal(json.load(f),
                                                       rehearse))
        with open(os.path.join(ROOT, "benchmark", "traffic",
                               "lm-ps-step-8k.json")) as f:
            length = with_rehearsal(json.load(f), rehearse)["seq_len"]
        assert (length == 8192) is fused
        assert delta.passes_fused(cfg, length) is fused
        assert delta.pass_counter(cfg, length) == (
            "LM_KDA_PASS_FUSED" if fused else "LM_KDA_PASS_PLAIN")


@pytest.mark.parametrize("which", ["short_conv", "gates", "output"])
def test_a_replaced_definition_takes_the_chain_that_calls_it(which,
                                                             monkeypatch):
    """The checks' controls put their own ``short_conv``, ``gates`` or
    ``output`` in the module (benchmark/tools/lm_kda_controls.py): the
    passes would not call it, so the chain runs, and is counted."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _cfg(4, 1)
    assert delta.passes_fused(cfg, 8192)
    exact = getattr(delta, which)
    monkeypatch.setattr(delta, which, lambda *a: exact(*a))
    assert not delta.passes_fused(cfg, 8192)
    assert delta.pass_counter(cfg, 8192) == "LM_KDA_PASS_PLAIN"


def test_off_the_chip_the_chain_runs_and_no_kernel_is_traced(monkeypatch):
    def never(*args):
        raise AssertionError("a pass was taken on the CPU")

    for name in ("gates", "conv_gates", "gated_norm", "conv"):
        monkeypatch.setattr(delta_passes, name, never)
    heads = CFG.kda_heads_held
    x = _drawn(heads, 4, SUBLAYER_T)
    flat = delta.gates_flat(CFG, x["a_log"], x["dt_bias"], x["q"], x["k"],
                            x["o"], x["f"], x["b"])
    assert [a.shape for a in flat] == 4 * [(SUBLAYER_T, heads * D)] + [
        (SUBLAYER_T, heads)]
    wide = delta.gates(CFG, x["a_log"], x["dt_bias"], x["q"], x["k"],
                       x["o"], x["f"], x["b"])
    for mine, theirs in zip(flat, wide):
        assert np.array_equal(mine, np.asarray(theirs).reshape(mine.shape))
    convs = tuple(jnp.full((heads * D, 4), 0.25, F32) for _ in range(3))
    through = delta.conv_gates_flat(CFG, convs, x["a_log"], x["dt_bias"],
                                    x["q"], x["k"], x["o"], x["f"], x["b"])
    want = delta.gates_flat(CFG, x["a_log"], x["dt_bias"], *(
        delta.short_conv(x[n], w) for n, w in zip("qko", convs)),
        x["f"], x["b"])
    for mine, theirs in zip(through, want):
        assert np.array_equal(mine, theirs)
    assert np.array_equal(delta.short_conv_flat(CFG, x["q"], convs[0]),
                          delta.short_conv(x["q"], convs[0]))
    wo = jnp.eye(heads * D, dtype=jnp.bfloat16)
    sinks = {"wo": jnp.zeros(wo.shape, F32)}
    got = delta.output_flat(CFG, {"wo": wo}, sinks, x["norm_o"], x["o"],
                            x["gate"])
    want = delta.output(CFG, {"wo": wo}, sinks, x["norm_o"],
                        x["o"].reshape(SUBLAYER_T, heads, D), x["gate"])
    assert np.array_equal(got, want)


@pytest.mark.parametrize("why", ["short_conv_replaced", "a_part_block",
                                 "heads_of_half_a_tile"])
def test_a_convolution_that_does_not_fit_keeps_the_chain(why, monkeypatch):
    """On a TPU too: a control's own ``short_conv`` is the one called, and
    a sequence that is no whole number of blocks or a head that is no one
    tile never reaches the kernel."""
    def never(*args):
        raise AssertionError("the kernel was taken")

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(delta_passes, "conv", never)
    t, d = {"short_conv_replaced": (512, D), "a_part_block": (96, D),
            "heads_of_half_a_tile": (512, 64)}[why]
    cfg = _cfg(2, 1, d)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(t, 2 * d)), F32)
    w = _taps(2)[:2 * d]
    want = delta.short_conv(x, w)
    if why == "short_conv_replaced":
        exact = delta.short_conv
        monkeypatch.setattr(delta, "short_conv", lambda x, w: 2 * exact(x, w))
        want = 2 * want
    assert np.array_equal(delta.short_conv_flat(cfg, x, w), want)


def _counted():
    monitors = dashboard.metrics_snapshot(max_samples=0)["monitors"]
    return [monitors.get(n, {"count": 0})["count"]
            for n in ("LM_KDA_PASS_FUSED", "LM_KDA_PASS_PLAIN")]


@pytest.mark.parametrize("on_chip, t, fused, plain", [
    (True, 8192, 6, 0), (True, 96, 0, 6), (False, 8192, 0, 6)])
def test_the_trainer_counts_one_a_delta_layer_a_sequence(on_chip, t, fused,
                                                         plain, monkeypatch):
    """Three delta layers of four, two sequences: six, under the name the
    shape test gives."""
    if on_chip:
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    trainer = PSLMTrainer.__new__(PSLMTrainer)
    trainer.cfg = dataclasses.replace(CFG, kda_head_dim=D)
    trainer.T = t
    trainer._sparse, trainer._experts_cap = [0, 1, 1, 1], 1 << 30
    trainer._attn_pass, trainer._heads = [None] * 4, (1, 1)
    trainer._attn_blocks = []
    before = _counted()
    trainer._count_stats(([np.zeros((2, 2 + 8 + 1), int)] * 4, 5, 7))
    assert [a - b for a, b in zip(_counted(), before)] == [fused, plain]


def test_the_counters_are_described():
    for name in ("LM_KDA_PASS_FUSED", "LM_KDA_PASS_PLAIN"):
        assert "delta_passes" in dashboard.METRIC_NAMES[name] \
            or "chain" in dashboard.METRIC_NAMES[name]


# -- the reader -----------------------------------------------------------------------

NAME = "trainer.kda_pass_fused_share.lm"


class _Window:
    def __init__(self, counters):
        self.counters, self.rounds, self.seconds = counters, 15, 20.0


@pytest.mark.parametrize("fused, plain, want", [
    (120, None, 100.0), (90, 30, 75.0), (None, 120, 0.0), (0, 0, None),
    (None, None, None)])
def test_the_reader_is_the_fused_share(fused, plain, want):
    """A counter exists from its first count; the parent's program has
    neither, and reads nothing without an exception."""
    counters = {"LM_STEP": {"count": 15, "ms": 20000.0},
                "LM_KDA_SCAN_KERNEL": {"count": 120, "ms": 0.0}}
    for name, n in (("LM_KDA_PASS_FUSED", fused),
                    ("LM_KDA_PASS_PLAIN", plain)):
        if n is not None:
            counters[name] = {"count": n, "ms": 0.0}
    got = load_module("metrics", NAME).read(
        Observations(window=_Window(counters)))
    assert got is None if want is None else got == pytest.approx(want)


def test_the_reader_s_entry_is_the_benchmark_s_last():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by NAME: the list grows at its end (PR 65 appended to it)
    assert next(m for m in bench["per_layer"] if m["name"] == NAME) == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "trainer",
        "moves": "words_per_s",
        "workloads": ["kimi48b.ps-8k", "solar250b.ps-8k"]}


# -- the tool ------------------------------------------------------------------------

def test_the_bench_s_delta_line_holds_every_pass_to_the_chain(monkeypatch):
    """tools/attn_pass_bench.py's line a delta layer, its timing left out
    (a time comes from the chip alone): the four passes' results beside the
    chain's on the tool's own drawn arrays, the bytes each moves."""
    import sys
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    import attn_pass_bench as bench
    monkeypatch.setattr(bench, "_ms_looped", lambda *a, **k: 1.0)
    cfg = dataclasses.replace(CFG, kda_beta_scale=2)
    t = delta_passes.TOKENS
    out = bench._delta_passes_alone(cfg, t, np.random.default_rng(0), 819e9)
    wide = 4 * t * cfg.kda_heads_held * D
    assert {n: line["bytes"] / wide for n, line in out.items()} == {
        "conv_gates": 6, "gates": 6, "gates_pull": 8.5, "norm": 2.5,
        "norm_pull": 4.5, "conv": 2, "conv_pull": 2.5}
    assert [len(out[n]["relative"]) for n in out] == [4, 4, 6, 1, 3, 1, 2]
    for line in out.values():
        assert max(line["relative"]) < ROUNDED
        assert line["bound_share"] == pytest.approx(
            line["bytes"] / 819e9 / 1e-3)

"""The language model of multiverso_tpu/models/lm against the plain
reference (benchmark/reference/lm_step.py) at small widths on the CPU:
loss and every gradient of a four-layer stack with both kinds of layer,
the window mask, and the share test."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu
from benchmark.reference import lm_step as ref
from multiverso_tpu.models.lm import PSLMTrainer, model as lm

CONFIG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "router_outputs": 8, "moe_num_active_primary_experts": 3,
    "moe_ffn_hidden_size": 32, "moe_num_primary_experts": 4,
    "vocab_size": 53, "num_hidden_layers": 4, "rope_layout": [0, 1, 1, 1],
    "sliding_window_layout": [0, 1, 1, 1], "sliding_window_size": 8,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-6, "loss_block": 16}
T, B = 32, 2        # four windows long, so the window mask bites
# Relative L2 error of a gradient at these widths, bfloat16 products
# against float32: matrices up to 1.0e-2; the gate matrices up to 8.1e-2
# (a gate value within rounding of zero flips relu's derivative for its
# element: a whole term wrong, not a rounded one) and the norms and
# routers, sums of nearly cancelling terms that the flips reach too, up to
# 5.5e-2. Float8-rounded expert inputs read 2.7e-2 to 4.5e-2 on the up and
# down matrices and 1.1e-1 to 1.5e-1 on the gates.
MATRIX_LIMIT, GATE_LIMIT, SMALL_LIMIT = 2e-2, 1e-1, 1e-1


def _params(cfg, seed, scale=0.08):
    rng = np.random.default_rng(seed)
    draw = lambda shape: jnp.asarray(       # noqa: E731
        rng.normal(0, scale, shape), jnp.float32)
    layers = []
    for _ in range(cfg.n_layers):
        layer = {n: draw(s) for n, s in cfg.layer_shapes().items()}
        layer["norm_attn"] = 1 + layer["norm_attn"]
        layer["norm_ffn"] = 1 + layer["norm_ffn"]
        layers.append(layer)
    return {"embedding": draw((cfg.vocab, cfg.hidden)) * 10, "layers": layers,
            "final_norm": 1 + draw((cfg.hidden,)),
            "head": draw((cfg.vocab, cfg.hidden))}


def program_step(cfg, params, tokens):
    """The trainer's step without its tables: the same functions in the
    same order. Returns the loss, every gradient and each layer's ids."""
    ids, targets = tokens[:, :-1], tokens[:, 1:].reshape(-1)
    x = params["embedding"][ids]
    kinds = [(bool(r), cfg.window if w else 0)
             for r, w in zip(cfg.rope_layout, cfg.window_layout)]
    kept, chosen = [], []
    for layer, kind in zip(params["layers"], kinds):
        mats = {n: layer[n].astype(jnp.bfloat16) for n in lm.LAYER_MATRICES}
        small = {n: layer[n] for n in lm.LAYER_SMALL}
        kept.append((mats, small, x))
        out = [lm.layer_forward(cfg, *kind, mats, small, x[b])
               for b in range(x.shape[0])]
        x = jnp.stack([o[0] for o in out])
        chosen.append(jnp.stack([o[2] for o in out]))
    loss, dx, d_head, d_norm = lm.head_loss_and_grads(
        cfg, params["head"].astype(jnp.bfloat16), params["final_norm"],
        x.reshape(-1, cfg.hidden), targets)
    dx = dx.reshape(x.shape)
    grads = {"head": d_head, "final_norm": d_norm, "layers": [None] * len(kept)}
    for i in reversed(range(len(kept))):
        mats, small, x_in = kept[i]
        out = [lm.layer_grads(cfg, *kinds[i], mats, small, x_in[b], dx[b])
               for b in range(x_in.shape[0])]
        dx = jnp.stack([o[0] for o in out])
        grads["layers"][i] = jax.tree_util.tree_map(
            lambda *g: sum(g), *[{**o[1], **o[2]} for o in out])
    grads["embedding"] = jnp.zeros_like(params["embedding"]).at[ids].add(dx)
    return loss, grads, chosen


def _relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.fixture(scope="module")
def both():
    cfg = lm.LMConfig.from_dict(CONFIG)
    params = _params(cfg, 0)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (B, T + 1)), jnp.int32)
    loss, grads, chosen = program_step(cfg, params, tokens)
    c = ref.sizes(CONFIG)
    with ref.PRECISION:
        want_loss, want = jax.value_and_grad(
            lambda p: ref.step_loss(c, p, tokens, chosen))(params)
        own = [ref.routing(c, params["layers"][0]["router"],
                           params["embedding"][tokens[b, :-1]])[0]
               for b in range(B)]
    return cfg, loss, grads, want_loss, want, chosen, own


def test_loss_matches_the_reference(both):
    _, loss, _, want_loss, *_ = both
    assert abs(float(loss) - float(want_loss)) < 2e-3 * float(want_loss)


def _names(cfg):
    return (["embedding", "final_norm", "head"]
            + [f"layers.{i}.{n}" for i in range(cfg.n_layers)
               for n in cfg.layer_shapes()])


@pytest.mark.parametrize("name", _names(lm.LMConfig.from_dict(CONFIG)))
def test_gradient_matches_the_reference(both, name):
    """bfloat16 products against float32: 2**-9 an input, a few layers
    deep. A norm's or a router's gradient is a sum of terms that nearly
    cancel, so it keeps more of the rounding than a matrix's."""
    _, _, grads, _, want, *_ = both
    for part in name.split("."):
        key = int(part) if part.isdigit() else part
        grads, want = grads[key], want[key]
    assert grads.shape == want.shape and grads.dtype == jnp.float32
    limit = SMALL_LIMIT if "norm" in name or "router" in name else \
        GATE_LIMIT if "w_gate" in name else MATRIX_LIMIT
    assert _relative(grads, want) < limit, name


def test_layer_zero_chooses_the_reference_s_own_experts(both):
    """Its input is the embedding row itself, bit for bit: no rounding
    yet to flip a near-tie."""
    *_, chosen, own = both
    for b in range(B):
        assert np.array_equal(np.sort(np.asarray(chosen[0][b]), -1),
                              np.sort(np.asarray(own[b]), -1))


@pytest.mark.parametrize("attend", [lm.blockwise_attention,
                                    "reference"])
def test_a_window_as_long_as_the_sequence_is_the_causal_mask(attend):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(2, 2, 24, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 24, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 24, 16)), jnp.bfloat16)
    if attend == "reference":
        def attend(q, k, v, window, block):
            q = q.reshape(4, 24, 16).transpose(1, 0, 2).astype(jnp.float32)
            k, v = (a.transpose(1, 0, 2).astype(jnp.float32) for a in (k, v))
            return ref.attention(q, k, v, window, block)
    causal = attend(q, k, v, 0, 8)
    assert np.array_equal(np.asarray(attend(q, k, v, 24, 8), np.float32),
                          np.asarray(causal, np.float32))
    assert np.array_equal(np.asarray(attend(q, k, v, 100, 8), np.float32),
                          np.asarray(causal, np.float32))
    assert not np.array_equal(np.asarray(attend(q, k, v, 8, 8), np.float32),
                              np.asarray(causal, np.float32))


def test_blockwise_attention_matches_the_reference_under_a_window():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(2, 2, 40, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, 40, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, 40, 16)), jnp.bfloat16)
    got = lm.blockwise_attention(q / 4, k, v, 8, block=8)
    with ref.PRECISION:
        want = ref.attention(
            q.reshape(4, 40, 16).transpose(1, 0, 2).astype(jnp.float32),
            k.transpose(1, 0, 2).astype(jnp.float32),
            v.transpose(1, 0, 2).astype(jnp.float32), 8, block=8)
    got = got.reshape(4, 40, 16).transpose(1, 0, 2).astype(jnp.float32)
    assert _relative(got, want) < 1e-2


def test_the_four_shares_add_up_to_the_uncut_layer():
    """Each share holds two of the eight experts, routes over all eight
    and normalises over all three chosen; attention, which every chip
    computes alike, is counted once."""
    cfg = lm.LMConfig.from_dict(CONFIG)
    rng = np.random.default_rng(4)
    draw = lambda shape: jnp.asarray(       # noqa: E731
        rng.normal(0, 0.08, shape), jnp.float32)
    whole = dict(CONFIG, moe_num_primary_experts=8)
    uncut = {n: draw(s) for n, s in
             lm.LMConfig.from_dict(whole).layer_shapes().items()}
    uncut["norm_attn"], uncut["norm_ffn"] = jnp.ones(64), jnp.ones(64) * 1.1
    x = draw((T, 64)) * 10
    with ref.PRECISION:
        want = ref.layer(ref.sizes(whole), True, 8, uncut, x)
    small = {n: uncut[n] for n in lm.LAYER_SMALL}
    attn = {n: uncut[n].astype(jnp.bfloat16)
            for n in ("wq", "wk", "wv", "wo")}
    a = lm.attention_block(cfg, True, 8, attn,
                           {n: None for n in attn}, uncut["norm_attn"], x)
    parts = 0
    for first in range(0, 8, 2):
        share = lm.LMConfig.from_dict(
            dict(CONFIG, moe_num_primary_experts=2, first_expert_held=first))
        h, w = cfg.hidden, cfg.expert_width
        mats = dict(attn)
        for name, rows in (("w_gate", h), ("w_up", h), ("w_down", w)):
            mats[name] = uncut[name][first * rows:(first + 2) * rows].astype(
                jnp.bfloat16)
        y, stats, _ = lm.layer_forward(share, True, 8, mats, small, x)
        parts = parts + (y - a)
        assert int(stats[0]) <= T * 3
    assert _relative(a + parts, want) < 1e-2


@pytest.mark.parametrize("total", [700, 1024, 0])
def test_the_tpu_s_grouped_kernel_equals_the_ragged_product(total):
    """The Pallas kernel a TPU takes (interpreted here) against XLA's
    ragged product, forward, transposed and as the weight gradient, with
    rows past the groups' sum: the kernel visits no tile for them and
    leaves them unwritten (the caller masks them)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(1024, 256)), jnp.bfloat16)
    g = jnp.asarray(rng.normal(size=(1024, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(4, 256, 128)), jnp.bfloat16)
    sizes = jnp.asarray(
        [total // 2, 0, total // 4, total - total // 2 - total // 4],
        jnp.int32)
    live = (np.arange(1024) < total)[:, None]
    for args, kw in (((x, w, sizes), {}),
                     ((g, w, sizes), {"transpose_w": True})):
        got = lm.grouped_product(*args, kernel=True, interpret=True, **kw)
        want = lm.grouped_product(*args, kernel=False, **kw)
        np.testing.assert_allclose(np.where(live, got, 0),
                                   np.where(live, want, 0), rtol=1e-5,
                                   atol=1e-4)
    xm, gm = jnp.where(live, x, 0), jnp.where(live, g, 0)
    np.testing.assert_allclose(
        lm.grouped_outer(xm, gm, sizes, kernel=True, interpret=True),
        lm.grouped_outer(xm, gm, sizes, kernel=False), rtol=1e-5, atol=1e-3)


def test_the_kernel_is_taken_for_whole_tiles_on_a_tpu_only(monkeypatch):
    assert not lm._use_gmm(49152, 2560, 768)            # this is a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert lm._use_gmm(49152, 2560, 768) and lm._use_gmm(49152, 768, 2560)
    assert not lm._use_gmm(96, 2560, 768) and not lm._use_gmm(512, 64, 32)
    assert (lm._tile(2560), lm._tile(768), lm._tile(64)) == (640, 768, 0)


# -- the trainer's entry points (the three-step run is test_lm_trainer.py) --

T_CLI, B_CLI = 16, 2


def test_the_trainer_refuses_another_rule():
    multiverso_tpu.init(["-updater_type=sgd"])
    try:
        with pytest.raises(Exception, match="adam"):
            PSLMTrainer(lm.LMConfig.from_dict(CONFIG), T_CLI, B_CLI)
    finally:
        multiverso_tpu.shutdown()


def test_the_cli_trains_a_small_configuration(tmp_path):
    import json
    from multiverso_tpu.models.lm import main
    path = tmp_path / "config.json"
    path.write_text(json.dumps(dict(CONFIG, vocab_size=41)))
    trainer = main.run([f"-lm_config={path}", "-lm_steps=2",
                        "-lm_seq_len=16", "-lm_sequences=2"])
    assert trainer.steps == 2 and trainer.cfg.vocab == 41
    assert np.isfinite(float(trainer.last_loss))
    assert trainer.option.learning_rate == pytest.approx(3e-4 * 2 / 2000)


def test_the_learning_rate_rises_over_the_warmup_and_stays():
    """Each step's Adds carry ``lr * min(1, step / warmup_steps)``."""
    multiverso_tpu.init(["-updater_type=adam"])
    try:
        cfg = lm.LMConfig.from_dict(CONFIG)
        trainer = PSLMTrainer(cfg, T_CLI, B_CLI, lr=1e-3, warmup_steps=2)
        sent, add = [], trainer.head.add_async
        trainer.head.add_async = lambda delta, option: (
            sent.append(option.learning_rate), add(delta, option))[1]
        tokens = jnp.zeros((B_CLI, T_CLI + 1), jnp.int32)
        for _ in range(3):
            trainer.step(tokens)
        trainer.close()
        assert sent == pytest.approx([5e-4, 1e-3, 1e-3])
    finally:
        multiverso_tpu.shutdown()


def test_float8_rounded_expert_inputs_fail_the_gradient_limit(monkeypatch):
    """The control the chip's check rests on: the next precision below
    bfloat16 in the experts' products is outside the limit that bfloat16
    is inside of."""
    cfg = lm.LMConfig.from_dict(CONFIG)
    params = _params(cfg, 0)
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (B, T + 1)), jnp.int32)
    exact = lm.grouped_mm.fun

    def rounded(x, w, sink, group_sizes):
        return lm.grouped_product(      # e4m3's bits; no cast pair, which
            jax.lax.reduce_precision(   # a compiler may remove
                x.astype(jnp.bfloat16), 4, 3), w, group_sizes)

    monkeypatch.setattr(lm.grouped_mm, "fun", rounded)
    _, grads, chosen = program_step(cfg, params, tokens)
    monkeypatch.setattr(lm.grouped_mm, "fun", exact)
    with ref.PRECISION:
        want = jax.grad(lambda p: ref.step_loss(
            ref.sizes(CONFIG), p, tokens, chosen))(params)
    def worst(*names):
        return max(_relative(grads["layers"][i][n], want["layers"][i][n])
                   for i in range(cfg.n_layers) for n in names)

    assert worst("w_up", "w_down") > MATRIX_LIMIT
    assert worst("w_gate") > GATE_LIMIT

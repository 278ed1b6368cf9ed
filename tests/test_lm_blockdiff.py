"""Block diffusion through multiverso_tpu/models/lm against the plain
reference (benchmark/reference/lm_bd_step.py) at small widths on the CPU:
the mask in its three forms pair by pair, the noise, the layer with its
q and k norms, silu experts and late router, loss and every gradient of a
two-layer stack, the eight shares, and two steps through the server's
tables and Adam."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_bd_step as ref
from multiverso_tpu.models.lm import (LMConfig, PSLMTrainer, model as lm,
                                      zipf_tokens)
from multiverso_tpu.util import dashboard
from tests.test_lm_trainer import _as_reference, _flat, _state

CONFIG = {
    "model_type": "sdar_moe", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "hidden_act": "silu",
    "router_outputs": 16, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "norm_topk_prob": True, "vocab_size": 53,
    "num_hidden_layers": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "loss_block": 16,
    "objective": {"kind": "block_diffusion", "block_length": 4,
                  "t_min": 0.001}}
L, B = 32, 2
# Relative L2 error of a gradient at these widths, bfloat16 products
# against float32, read over six seeds of weights, tokens and noise:
# matrices up to 1.2e-2 (the gates 9.5e-3: silu has no derivative that
# flips), norms and routers up to 1.4e-2, the loss 1.5e-4.
MATRIX_LIMIT, SMALL_LIMIT = 3e-2, 5e-2


def _relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# -- the mask: predicate, the kernel's mask object, the skipped tiles ---------

SIZES = [(8, 4), (12, 3), (32, 4), (64, 8), (512, 4), (768, 4)]


@pytest.mark.parametrize("half, block", SIZES)
def test_the_predicate_is_the_reference_s_pair_by_pair(half, block):
    i = np.arange(2 * half)[:, None]
    j = np.arange(2 * half)[None, :]
    mask = lm.Mask.blockdiff(half, block)
    got = np.asarray(mask.visible(i, j))
    want = np.asarray(ref.sees(jnp.asarray(i), jnp.asarray(j), half, block))
    assert np.array_equal(got, want)
    # L^2 + L b of the 4 L^2 pairs
    assert got.sum() == half * half + half * block
    assert np.array_equal(np.asarray(lm.visible(i, j, mask)), got)
    assert np.array_equal(mask.positions(2 * half) % half,
                          np.arange(2 * half) % half)
    assert mask.scope == "mv.lm.attn.blockdiff"


@pytest.mark.parametrize("half, block", SIZES)
def test_the_kernel_s_mask_object_is_the_predicate(half, block):
    """The splash kernel's mask object: its dense form a tile at a time
    (how the library finds the tiles to skip) and its function on index
    arrays (what the kernel computes) are the predicate."""
    mask = lm.Mask.blockdiff(half, block)
    one = lm._block_diffusion_mask(mask)
    t = 2 * half
    want = np.asarray(mask.visible(np.arange(t)[:, None],
                                   np.arange(t)[None, :]))
    assert one.shape == (t, t)
    assert np.array_equal(one[:, :], want)
    tile = min(128, t)
    for lo in range(0, t, tile):
        assert np.array_equal(one[lo:lo + tile, 0:t], want[lo:lo + tile])
    got = one.mask_function(jnp.arange(t, dtype=jnp.int32)[:, None],
                            jnp.arange(t, dtype=jnp.int32)[None, :])
    assert got.dtype == jnp.bool_ and np.array_equal(np.asarray(got), want)
    again = lm._block_diffusion_mask(lm.Mask.blockdiff(half, block))
    assert one == again and hash(one) == hash(again)
    assert one != lm._block_diffusion_mask(lm.Mask.blockdiff(half, 2 * block))


@pytest.mark.parametrize("half, block", SIZES)
@pytest.mark.parametrize("rows", [4, 128, 512])
def test_blockwise_attention_skips_only_masked_keys(half, block, rows):
    """A block of queries reads the runs of keys ``key_ranges`` names:
    every visible pair is inside them, and with whole tiles they leave
    out every tile that is wholly masked."""
    mask = lm.Mask.blockdiff(half, block)
    t = 2 * half
    dense = np.asarray(mask.visible(np.arange(t)[:, None],
                                    np.arange(t)[None, :]))
    rows = min(rows, t)
    for lo in range(0, t, rows):
        hi = min(lo + rows, t)
        read = np.zeros(t, bool)
        for first, last in mask.key_ranges(lo, hi):
            read[first:last] = True
        assert not dense[lo:hi][:, ~read].any()
        if rows % block == 0 and half % rows == 0:
            for k in range(0, t, rows):     # whole tiles
                assert read[k:k + rows].any() == dense[lo:hi, k:k + rows].any()


@pytest.mark.parametrize("half, block, rows", [(16, 4, 8), (12, 3, 512),
                                               (32, 4, 16)])
def test_blockwise_attention_matches_the_reference(half, block, rows):
    rng = np.random.default_rng(3)
    t = 2 * half
    q = jnp.asarray(rng.normal(size=(2, 2, t, 16)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, t, 16)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(2, t, 16)), jnp.bfloat16)
    got = lm.blockwise_attention(q / 4, k, v, lm.Mask.blockdiff(half, block),
                                 block=rows)
    with ref.PRECISION:
        want = ref.attention(
            q.reshape(4, t, 16).transpose(1, 0, 2).astype(jnp.float32),
            k.transpose(1, 0, 2).astype(jnp.float32),
            v.transpose(1, 0, 2).astype(jnp.float32), half, block, rows=t)
    got = got.reshape(4, t, 16).transpose(1, 0, 2).astype(jnp.float32)
    assert _relative(got, want) < 1e-2


def test_a_mask_in_its_short_form_is_the_first_two_kinds():
    assert lm.Mask.of(0) == lm.Mask() and lm.Mask.of(0).scope \
        == "mv.lm.attn.full"
    assert lm.Mask.of(8) == lm.Mask("window", window=8)
    assert lm.Mask.of(8).scope == "mv.lm.attn.window"
    assert lm.Mask.of(8).key_ranges(16, 24) == ((9, 24),)
    assert lm.Mask().key_ranges(16, 24) == ((0, 24),)


# -- the configuration's description ---------------------------------------------

def test_the_two_families_are_told_by_their_keys():
    from tests.test_lm_model import CONFIG as SMALLTHINKER
    cfg = LMConfig.from_dict(CONFIG)
    assert (cfg.activation, cfg.router_input, cfg.qk_norm, cfg.objective) \
        == ("silu", "ffn_norm", True, "block_diffusion")
    assert (cfg.n_experts, cfg.experts_held, cfg.top_k) == (16, (0, 4), 3)
    assert cfg.rope_layout == (1, 1) and cfg.window_layout == (0, 0)
    assert cfg.small_names == lm.LAYER_SMALL + lm.QK_NORMS
    assert cfg.layer_shapes()["norm_q"] == (16,) and cfg.mask_id == 52
    assert len(cfg.layer_shapes()) == 12
    assert cfg.layer_mask(0, 32) == lm.Mask.blockdiff(32, 4)
    old = LMConfig.from_dict(SMALLTHINKER)
    assert (old.activation, old.router_input, old.qk_norm, old.objective) \
        == ("relu", "input", False, "next_token")
    assert len(old.layer_shapes()) == 10 and old.small_names == lm.LAYER_SMALL
    assert old.layer_mask(1, 32) == lm.Mask.of(8)
    assert old.layer_mask(0, 32) == lm.Mask()


def test_the_benchmark_s_configuration_counts_its_parameters():
    with open("benchmark/configs/sdar-30b-a3b-l6.json") as f:
        config = json.load(f)
    config.pop("rehearsal")
    cfg = LMConfig.from_dict(config)
    assert cfg.parameters() == config["parameters"]["total"] == 645_623_296
    per_layer = sum(int(np.prod(s)) for s in cfg.layer_shapes().values())
    assert per_layer == 94_638_336
    assert (cfg.hidden, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim,
            cfg.expert_width, cfg.n_experts, cfg.top_k, cfg.rope_theta) \
        == (2048, 32, 4, 128, 768, 128, 8, 1e6)
    assert cfg.n_layers == 6 and cfg.experts_held == (0, 16)
    assert cfg.vocab == 18992 and cfg.mask_id == 18991


# -- the noise ------------------------------------------------------------------

def test_the_noise_is_consistent_and_near_one_half():
    cfg = LMConfig.from_dict(dict(CONFIG, vocab_size=1000))
    tokens = zipf_tokens(jax.random.PRNGKey(1), (4, 4096), cfg.vocab - 1)
    noised, masked, t = lm.noise(cfg, jax.random.PRNGKey(2), tokens)
    c = ref.sizes(dict(CONFIG, vocab_size=1000))
    assert ref.check_noise(c, tokens, noised, masked, t) == []
    assert t.shape == (4, 1024) and masked.dtype == jnp.bool_
    assert float(t.min()) > cfg.t_min and float(t.max()) <= 1.0
    assert 0.47 < float(masked.mean()) < 0.53
    # each block masked at its own rate
    by_block = np.asarray(masked).reshape(4, 1024, 4).mean(-1)
    high, low = np.asarray(t) > 0.8, np.asarray(t) < 0.2
    assert by_block[high].mean() > 0.8 and by_block[low].mean() < 0.2
    # and the reference refuses a noise that is not one
    assert ref.check_noise(c, tokens, tokens, masked, t)
    assert ref.check_noise(c, tokens, noised, masked, t * 0)
    assert ref.check_noise(c, tokens.at[0, 0].set(cfg.mask_id), noised,
                           masked, t)


def test_the_same_seed_and_step_give_the_same_draw():
    from multiverso_tpu.models.lm.ps_train import noise_program
    cfg = LMConfig.from_dict(CONFIG)
    tokens = zipf_tokens(jax.random.PRNGKey(1), (B, L), cfg.vocab - 1)
    prepare, key = noise_program(cfg), jax.random.PRNGKey(7)
    first, again, later = (prepare(tokens, key, np.int32(s))
                           for s in (3, 3, 4))
    for a, b in zip(first, again):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(first[5]), np.asarray(later[5]))
    other = prepare(tokens, jax.random.PRNGKey(8), np.int32(3))
    assert not np.array_equal(np.asarray(first[5]), np.asarray(other[5]))
    ids, targets, weights, distinct, scored, masked, t = first
    assert ids.shape == (B, 2 * L) and np.array_equal(ids[:, L:], tokens)
    assert np.array_equal(np.asarray(targets), np.asarray(tokens).ravel())
    assert int(scored) == int(masked.sum())
    assert int(distinct) == len(np.unique(np.asarray(ids)))
    c = ref.sizes(CONFIG)
    assert ref.check_noise(c, tokens, ids[:, :L], masked, t) == []
    np.testing.assert_allclose(
        np.asarray(weights).reshape(B, L),
        np.asarray(ref.loss_weights(c, masked, t)), rtol=1e-6)


# -- the stack against the reference ---------------------------------------------

def _params(cfg, seed, scale=0.08):
    rng = np.random.default_rng(seed)
    draw = lambda shape: jnp.asarray(       # noqa: E731
        rng.normal(0, scale, shape), jnp.float32)
    layers = []
    for _ in range(cfg.n_layers):
        layer = {n: draw(s) for n, s in cfg.layer_shapes().items()}
        for n in ("norm_attn", "norm_ffn") + lm.QK_NORMS:
            layer[n] = 1 + layer[n]
        layers.append(layer)
    return {"embedding": draw((cfg.vocab, cfg.hidden)) * 10, "layers": layers,
            "final_norm": 1 + draw((cfg.hidden,)),
            "head": draw((cfg.vocab, cfg.hidden))}


def program_step(cfg, params, clean, noised, weights):
    """The trainer's step without its tables: the same functions in the
    same order. Returns the loss, every gradient and each layer's ids."""
    half = clean.shape[1]
    ids = jnp.concatenate([noised, clean], axis=1)
    x = params["embedding"][ids]
    mask = cfg.layer_mask(0, half)
    pos = mask.positions(2 * half)
    kept, chosen = [], []
    for layer in params["layers"]:
        mats = {n: layer[n].astype(jnp.bfloat16) for n in lm.LAYER_MATRICES}
        small = {n: layer[n] for n in cfg.small_names}
        kept.append((mats, small, x))
        out = [lm.layer_forward(cfg, True, mask, mats, small, x[b], pos)
               for b in range(x.shape[0])]
        x = jnp.stack([o[0] for o in out])
        chosen.append(jnp.stack([o[2] for o in out]))
    loss, dx, d_head, d_norm = lm.head_loss_and_grads(
        cfg, params["head"].astype(jnp.bfloat16), params["final_norm"],
        x[:, :half].reshape(-1, cfg.hidden), clean.reshape(-1),
        weights.reshape(-1))
    dx = jnp.concatenate([dx.reshape(-1, half, cfg.hidden)] * 2, 1) \
        * jnp.repeat(jnp.asarray([1.0, 0.0]), half)[None, :, None]
    grads = {"head": d_head, "final_norm": d_norm, "layers": [None] * len(kept)}
    for i in reversed(range(len(kept))):
        mats, small, x_in = kept[i]
        out = [lm.layer_grads(cfg, True, mask, mats, small, x_in[b], dx[b],
                              pos) for b in range(x_in.shape[0])]
        dx = jnp.stack([o[0] for o in out])
        grads["layers"][i] = jax.tree_util.tree_map(
            lambda *g: sum(g), *[{**o[1], **o[2]} for o in out])
    grads["embedding"] = jnp.zeros_like(params["embedding"]).at[ids].add(dx)
    return loss, grads, chosen


@pytest.fixture(scope="module")
def both():
    cfg = LMConfig.from_dict(CONFIG)
    c = ref.sizes(CONFIG)
    params = _params(cfg, 0)
    clean = jnp.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab - 1, (B, L)), jnp.int32)
    noised, masked, t = lm.noise(cfg, jax.random.PRNGKey(4), clean)
    weights = ref.loss_weights(c, masked, t)
    loss, grads, chosen = program_step(cfg, params, clean, noised, weights)
    with ref.PRECISION:
        want_loss, want = jax.value_and_grad(
            lambda p: ref.step_loss(c, p, clean, noised, masked, t, chosen))(
                params)
        free = ref.step_loss(c, params, clean, noised, masked, t)
    return cfg, loss, grads, want_loss, want, free


def test_loss_matches_the_reference(both):
    _, loss, _, want_loss, _, free = both
    assert abs(float(loss) - float(want_loss)) < 2e-3 * float(want_loss)
    # the reference's own choice of experts gives nearly the same loss
    assert abs(float(free) - float(want_loss)) < 5e-2 * float(want_loss)


def _names(cfg):
    return (["embedding", "final_norm", "head"]
            + [f"layers.{i}.{n}" for i in range(cfg.n_layers)
               for n in cfg.layer_shapes()])


@pytest.mark.parametrize("name", _names(LMConfig.from_dict(CONFIG)))
def test_gradient_matches_the_reference(both, name):
    _, _, grads, _, want, _ = both
    for part in name.split("."):
        key = int(part) if part.isdigit() else part
        grads, want = grads[key], want[key]
    assert grads.shape == want.shape and grads.dtype == jnp.float32
    assert float(jnp.linalg.norm(want)) > 0
    limit = SMALL_LIMIT if "norm" in name or "router" in name \
        else MATRIX_LIMIT
    assert _relative(grads, want) < limit, name


def test_the_clean_half_carries_no_loss_but_a_gradient(both):
    """No logit of the clean half is scored, yet its embedding rows get a
    gradient through the keys and values the noised half reads."""
    cfg, _, grads, *_ = both
    clean = np.random.default_rng(1).integers(0, cfg.vocab - 1, (B, L))
    rows = np.abs(np.asarray(grads["embedding"])).sum(1)
    assert rows[np.unique(clean)].all() and rows[cfg.mask_id] > 0


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Each share holds two of the sixteen experts, routes over all
    sixteen and normalises over all three chosen; attention, which every
    chip computes alike, is counted once."""
    cfg = LMConfig.from_dict(CONFIG)
    rng = np.random.default_rng(4)
    draw = lambda shape: jnp.asarray(       # noqa: E731
        rng.normal(0, 0.08, shape), jnp.float32)
    whole = dict(CONFIG, num_experts=16)
    uncut = {n: draw(s) for n, s in
             LMConfig.from_dict(whole).layer_shapes().items()}
    for n in ("norm_attn", "norm_ffn") + lm.QK_NORMS:
        uncut[n] = 1 + uncut[n]
    x = draw((2 * L, 64)) * 10
    with ref.PRECISION:
        want = ref.layer(ref.sizes(whole), uncut, x)
    mask = lm.Mask.blockdiff(L, 4)
    pos = mask.positions(2 * L)
    small = {n: uncut[n] for n in cfg.small_names}
    attn = {n: uncut[n].astype(jnp.bfloat16)
            for n in ("wq", "wk", "wv", "wo")}
    a = lm.attention_block(cfg, True, mask, attn, {n: None for n in attn},
                           lm._attention_norms(cfg, small), x, pos)
    parts, held = 0, 0
    for first in range(0, 16, 2):
        share = LMConfig.from_dict(
            dict(CONFIG, num_experts=2, first_expert_held=first))
        h, w = cfg.hidden, cfg.expert_width
        mats = dict(attn)
        for name, rows in (("w_gate", h), ("w_up", h), ("w_down", w)):
            mats[name] = uncut[name][first * rows:(first + 2) * rows].astype(
                jnp.bfloat16)
        y, stats, _ = lm.layer_forward(share, True, mask, mats, small, x, pos)
        parts = parts + (y - a)
        held += int(stats[0])
    assert held == 2 * L * 3        # every assignment on exactly one share
    assert _relative(a + parts, want) < 1e-2


def test_positions_past_the_clean_copy_are_another_model():
    """The control the chip's check rests on: the noised copy at rotary
    positions L .. 2L-1 is not the model."""
    cfg = LMConfig.from_dict(CONFIG)
    params = _params(cfg, 0)
    layer = params["layers"][0]
    mats = {n: layer[n].astype(jnp.bfloat16) for n in lm.LAYER_MATRICES}
    small = {n: layer[n] for n in cfg.small_names}
    x = jnp.asarray(np.random.default_rng(2).normal(size=(2 * L, 64)),
                    jnp.float32)
    mask = lm.Mask.blockdiff(L, 4)
    right = lm.layer_forward(cfg, True, mask, mats, small, x,
                             mask.positions(2 * L))[0]
    wrong = lm.layer_forward(cfg, True, mask, mats, small, x)[0]
    with ref.PRECISION:
        want = ref.layer(ref.sizes(CONFIG), layer, x)
    assert _relative(right - x, want - x) < 3e-2
    assert _relative(wrong - x, want - x) > 0.1


# -- two steps through the server ------------------------------------------------

STEPS = 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8


@pytest.fixture(scope="module")
def run():
    """Two steps through the tables, and the reference's two beside them
    from the same start, given each step's noise and chosen experts."""
    from multiverso_tpu.util import configure
    mv.init(["-updater_type=adam"])
    try:
        cfg = LMConfig.from_dict(CONFIG)
        c = ref.sizes(CONFIG)
        trainer = PSLMTrainer(cfg, L, B, seed=3, lr=LR, beta1=B1, beta2=B2,
                              eps=EPS)
        tables = trainer.tables()
        start = {n: jnp.asarray(_state(trainer, t)[0])
                 for n, t in tables.items()}
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        chosen, losses, noises = [], [], []
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program):
                out = _program(*args)
                chosen[-1].append(out[3])
                return out
            trainer._forward[kind] = spy
        key = jax.random.PRNGKey(5)
        batches = [zipf_tokens(jax.random.fold_in(key, i), (B, L),
                               cfg.vocab - 1) for i in range(STEPS)]
        for tokens in batches:
            chosen.append([])
            noises.append(trainer.noised(tokens))
            losses.append(float(trainer.step(tokens)))
        trainer.sync()
        trainer.flush_stats()
        after = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        got = {n: _state(trainer, t) for n, t in tables.items()}

        params = _as_reference(start)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        m, v, want_losses = zeros, zeros, []
        with ref.PRECISION:
            for t, tokens in enumerate(batches, start=1):
                ids, _, _, _, _, masked, ts = noises[t - 1]
                assert ref.check_noise(c, tokens, ids[:, :L], masked, ts) == []
                loss, g = jax.value_and_grad(
                    lambda p: ref.step_loss(c, p, tokens, ids[:, :L], masked,
                                            ts, chosen[t - 1]))(params)
                want_losses.append(float(loss))
                new = jax.tree_util.tree_map(
                    lambda w, m_, v_, g_: ref.adam(w, m_, v_, t, g_, LR, B1,
                                                   B2, EPS), params, m, v, g)
                pick = lambda i: jax.tree_util.tree_map(     # noqa: E731
                    lambda x: x[i], new,
                    is_leaf=lambda x: isinstance(x, tuple))
                named = jnp.zeros(cfg.vocab, bool).at[ids.reshape(-1)].set(
                    True)[:, None]
                lazy = [jnp.where(named, pick(i)["embedding"], old["embedding"])
                        for i, old in enumerate((params, m, v))]
                params, m, v = pick(0), pick(1), pick(2)
                params["embedding"], m["embedding"], v["embedding"] = lazy
        want = {n: (np.asarray(_flat(params)[n]), np.asarray(_flat(m)[n]),
                    np.asarray(_flat(v)[n])) for n in tables}
        yield {"cfg": cfg, "losses": losses, "want_losses": want_losses,
               "got": got, "want": want, "start": start, "noises": noises,
               "counters": (before, after)}
    finally:
        mv.shutdown()
        configure.reset_flags()


def test_twenty_seven_tables(run):
    assert len(run["got"]) == 2 * 12 + 3
    assert run["cfg"].parameters() == sum(
        w.size for w, *_ in run["got"].values())


def test_losses_follow_the_reference(run):
    for got, want in zip(run["losses"], run["want_losses"]):
        assert abs(got - want) < 2e-3 * want


@pytest.mark.parametrize("name", ["embedding", "head", "final_norm"] + [
    f"layer{i}.{n}" for i in range(2)
    for n in LMConfig.from_dict(CONFIG).layer_shapes()])
def test_table_and_moments_after_two_steps(run, name):
    """As tests/test_lm_trainer.py: Adam divides the gradient by its own
    size, so a table's change is compared by its direction."""
    w, m, v, t = run["got"][name]
    want_w, want_m, want_v = run["want"][name]
    assert t == STEPS
    start = np.asarray(run["start"][name])
    norm = np.linalg.norm
    assert norm(m - want_m) < 6e-2 * norm(want_m), name
    assert norm(v - want_v) < 0.15 * norm(want_v), name
    assert norm((w - start) - (want_w - start)) \
        < 0.25 * norm(want_w - start), name
    assert norm(want_w - start) > 0


def test_the_mask_token_s_row_is_the_hottest_and_is_stepped_once(run):
    """About a quarter of a step's ids are the mask token's: the rows
    form sums them first, and the row's moments move as one row's."""
    cfg = run["cfg"]
    ids = np.asarray(run["noises"][0][0]).ravel()
    counts = np.bincount(ids, minlength=cfg.vocab)
    assert counts[cfg.mask_id] == counts.max()
    assert 0.15 < counts[cfg.mask_id] / ids.size < 0.35
    w, m, v, _ = run["got"]["embedding"]
    want_w, want_m, want_v = run["want"]["embedding"]
    row = cfg.mask_id
    assert np.linalg.norm(m[row] - want_m[row]) \
        < 6e-2 * np.linalg.norm(want_m[row])
    quiet = ~(np.abs(m).sum(1) > 0)
    assert np.array_equal(w[quiet],
                          np.asarray(run["start"]["embedding"])[quiet])


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def delta(name):
        return after[name]["count"] - before.get(name, {"count": 0})["count"]

    cfg = run["cfg"]
    assert delta("LM_STEP") == STEPS
    assert delta("LM_TOKENS") == STEPS * B * L
    assert delta("LM_POSITIONS") == STEPS * 2 * B * L
    assert delta("LM_MASKED_TOKENS") == sum(
        int(n[4]) for n in run["noises"])
    assert 0.3 < delta("LM_MASKED_TOKENS") / delta("LM_TOKENS") < 0.7
    held = delta("LM_HELD_ASSIGNMENTS")
    assert 0 < held <= STEPS * cfg.n_layers * B * 2 * L * cfg.top_k
    assert 0 < delta("LM_EMBED_ROWS") <= STEPS * min(2 * B * L, cfg.vocab)
    # 27 whole or row Gets and 27 Adds a step, and the closing row Get
    assert delta("WORKER_PROCESS_GET") == STEPS * 27 + 1
    assert delta("WORKER_PROCESS_ADD") == STEPS * 27

"""DeepSeek-V3's block on the PLAIN residual as ``model_type: glm4_moe_lite``
has it (GLM-4.7-Flash: rotary latent attention with a query latent under no
scaling, a dense layer and sparse ones with a shared expert under a sigmoid
router chosen through a bias, a multi-token module) against the plain
reference (benchmark/reference/lm_glm_step.py) at small widths on the CPU:
each layer's result and gradients with every product in float32 (the
equations) and in bfloat16 (the rounding), rotary positions without YaRN,
the eight shares of a sparse layer, the module on the plain residual, and one
step of ``PSLMTrainer`` through the tables with the module held."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_glm_step as ref
from benchmark.reference import lm_mla_step as ref_yarn
from multiverso_tpu.models.lm import PSLMTrainer, latent, model as lm
from multiverso_tpu.models.lm import mtp, zipf_tokens
from multiverso_tpu.util import dashboard
from tests.test_lm_mla import (CONFIG as XING, _as_reference, _draw, _flat,
                               _relative, _split, _state)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the catalog's keys (model-configs guide, architectures.jsonl, the row
# GLM-4.7-Flash) at small widths, and what the share and the training add
CONFIG = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 32,
    "intermediate_size": 48, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 16,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 2, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 4, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 1000000, "tie_word_embeddings": False, "q_lora_rank": 24,
    "kv_lora_rank": 16, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 16, "vocab_size": 97,
    "router_outputs": 8, "first_expert_held": 2, "router_bias_rate": 0.001,
    "mtp_loss_weight": 0.3, "loss_block": 16}
T, B = 32, 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8
EXACT = 2e-4        # float32 products against the reference's: rounding alone
# bfloat16 products at these widths (tests/test_lm_mla.py's reasons)
ROUNDED, ROUNDED_SCORES = 1e-1, 2.5e-1
FEEDS_SCORES = ("wq_a", "wq_b", "norm_q_a", "wkv_a", "wkv_b", "norm_kv_a",
                "norm_attn")
CFG = lm.LMConfig.from_dict(CONFIG)
LAYER_TENSORS = [(sparse, name) for sparse in (0, 1)
                 for name in CFG.layer_shapes(sparse) if name != "router_bias"]


def _limit(name):
    return ROUNDED_SCORES if name.rsplit(".", 1)[-1] in FEEDS_SCORES \
        else ROUNDED


@pytest.fixture
def float32_products(monkeypatch):
    """Every product of the program in float32: what is left against the
    reference is the equations."""
    monkeypatch.setattr(lm, "BF16", jnp.float32)


def _layer_both(cfg, c, sparse, dtype, seed=0):
    rng = np.random.default_rng(seed)
    p = _draw(cfg.layer_shapes(sparse), rng)
    x = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    mats, small = _split(cfg, p, sparse, dtype)
    with ref.PRECISION:
        y, (stats, ids), pull = lm.layer_vjp(cfg, True, 0, sparse, mats,
                                             small, x)
        dx, d_mats, d_small = pull(dy)
        chosen = ids if sparse else None
        want_y, own = ref.layer(c, p, x, chosen, own=True)
        d_p, want_dx = jax.vjp(lambda p, x: ref.layer(c, p, x, chosen),
                               p, x)[1](dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": (ids, own),
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p)}


# -- the description ------------------------------------------------------------

def test_the_block_on_the_plain_residual_is_told_by_its_keys():
    assert CFG.attention == "mla" and CFG.residual == "plain"
    assert CFG.yarn == () and CFG.rope_layout == (1, 1)
    assert CFG.one_ffn_input and not CFG.attention_layout
    assert CFG.scoring == "sigmoid_bias" and CFG.ffn_layout == (0, 1)
    assert CFG.heads_held == (0, 2) and CFG.n_heads == 2
    assert CFG.experts_held == (2, 4) and CFG.routed_scale == 1.8
    assert CFG.layer_kinds() == ((1, 0, 0), (1, 0, 1))
    assert CFG.head_dim == 16 and CFG.v_head_dim == 16
    assert CFG.shared_width == 16 and CFG.dense_width == 48
    assert CFG.mtp_layers == 1 and CFG.mtp_weight == 0.3
    assert "router" not in CFG.layer_shapes(0)
    assert CFG.layer_shapes(1)["router_bias"] == (8,)
    assert not [n for n in CFG.layer_shapes(1) if n.startswith("hc_")]
    assert CFG.matrices(1) == lm.MLA_MATRICES + lm.DENSE + lm.SHARED
    assert latent.softmax_scale(CFG) == 0.25


def _file(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_configuration_s_file_builds_the_published_widths():
    """Every number of the catalog's row under its own key but the three
    that ``reduced`` names, and the count the file states."""
    config = _file("glm47-flash-30b-a3b-l5")
    catalog = {
        "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 10240, "max_position_embeddings": 202752,
        "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
        "topk_method": "noaux_tc", "norm_topk_prob": True,
        "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
        "n_routed_experts": 64, "n_shared_experts": 1,
        "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
        "first_k_dense_replace": 1, "num_hidden_layers": 47,
        "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
        "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
        "rope_scaling": None, "rope_theta": 1000000,
        "tie_word_embeddings": False, "q_lora_rank": 768,
        "kv_lora_rank": 512, "qk_nope_head_dim": 192,
        "qk_rope_head_dim": 64, "v_head_dim": 256, "vocab_size": 154880}
    differs = {k for k, v in catalog.items() if config[k] != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: catalog[k] for k in differs} == {
        k: config["published"][k] for k in differs}
    config.pop("rehearsal")
    cfg = lm.LMConfig.from_dict(config)
    assert cfg.n_heads_held == 20 and cfg.head_dim == cfg.v_head_dim == 256
    assert cfg.ffn_layout == (0, 1, 1, 1, 1) and cfg.experts_held == (0, 8)
    assert cfg.n_experts == 64 and cfg.top_k == 4 and cfg.mtp_layers == 1
    stated = config["parameters"]
    assert cfg.parameters() == stated["total"] == 706518848

    def size(shapes):
        return sum(int(np.prod(s)) for s in shapes.values())

    assert size(cfg.layer_shapes(0)) == stated["dense_layer"]
    assert size(cfg.layer_shapes(1)) == stated["sparse_layer"]
    assert size({**cfg.layer_shapes(4), **cfg.mtp_shapes()}) \
        == stated["module"]
    tables = 3 + len(cfg.layer_shapes(0)) + 4 * len(cfg.layer_shapes(1)) \
        + len({**cfg.layer_shapes(4), **cfg.mtp_shapes()})
    assert tables == stated["tables"]
    assert lm.experts_capacity(cfg, 8192) == 8192      # the short buffer


# sha256 (16 hex digits) of ``repr(LMConfig.from_dict(file))`` at the cell's
# and at the rehearsal's widths, made from the commit before this family
# (710bc1f): the configurations that share ``_from_mla`` and latent.py with
# it build what they built.
PARENT_BUILT = {("xing4-29b-a4b-l5", False): "f3adb23655ff8571",
                ("xing4-29b-a4b-l5", True): "9d893e35a3593137",
                ("kimi-linear-48b-a3b-l5", False): "62dd6c1aa3e9837d",
                ("kimi-linear-48b-a3b-l5", True): "0c6543d14301eca6"}


@pytest.mark.parametrize("name,rehearse", list(PARENT_BUILT))
def test_an_older_latent_configuration_builds_what_it_built(name, rehearse):
    config = _file(name)
    tiny = config.pop("rehearsal")
    if rehearse:
        config.update(tiny)
    cfg = lm.LMConfig.from_dict(config)
    # the fields added since (PRs 60, 63, 65) stand at their defaults, last
    built = repr(cfg).replace(
        ", kda_beta_scale=1, conv_taps=0, tied=False, ssd_heads=0, "
        "ssd_head_dim=0, ssd_state=0, ssd_groups=0, ssd_conv=0, ssd_chunk=0, "
        "residual_scale=1.0, attn_scale=0.0, logits_scale=1.0, "
        "embed_scale=1.0)", ")")
    assert hashlib.sha256(built.encode()).hexdigest()[:16] \
        == PARENT_BUILT[name, rehearse]


@pytest.mark.parametrize("change", [
    {"rope_scaling": {"type": "linear", "factor": 2}},
    {"scoring_func": "softmax"}, {"partial_rotary_factor": 0.5},
    {"n_group": 2}, {"norm_topk_prob": False}])
def test_a_block_that_is_not_written_down_is_refused(change):
    with pytest.raises(Exception):
        lm.LMConfig.from_dict(dict(CONFIG, **change))


# -- each layer against the reference ----------------------------------------------

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


# -- rotary latent attention without YaRN ----------------------------------------------

def _attention(cfg, p, u, dv):
    mats, small = _split(cfg, p, 0)
    sinks = {n: jnp.zeros_like(w) for n, w in mats.items()}
    v, pull = latent.attention_vjp(cfg, mats, sinks, small, u)
    return (v,) + pull(dv)


def test_latent_attention_turns_at_theta_s_own_frequencies(float32_products):
    c = ref.sizes(CONFIG)
    rng = np.random.default_rng(2)
    p = _draw(CFG.layer_shapes(0), rng)
    u = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    dv = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    with ref.PRECISION:
        v, du, d_mats, d_small = _attention(CFG, p, u, dv)
        want_v, back = jax.vjp(lambda p, u: ref.attention_f(c, p, u), p, u)
        want_p, want_du = back(dv)
    assert _relative(v, want_v) < EXACT and _relative(du, want_du) < EXACT
    for name, grad in {**d_mats, **d_small}.items():
        assert _relative(grad, want_p[name]) < EXACT, name
    np.testing.assert_allclose(
        ref.frequencies(c), 1e6 ** (-np.arange(0, 8, 2) / 8), rtol=1e-6)


@pytest.mark.parametrize("what", ("no_turn", "yarn"))
def test_another_turn_is_another_attention(float32_products, what):
    """Neither a layer without positions nor YaRN's frequencies and scale
    pass for ``rope_scaling`` null."""
    rng = np.random.default_rng(3)
    p = _draw(CFG.layer_shapes(0), rng)
    u = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    mats, small = _split(CFG, p, 0)
    sinks = {n: jnp.zeros_like(w) for n, w in mats.items()}
    with ref.PRECISION:
        right = latent.attention_vjp(CFG, mats, sinks, small, u)[0]
        if what == "no_turn":
            other = latent.attention_vjp(CFG, mats, sinks, small, u,
                                         rope=False)[0]
        else:
            y = XING["rope_scaling"]
            under = dataclasses.replace(CFG, rope_theta=1e4, yarn=(
                y["factor"], y["beta_fast"], y["beta_slow"],
                y["original_max_position_embeddings"], 1.0, 1.0))
            plain = dataclasses.replace(CFG, rope_theta=1e4)
            right = latent.attention_vjp(plain, mats, sinks, small, u)[0]
            other = latent.attention_vjp(under, mats, sinks, small, u)[0]
    assert _relative(other, right) > 1e-2


def test_yarn_s_path_is_where_it_was(float32_products):
    """The configuration that has ``rope_scaling`` keeps YaRN's frequencies
    and its scale: latent.py against ITS reference."""
    cfg, c = lm.LMConfig.from_dict(XING), ref_yarn.sizes(XING)
    assert len(cfg.yarn) == 6 and cfg.residual == "mhc"
    rng = np.random.default_rng(4)
    p = _draw(cfg.layer_shapes(0), rng)
    u = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    dv = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    with ref.PRECISION:
        v, du, _, _ = _attention(cfg, p, u, dv)
        want_v, back = jax.vjp(lambda u: ref_yarn.attention_f(c, p, u), u)
    assert _relative(v, want_v) < EXACT
    assert _relative(du, back(dv)[0]) < EXACT


# -- the eight shares add up to the uncut layer ------------------------------------------

def test_the_eight_shares_add_up_to_the_uncut_layer(float32_products):
    """What chips 0 to 7 give of a sparse layer, each its own expert's part
    (one of eight held), with what every chip computes alike (attention, the
    shared expert, the residual) counted once, is the uncut reference's
    layer."""
    config = dict(CONFIG, n_routed_experts=8, first_expert_held=0)
    whole, c = lm.LMConfig.from_dict(config), ref.sizes(config)
    rng = np.random.default_rng(7)
    p = _draw(whole.layer_shapes(1), rng)
    x = jnp.asarray(rng.normal(size=(T, whole.hidden)), jnp.float32)
    h, w = whole.hidden, whole.expert_width
    with ref.PRECISION:
        want = ref.layer(c, p, x)
        a = x + ref.attention_f(c, p, x)
        alike = a + ref.gated(ref.rmsnorm(a, p["norm_ffn"], c["eps"]),
                              p["ws_gate"], p["ws_up"], p["ws_down"])
        total, seen = alike, 0
        for k in range(8):
            share = dataclasses.replace(whole, experts_held=(k, 1))
            cut = dict(p)
            cut["w_gate"] = p["w_gate"][k * h:(k + 1) * h]
            cut["w_up"] = p["w_up"][k * h:(k + 1) * h]
            cut["w_down"] = p["w_down"][k * w:(k + 1) * w]
            assert {n: cut[n].shape for n in cut} == share.layer_shapes(1)
            mats, small = _split(share, cut, 1)
            y, (stats, _), _ = lm.layer_vjp(share, True, 0, 1, mats, small, x)
            total = total + (y - alike)
            seen += int(stats[0])
            assert int(stats[2:].sum()) == T * whole.top_k
    assert seen == T * whole.top_k      # every assignment on one share
    assert _relative(total, want) < EXACT


# -- the multi-token module on the plain residual ------------------------------------------

def _module_tensors(seed):
    rng = np.random.default_rng(seed)
    p = _draw({**CFG.layer_shapes(1), **CFG.mtp_shapes()}, rng)
    xs, e, dy = (jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
                 for _ in range(3))
    names = CFG.matrices(1) + mtp.MATRICES
    mats = {n: p[n] for n in names}
    small = {n: p[n] for n in p if n not in names and n != "final_norm"}
    return p, mats, small, xs, e, dy


@pytest.fixture(scope="module")
def module():
    p, mats, small, xs, e, dy = _module_tensors(8)
    c = ref.sizes(CONFIG)
    with pytest.MonkeyPatch.context() as patch, ref.PRECISION:
        patch.setattr(lm, "BF16", jnp.float32)      # float32 products
        y, (stats, ids), pull = mtp.module_vjp(CFG, mats, small, xs, e)
        dxs, de, d_mats, d_small = pull(dy)
        want_y, own = ref.mtp(c, p, xs, e, ids, own=True)
        want_p, want_dxs, want_de = jax.vjp(
            lambda p, xs, e: ref.mtp(c, p, xs, e, ids), p, xs, e)[1](dy)
    got = {"y": y, "dxs": dxs, "de_next": de, **d_mats, **d_small}
    want = {"y": want_y, "dxs": want_dxs, "de_next": want_de, **want_p}
    return got, want, (stats, ids, own)


MODULE_RESULTS = ["y", "dxs", "de_next", "proj", "norm_h", "norm_e"] + [
    n for n in CFG.layer_shapes(1) if n != "router_bias"]


@pytest.mark.parametrize("name", MODULE_RESULTS)
def test_the_module_on_the_plain_residual_equals_the_reference(module, name):
    got, want, _ = module
    assert _relative(got[name].reshape(want[name].shape), want[name]) < EXACT


def test_the_module_s_layer_counts_and_chooses_as_a_layer_does(module):
    got, want, (stats, ids, own) = module
    assert sorted(n for n in got if n not in ("y", "dxs", "de_next")) \
        == sorted(n for n in want if n not in (
            "y", "dxs", "de_next", "router_bias", "final_norm"))
    assert np.array_equal(np.sort(ids, -1), np.sort(own, -1))
    stats = np.asarray(stats)
    assert stats.shape == (2 + CFG.n_experts,)
    assert stats[2:].sum() == T * CFG.top_k
    assert not np.any(np.asarray(want["router_bias"]))


def test_the_module_reads_the_next_token_s_row(float32_products):
    """``de_next`` is not zero and ``e_next`` moves the result: the module
    is no second copy of the last layer."""
    _, mats, small, xs, e, _ = _module_tensors(9)
    with ref.PRECISION:
        y = mtp.module_vjp(CFG, mats, small, xs, e)[0]
        other = mtp.module_vjp(CFG, mats, small, xs, jnp.roll(e, 1, 0))[0]
    assert _relative(other, y) > 1e-2


# -- one step of the trainer through the tables, the module held ---------------------------

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

        def only(which):    # one objective alone: the other's weight 0
            return jax.jit(jax.grad(lambda p: ref.step_loss(
                c, p, tokens, chosen)[1][which]))(params)

        with ref.PRECISION:
            (want_loss, parts), grads = jax.jit(jax.value_and_grad(
                lambda p: ref.step_loss(c, p, tokens, chosen),
                has_aux=True))(params)
            main_alone, second_alone = only(0), only(1)
        yield {"loss": loss, "want_loss": float(want_loss), "parts": parts,
               "got": got, "start": start, "grads": _flat(grads, tables),
               "main_alone": main_alone, "second_alone": second_alone,
               "stats": stats, "chosen": chosen, "counters": (before, after),
               "names": list(tables), "c": c}
    finally:
        mv.shutdown()
        configure.reset_flags()


def test_the_tables_of_which_two_under_the_plain_rule(run):
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
    assert float(second) > 0
    assert abs(run["loss"] - run["want_loss"]) < 2e-3 * run["want_loss"]


def test_every_table_got_one_add(run):
    """Adam's step count is 1 after one step for every table, embedding and
    head (read twice a step) among them; a bias's two Adds are the two
    layers' that have one."""
    for name, (_, state) in run["got"].items():
        if state:
            assert int(state[2]) == 1, name
    before, after = run["counters"]
    assert after["LM_ROUTER_BIAS_ADDS"]["count"] \
        - before.get("LM_ROUTER_BIAS_ADDS", {"count": 0})["count"] == 2


@pytest.mark.parametrize("name", ["embedding", "head"])
def test_a_table_read_twice_carries_the_sum_of_its_two_gradients(run, name):
    """Neither objective's gradient alone is what reached the table: the
    first moment is ``(1 - beta1)`` times their weighted SUM."""
    w, (m, v, t) = run["got"][name]
    m = np.asarray(m)[tuple(slice(0, n) for n in w.shape)] / (1 - B1)
    both = np.asarray(run["grads"][name])
    main = np.asarray(run["main_alone"][name])
    second = 0.3 * np.asarray(run["second_alone"][name])
    assert np.linalg.norm(main + second - both) < 1e-4 * np.linalg.norm(both)
    assert np.linalg.norm(second) > 1e-2 * np.linalg.norm(both)
    error = np.linalg.norm(m - both)
    assert error < ROUNDED * np.linalg.norm(both)
    assert error < 0.5 * min(np.linalg.norm(m - main),
                             np.linalg.norm(m - second))


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
    assert delta("LM_MTP_TOKENS") == B * T and delta("LM_MTP_STEP") == 1
    assert delta("LM_POSITIONS") == B * (T + 1)
    assert delta("LM_ROUTER_BIAS_ADDS") == 2
    held = sum(int(s[:, 0].sum()) for s in run["stats"])
    assert delta("LM_HELD_ASSIGNMENTS") == held > 0
    # one a latent layer a sequence, the module's layer too, by the form
    # ``latent.inputs`` took: the CPU takes the jax.numpy chain
    assert not delta("LM_ATTN_PASS_FUSED")
    assert delta("LM_ATTN_PASS_PLAIN") == B * (CFG.n_layers + 1)
    tables = len(run["names"])
    # a Get and an Add a table, and the closing row Get
    assert delta("WORKER_PROCESS_GET") == tables + 1
    assert delta("WORKER_PROCESS_ADD") == tables


def test_the_module_s_programs_are_named_for_the_readers():
    """``jit_mtp_*`` is what the device trace's readers find the module by,
    and ``mv.lm.mtp`` / ``mv.lm.mtp.head`` its scopes, on the plain residual
    as under the streams."""
    from multiverso_tpu.models.lm import ps_train
    shapes = {**CFG.layer_shapes(1), **CFG.mtp_shapes()}
    names = CFG.matrices(1) + mtp.MATRICES
    mats = {n: jnp.zeros(shapes[n], jnp.bfloat16) for n in names}
    small = {n: jnp.ones(s) for n, s in shapes.items()
             if n not in mats and n != "final_norm"}
    xs = jnp.ones((B, T, CFG.hidden))
    forward, head, backward = ps_train.module_programs(CFG)
    texts = [
        forward.lower({n: w.astype(jnp.float32) for n, w in mats.items()},
                      small, xs, xs).as_text(debug_info=True),
        head.lower(jnp.ones((CFG.vocab, CFG.hidden)), jnp.ones(CFG.hidden),
                   xs, jnp.zeros(B * T, jnp.int32)).as_text(debug_info=True),
        backward.lower(mats, small, xs, xs, xs).as_text(debug_info=True)]
    for text, stem in zip(texts, ("mtp_forward", "mtp_head", "mtp_backward")):
        assert stem in text
    assert "mv.lm.mtp" in texts[0] and "mv.lm.attn.mla.kernel" in texts[0]
    assert "mv.lm.mtp.head" in texts[1]
    assert "mv.lm.mtp" in texts[2] and "mv.lm.grad_sum" in texts[2]
    assert "mv.lm.hc" not in texts[0] + texts[2]

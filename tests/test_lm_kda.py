"""The sixth family of multiverso_tpu/models/lm (the block of ``model_type:
kimi_linear``: the attention's kind a LAYER's, layers whose attention is
the gated delta rule's scan behind short convolutions, models/lm/delta.py,
and layers of latent attention with no query latent and no positions,
models/lm/latent.py; a dense layer and sparse ones with a shared expert
under a sigmoid router that chooses through a bias, on the plain residual)
against the plain reference (benchmark/reference/lm_kda_step.py: the
recurrence position by position) at small widths on the CPU: the chunked
scan forward and every gradient at several chunks, each kind of layer with
every product in float32 (the equations) and in bfloat16 (the rounding),
causality, that no position is used, a decay that underflows, the share
test, the description, one step of ``PSLMTrainer`` through the tables, and
what the five older configurations and latent.py's older caller still
are."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_kda_step as ref
from benchmark.reference import lm_mla_step as ref_mla
from multiverso_tpu.models.lm import PSLMTrainer, delta, latent, model as lm
from multiverso_tpu.models.lm import ps_train, zipf_tokens
from multiverso_tpu.util import dashboard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# layer 1 delta and dense, layer 2 delta, layer 3 latent, layer 4 delta:
# the published period after the dense layer
CONFIG = {
    "hidden_size": 32, "num_attention_heads": 4, "num_key_value_heads": 4,
    "head_dim": 8, "kv_lora_rank": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 4, "v_head_dim": 8,
    "mla_use_nope": True, "rope_scaling": None, "rope_theta": 10000,
    "linear_attn_config": {"full_attn_layers": [3, 7], "head_dim": 8,
                           "kda_layers": [1, 2, 4, 5, 6], "num_heads": 4,
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
T, B = 32, 2
LR, B1, B2, EPS = 3e-4, 0.9, 0.95, 1e-8
EXACT = 3e-4        # float32 products against the reference's: rounding
ROUNDED = 1e-1      # bfloat16 products at these widths
CFG = lm.LMConfig.from_dict(CONFIG)
C = ref.sizes(CONFIG)
KINDS = CFG.layer_kinds()


def _relative(a, b):
    return float(jnp.linalg.norm(jnp.ravel(a - b)) / jnp.linalg.norm(b))


@pytest.fixture
def float32_products(monkeypatch):
    """Every product of the program in float32: what is left against the
    reference is the equations."""
    monkeypatch.setattr(lm, "BF16", jnp.float32)
    monkeypatch.setattr(delta, "BF16", jnp.float32)


def _draw(shapes, rng):
    """Seeded tensors, every mechanism awake: a bias that moves the choice,
    decays from a channel that forgets in a position to one that keeps."""
    out = {}
    for name, shape in shapes.items():
        if name == "router_bias":
            value = rng.normal(0, 0.1, shape)
        elif name == "a_log":
            value = np.log(rng.uniform(1, 16, shape))
        elif name == "dt_bias":
            value = np.log(np.expm1(np.exp(rng.uniform(
                np.log(1e-2), np.log(0.5), shape))))
        elif name.startswith("conv_"):
            value = rng.uniform(-0.5, 0.5, shape)
        elif len(shape) == 1:
            value = 1 + 0.1 * rng.normal(size=shape)
        else:
            value = rng.normal(0, 0.2, shape)
        out[name] = jnp.asarray(value, jnp.float32)
    return out


def _split(p, layer, dtype=jnp.float32):
    mats = {n: p[n].astype(dtype) for n in CFG.matrices(layer)}
    return mats, {n: p[n] for n in p if n not in mats}


def _scan_inputs(seed=0, t=T, heads=2, lanes=8, decay=(0.01, 2.0)):
    rng = np.random.default_rng(seed)

    def unit(a):
        return a / np.linalg.norm(a, axis=-1, keepdims=True)

    q = unit(rng.normal(size=(t, heads, lanes))) * lanes ** -0.5
    k = unit(rng.normal(size=(t, heads, lanes)))
    v = rng.normal(size=(t, heads, lanes))
    g = -rng.uniform(*decay, size=(t, heads, lanes))
    beta = rng.uniform(0.1, 0.9, size=(t, heads))
    return tuple(jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta))


# -- the description ------------------------------------------------------------

def test_the_sixth_family_is_told_by_its_keys():
    assert CFG.attention_layout == ("kda", "kda", "mla", "kda")
    assert KINDS == ((0, 0, 0, "kda"), (0, 0, 1, "kda"), (0, 0, 1, "mla"),
                     (0, 0, 1, "kda"))
    assert CFG.residual == "plain" and CFG.one_ffn_input
    assert CFG.scoring == "sigmoid_bias" and CFG.bias_rate == 0.001
    assert not CFG.yarn and not CFG.q_lora_rank and not any(CFG.rope_layout)
    assert CFG.matrices(0) == delta.MATRICES + lm.DENSE
    assert CFG.matrices(2) == lm.MLA_DIRECT + lm.DENSE + lm.SHARED
    assert "norm_q_a" not in CFG.layer_shapes(2)
    assert CFG.layer_shapes(1)["conv_k"] == (32, 4)
    assert CFG.layer_shapes(1)["a_log"] == (4,)
    assert CFG.layer_shapes(1)["router_bias"] == (8,)
    assert CFG.parameters() == 2 * 97 * 32 + 32 + sum(
        int(np.prod(s)) for i in range(4)
        for s in CFG.layer_shapes(i).values())


def test_the_published_cut_counts_the_issue_s_parameters():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-a3b-l5.json")) as f:
        config = json.load(f)
    config.pop("rehearsal")
    cfg = lm.LMConfig.from_dict(config)
    sizes = [sum(int(np.prod(s)) for s in cfg.layer_shapes(i).values())
             for i in range(cfg.n_layers)]
    assert cfg.attention_layout == ("kda", "kda", "kda", "mla", "kda")
    assert sizes == [103219872, 103809952, 103809952, 93410560, 103809952]
    assert cfg.parameters() == 602434432
    attention = sum(int(np.prod(s)) for s in delta.shapes(cfg).values())
    assert attention + cfg.hidden == 39516576


OLDER = {
    "smallthinker-21ba3b-l4": (((0, 0), (1, 1), (1, 1), (1, 1)), 43),
    "sdar-30b-a3b-l6": (((1, 0),) * 6, 75),
    "xing4-29b-a4b-l5": (((1, 0, 0),) + ((1, 0, 1),) * 4, 113),
    "laguna-xs2-33b-a3b-l5": (
        ((1, 0, 0, 48), (1, 1, 1, 64), (1, 1, 1, 64), (1, 1, 1, 64),
         (1, 0, 1, 48)), 69),
    "keye-vl2-30b-a3b-lm": (((1, 0),) * 5, 88)}


@pytest.mark.parametrize("name", list(OLDER))
def test_an_older_configuration_s_kinds_and_tables_are_what_they_were(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           f"{name}.json")) as f:
        config = json.load(f)
    config.pop("rehearsal")
    cfg = lm.LMConfig.from_dict(config)
    kinds, tables = OLDER[name]
    assert cfg.layer_kinds() == kinds and not cfg.attention_layout
    assert all(cfg.attention_of(i) == cfg.attention
               for i in range(cfg.n_layers))
    assert 3 + sum(len(cfg.layer_shapes(i))
                   for i in range(cfg.n_layers)) == tables


# -- the scan ---------------------------------------------------------------------

@pytest.mark.parametrize("chunk,block", [(4, 2), (8, 4), (T, 16), (T, 8)])
def test_the_chunked_scan_is_the_recurrence(chunk, block, float32_products):
    args = _scan_inputs()
    with ref.PRECISION:
        want = ref.recurrence(*args)
        got, _ = delta.scan(*args, chunk, block)
    assert _relative(got, want) < EXACT


@pytest.mark.parametrize("chunk", [4, 8, T])
@pytest.mark.parametrize("wrt", range(5), ids=["q", "k", "v", "g", "beta"])
def test_the_chunked_scan_s_gradient_is_the_recurrence_s(chunk, wrt,
                                                         float32_products):
    args = _scan_inputs(1)
    cot = jnp.asarray(np.random.default_rng(2).normal(size=args[2].shape),
                      jnp.float32)
    with ref.PRECISION:
        want = jax.grad(lambda *a: jnp.sum(ref.recurrence(*a) * cot), wrt)(
            *args)
        got = jax.grad(lambda *a: jnp.sum(delta.scan(*a, chunk)[0] * cot),
                       wrt)(*args)
    assert _relative(got, want) < EXACT


def test_the_scan_in_bfloat16_products_is_the_recurrence_rounded():
    args = _scan_inputs(3)
    with ref.PRECISION:
        want = ref.recurrence(*args)
    assert EXACT < _relative(delta.scan(*args, 8)[0], want) < 2e-2


def test_a_decay_that_underflows_stays_finite_and_right(float32_products):
    """Log decays that sum under -20 a chunk (to -700 here: exp of their
    negative is past float32): the chunked form gives the recurrence's
    numbers, its gradients too, and the counter counts the channels."""
    q, k, v, g, beta = _scan_inputs(4, decay=(0.5, 1.0))
    g = g.at[:, 0, :4].multiply(40.0)       # head 0's first four channels
    cot = jnp.ones_like(v)
    with ref.PRECISION:
        want = ref.recurrence(q, k, v, g, beta)
        (got, deep), pull = jax.vjp(
            lambda *a: delta.scan(*a, 16), q, k, v, g, beta, has_aux=False)
        grads = pull((cot, np.zeros((), jax.dtypes.float0)))
        want_grads = jax.grad(
            lambda *a: jnp.sum(ref.recurrence(*a)), (0, 1, 2, 3, 4))(
                q, k, v, g, beta)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert _relative(got, want) < EXACT
    for mine, theirs in zip(grads, want_grads):
        assert bool(jnp.all(jnp.isfinite(mine)))
        assert _relative(mine, theirs) < 10 * EXACT
    sums = np.asarray(g).reshape(T // 16, 16, 2, 8).sum(1)
    assert int(deep) == int((sums < delta.DEEP).sum()) >= 2 * 4
    assert float(sums.min()) < -300


def test_the_solve_is_the_inverse_and_its_pull_the_inverse_s():
    rng = np.random.default_rng(5)
    a = jnp.asarray(np.tril(rng.normal(0, 0.3, (3, 16, 16)), -1), jnp.float32)
    want = jnp.linalg.inv(jnp.eye(16) + a)
    assert _relative(delta.unit_lower_inverse(a), want) < 1e-5
    cot = jnp.asarray(rng.normal(size=a.shape), jnp.float32)
    got = jax.grad(lambda a: jnp.sum(delta.unit_lower_inverse(a) * cot))(a)
    theirs = jax.grad(
        lambda a: jnp.sum(jnp.linalg.inv(jnp.eye(16) + a) * cot))(a)
    assert _relative(got, theirs) < 1e-4


def test_the_convolution_reads_its_own_and_the_three_before():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.normal(size=(T, 6)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(6, 4)), jnp.float32)
    want = jax.nn.silu(ref.conv(x, w))
    assert _relative(delta.short_conv(x, w), want) < 1e-6
    by_hand = sum(w[:, j] * x[5 - 3 + j] for j in range(4))
    assert _relative(ref.conv(x, w)[5], by_hand) < 1e-6


# -- a layer of each kind against the reference ------------------------------------

def _layer_both(layer, dtype, seed=0):
    rng = np.random.default_rng(seed)
    p = _draw(CFG.layer_shapes(layer), rng)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    mats, small = _split(p, layer, dtype)
    _, _, sparse, kind = KINDS[layer]
    with ref.PRECISION:     # one program each: op by op the scans crawl
        y, stats, ids = jax.jit(lambda mats, small, x: lm.layer_forward(
            CFG, False, 0, mats, small, x, None, sparse, kind))(mats, small, x)
        dx, d_mats, d_small = jax.jit(
            lambda mats, small, x, dy: lm.layer_grads(
                CFG, False, 0, mats, small, x, dy, None, sparse, kind))(
                    mats, small, x, dy)
        chosen = ids if sparse else None
        want_y, own = jax.jit(
            lambda p, x: ref.layer(C, p, x, chosen, own=True))(p, x)
        d_p, want_dx = jax.jit(lambda p, x, dy: jax.vjp(
            lambda p, x: ref.layer(C, p, x, chosen), p, x)[1](dy))(p, x, dy)
    return {"y": (y, want_y), "dx": (dx, want_dx), "ids": (ids, own),
            "stats": stats, "grads": ({**d_mats, **d_small}, d_p)}


LAYER_TENSORS = [(layer, name) for layer in (0, 1, 2)
                 for name in CFG.layer_shapes(layer) if name != "router_bias"]


@pytest.fixture(scope="module")
def exact_layers():
    saved = lm.BF16, delta.BF16
    lm.BF16 = delta.BF16 = jnp.float32
    try:
        return {layer: _layer_both(layer, jnp.float32) for layer in (0, 1, 2)}
    finally:
        lm.BF16, delta.BF16 = saved


@pytest.fixture(scope="module")
def rounded_layers():
    return {layer: _layer_both(layer, jnp.bfloat16) for layer in (0, 1, 2)}


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_layer_s_result_is_the_reference_s(layer, exact_layers):
    both = exact_layers[layer]
    assert _relative(*both["y"]) < EXACT
    assert _relative(*both["dx"]) < EXACT
    if KINDS[layer][2]:
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


def test_a_delta_layer_s_stats_end_in_the_deep_count(exact_layers):
    # [held, fullest] + the router's outputs + the deep triples
    assert exact_layers[1]["stats"].shape == (2 + 8 + 1,)
    assert exact_layers[0]["stats"].shape == (2 + 1,)
    assert exact_layers[2]["stats"].shape == (2 + 8,)


# -- causality, positions ------------------------------------------------------------

def _programs(layer):
    kind = KINDS[layer]
    return (ps_train.forward_program(CFG, *kind[:2], T, kind[2],
                                     attention=kind[3]),
            ps_train.backward_program(CFG, *kind[:2], T, kind[2],
                                      attention=kind[3]))


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_a_token_changes_nothing_before_it_nor_in_the_other_sequence(layer):
    rng = np.random.default_rng(7)
    p = _draw(CFG.layer_shapes(layer), rng)
    mats, small = _split(p, layer)
    forward, _ = _programs(layer)
    x = jnp.asarray(rng.normal(size=(B, T, CFG.hidden)), jnp.float32)
    at = 13
    moved = x.at[1, at].add(1.0)
    y, other = (np.asarray(forward(mats, small, a)[0]) for a in (x, moved))
    assert np.array_equal(y[0], other[0])           # the other sequence
    assert np.array_equal(y[1, :at], other[1, :at])     # the positions before
    assert not np.allclose(y[1, at:], other[1, at:])
    if KINDS[layer][3] == "kda":    # the scan carries it to the end
        assert not np.allclose(y[1, -1], other[1, -1])


@pytest.mark.parametrize("layer", [1, 2])
def test_positions_change_nothing(layer):
    rng = np.random.default_rng(8)
    p = _draw(CFG.layer_shapes(layer), rng)
    mats, small = _split(p, layer, jnp.bfloat16)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    _, _, sparse, kind = KINDS[layer]
    results = [jax.jit(lambda x, pos=pos: lm.layer_forward(
        CFG, False, 0, mats, small, x, pos, sparse, kind)[0])(x)
               for pos in (None, np.arange(T) + 1000, np.arange(T)[::-1])]
    assert np.array_equal(results[0], results[1])
    assert np.array_equal(results[0], results[2])
    assert CFG.rotary(0, 0) is False


# -- the share ------------------------------------------------------------------------

def test_the_shares_add_up_to_the_uncut_layer(float32_products):
    """A sparse delta layer's output with experts 0-3 held and with 4-7
    held: the two routed parts, with attention, convolutions and shared
    expert counted once, are the reference's layer holding all eight."""
    import dataclasses
    rng = np.random.default_rng(9)
    whole_cfg = dataclasses.replace(CFG, experts_held=(0, 8))
    p = _draw(whole_cfg.layer_shapes(1), rng)
    x = jnp.asarray(rng.normal(size=(T, CFG.hidden)), jnp.float32)
    c = dict(C, held=8, first_held=0)

    def added_up(p, x):
        mats, small = _split(p, 1)
        a = x + delta.attention_vjp(CFG, mats, lm._zeros_like_f32(mats),
                                    small, x)[0]
        h = lm.rmsnorm(a, p["norm_ffn"], CFG.eps)
        ids, weights = lm.route(CFG, p["router"], h, p["router_bias"])
        shared = lm.gated_mlp(CFG, p, lm._zeros_like_f32(
            {n: p[n] for n in lm.SHARED}), lm.SHARED, h)
        total = a + shared
        for first in (0, 4):
            cut = dataclasses.replace(CFG, experts_held=(first, 4))
            part = {n: p[n].reshape(8, -1, p[n].shape[-1])[first:first + 4]
                    .reshape(-1, p[n].shape[-1]) for n in lm.DENSE}
            out, sizes = lm.routed_experts(
                cut, part, lm._zeros_like_f32(part), h, ids, weights)
            total = total + out
        return total

    with ref.PRECISION:
        want = jax.jit(lambda p, x: ref.layer(c, p, x))(p, x)
        total = jax.jit(added_up)(p, x)
    assert _relative(total, want) < EXACT


# -- latent.py's older caller ------------------------------------------------------------

def test_xing_through_latent_py_gives_what_it_gave(float32_products):
    """The third family's latent attention (a query latent, YaRN's turn)
    through the module that now also serves a layer without either: its
    five matrices and three norms by name, and the reference's numbers."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4-29b-a4b-l5.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearsal"))
    cfg = lm.LMConfig.from_dict(config)
    c = ref_mla.sizes(config)
    assert latent.names(cfg) == (lm.MLA_MATRICES, latent.NORMS)
    assert latent.names(CFG) == (lm.MLA_DIRECT, ("norm_attn", "norm_kv_a"))
    rng = np.random.default_rng(10)
    shapes = cfg.layer_shapes(1)
    p = {n: jnp.asarray(1 + 0.1 * rng.normal(size=s) if len(s) == 1
                        else rng.normal(0, 0.08, s), jnp.float32)
         for n, s in shapes.items()}
    u = jnp.asarray(rng.normal(size=(T, cfg.hidden)), jnp.float32)
    mats = {n: p[n] for n in lm.MLA_MATRICES}
    with ref_mla.PRECISION:
        got = jax.jit(lambda mats, p, u: latent.attention_vjp(
            cfg, mats, lm._zeros_like_f32(mats), p, u)[0])(mats, p, u)
        want = jax.jit(lambda p, u: ref_mla.attention_f(c, p, u))(p, u)
    assert _relative(got, want) < EXACT


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
        chosen, stats = {}, {}
        layers = iter(range(CFG.n_layers))
        for kind, program in dict(trainer._forward).items():
            def spy(*args, _program=program, _sparse=kind[2]):
                out = _program(*args)
                i = next(layers)
                chosen[i] = out[3] if _sparse else None
                stats[i] = np.asarray(out[1])
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
    assert biases == [f"layer{i}.router_bias" for i in (1, 2, 3)]
    for name, (w, state) in run["got"].items():
        if name in biases:
            assert not state, name      # no rule's state: the plain rule
        else:
            assert state and int(state[2]) == 1, name
    assert CFG.parameters() == sum(w.size for w, _ in run["got"].values())


@pytest.mark.parametrize("layer", [1, 2, 3])
def test_a_bias_moves_by_its_rate_against_the_load(run, layer):
    name = f"layer{layer}.router_bias"
    load = np.bincount(np.asarray(run["chosen"][layer]).ravel(), minlength=8)
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


def test_a_delta_layer_s_decay_starts_from_its_own_draw(run):
    a_log = np.asarray(run["start"]["layer0.a_log"])
    dt_bias = np.asarray(run["start"]["layer0.dt_bias"])
    assert np.all((a_log >= 0) & (a_log <= np.log(16))) and a_log.std() > 0
    dt = np.log1p(np.exp(dt_bias))
    assert np.all((dt > 0.9e-3) & (dt < 0.11)) and dt.std() > 0
    conv = np.asarray(run["start"]["layer1.conv_q"])
    assert conv.shape == (32, 4) and np.abs(conv).max() <= 0.5
    assert np.all(np.asarray(run["start"]["layer1.norm_o"]) == 1)
    assert not np.array_equal(a_log, np.asarray(run["start"]["layer1.a_log"]))


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def counted(name):
        return after.get(name, {"count": 0})["count"] \
            - before.get(name, {"count": 0})["count"]

    assert counted("LM_STEP") == 1 and counted("LM_TOKENS") == B * T
    stats = run["stats"]
    assert [stats[i].shape for i in range(4)] == [
        (B, 3), (B, 11), (B, 10), (B, 11)]
    assert counted("LM_ROUTER_BIAS_ADDS") == 3
    assert counted("LM_HELD_ASSIGNMENTS") == sum(
        int(s[:, 0].sum()) for s in stats.values()) > 0
    # three delta layers, one chunk a sequence at this length
    assert counted("LM_KDA_TOKENS") == 3 * B * T
    assert counted("LM_KDA_CHUNKS") == 3 * B * (T // delta.chunk_of(T))
    assert counted("LM_KDA_DECAY_CHANNELS") == 3 * B * 4 * 8
    assert counted("LM_KDA_DECAY_DEEP") == sum(
        int(stats[i][:, -1].sum()) for i in (0, 1, 3))
    fullest = sum(int(stats[i][:, 2:10].sum(0).max()) for i in (1, 2, 3))
    assert counted("LM_ROUTER_LOAD_MAX") == fullest
    # one a delta layer a sequence, by the test delta.scan chose by: the
    # CPU takes the jax.numpy scan
    assert counted("LM_KDA_SCAN_PLAIN") == 3 * B
    assert counted("LM_KDA_SCAN_KERNEL") == 0
    # the one latent layer's sequences: no turn, so ``latent.inputs``' chain
    assert counted("LM_ATTN_PASS_FUSED") == 0
    assert counted("LM_ATTN_PASS_PLAIN") == B

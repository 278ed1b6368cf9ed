"""The fifth family through multiverso_tpu/models/lm (attention over the
keys a learned indexer selects, sparse.py) against the plain reference
(benchmark/reference/lm_sparse_step.py) at small widths on the CPU: the
exact search and its tie rule, the kernels' bodies in interpret mode (the
selection's, the divergence's), the sectioned rotary, a layer's forward
pass and every gradient (the indexer's among them), which loss reaches
which tensor, the causal layer below ``topk``, the expert shares, and a
step through the server's tables and Adam; and that the four older
configurations are described as they were."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.reference import lm_sparse_step as ref
from multiverso_tpu.models.lm import (LMConfig, PSLMTrainer, model as lm,
                                      sparse, sparse_kernels, zipf_tokens)
from multiverso_tpu.util import dashboard

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = {
    "model_type": "KeyeVL2", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "hidden_act": "silu",
    "router_outputs": 16, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "norm_topk_prob": True, "vocab_size": 53,
    "num_hidden_layers": 2, "rope_theta": 10000.0, "rms_norm_eps": 1e-6,
    "loss_block": 16,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 32,
                  "q_chunk_size": 32, "topk": 16}}
T = 96      # so that 80 of 96 queries select
CFG = LMConfig.from_dict(CONFIG)
C = ref.sizes(CONFIG)
POS = np.tile(np.arange(T), (3, 1))
ROPE = CFG.rotary(1, 0)
# Relative L2 error of a gradient at these widths, bfloat16 products
# against float32, read over four seeds: matrices and norms up to 7e-3,
# the indexer's five up to 6e-2 (index heads of 8 lanes: little of the
# rounding averages away), both losses under 1e-3.
LIMIT, INDEX_LIMIT = 3e-2, 0.15
INDEXER = sparse.INDEX_MATRICES + sparse.INDEX_SMALL


def _relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _layer(seed, cfg=CFG, t=T):
    rng = np.random.default_rng(seed)
    shapes = cfg.layer_shapes()
    p = {}
    for n, s in shapes.items():
        if len(s) == 2:
            p[n] = rng.normal(size=s) * 0.1
        else:
            p[n] = rng.normal(size=s) * 0.2 + (not n.endswith("_b"))
    p = {n: jnp.asarray(v, jnp.float32) for n, v in p.items()}
    mats = {n: p[n].astype(jnp.bfloat16) for n in cfg.matrices()}
    small = {n: p[n] for n in p if n not in mats}
    # the reference reads the matrices as the program's bfloat16 copies
    exact = {n: mats[n].astype(jnp.float32) if n in mats else p[n] for n in p}
    x = jnp.asarray(rng.normal(size=(t, cfg.hidden)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(t, cfg.hidden)), jnp.float32)
    return mats, small, exact, x, dy


# -- the description ------------------------------------------------------------

def test_the_fifth_family_is_told_by_its_keys():
    assert CFG.selection == "topk_indexer"
    assert (CFG.index_heads, CFG.index_dim, CFG.index_topk,
            CFG.index_tile) == (4, 8, 16, 32)
    assert CFG.objective == "next_token" and CFG.qk_norm
    assert CFG.one_ffn_input and CFG.scoring == "softmax"
    assert ROPE.sections == (2, 3, 3) and ROPE.lanes == 16
    assert CFG.layer_kinds() == ((1, 0), (1, 0))
    assert CFG.layer_mask(0, T) == lm.Mask()        # no Mask kind of its own
    plain = dict(CONFIG)
    plain.pop("sa_config")
    assert LMConfig.from_dict(plain).selection == "none"


def test_a_layer_s_tensors():
    shapes = CFG.layer_shapes()
    assert list(shapes)[-5:] == list(INDEXER)
    assert shapes["wq_index"] == (64, 32) and shapes["wk_index"] == (64, 8)
    assert shapes["w_index"] == (64, 4)
    assert shapes["index_norm_g"] == shapes["index_norm_b"] == (8,)
    assert CFG.matrices()[-3:] == sparse.INDEX_MATRICES
    assert len(shapes) == 17
    plain = dataclasses.replace(CFG, selection="none")
    assert CFG.parameters() - plain.parameters() == 2 * (
        64 * 32 + 64 * 8 + 64 * 4 + 16)


def test_the_published_widths_count():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "keye-vl2-30b-a3b-lm.json")) as f:
        config = json.load(f)
    cfg = LMConfig.from_dict(config)
    indexer = sum(int(np.prod(s)) for s in sparse.shapes(cfg).values())
    assert indexer == 2048 * 1024 + 2048 * 64 + 2048 * 16 + 128 == 2261120
    layer = sum(int(np.prod(s)) for s in cfg.layer_shapes().values())
    assert layer == 19140864 + 2261120 + 75497472 == 96899456
    assert cfg.parameters() == config["parameters"]["total"] \
        == cfg.n_layers * layer + 77791232 + 2048
    assert (cfg.index_heads, cfg.index_dim, cfg.index_topk,
            cfg.index_tile) == (16, 64, 2048, 512)
    assert cfg.rotary(1, 0).sections == (16, 24, 24)


# what the four older configurations build their programs from: the layer
# kinds and each layer's tables, by name
OLDER = {
    "smallthinker-21ba3b-l4": (((0, 0), (1, 1), (1, 1), (1, 1)), 10),
    "sdar-30b-a3b-l6": (((1, 0),) * 6, 12),
    "xing4-29b-a4b-l5": (((1, 0, 0),) + ((1, 0, 1),) * 4, None),
    "laguna-xs2-33b-a3b-l5": (None, None)}


@pytest.mark.parametrize("name", sorted(OLDER))
def test_an_older_configuration_is_described_as_it_was(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        cfg = LMConfig.from_dict(json.load(f))
    kinds, tables = OLDER[name]
    assert cfg.selection == "none" and cfg.index_topk == 0
    if kinds is not None:
        assert cfg.layer_kinds() == kinds
    if tables is not None:
        assert all(len(cfg.layer_shapes(i)) == tables
                   for i in range(cfg.n_layers))
    for i in range(cfg.n_layers):
        assert not set(INDEXER) & set(cfg.layer_shapes(i))
        assert not set(INDEXER) & set(cfg.matrices(i))
    if name == "laguna-xs2-33b-a3b-l5":
        assert [k[3] for k in cfg.layer_kinds()] == list(cfg.heads_layout)
        assert not any(r.sections for r in cfg.rotary_kinds)


# -- the search -------------------------------------------------------------------

def _top_k_mask(scores, k):
    r, t = scores.shape
    top = jax.lax.top_k(scores, min(k, t))[1]
    return np.asarray(jnp.zeros((r, t), bool).at[
        jnp.arange(r)[:, None], top].set(True))


@pytest.mark.parametrize("rows, t, k", [(8, 64, 16), (4, 96, 16), (3, 33, 5),
                                        (5, 16, 16), (2, 8, 16), (6, 200, 1)])
@pytest.mark.parametrize("ties", [False, True])
def test_the_search_is_the_k_largest_ties_to_the_earlier_key(rows, t, k, ties):
    rng = np.random.default_rng(rows * t + k)
    scores = rng.normal(size=(rows, t)).astype(np.float32)
    if ties:        # a handful of values, zeros of both signs among them
        scores = np.round(scores) * np.float32(0.5)
        scores[0, ::3] = -0.0
    got = np.asarray(sparse.search(sparse.sortable(jnp.asarray(scores)), k))
    assert np.array_equal(got, _top_k_mask(jnp.asarray(scores), k))
    assert (got.sum(1) == min(k, t)).all()


def test_a_row_with_fewer_candidates_takes_them_all():
    scores = jnp.asarray(np.random.default_rng(0).normal(size=(T, T)),
                         jnp.float32)
    sel = np.asarray(sparse.select_block(CFG, scores, 0))
    assert np.array_equal(sel.sum(1), np.minimum(np.arange(T) + 1, 16))
    assert not np.triu(sel, 1).any()
    assert np.array_equal(sel[:16], np.tril(np.ones((16, T), bool)))


def test_sortable_keeps_the_order_of_floats():
    values = np.asarray([-np.inf, -3.5, -1e-30, -0.0, 0.0, 1e-30, 2.0,
                         np.inf], np.float32)
    keys = np.asarray(sparse.sortable(jnp.asarray(values)))
    assert (np.diff(keys.astype(np.int64)) > 0).all()
    assert keys.min() > sparse._LOWEST


@pytest.mark.parametrize("t, tile, k, ties", [
    (256, 128, 32, False), (256, 128, 32, True), (512, 128, 64, True),
    (256, 256, 300, False)])
def test_the_kernel_s_body_is_the_search(t, tile, k, ties):
    """sparse_kernels.select_tiles in interpret mode against
    ``index_scores`` + ``search``: the same selection, pair for pair."""
    rng = np.random.default_rng(t + k)
    qi = rng.normal(size=(t, 4, 8))
    ki = rng.normal(size=(t, 8))
    w = rng.normal(size=(t, 4))
    if ties:
        qi, ki, w = np.round(qi), np.round(ki), np.abs(np.round(w))
    qi, ki, w = (jnp.asarray(a, jnp.float32) for a in (qi, ki, w))
    got = sparse_kernels.select_tiles(qi, ki, w, topk=k, tile=tile,
                                      interpret=True)
    assert got.shape == (t // tile, t // tile, tile, tile)
    want = sparse.select_block(dataclasses.replace(CFG, index_topk=k),
                               sparse.index_scores(qi, ki, w), 0)
    assert np.array_equal(np.asarray(sparse._untiled(got != 0)),
                          np.asarray(want))


def _shapes_on_a_described_v5e():
    """``shape(*sizes, dtype=float32)`` on one chip of a described v5e;
    skips where no topology can be described."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler for it here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one = SingleDeviceSharding(topo.devices[0])
    return lambda *s, dtype=jnp.float32: jax.ShapeDtypeStruct(
        s, dtype, sharding=one)


def test_the_kernel_compiles_for_the_chip_at_the_published_widths():
    shape = _shapes_on_a_described_v5e()
    t = 16384
    compiled = jax.jit(lambda qi, ki, w: sparse_kernels.select_tiles(
        qi, ki, w, topk=2048, tile=512)).lower(
            shape(t, 16, 64), shape(t, 64), shape(t, 16)).compile()
    assert compiled.memory_analysis().output_size_in_bytes == t * t


# -- the divergence's kernel --------------------------------------------------------
# sparse_kernels.index_loss_tiles, interpreted, against ``index_loss_vjp``'s
# ``jax.numpy`` form (what runs here). The two round alike going INTO the
# products; the ``jax.numpy`` form's pull also rounds a block's d qI and
# d kI to bfloat16 (its operands' type), the kernel keeps float32: 2.4e-3
# and 1.8e-3 of their norms at these sizes, d w and the loss to float32's
# last digits.

LOSS_CASES = {      # t, tile, topk, tied scores, tiles emptied and filled
    "every_query_below_topk": (256, 128, 300, False, False),
    "most_queries_select": (256, 128, 32, False, False),
    "tied_scores": (512, 128, 64, True, False),
    "a_tile_empty_and_a_tile_full": (512, 128, 64, False, True),
    "two_query_blocks_a_tile": (1024, 512, 200, False, False)}
LOSS_LIMITS = {"loss": 1e-5, "d_qi": 8e-3, "d_ki": 8e-3, "d_w": 1e-5}


@functools.lru_cache(maxsize=None)
def _divergence_inputs(t, tile, topk, ties=False, crafted=False):
    rng = np.random.default_rng(0)
    qi = rng.normal(size=(t, 4, 8))
    ki = rng.normal(size=(t, 8))
    w = rng.normal(size=(t, 4))
    if ties:
        qi, ki, w = np.round(qi), np.round(ki), np.abs(np.round(w))
    qi, ki, w = (jnp.asarray(a, jnp.float32) for a in (qi, ki, w))
    tiles = np.array(sparse_kernels.select_tiles(
        qi, ki, w, topk=topk, tile=tile, interpret=True) != 0)
    if crafted:     # what an untrained indexer never gives
        tiles[2, 0] = tiles[3, 0] = False
        tiles[3, 1] = True
    q = jnp.asarray(rng.normal(size=(2, 2, t, 16)) * 0.25, jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(2, t, 16)), jnp.bfloat16)
    tiles = jnp.asarray(tiles)
    _, lse = sparse._dense_attention(q, k, jnp.zeros_like(k),
                                     sparse._untiled(tiles))
    return qi, ki, w, tiles, q, k, lse


def _both_forms(args):
    """``{loss, d_qi, d_ki, d_w: (the kernel's, the jax.numpy form's)}``,
    traced anew (whatever ``sparse.target_of`` is now)."""
    loss, grads = jax.jit(lambda *a: sparse_kernels.index_loss_tiles.__wrapped__(
        *a, interpret=True))(*args)
    want_loss, want = jax.jit(lambda *a: sparse.index_loss_vjp(*a))(*args)
    return dict(zip(LOSS_LIMITS, zip((loss,) + grads, (want_loss,) + want)))


@functools.lru_cache(maxsize=None)
def _divergence(case):
    return _both_forms(_divergence_inputs(*LOSS_CASES[case]))


def test_the_divergence_s_cases_hold_what_their_names_say():
    for case, (t, tile, topk, _, crafted) in LOSS_CASES.items():
        tiles = np.asarray(_divergence_inputs(*LOSS_CASES[case])[3])
        chosen = sparse._untiled(jnp.asarray(tiles)).sum(1)
        assert (chosen[:topk] == np.arange(1, min(topk, t) + 1)).all()
        assert (chosen >= 1).all()
        full, empty = tiles.all((2, 3)), ~tiles.any((2, 3))
        under = np.tril(np.ones(full.shape, bool), -1)
        if case == "every_query_below_topk":
            assert chosen.max() < topk and full[under].all()
        if crafted:
            assert empty[under].sum() == 2 and full[under].sum() == 1
        if case == "two_query_blocks_a_tile":
            assert tile // min(sparse_kernels.LOSS_ROWS, tile) == 2


@pytest.mark.parametrize("what", list(LOSS_LIMITS))
@pytest.mark.parametrize("case", list(LOSS_CASES))
def test_the_divergence_s_kernel_is_its_jax_numpy_form(case, what):
    got, want = _divergence(case)[what]
    assert got.shape == want.shape and got.dtype == want.dtype == jnp.float32
    assert np.isfinite(np.asarray(got)).all()
    error = abs(float(got) - float(want)) / float(want) if what == "loss" \
        else _relative(got, want)
    assert error < LOSS_LIMITS[what], (case, what, error)


def test_the_divergence_s_kernel_follows_target_of(monkeypatch):
    """benchmark/tools/lm_sparse_controls.py ``target_not_rescaled``
    replaces ``sparse.target_of``: the kernel calls it from its body, for
    the target and for the row's sum of it."""
    args = _divergence_inputs(*LOSS_CASES["most_queries_select"])
    plain = _both_forms(args)
    monkeypatch.setattr(sparse, "target_of",
                        lambda probabilities, heads: probabilities)
    summed = _both_forms(args)
    for what, limit in LOSS_LIMITS.items():
        got, want = summed[what]
        assert _relative(got, want) < 10 * limit, what
    # the heads' probabilities sum to 4 over a row's keys, not to 1
    assert float(summed["loss"][0]) > 4 * float(plain["loss"][0])
    assert _relative(summed["d_w"][0], plain["d_w"][0]) > 1.0


def test_the_kernel_is_taken_by_what_the_code_sees(monkeypatch):
    """On a TPU at a tile of whole lanes ``index_loss_vjp`` is the kernel,
    under no flag; at any other tile the ``jax.numpy`` form."""
    args = _divergence_inputs(*LOSS_CASES["most_queries_select"])
    want = sparse.index_loss_vjp(*args)
    calls, interpreted = [], sparse_kernels.index_loss_tiles

    def kernel(*a):
        calls.append(a)
        return interpreted(*a, interpret=True)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sparse_kernels, "index_loss_tiles", kernel)
    loss, _ = sparse.index_loss_vjp(*args)
    assert len(calls) == 1
    assert abs(float(loss) - float(want[0])) < 1e-5 * float(want[0])
    sparse.index_loss_vjp(*_divergence_inputs(96, 32, 16))
    assert len(calls) == 1


def test_the_divergence_s_kernel_compiles_for_the_chip_at_the_published_widths():
    shape = _shapes_on_a_described_v5e()
    t, bf16 = 16384, jnp.bfloat16
    compiled = jax.jit(lambda *a: sparse_kernels.index_loss_tiles(*a)).lower(
        shape(t, 16, 64), shape(t, 64), shape(t, 16),
        shape(32, 32, 512, 512, dtype=jnp.bool_),
        shape(4, 8, t, 128, dtype=bf16), shape(4, t, 128, dtype=bf16),
        shape(4, 8, t)).compile()
    # L_I, d qI, d kI, d w: float32, and no [T, T] float array beside them
    assert compiled.memory_analysis().output_size_in_bytes < 4 * t * (
        16 * 64 + 64 + 16) + 4096
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * t * t // 2


# -- the sectioned rotary ------------------------------------------------------

def test_three_equal_rows_are_the_one_row_rotary():
    x = jnp.asarray(np.random.default_rng(1).normal(size=(T, 3, 16)),
                    jnp.float32)
    one = lm._rotary(x, 1e4, np.arange(T))
    three = lm._rotary(x, 1e4, POS, sections=(2, 3, 3))
    assert np.array_equal(np.asarray(one), np.asarray(three))


@pytest.mark.parametrize("sections", [(2, 3, 3), (8, 0, 0), (1, 1, 6)])
def test_three_different_rows_match_the_reference(sections):
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(T, 3, 16)), jnp.float32)
    pos = np.stack([np.arange(T), rng.integers(0, 40, T),
                    rng.integers(0, 40, T)])
    got = lm._rotary(x, 1e4, pos, sections=sections)
    want = ref.rotary(x, jnp.asarray(pos), 1e4, sections)
    assert _relative(got, want) < 1e-5
    if sections[0] < 8:
        assert _relative(got, lm._rotary(x, 1e4, pos[0])) > 1e-2


# -- a layer against the reference ------------------------------------------------

@pytest.fixture(scope="module", params=[0, 1])
def layer(request):
    mats, small, exact, x, dy = _layer(request.param)
    y, stats, ids = jax.jit(lambda m, s, x: lm.layer_forward(
        CFG, ROPE, 0, m, s, x, POS))(mats, small, x)
    tiles, counts, index_inputs = jax.jit(
        lambda m, s, x: sparse.selection_of(CFG, m, s, x, POS))(mats, small,
                                                                 x)
    sel = sparse._untiled(tiles)
    grads = jax.jit(lambda m, s, x, dy: lm.layer_grads(
        CFG, ROPE, 0, m, s, x, dy, POS))
    dx, d_mats, d_small, inner = grads(mats, small, x, dy)
    zero = grads(mats, small, x, jnp.zeros_like(dy))
    with ref.PRECISION:
        want_y, want_inner, own_ids, lacks = ref.layer(
            C, exact, x, jnp.asarray(POS), ids, sel, own=True,
            program=index_inputs)

        def both(p, x):
            return ref.layer(C, p, x, jnp.asarray(POS), ids, sel)

        _, pull = jax.vjp(both, exact, x)
        want_p, want_dx = pull((dy, jnp.ones(())))
        from_ce, _ = pull((dy, jnp.zeros(())))
        from_inner, dx_inner = pull((jnp.zeros_like(dy), jnp.ones(())))
    return dict(y=y, stats=stats, ids=ids, sel=sel, counts=counts, dx=dx,
                grads={**d_mats, **d_small}, inner=inner, zero=zero,
                want_y=want_y, want_inner=want_inner, own_ids=own_ids,
                lacks=lacks, want_p=want_p, want_dx=want_dx, from_ce=from_ce,
                from_inner=from_inner, dx_inner=dx_inner)


def test_the_forward_pass_and_both_losses_inputs(layer):
    assert _relative(layer["y"], layer["want_y"]) < 5e-3
    assert abs(float(layer["inner"]) - float(layer["want_inner"])) \
        < 2e-3 * float(layer["want_inner"])
    assert _relative(layer["dx"], layer["want_dx"]) < LIMIT
    assert np.array_equal(np.sort(np.asarray(layer["ids"]), -1),
                          np.sort(np.asarray(layer["own_ids"]), -1))


def test_the_selection_is_the_reference_s_own_but_for_near_ties(layer):
    """And exactly the reference's top-k of the program's own index
    inputs, scored as the guarantees state."""
    choices, lacking, inexact = (int(v) for v in layer["lacks"])
    assert choices == int(layer["sel"].sum()) == 16 * 17 // 2 + 80 * 16
    assert lacking <= 0.01 * choices
    assert inexact == 0


def test_what_the_forward_program_counts(layer):
    stats, counts = np.asarray(layer["stats"]), np.asarray(layer["counts"])
    assert np.array_equal(stats[-sparse.COUNTS:], counts)
    assert list(counts) == [16 * 17 // 2 + 80 * 16, T * (T + 1) // 2, 6, 6]
    assert stats.shape == (2 + CFG.n_experts + sparse.COUNTS,)


@pytest.mark.parametrize("name", list(CFG.layer_shapes()))
def test_a_tensor_s_gradient_is_the_reference_s(layer, name):
    want = layer["want_p"][name]
    got = layer["grads"][name].reshape(want.shape)
    assert _relative(got, want) < (INDEX_LIMIT if name in INDEXER else LIMIT)


@pytest.mark.parametrize("name", list(CFG.layer_shapes()))
def test_each_loss_reaches_its_own_tensors_alone(layer, name):
    """In the reference by ``jax.vjp`` with one loss's cotangent at a
    time; in the program by a zero ``dy``: the indexer's gradients are
    what they were, every other is zero."""
    _, d_mats, d_small, inner = layer["zero"]
    without_ce = {**d_mats, **d_small}[name]
    if name in INDEXER:
        assert not np.asarray(layer["from_ce"][name]).any()
        assert np.array_equal(np.asarray(without_ce),
                              np.asarray(layer["grads"][name]))
        assert np.asarray(without_ce).any()
    else:
        assert not np.asarray(layer["from_inner"][name]).any()
        assert not np.asarray(without_ce).any()
    assert float(inner) == float(layer["inner"])


def test_the_inner_loss_sends_the_layer_s_input_nothing(layer):
    assert not np.asarray(layer["dx_inner"]).any()
    assert not np.asarray(layer["zero"][0]).any()


@pytest.mark.parametrize("t", [8, 16])
def test_below_topk_the_layer_is_the_causal_layer(t):
    """No query has more than ``topk`` keys before it: the selection is
    the causal mask and the layer gives what the same block without a
    selection gives, forward and backward."""
    mats, small, _, x, dy = _layer(5, t=t)
    pos = POS[:, :t]
    plain = dataclasses.replace(CFG, selection="none")
    plain_mats = {n: mats[n] for n in plain.matrices()}
    plain_small = {n: v for n, v in small.items()
                   if n not in sparse.INDEX_SMALL}
    y, stats, ids = lm.layer_forward(CFG, ROPE, 0, mats, small, x, pos)
    want_y, want_stats, want_ids = lm.layer_forward(
        plain, ROPE, 0, plain_mats, plain_small, x, pos)
    assert _relative(y, want_y) < 1e-6
    assert np.array_equal(np.asarray(ids), np.asarray(want_ids))
    assert list(np.asarray(stats[-4:-2])) == [t * (t + 1) // 2] * 2
    dx, d_mats, _, _ = lm.layer_grads(CFG, ROPE, 0, mats, small, x, dy, pos)
    want_dx, want_mats, _ = lm.layer_grads(plain, ROPE, 0, plain_mats,
                                           plain_small, x, dy, pos)
    assert _relative(dx, want_dx) < 1e-5
    for n in plain.matrices():
        assert _relative(d_mats[n], want_mats[n]) < 1e-5


def test_above_topk_the_layer_is_not_the_causal_layer():
    mats, small, _, x, _ = _layer(5)
    plain = dataclasses.replace(CFG, selection="none")
    y = lm.layer_forward(CFG, ROPE, 0, mats, small, x, POS)[0]
    dense = lm.layer_forward(
        plain, ROPE, 0, {n: mats[n] for n in plain.matrices()},
        {n: v for n, v in small.items() if n not in sparse.INDEX_SMALL}, x,
        POS)[0]
    assert _relative(y, dense) > 1e-2


# -- the share -----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1])
def test_the_expert_shares_add_up_to_the_uncut_layer(seed):
    """Four chips with four of the sixteen experts each: attention and
    indexer counted once, the shares' experts' parts added, give the
    uncut reference's layer."""
    uncut = dict(CONFIG, num_experts=16)
    mats, small, exact, x, _ = _layer(seed, LMConfig.from_dict(uncut))
    # both choices are the whole layer's, the same on every chip: given
    ids = lm.layer_forward(LMConfig.from_dict(uncut), ROPE, 0, mats, small,
                           x, POS)[2]
    sel = sparse._untiled(sparse.selection_of(CFG, mats, small, x, POS)[0])
    with ref.PRECISION:
        want, want_inner = ref.layer(ref.sizes(uncut), exact, x,
                                     jnp.asarray(POS), ids, sel)
    total, inner = None, None
    for first in range(0, 16, 4):
        cfg = LMConfig.from_dict(dict(CONFIG, first_expert_held=first))
        rows = slice(first * 64, (first + 4) * 64)
        down = slice(first * 32, (first + 4) * 32)
        share = {**mats, "w_gate": mats["w_gate"][rows],
                 "w_up": mats["w_up"][rows], "w_down": mats["w_down"][down]}
        a = _attention_alone(cfg, share, small, x)
        y = lm.layer_forward(cfg, ROPE, 0, share, small, x, POS)[0]
        total = a + (y - a) if total is None else total + (y - a)
        inner = lm.layer_grads(cfg, ROPE, 0, share, small, x,
                               jnp.zeros_like(x), POS)[3]
    assert _relative(total, want) < 5e-3
    assert abs(float(inner) - float(want_inner)) < 2e-3 * float(want_inner)


def _attention_alone(cfg, mats, small, x):
    """``a = x + Attn(..)`` of the layer: what every share computes
    alike."""
    sinks = lm._zeros_like_f32(mats)
    q, k, v = lm.attention_inputs(cfg, ROPE, mats, sinks,
                                  lm._attention_norms(cfg, small), x, POS)
    tiles, _, _ = sparse.selection_of(cfg, mats, small, x, POS)
    o, _, _ = sparse.attention_vjp(q, k, v, tiles)
    return lm.attention_output(cfg, mats, sinks, x, o)


# -- the attention kernel under a dynamic mask ---------------------------------------
# On a TPU ``sparse.attention_vjp`` calls the library's splash kernel
# through its PRIVATE forward and backward rules, and an untrained indexer
# leaves every tile live: these pin what the call relies on and run the
# tables for empty, mixed and full tiles, the kernel interpreted.

def test_the_library_kernels_private_rules_take_what_the_call_gives():
    import inspect
    kernel, mask_info = sparse._splash_library()
    static = list(sparse._splash_static(128))
    forward = list(inspect.signature(
        kernel._splash_attention_fwd).parameters)
    assert forward == ["fwd_mask_info", "dq_mask_info", "dkv_mask_info", "q",
                       "k", "v", "segment_ids", "sinks"] + static
    # the backward rule takes the same eight by PLACE (``how.values()``)
    assert list(inspect.signature(
        kernel._splash_attention_bwd).parameters) == static + ["res", "do"]
    assert mask_info.MaskInfo._fields == (
        "data_next", "mask_next", "block_mask", "partial_mask_blocks",
        "q_sequence", "is_dynamic_mask")


@pytest.fixture(scope="module")
def tiles_errors():
    from benchmark.tools import lm_sparse_controls as controls
    return controls.attention_tiles(t=512, tile=128, interpret=True), \
        controls.TILE_LIMITS


@pytest.mark.parametrize("what", ["o", "lse", "dq", "dk", "dv"])
def test_the_kernel_over_empty_mixed_and_full_tiles_gives_the_dense_sums(
        tiles_errors, what):
    """``lse`` among them: the forward rule's residual that
    ``attention_vjp`` takes for the row logsumexp (``res[6]``) is one."""
    errors, limits = tiles_errors
    assert errors[what] <= limits[what], errors


def test_the_mask_tables_say_which_tiles_are_empty_mixed_and_full():
    tile = 128
    tiles = np.array(sparse._tiled(jnp.tril(jnp.ones((512, 512), bool)),
                                   tile))
    tiles[2, 0] = tiles[3, 0] = False
    tiles[3, 1, 5, 7] = False
    forward, dkv = sparse._mask_infos(jnp.asarray(tiles))
    assert np.asarray(forward.block_mask)[0].tolist() == [
        [1, 0, 0, 0], [2, 1, 0, 0], [0, 2, 1, 0], [0, 1, 2, 1]]
    assert np.array_equal(forward.block_mask, dkv.block_mask)
    # a mixed tile's place among the tiles under the diagonal, row by row
    assert np.asarray(forward.mask_next)[0].tolist() == [
        [0, 0, 0, 0], [0, 2, 0, 0], [0, 0, 5, 0], [0, 7, 0, 9]]
    assert forward.partial_mask_blocks.shape == (10, tile, tile)
    assert np.array_equal(forward.partial_mask_blocks[7], tiles[3, 1])
    assert np.array_equal(dkv.partial_mask_blocks[7], tiles[3, 1].T)
    # the tile to fetch: its own key (dkv: query) tile, 0 where none is
    assert np.asarray(forward.data_next)[0].tolist() == [
        [0, 0, 0, 0], [0, 1, 0, 0], [0, 1, 2, 0], [0, 1, 2, 3]]
    assert np.asarray(dkv.data_next)[0].tolist() == [
        [0, 0, 0, 0], [1, 1, 0, 0], [0, 2, 2, 0], [0, 3, 3, 3]]


# -- a step through the tables ------------------------------------------------------

B, STEPS, LR = 1, 2, 3e-4


@pytest.fixture(scope="module")
def run():
    from multiverso_tpu.util import configure
    mv.init(["-updater_type=adam"])
    try:
        trainer = PSLMTrainer(CFG, T, B, seed=3, lr=LR)
        tables = trainer.tables()
        start = {n: jnp.asarray(t.get_device()) for n, t in tables.items()}
        adds = {n: 0 for n in tables}
        for name, table in tables.items():
            method = "add_rows_async" if table is trainer.embedding \
                else "add_async"
            send = getattr(table, method)

            def counted(*args, _name=name, _send=send):
                adds[_name] += 1
                return _send(*args)

            setattr(table, method, counted)
        before = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        key = jax.random.PRNGKey(5)
        batches = [zipf_tokens(jax.random.fold_in(key, i), (B, T + 1),
                               CFG.vocab) for i in range(STEPS)]
        loss = float(trainer.step(batches[0]))
        inner = float(trainer.last_inner_loss)
        first_adds = dict(adds)
        trainer.step(batches[1])
        trainer.sync()
        trainer.flush_stats()
        after = dashboard.metrics_snapshot(max_samples=0)["monitors"]
        from tests.test_lm_trainer import _as_reference
        with ref.PRECISION:
            want = ref.step_losses(C, _as_reference(start), batches[0])
        yield dict(trainer=trainer, tables=tables, loss=loss, inner=inner,
                   want=want, adds=first_adds, counters=(before, after))
    finally:
        mv.shutdown()
        configure.reset_flags()


def test_thirty_seven_tables_under_adam(run):
    assert len(run["tables"]) == 2 * 17 + 3
    assert run["trainer"].cfg.parameters() == sum(
        int(np.prod(t.get_device().shape)) for t in run["tables"].values())


def test_both_losses_of_a_step_are_the_reference_s(run):
    ce, inner = (float(v) for v in run["want"])
    assert abs(run["loss"] - ce) < 2e-3 * ce
    # the reference selects for itself here: near-ties may differ
    assert abs(run["inner"] - inner) < 2e-2 * inner


def test_one_add_a_table_a_step(run):
    assert set(run["adds"].values()) == {1}


def test_what_a_step_counts(run):
    before, after = run["counters"]

    def counted(name):
        return after[name]["count"] - before.get(name, {"count": 0})["count"]

    layers = CFG.n_layers
    assert counted("LM_STEP") == STEPS
    assert counted("LM_SELECTED_PAIRS") == STEPS * layers * B * (
        16 * 17 // 2 + 80 * 16)
    assert counted("LM_CAUSAL_PAIRS") == STEPS * layers * B * T * (T + 1) // 2
    assert counted("LM_SELECT_TILES") == STEPS * layers * B * 6
    assert 0 < counted("LM_SELECT_TILES_LIVE") <= counted("LM_SELECT_TILES")
    assert counted("LM_TOKENS") == STEPS * B * T

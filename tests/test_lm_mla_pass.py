"""The one pass between latent attention's products and its kernel
(multiverso_tpu/models/lm/latent_kernels.py), interpreted on the CPU,
against the ``jax.numpy`` chain of ``latent.inputs`` that runs everywhere
but on a TPU and is the pass's definition: forward and pull at GLM's widths
(``192 | 64 | 256``), given positions, through the multi-token module's
layer; the rule that chooses the form (``latent.pass_fused``); that the two
configurations which share ``latent.inputs`` and bypass the pass build the
programs they built; and what the trainer counts."""

import dataclasses
import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from multiverso_tpu.models.lm import (LMConfig, PSLMTrainer, latent,
                                      latent_kernels, model as lm, mtp,
                                      ps_train)
from multiverso_tpu.util import dashboard
from tests.test_lm_glm import CONFIG as SMALL
from tests.test_lm_mixed import _rehearsal

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
F32, BF16 = jnp.float32, jnp.bfloat16
T, NOPE, ROPE, V = 1024, 192, 64, 256
THETA = 1000000.0
HOW = latent_kernels.Pass(NOPE, ROPE, V, (NOPE + ROPE) ** -0.5, BF16)
#: GLM's heads over tests/test_lm_glm.py's small products
GLM = LMConfig.from_dict({
    **SMALL, "hidden_size": 64, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": NOPE, "qk_rope_head_dim": ROPE, "v_head_dim": V})


@pytest.fixture(autouse=True)
def _interpreted(monkeypatch):
    monkeypatch.setattr(latent_kernels, "INTERPRET", True)


@pytest.fixture
def rule_as_on_a_tpu(monkeypatch):
    """``latent.pass_fused`` answers as it would on a TPU, and nothing else
    does: the attention proper and the experts keep their CPU forms."""
    real = latent.pass_fused

    def fused(cfg, t, rope=True):
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(jax, "default_backend", lambda: "tpu")
            return real(cfg, t, rope)

    monkeypatch.setattr(latent, "pass_fused", fused)


def _products(heads, seed=0):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.normal(size=(T, n)), F32) for n in (
        heads * (NOPE + ROPE), heads * (NOPE + V), ROPE))


def _chain(heads, pos, qf, kvf, k_r):
    """``latent.inputs``' lines after the products."""
    q, kv = qf.reshape(T, heads, NOPE + ROPE), kvf.reshape(T, heads, NOPE + V)
    q_r = lm._rotary(q[..., NOPE:], THETA, pos)
    k_r = lm._rotary(k_r[:, None, :], THETA, pos)
    q = jnp.concatenate([q[..., :NOPE], q_r], -1) * HOW.scale
    k = jnp.concatenate(
        [kv[..., :NOPE], jnp.broadcast_to(k_r, (T, heads, ROPE))], -1)
    return (q.astype(BF16).transpose(1, 0, 2)[:, None],
            k.astype(BF16).transpose(1, 0, 2),
            kv[..., NOPE:].astype(BF16).transpose(1, 0, 2))


def _pass(pos, qf, kvf, k_r):
    tables = tuple(jnp.asarray(table, F32) for table in lm.rotary_tables(
        T, ROPE, THETA, pos))
    # ``W_kvb``'s product comes rounded, as the chain rounds it
    return latent_kernels.heads_in(HOW, qf, kvf.astype(BF16), k_r, tables)


def _ties(got, want):
    """How many entries differ, each by no more than a step of bfloat16."""
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    off = got != want
    assert np.all(np.abs(got - want)[off] <= 2.0 ** -7 * np.abs(want)[off]
                  + 1e-6), "more than a rounding"
    return int(off.sum())


POSITIONS = {"rows": None, "given": (np.arange(T) * 7) % 4001}


@pytest.mark.parametrize("heads", [4, 20])
@pytest.mark.parametrize("pos", list(POSITIONS))
def test_the_pass_gives_the_chain_s_heads(heads, pos):
    """The same float32 products and sums, so the same bfloat16: every lane
    that is not turned (q's and k's first ``nope``, all of v) bit for bit,
    and the turned ones but for a counted handful of ties (XLA's CPU code
    contracts a turn's product and sum into one operation in one form and
    not the other; on the chip ``tools/attn_pass_bench.py`` counts 0)."""
    products = _products(heads)
    got, want = _pass(POSITIONS[pos], *products), _chain(
        heads, POSITIONS[pos], *products)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == BF16
    (q, k, v), (want_q, want_k, want_v) = (
        [np.asarray(a, np.float32) for a in heads_] for heads_ in (got, want))
    assert np.array_equal(v, want_v)
    assert np.array_equal(q[..., :NOPE], want_q[..., :NOPE])
    assert np.array_equal(k[..., :NOPE], want_k[..., :NOPE])
    assert _ties(q[..., NOPE:], want_q[..., NOPE:]) <= 1e-4 * q.size
    assert _ties(k[..., NOPE:], want_k[..., NOPE:]) <= 1e-4 * k.size
    # the shared key is one for all heads
    assert all(np.array_equal(k[0, :, NOPE:], k[h, :, NOPE:])
               for h in range(heads))


@pytest.mark.parametrize("heads", [4, 20])
@pytest.mark.parametrize("pos", list(POSITIONS))
def test_the_pull_gives_the_chain_s_cotangents(heads, pos):
    """``dq``, ``dk``, ``dv`` back to the two products' results, rounded to
    bfloat16 as ``mm``'s backward rule rounds them, and the shared key's
    lanes' cotangent, the sum over the heads, in float32."""
    products = _products(heads)
    rng = np.random.default_rng(1)
    laid, pull = jax.vjp(lambda *a: _pass(POSITIONS[pos], *a), *products)
    cotangents = tuple(jnp.asarray(rng.normal(size=h.shape), BF16)
                       for h in laid)
    got = pull(cotangents)
    want = jax.vjp(lambda *a: _chain(heads, POSITIONS[pos], *a),
                   *products)[1](cotangents)
    for g, w in zip(got[:2], want[:2]):
        assert g.shape == w.shape and g.dtype == F32
        # what the pass wrote is already the bfloat16 ``mm`` would make
        assert np.array_equal(np.asarray(g), np.asarray(g.astype(BF16), F32))
        assert _ties(g, w.astype(BF16)) <= 1e-4 * w.size
    # [dk_n | dv] is a copy
    assert np.array_equal(np.asarray(got[1]), np.asarray(want[1]))
    assert got[2].shape == want[2].shape == (T, ROPE)
    np.testing.assert_allclose(got[2], want[2], rtol=1e-5, atol=1e-5
                               * float(jnp.abs(want[2]).max()))


# -- through ``latent.inputs`` and the module's layer ------------------------

def _layer(sparse=1, seed=2):
    rng = np.random.default_rng(seed)
    shapes = GLM.layer_shapes(sparse)
    mats = {n: jnp.asarray(rng.normal(size=shapes[n]) * shapes[n][0] ** -0.5,
                           BF16) for n in GLM.matrices(sparse)}
    small = {n: jnp.asarray(
        rng.normal(size=s) * (0.1 if n.startswith("norm") else s[0] ** -0.5)
        + n.startswith("norm"), F32)
        for n, s in shapes.items() if n not in mats}
    x = jnp.asarray(rng.normal(size=(T, GLM.hidden)), F32)
    return mats, small, x


def _relative(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


@pytest.mark.parametrize("pos", list(POSITIONS))
def test_the_inputs_take_the_pass_at_given_positions(pos, rule_as_on_a_tpu):
    mats, small, x = _layer()
    _, norms = latent.names(GLM)

    def heads(x):
        return latent.inputs(GLM, mats, lm._zeros_like_f32(mats),
                             tuple(small[n] for n in norms), x,
                             POSITIONS[pos])

    assert latent.pass_fused(GLM, T)
    # (a trace is kept by the function's identity: a new one each time)
    assert "pallas_call" in str(jax.make_jaxpr(lambda x: heads(x))(x))
    got = heads(x)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent, "pass_fused", lambda cfg, t, rope=True: False)
        assert "pallas_call" not in str(
            jax.make_jaxpr(lambda x: heads(x))(x))
        want = heads(x)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype == BF16
        assert _ties(g, w) <= 1e-4 * w.size


def test_the_module_s_layer_takes_the_pass(rule_as_on_a_tpu):
    """``mtp._layer_vjp``'s result, ``dx`` and every gradient with the pass
    in the layer against the chain's: a few ties of the bfloat16 roundings
    apart."""
    mats, small, x = _layer()
    pos = np.arange(T) + 1      # the module's rows are the next positions'
    dy = jnp.asarray(np.random.default_rng(3).normal(size=x.shape), F32)

    def run():
        y, (stats, ids), pull = mtp._layer_vjp(GLM, mats, small, x, pos)
        return (y, ids) + tuple(pull(dy))

    assert "pallas_call" in str(jax.make_jaxpr(lambda: run())())
    got = run()
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(latent, "pass_fused", lambda cfg, t, rope=True: False)
        assert "pallas_call" not in str(jax.make_jaxpr(lambda: run())())
        want = run()
    assert np.array_equal(got[1], want[1])      # the same experts chosen
    leaves = jax.tree_util.tree_leaves
    assert [g.shape for g in leaves(got)] == [w.shape for w in leaves(want)]
    for g, w in zip(leaves((got[0], got[2:])), leaves((want[0], want[2:]))):
        assert _relative(g, w) < 2e-3


# -- the rule ----------------------------------------------------------------

def _heads(cfg, heads, nope, rope, v):
    return dataclasses.replace(
        cfg, n_heads=heads, heads_held=(0, heads), qk_nope_dim=nope,
        qk_rope_dim=rope, v_head_dim=v, head_dim=nope + rope)


@pytest.mark.parametrize("case, cfg, t, rope, backend, fused", [
    ("glm", _heads(GLM, 20, 192, 64, 256), 8192, True, "tpu", True),
    ("glm_s_module_rows", _heads(GLM, 20, 192, 64, 256), 8192, 1, "tpu",
     True),
    ("four_heads", GLM, 1024, True, "tpu", True),
    ("xing_s_192_lanes", _heads(GLM, 4, 128, 64, 128), 4096, True, "tpu",
     False),
    ("kimi_s_192_lanes_unturned", _heads(GLM, 32, 128, 64, 128), 8192, False,
     "tpu", False),
    ("a_layer_without_positions", _heads(GLM, 20, 192, 64, 256), 8192, False,
     "tpu", False),
    ("no_whole_blocks", _heads(GLM, 20, 192, 64, 256), 8192 + 256, True,
     "tpu", False),
    ("rehearsal_length", _heads(GLM, 20, 192, 64, 256), 64, True, "tpu",
     False),
    ("a_head_left_over", _heads(GLM, 3, 192, 64, 256), 8192, True, "tpu",
     False),
    ("v_of_no_whole_tile", _heads(GLM, 20, 192, 64, 192), 8192, True, "tpu",
     False),
    ("the_cpu", _heads(GLM, 20, 192, 64, 256), 8192, True, "cpu", False),
    ("a_gpu", _heads(GLM, 20, 192, 64, 256), 8192, True, "gpu", False)])
def test_the_rule_is_what_the_code_can_see(case, cfg, t, rope, backend,
                                           fused, monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert latent.pass_fused(cfg, t, rope) == fused
    assert latent.pass_name(cfg, t, rope) == (
        "LM_ATTN_PASS_FUSED" if fused else "LM_ATTN_PASS_PLAIN")


def test_a_replaced_rotary_takes_the_chain_that_calls_it(monkeypatch):
    """The checks' controls put their own ``_rotary`` in model.py's place
    (``lm_glm_controls.py no_rotary_key``): the pass would not call it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert latent.pass_fused(GLM, T)
    monkeypatch.setattr(lm, "_rotary", lambda x, theta, pos=None, inv=None: x)
    assert not latent.pass_fused(GLM, T)
    mats, small, x = _layer()
    _, norms = latent.names(GLM)
    jaxpr = jax.make_jaxpr(lambda x: latent.inputs(
        GLM, mats, lm._zeros_like_f32(mats),
        tuple(small[n] for n in norms), x))(x)
    assert "pallas_call" not in str(jaxpr)


def test_the_published_widths_are_a_pair_of_heads_a_step():
    how = latent._pass(_file_config("glm47-flash-30b-a3b-l5"))
    assert (how.nope, how.rope, how.v) == (192, 64, 256)
    assert (how.d, how.wide, how.together) == (256, 128, 2)
    assert how.scale == 256 ** -0.5 and how.dtype == BF16
    # a head whose two products are whole tiles goes alone
    assert latent_kernels.Pass(128, 128, 128, 1.0, BF16).together == 1


# -- who shares ``latent.inputs`` and bypasses the pass ----------------------

# sha256 (16 hex digits) of ``lower(..).as_text()`` of each kind's forward
# and backward program at the configuration's rehearsal widths, made from
# the parent commit (ce7e096) by ``_digests`` under the same JAX
# (tests/test_lm_mixed.py's pattern: after a change that is MEANT to move
# them, run ``_digests`` on the parent and replace these). PR 60 MEANT to
# move kimi's two delta kinds: the solve inside a chunk is built by halves
# (delta.unit_lower_inverse); with the series put back in its place both
# programs lower to ce7e096's text to the byte (a8761706279d97be,
# b5ab187450d7bff8; 1821d80da19179f0, e8053f7d1b03b8fc), so these two pairs
# are the solve's and nothing else's. Its latent kind stands unmoved.
PARENT_TEXT = {
    ("xing4-29b-a4b-l5", "lm-ps-step-4k"): {
        (1, 0, 0): ("431bf4dbc52fa4dc", "186d4f0a9fc791f5"),
        (1, 0, 1): ("7a2a627f49c34e43", "a073891544c7dca7")},
    ("kimi-linear-48b-a3b-l5", "lm-ps-step-8k"): {
        (0, 0, 0, "kda"): ("cdd5847363f02634", "0842be3b972adbd9"),
        (0, 0, 1, "kda"): ("5a1831e91e86bd0f", "45f0a696ec938514"),
        (0, 0, 1, "mla"): ("f3dddf4d44b823f7", "7fc81dcbab08ab9d")}}


def _file_config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", f"{name}.json")) as f:
        return LMConfig.from_dict(json.load(f))


def _digests(config, traffic):
    cfg = LMConfig.from_dict(_rehearsal("configs", f"{config}.json"))
    sizes = _rehearsal("traffic", f"{traffic}.json")
    t, b = sizes["seq_len"], sizes["sequences_per_step"]
    kinds, out = cfg.layer_kinds(), {}
    for kind in sorted(set(kinds)):
        layer = kinds.index(kind)
        shapes = cfg.layer_shapes(layer)
        mats = {n: jnp.zeros(shapes[n], BF16) for n in cfg.matrices(layer)}
        small = {n: jnp.ones(s) for n, s in shapes.items() if n not in mats}
        x = jnp.ones((b, cfg.hc_mult * cfg.hidden, t)) \
            if cfg.residual == "mhc" else jnp.ones((b, t, cfg.hidden))
        how = {"attention": kind[3]} if cfg.attention_layout else {}
        texts = (
            ps_train.forward_program(cfg, *kind[:2], t, kind[2], **how).lower(
                {n: w.astype(F32) for n, w in mats.items()}, small,
                x).as_text(),
            ps_train.backward_program(cfg, *kind[:2], t, kind[2],
                                      **how).lower(
                mats, small, x, x).as_text())
        out[kind] = tuple(hashlib.sha256(text.encode()).hexdigest()[:16]
                          for text in texts)
    return out


@pytest.mark.parametrize("config,traffic", list(PARENT_TEXT))
def test_a_bypassing_family_s_programs_lower_to_the_parent_s_text(config,
                                                                   traffic):
    assert _digests(config, traffic) == PARENT_TEXT[(config, traffic)]


# -- the counters ------------------------------------------------------------

def _counted():
    monitors = dashboard.metrics_snapshot(max_samples=0)["monitors"]
    return [monitors.get(n, {"count": 0})["count"]
            for n in ("LM_ATTN_PASS_FUSED", "LM_ATTN_PASS_PLAIN")]


@pytest.mark.parametrize("config, t, module, backend, fused, plain", [
    # five layers and the module's, two sequences a step
    ("glm47-flash-30b-a3b-l5", 8192, True, "tpu", 12, 0),
    ("glm47-flash-30b-a3b-l5", 8192, True, "cpu", 0, 12),
    ("glm47-flash-30b-a3b-l5", 8192, False, "tpu", 10, 0),
    # 192-lane heads; one latent layer of the five, not turned
    ("xing4-29b-a4b-l5", 4096, False, "tpu", 0, 10),
    ("kimi-linear-48b-a3b-l5", 8192, False, "tpu", 0, 2)])
def test_the_trainer_counts_one_a_latent_layer_a_sequence(
        config, t, module, backend, fused, plain, monkeypatch):
    cfg = _file_config(config)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    trainer = PSLMTrainer.__new__(PSLMTrainer)
    trainer.cfg, trainer.T, trainer._heads = cfg, t, (1, 1)
    trainer._attn_pass = ps_train.attn_pass_names(cfg, t, module)
    trainer._attn_blocks = []
    layers = cfg.n_layers + module
    assert len(trainer._attn_pass) == layers
    trainer._sparse, trainer._experts_cap = [1] * layers, 1 << 30
    before = _counted()
    width = 3 if "kda" in cfg.attention_layout else 2
    trainer._count_stats(([np.zeros((2, width), int)] * layers, 5, 7))
    assert [a - b for a, b in zip(_counted(), before)] == [fused, plain]

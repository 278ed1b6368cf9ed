"""Multi-head latent attention (DeepSeek-V2/V3's), one chip's heads of it,
as the sublayer ``F`` of a layer on either residual (the streams',
streams.py, or the plain one's, ``model.layer_vjp``): for a sublayer's normed
input ``h`` [T, hidden]

    c_q          = RMSNorm(h W_qa; g_qa)                 [q_lora_rank]
    [q_n | q_r]  = c_q W_qb, a head                      [nope | rope]
    [c_kv | k_r] = h W_kva                               [kv_lora_rank | rope]
    [k_n | v]    = RMSNorm(c_kv; g_kva) W_kvb, a head    [nope | v]
    q_r, k_r     = rotary(q_r), rotary(k_r)   ``cfg.yarn``: YaRN's
                                              frequencies
                                              (``model.yarn_frequencies``);
                                              empty: ``rope_theta``'s own;
                                              a layer without positions
                                              (``rope`` false) turns neither;
                                              ``k_r`` is one for all heads
    score        = (q_n . k_n + q_r . k_r) (nope + rope)^-0.5 m^2, causal,
                   m = 0.1 mscale_all_dim ln(factor) + 1 under YaRN, else 1
    F(h)         = softmax(score) v, the heads side by side, W_o

Without a query latent (``q_lora_rank`` 0) ``[q_n | q_r] = h W_q``.

**The share.** ``cfg.heads_held = (first, count)`` of the ``n_heads``:
``W_qb``, ``W_kvb`` and ``W_o`` hold the held heads' columns and rows,
``W_qa``, ``W_kva`` and the two latent norms are whole on every chip. The
layer adds its own heads' part of ``W_o``'s sum; the shares add up to the
uncut attention (tests/test_lm_mla.py, eight of eight).

**The kernel.** A head's q and k are ``nope + rope`` wide and its v
``v_head_dim``, whatever the configuration says (192 beside 128, or 256
beside 256): the library's splash kernel takes them as they are (it compiles
for a v5e at both, tests/test_row_scatter_tpu_compile.py), each head a
group of its own since its keys are its own; elsewhere
``blockwise_attention``. No lane is padded.

**Between the products and the kernel.** On a TPU, at whole blocks of 512
tokens, where ``nope + rope`` and ``v`` are each whole 128-lane tiles and the
layer is turned (``pass_fused``: GLM's ``192 | 64 | 256``; heads of ``128 |
64 | 128`` and a layer without positions are not), the turn of a head's last
lanes and of the shared ``k_r``, the joins, the split of ``[k_n | v]``, the
scale, the rounding and the kernel's layout are ONE Pallas pass each way
(latent_kernels.py ``heads_in`` and its pull); everywhere else the
``jax.numpy`` chain of ``inputs``, which is the definition
(tests/test_lm_mla_pass.py). ``PSLMTrainer`` counts ``LM_ATTN_PASS_FUSED``
or ``LM_ATTN_PASS_PLAIN`` a layer a sequence by ``pass_name``.

Scopes: ``mv.lm.attn.mla`` (projections, latent norms, rotary, ``W_o``),
the attention proper under ``mv.lm.attn.mla.kernel``; the backward pass
under the same names (``attention_vjp`` differentiates the three parts
one by one, as ``model.layer_grads`` does).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from . import model as lm
from .model import LMConfig

SCOPE = "mv.lm.attn.mla"
MATRICES = lm.MLA_MATRICES
NORMS = ("norm_attn", "norm_q_a", "norm_kv_a")


def names(cfg: LMConfig):
    """``(matrices, norms)`` of the attention by name: with a query latent
    the five and the three, without one (``q_lora_rank`` 0) ``W_q`` alone
    before the key-value pair and no norm of a query latent."""
    if cfg.q_lora_rank:
        return MATRICES, NORMS
    return lm.MLA_DIRECT, (NORMS[0], NORMS[2])


def softmax_scale(cfg: LMConfig) -> float:
    """``head_dim^-0.5 m^2``; the rotary's own ``mscale / mscale_all_dim``
    is 1 when the two are equal, which ``inputs`` checks. Without YaRN
    ``head_dim^-0.5``."""
    if not cfg.yarn:
        return 1.0 / math.sqrt(cfg.head_dim)
    factor, all_dim = cfg.yarn[0], cfg.yarn[5]
    m = 0.1 * all_dim * math.log(factor) + 1.0 if factor > 1 else 1.0
    return m * m / math.sqrt(cfg.head_dim)


def _pass(cfg: LMConfig):
    from . import latent_kernels
    return latent_kernels.Pass(cfg.qk_nope_dim, cfg.qk_rope_dim,
                               cfg.v_head_dim, softmax_scale(cfg), lm.BF16)


def pass_fused(cfg: LMConfig, t: int, rope=True) -> bool:
    """Whether ``inputs`` takes the one pass of latent_kernels.py for a
    sequence of ``t`` positions through a layer that is turned or not
    (``rope``): on a TPU, where its kernels fit (whole blocks of tokens,
    ``nope + rope`` and ``v`` each whole lane tiles) and there is a turn
    for them to do: without one the scale, the rounding and the layout go
    into the products' own output fusions (``model.attention_pass_fused``).
    Everywhere else, and where a caller has put its own ``_rotary`` in
    model.py's place (the checks' controls do), it is the ``jax.numpy``
    chain of ``inputs``, which is the definition the kernels are held
    to."""
    if not (rope and lm._rotary is lm._ROTARY
            and jax.default_backend() == "tpu"):
        return False
    from . import latent_kernels        # Pallas, where it is wanted
    return latent_kernels.fits(t, cfg.n_heads_held, _pass(cfg))


def pass_name(cfg: LMConfig, t: int, rope=True) -> str:
    """The counter that a sequence of ``t`` positions through one latent
    layer (turned or not: ``rope``) adds one to: which form its ``inputs``
    took (``PSLMTrainer._count_stats``; ``model.attention_pass_name`` is
    the other attention's)."""
    return "LM_ATTN_PASS_FUSED" if pass_fused(cfg, t, rope) \
        else "LM_ATTN_PASS_PLAIN"


def inputs(cfg: LMConfig, mats, sinks, norms, u, pos=None, rope=True):
    """The sublayer's norm, both low-rank projections with their norms
    (the query's one product ``h W_q`` where there is no query latent),
    rotary positions (``rope``: a layer without them leaves ``q_r`` and
    ``k_r`` as they are), the scale: ``(q [heads, 1, T, nope + rope], k
    [heads, T, nope + rope], v [heads, T, v])`` bfloat16 for
    ``model.attention_core``, each head a group. ``norms`` is ``names``'."""
    assert not cfg.yarn or cfg.yarn[4] == cfg.yarn[5], \
        "mscale != mscale_all_dim"
    t, heads = u.shape[0], cfg.n_heads_held
    nope, rope_dim, latent = cfg.qk_nope_dim, cfg.qk_rope_dim, \
        cfg.kv_lora_rank
    g_attn, *g_q, g_kv = norms
    h = lm.rmsnorm(u, g_attn, cfg.eps)
    if cfg.q_lora_rank:
        c_q = lm.rmsnorm(lm.mm(h, mats["wq_a"], sinks["wq_a"]), g_q[0],
                         cfg.eps)
        q = lm.mm(c_q, mats["wq_b"], sinks["wq_b"])
    else:
        q = lm.mm(h, mats["wq"], sinks["wq"])
    q = q.reshape(t, heads, nope + rope_dim)
    kv_a = lm.mm(h, mats["wkv_a"], sinks["wkv_a"])
    c_kv = lm.rmsnorm(kv_a[:, :latent], g_kv, cfg.eps)
    kv = lm.mm(c_kv, mats["wkv_b"], sinks["wkv_b"]).reshape(
        t, heads, nope + cfg.v_head_dim)
    # YaRN's frequencies, or (None) ``rope_theta``'s own
    inv = lm.yarn_frequencies(cfg.rope_theta, rope_dim, *cfg.yarn[:4]) \
        if rope and cfg.yarn else None
    if pass_fused(cfg, t, rope):
        from . import latent_kernels
        tables = tuple(jnp.asarray(table, lm.F32) for table in
                       lm.rotary_tables(t, rope_dim, cfg.rope_theta, pos,
                                        inv))
        # the products' results as they left them (the two reshapes fold),
        # ``kv`` rounded as the chain rounds it: in the product's own fusion
        return latent_kernels.heads_in(
            _pass(cfg), q.reshape(t, -1), kv.reshape(t, -1).astype(lm.BF16),
            kv_a[:, latent:], tables)
    if rope:
        q_r = lm._rotary(q[..., nope:], cfg.rope_theta, pos, inv)
        k_r = lm._rotary(kv_a[:, None, latent:], cfg.rope_theta, pos, inv)
    else:
        q_r, k_r = q[..., nope:], kv_a[:, None, latent:]
    q = jnp.concatenate([q[..., :nope], q_r], -1) * softmax_scale(cfg)
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(k_r, (t, heads, rope_dim))], -1)
    return (q.astype(lm.BF16).transpose(1, 0, 2)[:, None],
            k.astype(lm.BF16).transpose(1, 0, 2),
            kv[..., nope:].astype(lm.BF16).transpose(1, 0, 2))


def core(q, k, v):
    """The causal attention proper."""
    return lm.attention_core(q, k, v, 0)


def output(cfg: LMConfig, mats, sinks, o):
    """The held heads' outputs [heads, 1, T, v] through their rows of the
    output projection: [T, hidden]."""
    t = o.shape[2]
    o = o[:, 0].transpose(1, 0, 2).reshape(t, -1)
    return lm.mm(o, mats["wo"], sinks["wo"])


def attention_vjp(cfg: LMConfig, mats, sinks, small, u, pos=None, rope=True):
    """``F(u)`` and what pulls a cotangent back through it: ``(v, pull)``,
    ``pull(dv) -> (du, matrix gradients, small gradients)``. The three
    parts are differentiated one by one so that each part's backward pass
    runs under the scope of its forward pass."""
    matrices, norms_named = names(cfg)
    first = {n: sinks[n] for n in matrices[:-1]}
    with jax.named_scope(SCOPE):
        (q, k, v), pull_inputs = jax.vjp(
            lambda s, norms, u: inputs(cfg, mats, s, norms, u, pos, rope),
            first, tuple(small[n] for n in norms_named), u)
    with jax.named_scope(SCOPE + ".kernel"):
        o, pull_core = jax.vjp(core, q, k, v)
    with jax.named_scope(SCOPE):
        out, pull_output = jax.vjp(
            lambda s, o: output(cfg, mats, {"wo": s}, o), sinks["wo"], o)

    def pull(d_out):
        with jax.named_scope(SCOPE):
            d_wo, do = pull_output(d_out)
        with jax.named_scope(SCOPE + ".kernel"):
            d_qkv = pull_core(do)
        with jax.named_scope(SCOPE):
            d_mats, d_norms, du = pull_inputs(d_qkv)
        return du, {**d_mats, "wo": d_wo}, dict(zip(norms_named, d_norms))

    return out, pull

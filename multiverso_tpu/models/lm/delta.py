"""Kimi Delta Attention (the linear-attention layer of ``model_type:
kimi_linear``, Moonshot's Kimi Linear, arXiv 2510.26692), as the attention
of a layer on the plain residual: a state ``S`` [K x V] a head that every
position rewrites, and the first part of ``models/lm/`` in which a position
reads what the positions before it left. For a sublayer's input ``x``
[T, hidden], ``h = RMSNorm(x)``, heads ``i`` of ``kda_heads``, K = V =
``kda_head_dim``, ``conv`` the causal depthwise convolution over positions
(``y[t] = sum_j w[c, j] x[t - (n - 1) + j]``, ``x`` zero before the
sequence's first position, ``n = kda_conv`` weights a channel, no bias):

    q~, k~, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
    q_i = q~_i / |q~_i|_2 * K^-1/2,   k_i = k~_i / |k~_i|_2
    g    = -exp(A_log_i) softplus((h W_fa) W_fb + dt_bias)   log decay A CHANNEL
    beta = sigmoid(h W_b)                                    a head
    S_i[t] = (I - beta k k^T) Diag(exp g) S_i[t-1] + beta k v^T,  S_i[-1] = 0
    o_i[t] = S_i[t]^T q_i[t]
    F(x) = concat_i(RMSNorm(o_i; g_o) * sigmoid(((h W_ga) W_gb)_i)) W_o

**beta's range** (``cfg.kda_beta_scale``): 1, or 2 (``beta = 2 sigmoid(h
W_b)``, the released layers' ``allow_neg_eigval``): with ``k`` of unit
length the step's matrix ``(I - beta k k^T) Diag(exp g)`` has the eigenvalue
``1 - beta`` along ``k``, in (-1, 1) at 2, so a state can flip sign along a
key. ``scan`` and the kernels take beta as data: ``(I + A)``'s entries below
the diagonal double, and the solve is built by halves so that it holds
there (``unit_lower_inverse``; tests/test_lm_solar.py).

**The share** (``cfg.heads_held = (first, count)`` of ``kda_heads``):
``W_q``, ``W_k``, ``W_v``, ``W_fb``, ``W_gb``, ``W_b``, the three
convolutions, ``A_log`` and ``dt_bias`` hold the held heads' columns and
``W_o`` their rows; ``W_fa``, ``W_ga`` and the output norm are whole on every
chip. A head reads no other head, so the layer adds its heads' part of
``W_o``'s sum and the shares add up to the uncut attention
(tests/test_lm_solar.py, four of four).

**The scan in chunks** (``scan``). Position by position the recurrence is
T steps of rank-one work; over a chunk of ``CHUNK`` positions it is matrix
products. With ``G_t`` the log decays summed from the chunk's first
position to ``t`` (a channel), ``S`` the state the chunk starts from and
``r_t = beta_t (v_t - (Diag(exp g_t) S[t-1])^T k_t)`` what position ``t``
writes:

    A[t, s] = beta_t sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])     s < t
    B[t, s] =        sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])     s <= t
    (I + A) R = beta V - (beta K exp(G)) S        so, with M = (I + A)^-1,
    R = U - W S,   U = M (beta V),   W = M (beta K exp(G))
    O = (Q exp(G)) S + B R
    S' = Diag(exp(G_last)) S + (K exp(G_last - G))^T R

``M``, ``U``, ``W``, ``B`` and the decayed copies of q and k need no state:
``_within`` makes them for a run of ``CHUNKS_AT_ONCE`` chunks together,
every head at once; ``_across`` then carries ``S`` through the run's chunks
in order, four products a chunk over every head. The backward pass is the
transpose of both: the runs walked the other way with the state's
cotangent as the carry, each run made again from its inputs and the state
it started from before it is transposed (a run is under
``jax.checkpoint``: what lives from the forward to the backward walk is
the sequence's q, k, v, g, beta and the state at each run's boundary, never
a state a position nor a [T, T] array).

**On a TPU** at whole chunks of 64 and heads of one 128-lane tile
(``scan_in_kernels``) none of ``M``, ``U``, ``W``, ``A``, ``B`` nor the state
between chunks goes to HBM: ``scan`` is delta_kernels.py's two Pallas
kernels, a head's chunks walked with the state and the chunk's matrices in
fast memory, forward and backward, q, k, g, v read from ``[T, H K]`` as
``gates`` leaves them. The same algebra, sub-blocks and precision; the
functions below are every other shape's path and the kernels' definition.
Around it the heads' arrays stay [T, H K], the layout the projections
leave: ``gates`` and ``output`` work a head at a time on ``heads_apart``'s
view, and ``attention_vjp`` hands [T, H K] from part to part, so that no
copy turns 8 positions by 128 lanes into 8 heads by 128 lanes and back.
There too, at whole blocks of 512 tokens (``passes_fused``), ``gates`` and
``output``'s gated norm are delta_passes.py's Pallas passes, one over memory
each way with hand-written pulls, and so is every short convolution
(``short_conv_flat``: one read and one write, and a pull that keeps the
convolution's INPUT alone, makes the sum and the silu's slope again and hands
the weights' gradient back as a partial sum a block of tokens); where nothing
is pulled through them q's and k's convolutions go inside the gates' pass
(``conv_gates_flat``); ``short_conv``, ``gates`` and ``output`` below stay
their definition and every other shape's path.

**No division by a decay.** Every exponent is a difference of summed log
decays that is <= 0: ``G_t``, ``G_last - G_s``, and ``G_t - G_s`` for ``s
<= t``. The last sits INSIDE the sum over channels, so ``A`` and ``B`` are
no plain products of q and k: in sub-blocks of ``BLOCK`` positions, a block
of rows against the blocks before it is a product of ``x_t exp(G_t - G_n)``
and ``k_s exp(G_n - G_s)``, ``n`` the last position before the row block
(both exponents <= 0), and a block against itself is summed channel by
channel. A chunk whose decay underflows (``LM_KDA_DECAY_DEEP`` counts the
(chunk, head, channel) triples whose summed log decay is under ``DEEP``)
gives zeros where the factored form ``(x exp(G)) (k exp(-G))^T`` gives
``0 * inf``.

**Precision.** Matrix products take bfloat16 inputs and accumulate in
float32 (``bdot``, and ``model.mm`` for the projections); the log decay,
its sums within a chunk and every exponential of them, softplus, both L2
norms, beta, the state from chunk to chunk, the solve ``(I + A)^-1``
(``unit_lower_inverse``: by halves, float32 at "highest") and the gated norm
are float32.

Scopes: ``mv.lm.attn.kda`` (norm, projections, gates, L2 norms, the gated
output norm, ``W_o``), ``mv.lm.attn.kda.conv`` (the three convolutions and
their silu), ``mv.lm.attn.kda.scan`` (the scan), the backward pass under
the same names (``attention_vjp`` differentiates the parts one by one).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import model as lm
from .model import BF16, F32, LMConfig

SCOPE = "mv.lm.attn.kda"
MATRICES = ("wq", "wk", "wv", "w_fa", "w_fb", "w_ga", "w_gb", "w_beta", "wo")
CONVS = ("conv_q", "conv_k", "conv_v")
#: Positions a chunk of the scan and a sub-block of a chunk (where they
#: divide the sequence; a shorter sequence is one chunk).
CHUNK, BLOCK = 64, 16
#: A chunk's summed log decay under which a channel counts as deep:
#: exp(20) times a bfloat16 product's rounding is past 1.
DEEP = -20.0
#: Chunks ``_within`` works on at a time, every head of each: its
#: channel-by-channel sums hold [chunks, heads, C / BLOCK, BLOCK, BLOCK, K]
#: float32 while they are made (134 MB at 8 chunks of 32 heads of 128).
CHUNKS_AT_ONCE = 8
#: The state's dtype from chunk to chunk (a check's control lowers it).
CARRY = F32
#: Positions a tile of a float32 [T, lanes] array holds on a TPU.
SUBLANES = 8


def shapes(cfg: LMConfig) -> dict:
    """A delta layer's attention tensors as the server stores them: the
    nine matrices, then a convolution's weights a channel a row, the log
    decay's scale a head, its bias a channel, the output norm a lane."""
    h, heads, d = cfg.hidden, cfg.kda_heads_held, cfg.kda_head_dim
    lanes = heads * d
    out = {"wq": (h, lanes), "wk": (h, lanes), "wv": (h, lanes),
           "w_fa": (h, d), "w_fb": (d, lanes), "w_ga": (h, d),
           "w_gb": (d, lanes), "w_beta": (h, heads), "wo": (lanes, h)}
    out.update({n: (lanes, cfg.kda_conv) for n in CONVS})
    out.update({"a_log": (heads,), "dt_bias": (lanes,), "norm_o": (d,)})
    return out


def chunk_of(t: int) -> int:
    return CHUNK if t % CHUNK == 0 else t


# -- products ---------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _bdot(spec: str):
    ins, out = spec.split("->")
    left, right = ins.split(",")

    def product(a, b):
        return jnp.einsum(spec, a.astype(BF16), b.astype(BF16),
                          preferred_element_type=F32)

    def forward(a, b):
        return product(a, b), (a.astype(BF16), b.astype(BF16),
                               jnp.zeros((), a.dtype), jnp.zeros((), b.dtype))

    def backward(res, g):
        a, b, like_a, like_b = res
        g = g.astype(BF16)
        return (jnp.einsum(f"{out},{right}->{left}", g, b,
                           preferred_element_type=F32).astype(like_a.dtype),
                jnp.einsum(f"{left},{out}->{right}", a, g,
                           preferred_element_type=F32).astype(like_b.dtype))

    rule = jax.custom_vjp(product)
    rule.defvjp(forward, backward)
    return rule


def bdot(spec: str, a, b):
    """``einsum(spec, a, b)`` as ``model.mm`` multiplies: bfloat16 inputs,
    float32 sums, and for a float32 operand a cotangent that is a float32
    sum of bfloat16 inputs too (a convert's own transpose would round it).
    An operand that is only ever a product's input may come in bfloat16
    (``_within``'s do: half the bytes kept, the same numbers)."""
    return _bdot(spec)(a, b)


def _highest(a, b):
    return jnp.matmul(a, b, precision="highest")


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., n, n] strictly lower triangular,
    float32, by halves: with ``M_b`` the inverse of ``I +`` the part of
    ``a`` inside diagonal blocks of ``b``, a block of ``2 b`` is ``[[M11,
    0], [-M22 a21 M11, M22]]``, so ``M_2b = M_b - M_b D_b M_b`` with ``D_b``
    the lower-left quarters of ``a``'s blocks of ``2 b``: two products a
    doubling, and nothing larger than the inverses of ``a``'s own blocks is
    ever formed. (The series ``(I - a)(I + a^2)(I + a^4)..`` costs the same
    ten products at 64 and sums powers of ``a``: at 64 positions whose keys
    are near each other under a beta near 2 they pass 1e20 and cancel to
    nothing in float32, where this reads 1e-6; tests/test_lm_solar.py.)"""
    n = a.shape[-1]
    rows, cols = jnp.arange(n)[:, None], jnp.arange(n)[None, :]
    inverse, b = jnp.eye(n, dtype=F32), 1
    while b < n:
        quarter = (rows // (2 * b) == cols // (2 * b)) \
            & (rows // b % 2 == 1) & (cols // b % 2 == 0)
        part = jnp.where(quarter, a, 0.0)
        inverse = inverse - (_highest(_highest(inverse, part), inverse)
                             if b > 1 else part)
        b *= 2
    return inverse


def _inverse_fwd(a):
    m = unit_lower_inverse(a)
    return m, m


def _inverse_bwd(m, g):
    mt = jnp.swapaxes(m, -1, -2)
    return (-_highest(_highest(mt, g), mt),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# -- the scan ------------------------------------------------------------------

def _decayed_pairs(x, k, G, block: int):
    """``P[t, s] = sum_c x_t[c] k_s[c] exp(G_t[c] - G_s[c])`` for ``s <=
    t`` within a chunk, zero above the diagonal: x, k, G [..., C, K] ->
    [..., C, C]. ``G`` falls along a chunk."""
    *lead, c, lanes = x.shape
    nb = c // block
    xb, kb, Gb = (a.reshape(*lead, nb, block, lanes) for a in (x, k, G))
    # a block against itself: channel by channel, the exponent masked
    # before it is taken
    seen = jnp.tril(jnp.ones((block, block), bool))[..., None]
    fall = jnp.exp(jnp.where(seen, Gb[..., :, None, :] - Gb[..., None, :, :],
                             0.0))
    own = jnp.sum(jnp.where(seen, xb[..., :, None, :] * kb[..., None, :, :]
                            * fall, 0.0), axis=-1)      # [.., nb, b, b]
    rows = []
    for i in range(nb):
        lo, hi = i * block, (i + 1) * block
        parts = []
        if i:   # against the blocks before it, by way of position lo - 1
            at = G[..., lo - 1:lo, :]
            parts.append(bdot("...tk,...sk->...ts",
                              x[..., lo:hi, :] * jnp.exp(G[..., lo:hi, :] - at),
                              k[..., :lo, :] * jnp.exp(at - G[..., :lo, :])))
        parts.append(own[..., i, :, :])
        if hi < c:
            parts.append(jnp.zeros((*lead, block, c - hi), F32))
        rows.append(jnp.concatenate(parts, axis=-1))
    return jnp.concatenate(rows, axis=-2)


def _within(q, k, v, g, beta, block: int):
    """What a chunk's positions make of each other, for q, k, g [.., C, K],
    v [.., C, V], beta [.., C] (any leading axes: a run's chunks, the
    heads): ``(W, U, B, Q exp(G), K exp(G_last - G), exp(G_last), deep)``:
    the module's docstring."""
    G = jnp.cumsum(g, axis=-2)
    last = G[..., -1:, :]
    c = q.shape[-2]
    below = jnp.tril(jnp.ones((c, c), F32), -1)
    a = beta[..., None] * below * _decayed_pairs(k, k, G, block)
    b = _decayed_pairs(q, k, G, block)
    # M = I + X: the identity's part of both products is exact
    x = unit_lower_inverse(a) - jnp.eye(c, dtype=F32)
    kg = beta[..., None] * k * jnp.exp(G)
    vb = beta[..., None] * v
    w = kg + bdot("...ts,...sk->...tk", x, kg)
    u = vb + bdot("...ts,...sv->...tv", x, vb)
    deep = jnp.sum(last < DEEP, dtype=jnp.int32)
    # what ``_across`` only ever multiplies leaves in bfloat16
    return (w.astype(BF16), u, b.astype(BF16), (q * jnp.exp(G)).astype(BF16),
            (k * jnp.exp(last - G)).astype(BF16), jnp.exp(last[..., 0, :]),
            deep)


def _across(state, w, u, b, qg, kg, fall):
    """The state through a run of chunks in order, every head at once:
    ``state`` [H, K, V] float32 and the arrays of ``_within`` with the
    chunks leading ([n, H, ...]) -> ``(the state after them, the outputs
    [n, H, C, V])``. ``CARRY`` is the state's dtype between chunks."""
    def chunk(state, xs):
        w, u, b, qg, kg, fall = xs
        state = state.astype(F32)
        r = u - bdot("hck,hkv->hcv", w, state)
        o = bdot("hck,hkv->hcv", qg, state) + bdot("hcs,hsv->hcv", b, r)
        state = fall[..., None] * state + bdot("hck,hcv->hkv", kg, r)
        return state.astype(CARRY), o

    state, o = jax.lax.scan(chunk, state.astype(CARRY),
                            (w, u, b, qg, kg, fall))
    return state.astype(F32), o


def _sizes(t: int, chunk: int, block: int):
    """``scan``'s chunk and sub-block where they are left to it."""
    chunk = chunk or chunk_of(t)
    return chunk, block or (BLOCK if chunk % BLOCK == 0 else chunk)


def scan_in_kernels(t: int, lanes: int, v_lanes: int, chunk: int = 0,
                    block: int = 0) -> bool:
    """Whether ``scan`` runs as delta_kernels' Pallas kernels: on a TPU, at
    whole chunks of 64 in sub-blocks of 16 and heads of one 128-lane tile,
    with the state in float32 (``CARRY`` as it is). By what the code can
    see: no flag chooses."""
    if jax.default_backend() != "tpu" or CARRY != F32:
        return False
    from . import delta_kernels     # Pallas: imported where it can run
    return delta_kernels.shapes_fit(t, lanes, v_lanes,
                                    *_sizes(t, chunk, block))


def scan_counter(cfg: LMConfig, t: int) -> str:
    """The counter a delta layer's sequence of ``t`` tokens counts: which
    form ``scan`` took (``PSLMTrainer._count_stats``)."""
    d = cfg.kda_head_dim
    return "LM_KDA_SCAN_KERNEL" if scan_in_kernels(t, d, d) \
        else "LM_KDA_SCAN_PLAIN"


def scan(q, k, v, g, beta, chunk: int = 0, block: int = 0):
    """The delta rule's outputs ``o`` [T, H, V] float32 and the count of
    deep (chunk, head, channel) triples, for q, k, g [T, H, K], v [T, H,
    V], beta [T, H] float32, in chunks of ``chunk`` positions
    (``chunk_of(T)`` when 0) and sub-blocks of ``block``.

    Where ``scan_in_kernels``, delta_kernels.py's two kernels: a head's
    chunks walked with the state and the chunk's matrices in fast memory,
    read from [T, H K] as it lies. Everywhere else the lines below.

    The chunks go in runs of ``CHUNKS_AT_ONCE``: a run's chunks through
    ``_within`` together, then the state through them in order. A run is
    under ``jax.checkpoint``, so what the backward pass finds is the state
    at each run's start and the run's inputs; it walks the runs the other
    way and makes each again before it transposes it."""
    t, heads, lanes = q.shape
    if scan_in_kernels(t, lanes, v.shape[-1], chunk, block):
        from . import delta_kernels
        return delta_kernels.scan(q, k, v, g, beta, DEEP)
    chunk, block = _sizes(t, chunk, block)
    assert t % chunk == 0 and chunk % block == 0, (t, chunk, block)
    n = t // chunk
    at_once = next(m for m in range(min(CHUNKS_AT_ONCE, n), 0, -1)
                   if n % m == 0)

    def chunks(a):      # [T, H, ..] -> [N / at_once, at_once, H, C, ..]
        a = jnp.moveaxis(a.reshape(n, chunk, *a.shape[1:]), 1, 2)
        return a.reshape(n // at_once, at_once, *a.shape[1:])

    @jax.checkpoint
    def run(state, xs):
        *parts, deep = _within(*xs, block)
        state, o = _across(state, *parts)
        return state, (o, deep)

    _, (o, deep) = jax.lax.scan(
        run, jnp.zeros((heads, lanes, v.shape[-1]), F32),
        tuple(chunks(a) for a in (q, k, v, g, beta)))
    o = o.reshape(n, *o.shape[2:])      # [N, H, C, V]
    return jnp.moveaxis(o, 1, 2).reshape(t, heads, v.shape[-1]), \
        jnp.sum(deep)


# -- the sublayer's parts -----------------------------------------------------------

def projections(cfg: LMConfig, mats, sinks, norm, x):
    """The sublayer's norm and every product of its normed input: ``(h
    W_q, h W_k, h W_v, the decay's logits (h W_fa) W_fb, the output gate's
    (h W_ga) W_gb, beta's h W_b)``."""
    h = lm.rmsnorm(x, norm, cfg.eps)

    def of(*names):
        out = h
        for name in names:
            out = lm.mm(out, mats[name], sinks[name])
        return out

    return (of("wq"), of("wk"), of("wv"), of("w_fa", "w_fb"),
            of("w_ga", "w_gb"), of("w_beta"))


def short_conv(x, w):
    """The causal depthwise convolution over positions, then silu: x [T,
    channels], w [channels, n]; position ``t`` reads ``t - n + 1 .. t``."""
    t, n = x.shape[0], w.shape[1]
    padded = jnp.pad(x, ((n - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + t] * w[:, j] for j in range(n)))


def heads_apart(a, heads: int):
    """[T, H d] -> [T / 8, 8, H, d], a head's lanes the last axis with the
    positions' groups of ``SUBLANES`` kept whole ([T, 1, H, d] where 8 does
    not divide T). A TPU stores float32 [T, H d] in tiles of 8 positions by
    128 lanes, so this view is the array as it lies, and a sum over a
    head's lanes or a factor a head reads and writes [T, H d] once; [T, H,
    d] lies in tiles of 8 HEADS by 128 lanes, a copy each way (0.4 ms for
    [8192, 32, 128], and the broadcasts written out beside it)."""
    t = a.shape[0]
    rows = SUBLANES if t % SUBLANES == 0 else 1
    return a.reshape(t // rows, rows, heads, -1)


def gates(cfg: LMConfig, a_log, dt_bias, q, k, v, f, b):
    """From the convolved q, k, v [T, H K], the decay's logits ``f`` and
    beta's ``b``: ``(q, k, v, g [T, H, K], beta [T, H])`` float32 as
    ``scan`` takes them: the L2 norms, the scale on q, the log decay."""
    t, heads, d = q.shape[0], cfg.kda_heads_held, cfg.kda_head_dim

    def by_head(a):
        return heads_apart(a, heads)

    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True))

    g = -jnp.exp(a_log)[:, None] * by_head(jax.nn.softplus(f + dt_bias))
    out = (unit(by_head(q)) * d ** -0.5, unit(by_head(k)), by_head(v), g)
    out = tuple(a.reshape(t, heads, d) for a in out)
    beta = jax.nn.sigmoid(b)
    if cfg.kda_beta_scale != 1:
        beta = cfg.kda_beta_scale * beta
    return (*out, beta)


def output(cfg: LMConfig, mats, sinks, norm_o, o, gate):
    """The heads' outputs ``o`` [T, H, V] through the gated norm (each
    head normed alone, times the sigmoid of its lanes of ``gate`` [T, H
    V]) and ``W_o``: [T, hidden]."""
    t, heads, d = o.shape
    o = lm.rmsnorm(heads_apart(o.reshape(t, heads * d), heads), norm_o,
                   cfg.eps).reshape(t, heads * d)
    return lm.mm(o * jax.nn.sigmoid(gate), mats["wo"], sinks["wo"])


#: ``short_conv``, ``gates`` and ``output`` as this module defines them: a
#: caller that has put its own in their place (the checks' controls do)
#: keeps the chain.
_DEFINED = short_conv, gates, output


def passes_fused(cfg: LMConfig, t: int) -> bool:
    """Whether ``gates`` (with q's and k's convolutions where nothing is
    pulled through them), every other ``short_conv`` and its pull, and
    ``output``'s gated norm run as delta_passes.py's Pallas passes, one over
    memory each way: on a TPU, at whole blocks of tokens and heads of one
    128-lane tile, with the three functions the ones above. By what the code
    can see: no flag chooses. Everywhere else the ``jax.numpy`` lines above,
    which are the definition."""
    if (jax.default_backend() != "tpu"
            or (short_conv, gates, output) != _DEFINED):
        return False
    from . import delta_passes      # Pallas: imported where it can run
    return delta_passes.fits(t, cfg.kda_head_dim)


def pass_counter(cfg: LMConfig, t: int) -> str:
    """The counter a delta layer's sequence of ``t`` tokens counts: which
    form its short convolutions, gates and gated norm took
    (``PSLMTrainer._count_stats``)."""
    return "LM_KDA_PASS_FUSED" if passes_fused(cfg, t) \
        else "LM_KDA_PASS_PLAIN"


def _passes(cfg: LMConfig):
    from . import delta_passes
    return delta_passes, delta_passes.Pass(
        cfg.kda_heads_held, float(cfg.kda_beta_scale), cfg.eps)


def short_conv_flat(cfg: LMConfig, x, w):
    """``short_conv`` of a product's result [T, H K]: one pass over memory
    each way where ``passes_fused`` (its pull reads the cotangent and ``x``
    again and keeps nothing else)."""
    if passes_fused(cfg, x.shape[0]):
        passes, how = _passes(cfg)
        return passes.conv(how, x, w)
    return short_conv(x, w)


def gates_flat(cfg: LMConfig, a_log, dt_bias, q, k, v, f, b):
    """``gates`` with the heads' arrays [T, H K] in and out, as
    ``attention_vjp``'s parts exchange them: one pass over memory where
    ``passes_fused``."""
    t = q.shape[0]
    if passes_fused(cfg, t):
        passes, how = _passes(cfg)
        q, k, g, beta = passes.gates(how, a_log, dt_bias, q, k, f, b)
        return q, k, v, g, beta
    *wide, beta = gates(cfg, a_log, dt_bias, q, k, v, f, b)
    return (*(a.reshape(t, -1) for a in wide), beta)


def conv_gates_flat(cfg: LMConfig, convs, a_log, dt_bias, q, k, v, f, b):
    """``gates_flat`` of the three products' results through ``short_conv``
    (``convs``: the three convolutions' weights), where nothing is pulled
    through either: v's convolution under its scope (a pass of its own
    where ``passes_fused``), and there q's and k's INSIDE the gates' pass,
    which reads the products' results and writes no convolved copy of
    them."""
    if not passes_fused(cfg, q.shape[0]):
        with jax.named_scope(SCOPE + ".conv"):
            q, k, v = (short_conv(x, w) for x, w in zip((q, k, v), convs))
        with jax.named_scope(SCOPE):
            return gates_flat(cfg, a_log, dt_bias, q, k, v, f, b)
    with jax.named_scope(SCOPE + ".conv"):
        v = short_conv_flat(cfg, v, convs[2])
    with jax.named_scope(SCOPE):
        passes, how = _passes(cfg)
        q, k, g, beta = passes.conv_gates(how, *convs[:2], a_log, dt_bias,
                                          q, k, f, b)
    return q, k, v, g, beta


def output_flat(cfg: LMConfig, mats, sinks, norm_o, o, gate):
    """``output`` for ``o`` [T, H V] as the scan leaves it: the gated norm
    as one pass where ``passes_fused``."""
    t = o.shape[0]
    if passes_fused(cfg, t):
        passes, how = _passes(cfg)
        return lm.mm(passes.gated_norm(how, norm_o, o, gate), mats["wo"],
                     sinks["wo"])
    return output(cfg, mats, sinks, norm_o,
                  o.reshape(t, cfg.kda_heads_held, -1), gate)


def attention_vjp(cfg: LMConfig, mats, sinks, small, x):
    """``F(x)`` for one sequence and what pulls a cotangent back through
    it: ``(F(x), counts, pull)``, ``pull(d) -> (dx, matrix gradients, small
    gradients)``; ``counts`` by ``model.layer_stats``' names: ``decay_deep``,
    and where beta can pass 1 ``beta_over_one``, the (position, head) pairs
    at which it does. The parts are differentiated one by one so that each
    part's backward pass runs under the scope of its forward pass.

    What is kept from the forward pass to the pull is the six projections
    and the scan's outputs: the convolutions, the gates and the scan are
    computed AGAIN in the pull (behind a barrier, or the compiler would
    share them with the first time and keep what they hold alive), first
    for the scan's transpose and then for the gates' and the
    convolutions', so that what each keeps for its transpose, the state at
    every chunk's boundary among it, lies neither beside the feed-forward's
    nor beside the others'."""
    first = {n: sinks[n] for n in MATRICES[:-1]}
    convs = tuple(small[n] for n in CONVS)
    t, heads = x.shape[0], cfg.kda_heads_held
    chunk = chunk_of(t)

    # between the parts (what is differentiated, kept, held behind a
    # barrier) the heads' arrays go as [T, H d], the layout the projections
    # leave and the kernels read; [T, H, d] is a view inside a part
    def by_head(a):
        return a.reshape(t, heads, -1)

    def flat(a):
        return a.reshape(t, -1)

    def convolved(convs, q, k, v):
        return tuple(short_conv_flat(cfg, x, w)
                     for x, w in zip((q, k, v), convs))

    def gated(convs, a_log, dt_bias, q, k, v, f, b, vjp=None):
        """``scan``'s five arguments from the projections (and with
        ``vjp=jax.vjp`` what pulls their cotangents back), each part under
        its scope."""
        if vjp is None:
            return conv_gates_flat(cfg, convs, a_log, dt_bias, q, k, v, f,
                                   b), None
        with jax.named_scope(SCOPE + ".conv"):
            qkv, pull_conv = vjp(convolved, convs, q, k, v)

        def of(a_log, dt_bias, qkv, f, b):
            return gates_flat(cfg, a_log, dt_bias, *qkv, f, b)

        with jax.named_scope(SCOPE):
            scanned, pull_gates = vjp(of, a_log, dt_bias, qkv, f, b)

        def pull(d_scanned):
            with jax.named_scope(SCOPE):
                d_a_log, d_dt_bias, d_qkv, df, db = pull_gates(d_scanned)
            with jax.named_scope(SCOPE + ".conv"):
                d_convs, *d_projected = pull_conv(d_qkv)
            return d_convs, d_a_log, d_dt_bias, (*d_projected, df, db)

        return scanned, pull

    def scanned_through(*scanned):
        *wide, beta = scanned
        with jax.named_scope(SCOPE + ".scan"):
            o, deep = scan(*map(by_head, wide), beta, chunk)
            return flat(o), deep

    with jax.named_scope(SCOPE):
        (q, k, v, f, gate, b), pull_projections = jax.vjp(
            lambda s, norm, x: projections(cfg, mats, s, norm, x),
            first, small["norm_attn"], x)
    kept = (convs, small["a_log"], small["dt_bias"], q, k, v, f, b)
    scanned = gated(*kept)[0]
    o, deep = scanned_through(*scanned)
    counts = {"decay_deep": deep}
    if cfg.kda_beta_scale != 1:
        with jax.named_scope(SCOPE):
            counts["beta_over_one"] = jnp.sum(scanned[-1] > 1.0,
                                              dtype=jnp.int32)
    with jax.named_scope(SCOPE):
        out, pull_output = jax.vjp(
            lambda s, norm_o, o, gate: output_flat(cfg, mats, {"wo": s},
                                                   norm_o, o, gate),
            sinks["wo"], small["norm_o"], o, gate)

    def pull(d_out):
        with jax.named_scope(SCOPE):
            d_wo, d_norm_o, do, d_gate = pull_output(d_out)
        # one part's transpose at a time, each from the projections again:
        # the scan's own keep is gone before the gates' and the
        # convolutions' is made
        again, do = jax.lax.optimization_barrier((kept, do))
        with jax.named_scope(SCOPE + ".scan"):
            d_scanned = jax.vjp(lambda *a: scanned_through(*a)[0],
                                *gated(*again)[0])[1](do)
        again, d_scanned = jax.lax.optimization_barrier((again, d_scanned))
        d_convs, d_a_log, d_dt_bias, (dq, dk, dv, df, db) = gated(
            *again, vjp=jax.vjp)[1](d_scanned)
        with jax.named_scope(SCOPE):
            d_mats, d_norm, dx = pull_projections((dq, dk, dv, df, d_gate, db))
        d_small = {"norm_attn": d_norm, "a_log": d_a_log,
                   "dt_bias": d_dt_bias, "norm_o": d_norm_o,
                   **dict(zip(CONVS, d_convs))}
        return dx, {**d_mats, "wo": d_wo}, d_small

    return out, counts, pull

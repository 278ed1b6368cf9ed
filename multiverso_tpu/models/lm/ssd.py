"""The selective state-space mixer (Mamba-2's SSD layer as ``model_type:
granitemoehybrid`` has it, ibm-granite's Granite 4.0-H:
``GraniteMoeHybridMambaLayer``), as the attention of a layer on the plain
residual: a state ``S`` [P x N] a head under ONE scalar decay a head a
position, the first mixer in ``models/lm/`` whose state is no delta rule's
(no correction term, no solve, no key norm). For a sublayer's input ``x`` [T,
hidden], ``h = RMSNorm(x)``, heads ``i`` of ``ssd_heads`` (64) of
``ssd_head_dim`` P (64) lanes, a state of ``ssd_state`` N (128), ONE group of
B and C (``ssd_groups`` 1: every head reads the same two):

    (z, xBC, dt) = split(h W_in)        W_in [hidden, 2 H P + 2 N + H]
    xBC = silu(conv(xBC) + b_c)         the causal depthwise convolution of
                                        ``ssd_conv`` (4) taps, zeros before
                                        the sequence (``shortconv.taps``),
                                        then its bias and silu (``conv``)
    (X, B, C) = split(xBC)              X [T, H, P], B, C [T, N]
    dt = softplus(dt + dt_bias) [T, H], A = -exp(A_log) [H]   (``step``,
                                                               ``log_decay``)
    S_i[t] = exp(dt_t A_i) S_i[t-1] + dt_t X_t (x) B_t,   S_i[-1] = 0
    Y_t = S_i[t] C_t + D_i X_t
    G = Y * silu(z);  N = G rsqrt(mean_{H P}(G^2) + eps) g_n   the gate is
                                        INSIDE the norm, whose mean runs over
                                        all heads' lanes (``gated_norm``)
    F(x) = N W_out                      W_out [H P, hidden]

**The scan in chunks** (``scan``). With ``G_t`` the log decays ``dt A``
summed from a chunk's first position through ``t`` (a head) and ``S`` the
state the chunk starts from:

    L[t, s] = exp(G_t - G_s)  s <= t,  0 above          [H, c, c]
    Y = ((C B^T) * L) (dt X)  +  exp(G_t) (S C_t)
    S' = exp(G_last) S + sum_s exp(G_last - G_s) dt_s X_s (x) B_s

Every exponent is a difference of summed log decays that is <= 0 (the mask
is put on the exponent BEFORE it is taken), so a chunk whose decay
underflows gives zeros, never ``0 * inf`` (``LM_SSD_DEEP`` counts the
(chunk, head) pairs whose summed log decay is under ``delta.DEEP``).

**Which form runs where** (``scan_in_kernels``: the backend and the shapes
choose, no flag; ``scan_counter`` names the form, ``LM_SSD_SCAN_KERNEL`` |
``LM_SSD_SCAN_PLAIN``). On a TPU, at whole chunks of 256, heads of 64 lanes
by twos, a state of 128 and ``CARRY`` and ``DECAY`` float32 (the cell's
layers; any whole number of chunks), ``scan`` is ssd_kernels.py's two Pallas
kernels: the chunks walked in order with ``C B^T``, a head's ``L`` and EVERY
head's state in fast memory, X read and Y written as [T, H P] lies on a TPU
(positions along the lanes), the states that the chunks started from kept
for the pull (67 MB a sequence of 8,192) and the pull written by hand.
Everywhere else (the CPU, the tests' and the rehearsal's shapes, one head
under ``vmap``, a control's bfloat16 state) the ``jax.numpy`` lines below,
which are the definition the tests hold the kernels to: the chunks go in runs
of ``CHUNKS_AT_ONCE`` as ``delta.scan``'s do: a run's
within-chunk factors made together ([run, H, c, c] float32: 134 MB at 8
chunks of 256, never a layer's 1.07 GB), then the state through the run's
chunks in order; a run is under ``jax.checkpoint``, so the backward pass
keeps the state at each run's start and makes the run again.
The chunk is the blocking and no part of the function (the released kernels'
``mamba_chunk_size`` 256 is theirs; here the configuration's ``scan_chunk``,
``LMConfig.ssd_chunk``, or ``CHUNK``): tests/test_lm_granite.py holds two
sizes to the recurrence.

**Precision.** The products (``W_in``, ``W_out``, ``C B^T``, the chunk's
``M (dt X)``, the states in and out) take bfloat16 inputs and accumulate in
float32 (``model.mm``, ``delta.bdot``); ``dt``, the log decay and its sums,
``L`` (``DECAY``), the state between chunks (``CARRY``), the convolution, the
gate and the norm are float32.

Scopes: ``mv.lm.attn.ssd`` (norm, ``W_in``, ``W_out``),
``mv.lm.attn.ssd.conv`` (taps, bias, silu), ``mv.lm.attn.ssd.scan``
(softplus, the decay, the scan, the skip ``D X``), ``mv.lm.attn.ssd.gate``
(the gate and the norm), the backward pass under the same names
(``attention_vjp`` differentiates the parts one by one, each entered
outside the differentiated function).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import model as lm
from .delta import DEEP, bdot
from .model import F32, LMConfig
from .shortconv import taps

SCOPE = "mv.lm.attn.ssd"
MATRICES = ("w_in", "w_out")
SMALL = ("conv_w", "conv_b", "dt_bias", "a_log", "d", "norm_g")
#: Positions a chunk of the scan (where they divide the sequence; a shorter
#: sequence is one chunk) and the chunks a run works on at a time.
CHUNK = 256
CHUNKS_AT_ONCE = 8
#: The state's dtype from chunk to chunk and the within-chunk factor's (a
#: check's control lowers both).
CARRY = F32
DECAY = F32


def shapes(cfg: LMConfig) -> dict:
    """A state-space layer's mixer as the server stores it: the two
    matrices, the taps a channel a row and their bias, then a head's step
    bias, log of ``-A`` and skip, and the gated norm's scale a lane."""
    h, heads = cfg.hidden, cfg.ssd_heads
    inner, bc = heads * cfg.ssd_head_dim, 2 * cfg.ssd_groups * cfg.ssd_state
    return {"w_in": (h, 2 * inner + bc + heads), "w_out": (inner, h),
            "conv_w": (inner + bc, cfg.ssd_conv), "conv_b": (inner + bc,),
            "dt_bias": (heads,), "a_log": (heads,), "d": (heads,),
            "norm_g": (inner,)}


def chunk_of(cfg: LMConfig, t: int) -> int:
    """The scan's chunk for a sequence of ``t`` positions: the
    configuration's (``ssd_chunk``) or ``CHUNK``, where it divides the
    sequence; a sequence it does not divide is one chunk."""
    chunk = cfg.ssd_chunk or CHUNK
    return chunk if t % chunk == 0 else t


def scan_in_kernels(t: int, heads: int, lanes: int, state: int,
                    chunk: int = 0) -> bool:
    """Whether ``scan`` runs as ssd_kernels' Pallas kernels: on a TPU, at
    whole chunks of 256, heads of 64 lanes in pairs and a state of 128,
    with ``L`` and the state in float32 (``DECAY`` and ``CARRY`` as they
    are). By what the code can see: no flag chooses."""
    if jax.default_backend() != "tpu" or CARRY != F32 or DECAY != F32:
        return False
    from . import ssd_kernels       # Pallas: imported where it can run
    return ssd_kernels.shapes_fit(t, heads, lanes, state,
                                  chunk or (CHUNK if t % CHUNK == 0 else t))


def scan_counter(cfg: LMConfig, t: int) -> str:
    """The counter a state-space layer's sequence of ``t`` tokens counts:
    which form ``scan`` took (``PSLMTrainer._count_stats``)."""
    return "LM_SSD_SCAN_KERNEL" if scan_in_kernels(
        t, cfg.ssd_heads, cfg.ssd_head_dim, cfg.ssd_state,
        chunk_of(cfg, t)) else "LM_SSD_SCAN_PLAIN"


# -- the parts ---------------------------------------------------------------------

def conv(xbc, w, b):
    """``silu(taps(xbc) + b)``: xbc [T, channels], w [channels, n], b
    [channels]; position ``t`` reads ``t - n + 1 .. t``."""
    return jax.nn.silu(taps(xbc, w) + b)


def step(dt, dt_bias):
    """A position's step a head, > 0 (``time_step_limit`` (0, inf) clamps
    nothing)."""
    return jax.nn.softplus(dt + dt_bias)


def log_decay(dt, a_log):
    """``dt A`` [T, H] <= 0, ``A = -exp(a_log)``."""
    return dt * -jnp.exp(a_log)


def gated_norm(cfg: LMConfig, norm_g, y, z):
    """``RMSNorm(y * silu(z); g_n)`` over all ``H P`` lanes at once."""
    return lm.rmsnorm(y * jax.nn.silu(z), norm_g, cfg.eps)


def _run(state, xs, a):
    """A run of ``m`` chunks: x [m, c, H, P], dt [m, c, H], b, c [m, c, N]
    float32, the state [H, P, N] entering it -> ``(the state leaving it, (y
    [m, c, H, P], deep))``."""
    x, dt, b, c = xs
    g = jnp.cumsum(log_decay(dt, a), axis=1)        # [m, c, H], falling
    last = g[:, -1]
    by_head = jnp.moveaxis(g, 2, 1)                 # [m, H, c]
    n = x.shape[1]
    seen = jnp.tril(jnp.ones((n, n), bool))
    fall = jnp.where(seen, jnp.exp(jnp.where(
        seen, by_head[..., :, None] - by_head[..., None, :], 0.0)),
        0.0).astype(DECAY)                          # L [m, H, c, c]
    cb = bdot("mtn,msn->mts", c, b)
    xd = dt[..., None] * x
    y = bdot("mhts,mshp->mthp", cb[:, None] * fall, xd)
    # what each chunk adds to the state, and the state entering each
    added = bdot("mshp,msn->mhpn",
                 xd * jnp.exp(last[:, None] - g)[..., None], b)

    def chunk(state, xs):
        added, kept = xs
        after = kept[:, None, None] * state.astype(F32) + added
        return after.astype(CARRY), state

    state, entering = jax.lax.scan(chunk, state.astype(CARRY),
                                   (added, jnp.exp(last)))
    y = y + jnp.exp(g)[..., None] * bdot("mtn,mhpn->mthp", c, entering)
    return state.astype(F32), (y, jnp.sum(last < DEEP, dtype=jnp.int32))


def scan(x, dt, a_log, b, c, chunk: int = 0):
    """The recurrence's ``S_t C_t`` [T, H, P] float32 (no skip) and the
    count of deep (chunk, head) pairs, for x [T, H, P], dt [T, H] (> 0),
    a_log [H], b, c [T, N] float32, in chunks of ``chunk`` positions
    (``CHUNK`` where it divides T, else T, when 0): the module's
    docstring.

    Where ``scan_in_kernels``, ssd_kernels.py's two kernels: the chunks
    walked with ``L`` and every head's state in fast memory, X read from [T,
    H P] as it lies. Everywhere else the lines below."""
    t, heads, lanes = x.shape
    chunk = chunk or (CHUNK if t % CHUNK == 0 else t)
    assert t % chunk == 0, (t, chunk)
    if scan_in_kernels(t, heads, lanes, b.shape[-1], chunk):
        from . import ssd_kernels
        return ssd_kernels.scan(x, dt, log_decay(dt, a_log), b, c, DEEP)
    n = t // chunk
    at_once = next(m for m in range(min(CHUNKS_AT_ONCE, n), 0, -1)
                   if n % m == 0)

    def runs(a):        # [T, ..] -> [N / at_once, at_once, c, ..]
        return a.reshape(n // at_once, at_once, chunk, *a.shape[1:])

    run = jax.checkpoint(lambda state, xs: _run(state, xs, a_log))
    _, (y, deep) = jax.lax.scan(
        run, jnp.zeros((heads, lanes, b.shape[-1]), F32),
        tuple(runs(v) for v in (x, dt, b, c)))
    return y.reshape(t, heads, lanes), jnp.sum(deep)


def scanned(cfg: LMConfig, dt_bias, a_log, d, xbc, dt):
    """From the convolved ``xbc`` [T, H P + 2 N] and the raw steps ``dt``
    [T, H]: ``(Y [T, H P] with the skip, deep)``."""
    t, heads = dt.shape
    inner = heads * cfg.ssd_head_dim
    x = xbc[:, :inner]
    # ONE group: every head reads the same B and C [T, N]. [T, H, P] is a
    # view for ``scan``'s sake: the kernels read X and write Y as [T, H P]
    # lies, and the skip is taken there (a reshape that splits the lane
    # axis is a copy on a TPU)
    y, deep = scan(x.reshape(t, heads, -1), step(dt, dt_bias), a_log,
                   *jnp.split(xbc[:, inner:], 2, axis=-1), chunk_of(cfg, t))
    return y.reshape(t, inner) + jnp.repeat(d, cfg.ssd_head_dim) * x, deep


def attention_vjp(cfg: LMConfig, mats, sinks, small, x):
    """``F(x)`` for one sequence and what pulls a cotangent back through
    it: ``(F(x), counts, pull)``, ``pull(d) -> (dx, matrix gradients, small
    gradients)``, as ``delta.attention_vjp`` gives them; ``counts`` by
    ``model.layer_stats``' names (``decay_deep``)."""
    inner = cfg.ssd_heads * cfg.ssd_head_dim
    conv_dim = inner + 2 * cfg.ssd_groups * cfg.ssd_state
    with jax.named_scope(SCOPE):
        zxbcdt, pull_in = jax.vjp(
            lambda s, norm, x: lm.mm(lm.rmsnorm(x, norm, cfg.eps),
                                     mats["w_in"], s),
            sinks["w_in"], small["norm_attn"], x)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + conv_dim],
                  zxbcdt[:, inner + conv_dim:])
    with jax.named_scope(SCOPE + ".conv"):
        xbc, pull_conv = jax.vjp(conv, xbc, small["conv_w"], small["conv_b"])
    with jax.named_scope(SCOPE + ".scan"):
        y, pull_scan, deep = jax.vjp(
            lambda *a: scanned(cfg, *a), small["dt_bias"], small["a_log"],
            small["d"], xbc, dt, has_aux=True)
    with jax.named_scope(SCOPE + ".gate"):
        normed, pull_gate = jax.vjp(
            lambda g, y, z: gated_norm(cfg, g, y, z), small["norm_g"], y, z)
    with jax.named_scope(SCOPE):
        out, pull_out = jax.vjp(
            lambda s, normed: lm.mm(normed, mats["w_out"], s),
            sinks["w_out"], normed)

    def pull(d_out):
        with jax.named_scope(SCOPE):
            d_w_out, d_normed = pull_out(d_out)
        with jax.named_scope(SCOPE + ".gate"):
            d_norm_g, dy, dz = pull_gate(d_normed)
        with jax.named_scope(SCOPE + ".scan"):
            d_dt_bias, d_a_log, d_d, d_xbc, d_dt = pull_scan(dy)
        with jax.named_scope(SCOPE + ".conv"):
            d_xbc, d_conv_w, d_conv_b = pull_conv(d_xbc)
        with jax.named_scope(SCOPE):
            d_w_in, d_norm, dx = pull_in(
                jnp.concatenate([dz, d_xbc, d_dt], axis=-1))
        return (dx, {"w_in": d_w_in, "w_out": d_w_out},
                {"norm_attn": d_norm, "conv_w": d_conv_w, "conv_b": d_conv_b,
                 "dt_bias": d_dt_bias, "a_log": d_a_log, "d": d_d,
                 "norm_g": d_norm_g})

    return out, {"decay_deep": deep}, pull


def mix(cfg: LMConfig, mats, sinks, small, x):
    """``F(x)`` for one sequence."""
    return attention_vjp(cfg, mats, sinks, small, x)[0]

"""A sparse-expert decoder language model, one chip's share of it, as
pure functions over its tensors: what ``PSLMTrainer`` (ps_train.py)
pulls from the parameter server, steps and pushes back.

**The layer is described, not assumed.** ``LMConfig`` says, for a layer's
input ``x`` [T, hidden] at positions ``pos`` [T]:

    a = x + Attn(RMSNorm(x))             grouped-query; ``qk_norm``: each
                                          head's q and k through an RMSNorm
                                          of their own (g_q, g_k [head_dim])
                                          before the rotary turn; a layer is
                                          rotary or not; its ``Mask`` is a
                                          kind with its parameters
    p = softmax(r W_r) over all routed experts;  S = the top-k of p;
    w_e = p_e / sum_S p                  ``router_input``: r is the layer's
                                          raw input x ("input", BEFORE
                                          attention) or h below ("ffn_norm")
    y = a + sum_{e in S, e held} w_e W_d,e (act(h W_g,e) * (h W_u,e)),
        h = RMSNorm(a)                   ``activation``: relu or silu

Two published blocks are constructed from their own ``config.json`` keys
(``LMConfig.from_dict``): SmallThinker's (PowerInfer, 2025: relu, the
router on the raw input, full layers without rotary positions and rotary
layers with a sliding window) and Qwen3-MoE's as SDAR uses it (JetLM,
2025, ``model_type: sdar_moe``: silu, the router on the normed
post-attention stream, q and k norms, every layer rotary).

**A third family** (``from_dict`` tells it by ``kv_lora_rank``:
DeepSeek-V3's block as ``model_type: xing4_0`` has it, XingChen-AGI 2026)
is described by kinds that the two above leave at their defaults, and its
mechanisms live in modules of their own. The same keys WITHOUT ``hc_mult``
(``model_type: glm4_moe_lite``, zai-org's GLM-4.7-Flash, 2026) are the same
block on the plain residual, ``x + F(RMSNorm(x))`` through ``layer_vjp``,
and with ``rope_scaling`` null its rotary pairs turn at ``rope_theta``'s
own frequencies under the scale ``head_dim^-0.5``; the multi-token module
then reads and writes ``[T, C]`` (mtp.py):

    X [n C, T]: ``hc_mult`` residual streams     ``residual: "mhc"``,
      a column a token; a sublayer F reads             streams.py: r = RMSNorm(X);
      u = sum_j H_pre_j X_j, writes               [p, q, R] = r phi^T;
      X'_i = sum_j H_res_ij X_j + H_post_i v,     H_pre = sigmoid(a p + b),
      v = F(RMSNorm(u)); the streams are          H_post = 2 sigmoid(..),
      summed before the final norm                H_res = Sinkhorn(exp(clamp))
    F = latent attention, every layer           ``attention: "mla"``, latent.py:
      c_q = RMSNorm(h W_qa), [q_n|q_r] = c_q W_qb, [c_kv|k_r] = h W_kva,
      [k_n|v] = RMSNorm(c_kv) W_kvb; q_r, k_r rotated (YaRN), k_r one for
      all heads; score (q_n.k_n + q_r.k_r) 192^-0.5 m^2; ``heads_held``
    F = W_d (silu(h W_g) * h W_u)               ``ffn_layout`` 0: dense,
                                                  ``dense_width``
    F = sum_{e in S, e held} w_e E_e(h) + E_shared(h)   ``ffn_layout`` 1;
      s = sigmoid(h W_r); S the top-k of s + bias; w_e = routed_scale s_e /
      sum_S s (``scoring: "sigmoid_bias"``, ``route``); the bias gets no
      gradient: the trainer pushes ``bias_rate sign(mean load - load)``
    a multi-token module after the last layer   ``mtp_layers``, mtp.py

**A fourth family** (``from_dict`` tells it by
``num_attention_heads_per_layer``: the block of ``model_type: laguna``,
poolside's Laguna-XS.2, 2026) recombines what the three above have, on
the plain residual, for layer ``l``:

    H_l = heads(l) query heads over n_kv_heads    ``heads_layout``: 48 on
      key-value heads; q = h W_q [H_l, d]           full layers, 64 on window
                                                    ones; ``wq``, ``wo`` and
                                                    the gate differ by layer
    q, k turned by the layer KIND's ``Rotary``    ``rotary_kinds`` (full,
      (theta, the first ``lanes`` lanes turned,     window): YaRN on half a
      YaRN's blend, a factor on cos and sin)        head's lanes with the
                                                    factor | plain on all
    o_i = softmax(q_i . k d^-0.5) v under the     ``Mask`` causal | window
      kind's mask
    o_i <- sigmoid(h W_g)_i o_i                   ``attn_gate: "head"``,
                                                    W_g [hidden, H_l]
    a = x + concat(o) W_o
    y = a + F(RMSNorm(a)), F dense or             ``ffn_layout``,
      sum_{e in S, e held} w_e E_e + E_shared;      ``router_input:
      s = sigmoid(h W_r), S the top-k of s,         "ffn_input"``,
      w_e = routed_scale s_e / sum_S s              ``scoring: "sigmoid"``:
                                                    no bias, no bias table

**A fifth family** (``from_dict`` tells it by ``sa_config``: the language
model of ``model_type: KeyeVL2``, Kwai-Keye's Keye-VL-2.0-30B-A3B, 2026)
is the second's block under the next-token objective with a FOURTH thing
that a layer's attention is described by, WHICH of the keys its mask shows
a query it reads (``selection``: ``none`` | ``topk_indexer`` with
``index_heads``, ``index_dim``, ``index_topk``, ``index_tile``), and
rotary positions in sections (``Rotary.sections``; ``pos`` [3, T]); with
``h = RMSNorm(x)`` and ``sg`` = stop-gradient (sparse.py):

    q, k, v as the second family's; lane pair i    ``Rotary.sections``
      of a head takes the position row of its        (16 | 24 | 24 pairs
      section                                         of 64)
    qI_j = sg(h) W_qI, kI = LayerNorm(sg(h) W_kI),  16 index heads of 64
      both turned by position row 0;                  over one index key
      w = sg(h) W_w 16^-1/2 64^-1/2
    I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]), s <= t
    S_t = the ``index_topk`` keys of largest I[t, .] (all while t <
      index_topk; equal scores: the earlier key), EXACTLY
    o_i[t] = sum_{s in S_t} softmax_{S_t}(q_i[t] . k[s] d^-0.5) v[s]
    L_I = sum_t KL(sg(sum_i softmax_i[t, .]) / heads || softmax_{S_t} I[t, .])

  ``L_I`` is a loss INSIDE the layer: the cross entropy reaches no indexer
  tensor and ``L_I`` the indexer's five alone, so ``layer_grads`` makes
  their gradients from what the layer recomputes and returns ``L_I`` as
  a fourth result. The selection is a device array computed in the step:
  no ``Mask`` kind, and the layer's ``Mask`` stays causal.

**A sixth family** (``from_dict`` tells it by ``linear_attn_config``: the
block of ``model_type: kimi_linear``, Moonshot's Kimi Linear, 2025) makes
the attention's kind a LAYER's (``attention_layout``: ``kda`` | ``mla`` a
layer, three to one), on the plain residual with the third family's
feed-forward (``sigmoid_bias``, the shared expert), and uses no position
anywhere. With ``h = RMSNorm(x)``, ``conv`` a causal depthwise convolution
over positions (4 weights a channel, zero before the sequence):

    kda (delta.py), heads i of 32, K = V = 128:
      q~, k~, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))
      q_i = q~_i / |q~_i|_2 * K^-1/2,   k_i = k~_i / |k~_i|_2
      g    = -exp(A_log_i) softplus((h W_fa) W_fb + dt_bias)  log decay A CHANNEL
      beta = sigmoid(h W_b)                                   a head
      S_i[t] = (I - beta k k^T) Diag(exp g) S_i[t-1] + beta k v^T,  S_i[-1] = 0
      o_i[t] = S_i[t]^T q_i[t]           the FIRST state a position hands the
                                          next: a scan over positions, in
                                          chunks (``delta.scan``)
      a = x + concat_i(RMSNorm(o_i; g_o) * sigmoid(((h W_ga) W_gb)_i)) W_o
    mla (latent.py): q = h W_q (``q_lora_rank`` 0: no query latent, no
      norm of one), [c_kv | k_r] = h W_kva, [k_n | v] = RMSNorm(c_kv) W_kvb,
      NO rotary turn (``rope_layout`` 0), score (q_n.k_n + q_r.k_r) 192^-0.5

  ``A_log``, ``dt_bias``, the convolutions' weights [channels, 4] and ``g_o``
  are neither matrices nor norms: float32 tables under Adam, drawn at their
  own start (ps_train.py ``decay_init``).

**An eighth family** (``from_dict`` tells it by ``model_type: solar_open2``:
upstage's Solar-Open2-250B, 2026; the seventh is the third's block on the
plain residual, above) puts grouped-query attention beside the delta rule
(``attention_layout``: ``gqa`` | ``kda`` a layer, the softmax layer FIRST of
every four), every layer sparse with the third family's feed-forward, and
holds BOTH kinds of attention as a share of their heads:

    gqa: NO turn by position (``rope_layout`` 0), no q or k norm;
      o <- o * sigmoid(h W_g), W_g [hidden, heads x head_dim]: a gate a
      LANE (``attn_gate: "lane"``)
    kda: beta = 2 sigmoid(h W_b) in (0, 2) (``kda_beta_scale`` 2): the
      step's matrix has the eigenvalue 1 - beta along k, so a state can
      flip sign along a key
    ``heads_held = (first, count)``: the delta heads ``first .. first +
      count - 1`` of ``kda_heads``, as many query heads of ``n_heads`` with
      the ``n_kv_heads_held`` key-value heads they read; a head reads no
      other head, so the layer adds its heads' part of ``W_o``'s sum
      (``heads_of``; latent attention's share since the third family)

**A ninth family** (``from_dict`` tells it by ``model_type: lfm2_moe``:
LiquidAI's LFM2-8B-A1B, 2025) has a mixer that is NOT attention in three
layers of four (``attention_layout``: ``conv`` | ``gqa`` a layer, as
``layer_types`` publishes them, no period assumed), heads of 64 lanes, half a
tile, in the others, ``num_dense_layers`` leading dense layers and ONE table
for embedding and head (``tied``):

    conv (shortconv.py): (B, C, X) = split_3(h W_in); z = B * X;
      c[t] = sum_j w[:, j] z[t - 2 + j] (``conv_taps`` 3 a channel, zero
      before the sequence, no bias, no activation); a = x + (C * c) W_out
    gqa: q and k through an RMSNorm a head (``qk_norm``), every lane turned
      at ``rope_theta``; the second family's attention at ``head_dim`` 64:
      the library's kernel takes the half tile as it is
      (``attention_core``), the one pass of attn_kernels.py does not
      (``attention_pass_fused``: whole tiles) and the chain runs
    the third family's feed-forward without a shared expert
      (``sigmoid_bias``, ``shared_width`` 0); logits ``= . E^T`` with E the
      embedding table itself: the trainer pulls it by rows AND whole and
      makes one Add of both uses' gradients (ps_train.py)

**A tenth family** (``from_dict`` tells it by ``model_type:
granitemoehybrid``: ibm-granite's Granite 4.0-H Micro, 2025) mixes by a
selective state-space layer in nine layers of ten (``attention_layout``:
``ssd`` | ``gqa`` a layer, as ``layer_types`` publishes them), has NO router
(``n_experts`` 0: every feed-forward the dense MLP) and four scalars that
the nine above leave at 1:

    ssd (ssd.py): (z, xBC, dt) = split(h W_in); xBC = silu(conv_4(xBC) +
      b_c); S[t] = exp(dt_t A_i) S[t-1] + dt_t X_t (x) B_t a head [64 x 128],
      B and C shared by the heads; Y = S C + D X; the gate ``Y * silu(z)``
      INSIDE an RMSNorm over all heads' lanes; ``W_out``
    gqa: no position, no head norm, scores times ``attn_scale`` (1/64 at
      heads of 64 lanes, not 64^-1/2)
    h_0 = ``embed_scale`` E[ids]; a = x + ``residual_scale`` Mix(.), y = a +
      ``residual_scale`` MLP(.); logits = . E^T / ``logits_scale``

**A layer is described by three independent kinds**, and each selects
functions, not a family's branch: its ATTENTION (the model's ``attention``,
or where a model has more than one the LAYER's, ``attention_layout`` /
``attention_of``: ``gqa`` -> ``attention_inputs`` / ``attention_core`` /
``attention_gate`` / ``attention_output`` under ``attention_vjp``, with
``heads_layout``, ``rotary_kinds``, ``attn_gate``, ``qk_norm``; ``mla`` ->
latent.py, through the streams or, on the plain residual, through
``attention_vjp`` too; ``kda`` -> delta.py; ``conv`` -> shortconv.py;
``ssd`` -> ssd.py: ``MIXER_MODULES``), its
FEED-FORWARD (``ffn_layout``, ``dense_width``, ``shared_width``,
``scoring``, ``routed_scale`` -> ``feed_forward_vjp``: ``dense_vjp`` |
``sparse_vjp`` on ONE normed input, for every layer of ``router_input:
"ffn_input"`` whatever its residual) and its RESIDUAL (``plain`` ->
``layer_vjp`` adds the two sublayers' results; ``mhc`` ->
streams.layer_vjp writes them into the streams). ``layer_shapes`` and
``matrices`` are built from the kinds. The first two families' router and
experts each norm their own input (``router_input: "input" |
"ffn_norm"``: ``_route_layer``, ``experts_block``), kept to the operation
so that their programs lower to the text they had
(tests/test_lm_mixed.py).

``route``, ``routed_experts`` (what ``experts_block`` is around),
``gated_mlp`` (dense MLP and shared expert), ``yarn_frequencies`` and the
head are one code path for all six.

**Two objectives** (``LMConfig.objective``). ``next_token``: causal or
window masks, the loss the mean cross entropy of the next token.
``block_diffusion``: a sequence of ``L`` clean tokens is cut into blocks
of ``block_length``; ``noise`` masks each block's positions with the
block's own probability ``t``; the model's input is the noised copy and
the clean copy side by side, ``2L`` positions of which copy ``i`` and
``i + L`` share rotary position ``i`` (``Mask.positions``), under
``Mask.blockdiff``: a noised block sees itself and every clean block
before it, the clean copy is causal by blocks and never sees the noised
one. The loss is over the masked positions of the noised half alone,
each weighted ``1/t`` (``head_loss_and_grads``'s ``weights``).

**The share.** ``LMConfig.experts_held = (first, count)`` says which of
the ``n_experts`` routed experts this chip holds. The router keeps all
its outputs and its top-k, ``w_e`` is normalised over all k, and the
layer adds the part of the sum that its own experts give; what the
absent experts would add is left out (on a deployment it arrives from
the chips that hold them: no code here stands in for them). The shares
add up to the uncut layer (tests/test_lm_model.py, four of 16;
tests/test_lm_blockdiff.py, eight of 16). The vocabulary is a slice
too: ``vocab`` rows of embedding and of head, the loss over the slice.

**No token is dropped.** The (token, expert) assignments that fall on
held experts are sorted by expert and the three products run as grouped
products over the ragged groups (``grouped_product``: megablox's kernel
on a TPU, ``jax.lax.ragged_dot`` elsewhere). The held ones lie FIRST in
the sorted order, so the buffers hold ``experts_capacity`` rows (twice
the even share of the ``T * top_k`` assignments) where a sequence's held
assignments fit that, and all ``T * top_k`` rows where they do not (walked
in slabs of the short buffer's rows where one buffer of them all would be
more than ``FALLBACK_SLABS_OVER`` short ones, ``_in_slabs``): the
device chooses by the count (``routed_experts``; the short buffer gives
the numbers of the long one to the bit), and whatever the routing every
assignment is computed. Rows past the held ones belong to no group: the
kernel visits the tiles that hold a group's rows and no other, so the
products' cost follows the assignments that fell on held experts; the
gathers, masks and sums around them follow the buffer.

**Precision.** Matrix products take bfloat16 inputs and accumulate in
float32 (``mm``, ``grouped_mm``; their weight gradients come out in
float32 through a ``sink``, see ``mm``); the residual stream, norms,
softmaxes, the router's product and the loss are float32.

**Attention.** On a TPU the Pallas splash-attention kernel of
``jax.experimental.pallas.ops.tpu`` with the library's causal or local
mask or ``_BlockDiffusionMask`` (the same predicate computed in the
kernel from index arithmetic), which visits no tile that is wholly
masked, at tile sizes fitted to the call (``attention_blocks``: the mask,
the length, the heads' lanes; 512 where nothing else won); elsewhere
``blockwise_attention``, the same sum over the same unmasked blocks in
``jax.numpy``. Neither materialises a [heads, T, T] array.

Scopes (``jax.named_scope``; the backward pass runs under the same
names, layer_grads): ``mv.lm.router``, ``mv.lm.attn.full`` /
``mv.lm.attn.window`` / ``mv.lm.attn.blockdiff`` (norms, projections,
rotary, output projection) with the attention proper under
``<scope>.kernel``, ``mv.lm.experts``, ``mv.lm.head``; ``mv.lm.noise``
is the trainer's (``noise`` runs in its batch-preparation program). The
third family's: ``mv.lm.attn.mla`` (+ ``.kernel``), ``mv.lm.hc``,
``mv.lm.shared_expert``, ``mv.lm.dense_mlp`` (the fourth's too),
``mv.lm.mtp`` (+ ``.head``), and its backward programs' sums over the
sequences ``mv.lm.grad_sum``. The fourth's gate, its product, sigmoid and
multiply, forward and backward: ``mv.lm.attn.gate``. The fifth's:
``mv.lm.indexer``, ``mv.lm.select``, ``mv.lm.attn.sparse`` (+
``.kernel``), ``mv.lm.indexer.loss`` (sparse.py). The sixth's delta
layers': ``mv.lm.attn.kda``, ``mv.lm.attn.kda.conv``,
``mv.lm.attn.kda.scan`` (delta.py); its latent layer's the third's. The
ninth's convolution layers': ``mv.lm.attn.shortconv``,
``mv.lm.attn.shortconv.taps`` (shortconv.py). The tenth's state-space
layers': ``mv.lm.attn.ssd``, ``.conv``, ``.scan``, ``.gate`` (ssd.py).
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...util.log import CHECK

BF16 = jnp.bfloat16
F32 = jnp.float32

#: A layer's tensors by the kind that brings them. The matrices are pulled
#: as bfloat16 copies and their gradients are float32; the rest is small and
#: kept in float32.
GQA_MATRICES = ("wq", "wk", "wv", "wo")
ATTN_GATE = "w_attn_gate"           # with an ``LMConfig.attn_gate``
MLA_MATRICES = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")
MLA_DIRECT = ("wq", "wkv_a", "wkv_b", "wo")     # ``q_lora_rank`` 0: no latent
DENSE = ("w_gate", "w_up", "w_down")    # a dense MLP's, or the routed
#                                     experts' stacked by expert
SHARED = ("ws_gate", "ws_up", "ws_down")
LAYER_MATRICES = GQA_MATRICES + DENSE   # the first two families' layer
LAYER_SMALL = ("router", "norm_attn", "norm_ffn")
QK_NORMS = ("norm_q", "norm_k")     # with ``LMConfig.qk_norm``, float32 too
#: The kinds of a layer's attention that live in modules of their own and give
#: ``MATRICES``, ``shapes(cfg)`` and ``attention_vjp(cfg, mats, sinks, small,
#: x) -> (F(x), counts, pull)``: the kind -> the module's name.
MIXER_MODULES = {"kda": "delta", "conv": "shortconv", "ssd": "ssd"}


def mixer_module(kind: str):
    import importlib
    return importlib.import_module("." + MIXER_MODULES[kind], __package__)


@dataclasses.dataclass(frozen=True, order=True)
class Rotary:
    """The rotary positions of one kind of layer: ``theta``'s frequencies
    over the FIRST ``lanes`` lanes of a head (the others pass as they
    are); with ``yarn`` = (factor, beta_fast, beta_slow, original
    positions) blended as ``yarn_frequencies`` does; cos and sin times
    ``factor`` (YaRN's attention factor, which a score's rotated part
    then carries squared). With ``sections`` the positions come as rows
    and lane pair ``i`` takes the row of its section."""
    theta: float
    lanes: int
    yarn: Tuple[float, ...] = ()
    factor: float = 1.0
    sections: Tuple[int, ...] = ()  # lane pairs a ROW of positions, in runs:
    #                                 the positions are [rows, T] then

    def how(self) -> dict:
        """What ``_rotary`` takes beside ``theta`` and the positions."""
        how = {"lanes": self.lanes}
        if self.sections:
            how["sections"] = self.sections
        if self.yarn:
            how["inv"] = yarn_frequencies(self.theta, self.lanes, *self.yarn)
        if self.factor != 1.0:
            how["factor"] = self.factor
        return how


@dataclasses.dataclass(frozen=True)
class LMConfig:
    hidden: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    n_experts: int                  # the router's outputs
    top_k: int
    expert_width: int
    experts_held: Tuple[int, int]   # (first, count) held on this chip
    vocab: int                      # rows of embedding and head held here
    rope_layout: Tuple[int, ...]    # per layer: 1 rotary, 0 none
    window_layout: Tuple[int, ...]  # per layer: 1 sliding window, 0 full
    window: int
    rope_theta: float
    eps: float
    loss_block: int = 2048          # tokens a block of the head's loss
    activation: str = "relu"        # the experts': "relu" | "silu"
    router_input: str = "input"     # "input": the layer's raw input;
    #                                 "ffn_norm": the post-attention stream
    #                                 through the experts' norm, applied by
    #                                 the router itself; "ffn_input": the
    #                                 feed-forward's ONE normed input, which
    #                                 router, experts and shared expert all
    #                                 read (``feed_forward_vjp``)
    qk_norm: bool = False           # per-head RMSNorm of q and k
    objective: str = "next_token"   # | "block_diffusion"
    block_length: int = 0           # block diffusion: positions a block
    t_min: float = 0.0              # block diffusion: t ~ U(t_min, 1]
    # -- a layer by three independent kinds: its attention, ... -------------
    attention: str = "gqa"          # | "mla": latent attention (latent.py)
    heads_layout: Tuple[int, ...] = ()  # gqa, per layer: its query heads;
    #                                 (): ``n_heads`` in every layer
    rotary_kinds: Tuple[Rotary, ...] = ()   # gqa: (the full layers', the
    #                                 window layers'); (): ``rope_theta``
    #                                 over every lane
    attn_gate: str = "none"         # | "head": each head's output times
    #                                 sigmoid(h W_g), W_g [hidden, heads] |
    #                                 "lane": each LANE's, W_g [hidden,
    #                                 heads x head_dim]
    heads_held: Tuple[int, int] = (0, 0)    # (first, count) of the heads
    #                                 held here, of every kind of attention
    #                                 the model has (mla's ``n_heads``; kda's
    #                                 ``kda_heads``; gqa's ``n_heads`` query
    #                                 heads with the key-value heads they
    #                                 read); (0, 0): all
    q_lora_rank: int = 0            # mla: the five latent sizes
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    yarn: Tuple[float, ...] = ()    # (factor, beta_fast, beta_slow, original
    #                                 positions, mscale, mscale_all_dim)
    # -- ... its feed-forward, ... -------------------------------------------
    ffn_layout: Tuple[int, ...] = ()    # per layer: 1 sparse, 0 dense MLP;
    #                                 (): every layer sparse
    dense_width: int = 0
    shared_width: int = 0           # the shared expert's, 0: none
    scoring: str = "softmax"        # | "sigmoid": chosen and weighed by
    #                                 score | "sigmoid_bias": chosen by score
    #                                 + a bias the server keeps, weighed by
    #                                 score alone
    routed_scale: float = 1.0
    bias_rate: float = 0.0          # the bias's step after each step
    # -- ... and its residual ------------------------------------------------
    residual: str = "plain"         # | "mhc": hc_mult streams (streams.py)
    hc_mult: int = 1
    hc_iters: int = 0               # Sinkhorn rounds
    hc_eps: float = 0.0
    hc_clamp: Tuple[float, float] = (0.0, 0.0)
    mtp_layers: int = 0             # multi-token modules held here (mtp.py)
    mtp_weight: float = 0.0         # the second loss's weight
    # -- and, of its attention, WHICH KEYS a query reads among those its
    # mask shows it ------------------------------------------------------------
    selection: str = "none"         # | "topk_indexer": the ``index_topk``
    #                                 keys a learned indexer scores highest
    #                                 (sparse.py), with the indexer's sizes
    index_heads: int = 0
    index_dim: int = 0
    index_topk: int = 0
    index_tile: int = 0             # the selection's tiles, a side
    # -- the attention's kind as a LAYER's, where a model has more than one --------
    attention_layout: Tuple[str, ...] = ()  # per layer: "mla" | "gqa" |
    #                                 "kda" (the delta rule's scan,
    #                                 delta.py) | "conv" (the gated short
    #                                 convolution, shortconv.py) | "ssd" (the
    #                                 selective state-space mixer, ssd.py);
    #                                 (): ``attention`` in every layer
    kda_heads: int = 0              # kda: heads, each a state [d x d]
    kda_head_dim: int = 0
    kda_conv: int = 0               # kda: the short convolution's weights a
    #                                 channel
    kda_beta_scale: int = 1         # kda: beta = scale * sigmoid(h W_b); 2
    #                                 lets a state flip sign along a key
    conv_taps: int = 0              # conv: the positions a channel reads
    tied: bool = False              # ONE table is embedding and head
    ssd_heads: int = 0              # ssd: heads, each a state [P x N] under
    #                                 one scalar decay a position
    ssd_head_dim: int = 0           # ssd: P, a head's lanes
    ssd_state: int = 0              # ssd: N, the state's other side
    ssd_groups: int = 0             # ssd: groups of B and C (1: every head
    #                                 reads the same two)
    ssd_conv: int = 0               # ssd: the convolution's taps a channel
    ssd_chunk: int = 0              # ssd: positions a chunk of the scan, the
    #                                 blocking and no part of the function;
    #                                 0: ``ssd.CHUNK``
    # -- four scalars (Granite's multipliers), each at what every other model
    # has and folded into an operand that is there ----------------------------
    residual_scale: float = 1.0     # a = x + r Mix(.), y = a + r F(.)
    attn_scale: float = 0.0         # gqa: the scores' scale; 0: head_dim^-0.5
    logits_scale: float = 1.0       # logits = . E^T / logits_scale
    embed_scale: float = 1.0        # the first layer's input = scale * rows

    @property
    def n_layers(self) -> int:
        return len(self.rope_layout)

    @property
    def small_names(self) -> Tuple[str, ...]:
        """A layer's float32 tensors, by name (the first two families)."""
        return LAYER_SMALL + (QK_NORMS if self.qk_norm else ())

    @property
    def n_heads_held(self) -> int:
        return self.heads_held[1] or self.n_heads

    @property
    def n_kv_heads_held(self) -> int:
        """gqa: the key-value heads that the held query heads read (query
        head ``i`` reads ``i // (n_heads / n_kv_heads)``)."""
        return self.n_kv_heads * self.n_heads_held // self.n_heads

    @property
    def kda_heads_held(self) -> int:
        return self.heads_held[1] or self.kda_heads

    def heads_of(self, layer: int) -> Tuple[int, int]:
        """``(held here, all)`` of the heads of the layer's attention."""
        kind = self.attention_of(layer)
        if kind == "conv":      # a mixer without heads
            return 0, 0
        if kind == "ssd":       # whole here: the gated norm's mean runs
            return self.ssd_heads, self.ssd_heads   # over all of them
        if kind == "kda":
            return self.kda_heads_held, self.kda_heads
        if kind == "gqa" and self.heads_layout:
            return (self.heads_layout[layer],) * 2
        return self.n_heads_held, self.n_heads

    def sparse(self, layer: int) -> int:
        return self.ffn_layout[layer] if self.ffn_layout else 1

    def heads(self, layer: int) -> int:
        """The layer's query heads (grouped-query attention)."""
        return self.heads_layout[layer] if self.heads_layout \
            else self.n_heads_held

    def attention_of(self, layer: int) -> str:
        """The kind of the layer's attention: ``gqa`` | ``mla`` | ``kda`` |
        ``conv`` | ``ssd``."""
        return self.attention_layout[layer] if self.attention_layout \
            else self.attention

    def rotary(self, rope, window):
        """What a layer of this kind hands ``attention_inputs`` as its
        rotary positions: its ``Rotary``, or where the model has one kind
        whether the layer is rotary at all."""
        if rope and self.rotary_kinds:
            return self.rotary_kinds[bool(window)]
        return bool(rope)

    @property
    def one_ffn_input(self) -> bool:
        """Whether router, experts and shared expert (or the dense MLP)
        read one normed input: ``feed_forward_vjp``."""
        return self.router_input == "ffn_input"

    def layer_kinds(self) -> Tuple[tuple, ...]:
        """Per layer what its two programs are built from: ``(rotary,
        window)``; with an ``ffn_layout`` ``(rotary, window, sparse)``; and
        with a ``heads_layout`` the layer's query heads fourth, with an
        ``attention_layout`` the kind of the layer's attention."""
        kinds = tuple(zip(self.rope_layout, self.window_layout))
        if self.ffn_layout or self.heads_layout:
            kinds = tuple(k + (self.sparse(i),) for i, k in enumerate(kinds))
        if self.heads_layout:
            kinds = tuple(k + (h,) for k, h in zip(kinds, self.heads_layout))
        if self.attention_layout:
            kinds = tuple(k + (a,) for k, a in zip(kinds,
                                                   self.attention_layout))
        return kinds

    def matrices(self, layer: int = 0) -> Tuple[str, ...]:
        """The layer's tensors pulled as bfloat16 copies, by name: its
        attention's, then its feed-forward's."""
        kind = self.attention_of(layer)
        if kind in MIXER_MODULES:
            attention = mixer_module(kind).MATRICES
        elif kind == "mla":
            attention = MLA_MATRICES if self.q_lora_rank else MLA_DIRECT
        else:
            attention = GQA_MATRICES + (
                (ATTN_GATE,) if self.attn_gate != "none" else ())
        shared = self.sparse(layer) and self.shared_width
        if self.selection != "none":
            from . import sparse
            return attention + DENSE + sparse.INDEX_MATRICES
        return attention + DENSE + (SHARED if shared else ())

    @property
    def mask_id(self) -> int:
        """Block diffusion's mask token: the slice's last row."""
        return self.vocab - 1

    def layer_mask(self, windowed, seq_len: int) -> "Mask":
        """The mask of a layer over ``seq_len`` tokens a sequence."""
        if self.objective == "block_diffusion":
            return Mask.blockdiff(seq_len, self.block_length)
        return Mask.of(self.window if windowed else 0)

    @classmethod
    def from_dict(cls, c: dict) -> "LMConfig":
        """From a configuration in a published ``config.json``'s keys, the
        family told by them: SmallThinker's
        (benchmark/configs/smallthinker-21ba3b-l4.json; below) or, with
        ``num_experts``, Qwen3-MoE's (``_from_qwen3_moe``). In both the
        key that counts the experts is the number HELD and
        ``router_outputs`` the published number the router still has."""
        if c.get("model_type") == "solar_open2":
            return cls._from_solar(c)
        if c.get("model_type") == "lfm2_moe":
            return cls._from_lfm2(c)
        if c.get("model_type") == "granitemoehybrid":
            return cls._from_granite(c)
        if "linear_attn_config" in c:
            return cls._from_kda(c)
        if "kv_lora_rank" in c:
            return cls._from_mla(c)
        if "num_attention_heads_per_layer" in c:
            return cls._from_laguna(c)
        if "sa_config" in c:
            return cls._from_indexed(c)
        if "num_experts" in c:
            return cls._from_qwen3_moe(c)
        n = int(c["num_hidden_layers"])
        return cls(
            hidden=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            n_experts=int(c["router_outputs"]),
            top_k=int(c["moe_num_active_primary_experts"]),
            expert_width=int(c["moe_ffn_hidden_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["moe_num_primary_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=tuple(int(v) for v in c["rope_layout"][:n]),
            window_layout=tuple(
                int(v) for v in c["sliding_window_layout"][:n]),
            window=int(c["sliding_window_size"]),
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)))

    @classmethod
    def _from_qwen3_moe(cls, c: dict) -> "LMConfig":
        """Qwen3-MoE's block as ``model_type: sdar_moe`` configures it
        (benchmark/configs/sdar-30b-a3b-l6.json): every layer rotary under
        one mask, silu experts routed on the normed post-attention stream,
        q and k norms. ``objective`` (a dict with ``kind``,
        ``block_length``, ``t_min``) is the training objective, which the
        published config does not state."""
        n = int(c["num_hidden_layers"])
        CHECK(not c.get("use_sliding_window") and not c.get("mlp_only_layers")
              and int(c.get("decoder_sparse_step", 1)) == 1
              and c.get("norm_topk_prob", True),
              "only the block every layer of which is sparse, unwindowed "
              "and normalises its top-k is written down here")
        objective = c.get("objective", {"kind": "next_token"})
        return cls(
            hidden=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c["head_dim"]),
            n_experts=int(c["router_outputs"]),
            top_k=int(c["num_experts_per_tok"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["num_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=(1,) * n, window_layout=(0,) * n, window=0,
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c["hidden_act"]), router_input="ffn_norm",
            qk_norm=True,
            objective=str(objective["kind"]),
            block_length=int(objective.get("block_length", 0)),
            t_min=float(objective.get("t_min", 0.0)))

    @classmethod
    def _from_mla(cls, c: dict) -> "LMConfig":
        """DeepSeek-V3's block: latent attention (with a query latent where
        ``q_lora_rank`` is not null), ``first_k_dense_replace`` dense layers
        and then sparse ones with a shared expert, a sigmoid router chosen
        through a bias, a multi-token module. Its residual and its rotary
        positions are told by the keys: with ``hc_mult`` that many residual
        streams (``model_type: xing4_0``,
        benchmark/configs/xing4-29b-a4b-l5.json), without it the plain
        residual (``model_type: glm4_moe_lite``,
        benchmark/configs/glm47-flash-30b-a3b-l5.json); under
        ``rope_scaling`` YaRN's frequencies and scale, with none (null)
        ``rope_theta``'s own. A config without ``scoring_func`` scores by
        sigmoid (``noaux_tc`` is DeepSeek-V3's router). The keys that count
        experts and heads give the numbers HELD; ``router_outputs`` and
        ``attention_heads`` the published ones. ``router_bias_rate`` and
        ``mtp_loss_weight`` the published config does not state."""
        n, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
        y = c.get("rope_scaling")
        CHECK(c.get("scoring_func", "sigmoid") == "sigmoid"
              and c["topk_method"] == "noaux_tc"
              and int(c["n_group"]) == 1 and c["norm_topk_prob"]
              and int(c.get("moe_layer_freq", 1)) == 1
              and (y is None or y["type"] == "yarn")
              and float(c.get("partial_rotary_factor", 1)) == 1,
              "only the block whose router scores by sigmoid, chooses "
              "through a bias in one group and normalises its top-k, whose "
              "latent attention turns every rotary lane, under YaRN or under "
              "no scaling, is written down here")
        held = int(c["num_attention_heads"])
        streams = "hc_mult" in c
        return cls(
            hidden=int(c["hidden_size"]),
            n_heads=int(c.get("attention_heads", held)), n_kv_heads=0,
            head_dim=int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"]),
            n_experts=int(c["router_outputs"]),
            top_k=int(c["num_experts_per_tok"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["n_routed_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=(1,) * n, window_layout=(0,) * n, window=0,
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c["hidden_act"]),
            router_input="ffn_norm" if streams else "ffn_input",
            attention="mla",
            heads_held=(int(c.get("first_head_held", 0)), held),
            q_lora_rank=int(c["q_lora_rank"] or 0),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_dim=int(c["qk_nope_head_dim"]),
            qk_rope_dim=int(c["qk_rope_head_dim"]),
            v_head_dim=int(c["v_head_dim"]),
            yarn=(float(y["factor"]), float(y["beta_fast"]),
                  float(y["beta_slow"]),
                  float(y["original_max_position_embeddings"]),
                  float(y["mscale"]), float(y["mscale_all_dim"]))
            if y else (),
            ffn_layout=(0,) * dense + (1,) * (n - dense),
            dense_width=int(c["intermediate_size"]),
            shared_width=int(c["n_shared_experts"])
            * int(c["moe_intermediate_size"]),
            scoring="sigmoid_bias",
            routed_scale=float(c["routed_scaling_factor"]),
            bias_rate=float(c["router_bias_rate"]),
            mtp_layers=int(c["num_nextn_predict_layers"]),
            mtp_weight=float(c["mtp_loss_weight"]),
            **(dict(residual="mhc", hc_mult=int(c["hc_mult"]),
                    hc_iters=int(c["hc_sinkhorn_iters"]),
                    hc_eps=float(c["hc_eps"]),
                    hc_clamp=(float(c["mhc_h_res_clamp_min"]),
                              float(c["mhc_h_res_clamp_max"])))
               if streams else {}))

    @classmethod
    def _from_laguna(cls, c: dict) -> "LMConfig":
        """The block of ``model_type: laguna`` (poolside's Laguna-XS.2,
        benchmark/configs/laguna-xs2-33b-a3b-l5.json): grouped-query
        attention whose layers are of two kinds (``layer_types``), each
        with its own query heads (``num_attention_heads_per_layer``) and
        its own rotary positions (``rope_parameters`` by kind), a per-head
        output gate (``gating``); ``mlp_layer_types`` says which layers are
        dense; the sparse ones score by sigmoid with no bias and have a
        shared expert. ``num_experts`` gives the experts HELD and
        ``router_outputs`` the published number."""
        n = int(c["num_hidden_layers"])
        kinds = {"full_attention": 0, "sliding_attention": 1}
        CHECK(c["gating"] and not c["moe_apply_router_weight_on_input"]
              and not c["attention_bias"],
              "only the block with the output gate, the router's weights on "
              "the experts' outputs and no attention bias is written down "
              "here")
        d = int(c["head_dim"])

        def rotary(p):
            yarn = p["rope_type"] == "yarn"
            CHECK(yarn or p["rope_type"] == "default",
                  f"unknown rope_type {p['rope_type']!r}")
            return Rotary(
                theta=float(p["rope_theta"]),
                lanes=int(d * float(p.get("partial_rotary_factor", 1))),
                yarn=(float(p["factor"]), float(p["beta_fast"]),
                      float(p["beta_slow"]),
                      float(p["original_max_position_embeddings"]))
                if yarn else (),
                factor=float(p.get("attention_factor", 1.0)) if yarn else 1.0)

        by_kind = tuple(rotary(c["rope_parameters"][k]) for k in kinds)
        return cls(
            hidden=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]),
            n_kv_heads=int(c["num_key_value_heads"]), head_dim=d,
            n_experts=int(c["router_outputs"]),
            top_k=int(c["num_experts_per_tok"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["num_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=(1,) * n,
            window_layout=tuple(kinds[k] for k in c["layer_types"][:n]),
            window=int(c["sliding_window"]),
            rope_theta=by_kind[0].theta, eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c.get("hidden_act", "silu")),
            router_input="ffn_input",
            heads_layout=tuple(
                int(h) for h in c["num_attention_heads_per_layer"][:n]),
            rotary_kinds=by_kind, attn_gate="head",
            ffn_layout=tuple(int(k == "sparse")
                             for k in c["mlp_layer_types"][:n]),
            dense_width=int(c["intermediate_size"]),
            shared_width=int(c["shared_expert_intermediate_size"]),
            scoring="sigmoid",
            routed_scale=float(c["moe_routed_scaling_factor"]))

    @classmethod
    def _from_indexed(cls, c: dict) -> "LMConfig":
        """Qwen3-MoE's block as ``model_type: KeyeVL2`` configures its
        language model (benchmark/configs/keye-vl2-30b-a3b-lm.json):
        ``_from_qwen3_moe``'s block under the next-token objective, with
        rotary positions in sections (``rope_scaling.mrope_section``) and
        in every layer an indexer that selects ``sa_config.topk`` keys a
        query (sparse.py). Router, experts and shared input are one normed
        stream (``router_input: "ffn_input"``: the same sums as
        ``"ffn_norm"``'s, through ``feed_forward_vjp``)."""
        base = cls._from_qwen3_moe(c)
        sa, scaling = c["sa_config"], c["rope_scaling"]
        CHECK(base.objective == "next_token"
              and int(sa["indexer_num_kv_heads"]) == 1
              and scaling["rope_type"] == "default"
              and int(sa["q_chunk_size"]) == int(sa["kv_chunk_size"]),
              "only the indexer with one key head and square chunks, on "
              "plain sectioned rotary positions under the next-token "
              "objective, is written down here")
        sections = tuple(int(s) for s in scaling["mrope_section"])
        turned = Rotary(theta=base.rope_theta, lanes=base.head_dim,
                        sections=sections)
        return dataclasses.replace(
            base, router_input="ffn_input", rotary_kinds=(turned, turned),
            selection="topk_indexer",
            index_heads=int(sa["indexer_num_heads"]),
            index_dim=int(sa["indexer_head_dim"]),
            index_topk=int(sa["topk"]), index_tile=int(sa["q_chunk_size"]))

    @classmethod
    def _from_kda(cls, c: dict) -> "LMConfig":
        """The block of ``model_type: kimi_linear`` (Moonshot's Kimi Linear,
        benchmark/configs/kimi-linear-48b-a3b-l5.json): the attention's
        kind is a LAYER's, ``linear_attn_config`` listing (from 1) the
        layers that mix the sequence by the delta rule's scan (delta.py)
        and those of latent attention, which here has no query latent
        (``q_lora_rank`` null) and no rotary positions (``mla_use_nope``):
        the model uses no position anywhere. ``first_k_dense_replace``
        dense layers, then sparse ones with a shared expert under a sigmoid
        router that chooses through a bias the server keeps and
        renormalises its top-k, on the plain residual. ``num_experts``
        gives the experts HELD and ``router_outputs`` the published number;
        ``router_bias_rate`` the published config does not state."""
        n, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
        linear = c["linear_attn_config"]
        delta, full = (set(linear[k]) for k in ("kda_layers",
                                                "full_attn_layers"))
        CHECK(c["moe_router_activation_func"] == "sigmoid"
              and c["moe_renormalize"] and int(c["num_expert_group"]) == 1
              and c["mla_use_nope"] and c["q_lora_rank"] is None
              and c.get("rope_scaling") is None
              and int(c.get("moe_layer_freq", 1)) == 1
              and not c.get("num_nextn_predict_layers")
              and all((i in delta) != (i in full) for i in range(1, n + 1)),
              "only the block whose router scores by sigmoid in one group "
              "and renormalises its top-k, whose latent attention has no "
              "query latent and no positions, whose every layer is of one "
              "of the two kinds, and which holds no multi-token module, is "
              "written down here")
        nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
        return cls(
            hidden=int(c["hidden_size"]),
            n_heads=int(c["num_attention_heads"]), n_kv_heads=0,
            head_dim=nope + rope,
            n_experts=int(c["router_outputs"]),
            top_k=int(c["num_experts_per_token"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["num_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=(0,) * n, window_layout=(0,) * n, window=0,
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c["hidden_act"]), router_input="ffn_input",
            attention="mla",
            attention_layout=tuple("kda" if i in delta else "mla"
                                   for i in range(1, n + 1)),
            kv_lora_rank=int(c["kv_lora_rank"]),
            qk_nope_dim=nope, qk_rope_dim=rope,
            v_head_dim=int(c["v_head_dim"]),
            ffn_layout=(0,) * dense + (1,) * (n - dense),
            dense_width=int(c["intermediate_size"]),
            shared_width=int(c["num_shared_experts"])
            * int(c["moe_intermediate_size"]),
            scoring="sigmoid_bias",
            routed_scale=float(c["routed_scaling_factor"]),
            bias_rate=float(c["router_bias_rate"]),
            kda_heads=int(linear["num_heads"]),
            kda_head_dim=int(linear["head_dim"]),
            kda_conv=int(linear["short_conv_kernel_size"]))

    @classmethod
    def _from_solar(cls, c: dict) -> "LMConfig":
        """The block of ``model_type: solar_open2`` (upstage's
        Solar-Open2-250B, benchmark/configs/solar-open2-250b-a15b-l4.json):
        of every ``gqa_interval + 1`` layers the FIRST is grouped-query
        softmax attention with no positions (``use_rope`` false) and an
        output gate (``use_gqa_gate``; ``attn_gate``: a gate a ``"lane"``,
        the reading taken, or a ``"head"``), the others the delta rule's
        scan (delta.py) with ``beta = 2 sigmoid`` (``kda_allow_neg_eigval``)
        and low-rank decay and gate projections (``kda_use_full_proj``
        false); every layer sparse with a shared expert under DeepSeek-V3's
        router. The keys that count experts and heads give the numbers HELD
        (``num_attention_heads`` query heads from ``first_head_held`` with
        the ``num_key_value_heads`` they read, ``linear_attn_config.
        num_heads`` delta heads from the same first); ``router_outputs``,
        ``attention_heads``, ``key_value_heads`` and
        ``linear_attention_heads`` the published ones. ``router_bias_rate``
        and ``hidden_act`` the published config does not state."""
        n, linear = int(c["num_hidden_layers"]), c["linear_attn_config"]
        full, every = list(c["gqa_layers"]), int(c["gqa_interval"]) + 1
        heads, kv = int(c["attention_heads"]), int(c["key_value_heads"])
        first, held = int(c.get("first_head_held", 0)), int(
            c["num_attention_heads"])
        CHECK(not c["use_rope"] and int(c["first_k_dense_replace"]) == 0
              and full == list(range(0, full[-1] + 1, every))
              and not c["kda_use_full_proj"]
              and linear["num_kv_heads"] is None and c["norm_topk_prob"]
              and c.get("scoring_func", "sigmoid") == "sigmoid",
              "only the block whose softmax layers use no position and lead "
              "each period, whose every layer is sparse, whose delta layers "
              "have low-rank decay and gate projections and as many value "
              "heads as key heads, and whose router scores by sigmoid and "
              "normalises its top-k, is written down here")
        CHECK(heads % kv == 0 and first % (heads // kv) == 0
              and held % (heads // kv) == 0
              and int(c["num_key_value_heads"]) == held * kv // heads
              and int(linear["num_heads"]) == held
              and int(c["linear_attention_heads"]) == heads,
              "the held heads are whole groups of query heads with the "
              "key-value heads they read, and as many delta heads")
        return cls(
            hidden=int(c["hidden_size"]), n_heads=heads, n_kv_heads=kv,
            head_dim=int(c["head_dim"]),
            n_experts=int(c["router_outputs"]),
            top_k=int(c["num_experts_per_tok"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["n_routed_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=(0,) * n, window_layout=(0,) * n, window=0,
            rope_theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c.get("hidden_act", "silu")),
            router_input="ffn_input",
            attn_gate=str(c.get("attn_gate", "lane"))
            if c["use_gqa_gate"] else "none",
            heads_held=(first, held),
            attention_layout=tuple("gqa" if i in full else "kda"
                                   for i in range(n)),
            ffn_layout=(1,) * n,
            dense_width=int(c["intermediate_size"]),
            shared_width=int(c["n_shared_experts"])
            * int(c["moe_intermediate_size"]),
            scoring="sigmoid_bias",
            routed_scale=float(c["routed_scaling_factor"]),
            bias_rate=float(c["router_bias_rate"]),
            kda_heads=int(c["linear_attention_heads"]),
            kda_head_dim=int(linear["head_dim"]),
            kda_conv=int(linear["short_conv_kernel_size"]),
            kda_beta_scale=2 if c["kda_allow_neg_eigval"] else 1)

    @classmethod
    def _from_lfm2(cls, c: dict) -> "LMConfig":
        """The block of ``model_type: lfm2_moe`` (LiquidAI's LFM2-8B-A1B,
        benchmark/configs/lfm2-8b-a1b-l8.json): ``layer_types`` says layer
        by layer, as published and read up to ``num_hidden_layers``, whether
        the mixer is a gated short convolution of ``conv_L_cache`` taps
        (shortconv.py) or grouped-query attention with an RMSNorm a head on
        q and k and every lane turned at ``rope_theta``; the first
        ``num_dense_layers`` layers have a dense MLP, the others silu experts
        under a sigmoid router that chooses through a bias the server keeps
        (``use_expert_bias``) and normalises its top-k, with no shared
        expert; embedding and head are ONE table (``tie_word_embeddings``).
        ``num_experts`` gives the experts HELD and ``router_outputs`` the
        published number; ``head_dim`` (hidden / heads when absent),
        ``tie_word_embeddings``, ``router_bias_rate`` and ``hidden_act`` the
        published config does not state."""
        n, dense = int(c["num_hidden_layers"]), int(c["num_dense_layers"])
        kinds = {"conv": "conv", "full_attention": "gqa"}
        types = list(c["layer_types"][:n])
        CHECK(not c["conv_bias"] and c["norm_topk_prob"]
              and c["use_expert_bias"] and len(types) == n
              and all(t in kinds for t in types) and 0 <= dense <= n,
              "only the block whose convolutions have no bias, whose router "
              "chooses through a bias and normalises its top-k, and whose "
              "every layer is a convolution or full attention, is written "
              "down here")
        layout = tuple(kinds[t] for t in types)
        hidden, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
        return cls(
            hidden=hidden, n_heads=heads,
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or hidden // heads),
            n_experts=int(c["router_outputs"]),
            top_k=int(c["num_experts_per_tok"]),
            expert_width=int(c["moe_intermediate_size"]),
            experts_held=(int(c.get("first_expert_held", 0)),
                          int(c["num_experts"])),
            vocab=int(c["vocab_size"]),
            rope_layout=tuple(int(k == "gqa") for k in layout),
            window_layout=(0,) * n, window=0,
            rope_theta=float(c["rope_theta"]), eps=float(c["norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c.get("hidden_act", "silu")),
            router_input="ffn_input", qk_norm=True,
            attention_layout=layout,
            ffn_layout=(0,) * dense + (1,) * (n - dense),
            dense_width=int(c["intermediate_size"]), shared_width=0,
            scoring="sigmoid_bias",
            routed_scale=float(c["routed_scaling_factor"]),
            bias_rate=float(c["router_bias_rate"]),
            conv_taps=int(c["conv_L_cache"]),
            tied=bool(c.get("tie_word_embeddings", True)))

    @classmethod
    def _from_granite(cls, c: dict) -> "LMConfig":
        """The block of ``model_type: granitemoehybrid`` as Granite 4.0-H
        Micro has it (ibm-granite, benchmark/configs/
        granite-4.0-h-micro-l10.json): ``layer_types`` says layer by layer,
        as published and read up to ``num_hidden_layers``, whether the mixer
        is a selective state-space layer (``mamba``: ssd.py) or grouped-query
        attention with NO position, no head norm and the scores' scale
        ``attention_multiplier``; every layer's feed-forward is the dense
        silu-gated MLP of ``shared_intermediate_size`` and there is NO router
        (``num_local_experts`` 0: ``n_experts`` 0, ``top_k`` 0, nothing
        held); embedding and head are ONE table; the embedding's rows times
        ``embedding_multiplier``, each sublayer's result times
        ``residual_multiplier``, the logits over ``logits_scaling``.
        ``head_dim`` (hidden / heads when absent) and ``scan_chunk`` (the
        scan's blocking; ``mamba_chunk_size`` is the released kernels' and is
        not read) the published config does not state."""
        n = int(c["num_hidden_layers"])
        kinds = {"mamba": "ssd", "attention": "gqa"}
        types = list(c["layer_types"][:n])
        hidden, heads = int(c["hidden_size"]), int(c["num_attention_heads"])
        ssd_heads, lanes = int(c["mamba_n_heads"]), int(c["mamba_d_head"])
        CHECK(int(c["num_local_experts"]) == 0
              and int(c["num_experts_per_tok"]) == 0
              and int(c["mamba_n_groups"]) == 1 and not c["mamba_proj_bias"]
              and c["mamba_conv_bias"]
              and c["position_embedding_type"] == "nope"
              and not c["attention_bias"] and len(types) == n
              and all(t in kinds for t in types)
              and int(c["mamba_expand"]) * hidden == ssd_heads * lanes,
              "only the block with no experts, one group of B and C, a "
              "convolution with a bias and projections without, attention "
              "with no positions and no bias, and an inner width of "
              "mamba_expand * hidden_size = mamba_n_heads * mamba_d_head, "
              "whose every layer is mamba or attention, is written down here")
        return cls(
            hidden=hidden, n_heads=heads,
            n_kv_heads=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or hidden // heads),
            n_experts=0, top_k=0, expert_width=0, experts_held=(0, 0),
            vocab=int(c["vocab_size"]),
            rope_layout=(0,) * n, window_layout=(0,) * n, window=0,
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            loss_block=int(c.get("loss_block", 2048)),
            activation=str(c["hidden_act"]), router_input="ffn_input",
            attention_layout=tuple(kinds[t] for t in types),
            ffn_layout=(0,) * n,
            dense_width=int(c["shared_intermediate_size"]),
            tied=bool(c["tie_word_embeddings"]),
            ssd_heads=ssd_heads, ssd_head_dim=lanes,
            ssd_state=int(c["mamba_d_state"]),
            ssd_groups=int(c["mamba_n_groups"]),
            ssd_conv=int(c["mamba_d_conv"]),
            ssd_chunk=int(c.get("scan_chunk", 0)),
            residual_scale=float(c["residual_multiplier"]),
            attn_scale=float(c["attention_multiplier"]),
            logits_scale=float(c["logits_scaling"]),
            embed_scale=float(c["embedding_multiplier"]))

    def layer_shapes(self, layer: int = 0) -> dict:
        """Every tensor of one layer as the server stores it, built from
        the layer's kinds: a matrix table's (rows, columns) or a small
        tensor's (size,). In order: the attention's, the two norms, the
        stream mixers' (``residual: "mhc"``), the feed-forward's, the q
        and k norms, the indexer's five (``selection``). The experts' three
        are stacked by expert along the rows."""
        h, d = self.hidden, self.head_dim
        kind = self.attention_of(layer)
        if kind in MIXER_MODULES:
            shapes = mixer_module(kind).shapes(self)
        elif kind == "mla":
            # over the held heads: ``wq_b``, ``wkv_b``, ``wo`` cut by head,
            # each head's columns together, ``[nope | rope]``, ``[k nope | v]``
            heads = self.n_heads_held
            shapes = {
                "wq_a": (h, self.q_lora_rank),
                "norm_q_a": (self.q_lora_rank,),
                "wq_b": (self.q_lora_rank, heads * d)} if self.q_lora_rank \
                else {"wq": (h, heads * d)}
            shapes.update({
                "wkv_a": (h, self.kv_lora_rank + self.qk_rope_dim),
                "norm_kv_a": (self.kv_lora_rank,),
                "wkv_b": (self.kv_lora_rank,
                          heads * (self.qk_nope_dim + self.v_head_dim)),
                "wo": (heads * self.v_head_dim, h)})
        else:
            # over the held query heads and the key-value heads they read
            heads, kv = self.heads(layer), self.n_kv_heads_held
            shapes = {"wq": (h, heads * d), "wk": (h, kv * d),
                      "wv": (h, kv * d), "wo": (heads * d, h)}
            if self.attn_gate != "none":
                shapes[ATTN_GATE] = (
                    h, heads * (d if self.attn_gate == "lane" else 1))
        shapes.update({"norm_attn": (h,), "norm_ffn": (h,)})
        if self.residual == "mhc":
            # a sublayer's ``phi`` a coefficient a row, ``a`` three scalars
            n = self.hc_mult
            coefficients = 2 * n + n * n
            for sub in ("hc_attn", "hc_ffn"):
                shapes.update({f"{sub}_phi": (coefficients, n * h),
                               f"{sub}_b": (coefficients,),
                               f"{sub}_a": (3,)})
        if not self.sparse(layer):
            w = self.dense_width
            shapes.update({"w_gate": (h, w), "w_up": (h, w),
                           "w_down": (w, h)})
        else:
            e, w = self.experts_held[1], self.expert_width
            shapes["router"] = (h, self.n_experts)
            if self.scoring == "sigmoid_bias":
                shapes["router_bias"] = (self.n_experts,)
            shapes.update({"w_gate": (e * h, w), "w_up": (e * h, w),
                           "w_down": (e * w, h)})
            if self.shared_width:
                s = self.shared_width
                shapes.update({"ws_gate": (h, s), "ws_up": (h, s),
                               "ws_down": (s, h)})
        if self.qk_norm and kind == "gqa":
            shapes.update({n: (d,) for n in QK_NORMS})
        if self.selection != "none":
            from . import sparse
            shapes.update(sparse.shapes(self))
        return shapes

    def mtp_shapes(self) -> dict:
        """The multi-token module's own tensors beside its sparse layer's:
        the projection of ``[normed stream ; normed next embedding]``, the
        two norms before it and the norm before the (shared) head."""
        h = self.hidden
        return {"proj": (2 * h, h), "norm_h": (h,), "norm_e": (h,),
                "final_norm": (h,)}

    def parameters(self) -> int:
        def size(shapes):
            return sum(int(np.prod(s)) for s in shapes.values())
        layers = sum(size(self.layer_shapes(i)) for i in range(self.n_layers))
        module = self.mtp_layers * (size(self.mtp_shapes()) + size(
            self.layer_shapes(self.n_layers - 1))) if self.mtp_layers else 0
        tables = 1 if self.tied else 2      # of [vocab, hidden]
        return (layers + module + tables * self.vocab * self.hidden
                + self.hidden)


# -- products -------------------------------------------------------------

@jax.custom_vjp
def mm(x, w, sink):
    """``x @ w`` with bfloat16 inputs and a float32 result. ``w`` is the
    worker's bfloat16 copy of a table and is not differentiated;
    ``sink`` is a float32 array of ``w``'s shape that is never read and
    whose cotangent IS the weight gradient, accumulated and returned in
    float32 (a bfloat16 operand's own cotangent would be rounded to
    bfloat16). Built as zeros inside the program that differentiates,
    it costs no memory."""
    del sink
    return jnp.dot(x.astype(BF16), w, preferred_element_type=F32)


def _mm_fwd(x, w, sink):
    return mm(x, w, sink), (x.astype(BF16), w, jnp.zeros((), x.dtype))


def _mm_bwd(res, g):
    xb, w, like = res
    gb = g.astype(BF16)
    dx = jnp.dot(gb, w.T, preferred_element_type=F32).astype(like.dtype)
    dw = jnp.dot(xb.reshape(-1, xb.shape[-1]).T,
                 gb.reshape(-1, gb.shape[-1]), preferred_element_type=F32)
    return dx, jnp.zeros_like(w), dw


mm.defvjp(_mm_fwd, _mm_bwd)


@jax.custom_vjp
def mm_nt(x, w, sink):
    """``x @ w.T`` for a ``w`` stored [n, k] (the head, rows by
    vocabulary id), as ``mm``: no transposed copy of ``w`` or of its
    gradient is made."""
    del sink
    return jax.lax.dot_general(x.astype(BF16), w, (((1,), (1,)), ((), ())),
                               preferred_element_type=F32)


def _mm_nt_fwd(x, w, sink):
    return mm_nt(x, w, sink), (x.astype(BF16), w, jnp.zeros((), x.dtype))


def _mm_nt_bwd(res, g):
    xb, w, like = res
    gb = g.astype(BF16)
    dx = jnp.dot(gb, w, preferred_element_type=F32).astype(like.dtype)
    return dx, jnp.zeros_like(w), jnp.dot(gb.T, xb,
                                          preferred_element_type=F32)


mm_nt.defvjp(_mm_nt_fwd, _mm_nt_bwd)


_RAGGED_CONTRACT = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(((0,), (0,)), ((), ())),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])
#: Rows a tile of the TPU's grouped product holds, and its widest tile
#: along a width (the first of these that divides it).
GROUPED_TILE_ROWS = 512
_GROUPED_TILE_WIDTHS = (1024, 768, 640, 512, 384, 256, 128)


def _tile(width: int) -> int:
    return next((t for t in _GROUPED_TILE_WIDTHS if width % t == 0), 0)


def _use_gmm(rows: int, k: int, n: int) -> bool:
    """Whether the grouped products of this shape take the Pallas kernel
    (``jax.experimental.pallas.ops.tpu.megablox``): on a TPU, whole tiles.
    It visits the tiles that hold a group's rows and no other, so rows
    past the groups cost nothing; XLA's own ragged product computes every
    row of its buffer (measured, PERF.md section 6, PR 32) and is the
    form everywhere else."""
    return (jax.default_backend() == "tpu" and rows % GROUPED_TILE_ROWS == 0
            and _tile(k) > 0 and _tile(n) > 0)


def grouped_product(x, w, group_sizes, transpose_w=False, kernel=None,
                    interpret=False):
    """``x`` [rows, k] bfloat16 times its group's ``w[g]`` ([groups, k, n],
    or [groups, n, k] with ``transpose_w``), float32 result. Rows past
    the groups' sum are the caller's to mask: XLA's form gives zeros
    there, the kernel leaves them unwritten. ``kernel`` None picks by
    ``_use_gmm``."""
    k, n = (w.shape[2], w.shape[1]) if transpose_w else w.shape[1:]
    if kernel is None:
        kernel = _use_gmm(x.shape[0], k, n)
    if not kernel:
        return jax.lax.ragged_dot(x, w.swapaxes(1, 2) if transpose_w else w,
                                  group_sizes, preferred_element_type=F32)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    return gmm(x, w, group_sizes, F32,
               (GROUPED_TILE_ROWS, _tile(k), _tile(n)),
               transpose_rhs=transpose_w, interpret=interpret)


def grouped_outer(x, g, group_sizes, kernel=None, interpret=False):
    """Each group's ``x[rows of g].T @ g[rows of g]``: [groups, k, n]
    float32 from ``x`` [rows, k] and ``g`` [rows, n], bfloat16 (a grouped
    product's weight gradient)."""
    if kernel is None:
        kernel = _use_gmm(x.shape[0], x.shape[1], g.shape[1])
    if not kernel:
        return jax.lax.ragged_dot_general(x, g, group_sizes, _RAGGED_CONTRACT,
                                          preferred_element_type=F32)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
    return tgmm(x.swapaxes(0, 1), g, group_sizes, F32,
                (GROUPED_TILE_ROWS, _tile(x.shape[1]), _tile(g.shape[1])),
                interpret=interpret)


@jax.custom_vjp
def grouped_mm(x, w, sink, group_sizes):
    """Row ``i`` of ``x`` times the matrix of its group: ``w`` is
    [groups, k, n], the rows of ``x`` lie group after group with
    ``group_sizes`` rows each, and rows past their sum give nothing.
    Inputs, result and ``sink`` as in ``mm``."""
    del sink
    return grouped_product(x.astype(BF16), w, group_sizes)


def _grouped_fwd(x, w, sink, group_sizes):
    return (grouped_mm(x, w, sink, group_sizes),
            (x.astype(BF16), w, group_sizes, jnp.zeros((), x.dtype)))


def _grouped_bwd(res, g, kernel=None):
    xb, w, group_sizes, like = res
    gb = g.astype(BF16)
    dx = grouped_product(gb, w, group_sizes, transpose_w=True,
                         kernel=kernel).astype(like.dtype)
    return (dx, jnp.zeros_like(w),
            grouped_outer(xb, gb, group_sizes, kernel=kernel), None)


grouped_mm.defvjp(_grouped_fwd, _grouped_bwd)


@jax.custom_vjp
def grouped_mm_xla(x, w, sink, group_sizes):
    """``grouped_mm`` by XLA's grouped product on every backend, both
    passes: no kernel in the program (``routed_experts``' fallback)."""
    del sink
    return grouped_product(x.astype(BF16), w, group_sizes, kernel=False)


grouped_mm_xla.defvjp(
    lambda x, w, sink, group_sizes: (
        grouped_mm_xla(x, w, sink, group_sizes),
        (x.astype(BF16), w, group_sizes, jnp.zeros((), x.dtype))),
    functools.partial(_grouped_bwd, kernel=False))


def rmsnorm(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


# -- attention -------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Mask:
    """Which keys a query sees: a kind with its parameters.

    ``causal``: ``j <= i``. ``window``: the last ``window`` positions,
    itself included (``i - window < j <= i``). ``blockdiff``: ``2 * half``
    positions, the noised copy of a sequence at ``0 .. half - 1`` and the
    clean copy at ``half .. 2 half - 1``, both cut into blocks of
    ``block``; with ``blk(i) = (i mod half) // block``, a noised query
    sees the noised keys of its own block and the clean keys of earlier
    blocks, a clean query the clean keys of its own and earlier blocks
    and no noised key."""
    kind: str = "causal"
    window: int = 0
    half: int = 0
    block: int = 0

    @classmethod
    def of(cls, mask) -> "Mask":
        """``mask`` itself, or from the short form of the first two kinds:
        an int, the window (0: causal)."""
        if isinstance(mask, cls):
            return mask
        return cls("window", window=int(mask)) if mask else cls()

    @classmethod
    def blockdiff(cls, half: int, block: int) -> "Mask":
        CHECK(block > 0 and half % block == 0,
              f"blocks of {block} do not divide a sequence of {half}")
        return cls("blockdiff", half=int(half), block=int(block))

    @property
    def scope(self) -> str:
        return "mv.lm.attn." + {"causal": "full"}.get(self.kind, self.kind)

    def visible(self, i, j):
        """Whether query position ``i`` sees key position ``j`` (arrays
        that broadcast, numpy's or JAX's, in or out of a kernel: integer
        division, comparisons and ``&``/``|`` alone)."""
        if self.kind == "blockdiff":
            # blocks counted over both copies: the noised copy's are
            # 0 .. n - 1, the clean copy's n .. 2n - 1
            n, q, k = self.half // self.block, i // self.block, \
                j // self.block
            return (k == q) | ((k >= n) & (((q < n) & (k < q + n))
                                           | (k <= q)))
        seen = j <= i
        return seen & (j > i - self.window) if self.kind == "window" else seen

    def key_ranges(self, lo: int, hi: int):
        """The runs of keys ``(first, last)`` outside of which no query in
        ``lo .. hi - 1`` sees a key."""
        if self.kind == "blockdiff":
            b, half = self.block, self.half
            end = -(-hi // b) * b           # where the last query's block ends
            if lo >= half:                  # clean queries: clean keys alone
                return ((half, end),)
            if hi > half:                   # both kinds of query
                return ((lo // b * b, 2 * half),)
            # noised queries: their own blocks, the clean blocks before
            return ((lo // b * b, end), (half, half + end - b))
        return ((max(lo - self.window + 1, 0)
                 if self.kind == "window" else 0, hi),)

    def positions(self, t: int):
        """Each of ``t`` positions' rotary position: under ``blockdiff``
        the two copies of a token share one."""
        if self.kind == "blockdiff":
            assert t == 2 * self.half, (t, self.half)
            return np.tile(np.arange(self.half), 2)
        return np.arange(t)


def yarn_frequencies(theta: float, lanes: int, factor: float, fast: float,
                     slow: float, original: float) -> np.ndarray:
    """The rotary pairs' frequencies under YaRN [lanes / 2]: ``theta``'s
    own where a pair turns more than ``fast`` times over the ``original``
    context, divided by ``factor`` where fewer than ``slow``, a linear
    ramp between."""
    d = lanes
    own = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)

    def pair_of(turns):     # the pair that makes ``turns`` over the context
        return d * math.log(original / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(pair_of(fast)), 0)
    high = min(math.ceil(pair_of(slow)), d - 1)
    ramp = np.clip((np.arange(d // 2) - low) / max(high - low, 1e-3), 0, 1)
    return own / factor * ramp + own * (1 - ramp)


def rotary_tables(t, d, theta, pos=None, inv=None, lanes=None, factor=1.0,
                  sections=()):
    """``(cos, sin)`` float64 [t, lanes / 2], ``factor`` inside, of the
    rotary pairs of a head of ``d`` lanes at ``t`` rows: ``_rotary``'s
    arguments say how."""
    lanes = d if lanes is None else lanes
    if inv is None:
        inv = 1.0 / theta ** (np.arange(0, lanes, 2, dtype=np.float64)
                              / lanes)
    pos = np.arange(t) if pos is None else np.asarray(pos)
    if sections:
        assert sum(sections) == lanes // 2, (sections, lanes)
        pos = pos[np.repeat(np.arange(len(sections)), sections)].T
    else:
        pos = pos[:, None]
    angle = pos.astype(np.float64) * inv[None, :]
    return factor * np.cos(angle), factor * np.sin(angle)


def _rotary(x, theta, pos=None, inv=None, lanes=None, factor=1.0,
            sections=()):
    """Rotary positions on [T, heads, d] (the halves paired, as the
    published model's ``rotate_half``), float32. ``pos`` [T] gives each
    row's position (``arange(T)`` when None); ``inv`` the pairs'
    frequencies where they are not ``theta``'s own (YaRN's); ``lanes``
    how many of a head's FIRST lanes are turned (all when None; the
    others pass as they are, bit for bit); ``factor`` multiplies cos and
    sin. With ``sections`` (lane pairs each, in runs) ``pos`` is [rows, T]
    and a pair takes the row of its section."""
    t, _, d = x.shape
    lanes = d if lanes is None else lanes
    cos, sin = (jnp.asarray(table, F32)[:, None, :] for table in
                rotary_tables(t, d, theta, pos, inv, lanes, factor, sections))
    x1, x2 = x[..., :lanes // 2], x[..., lanes // 2:lanes]
    turned = [x1 * cos - x2 * sin, x2 * cos + x1 * sin]
    return jnp.concatenate(turned + ([x[..., lanes:]] if lanes < d else []),
                           -1)


def visible(i, j, mask):
    """Whether query position ``i`` sees key position ``j`` under
    ``mask`` (a ``Mask``, or an int: a window, 0 causal)."""
    return Mask.of(mask).visible(i, j)


def blockwise_attention(q, k, v, mask, block: int = 512):
    """Masked attention in blocks of queries, each over the key blocks it
    can see and no other: q [groups, per_group, T, d] (already scaled), k
    and v [groups, T, d], bfloat16; returns q's shape. Scores and softmax
    float32, the probabilities rounded to bfloat16 for the product with
    v, as the kernel does."""
    mask = Mask.of(mask)
    t = q.shape[2]
    block = min(block, t)
    out = []
    for lo in range(0, t, block):
        hi = min(lo + block, t)
        runs = [r for r in mask.key_ranges(lo, hi) if r[1] > r[0]]
        kk, vv, j = (jnp.concatenate([a[:, f:l] for f, l in runs], axis=1)
                     for a in (k, v, jnp.arange(t)[None, :]))
        s = jnp.einsum("ghqd,gkd->ghqk", q[:, :, lo:hi], kk,
                       preferred_element_type=F32)
        i = jnp.arange(lo, hi)[:, None]
        s = jnp.where(mask.visible(i, j), s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out.append(jnp.einsum("ghqk,gkd->ghqd", p.astype(BF16), vv,
                              preferred_element_type=F32))
    return jnp.concatenate(out, axis=2).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _block_diffusion_mask_class():
    """The splash kernel's mask object for ``Mask.blockdiff`` (the
    library is imported when a kernel is first wanted)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_mask as masks)

    class BlockDiffusionMask(masks._ComputableMask):
        """``mask.visible`` computed in the kernel from the positions'
        indices, and on the host a tile at a time to find the tiles to
        skip; no [T, T] array."""

        def __init__(self, mask: Mask):
            self.mask = mask
            super().__init__(shape=(2 * mask.half, 2 * mask.half),
                             mask_function=mask.visible)

        def __eq__(self, other):
            return isinstance(other, type(self)) and other.mask == self.mask

        def __hash__(self):
            return hash((type(self), self.mask))

    return BlockDiffusionMask


def _block_diffusion_mask(mask: Mask):
    return _block_diffusion_mask_class()(mask)


class Blocks(NamedTuple):
    """The splash kernels' tile sizes (the library's ``BlockSizes`` fields
    of the same names): queries by keys a grid step of the forward, the dkv
    and the dq kernel, and the keys a product inside a step of the first
    two."""
    block_q: int
    block_kv: int
    block_kv_compute: int
    block_q_dkv: int
    block_kv_dkv: int
    block_kv_dkv_compute: int
    block_q_dq: int
    block_kv_dq: int


PLAIN_BLOCK = 512
PLAIN_BLOCKS = Blocks(*[PLAIN_BLOCK] * 8)


def _wanted_blocks(mask: Mask, t: int, qk_lanes: int, v_lanes: int,
                   per_group: int) -> Blocks:
    """The sizes measured to win for a kind of call on a v5e
    (``tools/splash_bench.py``, each kernel's time apart in a trace;
    PERF.md section 6, PR 62, has the tables with what lost), and
    ``PLAIN_BLOCKS`` for every kind that was not measured or where nothing
    beat 512 by 2%. All at 8192 positions, against 512 everywhere:

    - a mask that reaches far (causal; a window of 4096; block diffusion,
      whose clean half is causal): tiles of 1024 x 1024 in the forward and
      the dkv kernel, -16 to -27% and -6 to -22% of their time (a grid
      step's cost and its fetches of q, k and v are paid a quarter as often;
      2048 on either side is refused for fast memory or slower);
    - the forward kernel's product: 512 keys at a time at 128 | 128 lanes
      (256: the same), 256 at wider heads (192 | 128 -19% for 512's -16%,
      256 | 256 -21% for -18%) and under block diffusion (-12% for -7%:
      more of a diagonal tile's 256-key slices are masked whole); the dkv
      kernel's 512 everywhere (1024 and 256 are within 0.7% of it, 1.6%
      under block diffusion, and a product of all 1024 keys at 256 | 256
      lanes passes the compiler's 16 MiB of fast memory by 0.9 in one
      program and fits in another);
    - the dq kernel: 1024 x 1024 too (-10% at 256 | 256 lanes to -26%),
      but under block diffusion, where it is 3% SLOWER than 512;
    - a window of 512 keeps 512 in all three kernels: every other size
      lost by 15% or more (128 x 128 takes 3.8 to 5 times as long: a grid
      step costs more than the masked half of a tile saves);
    - 6 and 7 query heads a group chose alike, so ``per_group`` is not
      read; at 4096 positions of 4 heads (192 | 128 lanes) every candidate
      is within the noise of 512, so shorter sequences keep 512;
    - heads of 64 | 64 lanes, half a tile (8 groups of 4, causal; PERF.md
      section 6, PR 63), choose the causal row's sizes in all three kernels:
      forward -17%, dkv -21%, dq -20% of their time at 512, and nothing
      else within 2% of them (2048 on either side of the dq kernel and of
      the forward is refused for fast memory there too)."""
    del v_lanes, per_group
    far = mask.kind in ("causal", "blockdiff") or mask.window >= 4096
    if not far or t < 8192:
        return PLAIN_BLOCKS
    diffusion = mask.kind == "blockdiff"
    compute = 512 if qk_lanes <= 128 and not diffusion else 256
    dq = 512 if diffusion else 1024
    return Blocks(1024, 1024, compute, 1024, 1024, 512, dq, dq)


def _blocks_within(wanted: Blocks, t: int) -> Blocks:
    """``wanted`` at length ``t``: a size that does not divide ``t``, or
    exceeds it, falls to the largest multiple of 128 that does, and a
    step's product to the largest that divides its step."""
    def fit(size, whole):
        return max(b for b in range(128, min(size, whole) + 1, 128)
                   if whole % b == 0)

    w = wanted
    kv, kv_dkv = fit(w.block_kv, t), fit(w.block_kv_dkv, t)
    return Blocks(fit(w.block_q, t), kv, fit(w.block_kv_compute, kv),
                  fit(w.block_q_dkv, t), kv_dkv,
                  fit(w.block_kv_dkv_compute, kv_dkv),
                  fit(w.block_q_dq, t), fit(w.block_kv_dq, t))


def attention_blocks(mask, t: int, qk_lanes: int = 128, v_lanes: int = 128,
                     per_group: int = 1) -> Blocks:
    """The tile sizes ``_splash`` gives the library's kernels for
    ``per_group`` query heads a key-value head at length ``t`` (a multiple
    of 128) under ``mask``, q and k of ``qk_lanes`` and v of ``v_lanes``: a
    function of these alone, every size dividing ``t``
    (``_blocks_within``)."""
    return _blocks_within(_wanted_blocks(Mask.of(mask), t, qk_lanes,
                                         v_lanes, per_group), t)


def _in_kernel(t: int) -> bool:
    """Whether ``attention_core`` takes the library's kernel at ``t``
    positions."""
    return jax.default_backend() == "tpu" and t % 128 == 0


def attention_blocks_name(mask, t: int, qk_lanes: int = 128,
                          v_lanes: int = 128, per_group: int = 1):
    """The counter that one sequence of ``t`` positions through one layer's
    ``attention_core`` adds one to (``PSLMTrainer._count_stats``): whether
    its kernels' sizes are the rule's own or the plain 512 everywhere (None
    where ``attention_core`` takes no kernel)."""
    if not _in_kernel(t):
        return None
    fitted = attention_blocks(mask, t, qk_lanes, v_lanes, per_group) \
        != _blocks_within(PLAIN_BLOCKS, t)
    return "LM_ATTN_BLOCKS_FITTED" if fitted else "LM_ATTN_BLOCKS_PLAIN"


@functools.lru_cache(maxsize=None)
def _splash_at(t: int, per_group: int, mask, blocks: Blocks,
               interpret: bool = False):
    """The TPU kernel for one key-value head and its ``per_group`` query
    heads at length ``t`` under ``mask`` (a ``Mask``, or an int: a window,
    0 causal) at tiles of ``blocks``. Its mask is worked out on the host,
    once, and kept as arrays; the first call comes from inside a program's
    trace, so they are made under ``ensure_compile_time_eval``: a tracer
    kept here would leak into the next program that takes the kernel."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel, splash_attention_mask as masks)
    mask = Mask.of(mask)
    if mask.kind == "blockdiff":
        assert t == 2 * mask.half, (t, mask)
        one = _block_diffusion_mask(mask)
    else:
        one = masks.LocalMask((t, t), (mask.window - 1, 0), 0) \
            if mask.kind == "window" else masks.CausalMask((t, t))
    # the fused backward kernel stays off: it sums dq over key blocks in
    # q's dtype (bfloat16) and holds t / block_kv_dkv copies of dq
    sizes = kernel.BlockSizes(**blocks._asdict(), use_fused_bwd_kernel=False)
    with jax.ensure_compile_time_eval():
        return kernel.make_splash_mqa_single_device(
            masks.MultiHeadMask([one] * per_group), block_sizes=sizes,
            interpret=interpret)


def _splash(t: int, per_group: int, mask, qk_lanes: int, v_lanes: int):
    """``_splash_at`` the sizes ``attention_blocks`` chooses for the
    call."""
    return _splash_at(t, per_group, mask, attention_blocks(
        mask, t, qk_lanes, v_lanes, per_group))


def attention_core(q, k, v, mask):
    """The attention proper: q [groups, per group, T, d] (scaled), k and
    v [groups, T, d], bfloat16, under ``mask`` (a ``Mask``, or an int: a
    window, 0 causal)."""
    t = q.shape[2]
    if _in_kernel(t):
        return jax.vmap(_splash(t, q.shape[1], mask, q.shape[-1],
                                v.shape[-1]))(q, k, v)
    return blockwise_attention(q, k, v, mask)


_ROTARY = _rotary


def attention_pass_fused(cfg: LMConfig, t: int, rope=True) -> bool:
    """Whether ``attention_inputs`` takes the one pass of attn_kernels.py
    for a sequence of ``t`` positions through a layer that is turned or not
    (``rope``): where its kernels fit (a TPU, whole blocks of tokens, heads
    of whole lane tiles) and there is a norm or a turn for them to do. With
    neither, the scale, the rounding and the layout go into the products'
    own output fusions, and a pass over their float32 results only adds to
    that (PERF.md section 6, PR 53: 2.66 ms a sequence of 8192 for the
    chain's 2.54).
    Everywhere else, and where a caller has put its own ``_rotary`` in this
    module's place (the checks' controls do), it is the ``jax.numpy`` chain
    below, which is the definition the kernels are held to.
    ``PSLMTrainer._count_stats`` counts ``LM_ATTN_PASS_FUSED`` /
    ``LM_ATTN_PASS_PLAIN`` by it."""
    from . import attn_kernels
    return (attn_kernels.fits(t, cfg.head_dim) and bool(rope or cfg.qk_norm)
            and _rotary is _ROTARY)


def attention_pass_name(cfg: LMConfig, t: int, rope=True):
    """The counter that a sequence of ``t`` positions through one layer
    (turned or not: ``rope``) adds one to: which form its
    ``attention_inputs`` took (None under latent attention, which is
    latent.py's: ``latent.pass_name`` there)."""
    if cfg.attention == "mla":
        return None
    return "LM_ATTN_PASS_FUSED" if attention_pass_fused(cfg, t, rope) \
        else "LM_ATTN_PASS_PLAIN"


def attention_inputs(cfg: LMConfig, rope, mats, sinks, norms, x, pos=None):
    """Norm, the three projections, the heads' q and k norms
    (``cfg.qk_norm``), rotary positions (``rope``: a ``Rotary``, or
    whether ``cfg.rope_theta`` turns every lane; at ``pos``, ``arange(T)``
    when None), the scale: ``(q, k, v)`` laid out for ``attention_core``,
    and with ``cfg.attn_gate`` the normed input fourth (the gate reads
    it). The layer's query heads are its ``wq``'s. ``norms`` is the
    attention norm's scale, or with ``cfg.qk_norm`` the three
    ``(norm_attn, norm_q, norm_k)``. What follows the products is one
    pass over memory where ``attention_pass_fused``."""
    t, d = x.shape[0], cfg.head_dim
    heads = mats["wq"].shape[1] // d
    g = cfg.n_kv_heads_held
    per = heads // g
    scale = cfg.attn_scale or 1.0 / math.sqrt(d)
    norm, *qk = norms if cfg.qk_norm else (norms,)
    h = rmsnorm(x, norm, cfg.eps)
    at = () if pos is None else (pos,)
    theta, how = (rope.theta, rope.how()) if isinstance(rope, Rotary) \
        else (cfg.rope_theta, {})
    if attention_pass_fused(cfg, t, rope):
        from . import attn_kernels
        tables = tuple(jnp.asarray(table, F32) for table in rotary_tables(
            t, d, theta, *at, **how)) if rope else ()
        qkv = attn_kernels.heads_in(
            attn_kernels.Pass(per, d, how.get("lanes", d) if rope else 0,
                              bool(qk), cfg.eps, scale, BF16),
            *(mm(h, mats[n], sinks[n]) for n in ("wq", "wk", "wv")),
            tuple(qk), tables)
        return qkv + (h,) if cfg.attn_gate != "none" else qkv
    q = mm(h, mats["wq"], sinks["wq"]).reshape(t, heads, d)
    k = mm(h, mats["wk"], sinks["wk"]).reshape(t, g, d)
    v = mm(h, mats["wv"], sinks["wv"]).reshape(t, g, d)
    if qk:      # over a head's lanes: each head normed alone
        q, k = rmsnorm(q, qk[0], cfg.eps), rmsnorm(k, qk[1], cfg.eps)
    if rope:    # positions go only where given: ``_rotary``'s short form
        q = _rotary(q, theta, *at, **how)
        k = _rotary(k, theta, *at, **how)
    q = (q * scale).astype(BF16)
    # query head i reads key-value head i // per
    q = q.reshape(t, g, per, d).transpose(1, 2, 0, 3)
    qkv = (q, k.astype(BF16).transpose(1, 0, 2),
           v.astype(BF16).transpose(1, 0, 2))
    return qkv + (h,) if cfg.attn_gate != "none" else qkv


GATE_SCOPE = "mv.lm.attn.gate"


def attention_gate(mats, sinks, h, o):
    """Each head's output times its gate ``sigmoid(h W_g)`` (``W_g``
    [hidden, heads], a logit a head from the layer's normed input ``h``):
    ``(o [groups, per group, T, d] gated, the gates' sum over tokens and
    heads)``. A ``W_g`` [hidden, heads x d] is a gate a LANE (``attn_gate:
    "lane"``), and the second result the count of lanes whose gate is over
    a half."""
    groups, per, t, d = o.shape
    gate = jax.nn.sigmoid(mm(h, mats[ATTN_GATE], sinks[ATTN_GATE]))
    if gate.shape[1] != groups * per:
        by_lane = gate.reshape(t, groups, per, d).transpose(1, 2, 0, 3)
        return ((o.astype(F32) * by_lane).astype(BF16),
                jnp.sum(gate > 0.5, dtype=jnp.int32))
    by_head = gate.reshape(t, groups, per).transpose(1, 2, 0)[..., None]
    return (o.astype(F32) * by_head).astype(BF16), jnp.sum(gate)


def attention_output(cfg: LMConfig, mats, sinks, x, o):
    """``x`` plus the heads' outputs through the output projection (times
    ``cfg.residual_scale``)."""
    t = x.shape[0]
    o = o.transpose(2, 0, 1, 3).reshape(t, mats["wo"].shape[0])
    out = mm(o, mats["wo"], sinks["wo"])
    return x + (out if cfg.residual_scale == 1.0
                else cfg.residual_scale * out)


def _attention_norms(cfg: LMConfig, small):
    """What ``attention_inputs`` takes as ``norms`` out of a layer's small
    tensors."""
    if cfg.qk_norm:
        return (small["norm_attn"],) + tuple(small[n] for n in QK_NORMS)
    return small["norm_attn"]


def attention_block(cfg: LMConfig, rope: bool, mask, mats, sinks,
                    norms, x, pos=None):
    """``x + Attn(RMSNorm(x))`` for one sequence ``x`` [T, hidden]."""
    q, k, v, *h = attention_inputs(cfg, rope, mats, sinks, norms, x, pos)
    with jax.named_scope(Mask.of(mask).scope + ".kernel"):
        o = attention_core(q, k, v, mask)
    if h:
        with jax.named_scope(GATE_SCOPE):
            o, _ = attention_gate(mats, sinks, h[0], o)
    return attention_output(cfg, mats, sinks, x, o)


# -- router and experts -----------------------------------------------------

def route(cfg: LMConfig, router, x, bias=None):
    """The top-k experts of each token and their weights, normalised
    over the k: ``(ids [T, k] int32, weights [T, k] float32)``. Under
    ``cfg.scoring == "sigmoid"`` a score is the sigmoid of its logit, the
    k are the largest scores and the weights ``routed_scale`` times the
    chosen scores over their sum; under ``"sigmoid_bias"`` the k are the
    largest of score + ``bias`` (which gets no gradient) and the weights
    still the chosen SCORES'."""
    logits = jnp.dot(x.astype(F32), router, precision="highest")
    if cfg.scoring == "sigmoid_bias":
        p = jax.nn.sigmoid(logits)
        chosen_by = jax.lax.stop_gradient(p + bias)
    elif cfg.scoring == "sigmoid":
        p = chosen_by = jax.nn.sigmoid(logits)
    else:
        p = chosen_by = jax.nn.softmax(logits, axis=-1)
    ids = jax.lax.top_k(chosen_by, cfg.top_k)[1].astype(jnp.int32)
    # the chosen probabilities by a one-hot product, not a gather: its
    # backward pass is then a product too, where a gather's is a scatter
    # (serial on a TPU)
    picks = (ids[..., None] == jnp.arange(cfg.n_experts)).astype(F32)
    top = jnp.einsum("tke,te->tk", picks, p)
    weights = top / jnp.sum(top, -1, keepdims=True)
    if cfg.routed_scale != 1.0:
        weights = cfg.routed_scale * weights
    return ids, weights


def router_load(cfg: LMConfig, ids):
    """Each router output's assignments among ``ids`` [T, k]: int32
    [n_experts]."""
    return jnp.sum(ids.reshape(-1, 1) == jnp.arange(cfg.n_experts),
                   axis=0, dtype=jnp.int32)


def held_groups(cfg: LMConfig, ids):
    """The (token, expert) assignments that fall on held experts, sorted
    by expert: ``(order, group_sizes)``. ``order`` [T*k] lists the
    flattened assignments, the held ones first and grouped by expert;
    ``group_sizes`` [held] counts each held expert's."""
    first, count = cfg.experts_held
    local = ids.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    sizes = jnp.sum(local[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return order, sizes


def _rows_of(x, order, k):
    return x[order // k]


def _rows_at(rows, back):
    """``rows[back]``. A buffer shorter than the assignments ends in a
    zero row of its own here, and ``back`` was held to it
    (``_experts_in``): an assignment that has no row reads the zero that
    it reads in the full buffer. (Left to itself a gather CLAMPS an index
    past the end, and reads the last row, which is live when the buffer
    is full.)"""
    if rows.shape[0] < back.shape[0]:
        rows = jnp.pad(rows, ((0, 1), (0, 0)))
    return rows[back]


def _sum_back(rows, back, k):
    return _rows_at(rows, back).reshape(-1, k, rows.shape[-1]).astype(
        F32).sum(axis=1)


@jax.custom_vjp
def permute(x, order, back):
    """``x[order]`` for a permutation ``order`` with inverse ``back``, or
    for its first rows: backward ``g[back]`` (``_rows_at``)."""
    return x[order]


permute.defvjp(lambda x, order, back: (x[order], back),
               lambda back, g: (_rows_at(g, back), None, None))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch(h, order, back, k):
    """Each sorted assignment's token row: ``h[order // k]``. ``order``
    is a permutation of the ``T * k`` assignments (or its first rows:
    ``_experts_in``) and ``back`` its inverse, so the backward pass is
    ``combine``'s sum, a gather, where the gather's own transpose would
    be a scatter-add (serial on a TPU)."""
    return _rows_of(h, order, k)


def _dispatch_fwd(h, order, back, k):
    return _rows_of(h, order, k), (back, jnp.zeros((), h.dtype))


def _dispatch_bwd(k, res, g):
    back, like = res
    return _sum_back(g, back, k).astype(like.dtype), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def combine(rows, order, back, k):
    """Each token's ``k`` assignment rows summed in float32 (``rows``
    lie in sorted order): the transpose of ``dispatch``, and its
    backward pass is ``dispatch``'s gather."""
    return _sum_back(rows, back, k)


def _combine_fwd(rows, order, back, k):
    return _sum_back(rows, back, k), (order, jnp.zeros((), rows.dtype))


def _combine_bwd(k, res, g):
    order, like = res
    return _rows_of(g, order, k).astype(like.dtype), None, None


combine.defvjp(_combine_fwd, _combine_bwd)


ACTIVATIONS = {"relu": jax.nn.relu, "silu": jax.nn.silu}


def experts_block(cfg: LMConfig, mats, sinks, norm, a, ids, weights):
    """``a + sum over held experts`` for one sequence ``a`` [T, hidden]
    with its routing."""
    out, sizes = routed_experts(cfg, mats, sinks, a, ids, weights, norm)
    return a + out, sizes


#: The routed experts' short buffer holds this many times the even share
#: of a sequence's assignments (``experts_capacity``). Chosen by the
#: records (PERF.md section 5): laguna33b.ps-8k's held share is 0.10 to
#: 0.18 a layer where 0.125 is even, and in sdar30b.ps-bd4k one held
#: expert takes every masked position (the fullest reads 4.77 times the
#: mean with 50.3% masked: about 0.21 of the assignments live, 1.7 times
#: the even eighth). 1.5 would send both to the full buffer in some
#: layers; PERF.md section 6, PR 49, has the shares read at 2.
EXPERTS_SHORT_SHARES = 2


def experts_capacity(cfg: LMConfig, t: int) -> int:
    """Rows of the routed experts' short buffer for a sequence of ``t``
    positions: ``EXPERTS_SHORT_SHARES`` times the even share of its ``t *
    top_k`` assignments in whole tiles of the grouped products (``_use_gmm``
    then picks the full buffer's kernel, on the same tiles), and never more
    than all of them: there is then ONE buffer, and no choice. A model of
    no experts has no assignments and a buffer of no rows."""
    every = t * cfg.top_k
    cap = -(-EXPERTS_SHORT_SHARES * every * cfg.experts_held[1]
            // (max(cfg.n_experts, 1) * GROUPED_TILE_ROWS)) \
        * GROUPED_TILE_ROWS
    return min(cap, every)


def _experts_in(cfg: LMConfig, n: int, mats, sinks, h, weights, norm,
                order, back, sizes, product_of=None, lo=None):
    """``routed_experts``' sum in buffers of ``n`` rows, which hold every
    assignment on a held expert (``sum(sizes) <= n``): all ``T * k`` of
    them, or the first ``n`` of the sorted order, where the held ones
    lie. ``product_of``: ``grouped_mm`` (when None) or its twin. With
    ``lo`` (a device scalar) the buffers hold rows ``lo .. lo + n - 1`` of
    the sorted order, a SLAB, and the sum is the part those rows give
    (``_in_slabs``)."""
    t, k = weights.shape
    count = cfg.experts_held[1]
    if lo is not None:      # an assignment outside the slab: the zero row
        order = jax.lax.dynamic_slice(jnp.pad(order, (0, n)), (lo,), (n,))
        back = jnp.where((back >= lo) & (back < lo + n), back - lo, n)
        ends = jnp.cumsum(sizes)
        sizes = jnp.clip(ends, lo, lo + n) - jnp.clip(ends - sizes, lo,
                                                      lo + n)
    elif n < t * k:   # a row past the buffer is the zero row: ``_rows_at``
        order, back = order[:n], jnp.minimum(back, n)
    live = (jnp.arange(n) < jnp.sum(sizes))[:, None]
    if norm is not None:
        h = rmsnorm(h, norm, cfg.eps).astype(BF16)
    rows = jnp.where(live, dispatch(h, order, back, k), 0)

    def product(rows, name, n_in, n_out):
        return (product_of or grouped_mm)(
            rows, mats[name].reshape(count, n_in, n_out),
            sinks[name].reshape(count, n_in, n_out), sizes)

    gate = product(rows, "w_gate", cfg.hidden, cfg.expert_width)
    up = product(rows, "w_up", cfg.hidden, cfg.expert_width)
    act = jnp.where(live, ACTIVATIONS[cfg.activation](gate) * up, 0)
    out = product(act, "w_down", cfg.expert_width, cfg.hidden)
    # each row weighted by its assignment's w_e (the [T, k] weights one a
    # row, in the rows' order), rounded to bfloat16 for the way back to
    # the tokens and summed there in float32
    w_rows = permute(weights.reshape(t * k, 1), order, back)
    out = (jnp.where(live, out, 0) * w_rows).astype(BF16)
    return combine(out, order, back, k)


#: The full buffer in short ones past which the fallback walks it in slabs
#: (``_in_slabs``): a buffer of every assignment is sized in every program
#: whether taken or not, and ``kimi48b.ps-8k`` fits with one of 16.
FALLBACK_SLABS_OVER = 16


def _in_slabs(run, cap: int, sizes, operands):
    """``run``'s result (``_by_load``) summed over the slabs of ``cap`` rows
    of the sorted order that hold a held expert's assignment, one slab at a
    time in buffers of ``cap`` rows by XLA's grouped product: the fallback
    where a buffer of all ``T * k`` rows is too large to be sized beside the
    step (``solar250b.ps-8k``: 65,536 rows of 4,096 against a short buffer of
    3,584). The same sums as the full buffer's in another order: a token's
    assignments in two slabs meet in float32, what a slab gives in bfloat16
    (a pull's ``dh``) is rounded a slab."""
    def slab(s):
        return run(cap, grouped_mm_xla, *operands, lo=s * cap)

    like = jax.eval_shape(slab, jnp.int32(0))
    total = jax.lax.fori_loop(
        0, -(-jnp.sum(sizes) // cap),
        lambda s, total: jax.tree_util.tree_map(
            lambda a, b: a + b.astype(F32), total, slab(s)),
        jax.tree_util.tree_map(lambda a: jnp.zeros(a.shape, F32), like))
    return jax.tree_util.tree_map(lambda a, to: a.astype(to.dtype), total,
                                  like)


def _by_load(cfg: LMConfig, run, weights, sizes, *operands):
    """``run(n, product, *operands)`` at ``n`` the short buffer's rows
    where the sequence's held assignments fit them, else at all ``T * k``:
    chosen on the device, one of the two run. The short buffer takes the
    grouped products' kernel wherever one buffer of ``T * k`` rows would
    (``grouped_mm``); the fallback takes XLA's form on every backend
    (``grouped_mm_xla``): it computes every row, about twice the kernel's
    cost, and no sequence of the four cells takes it. What a second body
    costs is SET-UP, tracing and lowering it (PERF.md section 6, PR 49:
    with the kernel in both, 0.65 to 1.3 s a layer program, 11% of
    ``sdar30b.ps-bd4k``'s ``setup_s``, over its bound; so, 0.4 to 0.75 s).
    Where the short one is half the rows (``st21b.ps-8k``) it is paid too
    since PR 59: four programs' set-up, ~2 s, for a step 4% shorter (one
    buffer of them all until then)."""
    t, k = weights.shape
    cap = experts_capacity(cfg, t)
    if t * k > FALLBACK_SLABS_OVER * cap:   # never a buffer of them all
        def fallback(ops):
            return _in_slabs(run, cap, sizes, ops)
    else:
        def fallback(ops):
            return run(t * k, grouped_mm_xla, *ops)
    return jax.lax.cond(jnp.sum(sizes) <= cap,
                        lambda ops: run(cap, grouped_mm, *ops), fallback,
                        operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts_by_load(cfg: LMConfig, mats, sinks, h, weights, order, back,
                     sizes):
    """``_experts_in`` the short buffer or the full one, by the
    sequence's routing. Differentiated as ONE function: ``jax.vjp``
    through a ``cond`` would keep both branches' residuals and fill the
    untaken one's with zeros at full size, which costs what the short
    buffer saves. So the forward rule keeps the arguments alone, and the
    backward rule chooses again and differentiates the chosen branch
    inside it: a pass costs one forward and one pull, of one path. The
    matrices' gradients leave through ``sinks`` as everywhere (``mm``);
    a branch makes the zeros it differentiates against."""
    del sinks

    def run(n, product_of, mats, *rest, lo=None):
        return _experts_in(cfg, n, mats, _zeros_like_f32(mats), *rest,
                           product_of, lo)

    return _by_load(cfg, run, weights, sizes, mats, h, weights, None, order,
                    back, sizes)


def _experts_by_load_fwd(cfg, mats, sinks, h, weights, order, back, sizes):
    out = _experts_by_load(cfg, mats, sinks, h, weights, order, back, sizes)
    return out, (mats, h, weights, order, back, sizes)


def _experts_by_load_bwd(cfg, res, g):
    mats, _, weights, _, _, sizes = res

    def pull(n, product_of, mats, h, weights, order, back, sizes, g,
             lo=None):
        return jax.vjp(
            lambda s, h, w: _experts_in(cfg, n, mats, s, h, w, None, order,
                                        back, sizes, product_of, lo),
            _zeros_like_f32(mats), h, weights)[1](g)

    d_sinks, dh, dw = _by_load(cfg, pull, weights, sizes, *res, g)
    return (jax.tree_util.tree_map(jnp.zeros_like, mats), d_sinks, dh, dw,
            None, None, None)


_experts_by_load.defvjp(_experts_by_load_fwd, _experts_by_load_bwd)


def routed_experts(cfg: LMConfig, mats, sinks, h, ids, weights, norm=None):
    """The held experts' part of ``sum_e w_e E_e(h)`` for one sequence
    with its routing: ``(sum [T, hidden] float32, each held expert's
    assignments)``. ``h`` [T, hidden] is the experts' normed input in
    bfloat16, or with ``norm`` the stream that is normed by it here.

    The work is done in a buffer of ``experts_capacity`` rows where the
    sequence's assignments on held experts fit it: the same rows in the
    same order through the same tiles as in a buffer of all ``T * k``
    rows, so the same numbers to the bit. Where they do not fit, it is
    done in one of all ``T * k`` rows (``_experts_by_load``, ``_by_load``:
    the same sums by XLA's grouped product), so no routing drops an
    assignment."""
    t, k = ids.shape
    order, sizes = held_groups(cfg, ids)
    back = jnp.argsort(order).astype(jnp.int32)     # assignment -> its row
    mats, sinks = ({n: of[n] for n in DENSE} for of in (mats, sinks))
    if experts_capacity(cfg, t) == t * k:       # one buffer: no choice
        return _experts_in(cfg, t * k, mats, sinks, h, weights, norm, order,
                           back, sizes), sizes
    if norm is not None:    # no buffer's own: outside the choice
        h = rmsnorm(h, norm, cfg.eps).astype(BF16)
    return _experts_by_load(cfg, mats, sinks, h, weights, order, back,
                            sizes), sizes


def gated_mlp(cfg: LMConfig, mats, sinks, names, h):
    """``W_d (act(h W_g) * (h W_u))`` for ``h`` [T, hidden]: a dense
    layer's MLP or the shared expert, ``names`` its three tables."""
    gate, up, down = names
    act = ACTIVATIONS[cfg.activation](mm(h, mats[gate], sinks[gate])) \
        * mm(h, mats[up], sinks[up])
    return mm(act, mats[down], sinks[down])


# -- the feed-forward on ONE normed input, with its pull --------------------------

def dense_vjp(cfg: LMConfig, mats, sinks, small, u):
    with jax.named_scope("mv.lm.dense_mlp"):
        v, pull_mlp = jax.vjp(
            lambda s, g, u: gated_mlp(cfg, mats, s, DENSE,
                                      rmsnorm(u, g, cfg.eps)),
            {n: sinks[n] for n in DENSE}, small["norm_ffn"], u)

    def pull(dv):
        with jax.named_scope("mv.lm.dense_mlp"):
            d_mats, d_norm, du = pull_mlp(dv)
        return du, (d_mats, {"norm_ffn": d_norm})

    return v, None, pull


def sparse_vjp(cfg: LMConfig, mats, sinks, small, u):
    """Router, held routed experts and shared expert on one normed ``h``:
    ``aux`` is ``(ids [T, k], held experts' assignments [held], every
    router output's assignments [n_experts])``."""
    routed = {n: sinks[n] for n in DENSE}
    with jax.named_scope("mv.lm.experts"):
        h, pull_norm = jax.vjp(lambda g, u: rmsnorm(u, g, cfg.eps),
                               small["norm_ffn"], u)
    with jax.named_scope("mv.lm.router"):
        weights, pull_router, ids = jax.vjp(
            lambda r, h: route(cfg, r, h, small.get("router_bias"))[::-1],
            small["router"], h, has_aux=True)
        load = router_load(cfg, ids)
    with jax.named_scope("mv.lm.experts"):
        y, pull_experts, sizes = jax.vjp(
            lambda s, h, w: routed_experts(cfg, mats, s, h.astype(BF16),
                                           ids, w),
            routed, h, weights, has_aux=True)
    pull_shared = None
    if cfg.shared_width:
        with jax.named_scope("mv.lm.shared_expert"):
            shared, pull_shared = jax.vjp(
                lambda s, h: gated_mlp(cfg, mats, s, SHARED, h),
                {n: sinks[n] for n in SHARED}, h)
        y = y + shared

    def pull(dy):
        with jax.named_scope("mv.lm.experts"):
            d_mats, dh, dw = pull_experts(dy)
        with jax.named_scope("mv.lm.router"):
            d_router, dh_router = pull_router(dw)
        dh = dh + dh_router
        if pull_shared is not None:
            with jax.named_scope("mv.lm.shared_expert"):
                d_shared, dh_shared = pull_shared(dy)
            d_mats, dh = {**d_mats, **d_shared}, dh + dh_shared
        with jax.named_scope("mv.lm.experts"):
            d_norm, du = pull_norm(dh)
        return du, (d_mats, {"norm_ffn": d_norm, "router": d_router})

    return y, (ids, sizes, load), pull


def feed_forward_vjp(cfg: LMConfig, sparse: int, mats, sinks, small, u):
    """A layer's feed-forward ``F(RMSNorm(u))`` for one sequence ``u`` [T,
    hidden], of the layer's kind (``sparse``: router, held routed experts
    and shared expert; else the dense MLP), and what pulls a cotangent
    back through it: ``(v, aux, pull)``, ``pull(dv) -> (du, (matrix
    gradients, small gradients))``. The residual is the caller's: the
    plain one adds ``v`` (``layer_vjp``), the streams write it
    (streams.sublayer_vjp)."""
    return (sparse_vjp if sparse else dense_vjp)(cfg, mats, sinks, small, u)


def layer_stats(cfg: LMConfig, sparse: int, aux, gate_open=None,
                selected=None, decay_deep=None, gate_lanes_open=None,
                beta_over_one=None):
    """What a forward program reports of one sequence through a layer
    whose feed-forward is ``feed_forward_vjp``'s: ``(stats, ids)``. A
    sparse layer's ``stats`` int32 [2 + n_experts]: assignments on held
    experts, the fullest held expert's, then every router output's; a
    dense layer's two zeros and no ids. With a gate its ``gate_open``
    (the gates' sum over heads of their mean over tokens) comes last, in
    thousandths; with a ``cfg.selection`` its counts (``selected``:
    sparse.COUNTS of them) come last; a delta layer's ``decay_deep``
    (delta.py: the (chunk, head, channel) triples whose summed log decay is
    under ``delta.DEEP``) comes last, after its ``beta_over_one`` (the
    (position, head) pairs whose beta is over 1, where ``kda_beta_scale``
    lets it be); a lane gate's ``gate_lanes_open`` (the lanes whose gate is
    over a half) comes last."""
    if sparse:
        ids, sizes, load = aux
        stats = jnp.concatenate(
            [jnp.stack([jnp.sum(sizes), jnp.max(sizes)]), load])
    else:
        stats = jnp.zeros((2,), jnp.int32)
        ids = jnp.zeros((0, cfg.top_k), jnp.int32)
    if gate_open is not None:
        stats = jnp.concatenate(
            [stats, jnp.round(1e3 * gate_open)[None].astype(jnp.int32)])
    if selected is not None:
        stats = jnp.concatenate([stats, selected])
    for count in (gate_lanes_open, beta_over_one):
        if count is not None:
            stats = jnp.concatenate([stats, count[None].astype(jnp.int32)])
    if decay_deep is not None:
        stats = jnp.concatenate([stats, decay_deep[None].astype(jnp.int32)])
    return stats, ids


# -- a layer, forward and with its gradients -----------------------------------

def _zeros_like_f32(mats):
    return {name: jnp.zeros(w.shape, F32) for name, w in mats.items()}


def _route_layer(cfg: LMConfig, router, norm_ffn, stream):
    """``route`` on what the configuration's router reads of ``stream``:
    the layer's raw input as it is, or the post-attention stream through
    the experts' norm."""
    if cfg.router_input == "ffn_norm":
        stream = rmsnorm(stream, norm_ffn, cfg.eps)
    return route(cfg, router, stream)


def _module_attention_vjp(cfg: LMConfig, kind: str, rope, mats, sinks,
                          small, x, pos):
    """``attention_vjp``'s results for the kinds of attention that live in
    modules of their own and give ``F(x)``: latent.py's (``mla``) and
    ``MIXER_MODULES``' (``kda``, ``conv``, ``ssd``), which read no position;
    ``cfg.residual_scale`` on ``F(x)``."""
    stats = {}
    if kind in MIXER_MODULES:
        out, stats, pull_f = mixer_module(kind).attention_vjp(
            cfg, mats, sinks, small, x)
    else:
        from . import latent
        out, pull_f = latent.attention_vjp(cfg, mats, sinks, small, x, pos,
                                           rope=bool(rope))
    r = cfg.residual_scale
    if r != 1.0:    # on the output product's result: its own fusion
        out = r * out

    def pull(da):
        dx, d_mats, d_small = pull_f(da if r == 1.0 else r * da)
        return da + dx, d_mats, d_small

    return x + out, stats, pull


def attention_vjp(cfg: LMConfig, rope, mask, mats, sinks, small, x,
                  pos=None, kind=None):
    """``a = x + Attn(RMSNorm(x))`` for one sequence and what pulls a
    cotangent back through it: ``(a, stats, pull)``, ``pull(da) -> (dx,
    matrix gradients, small gradients)``; ``stats`` is what
    ``layer_stats`` takes by name of this attention (``gate_open`` with a
    gate, ``selected`` with a selection). A scope names a backward pass
    only where it is entered OUTSIDE the differentiated function (inside,
    JAX writes it as transpose(jvp(..)), which no reader takes for a
    scope): so the attention's parts are differentiated one by one, the
    kernel and the gate under their own names.

    With a ``cfg.selection`` the attention proper runs over the keys that
    sparse.py's indexer selects of the layer's input (``selection_vjp``,
    ``attention_vjp`` there), and the pull has a fourth result: the loss
    that lives INSIDE the layer, whose gradients to the indexer's tensors
    are made from what the layer recomputed, whatever ``da`` is.

    ``kind`` is the layer's attention (``cfg.attention_of``; the model's
    when None) and selects functions: ``gqa``'s below, or a module's."""
    kind = kind or cfg.attention
    if kind != "gqa":
        return _module_attention_vjp(cfg, kind, rope, mats, sinks, small, x,
                                     pos)
    selection = None
    if cfg.selection != "none":
        from . import sparse as selection
    scope = selection.SCOPE if selection else Mask.of(mask).scope
    qkv = {n: sinks[n] for n in ("wq", "wk", "wv")}
    with jax.named_scope(scope):
        (q, k, v, *h), pull_inputs = jax.vjp(
            lambda s, norms, x: attention_inputs(cfg, rope, mats, s, norms,
                                                 x, pos),
            qkv, _attention_norms(cfg, small), x)
    stats = {}
    if selection:
        tiles, stats["selected"], pull_inner = selection.selection_vjp(
            cfg, mats, sinks, small, x, pos)
    with jax.named_scope(scope + ".kernel"):
        if selection:
            o, lse, pull_core = selection.attention_vjp(q, k, v, tiles)
        else:
            o, pull_core = jax.vjp(
                lambda q, k, v: attention_core(q, k, v, mask), q, k, v)
    pull_gate = None
    if h:
        with jax.named_scope(GATE_SCOPE):
            o, pull_gate, gate_open = jax.vjp(
                lambda s, h, o: attention_gate(mats, {ATTN_GATE: s}, h, o),
                sinks[ATTN_GATE], h[0], o, has_aux=True)
        if cfg.attn_gate == "lane":
            stats["gate_lanes_open"] = gate_open
        else:
            stats["gate_open"] = gate_open / x.shape[0]
    with jax.named_scope(scope):
        a, pull_output = jax.vjp(
            lambda s, x, o: attention_output(cfg, mats, {"wo": s}, x, o),
            sinks["wo"], x, o)

    def pull(da):
        with jax.named_scope(scope):
            d_wo, dx, do = pull_output(da)
        dh = ()
        if pull_gate is not None:
            with jax.named_scope(GATE_SCOPE):
                d_gate, *dh, do = pull_gate(do)
        with jax.named_scope(scope + ".kernel"):
            d_qkv = pull_core(do)
        with jax.named_scope(scope):
            d_attn, d_norms, dx_inputs = pull_inputs(d_qkv + tuple(dh))
        d_attn["wo"], dx = d_wo, dx + dx_inputs
        if pull_gate is not None:
            d_attn[ATTN_GATE] = d_gate
        d_small = dict(zip(("norm_attn",) + QK_NORMS, d_norms)) \
            if cfg.qk_norm else {"norm_attn": d_norms}
        if not selection:
            return dx, d_attn, d_small
        inner, dx_inner, d_mats, d_inner, d_norm_attn = pull_inner(q, k, lse)
        d_small["norm_attn"] = d_small["norm_attn"] + d_norm_attn
        return (dx + dx_inner, {**d_attn, **d_mats}, {**d_small, **d_inner},
                inner)

    return a, stats, pull


def layer_vjp(cfg: LMConfig, rope, mask, sparse: int, mats, small, x,
              pos=None, attention=None):
    """One sequence through one layer of the plain residual whose
    feed-forward reads one normed input (``cfg.one_ffn_input``): ``y = a
    + F(RMSNorm(a))``, ``a = x + Attn(RMSNorm(x))``: ``(y, (stats, ids),
    pull)``, ``pull(dy) -> (dx, matrix gradients, small gradients)`` and
    after them what the attention's pull gives beyond its three (the loss
    inside a layer with a ``cfg.selection``). ``attention``: the layer's
    kind of attention where it is a layer's to say."""
    sinks = _zeros_like_f32(mats)
    a, stats, pull_attention = attention_vjp(cfg, rope, mask, mats, sinks,
                                             small, x, pos, attention)
    v, aux, pull_ffn = feed_forward_vjp(cfg, sparse, mats, sinks, small, a)
    r = cfg.residual_scale
    if r != 1.0:    # on the down product's result: its own fusion
        v = r * v

    def pull(dy):
        du, (d_mats_ffn, d_small_ffn) = pull_ffn(dy if r == 1.0 else r * dy)
        if attention is not None:
            # the feed-forward's gradients all made, and what it kept for
            # them let go, before the attention's pull makes its own keep
            du, d_mats_ffn, d_small_ffn = jax.lax.optimization_barrier(
                (du, d_mats_ffn, d_small_ffn))
        dx, d_mats, d_small, *inner = pull_attention(dy + du)
        return (dx, {**d_mats, **d_mats_ffn}, {**d_small, **d_small_ffn},
                *inner)

    return a + v, layer_stats(cfg, sparse, aux, **stats), pull


def layer_forward(cfg: LMConfig, rope, mask, mats, small, x, pos=None,
                  sparse: int = 1, attention=None):
    """One sequence through one layer under ``mask`` (a ``Mask``, or an
    int: a window, 0 causal) at rotary positions ``pos``: ``(y, stats,
    ids)``, ``stats`` int32[2] = (assignments on held experts, the
    fullest held expert's; ``layer_stats`` says what follows them where
    the feed-forward is ``feed_forward_vjp``'s) and ``ids`` [T, k] each
    token's experts (a check hands them to its reference; a step drops
    them)."""
    if cfg.one_ffn_input:
        y, stats, _ = layer_vjp(cfg, rope, mask, sparse, mats, small, x, pos,
                                attention)
        return (y,) + stats
    sinks = _zeros_like_f32(mats)
    early = cfg.router_input == "input"

    def routed(stream):
        with jax.named_scope("mv.lm.router"):
            return _route_layer(cfg, small["router"], small["norm_ffn"],
                                stream)

    if early:
        ids, weights = routed(x)
    with jax.named_scope(Mask.of(mask).scope):
        a = attention_block(cfg, rope, mask, mats, sinks,
                            _attention_norms(cfg, small), x, pos)
    if not early:
        ids, weights = routed(a)
    with jax.named_scope("mv.lm.experts"):
        y, sizes = experts_block(cfg, mats, sinks, small["norm_ffn"], a,
                                 ids, weights)
    return y, jnp.stack([jnp.sum(sizes), jnp.max(sizes)]), ids


def layer_grads(cfg: LMConfig, rope, mask, mats, small, x, dy, pos=None,
                sparse: int = 1, attention=None):
    """The layer recomputed from its input ``x`` and differentiated:
    ``(dx, matrix gradients, small gradients)`` for one sequence. Each
    part's backward pass runs under the scope of its forward pass, so a
    device trace reads the two together. With a ``cfg.selection`` there is
    a fourth result, the loss that lives inside the layer
    (``attention_vjp``)."""
    if cfg.one_ffn_input:
        return layer_vjp(cfg, rope, mask, sparse, mats, small, x, pos,
                         attention)[2](dy)
    sinks = _zeros_like_f32(mats)
    early = cfg.router_input == "input"

    def routed(stream):     # the router's part, on what it reads
        with jax.named_scope("mv.lm.router"):
            return jax.vjp(
                lambda r, n, s: _route_layer(cfg, r, n, s),
                small["router"], small["norm_ffn"], stream)

    if early:
        (ids, weights), pull_router = routed(x)
    a, _, pull_attention = attention_vjp(cfg, rope, mask, mats, sinks, small,
                                         x, pos)
    if not early:
        (ids, weights), pull_router = routed(a)
    with jax.named_scope("mv.lm.experts"):
        y, pull_experts = jax.vjp(
            lambda s, norm, a, w: experts_block(cfg, mats, s, norm, a, ids,
                                                w)[0],
            {n: s for n, s in sinks.items() if n not in GQA_MATRICES},
            small["norm_ffn"], a, weights)
        d_experts, d_norm_ffn, da, dw = pull_experts(dy.astype(y.dtype))

    def pull_routed():
        with jax.named_scope("mv.lm.router"):
            return pull_router((np.zeros(ids.shape, jax.dtypes.float0), dw))

    if not early:   # the router read the stream the experts read
        d_router, d_norm_router, d_stream = pull_routed()
        d_norm_ffn, da = d_norm_ffn + d_norm_router, da + d_stream
    dx, d_attn, d_small = pull_attention(da)
    if early:       # it read the layer's input: its norm argument unused
        d_router, _, d_stream = pull_routed()
        dx = dx + d_stream
    d_small = {"router": d_router, "norm_ffn": d_norm_ffn, **d_small}
    return dx, {**d_attn, **d_experts}, d_small


# -- the head: final norm, logits over the slice, the loss, its gradients ------

def head_loss_and_grads(cfg: LMConfig, head, norm, x, targets, weights=None,
                        normaliser=None, scope="mv.lm.head"):
    """The cross entropy of ``targets`` [N] over ``x`` [N, hidden], each
    position weighted by ``weights`` [N] (1 when None), summed and divided
    by ``normaliser`` (N when None: the plain mean), and its gradients, a
    block of ``loss_block`` tokens at a time so that no [N, vocab] array
    exists: ``(loss, dx [N, hidden], d_head [vocab, hidden] float32,
    d_norm)``. ``head`` is the bfloat16 copy, rows by vocabulary id."""
    n = x.shape[0]
    block = min(cfg.loss_block, n)
    assert n % block == 0, (n, block)
    over = n if normaliser is None else normaliser

    def block_loss(x, norm, sink, targets, weights):
        h = rmsnorm(x, norm, cfg.eps)
        if cfg.logits_scale != 1.0:     # on the product's narrow operand
            h = h * (1.0 / cfg.logits_scale)
        logits = mm_nt(h, head, sink)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        each = jax.nn.logsumexp(logits, axis=-1) - picked
        return jnp.sum(each if weights is None else each * weights) / over

    def one(carry, xs):
        loss, d_head, d_norm = carry
        x, targets, weights = xs
        more, (dx, dn, dh) = jax.value_and_grad(block_loss, (0, 1, 2))(
            x, norm, jnp.zeros(head.shape, F32), targets, weights)
        return (loss + more, d_head + dh, d_norm + dn), dx

    with jax.named_scope(scope):
        (loss, d_head, d_norm), dx = jax.lax.scan(
            one, (jnp.zeros((), F32), jnp.zeros(head.shape, F32),
                  jnp.zeros(norm.shape, F32)),
            (x.reshape(n // block, block, -1),
             targets.reshape(n // block, block),
             None if weights is None else weights.reshape(n // block, block)))
    return loss, dx.reshape(x.shape), d_head, d_norm


# -- block diffusion's noise ---------------------------------------------------

def noise(cfg: LMConfig, key, tokens):
    """Block diffusion's noised copy of ``tokens`` [B, L] (clean ids,
    none the mask token's): each block of ``cfg.block_length`` positions
    draws ``t ~ U(t_min, 1]`` and each of its positions is masked with
    probability ``t``. Returns ``(noised [B, L], masked [B, L] bool, t
    [B, L // block_length] float32)``; the loss weighs a masked position
    of block ``j`` by ``1 / t[j]`` (the linear schedule of masked
    diffusion)."""
    b, n = tokens.shape
    CHECK(n % cfg.block_length == 0,
          f"blocks of {cfg.block_length} do not divide a sequence of {n}")
    key_t, key_mask = jax.random.split(key)
    blocks = (b, n // cfg.block_length)
    # uniform() is on [0, 1): 1 - it on (0, 1], so t on (t_min, 1]
    t = cfg.t_min + (1.0 - cfg.t_min) * (
        1.0 - jax.random.uniform(key_t, blocks, F32))
    masked = jax.random.uniform(key_mask, (b, n), F32) < jnp.repeat(
        t, cfg.block_length, axis=1)
    return jnp.where(masked, cfg.mask_id, tokens), masked, t

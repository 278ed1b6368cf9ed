"""``PSLMTrainer``: the language model of model.py trained through the
parameter server, built the way ``PSDeviceCorpusTrainer`` is
(models/wordembedding/device_train.py).

**Every parameter is a server table**, made through the table factory:
the embedding a ``MatrixTable`` [vocab, hidden] pulled and pushed by the
step's token ids as DEVICE keys (``get_rows_device`` /
``add_rows_async``); every other tensor a table pulled whole by
``get_device`` and pushed whole as a device delta (a ``MatrixTable`` per
matrix, the experts' stacked by expert along the rows; an
``ArrayTable`` per norm). **The optimizer lives in the server**
(``-updater_type=adam``, updater/rules.py ``AdamRule``): an Add carries
the raw float32 gradient and the step's ``AddOption`` (beta1, lr,
beta2, eps in its four slots); the worker keeps nothing between steps
but the token stream and the step's number, from which the learning
rate comes (``warmup_steps``: it rises linearly to ``lr``; the rule
takes each Add's rate as it comes, a traced scalar).

**A step** (``step``) is Gets, compiled programs, Adds, and nothing but
scalars on the host; no parameter, gradient or id crosses to it:

    ids, targets = split(tokens)                    program, mv.lm.embed
                                                    (block diffusion: the
                                                    noise program, below)
    x = embedding[ids]                              Get, device keys
    for each layer:   pull its tables (ten, or      Gets, whole
                      twelve with q and k norms)
                      x = layer_forward(x)          program (keeps the
                                                    bfloat16 copies, drops
                                                    the float32 snapshots)
    pull head and final norm; loss, dx, gradients   program, mv.lm.head
    push head and norm gradients                    Adds
    for each layer, last first:
                      dx, gradients = layer_grads   program: the layer is
                                                    recomputed from its
                                                    saved input
                      push its gradients            Adds, whole
    embedding[ids] += dx  (Adam's rows form)        Add, device keys

**The objective is the configuration's** (``LMConfig.objective``).
``next_token``: ``tokens`` [B, T+1], the inputs the first T and the
targets the next token, ``B T`` positions through the layers.
``block_diffusion``: ``tokens`` [B, T] clean; the batch-preparation
program (``mv.lm.noise``) draws the step's noise on the device from the
trainer's seed and the step's number (``model.noise``), and the step
runs over ``[noised ; clean]``, ``[B, 2T]`` positions of which copy ``i``
and ``i + T`` share a rotary position, under the block mask
(``mv.lm.attn.blockdiff``); the head reads the noised half alone, each
masked position weighted ``1/t`` of its block, over ``B T``; the clean
half gets its gradients through the keys and values the noised half
reads. The embedding's Get and Add name ``2 B T`` ids, the mask token's
about a quarter of them.

**One table for embedding and head** (``LMConfig.tied``). The step keeps
its device-key Get of the step's rows and its whole-table Get at the head,
of the SAME table, and makes ONE Add to it, last: the head's gradient
[vocab, hidden] with the rows' gradients added into it (``_tie``, a program
under ``mv.lm.embed``), under dense Adam. Two Adds would be two Adam steps
for a row that a step both reads and scores; the rows form has nothing left
to do there. ``LM_TIED_ADDS`` counts the Add.

**Four scalars** (``LMConfig.embed_scale``, ``residual_scale``,
``attn_scale``, ``logits_scale``; 1, 1, ``head_dim^-0.5``, 1 in every model
but Granite's). The last three are the layer programs' and the head's
(model.py). ``embed_scale``: the rows the Get brings go through a program
under ``mv.lm.embed`` that multiplies them (``_enter_scaled``), and the first
layer's input's gradient through its twin before it goes into the table's
Add: a tied table's one Add carries ``scale * d_rows`` beside the head's.

**A model with no router** (``n_experts`` 0, every ``ffn_layout`` 0) is a
case of the same code: no layer has a ``router`` or ``router_bias`` table,
no forward program returns a bias's step, the experts' counters count
nothing.

The Adds are asynchronous; the next step's Gets of the same tables wait
for them by the server's own order (an acknowledged Add is in every
later Get). A layer's gradients leave for the server as soon as its
backward program is dispatched, so a whole model's gradients are never
held at once; the worker's pulled copies are bfloat16.

Two programs a kind of layer (SmallThinker: full attention without
rotary positions, and rotary with a sliding window, so a stack of any
depth compiles four layer programs; a block-diffusion stack has one
kind). Sequences go through a layer one at a time
(``lax.map``), which halves every activation buffer at two sequences a
step.

Monitors (each an ``mv:`` span in a trace): ``LM_STEP``,
``LM_GET_PARAMS``, ``LM_ADD_GRADS``, and around a multi-token module's
Gets, three programs and Adds ``LM_MTP_STEP``. Counters: ``LM_TOKENS`` (tokens
trained, ``B T``), ``LM_POSITIONS`` (positions through the layers: the
same, or ``2 B T`` under block diffusion), ``LM_GET_BYTES`` and
``LM_ADD_BYTES`` (whole-table traffic) at once; ``LM_HELD_ASSIGNMENTS``
((token, expert) assignments on held experts, every layer),
``LM_EXPERT_MAX_TOKENS`` (the fullest held expert's tokens, summed over
layers), ``LM_EXPERTS_SHORT`` and ``LM_EXPERTS_FULL`` (one a sparse layer
a sequence: whether its routed experts took the short buffer or the one
of every assignment, from the same count and ``model.experts_capacity``),
``LM_ATTN_PASS_FUSED`` or ``LM_ATTN_PASS_PLAIN`` (one a layer a sequence,
the module's layer too: which form ``model.attention_inputs`` took,
``model.attention_pass_name``, or ``latent.inputs``, ``latent.pass_name``),
``LM_ATTN_BLOCKS_FITTED`` or ``LM_ATTN_BLOCKS_PLAIN`` (the same: whether
the attention kernels' tile sizes are ``model.attention_blocks``' own or
512 everywhere; ``attn_blocks_names``),
``LM_KDA_SCAN_KERNEL`` or ``LM_KDA_SCAN_PLAIN`` (one a delta layer a
sequence: which form ``delta.scan`` took, ``delta.scan_counter``),
``LM_KDA_PASS_FUSED`` or ``LM_KDA_PASS_PLAIN`` (the same: whether its gates
and gated output norm ran as delta_passes.py's, ``delta.pass_counter``),
``LM_KDA_BETA_OVER_ONE`` of ``LM_KDA_BETA`` ((position, head) pairs of the
delta layers whose beta is over 1, where ``kda_beta_scale`` lets it be),
``LM_GATE_LANES_OPEN`` of ``LM_GATE_LANES`` (a lane gate's lanes over a
half), ``LM_HEADS_HELD`` of ``LM_HEADS`` (a layer a sequence: the heads of
its attention held here, of all it has; from the host),
``LM_MIXERS_CONV`` of ``LM_MIXERS`` (a layer a sequence: the mixers that are
gated short convolutions, of all; with a ``conv`` layer, from the host),
``LM_MIXERS_SSD`` of ``LM_MIXERS`` (the same for selective state-space
mixers, ssd.py), ``LM_SSD_CHUNKS`` and ``LM_SSD_DEEP`` (a state-space layer
a sequence: the chunks its scan walked; the (chunk, head) pairs whose summed
log decay is under ``delta.DEEP``, counted on the device),
``LM_SSD_SCAN_KERNEL`` or ``LM_SSD_SCAN_PLAIN`` (which form ``ssd.scan``
took, ``ssd.scan_counter``),
``LM_ATTN_LANES`` of ``LM_ATTN_LANES_TILED`` (the same models' attention
layers, a layer a sequence: the lanes a head holds, of the lanes the
attention kernel is handed a head: equal where a head under a 128-lane tile
goes to it unpadded),
``LM_MTP_TOKENS`` (positions a multi-token module predicted from: ``B T``
a step that held one), ``LM_EMBED_ROWS`` (distinct embedding rows) and
``LM_MASKED_TOKENS`` (positions that carry a loss: the masked ones) are
computed on the device and read at the start of the next step,
which waits for the last one's programs anyway: one step is in flight
(``flush_stats`` reads the last step's).
"""

from __future__ import annotations

import contextlib
import itertools
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from ...runtime import device_lock
from ...runtime.zoo import current_zoo
from ...tables.factory import create_array_table, create_matrix_table
from ...updater import AddOption
from ...updater.rules import create_rule
from ...util.dashboard import count, monitor
from ...util.log import CHECK
from . import model as lm
from . import latent, mtp, streams
from .model import LMConfig

BF16 = jnp.bfloat16


def zipf_tokens(key, shape, vocab: int, exponent: float = 1.0):
    """Token ids drawn on the device from Zipf(``exponent``) over
    ``vocab`` ids, id 0 the most frequent: the inverse of the
    distribution's cumulative sum at uniform draws."""
    weights = 1.0 / np.arange(1, vocab + 1, dtype=np.float64) ** exponent
    cdf = jnp.asarray(np.cumsum(weights) / weights.sum(), jnp.float32)
    u = jax.random.uniform(key, shape, jnp.float32)
    return jnp.minimum(jnp.searchsorted(cdf, u), vocab - 1).astype(jnp.int32)


#: A delta layer's tensors (delta.py) that start from a draw of their own.
DECAY_INITS = ("a_log", "dt_bias")


def decay_init(name: str, size: int, seed: int) -> np.ndarray:
    """Where a delta layer's log decay ``-exp(a_log) softplus(. + dt_bias)``
    starts, as the published model's released implementation draws it:
    ``a_log`` the log of uniform(1, 16) a head; ``dt_bias`` the inverse
    softplus of a step drawn log-uniformly on [0.001, 0.1] a channel, so
    that a fresh layer's decay a position is ``exp(-1.6)`` at the fastest
    and mostly near 1."""
    rng = np.random.RandomState(seed % (2 ** 32))
    if name == "a_log":
        return np.log(rng.uniform(1.0, 16.0, size)).astype(np.float32)
    dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), size))
    return (dt + np.log(-np.expm1(-dt))).astype(np.float32)


def _dispatch(fn, *args):
    """A trainer-thread dispatch (guarded like every other: a no-op but
    where one process' threads must not overlap device programs)."""
    with device_lock.guard():
        return device_lock.settle(fn(*args))


def _kind(cfg: LMConfig, rope: int, window: int, seq_len: int):
    """``(rotary, mask, positions)`` of a layer's programs: the rotary
    positions are the kind's own description where the model has one a
    kind (``LMConfig.rotary``), and the positions are given where they
    are not the rows' own numbers: block diffusion's two copies, or the
    rows of a sectioned rotary (``Rotary.sections``)."""
    mask = cfg.layer_mask(window, seq_len)
    rotary = cfg.rotary(rope, window)
    if getattr(rotary, "sections", ()):     # text: the rows equal
        return rotary, mask, np.tile(np.arange(seq_len),
                                     (len(rotary.sections), 1))
    return rotary, mask, (
        mask.positions(2 * seq_len) if mask.kind == "blockdiff" else None)


def attn_pass_names(cfg: LMConfig, positions: int, module: bool):
    """A layer each, and with ``module`` the multi-token module's layer
    last (it is of the last layer's kinds): the counter one sequence of
    ``positions`` through it adds one to, by the form that the way from
    its attention's products to the kernel took (None where there is none:
    a delta layer, a convolution layer, a state-space layer)."""
    def name(layer):
        kind, rope = cfg.attention_of(layer), cfg.rope_layout[layer]
        if kind == "mla":
            return latent.pass_name(cfg, positions, rope)
        return None if kind in lm.MIXER_MODULES \
            else lm.attention_pass_name(cfg, positions, rope)

    names = [name(layer) for layer in range(cfg.n_layers)]
    return names + names[-1:] * bool(module)


def attn_blocks_names(cfg: LMConfig, seq_len: int, module: bool):
    """A layer each, and with ``module`` the multi-token module's layer
    last: the counter one sequence of ``seq_len`` tokens through it adds
    one to, by the tile sizes ``model._splash`` gives the attention's
    kernels (``model.attention_blocks_name``: the rule the program chose
    by, asked what ``attention_core`` asks it; None where the attention is
    not ``attention_core``'s: a delta layer, a selected one, no TPU)."""
    positions = seq_len * (2 if cfg.objective == "block_diffusion" else 1)

    def name(layer):
        kind = cfg.attention_of(layer)
        if kind in lm.MIXER_MODULES or cfg.selection != "none":
            return None
        if kind == "mla":       # latent.core: each head a group, causal
            return lm.attention_blocks_name(
                0, positions, cfg.qk_nope_dim + cfg.qk_rope_dim,
                cfg.v_head_dim, 1)
        return lm.attention_blocks_name(
            cfg.layer_mask(cfg.window_layout[layer], seq_len), positions,
            cfg.head_dim, cfg.head_dim,
            cfg.heads(layer) // cfg.n_kv_heads_held)

    names = [name(layer) for layer in range(cfg.n_layers)]
    return names + names[-1:] * bool(module)


def bias_step(cfg: LMConfig, stats):
    """What a sparse layer's router bias gets after a step whose forward
    program counted ``stats`` [B, 2 + n_experts] (model.layer_stats):
    ``bias_rate * sign(mean load - load)`` over the step's sequences, a
    DELTA that the server adds (the bias's table is under the plain
    rule; no gradient goes to it)."""
    load = jnp.sum(stats[:, 2:], axis=0).astype(jnp.float32)
    return cfg.bias_rate * jnp.sign(jnp.mean(load) - load)


def forward_program(cfg: LMConfig, rope: int, window: int, seq_len: int,
                    sparse: int = 1, *, attention=None):
    """``(float32 matrices, small, x [B, T, hidden]) -> (y, stats [B, 2],
    the matrices' bfloat16 copies, each token's experts [B, T, k])``, for a
    layer of the kind ``LMConfig.layer_kinds`` names (``sparse``: its
    feed-forward; its query heads are its ``wq``'s).
    Where the feed-forward is ``model.feed_forward_vjp``'s the ``stats``
    hold more (model.layer_stats). The streams' (``cfg.residual ==
    "mhc"``) takes and gives [B, n hidden, T], and its sparse layers give a
    fifth result, the bias's step (``bias_step``); so do the plain
    residual's where the router chooses through a bias. ``attention`` says
    the layer's kind of attention where it is a layer's to say
    (``LMConfig.attention_layout``)."""
    rope, mask, pos = _kind(cfg, rope, window, seq_len)

    biased = sparse and cfg.scoring == "sigmoid_bias"

    def forward(mats32, small, x):
        mats = {n: w.astype(BF16) for n, w in mats32.items()}
        y, stats, ids = jax.lax.map(
            lambda seq: lm.layer_forward(cfg, rope, mask, mats, small, seq,
                                         pos, sparse, attention), x)
        # the router outputs' counts alone: a delta layer's stats end in
        # another count (model.layer_stats)
        more = (bias_step(cfg, stats[:, :2 + cfg.n_experts]),) if biased \
            else ()
        return (y, stats, mats, ids) + more

    def forward_streams(mats32, small, x):
        mats = {n: w.astype(BF16) for n, w in mats32.items()}
        # a sequence is read where it lies in the step's stack and its
        # result written where it will lie (streams.Of): the loop's own
        # work is the streams' work
        def one(y, b):
            y, stats, ids = streams.layer_forward(
                cfg, sparse, mats, small, streams.Of(x, b),
                into=streams.Of(y, b))
            return y, (stats, ids)

        with jax.named_scope(streams.SCOPE):
            y, (stats, ids) = jax.lax.scan(one, jnp.zeros_like(x),
                                           jnp.arange(x.shape[0]))
        more = (bias_step(cfg, stats),) if sparse else ()
        return (y, stats, mats, ids) + more

    return jax.jit(forward_streams if cfg.residual == "mhc" else forward)


def _summed_over_sequences(one, mats, small, sequences, loop_scope=""):
    """``one(sequence) -> (carried cotangents, matrix gradients, small
    gradients)`` over the step's sequences, one at a time, the gradients
    float32 and summed. With a ``loop_scope`` the loop's own work is
    named: a sequence's way in and out of it (slices of the step's widest
    arrays) by that scope, the sums ``mv.lm.grad_sum``."""
    def named(scope):
        return jax.named_scope(scope) if loop_scope \
            else contextlib.nullcontext()

    def step(carry, seq):
        out, d_mats, d_small = one(seq)
        with named("mv.lm.grad_sum"):
            carry = jax.tree_util.tree_map(jnp.add, carry, (d_mats, d_small))
        return carry, out

    with named(loop_scope):
        (d_mats, d_small), out = jax.lax.scan(
            step, _zero_gradients(mats, small), sequences)
    return out, d_mats, d_small


def _zero_gradients(mats, small):
    return ({n: jnp.zeros(w.shape, jnp.float32) for n, w in mats.items()},
            jax.tree_util.tree_map(jnp.zeros_like, small))


def _learned(small):
    """A layer's float32 tensors that get a gradient: all but the
    router's bias."""
    return {n: g for n, g in small.items() if n != "router_bias"}


def backward_program(cfg: LMConfig, rope: int, window: int, seq_len: int,
                     sparse: int = 1, *, attention=None):
    """``(bfloat16 matrices, small, x, dy) -> (dx, matrix gradients, small
    gradients)``, the gradients float32 and summed over the sequences (a
    router bias gets none: ``small`` holds it, the gradients do not). With
    a ``cfg.selection`` a fourth result: the layer's inner loss (the
    indexer's divergence, sparse.py), summed over the sequences."""
    rope, mask, pos = _kind(cfg, rope, window, seq_len)

    def backward(mats, small, x, dy):
        # what a layer's pull gives beyond its three (a loss inside the
        # layer) rides with dx and is summed after
        def one(seq):
            dx, d_mats, d_small, *inner = lm.layer_grads(
                cfg, rope, mask, mats, small, *seq, pos, sparse, attention)
            return (dx, *inner), d_mats, d_small

        (dx, *inner), d_mats, d_small = _summed_over_sequences(
            one, mats, _learned(small), (x, dy))
        return (dx, d_mats, d_small, *(jnp.sum(s) for s in inner))

    def backward_streams(mats, small, x, dy):
        # the donated cotangents' stack is the carry: a sequence's dx is
        # written where its dy lay, once the layer has read that
        def one(carry, b):
            d, sums = carry
            d, d_mats, d_small = streams.layer_grads(
                cfg, sparse, mats, small, streams.Of(x, b), streams.Of(d, b),
                into=streams.Of(d, b))
            with jax.named_scope("mv.lm.grad_sum"):
                sums = jax.tree_util.tree_map(jnp.add, sums,
                                              (d_mats, d_small))
            return (d, sums), None

        with jax.named_scope(streams.SCOPE):
            (dx, (d_mats, d_small)), _ = jax.lax.scan(
                one, (dy, _zero_gradients(mats, _learned(small))),
                jnp.arange(x.shape[0]))
        return dx, d_mats, d_small

    return jax.jit(backward_streams if cfg.residual == "mhc" else backward,
                   donate_argnums=(3,))


def module_programs(cfg: LMConfig):
    """The multi-token module's three programs (mtp.py):
    ``forward(float32 matrices, small, xs [B, T, hidden], e_next) -> (y,
    stats, bfloat16 copies, experts, the bias's step)``;
    ``head(float32 head, the module's final norm, y, targets [B*T]) ->
    (weighted loss, dy, head gradient, norm gradient)``, the loss and its
    gradients times ``cfg.mtp_weight``; ``backward(bfloat16 matrices,
    small, xs, e_next, dy) -> ((dxs, de_next), matrix gradients, small
    gradients)``."""
    def mtp_forward(mats32, small, xs, e_next):
        mats = {n: w.astype(BF16) for n, w in mats32.items()}

        def one(seq):
            y, stats, _ = mtp.module_vjp(cfg, mats, small, *seq)
            return (y,) + stats

        y, stats, ids = jax.lax.map(one, (xs, e_next))
        return y, stats, mats, ids, bias_step(
            cfg, stats[:, :2 + cfg.n_experts])

    def mtp_head(head32, norm, y, targets):
        loss, dy, d_head, d_norm = lm.head_loss_and_grads(
            cfg, head32.astype(BF16), norm, y.reshape(-1, y.shape[-1]),
            targets, normaliser=targets.size / cfg.mtp_weight,
            scope="mv.lm.mtp.head")
        return loss, dy.reshape(y.shape), d_head, d_norm

    def mtp_backward(mats, small, xs, e_next, dy):
        def one(seq):
            xs, e_next, dy = seq
            dxs, de, d_mats, d_small = mtp.module_vjp(
                cfg, mats, small, xs, e_next)[2](dy)
            return (dxs, de), d_mats, d_small

        return _summed_over_sequences(one, mats, _learned(small),
                                      (xs, e_next, dy), loop_scope=mtp.SCOPE)

    return (jax.jit(mtp_forward), jax.jit(mtp_head, donate_argnums=(2,)),
            jax.jit(mtp_backward, donate_argnums=(4,)))


def head_program(cfg: LMConfig):
    """``(float32 head, final norm, x [B, T, hidden], targets [B*T]) ->
    (loss, dx, head gradient, norm gradient)``. Under block diffusion x is
    [B, 2T, hidden] and ``weights`` [B*T] a fifth argument: the loss is
    over the noised half, ``sum(weights * CE) / (B T)``, and the clean
    half's ``dx`` is zero."""
    def head_step(head32, norm, x, targets, weights=None):
        scored = x if weights is None else x[:, :x.shape[1] // 2]
        loss, dx, d_head, d_norm = lm.head_loss_and_grads(
            cfg, head32.astype(BF16), norm,
            scored.reshape(-1, x.shape[-1]), targets, weights)
        dx = dx.reshape(scored.shape)
        if weights is not None:
            dx = jnp.concatenate([dx, jnp.zeros_like(dx)], axis=1)
        return loss, dx, d_head, d_norm

    return jax.jit(head_step, donate_argnums=(2,))


def noise_program(cfg: LMConfig):
    """Block diffusion's batch preparation: ``(clean tokens [B, T], the
    trainer's key, the step's number) -> (ids [B, 2T] = [noised ; clean],
    targets [B*T], weights [B*T], distinct ids, masked positions, masked
    [B, T], t [B, T / block])``. The same key and step give the same
    draw; the key is an argument, so every seed runs one program."""
    def prepare(tokens, key, step):
        with jax.named_scope("mv.lm.noise"):
            key = jax.random.fold_in(key, step)
            noised, masked, t = lm.noise(cfg, key, tokens)
            weights = jnp.where(
                masked, 1.0 / jnp.repeat(t, cfg.block_length, axis=1), 0.0)
            ids = jnp.concatenate([noised, tokens], axis=1)
        return (ids, tokens.reshape(-1), weights.reshape(-1), _distinct(ids),
                jnp.sum(masked, dtype=jnp.int32), masked, t)

    return jax.jit(prepare)


def _distinct(ids):
    """The number of distinct ids."""
    with jax.named_scope("mv.lm.embed"):
        ordered = jnp.sort(ids.reshape(-1))
        return 1 + jnp.sum(ordered[1:] != ordered[:-1], dtype=jnp.int32)


class PSLMTrainer:
    def __init__(self, cfg: LMConfig, seq_len: int, sequences_per_step: int,
                 seed: int = 0, lr: float = 3e-4, beta1: float = 0.9,
                 beta2: float = 0.95, eps: float = 1e-8,
                 init_std: float = 0.02, embedding_std: float = 1.0,
                 warmup_steps: int = 0):
        # TRAINER_BUILD, entered and left by hand: a decorator's frame
        # between the caller and this constructor made the tables' init
        # programs lower a third slower on the chip machine's host
        # (PERF.md section 6, PR 68), and a ``with`` would indent it all.
        building = monitor("TRAINER_BUILD")
        building.__enter__()
        zoo = current_zoo()
        CHECK(zoo.servers_in_process,
              "PSLMTrainer needs in-process servers (device keys and "
              "device deltas)")
        CHECK(create_rule().name == "adam",
              "PSLMTrainer pushes raw gradients: start with "
              "-updater_type=adam")
        self.cfg, self.T, self.B = cfg, int(seq_len), int(sequences_per_step)
        self.diffusion = cfg.objective == "block_diffusion"
        CHECK(self.diffusion or cfg.objective == "next_token",
              f"unknown objective {cfg.objective!r}")
        self.lr, self.warmup_steps = float(lr), int(warmup_steps)
        self.option = AddOption(worker_id=max(zoo.worker_id, 0),
                                momentum=beta1, learning_rate=lr, rho=beta2,
                                lambda_=eps)    # the step's: see step()
        seeds = itertools.count(seed * 64)  # a matrix table each, in order

        def matrix(shape, std=init_std):
            # uniform on (-a, a) has the standard deviation a / sqrt(3): the
            # tables' own device-side init program (MatrixServer random_init)
            a = std * 3 ** 0.5
            return create_matrix_table(shape[0], shape[1],
                                       random_init=(-a, a), seed=next(seeds))

        # The embedding's rows are drawn at the size of a normed activation
        # and not at the matrices': under the pre-norm a row of 0.02 is
        # outweighed sevenfold after layer 0 by the attention's mean of
        # values, which untrained attention makes the same for every
        # position, and the routers of the later layers (they read the
        # residual stream raw) then send every token to the same experts
        # from the first step on (docs/LM_TRAINER.md).
        def table(name, shape):
            """A tensor's table by its name: a matrix drawn at ``init_std``
            (a stream mixer's ``phi`` at ``1 / sqrt(n hidden)``, so that a
            coefficient of a normed input is of order 1), a norm or a
            mixer's scalars 1, a mixer's offsets 0; a router's bias 0 and
            under the PLAIN rule, every other under the flag's Adam."""
            if name == "router_bias":   # ``scoring: "sigmoid_bias"`` alone
                return create_array_table(shape[0], updater_type="default")
            if name in DECAY_INITS:     # a delta layer's log decay: no
                #                         constant starts it (``decay_init``)
                return create_array_table(shape[0], fill=decay_init(
                    name, shape[0], next(seeds)))
            if name.startswith("conv_") and len(shape) == 2:    # uniform
                #                         on +- weights^-1/2 for [channels,
                #                         weights], a convolution's usual
                #                         start (its bias, ``conv_b``: 0)
                return matrix(shape, (3 * shape[1]) ** -0.5)
            if len(shape) == 2:
                return matrix(shape, shape[1] ** -0.5
                              if name.endswith("_phi") else init_std)
            return create_array_table(
                shape[0], fill=0.0 if name.endswith("_b") else 1.0)

        # Where ONE table is embedding and head (``cfg.tied``) this draw is
        # both, and the configuration gives ``embedding_std`` at the
        # matrices' size: at 1 a row's logit against itself would be
        # ``hidden``.
        self.embedding = matrix((cfg.vocab, cfg.hidden), embedding_std)
        self.layers: List[Dict[str, object]] = []
        for i in range(cfg.n_layers):
            self.layers.append({name: table(name, shape) for name, shape
                                in cfg.layer_shapes(i).items()})
        self.final_norm = create_array_table(cfg.hidden, fill=1.0)
        self.head = self.embedding if cfg.tied \
            else matrix((cfg.vocab, cfg.hidden))
        # the multi-token module: its own tensors, then its sparse layer's
        self.module: Dict[str, object] = {}
        if cfg.mtp_layers:
            CHECK(cfg.mtp_layers == 1 and not self.diffusion
                  and (cfg.residual == "mhc" or cfg.one_ffn_input),
                  "one multi-token module, after a stack of streams or of "
                  "plain layers whose feed-forward reads one normed input, "
                  "under the next-token objective")
            shapes = {**cfg.mtp_shapes(),
                      **cfg.layer_shapes(cfg.n_layers - 1)}
            self.module = {name: table(name, shape)
                           for name, shape in shapes.items()}
            self._module = module_programs(cfg)
        CHECK(not cfg.tied or not (cfg.mtp_layers or self.diffusion
                                   or cfg.residual == "mhc"),
              "one table for embedding and head: on the plain residual under "
              "the next-token objective, without a multi-token module")
        CHECK((cfg.residual_scale == 1.0 and cfg.embed_scale == 1.0)
              or (cfg.one_ffn_input and cfg.residual == "plain"
                  and not cfg.mtp_layers),
              "a residual or embedding multiplier: on the plain residual "
              "whose feed-forward reads one normed input, without a "
              "multi-token module")
        # whole-table traffic: every table but the embedding, which goes by
        # rows; a tied table is pulled whole at the head and pushed whole
        self._whole_bytes = 4 * (cfg.parameters() - (
            0 if cfg.tied else cfg.vocab * cfg.hidden))

        kinds = sorted(set(cfg.layer_kinds()))
        # a kind is (rotary, window[, sparse[, query heads]]); the heads
        # tell two kinds apart and are their tensors' to say. With an
        # ``attention_layout`` the kind's last says the layer's attention,
        # which its programs are built from.
        self._forward, self._backward = (
            {k: build(cfg, *k[:2], self.T, *k[2:3],
                      **({"attention": k[3]} if cfg.attention_layout else {}))
             for k in kinds} for build in (forward_program, backward_program))
        self._split = jax.jit(self._split_more if cfg.mtp_layers
                              else self._split_tokens)
        self.streams = cfg.residual == "mhc"
        # the embedding's rows' way into the first layer and back out of
        # it: the streams', or with a module on the plain residual the
        # split from the next tokens' rows; None where the rows ARE the
        # first layer's input
        self._enter = self._enter_back = None
        if self.streams:
            self._enter = jax.jit(self._enter_streams)
            self._leave = jax.jit(self._leave_streams, donate_argnums=(0,))
            self._leave_back = jax.jit(self._leave_streams_back,
                                       donate_argnums=(0,))
            self._enter_back = jax.jit(self._enter_streams_back,
                                       donate_argnums=(0,))
        elif self.module:
            self._enter = jax.jit(self._enter_rows)
            self._enter_back = jax.jit(self._enter_rows_back,
                                       donate_argnums=(0,))
        elif cfg.embed_scale != 1.0:
            self._enter = jax.jit(self._enter_scaled)
            self._enter_back = jax.jit(self._enter_scaled_back,
                                       donate_argnums=(0,))
        if self.streams or self.module:
            self._sum = jax.jit(
                lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
                donate_argnums=(0, 1))
        # which of a step's stats are a sparse layer's (the module's layer
        # last), and the rows of its experts' buffer (model.routed_experts)
        self._sparse = [cfg.sparse(i) for i in range(cfg.n_layers)] \
            + [1] * bool(self.module)
        positions = self.T * (2 if self.diffusion else 1)
        self._experts_cap = lm.experts_capacity(cfg, positions)
        # and which form each layer's ``model.attention_inputs`` (or
        # ``latent.inputs``) takes of a sequence: the counter's name
        self._attn_pass = attn_pass_names(cfg, positions, bool(self.module))
        # and which tile sizes its attention's kernels run at
        self._attn_blocks = attn_blocks_names(cfg, self.T, bool(self.module))
        # (held, all) of a step's heads, a layer a sequence (the module's
        # layer is of the last layer's kind)
        layers = list(range(cfg.n_layers)) \
            + [cfg.n_layers - 1] * bool(self.module)
        self._heads = tuple(self.B * sum(heads) for heads in zip(
            *(cfg.heads_of(i) for i in layers)))
        # the mixers that are convolutions or state-space layers, of all,
        # and the lanes a head of the others holds, a layer a sequence
        # (``_count_stats``)
        kinds_of = [cfg.attention_of(i) for i in range(cfg.n_layers)]
        self._mixers = {"LM_MIXERS": self.B * cfg.n_layers, **{
            name: self.B * kinds_of.count(kind)
            for kind, name in (("conv", "LM_MIXERS_CONV"),
                               ("ssd", "LM_MIXERS_SSD"))
            if kind in kinds_of}}
        self._attn_lanes = self.B * kinds_of.count("gqa") * cfg.head_dim
        self._tie = jax.jit(self._tie_gradients, donate_argnums=(0,)) \
            if cfg.tied else None
        self._noise = noise_program(cfg) if self.diffusion else None
        self._noise_key = jax.random.PRNGKey(seed)
        self._head_program = head_program(cfg)
        self._sum_scalars = jax.jit(lambda xs: sum(xs[1:], xs[0]))
        self._pending = []      # (table, msg id) of Adds not yet waited for
        self._stats = []        # device counts of steps not yet read
        self.steps = 0
        self.last_loss = None   # device scalar
        self.last_inner_loss = None     # the layers' inner losses' sum, with
        #                                 a ``cfg.selection``: device scalar
        self._last_dx = None    # the last program's result of the last step
        building.__exit__(None, None, None)

    # -- the tables, by name (the checks read them) ---------------------------
    def tables(self) -> Dict[str, object]:
        out = {"embedding": self.embedding}
        for i, layer in enumerate(self.layers):
            out.update({f"layer{i}.{name}": t for name, t in layer.items()})
        out["final_norm"] = self.final_norm
        if not self.cfg.tied:       # tied: the embedding IS the head
            out["head"] = self.head
        own = self.cfg.mtp_shapes() if self.module else ()
        out.update({f"mtp.{name}" if name in own else f"mtp.layer.{name}": t
                    for name, t in self.module.items()})
        return out

    # -- programs -----------------------------------------------------------------
    def _split_tokens(self, tokens):
        """[B, T+1] tokens -> ids [B, T], the next tokens [B*T] and the
        number of distinct ids."""
        with jax.named_scope("mv.lm.embed"):
            ids = tokens[:, :-1]
            distinct = _distinct(ids)
            return ids, tokens[:, 1:].reshape(-1), distinct

    def _split_more(self, tokens):
        """With a multi-token module: [B, T+2] tokens -> the ids to embed
        [B, T+1] (the last only as a next token), the two targets ([B*T]
        each: the next token and the one after) and the distinct ids."""
        with jax.named_scope("mv.lm.embed"):
            ids = tokens[:, :-1]
            return ids, (tokens[:, 1:-1].reshape(-1),
                         tokens[:, 2:].reshape(-1)), _distinct(ids)

    @staticmethod
    def _tie_gradients(d_head, ids, d_rows):
        """One table's gradient from both of its uses: the head's [vocab,
        hidden] with the gradient of each position's embedding row added to
        the row its id names."""
        with jax.named_scope("mv.lm.embed"):
            return d_head.at[ids.reshape(-1)].add(
                d_rows.reshape(-1, d_rows.shape[-1]))

    # -- the streams' two ends (streams.py; under mv.lm.embed) -------------------
    def _enter_streams(self, rows):
        """The embedding's rows [B, T (+1), hidden] -> the first layer's
        input, every stream alike, and the next tokens' rows (None without
        a module)."""
        with jax.named_scope("mv.lm.embed"):
            x = streams.expand(self.cfg, rows[:, :self.T])
            return x, (rows[:, 1:] if self.module else None)

    def _leave_streams(self, x):
        with jax.named_scope("mv.lm.embed"):
            return streams.collapse(self.cfg, x)

    def _leave_streams_back(self, dxs):
        with jax.named_scope("mv.lm.embed"):
            return streams.expand(self.cfg, dxs)

    def _enter_streams_back(self, dx, de_next):
        """The rows' gradient [B, T (+1), hidden] from the first layer's
        and (with a module) the next tokens' rows'."""
        with jax.named_scope("mv.lm.embed"):
            d_rows = streams.collapse(self.cfg, dx)
            if de_next is None:
                return d_rows
            return self._rows_back(d_rows, de_next)

    # -- and, with a module, the plain residual's (under mv.lm.embed too) --------
    def _enter_rows(self, rows):
        """The embedding's rows [B, T+1, hidden] -> the first layer's input
        [B, T, hidden] and the next tokens' rows."""
        with jax.named_scope("mv.lm.embed"):
            return rows[:, :self.T], rows[:, 1:]

    def _enter_rows_back(self, dx, de_next):
        with jax.named_scope("mv.lm.embed"):
            return self._rows_back(dx, de_next)

    # -- and a model whose first layer reads ``embed_scale`` times the rows ------
    def _enter_scaled(self, rows):
        with jax.named_scope("mv.lm.embed"):
            return self.cfg.embed_scale * rows, None

    def _enter_scaled_back(self, dx, _):
        """The rows' gradient: the first layer's input's times the scale,
        before it goes into the table's Add."""
        with jax.named_scope("mv.lm.embed"):
            return self.cfg.embed_scale * dx

    @staticmethod
    def _rows_back(d_rows, de_next):
        """The rows' gradient [B, T+1, hidden]: position ``i``'s as the
        first layer's input and as position ``i - 1``'s next token."""
        return jnp.pad(d_rows, ((0, 0), (0, 1), (0, 0))) \
            + jnp.pad(de_next, ((0, 0), (1, 0), (0, 0)))

    def noised(self, tokens):
        """Block diffusion's batch for ``tokens`` as the step about to run
        makes it (``noise_program``'s results: the noise is drawn from the
        trainer's seed and that step's number)."""
        return _dispatch(self._noise, tokens, self._noise_key,
                         np.int32(self.steps))

    def prepare(self, tokens):
        """The step's ``(ids, targets, weights or None, distinct ids,
        positions that carry a loss)`` from ``tokens``: split, or under
        block diffusion noised."""
        if self.diffusion:
            return self.noised(tokens)[:5]
        ids, targets, distinct = _dispatch(self._split, tokens)
        return ids, targets, None, distinct, self.B * self.T

    # -- Gets and Adds ---------------------------------------------------------------
    def _pull(self, tables, shapes, matrices):
        """``tables`` whole: ``(float32 matrices, small)``, a matrix in its
        tensor's shape."""
        with monitor("LM_GET_PARAMS"):
            mats = {n: tables[n].get_device().reshape(shapes[n])
                    for n in matrices}
            small = {n: tables[n].get_device().reshape(shapes[n])
                     for n in tables if n not in matrices}
        return mats, small

    def _pull_layer(self, i: int):
        return self._pull(self.layers[i], self.cfg.layer_shapes(i),
                          self.cfg.matrices(i))

    def _pull_module(self):
        cfg = self.cfg
        shapes = {**cfg.layer_shapes(cfg.n_layers - 1), **cfg.mtp_shapes()}
        mats, small = self._pull(
            {n: t for n, t in self.module.items() if n != "final_norm"},
            shapes, cfg.matrices(cfg.n_layers - 1) + mtp.MATRICES)
        return mats, small

    def _push_layer(self, tables, grads, bias_step) -> None:
        """A layer's gradients to their tables and, with a router, its
        bias's step to the table under the plain rule."""
        with monitor("LM_ADD_GRADS"):
            for name, grad in grads.items():
                self._push(tables[name], grad)
            for delta in bias_step:
                self._push(tables["router_bias"], delta)
                count("LM_ROUTER_BIAS_ADDS")

    def _push(self, table, delta, ids=None) -> None:
        """One gradient to its table: whole, or by ``ids`` as device keys."""
        if ids is None:
            msg_id = table.add_async(delta, self.option)
        else:
            msg_id = table.add_rows_async(ids, delta, self.option)
        self._pending.append((table, msg_id))

    def _push_embedding(self, d_head, ids, d_rows) -> None:
        """The embedding's Add, the step's last: its rows' gradient by the
        step's ids as device keys; a tied table's ONE Add, whole, of both
        uses' gradients summed (``_tie_gradients``; under Adam two Adds
        would be two steps of the moments)."""
        if not self.cfg.tied:
            return self._push(self.embedding, d_rows, ids)
        self._push(self.embedding, _dispatch(self._tie, d_head, ids, d_rows))
        count("LM_TIED_ADDS")

    def _drain(self, upto=None) -> None:
        """Wait for the pending Adds' acknowledgements: all, or the first
        ``upto`` (which stay listed; waiting again costs nothing)."""
        for table, msg_id in self._pending[:upto]:
            table.wait(msg_id)
        if upto is None:
            self._pending.clear()

    # -- a step ------------------------------------------------------------------------
    def step(self, tokens):
        """One step on ``tokens`` int32 on the device: [B, T+1] (with a
        multi-token module [B, T+2]), or [B, T] clean tokens under block
        diffusion. Returns the loss, a device scalar."""
        cfg = self.cfg
        CHECK(tuple(tokens.shape) == (
            self.B, self.T + (not self.diffusion) + cfg.mtp_layers),
            "bad token shape")
        with monitor("LM_STEP"):
            # One step in flight: a program's results are allocated when
            # it is dispatched, so a host that ran a step ahead would hold
            # two steps' activations. The last step's counts are there by
            # then, and its Adds long acknowledged.
            if self._last_dx is not None:
                with device_lock.guard():
                    self._last_dx.block_until_ready()
                self._last_dx = None
            self.flush_stats()
            self._drain()
            if self.warmup_steps:   # no Add of the last step still reads it
                self.option.learning_rate = self.lr * min(
                    1.0, (self.steps + 1) / self.warmup_steps)
            ids, targets, weights, distinct, scored = self.prepare(tokens)
            with monitor("LM_GET_PARAMS"):
                x = self.embedding.get_rows_device(ids)
            if self._enter:
                x, e_next = _dispatch(self._enter, x)
            kinds = cfg.layer_kinds()
            kept, stats = [], []
            for i, kind in enumerate(kinds):
                mats32, small = self._pull_layer(i)
                y, layer_stats, mats, _, *bias_step = _dispatch(
                    self._forward[kind], mats32, small, x)
                del mats32
                kept.append((mats, small, x, bias_step))
                stats.append(layer_stats)
                x = y
            if self.streams:
                x = _dispatch(self._leave, x)
            with monitor("LM_GET_PARAMS"):
                head32 = self.head.get_device()
                norm = self.final_norm.get_device()
            if self.module:     # targets: (the next token, the one after)
                targets, after = targets
                second = self._module_step(head32, x, e_next, after, stats)
            loss, dx, d_head, d_norm = _dispatch(
                self._head_program, head32, norm, x, targets,
                *(() if weights is None else (weights,)))
            del head32, x
            if self.module:
                # ONE Add a table a step: the head's two gradients, the
                # stream's and the loss summed first (under Adam two Adds
                # would be two steps of the moments)
                (loss, dx, d_head), de_next = _dispatch(
                    self._sum, (loss, dx, d_head), second[:3]), second[3]
            with monitor("LM_ADD_GRADS"):
                if not cfg.tied:    # tied: with the rows' gradient, below
                    self._push(self.head, d_head)
                self._push(self.final_norm, d_norm)
            if self.streams:
                dx = _dispatch(self._leave_back, dx)
            pushed = []     # where each layer's Adds begin in _pending
            inner_losses = []   # a layer's own loss (``cfg.selection``)
            for i in reversed(range(cfg.n_layers)):
                mats, small, x_in, bias_step = kept.pop()
                if self.streams and len(pushed) >= 2:
                    # A layer's gradients live until the server has taken
                    # their Adds: with the mixers' passes as kernels a
                    # layer's backward program is shorter than the
                    # server's way through its two dozen Adds, and left
                    # alone the trainer ends a step with every layer's
                    # gradients waiting (0.7 GB more at the peak). So it
                    # goes on only once the layer before the last one's
                    # are acknowledged; the device has the last one's
                    # program to run meanwhile.
                    self._drain(pushed[-1])
                pushed.append(len(self._pending))
                dx, d_mats, d_small, *inner = _dispatch(
                    self._backward[kinds[i]], mats, small, x_in, dx)
                inner_losses += inner
                self._push_layer(self.layers[i], {**d_mats, **d_small},
                                 bias_step)
            if self._enter_back:    # and the next tokens' rows' gradient
                dx = _dispatch(self._enter_back, dx,
                               de_next if self.module else None)
            with monitor("LM_ADD_GRADS"):
                self._push_embedding(d_head, ids, dx)
        self.steps += 1
        self.last_loss, self._last_dx = loss, dx
        if inner_losses:
            self.last_inner_loss = _dispatch(self._sum_scalars, inner_losses)
        count("LM_TOKENS", self.B * self.T)
        count("LM_POSITIONS", ids.size)
        count("LM_GET_BYTES", self._whole_bytes)
        count("LM_ADD_BYTES", self._whole_bytes)
        if self.module:
            count("LM_MTP_TOKENS", self.B * self.T)
        self._stats.append((stats, distinct, scored))
        return loss

    def _module_step(self, head32, xs, e_next, targets, stats):
        """The multi-token module from the last layer's stream ``xs`` [B,
        T, hidden] (the summed streams where there are streams) to its
        Adds, all but the head's and the embedding's: ``(weighted second
        loss, dxs, the head's second gradient, the next tokens' rows'
        gradient)``. Its layer's counts join ``stats``."""
        forward, head, backward = self._module
        with monitor("LM_MTP_STEP"):
            mats32, small = self._pull_module()
            with monitor("LM_GET_PARAMS"):
                norm = self.module["final_norm"].get_device()
            y, layer_stats, mats, _, bias_step = _dispatch(
                forward, mats32, small, xs, e_next)
            del mats32
            stats.append(layer_stats)
            loss, dy, d_head, d_norm = _dispatch(head, head32, norm, y,
                                                 targets)
            (dxs, de_next), d_mats, d_small = _dispatch(
                backward, mats, small, xs, e_next, dy)
            self._push_layer(self.module,
                             {**d_mats, **d_small, "final_norm": d_norm},
                             [bias_step])
        return loss, dxs, d_head, de_next

    def _count_stats(self, entry) -> None:
        stats, distinct, scored = entry
        # a layer: [B, 2]; with every router output counted [B, 2 + E];
        # with a gate one more, last (model.layer_stats)
        per_layer = [np.asarray(s) for s in stats]
        count("LM_HELD_ASSIGNMENTS", int(sum(s[:, 0].sum()
                                             for s in per_layer)))
        count("LM_EXPERT_MAX_TOKENS", int(sum(s[:, 1].sum()
                                              for s in per_layer)))
        # which buffer ``model.routed_experts`` took on the device, from
        # the count it chose by
        fits = np.concatenate([np.zeros(0, bool)] + [
            s[:, 0] <= self._experts_cap for s, sparse
            in zip(per_layer, self._sparse) if sparse])
        for name, n in (("LM_EXPERTS_SHORT", fits.sum()),
                        ("LM_EXPERTS_FULL", (~fits).sum())):
            if n:
                count(name, int(n))
        for name, s in zip(self._attn_pass + self._attn_blocks,
                           per_layer * 2):
            if name:            # one a layer a sequence
                count(name, len(s))
        outputs = self.cfg.n_experts
        fullest = sum(int(s[:, 2:2 + outputs].sum(axis=0).max(initial=0))
                      for s in per_layer if s.shape[1] >= 2 + outputs)
        if fullest:
            count("LM_ROUTER_LOAD_MAX", fullest)
        count("LM_HEADS_HELD", self._heads[0])
        count("LM_HEADS", self._heads[1])
        if self.cfg.attn_gate == "head":
            # each layer's gates summed over its heads, the step's mean
            count("LM_GATE_OPEN", int(round(sum(
                s[:, -1].mean() for s in per_layer))))
        if self.cfg.attn_gate == "lane":
            # a gated layer's last: the lanes whose gate is over a half
            gated = [s for s, kind in zip(per_layer,
                                          self.cfg.attention_layout)
                     if kind == "gqa"]
            count("LM_GATE_LANES", sum(len(s) for s in gated) * self.T
                  * self.cfg.n_heads_held * self.cfg.head_dim)
            count("LM_GATE_LANES_OPEN",
                  int(sum(s[:, -1].astype(np.int64).sum() for s in gated)))
        if self.cfg.selection != "none":
            # a layer's last four (sparse.COUNTS), every layer and sequence
            for at, name in enumerate(("LM_SELECTED_PAIRS", "LM_CAUSAL_PAIRS",
                                       "LM_SELECT_TILES_LIVE",
                                       "LM_SELECT_TILES"), start=-4):
                count(name, int(sum(s[:, at].astype(np.int64).sum()
                                    for s in per_layer)))
        if "kda" in self.cfg.attention_layout:
            # a delta layer's last: the (chunk, head, channel) triples whose
            # summed log decay is under delta.DEEP, of all it has
            from . import delta
            scanned = [s for s, kind in zip(per_layer,
                                            self.cfg.attention_layout)
                       if kind == "kda"]
            sequences = sum(len(s) for s in scanned)
            chunks = sequences * (self.T // delta.chunk_of(self.T))
            count("LM_KDA_TOKENS", sequences * self.T)
            # which form each of their scans, and of their gates and gated
            # norms, took: the tests delta.scan and delta.attention_vjp
            # chose by
            count(delta.scan_counter(self.cfg, self.T), sequences)
            count(delta.pass_counter(self.cfg, self.T), sequences)
            count("LM_KDA_CHUNKS", chunks)
            heads = self.cfg.kda_heads_held
            count("LM_KDA_DECAY_CHANNELS",
                  chunks * heads * self.cfg.kda_head_dim)
            if self.cfg.kda_beta_scale != 1:
                # before the deep count: the (position, head) pairs whose
                # beta is over 1, of all
                count("LM_KDA_BETA", sequences * self.T * heads)
                count("LM_KDA_BETA_OVER_ONE", int(sum(
                    s[:, -2].astype(np.int64).sum() for s in scanned)))
            deep = int(sum(s[:, -1].astype(np.int64).sum() for s in scanned))
            if deep:
                count("LM_KDA_DECAY_DEEP", deep)
        if "ssd" in self.cfg.attention_layout:
            # a state-space layer's last: the (chunk, head) pairs whose
            # summed log decay is under delta.DEEP, of LM_SSD_CHUNKS times
            # the heads
            from . import ssd
            scanned = [s for s, kind in zip(per_layer,
                                            self.cfg.attention_layout)
                       if kind == "ssd"]
            sequences = sum(len(s) for s in scanned)
            count(ssd.scan_counter(self.cfg, self.T), sequences)
            count("LM_SSD_CHUNKS", sequences
                  * (self.T // ssd.chunk_of(self.cfg, self.T)))
            deep = int(sum(s[:, -1].astype(np.int64).sum() for s in scanned))
            if deep:
                count("LM_SSD_DEEP", deep)
        if {"conv", "ssd"} & set(self.cfg.attention_layout):
            # a model with a mixer that is not attention
            for name, n in self._mixers.items():
                count(name, n)
            # a head goes to ``model.attention_core`` at its own lanes
            count("LM_ATTN_LANES", self._attn_lanes)
            count("LM_ATTN_LANES_TILED", self._attn_lanes)
        count("LM_EMBED_ROWS", int(distinct))
        count("LM_MASKED_TOKENS", int(scored))

    def flush_stats(self) -> None:
        """Read the device counts of the steps still unread (a sync)."""
        while self._stats:
            self._count_stats(self._stats.pop(0))

    def sync(self) -> None:
        """Return when every Add so far has been applied: a Get of one
        row of the last table written comes back only behind them."""
        self._drain()
        self.embedding.get_rows(np.zeros(1, np.int32))

    def close(self) -> None:
        self._drain()
        self.flush_stats()

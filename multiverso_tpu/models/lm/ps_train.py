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
``LM_GET_PARAMS``, ``LM_ADD_GRADS``. Counters: ``LM_TOKENS`` (tokens
trained, ``B T``), ``LM_POSITIONS`` (positions through the layers: the
same, or ``2 B T`` under block diffusion), ``LM_GET_BYTES`` and
``LM_ADD_BYTES`` (whole-table traffic) at once; ``LM_HELD_ASSIGNMENTS``
((token, expert) assignments on held experts, every layer),
``LM_EXPERT_MAX_TOKENS`` (the fullest held expert's tokens, summed over
layers), ``LM_EMBED_ROWS`` (distinct embedding rows) and
``LM_MASKED_TOKENS`` (positions that carry a loss: the masked ones) are
computed on the device and read at the start of the next step,
which waits for the last one's programs anyway: one step is in flight
(``flush_stats`` reads the last step's).
"""

from __future__ import annotations

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


def _dispatch(fn, *args):
    """A trainer-thread dispatch (guarded like every other: a no-op but
    where one process' threads must not overlap device programs)."""
    with device_lock.guard():
        return device_lock.settle(fn(*args))


def _kind(cfg: LMConfig, rope: int, window: int, seq_len: int):
    """``(rotary, mask, positions)`` of a layer's programs: the positions
    are given where they are not the rows' own numbers."""
    mask = cfg.layer_mask(window, seq_len)
    return bool(rope), mask, (mask.positions(2 * seq_len)
                              if mask.kind == "blockdiff" else None)


def forward_program(cfg: LMConfig, rope: int, window: int, seq_len: int):
    """``(float32 matrices, small, x [B, T, hidden]) -> (y, stats [B, 2],
    the matrices' bfloat16 copies, each token's experts [B, T, k])``."""
    rope, mask, pos = _kind(cfg, rope, window, seq_len)

    def forward(mats32, small, x):
        mats = {n: w.astype(BF16) for n, w in mats32.items()}
        y, stats, ids = jax.lax.map(
            lambda seq: lm.layer_forward(cfg, rope, mask, mats, small, seq,
                                         pos), x)
        return y, stats, mats, ids

    return jax.jit(forward)


def backward_program(cfg: LMConfig, rope: int, window: int, seq_len: int):
    """``(bfloat16 matrices, small, x, dy) -> (dx, matrix gradients, small
    gradients)``, the gradients float32 and summed over the sequences."""
    rope, mask, pos = _kind(cfg, rope, window, seq_len)

    def backward(mats, small, x, dy):
        def one(carry, seq):
            x, dy = seq
            dx, d_mats, d_small = lm.layer_grads(cfg, rope, mask, mats,
                                                 small, x, dy, pos)
            return jax.tree_util.tree_map(jnp.add, carry,
                                          (d_mats, d_small)), dx

        zeros = ({n: jnp.zeros(w.shape, jnp.float32)
                  for n, w in mats.items()},
                 jax.tree_util.tree_map(jnp.zeros_like, small))
        (d_mats, d_small), dx = jax.lax.scan(one, zeros, (x, dy))
        return dx, d_mats, d_small

    return jax.jit(backward, donate_argnums=(3,))


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
        seeds = iter(range(seed * 64, seed * 64 + 64))

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
        self.embedding = matrix((cfg.vocab, cfg.hidden), embedding_std)
        self.layers: List[Dict[str, object]] = []
        for _ in range(cfg.n_layers):
            tables = {}
            for name, shape in cfg.layer_shapes().items():
                tables[name] = matrix(shape) if len(shape) == 2 \
                    else create_array_table(shape[0], fill=1.0)
            self.layers.append(tables)
        self.final_norm = create_array_table(cfg.hidden, fill=1.0)
        self.head = matrix((cfg.vocab, cfg.hidden))
        self._whole_bytes = 4 * (cfg.parameters() - cfg.vocab * cfg.hidden)

        kinds = sorted(set(zip(cfg.rope_layout, cfg.window_layout)))
        self._forward = {k: forward_program(cfg, *k, self.T) for k in kinds}
        self._backward = {k: backward_program(cfg, *k, self.T)
                          for k in kinds}
        self._split = jax.jit(self._split_tokens)
        self._noise = noise_program(cfg) if self.diffusion else None
        self._noise_key = jax.random.PRNGKey(seed)
        self._head_program = head_program(cfg)
        self._pending = []      # (table, msg id) of Adds not yet waited for
        self._stats = []        # device counts of steps not yet read
        self.steps = 0
        self.last_loss = None   # device scalar
        self._last_dx = None    # the last program's result of the last step

    # -- the tables, by name (the checks read them) ---------------------------
    def tables(self) -> Dict[str, object]:
        out = {"embedding": self.embedding}
        for i, layer in enumerate(self.layers):
            out.update({f"layer{i}.{name}": t for name, t in layer.items()})
        out.update({"final_norm": self.final_norm, "head": self.head})
        return out

    # -- programs -----------------------------------------------------------------
    def _split_tokens(self, tokens):
        """[B, T+1] tokens -> ids [B, T], the next tokens [B*T] and the
        number of distinct ids."""
        with jax.named_scope("mv.lm.embed"):
            ids = tokens[:, :-1]
            distinct = _distinct(ids)
            return ids, tokens[:, 1:].reshape(-1), distinct

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
        return ids, targets, None, distinct, targets.size

    # -- Gets and Adds ---------------------------------------------------------------
    def _pull_layer(self, i: int):
        tables = self.layers[i]
        with monitor("LM_GET_PARAMS"):
            mats = {n: tables[n].get_device().reshape(
                self.cfg.layer_shapes()[n]) for n in lm.LAYER_MATRICES}
            small = {n: tables[n].get_device() for n in self.cfg.small_names}
        return mats, small

    def _push(self, table, delta, ids=None) -> None:
        """One gradient to its table: whole, or by ``ids`` as device keys."""
        if ids is None:
            msg_id = table.add_async(delta, self.option)
        else:
            msg_id = table.add_rows_async(ids, delta, self.option)
        self._pending.append((table, msg_id))

    def _drain(self) -> None:
        for table, msg_id in self._pending:
            table.wait(msg_id)
        self._pending.clear()

    # -- a step ------------------------------------------------------------------------
    def step(self, tokens):
        """One step on ``tokens`` int32 on the device: [B, T+1], or
        [B, T] clean tokens under block diffusion. Returns the loss, a
        device scalar."""
        cfg = self.cfg
        CHECK(tuple(tokens.shape) == (self.B, self.T + (not self.diffusion)),
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
            kinds = list(zip(cfg.rope_layout, cfg.window_layout))
            kept, stats = [], []
            for i, kind in enumerate(kinds):
                mats32, small = self._pull_layer(i)
                y, layer_stats, mats, _ = _dispatch(self._forward[kind],
                                                    mats32, small, x)
                del mats32
                kept.append((mats, small, x))
                stats.append(layer_stats)
                x = y
            with monitor("LM_GET_PARAMS"):
                head32 = self.head.get_device()
                norm = self.final_norm.get_device()
            loss, dx, d_head, d_norm = _dispatch(
                self._head_program, head32, norm, x, targets,
                *(() if weights is None else (weights,)))
            del head32, x
            with monitor("LM_ADD_GRADS"):
                self._push(self.head, d_head)
                self._push(self.final_norm, d_norm)
            for i in reversed(range(cfg.n_layers)):
                mats, small, x_in = kept.pop()
                dx, d_mats, d_small = _dispatch(self._backward[kinds[i]],
                                                mats, small, x_in, dx)
                with monitor("LM_ADD_GRADS"):
                    for name, grad in {**d_mats, **d_small}.items():
                        self._push(self.layers[i][name], grad)
            with monitor("LM_ADD_GRADS"):
                self._push(self.embedding, dx, ids)
        self.steps += 1
        self.last_loss, self._last_dx = loss, dx
        count("LM_TOKENS", self.B * self.T)
        count("LM_POSITIONS", ids.size)
        count("LM_GET_BYTES", self._whole_bytes)
        count("LM_ADD_BYTES", self._whole_bytes)
        self._stats.append((stats, distinct, scored))
        return loss

    def _count_stats(self, entry) -> None:
        stats, distinct, scored = entry
        per_layer = np.stack([np.asarray(s) for s in stats])  # [L, B, 2]
        count("LM_HELD_ASSIGNMENTS", int(per_layer[..., 0].sum()))
        count("LM_EXPERT_MAX_TOKENS", int(per_layer[..., 1].sum()))
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

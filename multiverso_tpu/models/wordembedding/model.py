"""Word2vec models: SGNS + hierarchical softmax, skip-gram + CBOW.

TPU-native re-design of the reference's WordEmbedding compute core
(ref: Applications/WordEmbedding/src/wordembedding.cpp — per-window scalar
FeedForward/BPOutputLayer loops): one jitted step trains a whole batch of
(center, context) pairs on the MXU.

The central design decision (round 2): **both** the local and the
parameter-server trainer work on COMPACT row sets. A host-side
preparation pass computes the unique embedding rows a batch touches
(input rows from centers/window words; output rows from targets plus
host-sampled negatives or Huffman path nodes) and remaps batch indices
to compact slots. Then:

- **local mode**: one jitted step gathers those rows from the full
  device tables, trains the compact [R, D] matrices, and scatter-adds
  the updates back — donated buffers, HBM traffic O(batch). (The naive
  formulation differentiates through the full V x D tables and makes
  every step O(vocab) in memory traffic: at 1M+ vocab that is ~GBs per
  batch and dominates wall clock.)
- **PS mode**: the same prepared row sets drive row-sparse table pulls,
  the same compact loss trains the pulled rows, and row deltas
  ``(new - old) / num_workers`` push back (ref: communicator.cpp:
  117-249), pipelined across batches (ref: distributed_wordembedding.
  cpp:203-224).

Negatives sample from the unigram^0.75 distribution via Vose alias
tables — in-jit on the local path, host-side (numpy) on the PS path,
where the row set must be known before the pull. The learning rate
decays linearly in processed words (ref:
distributed_wordembedding.cpp:92-134; in PS mode the global count rides
a KV table)."""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ... import create_kv_table, create_matrix_table
from ...tables import client_cache
from ...util.dashboard import monitor
from .data import CbowBatch, PairBatch
from .dictionary import Dictionary
from .huffman import build_huffman


_MAX_EXP = 6.0  # word2vec.c's sigmoid-table range


class Word2VecConfig:
    """Mirror of the reference's CLI options (ref: WordEmbedding
    src/util.cpp ParseArgs: -size -window -negative -epoch -min_count
    -sample -init_learning_rate -cbow -hs ...)."""

    def __init__(self, embedding_size: int = 100, window: int = 5,
                 negative: int = 5, epochs: int = 1, min_count: int = 5,
                 sample: float = 1e-3, init_learning_rate: float = 0.025,
                 cbow: bool = False, hs: bool = False,
                 batch_size: int = 4096, seed: int = 1,
                 use_ps: bool = False, batch_group: int = 16,
                 neg_block: int = 1, per_pair: bool = False):
        self.embedding_size = embedding_size
        self.window = window
        self.negative = negative
        self.epochs = epochs
        self.min_count = min_count
        self.sample = sample
        self.init_learning_rate = init_learning_rate
        self.cbow = cbow
        self.hs = hs
        self.batch_size = batch_size
        self.seed = seed
        self.use_ps = use_ps
        # Batches per device dispatch in train_batches (local mode): the
        # K-step on-device loop that amortizes per-call dispatch latency.
        # 1 disables grouping.
        self.batch_group = batch_group
        # Device-pipeline negative sharing: one draw of K negatives per
        # block of this many consecutive centers (1 = per-center, the
        # round-3 behavior; expected gradient unchanged, negative row
        # traffic divided by the block factor).
        self.neg_block = neg_block
        # QUALITY mode (skip-gram device pipelines): negatives drawn per
        # (center, offset) PAIR and the 2W window offsets applied as
        # sequential sub-steps — the reference's pair-by-pair update
        # structure. ~8x slower than the banded fast path; reaches the
        # sequential C++ baseline's topic separation at equal epochs.
        self.per_pair = per_pair


def build_alias(probs: np.ndarray):
    """Vose's alias method: O(V) build, O(1) vectorized sampling.
    Returns (prob[V] float32, alias[V] int32): draw ``i`` uniformly, then
    take ``i`` with probability ``prob[i]`` else ``alias[i]``."""
    probs = np.asarray(probs, np.float64)
    n = probs.size
    scaled = probs * (n / probs.sum())
    below = scaled < 1.0
    # The pairing sweep is a sequential Python O(n) loop, run on lists
    # of Python floats (the same IEEE doubles, so the same tables as on
    # the arrays: tests/test_alias_build.py) because indexing a list
    # costs a fraction of indexing an array: 8 s at the reference's
    # 21M-word vocabulary where the arrays took 18, once a model —
    # accepted, since it buys O(1) in-jit sampling every batch (the
    # device searchsorted it replaces cost ~26 ms per 160K draws, i.e.
    # seconds per epoch).
    small = np.flatnonzero(below)[::-1].tolist()
    large = np.flatnonzero(~below)[::-1].tolist()
    scaled = scaled.tolist()
    prob = [1.0] * n
    alias = list(range(n))
    while small and large:
        s, g = small.pop(), large.pop()
        left = scaled[s]
        prob[s] = left
        alias[s] = g
        left = scaled[g] + left - 1.0
        scaled[g] = left
        (small if left < 1.0 else large).append(g)
    return (np.array(prob, np.float64).astype(np.float32),
            np.array(alias, np.int32))


def _alias_draw_np(prob: np.ndarray, alias: np.ndarray,
                   rng: np.random.Generator, shape) -> np.ndarray:
    idx = rng.integers(0, prob.size, size=shape).astype(np.int32)
    keep = rng.random(size=shape) < prob[idx]
    return np.where(keep, idx, alias[idx])


def _unique_rows_and_remap(ids_list, num_rows: int):
    """Sorted unique ids over ``ids_list`` plus a remap array such that
    ``remap[id] = compact slot``. Bitmap-based — O(num_rows + K), ~4x
    faster than sort-based ``np.unique`` + ``searchsorted`` at word2vec
    batch shapes — falling back to the sort path when the table is huge
    relative to the batch (the O(num_rows) sweep would dominate)."""
    total = sum(a.size for a in ids_list)
    if num_rows > max(1 << 22, 32 * total):
        rows = np.unique(np.concatenate(
            [a.reshape(-1) for a in ids_list])).astype(np.int32)
        return rows, None
    mark = np.zeros(num_rows, bool)
    for a in ids_list:
        mark[a.reshape(-1)] = True
    rows = np.flatnonzero(mark).astype(np.int32)
    # Absent ids map to slot 0 (zeros, not empty): CBOW/HS paths look up
    # pad id 0 even when word 0 is not in the batch — the result is
    # masked downstream, but it must still be deterministic memory.
    remap = np.zeros(num_rows, np.int32)
    remap[rows] = np.arange(rows.size, dtype=np.int32)
    return rows, remap


def _slot_map(rows: np.ndarray, remap, ids: np.ndarray) -> np.ndarray:
    """Compact slot of every id: remap gather when available, else
    binary search over the sorted unique rows."""
    if remap is not None:
        return remap[ids]
    return np.searchsorted(rows, ids).astype(np.int32)


def _pad_rows(rows: np.ndarray, minimum: int = 8) -> np.ndarray:
    """Pad a sorted unique row-id set to the next power of two (bounded
    set of jit trace shapes) by repeating the last id. Padded slots are
    never referenced by the compact index maps, so they receive zero
    gradient; local scatter-adds of zero are no-ops and PS delta pushes
    slice them off."""
    n = max(int(rows.size), 1)
    target = max(minimum, 1 << (n - 1).bit_length())
    if rows.size == 0:
        return np.zeros(target, np.int32)
    if rows.size == target:
        return rows
    return np.concatenate(
        [rows, np.full(target - rows.size, rows[-1], np.int32)])


class CompactBatch:
    """Host-prepared batch: unique touched rows + compact index maps.

    ``rows_in``/``rows_out`` are the real (unpadded) sorted unique row
    sets; ``rows_in_p``/``rows_out_p`` the power-of-two padded versions
    the device step uses; ``in_args``/``out_args`` index into the padded
    compact arrays."""

    __slots__ = ("rows_in", "rows_out", "rows_in_p", "rows_out_p",
                 "in_args", "out_args", "count", "words", "size")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class Word2Vec:
    """Local (single-process) trainer; device-resident embeddings,
    compact-row update steps."""

    def __init__(self, config: Word2VecConfig, dictionary: Dictionary):
        self.config = config
        self.dictionary = dictionary
        self._dim = config.embedding_size
        self._out_rows = self._init_output_structures()
        self._rng = np.random.default_rng(config.seed + 13)
        self.trained_words = 0
        self.total_words = dictionary.total_count * config.epochs
        self._multi_step = None  # built on first grouped dispatch
        # Row-set pad minimums (see _pad_rows): the local path lets them
        # float per batch; the PS path freezes them to one bucket per
        # table so exactly ONE jit trace per gather/step/scatter exists.
        self._pad_in_min = 8
        self._pad_out_min = 8
        self._init_embeddings()

    def _init_output_structures(self) -> int:
        """Huffman tables (hs) or the unigram^0.75 CDF (sgns); returns
        the output-embedding row count. All host-side: row-set
        preparation must know the touched output rows before the device
        step runs."""
        config, dictionary = self.config, self.dictionary
        if config.hs:
            tree = build_huffman(dictionary.counts)
            self._codes_host = np.asarray(tree.codes)
            self._points_host = np.asarray(tree.points)
            return max(tree.num_inner_nodes, 1)
        # Alias-method tables (Vose) over the unigram^0.75 distribution:
        # a draw is (randint, uniform, two table lookups) — O(1) and fully
        # vectorized. The inverse-CDF searchsorted it replaces costs
        # ~26 ms per 160K draws inside the jitted step on TPU (binary
        # search lowers badly); alias sampling is ~0.1 ms.
        with monitor("DICT_ALIAS_BUILD"):
            self._neg_prob_host, self._neg_alias_host = build_alias(
                dictionary.negative_table())
        return dictionary.size

    def _init_embeddings(self) -> None:
        """Local mode: full device-resident matrices. ref init: uniform
        (-0.5/dim, 0.5/dim) input, zeros output. Initialized ON device
        (jax.random) — a host-side init means uploading the whole V x D
        table, ~0.5 GB at reference scale, over a possibly-slow
        host->device link. The PS subclass overrides this with table
        creation (no full local copies)."""
        vocab, dim = self.dictionary.size, self.config.embedding_size
        init_key = jax.random.PRNGKey(self.config.seed ^ 0x5EED)
        self._emb_in = (jax.random.uniform(init_key, (vocab, dim),
                                           jnp.float32) - 0.5) / dim
        self._emb_out = jnp.zeros((self._out_rows, dim), jnp.float32)
        if self.config.hs:
            self._codes_dev = jnp.asarray(self._codes_host)
            self._points_dev = jnp.asarray(self._points_host)
        else:
            self._neg_prob_dev = jnp.asarray(self._neg_prob_host)
            self._neg_alias_dev = jnp.asarray(self._neg_alias_host)
        # Per-batch PRNG keys derive as fold_in(base, batch_counter):
        # the counter advances once per REAL batch, so the grouped scan
        # (whose padded tail slots are masked no-ops) and the sequential
        # path consume identical key streams — bit-identical training.
        self._key = jax.random.PRNGKey(self.config.seed)
        self._batch_counter = 0
        self._step = self._build_step()

    # -- learning rate schedule --
    def learning_rate(self) -> float:
        remain = max(1.0 - self.trained_words / max(self.total_words, 1),
                     1e-4)
        return self.config.init_learning_rate * remain

    # -- host preparation: batch -> compact row sets + index maps --
    def prepare(self, batch) -> CompactBatch:
        """Compute the rows this batch touches and remap its indices to
        compact slots (the reference's per-block row collection,
        ref: communicator.cpp:117-155). Pure numpy — run it in the
        loader thread to overlap with device steps."""
        config = self.config
        vocab = self.dictionary.size
        if isinstance(batch, CbowBatch):
            win, targets = batch.window, batch.centers
            real = win[win >= 0]
            if real.size:
                rows_in, remap = _unique_rows_and_remap([real], vocab)
            else:
                rows_in, remap = np.zeros(1, np.int32), None
            win_l = np.clip(_slot_map(rows_in, remap, np.maximum(win, 0)),
                            0, rows_in.size - 1).astype(np.int32)
            in_args = (win_l, (win >= 0).astype(np.float32))
            size = batch.centers.shape[0]
        else:
            centers, targets = batch.centers, batch.contexts
            rows_in, remap = _unique_rows_and_remap([centers], vocab)
            in_args = (_slot_map(rows_in, remap, centers),)
            size = centers.shape[0]

        if config.hs:
            points = self._points_host[targets]  # [B, L], -1 padded
            real = points[points >= 0]
            if real.size:
                rows_out, remap = _unique_rows_and_remap(
                    [real], self._out_rows)
            else:
                rows_out, remap = np.zeros(1, np.int32), None
            points_l = np.clip(
                _slot_map(rows_out, remap, np.maximum(points, 0)),
                0, rows_out.size - 1).astype(np.int32)
            out_args = (points_l, self._codes_host[targets])
        else:
            k = config.negative
            # neg_block pairs share one K-draw (expected gradient
            # unchanged): divides the negative row volume — which
            # dominates the block's row set and therefore the id/delta
            # bytes every pull/push ships — by the block factor. The
            # wire bytes are what bind the host-batch path.
            nb = max(int(getattr(config, "neg_block", 1)), 1)
            # The shipped batch iterators emit FIXED-size batches (tail
            # padded, count < size), so nb divides in practice; an odd
            # caller-supplied size falls back to the nearest divisor so
            # the unique-row count stays within the frozen _pad_out_min
            # bucket (nb=1 could overflow it and compile a new shape).
            while targets.size % nb:
                nb //= 2
            neg = _alias_draw_np(self._neg_prob_host,
                                 self._neg_alias_host, self._rng,
                                 (targets.size // nb, k)).astype(np.int32)
            rows_out, remap = _unique_rows_and_remap([targets, neg], vocab)
            out_args = (_slot_map(rows_out, remap, targets),
                        _slot_map(rows_out, remap, neg))

        rows_in_p = _pad_rows(rows_in, self._pad_in_min)
        rows_out_p = _pad_rows(rows_out, self._pad_out_min)
        # Slot maps index the padded pulled buffers; when a buffer has
        # <= 65536 slots they fit uint16 — halves the per-batch id
        # upload (the frozen buckets keep the dtype stable per config,
        # so the jit signature does not churn).
        if rows_in_p.size <= 65536 and not config.cbow:
            in_args = tuple(a.astype(np.uint16) for a in in_args)
        if rows_out_p.size <= 65536 and not config.hs:
            out_args = tuple(a.astype(np.uint16) for a in out_args)
        return CompactBatch(
            rows_in=rows_in, rows_out=rows_out,
            rows_in_p=rows_in_p, rows_out_p=rows_out_p,
            in_args=in_args, out_args=out_args,
            count=batch.count, words=batch.words, size=size)

    # -- the shared compact loss --
    def _compact_loss(self):
        config = self.config

        def input_vec(ein, in_args):
            if config.cbow:
                win_l, win_mask = in_args
                vecs = ein[win_l] * win_mask[..., None]
                denom = jnp.maximum(win_mask.sum(axis=1, keepdims=True),
                                    1.0)
                return vecs.sum(axis=1) / denom
            (centers_l,) = in_args
            return ein[centers_l]

        if config.hs:
            def loss_fn(ein, eout, in_args, out_args, pair_mask):
                """Hierarchical softmax over the target's Huffman path;
                code 0 = positive class — the word2vec convention
                (ref: wordembedding.cpp HS branch)."""
                v = input_vec(ein, in_args)
                points_l, codes = out_args
                mask = (codes >= 0).astype(jnp.float32) * pair_mask[:, None]
                u = eout[points_l]  # [B, L, D]
                logits = jnp.clip(jnp.einsum("bd,bld->bl", v, u),
                                  -_MAX_EXP, _MAX_EXP)
                labels = 1.0 - codes.astype(jnp.float32)
                return jnp.sum(_sigmoid_xent(logits, labels * mask) * mask)
        else:
            def loss_fn(ein, eout, in_args, out_args, pair_mask):
                """SGNS. The MAX_EXP clamp is word2vec's sigmoid table:
                saturated pairs get ZERO gradient. SUM over the batch:
                word2vec applies the learning rate per pair; a mean
                would shrink the per-pair step by the batch size.
                ``negs_l`` is [B // neg_block, K]: each block of
                consecutive pairs shares one K-draw."""
                v = input_vec(ein, in_args)
                targets_l, negs_l = out_args
                pos = jnp.clip(jnp.sum(v * eout[targets_l], axis=-1),
                               -_MAX_EXP, _MAX_EXP)
                u_neg = eout[negs_l]                   # [B//nb, K, D]
                vb = v.reshape(u_neg.shape[0], -1, v.shape[-1])
                neg = jnp.clip(jnp.einsum("nbd,nkd->nbk", vb, u_neg),
                               -_MAX_EXP, _MAX_EXP)
                mb = pair_mask.reshape(u_neg.shape[0], -1)
                return (jnp.sum(_sigmoid_xent(pos, 1.0) * pair_mask)
                        + jnp.sum(_sigmoid_xent(neg, 0.0)
                                  * mb[:, :, None]))

        return loss_fn

    # -- the fused local train step: gather -> train -> scatter-add.
    #
    # The batch only ships its (center, context) ids (negatives sample
    # in-jit); gradients are taken w.r.t. the GATHERED rows and
    # scatter-added back at the global ids — duplicate ids sum, which is
    # exactly the dense-gradient semantics — so HBM traffic per step is
    # O(batch), not O(vocab). (Differentiating through the full V x D
    # tables rewrites both tables every step: ~GBs of traffic per batch
    # at 1M+ vocab, which is what capped round-1 scaling.)
    def _make_step_core(self):
        """The per-batch update: gather -> grad -> scatter-add, taking an
        already-split PRNG key. Shared by the single-step jit and the
        grouped ``lax.scan`` multi-step."""
        config = self.config
        k = config.negative

        def core(emb_in, emb_out, lr, key, pair_mask, in_ids, targets):
            if config.hs:
                points = self._points_dev[targets]  # [B, L]
                codes = self._codes_dev[targets]
                out_ids = jnp.maximum(points, 0)
                out_mask = (codes >= 0).astype(jnp.float32) \
                    * pair_mask[:, None]
                labels = (1.0 - codes.astype(jnp.float32)) * out_mask
            else:
                batch = targets.shape[0]
                k_idx, k_keep = jax.random.split(key)
                idx = jax.random.randint(
                    k_idx, (batch, k), 0, self._neg_prob_dev.shape[0])
                keep = jax.random.uniform(k_keep, (batch, k)) \
                    < self._neg_prob_dev[idx]
                negs = jnp.where(keep, idx, self._neg_alias_dev[idx])
                out_ids = jnp.concatenate([targets[:, None], negs], axis=1)
                out_mask = pair_mask[:, None] * jnp.ones((1, 1 + k))
                labels = jnp.concatenate(
                    [jnp.ones((batch, 1)), jnp.zeros((batch, k))], axis=1)

            if config.cbow:
                window = in_ids
                in_mask = (window >= 0).astype(jnp.float32)
                in_gather = jnp.maximum(window, 0)
                vecs = emb_in[in_gather]  # [B, 2W, D]
            else:
                in_gather = in_ids
                vecs = emb_in[in_ids]  # [B, D]
            u = emb_out[out_ids]  # [B, S, D]

            def loss_fn(vecs, u):
                if config.cbow:
                    masked = vecs * in_mask[..., None]
                    denom = jnp.maximum(
                        in_mask.sum(axis=1, keepdims=True), 1.0)
                    v = masked.sum(axis=1) / denom
                else:
                    v = vecs
                logits = jnp.clip(jnp.einsum("bd,bsd->bs", v, u),
                                  -_MAX_EXP, _MAX_EXP)
                if config.hs:
                    losses = _sigmoid_xent(logits, labels) * out_mask
                else:
                    losses = _sigmoid_xent(logits, labels) \
                        * pair_mask[:, None]
                return jnp.sum(losses)

            loss, (g_vecs, g_u) = jax.value_and_grad(
                loss_fn, argnums=(0, 1))(vecs, u)
            new_in = emb_in.at[in_gather].add(-lr * g_vecs)
            new_out = emb_out.at[out_ids].add(-lr * g_u)
            return new_in, new_out, loss

        return core

    def _build_step(self):
        core = self._make_step_core()

        def step(emb_in, emb_out, lr, base_key, counter, pair_mask,
                 in_ids, targets):
            # The per-batch key folds in-jit (a host-side fold would be
            # one more device call per batch, and each call pays the
            # transport's dispatch latency).
            key = jax.random.fold_in(base_key, counter)
            return core(emb_in, emb_out, lr, key, pair_mask, in_ids,
                        targets)

        return jax.jit(step, donate_argnums=(0, 1))

    def _build_multi_step(self):
        """K batches per dispatch: ``lax.scan`` of the step core over
        stacked batch tensors. One host->device dispatch then drives K
        sequential SGD steps entirely in HBM — each slot's key folds
        from the SAME per-batch counter the sequential path uses (and
        masked padding slots carry counter -1, consuming nothing), so
        grouped and ungrouped training are bit-identical; only the
        dispatch count changes. This is what amortizes the per-dispatch
        launch cost (not measured on the current machine)."""
        core = self._make_step_core()

        def multi(emb_in, emb_out, base_key, lrs, counts, counters,
                  in_ids, targets):
            def body(carry, xs):
                emb_in, emb_out = carry
                lr, count, counter, ii, tt = xs
                key = jax.random.fold_in(base_key, counter)
                # Mask from the scalar count (shipping [K, B] float masks
                # would triple the per-group host->device transfer).
                pm = (jnp.arange(tt.shape[0]) < count).astype(jnp.float32)
                emb_in, emb_out, loss = core(emb_in, emb_out, lr, key,
                                             pm, ii, tt)
                return (emb_in, emb_out), loss

            (emb_in, emb_out), losses = jax.lax.scan(
                body, (emb_in, emb_out),
                (lrs, counts, counters, in_ids, targets))
            return emb_in, emb_out, losses.sum()

        return jax.jit(multi, donate_argnums=(0, 1))

    def _pair_mask_for(self, count: int, size: int):
        if count == size:
            return _full_mask(size)
        return jnp.asarray((np.arange(size) < count).astype(np.float32))

    # -- public API --
    def train_batch_async(self, batch):
        """Dispatch one training step WITHOUT synchronizing; returns the
        device scalar loss. The hot loop must not materialize per-batch
        scalars — a host fetch per step serializes the host on the
        device and caps words/sec."""
        if isinstance(batch, CbowBatch):
            in_ids, targets = batch.window, batch.centers
        else:
            in_ids, targets = batch.centers, batch.contexts
        size = batch.centers.shape[0]
        counter = self._batch_counter
        self._batch_counter += 1
        self._emb_in, self._emb_out, loss = self._step(
            self._emb_in, self._emb_out,
            jnp.float32(self.learning_rate()), self._key,
            np.int32(counter),
            self._pair_mask_for(batch.count, size),
            jnp.asarray(in_ids), jnp.asarray(targets))
        self.trained_words += batch.words
        return loss

    def train_batch(self, batch) -> float:
        loss = self.train_batch_async(batch)
        return float(loss) / max(batch.count, 1)  # display per-pair loss

    def _train_group(self, batches) -> object:
        """Stack up to ``batch_group`` prepared batches and dispatch ONE
        scanned device step over them. Short groups (the stream tail) pad
        with count=0 slots — masked to zero loss and zero gradient — so
        exactly one trace shape exists. Returns the group's device-scalar
        loss sum."""
        group = max(int(self.config.batch_group), 1)
        first = batches[0]
        cbow = isinstance(first, CbowBatch)
        in_shape = first.window.shape if cbow else first.centers.shape
        bsz = first.centers.shape[0]
        in_ids = np.zeros((group,) + in_shape, np.int32)
        targets = np.zeros((group, bsz), np.int32)
        counts = np.zeros(group, np.int32)
        counters = np.full(group, -1, np.int32)  # -1 = padded no-op slot
        lrs = np.zeros(group, np.float32)
        for i, b in enumerate(batches):
            if cbow:
                in_ids[i], targets[i] = b.window, b.centers
            else:
                in_ids[i], targets[i] = b.centers, b.contexts
            counts[i] = b.count
            counters[i] = self._batch_counter
            self._batch_counter += 1
            # Per-batch lr follows the word schedule exactly as the
            # sequential path would have computed it.
            lrs[i] = self.learning_rate()
            self.trained_words += b.words
        if self._multi_step is None:
            self._multi_step = self._build_multi_step()
        self._emb_in, self._emb_out, loss = self._multi_step(
            self._emb_in, self._emb_out, self._key,
            jnp.asarray(lrs), jnp.asarray(counts), jnp.asarray(counters),
            jnp.asarray(in_ids), jnp.asarray(targets))
        return loss

    def train_batches(self, iterator) -> Tuple[float, int]:
        """Drive a whole batch stream; returns (loss_sum, pair_count).

        Batches dispatch in groups of ``batch_group`` through the scanned
        multi-step — one host->device call per group (the reference's
        block granularity, ref: distributed_wordembedding.cpp:147-236,
        where a data block also carries many sentences per
        request/train/push cycle). Device losses accumulate into ONE
        device scalar (a lazy ``+`` per group) and materialize once at
        the end. Any per-batch host read of a device scalar is a full
        device round-trip, and so is each element of a deferred
        ``jnp.stack``; the running add keeps
        exactly one buffer and one final transfer."""
        group = max(int(self.config.batch_group), 1)
        acc = None
        pairs = 0
        if group == 1:
            for batch in iterator:
                loss = self.train_batch_async(batch)
                acc = loss if acc is None else acc + loss
                pairs += batch.count
            return 0.0 if acc is None else float(acc), pairs
        buf = []
        for batch in iterator:
            buf.append(batch)
            if len(buf) == group:
                loss = self._train_group(buf)
                acc = loss if acc is None else acc + loss
                pairs += sum(b.count for b in buf)
                buf = []
        if buf:
            loss = self._train_group(buf)
            acc = loss if acc is None else acc + loss
            pairs += sum(b.count for b in buf)
        return 0.0 if acc is None else float(acc), pairs

    def prepared(self, batches):
        """Adapter for the loader thread. Local mode needs no host
        preparation (negatives sample in-jit) — identity; the PS
        subclass overrides with CompactBatch preparation."""
        return batches

    @property
    def embeddings(self) -> np.ndarray:
        return np.asarray(self._emb_in)

    def save_embeddings(self, path: str) -> None:
        """word2vec text format (ref rank-0 save,
        distributed_wordembedding.cpp:231-236)."""
        from ...io import StreamFactory
        emb = self.embeddings
        with StreamFactory.get_stream(path, "w") as stream:
            stream.write(f"{emb.shape[0]} {emb.shape[1]}\n".encode())
            for word, row in zip(self.dictionary.words, emb):
                vec = " ".join(f"{x:.6f}" for x in row)
                stream.write(f"{word} {vec}\n".encode())


@functools.lru_cache(maxsize=None)
def _full_mask(size: int):
    return jnp.ones((size,), jnp.float32)


def _sigmoid_xent(logits, labels):
    """Numerically stable sigmoid cross-entropy."""
    return jnp.maximum(logits, 0) - logits * labels \
        + jnp.log1p(jnp.exp(-jnp.abs(logits)))


class _Prep:
    """One batch's prepared pull: the CompactBatch plus the in-flight
    async Get requests and their destination buffers."""

    __slots__ = ("compact", "buf_in", "buf_out", "mid_in", "mid_out")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class _Launched:
    __slots__ = ("prep", "delta_in", "delta_out", "loss")

    def __init__(self, **kw):
        for k, v in kw.items():
            setattr(self, k, v)


class PSWord2Vec(Word2Vec):
    """Distributed trainer over row-sharded matrix tables.

    Redesigned around the reference's block protocol
    (ref: Applications/WordEmbedding/src/communicator.cpp:117-249,
    distributed_wordembedding.cpp:203-224):

    - each batch pulls ONLY the rows its CompactBatch names — never the
      full V x D tables;
    - the shared compact loss trains the pulled [R, D] row matrices;
    - row deltas ``(new - old) / num_workers`` push back asynchronously,
      acks drained before any barrier or full-table read;
    - ``train_batches`` pipelines: batch i+1's pull is serviced by the
      server actors while batch i's step runs on device;
    - word-count KV traffic for the lr schedule is async and amortized
      over ``_WC_SYNC`` batches (ref: communicator.cpp:251-259 runs it
      on a side thread)."""

    _WC_SYNC = 16  # batches between global word-count syncs

    def __init__(self, config: Word2VecConfig, dictionary: Dictionary,
                 num_workers: Optional[int] = None):
        self._num_workers_override = num_workers
        super().__init__(config, dictionary)
        if self._in_table is None:  # server-only rank: tables hosted
            return
        zoo = self._in_table.zoo
        self._rng = np.random.default_rng(
            config.seed + 97 * max(zoo.worker_id, 0))
        self._wc_pending = 0.0
        self._batches_done = 0
        self._pending_pushes: List = []
        # Pipelined Get prefetch (-max_get_staleness > 0, host path
        # only): while the device computes step i, step i+1's rows are
        # prefetched into the client cache, so _prepare's real pull
        # hits locally or joins the in-flight fetch instead of paying a
        # fresh wire roundtrip. The device path already keeps the whole
        # loop in HBM — nothing to hide there.
        self._use_prefetch = (client_cache.cache_enabled()
                              and not self._device_path)

    def _init_embeddings(self) -> None:
        """No full local matrices: the input table is random-initialized
        SERVER-side (the reference's random-init server ctor,
        ref: matrix_table.cpp:372-384), and there on the server's own
        devices, each shard drawing its rows (MatrixServer): no V x D
        array ever materializes on a worker or on any host — at
        reference scale (21M x D, 10.75 GB at D = 128) it could not."""
        config = self.config
        vocab, dim = self.dictionary.size, config.embedding_size
        bound = 0.5 / dim
        self._in_table = create_matrix_table(
            vocab, dim, updater_type="default",
            random_init=(-bound, bound), seed=config.seed)
        self._out_table = create_matrix_table(self._out_rows, dim,
                                              updater_type="default")
        self._wc_table = create_kv_table()
        if self._in_table is None:
            # Server-only rank (-ps_role=server): it hosts its table
            # shards and idles — the reference runs the same binary on
            # every rank and lets role decide (src/zoo.cpp:29-35). No
            # worker-side step/bucket state to build.
            from ...runtime.zoo import current_zoo
            self._device_path = current_zoo().servers_in_process
            self._num_workers = max(current_zoo().num_workers, 1)
            return
        zoo = self._in_table.zoo
        self._num_workers = max(
            zoo.num_workers if self._num_workers_override is None
            else self._num_workers_override, 1)
        # When every server shard lives in THIS process the whole
        # pull->step->push loop stays in HBM: device row gathers, device
        # delta scatters — no host round-trips (critical when the
        # host<->device link is slow relative to HBM). That covers both
        # the single-process cluster AND a co-located worker+server rank
        # in a multi-process -ps_role deployment; workers whose server
        # traffic crosses the wire take the host-buffer path.
        self._device_path = zoo.servers_in_process
        # FROZEN row buckets: each batch's unique row count is bounded
        # by what the batch can touch, so padding every request to that
        # one bound gives exactly one compiled gather/step/scatter shape
        # per table — warming 2 batches covers the whole compile set.
        # (A floating power-of-two ladder compiles a program PER
        # distinct size combination, serially, on first touch — the
        # round-2 "warmup tax" that cost ~300s per run.)
        from ...updater.engine import bucket_size
        batch = config.batch_size
        in_cap = batch * (2 * config.window if config.cbow else 1)
        if config.hs:
            out_cap = batch * int(self._points_host.shape[1])
        else:
            nb = max(int(getattr(config, "neg_block", 1)), 1)
            out_cap = batch + batch * config.negative // nb
        self._pad_in_min = bucket_size(min(in_cap, vocab))
        self._pad_out_min = bucket_size(min(out_cap, self._out_rows))
        self._step = self._build_ps_step()

    def _build_ps_step(self):
        loss_fn = self._compact_loss()

        def step(ein, eout, lr_scaled, in_args, out_args, pair_mask):
            """One fused jitted step returning the PUSH deltas directly:
            ``-lr * grad / num_workers`` (the reference's
            ``(new - old) / num_workers`` with one local step,
            ref: communicator.cpp:157-249) plus the batch loss."""
            loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
                ein, eout, in_args, out_args, pair_mask)
            return -lr_scaled * grads[0], -lr_scaled * grads[1], loss

        return jax.jit(step)

    # -- phase 1: row-set preparation + async pull --
    def _prepare(self, batch) -> _Prep:
        compact = batch if isinstance(batch, CompactBatch) \
            else self.prepare(batch)
        if self._device_path:
            # Device pull of the PADDED row sets (gather duplicates are
            # free; the result is already step-shaped in HBM).
            return _Prep(
                compact=compact, buf_in=None, buf_out=None,
                mid_in=self._in_table.get_rows_device_async(
                    compact.rows_in_p),
                mid_out=self._out_table.get_rows_device_async(
                    compact.rows_out_p))
        # Host path: pull only the REAL unique rows into the head of the
        # padded buffer (the padded tail is never referenced by the
        # compact index maps and its deltas are sliced off before the
        # push, so it only needs to be NaN-free). Requesting the padded
        # vector instead would ship thousands of duplicates of the last
        # row over the wire in both directions.
        n_in, n_out = compact.rows_in.size, compact.rows_out.size
        buf_in = np.empty((compact.rows_in_p.size, self._dim), np.float32)
        buf_out = np.empty((compact.rows_out_p.size, self._dim),
                           np.float32)
        buf_in[n_in:] = 0.0
        buf_out[n_out:] = 0.0
        return _Prep(
            compact=compact, buf_in=buf_in, buf_out=buf_out,
            mid_in=self._in_table.get_rows_async(compact.rows_in,
                                                 out=buf_in[:n_in]),
            mid_out=self._out_table.get_rows_async(compact.rows_out,
                                                   out=buf_out[:n_out]))

    # -- phase 2: wait the pull, dispatch the device step (async) --
    def _launch(self, prep: _Prep) -> _Launched:
        compact = prep.compact
        with monitor("PS_GET_STALL"):
            # The trainer's pull-stall: wire latency NOT hidden by the
            # pipeline (cache hits and completed prefetches make this
            # ~zero).
            self._in_table.wait(prep.mid_in)
            self._out_table.wait(prep.mid_out)
        if self._device_path:
            old_in = self._in_table.take_device_rows()
            old_out = self._out_table.take_device_rows()
        else:
            old_in = jnp.asarray(prep.buf_in)
            old_out = jnp.asarray(prep.buf_out)
        lr_scaled = jnp.float32(self.learning_rate() / self._num_workers)
        delta_in, delta_out, loss = self._step(
            old_in, old_out, lr_scaled,
            tuple(jnp.asarray(a) for a in compact.in_args),
            tuple(jnp.asarray(a) for a in compact.out_args),
            self._pair_mask_for(compact.count, compact.size))
        return _Launched(prep=prep, delta_in=delta_in,
                         delta_out=delta_out, loss=loss)

    # -- phase 3: push deltas, account words --
    def _finish(self, launched: _Launched):
        """Push this batch's deltas (device arrays stay in HBM on the
        device path) and return the batch loss as a DEVICE scalar — the
        hot loop must not synchronize on it."""
        compact = launched.prep.compact
        if self._device_path:
            # Padded device push: padded slots carry exactly-zero deltas
            # (their rows got no gradient), so the duplicate trailing ids
            # scatter-add zeros — a no-op.
            push_in, rows_in = launched.delta_in, compact.rows_in_p
            push_out, rows_out = launched.delta_out, compact.rows_out_p
        else:
            push_in = np.asarray(launched.delta_in)[:compact.rows_in.size]
            rows_in = compact.rows_in
            push_out = np.asarray(
                launched.delta_out)[:compact.rows_out.size]
            rows_out = compact.rows_out
        self._pending_pushes.append(
            (self._in_table,
             self._in_table.add_rows_async(rows_in, push_in)))
        self._pending_pushes.append(
            (self._out_table,
             self._out_table.add_rows_async(rows_out, push_out)))
        self._account_words(compact.words)
        return launched.loss

    def _drain_pushes(self) -> None:
        """Wait every outstanding Add ack: a barrier alone orders only
        controller traffic, not worker->server adds still in TCP flight —
        peers reading after the barrier would nondeterministically miss
        them."""
        for table, msg_id in self._pending_pushes:
            table.wait(msg_id)
        self._pending_pushes.clear()

    def _flush_word_count(self) -> None:
        if self._wc_pending:
            self._wc_table.add_async([0], [self._wc_pending])
            self._wc_pending = 0.0

    def _account_words(self, words: float) -> None:
        """Global word count for the lr schedule via the KV table, synced
        every _WC_SYNC batches (the reference keeps it off the hot path on
        a side thread, ref: distributed_wordembedding.cpp:92-134)."""
        self.trained_words += words
        self._wc_pending += words
        self._batches_done += 1
        if self._batches_done % self._WC_SYNC == 0:
            self._flush_word_count()
            global_words = self._wc_table.get([0])[0]
            # Take the max: the global clock includes our own pushes and
            # every peer's; between syncs we advance locally.
            self.trained_words = max(self.trained_words, int(global_words))

    # -- public API --
    def prepared(self, batches):
        """Generator adapter: raw batches -> CompactBatch (run inside a
        BlockLoader so host row preparation overlaps device steps)."""
        for batch in batches:
            yield self.prepare(batch)

    def train_batch(self, batch) -> float:
        launched = self._launch(self._prepare(batch))
        loss = self._finish(launched)
        self._drain_pushes()
        return float(loss) / max(launched.prep.compact.count, 1)

    def train_batch_async(self, batch):
        return jnp.float32(self.train_batch(batch))

    def _prefetched(self, batches):
        """Double-buffer adapter: prepare batch i+1 and PREFETCH its row
        sets into the client cache before yielding batch i, so the real
        pull in ``_prepare`` overlaps the device step instead of
        serializing behind it (the async twin of the reference's
        pipelined block protocol, distributed_wordembedding.cpp:203-224
        — there via double server-side consumer slots, here via the
        versioned worker cache)."""
        held = None
        for batch in batches:
            compact = batch if isinstance(batch, CompactBatch) \
                else self.prepare(batch)
            self._in_table.prefetch_rows_async(compact.rows_in)
            self._out_table.prefetch_rows_async(compact.rows_out)
            if held is not None:
                yield held
            held = compact
        if held is not None:
            yield held

    def train_batches(self, iterator) -> Tuple[float, int]:
        """Pipelined loop: batch i+1's row pull is serviced by the server
        actors while batch i's step runs on device and its deltas push
        (ref overlap: distributed_wordembedding.cpp:203-224). Losses
        accumulate as device scalars — one host materialization at the
        end, no per-batch syncs. With the client cache enabled the loop
        additionally prefetches batch i+1's rows during batch i's step
        (see ``_prefetched``)."""
        acc = None
        pairs = 0
        launched: Optional[_Launched] = None
        if self._use_prefetch:
            iterator = self._prefetched(iterator)
        for batch in iterator:
            prep = self._prepare(batch)  # async pull in flight
            if launched is not None:
                loss = self._finish(launched)
                acc = loss if acc is None else acc + loss
                pairs += launched.prep.compact.count
            launched = self._launch(prep)
        if launched is not None:
            loss = self._finish(launched)
            acc = loss if acc is None else acc + loss
            pairs += launched.prep.compact.count
        # Every push acked, trailing word count published, then the
        # barrier: a peer's post-barrier read sees all of our updates.
        self._drain_pushes()
        self._flush_word_count()
        self._in_table.zoo.barrier()
        return 0.0 if acc is None else float(acc), pairs

    @property
    def embeddings(self) -> np.ndarray:
        self._drain_pushes()
        return self._in_table.get()

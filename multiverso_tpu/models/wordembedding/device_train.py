"""Device-resident corpus training: the word2vec data pipeline in HBM.

The round-2 hot loop shipped every batch's (center, context) ids from the
host; that transfer (plus one dispatch per batch) bounds words/sec long
before the chip works. This module is the
TPU-native fix: the TOKENIZED CORPUS is uploaded once (~4 bytes/token)
and everything the reference's reader/trainer pipeline does per pass —
subsampling, sentence-bounded dynamic windows, negative sampling, the
SGNS update — happens inside jitted device programs
(ref: Applications/WordEmbedding/src/reader.cpp — subsample-as-you-read;
wordembedding.cpp — per-center shrunk window + SGNS FeedForward/
BPOutputLayer). The host's only per-epoch work is the learning-rate
schedule (a handful of scalars per dispatch group) and one scalar fetch
of the post-subsampling length.

Per epoch, one jitted ``_prep`` pass draws the subsample mask and
stably compacts kept tokens to the front (word2vec subsamples BEFORE
windowing, so windows must span the kept sequence): one sort on a
unique key (the position, dropped tokens after all kept ones) whose
operands are the tokens and their sentence ids, so the compacted
streams come out of the sort itself and nothing is gathered by a sorted
order afterwards; the one gather left is the mask's ``keep[flat]``.
Training then scans ``steps_per_dispatch`` windowed steps per dispatch.

The SGNS/CBOW steps use a BANDED formulation that exploits window
overlap: the contexts of C consecutive centers all lie in the
contiguous band ``kept[base-W : base+C+W]``, so the step gathers those
C+2W rows ONCE and forms the 2W context logits as shifted slices of the
band — 2W-fold less gather AND scatter row traffic than materializing
the [C, 2W] context row matrix, which round-3 profiling showed was the
step's dominant cost (scatter of C*(2W+K) ≈ 0.5M random rows per step).
The per-center shrunk window and sentence bounds survive as masks on
the shifted slices; the update math is bit-identical to the row-matrix
form (duplicates in the band sum, exactly as duplicate scatter ids
did). Negatives come from the unigram^0.75 alias tables, drawn per
center by default; ``neg_block`` > 1 shares one draw of K negatives
across each block of that many consecutive centers (expected gradient
unchanged — every center still sees K ^0.75-unigram negatives — but the
random-row traffic for negatives drops by the block factor; measured
~1.8x words/s at block 32 on v5e).

Measured v5e cost model (see PROGRESS notes, round 4): full-table
sweeps run near peak (~680 GB/s), row gathers ~50-100 GB/s, random-row
scatter-adds are the slowest path (~13 GB/s at 32K rows) — so the
design minimizes SCATTERED ROWS first, gathered rows second, and
never sweeps. Every row update of every group program goes through
``updater.rules.scatter_add``, the function the tables' rows programs
end in: on a TPU, from 2048 ids, sorted runs and one visit a distinct
row (updater/row_scatter.py; PERF.md section 6, PRs 28 and 30).
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...runtime import device_lock, thread_roles
from ...sharding import mesh as meshlib
from ...updater.rules import fast_rows, scatter_add
from ...util.dashboard import count, monitor
from .data import TokenizedCorpus
from .model import _MAX_EXP, _sigmoid_xent


# -- per-epoch subsample + stable compaction (shape-polymorphic jit) --
@jax.jit
def _prep(flat, sent, keep, key):
    # The scopes name the program's two steps in a device trace
    # (benchmark/lib/xplane.py reduce, which tools/trace_spans.py
    # prints, sums device time by them).
    with jax.named_scope("mv.prep.mask"):
        mask = jax.random.uniform(key, flat.shape) < keep[flat]
    # Kept tokens keep corpus order, so positional distance in the
    # compacted array IS the word2vec window distance over the
    # subsampled sentence. ONE sort compacts, and it carries the tokens
    # and their sentence ids as operands: applying a sorted order
    # afterwards is a random 4-byte read per element per array (24 ns
    # each on a v5e, 4.1 s of the epoch at 72M tokens against 0.37 s
    # for this sort). The key is the position with the top bit set on
    # dropped tokens: unique, so the order is the stable one without
    # the iota tie-breaker a stable sort would add as a fourth operand
    # (a position fits 31 bits: arrays are indexed by int32).
    with jax.named_scope("mv.prep.sort"):
        pos = jax.lax.iota(jnp.uint32, flat.shape[0])
        slot = jnp.where(mask, pos, pos | jnp.uint32(1 << 31))
        # Dropped tail gets sentence -1: it can never match a real
        # sentence id, so windows cannot cross into it.
        _, kept, ksent = jax.lax.sort(
            (slot, flat, jnp.where(mask, sent, -1)), num_keys=1,
            is_stable=False)
        return kept, ksent, mask.sum(dtype=jnp.int32)


def _pad_stream(C, W, kept, ksent):
    """Pad the compacted stream so banded slices never clamp: W on the
    left, C+W on the right (a clamped ``dynamic_slice`` would shift the
    whole band and misalign valid centers on the epoch's tail step).
    Padding carries sentence -2, which never matches a real sentence,
    so every padded position is masked out."""
    return (jnp.pad(kept, (W, C + W)),
            jnp.pad(ksent, (W, C + W), constant_values=-2))


def _band_former(C, W, n_kept, kept_pad, ksent_pad, k_shrink, base):
    """The banded window former: C consecutive kept positions as
    centers; their contexts all lie in the contiguous band
    ``kept[base-W : base+C+W]`` (C+2W tokens), and the per-(center,
    offset) validity — in-stream, same sentence, within the per-center
    shrunk window (the word2vec trick, ref: wordembedding.cpp Train
    window sampling) — is a mask over shifted slices of the band.
    Returns (centers[C], band[C+2W], pmask[C,2W])."""
    offs = [o for o in range(-W, W + 1) if o != 0]
    idx = base + jnp.arange(C, dtype=jnp.int32)
    centers = jax.lax.dynamic_slice_in_dim(kept_pad, base + W, C)
    csent = jax.lax.dynamic_slice_in_dim(ksent_pad, base + W, C)
    center_ok = (idx < n_kept) & (csent >= 0)
    shrink = jax.random.randint(k_shrink, (C,), 1, W + 1)
    band = jax.lax.dynamic_slice_in_dim(kept_pad, base, C + 2 * W)
    band_sent = jax.lax.dynamic_slice_in_dim(ksent_pad, base, C + 2 * W)
    masks = []
    for off in offs:
        p = idx + off
        inb = (p >= 0) & (p < n_kept)
        s = jax.lax.dynamic_slice_in_dim(band_sent, W + off, C)
        masks.append(inb & (s == csent) & (abs(off) <= shrink)
                     & center_ok)
    pmask = jnp.stack(masks, axis=1).astype(jnp.float32)
    return centers, band, pmask


def _draw_negs(C, K, B, neg_prob, neg_alias, k_idx, k_keep):
    """K negatives per block of B consecutive centers via the alias
    tables — B=1 is the per-center draw (and reproduces the round-3
    draws bit-exactly). Returns negs[C//B, K]."""
    nb = C // B
    draw = jax.random.randint(k_idx, (nb, K), 0, neg_prob.shape[0])
    keep_draw = jax.random.uniform(k_keep, (nb, K)) < neg_prob[draw]
    return jnp.where(keep_draw, draw, neg_alias[draw])


def _hs_center_cap(path_len: int, dim: int) -> int:
    """Centers-per-step bound for the HS pipelines: the banded path
    activations are [C+2W, L, D] plus their grad — cap C so they stay
    within ~1.5 GB of HBM. Shared by the local and PS trainers so the
    budget cannot drift between them."""
    return max((3 << 29) // (3 * max(path_len, 1) * dim * 4), 64)


def _banded_sgns_loss_and_grads(v, u_band, u_neg, pmask):
    """SGNS objective in banded form: context logits are dot products
    of each center row against 2W shifted slices of the band's OUTPUT
    rows; sigmoid xent at label 1 (masked) plus label 0 for the
    block-shared negatives (weighted by the center's valid-pair count).
    Returns (loss, g_v, g_band, g_neg)."""
    C, W = pmask.shape[0], pmask.shape[1] // 2
    nb, B = u_neg.shape[0], C // u_neg.shape[0]
    offs = [o for o in range(-W, W + 1) if o != 0]
    nvalid = pmask.sum(axis=1)

    def loss_fn(v, u_band, u_neg):
        pos = jnp.stack(
            [jnp.sum(v * jax.lax.dynamic_slice_in_dim(u_band, W + off, C),
                     axis=-1) for off in offs], axis=1)
        pos = jnp.clip(pos, -_MAX_EXP, _MAX_EXP)
        vb = v.reshape(nb, B, v.shape[-1])
        neg = jnp.clip(jnp.einsum("nbd,nkd->nbk", vb, u_neg),
                       -_MAX_EXP, _MAX_EXP)
        xp = _sigmoid_xent(pos, 1.0) * pmask
        xn = _sigmoid_xent(neg, 0.0) * nvalid.reshape(nb, B)[:, :, None]
        return xp.sum() + xn.sum()

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        v, u_band, u_neg)
    return (loss,) + grads


def _banded_cbow_loss_and_grads(u_band, u_center, u_neg, pmask):
    """CBOW objective in banded form: the masked mean of the window's
    INPUT rows (shifted band slices) predicts the center and the
    block-shared negatives from the OUTPUT table — one example per
    center (ref: wordembedding.cpp CBOW branch; gradient through the
    mean is the 1/|window| form, as on the host-batch path).
    ``u_band`` [C+2W, D] INPUT rows, ``u_center`` [C, D] and ``u_neg``
    [C//B, K, D] OUTPUT rows. Returns
    (loss, g_band, g_center, g_neg, examples)."""
    C, W = pmask.shape[0], pmask.shape[1] // 2
    nb, B = u_neg.shape[0], C // u_neg.shape[0]
    offs = [o for o in range(-W, W + 1) if o != 0]
    nvalid = pmask.sum(axis=1)
    has_ctx = (nvalid > 0).astype(jnp.float32)

    def loss_fn(u_band, u_center, u_neg):
        denom = jnp.maximum(nvalid, 1.0)
        acc = 0.0
        for w, off in enumerate(offs):
            acc = acc + pmask[:, w:w + 1] * \
                jax.lax.dynamic_slice_in_dim(u_band, W + off, C)
        vmean = acc / denom[:, None]
        pos = jnp.clip(jnp.sum(vmean * u_center, axis=-1),
                       -_MAX_EXP, _MAX_EXP)
        vb = vmean.reshape(nb, B, vmean.shape[-1])
        neg = jnp.clip(jnp.einsum("nbd,nkd->nbk", vb, u_neg),
                       -_MAX_EXP, _MAX_EXP)
        xp = _sigmoid_xent(pos, 1.0) * has_ctx
        xn = _sigmoid_xent(neg, 0.0) \
            * has_ctx.reshape(nb, B)[:, :, None]
        return xp.sum() + xn.sum()

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        u_band, u_center, u_neg)
    return (loss,) + grads + (has_ctx.sum(),)


def _scatter_add_pair(table, ids_a, step_a, ids_b, step_b):
    """Two id sets of one table as ONE scatter-add, as a PS block's Add
    of its output ids has it: the ids flat, their delta rows
    concatenated (one sort and one walk of the runs where two calls
    make two; a row named in both gets ``row + (d_a + d_b)``)."""
    dim = table.shape[-1]
    ids = jnp.concatenate([ids_a.reshape(-1), ids_b.reshape(-1)])
    step = jnp.concatenate([step_a.reshape(-1, dim),
                            step_b.reshape(-1, dim)])
    return scatter_add(table, ids, step)


def _apply_step(C, W, K, cbow, emb_in, emb_out, kept_pad, ksent_pad,
                neg_prob, neg_alias, key, base, lr, n_kept,
                neg_block: int = 1):
    """One full in-jit banded training step against local table arrays
    — band former + objective + scatter-add updates of C+2W band rows,
    C center rows and C//B negative rows (vs the C*(2W+K) scattered
    rows of the row-matrix form), one ``rules.scatter_add`` a table as
    a PS block has one Add a table: XLA's scatter or the sorted-runs
    kernel by what ``rules.fast_rows`` reads from the shapes. Shared
    by the single-device group scan and the MA mesh path so the update
    math cannot diverge.
    ``kept_pad``/``ksent_pad`` must come from ``_pad_stream``. Returns
    (emb_in, emb_out, loss, examples)."""
    k_shrink, k_idx, k_keep = jax.random.split(key, 3)
    centers, band, pmask = _band_former(C, W, n_kept, kept_pad,
                                        ksent_pad, k_shrink, base)
    negs = _draw_negs(C, K, neg_block, neg_prob, neg_alias,
                      k_idx, k_keep)
    if cbow:
        # window (input table) -> [center | negs] (output table)
        u_band = emb_in[band]                 # [C+2W, D]
        u_center = emb_out[centers]           # [C, D]
        u_neg = emb_out[negs]                 # [C//B, K, D]
        loss, g_band, g_center, g_neg, examples = \
            _banded_cbow_loss_and_grads(u_band, u_center, u_neg, pmask)
        emb_in = scatter_add(emb_in, band, -lr * g_band)
        emb_out = _scatter_add_pair(emb_out, centers, -lr * g_center,
                                    negs, -lr * g_neg)
        return emb_in, emb_out, loss, examples
    v = emb_in[centers]              # [C, D]
    u_band = emb_out[band]           # [C+2W, D]
    u_neg = emb_out[negs]            # [C//B, K, D]
    loss, g_v, g_band, g_neg = _banded_sgns_loss_and_grads(
        v, u_band, u_neg, pmask)
    emb_in = scatter_add(emb_in, centers, -lr * g_v)
    emb_out = _scatter_add_pair(emb_out, band, -lr * g_band,
                                negs, -lr * g_neg)
    return emb_in, emb_out, loss, pmask.sum()


def _pair_offset_loss_and_grads(v, u_pos, u_neg, m):
    """One offset's C pairs of the quality mode: label-1 xent against
    the positive rows, label-0 against that offset's per-pair
    negatives, masked by the pair validity. Shared by the local
    sequential sub-steps and the PS block's local-copy sub-steps so the
    quality-mode objective cannot diverge between pipelines. Returns
    (loss, g_v, g_pos, g_neg)."""

    def loss_fn(v, u_pos, u_neg):
        pos = jnp.clip(jnp.sum(v * u_pos, axis=-1), -_MAX_EXP, _MAX_EXP)
        neg = jnp.clip(jnp.einsum("cd,ckd->ck", v, u_neg),
                       -_MAX_EXP, _MAX_EXP)
        return (jnp.sum(_sigmoid_xent(pos, 1.0) * m)
                + jnp.sum(_sigmoid_xent(neg, 0.0) * m[:, None]))

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
        v, u_pos, u_neg)
    return (loss,) + grads


def _seq_pair_step(C, W, K, emb_in, emb_out, kept_pad, ksent_pad,
                   neg_prob, neg_alias, key, base, lr, n_kept):
    """QUALITY-mode skip-gram step: per-PAIR negatives and per-offset
    SEQUENTIAL updates — the closest in-jit approximation of the
    reference's pair-by-pair SGD (ref: wordembedding.cpp Train: each
    (center, context) pair draws its own K negatives and applies its
    update before the next pair trains). The 2W offsets run as
    sequential sub-steps against the LIVE tables, so each offset's C
    pairs see every earlier offset's updates. ~8x the row traffic of
    the shared-negative banded step; it trades that for the reference's
    per-epoch quality (no cell runs it: not measured on the chip)."""
    k_shrink, k_idx, k_keep = jax.random.split(key, 3)
    centers, band, pmask = _band_former(C, W, n_kept, kept_pad,
                                        ksent_pad, k_shrink, base)
    draw = jax.random.randint(k_idx, (2 * W, C, K), 0,
                              neg_prob.shape[0])
    keep_draw = jax.random.uniform(k_keep, (2 * W, C, K)) \
        < neg_prob[draw]
    negs_all = jnp.where(keep_draw, draw, neg_alias[draw])
    offs = [o for o in range(-W, W + 1) if o != 0]
    loss_acc = 0.0
    for w, off in enumerate(offs):
        ctx = jax.lax.dynamic_slice_in_dim(band, W + off, C)
        negs = negs_all[w]                       # [C, K]
        loss, g_v, g_pos, g_neg = _pair_offset_loss_and_grads(
            emb_in[centers], emb_out[ctx], emb_out[negs], pmask[:, w])
        emb_in = scatter_add(emb_in, centers, -lr * g_v)
        emb_out = _scatter_add_pair(emb_out, ctx, -lr * g_pos,
                                    negs, -lr * g_neg)
        loss_acc = loss_acc + loss
    return emb_in, emb_out, loss_acc, pmask.sum()


def _make_group(step, pad):
    """The scan driver shared by every device group program: carry the
    tables + PRNG key through G steps, sum losses/examples, return the
    advanced key, donate the table buffers. ``pad=(C, W)`` pads the
    kept stream for the banded steps at group entry (one ~24 MB fused
    copy per dispatch — the per-step slices then never clamp); every
    step formulation is banded now, so padding is unconditional."""

    def group(emb_in, emb_out, kept, ksent, aux1, aux2,
              key, bases, lrs, n_kept):
        kept, ksent = _pad_stream(pad[0], pad[1], kept, ksent)

        def body(carry, xs):
            emb_in, emb_out, key = carry
            base, lr = xs
            key, sub = jax.random.split(key)
            emb_in, emb_out, loss, pairs = step(
                emb_in, emb_out, kept, ksent, aux1, aux2, sub, base,
                lr, n_kept)
            return (emb_in, emb_out, key), (loss, pairs)

        (emb_in, emb_out, key), (losses, pairs) = jax.lax.scan(
            body, (emb_in, emb_out, key), (bases, lrs))
        return emb_in, emb_out, losses.sum(), pairs.sum(), key

    return jax.jit(group, donate_argnums=(0, 1))


def _hs_sg_loss_and_grads(v, u_band_path, path_band, code_band, pmask):
    """Banded skip-gram HS objective: the center row against the
    Huffman-path rows of each context word, labels ``1 - code`` (code 0
    = positive, the word2vec convention; ref: wordembedding.cpp HS
    branch). Path rows are gathered ONCE per band position
    (``u_band_path`` [C+2W, L, D]) and the 2W context logits come from
    shifted slices — the same overlap trick as the SGNS band, 2W-fold
    less gather/scatter than the [C, 2W, L, D] row-matrix form.
    Returns (loss, g_v, g_band_path)."""
    C, W = pmask.shape[0], pmask.shape[1] // 2
    offs = [o for o in range(-W, W + 1) if o != 0]
    node_ok = ((path_band >= 0) & (code_band >= 0)).astype(jnp.float32)
    labels_band = (1.0 - code_band.astype(jnp.float32))

    def loss_fn(v, u_band_path):
        total = 0.0
        for w, off in enumerate(offs):
            u_off = jax.lax.dynamic_slice_in_dim(
                u_band_path, W + off, C)                  # [C, L, D]
            mask = jax.lax.dynamic_slice_in_dim(
                node_ok, W + off, C) * pmask[:, w:w + 1]
            labels = jax.lax.dynamic_slice_in_dim(
                labels_band, W + off, C) * mask
            logits = jnp.clip(jnp.einsum("cd,cld->cl", v, u_off),
                              -_MAX_EXP, _MAX_EXP)
            total = total + jnp.sum(_sigmoid_xent(logits, labels)
                                    * mask)
        return total

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        v, u_band_path)
    return (loss,) + grads


def _hs_cbow_loss_and_grads(u_band_in, u_path, path, code, pmask):
    """CBOW + HS objective: the masked mean of the window's INPUT rows
    (shifted band slices) against the CENTER's Huffman path — one
    example per center (ref: wordembedding.cpp CBOW+HS combination).
    ``u_band_in`` [C+2W, D] INPUT rows, ``u_path`` [C, L, D] the
    center-path OUTPUT rows. Returns (loss, g_band, g_path, examples)."""
    C, W = pmask.shape[0], pmask.shape[1] // 2
    offs = [o for o in range(-W, W + 1) if o != 0]
    nvalid = pmask.sum(axis=1)
    has_ctx = (nvalid > 0).astype(jnp.float32)
    mask = ((path >= 0) & (code >= 0)).astype(jnp.float32) \
        * has_ctx[:, None]
    labels = (1.0 - code.astype(jnp.float32)) * mask

    def loss_fn(u_band_in, u_path):
        denom = jnp.maximum(nvalid, 1.0)
        acc = 0.0
        for w, off in enumerate(offs):
            acc = acc + pmask[:, w:w + 1] * \
                jax.lax.dynamic_slice_in_dim(u_band_in, W + off, C)
        vmean = acc / denom[:, None]
        logits = jnp.clip(jnp.einsum("cd,cld->cl", vmean, u_path),
                          -_MAX_EXP, _MAX_EXP)
        return jnp.sum(_sigmoid_xent(logits, labels) * mask)

    loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(
        u_band_in, u_path)
    return (loss,) + grads + (has_ctx.sum(),)


@functools.lru_cache(maxsize=None)
def _group_fn_hs(C: int, W: int, cbow: bool = False):
    """Hierarchical-softmax group in banded form, covering skip-gram
    (center row vs the context words' Huffman paths) and CBOW (window
    mean vs the center's path). The aux argument slots carry
    (points, codes) [V, L] (-1 padded) instead of the SGNS alias
    tables — same arity as ``_group_fn``, so the trainer drives either
    interchangeably."""

    def step(emb_in, emb_out, kept_pad, ksent_pad, points, codes,
             key, base, lr, n_kept):
        k_shrink, _ = jax.random.split(key)
        centers, band, pmask = _band_former(C, W, n_kept, kept_pad,
                                            ksent_pad, k_shrink, base)
        if cbow:
            path = points[centers]                # [C, L]
            code = codes[centers]
            out_ids = jnp.maximum(path, 0)
            u_band = emb_in[band]
            u_path = emb_out[out_ids]             # [C, L, D]
            loss, g_band, g_path, examples = _hs_cbow_loss_and_grads(
                u_band, u_path, path, code, pmask)
            emb_in = scatter_add(emb_in, band, -lr * g_band)
            emb_out = scatter_add(emb_out, out_ids, -lr * g_path)
            return emb_in, emb_out, loss, examples
        path_band = points[band]                  # [C+2W, L]
        code_band = codes[band]
        out_ids = jnp.maximum(path_band, 0)
        v = emb_in[centers]
        u_band_path = emb_out[out_ids]            # [C+2W, L, D]
        loss, g_v, g_band_path = _hs_sg_loss_and_grads(
            v, u_band_path, path_band, code_band, pmask)
        emb_in = scatter_add(emb_in, centers, -lr * g_v)
        emb_out = scatter_add(emb_out, out_ids, -lr * g_band_path)
        return emb_in, emb_out, loss, pmask.sum()

    return _make_group(step, pad=(C, W))


# Module-level cache so every trainer instance with the same static
# shape (C, window, negative, corpus length, mode) shares one compiled
# group program — a warmup trainer's compile pays for the timed one.
@functools.lru_cache(maxsize=None)
def _group_fn(C: int, W: int, K: int, cbow: bool = False,
              neg_block: int = 1, per_pair: bool = False):
    def step(emb_in, emb_out, kept_pad, ksent_pad, neg_prob, neg_alias,
             key, base, lr, n_kept):
        if per_pair:
            return _seq_pair_step(C, W, K, emb_in, emb_out, kept_pad,
                                  ksent_pad, neg_prob, neg_alias, key,
                                  base, lr, n_kept)
        return _apply_step(C, W, K, cbow, emb_in, emb_out, kept_pad,
                           ksent_pad, neg_prob, neg_alias, key, base,
                           lr, n_kept, neg_block=neg_block)

    return _make_group(step, pad=(C, W))


@functools.lru_cache(maxsize=None)
def _ma_group_fn(mesh, C: int, W: int, K: int, neg_block: int = 1):
    """Model-average (``-ma``) word2vec over a device mesh: each device
    scans G local SGNS steps against its own REPLICA of the embedding
    tables on its own CORPUS SHARD, then the replicas average with
    ``lax.pmean`` over ICI — the reference's MA mode (train locally,
    MV_Aggregate; ref: src/zoo.cpp:24,49, src/multiverso.cpp:53-56)
    with the aggregate riding XLA collectives inside one jitted step.

    Arguments of the returned jit (all as ONE global call):
    ``emb_in/emb_out`` replicated [V, D]; ``kept/ksent`` sharded
    [n_devices * n_local]; ``keys`` one PRNG key per device
    [n_devices, 2]; ``bases/lrs`` [G]; ``n_kept_local`` per-device kept
    counts [n_devices]. Returns (averaged tables, summed loss, summed
    pairs, advanced per-device keys) — feed the keys back when chaining
    dispatches or every group replays the same draws."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    axis = mesh.axis_names[0]

    def device_group(emb_in, emb_out, kept, ksent, neg_prob, neg_alias,
                     keys, bases, lrs, n_kept_local):
        key = keys[0]
        n_kept = n_kept_local[0]
        # Pad each device's LOCAL stream for the banded slices (inside
        # shard_map, so this is a per-shard local op).
        kept_pad, ksent_pad = _pad_stream(C, W, kept, ksent)

        def body(carry, xs):
            emb_in, emb_out, key = carry
            base, lr = xs
            key, sub = jax.random.split(key)
            emb_in, emb_out, loss, pairs = _apply_step(
                C, W, K, False, emb_in, emb_out, kept_pad,
                ksent_pad, neg_prob, neg_alias, sub, base, lr, n_kept,
                neg_block=neg_block)
            return (emb_in, emb_out, key), (loss, pairs)

        (emb_in, emb_out, key), (losses, pairs) = jax.lax.scan(
            body, (emb_in, emb_out, key), (bases, lrs))
        # MV_Aggregate: average the trained replicas over the mesh.
        emb_in = jax.lax.pmean(emb_in, axis)
        emb_out = jax.lax.pmean(emb_out, axis)
        return (emb_in, emb_out, jax.lax.psum(losses.sum(), axis),
                jax.lax.psum(pairs.sum(), axis), key[None])

    mapped = shard_map(
        device_group, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis), P(), P(),
                  P(axis), P(), P(), P(axis)),
        out_specs=(P(), P(), P(), P(), P(axis)),
        # The replicated tables DIVERGE per device once local training
        # starts and pmean collapses them back; the step's scatter-add
        # may be an exported kernel program (row_scatter.py), whose
        # call carries no varying types, so they are not checked here,
        # as in row_scatter's own shard_map.
        check_vma=False)
    return jax.jit(mapped, donate_argnums=(0, 1))


class _CorpusOnDevice:
    """Shared upload of a ``TokenizedCorpus``: the flat id stream, its
    per-token sentence ids, and the subsample keep probabilities — one
    transfer, reused every epoch by both the local and the PS device
    trainers."""

    def __init__(self, model, tokenized: TokenizedCorpus):
        config = model.config
        flat = np.asarray(tokenized.flat, np.int32)
        lengths = np.diff(tokenized.offsets).astype(np.int64)
        sent = np.repeat(np.arange(lengths.size, dtype=np.int32), lengths)
        self.n_tokens = int(flat.size)
        # One-time host->device uploads; construction can overlap a
        # sibling rank's step in multi-zoo mode, so guard like any
        # dispatch (no-op in the one-zoo deployment).
        with device_lock.guard():
            self.flat = device_lock.settle(jnp.asarray(flat))
            self.sent = device_lock.settle(jnp.asarray(sent))
            self.keep = device_lock.settle(jnp.asarray(
                model.dictionary.subsample_keep_prob(config.sample)))

    def prep_epoch(self, key):
        # Multi-zoo mode (device_lock.py): the prep program is a
        # multi-device dispatch like any step — serialize and settle.
        with device_lock.guard():
            return device_lock.settle(
                _prep(self.flat, self.sent, self.keep, key))


class DeviceCorpusTrainer:
    """Drives a ``Word2Vec`` model's embeddings straight from a
    device-resident ``TokenizedCorpus``. Covers the FULL mode matrix:
    {skip-gram, CBOW} x {negative sampling, hierarchical softmax}
    (ref: wordembedding.h:95-125 trains every combination through its
    one hot loop), plus the -per_pair skip-gram quality mode."""

    def __init__(self, model, tokenized: TokenizedCorpus,
                 centers_per_step: int = 32768,
                 steps_per_dispatch: int = 8):
        # TRAINER_BUILD, entered and left by hand as PSLMTrainer's is
        # (models/lm/ps_train.py says why no decorator and no ``with``)
        building = monitor("TRAINER_BUILD")
        building.__enter__()
        config = model.config
        self.model = model
        self.config = config
        self._C = int(centers_per_step)
        self._G = int(steps_per_dispatch)
        self._corpus = _CorpusOnDevice(model, tokenized)
        self._n_tokens = self._corpus.n_tokens
        if config.hs:
            # Banded HS activations are [C+2W, L, D] (L = max Huffman
            # path, ~log2 vocab; the round-3 row-matrix form was
            # [C, 2W, L, D] — 2W-fold bigger). Cap C so the gathered
            # path rows + their grad stay within ~1.5 GB; callers can
            # pass a smaller centers_per_step, larger is refused by the
            # cap rather than by an HBM OOM mid-epoch.
            path_len = max(int(model._points_host.shape[1]), 1)
            self._C = min(self._C, _hs_center_cap(
                path_len, int(config.embedding_size)))
            self._group = _group_fn_hs(self._C, config.window,
                                       bool(config.cbow))
            # aux slots: the Huffman path/code tables.
            self._aux = (model._points_dev, model._codes_dev)
        else:
            B = max(int(getattr(config, "neg_block", 1)), 1)
            if self._C % B:
                raise ValueError("neg_block must divide centers_per_step")
            per_pair = bool(getattr(config, "per_pair", False))
            if per_pair and config.cbow:
                raise ValueError("per_pair is a skip-gram quality mode")
            self._group = _group_fn(self._C, config.window,
                                    config.negative, bool(config.cbow),
                                    B, per_pair)
            self._aux = (model._neg_prob_dev, model._neg_alias_dev)
        # What a dispatch counts, as the tables' engine counts its Adds:
        # one a scatter-add call of a block, by the path the call takes.
        # rules.fast_rows reads only what is static, so it is known
        # here, from the id count of the input table's call and of the
        # output table's (one call each a step; -per_pair one an offset).
        band, calls = self._C + 2 * config.window, 1
        if config.hs:
            in_ids, out_ids = ((band, self._C * path_len) if config.cbow
                               else (self._C, band * path_len))
        elif per_pair:
            in_ids, out_ids = self._C, self._C * (1 + config.negative)
            calls = 2 * config.window
        else:
            negs = self._C // B * config.negative
            in_ids, out_ids = ((band, self._C + negs) if config.cbow
                               else (self._C, band + negs))
        self._add_counts = tuple(
            ("UPDATE_ROWS_FAST" if fast_rows(table.shape, table.dtype, n)
             else "UPDATE_ROWS_XLA", calls)
            for table, n in ((model._emb_in, in_ids),
                             (model._emb_out, out_ids)))
        # One signature for every dispatch of the group. A program that
        # calls an exported one (the kernel's chunk program) hands back
        # arrays COMMITTED to their device, so tables or a key that go
        # in uncommitted the first time would make the same program a
        # second and a third one to jit, each compiled, one of them in
        # the second epoch. The tables are committed here and each
        # epoch's key below: no copy, they are on that device.
        with device_lock.guard():
            model._emb_in, model._emb_out = device_lock.settle(
                jax.device_put((model._emb_in, model._emb_out),
                               model._emb_in.sharding))
        # Post-subsampling tokens actually trained (centers), across
        # epochs — the exact basis for utilization accounting.
        self.kept_words_trained = 0
        building.__exit__(None, None, None)

    def train_epoch(self, seed: int, group_hook=None,
                    max_steps: int = 0) -> Tuple[float, float]:
        """One full epoch on device. ``group_hook(words)`` is called
        after each dispatched group with the raw-word count it covered
        (the benchmark's word count); ``max_steps`` truncates the epoch (warmup).
        Returns (loss_sum, examples) as floats — fetched ONCE at epoch
        end. ``examples`` counts (center, context) pairs in skip-gram
        mode and trained centers in CBOW mode (one prediction per
        center)."""
        thread_roles.ensure_heartbeat()    # no actor here to start it
        model, C, G = self.model, self._C, self._G
        with monitor("TRAINER_EPOCH_PREP"):
            key = jax.random.PRNGKey(seed)
            key, prep_key = jax.random.split(key)
            kept, ksent, n_kept_dev = self._corpus.prep_epoch(prep_key)
            n_kept = int(n_kept_dev)  # the one host fetch per epoch
            with device_lock.guard():  # committed, as a group returns it
                key = device_lock.settle(
                    jax.device_put(key, model._emb_in.sharding))
        steps = max(math.ceil(n_kept / C), 1)
        if max_steps:
            steps = min(steps, max_steps)
        self.kept_words_trained += min(steps * C, n_kept)
        # lr schedule decays in RAW corpus words (subsample-dropped words
        # count, ref: distributed_wordembedding.cpp:92-134): spread the
        # epoch's raw words uniformly over its steps.
        raw_per_step = self._n_tokens / max(math.ceil(n_kept / C), 1)
        loss_acc = None
        pair_acc = None
        for g0 in range(0, steps, G):
            bases = np.full(G, n_kept, np.int32)  # padded steps: no-ops
            real = min(G, steps - g0)
            bases[:real] = (np.arange(g0, g0 + real) * C).astype(np.int32)
            lrs = np.zeros(G, np.float32)
            for i in range(real):
                lrs[i] = model.learning_rate()
                model.trained_words += raw_per_step
            with monitor("TRAINER_GROUP_DISPATCH"), device_lock.guard():
                (model._emb_in, model._emb_out, loss, pairs,
                 key) = device_lock.settle(self._group(
                    model._emb_in, model._emb_out, kept, ksent,
                    self._aux[0], self._aux[1], key,
                    jnp.asarray(bases), jnp.asarray(lrs), n_kept_dev))
            for path, calls in self._add_counts:
                count(path, real * calls)
            loss_acc = loss if loss_acc is None else loss_acc + loss
            pair_acc = pairs if pair_acc is None else pair_acc + pairs
            if group_hook is not None:
                group_hook(raw_per_step * real)
        return (0.0 if loss_acc is None else float(loss_acc),
                0.0 if pair_acc is None else float(pair_acc))


def _sum_parts(x):
    """Sum a tuple of per-server reply shards (or pass a single array
    through) — used INSIDE the PS step jits."""
    if isinstance(x, (tuple, list)):
        return functools.reduce(jnp.add, x)
    return x


@functools.lru_cache(maxsize=None)
def _block_ids_fn_hs(C: int, W: int, cbow: bool = False):
    """HS block preparation for the PS pipeline: the OUTPUT ids are the
    Huffman-path inner-node rows (banded for skip-gram — one path per
    band position; the center's path for CBOW). The third slot carries
    (pmask, path, code) so the step can mask and label without
    re-deriving them."""

    def ids(kept_pad, ksent_pad, points, codes, key, base, n_kept):
        k_shrink, _ = jax.random.split(key)
        centers, band, pmask = _band_former(C, W, n_kept, kept_pad,
                                            ksent_pad, k_shrink, base)
        if cbow:
            path = points[centers]                 # [C, L]
            code = codes[centers]
            return band, jnp.maximum(path, 0).reshape(-1), \
                (pmask, path, code)
        path = points[band]                        # [C+2W, L]
        code = codes[band]
        return centers, jnp.maximum(path, 0).reshape(-1), \
            (pmask, path, code)

    return jax.jit(ids)


@functools.lru_cache(maxsize=None)
def _block_step_fn_hs(C: int, W: int, L: int, cbow: bool = False):
    """HS PS block step over PULLED rows: mirrors ``_block_step_fn``'s
    contract (aux = the (pmask, path, code) tuple from
    ``_block_ids_fn_hs``)."""

    def step(v, u, aux, lr, inv_workers):
        v = _sum_parts(v)
        u = _sum_parts(u)
        pmask, path, code = aux
        lr_scaled = lr * inv_workers
        if cbow:
            u_path = u.reshape(C, L, -1)
            loss, g_band, g_path, examples = _hs_cbow_loss_and_grads(
                v, u_path, path, code, pmask)
            return (-lr_scaled * g_band,
                    -lr_scaled * g_path.reshape(C * L, -1), loss,
                    examples)
        u_bp = u.reshape(C + 2 * W, L, -1)
        loss, g_v, g_bp = _hs_sg_loss_and_grads(v, u_bp, path, code,
                                                pmask)
        return (-lr_scaled * g_v,
                -lr_scaled * g_bp.reshape((C + 2 * W) * L, -1), loss,
                pmask.sum())

    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def _block_ids_fn(C: int, W: int, K: int, cbow: bool = False,
                  neg_block: int = 1, per_pair: bool = False):
    """Jitted block preparation for the PS pipeline: the INPUT-table id
    block, the OUTPUT-table id block (flat), and the pair validity mask
    — all device-resident, ready to hand to the tables as DEVICE keys.
    Takes the PADDED stream (pad once per epoch, not per step).
    Banded form: skip-gram in=centers [C],
    out=[band (C+2W) | negs (C//B*K)]; CBOW in=band [C+2W],
    out=[centers (C) | negs (C//B*K)]. The band replaces the [C, 2W]
    context id matrix — 2W-fold fewer pulled/pushed rows."""

    # The scope names the program's operations in a device trace; the
    # jitted function keeps its own name.
    @jax.named_scope("mv.sgns.ids")
    def ids(kept_pad, ksent_pad, neg_prob, neg_alias, key, base,
            n_kept):
        k_shrink, k_idx, k_keep = jax.random.split(key, 3)
        centers, band, pmask = _band_former(C, W, n_kept, kept_pad,
                                            ksent_pad, k_shrink, base)
        if per_pair:
            # Quality mode: K negatives per (center, offset) pair, drawn
            # with the SAME key-split order as _seq_pair_step.
            draw = jax.random.randint(k_idx, (2 * W, C, K), 0,
                                      neg_prob.shape[0])
            keep_draw = jax.random.uniform(k_keep, (2 * W, C, K)) \
                < neg_prob[draw]
            negs = jnp.where(keep_draw, draw, neg_alias[draw])
            return centers, jnp.concatenate([band, negs.reshape(-1)]), \
                pmask
        negs = _draw_negs(C, K, neg_block, neg_prob, neg_alias,
                          k_idx, k_keep)
        if cbow:
            return band, jnp.concatenate([centers, negs.reshape(-1)]), \
                pmask
        return centers, jnp.concatenate([band, negs.reshape(-1)]), pmask

    return jax.jit(ids)


@functools.lru_cache(maxsize=None)
def _block_step_fn(C: int, W: int, K: int, cbow: bool = False,
                   neg_block: int = 1, per_pair: bool = False):
    """Jitted PS block step over PULLED rows (banded layout from
    ``_block_ids_fn``): returns the PUSH deltas
    ``-lr*grad/num_workers`` (the reference's (new-old)/num_workers with
    one local step, ref: communicator.cpp:157-249) plus loss/examples.
    ``per_pair``: the quality mode's 2W sequential sub-steps run against
    the PULLED copies (the reference's PS trainer also trains local row
    copies and pushes new-old, communicator.cpp:157-249); the pushed
    delta is the net local change over all sub-steps, / num_workers."""
    nb = C // neg_block

    @jax.named_scope("mv.sgns.step")
    def step(v, u, pmask, lr, inv_workers):
        # Multi-server pulls arrive as per-server shard tuples (foreign
        # rows zero-filled); summing them HERE folds the reassembly into
        # this program instead of a separate eager dispatch per pull.
        v = _sum_parts(v)
        u = _sum_parts(u)
        if per_pair:
            u_band0 = u[:C + 2 * W]
            u_negs0 = u[C + 2 * W:].reshape(2 * W, C, K, -1)
            offs = [o for o in range(-W, W + 1) if o != 0]
            v_cur, u_band, u_negs = v, u_band0, u_negs0
            loss_acc = 0.0
            for w, off in enumerate(offs):
                u_pos = jax.lax.dynamic_slice_in_dim(u_band, W + off, C)
                loss, g_v, g_pos, g_neg = _pair_offset_loss_and_grads(
                    v_cur, u_pos, u_negs[w], pmask[:, w])
                # Sub-steps apply the RAW lr to the local copies; the
                # pushed net delta carries the 1/num_workers scale.
                v_cur = v_cur - lr * g_v
                u_band = u_band.at[W + off:W + off + C].add(-lr * g_pos)
                u_negs = u_negs.at[w].add(-lr * g_neg)
                loss_acc = loss_acc + loss
            d_v = (v_cur - v) * inv_workers
            d_u = jnp.concatenate(
                [u_band - u_band0,
                 (u_negs - u_negs0).reshape(2 * W * C * K, -1)]) \
                * inv_workers
            return d_v, d_u, loss_acc, pmask.sum()
        lr_scaled = lr * inv_workers
        if cbow:
            # v = pulled INPUT band rows [C+2W, D]; u = pulled OUTPUT
            # [centers | negs] rows [C + nb*K, D].
            u_center = u[:C]
            u_neg = u[C:].reshape(nb, K, -1)
            loss, g_band, g_center, g_neg, examples = \
                _banded_cbow_loss_and_grads(v, u_center, u_neg, pmask)
            g_out = jnp.concatenate(
                [g_center, g_neg.reshape(nb * K, -1)])
            return -lr_scaled * g_band, -lr_scaled * g_out, loss, examples
        # v = pulled center rows [C, D]; u = [band | negs] rows.
        u_band = u[:C + 2 * W]
        u_neg = u[C + 2 * W:].reshape(nb, K, -1)
        loss, g_v, g_band, g_neg = _banded_sgns_loss_and_grads(
            v, u_band, u_neg, pmask)
        g_u = jnp.concatenate([g_band, g_neg.reshape(nb * K, -1)])
        return -lr_scaled * g_v, -lr_scaled * g_u, loss, pmask.sum()

    return jax.jit(step)


@functools.lru_cache(maxsize=None)
def _grouped_ids_fn(ids_fn, G: int):
    """vmap an ids program over G blocks: one program launch prepares
    G blocks' id sets (stacked on a leading axis) from one folded key
    and a [G] base vector."""
    mapped = jax.vmap(ids_fn, in_axes=(None, None, None, None, 0, 0,
                                       None))

    @jax.jit
    def ids(kept_pad, ksent_pad, aux1, aux2, key, bases, n_kept):
        keys = jax.random.split(key, G)
        return mapped(kept_pad, ksent_pad, aux1, aux2, keys, bases,
                      n_kept)

    return ids


@functools.lru_cache(maxsize=None)
def _grouped_step_fn(step_fn, G: int):
    """vmap a PS block step over G stacked blocks (per-block lr vector),
    summing losses/examples — one program launch trains G blocks
    against the group's shared pulled state."""
    mapped = jax.vmap(step_fn, in_axes=(0, 0, 0, 0, None))

    @jax.jit
    def step(v, u, pmask, lrs, inv_workers):
        d_v, d_u, loss, examples = mapped(v, u, pmask, lrs, inv_workers)
        return d_v, d_u, loss.sum(), examples.sum()

    return step


@functools.lru_cache(maxsize=None)
def _loop_ids_fn(ids_fn, C: int, G: int):
    """The ids program as ``PSDeviceCorpusTrainer.train_epoch`` runs it:
    ``ids_fn`` (a ``_block_ids_fn*``, or its ``_grouped_ids_fn``) with
    the block's key folded from the epoch's and its base (the [G]
    bases) computed inside, from the block's number ``g0`` and the
    group's count of real blocks: the key and the integers that
    ``fold_in(key, g0)`` and ``g0 * C`` give on the host, so a block's
    number is all the host hands the program."""

    @jax.jit
    def ids(kept_pad, ksent_pad, aux1, aux2, key, g0, real, n_kept):
        with jax.named_scope("mv.sgns.ids"):
            step_key = jax.random.fold_in(key, g0)
            if G == 1:
                base = g0 * C
            else:
                # Padded tail blocks get base = n_kept (fully masked).
                i = jnp.arange(G, dtype=jnp.int32)
                base = jnp.where(i < real, (g0 + i) * C, n_kept)
        return ids_fn(kept_pad, ksent_pad, aux1, aux2, step_key, base,
                      n_kept)

    return ids


@functools.lru_cache(maxsize=None)
def _loop_step_fn(step_fn):
    """The step program as the loop runs it: ``step_fn`` (a
    ``_block_step_fn*``, or its ``_grouped_step_fn``) and the epoch's
    running sums, which it takes (donated) and hands back beside the
    block's own loss and examples: one float32 addition each a block,
    in the blocks' order."""

    @functools.partial(jax.jit, donate_argnums=(5, 6))
    def step(v, u, pmask, lr, inv_workers, loss_acc, pair_acc):
        d_v, d_u, loss, pairs = step_fn(v, u, pmask, lr, inv_workers)
        return d_v, d_u, loss, pairs, loss_acc + loss, pair_acc + pairs

    return step


class PSDeviceCorpusTrainer:
    """The PS twin of ``DeviceCorpusTrainer``: same HBM-resident corpus
    pipeline, but the embeddings live in PARAMETER-SERVER matrix tables
    — every block pulls its rows through the full worker/server actor
    stack (device-key Gets), trains, and pushes ``-lr*grad/num_workers``
    deltas back (device-key Adds). Nothing but the block's number and
    learning rate crosses the host boundary, as arguments of the two
    programs a block dispatches (ids, step); that is what lets the PS
    path approach local-mode throughput in-process (the reference's
    block protocol, ref:
    Applications/WordEmbedding/src/communicator.cpp:117-249, with the
    row list living in HBM).

    Requires the in-process device path. Multi-server tables work —
    device keys broadcast to every server, which masks foreign rows on
    device (ref partition contract: src/table/matrix_table.cpp:234-315)
    — at the cost of one extra [k, D] pass per additional server; the
    host-batch ``PSWord2Vec.train_batches`` remains the general path
    for cross-process runs."""

    def __init__(self, model, tokenized: TokenizedCorpus,
                 centers_per_step: int = 32768,
                 blocks_per_dispatch: int = 1):
        """``blocks_per_dispatch`` (G) batches G blocks' ids into ONE
        pull/step/push round trip — G-fold fewer program launches (the
        per-dispatch launch cost, not measured on the current machine), at
        the price of G blocks reading the same table state before their
        deltas land: the same bounded-staleness trade the reference
        makes with -is_pipeline prefetch and sync_frequency > 1
        (ref: distributed_wordembedding.cpp:203-224,
        LogisticRegression configure.h sync_frequency). G=1 keeps exact
        per-block semantics."""
        building = monitor("TRAINER_BUILD")     # as DeviceCorpusTrainer's
        building.__enter__()
        config = model.config
        if not getattr(model, "_device_path", False):
            raise ValueError("PS device pipeline needs in-process "
                             "servers (device path)")
        self.model = model
        self.config = config
        self._C = int(centers_per_step)
        self._G = max(int(blocks_per_dispatch), 1)
        self._corpus = _CorpusOnDevice(model, tokenized)
        self._n_tokens = self._corpus.n_tokens
        # Where a block's rows are: replicated over the mesh the
        # in-process servers lay their tables on. Whatever the loop's
        # two programs read is placed so, once: the ids program then
        # hands the tables ids, and the step a mask, that are where the
        # table's programs and the step run, and no program has to
        # place an argument again at every dispatch (over several
        # devices that is a copy a device, made from Python).
        self._on_rows = meshlib.replicated(meshlib.local_mesh())
        if config.hs:
            if not hasattr(model, "_points_dev"):
                # PSWord2Vec keeps the Huffman tables host-side (its
                # batch path preps row sets on the host); this pipeline
                # derives paths in-jit, so upload them once (guarded:
                # construction can overlap a sibling rank's step).
                with device_lock.guard():
                    model._points_dev, model._codes_dev = \
                        device_lock.settle(jax.device_put(
                            (model._points_host, model._codes_host),
                            self._on_rows))
            path_len = max(int(model._points_host.shape[1]), 1)
            self._C = min(self._C, _hs_center_cap(
                path_len, int(config.embedding_size)))
            self._ids = _block_ids_fn_hs(self._C, config.window,
                                         bool(config.cbow))
            self._step = _block_step_fn_hs(self._C, config.window,
                                           path_len, bool(config.cbow))
            self._aux_tables = (model._points_dev, model._codes_dev)
        else:
            if not hasattr(model, "_neg_prob_dev"):
                # PSWord2Vec keeps the alias tables host-side (its batch
                # path draws negatives on the host); this pipeline
                # samples in-jit, so upload them once (guarded:
                # construction can overlap a sibling rank's step).
                with device_lock.guard():
                    model._neg_prob_dev, model._neg_alias_dev = \
                        device_lock.settle(jax.device_put(
                            (model._neg_prob_host, model._neg_alias_host),
                            self._on_rows))
            B = max(int(getattr(config, "neg_block", 1)), 1)
            if self._C % B:
                raise ValueError("neg_block must divide centers_per_step")
            per_pair = bool(getattr(config, "per_pair", False))
            if per_pair and config.cbow:
                raise ValueError("per_pair is a skip-gram quality mode")
            self._ids = _block_ids_fn(self._C, config.window,
                                      config.negative,
                                      bool(config.cbow), B, per_pair)
            self._step = _block_step_fn(self._C, config.window,
                                        config.negative,
                                        bool(config.cbow), B, per_pair)
            self._aux_tables = (model._neg_prob_dev,
                                model._neg_alias_dev)
        self._pad = jax.jit(functools.partial(_pad_stream, self._C,
                                              config.window),
                            out_shardings=self._on_rows)
        if self._G > 1:
            self._ids = _grouped_ids_fn(self._ids, self._G)
            self._step = _grouped_step_fn(self._step, self._G)
        # The loop's two programs: _ids and _step (which keep their
        # signatures for callers that run one block by hand) with the
        # block's key and base, and the epoch's sums, inside.
        self._loop_ids = _loop_ids_fn(self._ids, self._C, self._G)
        self._loop_step = _loop_step_fn(self._step)
        with device_lock.guard():
            self._inv_workers = device_lock.settle(jax.device_put(
                np.float32(1.0 / model._num_workers), self._on_rows))
        self.kept_words_trained = 0
        building.__exit__(None, None, None)

    def train_epoch(self, seed: int, block_hook=None,
                    max_steps: int = 0) -> Tuple[float, float]:
        """One epoch: per dispatch group (G blocks; G=1 default),
        compute ids on device -> device-key pulls -> jitted step ->
        device-key delta pushes, all dispatched asynchronously (pushes
        are fire-and-forget until the trailing drain). A block is two
        dispatches of the trainer's own, ids and step: nothing but the
        block's number and learning rate crosses the host boundary, as
        numpy arguments of the two; the block's key is folded and the
        epoch's loss and example sums are kept inside them."""
        model, C, G = self.model, self._C, self._G
        in_table, out_table = model._in_table, model._out_table
        with monitor("TRAINER_EPOCH_PREP"):
            key = jax.random.PRNGKey(seed)
            key, prep_key = jax.random.split(key)
            kept, ksent, n_kept_dev = self._corpus.prep_epoch(prep_key)
            # Pad ONCE per epoch; the per-step ids program then slices
            # the padded stream directly (padding per step would
            # re-copy the whole stream every block). The padded stream,
            # the epoch's key (the ids program folds each block's from
            # it), the kept count and the epoch's sums, which start at
            # zero, are placed where the blocks' rows are.
            with device_lock.guard():
                kept_pad, ksent_pad = device_lock.settle(
                    self._pad(kept, ksent))
                del kept, ksent   # not held through the epoch
                key, n_kept_dev, loss_acc, pair_acc = device_lock.settle(
                    jax.device_put(
                        (key, n_kept_dev, np.float32(0), np.float32(0)),
                        self._on_rows))
            n_kept = int(n_kept_dev)
            # The first block waits for the pad: dispatched before it
            # has run, its rows would lie beside both streams, the
            # epoch's peak of device memory.
            jax.block_until_ready(ksent_pad)
        steps = max(math.ceil(n_kept / C), 1)
        if max_steps:
            steps = min(steps, max_steps)
        self.kept_words_trained += min(steps * C, n_kept)
        raw_per_step = self._n_tokens / max(math.ceil(n_kept / C), 1)
        # The trainer's own dispatches are one monitor a program; the
        # client calls between them have CLIENT_ISSUE_*, the waits
        # TABLE_WAIT.
        behind = None   # the last block's own loss: see TRAINER_BLOCK_PACE
        for g0 in range(0, steps, G):
            with monitor("TRAINER_BLOCK_IDS"):
                real = min(G, steps - g0)
                if G == 1:
                    lr = np.float32(model.learning_rate())
                    model._account_words(raw_per_step)
                else:
                    # Padded tail blocks get lr 0 (and a fully masked
                    # base, in the program) — exact no-ops, so the
                    # program set stays one fixed shape.
                    lr = np.zeros(G, np.float32)
                    for i in range(real):
                        lr[i] = model.learning_rate()
                        model._account_words(raw_per_step)
                # in_ids: centers (skip-gram) or the band (CBOW);
                # out_ids: [band | negs] / [centers | negs] / Huffman
                # path rows — see _block_ids_fn / _block_ids_fn_hs;
                # leading G axis when grouped.
                with device_lock.guard():
                    in_ids, out_ids, pmask = device_lock.settle(
                        self._loop_ids(
                            kept_pad, ksent_pad, self._aux_tables[0],
                            self._aux_tables[1], key, np.int32(g0),
                            np.int32(real), n_kept_dev))
            # Device-key pulls ride the worker->server actor round
            # trip; the replies are lazy device arrays (no host
            # sync).
            mid_in = in_table.get_rows_device_async(in_ids)
            mid_out = out_table.get_rows_device_async(out_ids)
            in_table.wait(mid_in)
            out_table.wait(mid_out)
            # Per-server shard tuples; the step jit sums them
            # (fused — no separate reassembly dispatch on
            # multi-server tables). The rows are given no name here:
            # once the step has run, nothing holds them.
            with monitor("TRAINER_BLOCK_STEP"), device_lock.guard():
                (d_v, d_u, loss, _, loss_acc,
                 pair_acc) = device_lock.settle(self._loop_step(
                    tuple(in_table.take_device_row_parts()),
                    tuple(out_table.take_device_row_parts()),
                    pmask, lr, self._inv_workers, loss_acc, pair_acc))
            # Fire-and-forget pushes: waiters self-reap on ack; the
            # trailing drain below bounds the epoch.
            model._pending_pushes.append(
                (in_table, in_table.add_rows_async(in_ids, d_v)))
            model._pending_pushes.append(
                (out_table, out_table.add_rows_async(out_ids, d_u)))
            # The host stays one block ahead of the device and no
            # further: a block in flight holds its ids, rows and deltas
            # (90 MB at 32768 centers of 128 columns), and where the
            # device is the pace the runtime's queue would let them
            # pile up. The wait gives the actors the GIL for the Adds
            # just issued; where the host is the pace it returns at
            # once.
            with monitor("TRAINER_BLOCK_PACE"):
                if behind is not None:
                    jax.block_until_ready(behind)
            behind = self.last_loss = loss  # callers sync on last_loss
            if block_hook is not None:
                block_hook(raw_per_step * real)
        model._drain_pushes()
        model._flush_word_count()
        model._in_table.zoo.barrier()
        return float(loss_acc), float(pair_acc)

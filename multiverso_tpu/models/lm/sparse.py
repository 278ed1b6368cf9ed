"""Attention over keys that a learned indexer selects (DeepSeek-V3.2's
sparse attention, as ``model_type: KeyeVL2``'s ``sa_config`` sizes it):
the fifth family's attention, ``LMConfig.selection == "topk_indexer"``.
For a layer's input ``x`` [T, hidden] at positions ``pos`` [3, T], ``h =
RMSNorm(x)``, ``sg`` = stop-gradient:

    q, k, v      grouped-query, q and k normed by head, turned by the
                 sectioned rotary (``Rotary.sections``):
                 ``model.attention_inputs``
    qI_j = sg(h) W_qI   [index_heads of index_dim]    ``index_inputs``
    kI   = LayerNorm(sg(h) W_kI)   one for all heads; both turned by
                 position row 0, every lane pair
    w    = sg(h) W_w index_heads^-1/2 index_dim^-1/2
    I[t, s] = sum_j w[t, j] relu(qI_j[t] . kI[s]),  s <= t   ``index_scores``
    S_t  = the ``index_topk`` keys s <= t of largest I[t, s], every s <= t
           while t < index_topk; equal scores: the earlier key first.
           EXACT: a search for each query's threshold, no approximate
           top-k                                       ``select``
    o_i[t] = sum_{s in S_t} softmax_{s in S_t}(q_i[t] . k[s] d^-1/2) v[s]
    L_I  = sum_t KL( P_t || softmax_{s in S_t} I[t, s] ),
           P_t = sg( sum_i softmax_i[t, .] ) / heads   ``index_loss_vjp``

**A loss that lives inside the layer.** ``L_I`` reaches ``W_qI``, ``W_kI``,
``W_w`` and the LayerNorm's two alone and the language-model loss reaches
none of them (their input is detached, the selection has no gradient). The
layer is ``model.layer_vjp``'s, as every layer of the plain residual: its
attention (``model.attention_vjp``) takes from here the selection and the
attention over it (``selection_vjp``, ``attention_vjp``), and its pull
makes the indexer's gradients from what the layer recomputes, whatever
``dy`` is, and hands ``L_I`` back beside them: ``(dx, matrix gradients,
small gradients, L_I)``; the trainer pushes all of a layer's gradients in
the same Adds.

**What is kept.** Between a layer's forward and backward program: the
layer's input, as for every layer. Inside a program the selection is a
bool a (query, key) pair, cut into ``index_tile`` x ``index_tile`` tiles
[q tiles, key tiles, tile, tile]; no [T, T] float array outlives a block
of queries.

**On a TPU** the selection is one Pallas kernel a layer a pass
(sparse_kernels.select_tiles: index scores, the search and the tiles for
a block of queries whose scores stay in fast memory; ``search_by``'s own
lines), and the attention is the library's splash kernel under a DYNAMIC
mask: the tiles under the diagonal are its ``partial_mask_blocks`` and its
three small tables say which tile to skip among them (``_mask_infos``,
built on the device in the step), so a tile with no selected pair is not
visited and every other tile is computed whole; the divergence is one
kernel too (sparse_kernels.index_loss_tiles under ``index_loss_vjp``).
Elsewhere ``index_scores`` + ``search`` a block of queries at a time, the
same sums over the dense mask, and the divergence a block of queries at a
time in ``jax.numpy``. Pallas is imported here and in sparse_kernels.py
alone, and these modules only where a configuration names a selection.

Scopes: ``mv.lm.indexer`` (the indexer's projections, norm, rotary, and
the scores), ``mv.lm.select`` (the search and the tiles),
``mv.lm.attn.sparse`` (+ ``.kernel``), ``mv.lm.indexer.loss`` (the
divergence and its gradients); the feed-forward's as everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import model as lm
from .model import BF16, F32, LMConfig

SCOPE = "mv.lm.attn.sparse"
INDEX_SCOPE = "mv.lm.indexer"
SELECT_SCOPE = "mv.lm.select"
LOSS_SCOPE = "mv.lm.indexer.loss"
INDEX_MATRICES = ("wq_index", "wk_index", "w_index")
INDEX_SMALL = ("index_norm_g", "index_norm_b")  # the LayerNorm's scale, offset
#: What a forward program counts of a layer's selection, after
#: ``model.layer_stats``' own: selected pairs, causal pairs, tiles with a
#: selected pair, tiles under the diagonal.
COUNTS = 4
QUERY_BLOCK = 256   # queries a block of the scores and the search
LOSS_BLOCK = 64     # queries a block of the divergence (every head's
#                     probabilities of a block are alive at once)


def shapes(cfg: LMConfig) -> dict:
    """The indexer's five tensors as the server stores them."""
    h, wide = cfg.hidden, cfg.index_heads * cfg.index_dim
    return {"wq_index": (h, wide), "wk_index": (h, cfg.index_dim),
            "w_index": (h, cfg.index_heads),
            "index_norm_g": (cfg.index_dim,), "index_norm_b": (cfg.index_dim,)}


def layernorm(x, scale, offset, eps):
    x = x.astype(F32)
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * scale + offset


def index_inputs(cfg: LMConfig, mats, sinks, norm, h, pos=None):
    """The indexer's three from the layer's normed input ``h`` (already
    detached): ``(qI [T, heads, dim], kI [T, dim], w [T, heads])``
    float32. ``norm`` is the LayerNorm's ``(scale, offset)``; ``pos`` the
    layer's positions, of which the indexer takes row 0."""
    t, heads, dim = h.shape[0], cfg.index_heads, cfg.index_dim
    at = () if pos is None else (np.asarray(pos).reshape(-1, t)[0],)
    qi = lm.mm(h, mats["wq_index"], sinks["wq_index"]).reshape(t, heads, dim)
    ki = layernorm(lm.mm(h, mats["wk_index"], sinks["wk_index"]), *norm,
                   cfg.eps)
    qi = lm._rotary(qi, cfg.rope_theta, *at)
    ki = lm._rotary(ki[:, None, :], cfg.rope_theta, *at)[:, 0]
    w = lm.mm(h, mats["w_index"], sinks["w_index"]) * (heads * dim) ** -0.5
    return qi, ki, w


def index_scores(qi, ki, w):
    """``I`` for a block of queries against every key: qi [R, heads,
    dim] and ki [T, dim] rounded to bfloat16 for the product, the rest
    float32: [R, T]."""
    s = jnp.einsum("rjd,kd->rjk", qi.astype(BF16), ki.astype(BF16),
                   preferred_element_type=F32)
    return jnp.sum(w[:, :, None] * jax.nn.relu(s), axis=1)


def _blocks(t: int, rows: int = QUERY_BLOCK) -> int:
    """Queries a block: ``rows`` where they divide the sequence, else all
    (a test's size)."""
    return rows if t % rows == 0 else t


def _causal(first, rows: int, t: int):
    return jnp.arange(t)[None, :] <= first + jnp.arange(rows)[:, None]


def sortable(scores):
    """float32 -> int32 of the same TOTAL order: ``-0.0`` below ``+0.0``,
    as ``jax.lax.top_k`` (the reference's selection) orders them."""
    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)


_LOWEST = np.int32(-2 ** 31)     # what no float32's key is: not a key


def search_by(count, topk: int, rows: int, width: int):
    """Each row's threshold for its ``topk`` largest keys, ties to the
    earlier column, EXACTLY: ``(thr, cut)`` [rows, 1] int32, with which
    ``chosen`` tells a key. ``count(holds)`` is how many of a row's keys
    ``holds(keys, their columns)`` says yes to, [rows, 1]: over a dense
    array here (``search``), over a scratch a tile at a time in the
    kernel (sparse_kernels.py); the lines below are both's. The threshold
    is built a bit at a time from the top (the largest value that ``topk``
    keys reach: the sign first, signed compares, then the 31 bits below);
    a row without ``topk`` candidates ends at the lowest value, below
    every candidate, and takes them all. Of the keys EQUAL to the
    threshold the earliest that are still wanted: a search for the column
    by which their count is reached, run only where some row is tied
    beyond what it wants."""
    def reach(thr):
        return count(lambda k, col: k >= thr)

    base = jnp.where(reach(jnp.zeros((rows, 1), jnp.int32)) >= topk,
                     jnp.int32(0), jnp.int32(_LOWEST))

    def bit(b, base):
        cand = base + jnp.left_shift(jnp.int32(1), 30 - b)
        return jnp.where(reach(cand) >= topk, cand, base)

    thr = jnp.maximum(jax.lax.fori_loop(0, 31, bit, base),
                      jnp.int32(_LOWEST + 1))
    want = topk - count(lambda k, col: k > thr)
    tied = count(lambda k, col: k == thr)
    bits = max(int(width - 1).bit_length(), 1)
    everything = jnp.full((rows, 1), 2 ** bits - 1, jnp.int32)

    def cut_search():
        def cut_bit(i, cut):
            cand = cut - jnp.left_shift(jnp.int32(1), bits - 1 - i)
            have = count(lambda k, col: (k == thr) & (col <= cand))
            return jnp.where(have >= want, cand, cut)

        return jax.lax.fori_loop(0, bits, cut_bit, everything)

    return thr, jax.lax.cond(jnp.max(tied - want) > 0, cut_search,
                             lambda: everything)


def chosen(keys, col, thr, cut):
    """Whether a key at column ``col`` is among its row's ``topk``."""
    return (keys > thr) | ((keys == thr) & (col <= cut))


def search(keys, topk: int):
    """Each row's ``topk`` largest of ``keys`` [R, T] int32 (``_LOWEST``:
    not a candidate), ties to the earlier column: bool [R, T]
    (``search_by`` over the dense array)."""
    r, t = keys.shape
    col = jnp.arange(t, dtype=jnp.int32)[None, :]
    thr, cut = search_by(
        lambda holds: jnp.sum(holds(keys, col).astype(jnp.int32), -1,
                              keepdims=True), topk, r, t)
    return chosen(keys, col, thr, cut)


def select_block(cfg: LMConfig, scores, first):
    """``S_t`` for a block of queries ``first ..`` with their ``scores``
    [R, T]: bool [R, T]."""
    causal = _causal(first, *scores.shape)
    keys = jnp.where(causal, sortable(scores), _LOWEST)
    return search(keys, cfg.index_topk) & causal


def _tiled(mask, tile: int):
    """[T, T] -> [q tiles, key tiles, tile, tile]."""
    n = mask.shape[0] // tile
    return mask.reshape(n, tile, n, tile).swapaxes(1, 2)


def _untiled(tiles):
    n, _, tile, _ = tiles.shape
    return tiles.swapaxes(1, 2).reshape(n * tile, n * tile)


def tile_of(cfg: LMConfig, t: int) -> int:
    return min(cfg.index_tile, t)


def _on_chip(tile: int) -> bool:
    """Whether the kernels' forms are taken: on a TPU, whole lanes."""
    return jax.default_backend() == "tpu" and tile % 128 == 0


def select(cfg: LMConfig, qi, ki, w):
    """The selection of one sequence from the indexer's three: ``(tiles
    bool [q tiles, key tiles, tile, tile], counts int32 [COUNTS])``. On a
    TPU one kernel for scores, search and tiles, a block of queries in
    fast memory (sparse_kernels.select_tiles, under ``mv.lm.select``);
    elsewhere ``index_scores`` and ``search`` a block of queries at a
    time."""
    t = qi.shape[0]
    rows, tile = _blocks(t), tile_of(cfg, t)
    assert t % rows == 0 and t % tile == 0, (t, rows, tile)
    if _on_chip(tile):
        from . import sparse_kernels
        with jax.named_scope(SELECT_SCOPE):
            tiles = sparse_kernels.select_tiles(
                qi, ki, w, topk=cfg.index_topk, tile=tile) != 0
    else:
        def one(args):
            qib, wb, first = args
            with jax.named_scope(INDEX_SCOPE):
                scores = index_scores(qib, ki, wb)
            with jax.named_scope(SELECT_SCOPE):
                return select_block(cfg, scores, first)

        sel = jax.lax.map(one, (
            qi.reshape((t // rows, rows) + qi.shape[1:]),
            w.reshape(t // rows, rows, -1), jnp.arange(0, t, rows)))
        with jax.named_scope(SELECT_SCOPE):
            tiles = _tiled(sel.reshape(t, t), tile)
    with jax.named_scope(SELECT_SCOPE):
        n = t // tile
        live = jnp.sum(jnp.any(tiles, axis=(2, 3)), dtype=jnp.int32)
        counts = jnp.stack([
            jnp.sum(tiles, dtype=jnp.int32), jnp.int32(t * (t + 1) // 2),
            live, jnp.int32(n * (n + 1) // 2)])
    return tiles, counts


# -- the attention over a selection ------------------------------------------------

def _dense_attention(q, k, v, mask):
    """``model.blockwise_attention``'s sums under a dense ``mask`` [T, T]:
    ``(o, each head's row logsumexp [groups, per, T])``."""
    s = jnp.einsum("ghqd,gkd->ghqk", q, k, preferred_element_type=F32)
    s = jnp.where(mask, s, -jnp.inf)
    lse = jax.nn.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    o = jnp.einsum("ghqk,gkd->ghqd", p.astype(BF16), v,
                   preferred_element_type=F32)
    return o.astype(q.dtype), lse


@functools.lru_cache(maxsize=None)
def _splash_library():
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as kernel,
        splash_attention_mask_info as mask_info)
    return kernel, mask_info


def _mask_infos(tiles):
    """The splash kernel's description of a dynamic mask, for every head
    alike (a leading 1), from its tiles: ``(forward and dq, dkv)``. As the
    library's ``process_dynamic_mask`` makes them from a dense [heads, T,
    T] mask, without that array and with the tiles UNDER THE DIAGONAL
    alone among ``partial_mask_blocks`` (the kernel takes them as int32:
    every tile would be a [T, T] int32 array twice over): ``block_mask`` 0
    | 1 | 2 (a tile empty, mixed, full: an empty one is not visited, a
    full one reads no mask), ``mask_next`` the tile's place among the
    blocks, ``data_next`` the key (dkv: query) tile to fetch."""
    _, mask_info = _splash_library()
    nq, nk, bq, bk = tiles.shape
    full, empty = jnp.all(tiles, (2, 3)), ~jnp.any(tiles, (2, 3))
    block = jnp.where(full, 2, jnp.where(empty, 0, 1)).astype(jnp.int8)[None]
    under = np.argwhere(np.tril(np.ones((nq, nk), bool)))   # (q, key) tiles
    place = np.zeros((1, nq, nk), np.int32)
    place[0, under[:, 0], under[:, 1]] = np.arange(len(under))
    mask_next = jnp.where(block == 1, jnp.asarray(place), 0).astype(
        jnp.int16 if len(under) <= np.iinfo(np.int16).max else jnp.int32)
    small = jnp.int8 if max(nq, nk) <= np.iinfo(np.int8).max else jnp.int16
    blocks = tiles[under[:, 0], under[:, 1]]

    def info(data_next, blocks):
        return mask_info.MaskInfo(
            data_next=jnp.where(block == 0, 0, data_next).astype(small),
            mask_next=mask_next, block_mask=block, partial_mask_blocks=blocks,
            q_sequence=None, is_dynamic_mask=True)

    keys = jnp.broadcast_to(jnp.arange(nk, dtype=jnp.int32)[None, None, :],
                            (1, nq, nk))
    queries = jnp.broadcast_to(jnp.arange(nq, dtype=jnp.int32)[None, :, None],
                               (1, nq, nk))
    return info(keys, blocks), info(queries, blocks.swapaxes(1, 2))


def _splash_static(tile: int):
    kernel, _ = _splash_library()
    sizes = kernel.BlockSizes(
        block_q=tile, block_kv=tile, block_kv_compute=tile, block_q_dkv=tile,
        block_kv_dkv=tile, block_kv_dkv_compute=tile, block_q_dq=tile,
        block_kv_dq=tile)
    # in the order of the library's backward rule's leading arguments
    return dict(save_residuals=False, mask_value=kernel.DEFAULT_MASK_VALUE,
                is_mqa=True, block_sizes=sizes, residual_checkpoint_name=None,
                mask_function=None, attn_logits_soft_cap=None,
                interpret=False)


def attention_vjp(q, k, v, tiles):
    """The attention proper over the selection ``tiles`` and its pull:
    ``(o, lse [groups, per, T], pull)``, q [groups, per group, T, d]
    (scaled), k and v [groups, T, d], bfloat16. On a TPU the splash
    kernel's own forward and backward rules, called as its custom
    differentiation calls them (the forward's row logsumexp is the
    divergence's to read); elsewhere ``jax.vjp`` of the dense sums."""
    tile = tiles.shape[2]
    if not _on_chip(tile):
        mask = _untiled(tiles)
        (o, lse), pull = jax.vjp(
            lambda q, k, v: _dense_attention(q, k, v, mask), q, k, v)
        return o, lse, lambda do: pull((do, jnp.zeros_like(lse)))
    kernel, _ = _splash_library()
    forward, dkv = _mask_infos(tiles)
    how = _splash_static(tile)

    def one(q, k, v):       # a key-value head and its query heads
        o, res = kernel._splash_attention_fwd(
            forward, forward, dkv, q, k, v, None, None, **how)
        return o, res[6]

    o, lse = jax.vmap(one)(q, k, v)

    def pull_one(q, k, v, o, lse, do):
        res = (q, k, v, None, None, o, lse, forward, dkv)
        return kernel._splash_attention_bwd(*how.values(), res, do)[3:6]

    return o, lse, lambda do: jax.vmap(pull_one)(q, k, v, o, lse, do)


# -- the divergence and its gradients --------------------------------------------

def target_of(probabilities, heads: int):
    """``P_t`` from the heads' probabilities summed over the heads: scaled
    to sum to 1 over ``S_t`` (each head's sum to 1 there)."""
    return probabilities / heads


def _block_divergence(qi, ki, w, sel, q, k, lse):
    """A block of queries' part of ``L_I``: qi [R, heads, dim], ki [T,
    dim], w [R, heads], ``sel`` [R, T]; the attention's q [groups, per, R,
    d] (scaled, bfloat16), k [groups, T, d] and each head's row logsumexp
    [groups, per, R] to make the target from, which gets no gradient."""
    heads = q.shape[0] * q.shape[1]
    s = jnp.einsum("ghqd,gkd->ghqk", q, k, preferred_element_type=F32)
    target = jax.lax.stop_gradient(target_of(jnp.sum(
        jnp.where(sel, jnp.exp(s - lse[..., None]), 0.0), axis=(0, 1)),
        heads))
    log_pi = jax.nn.log_softmax(
        jnp.where(sel, index_scores(qi, ki, w), -jnp.inf), axis=-1)
    live = sel & (target > 0)
    return jnp.sum(jnp.where(
        live, target * (jnp.log(jnp.where(live, target, 1.0))
                        - jnp.where(live, log_pi, 0.0)), 0.0))


def index_loss_vjp(qi, ki, w, tiles, q, k, lse):
    """``L_I`` of one sequence and its gradients to the indexer's three:
    ``(L_I, (d qI, d kI, d w))``, a block of queries at a time (the
    block's scores and probabilities are recomputed in its pull: no [T, T]
    float array). On a TPU one kernel, a tile of queries against the key
    tiles under the diagonal (sparse_kernels.index_loss_tiles)."""
    if _on_chip(tiles.shape[2]):
        from . import sparse_kernels
        return sparse_kernels.index_loss_tiles(qi, ki, w, tiles, q, k, lse)
    t = qi.shape[0]
    rows = _blocks(t, LOSS_BLOCK)
    n = t // rows

    def one(carry, args):
        qib, wb, selb, qb, lseb = args
        loss, (d_qi, d_ki, d_w) = jax.value_and_grad(
            lambda qib, ki, wb: _block_divergence(qib, ki, wb, selb, qb, k,
                                                  lseb), (0, 1, 2))(
                                                      qib, ki, wb)
        total, sum_ki = carry
        return (total + loss, sum_ki + d_ki), (d_qi, d_w)

    (loss, d_ki), (d_qi, d_w) = jax.lax.scan(
        one, (jnp.zeros((), F32), jnp.zeros(ki.shape, F32)), (
            qi.reshape((n, rows) + qi.shape[1:]), w.reshape(n, rows, -1),
            _untiled(tiles).reshape(n, rows, t),
            q.reshape(q.shape[:2] + (n, rows, -1)).transpose(2, 0, 1, 3, 4),
            lse.reshape(lse.shape[:2] + (n, rows)).transpose(2, 0, 1, 3)))
    return loss, (d_qi.reshape(qi.shape), d_ki, d_w.reshape(w.shape))


# -- what the layer's attention takes ----------------------------------------------

#: What the indexer reads of the layer's normed input: none of the
#: gradients that reach the indexer go on into the layer's input.
detached = jax.lax.stop_gradient


def _index_norm(small):
    return tuple(small[n] for n in INDEX_SMALL)


def selection_of(cfg: LMConfig, mats, small, x, pos=None):
    """The selection a layer makes of its input ``x`` alone, and what it
    made it of: ``(tiles, counts, (qI, kI, w))`` (``select``,
    ``index_inputs``); what a check hands its reference."""
    h = detached(lm.rmsnorm(x, small["norm_attn"], cfg.eps))
    inputs = index_inputs(cfg, mats, lm._zeros_like_f32(
        {n: mats[n] for n in INDEX_MATRICES}), _index_norm(small), h, pos)
    return select(cfg, *inputs) + (inputs,)


def selection_vjp(cfg: LMConfig, mats, sinks, small, x, pos=None):
    """What a layer's attention (``model.attention_vjp``) takes of this
    module beside ``attention_vjp``: the selection the layer makes of its
    input ``x`` and the hook for the loss that lives inside it: ``(tiles,
    counts, pull)``. ``pull(q, k, lse)``, the attention's scaled queries,
    keys and row logsumexp, gives ``(L_I, dx, the indexer's matrix
    gradients, its small gradients, the attention norm's)``: ``dx`` and
    the norm's are what ``detached`` lets through (nothing)."""
    with jax.named_scope(INDEX_SCOPE):
        (qi, ki, w), pull_index = jax.vjp(
            lambda s, norm, g, x: index_inputs(
                cfg, mats, s, norm, detached(lm.rmsnorm(x, g, cfg.eps)), pos),
            {n: sinks[n] for n in INDEX_MATRICES}, _index_norm(small),
            small["norm_attn"], x)
    tiles, counts = select(cfg, qi, ki, w)

    def pull(q, k, lse):
        with jax.named_scope(LOSS_SCOPE):
            index_loss, d_index = index_loss_vjp(qi, ki, w, tiles, q, k, lse)
        with jax.named_scope(INDEX_SCOPE):
            d_mats, d_norm, d_norm_attn, dx = pull_index(d_index)
        return (index_loss, dx, d_mats, dict(zip(INDEX_SMALL, d_norm)),
                d_norm_attn)

    return tiles, counts, pull

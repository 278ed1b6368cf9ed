"""The Pallas kernels of sparse.py on a TPU: the selection (index scores,
the exact search and the tiles for a block of queries in one pass, the
block's scores in fast memory: ``select_tiles``) and the divergence
(``index_loss_tiles``, described where it stands).

``select_tiles``: a grid step takes ``rows`` queries. Their index scores
against every key before the block's last query are computed a key tile at
a time (sixteen products of [rows, 64] by [64, tile], relu, the weights,
summed in float32) and kept as sortable int32 keys [rows, T] in a scratch;
a key tile past the diagonal is never computed. The search
(sparse.search_by's own lines, a bit of the threshold a pass) counts over
that scratch a tile at a time, so a pass reads fast memory alone and only
the tiles under the diagonal; the search among equal scores runs only
where a row's threshold is tied beyond what it still wants. What leaves is
the selection, an int8 a (query, key) pair, written straight into the tile
layout the attention's mask tables take ([q tiles, key tiles, tile, tile]).

No [T, T] float array exists at all: a block's scores live and die in
fast memory, in both kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import sparse

F32 = jnp.float32
ROWS = 128                      # queries a grid step


def _kernel(qi_ref, w_ref, ki_ref, out_ref, keys_ref, *, topk: int,
            tile: int, heads: int, dim: int, rows: int):
    step = pl.program_id(0)
    first = step * rows
    n_tiles = keys_ref.shape[1] // tile
    # key tiles that hold a key at or before the block's last query
    live = (first + rows - 1) // tile + 1
    row = first + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)

    def scores_of(kj, carry):
        at = pl.multiple_of(kj * tile, tile)
        keys = ki_ref[pl.ds(at, tile), :]
        acc = jnp.zeros((rows, tile), F32)
        for j in range(heads):
            s = jax.lax.dot_general(
                qi_ref[:, j * dim:(j + 1) * dim], keys,
                (((1,), (1,)), ((), ())), preferred_element_type=F32)
            acc = acc + w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
        keys_ref[:, pl.ds(at, tile)] = jnp.where(
            at + lane <= row, sparse.sortable(acc), sparse._LOWEST)
        return carry

    jax.lax.fori_loop(0, live, scores_of, 0)

    def count(holds):
        """sparse.search_by's count over the live tiles: [rows, 1]."""
        def more(kj, acc):
            at = pl.multiple_of(kj * tile, tile)
            return acc + holds(keys_ref[:, pl.ds(at, tile)],
                               at + lane).astype(jnp.int32)

        acc = jax.lax.fori_loop(0, live, more,
                                jnp.zeros((rows, tile), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)

    thr, cut = sparse.search_by(count, topk, rows, keys_ref.shape[1])
    for kj in range(n_tiles):
        @pl.when(kj < live)
        def _(kj=kj):
            keys = keys_ref[:, kj * tile:(kj + 1) * tile]
            out_ref[kj] = sparse.chosen(keys, kj * tile + lane, thr,
                                        cut).astype(jnp.int32).astype(jnp.int8)

        @pl.when(kj >= live)
        def _(kj=kj):
            out_ref[kj] = jnp.zeros((rows, tile), jnp.int8)


@functools.partial(jax.jit, static_argnames=("topk", "tile", "interpret"))
def select_tiles(qi, ki, w, *, topk: int, tile: int, interpret=False):
    """sparse.select's tiles from the indexer's three (qi [T, heads, dim]
    and ki [T, dim] float32, rounded to bfloat16 here; w [T, heads]
    float32): int8 [q tiles, key tiles, tile, tile]."""
    t, heads, dim = qi.shape
    rows = min(ROWS, tile)
    assert t % tile == 0 and tile % rows == 0, (t, tile, rows)
    n, per = t // tile, tile // rows
    kernel = functools.partial(_kernel, topk=topk, tile=tile, heads=heads,
                               dim=dim, rows=rows)
    return pl.pallas_call(
        kernel,
        grid=(t // rows,),
        in_specs=[pl.BlockSpec((rows, heads * dim), lambda i: (i, 0)),
                  pl.BlockSpec((rows, heads), lambda i: (i, 0)),
                  pl.BlockSpec((t, dim), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((None, n, rows, tile),
                               lambda i: (i // per, 0, i % per, 0)),
        out_shape=jax.ShapeDtypeStruct((n, n, tile, tile), jnp.int8),
        scratch_shapes=[pltpu.VMEM((rows, t), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        name="mv_lm_select_tiles",
    )(qi.reshape(t, heads * dim).astype(jnp.bfloat16), w,
      ki.astype(jnp.bfloat16))


# -- the divergence ---------------------------------------------------------------
# sparse.index_loss_vjp on a TPU. A grid step is (a tile of ``rows`` queries,
# a walk, a key tile); only the key tiles at or under the diagonal are
# visited (a step past it does nothing and fetches nothing: its blocks'
# indices stay at the diagonal's). The FIRST walk makes each row's logsumexp
# of ``I`` over its selected keys (a running maximum and sum, the index
# scores alone). The SECOND, a visit: the attention heads' ``exp(q . k -
# lse)`` summed where selected, the target (sparse.target_of), ``I`` by
# select_tiles' lines with each index head's scores kept in a scratch,
# ``log pi``, the visit's part of ``L_I``, ``dI = pi R - target`` (``R``, the
# row's sum of the target, is ``target_of(heads, heads)``: each head's
# probabilities sum to 1 over the selected keys, whose logsumexp ``lse``
# is), and back through ``w`` and the relu: ``d w`` (summed by lane block in
# a scratch, by row at the tile's last visit), ``d qI`` (summed in its output
# block, which stays while the walk lasts) and ``d kI`` (summed over the
# WHOLE grid in one resident [T, dim] block, every axis sequential).
# bfloat16 goes into every matrix product and float32 comes out; the
# probabilities of a visit never leave fast memory.

LOSS_ROWS = 256                 # queries a grid step of the divergence
_NOT_YET = -1e30                # a row's running maximum before a selected key


def _loss_kernel(qi_ref, w_ref, lse_ref, q_ref, ki_ref, k_ref, sel_ref,
                 loss_ref, dqi_ref, dki_ref, dw_ref, top_ref, sum_ref,
                 scores_ref, dw_acc, *, tile: int, rows: int, heads: int,
                 dim: int):
    iq, walk, kj = (pl.program_id(i) for i in range(3))
    # key tiles that hold a key at or before the tile's last query
    live = (iq * rows + rows - 1) // tile + 1
    groups, per = q_ref.shape[:2]
    nt = (((1,), (1,)), ((), ()))       # a product with the keys' rows

    def index_scores(keep: bool):
        """select_tiles' lines: ``I`` [rows, tile] float32."""
        keys = ki_ref[...]
        acc = jnp.zeros((rows, tile), F32)
        for j in range(heads):
            s = jax.lax.dot_general(qi_ref[:, j * dim:(j + 1) * dim], keys,
                                    nt, preferred_element_type=F32)
            if keep:
                scores_ref[j] = s
            acc = acc + w_ref[:, j:j + 1] * jnp.maximum(s, 0.0)
        return acc

    @pl.when((iq == 0) & (walk == 0) & (kj == 0))
    def _():
        loss_ref[0, 0] = 0.0
        dki_ref[...] = jnp.zeros(dki_ref.shape, F32)

    @pl.when((walk == 0) & (kj == 0))
    def _():
        top_ref[...] = jnp.full(top_ref.shape, _NOT_YET, F32)
        sum_ref[...] = jnp.zeros(sum_ref.shape, F32)

    @pl.when((walk == 0) & (kj < live))
    def _():        # each row's logsumexp of I over its selected keys
        sel = sel_ref[...].astype(jnp.int32) != 0
        scores = jnp.where(sel, index_scores(False), _NOT_YET)
        top = jnp.maximum(top_ref[...],
                          jnp.max(scores, axis=1, keepdims=True))
        sum_ref[...] = sum_ref[...] * jnp.exp(top_ref[...] - top) + jnp.sum(
            jnp.where(sel, jnp.exp(scores - top), 0.0), axis=1, keepdims=True)
        top_ref[...] = top

    @pl.when((walk == 1) & (kj == 0))
    def _():
        dqi_ref[...] = jnp.zeros(dqi_ref.shape, F32)
        dw_acc[...] = jnp.zeros(dw_acc.shape, F32)

    @pl.when((walk == 1) & (kj < live))
    def _():
        sel = sel_ref[...].astype(jnp.int32) != 0
        summed = jnp.zeros((rows, tile), F32)   # the heads' probabilities
        for g in range(groups):
            keys = k_ref[g]
            for h in range(per):
                s = jax.lax.dot_general(q_ref[g, h], keys, nt,
                                        preferred_element_type=F32)
                at = g * per + h
                summed = summed + jnp.exp(s - lse_ref[:, at:at + 1])
        target = jnp.where(sel, sparse.target_of(summed, groups * per), 0.0)
        log_pi = index_scores(True) - (top_ref[...] + jnp.log(sum_ref[...]))
        held = sel & (target > 0)
        loss_ref[0, 0] += jnp.sum(jnp.where(
            held, target * (jnp.log(jnp.where(held, target, 1.0)) - log_pi),
            0.0))
        # each head's probabilities sum to 1 over the row's selected keys
        whole = sparse.target_of(jnp.full((1, 1), groups * per, F32),
                                 groups * per)
        d_scores = jnp.where(sel, jnp.exp(log_pi) * whole - target, 0.0)
        keys = ki_ref[...]
        d_keys = jnp.zeros((tile, dim), F32)
        for j in range(heads):
            s = scores_ref[j]
            part = d_scores * jnp.maximum(s, 0.0)
            dw_acc[j] += sum(part[:, c:c + 128] for c in range(0, tile, 128))
            ds = jnp.where(s > 0, d_scores * w_ref[:, j:j + 1], 0.0).astype(
                jnp.bfloat16)
            dqi_ref[:, j * dim:(j + 1) * dim] += jax.lax.dot_general(
                ds, keys, (((1,), (0,)), ((), ())),
                preferred_element_type=F32)
            d_keys = d_keys + jax.lax.dot_general(
                ds, qi_ref[:, j * dim:(j + 1) * dim],
                (((0,), (0,)), ((), ())), preferred_element_type=F32)
        at = pl.multiple_of(kj * tile, tile)
        dki_ref[pl.ds(at, tile), :] += d_keys

    @pl.when((walk == 1) & (kj == live - 1))
    def _():
        head = jax.lax.broadcasted_iota(jnp.int32, (rows, heads), 1)
        d_w = jnp.zeros((rows, heads), F32)
        for j in range(heads):
            d_w = jnp.where(head == j,
                            jnp.sum(dw_acc[j], axis=1, keepdims=True), d_w)
        dw_ref[...] = d_w


@functools.partial(jax.jit, static_argnames=("interpret",))
def index_loss_tiles(qi, ki, w, tiles, q, k, lse, *, interpret=False):
    """sparse.index_loss_vjp on a TPU: ``(L_I, (d qI, d kI, d w))`` of one
    sequence from the indexer's three (float32; qi and ki rounded to
    bfloat16 here), the selection's ``tiles`` [q tiles, key tiles, tile,
    tile] and the attention's scaled q [groups, per, T, d], k [groups, T,
    d] (bfloat16) and row logsumexp [groups, per, T]."""
    t, heads, dim = qi.shape
    groups, per, _, d = q.shape
    tile = tiles.shape[2]
    rows = min(LOSS_ROWS, tile)
    assert t % tile == 0 and tile % rows == 0 and tile % 128 == 0, (t, tile)
    n, split = t // tile, tile // rows

    def last(i, kj):    # the key tile a step reads: none past the diagonal
        return jnp.minimum(kj, (i * rows + rows - 1) // tile)

    kernel = functools.partial(_loss_kernel, tile=tile, rows=rows,
                               heads=heads, dim=dim)
    loss, d_qi, d_ki, d_w = pl.pallas_call(
        kernel,
        grid=(t // rows, 2, n),
        in_specs=[
            pl.BlockSpec((rows, heads * dim), lambda i, p, kj: (i, 0)),
            pl.BlockSpec((rows, heads), lambda i, p, kj: (i, 0)),
            pl.BlockSpec((rows, groups * per), lambda i, p, kj: (i, 0)),
            pl.BlockSpec((groups, per, rows, d),
                         lambda i, p, kj: (0, 0, i, 0)),
            pl.BlockSpec((tile, dim), lambda i, p, kj: (last(i, kj), 0)),
            # the first walk reads no attention key: its tile stays
            pl.BlockSpec((groups, tile, d),
                         lambda i, p, kj: (0, p * last(i, kj), 0)),
            pl.BlockSpec((None, None, rows, tile),
                         lambda i, p, kj: (i // split, last(i, kj),
                                           i % split, 0))],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((rows, heads * dim), lambda i, p, kj: (i, 0)),
            pl.BlockSpec((t, dim), lambda i, p, kj: (0, 0)),
            pl.BlockSpec((rows, heads), lambda i, p, kj: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((1, 1), F32),
                   jax.ShapeDtypeStruct((t, heads * dim), F32),
                   jax.ShapeDtypeStruct((t, dim), F32),
                   jax.ShapeDtypeStruct((t, heads), F32)],
        scratch_shapes=[pltpu.VMEM((rows, 1), F32), pltpu.VMEM((rows, 1), F32),
                        pltpu.VMEM((heads, rows, tile), F32),
                        pltpu.VMEM((heads, rows, 128), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=96 * 1024 * 1024),
        interpret=interpret,
        name="mv_lm_index_loss_tiles",
    )(qi.reshape(t, heads * dim).astype(jnp.bfloat16), w,
      lse.reshape(groups * per, t).T, q, ki.astype(jnp.bfloat16), k,
      tiles.astype(jnp.int8))
    return loss[0, 0], (d_qi.reshape(qi.shape), d_ki, d_w)

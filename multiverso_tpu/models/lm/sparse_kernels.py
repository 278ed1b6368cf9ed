"""The Pallas kernel of sparse.py's selection on a TPU: index scores,
the exact search and the selection's tiles for a block of queries, in one
pass with the block's scores in fast memory (``select_tiles``).

A grid step takes ``rows`` queries. Their index scores against every key
before the block's last query are computed a key tile at a time (sixteen
products of [rows, 64] by [64, tile], relu, the weights, summed in
float32) and kept as sortable int32 keys [rows, T] in a scratch; a key
tile past the diagonal is never computed. The search (sparse.search_by's
own lines, a bit of the threshold a pass) counts over that scratch a tile
at a time,
so a pass reads fast memory alone and only the tiles under the diagonal;
the search among equal scores runs only where a row's threshold is tied
beyond what it still wants. What leaves is the selection, an int8 a
(query, key) pair, written straight into the tile layout that the
attention's mask tables take ([q tiles, key tiles, tile, tile]).

No [T, T] float array exists at all: a block's scores live and die in
the scratch.
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

"""The Pallas TPU kernel of row_scatter.py: one chunk of sorted positions
read-modify-written into the table. Imported only when a program is
built from source (``jax.experimental.pallas`` takes a second and more
to import; row_scatter.py keeps built programs beside the compile
cache)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .row_scatter import TILE

#: Row DMAs issued a trip of the kernel's loops over a tile's run ends.
_ISSUE = 8


def _kernel(code, ends, rows, ends_next, rows_next, counts, delta, carry_in,
            table_in, table, carry, tbuf, obuf, acc_ref, sem_t, sem_o):
    """One grid step = one tile of sorted positions of one chunk.
    ``delta`` is the tile's delta rows in sorted order; ``ends`` lists
    the tile's run ends and ``rows`` their table rows
    (row_scatter.sorted_runs), ``*_next`` those of the tile after this
    one, ``counts`` every tile's count of them; ``table_in`` is
    ``table`` (aliased); ``carry`` hands the open run's sum to the next
    chunk.

    A row DMA takes the scalar core a dozen cycles to issue, taken or
    predicated off, so the two DMA loops walk the listed ends, not the
    positions, and a tile's reads are issued a tile ahead: only a
    chunk's first tile waits for its rows. The sums go eight positions
    at a time, one aligned sublane block, the running sum broadcast over
    the block's sublanes: no vector operation has a dynamic offset."""
    del table_in
    t = pl.program_id(0)
    last = pl.num_programs(0) - 1
    slot = t % 2
    cols = delta.shape[1]
    sublane = lax.broadcasted_iota(jnp.int32, (8, cols), 0)

    def wait_rows(sem, n):
        # A DMA semaphore counts bytes, so a descriptor of 2**b rows
        # waits for that many one-row copies: n ends' copies in 11 waits.
        n = -(-n // _ISSUE) * _ISSUE
        for b in range(TILE.bit_length() - 1, -1, -1):
            @pl.when((n >> b) & 1 == 1)
            def _():
                pltpu.make_async_copy(table.at[pl.ds(0, 1 << b)],
                                      obuf.at[0, pl.ds(0, 1 << b)],
                                      sem).wait()

    def each_end(ends, rows, n, copy):
        """``copy(position, row).start()`` for the n listed ends, _ISSUE
        to a trip: the list's tail repeats its last end, whose copy
        moves the same bytes again."""
        def trip(i, _):
            for u in range(_ISSUE):
                copy(ends[i * _ISSUE + u], rows[i * _ISSUE + u]).start()
        lax.fori_loop(0, -(-n // _ISSUE), trip, None)

    def read_into(slot):
        return lambda j, row: pltpu.make_async_copy(
            table.at[pl.ds(row, 1)], tbuf.at[slot, pl.ds(j, 1)],
            sem_t.at[slot])

    def fold(i, acc):
        base = pl.multiple_of(i * 8, 8)
        dblk = delta[pl.ds(base, 8), :]
        tblk = tbuf[slot, pl.ds(base, 8), :]
        out = tblk
        for u in range(8):
            du = jnp.broadcast_to(dblk[u:u + 1, :], (8, cols))
            acc = jnp.where(code[base + u] & 2 == 2, du, acc + du)
            out = jnp.where(sublane == u, tblk + acc, out)
        obuf[slot, pl.ds(base, 8), :] = out
        return acc

    @pl.when(t == 0)
    def _():
        acc_ref[...] = carry_in[...]
        each_end(ends, rows, counts[0], read_into(0))

    # No row is read after it was written: a row is written once, at its
    # run's end, and the tile after this one reads the rows of its own.
    @pl.when(t < last)
    def _():
        each_end(ends_next, rows_next, counts[t + 1], read_into(1 - slot))

    # The live positions come first: a tile whose first is dead is dead.
    @pl.when(code[0] >= 0)
    def _():
        wait_rows(sem_t.at[slot], counts[t])
        acc_ref[...] = lax.fori_loop(0, TILE // 8, fold, acc_ref[...])
        each_end(ends, rows, counts[t], lambda j, row: pltpu.make_async_copy(
            obuf.at[slot, pl.ds(j, 1)], table.at[pl.ds(row, 1)],
            sem_o.at[slot]))

    # The tile before's writes are done before their buffer's next use.
    @pl.when(t > 0)
    def _():
        wait_rows(sem_o.at[1 - slot], counts[t - 1])

    @pl.when(t == last)
    def _():
        wait_rows(sem_o.at[slot], counts[t])
        carry[...] = acc_ref[...]


def rmw_chunk(table, code, ends, rows, counts, delta, carry, interpret):
    """One chunk's sorted positions applied to ``table``."""
    cols = table.shape[1]
    tiles = code.shape[0] // TILE
    smem = pl.BlockSpec((TILE,), lambda t: (t,), memory_space=pltpu.SMEM)
    after = pl.BlockSpec((TILE,), lambda t: (jnp.minimum(t + 1, tiles - 1),),
                         memory_space=pltpu.SMEM)
    whole = pl.BlockSpec((8, cols), lambda t: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel, grid=(tiles,),
        in_specs=[smem, smem, smem, after, after,
                  pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((TILE, cols), lambda t: (t, 0)), whole, hbm],
        out_specs=[hbm, whole],
        scratch_shapes=[
            *[pltpu.VMEM((2, TILE, cols), table.dtype)] * 2,
            pltpu.VMEM((8, cols), table.dtype),
            *[pltpu.SemaphoreType.DMA((2,))] * 2,
        ],
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct(carry.shape, carry.dtype)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret, name="mv_row_scatter_add",
    )(code, ends, rows, ends, rows, counts, delta, carry, table)

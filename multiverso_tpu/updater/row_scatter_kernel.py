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


def _kernel(code, delta, carry_in, table_in, table, carry, tbuf, obuf,
            acc_ref, ends, written, sem_t, sem_o):
    """One grid step = one tile of sorted positions of one chunk.
    ``delta`` is the tile's delta rows in sorted order; ``table_in`` is
    ``table`` (aliased); ``carry`` hands the open run's sum to the next
    chunk.

    A row DMA takes the scalar core a dozen cycles to issue, taken or
    predicated off, so the tile's run ends are first listed (``ends``,
    no branch), and the two DMA loops walk the list, not the positions.
    The sums go eight positions at a time, one aligned sublane block,
    the running sum broadcast over the block's sublanes: no vector
    operation has a dynamic offset."""
    del table_in
    t = pl.program_id(0)
    slot = t % 2
    cols = delta.shape[1]
    sublane = lax.broadcasted_iota(jnp.int32, (8, cols), 0)

    def wait_rows(sem, n):
        # A DMA semaphore counts bytes, so a descriptor of 2**b rows
        # waits for that many one-row copies: n copies in 11 waits.
        for b in range(TILE.bit_length() - 1, -1, -1):
            @pl.when((n >> b) & 1 == 1)
            def _():
                pltpu.make_async_copy(table.at[pl.ds(0, 1 << b)],
                                      tbuf.at[pl.ds(0, 1 << b)], sem).wait()

    def list_ends(i, n):
        for u in range(8):
            j = i * 8 + u
            c = code[j]
            ends[n] = j
            n = n + ((c >= 0) & (c & 1 == 1)).astype(jnp.int32)
        return n

    def each_end(n, copy):
        """``copy(position, row).start()`` for the n listed ends, _ISSUE
        to a trip: the list's tail repeats its last end, whose copy
        moves the same bytes again."""
        def trip(i, _):
            for u in range(_ISSUE):
                j = ends[i * _ISSUE + u]
                copy(j, code[j] >> 2).start()
        trips = -(-n // _ISSUE)
        lax.fori_loop(0, trips, trip, None)
        return trips * _ISSUE

    def fold(i, acc):
        base = pl.multiple_of(i * 8, 8)
        dblk = delta[pl.ds(base, 8), :]
        tblk = tbuf[pl.ds(base, 8), :]
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

    written[slot] = 0

    # The live positions come first: a tile whose first is dead is dead.
    @pl.when(code[0] >= 0)
    def _():
        n = lax.fori_loop(0, TILE // 8, list_ends, jnp.int32(0))
        for u in range(_ISSUE):
            ends[n + u] = ends[jnp.maximum(n - 1, 0)]
        reads = each_end(n, lambda j, row: pltpu.make_async_copy(
            table.at[pl.ds(row, 1)], tbuf.at[pl.ds(j, 1)], sem_t))
        wait_rows(sem_t, reads)
        acc_ref[...] = lax.fori_loop(0, TILE // 8, fold, acc_ref[...])
        written[slot] = each_end(n, lambda j, row: pltpu.make_async_copy(
            obuf.at[slot, pl.ds(j, 1)], table.at[pl.ds(row, 1)],
            sem_o.at[slot]))

    # The writes of the tile before this one overlapped this tile's
    # reads; they are done before their buffer is used again.
    @pl.when(t > 0)
    def _():
        wait_rows(sem_o.at[1 - slot], written[1 - slot])

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        wait_rows(sem_o.at[slot], written[slot])
        carry[...] = acc_ref[...]


def rmw_chunk(table, code, delta, carry, interpret):
    """One chunk's sorted positions applied to ``table``."""
    cols = table.shape[1]
    smem = pl.BlockSpec((TILE,), lambda t: (t,), memory_space=pltpu.SMEM)
    rows = pl.BlockSpec((TILE, cols), lambda t: (t, 0))
    whole = pl.BlockSpec((8, cols), lambda t: (0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _kernel,
        grid=(code.shape[0] // TILE,),
        in_specs=[smem, rows, whole, hbm], out_specs=[hbm, whole],
        scratch_shapes=[
            pltpu.VMEM((TILE, cols), table.dtype),
            pltpu.VMEM((2, TILE, cols), table.dtype),
            pltpu.VMEM((8, cols), table.dtype),
            pltpu.SMEM((TILE + _ISSUE,), jnp.int32),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.SemaphoreType.DMA(()),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        out_shape=[jax.ShapeDtypeStruct(table.shape, table.dtype),
                   jax.ShapeDtypeStruct(carry.shape, carry.dtype)],
        input_output_aliases={3: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), has_side_effects=True),
        interpret=interpret, name="mv_row_scatter_add",
    )(code, delta, carry, table)

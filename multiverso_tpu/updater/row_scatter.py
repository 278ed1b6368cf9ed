"""Row scatter-add that writes every table row once: sorted runs, the
duplicates of a row summed before the table is touched, the rows
read-modify-written by DMAs in flight.

XLA's TPU scatter walks the ids one after another, each row's
read-modify-write waiting on the last, whatever the ids are and whatever
it is told about them (73-78 ns a 128-lane float32 row on a v5e,
tools/scatter_bench.py; PERF.md section 6, PR 28). Here the ids are
sorted first with their positions as payload, so that equal ids are
adjacent (a *run*) and ids outside the table's rows sort to the end and
are never visited. What XLA does well it does: beside the sort, the list
of every tile's run ends and their rows (``sorted_runs``: a second sort,
within tiles), and, a chunk of sorted positions at a time, the gather of
the chunk's delta rows into sorted order. The Pallas kernel
(row_scatter_kernel.py) walks a chunk a tile at a time, and its scalar
core does what only it can: it reads the table row of every listed end
into VMEM by a DMA of its own, a tile ahead, sums a run's delta rows in
sorted-position order in float32, adds the sum to the table row and
writes the row back. No table row depends on another, so the reads of
the next tile and the writes of the tile before are in flight while a
tile is summed. A run of one row is ``table + delta``, bit for bit what
XLA's scatter-add gives.

The table is aliased to the kernel's output and stays in HBM. The
temporaries are the sorted ids and positions and the listed ends and
their rows (16 bytes an id) and one chunk of delta rows (``CHUNK`` x
columns), whatever the id count. The table rows do not come by XLA's
gather as the delta rows do: a gather is a position's (8 to 11 ns), the
kernel's read a distinct row's (14 ns to issue), so the gather loses
wherever ids repeat (PERF.md section 6, PR 61).

On a row-sharded table the same code runs under ``shard_map`` over the
table's mesh with the ids and deltas replicated: every chip sorts the
ids with the rows it does not own keyed out of range, so its own runs
come first and are all it visits. No collective.
"""

from __future__ import annotations

import functools
import hashlib
import os
import threading

import jax
import jax.numpy as jnp
from jax import lax
from jax import export
from jax.sharding import PartitionSpec

from ..util import log

#: Sorted positions a grid step handles: its delta rows, table rows and
#: results are VMEM tiles of this many rows (6 x TILE x columns x 4 B).
TILE = 1024
#: Sorted positions whose delta rows are gathered at a time: the Add's
#: temporaries are CHUNK x columns x 4 B (8 MB at 128 columns).
CHUNK = 16 * TILE
#: ``row * 4 + head * 2 + end`` has to fit an int32.
MAX_ROWS = 1 << 29
_FAR = jnp.iinfo(jnp.int32).max


def sorted_runs(row_ids, lo, num_rows):
    """Sort flat ``row_ids`` and mark the runs of equal ids.

    Ids are global; the rows ``[lo, lo + num_rows)`` are the caller's
    (``lo`` may be traced: a shard's first row). Returns ``(code, perm,
    n_live, ends, end_rows, counts)``, all but ``n_live`` and ``counts``
    of the ids' length padded to whole tiles (whole chunks past one
    chunk): ``perm[i]`` is the position in ``row_ids`` of the i-th
    smallest id (ties by position, so the order is a function of the ids
    alone), ``code[i] = (id - lo) * 4 + 2 * head + end`` where ``head``
    and ``end`` say whether i is the first/last position of its run, and
    -1 at the positions of ids outside the rows, which all sort behind
    the ``n_live`` positions of the ids inside. A tile of ``ends`` lists
    the positions within the tile of its live run ends, in order, then
    repeats the last of them (0 where it has none), ``end_rows`` their
    rows ``id - lo`` the same way; ``counts`` has a tile's count of
    them."""
    k = row_ids.shape[0]
    ids = row_ids.astype(jnp.int32)
    local = ids - lo
    inside = (local >= 0) & (local < num_rows)
    key = jnp.where(inside, local, _FAR)
    pos = lax.iota(jnp.int32, k)
    key, perm = lax.sort((key, pos), num_keys=2, is_stable=False)
    step = key[1:] != key[:-1]
    edge = jnp.ones((1,), bool)
    head = jnp.concatenate([edge, step])
    end = jnp.concatenate([step, edge])
    live = key != _FAR
    code = jnp.where(live, key * 4 + head * 2 + end, -1)
    pad = -k % (CHUNK if k > CHUNK else TILE)
    if pad:
        code = jnp.concatenate([code, jnp.full((pad,), -1, jnp.int32)])
        perm = jnp.concatenate([perm, jnp.zeros((pad,), jnp.int32)])
    tiles = code.reshape(-1, TILE)
    at = lax.broadcasted_iota(jnp.int32, tiles.shape, 1)
    is_end = (tiles >= 0) & (tiles & 1 == 1)
    # The ends sort to the front in order, and behind them goes the last.
    ends, end_rows = lax.sort(
        (jnp.where(is_end, at, TILE), jnp.where(is_end, tiles >> 2, 0)),
        num_keys=1, is_stable=False)
    listed = ends < TILE
    ends = jnp.where(listed, ends, jnp.max(ends * listed, 1, keepdims=True))
    end_rows = jnp.where(listed, end_rows,
                         jnp.max(end_rows, 1, keepdims=True))
    return (code, perm, jnp.sum(live, dtype=jnp.int32), ends.reshape(-1),
            end_rows.reshape(-1), jnp.sum(is_end, axis=1, dtype=jnp.int32))


def _artifact(cache_dir: str, *key) -> str:
    """Where a built chunk program is kept: in a directory of its own
    in the process' compile cache, named by everything the program is
    a function of, this package's kernel source included."""
    here = os.path.dirname(os.path.abspath(__file__))
    digest = hashlib.sha256(repr((key, jax.__version__)).encode())
    for name in ("row_scatter.py", "row_scatter_kernel.py"):
        with open(os.path.join(here, name), "rb") as f:
            digest.update(f.read())
    return os.path.join(cache_dir, "mv_row_scatter",
                        digest.hexdigest()[:32] + ".jaxexport")


@functools.lru_cache(maxsize=None)
def _chunk_program(shape, dtype, chunk: int, cache_dir: str):
    """``(table, code, ends, end_rows, counts, rows, carry) -> (table,
    carry)``: the kernel on one chunk of a ``shape`` table, as an
    exported program.

    Tracing and lowering the kernel is Python work of a second or two
    in every process that builds a rows program, beside the import of
    Pallas; the lowered program is a few kilobytes. So where the
    process has a compile cache (``cache_dir``, "" when not), the first
    process writes the program there and the later ones read it back
    and import no Pallas."""
    path = cache_dir and _artifact(cache_dir, shape, dtype, chunk)
    if path and os.path.exists(path):
        try:
            with open(path, "rb") as f:
                return export.deserialize(bytearray(f.read())).call
        except Exception as e:  # a torn or foreign file: build it again
            log.error("row_scatter: %s unreadable (%r); rebuilding", path, e)
    from . import row_scatter_kernel

    def on_chunk(*args):
        with jax.named_scope("mv.update.scatter_add"):
            return row_scatter_kernel.rmw_chunk(*args, interpret=False)

    shaped = jax.ShapeDtypeStruct
    ids, rows = shaped((chunk,), jnp.int32), shaped((chunk, shape[1]), dtype)
    program = export.export(jax.jit(on_chunk), platforms=["tpu"])(
        shaped(shape, dtype), ids, ids, ids,
        shaped((chunk // TILE,), jnp.int32), rows,
        shaped((8, shape[1]), dtype))
    if path:
        scratch = f"{path}.{os.getpid()}.{threading.get_ident()}"
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            with open(scratch, "wb") as f:
                f.write(program.serialize())
            os.replace(scratch, path)
        except OSError as e:  # a cache that cannot be written is no cache
            log.error("row_scatter: %s not kept (%r)", path, e)
    return program.call


def _rmw(table, code, perm, n_live, ends, end_rows, counts, delta,
         interpret):
    """The live positions a chunk at a time: a chunk's delta rows are
    gathered into sorted order (XLA's gather moves a row in 8 ns, a DMA
    of the kernel's own takes 14 to issue), so the temporaries are a
    chunk's rows whatever the id count, and the chunks behind the last
    live position are not visited at all."""
    chunk = min(CHUNK, code.shape[0])
    if interpret:
        from . import row_scatter_kernel
        on_chunk = functools.partial(row_scatter_kernel.rmw_chunk,
                                     interpret=True)
    else:
        on_chunk = _chunk_program(
            table.shape, table.dtype.name, chunk,
            jax.config.jax_compilation_cache_dir or "")

    tiles = chunk // TILE

    def one(i, state):
        table, carry = state
        own = [lax.dynamic_slice(part, (i * chunk,), (chunk,))
               for part in (code, ends, end_rows, perm)]
        return on_chunk(table, *own[:3],
                        lax.dynamic_slice(counts, (i * tiles,), (tiles,)),
                        delta[own[3]], carry)

    carry = jnp.zeros((8, table.shape[1]), table.dtype)
    table, _ = lax.fori_loop(0, -(-n_live // chunk), one, (table, carry))
    return table


def _scatter_add_runs(table, row_ids, delta, lo, interpret):
    with jax.named_scope("mv.update.dedup"):
        runs = sorted_runs(row_ids, lo, table.shape[0])
    with jax.named_scope("mv.update.scatter_add"):
        return _rmw(table, *runs, delta, interpret)


def scatter_add(table, row_ids, delta, mesh=None, interpret=False):
    """``table[row_ids] += delta``, ids outside the table dropped:
    ``table`` is ``[rows, columns]`` float32 with ``columns`` a multiple
    of 128 and fewer than ``MAX_ROWS`` rows, ``row_ids`` flat int32,
    ``delta`` one table-width row an id. With a ``mesh`` of more than
    one device the table is row-sharded over its one axis and each
    device takes the replicated ids and deltas and visits its own rows'
    runs. ``interpret`` runs the kernel in Pallas' interpreter (the CPU
    tests)."""
    if mesh is None or mesh.size == 1:
        return _scatter_add_runs(table, row_ids, delta, 0, interpret)
    (axis,) = mesh.axis_names
    shard_rows = table.shape[0] // mesh.size

    def own_rows(shard, row_ids, delta):
        lo = lax.axis_index(axis) * shard_rows
        return _scatter_add_runs(shard, row_ids, delta, lo, interpret)

    rows = PartitionSpec(axis, None)
    return jax.shard_map(
        own_rows, mesh=mesh, in_specs=(rows, PartitionSpec(),
                                       PartitionSpec()),
        out_specs=rows, check_vma=False)(table, row_ids, delta)

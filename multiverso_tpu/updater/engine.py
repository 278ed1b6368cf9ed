"""Jit-compiled updater engine: in-place donated updates on sharded state.

Binds an ``UpdaterRule`` to a concrete table: owns the optimizer state
(sharded like the table data) and the jitted dense/row update callables.
Donation (``donate_argnums``) lets XLA update the table buffers in place in
HBM — the TPU equivalent of the reference server's in-place OpenMP loops
(ref: src/updater/updater.cpp:24-31).

Row-sparse calls are padded to power-of-two bucket sizes so XLA compiles a
small, bounded set of scatter programs instead of one per distinct row
count (the host-variable-shape hazard called out in SURVEY.md §7).

The rows programs end in the rule's scatter-add, which takes one of two
forms by what is static in the call (``rules.fast_rows``; no flag): on a
TPU, for a float32 table whose stored row is whole 128-lane tiles and
``rules.FAST_MIN_IDS`` ids or more, the ids are sorted, equal ids'
deltas summed in float32 in the order of their positions, and every
row read and written once by ``row_scatter.py``'s kernel, on a
row-sharded table under ``shard_map`` with each device visiting its own
rows; otherwise XLA's scatter applies the ids as they come. Every
dispatch counts ``UPDATE_ROWS_FAST`` or ``UPDATE_ROWS_XLA``.

A large HOST delta is padded into a staging buffer the engine keeps
(``Staging``; docs/MEMORY.md "Send side of an Add"): a request copies
its rows into the head of a bucket-shaped array that already exists and
allocates nothing. Every host delta that enters ``pad_rows`` counts
``UPDATE_PAD_STAGED`` or ``UPDATE_PAD_FRESH``.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import numpy as np

from ..sharding import mesh as meshlib
from .options import AddOption
from ..util.dashboard import count, monitor
from .rules import UpdaterRule, create_rule, fast_rows

_DEFAULT_HYP = AddOption().hyper_array()

#: Why a device-key row Add is refused, for the engine's CHECK and the
#: worker table's (which raises in the caller's thread).
DEVICE_KEYS_REFUSED = (
    "device-key row adds under -updater_type=%s: duplicate ids in one "
    "request must sum, and this rule updates its state once a unique "
    "row from one duplicate's delta (default, sgd and adam take them)")


def bucket_size(n: int, minimum: int = 8) -> int:
    """Smallest power of two >= n (>= minimum)."""
    size = minimum
    while size < n:
        size *= 2
    return size


#: Staging buffers an engine keeps per bucket shape. A request whose
#: first is still being read by the runtime takes the second; where
#: neither is free it pads into a fresh array and waits for nothing.
STAGING_BUFFERS = 2

#: Padded deltas under glibc's default mmap threshold keep ``np.pad``:
#: the heap recycles an array that small, and from this size a fresh
#: array is a fresh mapping, its page faults and its munmap.
STAGING_MIN_BYTES = 128 * 1024


def _zeros_no_client_adopts(shape, dtype) -> np.ndarray:
    """A zeroed array that starts 16 bytes past a 64-byte boundary
    (where glibc's large blocks start anyway). XLA's CPU client takes a
    64-byte-aligned host array as the device buffer itself, zero copy,
    and such an upload reads ready while the program has yet to read the
    memory; an array that no client can adopt is copied by every client,
    and the copy's readiness says the host memory has been read."""
    nbytes = int(np.prod(shape)) * dtype.itemsize
    raw = np.zeros(nbytes + 64, np.uint8)
    skip = (16 - raw.ctypes.data) % 64
    return raw[skip:skip + nbytes].view(dtype).reshape(shape)


class StagingBuffer:
    """One bucket-shaped host array and what guards it. ``array`` holds
    the last request's ``filled`` rows, then zeros. ``guard`` is the
    device array the last fill was uploaded as: it is no program's
    donated argument, and once it is ready the runtime has read
    ``array``, which may then be filled again."""

    __slots__ = ("array", "filled", "guard")

    def __init__(self, shape, dtype):
        self.array = _zeros_no_client_adopts(shape, dtype)
        self.filled = 0
        self.guard = None

    def free(self) -> bool:
        if self.guard is not None and self.guard.is_ready():
            self.guard = None       # and the device's copy with it
        return self.guard is None

    def fill(self, delta: np.ndarray) -> None:
        k = delta.shape[0]
        np.copyto(self.array[:k], delta)
        if k < self.filled:         # the tail stays zero
            self.array[k:self.filled] = 0
        self.filled = k

    def upload(self):
        self.guard = jax.device_put(self.array)
        return self.guard


class Staging:
    """The staging buffers of one engine: at most ``STAGING_BUFFERS`` per
    padded shape it has seen, each allocated when first needed and kept
    (host memory, not HBM)."""

    def __init__(self):
        self._buffers = {}

    def take(self, shape, dtype) -> Optional[StagingBuffer]:
        """A buffer of ``shape`` that nothing reads any more; None for a
        small one or where every buffer is still being read. Never
        waits."""
        if int(np.prod(shape)) * dtype.itemsize < STAGING_MIN_BYTES:
            return None
        buffers = self._buffers.setdefault((shape, dtype), [])
        for buffer in buffers:
            if buffer.free():
                return buffer
        if len(buffers) < STAGING_BUFFERS:
            buffers.append(StagingBuffer(shape, dtype))
            return buffers[-1]
        return None


class UpdateEngine:
    """Applies a rule to a table's device array with donated buffers."""

    def __init__(self, rule: Optional[UpdaterRule], shape, dtype,
                 num_workers: int, sharding=None):
        self.rule = rule if rule is not None else create_rule(dtype=dtype)
        self.shape = tuple(shape)
        self.dtype = np.dtype(dtype)
        state = self.rule.init_state(self.shape, self.dtype, num_workers)
        if state is not None and sharding is not None:
            # Optimizer state lives shard-aligned with the data; the
            # per-worker leading axis (adagrad) is replicated. A state of
            # several parts (adam's two moments and its step count) is
            # placed part by part: a table-shaped part like the table, a
            # smaller one (the count) on every device.
            state = jax.tree_util.tree_map(
                lambda part: jax.device_put(
                    part, _state_sharding(part, sharding)), state)
        self._state = state
        # The rows form's scatter-add picks its path by the table's
        # shape, dtype, mesh and the request's id count (rules.py
        # fast_rows): the sorted-runs kernel on a TPU, under shard_map
        # where the mesh has several devices, XLA's scatter otherwise.
        self._mesh = getattr(sharding, "mesh", None)
        self.rule.mesh = self._mesh
        # Host deltas are staged for a table on one device. Over several
        # the compiled program places its host argument, each device its
        # part; an array uploaded ahead of the call would sit on one.
        self._staging = Staging() \
            if self._mesh is None or self._mesh.size == 1 else None

        # Table storage is padded to the mesh shard count (uneven shardings
        # are not device_put-able) and possibly to the 128-lane tile width
        # in the last dim (sub-lane rows scatter ~25x slower on v5e);
        # deltas arrive logical-sized and are zero-extended *inside* the
        # jit so XLA fuses the pad into the update — no host-side copy.
        def pad_cols(data, delta):
            """Zero-extend the delta's LAST dim to the storage width."""
            if delta.ndim >= 2 and data.shape[-1] != delta.shape[-1]:
                pad = [(0, 0)] * (delta.ndim - 1) \
                    + [(0, data.shape[-1] - delta.shape[-1])]
                delta = jax.numpy.pad(delta, pad)
            return delta

        # The named scopes mark the programs' steps in a device trace
        # (benchmark/lib/xplane.py reduce sums device time by them, for
        # the benchmark and for tools/trace_spans.py): the pads,
        # the rule, and inside a rule's rows form the scatter-add
        # (rules.py). They change no operation and no program's name.
        def dense_padded(data, st, delta, hyp, worker_id):
            with jax.named_scope("mv.update.pad"):
                delta = pad_cols(data, delta)
                if data.shape[0] != delta.shape[0]:
                    pad = ((0, data.shape[0] - delta.shape[0]),) \
                        + ((0, 0),) * (delta.ndim - 1)
                    delta = jax.numpy.pad(delta, pad)
            with jax.named_scope("mv.update.rule"):
                return self.rule.dense(data, st, delta, hyp, worker_id)

        def pad_row_count(row_ids, delta):
            """Zero-extend a [k, ...] delta to the padded id count —
            in-jit, so a device delta costs no separate pad program
            (one more per-dispatch launch cost, not measured on the
            current machine)."""
            if delta.ndim >= 2 and row_ids.ndim == 1 \
                    and delta.shape[0] != row_ids.shape[0]:
                pad = ((0, row_ids.shape[0] - delta.shape[0]),) \
                    + ((0, 0),) * (delta.ndim - 1)
                delta = jax.numpy.pad(delta, pad)
            return delta

        def rows_padded(data, st, row_ids, delta, hyp, worker_id):
            with jax.named_scope("mv.update.pad"):
                delta = pad_row_count(row_ids, pad_cols(data, delta))
            with jax.named_scope("mv.update.rule"):
                return self.rule.rows(data, st, row_ids, delta, hyp,
                                      worker_id)

        self._pad_cols = pad_cols
        self._pad_row_count = pad_row_count
        self._dense = jax.jit(dense_padded, donate_argnums=(0, 1))
        self._rows = jax.jit(rows_padded, donate_argnums=(0, 1))
        self._rows_bounded = {}
        self._rows_gather = {}

    def apply_dense(self, data, delta, option: Optional[AddOption] = None):
        hyp, worker_id = _unpack(option)
        with monitor("UPDATE_DISPATCH"):  # a host delta's upload too
            data, self._state = self._dense(data, self._state, delta,
                                            hyp, worker_id)
        return data

    def apply_rows(self, data, row_ids, delta,
                   option: Optional[AddOption] = None, bounds=None):
        """``row_ids`` int32[k], ``delta`` [k, ...]; pads to a power-of-two
        bucket with out-of-range indices (dropped by scatter). Device
        row_ids (any shape, delta shaped ids.shape + row shape) skip
        padding — the caller's shapes are already fixed, so each distinct
        caller shape compiles exactly once. ``bounds=(offset, n)`` maps
        GLOBAL row ids to this shard's local indices INSIDE the jit
        (foreign rows go out-of-range and drop) — one dispatch, not a
        separate masking op per request."""
        hyp, worker_id = _unpack(option)
        from ..core.blob import is_device_array
        staged = None
        if is_device_array(row_ids):
            # Device-key ids may carry duplicates that no caller can
            # take out without a host sync. default/sgd scatter-add
            # them and adam sums equal ids' deltas before it touches a
            # row; momentum, adagrad and dcasgd write their state once
            # per unique row from ONE duplicate's delta, which would
            # drop the others from the state silently.
            from ..util.log import CHECK
            CHECK(self.rule.sums_duplicates, DEVICE_KEYS_REFUSED
                  % self.rule.name)
        else:
            row_ids, delta, staged = pad_rows(
                row_ids, delta, self.shape[0], self._staging)
        self._count_path(row_ids)
        rows_fn = self._rows if bounds is None \
            else self._bounded_rows_fn(bounds)
        with monitor("UPDATE_DISPATCH"):  # a host delta's upload too
            if staged is not None:
                # Uploaded here and not by the call, for the guard: the
                # program's own outputs are donated by the next dispatch.
                delta = staged.upload()
            data, self._state = rows_fn(data, self._state, row_ids, delta,
                                        hyp, worker_id)
        return data

    def _count_path(self, row_ids) -> None:
        """One count a dispatch, by the path its shapes chose."""
        n_ids = int(np.prod(np.shape(row_ids)))
        count("UPDATE_ROWS_FAST" if fast_rows(
            self.shape, self.dtype, n_ids, self._mesh)
            else "UPDATE_ROWS_XLA")

    def _bounded_rows_fn(self, bounds):
        fn = self._rows_bounded.get(bounds)
        if fn is None:
            import jax.numpy as jnp
            ofs, n = bounds
            padded = self.shape[0]
            rule_rows = self.rule.rows

            def rows_fn(data, st, row_ids, delta, hyp, worker_id):
                # Foreign rows map to the padded row count: out of range
                # for the scatter (drop) — NOT merely offset-shifted,
                # which could land a foreign row inside this shard's
                # padding where a later masked gather would read it.
                row_ids = jnp.where((row_ids >= ofs) & (row_ids < ofs + n),
                                    row_ids - ofs, padded)
                with jax.named_scope("mv.update.pad"):
                    delta = self._pad_cols(data, delta)
                with jax.named_scope("mv.update.rule"):
                    return rule_rows(data, st, row_ids, delta, hyp,
                                     worker_id)

            fn = jax.jit(rows_fn, donate_argnums=(0, 1))
            self._rows_bounded[bounds] = fn
        return fn

    def apply_rows_gather(self, data, row_ids, delta, option,
                          get_ids, n_col: int):
        """FUSED row update + row gather in ONE compiled program: apply
        the delta, then gather ``get_ids`` from the UPDATED table. Each
        separately dispatched program pays a per-dispatch launch cost
        (not measured on the current machine); for the sparse dirty-row
        roundtrip (add, then dirty get) fusing the pair halves the
        launches. Both id
        vectors MUST arrive padded to power-of-two buckets
        (out-of-range drops/zero-fills); the delta pads in-jit like
        apply_rows. Device-mirror ids are held to the same contract —
        an exact-k mirror would recompile the fused program for every
        distinct k instead of once per bucket width."""
        hyp, worker_id = _unpack(option)
        from ..util.log import CHECK
        k = int(np.shape(row_ids)[0])
        CHECK(k == bucket_size(k),
              "apply_rows_gather ids must be bucket-padded "
              "(pad_ids on the host, a pad_ids-built device mirror)")
        self._count_path(row_ids)
        fn = self._rows_gather.get(n_col)
        if fn is None:
            rule_rows = self.rule.rows
            pad_cols = self._pad_cols
            pad_row_count = self._pad_row_count

            def f(data, st, row_ids, delta, hyp, wid, get_ids):
                with jax.named_scope("mv.update.pad"):
                    delta = pad_row_count(row_ids, pad_cols(data, delta))
                with jax.named_scope("mv.update.rule"):
                    data, st = rule_rows(data, st, row_ids, delta, hyp,
                                         wid)
                with jax.named_scope("mv.table.gather"):
                    values = data.at[get_ids].get(
                        mode="fill", fill_value=0)[..., :n_col]
                return data, st, values

            fn = jax.jit(f, donate_argnums=(0, 1))
            self._rows_gather[n_col] = fn
        with monitor("UPDATE_DISPATCH"):
            data, self._state, values = fn(data, self._state, row_ids,
                                           delta, hyp, worker_id, get_ids)
        return data, values

    @property
    def state(self):
        return self._state


def _unpack(option: Optional[AddOption]) -> Tuple[np.ndarray, np.ndarray]:
    if option is None:
        return _DEFAULT_HYP, np.int32(0)
    return option.hyper_array(), np.int32(max(option.worker_id, 0))


def pad_ids(row_ids, num_rows: int) -> np.ndarray:
    """Pad a row-id vector to the next bucket size with an out-of-range
    sentinel (gather fills zeros, scatter drops)."""
    row_ids = np.asarray(row_ids, dtype=np.int32)
    b = bucket_size(row_ids.shape[0])
    if b != row_ids.shape[0]:
        row_ids = np.concatenate(
            [row_ids, np.full(b - row_ids.shape[0], num_rows,
                              dtype=np.int32)])
    return row_ids


def pad_rows(row_ids, delta, num_rows: int,
             staging: Optional[Staging] = None):
    """Pad (row_ids, delta) to the next bucket size; padding rows index
    out-of-range so scatter drops them and gather fills zeros. DEVICE
    deltas pass through logical-sized — the engine's rows jit extends
    them to the id count internally (a separate device pad would cost a
    full program launch per add).

    A HOST delta always leaves here as an array the table owns, k rows
    of it and then zero rows, at a bucket-sized k too. The jitted
    program that takes it returns before the runtime has read the host
    buffer (it reads the numpy argument's memory after the call,
    docs/MEMORY.md "Send side of an Add"), and the buffer may be the
    caller's own delta, which the caller may overwrite once the Add is
    acknowledged. With ``staging`` a large delta is copied into a buffer
    the engine keeps, returned third for the caller to ``upload()`` (the
    upload guards the buffer's next fill); a small one, or one that
    finds every buffer still being read, gets a fresh array as without
    ``staging``, and None third."""
    row_ids = np.asarray(row_ids, dtype=np.int32)
    k = row_ids.shape[0]
    b = bucket_size(k)
    if b != k:
        row_ids = np.concatenate(
            [row_ids, np.full(b - k, num_rows, dtype=np.int32)])
    staged = None
    from ..core.blob import is_device_array
    if not is_device_array(delta):
        delta = np.asarray(delta)
        with monitor("UPDATE_PAD_ROWS"):
            if staging is not None:
                staged = staging.take((b,) + delta.shape[1:], delta.dtype)
            if staged is not None:
                staged.fill(delta)
                delta = staged.array
            elif b != k:
                pad = ((0, b - k),) + ((0, 0),) * (delta.ndim - 1)
                delta = np.pad(delta, pad)
            else:
                delta = np.array(delta)
        count("UPDATE_PAD_FRESH" if staged is None else "UPDATE_PAD_STAGED")
    return row_ids, delta, staged


@functools.lru_cache(maxsize=None)
def _state_sharding_cached(ndim_state: int, data_sharding):
    mesh = data_sharding.mesh
    spec = data_sharding.spec
    # Prepend replicated axes for any leading state dims beyond the data's.
    pad = ndim_state - len(spec)
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(*([None] * pad + list(spec))))


def _state_sharding(state, data_sharding):
    if np.ndim(state) < len(data_sharding.spec):     # a count: everywhere
        return meshlib.replicated(data_sharding.mesh)
    return _state_sharding_cached(np.ndim(state), data_sharding)

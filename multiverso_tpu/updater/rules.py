"""Server-side optimizer rules as pure jittable functions.

TPU-native re-design of the reference's updater family
(ref: include/multiverso/updater/, src/updater/updater.cpp:23-58). The
reference applies per-element OpenMP loops on the server thread; here each
rule is a pure function over whole (sharded) arrays, jit-compiled once per
table with donated buffers so updates happen in-place in HBM, and a `rows`
variant for row-sparse traffic whose last step is a row scatter-add
(``scatter_add``: the sorted-runs kernel of row_scatter.py on a TPU for
``FAST_MIN_IDS`` ids or more, XLA's scatter otherwise; ``fast_rows`` is
the rule, read from shapes, dtype and the table's mesh alone).

Hyperparameters arrive as a traced float32[4] array ``hyp`` =
[momentum, learning_rate, rho, lambda] (from ``AddOption.hyper_array``) so
changing them never triggers recompilation; ``worker_id`` is a traced int32
scalar indexing per-worker optimizer state.

Formulas (and deviations):

- default: ``data += delta`` (ref: src/updater/updater.cpp:24-31)
- sgd: ``data -= delta`` — caller pre-multiplies the learning rate
  (ref: include/multiverso/updater/sgd_updater.h:15-19)
- momentum: ``smooth = m*smooth + (1-m)*delta; data -= smooth``
  (ref: include/multiverso/updater/momentum_updater.h:17-26)
- adagrad: per-worker accumulator ``G[w] += (delta/lr)^2``;
  ``data -= rho * (delta/lr) / sqrt(G[w] + e)``. NOTE: the reference's
  implementation (adagrad_updater.h:23-41) mutates a *copy* of the
  accumulator row and *subtracts* the squared gradient — two bugs that make
  its accumulator never persist and go negative; we implement the intended
  AdaGrad semantics its structure describes (per-worker historic squared
  gradients, lr-normalized delta, rho-scaled step).

- dcasgd: delay-compensated ASGD — see DCASGDRule (the reference ships
  this updater permanently disabled; here it works).
- adam: the delta is the raw gradient; the rule owns both moments and
  the table's step count — see AdamRule (no reference twin).

Duplicate row indices within one row-sparse Add compound correctly for
default/sgd (scatter-add: XLA's form adds them one after another, the
sorted-runs form adds their float32 sum, taken in the order of their
positions in the request, once); for momentum/adagrad/dcasgd the state update
applies once per unique row (the reference's sequential loop compounds
instead — callers there dedupe rows per block, e.g. WordEmbedding's
DataBlock). adam sums the deltas of equal ids before it touches a row
(``distinct_rows``), so it alone of the stateful rules takes device-key
row Adds (``UpdaterRule.sums_duplicates``).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import row_scatter
from ..util import log
from ..util.configure import define_string, get_flag

define_string("updater_type", "default",
              "server updater: default / sgd / momentum / adagrad / "
              "dcasgd / adam")

ADAGRAD_EPS = 1e-6  # ref: adagrad_updater.h:18


def _safe_lr(lr):
    """Rules that recover the gradient as delta/lr must not turn a
    user-supplied learning_rate=0 into inf/NaN written silently into the
    table — clamp away from zero (delta is 0 whenever lr is)."""
    return jnp.maximum(lr, jnp.asarray(1e-12, lr.dtype))


#: The stored row the sorted-runs kernel takes: one 128-lane tile. On a
#: wider table the TPU's compiler refuses its one-row DMAs (fast_rows).
KERNEL_LANES = 128

#: Static id count from which the sorted-runs kernel beats XLA's
#: scatter on a v5e (tools/scatter_bench.py --small; PERF.md section 6,
#: PR 28): under it the sort costs more than the serial rows it saves.
FAST_MIN_IDS = 2048


def _platform(mesh) -> str:
    """Where the table lives: its mesh's devices, or with no mesh the
    default backend's."""
    device = jax.devices()[0] if mesh is None else mesh.devices.flat[0]
    return device.platform


def fast_rows(shape, dtype, n_ids: int, mesh=None) -> bool:
    """The path a row Add of ``n_ids`` ids on a table of this stored
    shape takes, from what is static: the sorted-runs kernel
    (row_scatter.py) when the table is on a TPU, float32, two
    dimensional with a stored row of exactly one 128-lane tile, and the
    id count is at or above ``FAST_MIN_IDS``; XLA's scatter otherwise.
    A wider row (a language model's embedding, 20 tiles) takes XLA's
    scatter: the kernel's one-row DMAs are refused by the TPU's compiler
    on a table more than one tile wide ("slice shape along dimension 0
    must be aligned to tiling (8)"; tests/test_row_scatter_tpu_compile.py).
    ``mesh`` is the table's."""
    return (_platform(mesh) == "tpu" and np.dtype(dtype) == np.float32
            and len(shape) == 2 and shape[1] == KERNEL_LANES
            and shape[0] < row_scatter.MAX_ROWS
            and n_ids >= FAST_MIN_IDS)


def scatter_add(data, row_ids, step, mesh=None):
    """``data[row_ids] += step``, out-of-range ids dropped: the last
    operation of every rule's rows form and every row update of the
    word2vec trainers' group programs (device_train.py), under one
    scope name so that a device trace shows it apart from the
    arithmetic before it.

    One algorithm in two forms, chosen by ``fast_rows``. Small id
    counts, other dtypes and other backends take XLA's scatter, which
    applies the ids as they come. Otherwise the ids are sorted and every
    tile's run ends listed (scope ``mv.update.dedup``, all XLA's), the
    deltas of equal ids are summed in float32 in the order of their
    positions in ``row_ids``, and each table row is read and written
    once, by one DMA each of the kernel's own (scope
    ``mv.update.scatter_add``: XLA's gather of the delta rows into
    sorted order, and the kernel): a row named once gets ``row + delta``
    bit for bit as XLA's scatter gives it; a row named n times gets
    ``row + (d1 + ... + dn)`` where XLA's gives ``((row + d1) + ...)``."""
    n_ids = int(np.prod(row_ids.shape))
    if not fast_rows(data.shape, data.dtype, n_ids, mesh):
        with jax.named_scope("mv.update.scatter_add"):
            return data.at[row_ids].add(step, mode="drop")
    ids = row_ids.reshape(n_ids).astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + data.shape[0], ids)  # as .at[] wraps
    step = step.reshape(n_ids, data.shape[1]).astype(data.dtype)
    return row_scatter.scatter_add(data, ids, step, mesh)


class UpdaterRule:
    """A pure update rule: (data, state, delta, hyp, worker_id) -> (data, state)."""

    name = "base"
    # True when init_state returns None — i.e. duplicate row ids in one
    # scatter-add SUM correctly, requests fold before one apply (server
    # fusion) and rows migrate live. A stateful rule is refused all
    # three; what it may still take is device-key row Adds, if
    # ``sums_duplicates``.
    stateless = True
    # True when the rows form gives duplicate ids in one request the
    # update their summed delta would get: every stateless rule, and a
    # stateful one that sums equal ids' deltas before it touches a row
    # (adam). Device keys cannot be deduplicated by the caller, so the
    # worker's and the engine's CHECKs on device-key row Adds read this
    # (through create_rule). momentum, adagrad and dcasgd update their
    # state once per unique row from ONE of the duplicates' deltas and
    # stay refused.
    sums_duplicates = True
    # The table's mesh, set by the engine that binds the rule to a
    # table: the rows form's scatter-add picks its path by it.
    mesh = None

    def init_state(self, shape, dtype, num_workers: int) -> Any:
        return None

    def dense(self, data, state, delta, hyp, worker_id):
        raise NotImplementedError

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        """Row-sparse update. ``row_ids`` may be padded with out-of-range
        indices (>= data.shape[0]); padded entries are dropped by XLA
        scatter semantics."""
        raise NotImplementedError


class DefaultRule(UpdaterRule):
    name = "default"

    def dense(self, data, state, delta, hyp, worker_id):
        return data + delta, state

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        return scatter_add(data, row_ids, delta, self.mesh), state


class SGDRule(UpdaterRule):
    name = "sgd"

    def dense(self, data, state, delta, hyp, worker_id):
        return data - delta, state

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        return scatter_add(data, row_ids, -delta, self.mesh), state


class MomentumRule(UpdaterRule):
    name = "momentum"
    stateless = False
    sums_duplicates = False

    def init_state(self, shape, dtype, num_workers: int):
        return jnp.zeros(shape, dtype)

    def dense(self, data, state, delta, hyp, worker_id):
        m = hyp[0].astype(data.dtype)
        smooth = m * state + (1 - m) * delta
        return data - smooth, smooth

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        m = hyp[0].astype(data.dtype)
        smooth_rows = (m * state.at[row_ids].get(mode="fill", fill_value=0)
                       + (1 - m) * delta)
        state = state.at[row_ids].set(smooth_rows, mode="drop")
        return scatter_add(data, row_ids, -smooth_rows, self.mesh), state


class AdaGradRule(UpdaterRule):
    name = "adagrad"
    stateless = False
    sums_duplicates = False

    def init_state(self, shape, dtype, num_workers: int):
        # Per-worker historic squared gradients, leading worker axis
        # (ref: adagrad_updater.h:17-21).
        return jnp.zeros((num_workers,) + tuple(shape), dtype)

    def dense(self, data, state, delta, hyp, worker_id):
        lr, rho = hyp[1].astype(data.dtype), hyp[2].astype(data.dtype)
        grad = delta / _safe_lr(lr)
        g_sqr = state[worker_id] + grad * grad
        step = rho * grad * jax.lax.rsqrt(g_sqr + ADAGRAD_EPS)
        return data - step, state.at[worker_id].set(g_sqr)

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        lr, rho = hyp[1].astype(data.dtype), hyp[2].astype(data.dtype)
        grad = delta / _safe_lr(lr)
        g_rows = state.at[worker_id, row_ids].get(mode="fill", fill_value=0)
        g_sqr = g_rows + grad * grad
        step = rho * grad * jax.lax.rsqrt(g_sqr + ADAGRAD_EPS)
        state = state.at[worker_id, row_ids].set(g_sqr, mode="drop")
        return scatter_add(data, row_ids, -step, self.mesh), state


class DCASGDRule(UpdaterRule):
    """Delay-compensated ASGD (Zheng et al. 2017). The reference declares
    this updater but ships it permanently disabled — the source file is
    absent and the ENABLE_DCASGD macro is never defined
    (ref: src/updater/updater.cpp:2-9,53-55, CMakeLists.txt:9); this is a
    working implementation of the hook.

    The server keeps a per-worker parameter backup; a delta arriving from
    worker m (delta = lr * g, the sgd convention) is compensated for the
    staleness it accumulated since that worker's last update:

        w -= lr * (g + lambda * g * g * (w - backup[m]));  backup[m] = w

    The backup starts at zero, so each worker's FIRST push compensates
    against the origin — with the second-order term scaled by lambda this
    is benign, and every later push uses the true snapshot."""

    name = "dcasgd"
    stateless = False
    sums_duplicates = False

    def init_state(self, shape, dtype, num_workers: int):
        return jnp.zeros((num_workers,) + tuple(shape), dtype)

    def dense(self, data, state, delta, hyp, worker_id):
        lr, lam = hyp[1].astype(data.dtype), hyp[3].astype(data.dtype)
        grad = delta / _safe_lr(lr)
        comp = lam * grad * grad * (data - state[worker_id])
        new = data - (delta + lr * comp)
        return new, state.at[worker_id].set(new)

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        lr, lam = hyp[1].astype(data.dtype), hyp[3].astype(data.dtype)
        grad = delta / _safe_lr(lr)
        rows_now = data.at[row_ids].get(mode="fill", fill_value=0)
        bak = state.at[worker_id, row_ids].get(mode="fill", fill_value=0)
        step = delta + lr * lam * grad * grad * (rows_now - bak)
        # Scatter-ADD the step so duplicate row ids compound their deltas
        # (matching sgd; the compensation term is evaluated against the
        # same pre-update rows for each duplicate, like momentum/adagrad's
        # once-per-unique-row state). The backup records one step for a
        # duplicated row — second-order staleness error, documented.
        data = scatter_add(data, row_ids, -step, self.mesh)
        state = state.at[worker_id, row_ids].set(rows_now - step,
                                                 mode="drop")
        return data, state



def distinct_rows(row_ids, delta, num_rows: int):
    """The distinct ids of a row request and the summed delta of each:
    ``(ids, sums)``, both of the request's length. ``ids`` holds the
    distinct in-range ids in rising order and then ``num_rows`` (out of
    range: a gather fills, a scatter drops); ``sums[i]`` is the float32
    sum of the deltas of ``ids[i]``'s positions. One sort of the ids and
    one scatter-add with sorted indices (scope ``mv.update.dedup``); no
    host value, whatever the ids."""
    n = int(np.prod(row_ids.shape))
    ids = row_ids.reshape(n).astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + num_rows, ids)       # as .at[] wraps
    key = jnp.where((ids >= 0) & (ids < num_rows), ids, num_rows)
    key, perm = jax.lax.sort((key, jax.lax.iota(jnp.int32, n)), num_keys=2)
    head = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    run = jnp.cumsum(head, dtype=jnp.int32) - 1     # the run of each position
    # a run's id, written by each of its positions alike
    unique = jnp.full((n,), num_rows, jnp.int32).at[run].set(
        key, indices_are_sorted=True)
    sums = jnp.zeros((n,) + delta.shape[row_ids.ndim:], delta.dtype).at[
        run].add(delta.reshape((n,) + delta.shape[row_ids.ndim:])[perm],
                 indices_are_sorted=True)
    return unique, sums


#: A stored row at or over which ``AdamRule.rows`` keeps the formula out of
#: the writes' fusions: at 4,096 float32 columns the TPU's compiler gives the
#: scatter fused with it 16.12 MB of scoped vector memory, of the 16 it has,
#: and refuses the program (3,584 columns compile whole). The rows then lie
#: in memory once more before they are written: 18.3 ms an Add of 16,384 ids
#: at [24576, 4096], where 2,304 columns take 9.2 whole and 10.3 so (my chip
#: run, PR 60); the same rows in two column blocks took 397.
WIDE_ROW_BYTES = 16384


class AdamRule(UpdaterRule):
    """Adam (Kingma & Ba 2015) in the server: the Add carries the raw
    gradient ``g`` and the rule owns the rest.

    State, three parts ``(m, v, t)``: first and second moment, each
    shaped, typed and sharded like the table, and ``t``, an int32 scalar
    that counts the table's Adds (dense or rows alike), replicated.
    Hyperparameters ride ``AddOption`` as every rule's do, in the slots
    it has: ``momentum`` = beta1, ``learning_rate`` = lr, ``rho`` =
    beta2, ``lambda_`` = eps. No weight decay.

        t += 1;  m = b1 m + (1-b1) g;  v = b2 v + (1-b2) g g
        w -= lr * (m / (1-b1^t)) / (sqrt(v / (1-b2^t)) + eps)

    The rows form is *lazy* Adam: the deltas of equal ids are summed
    first (``distinct_rows``), then each distinct row named, its ``m``
    row and its ``v`` row are read, updated by the formula above with
    the table's ``t``, and written once; a row not named keeps its
    weights and its moments (no decay of ``m`` toward zero), and ``t``
    still advances. One worker's state: ``worker_id`` is not read."""

    name = "adam"
    stateless = False
    sums_duplicates = True

    def init_state(self, shape, dtype, num_workers: int):
        return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                jnp.zeros((), jnp.int32))

    @staticmethod
    def _step(w, m, v, g, t, hyp):
        b1, lr, b2, eps = (hyp[i].astype(w.dtype) for i in range(4))
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        tf = t.astype(w.dtype)
        m_hat = m / (1 - b1 ** tf)
        v_hat = v / (1 - b2 ** tf)
        return w - lr * m_hat / (jnp.sqrt(v_hat) + eps), m, v

    def dense(self, data, state, delta, hyp, worker_id):
        m, v, t = state
        t = t + 1
        data, m, v = self._step(data, m, v, delta, t, hyp)
        return data, (m, v, t)

    def rows(self, data, state, row_ids, delta, hyp, worker_id):
        m, v, t = state
        t = t + 1
        with jax.named_scope("mv.update.dedup"):
            ids, g = distinct_rows(row_ids, delta, data.shape[0])

        def read(x):
            return x.at[ids].get(mode="fill", fill_value=0,
                                 indices_are_sorted=True)

        def write(x, rows):
            if x.ndim == 2 and x.shape[1] * x.dtype.itemsize \
                    >= WIDE_ROW_BYTES:      # a wide row: no fused formula
                rows = jax.lax.optimization_barrier(rows)
            return x.at[ids].set(rows, mode="drop",
                                 indices_are_sorted=True)

        w_rows, m_rows, v_rows = self._step(read(data), read(m), read(v),
                                            g, t, hyp)
        with jax.named_scope("mv.update.scatter_add"):
            return write(data, w_rows), (write(m, m_rows),
                                         write(v, v_rows), t)


_RULES = {cls.name: cls for cls in
          (DefaultRule, SGDRule, MomentumRule, AdaGradRule, DCASGDRule,
           AdamRule)}
# The reference's flag value for the momentum updater is "momentum_sgd"
# (ref: src/updater/updater.cpp:47-58); accept both spellings.
_RULES["momentum_sgd"] = MomentumRule


def create_rule(updater_type: Optional[str] = None,
                dtype=np.float32) -> UpdaterRule:
    """Factory on the -updater_type flag (ref: src/updater/updater.cpp:42-58).
    Integer tables always get the default adder, as in the reference."""
    if np.issubdtype(np.dtype(dtype), np.integer):
        return DefaultRule()
    name = updater_type if updater_type is not None \
        else get_flag("updater_type")
    cls = _RULES.get(name)
    if cls is None:
        log.error("unknown updater_type %r; using default", name)
        return DefaultRule()
    return cls()

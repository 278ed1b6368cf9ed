"""A large host delta is padded into a staging buffer the engine keeps
(``updater/engine.py`` ``Staging``, ``pad_rows``; docs/MEMORY.md "Send
side of an Add"): what reaches the table is what the fresh ``np.pad``
gave, whatever the runtime has yet to read when the next request comes.

The table cases run on a ONE-device CPU platform, in a process of their
own a rule: there nothing waits for the server's program (the device
lock is off, as on a chip) and the runtime reads a jitted call's host
argument after the call returns. One process runs the six cases on one
table against a second table that gets the same Adds one at a time
through fresh arrays; each test reads its case's line."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

from multiverso_tpu.updater import (UpdateEngine, bucket_size, create_rule,
                                    pad_rows)
from multiverso_tpu.updater.engine import (STAGING_BUFFERS,
                                           STAGING_MIN_BYTES, Staging,
                                           StagingBuffer)
from multiverso_tpu.util.dashboard import Dashboard

RULES = ["default", "sgd", "adam"]

ON_ONE_DEVICE = r"""
import json, sys
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.updater import engine
from multiverso_tpu.util.dashboard import Dashboard

rule = sys.argv[1]
COLS, BUCKET = 32, 131072           # a staging buffer is 16 MB
mv.init(["-updater_type=" + rule])
table = mv.create_matrix_table(BUCKET, COLS)
plain = mv.create_matrix_table(BUCKET, COLS)          # the reference
servers = mv.current_zoo().server_tables
staging = servers[table.table_id]._engine._staging
servers[plain.table_id]._engine._staging = None       # fresh arrays only
rng = np.random.default_rng(38)
all_ids = np.arange(BUCKET, dtype=np.int32)[::-1].copy()
sign = -1.0 if rule == "sgd" else 1.0
total = np.zeros((BUCKET, COLS), np.float32)          # default, sgd only


def draw(k):
    return rng.standard_normal((k, COLS)).astype(np.float32)


def counts():
    return [Dashboard.get(n).count
            for n in ("UPDATE_PAD_STAGED", "UPDATE_PAD_FRESH")]


def reference(k, delta):
    plain.add_rows(all_ids[:k], delta.copy())
    total[:k] += sign * delta


def report(case, before, **more):
    got = table.get_rows(all_ids)
    want = plain.get_rows(all_ids)
    staged, fresh = (a - b for a, b in zip(counts(), before))
    # (the reference table's Adds all count fresh: "fresh" is the
    # caller's, taken before them)
    out = dict(more, differ=int((got != want).any(axis=1).sum()),
               nan=int(np.isnan(got).sum()), staged=staged,
               buffers={str(k[0][0]): len(v)
                        for k, v in staging._buffers.items()})
    if rule != "adam":
        out["differ_from_sum"] = int((got != total).any(axis=1).sum())
    print("CASE", case, json.dumps(out), flush=True)


# programs built before any Add is measured against an overwrite
for k in (100, 100000):
    table.add_rows(all_ids[:k], np.zeros((k, COLS), np.float32))
    reference(k, np.zeros((k, COLS), np.float32))

# (a) two Adds of different deltas, same k, no wait between
before = counts()
k = 100000
a, b = draw(k), draw(k)
first = table.add_rows_async(all_ids[:k], a)
second = table.add_rows_async(all_ids[:k], b)
table.wait(first)
table.wait(second)
mine = [x - y for x, y in zip(counts(), before)]
reference(k, a)
reference(k, b)
report("a", before, adds=2, fresh=mine[1])

# (b) k = 100,000 then k = 70,000 in one bucket, each overwritten at its ack
before = counts()
a, b = draw(100000), draw(70000)
keep_a = a.copy()
keep_b = b.copy()
table.wait(table.add_rows_async(all_ids[:100000], a))
a[:] = np.nan
table.wait(table.add_rows_async(all_ids[:70000], b))
b[:] = np.nan
mine = [x - y for x, y in zip(counts(), before)]
reference(100000, keep_a)
reference(70000, keep_b)
report("b", before, adds=2, fresh=mine[1])

# (c) k is the bucket: nothing to pad, and still the table's own copy
before = counts()
mine = [0, 0]
for form in ("sync", "async", "sync"):
    a = draw(BUCKET)
    keep = a.copy()
    at = counts()
    if form == "sync":
        table.add_rows(all_ids, a)
    else:
        table.wait(table.add_rows_async(all_ids, a))
    a[:] = np.nan
    mine = [m + x - y for m, x, y in zip(mine, counts(), at)]
    reference(BUCKET, keep)
report("c", before, adds=3, fresh=mine[1])

# (d) four Adds in flight; then guards that are never ready
before = counts()
k = 100000
deltas = [draw(k) for _ in range(4)]
waits = [table.add_rows_async(all_ids[:k], d) for d in deltas]
for w in waits:
    table.wait(w)
mine = [x - y for x, y in zip(counts(), before)]
for d in deltas:
    reference(k, d)
report("d", before, adds=4, fresh=mine[1], mine=sum(mine))


class Never:
    def is_ready(self):
        return False


key = ((BUCKET, COLS), np.dtype(np.float32))
while len(staging._buffers[key]) < engine.STAGING_BUFFERS:
    staging._buffers[key].append(engine.StagingBuffer(*key))
for buffer in staging._buffers[key]:
    buffer.guard = Never()
before = counts()
deltas = [draw(k) for _ in range(3)]
waits = [table.add_rows_async(all_ids[:k], d) for d in deltas]
for w in waits:
    table.wait(w)                     # returns: nothing waited for a guard
mine = [x - y for x, y in zip(counts(), before)]
for d in deltas:
    reference(k, d)
report("d_never", before, adds=3, fresh=mine[1], mine=sum(mine))
for buffer in staging._buffers[key]:
    buffer.guard = None

# (e) a padded delta under 128 KiB: a fresh array, and no buffer kept for it
before = counts()
k = 100                               # 128 rows of 128 bytes
a = draw(k)
keep = a.copy()
table.add_rows(all_ids[:k], a)
a[:] = np.nan
mine = [x - y for x, y in zip(counts(), before)]
reference(k, keep)
report("e", before, adds=1, fresh=mine[1])

# (f) a read-only view, as a shard off the wire is
before = counts()
k = 100000
frame = draw(k).tobytes()
view = np.frombuffer(frame, np.float32).reshape(k, COLS)
assert not view.flags.writeable
table.add_rows(all_ids[:k], view)
mine = [x - y for x, y in zip(counts(), before)]
reference(k, view)
report("f", before, adds=1, fresh=mine[1])
mv.shutdown()
"""


@pytest.fixture(scope="module", params=RULES)
def cases(request):
    """The six cases' lines under one rule, from one process on a
    one-device CPU platform."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c", ON_ONE_DEVICE, request.param],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1",
                 PYTHONPATH=os.pathsep.join(
                     p for p in (repo, os.environ.get("PYTHONPATH", ""))
                     if p)))
    found = {}
    for line in out.stdout.splitlines():
        if line.startswith("CASE "):
            _, case, rest = line.split(" ", 2)
            found[case] = json.loads(rest)
    assert out.returncode == 0 and "f" in found, \
        (out.stdout[-600:], out.stderr[-1500:])
    return found


def _exact(case):
    """The table equals the reference table (the same Adds through
    fresh arrays, one at a time) and, under default and sgd, the
    float32 sum; no NaN anywhere."""
    assert case["differ"] == 0 and case["nan"] == 0, case
    assert case.get("differ_from_sum", 0) == 0, case


def test_two_adds_in_flight_of_one_k_both_land(cases):
    """(a) the second Add may not refill the buffer the first was
    uploaded from until that upload has run: it takes the other."""
    case = cases["a"]
    _exact(case)
    assert case["staged"] == 2 and case["fresh"] == 0, case
    assert case["buffers"] in ({"131072": 1}, {"131072": 2}), case


def test_a_shorter_delta_in_the_same_bucket_finds_a_zero_tail(cases):
    """(b) 100,000 rows, then 70,000 in the same buffer: rows 70,000
    to 99,999 of the first request are zeroed before the second call,
    so they are applied once; each delta is overwritten at its ack."""
    case = cases["b"]
    _exact(case)
    assert case["staged"] == 2 and case["fresh"] == 0, case


def test_a_bucket_sized_delta_is_owned_through_the_buffer(cases):
    """(c) PR 36's guarantee at b == k, now by the staging buffer: the
    caller overwrites 131,072 rows at the ack and the table has the old
    bytes."""
    case = cases["c"]
    _exact(case)
    assert case["staged"] == 3 and case["fresh"] == 0, case


def test_four_adds_in_flight_are_each_counted_once_and_exact(cases):
    """(d) every host delta that entered ``pad_rows`` is counted as
    staged or fresh; with guards that are never ready every request
    takes the fresh path and returns (the handler waits for none)."""
    case = cases["d"]
    _exact(case)
    assert case["mine"] == 4 and case["staged"] >= 1, case
    assert case["staged"] + case["fresh"] == 4, case
    never = cases["d_never"]
    _exact(never)
    assert never["mine"] == 3 and never["fresh"] == 3, never


def test_a_small_delta_keeps_np_pad_and_no_buffer(cases):
    """(e) under 128 KiB padded the heap recycles the array: counted
    fresh, and the table holds no buffer of that bucket."""
    case = cases["e"]
    _exact(case)
    assert case["fresh"] == 1, case
    assert set(case["buffers"]) == {"131072"}, case


def test_a_read_only_view_is_staged(cases):
    """(f) a shard off the wire is a read-only view into a leased
    frame: copied into the buffer by the same line."""
    case = cases["f"]
    _exact(case)
    assert case["fresh"] == 0, case


# -- the pieces, in this process ---------------------------------------------

SHAPE = (bucket_size(5000), 8)     # 8192 rows of 32 bytes: 256 KiB


def test_no_client_adopts_a_staging_buffer():
    """XLA's CPU client takes a 64-byte-aligned host array as the device
    buffer itself, and such an upload reads ready while the program has
    the memory yet to read. The buffer is built so that no client can:
    its upload is a copy, whose readiness is the guard."""
    buffer = StagingBuffer(SHAPE, np.dtype(np.float32))
    assert buffer.array.shape == SHAPE and not buffer.array.any()
    assert buffer.array.ctypes.data % 64 == 16
    uploaded = buffer.upload()
    assert buffer.guard is uploaded
    uploaded.block_until_ready()
    assert uploaded.unsafe_buffer_pointer() != buffer.array.ctypes.data
    buffer.array[:] = 7.0           # the upload is its own memory
    assert not np.asarray(uploaded).any()
    assert buffer.free() and buffer.guard is None


def test_a_fill_leaves_k_rows_then_zeros():
    buffer = StagingBuffer(SHAPE, np.dtype(np.float32))
    first = np.full((5000, 8), 2.0, np.float32)
    buffer.fill(first)
    assert buffer.filled == 5000 and (buffer.array[:5000] == 2.0).all()
    buffer.fill(np.full((3000, 8), 3.0, np.float32))
    assert buffer.filled == 3000
    assert (buffer.array[:3000] == 3.0).all()
    assert not buffer.array[3000:].any()
    buffer.fill(np.full((8192, 8), 4.0, np.float32))
    assert (buffer.array == 4.0).all()


class _Guard:
    def __init__(self, ready):
        self.ready = ready

    def is_ready(self):
        return self.ready


def test_take_goes_round_the_buffers_and_then_gives_none():
    staging = Staging()
    dtype = np.dtype(np.float32)
    one = staging.take(SHAPE, dtype)
    assert staging.take(SHAPE, dtype) is one        # free: taken again
    one.guard = _Guard(False)
    two = staging.take(SHAPE, dtype)
    assert two is not one
    two.guard = _Guard(False)
    assert staging.take(SHAPE, dtype) is None       # both still read
    assert len(staging._buffers[(SHAPE, dtype)]) == STAGING_BUFFERS == 2
    one.guard.ready = True
    assert staging.take(SHAPE, dtype) is one and one.guard is None
    # another bucket, another pair; a small one, none
    assert staging.take((16384, 8), dtype) is not one
    small = (STAGING_MIN_BYTES // 32 - 1, 8)
    assert staging.take(small, dtype) is None
    assert set(staging._buffers) == {(SHAPE, dtype), ((16384, 8), dtype)}


@pytest.mark.parametrize("k", [5000, 8192], ids=["padded", "bucket"])
@pytest.mark.parametrize("staged", [True, False], ids=["staged", "fresh"])
def test_pad_rows_gives_the_same_bytes_either_way(staged, k):
    ids = np.arange(k, dtype=np.int32)
    delta = np.arange(k * 8, dtype=np.float32).reshape(k, 8)
    delta.flags.writeable = False
    names = ("UPDATE_PAD_STAGED", "UPDATE_PAD_FRESH")
    before = [Dashboard.get(n).count for n in names]
    out_ids, out, buffer = pad_rows(ids, delta, 10000,
                                    Staging() if staged else None)
    moved = [Dashboard.get(n).count - b for n, b in zip(names, before)]
    assert moved == ([1, 0] if staged else [0, 1])
    assert (buffer is not None) == staged
    assert out.shape == SHAPE and out.dtype == np.float32
    assert not np.shares_memory(out, delta)
    np.testing.assert_array_equal(out[:k], delta)
    assert not out[k:].any()
    assert (out_ids[:k] == ids).all() and (out_ids[k:] == 10000).all()
    if staged:
        assert out is buffer.array and buffer.filled == k


@pytest.mark.parametrize("rule", RULES)
def test_the_engine_stages_on_one_device_and_not_over_a_mesh(rule):
    """A table on one device stages; over several devices the compiled
    program places its host argument and the engine keeps np.pad."""
    from multiverso_tpu.sharding import mesh as meshlib
    shape = (16384, 8)
    alone = UpdateEngine(create_rule(rule, np.float32), shape, np.float32,
                         1)
    assert isinstance(alone._staging, Staging)
    if len(jax.devices()) > 1:
        sharding = meshlib.row_sharded(meshlib.local_mesh())
        spread = UpdateEngine(create_rule(rule, np.float32), shape,
                              np.float32, 1, sharding)
        assert spread._staging is None
    ids = np.arange(5000, dtype=np.int32) * 2
    delta = np.ones((5000, 8), np.float32)
    data = jax.numpy.zeros(shape, np.float32)
    before = Dashboard.get("UPDATE_PAD_STAGED").count
    data = alone.apply_rows(data, ids, delta)
    data = alone.apply_rows(data, ids, delta)
    assert Dashboard.get("UPDATE_PAD_STAGED").count - before == 2
    got = np.asarray(data)
    assert not got[1::2].any() and not got[10000:].any()
    if rule != "adam":
        want = -2.0 if rule == "sgd" else 2.0
        assert (got[:10000:2] == want).all()
    # a device delta does not enter the branch
    before = [Dashboard.get(n).count
              for n in ("UPDATE_PAD_STAGED", "UPDATE_PAD_FRESH")]
    alone.apply_rows(data, ids, jax.numpy.ones((5000, 8), np.float32))
    assert before == [Dashboard.get(n).count
                      for n in ("UPDATE_PAD_STAGED", "UPDATE_PAD_FRESH")]
    assert STAGING_MIN_BYTES == 128 * 1024

"""The seam between a Get and its reply: one sink a request, registered
where the request is issued (tables/table_interface.py ``_sinks``).

The cases below play the worker actor by hand: the table's zoo is a
stub whose mailbox is a list, ``_Actor`` partitions what the table sent
and hands each server's reply shard to ``process_reply_get`` under the
request's id, in the order a case asks for. Nothing here needs a
server, so every case also runs over three."""

import contextlib
import gc
import threading
import time
import types
import weakref

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core import blob as blobmod
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.runtime.zoo import ClusterAborted
from multiverso_tpu.tables.array_table import ArrayWorker
from multiverso_tpu.tables.matrix_table import MatrixWorker
from multiverso_tpu.tables.table_interface import (RpcTimeoutError,
                                                   TableRequestError)
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.dashboard import Dashboard

ROWS, COLS = 24, 3
UNTOUCHED = -7.0


class _Zoo:
    """What a worker table asks of its zoo, and a list for a mailbox."""

    rank = 0
    worker_id = 0
    num_workers = 1
    servers_in_process = True
    net = types.SimpleNamespace(in_process=True)
    _actors = {}

    def __init__(self, num_servers):
        self.num_servers = num_servers
        self.sent = []

    def register_worker_table(self, table):
        return 0

    def rank_to_server_id(self, rank):
        return rank

    def send_to(self, actor, msg):
        self.sent.append(msg)


class _Actor:
    """The worker actor and the servers, by hand."""

    def __init__(self, table, data, device=()):
        self.table = table
        self.data = data
        self.shards = {}
        self.device = set(device)  # ids whose values reply from HBM
        self.codec = False  # host row values as a wire-codec frame

    def partition(self):
        sent, self.table.zoo.sent = self.table.zoo.sent, []
        for msg in sent:
            self.table._partition_msg_id = msg.msg_id
            parts = self.table.partition(msg.data, msg.type)
            self.table._partition_msg_id = -1
            self.table.reset(msg.msg_id, len(parts))
            self.shards.setdefault(msg.msg_id, []).extend(parts.items())
        return [msg.msg_id for msg in sent]

    def _serve(self, msg_id, sid, blobs):
        import jax.numpy as jnp
        table, data = self.table, self.data
        lo, hi = table._offsets[sid], table._offsets[sid + 1]
        server = Blob(np.array([sid], np.int32))
        if isinstance(table, ArrayWorker):
            part = data[lo:hi]
            return [server, Blob(jnp.asarray(part)
                                 if msg_id in self.device else part)]
        if blobs[0].on_device:
            ids = blobs[0].typed(np.int32)
            mine = ((ids >= lo) & (ids < hi))[..., None]
            return [blobs[0], Blob(jnp.where(mine, jnp.asarray(data)[ids],
                                             0.0)), server]
        keys = blobs[0].as_array(np.int32)
        whole = keys.size == 1 and keys[0] == -1
        values = data[lo:hi] if whole else data[keys]
        if msg_id in self.device:
            values = jnp.asarray(values)
        if self.codec:
            from multiverso_tpu.tables.matrix_table import _compress_values
            return [blobs[0]] + _compress_values(np.asarray(values))[0]
        return [blobs[0], Blob(values)] + ([server] if whole else [])

    def reply(self, msg_id, version=1, reverse=False, replica_rows=0):
        """Every shard of ``msg_id``: reply, then the notify. With
        ``replica_rows`` a shard declares that many of its last rows one
        replica group, its own, at the reply's version."""
        shards = self.shards.pop(msg_id)
        for sid, blobs in (reversed(shards) if reverse else shards):
            reply = self._serve(msg_id, sid, blobs)
            if replica_rows:
                reply.append(Blob(np.array(
                    [1, sid, version + 1, replica_rows], np.int32)))
            self.table._begin_reply(sid, version, msg_id, replica_rows)
            try:
                self.table.process_reply_get(reply)
            finally:
                self.table._end_reply()
            self.table.notify(msg_id)

    def fail(self, msg_id):
        for _ in self.shards.pop(msg_id):
            self.table.fail(msg_id, "the server said no")


def _matrix(num_servers, cache=False):
    if cache:
        set_flag("max_get_staleness", 4)
    table = MatrixWorker(ROWS, COLS, zoo=_Zoo(num_servers))
    data = np.arange(ROWS * COLS, dtype=np.float32).reshape(ROWS, COLS)
    return table, data, _Actor(table, data)


def _array(num_servers, cache=False):
    if cache:
        set_flag("max_get_staleness", 4)
    table = ArrayWorker(ROWS, zoo=_Zoo(num_servers))
    data = np.arange(ROWS, dtype=np.float32) + 100.0
    return table, data, _Actor(table, data)


def _buffer(*shape):
    return np.full(shape, UNTOUCHED, np.float32)


IDS = np.array([1, 1, 9, 10, 17, 23, 23], np.int32)  # sorted, repeats
OTHER = np.array([20, 2, 11], np.int32)


# -- the kinds: issue one, return (its ids, how to check it landed) --

def _rows(table, data, actor):
    out = _buffer(IDS.size, COLS)
    mid = table.get_rows_async(IDS, out)
    return [mid], lambda: np.testing.assert_array_equal(out, data[IDS])


def _whole(table, data, actor):
    out = _buffer(*data.shape)
    mid = table.get_async(out)
    return [mid], lambda: np.testing.assert_array_equal(out, data)


def _device(table, data, actor):
    if isinstance(table, ArrayWorker):
        mid = table.get_device_async()
        take = lambda: table._last_device.ordered()  # noqa: E731
        want = data
    else:
        mid = table.get_rows_device_async(np.unique(IDS))
        take = table.take_device_row_parts
        want = data[np.unique(IDS)]
    actor.device.add(mid)
    return [mid], lambda: np.testing.assert_array_equal(
        np.concatenate([np.asarray(p) for p in take()]), want)


def _device_keys(table, data, actor):
    import jax.numpy as jnp
    ids = jnp.asarray(IDS[::-1].reshape(-1, 1))  # any shape, any order
    mid = table.get_rows_device_async(ids)
    return [mid], lambda: np.testing.assert_array_equal(
        np.asarray(table.take_device_rows()), data[np.asarray(ids)])


def _cache_only(table, data, actor):
    if isinstance(table, ArrayWorker):
        mid = table.prefetch_async()
        return [mid], lambda: np.testing.assert_array_equal(
            np.concatenate([table._blob_cache.fetch_all()[s]
                            for s in range(table._num_server)]), data)
    mid = table.prefetch_rows_async(IDS)

    def landed():
        out = _buffer(IDS.size, COLS)
        assert table._row_cache.fetch_into(IDS, out).size == 0
        np.testing.assert_array_equal(out, data[IDS])
    return [mid], landed


def _scatter(table, data, actor):
    result = []
    reader = threading.Thread(
        target=lambda: result.append(table.read_rows_scatter(IDS)),
        daemon=True)
    reader.start()
    groups = np.unique(table._server_of_rows(np.unique(IDS))).size
    deadline = time.monotonic() + 30
    while len(table.zoo.sent) < groups and time.monotonic() < deadline:
        time.sleep(0.001)
    mids = [msg.msg_id for msg in table.zoo.sent]
    assert len(mids) == groups

    def landed():
        reader.join(30)
        values, info = result[0]
        np.testing.assert_array_equal(values, data[np.unique(IDS)])
        assert info["failed"].size == 0
        assert (info["versions"] == 1).all()
    return mids, landed


def _lands_in_its_own_sink(make, kind, num_servers):
    """The kind's Get, then two host Gets issued AFTER it (whose buffers
    the table's registers would have named): the kind's reply shards
    land in the kind's sink and leave the bystanders' buffers alone."""
    table, data, actor = make(num_servers, cache=kind is _cache_only)
    mids, landed = kind(table, data, actor)
    for mid in mids:
        assert mid in table._sinks  # registered before the send
    whole = _buffer(*data.shape)
    bystanders = [table.get_async(whole)]
    if isinstance(table, MatrixWorker):
        rows = _buffer(OTHER.size, COLS)
        bystanders.append(table.get_rows_async(OTHER, rows))
    assert len(set(table._sinks.values())) == 1 + len(bystanders)
    actor.partition()
    for mid in mids:
        actor.reply(mid, reverse=True)
        assert mid not in table._sinks
    landed()
    assert (whole == UNTOUCHED).all()
    for mid in bystanders:
        actor.reply(mid)
    np.testing.assert_array_equal(whole, data)
    if isinstance(table, MatrixWorker):
        np.testing.assert_array_equal(rows, data[OTHER])
    assert not table._sinks


def _prefetch_under_a_host_get(make, kind, num_servers):
    """A prefetch's reply, arriving while a host row Get is in flight,
    fills the cache and leaves the Get's buffer as it was."""
    table, data, actor = _matrix(num_servers, cache=True)
    out = _buffer(OTHER.size, COLS)
    get = table.get_rows_async(OTHER, out)
    prefetch = table.prefetch_rows_async(IDS)
    actor.partition()
    actor.reply(prefetch)
    assert (out == UNTOUCHED).all()
    assert table._row_cache.missing_of(np.unique(IDS)).size == 0
    assert table._row_cache.missing_of(np.unique(OTHER)).size == OTHER.size
    actor.reply(get)
    np.testing.assert_array_equal(out, data[OTHER])
    assert not table._sinks and not table._pf_rows


def _joined_get_forwarded(make, kind, num_servers):
    """A Get joins an in-flight prefetch; an own Add invalidates the
    rows before the prefetch lands, so its completion forwards the Get
    to the wire under the Get's own id. Another Get has been issued on
    the table since: the forwarded rows land in the joined Get's buffer
    all the same."""
    table, data, actor = _matrix(num_servers, cache=True)
    prefetch = table.prefetch_rows_async(IDS)
    out = _buffer(IDS.size, COLS)
    joined = table.get_rows_async(IDS, out)
    assert table._pf_joined == {prefetch: [joined]}
    later = _buffer(OTHER.size, COLS)
    other = table.get_rows_async(OTHER, later)
    token = table._row_cache.begin_add(None)  # blocks every slot
    assert actor.partition() == [prefetch, other]
    actor.reply(prefetch)  # stores nothing: forwards the joined Get
    assert actor.partition() == [joined]
    assert (out == UNTOUCHED).all()
    actor.reply(other)
    actor.reply(joined)
    table._row_cache.finish_add(token)
    np.testing.assert_array_equal(out, data[IDS])
    np.testing.assert_array_equal(later, data[OTHER])
    assert table.wait(joined, timeout=0)
    assert not table._sinks and not table._pf_joined


def _failed_device_key_get(make, kind, num_servers):
    """A device-key Get (its parts sum) that fails leaves nothing that
    makes the next host-key device Get sum where it must concatenate."""
    table, data, actor = _matrix(num_servers)
    mids, _ = _device_keys(table, data, actor)
    actor.partition()
    actor.fail(mids[0])
    with pytest.raises(TableRequestError):
        table.wait(mids[0])
    assert not table._sinks
    ids = np.unique(IDS)
    mid = table.get_rows_device_async(ids)
    actor.device.add(mid)
    actor.partition()
    actor.reply(mid)
    assert table.wait(mid, timeout=0)
    np.testing.assert_array_equal(np.asarray(table.take_device_rows()),
                                  data[ids])
    with pytest.raises(Exception, match="no device row get outstanding"):
        table.take_device_rows()


def _reply_without_a_sink(make, kind, num_servers):
    """A reply that outlives its request (timed out) fails the CHECK and
    touches no buffer."""
    table, data, actor = make(num_servers)
    out = _buffer(*data.shape)
    set_flag("rpc_timeout_s", 0.001)
    mid = table.get_async(out)
    actor.partition()
    with pytest.raises(RpcTimeoutError):
        table.wait(mid)
    assert not table._sinks
    with pytest.raises(Exception, match="no outstanding destination"):
        actor.reply(mid)
    assert (out == UNTOUCHED).all()


def _registry_drains(make, kind, num_servers):
    """A thousand Gets of every kind, failures and timeouts among them
    and an abort at the end: no sink is left."""
    table, data, actor = _matrix(num_servers, cache=True)
    kinds = [_rows, _whole, _device, _device_keys, _cache_only]
    rng = np.random.default_rng(0)
    fates = rng.choice(["reply", "fail", "timeout"], 1000,
                       p=[0.9, 0.08, 0.02])
    for i, fate in enumerate(fates):
        mids, _ = kinds[i % len(kinds)](table, data, actor)
        actor.partition()
        for mid in mids:
            if mid not in actor.shards:  # served by the cache
                assert table.wait(mid, timeout=0)
            elif fate == "reply":
                actor.reply(mid, version=i + 1)
                assert table.wait(mid, timeout=0)
            elif fate == "fail":
                actor.fail(mid)
                with pytest.raises(TableRequestError):
                    table.wait(mid)
            else:
                set_flag("rpc_timeout_s", 0.001)
                with pytest.raises(RpcTimeoutError):
                    table.wait(mid)
                set_flag("rpc_timeout_s", 0.0)
                actor.shards.pop(mid)
        assert not table._sinks, (i, fate)
    left = [kind(table, data, actor)[0][0] for kind in kinds]
    assert len(table._sinks) >= 3  # the cache may serve two of them
    table.abort("the cluster went away")
    assert not table._sinks
    with pytest.raises(ClusterAborted):
        table.wait(left[1])


def _two_host_gets_in_flight(make, kind, num_servers):
    """The real runtime, actors and all: two host row Gets issued back
    to back, waited for afterwards, each fill their own buffer."""
    mv.init([])
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        data = np.arange(ROWS * COLS, dtype=np.float32).reshape(ROWS, COLS)
        table.add(data)
        first, second = _buffer(IDS.size, COLS), _buffer(OTHER.size, COLS)
        mids = [table.get_rows_async(IDS, first),
                table.get_rows_async(OTHER, second), table.get_async()]
        for mid in mids:
            assert table.wait(mid, timeout=30)
        np.testing.assert_array_equal(first, data[IDS])
        np.testing.assert_array_equal(second, data[OTHER])
        assert not table._sinks
    finally:
        mv.shutdown()


def _many_requesters_one_table(make, kind, num_servers):
    """More requester threads than cores on one table of the real
    runtime, the interpreter switching every few bytecodes: every Get's
    rows land in its own buffer and the registry ends empty."""
    import sys
    mv.init([])
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        data = np.arange(ROWS * COLS, dtype=np.float32).reshape(ROWS, COLS)
        table.add(data)
        wrong = []

        def requester(seed):
            rng = np.random.default_rng(seed)
            for _ in range(40):
                ids = rng.integers(0, ROWS, 5).astype(np.int32)
                outs = [_buffer(ids.size, COLS) for _ in range(3)]
                mids = [table.get_rows_async(ids + 0, out) for out in outs]
                for mid, out in zip(mids, outs):
                    if not (table.wait(mid, timeout=60)
                            and np.array_equal(out, data[ids])):
                        wrong.append((seed, mid))

        threads = [threading.Thread(target=requester, args=(i,), daemon=True)
                   for i in range(32)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not any(t.is_alive() for t in threads)
        assert not wrong
        assert not table._sinks
    finally:
        sys.setswitchinterval(interval)
        mv.shutdown()


# -- a large device reply leaves the device in pieces ----------------------

#: every monitor a host row Get's reply moves on the worker's thread
_REPLY_MONITORS = ("BLOB_D2H", "BLOB_D2H_READY", "BLOB_D2H_COPY",
                   "CLIENT_PLACE_ROWS", "GET_REPLY_ROWS_PIECED",
                   "GET_REPLY_ROWS_WHOLE", "GET_REPLY_ROWS_DIRECT",
                   "GET_REPLY_ROWS_PLACED")
CUT_FROM, PIECE = 72, 96  # bytes: replies of 6 rows up, in pieces of 8


@contextlib.contextmanager
def _cut_at(whole_under, piece=PIECE):
    """The blob's two constants, for a table of 24 x 3."""
    old = blobmod.D2H_WHOLE_UNDER_BYTES, blobmod.D2H_PIECE_BYTES
    blobmod.D2H_WHOLE_UNDER_BYTES, blobmod.D2H_PIECE_BYTES = whole_under, piece
    try:
        yield
    finally:
        blobmod.D2H_WHOLE_UNDER_BYTES, blobmod.D2H_PIECE_BYTES = old


def _moved(before):
    return {name: Dashboard.get(name).count - before[name]
            for name in _REPLY_MONITORS}


def _counts():
    return {name: Dashboard.get(name).count for name in _REPLY_MONITORS}


def _keys_are_the_request(rng):
    return rng.integers(0, ROWS, 40).astype(np.int32)  # any order, repeats


def _padded_tail(rng):
    return np.concatenate([[1, 4, 9, 17], np.full(3000, ROWS - 1)]
                          ).astype(np.int32)


def _sorted_runs(rng):
    return np.sort(rng.integers(0, ROWS, 90)).astype(np.int32)


def _device_get(table, actor, ids, routed=False, **reply):
    """One host row Get of ``ids`` whose shards reply from HBM: its
    buffer, the sizes of its shards in bytes, and what the reply moved."""
    out = _buffer(ids.size, COLS)
    mid = table.get_rows_async(ids, out)
    actor.device.add(mid)
    actor.partition()
    if routed:
        table._replica_sent[mid] = {sid: np.empty(0, np.int32)
                                    for sid, _ in actor.shards[mid]}
    sizes = [blobs[0].size * COLS for _, blobs in actor.shards[mid]]
    before = _counts()
    actor.reply(mid, **reply)
    assert table.wait(mid, timeout=0) and not table._sinks
    return out, sizes, _moved(before)


def _pieced_equals_whole(make, shape, num_servers):
    """The three direct shapes: the reply placed by pieces is the reply
    placed whole, bit for bit, and every monitor counts one entry a
    shard either way."""
    ids = shape(np.random.default_rng(7))
    placed = {}
    for cut_from in (CUT_FROM, 1 << 30):
        table, data, actor = _matrix(num_servers)
        with _cut_at(cut_from):
            out, sizes, moved = _device_get(table, actor, ids)
        pieced = sum(size >= cut_from for size in sizes)
        assert pieced or cut_from > CUT_FROM
        assert moved == {
            "BLOB_D2H": len(sizes), "BLOB_D2H_READY": len(sizes),
            "BLOB_D2H_COPY": len(sizes), "CLIENT_PLACE_ROWS": len(sizes),
            "GET_REPLY_ROWS_DIRECT": len(sizes), "GET_REPLY_ROWS_PLACED": 0,
            "GET_REPLY_ROWS_PIECED": pieced,
            "GET_REPLY_ROWS_WHOLE": len(sizes) - pieced}, (cut_from, sizes)
        placed[cut_from] = out
    assert pieced == 0 and len(sizes) == min(
        num_servers, np.unique(ids * num_servers // ROWS).size)
    np.testing.assert_array_equal(placed[CUT_FROM], data[ids])
    assert placed[CUT_FROM].tobytes() == placed[1 << 30].tobytes()


def _searched(table, actor):
    ids = np.random.default_rng(3).permutation(np.repeat(np.arange(ROWS), 2))
    return ids.astype(np.int32), {}


def _replica_rows(table, actor):
    return _sorted_runs(np.random.default_rng(4)), {"replica_rows": 2}


def _routed_to_a_holder(table, actor):
    """Every shard is a holder's answer to rows routed to it (all of
    which it served: nothing to repair)."""
    return _sorted_runs(np.random.default_rng(5)), {"routed": True}


def _active_row_cache(table, actor):
    return _sorted_runs(np.random.default_rng(6)), {}


def _takes_the_whole(make, kind, num_servers):
    """Replies that more than one reader has to see (a search, replica
    groups, a cache that stores) take the whole array as before, however
    large, and say so."""
    table, data, actor = _matrix(num_servers,
                                 cache=kind is _active_row_cache)
    ids, reply = kind(table, actor)
    with _cut_at(CUT_FROM):
        out, sizes, moved = _device_get(table, actor, ids, **reply)
    np.testing.assert_array_equal(out, data[ids])
    assert min(sizes) >= CUT_FROM  # large enough, each of them
    assert moved["GET_REPLY_ROWS_PIECED"] == 0
    assert moved["GET_REPLY_ROWS_WHOLE"] == len(sizes)
    assert moved["BLOB_D2H"] == moved["BLOB_D2H_COPY"] == len(sizes)
    if kind is _searched and num_servers > 1:
        assert moved["GET_REPLY_ROWS_PLACED"] == len(sizes)
    if kind is _active_row_cache:
        assert table._row_cache.missing_of(np.unique(ids)).size == 0


def _codec_reply(make, kind, num_servers):
    """A sparse table over a wire: the values come as a codec frame, a
    host payload however large, and are decoded whole."""
    zoo = _Zoo(num_servers)
    zoo.net = types.SimpleNamespace(in_process=False)
    table = MatrixWorker(ROWS, COLS, is_sparse=True, zoo=zoo)
    assert table._compress
    data = np.arange(ROWS * COLS, dtype=np.float32).reshape(ROWS, COLS)
    actor = _Actor(table, data)
    actor.codec = True
    ids = _sorted_runs(np.random.default_rng(8))
    out = _buffer(ids.size, COLS)
    with _cut_at(CUT_FROM):
        mid = table.get_rows_async(ids, out)
        actor.partition()
        shards = len(actor.shards[mid])
        before = _counts()
        actor.reply(mid)
    moved = _moved(before)
    np.testing.assert_array_equal(out, data[ids])
    assert moved["GET_REPLY_ROWS_PIECED"] == moved["BLOB_D2H"] == 0
    assert moved["GET_REPLY_ROWS_WHOLE"] == shards
    assert moved["CLIENT_PLACE_ROWS"] == shards


def _other_sinks_take_the_whole(make, kind, num_servers):
    """A scatter read and a prefetch read a reply shard's rows in their
    own way: large device replies reach them as one array."""
    table, data, actor = _matrix(num_servers, cache=kind is _cache_only)
    with _cut_at(4):  # everything is large
        before = _counts()
        mids, landed = kind(table, data, actor)
        actor.device.update(mids)
        actor.partition()
        shards = sum(len(actor.shards[mid]) for mid in mids)
        for mid in mids:
            actor.reply(mid)
        landed()
    moved = _moved(before)
    assert moved["GET_REPLY_ROWS_PIECED"] == 0
    assert moved["GET_REPLY_ROWS_WHOLE"] == moved["BLOB_D2H"] == shards


_MATRIX_KINDS = [_rows, _whole, _device, _device_keys, _cache_only, _scatter]
_ARRAY_KINDS = [_whole, _device, _cache_only]
CASES = (
    [(_lands_in_its_own_sink, _matrix, kind, n)
     for kind in _MATRIX_KINDS for n in (1, 3)]
    + [(_lands_in_its_own_sink, _array, kind, n)
       for kind in _ARRAY_KINDS for n in (1, 3)]
    + [(case, _matrix, None, n)
       for case in (_prefetch_under_a_host_get, _joined_get_forwarded,
                    _failed_device_key_get, _registry_drains)
       for n in (1, 3)]
    + [(_reply_without_a_sink, make, None, n)
       for make in (_matrix, _array) for n in (1, 3)]
    + [(_two_host_gets_in_flight, None, None, 1),
       (_many_requesters_one_table, None, None, 1)]
    + [(_pieced_equals_whole, None, shape, n)
       for shape, n in ((_keys_are_the_request, 1), (_padded_tail, 1),
                        (_padded_tail, 3), (_sorted_runs, 1),
                        (_sorted_runs, 3))]
    + [(_takes_the_whole, None, kind, n)
       for kind in (_searched, _replica_rows, _routed_to_a_holder,
                    _active_row_cache) for n in (1, 3)
       if (kind, n) != (_searched, 1)]  # one server's shard is the request
    + [(_codec_reply, None, None, n) for n in (1, 3)]
    + [(_other_sinks_take_the_whole, None, kind, n)
       for kind in (_scatter, _cache_only) for n in (1, 3)])


def _case_id(case):
    scenario, make, kind, n = case
    names = [scenario, make, kind]
    return "-".join([f.__name__.strip("_") for f in names if f] + [str(n)])


@pytest.mark.parametrize("case", CASES, ids=_case_id)
def test_reply_sinks(case):
    scenario, make, kind, num_servers = case
    scenario(make, kind, num_servers)


# -- Blob.host_row_pieces: a device payload of rows, by row ranges ----------

@pytest.mark.parametrize("n_col", [50, 128])
@pytest.mark.parametrize("n_rows, cut_from, pieces", [
    (64, 1, 4),       # a multiple of the piece
    (70, 1, 5),       # and not: the last piece is the short one
    (3, 1, 2),        # never fewer than two
    (200, 1, 8),      # nor more than D2H_MOST_PIECES: larger pieces then
    (64, None, 4),    # AT the threshold: cut
    (63, "64 rows", 1),   # under it: the one array _host gives
])
def test_blob_row_pieces(n_rows, n_col, cut_from, pieces):
    """The pieces of a device [n, c] payload concatenate to np.asarray
    of the whole; the monitors count one entry a payload; once the
    pieces are cut the blob holds no reference to the device array and
    afterwards reads as the same payload."""
    import jax.numpy as jnp
    rows = np.random.default_rng(n_rows).normal(
        size=(n_rows, n_col)).astype(np.float32)
    row_bytes = n_col * 4
    cut_from = {1: 1, None: rows.nbytes, "64 rows": 64 * row_bytes}[cut_from]
    names = ("BLOB_D2H", "BLOB_D2H_READY", "BLOB_D2H_COPY", "BLOB_D2H_BYTES")
    before = {name: Dashboard.get(name).count for name in names}
    device = jnp.asarray(rows)
    alive = weakref.ref(device)
    blob = Blob(device)
    del device
    programs = blobmod._row_piece()._cache_size()
    with _cut_at(cut_from, piece=16 * row_bytes):
        assert blob.pieced_rows(np.float32, n_rows, n_col) == (pieces > 1)
        assert not blob.pieced_rows(np.float32, n_rows, n_col + 1)
        assert not blob.pieced_rows(np.int32, n_rows, n_col)
        taken = blob.host_row_pieces(np.float32, n_rows, n_col)
        first, piece = next(taken)
        if pieces > 1:  # cut: nothing keeps the whole on the device
            gc.collect()
            assert alive() is None and not blob.on_device
        got = [(first, piece)] + list(taken)
    assert len(got) == pieces
    # one cut program a reply shape, the short last piece's too (64 rows
    # come twice: the second case finds the first's program)
    built = blobmod._row_piece()._cache_size() - programs
    assert built == (pieces > 1) or (n_rows == 64 and built == 0)
    assert [first for first, _ in got] \
        == np.cumsum([0] + [len(p) for _, p in got[:-1]]).tolist()
    assert len({len(p) for _, p in got[:-1]}) <= 1  # equal, the last aside
    for _, piece in got:
        assert piece.shape[1:] == (n_col,) and not piece.flags.writeable
    np.testing.assert_array_equal(np.concatenate([p for _, p in got]), rows)
    moved = {name: Dashboard.get(name).count - before[name]
             for name in names}
    assert moved == {"BLOB_D2H": 1, "BLOB_D2H_READY": 1, "BLOB_D2H_COPY": 1,
                     "BLOB_D2H_BYTES": rows.nbytes}
    # the same payload afterwards, and no second copy off the device
    np.testing.assert_array_equal(blob.as_rows(np.float32, n_rows, n_col),
                                  rows)
    assert blob.size == rows.nbytes
    assert Dashboard.get("BLOB_D2H").count - before["BLOB_D2H"] == 1


def test_a_host_payload_is_one_piece():
    """Nothing to overlap: a host payload, however large, is the one
    array ``as_rows`` gives, in place."""
    rows = np.arange(600, dtype=np.float32).reshape(100, 6)
    blob = Blob(rows)
    with _cut_at(1, piece=48):
        assert not blob.pieced_rows(np.float32, 100, 6)
        (first, piece), = blob.host_row_pieces(np.float32, 100, 6)
    assert first == 0 and np.shares_memory(piece, rows)

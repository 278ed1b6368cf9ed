"""Client-cache tests: versioned replies, staleness bound, prefetch.

Covers the worker-side parameter cache (tables/client_cache.py):
default-off byte-identical behavior, row-cache hits that bypass the
wire, read-your-writes via ack-resolved self-invalidation, the
staleness-bound property (a cached Get never serves a version older
than latest-observed minus -max_get_staleness), in-flight Get
deduplication, prefetch, BSP force-disable, and the Array/KV variants.
"""

import threading

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.dashboard import Dashboard


@pytest.fixture
def env():
    mv.init([])
    yield
    mv.shutdown()


@pytest.fixture
def cache_env():
    """Cache enabled with a staleness bound of 4 applied Adds."""
    mv.init([])
    set_flag("max_get_staleness", 4)
    yield
    mv.shutdown()


def _server_gets() -> int:
    return Dashboard.get("SERVER_PROCESS_GET").count


class TestDisabledByDefault:
    def test_no_cache_objects_without_flag(self, env):
        matrix = mv.create_matrix_table(16, 4)
        array = mv.create_array_table(16)
        kv = mv.create_kv_table()
        # The matrix row cache is now ALWAYS constructed (so a live
        # Control_Config can activate it, docs/AUTOTUNE.md) but must
        # be INACTIVE — the pass-through contract the tests below
        # pin. Array/KV caches stay construction-gated.
        assert matrix._row_cache is not None
        assert not matrix._row_cache.active
        assert matrix._live_cache() is None
        assert array._blob_cache is None
        assert kv._snap_cache is None

    def test_every_get_takes_the_wire(self, env):
        table = mv.create_matrix_table(16, 4)
        table.add(np.ones((16, 4), np.float32))
        ids = np.array([1, 2], np.int32)
        before = _server_gets()
        table.get_rows(ids)
        table.get_rows(ids)
        assert _server_gets() - before == 2

    def test_prefetch_is_a_noop_when_disabled(self, env):
        table = mv.create_matrix_table(16, 4)
        before = _server_gets()
        mid = table.prefetch_rows_async(np.array([1, 2], np.int32))
        assert table.wait(mid, timeout=10)
        assert _server_gets() - before == 0

    def test_sync_mode_force_disables(self):
        # BSP: a locally served Get would bypass the sync server's
        # vector clocks — the flag must not matter.
        mv.init(["-sync=true", "-max_get_staleness=8"])
        try:
            table = mv.create_matrix_table(8, 2)
            assert table._row_cache is None  # sync: never constructed
            # — no hook exists, so no live config can ever enable it
            table.add(np.ones((8, 2), np.float32))
            out = table.get_rows(np.array([3], np.int32))
            np.testing.assert_array_equal(out, np.ones((1, 2)))
        finally:
            mv.shutdown()


class TestRowCache:
    def test_repeat_get_hits_locally(self, cache_env):
        table = mv.create_matrix_table(32, 4)
        base = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        table.add(base)
        ids = np.array([1, 5, 5, 31], np.int32)  # dups welcome
        before = _server_gets()
        first = table.get_rows(ids).copy()
        hit = table.get_rows(ids).copy()
        np.testing.assert_array_equal(first, base[ids])
        np.testing.assert_array_equal(hit, base[ids])
        assert _server_gets() - before == 1  # second get never left
        assert table._row_cache.hits == 1

    def test_versions_ride_replies(self, cache_env):
        table = mv.create_matrix_table(8, 2)
        for i in range(3):
            table.add(np.ones((8, 2), np.float32))
        # Single in-process server = server id 0; three acked adds.
        assert table._version_tracker.latest(0) == 3
        table.get_rows(np.array([0], np.int32))
        assert table._version_tracker.latest(0) == 3

    def test_read_your_writes(self, cache_env):
        table = mv.create_matrix_table(16, 4)
        base = np.arange(16 * 4, dtype=np.float32).reshape(16, 4)
        table.add(base)
        ids = np.array([2, 7], np.int32)
        table.get_rows(ids)  # populate
        table.add_rows(np.array([7], np.int32),
                       np.ones((1, 4), np.float32))
        # The own write must be visible immediately — the cached copy
        # of row 7 was invalidated at issue and its floor raised by the
        # ack, so this get refetches.
        got = table.get_rows(ids)
        np.testing.assert_array_equal(got[0], base[2])
        np.testing.assert_array_equal(got[1], base[7] + 1.0)

    def test_whole_table_add_invalidates(self, cache_env):
        table = mv.create_matrix_table(8, 2)
        ids = np.array([1, 3], np.int32)
        table.get_rows(ids)  # populate at version 0
        table.add(np.full((8, 2), 5.0, np.float32))
        got = table.get_rows(ids)
        np.testing.assert_array_equal(got, np.full((2, 2), 5.0))

    def test_staleness_bound_property(self, cache_env):
        # THE acceptance property: a cached Get never serves a version
        # older than latest-observed - max_get_staleness. Randomized
        # add/get interleaving against a shadow model; every served row
        # is checked via the cache's on_hit hook, and (single worker =
        # every add is an own-add) every get must equal the shadow
        # exactly.
        rng = np.random.default_rng(17)
        table = mv.create_matrix_table(24, 3)
        bound = table._row_cache._bound
        served = []

        def on_hit(row, entry_version, latest, k):
            served.append((row, entry_version, latest, k))
            assert entry_version >= latest - k, \
                (row, entry_version, latest, k)

        table._row_cache.on_hit = on_hit
        shadow = np.zeros((24, 3), np.float32)
        for step in range(80):
            if rng.random() < 0.4:
                rows = np.unique(rng.integers(0, 24, size=3)) \
                    .astype(np.int32)
                delta = rng.normal(size=(rows.size, 3)) \
                    .astype(np.float32)
                table.add_rows(rows, delta)
                shadow[rows] += delta
            else:
                rows = np.unique(rng.integers(0, 24, size=4)) \
                    .astype(np.int32)
                got = table.get_rows(rows)
                np.testing.assert_allclose(got, shadow[rows],
                                           rtol=0, atol=1e-5)
        assert served, "no cached Get ever served — cache inert"
        assert all(v >= latest - bound for _, v, latest, _ in served)

    def test_capacity_eviction(self, cache_env):
        from multiverso_tpu.tables.client_cache import RowCache
        table = mv.create_matrix_table(64, 2)
        table._row_cache = RowCache(
            4, table._row_cache._server_of, 1,
            table._version_tracker, capacity=8)
        table.add(np.ones((64, 2), np.float32))
        for lo in range(0, 64, 8):
            table.get_rows(np.arange(lo, lo + 8, dtype=np.int32))
        assert len(table._row_cache._rows) <= 8


class TestPrefetchAndDedup:
    def test_prefetch_then_get_is_local(self, cache_env):
        table = mv.create_matrix_table(32, 4)
        base = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        table.add(base)
        ids = np.array([3, 9], np.int32)
        before = _server_gets()
        mid = table.prefetch_rows_async(ids)
        assert table.wait(mid, timeout=10)
        got = table.get_rows(ids)
        np.testing.assert_array_equal(got, base[ids])
        assert _server_gets() - before == 1  # only the prefetch went out

    def test_inflight_dedup_single_wire_get(self, cache_env):
        # A Get issued while a prefetch for the same rows is in flight
        # must join it (or hit the already-landed cache): exactly ONE
        # server-side Get either way, and the values are exact.
        table = mv.create_matrix_table(32, 4)
        base = np.arange(32 * 4, dtype=np.float32).reshape(32, 4)
        table.add(base)
        ids = np.array([4, 11], np.int32)
        before = _server_gets()
        table.prefetch_rows_async(ids)  # not waited: maybe in flight
        got = table.get_rows(ids)
        np.testing.assert_array_equal(got, base[ids])
        assert _server_gets() - before == 1

    def test_duplicate_prefetches_dedup(self, cache_env):
        table = mv.create_matrix_table(32, 4)
        table.add(np.ones((32, 4), np.float32))
        ids = np.array([6, 13], np.int32)
        before = _server_gets()
        mids = {table.prefetch_rows_async(ids) for _ in range(4)}
        for mid in mids:
            assert table.wait(mid, timeout=10)
        # All four returned ids resolve, but at most one hit the wire
        # (later calls either dedup to the in-flight id or see the
        # landed cache).
        assert _server_gets() - before <= 1

    def test_joined_get_falls_back_after_invalidation(self, cache_env):
        # Pathological interleave: join an in-flight prefetch, then the
        # rows get invalidated by an own add before completion — the
        # joined Get must still complete with fresh values (forwarded
        # to the wire), never hang or serve the pre-add row.
        table = mv.create_matrix_table(16, 2)
        table.add(np.ones((16, 2), np.float32))
        ids = np.array([5], np.int32)
        pf = table.prefetch_rows_async(ids)
        table.wait(pf, timeout=10)
        # The deferred path: an in-flight prefetch of the row (its id
        # stands in the registry, its message is held back), the Get
        # that joins it, the row blocked by an own add, then the
        # prefetch's completion. The forwarded request carries the
        # joined Get's id, and its reply goes to that Get's sink
        # whatever other Get the table has issued since.
        out = np.full((1, 2), -1.0, np.float32)
        table._pf_rows[99] = ids
        tok = table._row_cache.begin_add(ids)  # invalidates row 5
        mid = table.get_rows_async(ids, out)
        assert table._pf_joined[99] == [mid]
        other = np.full((2, 2), -1.0, np.float32)
        table.get_rows(np.array([2, 3], np.int32), other)
        np.testing.assert_array_equal(out, np.full((1, 2), -1.0))
        table._on_prefetch_done(99)
        table._row_cache.finish_add(tok)
        assert table.wait(mid, timeout=10)
        np.testing.assert_array_equal(out, np.ones((1, 2)))
        np.testing.assert_array_equal(other, np.ones((2, 2)))
        assert not table._sinks


class TestArrayAndKV:
    def test_array_blob_cache_roundtrip(self, cache_env):
        table = mv.create_array_table(64)
        table.add(np.ones(64, np.float32))
        before = _server_gets()
        first = table.get().copy()
        hit = table.get().copy()
        np.testing.assert_array_equal(first, hit)
        assert _server_gets() - before == 1
        # Own add invalidates; the next get refetches the new state.
        table.add(np.ones(64, np.float32))
        np.testing.assert_array_equal(table.get(),
                                      2 * np.ones(64, np.float32))

    def test_array_prefetch(self, cache_env):
        table = mv.create_array_table(32)
        table.add(np.full(32, 3.0, np.float32))
        before = _server_gets()
        mid = table.prefetch_async()
        assert table.wait(mid, timeout=10)
        np.testing.assert_array_equal(table.get(),
                                      np.full(32, 3.0, np.float32))
        assert _server_gets() - before == 1

    def test_kv_snapshot_cache(self, cache_env):
        table = mv.create_kv_table()
        table.add([1, 9], [1.0, 2.0])
        before = _server_gets()
        assert table.get([1, 9])[1] == pytest.approx(1.0)
        assert table.get([1, 9])[9] == pytest.approx(2.0)
        assert _server_gets() - before == 1
        table.add([1], [10.0])
        assert table.get([1, 9])[1] == pytest.approx(11.0)


class TestMultiServer:
    def test_two_servers_cache_correctness(self):
        # Rows spanning both servers' ranges: per-server version
        # tracking, own-write visibility, and hits across shards.
        def body(rank):
            table = mv.create_matrix_table(10, 3)
            zoo = mv.current_zoo()
            base = np.arange(30, dtype=np.float32).reshape(10, 3)
            if rank == 0:
                table.add(base)
            zoo.barrier()
            ids = np.array([1, 8], np.int32)  # one row per server
            first = table.get_rows(ids).copy()
            hit = table.get_rows(ids).copy()
            ok = (np.array_equal(first, base[ids])
                  and np.array_equal(hit, base[ids]))
            zoo.barrier()
            if rank == 1:
                table.add_rows(ids, np.ones((2, 3), np.float32))
                own = table.get_rows(ids)  # read-your-writes, 2 shards
                ok = ok and np.array_equal(own, base[ids] + 1.0)
            zoo.barrier()
            return ok, table._row_cache.hits

        results = LocalCluster(2, argv=["-max_get_staleness=4"]).run(body)
        assert all(ok for ok, _ in results)
        assert all(hits >= 1 for _, hits in results)

    def test_bounded_staleness_under_peer_writes(self):
        # A peer's adds bump the version; once this worker OBSERVES the
        # newer version (via its own traffic), entries older than the
        # bound stop serving. With bound=1 and two observed peer adds,
        # the cached entry must be refetched.
        def body(rank):
            table = mv.create_matrix_table(8, 2)
            zoo = mv.current_zoo()
            ids = np.array([2], np.int32)
            if rank == 0:
                table.get_rows(ids)  # cache at version 0
            zoo.barrier()
            if rank == 1:
                for _ in range(2):
                    table.add_rows(ids, np.ones((1, 2), np.float32))
            zoo.barrier()
            if rank == 0:
                # Observe the head version through an uncached row of
                # the SAME server shard (rows 0-3 on server 0; version
                # stamps are per shard), then the stale entry (2
                # versions behind > bound 1) must miss and refetch.
                table.get_rows(np.array([3], np.int32))
                got = table.get_rows(ids)
                return got.tolist()
            return None

        results = LocalCluster(
            2, argv=["-max_get_staleness=1"]).run(body)
        assert results[0] == [[2.0, 2.0]]


class TestPSTrainerPrefetch:
    def test_host_path_trainer_prefetches_and_trains(self, tmp_path):
        # The wordembedding PS loop's double-buffer: with the cache on
        # and the host (wire-shaped) path forced, train_batches must
        # issue prefetches for batch i+1 while batch i runs, and the
        # model must still train (finite decreasing-ish loss, moved
        # embeddings).
        from multiverso_tpu.models.wordembedding import (
            Dictionary, PSWord2Vec, Word2VecConfig, iter_pair_batches)
        path = tmp_path / "corpus.txt"
        rng = np.random.default_rng(0)
        words = [f"w{i}" for i in range(30)]
        path.write_text("\n".join(
            " ".join(rng.choice(words, size=12)) for _ in range(120)))
        mv.init([])
        set_flag("max_get_staleness", 8)
        d = Dictionary.build(str(path), min_count=1)
        config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                                negative=2, sample=0, batch_size=256)
        model = PSWord2Vec(config, d)
        # Force the host-buffer pull/push path (in-process tests are
        # device-path by default; remote workers take this branch).
        model._device_path = False
        model._use_prefetch = True
        before = Dashboard.get("CLIENT_CACHE_PREFETCH").count
        loss, pairs = model.train_batches(iter_pair_batches(
            d, str(path), batch_size=256, window=2, subsample=0))
        assert np.isfinite(loss) and pairs > 0
        assert Dashboard.get("CLIENT_CACHE_PREFETCH").count > before
        emb = model.embeddings
        assert np.abs(emb).sum() > 0
        mv.shutdown()


class TestErrorReaping:
    def test_fire_and_forget_failures_bounded(self, env):
        # Satellite: never-waited failed requests must not leak error
        # entries until shutdown.
        from multiverso_tpu.core.blob import Blob
        from multiverso_tpu.tables import table_interface as ti
        table = mv.create_matrix_table(8, 2)
        cap = ti._MAX_RETAINED_ERRORS
        for i in range(cap + 60):
            # Raw API bypasses caller-side checks; partition fails in
            # the worker actor and records an error nobody waits for.
            table.get_async_raw(
                Blob(np.array([-9], np.int32).view(np.uint8)))
        # Drain: a waited request forces the worker actor through the
        # backlog before we inspect.
        table.add(np.ones((8, 2), np.float32))
        assert len(table._errors) <= cap + 1
        # The table remains fully usable and errors still surface for
        # requests that ARE waited.
        from multiverso_tpu.tables.table_interface import \
            TableRequestError
        mid = table.get_async_raw(
            Blob(np.array([-9], np.int32).view(np.uint8)))
        with pytest.raises(TableRequestError):
            table.wait(mid)


# -- place_rows: a reply shard into the caller's buffer -----------------------

#: name -> (request, the shard's keys, the counter one call moves)
_PLACEMENTS = {
    "whole, distinct sorted": ([1, 3, 5, 9], [1, 3, 5, 9], "DIRECT"),
    "whole, padded tail of repeats": ([2, 4, 9, 9, 9], [2, 4, 9, 9, 9],
                                      "DIRECT"),
    "whole, unsorted request": ([9, 2, 4, 2], [9, 2, 4, 2], "DIRECT"),
    "run at the head": ([1, 3, 5, 7, 9], [1, 3], "DIRECT"),
    "run in the middle": ([1, 3, 5, 7, 9], [3, 5, 7], "DIRECT"),
    "run at the tail": ([1, 3, 5, 7, 9], [7, 9], "DIRECT"),
    "run that holds its repeats": ([1, 3, 3, 5, 5, 8], [3, 3, 5, 5],
                                   "DIRECT"),
    # a bisection of this request for 5 lands on the run at position 2
    "run refused: unsorted request": ([5, 1, 5, 7, 9], [5, 7], "PLACED"),
    "run refused: last id repeats past it": ([5, 7, 7], [5, 7], "PLACED"),
    "same ids, another order": ([1, 3, 5], [5, 1, 3], "PLACED"),
    "subset keys": ([1, 3, 5, 7, 9, 3], [9, 3], "PLACED"),
    "keys absent from the request": ([1, 3, 5], [2, 4], "PLACED"),
    "more keys than positions": ([3, 5], [1, 3, 5, 7], "PLACED"),
    "empty keys": ([1, 3, 5], [], None),
    "empty request": ([], [1, 3], None),
}

_COLS = 6


def _row_values(keys, layout):
    """One row a key, a function of the key (so equal ids carry equal
    rows, as a table's do), in the memory layout named."""
    rows = (np.asarray(keys, np.float32)[:, None] * 10
            + np.arange(_COLS, dtype=np.float32))
    if layout == "fortran":
        rows = np.asfortranarray(rows)
        assert len(keys) < 2 or not rows.flags["C_CONTIGUOUS"]
    elif layout == "column slice":
        wide = np.full((len(keys), 128), -7.0, np.float32)
        wide[:, 40:40 + _COLS] = rows
        rows = wide[:, 40:40 + _COLS]
        assert not len(keys) or not rows.flags["C_CONTIGUOUS"]
    return rows


@pytest.mark.parametrize("layout", ["c", "fortran", "column slice"])
@pytest.mark.parametrize("case", sorted(_PLACEMENTS))
def test_place_rows_is_the_per_position_loop(case, layout):
    """Whichever form place_rows picks from ``keys`` and ``req``, every
    position whose id is in the shard holds that id's row, every other
    keeps what it held, and the counter says which form ran."""
    from multiverso_tpu.tables import client_cache
    req, keys, counted = _PLACEMENTS[case]
    req = np.asarray(req, np.int32)
    keys = np.asarray(keys, np.int32)
    values = _row_values(keys, layout)
    sentinel = -1.0
    want = np.full((req.size, _COLS), sentinel, np.float32)
    for pos, row_id in enumerate(req.tolist()):
        where = np.flatnonzero(keys == row_id)
        if where.size:
            want[pos] = values[where[0]]
    out = np.full((req.size, _COLS), sentinel, np.float32)
    names = {"DIRECT": client_cache.DIRECT, "PLACED": client_cache.PLACED}
    before = {k: Dashboard.get(n).count for k, n in names.items()}
    client_cache.place_rows(keys, values, req, out)
    moved = {k: Dashboard.get(n).count - before[k] for k, n in names.items()}
    np.testing.assert_array_equal(out, want)
    assert moved == {k: int(k == counted) for k in names}


_DIRECT = sorted(name for name, (_, _, counted) in _PLACEMENTS.items()
                 if counted == "DIRECT")


def _large_direct(name):
    """The three direct shapes at a size worth cutting: (request, keys)."""
    rng = np.random.default_rng(11)
    if name == "keys are the request":
        req = rng.integers(0, 500, 4000)
        return req, req
    if name == "thousands of repeats of the last id":
        req = np.concatenate([np.sort(rng.choice(499, 96, replace=False)),
                              np.full(4000, 499)])
        return req, req
    assert name == "a maximal run at an offset"
    req = np.sort(rng.integers(0, 500, 6000))
    mine = (req >= 200) & (req < 300)  # one server's bucket
    assert 0 < np.flatnonzero(mine)[0]
    return req, req[mine]


@pytest.mark.parametrize("layout", ["c", "fortran", "column slice"])
@pytest.mark.parametrize("n_pieces", [1, 2, 5])
@pytest.mark.parametrize("case", _DIRECT + [
    "keys are the request", "thousands of repeats of the last id",
    "a maximal run at an offset"])
def test_a_run_placed_in_pieces_is_the_run_placed_whole(case, layout,
                                                        n_pieces):
    """``place_run`` with the shard's rows handed over as row-range
    pieces, an iterator's, fills the buffer ``place_rows`` fills from
    the whole array, bit for bit, and counts the one direct shard."""
    from multiverso_tpu.tables import client_cache
    req, keys = _PLACEMENTS[case][:2] if case in _PLACEMENTS \
        else _large_direct(case)
    req, keys = np.asarray(req, np.int32), np.asarray(keys, np.int32)
    values = _row_values(keys, layout)
    whole = np.full((req.size, _COLS), -1.0, np.float32)
    client_cache.place_rows(keys, values, req, whole)
    start = client_cache.run_start(keys, req)
    assert start >= 0
    rows = -(-keys.size // n_pieces)
    handed = []

    def pieces():
        for first in range(0, keys.size, rows):
            handed.append(first)
            yield first, values[first:first + rows]

    out = np.full((req.size, _COLS), -1.0, np.float32)
    before = Dashboard.get(client_cache.DIRECT).count
    client_cache.place_run(start, pieces(), out)
    assert Dashboard.get(client_cache.DIRECT).count - before == 1
    assert len(handed) == -(-keys.size // rows)
    assert out.tobytes() == whole.tobytes()


# ---------------------------------------------------------------------------
# An inactive cache's fence token: the servers named from the ids' two
# ends (RowCache._fence_servers).

_FENCE_ROWS = 64


def _division(servers):
    """The frozen division rule over ``_FENCE_ROWS`` rows."""
    length = _FENCE_ROWS // servers

    def server_of(rows):
        return np.minimum(np.asarray(rows) // length, servers - 1)
    return server_of


def _inactive_cache(servers, server_of=None, rises=True):
    from multiverso_tpu.tables.client_cache import RowCache, VersionTracker
    return RowCache(0, server_of or _division(servers), servers,
                    VersionTracker(),
                    server_of_rises=(lambda: rises)
                    if rises is not None else None)


def _fence_ids(kind, servers, rng):
    """Ids of one ``kind`` and whether they touch every server between
    their ends."""
    length = _FENCE_ROWS // servers
    if kind == "anywhere":
        ids = rng.integers(0, _FENCE_ROWS, size=int(rng.integers(1, 40)))
        return ids, None
    if kind == "one server":
        sid = int(rng.integers(0, servers))
        return rng.integers(sid * length, (sid + 1) * length, size=7), True
    if kind == "every server between":
        first = int(rng.integers(0, servers))
        last = int(rng.integers(first, servers))
        ids = np.concatenate([
            rng.integers(s * length, (s + 1) * length, size=3)
            for s in range(first, last + 1)])
        return ids, True
    assert kind == "skips a server"  # the two outermost ranges only
    return np.concatenate([rng.integers(0, length, size=4),
                           rng.integers(_FENCE_ROWS - length, _FENCE_ROWS,
                                        size=4)]), servers <= 2


@pytest.mark.parametrize("order", ["sorted", "unsorted"])
@pytest.mark.parametrize("kind", ["anywhere", "one server",
                                  "every server between",
                                  "skips a server"])
@pytest.mark.parametrize("servers", [1, 2, 4])
def test_inactive_fence_names_a_superset_of_the_owners(servers, kind,
                                                       order):
    """The token's servers hold every owner of the ids; they ARE the
    owners with one server and wherever the ids touch every server
    between their ends. With or without the caller's ``ends`` the token
    is the same."""
    rng = np.random.default_rng(servers * 31 + len(kind) + len(order))
    server_of = _division(servers)
    cache = _inactive_cache(servers)
    for _ in range(25):
        ids, exact = _fence_ids(kind, servers, rng)
        ids = np.sort(ids) if order == "sorted" else rng.permutation(ids)
        ids = ids.astype(np.int32)
        owners = np.unique(server_of(ids)).tolist()
        kind_, sids = cache.begin_add(ids, (int(ids.min()), int(ids.max())))
        assert kind_ == "fence"
        assert sids == sorted(set(sids))
        assert set(sids) >= set(owners)
        if servers == 1 or exact:
            assert sids == owners
        if exact is None and len(owners) == owners[-1] - owners[0] + 1:
            assert sids == owners
        assert cache.begin_add(ids) == ("fence", sids)
        assert cache.begin_add(ids.tolist()) == ("fence", sids)


@pytest.mark.parametrize("servers", [1, 2, 4])
@pytest.mark.parametrize("empty", [np.zeros(0, np.int32), []],
                         ids=["array", "list"])
def test_inactive_fence_of_no_ids_names_no_server(servers, empty):
    cache = _inactive_cache(servers)
    assert cache.begin_add(empty) == ("fence", [])
    assert cache.begin_add(None) == ("fence", list(range(servers)))


@pytest.mark.parametrize("rises", [False, None],
                         ids=["live shard map", "no promise"])
@pytest.mark.parametrize("servers", [1, 2, 4])
def test_inactive_fence_names_every_server_where_owners_may_interleave(
        servers, rises):
    """A ``server_of`` that may fall as the row grows (a live shard map;
    a cache built with no word about it) cannot be read at two ids: every
    server is fenced, as for a whole-table Add."""
    interleaved = lambda rows: np.asarray(rows) // 4 % servers  # noqa: E731
    cache = _inactive_cache(servers, interleaved, rises)
    ids = np.array([5, 6], np.int32)  # one owner: server 1 % servers
    assert cache.begin_add(ids, (5, 6)) == ("fence", list(range(servers)))
    assert cache.begin_add(ids) == ("fence", list(range(servers)))


@pytest.mark.parametrize("with_ends", [True, False])
@pytest.mark.parametrize("servers", [1, 4])
def test_inactive_begin_add_makes_no_array_of_the_rows(servers, with_ends,
                                                       monkeypatch):
    """A million ids: ``server_of`` sees at most two of them, nothing is
    sorted, and the ids are never copied to another type."""
    rows = 1_000_000
    seen = []

    def server_of(ids):
        seen.append(np.size(ids))
        return np.minimum(np.asarray(ids) // (rows // servers), servers - 1)

    def refuse(*_args, **_kwargs):
        raise AssertionError("the inactive branch went over the rows")

    cache = _inactive_cache(servers, server_of)
    ids = np.arange(rows, dtype=np.int32)[::-1]
    monkeypatch.setattr(np, "unique", refuse)
    monkeypatch.setattr(np, "sort", refuse)
    monkeypatch.setattr(np, "argsort", refuse)
    ends = (0, rows - 1) if with_ends else None
    assert cache.begin_add(ids, ends) == ("fence", list(range(servers)))
    assert max(seen, default=0) <= 2
    assert len(seen) <= 1


@pytest.mark.parametrize("servers", [1, 2])
def test_host_row_add_on_an_inactive_cache_then_live_activation(servers):
    """``add_rows_async`` with host ids while the cache is inactive, then
    ``-max_get_staleness`` raised live, then Gets: an acknowledged Add is
    in every later Get, before and after the cache serves; ids out of
    range still fail in the caller."""
    from multiverso_tpu.util import configure
    rows, cols = 32, 3

    def body(rank):
        table = mv.create_matrix_table(rows, cols)
        zoo = mv.current_zoo()
        zoo.barrier()
        if rank == 0:
            cache = table._row_cache
            assert not cache.active
            want = np.zeros((rows, cols), np.float32)
            everything = np.arange(rows, dtype=np.int32)
            # On the last server alone, then across the table, unsorted.
            for ids in ([rows - 1, rows - 3], [rows - 2, 1, 7, 1]):
                ids = np.asarray(ids, np.int32)
                delta = np.full((ids.size, cols), 1.5, np.float32)
                assert table.wait(table.add_rows_async(ids, delta),
                                  timeout=20)
                np.add.at(want, ids, delta)
            for bad in ([rows], [-1, 3]):
                with pytest.raises(Exception, match="out of range"):
                    table.add_rows_async(
                        np.asarray(bad, np.int32),
                        np.zeros((len(bad), cols), np.float32))
            try:
                configure.apply_tunable("max_get_staleness", 8)
                assert cache.active
                np.testing.assert_array_equal(
                    table.get_rows(everything), want)
                # Served from the cache now; an own Add still shows.
                np.testing.assert_array_equal(
                    table.get_rows(everything), want)
                ids = np.array([rows - 1, 0], np.int32)
                table.add_rows(ids, np.ones((2, cols), np.float32))
                want[ids] += 1.0
                np.testing.assert_array_equal(
                    table.get_rows(everything), want)
            finally:
                configure.apply_tunable("max_get_staleness", 0)
        zoo.barrier()
        return True

    if servers == 1:
        mv.init([])
        try:
            assert body(0)
        finally:
            mv.shutdown()
    else:
        assert LocalCluster(servers).run(body)[0]

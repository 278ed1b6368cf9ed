"""Online serving tier (docs/SERVING.md): shared HTTP base, admission
control, mailbox-depth observability, the serving frontend's
endpoints + version/staleness metadata, and the acceptance invariant —
every served response respects the configured staleness bound while a
trainer concurrently pushes Adds."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.io.http_server import (HttpError, HttpServer,
                                           Response, json_response)
from multiverso_tpu.serving.admission import (AdmissionController,
                                              ShedError)
from multiverso_tpu.serving.frontend import ServingFrontend
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.dashboard import Dashboard, reset_samples, samples
from multiverso_tpu.util.mt_queue import MtQueue
from multiverso_tpu.util.net_util import free_listen_port


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, dict(resp.headers), json.loads(resp.read())


def _http_error(url, timeout=10):
    with pytest.raises(urllib.error.HTTPError) as exc:
        urllib.request.urlopen(url, timeout=timeout)
    err = exc.value
    body = json.loads(err.read())
    return err.code, dict(err.headers), body


# ---------------------------------------------------------------------------
# shared stdlib HTTP base (io/http_server.py)
# ---------------------------------------------------------------------------

class TestHttpServerBase:
    def _server(self, resolve):
        return HttpServer(0, resolve, host="127.0.0.1", name="test-http")

    def test_query_params_and_custom_headers(self):
        def resolve(path):
            if path != "/echo":
                return None
            return lambda query: json_response(
                {"q": query}, headers={"X-Test": "yes"})
        server = self._server(resolve)
        try:
            status, headers, doc = _get(
                f"http://127.0.0.1:{server.port}/echo?a=1&b=two&a=3")
            assert status == 200
            assert headers["X-Test"] == "yes"
            assert doc == {"q": {"a": "3", "b": "two"}}  # last wins
        finally:
            server.stop()

    def test_http_error_carries_status_headers_and_extra(self):
        def resolve(path):
            def handler(query):
                raise HttpError(429, "too busy",
                                headers={"Retry-After": "1"},
                                extra={"retry_after_s": 0.25})
            return handler
        server = self._server(resolve)
        try:
            code, headers, body = _http_error(
                f"http://127.0.0.1:{server.port}/x")
            assert code == 429
            assert headers["Retry-After"] == "1"
            assert body["retry_after_s"] == 0.25
            assert "too busy" in body["error"]
        finally:
            server.stop()

    def test_unknown_path_404_lists_describe(self):
        server = self._server(lambda path: None)
        try:
            code, _, body = _http_error(
                f"http://127.0.0.1:{server.port}/nope")
            assert code == 404
            assert "test-http" in body["error"]  # default describe()
        finally:
            server.stop()

    def test_handler_exception_is_500(self):
        def resolve(path):
            def handler(query):
                raise RuntimeError("broken")
            return handler
        server = self._server(resolve)
        try:
            code, _, body = _http_error(
                f"http://127.0.0.1:{server.port}/x")
            assert code == 500 and "broken" in body["error"]
        finally:
            server.stop()

    def test_non_200_response_passthrough(self):
        def resolve(path):
            return lambda query: Response(b"made", "text/plain",
                                          status=201)
        server = self._server(resolve)
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/x",
                    timeout=10) as resp:
                assert resp.status == 201 and resp.read() == b"made"
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# admission control (serving/admission.py)
# ---------------------------------------------------------------------------

class TestAdmission:
    def test_inflight_cap_sheds_with_retryable_error(self):
        adm = AdmissionController(max_inflight=1, shed_depth=0,
                                  retry_after_s=0.125)
        adm.admit("rows")
        with pytest.raises(ShedError) as exc:
            adm.admit("rows")
        assert exc.value.status == 429
        assert exc.value.retry_after_s == 0.125
        assert "in flight" in str(exc.value)
        # Caps are per endpoint: a different endpoint still admits.
        adm.admit("neighbors")
        adm.release("neighbors")
        adm.release("rows")
        adm.admit("rows")  # freed slot admits again
        adm.release("rows")
        stats = adm.stats()
        assert stats["shed"] == 1 and stats["admitted"] == 3
        assert stats["inflight"] == {}

    def test_depth_watermark_sheds(self):
        depth = [0]
        adm = AdmissionController(depth_of=lambda: depth[0],
                                  max_inflight=0, shed_depth=10)
        adm.admit("rows")
        adm.release("rows")
        depth[0] = 11
        with pytest.raises(ShedError) as exc:
            adm.admit("rows")
        assert "watermark" in str(exc.value)
        # shed_depth=0 disables the gate entirely.
        adm.configure(shed_depth=0)
        adm.admit("rows")
        adm.release("rows")

    def test_drain_rejects_new_with_503(self):
        adm = AdmissionController(max_inflight=0, shed_depth=0)
        assert adm.begin_drain(timeout_s=0.1) is True  # nothing in flight
        with pytest.raises(ShedError) as exc:
            adm.admit("rows")
        assert exc.value.status == 503
        assert "draining" in str(exc.value)

    def test_drain_waits_for_inflight(self):
        adm = AdmissionController(max_inflight=0, shed_depth=0)
        adm.admit("rows")
        t = threading.Timer(0.3, adm.release, args=("rows",))
        t.start()
        t0 = time.monotonic()
        assert adm.begin_drain(timeout_s=5.0) is True
        assert time.monotonic() - t0 >= 0.2  # actually waited
        t.join()

    def test_drain_timeout_reports_false(self):
        adm = AdmissionController(max_inflight=0, shed_depth=0)
        adm.admit("rows")
        assert adm.begin_drain(timeout_s=0.2) is False
        adm.release("rows")


# ---------------------------------------------------------------------------
# mailbox depth observability (util/mt_queue.py)
# ---------------------------------------------------------------------------

class TestMtQueueDepth:
    def test_high_watermark_tracks_and_resets(self):
        q = MtQueue()
        assert q.depth_high_watermark == 0
        for i in range(5):
            q.push(i)
        q.pop()
        q.pop()
        assert q.depth_high_watermark == 5  # monotonic past pops
        q.reset_depth_watermark()
        assert q.depth_high_watermark == 3  # re-anchored at current
        q.push(99)
        assert q.depth_high_watermark == 4

    def test_track_depth_records_samples(self):
        reset_samples()
        q = MtQueue()
        q.track_depth("MAILBOX_DEPTH[test]")
        for i in range(4):
            q.push(i)
        reservoir = samples("MAILBOX_DEPTH[test]")
        assert reservoir.count == 4
        snap = reservoir.snapshot()
        assert snap["max"] == 4.0 and snap["p50"] >= 1.0
        reset_samples()

    def test_server_and_worker_mailboxes_report_depth(self):
        """With a consumer enabled (-metrics_interval_s here; serving
        would too), the server/worker mailboxes feed the
        MAILBOX_DEPTH[*] family."""
        reset_samples()
        mv.init(["-metrics_interval_s=30"])
        try:
            table = mv.create_matrix_table(16, 4)
            table.add_rows(np.arange(4, dtype=np.int32),
                           np.ones((4, 4), np.float32))
            table.get_rows(np.arange(4, dtype=np.int32))
        finally:
            mv.shutdown()
        assert samples("MAILBOX_DEPTH[worker]").count > 0
        assert samples("MAILBOX_DEPTH[server]").count > 0
        reset_samples()

    def test_depth_sampling_off_without_a_consumer(self):
        """Training-only deployments (no serving, no metrics export)
        must not pay the per-push reservoir append: the samples gate
        stays closed at default flags (the high watermark alone is
        always tracked)."""
        reset_samples()
        mv.init([])
        try:
            table = mv.create_matrix_table(16, 4)
            table.add_rows(np.arange(4, dtype=np.int32),
                           np.ones((4, 4), np.float32))
            table.get_rows(np.arange(4, dtype=np.int32))
            worker = mv.current_zoo()._actors["worker"]
            assert worker.mailbox.depth_high_watermark > 0
        finally:
            mv.shutdown()
        assert samples("MAILBOX_DEPTH[worker]").count == 0
        assert samples("MAILBOX_DEPTH[server]").count == 0
        reset_samples()


# ---------------------------------------------------------------------------
# the versioned serving read (tables/matrix_table.py)
# ---------------------------------------------------------------------------

class TestReadRowsVersioned:
    def test_metadata_with_cache(self):
        mv.init([])
        set_flag("max_get_staleness", 6)
        try:
            table = mv.create_matrix_table(32, 4)
            ids = np.arange(8, dtype=np.int32)
            table.add_rows(ids, np.ones((8, 4), np.float32))
            values, meta = table.read_rows_versioned(ids)
            assert np.allclose(values, 1.0)
            assert meta["staleness_bound"] == 6
            assert meta["cache_hit"] is False  # first read fetched
            assert meta["served_version"] <= meta["latest_version"]
            values, meta = table.read_rows_versioned(ids)
            assert meta["cache_hit"] is True
            assert meta["max_staleness"] <= 6
            # An Add ages the shard; the next read re-fetches only
            # once past the bound — here it still serves locally, and
            # the reported staleness reflects the aging.
            table.add_rows(np.asarray([30], np.int32),
                           np.ones((1, 4), np.float32))
            _, meta = table.read_rows_versioned(ids)
            assert meta["cache_hit"] is True
            assert 1 <= meta["max_staleness"] <= 6
        finally:
            mv.shutdown()

    def test_metadata_cache_disabled(self):
        mv.init([])  # default flags: no cache
        try:
            table = mv.create_matrix_table(32, 4)
            ids = np.arange(8, dtype=np.int32)
            table.add_rows(ids, np.ones((8, 4), np.float32))
            _, meta = table.read_rows_versioned(ids)
            assert meta["staleness_bound"] == 0
            assert meta["cache_hit"] is False
            assert meta["max_staleness"] == 0  # everything wire-fresh
        finally:
            mv.shutdown()


# ---------------------------------------------------------------------------
# serving frontend endpoints
# ---------------------------------------------------------------------------

@pytest.fixture
def serving_env():
    """In-process PS + frontend on an ephemeral port, cache enabled."""
    mv.init([])
    set_flag("max_get_staleness", 8)
    table = mv.create_matrix_table(128, 8)
    frontend = ServingFrontend(mv.current_zoo(), port=0,
                               host="127.0.0.1")
    frontend.register_table(
        "emb", table, vocab={f"w{i}": i for i in range(128)})
    ids = np.arange(128, dtype=np.int32)
    table.add_rows(ids, np.arange(128 * 8, dtype=np.float32)
                   .reshape(128, 8))
    base = f"http://127.0.0.1:{frontend.port}"
    yield frontend, table, base
    frontend.stop()
    mv.shutdown()


class TestServingFrontend:
    def test_rows_values_and_metadata(self, serving_env):
        frontend, table, base = serving_env
        status, headers, doc = _get(base + "/v1/tables/emb/rows"
                                         "?ids=3,5,3")
        assert status == 200
        expected = np.arange(128 * 8, dtype=np.float32).reshape(128, 8)
        assert np.allclose(np.asarray(doc["rows"]),
                           expected[[3, 5, 3]])
        assert doc["ids"] == [3, 5, 3]
        assert doc["staleness_bound"] == 8
        assert doc["served_version"] <= doc["latest_version"]
        assert doc["max_staleness"] <= doc["staleness_bound"]
        assert headers["X-MV-Version"] == str(doc["served_version"])
        assert headers["X-MV-Staleness-Bound"] == "8"
        assert headers["X-MV-Cache"] in ("hit", "miss")

    def test_cache_hit_marker_flips_miss_to_hit(self, serving_env):
        frontend, table, base = serving_env
        url = base + "/v1/tables/emb/rows?ids=7,9"
        _, headers1, doc1 = _get(url)
        _, headers2, doc2 = _get(url)
        assert doc1["cache_hit"] is False
        assert headers1["X-MV-Cache"] == "miss"
        assert doc2["cache_hit"] is True
        assert headers2["X-MV-Cache"] == "hit"

    def test_listing_and_status(self, serving_env):
        frontend, table, base = serving_env
        _, _, doc = _get(base + "/v1/tables")
        assert doc["tables"] == ["emb"]
        _, _, status = _get(base + "/v1/status")
        assert status["tables"]["emb"]["num_row"] == 128
        assert status["tables"]["emb"]["vocab"] is True
        assert status["admission"]["draining"] is False
        assert "worker" in status["mailboxes"]
        assert "server" in status["mailboxes"]

    def test_unknown_table_404(self, serving_env):
        frontend, table, base = serving_env
        code, _, body = _http_error(base + "/v1/tables/nope/rows"
                                         "?ids=1")
        assert code == 404 and "'emb'" in body["error"]

    def test_bad_ids_400(self, serving_env):
        frontend, table, base = serving_env
        for query in ("", "?ids=", "?ids=a,b", "?ids=4096",
                      "?ids=-1"):
            code, _, _ = _http_error(
                base + "/v1/tables/emb/rows" + query)
            assert code == 400, query
        frontend._max_rows = 2
        code, _, body = _http_error(base + "/v1/tables/emb/rows"
                                         "?ids=1,2,3")
        assert code == 400 and "serving_max_rows" in body["error"]

    def test_neighbors_cosine_order(self, serving_env):
        frontend, table, base = serving_env
        # Overwrite the WHOLE table with known directions: rows 0-3 in
        # the (x, y) plane at 0, 10, 50, 80 degrees, everything else
        # on the z axis (cosine 0 against the query and below row 3's
        # 0.17). Neighbors of row 0 must rank 1 over 2 over 3.
        all_ids = np.arange(128, dtype=np.int32)
        current = table.get_rows(all_ids)
        vecs = np.zeros((128, 8), np.float32)
        vecs[:, 2] = 1.0
        for i, deg in enumerate((0.0, 10.0, 50.0, 80.0)):
            vecs[i] = 0.0
            vecs[i, 0] = np.cos(np.radians(deg))
            vecs[i, 1] = np.sin(np.radians(deg))
        table.add_rows(all_ids, vecs - current)
        _, headers, doc = _get(base + "/v1/tables/emb/neighbors"
                                    "?word=w0&k=3")
        ranked = [n["id"] for n in doc["neighbors"]]
        assert ranked[:3] != [0] * 3 and 0 not in ranked  # not self
        assert ranked.index(1) < ranked.index(2) < ranked.index(3)
        assert doc["neighbors"][0]["word"] == "w1"
        assert doc["query"] == {"id": 0, "word": "w0"}
        assert doc["staleness_bound"] == 8
        assert headers["X-MV-Version"] == str(doc["served_version"])
        # Same query by id.
        _, _, by_id = _get(base + "/v1/tables/emb/neighbors?id=0&k=3")
        assert [n["id"] for n in by_id["neighbors"]] == ranked

    def test_neighbors_unknown_word_404_and_bad_query_400(
            self, serving_env):
        frontend, table, base = serving_env
        code, _, _ = _http_error(base + "/v1/tables/emb/neighbors"
                                      "?word=nope")
        assert code == 404
        code, _, _ = _http_error(base + "/v1/tables/emb/neighbors")
        assert code == 400
        code, _, _ = _http_error(base + "/v1/tables/emb/neighbors"
                                      "?id=9999")
        assert code == 400

    def test_neighbor_index_refresh_follows_staleness(self,
                                                      serving_env):
        frontend, table, base = serving_env
        _, _, first = _get(base + "/v1/tables/emb/neighbors?id=1")
        assert first["index_refreshed"] is True  # cold index builds
        _, _, second = _get(base + "/v1/tables/emb/neighbors?id=1")
        assert second["index_refreshed"] is False  # fresh enough
        # Age the shard past the bound: the index must rebuild.
        for _ in range(9):  # bound is 8
            table.add_rows(np.asarray([120], np.int32),
                           np.ones((1, 8), np.float32))
        _, _, third = _get(base + "/v1/tables/emb/neighbors?id=1")
        assert third["index_refreshed"] is True
        assert third["served_version"] > first["served_version"]

    def test_shed_is_429_with_retry_after(self, serving_env):
        frontend, table, base = serving_env
        shed_before = Dashboard.get("SERVING_SHED").count
        frontend.admission.configure(max_inflight=1,
                                     retry_after_s=0.25)
        frontend.admission.admit("rows")  # occupy the only slot
        try:
            code, headers, body = _http_error(
                base + "/v1/tables/emb/rows?ids=1")
        finally:
            frontend.admission.release("rows")
        assert code == 429
        assert headers["Retry-After"] == "1"  # ceil to whole seconds
        assert body["retry_after_s"] == 0.25  # exact in the body
        assert body["shed"] is True
        assert Dashboard.get("SERVING_SHED").count == shed_before + 1
        # The slot freed: the same request now serves.
        status, _, _ = _get(base + "/v1/tables/emb/rows?ids=1")
        assert status == 200

    def test_status_answers_while_saturated(self, serving_env):
        frontend, table, base = serving_env
        frontend.admission.configure(max_inflight=1)
        frontend.admission.admit("rows")
        try:
            status, _, doc = _get(base + "/v1/status")
            assert status == 200
            assert doc["admission"]["inflight"] == {"rows": 1}
        finally:
            frontend.admission.release("rows")

    def test_many_keepalive_connections_held_open(self, serving_env):
        # The many-connection shape: clients that hold keep-alive
        # connections open and mostly sit idle. Every connection is
        # established at once, each answers on its own socket more
        # than once, and none of them costs a transport thread (the
        # event loops and ring writers are counted per peer rank).
        import http.client
        from multiverso_tpu.runtime import thread_roles
        frontend, table, base = serving_env

        def transport_threads():
            alive = thread_roles.roles_alive()
            return (alive.get(thread_roles.EVENTLOOP, 0)
                    + alive.get(thread_roles.WRITER, 0))

        before = transport_threads()
        conns = [http.client.HTTPConnection("127.0.0.1", frontend.port,
                                            timeout=30)
                 for _ in range(32)]
        try:
            for conn in conns:
                conn.connect()
            for _ in range(2):
                for conn in conns:
                    conn.request("GET", "/v1/tables/emb/rows?ids=1,5,9")
                    resp = conn.getresponse()
                    doc = json.loads(resp.read())
                    assert resp.status == 200, doc
                    assert doc["ids"] == [1, 5, 9]
                    assert doc["max_staleness"] <= doc["staleness_bound"]
            assert transport_threads() == before
        finally:
            for conn in conns:
                conn.close()

    def test_graceful_drain_finishes_inflight(self, serving_env):
        frontend, table, base = serving_env
        orig = table.read_rows_versioned

        def slow_read(row_ids, out=None):
            time.sleep(0.5)
            return orig(row_ids, out)
        table.read_rows_versioned = slow_read
        result = {}

        def request():
            try:
                result["resp"] = _get(base + "/v1/tables/emb/rows"
                                           "?ids=1,2")
            except Exception as exc:  # noqa: BLE001
                result["error"] = exc
        t = threading.Thread(target=request)
        t.start()
        time.sleep(0.15)  # request is inside the slow read
        frontend.stop()   # must drain, not cut the connection
        t.join(timeout=10)
        assert "error" not in result, result
        assert result["resp"][0] == 200
        # The port is closed now.
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(base + "/v1/status", timeout=2)


# ---------------------------------------------------------------------------
# zoo wiring (-serving_port + mv.serve_table)
# ---------------------------------------------------------------------------

class TestZooWiring:
    def test_flag_starts_frontend_and_serve_table_registers(self):
        port = free_listen_port()
        mv.init([f"-serving_port={port}", "-max_get_staleness=4"])
        try:
            zoo = mv.current_zoo()
            assert zoo.serving is not None
            table = mv.create_matrix_table(16, 4)
            mv.serve_table("t", table)
            table.add_rows(np.arange(4, dtype=np.int32),
                           np.ones((4, 4), np.float32))
            _, _, doc = _get(f"http://127.0.0.1:{port}"
                             f"/v1/tables/t/rows?ids=0,1")
            assert np.allclose(doc["rows"], 1.0)
        finally:
            mv.shutdown()
        with pytest.raises(urllib.error.URLError):
            urllib.request.urlopen(f"http://127.0.0.1:{port}"
                                   f"/v1/status", timeout=2)

    def test_serving_off_by_default_and_serve_table_noop(self):
        mv.init([])
        try:
            assert mv.current_zoo().serving is None
            table = mv.create_matrix_table(8, 2)
            mv.serve_table("t", table)  # must not raise
        finally:
            mv.shutdown()

    def test_non_matrix_table_rejected(self):
        mv.init([])
        try:
            frontend = ServingFrontend(mv.current_zoo(), port=0,
                                       host="127.0.0.1")
            try:
                array_table = mv.create_array_table(8)
                with pytest.raises(ValueError,
                                   match="read_rows_versioned"):
                    frontend.register_table("a", array_table)
            finally:
                frontend.stop()
        finally:
            mv.shutdown()


# ---------------------------------------------------------------------------
# acceptance: staleness bound respected while Adds land concurrently
# ---------------------------------------------------------------------------

def test_staleness_bound_respected_under_concurrent_adds():
    """The PR's serving acceptance invariant: a client hammering the
    rows endpoint while a trainer thread pushes Adds must see, on
    EVERY response, max_staleness <= staleness_bound — and both cache
    hits and misses must actually occur (the adds age entries, the
    re-fetches refresh them), proving the bound is doing work rather
    than the cache sitting idle."""
    bound = 4
    mv.init([])
    set_flag("max_get_staleness", bound)
    table = mv.create_matrix_table(256, 8)
    frontend = ServingFrontend(mv.current_zoo(), port=0,
                               host="127.0.0.1")
    frontend.register_table("emb", table)
    all_ids = np.arange(256, dtype=np.int32)
    table.add_rows(all_ids, np.ones((256, 8), np.float32))
    base = f"http://127.0.0.1:{frontend.port}"

    stop = threading.Event()
    trainer_adds = [0]

    def trainer():
        rng = np.random.default_rng(3)
        while not stop.is_set():
            ids = np.unique(rng.integers(0, 256, size=8)) \
                .astype(np.int32)
            table.add_rows(ids, np.full((ids.size, 8), 1e-3,
                                        np.float32))
            trainer_adds[0] += 1
            time.sleep(0.002)

    thread = threading.Thread(target=trainer, daemon=True)
    thread.start()
    rng = np.random.default_rng(4)
    hits = misses = 0
    try:
        for _ in range(150):
            ids = np.unique((rng.zipf(1.6, 6) - 1) % 256)
            _, _, doc = _get(base + "/v1/tables/emb/rows?ids="
                             + ",".join(str(i) for i in ids))
            assert doc["staleness_bound"] == bound
            assert doc["max_staleness"] <= bound, doc
            assert doc["served_version"] <= doc["latest_version"]
            if doc["cache_hit"]:
                hits += 1
            else:
                misses += 1
    finally:
        stop.set()
        thread.join(timeout=10)
        frontend.stop()
        mv.shutdown()
    assert trainer_adds[0] > 0
    # Both paths exercised: the adds aged entries (misses) and the
    # cache served within the bound between them (hits).
    assert misses > 0, (hits, misses, trainer_adds[0])
    assert hits > 0, (hits, misses, trainer_adds[0])

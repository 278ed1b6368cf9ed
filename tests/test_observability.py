"""Observability layer tests (docs/OBSERVABILITY.md).

The wire header's reserved slot 9 (what an older, sampling peer sends
there is ignored), the metrics export/aggregation pipeline
(runtime/metrics.py), the HTTP scrape surface (io/metrics_http.py),
and the acceptance integration: a 3-process TCP PS cluster
(1 worker + 2 servers) whose /metrics scrape exposes
cluster-aggregated SERVER_PROCESS_GET counts equal to the sum of the
per-rank dumps.
"""

import json
import re
import struct
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import (HEADER_SIZE, Message, MsgType,
                                         WIRE_SLOTS)
from multiverso_tpu.io.metrics_http import (MetricsHttpServer,
                                            prometheus_route)
from multiverso_tpu.runtime.metrics import (ClusterMetrics,
                                            parse_report,
                                            split_family)
from multiverso_tpu.runtime.tcp import _serialize
from multiverso_tpu.util.dashboard import (Dashboard, metrics_snapshot,
                                           reset_samples, samples)

from test_net_integration import run_cluster, write_machine_file


@pytest.fixture(autouse=True)
def _clean_registries():
    Dashboard.reset()
    reset_samples()
    yield
    Dashboard.reset()
    reset_samples()


# ---------------------------------------------------------------------------
# wire: header slot 9 is reserved, the frames are what they were
# ---------------------------------------------------------------------------

def _serialize_9int(msg):
    """What the 9-int-header build put on the wire — the reference
    layout the byte-identity acceptance compares against."""
    blobs = [b.wire_bytes().tobytes() for b in msg.data]
    legacy = msg.header[:9]  # mvlint: ignore[wire-slot] - the legacy
    # 9-int layout is exactly what this helper reconstructs
    parts = [struct.pack("<9i", *[int(v) for v in legacy]),
             struct.pack("<I", len(blobs))]
    parts += [struct.pack("<Q", len(b)) for b in blobs]
    parts += blobs
    body = b"".join(parts)
    return struct.pack("<Q", len(body)) + body


class TestWirePlumbing:
    def test_trace_slot_registered(self):
        """The header keeps its ten ints; the tenth is reserved, in no
        registry."""
        assert HEADER_SIZE == 10
        assert sorted(WIRE_SLOTS.values()) == [5, 6, 7, 8]

    def test_untraced_wire_bytes_identical_modulo_header_bump(self):
        """Acceptance: the wire bytes of a Get/Add exchange are
        byte-identical to a 9-int-header build everywhere except the
        declared header-length bump — i.e. the frame differs ONLY by
        four zero bytes of header slot 9 and the total-length prefix
        that grows with them."""
        for msg_type in (MsgType.Request_Get, MsgType.Request_Add):
            msg = Message(src=0, dst=1, msg_type=msg_type,
                          table_id=2, msg_id=3)
            msg.push(Blob(np.arange(6, dtype=np.int32)
                          .view(np.uint8)))
            msg.push(Blob(np.linspace(0, 1, 5, dtype=np.float32)))
            frame = _serialize(msg)
            old = _serialize_9int(msg)
            # New frame: 4 extra bytes total, all in the header.
            (total,) = struct.unpack_from("<Q", frame, 0)
            (old_total,) = struct.unpack_from("<Q", old, 0)
            assert total == old_total + 4
            header = struct.unpack_from(f"<{HEADER_SIZE}i", frame, 8)
            assert header[9] == 0
            # Splicing the 10th header int out reproduces the old
            # frame exactly, byte for byte.
            spliced = struct.pack("<Q", old_total) \
                + frame[8:8 + 9 * 4] + frame[8 + 10 * 4:]
            assert spliced == old

    @pytest.mark.parametrize("stamped", [
        "Request_Get", "Request_Add", "Request_BatchAdd",
        "Reply_Get", "Reply_Add"])
    def test_an_older_peers_nonzero_slot_9_is_served_like_any_other(
            self, stamped, monkeypatch):
        """An older, sampling peer sends a request's trace id in header
        slot 9. Over real TCP (rank 0 the worker, rank 1 the server)
        every frame of one type goes out stamped as that peer would
        send it: the frame deserialises, the traffic gives the sums it
        gives unstamped, every waiter completes, and no other frame —
        the stamped requests' replies among them — carries anything but
        0 there."""
        from multiverso_tpu.runtime import actor as actors
        from multiverso_tpu.runtime import tcp
        from multiverso_tpu.runtime.cluster import LocalCluster
        from multiverso_tpu.util.net_util import free_listen_port
        slot_9 = tcp._LEN.size + 9 * 4
        seen = {}   # type -> the slot-9 values that arrived
        serialize_views, deserialize_frame = (tcp.serialize_views,
                                              tcp._deserialize_frame)

        def views_of_an_older_peer(msg):
            views, nbytes = serialize_views(msg)
            if msg.type == MsgType[stamped]:
                struct.pack_into("<i", views[0], slot_9, 4242)
            return views, nbytes

        def deserialize_and_note(body, lease):
            msg = deserialize_frame(body, lease)
            seen.setdefault(MsgType(msg.type).name, set()).add(
                struct.unpack_from(f"<{HEADER_SIZE}i", body, 0)[9])
            return msg

        monkeypatch.setattr(tcp, "serialize_views", views_of_an_older_peer)
        monkeypatch.setattr(tcp, "_deserialize_frame", deserialize_and_note)

        def body(rank):
            table = mv.create_matrix_table(16, 4)
            got = None
            if rank == 0:
                ids = np.arange(16, dtype=np.int32)
                ones = np.ones((16, 4), np.float32)
                # A burst the worker's thread pops slowly is staged
                # whole and leaves as one Request_BatchAdd; the Add
                # that follows alone, as a plain Request_Add.
                worker = mv.current_zoo()._actors[actors.WORKER]
                popped = worker._popped
                worker._popped = lambda m: (time.sleep(0.05), popped(m))
                for msg_id in [table.add_rows_async(ids, ones)
                               for _ in range(3)]:
                    table.wait(msg_id)
                table.add_rows(ids, ones)
                del worker._popped
                got = table.get_rows(ids)
            mv.barrier()
            return got

        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        got, _ = LocalCluster(
            2, roles=["worker", "server"],
            nets=[tcp.TcpNet(r, eps) for r in range(2)]).run(body)
        np.testing.assert_array_equal(got, np.full((16, 4), 4.0, np.float32))
        assert {"Request_Get", "Request_Add", "Request_BatchAdd",
                "Reply_Get", "Reply_Add", "Reply_BatchAdd"} <= set(seen)
        assert seen.pop(stamped) == {4242}
        assert set().union(*seen.values()) == {0}, seen


# ---------------------------------------------------------------------------
# metrics snapshot + cluster aggregation + prometheus rendering
# ---------------------------------------------------------------------------

PROM_LINE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"          # metric name
    r"(\{[a-zA-Z0-9_]+=\"[^\"]*\""        # first label
    r"(,[a-zA-Z0-9_]+=\"[^\"]*\")*\})?"   # more labels
    r" -?[0-9.eE+-]+(inf)?$")             # value


def validate_prometheus(text):
    """Line-level validation of the text exposition format; returns
    {(metric, frozenset(labels)): float value}."""
    series = {}
    for line in text.strip().splitlines():
        if line.startswith("#"):
            assert re.match(r"^# (HELP|TYPE) [a-zA-Z_:][a-zA-Z0-9_:]* ",
                            line), line
            continue
        assert PROM_LINE_RE.match(line), f"bad exposition line: {line}"
        name_labels, value = line.rsplit(" ", 1)
        name, _, labels = name_labels.partition("{")
        labels = labels.rstrip("}")
        key = (name, frozenset(labels.split(",")) if labels
               else frozenset())
        series[key] = float(value)
    return series


def _fake_report(rank, gets, window, older_rank=False):
    """A rank's report; an older rank's still carries the span events
    it shipped for the merged trace, which the controller ignores."""
    report = {"v": 1, "rank": rank,
              "monitors": {"SERVER_PROCESS_GET":
                           {"count": gets, "elapsed_ms": gets * 1.5}},
              "samples": {"DISPATCH_MS[d1]":
                          {"count": len(window), "recent": window}}}
    if older_rank:
        report["trace_events"] = [
            {"trace": 5, "name": "server_process_get", "ph": "X",
             "rank": rank, "ts": 1000, "dur": 10, "seq": rank}]
    return report


#: The cluster's snapshot is the same whether the ranks that report are
#: of this build or still ship a ``trace_events`` key.
OLDER_RANKS = pytest.mark.parametrize(
    "older_rank", [False, True], ids=["this_build", "older_rank"])


class TestClusterMetrics:
    def test_snapshot_is_versioned_and_complete(self):
        Dashboard.get("SERVER_PROCESS_GET").add(2.0)
        samples("DISPATCH_MS[d0]").add(1.25)
        snap = metrics_snapshot()
        assert snap["v"] == 1
        assert snap["monitors"]["SERVER_PROCESS_GET"]["count"] == 1
        assert snap["samples"]["DISPATCH_MS[d0]"]["recent"] == [1.25]

    def test_parse_report_rejects_foreign_versions(self):
        msg = Message(src=1, dst=0, msg_type=MsgType.Control_Metrics)
        msg.push(Blob(np.frombuffer(
            json.dumps({"v": 99, "rank": 1}).encode(),
            np.uint8).copy()))
        assert parse_report(msg) is None
        bad = Message(src=1, dst=0, msg_type=MsgType.Control_Metrics)
        bad.push(Blob(np.frombuffer(b"not json", np.uint8).copy()))
        assert parse_report(bad) is None
        assert parse_report(Message()) is None

    @OLDER_RANKS
    def test_cluster_sum_and_merged_percentiles(self, older_rank):
        cm = ClusterMetrics()
        cm.ingest(_fake_report(1, 30, [1.0, 2.0], older_rank))
        cm.ingest(_fake_report(2, 12, [100.0, 200.0], older_rank))
        # newest per rank wins
        cm.ingest(_fake_report(1, 31, [1.0, 2.0], older_rank))
        view = cm.cluster_view()
        agg = view["monitors_sum"]["SERVER_PROCESS_GET"]
        assert agg["count"] == 31 + 12
        merged = view["samples_merged"]["DISPATCH_MS[d1]"]
        assert merged["count"] == 4
        assert merged["max"] == 200.0
        assert merged["p50"] == 2.0  # nearest-rank over the union
        assert view["ranks"][2]["monitors"][
            "SERVER_PROCESS_GET"]["count"] == 12

    @OLDER_RANKS
    def test_prometheus_text_is_valid_and_sums(self, older_rank):
        cm = ClusterMetrics()
        cm.ingest(_fake_report(1, 30, [1.0], older_rank))
        cm.ingest(_fake_report(2, 12, [3.0], older_rank))
        series = validate_prometheus(cm.prometheus_text())
        name = 'name="SERVER_PROCESS_GET"'
        per_rank = [v for (metric, labels), v in series.items()
                    if metric == "mv_monitor_count_total"
                    and name in labels]
        assert sorted(per_rank) == [12.0, 30.0]
        total = series[("mv_cluster_monitor_count_total",
                        frozenset([name]))]
        assert total == sum(per_rank) == 42.0
        q99 = series[("mv_cluster_samples",
                      frozenset(['name="DISPATCH_MS"', 'key="d1"',
                                 'quantile="0.99"']))]
        assert q99 == 3.0

    def test_split_family(self):
        assert split_family("DISPATCH_MS[d1]") == ("DISPATCH_MS", "d1")
        assert split_family("SERVER_PROCESS_GET") \
            == ("SERVER_PROCESS_GET", "")


# ---------------------------------------------------------------------------
# HTTP scrape surface
# ---------------------------------------------------------------------------

class TestMetricsHttp:
    def test_routes_content_and_404(self):
        server = MetricsHttpServer(0, {
            "/metrics": prometheus_route(lambda: "mv_up 1\n"),
        }, host="127.0.0.1")
        try:
            base = f"http://127.0.0.1:{server.port}"
            with urllib.request.urlopen(f"{base}/metrics",
                                        timeout=10) as resp:
                assert resp.status == 200
                assert resp.headers["Content-Type"].startswith(
                    "text/plain; version=0.0.4")
                assert resp.read() == b"mv_up 1\n"
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{base}/nope", timeout=10)
            assert exc.value.code == 404
        finally:
            server.stop()

    def test_renderer_failure_is_a_500_not_a_crash(self):
        def boom():
            raise RuntimeError("broken renderer")
        server = MetricsHttpServer(0, {
            "/metrics": prometheus_route(boom)}, host="127.0.0.1")
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/metrics",
                    timeout=10)
            assert exc.value.code == 500
        finally:
            server.stop()


# ---------------------------------------------------------------------------
# acceptance: 3-process TCP cluster (1 worker + 2 servers)
# ---------------------------------------------------------------------------

def test_three_process_metrics_scrape(tmp_path):
    """The acceptance integration: metrics export over a real
    3-process TCP cluster. The worker writes the /metrics scrape to a
    file this process then validates: the Prometheus scrape is valid
    text exposition and its cluster-aggregated SERVER_PROCESS_GET
    equals the sum of the per-rank dumps the servers print."""
    from multiverso_tpu.util.net_util import free_listen_port
    n = 3
    mf, _ = write_machine_file(tmp_path, n)
    mport = free_listen_port()
    prom_path = tmp_path / "metrics.txt"
    common = f"""
role = "worker" if rank == 0 else "server"
mv.init(["-machine_file={mf}", "-rank=" + str(rank),
         "-ps_role=" + role,
         "-metrics_interval_s=0.2", "-metrics_port={mport}"])
from multiverso_tpu.runtime.zoo import current_zoo
from multiverso_tpu.util.dashboard import Dashboard
zoo = current_zoo()
table = mv.create_matrix_table(16, 4)
"""
    worker = common + f"""
import time, urllib.request
ids = np.arange(16, dtype=np.int32)   # spans BOTH server shards
table.add_rows(ids, np.ones((16, 4), np.float32))
for _ in range(20):
    out = table.get_rows(ids)
assert out.shape == (16, 4) and out.sum() > 0
mv.barrier()            # traffic done cluster-wide
zoo.metrics_flush()     # final local report
mv.barrier()            # every rank flushed
base = "http://127.0.0.1:{mport}"
# Remote reports ride async writer threads: scrape until the cluster
# SERVER_PROCESS_GET stabilizes across two polls (bounded).
prev = None
for _ in range(50):
    prom = urllib.request.urlopen(base + "/metrics",
                                  timeout=10).read()
    import re as _re
    m = _re.search(rb'mv_cluster_monitor_count_total'
                   rb'\\{{name="SERVER_PROCESS_GET"\\}} (\\d+)', prom)
    cur = m.group(1) if m else None
    if cur is not None and cur == prev:
        break
    prev = cur
    time.sleep(0.3)
open(r"{prom_path}", "wb").write(prom)
mv.barrier()            # keep the scrape inside the cluster lifetime
mv.shutdown()
print("WORKER_OK")
"""
    server = common + """
mv.barrier()            # traffic done
zoo.metrics_flush()
mv.barrier()
print("SERVER_GET_COUNT=%d"
      % Dashboard.get("SERVER_PROCESS_GET").count)
mv.barrier()            # wait out the worker's scrape
mv.shutdown()
print("SERVER_OK")
"""
    outs = run_cluster([worker, server, server], timeout=300)
    assert "WORKER_OK" in outs[0]
    per_rank = [int(m.group(1)) for o in outs[1:]
                for m in [re.search(r"SERVER_GET_COUNT=(\d+)", o)]
                if m]
    assert len(per_rank) == 2 and all(c > 0 for c in per_rank), outs

    # valid Prometheus exposition; cluster aggregate == sum of the
    # per-rank dumps, and the per-rank series match them too.
    series = validate_prometheus(prom_path.read_text())
    name = 'name="SERVER_PROCESS_GET"'
    total = series[("mv_cluster_monitor_count_total",
                    frozenset([name]))]
    assert total == sum(per_rank)
    scraped_ranks = sorted(
        v for (metric, labels), v in series.items()
        if metric == "mv_monitor_count_total" and name in labels
        and 'rank="0"' not in labels)
    assert scraped_ranks == sorted(float(c) for c in per_rank)

"""The program's spans and counters (docs/OBSERVABILITY.md "Spans on the
device trace"): every Dashboard monitor is an ``mv:<NAME>`` span in a
profiler capture, and the monitors that say where a request's time goes
(TABLE_WAIT, MAILBOX_WAIT[*], WORKER_REPLY_GET, BLOB_D2H(+_BYTES),
CLIENT_PLACE_ROWS, TRAINER_EPOCH_PREP) move by what one request does.
Since PR 37 the caller's thread has its own (CLIENT_ISSUE_GET/ADD,
TABLE_WAKE, the trainers' TRAINER_BLOCK_*/TRAINER_GROUP_DISPATCH), the
ack's way back has WORKER_REPLY_ADD, and the two wide spans are cut
inside (BLOB_D2H_READY/COPY; UPDATE_PAD_ROWS/UPDATE_DISPATCH and
TABLE_GATHER_DISPATCH in the server's handlers)."""

import functools
import glob
import inspect
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import multiverso_tpu as mv
from benchmark.lib import counters
from multiverso_tpu.runtime import actor as actors
from multiverso_tpu.util import dashboard
from multiverso_tpu.util.dashboard import Dashboard, monitor, trace_to

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROWS, COLS, IDS = 1000, 8, 100
SLEEP_S = 0.05


def _snapshot():
    return counters.snapshot()


def _delta(before, after):
    """``{name: (count, ms)}`` over an interval, as the benchmark's
    harness reads the monitors around its window."""
    return {name: (moved["count"], moved["ms"])
            for name, moved in counters.delta(before, after).items()}


def _slow_pops(server):
    """Every pop of the server's mailbox sleeps before its MAILBOX_WAIT
    closes: the caller is then surely blocked in wait() when the reply
    comes, and the wait the monitor reads has a known floor."""
    popped = server._popped

    def slow(msg):
        time.sleep(SLEEP_S)
        popped(msg)

    server._popped = slow


@pytest.fixture(scope="module")
def one_get_one_add():
    """Monitor deltas over one get_rows and one add_rows of a small
    MatrixTable in a one-process zoo, programs compiled beforehand."""
    mv.init([])
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        ids = np.arange(IDS, dtype=np.int32)
        out = np.empty((IDS, COLS), np.float32)
        delta = np.ones((IDS, COLS), np.float32)
        table.get_rows(ids, out)
        table.add_rows(ids, delta)
        server = mv.current_zoo()._actors[actors.SERVER]
        _slow_pops(server)
        before = _snapshot()
        table.get_rows(ids, out)
        table.add_rows(ids, delta)
        moved = _delta(before, _snapshot())
        del server._popped
    finally:
        mv.shutdown()
    return moved


@pytest.mark.parametrize("name, count", [
    ("TABLE_WAIT", 2),                 # one wait a request
    ("WORKER_REPLY_GET", 1),
    ("CLIENT_PLACE_ROWS", 1),
    ("GET_REPLY_ROWS_DIRECT", 1),      # the one shard is the request
    ("BLOB_D2H", 1),
    ("BLOB_D2H_BYTES", IDS * COLS * 4),   # the reply's bytes
    ("MAILBOX_WAIT[server]", 2),       # the Get and the Add
    ("MAILBOX_WAIT[worker]", 4),       # two requests, two replies
    ("CLIENT_ISSUE_GET", 1),           # the caller's thread, a request
    ("CLIENT_ISSUE_ADD", 1),
    ("TABLE_WAKE", 2),                 # both waits blocked (_slow_pops)
    ("WORKER_REPLY_ADD", 1),
    ("BLOB_D2H_READY", 1),             # the two halves of BLOB_D2H
    ("BLOB_D2H_COPY", 1),
    ("TABLE_GATHER_DISPATCH", 1),      # inside SERVER_PROCESS_GET
    ("UPDATE_PAD_ROWS", 1),            # 100 host rows padded to 128
    ("UPDATE_DISPATCH", 1),            # inside SERVER_PROCESS_ADD
    ("WORKER_PROCESS_GET", 1),         # what was there counts as it did
    ("WORKER_PROCESS_ADD", 1),
])
def test_one_get_and_one_add_move_each_monitor(one_get_one_add, name, count):
    assert one_get_one_add[name][0] == count


def test_mailbox_wait_counts_what_the_server_handled(one_get_one_add):
    handled = (one_get_one_add["SERVER_PROCESS_GET"][0]
               + one_get_one_add["SERVER_PROCESS_ADD"][0])
    assert one_get_one_add["MAILBOX_WAIT[server]"][0] == handled == 2


def test_mailbox_wait_holds_the_sleep_before_the_pop(one_get_one_add):
    assert one_get_one_add["MAILBOX_WAIT[server]"][1] >= 2 * SLEEP_S * 1e3
    # the caller was blocked at least as long, the worker's pops were not
    assert one_get_one_add["TABLE_WAIT"][1] >= 2 * SLEEP_S * 1e3
    assert one_get_one_add["MAILBOX_WAIT[worker]"][1] \
        < one_get_one_add["MAILBOX_WAIT[server]"][1]


def test_the_pieces_of_a_get_nest(one_get_one_add):
    d2h = one_get_one_add["BLOB_D2H"][1]
    place = one_get_one_add["CLIENT_PLACE_ROWS"][1]
    reply = one_get_one_add["WORKER_REPLY_GET"][1]
    assert 0 < d2h + place <= reply <= one_get_one_add["TABLE_WAIT"][1]


def test_the_halves_of_the_two_wide_spans_lie_inside_them(one_get_one_add):
    moved = one_get_one_add
    halves = moved["BLOB_D2H_READY"][1] + moved["BLOB_D2H_COPY"][1]
    assert 0 < halves <= moved["BLOB_D2H"][1]
    halves = moved["UPDATE_PAD_ROWS"][1] + moved["UPDATE_DISPATCH"][1]
    assert 0 < halves <= moved["SERVER_PROCESS_ADD"][1]
    assert 0 < moved["TABLE_GATHER_DISPATCH"][1] \
        <= moved["SERVER_PROCESS_GET"][1]


def test_the_wake_up_is_the_tail_of_the_wait(one_get_one_add):
    """TABLE_WAKE starts at the worker actor's notify, inside the
    caller's TABLE_WAIT, and ends after it: a hand-off, not the wait."""
    wake, wait = one_get_one_add["TABLE_WAKE"][1], \
        one_get_one_add["TABLE_WAIT"][1]
    assert 0 < wake < wait and wait >= 2 * SLEEP_S * 1e3


def test_a_wait_that_finds_its_request_complete_wakes_nobody():
    from multiverso_tpu.util.waiter import Waiter
    done = Waiter(1)
    done.notify()
    assert done.wait() and done.woke_after_ms is None
    import threading
    blocked = Waiter(1)
    threading.Timer(SLEEP_S, blocked.notify).start()
    t0 = time.perf_counter()
    assert blocked.wait(timeout=30)
    assert 0 <= blocked.woke_after_ms < (time.perf_counter() - t0) * 1e3
    assert not Waiter(1).wait(timeout=0.01)     # timed out: no wake-up
    mv.init([])
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        ids = np.arange(IDS, dtype=np.int32)
        table.add_rows(ids, np.ones((IDS, COLS), np.float32))
        before = _snapshot()
        msg_id = table.add_rows_async(ids, np.ones((IDS, COLS), np.float32))
        deadline = time.monotonic() + 30
        while msg_id in table._waitings and time.monotonic() < deadline:
            time.sleep(0.001)
        assert table.wait(msg_id)
        moved = _delta(before, _snapshot())
    finally:
        mv.shutdown()
    assert moved["CLIENT_ISSUE_ADD"][0] == moved["WORKER_REPLY_ADD"][0] == 1
    assert moved["TABLE_WAIT"][0] == moved["TABLE_WAKE"][0] == 0


def test_device_keys_pad_nothing_on_the_host():
    """A device-key Add has no host delta: UPDATE_PAD_ROWS stays, the
    dispatch and the caller's issue count; a device-key Get's gather is
    a TABLE_GATHER_DISPATCH and copies nothing off the device."""
    mv.init([])
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        ids = jnp.arange(IDS, dtype=jnp.int32)
        delta = jnp.ones((IDS, COLS), jnp.float32)
        table.add_rows(ids, delta)
        table.get_rows_device(ids).block_until_ready()
        before = _snapshot()
        table.add_rows(ids, delta)
        rows = table.get_rows_device(ids)
        np.testing.assert_array_equal(
            np.asarray(rows), np.full((IDS, COLS), 2.0, np.float32))
        moved = _delta(before, _snapshot())
    finally:
        mv.shutdown()
    assert moved["UPDATE_PAD_ROWS"][0] == 0
    for name in ("UPDATE_DISPATCH", "TABLE_GATHER_DISPATCH",
                 "CLIENT_ISSUE_ADD", "CLIENT_ISSUE_GET", "WORKER_REPLY_ADD"):
        assert moved[name][0] == 1, name
    assert moved.get("BLOB_D2H", (0, 0.0))[0] == 0


def test_every_message_of_a_fused_batch_counts():
    """Adds to three tables pile up behind a slowed first pop; the next
    pop_batch drains them together, and each has waited."""
    mv.init([])
    try:
        tables = [mv.create_matrix_table(ROWS, COLS) for _ in range(3)]
        ids = np.arange(IDS, dtype=np.int32)
        delta = np.ones((IDS, COLS), np.float32)
        for table in tables:
            table.add_rows(ids, delta)
        server = mv.current_zoo()._actors[actors.SERVER]
        received = []
        receive = server.receive
        server.receive = lambda msg: (received.append(msg), receive(msg))
        _slow_pops(server)
        fused = dashboard.samples("SERVER_FUSE_BATCH").count
        before = _snapshot()
        pending = [(t, t.add_rows_async(ids, delta))
                   for t in tables for _ in range(2)]
        for table, msg_id in pending:
            table.wait(msg_id)
        moved = _delta(before, _snapshot())
        assert dashboard.samples("SERVER_FUSE_BATCH").count > fused
        assert moved["MAILBOX_WAIT[server]"][0] == len(received) >= 3
        # those that queued behind the first waited out its sleep too
        assert moved["MAILBOX_WAIT[server]"][1] >= 2 * SLEEP_S * 1e3
        for table in tables:
            np.testing.assert_array_equal(
                table.get_rows(ids), np.full((IDS, COLS), 3.0, np.float32))
    finally:
        mv.shutdown()


def _host_spans(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``mv:`` spans on the
    host's lines of the capture under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(dashboard.SPAN_PREFIX):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return spans


#: The request forms the cells send, (Get, Add) of one MatrixTable: host
#: row ids, device keys with a device delta, the whole table on the
#: device.
FORMS = {
    "host_rows": (
        lambda t, ids: t.get_rows(ids),
        lambda t, ids: t.add_rows(ids, np.ones((IDS, COLS), np.float32))),
    "device_keys": (
        lambda t, ids: t.get_rows_device(jnp.asarray(ids)),
        lambda t, ids: t.wait(t.add_rows_async(
            jnp.asarray(ids), jnp.ones((IDS, COLS), jnp.float32)))),
    "whole_table": (
        lambda t, ids: t.get_device(),
        lambda t, ids: t.wait(t.add_async(
            jnp.ones((ROWS, COLS), jnp.float32)))),
}

#: (form, servers, -sync): every form in the one-process zoo, and the
#: host rows over two servers' shards and under the BSP server.
ONE_REQUEST = [pytest.param(form, 1, False, id=form) for form in FORMS] + [
    pytest.param("host_rows", 2, False, id="host_rows-2servers"),
    pytest.param("host_rows", 1, True, id="host_rows-sync"),
    pytest.param("host_rows", 2, True, id="host_rows-2servers-sync")]


def _capture_one(trace_dir, form, servers, sync, op):
    """One Get (``op`` 0) or Add (1) of ``form`` inside a profiler
    session, in a zoo of ``servers`` servers and one worker: ``(spans by
    name, the request's msg_id, the table's id)``."""
    from multiverso_tpu.runtime.cluster import LocalCluster
    seen = {}

    def body(rank):
        table = mv.create_matrix_table(ROWS, COLS)
        if rank == 0:
            # rows of every server's shard
            ids = np.arange(0, ROWS, ROWS // IDS, dtype=np.int32)
            # no session open: the same calls raise nothing, record
            # nothing
            for call in FORMS[form]:
                call(table, ids)
            with trace_to(trace_dir):
                with monitor("caller_window"):  # mvlint: ignore[metric-name]
                    FORMS[form][op](table, ids)
                seen.update(msg_id=table._msg_id, table=table.table_id)
        mv.barrier()

    LocalCluster(servers, argv=["-sync=true"] if sync else [],
                 roles=["all"] + ["server"] * (servers - 1)).run(body)
    by_name = {}
    for span in _host_spans(trace_dir):
        by_name.setdefault(span[0], []).append(span)
    return by_name, seen["msg_id"], seen["table"]


def _hops_carry_the_request(by_name, kind, servers, msg_id, table_id):
    """One worker span, one wait, and a server span and a reply span a
    shard, every one under the request's ``msg_id`` and ``table``; the
    issue names the table (the id is drawn inside it)."""
    (_, _, _, issue_stats), = by_name[f"mv:CLIENT_ISSUE_{kind}"]
    assert issue_stats == {"table": table_id}
    for name, n in ((f"mv:WORKER_PROCESS_{kind}", 1),
                    (f"mv:SERVER_PROCESS_{kind}", servers),
                    (f"mv:WORKER_REPLY_{kind}", servers),
                    ("mv:TABLE_WAIT", 1)):
        assert [s[3] for s in by_name[name]] == [
            {"msg_id": msg_id, "table": table_id}] * n, name


def _each_inside_one(inner, outer):
    return all(any(lo <= a <= b <= hi for _, lo, hi, _ in outer)
               for _, a, b, _ in inner)


@pytest.mark.parametrize("form, servers, sync", ONE_REQUEST)
def test_a_capture_around_one_get_holds_the_servers_span(
        tmp_path, form, servers, sync):
    by_name, msg_id, table_id = _capture_one(
        str(tmp_path), form, servers, sync, op=0)
    # only what ran inside the session is there: one Get, no Add
    assert "mv:SERVER_PROCESS_ADD" not in by_name
    _hops_carry_the_request(by_name, "GET", servers, msg_id, table_id)
    window = by_name["mv:caller_window"]
    assert len(window) == 1
    # the caller's wait, the server's handling and the worker's reply
    # handling lie in the same window, on threads of their own
    for name in ("mv:TABLE_WAIT", "mv:WORKER_PROCESS_GET",
                 "mv:SERVER_PROCESS_GET", "mv:WORKER_REPLY_GET"):
        assert _each_inside_one(by_name[name], window), name
    if form != "whole_table":
        assert len(by_name["mv:TABLE_GATHER_DISPATCH"]) == servers
        assert _each_inside_one(by_name["mv:TABLE_GATHER_DISPATCH"],
                                by_name["mv:SERVER_PROCESS_GET"])
    # a reply that stays on the device is copied and placed by nobody
    assert ("mv:BLOB_D2H" in by_name) == (form == "host_rows")
    if form == "host_rows":
        for name in ("mv:BLOB_D2H", "mv:CLIENT_PLACE_ROWS"):
            assert len(by_name[name]) == servers
            assert _each_inside_one(by_name[name],
                                    by_name["mv:WORKER_REPLY_GET"]), name
        # the copy's two halves nest in it, the wait before the copy
        # (the worker's thread handles the shards' replies in turn)
        for (_, c, d, _), (_, r0, r1, _), (_, c0, c1, _) in zip(
                *(sorted(by_name[name], key=lambda s: s[1])
                  for name in ("mv:BLOB_D2H", "mv:BLOB_D2H_READY",
                               "mv:BLOB_D2H_COPY"))):
            assert c <= r0 <= r1 <= c0 <= c1 <= d
    # the caller's own spans are one thread's, in order: one request is
    # matched across its three threads
    (_, lo, hi, _), = window
    (_, i0, i1, _), = by_name["mv:CLIENT_ISSUE_GET"]
    (_, w0, w1, _), = by_name["mv:TABLE_WAIT"]
    assert lo <= i0 <= i1 <= w0 <= w1 <= hi
    # ... and the Monitor never sees the arguments
    assert set(vars(Dashboard.get("TABLE_WAIT"))) == set(
        vars(dashboard.Monitor("x")))


@pytest.mark.parametrize("form, servers, sync", ONE_REQUEST)
def test_a_capture_around_one_add_holds_the_handler_s_halves(
        tmp_path, form, servers, sync):
    by_name, msg_id, table_id = _capture_one(
        str(tmp_path), form, servers, sync, op=1)
    assert "mv:SERVER_PROCESS_GET" not in by_name
    _hops_carry_the_request(by_name, "ADD", servers, msg_id, table_id)
    handled = by_name["mv:SERVER_PROCESS_ADD"]
    # host ids are padded on the host, device ids nowhere
    assert ("mv:UPDATE_PAD_ROWS" in by_name) == (form == "host_rows")
    for name in ("mv:UPDATE_PAD_ROWS", "mv:UPDATE_DISPATCH"):
        if name in by_name:
            assert len(by_name[name]) == servers
            assert _each_inside_one(by_name[name], handled), name
    if form == "host_rows":     # padded, then dispatched
        padded = min(p1 for _, _, p1, _ in by_name["mv:UPDATE_PAD_ROWS"])
        assert all(padded <= d0
                   for _, d0, _, _ in by_name["mv:UPDATE_DISPATCH"])
    # issued before a server has it, acknowledged after (the ack
    # leaves inside the server's span, so only the starts are ordered)
    (_, i0, i1, _), = by_name["mv:CLIENT_ISSUE_ADD"]
    first = min(lo for _, lo, _, _ in handled)
    assert i0 <= i1 and i0 <= first
    assert all(first <= a0 <= a1
               for _, a0, a1, _ in by_name["mv:WORKER_REPLY_ADD"])
    assert "mv:TABLE_WAKE" not in by_name     # a Monitor.add: no span


def test_a_pieced_reply_is_one_entry_and_a_span_a_piece(tmp_path,
                                                        monkeypatch):
    """A reply that leaves the device in pieces (tests cut at a few
    rows): every monitor counts ONE entry for it, the trace holds a copy
    span and a placing span a piece, in turn, and BLOB_D2H's time is its
    two halves', the placing outside it."""
    from multiverso_tpu.core import blob as blobmod
    from multiverso_tpu.util.dashboard import laps
    monkeypatch.setattr(blobmod, "D2H_WHOLE_UNDER_BYTES", 64)
    monkeypatch.setattr(blobmod, "D2H_PIECE_BYTES", IDS * COLS * 4 // 3 + 4)
    names = ("BLOB_D2H", "BLOB_D2H_READY", "BLOB_D2H_COPY",
             "CLIENT_PLACE_ROWS", "GET_REPLY_ROWS_PIECED",
             "GET_REPLY_ROWS_WHOLE", "WORKER_REPLY_GET")
    mv.init([])
    try:
        table = mv.create_matrix_table(ROWS, COLS)
        ids = np.arange(IDS, dtype=np.int32)
        base = np.arange(ROWS * COLS, dtype=np.float32).reshape(ROWS, COLS)
        table.add(base)
        table.get_rows(ids)  # programs built
        before = {n: (Dashboard.get(n).count, Dashboard.get(n).elapse)
                  for n in names}
        with trace_to(str(tmp_path)):
            got = table.get_rows(ids)
        moved = {n: (Dashboard.get(n).count - before[n][0],
                     Dashboard.get(n).elapse - before[n][1])
                 for n in names}
    finally:
        mv.shutdown()
    np.testing.assert_array_equal(got, base[ids])
    assert {n: c for n, (c, _) in moved.items()} == {
        **dict.fromkeys(names, 1), "GET_REPLY_ROWS_WHOLE": 0}
    halves = moved["BLOB_D2H_READY"][1] + moved["BLOB_D2H_COPY"][1]
    assert 0 < halves <= moved["BLOB_D2H"][1] * (1 + 1e-6)
    assert moved["BLOB_D2H"][1] + moved["CLIENT_PLACE_ROWS"][1] \
        <= moved["WORKER_REPLY_GET"][1]
    by_name = {}
    for span in _host_spans(str(tmp_path)):
        by_name.setdefault(span[0], []).append(span)
    copies = sorted(by_name["mv:BLOB_D2H_COPY"], key=lambda s: s[1])
    places = sorted(by_name["mv:CLIENT_PLACE_ROWS"], key=lambda s: s[1])
    assert len(copies) == len(places) == 3
    assert len(by_name["mv:BLOB_D2H"]) == 4  # the wait, then a piece each
    (_, r0, r1, _), = by_name["mv:BLOB_D2H_READY"]
    order = [r0, r1]
    for copy, place in zip(copies, places):
        order += [copy[1], copy[2], place[1], place[2]]
    assert order == sorted(order)
    # a laps entry that is never closed counts nothing
    with laps("SPAN_ARGS"):  # mvlint: ignore[metric-name]
        pass


def test_monitor_has_no_trace_parameter():
    params = inspect.signature(monitor.__init__).parameters
    assert "trace" not in params
    assert params["args"].kind is inspect.Parameter.VAR_KEYWORD


def test_span_arguments_do_not_reach_the_monitor():
    before = Dashboard.get("SPAN_ARGS").count
    with monitor("SPAN_ARGS",  # mvlint: ignore[metric-name]
                 msg_id=7, table=1) as mon:
        pass
    assert mon is Dashboard.get("SPAN_ARGS")
    assert mon.count == before + 1


def test_concurrent_entries_lose_no_count():
    """Dashboard.get reads a registered monitor without the registry
    lock: threads racing through a first use must still share ONE
    Monitor, and no entry may be lost."""
    import threading
    threads, entries = 16, 2000
    Dashboard.reset()
    start = threading.Barrier(threads)

    def work():
        start.wait(timeout=30)
        for _ in range(entries):
            with monitor("RACED_REGION"):  # mvlint: ignore[metric-name]
                pass

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        workers = [threading.Thread(target=work) for _ in range(threads)]
        for w in workers:
            w.start()
        for w in workers:
            w.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(w.is_alive() for w in workers)
    assert Dashboard.get("RACED_REGION").count == threads * entries
    Dashboard.reset()


# -- TRAINER_EPOCH_PREP -----------------------------------------------------

def _corpus(tmp_path):
    from multiverso_tpu.models.wordembedding import (Dictionary,
                                                     TokenizedCorpus)
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(16)]
    path = tmp_path / "corpus.txt"
    path.write_text("\n".join(
        " ".join(rng.choice(words, size=12)) for _ in range(60)))
    d = Dictionary.build(str(path), min_count=1)
    return d, TokenizedCorpus.build(d, str(path))


@pytest.mark.parametrize("use_ps", [False, True], ids=["local", "ps"])
def test_epoch_prep_counts_one_an_epoch(use_ps, tmp_path):
    from multiverso_tpu.models.wordembedding import (
        DeviceCorpusTrainer, PSDeviceCorpusTrainer, PSWord2Vec, Word2Vec,
        Word2VecConfig)
    d, tok = _corpus(tmp_path)
    config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                            init_learning_rate=0.01, batch_size=512,
                            sample=0, use_ps=use_ps)
    if use_ps:
        mv.init([])
    try:
        if use_ps:
            trainer = PSDeviceCorpusTrainer(PSWord2Vec(config, d), tok,
                                            centers_per_step=64)
        else:
            trainer = DeviceCorpusTrainer(Word2Vec(config, d), tok,
                                          centers_per_step=64)
        before = Dashboard.get("TRAINER_EPOCH_PREP").count
        blocks = []
        hook = {"block_hook" if use_ps else "group_hook": blocks.append}
        for epoch in range(2):
            ms = Dashboard.get("TRAINER_EPOCH_PREP").elapse
            t0 = time.perf_counter()
            trainer.train_epoch(seed=epoch, **hook)
            whole = (time.perf_counter() - t0) * 1e3
            # the preparation only: it ends before the first block
            assert 0 < Dashboard.get("TRAINER_EPOCH_PREP").elapse - ms < whole
        assert Dashboard.get("TRAINER_EPOCH_PREP").count == before + 2
        assert blocks
    finally:
        if use_ps:
            mv.shutdown()


@pytest.mark.parametrize("use_ps", [False, True], ids=["local", "ps"])
def test_the_trainers_dispatches_count_one_a_block_or_a_group(
        use_ps, tmp_path):
    from multiverso_tpu.models.wordembedding import (
        DeviceCorpusTrainer, PSDeviceCorpusTrainer, PSWord2Vec, Word2Vec,
        Word2VecConfig)
    d, tok = _corpus(tmp_path)
    config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                            init_learning_rate=0.01, batch_size=512,
                            sample=0, use_ps=use_ps)
    if use_ps:
        mv.init([])
    try:
        if use_ps:
            trainer = PSDeviceCorpusTrainer(PSWord2Vec(config, d), tok,
                                            centers_per_step=64)
        else:
            trainer = DeviceCorpusTrainer(Word2Vec(config, d), tok,
                                          centers_per_step=64)
        ticks = []
        before = _snapshot()
        trainer.train_epoch(
            seed=0, **{"block_hook" if use_ps else "group_hook":
                       ticks.append})
        moved = _delta(before, _snapshot())
    finally:
        if use_ps:
            mv.shutdown()
    assert len(ticks) > 1      # several blocks (ps) or groups (local)
    own = {name: moved[name] for name in moved
           if name.startswith("TRAINER_") and moved[name][0]}
    if use_ps:
        # one monitor a program the block dispatches, ids and step, and
        # one for its wait for the block before it
        assert {n: c for n, (c, _) in own.items()} == {
            "TRAINER_EPOCH_PREP": 1, "TRAINER_BLOCK_IDS": len(ticks),
            "TRAINER_BLOCK_STEP": len(ticks),
            "TRAINER_BLOCK_PACE": len(ticks)}
        # a block's two Gets and two Adds are the client's to name
        assert moved["CLIENT_ISSUE_GET"][0] == 2 * len(ticks)
        assert moved["CLIENT_ISSUE_ADD"][0] == 2 * len(ticks)
    else:
        assert {n: c for n, (c, _) in own.items()} == {
            "TRAINER_EPOCH_PREP": 1, "TRAINER_GROUP_DISPATCH": len(ticks)}
    assert all(ms > 0 for _, ms in own.values())


def _launches_of_the_thread_with(trace_dir, span):
    """From the capture under ``trace_dir``, the host line that holds
    ``span``: its ``mv:`` spans, the jitted calls its thread made (the
    outermost ``PjitFunction(<name>)`` events: an eager ``x + y`` or a
    ``jnp.asarray`` is one too) and its transfers (``DevicePut*``), each
    as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    host, = [plane for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU"]
    line, = [line for line in host.lines
             if any(e.name == span for e in line.events)]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events]
    calls = sorted((e for e in events if e[0].startswith("PjitFunction(")),
                   key=lambda e: (e[1], -e[2]))
    outermost = []
    for call in calls:       # the runtime records a call twice, nested
        if not outermost or call[1] >= outermost[-1][2]:
            outermost.append(call)
    return ([e for e in events if e[0].startswith(dashboard.SPAN_PREFIX)],
            outermost,
            [e for e in events if e[0].startswith("DevicePut")])


@pytest.mark.parametrize("grouped", [1, 3], ids=["G1", "G3"])
def test_a_ps_block_is_two_programs_of_the_trainers_own(tmp_path, grouped):
    """Over the N blocks of an epoch the trainer's thread launches 2 N
    programs, ids and step, and uploads nothing by a call of its own:
    the block's number and learning rate are arguments of the two."""
    from multiverso_tpu.models.wordembedding import (
        PSDeviceCorpusTrainer, PSWord2Vec, Word2VecConfig)
    d, tok = _corpus(tmp_path)
    config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                            init_learning_rate=0.01, batch_size=512,
                            sample=0, use_ps=True)
    mv.init([])
    try:
        trainer = PSDeviceCorpusTrainer(PSWord2Vec(config, d), tok,
                                        centers_per_step=64,
                                        blocks_per_dispatch=grouped)
        trainer.train_epoch(seed=0)        # every program compiled
        ticks = []
        with trace_to(str(tmp_path / "trace")):
            trainer.train_epoch(seed=1, block_hook=ticks.append)
    finally:
        mv.shutdown()
    spans, calls, puts = _launches_of_the_thread_with(
        str(tmp_path / "trace"), "mv:TRAINER_BLOCK_IDS")
    blocks = len(ticks)
    assert blocks > 2
    (_, _, prep_end), = [s for s in spans if s[0] == "mv:TRAINER_EPOCH_PREP"]
    in_loop = [c for c in calls if c[1] >= prep_end]
    assert [c[0] for c in in_loop] == [
        "PjitFunction(ids)", "PjitFunction(step)"] * blocks
    # each under its monitor, in the block's order
    ids = sorted(s for s in spans if s[0] == "mv:TRAINER_BLOCK_IDS")
    step = sorted(s for s in spans if s[0] == "mv:TRAINER_BLOCK_STEP")
    assert len(ids) == len(step) == blocks
    for call, (_, lo, hi) in zip(in_loop, [
            s for pair in zip(ids, step) for s in pair]):
        assert lo <= call[1] <= call[2] <= hi
    # what is transferred in the loop is an argument of one of the two
    for _, start, end in (p for p in puts if p[1] >= prep_end):
        assert any(lo <= start <= end <= hi for _, lo, hi in in_loop)


# -- named scopes -------------------------------------------------------------

def _lowered(fn, *args):
    return fn.lower(*args).as_text(debug_info=True)


def test_prep_names_its_two_steps():
    from multiverso_tpu.models.wordembedding import device_train
    flat = jnp.zeros(64, jnp.int32)
    text = _lowered(device_train._prep, flat, flat,
                    jnp.ones(4, jnp.float32), jax.random.PRNGKey(0))
    for scope in ("mv.prep.mask", "mv.prep.sort"):
        assert scope in text
    assert "mv.prep.take" not in text and "mv.prep.argsort" not in text
    assert "module @jit__prep" in text   # the program's name is as it was


def test_update_programs_name_their_steps_and_keep_their_names():
    from multiverso_tpu.updater.engine import UpdateEngine
    engine = UpdateEngine(None, (128, 128), np.float32, 1)
    data = jnp.zeros((128, 128), jnp.float32)
    hyp, wid = np.zeros(4, np.float32), np.int32(0)
    rows = _lowered(engine._rows, data, None, jnp.zeros(8, jnp.int32),
                    jnp.zeros((8, 50), jnp.float32), hyp, wid)
    for scope in ("mv.update.pad", "mv.update.rule",
                  "mv.update.scatter_add"):
        assert scope in rows
    assert "module @jit_rows_padded" in rows
    dense = _lowered(engine._dense, data, None,
                     jnp.zeros((100, 50), jnp.float32), hyp, wid)
    assert "mv.update.pad" in dense and "mv.update.rule" in dense
    assert "module @jit_dense_padded" in dense


@pytest.mark.parametrize("devices", [1, 4])
def test_the_sorted_runs_form_keeps_the_name_and_splits_its_scopes(
        monkeypatch, devices):
    """On a TPU the rows program sorts the ids under ``mv.update.dedup``
    and writes the rows under ``mv.update.scatter_add``, in the same
    ``jit_rows_padded`` (benchmark/lib/tableprograms.py keys on the
    stem), on one device and on a row-sharded table."""
    from multiverso_tpu.sharding import mesh as meshlib
    from multiverso_tpu.updater import row_scatter, rules
    from multiverso_tpu.updater.engine import UpdateEngine
    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    monkeypatch.setattr(rules.row_scatter, "scatter_add", functools.partial(
        row_scatter.scatter_add, interpret=True))
    sharding = meshlib.row_sharded(meshlib.local_mesh(devices))
    engine = UpdateEngine(None, (4096, 128), np.float32, 1, sharding)
    data = jax.device_put(jnp.zeros((4096, 128), jnp.float32), sharding)
    text = _lowered(engine._rows, data, None, jnp.zeros((2, 1024), jnp.int32),
                    jnp.zeros((2, 1024, 50), jnp.float32),
                    np.zeros(4, np.float32), np.int32(0))
    for scope in ("mv.update.pad", "mv.update.rule", "mv.update.dedup",
                  "mv.update.scatter_add"):
        assert scope in text
    assert "module @jit_rows_padded" in text


def test_the_gather_is_scoped_and_keeps_its_lambdas_name():
    mv.init([])
    try:
        mv.create_matrix_table(ROWS, COLS)
        server_table = mv.current_zoo()._actors[actors.SERVER]._store[0]
        text = _lowered(server_table._gather, server_table._data,
                        jnp.zeros(8, jnp.int32))
    finally:
        mv.shutdown()
    assert "mv.table.gather" in text
    # benchmark/lib/tableprograms.py keys on this stem
    assert "module @jit__lambda" in text


def test_the_ps_block_programs_are_scoped():
    from multiverso_tpu.models.wordembedding import device_train
    C, W, K = 8, 2, 2
    ids = device_train._block_ids_fn(C, W, K)
    stream = jnp.zeros(C + 2 * W + C, jnp.int32)
    text = _lowered(ids, stream, stream, jnp.ones(16, jnp.float32),
                    jnp.zeros(16, jnp.int32), jax.random.PRNGKey(0),
                    np.int32(0), np.int32(C))
    assert "mv.sgns.ids" in text and "module @jit_ids" in text
    step = device_train._block_step_fn(C, W, K)
    text = _lowered(step, jnp.zeros((C, 4)), jnp.zeros((C + 2 * W + C * K, 4)),
                    jnp.ones((C, 2 * W)), jnp.float32(0.1), jnp.float32(1.0))
    assert "mv.sgns.step" in text and "module @jit_step" in text


# -- tools/trace_spans.py -------------------------------------------------------

RECORDED = os.path.join(ROOT, "benchmark", "tests", "data",
                        "rows_traced.xplane.pb")


def test_trace_spans_falls_back_to_the_bench_spans_of_the_recorded_trace():
    """The recorded trace (PR 23) holds no ``mv:`` span: every gap falls
    back to the harness's span, and the totals are xplane.reduce's."""
    from benchmark.lib import xplane
    from tools import trace_spans
    report = trace_spans.read(RECORDED)
    reduced = xplane.reduce(xplane.load(RECORDED))
    assert report["gaps"]["mv_share"] == 0.0
    want = {f"bench:{name}": seconds
            for name, seconds in reduced["gap_totals"].items()
            if name != xplane.NO_SPAN}
    got = {name: seconds for name, seconds in report["gaps"]["totals"].items()
           if name != trace_spans.NO_SPAN}
    assert got == pytest.approx(want, abs=1e-9)
    assert report["gaps"]["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], abs=1e-9)
    # no scope path in a trace trimmed of its stats: time by program only
    assert set(report["scopes"]) == set(reduced["programs"])
    for stem, program in reduced["programs"].items():
        assert sum(report["scopes"][stem].values()) <= \
            program["seconds"] + 1e-9


def test_trace_spans_cuts_a_gap_by_the_innermost_working_mv_span():
    from tools import trace_spans
    ms = 1_000_000
    device = {"modules": [("jit_rows_padded(123456)", 0, 10 * ms),
                          ("jit_rows_padded(123456)", 90 * ms, 100 * ms)],
              "ops": [("%fusion.1", 0, 10 * ms,
                       "jit(rows_padded)/mv.update.rule/"
                       "mv.update.scatter_add/scatter-add:"),
                      ("%while.2", 90 * ms, 100 * ms, ""),
                      ("%fusion.3", 92 * ms, 96 * ms,   # inside the loop
                       "jit(rows_padded)/mv.update.pad/pad:")]}
    spans = [  # (name, start, end, thread)
        ("bench:window", 0, 100 * ms, 1),
        ("bench:get_rows", 5 * ms, 95 * ms, 1),
        ("mv:TABLE_WAIT", 12 * ms, 94 * ms, 1),      # only waits
        ("mv:WORKER_REPLY_GET", 20 * ms, 90 * ms, 2),
        ("mv:BLOB_D2H", 22 * ms, 70 * ms, 2),        # innermost
        ("mv:CLIENT_PLACE_ROWS", 72 * ms, 89 * ms, 2)]
    report = trace_spans.report({"/device:TPU:0": device}, spans)
    # one gap, 10 to 90 ms, cut at the spans' edges
    assert report["gaps"]["totals"] == pytest.approx({
        "bench:get_rows": 0.002,          # before any mv: span opens
        "mv:TABLE_WAIT": 0.008,           # nothing but the wait yet
        "mv:WORKER_REPLY_GET": 0.005,     # 20-22, 70-72, 89-90
        "mv:BLOB_D2H": 0.048,
        "mv:CLIENT_PLACE_ROWS": 0.017})
    assert report["gaps"]["idle_s"] == pytest.approx(0.080)
    assert report["gaps"]["mv_share"] == pytest.approx(0.078 / 0.080)
    # an operation under two scopes goes to the inner one; one that
    # encloses another counts its own time only
    assert report["scopes"] == {"jit_rows_padded": pytest.approx({
        "mv.update.scatter_add": 0.010, "mv.update.pad": 0.004,
        trace_spans.NO_SCOPE: 0.006})}


def test_trace_spans_reports_collectives_by_scope_and_each_chip():
    """A table laid over two chips: the gather's all-reduce (both
    halves of the asynchronous pair) is counted under its scope on the
    busiest chip, beside the scope's total over the chips."""
    from tools import trace_spans
    ms = 1_000_000
    gather = "jit(_lambda)/mv.table.gather/"

    def chip(work_ms):
        return {"modules": [("jit__lambda(123456)", 0, 40 * ms)],
                "ops": [("%fusion.1", 0, work_ms * ms, gather + "gather:"),
                        ("%all-reduce-start.2", 20 * ms, 22 * ms,
                         gather + "all-reduce:"),
                        ("%all-reduce-done.2", 30 * ms, 36 * ms,
                         gather + "all-reduce:"),
                        ("%copy.3", 36 * ms, 40 * ms, "")]}
    devices = {"/device:TPU:0": chip(20), "/device:TPU:1": chip(10)}
    spans = [("bench:window", 0, 50 * ms, 1)]
    report = trace_spans.report(devices, spans)
    assert report["gaps"]["busiest"] == "/device:TPU:0"
    assert report["scopes"] == {"jit__lambda": pytest.approx({
        "mv.table.gather": 0.020 + 0.010 + 2 * 0.008,
        trace_spans.NO_SCOPE: 0.008})}
    assert report["collectives"] == {"jit__lambda": pytest.approx({
        "mv.table.gather": 0.008})}
    assert report["chips"] == {
        "/device:TPU:0": pytest.approx(
            {"busy_s": 0.032, "collective_s": 0.008}),
        "/device:TPU:1": pytest.approx(
            {"busy_s": 0.022, "collective_s": 0.008})}
    text = trace_spans.render(report)
    assert "| `jit__lambda` | `mv.table.gather` | 0.0460 | 85.2 | 0.0080 |" \
        in text
    assert "| `/device:TPU:1` | 0.0220 | 0.0080 |" in text


def test_trace_spans_command_prints_both_tables():
    done = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "trace_spans.py"),
         RECORDED], capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert done.returncode == 0, done.stderr[-2000:]
    assert "bench:get_rows" in done.stdout
    assert "jit_rows_padded" in done.stdout

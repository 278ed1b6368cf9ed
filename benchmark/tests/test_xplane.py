"""The trace reduction against a small recorded trace (a traced run of
mperf16m.rows on one v5e chip, PR 23, cut down by tools/trim_trace.py)
and against hand-made traces for what one chip cannot show."""

import os

import pytest

from benchmark.lib import xplane

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "rows_traced.xplane.pb")


@pytest.fixture(scope="module")
def recorded():
    return xplane.reduce(xplane.load(RECORDED))


def test_busy_and_idle_share_of_the_recorded_trace(recorded):
    assert recorded["device_count"] == 1
    assert recorded["window_s"] == pytest.approx(3.159902986, abs=1e-9)
    assert recorded["busy_s"] == pytest.approx(0.359770761, abs=1e-9)
    idle = 100.0 * (1.0 - recorded["busy_s"] / recorded["window_s"])
    assert idle == pytest.approx(88.6145, abs=1e-3)


def test_program_stems_have_the_hash_stripped(recorded):
    programs = recorded["programs"]
    assert set(programs) == {"jit__lambda", "jit_rows_padded",
                             "jit_dynamic_slice"}
    assert programs["jit_rows_padded"]["count"] == 32
    assert programs["jit__lambda"]["count"] == 33  # 32 Gets + the sync
    assert programs["jit_rows_padded"]["seconds"] == pytest.approx(
        0.307854062, abs=1e-9)
    assert recorded["launches"] == 98


def test_gaps_are_joined_to_the_harness_spans(recorded):
    totals = recorded["gap_totals"]
    assert totals["get_rows"] == pytest.approx(2.701573245, abs=1e-6)
    assert totals["add_rows"] == pytest.approx(0.095995177, abs=1e-6)
    assert totals[xplane.NO_SPAN] < 0.01
    name, seconds = recorded["gaps"][0]
    assert name == "get_rows" and seconds == pytest.approx(0.102486494,
                                                           abs=1e-6)
    # busy and idle make up the window
    assert sum(s for _, s in recorded["gaps"]) + recorded["busy_s"] == \
        pytest.approx(recorded["window_s"], abs=1e-6)


def test_breakdown_fits_the_ledger(recorded):
    b = xplane.breakdown(recorded)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit_rows_padded"
    assert b["idle_gaps"][5][0] == "all:get_rows"


@pytest.mark.parametrize("name,want", [
    ("jit_rows_padded(15895034004113943741)", "jit_rows_padded"),
    ("jit_rows_padded_15895034004113943741_", "jit_rows_padded"),
    ("jit__lambda(4628507844919258942)", "jit__lambda"),
    ("jit_step", "jit_step"),
    ("jit_group(12)", "jit_group(12)"),     # too short to be a hash
])
def test_stem(name, want):
    assert xplane.stem(name) == want


def _trace(devices, spans):
    return {"devices": devices, "spans": spans}


def test_two_chips_union_collectives_and_clipping():
    ms = 1_000_000
    chip0 = {"modules": [("jit_step(123456)", 0, 40 * ms),
                         ("jit_step(123456)", 60 * ms, 130 * ms)],
             "ops": [("%fusion.1 = f32[8]", 0, 30 * ms),
                     ("%fusion.2 = f32[8]", 20 * ms, 40 * ms),   # overlaps
                     ("%all-reduce.3 = f32[8]", 60 * ms, 90 * ms),
                     ("%fusion.4 = f32[8]", 90 * ms, 130 * ms)]}  # runs out
    chip1 = {"modules": [("jit_step(123456)", 0, 10 * ms)],
             "ops": [("%all-gather-start.1 = f32[8]", 0, 10 * ms)]}
    spans = [("bench:window", 0, 100 * ms),
             ("bench:train_epoch", 35 * ms, 70 * ms)]
    got = xplane.reduce(_trace({"/device:TPU:0": chip0,
                                "/device:TPU:1": chip1}, spans))
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s_by_device"]["/device:TPU:0"] == pytest.approx(0.080)
    assert got["busy_s_by_device"]["/device:TPU:1"] == pytest.approx(0.010)
    assert got["busy_s"] == pytest.approx(0.045)      # mean over the chips
    assert got["busiest"] == "/device:TPU:0"
    assert got["collective_s"] == pytest.approx(0.030)
    assert got["launches"] == 2
    assert got["programs"]["jit_step"]["seconds"] == pytest.approx(0.080)
    assert got["gaps"] == [("train_epoch", pytest.approx(0.020))]


def test_no_device_plane_gives_nothing():
    assert xplane.reduce(_trace({}, [("bench:window", 0, 10)])) is None


# -- the trace as the program names it: mv: spans and mv. scopes -------------

def test_the_recorded_trace_has_neither_and_reduces_as_before(recorded):
    """No ``mv:`` span, no ``tf_op`` (trimmed of its stats): time by
    program only, and every gap under its ``bench:`` span."""
    assert recorded["idle_by_span"] == pytest.approx(recorded["gap_totals"])
    assert recorded["gaps_by_span"] == recorded["gaps"]
    assert set(recorded["scopes"]) == set(recorded["programs"])
    for name, program in recorded["programs"].items():
        assert set(recorded["scopes"][name]) == {xplane.NO_SCOPE}
        assert recorded["scopes"][name][xplane.NO_SCOPE] <= \
            program["seconds"] + 1e-9
    assert recorded["collective_s_by_scope"] == {}


def test_a_gap_is_cut_by_the_innermost_working_mv_span():
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
        ("mv:CLIENT_PLACE_ROWS", 72 * ms, 89 * ms, 2),
        ("mv:SERVER_PROCESS_ADD", -9 * ms, -1 * ms, 3)]   # before the window
    got = xplane.reduce(_trace({"/device:TPU:0": device}, spans))
    # one gap, 10 to 90 ms, cut at the spans' edges
    assert got["idle_by_span"] == pytest.approx({
        "get_rows": 0.002,                # before any mv: span opens
        "mv:TABLE_WAIT": 0.008,           # nothing but the wait yet
        "mv:WORKER_REPLY_GET": 0.005,     # 20-22, 70-72, 89-90
        "mv:BLOB_D2H": 0.048,
        "mv:CLIENT_PLACE_ROWS": 0.017})
    assert got["gaps_by_span"] == [("mv:BLOB_D2H", pytest.approx(0.080))]
    # what it gave before the spans were read is still there
    assert got["gaps"] == [("get_rows", pytest.approx(0.080))]
    assert got["gap_totals"] == pytest.approx({"get_rows": 0.080})
    # idle by span and busy make up the window
    assert sum(got["idle_by_span"].values()) + got["busy_s"] == \
        pytest.approx(got["window_s"])
    # an operation under two scopes goes to the inner one; one that
    # encloses another counts its own time only
    assert got["scopes"] == {"jit_rows_padded": pytest.approx({
        "mv.update.scatter_add": 0.010, "mv.update.pad": 0.004,
        xplane.NO_SCOPE: 0.006})}
    b = xplane.breakdown(got)
    assert [name for name, _ in b["device_ops"]] == [
        "jit_rows_padded/mv.update.scatter_add",
        "jit_rows_padded/no-scope", "jit_rows_padded/mv.update.pad"]
    assert b["idle_gaps"][:2] == [["mv:BLOB_D2H", pytest.approx(0.080)],
                                  ["all:mv:BLOB_D2H", pytest.approx(0.048)]]


def test_the_wait_for_a_copy_loses_to_a_working_span_on_another_thread():
    """`mv:BLOB_D2H_READY` only waits (for the program that fills the
    buffer, and for the copy): an idle piece under it goes to a working
    `mv:` span open on another thread at that instant, however much longer
    that span is, and stays the wait's only where nothing else is open."""
    ms = 1_000_000
    device = {"modules": [("jit_rows_padded(123456)", 0, 10 * ms),
                          ("jit_rows_padded(123456)", 90 * ms, 100 * ms)],
              "ops": [("%fusion.1", 0, 10 * ms, ""),
                      ("%fusion.2", 90 * ms, 100 * ms, "")]}
    spans = [
        ("bench:window", 0, 100 * ms, 1),
        ("bench:get_rows", 5 * ms, 95 * ms, 1),
        ("mv:BLOB_D2H", 10 * ms, 90 * ms, 2),
        ("mv:BLOB_D2H_READY", 12 * ms, 80 * ms, 2),       # inside it, shorter
        ("mv:UPDATE_PAD_ROWS", 20 * ms, 50 * ms, 3),      # working, elsewhere
        ("mv:CLIENT_ISSUE_ADD", 45 * ms, 60 * ms, 4)]     # working, shorter
    assert "mv:BLOB_D2H_READY" in xplane.WAITS
    got = xplane.reduce(_trace({"/device:TPU:0": device}, spans))
    assert got["idle_by_span"] == pytest.approx({
        # 10-12 and 80-90 alone; 12-20 and 60-80 over the wait inside it,
        # which is shorter: the innermost WORKING span wins
        "mv:BLOB_D2H": 0.002 + 0.010 + 0.008 + 0.020,
        "mv:UPDATE_PAD_ROWS": 0.025,          # 20-45
        "mv:CLIENT_ISSUE_ADD": 0.015})        # 45-60: the shorter working one
    assert "mv:BLOB_D2H_READY" not in got["idle_by_span"]
    # alone, the wait keeps its piece: a wait is better than no name
    alone = [s for s in spans if s[0] in ("bench:window", "bench:get_rows",
                                          "mv:BLOB_D2H_READY")]
    got = xplane.reduce(_trace({"/device:TPU:0": device}, alone))
    assert got["idle_by_span"] == pytest.approx({
        "get_rows": 0.002 + 0.010, "mv:BLOB_D2H_READY": 0.068})


def test_scopes_are_the_busiest_chips_inside_the_window():
    """A table over two chips: the scope's seconds and the collectives'
    part of them on the busiest chip, clipped to the window; a program
    without a scope keeps its stem in the breakdown."""
    ms = 1_000_000
    gather = "jit(_lambda)/mv.table.gather/"

    def chip(work_ms):
        return {"modules": [("jit__prep(123456)", -9 * ms, -1 * ms),
                            ("jit__lambda(123456)", 0, 40 * ms),
                            ("jit_add(123456)", 45 * ms, 47 * ms)],
                "ops": [("%sort.0", -9 * ms, -1 * ms,   # before the window
                         "jit(_prep)/mv.prep.sort/sort:"),
                        ("%fusion.1", 0, work_ms * ms, gather + "gather:"),
                        ("%all-reduce-start.2", 20 * ms, 22 * ms,
                         gather + "all-reduce:"),
                        ("%all-reduce-done.2", 30 * ms, 36 * ms,
                         gather + "all-reduce:"),
                        ("%copy.3", 36 * ms, 40 * ms, ""),
                        ("%add.4", 45 * ms, 47 * ms, "")]}
    devices = {"/device:TPU:0": chip(20), "/device:TPU:1": chip(10)}
    got = xplane.reduce(_trace(devices, [("bench:window", 5 * ms, 50 * ms)]))
    assert got["busiest"] == "/device:TPU:0"
    # what ran outside the window has no entry, not a zero
    assert got["scopes"] == {
        "jit__lambda": pytest.approx({"mv.table.gather": 0.015 + 0.008,
                                      xplane.NO_SCOPE: 0.004}),
        "jit_add": pytest.approx({xplane.NO_SCOPE: 0.002})}
    assert got["collective_s_by_scope"] == {"jit__lambda": pytest.approx({
        "mv.table.gather": 0.008})}
    assert got["collective_s"] == pytest.approx(0.008)
    assert sum(got["scopes"]["jit__lambda"].values()) <= \
        got["programs"]["jit__lambda"]["seconds"]
    assert [name for name, _ in xplane.breakdown(got)["device_ops"]] == [
        "jit__lambda/mv.table.gather", "jit__lambda/no-scope", "jit_add"]


# -- lib/xspace.py: the file itself ------------------------------------------

def _varint(n):
    out = b""
    while n >= 0x80:
        out, n = out + bytes([n & 0x7F | 0x80]), n >> 7
    return out + bytes([n])


def _int(field, n):
    return _varint(field << 3) + _varint(n)


def _bytes(field, payload):
    payload = payload.encode() if isinstance(payload, str) else payload
    return _varint(field << 3 | 2) + _varint(len(payload)) + payload


def _metadata(ident, name, stats=b""):
    return _bytes(4, _int(1, ident) + _bytes(2, _int(1, ident)
                                             + _bytes(2, name) + stats))


def _line(ident, name, stamp_ns, events):
    return _bytes(3, _int(1, ident) + _bytes(2, name) + _int(3, stamp_ns)
                  + b"".join(_bytes(4, _int(1, m) + _int(2, ps) + _int(3, d))
                             for m, ps, d in events))


def test_the_file_reader_finds_scope_paths_and_both_kinds_of_span(tmp_path):
    tf_op = _bytes(5, _int(1, 9) + _bytes(2, _int(1, 9) + _bytes(2, "tf_op")))
    ref = _bytes(5, _int(1, 8) + _bytes(2, _int(1, 8) + _bytes(
        2, "jit(step)/mv.sgns.step/dot:")))
    device = _bytes(1, _int(1, 1) + _bytes(2, "/device:TPU:0")
                    + _line(1, "XLA Modules", 1000, [(1, 0, 9_000_999)])
                    + _line(2, "XLA Ops", 1000, [(2, 1_234_567, 2500),
                                                 (3, 5_000_000, 1_000_000)])
                    + _line(3, "Steps", 1000, [(1, 0, 5)])    # not read
                    + _metadata(1, "jit_step(123456)")
                    + _metadata(2, "%fusion.1", _bytes(5, _int(1, 9) + _bytes(
                        5, "jit(step)/mv.update.rule/mv.update.pad/pad:")))
                    # the stat's value as a reference to a stat's name
                    + _metadata(3, "%dot.2", _bytes(5, _int(1, 9) + _int(7, 8)))
                    + tf_op + ref)
    host = _bytes(1, _int(1, 2) + _bytes(2, "/host:CPU")
                  + _line(77, "thread", 500, [(1, 1_000_000, 3_000_000),
                                              (2, 0, 1_000),    # not a span
                                              (3, 0, 9_000_000)])
                  + _metadata(1, "mv:TABLE_WAIT")
                  + _metadata(2, "$run.py:1 main")
                  + _metadata(3, "bench:window"))
    other = _bytes(1, _int(1, 3) + _bytes(2, "/host:metadata"))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(device + host + other)
    trace = xplane.load(str(path))
    assert trace["devices"] == {"/device:TPU:0": {
        "modules": [("jit_step(123456)", 1000, 10000)],
        # whole nanoseconds: 1000 + 1234 and 2 more, as ProfileData cuts
        "ops": [("%fusion.1", 2234, 2236,
                 "jit(step)/mv.update.rule/mv.update.pad/pad:"),
                ("%dot.2", 6000, 7000, "jit(step)/mv.sgns.step/dot:")]}}
    assert trace["spans"] == [("mv:TABLE_WAIT", 1500, 4500, 77),
                              ("bench:window", 500, 9500, 77)]
    got = xplane.reduce(trace)
    assert got["scopes"] == {"jit_step": pytest.approx(
        {"mv.update.pad": 2e-9, "mv.sgns.step": 1e-6})}


def test_the_file_reader_agrees_with_profile_data_on_the_recorded_trace():
    from jax.profiler import ProfileData
    trace = xplane.load(RECORDED)
    planes = {p.name: p for p in ProfileData.from_file(RECORDED).planes}
    for plane, lines in trace["devices"].items():
        by_line = {l.name: l for l in planes[plane].lines}
        for key, line in (("modules", "XLA Modules"), ("ops", "XLA Ops")):
            want = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in by_line[line].events]
            assert [e[:3] for e in lines[key]] == want
    spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for line in planes["/host:CPU"].lines for e in line.events
             if e.name.startswith("bench:")]
    assert [s[:3] for s in trace["spans"]] == spans

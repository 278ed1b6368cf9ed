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

"""Allreduce engine tests: chunked ring, recursive halving, generation
tags, error-feedback lossy tiers, failure diagnostics, async transport.

Complements tests/test_collectives.py (which covers the ma-mode public
API and the device-mesh collectives): this file drives the engine
directly over LocalFabric virtual ranks and over real localhost TCP
endpoints, forcing each algorithm via ``-allreduce_algo``.
"""

import threading
import time

import numpy as np
import pytest

from multiverso_tpu.core.message import Message, MsgType
from multiverso_tpu.runtime.allreduce_engine import (AllreduceEngine,
                                                     choose_algo)
from multiverso_tpu.runtime.net import LocalFabric
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.net_util import free_listen_port


def run_ranks(engines, fn, timeout=60):
    """Run fn(rank, engine) on one thread per engine; returns results."""
    world = len(engines)
    results = [None] * world
    errors = [None] * world

    def body(rank):
        try:
            results[rank] = fn(rank, engines[rank])
        except BaseException as exc:  # noqa: BLE001 - reported below
            errors[rank] = exc

    threads = [threading.Thread(target=body, args=(r,))
               for r in range(world)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=timeout)
        assert not t.is_alive(), "engine deadlocked"
    for exc in errors:
        if exc is not None:
            raise exc
    return results


def fabric_engines(world):
    fabric = LocalFabric(world)
    return [AllreduceEngine(fabric.endpoint(r)) for r in range(world)]


def expected_reduce(inputs, reducer):
    out = inputs[0].copy()
    for part in inputs[1:]:
        out = reducer(out, part)
    return out


class TestRingAllreduce:
    @pytest.mark.parametrize("world", [2, 3, 4, 5, 6])
    @pytest.mark.parametrize("count", [8, 5000, 40003])
    def test_matches_numpy(self, world, count):
        # count=8 still routes through the small/Bruck path (forcing
        # ring only affects the large path); 40003 is indivisible by
        # every world size AND the chunk size, so both the chunk and
        # the per-chunk segment bounds are unequal.
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 16)  # force many chunks
        set_flag("allreduce_window", 2)
        engines = fabric_engines(world)
        rng = np.random.default_rng(0)
        inputs = [rng.standard_normal(count) for _ in range(world)]
        expected = np.sum(inputs, axis=0)
        results = run_ranks(engines,
                            lambda r, e: e.allreduce(inputs[r]))
        for out in results:
            np.testing.assert_allclose(out, expected, rtol=1e-10)

    @pytest.mark.parametrize("world", [3, 5])
    def test_other_reducer(self, world):
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 16)
        engines = fabric_engines(world)
        rng = np.random.default_rng(1)
        inputs = [rng.standard_normal(9001) for _ in range(world)]
        expected = expected_reduce(inputs, np.maximum)
        results = run_ranks(
            engines, lambda r, e: e.allreduce(inputs[r], np.maximum))
        for out in results:
            np.testing.assert_array_equal(out, expected)

    def test_shape_preserved(self):
        set_flag("allreduce_algo", "ring")
        engines = fabric_engines(3)
        inputs = [np.full((50, 40), float(r + 1)) for r in range(3)]
        results = run_ranks(engines,
                            lambda r, e: e.allreduce(inputs[r]))
        for out in results:
            assert out.shape == (50, 40)
            np.testing.assert_array_equal(out, np.full((50, 40), 6.0))

    def test_auto_prefers_ring_for_non_pow2(self):
        assert choose_algo(4 << 20, 1 << 20, 3) == "ring"
        assert choose_algo(32 * 1024, 8 * 1024, 3) == "ring"  # fold
        assert choose_algo(5000, 1250, 3) == "rhalving"

    def test_auto_prefers_rhalving_for_small_pow2(self):
        assert choose_algo(5000, 1250, 4) == "rhalving"
        assert choose_algo(4 << 20, 1 << 20, 4) == "ring"


class TestRecursiveHalving:
    @pytest.mark.parametrize("world", [3, 5, 6])
    @pytest.mark.parametrize("reducer", [np.add, np.maximum])
    def test_non_pow2_worlds(self, world, reducer):
        # The surplus-fold path, explicitly forced (auto would switch
        # non-pow2 worlds to the ring at these sizes).
        set_flag("allreduce_algo", "rhalving")
        engines = fabric_engines(world)
        rng = np.random.default_rng(2)
        inputs = [rng.standard_normal(5003) for _ in range(world)]
        expected = expected_reduce(inputs, reducer)
        results = run_ranks(
            engines, lambda r, e: e.allreduce(inputs[r], reducer))
        for out in results:
            np.testing.assert_allclose(out, expected, rtol=1e-10)

    def test_surplus_result_is_private(self):
        # The surplus rank's result must be its own buffer: in-process
        # the final frame is a reference to the leader's array, and a
        # caller mutating its result in place must not corrupt peers.
        set_flag("allreduce_algo", "rhalving")
        engines = fabric_engines(3)
        inputs = [np.full(2000, float(r + 1)) for r in range(3)]

        def body(rank, engine):
            out = engine.allreduce(inputs[rank])
            out += rank  # in-place mutation of the returned buffer
            return out

        results = run_ranks(engines, body)
        for rank, out in enumerate(results):
            np.testing.assert_array_equal(out, np.full(2000, 6.0 + rank))


class TestGenerationTags:
    def test_back_to_back_different_round_counts(self):
        # Regression: tags used to restart at fixed bases (1000/2000),
        # so consecutive allreduces whose round counts differ could
        # cross-match stash entries. The per-call generation in the
        # msg_id high bits makes every sequence safe; run a mix of
        # small (Bruck), ring, and rhalving payloads back to back on
        # persistent engines.
        set_flag("allreduce_algo", "auto")
        set_flag("allreduce_ring_kb", 16)
        set_flag("allreduce_chunk_kb", 16)
        world = 3
        engines = fabric_engines(world)
        rng = np.random.default_rng(3)
        for count in (6000, 41, 12000, 300, 9000, 8, 40000):
            inputs = [rng.standard_normal(count) for _ in range(world)]
            expected = np.sum(inputs, axis=0)
            results = run_ranks(engines,
                                lambda r, e: e.allreduce(inputs[r]))
            for out in results:
                np.testing.assert_allclose(out, expected, rtol=1e-10)

    def test_generation_in_msg_id_high_bits(self):
        engine = fabric_engines(2)[0]
        engine._gen = 5
        assert engine._mid(1000) == (5 << 20) | 1000


class TestFailureDiagnostics:
    def test_timeout_error_carries_context(self):
        # Peer never shows up: the error must name the peer, the tag,
        # the elapsed time, the flag to tune, and the stash state —
        # and must honor -allreduce_timeout_s instead of 120s.
        set_flag("allreduce_timeout_s", 0.3)
        fabric = LocalFabric(2)
        engine = AllreduceEngine(fabric.endpoint(0))
        start = time.monotonic()
        with pytest.raises(RuntimeError) as info:
            engine.allreduce(np.ones(8, np.float32))
        elapsed = time.monotonic() - start
        assert elapsed < 5.0, "flag-configured timeout not honored"
        text = str(info.value)
        for needle in ("peer 1", "msg_id", "allreduce_timeout_s",
                       "stash"):
            assert needle in text, (needle, text)

    def test_stash_cap_fails_loudly(self):
        # A crashed peer (or tag bug) flooding the endpoint with
        # unmatched frames must trip the cap, not grow the stash
        # unboundedly until the timeout.
        set_flag("allreduce_stash_cap", 8)
        set_flag("allreduce_timeout_s", 30.0)
        fabric = LocalFabric(2)
        junk_src = fabric.endpoint(1)
        for i in range(12):
            msg = Message(src=1, dst=0, msg_type=MsgType.Default,
                          msg_id=900000 + i)
            msg.push(np.zeros(4, np.float32))
            junk_src.send(msg)
        engine = AllreduceEngine(fabric.endpoint(0))
        start = time.monotonic()
        with pytest.raises(RuntimeError) as info:
            engine.allreduce(np.ones(8, np.float32))
        assert time.monotonic() - start < 5.0, "cap did not short-circuit"
        text = str(info.value)
        assert "stash exceeded 8" in text
        assert "allreduce_stash_cap" in text


class TestErrorFeedback:
    def _step_inputs(self, rng, world, n):
        # Bounded dynamic range so the int8 tier is eligible
        # (wire_codec._i8_fits) — the shape of normalized gradients.
        return [(np.sign(rng.standard_normal(n))
                 * rng.uniform(0.5, 1.5, n)).astype(np.float32)
                for _ in range(world)]

    def test_residual_corrected_lossy_tracks_lossless(self):
        # The EQuARX property: per-step quantization error is ~1%, but
        # with the residual carried across calls the ACCUMULATED sum
        # tracks the exact one — noise averages out instead of random-
        # walking. N=200000 fp32 with 64KB chunks puts every segment
        # over the 4KB codec floor, so the int8/f16 tiers engage.
        world, steps, n = 3, 20, 200000
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 64)
        set_flag("allreduce_lossy", True)
        engines = fabric_engines(world)
        rng = np.random.default_rng(7)
        acc = np.zeros(n, np.float64)
        exact = np.zeros(n, np.float64)
        per_step_rel = []
        for _ in range(steps):
            inputs = self._step_inputs(rng, world, n)
            step_exact = np.sum([x.astype(np.float64) for x in inputs],
                                axis=0)
            exact += step_exact
            results = run_ranks(engines,
                                lambda r, e: e.allreduce(inputs[r]))
            # Lossy results are still bit-identical across ranks: the
            # allgather forwards each owner's encoded frame verbatim
            # and the owner adopts its own decoded copy.
            for out in results[1:]:
                np.testing.assert_array_equal(out, results[0])
            acc += results[0].astype(np.float64)
            per_step_rel.append(
                float(np.abs(results[0] - step_exact).max()
                      / np.abs(step_exact).max()))
        assert engines[0]._ef, "lossy tiers never engaged"
        assert per_step_rel[0] > 1e-5, \
            "quantization inactive — the property test is vacuous"
        rel = float(np.abs(acc - exact).max() / np.abs(exact).max())
        # Residual-corrected: accumulated error stays ~one step's
        # quantization noise, far below steps * per-step error.
        assert rel < 0.02, (rel, per_step_rel)
        assert rel < 2 * max(per_step_rel), (rel, max(per_step_rel))

    def test_lossless_when_flag_off(self):
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 64)
        set_flag("allreduce_lossy", False)
        world, n = 2, 100000
        engines = fabric_engines(world)
        rng = np.random.default_rng(8)
        inputs = self._step_inputs(rng, world, n)
        expected = inputs[0] + inputs[1]
        results = run_ranks(engines,
                            lambda r, e: e.allreduce(inputs[r]))
        for out in results:
            np.testing.assert_array_equal(out, expected)
        assert not engines[0]._ef

    def test_non_add_reducer_stays_exact_under_lossy_flag(self):
        # Error feedback is an ADDITIVE identity: folding a carried
        # residual into a max-reduction would corrupt it, so a non-add
        # reducer must bypass the lossy tier entirely.
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 64)
        set_flag("allreduce_lossy", True)
        world, n = 3, 120000
        engines = fabric_engines(world)
        rng = np.random.default_rng(11)
        inputs = self._step_inputs(rng, world, n)
        expected = expected_reduce(inputs, np.maximum)
        results = run_ranks(
            engines, lambda r, e: e.allreduce(inputs[r], np.maximum))
        for out in results:
            np.testing.assert_array_equal(out, expected)
        assert not engines[0]._ef  # quantization never engaged

    def test_small_segments_fall_back_lossless(self):
        # Segments under the 4KB codec floor must ride exact even with
        # the lossy flag on (and consume any pending residual exactly).
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 4)  # segments ~1-2KB
        set_flag("allreduce_lossy", True)
        world, n = 3, 9000
        engines = fabric_engines(world)
        rng = np.random.default_rng(9)
        inputs = self._step_inputs(rng, world, n)
        expected = np.sum([x.astype(np.float64) for x in inputs],
                          axis=0).astype(np.float32)
        results = run_ranks(engines,
                            lambda r, e: e.allreduce(inputs[r]))
        for out in results:
            np.testing.assert_allclose(out, expected, atol=1e-5)


def sparse_inputs(rng, world, count, nnz, scale=1.0):
    """Per-rank sparse float32 blobs with exactly ``nnz`` nonzeros each
    (random support, bounded dynamic range so lossy tiers stay
    eligible)."""
    inputs = []
    for _ in range(world):
        x = np.zeros(count, np.float32)
        idx = rng.choice(count, nnz, replace=False)
        x[idx] = (np.sign(rng.standard_normal(nnz))
                  * rng.uniform(0.5, 1.5, nnz) * scale).astype(np.float32)
        inputs.append(x)
    return inputs


class TestChooseAlgo:
    """The ONE documented decision function: path pinned per
    (size, density, world) tuple — replacing the scattered byte-size
    checks (docs/ALLREDUCE.md algorithm-choice table)."""

    def test_small_payloads_always_bruck(self):
        for world in (2, 3, 8):
            assert choose_algo(4000, 1000, world) == "bruck"
            assert choose_algo(4000, 1000, world,
                               density=0.01) == "bruck"
            assert choose_algo(4000, 1000, world,
                               forced="sparse") == "bruck"
        # fewer elements than ranks: small path regardless of bytes
        assert choose_algo(40000, 5, 8) == "bruck"

    @pytest.mark.parametrize("world", [2, 3, 4, 5, 6])
    def test_sparse_picked_for_sparse_sums(self, world):
        assert choose_algo(8 << 20, 2 << 20, world,
                           density=0.05) == "sparse"

    def test_path_pinned_per_size_density_world(self):
        n = 2 << 20  # 8 MB fp32
        table = [
            # (nbytes, n_elems, world, density, expected)
            (8 << 20, n, 3, 0.05, "sparse"),
            (8 << 20, n, 3, 0.249, "sparse"),   # just below cutoff
            (8 << 20, n, 3, 0.251, "ring"),     # just above cutoff
            (8 << 20, n, 3, 0.9, "ring"),
            (8 << 20, n, 3, None, "ring"),      # no density signal
            (8 << 20, n, 4, 0.9, "ring"),
            (100 * 1024, 25600, 4, None, "rhalving"),
            (100 * 1024, 25600, 4, 0.01, "sparse"),
            (100 * 1024, 25600, 3, None, "ring"),  # non-pow2 fold
            (4000, 1000, 3, 0.01, "bruck"),
        ]
        for nbytes, n_elems, world, density, expected in table:
            got = choose_algo(nbytes, n_elems, world, density=density)
            assert got == expected, \
                (nbytes, n_elems, world, density, got, expected)

    def test_cutoff_clamped_to_codec_break_even(self):
        # -allreduce_sparse_density above the codec break-even is
        # meaningless (reduced segments would ride RAW): the effective
        # cutoff is min of the two.
        set_flag("allreduce_sparse_density", 0.6)
        set_flag("wire_codec_density", 0.3)
        assert choose_algo(8 << 20, 2 << 20, 3, density=0.29) == "sparse"
        assert choose_algo(8 << 20, 2 << 20, 3, density=0.31) == "ring"

    def test_index_budget_caps_sparse(self):
        set_flag("allreduce_sparse_idx_budget", 10000)
        # density 0.01 of 2M elements = 20971 union indices > budget
        assert choose_algo(8 << 20, 2 << 20, 3, density=0.01) == "ring"
        set_flag("allreduce_sparse_idx_budget", 30000)
        assert choose_algo(8 << 20, 2 << 20, 3, density=0.01) == "sparse"

    def test_non_add_or_non_f32_never_sparse(self):
        assert choose_algo(8 << 20, 2 << 20, 3, density=0.01,
                           reducer_is_add=False) == "ring"
        assert choose_algo(8 << 20, 1 << 20, 3, density=0.01,
                           is_f32=False) == "ring"
        # forcing sparse falls back to the ring for both
        assert choose_algo(8 << 20, 2 << 20, 3, reducer_is_add=False,
                           forced="sparse") == "ring"
        assert choose_algo(8 << 20, 1 << 20, 3, is_f32=False,
                           forced="sparse") == "ring"

    def test_forced_flags_win(self):
        set_flag("allreduce_algo", "rhalving")
        assert choose_algo(64 << 20, 16 << 20, 3, density=0.01) \
            == "rhalving"
        set_flag("allreduce_algo", "sparse")
        assert choose_algo(8 << 20, 2 << 20, 3) == "sparse"


class TestSparseAllreduce:
    @pytest.mark.parametrize("world", [2, 3, 4, 5, 6])
    @pytest.mark.parametrize("count", [40003, 150001])
    def test_index_union_reduce_matches_numpy(self, world, count):
        # Odd element counts: segment bounds and index streams are all
        # unequal; supports overlap partially (union ≠ any single
        # rank's support).
        set_flag("allreduce_algo", "sparse")
        engines = fabric_engines(world)
        rng = np.random.default_rng(13)
        inputs = sparse_inputs(rng, world, count, count // 25)
        expected = np.sum([x.astype(np.float64) for x in inputs],
                          axis=0)
        results = run_ranks(engines,
                            lambda r, e: e.allreduce(inputs[r]))
        assert engines[0].last_algo == "sparse"
        for out in results:
            assert out.dtype == np.float32
            np.testing.assert_allclose(out, expected, rtol=1e-5,
                                       atol=1e-5)
        # All ranks land on identical bytes.
        for out in results[1:]:
            np.testing.assert_array_equal(out, results[0])

    @pytest.mark.parametrize("world", [2, 3, 5])
    def test_bit_identical_to_unchunked_dense_ring(self, world):
        # The lossless contract that makes the switchover safe: the
        # sparse fold replays the unchunked ring's pairwise sums, so
        # the two paths agree BIT FOR BIT (docs/ALLREDUCE.md).
        count = 120000
        rng = np.random.default_rng(17)
        inputs = sparse_inputs(rng, world, count, count // 20)
        set_flag("allreduce_algo", "sparse")
        engines = fabric_engines(world)
        sparse = run_ranks(engines,
                           lambda r, e: e.allreduce(inputs[r]))
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 1 << 20)  # one chunk
        engines = fabric_engines(world)
        ring = run_ranks(engines, lambda r, e: e.allreduce(inputs[r]))
        for r in range(world):
            np.testing.assert_array_equal(sparse[r], ring[r])

    def test_switchover_boundary_picks_right_path(self):
        # Union density (sum of per-rank nnz / elements) just below the
        # cutoff rides sparse; just above rides the dense ring; both
        # produce the same answer (bit-equal to the unchunked ring).
        world, count = 2, 200000  # 800 KB fp32, cutoff 0.25
        rng = np.random.default_rng(19)
        set_flag("allreduce_algo", "auto")
        set_flag("allreduce_chunk_kb", 1 << 20)
        for per_rank_nnz, expected in ((24900, "sparse"),
                                       (25100, "ring")):
            inputs = sparse_inputs(rng, world, count, per_rank_nnz)
            engines = fabric_engines(world)
            auto = run_ranks(engines,
                             lambda r, e: e.allreduce(inputs[r]))
            assert engines[0].last_algo == expected, \
                (per_rank_nnz, engines[0].last_algo)
            set_flag("allreduce_algo", "ring")
            ring = run_ranks(fabric_engines(world),
                             lambda r, e: e.allreduce(inputs[r]))
            set_flag("allreduce_algo", "auto")
            for r in range(world):
                np.testing.assert_array_equal(auto[r], ring[r])

    def test_mixed_sparse_dense_generation_tags(self):
        # Back-to-back auto collectives alternating sparse (probe +
        # scatter + allgather bands) and dense (probe + ring bands)
        # payloads on PERSISTENT engines: stale frames from call g must
        # never cross-match call g+1 even across protocol shapes.
        set_flag("allreduce_algo", "auto")
        set_flag("allreduce_ring_kb", 16)
        set_flag("allreduce_chunk_kb", 16)
        world = 3
        engines = fabric_engines(world)
        rng = np.random.default_rng(23)
        seen = []
        for count, nnz in ((60000, 600), (41, 41), (120000, 120000),
                           (9000, 90), (200000, 1000), (8, 8)):
            if nnz == count:
                inputs = [rng.standard_normal(count).astype(np.float32)
                          for _ in range(world)]
            else:
                inputs = sparse_inputs(rng, world, count, nnz)
            expected = np.sum([x.astype(np.float64) for x in inputs],
                              axis=0)
            results = run_ranks(engines,
                                lambda r, e: e.allreduce(inputs[r]))
            seen.append(engines[0].last_algo)
            for out in results:
                np.testing.assert_allclose(out, expected, rtol=1e-4,
                                           atol=1e-4)
        assert "sparse" in seen and "bruck" in seen \
            and ("ring" in seen or "rhalving" in seen), seen

    def test_all_zero_input(self):
        # Density 0: every contribution is an empty index stream.
        set_flag("allreduce_algo", "sparse")
        engines = fabric_engines(3)
        inputs = [np.zeros(50000, np.float32) for _ in range(3)]
        results = run_ranks(engines,
                            lambda r, e: e.allreduce(inputs[r]))
        for out in results:
            np.testing.assert_array_equal(out,
                                          np.zeros(50000, np.float32))

    def test_fill_recorded_per_hop(self):
        from multiverso_tpu.util.dashboard import samples
        set_flag("allreduce_algo", "sparse")
        world, count = 3, 60000
        engines = fabric_engines(world)
        rng = np.random.default_rng(29)
        inputs = sparse_inputs(rng, world, count, 1200)
        reduce_fill = samples("SPARSE_FILL[reduce]")
        before = reduce_fill.count
        run_ranks(engines, lambda r, e: e.allreduce(inputs[r]))
        # one sample per folded stream per rank: world ranks x world
        # streams (the union can only grow hop over hop)
        assert reduce_fill.count - before == world * world
        recent = reduce_fill.export_recent(world * world)
        assert all(0.0 <= f <= 1.0 for f in recent)
        assert max(recent) <= 3 * 1200 * world / count

    def test_lossy_sparse_ef_convergence(self):
        # The EQuARX property on the SPARSE path: per-step quantization
        # error is visible, but with residuals carried across calls the
        # accumulated sum tracks the exact one.
        world, steps, count = 3, 20, 400000
        set_flag("allreduce_algo", "sparse")
        set_flag("allreduce_lossy", True)
        engines = fabric_engines(world)
        rng = np.random.default_rng(7)
        acc = np.zeros(count, np.float64)
        exact = np.zeros(count, np.float64)
        per_step_rel = []
        for _ in range(steps):
            inputs = sparse_inputs(rng, world, count, count // 20)
            step_exact = np.sum(
                [x.astype(np.float64) for x in inputs], axis=0)
            exact += step_exact
            results = run_ranks(engines,
                                lambda r, e: e.allreduce(inputs[r]))
            for out in results[1:]:
                np.testing.assert_array_equal(out, results[0])
            acc += results[0].astype(np.float64)
            per_step_rel.append(
                float(np.abs(results[0] - step_exact).max()
                      / np.abs(step_exact).max()))
        assert engines[0]._ef, "lossy tiers never engaged"
        assert per_step_rel[0] > 1e-6, \
            "quantization inactive — the property test is vacuous"
        rel = float(np.abs(acc - exact).max() / np.abs(exact).max())
        assert rel < 0.02, (rel, per_step_rel)
        assert rel < 2 * max(per_step_rel), (rel, max(per_step_rel))

    def test_sparse_over_tcp(self):
        set_flag("allreduce_algo", "sparse")
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(3)]
        from multiverso_tpu.runtime.tcp import TcpNet
        nets = [TcpNet(r, eps) for r in range(3)]
        try:
            engines = [AllreduceEngine(n) for n in nets]
            rng = np.random.default_rng(31)
            inputs = sparse_inputs(rng, 3, 150000, 3000)
            expected = np.sum([x.astype(np.float64) for x in inputs],
                              axis=0)
            results = run_ranks(engines,
                                lambda r, e: e.allreduce(inputs[r]),
                                timeout=90)
            for out in results:
                np.testing.assert_allclose(out, expected, rtol=1e-5,
                                           atol=1e-5)
            for out in results[1:]:
                np.testing.assert_array_equal(out, results[0])
        finally:
            for n in nets:
                n.finalize()


class TestShardedAverage:
    @pytest.mark.parametrize("world", [2, 3, 4, 5])
    def test_matches_mean(self, world):
        count = 90001
        engines = fabric_engines(world)
        rng = np.random.default_rng(37)
        inputs = sparse_inputs(rng, world, count, count // 30)
        expected = np.sum([x.astype(np.float64) for x in inputs],
                          axis=0) / world
        results = run_ranks(engines,
                            lambda r, e: e.sharded_average(inputs[r]))
        assert engines[0].last_algo == "sharded"
        for out in results:
            np.testing.assert_allclose(out, expected, rtol=1e-5,
                                       atol=1e-6)
        for out in results[1:]:
            np.testing.assert_array_equal(out, results[0])

    def test_bit_identical_to_ring_then_divide(self):
        # The acceptance contract: sharded (reduce-scatter, divide the
        # shard, allgather) equals the unchunked dense ring's
        # allreduce-then-divide BIT FOR BIT — same fold, same
        # elementwise divide, lossless transport in between.
        world, count = 3, 120000
        rng = np.random.default_rng(41)
        inputs = sparse_inputs(rng, world, count, count // 20)
        engines = fabric_engines(world)
        sharded = run_ranks(engines,
                            lambda r, e: e.sharded_average(inputs[r]))
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 1 << 20)
        engines = fabric_engines(world)
        dense = run_ranks(
            engines,
            lambda r, e: e.allreduce(inputs[r]) / world)
        for r in range(world):
            np.testing.assert_array_equal(sharded[r], dense[r])

    def test_reduce_state_is_one_segment(self):
        # The memory story: per-rank reduce state is ~1/world of the
        # buffer where the dense paths copy the whole flat buffer.
        world, count = 4, 200000
        engines = fabric_engines(world)
        rng = np.random.default_rng(43)
        inputs = sparse_inputs(rng, world, count, 2000)
        run_ranks(engines, lambda r, e: e.sharded_average(inputs[r]))
        for e in engines:
            assert e.last_reduce_state_bytes <= count * 4 / world + 64
        set_flag("allreduce_algo", "ring")
        run_ranks(engines, lambda r, e: e.allreduce(inputs[r]))
        assert engines[0].last_reduce_state_bytes == count * 4

    def test_small_payload_falls_back_to_bruck(self):
        engines = fabric_engines(3)
        inputs = [np.full(100, float(r + 1), np.float32)
                  for r in range(3)]
        results = run_ranks(engines,
                            lambda r, e: e.sharded_average(inputs[r]))
        for out in results:
            np.testing.assert_array_equal(out,
                                          np.full(100, 2.0, np.float32))

    def test_non_f32_raises(self):
        engine = fabric_engines(2)[0]
        with pytest.raises(TypeError):
            engine.sharded_average(np.zeros(10000, np.float64))

    def test_localnet_override_matches_fabric_mean(self):
        # LocalNet.sharded_average rides the shared-memory fabric (no
        # wire to save in-process): plain rank-ordered mean.
        fabric = LocalFabric(2)
        nets = [fabric.endpoint(r) for r in range(2)]
        inputs = [np.full(1000, float(r), np.float32) for r in range(2)]
        results = run_ranks(
            nets, lambda r, n: n.sharded_average(inputs[r]))
        for out in results:
            np.testing.assert_array_equal(out, np.full(1000, 0.5))

    def test_sharded_over_tcp_lossy(self):
        # Lossy sharded average over a real wire: ranks still land on
        # identical bytes (single-encode allgather forwards verbatim).
        set_flag("allreduce_lossy", True)
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        from multiverso_tpu.runtime.tcp import TcpNet
        nets = [TcpNet(r, eps) for r in range(2)]
        try:
            engines = [AllreduceEngine(n) for n in nets]
            rng = np.random.default_rng(47)
            inputs = sparse_inputs(rng, 2, 200000, 10000)
            expected = (inputs[0].astype(np.float64)
                        + inputs[1].astype(np.float64)) / 2
            results = run_ranks(
                engines, lambda r, e: e.sharded_average(inputs[r]),
                timeout=90)
            np.testing.assert_array_equal(results[0], results[1])
            np.testing.assert_allclose(results[0], expected, atol=0.02)
        finally:
            for n in nets:
                n.finalize()


class TestTcpAsyncTransport:
    def _pair(self):
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        from multiverso_tpu.runtime.tcp import TcpNet
        return [TcpNet(r, eps) for r in range(2)]

    def test_send_async_fifo_and_flush(self):
        a, b = self._pair()
        try:
            for i in range(40):
                msg = Message(src=0, dst=1, msg_type=MsgType.Default,
                              msg_id=i)
                msg.push(np.full(64, float(i), np.float32))
                a.send_async(msg)
            a.flush_sends(1, timeout=30)
            assert a.bytes_sent > 40 * 64 * 4
            got = [b.recv(timeout=10) for _ in range(40)]
            assert [m.msg_id for m in got] == list(range(40))
            np.testing.assert_array_equal(
                got[7].data[0].as_array(np.float32), np.full(64, 7.0))
        finally:
            a.finalize()
            b.finalize()

    def test_sync_send_ordered_after_async(self):
        # A blocking send must not overtake queued async frames.
        a, b = self._pair()
        try:
            for i in range(10):
                msg = Message(src=0, dst=1, msg_type=MsgType.Default,
                              msg_id=i)
                msg.push(np.zeros(50000, np.float32))  # non-trivial wire
                a.send_async(msg)
            tail = Message(src=0, dst=1, msg_type=MsgType.Default,
                           msg_id=99)
            tail.push(np.zeros(4, np.float32))
            a.send(tail)
            ids = [b.recv(timeout=10).msg_id for _ in range(11)]
            assert ids == list(range(10)) + [99]
        finally:
            a.finalize()
            b.finalize()

    def test_ring_allreduce_over_tcp(self):
        set_flag("allreduce_algo", "ring")
        set_flag("allreduce_chunk_kb", 64)
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(3)]
        from multiverso_tpu.runtime.tcp import TcpNet
        nets = [TcpNet(r, eps) for r in range(3)]
        try:
            engines = [AllreduceEngine(n) for n in nets]
            rng = np.random.default_rng(5)
            inputs = [rng.standard_normal(120000).astype(np.float32)
                      for _ in range(3)]
            expected = np.sum([x.astype(np.float64) for x in inputs],
                              axis=0)
            results = run_ranks(engines,
                                lambda r, e: e.allreduce(inputs[r]),
                                timeout=90)
            for out in results:
                np.testing.assert_allclose(out, expected, rtol=1e-4,
                                           atol=1e-4)
        finally:
            for n in nets:
                n.finalize()

    @pytest.mark.parametrize("tier", ["sparse", "int8"])
    def test_bytes_on_the_wire_over_tcp(self, tier):
        # What each tier saves is a count of bytes, whatever the wire's
        # speed: a 5%-fill sum through the sparse stream ships under a
        # quarter of the dense ring's bytes for a buffer of that size,
        # and the int8 error-feedback tier under half of the lossless
        # ring's on the same dense input.
        from multiverso_tpu.runtime.tcp import TcpNet
        count = 1 << 18  # 1 MB of float32
        rng = np.random.default_rng(61)
        dense = [(np.sign(rng.standard_normal(count))
                  * rng.uniform(0.5, 1.5, count)).astype(np.float32)
                 for _ in range(3)]
        sparse = sparse_inputs(rng, 3, count, count // 20)

        def wire_bytes(inputs, algo, lossy, tol):
            set_flag("allreduce_algo", algo)
            set_flag("allreduce_lossy", lossy)
            eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(3)]
            nets = [TcpNet(r, eps) for r in range(3)]
            try:
                engines = [AllreduceEngine(n) for n in nets]
                results = run_ranks(
                    engines, lambda r, e: e.allreduce(inputs[r]),
                    timeout=90)
                expected = np.sum([x.astype(np.float64) for x in inputs],
                                  axis=0)
                np.testing.assert_allclose(results[0], expected,
                                           rtol=tol, atol=tol)
                return sum(n.bytes_sent for n in nets), \
                    engines[0].last_algo
            finally:
                for n in nets:
                    n.finalize()

        ring_bytes, algo = wire_bytes(dense, "ring", False, 1e-3)
        assert algo == "ring"
        if tier == "sparse":
            got, algo = wire_bytes(sparse, "auto", False, 1e-3)
            assert algo == "sparse"
            assert got < 0.25 * ring_bytes, (got, ring_bytes)
        else:
            got, algo = wire_bytes(dense, "ring", True, 0.2)
            assert algo == "ring"
            assert got < 0.5 * ring_bytes, (got, ring_bytes)

"""Wire codec: round-trip properties, golden header bytes, negotiation,
error feedback, and batch-add coalescing framing.

Tier-1 (fast, host-only) coverage for the compact wire format — codec
regressions fail here, on the host, before any run on the chip.
"""

import numpy as np
import pytest

from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import (CODEC_SLOT, Message, MsgType,
                                         pack_add_batch, unpack_add_batch)
from multiverso_tpu.util import wire_codec as wc


def _power_law_blob(n=65536, nnz=1024, seed=0):
    rng = np.random.default_rng(seed)
    blob = np.zeros(n, np.float32)
    idx = np.sort(rng.choice(n, nnz, replace=False))
    blob[idx] = ((rng.pareto(2.0, nnz) + 0.1)
                 * np.sign(rng.standard_normal(nnz))).astype(np.float32)
    return blob


BLOBS = {
    "empty": np.zeros(0, np.float32),
    "all_zero": np.zeros(4096, np.float32),
    "fully_dense": np.arange(1, 513, dtype=np.float32),
    "power_law_sparse": _power_law_blob(),
    # Magnitudes past fp16's max finite (65504): the fp16 tiers must be
    # ruled out by the dynamic-range heuristic, never overflow to inf.
    "fp16_overflow": np.where(np.arange(2048) % 64 == 0,
                              1.0e5, 0.0).astype(np.float32),
    "single_nnz": np.eye(1, 300, 42, dtype=np.float32).reshape(-1),
    "wide_gap": np.bincount([0, 150000], weights=[1.0, -2.0],
                            minlength=200000).astype(np.float32),
}


class TestRoundTrip:
    @pytest.mark.parametrize("name", sorted(BLOBS))
    def test_lossless_exact(self, name):
        blob = BLOBS[name]
        frame, residual = wc.encode_blob(blob)
        assert residual is None  # lossless tiers carry no residual
        out = wc.decode_blob(frame)
        assert out.dtype == blob.dtype
        np.testing.assert_array_equal(out, blob)

    @pytest.mark.parametrize("name", sorted(BLOBS))
    def test_lossy_bounded(self, name):
        blob = BLOBS[name]
        frame, residual = wc.encode_blob(blob, lossy=True)
        out = wc.decode_blob(frame)
        assert np.all(np.isfinite(out)), "lossy tier overflowed"
        if residual is None:
            np.testing.assert_array_equal(out, blob)
        else:
            # decoded + residual == original: the residual is exactly
            # the information the wire dropped.
            np.testing.assert_allclose(out + residual, blob, rtol=0,
                                       atol=1e-5)

    @pytest.mark.parametrize("tier_floats", [
        np.zeros(100, np.float32),                          # sparse empty
        _power_law_blob(4096, 64, seed=1),                  # sparse f32/f16/i8
        np.linspace(-1, 1, 4096, dtype=np.float32),         # dense f16/i8
        np.linspace(-1e5, 1e5, 4096, dtype=np.float32),     # fp16-ineligible
    ])
    def test_every_lossy_choice_reversible(self, tier_floats):
        frame, residual = wc.encode_blob(tier_floats, lossy=True)
        out = wc.decode_blob(frame)
        ref = tier_floats if residual is None else tier_floats - residual
        np.testing.assert_allclose(out, ref, rtol=0, atol=1e-4)

    def test_nan_and_inf_survive_every_mode(self):
        # NaN compares False against the clip threshold: a naive
        # magnitude test would drop a diverging trainer's NaN gradients
        # and deliver ZEROS, masking the divergence. Non-finite slots
        # must ride the index stream and come back bit-identical, in
        # both lossless and lossy modes (where they also disqualify the
        # fp16/int8 tiers).
        blob = _power_law_blob(4096, 64, seed=7)
        blob[100] = np.nan
        blob[200] = np.inf
        blob[300] = -np.inf
        for lossy in (False, True):
            frame, residual = wc.encode_blob(blob, lossy=lossy)
            out = wc.decode_blob(frame)
            assert residual is None  # lossy tiers must opt out
            np.testing.assert_array_equal(out, blob)

    def test_non_float32_rides_raw(self):
        for arr in (np.arange(7, dtype=np.int64),
                    np.frombuffer(b"option blob bytes", np.uint8),
                    np.array([1.5, 0.0, 2.5], np.float64)):
            frame, residual = wc.encode_blob(arr, lossy=True)
            assert wc.peek_tier(frame) == wc.RAW
            assert residual is None
            out = wc.decode_blob(frame)
            assert out.dtype == arr.dtype
            np.testing.assert_array_equal(out, arr)

    def test_fp16_overflow_never_picks_fp16(self):
        frame, _ = wc.encode_blob(BLOBS["fp16_overflow"], lossy=True)
        assert wc.peek_tier(frame) not in (wc.SPARSE_F16, wc.DENSE_F16)

    def test_bad_magic_rejected(self):
        with pytest.raises(ValueError, match="magic"):
            wc.decode_blob(np.zeros(64, np.uint8))

    def test_is_codec_frame_sniff(self):
        frame, _ = wc.encode_blob(_power_law_blob(1024, 16, seed=9))
        assert wc.is_codec_frame(frame)
        # Raw float32 values, short buffers, and near-miss headers all
        # sniff negative — receivers fall back to the raw layout.
        assert not wc.is_codec_frame(
            np.linspace(0, 1, 256, dtype=np.float32))
        assert not wc.is_codec_frame(np.zeros(8, np.uint8))
        broken = bytearray(frame)
        broken[3] = 99  # unknown tier
        assert not wc.is_codec_frame(bytes(broken))


class TestCompressionRatio:
    def test_beats_old_float64_pairs_on_sparse_gradient(self):
        # CI gate for the headline claim: a canned power-law sparse
        # gradient must shrink vs BOTH the removed float64-pair format
        # (16 B/pair + 8 B size record) and the raw dense bytes.
        blob = _power_law_blob(1 << 18, (1 << 18) // 20, seed=3)
        nnz = int(np.count_nonzero(blob))
        old_bytes = 16 * nnz + 8
        frame, _ = wc.encode_blob(blob)
        assert old_bytes / len(frame) > 2.0, (old_bytes, len(frame))
        assert blob.nbytes / len(frame) > 1.0
        lossy_frame, _ = wc.encode_blob(blob, lossy=True)
        assert len(lossy_frame) < len(frame)

    def test_dense_blob_costs_only_header(self):
        dense = np.arange(1, 4097, dtype=np.float32)
        frame, _ = wc.encode_blob(dense)
        assert len(frame) == wc.HEADER_BYTES + dense.nbytes


class TestGoldenHeader:
    def test_header_layout_stable(self):
        # Golden bytes: the on-wire header of a known blob. Any change
        # here is a WIRE FORMAT BREAK — bump VERSION and update
        # docs/WIRE_FORMAT.md, don't just fix the test.
        blob = np.zeros(256, np.float32)
        blob[[3, 10]] = [1.0, -2.0]
        frame, _ = wc.encode_blob(blob)
        assert frame[:24] == (
            b"MV"                       # magic
            b"\x01"                     # version
            b"\x01"                     # tier = SPARSE_F32
            b"\x00"                     # dtype = float32
            b"\x01"                     # idx encoding = u16 gaps
            b"\x00\x00"                 # chunk (unused for f32)
            b"\x00\x01\x00\x00\x00\x00\x00\x00"   # n = 256
            b"\x02\x00\x00\x00\x00\x00\x00\x00")  # nnz = 2
        # Payload: first idx u32(3), gap u16(7), two fp32 values.
        assert frame[24:] == (b"\x03\x00\x00\x00" b"\x07\x00"
                              + np.array([1.0, -2.0], np.float32).tobytes())

    def test_raw_header_stable(self):
        frame, _ = wc.encode_blob(np.arange(3, dtype=np.int32))
        assert frame[:8] == b"MV\x01\x00\x02\x00\x00\x00"
        assert frame[8:24] == (3).to_bytes(8, "little") * 2


class TestErrorFeedback:
    def test_residual_fold_bounds_accumulated_error(self):
        # OneBitFilter-style error feedback: folding the residual into
        # the next delta keeps the ACCUMULATED decoded sum within one
        # quantization step of the true sum, instead of drifting by
        # O(steps) * step.
        rng = np.random.default_rng(11)
        n, nnz, steps = 1 << 14, 1 << 9, 25
        idx = np.sort(rng.choice(n, nnz, replace=False))
        true_sum = np.zeros(n, np.float64)
        fed_sum = np.zeros(n, np.float64)
        naive_sum = np.zeros(n, np.float64)
        residual = np.zeros(n, np.float32)
        one_step_err = 0.0
        for _ in range(steps):
            g = np.zeros(n, np.float32)
            g[idx] = rng.standard_normal(nnz).astype(np.float32)
            true_sum += g
            frame, res = wc.encode_blob(g + residual, lossy=True)
            residual = res if res is not None \
                else np.zeros(n, np.float32)
            fed_sum += wc.decode_blob(frame)
            nf, nres = wc.encode_blob(g, lossy=True)
            naive_sum += wc.decode_blob(nf)
            if nres is not None:
                one_step_err = max(one_step_err,
                                   float(np.abs(nres).max()))
        fed_err = float(np.abs(fed_sum - true_sum).max())
        naive_err = float(np.abs(naive_sum - true_sum).max())
        assert fed_err <= one_step_err * 2 + 1e-5, (fed_err, one_step_err)
        assert fed_err < naive_err  # feedback strictly beats drift


class TestMessageFilter:
    def _msg(self, *arrays):
        msg = Message(src=0, dst=1, msg_type=MsgType.Request_Add,
                      table_id=0, msg_id=5)
        for arr in arrays:
            msg.push(Blob(arr))
        return msg

    def test_message_roundtrip_mixed_blobs(self):
        keys = np.arange(64, dtype=np.int32)
        vals = _power_law_blob(1 << 15, 200, seed=5)
        opt = np.frombuffer(b"\x01\x02" * 24, np.uint8).copy()
        msg = self._msg(keys.view(np.uint8), vals, opt)
        assert wc.encode_message(msg)
        assert msg.header[CODEC_SLOT] == 1
        wire = sum(b.size for b in msg.data)
        assert wire < keys.nbytes + vals.nbytes + opt.nbytes
        wc.decode_message(msg)
        assert msg.header[CODEC_SLOT] == 0
        np.testing.assert_array_equal(
            msg.data[0].as_array(np.int32), keys)
        np.testing.assert_array_equal(
            msg.data[1].as_array(np.float32), vals)
        np.testing.assert_array_equal(msg.data[2].as_array(np.uint8), opt)

    def test_small_messages_pass_through(self):
        msg = self._msg(np.arange(8, dtype=np.int32).view(np.uint8))
        assert not wc.encode_message(msg)
        assert msg.header[CODEC_SLOT] == 0

    def test_transport_filter_is_lossless(self):
        # The filter stage must never quantize: table keys and replies
        # ride the same path as values.
        vals = np.linspace(-3, 3, 4096).astype(np.float32)
        msg = self._msg(vals)
        wc.encode_message(msg)
        wc.decode_message(msg)
        np.testing.assert_array_equal(
            msg.data[0].as_array(np.float32), vals)

    def test_double_encode_is_noop(self):
        msg = self._msg(_power_law_blob(1 << 14, 64, seed=6))
        assert wc.encode_message(msg)
        sizes = [b.size for b in msg.data]
        assert not wc.encode_message(msg)  # already marked
        assert [b.size for b in msg.data] == sizes


class TestNegotiation:
    """Mixed-version handshake: a passthrough peer (no CAP_WIRE_CODEC)
    must keep receiving plain frames. Unit level — the TCP two-process
    flavor lives in test_net_integration.py."""

    def test_controller_collects_and_broadcasts_caps(self):
        from multiverso_tpu.runtime import actor as actors
        from multiverso_tpu.runtime.controller import Controller

        sent = []

        class _FakeZoo:
            net_size = 2
            rank = 0

            def register_actor(self, a):
                pass

            def send_to(self, name, msg):
                sent.append(msg)

        ctrl = Controller(_FakeZoo())
        # Rank 0 advertises the codec (3-int register blob); rank 1 is
        # an old peer sending the legacy 2-int blob.
        new_peer = Message(src=0, dst=0,
                           msg_type=MsgType.Control_Register)
        new_peer.push(Blob(np.array([0, 3, wc.CAP_WIRE_CODEC],
                                    np.int32)))
        old_peer = Message(src=1, dst=0,
                           msg_type=MsgType.Control_Register)
        old_peer.push(Blob(np.array([1, 3], np.int32)))
        ctrl._process_register(new_peer)
        ctrl._process_register(old_peer)
        assert len(sent) == 2
        for reply in sent:
            caps = reply.data[2].as_array(np.int32)
            assert caps[0] == wc.CAP_WIRE_CODEC and caps[1] == 0
        assert actors.CONTROLLER == "controller"  # module really used

    def test_zoo_defaults_unknown_peers_to_passthrough(self):
        from multiverso_tpu.runtime.zoo import Zoo
        zoo = Zoo()
        assert zoo.peer_caps(0) == 0  # before registration: passthrough


class TestBatchAddFraming:
    def test_pack_unpack_identity(self):
        subs = []
        for i in range(5):
            sub = Message(src=2, dst=1, msg_type=MsgType.Request_Add,
                          table_id=i % 2, msg_id=100 + i)
            sub.push(Blob(np.array([i], np.int32).view(np.uint8)))
            sub.push(Blob(np.full(8, float(i), np.float32)))
            if i % 2:
                sub.push(Blob(np.zeros(4, np.uint8)))
            subs.append(sub)
        batch = pack_add_batch(subs)
        assert batch.type == MsgType.Request_BatchAdd
        assert batch.src == 2 and batch.dst == 1
        out = unpack_add_batch(batch)
        assert [(m.table_id, m.msg_id, len(m.data)) for m in out] \
            == [(m.table_id, m.msg_id, len(m.data)) for m in subs]
        for a, b in zip(out, subs):
            for blob_a, blob_b in zip(a.data, b.data):
                np.testing.assert_array_equal(
                    blob_a.as_array(np.uint8), blob_b.as_array(np.uint8))

    def test_truncated_batch_rejected(self):
        sub = Message(src=0, dst=1, msg_type=MsgType.Request_Add,
                      table_id=0, msg_id=1)
        sub.push(Blob(np.ones(4, np.float32)))
        batch = pack_add_batch([sub])
        batch.data = batch.data[:-1]  # lose a payload blob
        with pytest.raises(ValueError, match="batch add"):
            unpack_add_batch(batch)

    def test_batch_survives_codec_filter(self):
        # Coalesced messages ride the same filter stage: descriptor and
        # sub-blobs must round-trip through encode/decode.
        subs = []
        for i in range(3):
            sub = Message(src=0, dst=1, msg_type=MsgType.Request_Add,
                          table_id=0, msg_id=i)
            sub.push(Blob(np.arange(4, dtype=np.int32).view(np.uint8)))
            sub.push(Blob(_power_law_blob(1 << 13, 50, seed=i)))
            subs.append(sub)
        batch = pack_add_batch(subs)
        wc.encode_message(batch)
        wc.decode_message(batch)
        out = unpack_add_batch(batch)
        assert len(out) == 3
        np.testing.assert_array_equal(
            out[2].data[1].as_array(np.float32),
            _power_law_blob(1 << 13, 50, seed=2))


class TestSparseStreamHelpers:
    """decode_blob_sparse + the public density/break-even helpers the
    sparse collective tier rides (docs/ALLREDUCE.md break-even model)."""

    def test_sparse_frame_streams_without_densifying(self):
        blob = _power_law_blob(1 << 16, 1 << 11, seed=3)
        frame, _ = wc.encode_blob(blob)
        assert wc.peek_tier(frame) in (wc.SPARSE_F32,)
        idx, vals = wc.decode_blob_sparse(frame)
        assert idx is not None
        ref_idx = np.nonzero(blob)[0]
        np.testing.assert_array_equal(np.asarray(idx), ref_idx)
        np.testing.assert_array_equal(np.asarray(vals), blob[ref_idx])
        # scatter-rebuild equals the dense decode
        full = np.zeros(blob.size, np.float32)
        full[idx] = vals
        np.testing.assert_array_equal(full, wc.decode_blob(frame))

    def test_dense_and_raw_frames_stream_as_dense(self):
        dense = np.ones(2000, np.float32)
        frame, _ = wc.encode_blob(dense)
        idx, vals = wc.decode_blob_sparse(frame)
        assert idx is None
        np.testing.assert_array_equal(np.asarray(vals), dense)
        ints = np.arange(100, dtype=np.int64)
        frame, _ = wc.encode_blob(ints)
        idx, vals = wc.decode_blob_sparse(frame)
        assert idx is None and vals.dtype == np.int64
        np.testing.assert_array_equal(np.asarray(vals), ints)

    def test_lossy_sparse_frame_streams(self):
        blob = _power_law_blob(1 << 16, 1 << 11, seed=5)
        frame, residual = wc.encode_blob(blob, lossy=True)
        idx, vals = wc.decode_blob_sparse(frame)
        assert idx is not None
        full = np.zeros(blob.size, np.float32)
        full[idx] = vals
        np.testing.assert_allclose(full + residual, blob, atol=1e-5)

    def test_density_of(self):
        x = np.zeros(1000, np.float32)
        assert wc.density_of(x) == 0.0
        x[:250] = 1.0
        assert wc.density_of(x) == 0.25
        assert wc.density_of(np.zeros(0, np.float32)) == 0.0

    def test_break_even_density_flag_driven(self):
        from multiverso_tpu.util.configure import set_flag
        assert wc.break_even_density() == 0.5
        blob = np.zeros(4096, np.float32)
        blob[: 4096 * 2 // 5] = 1.0  # density 0.4
        assert wc.worth_encoding(blob)
        set_flag("wire_codec_density", 0.3)
        assert wc.break_even_density() == 0.3
        assert not wc.worth_encoding(blob)

    def test_worth_encoding_gates(self):
        # non-f32 and sub-1KB payloads never encode, any density
        assert not wc.worth_encoding(np.zeros(4096, np.float64))
        assert not wc.worth_encoding(np.zeros(64, np.float32))
        assert wc.worth_encoding(np.zeros(4096, np.float32))

"""Zero-copy wire path: golden frames, buffer pool, COW discipline.

PR 15 (docs/MEMORY.md): the send side serializes scatter-gather view
lists drained by vectored ``sendmsg`` writes; the receive side leases
pooled frame buffers and cuts READ-ONLY Blob views out of them. The
contract under test:

* frames are BYTE-IDENTICAL to the reference flat serializer's
  (``tcp._serialize``) across the whole header-slot space, codec frames
  and batch descriptors, so the wire format is the documented one;
* the pool recycles only export-free buffers (a blob-outlived array can
  never be scribbled), leases always succeed, hit/miss/resident
  accounting holds, and concurrent lease/release survives
  ``-debug_locks``;
* pool-backed views are read-only (mutation raises) and
  ``Blob.materialize()`` is the copy-on-write escape hatch;
* TCP round trips with the pool active deliver correct payloads, both
  directions, including re-sending received (view-backed) blobs.
"""

from __future__ import annotations

import gc
import threading

import numpy as np
import pytest

from multiverso_tpu.core.blob import Blob
from multiverso_tpu.core.message import (CODEC_SLOT, Message, MsgType,
                                         pack_add_batch)
from multiverso_tpu.runtime.tcp import (TcpNet, _deserialize,
                                        _deserialize_frame, _serialize,
                                        serialize_views)
from multiverso_tpu.util import wire_codec as wc
from multiverso_tpu.util.buffer_pool import BufferPool, FrameLease
from multiverso_tpu.util.configure import set_flag
from multiverso_tpu.util.net_util import free_listen_port


def joined(views) -> bytes:
    return b"".join(bytes(v) for v in views)


def random_message(rng: np.random.Generator) -> Message:
    """A message with every header slot 0-9 exercised and a random blob
    mix (dtypes, sizes, empties, raw bytes)."""
    msg = Message(src=int(rng.integers(0, 8)),
                  dst=int(rng.integers(0, 8)),
                  msg_type=MsgType.Request_Get,
                  table_id=int(rng.integers(-1, 16)),
                  msg_id=int(rng.integers(-1, 1 << 20)))
    # Slots 5-9 carry error/codec/version/replica/trace values on real
    # traffic; golden identity must hold for arbitrary ints.
    for slot in range(5, 10):
        msg.header[slot] = int(rng.integers(0, 1 << 30))  # mvlint: ignore[wire-slot]
    dtypes = [np.float32, np.int32, np.uint8, np.float64, np.int64]
    for _ in range(int(rng.integers(0, 4))):
        kind = int(rng.integers(0, 3))
        if kind == 0:
            n = int(rng.integers(0, 300))
            dt = dtypes[int(rng.integers(0, len(dtypes)))]
            msg.push(Blob(rng.standard_normal(n).astype(dt)))
        elif kind == 1:
            msg.push(Blob(bytes(rng.integers(0, 256, int(rng.integers(
                0, 64)), dtype=np.uint8))))
        else:
            msg.push(Blob(np.zeros(0, np.float32)))  # empty blob
    return msg


class TestGoldenFrames:
    def test_property_views_equal_flat_serializer(self):
        rng = np.random.default_rng(123)
        for _ in range(200):
            msg = random_message(rng)
            flat = _serialize(msg)
            views, nbytes = serialize_views(msg)
            assert nbytes == len(flat)
            assert joined(views) == flat

    def test_codec_frames_identical(self):
        # Parted codec blobs (header + stream parts) must frame the
        # same bytes as the flat encode_blob output.
        rng = np.random.default_rng(7)
        dense = rng.standard_normal(4096).astype(np.float32)
        sparse = np.zeros(8192, np.float32)
        idx = np.sort(rng.choice(8192, 200, replace=False))
        sparse[idx] = rng.standard_normal(200).astype(np.float32)
        for payload in (dense, sparse):
            for lossy in (False, True):
                parts, _ = wc.encode_blob_views(payload, lossy=lossy)
                flat, _ = wc.encode_blob(payload, lossy=lossy)
                msg = Message(src=0, dst=1, msg_type=MsgType.Default)
                msg.data.append(Blob.from_parts(parts))
                msg.header[CODEC_SLOT] = 1
                ref = Message(src=0, dst=1, msg_type=MsgType.Default)
                ref.push(Blob(np.frombuffer(flat, np.uint8)))
                ref.header[CODEC_SLOT] = 1
                assert joined(serialize_views(msg)[0]) == _serialize(ref)
                decoded = wc.decode_blob(msg.data[0].data)
                if lossy:
                    np.testing.assert_allclose(decoded, payload,
                                               rtol=0, atol=2e-2)
                else:
                    np.testing.assert_array_equal(decoded, payload)

    def test_encode_message_parts_roundtrip(self):
        sparse = np.zeros(4096, np.float32)
        sparse[::13] = 1.5
        msg = Message(src=0, dst=1, msg_type=MsgType.Request_Add)
        msg.push(Blob(sparse))
        assert wc.encode_message(msg)
        assert msg.data[0]._parts is not None  # parted, not joined
        views, _ = serialize_views(msg)
        wc.decode_message(msg)
        np.testing.assert_array_equal(
            msg.data[0].as_array(np.float32), sparse)

    def test_batch_descriptor_frames_identical(self):
        subs = []
        for i in range(3):
            sub = Message(src=0, dst=1, msg_type=MsgType.Request_Add,
                          table_id=i, msg_id=100 + i)
            sub.push(Blob(np.arange(4, dtype=np.int32)))
            sub.push(Blob(np.full(8, float(i), np.float32)))
            subs.append(sub)
        batch = pack_add_batch(subs)
        assert joined(serialize_views(batch)[0]) == _serialize(batch)

    def test_view_frame_parses_back(self):
        rng = np.random.default_rng(5)
        for _ in range(40):
            msg = random_message(rng)
            flat = _serialize(msg)
            pool = BufferPool(capacity_mb=4, classes=8)
            lease = pool.lease(len(flat) - 8)
            view = lease.view(len(flat) - 8)
            view[:] = flat[8:]
            out = _deserialize_frame(lease.view(len(flat) - 8), lease)
            ref = _deserialize(bytearray(flat[8:]))
            assert out.header == ref.header == msg.header
            assert len(out.data) == len(msg.data)
            for got, want in zip(out.data, msg.data):
                np.testing.assert_array_equal(got.wire_bytes(),
                                              want.wire_bytes())


class TestBufferPool:
    def test_hit_miss_and_resident_accounting(self):
        pool = BufferPool(capacity_mb=1, classes=4)  # 4K..32K
        lease = pool.lease(5000)  # -> 8K class
        assert lease.nbytes == 8192
        buf_id = id(lease._buf)
        lease.release()
        assert pool.resident_bytes == 8192
        again = pool.lease(6000)
        assert id(again._buf) == buf_id  # recycled, not reallocated
        assert pool.resident_bytes == 0

    def test_release_idempotent(self):
        pool = BufferPool(capacity_mb=1, classes=4)
        lease = pool.lease(100)
        lease.release()
        lease.release()
        assert pool.resident_bytes == 4096

    def test_oversized_frame_unpooled(self):
        pool = BufferPool(capacity_mb=64, classes=3)  # max 16K
        lease = pool.lease(1 << 20)
        assert lease.nbytes == 1 << 20
        lease.release()
        assert pool.resident_bytes == 0  # never retained

    def test_disabled_pool_still_leases(self):
        pool = BufferPool(capacity_mb=0)
        assert not pool.enabled
        lease = pool.lease(4096)
        lease.view(4096)[:] = b"\x07" * 4096
        lease.release()
        assert pool.resident_bytes == 0

    def test_capacity_cap_drops_to_gc(self):
        pool = BufferPool(capacity_mb=1, classes=9)  # max class 1 MB
        a = pool.lease(1 << 20)
        b = pool.lease(1 << 20)
        a.release()
        b.release()
        # Cap is 1 MB: only one buffer retained, the second dropped.
        assert pool.resident_bytes == 1 << 20

    def test_blob_outlives_frame_lease_safety(self):
        """An array extracted from a pool blob and held past the Blob
        must never be aliased by a recycled frame."""
        pool = BufferPool(capacity_mb=4, classes=8)
        msg = Message(src=0, dst=1, msg_type=MsgType.Request_Get)
        msg.push(Blob(np.arange(1000, dtype=np.float32)))
        flat = _serialize(msg)
        lease = pool.lease(len(flat) - 8)
        lease.view(len(flat) - 8)[:] = flat[8:]
        out = _deserialize_frame(lease.view(len(flat) - 8), lease)
        del lease
        kept = out.data[0].as_array(np.float32)
        del out, msg
        gc.collect()
        # The frame buffer is still exported through `kept`: the pool
        # must NOT have retaken it.
        assert pool.resident_bytes == 0
        # Churn the pool: new leases must not scribble `kept`.
        for _ in range(8):
            lse = pool.lease(len(flat) - 8)
            lse.view(len(flat) - 8)[:] = b"\xff" * (len(flat) - 8)
            lse.release()
        np.testing.assert_array_equal(
            kept, np.arange(1000, dtype=np.float32))
        # Once the last export dies, the parked buffer is reclaimed by
        # a later lease's pending sweep.
        del kept
        gc.collect()
        pool.lease(16).release()
        assert pool.resident_bytes > 0

    def test_frame_recycles_when_blobs_die_first(self):
        pool = BufferPool(capacity_mb=4, classes=8)
        msg = Message(src=0, dst=1, msg_type=MsgType.Request_Get)
        msg.push(Blob(np.arange(256, dtype=np.float32)))
        flat = _serialize(msg)
        lease = pool.lease(len(flat) - 8)
        lease.view(len(flat) - 8)[:] = flat[8:]
        out = _deserialize_frame(lease.view(len(flat) - 8), lease)
        del lease
        assert pool.resident_bytes == 0  # blob still pins the frame
        del out
        gc.collect()
        assert pool.resident_bytes > 0  # last blob out returned it

    def test_read_only_mutation_guard_raises(self):
        msg = Message(src=0, dst=1, msg_type=MsgType.Request_Get)
        msg.push(Blob(np.ones(64, np.float32)))
        flat = _serialize(msg)
        pool = BufferPool(capacity_mb=4, classes=8)
        lease = pool.lease(len(flat) - 8)
        lease.view(len(flat) - 8)[:] = flat[8:]
        out = _deserialize_frame(lease.view(len(flat) - 8), lease)
        blob = out.data[0]
        assert blob.pool_backed
        with pytest.raises(ValueError):
            blob.as_array(np.float32)[0] = 2.0
        # Copy-on-write: materialize yields a private writable payload
        # and drops the lease so the frame can recycle.
        blob.materialize()
        assert not blob.pool_backed
        blob.as_array(np.float32)[0] = 2.0
        assert blob.as_array(np.float32)[0] == 2.0

    def test_concurrent_lease_release_under_debug_locks(self):
        set_flag("debug_locks", True)
        try:
            pool = BufferPool(capacity_mb=8, classes=8)
            errors = []

            def pound(seed):
                rng = np.random.default_rng(seed)
                try:
                    for _ in range(300):
                        n = int(rng.integers(1, 200_000))
                        lease = pool.lease(n)
                        view = lease.view(min(n, 64))
                        view[:] = bytes([seed]) * view.nbytes
                        lease.release()
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)

            threads = [threading.Thread(target=pound, args=(t,))
                       for t in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
            assert not any(t.is_alive() for t in threads)
            assert not errors, errors
            assert pool.resident_bytes <= 8 << 20
        finally:
            set_flag("debug_locks", False)

    def test_bytearray_blob_is_one_private_copy(self):
        src = bytearray(b"abcdef")
        blob = Blob(src)
        src[0] = ord("z")  # caller keeps mutating its buffer
        assert bytes(blob.as_array(np.uint8)[:1]) == b"a"

    def test_bytes_blob_is_zero_copy_read_only(self):
        blob = Blob(b"abcd")
        arr = blob.as_array(np.uint8)
        assert not arr.flags.writeable
        with pytest.raises(ValueError):
            arr[0] = 1
        blob.materialize().as_array(np.uint8)[0] = 9


class TestTextPayload:
    def test_matches_manual_decode(self):
        msg = Message(src=0, dst=1, msg_type=MsgType.Default)
        text = "héllo wörld — zero copy"
        msg.push(Blob(text.encode()))
        assert msg.text_payload() == text

    def test_index_and_errors(self):
        msg = Message(src=0, dst=1, msg_type=MsgType.Default)
        msg.push(Blob(np.zeros(3, np.float32)))
        msg.push(Blob(b"\xff\xfe not utf8"))
        out = msg.text_payload(1)
        assert "not utf8" in out  # invalid bytes replaced, not raised


class _Pair:
    """Two TcpNet endpoints over loopback."""

    def __enter__(self):
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        self.nets = [TcpNet(r, eps) for r in range(2)]
        return self.nets

    def __exit__(self, *exc):
        for net in self.nets:
            net.finalize()


class TestTcpZeroCopy:
    def test_round_trip_with_pool_active(self):
        with _Pair() as (a, b):
            for i in range(10):
                msg = Message(src=0, dst=1,
                              msg_type=MsgType.Request_Add, msg_id=i)
                msg.push(Blob(np.full(4096, float(i), np.float32)))
                msg.push(Blob(f"payload {i}".encode()))
                a.send(msg)
            for i in range(10):
                got = b.recv(timeout=30)
                assert got.msg_id == i
                assert got.data[0].pool_backed
                np.testing.assert_array_equal(
                    got.data[0].as_array(np.float32),
                    np.full(4096, float(i), np.float32))
                assert got.text_payload(1) == f"payload {i}"

    def test_echo_of_received_view_blobs(self):
        # The pingpong idiom: re-sending a received (pool-view) blob
        # must serialize straight from the leased frame.
        with _Pair() as (a, b):
            msg = Message(src=0, dst=1, msg_type=MsgType.Request_Get,
                          msg_id=3)
            payload = np.linspace(0, 1, 50_000).astype(np.float32)
            msg.push(Blob(payload))
            a.send(msg)
            got = b.recv(timeout=30)
            reply = got.create_reply_message()
            reply.data = list(got.data)
            b.send(reply)
            back = a.recv(timeout=30)
            assert back.type == MsgType.Reply_Get
            np.testing.assert_array_equal(
                back.data[0].as_array(np.float32), payload)

    def test_async_and_large_unpooled_frames(self):
        with _Pair() as (a, b):
            big = np.arange(3 << 20, dtype=np.uint8)  # > max pool class
            msg = Message(src=0, dst=1, msg_type=MsgType.Request_Add,
                          msg_id=8)
            msg.push(Blob(big))
            a.send_async(msg)
            a.flush_sends()
            got = b.recv(timeout=30)
            np.testing.assert_array_equal(got.data[0].as_array(np.uint8),
                                          big)

    def test_many_blob_frame_beyond_iov_cap(self):
        # >64 payload views in one frame exercises the sendmsg batching
        # loop (_IOV_CAP) and partial-send advance.
        with _Pair() as (a, b):
            msg = Message(src=0, dst=1, msg_type=MsgType.Request_Add,
                          msg_id=9)
            for i in range(200):
                msg.push(Blob(np.full(17, i, np.int32)))
            a.send(msg)
            got = b.recv(timeout=30)
            assert len(got.data) == 200
            for i in (0, 63, 64, 150, 199):
                np.testing.assert_array_equal(
                    got.data[i].as_array(np.int32),
                    np.full(17, i, np.int32))

    def test_echo_copies_framing_bytes_only(self):
        # What the wire path copies per payload byte, counted: the
        # framer builds only the length prefix, header and size table,
        # the parser cuts views out of leased frames, and an echo
        # re-sends those views. Released leases come back as pool hits.
        from multiverso_tpu.util.dashboard import Dashboard
        counters = ("WIRE_BYTES_COPIED", "WIRE_PAYLOAD_BYTES", "POOL_HIT")
        before = {n: Dashboard.get(n).count for n in counters}
        payload = np.arange(64 << 10, dtype=np.float32)  # 256 KB
        with _Pair() as (a, b):
            for i in range(8):
                msg = Message(src=0, dst=1,
                              msg_type=MsgType.Request_Get, msg_id=i)
                msg.push(Blob(payload))
                a.send(msg)
                got = b.recv(timeout=30)
                reply = got.create_reply_message()
                reply.data = list(got.data)
                b.send(reply)
                back = a.recv(timeout=30)
                np.testing.assert_array_equal(
                    back.data[0].as_array(np.float32), payload)
                del got, reply, back
                gc.collect()
        grew = {n: Dashboard.get(n).count - before[n] for n in counters}
        # Send and receive each count the payload: 8 echoes, 4 passes.
        assert grew["WIRE_PAYLOAD_BYTES"] == 8 * 4 * payload.nbytes
        assert grew["WIRE_BYTES_COPIED"] < 1e-3 * grew["WIRE_PAYLOAD_BYTES"]
        assert grew["POOL_HIT"] > 0


class TestLeaseViewHelpers:
    def test_lease_view_is_writable_window(self):
        lease = FrameLease(None, bytearray(64))
        view = lease.view(16)
        view[:] = b"x" * 16
        assert lease.nbytes == 64
        lease.release()
        assert lease.nbytes == 0

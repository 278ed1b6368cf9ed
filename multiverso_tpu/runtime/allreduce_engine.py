"""Hand-rolled collectives over point-to-point transport (control plane).

Functional equivalent of the reference's ``AllreduceEngine``
(ref: include/multiverso/net/allreduce_engine.h:80-168,
src/net/allreduce_engine.cpp:31-172), grown into a chunked, pipelined
collective stack:

- **small path**: Bruck-style doubling allgather + local reduce, same
  size threshold as the reference (ref: allreduce_engine.cpp:31-54);
- **recursive halving**: the reference's reduce-scatter + allgather with
  an initial fold of surplus ranks onto a power-of-two group — the
  *monolithic* path (one blocking sendrecv per round);
- **chunked ring** (new): ring reduce-scatter + ring allgather over
  ``-allreduce_chunk_kb`` chunks with a sliding window of in-flight
  frames riding the transport's ``send_async`` writer threads, so round
  k's wire time overlaps round k+1's receive + reduce (SparCML-style
  chunking). Works for ANY rank count (no surplus fold), which is why
  non-power-of-two worlds prefer it even at modest sizes;
- **sparse stream** (new): for sparse float32 sums (model-average
  deltas are power-law sparse — SparCML, arxiv 1802.08021 / 1312.3020)
  a direct reduce-scatter of codec sparse index+value frames — every
  rank ships only its own nonzeros straight to each segment's owner,
  so hop-by-hop fill-in never rides the wire — followed by a
  single-encode ring allgather of the reduced segments. The owner
  merges inbound index streams in rank order (union of indices, sum of
  values, fill-in tracked per hop into ``SPARSE_FILL[*]``), which
  reproduces the unchunked dense ring's fold association exactly:
  lossless sparse results are bit-identical to the dense ring's.
  ``choose_algo`` picks it from a cluster-agreed nnz probe and falls
  back to the dense ring once the union density crosses the break-even
  (``-allreduce_sparse_*``); ``sharded_average`` adds the cross-replica
  sharded model-average step (arxiv 2004.13336): reduce-scatter,
  shard-local divide, allgather — per-rank reduce-state is one segment
  instead of the full buffer.

Per-chunk segments >= 4 KB ride the wire codec; the opt-in
``-allreduce_lossy`` tier quantizes segment values (int8 / f16 via
``util/wire_codec``) *inside* the collective with per-destination
error-feedback residuals carried across calls (EQuARX-style), so
quantization noise averages out over training steps instead of
accumulating. In the allgather phase each reduced segment is encoded
ONCE at its owner and the encoded frame is forwarded verbatim around the
ring — no re-quantization per hop, and every rank (owner included)
decodes the same bytes, so lossy results are still bit-identical across
ranks.

Every message's ``msg_id`` carries a per-call generation in its high
bits: back-to-back collectives with different round counts (or a future
concurrent caller) can never cross-match stash entries.

On TPU this engine is the *fallback* path: the data plane rides XLA
collectives over ICI (``multiverso_tpu.parallel``); this host-side engine
exists for model-average mode over the control transport where no device
mesh spans the ranks (the reference's ``-ma`` mode bypasses the PS the
same way, ref: src/zoo.cpp:49). It drives the raw endpoint directly, so
it must only run when the PS actors are down (ma mode) — exactly the
reference's usage pattern. See docs/ALLREDUCE.md for the algorithm
choice table and flag semantics.
"""

from __future__ import annotations

import collections
import time
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ..core.blob import Blob
from ..core.message import Message, MsgType, is_wire_encoded
from ..util.configure import (define_bool, define_double, define_int,
                              define_string, get_flag,
                              register_tunable_hook)
from ..util.dashboard import samples
from ..util.wire_codec import (CODEC_SLOT, break_even_density, decode_blob,
                               decode_blob_sparse, density_of,
                               encode_blob_views, worth_encoding)
from .net import NetInterface

define_string("allreduce_algo", "auto",
              "large-payload allreduce algorithm: auto (pick by payload "
              "size and rank count) | ring (chunked pipelined ring) | "
              "rhalving (monolithic recursive halving)")
define_int("allreduce_chunk_kb", 512,
           "ring path: split the flat buffer into chunks of this many "
           "KB; each chunk is an independent ring whose frames pipeline "
           "on the transport writer threads. Smaller chunks overlap "
           "more but pay more per-frame overhead (~0.3-1.5 ms each on "
           "a single-core host); 512 suited 4-16 MB buffers on a "
           "200 Mbit/s wire (a CPU host; not measured on the chip)")
define_int("allreduce_window", 4,
           "ring path: max in-flight (sent but not yet matched by a "
           "receive) chunks per ring step")
define_int("allreduce_ring_kb", 256,
           "auto algorithm choice: payloads at least this many KB take "
           "the chunked ring path (non-power-of-two worlds switch "
           "earlier — the recursive-halving surplus fold costs two "
           "extra full-buffer serial hops)")
define_double("allreduce_timeout_s", 120.0,
              "seconds a collective waits for one peer frame before "
              "failing loudly (tests lower this to fail fast)")
define_int("allreduce_stash_cap", 4096,
           "max early-arriving frames stashed while waiting for a "
           "specific (src, tag); exceeding it means a crashed peer or a "
           "tag-protocol bug and fails loudly instead of growing "
           "unboundedly")

# Lossy tier flag lives here (the codec's -wire_codec_lossy governs the
# PS matrix-Add filter stage; the collective gets its own opt-in).
define_bool("allreduce_lossy", False,
            "quantize allreduce segment values (int8/f16 wire-codec "
            "tiers) inside the collective, with per-destination "
            "error-feedback residuals carried across calls "
            "(EQuARX-style). Lossless when off — bit-identical to the "
            "unquantized path")
define_double("allreduce_sparse_density", 0.25,
              "auto algorithm choice: float32 sum-allreduces whose "
              "cluster-agreed union density (sum of per-rank nnz / "
              "element count, the nnz-probe upper bound on reduced "
              "fill-in) sits at or below this take the sparse-stream "
              "path; the effective cutoff is additionally clamped to "
              "the codec break-even (-wire_codec_density) — past that "
              "the reduced segments would ride RAW frames and the "
              "index merge buys nothing")
define_int("allreduce_sparse_idx_budget", 8388608,
           "auto algorithm choice: cap on the union index count "
           "(density x elements) the sparse path will carry per "
           "collective — past it the per-index Python merge cost beats "
           "the dense ring's streaming chunks even at low density")


def _chunk_kb_retuned(value) -> None:
    """``-allreduce_chunk_kb`` is read fresh per collective call
    (``_chunk_elems``), so a live retune needs no state rebind — this
    hook declares the handoff (the ``TUNABLE_FLAGS`` contract: every
    tunable names HOW its value lands) and logs the step so the knob
    trajectory is traceable in rank logs, not just controller
    gauges."""
    from ..util import log
    log.info("allreduce: -allreduce_chunk_kb retuned to %s (applies "
             "from the next collective call)", value)


register_tunable_hook("allreduce_chunk_kb", _chunk_kb_retuned)

_SMALL_BYTES = 4096  # allgather-based path threshold (ref: engine.cpp:33)

#: Segment payloads at least this large run through the wire codec on
#: non-in-process transports (lossless tiers; sparse model-average
#: deltas shrink, dense ones ride RAW with only the header overhead).
_CODEC_MIN_BYTES = 4096

# -- msg_id layout: [ 11-bit generation | 20-bit tag ] ----------------
# The generation increments once per public collective call (all ranks
# call collectives in the same order, so engine counters stay in sync);
# a stale frame from call g can never match a key from call g+1 even
# when the low tag bits collide. Tag bases partition the 20-bit space:
_TAG_BITS = 20
_GEN_MOD = 2047  # 11 bits, cycling 1..2047 (msg_id stays positive i32)
_BRUCK_BASE = 1000       # doubling allgather rounds
_RH_BASE = 2000          # recursive-halving rounds
_RH_RESULT = 2900        # surplus-rank final result
_RING_RS_BASE = 100000   # ring reduce-scatter: base + step*nchunks + chunk
_RING_AG_BASE = 550000   # ring allgather:     base + step*nchunks + chunk
_RING_TAG_SPAN = 400000  # per-phase room; bounds (size-1)*nchunks
_PROBE_BASE = 955000     # nnz-agreement allgather before an auto pick
_SPARSE_RS_BASE = 960000  # sparse direct scatter: base + segment
_SPARSE_AG_BASE = 1000000  # sparse allgather ring: base + step


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def choose_algo(nbytes: int, n_elems: int, world: int, *,
                density: Optional[float] = None,
                reducer_is_add: bool = True, is_f32: bool = True,
                forced: Optional[str] = None) -> str:
    """THE algorithm decision — one documented function replacing the
    scattered size checks (auto used to key on byte size only). Every
    input is either cluster-identical by the collective contract
    (payload shape/dtype, reducer, world, flags) or cluster-AGREED
    (``density`` comes from the nnz probe round, the same value on
    every rank), so every rank lands on the same branch — a split
    decision would mismatch the wire protocol.

    Order of precedence:

    1. payloads under 4 KB, or with fewer elements than ranks, take the
       Bruck allgather + local reduce ``small`` path regardless of any
       forced algorithm (the reference's small-path contract);
    2. a forced ``-allreduce_algo`` (ring / rhalving / sparse) wins;
       forcing ``sparse`` for a non-additive reducer or a non-float32
       payload falls back to the ring (the index-union merge is a SUM
       over float32 codec streams, nothing else);
    3. auto, sparse: float32 sum-reductions whose agreed union density
       sits at or below min(``-allreduce_sparse_density``,
       ``break_even_density()``) AND whose union index count
       (density x elements) fits ``-allreduce_sparse_idx_budget`` take
       the sparse-stream path — the measured fill-in signal, re-probed
       every call, is exactly what switches a densifying workload back
       to the dense ring;
    4. auto, dense: at or above ``-allreduce_ring_kb`` the chunked
       ring; non-power-of-two worlds switch to the ring from 16 KB (the
       recursive-halving surplus fold costs two extra full-buffer
       serial hops); everything else recursive halving.
    """
    if nbytes < _SMALL_BYTES or n_elems < world:
        return "bruck"
    algo = str(get_flag("allreduce_algo")) if forced is None else forced
    if algo == "sparse":
        return "sparse" if (reducer_is_add and is_f32) else "ring"
    if algo in ("ring", "rhalving"):
        return algo
    if reducer_is_add and is_f32 and density is not None:
        cutoff = min(float(get_flag("allreduce_sparse_density")),
                     break_even_density())
        if density <= cutoff and density * n_elems <= int(
                get_flag("allreduce_sparse_idx_budget")):
            return "sparse"
    if nbytes >= int(get_flag("allreduce_ring_kb")) * 1024:
        return "ring"
    if not _is_pow2(world) and nbytes >= 4 * _SMALL_BYTES:
        # Surplus fold pays 2 extra full-buffer serial hops; the
        # ring needs no fold, so non-pow2 worlds switch early.
        return "ring"
    return "rhalving"


class AllreduceEngine:
    def __init__(self, net: NetInterface):
        self._net = net
        self.rank = net.rank
        self.size = net.size
        # (src, msg_id) -> (blob, wire_encoded): early-arriving frames.
        # Decoding is lazy so allgather forwarding can relay the exact
        # received frame bytes.
        self._stash: Dict[Tuple[int, int], Tuple[Blob, bool]] = {}
        self._gen = 0
        # Error-feedback residuals, keyed by (phase, element count):
        # carried across calls so quantization noise from step t is
        # folded into step t+1's payload (OneBitFilter convention).
        self._ef: Dict[Tuple[str, int], np.ndarray] = {}
        # Frames are self-describing (CODEC_SLOT marks an encoded
        # payload), so decode needs no negotiation; in ma mode every
        # rank runs this same engine. In-process transports move object
        # references — lossless encoding there only burns CPU (the
        # lossy tier still engages: its point is the quantization
        # semantics, not the bytes). The SPARSE path frames regardless:
        # the index+value stream is the representation its O(nnz) merge
        # runs on, not just a wire shrink.
        self._codec = (not net.in_process
                       and bool(get_flag("wire_codec")))
        #: Algorithm the last public collective ran
        #: (bruck/ring/rhalving/sparse/sharded) — tests read it.
        self.last_algo: Optional[str] = None
        #: Bytes of reduce-state this rank held during the last
        #: collective: the buffer(s) that accumulate reduced values
        #: before the allgather re-assembles the full result. The
        #: sharded paths hold one SEGMENT (~1/world of the buffer);
        #: the monolithic/ring paths hold the full flat copy; the
        #: small path stacks `world` whole blocks.
        self.last_reduce_state_bytes = 0

    # -- msg_id construction --
    def _mid(self, tag: int) -> int:
        return (self._gen << _TAG_BITS) | tag

    def _next_gen(self) -> None:
        self._gen = (self._gen % _GEN_MOD) + 1

    # -- raw paired exchange over the message transport --
    def _post(self, dst: int, blob: Blob, tag: int, encoded: bool) -> None:
        msg = Message(src=self.rank, dst=dst, msg_type=MsgType.Default,
                      msg_id=self._mid(tag))
        msg.push(blob)
        if encoded:
            msg.header[CODEC_SLOT] = 1
        self._net.send_async(msg)

    def _send(self, dst: int, payload: np.ndarray, tag: int) -> None:
        """Lossless send: codec-framed when the wire would benefit."""
        payload = np.ascontiguousarray(payload)
        if self._net.in_process and payload.base is not None:
            # In-process transports deliver references; a view of this
            # rank's working buffer must be snapshotted, or a receiver
            # still holding it (e.g. an allgather forward) would observe
            # later in-place mutations.
            payload = payload.copy()
        # worth_encoding gates on density too: dense model-average
        # segments (the common ma workload) skip the frame-copy round
        # trip a RAW frame would cost.
        if self._codec and payload.nbytes >= _CODEC_MIN_BYTES \
                and worth_encoding(payload):
            # Lossless tiers only; the (header, streams) parts ride the
            # scatter-gather framer unjoined (docs/MEMORY.md).
            parts, _ = encode_blob_views(payload)
            self._post(dst, Blob.from_parts(parts), tag, True)
        else:
            self._post(dst, Blob(payload), tag, False)

    def _send_lossy(self, dst: int, flat: np.ndarray, lo: int, hi: int,
                    tag: int, ef: np.ndarray) -> np.ndarray:
        """Quantized send of ``flat[lo:hi]`` with error feedback: the
        residual from this range's previous quantization is folded into
        the values before encoding and the fresh residual stored back.
        Segments below the codec threshold fall back to the lossless
        path (the folded correction goes out exactly, so the residual
        zeroes). Returns the values AS THE RECEIVER WILL DECODE THEM —
        allgather origins adopt these so every rank lands on identical
        bytes."""
        vals = flat[lo:hi] + ef[lo:hi]
        if vals.nbytes < _CODEC_MIN_BYTES:
            ef[lo:hi] = 0.0
            self._send(dst, vals, tag)
            return vals
        parts, residual = encode_blob_views(vals, lossy=True)
        ef[lo:hi] = residual if residual is not None else 0.0
        self._post(dst, Blob.from_parts(parts), tag, True)
        # decoded == vals - residual; reconstruct instead of re-decoding.
        return vals - ef[lo:hi]

    def _drain_until(self, src: int, tag: int) -> Tuple[Blob, bool]:
        """Tag-matched receive: a fast peer's next-round message may
        arrive before the one this round is waiting on; stash and keep
        draining. Fails loudly (with full context) on timeout, closed
        transport, or unbounded stash growth."""
        key = (src, self._mid(tag))
        timeout = float(get_flag("allreduce_timeout_s"))
        cap = int(get_flag("allreduce_stash_cap"))
        start = time.monotonic()
        while key not in self._stash:
            remaining = timeout - (time.monotonic() - start)
            msg = self._net.recv(timeout=max(remaining, 0.001)) \
                if remaining > 0 else None
            if msg is None:
                raise RuntimeError(
                    f"allreduce engine rank {self.rank}: transport closed "
                    f"or timed out after {time.monotonic() - start:.1f}s "
                    f"(timeout {timeout:.1f}s, -allreduce_timeout_s) "
                    f"waiting for peer {src} msg_id 0x{self._mid(tag):x} "
                    f"(gen {self._gen}, tag {tag}); stash holds "
                    f"{len(self._stash)} early frames "
                    f"{sorted(self._stash)[:8]}")
            self._stash[(msg.src, msg.msg_id)] = \
                (msg.data[0], is_wire_encoded(msg))
            if key in self._stash:
                # The awaited frame landed: popping it below shrinks
                # the stash again, so don't let a boundary-sitting cap
                # fail a collective at the moment it makes progress.
                break
            if len(self._stash) > cap:
                sample = sorted(self._stash)[:8]
                raise RuntimeError(
                    f"allreduce engine rank {self.rank}: stash exceeded "
                    f"{cap} unmatched frames (-allreduce_stash_cap) while "
                    f"waiting for peer {src} msg_id 0x{self._mid(tag):x} "
                    f"— a crashed peer or tag-protocol bug is flooding "
                    f"the endpoint; sample keys {sample}")
        return self._stash.pop(key)

    def _recv(self, src: int, tag: int, dtype) -> np.ndarray:
        blob, encoded = self._drain_until(src, tag)
        if encoded:
            decoded = decode_blob(np.asarray(blob.data))
            return decoded if decoded.dtype == np.dtype(dtype) \
                else np.asarray(decoded, dtype=dtype)
        return blob.as_array(dtype)

    def _exchange(self, peer: int, payload: np.ndarray,
                  tag: int) -> np.ndarray:
        """Blocking sendrecv with one peer (ref: mpi_net.h:269-287)."""
        self._send(peer, payload, tag)
        return self._recv(peer, tag, payload.dtype)

    # -- algorithm choice --
    def _probe_union_density(self, data: np.ndarray) -> float:
        """Cluster-agreed density signal for ``choose_algo``: a tiny
        Bruck allgather of each rank's nnz, reduced to
        min(1, sum nnz / n) — the union upper bound on the reduced
        result's fill-in (cancellation only shrinks it). Every rank
        computes the identical value, so the dense-vs-sparse pick can
        never split the cluster the way a LOCAL density test would
        (rank 0 at 5.1%% picking dense while rank 1 at 4.9%% picks
        sparse deadlocks the protocol)."""
        nnz = int(np.count_nonzero(data))
        parts = self._bruck_allgather(np.array([nnz], np.int64),
                                      base=_PROBE_BASE)
        total = sum(int(p[0]) for p in parts)
        return min(1.0, total / max(data.size, 1))

    def _should_probe(self, data: np.ndarray, reducer: Callable) -> bool:
        # Rank-identical by the collective contract (same payload
        # shape/dtype, same reducer, same flags everywhere): every rank
        # either joins the probe round or skips it.
        return (str(get_flag("allreduce_algo")) == "auto"
                and reducer is np.add
                and data.dtype == np.float32
                and data.nbytes >= _SMALL_BYTES
                and data.size >= self.size)

    # -- public API (ref: allreduce_engine.h:96-118) --
    def allreduce(self, data: np.ndarray,
                  reducer: Callable = np.add) -> np.ndarray:
        data = np.asarray(data)
        if self.size == 1:
            return data.copy()
        self._next_gen()
        density = self._probe_union_density(data) \
            if self._should_probe(data, reducer) else None
        algo = choose_algo(data.nbytes, data.size, self.size,
                           density=density,
                           reducer_is_add=reducer is np.add,
                           is_f32=data.dtype == np.float32)
        self.last_algo = algo
        if algo == "bruck":
            # Small path: allgather everyone's buffer, reduce locally
            # (ref: allreduce_engine.cpp:34-43).
            stacked = self._bruck_allgather(data)
            self.last_reduce_state_bytes = self.size * data.nbytes
            out = stacked[0]
            for part in stacked[1:]:
                out = reducer(out, part)
            return out
        if algo == "sparse":
            return self._sparse_allreduce(data, density)
        if algo == "ring":
            self.last_reduce_state_bytes = data.nbytes
            return self._ring_allreduce(data, reducer)
        self.last_reduce_state_bytes = data.nbytes
        return self._reduce_scatter_allgather(data, reducer)

    def allgather(self, data: np.ndarray) -> list:
        self._next_gen()
        return self._bruck_allgather(data)

    def _bruck_allgather(self, data: np.ndarray,
                         base: int = _BRUCK_BASE) -> list:
        """Bruck doubling allgather: after round k every rank holds 2^(k+1)
        blocks; blocks are sent to rank-2^k and received from rank+2^k
        (ref: allreduce_engine.cpp:90-117, allreduce_topo.cpp:20-37)."""
        n = self.size
        blocks = [np.asarray(data)]
        tag = base
        distance = 1
        while distance < n:
            dst = (self.rank - distance) % n
            src = (self.rank + distance) % n
            count = min(distance, n - distance)
            payload = np.concatenate(
                [b.reshape(-1) for b in blocks[:count]])
            self._send(dst, payload, tag)
            incoming = self._recv(src, tag,
                                  blocks[0].dtype).reshape(count, -1)
            for i in range(count):
                blocks.append(incoming[i].reshape(blocks[0].shape))
            distance *= 2
            tag += 1
        # blocks[j] is the buffer of rank (self.rank + j) % n; rotate to
        # rank order.
        ordered = [None] * n
        for j, block in enumerate(blocks[:n]):
            ordered[(self.rank + j) % n] = block
        return ordered

    # -- chunked pipelined ring --------------------------------------
    def _ring_allreduce(self, data: np.ndarray,
                        reducer: Callable) -> np.ndarray:
        """Ring reduce-scatter + ring allgather over chunks, with a
        sliding window of in-flight chunks per step. Any rank count.

        Reduce-scatter step s: send segment (rank-s) of every chunk to
        the right neighbor, receive segment (rank-s-1) from the left and
        fold it in; after n-1 steps this rank owns the fully reduced
        segment (rank+1). Allgather step s: forward segment (rank+1-s)
        right, receive (rank-s) from the left. Sends ride
        ``send_async`` writer threads, so while this rank blocks on
        chunk c's inbound frame, chunks c+1..c+window are already on
        the wire and the previous chunk's reduce ran during their
        transfer — wire time and reduce time overlap instead of
        alternating."""
        n, r = self.size, self.rank
        right, left = (r + 1) % n, (r - 1) % n
        shape = np.asarray(data).shape
        flat = np.asarray(data).reshape(-1).copy()
        N = flat.size
        chunk_elems = max(1, (int(get_flag("allreduce_chunk_kb")) * 1024)
                          // max(flat.itemsize, 1))
        nchunks = max(1, -(-N // chunk_elems))
        # Tag-space guard: (n-1)*nchunks must fit each phase's band.
        nchunks = min(nchunks, max(1, _RING_TAG_SPAN // max(n - 1, 1)))
        cb = np.linspace(0, N, nchunks + 1).astype(np.int64)
        segs = [np.linspace(cb[c], cb[c + 1], n + 1).astype(np.int64)
                for c in range(nchunks)]
        window = max(1, int(get_flag("allreduce_window")))
        # Lossy only for float32 SUMS: the error-feedback identity
        # (residual folded into the next payload cancels over
        # accumulation) only holds for additive reduction — adding a
        # carried residual before a max/min would corrupt the result.
        lossy = bool(get_flag("allreduce_lossy")) \
            and flat.dtype == np.float32 and reducer is np.add
        ef_rs = self._ef_buffer("rs", N) if lossy else None
        ef_ag = self._ef_buffer("ag", N) if lossy else None

        def bounds(c: int, seg: int) -> Tuple[int, int]:
            return int(segs[c][seg]), int(segs[c][seg + 1])

        # Phase 1: reduce-scatter.
        for step in range(n - 1):
            send_id = (r - step) % n
            recv_id = (r - step - 1) % n

            def rs_recv(c: int, step: int = step,
                        recv_id: int = recv_id) -> None:
                tag = _RING_RS_BASE + step * nchunks + c
                lo, hi = bounds(c, recv_id)
                incoming = self._recv(left, tag, flat.dtype)
                flat[lo:hi] = reducer(flat[lo:hi], incoming)

            pending = collections.deque()
            for c in range(nchunks):
                tag = _RING_RS_BASE + step * nchunks + c
                lo, hi = bounds(c, send_id)
                if lossy:
                    self._send_lossy(right, flat, lo, hi, tag, ef_rs)
                else:
                    self._send(right, flat[lo:hi], tag)
                pending.append(c)
                if len(pending) >= window:
                    rs_recv(pending.popleft())
            while pending:
                rs_recv(pending.popleft())

        # Phase 2: allgather with verbatim frame forwarding — each
        # reduced segment is encoded once at its owner; hops relay the
        # received blob untouched (no per-hop re-quantization), and the
        # owner adopts its own decoded frame, so every rank lands on
        # the same bytes even in lossy mode.
        carry: list = [None] * nchunks
        for step in range(n - 1):
            send_id = (r + 1 - step) % n
            recv_id = (r - step) % n

            def ag_recv(c: int, step: int = step,
                        recv_id: int = recv_id) -> None:
                tag = _RING_AG_BASE + step * nchunks + c
                blob, encoded = self._drain_until(left, tag)
                lo, hi = bounds(c, recv_id)
                if encoded:
                    flat[lo:hi] = decode_blob(np.asarray(blob.data))
                else:
                    flat[lo:hi] = blob.as_array(flat.dtype)
                carry[c] = (blob, encoded)

            pending = collections.deque()
            for c in range(nchunks):
                tag = _RING_AG_BASE + step * nchunks + c
                if step == 0:
                    lo, hi = bounds(c, send_id)
                    if lossy:
                        flat[lo:hi] = self._send_lossy(
                            right, flat, lo, hi, tag, ef_ag)
                    else:
                        self._send(right, flat[lo:hi], tag)
                else:
                    blob, encoded = carry[c]
                    self._post(right, blob, tag, encoded)
                pending.append(c)
                if len(pending) >= window:
                    ag_recv(pending.popleft())
            while pending:
                ag_recv(pending.popleft())
        # Queued async frames are zero-copy VIEWS of ``flat`` now
        # (scatter-gather framing): drain them before handing the
        # buffer to the caller, who is free to mutate the result. The
        # old path paid a serialize-time copy per frame instead; the
        # flush costs one wait for writes already in flight.
        self._net.flush_sends()
        return flat.reshape(shape)

    # -- sparse-stream tier (SparCML-style index+value collectives) ----
    def sharded_average(self, data: np.ndarray) -> np.ndarray:
        """Cross-rank MEAN with sharded reduce state (arxiv
        2004.13336's cross-replica sharding of the update step): direct
        sparse reduce-scatter — each rank accumulates only the segment
        it owns — then the divide applied SHARD-LOCALLY, then a
        single-encode allgather that re-assembles the full averaged
        buffer straight into the output. No rank ever holds more
        reduce-state than one segment (~1/world of the buffer, reported
        via ``last_reduce_state_bytes``), where the dense paths copy
        and accumulate the whole flat buffer; see docs/ALLREDUCE.md
        for the memory math. float32 only — this is the model-average
        parameter path, and the sparse merge is an f32 sum.

        Bit-identity: the segment fold order matches the UNCHUNKED
        dense ring's, and the divide is the same elementwise op the
        dense ``allreduce(x) / world`` path runs, so a lossless sharded
        average equals ring-then-divide bit for bit (one chunk)."""
        data = np.asarray(data)
        if data.dtype != np.float32:
            raise TypeError(
                "sharded_average is float32-only (model-average "
                f"parameters); got {data.dtype}")
        if self.size == 1:
            return data.copy()
        self._next_gen()
        self.last_algo = "sharded"
        if data.nbytes < _SMALL_BYTES or data.size < self.size:
            # Sharding a sub-4KB buffer buys nothing: small path.
            stacked = self._bruck_allgather(data)
            self.last_reduce_state_bytes = self.size * data.nbytes
            out = stacked[0].copy()
            for part in stacked[1:]:
                out += part
            out /= self.size
            return out
        samples("SPARSE_FILL[input]").add(density_of(data))
        return self._sparse_collective(data, average=True)

    def _sparse_allreduce(self, data: np.ndarray,
                          density: Optional[float]) -> np.ndarray:
        """Sum-allreduce over sparse index+value streams (same two
        phases as ``sharded_average`` minus the divide)."""
        if density is not None:
            samples("SPARSE_FILL[input]").add(density)
        return self._sparse_collective(np.asarray(data), average=False)

    def _sparse_collective(self, data: np.ndarray,
                           average: bool) -> np.ndarray:
        """The sparse-tier driver both public forms share: direct
        reduce-scatter, optional shard-local divide, single-encode
        allgather into a fresh output buffer."""
        shape = data.shape
        flat = np.ascontiguousarray(data).reshape(-1)
        bounds = np.linspace(0, flat.size,
                             self.size + 1).astype(np.int64)
        lossy = bool(get_flag("allreduce_lossy"))
        acc = self._sparse_reduce_scatter(flat, bounds, lossy)
        self.last_reduce_state_bytes = acc.nbytes
        if average:
            acc /= self.size  # the shard-local average
        out = np.empty(flat.size, np.float32)
        self._sparse_allgather(out, bounds, acc, lossy)
        return out.reshape(shape)

    def _post_segment(self, dst: int, payload: np.ndarray,
                      tag: int) -> None:
        """Sparse-tier lossless contribution send: codec-framed
        whenever the sparse tier wins — even in-process, because the
        index+value stream IS the representation the owner's O(nnz)
        merge consumes — raw otherwise (``_send`` handles the
        in-process snapshot copy)."""
        payload = np.ascontiguousarray(payload)
        if payload.nbytes >= _CODEC_MIN_BYTES and worth_encoding(payload):
            parts, _ = encode_blob_views(payload)
            self._post(dst, Blob.from_parts(parts), tag, True)
        else:
            self._send(dst, payload, tag)

    def _merge_stream(self, acc: np.ndarray, blob: Blob,
                      encoded: bool) -> None:
        """Fold one inbound contribution into the owner's segment
        accumulator: sparse frames through the index stream
        (``acc[idx] += vals`` — codec indices are strictly increasing,
        so the fancy-index add never collides with itself), raw / dense
        tiers through a dense add. Elementwise this performs the same
        additions the dense ring's fold would, so the lossless result
        is bit-identical."""
        if encoded:
            idx, vals = decode_blob_sparse(np.asarray(blob.data))
            if idx is None:
                acc += vals.astype(np.float32, copy=False)
            else:
                acc[idx] += vals
        else:
            acc += blob.as_array(np.float32)

    def _sparse_reduce_scatter(self, flat: np.ndarray,
                               bounds: np.ndarray,
                               lossy: bool) -> np.ndarray:
        """Phase 1 of the sparse tier: DIRECT scatter. Each rank sends
        its own contribution for segment s straight to s's owner as a
        codec sparse frame — partial sums never ride the wire, so the
        hop-by-hop fill-in growth a sparse RING would pay (the union
        densifies every hop) costs bytes only once, in the allgather
        of the fully-reduced segments. The owner then folds the n-1
        inbound index streams plus its own slice IN RANK ORDER,
        starting from the segment index — the same pairwise sums as
        the unchunked dense ring's fold (operand order differs only
        where IEEE-754 addition commutes), which is what makes the
        lossless sparse path bit-identical to the dense ring. Rank r
        owns segment (r+1) %% n, the dense ring's ownership map.
        Fill-in after every folded stream lands on the
        ``SPARSE_FILL[reduce]`` samples reservoir."""
        n, r = self.size, self.rank
        ef = self._ef_buffer("sprs", flat.size) if lossy else None
        for off in range(1, n):
            o = (r + off) % n  # stagger: rank 0 is not everyone's
            s = (o + 1) % n    # first target
            lo, hi = int(bounds[s]), int(bounds[s + 1])
            tag = _SPARSE_RS_BASE + s
            if lossy:
                self._send_lossy(o, flat, lo, hi, tag, ef)
            else:
                self._post_segment(o, flat[lo:hi], tag)
        own = (r + 1) % n
        lo, hi = int(bounds[own]), int(bounds[own + 1])
        seglen = hi - lo
        acc = np.zeros(seglen, np.float32)
        fill = samples("SPARSE_FILL[reduce]")
        for k in range(n):
            src = (own + k) % n
            if src == r:  # own slice folds last (r == own - 1 mod n)
                acc += flat[lo:hi]
            else:
                blob, encoded = self._drain_until(
                    src, _SPARSE_RS_BASE + own)
                self._merge_stream(acc, blob, encoded)
            fill.add(np.count_nonzero(acc) / max(seglen, 1))
        return acc

    def _sparse_allgather(self, out: np.ndarray, bounds: np.ndarray,
                          acc: np.ndarray, lossy: bool) -> None:
        """Phase 2 of the sparse tier: ring allgather of the reduced
        (or reduced-and-averaged) segments with verbatim frame
        forwarding. Each segment is encoded ONCE at its owner — as a
        sparse stream while its measured fill-in stays below the codec
        break-even, as a RAW frame past it (the automatic per-segment
        dense switchover) — and relayed untouched, so every rank lands
        on identical bytes, lossy tiers included."""
        n, r = self.size, self.rank
        right, left = (r + 1) % n, (r - 1) % n
        own = (r + 1) % n
        lo, hi = int(bounds[own]), int(bounds[own + 1])
        if lossy:
            ef = self._ef_buffer("spag", out.size)
            vals = acc + ef[lo:hi]
            if vals.nbytes >= _CODEC_MIN_BYTES:
                parts, residual = encode_blob_views(vals, lossy=True)
                ef[lo:hi] = residual if residual is not None else 0.0
            else:  # sub-threshold: exact, pending residual consumed
                parts, _ = encode_blob_views(vals)
                ef[lo:hi] = 0.0
            # decoded == vals - residual; every rank lands on this.
            own_vals = vals - ef[lo:hi]
            carry, encoded = Blob.from_parts(parts), True
        elif acc.nbytes >= _CODEC_MIN_BYTES and worth_encoding(acc):
            parts, _ = encode_blob_views(acc)
            own_vals = acc
            carry, encoded = Blob.from_parts(parts), True
        else:
            own_vals = acc
            carry, encoded = Blob(acc), False
        out[lo:hi] = own_vals
        for step in range(n - 1):
            tag = _SPARSE_AG_BASE + step
            self._post(right, carry, tag, encoded)
            blob, enc = self._drain_until(left, tag)
            seg = (r - step) % n
            slo, shi = int(bounds[seg]), int(bounds[seg + 1])
            seg_out = out[slo:shi]
            if enc:
                # Scatter the index stream straight into the output
                # slice — decode_blob would allocate a full segment
                # temp just to copy it here.
                idx, vals = decode_blob_sparse(np.asarray(blob.data))
                if idx is None:
                    seg_out[:] = vals
                else:
                    seg_out[:] = 0.0
                    seg_out[idx] = vals
            else:
                seg_out[:] = blob.as_array(np.float32)
            carry, encoded = blob, enc

    def _ef_buffer(self, phase: str, n: int) -> np.ndarray:
        buf = self._ef.get((phase, n))
        if buf is None:
            # One buffer per phase: a residual only means something for
            # the SAME flat layout, so a size change (new model shape)
            # both invalidates and evicts the old one — the engine is
            # cached for the process lifetime and must not pin two
            # float32 buffers per distinct size ever seen.
            for key in [k for k in self._ef if k[0] == phase]:
                del self._ef[key]
            buf = self._ef[(phase, n)] = np.zeros(n, np.float32)
        return buf

    # -- monolithic recursive halving ---------------------------------
    def _reduce_scatter_allgather(self, data: np.ndarray,
                                  reducer: Callable) -> np.ndarray:
        """Large path: recursive-halving reduce-scatter then allgather of
        the reduced segments (ref: allreduce_engine.cpp:44-54,120-172)."""
        n = self.size
        flat = np.asarray(data).reshape(-1).copy()
        # Fold surplus ranks onto the largest power-of-two group (the
        # reference pairs each surplus rank with a group leader,
        # ref: allreduce_topo.cpp:58-168).
        pow2 = 1
        while pow2 * 2 <= n:
            pow2 *= 2
        surplus = n - pow2
        tag = _RH_BASE
        if self.rank >= pow2:
            # Surplus rank: hand the whole buffer to its leader, then wait
            # for the final result.
            leader = self.rank - pow2
            self._send(leader, flat, tag)
            result = self._recv(leader, _RH_RESULT, flat.dtype)
            # Copy: in-process the received blob is (a view of) the
            # leader's result buffer — the caller owns its return value.
            return result.reshape(np.asarray(data).shape).copy()
        if self.rank < surplus:
            incoming = self._recv(self.rank + pow2, tag, flat.dtype)
            flat = reducer(flat, incoming)

        # Recursive halving among the pow2 group: segment boundaries are
        # even splits of the flat buffer.
        bounds = np.linspace(0, flat.size, pow2 + 1).astype(np.int64)
        lo, hi = 0, pow2
        step_tag = tag + 1
        while hi - lo > 1:
            mid = (lo + hi) // 2
            half = (hi - lo) // 2
            in_low = self.rank < mid
            peer = self.rank + half if in_low else self.rank - half
            keep = (lo, mid) if in_low else (mid, hi)
            give = (mid, hi) if in_low else (lo, mid)
            give_seg = flat[bounds[give[0]]:bounds[give[1]]]
            recv_seg = self._exchange(peer, give_seg, step_tag)
            seg = slice(bounds[keep[0]], bounds[keep[1]])
            flat[seg] = reducer(flat[seg], recv_seg)
            lo, hi = keep
            step_tag += 1

        # Allgather the reduced segments back (ring of exchanges via the
        # Bruck machinery on the segment level).
        my_seg = flat[bounds[self.rank]:bounds[self.rank + 1]]
        gathered = self._gather_segments(my_seg, bounds, flat.dtype,
                                         step_tag)
        flat = np.concatenate(gathered)
        if self.rank < surplus:
            self._send(self.rank + pow2, flat, _RH_RESULT)
        # The queued exchange/result frames view ``flat`` and the round
        # segments directly (scatter-gather framing): drain before the
        # caller may mutate the returned buffer.
        self._net.flush_sends()
        return flat.reshape(np.asarray(data).shape)

    def _gather_segments(self, my_seg, bounds, dtype, tag) -> list:
        """Bruck doubling allgather of the (unequal) reduced segments.
        Ownership after round r is deterministic — rank holds segments
        {rank+j mod p : j < 2^r} — so no ids ride the wire."""
        pow2 = len(bounds) - 1
        have = {self.rank: np.asarray(my_seg)}
        distance = 1
        while distance < pow2:
            dst = (self.rank - distance) % pow2
            src = (self.rank + distance) % pow2
            count = min(distance, pow2 - distance)
            send_ids = [(self.rank + j) % pow2 for j in range(count)]
            self._send(dst, np.concatenate([have[i] for i in send_ids]), tag)
            raw = self._recv(src, tag, dtype)
            offset = 0
            for j in range(count):
                seg_id = (src + j) % pow2
                seg_len = int(bounds[seg_id + 1] - bounds[seg_id])
                have[seg_id] = raw[offset:offset + seg_len]
                offset += seg_len
            distance *= 2
            tag += 1
        return [have[i] for i in range(pow2)]

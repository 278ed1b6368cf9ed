"""Shared byte buffer with typed views.

TPU-native equivalent of the reference's ``Blob``
(ref: include/multiverso/blob.h:13-53, src/blob.cpp:8-46). The reference is
a ref-counted byte chunk whose copies share memory and whose ``As<T>(i)``
reinterpret-casts. In Python the natural carrier is a numpy array: numpy
views already give zero-copy sharing with refcounting (the Allocator/refcount
machinery of the reference collapses into CPython's GC), and ``as_array``
gives the reinterpret-cast view. A Blob can also wrap a ``jax.Array``
lazily — device blobs defer transfer until host bytes are demanded, which is
what lets table replies stay on-device end to end.

Two zero-copy carrier forms beyond the plain host array
(docs/MEMORY.md):

- **parted** (``Blob.from_parts``): the payload is the concatenation of
  several buffers that are never joined on the send side — the
  scatter-gather framer (``tcp.serialize_views``) reads each part as its
  own vectored-write view, so a codec frame's ``(header, payload)`` pair
  crosses the wire without the ``head + payload.tobytes()`` concat copy.
  Materialized (one concatenate) only if something demands the flat
  payload locally.
- **pool-backed** (``Blob.from_lease``): a READ-ONLY view into a leased
  receive-frame buffer (``util/buffer_pool.py``). The lease rides the
  Blob; when the last Blob cut from a frame dies, the frame returns to
  the pool. Pool views must never be written — a recycled buffer would
  be scribbled — so mutation raises and the rare consumer that needs a
  writable payload calls ``materialize()`` first (copy-on-write).
"""

from __future__ import annotations

import functools
from typing import Any, Iterator, List, Tuple

import numpy as np

#: A device payload of rows that its consumer takes by row ranges
#: (``Blob.host_row_pieces``) crosses the host boundary in equal pieces
#: of about ``D2H_PIECE_BYTES``, at least two and at most
#: ``D2H_MOST_PIECES``; one under ``D2H_WHOLE_UNDER_BYTES`` stays whole.
#: Chosen on a v5e from ``mperf16m.rows``' 20 MB reply (PR 48, PERF.md
#: section 6; a Get at the caller, ms, parent 25.1 to 25.6): 2 pieces
#: 27.5, 4 pieces 22.5 to 23.0, **8 pieces 20.8 to 21.2**, 16 pieces 19.7,
#: 32 pieces 25.9. A piece costs the thread ~0.5 ms to cut and ask for
#: (the cut's dispatch with its start, ``copy_to_host_async``), hidden
#: only while the device still runs what the reply waits for (9.4 ms in
#: that cell: 16 pieces just fit, 32 do not), so 8: the same cut on an
#: idle device still beats the whole array (9.1 ms against 11.5 from
#: dispatch to placed; 16 pieces 13.0). 8 pieces of an 80 MB reply read
#: 44 ms against the whole's 65 and 16 pieces' 44.5: the count is capped,
#: not the size. Under 4 MiB two pieces save less than they cost to cut.
D2H_PIECE_BYTES = 5 << 19  # 2.5 MiB
D2H_MOST_PIECES = 8
D2H_WHOLE_UNDER_BYTES = 4 << 20


@functools.lru_cache(maxsize=None)
def _row_piece():
    """The program that cuts ``rows`` rows from ``first`` out of a device
    ``[n, c]`` array AND flattens them. The start is an argument, so one
    program serves every piece of a reply shape. Built at first use:
    ``core`` stays free of jax until a jax array is here to be cut."""
    import jax

    @functools.partial(jax.jit, static_argnums=2)
    @jax.named_scope("mv.blob.row_piece")
    def row_piece(whole, first, rows):
        return jax.lax.dynamic_slice_in_dim(whole, first, rows).reshape(-1)
    return row_piece


def is_device_array(x: Any) -> bool:
    """True for jax.Array-like payloads (duck-typed so core stays
    jax-import-free)."""
    return not isinstance(x, np.ndarray) and hasattr(x, "addressable_shards")


class Blob:
    # Slot order matters for the pool: on deallocation CPython clears
    # slots in definition order, so the payload view (_data) drops its
    # buffer export before the lease's __del__ probes the frame for
    # reuse — the common single-owner case re-pools immediately instead
    # of parking on the pending list.
    __slots__ = ("_data", "_parts", "_lease")

    def __init__(self, data: Any = None, size: int = None):
        """Wrap existing data (zero-copy for numpy/bytes/memoryview
        inputs) or allocate.

        ``Blob(size=n)`` allocates ``n`` bytes; ``Blob(array)`` wraps.
        """
        self._parts = None
        self._lease = None
        if data is None:
            if size is None:
                raise ValueError("Blob needs data or size")
            self._data = np.zeros(size, dtype=np.uint8)
        elif isinstance(data, Blob):
            # Shallow share, like the reference copy-ctor: payload,
            # pending parts and frame lease all ride along.
            self._data = data._data
            self._parts = data._parts
            self._lease = data._lease
        elif isinstance(data, np.ndarray):
            # Zero-copy only holds for contiguous input; a non-contiguous
            # array is copied here so as_array views stay writable+attached.
            self._data = np.ascontiguousarray(data)
        elif isinstance(data, bytes):
            # Zero-copy wrap: bytes is immutable, so the view is
            # read-only and can alias the caller's object safely
            # (the old frombuffer(bytes(..)).copy() paid two copies).
            self._data = np.frombuffer(data, dtype=np.uint8)
        elif isinstance(data, memoryview):
            # Zero-copy wrap; writability (and the no-alias discipline)
            # is the caller's — the wire path hands out read-only
            # pool views through from_lease, never through here.
            self._data = np.frombuffer(data, dtype=np.uint8)
        elif isinstance(data, bytearray):
            # ONE copy (down from two): the caller may keep mutating
            # its bytearray, so aliasing it would let later writes
            # bleed into the blob.
            self._data = np.frombuffer(data, dtype=np.uint8).copy()
        else:
            # jax.Array and anything else exposing __array__ kept as-is;
            # converted to host bytes only on demand.
            self._data = data

    @classmethod
    def from_parts(cls, parts: List[Any]) -> "Blob":
        """Scatter-gather blob: the payload is the concatenation of
        ``parts`` (bytes / contiguous arrays), kept separate so
        ``wire_views`` can hand each to a vectored write with no join
        copy. Anything that needs the flat payload (``data``,
        ``as_array``) materializes it lazily — once."""
        blob = cls.__new__(cls)
        blob._data = None
        blob._lease = None
        norm = []
        for part in parts:
            if isinstance(part, np.ndarray):
                norm.append(np.ascontiguousarray(part)
                            .view(np.uint8).reshape(-1))
            else:
                norm.append(np.frombuffer(part, dtype=np.uint8))
        blob._parts = norm
        return blob

    @classmethod
    def from_lease(cls, view: np.ndarray, lease: Any) -> "Blob":
        """Pool-backed blob: ``view`` is a (read-only) uint8 view into a
        leased receive-frame buffer; the blob keeps ``lease`` alive so
        the frame cannot be recycled under it (util/buffer_pool.py)."""
        blob = cls.__new__(cls)
        blob._data = view
        blob._parts = None
        blob._lease = lease
        return blob

    @property
    def data(self) -> Any:
        if self._parts is not None:
            self._materialize_parts()
        return self._data

    @property
    def pool_backed(self) -> bool:
        """True while the payload views a pooled receive frame (and is
        therefore read-only; see ``materialize``)."""
        return self._lease is not None

    def _materialize_parts(self) -> None:
        parts = self._parts
        self._data = parts[0] if len(parts) == 1 \
            else np.concatenate(parts)
        self._parts = None

    @property
    def on_device(self) -> bool:
        """True when the payload is a device array (jax.Array) that has not
        been materialized to host bytes. Device blobs flow through the PS
        stack with zero host copies."""
        return self._parts is None and is_device_array(self._data)

    def typed(self, dtype=np.float32) -> Any:
        """Typed payload without forcing a host transfer: the device array
        itself when on device, else the host view."""
        return self._data if self.on_device else self.as_array(dtype)

    def _host(self) -> np.ndarray:
        if self._parts is not None:
            self._materialize_parts()
        if not isinstance(self._data, np.ndarray):
            if is_device_array(self._data):
                # THE host boundary of a device reply: the wait for the
                # program that produces the array, then the copy device
                # to host into a fresh buffer; BLOB_D2H is both. The
                # copy is queued behind the program first, as np.asarray
                # alone queues it: waiting before asking for it would
                # put a host wake-up between the two.
                from ..util.dashboard import count, monitor
                with monitor("BLOB_D2H"):
                    self._data.copy_to_host_async()
                    with monitor("BLOB_D2H_READY"):
                        self._data.block_until_ready()
                    with monitor("BLOB_D2H_COPY"):
                        self._data = np.asarray(self._data)
                count("BLOB_D2H_BYTES", self._data.nbytes)
            else:
                self._data = np.asarray(self._data)
        return self._data

    def pieced_rows(self, dtype, n_rows: int, n_col: int) -> bool:
        """True where ``host_row_pieces`` cuts: the payload is on the
        device, already ``[n_rows, n_col]`` of ``dtype``, and large
        enough for a piece to be worth its dispatch."""
        return (self.on_device
                and tuple(self._data.shape) == (n_rows, n_col)
                and np.dtype(self._data.dtype) == np.dtype(dtype)
                and self.size >= D2H_WHOLE_UNDER_BYTES)

    def host_row_pieces(self, dtype, n_rows: int, n_col: int
                        ) -> Iterator[Tuple[int, np.ndarray]]:
        """The payload's rows on the host as ``(first_row, rows)`` in
        row order, for a consumer that reads every row once (a Get's
        placement). A large device payload (``pieced_rows``) crosses the
        host boundary in equal row ranges: each is cut AND flattened on
        the device (``_row_piece``: a flat array has one layout, so it
        arrives row-major and its rows are contiguous, whatever layout
        the chip gave the ``[n, c]`` whole), every piece's copy is asked
        for before any is waited for, and the runtime copies piece k + 1
        while the consumer holds piece k. The last range starts early
        enough to be as long as the others (one program a reply shape);
        the rows it shares with the one before are skipped on the host.
        The blob lets go of the device array once it is cut and keeps
        the host pieces as its parts, so it reads as the same payload
        afterwards. Anything else is the one array ``as_rows`` gives.
        Read-only like every device reply's host array (docs/MEMORY.md).

        The monitors count ONE entry a payload as ``_host`` does:
        BLOB_D2H_READY is the cuts' dispatch and the wait for the first
        piece's program, BLOB_D2H_COPY the time in ``np.asarray`` of
        all pieces, BLOB_D2H both and nothing of what the consumer
        did in between."""
        if not self.pieced_rows(dtype, n_rows, n_col):
            yield 0, self.as_rows(dtype, n_rows, n_col)
            return
        from ..util.dashboard import count, laps, monitor
        nbytes = self.size
        rows = -(-n_rows // min(D2H_MOST_PIECES,
                                max(2, -(-nbytes // D2H_PIECE_BYTES))))
        # (a piece's first new row, the row its cut starts at)
        starts = [(first, min(first, n_rows - rows))
                  for first in range(0, n_rows, rows)]
        d2h, copied = laps("BLOB_D2H"), laps("BLOB_D2H_COPY")
        try:
            with d2h, monitor("BLOB_D2H_READY"):
                cut, whole = _row_piece(), self._data
                cuts = [cut(whole, np.int32(at), rows) for _, at in starts]
                for piece in cuts:
                    piece.copy_to_host_async()
                del whole
                self._data, self._parts = None, []
                cuts[0].block_until_ready()
            cuts.reverse()  # popped: a piece's device array goes with it
            for first, at in starts:
                with d2h, copied:
                    piece = np.asarray(cuts.pop())
                piece = piece[(first - at) * n_col:]
                self._parts.append(piece.view(np.uint8))
                yield first, piece.reshape(-1, n_col)
        finally:
            d2h.close()
            copied.close()
        count("BLOB_D2H_BYTES", nbytes)

    @property
    def size(self) -> int:
        """Size in bytes (the reference's ``size()``). Computed from
        shape/dtype for device payloads — materializing here would silently
        defeat the zero-copy device path — and summed over pending parts
        for scatter-gather blobs."""
        if self._parts is not None:
            return sum(p.nbytes for p in self._parts)
        if self.on_device:
            return int(np.prod(self._data.shape)) \
                * np.dtype(self._data.dtype).itemsize
        return self._host().nbytes

    def count(self, dtype=np.float32) -> int:
        """Element count under a typed view (the reference's ``size<T>()``)."""
        return self.size // np.dtype(dtype).itemsize

    def as_array(self, dtype=np.float32) -> np.ndarray:
        """Typed zero-copy view (the reference's ``As<T>``). Pool-backed
        payloads yield READ-ONLY views — ``materialize()`` first for a
        writable private copy (the copy-on-write contract,
        docs/MEMORY.md)."""
        arr = self._host()
        if arr.dtype == np.dtype(dtype) and arr.ndim == 1:
            return arr
        return arr.reshape(-1).view(dtype)

    def as_rows(self, dtype, n_rows: int, n_col: int) -> np.ndarray:
        """The payload as ``[n_rows, n_col]`` rows of ``dtype``. A host
        payload that already is that array (a device reply's rows after
        the host boundary) is handed back as it is, strides and all: a
        consumer that copies the rows once reads them in place, and a
        payload that is not C-contiguous is never copied just to be
        flattened. Anything else is ``as_array`` reshaped. Read-only
        wherever ``as_array`` is (docs/MEMORY.md)."""
        arr = self._host()
        if arr.shape == (n_rows, n_col) and arr.dtype == np.dtype(dtype):
            return arr
        return self.as_array(dtype).reshape(n_rows, n_col)

    def materialize(self) -> "Blob":
        """Copy-on-write escape hatch: replace a pool-backed (or
        otherwise read-only) payload with a private writable copy and
        drop the frame lease, so the buffer can recycle. The few wire
        consumers that mutate a received payload in place call this
        once; everything else reads through the zero-copy view."""
        arr = self._host()
        if self._lease is not None or not arr.flags.writeable:
            self._data = arr.copy()
        self._lease = None
        return self

    def wire_bytes(self) -> np.ndarray:
        """Flat uint8 view of the payload for wire serialization
        (materializes device arrays — this IS the host boundary). The
        single place the byte layout of an outgoing blob is defined:
        the TCP framer and the wire-codec filter both read through it,
        so a filtered and an unfiltered serialization path cannot
        disagree on what the raw bytes are."""
        arr = np.asarray(self.data)
        return np.ascontiguousarray(arr).view(np.uint8).reshape(-1)

    def wire_views(self) -> List[memoryview]:
        """The payload as buffer views for scatter-gather serialization
        (``tcp.serialize_views``): one view per pending part — never
        joined — or a single view of the flat payload. Zero-copy for
        host payloads; device arrays materialize exactly as in
        ``wire_bytes``."""
        if self._parts is not None:
            return [memoryview(p) for p in self._parts]
        return [memoryview(self.wire_bytes())]

    def __getitem__(self, i: int) -> int:
        return int(self._host().reshape(-1).view(np.uint8)[i])

    def copy(self) -> "Blob":
        """Deep copy (the reference's CopyFrom)."""
        return Blob(self._host().copy())

    def __len__(self) -> int:
        return self.size


def typed_blob(arr: np.ndarray) -> Blob:
    """Wrap a typed array as a Blob without byte-flattening."""
    return Blob(np.ascontiguousarray(arr))

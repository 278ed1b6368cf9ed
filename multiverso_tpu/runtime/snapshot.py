"""Periodic async server snapshots + manifest-consistent restore.

The paper's ``ServerTable Store/Load`` surface (ref:
include/multiverso/table_interface.h:68-75) only ever ran under the
manual ``save_checkpoint`` driver; this module turns it into a
fault-tolerance primitive (ROADMAP item 3):

- a **background snapshotter thread per server actor**
  (``-snapshot_interval_s`` > 0 and ``-snapshot_dir`` set) takes a
  consistent cut of every registered table: the CAPTURE runs under the
  server's table lock via ``ServerTable.snapshot_state()`` — a jitted
  device-side copy for device tables (the updater DONATES the live
  buffer away on the next add, so a bare reference would be deleted
  under the snapshotter) / a C-level dict copy for KV — and the
  expensive host transfer + serialize + write runs OFF the lock through
  the ``io/stream.py`` URI drivers, so ``Get``/``Add`` latency is
  barely affected by snapshotting;
- each round writes per-table files named by round sequence
  (``t{tid}.seq{n}.snap``), then an fsync'd atomically-renamed
  ``manifest.json`` recording ``{table, shard, version, file, bytes,
  crc32}`` per entry — a crash between writes leaves the previous
  manifest pointing at the previous round's (still present) files, so
  the newest manifest is ALWAYS internally consistent;
- a **restarted server** (``-rejoin=true``) loads the latest manifest at
  startup and restores each table — bytes verified against the recorded
  crc32/size — as the application re-registers it, then resumes serving;
  workers retry their failed requests against it (zoo/worker
  fault-containment paths) and their client caches invalidate on the
  shard's version regression (tables/client_cache.py generation guard).

See docs/FAULT_TOLERANCE.md for the full snapshot/rejoin story.
"""

from __future__ import annotations

import contextlib
import io
import json
import threading
import zlib
from typing import Dict, List, Optional, Tuple

from ..util import log
from ..util.configure import define_double, define_string, get_flag
from ..util.dashboard import monitor
from ..util.lock_witness import named_condition
from . import thread_roles

define_double("snapshot_interval_s", 0.0,
              "period of the per-server background snapshotter: every "
              "interval it takes a consistent cut of all registered "
              "server tables (capture under the table lock, serialize+"
              "write off it) into -snapshot_dir. 0 (default) disables "
              "periodic snapshots; snapshot_once() remains callable")
define_string("snapshot_dir", "",
              "URI prefix snapshots live under (file path or any "
              "io/stream.py scheme; per-rank subtree "
              "{dir}/rank{r}/...). Empty (default) disables the "
              "snapshot subsystem entirely")

MANIFEST_FORMAT = 1


def _rank_prefix(base: str, rank: int) -> str:
    return f"{base.rstrip('/')}/rank{rank}"


def _state_lock_of(table):
    """The lock that pairs a table's state with its version
    (tables/table_interface.py ``_state_lock``). Host-only tables' adds
    always run under it; device-backed tables' adds run under it
    whenever multi-device serialization is inactive (the single-device
    relaxation in ``Server._lock_for``) and under the device table lock
    otherwise — the snapshotter takes BOTH (table lock + every state
    lock), so the capture is atomic against adders in either mode."""
    return getattr(table, "_state_lock", contextlib.nullcontext())


class SnapshotError(RuntimeError):
    """A snapshot manifest or payload failed validation (torn write,
    mixed rounds, missing file): restoring it would silently serve
    corrupt parameters, so it fails loudly instead."""


class SnapshotManager:
    """Owns snapshotting + restore for ONE server actor's tables.

    Created by the Server actor when ``-snapshot_dir`` is set. Tables
    are handed in via ``track`` as they register; with ``-rejoin`` the
    latest manifest is loaded up front and each tracked table restores
    immediately (the restarted process re-creates tables through the
    same application code, in the same order, so ids line up)."""

    def __init__(self, zoo, table_lock) -> None:
        self._zoo = zoo
        self._table_lock = table_lock
        self._base = str(get_flag("snapshot_dir"))
        self._prefix = _rank_prefix(self._base, zoo.rank)
        self._interval = float(get_flag("snapshot_interval_s"))
        self._tables: List[Tuple[int, object]] = []
        self._seq = 0
        self.rounds_written = 0   # test observability
        self.tables_restored = 0
        self._stop_cond = named_condition(
            f"snapshot[r{zoo.rank}].stop")
        self._stopped = False  # guarded_by: _stop_cond
        self._thread: Optional[threading.Thread] = None
        self._restored_ids: set = set()
        #: Tables open to the snapshotter: a shard is tracked at
        #: REGISTRATION (inside the base constructor) but only safe to
        #: capture once the factory's table_ready hook fires.
        self._ready_ids: set = set()
        self._restore: Optional[dict] = None
        #: Payload files the loaded restore manifest still points at:
        #: _cleanup must never collect these while a restore is pending
        #: (the periodic rounds of a rejoining server would otherwise
        #: delete the very bytes a not-yet-recreated table needs).
        self._protected: set = set()
        self._idle_reason: Optional[str] = None
        if bool(get_flag("rejoin")):
            self._restore = self._load_manifest()
            if self._restore is None:
                log.error("rank %d: -rejoin set but no usable snapshot "
                          "manifest under %s — tables start from their "
                          "constructors (training will re-converge "
                          "from further away)", zoo.rank, self._prefix)
            else:
                self._seq = int(self._restore.get("seq", 0))
                self._protected = {e["file"] for e
                                   in self._restore["tables"].values()}

    # -- registration / restore --
    def track(self, table_id: int, table) -> None:
        """Called at REGISTRATION, which runs inside the table base
        constructor — the subclass's storage does not exist yet, so
        restore must wait for ``restore_if_pending`` (the table factory
        calls it once construction finishes)."""
        self._tables.append((table_id, table))

    def restore_if_pending(self, table) -> None:
        """Mark one fully-constructed table ready for snapshotting and
        — when a rejoin manifest is loaded — restore it (once)."""
        for table_id, tracked in self._tables:
            if tracked is table:
                break
        else:
            return
        if self._restore is not None and table_id not in self._restored_ids:
            self._restored_ids.add(table_id)
            self._restore_table(table_id, table)
            if not (set(self._restore["tables"])
                    - {str(t) for t in self._restored_ids}):
                # Every manifest table has restored: its payload files
                # no longer need _cleanup protection.
                self._protected = set()
        self._ready_ids.add(table_id)

    def _restore_table(self, table_id: int, table) -> None:
        entry = self._restore["tables"].get(str(table_id))
        if entry is None:
            # A table the manifest does not cover was (most plausibly)
            # created AFTER the snapshot round committed — at the cut's
            # point in time it had no state, so starting it fresh IS
            # the consistent restore. Loud, because its post-snapshot
            # updates are lost; creation-order drift (a genuinely
            # different table shape mapped onto a recorded id) still
            # fails hard at load time via the size/crc checks.
            log.error("rank %d: snapshot manifest seq %d has no entry "
                      "for table %d (created after the cut?) — it "
                      "starts fresh from its constructor",
                      self._zoo.rank, self._seq, table_id)
            return
        data = _read_uri(f"{self._prefix}/{entry['file']}")
        if data is None or len(data) != int(entry["bytes"]) \
                or zlib.crc32(data) != int(entry["crc32"]):
            raise SnapshotError(
                f"rank {self._zoo.rank}: snapshot payload "
                f"{entry['file']} for table {table_id} is torn "
                f"(got {0 if data is None else len(data)} bytes, "
                f"manifest says {entry['bytes']}) — refusing to "
                f"restore corrupt parameters")
        with self._table_lock, _state_lock_of(table):
            # The manifest sidecar carries the table's shard-map epoch
            # and elastic inventory (overlay/forwarding state) so a
            # rejoin restores into the RIGHT map (docs/SHARDING.md);
            # sidecar-less entries take the legacy load path.
            table.load_with_meta(io.BytesIO(data), entry.get("meta"))
            table.version = int(entry["version"])
        self.tables_restored += 1
        log.info("rank %d: restored table %d from %s (version %d)",
                 self._zoo.rank, table_id, entry["file"],
                 table.version)

    def _load_manifest(self) -> Optional[dict]:
        raw = _read_uri(f"{self._prefix}/manifest.json")
        if raw is None:
            return None
        try:
            manifest = json.loads(raw.decode("utf-8"))
        except ValueError as exc:
            raise SnapshotError(
                f"rank {self._zoo.rank}: snapshot manifest under "
                f"{self._prefix} is torn (unparseable JSON): {exc}"
            ) from exc
        if manifest.get("format") != MANIFEST_FORMAT \
                or "tables" not in manifest:
            raise SnapshotError(
                f"rank {self._zoo.rank}: snapshot manifest format "
                f"{manifest.get('format')!r} unsupported")
        # Internal consistency: every entry must come from the SAME
        # round — mixed seqs would splice two points in time.
        seqs = {int(e["seq"]) for e in manifest["tables"].values()}
        if len(seqs) > 1:
            raise SnapshotError(
                f"rank {self._zoo.rank}: snapshot manifest mixes "
                f"rounds {sorted(seqs)} — refusing a spliced restore")
        return manifest

    # -- periodic snapshotting --
    def start(self) -> None:
        if self._interval <= 0 or self._thread is not None:
            return
        self._thread = thread_roles.spawn(
            thread_roles.BACKGROUND, target=self._main,
            name=f"mv-snapshot-r{self._zoo.rank}")

    def stop(self) -> None:
        with self._stop_cond:
            self._stopped = True
            self._stop_cond.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=30)
            self._thread = None

    def _rounds_blocked(self) -> Optional[str]:
        """Why the periodic thread must NOT take a round right now, or
        None when it may. Rounds hold off while the application is
        still (re)building the table set: a round taken then would
        commit a manifest MISSING the not-yet-ready tables — and on a
        rejoining rank, empty early rounds would overwrite the good
        manifest and then garbage-collect the payloads the pending
        restores still need (observed: a restarted server whose first
        table takes > 2 intervals to re-create loses its restore).
        Reads actor-thread-written sets without a lock: GIL-atomic, and
        staleness only delays a round."""
        if self._restore is not None:
            pending = (set(self._restore["tables"])
                       - {str(t) for t in self._restored_ids})
            if pending:
                return (f"waiting for {len(pending)} manifest table(s) "
                        f"still to be re-created and restored")
        if not self._ready_ids:
            return "no table is ready to capture yet"
        if any(tid not in self._ready_ids for tid, _ in self._tables):
            return "a registered table is still under construction"
        return None

    def _main(self) -> None:
        while True:
            with self._stop_cond:
                if self._stopped:
                    return
                self._stop_cond.wait(timeout=self._interval)
                if self._stopped:
                    return
            blocked = self._rounds_blocked()
            if blocked is not None:
                if blocked != self._idle_reason:
                    self._idle_reason = blocked
                    log.info("rank %d: snapshotter idle: %s",
                             self._zoo.rank, blocked)
                continue
            self._idle_reason = None
            try:
                self.snapshot_once()
            except Exception:  # noqa: BLE001 - one failed round (disk
                # full, teardown race) must not kill the snapshotter:
                # the next round retries and the previous manifest
                # stays valid.
                log.error("rank %d: snapshot round failed",
                          self._zoo.rank)
                import traceback
                traceback.print_exc()

    def snapshot_once(self) -> int:
        """Take one consistent cut of every tracked table and persist
        it. Returns the round's sequence number. Callable from tests/
        drivers even with the periodic thread disabled."""
        with monitor("SNAPSHOT_CAPTURE"):
            # Capture phase: under the server's table lock PLUS every
            # host-only table's per-instance state lock (their adds
            # bypass the device lock — without the state lock a KV
            # (state, version) pair could tear), so no add can
            # interleave a table's state and its version stamp, and the
            # cut is a single point in time ACROSS tables. Lock order
            # is table lock -> state locks in ascending table id;
            # adders only ever hold ONE of these at a time, so no
            # cycle. Cheap by contract (a device-side jitted copy /
            # C-level dict copy — no host transfer or serialization
            # under the locks).
            tracked = sorted(((tid, table) for tid, table in self._tables
                              if tid in self._ready_ids),
                             key=lambda entry: entry[0])
            with self._table_lock, contextlib.ExitStack() as stack:
                for tid, table in tracked:
                    stack.enter_context(_state_lock_of(table))
                captures = [(tid, table, table.snapshot_state(),
                             int(table.version), table.snapshot_meta())
                            for tid, table in tracked]
        seq = self._seq + 1
        entries: Dict[str, dict] = {}
        with monitor("SNAPSHOT_WRITE"):
            for tid, table, state, version, meta in captures:
                buf = io.BytesIO()
                table.write_snapshot(state, buf)
                data = buf.getvalue()
                fname = f"t{tid}.seq{seq}.snap"
                # fsync'd: the manifest below commits the round, so
                # every payload it names must be durable BEFORE the
                # manifest rename — without this, a power loss could
                # leave a durable manifest pointing at payloads whose
                # blocks never hit disk (and the previous round's
                # files already collected).
                _write_uri_atomic(f"{self._prefix}/{fname}", data,
                                  fsync=True)
                entries[str(tid)] = {
                    "table": tid, "shard": self._zoo.server_id,
                    "seq": seq, "version": version, "file": fname,
                    "bytes": len(data), "crc32": zlib.crc32(data)}
                if meta:
                    # Elastic sidecar: shard-map epoch + overlay/
                    # forwarding inventory (tables define it;
                    # docs/SHARDING.md).
                    entries[str(tid)]["meta"] = meta
            manifest = {"format": MANIFEST_FORMAT,
                        "rank": self._zoo.rank,
                        "server_id": self._zoo.server_id,
                        "seq": seq, "tables": entries}
            # fsync'd atomic rename: after this line the newest
            # manifest names only files that are fully on disk.
            _write_uri_atomic(f"{self._prefix}/manifest.json",
                              json.dumps(manifest, indent=1).encode(),
                              fsync=True)
        self._seq = seq
        self.rounds_written += 1
        self._cleanup(keep_from=seq - 1)
        return seq

    def _cleanup(self, keep_from: int) -> None:
        """Delete payloads from rounds older than ``keep_from`` (the
        round before the current manifest stays as a safety margin).
        Local filesystem prefixes only — URI stores without listing
        keep their garbage (document in FAULT_TOLERANCE.md)."""
        import os
        from urllib.parse import urlparse
        parsed = urlparse(self._prefix)
        if parsed.scheme not in ("", "file"):
            return
        root = (parsed.netloc + parsed.path) if parsed.scheme == "file" \
            else self._prefix
        try:
            names = os.listdir(root)
        except OSError:
            return
        for name in names:
            if not name.endswith(".snap") or name in self._protected:
                continue
            try:
                seq = int(name.rsplit(".seq", 1)[1][:-len(".snap")])
            except (IndexError, ValueError):
                continue
            if seq < keep_from:
                try:
                    os.unlink(os.path.join(root, name))
                except OSError:
                    pass


def _read_uri(uri: str) -> Optional[bytes]:
    """Read a whole object; None when it definitively does not exist
    (any scheme's read failure counts as absent — the caller treats
    'no snapshot' as a fresh start, and a PRESENT-but-torn local file
    still surfaces through size/crc validation)."""
    from ..io.stream import read_bytes_or_none
    return read_bytes_or_none(uri)


def _write_uri_atomic(uri: str, data: bytes, fsync: bool = False) -> None:
    from ..io.stream import write_bytes_atomic
    write_bytes_atomic(uri, data, fsync=fsync)

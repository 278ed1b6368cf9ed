"""Named performance counters (tracing/profiling subsystem).

TPU-native equivalent of the reference's ``Dashboard``/``Monitor``
(ref: include/multiverso/dashboard.h:16-74, src/dashboard.cpp:14-49): global
registry of named monitors, each accumulating call count and elapsed ms;
``Dashboard.display()`` dumps all. The MONITOR_BEGIN/END macro pair becomes a
context manager (``with monitor("name"):``) that is also a span on the
profiler's clock: every entry opens a ``jax.profiler.TraceAnnotation``
named ``mv:<name>``, so a monitored region lies beside the device's
operations in any trace captured around it (``trace_to``, or the
benchmark's ``--trace 1``). With no profiler session open no annotation
is built.
"""

from __future__ import annotations

import collections
import functools
import math
import re
import sys
import threading
import time
from typing import Dict, List

from .lock_witness import named_lock

#: CANONICAL METRIC-NAME REGISTRY — the one name-and-meaning table for
#: every ``monitor("X")`` / ``samples("X")`` / ``count("X")`` literal
#: in the tree. ``tools/mvlint``'s metric-name pass parses this literal
#: (never imports) and fails CI on any call site naming an unlisted
#: metric, and cross-checks the table against the metric table in
#: ``docs/OBSERVABILITY.md`` in both directions. A trailing ``*``
#: matches a per-destination / per-table FAMILY suffix
#: (``DISPATCH_MS[d*]`` covers ``DISPATCH_MS[d0]``, ``DISPATCH_MS[d7]``,
#: ...). Keep the literal plain (no computed values).
METRIC_NAMES: Dict[str, str] = {
    # -- worker actor / table layer --
    "WORKER_PROCESS_GET": "worker actor Get partition+send handling",
    "WORKER_PROCESS_ADD": "worker actor Add partition+send handling",
    "WORKER_COALESCE_FLUSH": "coalesced BatchAdd flushes packed",
    "WORKER_TABLE_SYNC_GET": "blocking table get_raw issue-to-reply",
    "WORKER_TABLE_SYNC_ADD": "blocking table add_raw issue-to-ack",
    "WORKER_REPLY_GET": "worker actor Get reply handling: materialise, "
                        "place",
    "WORKER_REPLY_ADD": "worker actor Add ack handling: version "
                        "stamp, error, the waiter's notify",
    "CLIENT_ISSUE_GET": "caller's thread, a table's public async Get "
                        "entry to the message in the worker's mailbox",
    "CLIENT_ISSUE_ADD": "the same for an Add (the ids' range check, "
                        "the cache's begin_add — an inactive cache's "
                        "fence is named from the ids' two ends — and "
                        "the blobs included)",
    "TABLE_WAIT": "calling thread blocked in WorkerTable.wait on replies",
    "TABLE_WAKE": "the completing notify on the worker actor's thread "
                  "to a blocked WorkerTable.wait running again",
    "CLIENT_PLACE_ROWS": "Get reply rows placed into the caller's buffer",
    "GET_REPLY_ROWS_DIRECT": "placed shards that were the request, or a "
                             "run of a sorted one: one copy",
    "GET_REPLY_ROWS_PLACED": "placed shards that took the general "
                             "sort-search-gather-scatter",
    "ADD_ROWS_SHARD_VIEW": "host row-Add shards partition cut as views "
                           "of the request (one server, or a run of "
                           "a request in server order): no copy",
    "ADD_ROWS_SHARD_COPIED": "host row-Add shards partition gathered "
                             "with a mask into a fresh array",
    "GET_REPLY_ROWS_PIECED": "host row Get reply shards whose payload "
                             "left the device in row-range pieces, "
                             "each placed while the next was copied",
    "GET_REPLY_ROWS_WHOLE": "host row Get reply shards that reached "
                            "their sink as one array",
    "BLOB_D2H": "device payload copied to host (np.asarray of a "
                "jax.Array: waits for its program, then copies; one "
                "entry a payload, whole or in pieces)",
    "BLOB_D2H_READY": "inside BLOB_D2H: the wait for the program that "
                      "makes the array (block_until_ready; a pieced "
                      "payload's cuts are dispatched inside it)",
    "BLOB_D2H_COPY": "inside BLOB_D2H: the copy (np.asarray of the "
                     "ready array, or of its pieces one after the "
                     "other: the thread's time in np.asarray alone)",
    "BLOB_D2H_BYTES": "bytes those device-to-host copies moved",
    # -- server actor --
    "SERVER_PROCESS_GET": "server-side Get table op + reply",
    "SERVER_PROCESS_ADD": "server-side Add apply + ack",
    "SERVER_PROCESS_BATCH_ADD": "server-side coalesced batch apply",
    # -- server request fusion (runtime/fusion.py; docs/SERVER_ENGINE.md) --
    "SERVER_FUSE_BATCH": "fused mailbox batch sizes (messages drained "
                         "per dispatch; sampled only when > 1)",
    "SERVER_DEVICE_DISPATCHES": "device programs dispatched by server "
                                "table ops (serial + fused paths)",
    "SERVER_FUSE_DEDUP_ROWS": "cross-request duplicate rows gathered "
                              "once by a fused Get",
    # -- model / collective stalls --
    "PS_GET_STALL": "trainer blocked on a parameter Get (prefetch miss)",
    "MA_COMM_STALL": "model-average blocked on the collective",
    # -- sparse collective tier (runtime/allreduce_engine.py) --
    "SPARSE_FILL[*]": "sparse collective fill-in: union density per "
                      "merge hop ([reduce]) and probed input density "
                      "([input])",
    # -- snapshotter --
    "SNAPSHOT_CAPTURE": "consistent state cut under the table lock",
    "SNAPSHOT_WRITE": "snapshot serialize+write off the lock",
    # -- wire transport --
    "tcp_serialize": "message -> wire frame serialize",
    "tcp_send": "blocking socket send of one frame",
    "tcp_recv": "socket read of one inbound frame body",
    "tcp_deserialize": "wire frame -> message parse",
    # -- zero-copy wire path (runtime/tcp.py, util/buffer_pool.py;
    #    docs/MEMORY.md) --
    "WIRE_BYTES_COPIED": "payload+framing bytes memcpy'd by "
                         "serialize/deserialize (near zero on the "
                         "transport's path)",
    "WIRE_PAYLOAD_BYTES": "payload bytes that crossed "
                          "serialize/deserialize (the copy-ratio "
                          "denominator)",
    "POOL_HIT": "receive-frame leases served from the buffer pool",
    "POOL_MISS": "receive-frame leases that allocated fresh",
    "POOL_RESIDENT_KB": "buffer-pool retained free bytes (KB) at "
                        "each return",
    # -- shared-memory transport (runtime/shm.py; docs/MEMORY.md
    #    "Below the socket") --
    "shm_send": "ring-slot copy of one outbound frame (the shm data "
                "path's single copy)",
    "shm_recv": "in-place parse (or chunk reassembly) of one "
                "ring-borne frame",
    "SHM_FRAMES": "frames sent through shm rings",
    "SHM_BYTES": "frame bytes sent through shm rings",
    "SHM_RING_FULL_WAITS": "ring-full backpressure episodes on shm "
                           "writer threads (slow-reader signal)",
    "SHM_CHUNKED_FRAMES": "frames larger than one ring slot, streamed "
                          "as CONT chunks",
    "SHM_BYTES_COPIED": "bytes copied out of ring slots reassembling "
                        "chunked frames (single-slot frames parse in "
                        "place and count nothing here)",
    "SHM_SLOT_PARKED": "ring slots parked because a Blob view "
                       "outlived its message (freed on re-probe)",
    "SHM_PIN_COPIES": "frames copied off the ring because consumer-"
                      "held frames pinned half the slots (the anti-"
                      "deadlock pressure valve)",
    # -- client cache (tables/client_cache.py) --
    "CLIENT_CACHE_HIT": "cache lookups served locally",
    "CLIENT_CACHE_MISS": "cache lookups that crossed the wire",
    "CLIENT_CACHE_JOIN": "gets joined onto an in-flight prefetch",
    "CLIENT_CACHE_PREFETCH": "prefetch requests issued",
    # -- hot-shard replication (runtime/replica.py) --
    "REPLICA_HIT": "rows served from a replica store",
    "REPLICA_MISS": "replicated rows a holder could not serve",
    "REPLICA_REPAIR": "repair requests issued to row owners",
    "REPLICA_STALE": "replica groups rejected below a RYW floor",
    "REPLICA_SYNC": "write-through refreshes fanned out",
    # -- elastic resharding + chaos harness (runtime/shard_map.py,
    #    util/chaos.py; docs/SHARDING.md) --
    "SHARD_MIGRATE_ROWS": "rows/buckets streamed between servers by "
                          "live migrations",
    "SHARD_FWD": "requests routed through a dual-read/forwarding "
                 "window",
    "SHARD_RETRANSMIT": "migration chunks re-sent after a detected "
                        "seq gap",
    "CHAOS_DROPPED": "frames dropped by the -chaos_frames harness",
    "CHAOS_DELAYED": "frames delayed by the -chaos_frames harness",
    # -- event-loop transport core (runtime/tcp.py; docs/THREADS.md) --
    "DISPATCH_MS[d*]": "per-destination submit-to-wire-complete "
                       "latency (ms) on the event loop",
    "DISPATCH_QUEUE_DEPTH[d*]": "per-destination outbound frame-queue "
                                "depth at submit",
    "EVENTLOOP_TICK_MS": "event-loop tick duration (ms): one "
                         "select-wake's worth of handler+timer work",
    "EVENTLOOP_READY_FDS": "fds reported ready per selector wake",
    "NET_PEER_STATE[*]": "peer state-machine transitions entered "
                         "(CONNECTING/HANDSHAKE/READY/DRAINING/DEAD)",
    "TRANSPORT_THREADS": "live transport threads per rank (EVENTLOOP "
                         "+ shm WRITER); the O(1)-in-peers invariant",
    # -- observability export (runtime/metrics.py) --
    "METRICS_REPORT": "per-rank metrics snapshots shipped",
    "METRICS_DROPPED_STALE": "out-of-order/stale rank reports the "
                             "controller aggregation dropped",
    # -- closed-loop self-tuning (runtime/autotune.py) --
    "AUTOTUNE_DECISION": "knob changes broadcast by the autotune "
                         "controller",
    # -- actor mailboxes (util/mt_queue.py track_depth) --
    "MAILBOX_DEPTH[*]": "actor mailbox depth at each push",
    # -- actor mailboxes (runtime/actor.py: stamped in receive, closed
    #    at the pop) --
    "MAILBOX_WAIT[*]": "enqueue-to-dequeue time of each message, per "
                       "actor ([server], [worker], ...)",
    # -- server table construction (tables/matrix_table.py) --
    "TABLE_INIT": "MatrixServer random_init: the uniform draw on the "
                  "devices, one program a table, each shard its own "
                  "rows (sharding/mesh.py uniform_sharded), to ready",
    # -- set-up seen from inside: jax.monitoring's stage events of every
    # program a thread makes, heard by the listeners below (registered by
    # util/compile_cache.py enable()). Exclusive: their sum is the
    # THREAD-seconds spent making programs (two threads that build at
    # once both count, so a process that compiles can read more than its
    # wall clock) --
    "PROGRAM_TRACE": "a program traced to a jaxpr: the OUTERMOST trace of "
                     "a thread alone, the jitted functions traced inside "
                     "it included",
    "PROGRAM_LOWER": "a module lowered to MLIR, its Pallas kernels' "
                     "lowering to Mosaic and the tracing of their bodies "
                     "inside it",
    "PROGRAM_CACHE_READ": "a program's whole backend stage where the "
                          "persistent cache supplied it: key, retrieval, "
                          "load",
    "PROGRAM_COMPILE": "a program's whole backend stage where it did not: "
                       "key and XLA's compile",
    # -- set-up that is not a program's build --
    "TRAINER_BUILD": "a trainer's constructor (PSLMTrainer, "
                     "DeviceCorpusTrainer, PSDeviceCorpusTrainer), the "
                     "program builds inside it included",
    "DICT_ALIAS_BUILD": "models/wordembedding/model.py build_alias at its "
                        "call in Word2Vec: the negative sampler's alias "
                        "tables on the host",
    # -- row scatter-adds by the path their shapes chose: a table's Add
    # (updater/engine.py), a block of the local word2vec trainer's
    # group (models/wordembedding/device_train.py) --
    "UPDATE_ROWS_FAST": "apply_rows / apply_rows_gather dispatches, and "
                        "the scatter-add calls of each block a "
                        "DeviceCorpusTrainer group trained (two a "
                        "block), whose scatter-add is the sorted-runs "
                        "kernel (updater/row_scatter.py; "
                        "rules.fast_rows)",
    "UPDATE_ROWS_XLA": "the same dispatches and calls whose scatter-add "
                       "is XLA's scatter (off the TPU, other dtypes, "
                       "under rules.FAST_MIN_IDS ids)",
    # -- inside the server's handlers (updater/engine.py,
    # tables/matrix_table.py process_get) --
    "UPDATE_PAD_ROWS": "pad_rows on a HOST delta: its rows copied into "
                       "the head of a staging buffer the engine keeps, "
                       "or np.pad / the copy at a bucket-sized k into a "
                       "new array",
    "UPDATE_PAD_STAGED": "host deltas pad_rows copied into a staging "
                         "buffer the engine keeps (nothing allocated)",
    "UPDATE_PAD_FRESH": "host deltas pad_rows gave a new array: padded "
                        "size under 128 KiB, every buffer of the bucket "
                        "still being read by the runtime, or a table "
                        "over several devices",
    "UPDATE_DISPATCH": "the update's jitted call, dense or rows (a "
                       "host delta's upload is inside it)",
    "TABLE_GATHER_DISPATCH": "a row Get's pad_ids and the gather's "
                             "jitted call, host ids or device keys",
    # -- device-corpus trainers (models/wordembedding/device_train.py) --
    "TRAINER_EPOCH_PREP": "train_epoch entry to its first block's "
                          "dispatch: _prep (subsample mask, one sort "
                          "that carries tokens and sentence ids), pad "
                          "(the PS trainer waits for it), kept-count "
                          "readback",
    "TRAINER_BLOCK_IDS": "a PS block's learning rate and word count "
                         "on the host and its ids program dispatched "
                         "(the block's key and base computed inside)",
    "TRAINER_BLOCK_STEP": "a PS block's reply parts taken and its step "
                          "program dispatched (the epoch's loss and "
                          "pair sums kept inside)",
    "TRAINER_BLOCK_PACE": "a PS block's wait for the block before it "
                          "to have run its step: the host stays one "
                          "block ahead of the device",
    "TRAINER_GROUP_DISPATCH": "the local trainer's group program "
                              "dispatched with its two uploads (G "
                              "blocks)",
    # -- language-model trainer (models/lm/ps_train.py) --
    "LM_STEP": "PSLMTrainer.step: Gets, programs and Adds dispatched "
               "(the wait for the last step's programs included)",
    "LM_GET_PARAMS": "a step's Gets: the embedding rows by device keys, "
                     "a layer's tables whole, head and final norm",
    "LM_ADD_GRADS": "a step's Adds: a layer's gradients whole as "
                    "device deltas, head and norm, the embedding rows",
    "LM_TOKENS": "tokens trained",
    "LM_POSITIONS": "positions through the layers: the tokens trained, or "
                    "twice as many under block diffusion (the noised and "
                    "the clean copy)",
    "LM_MASKED_TOKENS": "positions that carry a loss: every one under "
                        "next-token, the masked ones under block diffusion",
    "LM_GET_BYTES": "bytes of the whole-table device Gets' replies",
    "LM_ADD_BYTES": "bytes of the whole-table device Adds' deltas",
    "LM_HELD_ASSIGNMENTS": "(token, expert) assignments that fell on held "
                           "experts, every layer and sequence",
    "LM_EXPERT_MAX_TOKENS": "the fullest held expert's tokens, summed "
                            "over layers and sequences",
    "LM_EXPERTS_SHORT": "sparse layers' sequences whose routed experts "
                        "worked in the short buffer (the held "
                        "assignments fit model.experts_capacity)",
    "LM_EXPERTS_FULL": "sparse layers' sequences whose routed experts "
                       "took the buffer of every assignment",
    "LM_ATTN_PASS_FUSED": "layers' sequences whose way from the attention's "
                          "projections to its kernel and back was the one "
                          "pass of models/lm/attn_kernels.py (latent "
                          "attention: latent_kernels.py)",
    "LM_ATTN_PASS_PLAIN": "layers' sequences that took the jax.numpy "
                          "chain there (no TPU, no whole block of tokens "
                          "or tile of lanes, neither head norms nor a turn)",
    "LM_ATTN_BLOCKS_FITTED": "layers' sequences whose attention kernels ran "
                             "at tile sizes fitted to the call "
                             "(model.attention_blocks: the mask's kind and "
                             "reach, the length, the heads' lanes)",
    "LM_ATTN_BLOCKS_PLAIN": "layers' sequences whose attention kernels "
                            "kept tiles of 512 everywhere",
    "LM_KDA_TOKENS": "tokens through delta layers (models/lm/delta.py), "
                     "every such layer and sequence",
    "LM_KDA_CHUNKS": "chunks the delta layers' scans walked, a layer a "
                     "sequence",
    "LM_KDA_DECAY_CHANNELS": "(chunk, head, channel) triples of the delta "
                             "layers' scans",
    "LM_KDA_DECAY_DEEP": "of those, the triples whose log decay summed over "
                         "the chunk is under delta.DEEP (computed in the "
                         "scan)",
    "LM_KDA_SCAN_KERNEL": "delta layers' sequences whose scan ran as the "
                          "Pallas kernels of models/lm/delta_kernels.py "
                          "(delta.scan_in_kernels)",
    "LM_KDA_SCAN_PLAIN": "delta layers' sequences whose scan took the "
                         "jax.numpy runs of chunks (no TPU, a chunk that "
                         "is not 64, a head that is not one 128-lane tile)",
    "LM_KDA_PASS_FUSED": "delta layers' sequences whose short "
                         "convolutions, gates and gated output norm ran as "
                         "the Pallas passes of models/lm/delta_passes.py "
                         "(delta.passes_fused)",
    "LM_KDA_PASS_PLAIN": "delta layers' sequences whose short "
                         "convolutions, gates and gated output norm took "
                         "the jax.numpy chain (no TPU, a "
                         "length that 512 does not divide, a head that is "
                         "not one 128-lane tile)",
    "LM_EMBED_ROWS": "distinct embedding rows a step named, summed",
    "LM_MTP_TOKENS": "positions the multi-token module predicted (a "
                     "trainer that holds the module)",
    "LM_MTP_STEP": "PSLMTrainer._module_step: the multi-token module's "
                   "Gets, three programs and Adds dispatched, inside "
                   "LM_STEP",
    "LM_ROUTER_BIAS_ADDS": "plain Adds of a router bias's step, one a "
                           "sparse layer a step",
    "LM_ROUTER_LOAD_MAX": "the fullest router output's assignments over "
                          "ALL of a sparse layer's outputs, summed over "
                          "layers",
    "LM_GATE_OPEN": "the per-head attention gate's value: each layer's "
                    "gates summed over its heads, the step's mean over "
                    "tokens, summed over layers, in thousandths",
    "LM_KDA_BETA": "(position, head) pairs of the delta layers' scans, "
                   "where beta can pass 1 (LMConfig.kda_beta_scale 2)",
    "LM_KDA_BETA_OVER_ONE": "of those, the pairs whose beta is over 1: a "
                            "state can flip sign along their key (computed "
                            "on the device)",
    "LM_GATE_LANES": "lanes of the per-lane attention gate "
                     "(LMConfig.attn_gate \"lane\"): a position a held head "
                     "a lane, every gated layer and sequence",
    "LM_GATE_LANES_OPEN": "of those, the lanes whose gate is over a half "
                          "(computed on the device)",
    "LM_HEADS": "heads of the layers' attention as published, a layer a "
                "sequence",
    "LM_HEADS_HELD": "of those, the heads held here (LMConfig.heads_held)",
    "LM_MIXERS": "mixers of a model with a layer whose mixer is not "
                 "attention (LMConfig.attention_layout \"conv\" or "
                 "\"ssd\"), a layer a sequence",
    "LM_MIXERS_CONV": "of those, the gated short convolutions "
                      "(models/lm/shortconv.py)",
    "LM_MIXERS_SSD": "of those, the selective state-space mixers "
                     "(models/lm/ssd.py)",
    "LM_SSD_CHUNKS": "chunks the state-space layers' scans walked, a layer "
                     "a sequence (LM_SSD_CHUNKS times the layer's heads: "
                     "the (chunk, head) pairs)",
    "LM_SSD_DEEP": "the (chunk, head) pairs of those scans whose log decay "
                   "summed over the chunk is under delta.DEEP (computed in "
                   "the scan)",
    "LM_SSD_SCAN_KERNEL": "state-space layers' sequences whose scan ran as "
                          "ssd_kernels.py's kernels (ssd.scan_counter)",
    "LM_SSD_SCAN_PLAIN": "state-space layers' sequences whose scan took "
                         "the jax.numpy runs of chunks (models/lm/ssd.py)",
    "LM_ATTN_LANES": "the same models' attention layers, a layer a "
                     "sequence: the lanes a head holds",
    "LM_ATTN_LANES_TILED": "the lanes the attention kernel is handed a "
                           "head: the same where no head is padded",
    "LM_TIED_ADDS": "the one Add a step to a table that is embedding and "
                    "head (LMConfig.tied)",
    # -- thread-role blocking watchdog (runtime/thread_roles.py;
    #    docs/THREADS.md) --
    "ROLE_BLOCKED_MS[*]": "wall-clock ms a DISPATCH/LIVENESS/"
                          "EVENTLOOP thread sat blocked past "
                          "-role_block_budget_ms (per role, "
                          "-debug_locks watchdog)",
    # -- the heartbeat, the process's one always-on sampler
    #    (runtime/thread_roles.py; docs/OBSERVABILITY.md "Stalls") --
    "HOST_BEAT_LATE": "one entry a beat of the heartbeat thread: how "
                      "many ms after its 10 ms sleep was due it ran "
                      "again (the wait for the GIL or a core)",
    "HOST_STALL": "one entry a stall record (overlapping openings are "
                  "one record), ms what its openings added as each was "
                  "seen: a late beat's lateness, a working entry's length",
    "HOST_STALL_FROZEN": "the same for the late beats over which the "
                         "process used next to no CPU: the machine's part "
                         "(held and blocked are read from the records)",
    # -- online serving tier (serving/; docs/SERVING.md) --
    "SERVING_REQUESTS": "serving-frontend requests admitted and served",
    "SERVING_SHED": "serving-frontend requests rejected by admission",
    "SERVING_LATENCY_MS": "serving-frontend request latency (ms)",
    "SERVING_BATCH_SIZE": "requests folded into one serving read batch",
    "SERVING_CACHE_HIT": "requests served whole from the hot-response "
                         "cache",
    "ANN_PROBE_MS": "IVF neighbors probe latency (ms)",
}

#: Version stamp on serialized metrics snapshots
#: (``metrics_snapshot()``): consumers reject a snapshot whose version
#: they do not understand instead of mis-merging it.
#: Family matching against the registry (trailing-``*`` entries) lives
#: in ``tools/mvlint/metric_lint.py family_match`` — the one
#: implementation, used by the lint that enforces this registry.
METRICS_SNAPSHOT_VERSION = 1

#: A monitor entry longer than this (ms) is handed to the heartbeat
#: (runtime/thread_roles.py), which decides on its own thread whether it
#: opens a stall record or is context to one. The hot path pays one
#: comparison; the stalls on record are 112 ms and more, and the
#: heartbeat's floor for a late beat is the same 40.
LONG_ENTRY_MS = 40.0

#: ``(name, thread ident, end on time.monotonic(), ms, the monitor's
#: count and elapsed ms with this entry in)`` of each such entry, oldest
#: first; the heartbeat drains it. Bounded, so a process that never
#: starts a thread keeps at most this many.
long_entries: collections.deque = collections.deque(maxlen=1024)

#: Monitors that only wait: an entry of theirs never opens a stall
#: record by itself (a TABLE_WAIT of 100 ms behind a backward program is
#: ordinary), it is context to one. A ``*`` stands for a family's
#: suffix, as in METRIC_NAMES.
WAITING = ("TABLE_WAIT", "TABLE_WAKE", "MAILBOX_WAIT[*]", "BLOB_D2H_READY",
           "TRAINER_BLOCK_PACE", "PS_GET_STALL", "MA_COMM_STALL")


def only_waits(name: str) -> bool:
    for waiting in WAITING:
        head, star, tail = waiting.partition("*")
        if name == waiting or (star and name.startswith(head)
                               and name.endswith(tail)):
            return True
    return False


#: The last stall records the heartbeat closed, oldest first, and how
#: many it has closed in all.
_stalls: collections.deque = collections.deque(maxlen=64)
_stalls_closed = 0


def stalls() -> List[dict]:
    """The last 64 stall records (docs/OBSERVABILITY.md "Stalls"): this
    process's own, read here or in ``Dashboard.display()``."""
    return list(_stalls)


def keep_stall(record: dict) -> None:
    global _stalls_closed
    _stalls.append(record)
    _stalls_closed += 1


def reset_stalls() -> None:
    global _stalls_closed
    _stalls.clear()
    _stalls_closed = 0
    long_entries.clear()


class Monitor:
    def __init__(self, name: str):
        self.name = name
        self._count = 0
        self._elapsed_ms = 0.0
        self._local = threading.local()  # per-thread begin time
        self._lock = named_lock(f"dashboard.monitor[{name}]")

    def begin(self) -> None:
        self._local.begin = time.perf_counter()

    def end(self) -> None:
        begin = getattr(self._local, "begin", None)
        if begin is None:
            return
        self.add((time.perf_counter() - begin) * 1e3)
        self._local.begin = None

    def add(self, elapsed_ms: float, entries: int = 1) -> None:
        """One entry of ``elapsed_ms``; ``entries=0`` adds time to an
        entry already counted (a stall record's later openings)."""
        with self._lock:
            self._count += entries
            self._elapsed_ms += elapsed_ms
        if elapsed_ms > LONG_ENTRY_MS:
            long_entries.append((self.name, threading.get_ident(),
                                 time.monotonic(), elapsed_ms,
                                 self._count, self._elapsed_ms))

    def add_count(self, n: int) -> None:
        """Bulk count bump with no elapsed time (row-granular event
        counters — replica hit/miss rows per reply)."""
        with self._lock:
            self._count += n

    @property
    def count(self) -> int:
        return self._count

    @property
    def elapse(self) -> float:
        return self._elapsed_ms

    @property
    def average(self) -> float:
        return self._elapsed_ms / self._count if self._count else 0.0

    def __str__(self) -> str:
        return (f"[{self.name}] count = {self._count} "
                f"elapse = {self._elapsed_ms:.2f}ms "
                f"average = {self.average:.3f}ms")


class Dashboard:
    _monitors: Dict[str, Monitor] = {}
    # Module-level singleton: witnessed only when -debug_locks was set
    # before the first dashboard import (util/lock_witness.py).
    _lock = named_lock("dashboard.registry")

    @classmethod
    def get(cls, name: str) -> Monitor:
        # A registered monitor is read without the registry lock (one
        # dict lookup, atomic under the GIL): every hot site re-resolves
        # here per entry. Only a first use, or the first after a
        # reset(), takes the lock.
        mon = cls._monitors.get(name)
        if mon is not None:
            return mon
        with cls._lock:
            mon = cls._monitors.get(name)
            if mon is None:
                mon = Monitor(name)
                cls._monitors[name] = mon
            return mon

    @classmethod
    def add_monitor(cls, monitor: Monitor) -> None:
        with cls._lock:
            cls._monitors[monitor.name] = monitor

    @classmethod
    def watch(cls, name: str) -> str:
        with cls._lock:
            mon = cls._monitors.get(name)
            return str(mon) if mon else f"[{name}] <unregistered>"

    @classmethod
    def display(cls) -> str:
        """Full registry report: monitors AND sample reservoirs, each
        section sorted by name so successive dumps diff cleanly (dict
        insertion order made the report depend on which code path ran
        first); after the monitors the ten programs whose making took
        longest (``program_builds``), then the stall records."""
        with cls._lock:
            lines = [str(m) for _, m in sorted(cls._monitors.items())]
        lines += program_lines(DISPLAYED_PROGRAMS)
        with _samples_lock:
            reservoirs = sorted(_samples.items())
        for name, s in reservoirs:
            snap = s.snapshot()
            if snap.get("count"):
                lines.append(
                    f"[{name}] count = {snap['count']} "
                    f"p50 = {snap.get('p50', 0.0):.3f} "
                    f"p90 = {snap.get('p90', 0.0):.3f} "
                    f"p99 = {snap.get('p99', 0.0):.3f} "
                    f"max = {snap.get('max', 0.0):.3f}")
        for record in stalls():
            lines.append(
                f"[stall] {record['class']} {record['ms']:.1f}ms "
                f"began_wall_ns = {record['began_wall_ns']} "
                f"by = {record.get('by')} cpu = {record['cpu_ms']:.1f}ms")
        return "\n".join(lines)

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._monitors.clear()


#: Prefix of every monitor's span in a profiler trace. The benchmark's
#: own spans are ``bench:``; its reduction keeps only those.
SPAN_PREFIX = "mv:"

_trace_annotation = None  # jax.profiler.TraceAnnotation, bound on first use


def _bind_annotation():
    global _trace_annotation
    from jax.profiler import TraceAnnotation
    _trace_annotation = TraceAnnotation
    return TraceAnnotation


def mark(name: str, **args) -> None:
    """An instant ``mv:<name>`` annotation with ``args`` as its stats
    under an open profiler session; nothing without one, and jax is not
    imported for it (a process that never touched jax has no session)."""
    if "jax" not in sys.modules:
        return
    annotation = _trace_annotation or _bind_annotation()
    if annotation.is_enabled():
        with annotation(SPAN_PREFIX + name, **args):
            pass


class monitor:
    """Context manager replacing MONITOR_BEGIN/END macro pair, and a
    ``mv:<name>`` span in any profiler trace captured around it.

    ``args`` (a request's ``msg_id`` and ``table``) go to the span
    only, so that a span in a trace can be matched to its request;
    the Monitor counts and times the same whatever they are. "Tracing
    off" is "no profiler session open" (``TraceMe.is_enabled()``): no
    annotation is built then.
    """

    __slots__ = ("_name", "_args", "_monitor", "_span", "_begin")

    def __init__(self, name: str, **args):
        self._name = name
        self._args = args

    def __enter__(self) -> Monitor:
        annotation = _trace_annotation or _bind_annotation()
        # Built only under a profiler session: a disabled TraceMe
        # records nothing, and building one is a third of an entry.
        if annotation.is_enabled():
            self._span = annotation(SPAN_PREFIX + self._name, **self._args)
            self._span.__enter__()
        else:
            self._span = None
        # Re-resolved per entry, NOT cached at construction: a
        # ``Dashboard.reset()`` (tests do one between cases) replaces
        # the registry, and a long-lived ``monitor(...)`` instance
        # caching its Monitor would keep writing to an unregistered
        # orphan that no display()/snapshot ever sees.
        self._monitor = Dashboard.get(self._name)
        self._begin = time.perf_counter()
        return self._monitor

    def __exit__(self, *exc) -> None:
        self._monitor.add((time.perf_counter() - self._begin) * 1e3)
        if self._span is not None:
            self._span.__exit__(*exc)
        return None


class laps(monitor):
    """ONE Monitor entry made of several stretches: every ``with`` is a
    span in a trace and adds its time to the entry, ``close()`` counts
    it. For work that comes in pieces with a consumer in between (a
    reply's device-to-host copy, piece by piece, and the placing of
    each): the monitor keeps counting one entry a reply, and holds none
    of what ran between its stretches."""

    __slots__ = ("_ms",)

    def __init__(self, name: str, **args):
        super().__init__(name, **args)
        self._ms = 0.0

    def __exit__(self, *exc) -> None:
        self._ms += (time.perf_counter() - self._begin) * 1e3
        if self._span is not None:
            self._span.__exit__(*exc)
        return None

    def close(self) -> None:
        Dashboard.get(self._name).add(self._ms)


class Samples:
    """Bounded reservoir of per-op scalar samples (latencies, queue
    depths) with percentile readout — the p50/p99 companion to the
    cumulative ``Monitor``. Ring-buffer overwrite past ``cap`` keeps the
    cost O(1) per sample and the memory bounded; percentiles are then
    over the most recent ``cap`` observations, which is what a measured
    window wants anyway."""

    def __init__(self, name: str, cap: int = 8192):
        self.name = name
        self._cap = int(cap)
        self._buf: list = []
        self._next = 0
        self._total = 0
        self._lock = named_lock(f"dashboard.samples[{name}]")

    def add(self, value: float) -> None:
        with self._lock:
            if len(self._buf) < self._cap:
                self._buf.append(float(value))
            else:
                self._buf[self._next] = float(value)
                self._next = (self._next + 1) % self._cap
            self._total += 1

    @property
    def count(self) -> int:
        return self._total

    @staticmethod
    def _nearest_rank(data: list, p: float) -> float:
        """Nearest-rank percentile over sorted ``data``: the
        ceil(p/100 * n)-th smallest value (1-indexed), so p50 of a
        2-element window is the LOWER value and a 1-element window
        answers every p with its only value."""
        idx = max(math.ceil(len(data) * min(max(p, 0.0), 100.0)
                            / 100.0), 1) - 1
        return data[min(idx, len(data) - 1)]

    def percentile(self, p: float) -> float:
        """The p-th percentile (0-100, nearest-rank) of the retained
        window; 0.0 when empty."""
        with self._lock:
            data = sorted(self._buf)
        if not data:
            return 0.0
        return self._nearest_rank(data, p)

    def snapshot(self) -> dict:
        """Summary: count + p50/p90/p99/max."""
        with self._lock:
            data = sorted(self._buf)
            total = self._total
        if not data:
            return {"count": total}
        return {"count": total,
                "p50": self._nearest_rank(data, 50),
                "p90": self._nearest_rank(data, 90),
                "p99": self._nearest_rank(data, 99),
                "max": data[-1]}

    def export_recent(self, limit: int = 256) -> List[float]:
        """Up to ``limit`` of the most recent retained values, oldest
        first — the raw window the controller merges cluster-wide
        percentiles from (summary snapshots cannot be merged without
        the underlying samples; docs/OBSERVABILITY.md)."""
        with self._lock:
            if len(self._buf) < self._cap or self._next == 0:
                ordered = list(self._buf)
            else:  # ring wrapped: oldest sits at _next
                ordered = self._buf[self._next:] + self._buf[:self._next]
        return ordered[-max(int(limit), 1):]


_samples: Dict[str, Samples] = {}
_samples_lock = named_lock("dashboard.samples_registry")


def samples(name: str, cap: int = 8192) -> Samples:
    """Registry accessor for ``Samples`` (mirrors ``Dashboard.get``)."""
    with _samples_lock:
        s = _samples.get(name)
        if s is None:
            s = Samples(name, cap)
            _samples[name] = s
        return s


def reset_samples() -> None:
    with _samples_lock:
        _samples.clear()


def metrics_snapshot(max_samples: int = 256) -> dict:
    """Serialize the whole registry (monitors + sample reservoirs) into
    a versioned plain dict — the per-rank payload of the
    ``Control_Metrics`` export (runtime/metrics.py) and the local half
    of every ``/metrics`` scrape. ``max_samples`` caps the raw window
    shipped per reservoir (the controller merges these into cluster
    percentiles)."""
    with Dashboard._lock:
        monitors = list(Dashboard._monitors.items())
    with _samples_lock:
        reservoirs = list(_samples.items())
    return {
        "v": METRICS_SNAPSHOT_VERSION,
        # the records stay in their process (1 to 2 KB each, and this
        # dict goes to the controller whole at every report): how many
        # it has closed and when the last began say where to look
        "stalls": {"count": _stalls_closed,
                   "last_began_wall_ns": _stalls[-1]["began_wall_ns"]
                   if _stalls else None},
        # the rows stay in their process too: ``program_builds()``
        "program_builds": {"programs": len(_programs)},
        "monitors": {name: {"count": m.count,
                            "elapsed_ms": round(m.elapse, 3)}
                     for name, m in monitors},
        "samples": {name: {"count": s.count,
                           "recent": s.export_recent(max_samples)}
                    for name, s in reservoirs},
    }


def count(name: str, n: int = 1) -> None:
    """Bump a named counter by ``n`` — a Monitor used purely for its
    call count (elapsed stays 0). The client cache's hit/miss/join
    counters ride the same registry as the timing monitors so
    ``Dashboard.display()`` shows them side by side. ``n`` > 1 serves
    row-granular counters (replica hit/miss rows per reply) without a
    per-row Python loop."""
    if n > 0:
        Dashboard.get(name).add_count(n)


# -- program builds: set-up seen from inside ------------------------------
#
# JAX times each stage of making a program where the work happens and
# hands the time to any listener with the program's name
# (``jax.monitoring``; jax 0.9.0 ``_src/dispatch.py`` ``log_elapsed_time``):
# a SCALAR event when a stage begins and a DURATION event when it ends,
# for tracing to a jaxpr, lowering to MLIR and the backend stage (cache
# key, then the persistent cache's read or XLA's compile), and a plain
# event ``cache_hits`` inside a backend stage that the cache supplied.
# The listeners below turn them into four Dashboard monitors, ``mv:``
# spans under a profiler session, and one row a program. Nothing is built
# inside a steady loop, so they run in set-up alone.

_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

#: stage event -> (monitor, span). The backend stage's monitor is chosen
#: when it ends: PROGRAM_CACHE_READ where a cache hit fired inside it.
_STAGES = {_TRACE_EVENT: ("PROGRAM_TRACE", "PROGRAM_TRACE"),
           _LOWER_EVENT: ("PROGRAM_LOWER", "PROGRAM_LOWER"),
           _BACKEND_EVENT: ("PROGRAM_COMPILE", "PROGRAM_BACKEND")}

#: monitor -> a row's (count, milliseconds) fields
_ROW_FIELDS = {"PROGRAM_TRACE": ("traces", "trace_ms"),
               "PROGRAM_LOWER": ("lowerings", "lower_ms"),
               "PROGRAM_CACHE_READ": ("cache_reads", "cache_read_ms"),
               "PROGRAM_COMPILE": ("compiles", "compile_ms")}

#: The four monitors. An entry is 1 ms or many seconds by the program it
#: makes, so "8 times the monitor's mean" says nothing and none opens a
#: stall record (runtime/thread_roles.py); a long one is context to a
#: record, by name, as a wait's is.
BUILDS = tuple(_ROW_FIELDS)

#: The table holds this many programs by name (the cell with the most
#: makes 209); what comes after goes to one row, OTHER_PROGRAMS.
MAX_PROGRAMS = 512
OTHER_PROGRAMS = "(other programs)"
DISPLAYED_PROGRAMS = 10

_programs: Dict[str, Dict[str, float]] = {}
_programs_lock = named_lock("dashboard.program_builds")
_NOT_OF_A_MODULE_NAME = re.compile(r"[^\w.-]")


class _Building(threading.local):
    """The stages open on one thread: the server actor's thread builds
    the update programs while the trainer's builds the layers'."""

    def __init__(self):
        self.depth = 0          # stages open, of any kind
        self.backend_at = 0     # the depth a backend stage opened at
        self.hit = False        # the cache supplied that one
        self.inside_s = 0.0     # backend stages closed inside the outermost
        self.spans = []         # of the stages that will be counted


_building = _Building()
_listening = False


@functools.lru_cache(maxsize=4096)
def program_key(fun_name: str) -> str:
    """``backward`` and ``jit(backward)`` -> ``jit_backward``: the name
    the device trace prints the program under, less its hash (what
    ``benchmark/lib/xplane.py`` ``stem`` leaves)."""
    if "(" not in fun_name:     # a trace event names the function alone
        fun_name = f"jit({fun_name})"
    return _NOT_OF_A_MODULE_NAME.sub("_", fun_name).rstrip("_")


def _stage_begins(event: str, started: float, fun_name: str = "",
                  **_) -> None:
    """Scalar listener. Tracing a program emits one event for every
    jitted function traced inside it, ``jax.numpy``'s included, tens of
    thousands a process: a nested one is a depth bump and a return."""
    if event not in _STAGES:
        return
    state = _building
    depth = state.depth = state.depth + 1
    if depth > 1 and (event != _BACKEND_EVENT or state.backend_at):
        return
    if event == _BACKEND_EVENT:
        state.backend_at = depth
        state.hit = False
    annotation = _trace_annotation or _bind_annotation()
    if annotation.is_enabled():
        span = annotation(SPAN_PREFIX + _STAGES[event][1],
                          program=program_key(fun_name))
        span.__enter__()
    else:
        span = None
    state.spans.append(span)


def _stage_ends(event: str, seconds: float, fun_name: str = "",
                **_) -> None:
    """Duration listener. One stack for the three stages: a trace or a
    lowering entered inside another stage adds nothing of its own (the
    outermost holds it); a backend stage inside another (an eager
    operation while a program is traced) is a program made, so it is an
    entry of its own and the outermost stage's entry is shorter by it.
    The four monitors so stay exclusive (of a thread's time: two threads
    that build at once both count) and the two backend monitors count
    every program."""
    stage = _STAGES.get(event)
    state = _building
    depth = state.depth
    if stage is None or not depth:  # another event, or a stage that began
        return                      # before the listeners were there
    state.depth = depth - 1
    name = stage[0]
    if state.backend_at == depth:
        state.backend_at = 0
        if state.hit:
            name = "PROGRAM_CACHE_READ"
        if depth > 1:
            state.inside_s += seconds
    elif depth > 1:
        return
    else:
        seconds = max(seconds - state.inside_s, 0.0)
    if depth == 1:
        state.inside_s = 0.0
    span = state.spans.pop()
    if span is not None:
        span.__exit__(None, None, None)
    ms = seconds * 1e3
    Dashboard.get(name).add(ms)
    counted, timed = _ROW_FIELDS[name]
    key = program_key(fun_name)
    with _programs_lock:
        row = _programs.get(key)
        if row is None:
            if len(_programs) >= MAX_PROGRAMS:
                key = OTHER_PROGRAMS
            row = _programs.setdefault(
                key, {field: 0 for pair in _ROW_FIELDS.values()
                      for field in pair})
        row[counted] += 1
        row[timed] += ms


def _cache_event(event: str, **_) -> None:
    if event == _CACHE_HIT_EVENT:
        _building.hit = True


def listen_to_program_builds() -> bool:
    """Register the three listeners, once a process (``compile_cache.
    enable()`` calls this; a second call registers nothing). True where
    this call registered them."""
    global _listening
    if _listening:
        return False
    import jax.monitoring
    jax.monitoring.register_scalar_listener(_stage_begins)
    jax.monitoring.register_event_duration_secs_listener(_stage_ends)
    jax.monitoring.register_event_listener(_cache_event)
    for name in BUILDS:     # a process that compiled nothing reads 0, and
        Dashboard.get(name)  # one that does not listen reads nothing
    _listening = True
    return True


def stop_listening_to_program_builds() -> None:
    """Take the listeners off again (the tests')."""
    global _listening
    if not _listening:
        return
    import jax.monitoring
    jax.monitoring.unregister_scalar_listener(_stage_begins)
    jax.monitoring.unregister_event_duration_listener(_stage_ends)
    jax.monitoring.unregister_event_listener(_cache_event)
    _listening = False


def program_builds() -> Dict[str, Dict[str, float]]:
    """``{program: {traces, trace_ms, lowerings, lower_ms, cache_reads,
    cache_read_ms, compiles, compile_ms}}`` of every program this process
    made, by the name the device trace prints it under (``jit_backward``).
    The rows stay in their process, as the stall records do."""
    with _programs_lock:
        return {key: dict(row) for key, row in _programs.items()}


def program_ms(row: Dict[str, float]) -> float:
    return sum(row[timed] for _, timed in _ROW_FIELDS.values())


def program_lines(limit: int = 0) -> List[str]:
    """The table's rows, largest total first (``limit`` 0: all)."""
    rows = sorted(program_builds().items(),
                  key=lambda item: -program_ms(item[1]))
    return [f"[program {key}] total = {program_ms(row):.1f}ms " + " ".join(
                f"{counted} = {row[counted]} {timed} = {row[timed]:.1f}"
                for counted, timed in _ROW_FIELDS.values())
            for key, row in rows[:limit or None]]


def reset_program_builds() -> None:
    with _programs_lock:
        _programs.clear()


def trace_to(log_dir: str):
    """Whole-program xprof capture: everything inside the block —
    including every ``monitor(...)`` region, as an ``mv:<name>`` span on
    the host's lines — lands in a TensorBoard-loadable trace under
    ``log_dir`` (``tools/trace_spans.py`` reads it). The TPU-native
    counterpart of reading Dashboard.display() next to an MPI profile
    (SURVEY.md section 5.1). Thin lazy-import alias of
    ``jax.profiler.trace`` so future jax trace features are inherited.

        with trace_to("/tmp/xprof"):
            model.train_batches(loader)
    """
    import jax.profiler
    return jax.profiler.trace(log_dir)

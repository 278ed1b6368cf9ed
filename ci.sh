#!/usr/bin/env bash
# CI gate — the repo's equivalent of the reference's Docker test list
# (ref: deploy/docker/Dockerfile:94-113: build, unit tests, binding
# tests, mpirun -np 4 integration tests). Runnable locally and from CI.
set -euo pipefail
cd "$(dirname "$0")"

echo "== mvlint static-analysis gate =="
# Project invariants, machine-checked before anything runs: flag
# registry, wire-slot registry (cross-checked vs docs/WIRE_FORMAT.md),
# device-dispatch guarding, lock discipline, copy discipline on the
# zero-copy wire path (cross-checked vs docs/MEMORY.md),
# interprocedural thread-role blocking reachability (cross-checked vs
# docs/THREADS.md + the THREAD_ROLES registry; runtime twin is the
# -debug_locks/-role_block_budget_ms watchdog), guarded-by field/lock
# annotations, message-protocol flow (every Request reaches exactly
# one handler, every reply path counts the requester's Waiter down,
# cross-checked vs the docs/WIRE_FORMAT.md flow table both
# directions) and the wake-latch re-arm ordering (the PR-19 lost-
# wakeup shape) — twelve passes total. Fails on any non-pragma'd
# violation and prints file:line diagnostics; the trailing summary
# shows per-pass counts. (`python -m tools.mvlint --baseline ...`
# prints the same counts WITHOUT failing — drift-at-a-glance for PRs.)
# See docs/STATIC_ANALYSIS.md.
python -m tools.mvlint multiverso_tpu tests

# Stale-suppression review line, NOT a gate: pragmas that suppressed
# zero findings are listed for cleanup but never fail the build (a
# pragma can be load-bearing only on certain trees).
python -m tools.mvlint --report-unused-pragmas \
    multiverso_tpu tests | grep '^warning:' || true

echo "== mvlint self-check (seeded fixtures must still fail) =="
# The analyzers are regression-protected: a pass that silently stops
# firing would green-light real violations, so the seeded-violation
# fixtures must keep exiting with status 1 (violations found) —
# SPECIFICALLY 1: status 2 means a bad/empty path, i.e. the self-check
# itself went vacuous (fixtures moved), which must also fail loudly.
rc=0
python -m tools.mvlint tools/mvlint/fixtures > /tmp/mv_lint_fix.log 2>&1 \
    || rc=$?
if [ "$rc" -ne 1 ]; then
    cat /tmp/mv_lint_fix.log
    echo "FATAL: mvlint fixtures self-check expected exit 1, got $rc"
    exit 1
fi

echo "== mvchk model-checker gate (systematic schedules) =="
# The dynamic half of the concurrency gate (docs/STATIC_ANALYSIS.md
# "The dynamic half"): deterministic bounded-preemption exploration of
# the real MtQueue/Waiter/_VectorClock primitives on model locks, plus
# the event-loop wake protocol. The exit code is the expectation check
# both ways — every good spec must pass ALL explored schedules AND the
# known-bad pre-PR-19 wake-drain ordering must be REFUTED with a
# printed counterexample trace; a checker that blesses it has gone
# vacuous and fails here, the same self-check discipline as the mvlint
# fixtures above. Seeded-random long runs ride the slow gate.
python -m tools.mvchk
if [ "${MV_CI_SLOW:-0}" = "1" ]; then
    echo "== mvchk soak (seeded-random schedules) =="
    python -m tools.mvchk --random 300 --seed 20260807
fi

echo "== build native (c_api shim) from source =="
make -C native clean
make -C native

echo "== collection sanity (no tests silently skipped) =="
# A collection error under --continue-on-collection-errors silently
# shrinks the suite; gate on a clean collection pass so a broken import
# fails CI loudly instead of skipping its whole file.
python -m pytest tests/ --collect-only -q > /tmp/mv_collect.log 2>&1 \
    || { cat /tmp/mv_collect.log; echo "FATAL: test collection errors"; \
         exit 1; }

echo "== fast wire-codec + client-cache + allreduce subsets =="
# The wire-facing suites run first and explicitly: a regression in the
# codec frames, the versioned cache, or the collective engine must name
# itself, not hide inside the full run's output.
python -m pytest tests/test_wire_codec.py tests/test_client_cache.py -x -q

echo "== zero-copy wire path subset (golden frames / buffer pool / COW) =="
# The zero-copy transport invariants get their own named gate: frame
# byte-identity between the scatter-gather framer and the legacy flat
# serializer (header slots 0-9, codec frames, batch descriptors — the
# no-wire-break proof), buffer-pool lease safety (a blob-outlived array
# is never aliased by a recycled frame), the read-only/materialize
# copy-on-write contract, and TCP round trips with the pool active
# (tests/test_zero_copy.py; docs/MEMORY.md). The static half — mvlint
# pass 8 copy-lint, banning tobytes/bytes()/join on wire-path modules —
# already ran in the mvlint block above.
python -m pytest tests/test_zero_copy.py -x -q

echo "== shm transport subset (co-located rings / lifecycle hygiene / interop) =="
# The below-the-socket transport gets its own named gate: ring round
# trips land as read-only views INTO the shared segment, bounded
# backpressure on a saturated ring, the weakref slot-parking contract,
# oversize chunking through the receive pool, -chaos_frames coverage
# of ring sends, segment unlink on finalize/SIGKILL/rejoin (a
# /dev/shm entry or resource_tracker warning surviving a test is a
# failure), and the mixed shm+TCP 3-process byte-identity proof
# (tests/test_shm.py; docs/MEMORY.md "Below the socket"). The static
# half — copy-lint over runtime/shm.py — ran in the mvlint block.
python -m pytest tests/test_shm.py -x -q

echo "== sparse-allreduce subset (index-union reduce / switchover / sharded avg) =="
# The sparse collective tier gets its own named gate: choose_algo path
# pinning per (size, density, world), index-union merge correctness vs
# numpy, the switchover boundary (results bit-equal on both sides of
# the cutoff), lossy sparse error feedback, sharded-average
# bit-identity + 1/world reduce-state, and the mixed sparse/dense
# generation-tag regression (docs/ALLREDUCE.md sparse tier).
python -m pytest tests/test_allreduce.py -x -q \
    -k "Sparse or ChooseAlgo or Sharded"

echo "== allreduce engine (ring / rhalving / lossy EF / async writer) =="
python -m pytest tests/test_allreduce.py -x -q

echo "== sharding subset (routing equivalence / hot-shard replication) =="
# Multi-server invariants get their own named gate: 1-vs-N element-wise
# routing equivalence across all table types (boundary/off-by-one row
# splits included), the replica protocol's read-your-writes floor and
# version watermark, sticky promotion, and demotion pruning
# (tests/test_sharding.py; docs/SHARDING.md).
python -m pytest tests/test_sharding.py -x -q

echo "== resharding subset (elastic shard maps / live migration) =="
# Elastic-resharding invariants get their own named gate: shard-map
# algebra (epoch-0 equivalence to the frozen layout, move/coalesce,
# planning), the migration state machines (dirty re-streaming, seq-gap
# retransmits), mid-stream 1-vs-N equivalence across a live grow/
# shrink for matrix + KV with array/sparse siblings, the
# no-version-regression handoff property, the unsupported-table NACK
# rollback, and the in-process controller-partition chaos case
# (tests/test_resharding.py; docs/SHARDING.md "Elastic resharding").
# The SIGKILL chaos matrix (kill the migration source / destination
# mid-handoff) is subprocess-heavy and lives behind -m slow.
python -m pytest tests/test_resharding.py -x -q -m 'not slow'
if [ "${MV_CI_SLOW:-0}" = "1" ]; then
    echo "== slow chaos matrix (kill source / kill dest mid-handoff) =="
    python -m pytest tests/test_resharding.py -x -q -m slow
fi

echo "== autotune subset (dynamic flags / config broadcast / policies) =="
# The closed-loop self-tuning layer gets its own named gate: the
# TUNABLE_FLAGS dynamic-flag layer (apply hooks fire on broadcast,
# non-tunable flags rejected atomically, config-epoch regression
# ignored, weak hooks pruned), the Control_Config/Reply round trip,
# the rejoin config re-anchor, the AutotuneManager policies
# (SLO-gated widening/shrinking, hysteresis, cooldown, pinning,
# guardrails), live retunes of construction-time caches, and the
# ClusterMetrics ingest ordering guard (tests/test_autotune.py;
# docs/AUTOTUNE.md). The static half of the gate — tunable-lint —
# already ran in the mvlint block above.
python -m pytest tests/test_autotune.py -x -q -m 'not slow'

echo "== roles subset (thread-role registry / blocking watchdog / call graph) =="
# The thread-role layer gets its own named gate: the spawn contract
# (role registry, auto-start, live-registry drain), the -debug_locks
# blocking watchdog (fires on a deliberately-parked DISPATCH thread,
# silent on a clean 2-rank PS smoke), and the interprocedural call
# graph passes 9/10 stand on (method resolution under a subclass
# binding, Thread-target edges, functools.partial, recursion/depth
# bounds). The static half — thread-role + guarded-by — already ran
# in the mvlint block above. docs/THREADS.md.
python -m pytest tests/test_thread_roles.py tests/test_callgraph.py -x -q

echo "== event-loop transport subset (peer state machines / O(1) threads) =="
# The selector-loop transport core gets its own named gate: every peer
# state transition (CONNECTING -> HANDSHAKE -> READY -> DRAINING ->
# DEAD) driven over real loopback sockets, nonblocking connect backoff
# against a not-yet-bound listener, the connect-deadline typed failure,
# the idle-EOF quiet retire + same-endpoint rejoin, goodbye-draining
# finalize with a peer dying mid-drain, and the O(1)-threads-in-peers
# invariant. The conftest leak guard additionally asserts around EVERY
# test in the repo that role-thread and fd counts return to baseline
# (tests/test_event_loop.py; docs/THREADS.md).
python -m pytest tests/test_event_loop.py -x -q

echo "== server-fusion subset (mailbox drain / fused dispatch / fused == serial) =="
# The server execution engine's request fusion gets its own named
# gate: MtQueue.pop_batch drain semantics (high-watermark + push-side
# track_depth sampling preserved, byte cap bounds the tail, exit
# drains the remainder), the pure planner invariants (barrier
# classes, per-table op exclusivity, BatchAdd all-or-nothing), the
# dispatch protocol (arrival-order replies around barriers,
# post-batch version stamps, PartialFuseError prefix accounting,
# sync-mode force-disable), and the fused == serial equivalence
# integrations across all four table types + the read-your-writes
# floor + a -chaos_frames smoke (tests/test_server_fusion.py;
# docs/SERVER_ENGINE.md).
python -m pytest tests/test_server_fusion.py -x -q -m 'not slow'

echo "== obs subset (wire header / metrics export / scrape surface) =="
# Observability invariants get their own named gate: the ten-int header
# (golden frame bytes; an older peer's nonzero slot 9 is ignored),
# snapshot/cluster aggregation + Prometheus text exposition validity,
# the /metrics HTTP surface, and the 3-process TCP integration proof
# (cluster SERVER_PROCESS_GET == sum of per-rank dumps). The request's
# mv: spans are tests/test_program_spans.py's.
# docs/OBSERVABILITY.md.
python -m pytest tests/test_observability.py -x -q -m 'not slow'

echo "== serving subset (frontend / admission / staleness invariant) =="
# The online serving tier gets its own named gate: the shared HTTP
# base (route dispatch, typed errors), admission control (in-flight
# caps, depth shedding, 429 + Retry-After, graceful drain), mailbox
# depth observability, the versioned serving read's metadata, the
# /v1 endpoints, and the acceptance invariant — every served
# response's max_staleness respects the configured bound while a
# trainer pushes Adds concurrently (tests/test_serving.py;
# docs/SERVING.md).
python -m pytest tests/test_serving.py -x -q -m 'not slow'

echo "== serving-fleet subset (scatter-gather / batching / hot cache / ANN) =="
# The fleet read path gets its own named gate: scatter-gather reads
# with row-scoped partial-failure containment (dead shard owner ->
# retryable 503 on exactly the affected rows, never a wrong value),
# request-batching boundaries (window-deadline vs size-cap flush, the
# lone-request latency bound, batch error isolation), hot-response-
# cache freshness + the data-generation forced invalidation
# (reshard/rejoin), the IVF neighbors index (exactness at full probe,
# recall, the brute=1 escape), and the /v1/status fleet view
# (tests/test_serving_fleet.py; docs/SERVING.md fleet section).
python -m pytest tests/test_serving_fleet.py -x -q -m 'not slow'

echo "== fault-tolerance subset (snapshots / rejoin / backup workers) =="
# Crash-survival invariants get their own named gate: async snapshot
# consistency + restore, dead-peer containment and retry, the BSP
# backup-worker straggler cutoff, and the kill-a-server-mid-epoch
# integration proof (tests/test_fault_tolerance.py). The chaos smoke
# and the snapshot p99 bound are heavier and live behind -m slow — run
# `MV_CI_SLOW=1 ./ci.sh` (or pytest -m slow directly) to include them.
python -m pytest tests/test_fault_tolerance.py -x -q -m 'not slow'
if [ "${MV_CI_SLOW:-0}" = "1" ]; then
    echo "== slow chaos / latency-bound extras =="
    python -m pytest tests/test_fault_tolerance.py -x -q -m slow
fi

echo "== unit + in-process integration tests =="
# Virtual 8-device CPU mesh (tests/conftest.py forces the platform).
# Slow chaos extras stay behind the -m slow gate above.
# test_fault_tolerance.py already ran in its named gate above — its
# kill-a-server integration proof spawns two full subprocess word2vec
# cluster runs, far too heavy to pay twice per CI pass.
python -m pytest tests/ -x -q -m 'not slow' \
    --ignore=tests/test_net_integration.py \
    --ignore=tests/test_fault_tolerance.py

echo "== multi-process TCP integration (the mpirun -np 4 equivalent) =="
python -m pytest tests/test_net_integration.py -x -q

echo "== c_api ABI through ctypes (+ Lua when a runtime exists) =="
python -m pytest tests/test_binding.py -x -q

echo "== runnable distributed example (2 OS processes, machine file) =="
python binding/python/examples/distributed_word2vec.py -n 2

echo "== driver entry points =="
python -c "import __graft_entry__ as g; fn, a = g.entry(); fn(*a)"
# CI has no chips: ask for the 8-device CPU dry run explicitly (the entry
# point itself never leaves the platform jax reports).
JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python -c "import __graft_entry__ as g; g.dryrun_multichip(8)"

echo "CI OK"

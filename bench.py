"""Benchmark entry point for the driver.

Primary metric = the north-star workload: WordEmbedding (skip-gram +
negative sampling) words/sec on one chip through the framework's batched
jitted step (the TPU re-design of the reference's OpenMP word2vec,
ref: Applications/WordEmbedding/src/wordembedding.cpp).

The corpus is synthetic (no network egress in this environment, so enwik9
cannot be fetched): two-topic banded Zipf text at >= 1M raw vocabulary,
which gives the PS path a realistic sparse row working set AND admits a
quality check (within-topic vs cross-topic similarity of frequent words).

Measured and reported honestly (round-2 requirements):
- ``value``: local-mode words/s/chip (must not regress across rounds);
- ``detail.ps_words_per_sec``: the SAME workload trained through the
  parameter-server path — row-sparse pulls, compact jitted step, row
  delta pushes, pipelined (ref: communicator.cpp:117-249);
- ``detail.loss_parity``: fixed-seed loss vs the identical run on the
  host CPU backend, plus the topic-separation quality score;
- ``detail.mfu`` / ``detail.hbm``: achieved FLOP/s and bytes/s for the
  training step against the chip's nominal peaks — the headroom, made
  visible;
- ``detail.matrix_table_bandwidth``: whole-table Add/Get GB/s plus the
  sparse dirty-row Get path (ref: Test/test_matrix_perf.cpp:33-171).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
"""

import contextlib
import glob
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np


@contextlib.contextmanager
def flag_guard():
    """Snapshot/restore EVERY registered flag value around a bench
    phase. Flag state is process-global and survives mv.shutdown()/
    mv.init() cycles, so the old pattern — each phase hand-restoring
    the specific flags it set in a try/finally — has already bitten
    once per the in-file comments (a leaked `max_get_staleness` turns
    the cache on for every later phase's default-flag numbers, a
    leaked `net_pace_mbps` paces every later wire). This guard makes
    the restore structural: whatever `set_flag` calls (or autotune
    Control_Config broadcasts) a phase makes, exit puts every flag
    back — flags registered DURING the phase reset to their defaults."""
    from multiverso_tpu.util.configure import (CANONICAL_FLAGS,
                                               FlagRegister)
    reg = FlagRegister.get()
    before = {name: flag.value for name, flag in reg._flags.items()}
    try:
        yield
    finally:
        for name, flag in reg._flags.items():
            if name in before:
                flag.value = before[name]
            else:
                # Registered DURING the phase. Prefer the canonical
                # default over flag.default: a tunable applied via
                # Control_Config before its defining module imported
                # was implicitly registered with default == the
                # broadcast value, and "restoring" that would leak
                # the tuned knob into every later phase.
                flag.value = CANONICAL_FLAGS.get(name, flag.default)


def flag_guarded(fn):
    """Decorator form of ``flag_guard`` — converts a whole phase: no
    matter how the phase exits, every flag it set is restored."""
    import functools

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with flag_guard():
            return fn(*args, **kwargs)
    return wrapper

VOCAB = 1_200_000
SENTENCES = 150_000
WORDS_PER_SENTENCE = 40
EPOCHS = 3
BATCH = 32768
DIM = 128
NEG = 5
PS_MAX_BATCHES = 240  # cap the timed PS segment (words/s is a rate)
MIN_COUNT = 1  # ~1M-word real dictionary on this corpus (reported below)

# Nominal per-chip peaks for utilization reporting (dense matmul peak for
# the compute dtype class; memory bandwidth). Conservative defaults.
_CHIP_PEAKS = {
    # device_kind substring: (flops_peak, hbm_bytes_per_sec)
    "v5 lite": (197e12, 819e9),
    "v5e": (197e12, 819e9),
    "v4": (275e12, 1228e9),
    "v5p": (459e12, 2765e9),
    "v6": (918e12, 1640e9),
}


def write_corpus(path: str) -> None:
    """Two topic bands over a Zipf(0.8) unigram distribution: sentences
    draw all words from one band, so frequent words cluster by band —
    trainable structure at 1M+ vocabulary scale. The flat exponent (0.8)
    spreads the 6M tokens wide enough that the TRAINED dictionary itself
    exceeds 1M words (reported as vocab_actual), so the PS path is
    exercised at reference-like table heights."""
    rng = np.random.default_rng(0)
    half = VOCAB // 2
    ranks = np.arange(1, half + 1)
    probs = 1.0 / ranks**0.8
    cdf = np.cumsum(probs / probs.sum())
    topics = rng.integers(0, 2, size=SENTENCES)
    draws = rng.random((SENTENCES, WORDS_PER_SENTENCE))
    ids = np.searchsorted(cdf, draws).astype(np.int64)
    ids = np.minimum(ids, half - 1) + topics[:, None] * half
    with open(path, "w") as f:
        for row in ids:
            f.write(" ".join(f"w{i}" for i in row) + "\n")


def _build(corpus: str):
    from multiverso_tpu.models.wordembedding import (Dictionary,
                                                     TokenizedCorpus)
    dictionary = Dictionary.build(corpus, min_count=MIN_COUNT)
    tokenized = TokenizedCorpus.build(dictionary, corpus)
    return dictionary, tokenized


LOCAL_CENTERS = 16384  # centers per device step (window pairs ≈ 2W x C)
LOCAL_DISPATCH = 16    # steps per dispatch group (lax.scan length)
NEG_BLOCK = 8          # fast-mode negative sharing (one K-draw per 8
#   consecutive centers): ~2.4x words/s over per-center draws; the
#   QUALITY record below uses per-pair draws instead.
PS_CENTERS = 32768     # PS blocks pay per-block actor round trips, so
#   bigger blocks win there.
PS_GROUP = 8           # blocks per dispatch in the grouped PS segment
SYNC_GROUPS = 4        # timing-window width, in dispatch groups
# Quality-mode (-per_pair) settings: the sequential-update structure
# that reaches the C++ baseline's topic separation (grid-searched on
# this corpus: C=2048 best; 4-epoch schedule crosses the cpp separation
# at epoch 3 and exceeds it at epoch 4).
QUALITY_C = 2048
QUALITY_DISPATCH = 32
QUALITY_EPOCHS = 4
QUALITY_PS_GROUP = 4   # PS quality mode: 4 blocks per round trip — the
#   largest grouping whose staleness still reaches the cpp separation
#   (G=8 plateaus at ~0.87); 4x fewer per-block program launches makes
#   the crossing time less sensitive to per-dispatch launch cost
QUALITY_WALL_BUDGET_SEC = 420.0  # wall guard for the quality phases:
#   a run whose per-block launches are slow reports a partial curve
#   instead of blowing the whole bench's runtime
CPP_SEP_FALLBACK = 1.0305  # r3's measured cpp separation, used only if
#   the cpp phase fails


class _TimedHook:
    """Shared per-hook timing with forced device syncs: every ``every``
    calls, ``sync()`` must force all dispatched work to completion (a
    tiny scalar readback), and one (wall, words) window sample lands.
    ``median_wps()`` is the steady-state rate estimate."""

    def __init__(self, sync, every: int):
        self._sync = sync
        self._every = every
        self.walls = []
        self.words = []
        self._acc = 0.0
        self._n = 0
        self._t = None

    def start(self) -> None:
        self._t = time.perf_counter()

    def __call__(self, words: float) -> None:
        self._acc += words
        self._n += 1
        if self._n % self._every == 0:
            self._sync()
            now = time.perf_counter()
            self.walls.append(now - self._t)
            self.words.append(self._acc)
            self._t = now
            self._acc = 0.0

    def median_wps(self) -> float:
        med = float(np.median(self.walls)) if self.walls else 0.0
        return (float(np.mean(self.words)) / med) if med else 0.0


def run_local(corpus: str, prebuilt=None, epochs: int = EPOCHS,
              schedule_epochs: int = None, warm: bool = True) -> dict:
    """Train ``epochs`` epochs through the device-resident pipeline
    (corpus in HBM; in-jit subsample/window/negatives — see
    models/wordembedding/device_train.py). ``schedule_epochs``
    (default = epochs) sets the lr-decay horizon — the CPU parity twin
    trains ONE epoch under the SAME schedule, so epoch-0 losses are
    comparable. ``warm=True`` compiles on a throwaway model first (the
    jitted group program is shared via the module-level cache), keeping
    XLA compilation out of the timed region."""
    from multiverso_tpu.models.wordembedding import (DeviceCorpusTrainer,
                                                     Word2Vec,
                                                     Word2VecConfig)
    dictionary, tokenized = prebuilt if prebuilt else _build(corpus)

    def make_model():
        config = Word2VecConfig(embedding_size=DIM, window=5,
                                negative=NEG,
                                epochs=schedule_epochs or epochs,
                                batch_size=BATCH, sample=1e-3,
                                neg_block=NEG_BLOCK)
        return Word2Vec(config, dictionary)

    if warm:
        warm_model = make_model()
        # TWO group calls: the first runs on freshly-uploaded (host
        # layout) tables, the second feeds back donated XLA-layout
        # outputs — each is its own compiled variant, and both must be
        # warm or epoch 0 eats a second compile mid-timing.
        DeviceCorpusTrainer(warm_model, tokenized, LOCAL_CENTERS,
                            LOCAL_DISPATCH).train_epoch(
            seed=99, max_steps=2 * LOCAL_DISPATCH)
        float(warm_model._emb_in[0, 0])  # compile the sync read too
        del warm_model

    model = make_model()
    trainer = DeviceCorpusTrainer(model, tokenized, LOCAL_CENTERS,
                                  LOCAL_DISPATCH)
    # Force the embedding init and corpus upload to COMPLETE before the
    # clock starts (dispatch is async; the transfers would otherwise
    # land inside the first timed window).
    float(model._emb_in[0, 0])
    float(trainer._corpus.flat[0])
    hook = _TimedHook(lambda: float(model._emb_in[0, 0]), SYNC_GROUPS)
    epoch_losses = []
    pair_total = 0.0
    start = time.perf_counter()
    hook.start()
    for epoch in range(epochs):
        loss_sum, pairs = trainer.train_epoch(seed=epoch, group_hook=hook)
        epoch_losses.append(loss_sum / max(pairs, 1))
        pair_total += pairs
    elapsed = time.perf_counter() - start
    assert all(np.isfinite(x) for x in epoch_losses), epoch_losses
    return {
        "wps": model.trained_words / elapsed,
        "median_batch_wps": round(hook.median_wps(), 0),
        "pairs_per_sec": pair_total / elapsed,
        "centers_per_sec": trainer.kept_words_trained / elapsed,
        # One program launch per dispatch group (= one group_hook call):
        # feeds the launch-overhead share of the time decomposition.
        "groups_per_sec": hook._n / elapsed,
        "epoch_losses": [round(float(x), 4) for x in epoch_losses],
        "model": model,
        "dictionary": dictionary,
    }


def run_ps(corpus: str, prebuilt=None) -> dict:
    """Same workload through the parameter-server path: the HBM corpus
    pipeline driving PS matrix tables with DEVICE-RESIDENT keys — every
    block's pull/train/push crosses the full worker/server actor stack
    (models/wordembedding/device_train.py PSDeviceCorpusTrainer). A
    short host-batch PS segment (the cross-process-capable path) is
    timed alongside for continuity with earlier rounds.

    Single worker by design: N virtual ranks on ONE device measure
    contention, not scaling (each reference worker owns its hardware);
    multi-worker correctness is gated by tests/test_wordembedding.py and
    tests/test_net_integration.py, multi-chip sharding by
    __graft_entry__.dryrun_multichip."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.wordembedding import (PSDeviceCorpusTrainer,
                                                     PSWord2Vec,
                                                     Word2VecConfig)
    dictionary, tokenized = prebuilt if prebuilt else _build(corpus)
    mv.init([])
    config = Word2VecConfig(embedding_size=DIM, window=5, negative=NEG,
                            epochs=EPOCHS, batch_size=BATCH, sample=1e-3,
                            use_ps=True, neg_block=NEG_BLOCK)
    model = PSWord2Vec(config, dictionary)
    trainer = PSDeviceCorpusTrainer(model, tokenized, PS_CENTERS)

    # Warm OUTSIDE the timed region (compiles: block-id program, table
    # gathers, the step, the server scatter engines incl. both donated
    # layout variants). The COLD rate (compile included) is reported
    # alongside.
    cold_start = time.perf_counter()
    trainer.train_epoch(seed=99, max_steps=4)
    warm_secs = time.perf_counter() - cold_start
    warm_words = model.trained_words

    # PS blocks are single steps (no scan), so the same wall-clock
    # window width = SYNC_GROUPS * LOCAL_DISPATCH blocks.
    hook = _TimedHook(lambda: float(trainer.last_loss),
                      SYNC_GROUPS * LOCAL_DISPATCH)
    start = time.perf_counter()
    hook.start()
    loss_sum = 0.0
    pairs = 0.0
    for epoch in range(EPOCHS):
        ep_loss, ep_pairs = trainer.train_epoch(seed=epoch,
                                                block_hook=hook)
        loss_sum += ep_loss
        pairs += ep_pairs
    elapsed = time.perf_counter() - start
    words = model.trained_words - warm_words
    median_wps = hook.median_wps()

    # Grouped-dispatch segment: G blocks per pull/step/push round trip
    # (blocks_per_dispatch — bounded staleness, the reference's
    # sync_frequency trade) amortizes the per-block program launches
    # (per-dispatch launch cost: not measured on the current machine).
    grouped = PSDeviceCorpusTrainer(model, tokenized, PS_CENTERS,
                                    blocks_per_dispatch=PS_GROUP)
    grouped.train_epoch(seed=96, max_steps=2 * PS_GROUP)  # warm
    g_words0 = model.trained_words
    g_start = time.perf_counter()
    grouped.train_epoch(seed=95, max_steps=PS_GROUP * 16)
    float(grouped.last_loss)
    grouped_wps = (model.trained_words - g_words0) \
        / (time.perf_counter() - g_start)

    # Observability artifacts for the overhead hunt: the Dashboard
    # counter report (stderr) and an xprof trace of a few PS blocks
    # (ref: the reference ends its perf harness with Dashboard::Display,
    # Test/test_matrix_perf.cpp:125).
    from multiverso_tpu.util.dashboard import Dashboard, trace_to
    trace_dir = os.path.join(tempfile.gettempdir(), "mv_ps_xprof")
    with trace_to(trace_dir):
        trainer.train_epoch(seed=97, max_steps=4)
    dashboard = Dashboard.display()
    print(f"[bench] PS dashboard:\n{dashboard}", file=sys.stderr)
    print(f"[bench] PS xprof trace: {trace_dir}", file=sys.stderr)
    model._drain_pushes()
    separation = topic_separation(
        None, dictionary,
        fetch_rows=lambda ids: model._in_table.get_rows(ids))
    mv.shutdown()
    assert np.isfinite(loss_sum / max(pairs, 1))
    return {"wps": words / elapsed,
            "grouped_wps": round(grouped_wps, 0),
            "dashboard": dashboard.splitlines(),
            "xprof_trace_dir": trace_dir,
            "cold_wps": round(
                (words + warm_words) / (warm_secs + elapsed), 0),
            "warmup_seconds": round(warm_secs, 1),
            "median_batch_wps": round(float(median_wps), 0),
            "avg_loss": round(loss_sum / max(pairs, 1), 4),
            "separation": round(float(separation), 4)}


def run_hs(prebuilt) -> dict:
    """Hierarchical softmax on the local device pipeline (banded
    Huffman paths — one path gather per band position): a capped
    timed segment reporting HS words/s (VERDICT r3 #5)."""
    from multiverso_tpu.models.wordembedding import (DeviceCorpusTrainer,
                                                     Word2Vec,
                                                     Word2VecConfig)
    dictionary, tokenized = prebuilt
    config = Word2VecConfig(embedding_size=DIM, window=5, negative=0,
                            hs=True, epochs=EPOCHS, sample=1e-3)
    # Same warm-then-time protocol as run_local (throwaway model warms
    # both donated-layout variants; drop it BEFORE the timed model so
    # two sets of tables + corpus never coexist in HBM; sync the corpus
    # upload or it lands inside the timed window).
    warm_model = Word2Vec(config, dictionary)
    DeviceCorpusTrainer(warm_model, tokenized, centers_per_step=8192,
                        steps_per_dispatch=8).train_epoch(
        seed=99, max_steps=16)
    float(warm_model._emb_in[0, 0])
    del warm_model
    model = Word2Vec(config, dictionary)
    trainer = DeviceCorpusTrainer(model, tokenized,
                                  centers_per_step=8192,
                                  steps_per_dispatch=8)
    float(model._emb_in[0, 0])
    float(trainer._corpus.flat[0])
    start = time.perf_counter()
    loss, pairs = trainer.train_epoch(seed=0, max_steps=96)
    float(model._emb_in[0, 0])
    elapsed = time.perf_counter() - start
    return {"wps": round(model.trained_words / elapsed, 0),
            "avg_loss": round(loss / max(pairs, 1), 4),
            "centers_per_step": trainer._C,
            "path_len": int(model._points_host.shape[1])}


HOSTBATCH_SIZE = 131072  # the host-batch path is upload/dispatch bound
#   per BLOCK, so the cross-process-capable segment uses reference-style
#   big data blocks (the reference's loader also ships multi-sentence
#   blocks, ref: distributed_wordembedding.cpp:33-56)


def run_hostbatch(prebuilt) -> dict:
    """The HOST-BATCH PS path (row sets prepped host-side — the form
    that also runs cross-process over TCP), timed as its own phase with
    reference-style large blocks."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.wordembedding import (BlockLoader,
                                                     PSWord2Vec,
                                                     Word2VecConfig,
                                                     iter_pair_batches)
    dictionary, tokenized = prebuilt
    mv.init([])
    config = Word2VecConfig(embedding_size=DIM, window=5, negative=NEG,
                            epochs=EPOCHS, batch_size=HOSTBATCH_SIZE,
                            sample=1e-3, use_ps=True,
                            neg_block=NEG_BLOCK)
    model = PSWord2Vec(config, dictionary)

    def capped(seed, cap):
        for i, batch in enumerate(iter_pair_batches(
                dictionary, tokenized, batch_size=HOSTBATCH_SIZE,
                window=5, subsample=1e-3, seed=seed)):
            if i >= cap:
                return
            yield batch

    for warm_batch in capped(99, 3):
        model.train_batch(warm_batch)
    # Bring the loader/actor/device pipeline to steady state before
    # timing — words/s is a rate, and a cold pipeline understates it.
    model.train_batches(BlockLoader(model.prepared(capped(98, 6))))
    words_0 = model.trained_words
    start = time.perf_counter()
    model.train_batches(BlockLoader(model.prepared(capped(0, 72))))
    model._drain_pushes()
    elapsed = time.perf_counter() - start
    mv.shutdown()
    return {"wps": round((model.trained_words - words_0) / elapsed, 0),
            "batch_size": HOSTBATCH_SIZE}


def run_quality(prebuilt, cpp_sep: float, use_ps: bool) -> dict:
    """TIME-TO-QUALITY record: train the -per_pair quality mode (per-
    pair negatives + sequential window sub-steps — the reference's
    update structure, models/wordembedding/device_train.py
    _seq_pair_step) until topic separation reaches the C++ baseline's
    3-epoch value, and report the wall-clock. This is the honest half
    of the throughput claim: the fast banded mode above measures raw
    words/s; this measures learning the same structure the sequential
    C++ SGD learns, in less time."""
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.models.wordembedding import (
        DeviceCorpusTrainer, PSDeviceCorpusTrainer, PSWord2Vec, Word2Vec,
        Word2VecConfig)
    dictionary, tokenized = prebuilt
    config = Word2VecConfig(embedding_size=DIM, window=5, negative=NEG,
                            epochs=QUALITY_EPOCHS, sample=1e-3,
                            per_pair=True, use_ps=use_ps)

    def setup():
        """(model, trainer, fetch) — one shared construction for the
        warm pass and the timed pass, so they cannot drift apart."""
        if use_ps:
            mv.init([])
            model = PSWord2Vec(config, dictionary)
            trainer = PSDeviceCorpusTrainer(
                model, tokenized, QUALITY_C,
                blocks_per_dispatch=QUALITY_PS_GROUP)

            def fetch(ids):
                model._drain_pushes()
                return model._in_table.get_rows(ids)
        else:
            model = Word2Vec(config, dictionary)
            trainer = DeviceCorpusTrainer(model, tokenized, QUALITY_C,
                                          QUALITY_DISPATCH)

            def fetch(ids):
                return np.asarray(model._emb_in[jnp.asarray(ids)])

        return model, trainer, fetch

    # Warm the compile set out of the timed region (cached across runs).
    model, trainer, fetch = setup()
    trainer.train_epoch(seed=99, max_steps=2 * QUALITY_DISPATCH)
    fetch(np.array([0], np.int32))
    if use_ps:
        mv.shutdown()
    model, trainer, fetch = setup()
    if not use_ps:
        float(model._emb_in[0, 0])

    start = time.perf_counter()
    # The phase's own wall guard must also fit inside the GLOBAL bench
    # budget: the phase-skip estimate assumes a typical run, and slow
    # launches may legitimately push the phase to its cap — cap
    # it at what the global budget has left (less a teardown margin).
    global_left = (_BENCH_T0 + WALL_BUDGET_SEC) - time.monotonic() - 30.0
    deadline = start + max(min(QUALITY_WALL_BUDGET_SEC, global_left),
                           10.0)

    class _Deadline(Exception):
        pass

    def deadline_hook(words):
        # Checked per dispatch group, so a single slow epoch
        # cannot blow the budget many times over before the first
        # epoch-boundary check.
        if time.perf_counter() > deadline:
            raise _Deadline

    hook_kw = {"block_hook" if use_ps else "group_hook": deadline_hook}
    curve = []
    losses = []
    time_to_quality = None
    guard_tripped = False
    for epoch in range(QUALITY_EPOCHS):
        try:
            loss, pairs = trainer.train_epoch(seed=epoch, **hook_kw)
        except _Deadline:
            guard_tripped = True
            if use_ps:
                # The aborted epoch left async pushes in flight; wait
                # their acks so shutdown does not race the actors.
                model._drain_pushes()
            break
        losses.append(round(loss / max(pairs, 1), 4))
        sep = float(topic_separation(None, dictionary, fetch_rows=fetch))
        elapsed = time.perf_counter() - start
        curve.append({"epoch": epoch, "separation": round(sep, 4),
                      "elapsed_sec": round(elapsed, 1)})
        if sep >= cpp_sep and time_to_quality is None:
            time_to_quality = round(elapsed, 1)
            break  # record set; spend no more bench time here
        if time.perf_counter() > deadline:
            guard_tripped = True
            break
    if use_ps:
        mv.shutdown()
    return {"time_to_cpp_quality_sec": time_to_quality,
            "cpp_separation_target": round(cpp_sep, 4),
            "wall_guard_tripped": guard_tripped,
            "curve": curve, "epoch_losses": losses,
            "mode": "ps" if use_ps else "local"}


def run_ps_two_workers(prebuilt, blocks: int = 48) -> dict:
    """A MEASURED 2-worker/1-server number (VERDICT r3 #7): two virtual
    worker ranks drive concurrent device-key streams through one shared
    server on one chip — aggregate words/s quantifies server-side
    serialization of concurrent workers (not chip scaling; each
    reference worker owns its hardware)."""
    from multiverso_tpu.models.wordembedding import (PSDeviceCorpusTrainer,
                                                     PSWord2Vec,
                                                     Word2VecConfig)
    from multiverso_tpu.runtime.cluster import LocalCluster
    dictionary, tokenized = prebuilt

    def body(rank):
        import multiverso_tpu as mv
        config = Word2VecConfig(embedding_size=DIM, window=5,
                                negative=NEG, epochs=EPOCHS,
                                batch_size=BATCH, sample=1e-3,
                                use_ps=True, neg_block=NEG_BLOCK)
        model = PSWord2Vec(config, dictionary)
        trainer = PSDeviceCorpusTrainer(model, tokenized, PS_CENTERS)
        trainer.train_epoch(seed=99, max_steps=2)  # warm
        mv.current_zoo().barrier()
        w0 = model.trained_words
        t0 = time.perf_counter()
        trainer.train_epoch(seed=rank, max_steps=blocks)
        elapsed = time.perf_counter() - t0
        return model.trained_words - w0, elapsed

    cluster = LocalCluster(2, roles=["all", "worker"])
    cluster.timeout = 600.0  # 2 ranks time-share one dispatch path
    results = cluster.run(body)
    words = sum(r[0] for r in results)
    elapsed = max(r[1] for r in results)
    return {"aggregate_wps": round(words / elapsed, 0),
            "per_worker": [round(r[0] / r[1], 0) for r in results]}


_SHARD_CHILD = r"""
import os, sys, time, json
import faulthandler
faulthandler.dump_traceback_later(240 + 60 * int(sys.argv[2]), exit=True)
# Host-only child: the parent bench process holds the chip, and a chip
# belongs to one process, so every spawned rank runs on the CPU.
import jax
jax.config.update('jax_platforms', 'cpu')
sys.path.insert(0, {repo!r})
from multiverso_tpu.util import compile_cache
compile_cache.enable()
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.runtime import actor as actors
from multiverso_tpu.util.dashboard import Dashboard, samples

rank = int(sys.argv[1]); n = int(sys.argv[2])
n_servers = n - 1
# Rank 0 is the controller + THE worker; every other rank hosts one
# server shard, so each server owns its own (emulated) wire.
role = 'worker' if rank == 0 else 'server'
mv.init(['-machine_file=' + {mf!r}, '-rank=' + str(rank),
         '-ps_role=' + role, '-net_pace_mbps={pace}',
         '-replica_hot_rows={hot_rows}', '-replica_report_gets=16',
         '-replica_min_gets={min_gets}', '-replica_sync_every={sync_every}',
         '-replica_sync_rows=8'])
ROWS, COLS = {rows}, {cols}
# A POOL of tables, as in a real model (word2vec alone has input +
# output embeddings): the measured loop round-robins async Gets across
# the pool, so per-op fixed costs (partition, turnaround, scheduler
# latency on this one-core box) pipeline behind the paced wire instead
# of adding to every op's critical path — each table still honors the
# one-Get-in-flight rule.
POOL = {pool}
tables = [mv.create_matrix_table(ROWS, COLS)  # creation barrier inside
          for _ in range(POOL)]
table = tables[0]
rng = np.random.default_rng(1234 + rank)


def zipf_ids(k):
    # Word2vec-shaped key stream: ids sorted by frequency, so the Zipf
    # head is CLUSTERED at low ids — i.e. inside server 0's row range.
    # That concentration is exactly what hot-shard replication exists
    # to fix (docs/SHARDING.md).
    return np.unique((rng.zipf({zipf_a}, k) - 1) % ROWS).astype(np.int32)


if rank == 0:
    table.add(rng.standard_normal((ROWS, COLS)).astype(np.float32))
    mv.barrier()  # content line
    # Bucket-size warm sweep: per-shard gather jits compile per padded
    # bucket width — a first-seen width MID-WINDOW is a multi-hundred-ms
    # compile stall charged to one unlucky get.
    for k in (4, 8, 16, 24, 32, 48, 64, 96, 128, 192, 256):
        for t in tables:
            t.get_rows(np.linspace(0, ROWS - 1, k).astype(np.int32))
    t_end = time.perf_counter() + {warm_s}
    t_cap = time.perf_counter() + 4 * {warm_s}
    expect_replica = n_servers > 1 and {hot_rows} > 0
    while time.perf_counter() < t_end or (
            expect_replica and time.perf_counter() < t_cap
            and not (table._replica_router is not None
                     and table._replica_router.active)):
        # Warm jits AND drive hot-row promotion: the timed window
        # must measure the steady replicated state, not the
        # promotion ramp (the cap keeps a broken control plane
        # from wedging the phase; the result will show rate=None).
        for t in tables:
            t.get_rows(zipf_ids({draws}))
    mv.barrier()  # start line
    lat = []
    rows_got = ops = adds = 0
    inflight = []  # (table, msg_id, n_rows, issued_at) oldest first
    t0 = time.perf_counter()
    t_end = t0 + {window_s}
    slot = 0
    while time.perf_counter() < t_end:
        ids = zipf_ids({draws})
        t = tables[slot % POOL]
        slot += 1
        inflight.append((t, t.get_rows_async(ids), ids.size,
                         time.perf_counter()))
        if len(inflight) < POOL:
            continue
        t, mid, n_rows, issued = inflight.pop(0)
        t.wait(mid)
        lat.append((time.perf_counter() - issued) * 1e3)
        rows_got += n_rows
        ops += 1
        if ops % {add_every} == 0:  # write-through + RYW floors exercised
            aid = zipf_ids({add_draws})
            table.add_rows(aid,
                           np.full((aid.size, COLS), 1e-3, np.float32))
            adds += 1
    for t, mid, n_rows, issued in inflight:
        t.wait(mid)
        rows_got += n_rows
        ops += 1
    elapsed = time.perf_counter() - t0
    mv.barrier()  # exit line
    worker = mv.current_zoo()._actors.get(actors.WORKER)
    comm = mv.current_zoo()._actors.get(actors.COMMUNICATOR)
    lat.sort()
    pick = lambda p: round(lat[min(int(len(lat) * p / 100),
                                   len(lat) - 1)], 3) if lat else None
    out = {{'rank': rank, 'get_ops': ops, 'adds': adds,
            'elapsed': round(elapsed, 3),
            'rows_per_s': round(rows_got / elapsed, 1),
            'get_p50_ms': pick(50), 'get_p99_ms': pick(99),
            'reqs_by_dst': {{str(k): v for k, v
                             in worker.request_counts().items()}},
            'queue_depths': {{str(k): v for k, v
                              in comm.queue_depths().items()}},
            'dispatch_ms': {{str(d): samples('DISPATCH_MS[d{{}}]'
                                             .format(d)).snapshot()
                             for d in range(1, n)}},
            'repairs': Dashboard.get('REPLICA_REPAIR').count,
            'stale_groups': Dashboard.get('REPLICA_STALE').count}}
else:
    for _ in range(3):  # content / start / exit lines
        mv.barrier()
    out = {{'rank': rank,
            'server_gets': Dashboard.get('SERVER_PROCESS_GET').count,
            'replica_hit_rows': Dashboard.get('REPLICA_HIT').count,
            'replica_miss_rows': Dashboard.get('REPLICA_MISS').count,
            'replica_syncs': Dashboard.get('REPLICA_SYNC').count}}
faulthandler.cancel_dump_traceback_later()
print('SHARDRES', json.dumps(out), flush=True)
mv.barrier()
mv.shutdown()
"""


def _run_shard_point(tmp: str, n_servers: int, pace_mbps: float,
                     hot_rows: int, rows: int, cols: int,
                     zipf_a: float, draws: int, warm_s: float,
                     window_s: float, min_gets: int = 2,
                     sync_every: int = 8, add_every: int = 32,
                     add_draws: int = 8, pool: int = 4) -> dict:
    """One point of the N-server scale-out sweep: 1 worker + n_servers
    server processes on a paced localhost TCP mesh."""
    from multiverso_tpu.util.net_util import free_listen_port
    n = n_servers + 1
    mf = os.path.join(tmp, f"shard_mf_{n_servers}.txt")
    with open(mf, "w") as f:
        for p in [free_listen_port() for _ in range(n)]:
            f.write(f"127.0.0.1:{p}\n")
    code = _SHARD_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)), mf=mf,
        pace=pace_mbps, hot_rows=hot_rows, rows=rows, cols=cols,
        zipf_a=zipf_a, draws=draws, warm_s=warm_s, window_s=window_s,
        min_gets=min_gets, sync_every=sync_every, add_every=add_every,
        add_draws=add_draws, pool=pool)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(rank), str(n)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for rank in range(n)]
    results = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=600)
            if p.returncode:
                raise RuntimeError(f"shard child failed: {err[-300:]}")
            for line in out.splitlines():
                if line.startswith("SHARDRES "):
                    results.append(json.loads(line[9:]))
    finally:
        for p in procs:  # a raise must not orphan sibling ranks
            if p.poll() is None:
                p.kill()
                p.communicate()
    worker = next(r for r in results if r["rank"] == 0)
    servers = sorted((r for r in results if r["rank"] != 0),
                     key=lambda r: r["rank"])
    hits = sum(s["replica_hit_rows"] for s in servers)
    misses = sum(s["replica_miss_rows"] for s in servers)
    return {
        "n_servers": n_servers,
        "rows_per_s": worker["rows_per_s"],
        "get_p50_ms": worker["get_p50_ms"],
        "get_p99_ms": worker["get_p99_ms"],
        "get_ops": worker["get_ops"],
        "reqs_by_dst": worker["reqs_by_dst"],
        "dispatch_ms": worker["dispatch_ms"],
        "queue_depths": worker["queue_depths"],
        "repairs": worker["repairs"],
        "stale_groups": worker["stale_groups"],
        "per_server_gets": [s["server_gets"] for s in servers],
        "replica_hit_rows": hits,
        "replica_miss_rows": misses,
        "replica_hit_rate": round(hits / (hits + misses), 3)
        if hits + misses else None,
        "replica_syncs": sum(s["replica_syncs"] for s in servers),
    }


def run_ps_two_servers(prebuilt=None, tmp: str = None,
                       servers=(1, 2, 4)) -> dict:
    """N-server scale-out sweep (ISSUE 7 tentpole proof): 1 worker
    driving Zipf-skewed row Get/Add traffic against N in {1,2,4} server
    processes over the paced TCP transport (-net_pace_mbps emulates one
    DCN-speed link PER endpoint, so N servers = N independent wires —
    the deployment the sharded design is for; this box's single core
    cannot show device-side scaling). The old one-chip device-pipeline
    comparison this phase replaces measured broadcast physics (each
    server processed the full key set on ONE chip — 2 servers were 2x
    the device work) and could never reach 1.0x; docs/SHARDING.md
    records the analysis. The Zipf head is CLUSTERED in server 0's row
    range, as in word2vec's frequency-sorted vocabulary: without
    hot-shard replication the head's bytes all leave server 0's wire
    and siblings idle; with it (-replica_hot_rows) the head stripes
    across every server's wire. Reports per-server request counts,
    per-destination dispatch p50/p99 + queue depths, and the replica
    hit rate, so a future regression localizes itself from the bench
    record alone."""
    if tmp is None:
        tmp = tempfile.mkdtemp(prefix="mv_shard_")
    sweep = []
    for n_servers in servers:
        sweep.append(_run_shard_point(
            tmp, n_servers, pace_mbps=8.0, hot_rows=256,
            rows=4096, cols=512, zipf_a=1.6, draws=512,
            warm_s=4.0, window_s=6.0, min_gets=3, sync_every=4,
            add_every=64, pool=2))
    by_n = {point["n_servers"]: point for point in sweep}
    base = by_n.get(1, {}).get("rows_per_s")
    ratios = {n: round(point["rows_per_s"] / base, 3)
              for n, point in by_n.items()} if base else {}
    monotonic = all(
        by_n[a]["rows_per_s"] < by_n[b]["rows_per_s"]
        for a, b in zip(sorted(by_n), sorted(by_n)[1:]))
    return {"sweep": sweep,
            "scaling_vs_one_server": ratios,
            "monotonic_1_2_4": monotonic,
            "vs_single_same_window": ratios.get(2),
            "pace_mbps": 8.0, "replica_hot_rows": 256}


_ELASTIC_CHILD = r"""
import os, sys, time, json
import faulthandler
faulthandler.dump_traceback_later(360, exit=True)
# Host-only child: the parent bench process holds the chip, and a chip
# belongs to one process, so every spawned rank runs on the CPU.
import jax
jax.config.update('jax_platforms', 'cpu')
sys.path.insert(0, {repo!r})
import numpy as np
import multiverso_tpu as mv
rank = int(sys.argv[1]); n = int(sys.argv[2])
role = 'worker' if rank == 0 else 'server'
mv.init(['-machine_file=' + {mf!r}, '-rank=' + str(rank),
         '-ps_role=' + role, '-net_pace_mbps={pace}',
         '-shard_initial_servers=2', '-reshard_chunk_rows=256',
         '-heartbeat_interval_s=0.5', '-heartbeat_timeout_s=5',
         '-rpc_retry_max=8', '-rpc_backoff_ms=50'])
table = mv.create_matrix_table({rows}, {cols})
if rank != 0:
    # Servers idle until the worker's goodbye barrier.
    mv.barrier()
    mv.shutdown()
    sys.exit(0)
rng = np.random.default_rng(7)
expect = rng.standard_normal(({rows}, {cols})).astype(np.float32)
table.add(expect.copy())
shadow = expect


def window(label, seconds, reshard_to=None):
    '''Drive row Gets (verified element-wise) for a timed window;
    reshard_to fires MID-window so the transition itself is measured
    inside the window it claims to improve.'''
    t0 = time.perf_counter()
    rows_served = 0
    failed = wrong = 0
    resharded = reshard_to is None
    add_tick = 0
    while time.perf_counter() - t0 < seconds:
        if not resharded and time.perf_counter() - t0 > 1.0:
            resharded = True
            mv.current_zoo().reshard_table(table, reshard_to,
                                           wait_s=0)
        ids = np.sort(rng.choice({rows}, size={get_rows},
                                 replace=False)).astype(np.int32)
        try:
            got = table.get_rows(ids)
            if not np.allclose(got, shadow[ids], atol=1e-5):
                wrong += 1
            rows_served += ids.size
        except Exception:
            failed += 1
        add_tick += 1
        if add_tick % 16 == 0:
            # A few writes keep the dual-write window honest.
            aid = np.sort(rng.choice({rows}, size=8,
                                     replace=False)).astype(np.int32)
            d = np.ones((8, {cols}), np.float32) * 0.001
            try:
                table.add_rows(aid, d)
                shadow[aid] += d
            except Exception:
                failed += 1
    dt = time.perf_counter() - t0
    return dict(label=label, rows_per_s=round(rows_served / dt, 1),
                failed=failed, wrong=wrong,
                owners=table.shard_owner_sids(),
                epoch=table.shard_epoch())


out = []
out.append(window('w1_two_servers', {window_s}))
out.append(window('w2_grown', {window_s} + 8.0,
                  reshard_to=[0, 1, 2]))
out.append(window('w3_grown_steady', {window_s}))
out.append(window('w4_drained', {window_s} + 8.0,
                  reshard_to=[0, 1]))
faulthandler.cancel_dump_traceback_later()
print('ELASTICRES', json.dumps(out), flush=True)
mv.barrier()
mv.shutdown()
"""


def run_elastic(tmp: str = None) -> dict:
    """Elastic-resharding phase (ISSUE 12 acceptance,
    docs/SHARDING.md): 1 pure worker + 3 server processes on a paced
    localhost TCP mesh (8 Mbps per endpoint — each server owns its
    emulated DCN link). The table starts on 2 servers
    (-shard_initial_servers=2, server 2 a standby); mid-run the worker
    grows it onto all three with LIVE row migration and later drains
    back — while every read is verified element-wise against a shadow
    model. Acceptance: the grown steady-state moves more rows/s than
    the 2-server window (one extra paced link's worth), the drain
    converges back, and ZERO requests fail or return wrong values
    across both transitions."""
    if tmp is None:
        tmp = tempfile.mkdtemp(prefix="mv_elastic_")
    from multiverso_tpu.util.net_util import free_listen_port
    n = 4
    mf = os.path.join(tmp, "elastic_mf.txt")
    with open(mf, "w") as f:
        for p in [free_listen_port() for _ in range(n)]:
            f.write(f"127.0.0.1:{p}\n")
    code = _ELASTIC_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)), mf=mf,
        pace=8.0, rows=1024, cols=256, get_rows=64, window_s=6.0)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(rank), str(n)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for rank in range(n)]
    windows = None
    try:
        for p in procs:
            out, err = p.communicate(timeout=420)
            if p.returncode:
                raise RuntimeError(
                    f"elastic child failed: {err[-400:]}")
            for line in out.splitlines():
                if line.startswith("ELASTICRES "):
                    windows = json.loads(line[11:])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    if windows is None:
        raise RuntimeError("elastic worker never reported")
    by = {w["label"]: w for w in windows}
    failed = sum(w["failed"] for w in windows)
    wrong = sum(w["wrong"] for w in windows)
    grow_ratio = round(by["w3_grown_steady"]["rows_per_s"]
                       / max(by["w1_two_servers"]["rows_per_s"], 1e-9),
                       3)
    drain_ratio = round(by["w4_drained"]["rows_per_s"]
                        / max(by["w1_two_servers"]["rows_per_s"],
                              1e-9), 3)
    return {
        "windows": windows,
        "failed_requests": failed,
        "wrong_values": wrong,
        "grown_vs_two_servers": grow_ratio,
        "drained_vs_two_servers": drain_ratio,
        "grown_owner_sids": by["w3_grown_steady"]["owners"],
        "drained_owner_sids": by["w4_drained"]["owners"],
        # Acceptance: more links = more rows/s, zero failed/wrong
        # requests across both live transitions.
        "accept_grow_speedup": grow_ratio >= 1.15,
        "accept_zero_failed": failed == 0 and wrong == 0,
        "pace_mbps": 8.0,
    }


_TCP_CHILD = r"""
import os, sys, time, json
import faulthandler
# Self-report hangs (a mispaired barrier would otherwise wedge the
# whole phase silently); budget scales with the rank count since n
# processes time-share this host's one core, and is cancelled once the
# timed window ends — teardown must not be hard-killed on a slow run.
faulthandler.dump_traceback_later(420 + 180 * int(sys.argv[2]),
                                  exit=True)
# Host-only child: the parent bench process holds the chip, and a chip
# belongs to one process, so every spawned rank runs on the CPU.
import jax
jax.config.update('jax_platforms', 'cpu')
sys.path.insert(0, {repo!r})
from multiverso_tpu.util import compile_cache
compile_cache.enable()
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding import (
    BlockLoader, Dictionary, PSDeviceCorpusTrainer, PSWord2Vec,
    TokenizedCorpus, Word2VecConfig, iter_pair_batches)
rank = int(sys.argv[1]); n = int(sys.argv[2])
# Mixed-role deployment (the reference's -ps_role split): rank 0 is
# worker+server and — being co-located with EVERY shard — keeps the
# zero-copy device pipeline; other ranks are workers whose PS traffic
# crosses the TCP wire with host batches.
role = 'all' if rank == 0 else 'worker'
mv.init(['-machine_file=' + {mf!r}, '-rank=' + str(rank),
         '-ps_role=' + role])
d = Dictionary.load({dict_path!r})
config = Word2VecConfig(embedding_size={dim}, window=5, negative={neg},
                        epochs={epochs}, batch_size={batch},
                        sample=1e-3, use_ps=True, neg_block={neg_block})
model = PSWord2Vec(config, d)


def capped(seed, cap):
    for i, b in enumerate(iter_pair_batches(
            d, {corpus!r}, batch_size={batch}, window=5,
            subsample=1e-3, seed=seed)):
        if i >= cap:
            return
        yield b


# Barrier protocol — 5 per rank, IDENTICAL on both branches (both
# train calls end with one internal cluster barrier: train_epoch's
# epoch-end and train_batches' stream-end): warm-internal, start line,
# timed-internal, exit line, shutdown.
if model._device_path:
    tok = TokenizedCorpus.build(d, {corpus!r})
    trainer = PSDeviceCorpusTrainer(model, tok, 16384,
                                    blocks_per_dispatch=4)
    trainer.train_epoch(seed=99, max_steps=8)   # warm (barrier inside)
    mv.barrier()  # start line
    w0 = model.trained_words
    t0 = time.perf_counter()
    trainer.train_epoch(seed=0, max_steps={dev_blocks})  # barrier inside
    elapsed = time.perf_counter() - t0
else:
    model.train_batches(BlockLoader(model.prepared(capped(99, 4))))
    mv.barrier()  # start line
    w0 = model.trained_words
    t0 = time.perf_counter()
    model.train_batches(BlockLoader(model.prepared(
        capped(rank, {cap}))))   # ends with the stream barrier
    model._drain_pushes()
    elapsed = time.perf_counter() - t0
faulthandler.cancel_dump_traceback_later()
print('TCPRES', json.dumps({{'rank': rank, 'device': model._device_path,
                             'words': model.trained_words - w0,
                             'elapsed': elapsed}}), flush=True)
mv.barrier()
mv.shutdown()
"""


def run_tcp_processes(corpus: str, prebuilt, n: int, tmp: str,
                      cap: int = 24) -> dict:
    """Cross-process PS over the TCP transport (VERDICT r3 #4): n OS
    processes on a localhost machine-file mesh (the reference's ZMQ
    deployment, zmq_net.h:20-61): rank 0 is worker+server (keeping the
    device pipeline under the co-location rule), other ranks are
    workers on the CPU backend. NOTE this box has ONE CPU core — n
    processes time-share it, so aggregate words/s measures transport
    overhead, not scaling headroom."""
    from multiverso_tpu.util.net_util import free_listen_port
    dictionary, _ = prebuilt
    dict_path = os.path.join(tmp, "bench_dict.txt")
    if not os.path.exists(dict_path):
        dictionary.store(dict_path)
    mf = os.path.join(tmp, f"bench_mf_{n}.txt")
    with open(mf, "w") as f:
        # Fresh probed ports per run (free_listen_port scans below the
        # ephemeral range — deliberately NOT bind(0)-assigned, which
        # could be stolen before the child binds): a static port list
        # breaks the whole phase if any earlier crashed run left an
        # orphan holding one.
        for p in [free_listen_port() for _ in range(n)]:
            f.write(f"127.0.0.1:{p}\n")
    code = _TCP_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)), mf=mf,
        dict_path=dict_path, corpus=corpus, dim=DIM, neg=NEG,
        epochs=EPOCHS, batch=BATCH, neg_block=NEG_BLOCK, cap=cap,
        dev_blocks=48)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = [subprocess.Popen(
        [sys.executable, "-c", code, str(rank), str(n)],
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env=env) for rank in range(n)]
    results = []
    try:
        for p in procs:
            out, err = p.communicate(timeout=1200)
            if p.returncode:
                raise RuntimeError(f"tcp child failed: {err[-300:]}")
            for line in out.splitlines():
                if line.startswith("TCPRES "):
                    results.append(json.loads(line[7:]))
    finally:
        # A raise above (timeout, failed child) must not ORPHAN the
        # sibling ranks: they would keep time-sharing this host's one
        # core and holding their mesh ports for the rest of the bench.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    words = sum(r["words"] for r in results)
    elapsed = max(r["elapsed"] for r in results)
    return {"n_processes": n,
            "aggregate_wps": round(words / elapsed, 0),
            "per_rank_wps": [round(r["words"] / r["elapsed"], 0)
                             for r in results],
            "per_rank_device_path": [bool(r.get("device"))
                                     for r in results]}


def topic_separation(emb: np.ndarray, dictionary,
                     fetch_rows=None) -> float:
    """Within-band minus cross-band cosine similarity of the most
    frequent words of each topic band (quality signal; positive =
    embeddings learned the corpus structure). ``fetch_rows(ids)``
    fetches just the scored rows — a PS table's full-matrix download
    would ship the whole table over the host link for 48 rows."""
    half = VOCAB // 2
    per_band = 24
    band_a, band_b = [], []
    for word, wid in dictionary.word2id.items():
        raw = int(word[1:])
        (band_a if raw < half else band_b).append(wid)
        if len(band_a) >= per_band and len(band_b) >= per_band:
            break
    band_a, band_b = band_a[:per_band], band_b[:per_band]
    if fetch_rows is not None:
        rows = fetch_rows(np.array(band_a + band_b, np.int32))
        a, b = rows[:len(band_a)], rows[len(band_a):]
    else:
        a = emb[band_a]
        b = emb[band_b]
    a = a / np.maximum(np.linalg.norm(a, axis=1, keepdims=True), 1e-9)
    b = b / np.maximum(np.linalg.norm(b, axis=1, keepdims=True), 1e-9)
    within = ((a @ a.T).mean() + (b @ b.T).mean()) / 2
    across = (a @ b.T).mean()
    return within - across


def cpu_baseline(corpus: str) -> dict:
    """Identical fixed-seed run, host CPU backend, separate process —
    the LOSS PARITY twin (same code, same seeds, different backend).
    The performance baseline is ``cpp_baseline`` below."""
    code = (
        # The twin is a CPU run by definition, and a host-only child:
        # the parent holds the chip (one process per chip).
        "import jax; jax.config.update('jax_platforms','cpu')\n"
        "import json, bench\n"
        # Mirror the parent's effective constants so the fixed-seed runs
        # are bit-comparable.
        f"bench.VOCAB={VOCAB}; bench.SENTENCES={SENTENCES}\n"
        f"bench.EPOCHS={EPOCHS}; bench.BATCH={BATCH}\n"
        f"bench.DIM={DIM}; bench.NEG={NEG}\n"
        f"bench.MIN_COUNT={MIN_COUNT}\n"
        f"bench.NEG_BLOCK={NEG_BLOCK}\n"
        f"bench.LOCAL_CENTERS={LOCAL_CENTERS}\n"
        f"bench.LOCAL_DISPATCH={LOCAL_DISPATCH}\n"
        # ALL epochs (VERDICT r3 #8): the banded step cut the CPU twin's
        # per-epoch cost enough to afford the full fixed-seed run, so
        # loss parity covers every epoch, not just epoch 0.
        f"r = bench.run_local({corpus!r}, epochs={EPOCHS},"
        f" schedule_epochs={EPOCHS})\n"
        "print('RES', json.dumps({'wps': r['wps'],"
        " 'epoch_losses': r['epoch_losses']}))\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", code], cwd=os.path.dirname(
        os.path.abspath(__file__)), env=env, capture_output=True,
        text=True, timeout=3000)
    for line in out.stdout.splitlines():
        if line.startswith("RES "):
            return json.loads(line[4:])
    raise RuntimeError(f"cpu baseline failed: {out.stderr[-500:]}")


def cpp_baseline(corpus: str, tmp: str, dictionary) -> dict:
    """The honest CPU number to beat: a from-scratch C++ word2vec SGNS
    trainer (native/baseline/word2vec_baseline.cpp — OpenMP hogwild,
    sigmoid table, alias-method negatives; the style of the reference's
    hot loop, ref: Applications/WordEmbedding/src/wordembedding.cpp:
    95-125) run on the SAME corpus with the SAME hyperparameters and
    epochs. Returns its words/s plus the topic-separation quality of
    the embeddings it trained."""
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "native", "baseline",
                       "word2vec_baseline.cpp")
    binary = os.path.join(tmp, "w2v_baseline")
    subprocess.run(["g++", "-O3", "-march=native", "-fopenmp",
                    "-o", binary, src], check=True, capture_output=True)
    vec_path = os.path.join(tmp, "cpp_vectors.bin")
    out = subprocess.run(
        [binary, corpus, vec_path, str(EPOCHS), str(DIM), "5", str(NEG),
         "1e-3", "0.025", str(MIN_COUNT)],
        capture_output=True, text=True, timeout=3000, check=True)
    stats = json.loads(out.stdout.strip().splitlines()[-1])
    emb = np.fromfile(vec_path, dtype=np.float32).reshape(-1, DIM)
    with open(vec_path + ".words") as f:
        cpp_words = [line.rstrip("\n") for line in f]
    # Same vocab sort rules (count desc, then lexicographic) on both
    # sides — verify, then compare quality on identical word sets.
    assert cpp_words[:100] == dictionary.words[:100], \
        "C++ vocab order diverged from the framework dictionary"
    stats["topic_separation"] = round(
        float(topic_separation(emb, dictionary)), 4)
    return stats


def _dispatch_rtt_ms(iters: int) -> float:
    """Per-call dispatch + completion round trip for a tiny jitted op
    (scalar readback per call — the async pipeline would otherwise
    hide it); the float() readback is the sync."""
    import jax
    import jax.numpy as jnp
    tiny = jax.jit(lambda x: x + 1.0)
    s = tiny(jnp.float32(0))
    float(s)
    t0 = time.perf_counter()
    for _ in range(iters):
        s = tiny(s)
        float(s)
    return (time.perf_counter() - t0) / iters * 1e3


def _launch_overhead_samples(blocks: int, per_block: int) -> list:
    """Per-program launch cost: chained (no readback) executions still
    serialize device-side; each sample is one block's mean."""
    import jax
    import jax.numpy as jnp
    tiny = jax.jit(lambda x: x + 1.0)
    s = tiny(jnp.float32(0))
    float(s)
    samples = []
    for _ in range(blocks):
        t0 = time.perf_counter()
        for _ in range(per_block):
            s = tiny(s)
        float(s)
        samples.append((time.perf_counter() - t0) / per_block * 1e3)
    return samples


def _host_transfer_rates_mbps(n_floats: int) -> tuple:
    """(upload, download) MB/s between host memory and the device:
    warmed path, fresh bytes allocated OUTSIDE the timed window."""
    import jax.numpy as jnp
    probe = np.ones(n_floats, np.float32)
    float(jnp.asarray(probe)[0])  # warm the transfer path
    probe2 = probe * 2.0
    t0 = time.perf_counter()
    dev = jnp.asarray(probe2)
    float(dev[0])
    up = probe.nbytes / (time.perf_counter() - t0) / 1e6
    t0 = time.perf_counter()
    np.asarray(dev)
    down = probe.nbytes / (time.perf_counter() - t0) / 1e6
    return up, down


def weather_probe() -> dict:
    """A launch and transfer probe, ~10s, taken before any TIMED
    phase: the dispatch round trip of a tiny program, the per-program
    launch overhead and the host-to-device upload rate of the machine
    the run is on. Recorded first so even a truncated run carries its
    context (the matrix phase re-measures at the end with the same
    helpers)."""
    rtt_ms = _dispatch_rtt_ms(5)
    launch = _launch_overhead_samples(2, 20)
    up_mbps, _ = _host_transfer_rates_mbps(2 << 20)  # 8 MB
    return {"dispatch_roundtrip_ms": round(rtt_ms, 1),
            "program_launch_ms": round(float(np.median(launch)), 3),
            "host_upload_mbps": round(up_mbps, 1)}


def run_wire_codec() -> dict:
    """Pure-host codec phase: compression ratio + encode/decode GB/s on
    a canned power-law sparse gradient (the PS push/pull and ma-mode
    allreduce wire shape), against the REMOVED float64-pair encoding
    (16 B/surviving pair + an 8-byte size record) as the baseline."""
    from multiverso_tpu.util import wire_codec as wc
    rng = np.random.default_rng(7)
    n = 1 << 20  # 4 MB of fp32 — a realistic embedding-push blob
    nnz = n // 20  # 5% density, power-law magnitudes
    blob = np.zeros(n, np.float32)
    idx = np.sort(rng.choice(n, nnz, replace=False))
    blob[idx] = ((rng.pareto(2.0, nnz) + 0.1)
                 * np.sign(rng.standard_normal(nnz))).astype(np.float32)
    old_pair_bytes = 16 * nnz + 8  # float64 pairs + int64 size record

    out = {"blob_elements": n, "density": nnz / n,
           "old_float64_pair_bytes": old_pair_bytes}
    for label, lossy in (("lossless", False), ("lossy", True)):
        frame, _ = wc.encode_blob(blob, lossy=lossy)
        decoded = wc.decode_blob(frame)
        if not lossy:
            np.testing.assert_array_equal(decoded, blob)
        iters = 8
        t0 = time.perf_counter()
        for _ in range(iters):
            wc.encode_blob(blob, lossy=lossy)
        enc_s = (time.perf_counter() - t0) / iters
        t0 = time.perf_counter()
        for _ in range(iters):
            wc.decode_blob(frame)
        dec_s = (time.perf_counter() - t0) / iters
        out[label] = {
            "tier": wc.tier_name(wc.peek_tier(frame)),
            "wire_bytes": len(frame),
            "ratio_vs_float64_pairs": round(old_pair_bytes / len(frame), 3),
            "ratio_vs_raw": round(blob.nbytes / len(frame), 3),
            "encode_gbps": round(blob.nbytes / enc_s / 1e9, 3),
            "decode_gbps": round(blob.nbytes / dec_s / 1e9, 3),
        }
        if lossy:
            out[label]["max_abs_err"] = \
                round(float(np.abs(decoded - blob).max()), 6)
    return out


@flag_guarded
def _wire_pump(zero_copy: bool, n_msgs: int, rows: int,
               dims: int = 256, shm: bool = False) -> dict:
    """One arm of the ``zero_copy`` phase: large-blob PS-shaped traffic
    over loopback TCP — rank 0 streams ``n_msgs`` Get replies' worth of
    (rows x dims) fp32 payload to rank 1, which echoes each frame's
    blob straight back (the serving read shape: big payloads both
    directions, and the echo re-serializes RECEIVED view-backed blobs).
    Serialization — not the wire — dominates on loopback, which is
    exactly where the copy count shows. ``shm=True`` negotiates the
    pair onto shared-memory rings (docs/MEMORY.md "Below the socket"):
    same traffic, same counters, zero wire syscalls — slots sized so a
    whole frame fits one slot and the receive side parses in place.
    Returns rows/s and the measured copied-bytes-per-payload-byte off
    the WIRE_BYTES_COPIED / WIRE_PAYLOAD_BYTES counters."""
    import threading
    from multiverso_tpu.core.blob import Blob
    from multiverso_tpu.core.message import Message, MsgType
    from multiverso_tpu.runtime.tcp import TcpNet
    from multiverso_tpu.util.configure import set_flag
    from multiverso_tpu.util.dashboard import Dashboard
    from multiverso_tpu.util.net_util import free_listen_port

    set_flag("zero_copy", zero_copy)
    set_flag("buffer_pool_mb", 32 if zero_copy else 0)
    Dashboard.reset()
    eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
    nets = [TcpNet(r, eps) for r in range(2)]
    if shm:
        from multiverso_tpu.runtime.shm import ShmNet
        # 8 slots keeps the echo's in-flight window under the pin
        # valve (half the ring), so frames stay zero-copy end to end.
        set_flag("shm_ring_slots", 8)
        set_flag("shm_slot_kb", 8192)  # a 4 MB frame fits one slot
        nets = [ShmNet(n) for n in nets]
        for n in nets:
            n.enable_shm(0x6B3A, [1 - n.rank])
    try:
        payload = np.arange(rows * dims, dtype=np.float32)
        errs = []

        def echo():
            try:
                for _ in range(n_msgs):
                    msg = nets[1].recv(timeout=120)
                    assert msg is not None
                    reply = msg.create_reply_message()
                    reply.data = list(msg.data)  # re-send the view
                    nets[1].send(reply)
            except Exception as exc:  # noqa: BLE001 - surfaced below
                errs.append(exc)

        server = threading.Thread(target=echo, daemon=True)
        server.start()
        window = 4
        inflight = 0
        t0 = time.perf_counter()
        for i in range(n_msgs):
            msg = Message(src=0, dst=1, msg_type=MsgType.Request_Get,
                          msg_id=i)
            msg.push(Blob(payload))
            nets[0].send(msg)
            inflight += 1
            if inflight >= window:
                assert nets[0].recv(timeout=120) is not None
                inflight -= 1
        for _ in range(inflight):
            assert nets[0].recv(timeout=120) is not None
        elapsed = time.perf_counter() - t0
        server.join(timeout=30)
        assert not errs, errs
        copied = Dashboard.get("WIRE_BYTES_COPIED").count
        payload_bytes = Dashboard.get("WIRE_PAYLOAD_BYTES").count
        pool_hits = Dashboard.get("POOL_HIT").count
        pool_miss = Dashboard.get("POOL_MISS").count
        total_rows = n_msgs * rows * 2  # both directions
        out = {
            "rows_per_sec": round(total_rows / elapsed, 0),
            "payload_mb_per_sec": round(
                n_msgs * payload.nbytes * 2 / elapsed / 1e6, 1),
            "sec": round(elapsed, 4),
            "copied_bytes_per_payload_byte": round(
                copied / max(payload_bytes, 1), 6),
            "pool_hits": pool_hits, "pool_misses": pool_miss,
        }
        if shm:
            out["shm_frames"] = Dashboard.get("SHM_FRAMES").count
            out["shm_bytes_copied"] = \
                Dashboard.get("SHM_BYTES_COPIED").count
        return out
    finally:
        for n in nets:
            n.finalize()


def run_zero_copy() -> dict:
    """Zero-copy wire-path phase (docs/MEMORY.md): the scatter-gather +
    pooled-receive path vs the legacy join/tobytes baseline
    (``-zero_copy=0``) on the SAME traffic — large-blob PS echoes and a
    dense 2-rank ring allreduce over loopback TCP. Acceptance: the
    copied-bytes-per-payload-byte ratio drops >=2x and rows/s improves
    on the large-blob arm; frames stay byte-identical (the golden
    check below + tests/test_zero_copy.py)."""
    from multiverso_tpu.core.blob import Blob
    from multiverso_tpu.core.message import Message, MsgType
    from multiverso_tpu.runtime.tcp import _serialize, serialize_views

    # Inline golden proof on a representative frame: the two
    # serializers emit identical bytes, so the bench's two arms (and
    # mixed-build clusters) speak one wire format.
    probe = Message(src=0, dst=1, msg_type=MsgType.Request_Get,
                    msg_id=77)
    probe.push(Blob(np.arange(4096, dtype=np.float32)))
    probe.push(Blob(b"text payload"))
    views, nbytes = serialize_views(probe)
    flat = _serialize(probe)
    identical = b"".join(bytes(v) for v in views) == flat \
        and nbytes == len(flat)

    n_msgs, rows = 64, 4096  # 4 MB blobs: an embedding-table Get reply

    def best_of(arms):
        """Best-of-2 per arm: the pumps share one GIL with their echo
        threads, so single runs are scheduling-noisy; the max is the
        honest capability number for a throughput arm."""
        runs = [arms() for _ in range(2)]
        return max(runs, key=lambda r: r["rows_per_sec"])

    zc = best_of(lambda: _wire_pump(True, n_msgs, rows))
    base = best_of(lambda: _wire_pump(False, n_msgs, rows))
    out = {
        "frames_byte_identical": identical,
        "blob_mb": round(rows * 256 * 4 / 1e6, 2),
        "zero_copy": zc,
        "copy_baseline": base,
        "copied_ratio_improvement": round(
            base["copied_bytes_per_payload_byte"]
            / max(zc["copied_bytes_per_payload_byte"], 1e-9), 1),
        "rows_per_sec_speedup": round(
            zc["rows_per_sec"] / max(base["rows_per_sec"], 1), 3),
    }
    # Below the socket (docs/MEMORY.md): the same echo traffic with the
    # pair negotiated onto shared-memory rings. Acceptance: rows/s
    # >= 1.3x the loopback-TCP zero-copy arm, and shm_bytes_copied ~ 0
    # (single-slot frames parse in place on the receive side).
    from multiverso_tpu.runtime import shm as shm_mod
    if shm_mod.supported():
        with flag_guard():
            shm_echo = best_of(
                lambda: _wire_pump(True, n_msgs, rows, shm=True))
        out["shm_echo"] = shm_echo
        out["shm_rows_per_sec_speedup_vs_tcp"] = round(
            shm_echo["rows_per_sec"] / max(zc["rows_per_sec"], 1), 3)
    # Allreduce over loopback: the collective's segment frames ride the
    # same framer; dense 4 MB fp32, forced ring, codec on (RAW frames
    # pass the payload as a zero-copy view).
    with flag_guard():
        from multiverso_tpu.util.configure import set_flag
        set_flag("zero_copy", True)
        ar_zc = _allreduce_world(2, "ring", 0.0, False, "tcp", 1 << 20)
        ar_shm = None
        if shm_mod.supported():
            # Enough slots that the engine's out-of-order stash (its
            # pipelined segment window) stays under the pin valve.
            set_flag("shm_ring_slots", 16)
            set_flag("shm_slot_kb", 4096)
            ar_shm = _allreduce_world(2, "ring", 0.0, False, "shm",
                                      1 << 20)
        set_flag("zero_copy", False)
        set_flag("buffer_pool_mb", 0)
        ar_base = _allreduce_world(2, "ring", 0.0, False, "tcp", 1 << 20)
    out["allreduce"] = {
        "zero_copy": ar_zc, "copy_baseline": ar_base,
        "speedup": round(ar_base["sec"] / max(ar_zc["sec"], 1e-9), 3)}
    if ar_shm is not None:
        out["allreduce"]["shm"] = ar_shm
        out["allreduce"]["shm_speedup_vs_tcp"] = round(
            ar_zc["sec"] / max(ar_shm["sec"], 1e-9), 3)
    return out


@flag_guarded
def _allreduce_world(world: int, algo: str, pace_mbps: float,
                     lossy: bool, transport: str, n_elems: int,
                     reps: int = 2, fill: float = 1.0,
                     codec: bool = True, sharded: bool = False) -> dict:
    """One engine configuration: ``world`` thread-ranks allreducing a
    ``n_elems`` fp32 buffer, over LocalFabric or localhost TCP (paced
    to emulate the DCN wire); ``transport="shm"`` wraps the TCP mesh
    in the co-located shared-memory rings (runtime/shm.py). ``fill`` < 1 draws power-law sparse
    inputs (pareto magnitudes on a random support, the MA-delta wire
    shape); ``codec=False`` disables the wire codec — the dense-RAW
    baseline an MA round shipping full parameters pays; ``sharded``
    runs ``sharded_average`` instead (mean semantics). Returns best
    wall time + bytes on wire + the engine's algorithm pick and
    per-rank reduce-state bytes."""
    import threading
    from multiverso_tpu.runtime.allreduce_engine import AllreduceEngine
    from multiverso_tpu.runtime.net import LocalFabric
    from multiverso_tpu.util.configure import set_flag
    from multiverso_tpu.util.net_util import free_listen_port

    set_flag("allreduce_algo", algo)
    set_flag("allreduce_lossy", lossy)
    set_flag("net_pace_mbps", pace_mbps)
    set_flag("wire_codec", codec)
    nets = []
    try:
        if transport in ("tcp", "shm"):
            from multiverso_tpu.runtime.tcp import TcpNet
            eps = [f"127.0.0.1:{free_listen_port()}"
                   for _ in range(world)]
            # Construct INSIDE the try: a bind race on a freed port
            # must clean up the endpoints already built and surface
            # the real error, not a NameError from the finally.
            for r in range(world):
                nets.append(TcpNet(r, eps))
            if transport == "shm":
                from multiverso_tpu.runtime.shm import ShmNet
                nets = [ShmNet(n) for n in nets]
                for n in nets:
                    n.enable_shm(0x6B3A, [r for r in range(world)
                                          if r != n.rank])
        else:
            fabric = LocalFabric(world)
            nets = [fabric.endpoint(r) for r in range(world)]
        engines = [AllreduceEngine(n) for n in nets]
        rng = np.random.default_rng(11)
        if fill < 1.0:
            nnz = max(int(n_elems * fill), 1)
            inputs = []
            for _ in range(world):
                x = np.zeros(n_elems, np.float32)
                idx = np.sort(rng.choice(n_elems, nnz, replace=False))
                x[idx] = ((rng.pareto(2.0, nnz) + 0.1)
                          * np.sign(rng.standard_normal(nnz))
                          ).astype(np.float32)
                inputs.append(x)
        else:
            # Bounded dynamic range: int8-eligible, the shape of
            # normalized model-average deltas.
            inputs = [(np.sign(rng.standard_normal(n_elems))
                       * rng.uniform(0.5, 1.5, n_elems))
                      .astype(np.float32) for _ in range(world)]
        expected = np.sum([x.astype(np.float64) for x in inputs], axis=0)
        if sharded:
            expected = expected / world
        results = [None] * world
        best = float("inf")
        wire = 0

        def call(r):
            if sharded:
                return engines[r].sharded_average(inputs[r])
            return engines[r].allreduce(inputs[r])

        for _ in range(reps):
            before = sum(n.bytes_sent for n in nets)
            t0 = time.perf_counter()
            threads = [threading.Thread(
                target=lambda r=r: results.__setitem__(r, call(r)))
                for r in range(world)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=300)
                assert not t.is_alive(), "allreduce bench deadlocked"
            best = min(best, time.perf_counter() - t0)
            wire = sum(n.bytes_sent for n in nets) - before
        tol = 0.2 if lossy else 1e-3
        np.testing.assert_allclose(results[0], expected, rtol=tol,
                                   atol=tol)
        return {"sec": round(best, 4), "wire_mb": round(wire / 1e6, 3),
                "algo": engines[0].last_algo,
                "reduce_state_mb": round(
                    engines[0].last_reduce_state_bytes / 1e6, 3)}
    finally:
        # Flag restore is structural now (@flag_guarded).
        if transport in ("tcp", "shm"):
            for n in nets:
                n.finalize()


@flag_guarded
def _ma_overlap_stall(pace_mbps: float = 100.0) -> dict:
    """MACorpusTrainer sync vs overlap over a paced 2-rank TCP wire:
    same seeds, same schedule — bit-identical embeddings required —
    with MA_COMM_STALL recording how much of the communication the
    trainer actually waited on in each mode."""
    import threading
    import types
    from multiverso_tpu.models.wordembedding import (
        Dictionary, MACorpusTrainer, TokenizedCorpus, Word2Vec,
        Word2VecConfig)
    from multiverso_tpu.runtime.tcp import TcpNet
    from multiverso_tpu.util.configure import set_flag
    from multiverso_tpu.util.dashboard import Dashboard
    from multiverso_tpu.util.net_util import free_listen_port

    from multiverso_tpu.runtime import device_lock

    rng = np.random.default_rng(0)
    vocab = [f"w{i}" for i in range(2000)]
    lines = [" ".join(rng.choice(vocab, size=20)) for _ in range(400)]
    path = os.path.join(tempfile.mkdtemp(), "ma_corpus.txt")
    with open(path, "w") as f:
        f.write("\n".join(lines))
    d = Dictionary.build(path, min_count=1)
    tok = TokenizedCorpus.build(d, path)
    set_flag("allreduce_algo", "ring")
    # Pin the LOSSLESS contract explicitly: the bit-identical check
    # below is about sync-vs-overlap scheduling, and a lossy flag
    # leaked from an earlier phase would silently measure DENSE_F16
    # transfers instead.
    set_flag("allreduce_lossy", False)
    set_flag("net_pace_mbps", pace_mbps)
    # Two thread-ranks dispatch sharded trainer programs in one
    # process: serialize device work like LocalCluster does
    # (runtime/device_lock.py) so the bench can't hit the XLA CPU
    # pool wedge. Host-side comm (the thing measured) still overlaps.
    device_lock.enable()

    def run_mode(overlap: bool):
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        nets = [TcpNet(r, eps) for r in range(2)]
        mon = Dashboard.get("MA_COMM_STALL")
        stall0, count0 = mon.elapse, mon.count
        embs = [None, None]
        rounds = [0, 0]
        errs = [None, None]

        def body(rank):
            try:
                config = Word2VecConfig(
                    embedding_size=64, window=3, epochs=2,
                    init_learning_rate=0.02, batch_size=1024,
                    sample=0, negative=3, seed=17)
                model = Word2Vec(config, d)
                # avg_every=4 groups of 1024 centers: enough device
                # compute between averages to actually hide the ~80ms
                # the 1MB parameter allreduce spends on the paced wire.
                trainer = MACorpusTrainer(
                    model, tok, avg_every=4, overlap=overlap,
                    zoo=types.SimpleNamespace(net=nets[rank]),
                    centers_per_step=1024, steps_per_dispatch=1)
                for epoch in range(2):
                    trainer.train_epoch(seed=epoch)
                trainer.finish()
                embs[rank] = np.asarray(model._emb_in).copy()
                rounds[rank] = trainer.comm_rounds
            except BaseException as exc:  # noqa: BLE001
                errs[rank] = exc

        t0 = time.perf_counter()
        threads = [threading.Thread(target=body, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        hung = [t.name for t in threads if t.is_alive()]
        wall = time.perf_counter() - t0
        for n in nets:
            n.finalize()
        for exc in errs:
            if exc is not None:
                raise exc
        # A silently hung rank must fail the phase, not report
        # half-measured stalls (or compare two None embeddings as
        # bit-identical).
        assert not hung, f"ma trainer rank hung: {hung}"
        return {"stall_ms": round(mon.elapse - stall0, 1),
                "stall_samples": mon.count - count0,
                "wall_sec": round(wall, 2),
                "comm_rounds": rounds[0]}, embs

    try:
        sync, sync_embs = run_mode(False)
        over, over_embs = run_mode(True)
    finally:
        device_lock.disable()
        # Flag restore is structural now (@flag_guarded).
    identical = all(np.array_equal(sync_embs[r], over_embs[r])
                    for r in range(2))
    return {
        "emulated_wire_mbps": pace_mbps,
        "sync": sync, "overlap": over,
        "stall_reduction": round(
            sync["stall_ms"] / max(over["stall_ms"], 1e-3), 3),
        "bit_identical_sync_vs_overlap": identical,
    }


def _sparse_allreduce_points(n: int, pace: float,
                             dense_ring: dict) -> dict:
    """Sparse-stream tier points (docs/ALLREDUCE.md): power-law blobs
    at 1%/5%/20% fill on the same logical size, over the paced TCP
    wire. ``dense_ring`` is the ring on a DENSE payload of that size —
    its segments fail ``worth_encoding`` so every frame rides RAW: the
    bytes an MA round shipping full parameters pays today (the codec
    stays negotiated-on but inert; a future ``worth_encoding`` change
    that starts encoding dense payloads would shift this baseline's
    meaning). Also vs the ring WITH per-segment codec sparse encoding
    engaged on the same SPARSE payload (the strongest dense-path
    configuration). Plus the dense-input auto regression (the nnz
    probe is the only added cost) and the sharded-average
    reduce-state ratio."""
    out = {}
    for fill in (0.01, 0.05, 0.20):
        point = {}
        for world in (2, 3):
            sp = _allreduce_world(world, "auto", pace, False, "tcp", n,
                                  fill=fill)
            base = dense_ring[world]
            point[f"{world}rank"] = {
                **sp,
                "bytes_vs_dense_ring": round(
                    sp["wire_mb"] / base["wire_mb"], 4),
                "speedup_vs_dense_ring": round(
                    base["sec"] / sp["sec"], 3),
            }
        out[f"fill_{int(fill * 100)}pct"] = point
    # The strongest dense-path config on the same 5% payload: the ring
    # with per-segment sparse codec frames (partial sums still ride
    # every hop and densify; the sparse tier ships each contribution
    # once).
    out["ring_codec_5pct_3rank"] = _allreduce_world(
        3, "ring", pace, False, "tcp", n, fill=0.05)
    # Dense inputs above break-even: auto (probe + pick) vs forced
    # ring — the regression budget is 5%.
    auto_dense = _allreduce_world(3, "auto", pace, False, "tcp", n)
    out["dense_auto"] = {
        **auto_dense,
        "regression_vs_forced_ring": round(
            auto_dense["sec"] / dense_ring[3]["sec"], 3),
    }
    # Sharded average: per-rank reduce state ~ 1/world of the buffer.
    sh = _allreduce_world(3, "auto", 0.0, False, "local", n,
                          fill=0.05, sharded=True)
    out["sharded_avg_3rank"] = {
        **sh,
        "reduce_state_vs_buffer": round(
            sh["reduce_state_mb"] / (n * 4 / 1e6), 4),
    }
    return out


@flag_guarded
def _ma_sharded_arm(pace_mbps: float = 200.0) -> dict:
    """MACorpusTrainer sharded (delta-vs-last-average over the sparse
    sharded collective) vs the dense MA trainer on the same schedule,
    over a paced 2-rank TCP wire: bytes on wire, wall, measured delta
    fill, per-rank reduce-state — and the lossless bit-identity proof:
    the sharded run's embeddings equal the SAME delta schedule forced
    down the unchunked dense ring, bit for bit."""
    import threading
    import types
    from multiverso_tpu.models.wordembedding import (
        Dictionary, MACorpusTrainer, TokenizedCorpus, Word2Vec,
        Word2VecConfig)
    from multiverso_tpu.runtime.tcp import TcpNet
    from multiverso_tpu.runtime import device_lock
    from multiverso_tpu.util.configure import set_flag
    from multiverso_tpu.util.dashboard import Dashboard, samples
    from multiverso_tpu.util.net_util import free_listen_port

    rng = np.random.default_rng(3)
    # Zipf token draws over a wide vocabulary: each averaging round
    # touches only the rows its batches hit, so the delta is sparse —
    # the regime the sparse tier exists for.
    vocab = [f"w{i}" for i in range(12000)]
    probs = 1.0 / np.arange(1, len(vocab) + 1) ** 1.3
    probs /= probs.sum()
    lines = [" ".join(rng.choice(vocab, size=20, p=probs))
             for _ in range(700)]
    path = os.path.join(tempfile.mkdtemp(), "ma_sparse_corpus.txt")
    with open(path, "w") as f:
        f.write("\n".join(lines))
    d = Dictionary.build(path, min_count=1)
    tok = TokenizedCorpus.build(d, path)
    set_flag("net_pace_mbps", pace_mbps)
    set_flag("allreduce_lossy", False)
    device_lock.enable()

    def run_mode(sharded: bool, dense_ring_delta: bool = False):
        eps = [f"127.0.0.1:{free_listen_port()}" for _ in range(2)]
        nets = [TcpNet(r, eps) for r in range(2)]
        if dense_ring_delta:
            # Same delta schedule, dense collective: route
            # sharded_average through allreduce/n on the UNCHUNKED
            # ring (one chunk = the sharded fold's association).
            set_flag("allreduce_algo", "ring")
            set_flag("allreduce_chunk_kb", 1 << 20)
            for net in nets:
                net.sharded_average = types.MethodType(
                    lambda self, arr, slot=None:
                    self.allreduce(arr, slot) / self.size, net)
        else:
            set_flag("allreduce_algo", "auto")
        mon = Dashboard.get("MA_COMM_STALL")
        stall0 = mon.elapse
        embs = [None, None]
        errs = [None, None]
        rounds = [0, 0]

        def body(rank):
            try:
                config = Word2VecConfig(
                    embedding_size=64, window=2, epochs=1,
                    init_learning_rate=0.02, batch_size=1024,
                    sample=0, negative=2, seed=23)
                model = Word2Vec(config, d)
                trainer = MACorpusTrainer(
                    model, tok, avg_every=1, overlap=True,
                    sharded=sharded,
                    zoo=types.SimpleNamespace(net=nets[rank]),
                    centers_per_step=256, steps_per_dispatch=1)
                trainer.train_epoch(seed=0, max_steps=24)
                trainer.finish()
                embs[rank] = np.asarray(model._emb_in).copy()
                rounds[rank] = trainer.comm_rounds
            except BaseException as exc:  # noqa: BLE001
                errs[rank] = exc

        t0 = time.perf_counter()
        threads = [threading.Thread(target=body, args=(r,))
                   for r in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        hung = [t.name for t in threads if t.is_alive()]
        wall = time.perf_counter() - t0
        wire = sum(n.bytes_sent for n in nets)
        state = max(
            getattr(getattr(n, "_allreduce_engine", None),
                    "last_reduce_state_bytes", 0) for n in nets)
        for n in nets:
            n.finalize()
        for exc in errs:
            if exc is not None:
                raise exc
        assert not hung, f"ma trainer rank hung: {hung}"
        return {"wall_sec": round(wall, 2),
                "wire_mb": round(wire / 1e6, 2),
                "stall_ms": round(mon.elapse - stall0, 1),
                "comm_rounds": rounds[0],
                "reduce_state_mb": round(state / 1e6, 3)}, embs

    try:
        # Dense first: it pays the one-time trainer jit compile, so
        # the two delta arms (and their bit-identity) compare warm.
        dense_res, _ = run_mode(False)
        fill_s = samples("SPARSE_FILL[input]")
        fills_before = fill_s.count
        sharded_res, sharded_embs = run_mode(True)
        fills = fill_s.export_recent(
            max(fill_s.count - fills_before, 1))
        ring_res, ring_embs = run_mode(True, dense_ring_delta=True)
    finally:
        device_lock.disable()
        # Flag restore is structural now (@flag_guarded).
    identical = all(np.array_equal(sharded_embs[r], ring_embs[r])
                    for r in range(2))
    params_mb = sharded_embs[0].size * 2 * 4 / 1e6  # emb_in + emb_out
    return {
        "emulated_wire_mbps": pace_mbps,
        "model_params_mb": round(params_mb, 2),
        "sharded_sparse": sharded_res,
        "dense_ma": dense_res,
        "delta_dense_ring": ring_res,
        "wire_reduction_vs_dense_ma": round(
            dense_res["wire_mb"] / max(sharded_res["wire_mb"], 1e-6),
            3),
        "stall_reduction_vs_dense_ma": round(
            dense_res["stall_ms"] / max(sharded_res["stall_ms"], 1e-3),
            3),
        "note": "dense_ma runs first and absorbs the one-time trainer "
                "jit compile in wall_sec; wire/stall compare cleanly",
        "median_delta_fill": round(float(np.median(fills)), 4)
        if fills else None,
        "reduce_state_vs_params": round(
            sharded_res["reduce_state_mb"] / params_mb, 4),
        "bit_identical_sharded_vs_dense_ring_delta": identical,
    }


@flag_guarded
def run_allreduce() -> dict:
    """Collective-stack phase: chunked pipelined ring vs monolithic
    recursive halving, lossless vs int8 error-feedback, on a 4 MB fp32
    buffer at 2 and 3 ranks, in-process and over localhost TCP paced to
    DCN-class rates; plus the MA trainer sync-vs-overlap stall
    comparison. All ranks share this host's single core, so in-process
    and codec-CPU numbers UNDERSTATE the multi-host win."""
    n = 2 << 20  # 8 MB fp32 (acceptance floor is >= 4 MB)
    pace = 200.0  # an emulated wire well under localhost; stable
    # against this host's scheduler noise (one core for everything)
    out = {"buffer_mb": round(n * 4 / 1e6, 1),
           "emulated_wire_mbps": pace,
           "note": "single-core host: every rank, writer thread and "
                   "codec pass time-shares one core"}
    dense_ring = {}
    for world in (2, 3):
        mono = _allreduce_world(world, "rhalving", pace, False,
                                "tcp", n)
        ring = _allreduce_world(world, "ring", pace, False,
                                "tcp", n)
        dense_ring[world] = ring
        ring_i8 = _allreduce_world(world, "ring", pace, True,
                                   "tcp", n)
        local = {
            "monolithic": _allreduce_world(world, "rhalving", 0.0,
                                           False, "local", n),
            "ring": _allreduce_world(world, "ring", 0.0, False,
                                     "local", n)}
        out[f"tcp_{world}rank"] = {
            "monolithic_rhalving": mono,
            "chunked_ring": ring,
            "chunked_ring_int8": ring_i8,
            "ring_speedup": round(mono["sec"] / ring["sec"], 3),
            "int8_wire_reduction": round(
                ring["wire_mb"] / ring_i8["wire_mb"], 3),
            "int8_speedup": round(mono["sec"] / ring_i8["sec"], 3),
        }
        out[f"inprocess_{world}rank"] = local
    # A slow emulated wire (100 Mbps): where the int8 byte cut
    # dominates the codec CPU cost outright.
    slow_mono = _allreduce_world(3, "rhalving", 100.0, False,
                                 "tcp", n, reps=1)
    slow_i8 = _allreduce_world(3, "ring", 100.0, True, "tcp", n,
                               reps=1)
    out["tcp_3rank_100mbps"] = {
        "monolithic_rhalving": slow_mono,
        "chunked_ring_int8": slow_i8,
        "int8_speedup": round(slow_mono["sec"] / slow_i8["sec"], 3),
    }
    # Headline numbers the acceptance criteria read.
    out["ring_speedup"] = out["tcp_3rank"]["ring_speedup"]
    out["int8_wire_reduction"] = \
        out["tcp_3rank"]["int8_wire_reduction"]
    # Sparse-stream tier points + the sharded MA arm
    # (docs/ALLREDUCE.md sparse tier; acceptance: 5% fill bytes
    # <= 0.25x / speedup >= 1.5x vs the dense ring, dense auto
    # regression <= 5%, reduce-state ~ 1/world).
    out["sparse"] = _sparse_allreduce_points(n, pace, dense_ring)
    out["sparse_bytes_vs_dense_ring"] = \
        out["sparse"]["fill_5pct"]["3rank"]["bytes_vs_dense_ring"]
    out["sparse_speedup_vs_dense_ring"] = \
        out["sparse"]["fill_5pct"]["3rank"]["speedup_vs_dense_ring"]
    out["ma_sharded"] = _ma_sharded_arm()
    out["ma_overlap"] = _ma_overlap_stall()
    return out


def utilization(pairs_per_sec: float, centers_per_sec: float,
                window: int = 5) -> dict:
    """Achieved FLOP/s and HBM bytes/s for the BANDED SGNS step vs chip
    peaks.

    Per valid pair (D = DIM): pos dot fwd+bwd = 6*D. Negatives are
    drawn per BLOCK of NEG_BLOCK centers (K per block, logits per
    center): 6*D*K per center. ``centers_per_sec`` is the exact
    post-subsampling token rate tracked by the trainer. Bytes (banded
    form): per center ~(2 + K/NEG_BLOCK) rows touched (v + band +
    shared negs), each gathered once (read) and scatter-added once
    (read+write) = 3 * D * 4 bytes per row."""
    import jax
    kind = jax.devices()[0].device_kind.lower()
    peaks = [p for key, p in _CHIP_PEAKS.items() if key in kind]
    if not peaks:
        # A device that is not in the table is an error, not a default:
        # a utilization against another chip's peaks is a wrong number.
        raise ValueError(
            f"no peak FLOP/s and HBM bytes/s recorded for device_kind "
            f"{kind!r}; add it to _CHIP_PEAKS with its source")
    flops_peak, hbm_peak = peaks[0]
    achieved_flops = 6 * DIM * (pairs_per_sec + NEG * centers_per_sec)
    achieved_bytes = centers_per_sec * 3 * (2 + NEG / NEG_BLOCK) \
        * DIM * 4
    # Elementwise logit/grad formation over the band: per window offset
    # the step reads a [C, D] band slice and the [C, D] center rows
    # (forward) and re-reads both plus writes grads (backward) — ~6
    # HBM passes per offset IF none of it stays resident in VMEM. An
    # upper-bound model, reported separately from the hard gather/
    # scatter floor (XLA may fuse much of it).
    elementwise_bytes = centers_per_sec * 6 * (2 * window) * DIM * 4
    return {
        "device_kind": kind,
        "achieved_tflops": round(achieved_flops / 1e12, 4),
        "mfu": round(achieved_flops / flops_peak, 6),
        "achieved_gbps": round(achieved_bytes / 1e9, 2),
        "hbm_utilization": round(achieved_bytes / hbm_peak, 4),
        "elementwise_gbps_upper_bound": round(elementwise_bytes / 1e9,
                                              2),
        "hbm_utilization_with_elementwise": round(
            (achieved_bytes + elementwise_bytes) / hbm_peak, 4),
    }


def step_decomposition(local: dict, matrix: dict) -> dict:
    """MEASURED wall-clock decomposition of the banded local step
    (VERDICT r4 weak #4): convert the step's known row traffic into
    time shares using the SAME-RUN microbench rates (slope-timed
    scatter/gather GB/s, per-program launch ms) — the remainder is
    elementwise compute + XLA overhead. Fractions of 1s of wall."""
    cps = local["centers_per_sec"]
    rows_per_center = 2 + NEG / NEG_BLOCK  # v + band + shared negs
    gather_Bps = cps * rows_per_center * DIM * 4
    scatter_Bps = cps * rows_per_center * DIM * 4 * 2  # read+write
    out = {"note": "fraction of each wall-clock second attributed by "
                   "measured microbench rates; residual = elementwise "
                   "compute + fusion + XLA overhead"}
    sg = matrix.get("scatter_32k_rows_gbps")
    gg = matrix.get("gather_256k_rows_gbps")
    lm = matrix.get("program_launch_ms")
    total = 0.0
    if sg:
        out["scatter_frac"] = round(scatter_Bps / (sg * 1e9), 4)
        total += out["scatter_frac"]
    if gg:
        out["gather_frac"] = round(gather_Bps / (gg * 1e9), 4)
        total += out["gather_frac"]
    if lm and local.get("groups_per_sec"):
        out["launch_frac"] = round(
            local["groups_per_sec"] * lm / 1e3, 4)
        total += out["launch_frac"]
    out["residual_frac"] = round(max(1.0 - total, 0.0), 4)
    return out


def run_client_cache() -> dict:
    """Client-cache phase: repeated power-law row-Get workload (the
    wordembedding access shape, SparCML's observation) through the full
    PS stack, cached vs uncached, plus the trainer-shaped prefetch
    double-buffer. Reports hit rate, effective Get throughput, and the
    per-step pull-stall with and without prefetch. Acceptance: >=1.5x
    effective Get throughput on the hot-row workload."""
    import multiverso_tpu as mv
    from multiverso_tpu.util.configure import set_flag

    num_row, num_col, per_batch = 1 << 15, 64, 256
    pool, passes = 80, 3  # epoch-style: the pool repeats, as a
    #   trainer's working set does across epochs
    staleness = 24  # versions are per SHARD (any add ages every
    #   entry), so the bound must cover the ~10 adds-per-pass x the
    #   passes between revisits of a pool batch
    rng = np.random.default_rng(11)
    ranks = np.arange(1, num_row + 1)
    probs = 1.0 / ranks  # Zipf(1.0) row popularity
    probs /= probs.sum()
    batches = [np.unique(rng.choice(num_row, size=per_batch,
                                    p=probs)).astype(np.int32)
               for _ in range(pool)]
    stream = batches * passes
    hot = np.unique(rng.choice(256, size=64)).astype(np.int32)

    def warm(table):
        """One untimed pass: identical in BOTH arms, so jit/bucket
        compiles never contaminate the timed window (the cached arm
        additionally enters the timed window populated — the steady
        state the phase measures)."""
        for ids in batches:
            table.get_rows(ids)

    def workload(table):
        """Timed Get stream with periodic hot-row adds riding along
        (every 24 gets), so invalidation/re-population is priced in.
        Each add is followed by the idiomatic recovery prefetch of the
        rows it dirtied (one async roundtrip restores them for every
        later Get; a no-op in the uncached arm, so both arms run the
        identical call sequence)."""
        t0 = time.perf_counter()
        for i, ids in enumerate(stream):
            table.get_rows(ids)
            if i % 24 == 23:
                table.add_rows(hot, np.ones((hot.size, num_col),
                                            np.float32))
                table.prefetch_rows_async(hot)
        return time.perf_counter() - t0

    def trainer_shaped(table, prefetch):
        """Double-buffer stand-in: prefetch batch i+1, 'compute' 2 ms
        (simulated device step), then pull batch i; returns the mean
        pull-stall only (the compute sleep is constant across arms)."""
        stall = 0.0
        steps = min(60, len(stream))
        for i in range(steps):
            if prefetch and i + 1 < steps:
                table.prefetch_rows_async(stream[i + 1])
            time.sleep(0.002)
            t0 = time.perf_counter()
            table.get_rows(stream[i])
            stall += time.perf_counter() - t0
        return stall / steps

    out = {"num_row": num_row, "num_col": num_col,
           "batch_pool": pool, "passes": passes,
           "rows_per_get": per_batch, "max_get_staleness": staleness}

    mv.init([])  # default flags: cache disabled
    table = mv.create_matrix_table(num_row, num_col)
    table.add_rows(batches[0], np.ones((batches[0].size, num_col),
                                       np.float32))
    warm(table)
    uncached = workload(table)
    stall_plain = trainer_shaped(table, prefetch=False)
    mv.shutdown()

    with flag_guard():  # flag state survives shutdown/init cycles —
        # a leak (even via a mid-phase exception, which _Result.run
        # swallows) would turn the cache on for every later phase's
        # default-flag numbers. The guard restores EVERY flag.
        mv.init([])
        set_flag("max_get_staleness", staleness)  # before table creation
        table = mv.create_matrix_table(num_row, num_col)
        table.add_rows(batches[0], np.ones((batches[0].size, num_col),
                                           np.float32))
        warm(table)
        before = dict(table._row_cache.stats)
        cached = workload(table)
        after = table._row_cache.stats
        timed_hits = after["hits"] - before["hits"]
        timed_total = timed_hits + after["misses"] - before["misses"]
        stall_prefetch = trainer_shaped(table, prefetch=True)
        mv.shutdown()

    timed_rows_hit = after["rows_hit"] - before["rows_hit"]
    timed_rows = timed_rows_hit + after["rows_missed"] \
        - before["rows_missed"]
    out.update(
        hit_rate=round(timed_hits / max(timed_total, 1), 4),
        row_hit_rate=round(timed_rows_hit / max(timed_rows, 1), 4),
        uncached_gets_per_sec=round(len(stream) / uncached, 1),
        cached_gets_per_sec=round(len(stream) / cached, 1),
        effective_get_speedup=round(uncached / cached, 3),
        stall_ms_per_step_no_prefetch=round(stall_plain * 1e3, 3),
        stall_ms_per_step_prefetch=round(stall_prefetch * 1e3, 3),
        prefetch_stall_reduction=round(
            stall_plain / max(stall_prefetch, 1e-9), 3))
    return out


@flag_guarded
def run_server_fusion() -> dict:
    """Server-side request fusion phase (runtime/fusion.py;
    docs/SERVER_ENGINE.md): three client ranks hammer ONE server with
    a Zipf(1.6) Get/Add row mix — the multi-client shape where the
    server mailbox actually backs up — over the co-located shm rings
    and over paced localhost TCP, with fusion off (-server_fuse_max=1)
    vs on (16). Each server dispatch is paced by an emulated
    launch cost (the device twin of -net_pace_mbps; this 1-core host's
    ~40us CPU launches would otherwise drown the fixed cost fusion
    amortizes in thread-scheduling noise). Reports rows/s, device
    dispatches per 1k requests, fused-batch p50/p99, cross-request
    dedup rows, and a post-run bit-identity check of a deterministic
    read against the fusion-off arm. Acceptance: >=1.5x rows/s
    fused-on and a >=3x dispatch cut on at least one transport."""
    import multiverso_tpu as mv
    from multiverso_tpu.runtime import shm as shm_mod
    from multiverso_tpu.runtime.cluster import LocalCluster
    from multiverso_tpu.runtime.tcp import TcpNet
    from multiverso_tpu.util.configure import set_flag
    from multiverso_tpu.util.dashboard import Dashboard, samples
    from multiverso_tpu.util.net_util import free_listen_port

    world, num_row, num_col = 3, 1 << 12, 32
    iters, per_get, window, pace_mbps = 256, 16, 32, 150.0
    # Per-dispatch launch pacing: this host's XLA CPU launches in
    # ~40us; the fixed cost fusion amortizes is the device's
    # per-dispatch launch cost (program_launch_ms / launch_big_ms,
    # measured elsewhere in this bench; not measured on the current
    # machine), emulated here at 2 ms. Sleeping
    # launch_ms inside each server dispatch is the device twin of
    # -net_pace_mbps emulating the DCN wire; both arms pay it per
    # PROGRAM, so the ratio isolates exactly the dispatch-count cut.
    launch_ms = 2.0
    ranks = np.arange(1, num_row + 1, dtype=np.float64)
    probs = ranks ** -1.6  # Zipf(1.6): hot heads => cross-request
    probs /= probs.sum()   # duplicate rows for the fused-Get dedup
    n_requests = world * (iters + iters // 8)

    def body(rank):
        # Windowed async-add pipeline (the trainer push shape) with a
        # sync Get every 4th step riding the backlog: clients keep
        # streaming while the server drains, so the serial arm pays
        # one dispatch per message at full mailbox pressure. The
        # client Get register allows only ONE Get in flight per
        # table, so the depth fusion feeds on comes from the add
        # window — 3 clients x window deep.
        from collections import deque
        rng = np.random.default_rng(101 + rank)
        table = mv.create_matrix_table(num_row, num_col, np.float32)
        if rank == 0:
            # Rank 0 hosts the server table ("all" role, registered
            # inline by create): pace its two dispatch sites with the
            # emulated launch cost (see launch_ms above). The
            # sleep sits where the real launch stall sits — inside
            # the server's table-locked dispatch — and releases the
            # GIL, exactly like a host thread blocked on a launch.
            stab = mv.current_zoo()._server_tables[0]
            real_gather = stab._gather
            real_apply = stab._engine.apply_rows

            def paced_gather(*a):
                time.sleep(launch_ms / 1e3)
                return real_gather(*a)

            def paced_apply(*a, **kw):
                time.sleep(launch_ms / 1e3)
                return real_apply(*a, **kw)

            stab._gather = paced_gather
            stab._engine.apply_rows = paced_apply
        batches = [np.unique(rng.choice(num_row, size=per_get,
                                        p=probs)).astype(np.int32)
                   for _ in range(iters)]
        delta = np.ones((per_get, num_col), np.float32)
        mv.current_zoo().barrier()
        t0 = time.perf_counter()
        rows = 0
        pend = deque()
        for i, ids in enumerate(batches):
            pend.append(table.add_rows_async(ids, delta[:ids.size]))
            rows += int(ids.size)
            if len(pend) >= window:
                table.wait(pend.popleft())
            if i % 8 == 7:
                table.get_rows(ids)
                rows += int(ids.size)
        for msg_id in pend:
            table.wait(msg_id)
        elapsed = time.perf_counter() - t0
        mv.current_zoo().barrier()
        # Post-barrier deterministic read: every client's adds are
        # acked, so the table state is a fixed function of the seeds
        # — the fused arm must reproduce it BIT-identically.
        final = np.array(
            table.get_rows(np.arange(256, dtype=np.int32)), copy=True)
        mv.current_zoo().barrier()
        return elapsed, rows, final

    def arm(transport: str, fuse_max: int) -> dict:
        # Pacing must be set BEFORE TcpNet construction (the writer
        # loop samples the flag once at connect).
        set_flag("net_pace_mbps", pace_mbps if transport == "tcp"
                 else 0.0)
        nets = []
        try:
            eps = [f"127.0.0.1:{free_listen_port()}"
                   for _ in range(world)]
            for r in range(world):
                nets.append(TcpNet(r, eps))
            if transport == "shm":
                from multiverso_tpu.runtime.shm import ShmNet
                nets = [ShmNet(n) for n in nets]
                for n in nets:
                    n.enable_shm(0x51F5, [r for r in range(world)
                                          if r != n.rank])
            disp0 = Dashboard.get("SERVER_DEVICE_DISPATCHES").count
            dedup0 = Dashboard.get("SERVER_FUSE_DEDUP_ROWS").count
            batch_mon = samples("SERVER_FUSE_BATCH")
            batch0 = batch_mon.snapshot()["count"]
            cluster = LocalCluster(
                world, argv=[f"-server_fuse_max={fuse_max}"],
                roles=["all", "worker", "worker"], nets=nets)
            cluster.timeout = 240.0
            res = cluster.run(body)
            disp = Dashboard.get("SERVER_DEVICE_DISPATCHES").count \
                - disp0
            dedup = Dashboard.get("SERVER_FUSE_DEDUP_ROWS").count \
                - dedup0
            fused_batches = batch_mon.snapshot()["count"] - batch0
            # This arm's batch sizes only: the monitor is process-
            # global and the serial arm ran before us.
            recent = batch_mon.export_recent(fused_batches) \
                if fused_batches else []
            sec = max(e for e, _, _ in res)
            rows = sum(r for _, r, _ in res)
            out = {"sec": round(sec, 4),
                   "final": res[0][2],
                   "rows_per_sec": round(rows / max(sec, 1e-9), 1),
                   "device_dispatches": disp,
                   "dispatches_per_1k_requests": round(
                       disp * 1000.0 / n_requests, 1),
                   "fused_batches": fused_batches,
                   "dedup_rows": dedup}
            if recent:
                out["fused_batch_p50"] = float(
                    np.percentile(recent, 50))
                out["fused_batch_p99"] = float(
                    np.percentile(recent, 99))
            return out
        finally:
            for n in nets:  # idempotent: Zoo.stop finalizes the nets
                n.finalize()  # it started; this covers setup failures

    out = {"world": world, "clients": world, "num_row": num_row,
           "num_col": num_col, "rows_per_get": per_get,
           "iters_per_client": iters, "zipf_alpha": 1.6,
           "tcp_pace_mbps": pace_mbps,
           "emulated_launch_ms": launch_ms}
    def best_of(transport: str, fuse_max: int, reps: int = 2) -> dict:
        # Best-of-N: every virtual rank time-shares this host's single
        # core, so one unlucky scheduler quantum can swing an arm far
        # more than the effect under measurement.
        runs = [arm(transport, fuse_max) for _ in range(reps)]
        return max(runs, key=lambda r: r["rows_per_sec"])

    transports = ["tcp"] + (["shm"] if shm_mod.supported() else [])
    for transport in transports:
        serial = best_of(transport, 1)
        fused = best_of(transport, 16)
        identical = bool(np.array_equal(serial.pop("final"),
                                        fused.pop("final")))
        out[transport] = {
            "fuse_off": serial, "fuse_on": fused,
            "rows_per_sec_speedup": round(
                fused["rows_per_sec"]
                / max(serial["rows_per_sec"], 1e-9), 3),
            "dispatch_cut": round(
                serial["dispatches_per_1k_requests"]
                / max(fused["dispatches_per_1k_requests"], 1e-9), 2),
            "gets_bit_identical": identical}
    return out


@flag_guarded
def run_observability() -> dict:
    """Tracing-overhead phase (docs/OBSERVABILITY.md): the PS matrix
    Get hot path at -trace_sample_rate off / 1% / 100%, identical call
    sequences, reporting rows/s per arm. 'Off' runs twice so the
    repeat delta exposes the platform noise floor the comparisons sit
    on; the per-request cost of the disabled sampling hook is also
    microbenched directly, giving a structural upper bound on what the
    off path adds vs a pre-trace build (acceptance: <= 1%)."""
    import multiverso_tpu as mv
    from multiverso_tpu.util import tracing
    from multiverso_tpu.util.configure import set_flag

    num_row, num_col, per_batch, n_gets = 1 << 14, 32, 256, 480
    rng = np.random.default_rng(7)
    stream = [np.unique(rng.integers(0, num_row, size=per_batch))
              .astype(np.int32) for _ in range(n_gets)]

    out = {"num_row": num_row, "num_col": num_col,
           "rows_per_get": per_batch, "gets_per_arm": n_gets}
    mv.init([])
    try:
        table = mv.create_matrix_table(num_row, num_col)
        table.add_rows(stream[0], np.ones((stream[0].size, num_col),
                                          np.float32))
        for ids in stream[:40]:  # warm: compiles + buckets out of
            table.get_rows(ids)  # every timed window

        def arm(rate):
            set_flag("trace_sample_rate", rate)
            tracing.reset()
            rows = 0
            t0 = time.perf_counter()
            for ids in stream:
                table.get_rows(ids)
                rows += ids.size
            dt = time.perf_counter() - t0
            return rows / dt, len(tracing.snapshot_events())

        off, _ = arm(0.0)
        off2, _ = arm(0.0)       # repeat: the noise floor
        one_pct, ev1 = arm(0.01)
        full, ev100 = arm(1.0)

        # Structural off-path bound: the ONLY work the disabled layer
        # adds per request vs a pre-trace build is the sampling hook
        # (one flag read) + inert span checks; microbench the hook and
        # scale by the measured request rate.
        reps = 20000
        t0 = time.perf_counter()
        for _ in range(reps):
            tracing.new_trace(0)
        hook_ns = (time.perf_counter() - t0) / reps * 1e9
        # ~4 hook-class checks per get (issue + shard + reply + notify)
        off_bound = (hook_ns * 4e-9) * (off / per_batch)
    finally:
        # Flag restore is structural now (@flag_guarded).
        tracing.reset()
        mv.shutdown()
    out.update(
        off_rows_per_sec=round(off, 1),
        off_repeat_rows_per_sec=round(off2, 1),
        one_pct_rows_per_sec=round(one_pct, 1),
        full_rows_per_sec=round(full, 1),
        noise_floor=round(abs(off - off2) / max(off, off2), 4),
        overhead_one_pct=round(max(off, off2) / one_pct - 1, 4),
        overhead_full=round(max(off, off2) / full - 1, 4),
        events_at_one_pct=ev1, events_at_full=ev100,
        sampling_hook_ns=round(hook_ns, 1),
        off_overhead_bound=round(off_bound, 6),
        accept_off_overhead_le_1pct=bool(off_bound <= 0.01))
    return out


@flag_guarded
def run_serving() -> dict:
    """Serving-tier phase (docs/SERVING.md): Zipf(1.6) HTTP QPS
    against the online serving frontend while a trainer thread
    concurrently pushes Adds into the same table — the ROADMAP item 4
    'training + serving system' proof. Two arms over identical
    request streams:

    - NORMAL: default admission knobs; reports p50/p99 latency, QPS,
      rows/s, cache hit rate (request-level and row-granular, overall
      + on the Zipf head), shed rate (expected ~0), and
      staleness-bound violations (must be 0).
    - OVERLOAD: the per-endpoint in-flight cap is dropped to 1 and
      twice the client threads hammer with no backoff; the frontend
      must shed (429 + Retry-After on every shed) while the p99 of
      ACCEPTED requests stays bounded — load shedding IS the latency
      defense, so p99 must not collapse.

    Clients hold keep-alive connections (http.client over the
    frontend's HTTP/1.1) — the inference-client shape, and without it
    the TCP handshake per request IS the benchmark. Acceptance: head
    row-granular cache coverage >= 0.9 (the trainer deliberately
    dirties the head, so request-level all-rows-fresh hits are
    reported but not gated), every shed carries Retry-After, zero
    staleness violations, and overload p99 of accepted requests <=
    max(10x normal p99, 250 ms)."""
    import http.client
    import json
    import threading

    import multiverso_tpu as mv
    from multiverso_tpu.serving.frontend import ServingFrontend
    from multiverso_tpu.util.configure import set_flag

    num_row, num_col = 4096, 32
    staleness, head_n, per_req = 16, 16, 6
    out = {"num_row": num_row, "num_col": num_col,
           "max_get_staleness": staleness, "zipf_a": 1.6,
           "head_rows": head_n, "ids_per_request": per_req}

    mv.init([])
    set_flag("max_get_staleness", staleness)
    try:
        table = mv.create_matrix_table(num_row, num_col)
        rng = np.random.default_rng(5)
        table.add(rng.standard_normal((num_row, num_col))
                  .astype(np.float32))
        frontend = ServingFrontend(mv.current_zoo(), port=0,
                                   host="127.0.0.1")
        frontend.register_table("emb", table)

        stop = threading.Event()
        adds = [0]

        def trainer():
            """Concurrent write load: Zipf-shaped Adds (the word2vec
            delta pattern — head-heavy, so the trainer keeps dirtying
            exactly the rows users read most) with the idiomatic
            recovery prefetch of the dirtied rows
            (docs/CLIENT_CACHE.md)."""
            trng = np.random.default_rng(17)
            while not stop.is_set():
                ids = np.unique((trng.zipf(1.6, 16) - 1) % num_row) \
                    .astype(np.int32)
                table.add_rows(ids, np.full((ids.size, num_col), 1e-4,
                                            np.float32))
                table.prefetch_rows_async(ids)
                adds[0] += 1
                time.sleep(0.02)

        def _new_arm():
            return {"lock": threading.Lock(), "lat": [], "rows": 0,
                    "hits": 0, "misses": 0, "rows_req": 0,
                    "rows_cached": 0, "head_total": 0, "head_hits": 0,
                    "head_rows_req": 0, "head_rows_cached": 0,
                    "shed": 0, "shed_no_retry_after": 0,
                    "staleness_violations": 0}

        def client(seed, n, arm):
            """One keep-alive inference client: Zipf(1.6) row reads,
            sheds counted (and their Retry-After checked), accepted
            responses checked for the staleness invariant."""
            crng = np.random.default_rng(seed)
            conn = http.client.HTTPConnection("127.0.0.1",
                                              frontend.port,
                                              timeout=60)
            try:
                for _ in range(n):
                    ids = np.unique((crng.zipf(1.6, per_req) - 1)
                                    % num_row)
                    path = ("/v1/tables/emb/rows?ids="
                            + ",".join(str(i) for i in ids))
                    t0 = time.perf_counter()
                    conn.request("GET", path)
                    resp = conn.getresponse()
                    body = resp.read()  # always: keep-alive reuse
                    if resp.status in (429, 503):
                        with arm["lock"]:
                            arm["shed"] += 1
                            if resp.getheader("Retry-After") is None:
                                arm["shed_no_retry_after"] += 1
                        continue
                    assert resp.status == 200, (resp.status, body)
                    doc = json.loads(body)
                    lat_ms = (time.perf_counter() - t0) * 1e3
                    head = bool(ids.max() < head_n)
                    with arm["lock"]:
                        arm["lat"].append(lat_ms)
                        arm["rows"] += int(ids.size)
                        arm["hits" if doc["cache_hit"]
                            else "misses"] += 1
                        arm["rows_req"] += doc["rows_requested"]
                        arm["rows_cached"] += doc["rows_cached"]
                        if head:
                            arm["head_total"] += 1
                            arm["head_hits"] += int(doc["cache_hit"])
                            arm["head_rows_req"] += \
                                doc["rows_requested"]
                            arm["head_rows_cached"] += \
                                doc["rows_cached"]
                        if doc["max_staleness"] > \
                                doc["staleness_bound"]:
                            arm["staleness_violations"] += 1
            finally:
                conn.close()

        def run_arm(n_threads, n_per, seed0):
            arm = _new_arm()
            threads = [threading.Thread(target=client,
                                        args=(seed0 + i, n_per, arm))
                       for i in range(n_threads)]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
            lat = sorted(arm["lat"])

            def pick(p):
                return round(lat[min(int(len(lat) * p / 100),
                                     len(lat) - 1)], 3) if lat else None
            served = arm["hits"] + arm["misses"]
            total = served + arm["shed"]
            return {
                "requests": total, "served": served,
                "elapsed_s": round(elapsed, 3),
                "qps": round(total / elapsed, 1),
                "rows_per_s": round(arm["rows"] / elapsed, 1),
                "p50_ms": pick(50), "p99_ms": pick(99),
                "hit_rate": round(arm["hits"] / max(served, 1), 4),
                "row_hit_rate": round(
                    arm["rows_cached"] / max(arm["rows_req"], 1), 4),
                "head_requests": arm["head_total"],
                "head_hit_rate": round(
                    arm["head_hits"] / max(arm["head_total"], 1), 4),
                "head_row_hit_rate": round(
                    arm["head_rows_cached"]
                    / max(arm["head_rows_req"], 1), 4),
                "shed": arm["shed"],
                "shed_rate": round(arm["shed"] / max(total, 1), 4),
                "shed_without_retry_after":
                    arm["shed_no_retry_after"],
                "staleness_violations": arm["staleness_violations"]}

        trainer_thread = threading.Thread(target=trainer, daemon=True)
        trainer_thread.start()
        # Warm: gather-bucket compiles out of the timed window, cache
        # populated to steady state (the state a serving replica runs
        # in; cold-start is the client_cache phase's story).
        for k in (4, 8, 16, 32, 64):
            table.get_rows(np.linspace(0, num_row - 1, k)
                           .astype(np.int32))
        client(99, 120, _new_arm())

        normal = run_arm(n_threads=3, n_per=200, seed0=100)
        # Deliberate overload: one admitted request at a time, twice
        # the clients, zero client backoff. Restore whatever cap the
        # controller actually ran with (flag-sourced — a hand-copied
        # constant here would drift from the canonical default).
        prior_inflight = frontend.admission.stats()["max_inflight"]
        frontend.admission.configure(max_inflight=1)
        overload = run_arm(n_threads=6, n_per=100, seed0=200)
        frontend.admission.configure(max_inflight=prior_inflight)
        stop.set()
        trainer_thread.join(timeout=10)
        out["adds_during_run"] = adds[0]
        out["admission"] = frontend.admission.stats()
        drain_t0 = time.perf_counter()
        frontend.stop()
        out["drain_s"] = round(time.perf_counter() - drain_t0, 3)
    finally:
        # Flag restore is structural now (@flag_guarded).
        mv.shutdown()

    p99_bound_ms = max(10 * (normal["p99_ms"] or 0.0), 250.0)
    out.update(
        normal=normal, overload=overload,
        accept_head_hit_rate_ge_090=bool(
            normal["head_row_hit_rate"] >= 0.9),
        accept_overload_sheds=bool(overload["shed"] > 0),
        accept_sheds_carry_retry_after=bool(
            overload["shed_without_retry_after"] == 0
            and normal["shed_without_retry_after"] == 0),
        accept_zero_staleness_violations=bool(
            normal["staleness_violations"] == 0
            and overload["staleness_violations"] == 0),
        overload_p99_bound_ms=round(p99_bound_ms, 3),
        accept_overload_p99_accepted_bounded=bool(
            overload["p99_ms"] is not None
            and overload["p99_ms"] <= p99_bound_ms))
    return out


@flag_guarded
def run_autotune() -> dict:
    """Closed-loop self-tuning phase (docs/AUTOTUNE.md): the ps-matrix
    Zipf read/write workload and the HTTP serving workload, each run
    under three configurations over identical request streams:

    - DEFAULT: all-default flags, no controller — the baseline a
      fresh cluster starts from;
    - HAND-TUNED: the best known static configuration
      (-max_get_staleness=24, the client_cache/serving phases'
      tuning) pinned before table creation;
    - ADAPTIVE: all-default flags plus the controller
      (-metrics_interval_s + -autotune_interval_s): per-rank metric
      reports feed ClusterMetrics, the AutotuneManager's policies
      widen the knobs via epoch-stamped Control_Config broadcasts,
      and the dynamic-flag layer's apply hooks land them on the LIVE
      table and frontend.

    Correctness is checked WHILE the knobs move: a same-thread
    read-your-writes probe after every hot-row add (the served value
    must reflect the just-acked write exactly), and the serving
    staleness invariant on every response. Acceptance: the adaptive
    run converges to >= 0.95x the hand-tuned static configuration on
    both workloads with zero violations, and the decision trajectory
    (mv_autotune_*) is present in /metrics and recorded here."""
    import http.client
    import threading

    import multiverso_tpu as mv
    from multiverso_tpu.runtime import actor as actors
    from multiverso_tpu.serving.frontend import ServingFrontend
    from multiverso_tpu.util.configure import get_flag, set_flag

    num_row, num_col, per_batch = 1 << 14, 32, 192
    pool, hand_staleness = 64, 24
    rng = np.random.default_rng(23)
    ranks = np.arange(1, num_row + 1)
    probs = 1.0 / ranks  # Zipf(1.0) row popularity
    probs /= probs.sum()
    batches = [np.unique(rng.choice(num_row, size=per_batch,
                                    p=probs)).astype(np.int32)
               for _ in range(pool)]
    hot = np.unique(rng.choice(256, size=64)).astype(np.int32)
    # Init rows exclude the hot set so the RYW probe's expected value
    # is exactly the number of acked hot adds (all cols move by 1).
    init_rows = np.setdiff1d(batches[0], hot).astype(np.int32)

    def matrix_workload(table, seconds, adds_so_far, ryw):
        """TIME-BOUNDED Zipf Get stream with periodic hot-row adds
        riding along (the client_cache phase's shape). Time-bounded,
        not pass-bounded: one pass over the pool takes ~70 ms on this
        host, far inside its ±20% scheduler noise — a multi-second
        window averages it out, and keeps the metrics stream hot for
        the whole autotune decision cadence. After every acked add the
        SAME THREAD re-reads a hot-row slice and checks the value
        reflects the write exactly — read-your-writes must hold at
        whatever staleness bound is live. Returns (rows/s, adds)."""
        rows = 0
        i = 0
        t0 = time.perf_counter()
        deadline = t0 + seconds
        while time.perf_counter() < deadline:
            table.get_rows(batches[i % pool])
            rows += batches[i % pool].size
            i += 1
            if i % 24 == 0:
                table.add_rows(hot, np.ones((hot.size, num_col),
                                            np.float32))
                adds_so_far += 1
                probe = table.get_rows(hot[:8])
                if not np.allclose(probe, float(adds_so_far)):
                    ryw[0] += 1
                table.prefetch_rows_async(hot)
        return rows / (time.perf_counter() - t0), adds_so_far

    def serving_workload(port, n_threads, n_per, seed0):
        """Keep-alive Zipf(1.6) HTTP clients against /rows; returns
        qps / p99 / staleness violations / request-level hit rate."""
        lock = threading.Lock()
        acc = {"lat": [], "hits": 0, "served": 0, "violations": 0,
               "shed": 0}

        def client(seed, n):
            crng = np.random.default_rng(seed)
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=60)
            try:
                for _ in range(n):
                    ids = np.unique((crng.zipf(1.6, 6) - 1) % num_row)
                    path = ("/v1/tables/emb/rows?ids="
                            + ",".join(str(i) for i in ids))
                    t0 = time.perf_counter()
                    conn.request("GET", path)
                    resp = conn.getresponse()
                    body = resp.read()
                    if resp.status in (429, 503):
                        with lock:
                            acc["shed"] += 1
                        continue
                    assert resp.status == 200, (resp.status, body)
                    doc = json.loads(body)
                    lat = (time.perf_counter() - t0) * 1e3
                    with lock:
                        acc["lat"].append(lat)
                        acc["served"] += 1
                        acc["hits"] += int(bool(doc["cache_hit"]))
                        if doc["max_staleness"] > \
                                doc["staleness_bound"]:
                            acc["violations"] += 1
            finally:
                conn.close()

        threads = [threading.Thread(target=client,
                                    args=(seed0 + i, n_per))
                   for i in range(n_threads)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        lat = sorted(acc["lat"])
        return {
            "qps": round((acc["served"] + acc["shed"]) / elapsed, 1),
            "p50_ms": round(lat[len(lat) // 2], 3) if lat else None,
            "p99_ms": round(lat[min(int(len(lat) * 0.99),
                                    len(lat) - 1)], 3) if lat else None,
            "hit_rate": round(acc["hits"] / max(acc["served"], 1), 4),
            "shed": acc["shed"],
            "staleness_violations": acc["violations"]}

    def run_arm(static_flags, autotune):
        """One full configuration: matrix workload then serving
        workload in a single cluster lifetime, all flags restored on
        exit (flag_guard)."""
        arm = {}
        with flag_guard():
            for k, v in static_flags.items():
                set_flag(k, v)
            if autotune:
                set_flag("metrics_interval_s", 0.2)
                set_flag("autotune_interval_s", 0.3)
            mv.init([])
            try:
                zoo = mv.current_zoo()
                table = mv.create_matrix_table(num_row, num_col)
                table.add_rows(init_rows,
                               np.ones((init_rows.size, num_col),
                                       np.float32))
                ryw = [0]
                adds = 0
                for ids in batches:  # warm: compiles + buckets out of
                    table.get_rows(ids)  # every timed window
                if autotune:
                    # Convergence window (untimed): keep the workload
                    # hot while the controller widens the knobs from
                    # live ClusterMetrics. Settled = the staleness
                    # policy VERDICT reads "hold" at a nonzero bound
                    # for two consecutive passes — i.e. the controller
                    # itself judges the knob at its operating point
                    # (miss rate absorbed), not merely between
                    # cooldown steps. An intermediate bound is the
                    # worst regime (cache bookkeeping with no hits),
                    # so timing before the verdict settles would
                    # measure the transition, not the steady state.
                    mgr = zoo._actors[actors.CONTROLLER].autotune
                    deadline = time.monotonic() + 30.0
                    settled = 0
                    while time.monotonic() < deadline and settled < 2:
                        _, adds = matrix_workload(table, 1.0, adds,
                                                  ryw)
                        gauge = mgr.gauges().get(
                            "max_get_staleness", {})
                        # "hold" = the POLICY judged the knob at its
                        # operating point under live traffic ("idle"
                        # windows don't count; "up"/"down" means
                        # still stepping or cooling down).
                        held = (gauge.get("verdict") == "hold"
                                and get_flag("max_get_staleness") > 0)
                        settled = settled + 1 if held else 0
                    arm["converged_staleness"] = int(
                        get_flag("max_get_staleness"))
                matrix_rows_s, adds = matrix_workload(table, 4.0,
                                                      adds, ryw)
                arm["matrix_rows_per_s"] = round(matrix_rows_s, 1)
                arm["ryw_violations"] = ryw[0]

                frontend = ServingFrontend(zoo, port=0,
                                           host="127.0.0.1")
                frontend.register_table("emb", table)
                stop = threading.Event()

                def trainer():
                    trng = np.random.default_rng(17)
                    while not stop.is_set():
                        ids = np.unique((trng.zipf(1.6, 16) - 1)
                                        % num_row).astype(np.int32)
                        table.add_rows(
                            ids, np.full((ids.size, num_col), 1e-4,
                                         np.float32))
                        table.prefetch_rows_async(ids)
                        time.sleep(0.02)

                trainer_thread = threading.Thread(target=trainer,
                                                  daemon=True)
                trainer_thread.start()
                serving_workload(frontend.port, 1, 60, 900)  # warm
                arm["serving"] = serving_workload(
                    frontend.port, 3, 250, 1000)
                stop.set()
                trainer_thread.join(timeout=10)

                if autotune:
                    controller = zoo._actors.get(actors.CONTROLLER)
                    mgr = controller.autotune
                    arm["trajectory"] = mgr.trajectory()
                    arm["gauges"] = mgr.gauges()
                    arm["config_epoch"] = mgr.epoch
                    arm["acked_epochs"] = {
                        str(r): e
                        for r, e in mgr.acked_epochs().items()}
                    arm["final_knobs"] = {
                        k: get_flag(k)
                        for k in ("max_get_staleness",
                                  "serving_batch_window_ms",
                                  "coalesce_max_msgs")}
                    # Scrape-surface proof: the EXACT /metrics
                    # composition the zoo serves on -metrics_port,
                    # fetched over real HTTP (ephemeral port).
                    from multiverso_tpu.io.metrics_http import (
                        MetricsHttpServer, prometheus_route)
                    scrape = MetricsHttpServer(0, {
                        "/metrics": prometheus_route(
                            lambda: controller.metrics
                            .prometheus_text()
                            + mgr.prometheus_text())},
                        host="127.0.0.1")
                    try:
                        conn = http.client.HTTPConnection(
                            "127.0.0.1", scrape.port, timeout=10)
                        conn.request("GET", "/metrics")
                        text = conn.getresponse().read().decode()
                        conn.close()
                    finally:
                        scrape.stop()
                    arm["metrics_scrape"] = {
                        "autotune_gauge_lines": sum(
                            1 for line in text.splitlines()
                            if line.startswith("mv_autotune_")),
                        "has_config_epoch":
                            "mv_autotune_config_epoch" in text,
                        "has_knob_values":
                            'mv_autotune_value{knob=' in text}
                frontend.stop()
            finally:
                mv.shutdown()
        return arm

    out = {"num_row": num_row, "num_col": num_col,
           "rows_per_get": per_batch, "batch_pool": pool,
           "hand_tuned_staleness": hand_staleness}
    out["default_static"] = run_arm({}, autotune=False)
    out["hand_tuned"] = run_arm(
        {"max_get_staleness": hand_staleness}, autotune=False)
    out["adaptive"] = run_arm({}, autotune=True)

    tuned, adaptive = out["hand_tuned"], out["adaptive"]
    out.update(
        adaptive_vs_hand_tuned_matrix=round(
            adaptive["matrix_rows_per_s"]
            / max(tuned["matrix_rows_per_s"], 1e-9), 3),
        adaptive_vs_hand_tuned_qps=round(
            adaptive["serving"]["qps"]
            / max(tuned["serving"]["qps"], 1e-9), 3),
        adaptive_vs_default_matrix=round(
            adaptive["matrix_rows_per_s"]
            / max(out["default_static"]["matrix_rows_per_s"], 1e-9),
            3),
        accept_matrix_ge_095x_hand_tuned=bool(
            adaptive["matrix_rows_per_s"]
            >= 0.95 * tuned["matrix_rows_per_s"]),
        accept_qps_ge_095x_hand_tuned=bool(
            adaptive["serving"]["qps"]
            >= 0.95 * tuned["serving"]["qps"]),
        accept_zero_violations_while_tuning=bool(
            adaptive["ryw_violations"] == 0
            and adaptive["serving"]["staleness_violations"] == 0),
        accept_trajectory_in_metrics=bool(
            len(adaptive.get("trajectory") or []) > 0
            and adaptive["metrics_scrape"]["has_config_epoch"]
            and adaptive["metrics_scrape"]["has_knob_values"]))
    return out


_FLEET_CHILD = '''
import sys, threading, time
sys.path.insert(0, {repo!r})
import os
# Host-only child: the parent bench process holds the chip, and a chip
# belongs to one process, so every spawned rank runs on the CPU.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import numpy as np
import multiverso_tpu as mv

rank, n = int(sys.argv[1]), int(sys.argv[2])
role, serving_port = sys.argv[3], int(sys.argv[4])
argv = ["-machine_file=" + {mf!r}, "-rank=" + str(rank),
        "-ps_role=" + role, "-serving_fleet_interval_s=0.5"]
argv += list(sys.argv[5:])  # arm-specific flags from the parent
if serving_port:
    argv.append("-serving_port=" + str(serving_port))
mv.init(argv)
NUM_ROW, NUM_COL = {num_row}, {num_col}
table = mv.create_matrix_table(NUM_ROW, NUM_COL)
if table is not None:
    if rank == 1:
        # Deterministic integer-valued base: the parent recomputes it
        # and verifies every served row against the legal-value rule
        # (cols 1+ untouched, col 0 = base + integer add count).
        base = (np.arange(NUM_ROW)[:, None] % 50
                + np.arange(NUM_COL)[None, :]).astype(np.float32)
        table.add_rows(np.arange(NUM_ROW, dtype=np.int32), base)
    mv.barrier()
    mv.serve_table("emb", table)
    # Warm the gather buckets out of the measured window (requests
    # carry up to ~8 unique rows -> power-of-two buckets 1..16, and
    # the scatter path splits per owner, so small widths occur too).
    for k in (1, 2, 3, 4, 6, 8, 12, 16):
        table.get_rows(np.linspace(0, NUM_ROW - 1, k)
                       .astype(np.int32))
    stop = threading.Event()
    adds = [0]

    def trainer():
        rng = np.random.default_rng(100 + rank)
        while not stop.is_set():
            ids = np.unique((rng.zipf(1.6, 8) - 1)
                            % NUM_ROW).astype(np.int32)
            delta = np.zeros((ids.size, NUM_COL), np.float32)
            delta[:, 0] = 1.0
            table.add_rows(ids, delta)
            adds[0] += 1
            time.sleep(0.02)

    t = threading.Thread(target=trainer, daemon=True)
    t.start()
    print("READY", serving_port, flush=True)
    while True:
        line = sys.stdin.readline()
        if line.startswith("SAMPLE"):
            # Self-reported thread census for the many-connection arm
            # (Python 3.10 does not propagate thread names to /proc
            # comm, so the parent cannot count roles from outside).
            from multiverso_tpu.runtime import thread_roles as tr
            alive = tr.roles_alive()
            print("THREADS", threading.active_count(),
                  alive.get(tr.EVENTLOOP, 0) + alive.get(tr.WRITER, 0),
                  flush=True)
            continue
        break
    stop.set()
    t.join(timeout=10)
    print("ADDS", adds[0], flush=True)
else:
    mv.barrier()
    print("READY 0", flush=True)
    sys.stdin.readline()
mv.shutdown()
print("DONE", flush=True)
'''


_FLEET_CLIENT = '''
import json, sys, time
import http.client
import numpy as np

port, seed, n_reqs = (int(v) for v in sys.argv[1:4])
ids_per_req, zipf_a = int(sys.argv[4]), float(sys.argv[5])
NUM_ROW, NUM_COL = {num_row}, {num_col}
base = (np.arange(NUM_ROW)[:, None] % 50
        + np.arange(NUM_COL)[None, :]).astype(np.float32)
crng = np.random.default_rng(seed)
out = {{"lat": [], "served": 0, "shed": 0,
       "staleness_violations": 0, "wrong_values": 0, "hits": 0,
       "rows_req": 0, "rows_cached": 0, "response_cache_hits": 0,
       "errors": []}}
conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
t_start = time.perf_counter()
for _ in range(n_reqs):
    ids = np.unique((crng.zipf(zipf_a, ids_per_req) - 1) % NUM_ROW)
    path = "/v1/tables/emb/rows?ids=" \\
        + ",".join(str(i) for i in ids)
    t0 = time.perf_counter()
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    if resp.status in (429, 503):
        out["shed"] += 1
        continue
    if resp.status != 200:
        out["errors"].append([resp.status, body[:200].decode(
            errors="replace")])
        continue
    doc = json.loads(body)
    out["lat"].append((time.perf_counter() - t0) * 1e3)
    out["served"] += 1
    # Legal-value rule: cols 1+ untouched by the trainer, col 0 =
    # base + integer add count. A stale/torn/misrouted row cannot
    # pass.
    for row_id, row in zip(doc["ids"], doc["rows"]):
        row = np.asarray(row, np.float64)
        if not np.array_equal(row[1:], base[row_id][1:]):
            out["wrong_values"] += 1
            continue
        delta = row[0] - base[row_id][0]
        if delta < -1e-6 or abs(delta - round(delta)) > 1e-3:
            out["wrong_values"] += 1
    out["hits"] += int(bool(doc["cache_hit"]))
    out["rows_req"] += doc["rows_requested"]
    out["rows_cached"] += doc["rows_cached"]
    out["response_cache_hits"] += int(
        doc.get("response_cache") == "hit")
    if doc["max_staleness"] > doc["staleness_bound"]:
        out["staleness_violations"] += 1
out["elapsed"] = time.perf_counter() - t_start
conn.close()
print("CLIENTRES " + json.dumps(out), flush=True)
'''


def _fleet_sweep_arm(n_frontends: int, tmp: str, num_row: int = 4096,
                     num_col: int = 32, clients: int = 8,
                     reqs_per_client: int = 150,
                     child_flags=("-max_get_staleness=16",),
                     ids_per_req: int = 6, zipf_a: float = 1.6,
                     label: str = "") -> dict:
    """One multi-process fleet point: rank 0 = server + controller,
    ranks 1..N = worker frontends (each its own OS process and GIL —
    the real fleet shape). The HTTP clients are their OWN processes
    too (one synchronous keep-alive connection each, spread across
    the frontends), so the measurement is never capped by a shared
    client-side GIL; every response is checked for the staleness
    invariant AND the legal-value rule (cols 1+ must equal the
    deterministic base exactly; col 0 must exceed it by a
    non-negative INTEGER — the trainer only ever adds +1.0 there), so
    a torn/stale/misrouted row can never pass."""
    from multiverso_tpu.util.net_util import free_listen_port

    n = n_frontends + 1
    mf = os.path.join(tmp, f"fleet_mf_{n_frontends}{label}.txt")
    with open(mf, "w") as f:
        for p in [free_listen_port() for _ in range(n)]:
            f.write(f"127.0.0.1:{p}\n")
    serving_ports = [free_listen_port() for _ in range(n_frontends)]
    code = _FLEET_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)), mf=mf,
        num_row=num_row, num_col=num_col)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    for rank in range(n):
        role = "server" if rank == 0 else "worker"
        port = 0 if rank == 0 else serving_ports[rank - 1]
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code, str(rank), str(n),
             role, str(port), *child_flags],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env))
    client_code = _FLEET_CLIENT.format(num_row=num_row,
                                       num_col=num_col)

    fleet_doc = None
    try:
        for p in procs:  # all ranks up and serving; log INFO lines
            while True:  # share the pipe with the READY marker
                line = p.stdout.readline()
                if not line:
                    # Child died before READY; stderr is safe to
                    # drain only because the process has exited.
                    p.wait(timeout=30)
                    raise RuntimeError(
                        f"fleet child exited rc={p.returncode}: "
                        f"{p.stderr.read()[-400:]}")
                if line.startswith("READY"):
                    break
        client_procs = []
        t0 = time.perf_counter()
        for i in range(clients):
            port = serving_ports[i % n_frontends]
            client_procs.append(subprocess.Popen(
                [sys.executable, "-c", client_code, str(port),
                 str(1000 + i), str(reqs_per_client),
                 str(ids_per_req), str(zipf_a)],
                stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                text=True, env=env))
        stats = {"lat": [], "served": 0, "shed": 0,
                 "staleness_violations": 0, "wrong_values": 0,
                 "hits": 0, "rows_req": 0, "rows_cached": 0,
                 "response_cache_hits": 0, "errors": [],
                 "client_qps": []}
        for p in client_procs:
            out, err = p.communicate(timeout=600)
            if p.returncode:
                raise RuntimeError(
                    f"fleet client failed: {err[-400:]}")
            doc = None
            for line in out.splitlines():
                if line.startswith("CLIENTRES "):
                    doc = json.loads(line[10:])
            if doc is None:
                raise RuntimeError(
                    f"fleet client printed no result: {out[-200:]}")
            stats["lat"].extend(doc.pop("lat"))
            stats["errors"].extend(doc.pop("errors"))
            # Per-client rate over the client's OWN request window
            # (excludes interpreter startup; clients run concurrently,
            # so the aggregate is the sum of rates).
            client_elapsed = doc.pop("elapsed")
            stats["client_qps"].append(
                (doc["served"] + doc["shed"])
                / max(client_elapsed, 1e-9))
            for key, value in doc.items():
                stats[key] += value
        elapsed = time.perf_counter() - t0
        # The fleet view any load balancer would scrape, from the
        # FIRST frontend (all frontends converge on the aggregate).
        try:
            import urllib.request
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{serving_ports[0]}/v1/status",
                    timeout=10) as resp:
                fleet_doc = json.loads(resp.read()).get("fleet")
        except Exception:  # noqa: BLE001 - observability only
            fleet_doc = None
    finally:
        for p in procs:
            try:
                p.stdin.write("\n")
                p.stdin.flush()
            except Exception:  # noqa: BLE001
                pass
        for p in procs:
            try:
                p.communicate(timeout=120)
            except Exception:  # noqa: BLE001
                p.kill()
                p.communicate()
    lat = sorted(stats["lat"])

    def pick(p):
        return round(lat[min(int(len(lat) * p / 100),
                             len(lat) - 1)], 3) if lat else None

    total = stats["served"] + stats["shed"]
    return {
        "frontends": n_frontends, "clients": clients,
        "requests": total, "served": stats["served"],
        "elapsed_s": round(elapsed, 3),
        "aggregate_qps": round(sum(stats["client_qps"]), 1),
        "p50_ms": pick(50), "p99_ms": pick(99),
        "hit_rate": round(stats["hits"] / max(stats["served"], 1), 4),
        "row_hit_rate": round(stats["rows_cached"]
                              / max(stats["rows_req"], 1), 4),
        "response_cache_hit_rate": round(
            stats["response_cache_hits"]
            / max(stats["served"], 1), 4),
        "shed": stats["shed"],
        "staleness_violations": stats["staleness_violations"],
        "wrong_values": stats["wrong_values"],
        "http_errors": stats["errors"][:5],
        "fleet_view": fleet_doc}


def _ann_arm(num_row: int = 131072, num_col: int = 64,
             n_queries: int = 200, k: int = 10) -> dict:
    """IVF vs the linear scan on an embedding-shaped (clustered)
    table: measured recall@10 against the exact brute ranking and the
    per-query speedup. Pure host compute — exactly what the neighbors
    endpoint runs per request on its snapshot."""
    from multiverso_tpu.serving.ann import IVFIndex

    rng = np.random.default_rng(7)
    n_clusters = 256
    centers = rng.standard_normal((n_clusters, num_col)) \
        .astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1)[:, None]
    values = (centers[rng.integers(0, n_clusters, num_row)]
              + 0.08 * rng.standard_normal((num_row, num_col))
              .astype(np.float32)).astype(np.float32)
    norms = np.maximum(np.linalg.norm(values, axis=1), 1e-12)
    # Past sqrt(N) toward smaller lists: per-query cost follows
    # nprobe x N / nlist candidate rows, and on well-clustered
    # embedding data recall holds at small nprobe (measured below,
    # not assumed).
    nlist = 512
    nprobe = 4
    t0 = time.perf_counter()
    index = IVFIndex(values, norms, nlist=nlist)
    build_s = time.perf_counter() - t0
    queries = rng.integers(0, num_row, n_queries)

    def brute(row):
        q = values[row]
        scores = (values @ q) / (norms * max(np.linalg.norm(q),
                                             1e-12))
        scores[row] = -np.inf
        top = np.argpartition(-scores, k)[:k]
        return top[np.argsort(-scores[top])]

    t0 = time.perf_counter()
    exact = [brute(int(r)) for r in queries]
    brute_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    approx = [index.search(values[int(r)], k, nprobe,
                           exclude=int(r))[0] for r in queries]
    ivf_s = time.perf_counter() - t0
    recall = float(np.mean(
        [len(set(map(int, e)) & set(map(int, a))) / k
         for e, a in zip(exact, approx)]))
    return {
        "num_row": num_row, "num_col": num_col, "nlist": nlist,
        "nprobe": nprobe, "queries": n_queries,
        "build_s": round(build_s, 3),
        "brute_ms_per_query": round(brute_s / n_queries * 1e3, 4),
        "ivf_ms_per_query": round(ivf_s / n_queries * 1e3, 4),
        "speedup": round(brute_s / ivf_s, 2),
        "recall_at_10": round(recall, 4)}


def _batching_arm(tmp: str) -> dict:
    """Batched scatter reads vs the serialized per-request gather
    path, A/B over identical load shape: a 2-process TCP cluster
    (worker+frontend process, server process) on a paced 1 Mbps
    emulated expensive-roundtrip link (the PR-7 pacing convention,
    turned down so the backend roundtrip — not frontend CPU — is the
    dominant cost), client cache and
    hot-response cache OFF so every request really crosses the wire.
    8 concurrent keep-alive clients, Zipf(2.0) multi-row reads
    (the hot-head read regime ISSUE/ROADMAP motivate batching with),
    trainer running throughout.

    The legacy arm (-serving_scatter=false) serializes requests on
    the table's one-get-in-flight registers: 8 clients queue behind
    one paced roundtrip per request. The batched arm folds the
    concurrent requests of each -serving_batch_window_ms window into
    ONE merged read — one roundtrip (and one device gather per
    shard) per BATCH, with the Zipf head deduplicated across the
    folded requests (~2x fewer unique rows than the per-request sum
    at this skew), so both the fixed roundtrip AND the paced bytes
    amortize over the batch."""
    common = ("-max_get_staleness=0", "-serving_hot_rows=0",
              "-net_pace_mbps=1")
    per_request = _fleet_sweep_arm(
        1, tmp, clients=8, reqs_per_client=100, zipf_a=2.0,
        child_flags=common + ("-serving_scatter=false",),
        label="_ab_legacy")
    batched = _fleet_sweep_arm(
        1, tmp, clients=8, reqs_per_client=100, zipf_a=2.0,
        child_flags=common + ("-serving_batch_window_ms=3",),
        label="_ab_batched")
    return {
        "clients": 8, "pace_mbps": 1, "zipf_a": 2.0,
        "per_request": per_request, "batched": batched,
        "batched_vs_per_request": round(
            batched["aggregate_qps"]
            / max(per_request["aggregate_qps"], 1e-9), 3)}


def run_serving_fleet(tmp: str) -> dict:
    """Serving-fleet phase (docs/SERVING.md fleet section): the
    multi-rank read path measured end to end.

    - ANN: IVF vs the linear scan on a 32k-row clustered table —
      acceptance >= 5x per-query speedup at recall@10 >= 0.95.
    - BATCHING: batched scatter reads vs the serialized per-request
      gather path under 8 concurrent clients — acceptance >= 2x QPS.
    - FLEET SWEEP: 1 vs 2 frontend PROCESSES over a shared server
      rank (TCP machine-file mesh), training concurrent, parent-side
      clients verifying every response's staleness bound and legal
      value — acceptance: 2 frontends >= 1.5x aggregate QPS with p99
      within the shared bound, 0 staleness violations, 0 wrong
      values across ALL arms."""
    out = {"ann": _ann_arm(), "batching": _batching_arm(tmp)}
    sweep = {}
    for n_frontends in (1, 2):
        # 24 clients saturate one frontend process (the GIL is the
        # per-frontend capacity on this host): without queueing at
        # the single frontend there is nothing for the second one to
        # relieve and the ratio just measures latency, not capacity.
        sweep[f"f{n_frontends}"] = _fleet_sweep_arm(
            n_frontends, tmp, clients=24, reqs_per_client=250)
    out["sweep"] = sweep
    f1, f2 = sweep["f1"], sweep["f2"]
    # Equal p99 bound for both sweep arms: generous vs the
    # single-frontend measurement, floored against timer noise.
    p99_bound_ms = max(3.0 * (f1["p99_ms"] or 0.0), 50.0)
    out.update(
        p99_bound_ms=round(p99_bound_ms, 3),
        fleet_qps_ratio=round(
            f2["aggregate_qps"] / max(f1["aggregate_qps"], 1e-9), 3),
        accept_ann_5x_at_recall_095=bool(
            out["ann"]["speedup"] >= 5.0
            and out["ann"]["recall_at_10"] >= 0.95),
        accept_batched_2x=bool(
            out["batching"]["batched_vs_per_request"] >= 2.0),
        accept_two_frontends_150=bool(
            f2["aggregate_qps"] >= 1.5 * f1["aggregate_qps"]
            and (f1["p99_ms"] or 1e9) <= p99_bound_ms
            and (f2["p99_ms"] or 1e9) <= p99_bound_ms),
        accept_zero_staleness_violations=bool(
            f1["staleness_violations"] == 0
            and f2["staleness_violations"] == 0
            and out["batching"]["per_request"]
                   ["staleness_violations"] == 0
            and out["batching"]["batched"]
                   ["staleness_violations"] == 0),
        accept_zero_wrong_values=bool(
            f1["wrong_values"] == 0 and f2["wrong_values"] == 0
            and out["batching"]["per_request"]["wrong_values"] == 0
            and out["batching"]["batched"]["wrong_values"] == 0))
    return out


_MANYCONN_CLIENT = '''
import json, os, socket, sys, time
import selectors

port, n_conns, reqs_per_conn, window = (int(v) for v in sys.argv[1:5])
REQ = (b"GET /v1/tables/emb/rows?ids=1,5,9,13 HTTP/1.1\\r\\n"
       b"Host: 127.0.0.1\\r\\nConnection: keep-alive\\r\\n\\r\\n")

# Phase 1: establish every keep-alive connection up front (sequential
# blocking dials on loopback are ~0.1 ms each and never overflow the
# accept backlog). The pump itself is ONE thread + one selector.
socks = []
for _ in range(n_conns):
    s = socket.create_connection(("127.0.0.1", port), timeout=30)
    s.setblocking(False)
    socks.append(s)
fd_count = len(os.listdir("/proc/self/fd"))
print("CONNECTED", len(socks), fd_count, flush=True)
sys.stdin.readline()  # parent samples the frontend /proc, then acks

# Phase 2: single-threaded selectors pump. Each connection answers
# reqs_per_conn requests; at most `window` are in flight at once so
# the other ~500 connections sit ESTABLISHED-idle — the C10k shape the
# event-loop transport exists for. One request outstanding per
# connection, so a read buffer never holds more than one response.
sel = selectors.DefaultSelector()
state = {}  # sock -> [buf, t0, remaining]
for s in socks:
    state[s] = [b"", 0.0, reqs_per_conn]
idle = list(socks)
out = {"lat": [], "served": 0, "shed": 0, "errors": 0, "inflight_window": window}
total = n_conns * reqs_per_conn
done = 0
inflight = 0
t_start = time.perf_counter()
deadline = t_start + 600
while done < total and time.perf_counter() < deadline:
    while idle and inflight < window:
        s = idle.pop()
        st = state[s]
        st[0] = b""
        st[1] = time.perf_counter()
        assert s.send(REQ) == len(REQ)  # 80 B into an empty buffer
        sel.register(s, selectors.EVENT_READ)
        inflight += 1
    for key, _ in sel.select(timeout=10):
        s = key.fileobj
        st = state[s]
        try:
            data = s.recv(65536)
        except BlockingIOError:
            continue
        if not data:  # server hung up mid-exchange
            sel.unregister(s)
            s.close()
            st[2] = 0
            done += 1
            inflight -= 1
            out["errors"] += 1
            continue
        st[0] += data
        head_end = st[0].find(b"\\r\\n\\r\\n")
        if head_end < 0:
            continue
        head = st[0][:head_end].decode("latin-1")
        clen = 0
        for line in head.split("\\r\\n")[1:]:
            if line.lower().startswith("content-length:"):
                clen = int(line.split(":", 1)[1])
        if len(st[0]) < head_end + 4 + clen:
            continue
        status = int(head.split(None, 2)[1])
        if status == 200:
            out["lat"].append((time.perf_counter() - st[1]) * 1e3)
            out["served"] += 1
        elif status in (429, 503):
            out["shed"] += 1
        else:
            out["errors"] += 1
        sel.unregister(s)
        done += 1
        inflight -= 1
        st[2] -= 1
        if st[2] > 0:
            idle.append(s)
out["elapsed"] = time.perf_counter() - t_start
out["completed"] = done
out["total"] = total
for s in socks:
    s.close()
print("CLIENTRES " + json.dumps(out), flush=True)
'''


def run_many_connections(tmp: str, n_conns: int = 512,
                         reqs_per_conn: int = 4,
                         window: int = 48) -> dict:
    """Many-connection arm (docs/THREADS.md event-loop core): >= 512
    keep-alive HTTP clients held open against ONE frontend rank by a
    single-threaded selectors pump, with a bounded in-flight window so
    most connections sit established-idle — the C10k shape. Records
    QPS and p99 over the served requests plus the frontend's fd count
    and TRANSPORT thread count sampled from /proc while every
    connection is up. Acceptance: all n_conns connections concurrently
    established, and transport threads O(1) — the selector loop plus
    the (peer-count-bounded, connection-count-independent) shm ring
    writers — while total fds scale with connections."""
    from multiverso_tpu.util.net_util import free_listen_port

    mf = os.path.join(tmp, "manyconn_mf.txt")
    with open(mf, "w") as f:
        for p in (free_listen_port(), free_listen_port()):
            f.write(f"127.0.0.1:{p}\n")
    serving_port = free_listen_port()
    code = _FLEET_CHILD.format(
        repo=os.path.dirname(os.path.abspath(__file__)), mf=mf,
        num_row=4096, num_col=32)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    procs = []
    for rank, role, port in ((0, "server", 0),
                             (1, "worker", serving_port)):
        procs.append(subprocess.Popen(
            [sys.executable, "-c", code, str(rank), "2", role,
             str(port), "-max_get_staleness=16"],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env))
    out = {"n_conns": n_conns, "reqs_per_conn": reqs_per_conn}
    try:
        for p in procs:
            while True:
                line = p.stdout.readline()
                if not line:
                    p.wait(timeout=30)
                    raise RuntimeError(
                        f"manyconn child exited rc={p.returncode}: "
                        f"{p.stderr.read()[-400:]}")
                if line.startswith("READY"):
                    break
        client = subprocess.Popen(
            [sys.executable, "-c", _MANYCONN_CLIENT,
             str(serving_port), str(n_conns), str(reqs_per_conn),
             str(window)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True, env=env)
        try:
            line = client.stdout.readline()
            if not line.startswith("CONNECTED"):
                raise RuntimeError(
                    f"manyconn client failed to connect: "
                    f"{client.stderr.read()[-400:]}")
            _, connected, client_fds = line.split()
            out["connected"] = int(connected)
            out["client_fd_count"] = int(client_fds)
            # Every connection is established and held right now —
            # fd census from /proc, thread census self-reported by the
            # frontend over its stdin/stdout pipe (thread ROLES are
            # not visible from outside the process).
            fe = procs[1]
            try:
                out["frontend_fd_count"] = len(
                    os.listdir(f"/proc/{fe.pid}/fd"))
            except OSError:
                out["frontend_fd_count"] = None
            fe.stdin.write("SAMPLE\n")
            fe.stdin.flush()
            out["frontend_threads_total"] = None
            out["frontend_transport_threads"] = None
            while True:
                line = fe.stdout.readline()
                if not line:
                    break
                if line.startswith("THREADS"):
                    _, total, transport = line.split()
                    out["frontend_threads_total"] = int(total)
                    out["frontend_transport_threads"] = int(transport)
                    break
            client.stdin.write("\n")
            client.stdin.flush()
            cout, cerr = client.communicate(timeout=700)
        except Exception:
            client.kill()
            client.communicate()
            raise
        if client.returncode:
            raise RuntimeError(f"manyconn client failed: {cerr[-400:]}")
        doc = None
        for line in cout.splitlines():
            if line.startswith("CLIENTRES "):
                doc = json.loads(line[10:])
        if doc is None:
            raise RuntimeError(
                f"manyconn client printed no result: {cout[-200:]}")
    finally:
        for p in procs:
            try:
                p.stdin.write("\n")
                p.stdin.flush()
            except Exception:  # noqa: BLE001
                pass
        for p in procs:
            try:
                p.communicate(timeout=120)
            except Exception:  # noqa: BLE001
                p.kill()
                p.communicate()
    lat = sorted(doc.pop("lat"))

    def pick(p):
        return round(lat[min(int(len(lat) * p / 100),
                             len(lat) - 1)], 3) if lat else None

    out.update(
        served=doc["served"], shed=doc["shed"],
        errors=doc["errors"], completed=doc["completed"],
        elapsed_s=round(doc["elapsed"], 3),
        qps=round(doc["completed"] / max(doc["elapsed"], 1e-9), 1),
        p50_ms=pick(50), p99_ms=pick(99),
        inflight_window=doc["inflight_window"],
        accept_512_keepalive_connections=bool(
            out["connected"] >= 512
            and (out["frontend_fd_count"] or 0) >= 512),
        # O(1): one selector loop + at most one shm ring writer per
        # CO-LOCATED RANK (here: 1), never a thread per connection.
        accept_o1_transport_threads=bool(
            out["frontend_transport_threads"] is not None
            and out["frontend_transport_threads"] <= 4))
    return out


def matrix_bandwidth() -> dict:
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.updater import AddOption

    num_row, num_col, iters = 1_000_000, 50, 10
    nbytes = num_row * num_col * 4
    import jax

    # NOTE on timing: every measurement below forces completion with
    # a tiny scalar READBACK chained onto the measured work.
    mv.init([])
    table = mv.create_matrix_table(num_row, num_col)
    delta = jnp.ones((num_row, num_col), jnp.float32)
    float(delta[0, 0])  # settle the upload
    table.add(delta)
    float(table.get_device()[0, 0])  # compile + settle
    start = time.perf_counter()
    ids = [table.add_async(delta) for _ in range(iters)]
    for msg_id in ids:
        table.wait(msg_id)
    float(table.get_device()[0, 0])  # the adds chain through the table
    add_gbps = nbytes / ((time.perf_counter() - start) / (iters + 1)) / 1e9
    start = time.perf_counter()
    acc = None
    for _ in range(iters):
        probe_elt = table.get_device()[0, 0]  # ties each get into the
        acc = probe_elt if acc is None else acc + probe_elt  # readback
    float(acc)
    get_gbps = nbytes / ((time.perf_counter() - start) / iters) / 1e9

    # Launch and transfer characterization (shared helpers with the
    # start-of-run weather_probe, so the two snapshots stay
    # comparable): host<->device transfer rates both directions — the
    # host-buffer dirty Get is capped by them, not by the table stack;
    # the per-call dispatch floor; and the per-PROGRAM launch floor
    # sampled as a small DISTRIBUTION (a single mean hides its spread).
    up_mbps, down_mbps = _host_transfer_rates_mbps(4 << 20)  # 16 MB
    dispatch_ms = _dispatch_rtt_ms(20)
    launch_samples = _launch_overhead_samples(4, 20)
    launch_ms = float(np.median(launch_samples))

    # Sparse dirty-row path (ref: test_matrix_perf.cpp sparse variants):
    # dirty rows per round, dirty-only whole-table get — measured on
    # the DEVICE path (host bitmap bookkeeping, HBM payload: deltas
    # push as device arrays, dirty values reply as device arrays). The
    # reference-shaped host-buffer variant is timed alongside; it is
    # bounded by host<->device bandwidth, which the transfer rates
    # below make interpretable.
    # (In-process tables skip the sparse wire filter automatically —
    # there is no wire.)
    sparse = mv.create_matrix_table(num_row, num_col, is_sparse=True)
    sparse.get_dirty_device()  # initial full sync marks everything clean
    dirty_n = num_row // 10  # the reference perf test's p/10 fraction
    rows = np.arange(dirty_n, dtype=np.int32) * 10
    dev_delta = jnp.ones((dirty_n, num_col), jnp.float32)
    jax.block_until_ready(dev_delta)
    opt = AddOption(worker_id=1)  # dirties the rows for worker 0
    # One untimed roundtrip compiles the dirty gather/scatter bucket.
    sparse.add_rows(rows, dev_delta, option=opt)
    _, warm_vals = sparse.get_dirty_device()
    float(warm_vals[0, 0])
    start = time.perf_counter()
    sparse_iters = 10
    vals = None
    for _ in range(sparse_iters):
        sparse.add_rows(rows, dev_delta, option=opt)
        _, vals = sparse.get_dirty_device()  # only the dirty rows
    float(vals[0, 0])  # force the dispatched chain
    sparse_elapsed = time.perf_counter() - start
    sparse_bytes = dirty_n * num_col * 4 * 2  # add + dirty-row get
    sparse_gbps = sparse_bytes * sparse_iters / sparse_elapsed / 1e9

    # FUSED roundtrip (r5): the -4 extension composes the add and the
    # dirty get into ONE compiled program server-side — one launch per
    # iteration instead of two — and the caller keeps a device mirror
    # of its row ids (skipping the per-call host-to-device id upload).
    from multiverso_tpu.updater.engine import pad_ids
    dev_rows = jnp.asarray(pad_ids(rows, num_row))  # bucket-padded mirror
    _, f_vals = sparse.add_get_dirty_device(rows, dev_delta,
                                            option=opt, get_worker=0,
                                            row_ids_device=dev_rows)
    float(f_vals[0, 0])  # warm the fused compile
    start = time.perf_counter()
    for _ in range(sparse_iters):
        _, f_vals = sparse.add_get_dirty_device(rows, dev_delta,
                                                option=opt,
                                                get_worker=0,
                                                row_ids_device=dev_rows)
    float(f_vals[0, 0])
    fused_gbps = sparse_bytes * sparse_iters \
        / (time.perf_counter() - start) / 1e9

    # Launch overhead with a BIG donated buffer argument — the sparse
    # roundtrip's actual program shape (the tiny-arg launch_ms above
    # may understate it).
    big = jnp.zeros((num_row, 128), jnp.float32)
    bump = jax.jit(lambda t: t.at[0, 0].add(1.0), donate_argnums=0)
    big = bump(big)
    float(big[0, 0])
    t0 = time.perf_counter()
    for _ in range(10):
        big = bump(big)
    float(big[0, 0])
    launch_big_ms = (time.perf_counter() - t0) / 10 * 1e3
    del big
    # Platform bound for the roundtrip (VERDICT r4 weak #3): each
    # unfused iteration is 2 dependent big-argument program launches,
    # so the launch floor caps it at payload/(2*launch_big_ms)
    # regardless of code; the fused form's cap is one launch. Record
    # caps and achieved fractions so the 1.6 GB/s bar is auditable
    # against the measured launch cost, not prose.
    sparse_implied_cap = sparse_bytes / (2 * launch_big_ms / 1e3) / 1e9
    fused_implied_cap = sparse_bytes / (launch_big_ms / 1e3) / 1e9

    # Host-buffer variant (the reference API shape: Get fills caller
    # memory) for comparison.
    buf = np.zeros((num_row, num_col), np.float32)
    row_delta = np.ones((dirty_n, num_col), np.float32)
    sparse.get(out=buf)
    start = time.perf_counter()
    for _ in range(2):
        sparse.add_rows(rows, row_delta, option=opt)
        sparse.get(out=buf)
    host_sparse_gbps = sparse_bytes * 2 / (time.perf_counter() - start) \
        / 1e9
    mv.shutdown()

    # Scatter/sweep microbench (VERDICT r3 #2): slope-timed — T(G_hi) -
    # T(G_lo) of an in-jit scan cancels the ~100ms readback RTT that
    # made single-op timings claim scatter was O(table).
    def slope(make, lo=4, hi=12):
        def run_g(g):
            fn = make(g)
            t_val = jnp.zeros((num_row, 128), jnp.float32)
            out = fn(t_val)
            float(jnp.ravel(out)[0])
            best = float("inf")
            for _ in range(3):
                t_val = jnp.zeros((num_row, 128), jnp.float32)
                float(t_val[0, 0])
                t0 = time.perf_counter()
                out = fn(t_val)
                float(jnp.ravel(out)[0])
                best = min(best, time.perf_counter() - t0)
            return best
        return (run_g(hi) - run_g(lo)) / (hi - lo)

    import functools as _ft
    k = 32768
    ids_scan = jax.random.randint(jax.random.PRNGKey(0), (12, k), 0,
                                  num_row, jnp.int32)
    delta_rows = jnp.ones((k, 128), jnp.float32)

    def make_scatter(g):
        @_ft.partial(jax.jit, donate_argnums=0, static_argnums=1)
        def f(t, g):
            def body(t, i):
                return t.at[i].add(delta_rows), 0.0
            t, _ = jax.lax.scan(body, t, ids_scan[:g])
            return t
        return lambda t: f(t, g)

    # Gather slope needs a BIGGER row set than scatter: a 32K-row
    # gather (~16 MB) finishes in ~0.2 ms, far under the min-of-3
    # timing noise, and the r5.0 run measured a null slope. 256K rows
    # per step puts the per-step cost well above the noise floor.
    k_gather = 262144
    ids_gather = jax.random.randint(jax.random.PRNGKey(1),
                                    (12, k_gather), 0, num_row,
                                    jnp.int32)

    def make_gather(g):
        @_ft.partial(jax.jit, static_argnums=1)
        def f(t, g):
            def body(acc, i):
                # Reduce the gathered rows into the carry scalar: the
                # output depends on every gather, so none can be
                # dead-code-eliminated.
                return acc + t[i].sum(), None
            acc, _ = jax.lax.scan(body, jnp.float32(0), ids_gather[:g])
            return acc
        return lambda t: f(t, g)

    def make_sweep(g):
        @_ft.partial(jax.jit, donate_argnums=0, static_argnums=1)
        def f(t, g):
            def body(t, _):
                return t + 1.0, 0.0
            t, _ = jax.lax.scan(body, t, jnp.arange(g))
            return t
        return lambda t: f(t, g)

    def gbps(io_bytes, slope_s):
        # A non-positive slope means the measurement noise exceeded the
        # per-step cost — report None, not infinity.
        if slope_s <= 1e-5:
            return None
        return round(io_bytes / slope_s / 1e9, 2)

    scatter_gbps = gbps(2 * k * 128 * 4, slope(make_scatter))
    gather_gbps = gbps(k_gather * 128 * 4, slope(make_gather))
    sweep_gbps = gbps(2 * num_row * 128 * 4, slope(make_sweep))

    return {"add_gbps": round(add_gbps, 3),
            "get_gbps": round(get_gbps, 3),
            "scatter_32k_rows_gbps": scatter_gbps,
            "gather_256k_rows_gbps": gather_gbps,
            "table_sweep_gbps": sweep_gbps,
            "sparse_dirty_roundtrip_gbps": round(sparse_gbps, 3),
            "sparse_dirty_fused_gbps": round(fused_gbps, 3),
            "sparse_dirty_launch_cap_gbps": round(sparse_implied_cap, 3),
            "sparse_dirty_fraction_of_cap": round(
                sparse_gbps / sparse_implied_cap, 3),
            "sparse_fused_launch_cap_gbps": round(fused_implied_cap, 3),
            "sparse_fused_fraction_of_cap": round(
                fused_gbps / fused_implied_cap, 3),
            "program_launch_big_arg_ms": round(launch_big_ms, 3),
            "sparse_dirty_hostbuf_gbps": round(host_sparse_gbps, 3),
            "host_upload_mbps": round(up_mbps, 1),
            "host_download_mbps": round(down_mbps, 1),
            "dispatch_roundtrip_ms": round(dispatch_ms, 3),
            "program_launch_ms": round(launch_ms, 3),
            "program_launch_ms_samples": [round(x, 3)
                                          for x in launch_samples]}


def _phase(name: str, fn, *args, **kw):
    """Run one bench phase with stderr progress + timing (stdout carries
    only cumulative JSON result lines — the last one wins)."""
    print(f"[bench] {name}...", file=sys.stderr, flush=True)
    start = time.perf_counter()
    out = fn(*args, **kw)
    dt = time.perf_counter() - start
    _phase.seconds[name] = round(dt, 1)
    print(f"[bench] {name} done in {dt:.1f}s", file=sys.stderr, flush=True)
    return out


_phase.seconds = {}


# ---------------------------------------------------------------------------
# Loss-proof harness (VERDICT r4 #1): round 4's entire perf story died in a
# driver timeout because the bench printed its single JSON line only at the
# very end. Three defenses, in depth:
#   1. EMIT AFTER EVERY PHASE — the cumulative result is reprinted to stdout
#      as a complete JSON line after each phase lands; whatever kills the
#      process, everything already finished is already on stdout (the
#      driver parses the last complete JSON line).
#   2. SIGTERM/SIGINT handler — `timeout` sends SIGTERM first; the handler
#      prints one final cumulative line and exits, so even the in-flight
#      phase's partial absence is recorded explicitly.
#   3. GLOBAL WALL BUDGET — before each phase, elapsed + a conservative
#      worst-case estimate is checked against the budget; phases that no
#      longer fit are skipped with a note instead of being started.
# Deterministic CPU baselines (cpp_baseline, cpu_baseline) are additionally
# cached on disk keyed by a config+source hash (~12 min recovered per run).

WALL_BUDGET_SEC = float(os.environ.get("BENCH_WALL_BUDGET", "1500"))
_BENCH_T0 = time.monotonic()

# Conservative worst-case phase costs (sec) on this platform, from the r3/r4
# driver tails — used only for the skip decision, never for timing.
_PHASE_EST = {
    "write_corpus": 8, "build_dictionary": 25, "weather_probe": 30,
    "cpp_baseline": 340, "cpu_baseline": 430,
    "local_train": 100, "ps_train": 110,
    "quality_local": 190, "quality_ps": 180,
    "ps_hostbatch": 70, "hs_train": 60,
    "ps_two_workers": 60, "ps_two_servers": 150,
    "tcp_one_process": 65, "tcp_two_process": 110,
    "matrix_bandwidth": 60, "local_retime": 60,
    "wire_codec": 15, "zero_copy": 45, "client_cache": 45,
    "server_fusion": 60,
    "allreduce": 260,
    "observability": 60, "elastic": 110, "autotune": 120,
    "many_connections": 90,
}


class _Result:
    """Cumulative bench result: phases merge fields in as they finish,
    ``emit()`` prints the whole thing as one JSON line each time."""

    def __init__(self):
        self.doc = {
            "metric": "wordembedding_words_per_sec_per_chip",
            "value": None, "unit": "words/s", "vs_baseline": None,
            "detail": {"phase_seconds": _phase.seconds,
                       "wall_budget": {"budget_sec": WALL_BUDGET_SEC,
                                       "skipped": [],
                                       "interrupted": None}},
        }
        #: phases that were started and raised — main() exits with their
        #: count, after everything that did land has been printed.
        self.failed = []

    def merge(self, **fields) -> None:
        self.doc["detail"].update(fields)
        # Every merge lands on stdout immediately — "merged but not yet
        # emitted" is exactly the window a kill would erase.
        self.emit()

    _last_json = "{}"

    def emit(self) -> None:
        self.doc["detail"]["wall_budget"]["elapsed_sec"] = round(
            time.monotonic() - _BENCH_T0, 1)
        # ONE write call per line: the SIGTERM handler may fire mid-emit
        # and append its own line — a torn multi-part write would leave
        # no complete final JSON line for the driver to parse.
        self._last_json = json.dumps(self.doc)
        sys.stdout.write(self._last_json + "\n")
        sys.stdout.flush()

    def run(self, name: str, fn, *args, **kw):
        """Budget-checked phase: skip (recording why) if the worst-case
        estimate no longer fits; emit the cumulative line after every
        completion OR failure."""
        elapsed = time.monotonic() - _BENCH_T0
        est = kw.pop("est", None) or _PHASE_EST.get(name, 60)
        if elapsed + est > WALL_BUDGET_SEC:
            print(f"[bench] SKIP {name}: {elapsed:.0f}s elapsed + "
                  f"~{est}s estimate exceeds {WALL_BUDGET_SEC:.0f}s "
                  "budget", file=sys.stderr, flush=True)
            self.doc["detail"]["wall_budget"]["skipped"].append(name)
            self.emit()  # the skip record must not wait for a later
            # phase to land on stdout
            return None
        try:
            return _phase(name, fn, *args, **kw)
        except Exception as exc:  # noqa: BLE001 - a phase failure must
            # not take down the phases that already landed or follow; it
            # is recorded here and becomes the process's exit code
            print(f"[bench] {name} FAILED: {exc!r}", file=sys.stderr,
                  flush=True)
            self.failed.append(name)
            self.merge(**{name + "_error": str(exc)[:300]})
            return None
        finally:
            self.emit()


def _install_kill_emitter(result: _Result) -> None:
    import signal

    def _on_kill(signum, frame):  # noqa: ARG001
        # The main thread may be mid-merge (dict resizing) — a fresh
        # json.dumps can raise mid-iteration. Fall back to re-printing
        # the last complete serialized line: losing the "interrupted"
        # marker is acceptable; losing the whole record is not.
        try:
            result.doc["detail"]["wall_budget"]["interrupted"] = \
                signal.Signals(signum).name
            result.emit()
        except Exception:  # noqa: BLE001
            sys.stdout.write(result._last_json + "\n")
        sys.stdout.flush()
        os._exit(98)

    for sig in (signal.SIGTERM, signal.SIGINT):
        signal.signal(sig, _on_kill)


def _baseline_cache_path(name: str, src_paths) -> str:
    """Cache file path for a deterministic baseline. Key = hash of the
    bench config constants + the baseline's source files + the
    bench-side logic they depend on; any edit invalidates."""
    import hashlib
    import inspect
    h = hashlib.sha256()
    h.update(repr((VOCAB, SENTENCES, WORDS_PER_SENTENCE, EPOCHS, BATCH,
                   DIM, NEG, MIN_COUNT, NEG_BLOCK, LOCAL_CENTERS,
                   LOCAL_DISPATCH)).encode())
    # The baselines also depend on bench-side logic that is not in the
    # constants: the corpus generator and the baseline runners (CLI
    # args, compile flags, the cpu twin's run_local). Hash their SOURCE
    # so editing any of them invalidates the cache.
    for bench_fn in (write_corpus, _build, run_local, cpu_baseline,
                     cpp_baseline):
        h.update(inspect.getsource(bench_fn).encode())
    for p in sorted(src_paths):
        with open(p, "rb") as f:
            h.update(f.read())
    here = os.path.dirname(os.path.abspath(__file__))
    return os.path.join(here, ".bench_cache",
                        f"{name}-{h.hexdigest()[:16]}.json")


def _cached_baseline(name: str, src_paths, fn, *args):
    """Disk cache for the two DETERMINISTIC baselines: same corpus
    constants + same sources => same numbers, so recomputing ~12 min of
    CPU work every bench run is pure waste (VERDICT r4 weak #6). The
    loss/separation fields are exactly reproducible; the cached TIMING
    fields carry whatever load the populating run saw, which is why the
    reply is marked ``cached`` (populate from an uncontended run)."""
    path = _baseline_cache_path(name, src_paths)
    if os.path.exists(path):
        with open(path) as f:
            out = json.load(f)
        out["cached"] = True
        print(f"[bench] {name}: cache hit ({os.path.basename(path)})",
              file=sys.stderr, flush=True)
        return out
    out = fn(*args)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    tmp_path = path + ".tmp"
    with open(tmp_path, "w") as f:
        json.dump(out, f)
    os.replace(tmp_path, path)
    return out


def _baseline_est(name: str, src_paths) -> int:
    """Skip-check estimate for a cached baseline: seconds when the
    cache file exists, the worst-case recompute estimate otherwise."""
    if os.path.exists(_baseline_cache_path(name, src_paths)):
        return 10
    return _PHASE_EST[name]


def main() -> int:
    """Returns the number of phases that were started and failed (the
    exit code); what landed is on stdout either way."""
    # Handler FIRST: the compilation-cache setup imports jax (slow cold)
    # and a TERM landing before installation would die silently.
    result = _Result()
    _install_kill_emitter(result)
    from multiverso_tpu.util import compile_cache
    compile_cache.enable()
    here = os.path.dirname(os.path.abspath(__file__))
    tmp = tempfile.mkdtemp()
    corpus = os.path.join(tmp, "corpus.txt")
    result.merge(setup={
        "vocab_raw": VOCAB, "min_count": MIN_COUNT,
        "sentences": SENTENCES, "epochs": EPOCHS, "batch": BATCH,
        "dim": DIM, "negative": NEG, "neg_block": NEG_BLOCK,
        "quality_mode": {"per_pair": True, "centers": QUALITY_C,
                         "epochs": QUALITY_EPOCHS},
        "ps_batches": PS_MAX_BATCHES,
        "corpus": "synthetic 2-topic banded Zipf "
                  "(no egress: enwik9 unavailable)"})
    result.emit()  # a complete (if empty) line exists from second zero
    weather = result.run("weather_probe", weather_probe)
    if weather:
        result.merge(weather_at_start=weather)
    codec = result.run("wire_codec", run_wire_codec)
    if codec:
        result.merge(wire_codec=codec)
    zero_copy = result.run("zero_copy", run_zero_copy)
    if zero_copy:
        result.merge(zero_copy=zero_copy)
    allreduce = result.run("allreduce", run_allreduce)
    if allreduce:
        result.merge(allreduce=allreduce)
    _phase("write_corpus", write_corpus, corpus)
    prebuilt = _phase("build_dictionary", _build, corpus)
    result.doc["detail"]["setup"]["vocab_actual"] = prebuilt[0].size

    # Phases run in IMPORTANCE order: if the wall budget truncates the
    # run, what remains on stdout is the most valuable prefix. The two
    # deterministic CPU baselines are disk-cached (first run pays, every
    # later run is free), so cpp lands first cheaply and cpu can wait.
    cpp_srcs = [os.path.join(here, "native", "baseline",
                             "word2vec_baseline.cpp")]
    cpp = result.run("cpp_baseline", _cached_baseline, "cpp_baseline",
                     cpp_srcs, cpp_baseline, corpus, tmp, prebuilt[0],
                     est=_baseline_est("cpp_baseline", cpp_srcs)) \
        or {"error": "skipped or failed"}
    cpp_sep = cpp.get("topic_separation", CPP_SEP_FALLBACK)
    cpp_wps = cpp.get("words_per_sec")
    result.merge(cpp_baseline=cpp)

    local = result.run("local_train", run_local, corpus, prebuilt)
    if local:
        result.doc["value"] = round(local["wps"], 0)
        if cpp_wps:
            # The number to beat: the C++/OpenMP word2vec on this
            # host's CPU (BASELINE.md north star: >=10x CPU words/sec).
            result.doc["vs_baseline"] = round(local["wps"] / cpp_wps, 3)
        result.merge(
            local_median_batch_words_per_sec=local["median_batch_wps"],
            # Pure host arithmetic — never gated on the device fetch.
            utilization=utilization(local["pairs_per_sec"],
                                    local["centers_per_sec"]))
        result.doc["detail"]["mfu"] = \
            result.doc["detail"]["utilization"]["mfu"]
        # Row-fetch form: np.asarray(model.embeddings) would pull the
        # whole table over the host link for 48 rows.
        separation = result.run(
            "local_topic_separation", topic_separation, None,
            local["dictionary"], fetch_rows=lambda ids: np.asarray(
                local["model"]._emb_in[ids]), est=10)
        if separation is not None:
            result.merge(
                local_topic_separation=round(float(separation), 4))

    ps = result.run("ps_train", run_ps, corpus, prebuilt)
    if ps:
        result.merge(
            ps_words_per_sec=round(ps["wps"], 0),
            ps_grouped_words_per_sec=ps.get("grouped_wps"),
            ps_blocks_per_dispatch=PS_GROUP,
            ps_cold_words_per_sec=ps["cold_wps"],
            ps_warmup_seconds=ps["warmup_seconds"],
            ps_median_batch_words_per_sec=ps["median_batch_wps"],
            ps_avg_loss=ps["avg_loss"],
            ps_topic_separation=ps["separation"],
            ps_dashboard=ps["dashboard"],
            ps_xprof_trace_dir=ps["xprof_trace_dir"])
        if local:
            result.merge(ps_vs_local=round(ps["wps"] / local["wps"], 3))
        result.emit()

    quality_local = result.run("quality_local", run_quality, prebuilt,
                               cpp_sep, False) or {}
    # Merge EACH quality result as it lands (not after both): a kill
    # during the second phase must not erase the first's record.
    result.merge(quality_local=quality_local)
    quality_ps = result.run("quality_ps", run_quality, prebuilt,
                            cpp_sep, True) or {}
    result.merge(
        quality_ps=quality_ps,
        time_to_cpp_quality_sec={
            "local": quality_local.get("time_to_cpp_quality_sec"),
            "ps": quality_ps.get("time_to_cpp_quality_sec"),
            "cpp_elapsed_sec": cpp.get("elapsed_sec")})

    # Cross-process PS over TCP: the 2-process number is the record that
    # must beat the C++ baseline (VERDICT r4 #3), so it runs BEFORE the
    # 1-process continuity point.
    tcp2 = result.run("tcp_two_process", run_tcp_processes, corpus,
                      prebuilt, 2, tmp)
    tcp = {"two_process": tcp2,
           # None (not False) when either operand is missing: a skipped
           # phase must not read as "lost to the baseline".
           "beats_cpp_baseline": bool(
               tcp2["aggregate_wps"] > cpp_wps)
           if (tcp2 and cpp_wps) else None,
           "note": "CPU backend; this host has ONE core, so two "
                   "processes time-share it"}
    result.merge(tcp_cross_process=tcp)

    two_servers = result.run("ps_two_servers", run_ps_two_servers,
                             prebuilt, tmp)
    if two_servers:
        result.merge(ps_two_servers=two_servers,
                     ps_two_servers_vs_single=two_servers.get(
                         "vs_single_same_window"))

    elastic = result.run("elastic", run_elastic, tmp)
    if elastic:
        result.merge(elastic=elastic)

    cache = result.run("client_cache", run_client_cache)
    if cache:
        result.merge(client_cache=cache)

    fusion = result.run("server_fusion", run_server_fusion)
    if fusion:
        result.merge(server_fusion=fusion)

    obs = result.run("observability", run_observability)
    if obs:
        result.merge(observability=obs)

    serving = result.run("serving", run_serving)
    if serving:
        result.merge(serving=serving)

    autotune = result.run("autotune", run_autotune)
    if autotune:
        result.merge(autotune=autotune)

    fleet = result.run("serving_fleet", run_serving_fleet, tmp)
    if fleet:
        result.merge(serving_fleet=fleet)

    manyconn = result.run("many_connections", run_many_connections,
                          tmp)
    if manyconn:
        result.merge(many_connections=manyconn)

    matrix = result.run("matrix_bandwidth", matrix_bandwidth)
    if matrix:
        result.merge(matrix_table_bandwidth=matrix)
        if local:
            util = result.doc["detail"].get("utilization")
            if util is not None:
                util["step_time_decomposition"] = \
                    step_decomposition(local, matrix)
                result.emit()

    cpu_srcs = sorted(glob.glob(os.path.join(
        here, "multiverso_tpu", "models", "wordembedding", "*.py")))
    cpu = result.run("cpu_baseline", _cached_baseline, "cpu_baseline",
                     cpu_srcs, cpu_baseline, corpus,
                     est=_baseline_est("cpu_baseline", cpu_srcs))
    if cpu and local:
        # Fixed-seed full-run comparison: the CPU twin runs ALL epochs
        # with the same seeds/config, so every epoch has a rel-diff.
        rel = [round(abs(t - c) / max(abs(c), 1e-9), 4)
               for t, c in zip(local["epoch_losses"],
                               cpu["epoch_losses"])]
        result.merge(
            cpu_backend_words_per_sec=round(cpu["wps"], 0),
            loss_parity={"tpu_epoch_losses": local["epoch_losses"],
                         "cpu_epoch_losses": cpu["epoch_losses"],
                         "epoch_rel_diff": rel,
                         "epoch0_rel_diff": rel[0] if rel else None})
    result.merge(loss_curves={
        "cpp_epoch_losses": cpp.get("epoch_losses"),
        "tpu_quality_epoch_losses": quality_local.get("epoch_losses"),
        "tpu_fast_epoch_losses": local["epoch_losses"] if local
        else None})

    hostbatch = result.run("ps_hostbatch", run_hostbatch, prebuilt)
    if hostbatch:
        result.merge(ps_hostbatch_words_per_sec=hostbatch.get("wps"),
                     ps_hostbatch_batch_size=hostbatch.get("batch_size"))
    hs = result.run("hs_train", run_hs, prebuilt)
    if hs:
        result.merge(hs_train=hs)
    two_workers = result.run("ps_two_workers", run_ps_two_workers,
                             prebuilt)
    if two_workers:
        result.merge(ps_two_workers=two_workers)
    tcp1 = result.run("tcp_one_process", run_tcp_processes, corpus,
                      prebuilt, 1, tmp)
    if tcp1:
        tcp["one_process"] = tcp1
        if tcp2:
            tcp["two_vs_one"] = round(tcp2["aggregate_wps"]
                                      / max(tcp1["aggregate_wps"], 1), 3)

    # Late re-timing of the headline path (~35s, programs already
    # compiled by local_train — which is also why this only runs when
    # local_train did: warm=False would otherwise compile inside the
    # timed window, and with no first measurement there is nothing to
    # compare against). One early-vs-late pair makes intra-run
    # drift visible — a
    # degraded `value` is then self-explaining instead of mysterious.
    # `value` itself stays the FIRST measurement, as in every round.
    if local:
        late = result.run("local_retime", run_local, corpus, prebuilt,
                          1, EPOCHS, False)
        if late:
            result.merge(local_late_median_batch_words_per_sec=late[
                "median_batch_wps"],
                local_late_vs_first=round(
                    late["median_batch_wps"]
                    / max(local["median_batch_wps"], 1), 3))
    result.emit()
    if result.failed:
        print(f"[bench] FAILED phases: {result.failed}", file=sys.stderr,
              flush=True)
    return len(result.failed)


if __name__ == "__main__":
    sys.exit(main())

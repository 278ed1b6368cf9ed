#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python3 chip_smoke.py        (from the root of a checkout; no arguments)

One process, which holds the chip for all four arms:

1. PS trainer: word2vec SGNS trained through the parameter server
   (Dictionary, TokenizedCorpus, mv.init, PSWord2Vec,
   PSDeviceCorpusTrainer, worker and server actors, MatrixServer gather,
   UpdateEngine scatter-add, mv.shutdown), one epoch.
2. Local trainer: the same CLI with -use_ps=false (DeviceCorpusTrainer).
3. Table API: a server that answers a few requests. Array, dense (sgd,
   adagrad), sparse and KV tables; every reply compared with a numpy
   shadow.
4. Scatter-add: the rows programs' sorted-runs kernel against XLA's
   scatter, on one device and row-sharded over all of them.

All at the width the repo benchmarks: a little over 1,000,000 vocabulary
rows x dim 128, window 5, 5 negatives, neg_block 8. The corpus is made
from a seed. Weights are random. Nothing is read from the network, git
or a cache of an earlier machine's results.

It selects no platform. It prints what JAX found and exits non-zero at
once unless that is a TPU; any failed check, exception or missed
deadline is a non-zero exit too. On success the last line of stdout is
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

The seconds it prints are observations for CHANGES.md (cold set-up,
compilation included, apart from steady running). None is a speed.
"""

import contextlib
import faulthandler
import json
import math
import os
import sys
import tempfile
import time
import traceback

import numpy as np

ROWS = 1_000_003      # vocabulary = table height; odd, so four chips pad
DIM = 128
SENTENCES = 100_000   # topic text after one pass over the vocabulary
WINDOW, NEGATIVE, NEG_BLOCK = 5, 5, 8
SENTENCE_LEN = 40
WARM_GROUPS = 4       # PS groups treated as warm-up
ARM_DEADLINE_S = 330  # three arms + corpus stay inside the 1200 s limit

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


class ProgramBuilds:
    """Every XLA program JAX had to build, as jax.monitoring reports it:
    one duration event per executable, whether XLA compiled it or the
    persistent cache supplied it, and one plain event per cache hit."""

    def __init__(self):
        import jax.monitoring
        self.events = []   # (monotonic time it ended, seconds it took)
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._took)
        jax.monitoring.register_event_listener(self._happened)

    def _took(self, event, seconds, **kw):
        if event == _BUILD_EVENT:
            self.events.append((time.monotonic(), seconds))

    def _happened(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return len(self.events), self.cache_hits

    def since(self, mark) -> dict:
        seconds = [took for _, took in self.events[mark[0]:]]
        from_disk = self.cache_hits - mark[1]
        quick = [took for took in seconds if took < 1.0]
        return {"programs_built": len(seconds),
                "from_persistent_cache": from_disk,
                # really compiled: built, and not found on disk
                "programs_compiled": len(seconds) - from_disk,
                "build_seconds": round(sum(seconds), 2),
                # what a 1 s persistent-cache threshold would leave out
                "built_in_under_1s": len(quick),
                "seconds_in_those": round(sum(quick), 2)}

    def last_build_since(self, mark, default: float) -> float:
        return max((at for at, _ in self.events[mark[0]:]), default=default)


@contextlib.contextmanager
def deadline(seconds: float):
    """An arm that hangs dumps every thread's stack and exits non-zero."""
    faulthandler.dump_traceback_later(seconds, exit=True)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()


def write_corpus(path: str, rows: int, sentences: int, seed: int) -> None:
    """One pass over the whole vocabulary (so min_count=1 yields exactly
    ``rows`` table rows) followed by two-topic Zipf text: each sentence
    draws from one half of the vocabulary, so frequent words have
    structure the loss can fall on."""
    rng = np.random.default_rng(seed)
    half = rows // 2
    cdf = np.cumsum(1.0 / np.arange(1, half + 1))
    cdf /= cdf[-1]
    topic = rng.integers(0, 2, size=(sentences, 1))
    ids = np.minimum(np.searchsorted(
        cdf, rng.random((sentences, SENTENCE_LEN))), half - 1) + topic * half
    cover = rng.permutation(rows)
    words = [f"w{i}" for i in range(rows)]
    with open(path, "w") as f:
        for lo in range(0, rows, SENTENCE_LEN):
            f.write(" ".join(words[i] for i in cover[lo:lo + SENTENCE_LEN]))
            f.write("\n")
        for row in ids:
            f.write(" ".join(words[i] for i in row))
            f.write("\n")


def devices_of(array) -> list:
    return sorted(str(d) for d in array.devices())


def check_on_backend(what: str, array, platform: str) -> None:
    check(all(d.platform == platform for d in array.devices()),
          f"{what} lives on {devices_of(array)}, not on {platform}")


def check_trained_rows(what: str, rows: np.ndarray) -> None:
    check(np.isfinite(rows).all(), f"{what}: non-finite values")
    check((np.abs(rows).max(axis=1) > 0).all(),
          f"{what}: a row the corpus touched is still zero")


def ps_arm(corpus: str, rows: int, dim: int, builds: ProgramBuilds,
           platform: str) -> dict:
    """models/wordembedding/main.py run() with -use_ps=true shuts the zoo
    down before it returns, and the checks below read the tables and the
    trainer first. So this wires the same classes in the same order with
    the same defaults as run() does for the local arm's arguments with
    -use_ps=true."""
    import multiverso_tpu as mv
    from multiverso_tpu.models.wordembedding import (
        Dictionary, PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus,
        Word2VecConfig)
    from multiverso_tpu.util.dashboard import Dashboard

    t0 = time.monotonic()
    mark = builds.mark()
    config = Word2VecConfig(embedding_size=dim, window=WINDOW,
                            negative=NEGATIVE, epochs=1, min_count=1,
                            use_ps=True, neg_block=NEG_BLOCK)
    dictionary = Dictionary.build(corpus, min_count=config.min_count)
    check(dictionary.size == rows, f"vocabulary {dictionary.size} != {rows}")
    mv.init([f"-rpc_timeout_s={ARM_DEADLINE_S}"])
    model = PSWord2Vec(config, dictionary)
    tokenized = TokenizedCorpus.build(dictionary, corpus)
    trainer = PSDeviceCorpusTrainer(model, tokenized)

    gets0 = Dashboard.get("SERVER_PROCESS_GET").count
    adds0 = Dashboard.get("SERVER_PROCESS_ADD").count
    group_losses = []
    warm = {}

    def on_group(_words):
        group_losses.append(trainer.last_loss)
        if len(group_losses) == WARM_GROUPS:
            float(trainer.last_loss)  # everything dispatched has run
            warm["at"] = time.monotonic()
            warm["mark"] = builds.mark()

    loss_sum, pairs = trainer.train_epoch(seed=config.seed,
                                          block_hook=on_group)
    t_end = time.monotonic()
    check(len(group_losses) >= WARM_GROUPS + 10,
          f"epoch had only {len(group_losses)} groups")
    after_warm = builds.since(warm["mark"])

    losses = [float(x) for x in group_losses]
    check(all(math.isfinite(x) for x in losses) and math.isfinite(loss_sum),
          f"non-finite loss: {losses}")
    # Every full group has the same number of centers, and the pairs per
    # center vary by well under a percent, so group loss sums compare
    # directly. The epoch's last group is its short tail and is left
    # out. The corpus ends on its topic text, where the loss falls by
    # about a seventh; an untrained model would stay within noise of 1.
    late = sum(losses[-9:-1]) / 8
    check(pairs > 0 and late < 0.95 * losses[0],
          f"late groups' loss {late:.1f} is not below the first "
          f"group's {losses[0]:.1f}")

    gets = Dashboard.get("SERVER_PROCESS_GET").count - gets0
    adds = Dashboard.get("SERVER_PROCESS_ADD").count - adds0
    check(gets > 0 and adds > 0,
          f"no traffic crossed the server actor: gets={gets} adds={adds}")

    hot = np.arange(64, dtype=np.int32)  # ids sort by count: the hot rows
    check_trained_rows("PS output table", model._out_table.get_rows(hot))
    check(np.isfinite(model._in_table.get_rows(hot)).all(),
          "PS input table: non-finite values")
    tables = [t._data for t in mv.current_zoo().server_tables
              if hasattr(t, "_data")]
    check(len(tables) == 2 and tables[0].shape[0] >= rows
          and tables[0].shape[1] == dim, "unexpected server tables")
    for data in tables:
        check_on_backend("server table", data, platform)
    check_on_backend("corpus", trainer._corpus.flat, platform)
    placement = {"table": sorted({d for t in tables for d in devices_of(t)}),
                 "corpus": devices_of(trainer._corpus.flat)}
    mv.shutdown()
    return {"cold_setup_s": round(warm["at"] - t0, 1),
            "steady_s": round(t_end - warm["at"], 1),
            "groups": len(losses), "warm_groups": WARM_GROUPS,
            "first_group_loss": round(losses[0], 1),
            "late_groups_loss": round(late, 1),
            "mean_pair_loss": round(loss_sum / pairs, 4),
            "server_gets": gets, "server_adds": adds,
            "builds": builds.since(mark),
            "built_after_warmup": after_warm["programs_built"],
            "compiled_after_warmup": after_warm["programs_compiled"],
            "placement": placement}


def local_arm(corpus: str, rows: int, dim: int, builds: ProgramBuilds,
              platform: str) -> dict:
    """The CLI's own function, -use_ps=false: DeviceCorpusTrainer on
    embeddings the trainer holds itself."""
    import jax.numpy as jnp

    from multiverso_tpu.models.wordembedding.main import run

    t0 = time.monotonic()
    mark = builds.mark()
    model = run([f"-train_file={corpus}", "-use_ps=false", f"-size={dim}",
                 f"-window={WINDOW}", f"-negative={NEGATIVE}",
                 f"-neg_block={NEG_BLOCK}", "-min_count=1", "-epoch=1",
                 "-output_file="])
    t_end = time.monotonic()
    # The CLI takes no hook, so cold ends where its last program was
    # built: set-up and compilation before it, steady running after.
    cold_end = builds.last_build_since(mark, default=t0)
    built = builds.since(mark)
    emb_in, emb_out = model._emb_in, model._emb_out
    check(emb_in.shape == (rows, dim) and emb_out.shape == (rows, dim),
          f"embedding shapes {emb_in.shape} {emb_out.shape}")
    check_trained_rows("local output embeddings", np.asarray(emb_out[:64]))
    check(bool(jnp.isfinite(emb_in).all()),  # reduced on the device
          "local input embeddings: non-finite values")
    total = model.dictionary.total_count
    check(abs(model.trained_words - total) <= 0.01 * total,
          f"trained {model.trained_words} of {total} words")
    check_on_backend("trainer embeddings", emb_in, platform)
    return {"cold_setup_s": round(cold_end - t0, 1),
            "steady_s": round(t_end - cold_end, 1), "builds": built,
            "placement": {"trainer_embeddings": devices_of(emb_in)}}


def table_arm(rows: int, dim: int, builds: ProgramBuilds,
              platform: str) -> dict:
    """A server that answers a few requests, each checked against numpy.
    The same request sequence runs twice: the first pass is cold, the
    second must build nothing."""
    import jax.numpy as jnp

    import multiverso_tpu as mv
    from multiverso_tpu.updater import AddOption
    from multiverso_tpu.updater.engine import pad_ids
    from multiverso_tpu.updater.rules import ADAGRAD_EPS

    rng = np.random.default_rng(7)
    t0 = time.monotonic()
    mark = builds.mark()
    mv.init([f"-rpc_timeout_s={ARM_DEADLINE_S}"])
    array = mv.create_array_table(rows * dim)
    dense = mv.create_matrix_table(rows, dim, updater_type="sgd")
    stateful = mv.create_matrix_table(rows, dim, updater_type="adagrad")
    sparse = mv.create_matrix_table(rows, dim, is_sparse=True)
    kv = mv.create_kv_table()
    for table in mv.current_zoo().server_tables:
        if hasattr(table, "_data"):
            check_on_backend("server table", table._data, platform)

    array_shadow = np.zeros(rows * dim, np.float32)
    dense_shadow = np.zeros((rows, dim), np.float32)
    n_host = min(65_536, rows // 2)
    host_ids = np.sort(rng.choice(rows, n_host, replace=False)) \
        .astype(np.int32)
    dev_ids_host = rng.integers(0, rows, (3, n_host // 4)).astype(np.int32)
    dev_ids = jnp.asarray(dev_ids_host)  # any shape, duplicates sum
    ada_rows = np.zeros((n_host, dim), np.float32)
    ada_hist = np.zeros((n_host, dim), np.float32)
    ada = AddOption(learning_rate=0.1, rho=0.05)
    dirty_ids = (np.arange(min(100_000, rows // 4), dtype=np.int32) * 3)
    dirty_mirror = jnp.asarray(pad_ids(dirty_ids, rows))
    other_worker = AddOption(worker_id=1)  # dirties the rows for worker 0
    ids0, vals0 = sparse.get_dirty_device()  # at first every row is dirty
    check(ids0.size == rows and vals0.shape == (rows, dim)
          and float(jnp.abs(vals0).max()) == 0.0, "sparse initial dirty get")
    sparse_sum = np.zeros((dirty_ids.size, dim), np.float32)
    kv_keys = np.arange(0, 4096, 7, dtype=np.int64)
    kv_shadow = np.zeros(kv_keys.size, np.float32)

    def close(got, want, what):
        check(np.allclose(np.asarray(got), want, rtol=1e-3, atol=1e-5),
              f"table arm: {what} disagrees with the numpy shadow")

    def one_pass():
        # array table: whole-table host add / get
        delta = rng.random(rows * dim, dtype=np.float32)
        array.add(delta)
        np.add(array_shadow, delta, out=array_shadow)
        close(array.get(), array_shadow, "array add/get")
        # dense sgd table: whole-table add / get
        delta = delta.reshape(rows, dim)
        dense.add(delta)
        np.subtract(dense_shadow, delta, out=dense_shadow)
        close(dense.get(), dense_shadow, "matrix add/get")
        # rows by host ids
        row_delta = rng.random((n_host, dim), dtype=np.float32)
        dense.add_rows(host_ids, row_delta)
        dense_shadow[host_ids] -= row_delta
        close(dense.get_rows(host_ids), dense_shadow[host_ids],
              "add_rows/get_rows with host ids")
        # rows by device-resident ids, device delta
        close(dense.get_rows_device(dev_ids), dense_shadow[dev_ids_host],
              "get_rows with device ids")
        dev_delta = rng.random(dev_ids_host.shape + (dim,),
                               dtype=np.float32)
        dense.add_rows(dev_ids, jnp.asarray(dev_delta))
        np.subtract.at(dense_shadow, dev_ids_host, dev_delta)
        touched = np.unique(dev_ids_host)
        close(dense.get_rows(touched), dense_shadow[touched],
              "add_rows with device ids")
        # stateful updater: AdaGrad keeps per-worker squared gradients
        grad = row_delta / ada.learning_rate
        np.add(ada_hist, grad * grad, out=ada_hist)
        np.subtract(ada_rows, ada.rho * grad
                    / np.sqrt(ada_hist + ADAGRAD_EPS), out=ada_rows)
        stateful.add_rows(host_ids, row_delta, option=ada)
        close(stateful.get_rows(host_ids), ada_rows, "adagrad add_rows")
        # sparse table: fused add + dirty pull, host ids then the mirror
        one = jnp.ones((dirty_ids.size, dim), jnp.float32)
        for mirror in (None, dirty_mirror):
            ids, vals = sparse.add_get_dirty_device(
                dirty_ids, one, option=other_worker, get_worker=0,
                row_ids_device=mirror)
            np.add(sparse_sum, 1.0, out=sparse_sum)
            check(np.array_equal(ids, dirty_ids), "dirty ids")
            close(vals, sparse_sum, "add_get_dirty_device")
        # KV table
        kv_delta = rng.random(kv_keys.size, dtype=np.float32)
        kv.add(kv_keys, kv_delta)
        np.add(kv_shadow, kv_delta, out=kv_shadow)
        got = kv.get(kv_keys)
        close([got[int(k)] for k in kv_keys], kv_shadow, "kv add/get")

    one_pass()
    t_warm = time.monotonic()
    warm_mark = builds.mark()
    one_pass()
    t_end = time.monotonic()
    after_warm = builds.since(warm_mark)
    refused = refused_request(dense, dim)
    close(dense.get_rows(host_ids), dense_shadow[host_ids],
          "get_rows after the refused request")
    mv.barrier()
    mv.shutdown()
    return {"cold_setup_s": round(t_warm - t0, 1),
            "steady_s": round(t_end - t_warm, 1),
            "builds": builds.since(mark),
            "built_after_warmup": after_warm["programs_built"],
            "compiled_after_warmup": after_warm["programs_compiled"],
            "refused_request": refused}


def refused_request(table, dim: int) -> str:
    """A request the device must refuse: a device-key Get of twice the
    HBM there is. The failure happens inside the server actor; it has to
    come back to the caller as an exception (any other outcome fails the
    smoke), and the actor has to go on answering, which the caller
    checks next. Sized from the limit the backend reports; a backend
    that reports none (the CPU) has no such request."""
    import jax
    import jax.numpy as jnp

    import multiverso_tpu as mv

    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if not limit:
        return "not tried: the backend reports no memory limit"
    ids = jnp.zeros((2 * limit * len(jax.devices()) // (dim * 4),),
                    jnp.int32)
    try:
        table.get_rows_device(ids).block_until_ready()
    except (mv.TableRequestError, jax.errors.JaxRuntimeError) as exc:
        return f"{type(exc).__name__}: {str(exc)[:120]}"
    raise SmokeFailure(f"a Get of {ids.size} rows, twice the HBM, was "
                       "answered instead of refused")


def scatter_arm(rows: int, dim: int, platform: str) -> dict:
    """The rows programs' scatter-add against XLA's scatter on the same
    table, ids and deltas: on the first device alone, and row-sharded
    over every device where there are several. On a TPU (and a row of
    whole 128-lane tiles) the engine takes the sorted-runs kernel
    (updater/row_scatter.py), which this holds to XLA's result: bit for
    bit where the ids are distinct, to rounding where they repeat."""
    import jax
    import jax.numpy as jnp

    from multiverso_tpu.sharding import mesh as meshlib
    from multiverso_tpu.updater import UpdateEngine
    from multiverso_tpu.updater.rules import fast_rows

    rng = np.random.default_rng(28)
    k = 40_000
    n_devices = len(jax.local_devices())
    padded = meshlib.padded_size(rows, n_devices)
    distinct = rng.permutation(padded + k // 8)[:k].astype(np.int32)
    repeated = rng.integers(0, padded, (2, k // 2)).astype(np.int32)
    repeated[0, :5000] = padded - 1      # one long run, on the last shard
    took = {}
    for n in sorted({1, n_devices}):
        mesh = meshlib.local_mesh(n)
        sharding = meshlib.row_sharded(mesh)
        fast = fast_rows((padded, dim), np.float32, k, mesh)
        check(fast == (platform == "tpu" and dim % 128 == 0),
              f"the path on {n} device(s) follows the platform and the row")
        engine = UpdateEngine(None, (padded, dim), np.float32, 1, sharding)
        plain = jax.jit(lambda t, i, d: t.at[i].add(d, mode="drop"),
                        out_shardings=sharding)
        for name, ids, exact in (("distinct", distinct, True),
                                 ("repeated", repeated, False)):
            table = jax.device_put(rng.normal(size=(padded, dim)).astype(
                np.float32), sharding)
            ids = jax.device_put(ids, meshlib.replicated(mesh))
            delta = jax.device_put(rng.normal(size=ids.shape + (dim,)).astype(
                np.float32), meshlib.replicated(mesh))
            want = np.asarray(plain(table, ids, delta))
            got = engine.apply_rows(table, ids, delta)
            check_on_backend(f"scatter-add result ({n} devices)", got,
                             platform)
            check(len(devices_of(got)) == n, "the table kept its devices")
            got = np.asarray(got)
            if exact:
                check(np.array_equal(got, want),
                      f"{name} ids on {n} device(s): bit-equal to XLA's")
            else:
                check(np.allclose(got, want, rtol=1e-4, atol=1e-4),
                      f"{name} ids on {n} device(s): equal to XLA's to "
                      "rounding")
        took[f"{n}_devices"] = "sorted_runs_kernel" if fast else "xla_scatter"
    return {"path": took}


def peak_memory():
    import jax
    peaks = {str(d): (d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()}
    return peaks if any(peaks.values()) else "not reported"


def run_arms(workdir: str, rows: int, dim: int, sentences: int) -> dict:
    """The four arms on whatever backend JAX has (main() has already
    refused anything but a TPU; tests call this at a toy size on the
    CPU). Raises on the first failed check."""
    import jax
    platform = jax.devices()[0].platform
    builds = ProgramBuilds()
    corpus = os.path.join(workdir, "corpus.txt")
    t0 = time.monotonic()
    write_corpus(corpus, rows, sentences, seed=0)
    print(f"[chip_smoke] corpus written in {time.monotonic() - t0:.1f}s",
          flush=True)
    report = {}
    for name, arm in (("ps", lambda: ps_arm(corpus, rows, dim, builds,
                                             platform)),
                      ("local", lambda: local_arm(corpus, rows, dim, builds,
                                                  platform)),
                      ("tables", lambda: table_arm(rows, dim, builds,
                                                   platform)),
                      ("scatter", lambda: scatter_arm(rows, dim,
                                                      platform))):
        with deadline(ARM_DEADLINE_S):
            report[name] = arm()
        report[name]["peak_bytes_in_use"] = peak_memory()
        print(f"[chip_smoke] {name} arm passed: "
              f"{json.dumps(report[name])}", flush=True)
    return report


def main() -> int:
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(f"[chip_smoke] platform={device['platform']} "
          f"device_kind={device['kind']} devices={device['count']}",
          flush=True)
    if device["platform"] != "tpu":
        print(f"[chip_smoke] FAIL: the backend is {device['platform']!r}, "
              "not 'tpu'; nothing was run", file=sys.stderr)
        return 1
    from multiverso_tpu.util import compile_cache
    cache_dir = compile_cache.enable()
    files_before = len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) \
        else 0
    print(f"[chip_smoke] compile cache: {cache_dir} "
          f"({files_before} files before this run)", flush=True)
    t0 = time.monotonic()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        run_arms(workdir, ROWS, DIM, SENTENCES)
    print(f"[chip_smoke] all arms passed in {time.monotonic() - t0:.0f}s; "
          f"compile cache now holds {len(os.listdir(cache_dir))} files",
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - not survived: reported, then out
        traceback.print_exc()
        sys.stderr.flush()
        # Actor threads may still be parked on the device; leave without
        # waiting for them so a failure can never turn into a hang.
        os._exit(1)
    sys.exit(code)

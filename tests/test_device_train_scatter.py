"""The local trainer's group program scatter-adds its rows through
rules.scatter_add, as the tables' rows programs do: XLA's scatter on
the CPU (what every CPU test trains with), the sorted-runs kernel where
rules.fast_rows says so. Here the kernel runs in Pallas' interpreter
with the platform steered, as in tests/test_updater.py, and a step is
held to the same step with XLA's scatter; and a dispatch counts the
path each of its scatter-add calls took."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from multiverso_tpu.models.wordembedding import device_train
from multiverso_tpu.updater import row_scatter, rules
from multiverso_tpu.util.dashboard import Dashboard

ROWS, DIM = 3000, 128
C, W, K, NEG_BLOCK = 2048, 5, 5, 8
N_KEPT = 3 * C


def _zipf_stream(rng):
    """A kept stream of Zipf(1.0) words in sentences of 40, so that a
    block's band, its centers and its negatives name rows many times
    and share rows, with the alias tables of a Zipf unigram law."""
    rank = np.exp(rng.random(N_KEPT) * np.log(ROWS)).astype(np.int64) - 1
    kept = ((rank * 2654435761) % ROWS).astype(np.int32)
    ksent = (np.arange(N_KEPT) // 40).astype(np.int32)
    neg_prob = rng.random(ROWS).astype(np.float32)
    neg_alias = ((np.exp(rng.random(ROWS) * np.log(ROWS)).astype(np.int64)
                  * 2654435761) % ROWS).astype(np.int32)
    return device_train._pad_stream(C, W, jnp.asarray(kept),
                                    jnp.asarray(ksent)) + (
        jnp.asarray(neg_prob), jnp.asarray(neg_alias))


def _step(cbow, tables, stream, key, base, lr):
    """One ``_apply_step`` as a program of its own, traced now."""

    def step(emb_in, emb_out, base, lr):
        return device_train._apply_step(
            C, W, K, cbow, emb_in, emb_out, *stream, key, base, lr,
            jnp.int32(N_KEPT), neg_block=NEG_BLOCK)[:2]

    return [np.asarray(t) for t in jax.jit(step)(
        *tables, jnp.int32(base), jnp.float32(lr))]


def _ids_of_the_step(cbow, stream, key, base):
    """The ids the step adds into (input table, output table)."""
    k_shrink, k_idx, k_keep = jax.random.split(key, 3)
    centers, band, _ = device_train._band_former(
        C, W, jnp.int32(N_KEPT), stream[0], stream[1], k_shrink,
        jnp.int32(base))
    negs = device_train._draw_negs(C, K, NEG_BLOCK, stream[2], stream[3],
                                   k_idx, k_keep)
    centers, band, negs = (np.asarray(x).reshape(-1)
                           for x in (centers, band, negs))
    if cbow:
        return band, np.concatenate([centers, negs])
    return centers, np.concatenate([band, negs])


def _steer_to_the_kernel(monkeypatch, calls):
    """What a test steers, as tests/test_updater.py does: the platform
    rules.fast_rows sees, and the kernel interpreted. ``calls`` takes
    the id count of every call."""

    kernel = row_scatter.scatter_add

    def interpreted(table, ids, delta, mesh=None):
        calls.append(ids.shape[0])
        return kernel(table, ids, delta, mesh, interpret=True)

    monkeypatch.setattr(rules, "_platform", lambda mesh: "tpu")
    monkeypatch.setattr(rules.row_scatter, "scatter_add", interpreted)


def _scattered(tables, handed):
    """rules.scatter_add of each table's handed ids and delta rows, as
    a program traced now: by the path fast_rows gives now."""
    return [np.asarray(jax.jit(
        lambda t, i, d: rules.scatter_add(t, i, d))(t, i, d))
        for t, (i, d) in zip(tables, handed)]


@pytest.mark.parametrize("cbow", [False, True], ids=["sgns", "cbow"])
def test_a_step_with_sorted_runs_equals_the_step_with_xlas_scatter(
        monkeypatch, cbow):
    rng = np.random.default_rng(30 + cbow)
    stream = _zipf_stream(rng)
    tables = [jnp.asarray(rng.normal(size=(ROWS, DIM), scale=0.3)
                          .astype(np.float32)) for _ in range(2)]
    key = jax.random.PRNGKey(7)
    want = _step(cbow, tables, stream, key, C, 0.025)
    idle_want = _step(cbow, tables, stream, key, N_KEPT, 0.0)

    # The step's own ids and delta rows, as it hands them over: the two
    # programs round a band's gradient differently (XLA fuses its sum
    # of shifted slices by what consumes it), so the rows named once
    # are held bit for bit on these, and the whole step within float32
    # rounding.
    handed = []
    with monkeypatch.context() as m:
        m.setattr(device_train, "scatter_add",
                  lambda table, ids, delta: handed.append(
                      (np.asarray(ids), np.asarray(delta))) or table)
        with jax.disable_jit():
            device_train._apply_step(
                C, W, K, cbow, *tables, *stream, key, jnp.int32(C),
                jnp.float32(0.025), jnp.int32(N_KEPT),
                neg_block=NEG_BLOCK)
    ids = _ids_of_the_step(cbow, stream, key, C)
    # one call a table, band or centers with the negatives as one
    assert [i.size for i, _ in handed] == [i.size for i in ids]
    assert ids[1].size == (C if cbow else C + 2 * W) + C // NEG_BLOCK * K
    for (flat, _), named in zip(handed, ids):
        np.testing.assert_array_equal(flat.reshape(-1), named)
    shared = np.intersect1d(ids[1][:-C // NEG_BLOCK * K],
                            ids[1][-C // NEG_BLOCK * K:])
    assert shared.size > 5  # rows named by the band and by negatives
    xla = _scattered(tables, handed)

    calls = []
    _steer_to_the_kernel(monkeypatch, calls)
    got = _step(cbow, tables, stream, key, C, 0.025)
    assert calls == [i.size for i in ids]
    runs = _scattered(tables, handed)
    assert calls == 2 * [i.size for i in ids]
    for table, new, old, a, b, named in zip(tables, got, want, runs, xla,
                                            ids):
        assert not np.array_equal(new, np.asarray(table))
        rows, times = np.unique(named, return_counts=True)
        assert (times > 1).sum() > 50 and (times == 1).sum() > 50
        once = np.setdiff1d(np.arange(ROWS), rows[times > 1])
        np.testing.assert_array_equal(a[once], b[once])
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
        untouched = np.setdiff1d(np.arange(ROWS), rows)
        np.testing.assert_array_equal(new[untouched], old[untouched])
        np.testing.assert_allclose(new, old, rtol=1e-5, atol=1e-6)

    # A padded step of a group's tail (base = n_kept, lr = 0) adds
    # zeros, on either path: both tables as they were.
    idle = _step(cbow, tables, stream, key, N_KEPT, 0.0)
    assert calls == 3 * [i.size for i in ids]
    for table, new, old in zip(tables, idle, idle_want):
        np.testing.assert_array_equal(new, np.asarray(table))
        np.testing.assert_array_equal(old, np.asarray(table))


def _trainer(tmp_path, centers, **config):
    from multiverso_tpu.models.wordembedding import (
        DeviceCorpusTrainer, Dictionary, TokenizedCorpus, Word2Vec,
        Word2VecConfig)
    rng = np.random.default_rng(0)
    words = [f"w{i}" for i in range(40)]
    path = tmp_path / "corpus.txt"
    path.write_text("\n".join(
        " ".join(rng.choice(words, size=20)) for _ in range(150)))
    d = Dictionary.build(str(path), min_count=1)
    tok = TokenizedCorpus.build(d, str(path))
    model = Word2Vec(Word2VecConfig(
        embedding_size=DIM, window=2, epochs=1, sample=0,
        init_learning_rate=0.01, **config), d)
    return DeviceCorpusTrainer(model, tok, centers_per_step=centers,
                               steps_per_dispatch=2)


def _path_counts():
    return np.array([Dashboard.get(name).count for name in
                     ("UPDATE_ROWS_FAST", "UPDATE_ROWS_XLA")])


PATH_CASES = {
    # name: (config, centers a step, scatter-add calls a block)
    "sgns": (dict(neg_block=8), 1024, 2),
    "cbow": (dict(cbow=True), 1024, 2),
    "hs": (dict(hs=True), 1024, 2),
    "per_pair": (dict(per_pair=True), 1024, 8),  # two an offset, W = 2
}


@pytest.mark.parametrize("case", sorted(PATH_CASES))
def test_a_dispatch_on_the_cpu_counts_xlas_scatter(tmp_path, case):
    config, centers, calls = PATH_CASES[case]
    trainer = _trainer(tmp_path, centers, **config)
    blocks = []
    before = _path_counts()
    loss, examples = trainer.train_epoch(
        seed=1, group_hook=lambda words: blocks.append(words))
    assert np.isfinite(loss) and examples > 0
    steps = -(-3000 // trainer._C)
    assert len(blocks) == -(-steps // 2)
    np.testing.assert_array_equal(_path_counts() - before,
                                  [0, calls * steps])


def test_a_dispatch_counts_the_path_fast_rows_gives(tmp_path, monkeypatch):
    """2048 centers a step are the crossover's id count: both tables'
    calls take the kernel; under it (1024 centers: 1024 ids into the
    input table, 1024 + 4 + 128 * 5 into the output table) only XLA's
    scatter is counted, though the platform says TPU."""
    _steer_to_the_kernel(monkeypatch, [])
    # Group programs traced anew: the module's cache would hand a
    # program traced this way to a later test.
    monkeypatch.setattr(device_train, "_group_fn",
                        device_train._group_fn.__wrapped__)
    trainer = _trainer(tmp_path, 2048, neg_block=8)
    assert rules.fast_rows((ROWS, DIM), np.float32, 2048)
    before = _path_counts()
    loss, _ = trainer.train_epoch(seed=1)
    assert np.isfinite(loss)
    steps = -(-3000 // 2048)  # two blocks, one dispatch
    np.testing.assert_array_equal(_path_counts() - before,
                                  [2 * steps, 0])
    small = _trainer(tmp_path, 1024, neg_block=8)
    before = _path_counts()
    small.train_epoch(seed=1)
    np.testing.assert_array_equal(_path_counts() - before, [0, 2 * 3])


def test_every_dispatch_of_a_group_has_one_signature(tmp_path):
    """A group program that calls an exported one (the kernel's chunk
    program on a TPU) returns committed arrays; were the tables or an
    epoch's key uncommitted when they first go in, jit would build the
    same program again for the second dispatch and a third time for the
    second epoch's first (found on the chip, PR 30: a program built
    inside the benchmark's window). So what goes in is committed from
    the start, whatever the program holds."""
    trainer = _trainer(tmp_path, 512, neg_block=8)
    seen = []
    group = trainer._group

    def recording(*args):
        seen.append(tuple(bool(a.committed) for a in args))
        return group(*args)

    trainer._group = recording
    for epoch in (1, 2):
        trainer.train_epoch(seed=epoch)
    assert len(seen) == 2 * -(-(-(-3000 // 512)) // 2) and len(set(seen)) == 1
    tables_and_key = (0, 1, 6)
    assert all(seen[0][i] for i in tables_and_key)
    assert trainer.model._emb_in.committed and trainer.model._emb_out.committed

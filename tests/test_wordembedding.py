"""WordEmbedding application tests.

Covers the reference's component behaviors (dictionary/huffman/reader,
ref: Applications/WordEmbedding/src/) plus end-to-end training quality:
on a synthetic corpus with two disjoint topic clusters, within-topic
embedding similarity must exceed cross-topic similarity for every mode
(SGNS skip-gram, CBOW, hierarchical softmax, PS-backed).
"""

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.models.wordembedding import (Dictionary, PSWord2Vec,
                                                 Word2Vec, Word2VecConfig,
                                                 build_huffman,
                                                 iter_pair_batches,
                                                 sentence_pairs)


def write_topic_corpus(path, n_sentences=800, seed=0):
    """Two topic clusters; words co-occur only within their topic."""
    rng = np.random.default_rng(seed)
    topics = [[f"a{i}" for i in range(8)], [f"b{i}" for i in range(8)]]
    lines = []
    for _ in range(n_sentences):
        topic = topics[rng.integers(0, 2)]
        lines.append(" ".join(rng.choice(topic, size=12)))
    path.write_text("\n".join(lines))


def topic_separation(model, dictionary):
    emb = model.embeddings
    emb = emb / np.maximum(np.linalg.norm(emb, axis=1, keepdims=True),
                           1e-9)
    ids_a = [dictionary.word2id[w] for w in dictionary.words
             if w.startswith("a")]
    ids_b = [dictionary.word2id[w] for w in dictionary.words
             if w.startswith("b")]
    sims = emb @ emb.T
    within = (sims[np.ix_(ids_a, ids_a)].mean()
              + sims[np.ix_(ids_b, ids_b)].mean()) / 2
    across = sims[np.ix_(ids_a, ids_b)].mean()
    return within - across


class TestDictionary:
    def test_build_and_counts(self, tmp_path):
        path = tmp_path / "c.txt"
        path.write_text("x x x y y z\nx y q")
        d = Dictionary.build(str(path), min_count=2)
        assert d.word2id["x"] == 0  # most frequent first
        assert set(d.words) == {"x", "y"}
        assert d.counts[d.word2id["x"]] == 4

    def test_store_load_roundtrip(self, tmp_path):
        path = tmp_path / "c.txt"
        path.write_text("x x x y y z z z z")
        d = Dictionary.build(str(path), min_count=1)
        d.store(str(tmp_path / "vocab.txt"))
        d2 = Dictionary.load(str(tmp_path / "vocab.txt"))
        assert d2.words == d.words
        np.testing.assert_array_equal(d2.counts, d.counts)

    def test_negative_table_sums_to_one(self, tmp_path):
        path = tmp_path / "c.txt"
        path.write_text("x x x y y z")
        d = Dictionary.build(str(path), min_count=1)
        table = d.negative_table()
        assert table.sum() == pytest.approx(1.0, rel=1e-5)


class TestHuffman:
    def test_codes_are_prefix_free(self):
        counts = np.array([50, 30, 10, 5, 3, 2])
        tree = build_huffman(counts)
        codes = []
        for i in range(len(counts)):
            length = tree.code_lengths[i]
            codes.append(tuple(tree.codes[i, :length]))
        for i, c1 in enumerate(codes):
            for j, c2 in enumerate(codes):
                if i != j:
                    assert c1 != c2[:len(c1)], "prefix violation"

    def test_frequent_words_get_short_codes(self):
        counts = np.array([1000, 500, 10, 5, 2, 1, 1, 1])
        tree = build_huffman(counts)
        assert tree.code_lengths[0] <= tree.code_lengths[-1]

    def test_inner_node_count(self):
        tree = build_huffman(np.array([5, 4, 3, 2, 1]))
        assert tree.num_inner_nodes == 4  # vocab-1 inner nodes


class TestPairGeneration:
    def test_sentence_pairs_within_window(self):
        rng = np.random.default_rng(0)
        ids = np.arange(10, dtype=np.int32)
        pairs = sentence_pairs(ids, window=3, rng=rng)
        assert pairs.shape[0] == 2
        assert (pairs[0] != pairs[1]).any()
        # Every pair must be within the max window.
        pos = {int(v): i for i, v in enumerate(ids)}
        for c, t in pairs.T:
            assert 1 <= abs(pos[int(c)] - pos[int(t)]) <= 3

    def test_batches_have_fixed_shape(self, tmp_path):
        path = tmp_path / "c.txt"
        write_topic_corpus(path, n_sentences=50)
        d = Dictionary.build(str(path), min_count=1)
        batches = list(iter_pair_batches(d, str(path), batch_size=256,
                                         window=3, subsample=0))
        assert all(b.centers.shape == (256,) for b in batches)
        assert all(b.count <= 256 for b in batches)

    def test_batch_words_sum_to_corpus_tokens(self, tmp_path):
        # words (the lr-schedule unit) must count corpus words, not pairs
        # (pairs ~ window x words).
        path = tmp_path / "c.txt"
        write_topic_corpus(path, n_sentences=50)
        d = Dictionary.build(str(path), min_count=1)
        batches = list(iter_pair_batches(d, str(path), batch_size=256,
                                         window=3, subsample=0))
        total_words = sum(b.words for b in batches)
        total_pairs = sum(b.count for b in batches)
        assert total_words == pytest.approx(d.total_count, rel=1e-6)
        assert total_pairs > 2 * total_words  # different units indeed

    def test_tail_padding_pairs_do_not_train(self, tmp_path):
        # A tail batch's padded (0,0) rows must not push word 0 toward
        # itself as a positive pair: with every pair masked out, the step
        # must be an exact no-op.
        from multiverso_tpu.models.wordembedding.data import PairBatch
        path = tmp_path / "c.txt"
        path.write_text("q0 q1 q2 q0 q1 q2\n")
        d = Dictionary.build(str(path), min_count=1)
        config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                                init_learning_rate=0.1, batch_size=16,
                                sample=0)
        model = Word2Vec(config, d)
        before = np.asarray(model._emb_in).copy()
        all_padding = PairBatch(np.zeros(16, np.int32),
                                np.zeros(16, np.int32), count=0, words=0)
        loss = model.train_batch_async(all_padding)
        assert float(loss) == 0.0
        np.testing.assert_array_equal(np.asarray(model._emb_in), before)


class TestStopwords:
    def test_cli_stopwords_filtered(self, tmp_path):
        # ref: Applications/WordEmbedding/src/reader.cpp — the -stopwords
        # table drops listed words before training.
        from multiverso_tpu.models.wordembedding.main import run
        corpus = tmp_path / "c.txt"
        corpus.write_text("the a0 the a1 the a2 a0 a1\n"
                          "the a1 a2 the a0 a2 a1 a0\n" * 10)
        stop = tmp_path / "stop.txt"
        stop.write_text("the\n")
        model = run([f"-train_file={corpus}", f"-stopwords={stop}",
                     "-min_count=1", "-size=8", "-epoch=1",
                     "-batch_size=64",
                     f"-output_file={tmp_path / 'v.txt'}"])
        assert "the" not in model.dictionary.word2id
        assert "a0" in model.dictionary.word2id


class TestDeviceCorpusTrainer:
    def test_device_pipeline_separates_topics(self, tmp_path):
        # The HBM-resident pipeline (in-jit subsample/window/negatives)
        # must learn the same structure the host-batch path does.
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                init_learning_rate=0.01, batch_size=1024,
                                sample=0)
        model = Word2Vec(config, d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=128,
                                      steps_per_dispatch=4)
        losses = []
        for epoch in range(3):
            loss, pairs = trainer.train_epoch(seed=epoch)
            losses.append(loss / max(pairs, 1))
        assert losses[-1] < losses[0], losses
        sep = topic_separation(model, d)
        assert sep > 0.3, f"separation {sep}"
        assert model.trained_words == pytest.approx(3 * tok.flat.size)

    def test_device_pipeline_subsample_counts(self, tmp_path):
        # With aggressive subsampling the trained pair count must drop
        # but raw-word accounting (the lr clock) must still cover the
        # whole corpus (ref: reader.cpp counts discarded words too).
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        pair_counts = {}
        for sample in (0, 1e-4):
            config = Word2VecConfig(embedding_size=8, window=3, epochs=1,
                                    batch_size=256, sample=sample)
            model = Word2Vec(config, d)
            trainer = DeviceCorpusTrainer(model, tok,
                                          centers_per_step=128,
                                          steps_per_dispatch=2)
            _, pairs = trainer.train_epoch(seed=0)
            pair_counts[sample] = pairs
            assert model.trained_words == pytest.approx(tok.flat.size)
        assert pair_counts[1e-4] < 0.7 * pair_counts[0]

    def test_device_pipeline_max_steps_and_accounting(self, tmp_path):
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=100)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        model = Word2Vec(Word2VecConfig(embedding_size=8, window=2,
                                        epochs=1, batch_size=128,
                                        sample=0), d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=64,
                                      steps_per_dispatch=4)
        # A truncated (warmup-style) epoch trains only max_steps steps.
        _, pairs = trainer.train_epoch(seed=0, max_steps=2)
        assert 0 < pairs < tok.flat.size * 4  # a fraction of the epoch
        assert trainer.kept_words_trained == 2 * 64
        # lr clock advanced proportionally, not a full epoch.
        assert 0 < model.trained_words < tok.flat.size

    def test_device_pipeline_group_hook_words_sum(self, tmp_path):
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=100)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        model = Word2Vec(Word2VecConfig(embedding_size=8, window=2,
                                        epochs=1, batch_size=128,
                                        sample=0), d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=64,
                                      steps_per_dispatch=4)
        seen = []
        trainer.train_epoch(seed=0, group_hook=seen.append)
        # Hook word counts must sum to exactly the epoch's raw words
        # (the words/sec denominators depend on it).
        assert sum(seen) == pytest.approx(tok.flat.size)
        assert model.trained_words == pytest.approx(tok.flat.size)

    def test_device_pipeline_per_pair_separates_topics(self, tmp_path):
        # The quality mode (per-pair negatives, sequential window
        # sub-steps) must train at least as well as the banded fast
        # path on the topic corpus.
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                init_learning_rate=0.01,
                                batch_size=1024, sample=0,
                                per_pair=True)
        model = Word2Vec(config, d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=128,
                                      steps_per_dispatch=4)
        losses = []
        for epoch in range(3):
            loss, pairs = trainer.train_epoch(seed=epoch)
            losses.append(loss / max(pairs, 1))
        assert losses[-1] < losses[0], losses
        sep = topic_separation(model, d)
        assert sep > 0.3, f"separation {sep}"

    def test_device_pipeline_cbow_separates_topics(self, tmp_path):
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                init_learning_rate=0.02, batch_size=1024,
                                sample=0, cbow=True)
        model = Word2Vec(config, d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=128,
                                      steps_per_dispatch=4)
        losses = []
        for epoch in range(3):
            loss, examples = trainer.train_epoch(seed=epoch)
            losses.append(loss / max(examples, 1))
        assert losses[-1] < losses[0], losses
        sep = topic_separation(model, d)
        assert sep > 0.3, f"separation {sep}"

    def test_device_pipeline_hs_separates_topics(self, tmp_path):
        # Hierarchical softmax on the device pipeline: skip-gram over
        # the context word's Huffman path (code 0 = positive).
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                init_learning_rate=0.02, batch_size=1024,
                                sample=0, hs=True, negative=0)
        model = Word2Vec(config, d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=128,
                                      steps_per_dispatch=4)
        losses = []
        for epoch in range(3):
            loss, pairs = trainer.train_epoch(seed=epoch)
            losses.append(loss / max(pairs, 1))
        assert losses[-1] < losses[0], losses
        sep = topic_separation(model, d)
        assert sep > 0.3, f"separation {sep}"

    def test_device_pipeline_cbow_hs_separates_topics(self, tmp_path):
        # The last cell of the mode matrix on the device pipeline:
        # CBOW + hierarchical softmax (window mean vs the center's
        # Huffman path; ref: wordembedding.h:95-125 trains all four
        # combinations through one loop).
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                init_learning_rate=0.04,
                                batch_size=1024, sample=0, hs=True,
                                cbow=True, negative=0)
        model = Word2Vec(config, d)
        trainer = DeviceCorpusTrainer(model, tok, centers_per_step=128,
                                      steps_per_dispatch=4)
        losses = []
        for epoch in range(3):
            loss, examples = trainer.train_epoch(seed=epoch)
            losses.append(loss / max(examples, 1))
        assert losses[-1] < losses[0], losses
        sep = topic_separation(model, d)
        assert sep > 0.3, f"separation {sep}"

    def test_ps_device_pipeline_hs(self, tmp_path):
        # HS through the PS device pipeline (VERDICT r3 #5): path-node
        # ids computed in-jit, pulled/pushed as device keys.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.02,
                                    batch_size=1024, sample=0, hs=True,
                                    negative=0)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            losses = []
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=epoch)
                losses.append(loss / max(pairs, 1))
            assert losses[-1] < losses[0], losses
            sep = topic_separation(model, d)
            assert sep > 0.3, f"separation {sep}"
        finally:
            mv.shutdown()


class TestMAWord2Vec:
    def test_ma_group_trains_over_mesh(self):
        # The reference's -ma mode on the flagship: each mesh device
        # trains a table replica on its corpus shard, MV_Aggregate =
        # in-jit pmean over the mesh. Replicas must come back averaged
        # (identical) and the loss finite.
        import jax
        import jax.numpy as jnp
        from multiverso_tpu.models.wordembedding.device_train import (
            _ma_group_fn)
        from multiverso_tpu.sharding import mesh as meshlib
        ndev = len(jax.devices())
        mesh = meshlib.local_mesh(ndev)
        C, W, K, n_local, V, D, G = 64, 2, 3, 512, 40, 8, 2
        rng = np.random.default_rng(0)
        fn = _ma_group_fn(mesh, C, W, K)
        emb_in = jnp.asarray(
            (rng.random((V, D)).astype(np.float32) - 0.5) / D)
        emb_out = jnp.zeros((V, D), jnp.float32)
        kept = jnp.asarray(
            rng.integers(0, V, ndev * n_local).astype(np.int32))
        ksent = jnp.asarray(np.repeat(
            np.arange(ndev * n_local // 16, dtype=np.int32), 16))
        keys = jax.random.split(jax.random.PRNGKey(0), ndev)
        bases = jnp.asarray((np.arange(G) * C).astype(np.int32))
        lrs = jnp.full(G, 0.05, jnp.float32)
        n_kept_local = jnp.full(ndev, n_local, jnp.int32)
        neg_prob = jnp.ones(V, jnp.float32)
        neg_alias = jnp.asarray(np.arange(V, dtype=np.int32))
        before = np.asarray(emb_out).copy()
        emb_in, emb_out, loss, pairs, next_keys = fn(
            emb_in, emb_out, kept, ksent, neg_prob, neg_alias, keys,
            bases, lrs, n_kept_local)
        assert np.isfinite(float(loss)) and float(pairs) > 0
        assert not np.allclose(np.asarray(emb_out), before)  # trained
        # Averaged result is a single replicated array; keys advanced.
        assert emb_in.shape == (V, D)
        assert next_keys.shape == keys.shape
        assert not np.array_equal(np.asarray(next_keys),
                                  np.asarray(keys))
        # Chained dispatch with the advanced keys draws FRESH windows:
        # a second group over the same bases must not reproduce the
        # first group's loss (replayed keys would, bit for bit).
        _, _, loss2, _, _ = fn(
            emb_in, emb_out, kept, ksent, neg_prob, neg_alias,
            next_keys, bases, lrs, n_kept_local)
        assert float(loss2) != float(loss)


class TestMACorpusTrainer:
    def _run(self, tmp_path, overlap, sharded=False):
        from multiverso_tpu.models.wordembedding import (MACorpusTrainer,
                                                         TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=200)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))

        def body(rank):
            config = Word2VecConfig(embedding_size=8, window=2, epochs=2,
                                    init_learning_rate=0.02,
                                    batch_size=256, sample=0,
                                    negative=3, seed=7)
            model = Word2Vec(config, d)
            trainer = MACorpusTrainer(model, tok, avg_every=2,
                                      overlap=overlap, sharded=sharded,
                                      centers_per_step=64,
                                      steps_per_dispatch=1)
            losses = []
            for epoch in range(2):
                loss, examples = trainer.train_epoch(seed=epoch)
                losses.append(loss / max(examples, 1))
            trainer.finish()
            return (np.asarray(model._emb_in).copy(), losses,
                    trainer.comm_rounds)

        return LocalCluster(2, argv=["-ma=true"]).run(body)

    def test_uneven_shards_with_group_quota(self, tmp_path):
        # Data-parallel shards of different sizes produce different
        # group counts per epoch; group_quota (the largest rank's
        # count) keeps every rank joining the same number of
        # collectives instead of hanging the longer rank's average.
        from multiverso_tpu.models.wordembedding import (MACorpusTrainer,
                                                         TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        paths = [tmp_path / "a.txt", tmp_path / "b.txt"]
        write_topic_corpus(paths[0], n_sentences=150)
        write_topic_corpus(paths[1], n_sentences=60, seed=1)
        d = Dictionary.build(str(paths[0]), min_count=1)
        toks = [TokenizedCorpus.build(d, str(p)) for p in paths]

        def body(rank):
            config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                                    init_learning_rate=0.02,
                                    batch_size=256, sample=0,
                                    negative=3, seed=5)
            model = Word2Vec(config, d)
            trainer = MACorpusTrainer(model, toks[rank], avg_every=2,
                                      overlap=True, centers_per_step=64,
                                      steps_per_dispatch=1)
            trainer.train_epoch(seed=0, group_quota=40)
            trainer.finish()
            return (trainer.comm_rounds,
                    float(np.asarray(model._emb_in).sum()))

        outs = LocalCluster(2, argv=["-ma=true"]).run(body)
        assert outs[0][0] == outs[1][0]  # same collective count
        assert abs(outs[0][1] - outs[1][1]) < 1e-5  # replicas agree

    def test_overlap_bit_identical_to_sync_and_trains(self, tmp_path):
        # The MA overlap acceptance contract: with -allreduce_lossy
        # off, the double-buffered trainer follows EXACTLY the sync
        # trainer's trajectory (the average is applied at the same
        # point in both modes; only where the stall lands differs) —
        # and the model actually learns.
        sync = self._run(tmp_path, overlap=False)
        over = self._run(tmp_path, overlap=True)
        for rank in range(2):
            np.testing.assert_array_equal(sync[rank][0], over[rank][0])
        losses = sync[0][1]
        assert losses[-1] < losses[0], losses
        assert sync[0][2] > 0  # averages actually happened
        assert sync[0][2] == over[0][2]

    def test_sharded_bit_identical_sync_overlap_and_trains(self, tmp_path):
        # The sharded-average (delta-vs-last-average) trainer keeps the
        # same contract the dense mode established: sync and overlapped
        # schedules apply the same update at the same point, so the
        # trajectories are BIT-IDENTICAL — and the model still learns.
        # (Sharded-vs-dense-ring bit-identity of the collective itself
        # is pinned in tests/test_allreduce.py TestShardedAverage.)
        sync = self._run(tmp_path, overlap=False, sharded=True)
        over = self._run(tmp_path, overlap=True, sharded=True)
        for rank in range(2):
            np.testing.assert_array_equal(sync[rank][0], over[rank][0])
        # Replicas agree after finish() (the reference is rebuilt from
        # collective results, identical on every rank).
        np.testing.assert_array_equal(sync[0][0], sync[1][0])
        losses = sync[0][1]
        assert losses[-1] < losses[0], losses
        assert sync[0][2] > 0
        assert sync[0][2] == over[0][2]
        # Delta-MA converges where dense MA does: same data, same
        # schedule, embeddings in the same neighborhood (NOT bitwise —
        # averaging params vs averaging deltas associates differently).
        dense = self._run(tmp_path, overlap=False, sharded=False)
        assert np.abs(sync[0][0] - dense[0][0]).max() < 0.05


class TestPSDevicePipeline:
    def test_ps_device_pipeline_trains_through_tables(self, tmp_path):
        # The HBM corpus pipeline driving PARAMETER-SERVER tables with
        # device-resident keys: pulls/pushes ride the full actor stack,
        # loss decreases, and the trained state lives in the tables.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.01,
                                    batch_size=1024, sample=0)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            losses = []
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=epoch)
                assert pairs > 0
                losses.append(loss / pairs)
            assert losses[-1] < losses[0], losses
            sep = topic_separation(model, d)
            assert sep > 0.3, f"separation {sep}"
        finally:
            mv.shutdown()


    def test_ps_device_pipeline_cbow(self, tmp_path):
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.02,
                                    batch_size=1024, sample=0, cbow=True)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            losses = []
            for epoch in range(3):
                loss, examples = trainer.train_epoch(seed=epoch)
                losses.append(loss / max(examples, 1))
            assert losses[-1] < losses[0], losses
            sep = topic_separation(model, d)
            assert sep > 0.3, f"separation {sep}"
        finally:
            mv.shutdown()

    def test_ps_device_pipeline_bsp_sync(self, tmp_path):
        # The device-key PS pipeline under -sync=true: both workers
        # issue identical per-block op sequences (same corpus, same
        # seeds), so the SyncServer vector clock must admit every pull
        # and training must converge.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=300)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))

        def body(rank):
            config = Word2VecConfig(embedding_size=8, window=3,
                                    epochs=2, init_learning_rate=0.02,
                                    batch_size=256, sample=0)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            losses = []
            for epoch in range(2):
                loss, examples = trainer.train_epoch(seed=epoch)
                losses.append(loss / max(examples, 1))
            return losses

        results = LocalCluster(2, argv=["-sync=true"],
                               roles=["all", "worker"]).run(body)
        for losses in results:
            assert losses[-1] < losses[0], losses

    def test_ps_device_pipeline_two_workers(self, tmp_path):
        # Two virtual worker ranks drive the device-key PS pipeline
        # against one shared server (device keys need a single server):
        # delta scaling 1/num_workers, interleaved device-key
        # pulls/pushes through one device.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))

        def body(rank):
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.01,
                                    batch_size=1024, sample=0)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=100 * rank + epoch)
                assert np.isfinite(loss) and pairs > 0
            mv.current_zoo().barrier()
            return topic_separation(model, d)

        seps = LocalCluster(2, roles=["all", "worker"]).run(body)
        assert all(s > 0.3 for s in seps), seps

    def test_ps_device_pipeline_per_pair(self, tmp_path):
        # Quality mode through the PS: per-pair negatives + sequential
        # window sub-steps on the pulled copies, net delta pushed.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.01,
                                    batch_size=1024, sample=0,
                                    per_pair=True)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            losses = []
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=epoch)
                losses.append(loss / max(pairs, 1))
            assert losses[-1] < losses[0], losses
            sep = topic_separation(model, d)
            assert sep > 0.3, f"separation {sep}"
        finally:
            mv.shutdown()

    def test_ps_device_pipeline_grouped_blocks(self, tmp_path):
        # blocks_per_dispatch > 1: G blocks per pull/step/push round
        # trip (bounded staleness, the reference's sync_frequency
        # trade). Must converge and handle the padded tail group.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.01,
                                    batch_size=1024, sample=0)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128,
                                            blocks_per_dispatch=4)
            losses = []
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=epoch)
                assert pairs > 0
                losses.append(loss / pairs)
            assert losses[-1] < losses[0], losses
            sep = topic_separation(model, d)
            assert sep > 0.3, f"separation {sep}"
        finally:
            mv.shutdown()

    @pytest.mark.parametrize("mode", ["per_pair", "hs", "two_servers"])
    def test_ps_device_pipeline_grouped_variants(self, tmp_path, mode):
        # The grouped-dispatch wrappers vmap every step variant: the
        # per-pair quality step (`-per_pair` through the PS), the HS
        # step (tuple aux pytree), and multi-server reply tuples.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        kw = {"per_pair": True} if mode == "per_pair" else \
            ({"hs": True, "negative": 0} if mode == "hs" else {})
        config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                init_learning_rate=0.002, sample=0,
                                batch_size=1024, **kw)

        def train(seed_base=0):
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128,
                                            blocks_per_dispatch=4)
            losses = []
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=seed_base + epoch)
                assert pairs > 0
                losses.append(loss / pairs)
            assert losses[-1] < losses[0], losses
            return True

        if mode == "two_servers":
            def body(rank):
                if rank == 1:  # server-only rank hosts the second shard
                    PSWord2Vec(config, d)
                    for _ in range(3):
                        mv.current_zoo().barrier()
                    return True
                return train()
            assert all(LocalCluster(
                2, roles=["all", "server"]).run(body))
        else:
            mv.init([])
            try:
                assert train()
            finally:
                mv.shutdown()

    def test_ps_device_pipeline_two_servers(self, tmp_path):
        # Multi-server device keys (VERDICT r3 #3): the PS device
        # pipeline drives TWO in-process servers — ids broadcast, each
        # server masks foreign rows, worker sums the replies — and
        # training converges to the same topic structure.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))

        def body(rank):
            config = Word2VecConfig(embedding_size=16, window=3,
                                    epochs=3, init_learning_rate=0.01,
                                    batch_size=1024, sample=0)
            model = PSWord2Vec(config, d)
            if rank == 1:  # server-only rank holds the second shard
                for _ in range(3):  # mirror the per-epoch barrier
                    mv.current_zoo().barrier()
                return None
            assert model._in_table._num_server == 2
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            losses = []
            for epoch in range(3):
                loss, pairs = trainer.train_epoch(seed=epoch)
                losses.append(loss / max(pairs, 1))
            assert losses[-1] < losses[0], losses
            return topic_separation(model, d)

        seps = LocalCluster(2, roles=["all", "server"]).run(body)
        assert seps[0] is not None and seps[0] > 0.3, seps

    @pytest.mark.parametrize("grouped", [1, 2])
    def test_ps_device_two_servers_match_one_server(self, tmp_path,
                                                    grouped):
        # Device keys broadcast to every server, each masks the rows it
        # does not own, and the step sums the per-server parts: over
        # two servers that must train to the tables one server gives
        # (ref: src/table/matrix_table.cpp:234-315). Pulled rows
        # reassemble to identical values; only duplicate-id scatter-add
        # order may differ, so allow float slop.
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, PSWord2Vec, TokenizedCorpus)
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        rows = np.arange(d.size, dtype=np.int32)
        # A table's random init is a function of (seed, server id):
        # start both layouts from the same input rows.
        start = np.random.default_rng(5).uniform(
            -0.03, 0.03, (d.size, 16)).astype(np.float32)

        def run(roles):
            def body(rank):
                config = Word2VecConfig(embedding_size=16, window=3,
                                        epochs=2,
                                        init_learning_rate=0.01,
                                        batch_size=1024, sample=0)
                model = PSWord2Vec(config, d)
                if rank == 1:  # server-only rank holds the second shard
                    for _ in range(2):
                        mv.current_zoo().barrier()
                    return None
                table = model._in_table
                assert table._num_server == len(roles)
                table.add_rows(rows, start - table.get_rows(rows))
                trainer = PSDeviceCorpusTrainer(
                    model, tok, centers_per_step=128,
                    blocks_per_dispatch=grouped)
                for epoch in range(2):
                    trainer.train_epoch(seed=epoch)
                return np.array(table.get_rows(rows), copy=True)
            return LocalCluster(len(roles), roles=roles).run(body)[0]

        one, two = run(["all"]), run(["all", "server"])
        assert np.abs(one - start).max() > 1e-3  # it trained
        np.testing.assert_allclose(two, one, rtol=1e-4, atol=1e-6)


def _epoch_as_nine_dispatches(trainer, seed, block_hook):
    """``PSDeviceCorpusTrainer.train_epoch`` as it was before the
    block's key, base and sums went inside its two programs: the key
    folded and the scalars uploaded eagerly, ``trainer._ids``,
    ``trainer._step``, the sums added eagerly. Same requests, same
    order."""
    import math
    import jax
    import jax.numpy as jnp
    model, C, G = trainer.model, trainer._C, trainer._G
    in_table, out_table = model._in_table, model._out_table
    key = jax.random.PRNGKey(seed)
    key, prep_key = jax.random.split(key)
    kept, ksent, n_kept_dev = trainer._corpus.prep_epoch(prep_key)
    kept_pad, ksent_pad = trainer._pad(kept, ksent)
    n_kept = int(n_kept_dev)
    steps = max(math.ceil(n_kept / C), 1)
    trainer.kept_words_trained += min(steps * C, n_kept)
    raw_per_step = trainer._n_tokens / steps
    loss_acc = pair_acc = None
    for g0 in range(0, steps, G):
        real = min(G, steps - g0)
        step_key = jax.random.fold_in(key, g0)
        if G == 1:
            base = np.int32(g0 * C)
            lr_host = np.float32(model.learning_rate())
            model._account_words(raw_per_step)
        else:
            bases = np.full(G, n_kept, np.int32)
            bases[:real] = (np.arange(g0, g0 + real) * C).astype(np.int32)
            lr_host = np.zeros(G, np.float32)
            for i in range(real):
                lr_host[i] = model.learning_rate()
                model._account_words(raw_per_step)
            base = jnp.asarray(bases)
        lr = jnp.asarray(lr_host)
        inv_w = jnp.float32(1.0 / model._num_workers)
        in_ids, out_ids, pmask = trainer._ids(
            kept_pad, ksent_pad, trainer._aux_tables[0],
            trainer._aux_tables[1], step_key, base, n_kept_dev)
        mid_in = in_table.get_rows_device_async(in_ids)
        mid_out = out_table.get_rows_device_async(out_ids)
        in_table.wait(mid_in)
        out_table.wait(mid_out)
        v = tuple(in_table.take_device_row_parts())
        u = tuple(out_table.take_device_row_parts())
        d_v, d_u, loss, pairs = trainer._step(v, u, pmask, lr, inv_w)
        model._pending_pushes.append(
            (in_table, in_table.add_rows_async(in_ids, d_v)))
        model._pending_pushes.append(
            (out_table, out_table.add_rows_async(out_ids, d_u)))
        loss_acc = loss if loss_acc is None else loss_acc + loss
        pair_acc = pairs if pair_acc is None else pair_acc + pairs
        trainer.last_loss = loss
        block_hook(raw_per_step * real)
    model._drain_pushes()
    model._flush_word_count()
    model._in_table.zoo.barrier()
    return float(loss_acc), float(pair_acc)


_LOOP_MODES = {
    "skipgram": ({}, 1),
    "cbow": ({"cbow": True}, 1),
    "hs_skipgram": ({"hs": True, "negative": 0}, 1),
    "hs_cbow": ({"hs": True, "negative": 0, "cbow": True}, 1),
    "per_pair": ({"per_pair": True}, 1),
    "grouped4": ({}, 4),
}


class TestPSLoopPrograms:
    """The PS loop's two programs a block (the block's key and base
    inside the ids program, the epoch's sums inside the step) against
    the nine dispatches they took the place of."""

    @staticmethod
    def _train(d, tok, kw, grouped, epoch_fn):
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer)
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3, epochs=2,
                                    init_learning_rate=0.01,
                                    batch_size=1024, sample=1e-2, **kw)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128,
                                            blocks_per_dispatch=grouped)
            losses, sums = [], []
            for epoch in range(2):   # the second starts from the first's
                sums.append(epoch_fn(trainer)(
                    seed=3 + epoch, block_hook=lambda words: losses.append(
                        (words, trainer.last_loss))))
            return (np.array(model._in_table.get(), copy=True),
                    np.array(model._out_table.get(), copy=True),
                    [(w, float(x)) for w, x in losses], sums,
                    trainer.kept_words_trained, model.trained_words)
        finally:
            mv.shutdown()

    @pytest.mark.parametrize("mode", list(_LOOP_MODES))
    def test_two_dispatches_train_what_nine_did(self, tmp_path, mode):
        import functools
        from multiverso_tpu.models.wordembedding import TokenizedCorpus
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=120)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        kw, grouped = _LOOP_MODES[mode]
        new = self._train(d, tok, kw, grouped,
                          lambda trainer: trainer.train_epoch)
        old = self._train(d, tok, kw, grouped, lambda trainer:
                          functools.partial(_epoch_as_nine_dispatches,
                                            trainer))
        assert len(new[2]) > (2 if grouped > 1 else 8)   # blocks
        assert np.abs(new[0]).max() > 0 and np.abs(new[1]).max() > 0
        assert np.array_equal(new[0], old[0])       # the input table
        assert np.array_equal(new[1], old[1])       # the output table
        assert new[2] == old[2]      # every block's words and own loss
        assert new[3] == old[3]      # (loss, examples) of both epochs
        assert new[4:] == old[4:]    # the words accounted

    def test_ids_and_step_run_one_block_by_hand(self, tmp_path):
        # benchmark/reference/sgns_block.py check() runs one block
        # through trainer._ids and trainer._step with these arguments.
        import jax
        import jax.numpy as jnp
        from multiverso_tpu.models.wordembedding import (
            PSDeviceCorpusTrainer, TokenizedCorpus)
        from multiverso_tpu.models.wordembedding import device_train as dt
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=120)
        d = Dictionary.build(str(path), min_count=1)
        tok = TokenizedCorpus.build(d, str(path))
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3, epochs=1,
                                    init_learning_rate=0.01,
                                    batch_size=1024, sample=0)
            model = PSWord2Vec(config, d)
            trainer = PSDeviceCorpusTrainer(model, tok,
                                            centers_per_step=128)
            trainer.train_epoch(seed=0, max_steps=2)
            C, W, K = trainer._C, config.window, config.negative
            key, prep_key = jax.random.split(jax.random.PRNGKey(7))
            kept, ksent, n_kept_dev = trainer._corpus.prep_epoch(prep_key)
            kept_pad, ksent_pad = dt._pad_stream(C, W, kept, ksent)
            tin, tout = model._in_table, model._out_table
            ids = trainer._ids(
                kept_pad, ksent_pad, model._neg_prob_dev,
                model._neg_alias_dev, key, np.int32(0), n_kept_dev)
            assert len(ids) == 3
            in_ids, out_ids, pmask = ids
            assert in_ids.shape == (C,)
            assert out_ids.shape == (C + 2 * W + C * K,)
            assert pmask.shape == (C, 2 * W)
            v, u = tin.get_rows_device(in_ids), tout.get_rows_device(out_ids)
            out = trainer._step(
                (v,), (u,), pmask, jnp.asarray(np.float32(0.01)),
                jnp.float32(1.0 / model._num_workers))
            assert len(out) == 4
            d_v, d_u, loss, pairs = out
            assert d_v.shape == v.shape and d_u.shape == u.shape
            assert float(loss) > 0 and float(pairs) == float(pmask.sum())
            assert float(trainer.last_loss) > 0   # a block's own loss
        finally:
            mv.shutdown()


class TestBatchGroup:
    @pytest.mark.parametrize("mode", ["sgns", "cbow", "hs"])
    def test_grouped_scan_matches_sequential(self, tmp_path, mode):
        # The lax.scan multi-step must be bit-identical to dispatching
        # the same batches one step at a time (same key-split order) —
        # including a short tail group padded with count=0 slots.
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=60)
        d = Dictionary.build(str(path), min_count=1)
        kw = {"cbow": mode == "cbow", "hs": mode == "hs"}
        if mode == "hs":
            kw["negative"] = 0
        embs = []
        for group in (1, 4):
            config = Word2VecConfig(embedding_size=8, window=3, epochs=2,
                                    batch_size=256, sample=0,
                                    batch_group=group, **kw)
            model = Word2Vec(config, d)
            loss = 0.0
            # TWO epochs: each ends with a padded tail group, which must
            # not desync the per-batch key stream across epochs.
            for epoch in range(2):
                ep_loss, pairs = model.train_batches(iter_pair_batches(
                    d, str(path), batch_size=256, window=3, subsample=0,
                    cbow=config.cbow, seed=5 + epoch))
                loss += ep_loss
                assert pairs > 256  # several batches incl. a padded tail
            embs.append((model.embeddings, loss))
        np.testing.assert_array_equal(embs[0][0], embs[1][0])
        assert embs[0][1] == pytest.approx(embs[1][1], rel=1e-6)


def train_and_separate(tmp_path, **config_kw):
    path = tmp_path / "corpus.txt"
    write_topic_corpus(path)
    d = Dictionary.build(str(path), min_count=1)
    # Small lr: batch-summed gradients on this tiny vocab hit each row
    # ~64x per batch (see model.py on per-pair lr semantics).
    config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                            init_learning_rate=0.01, batch_size=1024,
                            sample=0, **config_kw)
    model = Word2Vec(config, d)
    for epoch in range(config.epochs):
        for batch in iter_pair_batches(d, str(path), batch_size=1024,
                                       window=3, subsample=0,
                                       cbow=config.cbow,
                                       seed=epoch):
            model.train_batch(batch)
    return topic_separation(model, d), model, d


class TestTraining:
    def test_sgns_skipgram_separates_topics(self, tmp_path):
        sep, _, _ = train_and_separate(tmp_path)
        assert sep > 0.3, f"separation {sep}"

    def test_cbow_separates_topics(self, tmp_path):
        sep, _, _ = train_and_separate(tmp_path, cbow=True)
        assert sep > 0.3, f"separation {sep}"

    def test_hierarchical_softmax_separates_topics(self, tmp_path):
        sep, _, _ = train_and_separate(tmp_path, hs=True, negative=0)
        assert sep > 0.3, f"separation {sep}"

    def test_save_embeddings_format(self, tmp_path):
        _, model, d = train_and_separate(tmp_path)
        out = tmp_path / "vec.txt"
        model.save_embeddings(str(out))
        lines = out.read_text().strip().split("\n")
        header = lines[0].split()
        assert int(header[0]) == d.size and int(header[1]) == 16
        assert len(lines) == d.size + 1


class TestPSWord2Vec:
    def test_ps_training_separates_topics(self, tmp_path):
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                    init_learning_rate=0.01,
                                    batch_size=1024, sample=0, use_ps=True)
            model = PSWord2Vec(config, d)
            for epoch in range(config.epochs):
                for batch in iter_pair_batches(d, str(path),
                                               batch_size=1024, window=3,
                                               subsample=0, seed=epoch):
                    model.train_batch(batch)
            sep = topic_separation(model, d)
        finally:
            mv.shutdown()
        assert sep > 0.3, f"separation {sep}"

    @pytest.mark.parametrize("mode", ["cbow", "hs"])
    def test_ps_compact_step_modes(self, tmp_path, mode):
        # CBOW and hierarchical softmax through the compact pulled-row
        # step (the PS redesign trains on [R, D] row sets, not V x D).
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)
        d = Dictionary.build(str(path), min_count=1)
        mv.init([])
        try:
            kw = dict(cbow=True) if mode == "cbow" \
                else dict(hs=True, negative=0)
            config = Word2VecConfig(embedding_size=16, window=3, epochs=5,
                                    init_learning_rate=0.01,
                                    batch_size=1024, sample=0, use_ps=True,
                                    **kw)
            model = PSWord2Vec(config, d)
            for epoch in range(config.epochs):
                loss_sum, pairs = model.train_batches(iter_pair_batches(
                    d, str(path), batch_size=1024, window=3, subsample=0,
                    cbow=config.cbow, seed=epoch))
                assert np.isfinite(loss_sum) and pairs > 0
            sep = topic_separation(model, d)
        finally:
            mv.shutdown()
        assert sep > 0.3, f"separation {sep}"

    def test_ps_pulls_are_row_sparse(self, tmp_path):
        # The PS path must pull only the rows a batch touches — never the
        # whole table (the round-1 design pulled V x D per batch).
        rng = np.random.default_rng(3)
        vocab = [f"w{i}" for i in range(600)]
        path = tmp_path / "corpus.txt"
        path.write_text("\n".join(
            " ".join(rng.choice(vocab, size=10)) for _ in range(400)))
        d = Dictionary.build(str(path), min_count=1)
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                                    batch_size=128, sample=0, use_ps=True)
            model = PSWord2Vec(config, d)
            pulled = []
            orig_host = model._in_table.get_rows_async
            orig_dev = model._in_table.get_rows_device_async

            def spy_host(rows, out=None):
                pulled.append(len(rows))
                return orig_host(rows, out=out)

            def spy_dev(rows):
                pulled.append(len(rows))
                return orig_dev(rows)

            model._in_table.get_rows_async = spy_host
            model._in_table.get_rows_device_async = spy_dev
            loss_sum, pairs = model.train_batches(iter_pair_batches(
                d, str(path), batch_size=128, window=2, subsample=0))
            assert pairs > 0 and np.isfinite(loss_sum)
            assert pulled, "no row pulls recorded"
            # 128 pairs touch at most 128 input rows (padded to a power of
            # two) out of a 600-word vocab.
            assert max(pulled) <= 128 < d.size, pulled
        finally:
            mv.shutdown()

    def test_ps_two_workers_cluster(self, tmp_path):
        # Two virtual ranks train concurrently against shared tables:
        # delta scaling (1/num_workers) and concurrent row pulls/pushes.
        from multiverso_tpu.runtime.cluster import LocalCluster
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path)

        def body(rank):
            d = Dictionary.build(str(path), min_count=1)
            config = Word2VecConfig(embedding_size=16, window=3, epochs=3,
                                    init_learning_rate=0.005,
                                    batch_size=1024, sample=0, use_ps=True)
            model = PSWord2Vec(config, d)
            for epoch in range(config.epochs):
                model.train_batches(iter_pair_batches(
                    d, str(path), batch_size=1024, window=3, subsample=0,
                    seed=100 * rank + epoch))
            mv.current_zoo().barrier()
            return topic_separation(model, d)

        seps = LocalCluster(2).run(body)
        assert all(s > 0.3 for s in seps), seps

    def test_ps_word_count_drives_lr(self, tmp_path):
        path = tmp_path / "corpus.txt"
        write_topic_corpus(path, n_sentences=100)
        d = Dictionary.build(str(path), min_count=1)
        mv.init([])
        try:
            config = Word2VecConfig(embedding_size=8, window=2, epochs=1,
                                    batch_size=512, sample=0, use_ps=True)
            model = PSWord2Vec(config, d)
            lr0 = model.learning_rate()
            for batch in iter_pair_batches(d, str(path), batch_size=512,
                                           window=2, subsample=0):
                model.train_batch(batch)
            assert model.trained_words > 0
            assert model.learning_rate() < lr0
        finally:
            mv.shutdown()


class TestPreprocess:
    def test_word_count_cli(self, tmp_path):
        # ref: Applications/WordEmbedding/preprocess/word_count.cpp:30-46
        # — count, filter by min_count + stopwords, save, reload.
        corpus = tmp_path / "c.txt"
        corpus.write_text("a b c a b a\nthe the the a b\n")
        (tmp_path / "sw.txt").write_text("the\n")
        vocab = tmp_path / "v.txt"
        from multiverso_tpu.models.wordembedding import preprocess
        from multiverso_tpu.util.configure import reset_flags
        reset_flags()
        try:
            d = preprocess.run([f"-train_file={corpus}",
                                f"-save_vocab_file={vocab}",
                                "-min_count=2",
                                f"-sw_file={tmp_path / 'sw.txt'}"])
        finally:
            reset_flags()
        assert d.size == 2 and "the" not in d.word2id
        reloaded = Dictionary.load(str(vocab))
        assert reloaded.word2id == d.word2id
        assert list(reloaded.counts) == list(d.counts)

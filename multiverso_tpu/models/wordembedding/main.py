"""WordEmbedding CLI: distributed word2vec trainer.

ref: Applications/WordEmbedding/src/main.cpp:16-28 and
distributed_wordembedding.cpp (epoch loop over blocks with a loader
thread; rank 0 saves embeddings after the last epoch). Flags use the
framework's -key=value convention, mirroring the reference's argv names.

Usage::

    python -m multiverso_tpu.models.wordembedding.main \
        -train_file=corpus.txt -output_file=vectors.txt -size=100 \
        -window=5 -negative=5 -epoch=1 [-cbow=true] [-hs=true] \
        [-use_ps=true] [-min_count=5] [-sample=1e-3] [-batch_size=4096]
"""

from __future__ import annotations

import sys
import time

from ... import init as mv_init, shutdown as mv_shutdown
from ...sharding.mesh import describe_backend
from ...util import compile_cache, log
from ...util.configure import (define_bool, define_double, define_int,
                               define_string, get_flag, parse_cmd_flags)
from .data import BlockLoader, TokenizedCorpus, iter_pair_batches
from .device_train import DeviceCorpusTrainer, PSDeviceCorpusTrainer
from .dictionary import Dictionary
from .model import PSWord2Vec, Word2Vec, Word2VecConfig

define_string("train_file", "", "training corpus (';'-separated)")
define_string("output_file", "vectors.txt", "embedding output path")
define_string("vocab_file", "", "optional prebuilt vocab to load")
define_int("size", 100, "embedding dimension")
define_int("window", 5, "max context window")
define_int("negative", 5, "negative samples (0 with -hs)")
define_int("epoch", 1, "training epochs")
define_int("min_count", 5, "discard words rarer than this")
define_double("sample", 1e-3, "subsampling threshold")
define_double("init_learning_rate", 0.025, "initial learning rate")
define_bool("cbow", False, "CBOW instead of skip-gram")
define_bool("hs", False, "hierarchical softmax instead of negative "
                         "sampling")
define_bool("use_ps", False, "train through the parameter server")
define_int("batch_size", 4096, "pairs per jitted step")
define_int("neg_block", 1, "device pipelines: share one draw of K "
           "negatives across this many consecutive centers (1 = "
           "per-center draws; larger divides negative row traffic)")
define_bool("per_pair", False, "device pipelines, skip-gram: per-pair "
            "negatives + sequential window sub-steps (the reference's "
            "update structure; slower, reaches sequential-SGD quality)")
define_bool("is_pipeline", True, "overlap loading with training")
define_bool("device_pipeline", True, "train through the HBM-resident "
            "device pipeline (the fast path; -batch_size/-is_pipeline "
            "apply only to the host-batch loop); false = host-batch "
            "loop (the cross-process-capable form)")
define_string("stopwords", "", "optional stopwords file (one word per "
              "line) filtered out of the vocabulary — the reference "
              "reader's stopwords table (ref: Applications/WordEmbedding"
              "/src/reader.cpp, flag -stopwords)")


def run(argv=None) -> Word2Vec:
    parse_cmd_flags(list(argv) if argv is not None else sys.argv[1:])
    config = Word2VecConfig(
        embedding_size=get_flag("size"), window=get_flag("window"),
        negative=get_flag("negative"), epochs=get_flag("epoch"),
        min_count=get_flag("min_count"), sample=get_flag("sample"),
        init_learning_rate=get_flag("init_learning_rate"),
        cbow=get_flag("cbow"), hs=get_flag("hs"),
        batch_size=get_flag("batch_size"), use_ps=get_flag("use_ps"),
        neg_block=get_flag("neg_block"), per_pair=get_flag("per_pair"))
    train_file = get_flag("train_file")
    if not train_file:
        raise SystemExit("need -train_file=<corpus>")

    stopwords = None
    if get_flag("stopwords"):
        from ...io import TextReader
        stopwords = set()
        reader = TextReader(get_flag("stopwords"))
        while True:
            line = reader.get_line()
            if line is None:
                break
            word = line.strip()
            if word:
                stopwords.add(word)
        reader.close()
        log.info("loaded %d stopwords", len(stopwords))

    if get_flag("vocab_file"):
        dictionary = Dictionary.load(get_flag("vocab_file"))
    else:
        dictionary = Dictionary.build(train_file,
                                      min_count=config.min_count,
                                      stopwords=stopwords)
    log.info("vocab: %d words, %d tokens", dictionary.size,
             dictionary.total_count)

    if config.use_ps:
        mv_init([])  # logs the backend
        model: Word2Vec = PSWord2Vec(config, dictionary)
    else:
        log.info("jax backend: %s", describe_backend())
        model = Word2Vec(config, dictionary)

    corpus = TokenizedCorpus.build(dictionary, train_file)
    # The DEVICE pipelines (corpus + windowing + sampling in HBM —
    # models/wordembedding/device_train.py) are the fast path for every
    # mode combination; -device_pipeline=false falls back to the
    # host-batch loop (the form that also runs cross-process, and the
    # only path for worker-only PS ranks whose servers live elsewhere).
    device_ok = not config.use_ps or getattr(model, "_device_path", False)
    use_device = get_flag("device_pipeline") and device_ok
    if use_device:
        log.info("training via the device pipeline "
                 "(-batch_size/-is_pipeline apply to the host-batch "
                 "loop only; -device_pipeline=false selects it)")
        trainer = (PSDeviceCorpusTrainer(model, corpus)
                   if config.use_ps else
                   DeviceCorpusTrainer(model, corpus))

        def train_one(epoch):
            return trainer.train_epoch(seed=config.seed + epoch)
    else:
        def train_one(epoch):
            batches = iter_pair_batches(
                dictionary, corpus, batch_size=config.batch_size,
                window=config.window, subsample=config.sample,
                cbow=config.cbow, seed=config.seed + epoch)
            # Row preparation runs in the loader thread (prepared()) so
            # it overlaps with device steps; the hot loop lives in the
            # model — local mode accumulates device losses without host
            # syncs, PS mode pipelines pull/train/push.
            iterator = BlockLoader(model.prepared(batches)) \
                if get_flag("is_pipeline") else batches
            return model.train_batches(iterator)

    start = time.perf_counter()
    for epoch in range(config.epochs):
        loss_sum, pair_count = train_one(epoch)
        elapsed = time.perf_counter() - start
        log.info("epoch %d: avg pair loss %.4f, %.0f words/s", epoch,
                 loss_sum / max(pair_count, 1),
                 model.trained_words / max(elapsed, 1e-9))

    should_save = not config.use_ps or model._in_table.zoo.rank == 0
    if should_save and get_flag("output_file"):
        model.save_embeddings(get_flag("output_file"))
    if config.use_ps:
        mv_shutdown()
    return model


if __name__ == "__main__":
    compile_cache.enable()
    run()

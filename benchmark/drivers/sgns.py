"""Driver for word2vec SGNS from a device-resident corpus, set up in
the order models/wordembedding/main.py run() sets it up: Dictionary ->
(mv.init, PSWord2Vec | Word2Vec) -> TokenizedCorpus ->
(PSDeviceCorpusTrainer | DeviceCorpusTrainer), then train_epoch with
seed + epoch. The traffic mix names the trainer (``"ps"`` or
``"local"``). Dictionary and TokenizedCorpus are filled from the
generator's arrays, not read back from text (benchmark/lib/corpus.py
says why).

A block is one step of ``centers_per_block`` centers. The measured
window opens before an epoch's call, so the epoch's own preparation
(`_prep`: the subsampling mask and the one sort that compacts the
corpus by it) is inside it, and
closes inside the trainer's hook at the first block boundary after
``seconds``, with a forced sync. The hook then leaves the epoch by an
exception and the driver does what the epoch's end does (drain the
pushes, flush the word count, barrier). An epoch that ends inside the
window is followed by the next: the 8M cells' window holds one epoch
and most of a second (two `_prep`s), the 21M cell's ends inside its
first.

The traced window opens at the epoch's first block instead: three
seconds that began with `_prep` would be mostly `_prep`. Every run
prints how long the call took to reach its first block.
"""

import math
import time

import numpy as np

from benchmark.lib.corpus import make_corpus
from benchmark.reference import sgns_block


SLICE_S = 2.0


class _WindowClosed(Exception):
    """Raised by the hook to leave the epoch when the window has closed."""


class Driver:
    def __init__(self, ctx):
        self.ctx = ctx
        self.config = ctx.config
        self.use_ps = ctx.traffic["trainer"] == "ps"
        self.epoch = 0
        self.block_losses = []   # PS: device scalars, one a block
        self.epochs_done = []    # (loss_sum, pairs) of finished epochs
        self.compared = {}       # what check() compared: [value, limit]

    # -- set-up ---------------------------------------------------------
    def build(self):
        from multiverso_tpu.models.wordembedding import (
            DeviceCorpusTrainer, Dictionary, PSDeviceCorpusTrainer,
            PSWord2Vec, TokenizedCorpus, Word2Vec, Word2VecConfig)
        c, corpus = self.config, self.config["corpus"]
        w2v = Word2VecConfig(
            embedding_size=c["embedding_size"], window=c["window"],
            negative=c["negative"], epochs=c["epochs"], min_count=1,
            sample=c["sample"], init_learning_rate=c["init_learning_rate"],
            use_ps=self.use_ps, neg_block=c["neg_block"],
            seed=self.ctx.seed % (2 ** 31 - 1))
        counts, flat, offsets = make_corpus(
            c["vocabulary_rows"], corpus["sentences"],
            corpus["sentence_len"], self.ctx.seed)
        dictionary = Dictionary()
        dictionary.counts = counts
        # words are named by their rank; only their number is read here
        dictionary.words = range(counts.size)
        if self.use_ps:
            import multiverso_tpu as mv
            mv.init([f"-rpc_timeout_s={self.ctx.deadline_s}"])
            self.model = PSWord2Vec(w2v, dictionary)
        else:
            self.model = Word2Vec(w2v, dictionary)
        tokenized = TokenizedCorpus(flat, offsets)
        make = PSDeviceCorpusTrainer if self.use_ps else DeviceCorpusTrainer
        self.trainer = make(self.model, tokenized,
                            centers_per_step=c["centers_per_block"])
        self.w2v = w2v

    def warm(self):
        """A fixed number of blocks of the cell's own shapes, through
        the same train_epoch call, then the sync the window closes with."""
        self.trainer.train_epoch(
            seed=self.w2v.seed, max_steps=int(self.ctx.traffic["warm_blocks"]))
        self._sync()

    def _sync(self):
        import jax
        if self.use_ps:
            # The last block's Adds are queued behind its step. A Get of
            # one row comes back only when they have run.
            one = np.zeros(1, np.int32)
            self.model._in_table.get_rows(one)
            self.model._out_table.get_rows(one)
        else:
            jax.block_until_ready((self.model._emb_in, self.model._emb_out))

    # -- the window -------------------------------------------------------
    def _end_epoch_early(self):
        """What train_epoch does after its last block."""
        if self.use_ps:
            self.model._drain_pushes()
            self.model._flush_word_count()
            self.model._in_table.zoo.barrier()

    def measure(self, seconds: float):
        trainer = self.trainer
        after_prep = self.ctx.tracing
        state = {"window": None if after_prep else self.ctx.open_window(),
                 "deadline": None, "per_block": None, "ticks": []}
        first_block = len(self.block_losses)

        def hook(words):
            now = time.monotonic()
            if self.use_ps:
                self.block_losses.append(trainer.last_loss)
            window = state["window"]
            if window is None:       # traced: opens at the first block
                state["window"] = self.ctx.open_window()
                return
            if state["deadline"] is None:
                state["deadline"] = window.t_start + seconds
            if state["per_block"] is None:
                # raw words a block stands for, this epoch (the trainer
                # spreads the corpus evenly over the epoch's blocks)
                state["per_block"] = trainer._n_tokens / max(math.ceil(
                    (trainer.kept_words_trained - state["kept0"])
                    / trainer._C), 1)
            blocks = max(int(round(words / state["per_block"])), 1)
            window.rounds += blocks
            window.attempted += blocks
            window.work["words"] = window.work.get("words", 0.0) + words
            state["ticks"].append((now, blocks))
            if now >= state["deadline"]:
                self._sync()
                self.ctx.close_window(window)
                raise _WindowClosed

        kind = "block_hook" if self.use_ps else "group_hook"
        first_tick = []
        while True:
            state["per_block"] = None
            state["kept0"] = trainer.kept_words_trained
            t0, n0 = time.monotonic(), len(state["ticks"])
            try:
                with self.ctx.span("train_epoch"):
                    done = trainer.train_epoch(
                        seed=self.w2v.seed + self.epoch, **{kind: hook})
            except _WindowClosed:
                done = None
                self._end_epoch_early()
            self.epoch += 1
            if len(state["ticks"]) > n0:
                first_tick.append(state["ticks"][n0][0] - t0)
            if done is None:
                break
            self.epochs_done.append(done)
        window = state["window"]
        # Where in the window the blocks fell: a run that reads low shows
        # here whether it was slow throughout or stalled once.
        slices = [0] * (int(window.seconds // SLICE_S) + 1)
        for t, blocks in state["ticks"]:
            slices[min(int((t - window.t_start) // SLICE_S),
                       len(slices) - 1)] += blocks
        print(f"[bench] epochs begun: {len(first_tick)}, call to first "
              f"block {[round(t, 3) for t in first_tick]} s; blocks in "
              f"each {SLICE_S:g} s of the window: {slices}", flush=True)
        if self.use_ps:
            window.failed = sum(
                not math.isfinite(float(x)) for x in
                self.block_losses[first_block:first_block + window.rounds])
        return window

    # -- after the window ---------------------------------------------------
    def check(self) -> list:
        wrong = []
        means = [loss / max(pairs, 1.0) for loss, pairs in self.epochs_done]
        if not all(math.isfinite(m) for m in means):
            wrong.append(f"non-finite epoch loss: {means}")
        if self.use_ps and not all(
                math.isfinite(float(x)) for x in self.block_losses):
            wrong.append("non-finite block loss")
        self.compared["non_finite_losses"] = [len(wrong), 0]
        wrong += sgns_block.check(self)   # adds the block's three
        return wrong

    def close(self):
        del self.trainer
        model, self.model = self.model, None
        if self.use_ps:
            import multiverso_tpu as mv
            del model
            mv.shutdown()

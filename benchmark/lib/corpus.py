"""The seeded synthetic corpus, made as arrays.

`chip_smoke.write_corpus`'s recipe (copied, not imported): one pass over
the whole vocabulary, so that every one of ``rows`` words exists and the
table has exactly ``rows`` rows, followed by two-topic Zipf(1.0) text:
each sentence draws from one half of the vocabulary, so frequent words
have structure. Same seed, same corpus.

Two departures from the smoke, both for set-up time (every run of every
later check pays it):

- What the smoke writes as text and reads back through Dictionary.build
  and TokenizedCorpus.build, this builds directly, vectorised: the word
  counts, and the corpus as word ids with sentence offsets, ids ranked
  by count as Dictionary.build ranks them (ties by the generator's word
  number where Dictionary.build breaks them by spelling). The text round
  trip cost 75-80 s of a run's set-up at 8M words and 4M text tokens (my
  chip runs, PR 23, PERF.md) and serves no block of the window.
- A word's rank in its topic is drawn by the inverse of the continuous
  1/x law on [1, half + 1): P(rank k) = ln((k+1)/k) / ln(half + 1), as
  `lib/rowtraffic.py` draws its Zipf(1.0) ids, instead of a search in a
  table of harmonic sums (24 s for 64M tokens against 3 s, on the CPU
  this was written on). The head is a little lighter than the harmonic
  law's (rank 1: 4.6% of a topic's text against 6.3% at 4M words).
"""

import numpy as np


def make_corpus(rows: int, sentences: int, sentence_len: int, seed: int):
    """Returns (counts[rows] int64, sorted descending; flat[tokens] int32
    word ids by rank; offsets[n_sentences + 1] int64)."""
    rng = np.random.default_rng(seed)
    half = rows // 2
    topic = rng.integers(0, 2, size=(sentences, 1), dtype=np.int32)
    x = rng.random((sentences, sentence_len))
    np.multiply(x, np.log(half + 1.0), out=x)
    np.exp(x, out=x)
    text = np.minimum(x.astype(np.int32) - 1, half - 1)
    del x
    text += topic * np.int32(half)
    tokens = np.concatenate([rng.permutation(rows).astype(np.int32),
                             text.ravel()])
    del text
    counts = np.bincount(tokens, minlength=rows)
    order = np.lexsort((np.arange(rows), -counts))
    rank = np.empty(rows, np.int32)
    rank[order] = np.arange(rows, dtype=np.int32)
    # the pass over the vocabulary in sentences of sentence_len; a last
    # sentence of one word would be dropped by the tokeniser, so it
    # joins the one before it
    full, rest = divmod(rows, sentence_len)
    cover = [sentence_len] * full
    if rest >= 2 or not cover:
        cover.append(rest)
    else:
        cover[-1] += rest
    lengths = np.concatenate([[0], cover, np.full(sentences, sentence_len)])
    return counts[order], rank[tokens], np.cumsum(lengths).astype(np.int64)

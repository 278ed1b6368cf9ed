"""IVF approximate-nearest-neighbor index for the serving tier.

The PR-10 neighbors endpoint scored every row per query — an
O(rows x dims) cosine matmul that caps a frontend at a few hundred
QPS and scales linearly with the table. This module replaces it with
a classic two-level inverted-file (IVF) search over the SAME
staleness-bounded snapshot the brute scan used (docs/SERVING.md):

1. **build** — a k-means coarse quantizer over the row directions
   (unit vectors; cosine similarity is dot product after
   normalization) partitions the rows into ``nlist`` inverted lists;
2. **search** — a query scores the ``nlist`` centroids (tiny), scans
   only the ``nprobe`` closest lists, and exact-scores those
   candidates — ``~nprobe/nlist`` of the table per query.

Recall is a knob, not a constant: embedding tables are clustered by
construction (that is what training does), so small ``nprobe``
reaches high recall; tests/test_serving_fleet.py holds recall@10
against the brute scan on clustered data, and the endpoint keeps a
``brute=1`` escape hatch. The index is
a DERIVED cache: it rebuilds under the same pre-fetch-anchored
version rule as the brute snapshot, plus forced invalidation on a
data-generation change (reshard / server rejoin — see
``WorkerTable.cache_generation``).

Pure numpy, host-side: the snapshot is already host memory and a
query touches a few thousand rows — a device roundtrip per request
would cost more than it saves.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

#: k-means refinement passes. Lloyd converges fast on the sampled
#: training set and the quantizer only has to be balanced, not
#: optimal — recall comes from nprobe, not centroid perfection.
_KMEANS_ITERS = 6

#: Rows sampled for centroid training on big tables: k-means cost is
#: O(sample x nlist x dims x iters) and a subsample trains an
#: equally-good quantizer; ASSIGNMENT still covers every row.
_KMEANS_SAMPLE = 16384


def _unit_rows(values: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(values, axis=1)
    return values / np.maximum(norms, 1e-12)[:, None]


class IVFIndex:
    """Inverted-file cosine index over a fixed snapshot.

    ``values`` is the ``[N, D]`` snapshot (NOT copied — the caller
    owns snapshot lifetime, exactly as with the brute scan's
    ``index_values``); ``norms`` its per-row L2 norms.
    """

    def __init__(self, values: np.ndarray, norms: np.ndarray,
                 nlist: int, seed: int = 0):
        n = values.shape[0]
        unit = _unit_rows(values)
        rng = np.random.default_rng(seed)
        train = unit if n <= _KMEANS_SAMPLE else \
            unit[rng.choice(n, _KMEANS_SAMPLE, replace=False)]
        # Clamped to the TRAINING sample, not just the table: each
        # centroid seeds on a distinct training row, so an oversized
        # -ann_nlist on a big table must not ask for more seeds than
        # the sample holds.
        self.nlist = int(max(1, min(nlist, train.shape[0])))
        centroids = train[rng.choice(train.shape[0], self.nlist,
                                     replace=False)]
        for _ in range(_KMEANS_ITERS):
            assign = np.argmax(train @ centroids.T, axis=1)
            for c in range(self.nlist):
                members = train[assign == c]
                if members.shape[0]:
                    mean = members.mean(axis=0)
                    centroids[c] = mean / max(
                        float(np.linalg.norm(mean)), 1e-12)
                else:
                    # Empty cluster: reseed on a random training row so
                    # no list degenerates to zero coverage.
                    centroids[c] = train[rng.integers(train.shape[0])]
        self.centroids = centroids
        # Full-table assignment + CSR-style inverted lists: rows
        # sorted by cluster, offsets[c]:offsets[c+1] slices cluster c.
        # The VALUES are stored cluster-sorted too (one extra snapshot
        # copy): a probe then scores a few CONTIGUOUS slices instead
        # of fancy-index gathering thousands of scattered rows — the
        # gather's cache misses, not the flops, dominated the scan.
        assign_all = np.argmax(unit @ centroids.T, axis=1)
        self._order = np.argsort(assign_all, kind="stable") \
            .astype(np.int64)
        self._offsets = np.searchsorted(
            assign_all[self._order], np.arange(self.nlist + 1))
        self._sorted_values = np.ascontiguousarray(values[self._order])
        self._sorted_norms = np.ascontiguousarray(
            np.maximum(norms[self._order], 1e-12))

    def search(self, query: np.ndarray, k: int, nprobe: int,
               exclude: Optional[int] = None
               ) -> Tuple[np.ndarray, np.ndarray, int]:
        """Top-``k`` rows by cosine against ``query`` scanning the
        ``nprobe`` closest inverted lists. Returns ``(ids, scores,
        candidates_scanned)``; ``exclude`` drops one row id (the
        query row is not its own neighbor)."""
        nprobe = int(max(1, min(nprobe, self.nlist)))
        qn = max(float(np.linalg.norm(query)), 1e-12)
        qunit = (query / qn).astype(np.float32, copy=False)
        cscores = self.centroids @ qunit
        if nprobe < self.nlist:
            probe = np.argpartition(-cscores, nprobe - 1)[:nprobe]
        else:
            probe = np.arange(self.nlist)
        id_parts, score_parts = [], []
        for c in probe:
            lo, hi = self._offsets[c], self._offsets[c + 1]
            if lo == hi:
                continue
            id_parts.append(self._order[lo:hi])
            score_parts.append(
                (self._sorted_values[lo:hi] @ qunit)
                / self._sorted_norms[lo:hi])
        if not id_parts:
            return (np.empty(0, np.int64), np.empty(0, np.float32), 0)
        cand = np.concatenate(id_parts)
        scores = np.concatenate(score_parts)
        if exclude is not None:
            keep = cand != exclude
            cand, scores = cand[keep], scores[keep]
        if cand.size == 0:
            return (np.empty(0, np.int64), np.empty(0, np.float32), 0)
        k = min(k, cand.size)
        top = np.argpartition(-scores, k - 1)[:k]
        top = top[np.argsort(-scores[top])]
        return cand[top], scores[top], int(cand.size)

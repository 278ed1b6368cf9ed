"""The one generator of row traffic. A traffic mix is a JSON file of
parameters (``benchmark/traffic/<mix>.json``); this turns one, a table
height and a seed into the ids and deltas of the whole run, once and
vectorised. Every seed gives requests of the same sizes: only the ids
and values differ.

Parameters a mix may set (all read here, nowhere else):

``ops``               the closed loop's round, e.g. ``["get", "add"]`` (host
                      ids and numpy buffers) or ``["get_device",
                      "add_device"]`` (the same ids and deltas as
                      ``jax.Array``s; ``drivers/rows.py`` places them)
``ids_per_request``   distinct row ids in one request
``id_distribution``   ``{"kind": "zipf", "s": 1.0}`` or ``{"kind": "uniform"}``
``id_order``          ``"sorted"`` (what a worker sends after np.unique) or
                      ``"drawn"``
``pool_requests``     distinct id sets made up front and cycled through:
                      enough of them that a window never comes round to
                      the first again, so the run reads and writes as
                      much of the table as its distribution reaches
``delta_pool``        distinct delta buffers (20 MB each at 100,000 x 50),
                      cycled through independently of the ids; default
                      ``pool_requests``
``delta_step``        deltas are whole multiples of this in [-4, 4]; a power
                      of two keeps float32 sums of them exact
``sample_per_request`` positions of each request whose replies are kept
                      and compared after the window
``gets_checked``      how many Gets' sampled replies the run keeps room for
``untouched_rows``    further rows, drawn uniformly, that the final check
                      reads (most were never written and must be zero)
"""

from concurrent.futures import ThreadPoolExecutor

import numpy as np

GENERATOR_THREADS = 4


def _draw_ids(rng, kind: dict, rows: int, n: int) -> np.ndarray:
    if kind["kind"] == "uniform":
        return rng.integers(0, rows, n, dtype=np.int64)
    if kind["kind"] == "zipf":
        s = float(kind.get("s", 1.0))
        u = rng.random(n)
        if s == 1.0:
            # Inverse of the continuous CDF of a 1/x density on
            # [1, rows + 1): P(id = k) = ln((k+2)/(k+1)) / ln(rows+1).
            # No table of the table's height is built on the host.
            x = np.exp(u * np.log(rows + 1.0))
        else:
            top = (rows + 1.0) ** (1.0 - s)
            x = (1.0 + u * (top - 1.0)) ** (1.0 / (1.0 - s))
        return np.minimum(x.astype(np.int64) - 1, rows - 1)
    raise ValueError(f"unknown id_distribution {kind!r}")


def distinct_ids(rng, kind: dict, rows: int, n: int, order: str):
    """``n`` distinct ids in the order first drawn, or sorted."""
    if n > rows:
        raise ValueError(f"{n} distinct ids from {rows} rows")
    seen = np.zeros(0, np.int64)
    while seen.size < n:
        draw = np.concatenate([seen, _draw_ids(rng, kind, rows, 3 * n)])
        _, first = np.unique(draw, return_index=True)
        seen = draw[np.sort(first)]
    ids = seen[:n].astype(np.int32)
    if order == "sorted":
        ids.sort()
    elif order != "drawn":
        raise ValueError(f"unknown id_order {order!r}")
    return ids


class RowTraffic:
    def __init__(self, mix: dict, rows: int, cols: int, seed: int):
        rng = np.random.default_rng(seed)
        self.ops = list(mix["ops"])
        n = int(mix["ids_per_request"])
        pool = int(mix["pool_requests"])
        step = float(mix["delta_step"])
        # Each id set from a generator of its own (seed, set number), so
        # that a few threads can make them side by side: the sort in
        # np.unique is most of a set's 35 ms and releases the GIL.
        with ThreadPoolExecutor(GENERATOR_THREADS) as threads:
            self.ids = list(threads.map(
                lambda i: distinct_ids(
                    np.random.default_rng([seed, i]),
                    mix["id_distribution"], rows, n,
                    mix.get("id_order", "sorted")), range(pool)))
        span = int(round(4.0 / step))
        self.deltas = [
            (rng.integers(-span, span + 1, (n, cols)).astype(np.float32)
             * np.float32(step))
            for _ in range(int(mix.get("delta_pool", pool)))]
        # The rows the checks follow: some positions of every request,
        # and some rows drawn uniformly (mostly never written).
        k = min(int(mix["sample_per_request"]), n)
        picked = [ids[rng.integers(0, n, k)] for ids in self.ids]
        extra = rng.integers(0, rows, int(mix["untouched_rows"]))
        self.sample = np.unique(np.concatenate(picked + [extra])) \
            .astype(np.int32)
        # the most rows a sample can have, whatever the seed
        self.sample_most = pool * k + extra.size
        # For each request: which of its positions are sampled rows, and
        # where those rows sit in ``sample``.
        self.positions, self.sample_index = [], []
        for ids in self.ids:
            pos = np.nonzero(np.isin(ids, self.sample))[0]
            self.positions.append(pos)
            self.sample_index.append(np.searchsorted(self.sample, ids[pos]))

    def request(self, round_index: int) -> int:
        return round_index % len(self.ids)

    def delta(self, request: int) -> np.ndarray:
        return self.deltas[request % len(self.deltas)]

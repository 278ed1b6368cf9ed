#!/usr/bin/env python3
"""The controls of `keye30b.ps-16k`'s check: the cell run with one piece
of its arithmetic changed in its own process, which has to come out
`correct: false` by at least the limit named for it (`CAUGHT_BY`).

    python3 benchmark/tools/lm_sparse_controls.py \
        dense_attention|selection_by_attention|no_relu|no_w| \
        target_not_rescaled|attached_input|bfloat16_scores| \
        float8_experts|bfloat16_moments|none  [--seed N] [--seconds S] [--rehearse]
    python3 benchmark/tools/lm_sparse_controls.py attention_tiles

A wrong model or objective: `dense_attention`: every query reads every
key before it where the configuration says the 2,048 the indexer scores
highest; `selection_by_attention`: the keys are chosen by the attention's
own projections (the first key-value head and its query heads, unweighted)
and the indexer's tensors decide nothing; `no_relu`: the index heads'
scores are summed as they are; `no_w`: every index head weighs the same;
`target_not_rescaled`: the divergence's target is the heads'
probabilities summed, which sum to 32 over the selected keys and not to
1; `attached_input`: the indexer reads the layer's normed input
undetached, so the divergence's gradient runs on into the layer's input
and its attention norm. The next precision below the one the
configuration states: `bfloat16_scores`: the index scores rounded to
bfloat16 where it says float32, in the selection and in the divergence
(both call `sparse.index_scores`), which `selection.inexact` tells apart:
the reference's top-k of the program's own index inputs, scored as
stated, lacks the keys that the rounding swapped; `float8_experts` and
`bfloat16_moments` (tools/lm_lower_precision.py's; float8-rounded expert
inputs stay under every GRADIENT limit on most seeds at the cell's 20 s
window, so the forward reading of each layer alone, `layer.output`, is
named for them). Which limit caught
which control, with the readings, is in the configuration's `limits.what`
and PERF.md section 4.

`attention_tiles` is no control: on an untrained indexer every tile under
the diagonal holds a selected pair, so no run of the cell visits the
attention kernel's tables for an EMPTY or a FULL tile. This mode calls
`sparse.attention_vjp` on a crafted selection that holds empty, mixed and
full tiles (at the cell's tile of 512 on a TPU; tests/test_lm_sparse.py
runs the same function here, the kernel interpreted at a tile of 128) and
holds its output, row logsumexp and three gradients to the dense sums:
`correct: true` when all five are inside `TILE_LIMITS`.

`none` changes nothing: the same seed and window as the others, for the
readings they are set beside. The window is the cell's own 20 s unless
`--seconds` says otherwise: a gradient's floor rises with the steps
trained, and a control read at a shorter window proves nothing of the
limit that the cell's runs are held to. The program has no option for any of these:
this tool replaces the one function in its own process and then runs
benchmark/run.py's `main` unchanged. The three that change the index
scores take the program's `jax.numpy` form of scores and search on the
chip too (the kernel computes its scores inside): slower, the same sums.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import lm_lower_precision as precision  # noqa: E402

CELL = "keye30b.ps-16k"
#: The limit each control has to be outside, at the published widths and in
#: the rehearsal (benchmark/tests/test_lm_sparse_cell.py).
CAUGHT_BY = {"dense_attention": "selection.differs",
             "selection_by_attention": "selection.differs",
             "no_relu": "selection.differs", "no_w": "selection.differs",
             "target_not_rescaled": "loss.indexer",
             "attached_input": "gradient.scores",
             "bfloat16_scores": "selection.inexact",
             "float8_experts": "layer.output",
             "bfloat16_moments": "adam.moments"}


def _sparse():
    from multiverso_tpu.models.lm import sparse
    return sparse


def _scores_in_numpy_form():
    """The selection by ``sparse.index_scores`` and ``sparse.search`` on
    every backend, so that a change to the scores reaches it."""
    sparse = _sparse()
    exact = sparse.select

    def select(cfg, qi, ki, w):
        on_chip, sparse._on_chip = sparse._on_chip, lambda tile: False
        try:
            return exact(cfg, qi, ki, w)
        finally:
            sparse._on_chip = on_chip

    sparse.select = select


def dense_attention():
    import jax.numpy as jnp
    sparse = _sparse()
    exact = sparse.select

    def every_key(cfg, qi, ki, w):
        tiles, counts = exact(cfg, qi, ki, w)
        t = qi.shape[0]
        causal = jnp.tril(jnp.ones((t, t), bool))
        return sparse._tiled(causal, tiles.shape[2]), counts

    sparse.select = every_key


def selection_by_attention():
    import jax.numpy as jnp
    sparse = _sparse()
    lm = sparse.lm

    def from_projections(cfg, mats, sinks, norm, h, pos=None):
        t, d = h.shape[0], cfg.head_dim
        per = cfg.n_heads // cfg.n_kv_heads
        q = lm.mm(h, mats["wq"], jnp.zeros(mats["wq"].shape, lm.F32))
        k = lm.mm(h, mats["wk"], jnp.zeros(mats["wk"].shape, lm.F32))
        return (q.reshape(t, cfg.n_heads, d)[:, :per], k[:, :d],
                jnp.full((t, per), 1.0 / per, lm.F32))

    sparse.index_inputs = from_projections


def no_relu():
    import jax.numpy as jnp
    sparse = _sparse()
    _scores_in_numpy_form()

    def summed(qi, ki, w):
        s = jnp.einsum("rjd,kd->rjk", qi.astype(sparse.BF16),
                       ki.astype(sparse.BF16),
                       preferred_element_type=sparse.F32)
        return jnp.sum(w[:, :, None] * s, axis=1)

    sparse.index_scores = summed


def no_w():
    import jax.numpy as jnp
    sparse = _sparse()
    _scores_in_numpy_form()
    exact = sparse.index_scores
    sparse.index_scores = lambda qi, ki, w: exact(
        qi, ki, jnp.full_like(w, (qi.shape[1] * qi.shape[2]) ** -0.5)
        + 0.0 * w)


def target_not_rescaled():
    _sparse().target_of = lambda probabilities, heads: probabilities


def attached_input():
    _sparse().detached = lambda h: h


def bfloat16_scores():
    import jax
    sparse = _sparse()
    _scores_in_numpy_form()
    exact = sparse.index_scores
    sparse.index_scores = lambda qi, ki, w: jax.lax.reduce_precision(
        exact(qi, ki, w), 8, 7)


CHANGES = {"dense_attention": dense_attention,
           "selection_by_attention": selection_by_attention,
           "no_relu": no_relu, "no_w": no_w,
           "target_not_rescaled": target_not_rescaled,
           "attached_input": attached_input,
           "bfloat16_scores": bfloat16_scores,
           "float8_experts": precision.float8_experts,
           "bfloat16_moments": precision.bfloat16_moments,
           "none": lambda: None}


#: ``attention_tiles``: relative L2 error against the dense sums. The
#: outputs and gradients are bfloat16 (2**-9 an element), the row
#: logsumexp float32.
TILE_LIMITS = {"o": 1e-2, "lse": 1e-4, "dq": 1e-2, "dk": 1e-2, "dv": 1e-2}


def attention_tiles(t: int = 2048, tile: int = 512,
                    interpret: bool = False) -> dict:
    """``sparse.attention_vjp``'s kernel form over a selection of four by
    four tiles, among them EMPTY ones at a row's start (2, 0), (3, 0), a
    MIXED one off the diagonal (3, 1: three pairs in ten), FULL ones (1,
    0), (2, 1), (3, 2) and the causal triangles on the diagonal, against
    ``sparse._dense_attention`` under the same mask: ``{o, lse, dq, dk,
    dv: relative L2 error}``. ``interpret`` runs the kernel interpreted,
    wherever the process runs."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    sparse = _sparse()
    assert t == 4 * tile, (t, tile)
    groups, per, d = 4, 8, 128
    rng = np.random.default_rng(7)
    tiles = np.array(sparse._tiled(jnp.tril(jnp.ones((t, t), bool)), tile))
    tiles[2, 0] = tiles[3, 0] = False
    tiles[3, 1] = rng.random((tile, tile)) < 0.3
    tiles = jnp.asarray(tiles)
    q, do = (jnp.asarray(rng.standard_normal((groups, per, t, d)) * scale,
                         sparse.BF16) for scale in (d ** -0.5, 1.0))
    k, v = (jnp.asarray(rng.standard_normal((groups, t, d)), sparse.BF16)
            for _ in range(2))

    def run(q, k, v, do):
        o, lse, pull = sparse.attention_vjp(q, k, v, tiles)
        return (o, lse) + tuple(pull(do))

    mask = sparse._untiled(tiles)
    (o, lse), pull = jax.vjp(
        lambda q, k, v: sparse._dense_attention(q, k, v, mask), q, k, v)
    want = (o, lse) + pull((do, jnp.zeros_like(lse)))
    on_chip, static = sparse._on_chip, sparse._splash_static
    try:
        if interpret:
            sparse._on_chip = lambda tile: True
            sparse._splash_static = lambda tile: dict(static(tile),
                                                      interpret=True)
        assert sparse._on_chip(tile), "the kernel's form is not taken here"
        got = jax.jit(run)(q, k, v, do)
    finally:
        sparse._on_chip, sparse._splash_static = on_chip, static

    def error(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    return {n: error(a, b) for n, a, b in zip(TILE_LIMITS, got, want)}


def main() -> int:
    if sys.argv[1:2] == ["attention_tiles"]:
        import json
        errors = attention_tiles()
        print(json.dumps({
            "correct": all(errors[n] <= TILE_LIMITS[n] for n in TILE_LIMITS),
            "compared": {n: {"value": errors[n], "limit": TILE_LIMITS[n]}
                         for n in TILE_LIMITS}}))
        return 0
    parser = argparse.ArgumentParser()
    parser.add_argument("what", choices=tuple(CHANGES))
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--seconds", type=float, default=20.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    CHANGES[args.what]()
    from benchmark import run
    print(f"[control] {args.what}", flush=True)
    return run.main(["--workload", CELL, "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"]
                    + (["--rehearse"] if args.rehearse else []))


if __name__ == "__main__":
    sys.exit(main())

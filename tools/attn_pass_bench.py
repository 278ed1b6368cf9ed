#!/usr/bin/env python3
"""A layer's attention, forward and pulled, with the one pass of
``models/lm/attn_kernels.py`` in it against the ``jax.numpy`` chain, on the
chip this runs on (it refuses the CPU):

    chiprun -- python3 tools/attn_pass_bench.py [config.json ...]

For every kind of layer of each configuration (default: the four under
``benchmark/configs`` whose attention is ``model.attention_vjp``'s) at the
cell's sequence length, on one drawn sequence and one drawn cotangent:
``a`` and every result of the pull, each fused result's distance from the
chain's over the chain's norm (a few ties of the bfloat16 roundings: 2e-3
or less, 0 where a layer has neither norm nor turn and keeps the chain), the heads
``attention_inputs`` gives counted entry by entry (how many bfloat16 values
differ, of how many), and the milliseconds of both forms (the attention
proper is in both: the difference is the passes'). One JSON line a kind.
"""

import dataclasses
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from multiverso_tpu.models.lm import (LMConfig, attn_kernels,  # noqa: E402
                                      model as lm, ps_train)

CELLS = {"smallthinker-21ba3b-l4": 8192, "sdar-30b-a3b-l6": 4096,
         "laguna-xs2-33b-a3b-l5": 8192, "keye-vl2-30b-a3b-lm": 16384}
REPEATS = 5


def _ms(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return float(np.median(times))


def _forms(cfg, kind, seq_len):
    """``(heads, layer)`` jitted for one kind of layer: what
    ``attention_inputs`` gives, and ``attention_vjp``'s ``a`` with its
    pull's results; dense attention (a selection's is sparse.py's own)."""
    rope, mask, pos = ps_train._kind(cfg, *kind[:2], seq_len)

    def heads(mats, small, x):
        return lm.attention_inputs(cfg, rope, mats, lm._zeros_like_f32(mats),
                                   lm._attention_norms(cfg, small), x,
                                   pos)[:3]

    def layer(mats, small, x, da):
        dense = dataclasses.replace(cfg, selection="none")
        a, _, pull = lm.attention_vjp(dense, rope, mask, mats,
                                      lm._zeros_like_f32(mats), small, x, pos)
        return (a,) + tuple(pull(da))

    return jax.jit(heads), jax.jit(layer)


def _relative(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def main(paths) -> int:
    if jax.default_backend() != "tpu":
        print("attn_pass_bench: needs a TPU", file=sys.stderr)
        return 2
    for path in paths:
        with open(path) as f:
            described = json.load(f)
        cfg = LMConfig.from_dict(described)
        seq_len = CELLS.get(described.get("name"), 8192)
        t = seq_len * (2 if cfg.objective == "block_diffusion" else 1)
        rng = np.random.default_rng(0)
        kinds = cfg.layer_kinds()
        for kind in sorted(set(kinds)):
            shapes = cfg.layer_shapes(kinds.index(kind))
            names = lm.GQA_MATRICES + (
                (lm.ATTN_GATE,) if cfg.attn_gate == "head" else ())
            mats = {n: jnp.asarray(
                rng.normal(size=shapes[n]) * shapes[n][0] ** -0.5,
                jnp.bfloat16) for n in names}
            small = {n: jnp.asarray(1 + 0.1 * rng.normal(size=s), jnp.float32)
                     for n, s in shapes.items()
                     if len(s) == 1 and n.startswith("norm")}
            x, da = (jnp.asarray(rng.normal(size=(t, cfg.hidden)),
                                 jnp.float32) for _ in range(2))
            fits, out = attn_kernels.fits, {}
            for form in ("fused", "plain"):
                if form == "plain":
                    attn_kernels.fits = lambda t, d: False
                try:
                    heads, layer = _forms(cfg, kind, seq_len)
                    out[form] = (
                        heads(mats, small, x), layer(mats, small, x, da),
                        _ms(heads, mats, small, x),
                        _ms(layer, mats, small, x, da))
                finally:
                    attn_kernels.fits = fits
            (h1, l1, ms_h1, ms_l1), (h0, l0, ms_h0, ms_l0) = (
                out["fused"], out["plain"])
            differ = [int(np.sum(np.asarray(a, np.float32)
                                 != np.asarray(b, np.float32)))
                      for a, b in zip(h1, h0)]
            leaves = jax.tree_util.tree_leaves
            print(json.dumps({
                "config": described.get("name"), "kind": kind, "tokens": t,
                "fused": lm.attention_pass_fused(cfg, t, kind[0]),
                "heads_differ": differ,
                "heads_of": [int(np.prod(a.shape)) for a in h1],
                "layer_relative_worst": max(
                    _relative(a, b) for a, b in zip(leaves(l1), leaves(l0))),
                "a_relative": _relative(l1[0], l0[0]),
                "dx_relative": _relative(l1[1], l0[1]),
                "heads_ms": {"fused": ms_h1, "plain": ms_h0},
                "layer_ms": {"fused": ms_l1, "plain": ms_l0}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or [
        os.path.join(ROOT, "benchmark", "configs", f"{name}.json")
        for name in CELLS]))

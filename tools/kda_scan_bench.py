#!/usr/bin/env python3
"""``delta.scan`` alone, both of its paths, on the chip this runs on (it
refuses the CPU):

    chiprun -- python3 tools/kda_scan_bench.py [T [heads]]

At the cell's shapes (default 8192 positions, 32 heads of 128 lanes, chunks
of 64; inputs drawn as ``gates`` leaves them: unit k, q a unit over
``sqrt(128)``, log decays that put about a fifth of the chunks' channels
under ``delta.DEEP``) the Pallas kernels of ``models/lm/delta_kernels.py``
against the ``jax.numpy`` runs of chunks: the milliseconds of the forward
pass and of the forward pass made again and pulled (what a layer's backward
program runs), each result's distance from the other path's over its norm
(bfloat16 roundings that fall the other way: 1e-3 or less), the deep
counts, and a head-chunk's microseconds. One JSON line.
"""

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from multiverso_tpu.models.lm import delta, delta_kernels  # noqa: E402

REPEATS = 5
LANES = 128


def _ms(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return float(np.median(times))


def _relative(a, b):
    a, b = (np.asarray(v, np.float64) for v in (a, b))
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def draw(t: int, heads: int, seed: int = 0):
    """``scan``'s five arguments and a cotangent, on the device."""
    def unit(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True))

    kq, kk, kv, kg, kb, kc = jax.random.split(jax.random.PRNGKey(seed), 6)
    wide = (t, heads, LANES)
    # a channel's decay a position from 1e-3 to 2: the slow ones keep a
    # chunk, the fast ones pass exp(-20) inside it
    rate = jnp.exp(jax.random.uniform(kg, (1, heads, LANES), minval=np.log(
        1e-3), maxval=np.log(2.0)))
    return (unit(jax.random.normal(kq, wide)) * LANES ** -0.5,
            unit(jax.random.normal(kk, wide)), jax.random.normal(kv, wide),
            -rate * jax.random.uniform(kg, wide, minval=0.5, maxval=1.5),
            jax.random.uniform(kb, (t, heads), minval=0.1, maxval=0.9),
            jax.random.normal(kc, wide))


def main(argv) -> int:
    if jax.default_backend() != "tpu":
        print("kda_scan_bench: needs a TPU", file=sys.stderr)
        return 2
    t = int(argv[0]) if argv else 8192
    heads = int(argv[1]) if len(argv) > 1 else 32
    *args, cot = draw(t, heads)
    chosen = delta.scan_in_kernels
    out = {"tokens": t, "heads": heads,
           "kernels": delta.scan_in_kernels(t, LANES, LANES)}
    results = {}
    for name, rule in (("kernels", chosen), ("plain", lambda *a: False)):
        delta.scan_in_kernels = rule
        forward = jax.jit(lambda *a: delta.scan(*a))
        pulled = jax.jit(lambda *a: jax.vjp(
            lambda *b: delta.scan(*b)[0], *a[:-1])[1](a[-1]))
        out[f"forward_ms.{name}"] = _ms(forward, *args)
        out[f"again_and_pulled_ms.{name}"] = _ms(pulled, *args, cot)
        o, deep = forward(*args)
        results[name] = (o, *pulled(*args, cot))
        out[f"deep.{name}"] = int(deep)
    delta.scan_in_kernels = chosen
    for at, part in enumerate(("o", "dq", "dk", "dv", "dg", "dbeta")):
        out[f"distance.{part}"] = _relative(results["kernels"][at],
                                            results["plain"][at])
    visits = heads * t // delta.CHUNK
    for name in ("kernels", "plain"):
        out[f"us_a_head_chunk.forward.{name}"] = \
            1e3 * out[f"forward_ms.{name}"] / visits
        out[f"us_a_head_chunk.again_and_pulled.{name}"] = \
            1e3 * out[f"again_and_pulled_ms.{name}"] / visits
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

#!/usr/bin/env python3
"""``ssd.scan`` alone, both of its paths, on the chip this runs on (it
refuses the CPU):

    chiprun -- python3 tools/ssd_scan_bench.py [T [heads [heads a grid step]]]

At the cell's shapes (default 8192 positions, 64 heads of 64 lanes, a state
of 128, chunks of 256; ``a_log`` and the steps' bias drawn as
``ps_train.decay_init`` leaves them, so that about half the (chunk, head)
pairs are under ``delta.DEEP``) the Pallas kernels of
``models/lm/ssd_kernels.py`` against the ``jax.numpy`` runs of chunks: the
milliseconds (host clock, and the device's from a trace) of the forward walk
and of the walk made again and pulled (what a layer's backward program
runs), for ``scan`` (the arrays apart, no skip) and for ``scanned`` (the
layer's part: X, B and C one array, the skip taken), each result's distance
from the other
path's over its norm (bfloat16 roundings that fall the other way: 1e-3 or
less), the deep counts, a chunk's microseconds, and from the trace of
both paths' programs the time by operation family (the last name of an
operation's ``tf_op`` path: the primitive a fusion ends in). The arrays are
handed over TURNED ([H P, T], [H, T], [N, T]: positions along the lanes, as
the convolution leaves them in the cell's layer program) and viewed as
``scan`` takes them inside the timed programs, so that neither path pays a
change of layout the cell does not. One JSON line.
"""

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from multiverso_tpu.models.lm import ssd  # noqa: E402
from multiverso_tpu.models.lm.ps_train import decay_init  # noqa: E402

REPEATS = 5
LANES, STATE = 64, 128
PARTS = ("y", "dx", "ddt", "da_log", "db", "dc")


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
    """``scan``'s five arguments and a cotangent, turned, on the device: x
    as a convolution's silu leaves it, steps ``softplus(a small projection
    + dt_bias)``."""
    kx, kd, kb, kc, ky = jax.random.split(jax.random.PRNGKey(seed), 5)
    wide = (heads * LANES, t)
    dt = ssd.step(0.1 * jax.random.normal(kd, (t, heads)),
                  jnp.asarray(decay_init("dt_bias", heads, seed)))
    return (jax.nn.silu(jax.random.normal(kx, wide)), dt.T,
            jnp.asarray(decay_init("a_log", heads, seed + 1)),
            jax.nn.silu(jax.random.normal(kb, (STATE, t))),
            jax.nn.silu(jax.random.normal(kc, (STATE, t))),
            jax.random.normal(ky, wide))


def scan(x, dt, a_log, b, c):
    """``ssd.scan`` of the turned arrays: ``(y [H P, T], deep)``."""
    t, heads = x.shape[1], dt.shape[0]
    y, deep = ssd.scan(x.T.reshape(t, heads, LANES), dt.T, a_log, b.T, c.T)
    return y.reshape(t, -1).T, deep


class _Widths:
    ssd_head_dim, ssd_state, ssd_groups, ssd_chunk = LANES, STATE, 1, 0


def scanned(x, dt, a_log, b, c):
    """``ssd.scanned`` (the layer's part: the steps' bias, the scan and the
    skip, X, B and C one array as the convolution leaves them) of the same
    arrays, a skip of 1 and no bias: ``(y [H P, T], deep)``."""
    heads = dt.shape[0]
    raw = jnp.log(jnp.expm1(dt))        # softplus's inverse
    y, deep = ssd.scanned(_Widths, jnp.zeros(heads), a_log, jnp.ones(heads),
                          jnp.concatenate([x, b, c], axis=0).T, raw.T)
    return y.T, deep


def families_ms(trace_dir, runs: int):
    """``{program stem: {family: ms a run}}`` from a trace: the device's
    operations inside each program's runs, by the last name of their
    ``tf_op`` path (the operation's own name where it has none)."""
    from benchmark.lib import xplane, xspace
    trace = xspace.load(xplane.find_xplane(trace_dir))
    found = {}
    for device in trace["devices"].values():
        modules = [(a, b, xplane.stem(name))
                   for name, a, b in device["modules"]]
        for name, a, b, path in device["ops"]:
            stem = next((s for lo, hi, s in modules if lo <= a and b <= hi),
                        None)
            if stem is None:
                continue
            if name.startswith("%while"):   # its body's operations follow
                continue
            family = path.rstrip(":/").split("/")[-1] if path \
                else name.split(" = ")[0].split(".")[0].lstrip("%")
            if "mv_ssd_scan" in name + path:
                family = "mv_ssd_scan_bwd" if "bwd" in name + path \
                    else "mv_ssd_scan_fwd"
            of = found.setdefault(stem, {})
            of[family] = of.get(family, 0.0) + (b - a) / 1e6 / runs
    return {stem: dict(sorted(of.items(), key=lambda kv: -kv[1]))
            for stem, of in found.items()}


def main(argv) -> int:
    if jax.default_backend() != "tpu":
        print("ssd_scan_bench: needs a TPU", file=sys.stderr)
        return 2
    t = int(argv[0]) if argv else 8192
    heads = int(argv[1]) if len(argv) > 1 else 64
    if len(argv) > 2:   # the kernels' blocking, to try another
        from multiverso_tpu.models.lm import ssd_kernels
        ssd_kernels.HEADS_A_STEP = int(argv[2])
    *args, cot = draw(t, heads)
    chosen = ssd.scan_in_kernels
    out = {"tokens": t, "heads": heads, "heads_a_step": argv[2:3],
           "kernels": ssd.scan_in_kernels(t, heads, LANES, STATE)}
    results, programs = {}, []
    for what in (scan, scanned):
        for path, rule in (("kernels", chosen), ("plain", lambda *a: False)):
            if path == "kernels" and not out["kernels"]:
                continue
            ssd.scan_in_kernels = rule
            name = f"{what.__name__}.{path}"

            def forward(*a, what=what):
                return what(*a)

            def pulled(*a, what=what):
                return jax.vjp(lambda *b: what(*b)[0], *a[:-1])[1](a[-1])

            forward.__name__ = f"forward_{what.__name__}_{path}"
            pulled.__name__ = f"pulled_{what.__name__}_{path}"
            forward, pulled = jax.jit(forward), jax.jit(pulled)
            out[f"forward_ms.{name}"] = _ms(forward, *args)
            out[f"again_and_pulled_ms.{name}"] = _ms(pulled, *args, cot)
            y, deep = forward(*args)
            results[name] = (y, *pulled(*args, cot))
            out[f"deep.{name}"] = int(deep)
            programs += [(forward, args), (pulled, (*args, cot))]
    ssd.scan_in_kernels = chosen
    out["pairs"] = heads * t // ssd.CHUNK
    for what in ("scan", "scanned"):
        if f"{what}.kernels" in results:
            for at, part in enumerate(PARTS):
                out[f"distance.{what}.{part}"] = _relative(
                    results[f"{what}.kernels"][at],
                    results[f"{what}.plain"][at])
    for name in results:
        out[f"us_a_chunk.again_and_pulled.{name}"] = \
            1e3 * out[f"again_and_pulled_ms.{name}"] / (t // ssd.CHUNK)
    trace_dir = tempfile.mkdtemp(prefix="ssd_scan_bench_")
    jax.profiler.start_trace(trace_dir)
    for _ in range(REPEATS):
        for program, its in programs:
            jax.block_until_ready(program(*its))
    jax.profiler.stop_trace()
    out["device_ms_by_family"] = families_ms(trace_dir, REPEATS)
    out["device_ms"] = {stem: sum(of.values()) for stem, of
                        in out["device_ms_by_family"].items()}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

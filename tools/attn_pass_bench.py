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

A configuration under latent attention (``glm47-flash-30b-a3b-l5``; give
xing's or kimi's file to see ``fused: false``) gets the same line from
``latent.inputs`` and ``latent.attention_vjp`` with ``latent_kernels.py``'s
pass in them, and ``pass_ms``: the two kernels alone on drawn products and
cotangents, with the bytes each moves and the share of the chip's memory
bound (``benchmark/peaks.json``) that it reaches, and how many of the
forward pass's bfloat16 values differ from the chain's ON THE SAME PRODUCTS
(``heads_differ`` there; expected 0).
"""

import contextlib
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
                                      latent, model as lm, ps_train)

CELLS = {"smallthinker-21ba3b-l4": 8192, "sdar-30b-a3b-l6": 4096,
         "laguna-xs2-33b-a3b-l5": 8192, "keye-vl2-30b-a3b-lm": 16384,
         "glm47-flash-30b-a3b-l5": 8192}
REPEATS = 5


def _ms(fn, *args):
    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(REPEATS):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - start))
    return float(np.median(times))


def _ms_looped(step, first, loops=21):
    """Milliseconds a call of ``step`` INSIDE a program: ``loops`` calls in
    one ``fori_loop``, each fed something of the last so that none is
    hoisted, less a loop of one, over the calls between. A call from the
    host costs ~0.6 ms here whatever it runs (as much as a kernel of a
    millisecond; ``_ms`` counts it, which is fair between two forms of a
    layer and wrong beside a bound)."""
    def program(n):
        return jax.jit(lambda carry: jax.lax.fori_loop(
            0, n, lambda _, c: step(c), carry))

    return (_ms(program(loops), first) - _ms(program(1), first)) \
        / (loops - 1)


def _both_forms(forms, plain, mats, small, x, da):
    """What the line says of the two forms: ``forms()`` gives the jitted
    ``(heads, layer)`` of the form in force, ``plain`` is a context that
    puts the ``jax.numpy`` chain in force."""
    out = {}
    for form in ("fused", "plain"):
        with plain() if form == "plain" else contextlib.nullcontext():
            heads, layer = forms()
            out[form] = (heads(mats, small, x), layer(mats, small, x, da),
                         _ms(heads, mats, small, x),
                         _ms(layer, mats, small, x, da))
    (h1, l1, ms_h1, ms_l1), (h0, l0, ms_h0, ms_l0) = (
        out["fused"], out["plain"])
    leaves = jax.tree_util.tree_leaves
    return {"heads_differ": _counted(h1, h0),
            "heads_of": [int(np.prod(a.shape)) for a in h1],
            "layer_relative_worst": max(
                _relative(a, b) for a, b in zip(leaves(l1), leaves(l0))),
            "a_relative": _relative(l1[0], l0[0]),
            "dx_relative": _relative(l1[1], l0[1]),
            "heads_ms": {"fused": ms_h1, "plain": ms_h0},
            "layer_ms": {"fused": ms_l1, "plain": ms_l0}}


@contextlib.contextmanager
def _replaced(module, name, value):
    kept = getattr(module, name)
    setattr(module, name, value)
    try:
        yield
    finally:
        setattr(module, name, kept)


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


def _counted(fused, plain):
    return [int(np.sum(np.asarray(a, np.float32) != np.asarray(b, np.float32)))
            for a, b in zip(fused, plain)]


def _latent_forms(cfg, rope):
    """``_forms`` of a latent layer: what ``latent.inputs`` gives, and
    ``latent.attention_vjp``'s ``F(x)`` with its pull's results."""
    _, norms = latent.names(cfg)

    def heads(mats, small, x):
        return latent.inputs(cfg, mats, lm._zeros_like_f32(mats),
                             tuple(small[n] for n in norms), x, None, rope)

    def layer(mats, small, x, da):
        out, pull = latent.attention_vjp(cfg, mats, lm._zeros_like_f32(mats),
                                         small, x, None, rope)
        return (out,) + tuple(pull(da))

    return jax.jit(heads), jax.jit(layer)


def _latent_pass_alone(cfg, t, rng):
    """The two kernels of ``latent_kernels.py`` on drawn products and
    cotangents: milliseconds, bytes moved, share of the memory bound."""
    from multiverso_tpu.models.lm import latent_kernels
    how, heads = latent._pass(cfg), cfg.n_heads_held
    with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
        peak = json.load(f)[jax.devices()[0].device_kind]["hbm_bytes_per_s"]
    # ``W_kvb``'s product comes rounded, as the chain rounds it
    products = [jnp.asarray(rng.normal(size=(t, n)), dtype)
                for n, dtype in ((heads * how.d, jnp.float32),
                                 (heads * (how.nope + how.v), how.dtype),
                                 (how.rope, jnp.float32))]
    tables = tuple(jnp.asarray(table, jnp.float32) for table in
                   lm.rotary_tables(t, how.rope, cfg.rope_theta))
    forward = jax.jit(lambda *a: latent_kernels.heads_in(how, *a, tables))
    out = forward(*products)

    def chain(qf, kvf, k_r):
        """``latent.inputs``' lines after the products, on the same ones
        (a layer's two forms compile their products apart)."""
        q, kv = qf.reshape(t, heads, how.d), kvf.reshape(t, heads, -1)
        q_r = lm._rotary(q[..., how.nope:], cfg.rope_theta)
        k_r = lm._rotary(k_r[:, None, :], cfg.rope_theta)
        q = jnp.concatenate([q[..., :how.nope], q_r], -1) * how.scale
        k = jnp.concatenate([kv[..., :how.nope], jnp.broadcast_to(
            k_r, (t, heads, how.rope))], -1)
        return (q.astype(how.dtype).transpose(1, 0, 2)[:, None],
                k.astype(how.dtype).transpose(1, 0, 2),
                kv[..., how.nope:].astype(how.dtype).transpose(1, 0, 2))

    differ = _counted(out, jax.jit(chain)(*products))
    cotangents = [jnp.asarray(rng.normal(size=a.shape), a.dtype)
                  for a in out]
    def nbytes(arrays):
        return sum(a.size * a.dtype.itemsize for a in arrays)

    # the pull's bfloat16 results, before they are widened for ``mm``
    moved = {"forward": nbytes(products) + nbytes(out),
             "pull": nbytes(cotangents) + 2 * t * heads * (
                 2 * how.d + how.v - how.rope) + 4 * t * how.rope}
    half = how.rope // 2

    def forward_step(k_r):      # the shared key's lanes carry the loop
        k = latent_kernels.heads_in(how, *products[:2], k_r, tables)[1]
        return k_r + 0.0 * k[0, :, how.nope:].astype(jnp.float32)

    def pull_step(cos):         # and the cosines the pull's
        d_k_r = latent_kernels._pull(how, (cos, tables[1]), cotangents)[2]
        return cos + 0.0 * d_k_r[:, :half]

    ms = {"forward": _ms_looped(forward_step, products[2]),
          "pull": _ms_looped(pull_step, tables[0])}
    return {"heads_differ": differ, **{
        way: {"ms": ms[way], "bytes": moved[way],
              "bound_share": moved[way] / peak / (1e-3 * ms[way])}
        for way in ms}}


def _latent(cfg, described, t, rng):
    """A latent layer's line a turned-or-not kind of layer."""
    for rope in sorted(set(cfg.rope_layout[i] for i in range(cfg.n_layers)
                           if cfg.attention_of(i) == "mla")):
        line = {"config": described.get("name"), "kind": ("mla", rope),
                "tokens": t, "fused": latent.pass_fused(cfg, t, rope)}
        if not line["fused"]:
            print(json.dumps(line), flush=True)
            continue
        matrices, _ = latent.names(cfg)
        shapes = cfg.layer_shapes(cfg.n_layers - 1)
        mats = {n: jnp.asarray(
            rng.normal(size=shapes[n]) * shapes[n][0] ** -0.5, jnp.bfloat16)
            for n in matrices}
        small = {n: jnp.asarray(1 + 0.1 * rng.normal(size=shapes[n]),
                                jnp.float32) for n in latent.NORMS
                 if n in shapes}
        x, da = (jnp.asarray(rng.normal(size=(t, cfg.hidden)), jnp.float32)
                 for _ in range(2))
        line.update(_both_forms(
            lambda: _latent_forms(cfg, rope),
            lambda: _replaced(latent, "pass_fused",
                              lambda cfg, t, rope=True: False),
            mats, small, x, da))
        line["pass_ms"] = _latent_pass_alone(cfg, t, rng)
        print(json.dumps(line), flush=True)


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
        if cfg.attention == "mla":
            _latent(cfg, described, t, rng)
            continue
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
            print(json.dumps({
                "config": described.get("name"), "kind": kind, "tokens": t,
                "fused": lm.attention_pass_fused(cfg, t, kind[0]),
                **_both_forms(
                    lambda: _forms(cfg, kind, seq_len),
                    lambda: _replaced(attn_kernels, "fits",
                                      lambda t, d: False),
                    mats, small, x, da)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:] or [
        os.path.join(ROOT, "benchmark", "configs", f"{name}.json")
        for name in CELLS]))

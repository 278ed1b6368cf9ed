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


def _ms_looped(step, first, operands=None, loops=21):
    """Milliseconds a call of ``step`` INSIDE a program: ``loops`` calls in
    one ``fori_loop``, each fed something of the last so that none is
    hoisted, less a loop of one, over the calls between. A call from the
    host costs ~0.6 ms here whatever it runs (as much as a kernel of a
    millisecond; ``_ms`` counts it, which is fair between two forms of a
    layer and wrong beside a bound). With ``operands`` the step is
    ``step(carry, operands)`` and they are the program's arguments."""
    if operands is None:
        operands, step = (), (lambda c, _, step=step: step(c))

    def program(n):
        return jax.jit(lambda carry, ops: jax.lax.fori_loop(
            0, n, lambda _, c: step(c, ops), carry))

    return (_ms(program(loops), first, operands)
            - _ms(program(1), first, operands)) / (loops - 1)


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


def _delta_passes_alone(cfg, t, rng, peak):
    """The kernels of ``delta_passes.py`` (the gates' once more with q's
    and k's convolutions in it; a short convolution alone and its pull,
    whose loops the weights carry) and the ``jax.numpy`` chain
    they stand for, on drawn products and cotangents: milliseconds inside a
    program, the bytes a pass moves, the share of the memory bound it
    reaches (``peak``: its bytes a second), each result's distance from the
    chain's. The kernels' loops
    are carried by a small operand (beta's logits or cotangent, the output
    norm's scale); the chain's by EVERY result, or the compiler computes
    the part that is read and drops the rest (so ``chain_ms`` holds the
    loop's own copies too, and on arrays that fit the chip's fast memory
    reads under what a layer program's chain takes: the cell's
    ``trainer.attn_kda_ms_per_step.lm`` is the comparison that counts)."""
    from multiverso_tpu.models.lm import delta
    passes, how = delta._passes(cfg)
    heads, d = cfg.kda_heads_held, cfg.kda_head_dim

    def drawn(*shape):
        return jnp.asarray(rng.normal(size=shape), jnp.float32)

    a_log, dt_bias, norm_o = drawn(heads), drawn(heads * d), 1 + 0.1 * drawn(d)
    q, k, f, gate, o = (drawn(t, heads * d) for _ in range(5))
    b = drawn(t, heads)
    ins, cots = (a_log, dt_bias, q, k, f, b), (q[::-1], k[::-1], f[::-1],
                                               b[::-1])
    norm_ins, dy = (norm_o, o, gate), gate[::-1]

    def gates_chain(a_log, dt_bias, q, k, f, b):
        qo, ko, _, g, beta = delta.gates(cfg, a_log, dt_bias, q, k, q, f, b)
        return qo.reshape(t, -1), ko.reshape(t, -1), g.reshape(t, -1), beta

    def norm_chain(norm_o, o, gate):
        return lm.rmsnorm(delta.heads_apart(o, heads), norm_o,
                          cfg.eps).reshape(t, -1) * jax.nn.sigmoid(gate)

    def gates_fused(*a):
        return passes.gates(how, *a)

    def norm_fused(*a):
        return passes.gated_norm(how, *a)

    def pull(fn, n):
        """``fn``'s pull as a function of its ``n`` inputs and then its
        cotangents, all arguments (a closed-over array is a constant of
        the program: hundreds of megabytes to compile and cache)."""
        return lambda *a: jax.vjp(fn, *a[:n])[1](
            a[n:] if len(a) > n + 1 else a[n])

    # name: (the pass, the chain, their arguments, bytes moved in units of a
    # wide float32 array, the pass's loop, the chain's loop); a loop is
    # (step(carry, operands), first carry, operands)
    def gates_pull_chain(c, ops):
        d_a_log, d_bias, dq, dk, df, db = pull(gates_chain, 6)(*ops, *c)
        return dq, dk, df + 0.0 * d_bias, db + 0.0 * d_a_log

    def norm_pull_chain(dy, ops):
        d_norm, do, d_gate = pull(norm_chain, 3)(*ops, dy)
        return do + 0.0 * (d_gate + jnp.tile(d_norm, heads))

    taps = drawn(heads * d, cfg.kda_conv) * 0.5, drawn(heads * d,
                                                       cfg.kda_conv) * 0.5

    def conv_fused(wq, wk, *a):
        return passes.conv_gates(how, wq, wk, *a)

    def conv_chain(wq, wk, a_log, dt_bias, q, k, f, b):
        return gates_chain(a_log, dt_bias, delta.short_conv(q, wq),
                           delta.short_conv(k, wk), f, b)

    def alone_fused(x, w):
        return passes.conv(how, x, w)

    def conv_pull_chain(g, ops):
        dx, dw = pull(delta.short_conv, 2)(*ops, g)
        return dx + 0.0 * jnp.sum(dw, 1)

    forms = {
        "conv_gates": (
            conv_fused, conv_chain, taps + ins, 6,
            (lambda b, ops: b + 0.0 * conv_fused(*ops, b)[3], b,
             taps + ins[:5]),
            (lambda c, ops: conv_chain(*ops, *c), (q, k, f, b),
             taps + ins[:2])),
        "gates": (
            gates_fused, gates_chain, ins, 6,
            (lambda b, ops: b + 0.0 * gates_fused(*ops, b)[3], b, ins[:5]),
            (lambda c, ops: gates_chain(*ops, *c), (q, k, f, b), ins[:2])),
        "gates_pull": (
            pull(gates_fused, 6), pull(gates_chain, 6), ins + cots, 8.5,
            (lambda db, ops: db + 0.0 * pull(gates_fused, 6)(*ops, db)[5],
             cots[3], ins + cots[:3]),
            (gates_pull_chain, cots, ins)),
        "norm": (
            norm_fused, norm_chain, norm_ins, 2.5,
            (lambda n, ops: n + 0.0 * norm_fused(n, *ops)[0, :d], norm_o,
             norm_ins[1:]),
            (lambda o, ops: norm_chain(ops[0], o, ops[1]), o,
             (norm_o, gate))),
        "norm_pull": (
            pull(norm_fused, 3), pull(norm_chain, 3), norm_ins + (dy,), 4.5,
            (lambda n, ops: n + 0.0 * pull(norm_fused, 3)(n, *ops)[0],
             norm_o, norm_ins[1:] + (dy,)),
            (norm_pull_chain, dy, norm_ins)),
        "conv": (
            alone_fused, delta.short_conv, (q, taps[0]), 2,
            (lambda w, ops: w + 0.0 * alone_fused(ops[0], w)[:w.shape[1]].T,
             taps[0], (q,)),
            (lambda x, ops: delta.short_conv(x, ops[0]), q, taps[:1])),
        "conv_pull": (
            pull(alone_fused, 2), pull(delta.short_conv, 2),
            (q, taps[0], dy), 2.5,
            (lambda w, ops: w + 0.0 * pull(alone_fused, 2)(ops[0], w,
                                                           ops[1])[1],
             taps[0], (q, dy)),
            (conv_pull_chain, dy, (q, taps[0])))}
    wide, out = 4 * t * heads * d, {}
    for name, (fused, chain, args, arrays, own, chains) in forms.items():
        got, want = jax.jit(fused)(*args), jax.jit(chain)(*args)
        ms = _ms_looped(*own)
        out[name] = {
            "ms": ms, "chain_ms": _ms_looped(*chains),
            "bytes": int(arrays * wide),
            "bound_share": arrays * wide / peak / (1e-3 * ms),
            "relative": [_relative(a, b) for a, b in zip(
                jax.tree_util.tree_leaves(got),
                jax.tree_util.tree_leaves(want))]}
    return out


def _delta(cfg, described, t, rng):
    """A delta layer's line: whether its gates and gated norm are
    ``delta_passes.py``'s, and the passes alone."""
    from multiverso_tpu.models.lm import delta
    line = {"config": described.get("name"), "kind": "kda", "tokens": t,
            "heads": cfg.kda_heads_held, "beta_scale": cfg.kda_beta_scale,
            "fused": delta.passes_fused(cfg, t)}
    if line["fused"]:
        with open(os.path.join(ROOT, "benchmark", "peaks.json")) as f:
            peak = json.load(f)[jax.devices()[0].device_kind]
        line["pass_ms"] = _delta_passes_alone(cfg, t, rng,
                                              peak["hbm_bytes_per_s"])
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
        if "kda" in cfg.attention_layout:
            _delta(cfg, described, t, rng)
        if cfg.attention == "mla":
            _latent(cfg, described, t, rng)
            continue
        if "kda" in cfg.attention_layout:
            continue    # its other layers are of a kind held by heads: no line
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

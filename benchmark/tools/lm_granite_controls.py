#!/usr/bin/env python3
"""The controls of `granite3b.ps-8k`'s check: the cell run with one piece of
its arithmetic changed in its own process, which has to come out `correct:
false` by the limit named for it (``CAUGHT_BY``).

    python3 benchmark/tools/lm_granite_controls.py \
        decay_of_one|no_softplus|no_d_skip|gate_after_norm|norm_a_head| \
        conv_no_bias|conv_no_silu|taps_of_3|acausal_taps|b_c_a_head| \
        scale_rsqrt_lanes|turned_attention|no_residual_multiplier| \
        no_embedding_multiplier|no_logits_scaling|ssd_where_attention| \
        untied_head|two_adds_tied|bfloat16_state|bfloat16_moments|none \
        [--seed N] [--seconds S] [--rehearse]

A wrong state-space layer: `decay_of_one`: the state never decays (no
`exp(dt A)`); `no_softplus`: the step is `dt + dt_bias` itself, of either
sign; `no_d_skip`: no `D X`; `gate_after_norm`: `RMSNorm(Y) * silu(z)`, the
delta layers' order, where this model gates first; `norm_a_head`: the norm's
mean over a head's 64 lanes where the model says all 4096; `conv_no_bias`,
`conv_no_silu`; `taps_of_3`: the oldest of the four taps dropped;
`acausal_taps`: position t reads t .. t + 3; `b_c_a_head`: every head reads
a C of its own (the shared C turned by the head's number along the state)
where the model has ONE group. A wrong attention layer: `scale_rsqrt_lanes`:
scores times 64^-1/2 = 0.125 where the model says `attention_multiplier`
1/64; `turned_attention`: a rotary turn where the model has no positions. A
wrong scalar: `no_residual_multiplier`, `no_embedding_multiplier`,
`no_logits_scaling` (each 1). A wrong layout: `ssd_where_attention`: layer 5
a state-space layer. A wrong table: `untied_head`, `two_adds_tied`
(tools/lm_lfm2_controls.py's). The next precision below the one the
configuration states: `bfloat16_state` (the state between chunks and the
within-chunk factor L in bfloat16), `bfloat16_moments`
(tools/lm_lower_precision.py's). Which limit catches which, with the
readings, is in the configuration's `limits.what`.

`none` changes nothing: the same seed and window as the others. The program
has no option for any of these: this tool replaces the one function in its
own process and then runs benchmark/run.py's `main` unchanged.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import lm_lower_precision as precision  # noqa: E402

CELL = "granite3b.ps-8k"
# the limit that has to catch each (benchmark/tests/test_lm_granite_cell.py
# holds the rehearsal to the same list)
CAUGHT_BY = {
    "decay_of_one": "gradient.decay", "no_softplus": "gradient.decay",
    "no_d_skip": "gradient.ssd", "gate_after_norm": "gradient.ssd",
    "norm_a_head": "gradient.ssd", "conv_no_bias": "gradient.ssd_small",
    "conv_no_silu": "gradient.ssd", "taps_of_3": "gradient.ssd",
    "acausal_taps": "gradient.ssd", "b_c_a_head": "gradient.decay",
    "scale_rsqrt_lanes": "gradient.attention",
    "turned_attention": "gradient.attention",
    "no_residual_multiplier": "gradient.mlp",
    "no_embedding_multiplier": "gradient.tied",
    "no_logits_scaling": "gradient.tied",
    "ssd_where_attention": "layout.differs",
    "untied_head": "gradient.tied", "two_adds_tied": "adds.extra",
    "bfloat16_state": "scan.carry", "bfloat16_moments": "adam.moments"}


def _ssd():
    from multiverso_tpu.models.lm import ssd
    return ssd


def decay_of_one():
    import jax.numpy as jnp
    _ssd().log_decay = lambda dt, a_log: jnp.zeros_like(dt)


def no_softplus():
    _ssd().step = lambda dt, dt_bias: dt + dt_bias


def no_d_skip():
    import jax.numpy as jnp
    ssd = _ssd()
    exact = ssd.scanned
    ssd.scanned = lambda cfg, dt_bias, a_log, d, xbc, dt: exact(
        cfg, dt_bias, a_log, jnp.zeros_like(d), xbc, dt)


def gate_after_norm():
    import jax
    from multiverso_tpu.models.lm import model as lm
    _ssd().gated_norm = lambda cfg, g, y, z: lm.rmsnorm(
        y, g, cfg.eps) * jax.nn.silu(z)


def norm_a_head():
    import jax
    from multiverso_tpu.models.lm import model as lm

    def by_head(cfg, g, y, z):
        t, inner = y.shape
        gated = (y * jax.nn.silu(z)).reshape(t, cfg.ssd_heads, -1)
        return lm.rmsnorm(gated, 1.0, cfg.eps).reshape(t, inner) * g

    _ssd().gated_norm = by_head


def _conv(changed):
    """``ssd.conv`` replaced by ``changed(xbc, w, b, taps)``."""
    ssd = _ssd()
    ssd.conv = lambda xbc, w, b: changed(xbc, w, b, ssd.taps)


def conv_no_bias():
    import jax
    _conv(lambda xbc, w, b, taps: jax.nn.silu(taps(xbc, w) + 0.0 * b))


def conv_no_silu():
    _conv(lambda xbc, w, b, taps: taps(xbc, w) + b)


def taps_of_3():
    import jax
    _conv(lambda xbc, w, b, taps: jax.nn.silu(
        taps(xbc, w.at[:, 0].set(0.0)) + b))


def acausal_taps():
    import jax
    # position t reads t .. t + 3: the sequence turned round, through the
    # causal taps, and turned back
    _conv(lambda xbc, w, b, taps: jax.nn.silu(
        taps(xbc[::-1], w)[::-1] + b))


def b_c_a_head():
    import jax
    import jax.numpy as jnp
    ssd = _ssd()
    exact = ssd.scan

    def apart(x, dt, a_log, b, c, chunk=0):
        heads = x.shape[1]
        turned = jnp.stack([jnp.roll(c, i, axis=-1) for i in range(heads)])

        def one(x, dt, a_log, c):
            y, deep = exact(x[:, None], dt[:, None], a_log[None], b, c, chunk)
            return y[:, 0], deep

        y, deep = jax.vmap(one, in_axes=(1, 1, 0, 0), out_axes=(1, 0))(
            x, dt, a_log, turned)
        return y, jnp.sum(deep)

    ssd.scan = apart


def _described(**changes):
    """``LMConfig._from_granite``'s result with ``changes`` (a value, or a
    function of the exact configuration)."""
    import dataclasses
    from multiverso_tpu.models.lm import model as lm
    exact = lm.LMConfig._from_granite.__func__

    def wrong(cls, c):
        cfg = exact(cls, c)
        return dataclasses.replace(cfg, **{
            k: v(cfg) if callable(v) else v for k, v in changes.items()})

    lm.LMConfig._from_granite = classmethod(wrong)


def scale_rsqrt_lanes():
    _described(attn_scale=0.0)      # the default: head_dim^-0.5


def turned_attention():
    _described(rope_layout=lambda cfg: tuple(
        int(k == "gqa") for k in cfg.attention_layout))


def no_residual_multiplier():
    _described(residual_scale=1.0)


def no_embedding_multiplier():
    _described(embed_scale=1.0)


def no_logits_scaling():
    _described(logits_scale=1.0)


def ssd_where_attention():
    _described(attention_layout=lambda cfg: ("ssd",) * cfg.n_layers)


def untied_head():
    _described(tied=False)


def two_adds_tied():
    from multiverso_tpu.models.lm import PSLMTrainer

    def apart(self, d_head, ids, d_rows):
        self._push(self.embedding, d_head)
        self._push(self.embedding, d_rows, ids)

    PSLMTrainer._push_embedding = apart


def bfloat16_state():
    import jax.numpy as jnp
    ssd = _ssd()
    ssd.CARRY = ssd.DECAY = jnp.bfloat16


CHANGES = {name: globals()[name] for name in CAUGHT_BY
           if name != "bfloat16_moments"}
CHANGES.update(bfloat16_moments=precision.bfloat16_moments,
               none=lambda: None)


def main() -> int:
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

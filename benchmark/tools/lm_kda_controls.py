#!/usr/bin/env python3
"""The controls of `kimi48b.ps-8k`'s check: the cell run with one piece of
its arithmetic changed in its own process, which has to come out `correct:
false` by the limit named for it (``CAUGHT_BY``).

    python3 benchmark/tools/lm_kda_controls.py \
        no_decay|head_decay|no_beta|no_qk_norm|conv_next|no_conv| \
        bfloat16_state|no_output_gate|rotary_latent|float8_experts| \
        bfloat16_moments|none [--seed N] [--seconds S] [--rehearse]

A wrong delta rule: `no_decay`: the state is never decayed (`g = 0`);
`head_decay`: one decay a head, the channels' mean, where the model has a
decay a channel; `no_beta`: every position writes with `beta = 1`;
`no_qk_norm`: q and k go into the scan as the convolutions left them (the
scale on q kept). A wrong convolution: `conv_next`: a position reads the
NEXT position where it should read its own (`t - 2 .. t + 1`); `no_conv`:
q, k and v are the silu of the projections alone. `no_output_gate`: the
gated norm without its gate. A wrong latent layer: `rotary_latent`: `q_r`
and `k_r` turned by position where the model uses none. The next precision
below the one the configuration states: `bfloat16_state`: the state goes
from chunk to chunk in bfloat16 where it says float32; `float8_experts`,
`bfloat16_moments`: tools/lm_lower_precision.py's. Which limit catches
which, with the readings, is in the configuration's `limits.what` and
PERF.md section 4.

`none` changes nothing: the same seed and window as the others, for the
readings they are set beside. The program has no option for any of these:
this tool replaces the one function in its own process and then runs
benchmark/run.py's `main` unchanged.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import lm_lower_precision as precision  # noqa: E402

CELL = "kimi48b.ps-8k"
# the limit that has to catch each (benchmark/tests/test_lm_kda_cell.py
# holds the rehearsal to the same list)
CAUGHT_BY = {"no_decay": "gradient.scan", "head_decay": "gradient.scan",
             "no_beta": "gradient.scan", "no_qk_norm": "gradient.scan",
             "conv_next": "gradient.scan", "no_conv": "gradient.scan",
             "bfloat16_state": "scan.carry",
             "no_output_gate": "gradient.table",
             "rotary_latent": "gradient.scores",
             "float8_experts": "gradient.router",
             "bfloat16_moments": "adam.moments"}


def _gates(change):
    """``delta.gates`` with its results ``(q, k, v, g, beta)`` through
    ``change``."""
    from multiverso_tpu.models.lm import delta
    exact = delta.gates
    delta.gates = lambda *args: change(*exact(*args))


def no_decay():
    import jax.numpy as jnp
    _gates(lambda q, k, v, g, beta: (q, k, v, jnp.zeros_like(g), beta))


def head_decay():
    import jax.numpy as jnp
    _gates(lambda q, k, v, g, beta: (
        q, k, v, jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape),
        beta))


def no_beta():
    import jax.numpy as jnp
    _gates(lambda q, k, v, g, beta: (q, k, v, g, jnp.ones_like(beta)))


def no_qk_norm():
    from multiverso_tpu.models.lm import delta
    exact = delta.gates

    def unnormed(cfg, a_log, dt_bias, q, k, v, f, b):
        _, _, v_, g, beta = exact(cfg, a_log, dt_bias, q, k, v, f, b)
        t, heads, d = v_.shape
        return (q.reshape(t, heads, d) * d ** -0.5, k.reshape(t, heads, d),
                v_, g, beta)

    delta.gates = unnormed


def conv_next():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import delta
    exact = delta.short_conv
    delta.short_conv = lambda x, w: exact(
        jnp.concatenate([x[1:], jnp.zeros_like(x[:1])]), w)


def no_conv():
    import jax
    from multiverso_tpu.models.lm import delta
    delta.short_conv = lambda x, w: jax.nn.silu(x + 0.0 * w[:, 0])


def bfloat16_state():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import delta
    delta.CARRY = jnp.bfloat16


def no_output_gate():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import delta
    exact = delta.output
    # sigmoid(inf) = 1, and no gradient reaches the gate's products
    delta.output = lambda cfg, mats, sinks, norm_o, o, gate: exact(
        cfg, mats, sinks, norm_o, o, jnp.full_like(gate, jnp.inf))


def rotary_latent():
    from multiverso_tpu.models.lm import latent, model as lm
    exact = latent.inputs

    def turned(cfg, mats, sinks, norms, u, pos=None, rope=True):
        import jax.numpy as jnp
        q, k, v = exact(cfg, mats, sinks, norms, u, pos, rope)
        nope = cfg.qk_nope_dim

        def turn(a):    # [heads, .., T, nope + rope] -> rope lanes turned
            flat = a.reshape(-1, *a.shape[-2:]).transpose(1, 0, 2)
            r = lm._rotary(flat[..., nope:].astype(jnp.float32),
                           cfg.rope_theta)
            out = jnp.concatenate([flat[..., :nope], r.astype(a.dtype)], -1)
            return out.transpose(1, 0, 2).reshape(a.shape)

        return turn(q), turn(k), v

    latent.inputs = turned


CHANGES = {"no_decay": no_decay, "head_decay": head_decay,
           "no_beta": no_beta, "no_qk_norm": no_qk_norm,
           "conv_next": conv_next, "no_conv": no_conv,
           "bfloat16_state": bfloat16_state,
           "no_output_gate": no_output_gate, "rotary_latent": rotary_latent,
           "float8_experts": precision.float8_experts,
           "bfloat16_moments": precision.bfloat16_moments,
           "none": lambda: None}


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

#!/usr/bin/env python3
"""The controls of `xing29b.ps-4k`'s check: the cell run with one piece of
its arithmetic changed in its own process, which has to come out `correct:
false` by the limit named for it.

    python3 benchmark/tools/lm_mla_controls.py \
        float8_experts|bfloat16_moments|no_rotary_key|scale_without_m2| \
        one_sinkhorn_round|choice_without_bias|two_head_adds|none \
        [--seed N] [--seconds S] [--rehearse]

The next precision below the one the configuration states:
`float8_experts`: the routed experts' grouped products take their inputs
rounded to float8 (e4m3) where it says bfloat16; `bfloat16_moments`: Adam
keeps both moments rounded to bfloat16 where it says float32 (both are
tools/lm_lower_precision.py's). A wrong model: `no_rotary_key`: the
score leaves out `q_r . k_r`, the rotary part that all heads share;
`scale_without_m2`: the softmax scale is `192^-0.5` without YaRN's `m^2`;
`one_sinkhorn_round`: one round of row and column normalisation where the
config says twenty; `choice_without_bias`: the four experts are the
largest scores, the bias the server keeps left out of the choice. A wrong
step: `two_head_adds`: the head's gradient reaches the server as two Adds
(with the multi-token module held, the main pass's and the module's
apart; without it, as the cell runs, two halves), two steps of Adam's
moments where the configuration says one. Which limit catches which, with
the readings, is in the configuration's `limits.what` and PERF.md
section 4.

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

CELL = "xing29b.ps-4k"
# the limit that has to catch each (benchmark/tests/test_lm_mla_cell.py
# holds the rehearsal's twin to the same list)
CAUGHT_BY = {"float8_experts": "gradient.router",
             "bfloat16_moments": "adam.moments",
             "no_rotary_key": "gradient.scores",
             "scale_without_m2": "gradient.scores",
             "one_sinkhorn_round": "gradient.mixer",
             "choice_without_bias": "routing.differs",
             "two_head_adds": "adds.extra"}


def no_rotary_key():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import latent
    exact = latent.inputs

    def without(cfg, *args, **kw):
        q, k, v = exact(cfg, *args, **kw)
        return q, jnp.concatenate(
            [k[..., :cfg.qk_nope_dim],
             jnp.zeros_like(k[..., cfg.qk_nope_dim:])], -1), v

    latent.inputs = without


def scale_without_m2():
    from multiverso_tpu.models.lm import latent
    latent.softmax_scale = lambda cfg: cfg.head_dim ** -0.5


def one_sinkhorn_round():
    from multiverso_tpu.models.lm import streams
    exact = streams.sinkhorn
    streams.sinkhorn = lambda logits, iters, eps: exact(logits, 1, eps)


def choice_without_bias():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import model as lm
    exact = lm.route
    lm.route = lambda cfg, router, x, bias=None: exact(
        cfg, router, x, None if bias is None else jnp.zeros_like(bias))


def two_head_adds():
    """With the module held: its head gradient and the main pass's apart.
    Without it (the cell's size: the module on a further rank) the head
    has one gradient a step, which goes in two halves: two Adds, two steps
    of the moments, all the same."""
    import jax.numpy as jnp
    from multiverso_tpu.models.lm.ps_train import PSLMTrainer
    module_step, push = PSLMTrainer._module_step, PSLMTrainer._push

    def apart(self, *args):
        loss, dxs, d_head, de_next = module_step(self, *args)
        push(self, self.head, d_head)
        return loss, dxs, jnp.zeros_like(d_head), de_next

    def halves(self, table, delta, ids=None):
        if table is self.head and not self.module:
            push(self, table, 0.5 * delta)
            delta = 0.5 * delta
        push(self, table, delta, ids)

    PSLMTrainer._module_step, PSLMTrainer._push = apart, halves


CHANGES = {"float8_experts": precision.float8_experts,
           "bfloat16_moments": precision.bfloat16_moments,
           "no_rotary_key": no_rotary_key,
           "scale_without_m2": scale_without_m2,
           "one_sinkhorn_round": one_sinkhorn_round,
           "choice_without_bias": choice_without_bias,
           "two_head_adds": two_head_adds, "none": lambda: None}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("what", choices=tuple(CHANGES))
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--seconds", type=float, default=3.0)
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

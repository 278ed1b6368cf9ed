#!/usr/bin/env python3
"""The controls of `sdar30b.ps-bd4k`'s check: the cell run with one piece
of its arithmetic changed in its own process, which has to come out
`correct: false` by at least one limit.

    python3 benchmark/tools/lm_bd_controls.py \
        causal_mask|shifted_positions|unweighted_loss|no_qk_norm| \
        float8_experts|bfloat16_moments|none  [--seed N] [--seconds S] [--rehearse]

A wrong model or objective: `causal_mask`: every layer attends under the
causal mask over the 2L positions where the configuration says the block
mask (the clean copy then sees the noised one, and a noised block the
noised blocks before it); `shifted_positions`: the noised copy takes
rotary positions L .. 2L-1 where a token's two copies share one;
`unweighted_loss`: the loss is the mean over EVERY noised position, each
weighing 1, where the configuration says the masked ones at 1/t;
`no_qk_norm`: q and k go to the rotary turn unnormed. The next precision
below the one the configuration states: `float8_experts`: the experts'
grouped products take their inputs rounded to float8 (e4m3) where it says
bfloat16; `bfloat16_moments`: Adam keeps both moments rounded to bfloat16
where it says float32. Which limit caught which control, with the
readings, is in the configuration's `limits.what` and PERF.md section 4.

`none` changes nothing: the same seed and window as the others, for the
readings they are set beside. The program has no option for any of these:
this tool replaces the one function in its own process (the two precision
controls are tools/lm_lower_precision.py's) and then runs
benchmark/run.py's `main` unchanged.
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.tools import lm_lower_precision as precision  # noqa: E402

CELL = "sdar30b.ps-bd4k"


def causal_mask():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_core
    lm.attention_core = lambda q, k, v, mask: exact(q, k, v, 0)


def shifted_positions():
    import numpy as np
    from multiverso_tpu.models.lm import model as lm
    lm.Mask.positions = lambda self, t: np.arange(t)


def unweighted_loss():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import model as lm
    exact = lm.head_loss_and_grads

    def every_position(cfg, head, norm, x, targets, weights=None,
                       normaliser=None):
        return exact(cfg, head, norm, x, targets,
                     None if weights is None else jnp.ones_like(weights),
                     normaliser)

    lm.head_loss_and_grads = every_position


def no_qk_norm():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_inputs

    def unnormed(cfg, rope, mats, sinks, norms, x, pos=None):
        import dataclasses
        return exact(dataclasses.replace(cfg, qk_norm=False), rope, mats,
                     sinks, norms[0] * 1.0, x, pos)

    lm.attention_inputs = unnormed


CHANGES = {"causal_mask": causal_mask,
           "shifted_positions": shifted_positions,
           "unweighted_loss": unweighted_loss, "no_qk_norm": no_qk_norm,
           "float8_experts": precision.float8_experts,
           "bfloat16_moments": precision.bfloat16_moments,
           "none": lambda: None}


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

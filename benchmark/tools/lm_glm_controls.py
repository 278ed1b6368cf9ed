#!/usr/bin/env python3
"""The controls of `glm30b.ps-8k`'s check: the cell run with one piece of
its arithmetic changed in its own process, which has to come out `correct:
false` by the limit named for it (``CAUGHT_BY``).

    python3 benchmark/tools/lm_glm_controls.py \
        float8_experts|bfloat16_moments|no_rotary_key| \
        yarn_frequencies_kept|module_targets_next|module_without_enorm| \
        two_head_adds|choice_without_bias|none \
        [--seed N] [--seconds S] [--rehearse]

The next precision below the one the configuration states:
`float8_experts`: the routed experts' grouped products take their inputs
rounded to float8 (e4m3) where it says bfloat16; `bfloat16_moments`: Adam
keeps both moments rounded to bfloat16 where it says float32 (both are
tools/lm_lower_precision.py's). A wrong attention: `no_rotary_key`: the
score leaves out `q_r . k_r`, the rotary part that all heads share;
`yarn_frequencies_kept`: the rotary pairs turn at YaRN's blended frequencies
(`xing4-29b-a4b-l5`'s factor 64 and ramp 32 to 1, over the sequence's own
length as there) where `rope_scaling` is null and `rope_theta`'s own
belong. A wrong module:
`module_targets_next`: the module predicts `t_{i+1}`, the main head's
target, where it has to predict `t_{i+2}`; `module_without_enorm`: the next
token's embedding row enters the projection as it is, without its norm. A
wrong step: `two_head_adds`: the head's two gradients (the main pass's and
the module's) reach the server as two Adds, two steps of Adam's moments
where the configuration says one; `choice_without_bias`: the four experts
are the largest scores, the bias the server keeps left out of the choice.
Which limit catches which, with the readings, is in the configuration's
`limits.what` and PERF.md section 4.

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
from benchmark.tools import lm_mla_controls as mla  # noqa: E402

CELL = "glm30b.ps-8k"
# the limit that has to catch each (benchmark/tests/test_lm_glm_cell.py
# holds the rehearsal to the same list)
CAUGHT_BY = {"float8_experts": "layer.output",
             "bfloat16_moments": "adam.moments",
             "no_rotary_key": "gradient.scores",
             "yarn_frequencies_kept": "gradient.scores",
             "module_targets_next": "gradient.table",
             "module_without_enorm": "gradient.table",
             "two_head_adds": "adds.extra",
             "choice_without_bias": "routing.differs"}
# xing4-29b-a4b-l5's ``rope_scaling``: factor, beta_fast, beta_slow; its
# original positions are its sequence's length, and so are they here
YARN = (64.0, 32.0, 1.0)


def yarn_frequencies_kept():
    from multiverso_tpu.models.lm import model as lm
    exact = lm._rotary

    def turned(x, theta, pos=None, inv=None, **how):
        if inv is None:
            inv = lm.yarn_frequencies(theta, x.shape[-1], *YARN, x.shape[0])
        return exact(x, theta, pos, inv, **how)

    lm._rotary = turned


def module_targets_next():
    import jax
    from multiverso_tpu.models.lm import ps_train

    def split(self, tokens):
        with jax.named_scope("mv.lm.embed"):
            ids, nxt = tokens[:, :-1], tokens[:, 1:-1].reshape(-1)
            return ids, (nxt, nxt), ps_train._distinct(ids)

    ps_train.PSLMTrainer._split_more = split


def module_without_enorm():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import model as lm, mtp

    def project(cfg, proj, sink, norms, xs, e_next):    # the plain residual's
        g_h, g_e = norms
        both = jnp.concatenate([lm.rmsnorm(xs, g_h, cfg.eps),
                                e_next + 0.0 * g_e], axis=-1)
        return lm.mm(both, proj, sink)

    mtp.project = project


CHANGES = {"float8_experts": precision.float8_experts,
           "bfloat16_moments": precision.bfloat16_moments,
           "no_rotary_key": mla.no_rotary_key,
           "yarn_frequencies_kept": yarn_frequencies_kept,
           "module_targets_next": module_targets_next,
           "module_without_enorm": module_without_enorm,
           "two_head_adds": mla.two_head_adds,
           "choice_without_bias": mla.choice_without_bias,
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

#!/usr/bin/env python3
"""The controls of `laguna33b.ps-8k`'s check: the cell run with one piece
of its arithmetic changed in its own process, which has to come out
`correct: false` by the limit named for it.

    python3 benchmark/tools/lm_mixed_controls.py \
        float8_experts|bfloat16_moments|causal_everywhere| \
        sliding_rotary_everywhere|yarn_without_factor|no_gate| \
        weights_without_scale|no_shared_expert|none \
        [--seed N] [--seconds S] [--rehearse]

The next precision below the one the configuration states:
`float8_experts`: the routed experts' grouped products take their inputs
rounded to float8 (e4m3) where it says bfloat16; `bfloat16_moments`: Adam
keeps both moments rounded to bfloat16 where it says float32 (both are
tools/lm_lower_precision.py's). A wrong model: `causal_everywhere`: every
layer under the causal mask, the window of 512 left out;
`sliding_rotary_everywhere`: every layer turned as the 64-head layers are
(plain rotary over all 128 lanes at theta 10000: the full layers' YaRN and
half rotation left out); `yarn_without_factor`: YaRN's frequencies without
the attention factor on cos and sin; `no_gate`: the heads' outputs as they
are, the per-head gate left out; `weights_without_scale`: the eight
weights normalised and not multiplied by 2.5; `no_shared_expert`: the
shared expert's output left out of the sum. Which limit catches which,
with the readings, is in the configuration's `limits.what` and PERF.md
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

CELL = "laguna33b.ps-8k"
# the limit that has to catch each on the chip
CAUGHT_BY = {"float8_experts": "routing.differs.later",
             "bfloat16_moments": "adam.moments",
             "causal_everywhere": "gradient.scores",
             "sliding_rotary_everywhere": "gradient.scores",
             "yarn_without_factor": "gradient.scores",
             "no_gate": "gradient.attn_gate",
             "weights_without_scale": "gradient.router",
             "no_shared_expert": "gradient.table"}
# and in the rehearsal's twin (benchmark/tests/test_lm_mixed_cell.py): its 64
# tokens flip no choice of experts; a fresh tiny model's gradients have no
# floor to speak of, and float8 shows there
IN_REHEARSAL = dict(CAUGHT_BY, float8_experts="gradient.router")


def causal_everywhere():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_core
    lm.attention_core = lambda q, k, v, mask: exact(q, k, v, 0)


def sliding_rotary_everywhere():
    from multiverso_tpu.models.lm import model as lm
    lm.LMConfig.rotary = lambda self, rope, window: self.rotary_kinds[1]


def yarn_without_factor():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.Rotary.how
    lm.Rotary.how = lambda self: {k: v for k, v in exact(self).items()
                                  if k != "factor"}


def no_gate():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_gate
    lm.attention_gate = lambda mats, sinks, h, o: (
        o, exact(mats, sinks, h, o)[1])


def weights_without_scale():
    import dataclasses
    from multiverso_tpu.models.lm import model as lm
    exact = lm.route
    lm.route = lambda cfg, router, x, bias=None: exact(
        dataclasses.replace(cfg, routed_scale=1.0), router, x, bias)


def no_shared_expert():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.gated_mlp

    def without(cfg, mats, sinks, names, h):
        out = exact(cfg, mats, sinks, names, h)
        return 0.0 * out if names == lm.SHARED else out

    lm.gated_mlp = without


CHANGES = {"float8_experts": precision.float8_experts,
           "bfloat16_moments": precision.bfloat16_moments,
           "causal_everywhere": causal_everywhere,
           "sliding_rotary_everywhere": sliding_rotary_everywhere,
           "yarn_without_factor": yarn_without_factor,
           "no_gate": no_gate,
           "weights_without_scale": weights_without_scale,
           "no_shared_expert": no_shared_expert, "none": lambda: None}


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

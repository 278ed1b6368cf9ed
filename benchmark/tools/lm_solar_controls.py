#!/usr/bin/env python3
"""The controls of `solar250b.ps-8k`'s check: the cell run with one piece of
its arithmetic changed in its own process, which has to come out `correct:
false` by the limit named for it (``CAUGHT_BY``).

    python3 benchmark/tools/lm_solar_controls.py \
        beta_under_one|gate_by_head|no_gate|rotary_softmax|wrong_kv_head| \
        no_conv|bfloat16_state|bfloat16_moments|float8_experts|none \
        [--seed N] [--seconds S] [--rehearse]

A wrong delta rule: `beta_under_one`: beta = sigmoid where the model says 2
sigmoid (`kda_allow_neg_eigval` ignored: no state ever flips sign along a
key); `no_conv`: q, k and v are the silu of the projections alone. A wrong
softmax layer: `gate_by_head`: the other reading of `use_gqa_gate`, ONE gate
a head (its first lane's logit) where the reading taken has a gate a lane;
`no_gate`: the heads' outputs ungated; `rotary_softmax`: q and k turned by
position where the model uses none (`use_rope` false); `wrong_kv_head`: the
share's query heads read the OTHER held key-value head (a cut by heads that
hands a group of query heads the wrong key-value head: on a deployment,
heads 0-15 reading key-value heads 2-3's place). The next precision below
the one the configuration states: `bfloat16_state`: the state goes from
chunk to chunk in bfloat16 where it says float32; `float8_experts`,
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

from benchmark.tools import lm_kda_controls as kda  # noqa: E402
from benchmark.tools import lm_lower_precision as precision  # noqa: E402

CELL = "solar250b.ps-8k"
# the limit that has to catch each (benchmark/tests/test_lm_solar_cell.py
# holds the rehearsal to the same list)
CAUGHT_BY = {"beta_under_one": "gradient.scan", "no_conv": "gradient.scan",
             "gate_by_head": "gradient.table", "no_gate": "gradient.table",
             "rotary_softmax": "gradient.scores",
             "wrong_kv_head": "gradient.scores",
             "bfloat16_state": "scan.carry",
             "float8_experts": "gradient.gate",
             "bfloat16_moments": "adam.moments"}


def beta_under_one():
    kda._gates(lambda q, k, v, g, beta: (q, k, v, g, 0.5 * beta))


def gate_by_head():
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import model as lm

    def by_head(mats, sinks, h, o):
        """``model.attention_gate`` with every lane of a head under the
        gate of the head's first lane."""
        groups, per, t, d = o.shape
        logits = lm.mm(h, mats[lm.ATTN_GATE], sinks[lm.ATTN_GATE]).reshape(
            t, groups, per, d)
        gate = jax.nn.sigmoid(logits[..., :1])
        by_lane = jnp.broadcast_to(gate, logits.shape).transpose(1, 2, 0, 3)
        return ((o.astype(jnp.float32) * by_lane).astype(o.dtype),
                d * jnp.sum(gate > 0.5, dtype=jnp.int32))

    lm.attention_gate = by_head


def no_gate():
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import model as lm
    lm.attention_gate = lambda mats, sinks, h, o: (
        o + 0 * jnp.sum(sinks[lm.ATTN_GATE]).astype(o.dtype),
        jnp.zeros((), jnp.int32))


def rotary_softmax():
    from multiverso_tpu.models.lm import model as lm
    lm.LMConfig.rotary = lambda self, rope, window: True


def wrong_kv_head():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_core
    lm.attention_core = lambda q, k, v, mask: exact(q, k[::-1], v[::-1], mask)


CHANGES = {"beta_under_one": beta_under_one, "gate_by_head": gate_by_head,
           "no_gate": no_gate, "rotary_softmax": rotary_softmax,
           "wrong_kv_head": wrong_kv_head, "no_conv": kda.no_conv,
           "bfloat16_state": kda.bfloat16_state,
           "bfloat16_moments": precision.bfloat16_moments,
           "float8_experts": precision.float8_experts,
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

#!/usr/bin/env python3
"""The controls of `lfm8b.ps-8k`'s check: the cell run with one piece of its
arithmetic changed in its own process, which has to come out `correct:
false` by the limit named for it (``CAUGHT_BY``).

    python3 benchmark/tools/lm_lfm2_controls.py \
        no_b_gate|no_c_gate|taps_of_2|acausal_taps|silu_after_taps| \
        conv_where_attention|no_head_norm|untied_head|two_adds_tied| \
        bfloat16_moments|float8_experts|none \
        [--seed N] [--seconds S] [--rehearse]

A wrong convolution layer: `no_b_gate`: the taps read X alone where the
model says B * X; `no_c_gate`: the taps' result goes to W_out ungated;
`taps_of_2`: the oldest of the three taps is dropped (a reach of 2);
`acausal_taps`: position t reads t .. t + 2 where the model says t - 2 .. t
(a convolution padded on the wrong side); `silu_after_taps`: the delta
layers' form of a short convolution, an activation after the taps, where
this model has none. A wrong layout: `conv_where_attention`: layer 2 is a
convolution where `layer_types` says full attention (a pattern read as one
period of convolutions). A wrong attention layer: `no_head_norm`: q and k go
to the rotary turn unnormed. A wrong table: `untied_head`: the head is a
second table drawn apart, with its own Add, and the embedding gets its
rows' gradient alone; `two_adds_tied`: the one table gets its two gradients
in two Adds, the head's whole and the rows' by rows: two Adam steps a
touched row. The next precision below the one the configuration states:
`float8_experts`, `bfloat16_moments`: tools/lm_lower_precision.py's. Which
limit catches which, with the readings, is in the configuration's
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

CELL = "lfm8b.ps-8k"
# the limit that has to catch each (benchmark/tests/test_lm_lfm2_cell.py
# holds the rehearsal to the same list)
CAUGHT_BY = {"no_b_gate": "gradient.conv", "no_c_gate": "gradient.conv",
             "taps_of_2": "gradient.conv", "acausal_taps": "gradient.conv",
             "silu_after_taps": "gradient.conv",
             "conv_where_attention": "layout.differs",
             "no_head_norm": "gradient.scores",
             "untied_head": "gradient.tied", "two_adds_tied": "adds.extra",
             "float8_experts": "layer.output",
             "bfloat16_moments": "adam.moments"}


def _chain(changed):
    """``shortconv.chain`` replaced by ``changed(w, b, c, x, taps)``."""
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import shortconv

    def chain(w, bcx):
        b, c, x = jnp.split(bcx, 3, axis=-1)
        return changed(w, b, c, x, shortconv.taps)

    shortconv.chain = chain


def no_b_gate():
    _chain(lambda w, b, c, x, taps: c * taps(x, w))


def no_c_gate():
    _chain(lambda w, b, c, x, taps: taps(b * x, w))


def taps_of_2():
    _chain(lambda w, b, c, x, taps: c * taps(b * x, w.at[:, 0].set(0.0)))


def acausal_taps():
    # position t reads t .. t + 2: the sequence turned round, through the
    # causal taps, and turned back
    _chain(lambda w, b, c, x, taps: c * taps((b * x)[::-1], w)[::-1])


def silu_after_taps():
    import jax
    _chain(lambda w, b, c, x, taps: c * jax.nn.silu(taps(b * x, w)))


def conv_where_attention():
    import dataclasses
    from multiverso_tpu.models.lm import model as lm
    exact = lm.LMConfig._from_lfm2.__func__

    def wrong(cls, c):
        cfg = exact(cls, c)
        layout, rope = list(cfg.attention_layout), list(cfg.rope_layout)
        layout[2], rope[2] = "conv", 0
        return dataclasses.replace(cfg, attention_layout=tuple(layout),
                                   rope_layout=tuple(rope))

    lm.LMConfig._from_lfm2 = classmethod(wrong)


def no_head_norm():
    import dataclasses
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_inputs

    def unnormed(cfg, rope, mats, sinks, norms, x, pos=None):
        # the head norms' gradients come out zeros: their Adds still go
        return exact(dataclasses.replace(cfg, qk_norm=False), rope, mats,
                     sinks, norms[0], x, pos)

    lm.attention_inputs = unnormed


def untied_head():
    import dataclasses
    from multiverso_tpu.models.lm import model as lm
    exact = lm.LMConfig._from_lfm2.__func__
    lm.LMConfig._from_lfm2 = classmethod(
        lambda cls, c: dataclasses.replace(exact(cls, c), tied=False))


def two_adds_tied():
    from multiverso_tpu.models.lm import PSLMTrainer

    def apart(self, d_head, ids, d_rows):
        self._push(self.embedding, d_head)
        self._push(self.embedding, d_rows, ids)

    PSLMTrainer._push_embedding = apart


CHANGES = {"no_b_gate": no_b_gate, "no_c_gate": no_c_gate,
           "taps_of_2": taps_of_2, "acausal_taps": acausal_taps,
           "silu_after_taps": silu_after_taps,
           "conv_where_attention": conv_where_attention,
           "no_head_norm": no_head_norm, "untied_head": untied_head,
           "two_adds_tied": two_adds_tied,
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

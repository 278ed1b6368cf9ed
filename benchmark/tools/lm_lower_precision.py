#!/usr/bin/env python3
"""The controls of `st21b.ps-8k`'s check: the cell run with one piece of
its arithmetic changed in its own process, which has to come out `correct:
false` by at least one limit, and the witness that has to side with the
reference.

    python3 benchmark/tools/lm_lower_precision.py \
        float8_experts|bfloat16_moments|causal_window_layers|rotary_off| \
        float32_products|none  [--seed N] [--seconds S] [--rehearse]

The next precision below the one the configuration states:
`float8_experts`: the experts' grouped products take their inputs rounded
to float8 (e4m3) where the configuration says bfloat16. `bfloat16_moments`:
Adam keeps both moments rounded to bfloat16 where the configuration says
float32; the Adam limits have to catch it whatever the model computed.
A wrong model: `causal_window_layers`: the rotary layers attend to every
earlier position where the configuration says the last 4096;
`rotary_off`: they take no rotary positions; forward and backward, and a
gradient limit has to catch either. Which limit caught which
control, with the readings, is in the configuration's `limits.what` and
PERF.md section 4.

The witness: `float32_products`: every product of the program takes
float32 inputs at "highest" where the configuration says bfloat16 (the
attention kernel too; the grouped products as XLA's ragged product, the
Pallas kernel's float32 tiles do not fit the chip's vector memory), one
sequence a step so that the float32 copies fit. Every gradient then has to
come out nearer the reference than the bfloat16 program's, the query and
key projections' too: what the check reads there is rounding.

`none` changes nothing: the same seed and window as the others, for the
readings they are set beside. The program has no option for any of these:
this tool replaces the one function in its own process and then runs
benchmark/run.py's `main` unchanged. The rounding is
`jax.lax.reduce_precision`: a cast down and up again is a pair the TPU's
compiler removes (it may keep excess precision), and both precision
controls then read exactly as the unrounded program (my chip runs, PR 32).
"""

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def float8_experts():
    import jax
    import jax.numpy as jnp
    from multiverso_tpu.models.lm import model as lm

    def rounded(x, w, sink, group_sizes):
        x = jax.lax.reduce_precision(x.astype(jnp.bfloat16), 4, 3)  # e4m3
        return lm.grouped_product(x, w, group_sizes)

    lm.grouped_mm.fun = rounded


def bfloat16_moments():
    import jax
    from multiverso_tpu.updater.rules import AdamRule
    exact = AdamRule._step

    def rounded(w, m, v, g, t, hyp):
        w, m, v = exact(w, m, v, g, t, hyp)
        return (w, jax.lax.reduce_precision(m, 8, 7),    # bfloat16's bits
                jax.lax.reduce_precision(v, 8, 7))

    AdamRule._step = staticmethod(rounded)


def causal_window_layers():
    from multiverso_tpu.models.lm import model as lm
    exact = lm.attention_core
    lm.attention_core = lambda q, k, v, window: exact(q, k, v, 0)


def rotary_off():
    from multiverso_tpu.models.lm import model as lm
    lm._rotary = lambda x, theta: x


def float32_products():
    import jax
    import jax.numpy as jnp
    from benchmark import run
    from multiverso_tpu.models.lm import model as lm, ps_train
    lm.BF16 = ps_train.BF16 = jnp.float32
    lm._use_gmm = lambda rows, k, n: False
    jax.config.update("jax_default_matmul_precision", "highest")
    load = run.load_json

    def one_sequence(*parts):
        params = load(*parts)
        if "sequences_per_step" in params:
            params["sequences_per_step"] = 1
        return params

    run.load_json = one_sequence


CHANGES = {"float8_experts": float8_experts,
           "bfloat16_moments": bfloat16_moments,
           "causal_window_layers": causal_window_layers,
           "rotary_off": rotary_off,
           "float32_products": float32_products, "none": lambda: None}


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("what", choices=tuple(CHANGES))
    parser.add_argument("--seed", type=int, default=2147483777)
    parser.add_argument("--seconds", type=float, default=3.0)
    parser.add_argument("--rehearse", action="store_true")
    args = parser.parse_args()
    CHANGES[args.what]()
    from benchmark import run
    print(f"[lower precision] {args.what}", flush=True)
    return run.main(["--workload", "st21b.ps-8k", "--seed", str(args.seed),
                     "--seconds", str(args.seconds), "--trace", "0"]
                    + (["--rehearse"] if args.rehearse else []))


if __name__ == "__main__":
    sys.exit(main())

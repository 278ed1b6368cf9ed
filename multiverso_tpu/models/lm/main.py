"""Language-model CLI: train one chip's share of a sparse-expert decoder
through the parameter server, Adam in the server.

Usage::

    python -m multiverso_tpu.models.lm.main \
        -lm_config=benchmark/configs/smallthinker-21ba3b-l4.json \
        [-lm_steps=10] [-lm_seq_len=8192] [-lm_sequences=2] \
        [-lm_warmup_steps=2000] [-lm_seed=0]

``-lm_config`` is a JSON file in a published ``config.json``'s keys
(``LMConfig.from_dict``: SmallThinker's, or Qwen3-MoE's as
benchmark/configs/sdar-30b-a3b-l6.json has them, whose ``objective``
makes the run block diffusion: ``-lm_seq_len`` clean tokens a sequence,
twice as many positions). Tokens are a Zipf(1.0) stream drawn on the
device (under block diffusion over every id but the mask token's). The
learning rate rises linearly to 3e-4 over ``-lm_warmup_steps`` steps (0: constant from the first step, at which
the share's routers send every token of a layer to the same experts
within some thirty steps, docs/LM_TRAINER.md). The updater is ``adam``
unless ``-updater_type`` says otherwise on the command line, and the
trainer refuses any other.
"""

from __future__ import annotations

import json
import sys
import time

import jax

from ... import init as mv_init, shutdown as mv_shutdown
from ...util import compile_cache, log
from ...util.configure import (define_int, define_string, get_flag,
                               parse_cmd_flags)
from .model import LMConfig
from .ps_train import PSLMTrainer, zipf_tokens

define_string("lm_config", "", "model configuration (JSON, published keys)")
define_int("lm_steps", 10, "training steps")
define_int("lm_seq_len", 8192, "tokens a sequence")
define_int("lm_sequences", 2, "sequences a step")
define_int("lm_seed", 0, "seed of the weights and of the drawn tokens")
define_int("lm_warmup_steps", 2000,
           "steps over which the learning rate rises linearly to its value")


def run(argv=None) -> PSLMTrainer:
    argv = list(argv) if argv is not None else sys.argv[1:]
    if not any(a.startswith("-updater_type") for a in argv):
        argv.append("-updater_type=adam")
    parse_cmd_flags(argv)
    if not get_flag("lm_config"):
        raise SystemExit("need -lm_config=<file>")
    with open(get_flag("lm_config")) as f:
        cfg = LMConfig.from_dict(json.load(f))
    mv_init([])     # logs the backend
    seq_len, batch = get_flag("lm_seq_len"), get_flag("lm_sequences")
    trainer = PSLMTrainer(cfg, seq_len, batch, seed=get_flag("lm_seed"),
                          warmup_steps=get_flag("lm_warmup_steps"))
    log.info("lm: %d parameters in %d tables, %d x %d tokens a step",
             cfg.parameters(), len(trainer.tables()), batch, seq_len)
    key = jax.random.PRNGKey(get_flag("lm_seed"))
    start = time.perf_counter()
    for step in range(get_flag("lm_steps")):
        tokens = zipf_tokens(
            jax.random.fold_in(key, step),
            (batch, seq_len + (not trainer.diffusion) + cfg.mtp_layers),
            cfg.vocab - trainer.diffusion)
        loss = float(trainer.step(tokens))
        log.info("step %d: loss %.4f, %.0f tokens/s", step, loss,
                 (step + 1) * batch * seq_len
                 / (time.perf_counter() - start))
    trainer.close()
    mv_shutdown()
    return trainer


if __name__ == "__main__":
    compile_cache.enable()
    run()

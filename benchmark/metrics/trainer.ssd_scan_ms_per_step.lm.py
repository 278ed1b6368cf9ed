"""Device milliseconds a step in the state-space layers' scan (softplus,
the decay, the chunks' own parts, the state from chunk to chunk, the skip
`D X`), forward, made again and backward: scope `mv.lm.attn.ssd.scan`,
whatever runs under it (the Pallas kernels of `models/lm/ssd_kernels.py`
and what XLA keeps around them, or the `jax.numpy` runs of chunks). Busiest
chip, traced window. None where the program has no such scope."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.attn.ssd.scan",))

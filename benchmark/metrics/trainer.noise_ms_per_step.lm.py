"""Device milliseconds a step in block diffusion's noise (each block's
t, the masked positions, the noised copy, the loss's weights): scope
`mv.lm.noise` of the trainer's batch-preparation program, busiest chip,
traced window."""

from benchmark.lib import lmshapes


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, ("mv.lm.noise",))

"""Device milliseconds a step in the state-space layers' mixers, all of
them: the four scopes `mv.lm.attn.ssd` (the sublayer's norm, `W_in`,
`W_out`), `.conv`, `.scan` and `.gate` (`models/lm/ssd.py`), forward and
backward, every state-space layer. Busiest chip, traced window. The parts
have readers of their own (`trainer.ssd_scan_ms_per_step.lm`,
`trainer.ssd_conv_ms_per_step.lm`, `trainer.ssd_gate_ms_per_step.lm`); what
is left is the two products'. None where the program has no such scope."""

from benchmark.lib import lmshapes

SCOPES = ("mv.lm.attn.ssd", "mv.lm.attn.ssd.conv", "mv.lm.attn.ssd.scan",
          "mv.lm.attn.ssd.gate")


def read(obs):
    return lmshapes.scopes_ms_per_step(obs, SCOPES)

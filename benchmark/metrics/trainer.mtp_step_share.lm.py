"""Percent of the traced window's device time, every program of the busiest
chip summed, that the multi-token module's three programs took
(`jit_mtp_forward`, `jit_mtp_head`, `jit_mtp_backward`:
`trainer.mtp_ms_per_step.lm`'s). The module is one block of six here and
one of 48 in the model: this is its share of THIS rank's step. None where
no such program ran."""

STEMS = ("jit_mtp_forward", "jit_mtp_head", "jit_mtp_backward")


def read(obs):
    if obs.trace is None:
        return None
    programs = obs.trace.get("programs", {})
    every = sum(p["seconds"] for p in programs.values())
    found = [programs[stem]["seconds"] for stem in STEMS if stem in programs]
    return 100.0 * sum(found) / every if found and every else None

"""Milliseconds a step the trainer's own thread spent inside
`PSLMTrainer._module_step` (Dashboard LM_MTP_STEP over the window's steps;
measured window, profiler off): the module's 21 Gets, its three programs'
dispatches and its Adds' issue, on the host's clock. What of a step's
host time the module is; in a trace the same span (`mv:LM_MTP_STEP`) names
the idle gaps that fall inside it (`idle_by_span`)."""

from benchmark.lib import callerspans


def read(obs):
    return callerspans.ms_per_round(obs, ("LM_MTP_STEP",))

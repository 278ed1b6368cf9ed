"""Percent of the layers' attention heads that this chip holds: counter
`LM_HEADS_HELD` over `LM_HEADS` (a layer a sequence, by the kind of the
layer's attention; counted on the host from the configuration), measured
window. 25 where both kinds of attention are cut by heads 4 ways; None
where the program has no such counter."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_HEADS_HELD", "LM_HEADS"))
    if counts is None:
        return None
    return 100.0 * counts[0] / counts[1]

"""Percent of the delta layers' (position, head) pairs whose beta is over 1:
counter `LM_KDA_BETA_OVER_ONE` over `LM_KDA_BETA` (both from the device,
every delta layer over its held heads, read a step late), measured window.
Over 1 the step's matrix `(I - beta k k^T) Diag(exp g)` has a NEGATIVE
eigenvalue along the key: the regime `kda_allow_neg_eigval` buys. Near 50 at
fresh weights (beta = 2 sigmoid of a logit near 0); 0 is a count too, None
where the program counts neither (a beta that cannot pass 1)."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_KDA_BETA",))
    if counts is None:
        return None
    over = obs.window.counters.get("LM_KDA_BETA_OVER_ONE", {}).get("count", 0)
    return 100.0 * over / counts[0]

"""Percent of the lanes the attention kernel is handed a head that the head
holds: counter `LM_ATTN_LANES` over `LM_ATTN_LANES_TILED` (a layer of
attention a sequence, `PSLMTrainer._count_stats`), measured window. 100
where a head of 64 lanes goes to the kernel as it is, 50 where it is padded
to a tile of 128; None where the program has no such counter."""

from benchmark.lib import lmshapes


def read(obs):
    counts = lmshapes.window_counts(obs.window, ("LM_ATTN_LANES",
                                                 "LM_ATTN_LANES_TILED"))
    if counts is None:
        return None
    return 100.0 * counts[0] / counts[1]

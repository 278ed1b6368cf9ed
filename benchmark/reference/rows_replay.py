"""Plain reference for row traffic on a dense float32 table with the
default updater (plain add), one worker, async, exact: a numpy replay of
the acknowledged Adds on the sampled rows.

Guarantee held: an acknowledged Add is in every later Get. So the k-th
Get's reply equals the table after exactly the Adds acknowledged before
it, and the final table equals the table after all of them.

TOLERANCE = 0. The deltas are whole multiples of a power of two no
larger than 4, and a row receives at most a few thousand of them in a
run, so every partial sum is a multiple of that step far below 2**24
steps: float32 addition of them is exact in any order. A table kept in
bfloat16 (8 bits of mantissa) drops such a sum's low bits after a few
Adds and fails; so does a lossy codec, a lost Add, a stale Get.
"""

import numpy as np

TOLERANCE = 0.0


def replay(traffic, log, final_rows: np.ndarray, cols: int) -> list:
    """``log`` is the run's requests in order: ``("get", request, kept)``
    with the reply's sampled positions (None for a Get past the number the
    mix says to keep), or ``("add", request, None)``.
    ``final_rows`` is a Get of ``traffic.sample`` after the last Add.
    Returns a list of what disagreed (empty: correct)."""
    shadow = np.zeros((traffic.sample.size, cols), np.float32)
    wrong = []
    for n, (op, request, kept) in enumerate(log):
        where = traffic.sample_index[request]
        if op == "add":
            shadow[where] += traffic.delta(request)[
                traffic.positions[request]]
        elif kept is not None and not np.array_equal(kept, shadow[where]):
            bad = int((kept != shadow[where]).any(axis=1).sum())
            wrong.append(f"request {n} (get): {bad} of {where.size} "
                         "sampled rows differ from the replay")
    if not np.array_equal(final_rows, shadow):
        bad = int((final_rows != shadow).any(axis=1).sum())
        wrong.append(f"final table: {bad} of {shadow.shape[0]} sampled "
                     "rows differ from the replay")
    return wrong

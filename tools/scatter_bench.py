#!/usr/bin/env python3
"""Nanoseconds a row of the table's row scatter-add, three ways, on the
chip this runs on (it refuses the CPU):

    chiprun -- python3 tools/scatter_bench.py [--rows 8000008] [--small]

(a) ``xla``: ``table.at[ids].add(delta, mode="drop")``, what
    ``updater/rules.py`` does off the TPU and under the crossover.
(b) ``xla+runs``: the ids sorted, the runs' deltas summed by a segmented
    scan, then XLA's scatter told what it may assume:
    ``unique_indices`` (dead positions made distinct and out of range),
    and, after a second sort that brings the runs' ends to the front,
    ``indices_are_sorted`` as well. ``xla+hints`` is (a) with both hints
    on ids that are sorted and distinct as they come.
(c) ``kernel``: ``updater/row_scatter.py`` (sort, then the Pallas
    read-modify-write of the runs).

Cases: 32,768 and 53,258 Zipf(1.0) ids (the input and output tables'
ids of one SGNS block of ``sgns8m.ps``; the share that is distinct is
printed) and 131,072 sorted distinct ids (``mperf16m.rows``' bucket),
128 float32 a row. ``--small`` adds the sweep over small id counts that
gives ``rules.FAST_MIN_IDS``, the crossover. ``--hs`` adds the output
ids of one hierarchical-softmax step of the local trainer: 32,778 band
words by the 24 nodes of their paths down a binary tree, 786,672 ids
of which about an eighth are distinct. Every way's table is compared
with (a)'s. One JSON line a reading; the table of PERF.md section 6
(PR 28) is this output.

``--scan`` times (a) and (c) on the two Zipf cases as the local
trainer's group program has them: ``SCAN_STEPS`` steps of a
``lax.scan`` whose carry is the table, each step gathering the rows it
is about to add into (a trainer's step reads them for its gradients),
the table donated to the whole. ``ms`` is a step; ``in scan: the
gather alone`` is the step without its scatter-add, so the difference
stands beside the standalone reading and a copy of the table in the
loop (5 ms at 4.1 GB) would show.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax import lax  # noqa: E402

from multiverso_tpu.updater import row_scatter  # noqa: E402

COLS = 128
REPEATS = 20


def xla(table, ids, delta):
    return table.at[ids].add(delta, mode="drop")


def xla_hints(table, ids, delta):
    return table.at[ids].add(delta, mode="drop", indices_are_sorted=True,
                             unique_indices=True)


def _run_sums(ids, delta, num_rows):
    """Sorted ids, and at each run's last position the run's sum."""
    k = ids.shape[0]
    key = jnp.where((ids >= 0) & (ids < num_rows), ids,
                    jnp.iinfo(jnp.int32).max)
    key, perm = lax.sort((key, lax.iota(jnp.int32, k)), num_keys=2,
                         is_stable=False)
    head = jnp.concatenate([jnp.ones((1,), bool), key[1:] != key[:-1]])
    end = jnp.concatenate([key[1:] != key[:-1], jnp.ones((1,), bool)])

    def seg_add(a, b):
        (fa, va), (fb, vb) = a, b
        return fa | fb, jnp.where(fb[:, None], vb, va + vb)

    _, sums = lax.associative_scan(seg_add, (head, delta[perm]))
    # Every other position: out of range, and distinct.
    rows = jnp.where(end & (key < num_rows), key,
                     num_rows + lax.iota(jnp.int32, k))
    return rows, sums


def xla_runs_unique(table, ids, delta):
    rows, sums = _run_sums(ids, delta, table.shape[0])
    return table.at[rows].add(sums, mode="drop", unique_indices=True)


def xla_runs_sorted_unique(table, ids, delta):
    rows, sums = _run_sums(ids, delta, table.shape[0])
    rows, at = lax.sort((rows, lax.iota(jnp.int32, rows.shape[0])),
                        num_keys=1, is_stable=False)
    return table.at[rows].add(sums[at], mode="drop", unique_indices=True,
                              indices_are_sorted=True)


def kernel(table, ids, delta):
    return row_scatter.scatter_add(table, ids, delta)


def dedup_only(table, ids, delta):
    """(c)'s sort, run marks and listed ends alone: what the kernel
    starts from."""
    runs = row_scatter.sorted_runs(ids, 0, table.shape[0])
    return table.at[0, 0].add(sum(part.reshape(-1)[0] for part in runs)
                              .astype(table.dtype) * 0)


SCAN_STEPS = 8


def in_scan(fn):
    """``fn`` as the last operation of a scan step that carries the
    table and has read the rows first."""

    def group(table, ids, delta):
        def body(table, _):
            step = delta + 1e-6 * table[ids]
            return fn(table, ids, step), None

        return lax.scan(body, table, None, length=SCAN_STEPS)[0]

    return group


def no_add(table, ids, step):
    """A step's reads and arithmetic with no scatter-add behind them."""
    return table.at[0].add(0.0 * step.sum(axis=0))


#: One HS step of the local trainer: the band's words, the nodes a path.
HS_WORDS, HS_PATH = 32768 + 10, 24


def hs_ids(rng, num_rows):
    """The inner nodes on the paths of ``HS_WORDS`` Zipf(1.0) words
    down a binary tree over the rows, root first, the last node of
    every path a padding that names row 0 (the trainer's
    ``max(point, 0)`` of a -1)."""
    depth = HS_PATH - 1
    rank = zipf_ranks(rng, HS_WORDS, num_rows)
    level = np.arange(depth)
    nodes = (1 << level) - 1 + (rank[:, None] >> (depth - level))
    nodes = np.concatenate([nodes % num_rows,
                            np.zeros((HS_WORDS, 1), np.int64)], axis=1)
    return nodes.reshape(-1).astype(np.int32)


def zipf_ranks(rng, k, num_rows):
    """k draws of Zipf(1.0) ranks over the rows."""
    return np.exp(rng.random(k) * np.log(num_rows)).astype(np.int64) - 1


def zipf_ids(rng, k, num_rows):
    """k Zipf(1.0) ranks, scattered over the table by a fixed odd
    multiplier."""
    return ((zipf_ranks(rng, k, num_rows) * 2654435761)
            % num_rows).astype(np.int32)


@jax.jit
def compare(a, b):
    return jnp.max(jnp.abs(a - b)), jnp.all(a == b)


def timed(fn, table, ids, delta):
    """Seconds a call, the table donated from call to call."""
    step = jax.jit(fn, donate_argnums=0)
    table = jax.block_until_ready(step(table, ids, delta))
    table = jax.block_until_ready(step(table, ids, delta))
    start = time.perf_counter()
    for _ in range(REPEATS):
        table = step(table, ids, delta)
    jax.block_until_ready(table)
    return (time.perf_counter() - start) / REPEATS, table


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--rows", type=int, default=8_000_008)
    parser.add_argument("--small", action="store_true")
    parser.add_argument("--hs", action="store_true")
    parser.add_argument("--scan", action="store_true")
    parser.add_argument("--seed", type=int, default=28)
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit(f"scatter_bench times the chip; this is {device.platform}")
    print(json.dumps({"device_kind": device.device_kind,
                      "rows": args.rows, "cols": COLS,
                      "tile": row_scatter.TILE}), flush=True)
    rng = np.random.default_rng(args.seed)
    cases = [("zipf", 32768), ("zipf", 53258), ("sorted_distinct", 131072)]
    if args.small:
        cases += [("zipf", k) for k in (256, 1024, 2048, 4096, 8192)]
    if args.hs:
        cases.append(("hs_paths", HS_WORDS * HS_PATH))
    if args.scan:
        cases += [("zipf_in_scan", 32768), ("zipf_in_scan", 53258)]
    fresh = jax.jit(lambda: jnp.zeros((args.rows, COLS), jnp.float32))
    for kind, k in cases:
        if kind.startswith("zipf"):
            host_ids = zipf_ids(rng, k, args.rows)
        elif kind == "hs_paths":
            host_ids = hs_ids(rng, args.rows)
        else:
            host_ids = np.sort(rng.choice(args.rows, k, replace=False)
                               ).astype(np.int32)
        ids = jnp.asarray(host_ids)
        delta = jnp.asarray(rng.normal(size=(k, COLS)).astype(np.float32))
        ways = [("xla", xla), ("xla+runs,unique", xla_runs_unique),
                ("xla+runs,sorted,unique", xla_runs_sorted_unique),
                ("kernel", kernel), ("kernel's sort alone", dedup_only)]
        if kind == "sorted_distinct":
            ways.insert(1, ("xla+hints", xla_hints))
        if k < 32768 or kind == "hs_paths":
            ways = [ways[0], ways[-2]]
        if kind == "zipf_in_scan":
            ways = [("in scan: xla", in_scan(xla)),
                    ("in scan: kernel", in_scan(kernel)),
                    ("in scan: the gather alone", in_scan(no_add))]
        want = None
        for name, fn in ways:
            seconds, table = timed(fn, fresh(), ids, delta)
            if kind == "zipf_in_scan":
                seconds /= SCAN_STEPS
            line = {"case": kind, "k": k,
                    "distinct_share": len(np.unique(host_ids)) / k,
                    "way": name, "ms": seconds * 1e3,
                    "ns_per_row": seconds / k * 1e9}
            if want is None:
                want = table
            elif "alone" not in name:
                # REPEATS + 2 applications of the same Add on zeros.
                diff, equal = compare(table, want)
                line["max_abs_diff"], line["equal"] = float(diff), bool(equal)
            print(json.dumps(line), flush=True)
            if table is not want:
                del table
        del want


if __name__ == "__main__":
    main()

"""Run by tests/test_sgns_sharded_reference.py in a process of its own,
on as many CPU devices as XLA_FLAGS gives it:

    python tests/sgns_block_on_devices.py <out.npz>

Builds the benchmark's `sgns-21m-d128-x4` configuration at its
`rehearsal` sizes through the normal path (the benchmark's SGNS driver:
Dictionary, mv.init, PSWord2Vec, PSDeviceCorpusTrainer, one in-process
server whose tables `local_mesh()` lays over every device), gives the output table seeded
rows at a lightly trained scale by one host-id Add (on an all-zero
output table every logit is exactly 0, where the step's autodiff of
`_sigmoid_xent` takes |x|'s slope as 1: the cell's check comes after
its warm blocks and never sees that), then runs one
block from the seeded tables through the trainer's own programs and the
comparison that decides `correct` in the cell
(benchmark/reference/sgns_block.py), at "highest". Writes what the
block read and wrote, and what the comparison said."""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from benchmark.drivers import sgns  # noqa: E402
from benchmark.lib import harness  # noqa: E402
from benchmark.lib.builds import ProgramBuilds  # noqa: E402
from benchmark.reference import sgns_block  # noqa: E402
from benchmark.run import load_json, with_rehearsal  # noqa: E402

SEED = 2 ** 31 + 27


def main(out_path: str) -> int:
    config = with_rehearsal(load_json(
        ROOT, "benchmark", "configs", "sgns-21m-d128-x4.json"), True)
    traffic = load_json(ROOT, "benchmark", "traffic", "sgns-ps-block.json")
    driver = sgns.Driver(harness.Context(
        config, traffic, SEED, ProgramBuilds(), 600))
    driver.build()
    # devices each server table's storage lies on: input, output
    shards = [len({s.device for s in t._data.addressable_shards})
              for t in driver.model._in_table.zoo.server_tables
              if getattr(t, "num_col", 0) == config["embedding_size"]]

    rows = config["vocabulary_rows"]
    driver.model._out_table.add_rows(
        np.arange(rows, dtype=np.int32),
        (np.random.default_rng(SEED).standard_normal(
            (rows, config["embedding_size"])) * 4e-3).astype(np.float32))

    seen = {}
    compare = sgns_block.compare

    def keeping(*args):
        seen.update(zip(("v", "u", "v_after", "u_after", "in_ids",
                         "out_ids", "pmask", "lr", "loss"), args))
        return compare(*args)

    sgns_block.compare = keeping
    with jax.default_matmul_precision("highest"):
        wrong = sgns_block.check(driver)
    driver.close()
    np.savez(out_path, wrong=json.dumps(wrong),
             devices=len(jax.devices()), shards=json.dumps(shards),
             **{k: np.asarray(v) for k, v in seen.items()})
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))

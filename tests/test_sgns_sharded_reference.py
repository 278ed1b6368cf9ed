"""The configuration `sgns-21m-d128-x4` at a small size on the CPU
against the plain reference: `PSDeviceCorpusTrainer` at the
configuration's `rehearsal` sizes (2,002 x 128, 512 centers a block) on
tables row-sharded over four devices, one block from the seeded tables.

Tolerances. The cell's own (`benchmark/reference/sgns_block.py`:
1e-3 of the loss, 6e-3 of the norm of the touched rows' change) are made
for the chip, where the program's products run in bfloat16 passes. Here
both sides compute in float32 at "highest" and differ only in the order
of their sums, so the block is also held to 1e-5: some forty times the
1.5e-7 and 2.8e-7 read here, and far under the 5e-3 to 2e-2 that a table kept in bfloat16
makes. The same block on one device differs from the four-device one by
the order in which the all-reduce sums the gathered rows' zeros, that
is not at all: held to 1e-6."""

import json
import os
import subprocess
import sys

import ml_dtypes
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from benchmark.reference import sgns_block  # noqa: E402

SCRIPT = os.path.join(REPO, "tests", "sgns_block_on_devices.py")
W, K, B, LR = 5, 5, 8, np.float32(0.025)    # the configuration's
TIGHT = 1e-5


@pytest.fixture(scope="module")
def blocks(tmp_path_factory):
    """The block on four devices and on one, each in its own process."""
    out = {}
    for devices in (4, 1):
        path = str(tmp_path_factory.mktemp("block") / f"d{devices}.npz")
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        env.update(JAX_PLATFORMS="cpu", XLA_FLAGS=(
            f"--xla_force_host_platform_device_count={devices}"))
        done = subprocess.run([sys.executable, SCRIPT, path], env=env,
                              capture_output=True, text=True, timeout=600)
        assert done.returncode == 0, done.stderr[-3000:]
        out[devices] = dict(np.load(path))
    return out


def _compare(block, **replaced):
    """The cell's comparison on a block's arrays: what disagreed."""
    b = dict(block, **replaced)
    return sgns_block.compare(
        b["v"], b["u"], b["v_after"], b["u_after"], b["in_ids"],
        b["out_ids"], b["pmask"], LR, float(b["loss"]), W, K, B)


@pytest.mark.parametrize("devices", [4, 1])
def test_the_tables_lie_over_every_device(blocks, devices):
    block = blocks[devices]
    assert int(block["devices"]) == devices
    assert json.loads(str(block["shards"])) == [devices, devices]
    assert block["in_ids"].shape == (512,)
    assert block["v"].shape == (512, 128) and block["v"].dtype == np.float32
    # the input table's rows come from the device-side draw
    bound = 0.5 / 128
    assert (np.abs(block["v"]) <= bound).all() and block["v"].std() > 0


@pytest.mark.parametrize("devices", [4, 1])
def test_one_block_matches_the_reference(blocks, devices, monkeypatch):
    block = blocks[devices]
    assert json.loads(str(block["wrong"])) == []       # the cell's check
    monkeypatch.setattr(sgns_block, "LOSS_RTOL", TIGHT)
    monkeypatch.setattr(sgns_block, "CHANGE_RTOL", TIGHT)
    assert _compare(block) == []
    assert np.abs(block["v_after"] - block["v"]).max() > 0


def test_four_devices_and_one_compute_the_same_block(blocks):
    four, one = blocks[4], blocks[1]
    for name in ("in_ids", "out_ids", "pmask", "v", "u"):
        np.testing.assert_array_equal(four[name], one[name], err_msg=name)
    for name in ("v_after", "u_after"):
        np.testing.assert_allclose(four[name], one[name], rtol=0, atol=1e-6,
                                   err_msg=name)
    assert abs(float(four["loss"]) - float(one["loss"])) \
        <= 1e-6 * abs(float(one["loss"]))


def test_a_table_kept_in_bfloat16_fails(blocks):
    """The four-device block's own rows and deltas, the tables stored in
    bfloat16: what the block reads is rounded, and so is what it
    leaves."""
    block = blocks[4]
    bf16 = ml_dtypes.bfloat16

    def stored(rows):
        return rows.astype(bf16).astype(np.float32)

    # a row's stored value plus the block's whole change of it, rounded
    v, u = stored(block["v"]), stored(block["u"])
    wrong = _compare(
        block, v=v, u=u,
        v_after=stored(v + (block["v_after"] - block["v"])),
        u_after=stored(u + (block["u_after"] - block["u"])))
    assert any("rows' change" in w for w in wrong), wrong

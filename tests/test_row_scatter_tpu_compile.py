"""The sorted-runs scatter-add compiled for a TPU v5e that is described,
not attached (the TPU's compiler is installed here): what Pallas'
interpreter cannot see (tiling, memory spaces, what Mosaic refuses) and
what only the partitioner shows (a row-sharded table takes no
collective). Nothing runs; no time comes from this.

The topology is described inside a fixture, never at import, and every
test of this kind lives in this one file: only one process at a time
may load the TPU's library, and a worker keeps it until it exits.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from multiverso_tpu.sharding import mesh as meshlib
from multiverso_tpu.updater import UpdateEngine, rules

ROWS, COLS = 1_000_004, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.mark.parametrize("chips", [1, 4])
def test_the_rows_program_compiles_for_a_v5e(topo, chips):
    mesh = Mesh(np.array(topo.devices[:chips]), (meshlib.SHARD_AXIS,))
    rows = NamedSharding(mesh, P(meshlib.SHARD_AXIS, None))
    everywhere = NamedSharding(mesh, P())
    engine = UpdateEngine(None, (ROWS, COLS), np.float32, 1, rows)
    k = (2, rules.FAST_MIN_IDS)
    assert rules.fast_rows((ROWS, COLS), np.float32, 2 * k[1], mesh)
    shaped = jax.ShapeDtypeStruct
    compiled = engine._rows.lower(
        shaped((ROWS, COLS), jnp.float32, sharding=rows), None,
        shaped(k, jnp.int32, sharding=everywhere),
        shaped(k + (50,), jnp.float32, sharding=everywhere),
        np.zeros(4, np.float32), np.int32(0)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-reduce", "all-gather", "all-to-all",
                       "collective-permute"):
        assert collective not in text
    memory = compiled.memory_analysis()
    # the table is updated in place (its tiles pad the rows to eights)
    assert memory.alias_size_in_bytes >= ROWS * COLS * 4 // chips
    assert memory.temp_size_in_bytes < 50e6

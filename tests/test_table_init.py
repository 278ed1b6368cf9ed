"""The contract of a server table's ``random_init`` (MatrixServer,
sharding/mesh.py ``uniform_sharded``): float32 draws in ``[lo, hi)``
written by one program on the table's own devices, padding rows and
lanes zero, a function of ``(seed, server id)`` and the element's
position only, so the same on one device, on four and on eight; and no
array of the table's size is ever made on the host. Nothing else pins
the values: they are JAX's threefry stream, not numpy's generator."""

import hashlib
import os
import subprocess
import sys

import numpy as np
import pytest

import multiverso_tpu as mv
from multiverso_tpu.runtime.cluster import LocalCluster
from multiverso_tpu.sharding import mesh as meshlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LO, HI = -0.25, 0.75


@pytest.fixture
def env():
    mv.init([])
    yield
    mv.shutdown()


def _stored(num_row, num_col, seed=7, bounds=(LO, HI)):
    from multiverso_tpu.tables.matrix_table import MatrixServer
    server = MatrixServer(num_row, num_col, random_init=bounds, seed=seed)
    return server, np.asarray(server._data)


# rows: a multiple of the conftest's 8 devices, not one, fewer than them;
# columns: kept compact (3), padded to the 128 lanes (50), a whole tile
@pytest.mark.parametrize("num_row, num_col", [
    (1003, 3), (1003, 50), (1003, 128), (1024, 50), (5, 128)])
def test_draws_lie_in_the_interval_and_padding_is_zero(env, num_row,
                                                       num_col):
    server, data = _stored(num_row, num_col)
    devices = meshlib.device_count(meshlib.local_mesh())
    assert data.dtype == np.float32
    assert data.shape == (meshlib.padded_size(num_row, devices),
                          server._col_store)
    inside = data[:num_row, :num_col]
    assert (inside >= LO).all() and (inside < HI).all()
    assert not data[num_row:].any() and not data[:, num_col:].any()
    if inside.size > 1000:      # uniform, not a constant or a ramp
        assert abs(inside.mean() - (LO + HI) / 2) < 0.02
        assert abs(inside.std() - (HI - LO) / 12 ** 0.5) < 0.02
    # every shard holds its own rows on its own device
    assert len({s.device for s in server._data.addressable_shards}) \
        == devices


def test_the_upper_bound_is_open(env):
    # one float32 lies in [1, nextafter(1, 2)): rounding lo + u * (hi - lo)
    # to nearest would give hi itself for half the draws
    hi = float(np.nextafter(np.float32(1.0), np.float32(2.0)))
    _, data = _stored(64, 128, bounds=(1.0, hi))
    assert (data == 1.0).all()


def _drawn(seed, stream):
    sharding = meshlib.row_sharded(meshlib.local_mesh())
    return np.asarray(meshlib.uniform_sharded(
        (64, 128), np.float32, sharding, 60, 100, LO, HI, seed, stream))


@pytest.mark.parametrize("seed, stream, same", [
    (7, 0, True),               # the same (seed, server id): the same table
    (8, 0, False),              # another seed
    (7, 1, False),              # another server of the same table
    (7 + 2 ** 32, 0, False),    # the seed's high word counts
])
def test_a_table_is_a_function_of_seed_and_server_id(seed, stream, same):
    assert np.array_equal(_drawn(7, 0), _drawn(seed, stream)) is same


def test_two_servers_of_one_table_draw_different_rows():
    def body(rank):
        table = mv.create_matrix_table(64, 16, random_init=(LO, HI), seed=3)
        mv.barrier()
        return table.get()
    whole, other = LocalCluster(2).run(body)
    np.testing.assert_array_equal(whole, other)
    assert (whole >= LO).all() and (whole < HI).all()
    # server 0 holds rows 0-31 and server 1 rows 32-63, each drawn at its
    # own positions 0-31: one key for both would repeat the block
    assert not np.array_equal(whole[:32], whole[32:])


_DIGEST = """
import hashlib, sys
import numpy as np
import multiverso_tpu as mv
from multiverso_tpu.tables.matrix_table import MatrixServer
import jax
assert len(jax.devices()) == int(sys.argv[1])
mv.init([])
server = MatrixServer(1003, 50, random_init=(-0.25, 0.75), seed=2 ** 31 + 5)
assert len(server._data.addressable_shards) == int(sys.argv[1])
print("digest", hashlib.sha256(
    np.asarray(server._data)[:1003].tobytes()).hexdigest())
mv.shutdown()
"""


@pytest.mark.parametrize("devices", [1, 4])
def test_the_table_is_the_same_on_one_device_on_four_and_on_eight(
        env, devices):
    """Element for element: a process of its own with ``devices`` CPU
    devices (as tests/test_chip_smoke.py starts its own) against this
    process's eight. 1003 rows pad to 1003, 1004 and 1008."""
    _, data = _stored(1003, 50, seed=2 ** 31 + 5)
    want = hashlib.sha256(data[:1003].tobytes()).hexdigest()
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO, XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    done = subprocess.run([sys.executable, "-c", _DIGEST, str(devices)],
                          env=base, capture_output=True, text=True,
                          timeout=300)
    assert done.returncode == 0, done.stderr[-3000:]
    got = [line.split()[1] for line in done.stdout.splitlines()
           if line.startswith("digest ")]
    assert got == [want]


def test_no_host_array_of_the_tables_size_is_made(env, monkeypatch):
    """200,003 x 128 float32 is 102 MB. Constructing the server may
    allocate no numpy array over 4 MB, draw nothing from numpy's
    generators and upload no host array: the table is born on its
    devices."""
    import jax
    from multiverso_tpu.tables import matrix_table
    limit = 4 << 20

    def guarded(name, real):
        def allocate(shape, *args, **kw):
            out = real(shape, *args, **kw)
            assert out.nbytes <= limit, (name, shape)
            return out
        return allocate

    for name in ("zeros", "empty", "ones", "full"):
        monkeypatch.setattr(np, name, guarded(name, getattr(np, name)))

    def no_generator(*args, **kw):
        raise AssertionError("numpy generator made in table construction")
    monkeypatch.setattr(np.random, "default_rng", no_generator)
    real_put = jax.device_put

    def small_put(x, *args, **kw):
        assert getattr(x, "nbytes", 0) <= limit, "host array uploaded"
        return real_put(x, *args, **kw)
    monkeypatch.setattr(jax, "device_put", small_put)

    server = matrix_table.MatrixServer(200_003, 128, random_init=(LO, HI),
                                       seed=11)
    assert server._data.nbytes > 100e6
    monkeypatch.undo()
    sample = np.asarray(server._data[200_000:200_003])
    assert (sample >= LO).all() and (sample < HI).all() and sample.std() > 0

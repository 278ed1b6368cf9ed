"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding correctness is
validated on XLA's host platform with 8 virtual devices (the environment
ci.sh gives the multichip dry run). Must run before the first jax import.
"""

import os

# Tests force the CPU platform before the first jax import, whatever the
# shell exported.
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: heavy chaos tests, excluded from the tier-1 run "
        "(-m 'not slow')")


@pytest.fixture(autouse=True)
def _reset_flags():
    from multiverso_tpu.util import configure
    yield
    configure.reset_flags()


@pytest.fixture(autouse=True)
def _transport_leak_guard():
    """Teardown leak guard (docs/THREADS.md): every test must return
    role-thread count to its baseline — a finalized transport leaves
    no loop, writer, or dispatch thread behind — and tests that built
    a transport must also return the process fd count to baseline
    (sockets, selector epoll fds, wake pipes, shm doorbell FIFOs)."""
    import gc
    import time

    from multiverso_tpu.runtime import thread_roles
    from multiverso_tpu.runtime.tcp import TcpNet

    def fd_count():
        try:
            return len(os.listdir("/proc/self/fd"))
        except OSError:  # pragma: no cover - no procfs
            return -1

    threads_before = sum(thread_roles.roles_alive().values())
    nets_before = TcpNet.instances_created
    fds_before = fd_count()
    yield
    deadline = time.monotonic() + 10.0
    while time.monotonic() < deadline:
        if sum(thread_roles.roles_alive().values()) <= threads_before:
            break
        time.sleep(0.05)
    alive = thread_roles.roles_alive()
    assert sum(alive.values()) <= threads_before, (
        f"role threads leaked past teardown: {alive} "
        f"(baseline {threads_before})")
    if TcpNet.instances_created != nets_before and fds_before >= 0:
        # Scoped to transport-building tests: unrelated tests may
        # fault in lazy runtime fds (jax, imports) that are not leaks.
        gc.collect()  # drop lingering frame leases / socket wrappers
        fds_after = fd_count()
        assert fds_after <= fds_before + 8, (
            f"fd count grew {fds_before} -> {fds_after} across a "
            f"transport-building test (leaked sockets/pipes?)")

"""chip_smoke.py and the no-fallback rules around it, checked on the CPU.

The smoke itself only means something on the chip. What can be checked
here is that it refuses to run anywhere else, that its arms work at a toy
size (so chip time is not spent debugging them), that the compile cache
goes where it is told, and that the entry points which used to carry on
without the device they were asked for now raise.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402


def _python(args, cwd, **env):
    """A fresh interpreter on one CPU device (the conftest's 8-device
    XLA_FLAGS dropped), with the repo importable from any cwd."""
    base = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    base.update(JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    base.pop("JAX_COMPILATION_CACHE_DIR", None)
    base.update(env)
    return subprocess.run([sys.executable] + args, cwd=cwd, env=base,
                          capture_output=True, text=True, timeout=120)


def test_smoke_refuses_a_cpu_backend():
    out = _python(["chip_smoke.py"], cwd=REPO)
    assert out.returncode != 0
    assert "platform=cpu" in out.stdout  # names the backend it found
    assert "arm passed" not in out.stdout  # and trained nothing
    assert '"ok"' not in out.stdout


def test_smoke_arms_at_toy_size(tmp_path):
    # The arms, not the platform gate. The size is the smallest at which
    # the PS arm's loss check holds: the trainer's batch-summed update
    # needs a vocabulary that is large next to its 32768-center step.
    report = chip_smoke.run_arms(str(tmp_path), rows=400_003, dim=16,
                                 sentences=40_000)
    assert report["ps"]["server_gets"] > 0
    assert report["ps"]["built_after_warmup"] == 0
    assert report["tables"]["built_after_warmup"] == 0
    assert report["local"]["placement"]["trainer_embeddings"]
    # Off the TPU the rows programs keep XLA's scatter, on 1 and 8 devices.
    assert set(report["scatter"]["path"].values()) == {"xla_scatter"}
    assert len(report["scatter"]["path"]) == 2


# Prints [the directory enable() reports, how many directory settings it
# made]. (The setting's name is spelled in the helper only, so that a
# grep for it finds every place that sets the cache.)
_REPORT_CACHE_DIR = (
    "import jax, json\n"
    "made, real = [], jax.config.update\n"
    "jax.config.update = lambda k, v: (made.append(k), real(k, v))\n"
    "from multiverso_tpu.util import compile_cache\n"
    "print(json.dumps([compile_cache.enable(),"
    " sum(k.endswith('cache_dir') for k in made)]))")


def test_compile_cache_default_is_the_checkout_from_any_cwd(tmp_path):
    want = os.path.join(REPO, ".jax_cache")
    for cwd in (REPO, str(tmp_path)):
        out = _python(["-c", _REPORT_CACHE_DIR], cwd=cwd)
        assert out.returncode == 0, out.stderr
        assert json.loads(out.stdout) == [want, 1]


def test_compile_cache_leaves_an_outside_placement_alone(tmp_path):
    placed = str(tmp_path / "elsewhere")
    out = _python(["-c", _REPORT_CACHE_DIR], cwd=REPO,
                  JAX_COMPILATION_CACHE_DIR=placed)
    assert out.returncode == 0, out.stderr
    # JAX read the variable itself; the helper set no directory.
    assert json.loads(out.stdout) == [placed, 0]


def test_dryrun_raises_with_too_few_devices():
    out = _python(["-c", "import __graft_entry__ as g; "
                         "g.dryrun_multichip(8)"], cwd=REPO)
    assert out.returncode != 0
    assert "needs 8 devices" in out.stderr
    assert "xla_force_host_platform_device_count=8" in out.stderr
    assert "OK" not in out.stdout  # no child ran the dry run for it

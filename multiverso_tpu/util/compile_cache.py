"""Where the persistent XLA compilation cache lives.

One rule for every entry point (``chip_smoke.py``, ``benchmark/run.py``,
the app CLIs): where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
reads it itself and this module touches no directory setting; where it
is not, the cache is ``<checkout>/.jax_cache`` (gitignored), derived
from this file's location so the path is the same from any working
directory.

Call ``enable()`` from a process entry point before the first jit
compiles: JAX opens the cache at the first compilation and keeps that
directory for the life of the process. The same call starts the
Dashboard's listeners to every program's trace, lowering, cache read and
compile (``util/dashboard.py`` ``listen_to_program_builds``: the
``PROGRAM_*`` monitors, ``mv:PROGRAM_*`` spans and ``program_builds()``),
once a process.
"""

from __future__ import annotations

import os

import jax

from . import dashboard

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: Used only when ``JAX_COMPILATION_CACHE_DIR`` is unset.
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")

#: Programs that compiled faster than this are not written. 0 keeps every
#: program: the table gather/scatter programs are small and many, and on
#: a locally attached v5e 42 of chip_smoke's 47 compile in under a second
#: (CHANGES.md, PR 21), so the old 5 s and JAX's default 1 s both leave a
#: second run recompiling them one by one.
MIN_COMPILE_SECS = 0.0


def enable() -> str:
    """Turn the persistent cache on and return the directory in effect."""
    dashboard.listen_to_program_builds()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      MIN_COMPILE_SECS)
    return jax.config.jax_compilation_cache_dir

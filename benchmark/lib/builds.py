"""Programs JAX built, from its own monitoring events.

A copy of ``chip_smoke.ProgramBuilds`` (the original stays with the
smoke): one duration event per executable JAX had to produce, whether
XLA compiled it or the persistent cache supplied it, and one plain
event per cache hit. ``programs_compiled`` is what was really built
from source: produced and not found on disk.
"""

import jax.monitoring

_BUILD_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class ProgramBuilds:
    def __init__(self):
        self.seconds = []
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._took)
        jax.monitoring.register_event_listener(self._happened)

    def _took(self, event, seconds, **kw):
        if event == _BUILD_EVENT:
            self.seconds.append(seconds)

    def _happened(self, event, **kw):
        if event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def mark(self):
        return len(self.seconds), self.cache_hits

    def since(self, mark) -> dict:
        took = self.seconds[mark[0]:]
        from_disk = self.cache_hits - mark[1]
        return {"programs_built": len(took),
                "from_persistent_cache": from_disk,
                "programs_compiled": len(took) - from_disk,
                "build_seconds": sum(took)}

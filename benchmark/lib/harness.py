"""What the entry point hands a driver (``Context``), what a driver
hands back for each measured window (``Window``), and what the metric
readers are given (``Observations``)."""

import contextlib
import gc
import time

from benchmark.lib import counters

GC_PAUSE_MS = 10.0   # a collection this long, or any full one, is logged


class Window:
    """One measured window. The driver fills ``rounds`` (blocks, or
    Get+Add rounds), ``work`` (words or rows done), ``attempted`` and
    ``failed``, and ``samples`` (caller-side milliseconds by name)."""

    def __init__(self, builds):
        self._builds = builds
        self._mark = builds.mark()
        # every monitor as it stood when the window opened; the first
        # window's is what set-up counted
        self.at_open = counters.snapshot()
        self.rounds = 0
        self.work = {}
        self.attempted = 0
        self.failed = 0
        self.samples = {}
        # [generation, milliseconds, seconds into the window] of every
        # full or long garbage collection in the window: a host-driven
        # loop stalls for as long as one lasts
        self.gc_pauses = []
        self._gc_started = None
        gc.callbacks.append(self._on_gc)
        self.t_start = time.monotonic()
        self.seconds = None
        self.counters = None
        self.builds = None

    def _on_gc(self, phase, info):
        now = time.monotonic()
        if phase == "start":
            self._gc_started = now
        elif self._gc_started is not None:
            ms = (now - self._gc_started) * 1e3
            if info["generation"] == 2 or ms >= GC_PAUSE_MS:
                self.gc_pauses.append(
                    [info["generation"], round(ms, 1),
                     round(self._gc_started - self.t_start, 2)])

    def close(self):
        self.seconds = time.monotonic() - self.t_start
        gc.callbacks.remove(self._on_gc)
        self.counters = counters.delta(self.at_open, counters.snapshot())
        self.builds = self._builds.since(self._mark)
        return self


class Context:
    def __init__(self, config: dict, traffic: dict, seed: int, builds,
                 deadline_s: int):
        self.config = config
        self.traffic = traffic
        self.seed = seed
        self.builds = builds
        self.deadline_s = deadline_s
        self.shapes = {}     # request shapes, for the byte arithmetic
        self._annotate = False
        self._window_span = None

    def annotate(self, on: bool):
        """Spans cost a little and mean something only under the
        profiler, so they are written in the traced window alone."""
        self._annotate = on

    @property
    def tracing(self) -> bool:
        return self._annotate

    def span(self, name: str):
        if not self._annotate:
            return contextlib.nullcontext()
        import jax.profiler
        return jax.profiler.TraceAnnotation(f"bench:{name}")

    def open_window(self) -> Window:
        window = Window(self.builds)
        if self._annotate:
            self._window_span = self.span("window")
            self._window_span.__enter__()
            window.t_start = time.monotonic()
        return window

    def close_window(self, window: Window) -> Window:
        window.close()
        if self._window_span is not None:
            self._window_span.__exit__(None, None, None)
            self._window_span = None
        return window


class Observations:
    """Everything a metric reader may read. ``window`` is the measured
    window (profiler off); ``traced`` and ``trace`` are the short traced
    window and its reduced device trace, present only with ``--trace 1``
    on a chip."""

    def __init__(self, **kw):
        self.phases = None
        self.setup_builds = None
        self.window = None
        self.traced = None
        self.trace = None
        self.shapes = None
        self.peaks = None
        self.device = None
        self.config = None
        self.traffic = None
        self.__dict__.update(kw)

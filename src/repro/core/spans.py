"""Program spans: one named interval per engine phase, written to the
profiler's trace and kept in memory from the same two clock readings.

    with span("dispatch.enqueue", w0=w0, n_win=n_win) as sp:
        ...
    sp.seconds  # the wall the engine records (Telemetry.block_walls)

Each span opens a `jax.profiler.TraceAnnotation` named
`repro.<layer>.<phase>`, so a profiler trace shows it on the host's
line beside the device's operations, on one clock. Its `start`/`end`
are `time.perf_counter()` readings taken inside that annotation, so the
seconds the engine keeps and the interval the trace shows are the same
interval. Attributes are host values the caller already holds (window
indices, counts already pulled): a span never reads a device value, so
with the profiler off it adds no host-device sync and no device work.

Names in use (trace readers match them by name):

    repro.simulate          api.run.simulate, the whole call
    repro.engine.build      api.run.build_engine
    repro.dispatch.place    the dispatch's mesh and the placement of a
                            new engine's pool, rates and group ids
    repro.dispatch.enqueue  the jitted window or block call
    repro.dispatch.fold     the eager per-window statistic folds and the
                            async device-to-host copies they start
    repro.collect.pull      the blocking device_get: the host waits on
                            the device
    repro.collect.reduce    the host-side per-window record emission
    repro.collect.gather    SimulationResult.final_state: the lane
                            states pulled from every shard
"""
from __future__ import annotations

import time

from jax.profiler import TraceAnnotation

PREFIX = "repro."


class span:
    """Context manager for one program span (see the module doc)."""

    __slots__ = ("_annotation", "start", "end")

    def __init__(self, name: str, **attrs):
        self._annotation = TraceAnnotation(PREFIX + name, **attrs)
        self.start = self.end = 0.0

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    def annotate(self, **attrs) -> None:
        """Add attributes known only inside the span (host values). A
        no-op unless a profiler trace is being taken."""
        if TraceAnnotation.is_enabled():
            self._annotation.set_metadata(**attrs)

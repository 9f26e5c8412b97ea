"""The names under which the benchmark harness (``bench/lib/harness.py``)
arms the program's tracer for a ``--trace 1`` run.

Not a second span system: ``Profiler`` is :class:`~repro.obs.trace.Tracer`
with ``device=True``, so the program's spans land on the profiler's
timeline, and its ``stats()`` is the span aggregate the harness copies
into ``Record.phases``.  Program code uses ``obs.trace`` directly.
"""

from __future__ import annotations

from .trace import Tracer, set_tracer as set_profiler

__all__ = ["Profiler", "set_profiler"]


class Profiler(Tracer):
    """A device tracer, as ``bench/lib/harness.py`` arms it."""

    def __init__(self) -> None:
        super().__init__(device=True)

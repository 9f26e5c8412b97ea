"""What XLA did, as JAX reports it (DESIGN.md §12).

One process-wide ``jax.monitoring`` listener counts jaxpr traces,
backend compiles (an executable built, or read back from the persistent
cache) and persistent-cache reads.  The batched executor snapshots the
counts around each launch and exports what fired inside it as
``executor_compiles_total{kind}``, so a compile is counted when XLA
compiles and not when a validator first sees a shape.  ``jax`` is
imported when the listener is first asked for.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

__all__ = ["EVENTS", "KINDS", "CompileEvents", "compile_events"]

EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/backend_compile_duration": "compile",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_read",
}
KINDS = tuple(EVENTS.values())


class CompileEvents:
    """Counts of JAX's compile events by kind, since the listener was
    registered."""

    def __init__(self) -> None:
        self.counts: Dict[str, int] = dict.fromkeys(KINDS, 0)

    def on_event(self, event: str, duration: float, **_: Any) -> None:
        kind = EVENTS.get(event)
        if kind is not None:
            self.counts[kind] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)

    def since(self, before: Dict[str, int]) -> Dict[str, int]:
        """Events of each kind after the ``before`` snapshot."""
        return {k: n - before[k] for k, n in self.counts.items()}


_EVENTS: Optional[CompileEvents] = None


def compile_events() -> CompileEvents:
    """The process's listener, registered with ``jax.monitoring`` on the
    first call."""
    global _EVENTS
    if _EVENTS is None:
        import jax

        _EVENTS = CompileEvents()
        jax.monitoring.register_event_duration_secs_listener(_EVENTS.on_event)
    return _EVENTS

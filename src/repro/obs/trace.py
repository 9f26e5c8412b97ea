"""The program's one span system (DESIGN.md §12).

Module-level ``span(name, **attrs)`` / ``trace_point(name, **attrs)``
seams mirror the ``fault_point`` pattern of ``core/outcomes.py``: with
no :class:`Tracer` armed they cost one module-global ``None`` check and
``span`` returns a shared no-op context.  An armed tracer keeps two
things:

* a per-name aggregate (:class:`SpanStat`: calls, inclusive
  ``total_ns``, exclusive ``self_ns`` with child time subtracted), so
  ``sum(self_ns)`` never double-counts and :meth:`Tracer.coverage`
  compares it with an externally timed window;
* a ring of the newest :class:`Span` records, each with its own id, its
  parent's id and the id of the root span (the submission or drain)
  that caused it.

``Tracer(device=True)`` also enters ``jax.profiler.TraceAnnotation`` for
every span, so the span lands on the profiler's host plane, on the same
timeline as the device's op events, where an idle gap can be put down
to the program layer the host was in.  ``jax`` is imported only then.

The instrumentation rule: spans at batch or stage granularity, never
per document or per key.  Every name the program opens is declared in
:data:`SPANS` (and :data:`POINTS` for point events).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

__all__ = [
    "SPANS",
    "POINTS",
    "Span",
    "SpanStat",
    "Tracer",
    "set_tracer",
    "tracer_armed",
    "span",
    "trace_point",
]

# one name per boundary; the roots (a submission, a drain, a pipeline
# admission) are the causes their descendants' records point at
SPANS = (
    "serve.submit_batch",
    "serve.submit",
    "serve.parse",
    "serve.validate",
    "serve.dispatch",
    "serve.drain",
    "admit.guard",
    "admit.encode",
    "admit.launch",
    "admit.explain",
    "admit.verdicts",
    "admit.fallback",
    "executor.launch",
    "executor.explain",
    "registry.relink",
    "pipeline.admit",
)
POINTS = ("executor.recompile", "executor.bisect")


@dataclass
class Span:
    """One completed (or point-in-time) trace record.

    ``dur_ns`` is -1 for point events; ``depth`` is the nesting level at
    entry.  ``parent_id`` is 0 for a root, and a root's ``cause_id`` is
    its own ``span_id``.
    """

    name: str
    t0_ns: int
    dur_ns: int = -1
    depth: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)
    span_id: int = 0
    parent_id: int = 0
    cause_id: int = 0

    @property
    def dur_us(self) -> float:
        return self.dur_ns / 1e3 if self.dur_ns >= 0 else -1.0


class SpanStat:
    """Accumulated timing for one span name."""

    __slots__ = ("name", "calls", "total_ns", "self_ns")

    def __init__(self, name: str):
        self.name = name
        self.calls = 0
        self.total_ns = 0
        self.self_ns = 0

    def as_dict(self) -> Dict[str, int]:
        return {
            "calls": self.calls,
            "total_ns": self.total_ns,
            "self_ns": self.self_ns,
        }


class _SpanCtx:
    """One live span (returned by ``Tracer.span``)."""

    __slots__ = ("_tracer", "_name", "attrs", "_t0", "_id", "_parent", "_cause", "_child_ns", "_note")

    def __init__(self, tracer: "Tracer", name: str, attrs: Dict[str, Any]):
        self._tracer = tracer
        self._name = name
        self.attrs = attrs

    def set(self, **attrs: Any) -> None:
        """Add attributes known only once the span's work has run."""
        self.attrs.update(attrs)

    def __enter__(self) -> "_SpanCtx":
        tr = self._tracer
        tr._ids += 1
        self._id = tr._ids
        stack = tr._stack
        if stack:
            self._parent, self._cause = stack[-1]._id, stack[0]._id
        else:
            self._parent, self._cause = 0, self._id
        self._child_ns = 0
        stack.append(self)
        if tr._annotation is not None:
            self._note = tr._annotation(self._name)
            self._note.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc: Any) -> None:
        dur = time.perf_counter_ns() - self._t0
        tr = self._tracer
        if tr._annotation is not None:
            self._note.__exit__(*exc)
        stack = tr._stack
        stack.pop()
        depth = len(stack)
        if stack:
            stack[-1]._child_ns += dur  # bill inclusive time to the parent
        stat = tr._stats.get(self._name)
        if stat is None:
            stat = tr._stats[self._name] = SpanStat(self._name)
        stat.calls += 1
        stat.total_ns += dur
        stat.self_ns += dur - self._child_ns
        tr._record(
            Span(self._name, self._t0, dur, depth, self.attrs, self._id, self._parent, self._cause)
        )


class _NoopCtx:
    """Shared do-nothing context for the disarmed path."""

    __slots__ = ()

    def set(self, **attrs: Any) -> None:
        pass

    def __enter__(self) -> "_NoopCtx":
        return self

    def __exit__(self, *exc: Any) -> None:
        pass


_NOOP = _NoopCtx()


class Tracer:
    """Per-name span aggregate plus a ring of recent span records.

    Appends overwrite the oldest record once ``capacity`` is reached
    (single-threaded: one index increment per record).  Arm with::

        with Tracer(capacity=512) as tr:
            ...  # instrumented code calls obs.trace.span(...)
        spans, stats = tr.recent(), tr.stats()
    """

    def __init__(self, capacity: int = 1024, *, device: bool = False):
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self.device = device
        self._annotation = None
        if device:
            import jax

            self._annotation = jax.profiler.TraceAnnotation
        self._prev: Optional["Tracer"] = None
        self.clear()

    # -- recording ---------------------------------------------------------

    def _record(self, s: Span) -> None:
        self._ring[self._next % self.capacity] = s
        self._next += 1

    def span(self, name: str, **attrs: Any) -> _SpanCtx:
        return _SpanCtx(self, name, attrs)

    def point(self, name: str, **attrs: Any) -> None:
        self._ids += 1
        stack = self._stack
        parent, cause = (stack[-1]._id, stack[0]._id) if stack else (0, self._ids)
        self._record(
            Span(name, time.perf_counter_ns(), -1, len(stack), attrs, self._ids, parent, cause)
        )

    # -- views -------------------------------------------------------------

    @property
    def recorded(self) -> int:
        """Total records since construction or ``clear`` (evicted included)."""
        return self._next

    def recent(self) -> List[Span]:
        """Records still in the ring, oldest first."""
        n = self._next
        if n <= self.capacity:
            return [s for s in self._ring[:n] if s is not None]
        start = n % self.capacity
        out = self._ring[start:] + self._ring[:start]
        return [s for s in out if s is not None]

    def stats(self) -> Dict[str, SpanStat]:
        return dict(self._stats)

    def attributed_ns(self) -> int:
        """Total exclusive nanoseconds across all spans (no double count)."""
        return sum(s.self_ns for s in self._stats.values())

    def coverage(self, window_ns: int) -> float:
        """Fraction of ``window_ns`` explained by recorded spans."""
        if window_ns <= 0:
            return 0.0
        return self.attributed_ns() / window_ns

    def report(self, window_ns: Optional[int] = None) -> Dict[str, Any]:
        """JSON-ready attribution report, spans sorted by exclusive time.

        With ``window_ns`` (an externally measured end-to-end window) the
        report carries per-span window fractions plus the aggregate
        coverage and the unattributed remainder.
        """
        ordered = sorted(self._stats.values(), key=lambda s: s.self_ns, reverse=True)
        phases: Dict[str, Any] = {}
        for s in ordered:
            entry: Dict[str, Any] = s.as_dict()
            if window_ns:
                entry["window_frac"] = s.self_ns / window_ns
            phases[s.name] = entry
        out: Dict[str, Any] = {"phases": phases, "attributed_ns": self.attributed_ns()}
        if window_ns:
            out["window_ns"] = window_ns
            out["coverage"] = self.coverage(window_ns)
            out["unattributed_ns"] = max(0, window_ns - self.attributed_ns())
        return out

    def clear(self) -> None:
        self._ring: List[Optional[Span]] = [None] * self.capacity
        self._next = 0
        self._ids = 0
        self._stack: List[_SpanCtx] = []
        self._stats: Dict[str, SpanStat] = {}

    # -- arming ------------------------------------------------------------

    def __enter__(self) -> "Tracer":
        self._prev = set_tracer(self)
        return self

    def __exit__(self, *exc: Any) -> None:
        set_tracer(self._prev)
        self._prev = None


# ---------------------------------------------------------------------------
# Module-level seams (one None check when disarmed, like fault_point)
# ---------------------------------------------------------------------------


_TRACER: Optional[Tracer] = None


def set_tracer(tracer: Optional[Tracer]) -> Optional[Tracer]:
    """Install (or clear) the process-wide tracer; returns the prior one."""
    global _TRACER
    prev = _TRACER
    _TRACER = tracer
    return prev


def tracer_armed() -> bool:
    """True when a tracer is armed (someone is measuring)."""
    return _TRACER is not None


def span(name: str, **attrs: Any) -> Any:
    """Context manager for a named span; shared no-op when disarmed."""
    if _TRACER is None:
        return _NOOP
    return _TRACER.span(name, **attrs)


def trace_point(name: str, **attrs: Any) -> None:
    """Point-in-time trace event; no-op unless a tracer is armed."""
    if _TRACER is not None:
        _TRACER.point(name, **attrs)

"""From a profiler trace to device busy time, idle share, launch time and
the breakdown.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps plain events (name, start and duration in nanoseconds): the
device planes' op and module lines, and the host events of the
benchmark's own ``bench.*`` annotations.  Everything after that works on
those plain events, so the reduction can be checked on a small recorded
trace (``bench/tests/data/``).
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, duration_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# the batched executor's launch program: ``jax.jit`` of a partial of
# ``core/batch_executor.py::_validate_batch``, which JAX names
# ``jit__unknown`` (a partial has no name of its own)
LAUNCH_PROGRAM = re.compile(r"^jit__(unknown|validate_batch)\b")


@dataclass
class Events:
    devices: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)
    host: List[Event] = field(default_factory=list)

    @classmethod
    def from_json(cls, data: dict) -> "Events":
        return cls(
            {d: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()} for d, lines in data["devices"].items()},
            [tuple(e) for e in data["host"]],
        )


def load(logdir: Path) -> Events:
    """Plain events of the newest trace under ``logdir``."""
    from jax.profiler import ProfileData

    paths = sorted(Path(logdir).rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(str(paths[-1]))
    out = Events()
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [(e.name, int(e.start_ns), int(e.duration_ns)) for e in line.events]
            out.devices[plane.name] = lines
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        out.host.append((e.name, int(e.start_ns), int(e.duration_ns)))
    return out


def merge(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Union of half-open ``(start, end)`` intervals, sorted and disjoint."""
    out: List[Tuple[int, int]] = []
    for start, end in sorted(intervals):
        if end <= start:
            continue
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def clip(intervals: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def total(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def gaps(busy: Sequence[Tuple[int, int]], lo: int, hi: int) -> List[Tuple[int, int]]:
    """The parts of ``[lo, hi)`` that the disjoint sorted ``busy`` leaves."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


@dataclass
class Summary:
    window_s: float
    busy_s: float  # averaged over the device planes
    n_devices: int
    device_ops: List[Tuple[str, float]]  # op name -> seconds, most first
    idle_gaps: List[Tuple[str, float]]  # host span -> idle seconds, most first
    launches: int
    launch_busy_s: float  # device busy inside launch programs

    @property
    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def window_of(events: Events) -> Tuple[int, int]:
    spans = [(s, s + d) for name, s, d in events.host if name == WINDOW_SPAN]
    if len(spans) != 1:
        raise ValueError(f"{len(spans)} {WINDOW_SPAN} spans in the trace")
    return spans[0]


def summarize(events: Events, top: int = 10) -> Summary:
    lo, hi = window_of(events)
    busy_total = 0
    by_op: Dict[str, int] = {}
    idle_by_span: Dict[str, int] = {}
    launches = 0
    launch_busy = 0
    host = sorted(((n, s, s + d) for n, s, d in events.host if n != WINDOW_SPAN), key=lambda x: x[1])
    for lines in events.devices.values():
        ops = lines.get(OPS_LINE, [])
        busy = merge(clip([(s, s + d) for _, s, d in ops], lo, hi))
        busy_total += total(busy)
        for name, s, d in ops:
            part = total(clip([(s, s + d)], lo, hi))
            if part:
                by_op[name] = by_op.get(name, 0) + part
        for name, part in idle_by_host(gaps(busy, lo, hi), host).items():
            idle_by_span[name] = idle_by_span.get(name, 0) + part
        for name, s, d in lines.get(MODULES_LINE, []):
            if LAUNCH_PROGRAM.match(name) and lo <= s and s + d <= hi:
                launches += 1
                launch_busy += total(clip(busy, s, s + d))
    n = max(1, len(events.devices))
    ranked = lambda d: [(k, v / 1e9) for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Summary(
        window_s=(hi - lo) / 1e9,
        busy_s=busy_total / n / 1e9,
        n_devices=len(events.devices),
        device_ops=ranked(by_op),
        idle_gaps=ranked(idle_by_span),
        launches=launches,
        launch_busy_s=launch_busy / 1e9,
    )


def idle_by_host(idle: Sequence[Tuple[int, int]], host: Sequence[Tuple[str, int, int]]) -> Dict[str, int]:
    """Nanoseconds of the sorted idle intervals spent in each host span
    (sorted by start; where spans overlap, the earlier one keeps the
    overlap); what no span covers is ``host.other``."""
    out: Dict[str, int] = {}
    j = 0
    for lo, hi in idle:
        while j < len(host) and host[j][2] <= lo:
            j += 1
        t, k = lo, j
        while k < len(host) and host[k][1] < hi:
            name, s, e = host[k]
            s, e = max(s, t), min(e, hi)
            if s > t:
                out["host.other"] = out.get("host.other", 0) + (s - t)
            if e > s:
                out[name] = out.get(name, 0) + (e - s)
                t = e
            k += 1
        if t < hi:
            out["host.other"] = out.get("host.other", 0) + (hi - t)
    return out

"""From profiler events to device busy time, idle share, launch time and
the breakdown: on hand-made events, and on a small trace recorded on one
TPU v5e (``data/trace_v5e_closed.json``: plain events of a traced
closed-loop window of batches of 4096 requests, cut to its first
launches)."""

import json
import random
from pathlib import Path

import pytest

from bench.lib import trace as T

MS = 1_000_000  # ns


def _events(ops, modules=(), host=()):
    window = [("bench.window", 0, 100 * MS)]
    return T.Events(
        devices={"/device:TPU:0": {T.OPS_LINE: list(ops), T.MODULES_LINE: list(modules)}},
        host=window + list(host),
    )


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [("a", 10 * MS, 10 * MS), ("b", 15 * MS, 10 * MS), ("c", 95 * MS, 10 * MS), ("d", -5 * MS, 10 * MS)]
    s = T.summarize(_events(ops))
    # [10,25) + [95,100) + [0,5) = 15 + 5 + 5 ms
    assert s.busy_s == pytest.approx(0.025)
    assert s.window_s == pytest.approx(0.1)
    assert s.idle_share == pytest.approx(75.0)
    assert dict(s.device_ops) == pytest.approx({"a": 0.01, "b": 0.01, "c": 0.005, "d": 0.005})


def test_union_matches_a_brute_force_count():
    rng = random.Random(0)
    ops = [("op", rng.randrange(0, 1000), rng.randrange(1, 50)) for _ in range(300)]
    covered = set()
    for _, s, d in ops:
        covered.update(range(max(s, 0), min(s + d, 1000)))
    ev = T.Events({"/device:TPU:0": {T.OPS_LINE: ops}}, [("bench.window", 0, 1000)])
    assert T.summarize(ev).busy_s * 1e9 == pytest.approx(len(covered))


def test_launch_time_is_busy_inside_launch_modules():
    ops = [("fusion", 10 * MS, 2 * MS), ("fusion", 13 * MS, 1 * MS), ("copy", 40 * MS, 3 * MS)]
    modules = [("jit__unknown(7)", 9 * MS, 6 * MS), ("jit_other(1)", 39 * MS, 5 * MS)]
    s = T.summarize(_events(ops, modules))
    assert s.launches == 1
    assert s.launch_busy_s == pytest.approx(0.003)


def test_idle_gaps_are_named_by_the_host_span_that_covers_them():
    ops = [("f", 20 * MS, 10 * MS)]
    host = [("bench.submit_batch", 0, 25 * MS), ("bench.submit_batch", 25 * MS, 50 * MS), ("bench.wait", 80 * MS, 10 * MS)]
    s = T.summarize(_events(ops, host=host))
    gaps = dict(s.idle_gaps)
    # idle: [0,20) and [30,100); submit_batch covers [0,20) and [30,75)
    assert gaps["bench.submit_batch"] == pytest.approx(0.065)
    assert gaps["bench.wait"] == pytest.approx(0.010)
    assert gaps["host.other"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(0.1 - s.busy_s)


def test_breakdown_lists_at_most_ten():
    ops = [(f"op{i}", i * MS, MS // 2) for i in range(30)]
    s = T.summarize(_events(ops))
    assert len(s.device_ops) == 10
    assert [v for _, v in s.device_ops] == sorted((v for _, v in s.device_ops), reverse=True)


def test_exactly_one_window_span_is_required():
    ev = _events([])
    ev.host.append(("bench.window", 0, 1))
    with pytest.raises(ValueError):
        T.summarize(ev)


RECORDED = Path(__file__).parent / "data" / "trace_v5e_closed.json"


def _sweep_busy(intervals, lo, hi):
    """Covered nanoseconds of [lo, hi), by walking sorted edges."""
    edges = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            edges += [(s, 1), (e, -1)]
    covered, depth, last = 0, 0, None
    for t, step in sorted(edges):
        if depth > 0:
            covered += t - last
        depth += step
        last = t
    return covered


def test_recorded_v5e_trace():
    events = T.Events.from_json(json.loads(RECORDED.read_text()))
    s = T.summarize(events)
    lo, hi = T.window_of(events)
    ops = events.devices["/device:TPU:0"][T.OPS_LINE]
    modules = events.devices["/device:TPU:0"][T.MODULES_LINE]
    assert s.n_devices == 1
    assert s.window_s == pytest.approx((hi - lo) / 1e9)
    assert s.busy_s == pytest.approx(_sweep_busy([(o[1], o[1] + o[2]) for o in ops], lo, hi) / 1e9)
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(100 * (1 - s.busy_s / s.window_s))
    # two launches of the batched executor's program lie in the cut window
    assert s.launches == 2 == sum(1 for m in modules if m[0].startswith("jit__unknown"))
    inside = sum(
        _sweep_busy([(o[1], o[1] + o[2]) for o in ops], m[1], m[1] + m[2]) for m in modules
    )
    assert s.launch_busy_s == pytest.approx(inside / 1e9)
    assert len(s.device_ops) == 10 and s.device_ops[0][1] >= s.device_ops[-1][1] > 0
    gaps = dict(s.idle_gaps)
    assert set(gaps) <= {"bench.submit_batch", "host.other"}
    assert sum(gaps.values()) == pytest.approx(s.window_s - s.busy_s)

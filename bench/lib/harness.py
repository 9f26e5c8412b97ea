"""One run of one cell: build, warm up, measure, check, report.

The window drives the program's served entries with its own defaults: ``ServeEngine.scheduler(...).offer``/``pump`` for an open loop,
``ServeEngine.submit_batch`` for a closed loop.  The harness sets only
what a deployment sets (the LM configuration the engine is built for,
the token-table node budget, and the scheduler settings that the cell's
traffic file names).  Nothing compiles inside the window: every shape is
warmed during set-up, and compilations inside the window are counted
and printed.
"""

from __future__ import annotations

import contextlib
import gc
import math
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional, Tuple

from bench.lib import check, spec, stats
from bench.lib import traffic as traffic_lib
from bench.lib.launch_work import LaunchWork, device_bytes, launch_work

# A traced run measures a shorter window: the profiler records every
# device op, and a stream cell launches hundreds of times a second.
TRACE_WINDOW_S = 4.0
START_LEAD_S = 0.02  # the first request falls due this long after the loop starts
SPIN_S = 0.0005  # closer than this to the next event, the loop spins instead of sleeping


@dataclass
class Record:
    """What a run measured; the metric readers read it."""

    workload: str
    loop: str
    setup_s: float
    window_s: float = 0.0
    attempted: int = 0
    failed: int = 0
    answered: int = 0  # results that are verdicts (not failed), in the window
    latency_s: List[float] = field(default_factory=list)  # open loop, due -> verdict
    queue_delay_s: List[float] = field(default_factory=list)  # open loop
    lateness_s: List[float] = field(default_factory=list)  # open loop, due -> offer
    offer_s: float = 0.0  # harness time inside offer(), summed
    offers: int = 0
    counts: Dict[str, int] = field(default_factory=dict)  # engine counters over the window
    routed: Dict[str, int] = field(default_factory=dict)  # drains by route over the window
    phases: Dict[str, Dict[str, int]] = field(default_factory=dict)  # obs profiler, traced closed runs
    rows_encoded: int = 0
    launches: List[LaunchWork] = field(default_factory=list)  # closed loop, in the window
    trace: Any = None  # bench.lib.trace.Summary of a traced run
    peaks: Optional[Dict[str, float]] = None
    compiles_in_window: Dict[str, int] = field(default_factory=dict)
    gc_in_window: Dict[str, Any] = field(default_factory=dict)
    submit_s: List[float] = field(default_factory=list)  # closed loop, each submission's wall time


class CompileCounter:
    """Counts JAX's tracing, compilation and cache reads as they happen."""

    EVENTS = {
        "/jax/core/compile/jaxpr_trace_duration": "traces",
        "/jax/core/compile/backend_compile_duration": "compiles",
        "/jax/compilation_cache/cache_retrieval_time_sec": "cache_reads",
    }

    def __init__(self):
        import jax

        self.counts = {v: 0 for v in self.EVENTS.values()}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_: Any) -> None:
        key = self.EVENTS.get(event)
        if key is not None:
            self.counts[key] += 1

    def snapshot(self) -> Dict[str, int]:
        return dict(self.counts)


class GcPauses:
    """The interpreter's garbage collections while it is armed: count and
    seconds per generation."""

    def __init__(self):
        self.pauses: List[Tuple[int, float]] = []
        self._t = 0.0

    def _on(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    @contextlib.contextmanager
    def armed(self):
        gc.callbacks.append(self._on)
        try:
            yield self
        finally:
            gc.callbacks.remove(self._on)

    def summary(self) -> Dict[str, Any]:
        out: Dict[str, Any] = {}
        for gen in sorted({g for g, _ in self.pauses}):
            ds = [d for g, d in self.pauses if g == gen]
            out[f"gen{gen}"] = {"count": len(ds), "total_ms": 1e3 * sum(ds), "max_ms": 1e3 * max(ds)}
        return out


def _annotate(enabled: bool) -> Callable[[str], Any]:
    if not enabled:
        return lambda name: contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation


ENGINE_COUNTS = ("batch_validated", "fallback_validated", "undecided", "oversize", "unroll_overflow")


def _engine_counts(engine) -> Dict[str, int]:
    return {k: int(getattr(engine.stats, k)) for k in ENGINE_COUNTS}


def _delta(after: Dict[str, int], before: Dict[str, int]) -> Dict[str, int]:
    return {k: after[k] - before.get(k, 0) for k in after}


def build_engine(config: Dict[str, Any], schemas: Dict[str, Any]):
    """The engine as a deployment builds it: for its LM (no weights:
    admission never reads them) over the program's default registry,
    with the endpoint schemas registered."""
    from repro.configs import get_config
    from repro.serve.engine import ServeConfig, ServeEngine

    return ServeEngine(
        get_config(config["lm"]),
        None,
        ServeConfig(admission_max_nodes=int(config["admission_max_nodes"])),
        endpoint_schemas=schemas,
    )


def _pow2_upto(n: int) -> Tuple[int, ...]:
    out, b = [], 1
    while b <= n:
        out.append(b)
        b *= 2
    return tuple(out)


def _wait_until(t: float, clock: Callable[[], float]) -> None:
    dt = t - clock()
    if dt > SPIN_S:
        time.sleep(dt - SPIN_S)
    while clock() < t:
        pass


def open_loop(
    engine, sched, requests: traffic_lib.Requests, seconds: float, rec: Record, annotate
) -> List[Optional[str]]:
    """Offer each request when it falls due, drain lanes as they come due,
    and wait for every verdict.  Returns each request's outcome value."""
    clock = engine.registry.clock
    n = len(requests.texts)
    due, texts, endpoints = requests.due_s, requests.texts, requests.endpoints
    tickets: List[Any] = [None] * n
    offered_at = [0.0] * n
    perf = time.perf_counter
    t0 = clock() + START_LEAD_S
    _wait_until(t0, clock)
    i = 0
    with annotate("bench.window"):
        while i < n or sched.depth():
            now = clock()
            while i < n and t0 + due[i] <= now:
                with annotate("bench.offer"):
                    a = perf()
                    tickets[i] = sched.offer(endpoints[i], texts[i])
                    rec.offer_s += perf() - a
                offered_at[i] = tickets[i].arrival
                i += 1
                now = clock()
            with annotate("bench.pump"):
                sched.pump()
            nxt = sched.next_fire_s()
            if i < n:
                nxt = t0 + due[i] if nxt is None else min(nxt, t0 + due[i])
            if nxt is not None and nxt > clock():
                with annotate("bench.wait"):
                    _wait_until(nxt, clock)
    rec.window_s = seconds
    rec.offers = n
    outcomes: List[Optional[str]] = []
    for i, t in enumerate(tickets):
        if t is None or not t.done:
            outcomes.append(None)
            rec.latency_s.append(math.inf)
            continue
        outcome = t.result.outcome.value
        outcomes.append(outcome)
        done_at = t.arrival + t.latency_s
        rec.latency_s.append(math.inf if outcome in check.FAILED else done_at - (t0 + due[i]))
        rec.queue_delay_s.append(t.queue_delay_s)
        rec.lateness_s.append(offered_at[i] - (t0 + due[i]))
    return outcomes


def closed_loop(engine, pool: List[traffic_lib.Requests], seconds: float, rec: Record, annotate, on_submit) -> List[Tuple[int, List[str]]]:
    """Submit the pool's batches back to back until ``seconds`` have gone
    by; the window ends when the last submission returns.  Returns
    (pool index, outcome values) per submission."""
    pairs = [list(zip(sub.endpoints, sub.texts)) for sub in pool]
    clock = time.perf_counter
    done: List[Tuple[int, List[str]]] = []
    k = 0
    with annotate("bench.window"):
        t0 = clock()
        while True:
            a = clock()
            with annotate("bench.submit_batch"):
                results = engine.submit_batch(pairs[k % len(pairs)])
            rec.submit_s.append(clock() - a)
            done.append((k % len(pairs), [r.outcome.value for r in results]))
            on_submit(k % len(pairs))
            k += 1
            if clock() - t0 >= seconds:
                break
        rec.window_s = clock() - t0
    return done


def _launch_plan(engine, sub: traffic_lib.Requests, max_nodes: int, doc_bytes: int) -> List[LaunchWork]:
    """The launches ``submit_batch(sub)`` makes: one per link group with
    rows aboard, padded to a power of two (guard rejects and requests
    that are not JSON never reach a launch)."""
    import json

    reg = engine.registry
    rows: Dict[str, int] = {}
    tapes: Dict[str, Any] = {}
    for endpoint, text in zip(sub.endpoints, sub.texts):
        try:
            json.loads(text)
        except json.JSONDecodeError:
            continue
        group = reg.group_of(endpoint)
        if group is None:
            continue
        rows[group.label] = rows.get(group.label, 0) + 1
        tapes[group.label] = group.tape
    out = []
    for label, n in rows.items():
        tape = tapes[label]
        bucket = 1 << (n - 1).bit_length() if n > 1 else 1
        out.append(
            launch_work(bucket, max_nodes, doc_bytes, tape_bytes(tape), int(tape.max_hash_run), int(tape.max_rows_per_loc))
        )
    return out


def tape_bytes(tape) -> int:
    """The linked tape's arrays, as the device holds them."""
    return sum(device_bytes(v) for v in vars(tape).values() if hasattr(v, "dtype") and hasattr(v, "shape"))


def doc_bytes(max_nodes: int) -> int:
    """One document's row of every token-table column, on the device."""
    from repro.data.doc_table import encode_batch

    return sum(device_bytes(v) for v in encode_batch([None], max_nodes=max_nodes).columns().values())


@contextlib.contextmanager
def _profiler_trace(enabled: bool):
    """``jax.profiler`` tracing into a scratch directory under TMPDIR;
    yields a function that returns the trace's plain events."""
    if not enabled:
        yield None
        return
    import jax

    from bench.lib import trace as trace_lib

    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # no event per Python call
        options.host_tracer_level = 1  # annotations, not the runtime's own spans
        jax.profiler.start_trace(logdir, profiler_options=options)
        stopped = False

        def events():
            nonlocal stopped
            if not stopped:
                jax.profiler.stop_trace()
                stopped = True
            return trace_lib.load(logdir)

        yield events
        if not stopped:
            jax.profiler.stop_trace()
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def run_cell(
    workload: str,
    *,
    seed: int,
    seconds: float,
    trace: bool,
    t_start: float,
    device: Dict[str, Any],
    log: Callable[[str], None] = print,
    root: Path = spec.ROOT,
) -> Tuple[Dict[str, Any], "check.Comparison"]:
    """Everything between the device check and the result line, for the
    benchmark whose ``BENCHMARK.json`` and ``bench/`` lie under ``root``."""
    import jax

    counter = CompileCounter()
    gc_pauses = GcPauses()
    annotate = _annotate(trace)

    bench = spec.load_benchmark(root / "BENCHMARK.json")
    cell = spec.cell(bench, workload)
    config = spec.config(bench, cell["config"], root)
    tr = spec.traffic(cell["traffic"], root / "bench")
    source = spec.documents(config["documents"], root / "bench").build(config)
    engine = build_engine(config, source.schemas)
    window = min(seconds, TRACE_WINDOW_S) if trace else seconds
    rec = Record(workload=workload, loop=tr["loop"], setup_s=0.0)
    if device.get("kind") is not None and device["platform"] == "tpu":
        from bench.lib.peaks import peaks

        rec.peaks = peaks(device["kind"])

    if tr["loop"] == "open":
        with annotate("bench.generate"):
            requests = traffic_lib.generate(
                source, tr, n=traffic_lib.open_loop_count(tr, window), seed=seed, seconds=window
            )
        settings = dict(tr.get("scheduler", {}))
        settings.setdefault("warm_shapes", _pow2_upto(int(settings.get("max_batch", 256))))
        sched = engine.scheduler(**settings)
        before, routed_before = _engine_counts(engine), dict(sched.stats.routed)
        compiled_before = counter.snapshot()
        rec.setup_s = time.perf_counter() - t_start
        with _profiler_trace(trace) as events, gc_pauses.armed():
            outcomes = open_loop(engine, sched, requests, window, rec, annotate)
            summary_events = events() if events else None
        rec.counts = _delta(_engine_counts(engine), before)
        rec.routed = _delta(dict(sched.stats.routed), routed_before)
        all_requests = [(requests.endpoints, requests.texts, outcomes)]
    else:
        with annotate("bench.generate"):
            pool = traffic_lib.pool(source, tr, seed=seed)
        for sub in pool:  # compiles every launch shape the pool uses
            engine.submit_batch(list(zip(sub.endpoints, sub.texts)))
        max_nodes = int(config["admission_max_nodes"])
        row_bytes = doc_bytes(max_nodes)
        plans = [_launch_plan(engine, sub, max_nodes, row_bytes) for sub in pool]
        before = _engine_counts(engine)
        compiled_before = counter.snapshot()
        prof = None
        if trace:
            from repro.obs.profile import Profiler, set_profiler

            prof = Profiler()
            set_profiler(prof)
        rec.setup_s = time.perf_counter() - t_start
        try:
            with _profiler_trace(trace) as events, gc_pauses.armed():
                done = closed_loop(engine, pool, window, rec, annotate, lambda k: rec.launches.extend(plans[k]))
                summary_events = events() if events else None
        finally:
            if prof is not None:
                from repro.obs.profile import set_profiler

                set_profiler(None)
                rec.phases = {k: v.as_dict() for k, v in prof.stats().items()}
        rec.counts = _delta(_engine_counts(engine), before)
        all_requests = [(pool[k].endpoints, pool[k].texts, outs) for k, outs in done]
    rec.compiles_in_window = _delta(counter.snapshot(), compiled_before)
    rec.gc_in_window = gc_pauses.summary()
    rec.rows_encoded = sum(rec.counts[k] for k in ("batch_validated", "undecided", "oversize", "unroll_overflow"))
    if summary_events is not None:
        from bench.lib import trace as trace_lib

        rec.trace = trace_lib.summarize(summary_events)

    memory_peak = None
    stats_now = jax.devices()[0].memory_stats()
    if stats_now:
        memory_peak = stats_now.get("peak_bytes_in_use")
    del engine

    # the reference, once the window has closed and its numbers are read
    ref = check.Reference(source.schemas, config["guard"])
    expected: Dict[Tuple[str, str], str] = {}
    want, got, eps = [], [], []
    for endpoints, texts, outs in all_requests:
        for ep, text, out in zip(endpoints, texts, outs):
            key = (ep, text)
            if key not in expected:
                expected[key] = ref.expect(ep, text)
            want.append(expected[key])
            got.append(out)
            eps.append(ep)
    comparison = check.compare(want, got, eps)
    rec.attempted = len(got)
    rec.failed = comparison.failed + comparison.missing
    rec.answered = comparison.compared
    return _report(bench, cell, rec, device, memory_peak, trace, log, root / "bench"), comparison


def _report(bench, cell, rec: Record, device, memory_peak, trace: bool, log, bench_dir: Path) -> Dict[str, Any]:
    kind = "per_layer" if trace else "end_to_end"
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in spec.metrics_of(bench, cell["name"], kind):
        value = spec.reader(m["name"], bench_dir).read(rec)
        if value is None:
            log(f"metric {m['name']} found nothing to read")
            continue
        metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    log(f"window_s {rec.window_s!r} attempted {rec.attempted} failed {rec.failed}")
    log(f"compiles_in_window {rec.compiles_in_window}")
    log(f"gc_in_window {rec.gc_in_window}")
    log(f"engine_counts {rec.counts}")
    if rec.loop == "open":
        log(f"drains_by_route {rec.routed}")
        late = sorted(rec.lateness_s)
        if late:
            log(
                f"generator_lateness_ms p50 {1e3 * stats.percentile(late, 50)!r} "
                f"p99 {1e3 * stats.percentile(late, 99)!r} max {1e3 * late[-1]!r}"
            )
    else:
        log(f"launches_in_window {len(rec.launches)}")
        if rec.submit_s:
            subs = sorted(rec.submit_s)
            med = stats.percentile(subs, 50)
            log(
                f"submit_ms n {len(subs)} min {1e3 * subs[0]!r} p50 {1e3 * med!r} max {1e3 * subs[-1]!r} "
                f"over_1.5x_p50 {sum(1 for x in subs if x > 1.5 * med)}"
            )
        if rec.launches and rec.peaks is not None:
            bounds = sorted({w.bound(rec.peaks) for w in rec.launches})
            log(
                f"launch_work bytes {sum(w.bytes for w in rec.launches)} ops {sum(w.ops for w in rec.launches)} "
                f"least_s {sum(w.least_s(rec.peaks) for w in rec.launches)!r} bound {'/'.join(bounds)}"
            )
    dev = {
        "platform": device["platform"],
        "kind": device["kind"],
        "count": device["count"],
        "memory_peak_bytes": memory_peak,
    }
    line: Dict[str, Any] = {"correct": None, "attempted": rec.attempted, "failed": rec.failed, "metrics": metrics, "device": dev}
    if trace and rec.trace is not None:
        dev["busy_s"] = rec.trace.busy_s
        dev["window_s"] = rec.trace.window_s
        log(f"trace launches {rec.trace.launches} launch_busy_s {rec.trace.launch_busy_s!r}")
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s in rec.trace.device_ops],
            "idle_gaps": [[n, s] for n, s in rec.trace.idle_gaps],
        }
    return line

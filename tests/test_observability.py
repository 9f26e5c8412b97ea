"""Serving telemetry: metric registry, trace spans, stats reconciliation
(DESIGN.md §12).

The reconciliation invariant under test: every document received via
``submit``/``submit_batch``/``admit_mixed_ex`` -- including under
injected faults -- lands in exactly one outcome counter, and per-
endpoint latency histogram totals equal request counts.
"""

import json

import pytest

from repro.core.outcomes import ValidationOutcome
from repro.obs.metrics import (
    DEFAULT_LATENCY_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
)
from repro.obs.trace import Tracer, set_tracer, span, trace_point, tracer_armed
from repro.registry import SchemaRegistry
from repro.serve.faults import FaultInjector

SCHEMA = {
    "type": "object",
    "required": ["a"],
    "properties": {"a": {"type": "integer", "minimum": 0}},
    "additionalProperties": False,
}


# ---------------------------------------------------------------------------
# metric registry
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_counter_gauge_histogram(self):
        c = Counter()
        c.inc()
        c.inc(4)
        assert c.value == 5
        c.reset()
        assert c.value == 0
        g = Gauge()
        g.set(3)
        g.inc(-1)
        assert g.value == 2
        h = Histogram((1.0, 10.0))
        for v in (0.5, 5.0, 50.0):
            h.observe(v)
        assert h.count == 3 and h.sum == 55.5
        assert h.cumulative() == [(1.0, 1), (10.0, 2), (float("inf"), 3)]

    def test_observe_many_is_bulk(self):
        h = Histogram((1.0,))
        h.observe_many(0.5, 1000)
        assert h.count == 1000 and h.sum == 500.0
        assert h.cumulative()[0] == (1.0, 1000)

    def test_registry_families_and_labels(self):
        reg = MetricRegistry()
        a = reg.counter("requests_total", "reqs", endpoint="x")
        b = reg.counter("requests_total", endpoint="y")
        assert a is not b
        assert reg.counter("requests_total", endpoint="x") is a  # cached
        a.inc(2)
        b.inc(3)
        children = dict(reg.family_children("requests_total"))
        assert len(children) == 2
        with pytest.raises(ValueError):
            reg.gauge("requests_total")  # kind mismatch

    def test_render_prometheus_format(self):
        reg = MetricRegistry()
        reg.counter("reqs_total", "requests", endpoint="a").inc(3)
        reg.gauge("temp", "temperature").set(1.5)
        h = reg.histogram("lat_seconds", "latency", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(5.0)
        text = reg.render_prometheus()
        assert "# TYPE reqs_total counter" in text
        assert 'reqs_total{endpoint="a"} 3' in text
        assert "# TYPE lat_seconds histogram" in text
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text
        assert "lat_seconds_sum 5.05" in text

    def test_snapshot_and_reset(self):
        reg = MetricRegistry()
        reg.counter("a_total").inc(7)
        reg.histogram("h", buckets=(1.0,)).observe(0.5)
        snap = reg.snapshot()
        assert snap["a_total"]["children"][0]["value"] == 7
        assert snap["h"]["children"][0]["count"] == 1
        reg.reset()
        assert reg.counter("a_total").value == 0
        assert reg.snapshot()["h"]["children"][0]["count"] == 0

    def test_default_latency_buckets_are_log_spaced(self):
        e = DEFAULT_LATENCY_BUCKETS
        assert len(e) == 13 and e[0] == 1e-6
        for lo, hi in zip(e, e[1:]):
            assert hi / lo == pytest.approx(4.0)


# ---------------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------------


class TestTracer:
    def test_disarmed_is_noop(self):
        assert not tracer_armed()
        with span("anything", x=1):
            trace_point("p")  # must not raise, must not record

    def test_spans_record_nesting_and_duration(self):
        with Tracer() as tr:
            with span("outer", label="a"):
                with span("inner"):
                    pass
            trace_point("mark", n=3)
        assert not tracer_armed()  # disarmed on exit
        spans = tr.recent()
        names = [s.name for s in spans]
        # inner closes before outer; the point is instantaneous
        assert names == ["inner", "outer", "mark"]
        by = {s.name: s for s in spans}
        assert by["outer"].depth == 0 and by["inner"].depth == 1
        assert by["outer"].dur_ns >= by["inner"].dur_ns >= 0
        assert by["outer"].attrs == {"label": "a"}
        # point events carry the -1 duration sentinel
        assert by["mark"].attrs == {"n": 3} and by["mark"].dur_ns == -1
        assert by["mark"].dur_us == -1.0

    def test_ring_buffer_keeps_newest(self):
        with Tracer(capacity=4) as tr:
            for i in range(10):
                with span(f"s{i}"):
                    pass
        assert tr.recorded == 10
        assert [s.name for s in tr.recent()] == ["s6", "s7", "s8", "s9"]

    def test_nested_arming_restores_previous(self):
        outer = Tracer()
        prev = set_tracer(outer)
        try:
            with Tracer() as inner:
                with span("x"):
                    pass
            assert [s.name for s in inner.recent()] == ["x"]
            assert outer.recorded == 0  # inner shadowed outer
            with span("y"):
                pass
            assert [s.name for s in outer.recent()] == ["y"]  # restored
        finally:
            set_tracer(prev)

    def test_serving_path_emits_expected_spans(self):
        reg = SchemaRegistry(use_pallas=False)
        with Tracer(capacity=256) as tr:
            reg.register("ep", SCHEMA)
            reg.admit_mixed_ex([{"a": 1}, {"a": -1}], ["ep", "ep"])
        names = {s.name for s in tr.recent()}
        assert "registry.relink" in names
        assert "admit.guard" in names
        assert "admit.encode" in names
        assert "executor.launch" in names


# ---------------------------------------------------------------------------
# registry-backed stats + reconciliation
# ---------------------------------------------------------------------------


def _engine():
    import jax

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = get_config("granite-3-8b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return ServeEngine(
        cfg, params, ServeConfig(batch_slots=2, max_len=64, default_max_tokens=4)
    )


def _latency_total(engine):
    children = engine.registry.metrics.family_children("serve_request_seconds")
    return sum(h.count for h in children.values())


class TestStatsReconciliation:
    @pytest.fixture(scope="class")
    def engine(self):
        return _engine()

    def test_outcomes_prepopulated(self, engine):
        outcomes = engine.stats.outcomes
        assert set(outcomes) == {o.value for o in ValidationOutcome}

    def test_every_submit_lands_in_one_outcome_and_one_observation(self, engine):
        engine.register_endpoint("ep", SCHEMA)
        base_recv = engine.stats.received
        base_lat = _latency_total(engine)
        engine.submit(json.dumps({"a": 1}), "ep")  # admitted
        engine.submit(json.dumps({"a": -1}), "ep")  # invalid
        engine.submit("{broken", "ep")  # guard (parse)
        engine.submit("{}", "nosuch")  # guard (unknown endpoint)
        assert engine.stats.received == base_recv + 4
        assert engine.stats.received == sum(engine.stats.outcomes.values())
        assert _latency_total(engine) == base_lat + 4

    def test_submit_batch_reconciles_under_faults(self, engine):
        engine.register_endpoint("ep", SCHEMA)
        reqs = []
        for i in range(24):
            if i % 6 == 5:
                reqs.append(("ep", "{broken"))
            else:
                reqs.append(("ep", json.dumps({"a": i - 4})))
        base_recv = engine.stats.received
        base_lat = _latency_total(engine)
        inj = FaultInjector(seed=7).rate("encode", 0.2).rate("launch", 0.05)
        with inj:
            results = engine.submit_batch(reqs)
        assert len(results) == 24
        assert engine.stats.received == base_recv + 24
        assert engine.stats.received == sum(engine.stats.outcomes.values())
        # histogram totals == request counts (one observation per request)
        assert _latency_total(engine) == base_lat + 24

    def test_explain_true_observes_once_per_request(self, engine):
        """The extra explain launch must not add latency observations:
        exactly one serve_request_seconds entry per received request."""
        engine.register_endpoint("ep", SCHEMA)
        base_recv = engine.stats.received
        base_lat = _latency_total(engine)
        reqs = [("ep", json.dumps({"a": i - 3})) for i in range(8)]
        reqs.append(("ep", "{broken"))  # guard reject rides along
        results = engine.submit_batch(reqs, explain=True)
        assert len(results) == 9
        assert any("schema" in (r.error or "") or r.error for r in results)
        assert engine.stats.received == base_recv + 9
        assert _latency_total(engine) == base_lat + 9
        # single-submit explain path observes exactly once too
        engine.submit(json.dumps({"a": -1}), "ep", explain=True)
        assert _latency_total(engine) == base_lat + 10

    def test_bisect_retries_observe_once_per_request(self, engine):
        """Launch faults trigger isolated-bisect relaunches; the retried
        launches must not multiply latency observations per request."""
        engine.register_endpoint("ep", SCHEMA)
        reqs = [("ep", json.dumps({"a": i - 3})) for i in range(16)]
        base_recv = engine.stats.received
        base_lat = _latency_total(engine)
        inj = FaultInjector(seed=13).rate("launch", 0.3)
        with inj:
            results = engine.submit_batch(reqs)
        # the bisection actually relaunched (initial launch + retries)
        assert inj.fired.get("launch", 0) > 1
        assert len(results) == 16
        assert engine.stats.received == base_recv + 16
        assert _latency_total(engine) == base_lat + 16

    def test_admit_mixed_ex_reconciles_under_faults(self):
        reg = SchemaRegistry(use_pallas=False)
        reg.register("ep", SCHEMA)
        docs = [{"a": i - 8} for i in range(32)] + [{"a": None}, {}]
        inj = FaultInjector(seed=3).rate("encode", 0.25).rate("fallback", 0.5)
        with inj:
            verdicts, counts = reg.admit_mixed_ex(docs, ["ep"] * len(docs))
        assert len(verdicts) == len(docs)
        total = (
            counts.batch_validated
            + counts.fallback_validated
            + counts.rejected_guard
            + counts.error_isolated
            + counts.timed_out
            + counts.breaker_open
        )
        assert total == len(docs)

    def test_snapshot_and_reset(self, engine):
        engine.submit(json.dumps({"a": 1}), "ep")
        snap = engine.stats.snapshot()
        assert snap["received"] > 0
        assert snap["outcomes"] == engine.stats.outcomes
        assert "by_endpoint" in snap and "fallback_reasons" in snap
        engine.stats.reset()
        assert engine.stats.received == 0
        assert sum(engine.stats.outcomes.values()) == 0
        assert all(
            v == 0 for per in engine.stats.by_endpoint.values() for v in per.values()
        )
        # registration-time info survives traffic-counter resets
        assert engine.stats.fallback_reasons == snap["fallback_reasons"]

    def test_attribute_compat(self, engine):
        # the historical mutation idioms still work through the facade
        engine.stats.decode_steps += 3
        assert engine.stats.snapshot()["decode_steps"] >= 3
        engine.stats.validation_seconds += 0.25
        assert engine.stats.validation_seconds >= 0.25

    def test_pipeline_stats_reconcile(self):
        from repro.data.pipeline import AdmissionController

        ctrl = AdmissionController(SCHEMA)
        ctrl.admit_ex([{"a": 1}, {"a": -1}, {"a": "x"}, {}])
        s = ctrl.stats
        assert s.seen == 4
        assert s.admitted + s.rejected == s.seen
        snap = s.snapshot()
        assert snap["seen"] == 4
        s.reset()
        assert s.seen == 0
        # shared registry: pipeline counters render alongside executor's
        text = ctrl.registry.metrics.render_prometheus()
        assert "pipeline_seen_total" in text
        assert "executor_launches_total" in text


class TestServingMetricsSurface:
    @pytest.fixture(scope="class")
    def engine(self):
        e = _engine()
        e.register_endpoint("ep", SCHEMA)
        e.submit(json.dumps({"a": 1}), "ep")
        e.submit_batch([("ep", json.dumps({"a": 2}))] * 3)
        return e

    def test_executor_counters(self, engine):
        m = engine.registry.metrics
        assert m.counter("executor_launches_total").value > 0
        assert m.counter("executor_recompiles_total").value > 0
        assert m.counter("executor_launch_seconds_total").value > 0

    def test_breaker_gauge(self, engine):
        text = engine.render_metrics()
        assert 'breaker_state{endpoint="ep"} 0' in text

    def test_swap_counters(self, engine):
        m = engine.registry.metrics
        ok = m.counter("registry_swap_total", result="ok").value
        assert ok >= 2
        with pytest.raises(Exception):
            engine.registry.register("bad", {"type": "string", "pattern": "("})
        assert m.counter("registry_swap_total", result="failed").value >= 1

    def test_endpoint_stats_tape_shape(self, engine):
        per = engine.endpoint_stats()["ep"]
        for key in ("a_hat", "k", "horizon", "n_circuits", "n_frontier",
                    "unroll_depth"):
            assert key in per
        assert per["a_hat"] >= 1 and per["horizon"] >= 1
        assert per["batchable"] is True

    def test_prometheus_and_json_export(self, engine):
        text = engine.render_metrics()
        assert "serve_received_total" in text
        assert "serve_request_seconds_bucket" in text
        assert 'serve_outcomes_total{outcome="admitted"}' in text
        snap = engine.metrics_snapshot()
        assert json.dumps(snap)  # JSON-serializable
        assert "serve_received_total" in snap

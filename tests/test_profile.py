"""Cost attribution, event log, and SLO tracking (DESIGN.md §12-13).

Three layers under test: the span aggregate of :mod:`repro.obs.trace`
(disarmed is one None check, armed attribution is nesting-aware and
double-count-free), the sampled :class:`EventLog` ring, and the
SLO/burn-rate math over the serving latency histograms -- plus the
tracer-armed smoke test over the real admission path that CI runs in
tier-1.
"""

import io
import json
import time

import pytest

from repro.obs.events import EventLog
from repro.obs.metrics import Histogram
from repro.obs.trace import (
    Tracer,
    set_tracer,
    span,
    tracer_armed,
)
from repro.obs.slo import SLObjective, SLOTracker, good_count, slo_status
from repro.registry import SchemaRegistry

SCHEMA = {
    "type": "object",
    "required": ["a"],
    "properties": {"a": {"type": "integer", "minimum": 0}},
    "additionalProperties": False,
}


# ---------------------------------------------------------------------------
# the tracer's aggregate
# ---------------------------------------------------------------------------


class TestProfiler:
    def test_disarmed_is_noop(self):
        assert not tracer_armed()
        with span("anything"):
            pass  # must not raise, must not record
        # the disarmed seam returns one shared object (no allocation)
        assert span("a") is span("b")

    def test_phases_accumulate(self):
        with Tracer() as prof:
            assert tracer_armed()
            for _ in range(3):
                with span("work"):
                    pass
        assert not tracer_armed()  # disarmed on exit
        stats = prof.stats()
        assert stats["work"].calls == 3
        assert stats["work"].total_ns >= stats["work"].self_ns >= 0

    def test_nested_phases_attribute_exclusive_time(self):
        with Tracer() as prof:
            with span("outer"):
                time.sleep(0.002)
                with span("inner"):
                    time.sleep(0.004)
        outer, inner = prof.stats()["outer"], prof.stats()["inner"]
        # inclusive: outer contains inner; exclusive: outer excludes it
        assert outer.total_ns >= inner.total_ns
        assert outer.self_ns == outer.total_ns - inner.total_ns
        # sum of exclusive time never double-counts
        assert prof.attributed_ns() == outer.self_ns + inner.self_ns
        assert prof.attributed_ns() <= outer.total_ns

    def test_coverage_and_report(self):
        with Tracer() as prof:
            t0 = time.perf_counter_ns()
            with span("a"):
                time.sleep(0.002)
            with span("b"):
                time.sleep(0.001)
            window = time.perf_counter_ns() - t0
        cov = prof.coverage(window)
        assert 0.5 < cov <= 1.0 + 1e-9  # sleeps dominate the window
        rep = prof.report(window)
        assert rep["coverage"] == pytest.approx(cov)
        assert list(rep["phases"]) == ["a", "b"]  # sorted by self_ns
        assert rep["phases"]["a"]["window_frac"] > rep["phases"]["b"]["window_frac"]
        assert rep["unattributed_ns"] == window - rep["attributed_ns"]
        assert prof.coverage(0) == 0.0
        prof.clear()
        assert prof.stats() == {} and prof.attributed_ns() == 0
        assert prof.recent() == []

    def test_nested_arming_restores_previous(self):
        outer = Tracer()
        prev = set_tracer(outer)
        try:
            with Tracer() as inner:
                with span("x"):
                    pass
            assert "x" in inner.stats() and "x" not in outer.stats()
            with span("y"):
                pass
            assert "y" in outer.stats()  # restored
        finally:
            set_tracer(prev)

    def test_admission_path_attribution_smoke(self):
        """The tier-1 armed smoke: a tracer over a real mixed admission
        must see the declared stage spans and explain most of the window."""
        reg = SchemaRegistry(use_pallas=False)
        reg.register("ep", SCHEMA)
        docs = [{"a": i} for i in range(24)] + [{"a": -1}, {}, {"a": "x"}]
        eps = ["ep"] * len(docs)
        reg.admit_mixed_ex(docs, eps)  # warm the jit outside the window
        with Tracer() as prof:
            t0 = time.perf_counter_ns()
            verdicts, _ = reg.admit_mixed_ex(docs, eps)
            window = time.perf_counter_ns() - t0
        assert len(verdicts) == len(docs)
        names = set(prof.stats())
        assert {"admit.guard", "admit.encode", "admit.launch",
                "admit.verdicts", "admit.fallback", "executor.launch"} <= names
        # warm small-batch coverage is noisier than the B=4096 bench bar
        assert prof.coverage(window) > 0.5


# ---------------------------------------------------------------------------
# event log
# ---------------------------------------------------------------------------


class TestEventLog:
    def test_validation(self):
        with pytest.raises(ValueError):
            EventLog(capacity=0)
        with pytest.raises(ValueError):
            EventLog(sample=1.5)

    def test_sampling_rate_is_exact_and_deterministic(self):
        ev = EventLog(capacity=16, sample=0.25)
        picks = [ev.want() for _ in range(100)]
        assert sum(picks) == 25  # exact long-run rate
        ev2 = EventLog(capacity=16, sample=0.25)
        assert picks == [ev2.want() for _ in range(100)]  # same schedule
        assert all(EventLog(sample=1.0).want() for _ in range(5))
        off = EventLog(sample=0.0)
        assert not any(off.want() for _ in range(50))

    def test_ring_keeps_newest(self):
        ev = EventLog(capacity=4)
        for i in range(10):
            ev.emit(n=i)
        assert ev.recorded == 10
        assert [r["n"] for r in ev.recent()] == [6, 7, 8, 9]
        assert all("ts" in r for r in ev.recent())

    def test_flush_jsonl_and_clear(self, tmp_path):
        ev = EventLog(capacity=8)
        ev.emit(endpoint="ep", outcome="admitted", ts=1.0)
        ev.emit(endpoint="ep", outcome="invalid", ts=2.0)
        dest = tmp_path / "events.jsonl"
        assert ev.flush(str(dest)) == 2
        lines = dest.read_text().splitlines()
        assert [json.loads(l)["outcome"] for l in lines] == [
            "admitted", "invalid"
        ]
        assert ev.recent() == [] and ev.flush(str(dest)) == 0
        # file-object destination appends without touching the filesystem
        buf = io.StringIO()
        ev.emit(n=1)
        assert ev.flush(buf) == 1
        assert json.loads(buf.getvalue())["n"] == 1


# ---------------------------------------------------------------------------
# SLO math
# ---------------------------------------------------------------------------


class TestSLO:
    def test_objective_validation(self):
        with pytest.raises(ValueError):
            SLObjective(objective_s=0.0)
        with pytest.raises(ValueError):
            SLObjective(target=1.0)
        assert SLObjective(target=0.99).error_budget == pytest.approx(0.01)

    def test_good_count_edges_and_interpolation(self):
        h = Histogram((0.1, 1.0))
        for v in (0.05, 0.5, 5.0):
            h.observe(v)
        assert good_count(h, 0.1) == pytest.approx(1.0)  # exact at an edge
        assert good_count(h, 1.0) == pytest.approx(2.0)
        # midway through the (0.1, 1.0] bucket: linear interpolation
        assert good_count(h, 0.55) == pytest.approx(1.5)
        # past the last finite edge: +Inf observations count as bad
        assert good_count(h, 100.0) == pytest.approx(2.0)

    def test_slo_status_burn_rate(self):
        h = Histogram((0.1, 1.0))
        for _ in range(98):
            h.observe(0.05)
        h.observe(0.5)
        h.observe(5.0)
        s = slo_status(h, SLObjective(objective_s=0.1, target=0.99))
        assert s["count"] == 100 and s["good"] == pytest.approx(98.0)
        assert s["good_ratio"] == pytest.approx(0.98)
        # 2% bad against a 1% budget: burning twice as fast as provisioned
        assert s["burn_rate"] == pytest.approx(2.0)
        # empty histogram: vacuously healthy
        empty = slo_status(Histogram((0.1,)), SLObjective())
        assert empty["good_ratio"] == 1.0 and empty["burn_rate"] == 0.0

    def test_tracker_windows_are_deltas(self):
        h = Histogram((0.1, 1.0))
        tr = SLOTracker(SLObjective(objective_s=0.1, target=0.9))
        for _ in range(10):
            h.observe(0.05)  # all good
        first = tr.update(h)
        assert first["window_count"] == 10
        assert first["window_burn_rate"] == pytest.approx(0.0)
        for _ in range(10):
            h.observe(5.0)  # all bad
        second = tr.update(h)
        assert second["window_count"] == 10
        assert second["window_good_ratio"] == pytest.approx(0.0)
        assert second["window_burn_rate"] == pytest.approx(10.0)  # 1/0.1
        # cumulative view still blends both windows
        assert second["good_ratio"] == pytest.approx(0.5)
        # idle window: no traffic, vacuously healthy
        third = tr.update(h)
        assert third["window_count"] == 0
        assert third["window_burn_rate"] == pytest.approx(0.0)


# ---------------------------------------------------------------------------
# engine integration: events + SLO surfaces
# ---------------------------------------------------------------------------


def _engine(**kw):
    import jax

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = get_config("granite-3-8b").reduced()
    params = Model(cfg).init(jax.random.PRNGKey(0))
    return ServeEngine(
        cfg,
        params,
        ServeConfig(batch_slots=2, max_len=64, default_max_tokens=4),
        **kw,
    )


class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def engine(self):
        e = _engine(events=EventLog(capacity=64))
        e.register_endpoint("ep", SCHEMA)
        return e

    def test_submit_emits_sampled_event(self, engine):
        engine.events.clear()
        engine.submit(json.dumps({"a": 1}), "ep")
        engine.submit(json.dumps({"a": -1}), "ep")
        engine.submit("{broken", "ep")
        kinds = [r["kind"] for r in engine.events.recent()]
        assert kinds == ["submit"] * 3
        by_outcome = {r["outcome"] for r in engine.events.recent()}
        assert {"admitted", "invalid", "rejected_guard"} <= by_outcome
        ok = engine.events.recent()[0]
        assert ok["endpoint"] == "ep" and ok["latency_s"] > 0
        assert "parse_s" in ok["stages"] and "validate_s" in ok["stages"]

    def test_submit_batch_emits_batch_events(self, engine):
        engine.events.clear()
        engine.submit_batch(
            [("ep", json.dumps({"a": i})) for i in range(4)]
            + [("ep", "{broken")]
        )
        records = engine.events.recent()
        assert len(records) == 5
        batch = [r for r in records if r["outcome"] != "rejected_guard"]
        assert len(batch) == 4
        assert len({r["batch_id"] for r in batch}) == 1
        assert all(r["stages"]["batch_rows"] == 4 for r in batch)
        guard = [r for r in records if r["outcome"] == "rejected_guard"]
        # true wall from batch entry to the parse reject (DESIGN.md §14
        # closed the historical 0.0-observation under-count)
        assert guard and guard[0]["latency_s"] > 0.0

    def test_flush_events(self, engine, tmp_path):
        engine.events.clear()
        engine.submit(json.dumps({"a": 1}), "ep")
        dest = tmp_path / "ev.jsonl"
        assert engine.flush_events(str(dest)) == 1
        assert json.loads(dest.read_text())["kind"] == "submit"
        # detached engine: flush is a no-op that reports 0
        engine2 = _engine()
        assert engine2.flush_events(str(dest)) == 0

    def test_slo_in_endpoint_stats_and_prometheus(self, engine):
        from repro.serve.engine import DEFAULT_SLO

        engine.submit(json.dumps({"a": 1}), "ep")
        per = engine.endpoint_stats()["ep"]
        slo = per["slo"]
        assert slo["objective_s"] == DEFAULT_SLO.objective_s
        assert 0.0 <= slo["good_ratio"] <= 1.0
        text = engine.render_metrics()
        assert 'serve_slo_good_ratio{endpoint="ep"}' in text
        assert 'serve_slo_burn_rate{endpoint="ep"}' in text

    def test_set_slo_overrides_default(self, engine):
        engine.set_slo("ep", SLObjective(objective_s=4.0, target=0.5))
        assert engine.slo_status("ep")["objective_s"] == 4.0
        assert engine.slo_status("ep")["target"] == 0.5

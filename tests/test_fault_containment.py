"""Chaos/differential suite for the fault-containment layer (DESIGN.md §11).

Every degradation invariant the serving stack promises is asserted here
under *injected*, seeded, deterministic faults:

- poison isolation: a batch with injected encode/launch faults returns
  ERROR_ISOLATED for exactly the poisoned rows and bit-identical
  verdicts for every other row, at batch sizes {64, 512, 4096};
- stats reconciliation: every received document lands in exactly one
  outcome class;
- the deadline-bounded fallback: depth bombs, step bombs, and
  backtracking-prone patterns return TIMED_OUT promptly;
- the circuit breaker trips and recovers deterministically (stub clock);
- hot-swap rollback: a failed registration never reaches serving.
"""

import json
import os
import time

import numpy as np
import pytest

from repro.core import (
    BreakerConfig,
    CircuitBreaker,
    DocumentDepthError,
    GuardLimits,
    ValidationBudget,
    ValidationOutcome,
    ValidationTimeout,
    Validator,
    compile_schema,
    resource_guard,
)
from repro.core.regex_opt import analyze_pattern
from repro.registry import RegistrationError, SchemaRegistry
from repro.serve.faults import FaultInjector, InjectedFault

SCHEMA = {
    "type": "object",
    "required": ["a"],
    "additionalProperties": False,
    "properties": {
        "a": {"type": "integer", "minimum": 0},
        "b": {"type": "string", "minLength": 1},
    },
}

OUTCOME_FIELDS = (
    "batch_validated",
    "fallback_validated",
    "rejected_guard",
    "error_isolated",
    "timed_out",
    "breaker_open",
)


def _docs(n, seed=0):
    """Deterministic valid/invalid mix for endpoint SCHEMA."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n):
        r = rng.integers(0, 4)
        if r == 0:
            out.append({"a": int(rng.integers(0, 100))})
        elif r == 1:
            out.append({"a": int(rng.integers(0, 100)), "b": "x" * int(rng.integers(1, 5))})
        elif r == 2:
            out.append({"a": -1})  # invalid: minimum
        else:
            out.append({"b": ""})  # invalid: required + minLength
    return out


def _sum_outcomes(counts):
    return sum(getattr(counts, f) for f in OUTCOME_FIELDS)


@pytest.fixture(scope="module")
def registry():
    reg = SchemaRegistry()
    reg.register("t", SCHEMA)
    return reg


@pytest.fixture(params=["interpreter", "codegen"])
def engine(request):
    """The sequential engine under the bounded fallback: the containment
    contract holds for both (DESIGN.md §11)."""
    return request.param


class Clock:
    """Deterministic injectable clock for breaker/deadline tests."""

    def __init__(self):
        self.t = 0.0

    def advance(self, dt):
        self.t += dt

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# Poison isolation (encode + launch) at {64, 512, 4096}
# ---------------------------------------------------------------------------


class TestPoisonIsolation:
    @pytest.mark.parametrize("B", [64, 512])
    def test_encode_poison_isolated(self, registry, B):
        self._check_point(registry, B, "encode")

    @pytest.mark.parametrize("B", [64, 512])
    def test_launch_poison_isolated(self, registry, B):
        self._check_point(registry, B, "launch")

    @pytest.mark.slow
    @pytest.mark.chaos
    @pytest.mark.parametrize("point", ["encode", "launch"])
    def test_poison_isolated_4096(self, registry, point):
        self._check_point(registry, 4096, point)

    @staticmethod
    def _check_point(registry, B, point):
        docs = _docs(B, seed=B)
        endpoints = ["t"] * B
        clean, clean_counts = registry.admit_mixed_ex(docs, endpoints)
        assert _sum_outcomes(clean_counts) == B
        poison = sorted({0, B // 3, B // 2, B - 1})
        inj = FaultInjector(seed=B).poison(point, *poison)
        with inj:
            got, counts = registry.admit_mixed_ex(docs, endpoints)
        assert inj.fired.get(point, 0) > 0
        assert _sum_outcomes(counts) == B
        assert counts.error_isolated == len(poison)
        for i in range(B):
            if i in poison:
                assert got[i].outcome is ValidationOutcome.ERROR_ISOLATED
                assert "injected" in got[i].reason
            else:
                # bit-identical to the poison-free run
                assert got[i].outcome is clean[i].outcome, i
                assert got[i].valid == clean[i].valid, i

    def test_rate_poison_is_deterministic(self, registry):
        docs = _docs(128, seed=9)
        endpoints = ["t"] * 128
        runs = []
        for _ in range(2):
            with FaultInjector(seed=3).rate("encode", 0.05) as inj:
                got, counts = registry.admit_mixed_ex(docs, endpoints)
            runs.append(([v.outcome for v in got], counts.error_isolated, dict(inj.fired)))
        assert runs[0] == runs[1]
        assert runs[0][1] > 0  # 5% of 128 rows should hit at least once

    def test_fallback_fault_isolated(self, registry):
        # tiny encode budget forces every row onto the sequential
        # fallback; poisoned rows are isolated there too
        docs = _docs(32, seed=5)
        endpoints = ["t"] * 32
        clean, _ = registry.admit_mixed_ex(docs, endpoints, max_nodes=1)
        with FaultInjector().poison("fallback", 7, 20):
            got, counts = registry.admit_mixed_ex(docs, endpoints, max_nodes=1)
        assert counts.batch_validated == 0
        assert counts.error_isolated == 2
        assert _sum_outcomes(counts) == 32
        for i in range(32):
            if i in (7, 20):
                assert got[i].outcome is ValidationOutcome.ERROR_ISOLATED
            else:
                assert (got[i].outcome, got[i].valid) == (clean[i].outcome, clean[i].valid)


# ---------------------------------------------------------------------------
# Launch containment: what is bisected and what propagates
# ---------------------------------------------------------------------------


class _FailingLaunch:
    """Stands in for a validator's jitted launch; raises on chosen batch
    sizes and otherwise calls the real launch."""

    def __init__(self, launch, fail_batches):
        self.launch = launch
        self.fail_batches = set(fail_batches)

    def __call__(self, cols, ids):
        if cols["node_type"].shape[0] in self.fail_batches:
            raise RuntimeError("Mosaic refused the kernel")
        return self.launch(cols, ids)


class TestLaunchContainment:
    B = 16

    @pytest.fixture
    def validator(self):
        from repro.core.batch_executor import BatchValidator

        reg = SchemaRegistry()
        reg.register("t", SCHEMA)
        return BatchValidator(reg.get("t").tape, use_pallas=False)

    @pytest.fixture
    def table(self):
        from repro.data.doc_table import encode_batch

        return encode_batch(_docs(self.B, seed=11), max_nodes=16)

    def test_first_compile_failure_propagates(self, validator, table):
        validator._fn = _FailingLaunch(validator._fn, {self.B})
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            validator.validate_isolated(table)
        # the shape never compiled, so its next launch is a first one too
        assert (self.B, table.max_nodes) not in validator.seen_shapes()
        with pytest.raises(RuntimeError, match="Mosaic refused"):
            validator.validate_isolated(table)

    def test_compiled_launch_failure_bisects_bit_identically(self, validator, table):
        clean_valid, clean_decided, _, clean_errors = validator.validate_isolated(table)
        assert not clean_errors
        # the full-batch shape has compiled; a device error on it is
        # bisected into half-batch launches that succeed
        validator._fn = _FailingLaunch(validator._fn, {self.B})
        valid, decided, _, errors = validator.validate_isolated(table)
        assert not errors
        np.testing.assert_array_equal(valid, clean_valid)
        np.testing.assert_array_equal(decided, clean_decided)

    def test_injected_launch_fault_bisects_bit_identically(self, validator, table):
        clean_valid, clean_decided, _, _ = validator.validate_isolated(table)
        poison = (3, 12)
        with FaultInjector(seed=1).poison("launch", *poison) as inj:
            valid, decided, _, errors = validator.validate_isolated(table)
        assert inj.fired.get("launch", 0) > 0
        assert sorted(errors) == list(poison)
        assert all("injected" in errors[r] for r in poison)
        keep = np.setdiff1d(np.arange(self.B), poison)
        np.testing.assert_array_equal(valid[keep], clean_valid[keep])
        np.testing.assert_array_equal(decided[keep], clean_decided[keep])
        assert not decided[list(poison)].any()

    def test_compile_failure_propagates_through_admission(self, registry):
        # a batch shape no other test launches (128 rows x 24 nodes):
        # nothing is bisected into ERROR_ISOLATED verdicts, the caller
        # sees the failure
        g = registry.group_of("t")
        launch = g.validator._fn
        g.validator._fn = _FailingLaunch(launch, {128})
        try:
            with pytest.raises(RuntimeError, match="Mosaic refused"):
                registry.admit_mixed_ex(
                    _docs(100, seed=2), ["t"] * 100, max_nodes=24
                )
        finally:
            g.validator._fn = launch


# ---------------------------------------------------------------------------
# Admission guards + stats reconciliation
# ---------------------------------------------------------------------------


class TestGuardsAndReconciliation:
    def test_resource_guard_reasons(self):
        limits = GuardLimits(max_depth=4, max_nodes=10)
        deep = [[[[[1]]]]]
        assert "depth" in resource_guard(deep, limits)
        assert "nodes" in resource_guard(list(range(50)), limits)
        assert resource_guard({"a": 1}, limits) == ""

    def test_guard_rejects_before_encode(self):
        reg = SchemaRegistry(guard=GuardLimits(max_depth=4))
        reg.register("t", SCHEMA)
        bomb = {"a": 1}
        node = bomb
        for _ in range(10):
            node["x"] = {}
            node = node["x"]
        # an encode fault on the bomb's row never fires: guards run first
        with FaultInjector().poison("encode", 1) as inj:
            got, counts = reg.admit_mixed_ex([{"a": 1}, bomb], ["t", "t"])
        assert inj.fired.get("encode", 0) == 0
        assert got[0].outcome is ValidationOutcome.ADMITTED
        assert got[1].outcome is ValidationOutcome.REJECTED_GUARD
        assert "depth" in got[1].reason
        assert counts.rejected_guard == 1
        assert _sum_outcomes(counts) == 2

    def test_mixed_stream_reconciles(self, registry):
        docs = _docs(60, seed=11)
        docs[3] = [[[x] for x in range(2)]]  # valid JSON, invalid vs schema
        endpoints = ["t"] * len(docs)
        with FaultInjector(seed=1).rate("encode", 0.08).rate("fallback", 0.5):
            got, counts = registry.admit_mixed_ex(docs, endpoints, max_nodes=8)
        assert _sum_outcomes(counts) == len(docs)
        per_outcome = {}
        for v in got:
            per_outcome[v.outcome] = per_outcome.get(v.outcome, 0) + 1
        assert per_outcome.get(ValidationOutcome.ERROR_ISOLATED, 0) == counts.error_isolated
        assert (
            per_outcome.get(ValidationOutcome.ADMITTED, 0)
            + per_outcome.get(ValidationOutcome.INVALID, 0)
            == counts.batch_validated + counts.fallback_validated
        )


# ---------------------------------------------------------------------------
# Bounded fallback: step budget, wall clock, depth bombs, risky patterns
# ---------------------------------------------------------------------------


class TestBoundedFallback:
    def test_step_budget_times_out_fast(self, engine):
        reg = SchemaRegistry(
            engine=engine, fallback_max_steps=500, fallback_deadline_s=None
        )
        reg.register("arr", {"type": "array", "items": {"type": "integer"}})
        big = list(range(10_000))
        t0 = time.perf_counter()
        v = reg.validate_one("arr", big)
        assert time.perf_counter() - t0 < 2.0
        assert v.outcome is ValidationOutcome.TIMED_OUT
        assert "budget" in v.reason

    def test_wall_clock_deadline(self, engine):
        reg = SchemaRegistry(
            engine=engine,
            fallback_deadline_s=0.02,
            guard=GuardLimits(max_nodes=1 << 20),
        )
        reg.register("arr", {"type": "array", "items": {"type": "integer", "minimum": 0}})
        big = list(range(400_000))
        t0 = time.perf_counter()
        v = reg.validate_one("arr", big)
        assert time.perf_counter() - t0 < 2.0
        assert v.outcome is ValidationOutcome.TIMED_OUT

    def test_depth_bomb_structured(self, engine):
        # no guard: the bomb reaches the parser, which must reject in a
        # structured way (TIMED_OUT) rather than blowing the stack
        reg = SchemaRegistry(
            engine=engine, guard=GuardLimits(max_depth=1 << 20, max_nodes=1 << 20)
        )
        reg.register("t", SCHEMA)
        bomb = 0
        for _ in range(50_000):
            bomb = [bomb]
        v = reg.validate_one("t", bomb)
        assert v.outcome is ValidationOutcome.TIMED_OUT

    def test_executor_depth_guard(self, engine):
        # satellite: the sequential executor raises a structured error,
        # never RecursionError, on hostile nesting
        validator = Validator(compile_schema({"type": "object"}), engine=engine)
        bomb = 0
        for _ in range(50_000):
            bomb = [bomb]
        with pytest.raises(DocumentDepthError):
            validator.is_valid(bomb)

    def test_risky_pattern_classification(self):
        assert analyze_pattern("(a+)+$").risky
        assert analyze_pattern("^(\\d*)*x").risky
        assert not analyze_pattern("^x-").risky
        assert not analyze_pattern("^[a-z]{1,10}$").risky

    def test_risky_pattern_times_out(self, engine):
        reg = SchemaRegistry(engine=engine)
        reg.register("p", {"type": "string", "pattern": "(a+)+$"})
        subject = "a" * 28 + "!"
        t0 = time.perf_counter()
        v = reg.validate_one("p", subject)
        assert time.perf_counter() - t0 < 1.0
        assert v.outcome is ValidationOutcome.TIMED_OUT
        assert "backtracking" in v.reason

    def test_unbounded_path_unchanged(self, engine):
        # the clean (unbounded) executor still runs engine regexes,
        # risky or not -- containment applies only under a budget
        validator = Validator(
            compile_schema({"type": "string", "pattern": "(a+)+$"}), engine=engine
        )
        assert validator.is_valid("aaa")

    def test_eval_depth_bound(self, engine):
        # a document the parser takes, nested past max_eval_depth under a
        # recursive schema: the evaluation bound refuses it in both engines
        reg = SchemaRegistry(
            engine=engine, guard=GuardLimits(max_depth=1 << 20, max_nodes=1 << 20)
        )
        reg.register("r", {"type": "array", "items": {"$ref": "#"}})
        deep = []
        for _ in range(300):
            deep = [deep]
        v = reg.validate_one("r", deep)
        assert v.outcome is ValidationOutcome.TIMED_OUT
        assert reg.validate_one("r", [[[]]]).outcome is ValidationOutcome.ADMITTED

    def test_fallback_rows_counter(self, engine):
        # registry_fallback_rows_total{engine}: rows handed to a fallback
        # validator, labelled by its engine, published once per loop
        reg = SchemaRegistry(engine=engine)
        reg.register("t", SCHEMA)
        reg.register("u", {"type": "array", "uniqueItems": True})  # no tape
        # the last row is past max_nodes: an oversize row falls back too
        docs = _docs(24, seed=3) + [[1, 2], [1, 1], "x", {"a": 1, "c": list(range(8))}]
        endpoints = ["t"] * 24 + ["u", "u", "u", "t"]
        _, counts = reg.admit_mixed_ex(docs, endpoints, max_nodes=4)
        rows = lambda e: reg.metrics.counter(
            "registry_fallback_rows_total", engine=e
        ).value
        other = "interpreter" if engine == "codegen" else "codegen"
        assert counts.fallback_validated >= 4 and counts.batch_validated > 0
        assert rows(engine) == counts.fallback_validated
        assert rows(other) == 0
        reg.validate_one("u", [3])
        assert rows(engine) == counts.fallback_validated + 1
        assert rows(other) == 0


# ---------------------------------------------------------------------------
# Circuit breaker
# ---------------------------------------------------------------------------


class TestCircuitBreaker:
    def test_unit_transitions(self):
        clock = Clock()
        b = CircuitBreaker(BreakerConfig(threshold=2, cooldown_s=10.0), clock=clock)
        assert b.allow()
        b.record_timeout()
        assert b.state == "closed" and b.allow()
        b.record_timeout()  # second consecutive -> trip
        assert b.state == "open" and not b.allow()
        clock.advance(9.0)
        assert not b.allow()
        clock.advance(1.5)
        assert b.allow()  # half-open probe
        assert b.state == "half_open"
        assert not b.allow()  # only one probe per window
        b.record_timeout()  # probe failed -> re-open
        assert b.state == "open" and b.trips == 2
        clock.advance(10.5)
        assert b.allow()
        b.record_success()
        assert b.state == "closed" and b.allow()

    def test_trips_and_recovers_through_registry(self, engine):
        clock = Clock()
        reg = SchemaRegistry(
            engine=engine,
            fallback_max_steps=4,
            fallback_deadline_s=None,
            breaker=BreakerConfig(threshold=3, cooldown_s=30.0),
            clock=clock,
        )
        reg.register("t", SCHEMA)
        slow_doc = {"a": 1, "b": "x"}  # needs > 4 instructions
        for _ in range(3):
            v = reg.validate_one("t", slow_doc)
            assert v.outcome is ValidationOutcome.TIMED_OUT
        assert reg.breaker("t").state == "open"
        v = reg.validate_one("t", slow_doc)
        assert v.outcome is ValidationOutcome.UNDECIDED_FALLBACK
        assert "circuit open" in v.reason
        clock.advance(31.0)
        # half-open probe: an in-budget verdict (fail-fast type check)
        # closes the breaker again
        v = reg.validate_one("t", 5)
        assert v.outcome is ValidationOutcome.INVALID
        assert reg.breaker("t").state == "closed"
        v = reg.validate_one("t", 6)
        assert v.outcome is ValidationOutcome.INVALID

    def test_probe_timeout_reopens(self, engine):
        clock = Clock()
        reg = SchemaRegistry(
            engine=engine,
            fallback_max_steps=4,
            fallback_deadline_s=None,
            breaker=BreakerConfig(threshold=2, cooldown_s=5.0),
            clock=clock,
        )
        reg.register("t", SCHEMA)
        slow_doc = {"a": 1, "b": "x"}
        for _ in range(2):
            reg.validate_one("t", slow_doc)
        assert reg.breaker("t").state == "open"
        clock.advance(5.5)
        v = reg.validate_one("t", slow_doc)  # probe times out again
        assert v.outcome is ValidationOutcome.TIMED_OUT
        assert reg.breaker("t").state == "open"
        assert reg.breaker("t").trips == 2


# ---------------------------------------------------------------------------
# Hot-swap safety
# ---------------------------------------------------------------------------


class TestHotSwap:
    def test_injected_link_fault_rolls_back(self):
        reg = SchemaRegistry()
        entry = reg.register("ep", SCHEMA)
        assert entry.version == 1
        new_schema = dict(SCHEMA, required=["a", "b"])
        with FaultInjector().poison("link", "ep"):
            with pytest.raises(RegistrationError, match="version 1 keeps serving"):
                reg.register("ep", new_schema)
        assert reg.get("ep").version == 1
        assert reg.get("ep").schema == SCHEMA
        assert "link" in reg.swap_failures()["ep"]
        # prior version still serves traffic
        got, _ = reg.admit_mixed_ex([{"a": 1}], ["ep"])
        assert got[0].outcome is ValidationOutcome.ADMITTED
        # a later clean swap succeeds and clears the failure record
        entry = reg.register("ep", new_schema)
        assert entry.version == 2
        assert "ep" not in reg.swap_failures()

    def test_build_failure_rolls_back(self):
        reg = SchemaRegistry()
        reg.register("ep", SCHEMA)
        bad = {"type": "string", "pattern": "("}  # invalid regex: build fails
        with pytest.raises(RegistrationError):
            reg.register("ep", bad)
        assert reg.get("ep").version == 1
        assert "build" in reg.swap_failures()["ep"]

    def test_first_registration_failure_raises(self):
        reg = SchemaRegistry()
        with pytest.raises(RegistrationError):
            reg.register("fresh", {"type": "string", "pattern": "("})
        assert "fresh" not in reg

    def test_smoke_verify_runs_probes(self):
        # well-formed schemas pass verification and register normally
        reg = SchemaRegistry()
        entry = reg.register("ok", {"type": "object", "required": ["x"]})
        assert entry.version == 1
        # verify="off" also works (no probes)
        entry = reg.register("ok2", SCHEMA, verify="off")
        assert entry.version == 1


# ---------------------------------------------------------------------------
# Serving engine: structured outcomes, payload hygiene, rollback surfacing
# ---------------------------------------------------------------------------


class TestServeEngineContainment:
    @pytest.fixture(scope="class")
    def engine(self):
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

    def test_submit_result_back_compat(self, engine):
        rid, err = engine.submit(json.dumps({"prompt": "hello"}))
        assert rid is not None and err == ""
        res = engine.submit(json.dumps({"prompt": ""}))
        assert res == (None, "schema validation failed")  # still a 2-tuple
        assert res.outcome is ValidationOutcome.INVALID

    def test_non_object_payloads_never_raise(self, engine):
        # satellite: non-dict JSON top-levels flow through the normal
        # validator verdict (REQUEST_SCHEMA wants an object -> INVALID)
        for payload in ('"5"', "5", "[]", "null", "true", "[1, 2]"):
            res = engine.submit(payload)
            assert res.request_id is None
            assert res.outcome is ValidationOutcome.INVALID, payload
        # on an open schema they are admitted (validation-only requests)
        engine.register_endpoint("open", {})
        res = engine.submit("[]", endpoint="open")
        assert res.request_id is not None
        assert res.outcome is ValidationOutcome.ADMITTED
        batch = engine.submit_batch([("open", "5"), ("open", '"x"')])
        assert all(r.request_id is not None for r in batch)

    def test_payload_guards(self, engine):
        res = engine.submit("[" * 200_000)  # deep + malformed
        assert res.request_id is None
        assert res.outcome is ValidationOutcome.REJECTED_GUARD
        huge = '{"prompt": "' + "x" * (engine.registry.guard.max_bytes + 16) + '"}'
        res = engine.submit(huge)
        assert res.outcome is ValidationOutcome.REJECTED_GUARD
        assert "guard cap" in res.error

    def test_outcomes_reconcile_with_received(self, engine):
        stats = engine.stats
        assert stats.received == sum(stats.outcomes.values())
        batch = engine.submit_batch(
            [
                ("default", json.dumps({"prompt": "ok"})),
                ("default", "{broken"),
                ("nosuch", "{}"),
                ("default", json.dumps({"prompt": ""})),
            ]
        )
        assert [r.outcome for r in batch] == [
            ValidationOutcome.ADMITTED,
            ValidationOutcome.REJECTED_GUARD,
            ValidationOutcome.REJECTED_GUARD,
            ValidationOutcome.INVALID,
        ]
        assert stats.received == sum(stats.outcomes.values())

    def test_hot_swap_rollback_surfaced(self, engine):
        good = engine.registry.get("default")
        entry = engine.register_endpoint("default", {"type": "string", "pattern": "("})
        assert entry.version == good.version  # prior version kept serving
        per = engine.endpoint_stats()["default"]
        assert per["version"] == good.version
        assert per["last_swap_error"].startswith("build:")
        rid, err = engine.submit(json.dumps({"prompt": "still serving"}))
        assert rid is not None, err


# ---------------------------------------------------------------------------
# Randomized poison-mix stress (the CI chaos step runs this for ~30 s)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
def test_randomized_poison_mix_stress(registry):
    """Seeded random traffic + poison mixes; every iteration re-asserts
    the isolation and reconciliation invariants.  Runtime is controlled
    by CHAOS_STRESS_SECONDS (default: a quick local smoke)."""
    budget_s = float(os.environ.get("CHAOS_STRESS_SECONDS", "2"))
    deadline = time.monotonic() + budget_s
    seed = 0
    iterations = 0
    while True:
        seed += 1
        docs = _docs(64, seed=seed)
        endpoints = ["t"] * 64
        clean, _ = registry.admit_mixed_ex(docs, endpoints)
        rng = np.random.default_rng(seed)
        rate = float(rng.uniform(0.01, 0.10))
        point = ["encode", "launch", "fallback"][seed % 3]
        with FaultInjector(seed=seed).rate(point, rate):
            got, counts = registry.admit_mixed_ex(docs, endpoints)
        assert _sum_outcomes(counts) == 64, f"seed {seed}: counters leak"
        for i in range(64):
            if got[i].outcome is ValidationOutcome.ERROR_ISOLATED:
                continue
            assert got[i].outcome is clean[i].outcome, f"seed {seed} row {i}"
            assert got[i].valid == clean[i].valid, f"seed {seed} row {i}"
        iterations += 1
        if time.monotonic() >= deadline:
            break
    assert iterations >= 1

"""The rest of a run, driven on the CPU with the timed path broken
underneath, comes out not correct: a verdict altered where the batched
launch, the sequential fallback or the admission guard produces it, and
a verdict that never comes.  Sound runs of the same cells come out
correct."""

import time

import pytest

from bench.lib.harness import run_cell
from bench.tests.tree import CPU, SMALL_CLOSED, SMALL_OPEN, add_cell, add_config, copy_tree, small_config

SEED = 2**31 + 999


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = copy_tree(tmp_path_factory.mktemp("bench"))
    add_config(root, small_config())
    add_cell(root, "small.closed", "small", "small_closed", SMALL_CLOSED)
    add_cell(root, "small.stream", "small", "small_stream", SMALL_OPEN)
    return root


def _run(root, workload, trace=False):
    return run_cell(
        workload, seed=SEED, seconds=0.4, trace=trace, t_start=time.perf_counter(), device=CPU, log=lambda s: None, root=root
    )


@pytest.mark.parametrize("workload", ["small.closed", "small.stream"])
def test_sound_run_is_correct(root, workload):
    line, cmp = _run(root, workload)
    assert cmp.correct, cmp.examples
    assert cmp.compared > 0 and line["attempted"] == cmp.compared + cmp.failed + cmp.missing


def test_traced_run_reports_per_layer_metrics(root):
    line, cmp = _run(root, "small.closed", trace=True)
    assert cmp.correct
    assert {"encode_us_per_doc.batch", "fallback_row_share.batch"} <= set(line["metrics"])
    assert "busy_s" in line["device"] and "breakdown" in line


def _flip_batched(monkeypatch):
    from repro.core.batch_executor import BatchValidator

    real = BatchValidator.validate_ex

    def flipped(self, table, schema_ids=None):
        valid, decided, frontier = real(self, table, schema_ids)
        valid = valid.copy()
        valid[0] = ~valid[0]
        return valid, decided, frontier

    monkeypatch.setattr(BatchValidator, "validate_ex", flipped)


def _flip_fallback(monkeypatch):
    from repro.core.outcomes import ValidationOutcome, Verdict
    from repro.registry.registry import SchemaRegistry

    real = SchemaRegistry._bounded_fallback

    def flipped(self, endpoint, doc, key, *, explain=False):
        v = real(self, endpoint, doc, key, explain=explain)
        if v.outcome is ValidationOutcome.ADMITTED:
            return Verdict(ValidationOutcome.INVALID, False, "flipped", v.engine)
        return v

    monkeypatch.setattr(SchemaRegistry, "_bounded_fallback", flipped)


def _reject_in_guard(monkeypatch):
    from repro.serve.engine import ServeEngine

    real = ServeEngine._parse

    def rejecting(self, request_json, endpoint):
        if endpoint == "helm-chart-lock":
            return None, "rejected"
        return real(self, request_json, endpoint)

    monkeypatch.setattr(ServeEngine, "_parse", rejecting)


def _drop_one(monkeypatch):
    from repro.serve.scheduler import StreamScheduler

    real = StreamScheduler._complete
    dropped = []

    def dropping(self, ticket, result, **kw):
        if not dropped:
            dropped.append(ticket)
            return
        return real(self, ticket, result, **kw)

    monkeypatch.setattr(StreamScheduler, "_complete", dropping)


FAULTS = [
    ("small.closed", _flip_batched, "mismatches"),
    ("small.stream", _flip_fallback, "mismatches"),
    ("small.closed", _reject_in_guard, "mismatches"),
    ("small.stream", _drop_one, "missing"),
]


@pytest.mark.parametrize("workload,fault,number", FAULTS, ids=[f[1].__name__ for f in FAULTS])
def test_fault_is_not_correct(root, monkeypatch, workload, fault, number):
    fault(monkeypatch)
    line, cmp = _run(root, workload)
    assert not cmp.correct
    assert cmp.limits()[number]["value"] > cmp.limits()[number]["limit"]

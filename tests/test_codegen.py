"""The codegen (closure-compiled) engine must agree with the interpreter
on the full conformance corpus and on random schema/document pairs --
unbounded, and metered under a ValidationBudget, where it must also
count the same instruction steps."""

import copy
import random

import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed in this environment")
from hypothesis import given, settings

from repro.core import (
    DocumentDepthError,
    NaiveValidator,
    ValidationBudget,
    ValidationTimeout,
    Validator,
    compile_schema,
)
from repro.data.corpus import TABLE3, make_dataset

try:  # pytest inserts tests/ on sys.path (no package); PYTHONPATH=. gives tests.*
    from test_conformance import CASES
    from test_differential import json_docs, schemas
except ImportError:  # pragma: no cover
    from tests.test_conformance import CASES
    from tests.test_differential import json_docs, schemas


@pytest.mark.parametrize("name,schema,docs", CASES, ids=[c[0] for c in CASES])
def test_codegen_conformance(name, schema, docs):
    v = Validator(compile_schema(schema), engine="codegen")
    for doc, expected in docs:
        assert v.is_valid(doc) is expected, f"{name}: doc={doc!r} expected={expected}"


@settings(max_examples=300, deadline=None)
@given(schema=schemas, doc=json_docs)
def test_codegen_matches_interpreter(schema, doc):
    compiled = compile_schema(schema)
    interp = Validator(compiled)
    cg = Validator(compiled, engine="codegen")
    assert interp.is_valid(doc) is cg.is_valid(doc), (schema, doc)


@settings(max_examples=100, deadline=None)
@given(schema=schemas, doc=json_docs)
def test_codegen_matches_naive(schema, doc):
    cg = Validator(compile_schema(schema), engine="codegen")
    naive = NaiveValidator(schema)
    assert cg.is_valid(doc) is naive.is_valid(doc), (schema, doc)


# ---------------------------------------------------------------------------
# Metered: the bounded fallback's engines (DESIGN.md §11)
# ---------------------------------------------------------------------------


def _metered(validator, doc):
    """(verdict or the refusal's type, steps) under a fresh budget with
    no deadline, so the outcome depends on the work alone."""
    budget = ValidationBudget(deadline_s=None)
    try:
        out = validator.is_valid_bounded(doc, budget=budget)
    except (ValidationTimeout, DocumentDepthError) as exc:
        out = type(exc).__name__
    return out, budget.steps


def _engines(schema):
    compiled = compile_schema(schema)
    return Validator(compiled), Validator(compiled, engine="codegen")


@pytest.mark.parametrize("name,schema,docs", CASES, ids=[c[0] for c in CASES])
def test_metered_codegen_matches_interpreter_conformance(name, schema, docs):
    interp, cg = _engines(schema)
    for doc, expected in docs:
        want = _metered(interp, doc)
        assert want[0] is expected or isinstance(want[0], str), (name, doc)
        assert _metered(cg, doc) == want, f"{name}: doc={doc!r}"


@settings(max_examples=200, deadline=None)
@given(schema=schemas, doc=json_docs)
def test_metered_codegen_matches_interpreter(schema, doc):
    interp, cg = _engines(schema)
    assert _metered(cg, doc) == _metered(interp, doc), (schema, doc)


def _break(doc, rng):
    """Swap one top-level value for one of another type, or drop a key."""
    if not isinstance(doc, dict) or not doc:
        return doc
    doc = copy.deepcopy(doc)
    key = rng.choice(sorted(doc))
    if rng.random() < 0.3:
        del doc[key]
    else:
        doc[key] = 12345 if isinstance(doc[key], str) else "x"
    return doc


ESTATE = (
    "ansible-meta", "babelrc", "code-climate", "cql2", "cypress",
    "dependabot", "helm-chart-lock", "jasmine", "lerna", "stale",
    "tmuxinator", "vercel",
)


@pytest.mark.parametrize("name", ESTATE)
def test_metered_codegen_matches_interpreter_estate(name):
    """Table 3 estate documents (data/corpus.py, seeded), a third of them
    broken at one key: the fallback's own traffic."""
    row = [r[0] for r in TABLE3].index(name)
    _, _, kb, avg = TABLE3[row]
    ds = make_dataset(name, 60, kb, avg, seed=row)
    rng = random.Random(row)
    docs = [_break(d, rng) if rng.random() < 0.33 else d for d in ds.documents]
    interp, cg = _engines(ds.schema)
    verdicts = set()
    for doc in docs:
        want = _metered(interp, doc)
        assert _metered(cg, doc) == want, (name, doc)
        verdicts.add(want[0])
    assert True in verdicts  # the sample reaches the accepting path


def test_metered_closures_are_unmetered_after_the_call():
    interp, cg = _engines({"type": "array", "items": {"type": "integer"}})
    budget = ValidationBudget(max_steps=5, deadline_s=None)
    with pytest.raises(ValidationTimeout):
        cg.is_valid_bounded(list(range(100)), budget=budget)
    assert cg.ctx.budget is None
    assert cg.is_valid(list(range(100)))  # no budget left behind
    assert budget.steps == 5

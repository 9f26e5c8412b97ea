"""The codegen (closure-compiled) engine must agree with the interpreter
on the full conformance corpus and on random schema/document pairs --
unbounded, and metered under a ValidationBudget, where it must also
count the same instruction steps."""

import copy
import random
from collections import OrderedDict

import pytest

pytest.importorskip("hypothesis", reason="hypothesis not installed in this environment")
from hypothesis import given, settings

from repro.core import (
    DocumentDepthError,
    GuardLimits,
    NaiveValidator,
    ValidationBudget,
    ValidationOutcome,
    ValidationTimeout,
    Validator,
    compile_schema,
)
from repro.core.doc_model import nesting_exceeds
from repro.data.corpus import TABLE3, make_dataset
from repro.registry import SchemaRegistry

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


# ---------------------------------------------------------------------------
# Document models: the closures read the decoded tree, the interpreter
# parse_document's HashedObject model
# ---------------------------------------------------------------------------


class _ListSub(list):
    pass


_LONG_A = "a" + "x" * 40 + "z"  # > SHORT_LIMIT bytes: the hash is a digest
_LONG_B = "a" + "y" * 40 + "z"  # same length, first and last byte: same hash


def _bomb(depth):
    doc = []
    for _ in range(depth):
        doc = [doc]
    return doc


MODEL_CASES = [
    (
        "long_keys_same_hash",
        {
            "properties": {_LONG_A: {"type": "integer"}},
            "required": [_LONG_A],
            "additionalProperties": False,
        },
        [{_LONG_A: 1}, {_LONG_B: 1}, {_LONG_A: "x"}, {_LONG_A: 1, _LONG_B: 2}],
    ),
    (
        "long_keys_dependencies",
        {
            "dependentRequired": {_LONG_A: [_LONG_B]},
            "propertyNames": {"maxLength": 50},
            "minProperties": 1,
        },
        [{_LONG_A: 1}, {_LONG_B: 1}, {_LONG_A: 1, _LONG_B: 2}, {}],
    ),
    (
        "non_ascii_and_empty_keys",
        {
            "properties": {"": {"type": "string"}, "ключ": {"minimum": 3}, "名前": {}},
            "required": ["", "名前"],
            "patternProperties": {"^é": {"type": "boolean"}},
            "additionalProperties": {"type": "null"},
        },
        [
            {"": "s", "名前": 1},
            {"": 1, "名前": 1},
            {"名前": 1},
            {"": "s", "名前": 1, "ключ": 2},
            {"": "s", "名前": 1, "ключ": 4, "éa": True, "z": None},
            {"": "s", "名前": 1, "éa": 1},
            {"": "s", "名前": 1, "z": 0},
        ],
    ),
    (
        "digit_string_keys_on_a_path",
        {
            "properties": {
                "0": {"properties": {"1": {"type": "integer", "minimum": 10}}},
                "2": {"items": {"properties": {"3": {"const": "x"}}}},
            }
        },
        [
            {"0": {"1": 11}},
            {"0": {"1": 9}},
            {"0": [5, 5]},
            [{"1": 3}],
            {"2": [{"3": "x"}, {"3": "y"}]},
            {"2": {"0": {"3": "y"}}},
        ],
    ),
    (
        "dict_subclass_is_an_object",
        {
            "type": "object",
            "properties": {"a": {"type": "object", "required": ["b"]}},
            "unevaluatedProperties": False,
        },
        [
            OrderedDict(a=OrderedDict(b=1)),
            OrderedDict(a=OrderedDict(c=1)),
            OrderedDict(a={"b": 1}, z=2),
            {"a": OrderedDict(b=1)},
        ],
    ),
    (
        "list_subclass_is_an_array",
        {"type": "array", "items": {"type": "integer"}, "minItems": 2},
        [_ListSub([1, 2]), _ListSub([1]), _ListSub([1, "x"]), {"a": _ListSub([1])}],
    ),
    (
        "tuple_is_not_an_array",
        {
            "properties": {
                "a": {"type": "array"},
                "b": {"items": {"type": "string"}, "maxItems": 1},
            }
        },
        [{"a": (1, 2)}, {"a": [1, 2]}, {"b": (1, 2)}, {"b": [1, 2]}, (1, 2)],
    ),
    (
        "const_and_enum_of_objects",
        {
            "properties": {
                "c": {"const": {"a": [1, {"b": 2.0}], "": None}},
                "e": {"enum": [{"k": "v"}, [1, {"k": True}], "s", 1]},
            }
        },
        [
            {"c": {"": None, "a": [1.0, {"b": 2}]}},
            {"c": OrderedDict([("a", [1, {"b": 2}]), ("", None)])},
            {"c": {"a": [1, {"b": 2}]}},
            {"c": {"a": [True, {"b": 2}], "": None}},
            {"e": {"k": "v"}},
            {"e": OrderedDict(k="v")},
            {"e": [1, {"k": True}]},
            {"e": [1, {"k": 1}]},
            {"e": {"k": "v", "x": 1}},
            {"e": 1.0},
        ],
    ),
    (
        "nesting_past_the_depth_bound",
        {"type": "array", "items": {"$ref": "#"}},
        [_bomb(300), _bomb(3)],
    ),
    (
        "nesting_past_the_parse_stack",
        {"type": "object"},
        [_bomb(5_000), {"a": _bomb(5_000)}, OrderedDict(a=_bomb(5_000))],
    ),
]


@pytest.mark.parametrize(
    "name,schema,docs", MODEL_CASES, ids=[c[0] for c in MODEL_CASES]
)
def test_metered_codegen_on_decoded_tree(name, schema, docs):
    """The closures on the decoded tree against the interpreter on
    parse_document's model: the same verdict or refusal, the same steps,
    and the naive validator's verdict wherever one is given."""
    interp, cg = _engines(schema)
    naive = NaiveValidator(schema)
    for doc in docs:
        want = _metered(interp, doc)
        assert _metered(cg, doc) == want, f"{name}: doc={doc!r}"
        if isinstance(want[0], bool):
            assert want[0] is naive.is_valid(doc), f"{name}: doc={doc!r}"
            assert cg.is_valid(doc) is want[0], f"{name}: doc={doc!r}"


@pytest.mark.parametrize(
    "doc,limit,deeper",
    [
        (1, 0, False),
        ([], 0, True),
        ([], 1, False),
        ({"a": [{"b": 1}]}, 3, False),
        ({"a": [{"b": 1}]}, 2, True),
        ([1, "x", None, [[]]], 2, True),
        (OrderedDict(a=_ListSub([{}])), 2, True),
        ([(1, [2])], 1, False),  # a tuple is no array: not descended
        (_bomb(2_000), 1_000, True),
    ],
)
def test_nesting_exceeds(doc, limit, deeper):
    assert nesting_exceeds(doc, limit) is deeper


def test_depth_bomb_times_out_through_the_registry():
    reg = SchemaRegistry(guard=GuardLimits(max_depth=1 << 20, max_nodes=1 << 20))
    reg.register("r", {"type": "array", "items": {"$ref": "#"}})
    validator = reg.get("r").validator
    assert validator.engine == "codegen"
    with pytest.raises(ValidationTimeout, match="evaluation depth"):
        validator.is_valid_bounded(_bomb(300), budget=ValidationBudget(deadline_s=None))
    v = reg.validate_one("r", _bomb(300))
    assert v.outcome is ValidationOutcome.TIMED_OUT
    assert "evaluation depth" in v.reason


def test_codegen_fallback_never_parses(monkeypatch):
    """Codegen fallback rows reach the closures as decoded, with no
    parse_document; the interpreter and explain still parse."""
    from repro.core import executor

    reg = SchemaRegistry()
    reg.register("t", {"type": "object", "properties": {"a": {"type": "integer"}}})
    reg.register("u", {"type": "array", "uniqueItems": True})  # no tape
    calls = []
    parse = executor.parse_document

    def counting(doc):
        calls.append(doc)
        return parse(doc)

    monkeypatch.setattr(executor, "parse_document", counting)
    docs = [{"a": 1}, {"a": "x"}, [1, 2], [1, 1], {"a": 2, "b": list(range(8))}]
    endpoints = ["t", "t", "u", "u", "t"]
    verdicts, counts = reg.admit_mixed_ex(docs, endpoints, max_nodes=4)
    assert [v.valid for v in verdicts] == [True, False, True, False, True]
    assert counts.fallback_validated >= 3 and counts.batch_validated > 0
    assert calls == []
    rows = reg.metrics.counter("registry_fallback_rows_total", engine="codegen")
    assert rows.value == counts.fallback_validated

    compiled = compile_schema({"type": "array", "uniqueItems": True})
    assert Validator(compiled, engine="codegen").explain([1, 1])[0] is False
    assert calls == [[1, 1]]
    assert Validator(compiled).is_valid([1, 2]) is True
    assert calls == [[1, 1], [1, 2]]

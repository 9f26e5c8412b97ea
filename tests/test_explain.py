"""Failure-trace diagnostics (paper §8's error-message option)."""

from repro.core import Validator, compile_schema

SCHEMA = {
    "$schema": "https://json-schema.org/draft/2020-12/schema",
    "type": "object",
    "required": ["name"],
    "additionalProperties": False,
    "properties": {
        "name": {"type": "string", "minLength": 2},
        "age": {"type": "integer", "minimum": 0},
        "tags": {"type": "array", "items": {"type": "string"}},
    },
}


def _validator():
    return Validator(compile_schema(SCHEMA))


class TestExplain:
    def test_valid_document_empty_trace(self):
        ok, trace = _validator().explain({"name": "bob", "age": 3})
        assert ok and trace == []

    def test_missing_required_points_at_required(self):
        ok, trace = _validator().explain({"age": 3})
        assert not ok
        assert any("required" in path for path, _ in trace), trace

    def test_minimum_failure_points_at_keyword(self):
        ok, trace = _validator().explain({"name": "bob", "age": -1})
        assert not ok
        paths = [p for p, _ in trace]
        assert any("age" in p for p in paths), trace

    def test_nested_item_failure(self):
        ok, trace = _validator().explain({"name": "bob", "tags": ["a", 1]})
        assert not ok
        assert any("items" in p or "tags" in p for p, _ in trace), trace

    def test_trace_does_not_leak_into_hot_path(self):
        v = _validator()
        v.explain({"age": 3})
        assert v.ctx.trace is None
        assert v.is_valid({"name": "ok"}) is True

    def test_explain_agrees_with_is_valid(self):
        v = _validator()
        docs = [
            {"name": "bob"}, {"age": 1}, {"name": "x"}, 5, [],
            {"name": "bob", "zzz": 1}, {"name": "bob", "tags": []},
        ]
        for d in docs:
            ok, trace = v.explain(d)
            assert ok == v.is_valid(d), d
            assert ok == (trace == []) or not ok, d

    def test_root_failure_names_the_root(self):
        """A failure at the root schema itself (keyword location "") is
        traced like any other: ``false`` rejects everything at ""."""
        ok, trace = Validator(compile_schema(False)).explain(None)
        assert not ok
        assert "" in {p for p, _ in trace}, trace

    def test_synthesized_condition_rows_stay_untraced(self):
        """Rows the compiler synthesizes for a condition carry no keyword
        location and never enter the trace, though they fail here (the
        document lacks "a")."""
        from repro.core.compiler import CompilerOptions

        schema = {"dependentSchemas": {"a": {"required": ["b"]}}, "not": {}}
        options = CompilerOptions(cisc=False, reorder=False)
        ok, trace = Validator(compile_schema(schema, options=options)).explain({"x": 1})
        assert not ok
        assert trace == [("/not", "AssertionFail")], trace

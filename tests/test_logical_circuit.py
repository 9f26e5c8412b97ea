"""Batched logical applicators via assertion-group circuits (DESIGN.md §10).

Differential fuzz of nested ``anyOf``/``oneOf``/``not``/``if`` schemas
over the scalar subset against the sequential oracle, jnp==Pallas
(spot-checked) bit-identity, conditional-requiredness semantics,
mixed-registry linking with a tagged-union member, and precise
``UnsupportedForBatch`` reasons for out-of-subset branches.
"""

import random

import numpy as np
import pytest

from repro.core import Validator, compile_schema
from repro.core.batch_executor import BatchValidator
from repro.core.tape import build_tape, try_build_tape
from repro.data.doc_table import encode_batch
from repro.registry import SchemaRegistry

from test_batch_csr import _KEYS, _rand_doc, _rand_leaf

UNION = {
    "type": "object",
    "required": ["kind"],
    "properties": {"kind": {"enum": ["card", "bank", "wallet"]}},
    "oneOf": [
        {
            "properties": {
                "kind": {"const": "card"},
                "number": {"type": "string", "minLength": 12},
                "cvv": {"type": "string", "minLength": 3, "maxLength": 4},
            },
            "required": ["number", "cvv"],
        },
        {
            "properties": {
                "kind": {"const": "bank"},
                "iban": {"type": "string", "minLength": 15},
            },
            "required": ["iban"],
        },
        {
            "properties": {
                "kind": {"const": "wallet"},
                "wallet_id": {"type": "string", "pattern": "^w-"},
            },
            "required": ["wallet_id"],
        },
    ],
}

UNION_DOCS = [
    {"kind": "card", "number": "4111111111111111", "cvv": "123"},
    {"kind": "card", "number": "4111", "cvv": "123"},
    {"kind": "card", "number": "4111111111111111"},
    {"kind": "bank", "iban": "DE89370400440532013000"},
    {"kind": "bank", "iban": "short"},
    {"kind": "wallet", "wallet_id": "w-42"},
    {"kind": "wallet", "wallet_id": "x-42"},
    {"kind": "crypto", "wallet_id": "w-42"},
    {"number": "4111111111111111", "cvv": "123"},
    {},
    5,
    "card",
    None,
    [],
    # satisfies two branch tails but only one kind const -> still one
    {"kind": "card", "number": "4111111111111111", "cvv": "123",
     "iban": "DE89370400440532013000"},
]


def _check(schema, docs, *, max_nodes=64, max_depth=8, pallas=False):
    compiled = compile_schema(schema)
    seq = Validator(compiled)
    tape, reason = try_build_tape(compiled)
    assert tape is not None, (schema, reason)
    table = encode_batch(docs, max_nodes=max_nodes, max_depth=max_depth)
    expected = [seq.is_valid(d) for d in docs]
    results = {}
    for use_pallas in (False, True) if pallas else (False,):
        bv = BatchValidator(tape, max_depth=max_depth, use_pallas=use_pallas)
        v, d = bv.validate(table)
        results[use_pallas] = (v, d)
        for i, doc in enumerate(docs):
            if d[i]:
                assert bool(v[i]) == expected[i], (use_pallas, schema, doc)
    base_v, base_d = results[False]
    for key, (v, d) in results.items():
        np.testing.assert_array_equal(v, base_v, err_msg=repr((key, schema)))
        np.testing.assert_array_equal(d, base_d, err_msg=repr((key, schema)))
    return tape, results[False]


class TestDirectedCircuits:
    def test_discriminated_union_jnp_and_pallas(self):
        tape, (v, d) = _check(UNION, UNION_DOCS, pallas=True)
        assert tape.n_circuits >= 4  # XOR1 + three branch ANDs
        assert d.all()

    def test_anyof_scalars(self):
        _check(
            {"anyOf": [{"type": "string"}, {"minimum": 10}, {"enum": [None, True]}]},
            ["x", 5, 15, 9.99, None, True, False, [], {}],
        )

    def test_oneof_overlap_counts_exactly_one(self):
        # 5 passes both branches -> oneOf fails; strings pass both
        # (precondition skip) -> fail; -5 and 15 pass exactly one
        _check(
            {"oneOf": [{"minimum": 0}, {"maximum": 10}]},
            [-5, 5, 15, "s", None, [], {}],
        )

    def test_not_and_nested_not(self):
        _check({"not": {"type": "string"}}, ["x", 5, None, [], {}])
        _check(
            {"not": {"not": {"type": "string"}}},
            ["x", 5, None, [], {}],
        )

    def test_not_vacuous_branch_fails(self):
        # inner group passes vacuously on objects without "a" -> not fails
        schema = {"not": {"properties": {"a": {"const": 1}}, "required": ["a"]}}
        _check(schema, [{"a": 1}, {"a": 2}, {}, 5])

    def test_circuit_at_missing_property_is_vacuous(self):
        # the applicator's target is absent -> instruction skipped -> pass
        schema = {"properties": {"x": {"oneOf": [{"type": "string"}, {"minimum": 100}]}}}
        _check(schema, [{"x": "s"}, {"x": 500}, {"x": 5}, {}, {"y": 1}, 5])

    def test_if_then_else(self):
        schema = {
            "if": {"properties": {"a": {"const": 1}}, "required": ["a"]},
            "then": {"required": ["b"]},
            "else": {"required": ["c"]},
        }
        docs = [{"a": 1, "b": 2}, {"a": 1}, {"a": 2, "c": 3}, {"a": 2},
                {"c": 1}, {}, 5, "s"]
        _check(schema, docs)

    def test_if_then_without_else(self):
        schema = {"if": {"type": "string"}, "then": {"minLength": 3}}
        _check(schema, ["ab", "abcd", 5, None, [], {}])

    def test_dependent_schemas_when_defines(self):
        schema = {"dependentSchemas": {"a": {"required": ["b"]}}}
        _check(schema, [{"a": 1, "b": 2}, {"a": 1}, {"b": 2}, {}, 5, []])

    def test_nested_anyof_in_oneof(self):
        schema = {
            "oneOf": [
                {"anyOf": [{"type": "string"}, {"type": "null"}]},
                {"minimum": 100},
            ]
        }
        _check(schema, ["s", None, 500, 5, [], {}])

    def test_enum_inside_branch(self):
        schema = {"properties": {"p": {"anyOf": [{"enum": ["a", "b", 3]},
                                                 {"type": "array"}]}}}
        _check(schema, [{"p": "a"}, {"p": 3}, {"p": []}, {"p": "z"}, {}, 5])

    def test_conditional_required_not_in_hard_mask(self):
        # branch-level `required` must observe, not demand: {} fails the
        # anyOf (both branches false) but non-objects pass (precondition)
        schema = {"anyOf": [{"required": ["a"]}, {"required": ["b"]}]}
        tape, _ = _check(schema, [{"a": 1}, {"b": 1}, {}, {"c": 1}, 5, "x", []])
        assert int(tape.loc_required_mask[0]) == 0

    def test_hard_and_conditional_required_share_slots(self):
        schema = {
            "required": ["a"],
            "anyOf": [{"required": ["b"]}, {"required": ["c"]}],
        }
        tape, _ = _check(
            schema,
            [{"a": 1, "b": 2}, {"a": 1, "c": 2}, {"a": 1}, {"b": 2}, {}, 5],
        )
        assert bin(int(tape.loc_required_mask[0])).count("1") == 1  # only "a"

    def test_when_array_size_conditions(self):
        # CISC'd if: {minItems} / {minItems,maxItems} forms
        _check(
            {"if": {"minItems": 2}, "then": {"maxItems": 3}},
            [[1, 2], [1, 2, 3, 4], [1], [], "s", 5],
        )
        _check(
            {"if": {"minItems": 1, "maxItems": 1}, "then": {"maxItems": 0}},
            [[1], [], [1, 2], "s", 5],
        )

    def test_depth_budget_still_undecided_with_circuits(self):
        # the circuit sits below the depth budget: documents reaching it
        # must stay undecided, never vacuously valid
        schema = {"properties": {"a": {"properties": {
            "b": {"anyOf": [{"type": "string"}, {"minimum": 100}]}}}}}
        compiled = compile_schema(schema)
        tape = build_tape(compiled)
        table = encode_batch([{"a": {"b": 5}}, {"x": 1}], max_nodes=64, max_depth=8)
        bv = BatchValidator(tape, max_depth=1, use_pallas=False)
        v, d = bv.validate(table)
        assert not d[0] and d[1]  # deep doc undecided, not vacuously valid
        assert bool(v[1])


class TestRoutingScopes:
    """Closed/additionalProperties scopes vs per-key routes (the
    conformance-sweep fixes found while wiring circuit descents)."""

    def test_required_only_key_validates_against_additional_properties(self):
        schema = {"required": ["r"], "properties": {"p": {"type": "integer"}},
                  "additionalProperties": {"type": "string"}}
        _check(schema, [{"r": 5}, {"r": "ok"}, {"r": "ok", "p": 1},
                        {"p": "bad"}, {"p": 2}, {}])

    def test_required_only_key_fails_closed_object(self):
        schema = {"required": ["r"], "properties": {"p": {}},
                  "additionalProperties": False}
        _check(schema, [{"r": 1, "p": 2}, {"p": 2}, {}, {"r": 1}])

    def test_branch_key_outside_closed_properties(self):
        # the branch descends into "z", which the closed base forbids
        schema = {
            "type": "object",
            "properties": {"p": {}},
            "additionalProperties": False,
            "anyOf": [{"properties": {"z": {"const": 1}}}, {"required": ["p"]}],
        }
        _check(schema, [{"p": 1}, {"z": 1}, {}, {"p": 1, "z": 1}])

    def test_branch_key_under_additional_properties_falls_back(self):
        schema = {
            "properties": {"p": {}},
            "additionalProperties": {"type": "string"},
            "anyOf": [{"properties": {"z": {"const": 1}}}, {"required": ["p"]}],
        }
        tape, reason = try_build_tape(compile_schema(schema))
        assert tape is None and "additionalProperties" in reason


class TestUnsupportedReasons:
    @pytest.mark.parametrize(
        "schema,fragment",
        [
            ({"items": {"anyOf": [{"type": "string"}]}},
             "not a unique instance path"),
            ({"additionalProperties": {"oneOf": [{"type": "string"}]}},
             "not a unique instance path"),
            ({"prefixItems": [{"not": {"type": "string"}}]},
             "not a unique instance path"),
            ({"not": {"items": {"type": "string"}}},
             "LOOP_ITEMS inside a logical applicator"),
            ({"anyOf": [{"type": "object", "additionalProperties": False}]},
             "additionalProperties: false inside a logical applicator"),
            ({"anyOf": [{"uniqueItems": True}, {"type": "string"}]},
             "UNIQUE"),
            ({"anyOf": [{"contains": {"type": "string"}}]},
             "LOOP_CONTAINS inside a logical applicator"),
        ],
    )
    def test_precise_reasons(self, schema, fragment):
        tape, reason = try_build_tape(compile_schema(schema))
        assert tape is None, schema
        assert fragment in reason, (schema, reason)

    def test_recursive_ref_inside_branch_falls_back(self):
        schema = {
            "$defs": {"n": {"properties": {"next": {"$ref": "#/$defs/n"}}}},
            "anyOf": [{"$ref": "#/$defs/n"}, {"type": "string"}],
        }
        tape, reason = try_build_tape(compile_schema(schema))
        assert tape is None and "logical applicator" in reason


class TestLinkedCircuits:
    def test_mixed_registry_with_union_member_bit_identical(self):
        reg = SchemaRegistry()
        reg.register("union", UNION)
        reg.register("plain", {
            "type": "object",
            "required": ["v"],
            "properties": {"v": {"type": "integer", "minimum": 0}},
        })
        rng = random.Random(0xC1C)
        plain_docs = [{"v": 1}, {"v": -1}, {"v": "s"}, {}, 5]
        docs, endpoints = [], []
        for i in range(len(UNION_DOCS) + len(plain_docs)):
            if i % 2 == 0 and i // 2 < len(UNION_DOCS):
                docs.append(UNION_DOCS[i // 2]); endpoints.append("union")
            else:
                docs.append(plain_docs[rng.randrange(len(plain_docs))])
                endpoints.append("plain")
        table = encode_batch(docs, max_nodes=64)
        valid, decided = reg.validate_mixed(table, endpoints)
        assert decided.all()
        # bit-identical to single-schema dispatch per member
        for ep in ("union", "plain"):
            sel = [i for i, e in enumerate(endpoints) if e == ep]
            sub = encode_batch([docs[i] for i in sel], max_nodes=64)
            bv = BatchValidator(reg.get(ep).tape, use_pallas=False)
            v1, d1 = bv.validate(sub)
            np.testing.assert_array_equal(valid[sel], v1)
            np.testing.assert_array_equal(decided[sel], d1)
        # and to the sequential oracle
        for doc, ep, v in zip(docs, endpoints, valid):
            assert bool(v) == reg.get(ep).validator.is_valid(doc), (ep, doc)

    def test_linked_circuit_relocation_invariants(self):
        from repro.registry import link_tapes

        t_union = build_tape(compile_schema(UNION))
        t_plain = build_tape(compile_schema(
            {"type": "object", "properties": {"v": {"type": "integer"}}}
        ))
        t_any = build_tape(compile_schema(
            {"anyOf": [{"type": "string"}, {"minimum": 0}]}
        ))
        linked = link_tapes([t_plain, t_union, t_any])
        assert linked.n_circuits == t_union.n_circuits + t_any.n_circuits
        np.testing.assert_array_equal(
            linked.member_n_circuits, [0, t_union.n_circuits, t_any.n_circuits]
        )
        assert linked.max_circ_depth == max(t_union.max_circ_depth, t_any.max_circ_depth)
        # member 1's circuit owners sit inside member 1's location range
        lo1, lo2 = int(linked.loc_offsets[1]), int(linked.loc_offsets[2])
        owners1 = linked.circ_owner[: t_union.n_circuits]
        assert ((owners1 >= lo1) & (owners1 < lo2)).all()
        # parents relocate inside the member's circuit block (-1 for roots)
        parents1 = linked.circ_parent[: t_union.n_circuits]
        assert ((parents1 == -1) | (parents1 < t_union.n_circuits)).all()
        # leaf wiring survives: per-member circuit leaf counts match
        circ = linked.asrt_circ[linked.asrt_circ >= 0]
        assert (np.sort(np.unique(circ)) < linked.n_circuits).all()


def _rand_logical(rng: random.Random, depth: int) -> dict:
    """Random schema biased toward logical applicators at unique paths."""
    if depth <= 0 or rng.random() < 0.3:
        return _rand_leaf(rng)
    c = rng.randrange(7)
    if c == 0:
        return {"anyOf": [_rand_logical(rng, depth - 1)
                          for _ in range(rng.randint(1, 3))]}
    if c == 1:
        return {"oneOf": [_rand_logical(rng, depth - 1)
                          for _ in range(rng.randint(1, 3))]}
    if c == 2:
        return {"not": _rand_logical(rng, depth - 1)}
    if c == 3:
        out = {"if": _rand_logical(rng, depth - 1)}
        if rng.random() < 0.8:
            out["then"] = _rand_logical(rng, depth - 1)
        if rng.random() < 0.5:
            out["else"] = _rand_logical(rng, depth - 1)
        return out
    if c == 4:
        return {"allOf": [_rand_logical(rng, depth - 1)
                          for _ in range(rng.randint(1, 2))]}
    props = {k: _rand_logical(rng, depth - 1)
             for k in rng.sample(_KEYS, rng.randint(1, 3))}
    out = {"properties": props}
    if rng.random() < 0.5:
        out["required"] = rng.sample(sorted(props), rng.randint(0, len(props)))
    return out


class TestDifferentialFuzz:
    def test_circuits_match_sequential(self):
        rng = random.Random(0x10C1C)
        tapes = circuits = checked_sites = 0
        for trial in range(80):
            schema = _rand_logical(rng, 3)
            compiled = compile_schema(schema)
            tape, _ = try_build_tape(compiled)
            if tape is None:
                continue
            tapes += 1
            circuits += tape.n_circuits
            docs = [_rand_doc(rng, 3) for _ in range(rng.randint(1, 6))]
            seq = Validator(compiled)
            expected = [seq.is_valid(d) for d in docs]
            table = encode_batch(docs, max_nodes=64, max_depth=8)
            csr = BatchValidator(tape, max_depth=8, use_pallas=False)
            v_c, d_c = csr.validate(table)
            for i, (v, d) in enumerate(zip(v_c, d_c)):
                if d:
                    assert bool(v) == expected[i], (schema, docs[i])
            # failure sites, not just verdicts: the batched attribution
            # must name a keyword location the sequential trace also blames
            invalid = [i for i, (v, d) in enumerate(zip(v_c, d_c)) if d and not v]
            if invalid:
                checked_sites += len(invalid)
                sites = csr.explain_batch(table, docs=docs)
                for i in invalid:
                    site = sites[i]
                    assert site is not None, (schema, docs[i])
                    ok, trace = seq.explain(docs[i])
                    assert not ok, (schema, docs[i])
                    assert site.schema_path in {p for p, _ in trace}, (
                        schema, docs[i], site, trace
                    )
        assert tapes >= 25 and circuits >= 40  # the fuzzer must hit circuits
        assert checked_sites >= 40  # and the site differential must bite

"""Differential coverage for bounded $ref-recursion unrolling (DESIGN.md §9).

Recursive schemas (linked lists, trees, mutual recursion) now build
location tapes: ``ControlLabel``/``ControlJump`` cycles unroll up to the
``unroll_depth`` budget and the frontier locations carry the
``LOC_FRONTIER`` sentinel.  The contract under test:

* documents shallower than the budget are **decided** on the batched
  path and bit-identical to the sequential oracle (jnp == Pallas too);
* documents that reach a frontier are **undecided** -- never vacuously
  valid -- and ``validate_ex`` flags them so callers can count
  ``unroll_overflow`` fallbacks distinctly;
* a mixed registry with a recursive member linked in stays bit-identical
  to per-schema sequential dispatch.
"""

import random

import numpy as np
import pytest

from repro.core import Validator, compile_schema
from repro.core.batch_executor import BatchValidator
from repro.core.tape import LOC_FRONTIER, build_tape, try_build_tape
from repro.data.doc_table import encode_batch
from repro.data.pipeline import AdmissionController
from repro.registry import SchemaRegistry

LIST_SCHEMA = {
    "$defs": {
        "node": {
            "type": "object",
            "properties": {
                "value": {"type": "integer"},
                "next": {"$ref": "#/$defs/node"},
            },
            "required": ["value"],
        }
    },
    "$ref": "#/$defs/node",
}

TREE_SCHEMA = {
    "$defs": {
        "t": {
            "type": "object",
            "properties": {
                "v": {"type": "number", "minimum": 0},
                "left": {"$ref": "#/$defs/t"},
                "right": {"$ref": "#/$defs/t"},
            },
        }
    },
    "$ref": "#/$defs/t",
}

MUTUAL_SCHEMA = {
    "$defs": {
        "a": {
            "type": "object",
            "properties": {"tag": {"const": "a"}, "b": {"$ref": "#/$defs/b"}},
            "required": ["tag"],
        },
        "b": {
            "type": "object",
            "properties": {"tag": {"const": "b"}, "a": {"$ref": "#/$defs/a"}},
            "required": ["tag"],
        },
    },
    "$ref": "#/$defs/a",
}

def chain(depth: int, bad_at=None) -> dict:
    doc = node = {"value": "bad" if bad_at == 0 else 0}
    for k in range(1, depth + 1):
        node["next"] = node = {"value": "bad" if bad_at == k else k}
    return doc


def mutual_chain(depth: int, bad_at=None) -> dict:
    tags = ["a", "b"]
    doc = node = {"tag": "x" if bad_at == 0 else "a"}
    for k in range(1, depth + 1):
        t = tags[k % 2]
        node[t] = node = {"tag": "x" if bad_at == k else t}
    return doc


class TestLinkedListUnroll:
    def _build(self, unroll_depth=4):
        compiled = compile_schema(LIST_SCHEMA)
        tape, reason = try_build_tape(compiled, unroll_depth=unroll_depth)
        assert tape is not None, reason
        return compiled, tape

    def test_tape_builds_with_frontier(self):
        _, tape = self._build()
        assert tape.n_frontier == 1
        assert tape.unroll_depth == 4
        # the frontier entry edge carries the sentinel
        assert (tape.prop_child_loc == LOC_FRONTIER).sum() == 1
        # frontier subtrees do not inflate the horizon: 4 chain levels +
        # the scalar value child
        assert tape.max_loc_depth == 5

    def test_depths_straddling_budget(self):
        compiled, tape = self._build()
        seq = Validator(compiled)
        docs = [chain(d) for d in range(8)]
        docs += [chain(3, bad_at=2), chain(4, bad_at=4), chain(6, bad_at=1)]
        table = encode_batch(docs, max_nodes=64)
        bv = BatchValidator(tape, use_pallas=False)
        valid, decided, frontier = bv.validate_ex(table)
        # depth <= unroll_depth: decided, bit-identical to sequential
        for i, doc in enumerate(docs):
            if decided[i]:
                assert bool(valid[i]) == seq.is_valid(doc), doc
        depths = list(range(8)) + [3, 4, 6]
        for i, d in enumerate(depths):
            assert bool(decided[i]) == (d <= 4), (i, d)
            # frontier-reaching docs are undecided, never vacuously valid
            assert bool(frontier[i]) == (d > 4), (i, d)

    def test_csr_pallas_bit_identity(self):
        compiled, tape = self._build()
        seq = Validator(compiled)
        docs = [chain(d) for d in range(7)]
        table = encode_batch(docs, max_nodes=64)
        v0, d0 = BatchValidator(tape, use_pallas=False).validate(table)
        for i, doc in enumerate(docs):
            if d0[i]:
                assert bool(v0[i]) == seq.is_valid(doc), doc
        v, d = BatchValidator(tape, use_pallas=True).validate(table)
        np.testing.assert_array_equal(v, v0)
        np.testing.assert_array_equal(d, d0)

    def test_unroll_depth_one(self):
        compiled, tape = self._build(unroll_depth=1)
        seq = Validator(compiled)
        docs = [chain(0), chain(1), chain(2)]
        table = encode_batch(docs, max_nodes=32)
        valid, decided = BatchValidator(tape, use_pallas=False).validate(table)
        assert decided.tolist() == [True, True, False]
        assert [bool(v) for v, d in zip(valid, decided) if d] == [
            seq.is_valid(docs[0]),
            seq.is_valid(docs[1]),
        ]

    def test_node_budget_forces_earlier_frontier(self):
        compiled = compile_schema(LIST_SCHEMA)
        tape = build_tape(compiled, unroll_depth=64, unroll_node_budget=8)
        assert tape.n_frontier >= 1
        assert tape.n_locations <= 8 + 2  # one level may finish past the cap
        docs = [chain(1), chain(20)]
        table = encode_batch(docs, max_nodes=128)
        valid, decided = BatchValidator(tape, use_pallas=False).validate(table)
        assert bool(decided[0]) and bool(valid[0])
        assert not bool(decided[1])


class TestRecursionShapes:
    def test_tree_recursion(self):
        compiled = compile_schema(TREE_SCHEMA)
        tape, reason = try_build_tape(compiled, unroll_depth=3)
        assert tape is not None, reason
        assert tape.n_frontier > 1  # one frontier per exhausted branch
        seq = Validator(compiled)

        def tree(depth, neg=False):
            out = {"v": -1 if neg else depth}
            if depth > 0:
                out["left"] = tree(depth - 1, neg)
                out["right"] = tree(depth - 1)
            return out

        docs = [tree(0), tree(2), tree(3), tree(4), tree(2, neg=True), {"v": -3}]
        table = encode_batch(docs, max_nodes=128)
        valid, decided, frontier = BatchValidator(
            tape, use_pallas=False
        ).validate_ex(table)
        assert decided.tolist() == [True, True, True, False, True, True]
        assert frontier.tolist() == [False, False, False, True, False, False]
        for i, d in enumerate(decided):
            if d:
                assert bool(valid[i]) == seq.is_valid(docs[i]), docs[i]

    def test_mutual_recursion(self):
        compiled = compile_schema(MUTUAL_SCHEMA)
        tape, reason = try_build_tape(compiled, unroll_depth=4)
        assert tape is not None, reason
        seq = Validator(compiled)
        depths = list(range(13)) + [3, 2]
        docs = [mutual_chain(d) for d in range(13)]
        docs += [mutual_chain(3, bad_at=3), mutual_chain(2, bad_at=0)]
        table = encode_batch(docs, max_nodes=64)
        valid, decided, frontier = BatchValidator(
            tape, use_pallas=False
        ).validate_ex(table)
        assert frontier.tolist() == (~decided).tolist()
        # each label gets its own budget: labels a AND b both re-expand
        # up to 4 times, so the a->b->a chain stays decided through doc
        # depth 9 and hits the frontier at 10
        assert decided.tolist() == [d <= 9 for d in depths]
        for i, d in enumerate(decided):
            if d:
                assert bool(valid[i]) == seq.is_valid(docs[i]), docs[i]

    def test_recursion_through_items(self):
        schema = {
            "$defs": {
                "deep": {
                    "type": "array",
                    "items": {"$ref": "#/$defs/deep"},
                }
            },
            "$ref": "#/$defs/deep",
        }
        compiled = compile_schema(schema)
        tape, reason = try_build_tape(compiled, unroll_depth=3)
        assert tape is not None, reason
        # the frontier edge rides loc_item, not a property row
        assert (tape.loc_item == LOC_FRONTIER).any()
        seq = Validator(compiled)

        def nest(depth):
            out = []
            for _ in range(depth):
                out = [out]
            return out

        docs = [nest(1), nest(3), nest(5), [1], [[["x"]]]]
        table = encode_batch(docs, max_nodes=64)
        valid, decided, frontier = BatchValidator(
            tape, use_pallas=False
        ).validate_ex(table)
        for i, d in enumerate(decided):
            if d:
                assert bool(valid[i]) == seq.is_valid(docs[i]), docs[i]
        assert bool(frontier[2]) and not bool(decided[2])  # nest(5) overran
        assert bool(decided[1])  # nest(3) fits the budget

    def test_recursion_through_additional_properties(self):
        schema = {
            "$defs": {
                "bag": {
                    "type": "object",
                    "additionalProperties": {"$ref": "#/$defs/bag"},
                }
            },
            "$ref": "#/$defs/bag",
        }
        compiled = compile_schema(schema)
        tape, reason = try_build_tape(compiled, unroll_depth=2)
        assert tape is not None, reason
        assert (tape.loc_addl == LOC_FRONTIER).any()
        seq = Validator(compiled)
        docs = [{}, {"a": {}}, {"a": {"b": {}}}, {"a": {"b": {"c": {}}}}, {"a": 1}]
        table = encode_batch(docs, max_nodes=64)
        valid, decided, frontier = BatchValidator(
            tape, use_pallas=False
        ).validate_ex(table)
        assert bool(frontier[3]) and not bool(decided[3])
        for i, d in enumerate(decided):
            if d:
                assert bool(valid[i]) == seq.is_valid(docs[i]), docs[i]


_LEAVES = [
    {"type": "integer"},
    {"type": "number", "minimum": 0},
    {"enum": ["x", "y", 3]},
    {"const": 7},
    {"type": "string", "minLength": 1},
]


def _rand_recursive_schema(rng: random.Random):
    """Random list/tree/mutual-recursive schema + a doc generator."""
    leaf = rng.choice(_LEAVES)
    shape = rng.randrange(3)
    if shape == 0:  # linked list
        schema = {
            "$defs": {
                "n": {
                    "type": "object",
                    "properties": {"v": leaf, "next": {"$ref": "#/$defs/n"}},
                }
            },
            "$ref": "#/$defs/n",
        }

        def gen(depth, ok):
            doc = node = {"v": _leaf_value(rng, leaf, ok or depth > 0)}
            for k in range(depth):
                node["next"] = node = {
                    "v": _leaf_value(rng, leaf, ok or k < depth - 1)
                }
            return doc

    elif shape == 1:  # binary tree
        schema = {
            "$defs": {
                "t": {
                    "type": "object",
                    "properties": {
                        "v": leaf,
                        "l": {"$ref": "#/$defs/t"},
                        "r": {"$ref": "#/$defs/t"},
                    },
                }
            },
            "$ref": "#/$defs/t",
        }

        def gen(depth, ok):
            def rec(d):
                out = {"v": _leaf_value(rng, leaf, ok or d < depth)}
                if d > 0:
                    if rng.random() < 0.8:
                        out["l"] = rec(d - 1)
                    if rng.random() < 0.8:
                        out["r"] = rec(d - 1)
                return out

            return rec(depth)

    else:  # mutual recursion
        schema = {
            "$defs": {
                "a": {
                    "type": "object",
                    "properties": {"v": leaf, "b": {"$ref": "#/$defs/b"}},
                },
                "b": {
                    "type": "object",
                    "properties": {"w": leaf, "a": {"$ref": "#/$defs/a"}},
                },
            },
            "$ref": "#/$defs/a",
        }

        def gen(depth, ok):
            keys = ["v", "w"]
            links = ["b", "a"]
            doc = node = {"v": _leaf_value(rng, leaf, ok or depth > 0)}
            for k in range(depth):
                nxt = {keys[(k + 1) % 2]: _leaf_value(rng, leaf, ok or k < depth - 1)}
                node[links[k % 2]] = node = nxt
            return doc

    return schema, gen


def _leaf_value(rng: random.Random, leaf: dict, ok: bool):
    if ok:
        good = {"integer": 3, "number": 1.5, "string": "yes"}
        if "enum" in leaf:
            return rng.choice(leaf["enum"])
        if "const" in leaf:
            return leaf["const"]
        return good[leaf["type"]]
    return rng.choice([None, "no" if leaf.get("type") != "string" else 9, -4.5, []])


class TestRecursiveDifferentialFuzz:
    def test_fuzz_straddles_unroll_depth(self):
        rng = random.Random(0xF30)
        decided_total = frontier_total = sites_total = 0
        # every distinct tape shape jit-compiles two executors: keep the
        # trial count CI-friendly (matching test_batch_csr's budget)
        for trial in range(14):
            unroll = rng.choice([2, 3, 4])
            schema, gen = _rand_recursive_schema(rng)
            compiled = compile_schema(schema)
            tape, reason = try_build_tape(compiled, unroll_depth=unroll)
            assert tape is not None, (schema, reason)
            seq = Validator(compiled)
            docs = [
                gen(rng.randrange(unroll + 3), rng.random() < 0.7)
                for _ in range(12)
            ]
            table = encode_batch(docs, max_nodes=256)
            csr = BatchValidator(tape, max_depth=16, use_pallas=False)
            v, d, f = csr.validate_ex(table)
            # frontier-reaching docs are exactly the undecided ones here
            # (depths fit both encoder and executor budgets)
            np.testing.assert_array_equal(f, ~d, err_msg=repr(schema))
            for i, doc in enumerate(docs):
                if d[i]:
                    assert bool(v[i]) == seq.is_valid(doc), (schema, doc)
            # failure sites, not just verdicts: batched attribution on the
            # decided-invalid rows must agree with the sequential trace
            invalid = [i for i in range(len(docs)) if d[i] and not v[i]]
            if invalid:
                sites_total += len(invalid)
                sites = csr.explain_batch(table, docs=docs)
                for i in invalid:
                    site = sites[i]
                    assert site is not None, (schema, docs[i])
                    ok, trace = seq.explain(docs[i])
                    assert not ok, (schema, docs[i])
                    assert site.schema_path in {p for p, _ in trace}, (
                        schema, docs[i], site, trace
                    )
            decided_total += int(d.sum())
            frontier_total += int(f.sum())
        # the fuzzer must exercise both sides of the budget
        assert decided_total >= 30
        assert frontier_total >= 15
        assert sites_total >= 10  # and the site differential must bite


class TestMixedRegistryWithRecursion:
    FLAT = {
        "type": "object",
        "properties": {"name": {"type": "string", "minLength": 1}},
        "required": ["name"],
        "additionalProperties": False,
    }
    SEQ_ONLY = {
        "type": "object",
        "propertyNames": {"maxLength": 8},  # LoopKeys: outside the subset
    }

    def _registry(self):
        reg = SchemaRegistry(unroll_depth=3)
        reg.register("flat", self.FLAT)
        reg.register("list", LIST_SCHEMA)
        reg.register("keys", self.SEQ_ONLY)
        return reg

    def test_recursive_member_links_and_stays_bit_identical(self):
        reg = self._registry()
        tape = reg.linked_tape()
        assert tape is not None and "list" in tape.members
        # per-member unroll metadata survives linking
        li = list(tape.members).index("list")
        assert tape.member_unroll_depths[li] == 3
        assert tape.member_n_frontier[li] >= 1
        assert tape.member_n_frontier[list(tape.members).index("flat")] == 0

        rng = random.Random(5)
        docs, endpoints = [], []
        for i in range(40):
            e = rng.choice(["flat", "list", "keys"])
            endpoints.append(e)
            if e == "flat":
                docs.append({"name": "ok"} if i % 3 else {"name": ""})
            elif e == "list":
                docs.append(chain(rng.randrange(6), bad_at=1 if i % 5 == 0 else None))
            else:
                docs.append({"k" * (i % 12 + 1): 1})
        verdicts, counts = reg.admit_mixed(docs, endpoints)
        for doc, e, got in zip(docs, endpoints, verdicts):
            assert got == reg.get(e).validator.is_valid(doc), (e, doc)
        assert counts.batch_validated > 0
        assert counts.unroll_overflow > 0  # deep lists overran the budget
        assert counts.fallback_validated >= counts.unroll_overflow

    def test_registry_stats_record_unroll_facts(self):
        reg = self._registry()
        st = reg.get("list").stats
        assert st.batchable and st.unroll_depth == 3 and st.n_frontier >= 1
        assert reg.get("flat").stats.n_frontier == 0
        reasons = reg.fallback_reasons()
        assert set(reasons) == {"keys"}
        assert "LOOP_KEYS" in reasons["keys"]

    def test_admission_controller_counts_and_reasons(self):
        reg = self._registry()
        ctrl = AdmissionController(registry=reg, endpoint="list")
        records = [chain(1), chain(5), chain(2, bad_at=2), chain(7)]
        oks = ctrl.admit(records)
        seq = reg.get("list").validator
        assert oks == [seq.is_valid(r) for r in records]
        assert ctrl.stats.unroll_overflow == 2  # chain(5), chain(7)
        assert ctrl.stats.batch_validated == 2
        assert ctrl.stats.fallback_validated == 2
        assert ctrl.fallback_reasons == {"keys": reg.get("keys").stats.fallback_reason}

"""Differential coverage for the owner-sorted CSR batch path.

Hypothesis-free (the CI image may lack it): a seeded ``random``-based
schema/document fuzzer compares the CSR executor against the sequential
oracle and checks jnp vs Pallas bit-identity, plus directed cases for enum
OR-groups, the depth>max_depth undecided flag, and the
>32-required-properties ``UnsupportedForBatch`` fallback.
"""

import random

import numpy as np
import pytest

from repro.core import Validator, compile_schema
from repro.core.batch_executor import BatchValidator
from repro.core.tape import UnsupportedForBatch, build_tape, try_build_tape
from repro.data.doc_table import encode_batch

_KEYS = ["a", "b", "name", "kind", "value", "tags", "n1", "x"]


def _rand_leaf(rng: random.Random) -> dict:
    choice = rng.randrange(12)
    if choice == 0:
        return {"type": rng.choice(
            ["string", "integer", "number", "boolean", "null", "array", "object"])}
    if choice == 1:
        return {"minimum": rng.randint(-5, 5)}
    if choice == 2:
        return {"maximum": rng.randint(-5, 5)}
    if choice == 3:
        return {"exclusiveMinimum": rng.randint(-5, 5)}
    if choice == 4:
        return {"multipleOf": rng.choice([1, 2, 0.5])}
    if choice == 5:
        return {"minLength": rng.randint(0, 5)}
    if choice == 6:
        return {"maxLength": rng.randint(0, 8)}
    if choice == 7:
        return {"pattern": rng.choice([".*", ".+", "^x-", "^.{2,4}$", "^ab$"])}
    if choice == 8:
        return {"const": rng.choice([None, True, False, rng.randint(-5, 5), "ab", ""])}
    if choice == 9:
        # enum -> OR-group rows; mixed types force several row ops per group
        n = rng.randint(1, 5)
        pool = [None, True, False, -2, 0, 3, "a", "bb", "x-foo", 1.5]
        return {"enum": [rng.choice(pool) for _ in range(n)]}
    if choice == 10:
        return {"minItems": rng.randint(0, 3)}
    return {"required": rng.sample(_KEYS, rng.randint(0, 2))}


def _rand_schema(rng: random.Random, depth: int) -> dict:
    if depth <= 0 or rng.random() < 0.4:
        return _rand_leaf(rng)
    choice = rng.randrange(4)
    if choice == 0:
        props = {k: _rand_schema(rng, depth - 1)
                 for k in rng.sample(_KEYS, rng.randint(1, 3))}
        out = {"properties": props}
        if rng.random() < 0.5:
            out["required"] = rng.sample(sorted(props), rng.randint(0, len(props)))
        if rng.random() < 0.4:
            out["additionalProperties"] = False
        return out
    if choice == 1:
        return {"properties": {k: _rand_schema(rng, depth - 1)
                               for k in rng.sample(_KEYS, rng.randint(1, 2))},
                "additionalProperties": _rand_schema(rng, depth - 1)}
    if choice == 2:
        return {"items": _rand_schema(rng, depth - 1)}
    return {"prefixItems": [_rand_schema(rng, depth - 1)
                            for _ in range(rng.randint(1, 2))],
            "items": rng.choice([False, _rand_schema(rng, depth - 1)])}


def _rand_doc(rng: random.Random, depth: int):
    if depth <= 0 or rng.random() < 0.45:
        return rng.choice([
            None, True, False, rng.randint(-8, 8),
            rng.choice([0.5, 1.0, 2.5, -3.0]),
            rng.choice(["", "a", "ab", "x-foo", "value", "x" * 40]),
        ])
    if rng.random() < 0.5:
        return [_rand_doc(rng, depth - 1) for _ in range(rng.randint(0, 4))]
    return {k: _rand_doc(rng, depth - 1)
            for k in rng.sample(_KEYS, rng.randint(0, 4))}


class TestDifferentialFuzz:
    def test_csr_matches_sequential(self):
        rng = random.Random(0xB1A2E)
        tapes = 0
        # every distinct tape shape recompiles the executor: keep the
        # trial count CI-friendly
        for trial in range(60):
            schema = _rand_schema(rng, 3)
            compiled = compile_schema(schema)
            tape, _ = try_build_tape(compiled)
            if tape is None:
                continue
            tapes += 1
            docs = [_rand_doc(rng, 3) for _ in range(rng.randint(1, 6))]
            seq = Validator(compiled)
            expected = [seq.is_valid(d) for d in docs]
            table = encode_batch(docs, max_nodes=64, max_depth=8)
            csr = BatchValidator(tape, max_depth=8, use_pallas=False)
            v_c, d_c = csr.validate(table)
            for i, (v, d) in enumerate(zip(v_c, d_c)):
                if d:
                    assert bool(v) == expected[i], (schema, docs[i])
        assert tapes >= 20  # the fuzzer must actually exercise the tape path

    def test_csr_pallas_matches_jnp(self):
        rng = random.Random(7)
        checked = 0
        while checked < 10:
            schema = _rand_schema(rng, 2)
            tape, _ = try_build_tape(compile_schema(schema))
            if tape is None:
                continue
            checked += 1
            docs = [_rand_doc(rng, 3) for _ in range(3)]
            table = encode_batch(docs, max_nodes=64, max_depth=8)
            v1, d1 = BatchValidator(
                tape, max_depth=8, use_pallas=False).validate(table)
            v2, d2 = BatchValidator(
                tape, max_depth=8, use_pallas=True).validate(table)
            np.testing.assert_array_equal(v1, v2)
            np.testing.assert_array_equal(d1, d2)


class TestEnumOrGroups:
    SCHEMA = {
        "type": "object",
        "properties": {
            "kind": {"enum": ["alpha", "beta", 3, None, True, 2.5]},
            "nested": {"properties": {"kind": {"enum": ["x", "y"]}}},
        },
    }

    def _run(self, docs):
        compiled = compile_schema(self.SCHEMA)
        tape, reason = try_build_tape(compiled)
        assert tape is not None, reason
        seq = Validator(compiled)
        table = encode_batch(docs, max_nodes=32)
        valid, decided = BatchValidator(tape, use_pallas=False).validate(table)
        assert decided.all()
        return valid, [seq.is_valid(d) for d in docs]

    def test_group_membership(self):
        docs = [
            {"kind": "alpha"}, {"kind": "beta"}, {"kind": 3}, {"kind": None},
            {"kind": True}, {"kind": 2.5}, {"kind": "gamma"}, {"kind": 4},
            {"kind": False}, {"kind": [1]}, {},
            {"nested": {"kind": "x"}}, {"nested": {"kind": "z"}},
        ]
        valid, expected = self._run(docs)
        assert [bool(v) for v in valid] == expected

    def test_windows_are_owner_sorted_csr(self):
        tape = build_tape(compile_schema(self.SCHEMA))
        owners = tape.asrt_owner
        assert (np.diff(owners) >= 0).all(), "rows must be owner-sorted"
        # windows partition the rows and bound A-hat
        for l in range(tape.n_locations):
            s, n = int(tape.loc_asrt_start[l]), int(tape.loc_asrt_len[l])
            assert (owners[s : s + n] == l).all()
            assert n <= tape.max_rows_per_loc
            # groups contiguous within the window, AND rows first
            grp = tape.asrt_group[s : s + n]
            nonzero = grp[grp > 0]
            assert (np.diff(grp) >= 0).all() or len(set(grp.tolist())) == len(
                np.unique(grp)
            )
            assert list(nonzero) == sorted(nonzero)
        assert tape.max_rows_per_loc == int(tape.loc_asrt_len.max())

    def test_hash_runs_cover_duplicate_keys(self):
        # "kind" appears under two owners -> one hash run of length 2
        tape = build_tape(compile_schema(self.SCHEMA))
        assert tape.max_hash_run >= 2
        runs = tape.psort_run_len
        h = tape.psort_hash
        for r in range(1, tape.n_props):
            same = (h[r] == h[r - 1]).all()
            assert same == (runs[r] > 1 and runs[r] == runs[r - 1])


class TestMultipleOfPrecision:
    def test_decimal_and_large_quotients_match_sequential(self):
        schema = {"type": "number", "multipleOf": 0.01}
        compiled = compile_schema(schema)
        tape = build_tape(compiled)
        seq = Validator(compiled)
        docs = [19.99, 19.994, 0.07, 1.0, 0, 0.015, 3, -19.99]
        table = encode_batch(docs, max_nodes=8)
        valid, decided = BatchValidator(tape, use_pallas=False).validate(table)
        assert decided.all()
        assert valid.tolist() == [seq.is_valid(d) for d in docs]

        # large quotients: the tolerance is capped, so 1000001 % 2 stays
        # False on the batched path too (quotient 500000.5)
        schema2 = {"type": "integer", "multipleOf": 2}
        compiled2 = compile_schema(schema2)
        tape2 = build_tape(compiled2)
        seq2 = Validator(compiled2)
        docs2 = [1000000, 1000001, 999999, 2000002]
        table2 = encode_batch(docs2, max_nodes=8)
        valid2, decided2 = BatchValidator(tape2, use_pallas=False).validate(table2)
        assert decided2.all()
        assert valid2.tolist() == [seq2.is_valid(d) for d in docs2]
        assert valid2.tolist() == [True, False, False, True]


class TestDepthBudget:
    def test_deeper_than_max_depth_is_undecided(self):
        schema = {"properties": {"a": {"properties": {"a": {"properties": {
            "a": {"properties": {"a": {"const": 1}}}}}}}}}
        compiled = compile_schema(schema)
        tape, reason = try_build_tape(compiled)
        assert tape is not None, reason
        shallow = {"a": 1}
        deep_ok = {"a": {"a": {"a": {"a": 1}}}}  # const site at depth 4
        deep_bad = {"a": {"a": {"a": {"a": 2}}}}
        table = encode_batch([shallow, deep_ok, deep_bad], max_nodes=32, max_depth=16)
        bv = BatchValidator(tape, max_depth=3, use_pallas=False)
        valid, decided = bv.validate(table)
        # depth-3 budget cannot see the const at depth 5: undecided, not
        # vacuously valid (the silent-correctness fix)
        assert decided.tolist() == [True, False, False]
        assert bool(valid[0])
        # routed to the sequential executor, verdicts recover
        seq = Validator(compiled)
        routed = [
            bool(v) if d else seq.is_valid(doc)
            for v, d, doc in zip(valid, decided, [shallow, deep_ok, deep_bad])
        ]
        assert routed == [True, True, False]

    def test_deep_docs_decided_with_enough_budget(self):
        schema = {"properties": {"a": {"properties": {"a": {"const": 1}}}}}
        tape = build_tape(compile_schema(schema))
        table = encode_batch([{"a": {"a": 1}}, {"a": {"a": 2}}], max_nodes=32)
        valid, decided = BatchValidator(tape, use_pallas=False).validate(table)
        assert decided.tolist() == [True, True]
        assert valid.tolist() == [True, False]


class TestUnsupportedFallback:
    def test_more_than_32_required_props_falls_back(self):
        schema = {"required": [f"k{i:02d}" for i in range(40)]}
        tape, reason = try_build_tape(compile_schema(schema))
        assert tape is None
        assert "required" in reason
        with pytest.raises(UnsupportedForBatch):
            build_tape(compile_schema(schema))

    def test_32_required_props_still_batchable(self):
        keys = [f"k{i:02d}" for i in range(32)]
        schema = {"type": "object", "required": keys}
        tape, reason = try_build_tape(compile_schema(schema))
        assert tape is not None, reason
        docs = [{k: 1 for k in keys}, {k: 1 for k in keys[:31]}, {}]
        table = encode_batch(docs, max_nodes=64)
        valid, decided = BatchValidator(tape, use_pallas=False).validate(table)
        assert decided.all()
        assert valid.tolist() == [True, False, False]

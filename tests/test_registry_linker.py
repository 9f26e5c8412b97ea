"""Registry + tape-linker coverage: relocation invariants, mixed-schema
differential fuzz vs the sequential oracle (in the style of
test_batch_csr.py), versioning/eviction/hot-swap, and multi-tenant
admission through the pipeline."""

import random

import numpy as np
import pytest

from repro.core import ValidationOutcome, Validator, compile_schema
from repro.core.batch_executor import BatchValidator
from repro.core.tape import build_tape, try_build_tape
from repro.data.doc_table import encode_batch
from repro.data.pipeline import AdmissionController
from repro.registry import (
    SchemaRegistry,
    group_signature,
    link_tapes,
    pow2_class,
    segment_tape,
    signature_label,
)
from repro.serve.faults import FaultInjector

from test_batch_csr import _rand_doc, _rand_schema

S1 = {
    "type": "object",
    "required": ["name"],
    "additionalProperties": False,
    "properties": {
        "name": {"type": "string", "minLength": 1},
        "age": {"type": "integer", "minimum": 0},
    },
}
S2 = {
    "type": "object",
    "required": ["name"],
    "properties": {
        "name": {"enum": ["a", "b"]},
        "kind": {"enum": ["x", "y", 3]},
        "tags": {"type": "array", "items": {"type": "string"}, "maxItems": 3},
    },
}
S3 = {
    "type": "object",
    "properties": {
        "x": {"type": "number", "maximum": 10},
        "nested": {
            "type": "object",
            "properties": {"name": {"const": 5}, "deep": {"properties": {"q": {"const": 1}}}},
        },
    },
}
SCHEMAS = [S1, S2, S3]


def _tapes():
    return [build_tape(compile_schema(s)) for s in SCHEMAS]


class TestRelocationInvariants:
    def test_windows_stay_contiguous_and_owner_sorted(self):
        tapes = _tapes()
        linked = link_tapes(tapes, names=["s1", "s2", "s3"])
        owners = linked.asrt_owner
        real = owners >= 0
        assert (np.diff(owners[real]) >= 0).all(), "global owner sort must survive linking"
        for loc in range(linked.n_locations):
            s, n = int(linked.loc_asrt_start[loc]), int(linked.loc_asrt_len[loc])
            assert (owners[s : s + n] == loc).all()
            assert n <= linked.max_rows_per_loc
        assert int(linked.loc_asrt_len.max()) == linked.max_rows_per_loc

    def test_member_windows_relocate_verbatim(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        for m, tape in enumerate(tapes):
            lo = int(linked.loc_offsets[m])
            for loc in range(tape.n_locations):
                ms, n = int(tape.loc_asrt_start[loc]), int(tape.loc_asrt_len[loc])
                ls = int(linked.loc_asrt_start[lo + loc])
                assert int(linked.loc_asrt_len[lo + loc]) == n
                sl, msl = slice(ls, ls + n), slice(ms, ms + n)
                np.testing.assert_array_equal(linked.asrt_op[sl], tape.asrt_op[msl])
                np.testing.assert_array_equal(linked.asrt_f0[sl], tape.asrt_f0[msl])
                np.testing.assert_array_equal(linked.asrt_i0[sl], tape.asrt_i0[msl])
                np.testing.assert_array_equal(linked.asrt_hash[sl], tape.asrt_hash[msl])
                # group structure is preserved up to the per-member offset
                grp_l, grp_m = linked.asrt_group[sl], tape.asrt_group[msl]
                np.testing.assert_array_equal(grp_l > 0, grp_m > 0)
                nz = grp_m > 0
                if nz.any():
                    off = grp_l[nz] - grp_m[nz]
                    assert len(set(off.tolist())) == 1 and off[0] >= 0

    def test_or_group_ids_globally_unique(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        seen = {}
        for m, tape in enumerate(tapes):
            ao = int(linked.asrt_offsets[m])
            n = np.count_nonzero(tape.asrt_owner >= 0)
            for g in linked.asrt_group[ao : ao + n]:
                if g > 0:
                    assert seen.setdefault(int(g), m) == m, "group id spans members"

    def test_psort_runs_never_span_members(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        assert (np.diff(linked.psort_member) >= 0).all(), "member segments must be contiguous"
        h, member = linked.psort_hash, linked.psort_member
        for r in range(1, linked.n_props):
            if member[r] == member[r - 1] and (h[r] == h[r - 1]).all():
                assert linked.psort_run_len[r] == linked.psort_run_len[r - 1] > 1
        # runs are intact within members: every run's rows share one member
        run_start = 0
        while run_start < linked.n_props:
            run_len = max(1, int(linked.psort_run_len[run_start]))
            run = member[run_start : run_start + run_len]
            assert (run == run[0]).all()
            run_start += run_len

    def test_member_prop_segments_cover_psort(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        starts, lens = linked.member_prop_start, linked.member_prop_len
        assert int(starts[0]) == 0
        for m in range(1, linked.n_members):
            assert starts[m] == starts[m - 1] + lens[m - 1]
        assert int(starts[-1] + lens[-1]) == linked.n_props
        assert linked.max_member_props == int(lens.max())
        for m in range(linked.n_members):
            seg = linked.psort_member[starts[m] : starts[m] + lens[m]]
            assert (seg == m).all()

    def test_constants_are_member_maxima(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        assert linked.max_rows_per_loc == max(t.max_rows_per_loc for t in tapes)
        assert linked.max_hash_run == max(t.max_hash_run for t in tapes)
        assert linked.max_loc_depth == max(t.max_loc_depth for t in tapes)
        np.testing.assert_array_equal(
            linked.member_horizons, [t.max_loc_depth + 1 for t in tapes]
        )
        np.testing.assert_array_equal(linked.roots, linked.loc_offsets)
        assert linked.n_locations == sum(t.n_locations for t in tapes)
        assert linked.n_members == len(tapes)

    def test_member_of_location(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        for m, tape in enumerate(tapes):
            lo = int(linked.loc_offsets[m])
            assert linked.member_of_location(lo) == m
            assert linked.member_of_location(lo + tape.n_locations - 1) == m
        with pytest.raises(IndexError):
            linked.member_of_location(linked.n_locations)

    def test_location_tables_relocate(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        for m, tape in enumerate(tapes):
            lo = int(linked.loc_offsets[m])
            sl = slice(lo, lo + tape.n_locations)
            np.testing.assert_array_equal(linked.loc_closed[sl], tape.loc_closed)
            np.testing.assert_array_equal(
                linked.loc_required_mask[sl], tape.loc_required_mask
            )
            reloc = np.where(tape.loc_addl >= 0, tape.loc_addl + lo, tape.loc_addl)
            np.testing.assert_array_equal(linked.loc_addl[sl], reloc)
            reloc = np.where(tape.loc_item >= 0, tape.loc_item + lo, tape.loc_item)
            np.testing.assert_array_equal(linked.loc_item[sl], reloc)

    def test_single_member_link_roundtrips(self):
        tape = _tapes()[0]
        linked = link_tapes([tape], names=["only"])
        np.testing.assert_array_equal(linked.asrt_op, tape.asrt_op)
        np.testing.assert_array_equal(linked.psort_hash, tape.psort_hash)
        np.testing.assert_array_equal(linked.roots, [0])
        docs = [{"name": "x"}, {"name": ""}, {}]
        table = encode_batch(docs, max_nodes=32)
        v1, d1 = BatchValidator(tape, use_pallas=False).validate(table)
        v2, d2 = BatchValidator(linked, use_pallas=False).validate(table)
        np.testing.assert_array_equal(v1, v2)
        np.testing.assert_array_equal(d1, d2)


class TestMixedBatchDifferential:
    def test_directed_mixed_batch(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        docs = [
            {"name": "x", "age": 3}, {"name": "", "age": 3}, {"name": "x", "bogus": 1},
            {"name": "a", "kind": 3}, {"name": "c"}, {"name": "a", "tags": ["q", 1]},
            {"x": 5}, {"x": 50}, {"nested": {"name": 5}}, {"nested": {"name": 6}},
        ]
        ids = np.array([0, 0, 0, 1, 1, 1, 2, 2, 2, 2], np.int32)
        table = encode_batch(docs, max_nodes=32)
        seqs = [Validator(compile_schema(s)) for s in SCHEMAS]
        bv = BatchValidator(linked, use_pallas=False)
        valid, decided = bv.validate(table, ids)
        assert decided.all()
        for i, d in enumerate(docs):
            assert bool(valid[i]) == seqs[ids[i]].is_valid(d), d

    def test_fuzz_mixed_vs_sequential_and_per_schema(self):
        rng = random.Random(0x11C8)
        linked_batches = 0
        trial = 0
        while linked_batches < 12 and trial < 120:
            trial += 1
            members, tapes, seqs = [], [], []
            for _ in range(rng.randint(2, 4)):
                schema = _rand_schema(rng, 3)
                compiled = compile_schema(schema)
                tape, _ = try_build_tape(compiled)
                if tape is not None:
                    members.append(schema)
                    tapes.append(tape)
                    seqs.append(Validator(compiled))
            if len(tapes) < 2:
                continue
            linked_batches += 1
            linked = link_tapes(tapes)
            docs = [_rand_doc(rng, 3) for _ in range(rng.randint(2, 8))]
            ids = np.array(
                [rng.randrange(len(tapes)) for _ in docs], np.int32
            )
            table = encode_batch(docs, max_nodes=64, max_depth=8)
            bv = BatchValidator(linked, max_depth=8, use_pallas=False)
            valid, decided = bv.validate(table, ids)
            # (1) bit-identical to per-schema single-tape dispatch
            for m in range(len(tapes)):
                idx = [i for i in range(len(docs)) if ids[i] == m]
                if not idx:
                    continue
                sub = encode_batch([docs[i] for i in idx], max_nodes=64, max_depth=8)
                v, d = BatchValidator(tapes[m], max_depth=8, use_pallas=False).validate(sub)
                np.testing.assert_array_equal(v, valid[idx], err_msg=repr(members[m]))
                np.testing.assert_array_equal(d, decided[idx], err_msg=repr(members[m]))
            # (2) decided rows match the sequential oracle
            for i, (v, d) in enumerate(zip(valid, decided)):
                if d:
                    assert bool(v) == seqs[ids[i]].is_valid(docs[i]), (
                        members[ids[i]], docs[i],
                    )
        assert linked_batches >= 12

    def test_linked_pallas_matches_jnp(self):
        tapes = _tapes()
        linked = link_tapes(tapes)
        docs = [{"name": "x", "age": 1}, {"name": "a"}, {"x": 3}, {"name": ""}]
        ids = np.array([0, 1, 2, 0], np.int32)
        table = encode_batch(docs, max_nodes=32)
        v1, d1 = BatchValidator(linked, use_pallas=False).validate(table, ids)
        v2, d2 = BatchValidator(linked, use_pallas=True).validate(table, ids)
        np.testing.assert_array_equal(v1, v2)
        np.testing.assert_array_equal(d1, d2)

    def test_mixed_depth_budget_stays_per_member(self):
        deep = {"properties": {"a": {"properties": {"a": {"properties": {
            "a": {"properties": {"a": {"const": 1}}}}}}}}}
        shallow = {"properties": {"a": {"const": 1}}}
        t_deep = build_tape(compile_schema(deep))
        t_shallow = build_tape(compile_schema(shallow))
        linked = link_tapes([t_deep, t_shallow], names=["deep", "shallow"])
        docs = [
            {"a": {"a": {"a": {"a": 1}}}},  # deep member, below the budget
            {"a": 1},                        # deep member, shallow doc
            {"a": {"a": {"a": {"a": 1}}}},  # shallow member, deep doc
            {"a": 1},                        # shallow member
        ]
        ids = np.array([0, 0, 1, 1], np.int32)
        table = encode_batch(docs, max_nodes=32, max_depth=16)
        bv = BatchValidator(linked, max_depth=3, use_pallas=False)
        valid, decided = bv.validate(table, ids)
        # bit-identity with per-member dispatch: the deep member's horizon
        # exceeds the budget only for docs that actually reach below it;
        # the shallow member's docs stay statically decided
        bv_deep = BatchValidator(t_deep, max_depth=3, use_pallas=False)
        v_d, d_d = bv_deep.validate(encode_batch(docs[:2], max_nodes=32, max_depth=16))
        bv_sh = BatchValidator(t_shallow, max_depth=3, use_pallas=False)
        v_s, d_s = bv_sh.validate(encode_batch(docs[2:], max_nodes=32, max_depth=16))
        np.testing.assert_array_equal(decided, np.concatenate([d_d, d_s]))
        np.testing.assert_array_equal(valid[decided], np.concatenate([v_d, v_s])[decided])
        assert decided.tolist() == [False, True, True, True]


class TestSchemaRegistry:
    def test_register_version_evict(self):
        reg = SchemaRegistry()
        e1 = reg.register("users", S1)
        assert (e1.version, reg.versions("users")) == (1, [1])
        e2 = reg.register("users", S2)
        assert (e2.version, reg.versions("users")) == (2, [1, 2])
        assert reg.get("users").version == 2
        assert reg.get("users", version=1) is e1
        reg.evict("users", version=2)  # roll back to v1
        assert reg.get("users") is e1
        reg.evict("users")
        assert "users" not in reg.endpoints()
        with pytest.raises(KeyError):
            reg.get("users")

    def test_compile_stats_recorded(self):
        reg = SchemaRegistry()
        entry = reg.register("s2", S2)
        st = entry.stats
        assert st.batchable and st.n_locations > 0 and st.n_assertions > 0
        assert st.a_hat == entry.tape.max_rows_per_loc
        assert st.k == entry.tape.max_hash_run
        assert st.horizon == entry.tape.max_loc_depth + 1
        assert st.compile_seconds >= 0 and st.instruction_count > 0
        # logical applicators are batchable now (circuits); uniqueItems
        # still is not -- keep a genuinely sequential-only member here
        bad = reg.register("seq-only", {"uniqueItems": True})
        assert not bad.stats.batchable and bad.stats.fallback_reason
        union = reg.register("union", {"anyOf": [{"type": "string"}, {"minimum": 0}]})
        assert union.stats.batchable and union.stats.n_circuits >= 3

    def test_incremental_relink_reuses_segments(self):
        reg = SchemaRegistry()
        reg.register("a", S1)
        assert reg.linked_tape() is not None
        seg_a = reg._segments[("a", 1)]
        gen = reg.generation
        reg.register("b", S2)
        assert reg.generation > gen
        linked = reg.linked_tape()  # lazy re-link on access
        assert list(linked.members) == ["a", "b"]
        assert reg._segments[("a", 1)] is seg_a, "unchanged member must re-link from cache"
        # linked state is cached per generation
        assert reg.linked_tape() is linked

    def test_register_snapshots_schema_by_value(self):
        reg = SchemaRegistry()
        s = {"properties": {"v": {"type": "integer"}}}
        reg.register("ep", s)
        s["properties"]["v"]["type"] = "string"  # caller mutates in place
        e2 = reg.register("ep", s)  # must be a real new version, not a no-op
        assert e2.version == 2
        assert reg.get("ep").validator.is_valid({"v": "x"})
        assert not reg.get("ep").validator.is_valid({"v": 1})

    def test_versions_survive_full_eviction(self):
        # version numbers must be monotonic per endpoint forever: a
        # re-registered endpoint reusing (endpoint, 1) would collide with
        # the cached linked-state signature and serve the OLD schema
        reg = SchemaRegistry()
        reg.register("a", S1)
        reg.register("b", {"properties": {"y": {"type": "integer", "minimum": 100}}})
        reg.batch_validator()  # cache the linked state for (a,1),(b,1)
        reg.evict("b")
        e = reg.register("b", {"properties": {"y": {"type": "integer", "maximum": 0}}})
        assert e.version == 2  # not a reused version 1
        table = encode_batch([{"y": 5}], max_nodes=16)
        valid, decided = reg.validate_mixed(table, ["b"])
        assert decided[0] and not valid[0]  # new schema serves, not stale tape

    def test_admit_mixed_splits_oversize_from_undecided(self):
        deep = {"properties": {"a": {"properties": {"a": {"properties": {
            "a": {"properties": {"a": {"const": 1}}}}}}}}}
        ctrl = AdmissionController(deep, max_depth=3, batch_max_nodes=8)
        big = {"k%d" % i: i for i in range(20)}  # > 8 nodes: encoder budget
        oks = ctrl.admit([{"a": {"a": {"a": {"a": 1}}}}, big, {"a": 1}])
        assert oks == [True, True, True]
        assert ctrl.stats.undecided == 1  # the deep doc (depth budget)
        assert ctrl.stats.oversize == 1  # the wide doc (encoder budget)
        assert ctrl.stats.batch_validated == 1

    def test_noop_generation_bumps_keep_jitted_validator(self):
        reg = SchemaRegistry()
        reg.register("a", S1)
        reg.register("a", S2)  # v2 serves
        bv = reg.batch_validator()
        assert bv is not None
        # none of these change the batchable serving membership: the
        # jitted linked validator must survive (no recompile stall)
        reg.evict("a", version=1)  # non-serving version
        assert reg.batch_validator() is bv
        reg.register("slow", {"uniqueItems": True})  # sequential-only
        assert reg.batch_validator() is bv
        reg.evict("slow")
        assert reg.batch_validator() is bv
        entry = reg.register("a", S2)  # identical serving schema: no-op
        assert entry.version == 2 and reg.batch_validator() is bv
        reg.register("a", S3)  # real hot-swap -> re-link
        assert reg.batch_validator() is not bv

    def test_hot_swap_changes_verdicts_without_stalling_members(self):
        reg = SchemaRegistry()
        reg.register("a", S1)
        reg.register("b", {"properties": {"v": {"type": "integer"}}})
        docs = [{"v": 3}, {"v": "s"}]
        table = encode_batch(docs, max_nodes=16)
        valid, decided = reg.validate_mixed(table, ["b", "b"])
        assert decided.all() and valid.tolist() == [True, False]
        seg_a = reg._segments[("a", 1)]
        reg.register("b", {"properties": {"v": {"type": "string"}}})  # v2
        valid, decided = reg.validate_mixed(table, ["b", "b"])
        assert decided.all() and valid.tolist() == [False, True]
        assert reg._segments[("a", 1)] is seg_a

    def test_validate_mixed_routes_unbatchable_to_fallback(self):
        reg = SchemaRegistry()
        reg.register("fast", S1)
        reg.register("slow", {"uniqueItems": True})  # sequential-only
        docs = [{"name": "x"}, 42, {"name": ""}]
        endpoints = ["fast", "slow", "fast"]
        table = encode_batch(docs, max_nodes=16)
        valid, decided = reg.validate_mixed(table, endpoints)
        assert decided.tolist() == [True, False, True]
        assert valid[0] and not valid[2]
        assert np.array_equal(reg.schema_ids(endpoints), [0, -1, 0])
        # the caller's routing contract
        verdict = [
            bool(v) if d else reg.get(e).validator.is_valid(doc)
            for v, d, e, doc in zip(valid, decided, endpoints, docs)
        ]
        assert verdict == [True, True, False]  # 42 is not an array -> uniqueItems passes

    def test_validate_mixed_rejects_unknown_endpoint(self):
        reg = SchemaRegistry()
        reg.register("a", S1)
        table = encode_batch([{}], max_nodes=16)
        with pytest.raises(KeyError):
            reg.validate_mixed(table, ["nope"])

    def test_registry_without_batchable_members(self):
        reg = SchemaRegistry()
        reg.register("slow", {"uniqueItems": True})
        assert reg.linked_tape() is None and reg.batch_validator() is None
        table = encode_batch([1], max_nodes=16)
        valid, decided = reg.validate_mixed(table, ["slow"])
        assert not decided[0]


class TestMultiTenantAdmission:
    def test_admission_with_registry_and_endpoints(self):
        reg = SchemaRegistry()
        reg.register("u", S1)
        reg.register("t", S2)
        ctrl = AdmissionController(registry=reg, endpoint="u")
        records = [{"name": "x"}, {"name": "c"}, {"name": "a"}, {"name": ""}]
        endpoints = ["u", "t", "t", "u"]
        oks = ctrl.admit(records, endpoints)
        assert oks == [True, False, True, False]
        assert ctrl.stats.batch_validated == 4
        assert ctrl.stats.fallback_validated == 0
        assert ctrl.stats.admitted == 2 and ctrl.stats.rejected == 2

    def test_undecided_counter_observes_depth_fallbacks(self):
        deep = {"properties": {"a": {"properties": {"a": {"properties": {
            "a": {"properties": {"a": {"const": 1}}}}}}}}}
        ctrl = AdmissionController(deep, max_depth=3)
        oks = ctrl.admit([{"a": {"a": {"a": {"a": 1}}}}, {"a": 1}])
        assert oks == [True, True]
        assert ctrl.stats.undecided == 1
        assert ctrl.stats.fallback_validated == 1
        assert ctrl.stats.batch_validated == 1

    def test_use_pallas_kwarg_exposed(self):
        ctrl = AdmissionController(S1, use_pallas=False)
        assert ctrl.registry.use_pallas is False
        assert ctrl.batch_validator is not None
        assert ctrl.batch_validator.use_pallas is False
        # one batched layout: no layout option at any level
        with pytest.raises(TypeError):
            AdmissionController(S1, layout="csr")
        with pytest.raises(TypeError):
            BatchValidator(ctrl.batch_validator.tape, layout="csr")
        oks = ctrl.admit([{"name": "x"}, {"name": ""}])
        assert oks == [True, False]


# ---------------------------------------------------------------------------
# Link groups (DESIGN.md §14): Â-compatible partition of the registry
# ---------------------------------------------------------------------------


def _grouped_registry():
    reg = SchemaRegistry(use_pallas=False)
    reg.register("s1", S1)
    reg.register("s2", S2)
    reg.register("s3", S3)
    return reg


def _group_docs(n, seed=0):
    """Deterministic (docs, endpoints) mix spanning all three groups."""
    rng = random.Random(seed)
    pool = [
        ("s1", {"name": "x", "age": 3}),
        ("s1", {"name": "", "age": -1}),  # invalid
        ("s2", {"name": "a", "kind": "x", "tags": ["t"]}),
        ("s2", {"name": "z"}),  # invalid: enum
        ("s3", {"x": 3.5, "nested": {"name": 5}}),
        ("s3", {"x": 99}),  # invalid: maximum
    ]
    picks = [pool[rng.randrange(len(pool))] for _ in range(n)]
    return [d for _, d in picks], [e for e, _ in picks]


class TestLinkGroups:
    def test_pow2_class_and_labels(self):
        assert [pow2_class(x) for x in (1, 2, 3, 4, 5, 8, 9)] == [
            1, 2, 4, 4, 8, 8, 16,
        ]
        assert signature_label((2, 8, 4)) == "a2.m8.h4"

    def test_partition_keys_on_tape_signatures(self):
        reg = _grouped_registry()
        groups = reg.groups()
        # S1/S2/S3 have pairwise-distinct pow2 signatures -> 3 groups
        assert {g.members for g in groups} == {("s1",), ("s2",), ("s3",)}
        for g in groups:
            for m in g.members:
                assert group_signature(reg.get(m).tape) == g.key
                assert reg.group_of(m) is g
                assert g.member_index[m] < len(g.members)
            assert g.label == signature_label(g.key)

    def test_partition_is_order_independent(self):
        a = _grouped_registry()
        b = SchemaRegistry(use_pallas=False)
        for name, schema in (("s3", S3), ("s1", S1), ("s2", S2)):
            b.register(name, schema)
        assert {g.label: set(g.members) for g in a.groups()} == {
            g.label: set(g.members) for g in b.groups()
        }

    def test_link_grouping_false_is_single_group(self):
        reg = SchemaRegistry(use_pallas=False, link_grouping=False)
        reg.register("s1", S1)
        reg.register("s2", S2)
        (g,) = reg.groups()
        assert g.label == "all" and set(g.members) == {"s1", "s2"}

    def test_group_windows_stay_member_local(self):
        """The §8 inflation fix: a fat member in its own group no longer
        widens a lean group's launch windows (charge-style regression)."""
        reg = _grouped_registry()
        stats = reg.group_stats()
        lean = stats[signature_label(group_signature(reg.get("s1").tape))]
        t1 = reg.get("s1").tape
        assert lean["a_hat"] == int(t1.max_rows_per_loc)
        assert lean["horizon"] == int(t1.max_loc_depth) + 1
        # the flat (union) layout pays the fattest member's windows
        flat = SchemaRegistry(use_pallas=False, link_grouping=False)
        for name, schema in (("s1", S1), ("s2", S2), ("s3", S3)):
            flat.register(name, schema)
        union = flat.group_stats()["all"]
        assert union["m_hat"] > lean["m_hat"]
        assert union["horizon"] > lean["horizon"]

    def test_grouped_vs_flat_bit_identity(self):
        grouped = _grouped_registry()
        flat = SchemaRegistry(use_pallas=False, link_grouping=False)
        for name, schema in (("s1", S1), ("s2", S2), ("s3", S3)):
            flat.register(name, schema)
        docs, endpoints = _group_docs(96, seed=7)
        vg, cg = grouped.admit_mixed_ex(docs, endpoints)
        vf, cf = flat.admit_mixed_ex(docs, endpoints)
        assert [(v.outcome, v.valid) for v in vg] == [
            (v.outcome, v.valid) for v in vf
        ]
        assert cg.batch_validated == cf.batch_validated

    def test_unrelated_swap_keeps_other_groups_jitted(self):
        reg = _grouped_registry()
        v1 = reg.group_of("s1").validator
        reg.register("s2", S2)  # identical serving schema: no-op bump
        assert reg.group_of("s1").validator is v1
        assert reg.group_of("s2").validator is v1 or True  # own group free
        # real hot-swap of s2 relinks ONLY s2's group
        v3 = reg.group_of("s3").validator
        reg.register("s2", {"properties": {"q": {"const": 1}}})
        assert reg.group_of("s1").validator is v1
        assert reg.group_of("s3").validator is v3

    def test_per_group_fallback_attribution(self):
        reg = _grouped_registry()
        docs, endpoints = _group_docs(32, seed=3)
        label_of = {e: reg.group_of(e).label for e in ("s1", "s2", "s3")}
        # poison one row belonging to s1's group only (keys default to
        # row indices in admit_mixed_ex)
        victim = endpoints.index("s1")
        inj = FaultInjector(seed=1).poison("launch", victim)
        with inj:
            verdicts, counts = reg.admit_mixed_ex(docs, endpoints)
        assert verdicts[victim].outcome is ValidationOutcome.ERROR_ISOLATED
        hit = label_of["s1"]
        assert counts.per_group[hit]["error_isolated"] == 1
        for lbl in set(label_of.values()) - {hit}:
            assert counts.per_group.get(lbl, {}).get("error_isolated", 0) == 0
        assert reg.group_fallbacks()[hit]["error_isolated"] == 1
        assert reg.group_stats()[hit]["fallbacks"]["error_isolated"] == 1

    def test_per_group_counts_partition_batch_validated(self):
        reg = _grouped_registry()
        docs, endpoints = _group_docs(48, seed=11)
        _, counts = reg.admit_mixed_ex(docs, endpoints)
        total = sum(
            per["batch_validated"] for per in counts.per_group.values()
        )
        assert total == counts.batch_validated > 0

    def test_warm_groups_pretraces_pow2_shapes(self):
        reg = _grouped_registry()
        traced = reg.warm_groups([1, 3], max_nodes=64)
        assert traced == len(reg.groups()) * 2  # buckets 1 and 4, per group
        assert reg.warm_groups([1, 3], max_nodes=64) == 0  # idempotent
        for g in reg.groups():
            shapes = g.validator.seen_shapes()
            assert (1, 64) in shapes and (4, 64) in shapes

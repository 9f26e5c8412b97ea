"""The CSR launch reads tape constants as packed rows (DESIGN.md §4, §5, §8).

Three tables built once per tape on the host -- the assertion window per
location, the candidate run per psort start row, the psort segment per
member -- are each read with one row gather per node (or per document),
never element by element per node and slot.  Covered here:

* the table builders against the tape's own columns;
* the lowered launch's ``gather`` primitives (no per-slot element
  gathers come back);
* verdicts, isolation and first-failure sites on the three taped
  Table 3 datasets, a frontier tape and a circuit tape, on the jnp and
  the Pallas (interpret mode on CPU) paths.
"""

import random

import jax
import numpy as np
import pytest
from jax.extend.core import ClosedJaxpr, Jaxpr

from repro.core import NaiveValidator, Validator, compile_schema
from repro.core.batch_executor import (
    BatchValidator,
    _candidate_table,
    _segment_tables,
    _window_table,
)
from repro.core.tape import build_tape
from repro.data.corpus import TABLE3, make_dataset
from repro.data.doc_table import encode_batch
from repro.registry import link_tapes

from test_logical_circuit import UNION, UNION_DOCS
from test_recursive_unroll import LIST_SCHEMA

_BIG = 2**30
_TAPED = ("helm-chart-lock", "jasmine", "tmuxinator")
_CASES = _TAPED + ("frontier", "circuit")
_MAX_NODES = 64
_CIRCUIT_KEYWORDS = ("anyOf", "oneOf", "not", "if")


def _dataset(name):
    i = next(k for k, row in enumerate(TABLE3) if row[0] == name)
    _, n_docs, kb, avg = TABLE3[i]
    return make_dataset(name, n_docs, kb, avg, seed=i, scale=0.01)


def _chain(depth, value=1):
    doc = {"value": value}
    for v in range(depth):
        doc = {"value": v, "next": doc}
    return doc


def _broken(doc, rng):
    """``doc`` with one top-level value replaced by a value of another kind."""
    out = dict(doc)
    if out:
        key = rng.choice(sorted(out))
        out[key] = rng.choice([None, "x" * 40, -7, 1.5, [1, "a"], {"q": 1}, True])
    return out


def _case(name):
    """(schema, tape, documents) of one equivalence case."""
    rng = random.Random(name)
    if name == "frontier":
        schema = LIST_SCHEMA
        tape = build_tape(compile_schema(schema), unroll_depth=3)
        docs = [_chain(d) for d in range(7)] + [_chain(2, "x"), {"next": {}}]
    elif name == "circuit":
        schema = UNION
        tape = build_tape(compile_schema(schema))
        docs = list(UNION_DOCS)
    else:
        ds = _dataset(name)
        schema = ds.schema
        tape = build_tape(compile_schema(schema))
        docs = ds.documents[:12]
    docs = docs + [_broken(d, rng) for d in docs if isinstance(d, dict)]
    return schema, tape, docs


def _tape(name):
    if name == "linked":
        return link_tapes([_case("tmuxinator")[1], _case("frontier")[1]])
    return _case(name)[1]


@pytest.mark.parametrize("name", _CASES + ("linked",))
def test_window_table_rows_are_the_location_windows(name):
    tape = _tape(name)
    W = max(1, tape.max_rows_per_loc)
    table = _window_table(tape, W)
    L = len(tape.loc_asrt_start)
    assert table.shape == (L + 1, 15 * W) and table.dtype == np.uint32
    op = table[:, :W].view(np.int32)
    f0 = table[:, W : 2 * W].view(np.float32)
    i0, i1 = table[:, 2 * W : 3 * W].view(np.int32), table[:, 3 * W : 4 * W].view(np.int32)
    u0, u1 = table[:, 4 * W : 5 * W], table[:, 5 * W : 6 * W]
    gcode = table[:, 6 * W : 7 * W].view(np.int32)
    lanes = table[:, 7 * W :].reshape(L + 1, W, 8)
    for loc in range(L + 1):
        start = int(tape.loc_asrt_start[loc]) if loc < L else 0
        length = int(tape.loc_asrt_len[loc]) if loc < L else 0
        for s in range(W):
            if s < length:
                r = start + s
                assert op[loc, s] == tape.asrt_op[r]
                assert f0[loc, s] == np.float32(tape.asrt_f0[r])
                assert (i0[loc, s], i1[loc, s]) == (tape.asrt_i0[r], tape.asrt_i1[r])
                assert (u0[loc, s], u1[loc, s]) == (tape.asrt_u0[r], tape.asrt_u1[r])
                flag = (1 << 20) if tape.asrt_circ[r] >= 0 else 0
                assert gcode[loc, s] == tape.asrt_group[r] + flag
                np.testing.assert_array_equal(lanes[loc, s], tape.asrt_hash[r])
            else:
                assert op[loc, s] == -1 and gcode[loc, s] == 0
                assert not table[loc, W + s :: W][:6].any() and not lanes[loc, s].any()


@pytest.mark.parametrize("name", _CASES + ("linked",))
def test_candidate_table_rows_are_the_hash_runs(name):
    tape = _tape(name)
    K = max(1, tape.max_hash_run)
    table = _candidate_table(tape, K)
    M = len(tape.psort_owner)
    assert table.shape == (M + 1, 4 * K) and table.dtype == np.int32
    cols = (tape.psort_owner, tape.psort_child_loc, tape.psort_required_slot, tape.psort_orig_row)
    for start in range(M + 1):
        run = int(tape.psort_run_len[start]) if start < M else 0
        for k in range(K):
            got = table[start, k::K]
            if k < run:  # rows past the table's end read its last row, as before
                assert list(got) == [int(c[min(start + k, M - 1)]) for c in cols]
            else:
                assert list(got) == [-1, 0, 0, 0]


@pytest.mark.parametrize("name", _CASES + ("linked",))
def test_segment_tables_are_the_member_segments(name):
    tape = _tape(name)
    Mh = max(1, tape.max_member_props)
    seg_hash, seg_row = _segment_tables(tape, Mh)
    S = tape.n_members
    assert seg_hash.shape == (S, 8, Mh) and seg_row.shape == (S, Mh)
    for s in range(S):
        start, length = int(tape.member_prop_start[s]), int(tape.member_prop_len[s])
        for j in range(Mh):
            if j < length:
                assert seg_row[s, j] == start + j
                np.testing.assert_array_equal(seg_hash[s, :, j], tape.psort_hash[start + j])
            else:
                assert seg_row[s, j] == _BIG and not seg_hash[s, :, j].any()


def _gathers(jaxpr):
    """(operand shape, output shape) of every ``gather`` in ``jaxpr`` and
    in the jaxprs nested in its equations' parameters."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather":
            out.append((tuple(eqn.invars[0].aval.shape), tuple(eqn.outvars[0].aval.shape)))
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else (param,):
                if isinstance(sub, ClosedJaxpr):
                    out.extend(_gathers(sub.jaxpr))
                elif isinstance(sub, Jaxpr):
                    out.extend(_gathers(sub))
    return out


@pytest.mark.parametrize(
    "name,use_pallas",
    [("linked", False), ("linked", True), ("helm-chart-lock", False)],
)
def test_launch_reads_tape_constants_as_packed_rows(name, use_pallas):
    tape = _tape(name)
    bv = BatchValidator(tape, max_depth=8, use_pallas=use_pallas)
    B, N = 4, 32
    W, K, Mh = bv.n_window, bv.k_cand, bv.m_hat
    # the shapes below identify the reads only while these differ from
    # each other and from the structural row's 6 columns
    assert len({W, K, Mh, 6, 8, 1}) == 6, (W, K, Mh)
    if name == "linked":
        assert tape.n_members == 2 and tape.n_circuits and tape.n_frontier
    table = encode_batch([{}] * B, max_nodes=N)
    cols = {k: np.asarray(v) for k, v in table.columns().items()}
    closed = jax.make_jaxpr(bv._fn)(cols, np.zeros(B, np.int32))
    gathers = _gathers(closed.jaxpr)
    BN = B * N
    c = bv._consts

    # no element gather per node and window slot, candidate or segment row
    for _, shape in gathers:
        assert shape not in ((BN, W), (BN, K), (BN, Mh, 8), (BN, Mh)), gathers
    # one row gather each, of B*N rows (window, candidates) or B (segments)
    assert gathers.count((c["win"].shape, (BN, 15 * W))) == 1, gathers
    assert gathers.count((c["cand"].shape, (BN, 4 * K))) == 1, gathers
    seg_reads = gathers.count((c["seg_hash"].shape, (B, 8, Mh))) + gathers.count(
        (c["seg_row"].shape, (B, Mh))
    )
    assert seg_reads == (2 if name == "linked" and not use_pallas else 0), gathers


@pytest.mark.parametrize("use_pallas", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("name", _CASES)
def test_packed_launch_verdicts_and_sites(name, use_pallas):
    schema, tape, docs = _case(name)
    naive = NaiveValidator(schema)
    seq = Validator(compile_schema(schema))
    table = encode_batch(docs, max_nodes=_MAX_NODES, max_depth=8)
    bv = BatchValidator(tape, max_depth=8, use_pallas=use_pallas)
    valid, decided, frontier = bv.validate_ex(table)

    truth = np.array([naive.is_valid(d) for d in docs])
    assert decided.any() and not truth[decided].all() and truth[decided].any()
    np.testing.assert_array_equal(valid[decided], truth[decided])
    if name == "frontier":
        assert frontier.any() and not decided[frontier].any()

    iv, idec, ifr, errors = bv.validate_isolated(table)
    assert not errors
    np.testing.assert_array_equal(iv, valid)
    np.testing.assert_array_equal(idec, decided)
    np.testing.assert_array_equal(ifr, frontier)

    sites = bv.explain_batch(table, docs=docs)
    for doc, ok, dec, site in zip(docs, valid, decided, sites):
        if not dec:
            continue
        if ok:
            assert site is None, (doc, site)
            continue
        seq_ok, trace = seq.explain(doc)
        assert not seq_ok and site is not None, doc
        if site.keyword in _CIRCUIT_KEYWORDS:
            # a failing circuit anchored above a failing keyword is the
            # lower BFS node and wins the tie-break; the sequential trace
            # may name only the keyword
            continue
        assert site.schema_path in {p for p, _ in trace}, (doc, site, trace)

"""The batch encoder against the per-node encoder it replaced.

``_oracle_batch`` below is the original encoder, kept verbatim as the
reference: one fresh set of numpy columns per document, every node
written as numpy scalars, every key and string hashed where it is met,
and the rows stacked.  ``encode_batch`` walks the batch once into flat
lists, hashes each distinct text once and scatters each column once; the
two must agree bit for bit -- every column's dtype, shape and bytes,
``ok``, ``n_nodes`` and ``errors``.
"""

from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Any, Dict, List, Optional, Tuple

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.doc_model import HashedObject, parse_document
from repro.core.hashing import SHORT_LIMIT, hash_lanes, shash_bytes
from repro.core.nodetypes import TYPE_CODES
from repro.core.outcomes import fault_point, set_fault_hook
from repro.data import doc_table
from repro.data.doc_table import TokenTable, encode_batch, encode_document, key_lanes

ROOT = Path(__file__).resolve().parents[1]


# ---------------------------------------------------------------------------
# the oracle: the per-node encoder
# ---------------------------------------------------------------------------


def _fnv64(data: bytes) -> int:
    h = 0xCBF29CE484222325
    for b in data:
        h ^= b
        h = (h * 0x100000001B3) & 0xFFFFFFFFFFFFFFFF
    return h


def _oracle_lanes(s: str) -> np.ndarray:
    data = s.encode("utf-8")
    lanes = hash_lanes(shash_bytes(data))
    if len(data) > SHORT_LIMIT:
        fnv = _fnv64(data)
        lanes = lanes.copy()
        lanes[6] = (fnv >> 32) & 0xFFFFFFFF
        lanes[7] = fnv & 0xFFFFFFFF
    return lanes


def _str_prefix8(data: bytes) -> Tuple[int, int]:
    padded = data[:8].ljust(8, b"\x00")
    return int.from_bytes(padded[:4], "big"), int.from_bytes(padded[4:], "big")


def _items_of(value: Any):
    if isinstance(value, HashedObject):
        return value.items()
    return list(value.items())


def _oracle_document(doc: Any, max_nodes: int, max_depth: int) -> Optional[Dict[str, np.ndarray]]:
    cols = {
        "node_type": np.zeros(max_nodes, np.int8),
        "is_int": np.zeros(max_nodes, bool),
        "num": np.zeros(max_nodes, np.float64),
        "size": np.zeros(max_nodes, np.int32),
        "parent": np.full(max_nodes, -1, np.int32),
        "depth": np.zeros(max_nodes, np.int32),
        "idx_in_parent": np.full(max_nodes, -1, np.int32),
        "child_start": np.zeros(max_nodes, np.int32),
        "key_hash": np.zeros((max_nodes, 8), np.uint32),
        "str_hash": np.zeros((max_nodes, 8), np.uint32),
        "str_prefix": np.zeros((max_nodes, 2), np.uint32),
        "str_last": np.zeros(max_nodes, np.uint32),
    }
    queue: List[Tuple[Any, int, int, Optional[str], int]] = [(doc, -1, 0, None, -1)]
    count = 0
    while queue:
        value, parent, depth, key, idx = queue.pop(0)
        if count >= max_nodes or depth > max_depth:
            return None
        i = count
        count += 1
        cols["parent"][i] = parent
        cols["depth"][i] = depth
        cols["idx_in_parent"][i] = idx
        if key is not None:
            cols["key_hash"][i] = _oracle_lanes(key)
        if value is None:
            cols["node_type"][i] = TYPE_CODES["null"]
        elif isinstance(value, bool):
            cols["node_type"][i] = TYPE_CODES["boolean"]
            cols["num"][i] = 1.0 if value else 0.0
        elif isinstance(value, (int, float)):
            cols["node_type"][i] = TYPE_CODES["number"]
            cols["num"][i] = float(value)
            cols["is_int"][i] = isinstance(value, int) or float(value).is_integer()
        elif isinstance(value, str):
            data = value.encode("utf-8")
            cols["node_type"][i] = TYPE_CODES["string"]
            cols["size"][i] = len(value)
            cols["str_hash"][i] = _oracle_lanes(value)
            cols["str_prefix"][i] = _str_prefix8(data)
            cols["str_last"][i] = data[-1] if data else 0
        elif isinstance(value, list):
            cols["node_type"][i] = TYPE_CODES["array"]
            cols["size"][i] = len(value)
            cols["child_start"][i] = count + len(queue)
            for j, item in enumerate(value):
                queue.append((item, i, depth + 1, None, j))
        elif isinstance(value, (dict, HashedObject)):
            items = _items_of(value)
            cols["node_type"][i] = TYPE_CODES["object"]
            cols["size"][i] = len(items)
            cols["child_start"][i] = count + len(queue)
            for j, (k, v) in enumerate(items):
                queue.append((v, i, depth + 1, k, j))
        else:
            raise TypeError(f"unsupported JSON value {type(value)!r}")
    cols["n_nodes"] = np.int32(count)
    return cols


def _oracle_batch(docs, max_nodes=256, max_depth=16, *, isolate=False, keys=None) -> TokenTable:
    batch = len(docs)
    stacked: Dict[str, List[np.ndarray]] = {}
    ok = np.ones(batch, bool)
    n_nodes = np.zeros(batch, np.int32)
    errors: Dict[int, str] = {}
    template = _oracle_document(None, max_nodes, 16)
    zero_cols = None
    for b, doc in enumerate(docs):
        if isolate:
            try:
                fault_point("encode", keys[b] if keys is not None else b)
                cols = _oracle_document(doc, max_nodes, max_depth)
            except RecursionError:
                errors[b] = "encode recursion limit exceeded"
                cols = None
            except Exception as exc:
                errors[b] = f"{type(exc).__name__}: {exc}"
                cols = None
        else:
            cols = _oracle_document(doc, max_nodes, max_depth)
        if cols is None:
            ok[b] = False
            if zero_cols is None:
                zero_cols = {k: np.zeros_like(v) for k, v in template.items() if k != "n_nodes"}
            cols = dict(zero_cols)
            cols["n_nodes"] = np.int32(0)
        n_nodes[b] = cols.pop("n_nodes")
        for k, v in cols.items():
            stacked.setdefault(k, []).append(v)
    arrays = {k: np.stack(v) for k, v in stacked.items()}
    return TokenTable(n_nodes=n_nodes, ok=ok, errors=errors, **arrays)


def _assert_identical(got: TokenTable, want: TokenTable) -> None:
    assert got.errors == want.errors
    assert got.columns().keys() == want.columns().keys()
    for name, w in want.columns().items():
        g = got.columns()[name]
        assert (g.dtype, g.shape) == (w.dtype, w.shape), name
        assert g.tobytes() == w.tobytes(), name  # bitwise: -0.0 and NaN too


def _both(docs, **kw) -> TokenTable:
    got = encode_batch(docs, **kw)
    _assert_identical(got, _oracle_batch(docs, **kw))
    return got


# ---------------------------------------------------------------------------
# random JSON
# ---------------------------------------------------------------------------

_KEYS = ["id", "name", "a", "", "é", "k" * 31, "k" * 32, "long-" * 12, "日本語キー"]
_STRINGS = ["", "x", "a" * 31, "a" * 32, "é" * 16, "é" * 15 + "a", "b" * 200, "☃" * 11, "\x00"]

_scalars = (
    st.none()
    | st.booleans()
    | st.integers(-(2**64), 2**64)
    | st.sampled_from([0, 1, -1, 2**53, 2**53 + 1, -(2**53) - 1])
    | st.floats(allow_nan=True, allow_infinity=True)
    | st.sampled_from([1.0, -0.0, 0.0, 0.5, 1e300, float(2**53 + 2)])
    | st.text(max_size=40)
    | st.sampled_from(_STRINGS)
)
_keys = st.sampled_from(_KEYS) | st.text(max_size=36)
_json = st.recursive(
    _scalars,
    lambda inner: st.lists(inner, max_size=5) | st.dictionaries(_keys, inner, max_size=5),
    max_leaves=30,
)


@settings(max_examples=300, deadline=None)
@given(
    docs=st.lists(_json | st.none(), min_size=1, max_size=8),
    max_nodes=st.sampled_from([1, 4, 16, 64]),
    max_depth=st.sampled_from([0, 2, 16]),
    hashed=st.booleans(),
)
def test_random_json_encodes_bit_identically(docs, max_nodes, max_depth, hashed):
    if hashed:  # the sequential executor's document model, as admission holds it
        docs = [parse_document(d) for d in docs]
    _both(docs, max_nodes=max_nodes, max_depth=max_depth)
    _both(docs, max_nodes=max_nodes, max_depth=max_depth, isolate=True)


# ---------------------------------------------------------------------------
# explicit cases
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "doc,max_nodes,max_depth,fits",
    [
        (list(range(15)), 16, 16, True),  # exactly max_nodes nodes
        (list(range(16)), 16, 16, False),  # max_nodes + 1
        ({"k%d" % i: [i] for i in range(7)}, 16, 16, True),  # 15 nodes
        ({"k%d" % i: [i] for i in range(8)}, 16, 16, False),  # 17 nodes
        ([[[[1]]]], 16, 4, True),  # deepest node at max_depth
        ([[[[[1]]]]], 16, 4, False),  # one node at max_depth + 1
        ({"a": {"b": {"c": "deep"}}}, 16, 2, False),
        ([], 1, 0, True),
        ([1], 1, 0, False),
    ],
)
def test_budget_edges(doc, max_nodes, max_depth, fits):
    got = _both([{"x": 1}, doc, "after"], max_nodes=max_nodes, max_depth=max_depth)
    assert bool(got.ok[1]) is fits
    if not fits:
        assert got.n_nodes[1] == 0 and not got.errors
        for name, col in got.columns().items():
            assert not col[1].any(), name  # zeroed, the -1 fills included


def test_pad_rows_are_one_null_node():
    got = _both([None, {"a": "b"}, None], max_nodes=8)
    assert got.ok.tolist() == [True, True, True]
    assert got.n_nodes.tolist() == [1, 2, 1]
    assert got.node_type[0, 0] == TYPE_CODES["null"]


@pytest.mark.parametrize(
    "poison",
    [
        object(),  # unsupported value
        {"n": 10**400},  # int too large for a float
        {"ok": "\ud800"},  # lone surrogate: no UTF-8
        {"\udfff": 1},  # ... in a key
        [1, 2, {"x": [set()]}],
    ],
)
def test_poisoned_row_between_two_good_ones(poison):
    good = [{"a": "shared", "b": [1, 2.5]}, {"a": "shared", "c": "only-here"}]
    got = _both([good[0], poison, good[1]], max_nodes=16, isolate=True)
    assert got.ok.tolist() == [True, False, True]
    assert set(got.errors) == {1}
    for name, col in got.columns().items():
        assert not col[1].any(), name
    # the good rows encode as they would without the poisoned one
    alone = encode_batch(good, max_nodes=16, isolate=True)
    for name, col in got.columns().items():
        np.testing.assert_array_equal(col[[0, 2]], alone.columns()[name], err_msg=name)
    with pytest.raises(Exception):
        encode_batch([good[0], poison], max_nodes=16)  # not isolated: raises


def test_injected_encode_fault_fires_once_per_row():
    seen = []

    def hook(point, key):
        seen.append((point, key))
        if point == "encode" and key == "bad":
            raise RuntimeError("injected")

    prev = set_fault_hook(hook)
    try:
        docs = [{"a": 1}, {"a": "s"}, None]
        keys = ["good", "bad", "pad"]
        got = _both(docs, max_nodes=8, isolate=True, keys=keys)
    finally:
        set_fault_hook(prev)
    assert got.errors == {1: "RuntimeError: injected"}
    assert got.ok.tolist() == [True, False, True]
    # once per row for each of the two encoders
    assert seen == [("encode", k) for k in keys] * 2


def test_keys_and_strings_are_hashed_once_per_batch_and_not_kept(monkeypatch):
    calls = []
    real = doc_table._text_tables

    def counted(texts):
        calls.append(list(texts))
        return real(texts)

    monkeypatch.setattr(doc_table, "_text_tables", counted)
    docs = [{"k": "v", "n": ["v", "w"]}, {"k": "w"}, {"n": []}]
    encode_batch(docs, max_nodes=8)
    encode_batch([{"k": "z"}], max_nodes=8)
    assert calls == [[b"k", b"v", b"n", b"w"], [b"k", b"z"]]


def test_text_tables_match_the_plain_lanes():
    texts = ["", "a", "a" * 31, "a" * 32, "é" * 16, "x" * 300, "日本語"]
    lanes, prefix, last = doc_table._text_tables([t.encode() for t in texts])
    for j, t in enumerate(texts):
        data = t.encode()
        np.testing.assert_array_equal(lanes[j], _oracle_lanes(t))
        np.testing.assert_array_equal(key_lanes(t), _oracle_lanes(t))
        assert tuple(prefix[j]) == _str_prefix8(data)
        assert last[j] == (data[-1] if data else 0)
    assert lanes.dtype == prefix.dtype == last.dtype == np.uint32


def test_encode_document_is_the_one_row_case():
    doc = {"a": [1, "two", None], "b": {"c": True}}
    cols = encode_document(doc, max_nodes=16, max_depth=4)
    want = _oracle_document(doc, 16, 4)
    assert cols.keys() == want.keys()
    for name, w in want.items():
        assert np.asarray(cols[name]).dtype == np.asarray(w).dtype, name
        np.testing.assert_array_equal(cols[name], w, err_msg=name)
    assert encode_document(list(range(16)), max_nodes=16) is None


@pytest.mark.parametrize("workload", ["schemastore.taped", "schemastore.full"])
def test_benchmark_pool_encodes_bit_identically(workload):
    """Every submission of a benchmark cell's pool (seed 1), at the
    admission budget the benchmark's configuration sets."""
    sys.path.insert(0, str(ROOT))
    from bench.lib import spec, traffic as traffic_lib

    bench = spec.load_benchmark()
    cell = spec.cell(bench, workload)
    config = spec.config(bench, cell["config"])
    source = spec.documents(config["documents"]).build(config)
    max_nodes = config["admission_max_nodes"]
    for sub in traffic_lib.pool(source, spec.traffic(cell["traffic"]), seed=1):
        docs = [json.loads(t) for t in sub.texts]
        got = _both(docs, max_nodes=max_nodes, isolate=True)
        assert got.ok.mean() > 0.5

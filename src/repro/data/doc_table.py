"""JSON documents as columnar token tables (the TPU-native document form).

The paper's C++ executor chases pointers through a DOM; a TPU wants flat,
fixed-shape tensors.  We encode each parsed document as struct-of-arrays in
**BFS order**, which guarantees (a) a node's parent precedes it, and (b) the
children of every node are *contiguous* -- property matching and item loops
become range scans.  Key/string hashes are computed at encode time, exactly
as the paper computes hashes during parsing (§4.1).

One encoder, :func:`encode_batch`, in three steps: (1) one BFS walk per
document appends plain Python values to flat per-batch lists, with no
numpy call per node; (2) every key and string value is interned in a
per-batch table, so each distinct text is hashed (8 lanes, its 8-byte
prefix, its last byte) once per batch; (3) each column is scattered once
into an array preallocated at (B, N), gathering hashes by text id.  A row
that raises or runs out of budget is cut back out of the flat lists, so
it leaves no partial writes.  The intern table lives and dies inside one
call.  :func:`encode_document` is its one-row case.

Long-string caveat: the paper resolves long-string (>31 byte) hash
collisions with a full string comparison.  The batched executor cannot
pointer-chase into variable-length strings, so long strings additionally
carry a 64-bit FNV-1a hash in lanes 6-7 (which the paper's scheme leaves
zero).  A residual collision needs identical length, first/last byte, *and*
FNV64 -- probability ~2^-64.  The sequential executor remains the exact
conformance oracle.  This deviation is recorded in DESIGN.md §7.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..core.doc_model import HashedObject
from ..core.hashing import SHORT_LIMIT, shash_bytes
from ..core.nodetypes import T_ARR, T_BOOL, T_NULL, T_NUM, T_OBJ, T_STR, TYPE_CODES
from ..core.outcomes import fault_point

__all__ = ["TokenTable", "encode_document", "encode_batch", "key_lanes", "TYPE_CODES"]

_FNV_OFFSET = 0xCBF29CE484222325
_FNV_PRIME = 0x100000001B3


def _fnv64(data: bytes) -> int:
    h = _FNV_OFFSET
    for b in data:
        h ^= b
        h = (h * _FNV_PRIME) & 0xFFFFFFFFFFFFFFFF
    return h


def key_lanes(s: str) -> np.ndarray:
    """8x uint32 lanes for a key/string: the paper's semi-perfect hash, with
    FNV64 strengthening in lanes 6-7 for long strings (batch mode only)."""
    return _text_tables([s.encode("utf-8")])[0][0]


@dataclass
class TokenTable:
    """Columnar encoding of a batch of documents, shape (B, N) per column."""

    node_type: np.ndarray  # int8   (B, N)
    is_int: np.ndarray  # bool     (B, N)
    num: np.ndarray  # float64    (B, N)   numeric value / bool as 0,1
    size: np.ndarray  # int32     (B, N)   str code points / arr len / obj props
    parent: np.ndarray  # int32   (B, N)   -1 for root
    depth: np.ndarray  # int32    (B, N)
    idx_in_parent: np.ndarray  # int32 (B, N)  array index or object slot
    child_start: np.ndarray  # int32 (B, N)  BFS-contiguous children
    key_hash: np.ndarray  # uint32 (B, N, 8)  hash of member key (else 0)
    str_hash: np.ndarray  # uint32 (B, N, 8)  hash of string value (else 0)
    str_prefix: np.ndarray  # uint32 (B, N, 2)  first 8 bytes of string value
    str_last: np.ndarray  # uint32 (B, N)  last byte of string value
    n_nodes: np.ndarray  # int32  (B,)
    ok: np.ndarray  # bool (B,)  encoded within budget
    # row index -> error message for rows whose *encode* raised (isolated
    # faults, not budget overflows); those rows also have ok=False.
    errors: Dict[int, str] = field(default_factory=dict)

    @property
    def batch(self) -> int:
        return self.node_type.shape[0]

    @property
    def max_nodes(self) -> int:
        return self.node_type.shape[1]

    def columns(self) -> Dict[str, np.ndarray]:
        return {
            "node_type": self.node_type,
            "is_int": self.is_int,
            "num": self.num,
            "size": self.size,
            "parent": self.parent,
            "depth": self.depth,
            "idx_in_parent": self.idx_in_parent,
            "child_start": self.child_start,
            "key_hash": self.key_hash,
            "str_hash": self.str_hash,
            "str_prefix": self.str_prefix,
            "str_last": self.str_last,
            "n_nodes": self.n_nodes,
            "ok": self.ok,
        }

    def take(self, rows: Sequence[int]) -> "TokenTable":
        """Row-slice a sub-batch (used by the bisecting launch isolator)."""
        idx = np.asarray(rows, np.int64)
        cols = {k: v[idx] for k, v in self.columns().items()}
        remap = {int(r): j for j, r in enumerate(idx)}
        errs = {remap[r]: m for r, m in self.errors.items() if r in remap}
        return TokenTable(errors=errs, **cols)


def encode_document(
    doc: Any,
    max_nodes: int = 256,
    max_depth: int = 16,
) -> Optional[Dict[str, np.ndarray]]:
    """Encode one parsed JSON value into single-document columns (N,).

    The one-row case of :func:`encode_batch`.  Returns None when the
    document exceeds the node or depth budget (callers fall back to the
    sequential executor).
    """
    table = encode_batch([doc], max_nodes, max_depth)
    if not table.ok[0]:
        return None
    cols = {k: v[0] for k, v in table.columns().items() if k not in ("n_nodes", "ok")}
    cols["n_nodes"] = table.n_nodes[0]
    return cols


def _text_tables(texts: List[bytes]) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per distinct text: its 8 hash lanes (S, 8), 8-byte prefix (S, 2)
    and last byte (S,), all uint32."""
    n = len(texts)
    packed = b"".join([shash_bytes(d).to_bytes(32, "big") for d in texts])
    lanes = np.frombuffer(packed, ">u4").reshape(n, 8).astype(np.uint32)
    for j, data in enumerate(texts):
        if len(data) > SHORT_LIMIT:
            fnv = _fnv64(data)
            lanes[j, 6] = fnv >> 32
            lanes[j, 7] = fnv & 0xFFFFFFFF
    heads = b"".join([d[:8].ljust(8, b"\x00") for d in texts])
    prefix = np.frombuffer(heads, ">u4").reshape(n, 2).astype(np.uint32)
    last = np.array([d[-1] if d else 0 for d in texts], np.uint32)
    return lanes, prefix, last


def encode_batch(
    docs: List[Any],
    max_nodes: int = 256,
    max_depth: int = 16,
    *,
    isolate: bool = False,
    keys: Optional[Sequence[Any]] = None,
) -> TokenTable:
    """Encode a batch of documents; oversize docs get ok=False rows.

    One BFS walk per document appends plain Python values to flat
    per-batch lists (the node's position in them is its row's offset
    plus its BFS index); every key and string value is interned once per
    batch and hashed once per distinct text; then each column is
    scattered once into an array preallocated at (B, N).  Nothing
    outlives the call.

    With ``isolate=True`` a per-document encode exception (including an
    injected ``"encode"`` fault and ``RecursionError`` on hostile
    nesting) is trapped into ``TokenTable.errors[row]`` instead of
    aborting the whole batch; the poisoned row becomes an all-zero
    ok=False row, so every other row encodes bit-identically to a
    poison-free run.  ``keys`` names each row at the fault seam
    (defaults to the row index).
    """
    batch = len(docs)
    ok = np.zeros(batch, bool)
    n_nodes = np.zeros(batch, np.int32)
    errors: Dict[int, str] = {}
    # one entry per node, in BFS order (appended when the node is queued)
    parent: List[int] = []
    depth: List[int] = []
    idx: List[int] = []
    raw_key: List[Any] = []
    # one entry per node, appended when the node is dequeued
    ntype: List[int] = []
    # (node, value) pairs for the nodes that carry the column
    key_at: List[int] = []
    key_id: List[int] = []
    str_at: List[int] = []
    str_id: List[int] = []
    num_at: List[int] = []
    num: List[float] = []
    num_int: List[bool] = []
    box_at: List[int] = []
    box_size: List[int] = []
    box_start: List[int] = []
    flat = (parent, depth, idx, raw_key, ntype, key_at, key_id, str_at,
            str_id, num_at, num, num_int, box_at, box_size, box_start)
    # text -> id; the batch's distinct keys and strings, as UTF-8 and in
    # code points
    intern: Dict[str, int] = {}
    texts: List[bytes] = []
    chars: List[int] = []

    for b, doc in enumerate(docs):
        marks = [len(col) for col in flat]
        base = marks[0]
        fits = True
        try:
            if isolate:
                fault_point("encode", keys[b] if keys is not None else b)
            queue = [doc]
            parent.append(-1)
            depth.append(0)
            idx.append(-1)
            raw_key.append(None)
            # the queue grows while it is read: BFS index i is node base + i
            for i, value in enumerate(queue):
                f = base + i
                d = depth[f]
                if i >= max_nodes or d > max_depth:
                    fits = False
                    break
                k = raw_key[f]
                if k is not None:
                    kid = intern.get(k)
                    if kid is None:
                        data = k.encode("utf-8")
                        kid = intern[k] = len(texts)
                        texts.append(data)
                        chars.append(len(k))
                    key_at.append(f)
                    key_id.append(kid)
                if isinstance(value, str):
                    sid = intern.get(value)
                    if sid is None:
                        data = value.encode("utf-8")
                        sid = intern[value] = len(texts)
                        texts.append(data)
                        chars.append(len(value))
                    ntype.append(T_STR)
                    str_at.append(f)
                    str_id.append(sid)
                elif isinstance(value, (dict, HashedObject, list)):
                    n = len(value)
                    is_list = isinstance(value, list)
                    ntype.append(T_ARR if is_list else T_OBJ)
                    box_at.append(f)
                    box_size.append(n)
                    box_start.append(len(queue))
                    if n:
                        if is_list:
                            queue.extend(value)
                            raw_key.extend([None] * n)
                        else:
                            queue.extend(value.values())
                            raw_key.extend(value.keys())
                        parent.extend([i] * n)
                        depth.extend([d + 1] * n)
                        idx.extend(range(n))
                elif value is None:
                    ntype.append(T_NULL)
                elif isinstance(value, bool):
                    ntype.append(T_BOOL)
                    num_at.append(f)
                    num.append(1.0 if value else 0.0)
                    num_int.append(False)
                elif isinstance(value, (int, float)):
                    x = float(value)
                    ntype.append(T_NUM)
                    num_at.append(f)
                    num.append(x)
                    num_int.append(isinstance(value, int) or x.is_integer())
                else:
                    raise TypeError(f"unsupported JSON value {type(value)!r}")
        except RecursionError:
            if not isolate:
                raise
            errors[b] = "encode recursion limit exceeded"
            fits = False
        except Exception as exc:  # isolated per-document fault
            if not isolate:
                raise
            errors[b] = f"{type(exc).__name__}: {exc}"
            fits = False
        if fits:
            ok[b] = True
            n_nodes[b] = len(queue)
        else:
            for col, mark in zip(flat, marks):
                del col[mark:]

    shape = (batch, max_nodes)
    cols = {
        "node_type": np.zeros(shape, np.int8),
        "is_int": np.zeros(shape, bool),
        "num": np.zeros(shape, np.float64),
        "size": np.zeros(shape, np.int32),
        "parent": np.full(shape, -1, np.int32),
        "depth": np.zeros(shape, np.int32),
        "idx_in_parent": np.full(shape, -1, np.int32),
        "child_start": np.zeros(shape, np.int32),
        "key_hash": np.zeros(shape + (8,), np.uint32),
        "str_hash": np.zeros(shape + (8,), np.uint32),
        "str_prefix": np.zeros(shape + (2,), np.uint32),
        "str_last": np.zeros(shape, np.uint32),
    }
    # dest[f]: flat (B * N) position of node f; rows that did not encode
    # hold no nodes
    dest = np.flatnonzero(np.arange(max_nodes) < n_nodes[:, None])

    def scatter(name: str, at: Optional[List[int]], values: Any) -> None:
        col = cols[name]
        col.reshape((-1,) + col.shape[2:])[dest if at is None else dest[at]] = values

    scatter("node_type", None, ntype)
    scatter("parent", None, parent)
    scatter("depth", None, depth)
    scatter("idx_in_parent", None, idx)
    scatter("num", num_at, num)
    scatter("is_int", num_at, num_int)
    scatter("size", box_at, box_size)
    scatter("child_start", box_at, box_start)
    lanes, prefix, last = _text_tables(texts)
    sids = np.asarray(str_id, np.intp)
    scatter("key_hash", key_at, lanes[np.asarray(key_id, np.intp)])
    scatter("str_hash", str_at, lanes[sids])
    scatter("str_prefix", str_at, prefix[sids])
    scatter("str_last", str_at, last[sids])
    scatter("size", str_at, np.asarray(chars, np.int32)[sids])
    # rows that did not encode are all zero, -1 fills included
    cols["parent"][~ok] = 0
    cols["idx_in_parent"][~ok] = 0
    return TokenTable(n_nodes=n_nodes, ok=ok, errors=errors, **cols)

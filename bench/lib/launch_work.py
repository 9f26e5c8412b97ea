"""The least work a batched launch has to do, from its own inputs and
outputs.

A launch validates ``batch`` documents of at most ``max_nodes`` nodes
each against one linked tape.  Whatever implements it (Pallas kernels or
``jax.numpy``), it has to read every column of the token table, the
schema ids and the tape, and write three flags per document (valid,
within the depth budget, at an unroll frontier).  In operations, each
node has to be compared with each of the (at most ``k_cand``) property
rows that share its key hash, and evaluated against each of the (at
most ``n_window``) assertion rows of its location.

The least time is the larger of bytes over peak bandwidth and operations
over peak integer rate (the int8 matrix-unit peak: integer comparisons
run on the vector units, whose peak is lower, so this overstates the
peak and understates the least time; the share can only come out low).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict


@dataclass(frozen=True)
class LaunchWork:
    bytes: int
    ops: int

    def least_s(self, peaks: Dict[str, float]) -> float:
        return max(self.bytes / peaks["hbm_bytes_per_s"], self.ops / peaks["int8_ops_per_s"])

    def bound(self, peaks: Dict[str, float]) -> str:
        return "bytes" if self.bytes / peaks["hbm_bytes_per_s"] >= self.ops / peaks["int8_ops_per_s"] else "ops"


OUTPUT_FLAGS = 3  # valid, in_depth, frontier: one bool each per document
SCHEMA_ID_BYTES = 4  # int32 per document


def device_bytes(array) -> int:
    """Bytes of a host array once JAX has put it on the device (64-bit
    types become 32-bit ones unless 64-bit mode is on)."""
    import jax
    import numpy as np

    return int(np.prod(array.shape, dtype=np.int64)) * jax.dtypes.canonicalize_dtype(array.dtype).itemsize


def launch_work(
    batch: int,
    max_nodes: int,
    doc_bytes: int,
    tape_bytes: int,
    k_cand: int,
    n_window: int,
) -> LaunchWork:
    """``doc_bytes``: one document's row of every token-table column, at
    ``max_nodes`` nodes, as the device holds it."""
    nbytes = batch * (doc_bytes + SCHEMA_ID_BYTES + OUTPUT_FLAGS) + tape_bytes
    ops = batch * max_nodes * (max(1, k_cand) + max(1, n_window))
    return LaunchWork(nbytes, ops)

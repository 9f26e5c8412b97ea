"""Jit'd public wrappers around the Pallas kernels.

Pads inputs to block multiples, dispatches to the Pallas kernel (interpret
mode on CPU, compiled on TPU) or to the pure-jnp reference when
``use_pallas=False``, and strips padding from the result.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import assertion_eval as _ae
from . import hash_match as _hm
from . import ref as _ref

__all__ = ["hash_match", "assertion_eval_window"]


def _interpret_default() -> bool:
    return jax.default_backend() == "cpu"


def _pad_to(x: jax.Array, size: int, axis: int = 0, fill=0):
    pad = size - x.shape[axis]
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _round_up(n: int, block: int) -> int:
    return max(block, ((n + block - 1) // block) * block)


def _with_acquired(node_cols: dict) -> dict:
    """Default the acquired-slot bitmask to zeros for callers without one.

    OBJ_HAS_SLOT rows only appear on tapes with logical-applicator
    circuits; plain callers (kernel tests over circuit-free rows) need
    not thread the column through.
    """
    if "acquired" in node_cols:
        return node_cols
    out = dict(node_cols)
    out["acquired"] = jnp.zeros_like(node_cols["size"])
    return out


@functools.partial(
    jax.jit, static_argnames=("block_n", "block_m", "use_pallas", "interpret")
)
def hash_match(
    q_lanes: jax.Array,
    q_owner: jax.Array,
    t_lanes: jax.Array,
    t_owner: jax.Array,
    *,
    block_n: int = _hm.BLOCK_N,
    block_m: int = _hm.BLOCK_M,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """(N,) int32 minimal matching table row or -1 (see hash_match.py)."""
    if not use_pallas:
        return _ref.hash_match_ref(q_lanes, q_owner, t_lanes, t_owner)
    interpret = _interpret_default() if interpret is None else interpret
    n, m = q_lanes.shape[0], t_lanes.shape[0]
    np_, mp = _round_up(n, block_n), _round_up(m, block_m)
    out = _hm.hash_match_pallas(
        _pad_to(q_lanes, np_),
        # padded queries get owner -1; padded table rows owner -9 -> no match
        _pad_to(q_owner, np_, fill=-1),
        _pad_to(t_lanes, mp),
        _pad_to(t_owner, mp, fill=-9),
        block_n=block_n,
        block_m=block_m,
        interpret=interpret,
    )
    return out[:n]


@functools.partial(
    jax.jit, static_argnames=("block_n", "use_pallas", "interpret")
)
def assertion_eval_window(
    node_cols: dict,
    w_cols: dict,
    *,
    block_n: int = _ae.BLOCK_N,
    use_pallas: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """(N, W) int8 pass matrix over pre-gathered CSR windows.

    ``w_cols`` holds per-node windowed operands (op/f0/i0/i1/u0/u1 of
    shape (N, W), hash of shape (N, W, 8)); masked slots must carry op=-1.
    """
    node_cols = _with_acquired(node_cols)
    if not use_pallas:
        return _ref.assertion_eval_window_ref(node_cols, w_cols)
    interpret = _interpret_default() if interpret is None else interpret
    n = node_cols["type"].shape[0]
    w = w_cols["op"].shape[1]
    np_ = _round_up(n, block_n)
    wp = _round_up(w, _ae.WINDOW_ALIGN)
    node_pad = {k: _pad_to(v, np_) for k, v in node_cols.items()}
    # padded slots get op -1 -> never selected -> result 0
    w_pad = {}
    for k, v in w_cols.items():
        v = _pad_to(v, np_, axis=0)
        w_pad[k] = _pad_to(v, wp, axis=1, fill=(-1 if k == "op" else 0))
    out = _ae.assertion_eval_window_pallas(
        node_pad, w_pad, block_n=block_n, interpret=interpret
    )
    return out[:n, :w]

"""Pure-jnp oracles for the Pallas kernels (per-kernel allclose targets)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ..core.nodetypes import (
    T_ARR as _T_ARR,
    T_BOOL as _T_BOOL,
    T_NULL as _T_NULL,
    T_NUM as _T_NUM,
    T_OBJ as _T_OBJ,
    T_STR as _T_STR,
)
from ..core.tape import AOP


def hash_match_ref(
    q_lanes: jax.Array,  # (N, 8) uint32
    q_owner: jax.Array,  # (N,)   int32
    t_lanes: jax.Array,  # (M, 8) uint32
    t_owner: jax.Array,  # (M,)   int32
) -> jax.Array:
    """(N,) int32: minimal matching table row or -1."""
    lane_eq = q_lanes[:, None, :] == t_lanes[None, :, :]  # (N, M, 8)
    matched = jnp.all(lane_eq, axis=-1) & (q_owner[:, None] == t_owner[None, :])
    big = jnp.int32(2**30)
    idx = jnp.where(matched, jnp.arange(t_lanes.shape[0], dtype=jnp.int32)[None, :], big)
    best = jnp.min(idx, axis=1)
    return jnp.where(best >= big, jnp.int32(-1), best)


def _eval_rows_ref(ntype, isint, num, size, acq, str_pfx0, str_pfx1, op, f0, i0, i1, u0, u1, hash_eq):
    """Mini-ISA row evaluation on already-broadcastable operands.

    ``hash_eq`` carries the 8-lane string-hash equality at the output
    shape; node operands are (N, 1) (``acq`` is the acquired required-slot
    bitmask), assertion operands (N, W).
    """
    out_shape = hash_eq.shape

    is_num = ntype == _T_NUM
    is_str = ntype == _T_STR
    is_arr = ntype == _T_ARR
    is_obj = ntype == _T_OBJ

    type_bit = jnp.left_shift(jnp.int32(1), ntype)
    r_type = ((type_bit & i0) != 0) & ((i1 == 0) | ~is_num | isint)

    r_ge = ~is_num | (num >= f0)
    r_gt = ~is_num | (num > f0)
    r_le = ~is_num | (num <= f0)
    r_lt = ~is_num | (num < f0)
    # NUM_MULTIPLE: decimal divisors (0.01) have no exact binary form, so
    # an exact quotient test would reject true decimal multiples
    # (19.99 % 0.01).  Tolerance on the quotient, relative to its
    # magnitude, matches the sequential executor's decimal-exact
    # semantics to within f32 representation error (DESIGN.md §7).
    # The 0.25 cap keeps the tolerance meaningful for large quotients:
    # without it, 1e-6*|q| crosses 0.5 near |q|~5e5 and every value
    # would pass (1000001 % 2 must stay False).  Past f32's integer
    # range the quotient itself is integral and indistinguishable --
    # the documented §7 precision caveat.
    q = num / jnp.where(f0 == 0, jnp.ones_like(f0), f0)
    q_near = jnp.floor(q + 0.5)
    q_tol = jnp.minimum(1e-6 * jnp.maximum(jnp.abs(q), 1.0), 0.25)
    r_mul = ~is_num | ((f0 != 0) & (jnp.abs(q - q_near) <= q_tol))

    r_str_min = ~is_str | (size >= i0)
    r_str_max = ~is_str | (size <= i0)
    r_arr_min = ~is_arr | (size >= i0)
    r_arr_max = ~is_arr | (size <= i0)
    r_obj_min = ~is_obj | (size >= i0)
    r_obj_max = ~is_obj | (size <= i0)

    len0 = jnp.minimum(i0, 4)
    len1 = jnp.maximum(i0 - 4, 0)
    shift0 = ((4 - len0) * 8).astype(jnp.uint32)
    shift1 = ((4 - len1) * 8).astype(jnp.uint32)
    full = jnp.uint32(0xFFFFFFFF)
    m0 = jnp.where(len0 == 0, jnp.uint32(0), (full >> shift0) << shift0)
    m1 = jnp.where(len1 == 0, jnp.uint32(0), (full >> shift1) << shift1)
    pfx_eq = ((str_pfx0 & m0) == (u0 & m0)) & ((str_pfx1 & m1) == (u1 & m1))
    r_prefix = ~is_str | (pfx_eq & (size >= i0))

    r_str_eq = jnp.broadcast_to(is_str, out_shape) & hash_eq
    r_str_eq_pre = jnp.broadcast_to(~is_str, out_shape) | hash_eq
    r_null = jnp.broadcast_to(ntype == _T_NULL, out_shape)
    r_bool = (ntype == _T_BOOL) & (num == f0)
    r_num_const = is_num & (num == f0)

    # OBJ_HAS_SLOT: acquired required-slot bit i0 (non-objects pass)
    slot_bit = (jnp.right_shift(acq, jnp.clip(i0, 0, 31)) & 1) != 0
    r_has_slot = ~is_obj | slot_bit

    result = jnp.zeros(out_shape, bool)
    for code, value in [
        (AOP.TYPE_MASK, r_type),
        (AOP.NUM_GE, r_ge),
        (AOP.NUM_GT, r_gt),
        (AOP.NUM_LE, r_le),
        (AOP.NUM_LT, r_lt),
        (AOP.NUM_MULTIPLE, r_mul),
        (AOP.STR_MINLEN, r_str_min),
        (AOP.STR_MAXLEN, r_str_max),
        (AOP.ARR_MINLEN, r_arr_min),
        (AOP.ARR_MAXLEN, r_arr_max),
        (AOP.OBJ_MINPROPS, r_obj_min),
        (AOP.OBJ_MAXPROPS, r_obj_max),
        (AOP.STR_PREFIX, r_prefix),
        (AOP.STR_EQ, r_str_eq),
        (AOP.CONST_NULL, r_null),
        (AOP.CONST_BOOL, r_bool),
        (AOP.CONST_NUM, r_num_const),
        (AOP.STR_EQ_PRE, r_str_eq_pre),
        (AOP.OBJ_HAS_SLOT, r_has_slot),
    ]:
        result = jnp.where(op == code, jnp.broadcast_to(value, out_shape), result)
    return result


def assertion_eval_window_ref(node_cols: dict, w_cols: dict) -> jax.Array:
    """(N, W) int8 pass matrix -- mirror of the windowed Pallas kernel.

    ``w_cols`` holds per-node gathered CSR-window operands: op/f0/i0/i1/
    u0/u1 of shape (N, W) and hash of shape (N, W, 8).  Masked slots carry
    op=-1 and evaluate to 0.
    """
    ntype = node_cols["type"].astype(jnp.int32)[:, None]  # (N, 1)
    isint = node_cols["is_int"].astype(bool)[:, None]
    num = node_cols["num"][:, None]
    size = node_cols["size"].astype(jnp.int32)[:, None]
    acq = node_cols["acquired"].astype(jnp.int32)[:, None]
    str_hash = node_cols["str_hash"]  # (N, 8)
    str_pfx = node_cols["str_prefix"]  # (N, 2)

    op = w_cols["op"].astype(jnp.int32)  # (N, W)
    f0 = w_cols["f0"]
    i0 = w_cols["i0"].astype(jnp.int32)
    i1 = w_cols["i1"].astype(jnp.int32)
    u0 = w_cols["u0"]
    u1 = w_cols["u1"]
    w_hash = w_cols["hash"]  # (N, W, 8)

    hash_eq = jnp.all(str_hash[:, None, :] == w_hash, axis=-1)  # (N, W)
    result = _eval_rows_ref(
        ntype, isint, num, size, acq, str_pfx[:, 0:1], str_pfx[:, 1:2],
        op, f0, i0, i1, u0, u1, hash_eq,
    )
    return result.astype(jnp.int8)

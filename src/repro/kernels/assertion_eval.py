"""Pallas TPU kernel: fused, windowed assertion-tape evaluation.

One *fused* pass over VMEM-resident columns evaluates a branch-free op
selector (the tensorised version of the paper's CISC observation, §2.5
-- it beats dispatching many small instructions).  The executor gathers,
per node, only the <= A-hat rows of the node's own schema location
(owner-sorted CSR windows built at compile time in ``core.tape``) and
hands them over as (nodes x A-hat) operand planes
(``assertion_eval_window_pallas``).  Every op evaluates element-wise on
(BN, W) tiles -- O(N*A-hat) -- with no ownership masking needed
downstream (a masked slot carries op=-1 and evaluates to 0).

The kernel bakes in the paper's *precondition* semantics per op (wrong
type => pass for AND rows, => no-match for OR/const rows).  float32 is
used for numeric bounds on TPU (no native f64); the CPU reference path
keeps f64.  Precision caveat recorded in DESIGN.md §7.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..core.nodetypes import (
    T_ARR as _T_ARR,
    T_BOOL as _T_BOOL,
    T_NULL as _T_NULL,
    T_NUM as _T_NUM,
    T_OBJ as _T_OBJ,
    T_STR as _T_STR,
)
from ..core.tape import AOP

BLOCK_N = 256
# windowed kernel: window (A-hat) padded to a sublane multiple
WINDOW_ALIGN = 8


def _eval_rows(ntype, isint, num, size, acq, pfx0, pfx1, op, f0, i0, i1, u0, u1, hash_eq, out_shape):
    """Branch-free mini-ISA evaluation of one tile.

    Node operands are (BN, 1); assertion operands are (BN, W);
    ``hash_eq`` is the 8-lane string-hash equality matrix already
    broadcast to ``out_shape``.  ``acq`` is the node's acquired
    required-slot bitmask (the executor's location propagation computes
    it; OBJ_HAS_SLOT reads one bit).  All candidate
    results are computed unconditionally and combined with a select chain
    on the op code -- the VPU is wide enough that computing all candidates
    costs less than divergent control flow would.

    Returns an int32 0/1 matrix.  The chain selects int32 values, never
    booleans: Mosaic rejects the i8 -> i1 truncation that a boolean select
    or a boolean broadcast lowers to, so each candidate is widened once
    and the caller narrows once at the store.
    """
    is_num = ntype == _T_NUM
    is_str = ntype == _T_STR
    is_arr = ntype == _T_ARR
    is_obj = ntype == _T_OBJ

    # TYPE_MASK: node type bit in mask; integers-only via i1
    type_bit = jnp.left_shift(jnp.int32(1), ntype.astype(jnp.int32))
    in_mask = (type_bit & i0) != 0
    ints_ok = jnp.logical_or(
        jnp.logical_or(i1 == 0, jnp.logical_not(is_num)), isint
    )
    r_type = jnp.logical_and(in_mask, ints_ok)

    cmp_num = num
    r_ge = jnp.logical_or(~is_num, cmp_num >= f0)
    r_gt = jnp.logical_or(~is_num, cmp_num > f0)
    r_le = jnp.logical_or(~is_num, cmp_num <= f0)
    r_lt = jnp.logical_or(~is_num, cmp_num < f0)
    # NUM_MULTIPLE: tolerance on the quotient (same formula as the jnp
    # reference, bit-identical) -- exact f32 remainders would reject
    # decimal multiples like 19.99 % 0.01 whose divisor has no exact
    # binary representation.  Capped at 0.25 so large quotients keep
    # rejecting non-multiples (1000001 % 2 stays False).
    q = cmp_num / jnp.where(f0 == 0, jnp.ones_like(f0), f0)
    q_near = jnp.floor(q + 0.5)
    q_tol = jnp.minimum(1e-6 * jnp.maximum(jnp.abs(q), 1.0), 0.25)
    divisible = jnp.logical_and(f0 != 0, jnp.abs(q - q_near) <= q_tol)
    r_mul = jnp.logical_or(~is_num, divisible)

    r_str_min = jnp.logical_or(~is_str, size >= i0)
    r_str_max = jnp.logical_or(~is_str, size <= i0)
    r_arr_min = jnp.logical_or(~is_arr, size >= i0)
    r_arr_max = jnp.logical_or(~is_arr, size <= i0)
    r_obj_min = jnp.logical_or(~is_obj, size >= i0)
    r_obj_max = jnp.logical_or(~is_obj, size <= i0)

    # STR_PREFIX: compare first i0 (<=8) bytes; big-endian packing makes a
    # left-aligned byte mask expressible as integer shifts
    len0 = jnp.minimum(i0, 4)
    len1 = jnp.maximum(i0 - 4, 0)
    shift0 = (jnp.int32(4) - len0) * 8
    shift1 = (jnp.int32(4) - len1) * 8
    full = jnp.uint32(0xFFFFFFFF)
    m0 = jnp.where(len0 == 0, jnp.uint32(0), (full >> shift0.astype(jnp.uint32)) << shift0.astype(jnp.uint32))
    m1 = jnp.where(len1 == 0, jnp.uint32(0), (full >> shift1.astype(jnp.uint32)) << shift1.astype(jnp.uint32))
    pfx_eq = jnp.logical_and((pfx0 & m0) == (u0 & m0), (pfx1 & m1) == (u1 & m1))
    long_enough = size >= i0
    r_prefix = jnp.logical_or(~is_str, jnp.logical_and(pfx_eq, long_enough))

    # STR_EQ / const rows: exact-match semantics (no pass-on-skip)
    r_str_eq = jnp.logical_and(jnp.broadcast_to(is_str, out_shape), hash_eq)
    r_str_eq_pre = jnp.logical_or(jnp.broadcast_to(~is_str, out_shape), hash_eq)
    r_null = jnp.broadcast_to(ntype == _T_NULL, out_shape)
    is_bool = ntype == _T_BOOL
    r_bool = jnp.logical_and(is_bool, num == f0)
    r_num_const = jnp.logical_and(is_num, num == f0)

    # OBJ_HAS_SLOT: the object defines the property wired to slot i0
    # (precondition semantics: non-objects pass)
    slot_bit = (jnp.right_shift(acq, jnp.minimum(jnp.maximum(i0, 0), 31)) & 1) != 0
    r_has_slot = jnp.logical_or(~is_obj, slot_bit)

    candidates = [
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
    ]
    result = jnp.zeros(out_shape, jnp.int32)
    for code, value in candidates:
        wide = jnp.broadcast_to(value.astype(jnp.int32), out_shape)
        result = jnp.where(op == code, wide, result)
    return result


def _assertion_window_kernel(
    # node columns, (BN, 1) each unless noted
    n_type_ref,
    n_isint_ref,
    n_num_ref,
    n_size_ref,
    n_acq_ref,
    n_strhash_ref,  # (BN, 8) uint32
    n_strpfx_ref,  # (BN, 2) uint32
    # per-node windowed assertion operands, (BN, W) each unless noted
    a_op_ref,
    a_f0_ref,
    a_i0_ref,
    a_i1_ref,
    a_u0_ref,
    a_u1_ref,
    a_hash_ref,  # (BN, 8*W) uint32, lane-major: columns [lane*W, (lane+1)*W)
    out_ref,  # (BN, W) int8
    *,
    window: int,
):
    ntype = n_type_ref[...]  # (BN, 1)
    isint = n_isint_ref[...] != 0
    num = n_num_ref[...]
    size = n_size_ref[...]
    acq = n_acq_ref[...]
    pfx0 = n_strpfx_ref[:, 0].reshape(-1, 1)
    pfx1 = n_strpfx_ref[:, 1].reshape(-1, 1)

    op = a_op_ref[...]  # (BN, W)
    f0 = a_f0_ref[...]
    i0 = a_i0_ref[...]
    i1 = a_i1_ref[...]
    u0 = a_u0_ref[...]
    u1 = a_u1_ref[...]

    # eight element-wise lane comparisons on static (BN, W) slices
    hash_eq = jnp.ones(out_ref.shape, jnp.bool_)
    for lane in range(8):
        nh = n_strhash_ref[:, lane].reshape(-1, 1)
        ah = a_hash_ref[:, lane * window : (lane + 1) * window]
        hash_eq = jnp.logical_and(hash_eq, nh == ah)

    result = _eval_rows(
        ntype, isint, num, size, acq, pfx0, pfx1, op, f0, i0, i1, u0, u1, hash_eq, out_ref.shape
    )
    out_ref[...] = result.astype(jnp.int8)


def assertion_eval_window_pallas(
    node_cols: dict,
    w_cols: dict,
    *,
    block_n: int = BLOCK_N,
    interpret: bool = False,
) -> jax.Array:
    """Returns (N, W) int8 pass matrix for pre-gathered CSR windows.

    node_cols: type/is_int/num/size/acquired (N,), str_hash (N,8),
    str_prefix (N,2)
    w_cols: op/f0/i0/i1/u0/u1 (N, W), hash (N, W, 8).  Masked window slots
    must carry op=-1 (evaluate to 0).  Caller pads N to a block multiple
    and W to a sublane multiple.
    """
    n = node_cols["type"].shape[0]
    w = w_cols["op"].shape[1]
    assert n % block_n == 0 and w % WINDOW_ALIGN == 0, (n, w)
    grid = (n // block_n,)

    def col2d(x):
        return x.reshape(-1, 1)

    # lane-major hash layout keeps every kernel slice static and rank-2
    hash_lm = jnp.transpose(w_cols["hash"], (0, 2, 1)).reshape(n, 8 * w)

    n_spec = pl.BlockSpec((block_n, 1), lambda i: (i, 0))
    w_spec = pl.BlockSpec((block_n, w), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_assertion_window_kernel, window=w),
        grid=grid,
        in_specs=[
            n_spec,
            n_spec,
            n_spec,
            n_spec,
            n_spec,
            pl.BlockSpec((block_n, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_n, 2), lambda i: (i, 0)),
            w_spec,
            w_spec,
            w_spec,
            w_spec,
            w_spec,
            w_spec,
            pl.BlockSpec((block_n, 8 * w), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, w), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, w), jnp.int8),
        interpret=interpret,
    )(
        col2d(node_cols["type"].astype(jnp.int32)),
        col2d(node_cols["is_int"].astype(jnp.int32)),
        col2d(node_cols["num"]),
        col2d(node_cols["size"].astype(jnp.int32)),
        col2d(node_cols["acquired"].astype(jnp.int32)),
        node_cols["str_hash"],
        node_cols["str_prefix"],
        w_cols["op"].astype(jnp.int32),
        w_cols["f0"],
        w_cols["i0"].astype(jnp.int32),
        w_cols["i1"].astype(jnp.int32),
        w_cols["u0"],
        w_cols["u1"],
        hash_lm,
    )
    return out

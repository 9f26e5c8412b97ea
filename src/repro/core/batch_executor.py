"""Batched, TPU-native schema validation over token tables.

Validates B documents against one compiled location tape in a handful of
large tensor ops.  The tape may be a multi-member *linked* tape
(``registry/linker.py``): per-document ``schema_ids`` seed each root
from ``tape.roots`` and the hash pass becomes member-windowed, so one
kernel launch validates a heterogeneous (multi-schema) batch
bit-identically to per-schema dispatch (DESIGN.md §8).  The pipeline:

1. **Location propagation** -- one owner-blind ``hash_match`` pass over
   all B*N nodes finds each node's *candidate set*: the contiguous run of
   hash-sorted property rows sharing the node's key hash (<= K rows,
   K = ``tape.max_hash_run``).  The BFS-level loop (static, ``max_depth``
   iterations) then resolves each node's schema location from its
   parent's with a cheap owner-equality check over the K candidates --
   O(N*M + depth*N*K) instead of the historical O(depth*N*M) of running
   the full kernel every iteration.  Unmatched properties map to the
   location's additionalProperties location, ``UNTRACKED`` (no
   constraints below) or ``INVALID`` (closed object); array items follow
   the item/prefix rules.
2. **Required tracking** -- matched children scatter their required-slot
   bit into the parent's acquired mask; objects then check
   ``acquired & required == required``.
3. **Assertion evaluation** -- each node gathers only its own location's
   owner-sorted CSR window (<= A-hat rows, ``tape.max_rows_per_loc``) and
   the windowed ``assertion_eval`` kernel computes the (nodes x A-hat)
   pass matrix; enum OR-groups reduce with a segmented scan over the
   window (groups are contiguous by construction).  O(N*A-hat) memory and
   compute.
3b. **Circuit reduce** (DESIGN.md §10) -- rows wired to logical-applicator
   circuits (``anyOf``/``oneOf``/``not``/``if`` over the scalar subset)
   are excluded from the plain AND/OR reduction; per-document *anchor*
   node indices (one masked reduction per circuit-relevant location)
   feed tiny (B, U) leaf gathers, and a statically-unrolled bottom-up
   pass (trace depth bounded by the tape's ``max_circ_depth``) reduces
   the circuit (AND/OR/XOR1-count/NOT), gating every node on its owner
   location's presence so absent targets stay vacuously true and other
   members' circuits are no-ops on a linked tape.  Root values AND into
   the verdict.
4. **Reduce** -- AND over nodes per document, plus a per-document
   ``decided`` flag: nodes deeper than the ``max_depth`` budget never
   receive a location, so their documents are flagged undecided and must
   be routed to the sequential executor (mirroring the encoder budget in
   ``TokenTable.ok``) instead of vacuously passing.  Documents whose
   recursion outran the tape's $ref-unroll budget carry ``LOC_FRONTIER``
   nodes and are likewise undecided (``validate_ex`` exposes the flag so
   callers can count those ``unroll_overflow`` fallbacks separately).

The per-document fail-fast of the sequential engine becomes batch-level
work (§2.3 short-circuiting has no analogue across a converged batch); the
compile-time *reordering* optimizations still apply because they shrink
the tape itself.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ops as kops
from ..obs.compiles import KINDS as _COMPILE_KINDS, compile_events
from ..obs.trace import span as _span, trace_point as _trace_point
from .explain import KIND_CIRCUIT, FailureSite, resolve_site
from .nodetypes import T_ARR as _T_ARR, T_OBJ as _T_OBJ
from .outcomes import fault_hook_armed, fault_point
from .tape import (
    CK_AND,
    CK_NOT,
    CK_OR,
    LOC_FRONTIER,
    LOC_INVALID,
    LOC_UNTRACKED,
    LocationTape,
)

__all__ = ["BatchValidator"]

_BIG = jnp.int32(2**30)
_CIRC_FLAG = 1 << 20  # packed circuit-membership bit in the window gcode column


def _circuit_leaf_units(tape: LocationTape):
    """Static circuit-leaf wiring: which row/group feeds which node.

    Returns ``(and_units, group_units)``: AND units are
    ``(circ, owner_loc, window_slot)`` for plain circuit rows, group
    units ``(circ, owner_loc, window_slot_of_start)`` for circuit enum
    groups (rows are (owner, group)-sorted, so the first row of a group
    is its window start).  Everything here is compile-time.
    """
    owner = np.asarray(tape.asrt_owner)
    grp = np.asarray(tape.asrt_group)
    circ = np.asarray(tape.asrt_circ)
    start = np.asarray(tape.loc_asrt_start)
    and_units, group_units = [], []
    seen_groups = set()
    for r in range(len(owner)):
        c = int(circ[r])
        if c < 0:
            continue
        o = int(owner[r])
        g = int(grp[r])
        s = r - int(start[o])
        if g == 0:
            and_units.append((c, o, s))
        elif g not in seen_groups:
            seen_groups.add(g)
            group_units.append((c, o, s))
    return tuple(and_units), tuple(group_units)


def _circuit_static_wiring(tape: LocationTape):
    """All compile-time circuit metadata for the executor.

    Circuit work must not tax non-circuit traffic: every location a
    circuit touches (node owners + leaf-unit owners) gets a compact
    *anchor rank*, so the executor can resolve, per document, the single
    node at each such location (unique-path precondition) with ONE small
    scatter and evaluate leaves/presence as (B, U)/(B, C) gathers --
    never (B*N, U) masking over the whole batch.
    """
    and_units, group_units = _circuit_leaf_units(tape)
    circ_owner = np.asarray(tape.circ_owner, np.int32)
    unit_owners = [u[1] for u in and_units] + [u[1] for u in group_units]
    owner_locs = np.unique(
        np.concatenate([circ_owner, np.asarray(unit_owners, np.int32)])
    ) if (len(circ_owner) or unit_owners) else np.zeros(0, np.int32)
    rank_of = {l: r for r, l in enumerate(owner_locs.tolist())}
    return {
        "kind": np.asarray(tape.circ_kind, np.int32),
        "parent": np.asarray(tape.circ_parent, np.int32),
        "owner": circ_owner,
        "and_units": and_units,
        "group_units": group_units,
        "owner_locs": owner_locs,
        "circ_ranks": np.asarray([rank_of[int(l)] for l in circ_owner], np.int32),
        "and_ranks": np.asarray([rank_of[u[1]] for u in and_units], np.int32),
        "group_ranks": np.asarray([rank_of[u[1]] for u in group_units], np.int32),
    }


def _window_table(tape: LocationTape, n_window: int) -> np.ndarray:
    """Per-location assertion windows as packed uint32 rows, (L + 1, 15 W).

    Row ``l`` holds location ``l``'s W window slots column by column:
    ``op``, ``f0`` (float32 bits), ``i0``, ``i1``, ``u0``, ``u1``,
    ``gcode`` (W words each), then the 8 hash lanes slot by slot (8 W
    words).  Slot ``s`` is assertion row ``loc_asrt_start[l] + s``; slots
    at or past ``loc_asrt_len[l]`` carry ``op = -1`` and zeros.  The last
    row is all masked slots, the row of every untracked node.  A launch
    reads a node's whole window with one row gather instead of one
    element gather per column and slot.
    """
    # the packed gcode column reserves bit 20 for circuit membership: a
    # linked tape accumulating that many distinct OR-group ids must fail
    # loudly, never silently misdecode enum rows as circuit rows
    assert int(np.asarray(tape.asrt_group).max(initial=0)) < _CIRC_FLAG, (
        "OR-group id space exceeds the gcode circuit-flag bit"
    )
    # op -1 marks a masked slot, so no real row may carry it
    assert int(np.asarray(tape.asrt_op).min(initial=0)) >= 0
    gcode = np.asarray(tape.asrt_group) + np.where(
        np.asarray(tape.asrt_circ) >= 0, _CIRC_FLAG, 0
    )
    start = np.append(np.asarray(tape.loc_asrt_start), 0)
    length = np.append(np.asarray(tape.loc_asrt_len), 0)
    slots = np.arange(n_window)
    rows = np.minimum(start[:, None] + slots, len(tape.asrt_op) - 1)  # (L+1, W)
    valid = slots < length[:, None]

    def col(values, dtype, fill=0):
        x = np.asarray(values).astype(dtype)[rows]
        return np.where(valid, x, np.asarray(fill, dtype)).view(np.uint32)

    hash_w = np.where(valid[..., None], np.asarray(tape.asrt_hash, np.uint32)[rows], 0)
    return np.concatenate(
        [
            col(tape.asrt_op, np.int32, -1),
            col(tape.asrt_f0, np.float32),
            col(tape.asrt_i0, np.int32),
            col(tape.asrt_i1, np.int32),
            col(tape.asrt_u0, np.uint32),
            col(tape.asrt_u1, np.uint32),
            col(gcode, np.int32),
            hash_w.reshape(len(start), n_window * 8),
        ],
        axis=1,
    )


def _candidate_table(tape: LocationTape, k_cand: int) -> np.ndarray:
    """Per-run-start candidate rows as packed int32 rows, (M + 1, 4 K).

    Row ``r`` holds psort rows ``r .. r + K - 1`` column by column:
    ``psort_owner``, ``psort_child_loc``, ``psort_required_slot``,
    ``psort_orig_row`` (K words each).  Slots at or past
    ``psort_run_len[r]`` carry owner -1 and zeros; the last row, read by
    nodes with no candidate run, is all such slots.
    """
    run_len = np.append(np.asarray(tape.psort_run_len), 0)
    k = np.arange(k_cand)
    rows = np.minimum(np.arange(len(run_len))[:, None] + k, len(tape.psort_owner) - 1)
    valid = k < run_len[:, None]
    return np.concatenate(
        [
            np.where(valid, np.asarray(tape.psort_owner)[rows], -1),
            np.where(valid, np.asarray(tape.psort_child_loc)[rows], 0),
            np.where(valid, np.asarray(tape.psort_required_slot)[rows], 0),
            np.where(valid, np.asarray(tape.psort_orig_row)[rows], 0),
        ],
        axis=1,
    ).astype(np.int32)


def _segment_tables(tape: LocationTape, m_hat: int) -> Tuple[np.ndarray, np.ndarray]:
    """Per-member psort segments: ``(hash (S, 8, M-hat) uint32, row (S,
    M-hat) int32)``.  Member ``s``'s slot ``j`` is psort row
    ``member_prop_start[s] + j``; slots at or past ``member_prop_len[s]``
    carry row ``_BIG`` (never a match) and zero lanes.  Each document
    reads its member's segment with one row gather."""
    start = np.asarray(tape.member_prop_start)
    j = np.arange(m_hat)
    rows = np.minimum(start[:, None] + j, len(tape.psort_hash) - 1)  # (S, Mh)
    valid = j < np.asarray(tape.member_prop_len)[:, None]
    seg_hash = np.where(valid[..., None], np.asarray(tape.psort_hash, np.uint32)[rows], 0)
    seg_row = np.where(valid, rows, int(_BIG)).astype(np.int32)
    return np.ascontiguousarray(seg_hash.transpose(0, 2, 1)), seg_row


def _tape_consts(tape: LocationTape, n_window: int, k_cand: int, m_hat: int) -> Dict[str, jnp.ndarray]:
    seg_hash, seg_row = _segment_tables(tape, m_hat)
    return {
        "psort_hash": jnp.asarray(tape.psort_hash),
        "psort_owner": jnp.asarray(tape.psort_owner),
        # packed tape-constant rows keyed by what varies per node: the
        # candidate run by its start row, the assertion window by
        # location, the psort segment by member (DESIGN.md §4, §5, §8)
        "cand": jnp.asarray(_candidate_table(tape, k_cand)),
        "prefix_loc": jnp.asarray(tape.prefix_loc),
        # packed per-location structural row: one gather per depth
        # iteration instead of six (addl, closed, item, item_start,
        # prefix_start, prefix_len)
        "loc_struct": jnp.stack(
            [
                jnp.asarray(tape.loc_addl),
                jnp.asarray(tape.loc_closed.astype(np.int32)),
                jnp.asarray(tape.loc_item),
                jnp.asarray(tape.loc_item_start),
                jnp.asarray(tape.loc_prefix_start),
                jnp.asarray(tape.loc_prefix_len),
            ],
            axis=1,
        ),
        "loc_required_mask": jnp.asarray(tape.loc_required_mask.astype(np.int32)),
        "loc_asrt_start": jnp.asarray(tape.loc_asrt_start),  # explain's row ids
        "win": jnp.asarray(_window_table(tape, n_window)),
        "psort_member": jnp.asarray(tape.psort_member),
        # a frontier root (degenerate: the unroll budget died at the
        # root) must seed documents with the sentinel, not location 0
        "roots": jnp.asarray(
            np.where(tape.loc_frontier[tape.roots], LOC_FRONTIER, tape.roots).astype(
                np.int32
            )
        ),
        "member_horizons": jnp.asarray(tape.member_horizons),
        "seg_hash": jnp.asarray(seg_hash),
        "seg_row": jnp.asarray(seg_row),
    }


def _named(fn, **static):
    """``fn`` with its static arguments bound, under ``fn``'s own name:
    ``jax.jit`` names the program ``jit_<name>`` (a bare partial would
    be ``jit__unknown``), which is how a profiler trace tells the launch
    from the explain program."""
    bound = functools.partial(fn, **static)
    bound.__name__ = fn.__name__
    return bound


class BatchValidator:
    """Validates encoded token-table batches against one schema tape."""

    def __init__(
        self,
        tape: LocationTape,
        *,
        max_depth: int = 16,
        use_pallas: bool = True,
        metrics=None,
    ):
        self.tape = tape
        self.max_depth = max_depth
        self.use_pallas = use_pallas
        # optional MetricRegistry (obs/metrics.py): children are cached
        # here once so the per-launch hot path is attribute adds gated on
        # one ``is not None`` check (DESIGN.md §12)
        self.metrics = metrics
        if metrics is not None:
            self._m_launches = metrics.counter(
                "executor_launches_total", "batched kernel launches"
            )
            self._m_launch_seconds = metrics.counter(
                "executor_launch_seconds_total",
                "wall seconds inside batched launches (device sync included)",
            )
            self._m_recompiles = metrics.counter(
                "executor_recompiles_total",
                "backend compiles (or persistent-cache reads) inside launches",
            )
            self._m_compiles = {
                kind: metrics.counter(
                    "executor_compiles_total",
                    "JAX compile events inside launches, by kind",
                    kind=kind,
                )
                for kind in _COMPILE_KINDS
            }
            self._m_bisect_depth = metrics.histogram(
                "executor_bisect_depth",
                "poison-isolation bisection depth per isolated validate",
                buckets=tuple(float(d) for d in range(13)),
            )
        # (B, max_nodes) shapes that have launched: warm() skips them, and
        # validate_isolated contains failures only under them
        self._seen_shapes: set = set()
        self._compiles = compile_events()
        # compile-time window bounds (clamped: the kernels need >= 1 slot)
        self.n_window = max(1, tape.max_rows_per_loc)
        self.k_cand = max(1, tape.max_hash_run)
        self.m_hat = max(1, tape.max_member_props)
        # static: tapes without frontier locations skip the detection scan
        self.has_frontier = tape.n_frontier > 0
        # logical-applicator circuits (DESIGN.md §10): all wiring is
        # compile-time -- kept as host numpy so the per-level reduce can
        # slice/scatter with static indices.  Circuit-free tapes (the
        # common case) statically skip every circuit op.
        self.n_circuits = tape.n_circuits
        self._circuits = _circuit_static_wiring(tape)
        self._consts = _tape_consts(tape, self.n_window, self.k_cand, self.m_hat)
        static = dict(
            consts=self._consts,
            max_depth=max_depth,
            max_loc_depth=tape.max_loc_depth,
            use_pallas=use_pallas,
            n_window=self.n_window,
            k_cand=self.k_cand,
            n_members=tape.n_members,
            circuits=self._circuits,
            n_circuits=self.n_circuits,
        )
        self._fn = jax.jit(
            _named(_validate_batch, has_frontier=self.has_frontier, **static)
        )
        # traced on its first call only, so traffic that never asks for
        # explanations never compiles it
        self._explain_fn = jax.jit(_named(_explain_batch, **static))

    def validate(self, table, schema_ids=None) -> Tuple[np.ndarray, np.ndarray]:
        """Returns (valid, decided) boolean arrays of shape (B,).

        ``schema_ids`` selects each document's member of a linked tape
        (``registry/linker.py``): document b's root node is seeded with
        ``tape.roots[schema_ids[b]]``.  Single-member tapes (the default)
        accept the implicit all-zeros vector.

        ``decided=False`` rows exceeded the encoder budget, contain
        nodes deeper than this validator's ``max_depth`` (which the
        location loop never reaches), *or* reached a ``LOC_FRONTIER``
        sentinel (the tape's $ref-unroll budget ran out below them); all
        must be routed to the sequential executor -- their ``valid``
        entry is meaningless.
        """
        valid, decided, _ = self.validate_ex(table, schema_ids)
        return valid, decided

    def validate_ex(
        self, table, schema_ids=None
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Like :meth:`validate` plus the per-doc ``frontier`` flag.

        ``frontier[b]`` is True when document b reached an unroll
        frontier -- one of the three undecided causes (the others being
        encoder oversize and the depth budget), kept separate so callers
        can count ``unroll_overflow`` fallbacks distinctly.
        """
        B = table.batch
        ids = self._normalize_ids(B, schema_ids)
        shape = (B, table.max_nodes)
        m = self.metrics
        if m is not None:
            t0 = time.perf_counter()
        before = self._compiles.snapshot()
        # host wall from the columns' transfer to numpy results
        with _span("executor.launch", batch=B) as sp:
            cols = {k: jnp.asarray(v) for k, v in table.columns().items()}
            valid, in_depth, frontier = self._fn(cols, jnp.asarray(ids))
            valid = np.asarray(valid)  # forces device sync inside the span
            in_depth = np.asarray(in_depth)
            frontier = np.asarray(frontier)
            sp.set(compiled=self._count_compiles(before, shape))
        self._seen_shapes.add(shape)
        if m is not None:
            self._m_launches.inc()
            self._m_launch_seconds.inc(time.perf_counter() - t0)
        decided = in_depth & ~frontier & np.asarray(table.ok)
        return valid, decided, frontier & np.asarray(table.ok)

    def _count_compiles(self, before: Dict[str, int], shape: Tuple[int, int]) -> bool:
        """Bill the compile events JAX reported since ``before`` to this
        executor; True when XLA built (or read back) an executable."""
        fired = self._compiles.since(before)
        if self.metrics is not None:
            for kind, n in fired.items():
                if n:
                    self._m_compiles[kind].inc(n)
            self._m_recompiles.inc(fired["compile"])
        if fired["compile"]:
            _trace_point("executor.recompile", shape=shape, **fired)
        return fired["compile"] > 0

    def seen_shapes(self) -> set:
        """Snapshot of the (B, max_nodes) shapes that have launched."""
        return set(self._seen_shapes)

    def warm(self, table, schema_ids=None) -> bool:
        """Pre-trace the launch for ``table``'s shape off the request
        path; returns True when a new shape was actually compiled.

        Streaming schedulers admit power-of-two buckets precisely so
        this set stays tiny; warming the expected buckets ahead of
        traffic keeps jit traces out of deadline-bounded drains.
        """
        if (table.batch, table.max_nodes) in self._seen_shapes:
            return False
        self.validate_ex(table, schema_ids)
        return True

    def _normalize_ids(self, B: int, schema_ids) -> np.ndarray:
        if schema_ids is None:
            if self.tape.n_members > 1:
                raise ValueError(
                    "linked tape: per-document schema_ids are required "
                    "(member 0 would otherwise be guessed silently)"
                )
            return np.zeros(B, np.int32)
        ids = np.asarray(schema_ids, np.int32)
        if ids.shape != (B,):
            raise ValueError(f"schema_ids shape {ids.shape} != ({B},)")
        if ids.size and (ids.min() < 0 or ids.max() >= self.tape.n_members):
            raise ValueError("schema_ids outside the tape's member range")
        return ids

    def validate_isolated(
        self, table, schema_ids=None, *, keys: Optional[Sequence[Any]] = None
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, Dict[int, str]]:
        """:meth:`validate_ex` with per-document launch-fault containment.

        A launch that raises (device error on a shape that has already
        compiled, injected ``"launch"`` fault) is bisected: rows are
        split in half and relaunched recursively
        until the poison is cornered in a single-row launch, whose error
        is recorded in ``errors[row]``; every other row's verdict is
        bit-identical to a fault-free run (the batched executor is
        row-independent, so sub-batch launches reproduce full-batch
        results exactly).  Worst case P poisoned rows cost
        O(P·log B) extra launches; halving keeps sub-batch shapes to at
        most log2(B) distinct jit traces.  Rows already error-isolated
        at encode time (``table.errors``) launch as zeroed ok=False rows
        and keep their encode error.

        The first launch of a shape is not contained: a failure to
        trace, lower or compile it says nothing about any row, so it
        propagates instead of turning every row into ERROR_ISOLATED.

        Returns ``(valid, decided, frontier, errors)``; ``errors`` rows
        are ERROR_ISOLATED -- callers must not route them to fallback.
        """
        B = table.batch
        ids = self._normalize_ids(B, schema_ids)
        row_keys = list(keys) if keys is not None else list(range(B))
        if len(row_keys) != B:
            raise ValueError(f"{len(row_keys)} keys for batch of {B}")
        valid = np.zeros(B, bool)
        decided = np.zeros(B, bool)
        frontier = np.zeros(B, bool)
        errors: Dict[int, str] = dict(table.errors)
        stack: List[Tuple[List[int], int]] = [(list(range(B)), 0)]
        max_bisect = 0  # deepest split reached while cornering poison
        while stack:
            rows, bdepth = stack.pop()
            full = len(rows) == B
            # the full-batch launch reuses the caller's table/ids objects:
            # a fresh ids copy per call would defeat the executor's
            # same-identity host->device transfer cache (~5% per launch)
            sub = table if full else table.take(rows)
            sub_ids = ids if full else ids[rows]
            first_launch = (sub.batch, sub.max_nodes) not in self._seen_shapes
            launched = False
            try:
                if fault_hook_armed():  # skip the key tuple on the clean path
                    fault_point("launch", tuple(row_keys[i] for i in rows))
                launched = True
                v, d, f = self.validate_ex(sub, sub_ids)
            except Exception as exc:
                if launched and first_launch:
                    raise  # the shape never compiled: no row is to blame
                if len(rows) == 1:
                    errors[rows[0]] = f"launch: {type(exc).__name__}: {exc}"
                    continue
                mid = len(rows) // 2
                stack.append((rows[mid:], bdepth + 1))
                stack.append((rows[:mid], bdepth + 1))
                if bdepth + 1 > max_bisect:
                    max_bisect = bdepth + 1
                    _trace_point("executor.bisect", depth=max_bisect)
                continue
            if full:
                valid[:] = v
                decided[:] = d
                frontier[:] = f
            else:
                valid[rows] = v
                decided[rows] = d
                frontier[rows] = f
        if self.metrics is not None:
            self._m_bisect_depth.observe(float(max_bisect))
        for r in errors:
            decided[r] = False
            frontier[r] = False
        return valid, decided, frontier, errors

    def explain_batch(
        self, table, schema_ids=None, *, docs: Optional[Sequence[Any]] = None
    ) -> List[Optional[FailureSite]]:
        """Batched first-failure attribution (DESIGN.md §12).

        Returns one entry per document: a :class:`FailureSite` where the
        batched pipeline attributes a failure, ``None`` where it finds
        none (the document is valid -- callers gate on their own
        verdicts and must not call this for undecided rows).  ``docs``
        (the original parsed documents, encode order) enables instance
        JSON pointers; without them ``instance_path`` stays empty.

        Tie-break contract: lowest BFS node first; within a node
        assertion-row < missing-required < closed-object, and among
        assertion rows the lowest row wins; structural failures beat
        circuit failures anchored at the same node, and among circuits
        the lowest circuit id wins.  Opt-in by construction -- the
        explain launch is a separate jitted function, so ``explain=False``
        traffic never pays for it.
        """
        B = table.batch
        ids = self._normalize_ids(B, schema_ids)
        if docs is not None and len(docs) != B:
            raise ValueError(f"{len(docs)} docs for batch of {B}")
        before = self._compiles.snapshot()
        with _span("executor.explain", batch=B) as sp:
            cols = {k: jnp.asarray(v) for k, v in table.columns().items()}
            out = self._explain_fn(cols, jnp.asarray(ids))
            sp.set(compiled=self._count_compiles(before, (B, table.max_nodes)))
        doc_key, bad_row, bad_loc, parent_loc, missing, root_fail, root_anchor = (
            np.asarray(x) for x in out
        )
        roots = _circuit_roots(self._circuits, self.n_circuits)
        big = int(_BIG)
        sites: List[Optional[FailureSite]] = []
        for b in range(B):
            doc = docs[b] if docs is not None else None
            skey = int(doc_key[b])  # structural pick: node*4 + kind
            ckey, circ = big, -1  # circuit pick: anchor*4 + KIND_CIRCUIT
            for j, r in enumerate(roots):
                if root_fail[b, j]:
                    anchor = int(root_anchor[b, j])
                    k = max(anchor, 0) * 4 + KIND_CIRCUIT
                    if k < ckey:
                        ckey, circ = k, r
            if skey >= big and ckey >= big:
                sites.append(None)
                continue
            if skey <= ckey:  # structural wins ties at the same node
                sites.append(
                    resolve_site(
                        self.tape,
                        kind=skey % 4,
                        node=skey // 4,
                        row=int(bad_row[b]),
                        loc=int(bad_loc[b]),
                        parent_loc=int(parent_loc[b]),
                        missing_mask=int(missing[b]) & 0xFFFFFFFF,
                        doc=doc,
                    )
                )
            else:
                sites.append(
                    resolve_site(
                        self.tape,
                        kind=KIND_CIRCUIT,
                        node=ckey // 4,
                        circ=circ,
                        doc=doc,
                    )
                )
        return sites


def _propagate_locations(
    cols,
    schema_ids,
    consts,
    *,
    loop_depth: int,
    use_pallas: bool,
    k_cand: int,
    n_members: int,
):
    """Assign every node a schema location; returns (loc, acquired, aux).

    ``aux`` carries the flat per-node columns reused by the caller.
    """
    B, N = cols["node_type"].shape
    flat = lambda x: x.reshape((B * N,) + x.shape[2:])

    node_type = flat(cols["node_type"]).astype(jnp.int32)
    parent = flat(cols["parent"])  # int32, -1 root
    depth = flat(cols["depth"])
    idx_in_parent = flat(cols["idx_in_parent"])
    key_hash = flat(cols["key_hash"])

    doc_base = jnp.repeat(jnp.arange(B, dtype=jnp.int32) * N, N)
    parent_flat = jnp.where(parent >= 0, doc_base + parent, 0)

    is_pad = node_type == 0

    # each document's root is its schema member's root location (plain
    # location 0 for single-member tapes)
    member = jnp.repeat(schema_ids.astype(jnp.int32), N)  # (B*N,)
    loc = jnp.where(
        jnp.arange(B * N, dtype=jnp.int32) % N == 0,
        jnp.repeat(consts["roots"][schema_ids], N),
        jnp.int32(-1),
    )
    acquired = jnp.zeros(B * N, jnp.int32)  # required-slot bits per object

    # loop-invariant node classification, shared by the hoisted hash pass
    # and the depth loop (one definition so they can never desynchronize)
    is_real = ~is_pad & (parent >= 0)
    parent_type = node_type[parent_flat]
    is_member_node = is_real & (parent_type == _T_OBJ)
    is_item_node = is_real & (parent_type == _T_ARR)

    # -- hoisted single hash pass: find each object-member node's
    # candidate-run start in its schema's hash-sorted property rows
    M = consts["psort_owner"].shape[0]
    if n_members == 1 or use_pallas:
        # hash_match kernel over the whole table, owner = the row's
        # member id (all zeros on a single tape): the kernel's minimal
        # matching row within the querying document's member is its
        # run start.  Streamed/blocked, so no giant gather -- the
        # right trade on the kernel path.  The empty-table placeholder
        # keeps owner -9 so all-zero key lanes cannot hit it
        t_owner0 = jnp.where(
            consts["psort_owner"] >= 0, consts["psort_member"], jnp.int32(-9)
        )
        q_owner0 = jnp.where(is_member_node, member, jnp.int32(-1))
        first = kops.hash_match(
            key_hash, q_owner0, consts["psort_hash"], t_owner0, use_pallas=use_pallas
        )
    else:
        # linked tape on the jnp path: member-windowed pass -- each
        # node scans only its member's psort segment (<= M-hat rows),
        # so per-node work tracks the *largest* member instead of the
        # member sum.  Each document reads its segment once; runs
        # never span members, so the minimal matching row in the
        # segment is the run start, exactly as the kernel branch
        # returns
        seg_hash = consts["seg_hash"][schema_ids]  # (B, 8, Mh)
        seg_row = consts["seg_row"][schema_ids]  # (B, Mh), _BIG past the segment
        q = key_hash.reshape(B, N, 8)
        hit = is_member_node.reshape(B, N)[:, :, None]
        for lane in range(8):
            hit = hit & (q[:, :, lane, None] == seg_hash[:, None, lane, :])
        first_row = jnp.min(jnp.where(hit, seg_row[:, None, :], _BIG), axis=2)
        first = jnp.where(first_row < _BIG, first_row, jnp.int32(-1)).reshape(B * N)
    # one packed row per node: its candidate run (the table's last
    # row, all owner -1, for nodes with no run)
    cand = consts["cand"][jnp.where(first >= 0, first, M)]  # (BN, 4K)
    cand_owner = cand[:, :k_cand]
    cand_valid = cand_owner >= 0
    cand_child = cand[:, k_cand : 2 * k_cand]
    cand_slot = cand[:, 2 * k_cand : 3 * k_cand]
    cand_orig = cand[:, 3 * k_cand :]

    # the required-bit contribution of every node is known the moment its
    # own depth iteration resolves it -- accumulate elementwise in the
    # loop and scatter ONCE afterwards instead of once per depth
    contrib_vec = jnp.zeros(B * N, jnp.int32)

    for d in range(1, loop_depth + 1):
        at_depth = depth == d
        parent_loc = loc[parent_flat]

        # -- object members: property-table match
        is_member = at_depth & is_member_node
        # owner-equality over the K pre-gathered candidates; ties break
        # to the minimal original row, the first row the tape emitted
        # for the key, so the pick never depends on the hash sort's
        # order.  Original rows are distinct, so the pick is one slot:
        # read it by an elementwise select, not a gather per node
        m = cand_valid & (cand_owner == parent_loc[:, None])
        best = jnp.min(jnp.where(m, cand_orig, _BIG), axis=1)
        matched = best < _BIG
        pick = m & (cand_orig == best[:, None])
        child_loc_m = jnp.sum(jnp.where(pick, cand_child, 0), axis=1)
        slot_m = jnp.sum(jnp.where(pick, cand_slot, 0), axis=1)
        child_loc = jnp.where(matched, child_loc_m, jnp.int32(LOC_UNTRACKED))
        # one packed row gather for the parent's structural facts
        p_loc_safe = jnp.where(parent_loc >= 0, parent_loc, 0)
        ls = consts["loc_struct"][p_loc_safe]  # (BN, 6)
        addl, closed = ls[:, 0], ls[:, 1]
        item_loc, item_start = ls[:, 2], ls[:, 3]
        pfx_start, pfx_len = ls[:, 4], ls[:, 5]
        # unmatched at a tracked object location: addl / closed / untracked
        # (an addl slot may carry the LOC_FRONTIER sentinel: recursion
        # through additionalProperties past the unroll budget)
        unmatched_loc = jnp.where(
            closed != 0,
            jnp.int32(LOC_INVALID),
            jnp.where(
                (addl >= 0) | (addl == LOC_FRONTIER),
                addl,
                jnp.int32(LOC_UNTRACKED),
            ),
        )
        member_loc = jnp.where(matched, child_loc, unmatched_loc)
        member_loc = jnp.where(parent_loc >= 0, member_loc, parent_loc)

        # required bit: record the contribution at the node's own depth
        slot = jnp.where(matched, slot_m, -1)
        contrib = jnp.where(
            is_member & (slot >= 0),
            jnp.left_shift(jnp.int32(1), jnp.maximum(slot, 0)),
            0,
        )
        contrib_vec = jnp.where(is_member, contrib, contrib_vec)

        # -- array items: prefix / tail-items rules
        is_item = at_depth & is_item_node
        in_prefix = idx_in_parent < pfx_len
        pfx_idx = jnp.clip(pfx_start + idx_in_parent, 0, consts["prefix_loc"].shape[0] - 1)
        prefix_loc = consts["prefix_loc"][pfx_idx]
        tail_loc = jnp.where(
            ((item_loc >= 0) | (item_loc == LOC_FRONTIER))
            & (idx_in_parent >= item_start),
            item_loc,
            jnp.int32(LOC_UNTRACKED),
        )
        arr_loc = jnp.where(in_prefix, prefix_loc, tail_loc)
        arr_loc = jnp.where(parent_loc >= 0, arr_loc, parent_loc)

        loc = jnp.where(
            is_member, member_loc, jnp.where(is_item, arr_loc, loc)
        )

    acquired = acquired.at[parent_flat].add(contrib_vec, mode="drop")

    aux = {
        "node_type": node_type,
        "is_pad": is_pad,
        "flat": flat,
        "B": B,
        "N": N,
    }
    return loc, acquired, aux


def _segment_or_suffix(vals: jnp.ndarray, grp: jnp.ndarray) -> jnp.ndarray:
    """Segmented suffix-OR along axis 1.

    ``out[:, j] = OR(vals[:, k] for k >= j while grp stays equal)`` --
    groups are contiguous within a CSR window, so evaluating at each
    segment start yields the whole group's OR.  Implemented as an
    associative segmented scan (O(log W) depth, static shapes).
    """
    same_next = jnp.concatenate(
        [grp[:, :-1] == grp[:, 1:], jnp.zeros_like(grp[:, :1], bool)], axis=1
    )
    rv = jnp.flip(vals, axis=1)
    rc = jnp.flip(same_next, axis=1)

    def combine(a, b):
        av, ac = a
        bv, bc = b
        return (bv | (bc & av), ac & bc)

    out, _ = jax.lax.associative_scan(combine, (rv, rc), axis=1)
    return jnp.flip(out, axis=1)


def _assertions_csr(
    loc,
    node_cols,
    consts,
    *,
    use_pallas: bool,
    n_window: int,
    n_circuits: int,
    detail=None,
):
    """Windowed assertion evaluation + segmented OR-group reduction.

    Returns ``(asrt_ok, passes, seg_any)``: the per-node verdict over
    *plain* rows (rows wired to a circuit are excluded from the plain
    reduction), plus the raw window pass matrix and per-window segmented
    group OR for the caller's circuit-leaf gathers (None without
    circuits).  ``detail`` (a dict, explain path only) receives the
    per-window intermediates so the first-failure pass can argmax over
    them without recomputing.
    """
    W = n_window
    tracked = loc >= 0
    # one packed row per node: its location's whole window (the table's
    # last row, all masked slots, for untracked nodes)
    win = consts["win"][jnp.where(tracked, loc, consts["win"].shape[0] - 1)]
    as_i32 = lambda x: jax.lax.bitcast_convert_type(x, jnp.int32)
    w_cols = {
        "op": as_i32(win[:, 0:W]),  # -1 on masked slots
        "f0": jax.lax.bitcast_convert_type(win[:, W : 2 * W], jnp.float32),
        "i0": as_i32(win[:, 2 * W : 3 * W]),
        "i1": as_i32(win[:, 3 * W : 4 * W]),
        "u0": win[:, 4 * W : 5 * W],
        "u1": win[:, 5 * W : 6 * W],
        "hash": win[:, 7 * W :].reshape(-1, W, 8),
    }
    passes = kops.assertion_eval_window(
        node_cols, w_cols, use_pallas=use_pallas
    ).astype(bool)  # (BN, W)

    w_valid = w_cols["op"] >= 0  # == "applies"
    gcode = as_i32(win[:, 6 * W : 7 * W])  # 0 on masked slots
    grp = gcode & jnp.int32(_CIRC_FLAG - 1)
    in_circ = gcode >= _CIRC_FLAG  # rows wired to a circuit
    is_and = w_valid & (grp == 0) & ~in_circ
    and_ok = jnp.all(jnp.where(is_and, passes, True), axis=1)

    # enum OR-groups: group passes iff any of its (contiguous) rows passes
    pass_or = passes & w_valid & (grp > 0)
    seg_any = _segment_or_suffix(pass_or, grp)
    first_col = jnp.ones_like(grp[:, :1], bool)
    is_start = (grp > 0) & jnp.concatenate(
        [first_col, grp[:, 1:] != grp[:, :-1]], axis=1
    )
    or_ok = jnp.all(jnp.where(is_start & ~in_circ, seg_any, True), axis=1)
    asrt_ok = and_ok & or_ok

    if detail is not None:
        slots = jnp.arange(W, dtype=jnp.int32)[None, :]
        w_start = consts["loc_asrt_start"][jnp.where(tracked, loc, 0)]
        detail.update(
            w_rows=w_start[:, None] + slots,  # read at applying slots only
            passes=passes,
            in_circ=in_circ,
            is_and=is_and,
            is_start=is_start,
            seg_any=seg_any,
        )
    if not n_circuits:
        return asrt_ok, None, None
    return asrt_ok, passes, seg_any


def _circuit_anchors(loc, circuits, B: int, N: int):
    """(B, O) in-document node index at each circuit-relevant location.

    -1 where the document does not instantiate the location.  The
    unique-path precondition guarantees at most one node per (document,
    location), so a masked max-reduction per location resolves every
    anchor; all further circuit work is (B, U)-sized gathers.
    """
    owner_locs = circuits["owner_locs"]
    loc_r = loc.reshape(B, N)
    n_idx = jnp.arange(N, dtype=jnp.int32)[None, :]  # (1, N)
    # one masked max-reduction per circuit-relevant location (O is small,
    # and a static loop of reductions beats an XLA scatter by a lot on
    # CPU for these shapes)
    cols = [
        jnp.max(jnp.where(loc_r == int(o), n_idx, -1), axis=1)
        for o in owner_locs.tolist()
    ]
    return jnp.stack(cols, axis=1) if cols else jnp.zeros((B, 0), jnp.int32)


def _anchor_gather(node_at, mat, ranks, cols, B: int, N: int):
    """(B, U) values of static columns of ``mat`` at anchored nodes.

    ``mat`` is (B*N, cols); unit u reads ``mat[anchor, cols[u]]`` at its
    owner's anchor node, vacuous-true where the anchor is absent.
    """
    rows = node_at[:, np.asarray(ranks, np.int32)]  # (B, U)
    safe = jnp.maximum(rows, 0)
    flat = jnp.arange(B, dtype=jnp.int32)[:, None] * N + safe
    vals = mat[flat, jnp.asarray(cols, np.int32)[None, :]]
    return jnp.where(rows >= 0, vals, True)


def _leaf_values(node_at, circuits, B: int, N: int, *, passes, seg_any):
    """Per-document circuit-leaf values via anchored gathers.

    ``passes``/``seg_any`` are the (B*N, W) window pass matrix and
    segmented group OR; each leaf unit reads its static window slot (an
    AND row's own slot, a group's start slot) at its owner location's
    anchor node.  Returns {circuit id: [(B,) bool, ...]}.
    """
    and_units, group_units = circuits["and_units"], circuits["group_units"]
    out = {}
    if and_units:
        slots = [u[2] for u in and_units]
        v = _anchor_gather(node_at, passes, circuits["and_ranks"], slots, B, N)
        for u, unit in enumerate(and_units):
            out.setdefault(unit[0], []).append(v[:, u])
    if group_units:
        slots = [u[2] for u in group_units]
        v = _anchor_gather(node_at, seg_any, circuits["group_ranks"], slots, B, N)
        for u, unit in enumerate(group_units):
            out.setdefault(unit[0], []).append(v[:, u])
    return out


def _circuit_presence(node_at, circuits):
    """(B, C) bool: does the document instantiate each circuit's owner
    location?  Gated circuits at absent locations are vacuously true
    (sequential engines skip instructions whose target is missing)."""
    return node_at[:, np.asarray(circuits["circ_ranks"], np.int32)] >= 0


def _reduce_circuits(leaf_vals, present, circuits, *, n_circuits: int, roots_out=None):
    """Bottom-up circuit reduce -> (B,) root conjunction.

    ``leaf_vals`` maps circuit ids to their per-document leaf values
    (from :func:`_leaf_values`).  All wiring (kinds, parents) is
    compile-time numpy, so the reduce unrolls into straight-line
    elementwise ops at trace time -- one AND/OR/count op per circuit
    edge, no gathers or scatters (XLA scatters are pathologically slow
    for this shape on CPU).  Children always have larger ids than their
    parent, so one descending pass evaluates the DAG in topological
    order; the tape's ``max_circ_depth`` bounds the dependency depth of
    the emitted ops at compile time.
    """
    kind = circuits["kind"]
    parent = circuits["parent"]
    B = present.shape[0]
    children = [[] for _ in range(n_circuits)]
    roots = []
    for c in range(n_circuits):
        p = int(parent[c])
        if p >= 0:
            children[p].append(c)
        else:
            roots.append(c)
    vals = [None] * n_circuits
    for c in range(n_circuits - 1, -1, -1):
        k = int(kind[c])
        ch = children[c]
        if k == CK_OR:
            v = jnp.zeros(B, bool)
            for d in ch:
                v = v | vals[d]
        elif k == CK_AND or k == CK_NOT:
            v = jnp.ones(B, bool)
            for lv in leaf_vals.get(c, ()):
                v = v & lv
            for d in ch:
                v = v & vals[d]
            if k == CK_NOT:
                v = ~v
        else:  # CK_XOR1: exactly one child true
            cnt = jnp.zeros(B, jnp.int32)
            for d in ch:
                cnt = cnt + vals[d].astype(jnp.int32)
            v = cnt == 1
        # presence gate: a circuit whose owner location has no node is
        # vacuously true (also makes other members' circuits no-ops on a
        # linked tape)
        vals[c] = v | ~present[:, c]
    ok = jnp.ones(B, bool)
    for r in roots:
        ok = ok & vals[r]
    if roots_out is not None:  # explain path: per-root gated values (B, R)
        roots_out.append(
            jnp.stack([vals[r] for r in roots], axis=1)
            if roots
            else jnp.zeros((B, 0), bool)
        )
    return ok


def _validate_batch(
    cols,
    schema_ids,
    *,
    consts,
    max_depth: int,
    max_loc_depth: int,
    use_pallas: bool,
    n_window: int,
    k_cand: int,
    n_members: int,
    has_frontier: bool = False,
    circuits=None,
    n_circuits: int = 0,
):
    # the tape caps trackable depth at compile time: below
    # max_loc_depth + 1 every location is untracked or under an invalid
    # ancestor, so the loop stops there
    tape_horizon = max_loc_depth + 1
    loop_depth = min(max_depth, tape_horizon)
    loc, acquired, aux = _propagate_locations(
        cols,
        schema_ids,
        consts,
        loop_depth=loop_depth,
        use_pallas=use_pallas,
        k_cand=k_cand,
        n_members=n_members,
    )
    node_type = aux["node_type"]
    is_pad = aux["is_pad"]
    flat = aux["flat"]
    B, N = aux["B"], aux["N"]
    size = flat(cols["size"])

    tracked = loc >= 0

    # ---- 2. required properties --------------------------------------------
    loc_safe = jnp.where(tracked, loc, 0)
    required_mask = jnp.where(
        tracked & (node_type == _T_OBJ), consts["loc_required_mask"][loc_safe], 0
    )
    required_ok = (acquired & required_mask) == required_mask

    # ---- 3. assertion rows -------------------------------------------------
    node_cols = {
        "type": node_type,
        "is_int": flat(cols["is_int"]),
        "num": flat(cols["num"]).astype(jnp.float32),
        "size": size,
        "acquired": acquired,
        "str_hash": flat(cols["str_hash"]),
        "str_prefix": flat(cols["str_prefix"]),
    }
    asrt_ok, w_passes, w_seg_any = _assertions_csr(
        loc,
        node_cols,
        consts,
        use_pallas=use_pallas,
        n_window=n_window,
        n_circuits=n_circuits,
    )

    # ---- 4. reduce -----------------------------------------------------------
    node_valid = ((loc != LOC_INVALID) & asrt_ok & required_ok) | is_pad
    valid = jnp.all(node_valid.reshape(B, N), axis=1)

    # logical-applicator circuits (DESIGN.md §10): per-document leaves ->
    # bounded-depth reduce -> AND of gated root values into the verdict
    if n_circuits:
        node_at = _circuit_anchors(loc, circuits, B, N)
        leaf_vals = _leaf_values(
            node_at,
            circuits,
            B,
            N,
            passes=w_passes,
            seg_any=w_seg_any,
        )
        present = _circuit_presence(node_at, circuits)
        valid = valid & _reduce_circuits(
            leaf_vals, present, circuits, n_circuits=n_circuits
        )

    # depth-budget coverage: a non-root, non-pad node that never received a
    # location sits below the max_depth horizon -- its document's verdict
    # is vacuous, flag it undecided (the silent-correctness fix).  When the
    # tape horizon fits inside the budget, deeper nodes are provably
    # unconstrained and every document is decided (statically).  On a
    # linked tape the global horizon is the member maximum, so documents
    # whose *own* member horizon fits the budget are still statically
    # decided -- keeping (valid, decided) bit-identical to dispatching
    # each document to its own single-member tape.
    if tape_horizon <= max_depth:
        in_depth = jnp.ones(B, bool)
    else:
        is_root = jnp.arange(B * N, dtype=jnp.int32) % N == 0
        unreached = ~is_pad & ~is_root & (loc == jnp.int32(-1))
        member_ok = consts["member_horizons"][schema_ids] <= max_depth  # (B,)
        in_depth = member_ok | ~jnp.any(unreached.reshape(B, N), axis=1)

    # $ref-unroll frontiers (DESIGN.md §9): transition edges past the
    # unroll budget carry LOC_FRONTIER, and the ordinary negative-parent
    # propagation spreads it down the subtree -- so one equality scan
    # finds every document whose recursion outran the tape.  Those
    # verdicts are vacuous: the caller must route them to the sequential
    # oracle (counted as ``unroll_overflow``, distinct from the depth
    # budget's ``undecided``).  Statically skipped for frontier-free
    # tapes (the overwhelming majority).
    if has_frontier:
        frontier = jnp.any((loc == jnp.int32(LOC_FRONTIER)).reshape(B, N), axis=1)
    else:
        frontier = jnp.zeros(B, bool)
    return valid, in_depth, frontier


def _circuit_roots(circuits, n_circuits: int) -> List[int]:
    """Root circuit ids in ascending order (compile-time)."""
    parent = circuits["parent"]
    return [c for c in range(n_circuits) if int(parent[c]) < 0]


def _explain_batch(
    cols,
    schema_ids,
    *,
    consts,
    max_depth: int,
    max_loc_depth: int,
    use_pallas: bool,
    n_window: int,
    k_cand: int,
    n_members: int,
    circuits=None,
    n_circuits: int = 0,
):
    """Device half of batched first-failure attribution (DESIGN.md §12).

    Re-runs the CSR validation pipeline keeping the per-window
    intermediates, then reduces every document to ONE failure pick:

    - per node, the lowest failing assertion row (a failed AND row fails
      at its own row; a failed enum OR-group at its first window row);
    - per document, an argmin over packed ``node*4 + kind`` keys, so the
      lowest BFS node wins and, within a node, assertion (0) beats
      missing-required (1) beats closed-object (2);
    - circuit failures come back separately as per-root gated values +
      the root owner's anchor node; the host merges them in as kind 3.

    Returns ``(doc_key, bad_row, bad_loc, parent_loc, missing,
    root_fail, root_anchor)`` -- all small (B,)/(B, R) tensors; the
    provenance mapping happens on the host (``core/explain.py``).
    """
    tape_horizon = max_loc_depth + 1
    loop_depth = min(max_depth, tape_horizon)
    loc, acquired, aux = _propagate_locations(
        cols,
        schema_ids,
        consts,
        loop_depth=loop_depth,
        use_pallas=use_pallas,
        k_cand=k_cand,
        n_members=n_members,
    )
    node_type = aux["node_type"]
    is_pad = aux["is_pad"]
    flat = aux["flat"]
    B, N = aux["B"], aux["N"]

    tracked = loc >= 0
    loc_safe = jnp.where(tracked, loc, 0)
    required_mask = jnp.where(
        tracked & (node_type == _T_OBJ), consts["loc_required_mask"][loc_safe], 0
    )
    required_ok = (acquired & required_mask) == required_mask

    node_cols = {
        "type": node_type,
        "is_int": flat(cols["is_int"]),
        "num": flat(cols["num"]).astype(jnp.float32),
        "size": flat(cols["size"]),
        "acquired": acquired,
        "str_hash": flat(cols["str_hash"]),
        "str_prefix": flat(cols["str_prefix"]),
    }
    detail: Dict[str, Any] = {}
    _asrt_ok, w_passes, w_seg_any = _assertions_csr(
        loc,
        node_cols,
        consts,
        use_pallas=use_pallas,
        n_window=n_window,
        n_circuits=n_circuits,
        detail=detail,
    )

    # per-node first failing plain assertion row (global row id)
    fail_and = detail["is_and"] & ~detail["passes"]
    fail_or = detail["is_start"] & ~detail["in_circ"] & ~detail["seg_any"]
    row_masked = jnp.where(fail_and | fail_or, detail["w_rows"], _BIG)
    node_first_row = jnp.min(row_masked, axis=1)  # (BN,)
    has_row_fail = node_first_row < _BIG

    req_fail = tracked & ~required_ok
    closed_fail = loc == jnp.int32(LOC_INVALID)
    node_fail = ~is_pad & (has_row_fail | req_fail | closed_fail)
    kind = jnp.where(has_row_fail, 0, jnp.where(req_fail, 1, 2))

    # packed argmin: lowest BFS node, then kind priority within the node
    n_in_doc = jnp.arange(B * N, dtype=jnp.int32) % N
    key = jnp.where(node_fail, n_in_doc * 4 + kind, _BIG)
    doc_key = jnp.min(key.reshape(B, N), axis=1)  # (B,)

    picked = doc_key < _BIG
    node_pick = jnp.where(picked, doc_key // 4, 0)
    chosen_flat = jnp.arange(B, dtype=jnp.int32) * N + node_pick
    bad_row = jnp.where(picked, node_first_row[chosen_flat], -1)
    bad_loc = jnp.where(picked, loc[chosen_flat], -1)
    missing = jnp.where(picked, (required_mask & ~acquired)[chosen_flat], 0)
    parent = flat(cols["parent"])
    par = parent[chosen_flat]  # (B,) in-document parent index
    par_flat = jnp.where(par >= 0, jnp.arange(B, dtype=jnp.int32) * N + par, 0)
    parent_loc = jnp.where(picked & (par >= 0), loc[par_flat], -1)

    if n_circuits:
        node_at = _circuit_anchors(loc, circuits, B, N)
        leaf_vals = _leaf_values(
            node_at,
            circuits,
            B,
            N,
            passes=w_passes,
            seg_any=w_seg_any,
        )
        present = _circuit_presence(node_at, circuits)
        roots_out: List[Any] = []
        _reduce_circuits(
            leaf_vals,
            present,
            circuits,
            n_circuits=n_circuits,
            roots_out=roots_out,
        )
        root_fail = ~roots_out[0]  # (B, R): gated root value False = fail
        roots = _circuit_roots(circuits, n_circuits)
        rank_cols = np.asarray(
            [int(circuits["circ_ranks"][r]) for r in roots], np.int32
        )
        root_anchor = node_at[:, rank_cols]  # (B, R) in-doc anchor, -1 absent
    else:
        root_fail = jnp.zeros((B, 0), bool)
        root_anchor = jnp.zeros((B, 0), jnp.int32)
    return doc_key, bad_row, bad_loc, parent_loc, missing, root_fail, root_anchor

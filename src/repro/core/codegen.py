"""Closure compilation of the instruction DSL (beyond-paper optimization).

The paper's §8 names "precompiling the code necessary to validate each
schema" as future work; this module does it.  Each instruction compiles to
a specialized Python closure with every operand, key, and type test
pre-bound -- eliminating opcode dispatch, dataclass attribute loads, and
precondition re-derivation from the per-document hot path.  Semantics are
identical to executor.py (differentially tested in tests/test_codegen.py).

Document model: the closures read the decoded JSON tree as it is -- a
``dict`` is an object, a ``list`` an array (subclasses included, as
``parse_document`` takes them; a tuple is neither).  No per-document
rebuild: ``str`` keys cache their hash, so every keyed lookup is one
``dict`` probe -- the paper's §4.1 "hash once, at parse time", done by
the JSON decoder.  The interpreter (executor.py) and the explain trace
keep the paper's model, ``parse_document``'s ``HashedObject`` with its
semi-perfect hashes, and are the oracle.

Notes on specialization:
* ``type(x) is`` tests first; a container test falls back to
  ``isinstance`` only on a miss, so subclasses cost nothing on the common
  path (bool/int discrimination falls out for free);
* scalar const/enum tests split by type at compile time -- enum membership
  is one frozenset probe, no json_equal walk;
* property matching is one ``dict`` probe per entry, into a table keyed by
  the property name and built once; excluded keys are a frozenset.

Metering (DESIGN.md §11): one closure set serves both the unbounded hot
path and the bounded fallback.  Every group closure reads the ``budget``
slot of the validator's :class:`~repro.core.executor.EvalContext`.  Left
``None``, a group pays that one check; set to a
:class:`~repro.core.outcomes.ValidationBudget`, a group meters exactly as
``executor._eval_group`` does -- ``enter_group``, one ``tick`` before
each instruction, ``exit_group`` -- and engine regexes pass the budget's
``regex_gate`` first, so step counts, depth bounds and refusals match the
interpreter's.  With no parse, ``Validator`` holds the decoded tree to
the parse's stack bound (``doc_model.nesting_exceeds``) before the
closures run, so a depth bomb is refused as the interpreter refuses it;
a nesting the schema follows stops at the budget's depth bound.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

from .compiler import CompiledSchema
from .doc_model import canonical, json_equal
from .instructions import Instruction, Instructions, OpCode
from .regex_opt import RegexKind

__all__ = ["compile_to_callable"]

Check = Callable[[Any], bool]

_MISS = object()


def _type_check(t: str) -> Check:
    if t == "string":
        return lambda v: type(v) is str
    if t == "integer":
        return lambda v: type(v) is int or (type(v) is float and v.is_integer())
    if t == "number":
        return lambda v: type(v) is int or type(v) is float
    if t == "object":
        return lambda v: type(v) is dict or isinstance(v, dict)
    if t == "array":
        return lambda v: type(v) is list or isinstance(v, list)
    if t == "boolean":
        return lambda v: type(v) is bool
    if t == "null":
        return lambda v: v is None
    return lambda v: False


def _const_check(value: Any) -> Check:
    if value is None:
        return lambda v: v is None
    if isinstance(value, bool):
        return lambda v: v is value
    if isinstance(value, str):
        return lambda v: type(v) is str and v == value
    if isinstance(value, (int, float)):
        f = float(value)
        return lambda v: (type(v) is int or type(v) is float) and v == f
    return lambda v: json_equal(v, value)


def _enum_check(values: Tuple[Any, ...]) -> Check:
    strs = frozenset(v for v in values if isinstance(v, str))
    nums = frozenset(
        float(v) for v in values if isinstance(v, (int, float)) and not isinstance(v, bool)
    )
    has_null = any(v is None for v in values)
    has_true = any(v is True for v in values)
    has_false = any(v is False for v in values)
    complex_vals = [v for v in values if isinstance(v, (list, dict))]

    def check(v):
        t = type(v)
        if t is str:
            return v in strs
        if t is bool:
            return has_true if v else has_false
        if t is int or t is float:
            return v in nums
        if v is None:
            return has_null
        return any(json_equal(v, c) for c in complex_vals)

    return check


class _Codegen:
    def __init__(self, compiled: CompiledSchema, meter: Any):
        self.compiled = compiled
        self.labels: Dict[int, Check] = {}
        self.meter = meter

    # -- groups ---------------------------------------------------------------

    def group(self, instructions: Instructions) -> Check:
        """AND over a group, fail-fast; metered while ``meter.budget`` is
        set.  The one- and two-instruction forms specialise only the
        unmetered path: the metered one is the interpreter's loop."""
        fns = tuple(self.one(i) for i in instructions)
        meter = self.meter
        if len(fns) == 1:
            (f0,) = fns

            def check1(v):
                budget = meter.budget
                if budget is None:
                    return f0(v)
                budget.enter_group()
                try:
                    budget.tick()
                    return f0(v)
                finally:
                    budget.exit_group()

            return check1
        if len(fns) == 2:
            f0, f1 = fns

            def check2(v):
                budget = meter.budget
                if budget is None:
                    return f0(v) and f1(v)
                budget.enter_group()
                try:
                    budget.tick()
                    if not f0(v):
                        return False
                    budget.tick()
                    return f1(v)
                finally:
                    budget.exit_group()

            return check2

        def check(v):
            budget = meter.budget
            if budget is None:
                for f in fns:
                    if not f(v):
                        return False
                return True
            budget.enter_group()
            try:
                for f in fns:
                    budget.tick()
                    if not f(v):
                        return False
                return True
            finally:
                budget.exit_group()

        return check

    # -- per-instruction ---------------------------------------------------------

    def one(self, inst: Instruction) -> Check:
        inner = self.body(inst)
        if not inst.rel_path:
            return inner
        # fold relative resolution into the closure: str tokens are keys,
        # int tokens array indices
        path = tuple(inst.rel_path)
        if len(path) == 1 and type(path[0]) is str:
            (key,) = path

            def resolved(v, _inner=inner, _k=key):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                child = v.get(_k, _MISS)
                if child is _MISS:
                    return True
                return _inner(child)

            return resolved

        def resolved_deep(v, _inner=inner, _path=path):
            node = v
            for tok in _path:
                if type(tok) is str:
                    if type(node) is not dict and not isinstance(node, dict):
                        return True
                    node = node.get(tok, _MISS)
                    if node is _MISS:
                        return True
                else:
                    if type(node) is not list and not isinstance(node, list):
                        return True
                    if not 0 <= tok < len(node):
                        return True
                    node = node[tok]
            return _inner(node)

        return resolved_deep

    def body(self, inst: Instruction) -> Check:  # noqa: C901 -- dispatch table
        op = inst.op
        if op is OpCode.FAIL:
            return lambda v: False
        if op is OpCode.TYPE:
            return _type_check(inst.type)
        if op is OpCode.TYPE_ANY:
            checks = tuple(_type_check(t) for t in inst.types)
            return lambda v: any(c(v) for c in checks)
        if op is OpCode.EQUAL:
            return _const_check(inst.value)
        if op is OpCode.EQUALS_ANY:
            return _enum_check(inst.values)

        if op is OpCode.DEFINES:
            k = inst.key
            return lambda v: (type(v) is not dict and not isinstance(v, dict)) or k in v
        if op is OpCode.DEFINES_ALL:
            keys = tuple(inst.keys)

            def defines_all(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for k in keys:
                    if k not in v:
                        return False
                return True

            return defines_all
        if op is OpCode.PROPERTY_DEPENDENCIES:
            deps = tuple((k, tuple(dk)) for k, _h, dk, _dh in inst.dependencies)

            def prop_deps(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for k, reqs in deps:
                    if k in v:
                        for dk in reqs:
                            if dk not in v:
                                return False
                return True

            return prop_deps
        if op is OpCode.OBJECT_SIZE_GREATER:
            b = inst.bound
            return lambda v: (
                (type(v) is not dict and not isinstance(v, dict)) or len(v) >= b
            )
        if op is OpCode.OBJECT_SIZE_LESS:
            b = inst.bound
            return lambda v: (
                (type(v) is not dict and not isinstance(v, dict)) or len(v) <= b
            )
        if op is OpCode.PROPERTY_TYPE:
            k = inst.key
            tcheck = _type_check(inst.type)

            def prop_type(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                child = v.get(k, _MISS)
                return child is not _MISS and tcheck(child)

            return prop_type

        if op is OpCode.REGEX:
            plan = inst.plan
            kind = plan.kind
            if kind is RegexKind.PREFIX:
                lit = plan.literal
                return lambda v: type(v) is not str or v.startswith(lit)
            if kind is RegexKind.SUFFIX:
                lit = plan.literal
                return lambda v: type(v) is not str or v.endswith(lit)
            if kind is RegexKind.EXACT:
                lit = plan.literal
                return lambda v: type(v) is not str or v == lit
            if kind is RegexKind.CONTAINS:
                lit = plan.literal
                return lambda v: type(v) is not str or lit in v
            if kind is RegexKind.NON_EMPTY:
                return lambda v: type(v) is not str or len(v) >= 1
            if kind is RegexKind.LENGTH_RANGE:
                lo, hi = plan.min_len, plan.max_len
                if hi is None:
                    return lambda v: type(v) is not str or len(v) >= lo
                return lambda v: type(v) is not str or lo <= len(v) <= hi
            if kind is RegexKind.ALL:
                return lambda v: True
            from .regex_opt import _engine

            rx = _engine(plan.source)
            meter = self.meter

            def engine_regex(v):
                if type(v) is not str:
                    return True
                budget = meter.budget
                if budget is not None:
                    # not preemptible mid-match: gate it up front
                    budget.regex_gate(plan, len(v))
                return rx.search(v) is not None

            return engine_regex
        if op is OpCode.STRING_SIZE_GREATER:
            b = inst.bound
            return lambda v: type(v) is not str or len(v) >= b
        if op is OpCode.STRING_SIZE_LESS:
            b = inst.bound
            return lambda v: type(v) is not str or len(v) <= b
        if op is OpCode.STRING_BOUNDS:
            lo, hi = inst.min_len, inst.max_len
            if hi is None:
                return lambda v: type(v) is not str or len(v) >= lo
            return lambda v: type(v) is not str or lo <= len(v) <= hi
        if op is OpCode.STRING_TYPE:
            from .executor import _check_format

            fmt = inst.format
            return lambda v: type(v) is not str or _check_format(fmt, v)

        if op is OpCode.UNIQUE:

            def unique(v):
                if type(v) is not list and not isinstance(v, list):
                    return True
                seen = set()
                for item in v:
                    c = canonical(item)
                    if c in seen:
                        return False
                    seen.add(c)
                return True

            return unique
        if op is OpCode.ARRAY_SIZE_GREATER:
            b = inst.bound
            return lambda v: (
                (type(v) is not list and not isinstance(v, list)) or len(v) >= b
            )
        if op is OpCode.ARRAY_SIZE_LESS:
            b = inst.bound
            return lambda v: (
                (type(v) is not list and not isinstance(v, list)) or len(v) <= b
            )
        if op is OpCode.ARRAY_BOUNDS:
            lo, hi = inst.min_len, inst.max_len
            if hi is None:
                return lambda v: (
                    (type(v) is not list and not isinstance(v, list)) or len(v) >= lo
                )
            return lambda v: (
                (type(v) is not list and not isinstance(v, list)) or lo <= len(v) <= hi
            )

        if op is OpCode.GREATER:
            b = inst.bound
            return lambda v: (type(v) is not int and type(v) is not float) or v > b
        if op is OpCode.GREATER_EQUAL:
            b = inst.bound
            return lambda v: (type(v) is not int and type(v) is not float) or v >= b
        if op is OpCode.LESS:
            b = inst.bound
            return lambda v: (type(v) is not int and type(v) is not float) or v < b
        if op is OpCode.LESS_EQUAL:
            b = inst.bound
            return lambda v: (type(v) is not int and type(v) is not float) or v <= b
        if op is OpCode.NUMBER_BOUNDS:
            lo, lo_x, hi, hi_x = inst.lo, inst.lo_exclusive, inst.hi, inst.hi_exclusive

            def bounds(v):
                t = type(v)
                if t is not int and t is not float:
                    return True
                if lo is not None:
                    if lo_x:
                        if not v > lo:
                            return False
                    elif not v >= lo:
                        return False
                if hi is not None:
                    if hi_x:
                        if not v < hi:
                            return False
                    elif not v <= hi:
                        return False
                return True

            return bounds
        if op is OpCode.DIVISIBLE:
            d = inst.divisor
            from .executor import _divisible as _div

            def divisible(v):
                t = type(v)
                if t is not int and t is not float:
                    return True
                # shared spec-exact check (decimal re-check on inexact
                # float quotients) -- keeps codegen == interpreter
                return _div(v, d)

            return divisible

        # ---- loops -----------------------------------------------------------
        if op is OpCode.LOOP_KEYS:
            child = self.group(inst.children)

            def loop_keys(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for key in v:
                    if not child(key):
                        return False
                return True

            return loop_keys
        if op is OpCode.LOOP_PROPERTIES:
            child = self.group(inst.children)

            def loop_props(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for val in v.values():
                    if not child(val):
                        return False
                return True

            return loop_props
        if op is OpCode.LOOP_PROPERTIES_EXCEPT:
            child = self.group(inst.children)
            excl = frozenset(inst.exclude_keys)
            plans = inst.exclude_patterns

            def loop_except(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for key, val in v.items():
                    if key in excl:
                        continue
                    if plans and any(p.matches(key) for p in plans):
                        continue
                    if not child(val):
                        return False
                return True

            return loop_except
        if op is OpCode.LOOP_PROPERTIES_REGEX:
            child = self.group(inst.children)
            plan = inst.plan

            def loop_regex(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for key, val in v.items():
                    if plan.matches(key) and not child(val):
                        return False
                return True

            return loop_regex
        if op in (OpCode.LOOP_PROPERTIES_MATCH, OpCode.LOOP_PROPERTIES_MATCH_CLOSED):
            table: Dict[str, Check] = {}
            for key, _h, grp in inst.matches:
                table.setdefault(key, self.group(grp))  # first entry wins
            closed = op is OpCode.LOOP_PROPERTIES_MATCH_CLOSED
            plans = getattr(inst, "tolerate_patterns", ())

            def loop_match(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                for key, val in v.items():
                    fn = table.get(key)
                    if fn is None:
                        if closed:
                            if plans and any(p.matches(key) for p in plans):
                                continue
                            return False
                        continue
                    if not fn(val):
                        return False
                return True

            return loop_match
        if op is OpCode.LOOP_ITEMS:
            child = self.group(inst.children)

            def loop_items(v):
                if type(v) is not list and not isinstance(v, list):
                    return True
                for item in v:
                    if not child(item):
                        return False
                return True

            return loop_items
        if op is OpCode.LOOP_ITEMS_FROM:
            child = self.group(inst.children)
            start = inst.start

            def loop_items_from(v):
                if type(v) is not list and not isinstance(v, list):
                    return True
                for i in range(start, len(v)):
                    if not child(v[i]):
                        return False
                return True

            return loop_items_from
        if op is OpCode.LOOP_CONTAINS:
            child = self.group(inst.children)
            lo, hi = inst.min_count, inst.max_count

            def loop_contains(v):
                if type(v) is not list and not isinstance(v, list):
                    return True
                count = 0
                for item in v:
                    if child(item):
                        count += 1
                        if hi is not None and count > hi:
                            return False
                        if hi is None and count >= lo:
                            return True
                return count >= lo and (hi is None or count <= hi)

            return loop_contains
        if op is OpCode.ARRAY_PREFIX:
            groups = tuple(self.group(g) for g in inst.groups)

            def array_prefix(v):
                if type(v) is not list and not isinstance(v, list):
                    return True
                for i, fn in enumerate(groups):
                    if i >= len(v):
                        break
                    if not fn(v[i]):
                        return False
                return True

            return array_prefix
        if op is OpCode.LOOP_UNEVALUATED_PROPERTIES:
            child = self.group(inst.children)
            static_keys = frozenset(inst.static_keys)
            static_plans = inst.static_patterns
            branches = tuple(
                (self.group(guard), frozenset(keys), pats, sees_all)
                for guard, keys, _h, pats, sees_all in inst.branches
            )

            def uneval_props(v):
                if type(v) is not dict and not isinstance(v, dict):
                    return True
                names = set(static_keys)
                plans = list(static_plans)
                for guard, keys, pats, sees_all in branches:
                    if guard(v):
                        if sees_all:
                            return True
                        names |= keys
                        plans.extend(pats)
                for key, val in v.items():
                    if key in names or any(p.matches(key) for p in plans):
                        continue
                    if not child(val):
                        return False
                return True

            return uneval_props
        if op is OpCode.LOOP_UNEVALUATED_ITEMS:
            child = self.group(inst.children)
            branches = tuple(
                (self.group(guard), prefix, sees_all)
                for guard, prefix, sees_all in inst.branches
            )
            contains = tuple(
                (self.group(guard) if guard else None, self.group(group))
                for guard, group in inst.contains_groups
            )
            static_prefix = inst.static_prefix

            def uneval_items(v):
                if type(v) is not list and not isinstance(v, list):
                    return True
                prefix = static_prefix
                for guard, bp, sees_all in branches:
                    if guard(v):
                        if sees_all:
                            return True
                        prefix = max(prefix, bp)
                # branch-gated contains annotations (guard None = unconditional)
                active = [g for guard, g in contains if guard is None or guard(v)]
                for i in range(prefix, len(v)):
                    item = v[i]
                    if active and any(g(item) for g in active):
                        continue
                    if not child(item):
                        return False
                return True

            return uneval_items

        # ---- logical -----------------------------------------------------------
        if op is OpCode.AND:
            return self.group(inst.children)
        if op is OpCode.OR:
            groups = tuple(self.group(g) for g in inst.groups)

            def logical_or(v):
                for fn in groups:
                    if fn(v):
                        return True
                return False

            return logical_or
        if op is OpCode.XOR:
            groups = tuple(self.group(g) for g in inst.groups)

            def logical_xor(v):
                passed = 0
                for fn in groups:
                    if fn(v):
                        passed += 1
                        if passed > 1:
                            return False
                return passed == 1

            return logical_xor
        if op is OpCode.NOT:
            child = self.group(inst.children)
            return lambda v: not child(v)
        if op is OpCode.CONDITION:
            cond = self.group(inst.condition)
            then_fn = self.group(inst.then_children)
            else_fn = self.group(inst.else_children)
            return lambda v: then_fn(v) if cond(v) else else_fn(v)
        if op is OpCode.WHEN_TYPE:
            tcheck = _type_check(inst.type)
            child = self.group(inst.children)
            return lambda v: child(v) if tcheck(v) else True
        if op is OpCode.WHEN_DEFINES:
            k = inst.key
            child = self.group(inst.children)
            return (
                lambda v: child(v)
                if (type(v) is dict or isinstance(v, dict)) and k in v
                else True
            )
        if op is OpCode.WHEN_ARRAY_SIZE_GREATER:
            b = inst.bound
            child = self.group(inst.children)
            return (
                lambda v: child(v)
                if (type(v) is list or isinstance(v, list)) and len(v) > b
                else True
            )
        if op is OpCode.WHEN_ARRAY_SIZE_EQUAL:
            b = inst.bound
            child = self.group(inst.children)
            return (
                lambda v: child(v)
                if (type(v) is list or isinstance(v, list)) and len(v) == b
                else True
            )

        # ---- control -------------------------------------------------------------
        if op is OpCode.CONTROL_LABEL:
            fn = self.group(inst.children)
            self.labels[inst.label] = fn
            return fn
        if op is OpCode.CONTROL_JUMP:
            labels = self.labels
            label = inst.label
            return lambda v: labels[label](v)

        raise AssertionError(f"codegen: unhandled opcode {op!r}")


def compile_to_callable(compiled: CompiledSchema, meter: Any) -> Check:
    """Compile a CompiledSchema into a single specialised closure.

    ``meter`` is the object whose ``budget`` attribute the closures read
    (the validator's EvalContext): they meter themselves while it is set."""
    gen = _Codegen(compiled, meter)
    # labels referenced by jumps may be registered during group compilation;
    # compile label bodies first so forward jumps resolve
    for label, group in compiled.labels.items():
        if label not in gen.labels:
            gen.labels[label] = gen.group(group)
    return gen.group(compiled.instructions)

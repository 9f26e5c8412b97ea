"""The plain reference for verdicts: a JSON Schema interpreter.

A copy of the repository's ``NaiveValidator`` (``core/interpreter.py``)
with its resolver (``core/schema_resolver.py``), JSON Pointer helpers
(``core/json_pointer.py``), type and equality rules
(``core/doc_model.py``) and exact ``multipleOf`` (``core/executor.py``),
folded into one module that imports nothing of the program.  It walks
the raw schema for every document and resolves ``$ref`` as it goes: no
compilation, no tape, no batching.  The benchmark keeps its own copy so
that no change to the program can move the yardstick.
"""

from __future__ import annotations

import re
from dataclasses import dataclass
from enum import Enum
from typing import Any, Dict, Iterable, List, Optional, Set, Tuple, Union
from urllib.parse import urldefrag, urljoin

__all__ = ["NaiveValidator"]

# -- JSON Pointer -------------------------------------------------------

Token = Union[str, int]
InstancePath = Tuple[Token, ...]

_MISSING = object()


def escape(token: str) -> str:
    """Escape a reference token per RFC 6901 (~ -> ~0, / -> ~1)."""
    return token.replace("~", "~0").replace("/", "~1")


def unescape(token: str) -> str:
    """Unescape a reference token per RFC 6901 (order matters: ~1 first)."""
    return token.replace("~1", "/").replace("~0", "~")


def parse_pointer(pointer: str) -> Tuple[str, ...]:
    """Split a JSON Pointer string into unescaped tokens."""
    if pointer == "":
        return ()
    if not pointer.startswith("/"):
        raise ValueError(f"invalid JSON pointer: {pointer!r}")
    return tuple(unescape(tok) for tok in pointer[1:].split("/"))


def format_pointer(tokens: Iterable[Token]) -> str:
    """Render tokens back into a JSON Pointer string."""
    return "".join("/" + escape(str(tok)) for tok in tokens)


def resolve_pointer(document: Any, pointer: str) -> Any:
    """Resolve a JSON Pointer against a plain-dict/list document.

    Raises ``KeyError`` when the pointer does not exist -- used for ``$ref``
    resolution where a dangling pointer is a schema bug.
    """
    node = document
    for tok in parse_pointer(pointer):
        if isinstance(node, dict):
            if tok not in node:
                raise KeyError(f"pointer token {tok!r} not found ({pointer!r})")
            node = node[tok]
        elif isinstance(node, list):
            try:
                idx = int(tok)
            except ValueError as exc:
                raise KeyError(f"non-integer index {tok!r} ({pointer!r})") from exc
            if not 0 <= idx < len(node):
                raise KeyError(f"index {idx} out of range ({pointer!r})")
            node = node[idx]
        else:
            raise KeyError(f"cannot descend into scalar at {tok!r} ({pointer!r})")
    return node


def get_instance(value: Any, path: InstancePath) -> Any:
    """Resolve a relative instance path; returns ``MISSING`` when absent.

    Instructions whose target is absent are skipped (vacuously true) --
    requiredness is asserted separately via ``AssertionDefines``.
    """
    node = value
    for tok in path:
        if isinstance(tok, str):
            # Instance objects are stored as HashedObject (vector of
            # entries) by the executor; support both plain dicts and the
            # executor's representation via duck typing.
            getter = getattr(node, "get_item", None)
            if getter is not None:
                node = getter(tok, _MISSING)
            elif isinstance(node, dict):
                node = node.get(tok, _MISSING)
            else:
                return _MISSING
            if node is _MISSING:
                return _MISSING
        else:
            if not isinstance(node, list) or not 0 <= tok < len(node):
                return _MISSING
            node = node[tok]
    return node


MISSING = _MISSING

# -- resolver ----------------------------------------------------------

class Dialect(Enum):
    DRAFT4 = "draft4"
    DRAFT6 = "draft6"
    DRAFT7 = "draft7"
    DRAFT2019 = "2019-09"
    DRAFT2020 = "2020-12"


_DIALECT_URIS = {
    "http://json-schema.org/draft-04/schema": Dialect.DRAFT4,
    "http://json-schema.org/draft-06/schema": Dialect.DRAFT6,
    "http://json-schema.org/draft-07/schema": Dialect.DRAFT7,
    "https://json-schema.org/draft/2019-09/schema": Dialect.DRAFT2019,
    "https://json-schema.org/draft/2020-12/schema": Dialect.DRAFT2020,
}


def detect_dialect(schema: Any, default: Dialect = Dialect.DRAFT2020) -> Dialect:
    if isinstance(schema, dict):
        uri = schema.get("$schema")
        if isinstance(uri, str):
            return _DIALECT_URIS.get(uri.rstrip("#"), default)
    return default


@dataclass
class ResolvedRef:
    """A resolved reference destination."""

    schema: Any
    base_uri: str
    key: str  # canonical identity used for use-counting / labels


class SchemaResolver:
    """Static index over a schema document (+ external resources)."""

    def __init__(self, root: Any, resources: Optional[Dict[str, Any]] = None):
        self.root = root
        self.dialect = detect_dialect(root)
        # canonical URI -> (schema fragment, base uri at that fragment)
        self._ids: Dict[str, Tuple[Any, str]] = {}
        self._anchors: Dict[str, Tuple[Any, str]] = {}
        # dynamic anchor name -> list of (schema, base uri) contexts
        self._dynamic: Dict[str, List[Tuple[Any, str]]] = {}
        self.root_base = ""
        if isinstance(root, dict):
            self.root_base = root.get("$id", "") or ""
        self._index(root, self.root_base)
        for uri, res in (resources or {}).items():
            base = res.get("$id", uri) if isinstance(res, dict) else uri
            self._ids.setdefault(uri.rstrip("#"), (res, base))
            self._index(res, base)

    # -- indexing -----------------------------------------------------------

    def _index(self, node: Any, base: str) -> None:
        if isinstance(node, dict):
            new_id = node.get("$id")
            if isinstance(new_id, str) and new_id:
                base = urljoin(base, new_id)
                self._ids[urldefrag(base)[0] or base] = (node, base)
            anchor = node.get("$anchor")
            if isinstance(anchor, str):
                self._anchors[urljoin(base, "#" + anchor)] = (node, base)
            dyn = node.get("$dynamicAnchor")
            if isinstance(dyn, str):
                self._dynamic.setdefault(dyn, []).append((node, base))
                # a $dynamicAnchor also behaves as a plain $anchor
                self._anchors.setdefault(urljoin(base, "#" + dyn), (node, base))
            if node.get("$recursiveAnchor") is True:
                self._dynamic.setdefault("", []).append((node, base))
            for key, value in node.items():
                if key in ("enum", "const", "default", "examples"):
                    continue  # instance data, not schemas
                self._index(value, base)
        elif isinstance(node, list):
            for item in node:
                self._index(item, base)

    # -- resolution ---------------------------------------------------------

    def resolve(self, ref: str, base: str) -> ResolvedRef:
        """Resolve ``$ref`` value ``ref`` against base URI ``base``."""
        target = urljoin(base, ref) if base or not ref.startswith("#") else ref
        uri, fragment = urldefrag(target)

        if not uri:  # same-document reference
            doc, doc_base = self.root, self.root_base
        elif uri in self._ids:
            doc, doc_base = self._ids[uri]
        elif uri == urldefrag(self.root_base)[0]:
            doc, doc_base = self.root, self.root_base
        else:
            raise KeyError(f"unresolvable $ref {ref!r} (base {base!r})")

        if not fragment:
            return ResolvedRef(doc, doc_base, key=uri or "#root")
        if fragment.startswith("/"):
            frag_schema = resolve_pointer(doc, fragment)
            # the fragment may itself re-declare $id; track base changes
            new_base = doc_base
            if isinstance(frag_schema, dict) and isinstance(frag_schema.get("$id"), str):
                new_base = urljoin(doc_base, frag_schema["$id"])
            return ResolvedRef(frag_schema, new_base, key=f"{uri}#{fragment}")
        # named anchor
        anchor_uri = urljoin(uri or doc_base or "#", "#" + fragment)
        if anchor_uri in self._anchors:
            schema, abase = self._anchors[anchor_uri]
            return ResolvedRef(schema, abase, key=anchor_uri)
        # anchors registered without base
        if "#" + fragment in self._anchors:
            schema, abase = self._anchors["#" + fragment]
            return ResolvedRef(schema, abase, key="#" + fragment)
        raise KeyError(f"unresolvable anchor {ref!r} (base {base!r})")

    def resolve_dynamic(self, ref: str, base: str) -> ResolvedRef:
        """Resolve ``$dynamicRef`` -- static rewrite for single contexts (§3.4).

        When the dynamic anchor has exactly one possible context across all
        known resources, the reference is replaced by a static one.  With
        multiple contexts we fall back to the lexically innermost definition
        (correct for schemas that never override the anchor; documented
        limitation for the general PSPACE-complete case).
        """
        _, fragment = urldefrag(ref)
        contexts = self._dynamic.get(fragment, [])
        if len(contexts) == 1:
            schema, cbase = contexts[0]
            return ResolvedRef(schema, cbase, key=f"dynamic:{fragment}")
        return self.resolve(ref, base)

    def resolve_recursive(self, base: str) -> ResolvedRef:
        """2019-09 ``$recursiveRef: "#"`` -- same single-context treatment."""
        contexts = self._dynamic.get("", [])
        if len(contexts) == 1:
            schema, cbase = contexts[0]
            return ResolvedRef(schema, cbase, key="recursive:#")
        return ResolvedRef(self.root, self.root_base, key="#root")

# -- JSON value rules --------------------------------------------------

def has_type(value: Any, t: str) -> bool:
    """Type check per 2020-12 semantics (1.0 is an integer; bool is not)."""
    if t == "integer":
        if isinstance(value, bool):
            return False
        return isinstance(value, int) or (isinstance(value, float) and value.is_integer())
    if t == "number":
        return isinstance(value, (int, float)) and not isinstance(value, bool)
    if t == "string":
        return isinstance(value, str)
    if t == "object":
        return isinstance(value, dict)
    if t == "array":
        return isinstance(value, list)
    if t == "boolean":
        return isinstance(value, bool)
    if t == "null":
        return value is None
    return False


def json_equal(a: Any, b: Any) -> bool:
    """Deep JSON equality: 1 == 1.0, but True != 1 and 0 != False."""
    if isinstance(a, bool) or isinstance(b, bool):
        return a is b if isinstance(a, bool) and isinstance(b, bool) else False
    if isinstance(a, (int, float)) and isinstance(b, (int, float)):
        return a == b
    if isinstance(a, str) and isinstance(b, str):
        return a == b
    if a is None or b is None:
        return a is None and b is None
    if isinstance(a, list) and isinstance(b, list):
        return len(a) == len(b) and all(json_equal(x, y) for x, y in zip(a, b))
    if isinstance(a, dict) and isinstance(b, dict):
        if len(a) != len(b):
            return False
        return all(k in b and json_equal(v, b[k]) for k, v in a.items())
    return False

def _divisible(value: float, divisor: float) -> bool:
    """Spec-exact ``multipleOf``.

    JSON numbers are decimal: ``19.99`` IS a multiple of ``0.01`` even
    though neither has an exact binary-float form and the float quotient
    comes out 1998.9999...  The float fast path decides the common case;
    inexact quotients are re-checked as exact rationals built from the
    shortest decimal representation (``repr`` round-trips floats, so
    this is the number the document actually wrote).
    """
    if divisor == 0:
        return False
    try:
        quotient = value / divisor
    except OverflowError:
        return False
    if quotient != quotient or quotient in (float("inf"), float("-inf")):
        return False
    # fast path only while floats still resolve integrality: at
    # |quotient| >= 2^53 every float is integral, so "looks integral"
    # proves nothing (1e30 is NOT a multiple of 7)
    if quotient == int(quotient) and abs(quotient) < 2.0**53:
        return True
    from fractions import Fraction

    try:
        return Fraction(repr(value)) % Fraction(repr(divisor)) == 0
    except (ValueError, ZeroDivisionError, OverflowError):
        return False

# -- interpreter -------------------------------------------------------

class NaiveValidator:
    """Direct schema interpretation, resolving keywords per document."""

    def __init__(self, schema: Any, resources: Optional[Dict[str, Any]] = None):
        self.schema = schema
        self.resolver = SchemaResolver(schema, resources)
        self.dialect = self.resolver.dialect

    def is_valid(self, instance: Any) -> bool:
        valid, _, _ = self._validate(self.schema, instance, self.resolver.root_base, 0)
        return valid

    # ------------------------------------------------------------------

    def _validate(
        self, schema: Any, instance: Any, base: str, depth: int
    ) -> Tuple[bool, Set[str], Set[int]]:
        """Returns (valid, evaluated property names, evaluated item indices)."""
        if depth > 512:
            raise RecursionError("schema recursion limit")
        if schema is True or schema == {}:
            return True, set(), set()
        if schema is False:
            return False, set(), set()
        s: Dict[str, Any] = schema

        from urllib.parse import urljoin

        sid = s.get("$id")
        if isinstance(sid, str) and sid:
            base = urljoin(base, sid)

        eval_props: Set[str] = set()
        eval_items: Set[int] = set()

        # --- references ---------------------------------------------------
        for kw in ("$ref", "$dynamicRef", "$recursiveRef"):
            ref = s.get(kw)
            if not isinstance(ref, str):
                continue
            if kw == "$ref":
                r = self.resolver.resolve(ref, base)
            elif kw == "$dynamicRef":
                r = self.resolver.resolve_dynamic(ref, base)
            else:
                r = self.resolver.resolve_recursive(base)
            ok, ep, ei = self._validate(r.schema, instance, r.base_uri, depth + 1)
            if not ok:
                return False, set(), set()
            eval_props |= ep
            eval_items |= ei

        # --- type/const/enum -----------------------------------------------
        t = s.get("type")
        if isinstance(t, str):
            if not has_type(instance, t):
                return False, set(), set()
        elif isinstance(t, list):
            if not any(has_type(instance, x) for x in t):
                return False, set(), set()
        if "const" in s and not json_equal(instance, s["const"]):
            return False, set(), set()
        if "enum" in s and not any(json_equal(instance, v) for v in s["enum"]):
            return False, set(), set()

        # --- numbers ---------------------------------------------------------
        if isinstance(instance, (int, float)) and not isinstance(instance, bool):
            if not self._check_number(s, instance):
                return False, set(), set()

        # --- strings ---------------------------------------------------------
        if isinstance(instance, str):
            if "minLength" in s and len(instance) < s["minLength"]:
                return False, set(), set()
            if "maxLength" in s and len(instance) > s["maxLength"]:
                return False, set(), set()
            if "pattern" in s and re.search(s["pattern"], instance, re.DOTALL) is None:
                return False, set(), set()

        # --- objects ----------------------------------------------------------
        if isinstance(instance, dict):
            ok, ep = self._check_object(s, instance, base, depth)
            if not ok:
                return False, set(), set()
            eval_props |= ep

        # --- arrays ------------------------------------------------------------
        if isinstance(instance, list):
            ok, ei = self._check_array(s, instance, base, depth)
            if not ok:
                return False, set(), set()
            eval_items |= ei

        # --- logical ---------------------------------------------------------
        for sub in s.get("allOf") or []:
            ok, ep, ei = self._validate(sub, instance, base, depth + 1)
            if not ok:
                return False, set(), set()
            eval_props |= ep
            eval_items |= ei
        any_of = s.get("anyOf")
        if isinstance(any_of, list):
            hit = False
            for sub in any_of:
                ok, ep, ei = self._validate(sub, instance, base, depth + 1)
                if ok:
                    hit = True
                    eval_props |= ep
                    eval_items |= ei
            if not hit:
                return False, set(), set()
        one_of = s.get("oneOf")
        if isinstance(one_of, list):
            passed = 0
            for sub in one_of:
                ok, ep, ei = self._validate(sub, instance, base, depth + 1)
                if ok:
                    passed += 1
                    eval_props |= ep
                    eval_items |= ei
            if passed != 1:
                return False, set(), set()
        if "not" in s:
            ok, _, _ = self._validate(s["not"], instance, base, depth + 1)
            if ok:
                return False, set(), set()
        if "if" in s and self.dialect not in (Dialect.DRAFT4, Dialect.DRAFT6):
            ok, ep, ei = self._validate(s["if"], instance, base, depth + 1)
            branch = s.get("then") if ok else s.get("else")
            if ok:
                eval_props |= ep
                eval_items |= ei
            if branch is not None:
                bok, ep2, ei2 = self._validate(branch, instance, base, depth + 1)
                if not bok:
                    return False, set(), set()
                eval_props |= ep2
                eval_items |= ei2

        # --- dependent schemas -------------------------------------------------
        if isinstance(instance, dict):
            for key, sub in self._dependent_schemas(s):
                if key in instance:
                    ok, ep, ei = self._validate(sub, instance, base, depth + 1)
                    if not ok:
                        return False, set(), set()
                    eval_props |= ep
                    eval_items |= ei

        # --- unevaluated* (after everything else) -------------------------------
        if self.dialect in (Dialect.DRAFT2019, Dialect.DRAFT2020):
            if isinstance(instance, dict) and "unevaluatedProperties" in s:
                sub = s["unevaluatedProperties"]
                for key in instance:
                    if key in eval_props or self._directly_evaluated(s, key):
                        continue
                    ok, _, _ = self._validate(sub, instance[key], base, depth + 1)
                    if not ok:
                        return False, set(), set()
                    eval_props.add(key)
                eval_props = set(instance.keys())
            if isinstance(instance, list) and "unevaluatedItems" in s:
                sub = s["unevaluatedItems"]
                for i, item in enumerate(instance):
                    if i in eval_items or i < self._direct_prefix(s):
                        continue
                    ok, _, _ = self._validate(sub, item, base, depth + 1)
                    if not ok:
                        return False, set(), set()
                eval_items = set(range(len(instance)))
        return True, eval_props, eval_items

    # ------------------------------------------------------------------

    def _check_number(self, s: Dict[str, Any], v: float) -> bool:
        if self.dialect is Dialect.DRAFT4:
            if "minimum" in s:
                if s.get("exclusiveMinimum") is True:
                    if not v > s["minimum"]:
                        return False
                elif not v >= s["minimum"]:
                    return False
            if "maximum" in s:
                if s.get("exclusiveMaximum") is True:
                    if not v < s["maximum"]:
                        return False
                elif not v <= s["maximum"]:
                    return False
        else:
            if "minimum" in s and not v >= s["minimum"]:
                return False
            if "maximum" in s and not v <= s["maximum"]:
                return False
            em = s.get("exclusiveMinimum")
            if isinstance(em, (int, float)) and not isinstance(em, bool) and not v > em:
                return False
            eM = s.get("exclusiveMaximum")
            if isinstance(eM, (int, float)) and not isinstance(eM, bool) and not v < eM:
                return False
        if "multipleOf" in s:

            # shared spec-exact check: decimal multipleOf (0.01) must
            # accept decimal multiples (19.99) despite binary floats
            if not _divisible(v, s["multipleOf"]):
                return False
        return True

    def _check_object(
        self, s: Dict[str, Any], obj: Dict[str, Any], base: str, depth: int
    ) -> Tuple[bool, Set[str]]:
        evaluated: Set[str] = set()
        req = s.get("required")
        if isinstance(req, list):
            for key in req:
                if key not in obj:
                    return False, evaluated
        if "minProperties" in s and len(obj) < s["minProperties"]:
            return False, evaluated
        if "maxProperties" in s and len(obj) > s["maxProperties"]:
            return False, evaluated
        for key, deps in self._dependent_required(s):
            if key in obj:
                for d in deps:
                    if d not in obj:
                        return False, evaluated
        props = s.get("properties") or {}
        pat_props = s.get("patternProperties") or {}
        addl = s.get("additionalProperties")
        for key, value in obj.items():
            matched = False
            if key in props:
                matched = True
                ok, _, _ = self._validate(props[key], value, base, depth + 1)
                if not ok:
                    return False, evaluated
            for pat, sub in pat_props.items():
                if re.search(pat, key, re.DOTALL) is not None:
                    matched = True
                    ok, _, _ = self._validate(sub, value, base, depth + 1)
                    if not ok:
                        return False, evaluated
            if matched:
                evaluated.add(key)
            elif addl is not None:
                if addl is False:
                    return False, evaluated
                ok, _, _ = self._validate(addl, value, base, depth + 1)
                if not ok:
                    return False, evaluated
                evaluated.add(key)
        if "propertyNames" in s:
            for key in obj:
                ok, _, _ = self._validate(s["propertyNames"], key, base, depth + 1)
                if not ok:
                    return False, evaluated
        return True, evaluated

    def _check_array(
        self, s: Dict[str, Any], arr: List[Any], base: str, depth: int
    ) -> Tuple[bool, Set[int]]:
        evaluated: Set[int] = set()
        if "minItems" in s and len(arr) < s["minItems"]:
            return False, evaluated
        if "maxItems" in s and len(arr) > s["maxItems"]:
            return False, evaluated
        if s.get("uniqueItems") is True:
            for i in range(len(arr)):
                for j in range(i + 1, len(arr)):
                    if json_equal(arr[i], arr[j]):
                        return False, evaluated
        prefix, tail = self._split_items(s)
        for i, sub in enumerate(prefix):
            if i >= len(arr):
                break
            ok, _, _ = self._validate(sub, arr[i], base, depth + 1)
            if not ok:
                return False, evaluated
            evaluated.add(i)
        if tail is not None:
            for i in range(len(prefix), len(arr)):
                if tail is False:
                    return False, evaluated
                ok, _, _ = self._validate(tail, arr[i], base, depth + 1)
                if not ok:
                    return False, evaluated
                evaluated.add(i)
        if "contains" in s and self.dialect is not Dialect.DRAFT4:
            min_c = s.get("minContains", 1)
            max_c = s.get("maxContains")
            if self.dialect in (Dialect.DRAFT6, Dialect.DRAFT7):
                min_c, max_c = 1, None
            count = 0
            for i, item in enumerate(arr):
                ok, _, _ = self._validate(s["contains"], item, base, depth + 1)
                if ok:
                    count += 1
                    evaluated.add(i)
            if count < min_c or (max_c is not None and count > max_c):
                return False, evaluated
        return True, evaluated

    # ------------------------------------------------------------------

    def _split_items(self, s: Dict[str, Any]):
        if self.dialect in (Dialect.DRAFT2019, Dialect.DRAFT2020):
            prefix = s.get("prefixItems") or []
            items = s.get("items")
            if self.dialect is Dialect.DRAFT2019 and isinstance(items, list):
                return items, s.get("additionalItems")
            return list(prefix), items
        items = s.get("items")
        if isinstance(items, list):
            return items, s.get("additionalItems")
        return [], items

    def _dependent_required(self, s: Dict[str, Any]):
        out = []
        dr = s.get("dependentRequired")
        if isinstance(dr, dict):
            out.extend((k, v) for k, v in dr.items() if isinstance(v, list))
        legacy = s.get("dependencies")
        if isinstance(legacy, dict):
            out.extend((k, v) for k, v in legacy.items() if isinstance(v, list))
        return out

    def _dependent_schemas(self, s: Dict[str, Any]):
        out = []
        ds = s.get("dependentSchemas")
        if isinstance(ds, dict):
            out.extend(ds.items())
        legacy = s.get("dependencies")
        if isinstance(legacy, dict):
            out.extend((k, v) for k, v in legacy.items() if not isinstance(v, list))
        return out

    def _directly_evaluated(self, s: Dict[str, Any], key: str) -> bool:
        if key in (s.get("properties") or {}):
            return True
        for pat in s.get("patternProperties") or {}:
            if re.search(pat, key, re.DOTALL) is not None:
                return True
        return "additionalProperties" in s

    def _direct_prefix(self, s: Dict[str, Any]) -> int:
        prefix, tail = self._split_items(s)
        if tail is not None:
            return 1 << 30
        return len(prefix)

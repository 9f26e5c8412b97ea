"""How ``correct`` is decided: every verdict the window produced against
the plain reference (``bench/reference/naive.py``).

A verdict is one of three classes: the document is valid, it is invalid,
or the admission guard rejects the request (body over the byte cap, not
JSON, nesting or node count over the caps).  The reference works each
one out from the request text and the configuration's schemas alone.
The program's ``admitted``/``invalid``/``rejected_guard`` outcomes map
onto the classes; ``timed_out``, ``undecided_fallback`` and
``error_isolated`` are no verdict: they count as ``failed``, not as
wrong.  Two numbers are compared, each with the limit 0:

- ``mismatches``: verdicts that differ from the reference's;
- ``missing``: requests due in the window that got no result at all.

The control (``numbers="bfloat16"``) is the same reference with every
number of the document held in bfloat16, the precision below the
float32 in which the batched executor compares numbers: it has to
disagree with the exact reference on the traffic's boundary values.
"""

from __future__ import annotations

import json
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence

from bench.reference.naive import NaiveValidator

VALID, INVALID, GUARD = "valid", "invalid", "guard"
FAILED = ("timed_out", "undecided_fallback", "error_isolated")
CLASS_OF = {"admitted": VALID, "invalid": INVALID, "rejected_guard": GUARD}


def _to_bfloat16(value: Any) -> Any:
    import ml_dtypes
    import numpy as np

    if isinstance(value, bool) or value is None or isinstance(value, str):
        return value
    if isinstance(value, (int, float)):
        return float(np.asarray(value, np.float64).astype(ml_dtypes.bfloat16))
    if isinstance(value, list):
        return [_to_bfloat16(v) for v in value]
    if isinstance(value, dict):
        return {k: _to_bfloat16(v) for k, v in value.items()}
    return value


def _over_caps(doc: Any, max_depth: int, max_nodes: int) -> bool:
    """Whether ``doc`` nests deeper than ``max_depth`` (the root at depth
    0) or holds more than ``max_nodes`` values."""
    nodes = 0
    stack = [(doc, 0)]
    while stack:
        value, depth = stack.pop()
        nodes += 1
        if depth > max_depth or nodes > max_nodes:
            return True
        if isinstance(value, dict):
            stack.extend((v, depth + 1) for v in value.values())
        elif isinstance(value, list):
            stack.extend((v, depth + 1) for v in value)
    return False


class Reference:
    """Expected verdict classes for requests to one configuration."""

    def __init__(self, schemas: Dict[str, Any], guard: Dict[str, int], numbers: str = "exact"):
        if numbers not in ("exact", "bfloat16"):
            raise ValueError(f"numbers {numbers!r}")
        self.validators = {ep: NaiveValidator(schema) for ep, schema in schemas.items()}
        self.guard = guard
        self.numbers = numbers

    def expect(self, endpoint: str, text: str) -> str:
        if endpoint not in self.validators or len(text) > self.guard["max_bytes"]:
            return GUARD
        try:
            doc = json.loads(text)
        except (json.JSONDecodeError, RecursionError):
            return GUARD
        if _over_caps(doc, self.guard["max_depth"], self.guard["max_nodes"]):
            return GUARD
        if self.numbers == "bfloat16":
            doc = _to_bfloat16(doc)
        return VALID if self.validators[endpoint].is_valid(doc) else INVALID


@dataclass
class Comparison:
    compared: int
    mismatches: int
    missing: int
    failed: int
    examples: List[str]

    @property
    def correct(self) -> bool:
        return self.mismatches == 0 and self.missing == 0

    def limits(self) -> Dict[str, Dict[str, int]]:
        return {
            "mismatches": {"value": self.mismatches, "limit": 0},
            "missing": {"value": self.missing, "limit": 0},
        }


def compare(expected: Sequence[str], outcomes: Sequence[Optional[str]], endpoints: Sequence[str]) -> Comparison:
    """``outcomes[i]`` is the program's outcome value for request ``i`` (None
    where it gave no result); ``expected[i]`` is the reference's class."""
    mismatches = missing = failed = compared = 0
    examples: List[str] = []
    for i, (want, got) in enumerate(zip(expected, outcomes)):
        if got is None:
            missing += 1
            continue
        if got in FAILED:
            failed += 1
            continue
        compared += 1
        if CLASS_OF[got] != want:
            mismatches += 1
            if len(examples) < 5:
                examples.append(f"request {i} ({endpoints[i]}): {got}, reference {want}")
    return Comparison(compared, mismatches, missing, failed, examples)

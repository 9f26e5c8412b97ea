"""The general traffic generator: one traffic file's parameters in,
the requests of one run out.

A traffic file (``bench/traffic/<name>.json``) holds:

- ``loop``: ``"open"`` (requests fall due on a schedule, whatever the
  system does) or ``"closed"`` (one client submits a batch, waits for
  its verdicts, and submits the next);
- open loop: ``arrivals`` = ``{"kind": "poisson", "rate_per_s": r}``;
- closed loop: ``batch`` (requests per submission) and ``pool`` (how
  many distinct submissions the client cycles through);
- ``weights``: ``[[name, weight], ...]`` over the configuration's
  endpoints (a tie in rounding goes to the earlier one);
- ``broken_share``: documents broken at one keyword each;
- ``boundary_share``: documents given one bounded integer at its bound
  or one past it;
- ``malformed_share``: request bodies cut short, so that JSON decoding
  fails;
- ``scheduler``: the stream scheduler's settings that the cell fixes.

Every seed gets the same work in another order: an open loop sends
exactly ``rate_per_s * seconds`` requests, at times drawn uniformly over
the window (a Poisson process given its count), and a closed loop the
same batch and pool sizes.  Each block of requests (a closed loop's
batch, an open loop's whole run) holds every endpoint the same number of
times for every seed, its share of ``weights`` rounded by largest
remainder, and the same number of documents picked to be broken, set
at a bound and cut short; the seed draws the documents and their order.
"""

from __future__ import annotations

import copy
import json
import random
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple


@dataclass
class Requests:
    """One run's requests: JSON text, endpoint and (open loop) due time."""

    texts: List[str]
    endpoints: List[str]
    due_s: Optional[List[float]] = None  # open loop, from the window's start


def _bounded_ints(schema: Any) -> List[Tuple[str, List[int]]]:
    """Top-level properties of ``schema`` that are integers with a bound,
    with the values at and one past each bound."""
    out = []
    props = schema.get("properties", {}) if isinstance(schema, dict) else {}
    for key, sub in props.items():
        if not isinstance(sub, dict) or sub.get("type") != "integer":
            continue
        values = []
        if isinstance(sub.get("minimum"), int):
            values += [sub["minimum"], sub["minimum"] - 1]
        if isinstance(sub.get("maximum"), int):
            values += [sub["maximum"], sub["maximum"] + 1]
        if values:
            out.append((key, values))
    return out


def set_boundary(schema: Any, doc: Any, rng: random.Random) -> bool:
    """Set one bounded top-level integer of ``doc`` at a bound or one past
    it; False where ``schema`` has no such property."""
    choices = _bounded_ints(schema)
    if not choices or not isinstance(doc, dict):
        return False
    key, values = rng.choice(choices)
    doc[key] = rng.choice(values)
    return True


def break_keyword(schema: Any, doc: Any, rng: random.Random) -> bool:
    """Break ``doc`` at one keyword of ``schema``'s top level: drop a
    required key, cross an integer bound by one, or put a value outside
    an ``enum`` or of another ``type``.  False where none applies."""
    if not isinstance(schema, dict) or not isinstance(doc, dict):
        return False
    ops = []
    for key in schema.get("required", []):
        if key in doc:
            ops.append(("drop", key, None))
    for key, values in _bounded_ints(schema):
        sub = schema["properties"][key]
        crossed = [sub["minimum"] - 1] if isinstance(sub.get("minimum"), int) else []
        crossed += [sub["maximum"] + 1] if isinstance(sub.get("maximum"), int) else []
        ops.append(("put", key, crossed))
    for key, sub in schema.get("properties", {}).items():
        if not isinstance(sub, dict):
            continue
        if "enum" in sub:
            ops.append(("put", key, ["not-" + "-".join(map(str, sub["enum"]))]))
        elif sub.get("type") in ("string", "object", "array"):
            ops.append(("put", key, [12345]))
        elif sub.get("type") in ("integer", "number", "boolean"):
            ops.append(("put", key, ["x"]))
    if not ops:
        return False
    kind, key, values = rng.choice(ops)
    if kind == "drop":
        doc.pop(key)
    else:
        doc[key] = rng.choice(values)
    return True


def apportion(weights: Sequence[float], n: int) -> List[int]:
    """``n`` split over ``weights`` by largest remainder: each count is
    its quota rounded down or up, ties to the earlier weight."""
    total = float(sum(weights))
    quotas = [n * w / total for w in weights]
    counts = [int(q) for q in quotas]
    by_remainder = sorted(range(len(weights)), key=lambda i: (counts[i] - quotas[i], i))
    for i in by_remainder[: n - sum(counts)]:
        counts[i] += 1
    return counts


def mix(weights: Sequence[Tuple[str, float]], n: int, block: int, rng: random.Random) -> List[str]:
    """The endpoints of ``n`` requests: each block of ``block`` holds every
    endpoint its apportioned number of times, shuffled."""
    names = [name for name, _ in weights]
    out: List[str] = []
    for start in range(0, n, block):
        size = min(block, n - start)
        counts = apportion([float(w) for _, w in weights], size)
        part = [name for name, c in zip(names, counts) for _ in range(c)]
        rng.shuffle(part)
        out += part
    return out


def _picks(n: int, block: int, share: float, rng: random.Random) -> List[int]:
    """``share`` of each block's indices, drawn from ``rng``."""
    out: List[int] = []
    for start in range(0, n, block):
        size = min(block, n - start)
        out += [start + i for i in rng.sample(range(size), round(share * size))]
    return out


def _edit(
    docs: List[Any], endpoints: List[str], share: float, edit, rng: random.Random, block: int
) -> None:
    for i in _picks(len(docs), block, share, rng):
        doc = copy.deepcopy(docs[i])
        if edit(endpoints[i], doc, rng):
            docs[i] = doc


def _texts(docs: List[Any], share: float, rng: random.Random, block: int) -> List[str]:
    texts = [json.dumps(d) for d in docs]
    for i in _picks(len(texts), block, share, rng):
        texts[i] = texts[i][: max(1, len(texts[i]) // 2)]  # cut short: not JSON
    return texts


def _due_times(arrivals: Dict[str, Any], n: int, seconds: float, rng: random.Random) -> List[float]:
    kind = arrivals["kind"]
    if kind == "poisson":
        return sorted(rng.uniform(0.0, seconds) for _ in range(n))
    raise ValueError(f"unknown arrivals kind {kind!r}")


def generate(
    source, traffic: Dict[str, Any], *, n: int, seed: int, seconds: float = 0.0, block: Optional[int] = None
) -> Requests:
    """``n`` requests of ``traffic`` from the documents ``source`` (what a
    documents module's ``build(config)`` returns: ``schemas``, ``draw`` and
    ``break_one``), made from ``seed`` alone, in blocks of ``block``
    (default: one block of ``n``)."""
    rng = random.Random(seed)
    block = block or n
    endpoints = mix(traffic["weights"], n, block, rng)
    docs = source.draw(endpoints, rng)
    _edit(docs, endpoints, traffic.get("broken_share", 0.0), source.break_one, rng, block)
    schemas = source.schemas
    _edit(
        docs,
        endpoints,
        traffic.get("boundary_share", 0.0),
        lambda ep, doc, r: set_boundary(schemas[ep], doc, r),
        rng,
        block,
    )
    texts = _texts(docs, traffic.get("malformed_share", 0.0), rng, block)
    due = None
    if traffic["loop"] == "open":
        due = _due_times(traffic["arrivals"], n, seconds, rng)
    return Requests(texts, endpoints, due)


def open_loop_count(traffic: Dict[str, Any], seconds: float) -> int:
    return max(1, round(float(traffic["arrivals"]["rate_per_s"]) * seconds))


def pool(source, traffic: Dict[str, Any], *, seed: int) -> List[Requests]:
    """A closed loop's submissions: ``pool`` batches of ``batch`` requests."""
    size, batch = int(traffic["pool"]), int(traffic["batch"])
    everything = generate(source, traffic, n=size * batch, seed=seed, block=batch)
    return [
        Requests(everything.texts[k * batch : (k + 1) * batch], everything.endpoints[k * batch : (k + 1) * batch])
        for k in range(size)
    ]

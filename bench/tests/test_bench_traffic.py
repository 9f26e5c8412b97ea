"""The general generator: the same work for every seed, made from the
seed alone."""

import json
import random

from bench.lib import spec
from bench.lib import traffic as T


def _stream():
    from bench.tests.tree import SMALL_OPEN, small_config

    config = small_config()
    return spec.documents(config["documents"]).build(config), SMALL_OPEN


def test_open_loop_sends_rate_times_seconds_requests_from_the_seed():
    source, tr = _stream()
    n = T.open_loop_count(tr, 2.0)
    assert n == round(tr["arrivals"]["rate_per_s"] * 2.0)
    a = T.generate(source, tr, n=n, seed=2**40 + 3, seconds=2.0)
    b = T.generate(source, tr, n=n, seed=2**40 + 3, seconds=2.0)
    c = T.generate(source, tr, n=n, seed=7, seconds=2.0)
    assert a == b and a != c
    assert len(a.texts) == len(c.texts) == len(a.due_s) == n
    assert a.due_s == sorted(a.due_s) and 0.0 <= a.due_s[0] and a.due_s[-1] < 2.0


def test_apportion_splits_by_largest_remainder():
    assert T.apportion([1, 1, 1], 10) == [4, 3, 3]
    assert T.apportion([3888, 980, 382], 1024) == [758, 191, 75]
    assert T.apportion([5, 0, 5], 7) == [4, 0, 3]


def test_every_seed_gets_the_same_mix_in_each_block():
    from collections import Counter

    source, tr = _stream()
    tr = dict(tr, broken_share=0.1, malformed_share=0.05)
    blocks = {}
    for seed in (3, 2**31 + 17):
        reqs = T.generate(source, tr, n=300, seed=seed, block=96)
        blocks[seed] = [Counter(reqs.endpoints[k : k + 96]) for k in range(0, 300, 96)]
        for k in range(0, 300, 96):
            texts = reqs.texts[k : k + 96]
            bad = 0
            for text in texts:
                try:
                    json.loads(text)
                except json.JSONDecodeError:
                    bad += 1
            assert bad == round(0.05 * len(texts))
    a, b = blocks.values()
    assert a == b and a[0] == a[1] and sum(a[3].values()) == 300 - 3 * 96
    assert dict(a[0]) == dict(zip([n for n, _ in tr["weights"]], T.apportion([w for _, w in tr["weights"]], 96)))


def test_shares_are_exact_counts():
    source, tr = _stream()
    reqs = T.generate(source, dict(tr, malformed_share=0.05), n=1000, seed=1, seconds=1.0)
    bad = 0
    for text in reqs.texts:
        try:
            json.loads(text)
        except json.JSONDecodeError:
            bad += 1
    assert bad == 50


def test_boundary_values_sit_at_or_one_past_a_bound():
    schema = {"type": "object", "properties": {"n": {"type": "integer", "minimum": 1, "maximum": 4096}}}
    rng = random.Random(0)
    seen = set()
    for _ in range(200):
        doc = {}
        assert T.set_boundary(schema, doc, rng)
        seen.add(doc["n"])
    assert seen == {0, 1, 4096, 4097}
    assert not T.set_boundary({"type": "object", "properties": {"s": {"type": "string"}}}, {}, rng)


def test_break_keyword_breaks_one_keyword():
    schema = {
        "type": "object",
        "required": ["a"],
        "properties": {"a": {"type": "string"}, "b": {"enum": ["x", "y"]}, "c": {"type": "integer", "maximum": 9}},
    }
    rng = random.Random(1)
    outcomes = set()
    for _ in range(200):
        doc = {"a": "s", "b": "x", "c": 3}
        assert T.break_keyword(schema, doc, rng)
        changed = {k for k in ("a", "b", "c") if doc.get(k, None) != {"a": "s", "b": "x", "c": 3}[k]}
        assert len(changed) == 1
        outcomes.add(next(iter(changed)))
    assert outcomes == {"a", "b", "c"}

"""The benchmark's copy of the corpus generator gives byte-identical
schemas and documents to the original (``data/corpus.py``) at three
seeds.

Two checks: against digests recorded from the original, which stand
when the original is gone, and directly against the original while it
exists."""

import hashlib
import json

import pytest

from bench.traffic import corpus

SEEDS = (0, 7, 2**31 + 5)
# small and large schemas, both dialects ($dynamicRef in cql2 and openapi)
DATASETS = ("helm-chart-lock", "importmap", "cql2", "openapi", "clang-format", "aws-cdk")
SCALE = 0.1


def _digest(value) -> str:
    return hashlib.sha256(json.dumps(value).encode()).hexdigest()[:16]


def _corpus(module, seed):
    out = {}
    for index, (name, n, kb, avg) in enumerate(module.TABLE3):
        if name in DATASETS:
            ds = module.make_dataset(name, n, kb, avg, seed=seed * 1000 + index, scale=SCALE)
            out[name] = [ds.schema, ds.documents]
    return out


# recorded from data/corpus.py at SEEDS
CORPUS_DIGESTS = {0: "e505f61f2afb941d", 7: "6c6e29cf7e84b808", 2**31 + 5: "592f32d0adcf9e22"}


@pytest.mark.parametrize("seed", SEEDS)
def test_corpus_copy_matches_recorded_original(seed):
    assert _digest(_corpus(corpus, seed)) == CORPUS_DIGESTS[seed]


@pytest.mark.parametrize("seed", SEEDS)
def test_corpus_copy_matches_original(seed):
    from repro.data import corpus as original

    assert json.dumps(_corpus(corpus, seed)) == json.dumps(_corpus(original, seed))


def test_schema_build_is_fast_for_the_largest_schemas():
    """The running sizes keep the 383 KB schemas linear (the original
    re-serialises the schema for every key)."""
    import time

    t = time.perf_counter()
    for index, (name, _n, kb, avg) in enumerate(corpus.TABLE3):
        if kb > 300:
            bp, _ = corpus.build_schema(name, kb, avg, seed=index)
            assert len(json.dumps(bp.schema)) >= kb * 1024
    assert time.perf_counter() - t < 5.0

"""The bytes and operations behind ``launch_roofline``, at one shape of
the taped cell: 256 documents of at most 64 nodes on the link groups of
the datasets that build a tape."""

import json
import random

from bench.lib.harness import _launch_plan, doc_bytes, tape_bytes
from bench.lib.launch_work import OUTPUT_FLAGS, SCHEMA_ID_BYTES, launch_work
from bench.lib.traffic import Requests, mix
from bench.lib import spec
from bench.tests.tree import MIX, small_config

B, N = 256, 64


def _source():
    config = small_config()
    return spec.documents(config["documents"]).build(config)


def _engine():
    from bench.lib.harness import build_engine

    return build_engine(dict(small_config(), admission_max_nodes=N), _source().schemas)


def mixed_stream(n, rng):
    endpoints = mix(MIX, n, n, rng)
    return _source().draw(endpoints, rng), endpoints


def test_table_bytes_are_the_columns_the_launch_uploads():
    import jax.numpy as jnp

    from repro.data.doc_table import encode_batch

    docs, _ = mixed_stream(B, random.Random(3))
    table = encode_batch(docs, max_nodes=N)
    uploaded = sum(jnp.asarray(v).nbytes for v in table.columns().values())
    work = launch_work(B, N, doc_bytes(N), tape_bytes=0, k_cand=1, n_window=1)
    assert work.bytes == uploaded + B * (SCHEMA_ID_BYTES + OUTPUT_FLAGS)
    assert work.ops == B * N * 2


def test_launch_counts_its_tape_and_windows():
    engine = _engine()
    tape = engine.registry.group_of("jasmine").tape
    k, a = int(tape.max_hash_run), int(tape.max_rows_per_loc)
    assert tape_bytes(tape) > 0 and k >= 1 and a >= 1
    work = launch_work(B, N, doc_bytes(N), tape_bytes(tape), k, a)
    assert work.bytes == B * (doc_bytes(N) + SCHEMA_ID_BYTES + OUTPUT_FLAGS) + tape_bytes(tape)
    assert work.ops == B * N * (k + a)
    peaks = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    assert work.least_s(peaks) == max(work.bytes / 819e9, work.ops / 393e12)
    assert work.bound(peaks) == "bytes"


def test_plan_has_one_launch_per_link_group_padded_to_pow2():
    engine = _engine()
    docs, endpoints = mixed_stream(B, random.Random(5))
    texts = [json.dumps(d) for d in docs]
    texts[0] = texts[0][:-1]  # not JSON: rejected before any launch
    plan = _launch_plan(engine, Requests(texts, endpoints), N, doc_bytes(N))
    rows, tapes = {}, {}
    for ep in endpoints[1:]:
        group = engine.registry.group_of(ep)
        if group is None:  # the sequential fallback: no launch
            continue
        rows[group.label] = rows.get(group.label, 0) + 1
        tapes[group.label] = group.tape
    assert len(plan) == len(rows) > 1
    expected = sum(
        (1 << (n - 1).bit_length()) * (doc_bytes(N) + SCHEMA_ID_BYTES + OUTPUT_FLAGS) + tape_bytes(tapes[label])
        for label, n in rows.items()
    )
    assert sum(w.bytes for w in plan) == expected

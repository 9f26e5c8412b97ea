"""Beyond-paper: batched (TPU-form) executor throughput + corpus coverage.

Measures (a) what fraction of the benchmark corpus compiles to the
structural-subset tensor tape (the batch fast path), and (b) throughput of
the batched executor on an API-gateway-style request schema at increasing
batch sizes (one hoisted hash pass + owner-sorted (B*N x A-hat) assertion
windows).  jnp path on CPU; the Pallas path is validated separately in
tests with interpret=True.

Emits ``results/BENCH_batched.json`` -- docs/s per batch size, the
tape-coverage fraction, and the per-tape A-hat/K constants --
so the perf trajectory stays machine-readable across PRs.
"""

from __future__ import annotations

import json
import os
import time
from pathlib import Path
from typing import Dict, List

from repro.core import Validator, compile_schema
from repro.core.batch_executor import BatchValidator
from repro.core.doc_model import parse_document
from repro.core.tape import try_build_tape
from repro.data.corpus import make_corpus
from repro.data.doc_table import encode_batch
from repro.serve.engine import REQUEST_SCHEMA

SCALE = float(os.environ.get("BENCH_CORPUS_SCALE", "0.1"))
BATCH_SIZES = (64, 512, 4096)
RESULTS = Path(__file__).resolve().parents[1] / "results"


def run(report: Dict[str, object]) -> List[str]:
    lines: List[str] = []

    # -- (a) corpus coverage of the tensor tape ------------------------------
    corpus = make_corpus(scale=SCALE)
    batchable, reasons = 0, {}
    tape_stats = []
    for ds in corpus:
        tape, reason = try_build_tape(compile_schema(ds.schema))
        if tape is not None:
            batchable += 1
            tape_stats.append(
                {
                    "dataset": ds.name,
                    "a_hat": tape.max_rows_per_loc,
                    "k": tape.max_hash_run,
                    "assertions": tape.n_assertions,
                    "locations": tape.n_locations,
                }
            )
        else:
            reasons[ds.name] = reason
    coverage = batchable / len(corpus)
    lines.append(f"batched/corpus_coverage,{coverage*100:.1f},percent_of_38_datasets")

    # -- (b) throughput on the serving request schema -------------------------
    # the full engine schema uses propertyNames (key-loop) for `metadata`,
    # which stays on the sequential fallback; the batched path handles the
    # structural rest -- benchmark that subset explicitly
    schema = {k: v for k, v in REQUEST_SCHEMA.items() if k != "properties"}
    schema["properties"] = {
        k: v for k, v in REQUEST_SCHEMA["properties"].items() if k != "metadata"
    }
    compiled = compile_schema(schema)
    tape, reason = try_build_tape(compiled)
    assert tape is not None, f"request schema must be batchable: {reason}"
    seq = Validator(compiled)
    bv = BatchValidator(tape, use_pallas=False)

    import random

    rng = random.Random(0)
    def mk_request(i):
        req = {
            "prompt": "hello world " * rng.randint(1, 20),
            "max_tokens": rng.randint(1, 512),
            "temperature": round(rng.random(), 2),
        }
        if i % 7 == 0:
            req["bogus_field"] = True  # invalid: closed object
        if i % 11 == 0:
            req["max_tokens"] = -5  # invalid: minimum
        return req

    rows = []
    for batch in BATCH_SIZES:
        docs = [mk_request(i) for i in range(batch)]
        parsed = [parse_document(d) for d in docs]
        t0 = time.perf_counter()
        seq_results = [seq.is_valid(d, parsed=True) for d in parsed]
        t_seq = time.perf_counter() - t0

        table = encode_batch(docs, max_nodes=64)
        t0 = time.perf_counter()
        encode_batch(docs, max_nodes=64)
        t_encode = time.perf_counter() - t0

        row = {
            "batch": batch,
            "sequential_docs_per_s": batch / t_seq,
            "sequential_us_per_doc": t_seq / batch * 1e6,
            "encode_us_per_doc": t_encode / batch * 1e6,
        }
        bv.validate(table)  # warm the jit
        t0 = time.perf_counter()
        valid, decided = bv.validate(table)
        t_batch = time.perf_counter() - t0
        assert all(bool(v) == r for v, r, d in zip(valid, seq_results, decided) if d)
        row["csr_docs_per_s"] = batch / t_batch
        row["csr_us_per_doc"] = t_batch / batch * 1e6
        rows.append(row)
        lines.append(
            f"batched/request_validation_b{batch},{row['csr_us_per_doc']:.2f},"
            f"seq_us={row['sequential_us_per_doc']:.2f}"
        )

    payload = {
        "schema": "api_gateway_request",
        "tape": {
            "a_hat": tape.max_rows_per_loc,
            "k": tape.max_hash_run,
            "assertions": tape.n_assertions,
            "prop_rows": tape.n_props,
            "locations": tape.n_locations,
        },
        "coverage": coverage,
        "corpus_tapes": tape_stats,
        "unbatchable": reasons,
        "throughput": rows,
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_batched.json").write_text(json.dumps(payload, indent=2))
    lines.append(f"batched/bench_json,0,results/BENCH_batched.json")
    report["batched"] = payload
    return lines

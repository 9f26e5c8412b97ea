"""Table 5 / Figure 6 analogue: per-dataset validation runtime.

Compares, per corpus dataset:
  * ``blaze``   -- compiled, all optimizations on (the paper's system)
  * ``codegen`` -- beyond-paper closure compilation (the paper's §8
                   future work, core/codegen.py)
  * ``unopt``   -- compiled with every §4 optimization disabled + string
                   comparison instead of semi-perfect hashing
  * ``naive``   -- the schema-walking interpreter (the "existing
                   validator" comparison point, cf. Python jsonschema)

Cold = first pass over the documents right after compilation; warm = best
of ``WARM_ROUNDS`` subsequent passes (paper §6.2.2 methodology).  Summary
= total across datasets + geomean speedup vs each baseline.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

from repro.core import CompilerOptions, NaiveValidator, Validator, compile_schema
from repro.core.doc_model import parse_document
from repro.data.corpus import make_corpus

SCALE = float(os.environ.get("BENCH_CORPUS_SCALE", "0.25"))
WARM_ROUNDS = int(os.environ.get("BENCH_WARM_ROUNDS", "3"))

_UNOPT = CompilerOptions(
    unroll=False, regex_specialize=False, reorder=False, cisc=False, elide=False
)


def _time_pass(validator, docs, *, parsed=True) -> float:
    t0 = time.perf_counter()
    for d in docs:
        validator.is_valid(d, parsed=True) if parsed else validator.is_valid(d)
    return time.perf_counter() - t0


def run(report: Dict[str, object]) -> List[str]:
    lines = []
    corpus = make_corpus(scale=SCALE)
    totals = {
        "blaze": [0.0, 0.0], "codegen": [0.0, 0.0],
        "unopt": [0.0, 0.0], "naive": [0.0, 0.0],
    }
    rows = []
    for ds in corpus:
        docs_parsed = [parse_document(d) for d in ds.documents]

        t0 = time.perf_counter()
        compiled = compile_schema(ds.schema)
        compile_s = time.perf_counter() - t0
        blaze = Validator(compiled)
        codegen = Validator(compiled, engine="codegen")
        unopt = Validator(compile_schema(ds.schema, options=_UNOPT), use_hashing=False)
        naive = NaiveValidator(ds.schema)

        # correctness cross-check on this dataset (documents are valid by
        # construction; all engines must agree)
        for d, dp in zip(ds.documents[:25], docs_parsed[:25]):
            a = blaze.is_valid(dp, parsed=True)
            b = naive.is_valid(d)
            c = codegen.is_valid(d)  # codegen reads the decoded tree
            assert a and b and c, f"validator disagreement on {ds.name}"

        cold = {
            "blaze": _time_pass(blaze, docs_parsed),
            "codegen": _time_pass(codegen, ds.documents),
            "unopt": _time_pass(unopt, docs_parsed),
        }
        t0 = time.perf_counter()
        for d in ds.documents:
            naive.is_valid(d)
        cold["naive"] = time.perf_counter() - t0

        warm = {k: float("inf") for k in cold}
        for _ in range(WARM_ROUNDS):
            warm["blaze"] = min(warm["blaze"], _time_pass(blaze, docs_parsed))
            warm["codegen"] = min(warm["codegen"], _time_pass(codegen, ds.documents))
            warm["unopt"] = min(warm["unopt"], _time_pass(unopt, docs_parsed))
            t0 = time.perf_counter()
            for d in ds.documents:
                naive.is_valid(d)
            warm["naive"] = min(warm["naive"], time.perf_counter() - t0)

        n = len(ds.documents)
        for k in totals:
            totals[k][0] += cold[k]
            totals[k][1] += warm[k]
        rows.append(
            dict(
                name=ds.name, docs=n, compile_s=compile_s,
                schema_kb=ds.schema_bytes / 1024,
                **{f"{k}_cold_ms": cold[k] * 1e3 for k in cold},
                **{f"{k}_warm_ms": warm[k] * 1e3 for k in warm},
            )
        )
        lines.append(
            f"validation/{ds.name},{warm['blaze']/n*1e6:.2f},"
            f"naive_x={warm['naive']/max(warm['blaze'],1e-12):.1f};"
            f"unopt_x={warm['unopt']/max(warm['blaze'],1e-12):.1f}"
        )

    cold_speedup = totals["naive"][0] / max(totals["blaze"][0], 1e-12)
    warm_speedup = totals["naive"][1] / max(totals["blaze"][1], 1e-12)
    unopt_speedup = totals["unopt"][1] / max(totals["blaze"][1], 1e-12)
    cg_cold = totals["naive"][0] / max(totals["codegen"][0], 1e-12)
    cg_warm = totals["naive"][1] / max(totals["codegen"][1], 1e-12)
    lines.append(f"validation/TOTAL_cold_speedup_vs_naive,{cold_speedup:.2f},x")
    lines.append(f"validation/TOTAL_warm_speedup_vs_naive,{warm_speedup:.2f},x")
    lines.append(f"validation/TOTAL_warm_speedup_vs_unopt,{unopt_speedup:.2f},x")
    lines.append(f"validation/TOTAL_codegen_cold_speedup_vs_naive,{cg_cold:.2f},x")
    lines.append(f"validation/TOTAL_codegen_warm_speedup_vs_naive,{cg_warm:.2f},x")
    report["validation"] = {"rows": rows, "totals": totals,
                            "speedups": {"cold_vs_naive": cold_speedup,
                                         "warm_vs_naive": warm_speedup,
                                         "warm_vs_unopt": unopt_speedup,
                                         "codegen_cold_vs_naive": cg_cold,
                                         "codegen_warm_vs_naive": cg_warm}}
    return lines

"""Logical applicators on the batched path (assertion-group circuits).

Before DESIGN.md §10, ANY ``anyOf``/``oneOf``/``not``/``if`` schema fell
back 100% to the sequential engine -- and tagged unions (the most common
real-world API-payload shape for logical applicators) are exactly that.
This benchmark measures what the circuit lowering buys on
discriminated-union traffic:

* **throughput** -- a payments-style tagged union (``oneOf`` over four
  method shapes discriminated by ``kind``) at B in {64, 512, 4096}: the
  hybrid path (one batched launch, all documents decided) against the
  old all-sequential fallback (which is just the sequential engine, so
  ``speedup_vs_sequential`` IS the hybrid-vs-fallback ratio);
* **shape sweep** -- batched speedup as the union widens (2..8 branches)
  at B=4096, with the tape's circuit/window growth (C, A-hat) reported
  alongside.

Emits ``results/BENCH_logical.json``.
"""

from __future__ import annotations

import json
import random
import time
from pathlib import Path
from typing import Dict, List

from repro.core import Validator, compile_schema
from repro.core.batch_executor import BatchValidator
from repro.core.doc_model import parse_document
from repro.core.tape import build_tape
from repro.data.doc_table import encode_batch

BATCH_SIZES = (64, 512, 4096)
RESULTS = Path(__file__).resolve().parents[1] / "results"

UNION_SCHEMA = {
    "type": "object",
    "required": ["amount", "method"],
    "properties": {
        "amount": {"type": "integer", "minimum": 1, "maximum": 1_000_000},
        "currency": {"enum": ["usd", "eur", "gbp"]},
        "method": {
            "type": "object",
            "required": ["kind"],
            "properties": {"kind": {"enum": ["card", "bank", "wallet", "crypto"]}},
            "oneOf": [
                {
                    "properties": {
                        "kind": {"const": "card"},
                        "number": {"type": "string", "minLength": 12, "maxLength": 19},
                        "cvv": {"type": "string", "minLength": 3, "maxLength": 4},
                    },
                    "required": ["number", "cvv"],
                },
                {
                    "properties": {
                        "kind": {"const": "bank"},
                        "iban": {"type": "string", "minLength": 15, "maxLength": 34},
                    },
                    "required": ["iban"],
                },
                {
                    "properties": {
                        "kind": {"const": "wallet"},
                        "wallet_id": {"type": "string", "pattern": "^w-"},
                    },
                    "required": ["wallet_id"],
                },
                {
                    "properties": {
                        "kind": {"const": "crypto"},
                        "address": {"type": "string", "minLength": 20},
                        "chain": {"enum": ["btc", "eth"]},
                    },
                    "required": ["address", "chain"],
                },
            ],
        },
    },
}


def _method(rng: random.Random) -> dict:
    kind = rng.choice(["card", "bank", "wallet", "crypto"])
    if kind == "card":
        m = {"kind": kind, "number": "4111111111111111", "cvv": "123"}
    elif kind == "bank":
        m = {"kind": kind, "iban": "DE8937040044053201"}
    elif kind == "wallet":
        m = {"kind": kind, "wallet_id": f"w-{rng.randint(0, 999)}"}
    else:
        m = {"kind": kind, "address": "bc1" + "q" * 20, "chain": rng.choice(["btc", "eth"])}
    r = rng.random()
    if r < 0.04:
        m.pop(rng.choice([k for k in m if k != "kind"]))  # missing branch field
    elif r < 0.08:
        m["kind"] = rng.choice(["card", "bank", "wallet", "crypto"])  # kind swap
    return m


def _doc(rng: random.Random) -> dict:
    out = {"amount": rng.randint(1, 500_000), "method": _method(rng)}
    if rng.random() < 0.5:
        out["currency"] = rng.choice(["usd", "eur", "gbp"])
    if rng.random() < 0.03:
        out["amount"] = 0  # below minimum
    return out


def _wide_union(n_branches: int) -> dict:
    kinds = [f"k{i}" for i in range(n_branches)]
    return {
        "type": "object",
        "required": ["kind"],
        "properties": {"kind": {"enum": kinds}},
        "oneOf": [
            {
                "properties": {
                    "kind": {"const": k},
                    f"f{i}": {"type": "integer", "minimum": 0},
                },
                "required": [f"f{i}"],
            }
            for i, k in enumerate(kinds)
        ],
    }


def _hybrid_time(bv, seq, table, parsed) -> Dict[str, float]:
    """One batched launch + sequential routing of undecided rows."""
    bv.validate(table)  # warm the jit for this shape
    t_launch = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        valid, decided = bv.validate(table)
        t_launch = min(t_launch, time.perf_counter() - t0)
    t0 = time.perf_counter()
    routed = [
        bool(v) if d else seq.is_valid(p, parsed=True)
        for v, d, p in zip(valid, decided, parsed)
    ]
    t_route = time.perf_counter() - t0
    return {
        "seconds": t_launch + t_route,
        "launch_seconds": t_launch,
        "route_seconds": t_route,
        "fallback_rate": 1.0 - float(decided.mean()),
        "verdicts": routed,
    }


def run(report: Dict[str, object]) -> List[str]:
    lines: List[str] = []
    rng = random.Random(0x10C)
    payload: Dict[str, object] = {}

    compiled = compile_schema(UNION_SCHEMA)
    tape = build_tape(compiled)
    seq = Validator(compiled)
    seq_cg = Validator(compiled, engine="codegen")
    bv = BatchValidator(tape, use_pallas=False)

    payload["tape"] = {
        "locations": tape.n_locations,
        "n_circuits": tape.n_circuits,
        "max_circ_depth": tape.max_circ_depth,
        "a_hat": tape.max_rows_per_loc,
        "k": tape.max_hash_run,
        "horizon": tape.max_loc_depth + 1,
        "assertions": tape.n_assertions,
    }

    rows = []
    for batch in BATCH_SIZES:
        docs = [_doc(rng) for _ in range(batch)]
        parsed = [parse_document(d) for d in docs]
        t0 = time.perf_counter()
        seq_results = [seq.is_valid(p, parsed=True) for p in parsed]
        t_seq = time.perf_counter() - t0
        t0 = time.perf_counter()
        [seq_cg.is_valid(d) for d in docs]  # codegen reads the decoded tree
        t_seq_cg = time.perf_counter() - t0
        t0 = time.perf_counter()
        table = encode_batch(docs, max_nodes=16)
        t_encode = time.perf_counter() - t0
        hybrid = _hybrid_time(bv, seq, table, parsed)
        assert hybrid["verdicts"] == seq_results, "hybrid != sequential"
        rows.append(
            {
                "batch": batch,
                "invalid_rate": 1.0 - sum(seq_results) / batch,
                "sequential_us_per_doc": t_seq / batch * 1e6,
                "sequential_codegen_us_per_doc": t_seq_cg / batch * 1e6,
                "encode_us_per_doc": t_encode / batch * 1e6,
                "hybrid_us_per_doc": hybrid["seconds"] / batch * 1e6,
                "launch_us_per_doc": hybrid["launch_seconds"] / batch * 1e6,
                "fallback_rate": hybrid["fallback_rate"],
                # the pre-circuit behaviour was 100% sequential fallback,
                # so this ratio is hybrid vs the all-sequential baseline
                "speedup_vs_all_sequential": t_seq / hybrid["seconds"],
            }
        )
        lines.append(
            f"logical/union_b{batch},{rows[-1]['hybrid_us_per_doc']:.2f},"
            f"seq_us={rows[-1]['sequential_us_per_doc']:.2f};"
            f"x_allseq={rows[-1]['speedup_vs_all_sequential']:.2f};"
            f"fallback={rows[-1]['fallback_rate']:.3f}"
        )
    payload["throughput"] = rows

    # -- union-width sweep at the largest batch ---------------------------
    sweep = []
    batch = BATCH_SIZES[-1]
    for width in (2, 4, 8):
        schema = _wide_union(width)
        c = compile_schema(schema)
        t = build_tape(c)
        s = Validator(c)
        b = BatchValidator(t, use_pallas=False)
        docs = []
        for _ in range(batch):
            k = rng.randrange(width)
            d = {"kind": f"k{k}", f"f{k}": rng.randint(-1, 9)}
            if rng.random() < 0.1:
                d.pop(f"f{k}")
            docs.append(d)
        parsed = [parse_document(d) for d in docs]
        t0 = time.perf_counter()
        seq_results = [s.is_valid(p, parsed=True) for p in parsed]
        t_seq = time.perf_counter() - t0
        table = encode_batch(docs, max_nodes=8)
        hybrid = _hybrid_time(b, s, table, parsed)
        assert hybrid["verdicts"] == seq_results
        sweep.append(
            {
                "branches": width,
                "n_circuits": t.n_circuits,
                "a_hat": t.max_rows_per_loc,
                "hybrid_us_per_doc": hybrid["seconds"] / batch * 1e6,
                "sequential_us_per_doc": t_seq / batch * 1e6,
                "speedup_vs_all_sequential": t_seq / hybrid["seconds"],
            }
        )
    payload["width_sweep"] = sweep
    lines.append(
        f"logical/width8_b{batch},{sweep[-1]['hybrid_us_per_doc']:.2f},"
        f"x_allseq={sweep[-1]['speedup_vs_all_sequential']:.2f}"
    )

    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_logical.json").write_text(json.dumps(payload, indent=2))
    lines.append("logical/bench_json,0,results/BENCH_logical.json")
    report["logical"] = payload
    return lines


if __name__ == "__main__":
    out: Dict[str, object] = {}
    for line in run(out):
        print(line)

"""Beyond-paper: recursive-$ref schemas on the batched path (DESIGN.md §9).

Before bounded unrolling, ANY recursive schema fell back 100% to the
sequential engine.  This benchmark measures what the unrolled tape buys
on recursion-shaped traffic:

* **throughput** -- linked-list and binary-tree schemas at
  B in {64, 512, 4096}: the hybrid path (one batched launch + sequential
  routing of the frontier/undecided rows) against the old all-sequential
  fallback;
* **depth-distribution sweep** -- the same hybrid at increasing
  shares of documents deeper than the unroll budget (the overflow rate
  is the knob that decays batched throughput toward sequential);
* **unroll_depth sweep** -- overflow-fallback rate and tape size
  (locations / horizon / A-hat) as the budget grows on a fixed depth
  distribution.

Emits ``results/BENCH_recursive.json``.
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

LIST_SCHEMA = {
    "$defs": {
        "node": {
            "type": "object",
            "properties": {
                "value": {"type": "integer"},
                "next": {"$ref": "#/$defs/node"},
            },
            "required": ["value"],
        }
    },
    "$ref": "#/$defs/node",
}

TREE_SCHEMA = {
    "$defs": {
        "t": {
            "type": "object",
            "properties": {
                "v": {"type": "number", "minimum": 0},
                "left": {"$ref": "#/$defs/t"},
                "right": {"$ref": "#/$defs/t"},
            },
        }
    },
    "$ref": "#/$defs/t",
}


def _chain(rng: random.Random, depth: int) -> dict:
    doc = node = {"value": rng.randint(0, 9)}
    for _ in range(depth):
        node["next"] = node = {"value": rng.randint(0, 9)}
    if rng.random() < 0.05:
        node["value"] = "bad"  # ~5% invalid traffic (fails at the tail)
    return doc


def _tree(rng: random.Random, depth: int) -> dict:
    out = {"v": rng.random() if rng.random() > 0.1 else -1.0}
    if depth > 0:
        out["left"] = _tree(rng, depth - 1)
        if rng.random() < 0.7:
            out["right"] = _tree(rng, depth - 1)
    return out


def _sample_depth(rng: random.Random, unroll: int, deep_frac: float) -> int:
    if rng.random() < deep_frac:
        return unroll + rng.randint(1, 3)  # overruns the budget
    return rng.randint(0, unroll)


def _hybrid_time(bv, seq, table, parsed) -> Dict[str, float]:
    """One batched launch + sequential routing of undecided rows.

    Best-of-3 on the launch (jit already warm); like BENCH_batched /
    BENCH_registry, encode time is reported separately by the caller --
    the comparison is validate-vs-validate.
    """
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
    rng = random.Random(0x5EC)

    payload: Dict[str, object] = {"schemas": {}}

    for name, schema, gen, unroll, max_nodes in (
        # max_nodes sized to the budgeted doc shapes: chain(4) is 10
        # nodes, a depth-3 tree at most 31 -- padding is pure overhead
        ("linked_list", LIST_SCHEMA, _chain, 4, 16),
        ("binary_tree", TREE_SCHEMA, _tree, 3, 32),
    ):
        compiled = compile_schema(schema)
        tape = build_tape(compiled, unroll_depth=unroll)
        seq = Validator(compiled)
        seq_cg = Validator(compiled, engine="codegen")
        bv = BatchValidator(tape, use_pallas=False)

        tape_facts = {
            "unroll_depth": tape.unroll_depth,
            "locations": tape.n_locations,
            "n_frontier": tape.n_frontier,
            "horizon": tape.max_loc_depth + 1,
            "a_hat": tape.max_rows_per_loc,
            "k": tape.max_hash_run,
        }

        # -- throughput: all docs within budget (the common case) ---------
        # realistic recursive payloads carry real nesting: depth skews
        # toward the budget (GeoJSON geometries, AST nodes) rather than
        # degenerate empty chains
        rows = []
        for batch in BATCH_SIZES:
            docs = [
                gen(rng, max(1, rng.randint(0, unroll * 2) % (unroll + 1)))
                for _ in range(batch)
            ]
            parsed = [parse_document(d) for d in docs]
            t0 = time.perf_counter()
            seq_results = [seq.is_valid(p, parsed=True) for p in parsed]
            t_seq = time.perf_counter() - t0
            t0 = time.perf_counter()
            [seq_cg.is_valid(d) for d in docs]  # codegen reads the decoded tree
            t_seq_cg = time.perf_counter() - t0
            t0 = time.perf_counter()
            table = encode_batch(docs, max_nodes=max_nodes)
            t_encode = time.perf_counter() - t0
            hybrid = _hybrid_time(bv, seq, table, parsed)
            assert hybrid["verdicts"] == seq_results, name
            rows.append(
                {
                    "batch": batch,
                    "sequential_us_per_doc": t_seq / batch * 1e6,
                    "sequential_codegen_us_per_doc": t_seq_cg / batch * 1e6,
                    "encode_us_per_doc": t_encode / batch * 1e6,
                    "hybrid_us_per_doc": hybrid["seconds"] / batch * 1e6,
                    "launch_us_per_doc": hybrid["launch_seconds"] / batch * 1e6,
                    "fallback_rate": hybrid["fallback_rate"],
                    "speedup_vs_sequential": t_seq / hybrid["seconds"],
                }
            )
            lines.append(
                f"recursive/{name}_b{batch},{rows[-1]['hybrid_us_per_doc']:.2f},"
                f"seq_us={rows[-1]['sequential_us_per_doc']:.2f};"
                f"x_seq={rows[-1]['speedup_vs_sequential']:.2f};"
                f"fallback={rows[-1]['fallback_rate']:.3f}"
            )

        # -- depth-distribution sweep at B=4096 ---------------------------
        # deeper-than-budget docs need wider tables (a depth-6 tree is
        # ~250 nodes); the sweep pays that honestly
        sweep = []
        batch = BATCH_SIZES[-1]
        sweep_nodes = max_nodes * (2 if name == "linked_list" else 8)
        for deep_frac in (0.0, 0.05, 0.2, 0.5):
            docs = [
                gen(rng, _sample_depth(rng, unroll, deep_frac))
                for _ in range(batch)
            ]
            parsed = [parse_document(d) for d in docs]
            t0 = time.perf_counter()
            seq_results = [seq.is_valid(p, parsed=True) for p in parsed]
            t_seq = time.perf_counter() - t0
            table = encode_batch(docs, max_nodes=sweep_nodes)
            hybrid = _hybrid_time(bv, seq, table, parsed)
            assert hybrid["verdicts"] == seq_results, name
            sweep.append(
                {
                    "deep_fraction": deep_frac,
                    "fallback_rate": hybrid["fallback_rate"],
                    "hybrid_us_per_doc": hybrid["seconds"] / batch * 1e6,
                    "speedup_vs_sequential": t_seq / hybrid["seconds"],
                }
            )

        # -- unroll_depth sweep: overflow rate vs budget ------------------
        depth_sweep = []
        docs = [gen(rng, _sample_depth(rng, 4, 0.15)) for _ in range(512)]
        table = encode_batch(docs, max_nodes=sweep_nodes)
        for budget in (1, 2, 4, 6, 8):
            t = build_tape(compiled, unroll_depth=budget)
            b = BatchValidator(t, use_pallas=False)
            _, decided = b.validate(table)
            depth_sweep.append(
                {
                    "unroll_depth": budget,
                    "locations": t.n_locations,
                    "n_frontier": t.n_frontier,
                    "horizon": t.max_loc_depth + 1,
                    "overflow_fallback_rate": 1.0 - float(decided.mean()),
                }
            )
        lines.append(
            f"recursive/{name}_overflow_at_d4,"
            f"{depth_sweep[2]['overflow_fallback_rate']:.3f},"
            f"locations={depth_sweep[2]['locations']}"
        )

        payload["schemas"][name] = {
            "tape": tape_facts,
            "throughput": rows,
            "depth_distribution_sweep": sweep,
            "unroll_depth_sweep": depth_sweep,
        }

    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_recursive.json").write_text(json.dumps(payload, indent=2))
    lines.append("recursive/bench_json,0,results/BENCH_recursive.json")
    report["recursive"] = payload
    return lines

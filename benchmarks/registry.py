"""Beyond-paper: multi-tenant mixed-traffic validation through the registry.

A gateway hosting several endpoint schemas sees *interleaved* traffic.
This benchmark compares three ways to validate one skewed mixed stream
(4 endpoint schemas at 70/15/10/5):

- **sequential** -- per-document compiled codegen validator (the paper's
  single-request critical path);
- **per-schema sub-batch dispatch** -- split the stream by endpoint,
  encode + validate each group on its own single-schema tape (what mixed
  traffic forces without a linker);
- **linked tape** -- ONE batched launch over the registry's linked tape
  with per-document schema ids (``registry/linker.py``).

Emits ``results/BENCH_registry.json`` with docs/s per batch size for all
three paths plus the linked-tape constants, so the multi-tenant perf
trajectory stays machine-readable across PRs.  jnp path on CPU; the
Pallas kernels are validated separately in tests with interpret=True.
"""

from __future__ import annotations

import json
import random
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from repro.core.batch_executor import BatchValidator
from repro.data.doc_table import encode_batch
from repro.registry import SchemaRegistry
from repro.registry.presets import GATEWAY_SCHEMAS as SCHEMAS

BATCH_SIZES = (64, 512, 4096)
MAX_NODES = 64
RESULTS = Path(__file__).resolve().parents[1] / "results"

# skewed mix: completions dominate, moderation is the tail; charge is the
# tagged-union endpoint (logical-applicator circuits, DESIGN.md §10)
MIX = [("complete", 60), ("chat", 15), ("embed", 10), ("charge", 10), ("moderate", 5)]


def _mk_request(endpoint: str, i: int, rng: random.Random):
    bad = i % 9 == 0  # ~11% invalid traffic
    if endpoint == "complete":
        req = {
            "prompt": "hello world " * rng.randint(1, 12),
            "max_tokens": rng.randint(1, 512),
            "temperature": round(rng.random(), 2),
        }
        if bad:
            req["max_tokens"] = -1
    elif endpoint == "chat":
        req = {
            "messages": [
                {"role": rng.choice(["system", "user"]), "content": "hi " * rng.randint(1, 6)}
                for _ in range(rng.randint(1, 3))
            ],
            "max_tokens": rng.randint(1, 256),
        }
        if bad:
            req["messages"][0]["role"] = "robot"
    elif endpoint == "embed":
        req = {"input": "text " * rng.randint(1, 16), "dimensions": rng.choice([64, 256, 1024])}
        if bad:
            req["dimensions"] = 2
    elif endpoint == "charge":
        kind = rng.choice(["card", "bank", "wallet"])
        if kind == "card":
            method = {"kind": kind, "number": "4111111111111111", "cvv": "123"}
        elif kind == "bank":
            method = {"kind": kind, "iban": "DE89370400440532013000"}
        else:
            method = {"kind": kind, "wallet_id": f"w-{rng.randint(0, 999)}"}
        req = {
            "amount": rng.randint(1, 500_000),
            "currency": rng.choice(["usd", "eur", "gbp"]),
            "method": method,
        }
        if bad:
            req["method"] = dict(method, kind=rng.choice(
                [k for k in ("card", "bank", "wallet") if k != kind]
            ))
    else:
        req = {"input": "msg " * rng.randint(1, 8), "category": rng.choice(["toxicity", "spam"])}
        if bad:
            req["category"] = "other"
    return req


def _mixed_stream(batch: int, rng: random.Random):
    lanes = [ep for ep, weight in MIX for _ in range(weight)]
    endpoints = [lanes[rng.randrange(len(lanes))] for _ in range(batch)]
    docs = [_mk_request(ep, i, rng) for i, ep in enumerate(endpoints)]
    return docs, endpoints


def run(report: Dict[str, object]) -> List[str]:
    lines: List[str] = []
    rng = random.Random(0)

    reg = SchemaRegistry(use_pallas=False)
    t0 = time.perf_counter()
    for name, schema in SCHEMAS.items():
        reg.register(name, schema)
    t_register = time.perf_counter() - t0
    linked = reg.linked_tape()
    assert linked is not None and len(linked.members) == len(SCHEMAS)
    bv_linked = reg.batch_validator()
    # single-schema executors for the dispatch baseline
    bv_single = {
        ep: BatchValidator(reg.get(ep).tape, use_pallas=False)
        for ep in SCHEMAS
    }

    rows = []
    for batch in BATCH_SIZES:
        docs, endpoints = _mixed_stream(batch, rng)
        ids = reg.schema_ids(endpoints)
        assert (ids >= 0).all()

        # -- sequential oracle ------------------------------------------------
        # the registry's codegen validators read the decoded tree as it is
        validators = {ep: reg.get(ep).validator for ep in SCHEMAS}
        seq_results = [validators[ep].is_valid(d) for ep, d in zip(endpoints, docs)]

        def run_seq():
            return [validators[ep].is_valid(d) for ep, d in zip(endpoints, docs)]

        # -- per-schema sub-batch dispatch -----------------------------------
        # Two baselines: *exact* warms a jit for each group's exact batch
        # size -- idealized, since real mixed traffic re-deals group sizes
        # every batch and would retrace constantly; *bucketed* pads each
        # group to a power-of-two batch (what a production dispatcher --
        # and our own registry.admit_mixed -- does to cap compilations).
        groups = {ep: [i for i, e in enumerate(endpoints) if e == ep] for ep in SCHEMAS}
        sub_tables = {
            ep: encode_batch([docs[i] for i in idx], max_nodes=MAX_NODES)
            for ep, idx in groups.items() if idx
        }
        bucket_tables = {}
        for ep, idx in groups.items():
            if not idx:
                continue
            bucket = 1 << (len(idx) - 1).bit_length() if len(idx) > 1 else 1
            bucket_tables[ep] = encode_batch(
                [docs[i] for i in idx] + [None] * (bucket - len(idx)),
                max_nodes=MAX_NODES,
            )
        dispatch_valid = np.zeros(batch, bool)
        dispatch_decided = np.zeros(batch, bool)

        def run_dispatch_exact():
            for ep, table in sub_tables.items():
                v, d = bv_single[ep].validate(table)
                idx = groups[ep]
                dispatch_valid[idx] = v
                dispatch_decided[idx] = d

        def run_dispatch_bucketed():
            for ep, table in bucket_tables.items():
                v, d = bv_single[ep].validate(table)
                idx = groups[ep]
                dispatch_valid[idx] = v[: len(idx)]
                dispatch_decided[idx] = d[: len(idx)]

        # -- linked tape: one launch -----------------------------------------
        table = encode_batch(docs, max_nodes=MAX_NODES)
        t0 = time.perf_counter()
        encode_batch(docs, max_nodes=MAX_NODES)
        t_encode = time.perf_counter() - t0

        def run_linked():
            return bv_linked.validate(table, ids)

        # warm every shape, then interleave best-of-5 so background load
        # hits all paths equally
        run_dispatch_exact()
        linked_valid, linked_decided = run_linked()
        timings = {"seq": [], "exact": [], "bucketed": [], "linked": []}
        contenders = [
            ("seq", run_seq),
            ("exact", run_dispatch_exact),
            ("bucketed", run_dispatch_bucketed),
            ("linked", run_linked),
        ]
        for _ in range(5):
            for name, fn in contenders:
                t0 = time.perf_counter()
                fn()
                timings[name].append(time.perf_counter() - t0)
        t_seq = min(timings["seq"])
        t_dispatch_exact = min(timings["exact"])
        t_dispatch = min(timings["bucketed"])
        t_linked = min(timings["linked"])
        run_dispatch_exact()  # leave exact-dispatch verdicts for the check

        # bit-identity: linked == per-schema dispatch; both == sequential
        # where decided (the acceptance criterion)
        np.testing.assert_array_equal(linked_valid, dispatch_valid)
        np.testing.assert_array_equal(linked_decided, dispatch_decided)
        assert all(
            bool(v) == r for v, r, d in zip(linked_valid, seq_results, linked_decided) if d
        )

        row = {
            "batch": batch,
            "mix": {ep: len(idx) for ep, idx in groups.items()},
            "decided_fraction": float(linked_decided.mean()),
            "sequential_docs_per_s": batch / t_seq,
            "dispatch_docs_per_s": batch / t_dispatch,  # bucketed (realistic)
            "dispatch_exact_docs_per_s": batch / t_dispatch_exact,
            "linked_docs_per_s": batch / t_linked,
            "sequential_us_per_doc": t_seq / batch * 1e6,
            "dispatch_us_per_doc": t_dispatch / batch * 1e6,
            "dispatch_exact_us_per_doc": t_dispatch_exact / batch * 1e6,
            "linked_us_per_doc": t_linked / batch * 1e6,
            "encode_us_per_doc": t_encode / batch * 1e6,
            "linked_speedup_vs_dispatch": t_dispatch / t_linked,
            "linked_speedup_vs_dispatch_exact": t_dispatch_exact / t_linked,
            "linked_speedup_vs_sequential": t_seq / t_linked,
        }
        rows.append(row)
        lines.append(
            f"registry/mixed_validation_b{batch},{row['linked_us_per_doc']:.2f},"
            f"dispatch_us={row['dispatch_us_per_doc']:.2f};"
            f"seq_us={row['sequential_us_per_doc']:.2f};"
            f"linked_x_dispatch={row['linked_speedup_vs_dispatch']:.2f}"
        )

    # -- link groups (DESIGN.md §14): window shrinkage vs the global tape --
    # The union member (charge) inflates the global linked windows to the
    # member maxima (Â 3->6, M-hat 4->8).  The group partition confines
    # that: report each group's local windows and the worst non-union
    # inflation ratio against the union-free reference (all members
    # minus the union endpoint linked together).
    from repro.registry import link_tapes

    union_free = link_tapes(
        tapes=[reg.get(ep).tape for ep in SCHEMAS if ep != "charge"],
        names=[ep for ep in SCHEMAS if ep != "charge"],
    )
    group_rows = {}
    worst_a = worst_m = 0.0
    for label, gs in reg.group_stats().items():
        non_union = "charge" not in gs["members"]
        ratio_a = gs["a_hat"] / union_free.max_rows_per_loc
        ratio_m = gs["m_hat"] / union_free.max_member_props
        if non_union:
            worst_a = max(worst_a, ratio_a)
            worst_m = max(worst_m, ratio_m)
        group_rows[label] = {
            **{k: gs[k] for k in ("members", "a_hat", "m_hat", "k", "horizon")},
            "signature_class": gs["signature_class"],
            "non_union": non_union,
            "a_hat_vs_union_free": round(ratio_a, 3),
            "m_hat_vs_union_free": round(ratio_m, 3),
        }
    # acceptance: non-union traffic within 1.2x of its union-free windows
    assert worst_a <= 1.2 and worst_m <= 1.2, (worst_a, worst_m)

    # differential: group-partitioned admission is bit-identical to the
    # legacy single-tape fast path, verdict for verdict
    reg_flat = SchemaRegistry(use_pallas=False, link_grouping=False)
    for name, schema in SCHEMAS.items():
        reg_flat.register(name, schema)
    diff_docs, diff_eps = _mixed_stream(256, random.Random(0xD1FF))
    grouped_v, _ = reg.admit_mixed_ex(diff_docs, diff_eps, max_nodes=MAX_NODES)
    flat_v, _ = reg_flat.admit_mixed_ex(diff_docs, diff_eps, max_nodes=MAX_NODES)
    assert [(v.outcome, v.valid) for v in grouped_v] == [
        (v.outcome, v.valid) for v in flat_v
    ]
    lines.append(
        f"registry/link_groups,{len(group_rows)},"
        f"worst_non_union_a_hat_ratio={worst_a:.2f};"
        f"worst_non_union_m_hat_ratio={worst_m:.2f}"
    )

    payload = {
        "schemas": list(SCHEMAS),
        "mix_weights": dict(MIX),
        "register_seconds": t_register,
        "linked_tape": {
            "members": list(linked.members),
            "locations": linked.n_locations,
            "prop_rows": linked.n_props,
            "assertions": linked.n_assertions,
            "a_hat": linked.max_rows_per_loc,
            "k": linked.max_hash_run,
            "max_loc_depth": linked.max_loc_depth,
            "member_horizons": linked.member_horizons.tolist(),
        },
        "link_groups": {
            "groups": group_rows,
            "union_free_reference": {
                "a_hat": int(union_free.max_rows_per_loc),
                "m_hat": int(union_free.max_member_props),
            },
            "worst_non_union_a_hat_ratio": round(worst_a, 3),
            "worst_non_union_m_hat_ratio": round(worst_m, 3),
            "grouped_vs_flat_bit_identical": True,
        },
        "throughput": rows,
    }
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "BENCH_registry.json").write_text(json.dumps(payload, indent=2))
    lines.append("registry/bench_json,0,results/BENCH_registry.json")
    report["registry"] = payload
    return lines

"""Observability overhead + batched first-failure attribution (DESIGN.md §12).

Three questions, machine-checked across PRs via
``results/BENCH_observability.json``:

1. **Disarmed instrumentation**: the trace seams and registry-backed
   counters sit on the serving path permanently.  With no tracer armed
   they must cost one module-global ``None`` check per seam -- the
   isolated linked launch at B=4096 must stay within noise (<5%) of the
   raw launch, i.e. no regression vs the PR 6 clean path.
2. **Armed tracer**: what arming actually costs (two monotonic-clock
   reads + one ring append per span, at batch granularity).
3. **Attribution**: what ``explain=True`` adds to the hybrid admission
   path (one extra detail-capturing launch over the already-encoded
   table), and whether the batched attribution agrees with the
   sequential oracle on the seeded mixed stream.
4. **Cost attribution** (DESIGN.md §12): with a :class:`Tracer` armed
   over one end-to-end ``admit_mixed_ex`` at B=4096, the exclusive
   span times must explain >=90% of the measured wall window, the armed
   overhead is recorded, and the disarmed admit path is compared against
   the committed HEAD baseline (the <2% disarmed-seam bar).

Same schemas, mix, and encode budget as ``benchmarks/registry.py``.
Also renders the shared MetricRegistry to
``results/metrics_snapshot.prom`` after a small end-to-end serve burst,
so CI archives one Prometheus export covering the whole surface.
"""

from __future__ import annotations

import json
import random
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from repro.core.outcomes import ValidationOutcome
from repro.data.doc_table import encode_batch
from repro.obs import Tracer
from repro.registry import SchemaRegistry
from repro.registry.presets import GATEWAY_SCHEMAS as SCHEMAS

from .registry import MAX_NODES, _mixed_stream

BATCH = 4096
DIFF_SAMPLE = 512  # differential-agreement sample of the mixed stream
RESULTS = Path(__file__).resolve().parents[1] / "results"


def _best_of(fn, n=5) -> float:
    times = []
    for _ in range(n):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


def _armed_admit(prof: Tracer, admit) -> None:
    """One admit pass with the (cleared) tracer armed -- measures what
    arming actually costs on top of the disarmed seams."""
    prof.clear()
    with prof:
        admit(False)


def _baseline_admit_us() -> float:
    """``admit_us_per_doc`` from the committed HEAD BENCH_observability
    baseline, or 0.0 when unavailable (first appearance / no git)."""
    import subprocess

    try:
        blob = subprocess.run(
            ["git", "show", "HEAD:results/BENCH_observability.json"],
            capture_output=True,
            text=True,
            check=True,
            cwd=Path(__file__).resolve().parents[1],
        ).stdout
        return float(json.loads(blob)["explain"]["admit_us_per_doc"])
    except Exception:
        return 0.0


def _serve_burst(reg: SchemaRegistry, docs, endpoints, n=64) -> None:
    """Push a small end-to-end burst through ServeEngine so the serve_*
    metric families (latency histograms, outcome counters) show up in
    the exported snapshot alongside the executor/registry families."""
    import jax

    from repro.configs import get_config
    from repro.models import Model
    from repro.serve.engine import ServeConfig, ServeEngine

    cfg = get_config("granite-3-8b").reduced()
    params = Model(cfg).init(jax.random.PRNGKey(0))
    engine = ServeEngine(
        cfg,
        params,
        ServeConfig(batch_slots=2, max_len=64, default_max_tokens=4),
        registry=reg,
    )
    requests = [
        (e, json.dumps(d, sort_keys=True))
        for e, d in zip(endpoints[:n], docs[:n])
    ]
    engine.submit_batch(requests, explain=True)
    engine.submit(requests[0][1], requests[0][0])


def run(report: Dict[str, object]) -> List[str]:
    lines: List[str] = []
    rng = random.Random(0)

    reg = SchemaRegistry(use_pallas=False)
    for name, schema in SCHEMAS.items():
        reg.register(name, schema)
    bv = reg.batch_validator()
    docs, endpoints = _mixed_stream(BATCH, rng)
    ids = reg.schema_ids(endpoints).astype(np.int32)
    table = encode_batch(docs, max_nodes=MAX_NODES)
    keys = list(range(BATCH))

    # -- 1. disarmed seams: raw launch vs the instrumented isolated path -----
    bv.validate_ex(table, ids)  # warm the jit
    bv.validate_isolated(table, ids, keys=keys)
    t_raw = _best_of(lambda: bv.validate_ex(table, ids))
    t_disarmed = _best_of(lambda: bv.validate_isolated(table, ids, keys=keys))
    disarmed_pct = 100.0 * (t_disarmed - t_raw) / t_raw

    # -- 2. armed tracer: same launch with the ring buffer recording ---------
    with Tracer(capacity=4096) as tr:
        t_armed = _best_of(lambda: bv.validate_isolated(table, ids, keys=keys))
        spans_recorded = tr.recorded
    armed_pct = 100.0 * (t_armed - t_disarmed) / t_disarmed

    raw_us = t_raw / BATCH * 1e6
    disarmed_us = t_disarmed / BATCH * 1e6
    armed_us = t_armed / BATCH * 1e6
    lines.append(f"launch_raw,{raw_us:.3f},B={BATCH}")
    lines.append(
        f"launch_disarmed,{disarmed_us:.3f},overhead={disarmed_pct:.2f}%"
    )
    lines.append(
        f"launch_traced,{armed_us:.3f},overhead={armed_pct:.2f}%"
        f" spans={spans_recorded}"
    )

    # -- 3. hybrid admission: explain=False vs explain=True ------------------
    def admit(explain: bool):
        return reg.admit_mixed_ex(
            docs, endpoints, max_nodes=MAX_NODES, explain=explain
        )

    verdicts, _ = admit(False)  # warm (encode cache is per-call; jit persists)
    admit(True)
    n_invalid = sum(
        1 for v in verdicts if v.outcome is ValidationOutcome.INVALID
    )
    t_admit = _best_of(lambda: admit(False), n=3)
    t_explain = _best_of(lambda: admit(True), n=3)
    explain_pct = 100.0 * (t_explain - t_admit) / t_admit
    admit_us = t_admit / BATCH * 1e6
    explain_us = t_explain / BATCH * 1e6
    lines.append(f"admit_mixed,{admit_us:.3f},B={BATCH}")
    lines.append(
        f"admit_mixed_explain,{explain_us:.3f},overhead={explain_pct:.2f}%"
        f" invalid={n_invalid}"
    )

    # -- 4. cost attribution: armed tracer over one admit at B=4096 ----------
    with Tracer() as prof:
        t0 = time.perf_counter_ns()
        admit(False)
        window_ns = time.perf_counter_ns() - t0
    attribution = prof.report(window_ns)
    t_admit_armed = _best_of(lambda: _armed_admit(prof, admit), n=3)
    profiler_armed_pct = 100.0 * (t_admit_armed - t_admit) / t_admit
    armed_admit_us = t_admit_armed / BATCH * 1e6
    lines.append(
        f"admit_attributed,{armed_admit_us:.3f},"
        f"coverage={attribution['coverage'] * 100:.1f}%"
        f" armed_overhead={profiler_armed_pct:.2f}%"
    )
    # disarmed seam bar (<2%): the same admit path against the committed
    # HEAD baseline -- cross-PR, so best-effort (first run has none)
    base_admit_us = _baseline_admit_us()
    disarmed_seam_pct = (
        100.0 * (admit_us - base_admit_us) / base_admit_us
        if base_admit_us
        else None
    )
    if disarmed_seam_pct is not None:
        lines.append(
            f"admit_disarmed_vs_baseline,{admit_us:.3f},"
            f"baseline_us={base_admit_us:.3f};delta={disarmed_seam_pct:+.2f}%"
        )

    # -- differential agreement vs the sequential oracle ---------------------
    sample_docs = docs[:DIFF_SAMPLE]
    sample_eps = endpoints[:DIFF_SAMPLE]
    verdicts, _ = reg.admit_mixed_ex(
        sample_docs, sample_eps, max_nodes=MAX_NODES, explain=True
    )
    agree = checked = 0
    for doc, ep, v in zip(sample_docs, sample_eps, verdicts):
        if v.outcome is not ValidationOutcome.INVALID or v.site is None:
            continue
        checked += 1
        ok, trace = reg.get(ep).validator.explain(doc)
        assert not ok
        if v.site.schema_path in {p for p, _ in trace}:
            agree += 1
    agreement = agree / checked if checked else 1.0
    lines.append(
        f"explain_agreement,{agreement * 100:.1f},"
        f"{agree}/{checked} invalid docs vs sequential"
    )

    payload = {
        "batch": BATCH,
        "max_nodes": MAX_NODES,
        "launch": {
            "raw_us_per_doc": raw_us,
            "disarmed_us_per_doc": disarmed_us,
            "traced_us_per_doc": armed_us,
            "disarmed_overhead_pct": disarmed_pct,
            "traced_overhead_pct": armed_pct,
            "spans_recorded": spans_recorded,
        },
        "explain": {
            "admit_us_per_doc": admit_us,
            "explain_us_per_doc": explain_us,
            "explain_overhead_pct": explain_pct,
            "n_invalid": n_invalid,
            "differential_checked": checked,
            "differential_agree": agree,
            "differential_agreement": agreement,
        },
        "profile": {
            "coverage": attribution["coverage"],
            "window_us": attribution["window_ns"] / 1e3,
            "attributed_us": attribution["attributed_ns"] / 1e3,
            "phases": attribution["phases"],
            "armed_admit_us_per_doc": armed_admit_us,
            "profiler_armed_overhead_pct": profiler_armed_pct,
            "baseline_admit_us": base_admit_us or None,
            "disarmed_seam_overhead_pct": disarmed_seam_pct,
        },
    }
    RESULTS.mkdir(exist_ok=True)
    out = RESULTS / "BENCH_observability.json"
    out.write_text(json.dumps(payload, indent=2) + "\n")
    report["observability"] = payload
    lines.append(f"# wrote {out}")

    # -- Prometheus snapshot artifact ----------------------------------------
    try:
        _serve_burst(reg, docs, endpoints)
    except Exception as exc:  # noqa: BLE001 -- snapshot still worth writing
        lines.append(f"# serve burst skipped: {type(exc).__name__}:{exc}")
    prom = RESULTS / "metrics_snapshot.prom"
    prom.write_text(reg.metrics.render_prometheus())
    lines.append(f"# wrote {prom}")
    return lines

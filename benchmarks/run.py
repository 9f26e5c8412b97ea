"""Benchmark harness entry point: ``python -m benchmarks.run``.

One module per paper table/figure:
  validation   -- Table 5 / Figure 6 (per-dataset runtimes + speedups)
  compile_time -- Figure 5 (compile time vs schema size)
  ablations    -- Figure 7 (per-optimization contribution)
  batched      -- beyond-paper TPU-form executor + coverage
  registry     -- beyond-paper multi-tenant mixed traffic (linked tape)
  recursive    -- beyond-paper recursive-$ref unrolling (frontier routing)
  logical      -- beyond-paper logical-applicator circuits (tagged unions)
  robustness   -- fault-containment overhead + poisoned-batch throughput
  observability -- trace/metric seam overhead + explain attribution cost
  serve_load   -- open-loop Poisson arrival-rate sweep (latency percentiles)
  roofline     -- §Roofline terms from the dry-run artifacts

Prints ``name,us_per_call,derived`` CSV lines and writes the full report
to results/bench_report.json.  The batched module additionally emits
results/BENCH_batched.json (CSR executor docs/s per batch size + tape
coverage) for machine-readable perf tracking across PRs.
Every selected module runs; the harness exits non-zero if any raised.
"""

from __future__ import annotations

import json
import sys
import time
import traceback
from pathlib import Path
from typing import Dict

from repro.launch.compile_cache import enable_compile_cache

RESULTS = Path(__file__).resolve().parents[1] / "results"


def main() -> None:
    from . import (
        ablations,
        batched,
        compile_time,
        logical,
        observability,
        recursive,
        registry,
        robustness,
        roofline,
        serve_load,
        validation,
    )

    modules = [
        ("validation", validation),
        ("compile_time", compile_time),
        ("ablations", ablations),
        ("batched", batched),
        ("registry", registry),
        ("recursive", recursive),
        ("logical", logical),
        ("robustness", robustness),
        ("observability", observability),
        ("serve_load", serve_load),
        ("roofline", roofline),
    ]
    enable_compile_cache()
    only = sys.argv[1] if len(sys.argv) > 1 else None
    report: Dict[str, object] = {}
    failed = []
    print("name,us_per_call,derived")
    for name, mod in modules:
        if only and name != only:
            continue
        t0 = time.time()
        try:
            for line in mod.run(report):
                print(line)
        except Exception as exc:  # noqa: BLE001 -- run the rest, then fail
            traceback.print_exc()
            print(f"{name}/ERROR,0,{type(exc).__name__}:{exc}")
            failed.append(name)
        print(f"{name}/_elapsed,{(time.time()-t0)*1e6:.0f},seconds={time.time()-t0:.1f}")
    RESULTS.mkdir(parents=True, exist_ok=True)
    (RESULTS / "bench_report.json").write_text(json.dumps(report, indent=2, default=str))
    if failed:
        sys.exit(f"benchmark modules raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""What arming the tracer adds to the encode the benchmark reports.

    PYTHONPATH=src python scripts/encode_overhead.py schemastore.taped [--reps 20] [--seed 1]

Submits pool 0 of a benchmark cell's traffic again and again through
``ServeEngine.submit_batch``, alternating two kinds of run: tracer
disarmed, with ``encode_batch`` timed by a wrapper, and tracer armed as a
``--trace 1`` run arms it (``obs/profile.Profiler``), reading the
``admit.encode`` span that ``encode_us_per_doc.batch`` reads.  Prints
microseconds per encoded row for both, minimum and median, and their
ratio.  The numbers are the host's: on a machine without a TPU, CPU
numbers.
"""

from __future__ import annotations

import argparse
import statistics
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
ENCODED = ("batch_validated", "undecided", "oversize", "unroll_overflow")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("workload")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import spec, traffic as traffic_lib
    from bench.lib.harness import build_engine
    from repro.data import doc_table
    from repro.obs.profile import Profiler, set_profiler

    bench = spec.load_benchmark()
    cell = spec.cell(bench, args.workload)
    config = spec.config(bench, cell["config"])
    source = spec.documents(config["documents"]).build(config)
    engine = build_engine(config, source.schemas)
    sub = traffic_lib.pool(source, spec.traffic(cell["traffic"]), seed=args.seed)[0]
    pairs = list(zip(sub.endpoints, sub.texts))
    engine.submit_batch(pairs)  # compiles the launch shapes
    before = sum(getattr(engine.stats, k) for k in ENCODED)
    engine.submit_batch(pairs)
    rows = sum(getattr(engine.stats, k) for k in ENCODED) - before

    encode = doc_table.encode_batch
    disarmed, armed = [], []

    def timed(*a, **kw):
        t0 = time.perf_counter_ns()
        try:
            return encode(*a, **kw)
        finally:
            disarmed[-1] += time.perf_counter_ns() - t0

    def run_disarmed():
        disarmed.append(0)
        doc_table.encode_batch = timed  # admit_mixed_ex looks it up per call
        try:
            engine.submit_batch(pairs)
        finally:
            doc_table.encode_batch = encode

    def run_armed():
        prof = Profiler()
        set_profiler(prof)
        try:
            engine.submit_batch(pairs)
        finally:
            set_profiler(None)
        armed.append(prof.stats()["admit.encode"].total_ns)

    for i in range(args.reps):
        for run in (run_disarmed, run_armed) if i % 2 == 0 else (run_armed, run_disarmed):
            run()
    us = lambda ns: ns / 1e3 / rows
    print(
        f"{args.workload} rows {rows} encode_us_per_row "
        f"disarmed min {us(min(disarmed))!r} median {us(statistics.median(disarmed))!r} "
        f"armed min {us(min(armed))!r} median {us(statistics.median(armed))!r} "
        f"ratio_of_medians {statistics.median(armed) / statistics.median(disarmed)!r}"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The control of ``correct``: the reference in bfloat16, put in the
program's place, has to come out not correct.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 [--seconds <s>]

For each seed it makes the requests that a run of the cell compares (an
open loop's ``rate_per_s * seconds`` requests, a closed loop's pool),
decides each with the reference holding the documents' numbers in
bfloat16, and compares those verdicts with the exact reference's as a
run compares the program's.  It prints one line per seed with
``mismatches`` beside its limit; the last line of standard output is a
JSON object with every seed's reading.  It needs no accelerator.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path
from typing import Dict, List

ROOT = Path(__file__).resolve().parents[1]
CLASS_TO_OUTCOME = {"valid": "admitted", "invalid": "invalid", "guard": "rejected_guard"}


def control(workload: str, seed: int, seconds: float) -> Dict[str, int]:
    from bench.lib import check, spec
    from bench.lib import traffic as traffic_lib

    bench = spec.load_benchmark()
    cell = spec.cell(bench, workload)
    config = spec.config(bench, cell["config"])
    tr = spec.traffic(cell["traffic"])
    source = spec.documents(config["documents"]).build(config)
    if tr["loop"] == "open":
        subs = [traffic_lib.generate(source, tr, n=traffic_lib.open_loop_count(tr, seconds), seed=seed, seconds=seconds)]
    else:
        subs = traffic_lib.pool(source, tr, seed=seed)
    exact = check.Reference(source.schemas, config["guard"])
    low = check.Reference(source.schemas, config["guard"], numbers="bfloat16")
    want: List[str] = []
    got: List[str] = []
    eps: List[str] = []
    for sub in subs:
        for ep, text in zip(sub.endpoints, sub.texts):
            want.append(exact.expect(ep, text))
            got.append(CLASS_TO_OUTCOME[low.expect(ep, text)])
            eps.append(ep)
    cmp = check.compare(want, got, eps)
    return {"seed": seed, "compared": cmp.compared, "mismatches": cmp.mismatches, "limit": 0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None, help="open-loop window (default: run_seconds)")
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import spec

    seconds = args.seconds if args.seconds is not None else float(spec.load_benchmark()["run_seconds"])
    readings = []
    for seed in args.seeds:
        r = control(args.workload, seed, seconds)
        print(f"control {args.workload} seed {seed} compared {r['compared']} mismatches {r['mismatches']} limit 0")
        readings.append(r)
    print(json.dumps({"workload": args.workload, "readings": readings}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

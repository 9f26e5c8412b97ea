#!/usr/bin/env python3
"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are named in
``BENCHMARK.json`` at the root of the checkout.  One process: it finds
the TPU (and exits non-zero, printing no result, where there is none or
too few chips), builds the cell's system and traffic from ``--seed``,
warms up every shape, measures for ``--seconds`` (``--trace 1``: a
shorter window under the profiler, for the per-layer metrics), checks
every verdict against the plain reference, and prints one JSON object
as the last line of standard output.  The numbers that decide
``correct`` are printed beside their limits as the last lines of
standard error and under ``checks``, the result's last key.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
# JAX's persistent compilation cache lives inside the checkout, at a
# fixed path; the program's enable_compile_cache() takes it from here
CACHE_DIR = ROOT / ".jax_cache"


def find_devices(chips: int) -> dict:
    """The TPU devices JAX sees; exits non-zero without a result when there
    is no TPU or fewer than ``chips`` of them."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"bench: no TPU found (JAX platform {platform!r}); nothing is measured")
    if len(devices) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind, "count": len(devices)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench.lib import spec

    cell = spec.cell(spec.load_benchmark(), args.workload)
    device = find_devices(int(cell["chips"]))
    print(f"device {device['kind']} x{device['count']}", flush=True)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile_cache {enable_compile_cache()}", flush=True)
    # cache every program, however quickly it compiles, so that a run
    # after the first finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from bench.lib.harness import run_cell

    line, comparison = run_cell(
        args.workload,
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        t_start=T_START,
        device=device,
    )
    line["correct"] = comparison.correct
    line["checks"] = comparison.limits()
    for example in comparison.examples:
        print(f"mismatch {example}", file=sys.stderr)
    print(json.dumps(line), flush=True)
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

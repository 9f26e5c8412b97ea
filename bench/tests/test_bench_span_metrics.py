"""The per-layer metrics that read the program's spans: a traced run on
the CPU reports them, and a program without the spans (as an older one
is) leaves them out instead of failing."""

import json
import time

from bench.lib import spec
from bench.lib.harness import Record, run_cell
from bench.tests.tree import CPU, SMALL_CLOSED, add_cell, add_config, copy_tree, small_config

SEED = 2**31 + 4242
NEW = ("parse_us_per_doc.batch", "fallback_us_per_row.batch", "launch_host_ms.batch")


def _read(name, rec):
    return spec.reader(name).read(rec)


def test_readers_find_nothing_without_their_spans():
    rec = Record(workload="w", loop="closed", setup_s=0.0, attempted=100)
    rec.counts = {"fallback_validated": 40}
    for name in NEW:
        assert _read(name, rec) is None
    # the spans of an older program: a parse root, launches split by compile
    rec.phases = {"serve.parse": {"calls": 2, "total_ns": 500_000, "self_ns": 500_000},
                  "executor.execute": {"calls": 3, "total_ns": 9_000_000, "self_ns": 9_000_000}}
    assert _read("parse_us_per_doc.batch", rec) == 5.0
    assert _read("fallback_us_per_row.batch", rec) is None
    assert _read("launch_host_ms.batch", rec) is None


def test_readers_divide_span_time_by_their_own_counts():
    rec = Record(workload="w", loop="closed", setup_s=0.0, attempted=1000)
    rec.counts = {"fallback_validated": 250}
    rec.phases = {
        "serve.parse": {"calls": 1, "total_ns": 2_000_000, "self_ns": 2_000_000},
        "admit.fallback": {"calls": 4, "total_ns": 5_000_000, "self_ns": 5_000_000},
        "executor.launch": {"calls": 8, "total_ns": 40_000_000, "self_ns": 40_000_000},
    }
    assert _read("parse_us_per_doc.batch", rec) == 2.0
    assert _read("fallback_us_per_row.batch", rec) == 20.0
    assert _read("launch_host_ms.batch", rec) == 5.0
    rec.counts = {"fallback_validated": 0}  # a cell with no fallback rows
    assert _read("fallback_us_per_row.batch", rec) is None


def test_traced_run_reports_the_span_metrics(tmp_path):
    root = copy_tree(tmp_path)
    add_config(root, small_config())
    add_cell(root, "small.closed", "small", "small_closed", SMALL_CLOSED)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in NEW and "small.closed" not in m["workloads"]:
            m["workloads"].append("small.closed")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    line, cmp = run_cell(
        "small.closed", seed=SEED, seconds=0.4, trace=True, t_start=time.perf_counter(), device=CPU, log=lambda s: None, root=root
    )
    assert cmp.correct
    metrics = line["metrics"]
    assert set(NEW) <= set(metrics)
    for name in NEW:
        assert metrics[name]["value"] > 0
    assert metrics["launch_host_ms.batch"]["unit"] == "ms"

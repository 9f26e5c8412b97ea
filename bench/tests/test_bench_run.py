"""A run of the harness without a TPU: the entry point refuses, and the
rest of a run, driven on the CPU, finds its cell's pieces by name and
decides ``correct`` against the reference."""

import hashlib
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from bench.lib import spec
from bench.lib.harness import run_cell
from bench.tests.tree import CPU, SMALL_CLOSED, add_cell, add_config, copy_tree, small_config, weights

SEED = 2**31 + 12345


def test_no_tpu_exits_nonzero_with_no_result_line():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "schemastore.taped", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not any(line.lstrip().startswith("{") for line in proc.stdout.splitlines())


def test_benchmark_names_only_files_that_exist():
    bench = spec.load_benchmark()
    for c in bench["workloads"]:
        config = spec.config(bench, c["config"])
        spec.traffic(c["traffic"])
        spec.documents(config["documents"])
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            assert hasattr(spec.reader(m["name"]), "read")


def _digests(root: Path):
    return {
        p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
        for p in root.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts
    }


def test_a_new_cell_runs_from_new_files_alone(tmp_path):
    """A new configuration, traffic mix and metric, each in a file of its
    own plus its entry in BENCHMARK.json, run with no edit to any file
    that was there."""
    root = copy_tree(tmp_path)
    before = _digests(root)
    small = small_config()
    two = dict(small, name="two", datasets=[r for r in small["datasets"] if r[0] in ("helm-chart-lock", "babelrc")])
    add_config(root, two)
    (root / "bench" / "metrics" / "verdicts_total.py").write_text(
        "def read(rec):\n    return float(rec.answered)\n"
    )
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["end_to_end"].append(
        {"name": "verdicts_total", "unit": "docs", "better": "higher", "bound": 0.25, "source": "host_clock", "workloads": ["two.closed"]}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_cell(root, "two.closed", "two", "two_closed", dict(SMALL_CLOSED, weights=weights(two)))
    after = _digests(root)
    changed = [p for p in before if before[p] != after[p]]
    assert changed == [Path("BENCHMARK.json")]

    line, cmp = run_cell(
        "two.closed", seed=SEED, seconds=0.2, trace=False, t_start=time.perf_counter(), device=CPU, log=lambda s: None, root=root
    )
    assert cmp.correct and cmp.compared > 0
    assert set(line["metrics"]) == {"docs_per_s", "setup_s", "verdicts_total"}
    assert line["metrics"]["verdicts_total"]["value"] == cmp.compared


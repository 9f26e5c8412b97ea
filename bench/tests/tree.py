"""A copy of the benchmark in a scratch directory, with small cells that
a CPU test run can hold."""

from __future__ import annotations

import json
import shutil
from pathlib import Path
from typing import Any, Dict, List

from bench.lib import spec

# three datasets that build a tape (two link groups) and two that take
# the sequential fallback
SMALL_DATASETS = ("helm-chart-lock", "jasmine", "tmuxinator", "babelrc", "lerna")
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def small_config() -> Dict[str, Any]:
    """The ``schemastore`` configuration cut to :data:`SMALL_DATASETS`."""
    config = spec.config(spec.load_benchmark(), "schemastore")
    return dict(config, name="small", datasets=[r for r in config["datasets"] if r[0] in SMALL_DATASETS])


def weights(config: Dict[str, Any]) -> List[List[Any]]:
    """Table 3's document counts as the weights of the configuration's datasets."""
    return [[row[0], row[1]] for row in config["datasets"]]


MIX = weights(small_config())
SMALL_CLOSED = {
    "loop": "closed",
    "batch": 96,
    "pool": 2,
    "weights": MIX,
    "broken_share": 0.1,
    "boundary_share": 0.1,
    "malformed_share": 0.02,
}
SMALL_OPEN = {
    "loop": "open",
    "arrivals": {"kind": "poisson", "rate_per_s": 300},
    "weights": MIX,
    "broken_share": 0.1,
    "boundary_share": 0.1,
    "malformed_share": 0.02,
    "scheduler": {"max_delay_s": 0.002, "max_batch": 4},
}


def copy_tree(dest: Path) -> Path:
    """``BENCHMARK.json`` and ``bench/`` (without its tests) under ``dest``."""
    shutil.copy(spec.ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(spec.BENCH, dest / "bench", ignore=shutil.ignore_patterns("tests", "__pycache__"))
    return dest


def add_config(root: Path, config: Dict[str, Any]) -> None:
    """A new configuration file and its entry."""
    name = config["name"]
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(
        {"name": name, "source": "test", "file": f"bench/configs/{name}.json", "reduced": [], "why": "test"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))


def add_cell(root: Path, name: str, config: str, traffic: str, traffic_body: Dict[str, Any]) -> None:
    """A new cell and its traffic file; a closed-loop cell joins every
    metric of ``schemastore.taped`` (no open-loop cell has metrics to join)."""
    (root / "bench" / "traffic" / f"{traffic}.json").write_text(json.dumps(traffic_body))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({"name": name, "config": config, "traffic": traffic, "chips": 1, "why": "test"})
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if traffic_body["loop"] == "closed" and "schemastore.taped" in m.get("workloads", []):
                m["workloads"].append(name)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))

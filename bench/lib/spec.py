"""Find a cell's pieces by name.

Everything that belongs to one configuration, one traffic mix or one
metric sits in a file of its own, found by the name ``BENCHMARK.json``
gives it:

- ``configs[].file``: the configuration (a JSON object);
- ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
- ``bench/traffic/<documents>.py``: the documents source that a
  configuration's ``"documents"`` key names;
- ``bench/metrics/<metric>.py``: the reader of one metric, a module with
  ``read(record) -> float | None``.

A cell, a configuration, a traffic mix or a metric is added by adding
its files and its entry in ``BENCHMARK.json``; no file here changes.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class SpecError(ValueError):
    pass


def load_benchmark(path: Optional[Path] = None) -> Dict[str, Any]:
    return json.loads((path or ROOT / "BENCHMARK.json").read_text())


def _named(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise SpecError(f"no {what} named {name!r} in BENCHMARK.json")


def cell(spec: Dict[str, Any], name: str) -> Dict[str, Any]:
    return _named(spec["workloads"], name, "workload")


def config(spec: Dict[str, Any], name: str, root: Path = ROOT) -> Dict[str, Any]:
    entry = _named(spec["configs"], name, "configuration")
    return json.loads((root / entry["file"]).read_text())


def traffic(name: str, bench: Path = BENCH) -> Dict[str, Any]:
    return json.loads((bench / "traffic" / f"{name}.json").read_text())


def _module(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise SpecError(f"{path} does not exist")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def documents(name: str, bench: Path = BENCH) -> ModuleType:
    """The documents source ``bench/traffic/<name>.py``."""
    return _module(bench / "traffic" / f"{name}.py", f"bench_documents_{name}")


def reader(metric: str, bench: Path = BENCH) -> ModuleType:
    """The reader ``bench/metrics/<metric>.py`` of one metric."""
    return _module(bench / "metrics" / f"{metric}.py", "bench_metric_" + metric.replace(".", "_"))


def metrics_of(spec: Dict[str, Any], workload: str, kind: str) -> List[Dict[str, Any]]:
    """The ``end_to_end`` or ``per_layer`` metrics that ``workload`` reports:
    those that list it under ``workloads``, and those that list no cells."""
    return [m for m in spec[kind] if workload in m.get("workloads", [workload])]

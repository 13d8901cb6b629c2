"""What a cell is made of, found by name: ``BENCHMARK.json`` names the
cell's configuration, traffic mix and metrics; each lives in a file of its
own under this directory.

* ``configs/<config>.json``: the deployment (graph family, scale, spec);
* ``traffic/<traffic>.json``: the mix's parameters, read by the loop its
  ``kind`` names, ``kinds/<kind>.py``;
* ``layer_metrics/<metric>.py``: one reader per per-layer metric, a
  ``read(record)`` that returns a number or ``None``.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK_FILE = ROOT / "BENCHMARK.json"


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def find_cell(name: str, overrides: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json``, with its configuration
    (updated by ``overrides``, which tests use to shrink it) and traffic
    read from their files."""
    bench = load_json(BENCHMARK_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    cfg = next(c for c in bench["configs"] if c["name"] == w["config"])
    config = load_json(ROOT / cfg["file"])
    config.update(overrides or {})
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def loop_class(kind: str) -> type:
    """The ``Loop`` of ``kinds/<kind>.py``."""
    path = HERE / "kinds" / f"{kind}.py"
    if not kind.isidentifier() or not path.is_file():
        raise KeyError(f"no traffic loop {kind!r} under bench/kinds/")
    return importlib.import_module(f"bench.kinds.{kind}").Loop


def reader(metric: str) -> Callable:
    """The ``read`` function of ``layer_metrics/<metric>.py``."""
    path = HERE / "layer_metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_layer_metric_" + metric.replace(".", "_").replace("-", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def metric_values(metrics: List[dict], values: Dict[str, float]) -> dict:
    """The result line's ``metrics``: each named metric that has a value,
    with its unit."""
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics if values.get(m["name"]) is not None}

"""The benchmark's cells, loaded by name from ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file of its own, found by the name ``BENCHMARK.json`` gives it:

- a configuration: the JSON file its entry names (under ``configs/``), and
  the plain reference module that file names (``references/<name>.py``);
- a traffic mix: ``traffic/<name>.json``, parameters for the one driver of
  its ``kind`` in ``drive.py``;
- a per-layer metric: ``metrics/<name>.py``, whose ``read(run)`` returns
  the metric's value, or None where the run has nothing to read.

A new cell is new files plus entries in ``BENCHMARK.json``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
from pathlib import Path
from typing import Any, Dict, List

ROOT = Path(__file__).resolve().parents[1]


def load_module(path: Path):
    """Import the Python file at ``path`` under a name made from its path."""
    name = "chipbench_file_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise ImportError(f"cannot load {path}")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _named(entries: List[Dict[str, Any]], name: str, what: str) -> Dict[str, Any]:
    for entry in entries:
        if entry["name"] == name:
            return entry
    raise KeyError(f"no {what} named {name!r} in BENCHMARK.json; known: "
                   f"{[e['name'] for e in entries]}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: Dict[str, Any]
    traffic_name: str
    traffic: Dict[str, Any]
    reference: Any                   # the configuration's reference module
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    root: Path

    def reader(self, metric: Dict[str, Any]):
        """The ``read`` function of a per-layer metric's file."""
        return load_module(self.root / "chipbench" / "metrics"
                           / f"{metric['name']}.py").read


def _benchmark(root: Path) -> Dict[str, Any]:
    return json.loads((root / "BENCHMARK.json").read_text())


def load_config(name: str, root: Path = ROOT):
    """A configuration's file, as a dict, and its reference module."""
    entry = _named(_benchmark(root)["configs"], name, "configuration")
    config = json.loads((root / entry["file"]).read_text())
    reference = load_module(root / "chipbench" / "references"
                            / f"{config['reference']}.py")
    return config, reference


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = _benchmark(root)
    workload = _named(bench["workloads"], name, "workload")
    config, reference = load_config(workload["config"], root)
    traffic = json.loads((root / "chipbench" / "traffic"
                          / f"{workload['traffic']}.json").read_text())
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    reported = {m["name"] for m in end_to_end}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in reported)]
    return Cell(name=name, chips=int(workload["chips"]),
                config_name=workload["config"], config=config,
                traffic_name=workload["traffic"], traffic=traffic,
                reference=reference, end_to_end=end_to_end,
                per_layer=per_layer, root=root)

"""Resolve a cell of ``BENCHMARK.json`` to the files that define it.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in a file of its own, found by name:

- ``benchmarks/configs/<config>.json`` (the file ``BENCHMARK.json`` names),
- ``benchmarks/traffic/<traffic>.json``: the mix's parameters; its
  ``call`` key names the call kind, ``benchmarks/calls/<call>.py``,
- ``benchmarks/metrics/<metric>.py``: one reader per per-layer metric.

So a later change adds a cell, a mix or a metric by adding files and
entries, never by editing a file that is already here.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class SpecError(ValueError):
    """The cell, or a file it names, is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    end_to_end: List[Dict]
    per_layer: List[Dict]
    root: str = ROOT


def _load_json(path: str) -> Dict:
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"cannot read {path}: {e}") from e


def _applies(metric: Dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    """The cell named ``workload``, with its configuration and mix
    loaded and the metrics it reports."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench.get("workloads", [])}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r} in BENCHMARK.json "
                        f"(have {sorted(cells)})")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _load_json(os.path.join(root, configs[w["config"]]["file"]))
    mix = _load_json(os.path.join(root, "benchmarks", "traffic",
                                  w["traffic"] + ".json"))
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                mix=mix,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, workload)], root=root)


def load_module(kind: str, name: str, root: str = ROOT):
    """``benchmarks/<kind>/<name>.py`` as a module (names may hold
    dots, so the file is loaded by path, not imported by name)."""
    path = os.path.join(root, "benchmarks", kind, name + ".py")
    if not os.path.isfile(path):
        raise SpecError(f"no {kind} module {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{kind}_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod

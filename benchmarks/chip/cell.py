"""Finds a cell's pieces by name: its entry in ``BENCHMARK.json``, the
configuration file the entry names, the model layout
``layouts/<layout>.py`` and the reference ``references/<reference>.py``
the file names, the traffic file ``traffic/<traffic>.json`` and each
metric's reader ``metrics/<metric>.py``.

A new cell, traffic mix or metric is new files plus new entries; so is
a configuration, of an architecture the benchmark already states or of
a new one: a layout file, a reference file, a configuration file naming
both, and entries.  Nothing here changes."""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with its files loaded."""

    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    def metrics(self, trace: bool) -> List[dict]:
        """The metrics a run reports: per-layer ones with ``--trace 1``,
        end-to-end ones otherwise; each only where its ``workloads``
        (if any) lists this cell."""
        pool = self.per_layer if trace else self.end_to_end
        return [m for m in pool
                if "workloads" not in m or self.name in m["workloads"]]


def load_cell(name: str, bench: Optional[dict] = None,
              root: str = ROOT) -> Cell:
    bench = load_benchmark(root) if bench is None else bench
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; known: {sorted(work)}")
    w = work[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = load_json(os.path.join(HERE, "traffic", f"{w['traffic']}.json"))
    return Cell(name=name, chips=int(w["chips"]), config=cfg,
                traffic=traffic, end_to_end=list(bench["end_to_end"]),
                per_layer=list(bench["per_layer"]))


def load_layout(cfg: dict):
    """The module ``layouts/<cfg["layout"]>.py``.  A layout gives
    ``Shape`` (``Shape.from_config(cfg)``, with what ``counts.Shape``
    reads), ``arch_config(cfg)``, the served model's ``ArchConfig``, and
    the served parameter tree made from the seed,
    ``program_params(cfg, seed, device)``, or its shapes,
    ``param_shapes(cfg)``.  The file names its layout; there is no
    default."""
    known = sorted(f[:-3] for f in os.listdir(os.path.join(HERE, "layouts"))
                   if f.endswith(".py"))
    name = cfg.get("layout")
    if name not in known:
        raise KeyError(f"configuration {cfg.get('name')!r}: layout {name!r} "
                       f"is none of {known}")
    return importlib.import_module(f"benchmarks.chip.layouts.{name}")


_READERS: Dict[str, Callable] = {}


def load_reader(metric: str) -> Callable:
    """``read(window)`` of ``metrics/<metric>.py``: a number, or None
    where the run holds nothing to read."""
    if metric not in _READERS:
        path = os.path.join(HERE, "metrics", f"{metric}.py")
        spec = importlib.util.spec_from_file_location(
            f"benchmarks.chip.metrics.{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _READERS[metric] = mod.read
    return _READERS[metric]

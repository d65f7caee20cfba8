"""Find a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, cell or per-layer
metric sits in a file of its own, found by name:

* ``bench/configs/<config>.json``: the configuration (the ``file`` of its
  ``configs`` entry);
* ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
* ``bench/limits/<workload>.json``: the cell's correctness limits;
* ``bench/end_to_end/<metric>.py`` and ``bench/layer_metrics/<metric>.py``:
  one metric's reader, a module with ``read(ctx) -> float | None``;
* ``bench/generators/<generator>.py``: the general traffic generator that
  the traffic file names, with the functions of
  ``bench.generators.CONTRACT``.

Adding any of them needs new files and new ``BENCHMARK.json`` entries only.
"""
from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass, field
from pathlib import Path
from types import ModuleType
from typing import Callable

from bench.generators import CONTRACT

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    """One workload with everything the harness needs to run it."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]  # metric entries this cell reports
    per_layer: list[dict]
    generator: ModuleType  # bench/generators/<traffic["generator"]>.py
    readers: dict[str, Callable] = field(default_factory=dict)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _reports(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _load_module(name: str, kind: str, root: Path) -> ModuleType:
    """The module ``<root>/<kind>/<name>.py``."""
    path = root / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_')}", path)
    if spec is None or not path.exists():
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    module = importlib.util.module_from_spec(spec)
    # Registered before it runs, as an import would: dataclasses look
    # their module up while the class is made.
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


def load_reader(name: str, kind: str, root: Path = BENCH) -> Callable:
    """The ``read`` function of ``<kind>/<name>.py`` (``kind`` is
    ``end_to_end`` or ``layer_metrics``)."""
    return _load_module(name, kind, root).read


def load_generator(name: str, root: Path = BENCH) -> ModuleType:
    """The generator ``generators/<name>.py``; one that lacks a function
    of the contract is an error."""
    module = _load_module(name, "generators", root)
    missing = [f for f in CONTRACT if not callable(getattr(module, f, None))]
    if missing:
        raise AttributeError(f"generator {name!r} lacks {missing}")
    return module


def load_cell(workload: str, benchmark: dict | None = None,
              root: Path = ROOT) -> Cell:
    """Resolve ``workload`` to its configuration, traffic, generator, limits
    and metrics. ``root`` holds ``BENCHMARK.json`` and the ``bench``
    directory."""
    bench_dir = root / "bench"
    if benchmark is None:
        benchmark = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in benchmark["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in benchmark["configs"]}
    config = load_json(root / configs[w["config"]]["file"])
    traffic = load_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = load_json(bench_dir / "limits" / f"{workload}.json")
    e2e = [m for m in benchmark["end_to_end"] if _reports(m, workload)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in benchmark["per_layer"]
                 if _reports(m, workload) and m["moves"] in reported]
    readers = {m["name"]: load_reader(m["name"], "end_to_end", bench_dir)
               for m in e2e}
    readers.update({m["name"]: load_reader(m["name"], "layer_metrics",
                                           bench_dir) for m in per_layer})
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer,
                generator=load_generator(traffic["generator"], bench_dir),
                readers=readers)

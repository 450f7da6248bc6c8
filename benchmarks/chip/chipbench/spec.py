"""Find a cell, its configuration, its traffic mix, its architecture and
its metrics by name.

The benchmark is driven by ``BENCHMARK.json``: a new cell, configuration,
traffic mix or per-layer metric is a new entry there plus a file of its
own in ``configs/``, ``traffic/`` or ``metrics/``.  A configuration names
its architecture by the published ``model_type`` key, and a new
architecture is one new file, ``arch/<model_type>.py`` (what it gives
is in ``arch/granitemoe.py``'s docstring).  Nothing in this package has
to change for any of them.
"""
from __future__ import annotations

import hashlib
import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, Dict, List, Optional

# benchmarks/chip: the directory that holds run.py and the data files
BENCH_DIR = Path(__file__).resolve().parents[1]


def find_spec_file(start: Path = BENCH_DIR) -> Path:
    """``BENCHMARK.json`` at the root of the checkout that holds us."""
    for d in (start, *start.parents):
        f = d / "BENCHMARK.json"
        if f.is_file():
            return f
    raise FileNotFoundError("no BENCHMARK.json above " + str(start))


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    workloads: Optional[tuple] = None

    def reported_in(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: tuple          # Metric, those this cell reports
    per_layer: tuple           # Metric, those this cell reports


def _metric(entry: dict) -> Metric:
    wl = entry.get("workloads")
    return Metric(name=entry["name"], unit=entry["unit"],
                  workloads=tuple(wl) if wl is not None else None)


def load_cell(name: str, spec_file: Optional[Path] = None) -> Cell:
    spec = json.loads((spec_file or find_spec_file()).read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    e2e = tuple(m for m in map(_metric, spec["end_to_end"])
                if m.reported_in(name))
    layer = tuple(m for m in map(_metric, spec["per_layer"])
                  if m.reported_in(name))
    return Cell(name=name, config=w["config"], traffic=w["traffic"],
                chips=int(w["chips"]), end_to_end=e2e, per_layer=layer)


def data_file(kind: str, name: str, base: Path = BENCH_DIR) -> Path:
    """``<base>/<kind>/<name>.json``: a configuration or a traffic mix."""
    f = base / kind / f"{name}.json"
    if not f.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r} at {f}")
    return f


def load_json(kind: str, name: str, base: Path = BENCH_DIR) -> dict:
    return json.loads(data_file(kind, name, base).read_text())


def _load_module(prefix: str, f: Path):
    """Import ``f`` once per name and contents, so the jitted functions
    and caches it keeps are shared by every caller in the process (and
    by every copy of the file, as the tests' trees hold)."""
    tag = hashlib.sha256(f.read_bytes()).hexdigest()[:16]
    name = f"{prefix}{f.stem.replace('.', '_').replace('-', '_')}_{tag}"
    if name not in sys.modules:
        mod_spec = importlib.util.spec_from_file_location(name, f)
        mod = importlib.util.module_from_spec(mod_spec)
        sys.modules[name] = mod          # dataclasses look their module up
        try:
            mod_spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[name]
            raise
    return sys.modules[name]


def metric_reader(name: str, base: Path = BENCH_DIR) -> Callable:
    """The ``read(run)`` function of ``<base>/metrics/<name>.py``."""
    f = base / "metrics" / f"{name}.py"
    if not f.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {f}")
    return _load_module("chipbench_metric_", f).read


def arch_module(model_type: Optional[str], base: Path = BENCH_DIR):
    """The architecture plug-in ``<base>/arch/<model_type>.py``."""
    f = base / "arch" / f"{model_type}.py"
    if not model_type or not f.is_file():
        known = sorted(p.stem for p in (base / "arch").glob("*.py"))
        raise KeyError(f"unknown model_type {model_type!r}; known: {known}")
    return _load_module("chipbench_arch_", f)


def read_metrics(metrics: List[Metric], run, base: Path = BENCH_DIR
                 ) -> Dict[str, dict]:
    """Each metric's value from its reader; a reader that finds nothing
    to read returns ``None`` and the metric is left out."""
    out = {}
    for m in metrics:
        value = metric_reader(m.name, base)(run)
        if value is not None:
            out[m.name] = {"value": float(value), "unit": m.unit}
    return out

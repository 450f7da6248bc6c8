"""A configuration file -> its architecture plug-in, the program's
``ModelConfig`` and engine settings.

Configuration files use the key names of the model's published
``config.json``; ``model_type`` names the architecture plug-in
(``arch/<model_type>.py``) that maps them to the program's
``ModelConfig``, makes the weights, runs the reference and counts the
model's operations.  ``reduced`` names every key changed from the
published file, and ``program`` holds what the program needs beyond the
published model (the padding of the expert axis, the worker fleet, the
shadow's scheme).
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

from . import spec


@dataclass(frozen=True)
class BenchConfig:
    name: str
    plugin: object             # the module arch/<model_type>.py
    model: object              # repro.models.config.ModelConfig
    n_workers: int
    predictor: str
    shadow_scheme: str
    raw: dict


def from_dict(raw: dict, base: Path = spec.BENCH_DIR) -> BenchConfig:
    plugin = spec.arch_module(raw.get("model_type"), base)
    prog = raw.get("program", {})
    return BenchConfig(name=raw["name"], plugin=plugin,
                       model=plugin.model_config(raw),
                       n_workers=int(prog.get("n_workers", 8)),
                       predictor=prog.get("predictor", "sep"),
                       shadow_scheme=prog.get("shadow_scheme", "int8"),
                       raw=raw)


def load(name: str, base: Path = spec.BENCH_DIR) -> BenchConfig:
    raw = spec.load_json("configs", name, base)
    if raw.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {raw.get('name')!r}")
    return from_dict(raw, base)

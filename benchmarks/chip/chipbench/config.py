"""A configuration file -> the program's ``ModelConfig`` and engine settings.

Configuration files use the key names of the model's published
``config.json``; ``reduced`` names every key changed from it, and
``program`` holds what the program needs beyond the published model (the
padding of the expert axis, the worker fleet, the shadow's scheme).
"""
from __future__ import annotations

from dataclasses import dataclass
from pathlib import Path

from . import spec


@dataclass(frozen=True)
class BenchConfig:
    name: str
    model: object              # repro.models.config.ModelConfig
    n_workers: int
    predictor: str
    shadow_scheme: str
    raw: dict


def model_config(raw: dict):
    from repro.models.config import ModelConfig
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's experts are SwiGLU (silu) only")
    prog = raw.get("program", {})
    return ModelConfig(
        name=raw["name"], family="moe",
        num_layers=int(raw["num_hidden_layers"]),
        d_model=int(raw["hidden_size"]),
        num_heads=int(raw["num_attention_heads"]),
        num_kv_heads=int(raw["num_key_value_heads"]),
        d_ff=int(raw["intermediate_size"]),
        vocab_size=int(raw["vocab_size"]),
        num_experts=int(raw["num_local_experts"]),
        top_k=int(raw["num_experts_per_tok"]),
        d_expert=int(raw["intermediate_size"]),
        padded_experts=int(prog.get("padded_experts", 0)),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        dtype=str(raw["torch_dtype"]),
        source=raw["source"])


def from_dict(raw: dict) -> BenchConfig:
    prog = raw.get("program", {})
    return BenchConfig(name=raw["name"], model=model_config(raw),
                       n_workers=int(prog.get("n_workers", 8)),
                       predictor=prog.get("predictor", "sep"),
                       shadow_scheme=prog.get("shadow_scheme", "int8"),
                       raw=raw)


def load(name: str, base: Path = spec.BENCH_DIR) -> BenchConfig:
    raw = spec.load_json("configs", name, base)
    if raw.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {raw.get('name')!r}")
    return from_dict(raw)

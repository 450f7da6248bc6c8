"""A tiny benchmark tree for CPU tests: one small Granite-shaped model in
bfloat16, a one-client and a three-client closed loop, and the real
architecture plug-ins and metric readers, laid out exactly like
``benchmarks/chip``."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parents[1]
for p in (str(BENCH), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

CONFIG = {
    "name": "tiny", "source": "a small model of the Granite-MoE shape",
    "model_type": "granitemoe",
    "hidden_size": 64, "intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2,
    "num_local_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512,
    "tie_word_embeddings": True, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "hidden_act": "silu", "torch_dtype": "bfloat16",
    "reduced": {},
    "program": {"n_workers": 2, "predictor": "sep",
                "shadow_scheme": "int8"},
}

MIX = {"loop": "closed", "prompt": {"median": 12, "sigma": 0.8, "lo": 4,
                                    "hi": 24},
       "output": {"median": 6, "sigma": 0.8, "lo": 2, "hi": 12},
       "block": 4, "requests": 64, "cache_window": 40,
       "check": {"max_requests": 4, "max_tokens": 60}}

# Clean CPU runs of this model on five seeds read token_gap 0, a mean
# logit_err of 0.0050-0.0056 and a mean route_gap under 1e-4 (bfloat16
# against float32 at d=64, two layers); the float8 control reads a mean
# logit_err of 0.050-0.060 on the same seeds.
LIMITS = {"token_gap": 0.0, "logit_err": 0.015, "route_gap": 0.05}


def metric(name, unit="%", better="higher", layer="x", moves="decode_tok_s",
           source="program_counter"):
    return {"name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": moves}


def build(base: Path) -> Path:
    """Write the tiny tree under ``base``; returns its BENCHMARK.json."""
    for d in ("configs", "traffic", "limits"):
        (base / d).mkdir(parents=True, exist_ok=True)
    for d in ("arch", "metrics"):
        shutil.copytree(BENCH / d, base / d, dirs_exist_ok=True,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (base / "configs" / "tiny.json").write_text(json.dumps(CONFIG))
    for name, clients in (("solo", 1), ("batch", 3)):
        mix = dict(MIX, name=name, clients=clients, max_batch=clients)
        (base / "traffic" / f"{name}.json").write_text(json.dumps(mix))
        (base / "limits" / f"tiny.{name}.json").write_text(
            json.dumps({"limits": LIMITS}))
    e2e = [{"name": n, "unit": u, "better": b, "bound": 0.05,
            "source": "host_clock"}
           for n, u, b in (("decode_tok_s", "tokens/s", "higher"),
                           ("tpot_p90_ms", "ms", "lower"),
                           ("ttft_p50_ms", "ms", "lower"),
                           ("setup_s", "s", "lower"))]
    spec = {"command": ["python3", "benchmarks/chip/run.py"],
            "paths": ["benchmarks/chip"], "run_seconds": 2,
            "configs": [{"name": "tiny", "source": "x",
                         "file": "configs/tiny.json", "reduced": [],
                         "why": "tests"}],
            "workloads": [{"name": f"tiny.{m}", "config": "tiny",
                           "traffic": m, "chips": 1, "why": "tests"}
                          for m in ("solo", "batch")],
            "end_to_end": e2e,
            "per_layer": [metric("loads_per_token", "loads/token", "lower"),
                          metric("sep_recall"),
                          metric("batch_rows_mean", "rows")]}
    f = base / "BENCHMARK.json"
    f.write_text(json.dumps(spec))
    return f


def run(base: Path, cell: str, *, seed: int = 2**33 + 7, seconds=2.0,
        fault=None, traced=False, keep_served=False):
    """One run of a tiny cell on the CPU, past the harness's look for a
    chip, with the persistent compile cache off."""
    import time
    import jax
    from chipbench import driver
    spec_file = build(base)
    jax.config.update("jax_enable_compilation_cache", False)
    out = driver.run(cell, seed, seconds, traced, lambda m: None,
                     t_start=time.perf_counter(), require_chip=False,
                     base=base, spec_file=spec_file, fault=fault,
                     compile_cache=False, keep_served=keep_served)
    return out if keep_served else out["result"]

"""Shared benchmark scaffolding.

Every benchmark module exposes ``run(fast=True) -> list[dict]`` with keys
``name`` (slash-separated id), ``us_per_call`` (wall-clock microseconds
per measured unit on THIS host) and ``derived`` (the figure/table value:
recall, tokens/s, bytes, ...).  ``run.py`` prints the combined CSV.

Engine benchmarks measure REAL routing/prediction on a small Mixtral-
family model (the container cannot hold 8x7B); timing-model benchmarks
replay those traces on the full-size config with the calibrated edge
profile.  This mirrors DESIGN.md §9's honesty notes.
"""
from __future__ import annotations

import json
import os
import time
from typing import Callable, Dict, List

import jax
import numpy as np

from repro.configs import get_config
from repro.models import init_params

ARTIFACTS = os.path.join(os.path.dirname(__file__), "artifacts")

# The small but real Mixtral-family model every engine benchmark shares.
BENCH_MODEL = dict(num_layers=6, d_model=128, num_experts=8,
                   d_expert=256, vocab_size=512)


def bench_cfg(**overrides):
    kw = dict(BENCH_MODEL)
    kw.update(overrides)
    return get_config("mixtral-8x7b").reduced(**kw)


_param_cache: Dict = {}


def bench_model(**overrides):
    key = tuple(sorted(overrides.items()))
    if key not in _param_cache:
        cfg = bench_cfg(**overrides)
        _param_cache[key] = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return _param_cache[key]


def bench_prompts(cfg, q: int = 2, length: int = 16):
    k = jax.random.PRNGKey(123)
    return [{"tokens": jax.random.randint(jax.random.fold_in(k, i),
                                          (1, length), 0, cfg.vocab_size)}
            for i in range(q)]


def timed(fn: Callable, *args, **kw):
    t0 = time.time()
    out = fn(*args, **kw)
    return out, (time.time() - t0) * 1e6


def save_artifact(name: str, obj) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, name)
    with open(path, "w") as f:
        json.dump(obj, f, indent=1, default=float)
    return path


def _short_commit(commit) -> str:
    """Normalize a commit id to git's 7-char short form.  CI exports the
    FULL sha in ``$BENCH_COMMIT`` while local runs use ``git rev-parse
    --short`` — without normalization the same commit recorded from both
    sides produced two series entries that never deduped against each
    other.  Non-sha values (e.g. "unknown") pass through unchanged."""
    commit = (commit or "").strip().lower()
    if len(commit) >= 7 and all(c in "0123456789abcdef" for c in commit):
        return commit[:7]
    return commit or "unknown"


def record_bench(name: str, metrics: dict, path: str = None) -> str:
    """Append this commit's measured point to the committed perf
    trajectory ``benchmarks/BENCH_<name>.json`` (one entry per commit;
    re-running on the same commit overwrites its point).  The commit id
    comes from ``$BENCH_COMMIT`` (CI, full sha) or ``git rev-parse
    --short`` (local), both normalized to the short form so the two
    sources collide instead of duplicating; historic entries are
    normalized and deduped on the way through (last point per commit
    wins).  The file is meant to be committed so tokens/s, overlap
    efficiency and re-hit rate are traceable PR over PR.  ``path``
    overrides the destination (unit tests)."""
    import subprocess
    commit = os.environ.get("BENCH_COMMIT")
    if not commit:
        try:
            commit = subprocess.run(
                ["git", "rev-parse", "--short", "HEAD"],
                capture_output=True, text=True, check=True,
                cwd=os.path.dirname(__file__)).stdout.strip()
        except Exception:
            commit = "unknown"
    commit = _short_commit(commit)
    if path is None:
        path = os.path.join(os.path.dirname(__file__),
                            f"BENCH_{name}.json")
    series = []
    if os.path.exists(path):
        with open(path) as f:
            series = json.load(f).get("series", [])
    deduped: Dict[str, dict] = {}
    for p in series:
        q = dict(p, commit=_short_commit(p.get("commit")))
        deduped[q["commit"]] = q          # later entries win
    deduped.pop(commit, None)
    series = list(deduped.values()) + [{"commit": commit, **metrics}]
    with open(path, "w") as f:
        json.dump({"benchmark": name, "series": series}, f, indent=1,
                  default=float)
        f.write("\n")
    return path


def row(name: str, us: float, derived) -> dict:
    return {"name": name, "us_per_call": round(us, 1), "derived": derived}

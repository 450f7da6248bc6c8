"""Fig. 6 — recall vs token/KV alignment periods (int8 shadow).

T_i_KV_j grid: recall should degrade as either period grows, with the
token period mattering more (paper §4.2).
"""
from __future__ import annotations

import numpy as np

from repro.core import AlignmentPolicy, ODMoEEngine
from .common import bench_model, bench_prompts, row, save_artifact, timed


def run(fast: bool = True):
    cfg, params = bench_model()
    periods = (1, 4, 16) if fast else (1, 2, 4, 8, 16)
    n_tokens = 24 if fast else 64
    prompts = bench_prompts(cfg, q=1 if fast else 4)
    rows, grid = [], {}
    for tp in periods:
        for kp in periods:
            policy = AlignmentPolicy(tp, kp)
            recs, us = [], 0.0
            for prompt in prompts:
                eng = ODMoEEngine(cfg, params, n_workers=8,
                                  predictor="sep", shadow_scheme="int8")
                (_, trace), dt = timed(eng.generate, prompt, n_tokens,
                                       policy)
                us += dt
                recs.append(trace.recall())
            import jax; jax.clear_caches()
            r = float(np.mean(recs))
            grid[policy.label()] = r
            rows.append(row(f"fig6/{policy.label()}", us / len(prompts), r))
    save_artifact("fig6_period_recall.json", grid)
    return rows

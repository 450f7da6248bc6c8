"""Table 1 — expert-activation prediction baselines vs SEP.

All predictors run on the SAME model/prompts/decode trajectory (real
engine).  Paper-reported numbers for the original systems are included
for side-by-side context.
"""
from __future__ import annotations

import numpy as np

from repro.core import AlignmentPolicy, ODMoEEngine
from .common import bench_model, bench_prompts, row, save_artifact, timed

PREDICTORS = [
    ("sep_fp16", "sep", "fp16"),
    ("sep_int8", "sep", "int8"),
    ("sep_nf4", "sep", "nf4"),
    ("nextgate(AdapMoE/DAOP)", "nextgate", None),
    ("multigate(HOBBIT)", "multigate", None),
    ("frequency(EdgeMoE/fMoE)", "freq", None),
    ("random", "random", None),
]

PAPER_REPORTED = {"AdapMoE": 0.86, "DAOP": 0.84, "HOBBIT": 0.91,
                  "MixtralOffloading_cache_hit": 0.80,
                  "fMoE_cache_hit": 0.85,
                  "SEP_fp16": 0.9994, "SEP_int8": 0.9734,
                  "SEP_nf4": 0.9567}


def run(fast: bool = True):
    cfg, params = bench_model()
    n_tokens = 24 if fast else 64
    prompts = bench_prompts(cfg, q=1 if fast else 5)
    rows, table = [], {}
    for name, pred, scheme in PREDICTORS:
        recs, us = [], 0.0
        for prompt in prompts:
            eng = ODMoEEngine(cfg, params, n_workers=8, predictor=pred,
                              shadow_scheme=scheme or "int8")
            (_, trace), dt = timed(eng.generate, prompt, n_tokens,
                                   AlignmentPolicy(1, 1))
            us += dt
            recs.append(trace.recall())
        import jax; jax.clear_caches()
        r = float(np.mean(recs))
        table[name] = r
        rows.append(row(f"table1/{name}", us / len(prompts), round(r, 4)))
    save_artifact("table1_predictors.json",
                  {"measured": table, "paper_reported": PAPER_REPORTED})
    return rows

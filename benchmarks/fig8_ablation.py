"""Fig. 8 — ablation Cases 1-6: decoding speed on the edge testbed.

  1. SEP + token & KV alignment        4. SEP, no alignment
  2. SEP + token alignment only        5. random prefetch
  3. SEP + KV alignment only           6. no prefetch (load after gate)

Recall for each case is MEASURED on the real small-model engine; the
measured recall then drives the full-size Mixtral-8x7B trace through the
calibrated discrete-event model (DESIGN.md §9).  The paper's monotone
Case1 > ... > Case6 ordering is the reproduction target.
"""
from __future__ import annotations

import numpy as np

from repro.configs import get_config
from repro.core import (AlignmentPolicy, ODMoEEngine, RTX3090_EDGE,
                        GroupSchedule, simulate_odmoe, synthetic_trace)
from .common import bench_model, bench_prompts, row, save_artifact, timed

CASES = {
    "case1_token+kv": ("sep", AlignmentPolicy(1, 1)),
    "case2_token_only": ("sep", AlignmentPolicy(1, 0)),
    "case3_kv_only": ("sep", AlignmentPolicy(0, 1)),
    "case4_no_align": ("sep", AlignmentPolicy(0, 0)),
    "case5_random": ("random", AlignmentPolicy(1, 1)),
    "case6_no_prefetch": ("none", AlignmentPolicy(1, 1)),
}


def measure_recalls(fast: bool = True):
    cfg, params = bench_model()
    n_tokens = 24 if fast else 64
    prompts = bench_prompts(cfg, q=1 if fast else 4)
    recalls, us_total = {}, {}
    for name, (pred, policy) in CASES.items():
        recs, us = [], 0.0
        for prompt in prompts:
            eng = ODMoEEngine(cfg, params, n_workers=8, predictor=pred,
                              shadow_scheme="int8")
            (_, trace), dt = timed(eng.generate, prompt, n_tokens, policy)
            us += dt
            recs.append(trace.recall())
        import jax; jax.clear_caches()
        # predictor-less decodes measure no recall (None, case 6): skip
        # them instead of poisoning the mean (JSON stores null)
        recs = [r for r in recs if r is not None]
        recalls[name] = float(np.mean(recs)) if recs else None
        us_total[name] = us / len(prompts)
    return recalls, us_total


def run(fast: bool = True):
    recalls, us = measure_recalls(fast)
    full = get_config("mixtral-8x7b")
    sched = GroupSchedule(8, 2)
    rows, speeds = [], {}
    for name, (pred, policy) in CASES.items():
        r = recalls[name]
        if pred == "none":
            tr = synthetic_trace(full, 128, recall=0.0,
                                 with_predictions=False)
        else:
            tr = synthetic_trace(full, 128, recall=r)
        # mark alignment flags for late-departure accounting
        for rec in tr.records:
            rec.aligned_token = policy.align_token_at(rec.index)
            rec.aligned_kv = policy.align_kv_at(rec.index)
        t = simulate_odmoe(full, tr, sched, RTX3090_EDGE,
                           shadow_scheme="int8",
                           predictor="sep" if pred == "sep" else pred)
        speeds[name] = t.tokens_per_s
        rows.append(row(f"fig8/{name}", us[name],
                        round(t.tokens_per_s, 3)))
    save_artifact("fig8_ablation.json",
                  {"measured_recall": recalls, "tokens_per_s": speeds})
    return rows

"""Fig. 3 — SEP recall vs output-token index per shadow quantization.

Real engine runs: the full-precision model decodes while fp16/int8/nf4
shadow models predict; recall per Eq. (2)/(3).  Shows (a) the ordering
fp16 > int8 > nf4 and (b) that alignment prevents autoregressive decay.
"""
from __future__ import annotations

import numpy as np

from repro.core import AlignmentPolicy, ODMoEEngine
from .common import bench_model, bench_prompts, row, save_artifact, timed

SCHEMES = ("fp16", "int8", "nf4")


def run(fast: bool = True):
    cfg, params = bench_model()
    n_tokens = 24 if fast else 64
    prompts = bench_prompts(cfg, q=2 if fast else 5)
    rows, curves = [], {}
    for scheme in SCHEMES:
        for aligned, policy in (("aligned", AlignmentPolicy(1, 1)),
                                ("unaligned", AlignmentPolicy(0, 0))):
            per_tok = []
            overall = []
            us = 0.0
            for prompt in prompts:
                eng = ODMoEEngine(cfg, params, n_workers=8,
                                  predictor="sep", shadow_scheme=scheme)
                (toks, trace), dt = timed(eng.generate, prompt, n_tokens,
                                          policy)
                us += dt
                # SEP predicts every token; None entries (tokens with no
                # predictions) would only appear for other predictors —
                # guard the aggregation anyway (NaN-free means)
                per_tok.append([r for r in trace.recall_per_token()
                                if r is not None])
                overall.append(trace.recall())
            overall = [r for r in overall if r is not None]
            curve = np.mean(np.array(per_tok), axis=0)
            curves[f"{scheme}_{aligned}"] = curve.tolist()
            rows.append(row(f"fig3/{scheme}/{aligned}",
                            us / len(prompts), float(np.mean(overall))))
    save_artifact("fig3_recall_curves.json", curves)
    return rows

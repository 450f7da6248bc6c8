"""Float32 yardstick for decode outputs.

Token bit-identity with ``greedy_generate`` holds only where the engine
and the reference run the same executables.  On a TPU they do not: the
engine's per-layer jitted steps and the reference's layer scan are
different XLA programs, whose bf16 roundings differ in the last bit
(XLA keeps bf16 intermediates in float32 inside a fusion, and the two
programs fuse differently).
That alone moves a top-k router decision wherever two experts' router
logits nearly tie, and a swapped expert changes a step's logits by a
large fraction — a different, equally valid outcome, not an error.

So a run is judged against a float32 ``precision="highest"`` forward of
the same weights, teacher-forced on the run's own tokens, step by step:

* routing — the engine's top-k expert set in each (layer, step) is
  compared with the reference's; at least ``MIN_ROUTE_AGREEMENT`` of
  them must agree.  Two unrelated top-8-of-40 sets agree with chance
  ~1e-8, so a routing or expert-loading fault scores near 0, while
  bf16-vs-float32 near-tie flips leave most sets equal;
* steps where every layer's routing agrees compute the same experts on
  both sides, so only arithmetic differs: the emitted token must lie
  within ``ARGMAX_GAP`` of the reference maximum and, where the run's
  logits are known, their relative RMS error must be within
  ``LOGIT_RTOL``.  At least one step must agree fully;
* steps with a routing flip carry no logit bound.

A served batch is judged per request, except that the agreeing steps
may come from any of its requests (``merge``): a short request can flip
in every step by chance, a phase of several requests does not.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as tf_lib
from repro.models.config import MOE_FF, ModelConfig

# Relative RMS logit error over the vocabulary on steps whose routing
# agrees.  bf16 keeps 8 significant bits (unit roundoff 2**-9); each
# layer rounds its residual stream, attention and expert outputs a few
# times and independent roundings add in quadrature, which leaves ~1-2%
# after 8 layers.  A wrong expert, a stale cache entry or a misplaced row
# moves the logits by their own magnitude (relative error ~1).
LOGIT_RTOL = 0.05
# Emitted token vs the reference maximum, in RMS units of the reference
# logits: twice the largest per-logit error that LOGIT_RTOL allows (the
# largest of ~50k errors is under 5x their RMS).  A random token sits
# ~4 RMS below the maximum.
ARGMAX_GAP = 2 * 5 * LOGIT_RTOL
MIN_ROUTE_AGREEMENT = 0.5


@functools.lru_cache(maxsize=None)
def _float32_forward(cfg: ModelConfig):
    cfg32 = dataclasses.replace(cfg, dtype="float32")

    def fn(params, tokens):
        p32 = jax.tree.map(
            lambda a: (a.astype(jnp.float32)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a),
            params)
        with jax.default_matmul_precision("highest"):
            logits, aux, _ = tf_lib.lm_seq(cfg32, p32, tokens,
                                           moe_method="dense")
        return logits, aux["topk"]
    return jax.jit(fn)


def float32_reference(cfg: ModelConfig, params, tokens):
    """Teacher-forced float32 forward of a decoder-only model: every
    weight cast to float32, every matmul at ``precision="highest"``, MoE
    layers through the exact ``dense`` dispatch.  Returns ``(logits
    (B, T, V), {moe layer: top-k expert ids (B, T, k)})``."""
    logits, topk = _float32_forward(cfg)(params, tokens)
    pattern, reps = cfg.pattern()
    moe_pos = [i for i, kinds in enumerate(pattern) if kinds[1] == MOE_FF]
    routing = {r * len(pattern) + i: np.asarray(t[r])
               for i, t in zip(moe_pos, topk) for r in range(reps)}
    return np.asarray(logits), routing


@dataclass
class YardstickReport:
    steps: int                    # decode steps judged
    agreeing_steps: int           # steps whose routing agrees in every layer
    route_agreement: float        # share of agreeing (layer, step) sets
    max_gap: float                # over agreeing steps, reference-RMS units
    max_rel: Optional[float]      # over agreeing steps; None without logits

    @property
    def ok(self) -> bool:
        return (self.agreeing_steps > 0
                and self.route_agreement >= MIN_ROUTE_AGREEMENT
                and self.max_gap <= ARGMAX_GAP
                and (self.max_rel is None or self.max_rel <= LOGIT_RTOL))

    def describe(self) -> str:
        rel = "n/a" if self.max_rel is None else f"{self.max_rel:.6f}"
        return (f"routing agrees in {self.route_agreement:.4f} of (layer, "
                f"step) sets (limit {MIN_ROUTE_AGREEMENT}); "
                f"{self.agreeing_steps}/{self.steps} steps agree in every "
                f"layer; on those, max argmax gap {self.max_gap:.6f} RMS "
                f"(limit {ARGMAX_GAP}), max relative RMS logit error "
                f"{rel} (limit {LOGIT_RTOL})")


def merge(reports: Sequence[YardstickReport]) -> YardstickReport:
    """One report over several requests: steps add up, and every bound
    takes its worst request (the lowest routing agreement, the largest
    gap and error)."""
    rels = [r.max_rel for r in reports if r.max_rel is not None]
    return YardstickReport(
        steps=sum(r.steps for r in reports),
        agreeing_steps=sum(r.agreeing_steps for r in reports),
        route_agreement=min(r.route_agreement for r in reports),
        max_gap=max(r.max_gap for r in reports),
        max_rel=max(rels) if rels else None)


def check_decode(cfg: ModelConfig, params, prompt, tokens,
                 records: Sequence, logits: Optional[List] = None
                 ) -> YardstickReport:
    """Judge one request's decode against the float32 reference.

    ``tokens[0]`` comes from the prefill, the rest from decode steps.
    ``records`` are this one request's engine ``TokenRecord``s, one
    routing row per emitted token (``ODMoEEngine.generate`` without
    speculation, or a served request's own trace); ``logits`` (optional,
    ``(1, V)`` each, one per decode step) the engine's logits."""
    prompt = np.asarray(prompt, np.int32)
    tokens = np.asarray(tokens, np.int32)
    rows = [[(lr.layer, t) for lr in rec.layers for t in lr.true[j:j + 1]]
            for rec in records for j in range(rec.layers[0].true.shape[0])]
    if len(rows) != len(tokens) - 1:
        raise ValueError(f"{len(rows)} routing rows for {len(tokens) - 1} "
                         "decode steps")
    seq = jnp.asarray(np.concatenate([prompt, tokens[:-1]]))[None]
    ref, routing = float32_reference(cfg, params, seq)
    ref = ref[0]
    t0 = len(prompt)
    agree_sets = total_sets = agreeing = 0
    gaps, rels = [], []
    for n in range(1, len(tokens)):
        pos = t0 - 1 + n
        step_agrees = True
        for layer, experts in rows[n - 1]:
            same = (set(map(int, experts))
                    == set(map(int, routing[layer][0, pos])))
            agree_sets += same
            total_sets += 1
            step_agrees &= same
        if not step_agrees:
            continue
        agreeing += 1
        r = ref[pos]
        rms = float(np.sqrt(np.mean(r ** 2)))
        gaps.append(float(r.max() - r[tokens[n]]) / rms)
        if logits is not None:
            e = np.asarray(logits[n - 1], np.float32)[0]
            rels.append(float(np.linalg.norm(e - r) / np.linalg.norm(r)))
    return YardstickReport(
        steps=len(tokens) - 1, agreeing_steps=agreeing,
        route_agreement=agree_sets / max(total_sets, 1),
        max_gap=max(gaps, default=0.0),
        max_rel=max(rels, default=0.0) if logits is not None else None)

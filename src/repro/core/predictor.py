"""Expert-activation predictors: SEP (the paper's) + reproduced baselines.

SEP (Scaled Emulative Prediction): a quantized *shadow* copy of the model
(held as its quantization codes) decodes in parallel and its own
observed routing decisions — unfolded several layers ahead of the full
model — are the predictions.  Baselines follow §2.3 / Table 1:

  * ``nextgate``  — feed layer l's router input to layer l+1's gate
                    (Mixtral-Offloading / AdapMoE / DAOP heuristic).
  * ``multigate`` — same but extrapolating up to 4 layers ahead (HOBBIT).
  * ``freq``      — historical per-layer expert popularity (EdgeMoE/fMoE).
  * ``random``    — ablation Case 5 (random prefetch).
  * ``none``      — ablation Case 6 (no prefetch; load after gating).

Recall is Eq. (2)/(3): correctly predicted experts / (k · L · tokens).
"""
from __future__ import annotations

import functools
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import prefill
from repro.models.config import MOE_FF, ModelConfig
from repro.quant.quantize import shadow_codes

from .spans import span


@functools.lru_cache(maxsize=None)
def _shadow_rollout_step(cfg: ModelConfig, S: int):
    """Fused ``S``-step shadow rollout: one jitted ``lax.scan`` dispatch
    instead of ``S`` sequential ``_shadow_step`` dispatches — the
    drafting hot path of speculative decoding, where per-dispatch
    overhead would otherwise be paid once per drafted token.  Returns
    the per-step greedy tokens, routing top-k and cache states stacked
    on a leading step axis (the caches ARE the per-step states — the
    rollback target after committing ``c`` is slice ``c - 1``)."""
    from repro.models.transformer import lm_decode

    def roll(p, tok, caches, pos):
        def body(carry, _):
            tok, caches, pos = carry
            logits, caches, aux = lm_decode(cfg, p, tok, caches, pos,
                                            moe_method="grouped")
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (nxt, caches, pos + 1), (nxt, aux["topk"], caches)

        _, ys = jax.lax.scan(body, (tok, caches, pos), None, length=S)
        return ys

    return jax.jit(roll)


@functools.lru_cache(maxsize=None)
def _shadow_step(cfg: ModelConfig):
    """One jitted whole-model shadow decode step per architecture.

    Cached on the frozen config (params enter as a pytree argument), so
    every ``SEPShadow`` over the same architecture — whatever its
    quantization scheme, and however many engines the caller builds —
    shares one compiled executable per batch shape.  The expert FFNs
    inside run the same ``grouped`` dispatch as the engine and the
    reference decoder."""
    from repro.models.transformer import lm_decode
    return jax.jit(lambda p, t, c, pos: lm_decode(
        cfg, p, t, c, pos, moe_method="grouped"))


def moe_layer_indices(cfg: ModelConfig) -> List[int]:
    return [i for i, (_, ff) in enumerate(cfg.layer_kinds()) if ff == MOE_FF]


def layers_within_horizon(moe_layers: Sequence[int], current_layer: int,
                          horizon: int) -> List[int]:
    """The peek window feeding the prefetch load queue: MoE layer
    indices at or after ``current_layer``, truncated to the first
    ``horizon`` of them.  ``horizon=0`` means unbounded — the SEP
    shadow predicts the whole token at once, so the default window is
    the full remaining depth; on-the-fly predictors
    (``GateExtrapolator``) naturally bound it by their own lookahead."""
    ahead = [li for li in sorted(moe_layers) if li >= current_layer]
    return ahead if horizon <= 0 else ahead[:horizon]


def topk_to_layer_dict(cfg: ModelConfig, topk_tuple) -> Dict[int, np.ndarray]:
    """Map ``lm_decode`` aux["topk"] (per-pattern-pos, (R,B,k)) to
    {absolute_layer: (B,k)}."""
    pattern, reps = cfg.pattern()
    moe_positions = [i for i, kinds in enumerate(pattern) if kinds[1] == MOE_FF]
    out = {}
    for j, pos in enumerate(moe_positions):
        arr = np.asarray(topk_tuple[j])           # (R, B, [T=1,] k)
        for r in range(arr.shape[0]):
            out[r * len(pattern) + pos] = arr[r].reshape(arr.shape[1], -1)
    return out


def recall_counts(pred: np.ndarray, true: np.ndarray) -> int:
    """c(q,n,l): correctly predicted experts.  pred/true: (B,k)."""
    total = 0
    for b in range(true.shape[0]):
        total += len(set(map(int, pred[b])) & set(map(int, true[b])))
    return total


# ------------------------------------------------------------------ SEP
class SEPShadow:
    """The quantized shadow model: an emulator that decodes in lockstep.

    ``step(token)`` runs one shadow decode step and returns the routing
    decisions it *observed* — the multi-layer-lookahead prediction for
    the full model — plus the shadow's own next greedy token.

    Two call styles share one implementation:

      * **stateful** (``reset`` / ``step`` / ``align_*``) — one shadow
        tracking one fixed batch, used by ``ODMoEEngine.generate``;
      * **functional** (``prefill_state`` / ``step_state`` /
        ``align_kv_state``) — the shadow state is an explicit pytree
        ``{"caches", "pos", "token"}`` owned by the caller, so the
        serving loop can keep one state per request, *peek* a step
        without committing it, and concatenate states into a composed
        batch (see ``concat_shadow_states``).
    """

    def __init__(self, cfg: ModelConfig, params, scheme: str = "int8"):
        self.cfg = cfg
        self.scheme = scheme
        # the scheme's real codes and scales (``QWeight`` leaves): the
        # model dequantizes them a layer at a time, and a routed expert
        # only where a token routes to it, so the shadow takes the
        # memory of its codes and reads the k experts it routes to
        self.params = shadow_codes(params, scheme)
        self.state = None
        self.token = None
        # the whole shadow decode step — grouped expert FFNs included —
        # compiles to ONE dispatch, shared across shadows of the same
        # architecture; the serving loop leans on this when it peeks
        # every runnable request's shadow as a single composed batch
        # (see ServingLoop._ensure_peeks)
        self._step = _shadow_step(cfg)

    # ------------------------------------------------------- functional
    def prefill_state(self, batch, max_cache_len: int) -> dict:
        """Prefill a fresh shadow state for one request (or batch)."""
        logits, state = prefill(self.cfg, self.params, batch,
                                max_cache_len, moe_method="grouped")
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return dict(state, token=token)

    def step_state(self, state: dict, token):
        """Pure one-step shadow decode (one jitted dispatch): consume
        ``token`` against ``state``; return ``({layer: predicted
        (B,k)}, new_state)`` without touching the stateful shadow."""
        with span("shadow_step", rows=int(token.shape[0])):
            logits, caches, aux = self._step(self.params, token,
                                             state["caches"], state["pos"])
            new = dict(state, caches=caches, pos=state["pos"] + 1,
                       token=jnp.argmax(logits, axis=-1).astype(jnp.int32))
            # the routing readback waits for the shadow step to finish
            return topk_to_layer_dict(self.cfg, aux["topk"]), new

    def rollout_states(self, state: dict, token, S: int):
        """Fused ``S``-step rollout (one jitted scan dispatch — the
        speculative drafting hot path).  Consumes ``token`` first, then
        free-runs on the shadow's own greedy continuations.  Returns
        ``(draft_tokens (B, S-1), preds_steps, stacked)``: arithmetic
        identical to ``S`` chained :meth:`step_state` calls, but
        per-step states come back stacked on a leading axis — slice the
        one you commit to with :func:`slice_rollout` instead of paying
        ``S`` dispatches up front."""
        toks, topks, caches = _shadow_rollout_step(self.cfg, S)(
            self.params, token, state["caches"], state["pos"])
        arrs = [np.asarray(t) for t in topks]        # (S, R, B, k) each
        preds_steps = [topk_to_layer_dict(self.cfg,
                                          tuple(a[s] for a in arrs))
                       for s in range(S)]
        drafts = (jnp.moveaxis(toks[:-1], 0, 1) if S > 1
                  else jnp.zeros((token.shape[0], 0), jnp.int32))
        stacked = {"caches": caches, "pos": state["pos"], "token": toks}
        return drafts, preds_steps, stacked

    @staticmethod
    def align_kv_state(state: dict, main_state: dict) -> dict:
        """Return ``state`` with caches/pos overwritten by the main
        model's (the §3.2 KV alignment, functional form)."""
        return dict(state, caches=main_state["caches"],
                    pos=main_state["pos"])

    # --------------------------------------------------------- stateful
    def reset(self, batch, max_cache_len: int):
        st = self.prefill_state(batch, max_cache_len)
        self.token = st.pop("token")
        self.state = st
        return self.token

    def step(self, token) -> Dict[int, np.ndarray]:
        """Consume ``token``; return {layer: predicted (B,k)} and update
        the shadow's own next token."""
        preds, new = self.step_state(self.state, token)
        self.token = new.pop("token")
        self.state = new
        return preds

    # ------------------------------------------------------------ align
    def align_tokens(self, main_token):
        self.token = main_token

    def align_kv(self, main_state):
        """Overwrite the shadow KV/SSM caches with the main model's —
        the stateful spelling of :meth:`align_kv_state` (one shared
        implementation; jax arrays are immutable, so adopting the main
        model's cache pytree needs no defensive copy)."""
        self.state = self.align_kv_state(self.state, main_state)


def slice_rollout(stacked: dict, s: int) -> dict:
    """Materialize per-step state ``s`` from a :meth:`rollout_states`
    stack: the state after consuming ``s + 1`` tokens — exactly what
    chained ``step_state`` calls would have returned (the rollback
    target after committing ``c`` is ``slice_rollout(stacked, c - 1)``)."""
    return {"caches": jax.tree.map(lambda a: a[s], stacked["caches"]),
            "pos": stacked["pos"] + s + 1,
            "token": stacked["token"][s]}


def concat_shadow_states(states: Sequence[dict]) -> dict:
    """Join per-request shadow states along the batch axis.

    Caches are stacked per pattern position with a leading repeat axis,
    so their batch axis is 1; ``pos`` and ``token`` are (B,).  States
    must share the same cache length (the serving loop allocates every
    request with a common ``max_cache_len``).

    This is how the serving loop batches shadow decode across requests:
    every runnable request needing a peek is aligned per-request first,
    composed here, stepped as ONE ``lm_decode`` dispatch, and sliced
    back with :func:`slice_shadow_state` (peeks stay cacheable per
    request) — see ``ServingLoop._ensure_peeks`` and
    tests/test_serving.py for the round-trip contract.
    """
    if len(states) == 1:
        return states[0]
    caches = tuple(
        jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=1),
                     *(s["caches"][p] for s in states))
        for p in range(len(states[0]["caches"])))
    return {"caches": caches,
            "pos": jnp.concatenate([s["pos"] for s in states]),
            "token": jnp.concatenate([s["token"] for s in states])}


def slice_shadow_state(state: dict, i: int) -> dict:
    """Extract request ``i`` from a composed shadow state (batch of 1)."""
    caches = tuple(jax.tree.map(lambda a: a[:, i:i + 1], c)
                   for c in state["caches"])
    return {"caches": caches, "pos": state["pos"][i:i + 1],
            "token": state["token"][i:i + 1]}


# ------------------------------------------------------- on-the-fly
class GateExtrapolator:
    """nextgate / multigate: apply future layers' routers to the current
    router input.  Called by the engine *during* the main decode."""

    def __init__(self, cfg: ModelConfig, routers: Dict[int, jax.Array],
                 lookahead: int = 1):
        self.cfg = cfg
        self.routers = routers          # {layer: (d, E)}
        self.lookahead = lookahead
        self.layers = sorted(routers)

    def predict_from(self, layer: int, router_input: jax.Array
                     ) -> Dict[int, np.ndarray]:
        """Predict the next ``lookahead`` MoE layers after ``layer``."""
        idx = self.layers.index(layer)
        preds = {}
        x = router_input.astype(jnp.float32)
        for nxt in self.layers[idx + 1: idx + 1 + self.lookahead]:
            logits = x @ self.routers[nxt].astype(jnp.float32)
            _, topk = jax.lax.top_k(logits, self.cfg.top_k)
            preds[nxt] = np.asarray(topk)
        return preds


class FrequencyPredictor:
    """EdgeMoE/fMoE-style statistics: per-layer expert popularity."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.counts: Dict[int, np.ndarray] = defaultdict(
            lambda: np.zeros(cfg.num_experts, np.int64))

    def observe(self, layer: int, true_topk: np.ndarray):
        for e in true_topk.reshape(-1):
            self.counts[layer][int(e)] += 1

    def predict(self, layer: int, batch: int) -> np.ndarray:
        top = np.argsort(-self.counts[layer])[: self.cfg.top_k]
        return np.tile(top, (batch, 1))


class RandomPredictor:
    """Ablation Case 5: prefetch uniformly random experts."""

    def __init__(self, cfg: ModelConfig, seed: int = 0):
        self.cfg = cfg
        self.rng = np.random.default_rng(seed)

    def predict(self, layer: int, batch: int) -> np.ndarray:
        return np.stack([
            self.rng.choice(self.cfg.num_experts, self.cfg.top_k,
                            replace=False)
            for _ in range(batch)])

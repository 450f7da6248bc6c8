"""Shadow-drafted speculative decoding (draft -> verify -> accept).

The SEP shadow is already a whole-model emulator decoding in lockstep —
promoting it to a *draft model* costs nothing new: ``shadow_rollout``
steps the functional shadow ``S`` times, collecting a draft token and a
per-layer expert prediction for each of the next ``S`` positions.  One
*verify wave* then runs all ``S`` positions through the full model at
once by folding them into the batch axis — row ``b*S + s`` carries
request ``b``'s draft position ``pos_b + s`` against its own copy of
the request's KV cache, seeded with the earlier draft rows' K/V — and
``accept_prefix`` keeps the longest prefix where the full model agrees
with the drafts.

Greedy acceptance makes the output *bit-identical to one-token-at-a-time
greedy decoding by construction*, not on average:

  * row ``b*S`` consumes the request's true last committed token, so
    its verified argmax IS the sequential next token;
  * row ``b*S + s`` equals the sequential step only if the draft tokens
    it consumed match the true continuation — exactly the prefix the
    accept rule keeps — so every committed token is the token the
    sequential loop would have produced;
  * per-row arithmetic is batch-independent (the same contract that
    lets the serving loop compose batches): attention reduces over the
    same cache window ``W`` whether one row or ``B*S`` ride the call,
    and expert FFNs flow through the shared ``grouped_topk_contrib`` /
    ``combine_topk`` fixed-rank-order primitives.

Speculation therefore changes WHEN tokens appear (fewer, wider waves —
the TPOT win), never WHICH tokens appear.  A rejected draft costs the
wasted rows' expert loads — the acceptance-rate/latency trade the
benchmarks measure (``benchmarks/spec_decode.py``).

The cache commit needs no rollback: row ``b*S + (c_b - 1)`` holds
exactly the slots of positions ``pos_b .. pos_b + c_b - 1`` (its own
write plus the seeds of the accepted earlier rows), so committing is a
row *selection*, and the discarded rows' writes never existed as far
as the request's cache is concerned.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.attention import (NEG_INF, _gqa_out, _gqa_scores,
                                    _project_qkv)
from repro.models.blocks import _apply_ff, residual
from repro.models.config import ModelConfig
from repro.models.layers import apply_norm, apply_rope
from repro.models.moe import route, shared_expert
from repro.models.transformer import at_rep


# ------------------------------------------------------------ verify wave
def spec_attn_decode(cfg: ModelConfig, params, x, cache, pos, S: int
                     ) -> Tuple[jax.Array, dict]:
    """Multi-position attention decode for a spec wave.

    ``x``: (B*S, 1, d) — rows grouped per request, row ``b*S + s`` at
    absolute position ``pos[b*S + s] = base_b + s``; ``cache`` is the
    per-row replicated KV (B*S, W, ...).  Every row writes its own slot
    (exactly ``attn_decode``), then each draft row's K/V is seeded into
    the LATER rows of the same request, so row ``s``'s cache holds
    precisely positions ``<= base_b + s`` — the state sequential decode
    would see.  Requires ``S <= W`` so the wave's slots are distinct
    (the engine guards this).
    """
    q, k, v = _project_qkv(cfg, params, x)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    w = cache["k"].shape[1]
    slot = pos % w
    r_idx = jnp.arange(x.shape[0])
    ck = cache["k"].at[r_idx, slot].set(k[:, 0])
    cv = cache["v"].at[r_idx, slot].set(v[:, 0])
    cp = cache["pos"].at[r_idx, slot].set(pos)
    if S > 1:
        b = x.shape[0] // S
        nk, hd = k.shape[2], k.shape[3]
        ck = ck.reshape(b, S, w, nk, hd)
        cv = cv.reshape(b, S, w, nk, hd)
        cp = cp.reshape(b, S, w)
        kr = k[:, 0].reshape(b, S, nk, hd)
        vr = v[:, 0].reshape(b, S, nk, hd)
        sl = slot.reshape(b, S)
        pr = pos.reshape(b, S)
        bi = jnp.arange(b)[:, None]
        for j in range(S - 1):
            rows = jnp.arange(j + 1, S)[None, :]     # rows after draft j
            sj = sl[:, j][:, None]
            ck = ck.at[bi, rows, sj].set(kr[:, j][:, None])
            cv = cv.at[bi, rows, sj].set(vr[:, j][:, None])
            cp = cp.at[bi, rows, sj].set(pr[:, j][:, None])
        ck = ck.reshape(b * S, w, nk, hd)
        cv = cv.reshape(b * S, w, nk, hd)
        cp = cp.reshape(b * S, w)
    cache = {"k": ck, "v": cv, "pos": cp}
    scores = _gqa_scores(cfg, q, cache["k"]).astype(jnp.float32)
    kp = cache["pos"][:, None, None, None, :]
    pq = pos[:, None, None, None, None]
    valid = (kp >= 0) & (kp <= pq)
    if cfg.sliding_window:
        valid = valid & (pq - kp < w)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return _gqa_out(cfg, probs, cache["v"], params), cache


# The per-layer jitted spec steps mirror the engine's ``_block_step`` /
# ``_mixer_router_step`` factories: lru-cached on (frozen config, layer
# kinds, wave width), a layer's parameters as its stack and repeat
# index, one dispatch per layer per wave.  ``S`` is part of the key
# because the seeding loop unrolls over it.

@functools.lru_cache(maxsize=None)
def _spec_block_step(cfg: ModelConfig, kinds, S: int) -> object:
    """Jitted non-MoE spec block: multi-position attention + dense/no
    FFN (rows are independent through the FFN, so ``_apply_ff`` is
    reused unchanged)."""
    def fn(stack, r, x, cache, pos):
        lp = at_rep(stack, r)
        h = apply_norm(cfg, x, lp["norm1"])
        out, cache = spec_attn_decode(cfg, lp["mixer"], h, cache, pos, S)
        x = residual(cfg, x, out)
        x, _ = _apply_ff(cfg, lp, kinds, x, "dense")
        return x, cache
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _spec_mixer_router_step(cfg: ModelConfig, kinds, S: int) -> object:
    """Jitted MoE-layer spec prefix: multi-position attention +
    residual, post-norm router input, and the top-k routing of ALL
    ``B*S`` wave rows in one dispatch.  The expert FFNs themselves run
    from worker slots via the engine's wave machinery, exactly as in
    one-token decode — a verify wave is just a (B*S)-row batch to it."""
    def fn(stack, r, x, cache, pos):
        lp = at_rep(stack, r)
        h = apply_norm(cfg, x, lp["norm1"])
        out, cache = spec_attn_decode(cfg, lp["mixer"], h, cache, pos, S)
        x = residual(cfg, x, out)
        hr = apply_norm(cfg, x, lp["norm2"])[:, 0]
        topk_idx, topk_gate, _ = route(cfg, lp["ff"], hr)
        return x, cache, hr, topk_idx, topk_gate, shared_expert(lp["ff"],
                                                                hr)
    return jax.jit(fn)


# ------------------------------------------------------------- acceptance
def accept_prefix(drafts, verified):
    """Greedy accept rule.  ``drafts``: (B, S) wave inputs (row 0 the
    true last token, rows 1.. the shadow's drafts); ``verified``:
    (B, S) the full model's argmax at each wave position.  Returns
    (B,) commit counts ``c`` in ``1..S``: position ``s`` is committable
    iff every earlier draft matched the model's output
    (``verified[:, s-1] == drafts[:, s]``), and the first token is
    always committed (row 0 consumed no draft).  The committed tokens
    are ``verified[:, :c]`` — bit-identical to sequential greedy decode
    by the prefix argument in the module docstring."""
    drafts = jnp.asarray(drafts)
    verified = jnp.asarray(verified)
    if drafts.shape[1] == 1:
        return jnp.ones((drafts.shape[0],), jnp.int32)
    ok = (verified[:, :-1] == drafts[:, 1:]).astype(jnp.int32)
    return 1 + jnp.cumprod(ok, axis=1).sum(axis=1).astype(jnp.int32)


def select_commit(spec_cache, c, S: int):
    """Select each request's accepted cache rows from a replicated
    (B*S, ...) wave cache: row ``b*S + (c_b - 1)`` -> (B, ...)."""
    c = jnp.asarray(c)
    idx = jnp.arange(c.shape[0]) * S + (c - 1)
    return jax.tree.map(lambda a: a[idx], spec_cache)


# ---------------------------------------------------------------- drafting
def shadow_rollout(shadow, state: dict, first_token, S: int
                   ) -> Tuple[jax.Array, List[Dict[int, np.ndarray]],
                              List[dict]]:
    """Roll the functional shadow ``S`` steps ahead of the main model.

    ``state`` is a functional shadow state (``{"caches", "pos",
    "token"}``); ``first_token`` is what the shadow consumes first (the
    main model's last token when token-aligned, else the shadow's own).
    Returns ``(draft_tokens (B, S-1), preds_steps, states)`` where
    ``preds_steps[s]`` maps layer -> (B, k) predicted experts for wave
    position ``s`` and ``states[s]`` is the shadow state after
    consuming ``s + 1`` tokens (``states[c-1]`` is the rollback target
    after committing ``c`` — the shadow then consumed exactly the
    accepted tokens, so rejection never leaves drafted junk in its
    KV)."""
    preds_steps: List[Dict[int, np.ndarray]] = []
    states: List[dict] = []
    drafts = []
    tok = first_token
    st = state
    for s in range(S):
        preds, st = shadow.step_state(st, tok)
        preds_steps.append(preds)
        states.append(st)
        tok = st["token"]              # the shadow's greedy continuation
        if s + 1 < S:
            drafts.append(tok)
    draft_tokens = (jnp.stack(drafts, axis=1) if drafts
                    else jnp.zeros((first_token.shape[0], 0), jnp.int32))
    return draft_tokens, preds_steps, states


def wave_preds(preds_steps: List[Dict[int, np.ndarray]]
               ) -> Dict[int, np.ndarray]:
    """Fold per-step predictions into wave-row order: {layer ->
    (B*S, k)} with row ``b*S + s`` = request ``b``, wave position
    ``s`` — the layout ``decode_batch_spec`` routes in."""
    S = len(preds_steps)
    out: Dict[int, np.ndarray] = {}
    for li in preds_steps[0]:
        per_step = [np.asarray(preds_steps[s][li]) for s in range(S)]
        stacked = np.stack(per_step, axis=1)          # (B, S, k)
        out[li] = stacked.reshape(-1, stacked.shape[-1])
    return out

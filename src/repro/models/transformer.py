"""Decoder-only LM assembled from blocks, executed as scan-over-pattern.

Layers are grouped into the smallest repeating pattern (period P) and the
stack of repeats (R = L / P).  Parameters for each pattern position are
stacked along a leading R axis and the model runs as ``lax.scan`` over R
with the P heterogeneous blocks unrolled inside the body.  The lowered
HLO therefore contains P layer bodies instead of L — this is what keeps
the 512-device dry-run compiles tractable (llama3's 32 identical layers
lower as a single scanned body; jamba's 64 layers as an 8-layer body
scanned 8 times).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.quant.quantize import QWeight, materialize

from .blocks import block_decode, block_seq, init_block, init_block_cache
from .config import ModelConfig
from .layers import apply_norm, embed, init_embedding, init_norm, unembed
from .layers import _dense_init


# --------------------------------------------------------------------- init
def init_lm(key, cfg: ModelConfig) -> dict:
    pattern, reps = cfg.pattern()
    dt = jnp.dtype(cfg.dtype)
    k_embed, k_head, k_proj, k_layers = jax.random.split(key, 4)
    params = {"embed": init_embedding(k_embed, cfg.vocab_size, cfg.d_model, dt),
              "final_norm": init_norm(cfg.d_model, dt)}
    if not cfg.tie_embeddings:
        params["head"] = {"w": _dense_init(k_head, (cfg.d_model, cfg.vocab_size), dt)}
    if cfg.frontend:
        fd = cfg.frontend_dim or cfg.d_model
        params["frontend_proj"] = {
            "w": _dense_init(k_proj, (fd, cfg.d_model), dt),
            "b": jnp.zeros((cfg.d_model,), dt)}
    layer_keys = jax.random.split(k_layers, len(pattern) * reps)
    stacked = []
    for i, kinds in enumerate(pattern):
        per_rep = [init_block(layer_keys[i * reps + r], cfg, kinds)
                   for r in range(reps)]
        stacked.append(jax.tree.map(lambda *xs: jnp.stack(xs), *per_rep))
    params["layers"] = tuple(stacked)
    return params


def init_caches(cfg: ModelConfig, batch: int, max_len: int, dtype) -> Tuple:
    """Per-pattern-position caches stacked over repeats."""
    pattern, reps = cfg.pattern()
    out = []
    for kinds in pattern:
        c = init_block_cache(cfg, kinds, batch, max_len, dtype)
        out.append(jax.tree.map(
            lambda a: jnp.broadcast_to(a, (reps,) + a.shape), c))
    return tuple(out)


def layer_weights(lp, h):
    """``(lp, h)`` with the shadow's codes in ``lp`` dequantized, once
    the layer's input ``h`` exists: the barrier keeps XLA from
    dequantizing every layer's weights at the start of the program."""
    if not any(isinstance(w, QWeight) for w in jax.tree.leaves(
            lp, is_leaf=lambda w: isinstance(w, QWeight))):
        return lp, h
    lp, h = jax.lax.optimization_barrier((lp, h))
    return materialize(lp), h


def at_rep(stack, r):
    """Repeat ``r`` of a pattern position's stacked parameters: one
    layer's, sliced inside a jitted step so that no per-layer copy of
    the weights is kept beside the stack."""
    return jax.tree.map(lambda a: a[r], stack)


def layer_params(cfg: ModelConfig, params, layer_idx: int):
    """Unstacked parameters of a single layer (used by the OD-MoE engine)."""
    pattern, reps = cfg.pattern()
    pos, rep = layer_idx % len(pattern), layer_idx // len(pattern)
    return jax.tree.map(lambda a: a[rep], params["layers"][pos])


# ------------------------------------------------------------------ embeds
def embed_tokens(cfg: ModelConfig, params, tokens):
    """Token embeddings, scaled by the embedding multiplier where the
    configuration has one."""
    x = embed(tokens, params["embed"])
    em = cfg.embedding_multiplier
    return x if em == 1.0 else x * em


def input_embeddings(cfg: ModelConfig, params, tokens,
                     frontend_embeds: Optional[jax.Array] = None):
    """Token embeddings, with projected modality embeddings prepended."""
    x = embed_tokens(cfg, params, tokens)
    n_front = 0
    if cfg.frontend and frontend_embeds is not None:
        proj = params["frontend_proj"]
        fx = frontend_embeds @ proj["w"] + proj["b"]
        x = jnp.concatenate([fx.astype(x.dtype), x], axis=1)
        n_front = frontend_embeds.shape[1]
    return x, n_front


def logits_from_hidden(cfg: ModelConfig, params, x):
    x = apply_norm(cfg, x, params["final_norm"])
    if cfg.tie_embeddings:
        logits = unembed(x, params["embed"])
    else:
        logits = x @ materialize(params["head"]["w"])
    ls = cfg.logits_scaling
    return logits if ls == 1.0 else logits / ls


# ---------------------------------------------------------------- sequence
def lm_seq(cfg: ModelConfig, params, tokens, *,
           frontend_embeds: Optional[jax.Array] = None,
           make_cache: bool = False, max_cache_len: int = 0,
           moe_method: str = "scatter", remat: bool = False,
           layer_constraint=None, residual_constraint=None):
    """Full-sequence forward (train / prefill).

    Returns (logits, aux, caches).  ``aux["topk"]`` is a tuple per MoE
    pattern position of (R, B, T, k) router decisions; ``caches`` is the
    stacked KV/SSM state when ``make_cache``.  ``remat`` checkpoints the
    scan body (training: per-layer activation rematerialization).
    ``layer_constraint`` (optional) resharsd the per-layer parameter
    slice inside the scan body — the FSDP just-in-time weight unshard:
    without it GSPMD may all-reduce full activations over the data axis
    instead of all-gathering the (much smaller) layer weights.
    """
    pattern, reps = cfg.pattern()
    x, n_front = input_embeddings(cfg, params, tokens, frontend_embeds)
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def body(carry, slices):
        h = carry
        if layer_constraint is not None:
            slices = layer_constraint(slices)
        if residual_constraint is not None:
            # sequence-parallel residual stream: the inter-block
            # activations shard over (data, model-on-seq); GSPMD then
            # lowers the TP boundary as reduce-scatter + all-gather
            h = residual_constraint(h)
        auxs, caches = [], []
        for i, kinds in enumerate(pattern):
            lp, h = layer_weights(slices[i], h)
            h, aux, cache = block_seq(
                cfg, lp, kinds, h, positions,
                moe_method=moe_method, make_cache=make_cache,
                max_cache_len=max_cache_len)
            auxs.append(aux)
            caches.append(cache if make_cache else 0)
        return h, (tuple(auxs), tuple(caches))

    if remat:
        # save the tagged TP-boundary outputs: backward then reuses the
        # forward all-reduce results instead of recomputing them
        policy = jax.checkpoint_policies.save_only_these_names("tp_out")
        scan_body = jax.checkpoint(body, policy=policy)
    else:
        scan_body = body
    x, (auxs, caches) = jax.lax.scan(scan_body, x, params["layers"])
    logits = logits_from_hidden(cfg, params, x)
    lb = sum(jnp.sum(a["load_balance_loss"]) for a in auxs
             if "load_balance_loss" in a)
    aux = {"load_balance_loss": lb,
           "topk": tuple(a["topk_idx"] for a in auxs if "topk_idx" in a),
           "n_front": n_front}
    return logits, aux, (caches if make_cache else None)


# ------------------------------------------------------------------ decode
def lm_decode(cfg: ModelConfig, params, token, caches, pos, *,
              moe_method: str = "dense"):
    """One-token decode.  token: (B,) int32; pos: (B,) absolute position.

    Returns (logits (B,V), new_caches, aux).

    The stacked caches ride in the scan CARRY and are updated with
    per-repeat dynamic slices: streaming them through xs/ys double-
    buffers the entire KV cache in temp memory (measured ~2x cache
    bytes per device on every decode shape; EXPERIMENTS.md §Perf 9).
    """
    pattern, reps = cfg.pattern()
    x = embed_tokens(cfg, params, token[:, None])

    def body(carry, lp):
        h, lc_all, r = carry
        lc = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(
            a, r, axis=0, keepdims=False), lc_all)
        new_caches, auxs = [], []
        for i, kinds in enumerate(pattern):
            lpi, h = layer_weights(lp[i], h)
            h, c, aux = block_decode(cfg, lpi, kinds, h, lc[i], pos,
                                     moe_method=moe_method)
            new_caches.append(c)
            auxs.append(aux)
        lc_all = jax.tree.map(
            lambda a, u: jax.lax.dynamic_update_index_in_dim(
                a, u.astype(a.dtype), r, axis=0),
            lc_all, tuple(new_caches))
        return (h, lc_all, r + 1), tuple(auxs)

    (x, new_caches, _), auxs = jax.lax.scan(
        body, (x, caches, jnp.int32(0)), params["layers"])
    logits = logits_from_hidden(cfg, params, x)[:, 0]
    aux = {"topk": tuple(a["topk_idx"] for a in auxs if "topk_idx" in a)}
    return logits, new_caches, aux

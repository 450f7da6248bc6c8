"""Unified model API — family dispatch for init / loss / prefill / decode.

Every architecture (dense / moe / ssm / hybrid / vlm / audio) is driven
through the same four functions, which is what lets configs, the
launcher, the OD-MoE engine and the dry-run treat the model zoo
uniformly:

    params              = init_params(cfg, key)
    loss, metrics       = loss_fn(cfg, params, batch)
    logits, state       = prefill(cfg, params, batch, max_cache_len)
    logits, state       = decode_step(cfg, params, token, state, pos)

``state`` bundles caches (KV / SSM / cross-memories) as one pytree.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import encdec as encdec_lib
from . import transformer as tf_lib
from .config import ATTN, ModelConfig


def init_params(cfg: ModelConfig, key) -> dict:
    if cfg.is_encoder_decoder:
        return encdec_lib.init_encdec(key, cfg)
    return tf_lib.init_lm(key, cfg)


# -------------------------------------------------------------------- train
def loss_fn(cfg: ModelConfig, params, batch, moe_method: str = "scatter",
            remat: bool = False, layer_constraint=None,
            residual_constraint=None) -> Tuple[jax.Array, dict]:
    """Next-token cross-entropy (+ MoE load-balance aux).

    batch: {"tokens": (B,T) int32, "loss_mask": (B,T) optional,
            "frontend_embeds": (B,N,fd) for vlm/audio}.
    """
    tokens = batch["tokens"]
    if cfg.is_encoder_decoder:
        logits, aux = encdec_lib.encdec_seq(
            cfg, params, batch["frontend_embeds"], tokens, remat=remat,
            layer_constraint=layer_constraint)
        n_front = 0
    else:
        logits, aux, _ = tf_lib.lm_seq(
            cfg, params, tokens,
            frontend_embeds=batch.get("frontend_embeds"),
            moe_method=moe_method, remat=remat,
            layer_constraint=layer_constraint,
            residual_constraint=residual_constraint)
        n_front = aux["n_front"]
        logits = logits[:, n_front:]
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = batch.get("loss_mask")
    mask = jnp.ones_like(nll) if mask is None else mask[:, 1:].astype(nll.dtype)
    ce = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    lb = aux.get("load_balance_loss", 0.0)
    loss = ce + cfg.router_aux_weight * lb
    return loss, {"ce": ce, "load_balance_loss": lb, "loss": loss}


# ------------------------------------------------------------------ serving
# Prefill used to trace ``lm_seq`` eagerly per prompt length, so every
# admission with a new length stalled the serving loop on a fresh
# compile.  The bucketed path below pads the prompt to the next
# power-of-two bucket and runs ONE jitted executable per (config,
# batch, bucket, window) — the true length rides in as a traced
# argument, the last REAL token's logits are selected inside the jit,
# and the pad slots' cache entries are invalidated to ``pos = -1``
# (exactly what an untouched dense-buffer slot holds, so decode's
# validity mask treats them as empty).  Padding the time axis is
# bit-exact on this backend: masked scores hit ``exp(NEG_INF - m) = 0``
# exactly, so the extra softmax terms contribute literal zeros
# (pinned by tests/test_prefill_bucket.py).
from .attention import seq_bucket as _prefill_bucket  # shared pow2 grid


@functools.lru_cache(maxsize=None)
def _bucketed_prefill_step(cfg: ModelConfig, batch_size: int, bucket: int,
                           max_cache_len: int, moe_method: str):
    """One compiled prefill per (config, batch, length-bucket, window).

    ``cache_info()`` on this factory counts compiles: every shape that
    determines the executable is part of the key, so misses == XLA
    compilations (tests pin the count flat across repeated serves)."""
    def fn(params, tokens_padded, true_len):
        logits, aux, caches = tf_lib.lm_seq(
            cfg, params, tokens_padded, make_cache=True,
            max_cache_len=max_cache_len, moe_method=moe_method)
        last = jnp.take_along_axis(
            logits, (true_len - 1)[:, None, None], axis=1)[:, 0]
        # stacked cache pos lanes are (R, B, W); pad slots hold stored
        # positions >= the true length — mark them empty
        fixed = tuple(
            dict(c, pos=jnp.where(c["pos"] >= true_len[None, :, None], -1,
                                  c["pos"]))
            for c in caches)
        return last, fixed
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _exact_prefill_step(cfg: ModelConfig, max_cache_len: int,
                        moe_method: str):
    """One compiled prefill per (config, window, dispatch) and prompt
    shape, for decoder-only models the bucketed path cannot pad (an SSM
    scan would take the pad tokens into its state)."""
    def fn(params, tokens):
        logits, _, caches = tf_lib.lm_seq(
            cfg, params, tokens, make_cache=True,
            max_cache_len=max_cache_len, moe_method=moe_method)
        return logits[:, -1], caches
    return jax.jit(fn)


def prefill_cache_info():
    """Compile-cache statistics of the bucketed prefill (misses ==
    compiled executables) — the serving loop's no-per-prompt-recompile
    guarantee is asserted through this."""
    return _bucketed_prefill_step.cache_info()


def _bucketed_prefill_ok(cfg: ModelConfig, batch, bucket: int,
                         max_cache_len: int) -> bool:
    """The padded path is gated to shapes where padding is provably
    inert: decoder-only, token-only input, every mixer an attention
    layer (an SSM scan would absorb the pad tokens into its state), the
    bucket within the cache window, and no sliding window narrower than
    the bucket (``seed_cache`` keeps the LAST ``window`` positions,
    which would be pads)."""
    if cfg.is_encoder_decoder or batch.get("frontend_embeds") is not None:
        return False
    if any(mixer != ATTN for mixer, _ in cfg.layer_kinds()):
        return False
    if bucket > max_cache_len:
        return False
    if cfg.sliding_window and cfg.sliding_window < bucket:
        return False
    return True


def prefill(cfg: ModelConfig, params, batch, max_cache_len: int,
            moe_method: str = "scatter"):
    """Process the prompt; return (last-token logits, decode state).

    Decoder-only all-attention models take the bucketed jit path (see
    above), other decoder-only models one jitted program per prompt
    length, and models with a frontend the eager per-length trace.
    Both produce bit-identical logits and caches, so callers — the
    reference decoder, the engine, the SEP shadow — never observe which
    path ran."""
    tokens = batch["tokens"]
    if not cfg.is_encoder_decoder:
        b, t = tokens.shape
        bucket = _prefill_bucket(t)
        if _bucketed_prefill_ok(cfg, batch, bucket, max_cache_len):
            padded = jnp.pad(tokens, ((0, 0), (0, bucket - t)))
            true_len = jnp.full((b,), t, jnp.int32)
            logits, caches = _bucketed_prefill_step(
                cfg, b, bucket, max_cache_len, moe_method)(
                    params, padded, true_len)
            return logits, {"caches": caches,
                            "pos": jnp.full((b,), t, jnp.int32)}
    if cfg.is_encoder_decoder:
        enc_out = encdec_lib.encode(cfg, params, batch["frontend_embeds"])
        memories = encdec_lib.build_memories(cfg, params, enc_out)
        b = tokens.shape[0]
        # run the decoder prefix through in one pass and seed the caches
        logits, aux, caches = tf_like_prefill_encdec(
            cfg, params, tokens, memories, max_cache_len)
        state = {"caches": caches, "memories": memories,
                 "pos": jnp.full((b,), tokens.shape[1], jnp.int32)}
        return logits, state
    b, t = tokens.shape
    if batch.get("frontend_embeds") is None:
        logits, caches = _exact_prefill_step(cfg, max_cache_len,
                                             moe_method)(params, tokens)
        return logits, {"caches": caches,
                        "pos": jnp.full((b,), t, jnp.int32)}
    logits, aux, caches = tf_lib.lm_seq(
        cfg, params, tokens, frontend_embeds=batch.get("frontend_embeds"),
        make_cache=True, max_cache_len=max_cache_len, moe_method=moe_method)
    n_front = aux["n_front"]
    state = {"caches": caches,
             "pos": jnp.full((b,), t + n_front, jnp.int32)}
    return logits[:, -1], state


def tf_like_prefill_encdec(cfg, params, tokens, memories, max_cache_len):
    """Decoder-side prefill for enc-dec: full pass + cache seeding."""
    from .blocks import block_seq
    from .layers import embed as _embed
    pattern, _ = cfg.pattern()
    x = _embed(tokens, params["embed"])
    b, t, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def body(h, slices):
        lp, mem = slices
        caches = []
        for i, kinds in enumerate(pattern):
            h, _, cache = block_seq(cfg, lp[i], kinds, h, positions,
                                    causal=True, memory=mem[i],
                                    make_cache=True,
                                    max_cache_len=max_cache_len)
            caches.append(cache)
        return h, tuple(caches)

    x, caches = jax.lax.scan(body, x, (params["layers"], memories))
    return tf_lib.logits_from_hidden(cfg, params, x)[:, -1], {}, caches


def decode_step(cfg: ModelConfig, params, token, state, *,
                moe_method: str = "grouped"):
    """One greedy-decode step.  token: (B,) int32.  MoE layers default
    to the ``grouped`` dispatch — the jit-grouped top-k hot path shared
    with the OD-MoE engine's wave compute (see ``models/moe.py``)."""
    pos = state["pos"]
    if cfg.is_encoder_decoder:
        logits, caches = encdec_lib.encdec_decode(
            cfg, params, token, state["caches"], state["memories"], pos)
        new_state = dict(state, caches=caches, pos=pos + 1)
        return logits, new_state
    logits, caches, aux = tf_lib.lm_decode(
        cfg, params, token, state["caches"], pos, moe_method=moe_method)
    new_state = dict(state, caches=caches, pos=pos + 1)
    return logits, new_state


@functools.lru_cache(maxsize=None)
def _jit_decode_step(cfg: ModelConfig, moe_method: str):
    """One compiled decode step per (config, dispatch).  Called eagerly,
    ``lm_decode`` re-traces its layer scan on every token, and each
    trace is a fresh XLA compile."""
    return jax.jit(functools.partial(decode_step, cfg,
                                     moe_method=moe_method))


def greedy_generate(cfg: ModelConfig, params, batch, num_tokens: int,
                    max_cache_len: int = 0, moe_method: str = "grouped",
                    transport=None):
    """Reference autoregressive generation (prefill + decode loop).

    MoE layers run the ``grouped`` dispatch — the same jitted top-k
    expert-FFN primitive (``repro.kernels.moe_gemm``) the OD-MoE engine
    consumes from worker slots, with the same fixed rank-order
    accumulation — so the engine ≡ reference invariant is a shared
    arithmetic contract, not a coincidence of loop order.

    ``transport`` (a ``repro.quant`` ``PrecisionPolicy`` or scheme
    name) makes this the reference for mixed-precision expert
    transport: expert weights are round-tripped through the SAME codec
    the OD-MoE store ships over worker links, so every engine decode
    path must match this output token-bit-exactly *under the same
    transport policy*.
    """
    if transport is not None:
        from repro.quant.transport import transport_params
        params = transport_params(cfg, params, transport)
    max_cache_len = max_cache_len or (batch["tokens"].shape[1] + num_tokens)
    logits, state = prefill(cfg, params, batch, max_cache_len,
                            moe_method=moe_method)
    token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    out = [token]
    step = _jit_decode_step(cfg, moe_method)
    for _ in range(num_tokens - 1):
        logits, state = step(params, token, state)
        token = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(token)
    return jnp.stack(out, axis=1)


"""Grouped-query attention with KV caching.

Three entry points:
  * ``attn_seq``    — full-sequence attention (train / prefill / encoder).
  * ``attn_decode`` — single-token decode against a (ring-buffer) KV cache.
  * ``cross_attn_*`` — encoder-decoder cross attention over a fixed memory.

KV cache layout (per layer): ``{"k","v": (B, W, n_kv, hd), "pos": (B, W)}``
where ``W`` is ``sliding_window`` if set, else the max sequence length, and
``pos`` holds the absolute position stored in each slot (-1 = empty).  Keys
are stored *post-RoPE* so decode never re-rotates the cache; a ring buffer
then makes sliding-window decode O(W) in both compute and memory, which is
what lets dense architectures run the ``long_500k`` shape sub-quadratically.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig
from .layers import _dense_init, apply_rope

NEG_INF = -1e30

SEQ_BUCKET_MIN = 8


def seq_bucket(n: int) -> int:
    """Smallest power-of-two >= n (floored at ``SEQ_BUCKET_MIN``) — the
    shared length-bucket grid of full-seq attention and the jitted
    prefill (``models/api.py``)."""
    b = SEQ_BUCKET_MIN
    while b < n:
        b *= 2
    return b


# --------------------------------------------------------------------- init
def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    kq, kk, kv, ko = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(kq, (d, cfg.num_heads * hd), cfg_dtype(cfg)),
        "wk": _dense_init(kk, (d, cfg.num_kv_heads * hd), cfg_dtype(cfg)),
        "wv": _dense_init(kv, (d, cfg.num_kv_heads * hd), cfg_dtype(cfg)),
        "wo": _dense_init(ko, (cfg.num_heads * hd, d), cfg_dtype(cfg)),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((cfg.num_heads * hd,), cfg_dtype(cfg))
        p["bk"] = jnp.zeros((cfg.num_kv_heads * hd,), cfg_dtype(cfg))
        p["bv"] = jnp.zeros((cfg.num_kv_heads * hd,), cfg_dtype(cfg))
    return p


def cfg_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    w = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, w, cfg.num_kv_heads, hd), dtype),
        "v": jnp.zeros((batch, w, cfg.num_kv_heads, hd), dtype),
        "pos": jnp.full((batch, w), -1, jnp.int32),
    }


# ------------------------------------------------------------------ helpers
def _project_qkv(cfg: ModelConfig, params, x):
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    q = x @ params["wq"]
    k = x @ params["wk"]
    v = x @ params["wv"]
    if "bq" in params:
        q, k, v = q + params["bq"], k + params["bk"], v + params["bv"]
    q = q.reshape(b, t, cfg.num_heads, hd)
    k = k.reshape(b, t, cfg.num_kv_heads, hd)
    v = v.reshape(b, t, cfg.num_kv_heads, hd)
    return q, k, v


def _gqa_scores(cfg: ModelConfig, q, k):
    """q: (B,T,H,hd)  k: (B,S,K,hd)  ->  (B,K,G,T,S) with H = K*G."""
    b, t, h, hd = q.shape
    g = h // cfg.num_kv_heads
    qg = q.reshape(b, t, cfg.num_kv_heads, g, hd)
    s = jnp.einsum("btkgh,bskh->bkgts", qg, k)
    if cfg.attention_multiplier:
        s = s * jnp.asarray(cfg.attention_multiplier, q.dtype)
    else:
        s = s / jnp.sqrt(hd).astype(q.dtype)
    if cfg.logit_soft_cap:
        s = cfg.logit_soft_cap * jnp.tanh(s / cfg.logit_soft_cap)
    return s


def _gqa_out(cfg: ModelConfig, probs, v, params):
    b, k, g, t, s = probs.shape
    o = jnp.einsum("bkgts,bskh->btkgh", probs, v)
    o = o.reshape(b, t, k * g * v.shape[-1])
    return o @ params["wo"]


# ---------------------------------------------------------------- full-seq
BLOCKWISE_THRESHOLD = 2048   # switch to online-softmax blocks beyond this


def attn_seq(cfg: ModelConfig, params, x, positions, *, causal: bool = True,
             window: int = 0) -> jax.Array:
    """Full-sequence attention (train / prefill / encoder self-attn).

    Sequences past ``BLOCKWISE_THRESHOLD`` use the memory-efficient
    blockwise path so the T x S score matrix is never materialized
    (flash-attention recurrence in pure JAX; the Pallas kernel mirrors
    this structure on TPU).
    """
    if x.shape[1] > BLOCKWISE_THRESHOLD:
        return attn_seq_blockwise(cfg, params, x, positions, causal=causal,
                                  window=window)
    q, k, v = _project_qkv(cfg, params, x)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    scores = _gqa_scores(cfg, q, k).astype(jnp.float32)
    qi = positions[:, None, None, :, None]
    kj = positions[:, None, None, None, :]
    mask = jnp.ones(scores.shape[-2:], bool)[None, None, None]
    if causal:
        mask = mask & (kj <= qi)
    if window:
        mask = mask & (qi - kj < window)
    scores = jnp.where(mask, scores, NEG_INF)
    # Pin the softmax reduction length: pad the key axis to the pow2
    # bucket the jitted prefill pads prompts to.  XLA's reduction tree
    # depends on the axis LENGTH even when the extra terms are exact
    # zeros, so without this an exact-length prompt and its bucket-
    # padded twin disagree in the last float bits; with it the summation
    # runs over identical shapes and identical values for every real
    # query row, and padded-vs-unpadded bit-exactness holds by
    # construction (tests/test_prefill_bucket.py).
    s_len = scores.shape[-1]
    s_pad = seq_bucket(s_len) - s_len
    if s_pad:
        scores = jnp.pad(scores, ((0, 0),) * 4 + ((0, s_pad),),
                         constant_values=NEG_INF)
        v = jnp.pad(v, ((0, 0), (0, s_pad), (0, 0), (0, 0)))
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return _gqa_out(cfg, probs, v, params)


def attn_seq_blockwise(cfg: ModelConfig, params, x, positions, *,
                       causal: bool = True, window: int = 0,
                       q_block: int = 512, kv_block: int = 512) -> jax.Array:
    """Online-softmax blockwise attention — O(T) activation memory.

    Outer ``lax.scan`` over query blocks, inner scan over KV blocks with
    the (m, l, acc) flash recurrence.  Fully-masked KV blocks still
    execute (static trip counts); skipping them is a recorded §Perf
    optimization, not a correctness issue.
    """
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    kv = cfg.num_kv_heads
    g = cfg.num_heads // kv
    q, k, v = _project_qkv(cfg, params, x)
    q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    qb = min(q_block, t)
    kb = min(kv_block, t)
    pad_q = (-t) % qb
    pad_k = (-t) % kb
    P_INVALID = jnp.int32(-2 ** 30)
    qpos = jnp.pad(positions, ((0, 0), (0, pad_q)),
                   constant_values=P_INVALID)
    kpos = jnp.pad(positions, ((0, 0), (0, pad_k)),
                   constant_values=P_INVALID)
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    nq, nk = (t + pad_q) // qb, (t + pad_k) // kb
    # (nq, B, qb, kv, g, hd) query blocks / (nk, B, kb, kv, hd) kv blocks
    qblocks = jnp.moveaxis(qp.reshape(b, nq, qb, kv, g, hd), 1, 0)
    if cfg.attention_multiplier:
        qblocks = qblocks * jnp.asarray(cfg.attention_multiplier, q.dtype)
    else:
        qblocks = qblocks / jnp.sqrt(hd).astype(q.dtype)
    kblocks = jnp.moveaxis(kp.reshape(b, nk, kb, kv, hd), 1, 0)
    vblocks = jnp.moveaxis(vp.reshape(b, nk, kb, kv, hd), 1, 0)
    qpos_b = jnp.moveaxis(qpos.reshape(b, nq, qb), 1, 0)
    kpos_b = jnp.moveaxis(kpos.reshape(b, nk, kb), 1, 0)

    def q_step(_, q_in):
        qi, qpi = q_in                       # (B,qb,kv,g,hd), (B,qb)

        def kv_step(carry, kv_in):
            m, l, acc = carry
            ki, vi, kpi = kv_in
            s = jnp.einsum("bqkgh,bskh->bkgqs", qi, ki).astype(jnp.float32)
            if cfg.logit_soft_cap:
                s = cfg.logit_soft_cap * jnp.tanh(s / cfg.logit_soft_cap)
            qv = qpi[:, None, None, :, None]
            kv_ = kpi[:, None, None, None, :]
            mask = (kv_ > P_INVALID) & (qv > P_INVALID)
            if causal:
                mask = mask & (kv_ <= qv)
            if window:
                mask = mask & (qv - kv_ < window)
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqs,bskh->bkgqh", p.astype(vi.dtype), vi
            ).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, kv, g, qb), NEG_INF, jnp.float32),
                jnp.zeros((b, kv, g, qb), jnp.float32),
                jnp.zeros((b, kv, g, qb, hd), jnp.float32))
        (m, l, acc), _ = jax.lax.scan(kv_step, init,
                                      (kblocks, vblocks, kpos_b))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(x.dtype)      # (B,kv,g,qb,hd)

    _, outs = jax.lax.scan(q_step, None, (qblocks, qpos_b))
    # (nq,B,kv,g,qb,hd) -> (B,T,kv*g*hd)
    o = jnp.moveaxis(outs, 0, 3)              # (B,kv,g,nq,qb,hd)
    o = o.reshape(b, kv, g, nq * qb, hd)[:, :, :, :t]
    o = jnp.moveaxis(o, 3, 1).reshape(b, t, kv * g * hd)
    return o @ params["wo"]


def seed_cache(cfg: ModelConfig, params, x, positions, max_len: int) -> dict:
    """Build a KV cache from a processed prompt (engine prefill->decode)."""
    b, t, _ = x.shape
    _, k, v = _project_qkv(cfg, params, x)
    k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    cache = init_cache(cfg, b, max_len, x.dtype)
    w = cache["k"].shape[1]
    take = min(t, w)
    slots = (positions[:, -take:] % w)
    cache = {
        "k": _scatter_slots(cache["k"], slots, k[:, -take:]),
        "v": _scatter_slots(cache["v"], slots, v[:, -take:]),
        "pos": _scatter_slots(cache["pos"], slots, positions[:, -take:]),
    }
    return cache


def _scatter_slots(buf, slots, vals):
    """buf: (B, W, ...), slots: (B, T), vals: (B, T, ...)."""
    b_idx = jnp.arange(buf.shape[0])[:, None]
    return buf.at[b_idx, slots].set(vals)


# ------------------------------------------------------------------- decode
def attn_decode(cfg: ModelConfig, params, x, cache, pos) -> Tuple[jax.Array, dict]:
    """One-token decode.  x: (B,1,d); pos: (B,) absolute position."""
    q, k, v = _project_qkv(cfg, params, x)
    q = apply_rope(q, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    k = apply_rope(k, pos[:, None], cfg.rope_theta, cfg.rope_fraction)
    w = cache["k"].shape[1]
    slot = (pos % w)
    b_idx = jnp.arange(x.shape[0])
    cache = {
        "k": cache["k"].at[b_idx, slot].set(k[:, 0]),
        "v": cache["v"].at[b_idx, slot].set(v[:, 0]),
        "pos": cache["pos"].at[b_idx, slot].set(pos),
    }
    scores = _gqa_scores(cfg, q, cache["k"]).astype(jnp.float32)  # (B,K,G,1,W)
    kp = cache["pos"][:, None, None, None, :]
    valid = (kp >= 0) & (kp <= pos[:, None, None, None, None])
    if cfg.sliding_window:
        valid = valid & (pos[:, None, None, None, None] - kp < w)
    scores = jnp.where(valid, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return _gqa_out(cfg, probs, cache["v"], params), cache


# -------------------------------------------------------------- cross-attn
def cross_attn_memory(cfg: ModelConfig, params, enc_out) -> dict:
    """Precompute K/V over encoder output once per request."""
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ params["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (enc_out @ params["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    return {"k": k, "v": v}


def cross_attn(cfg: ModelConfig, params, x, memory, memory_mask=None) -> jax.Array:
    """x: (B,T,d) attends over memory K/V (no RoPE, no causal mask)."""
    b, t, _ = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ params["wq"]).reshape(b, t, cfg.num_heads, hd)
    scores = _gqa_scores(cfg, q, memory["k"]).astype(jnp.float32)
    if memory_mask is not None:
        scores = jnp.where(memory_mask[:, None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    return _gqa_out(cfg, probs, memory["v"], params)

"""Primitive layers: norms, rotary embeddings, SwiGLU MLP, embedding tables.

Everything is pure-functional: ``init_*`` returns a pytree of parameters,
the matching apply function consumes it.  Parameter trees are plain dicts
so they stack cleanly along a leading axis for ``lax.scan`` over layers.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.quant.quantize import QWeight, materialize

from .config import ModelConfig


# --------------------------------------------------------------------- init
def _dense_init(key, shape, dtype, scale: Optional[float] = None):
    fan_in = shape[0]
    scale = scale if scale is not None else fan_in ** -0.5
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_norm(d: int, dtype) -> dict:
    return {"scale": jnp.ones((d,), dtype)}


def init_embedding(key, vocab: int, d: int, dtype) -> dict:
    return {"table": _dense_init(key, (vocab, d), dtype, scale=1.0)}


def init_mlp(key, d: int, d_ff: int, dtype) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": _dense_init(k1, (d, d_ff), dtype),
        "w_up": _dense_init(k2, (d, d_ff), dtype),
        "w_down": _dense_init(k3, (d_ff, d), dtype),
    }


# -------------------------------------------------------------------- apply
def rms_norm(x, params, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(dt) * params["scale"]


def layer_norm(x, params, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    return ((x32 - mu) * jax.lax.rsqrt(var + eps)).astype(dt) * params["scale"]


def apply_norm(cfg: ModelConfig, x, params):
    if cfg.norm_type == "layernorm":
        return layer_norm(x, params, cfg.norm_eps)
    return rms_norm(x, params, cfg.norm_eps)


def swiglu_mlp(x, params):
    h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    return h @ params["w_down"]


def embed(tokens, params):
    table = params["table"]
    if isinstance(table, QWeight):          # the shadow's codes: the rows only
        return table.take(tokens)
    return jnp.take(table, tokens, axis=0)


def unembed(x, params):
    table = params["table"]
    if isinstance(table, QWeight):          # dequantized once x exists
        table, x = jax.lax.optimization_barrier((table, x))
    return x @ materialize(table).T


# ------------------------------------------------------------------- rotary
def rope_frequencies(head_dim: int, theta: float, fraction: float = 1.0):
    """Inverse frequencies for the rotated sub-dimension."""
    rot = int(head_dim * fraction)
    rot -= rot % 2
    return 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)), rot


def apply_rope(x, positions, theta: float, fraction: float = 1.0):
    """Rotary embedding on the last dim of ``x``: (..., seq, heads, head_dim).

    ``fraction < 1`` implements partial rotary (ChatGLM-style "2d RoPE"):
    only the first ``fraction * head_dim`` channels are rotated, the rest
    pass through — positional information occupies a sub-space.
    ``positions``: (..., seq) absolute positions (cache-aware at decode).
    """
    head_dim = x.shape[-1]
    inv_freq, rot = rope_frequencies(head_dim, theta, fraction)
    if rot == 0:
        return x
    ang = positions[..., None].astype(jnp.float32) * inv_freq  # (..., seq, rot/2)
    cos = jnp.cos(ang)[..., None, :]                           # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x_rot, x_pass = x[..., :rot], x[..., rot:]
    x1, x2 = x_rot[..., : rot // 2], x_rot[..., rot // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)
    return jnp.concatenate([out, x_pass], axis=-1) if rot < head_dim else out

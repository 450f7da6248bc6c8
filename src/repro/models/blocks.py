"""Residual blocks: (mixer, ff) pairs assembled from layers/attention/moe/mamba.

A block is described by ``kinds = (mixer_kind, ff_kind)`` from
``ModelConfig.layer_kinds()``.  Parameters are plain dicts so whole blocks
stack along a leading "repeat" axis for ``lax.scan``.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name

from . import attention as attn_lib
from . import mamba as mamba_lib
from .config import ATTN, DENSE_FF, MAMBA, MOE_FF, NO_FF, ModelConfig
from .layers import apply_norm, init_mlp, init_norm, swiglu_mlp
from .moe import init_moe, moe_ff, shared_expert


# --------------------------------------------------------------------- init
def init_block(key, cfg: ModelConfig, kinds: Tuple[str, str],
               with_cross: bool = False) -> dict:
    mixer, ff = kinds
    dt = jnp.dtype(cfg.dtype)
    keys = jax.random.split(key, 4)
    p = {"norm1": init_norm(cfg.d_model, dt)}
    if mixer == ATTN:
        p["mixer"] = attn_lib.init_attention(keys[0], cfg)
    else:
        p["mixer"] = mamba_lib.init_mamba(keys[0], cfg)
    if with_cross and mixer == ATTN:
        p["norm_cross"] = init_norm(cfg.d_model, dt)
        p["cross"] = attn_lib.init_attention(keys[1], cfg, cross=True)
    if ff == MOE_FF:
        p["norm2"] = init_norm(cfg.d_model, dt)
        p["ff"] = init_moe(keys[2], cfg)
    elif ff == DENSE_FF:
        p["norm2"] = init_norm(cfg.d_model, dt)
        p["ff"] = init_mlp(keys[2], cfg.d_model, cfg.d_ff, dt)
    return p


def init_block_cache(cfg: ModelConfig, kinds: Tuple[str, str], batch: int,
                     max_len: int, dtype) -> dict:
    if kinds[0] == ATTN:
        return attn_lib.init_cache(cfg, batch, max_len, dtype)
    return mamba_lib.init_ssm_state(cfg, batch, dtype)


# ------------------------------------------------------------------- apply
def residual(cfg: ModelConfig, x, out):
    """``x + out``, the branch scaled by the residual multiplier where
    the configuration has one."""
    rm = cfg.residual_multiplier
    return x + (out if rm == 1.0 else out * rm)


def moe_branch(routed, shared):
    """A routed layer's FFN branch: the routed experts' output plus the
    shared expert's, where there is one."""
    return routed if shared is None else routed + shared


def _apply_ff(cfg: ModelConfig, params, kinds, x, moe_method: str):
    """x: (B, T, d) -> (out, aux)."""
    ff = kinds[1]
    if ff == NO_FF:
        return x, {}
    h = apply_norm(cfg, x, params["norm2"])
    if ff == MOE_FF:
        b, t, d = h.shape
        h2 = h.reshape(b * t, d)
        out, aux = moe_ff(cfg, params["ff"], h2, moe_method)
        out = moe_branch(out, shared_expert(params["ff"], h2))
        out = checkpoint_name(out.reshape(b, t, d), "tp_out")
        aux = {"load_balance_loss": aux["load_balance_loss"],
               "topk_idx": aux["topk_idx"].reshape(b, t, cfg.top_k)}
        return residual(cfg, x, out), aux
    return residual(cfg, x, checkpoint_name(swiglu_mlp(h, params["ff"]),
                                            "tp_out")), {}


def block_seq(cfg: ModelConfig, params, kinds, x, positions, *,
              causal: bool = True, memory: Optional[dict] = None,
              moe_method: str = "scatter", make_cache: bool = False,
              max_cache_len: int = 0):
    """Full-sequence block.  Returns (x, aux, cache-or-None)."""
    mixer = kinds[0]
    h = apply_norm(cfg, x, params["norm1"])
    cache = None
    if mixer == ATTN:
        window = cfg.sliding_window if causal else 0
        out = attn_lib.attn_seq(cfg, params["mixer"], h, positions,
                                causal=causal, window=window)
        if make_cache:
            cache = attn_lib.seed_cache(cfg, params["mixer"], h, positions,
                                        max_cache_len)
        # tag the row-parallel matmul output: the remat policy saves it so
        # backward does not RECOMPUTE the forward TP all-reduce
        x = residual(cfg, x, checkpoint_name(out, "tp_out"))
        if memory is not None and "cross" in params:
            hc = apply_norm(cfg, x, params["norm_cross"])
            x = x + attn_lib.cross_attn(cfg, params["cross"], hc, memory)
    else:
        out, state = mamba_lib.mamba_seq(cfg, params["mixer"], h)
        if make_cache:
            cache = state
        x = residual(cfg, x, checkpoint_name(out, "tp_out"))
    x, aux = _apply_ff(cfg, params, kinds, x, moe_method)
    return x, aux, cache


def block_decode(cfg: ModelConfig, params, kinds, x, cache, pos, *,
                 memory: Optional[dict] = None, moe_method: str = "dense"):
    """One-token block.  x: (B,1,d).  Returns (x, new_cache, aux)."""
    mixer = kinds[0]
    # named scopes label the device ops in a profiler trace (no arithmetic)
    with jax.named_scope("mixer"):
        h = apply_norm(cfg, x, params["norm1"])
        if mixer == ATTN:
            with jax.named_scope("attention"):
                out, cache = attn_lib.attn_decode(cfg, params["mixer"], h,
                                                  cache, pos)
            x = residual(cfg, x, out)
            if memory is not None and "cross" in params:
                hc = apply_norm(cfg, x, params["norm_cross"])
                x = x + attn_lib.cross_attn(cfg, params["cross"], hc, memory)
        else:
            out, cache = mamba_lib.mamba_decode(cfg, params["mixer"], h,
                                                cache)
            x = residual(cfg, x, out)
    x, aux = _apply_ff(cfg, params, kinds, x, moe_method)
    return x, cache, aux

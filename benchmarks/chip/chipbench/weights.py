"""Seeded random weights, made on the device in one jitted call.

The tree has the layout the program's serving entry points take
(``{"embed", "final_norm", "layers": (stacked block,)}``, every layer an
attention mixer with a routed-expert FFN), in the configuration's dtype.
Scales follow the usual fan-in rule: matrices ~ N(0, 1/fan_in), the
embedding ~ N(0, 1), norm scales 1.  The benchmark makes the weights
itself, so the reference and the program read the same arrays and the
program makes nothing the reference consumes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key for any non-negative seed, including seeds beyond 32
    bits (the low word seeds the key, the high word is folded in)."""
    if seed < 0:
        raise ValueError("seed must be >= 0")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def shapes(cfg) -> dict:
    """Leaf name -> (shape, fan_in or None for ones, or 'embed')."""
    L, d, hd = cfg.num_layers, cfg.d_model, cfg.resolved_head_dim
    h, kv, f = cfg.num_heads, cfg.num_kv_heads, cfg.d_expert_resolved
    e, ep, v = cfg.num_experts, cfg.num_experts_padded, cfg.vocab_size
    out = {
        "embed/table": ((v, d), "embed"),
        "final_norm/scale": ((d,), None),
        "norm1/scale": ((L, d), None),
        "mixer/wq": ((L, d, h * hd), d),
        "mixer/wk": ((L, d, kv * hd), d),
        "mixer/wv": ((L, d, kv * hd), d),
        "mixer/wo": ((L, h * hd, d), h * hd),
        "norm2/scale": ((L, d), None),
        "ff/router": ((L, d, e), d),
        "ff/w_gate": ((L, ep, d, f), d),
        "ff/w_up": ((L, ep, d, f), d),
        "ff/w_down": ((L, ep, f, d), f),
    }
    if not cfg.tie_embeddings:
        out["head/w"] = ((d, v), d)
    return out


def _check(cfg) -> None:
    kinds = cfg.layer_kinds()
    if cfg.pattern()[1] != cfg.num_layers or kinds[0] != ("attn", "moe"):
        raise ValueError("weights are made for all-attention, all-MoE "
                         "decoders")
    if cfg.qkv_bias or cfg.norm_type != "rmsnorm":
        raise ValueError("weights are made without qkv bias, with RMSNorm")


@functools.lru_cache(maxsize=None)
def _maker(cfg):
    _check(cfg)
    dt = jnp.dtype(cfg.dtype)
    leaves = shapes(cfg)

    def make(key):
        flat = {}
        keys = jax.random.split(key, len(leaves))
        for k, (name, (shape, fan)) in zip(keys, sorted(leaves.items())):
            if fan is None:
                flat[name] = jnp.ones(shape, dt)
            else:
                scale = 1.0 if fan == "embed" else fan ** -0.5
                flat[name] = jax.random.normal(k, shape, dt) * jnp.asarray(
                    scale, dt)
        tree: dict = {}
        for name, arr in flat.items():
            group, leaf = name.split("/")
            tree.setdefault(group, {})[leaf] = arr
        layer = {g: tree.pop(g) for g in ("norm1", "mixer", "norm2", "ff")}
        tree["layers"] = (layer,)
        return tree
    return jax.jit(make)


def make_params(cfg, seed: int) -> dict:
    params = _maker(cfg)(seed_key(seed))
    jax.block_until_ready(params)
    return params

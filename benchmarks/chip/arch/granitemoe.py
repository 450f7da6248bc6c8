"""Granite-MoE (``"model_type": "granitemoe"``): an all-attention, all-MoE
decoder, every layer grouped-query attention with rotary embeddings and
a routed SwiGLU expert FFN.

The harness finds this file by the configuration file's ``model_type``
(``chipbench/spec.py``).  It gives what the benchmark needs to know of
the architecture:

* ``model_config(raw)``: the published ``config.json`` keys -> the
  program's ``ModelConfig``;
* ``make_params(cfg, seed)``: seeded weights in the layout the program
  takes;
* ``Arch.from_config(raw)`` and ``forward(...)``: the float32 reference
  and the float8 control;
* ``prompt_flops(cfg, n)`` and ``decode_flops(cfg, context)``: the
  model's operations per prompt and per decoded token.

Weights.  Made on the device in one jitted call, in the configuration's
dtype, in the layout the program's serving entry points take
(``{"embed", "final_norm", "layers": (stacked block,)}``).  Scales follow
the usual fan-in rule: matrices ~ N(0, 1/fan_in), the embedding ~ N(0,
1), norm scales 1.  The benchmark makes the weights itself, so the
reference and the program read the same arrays and the program makes
nothing the reference consumes.

Reference.  Written from the architecture, not from the program: it
imports nothing of ``repro``.  One jitted program per layer, run layer
by layer over the whole sequence, so it fits beside the weights once
the engine is freed.

Per layer: RMSNorm, grouped-query attention with rotary embeddings
(rotate-half, both halves of each head), causal softmax, residual;
RMSNorm, a linear router, softmax over the top-k router logits, SwiGLU
experts weighted by those gates, residual.  Then the final RMSNorm and
the tied (or separate) output head.

Routing can be *forced*: at positions where ``forced_mask`` is set, the
layer uses the given expert ids instead of its own top-k (the gates are
still the softmax of its own router logits at those ids).  The check
forces the program's served routing, so a near-tie that rounds the
other way in bfloat16 does not swap experts between the two sides; each
forced choice is judged on its own by its gap below the reference's
k-th best router logit.

``mode="f32"``: every weight cast to float32, every matmul at
``precision="highest"``.  ``mode="fp8"`` (the control): the same
forward with every matrix round-tripped through float8 e4m3 with one
scale per output channel, computed in bfloat16 at default precision.

Operations.  2 x the active parameters that multiply a token
(projections, router, the top-k experts, the output head), plus the
attention scores and weighted values over its context.  (The per-token
arithmetic follows ``benchmarks/roofline.py::fwd_flops_per_token``.)
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import expert_flops
from chipbench.weights import seed_key
from chipbench.yardstick import RefOut

NEG_INF = -1e30


# ------------------------------------------------------------ configuration
def model_config(raw: dict):
    from repro.models.config import ModelConfig
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's experts are SwiGLU (silu) only")
    prog = raw.get("program", {})
    return ModelConfig(
        name=raw["name"], family="moe",
        num_layers=int(raw["num_hidden_layers"]),
        d_model=int(raw["hidden_size"]),
        num_heads=int(raw["num_attention_heads"]),
        num_kv_heads=int(raw["num_key_value_heads"]),
        d_ff=int(raw["intermediate_size"]),
        vocab_size=int(raw["vocab_size"]),
        num_experts=int(raw["num_local_experts"]),
        top_k=int(raw["num_experts_per_tok"]),
        d_expert=int(raw["intermediate_size"]),
        padded_experts=int(prog.get("padded_experts", 0)),
        rope_theta=float(raw["rope_theta"]),
        norm_eps=float(raw["rms_norm_eps"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        dtype=str(raw["torch_dtype"]),
        source=raw["source"])


# ------------------------------------------------------------------ weights
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


# ---------------------------------------------------------------- reference
@dataclass(frozen=True)
class Arch:
    """The sizes the reference needs, read from a configuration file."""
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool

    @classmethod
    def from_config(cls, raw: dict) -> "Arch":
        d, h = int(raw["hidden_size"]), int(raw["num_attention_heads"])
        return cls(num_layers=int(raw["num_hidden_layers"]), d_model=d,
                   num_heads=h, num_kv_heads=int(raw["num_key_value_heads"]),
                   head_dim=int(raw.get("head_dim") or d // h),
                   num_experts=int(raw["num_local_experts"]),
                   top_k=int(raw["num_experts_per_tok"]),
                   rope_theta=float(raw["rope_theta"]),
                   norm_eps=float(raw["rms_norm_eps"]),
                   tie_embeddings=bool(raw["tie_word_embeddings"]))


def fp8_roundtrip(w, axis: int):
    """float8 e4m3 with one scale per output channel (its absmax at the
    format's largest finite value, 448), back in bfloat16."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale).astype(jnp.bfloat16)


def _prep(w, mode: str, axis: int):
    if mode == "f32":
        return w.astype(jnp.float32)
    if mode == "fp8":
        return fp8_roundtrip(w, axis)
    raise ValueError(f"unknown reference mode {mode!r}")


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv          # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :hd // 2], x32[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


@functools.lru_cache(maxsize=None)
def _layer_fn(arch: Arch, mode: str):
    prec = "highest" if mode == "f32" else "default"
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    E, k = arch.num_experts, arch.top_k
    H, K, hd = arch.num_heads, arch.num_kv_heads, arch.head_dim
    G = H // K

    def fn(layers, li, x, positions, forced, forced_mask, out_idx):
        lw = jax.tree.map(lambda a: a[li], layers)
        with jax.default_matmul_precision(prec):
            T = x.shape[0]
            h = _rms(x, lw["norm1"]["scale"], arch.norm_eps)
            mx = lw["mixer"]
            q = (h @ _prep(mx["wq"], mode, 0)).reshape(T, H, hd)
            kk = (h @ _prep(mx["wk"], mode, 0)).reshape(T, K, hd)
            v = (h @ _prep(mx["wv"], mode, 0)).reshape(T, K, hd)
            q = _rope(q, positions, arch.rope_theta)
            kk = _rope(kk, positions, arch.rope_theta)
            qg = q.reshape(T, K, G, hd)
            s = jnp.einsum("tkgh,skh->kgts", qg, kk).astype(jnp.float32)
            s = s / jnp.sqrt(jnp.float32(hd))
            causal = positions[None, :] <= positions[:, None]
            s = jnp.where(causal[None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1).astype(dt)
            o = jnp.einsum("kgts,skh->tkgh", p, v).reshape(T, H * hd)
            x = x + (o @ _prep(mx["wo"], mode, 0)).astype(dt)
            h2 = _rms(x, lw["norm2"]["scale"], arch.norm_eps)
            ff = lw["ff"]
            router = (h2.astype(jnp.float32)
                      @ _prep(ff["router"], mode, 0).astype(jnp.float32))
            _, own = jax.lax.top_k(router, k)
            idx = jnp.where(forced_mask[:, None], forced, own)
            gates = jax.nn.softmax(
                jnp.take_along_axis(router, idx, axis=-1), axis=-1)
            dense = jnp.zeros((T, E), jnp.float32).at[
                jnp.arange(T)[:, None], idx].add(gates)
            wg = _prep(ff["w_gate"][:E], mode, 1)
            wu = _prep(ff["w_up"][:E], mode, 1)
            wd = _prep(ff["w_down"][:E], mode, 1)
            a = jnp.einsum("td,edf->etf", h2, wg)
            b = jnp.einsum("td,edf->etf", h2, wu)
            y = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * b, wd)
            y = jnp.einsum("etd,te->td", y.astype(jnp.float32), dense)
            x = x + y.astype(dt)
        return x, idx, router[out_idx]
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _head_fn(arch: Arch, mode: str):
    prec = "highest" if mode == "f32" else "default"

    def fn(params, x, out_idx):
        with jax.default_matmul_precision(prec):
            h = _rms(x[out_idx], params["final_norm"]["scale"],
                     arch.norm_eps)
            if arch.tie_embeddings:
                w = _prep(params["embed"]["table"], mode, 1).T
            else:
                w = _prep(params["head"]["w"], mode, 0)
            return (h @ w).astype(jnp.float32)
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _embed_fn(mode: str):
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16

    def fn(table, tokens):
        return jnp.take(_prep(table, mode, 1), tokens, axis=0).astype(dt)
    return jax.jit(fn)


def pad_len(n: int) -> int:
    """Sequence lengths fold onto powers of two (at least 128), so a
    handful of programs cover every request; causal attention keeps the
    padded tail out of the real positions."""
    b = 128
    while b < n:
        b *= 2
    return b


def forward(arch: Arch, params, tokens, out_pos, forced=None,
            mode: str = "f32") -> RefOut:
    """Forward of ``tokens`` (T,), read at positions ``out_pos`` (P,).
    ``forced``: optional ``{layer: (P, k)}`` expert ids to use at
    ``out_pos`` instead of the layer's own top-k."""
    tokens = np.asarray(tokens, np.int32)
    out_pos = np.asarray(out_pos, np.int32)
    T, P, k = len(tokens), len(out_pos), arch.top_k
    Tp, Pp = pad_len(T), pad_len(P)
    tok = jnp.asarray(np.pad(tokens, (0, Tp - T)))
    positions = jnp.arange(Tp, dtype=jnp.int32)
    out_idx = np.zeros(Pp, np.int32)
    out_idx[:P] = out_pos
    x = _embed_fn(mode)(params["embed"]["table"], tok)
    layers = params["layers"][0]
    routing, router = {}, {}
    for li in range(arch.num_layers):
        f_arr = np.zeros((Tp, k), np.int32)
        f_mask = np.zeros(Tp, bool)
        if forced is not None:
            f_arr[out_pos] = np.asarray(forced[li], np.int32)
            f_mask[out_pos] = True
        x, idx, r = _layer_fn(arch, mode)(
            layers, jnp.int32(li), x, positions, jnp.asarray(f_arr),
            jnp.asarray(f_mask), jnp.asarray(out_idx))
        routing[li] = np.asarray(idx)[out_pos]
        router[li] = np.asarray(r)[:P]
    logits = np.asarray(_head_fn(arch, mode)(params, x,
                                             jnp.asarray(out_idx)))[:P]
    return RefOut(logits=logits, routing=routing, router=router)


# --------------------------------------------------------------- operations
def active_matmul_flops(cfg) -> int:
    """Matmul FLOPs of one token through the model, attention scores
    excluded: projections, router, the top-k experts and the output
    head (2 x the active parameters that multiply the token)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    per_layer = (2 * d * (h * hd + 2 * kv * hd)        # q, k, v
                 + 2 * h * hd * d                      # o
                 + 2 * d * cfg.num_experts             # router
                 + cfg.top_k * expert_flops(d, cfg.d_expert_resolved))
    return cfg.num_layers * per_layer + 2 * d * cfg.vocab_size


def attention_flops(cfg, context: int) -> int:
    """Scores and weighted values of one token over ``context`` keys."""
    return cfg.num_layers * 4 * cfg.num_heads * cfg.resolved_head_dim \
        * context


def prompt_flops(cfg, n: int) -> int:
    """Model FLOPs of a causal prefill of ``n`` tokens."""
    return n * active_matmul_flops(cfg) + attention_flops(
        cfg, n * (n + 1) // 2)


def decode_flops(cfg, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context`` keys."""
    return active_matmul_flops(cfg) + attention_flops(cfg, context)

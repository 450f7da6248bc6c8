"""Granite-4.0-H (``"model_type": "granitemoehybrid"``): a hybrid decoder
whose layers are Mamba-2 mixers with a grouped-query attention layer at
fixed intervals (``layer_types``), and on every layer a routed SwiGLU
expert FFN with a shared SwiGLU expert beside it.

The harness finds this file by the configuration file's ``model_type``
(``chipbench/spec.py``); it gives what ``arch/granitemoe.py``'s
docstring lists, and ``mamba_step_bytes(cfg, rows)`` for the Mamba-2
layer's roofline.

Weights.  The non-expert weights are made on the device in one jitted
call, in the configuration's dtype; the routed experts are made a layer
and a weight at a time on the device and copied into one preallocated
host array per weight, so they reach the program as host ``numpy``
leaves of the layout it takes (``{"embed", "final_norm", "layers":
(stacked block per pattern position,)}``, each leaf with its leading
repeat axis) and never all sit on the device.  Scales: matrices ~
N(0, 1/fan_in), the embedding ~ N(0, 1), conv kernels ~ N(0, 1/4) with
biases ~ N(0, 0.1^2), ``A_log`` = log U(1, 16), ``dt_bias`` the inverse
softplus of a log-uniform dt in [1e-3, 1e-1], ``D`` and norm scales 1.

Reference.  Written from the published architecture, not from the
program: it imports nothing of ``repro``.  One jitted program per layer
kind, run layer by layer over the whole sequence with that layer's
experts brought to the device for it alone.  Per layer, with rm the
residual multiplier:

* Mamba-2 (one group): ``z, x, B, C, dt`` projections of the RMS-normed
  input; a depthwise causal convolution of width ``mamba_d_conv`` with
  bias over ``(x, B, C)`` (weight ``w[k]`` applies to the input ``K-1-k``
  steps back), then SiLU; ``dt = softplus(dt + dt_bias)`` (no clamp);
  the recurrence ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T`` with
  ``A = -exp(A_log)``, run as a plain sequential scan over positions,
  ``y_t = h_t C_t + D x_t``; the gated RMSNorm ``rms(y * silu(z))`` over
  all channels (``norm_before_gate=False``); the out projection;
  ``x += rm * out``.
* Attention: RMSNorm, grouped-query attention without positional
  rotation (``position_embedding_type`` ``nope``), scores scaled by
  ``attention_multiplier``, causal softmax, ``x += rm * out``.
* MoE: RMSNorm, a linear router, softmax over the top-k router logits,
  the experts weighted by those gates, plus the shared expert;
  ``x += rm * (routed + shared)``.

Then the final RMSNorm, the tied (or separate) head, and the logits
divided by ``logits_scaling``.  The embedding is multiplied by
``embedding_multiplier``.  Routing can be forced exactly as in
``arch/granitemoe.py``.  ``mode="f32"``: float32 weights, matmuls at
``precision="highest"``; ``mode="fp8"`` (the control): every projection,
router, expert, shared-expert and embedding matrix round-tripped through
float8 e4m3 with one scale per output channel, computed in bfloat16
(conv kernels, norms, biases and the SSM's per-head vectors stay
bfloat16).

Operations.  Per token: 2 x the weights that multiply it (projections,
router, the top-k routed experts, the shared expert, the output head),
the depthwise conv (2 x width x channels), the state update and readout
(5 x heads x head_dim x d_state) of each Mamba-2 layer, and the
attention scores and weighted values over its context.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.flops import expert_flops
from chipbench.weights import seed_key
from chipbench.yardstick import RefOut

NEG_INF = -1e30
ROUTED = ("w_gate", "w_up", "w_down")


# ------------------------------------------------------------ configuration
def _attention_period(types) -> Tuple[int, int]:
    """(every, offset) of the attention layers in ``layer_types``."""
    idx = [i for i, t in enumerate(types) if t == "attention"]
    if not idx or set(types) - {"mamba", "attention"}:
        raise ValueError("layer_types must be mamba and attention layers, "
                         "at least one attention")
    every = idx[1] - idx[0] if len(idx) > 1 else len(types)
    offset = idx[0]
    if [("attention" if i % every == offset else "mamba")
            for i in range(len(types))] != list(types):
        raise ValueError("attention layers must recur at one interval")
    return every, offset


def model_config(raw: dict):
    from repro.models.config import ModelConfig
    if raw.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's experts are SwiGLU (silu) only")
    if raw.get("normalization_function", "rmsnorm") != "rmsnorm":
        raise ValueError("the program's norms are RMSNorm")
    if raw.get("attention_bias") or raw.get("mamba_proj_bias"):
        raise ValueError("the program's projections have no bias")
    if not raw.get("mamba_conv_bias", True):
        raise ValueError("the program's Mamba-2 convolutions have a bias")
    if int(raw["mamba_n_groups"]) != 1:
        raise ValueError("the program's Mamba-2 mixer has one group")
    d = int(raw["hidden_size"])
    types = raw["layer_types"]
    if len(types) != int(raw["num_hidden_layers"]):
        raise ValueError("layer_types must list every layer")
    every, offset = _attention_period(types)
    if int(raw["mamba_n_heads"]) * int(raw["mamba_d_head"]) \
            != int(raw["mamba_expand"]) * d:
        raise ValueError("mamba_n_heads x mamba_d_head must be "
                         "mamba_expand x hidden_size")
    pe = raw.get("position_embedding_type", "rope")
    if pe not in ("nope", "rope"):
        raise ValueError(f"unknown position_embedding_type {pe!r}")
    h = int(raw["num_attention_heads"])
    prog = raw.get("program", {})
    return ModelConfig(
        name=raw["name"], family="hybrid",
        num_layers=int(raw["num_hidden_layers"]), d_model=d, num_heads=h,
        num_kv_heads=int(raw["num_key_value_heads"]),
        head_dim=int(raw.get("head_dim") or d // h),
        d_ff=int(raw["intermediate_size"]),
        vocab_size=int(raw["vocab_size"]),
        num_experts=int(raw["num_local_experts"]),
        top_k=int(raw["num_experts_per_tok"]),
        d_expert=int(raw["intermediate_size"]),
        d_shared=int(raw.get("shared_intermediate_size", 0)),
        padded_experts=int(prog.get("padded_experts", 0)),
        ssm_state=int(raw["mamba_d_state"]),
        ssm_head_dim=int(raw["mamba_d_head"]),
        ssm_expand=int(raw["mamba_expand"]),
        ssm_conv=int(raw["mamba_d_conv"]),
        ssm_chunk=int(raw["mamba_chunk_size"]),
        attn_every=every, attn_offset=offset,
        rope_theta=float(raw.get("rope_theta", 10000.0)),
        rope_fraction=0.0 if pe == "nope" else 1.0,
        norm_eps=float(raw["rms_norm_eps"]),
        tie_embeddings=bool(raw["tie_word_embeddings"]),
        embedding_multiplier=float(raw.get("embedding_multiplier", 1.0)),
        attention_multiplier=float(raw.get("attention_multiplier", 0.0)),
        residual_multiplier=float(raw.get("residual_multiplier", 1.0)),
        logits_scaling=float(raw.get("logits_scaling", 1.0)),
        dtype=str(raw.get("torch_dtype", "bfloat16")),
        source=raw["source"])


# ------------------------------------------------------------------ weights
def _mixer_shapes(cfg, mixer: str) -> dict:
    """Leaf -> (shape, init) of one layer's mixer, no repeat axis; init
    is a fan-in, "ones", "a_log", "dt_bias" or ("normal", std)."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    if mixer == "attn":
        h, kv = cfg.num_heads, cfg.num_kv_heads
        return {"wq": ((d, h * hd), d), "wk": ((d, kv * hd), d),
                "wv": ((d, kv * hd), d), "wo": ((h * hd, d), h * hd)}
    di, ns, nh, k = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_conv
    return {"w_z": ((d, di), d), "w_x": ((d, di), d), "w_B": ((d, ns), d),
            "w_C": ((d, ns), d), "w_dt": ((d, nh), d),
            "conv_x_w": ((k, di), k), "conv_x_b": ((di,), ("normal", 0.1)),
            "conv_B_w": ((k, ns), k), "conv_B_b": ((ns,), ("normal", 0.1)),
            "conv_C_w": ((k, ns), k), "conv_C_b": ((ns,), ("normal", 0.1)),
            "A_log": ((nh,), "a_log"), "dt_bias": ((nh,), "dt_bias"),
            "D": ((nh,), "ones"), "norm/scale": ((di,), "ones"),
            "out_proj": ((di, d), di)}


def _check(cfg) -> None:
    if cfg.family != "hybrid" or any(ff != "moe"
                                     for _, ff in cfg.layer_kinds()):
        raise ValueError("weights are made for hybrid decoders with a "
                         "routed FFN on every layer")
    if cfg.qkv_bias or cfg.norm_type != "rmsnorm":
        raise ValueError("weights are made without qkv bias, with RMSNorm")


def _init(key, shape, init, dt):
    if init == "ones":
        return jnp.ones(shape, dt)
    if init == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dt)
    if init == "dt_bias":
        u = jax.random.uniform(key, shape, jnp.float32)
        step = jnp.exp(jnp.log(1e-3) + u * (jnp.log(1e-1) - jnp.log(1e-3)))
        return (step + jnp.log(-jnp.expm1(-step))).astype(dt)
    if isinstance(init, tuple):
        return jax.random.normal(key, shape, dt) * jnp.asarray(init[1], dt)
    scale = 1.0 if init == "embed" else init ** -0.5
    return jax.random.normal(key, shape, dt) * jnp.asarray(scale, dt)


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for name, arr in flat.items():
        node = tree
        *groups, leaf = name.split("/")
        for g in groups:
            node = node.setdefault(g, {})
        node[leaf] = arr
    return tree


@functools.lru_cache(maxsize=None)
def _maker(cfg):
    """Jitted maker of every weight but the routed experts."""
    _check(cfg)
    dt = jnp.dtype(cfg.dtype)
    pattern, reps = cfg.pattern()
    d, e, ds = cfg.d_model, cfg.num_experts, cfg.d_shared

    def make(key):
        k_top, *k_pos = jax.random.split(key, 1 + len(pattern))
        top = {"embed/table": ((cfg.vocab_size, d), "embed"),
               "final_norm/scale": ((d,), "ones")}
        if not cfg.tie_embeddings:
            top["head/w"] = ((d, cfg.vocab_size), d)
        keys = jax.random.split(k_top, len(top))
        flat = {n: _init(k, s, i, dt)
                for k, (n, (s, i)) in zip(keys, sorted(top.items()))}
        layers = []
        for kp, (mixer, _) in zip(k_pos, pattern):
            leaves = {"norm1/scale": ((d,), "ones"),
                      "norm2/scale": ((d,), "ones"),
                      "ff/router": ((d, e), d)}
            leaves.update({f"mixer/{n}": v for n, v in
                           _mixer_shapes(cfg, mixer).items()})
            if ds:
                leaves.update({"ff/shared/w_gate": ((d, ds), d),
                               "ff/shared/w_up": ((d, ds), d),
                               "ff/shared/w_down": ((ds, d), ds)})
            ks = jax.random.split(kp, len(leaves))
            layers.append(_nest({n: _init(k, (reps,) + s, i, dt)
                                 for k, (n, (s, i)) in
                                 zip(ks, sorted(leaves.items()))}))
        tree = _nest(flat)
        tree["layers"] = tuple(layers)
        return tree
    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _expert_maker(cfg, name: str):
    """Jitted maker of one layer's routed weight ``name``: (E, d, f) or
    (E, f, d), in the configuration's dtype."""
    d, f = cfg.d_model, cfg.d_expert_resolved
    shape = (cfg.num_experts_padded,) + ((f, d) if name == "w_down"
                                         else (d, f))
    dt = jnp.dtype(cfg.dtype)
    return jax.jit(lambda key: jax.random.normal(key, shape, dt)
                   * jnp.asarray(shape[1] ** -0.5, dt))


def make_params(cfg, seed: int) -> dict:
    key = seed_key(seed)
    params = _maker(cfg)(jax.random.fold_in(key, 0))
    pattern, reps = cfg.pattern()
    for n_i, name in enumerate(ROUTED):
        maker = _expert_maker(cfg, name)
        host = None
        for li in range(cfg.num_layers):
            arr = maker(jax.random.fold_in(key, 1 + n_i * cfg.num_layers
                                           + li))
            if host is None:        # (P, R, E, ...), one array per weight
                host = np.empty((len(pattern), reps) + arr.shape,
                                dtype=arr.dtype)
            host[li % len(pattern), li // len(pattern)] = np.asarray(arr)
            del arr
        for pos in range(len(pattern)):
            params["layers"][pos]["ff"][name] = host[pos]
    jax.block_until_ready(params)
    return params


# ---------------------------------------------------------------- reference
@dataclass(frozen=True)
class Arch:
    """The sizes and constants the reference needs, read from a
    configuration file."""
    layer_types: tuple
    d_model: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    num_experts: int
    top_k: int
    d_shared: int
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    nope: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    embedding_multiplier: float
    attention_multiplier: float
    residual_multiplier: float
    logits_scaling: float

    @classmethod
    def from_config(cls, raw: dict) -> "Arch":
        d, h = int(raw["hidden_size"]), int(raw["num_attention_heads"])
        return cls(
            layer_types=tuple(raw["layer_types"]), d_model=d, num_heads=h,
            num_kv_heads=int(raw["num_key_value_heads"]),
            head_dim=int(raw.get("head_dim") or d // h),
            num_experts=int(raw["num_local_experts"]),
            top_k=int(raw["num_experts_per_tok"]),
            d_shared=int(raw.get("shared_intermediate_size", 0)),
            ssm_heads=int(raw["mamba_n_heads"]),
            ssm_head_dim=int(raw["mamba_d_head"]),
            ssm_state=int(raw["mamba_d_state"]),
            nope=raw.get("position_embedding_type") == "nope",
            rope_theta=float(raw.get("rope_theta", 10000.0)),
            norm_eps=float(raw["rms_norm_eps"]),
            tie_embeddings=bool(raw["tie_word_embeddings"]),
            embedding_multiplier=float(raw.get("embedding_multiplier", 1.0)),
            attention_multiplier=float(raw.get("attention_multiplier", 0.0)),
            residual_multiplier=float(raw.get("residual_multiplier", 1.0)),
            logits_scaling=float(raw.get("logits_scaling", 1.0)))

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def period(self) -> int:
        """Layers per pattern position's stack: the program's layout
        stacks layer ``li`` at position ``li % period``, repeat
        ``li // period``."""
        every, _ = _attention_period(self.layer_types)
        return every if self.num_layers % every == 0 else self.num_layers


def fp8_roundtrip(w, axis: int):
    """float8 e4m3 with one scale per output channel (its absmax at the
    format's largest finite value, 448), back in bfloat16."""
    w = w.astype(jnp.float32)
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return ((w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32)
            * scale).astype(jnp.bfloat16)


def _mat(w, mode: str, axis: int):
    """A matrix as the mode computes with it."""
    if mode == "f32":
        return w.astype(jnp.float32)
    if mode == "fp8":
        return fp8_roundtrip(w, axis)
    raise ValueError(f"unknown reference mode {mode!r}")


def _vec(w, mode: str):
    """A kernel, bias or per-head vector, not round-tripped."""
    return w.astype(jnp.float32 if mode == "f32" else jnp.bfloat16)


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :hd // 2], x32[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _attention(arch: Arch, mx, h, positions, mode):
    T = h.shape[0]
    H, K, hd = arch.num_heads, arch.num_kv_heads, arch.head_dim
    q = (h @ _mat(mx["wq"], mode, 0)).reshape(T, H, hd)
    k = (h @ _mat(mx["wk"], mode, 0)).reshape(T, K, hd)
    v = (h @ _mat(mx["wv"], mode, 0)).reshape(T, K, hd)
    if not arch.nope:
        q, k = _rope(q, positions, arch.rope_theta), _rope(
            k, positions, arch.rope_theta)
    s = jnp.einsum("tkgh,skh->kgts", q.reshape(T, K, H // K, hd),
                   k).astype(jnp.float32)
    s = s * (arch.attention_multiplier or hd ** -0.5)
    causal = positions[None, :] <= positions[:, None]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, NEG_INF), axis=-1)
    o = jnp.einsum("kgts,skh->tkgh", p.astype(h.dtype), v)
    return o.reshape(T, H * hd) @ _mat(mx["wo"], mode, 0)


def _causal_conv(u, w, b):
    """out[t] = sum_k w[k] u[t - (K-1) + k] + b, zeros before the start."""
    K = w.shape[0]
    up = jnp.concatenate([jnp.zeros((K - 1,) + u.shape[1:], u.dtype), u])
    T = u.shape[0]
    return sum(up[k:k + T] * w[k] for k in range(K)) + b


def _mamba(arch: Arch, mx, h, mode, eps):
    T = h.shape[0]
    H, P, N = arch.ssm_heads, arch.ssm_head_dim, arch.ssm_state
    z = h @ _mat(mx["w_z"], mode, 0)
    xs = jax.nn.silu(_causal_conv(h @ _mat(mx["w_x"], mode, 0),
                                  _vec(mx["conv_x_w"], mode),
                                  _vec(mx["conv_x_b"], mode)))
    B = jax.nn.silu(_causal_conv(h @ _mat(mx["w_B"], mode, 0),
                                 _vec(mx["conv_B_w"], mode),
                                 _vec(mx["conv_B_b"], mode)))
    C = jax.nn.silu(_causal_conv(h @ _mat(mx["w_C"], mode, 0),
                                 _vec(mx["conv_C_w"], mode),
                                 _vec(mx["conv_C_b"], mode)))
    dt = jax.nn.softplus((h @ _mat(mx["w_dt"], mode, 0)).astype(jnp.float32)
                         + mx["dt_bias"].astype(jnp.float32))      # (T, H)
    A = -jnp.exp(mx["A_log"].astype(jnp.float32))
    xh = xs.reshape(T, H, P).astype(jnp.float32)

    def step(state, inp):
        dt_t, x_t, b_t, c_t = inp
        state = (jnp.exp(dt_t * A)[:, None, None] * state
                 + dt_t[:, None, None] * x_t[:, :, None] * b_t[None, None])
        return state, jnp.einsum("hpn,n->hp", state, c_t)

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (dt, xh, B.astype(jnp.float32),
                         C.astype(jnp.float32)))
    y = y + mx["D"].astype(jnp.float32)[None, :, None] * xh
    y = y.reshape(T, H * P) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
    y = (y * mx["norm"]["scale"].astype(jnp.float32)).astype(h.dtype)
    return y @ _mat(mx["out_proj"], mode, 0)


def _swiglu(h, w, mode):
    a = h @ _mat(w["w_gate"], mode, 0)
    b = h @ _mat(w["w_up"], mode, 0)
    return (jax.nn.silu(a) * b) @ _mat(w["w_down"], mode, 0)


@functools.lru_cache(maxsize=None)
def _layer_fn(arch: Arch, mixer: str, mode: str):
    prec = "highest" if mode == "f32" else "default"
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16
    E, k, rm = arch.num_experts, arch.top_k, arch.residual_multiplier

    def fn(stack, r, experts, x, positions, forced, forced_mask, out_idx):
        lw = jax.tree.map(lambda a: a[r], stack)
        with jax.default_matmul_precision(prec):
            T = x.shape[0]
            h = _rms(x, lw["norm1"]["scale"], arch.norm_eps)
            if mixer == "attention":
                out = _attention(arch, lw["mixer"], h, positions, mode)
            else:
                out = _mamba(arch, lw["mixer"], h, mode, arch.norm_eps)
            x = x + (out * rm).astype(dt)
            h2 = _rms(x, lw["norm2"]["scale"], arch.norm_eps)
            ff = lw["ff"]
            router = (h2.astype(jnp.float32)
                      @ _mat(ff["router"], mode, 0).astype(jnp.float32))
            _, own = jax.lax.top_k(router, k)
            idx = jnp.where(forced_mask[:, None], forced, own)
            gates = jax.nn.softmax(
                jnp.take_along_axis(router, idx, axis=-1), axis=-1)
            dense = jnp.zeros((T, E), jnp.float32).at[
                jnp.arange(T)[:, None], idx].add(gates)
            wg, wu, wd = (_mat(experts[n][:E], mode, 1) for n in ROUTED)
            a = jnp.einsum("td,edf->etf", h2, wg)
            b = jnp.einsum("td,edf->etf", h2, wu)
            y = jnp.einsum("etf,efd->etd", jax.nn.silu(a) * b, wd)
            y = jnp.einsum("etd,te->td", y.astype(jnp.float32), dense)
            if arch.d_shared:
                y = y + _swiglu(h2, ff["shared"], mode).astype(jnp.float32)
            x = x + (y * rm).astype(dt)
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
                w = _mat(params["embed"]["table"], mode, 1).T
            else:
                w = _mat(params["head"]["w"], mode, 0)
            return (h @ w).astype(jnp.float32) / arch.logits_scaling
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _embed_fn(arch: Arch, mode: str):
    dt = jnp.float32 if mode == "f32" else jnp.bfloat16

    def fn(table, tokens):
        x = jnp.take(_mat(table, mode, 1), tokens, axis=0).astype(dt)
        return x * jnp.asarray(arch.embedding_multiplier, dt)
    return jax.jit(fn)


def pad_len(n: int) -> int:
    """Sequence lengths fold onto powers of two (at least 128); causal
    attention and the causal scan keep the padded tail out of the real
    positions."""
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
    x = _embed_fn(arch, mode)(params["embed"]["table"], tok)
    period = arch.period
    routing, router = {}, {}
    for li, mixer in enumerate(arch.layer_types):
        pos, r = li % period, li // period
        sub = params["layers"][pos]
        stack = dict(sub, ff={n: v for n, v in sub["ff"].items()
                              if n not in ROUTED})
        experts = jax.device_put({n: sub["ff"][n][r] for n in ROUTED})
        f_arr = np.zeros((Tp, k), np.int32)
        f_mask = np.zeros(Tp, bool)
        if forced is not None:
            f_arr[out_pos] = np.asarray(forced[li], np.int32)
            f_mask[out_pos] = True
        x, idx, rl = _layer_fn(arch, mixer, mode)(
            stack, jnp.int32(r), experts, x, positions, jnp.asarray(f_arr),
            jnp.asarray(f_mask), jnp.asarray(out_idx))
        del experts
        routing[li] = np.asarray(idx)[out_pos]
        router[li] = np.asarray(rl)[:P]
    logits = np.asarray(_head_fn(arch, mode)(params, x,
                                             jnp.asarray(out_idx)))[:P]
    return RefOut(logits=logits, routing=routing, router=router)


# --------------------------------------------------------------- operations
def _layer_matmul_flops(cfg, mixer: str) -> int:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    if mixer == "attn":
        h, kv = cfg.num_heads, cfg.num_kv_heads
        mix = 2 * d * (h * hd + 2 * kv * hd) + 2 * h * hd * d
    else:
        di, ns, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
        mix = (2 * d * (2 * di + 2 * ns + nh) + 2 * di * d
               + 2 * cfg.ssm_conv * (di + 2 * ns)          # depthwise conv
               + 5 * nh * cfg.ssm_head_dim * ns)          # update, readout
    return (mix + 2 * d * cfg.num_experts
            + cfg.top_k * expert_flops(d, cfg.d_expert_resolved)
            + (expert_flops(d, cfg.d_shared) if cfg.d_shared else 0))


def active_flops(cfg) -> int:
    """FLOPs of one token through the model, attention scores excluded."""
    return (sum(_layer_matmul_flops(cfg, m) for m, _ in cfg.layer_kinds())
            + 2 * cfg.d_model * cfg.vocab_size)


def attention_flops(cfg, context: int) -> int:
    """Scores and weighted values of one token over ``context`` keys, in
    every attention layer."""
    n_attn = sum(m == "attn" for m, _ in cfg.layer_kinds())
    return n_attn * 4 * cfg.num_heads * cfg.resolved_head_dim * context


def prompt_flops(cfg, n: int) -> int:
    """Model FLOPs of a causal prefill of ``n`` tokens."""
    return n * active_flops(cfg) + attention_flops(cfg, n * (n + 1) // 2)


def decode_flops(cfg, context: int) -> int:
    """Model FLOPs of one decoded token that attends ``context`` keys."""
    return active_flops(cfg) + attention_flops(cfg, context)


def mamba_step_bytes(cfg, rows: int) -> int:
    """Bytes a Mamba-2 layer's decode program (``jit_mamba_layer_step``:
    mixer, residual, router and shared expert) must move once for
    ``rows`` rows: every weight it reads (norms, the mixer, the router,
    the shared expert), and per row the SSM and conv states read and
    written, the input, the output, the router input and the shared
    expert's output."""
    item = jnp.dtype(cfg.dtype).itemsize
    d, di, ns, nh = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
    k = cfg.ssm_conv
    mixer = (d * (2 * di + 2 * ns + nh) + di * d       # projections
             + (k + 1) * (di + 2 * ns)                 # conv kernels, biases
             + 3 * nh + di)                            # A_log, dt_bias, D, norm
    weights = (mixer + 2 * d + d * cfg.num_experts
               + 3 * d * cfg.d_shared) * item
    state = (nh * cfg.ssm_head_dim * ns * 4            # h, float32
             + (k - 1) * (di + 2 * ns) * item)         # conv window
    return weights + rows * (2 * state + 4 * d * item)

"""Top-k routed Mixture-of-Experts FFN.

Routing (Mixtral-style): softmax over the top-k router logits only.
Three dispatch strategies, selectable per call site:

  * ``dense``   — every expert computes every token, combined with the
                  (mostly zero) gate matrix.  Exact, no drops; O(N·E).
                  Used by CPU smoke tests and as the routing oracle.
  * ``scatter`` — capacity-based gather/GEMM/scatter-add.  Each expert
                  owns ``C`` slots; tokens are placed by cumulative
                  position and over-capacity tokens fall through on the
                  residual path.  No (N,E,C) one-hot tensor is ever
                  materialized.  Default for compiled SPMD paths.
  * ``einsum``  — classic GShard one-hot dispatch/combine einsums.  Kept
                  as an alternative for the §Perf sharding comparison.
  * ``grouped`` — the decode-path default: the routed experts' FFNs run
                  through the shared jit-grouped primitive in
                  ``repro.kernels.moe_gemm`` with contributions gathered
                  per (row, top-k rank) and accumulated in fixed rank
                  order.  The grouped GEMM computes its stacked experts
                  densely over all rows (the *gather* is top-k sparse,
                  not the FLOPs — the deliberate price of per-pair bits
                  that never depend on batching).  This is the SAME
                  arithmetic the OD-MoE engine's wave compute consumes
                  from worker slots, which is what makes engine decode
                  token-bit-identical to ``greedy_generate`` *by
                  construction* rather than by accident of loop order.

The router also returns the per-token top-k expert ids — the signal the
OD-MoE engine and the SEP predictor consume.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.moe_gemm import combine_topk, grouped_topk_contrib
from repro.quant.quantize import EXPERT_WEIGHT_NAMES, QWeight, materialize

from .config import ModelConfig
from .layers import _dense_init, init_mlp, swiglu_mlp


# --------------------------------------------------------------------- init
def init_moe(key, cfg: ModelConfig) -> dict:
    """Router has ``num_experts`` outputs; expert weights carry
    ``num_experts_padded`` rows (pad rows are inert — never routed) so
    the expert axis divides the tensor-parallel mesh axis."""
    d, f, e = cfg.d_model, cfg.d_expert_resolved, cfg.num_experts
    ep = cfg.num_experts_padded
    kr, kg, ku, kd, ks = jax.random.split(key, 5)
    dt = jnp.dtype(cfg.dtype)
    p = {
        "router": _dense_init(kr, (d, e), dt),
        "w_gate": _dense_init(kg, (ep, d, f), dt),
        "w_up": _dense_init(ku, (ep, d, f), dt),
        "w_down": _dense_init(kd, (ep, f, d), dt),
    }
    if cfg.d_shared:
        p["shared"] = init_mlp(ks, d, cfg.d_shared, dt)
    return p


def shared_expert(params, x):
    """The shared expert's output for rows ``x`` (``None`` without one):
    every token runs it beside its routed experts."""
    if "shared" not in params:
        return None
    return swiglu_mlp(x, params["shared"])


def expert_chunk(num_experts: int) -> int:
    """Experts per block when a layer's experts are brought in blocks
    (prefill): the largest power of two up to 8 that divides the count,
    so every block has one shape and the grouped GEMM pads none."""
    g = 8
    while num_experts % g:
        g //= 2
    return g


def grouped_block_contrib(x, block, lo: int, topk_idx, topk_gate):
    """``grouped_topk_contrib`` for the experts ``lo .. lo + len`` of one
    block: pairs routed elsewhere are masked to exact zeros, so blocks
    add up to the one call over every expert."""
    g = block[0].shape[0]
    idx = topk_idx.astype(jnp.int32) - lo
    slot = jnp.where((idx >= 0) & (idx < g), idx, -1)
    return grouped_topk_contrib(x, *block, slot, topk_gate)


# ------------------------------------------------------------------- router
def route(cfg: ModelConfig, params, x) -> Tuple[jax.Array, jax.Array, dict]:
    """x: (N, d) -> (topk_idx (N,k), topk_gate (N,k), aux)."""
    logits = (x.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    topk_logits, topk_idx = jax.lax.top_k(logits, cfg.top_k)
    topk_gate = jax.nn.softmax(topk_logits, axis=-1)
    # Switch-style load-balance loss: E * sum_e f_e * p_e
    probs = jax.nn.softmax(logits, axis=-1)
    e = cfg.num_experts
    f_e = jnp.mean(
        jnp.sum(jax.nn.one_hot(topk_idx, e, dtype=jnp.float32), axis=1), axis=0)
    p_e = jnp.mean(probs, axis=0)
    lb_loss = e * jnp.sum(f_e * p_e) / cfg.top_k
    aux = {"load_balance_loss": lb_loss, "router_logits": logits}
    return topk_idx, topk_gate, aux


def capacity(cfg: ModelConfig, n_tokens: int, factor: float = None) -> int:
    factor = cfg.capacity_factor if factor is None else factor
    c = int(math.ceil(cfg.top_k * n_tokens / cfg.num_experts * factor))
    return max(c, 1)


# ----------------------------------------------------------------- dispatch
def moe_dense(cfg: ModelConfig, params, x) -> Tuple[jax.Array, dict]:
    """Exact dense dispatch.  x: (N, d)."""
    topk_idx, topk_gate, aux = route(cfg, params, x)
    e = cfg.num_experts
    gates = jnp.zeros((x.shape[0], e), x.dtype)
    gates = gates.at[jnp.arange(x.shape[0])[:, None], topk_idx].set(
        topk_gate.astype(x.dtype))
    wg, wu, wd = (params[k][:e] for k in ("w_gate", "w_up", "w_down"))
    h = jnp.einsum("nd,edf->enf", x, wg)
    u = jnp.einsum("nd,edf->enf", x, wu)
    y = jnp.einsum("enf,efd->end", jax.nn.silu(h) * u, wd)
    out = jnp.einsum("end,ne->nd", y, gates)
    aux["topk_idx"] = topk_idx
    return out, aux


def _slot_assignment(cfg: ModelConfig, topk_idx, topk_gate, cap: int):
    """Compute (token->slot) placement under per-expert capacity ``cap``.

    Returns flat ``slot_token`` (Ep*C,) token index feeding each slot,
    ``slot_gate`` / ``slot_valid`` (Ep*C,) and per-(token,k) ``kept``.
    Slots of padded experts (index >= num_experts) stay empty.
    """
    n, k = topk_idx.shape
    e = cfg.num_experts
    ep = cfg.num_experts_padded
    flat_expert = topk_idx.reshape(-1)                                   # (N*k,)
    onehot = jax.nn.one_hot(flat_expert, e, dtype=jnp.int32)             # (N*k,E)
    pos_in_expert = jnp.cumsum(onehot, axis=0) * onehot                  # 1-based
    pos = jnp.sum(pos_in_expert, axis=-1) - 1                            # (N*k,)
    kept = pos < cap
    slot = flat_expert * cap + jnp.where(kept, pos, 0)
    token_of = jnp.repeat(jnp.arange(n), k)
    slot_token = jnp.zeros((ep * cap,), jnp.int32)
    slot_gate = jnp.zeros((ep * cap,), topk_gate.dtype)
    slot_token = slot_token.at[jnp.where(kept, slot, ep * cap)].set(
        token_of, mode="drop")
    slot_gate = slot_gate.at[jnp.where(kept, slot, ep * cap)].set(
        topk_gate.reshape(-1), mode="drop")
    slot_valid = jnp.zeros((ep * cap,), bool).at[
        jnp.where(kept, slot, ep * cap)].set(True, mode="drop")
    return slot_token, slot_gate, slot_valid, kept


def moe_scatter(cfg: ModelConfig, params, x, cap_factor: float = None
                ) -> Tuple[jax.Array, dict]:
    """Capacity-based gather/GEMM/scatter dispatch.  x: (N, d)."""
    n, d = x.shape
    topk_idx, topk_gate, aux = route(cfg, params, x)
    cap = capacity(cfg, n, cap_factor)
    e = cfg.num_experts_padded
    slot_token, slot_gate, slot_valid, kept = _slot_assignment(
        cfg, topk_idx, topk_gate, cap)
    xd = jnp.take(x, slot_token, axis=0) * slot_valid[:, None].astype(x.dtype)
    xd = xd.reshape(e, cap, d)
    h = jnp.einsum("ecd,edf->ecf", xd, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xd, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, params["w_down"])
    y = (y.reshape(e * cap, d) * slot_gate[:, None].astype(x.dtype))
    out = jnp.zeros_like(x).at[slot_token].add(
        y * slot_valid[:, None].astype(x.dtype))
    aux["topk_idx"] = topk_idx
    aux["drop_fraction"] = 1.0 - jnp.mean(kept.astype(jnp.float32))
    return out, aux


def moe_einsum(cfg: ModelConfig, params, x, cap_factor: float = None
               ) -> Tuple[jax.Array, dict]:
    """GShard one-hot dispatch/combine einsums.  x: (N, d)."""
    n, d = x.shape
    topk_idx, topk_gate, aux = route(cfg, params, x)
    cap = capacity(cfg, n, cap_factor)
    e, k = cfg.num_experts_padded, cfg.top_k
    expert_oh = jax.nn.one_hot(topk_idx, e, dtype=jnp.float32)           # (N,k,E)
    pos = jnp.cumsum(expert_oh.reshape(n * k, e), axis=0).reshape(n, k, e)
    pos = (pos - 1.0) * expert_oh                                        # 0-based
    kept = (pos < cap) & (expert_oh > 0)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), cap, dtype=jnp.float32)
    dispatch = jnp.einsum("nke,nkec->nec",
                          expert_oh * kept.astype(jnp.float32), pos_oh)
    combine = jnp.einsum("nk,nke,nkec->nec",
                         topk_gate.astype(jnp.float32),
                         expert_oh * kept.astype(jnp.float32), pos_oh)
    xd = jnp.einsum("nd,nec->ecd", x.astype(jnp.float32), dispatch).astype(x.dtype)
    h = jnp.einsum("ecd,edf->ecf", xd, params["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", xd, params["w_up"])
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, params["w_down"])
    out = jnp.einsum("ecd,nec->nd", y.astype(jnp.float32), combine).astype(x.dtype)
    aux["topk_idx"] = topk_idx
    aux["drop_fraction"] = 1.0 - jnp.mean(
        jnp.sum(kept, axis=(1, 2)).astype(jnp.float32) / k)
    return out, aux


def moe_grouped(cfg: ModelConfig, params, x) -> Tuple[jax.Array, dict]:
    """Grouped top-k dispatch through the shared expert-FFN hot path.

    Routes ``x`` then runs ``repro.kernels.moe_gemm.
    grouped_topk_contrib`` on the stacked ``(E, d, f)`` expert weights
    — the top-k indices are themselves the slot map — and reduces with
    ``combine_topk``'s fixed rank-order accumulation.  As the reference
    it stacks ALL experts, so its FLOPs match ``dense`` (only the
    gather is top-k sparse); the win is one fused dispatch and, above
    all, the arithmetic contract: the OD-MoE engine feeds the same two
    functions only the wave's slot-resident experts, and per-pair bits
    are batching-independent, so reference and cacheless engine agree
    bit-for-bit.
    """
    topk_idx, topk_gate, aux = route(cfg, params, x)
    e = cfg.num_experts
    if isinstance(params["w_gate"], QWeight):
        contrib = _grouped_codes(params, x, topk_idx, topk_gate, e)
    else:
        wg, wu, wd = (params[k][:e] for k in ("w_gate", "w_up", "w_down"))
        contrib = grouped_topk_contrib(x, wg, wu, wd,
                                       topk_idx.astype(jnp.int32), topk_gate)
    out = combine_topk(contrib).astype(x.dtype)
    aux["topk_idx"] = topk_idx
    return out, aux


def _grouped_codes(params, x, topk_idx, topk_gate, e: int):
    """The grouped dispatch on experts held as codes (the SEP shadow):
    a few rows dequantize only the experts their pairs route to; many
    rows (prefill) dequantize the layer's experts one block at a time.
    Per-pair values do not depend on what rode along, so both agree
    with one call over every dequantized expert."""
    ws = [params[k] for k in EXPERT_WEIGHT_NAMES]
    n, k = topk_idx.shape
    if n * k <= e:
        flat = topk_idx.reshape(-1)
        slot = jnp.arange(n * k, dtype=jnp.int32).reshape(n, k)
        return grouped_topk_contrib(x, *(w.take(flat) for w in ws), slot,
                                    topk_gate)
    g = expert_chunk(e)
    contrib = None
    for lo in range(0, e, g):
        c = grouped_block_contrib(x, [w.block(lo, g) for w in ws], lo,
                                  topk_idx, topk_gate)
        contrib = c if contrib is None else contrib + c
    return contrib


DISPATCH = {"dense": moe_dense, "scatter": moe_scatter, "einsum": moe_einsum}


def moe_ff(cfg: ModelConfig, params, x2d, method="scatter",
           cap_factor: float = None) -> Tuple[jax.Array, dict]:
    """``method`` is a dispatch name or a callable
    ``(cfg, params, x2d) -> (out, aux)`` (e.g. the shard_map all-to-all
    dispatch from ``moe_a2a.make_moe_a2a``)."""
    if method != "grouped" and isinstance(params["w_gate"], QWeight):
        params = materialize(params, keep_routed=False)
    if callable(method):
        return method(cfg, params, x2d)
    if method == "dense":
        return moe_dense(cfg, params, x2d)
    if method == "grouped":
        return moe_grouped(cfg, params, x2d)
    return DISPATCH[method](cfg, params, x2d, cap_factor)

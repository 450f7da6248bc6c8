"""Expert-parallel MoE dispatch via shard_map + explicit all_to_all.

GSPMD lowers the capacity gather/scatter dispatch of ``moe_scatter``
through resharding heuristics that, inside layer/microbatch scans, can
move orders of magnitude more than the tokens themselves (EXPERIMENTS.md
§Perf: qwen3 train_4k residual ~2 TB/device).  This module bypasses the
partitioner: per-device token blocks are explicitly bucketed by
destination expert shard, exchanged with a single ``all_to_all`` each
way, and computed against the LOCAL expert shard — wire bytes are
exactly 2 x (routed token embeddings), the textbook EP cost.

Per-device layout inside the shard_map (mesh axes ("data","model")):
  x        : (n_loc, d)    tokens sharded over data, replicated on model
  experts  : rank m owns padded experts [m·epl, (m+1)·epl)
  send     : (tp, c_send, d) bucketed by destination rank  --all_to_all->
  recv     : (tp, c_send, d) tokens for MY experts          (and back)

Routing is computed identically on every model rank (x and router are
replicated across ``model``), so bucketing needs no extra agreement
step.  Over-capacity pairs drop to the residual path exactly like
``moe_scatter`` (same capacity-dispatch semantics, factored per rank).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig
from .moe import route


def _bucket_by_rank(dest, gate, token_of, local_expert, tp: int,
                    c_send: int):
    """Scatter (token,k) pairs into per-destination-rank buckets.

    dest/gate/token_of/local_expert: (N*k,).  Returns flat
    (tp*c_send,)-shaped slot arrays: token, valid, gate, local expert.
    """
    onehot = jax.nn.one_hot(dest, tp, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) * onehot
    rank_pos = jnp.sum(pos, axis=-1) - 1                       # (N*k,)
    kept = rank_pos < c_send
    tgt = jnp.where(kept, dest * c_send + jnp.where(kept, rank_pos, 0),
                    tp * c_send)
    slot_token = jnp.zeros((tp * c_send,), jnp.int32).at[tgt].set(
        token_of, mode="drop")
    slot_valid = jnp.zeros((tp * c_send,), bool).at[tgt].set(
        True, mode="drop")
    slot_gate = jnp.zeros((tp * c_send,), gate.dtype).at[tgt].set(
        gate, mode="drop")
    slot_le = jnp.zeros((tp * c_send,), jnp.int32).at[tgt].set(
        local_expert, mode="drop")
    return slot_token, slot_valid, slot_gate, slot_le


def _local_expert_ffn(recv_x, recv_le, recv_valid, wg, wu, wd,
                      epl: int, cap_loc: int):
    """Slot the received tokens by LOCAL expert id and run the FFN.

    recv_x: (S, d); recv_le: (S,) in [0, epl); returns y: (S, d)."""
    s, d = recv_x.shape
    onehot = jax.nn.one_hot(jnp.where(recv_valid, recv_le, epl), epl,
                            dtype=jnp.int32)
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    kept = (pos < cap_loc) & recv_valid
    slot = recv_le * cap_loc + jnp.where(kept, pos, 0)
    oob = epl * cap_loc
    tgt = jnp.where(kept, slot, oob)
    slot_src = jnp.zeros((epl * cap_loc,), jnp.int32).at[tgt].set(
        jnp.arange(s, dtype=jnp.int32), mode="drop")
    slot_valid = jnp.zeros((epl * cap_loc,), bool).at[tgt].set(
        True, mode="drop")
    xd = jnp.take(recv_x, slot_src, axis=0) \
        * slot_valid[:, None].astype(recv_x.dtype)
    xd = xd.reshape(epl, cap_loc, d)
    h = jnp.einsum("ecd,edf->ecf", xd, wg)
    u = jnp.einsum("ecd,edf->ecf", xd, wu)
    y = jnp.einsum("ecf,efd->ecd", jax.nn.silu(h) * u, wd)
    y = y.reshape(epl * cap_loc, d)
    out = jnp.zeros((s, d), recv_x.dtype).at[slot_src].add(
        jnp.where(slot_valid[:, None], y, 0).astype(recv_x.dtype))
    return out


def make_moe_a2a(mesh, cap_factor: float = 1.25):
    """Returns moe_ff(cfg, params, x2d) -> (out, aux) running the
    all-to-all expert dispatch on ``mesh`` axes ("data","model")."""
    tp = mesh.shape["model"]
    dp_axes = tuple(a for a in mesh.axis_names if a != "model")

    dp_size = 1
    for a in dp_axes:
        dp_size *= mesh.shape[a]

    def moe_a2a(cfg: ModelConfig, params, x):
        n, d = x.shape
        ep = cfg.num_experts_padded
        if ep % tp or n % dp_size:
            # shard_map needs exact divisibility (e.g. long_500k's single
            # token); fall back to the GSPMD capacity dispatch
            from .moe import moe_scatter
            return moe_scatter(cfg, params, x, cap_factor)
        epl = ep // tp
        k = cfg.top_k

        def local(x_loc, router, wg, wu, wd):
            n_loc = x_loc.shape[0]
            topk_idx, topk_gate, aux = route(cfg, {"router": router}, x_loc)
            c_send = max(
                int(-(-k * n_loc * cap_factor // tp)), 1)
            cap_loc = max(int(-(-k * n_loc * tp * cap_factor // ep)), 1)
            dest = (topk_idx // epl).reshape(-1)
            token_of = jnp.repeat(jnp.arange(n_loc, dtype=jnp.int32), k)
            slot_token, slot_valid, slot_gate, slot_le = _bucket_by_rank(
                dest, topk_gate.reshape(-1), token_of,
                (topk_idx % epl).reshape(-1), tp, c_send)
            send_x = (jnp.take(x_loc, slot_token, axis=0)
                      * slot_valid[:, None].astype(x_loc.dtype)
                      ).reshape(tp, c_send, d)
            # ---- exchange: tokens travel to their expert's shard
            recv_x = jax.lax.all_to_all(send_x, "model", 0, 0)
            recv_le = jax.lax.all_to_all(slot_le.reshape(tp, c_send),
                                         "model", 0, 0)
            recv_valid = jax.lax.all_to_all(slot_valid.reshape(tp, c_send),
                                            "model", 0, 0)
            y = _local_expert_ffn(
                recv_x.reshape(tp * c_send, d),
                recv_le.reshape(-1), recv_valid.reshape(-1),
                wg, wu, wd, epl, cap_loc)
            # ---- route results back to the owning token shard
            back = jax.lax.all_to_all(y.reshape(tp, c_send, d),
                                      "model", 0, 0).reshape(-1, d)
            out = jnp.zeros_like(x_loc).at[slot_token].add(
                back * (slot_gate * slot_valid.astype(slot_gate.dtype)
                        )[:, None].astype(x_loc.dtype))
            lb = aux["load_balance_loss"]
            if dp_axes:
                lb = jax.lax.pmean(lb, dp_axes)
            aux_out = {"load_balance_loss": lb, "topk_idx": topk_idx}
            return out, aux_out

        in_specs = (P(dp_axes, None), P(None, None),
                    P("model", None, None), P("model", None, None),
                    P("model", None, None))
        out_specs = (P(dp_axes, None),
                     {"load_balance_loss": P(), "topk_idx": P(dp_axes, None)})
        fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        return fn(x, params["router"], params["w_gate"], params["w_up"],
                  params["w_down"])

    return moe_a2a

"""Operations and bytes the model and its expert layer need.

Counted from shapes and from the routing the program recorded, never
from what an implementation happens to compute: padded experts, padded
rows and the SEP shadow's work are not the model's work.  (The per-token
arithmetic follows ``benchmarks/roofline.py::fwd_flops_per_token``.)
"""
from __future__ import annotations

from typing import Iterable, Tuple


def expert_flops(d: int, f: int) -> int:
    """One (row, expert) pair of a SwiGLU expert: gate, up and down."""
    return 2 * 3 * d * f


def expert_bytes(d: int, f: int, itemsize: int) -> int:
    return 3 * d * f * itemsize


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


def least_time_s(flops: float, nbytes: float, peak_flops: float,
                 peak_bytes_s: float) -> Tuple[float, str]:
    """The roofline: the larger of compute time and memory time at peak,
    and which of the two bounds it."""
    tc, tm = flops / peak_flops, nbytes / peak_bytes_s
    return (tc, "compute") if tc >= tm else (tm, "memory")


def gemm_call_work(d: int, f: int, itemsize: int, pairs: int,
                   experts: int, rows: int) -> Tuple[int, int]:
    """(FLOPs, bytes) a grouped expert GEMM call needs: ``pairs`` routed
    (row, expert) pairs, the weights of ``experts`` distinct routed
    experts read once, ``rows`` activations read and written."""
    return (pairs * expert_flops(d, f),
            experts * expert_bytes(d, f, itemsize) + 2 * rows * d * itemsize)


def wave_calls(records: Iterable, d: int, f: int, itemsize: int):
    """(FLOPs, bytes) of every decode-wave grouped GEMM call, from the
    program's per-layer records: each wave is one call over its experts,
    serving the (row, rank) pairs routed to them."""
    for rec in records:
        for lr in rec.layers:
            true = lr.true
            for wave in (lr.waves or []):
                experts = {e for e, _ in wave}
                if not experts:
                    continue
                pairs = int(sum(int(e) in experts for e in true.reshape(-1)))
                yield gemm_call_work(d, f, itemsize, pairs, len(experts),
                                     true.shape[0])

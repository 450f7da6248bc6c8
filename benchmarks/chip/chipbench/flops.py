"""Operations and bytes the grouped expert GEMM needs, and the roofline.

Counted from shapes and from the routing the program recorded, never
from what an implementation happens to compute: padded experts, padded
rows and the SEP shadow's work are not the model's work.  The whole
model's count per token is its architecture plug-in's
(``arch/<model_type>.py``: ``prompt_flops``, ``decode_flops``).
"""
from __future__ import annotations

from typing import Iterable, Tuple


def expert_flops(d: int, f: int) -> int:
    """One (row, expert) pair of a SwiGLU expert: gate, up and down."""
    return 2 * 3 * d * f


def expert_bytes(d: int, f: int, itemsize: int) -> int:
    return 3 * d * f * itemsize


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

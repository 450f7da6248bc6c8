"""jit'd public wrappers for the grouped expert FFN.

``moe_ffn`` is the raw (E, C, D) -> (E, C, D) grouped GEMM: kernel on
TPU, interpret-mode kernel or oracle fallback on CPU.

``grouped_topk_contrib`` / ``combine_topk`` are the system's ONE
expert-FFN hot path: every decode-time consumer — the OD-MoE engine's
wave compute, the reference ``greedy_generate`` dispatch
(``models/moe.py::moe_grouped``) and the SEP shadow — routes its
routed-expert arithmetic through these two jitted functions, so
engine ≡ reference holds because both consume *identical* arithmetic,
not by accident of Python loop order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import moe_ffn_kernel
from .packed import moe_ffn_packed_kernel
from .ref import moe_ffn_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def moe_ffn(xd, w_gate, w_up, w_down, *, block_c: int = 128,
            block_f=None, force_kernel: bool = False,
            interpret: bool | None = None):
    """Grouped expert FFN; see kernel.py for the tiling contract."""
    if interpret is None:
        interpret = not _on_tpu()
    if not _on_tpu() and not force_kernel:
        return moe_ffn_ref(xd, w_gate, w_up, w_down)
    return moe_ffn_kernel(xd, w_gate, w_up, w_down, block_c=block_c,
                          block_f=block_f, interpret=interpret)


def moe_ffn_packed(xd, parts, *, scheme: str, block_c: int = 128,
                   block_f=None, force_kernel: bool = False,
                   interpret: bool | None = None):
    """Grouped expert FFN on WIRE-format stacked weights (the packed-
    weights carrier): ``parts`` maps w_gate/w_up/w_down to device-layout
    part tuples with a leading stacked-expert axis.

    TPU (or ``force_kernel``) runs the fused in-kernel-dequant Pallas
    kernel; the CPU fallback dequantizes the stack elementwise
    (``repro.quant.quantize.dequantize_tiles`` — the exact arithmetic
    of dequantize-on-arrival) and calls the same oracle ``moe_ffn``
    uses, so both paths are bit-identical to computing on round-tripped
    full-width weights."""
    if scheme == "fp32":
        return moe_ffn(xd, parts["w_gate"][0], parts["w_up"][0],
                       parts["w_down"][0], block_c=block_c,
                       block_f=block_f, force_kernel=force_kernel,
                       interpret=interpret)
    if interpret is None:
        interpret = not _on_tpu()
    if not _on_tpu() and not force_kernel:
        from repro.quant.quantize import dequantize_tiles
        return moe_ffn_ref(xd,
                           dequantize_tiles(scheme, parts["w_gate"]),
                           dequantize_tiles(scheme, parts["w_up"]),
                           dequantize_tiles(scheme, parts["w_down"]))
    return moe_ffn_packed_kernel(xd, parts, scheme=scheme,
                                 block_c=block_c, block_f=block_f,
                                 interpret=interpret)


# ------------------------------------------------- top-k decode hot path
def _pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def _pad_expert_axis(arr, ep: int):
    es = arr.shape[0]
    if ep == es:
        return arr
    return jnp.pad(arr, ((0, ep - es),) + ((0, 0),) * (arr.ndim - 1))


@jax.jit
def _grouped_contrib(h, w_gate, w_up, w_down, slot, gates):
    """Traced body of :func:`grouped_topk_contrib` (rows pre-padded).

    The stacked-expert axis pads to its pow2 bucket HERE, inside the
    trace: XLA compiles the pad into the executable, so no decode wave
    ever copies the full weight stack host-side before dispatch (it
    used to — one eager ``jnp.pad`` per weight per wave).  Padded
    experts are all-zero and are never selected by ``slot``, so the
    pad is arithmetic-invisible."""
    x32 = h.astype(jnp.float32)
    n = x32.shape[0]
    ep = _pow2(max(w_gate.shape[0], 1))
    w_gate = _pad_expert_axis(w_gate, ep)
    w_up = _pad_expert_axis(w_up, ep)
    w_down = _pad_expert_axis(w_down, ep)
    xd = jnp.broadcast_to(x32[None], (ep,) + x32.shape)
    y = moe_ffn(xd, w_gate, w_up, w_down)            # (Ep, N, d) fp32
    valid = slot >= 0
    safe = jnp.where(valid, slot, 0)
    rows = jnp.arange(n)[:, None]                    # (N, 1)
    picked = y[safe, rows]                           # (N, k, d)
    return jnp.where(valid[..., None],
                     gates.astype(jnp.float32)[..., None] * picked, 0.0)


@functools.partial(jax.jit, static_argnames=("scheme",))
def _grouped_contrib_packed(h, parts, slot, gates, *, scheme):
    """Packed-carrier twin of :func:`_grouped_contrib`: identical
    gather/mask/gate arithmetic around ``moe_ffn_packed``.  Zero-padded
    experts dequantize to zero weights (int8: 0*0; nf4: LUT[0] * 0)
    and are never selected."""
    x32 = h.astype(jnp.float32)
    n = x32.shape[0]
    ep = _pow2(max(parts["w_gate"][0].shape[0], 1))
    parts = {name: tuple(_pad_expert_axis(p, ep) for p in ps)
             for name, ps in parts.items()}
    xd = jnp.broadcast_to(x32[None], (ep,) + x32.shape)
    y = moe_ffn_packed(xd, parts, scheme=scheme)     # (Ep, N, d) fp32
    valid = slot >= 0
    safe = jnp.where(valid, slot, 0)
    rows = jnp.arange(n)[:, None]
    picked = y[safe, rows]
    return jnp.where(valid[..., None],
                     gates.astype(jnp.float32)[..., None] * picked, 0.0)


def grouped_topk_contrib(h, w_gate, w_up, w_down, slot, gates):
    """Gate-weighted expert-FFN contributions for a routed top-k batch.

    ``h``: (N, d) rows; ``w_gate``/``w_up``: (Es, d, f) and ``w_down``:
    (Es, f, d) stacked expert weights; ``slot``: (N, k) int32 index of
    each (row, rank) pair's expert in the stacked axis, ``-1`` when that
    pair's expert is not part of this call (e.g. it computes in a later
    engine wave); ``gates``: (N, k) gate weights.  Returns (N, k, d)
    fp32 contributions — zeros at masked pairs — whose per-pair values
    are independent of which other experts/rows rode along (each row of
    each expert's GEMM is its own dot product), so wave partitioning can
    never change a request's arithmetic.

    Cost note: the grouped GEMM computes every stacked expert over
    every row and the top-k sparsity is applied by the *gather* — the
    deliberate trade that buys batching-independent bits and one fused
    dispatch.  Callers control the FLOPs by what they stack: the engine
    stacks only a wave's routed, slot-resident experts; the reference
    dispatch stacks all ``E`` (dense-equivalent FLOPs, as before).

    The row axis is padded to its power-of-two bucket OUTSIDE the
    jitted body (cheap: h/slot/gates only) so arbitrary batch sizes
    fold onto a handful of compiled shapes; the stacked-expert axis
    pads to its bucket INSIDE the trace (see ``_grouped_contrib``), so
    the weight stack is never copied eagerly.  Compiled-shape count =
    (#row buckets) x (#distinct wave sizes), pinned by
    tests/test_packed_kernel.py.
    """
    n, _ = slot.shape
    np_ = _pow2(max(n, 1))
    if np_ != n:
        h = jnp.pad(h, ((0, np_ - n), (0, 0)))
        slot = jnp.pad(slot, ((0, np_ - n), (0, 0)), constant_values=-1)
        gates = jnp.pad(gates, ((0, np_ - n), (0, 0)))
    out = _grouped_contrib(h, w_gate, w_up, w_down, slot, gates)
    return out[:n] if np_ != n else out


def grouped_topk_contrib_packed(h, parts, slot, gates, *, scheme: str):
    """:func:`grouped_topk_contrib` on the packed-weights carrier:
    ``parts`` stacks each wave expert's tile-aligned wire parts
    (codes + scales) instead of full-width fp32.  Same contract, same
    row bucketing, bit-identical contributions — in-kernel dequant is
    elementwise-exact, so per-(row, rank) values still cannot depend on
    wave composition.  ``scheme='fp32'`` delegates to the full-width
    path (a packed-resident fp32 slot IS the full-width weight)."""
    if scheme == "fp32":
        return grouped_topk_contrib(h, parts["w_gate"][0],
                                    parts["w_up"][0], parts["w_down"][0],
                                    slot, gates)
    n, _ = slot.shape
    np_ = _pow2(max(n, 1))
    if np_ != n:
        h = jnp.pad(h, ((0, np_ - n), (0, 0)))
        slot = jnp.pad(slot, ((0, np_ - n), (0, 0)), constant_values=-1)
        gates = jnp.pad(gates, ((0, np_ - n), (0, 0)))
    out = _grouped_contrib_packed(h, parts, slot, gates, scheme=scheme)
    return out[:n] if np_ != n else out


@jax.jit
def combine_topk(contrib):
    """Reduce (N, k, d) contributions to (N, d) in *fixed top-k rank
    order* — the accumulation order every decode path shares.  The
    unrolled loop pins the floating-point summation tree so the result
    is independent of how contributions were produced (one grouped call
    or several engine waves)."""
    y = contrib[:, 0]
    for j in range(1, contrib.shape[1]):
        y = y + contrib[:, j]
    return y

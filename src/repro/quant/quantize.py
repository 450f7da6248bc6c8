"""Weight quantization for the SEP shadow model: FP16 / INT8 / NF4.

The shadow model in OD-MoE is the full model quantized to a cheaper
precision.  We implement real quantize->dequantize so the shadow model's
numerics (and therefore its expert-routing divergence, the quantity the
paper studies) are faithful:

  * fp16  — plain dtype cast.
  * int8  — symmetric per-output-channel (last axis) scaling.
  * nf4   — 4-bit NormalFloat with per-block (64) absmax scaling, the
            QLoRA code-book.

``quantize``/``dequantize`` expose the packed representation (used by the
int8 Pallas shadow matmul kernel); ``simulate_quantization`` returns a
float tensor carrying the quantization error (used for SEP experiments
where we only care about numerics, not memory).
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.kernels.moe_gemm.packed import NF4_BLOCK, NF4_TABLE

NF4_LEVELS = jnp.array(NF4_TABLE, dtype=jnp.float32)


# ----------------------------------------------------------------- int8
def quantize_int8(w) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-channel (last axis) int8.  Returns (q, scale)."""
    absmax = jnp.max(jnp.abs(w), axis=tuple(range(w.ndim - 1)), keepdims=True)
    scale = jnp.maximum(absmax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(w / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_int8(q, scale):
    return q.astype(jnp.float32) * scale


# ------------------------------------------------------------------ nf4
def quantize_nf4(w) -> Tuple[jax.Array, jax.Array]:
    """Blockwise (64) absmax NF4.  Returns (codes uint8, scales)."""
    flat = w.reshape(-1)
    pad = (-flat.shape[0]) % NF4_BLOCK
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, NF4_BLOCK).astype(jnp.float32)
    absmax = jnp.maximum(jnp.max(jnp.abs(blocks), axis=1, keepdims=True), 1e-8)
    normed = blocks / absmax
    codes = jnp.argmin(
        jnp.abs(normed[..., None] - NF4_LEVELS[None, None, :]), axis=-1)
    return codes.astype(jnp.uint8), absmax.astype(jnp.float32)


def dequantize_nf4(codes, scales, shape):
    vals = NF4_LEVELS[codes.astype(jnp.int32)] * scales
    n = 1
    for s in shape:
        n *= s
    return vals.reshape(-1)[:n].reshape(shape)


def pack_nf4_codes(codes):
    """Bit-pack NF4 codes (values 0..15) two per byte, high nibble
    first.  ``codes`` is the (n_blocks, 64) uint8 array from
    ``quantize_nf4``; the flat length is always even (64-blocks), so the
    packing is exact and lossless."""
    flat = codes.reshape(-1).astype(jnp.uint8)
    return (flat[0::2] << 4) | (flat[1::2] & 0xF)


def unpack_nf4_codes(packed, n_blocks: int):
    """Inverse of ``pack_nf4_codes``: (n_pairs,) uint8 -> (n_blocks, 64)
    codes.  Lossless, so transport bit-packing never changes numerics."""
    hi = (packed >> 4) & 0xF
    lo = packed & 0xF
    flat = jnp.stack([hi, lo], axis=1).reshape(-1)
    return flat.reshape(n_blocks, NF4_BLOCK)


# ----------------------------------------- tile-aligned device layout
def nf4_pair_unpack(codes):
    """Unpack device-layout nf4 bytes along the LAST axis: ``(..., m)``
    packed bytes -> ``(..., 2m)`` 4-bit codes, high nibble first — the
    same bit order as :func:`unpack_nf4_codes`, so the two layouts
    decode identical code streams.  Works under arbitrary leading batch
    dims (stacked expert tiles)."""
    c = jnp.asarray(codes)
    hi = (c >> 4) & 0xF
    lo = c & 0xF
    return jnp.stack([hi, lo], axis=-1).reshape(
        c.shape[:-1] + (c.shape[-1] * 2,))


def dequantize_tiles(scheme: str, parts):
    """Elementwise dequantization of tile-aligned device-layout parts
    (see ``repro.quant.transport.device_layout``), with arbitrary
    leading batch dims (a stacked wave of experts dequantizes in one
    call).  Per element this is the SAME fp32 arithmetic as the wire-
    side ``dequantize`` — int8 ``code * scale``, nf4 ``LUT[code] *
    block_absmax`` — applied to the same (code, scale) pairs, so the
    result is bit-identical to dequantize-on-arrival; only the array
    layout the math reads from differs."""
    if scheme == "fp32":
        return jnp.asarray(parts[0])
    if scheme == "fp16":
        return parts[0].astype(jnp.float32)
    if scheme == "int8":
        return parts[0].astype(jnp.float32) * parts[1]
    if scheme == "nf4":
        codes = nf4_pair_unpack(parts[0]).astype(jnp.int32)
        scales = jnp.repeat(jnp.asarray(parts[1]), NF4_BLOCK, axis=-1)
        return NF4_LEVELS[codes] * scales
    raise ValueError(f"unknown scheme {scheme!r}")


# ------------------------------------------------------------- dispatch
def quantize(w, scheme: str):
    if scheme == "fp16":
        return (w.astype(jnp.float16),)
    if scheme == "int8":
        return quantize_int8(w)
    if scheme == "nf4":
        return quantize_nf4(w) + (w.shape,)
    raise ValueError(f"unknown scheme {scheme!r}")


def dequantize(packed, scheme: str):
    if scheme == "fp16":
        return packed[0].astype(jnp.float32)
    if scheme == "int8":
        return dequantize_int8(*packed)
    if scheme == "nf4":
        return dequantize_nf4(*packed)
    raise ValueError(f"unknown scheme {scheme!r}")


def simulate_quantization(w, scheme: str):
    """Quantize-dequantize round trip (float tensor with quant error)."""
    if scheme in ("fp32", "none"):
        return w
    return dequantize(quantize(w, scheme), scheme).astype(w.dtype)


_MIN_QUANT_SIZE = 256  # leave norms / small vectors in full precision


def quantize_pytree(params, scheme: str):
    """Quantize every large weight leaf; small leaves stay fp32."""
    def one(w):
        if w.ndim >= 2 and w.size >= _MIN_QUANT_SIZE and jnp.issubdtype(
                w.dtype, jnp.floating):
            return simulate_quantization(w, scheme)
        return w
    return jax.tree.map(one, params)


def shadow_params(params, scheme: str):
    """The SEP shadow model's parameters: quantized view of the full set."""
    return quantize_pytree(params, scheme)


def shadow_nbytes(params, scheme: str) -> int:
    """Deployed byte footprint of ``shadow_params(params, scheme)``.

    Walks the same per-leaf decision as :func:`quantize_pytree`: leaves
    that quantize are charged the scheme's *exact* packed size — codes
    plus scales, via the transport codec's closed-form accounting, which
    tests pin byte-equal to a real ``pack`` — while the leaves that stay
    full precision (norms, small vectors, non-float buffers) are charged
    their real ``nbytes``.  This replaces the old hard-coded
    ``{fp16: 0.5, int8: 0.25, nf4: 0.125}`` fraction table, which was
    wrong whenever any leaf skipped quantization (and ignored scale
    payloads entirely).
    """
    from .transport import get_codec             # deferred: avoids cycle
    codec = get_codec("fp32" if scheme in ("fp32", "none") else scheme)
    total = 0
    for w in jax.tree.leaves(params):
        if w.ndim >= 2 and w.size >= _MIN_QUANT_SIZE and jnp.issubdtype(
                w.dtype, jnp.floating):
            total += codec.packed_nbytes(tuple(int(s) for s in w.shape),
                                         elem_bytes=w.dtype.itemsize)
        else:
            total += int(w.size) * w.dtype.itemsize
    return total

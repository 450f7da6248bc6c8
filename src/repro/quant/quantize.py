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
float tensor carrying the quantization error (the numerics oracle).
``shadow_codes`` is what the SEP shadow holds: every leaf the scheme
quantizes kept as those same codes and scales (``QWeight``), which the
model dequantizes a layer at a time, and a routed expert only when a
token is routed to it.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


# ------------------------------------------------------------ shadow codes
EXPERT_WEIGHT_NAMES = ("w_gate", "w_up", "w_down")   # one routed expert


@jax.tree_util.register_dataclass
@dataclass(frozen=True)
class QWeight:
    """One weight held as its quantized codes: ``dequant()`` gives the
    values ``simulate_quantization`` gives, element for element.

    ``codes`` keeps the weight's leading axes, so a stacked leaf slices
    per layer (``lax.scan``) and per expert (``take``).  int8: codes of
    the weight's shape and one scale per output channel, broadcast over
    the stacked-layer axis; fp16: the half-precision values; nf4: the
    64-blocks unfolded as ``lead + (blocks, 64)`` with a scale per block,
    where ``tail`` is the logical shape past ``lead``."""
    codes: jax.Array
    scale: Optional[jax.Array]
    scheme: str = field(metadata=dict(static=True))
    dtype: str = field(metadata=dict(static=True))
    tail: Tuple[int, ...] = field(metadata=dict(static=True))

    @property
    def shape(self) -> Tuple[int, ...]:
        if self.scheme == "nf4":
            return tuple(self.codes.shape[:-2]) + self.tail
        return tuple(self.codes.shape)

    def _values(self, codes, scale):
        if self.scheme == "fp16":
            w = codes.astype(jnp.float32)
        elif self.scheme == "int8":
            w = dequantize_int8(codes, scale)
        else:
            lead = tuple(codes.shape[:-2])
            n = int(np.prod(self.tail))
            w = (NF4_LEVELS[codes.astype(jnp.int32)] * scale).reshape(
                lead + (-1,))[..., :n].reshape(lead + self.tail)
        return w.astype(jnp.dtype(self.dtype))

    def dequant(self):
        return self._values(self.codes, self.scale)

    def take(self, idx):
        """Dequantized entries ``idx`` of the leading axis."""
        if self.scheme == "nf4" and self.codes.ndim == 2:   # flat blocks
            return self.dequant()[idx]
        scale = self.scale[idx] if self.scheme == "nf4" else self.scale
        return self._values(self.codes[idx], scale)

    def block(self, lo: int, n: int):
        """Dequantized entries ``lo:lo + n`` of the leading axis."""
        scale = (self.scale[lo:lo + n] if self.scheme == "nf4"
                 else self.scale)
        return self._values(self.codes[lo:lo + n], scale)


def _quantizable(w) -> bool:
    return (w.ndim >= 2 and w.size >= _MIN_QUANT_SIZE
            and jnp.issubdtype(w.dtype, jnp.floating))


_absmax = jax.jit(lambda w: jnp.max(jnp.abs(w), axis=tuple(
    range(w.ndim - 1)), keepdims=True))
_int8_round = jax.jit(lambda w, scale: jnp.clip(
    jnp.round(w / scale), -127, 127).astype(jnp.int8))
_nf4_codes = jax.jit(quantize_nf4)
_half = jax.jit(lambda w: w.astype(jnp.float16))


def _codes(w, scheme: str):
    """``quantize(w, scheme)``'s codes and scales, with the passes over
    the whole weight compiled (no full-size temporaries).  The int8
    scale is the same eager arithmetic as ``quantize_int8``: compiled,
    XLA would divide by 127 as a multiply by its reciprocal."""
    if scheme == "int8":
        scale = jnp.maximum(_absmax(w), 1e-8) / 127.0
        return _int8_round(w, scale), scale.astype(jnp.float32)
    if scheme == "nf4":
        return _nf4_codes(w)
    return _half(w), None


def quantize_leaf(w, scheme: str, stacked: bool = False):
    """``w`` as the shadow holds it: a ``QWeight`` of its codes, or, for
    a leaf the scheme leaves alone, ``w`` itself.  ``stacked``: the
    leading axis is the stacked-layer axis every piece must keep.  A
    host ``numpy`` leaf is quantized on the device and only its codes
    stay there.  An nf4 leaf whose blocks straddle its leading slices
    keeps its dequantized values instead."""
    if scheme in ("fp32", "none") or not _quantizable(w):
        return w
    codes, scale = _codes(jnp.asarray(w), scheme)
    dtype = str(w.dtype)
    if scheme == "int8":
        if stacked:
            scale = jnp.broadcast_to(scale, (w.shape[0],)
                                     + scale.shape[1:])
        return QWeight(codes, scale, scheme, dtype, ())
    if scheme == "fp16":
        return QWeight(codes, None, scheme, dtype, ())
    lead = max((k for k in range(1, w.ndim)
                if int(np.prod(w.shape[k:])) % NF4_BLOCK == 0), default=0)
    if lead == 0:
        return (dequantize_nf4(codes, scale, w.shape).astype(w.dtype)
                if stacked else QWeight(codes, scale, scheme, dtype,
                                        tuple(w.shape)))
    blocks = int(np.prod(w.shape[lead:])) // NF4_BLOCK
    head = tuple(w.shape[:lead]) + (blocks,)
    return QWeight(codes.reshape(head + (NF4_BLOCK,)),
                   scale.reshape(head + (1,)), scheme, dtype,
                   tuple(w.shape[lead:]))


def shadow_codes(params, scheme: str):
    """The SEP shadow's weights: ``params`` with every leaf the scheme
    quantizes held as a ``QWeight`` (one leaf at a time, so a host leaf
    never has more than itself on the device)."""
    out = {}
    for k, v in params.items():
        stacked = k == "layers"
        out[k] = jax.tree.map(
            lambda w, s=stacked: quantize_leaf(w, scheme, stacked=s), v)
    return out


def materialize(tree, keep_routed: bool = True):
    """``tree`` with each ``QWeight`` dequantized, except (with
    ``keep_routed``) a routed-expert layer's expert weights, which the
    MoE dispatch dequantizes only where tokens are routed."""
    if isinstance(tree, QWeight):
        return tree.dequant()
    if isinstance(tree, dict):
        keep = keep_routed and "router" in tree
        return {k: v if keep and k in EXPERT_WEIGHT_NAMES
                else materialize(v, keep_routed) for k, v in tree.items()}
    if isinstance(tree, (tuple, list)):
        return type(tree)(materialize(v, keep_routed) for v in tree)
    return tree


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
    payloads entirely).  A ``QWeight`` (``shadow_codes``) is charged as
    the leaf it stands for.
    """
    from .transport import get_codec             # deferred: avoids cycle
    codec = get_codec("fp32" if scheme in ("fp32", "none") else scheme)
    total = 0
    for w in jax.tree.leaves(params,
                             is_leaf=lambda x: isinstance(x, QWeight)):
        if isinstance(w, QWeight):
            total += codec.packed_nbytes(
                w.shape, elem_bytes=jnp.dtype(w.dtype).itemsize)
        elif _quantizable(w):
            total += codec.packed_nbytes(tuple(int(s) for s in w.shape),
                                         elem_bytes=w.dtype.itemsize)
        else:
            total += int(w.size) * w.dtype.itemsize
    return total

"""Packed-weight grouped expert-FFN Pallas kernel (in-kernel dequant).

The packed sibling of kernel.py: identical ``(E, C/Cb, F/Fb)`` grid,
tiling rule (``pick_tiles``, counting weight tiles at their wire
width), ragged-F masking and fp32-accumulator contract, but the weight
operands arrive in WIRE format — fp16 halves, int8 codes + per-channel
scales, or bit-packed nf4 codes + per-block absmax — and are dequantized
in-register immediately before the MXU dots.  HBM->VMEM therefore
streams packed tiles (2x / 4x / ~8x fewer weight bytes than the fp32
kernel), which is where OD-MoE's Eq. (1) bandwidth term actually goes.

Bit-exactness (the load-bearing invariant): dequantization is
ELEMENTWISE — int8 is ``code.astype(f32) * scale``, nf4 is
``NF4_LEVELS[code] * block_absmax`` — so performing it per-tile inside
the kernel reproduces, bit-for-bit, the full-width weights the
dequantize-on-arrival path materializes.  The dots then see identical
operands in the identical tile order, making the fused kernel
bit-identical to ``moe_ffn_kernel`` on pre-dequantized weights (pinned
by tests/test_packed_kernel.py).  Fusing moves WHERE the multiply
happens, never its value.

Tile layout (see ``repro.quant.transport.device_layout``):

  * int8 — codes keep the weight's shape; the per-output-channel scale
    row ``(1, last)`` slices along the same Fb blocks as the codes.
  * nf4 — codes ``(d, f/2)`` hold two f-adjacent 4-bit codes per byte
    (high nibble first); absmax ``(d, f/64)`` holds one scale per
    contiguous 64-column run.  Tiles must therefore cut f on multiples
    of ``NF4_BLOCK`` — the wrapper enforces ``block_f % 64 == 0`` and a
    64-aligned logical f (misaligned shapes use the dequantize-on-
    arrival fallback upstream, never this kernel).

int8 compiles for TPU v5e at Granite and Mixtral widths.  nf4 does
not: its ``(d, Fb/64)`` absmax block has a lane dim that is
neither a multiple of 128 nor the full dim at Mixtral widths, which the
Pallas TPU lowering refuses, and where it is the full dim (Granite) the
Mosaic compile does not finish.  nf4 runs in interpret mode only
(tests/test_chip_compile.py pins the refusal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams

from .kernel import _mask_ragged_f, pick_tiles

_PARTS = {"fp16": 1, "int8": 2, "nf4": 2}
# bytes one weight element streams at (codes; the scale rows are small)
_WIRE_BYTES = {"fp16": 2, "int8": 1, "nf4": 0.5 + 4 / 64}
# The 16 NormalFloat-4 levels of QLoRA (Dettmers et al., 2023) and the
# absmax block length.  Python floats, so the kernel trace closes over
# constants; ``repro.quant.quantize`` builds its NF4_LEVELS from these.
NF4_TABLE = (
    -1.0, -0.6961928009986877, -0.5250730514526367, -0.39491748809814453,
    -0.28444138169288635, -0.18477343022823334, -0.09105003625154495, 0.0,
    0.07958029955625534, 0.16093020141124725, 0.24611230194568634,
    0.33791524171829224, 0.44070982933044434, 0.5626170039176941,
    0.7229568362236023, 1.0)
NF4_BLOCK = 64


def _dequant_tile(scheme: str, refs):
    """In-register dequant of one weight tile from its packed refs."""
    if scheme == "fp16":
        return refs[0][0].astype(jnp.float32)
    if scheme == "int8":
        # per-output-channel scale: (R, Cb) codes * (1, Cb) scales
        return refs[0][0].astype(jnp.float32) * refs[1][0]
    # nf4: unpack nibbles (high first) along the last axis, 16-way
    # branch-free LUT on the VPU, then the per-64-block absmax.  Exactly
    # one where-arm matches per element, so this reproduces
    # NF4_LEVELS[code] * absmax bit-for-bit.
    table = NF4_TABLE
    c = refs[0][0].astype(jnp.int32)                  # (R, Cb/2)
    hi = (c >> 4) & 0xF
    lo = c & 0xF
    idx = jnp.stack([hi, lo], axis=-1).reshape(
        c.shape[0], c.shape[1] * 2)                   # (R, Cb)
    levels = jnp.full(idx.shape, table[0], jnp.float32)
    for v in range(1, 16):
        levels = jnp.where(idx == v, table[v], levels)
    scales = jnp.repeat(refs[1][0], NF4_BLOCK, axis=-1)
    return levels * scales


def _make_packed_kernel(scheme: str, total_f: int, block_f: int):
    npart = _PARTS[scheme]

    def _kernel(*refs):
        x_ref, o_ref = refs[0], refs[-1]
        w = refs[1:-1]
        fi = pl.program_id(2)
        x = x_ref[0]                                   # (Cb, D)
        wg = _dequant_tile(scheme, w[0:npart])         # (D, Fb)
        wu = _dequant_tile(scheme, w[npart:2 * npart])
        wd = _dequant_tile(scheme, w[2 * npart:])      # (Fb, D)
        # same ragged-F zeroing as the fp32 kernel: an out-of-bounds
        # final tile dequantizes padding garbage, masked before the dots
        if total_f % block_f:
            wg, wu, wd = _mask_ragged_f(fi, total_f, block_f, wg, wu, wd)
        h = jax.nn.silu(jnp.dot(x, wg, preferred_element_type=jnp.float32))
        u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
        y = jnp.dot((h * u).astype(x.dtype), wd,
                    preferred_element_type=jnp.float32)

        @pl.when(fi == 0)
        def _init():
            o_ref[0] = y.astype(o_ref.dtype)

        @pl.when(fi > 0)
        def _acc():
            o_ref[0] += y.astype(o_ref.dtype)

    return _kernel


def _weight_specs(scheme: str, d: int, bf: int):
    """BlockSpecs for (gate parts..., up parts..., down parts...).

    Gate/up tiles cut the logical f axis at ``fi``; down tiles cut
    their leading f axis at ``fi`` with the full D minor axis.  Packed
    parts slice the SAME logical Fb blocks, just at their own widths
    (codes at f/2, nf4 absmax at f/64, int8 scales at the scale row).
    """
    up = [pl.BlockSpec((1, d, bf), lambda e_, ci, fi: (e_, 0, fi))]
    down = [pl.BlockSpec((1, bf, d), lambda e_, ci, fi: (e_, fi, 0))]
    if scheme == "int8":
        up.append(pl.BlockSpec((1, 1, bf), lambda e_, ci, fi: (e_, 0, fi)))
        down.append(pl.BlockSpec((1, 1, d), lambda e_, ci, fi: (e_, 0, 0)))
    elif scheme == "nf4":
        up = [pl.BlockSpec((1, d, bf // 2),
                           lambda e_, ci, fi: (e_, 0, fi)),
              pl.BlockSpec((1, d, bf // NF4_BLOCK),
                           lambda e_, ci, fi: (e_, 0, fi))]
        down = [pl.BlockSpec((1, bf, d // 2),
                             lambda e_, ci, fi: (e_, fi, 0)),
                pl.BlockSpec((1, bf, d // NF4_BLOCK),
                             lambda e_, ci, fi: (e_, fi, 0))]
    return up + up + down


def packed_logical_f(scheme: str, parts) -> int:
    """Recover the logical expert width f from stacked packed parts."""
    last = parts["w_gate"][0].shape[-1]
    return last * 2 if scheme == "nf4" else last


@functools.partial(jax.jit, static_argnames=("scheme", "block_c",
                                             "block_f", "interpret"))
def moe_ffn_packed_kernel(xd, parts, *, scheme: str, block_c: int = 128,
                          block_f=None, interpret: bool = False):
    """xd: (E, C, D) -> (E, C, D) on wire-format stacked weights.

    ``parts`` maps w_gate/w_up/w_down to their device-layout part
    tuples with a leading stacked-expert axis (what
    ``WorkerSlots.gather_stack_packed`` produces).  Same grid and
    accumulator contract as ``moe_ffn_kernel``.
    """
    if scheme not in _PARTS:
        raise ValueError(f"no packed kernel for scheme {scheme!r}")
    e, c, d = xd.shape
    f = packed_logical_f(scheme, parts)
    bc, bf = pick_tiles(c, d, f, x_bytes=xd.dtype.itemsize,
                        w_bytes=_WIRE_BYTES[scheme], block_c=block_c,
                        block_f=block_f)
    if scheme == "nf4" and (f % NF4_BLOCK or bf % NF4_BLOCK
                            or d % NF4_BLOCK):
        raise ValueError("nf4 packed kernel needs f, d and block_f "
                         "aligned to the 64-element absmax block; "
                         f"got f={f}, d={d}, block_f={bf}")
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf))
    operands = [xd] + [p for name in ("w_gate", "w_up", "w_down")
                       for p in parts[name]]
    in_specs = ([pl.BlockSpec((1, bc, d), lambda e_, ci, fi: (e_, ci, 0))]
                + _weight_specs(scheme, d, bf))
    return pl.pallas_call(
        _make_packed_kernel(scheme, f, bf),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc, d), lambda e_, ci, fi: (e_, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, d), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)

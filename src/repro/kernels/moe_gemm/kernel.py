"""Grouped expert-FFN Pallas kernel (gather-GEMM-scatter inner GEMMs).

TPU-native analogue of OD-MoE's cacheless loading: for each routed
expert, ONLY that expert's weight tiles stream HBM->VMEM while the tile
is being consumed — no expert weights are ever resident beyond the tile
in flight (the VMEM working set is the "<1 GB worker slot").

Computes, for dispatched activations xd: (E, C, D) and expert weights
w_gate/w_up: (E, D, F), w_down: (E, F, D):

    y[e] = (silu(xd[e] @ w_gate[e]) * (xd[e] @ w_up[e])) @ w_down[e]

Grid: (E, C/Cb, F/Fb).  The F axis is the contraction of the down-proj,
so output tiles are revisited and accumulated across the last grid dim
("arbitrary" semantics); E and C tiles are parallel.

Tiling rule (``pick_tiles``): the pipeline double-buffers every tile —
x ``(Cb, D)``, the three weight tiles ``(D, Fb)`` / ``(Fb, D)`` and the
fp32 output ``(Cb, D)`` — and all of it must fit the 16 MiB of scoped
VMEM a TPU v5e kernel gets by default.  ``Fb`` is the largest of
512 / 256 / 128 (all MXU-aligned) whose working set fits, so it follows
D and the weight itemsize: 512 at Granite widths (D=1536) in bf16, 256
for Mixtral (D=4096) in bf16, 128 for fp32 at D=4096.  When even
``Fb=128`` does not fit, ``Cb`` halves (in multiples of 8).  A ragged
final F tile (``F % Fb != 0``) is zero-masked in-kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams


# Scoped VMEM a kernel may take on TPU v5e without raising the limit
# (16 MiB), less 2 MiB for the in-kernel h / u / y temporaries, which the
# compiler counts beside the pipelined tiles.
_TILE_BUDGET = 14 * 2**20
_BLOCK_F = (512, 256, 128)


def pick_tiles(c: int, d: int, f: int, *, x_bytes: int, w_bytes: float,
               block_c: int = 128, block_f=None):
    """``(Cb, Fb)`` for a ``(E, c, d)`` call on ``(d, f)`` experts.

    ``x_bytes`` is the activation itemsize, ``w_bytes`` the bytes one
    element of one expert weight occupies in the tiles the kernel
    streams (the dtype itemsize; the code width for packed weights).
    An explicit ``block_f`` is kept as given."""
    bc = min(block_c, c)
    if block_f is not None:
        return bc, min(block_f, f)

    def tiles_bytes(bc, bf):
        return 2 * (bc * d * (x_bytes + 4) + 3 * d * bf * w_bytes)

    while True:
        for bf in _BLOCK_F:
            bf = min(bf, f)
            if tiles_bytes(bc, bf) <= _TILE_BUDGET:
                return bc, bf
        if bc <= 8:
            return bc, min(_BLOCK_F[-1], f)
        bc = max(8, bc // 16 * 8)


def _mask_ragged_f(fi, total_f: int, block_f: int, wg, wu, wd):
    """Zero the out-of-bounds columns of a ragged final F tile: they
    hold padding on the contraction dim and would contaminate the
    accumulator.  The masks are 2-D iotas laid out like the tiles they
    select (a reshaped 1-D iota is a shape cast Mosaic refuses)."""
    col = fi * block_f + jax.lax.broadcasted_iota(jnp.int32, (1, block_f), 1)
    row = fi * block_f + jax.lax.broadcasted_iota(jnp.int32, (block_f, 1), 0)
    return (jnp.where(col < total_f, wg, 0), jnp.where(col < total_f, wu, 0),
            jnp.where(row < total_f, wd, 0))


def _make_ffn_kernel(total_f: int, block_f: int):
    def _ffn_kernel(x_ref, wg_ref, wu_ref, wd_ref, o_ref):
        fi = pl.program_id(2)
        x = x_ref[0]                       # (Cb, D)
        wg = wg_ref[0]                     # (D, Fb)
        wu = wu_ref[0]
        wd = wd_ref[0]                     # (Fb, D)
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

    return _ffn_kernel


@functools.partial(jax.jit,
                   static_argnames=("block_c", "block_f", "interpret"))
def moe_ffn_kernel(xd, w_gate, w_up, w_down, *, block_c: int = 128,
                   block_f=None, interpret: bool = False):
    """xd: (E, C, D) -> (E, C, D), fp32 accumulation.  ``block_f=None``
    picks the tiles by the rule in the module docstring."""
    e, c, d = xd.shape
    f = w_gate.shape[-1]
    bc, bf = pick_tiles(c, d, f, x_bytes=xd.dtype.itemsize,
                        w_bytes=w_gate.dtype.itemsize, block_c=block_c,
                        block_f=block_f)
    grid = (e, pl.cdiv(c, bc), pl.cdiv(f, bf))
    return pl.pallas_call(
        _make_ffn_kernel(f, bf),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bc, d), lambda e_, ci, fi: (e_, ci, 0)),
            pl.BlockSpec((1, d, bf), lambda e_, ci, fi: (e_, 0, fi)),
            pl.BlockSpec((1, d, bf), lambda e_, ci, fi: (e_, 0, fi)),
            pl.BlockSpec((1, bf, d), lambda e_, ci, fi: (e_, fi, 0)),
        ],
        out_specs=pl.BlockSpec((1, bc, d), lambda e_, ci, fi: (e_, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((e, c, d), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
        interpret=interpret,
    )(xd, w_gate, w_up, w_down)

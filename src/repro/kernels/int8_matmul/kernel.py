"""w8a16 dequantizing matmul Pallas kernel — the SEP shadow model's GEMM.

The shadow node serves the quantized emulator; its weights live as int8
(symmetric per-output-channel scales).  Dequantization happens INSIDE
the kernel on the VMEM tile right before the MXU dot, so HBM traffic is
1 byte/weight — the whole point of the quantized shadow: ~4x faster
weight streaming at decode, which is what lets it run layers AHEAD of
the full-precision model (SEP's lookahead margin).

    y = x @ (w_q.astype(f32) * scale)     x: (M, K), w_q: (K, N) int8

Grid: (M/Mb, N/Nb, K/Kb); K is the contraction -> the output tile is
revisited and accumulated over the last grid dim; the per-channel scale
is applied once at the final K step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams


def _make_kernel(n_k: int, total_k: int, block_k: int):
    def body(x_ref, w_ref, s_ref, o_ref):
        ki = pl.program_id(2)
        x = x_ref[...].astype(jnp.float32)          # (Mb, Kb)
        w = w_ref[...].astype(jnp.float32)          # (Kb, Nb) int8 -> f32
        # mask a ragged final K tile (padding would contaminate the acc)
        kmask = (ki * block_k + jax.lax.iota(jnp.int32, block_k)
                 < total_k)
        x = jnp.where(kmask[None, :], x, 0.0)
        w = jnp.where(kmask[:, None], w, 0.0)
        y = jnp.dot(x, w, preferred_element_type=jnp.float32)

        @pl.when(ki == 0)
        def _init():
            o_ref[...] = y.astype(o_ref.dtype)

        @pl.when(ki > 0)
        def _acc():
            o_ref[...] += y.astype(o_ref.dtype)

        @pl.when(ki == n_k - 1)
        def _scale():
            o_ref[...] *= s_ref[...].astype(o_ref.dtype)

    return body


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_n", "block_k",
                                    "interpret"))
def int8_matmul_kernel(x, w_q, scale, *, block_m: int = 256,
                       block_n: int = 256, block_k: int = 512,
                       interpret: bool = False):
    """x: (M, K) float; w_q: (K, N) int8; scale: (N,) -> (M, N) f32."""
    m, k = x.shape
    _, n = w_q.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        _make_kernel(grid[2], k, bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
            pl.BlockSpec((1, bn), lambda mi, ni, ki: (0, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
        interpret=interpret,
    )(x, w_q, scale.reshape(1, -1))

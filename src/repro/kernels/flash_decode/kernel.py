"""Single-token GQA decode attention over a long (ring-buffer) KV cache.

The decode-shape hot spot: one query token attends over up to 500k
cached keys.  The cache streams HBM->VMEM in sequence blocks; the
(m, l, acc) flash recurrence accumulates in the output tile, which stays
VMEM-resident across the sequential KV grid dim.  Invalid slots (pos<0,
future positions, outside the sliding window) are masked with the cached
absolute positions, so the kernel handles the ring-buffer layout
natively.

Shapes:  q: (B, K, G, Hd)   k/v: (B, W, K, Hd)   kpos: (B, W)   pos: (B,)
Grid:    (B, K, W/Wb) — batch/kv-head parallel, sequence arbitrary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams

NEG_INF = -1e30


def _make_kernel(n_w: int, window: int, total_w: int, block_w: int):
    def body(q_ref, k_ref, v_ref, kpos_ref, pos_ref, o_ref, m_ref, l_ref):
        wi = pl.program_id(2)

        @pl.when(wi == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            o_ref[...] = jnp.zeros_like(o_ref)

        q = q_ref[0, 0].astype(jnp.float32)        # (G, Hd)
        k = k_ref[0, :, 0].astype(jnp.float32)     # (Wb, Hd)
        v = v_ref[0, :, 0].astype(jnp.float32)     # (Wb, Hd)
        kpos = kpos_ref[0]                         # (Wb,)
        pos = pos_ref[0]                           # scalar
        # a partial final block reads out-of-bounds padding: mask by the
        # GLOBAL slot index, and scrub non-finite padded k/v
        in_bounds = wi * block_w + jax.lax.iota(jnp.int32, block_w) < total_w
        k = jnp.where(in_bounds[:, None], k, 0.0)
        v = jnp.where(in_bounds[:, None], v, 0.0)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)  # (G, Wb)
        s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
        valid = (kpos >= 0) & (kpos <= pos) & in_bounds
        if window:
            valid = valid & (pos - kpos < window)
        s = jnp.where(valid[None, :], s, NEG_INF)
        m_prev = m_ref[0, 0, :, 0]                 # (G,)
        l_prev = l_ref[0, 0, :, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = o_ref[0, 0] * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[0, 0, :, 0] = m_new
        l_ref[0, 0, :, 0] = l_new
        o_ref[0, 0] = acc

        @pl.when(wi == n_w - 1)
        def _norm():
            o_ref[0, 0] = o_ref[0, 0] / jnp.maximum(
                l_ref[0, 0, :, 0], 1e-30)[:, None]

    return body


@functools.partial(jax.jit,
                   static_argnames=("block_w", "window", "interpret"))
def flash_decode_kernel(q, k, v, kpos, pos, *, block_w: int = 1024,
                        window: int = 0, interpret: bool = False):
    """q: (B,K,G,Hd); k/v: (B,W,K,Hd); kpos: (B,W); pos: (B,) -> (B,K,G,Hd)."""
    b, kh, g, hd = q.shape
    w = k.shape[1]
    bw = min(block_w, w)
    grid = (b, kh, pl.cdiv(w, bw))
    out, _, _ = pl.pallas_call(
        _make_kernel(grid[2], window, w, bw),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, ki, wi: (bi, ki, 0, 0)),
            pl.BlockSpec((1, bw, 1, hd), lambda bi, ki, wi: (bi, wi, ki, 0)),
            pl.BlockSpec((1, bw, 1, hd), lambda bi, ki, wi: (bi, wi, ki, 0)),
            pl.BlockSpec((1, bw), lambda bi, ki, wi: (bi, wi)),
            pl.BlockSpec((1,), lambda bi, ki, wi: (bi,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, g, hd), lambda bi, ki, wi: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bi, ki, wi: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, g, 1), lambda bi, ki, wi: (bi, ki, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kh, g, hd), jnp.float32),
            jax.ShapeDtypeStruct((b, kh, g, 1), jnp.float32),   # m
            jax.ShapeDtypeStruct((b, kh, g, 1), jnp.float32),   # l
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
        interpret=interpret,
    )(q, k, v, kpos, pos)
    return out

"""Mamba2 inter-chunk state recurrence Pallas kernel.

The SSD dual form splits into embarrassingly-parallel intra-chunk GEMMs
(left to the MXU via XLA) and this strictly-sequential inter-chunk
recurrence over chunk states:

    h_in[c]  = h                      (state entering chunk c, emitted)
    h        = decay[c] * h + s[c]    (per-head scalar decay)

Shapes: s: (B, NC, H, P, N) chunk states, decay: (B, NC, H).
Grid: (B, H/Hb, NC) — batch and head tiles parallel, chunk sequential;
the running state lives in the revisited output tile of the LAST chunk
slot, so no scratch is needed and the working set is one (Hb, P, N)
state tile per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas.tpu import CompilerParams


def _make_kernel():
    def body(s_ref, d_ref, hin_ref, hlast_ref):
        ci = pl.program_id(2)

        @pl.when(ci == 0)
        def _init():
            hlast_ref[0] = jnp.zeros_like(hlast_ref[0])

        h = hlast_ref[0]                          # (Hb, P, N)
        hin_ref[0, 0] = h                         # state entering chunk ci
        dec = d_ref[0, 0][:, None, None]          # (Hb,1,1)
        s = s_ref[0, 0]                           # (Hb, P, N)
        hlast_ref[0] = dec * h + s

    return body


@functools.partial(jax.jit, static_argnames=("block_h", "interpret"))
def ssd_scan_kernel(s, decay, *, block_h: int = 16,
                    interpret: bool = False):
    """s: (B,NC,H,P,N) f32; decay: (B,NC,H) f32.

    Returns (h_in: (B,NC,H,P,N) state entering each chunk,
             h_last: (B,H,P,N) final state)."""
    b, nc, h, p, n = s.shape
    bh = min(block_h, h)
    grid = (b, pl.cdiv(h, bh), nc)
    hin, hlast = pl.pallas_call(
        _make_kernel(),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bh, p, n),
                         lambda bi, hi, ci: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, bh), lambda bi, hi, ci: (bi, ci, hi)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bh, p, n),
                         lambda bi, hi, ci: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, bh, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, p, n), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                             "arbitrary")),
        interpret=interpret,
    )(s, decay)
    return hin, hlast

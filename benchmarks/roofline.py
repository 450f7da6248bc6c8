"""Roofline analysis (deliverable g): three terms per (arch x shape).

    compute   = FLOPs / (chips * 197 TFLOP/s bf16)
    memory    = HBM bytes / (chips * 819 GB/s)
    collective= wire bytes / (chips * 50 GB/s ICI)

Sources:
  * collective bytes — dry-run HLO, trip-count corrected
    (launch/hlo_analysis.py); per-device, so divide by link bw only.
  * FLOPs / HBM bytes — ANALYTIC per-op accounting below.  XLA's
    ``cost_analysis()`` counts every ``while`` body once (measured; see
    tests/test_hlo_analysis.py), which undercounts our scanned layers by
    the repeat factor, so the raw numbers are reported alongside but the
    roofline uses the analytic terms.
  * MODEL_FLOPS = 6·N_active·D (train) / 2·N_active (per decode token);
    ratio MODEL/compiled-estimate exposes remat + dispatch + full-
    rectangle-attention waste.

A MEASURED point rides along the analytic rows: the grouped expert-FFN
kernel, fp32 vs the fused in-kernel-dequant packed kernels (int8/nf4),
with closed-form HBM bytes-moved per kernel launch and the achieved
arithmetic intensity — recorded via ``record_bench`` into the committed
``BENCH_roofline.json`` so the packed kernel's bandwidth win is
traceable PR over PR.  ``--smoke`` (the CI fast job) gates the
invariants cheaply: packed bytes-moved strictly below fp32 AND
bit-identical outputs.

Usage: python -m benchmarks.roofline [--smoke] [--dryrun artifacts/dryrun.jsonl]
"""
from __future__ import annotations

import argparse
import json
import math
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models.config import ATTN, DENSE_FF, MOE_FF, INPUT_SHAPES
from repro.launch.specs import shape_config

PEAK_FLOPS = 197e12          # bf16 / chip
HBM_BW = 819e9               # B/s / chip
LINK_BW = 50e9               # B/s / link
CHIPS = 256                  # single-pod roofline (per spec)


# ------------------------------------------------------------- analytics
def fwd_flops_per_token(cfg, ctx: int, causal_factor: float = 1.0) -> Dict[str, float]:
    """Forward matmul FLOPs per token by component, context length ctx.

    causal_factor=1.0 reflects our blockwise attention computing the full
    rectangle (masked blocks are not skipped — a recorded §Perf item).
    """
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, kv = cfg.num_heads, cfg.num_kv_heads
    comp = {"attn_proj": 0.0, "attn_score": 0.0, "ff": 0.0, "moe": 0.0,
            "mamba": 0.0, "head": 2 * d * cfg.vocab_size}
    for mixer, ff in cfg.layer_kinds():
        if mixer == ATTN:
            comp["attn_proj"] += 2 * d * (2 * h * hd + 2 * kv * hd)
            comp["attn_score"] += 4 * h * hd * ctx * causal_factor
        else:
            di, ns, nh = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads
            q = cfg.ssm_chunk
            comp["mamba"] += 2 * d * (2 * di + 2 * ns + nh)   # projections
            comp["mamba"] += 2 * q * (ns + di)                 # intra-chunk
            comp["mamba"] += 4 * di * ns                       # states+inter
            comp["mamba"] += 2 * di * d                        # out_proj
        if ff == DENSE_FF:
            comp["ff"] += 2 * 3 * d * cfg.d_ff
        elif ff == MOE_FF:
            comp["moe"] += (2 * 3 * d * cfg.d_expert_resolved
                            * cfg.top_k * cfg.capacity_factor)
    if cfg.is_encoder_decoder:
        # encoder layers (bidirectional attention + dense FF)
        comp["attn_proj"] += cfg.num_encoder_layers * 2 * d * (
            2 * h * hd + 2 * kv * hd)
        comp["attn_score"] += cfg.num_encoder_layers * 4 * h * hd * ctx
        comp["ff"] += cfg.num_encoder_layers * 2 * 3 * d * cfg.d_ff
        # decoder cross-attention reads the encoder memory
        comp["attn_score"] += cfg.num_layers * 4 * h * hd * ctx
    return comp


def analytic_terms(arch: str, shape_name: str) -> Dict[str, float]:
    shape = INPUT_SHAPES[shape_name]
    cfg = shape_config(get_config(arch), shape)
    b, t = shape.global_batch, shape.seq_len
    wb = 2                                     # bf16 weights
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        comp = fwd_flops_per_token(cfg, t)
        fwd = sum(comp.values())
        tokens = b * t
        flops = fwd * tokens * 4.0             # fwd + bwd(2x) + remat(1x)
        model_flops = 6.0 * n_active * tokens
        # HBM: weights fwd+bwd+remat reads + grad w + adam (fp32 m,v rw + p rw)
        param_traffic = cfg.param_count() * (wb * 4 + 4 * 6)
        act_traffic = tokens * cfg.d_model * cfg.num_layers * wb * 4
        hbm = param_traffic + act_traffic
    elif shape.kind == "prefill":
        comp = fwd_flops_per_token(cfg, t)
        fwd = sum(comp.values())
        tokens = b * t
        flops = fwd * tokens
        model_flops = 2.0 * n_active * tokens
        cache_w = (2 * cfg.num_kv_heads * cfg.resolved_head_dim * wb
                   * sum(1 for m, _ in cfg.layer_kinds() if m == ATTN))
        hbm = cfg.param_count() * wb + tokens * (
            cache_w + cfg.d_model * cfg.num_layers * wb * 2)
    else:  # decode: ONE token against ctx-length cache
        ctx = min(t, cfg.sliding_window) if cfg.sliding_window else t
        comp = fwd_flops_per_token(cfg, ctx)
        fwd = sum(comp.values())
        tokens = b                              # one step, b sequences
        flops = fwd * tokens
        model_flops = 2.0 * n_active * tokens
        n_attn = sum(1 for m, _ in cfg.layer_kinds() if m == ATTN)
        cache_traffic = (b * ctx * 2 * cfg.num_kv_heads
                         * cfg.resolved_head_dim * wb * n_attn)
        if cfg.is_encoder_decoder:
            cache_traffic *= 2                  # + cross memory reads
        hbm = n_active * wb + cache_traffic
    return {"flops_global": flops, "model_flops": model_flops,
            "hbm_bytes_global": hbm, "components": comp,
            "tokens": tokens}


# --------------------------------------- measured grouped-GEMM point
NF4_BLOCK = 64


def kernel_bytes_moved(e: int, c: int, d: int, f: int, bc: int, bf: int,
                       scheme: str) -> int:
    """Closed-form HBM<->VMEM traffic of one grouped expert-FFN kernel
    launch at tiling (bc, bf) — the tile streams the ``(E, C/Cb, F/Fb)``
    grid actually issues (see kernels/moe_gemm/{kernel,packed}.py):
    every grid step reads its x tile and all three weight tiles; the
    output tile is written at fi==0 and read+written on every
    accumulating revisit.  Weight tiles are priced at their WIRE widths
    for the packed schemes — codes plus the scale tiles that ride along
    — which is exactly the traffic the fused in-kernel dequant saves."""
    gc, gf = -(-c // bc), -(-f // bf)
    steps = e * gc * gf
    x_bytes = steps * bc * d * 4
    out_bytes = e * gc * (2 * gf - 1) * bc * d * 4
    if scheme == "fp32":
        w_tile = 3 * d * bf * 4
    elif scheme == "fp16":
        w_tile = 3 * d * bf * 2
    elif scheme == "int8":
        # gate/up: codes (d, bf) + scale row tile (1, bf) f32;
        # down: codes (bf, d) + scale row tile (1, d) f32
        w_tile = 2 * (d * bf + 4 * bf) + (bf * d + 4 * d)
    elif scheme == "nf4":
        # codes at 2 values/byte + one f32 absmax per 64-run, both axes
        w_tile = 3 * (d * bf // 2 + 4 * d * bf // NF4_BLOCK)
    else:
        raise ValueError(f"unknown scheme {scheme!r}")
    return x_bytes + out_bytes + steps * w_tile


def grouped_gemm_rows(fast: bool = True, smoke: bool = False):
    """Measure the fp32 vs packed grouped-GEMM kernels (interpret mode
    on CPU — tile streams and arithmetic identical to TPU, wall clock
    indicative only) and derive bytes-moved + achieved intensity."""
    from repro.kernels.moe_gemm import (moe_ffn_kernel,
                                        moe_ffn_packed_kernel)
    from repro.quant.transport import device_layout, get_codec
    from .common import record_bench, row, timed

    e, c, d, f = (2, 16, 64, 128) if (fast or smoke) else (4, 32, 64, 256)
    bc, bf = min(32, c), min(128, f)
    flops = 6 * e * c * d * f
    key = jax.random.PRNGKey(0)
    weights = {}
    for i, (name, shp) in enumerate((("w_gate", (d, f)), ("w_up", (d, f)),
                                     ("w_down", (f, d)))):
        weights[name] = [jax.random.normal(jax.random.fold_in(key, i * 8 + j),
                                           shp, jnp.float32)
                         for j in range(e)]
    xd = jax.random.normal(jax.random.fold_in(key, 99), (e, c, d),
                           jnp.float32)
    rows, metrics = [], {"shape": f"e{e}c{c}d{d}f{f}", "flops": flops}
    baseline = {}
    for scheme in ("fp32", "int8", "nf4"):
        codec = get_codec(scheme)
        packed = {n: [codec.pack(w) for w in ws]
                  for n, ws in weights.items()}
        if scheme == "fp32":
            full = {n: jnp.stack(ws) for n, ws in weights.items()}
            fn = lambda: moe_ffn_kernel(
                xd, full["w_gate"], full["w_up"], full["w_down"],
                block_c=bc, block_f=bf, interpret=True)
        else:
            # dequantize-on-arrival oracle: fp32 kernel on the SAME
            # round-tripped weights the wire parts decode to
            full = {n: jnp.stack([codec.unpack(pw) for pw in pws])
                    for n, pws in packed.items()}
            parts = {n: tuple(jnp.stack([np.asarray(device_layout(pw)[j])
                                         for pw in pws])
                              for j in range(len(device_layout(pws[0]))))
                     for n, pws in packed.items()}
            fn = lambda: moe_ffn_packed_kernel(
                xd, parts, scheme=scheme, block_c=bc, block_f=bf,
                interpret=True)
        oracle = (None if scheme == "fp32" else np.asarray(moe_ffn_kernel(
            xd, full["w_gate"], full["w_up"], full["w_down"],
            block_c=bc, block_f=bf, interpret=True)))
        out = np.asarray(fn())                        # compile + warm
        _, us = timed(lambda: jax.block_until_ready(fn()))
        nbytes = kernel_bytes_moved(e, c, d, f, bc, bf, scheme)
        intensity = flops / nbytes
        baseline[scheme] = (out, nbytes)
        if oracle is not None:
            assert np.array_equal(out, oracle), \
                f"packed {scheme} kernel diverged from dequantized fp32"
        rows.append(row(f"roofline/grouped_gemm/{scheme}", us,
                        f"bytes:{nbytes} intensity:{intensity:.2f}"))
        metrics[f"{scheme}_bytes_moved"] = nbytes
        metrics[f"{scheme}_intensity"] = intensity
        metrics[f"{scheme}_us"] = round(us, 1)
    fp32_bytes = baseline["fp32"][1]
    for scheme in ("int8", "nf4"):
        out, nbytes = baseline[scheme]
        assert nbytes < fp32_bytes, \
            f"{scheme} kernel moves no fewer bytes than fp32"
        metrics[f"{scheme}_bytes_saved_x"] = fp32_bytes / nbytes
    record_bench("roofline", metrics)
    if smoke:
        print("roofline smoke OK: packed bytes-moved < fp32 "
              f"(int8 {fp32_bytes / baseline['int8'][1]:.2f}x, "
              f"nf4 {fp32_bytes / baseline['nf4'][1]:.2f}x), outputs "
              "bit-identical to the dequantize-on-arrival kernel")
    return rows


# ------------------------------------------------------------- reporting
def roofline_row(dry: dict) -> Dict:
    arch, shape = dry["arch"], dry["shape"]
    a = analytic_terms(arch, shape)
    flops_dev = a["flops_global"] / CHIPS
    hbm_dev = a["hbm_bytes_global"] / CHIPS
    coll_dev = dry["collective_bytes_per_device"]["total"]
    t_compute = flops_dev / PEAK_FLOPS
    t_memory = hbm_dev / HBM_BW
    t_coll = coll_dev / LINK_BW
    terms = {"compute": t_compute, "memory": t_memory,
             "collective": t_coll}
    dominant = max(terms, key=terms.get)
    return {
        "arch": arch, "shape": shape, "mesh": dry["mesh"],
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "model_flops": a["model_flops"],
        "flops_estimate": a["flops_global"],
        "useful_ratio": a["model_flops"] / a["flops_global"],
        "hlo_flops_per_device_raw": dry.get("flops_per_device"),
        "collective_bytes_per_device": coll_dev,
        "collective_counts": dry["collective_bytes_per_device"].get(
            "counts"),
    }


def _fmt(x: float) -> str:
    if x == 0:
        return "0"
    exp = int(math.floor(math.log10(abs(x))))
    if -3 <= exp <= 2:
        return f"{x:.4f}"
    return f"{x:.2e}"


def markdown_table(rows) -> str:
    hdr = ("| arch | shape | mesh | compute s | memory s | collective s "
           "| dominant | useful ratio |\n|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['mesh']} "
            f"| {_fmt(r['t_compute_s'])} | {_fmt(r['t_memory_s'])} "
            f"| {_fmt(r['t_collective_s'])} | **{r['dominant']}** "
            f"| {r['useful_ratio']:.2f} |")
    return "\n".join(lines)


def run(fast: bool = True, dryrun_path: Optional[str] = None,
        smoke: bool = False):
    """Benchmark-harness entry: the measured grouped-GEMM point plus
    rooflines for available dry-runs."""
    from .common import ARTIFACTS, row, save_artifact
    rows = grouped_gemm_rows(fast=fast, smoke=smoke)
    if smoke:
        return rows
    if dryrun_path is None:                 # rooflines need --dryrun
        return rows
    out = []
    with open(dryrun_path) as f:
        for line in f:
            dry = json.loads(line)
            if not dry.get("ok"):
                continue
            if dry["mesh"] != "16x16":
                continue
            r = roofline_row(dry)
            out.append(r)
            rows.append(row(
                f"roofline/{r['arch']}/{r['shape']}", 0.0,
                f"{r['dominant']}:{_fmt(max(r['t_compute_s'], r['t_memory_s'], r['t_collective_s']))}"))
    save_artifact("roofline.json", out)
    with open(os.path.join(ARTIFACTS, "roofline.md"), "w") as f:
        f.write(markdown_table(out))
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun", default=None)
    ap.add_argument("--smoke", action="store_true",
                    help="CI gate: packed kernel bytes-moved < fp32 "
                         "with bit-identical outputs")
    args = ap.parse_args()
    for r in run(fast=args.smoke, dryrun_path=args.dryrun,
                 smoke=args.smoke):
        print(f"{r['name']},{r['us_per_call']},{r['derived']}")


if __name__ == "__main__":
    main()

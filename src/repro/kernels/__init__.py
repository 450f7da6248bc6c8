"""Pallas TPU kernels for the serving/training hot spots.

Each subpackage ships kernel.py (pl.pallas_call + BlockSpec VMEM tiling),
ops.py (jit'd wrapper with CPU fallback), and ref.py (pure-jnp oracle).
On CPU the kernels are checked in interpret=True mode.  Only the MoE
grouped-GEMM kernels (fp32/bf16 and int8-packed) are compiled for TPU
v5e (tests/test_chip_compile.py); nf4-packed, flash_decode, int8_matmul
and ssd_scan have never been compiled for a chip.
"""
from .flash_decode import flash_decode, flash_decode_kernel, flash_decode_ref
from .int8_matmul import int8_matmul, int8_matmul_kernel, int8_matmul_ref
from .moe_gemm import (combine_topk, grouped_topk_contrib,
                       grouped_topk_contrib_packed, moe_ffn,
                       moe_ffn_kernel, moe_ffn_packed,
                       moe_ffn_packed_kernel, moe_ffn_ref)
from .ssd_scan import ssd_scan, ssd_scan_kernel, ssd_scan_ref

__all__ = [
    "flash_decode", "flash_decode_kernel", "flash_decode_ref",
    "int8_matmul", "int8_matmul_kernel", "int8_matmul_ref",
    "combine_topk", "grouped_topk_contrib", "grouped_topk_contrib_packed",
    "moe_ffn", "moe_ffn_kernel", "moe_ffn_packed",
    "moe_ffn_packed_kernel", "moe_ffn_ref",
    "ssd_scan", "ssd_scan_kernel", "ssd_scan_ref",
]

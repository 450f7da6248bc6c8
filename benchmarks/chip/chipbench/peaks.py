"""Published peaks of each accelerator the benchmark runs on.

Keyed by ``jax.Device.device_kind``.  A kind that is not here is an
error: a roofline or utilization against a guessed peak is no number.

TPU v5e (reported by JAX as "TPU v5 lite"): Google Cloud documentation,
"TPU v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class Peaks:
    bf16_flops: float          # FLOP/s
    int8_ops: float            # OP/s
    hbm_bytes_s: float         # bytes/s
    hbm_bytes: float           # bytes
    source: str


_V5E = Peaks(bf16_flops=197e12, int8_ops=393e12, hbm_bytes_s=819e9,
             hbm_bytes=16e9,
             source="Google Cloud documentation, TPU v5e")

PEAKS = {"TPU v5 lite": _V5E, "TPU v5e": _V5E}


def for_kind(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to peaks.py with their "
                       "source") from None

"""The grouped expert GEMM's operation and byte counts and the roofline,
checked by hand at Granite-3.0-3B-A800M's widths.  The whole model's
count is its architecture plug-in's (``test_chipbench_arch_granitemoe.py``)."""
from __future__ import annotations

import pytest

import chipbench_tiny  # noqa: F401
from chipbench import flops, peaks

D, F = 1536, 512          # hidden size, expert width


def test_decode_wave_roofline_by_hand():
    # one row, 8 routed experts: 8 pairs x 6 x 1536 x 512 FLOPs, and the
    # 8 experts' bf16 weights (3 x 1536 x 512 x 2 bytes each) read once
    fl, by = flops.gemm_call_work(D, F, 2, pairs=8, experts=8, rows=1)
    assert fl == 8 * 6 * D * F == 37_748_736
    assert by == 8 * 3 * D * F * 2 + 2 * D * 2 == 37_754_880
    p = peaks.for_kind("TPU v5 lite")
    t, bound = flops.least_time_s(fl, by, p.bf16_flops, p.hbm_bytes_s)
    assert bound == "memory"
    assert t == pytest.approx(37_754_880 / 819e9)        # ~46.1 us


def test_prefill_roofline_is_compute_bound_at_long_prompts():
    # 2048 rows x 8 pairs through all 40 experts
    fl, by = flops.gemm_call_work(D, F, 2, pairs=2048 * 8, experts=40,
                                  rows=2048)
    p = peaks.for_kind("TPU v5 lite")
    t, bound = flops.least_time_s(fl, by, p.bf16_flops, p.hbm_bytes_s)
    assert bound == "compute"
    assert t == pytest.approx(2048 * 8 * 6 * D * F / 197e12)


def test_wave_calls_count_pairs_and_distinct_experts():
    import numpy as np
    from types import SimpleNamespace as NS
    lr = NS(true=np.array([[1, 2], [2, 3]]),
            waves=[[(1, 0), (2, 1)], [(3, 0)]])
    calls = list(flops.wave_calls([NS(layers=[lr])], D, F, 2))
    assert calls[0] == flops.gemm_call_work(D, F, 2, 3, 2, 2)
    assert calls[1] == flops.gemm_call_work(D, F, 2, 1, 1, 2)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peaks.for_kind("TPU v9 imaginary")

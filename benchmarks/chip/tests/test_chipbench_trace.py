"""The trace reduction on a small recorded trace: busy time as the union
of device-op intervals, idle gaps named by the host span open in them,
and the grouped-GEMM kernel's events split into decode waves and
prefill."""
from __future__ import annotations

import pytest

import chipbench_tiny  # noqa: F401
from chipbench import trace as T

# A recorded shape of one decode tick and one admission, in ns: the
# engine's prefill program, a decode-wave program running the kernel,
# and the shadow's step (whose kernel is not the model's decode wave).
PROGRAMS = [T.Ev("jit_fn(11)", 100, 300),                 # prefill
            T.Ev("jit__grouped_contrib(12)", 600, 100),    # decode wave
            T.Ev("jit__lambda_(13)", 800, 50)]             # shadow step
OPS = [T.Ev("fusion.1", 100, 50),
       T.Ev("%moe_ffn_kernel.1 = f32 custom-call()", 160, 200),          # kernel inside prefill
       T.Ev("fusion.2", 150, 30),      # overlaps the one above
       T.Ev("%moe_ffn_kernel.1 = f32 custom-call()", 610, 60),           # kernel inside a decode wave
       T.Ev("copy.3", 680, 10),
       T.Ev("%moe_ffn_kernel.1 = f32 custom-call()", 805, 40)]           # kernel inside the shadow step
SPANS = [T.Ev("bench.window", 0, 1000), T.Ev("bench.tick", 0, 1000),
         T.Ev("bench.prefill", 50, 400), T.Ev("bench.decode_batch", 500, 300),
         T.Ev("bench.expert_load", 450, 140)]


@pytest.fixture
def tr():
    return T.Trace(devices=[T.DeviceTrace(ops=OPS, programs=PROGRAMS)],
                   spans=SPANS, window=(0, 1000))


def test_busy_is_the_union_of_op_intervals(tr):
    # [100, 360) + [610, 670) + [680, 690) + [805, 845) = 260+60+10+40
    assert T.busy_ns(OPS, 0, 1000) == 370
    assert T.busy_s(tr) == pytest.approx(370e-9)
    assert T.busy_ns(OPS, 200, 650) == 160 + 40


def test_idle_gaps_longest_first(tr):
    gaps = T.idle_gaps(OPS, 0, 1000)
    assert gaps[0] == (360, 610)
    assert sum(b - a for a, b in gaps) == 1000 - 370


def test_gaps_named_by_innermost_host_span(tr):
    bd = T.breakdown(tr)
    names = dict((round(s * 1e9), n) for n, s in bd["idle_gaps"])
    assert names[250] == "bench.expert_load"      # (360, 610), mid 485
    assert names[155] == "bench.tick"             # (845, 1000), mid 922
    assert names[100] == "bench.prefill"          # (0, 100), mid 50
    top = bd["device_ops"][0]
    assert top[0] == "%moe_ffn_kernel.1" and top[1] == pytest.approx(300e-9)


def test_kernel_events_split_by_program(tr):
    dev = tr.devices[0]
    from chipbench.kernels import is_kernel
    decode = T.ops_in_programs(dev, lambda n: T.module_name(n) ==
                               "jit__grouped_contrib", is_kernel, 0, 1000)
    prefill = T.ops_in_programs(dev, lambda n: T.module_name(n) == "jit_fn",
                                is_kernel, 0, 1000)
    assert [e.start_ns for e in decode] == [610]
    assert [e.start_ns for e in prefill] == [160]
    assert T.total_s(decode) == pytest.approx(60e-9)


def test_breakdown_counts_leaf_ops_under_their_short_names():
    ops = [T.Ev("%while.1 = (s32[]) while(...)", 0, 100),
           T.Ev("%fusion.2 = f32[4] fusion(...)", 10, 30),
           T.Ev("%fusion.2 = f32[4] fusion(...)", 50, 30),
           T.Ev("%copy.3 = f32[4] copy(...)", 200, 5)]
    tr = T.Trace([T.DeviceTrace(ops=ops, programs=[])],
                 [T.Ev("bench.window", 0, 300)], (0, 300))
    assert [op[0] for op in T.breakdown(tr)["device_ops"]] == \
        ["%fusion.2", "%copy.3"]
    assert T.busy_ns(ops, 0, 300) == 105


def test_window_clips_everything(tr):
    assert T.busy_ns(OPS, 650, 700) == 20 + 10
    assert T.idle_gaps(OPS, 650, 700) == [(670, 680), (690, 700)]


def test_readers_on_a_recorded_trace():
    """The prefill and decode readers pick the kernel events and spans
    they are meant to, and count their work by hand."""
    from types import SimpleNamespace as NS
    import numpy as np
    from chipbench import config, flops, peaks, spec
    bench_cfg = config.load("granite3-3b-a800m-8L")
    cfg, plugin = bench_cfg.model, bench_cfg.plugin
    pk = peaks.for_kind("TPU v5 lite")
    ms = 1_000_000
    progs = [T.Ev("jit_fn(1)", 10 * ms, 60 * ms),             # prefill
             T.Ev("jit__grouped_contrib(2)", 100 * ms, 1 * ms)]
    ops = [T.Ev("%moe_ffn_kernel.8 = f32[64,1024,1536] custom-call()",
                12 * ms, 40 * ms),
           T.Ev("%moe_ffn_kernel.1 = f32[8,1,1536] custom-call()",
                100 * ms, ms // 10)]
    spans = [T.Ev("bench.window", 0, 1000 * ms),
             T.Ev("bench.prefill", 5 * ms, 70 * ms)]
    tr = T.Trace([T.DeviceTrace(ops=ops, programs=progs)], spans,
                 (0, 1000 * ms))
    lr = NS(true=np.arange(8)[None], waves=[[(e, e) for e in range(8)]])
    run = NS(trace=tr, peaks=pk, cfg=cfg, plugin=plugin, prefills=[1000],
             records=[NS(layers=[lr])], clients=[], window=(0.0, 1.0))
    read = lambda name: spec.metric_reader(name)(run)  # noqa: E731
    assert read("prefill_ms_p50") == pytest.approx(60.0)
    pre = flops.gemm_call_work(1536, 512, 2, 8000, 40, 1000)
    least = flops.least_time_s(*pre, pk.bf16_flops, pk.hbm_bytes_s)[0]
    assert read("moe_gemm_roofline.prefill") == pytest.approx(
        100 * 8 * least / 0.040)
    dec = flops.gemm_call_work(1536, 512, 2, 8, 8, 1)
    least = flops.least_time_s(*dec, pk.bf16_flops, pk.hbm_bytes_s)[0]
    assert read("moe_gemm_roofline.decode") == pytest.approx(
        100 * least / 1e-4)
    assert read("mfu.prefill") == pytest.approx(
        100 * plugin.prompt_flops(cfg, 1000) / (0.070 * pk.bf16_flops))
    assert read("device_idle_share") == pytest.approx(100 * (1 - 0.0401))

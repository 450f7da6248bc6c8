"""Where the grouped expert GEMM shows in a device trace, and the work it
needs.

The kernel is the Pallas grouped-FFN kernel of ``kernels/moe_gemm``,
which the TPU trace shows as ``%moe_ffn_kernel.<n>`` custom calls.
Decode waves run it in their own program,
``jit(_grouped_contrib)``; the prefill runs it inside the prefill
program, which the benchmark's ``bench.prefill`` span encloses (the
harness waits for the first token inside that span).
"""
from __future__ import annotations

from typing import List, Optional

from . import flops, trace

KERNEL_NAME = "moe_ffn_kernel"
DECODE_PROGRAM = "jit__grouped_contrib"
PREFILL_SPAN = "bench.prefill"


def is_kernel(e: trace.Ev) -> bool:
    return trace.op_name(e).lstrip("%").startswith(KERNEL_NAME)


def decode_kernel_events(tr: trace.Trace) -> List[trace.Ev]:
    lo, hi = tr.window
    out = []
    for dev in tr.devices[:1]:
        out += trace.ops_in_programs(
            dev, lambda n: trace.module_name(n) == DECODE_PROGRAM,
            is_kernel, lo, hi)
    return out


def prefill_programs(tr: trace.Trace) -> List[List[trace.Ev]]:
    lo, hi = tr.window
    if not tr.devices:
        return []
    return trace.programs_within(tr.devices[0], tr.spans, PREFILL_SPAN,
                                 lo, hi)


def prefill_kernel_events(tr: trace.Trace) -> List[trace.Ev]:
    if not tr.devices:
        return []
    progs = [p for group in prefill_programs(tr) for p in group]
    return trace.ops_within(tr.devices[0], progs, is_kernel)


def roofline_share(least_s: float, events: List[trace.Ev]
                   ) -> Optional[float]:
    """Least time over the kernel's device time, in percent; nothing
    when the trace shows no kernel event to read."""
    t = trace.total_s(events)
    if not events or t <= 0:
        return None
    return 100.0 * least_s / t


def least_s(run, work) -> float:
    p = run.peaks
    return sum(flops.least_time_s(fl, by, p.bf16_flops, p.hbm_bytes_s)[0]
               for fl, by in work)

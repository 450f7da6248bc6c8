"""Median device time of one prefill: the device programs that ran
inside each ``bench.prefill`` span (the engine's prefill of one prompt,
which the harness waits for), summed per prefill."""


def read(run):
    if run.trace is None:
        return None
    from chipbench import kernels, stats, trace
    per = [trace.total_s(group) * 1e3
           for group in kernels.prefill_programs(run.trace) if group]
    return stats.percentile(per, 50)

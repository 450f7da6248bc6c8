"""Roofline share of the grouped expert GEMM in decode waves: the least
time the chip needs for the waves' work (each call's routed (row,
expert) pairs, and the weights of its distinct routed experts read
once, at bf16 peak and HBM bandwidth) over the device time of the
kernel's events in decode-wave programs, in percent."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    from chipbench import flops, kernels
    cfg = run.cfg
    item = 2 if cfg.dtype == "bfloat16" else 4
    work = flops.wave_calls(run.records, cfg.d_model, cfg.d_expert_resolved,
                            item)
    return kernels.roofline_share(kernels.least_s(run, work),
                                  kernels.decode_kernel_events(run.trace))

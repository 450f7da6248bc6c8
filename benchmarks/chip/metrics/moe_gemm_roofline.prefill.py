"""Roofline share of the grouped expert GEMM in the engine's prefill:
for each prefill of n prompt tokens, every MoE layer needs n x top-k
(row, expert) pairs and reads the weights of every routed expert (all
of them, at these lengths) once; least time at bf16 peak and HBM
bandwidth over the device time of the kernel's events inside the
prefill programs, in percent."""


def read(run):
    if run.trace is None or run.peaks is None or not run.prefills:
        return None
    from chipbench import flops, kernels
    cfg = run.cfg
    item = 2 if cfg.dtype == "bfloat16" else 4
    moe_layers = sum(ff == "moe" for _, ff in cfg.layer_kinds())
    work = []
    for n in run.prefills:
        experts = min(cfg.num_experts, n * cfg.top_k)
        call = flops.gemm_call_work(cfg.d_model, cfg.d_expert_resolved, item,
                                    n * cfg.top_k, experts, n)
        work += [call] * moe_layers
    return kernels.roofline_share(kernels.least_s(run, work),
                                  kernels.prefill_kernel_events(run.trace))

"""The prefill step's share of the chip's peak: model FLOPs of the
prompts prefilled in the traced window (the architecture plug-in's
``prompt_flops``) over the time of the ``bench.prefill`` spans (the
engine's prefill of one prompt, waited for) x the bf16 peak, in
percent.  It bounds ``moe_gemm_roofline.prefill`` from the whole step."""


def read(run):
    if run.trace is None or run.peaks is None or not run.prefills:
        return None
    from chipbench import kernels
    lo, hi = run.trace.window
    spans = [s for s in run.trace.spans if s.name == kernels.PREFILL_SPAN
             and lo <= s.start_ns and s.end_ns <= hi]
    seconds = sum(s.dur_ns for s in spans) * 1e-9
    if not spans or len(spans) != len(run.prefills) or seconds <= 0:
        return None
    total = sum(run.plugin.prompt_flops(run.cfg, n) for n in run.prefills)
    return 100.0 * total / (seconds * run.peaks.bf16_flops)

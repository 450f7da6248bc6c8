"""Model FLOPs of every token processed in the traced window (prompt and
output) over window x the chip's bf16 peak, in percent.  A prompt token
counts 2 x active parameters plus causal attention over its prefix; an
output token 2 x active parameters plus attention over its context.  The
SEP shadow's work does not count."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    from chipbench import flops
    total = sum(flops.prompt_flops(run.cfg, n) for n in run.prefills)
    t0, t1 = run.window
    for c in run.clients:
        prompt = len(c.req.prompt)
        for i, s in enumerate(c.stamps):
            if i >= 1 and t0 <= s <= t1:
                total += flops.decode_flops(run.cfg, prompt + i)
    return 100.0 * total / (run.trace.window_s * run.peaks.bf16_flops)

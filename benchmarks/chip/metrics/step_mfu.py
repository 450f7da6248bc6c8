"""Model FLOPs of every token processed in the traced window (prompt and
output) over window x the chip's bf16 peak, in percent.  The FLOPs are
the architecture plug-in's count: ``prompt_flops`` of each prompt
prefilled in the window, ``decode_flops`` of each output token at its
context.  The SEP shadow's work does not count."""


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    plugin = run.plugin
    total = sum(plugin.prompt_flops(run.cfg, n) for n in run.prefills)
    t0, t1 = run.window
    for c in run.clients:
        prompt = len(c.req.prompt)
        for i, s in enumerate(c.stamps):
            if i >= 1 and t0 <= s <= t1:
                total += plugin.decode_flops(run.cfg, prompt + i)
    return 100.0 * total / (run.trace.window_s * run.peaks.bf16_flops)

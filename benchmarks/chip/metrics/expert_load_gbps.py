"""Rate the host pays for expert loads, in GB/s: the packed bytes the
``odmoe.expert_load`` spans wholly inside the window shipped (their
``nbytes``) over those spans' time.  It is the link's rate only where
the copy ends before the span does."""


def read(run):
    from chipbench import program_spans as ps
    spans = ps.window_spans(run)
    if spans is None:
        return None
    return ps.bytes_per_ns(spans, "odmoe.expert_load", "nbytes",
                           *run.trace.window)

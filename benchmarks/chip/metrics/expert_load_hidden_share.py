"""Share of the expert loads' host time that device work hides: of the
union of the ``odmoe.expert_load`` spans in the window, the percent in
which the first device was running an op."""


def read(run):
    from chipbench import program_spans as ps
    spans = ps.window_spans(run)
    busy = None if spans is None else ps.device_busy(run)
    if busy is None:
        return None
    return ps.busy_share(spans, "odmoe.expert_load", busy,
                         *run.trace.window)

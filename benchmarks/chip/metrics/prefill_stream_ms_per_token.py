"""Host time of streaming the routed experts in prefill per output
token, in ms: the ``odmoe.prefill_stream`` spans (each routed layer's
experts brought from the host store a block at a time, computed and
dropped) over the tokens decoded in the window.  A program that holds
its experts on the device opens no such span and reports nothing."""


def read(run):
    from chipbench import program_spans as ps
    spans = ps.window_spans(run)
    if spans is None or not any(s.name == "odmoe.prefill_stream"
                                for s in spans):
        return None
    return ps.per_token_ms(run, ps.total_ns(spans, "odmoe.prefill_stream",
                                            *run.trace.window))

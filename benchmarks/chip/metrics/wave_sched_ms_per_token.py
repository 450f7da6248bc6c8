"""Host time of wave scheduling per output token, in ms: the self time
of the ``odmoe.serve`` spans (one per MoE layer: predictions, the
placement of loads onto workers, the wave loop, routing bookkeeping and
eviction), less the expert loads and wave dispatches nested in them."""


def read(run):
    from chipbench import program_spans as ps
    return ps.ms_per_token(run, lambda sp, lo, hi: ps.self_ns(
        sp, "odmoe.serve", lo, hi))

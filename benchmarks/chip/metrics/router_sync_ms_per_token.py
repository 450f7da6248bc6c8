"""Host time of the router's readback per output token, in ms: the
``odmoe.router_sync`` spans (the blocking copy of each MoE layer's
top-k expert ids to the host, which waits for the layer's mixer and
router on the device) over the tokens decoded in the window."""


def read(run):
    from chipbench import program_spans as ps
    return ps.ms_per_token(run, lambda sp, lo, hi: ps.total_ns(
        sp, "odmoe.router_sync", lo, hi))

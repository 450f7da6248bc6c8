"""Host time of the SEP shadow's steps per output token, in ms: the
``odmoe.shadow_step`` spans (the shadow's whole-model decode step and
the readback of its routing, which waits for it)."""


def read(run):
    from chipbench import program_spans as ps
    return ps.ms_per_token(run, lambda sp, lo, hi: ps.total_ns(
        sp, "odmoe.shadow_step", lo, hi))

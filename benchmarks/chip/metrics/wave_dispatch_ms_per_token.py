"""Host time of the expert waves' gather and dispatch per output token,
in ms: the ``odmoe.wave`` spans (stacking the wave's slot weights and
dispatching the grouped expert GEMM)."""


def read(run):
    from chipbench import program_spans as ps
    return ps.ms_per_token(run, lambda sp, lo, hi: ps.total_ns(
        sp, "odmoe.wave", lo, hi))

"""Host time of the expert loads that shipped per output token, in ms:
the ``odmoe.expert_load`` spans (the host->device copy of one expert's
packed weights; hits move nothing and open no span)."""


def read(run):
    from chipbench import program_spans as ps
    return ps.ms_per_token(run, lambda sp, lo, hi: ps.total_ns(
        sp, "odmoe.expert_load", lo, hi))

"""Host time of the per-step KV copies per output token, in ms: the
union of the ``odmoe.kv_gather`` spans (joining the batch's per-request
caches before a step) and the ``odmoe.kv_scatter`` spans (slicing each
request's cache back out after it)."""


def read(run):
    from chipbench import program_spans as ps
    return ps.ms_per_token(run, lambda sp, lo, hi: ps.union_ns(
        sp, ("odmoe.kv_gather", "odmoe.kv_scatter"), lo, hi))

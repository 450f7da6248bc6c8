"""Host->HBM expert loads (``WorkerSlots.stats["loads"]``) in the window
per output token decoded in it (first tokens come from prefill, which
loads nothing)."""


def read(run):
    n = run.counters.get("decoded_tokens", 0)
    return run.counters["loads"] / n if n else None

"""Time the decode thread spends on expert loads per output token, in
ms: on each thread that opens ``odmoe.decode_step``, the union of its
``odmoe.expert_load`` spans (fetches it ran itself) and its
``odmoe.expert_wait`` spans (joins on fetches that ran on loader
threads).  Fetches on other threads count only where the decode thread
waits for them."""

NAMES = ("odmoe.expert_load", "odmoe.expert_wait")


def read(run):
    from chipbench import program_spans as ps

    def stall(spans, lo, hi):
        decode = {s.thread for s in spans if s.name == "odmoe.decode_step"}
        return sum(ps.union_ns([s for s in spans if s.thread == th],
                               NAMES, lo, hi) for th in decode)
    return ps.ms_per_token(run, stall)

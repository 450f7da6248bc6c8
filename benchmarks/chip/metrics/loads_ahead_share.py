"""Share of the window's expert fetches that a prediction submitted
before its layer was served, in percent: the ``odmoe.expert_load``
spans with ``ahead=1`` over all of them (a span without the argument
counts as not ahead)."""


def read(run):
    from chipbench import program_spans as ps
    spans = ps.window_spans(run)
    if spans is None:
        return None
    loads = [s for s in spans if s.name == "odmoe.expert_load"]
    if not loads:
        return None
    return 100.0 * sum(1 for s in loads if s.args.get("ahead")) / len(loads)

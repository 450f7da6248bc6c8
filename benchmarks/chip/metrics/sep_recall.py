"""Recall of the SEP shadow's expert predictions over the window's
decode steps (the paper's Eq. 3): correctly predicted experts over
routed experts, summed over (layer, step), in percent."""


def read(run):
    num = den = 0
    for rec in run.records:
        for lr in rec.layers:
            if lr.predicted is not None:
                num += lr.correct
                den += lr.true.size
    return 100.0 * num / den if den else None

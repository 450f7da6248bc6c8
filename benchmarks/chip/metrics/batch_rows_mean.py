"""Mean rows (requests) per composed decode step in the window."""


def read(run):
    if not run.steps:
        return None
    return sum(len(s.request_ids) for s in run.steps) / len(run.steps)

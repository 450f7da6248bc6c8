"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, in percent."""


def read(run):
    if run.trace is None or not run.trace.devices:
        return None
    from chipbench import trace
    return 100.0 * (1.0 - trace.busy_s(run.trace) / run.trace.window_s)

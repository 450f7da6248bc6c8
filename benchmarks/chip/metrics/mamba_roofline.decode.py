"""Roofline share of the Mamba-2 layers' decode programs: the least time
the chip needs for their work (the bytes each ``jit_mamba_layer_step``
run must read and write once, from the architecture plug-in's
``mamba_step_bytes``, at HBM bandwidth; the programs are memory-bound
at a few rows) over the device time of those programs in the window, in
percent.  A configuration without such programs reports nothing."""

PROGRAM = "jit_mamba_layer_step"


def read(run):
    if run.trace is None or run.peaks is None or not run.trace.devices:
        return None
    step_bytes = getattr(run.plugin, "mamba_step_bytes", None)
    if step_bytes is None:
        return None
    from chipbench import trace
    lo, hi = run.trace.window
    progs = [p for p in run.trace.devices[0].programs
             if trace.module_name(p.name) == PROGRAM
             and p.start_ns >= lo and p.end_ns <= hi]
    seconds = trace.total_s(progs)
    n_mamba = sum(m == "mamba" for m, _ in run.cfg.layer_kinds())
    calls = [rec.layers[0].true.shape[0] for rec in run.records
             for _ in range(n_mamba) if rec.layers]
    if not progs or seconds <= 0 or len(calls) < len(progs):
        return None
    # the window's programs are its last steps' (a step's programs run
    # in order); each needs the bytes of its step's rows
    need = sum(step_bytes(run.cfg, rows) for rows in calls[-len(progs):])
    return 100.0 * need / run.peaks.hbm_bytes_s / seconds

"""Device time of one training step: the median run of the step's
program in the traced window (a run the trace's edge cut short does not
move it)."""


def read(run):
    seconds = run.trace_summary.module_run_s(r"jit_step")
    return 1e3 * seconds if seconds is not None else None

"""Device time of the decode program's median run in the traced window
over the token-steps a run makes (it scans `decode_chunk` of them)."""


def read(run):
    seconds = run.trace_summary.module_run_s(r"jit_decode")
    if seconds is None:
        return None
    return 1e3 * seconds / run.window["decode_chunk"]

"""Seconds of set-up in the first execution of the step's compiled
program, to the call's return (the device is not waited for): the
`first_run_s` of the record `step_lower_s.train` reads. The runtime's
first launch of a program: its buffers, the donation of the state."""


def read(run):
    return run.spec.module("layer_metrics", "step_lower_s.train") \
        .compile_seconds(run, "first_run_s")

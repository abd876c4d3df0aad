"""Device time per step of what is left of the optimizer's update
outside the gradients' fusions: the operations whose own scope is the
`optimizer` that `TrainStep` puts around `functional_update`.

Not the update's cost. A fusion takes its root's scope
(harness/trace_scopes.py), and XLA fuses each matrix's AdamW update
into the fusion that makes its gradient, which is rooted in the
backward pass: at GPT-3 1.3B this reads 3.4 ms where the update's bytes
need 32 (PERF.md section 5, PR 25). So it follows XLA's fusion choices:
it rises when an update is split from its gradient, whatever the step's
time does then. Read it beside `step_device_ms.train`, never alone."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: c.split("/")[0] == "optimizer")

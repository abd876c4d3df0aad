"""Device time per step of the sparse feed-forwards: every operation
scoped under a layer's `moe` (the router, the permutation, the expert
kernels and their activation, the shared expert, the combine), all
phases. A fusion takes its root's scope, so the AdamW update XLA fuses
into an expert gradient's conversion is in here with it."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "moe" in c.split("/"))

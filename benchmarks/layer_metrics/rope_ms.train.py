"""Device time per step of the rotary position embedding: every
operation scoped under a layer's `rope` (q and k turned, the kernel
`rope_rotate` or the composite and its gradient), all phases. Nothing to
read in a program without that scope."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "rope" in c.split("/"))

"""Device time per step of the Gated DeltaNet mixers: every operation
scoped under a layer's `gdn` (the two input projections, the
convolution, the gates and unit norms, the chunk preparation, the state
kernels, the gated norm, `out_proj`), all phases. Nothing to read in a
program without that scope."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "gdn" in c.split("/"))

"""Device time per step of latent attention's projections around the
core: every operation scoped under a layer's `mla_latent` (the query
projection, the down-projection to the latent and the shared rotary key,
the latent's norm, the up-projection to keys and values, the splits),
all phases. Nothing to read in a program without that scope."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "mla_latent" in c.split("/"))

"""Device time per step of compressed convolutional attention's mixing
of the latent: every operation scoped under a layer's `cca_mix` (the
depthwise and the grouped convolution over q and k, the q-k mean, the
unit norms and the temperature, the value shift), all phases. Nothing to
read in a program without that scope."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "cca_mix" in c.split("/"))

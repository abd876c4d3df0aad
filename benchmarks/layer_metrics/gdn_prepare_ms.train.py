"""Device time per step of the gated delta rule's chunk preparation:
every operation scoped `prepare` under a layer's `gdn/delta_rule` (the
decay masks, K K^T, Q K^T, the triangular inverse's products, U, W, Qg,
Kd for all chunks at once in XLA, and their gradients), all phases: the
forward, the forward `jax.checkpoint` runs again, and the backward, which
makes the preparation once more and differentiates it. The state kernels
stand beside the scope, not under it (`gdn_state_ms.train`). Nothing to
read in a program without that scope."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: {"gdn", "prepare"} <= set(c.split("/")))

"""Device time per step of the gated delta rule's state kernels
(`gdn_state_fwd`, `gdn_state_bwd`: the program's names, each the
innermost scope of its operation), in every phase: the forward, the
forward `jax.checkpoint` runs again and the backward."""
from harness import trace_scopes


def read(run):
    rule = run.spec.module("kernel_costs", "gated_delta")
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: rule.classify(c) is not None)

"""Device time per step of operations that run a second time: what
`jax.checkpoint` runs again in the backward pass (`rematted_computation`
in the operation's path) and XLA's own rematerialised clones (`.remat`
in the instruction's name). Work `mfu.train` does not count."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", phases=("recompute", "xla_remat"))

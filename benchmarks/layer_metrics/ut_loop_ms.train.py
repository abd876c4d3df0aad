"""Device time per step of the loop over the passes: every operation
scoped under `ut_loop` (the scanned stack: each held layer's `attn`,
`rope`, `mlp` and norms and the final norm, run `total_ut_steps` times,
and what the loop itself adds: the stacked residuals' updates, the
weights' cotangent sums), all phases: forward, run again by
`jax.checkpoint` and backward. An operation inside the `while` body is
one event each time it ran. Nothing to read in a program without that
scope."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "ut_loop" in c.split("/"))

"""Device time per step of the selective-scan kernels (`ssm_scan_fwd`,
`ssm_scan_bwd`: the program's names, each the innermost scope of its
operation), in every phase: the forward, the forward `jax.checkpoint`
runs again and the backward."""
from harness import trace_scopes


def read(run):
    scan = run.spec.module("kernel_costs", "ssm_scan")
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: scan.classify(c) is not None)

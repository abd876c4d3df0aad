"""Device time per step of the Mamba mixers: every operation scoped
under a layer's `mamba` (`in_proj`, `conv1d`, `x_proj`, the three small
norms, `dt_proj`, the scan kernels, the gate, `out_proj`), all phases. A
fusion takes its root's scope, so the AdamW update XLA fuses into a
projection's gradient is in here with it (PERF.md section 5)."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: "mamba" in c.split("/"))

"""Device time per step of the exit gate and the exit loss: every
operation scoped under `exit_gate` (the gate's product over the T normed
streams) or `exit_loss` (the exit distribution, the weights handed to the
head, the entropy, the aux), all phases: a few passes over [T, tokens]
and one [T x tokens, hidden] x [hidden] product, listed so that it is
seen when it is not small. Nothing to read in a program without those
scopes."""
from harness import trace_scopes


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(
        r"jit_step", lambda c: bool({"exit_gate", "exit_loss"}
                                    & set(c.split("/"))))

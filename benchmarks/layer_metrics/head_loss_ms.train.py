"""Device time per step of the 50304-wide head and the loss: the
operations the program scopes under `lm_head` (the tied or untied
projection, its two gradients) or under the criterion, in every phase
(forward, backward, run again by `jax.checkpoint` or by XLA)."""
from harness import trace_scopes


def in_head_or_loss(component: str) -> bool:
    return any(e == "lm_head" or "criterion" in e
               for e in component.split("/"))


def read(run):
    scoped = trace_scopes.of(run)
    return scoped and scoped.step_ms(r"jit_step", in_head_or_loss)

"""Rotate-half rotary position embedding over part of a head."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .registry import register_op

__all__ = ["rope_rotate_half"]


def _half_turn(d: int, rot: int) -> np.ndarray:
    """[d, d]: x @ it is rotate_half(x) = [-x2, x1] on the first `rot`
    dimensions (x1, x2 their halves) and zero on the rest."""
    p = np.zeros((d, d), np.float32)
    half = rot // 2
    for i in range(half):
        p[i + half, i] = -1.0       # out[i] = -x[i + half]
        p[i, i + half] = 1.0        # out[i + half] = x[i]
    return p


@register_op("rope_rotate_half", amp_policy="keep")
def rope_rotate_half(x, cos, sin):
    """x [b, s, h, d]; cos, sin [s, rot] float32, rot <= d (`nn.layers.
    moe.rope_tables`). The first rot dimensions of every head are rotated
    in pairs (i, i + rot/2): x * cos + rotate_half(x) * sin with
    rotate_half(x) = [-x2, x1]; the other d - rot pass through. Computed
    in float32 and returned in x's type.

    rotate_half is a product with a signed permutation of the head's
    dimensions, which is exact (one term a sum) and runs on the matrix
    unit: slicing a head's halves out and putting them back moves every
    element across lanes, and took a fifth of a step that holds 64 heads
    at 8192 positions (PERF.md, PR 31)."""
    d, rot = x.shape[-1], cos.shape[-1]
    exact = jax.lax.Precision.HIGHEST if x.dtype == jnp.float32 else None
    half = jnp.einsum("bshd,de->bshe", x,
                      jnp.asarray(_half_turn(d, rot), x.dtype),
                      precision=exact)
    if rot < d:     # what passes through: times one, plus nothing
        cos = jnp.concatenate([cos, jnp.ones((cos.shape[0], d - rot),
                                             cos.dtype)], axis=-1)
        sin = jnp.concatenate([sin, jnp.zeros((sin.shape[0], d - rot),
                                              sin.dtype)], axis=-1)
    out = x.astype(jnp.float32) * cos[None, :, None, :] \
        + half.astype(jnp.float32) * sin[None, :, None, :]
    return out.astype(x.dtype)

"""Rotate-half rotary position embedding over part of a head."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mesh_plan import current_mesh_plan
from ..kernels.pallas import rope as _rope
from ..kernels.pallas.flash_attention import (_pallas_available,
                                                _planned_specs)
from ..observability import perf as _pf
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


def rotate_path(x_shape, x_dtype, rot):
    """('rope_rotate' | 'composite', why): what turns an x of this
    shape, type and `rot` in a program traced now. The kernel
    (`kernels/pallas/rope.py`) on a TPU backend, for every input it
    takes; the composite elsewhere."""
    if not _pallas_available():
        return ("composite", f"no TPU Pallas backend ({jax.default_backend()})")
    why = _rope.reject_reason(x_shape, x_dtype, rot)
    return ("composite", why) if why else ("rope_rotate", "")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _turned(x, cos, sin, interpret):
    return _rope.rotate(x, cos, sin, interpret=interpret)


def _turned_fwd(x, cos, sin, interpret):
    return _turned(x, cos, sin, interpret), (cos, sin)


def _turned_bwd(interpret, tables, g):
    # linear in x: the gradient is the transpose, whatever the tables
    # hold; cos and sin are constants of the position
    return (_rope.rotate(g, *tables, back=True, interpret=interpret),
            None, None)


_turned.defvjp(_turned_fwd, _turned_bwd)


def _composite(x, cos, sin):
    """rotate_half as a product with a signed permutation of the head's
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


@register_op("rope_rotate_half", amp_policy="keep")
def rope_rotate_half(x, cos, sin):
    """x [b, s, h, d]; cos, sin [s, rot] float32, rot <= d
    (`nn.layers.rope.rope_tables`). The first rot dimensions of every head
    are rotated in pairs (i, i + rot/2): x * cos + rotate_half(x) * sin
    with rotate_half(x) = [-x2, x1]; the other d - rot pass through.
    Computed in float32 and returned in x's type.

    On a TPU backend the kernel `rope_rotate` turns x in one read and one
    write (x bfloat16 or float32, d a multiple of 128, rot even), forward
    and gradient (exact for any tables), and no gradient flows to cos and
    sin: they are constants of the position. Anything else takes the
    composite. Which, and at what shape, is `compile_record(...)["rope"]`."""
    rot = cos.shape[-1]
    path, why = rotate_path(x.shape, x.dtype, rot)
    _pf.trace_note("rope", f"{path}: " + (
        why or f"{x.shape[2]} heads, rot {rot} of {x.shape[-1]}"))
    if path == "composite":
        return _composite(x, cos, sin)
    plan = current_mesh_plan()
    if plan is None:
        return _turned(x, cos, sin, False)
    # the compiler cannot split the kernel: rows and heads as flash
    # takes them (`_planned_specs`), the tables whole on every device
    from jax.sharding import PartitionSpec as P
    spec = _planned_specs(plan, x.shape, x.shape)[0]
    return jax.shard_map(lambda x, cos, sin: _turned(x, cos, sin, False),
                         mesh=plan[0], in_specs=(spec, P(), P()),
                         out_specs=spec, check_vma=False)(x, cos, sin)

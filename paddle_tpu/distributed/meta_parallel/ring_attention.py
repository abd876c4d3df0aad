"""Ring attention: sequence-parallel attention for long context.

Reference intent: the reference scales long sequences with
sep-parallelism + segmented attention (sep axis in
fleet/meta_parallel + flash_attn over segments); the TPU-native
rendering is ring attention (Liu et al.) — each device holds one
sequence chunk of Q/K/V, K/V blocks rotate around the ring via
`ppermute` over ICI while every device accumulates its Q-chunk's
attention with the SAME online-softmax update flash attention uses.
Scores never materialize beyond [s_local, s_local] per step, so the
sequence-length memory wall becomes per-chip s/N.

Causal masking works on GLOBAL positions: chunk j contributes to
chunk i fully when j < i, triangularly when j == i, not at all when
j > i (those steps still run for SPMD uniformity — their contribution
is masked to zero).

Autograd: the whole ring is a `lax.scan` over ppermute steps inside
`shard_map`; jax differentiates it, and the backward re-runs the ring
in reverse — activation residuals stay O(s_local) per step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ...observability import comms as _comms
from ...observability import metrics as _om

_NEG_INF = -1e30


def _block_update(q, k, v, acc, m, l, q_pos, k_pos, sm_scale, causal):
    """One online-softmax accumulation of q against a (k, v) block.
    q: [b, sq, h, d]; k/v: [b, sk, h, d]; acc f32; m/l: [b, h, sq]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * sm_scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_cur = jnp.max(s, axis=-1)                       # [b, h, sq]
    m_new = jnp.maximum(m, m_cur)
    # guard fully-masked rows (no valid key yet): keep exp stable
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(s - safe_m[..., None])
    p = jnp.where(jnp.isfinite(m_new)[..., None], p, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
    l_new = alpha * l + jnp.sum(p, axis=-1)
    acc_new = acc * alpha[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32))
    return acc_new, m_new, l_new


def _ring_local(q, k, v, *, axis, sm_scale, causal, chunk):
    """Per-shard body (runs under shard_map). q/k/v: [b, s_loc, h, d]."""
    idx = jax.lax.axis_index(axis)
    n = jax.lax.psum(1, axis)  # devices on the ring
    b, s_loc, h, d = q.shape
    pos_base = jnp.arange(s_loc)
    q_pos = idx * s_loc + pos_base

    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # the zero carries are device-invariant at init but device-varying
    # after the first update; align their provenance for scan
    acc0, m0, l0 = (jax.lax.pcast(t, (axis,), to="varying")
                    for t in (acc0, m0, l0))
    perm = [(i, (i + 1) % chunk) for i in range(chunk)]

    def body(carry, step):
        acc, m, l, kb, vb = carry
        src = (idx - step) % n         # whose chunk we hold this step
        k_pos = src * s_loc + pos_base
        acc, m, l = _block_update(q, kb, vb, acc, m, l, q_pos, k_pos,
                                  sm_scale, causal)
        kb = jax.lax.ppermute(kb, axis, perm)
        vb = jax.lax.ppermute(vb, axis, perm)
        return (acc, m, l, kb, vb), None

    (acc, m, l, _, _), _ = jax.lax.scan(
        body, (acc0, m0, l0, k, v), jnp.arange(chunk))
    safe_l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / safe_l[..., None]).astype(q.dtype)    # [b, h, s, d]
    return jnp.swapaxes(out, 1, 2)                     # [b, s, h, d]


from ...ops.registry import register_op


def ring_attention_impl(q, k, v, mesh: Mesh = None, axis: str = "sep",
                        causal: bool = True, softmax_scale=None):
    """Raw-array ring attention (for jax.grad/jit callers)."""
    if mesh is None:
        raise ValueError(
            "ring attention needs a jax.sharding.Mesh with the "
            f"sequence axis ({axis!r})")
    qa, ka, va = jnp.asarray(q), jnp.asarray(k), jnp.asarray(v)
    n = mesh.shape[axis]
    if qa.shape[1] % n:
        raise ValueError(
            f"seq {qa.shape[1]} not divisible by {axis} size {n}")
    if _om._ENABLED:
        # count-only (the ring's ppermutes execute inside shard_map —
        # host timing there would be trace-time fiction): the scan runs
        # n steps, each rotating this device's K and V blocks once
        try:
            kv_bytes = (ka.size + va.size) * ka.dtype.itemsize // n
        except Exception:
            kv_bytes = 0
        _comms.count("ppermute", axis, kv_bytes * n, n=2 * n)
    d = qa.shape[-1]
    sm_scale = softmax_scale if softmax_scale is not None \
        else 1.0 / np.sqrt(d)

    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        functools.partial(_ring_local, axis=axis, sm_scale=sm_scale,
                          causal=causal, chunk=n),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    sharding = NamedSharding(mesh, spec)
    if not isinstance(qa, jax.core.Tracer):
        qa = jax.device_put(qa, sharding)
        ka = jax.device_put(ka, sharding)
        va = jax.device_put(va, sharding)
    return fn(qa, ka, va)


@register_op("ring_flash_attention")
def ring_flash_attention(q, k, v, mesh: Mesh = None, axis: str = "sep",
                         causal: bool = True, softmax_scale=None):
    """Sequence-parallel attention over `mesh[axis]`.

    q, k, v: [batch, seq, heads, head_dim] GLOBAL Tensors/arrays
    sharded (or shardable) on the sequence dim over `axis`. Returns the
    output with the same layout/sharding. seq must divide evenly by the
    axis size. Registered through the op registry so the eager tape
    differentiates it (jax.vjp through shard_map + scan); raw-jax
    callers use ring_attention_impl."""
    return ring_attention_impl(q, k, v, mesh=mesh, axis=axis,
                               causal=causal,
                               softmax_scale=softmax_scale)


class RingAttention:
    """Layer-style wrapper for the sep-parallel attention (drop-in for
    the model's SDPA when fleet's sep axis > 1)."""

    def __init__(self, mesh=None, axis="sep", causal=True):
        if mesh is None:
            from ..topology import get_hybrid_communicate_group
            hcg = get_hybrid_communicate_group()
            mesh = hcg.mesh if hcg is not None else None
        if mesh is None:
            raise ValueError(
                "RingAttention needs a mesh: pass one or call "
                "fleet.init(strategy) with a sep axis first")
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r}: "
                             f"{tuple(mesh.shape)}")
        self.mesh = mesh
        self.axis = axis
        self.causal = causal

    def __call__(self, q, k, v):
        return ring_flash_attention(q, k, v, self.mesh, self.axis,
                                    self.causal)

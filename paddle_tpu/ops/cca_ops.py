"""Compressed convolutional attention's mixing of the latent: what
stands between the fused projection and the attention product of a
`zaya` layer (nn/layers/cca.py)."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..observability import perf as _pf
from .registry import register_op

__all__ = ["cca_mix", "cca_mix_path"]

F32 = jnp.float32


def _before(x):
    """x_{t-1} along axis 1, zero before the row's start."""
    return jnp.pad(x[:, :-1], ((0, 0), (1, 0)) + ((0, 0),) * (x.ndim - 2))


def _unit(x):
    n2 = jnp.sum(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.maximum(n2, 1e-24))


def cca_mix_path(qkv_shape, qkv_dtype, heads, kv_heads):
    """-> (what mixes the latent in a program traced now, why if not the
    kernels): `pallas` (`cca_mix_fwd`, `cca_mix_bwd`,
    kernels/pallas/cca_mix.py) on a TPU backend wherever they take the
    shape, else `xla` (`_chain`). The CPU tests also give `interpret`,
    by `gated_delta.prepare_path`."""
    from ..kernels.pallas import cca_mix as kernels, gated_delta
    mode = gated_delta.prepare_path()
    if mode == "xla":
        return mode, "no TPU backend"
    why = kernels.reject_reason(qkv_shape, qkv_dtype, heads, kv_heads)
    return ("xla", why) if why else (mode, None)


@register_op("cca_mix", amp_policy="keep")
def cca_mix(qkv, dw_weight, dw_bias, group_weight, group_bias, temperature,
            heads, kv_heads):
    """qkv [b, s, (H + 2 Hk) d]: the latent queries (H heads of d), keys
    (Hk heads) and two value heads as one projection wrote them side by
    side -> (q [b, s, H, d], k, v [b, s, Hk, d]) in qkv's type, Hk = 2:

      z = q~ | k~;  z'_t = a_0 z_{t-1} + a_1 z_t + b        (a channel)
      z''_t[h] = z'_{t-1}[h] A_0[h] + z'_t[h] A_1[h] + b'[h]   (a head)
      m_h = (q~_h + k~_{h // G}) / 2,  mbar_g = mean of g's m_h
      q_h = sqrt(d) unit(z''_h + m_h)
      k_g = tau_g sqrt(d) unit(z''_{H + g} + mbar_g)
      v = (v1_t, v2_{t-1}): the second value head is the previous
          token's; positions before the row's start are zero.

    dw_weight [2, (H + Hk) d] (a_0, a_1), group_weight [H + Hk, 2 d, d]
    (A_0 over A_1), the biases [(H + Hk) d], temperature [Hk]. Both
    convolutions are two taps, so each is its input and its input moved
    one position: two multiply-adds and one product of [s, 2 d] x
    [2 d, d] a head, no padded copy for a convolution to slide over.
    The elementwise part, the norms and the temperature are computed in
    float32 whatever qkv's type (amp's black list); the grouped product
    takes and returns qkv's type.

    On a TPU backend one Pallas kernel each way
    (kernels/pallas/cca_mix.py): a block of rows is read as the
    projection wrote it, everything between is float32 in VMEM (z''
    too), and q, k and v come out as rows. Elsewhere, and for what the
    kernels refuse, the chain of XLA ops below. Which, and why, is
    `compile_record(...)["cca_mix"]` (`cca_mix_path`)."""
    from ..kernels.pallas import cca_mix as kernels
    path, why = cca_mix_path(qkv.shape, qkv.dtype, heads, kv_heads)
    _pf.trace_note("cca_mix", f"{path}: " + (
        why or f"cca_mix_fwd, cca_mix_bwd, rows of "
        f"{kernels.rows_a_block(qkv.shape[1])}"))
    params = (dw_weight, dw_bias, group_weight, group_bias, temperature)
    if path == "xla":
        return _chain(qkv, *params, heads, kv_heads)
    return kernels.mix(qkv, *params, heads, kv_heads, path == "interpret")


def _chain(qkv, dw_weight, dw_bias, group_weight, group_bias, temperature,
           heads, kv_heads):
    """`cca_mix` as XLA's: two multiply-adds, one grouped product, the
    mean, the norms and the shift, each a pass or two over
    [b, s, (H + Hk) d]."""
    b, s, width = qkv.shape
    H, Hk = heads, kv_heads
    if Hk != 2:
        raise NotImplementedError("cca_mix: two value heads, the token's "
                                  "own and the previous token's")
    d = width // (H + 2 * Hk)
    dt, n = qkv.dtype, (H + Hk) * d
    z = qkv[..., :n].astype(F32)
    v = qkv[..., n:].reshape(b, s, 2, d)
    v = jnp.stack([v[:, :, 0], _before(v[:, :, 1])], axis=2)
    z1 = (dw_weight[0].astype(F32) * _before(z)
          + dw_weight[1].astype(F32) * z + dw_bias.astype(F32))
    z1 = z1.astype(dt).reshape(b, s, H + Hk, d)
    exact = jax.lax.Precision.HIGHEST if dt == F32 else None
    taps = jnp.concatenate([_before(z1), z1], axis=-1)      # [.., 2 d]
    z2 = jnp.einsum("bshc,hcd->bshd", taps, group_weight.astype(dt),
                    precision=exact).astype(F32)
    z2 = z2 + group_bias.astype(F32).reshape(H + Hk, d)
    zq = z[..., :H * d].reshape(b, s, Hk, H // Hk, d)
    zk = z[..., H * d:].reshape(b, s, Hk, 1, d)
    m = (zq + zk) * 0.5
    mbar = jnp.mean(m, axis=3)
    scale = math.sqrt(d)
    q = scale * _unit(z2[:, :, :H] + m.reshape(b, s, H, d))
    k = (scale * temperature.astype(F32))[:, None] \
        * _unit(z2[:, :, H:] + mbar)
    return q.astype(dt), k.astype(dt), v

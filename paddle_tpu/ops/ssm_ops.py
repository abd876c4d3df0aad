"""State-space mixer ops: the Mamba-1 selective scan and the causal
depthwise convolution in front of it (Gu & Dao 2023)."""
from __future__ import annotations

import jax.numpy as jnp

from .registry import register_op

__all__ = ["selective_scan", "causal_conv1d"]


@register_op("selective_scan", amp_policy="black")
def selective_scan(x, delta, A, B, C, D):
    """h_t = exp(delta_t A) h_{t-1} + (delta_t x_t) (x) B_t from h_0 = 0,
    y_t = h_t . C_t + D x_t, per channel. x, delta [b, L, E]; A [E, N]
    (negative); B, C [b, L, N]; D [E] -> y [b, L, E] in x's type, the
    state in float32. One `jax.custom_vjp` with gradients to all six
    (kernels/pallas/selective_scan.py: Pallas kernels on a TPU, the same
    chunked algorithm in XLA elsewhere). Neither delta's softplus nor
    the gate that follows is fused in. Under amp the operands are cast
    to float32 (black list): the recurrence multiplies 4096 decays."""
    from ..kernels.pallas.selective_scan import selective_scan as scan
    return scan(x, delta, A, B, C, D)


@register_op("causal_conv1d")
def causal_conv1d(x, weight, bias=None):
    """Causal depthwise convolution along time, channels last:
    out[b, t, e] = bias[e] + sum_k weight[e, k] * x[b, t - (K-1) + k, e],
    x before t = 0 taken as zero. x [b, L, E]; weight [E, K]; bias [E].
    K shifted multiply-adds (XLA fuses them; no layout change, where a
    grouped `conv1d` wants channels first). Accumulated in float32,
    returned in x's type."""
    L, taps = x.shape[1], weight.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = weight.astype(jnp.float32)
    out = sum(xp[:, k:k + L].astype(jnp.float32) * w[:, k]
              for k in range(taps))
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out.astype(x.dtype)

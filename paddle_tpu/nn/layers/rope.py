"""The rotary tables of layers that rotate part of a head or stretch
their frequencies (YaRN): built once a forward on the host and handed to
every layer (`ops.rope_rotate_half` turns q and k by them)."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(dim, theta, factor, original_max_position_embeddings,
                  beta_fast=32.0, beta_slow=1.0):
    """YaRN's frequencies for `dim` rotated dimensions (Peng et al. 2023,
    as the `yarn` rope type computes them): with f_i = theta^(-2i/dim),
    c(n) = dim * ln(original / (2 pi n)) / (2 ln theta), low =
    max(floor(c(beta_fast)), 0), high = min(ceil(c(beta_slow)), dim - 1)
    and ramp_i = clip((i - low) / (high - low), 0, 1):
        inv_freq_i = f_i / factor * ramp_i + f_i * (1 - ramp_i).
    Returns (inv_freq [dim / 2] float64, low, high)."""
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)

    def c(n):
        return dim * math.log(original_max_position_embeddings
                              / (2 * math.pi * n)) / (2 * math.log(theta))
    low = max(math.floor(c(beta_fast)), 0)
    high = min(math.ceil(c(beta_slow)), dim - 1)
    ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp), low, high


def rope_tables(seq, head_dim, rope_theta=10000.0, rope_type="default",
                partial_rotary_factor=1.0, attention_factor=1.0, **yarn):
    """(cos, sin) [seq, rot] float32 for rotate-half RoPE over the first
    rot = head_dim * partial_rotary_factor dimensions of a head (the
    rest pass through): angle[p, i] = p * inv_freq[i mod rot/2], cos and
    sin multiplied by attention_factor. `rope_type` "default":
    inv_freq_i = theta^(-2i/rot); "yarn": `yarn_inv_freq(rot, theta,
    **yarn)`. Built on the host in float64 and rounded once."""
    rot = int(head_dim * partial_rotary_factor)
    if rope_type == "yarn":
        inv_freq, _low, _high = yarn_inv_freq(rot, rope_theta, **yarn)
    elif rope_type == "default":
        inv_freq = rope_theta ** (-2.0 * np.arange(rot // 2,
                                                   dtype=np.float64) / rot)
    else:
        raise NotImplementedError(f"rope_type {rope_type!r}")
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv_freq[None]
    angle = np.concatenate([angle, angle], axis=1)
    return (jnp.asarray(np.cos(angle) * attention_factor, jnp.float32),
            jnp.asarray(np.sin(angle) * attention_factor, jnp.float32))

"""A sparse (mixture-of-experts) feed-forward that is told which experts
it holds, and the rotary tables of layers that rotate part of a head or
stretch their frequencies (YaRN)."""
from __future__ import annotations

import math

import jax.numpy as jnp
import numpy as np

from ... import ops
from ...observability import perf
from ..initializer import Normal
from ..layer import Layer
from .common import Linear


class _Router(Layer):
    """scores -> the chosen experts and their weights (`ops.moe_route`)."""

    def __init__(self, hidden, num_experts, top_k, routed_scale, std):
        super().__init__()
        self.top_k, self.routed_scale = top_k, routed_scale
        self.weight = self.create_parameter((hidden, num_experts),
                                            attr=Normal(std=std))

    def forward(self, x):
        return ops.moe_route(x, self.weight, self.top_k, self.routed_scale)


class SwiGLU(Layer):
    """down(silu(gate(x)) * up(x)), no bias."""

    def __init__(self, hidden, width, std=0.02):
        super().__init__()
        attr = Normal(std=std)
        self.gate_proj = Linear(hidden, width, weight_attr=attr,
                                bias_attr=False)
        self.up_proj = Linear(hidden, width, weight_attr=attr,
                              bias_attr=False)
        self.down_proj = Linear(width, hidden, weight_attr=attr,
                                bias_attr=False)

    def forward(self, x):
        return self.down_proj(ops.silu(self.gate_proj(x)) * self.up_proj(x))


class SparseExpertFFN(Layer):
    """y = sum over a token's top_k experts e of w_e * SwiGLU_e(x)
           + SwiGLU_shared(x),     w = routed_scale * s / sum_chosen s,
    s = sigmoid(x W_r): dropless (no capacity; every assignment is
    computed, whatever the imbalance).

    `held = (first, count)`: the experts this layer's weights are, of
    `num_experts`. The router keeps its `num_experts` outputs and routes
    over all of them; the layer computes its own experts' part of the
    routed sum, and the shared expert in full. What the experts held
    elsewhere would add is left out: an expert-parallel deployment sums
    the parts over the chips that share the layer, and nothing here
    stands in for them or for that exchange.

    Weights: `gate_up_proj` [count, hidden, 2 * width] (gate in the first
    `width` columns) and `down_proj` [count, width, hidden]. forward
    returns (y, counts): counts [count] int32, the assignments each held
    expert got. On a TPU the expert products are the grouped-matmul
    kernels and the way back to the tokens' order the kernel
    `moe_sum_rows`, which moves the rows of the held assignments alone;
    elsewhere `jax.lax.ragged_dot` and a gather over every slot
    (`ops.moe_experts`). The `moe` note of the step's compile record
    says which."""

    def __init__(self, hidden, width, num_experts=256, top_k=8, held=None,
                 shared_width=512, routed_scale=2.5, std=0.02):
        super().__init__()
        first, count = held or (0, num_experts)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"held {held} is no range of {num_experts} "
                             "experts")
        self.num_experts, self.top_k = num_experts, top_k
        self.first, self.count = first, count
        self.router = _Router(hidden, num_experts, top_k, routed_scale, std)
        self.gate_up_proj = self.create_parameter(
            (count, hidden, 2 * width), attr=Normal(std=std))
        self.down_proj = self.create_parameter(
            (count, width, hidden), attr=Normal(std=std))
        self.shared_expert = SwiGLU(hidden, shared_width, std) \
            if shared_width else None

    def forward(self, x):
        from ...kernels.pallas.grouped_matmul import ROW_TILE, gmm_path
        from ...ops.moe_ops import way_back_path
        shape = x.shape
        flat = ops.reshape(x, (-1, shape[-1]))
        weights, experts = self.router(flat)
        perf.trace_note("moe", f"{gmm_path()}, experts {self.count} held "
                        f"of {self.num_experts}, top {self.top_k}, "
                        f"tiles of {ROW_TILE} rows, way back: "
                        f"{way_back_path()}")
        y, counts = ops.moe_experts(flat, weights, experts,
                                    self.gate_up_proj, self.down_proj,
                                    self.first)
        y = ops.reshape(y, shape)
        if self.shared_expert is not None:
            y = y + self.shared_expert(x)
        return y, counts


# -- rotary tables ------------------------------------------------------------
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

"""Gated DeltaNet: the linear-attention mixer of the `qwen3_next` model
type (Yang, Kautz & Hatamizadeh 2024), and the zero-centred RMSNorm that
family writes its norms with."""
from __future__ import annotations

import math

from ... import ops
from ...observability import perf
from ..initializer import Constant, Normal
from ..layer import Layer, traced_scope
from .common import Linear


class ZeroCenteredRMSNorm(Layer):
    """x * rsqrt(mean(x^2) + eps) * (1 + w): the weight is drawn around
    0 and the norm is the plain one's at w = 0."""

    def __init__(self, hidden_size, epsilon=1e-6):
        super().__init__()
        self.epsilon = epsilon
        self.weight = self.create_parameter(
            (hidden_size,), default_initializer=Constant(0.0))

    def forward(self, x):
        # in x's type, as the family's norm returns it: a bfloat16 q or k
        # stays bfloat16 for the rotary and the attention kernels
        return ops.cast(ops.rms_norm(x, 1.0 + self.weight, self.epsilon),
                        x.dtype)


class GatedDeltaNet(Layer):
    """Linear attention with a matrix-valued state a head. With Hk key
    heads and Hv value heads of d (Hv a multiple of Hk: a key head
    serves Hv / Hk value heads):

      * `in_proj_qkvz` [hidden, 2 Hk d + 2 Hv d] and `in_proj_ba`
        [hidden, 2 Hv], both laid out by key head: a key head's q, k,
        its value heads' v and z; its value heads' b and a;
      * q | k | v (2 Hk d + Hv d channels) through a causal depthwise
        convolution of `taps` taps without bias (`conv_weight`), then
        silu; q and k scaled to unit length, q also by 1 / sqrt(d):
        `ops.gdn_operands`, which reads the projection's output where
        it lies and writes q and k (at the Hk key heads) and v in
        float32, heads first, as the rule's kernels read them (one
        Pallas kernel each way on a TPU, a chain of XLA's elsewhere);
      * beta = sigmoid(b), g = -exp(A_log) * softplus(a + dt_bias), a
        value head, float32;
      * `ops.gated_delta_rule`: S_t = e^{g_t} S_{t-1} + beta_t k_t
        (v_t - e^{g_t} S_{t-1}^T k_t)^T, o_t = S_t^T q_t, S [d, d] a
        value head, which reads key head h's q and k for its value
        heads;
      * RMSNorm over a value head's d (`norm_weight`, a plain weight)
        times silu(z), then `out_proj` [Hv d, hidden].

    No bias anywhere."""

    def __init__(self, hidden, key_heads=16, value_heads=32, head_dim=128,
                 taps=4, epsilon=1e-6, std=0.02, out_std=None):
        super().__init__()
        if value_heads % key_heads:
            raise ValueError("key_heads must divide value_heads")
        self.key_heads, self.value_heads = key_heads, value_heads
        self.head_dim, self.taps, self.epsilon = head_dim, taps, epsilon
        kw, vw = key_heads * head_dim, value_heads * head_dim
        self.in_proj_qkvz = Linear(hidden, 2 * kw + 2 * vw, bias_attr=False,
                                   weight_attr=Normal(std=std))
        self.in_proj_ba = Linear(hidden, 2 * value_heads, bias_attr=False,
                                 weight_attr=Normal(std=std))
        self.conv_weight = self.create_parameter(
            (2 * kw + vw, taps), attr=Normal(std=1.0 / math.sqrt(taps)))
        # a decay a token of e^{-A dt}: A = 1, dt = softplus(dt_bias)
        self.dt_bias = self.create_parameter(
            (value_heads,), default_initializer=Constant(-4.6))
        self.A_log = self.create_parameter(
            (value_heads,), default_initializer=Constant(0.0))
        self.norm_weight = self.create_parameter(
            (head_dim,), default_initializer=Constant(1.0))
        self.out_proj = Linear(vw, hidden, bias_attr=False,
                               weight_attr=Normal(std=out_std or std))

    def forward(self, u):
        from ...kernels.pallas.gated_delta import (CHUNK, prepare_path,
                                                   state_path)
        from ...ops.linear_attn_ops import gdn_operands_path
        b, s, _ = u.shape
        Hk, Hv, d = self.key_heads, self.value_heads, self.head_dim
        rep = Hv // Hk
        qkvz = self.in_proj_qkvz(u)
        perf.trace_note(
            "gdn", f"heads {Hv} on {Hk}, state {d} x {d}, chunk {CHUNK}, "
            f"conv {self.taps} taps, operands: "
            f"{gdn_operands_path(qkvz.shape, qkvz.dtype.np_dtype, self.taps,
                                 Hk, Hv)}, q k at {Hk} heads, "
            f"chunk preparation: {prepare_path()}, "
            f"state pass: {state_path()}")
        ba = ops.reshape(self.in_proj_ba(u), (b, s, Hk, 2 * rep))
        beta, a = ops.split(ba, [rep, rep], axis=-1)
        with traced_scope("conv"):
            q, k, v, z = ops.gdn_operands(qkvz, self.conv_weight, Hk, Hv)
        with traced_scope("gates"):
            beta = ops.sigmoid(ops.cast(ops.reshape(beta, (b, s, Hv)),
                                        "float32"))
            a = ops.cast(ops.reshape(a, (b, s, Hv)), "float32")
            g = -ops.exp(ops.cast(self.A_log, "float32")) \
                * ops.softplus(a + self.dt_bias)
        with traced_scope("delta_rule"):
            o = ops.gated_delta_rule(q, k, v, g, beta, heads_first=True)
        with traced_scope("gated_norm"):
            y = ops.rms_norm(o, self.norm_weight, self.epsilon) \
                * ops.silu(ops.cast(z, "float32"))
            y = ops.reshape(y, (b, s, Hv * d))
        return self.out_proj(y)

"""Compressed convolutional attention (Zyphra's CCA, the grouped-query
form the `zaya` model type runs) and the learned residual scaling of
that family's sublayers."""
from __future__ import annotations

import math

from ... import ops
from ...observability import perf
from ..initializer import Constant, Normal
from ..layer import Layer, traced_scope
from .common import Linear


class CompressedConvAttention(Layer):
    """Causal attention all of which runs in a compressed latent: with
    H query heads on Hk = 2 key/value heads of d, the hidden state is
    projected to H d for the queries, Hk d for the keys and d for each
    of two value heads (one matrix, `qkv_proj`: W_q | W_k | W_v1 | W_v2),
    and then (`ops.cca_mix`: on a TPU one Pallas kernel each way,
    kernels/pallas/cca_mix.py, which reads the projection's rows and
    writes q, k and v as rows; `compile_record(...)["cca_mix"]`):

      * two causal convolutions of two taps along the sequence over
        q~ | k~: depthwise (`conv_dw_*`: a_0, a_1, b a channel), then
        grouped, a head a group (`conv_group_*`: A_0 over A_1 [2 d, d]
        and b' a head); positions before the row's start are zero;
      * the q-k mean, from the latents before the convolutions, added
        after them: m_h = (q~_h + k~_{h // G}) / 2 to query head h, its
        mean over a group's heads to the key head;
      * q and k scaled to length sqrt(d), k times a learned
        `temperature` a key head;
      * the second value head is the previous token's (the value shift);

    rotate-half RoPE on the first part of every q and k head (the
    tables are handed in), causal softmax(q k^T / sqrt(d)) v with query
    head h on key/value head h // G, and `o_proj` [H d, hidden]. No
    bias in the projections.

    The flash kernels take q, k and v as three arrays (`flash_operands`:
    `split`): the convolutions stand between the projection and the
    kernels, so nothing is read in place from `qkv_proj`'s output."""

    def __init__(self, hidden, heads=8, kv_heads=2, head_dim=128,
                 taps=(2, 2), std=0.02, use_flash_attention=False):
        super().__init__()
        if tuple(taps) != (2, 2):
            raise NotImplementedError(
                f"CompressedConvAttention: convolutions of {taps} taps; "
                "two and two are what `ops.cca_mix` computes")
        if heads % kv_heads:
            raise ValueError("kv_heads must divide heads")
        self.heads, self.kv_heads, self.head_dim = heads, kv_heads, head_dim
        self.taps = tuple(taps)
        d, ch = head_dim, (heads + kv_heads) * head_dim
        self.conv_dw_weight = self.create_parameter(
            (2, ch), attr=Normal(std=1.0 / math.sqrt(2)))
        self.conv_dw_bias = self.create_parameter((ch,), is_bias=True)
        self.conv_group_weight = self.create_parameter(
            (heads + kv_heads, 2 * d, d),
            attr=Normal(std=1.0 / math.sqrt(2 * d)))
        self.conv_group_bias = self.create_parameter((ch,), is_bias=True)
        self.temperature = self.create_parameter(
            (kv_heads,), default_initializer=Constant(1.0))
        self.qkv_proj = Linear(hidden, (heads + 2 * kv_heads) * d,
                               weight_attr=Normal(std=std), bias_attr=False)
        self.o_proj = Linear(heads * d, hidden, bias_attr=False,
                             weight_attr=Normal(std=std))
        self.use_flash_attention = use_flash_attention

    def forward(self, u, cos, sin):
        b, s, _ = u.shape
        H, Hk, d = self.heads, self.kv_heads, self.head_dim
        perf.trace_note(
            "cca", f"latent {H * d} q, {Hk * d} k, {Hk * d} v of "
            f"{u.shape[-1]}, {H} heads on {Hk}, taps {self.taps[0]} "
            f"depthwise and {self.taps[1]} grouped, value shift on head 1")
        with traced_scope("cca_proj"):
            qkv = self.qkv_proj(u)
        with traced_scope("cca_mix"):
            q, k, v = ops.cca_mix(
                qkv, self.conv_dw_weight, self.conv_dw_bias,
                self.conv_group_weight, self.conv_group_bias,
                self.temperature, H, Hk)
        with traced_scope("rope"):
            q = ops.rope_rotate_half(q, cos, sin)
            k = ops.rope_rotate_half(k, cos, sin)
        from ...incubate.nn.functional import causal_attention
        out = causal_attention(q, k, v, self.use_flash_attention)
        with traced_scope("out_proj"):
            return self.o_proj(ops.reshape(out, (b, s, H * d)))


class ResidualScale(Layer):
    """x <- (alpha_r * x + beta_r) + (alpha_o * f + beta_o): four
    learned vectors a sublayer, drawn 1 and 0 (the plain residual)."""

    def __init__(self, hidden):
        super().__init__()
        for name, value in (("alpha_r", 1.0), ("beta_r", 0.0),
                            ("alpha_o", 1.0), ("beta_o", 0.0)):
            setattr(self, name, self.create_parameter(
                (hidden,), default_initializer=Constant(value)))

    def forward(self, x, f):
        with traced_scope("res_scale"):
            return (self.alpha_r * x + self.beta_r) \
                + (self.alpha_o * f + self.beta_o)

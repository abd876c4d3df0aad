"""LLaMA-family decoder LM (RMSNorm + rotary embeddings + SwiGLU + GQA).

Capability target: BASELINE.json config 4 (LLaMA-2-13B hybrid-parallel with
recompute+amp); reference fused-op surface: fused_rms_norm /
fused_rotary_position_embedding / swiglu
(/root/reference/python/paddle/incubate/nn/functional/).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from .. import ops
from ..core.tensor import Tensor
from ..incubate.nn.functional import (causal_attention,
                                      fused_rotary_position_embedding)
from ..nn.layer import Layer
from ..nn.layers.common import Linear, Embedding
from ..nn.layers.norm import RMSNorm
from ..nn.layers.container import LayerList
from ..nn.initializer import Normal


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 0  # 0 -> num_heads (MHA); < num_heads -> GQA
    intermediate_size: int = 11008
    max_position_embeddings: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    use_flash_attention: bool = False

    def __post_init__(self):
        if self.num_kv_heads == 0:
            self.num_kv_heads = self.num_heads

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def llama_tiny(**kw):
    return LlamaConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=256,
                       max_position_embeddings=256, **kw)


def llama2_7b(**kw):
    return LlamaConfig(**kw)


def llama2_13b(**kw):
    return LlamaConfig(hidden_size=5120, num_layers=40, num_heads=40,
                       intermediate_size=13824, **kw)


def _rope_cos_sin(seq_len, head_dim, theta, dtype):
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    inv = 1.0 / (theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    freqs = jnp.outer(pos, inv)  # [s, d/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [s, d]
    return jnp.cos(emb).astype(dtype), jnp.sin(emb).astype(dtype)


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.num_kv_heads = config.num_kv_heads
        self.head_dim = config.head_dim
        self.hidden_size = config.hidden_size
        self.rope_theta = config.rope_theta
        w = Normal(std=config.initializer_range)
        kv_out = self.num_kv_heads * self.head_dim
        self.q_proj = Linear(config.hidden_size, config.hidden_size,
                             weight_attr=w, bias_attr=False)
        self.k_proj = Linear(config.hidden_size, kv_out, weight_attr=w,
                             bias_attr=False)
        self.v_proj = Linear(config.hidden_size, kv_out, weight_attr=w,
                             bias_attr=False)
        self.o_proj = Linear(config.hidden_size, config.hidden_size,
                             weight_attr=w, bias_attr=False)
        self.use_flash_attention = config.use_flash_attention

    def forward(self, x, rope_cos_sin=None):
        b, s, _ = x.shape
        q = ops.reshape(self.q_proj(x), (b, s, self.num_heads, self.head_dim))
        k = ops.reshape(self.k_proj(x),
                        (b, s, self.num_kv_heads, self.head_dim))
        v = ops.reshape(self.v_proj(x),
                        (b, s, self.num_kv_heads, self.head_dim))
        if rope_cos_sin is None:
            rope_cos_sin = _rope_cos_sin(s, self.head_dim, self.rope_theta,
                                         q._data.dtype)
        cos, sin = rope_cos_sin
        q, k = fused_rotary_position_embedding(
            q, k, sin=Tensor(sin), cos=Tensor(cos))
        out = causal_attention(q, k, v, self.use_flash_attention)
        out = ops.reshape(out, (b, s, self.hidden_size))
        return self.o_proj(out)


class LlamaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x))."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        w = Normal(std=config.initializer_range)
        self.gate_proj = Linear(config.hidden_size, config.intermediate_size,
                                weight_attr=w, bias_attr=False)
        self.up_proj = Linear(config.hidden_size, config.intermediate_size,
                              weight_attr=w, bias_attr=False)
        self.down_proj = Linear(config.intermediate_size, config.hidden_size,
                                weight_attr=w, bias_attr=False)

    def forward(self, x):
        return self.down_proj(ops.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                epsilon=config.rms_norm_eps)
        self.mlp = LlamaMLP(config)

    def forward(self, x, rope_cos_sin=None):
        x = x + self.self_attn(self.input_layernorm(x), rope_cos_sin)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [LlamaDecoderLayer(config) for _ in range(config.num_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        # rope tables are shared by every layer — build them once
        cfg = self.config
        rope = _rope_cos_sin(input_ids.shape[-1], cfg.head_dim,
                             cfg.rope_theta, x._data.dtype)
        for layer in self.layers:
            x = layer(x, rope)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size,
                              weight_attr=Normal(
                                  std=config.initializer_range),
                              bias_attr=False)

    def forward(self, input_ids):
        return self.lm_head(self.llama(input_ids))

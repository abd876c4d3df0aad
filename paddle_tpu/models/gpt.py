"""GPT decoder-only LM (flagship model).

Capability target: the reference's GPT-3 Fleet benchmarks
(/root/repo/BASELINE.json configs; reference model structure as in
test/auto_parallel/get_gpt_model.py — embeddings + pre-norm decoder stack +
tied LM head). TPU-native choices: fused QKV projection (one MXU matmul),
`is_causal` attention (no materialised [s,s] mask in HBM), bf16-friendly
throughout, and static shapes so the whole step compiles to one XLA
executable.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from .. import ops
from ..core.tensor import DeferredTensor
from ..nn.layer import Layer
from . import lm_head as _lm_head
from .lm_head import _Head
from ..nn.layers.common import Linear, Embedding, Dropout
from ..nn.layers.norm import LayerNorm
from ..nn.layers.container import LayerList
from ..nn.initializer import Normal, Constant


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    intermediate_size: int = 0  # 0 -> 4*hidden
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_dropout_prob: float = 0.1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    use_flash_attention: bool = False  # route SDPA through the Pallas kernel
    recompute: bool = False  # per-block activation remat (jax.checkpoint)
    # remat save-policy (reference recompute_granularity analog):
    # "full" | "dots" | "dots_no_batch" — see distributed/meta_parallel/
    # recompute._POLICIES. "dots" keeps matmul outputs so backward only
    # re-runs the elementwise tail (1/3 less recompute FLOPs).
    recompute_policy: str = "full"
    # remat only layers with index % recompute_interval == 0 (1 = all).
    # Skipped layers keep their activations — spend spare HBM to shave
    # recompute FLOPs (ref: fleet recompute_interval).
    recompute_interval: int = 1

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size
        if self.recompute_interval < 1:
            raise ValueError(
                f"recompute_interval must be >= 1 (got "
                f"{self.recompute_interval}); use recompute=False to "
                "disable remat")
        if self.recompute_policy not in ("full", "dots",
                                         "dots_no_batch"):
            raise ValueError(
                f"unknown recompute_policy {self.recompute_policy!r}")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_heads=4, max_position_embeddings=256,
                     **kw)


def gpt2_small(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=768, num_layers=12,
                     num_heads=12, max_position_embeddings=1024, **kw)


def gpt3_1p3b(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24,
                     num_heads=16, max_position_embeddings=2048, **kw)


def gpt3_6p7b(**kw):
    return GPTConfig(vocab_size=50304, hidden_size=4096, num_layers=32,
                     num_heads=32, max_position_embeddings=2048, **kw)


class GPTAttention(Layer):
    """Causal self-attention with a fused QKV projection."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_heads
        self.head_dim = config.head_dim
        self.hidden_size = config.hidden_size
        w_attr = Normal(std=config.initializer_range)
        out_attr = Normal(
            std=config.initializer_range / math.sqrt(2 * config.num_layers))
        self.qkv_proj = Linear(config.hidden_size, 3 * config.hidden_size,
                               weight_attr=w_attr)
        self.out_proj = Linear(config.hidden_size, config.hidden_size,
                               weight_attr=out_attr)
        self.attn_dropout_prob = config.attention_dropout_prob
        self.use_flash_attention = config.use_flash_attention

    def forward(self, x, cache=None):
        b, s, _ = x.shape
        qkv = self.qkv_proj(x)
        if self.use_flash_attention and cache is None:
            # the kernels read q, k, v where the projection wrote them
            from ..incubate.nn.functional import fused_flash_attention_qkv
            return self.out_proj(fused_flash_attention_qkv(
                qkv, self.num_heads, causal=True))
        qkv = ops.reshape(qkv, (b, s, 3, self.num_heads, self.head_dim))
        q, k, v = ops.unbind(qkv, axis=2)  # each [b, s, h, d]
        if cache is not None:
            k = ops.concat([cache[0], k], axis=1)
            v = ops.concat([cache[1], v], axis=1)
            cache = (k, v)
        if self.use_flash_attention:
            from ..incubate.nn.functional import fused_flash_attention
            out = fused_flash_attention(q, k, v, causal=True)
        else:
            out = ops.scaled_dot_product_attention(
                q, k, v, is_causal=True,
                dropout_p=self.attn_dropout_prob, training=self.training)
        out = ops.reshape(out, (b, s, self.hidden_size))
        out = self.out_proj(out)
        return (out, cache) if cache is not None else out


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        w_attr = Normal(std=config.initializer_range)
        out_attr = Normal(
            std=config.initializer_range / math.sqrt(2 * config.num_layers))
        self.fc1 = Linear(config.hidden_size, config.intermediate_size,
                          weight_attr=w_attr)
        self.fc2 = Linear(config.intermediate_size, config.hidden_size,
                          weight_attr=out_attr)

    def forward(self, x):
        return self.fc2(ops.gelu(self.fc1(x), approximate=True))


class GPTDecoderLayer(Layer):
    """Pre-norm decoder block."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln1 = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.attn = GPTAttention(config)
        self.ln2 = LayerNorm(config.hidden_size, config.layer_norm_eps)
        self.mlp = GPTMLP(config)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, x, cache=None):
        h = self.ln1(x)
        if cache is not None:
            h, cache = self.attn(h, cache)
        else:
            h = self.attn(h)
        x = x + self.dropout(h)
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return (x, cache) if cache is not None else x


class GPTEmbeddings(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        w_attr = Normal(std=config.initializer_range)
        self.word_embeddings = Embedding(config.vocab_size,
                                         config.hidden_size,
                                         weight_attr=w_attr)
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size,
            weight_attr=w_attr)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def forward(self, input_ids, position_ids=None):
        if position_ids is None:
            s = input_ids.shape[-1]
            position_ids = ops.arange(0, s, dtype="int32")
        x = self.word_embeddings(input_ids) + self.position_embeddings(
            position_ids)
        return self.dropout(x)


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = GPTEmbeddings(config)
        self.layers = LayerList(
            [GPTDecoderLayer(config) for _ in range(config.num_layers)])
        self.final_norm = LayerNorm(config.hidden_size,
                                    config.layer_norm_eps)

    def forward(self, input_ids, position_ids=None, caches=None):
        if position_ids is None and caches is not None:
            # default decode positions continue after the cached prefix
            past = caches[0][0].shape[1]
            s = input_ids.shape[-1]
            position_ids = ops.arange(past, past + s, dtype="int32")
        x = self.embeddings(input_ids, position_ids)
        new_caches = []
        use_remat = (self.config.recompute and self.training
                     and caches is None)
        if use_remat:
            from ..distributed.meta_parallel.recompute import recompute
        for i, layer in enumerate(self.layers):
            if caches is not None:
                x, c = layer(x, caches[i])
                new_caches.append(c)
            elif use_remat and i % self.config.recompute_interval == 0:
                # ref: fleet recompute_interval on GPT blocks
                # (python/paddle/distributed/fleet/recompute/recompute.py:108)
                pol = self.config.recompute_policy
                x = recompute(layer, x,
                              policy=None if pol == "full" else pol)
            else:
                x = layer(x)
        x = self.final_norm(x)
        return (x, new_caches) if caches is not None else x


class GPTForCausalLM(Layer):
    """GPT with a (tied) LM head producing [b, s, vocab] logits.

    In a traced training forward (a `TrainStep`, no caches, one device)
    the logits are a `DeferredTensor`: `GPTPretrainingCriterion` computes
    the loss from what they are the product of, in chunks of tokens
    (`ops.linear_cross_entropy`), and the [tokens, vocab] array never
    exists; any other reader gets the whole product as ever."""

    def generate(self, input_ids, **kwargs):
        """Static-shape KV-cache decoding (see models/generation.py)."""
        from .generation import generate
        return generate(self, input_ids, **kwargs)

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = Linear(config.hidden_size, config.vocab_size,
                                  weight_attr=Normal(
                                      std=config.initializer_range),
                                  bias_attr=False)

    def _tied_weight(self):
        return self.gpt.embeddings.word_embeddings.weight

    def lm_logits(self, hidden):
        """Project hidden states to vocab logits (tied or untied head) —
        shared by forward() and the decode path (models/generation.py)."""
        return _lm_head.lm_logits(hidden, self._tied_weight(), self.lm_head)

    def forward(self, input_ids, position_ids=None, caches=None):
        out = self.gpt(input_ids, position_ids, caches)
        if caches is not None:
            hidden, new_caches = out
            return self.lm_logits(hidden), new_caches
        return _lm_head.causal_lm_logits(self.training, out,
                                        self._tied_weight(), self.lm_head)


class GPTPretrainingCriterion(Layer):
    """Next-token cross-entropy (labels = input shifted by the caller)."""

    def forward(self, logits, labels, loss_mask=None):
        if (isinstance(logits, DeferredTensor) and not logits.computed
                and isinstance(logits.producer, _Head)):
            return self._from_head(logits.producer, labels, loss_mask)
        loss = ops.cross_entropy(logits, labels, reduction="none")
        if loss_mask is not None:
            loss_mask = ops.reshape(loss_mask, loss.shape)
            return ops.sum(loss * loss_mask) / ops.maximum(
                ops.sum(loss_mask), 1e-6)
        return ops.mean(loss)

    def _from_head(self, head, labels, loss_mask):
        """The same mean from the head's operands, the logits never
        whole (`lm_head.head_cross_entropy`)."""
        if loss_mask is None:
            weight = None                       # 1/n each: the mean
        else:
            mask = ops.cast(ops.reshape(loss_mask, (-1,)), "float32")
            weight = mask / ops.maximum(ops.sum(mask), 1e-6)
        return _lm_head.head_cross_entropy(head, labels, weight)


def num_params(config: GPTConfig) -> int:
    """Parameter count."""
    h, v, L = config.hidden_size, config.vocab_size, config.num_layers
    i = config.intermediate_size
    per_layer = (3 * h * h + 3 * h) + (h * h + h) + (h * i + i) + (
        i * h + h) + 4 * h
    emb = v * h + config.max_position_embeddings * h
    head = 0 if config.tie_word_embeddings else v * h
    return emb + L * per_layer + 2 * h + head

"""Laguna: a decoder whose layers differ by index in three ways at once
(the `laguna` model type of poolside's Laguna-XS.2 public config.json,
whose keys `LagunaConfig` carries under their own names).

Every layer is pre-norm, `x += attn(norm(x)); x += ffn(norm(x))`, RMSNorm,
no bias; a final norm and an untied head.

* attention, layer l: `num_attention_heads_per_layer[l]` query heads on
  `num_key_value_heads` key/value heads of `head_dim`, causal; in a
  `sliding_attention` layer a row sees its own position and the
  `sliding_window` - 1 before it. Rotate-half RoPE by the layer kind's
  entry of `rope_parameters`: theta, `default` or `yarn` frequencies, a
  `partial_rotary_factor` (the first part of a head is rotated, the rest
  passes through) and yarn's `attention_factor` on cos and sin.
* feed-forward, layer l: `mlp_layer_types[l]` `dense`: SwiGLU of
  `intermediate_size`; `sparse`: `nn.SparseExpertFFN`: a sigmoid router
  over `num_experts`, the `num_experts_per_tok` largest scores normalised
  to one and scaled by `moe_routed_scaling_factor`, experts of
  `moe_intermediate_size`, one shared expert of
  `shared_expert_intermediate_size`.

What the config.json leaves to a convention (benchmarks/configs/
laguna-xs2-l5-e64.json, `assumed`): `gating: true` is read as gated
(three-matrix) feed-forwards, no attention gate; no q/k norm; silu; no
selection bias and no auxiliary loss.

`experts_held = (first, count)`: the expert-parallel share. The layer
holds `count` of the `num_experts` experts, routes over all of them and
computes its own experts' part (nn/layers/moe.py).

`LagunaForCausalLM.forward` returns the logits (a promise in a traced
training forward, as the other families'); the per-layer counts of
assignments to held experts of that forward are `model.expert_counts`
([sparse layers, count] int32), for a loss function to hand out as aux.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields

from .. import ops
from ..incubate.nn.functional import causal_attention
from ..nn.initializer import Normal
from ..nn.layer import Layer, traced_scope
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.moe import (SparseExpertFFN, SwiGLU,  # noqa: F401
                             observe_expert_load)
from ..nn.layers.norm import RMSNorm
from ..nn.layers.rope import rope_tables
from ..observability import perf
from . import lm_head as _lm_head

_PERIOD = ("full_attention", "sliding_attention", "sliding_attention",
           "sliding_attention")


def _xs2_rope():
    return {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
    }


@dataclass
class LagunaConfig:
    # the published config.json's keys, Laguna-XS.2's values
    vocab_size: int = 100352
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 40
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    max_position_embeddings: int = 262144
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 256
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    tie_word_embeddings: bool = False
    gating: bool = True
    sliding_window: int = 512
    rope_parameters: dict = field(default_factory=_xs2_rope)
    layer_types: list = None            # None -> the 1:3 period
    mlp_layer_types: list = None        # None -> dense, then sparse
    num_attention_heads_per_layer: list = None  # None -> 48 full, 64 sliding
    moe_apply_router_weight_on_input: bool = False
    moe_routed_scaling_factor: float = 2.5
    # what the config.json leaves to the model type's code
    initializer_range: float = 0.02
    # the router's scores: "sigmoid" (this model type's), or "softmax"
    # over all the experts (the `mellum` model type, models/mellum2.py)
    router_score: str = "sigmoid"
    # the expert-parallel share: (first, count) of num_experts, None = all
    experts_held: tuple = None
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False         # jax.checkpoint around a layer
    recompute_interval: int = 1     # ... whose index % interval == 0

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [_PERIOD[i % 4] for i in range(n)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["dense"] + ["sparse"] * (n - 1)
        if self.num_attention_heads_per_layer is None:
            self.num_attention_heads_per_layer = [
                self.num_attention_heads if t == "full_attention" else 64
                for t in self.layer_types]
        for name in ("layer_types", "mlp_layer_types",
                     "num_attention_heads_per_layer"):
            if len(getattr(self, name)) != n:
                raise ValueError(f"LagunaConfig: {name} has "
                                 f"{len(getattr(self, name))} entries for "
                                 f"{n} layers")
        if self.attention_bias or self.tie_word_embeddings \
                or self.moe_apply_router_weight_on_input or not self.gating:
            raise NotImplementedError(
                "LagunaConfig: no attention bias, an untied head, router "
                "weights on the experts' output and gated feed-forwards "
                "only")
        if any(h % self.num_key_value_heads
               for h in self.num_attention_heads_per_layer):
            raise ValueError("num_key_value_heads must divide every "
                             "layer's query heads")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        if self.recompute_interval < 1:
            raise ValueError("recompute_interval must be >= 1")

    @classmethod
    def from_dict(cls, d: dict, **kw):
        """From a config.json's dict: the keys this class has, the rest
        left where they are. A benchmark configuration's cut
        (`num_experts` the experts held, the published count under
        `published`) becomes `experts_held` of the published count, from
        `expert_first` (0) on."""
        known = {f.name for f in fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        published = d.get("published", {}).get("num_experts")
        if published is not None and "experts_held" not in kw:
            kept["experts_held"] = (d.get("expert_first", 0),
                                    d["num_experts"])
            kept["num_experts"] = published
        return cls(**kept, **kw)


def laguna_tiny(**kw):
    kw = {"experts_held": None, **kw}
    return LagunaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=16, num_experts_per_tok=4,
        moe_intermediate_size=32, shared_expert_intermediate_size=32,
        sliding_window=8, num_attention_heads_per_layer=[4, 6, 6, 6, 4],
        **kw)


def _linear(n_in, n_out, config):
    return Linear(n_in, n_out, bias_attr=False,
                  weight_attr=Normal(std=config.initializer_range))


class LagunaAttention(Layer):
    """Causal attention of one layer kind: its own query-head count,
    its window or none, its rotary table (handed in by the model)."""

    def __init__(self, config: LagunaConfig, index: int):
        super().__init__()
        self.index = index
        self.heads = config.num_attention_heads_per_layer[index]
        self.kv_heads, self.head_dim = (config.num_key_value_heads,
                                        config.head_dim)
        self.window = config.sliding_window \
            if config.layer_types[index] == "sliding_attention" else None
        h, d = config.hidden_size, config.head_dim
        self.q_proj = _linear(h, self.heads * d, config)
        self.k_proj = _linear(h, self.kv_heads * d, config)
        self.v_proj = _linear(h, self.kv_heads * d, config)
        self.o_proj = _linear(self.heads * d, h, config)
        self.use_flash_attention = config.use_flash_attention

    def forward(self, u, cos, sin):
        b, s, _ = u.shape
        d = self.head_dim
        q = ops.reshape(self.q_proj(u), (b, s, self.heads, d))
        k = ops.reshape(self.k_proj(u), (b, s, self.kv_heads, d))
        v = ops.reshape(self.v_proj(u), (b, s, self.kv_heads, d))
        with traced_scope("rope"):
            q = ops.rope_rotate_half(q, cos, sin)
            k = ops.rope_rotate_half(k, cos, sin)
        if self.window is not None:
            perf.trace_note("attention_window",
                            f"layer {self.index}: {self.window}")
        out = causal_attention(q, k, v, self.use_flash_attention,
                               self.window)
        return self.o_proj(ops.reshape(out, (b, s, self.heads * d)))


class LagunaDecoderLayer(Layer):
    """One layer: `attn` by its kind, then `mlp` (dense) or `moe`
    (sparse). forward returns (x, counts): the assignments each held
    expert got, or None from a dense layer."""

    def __init__(self, config: LagunaConfig, index: int):
        super().__init__()
        h = config.hidden_size
        self.input_layernorm = RMSNorm(h, epsilon=config.rms_norm_eps)
        self.attn = LagunaAttention(config, index)
        self.post_attention_layernorm = RMSNorm(
            h, epsilon=config.rms_norm_eps)
        if config.mlp_layer_types[index] == "sparse":
            self.moe = SparseExpertFFN(
                h, config.moe_intermediate_size,
                num_experts=config.num_experts,
                top_k=config.num_experts_per_tok,
                held=tuple(config.experts_held),
                shared_width=config.shared_expert_intermediate_size,
                routed_scale=config.moe_routed_scaling_factor,
                std=config.initializer_range,
                router_score=config.router_score)
        else:
            self.mlp = SwiGLU(h, config.intermediate_size,
                              config.initializer_range)

    def forward(self, x, cos, sin):
        x = x + self.attn(self.input_layernorm(x), cos, sin)
        u = self.post_attention_layernorm(x)
        if hasattr(self, "moe"):
            y, counts = self.moe(u)
            return x + y, counts
        return x + self.mlp(u), None


class LagunaModel(Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [LagunaDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        """-> (hidden, counts of every sparse layer)."""
        cfg = self.config
        x = self.embed_tokens(input_ids)
        seq = input_ids.shape[1]
        # one table a layer kind, float32
        tables = {kind: rope_tables(seq, cfg.head_dim, **params)
                  for kind, params in cfg.rope_parameters.items()
                  if isinstance(params, dict) and kind in cfg.layer_types}
        from ..distributed.meta_parallel.recompute import layer_calls
        counts = []
        # recomputed, a full layer keeps its flash outputs, a window
        # layer not
        for i, call in enumerate(layer_calls(
                self.layers, cfg.recompute and self.training,
                cfg.recompute_interval)):
            x, c = call(x, *tables[cfg.layer_types[i]])
            if c is not None:
                counts.append(c)
        return self.norm(x), counts


class LagunaForCausalLM(Layer):
    def __init__(self, config: LagunaConfig):
        super().__init__()
        self.config = config
        self.laguna = LagunaModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size, config)
        self.expert_counts = None

    def lm_logits(self, hidden):
        return _lm_head.lm_logits(
            hidden, self.laguna.embed_tokens.weight, self.lm_head)

    def forward(self, input_ids):
        hidden, counts = self.laguna(input_ids)
        self.expert_counts = ops.stack(counts, axis=0) if counts else None
        return _lm_head.causal_lm_logits(
            self.training, hidden, self.laguna.embed_tokens.weight,
            self.lm_head)

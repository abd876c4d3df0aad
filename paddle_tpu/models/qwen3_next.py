"""Qwen3-Next: a decoder whose layers differ by index in their mixer
(the `qwen3_next` model type of Qwen's Qwen3-Next-80B-A3B public
config.json, whose keys `Qwen3NextConfig` carries under their own
names).

Every layer is pre-norm, `x += mixer(norm(x)); x += moe(norm(x))`, with
the zero-centred RMSNorm `x * rsqrt(mean(x^2) + eps) * (1 + w)`; a
final norm and an untied head. Layer i's mixer is full attention where
(i + 1) % `full_attention_interval` == 0 and linear attention elsewhere:

* linear attention: `nn.GatedDeltaNet`: `linear_num_key_heads` key
  heads and `linear_num_value_heads` value heads of
  `linear_key_head_dim` (= `linear_value_head_dim`), a causal
  convolution of `linear_conv_kernel_dim` taps over q | k | v, the
  gated delta rule (`ops.gated_delta_rule`), a norm a head gated by
  silu(z);
* full attention: `q_proj` writes, a head, the query and an output gate
  side by side (2 x `head_dim`); q and k are normalised a head
  (zero-centred weights), rotate-half RoPE turns the first
  `partial_rotary_factor` of a head, causal softmax attention of
  `num_attention_heads` on `num_key_value_heads`, the output times
  sigmoid(gate), `o_proj`;
* feed-forward, every layer: `nn.SparseExpertFFN` with the softmax
  router (`num_experts_per_tok` of `num_experts`, normalised to one:
  `norm_topk_prob`), experts of `moe_intermediate_size` and one shared
  expert of `shared_expert_intermediate_size` behind a sigmoid gate.

Not here: the multi-token-prediction module the family describes (the
config.json has no key of it); a dense layer (`mlp_only_layers` is
empty and `decoder_sparse_step` 1).

`experts_held = (first, count)`: the expert-parallel share, as
`models/laguna.py`'s. `Qwen3NextForCausalLM.forward` returns the logits
(a promise in a traced training forward); the per-layer counts of
assignments to held experts of that forward are `model.expert_counts`
([layers, count] int32), for a loss function to hand out as aux.
"""
from __future__ import annotations

from dataclasses import dataclass, fields

from .. import ops
from ..incubate.nn.functional import causal_attention
from ..nn.initializer import Normal
from ..nn.layer import Layer, traced_scope
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.gdn import GatedDeltaNet, ZeroCenteredRMSNorm
from ..nn.layers.moe import SparseExpertFFN
from ..nn.layers.rope import rope_tables
from . import lm_head as _lm_head


@dataclass
class Qwen3NextConfig:
    # the published config.json's keys, Qwen3-Next-80B-A3B's values
    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    max_position_embeddings: int = 262144
    partial_rotary_factor: float = 0.25
    rope_theta: float = 10000000.0
    rope_scaling: dict = None
    rms_norm_eps: float = 1e-6
    hidden_act: str = "silu"
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    decoder_sparse_step: int = 1
    mlp_only_layers: tuple = ()
    tie_word_embeddings: bool = False
    use_sliding_window: bool = False
    # what the config.json leaves to the model type's code
    initializer_range: float = 0.02
    # the depth matrices that write to the stream are drawn for (None:
    # this model's): a share of a deeper model keeps the whole one's
    residual_depth: int = None
    # the expert-parallel share: (first, count) of num_experts, None = all
    experts_held: tuple = None
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False         # jax.checkpoint around a layer
    recompute_interval: int = 1     # ... whose index % interval == 0

    def __post_init__(self):
        if (self.rope_scaling is not None or self.use_sliding_window
                or self.tie_word_embeddings or not self.norm_topk_prob
                or self.decoder_sparse_step != 1 or self.mlp_only_layers
                or self.hidden_act != "silu"):
            raise NotImplementedError(
                "Qwen3NextConfig: the default rotary rule, no window, an "
                "untied head, chosen probabilities normalised to one, "
                "sparse experts in every layer and silu only")
        if self.linear_key_head_dim != self.linear_value_head_dim:
            raise NotImplementedError(
                "Qwen3NextConfig: key and value heads of one size (the "
                "state is square)")
        if self.experts_held is None:
            self.experts_held = (0, self.num_experts)
        if self.recompute_interval < 1:
            raise ValueError("recompute_interval must be >= 1")

    def is_full(self, i: int) -> bool:
        return (i + 1) % self.full_attention_interval == 0

    @property
    def out_std(self) -> float:
        depth = self.residual_depth or self.num_hidden_layers
        return self.initializer_range / (2 * depth) ** 0.5

    @classmethod
    def from_dict(cls, d: dict, **kw):
        """From a config.json's dict: the keys this class has, the rest
        left where they are. A benchmark configuration's cut
        (`num_experts` the experts held, `num_hidden_layers` the layers
        here, the published counts under `published`) becomes
        `experts_held` of the published count, from `expert_first` (0)
        on, and `residual_depth` the published depth."""
        known = {f.name for f in fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        kept["mlp_only_layers"] = tuple(kept.get("mlp_only_layers", ()))
        published = d.get("published", {})
        if "num_experts" in published and "experts_held" not in kw:
            kept["experts_held"] = (d.get("expert_first", 0),
                                    d["num_experts"])
            kept["num_experts"] = published["num_experts"]
        if "num_hidden_layers" in published:
            kept.setdefault("residual_depth", published["num_hidden_layers"])
        return cls(**kept, **kw)


def qwen3_next_tiny(**kw):
    return Qwen3NextConfig(**{**dict(
        vocab_size=512, hidden_size=64, num_hidden_layers=4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_key_head_dim=8, linear_value_head_dim=8,
        linear_num_key_heads=2, linear_num_value_heads=4,
        num_experts=16, num_experts_per_tok=4, moe_intermediate_size=32,
        shared_expert_intermediate_size=32, max_position_embeddings=256,
        partial_rotary_factor=0.5), **kw})


def _linear(n_in, n_out, std):
    return Linear(n_in, n_out, bias_attr=False, weight_attr=Normal(std=std))


class Qwen3NextAttention(Layer):
    """Causal softmax attention with an output gate: `q_proj` writes a
    head's query and its gate side by side; q and k normalised a head;
    partial rotate-half RoPE (the tables are handed in)."""

    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.heads, self.kv_heads = (config.num_attention_heads,
                                     config.num_key_value_heads)
        self.head_dim = d = config.head_dim
        h, std = config.hidden_size, config.initializer_range
        self.q_proj = _linear(h, self.heads * 2 * d, std)
        self.k_proj = _linear(h, self.kv_heads * d, std)
        self.v_proj = _linear(h, self.kv_heads * d, std)
        self.o_proj = _linear(self.heads * d, h, config.out_std)
        self.q_norm = ZeroCenteredRMSNorm(d, config.rms_norm_eps)
        self.k_norm = ZeroCenteredRMSNorm(d, config.rms_norm_eps)
        self.use_flash_attention = config.use_flash_attention

    def forward(self, u, cos, sin):
        b, s, _ = u.shape
        H, Hk, d = self.heads, self.kv_heads, self.head_dim
        q, gate = ops.split(ops.reshape(self.q_proj(u), (b, s, H, 2 * d)),
                            2, axis=-1)
        q = self.q_norm(q)
        k = self.k_norm(ops.reshape(self.k_proj(u), (b, s, Hk, d)))
        v = ops.reshape(self.v_proj(u), (b, s, Hk, d))
        with traced_scope("rope"):
            q = ops.rope_rotate_half(q, cos, sin)
            k = ops.rope_rotate_half(k, cos, sin)
        out = causal_attention(q, k, v, self.use_flash_attention)
        with traced_scope("out_gate"):
            out = ops.reshape(out * ops.sigmoid(gate), (b, s, H * d))
        return self.o_proj(out)


class Qwen3NextDecoderLayer(Layer):
    """One layer: the mixer is held as `attn` (full) or as `gdn`
    (linear), by index; then `moe`. forward returns (x, counts): the
    assignments each held expert got."""

    def __init__(self, config: Qwen3NextConfig, index: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        std = config.initializer_range
        self.input_layernorm = ZeroCenteredRMSNorm(h, eps)
        if config.is_full(index):
            self.attn = Qwen3NextAttention(config)
        else:
            self.gdn = GatedDeltaNet(
                h, config.linear_num_key_heads,
                config.linear_num_value_heads, config.linear_key_head_dim,
                config.linear_conv_kernel_dim, eps, std, config.out_std)
        self.post_attention_layernorm = ZeroCenteredRMSNorm(h, eps)
        self.moe = SparseExpertFFN(
            h, config.moe_intermediate_size,
            num_experts=config.num_experts,
            top_k=config.num_experts_per_tok,
            held=tuple(config.experts_held),
            shared_width=config.shared_expert_intermediate_size,
            routed_scale=1.0, std=std, router_score="softmax",
            shared_gate=True)

    def forward(self, x, cos, sin):
        u = self.input_layernorm(x)
        x = x + (self.attn(u, cos, sin) if hasattr(self, "attn")
                 else self.gdn(u))
        y, counts = self.moe(self.post_attention_layernorm(x))
        return x + y, counts


class Qwen3NextModel(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [Qwen3NextDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = ZeroCenteredRMSNorm(config.hidden_size,
                                        config.rms_norm_eps)

    def forward(self, input_ids):
        """-> (hidden, counts of every layer)."""
        cfg = self.config
        x = self.embed_tokens(input_ids)
        cos, sin = rope_tables(
            input_ids.shape[1], cfg.head_dim, rope_theta=cfg.rope_theta,
            partial_rotary_factor=cfg.partial_rotary_factor)
        from ..distributed.meta_parallel.recompute import layer_calls
        counts = []
        # recomputed, a full layer keeps its flash outputs; a linear one
        # has none, and runs its state pass again
        for call in layer_calls(self.layers, cfg.recompute and self.training,
                                cfg.recompute_interval):
            x, c = call(x, cos, sin)
            counts.append(c)
        return self.norm(x), counts


class Qwen3NextForCausalLM(Layer):
    def __init__(self, config: Qwen3NextConfig):
        super().__init__()
        self.config = config
        self.model = Qwen3NextModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size,
                               config.initializer_range)
        self.expert_counts = None

    def lm_logits(self, hidden):
        return _lm_head.lm_logits(
            hidden, self.model.embed_tokens.weight, self.lm_head)

    def forward(self, input_ids):
        hidden, counts = self.model(input_ids)
        self.expert_counts = ops.stack(counts, axis=0)
        return _lm_head.causal_lm_logits(
            self.training, hidden, self.model.embed_tokens.weight,
            self.lm_head)

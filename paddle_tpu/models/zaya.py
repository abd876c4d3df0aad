"""ZAYA1: a decoder whose every layer is compressed convolutional
attention and a one-choice sparse feed-forward with an MLP router (the
`zaya` model type of Zyphra's ZAYA1-8B public config.json, whose keys
`ZayaConfig` carries under their own names).

Every layer is `hybrid`: with u = RMSNorm(x) before each sublayer f and
four learned vectors a sublayer (`nn.ResidualScale`),

    x <- (alpha_r * x + beta_r) + (alpha_o * f(u) + beta_o)

* attention: `nn.CompressedConvAttention`: queries, keys and values in a
  latent of `num_attention_heads`, `num_key_value_heads` and
  `num_key_value_heads` heads of `head_dim`, two causal convolutions of
  `cca_time0` and `cca_time1` taps over q and k, the q-k mean, unit
  norms and a temperature, the shifted value head, partial rotate-half
  RoPE by `rope_parameters[layer_type]`;
* feed-forward: `nn.SparseExpertFFN` with the MLP router: a
  down-projection to `router_hidden_size` that also runs from layer to
  layer (depth averaging: a second stream beside x), two hidden layers,
  softmax over `num_experts`, the one largest chosen and its probability
  the weight, SwiGLU experts of `moe_intermediate_size`, none shared.

A final RMSNorm; the head is the embedding (tied).

What the config.json leaves to the family's two public descriptions
(benchmarks/configs/zaya1-8b-l5-e8.json, `assumed`): every form above
but the sizes.

`experts_held = (first, count)`: the expert-parallel share, as
`models/laguna.py`'s. `ZayaForCausalLM.forward` returns the logits (a
promise in a traced training forward); of that forward,
`model.expert_counts` ([layers, count] int32: the tokens each held
expert got), `model.router_top_weight` ([layers] float32: the mean
chosen probability) and `model.expert_choice` ([layers, tokens] int32:
the expert each token chose) are there for a loss function to hand out
as aux.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields

from .. import ops
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.layers.cca import CompressedConvAttention, ResidualScale
from ..nn.layers.common import Embedding
from ..nn.layers.container import LayerList
from ..nn.layers.moe import SparseExpertFFN
from ..nn.layers.norm import RMSNorm
from ..nn.layers.rope import rope_tables
from . import lm_head as _lm_head


def _zaya1_rope():
    return {"hybrid": {"partial_rotary_factor": 0.5, "rope_theta": 5000000,
                       "rope_type": "default"}}


@dataclass
class ZayaConfig:
    # the published config.json's keys, ZAYA1-8B's values
    vocab_size: int = 262272
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    head_dim: int = 128
    cca_time0: int = 2
    cca_time1: int = 2
    max_position_embeddings: int = 131072
    attention_bias: bool = False
    lm_head_bias: bool = False
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    num_experts: int = 16
    num_experts_per_tok: int = 1
    moe_intermediate_size: int = 2048
    router_hidden_size: int = 256
    tie_word_embeddings: bool = True
    sliding_window: int = None
    rope_parameters: dict = field(default_factory=_zaya1_rope)
    layer_types: list = None            # None -> all `hybrid`
    # what the config.json leaves to the model type's code
    initializer_range: float = 0.02
    # the expert-parallel share: (first, count) of num_experts, None = all
    experts_held: tuple = None
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False         # jax.checkpoint around a layer
    recompute_interval: int = 1     # ... whose index % interval == 0

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = ["hybrid"] * n
        if len(self.layer_types) != n:
            raise ValueError(f"ZayaConfig: layer_types has "
                             f"{len(self.layer_types)} entries for {n} "
                             "layers")
        if set(self.layer_types) != {"hybrid"} \
                or self.sliding_window is not None:
            raise NotImplementedError(
                "ZayaConfig: `hybrid` layers without a window only (the "
                "`hybrid_sliding` kind is the 74B sibling's)")
        if self.attention_bias or self.lm_head_bias \
                or not self.tie_word_embeddings \
                or self.num_experts_per_tok != 1 \
                or self.hidden_act != "silu":
            raise NotImplementedError(
                "ZayaConfig: no bias, a tied head, one expert a token and "
                "silu only")
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


def zaya_tiny(**kw):
    kw = {"experts_held": None, **kw}
    return ZayaConfig(
        vocab_size=512, hidden_size=64, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        num_experts=4, moe_intermediate_size=64, router_hidden_size=32,
        max_position_embeddings=256, **kw)


class ZayaDecoderLayer(Layer):
    """One `hybrid` layer. forward(x, r, cos, sin) -> (x, r, counts,
    top, choice): r the router's state [b, s, router_hidden_size] of the
    layer before (zero before the first) and of this one, counts the
    tokens each held expert got, top the mean chosen probability, choice
    [b * s] the expert each token chose."""

    def __init__(self, config: ZayaConfig):
        super().__init__()
        h = config.hidden_size
        std, R = config.initializer_range, config.router_hidden_size
        self.input_layernorm = RMSNorm(h, epsilon=config.rms_norm_eps)
        self.attn = CompressedConvAttention(
            h, config.num_attention_heads, config.num_key_value_heads,
            config.head_dim, (config.cca_time0, config.cca_time1),
            std=std, use_flash_attention=config.use_flash_attention)
        self.attn_res = ResidualScale(h)
        self.post_attention_layernorm = RMSNorm(
            h, epsilon=config.rms_norm_eps)
        self.moe = SparseExpertFFN(
            h, config.moe_intermediate_size,
            num_experts=config.num_experts, top_k=1,
            held=tuple(config.experts_held), shared_width=0, std=std,
            # fan-in scaled: (down, hidden, out) standard deviations
            router_mlp=(R, (h ** -0.5, R ** -0.5, R ** -0.5)))
        self.moe_res = ResidualScale(h)

    def forward(self, x, r, cos, sin):
        x = self.attn_res(x, self.attn(self.input_layernorm(x), cos, sin))
        y, counts, r, weights, experts = self.moe(
            self.post_attention_layernorm(x), r)
        return (self.moe_res(x, y), r, counts, ops.mean(weights),
                ops.reshape(experts, (-1,)))


class ZayaModel(Layer):
    def __init__(self, config: ZayaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [ZayaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        """-> (hidden, then a list a layer of: counts, mean chosen
        probability, choices)."""
        cfg = self.config
        x = self.embed_tokens(input_ids)
        b, seq = input_ids.shape
        cos, sin = rope_tables(seq, cfg.head_dim,
                               **cfg.rope_parameters["hybrid"])
        # the second stream: the router's state, zero before layer 0
        r = ops.zeros([b, seq, cfg.router_hidden_size], "float32")
        from ..distributed.meta_parallel.recompute import layer_calls
        told = []
        for call in layer_calls(self.layers, cfg.recompute and self.training,
                                cfg.recompute_interval):
            x, r, *tell = call(x, r, cos, sin)
            told.append(tell)
        return (self.norm(x),) + tuple(zip(*told))


class ZayaForCausalLM(Layer):
    def __init__(self, config: ZayaConfig):
        super().__init__()
        self.config = config
        self.zaya = ZayaModel(config)
        self.expert_counts = self.router_top_weight = None
        self.expert_choice = None

    def lm_logits(self, hidden):
        return _lm_head.lm_logits(hidden, self.zaya.embed_tokens.weight)

    def forward(self, input_ids):
        hidden, counts, tops, choices = self.zaya(input_ids)
        self.expert_counts = ops.stack(list(counts), axis=0)
        self.router_top_weight = ops.stack(list(tops), axis=0)
        self.expert_choice = ops.stack(list(choices), axis=0)
        return _lm_head.causal_lm_logits(
            self.training, hidden, self.zaya.embed_tokens.weight)

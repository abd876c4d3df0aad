"""Mellum2: the `mellum` model type of JetBrains' Mellum2-12B-A2.5B
public config.json, whose keys `Mellum2Config` carries under their own
names. It is a reading of the decoder family `models/laguna.py` builds,
not a second family: `LagunaConfig` holds it whole, with

* the same query heads in every layer (`num_attention_heads` on
  `num_key_value_heads`, no per-layer list in the config.json);
* every `rope_parameters` entry over the whole head (no
  `partial_rotary_factor`): the default rule in a `sliding_attention`
  layer, YaRN with its `attention_factor` on cos and sin in a
  `full_attention` one;
* a softmax router over all `num_experts` (`router_score="softmax"`),
  the `num_experts_per_tok` largest normalised to one
  (`norm_topk_prob: true`), no scaling factor (1), no shared expert
  (width 0), and `mlp_layer_types` all `sparse`: no dense layer.

So `Mellum2ForCausalLM` is `LagunaForCausalLM` built from that
translation (its parameters keep the family's names, `laguna.layers...`),
and everything the family has (the flash kernels by layer kind, the
rotary tables by layer kind, `nn.SparseExpertFFN` with its exchange
under a mesh plan, the recomputed-layer loop, the promise of logits) is
this model's too. What the config.json leaves to a convention
(benchmarks/configs/mellum2-12b-l4.json, `assumed`): no q/k norm, no
router bias, no auxiliary loss, gated (three-matrix) experts; the "MTP
head" of the model card has no key in the config.json and is not built.
"""
from __future__ import annotations

from dataclasses import dataclass, field, fields

from .laguna import LagunaConfig, LagunaForCausalLM


def _mellum2_rope():
    return {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000},
    }


@dataclass
class Mellum2Config:
    # the published config.json's keys, Mellum2-12B-A2.5B's values
    vocab_size: int = 98304
    hidden_size: int = 2304
    intermediate_size: int = 7168       # a `dense` layer's; none published
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 131072
    attention_bias: bool = False
    rms_norm_eps: float = 1e-6
    num_experts: int = 64
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    norm_topk_prob: bool = True
    tie_word_embeddings: bool = False
    sliding_window: int = 1024
    use_sliding_window: bool = True
    rope_parameters: dict = field(default_factory=_mellum2_rope)
    layer_types: list = None            # None -> three sliding, one full
    mlp_layer_types: list = None        # None -> all sparse
    # what the config.json leaves to the model type's code
    initializer_range: float = 0.02
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False
    recompute_interval: int = 1

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.layer_types is None:
            self.layer_types = [
                "full_attention" if i % 4 == 3 else "sliding_attention"
                for i in range(n)]
        if self.mlp_layer_types is None:
            self.mlp_layer_types = ["sparse"] * n
        if (self.hidden_act != "silu" or not self.norm_topk_prob
                or not self.use_sliding_window):
            raise NotImplementedError(
                "Mellum2Config: silu, weights normalised over the chosen "
                "and windowed sliding layers only")

    @classmethod
    def from_dict(cls, d: dict, **kw):
        """From a config.json's dict: the keys this class has, the rest
        left where they are."""
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known}, **kw)

    def laguna(self) -> LagunaConfig:
        """This configuration as the decoder family's."""
        same = {f.name for f in fields(LagunaConfig)} \
            & {f.name for f in fields(self)}
        return LagunaConfig(
            **{k: getattr(self, k) for k in same},
            num_attention_heads_per_layer=[self.num_attention_heads]
            * self.num_hidden_layers,
            shared_expert_intermediate_size=0,
            moe_routed_scaling_factor=1.0, router_score="softmax")


def mellum2_tiny(**kw):
    return Mellum2Config(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, sliding_window=8, **kw)


class Mellum2ForCausalLM(LagunaForCausalLM):
    """`LagunaForCausalLM` at a `Mellum2Config`'s translation."""

    def __init__(self, config: Mellum2Config):
        super().__init__(config.laguna())
        self.mellum2_config = config

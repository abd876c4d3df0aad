"""DeepSeek-V2: multi-head latent attention and fine-grained experts
(the `deepseek_v2` model type of DeepSeek-AI's DeepSeek-V2-Lite public
config.json, whose keys `DeepseekV2Config` carries under their own
names; arXiv:2405.04434).

Every layer is pre-norm, `x += attn(norm(x)); x += ffn(norm(x))`,
RMSNorm, no bias; a final norm and an untied head.

* attention: `nn.MultiHeadLatentAttention`: a `kv_lora_rank`-wide latent
  a token from which `num_attention_heads` keys of `qk_nope_head_dim`
  and values of `v_head_dim` are projected up, beside ONE rotary key
  head of `qk_rope_head_dim` that every query head reads. One rotary
  table over `qk_rope_head_dim`, YaRN's frequencies by `rope_scaling`
  (its own factor on cos and sin is mscale(factor, mscale) /
  mscale(factor, mscale_all_dim)); the scores are scaled by
  (nope + rope)^-1/2 * mscale(factor, mscale_all_dim)^2, with
  mscale(s, m) = 0.1 m ln s + 1.
* feed-forward: the first `first_k_dense_replace` layers SwiGLU of
  `intermediate_size`; the rest `nn.SparseExpertFFN`: softmax over
  `n_routed_experts`, the `num_experts_per_tok` largest chosen
  (`topk_method: greedy`), weights the softmax's own values times
  `routed_scaling_factor`, NOT divided by their sum (`norm_topk_prob:
  false`); experts of `moe_intermediate_size`; `n_shared_experts` shared
  experts as one SwiGLU of n_shared_experts * moe_intermediate_size.
* loss (`DeepseekV2PretrainingCriterion`): next-token cross-entropy plus
  `aux_loss_alpha` times the sparse layers' mean sequence-wise balance
  term (`seq_aux`; `ops.moe_sequence_balance`).

What this program does not run is refused by name: a query latent
(`q_lora_rank`), group-limited routing (`n_group` > 1 or a
`topk_method` other than greedy), a `scoring_func` other than softmax,
`moe_layer_freq` other than 1, bias, a tied head.

`experts_held = (first, count)`: the expert-parallel share
(nn/layers/moe.py). `DeepseekV2ForCausalLM.forward` returns the logits
(a promise in a traced training forward, as the other families'); that
forward's per-layer counts of assignments to held experts are
`model.expert_counts` ([sparse layers, count] int32) and its balance
terms `model.balance_terms` ([sparse layers] float32).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field, fields

from .. import ops
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.mla import MultiHeadLatentAttention
from ..nn.layers.moe import SparseExpertFFN, SwiGLU
from ..nn.layers.norm import RMSNorm
from ..nn.layers.rope import rope_tables
from . import lm_head as _lm_head
from .gpt import GPTPretrainingCriterion


def _lite_rope():
    return {"type": "yarn", "factor": 40, "beta_fast": 32, "beta_slow": 1,
            "mscale": 0.707, "mscale_all_dim": 0.707,
            "original_max_position_embeddings": 4096}


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


@dataclass
class DeepseekV2Config:
    # the published config.json's keys, DeepSeek-V2-Lite's values
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    n_shared_experts: int = 2
    n_routed_experts: int = 64
    routed_scaling_factor: float = 1.0
    kv_lora_rank: int = 512
    q_lora_rank: int = None
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    topk_method: str = "greedy"
    n_group: int = 1
    topk_group: int = 1
    num_experts_per_tok: int = 6
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = False
    scoring_func: str = "softmax"
    aux_loss_alpha: float = 0.001
    seq_aux: bool = True
    hidden_act: str = "silu"
    max_position_embeddings: int = 163840
    initializer_range: float = 0.02
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: dict = field(default_factory=_lite_rope)
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    # a seeded model's draw of the matrices that write to the residual
    # stream (None: initializer_range)
    out_std: float = None
    # the expert-parallel share: (first, count) of n_routed_experts
    experts_held: tuple = None
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False         # jax.checkpoint around a layer
    recompute_interval: int = 1     # ... whose index % interval == 0

    def __post_init__(self):
        cannot = [why for bad, why in (
            (self.q_lora_rank is not None,
             f"q_lora_rank {self.q_lora_rank} (a query latent)"),
            (self.n_group > 1 or self.topk_group > 1
             or self.topk_method != "greedy",
             f"topk_method {self.topk_method!r} over n_group "
             f"{self.n_group} (group-limited routing)"),
            (self.scoring_func != "softmax",
             f"scoring_func {self.scoring_func!r}"),
            (self.moe_layer_freq != 1,
             f"moe_layer_freq {self.moe_layer_freq}"),
            (self.num_key_value_heads != self.num_attention_heads,
             f"num_key_value_heads {self.num_key_value_heads} for "
             f"{self.num_attention_heads} heads"),
            (self.hidden_act != "silu", f"hidden_act {self.hidden_act!r}"),
            (not self.seq_aux, "seq_aux false (the token-wise balance loss)"),
            (self.attention_bias, "attention_bias"),
            (self.tie_word_embeddings, "tie_word_embeddings")) if bad]
        if cannot:
            raise NotImplementedError(
                "DeepseekV2Config: this program does not run "
                + "; ".join(cannot))
        scaling = self.rope_scaling
        if scaling is not None and scaling.get("type") != "yarn":
            raise NotImplementedError(
                f"DeepseekV2Config: rope_scaling type {scaling.get('type')!r}")
        if self.experts_held is None:
            self.experts_held = (0, self.n_routed_experts)
        if self.recompute_interval < 1:
            raise ValueError("recompute_interval must be >= 1")

    @classmethod
    def from_dict(cls, d: dict, **kw):
        """From a config.json's dict: the keys this class has, the rest
        left where they are. A benchmark configuration's cut
        (`n_routed_experts` the experts held, the published count under
        `published`) becomes `experts_held` of the published count, from
        `expert_first` (0) on; its `seeded_draws.residual_output` is
        `out_std`."""
        known = {f.name for f in fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        published = d.get("published", {}).get("n_routed_experts")
        if published is not None and "experts_held" not in kw:
            kept["experts_held"] = (d.get("expert_first", 0),
                                    d["n_routed_experts"])
            kept["n_routed_experts"] = published
        out = d.get("seeded_draws", {}).get("residual_output")
        if out is not None:
            kept.setdefault("out_std", out)
        return cls(**kept, **kw)

    def is_sparse(self, index: int) -> bool:
        return index >= self.first_k_dense_replace

    @property
    def softmax_scale(self) -> float:
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        if self.rope_scaling is None:
            return scale
        return scale * yarn_mscale(
            self.rope_scaling["factor"],
            self.rope_scaling.get("mscale_all_dim", 0)) ** 2

    def rope_table(self, seq: int):
        """(cos, sin) [seq, qk_rope_head_dim] float32."""
        s = self.rope_scaling
        if s is None:
            return rope_tables(seq, self.qk_rope_head_dim,
                               rope_theta=self.rope_theta)
        return rope_tables(
            seq, self.qk_rope_head_dim, rope_theta=self.rope_theta,
            rope_type="yarn", factor=s["factor"],
            original_max_position_embeddings=s[
                "original_max_position_embeddings"],
            beta_fast=s.get("beta_fast", 32), beta_slow=s.get("beta_slow", 1),
            attention_factor=yarn_mscale(s["factor"], s.get("mscale", 1))
            / yarn_mscale(s["factor"], s.get("mscale_all_dim", 0)))


def deepseek_v2_tiny(**kw):
    kw = {"experts_held": None, **kw}
    return DeepseekV2Config(**{**dict(
        vocab_size=512, hidden_size=64, intermediate_size=160,
        moe_intermediate_size=32, num_hidden_layers=3,
        num_attention_heads=4, num_key_value_heads=4, n_routed_experts=16,
        num_experts_per_tok=4, kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16, max_position_embeddings=256,
        rope_scaling={**_lite_rope(),
                      "original_max_position_embeddings": 32}), **kw})


class DeepseekV2DecoderLayer(Layer):
    """One layer: `attn` (latent attention), then `mlp` (dense) or `moe`
    (sparse). forward returns (x, counts, balance term): the assignments
    each held expert got and the layer's balance term, or None twice
    from a dense layer."""

    def __init__(self, config: DeepseekV2Config, index: int):
        super().__init__()
        h, std = config.hidden_size, config.initializer_range
        self.input_layernorm = RMSNorm(h, epsilon=config.rms_norm_eps)
        self.attn = MultiHeadLatentAttention(
            h, config.num_attention_heads, config.qk_nope_head_dim,
            config.qk_rope_head_dim, config.v_head_dim, config.kv_lora_rank,
            config.rms_norm_eps, config.softmax_scale, std, config.out_std,
            config.use_flash_attention)
        self.post_attention_layernorm = RMSNorm(
            h, epsilon=config.rms_norm_eps)
        if config.is_sparse(index):
            self.moe = SparseExpertFFN(
                h, config.moe_intermediate_size,
                num_experts=config.n_routed_experts,
                top_k=config.num_experts_per_tok,
                held=tuple(config.experts_held),
                shared_width=config.n_shared_experts
                * config.moe_intermediate_size,
                routed_scale=config.routed_scaling_factor, std=std,
                router_score="softmax",
                router_normalize=config.norm_topk_prob,
                aux="sequence_balance")
        else:
            self.mlp = SwiGLU(h, config.intermediate_size, std,
                              config.out_std)

    def forward(self, x, cos, sin):
        x = x + self.attn(self.input_layernorm(x), cos, sin)
        u = self.post_attention_layernorm(x)
        if hasattr(self, "moe"):
            y, counts, balance = self.moe(u)
            return x + y, counts, balance
        return x + self.mlp(u), None, None


class DeepseekV2Model(Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [DeepseekV2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        """-> (hidden, counts and balance terms of every sparse layer)."""
        cfg = self.config
        x = self.embed_tokens(input_ids)
        cos, sin = cfg.rope_table(input_ids.shape[1])
        from ..distributed.meta_parallel.recompute import layer_calls
        counts, balance = [], []
        # recomputed, a layer keeps its flash outputs
        for call in layer_calls(self.layers, cfg.recompute and self.training,
                                cfg.recompute_interval):
            x, c, term = call(x, cos, sin)
            if c is not None:
                counts.append(c)
                balance.append(term)
        return self.norm(x), counts, balance


class DeepseekV2ForCausalLM(Layer):
    def __init__(self, config: DeepseekV2Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV2Model(config)
        self.lm_head = Linear(
            config.hidden_size, config.vocab_size, bias_attr=False,
            weight_attr=Normal(std=config.initializer_range))
        self.expert_counts = self.balance_terms = None

    def lm_logits(self, hidden):
        return _lm_head.lm_logits(
            hidden, self.model.embed_tokens.weight, self.lm_head)

    def forward(self, input_ids):
        hidden, counts, balance = self.model(input_ids)
        self.expert_counts = ops.stack(counts, axis=0) if counts else None
        self.balance_terms = ops.stack(balance, axis=0) if balance else None
        return _lm_head.causal_lm_logits(
            self.training, hidden, self.model.embed_tokens.weight,
            self.lm_head)


class DeepseekV2PretrainingCriterion(Layer):
    """Next-token cross-entropy (`GPTPretrainingCriterion`) plus `alpha`
    times the mean of the sparse layers' balance terms. forward(logits,
    labels, balance_terms [sparse layers] or None) -> (loss, the mean
    balance term, float32: 1 where the load is even; 0 with no sparse
    layer)."""

    def __init__(self, alpha: float = 0.001):
        super().__init__()
        self.alpha = alpha
        self.cross_entropy = GPTPretrainingCriterion()

    def forward(self, logits, labels, balance_terms=None):
        loss = self.cross_entropy(logits, labels)
        if balance_terms is None:
            return loss, ops.zeros([], "float32")
        balance = ops.mean(balance_terms)
        return loss + self.alpha * balance, balance

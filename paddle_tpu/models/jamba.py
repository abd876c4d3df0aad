"""Jamba: a hybrid of Mamba-1 state-space layers and attention layers
(Lieber et al. 2024, "Jamba: A Hybrid Transformer-Mamba Language Model";
the `jamba` model type of AI21-Jamba2-3B's public config.json, whose keys
`JambaConfig` carries under their own names).

Every layer is a mixer and a feed-forward, each behind an RMSNorm and
added to the residual stream:

    h = x + Mixer_i(RMSNorm(x));   out = h + MLP(RMSNorm(h))

Mixer_i is attention where `i % attn_layer_period == attn_layer_offset`
and Mamba elsewhere. Attention carries no positional signal (the Mamba
layers carry order) and may share one key/value head among all query
heads. The Mamba mixer is Mamba-1 (Gu & Dao 2023) with Jamba's addition
of an RMSNorm on each of dt, B and C:

    [xs, z] = u W_in;  xc = silu(conv(xs))      causal depthwise, d_conv taps
    [r, B, C] = xc W_x;  r, B, C <- RMSNorm each
    delta = softplus(r W_dt + b_dt);  A = -exp(A_log)
    h_t = exp(delta_t A) h_{t-1} + (delta_t xc_t) (x) B_t;  y_t = h_t C_t + D xc_t
    out = (y * silu(z)) W_out

Under amp the projections are bf16 matmuls; `b_dt` is added and the
softplus taken in float32, and the recurrence runs in float32
(`ops.selective_scan` is on amp's black list). Only dense feed-forwards
are built: `num_experts` above 1 raises.
"""
from __future__ import annotations

from dataclasses import dataclass, fields

from .. import ops
from ..incubate.nn.functional import causal_attention
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.norm import RMSNorm
from ..observability import perf
from . import lm_head as _lm_head


@dataclass
class JambaConfig:
    # the published config.json's keys, AI21-Jamba2-3B's values
    vocab_size: int = 65536
    hidden_size: int = 2560
    intermediate_size: int = 8192
    num_hidden_layers: int = 28
    num_attention_heads: int = 20
    num_key_value_heads: int = 1
    attn_layer_period: int = 14
    attn_layer_offset: int = 7
    expert_layer_period: int = 2
    expert_layer_offset: int = 1
    num_experts: int = 1
    num_experts_per_tok: int = 1
    hidden_act: str = "silu"
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-6
    sliding_window: int = None
    tie_word_embeddings: bool = True
    # what the config.json leaves to the model type's code
    head_dim: int = 0               # 0 -> hidden_size // num_attention_heads
    initializer_range: float = 0.02
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False         # jax.checkpoint around a layer
    recompute_interval: int = 1     # ... whose index % interval == 0

    def __post_init__(self):
        if self.head_dim == 0:
            self.head_dim = self.hidden_size // self.num_attention_heads
        if self.num_experts != 1:
            raise NotImplementedError(
                "JambaConfig: sparse feed-forwards (num_experts "
                f"{self.num_experts}) are not built; only dense layers")
        if self.sliding_window is not None:
            raise NotImplementedError("JambaConfig: no window attention")
        if self.hidden_act != "silu" or self.mamba_proj_bias:
            raise NotImplementedError(
                "JambaConfig: hidden_act silu and no projection bias only")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.recompute_interval < 1:
            raise ValueError("recompute_interval must be >= 1")

    @classmethod
    def from_dict(cls, d: dict, **kw):
        """From a config.json's dict: the keys this class has, the rest
        (`model_type`, `use_mamba_kernels`, ...) left where they are."""
        known = {f.name for f in fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in known}, **kw)

    @property
    def mamba_inner(self):
        return self.mamba_expand * self.hidden_size

    def is_attention(self, i: int) -> bool:
        return i % self.attn_layer_period == self.attn_layer_offset


def jamba_tiny(**kw):
    return JambaConfig(vocab_size=512, hidden_size=64, intermediate_size=128,
                       num_hidden_layers=4, num_attention_heads=4,
                       num_key_value_heads=1, attn_layer_period=2,
                       attn_layer_offset=1, mamba_dt_rank=8, **kw)


def _linear(n_in, n_out, config):
    return Linear(n_in, n_out, bias_attr=False,
                  weight_attr=Normal(std=config.initializer_range))


class _CausalConv(Layer):
    """Causal depthwise convolution over time; weight [channels, taps]."""

    def __init__(self, channels, taps, config):
        super().__init__()
        self.weight = self.create_parameter(
            (channels, taps), attr=Normal(std=config.initializer_range))
        self.bias = self.create_parameter(
            (channels,), is_bias=True) if config.mamba_conv_bias else None

    def forward(self, x):
        return ops.causal_conv1d(x, self.weight, self.bias)


class _DeltaProj(Layer):
    """delta = softplus(r W + b): the matmul as amp has it, the bias and
    the softplus in float32 (delta is about 0.01; its bias about -4.6)."""

    def __init__(self, rank, channels, config):
        super().__init__()
        self.weight = self.create_parameter(
            (rank, channels), attr=Normal(std=config.initializer_range))
        self.bias = self.create_parameter((channels,), is_bias=True)

    def forward(self, r):
        dt = ops.cast(ops.linear(r, self.weight), "float32")
        return ops.softplus(dt + self.bias)


class JambaMambaMixer(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        h, e = config.hidden_size, config.mamba_inner
        n, r = config.mamba_d_state, config.mamba_dt_rank
        self.rank, self.state = r, n
        self.in_proj = _linear(h, 2 * e, config)
        self.conv1d = _CausalConv(e, config.mamba_d_conv, config)
        self.x_proj = _linear(e, r + 2 * n, config)
        self.dt_layernorm = RMSNorm(r, epsilon=config.rms_norm_eps)
        self.b_layernorm = RMSNorm(n, epsilon=config.rms_norm_eps)
        self.c_layernorm = RMSNorm(n, epsilon=config.rms_norm_eps)
        self.dt_proj = _DeltaProj(r, e, config)
        self.A_log = self.create_parameter((e, n), attr=Normal(std=1.0))
        self.D = self.create_parameter((e,), attr=Constant(1.0))
        self.out_proj = _linear(e, h, config)

    def forward(self, u):
        from ..kernels.pallas.selective_scan import scan_path
        xs, z = ops.split(self.in_proj(u), 2, axis=-1)
        xc = ops.silu(self.conv1d(xs))
        r, b, c = ops.split(self.x_proj(xc),
                            [self.rank, self.state, self.state], axis=-1)
        delta = self.dt_proj(self.dt_layernorm(r))
        perf.trace_note("ssm_scan", scan_path(*xc.shape[1:]))
        y = ops.selective_scan(
            xc, delta, -ops.exp(ops.cast(self.A_log, "float32")),
            self.b_layernorm(b), self.c_layernorm(c), self.D)
        return self.out_proj(y * ops.silu(z))


class JambaAttention(Layer):
    """Causal attention, no bias, no positions; `num_key_value_heads`
    key/value heads shared by groups of query heads."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        self.heads, self.kv_heads = (config.num_attention_heads,
                                     config.num_key_value_heads)
        self.head_dim = config.head_dim
        h, d = config.hidden_size, config.head_dim
        self.q_proj = _linear(h, self.heads * d, config)
        self.k_proj = _linear(h, self.kv_heads * d, config)
        self.v_proj = _linear(h, self.kv_heads * d, config)
        self.o_proj = _linear(self.heads * d, h, config)
        self.use_flash_attention = config.use_flash_attention

    def forward(self, u):
        b, s, _ = u.shape
        d = self.head_dim
        q = ops.reshape(self.q_proj(u), (b, s, self.heads, d))
        k = ops.reshape(self.k_proj(u), (b, s, self.kv_heads, d))
        v = ops.reshape(self.v_proj(u), (b, s, self.kv_heads, d))
        out = causal_attention(q, k, v, self.use_flash_attention)
        return self.o_proj(ops.reshape(out, (b, s, self.heads * d)))


class JambaMLP(Layer):
    """SwiGLU: down(silu(gate(x)) * up(x)), no bias."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        h, i = config.hidden_size, config.intermediate_size
        self.gate_proj = _linear(h, i, config)
        self.up_proj = _linear(h, i, config)
        self.down_proj = _linear(i, h, config)

    def forward(self, x):
        return self.down_proj(ops.silu(self.gate_proj(x)) * self.up_proj(x))


class JambaDecoderLayer(Layer):
    """One layer: the mixer is held as `attn` or as `mamba`, by kind."""

    def __init__(self, config: JambaConfig, index: int):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)
        if config.is_attention(index):
            self.attn = JambaAttention(config)
        else:
            self.mamba = JambaMambaMixer(config)
        self.pre_ff_layernorm = RMSNorm(config.hidden_size,
                                        epsilon=config.rms_norm_eps)
        self.mlp = JambaMLP(config)

    def forward(self, x):
        mixer = self.attn if hasattr(self, "attn") else self.mamba
        x = x + mixer(self.input_layernorm(x))
        return x + self.mlp(self.pre_ff_layernorm(x))


class JambaModel(Layer):
    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [JambaDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.final_layernorm = RMSNorm(config.hidden_size,
                                       epsilon=config.rms_norm_eps)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        cfg = self.config
        from ..distributed.meta_parallel.recompute import layer_calls
        for call in layer_calls(self.layers, cfg.recompute and self.training,
                                cfg.recompute_interval):
            x = call(x)
        return self.final_layernorm(x)


class JambaForCausalLM(Layer):
    """Jamba with its LM head, tied to the embedding or not. In a traced
    training forward the logits are a promise the pretraining criterion
    settles in token chunks, as `GPTForCausalLM`'s (models/lm_head.py)."""

    def __init__(self, config: JambaConfig):
        super().__init__()
        self.config = config
        self.jamba = JambaModel(config)
        self.lm_head = None if config.tie_word_embeddings else _linear(
            config.hidden_size, config.vocab_size, config)

    def lm_logits(self, hidden):
        return _lm_head.lm_logits(
            hidden, self.jamba.embed_tokens.weight, self.lm_head)

    def forward(self, input_ids):
        return _lm_head.causal_lm_logits(
            self.training, self.jamba(input_ids),
            self.jamba.embed_tokens.weight, self.lm_head)

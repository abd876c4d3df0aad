"""Ouro: a decoder whose whole stack of layers runs several times over
the same tokens with the same weights (the `ouro` model type of
ByteDance's Ouro-2.6B public config.json, whose keys `OuroConfig`
carries under their own names; "Scaling Latent Reasoning via Looped
Language Models", Zhu et al., 2025).

* a layer, sandwich norms (four RMSNorm weights), no bias:
  `a = x + N2(Attn(N1(x)))`, `y = a + N4(SwiGLU(N3(a)))`; attention is
  causal, `num_attention_heads` on `num_key_value_heads` of `head_dim`,
  rotate-half RoPE over the whole head at `rope_theta`;
* the model: `h_0 = E[ids]`; for t = 1 .. `total_ut_steps`:
  `h_t = N_f(Stack(h_{t-1}))`, the final norm inside the loop, so that
  pass t + 1 starts from the normed stream; `logits_t = h_t W_head`
  (untied); the exit gate's logit `g_t = w_g . h_t + b_g`,
  `lambda_t = sigmoid(g_t)`;
* the exit distribution a token: `p_1 = lambda_1`,
  `p_t = lambda_t prod_{j<t} (1 - lambda_j)`, and the last pass takes
  what is left, `p_T = prod_{j<T} (1 - lambda_j)`: it sums to one;
* the pre-training loss (`OuroPretrainingCriterion`): the mean over
  tokens of `sum_t p_t CE_t - beta H(p)`.

The loop is `recompute.scan_passes`: one `jax.lax.scan` over the passes
whose body holds each layer once and the final norm; its stacked
outputs are the T normed streams. There is no written-out form. Where
`recompute` is set a layer is recomputed, and keeps beside its input
the flash kernel's `o` and `lse` and `down_proj`'s output
(`OuroDecoderLayer.branch_outputs`): the norm inside the branch reads
that output in its backward, and the block would make the whole product
again for it. `o_proj`'s output is named the same way and not kept.

`OuroForCausalLM.forward` returns (logits, gate logits): the logits of
all passes [T, batch, seq, vocab] as ONE promise in a traced training
forward (`lm_head.deferred_logits` on the hidden of [T, batch, seq,
hidden]), which the criterion settles in one fused head call, and the
gate's logits [T, batch, seq] float32. Not here: the second training
stage (the gate alone) and early exit at inference
(`early_exit_threshold` 1.0: never).
"""
from __future__ import annotations

from dataclasses import dataclass, fields

from .. import ops
from ..amp import auto_cast
from ..core.tensor import DeferredTensor
from ..distributed.meta_parallel.recompute import (
    ATTN_OUT, MLP_OUT, branch_output, layer_calls, recompute, scan_passes)
from ..incubate.nn.functional import causal_attention
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer, traced_scope
from ..nn.layers.common import Embedding, Linear
from ..nn.layers.container import LayerList
from ..nn.layers.moe import SwiGLU
from ..nn.layers.norm import RMSNorm
from ..nn.layers.rope import rope_tables
from ..observability import perf
from . import lm_head as _lm_head


@dataclass
class OuroConfig:
    # the published config.json's keys, Ouro-2.6B's values
    vocab_size: int = 49152
    hidden_size: int = 2048
    intermediate_size: int = 5632
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    hidden_act: str = "silu"
    max_position_embeddings: int = 65536
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    rope_scaling: dict = None
    sliding_window: int = None
    use_sliding_window: bool = False
    tie_word_embeddings: bool = False
    layer_types: tuple = None       # None -> every layer full_attention
    total_ut_steps: int = 4
    early_exit_threshold: float = 1.0
    # what the config.json leaves to the model type's code
    initializer_range: float = 0.02
    # the layer applications matrices that write to the stream are drawn
    # for (None: this model's layers x passes): a share of a deeper model
    # keeps the whole one's
    residual_depth: int = None
    # this program's choices
    use_flash_attention: bool = False
    recompute: bool = False         # jax.checkpoint around a layer
    recompute_interval: int = 1     # ... whose index % interval == 0

    def __post_init__(self):
        n = self.num_hidden_layers
        kinds = tuple(self.layer_types or ())[:n]
        if (self.rope_scaling is not None or self.use_sliding_window
                or self.sliding_window is not None
                or self.tie_word_embeddings or self.hidden_act != "silu"
                or any(k != "full_attention" for k in kinds)):
            raise NotImplementedError(
                "OuroConfig: the default rotary rule, full attention in "
                "every layer, an untied head and silu only")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("num_key_value_heads must divide "
                             "num_attention_heads")
        if self.total_ut_steps < 1 or self.recompute_interval < 1:
            raise ValueError("total_ut_steps and recompute_interval "
                             "must be >= 1")

    @property
    def out_std(self) -> float:
        depth = self.residual_depth or (self.num_hidden_layers
                                        * self.total_ut_steps)
        return self.initializer_range / (2 * depth) ** 0.5

    @classmethod
    def from_dict(cls, d: dict, **kw):
        """From a config.json's dict: the keys this class has, the rest
        left where they are. A benchmark configuration's cut in depth
        (`num_hidden_layers` the layers here, the published count under
        `published`) sets `residual_depth` to the published layer
        applications; a `layer_types` longer than the depth is read as
        far as the depth goes."""
        known = {f.name for f in fields(cls)}
        kept = {k: v for k, v in d.items() if k in known}
        published = d.get("published", {}).get("num_hidden_layers")
        if published is not None:
            kept.setdefault(
                "residual_depth",
                published * kept.get("total_ut_steps", cls.total_ut_steps))
        return cls(**kept, **kw)


def ouro_tiny(**kw):
    return OuroConfig(**{**dict(
        vocab_size=512, hidden_size=64, intermediate_size=128,
        num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=4,
        head_dim=16, max_position_embeddings=256, total_ut_steps=4), **kw})


def _linear(n_in, n_out, std):
    return Linear(n_in, n_out, bias_attr=False, weight_attr=Normal(std=std))


class OuroAttention(Layer):
    """Causal attention, rotate-half RoPE over the whole head (the
    tables are handed in), no bias."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        self.heads, self.kv_heads = (config.num_attention_heads,
                                     config.num_key_value_heads)
        self.head_dim = d = config.head_dim
        h, std = config.hidden_size, config.initializer_range
        self.q_proj = _linear(h, self.heads * d, std)
        self.k_proj = _linear(h, self.kv_heads * d, std)
        self.v_proj = _linear(h, self.kv_heads * d, std)
        self.o_proj = _linear(self.heads * d, h, config.out_std)
        self.use_flash_attention = config.use_flash_attention

    def forward(self, u, cos, sin):
        b, s, _ = u.shape
        H, Hk, d = self.heads, self.kv_heads, self.head_dim
        q = ops.reshape(self.q_proj(u), (b, s, H, d))
        k = ops.reshape(self.k_proj(u), (b, s, Hk, d))
        v = ops.reshape(self.v_proj(u), (b, s, Hk, d))
        with traced_scope("rope"):
            q = ops.rope_rotate_half(q, cos, sin)
            k = ops.rope_rotate_half(k, cos, sin)
        out = causal_attention(q, k, v, self.use_flash_attention)
        return self.o_proj(ops.reshape(out, (b, s, H * d)))


class OuroDecoderLayer(Layer):
    """The sandwich: a norm before and a norm after each sublayer, the
    second inside the residual branch. That norm's backward reads its
    input, the branch's last product: the layer names both
    (`branch_output`), and a recomputed layer keeps `down_proj`'s, which
    buys three times what `o_proj`'s does for the same bytes (32 ms a GB
    against 11, where the stack itself costs 15: `recompute.py`'s
    table); with both the cell's step also reads over the fit guard
    (`tests/test_tpu_aot_compile.py`)."""

    branch_outputs = (MLP_OUT,)

    def __init__(self, config: OuroConfig):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = RMSNorm(h, epsilon=eps)
        self.attn = OuroAttention(config)
        self.input_layernorm_2 = RMSNorm(h, epsilon=eps)
        self.post_attention_layernorm = RMSNorm(h, epsilon=eps)
        self.mlp = SwiGLU(h, config.intermediate_size,
                          config.initializer_range, config.out_std)
        self.post_attention_layernorm_2 = RMSNorm(h, epsilon=eps)

    def forward(self, x, cos, sin):
        x = x + self.input_layernorm_2(branch_output(
            self.attn(self.input_layernorm(x), cos, sin), ATTN_OUT))
        return x + self.post_attention_layernorm_2(branch_output(
            self.mlp(self.post_attention_layernorm(x)), MLP_OUT))


class OuroModel(Layer):
    """The embedding, then the stack and the final norm
    `total_ut_steps` times over. forward returns the T normed streams
    [T, batch, seq, hidden]."""

    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = Embedding(
            config.vocab_size, config.hidden_size,
            weight_attr=Normal(std=config.initializer_range))
        self.layers = LayerList(
            [OuroDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, epsilon=config.rms_norm_eps)

    def one_pass(self, x, cos, sin):
        """The stack once and the final norm: the loop's body."""
        cfg = self.config
        remat = cfg.recompute and self.training
        for call in layer_calls(self.layers, remat, cfg.recompute_interval):
            x = call(x, cos, sin)
        if remat:
            # the norm's input alone is kept a pass, not its statistics
            # and its normalised stream as well
            return recompute(self.norm, x)
        return self.norm(x)

    def forward(self, input_ids):
        cfg = self.config
        x = self.embed_tokens(input_ids)
        cos, sin = rope_tables(input_ids.shape[1], cfg.head_dim,
                               rope_theta=cfg.rope_theta)
        perf.trace_note("ut_loop", f"scan, {cfg.total_ut_steps} x "
                        f"{cfg.num_hidden_layers} layers")
        with traced_scope("ut_loop"):
            return scan_passes(
                self.one_pass, cfg.total_ut_steps, x, cos, sin,
                parameters=self.layers.parameters() + self.norm.parameters())


class OuroForCausalLM(Layer):
    def __init__(self, config: OuroConfig):
        super().__init__()
        self.config = config
        self.model = OuroModel(config)
        self.lm_head = _linear(config.hidden_size, config.vocab_size,
                               config.initializer_range)
        self.exit_gate = Linear(
            config.hidden_size, 1,
            weight_attr=Normal(std=config.initializer_range),
            bias_attr=Constant(0.0))

    def lm_logits(self, hidden):
        return _lm_head.lm_logits(
            hidden, self.model.embed_tokens.weight, self.lm_head)

    def forward(self, input_ids):
        """-> (logits [T, batch, seq, vocab], a promise in a traced
        training forward; the gate's logits [T, batch, seq] float32)."""
        hidden = self.model(input_ids)
        # the gate reads the stream in float32, whatever amp does to the
        # head: lambda decides every pass's weight in the loss
        with auto_cast(enable=False):
            gate = ops.squeeze(self.exit_gate(hidden), axis=-1)
        return _lm_head.causal_lm_logits(
            self.training, hidden, self.model.embed_tokens.weight,
            self.lm_head), gate


def exit_distribution(gate_logits):
    """[T, ...] gate logits -> (p, log p), each [T, ...] float32: the
    probability of leaving after pass t, the last pass taking what is
    left. From log sigmoid(g) and log sigmoid(-g) = log(1 - lambda), so
    that no p_t is a product of rounded factors and log p is exact where
    p is tiny."""
    g = ops.cast(gate_logits, "float32")
    stay = ops.logsigmoid(-g)                       # log(1 - lambda_t)
    before = ops.cumsum(stay, axis=0) - stay        # sum over j < t
    log_p = ops.concat([(ops.logsigmoid(g) + before)[:-1], before[-1:]],
                       axis=0)
    return ops.exp(log_p), log_p


class OuroPretrainingCriterion(Layer):
    """The expected next-token cross-entropy over the exit distribution
    less `beta` times its entropy, the mean over tokens:
    `mean_i (sum_t p_ti CE_ti - beta H(p_i))`. forward takes the model's
    (logits, gate logits) and the labels [batch, seq], and returns
    (loss, aux): aux [2, T] float32, the mean over the tokens of CE_t and
    of p_t, for a step to hand out (`TrainStep(has_aux=True)`).

    Where the logits are a promise (`lm_head.deferred_logits`), all T x n
    rows go through ONE `lm_head.head_cross_entropy` with
    `token_weight = p_t / n`: the gate's gradient from the first term is
    that op's gradient to its weights."""

    def __init__(self, beta: float = 0.05):
        super().__init__()
        self.beta = beta

    def forward(self, outputs, labels):
        logits, gate_logits = outputs
        T = gate_logits.shape[0]
        n = 1
        for size in gate_logits.shape[1:]:
            n *= size
        with traced_scope("exit_loss"):
            p, log_p = exit_distribution(
                ops.reshape(gate_logits, (T, n)))               # [T, n]
            rows = ops.tile(ops.reshape(labels, (1, n)), (T, 1))
        if (isinstance(logits, DeferredTensor) and not logits.computed
                and isinstance(logits.producer, _lm_head._Head)):
            expected, ce = _lm_head.head_cross_entropy(
                logits.producer, rows, p / n, with_rows=True)
            ce = ops.reshape(ce, (T, n))
        else:
            ce = ops.reshape(ops.cross_entropy(
                ops.reshape(logits, (T * n, logits.shape[-1])),
                ops.reshape(rows, (T * n,)), reduction="none"), (T, n))
            expected = ops.sum(p * ce) / n
        with traced_scope("exit_loss"):
            entropy = -ops.sum(p * log_p) / n
            aux = ops.stack([ops.mean(ce, axis=1), ops.mean(p, axis=1)],
                            axis=0)
            return expected - self.beta * entropy, aux

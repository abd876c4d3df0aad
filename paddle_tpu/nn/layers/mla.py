"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434, section
2.1): keys and values are projected up from one compressed vector a
token, and a key has two parts."""
from __future__ import annotations

from ... import ops
from ...incubate.nn.functional import causal_attention
from ..initializer import Normal
from ..layer import Layer, traced_scope
from .common import Linear
from .norm import RMSNorm


class MultiHeadLatentAttention(Layer):
    """For a token's (normed) input u [hidden]:

        q = u W_q                    [heads, nope + rope]
        (c, k') = u W_kva            [rank + rope]: the latent, and ONE
                                     rotary key head that all heads read
        (k, v) = RMSNorm(c) W_kvb    [heads, nope + value]
        score_j = (q_j[:nope] . k_j + rot(q_j[nope:]) . rot(k')) * scale,
        causal, softmax in float32, o_j = sum p v_j, y = o W_o.

    Rotate-half RoPE turns the `rope` dimensions alone, by the tables
    handed in (`nn.rope_tables`). `scale`: None is 1 / sqrt(nope + rope);
    YaRN's `mscale` squared rides on it (`models/deepseek_v2.py`). No
    query latent (`q_lora_rank: null`, DeepSeek-V2-Lite's). No bias.

    With `use_flash_attention` the core is `causal_attention(...,
    shared=)`: the flash kernels take the key in its two parts, the
    shared head never copied to the heads. The down-projection, its
    norm, the up-projection and the splits run under the scope
    `mla_latent`, the rotary under `rope`."""

    def __init__(self, hidden, num_heads, nope_dim=128, rope_dim=64,
                 value_dim=128, latent_rank=512, epsilon=1e-6, scale=None,
                 std=0.02, out_std=None, use_flash_attention=False):
        super().__init__()
        self.heads = num_heads
        self.nope, self.rope, self.value = nope_dim, rope_dim, value_dim
        self.rank, self.scale = latent_rank, scale
        attr = Normal(std=std)

        def linear(n_in, n_out, attr=attr):
            return Linear(n_in, n_out, bias_attr=False, weight_attr=attr)

        self.q_proj = linear(hidden, num_heads * (nope_dim + rope_dim))
        self.kv_a_proj_with_mqa = linear(hidden, latent_rank + rope_dim)
        self.kv_a_layernorm = RMSNorm(latent_rank, epsilon=epsilon)
        self.kv_b_proj = linear(latent_rank,
                                num_heads * (nope_dim + value_dim))
        self.o_proj = linear(num_heads * value_dim, hidden,
                             attr if out_std is None else Normal(std=out_std))
        self.use_flash_attention = use_flash_attention

    def forward(self, u, cos, sin):
        b, s, _ = u.shape
        H, dn, dr, dv = self.heads, self.nope, self.rope, self.value
        with traced_scope("mla_latent"):
            q = ops.reshape(self.q_proj(u), (b, s, H, dn + dr))
            q, q_pe = q[..., :dn], q[..., dn:]
            latent = self.kv_a_proj_with_mqa(u)
            k_pe = ops.reshape(latent[..., self.rank:], (b, s, 1, dr))
            kv = ops.reshape(
                self.kv_b_proj(self.kv_a_layernorm(latent[..., :self.rank])),
                (b, s, H, dn + dv))
            k, v = kv[..., :dn], kv[..., dn:]
        with traced_scope("rope"):
            q_pe = ops.rope_rotate_half(q_pe, cos, sin)
            k_pe = ops.rope_rotate_half(k_pe, cos, sin)
        out = causal_attention(q, k, v, self.use_flash_attention,
                               shared=(q_pe, k_pe), scale=self.scale)
        return self.o_proj(ops.reshape(out, (b, s, H * dv)))

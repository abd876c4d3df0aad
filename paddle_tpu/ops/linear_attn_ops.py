"""Linear-attention ops: the gated delta rule (Yang, Kautz &
Hatamizadeh 2024), a matrix-valued state a head updated by a rank-one
correction a token."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .registry import register_op
from .ssm_ops import causal_conv1d

__all__ = ["gated_delta_rule", "gdn_operands", "gdn_operands_path"]


@register_op("gated_delta_rule", amp_policy="black")
def gated_delta_rule(q, k, v, g, beta, heads_first=False):
    """S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    from S_0 = 0, o_t = S_t^T q_t, a head. q, k [b, s, Hk, dk]; v
    [b, s, H, dv]; g (the log of the decay, <= 0) and beta [b, s, H] ->
    o [b, s, H, dv] in v's type, the state and every product in float32.
    q and k come as the recurrence takes them: the caller has scaled
    them (a unit k keeps the correction a contraction). They come a
    value head (Hk = H) or a key head, Hk dividing H: key head h serves
    value heads h H / Hk and on; the kernels read a key head's q and k
    where they lie for each of its value heads and sum its dq and dk.
    `heads_first`: q, k [b, Hk, sp, dk] and v [b, H, sp, dv] as
    `gdn_operands` makes them, sp whole chunks with zero rows beyond s;
    the kernels read them as they lie.

    Computed in chunks of 64 tokens (kernels/pallas/gated_delta.py:
    `CHUNK`; a row that is no whole number of them is padded with tokens
    the state passes through): what depends on no state (the
    chunk's triangular inverse and its masked products) for all chunks
    at once, and the pass that carries the state: both as Pallas kernels
    on a TPU, in XLA and as a `lax.scan` elsewhere. One `jax.custom_vjp` with
    gradients to all five. Under amp the operands are cast to float32
    (black list): the state is a sum over thousands of tokens."""
    from ..kernels.pallas import gated_delta
    rule = gated_delta.gated_delta_rule_heads_first if heads_first \
        else gated_delta.gated_delta_rule
    return rule(q, k, v, g, beta)


def gdn_operands_path(qkvz_shape, qkvz_dtype, taps, key_heads, value_heads):
    """What makes a Gated DeltaNet's operands in a program traced now:
    `pallas` (`gdn_operands_fwd`, `gdn_operands_bwd`) wherever the chunk
    preparation runs on its kernels and they take the shape, else `xla`.
    The CPU tests also give `interpret`, by `gated_delta.prepare_path`."""
    from ..kernels.pallas import gated_delta, gdn_operands as kernels
    mode = gated_delta.prepare_path()
    if mode != "xla" and kernels.reject_reason(
            qkvz_shape, qkvz_dtype, taps, key_heads, value_heads):
        return "xla"
    return mode


def _operands_in_xla(qkvz, conv_weight, Hk, Hv):
    """`gdn_operands`' q, k and v as a chain of XLA's: the key heads' q,
    k and v gathered into q | k | v each by head (`conv_weight`'s rows),
    `causal_conv1d`, silu in qkvz's type, the unit norms in float32, and
    the turn to heads first."""
    from ..kernels.pallas.gdn_operands import padded, unit
    b, s, width = qkvz.shape
    rep, d = Hv // Hk, width // (2 * Hk + 2 * Hv)
    q, k, v, _z = jnp.split(qkvz.reshape(b, s, Hk, -1),
                            [d, 2 * d, (2 + rep) * d], axis=-1)
    mixed = jnp.concatenate([x.reshape(b, s, -1) for x in (q, k, v)],
                            axis=-1)
    mixed = jax.nn.silu(causal_conv1d.raw_fn(mixed, conv_weight))
    q, k, v = (x.reshape(b, s, -1, d).astype(jnp.float32) for x in
               jnp.split(mixed, [Hk * d, 2 * Hk * d], axis=-1))

    def heads_first(x):
        return jnp.pad(jnp.moveaxis(x, 2, 1),
                       ((0, 0), (0, 0), (0, padded(s) - s), (0, 0)))

    return (heads_first(unit(q, d ** -0.5)), heads_first(unit(k, 1.0)),
            heads_first(v))


@register_op("gdn_operands", amp_policy="keep")
def gdn_operands(qkvz, conv_weight, key_heads, value_heads):
    """What a Gated DeltaNet hands `gated_delta_rule(heads_first=True)`,
    from `in_proj_qkvz`'s output [b, s, Hk (2 + 2 rep) d] as it is laid
    out, a key head's q d | k d | its rep = Hv / Hk value heads' v | z,
    and `conv_weight` [2 Hk d + Hv d, taps] (rows q | k | v, each by
    head). q | k | v go through the causal depthwise convolution (float32
    sums, zeros before t = 0) and silu; q and k are scaled to unit
    length over d, q also by d^-0.5. -> q, k [b, Hk, sp, d] and v
    [b, Hv, sp, d], float32 and heads first, sp = s up to whole chunks
    of the rule and the rows beyond s zeros; z [b, s, Hv, d] as it lies.

    On a TPU backend one Pallas kernel each way
    (kernels/pallas/gdn_operands.py): a key head's lanes are read where
    the projection wrote them and everything between is float32 in
    VMEM.
    Elsewhere the chain of XLA ops it replaced (`causal_conv1d`, silu
    in qkvz's type, the norms, the turn). Which: `gdn_operands_path`."""
    from ..kernels.pallas import gdn_operands as kernels
    path = gdn_operands_path(qkvz.shape, qkvz.dtype, conv_weight.shape[1],
                             key_heads, value_heads)
    if path == "xla":
        made = _operands_in_xla(qkvz, conv_weight, key_heads, value_heads)
    else:
        made = kernels.operands(
            qkvz, kernels.taps_by_head(conv_weight, key_heads, value_heads),
            key_heads, value_heads, path == "interpret")
    b, s, _ = qkvz.shape
    z = qkvz.reshape(b, s, key_heads, -1)[
        ..., conv_weight.shape[0] // key_heads:]
    return (*made, z.reshape(b, s, value_heads, -1))

"""Linear-attention ops: the gated delta rule (Yang, Kautz &
Hatamizadeh 2024), a matrix-valued state a head updated by a rank-one
correction a token."""
from __future__ import annotations

from .registry import register_op

__all__ = ["gated_delta_rule"]


@register_op("gated_delta_rule", amp_policy="black")
def gated_delta_rule(q, k, v, g, beta):
    """S_t = e^{g_t} S_{t-1} + beta_t k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T
    from S_0 = 0, o_t = S_t^T q_t, a head. q, k [b, s, H, dk]; v
    [b, s, H, dv]; g (the log of the decay, <= 0) and beta [b, s, H] ->
    o [b, s, H, dv] in v's type, the state and every product in float32.
    q and k come as the recurrence takes them: the caller has scaled
    them (a unit k keeps the correction a contraction).

    Computed in chunks of 64 tokens (kernels/pallas/gated_delta.py:
    `CHUNK`; a row that is no whole number of them is padded with tokens
    the state passes through): what depends on no state (the
    chunk's triangular inverse and its masked products) for all chunks
    at once, and the pass that carries the state: both as Pallas kernels
    on a TPU, in XLA and as a `lax.scan` elsewhere. One `jax.custom_vjp` with
    gradients to all five. Under amp the operands are cast to float32
    (black list): the state is a sum over thousands of tokens."""
    from ..kernels.pallas.gated_delta import gated_delta_rule as rule
    return rule(q, k, v, g, beta)

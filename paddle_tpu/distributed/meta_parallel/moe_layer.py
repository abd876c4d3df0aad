"""Expert-parallel MoE layer.

Reference: MoELayer + MoEScatter/MoEGather + gshard/switch gates
(/root/reference/python/paddle/incubate/distributed/models/moe/
moe_layer.py:263,99,149; gates in moe/gate/) and the global_scatter/
global_gather alltoall ops (SURVEY P9).

TPU rendering: the reference routes tokens with count-based alltoalls
(dynamic shapes). XLA wants static shapes, so this uses the GShard
capacity-factor dispatch: a dense [tokens, experts, capacity] one-hot
dispatch/combine einsum pair. Expert weights are stacked [E, ...] and
sharded over the expert axis; the dispatch einsum's contraction over
tokens->experts IS the all-to-all, inserted by GSPMD (SURVEY §7.1 "MoE
alltoall layer").

The capacity dispatch DROPS tokens: an assignment past an expert's
capacity (top_k * capacity_factor * tokens / experts) is left out of the
result, and the one-hot is quadratic in the experts (at 256 it is
neither a published model's mathematics nor runnable). The dropless
path, which computes every assignment whatever the imbalance and is told
which experts it holds, is `paddle_tpu.nn.SparseExpertFFN`
(nn/layers/moe.py, ops/moe_ops.py, kernels/pallas/grouped_matmul.py);
it runs one chip's share without its exchange. This file stays until an
expert-parallel mesh rule carries that layer's exchange (ROADMAP C12).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ... import ops
from ...core.tensor import Tensor
from ...nn.layer import Layer
from ...ops.registry import register_op
from ..topology import get_hybrid_communicate_group


@register_op("moe_gshard_dispatch")
def _moe_forward(x, gate_w, w1, b1, w2, b2, top_k=2, capacity_factor=1.5,
                 train=True):
    """[tokens, d] -> gshard top-k routing -> per-expert FFN -> combine.
    Returns (out, aux_loss)."""
    t, d = x.shape
    e = gate_w.shape[1]
    cap = int(np.ceil(top_k * capacity_factor * t / e))
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        gate_w.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)

    # top-k expert choice per token
    topv, topi = jax.lax.top_k(probs, top_k)          # [t, k]
    # position of each token within its expert's buffer
    onehot = jax.nn.one_hot(topi, e, dtype=jnp.int32)  # [t, k, e]
    flatoh = onehot.reshape(t * top_k, e)
    pos_in_expert = (jnp.cumsum(flatoh, axis=0) - 1).reshape(t, top_k, e)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)     # [t, k]
    keep = pos < cap                                    # capacity drop
    gates = topv * keep.astype(topv.dtype)
    denom = jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    gates = gates / denom

    # dense dispatch tensor [t, e, cap]
    disp = jnp.zeros((t, e, cap), x.dtype)
    comb = jnp.zeros((t, e, cap), jnp.float32)
    for k in range(top_k):  # static unroll over k (small)
        sel = jax.nn.one_hot(topi[:, k], e, dtype=x.dtype) * \
            keep[:, k:k + 1].astype(x.dtype)
        poh = jax.nn.one_hot(pos[:, k], cap, dtype=x.dtype)
        disp = disp + sel[:, :, None] * poh[:, None, :]
        comb = comb + (gates[:, k:k + 1] * sel.astype(jnp.float32)
                       )[:, :, None] * poh.astype(jnp.float32)[:, None, :]

    # route tokens to experts: [e, cap, d] (GSPMD all-to-all)
    expert_in = jnp.einsum("tec,td->ecd", disp, x)
    h = jnp.einsum("ecd,edf->ecf", expert_in, w1) + b1[:, None, :]
    h = jax.nn.gelu(h)
    expert_out = jnp.einsum("ecf,efd->ecd", h, w2) + b2[:, None, :]
    out = jnp.einsum("tec,ecd->td", comb.astype(x.dtype), expert_out)

    # gshard load-balance aux loss
    me = jnp.mean(probs, axis=0)                  # mean router prob
    ce = jnp.mean(jax.nn.one_hot(topi[:, 0], e, dtype=jnp.float32), axis=0)
    aux = jnp.sum(me * ce) * e
    return out, aux.astype(x.dtype)


class MoELayer(Layer):
    """GShard-style MoE FFN with expert-parallel placement.

    API shape follows the reference MoELayer (d_model, experts, gate,
    top_k); experts are homogeneous FFNs stacked on a leading expert dim
    sharded over the mp axis (expert parallelism rides the mesh)."""

    def __init__(self, d_model, d_hidden, num_experts, top_k=2,
                 capacity_factor=1.5, gate="gshard", group=None,
                 recompute_interval=0):
        super().__init__()
        self.d_model = d_model
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        self.gate_weight = self.create_parameter((d_model, num_experts))
        self.w1 = self.create_parameter((num_experts, d_model, d_hidden))
        self.b1 = self.create_parameter((num_experts, d_hidden),
                                        is_bias=True)
        self.w2 = self.create_parameter((num_experts, d_hidden, d_model))
        self.b2 = self.create_parameter((num_experts, d_model),
                                        is_bias=True)
        hcg = get_hybrid_communicate_group()
        if hcg is not None and hcg.get_model_parallel_world_size() > 1 \
                and num_experts % hcg.get_model_parallel_world_size() == 0:
            mesh = hcg.mesh
            for p, spec in ((self.w1, P("mp", None, None)),
                            (self.b1, P("mp", None)),
                            (self.w2, P("mp", None, None)),
                            (self.b2, P("mp", None))):
                p._data = jax.device_put(p._data,
                                         NamedSharding(mesh, spec))
                p._dist_attr = spec
        self.aux_loss = None

    def forward(self, x):
        shape = x.shape
        flat = ops.reshape(x, (-1, self.d_model))
        out, aux = _moe_forward(
            flat, self.gate_weight, self.w1, self.b1, self.w2, self.b2,
            top_k=self.top_k, capacity_factor=self.capacity_factor,
            train=self.training)
        self.aux_loss = aux
        return ops.reshape(out, shape)

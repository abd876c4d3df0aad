"""A sparse (mixture-of-experts) feed-forward that is told which experts
it holds, its three routers, and the load gauges of a step's counts."""
from __future__ import annotations

import numpy as np

from ... import ops
from ...observability import metrics as _metrics
from ...observability import perf
from ..initializer import Constant, Normal
from ..layer import Layer
from .common import Linear


class _Router(Layer):
    """scores -> the chosen experts and their weights (`ops.moe_route`)."""
    carries_state = False

    def __init__(self, hidden, num_experts, top_k, routed_scale, std,
                 score="sigmoid", normalize=True, with_scores=False):
        super().__init__()
        self.top_k, self.routed_scale = top_k, routed_scale
        self.score = score
        # what `ops.moe_route` is told beyond the older routers' call
        self.more = {} if normalize and not with_scores else {
            "normalize": normalize, "with_scores": with_scores}
        self.weight = self.create_parameter((hidden, num_experts),
                                            attr=Normal(std=std))

    def forward(self, x):
        return ops.moe_route(x, self.weight, self.top_k, self.routed_scale,
                             self.score, **self.more)


class _MLPRouter(Layer):
    """A down-projection whose output also runs from layer to layer
    (depth averaging), an MLP, softmax, one expert a token
    (`ops.moe_route_mlp`). forward(x, state) -> (weights, experts, the
    state to hand to the next layer's router)."""
    carries_state = True
    top_k = 1

    def __init__(self, hidden, num_experts, width, draws):
        super().__init__()
        def make(shape, std):
            return self.create_parameter(shape, attr=Normal(std=std))
        down, inner, out = draws
        self.down_proj = make((hidden, width), down)
        self.eda_scale = self.create_parameter(
            (1,), default_initializer=Constant(1.0))
        self.fc1 = make((width, width), inner)
        self.fc2 = make((width, width), inner)
        self.fc3 = make((width, num_experts), out)

    def forward(self, x, state):
        return ops.moe_route_mlp(x, state, self.down_proj, self.eda_scale,
                                 self.fc1, self.fc2, self.fc3)


class SwiGLU(Layer):
    """down(silu(gate(x)) * up(x)), no bias. `out_std`: the draw of
    `down_proj`, which writes to the stream (None: `std`)."""

    def __init__(self, hidden, width, std=0.02, out_std=None):
        super().__init__()
        attr = Normal(std=std)
        self.gate_proj = Linear(hidden, width, weight_attr=attr,
                                bias_attr=False)
        self.up_proj = Linear(hidden, width, weight_attr=attr,
                              bias_attr=False)
        self.down_proj = Linear(
            width, hidden, bias_attr=False,
            weight_attr=attr if out_std is None else Normal(std=out_std))

    def forward(self, x):
        return self.down_proj(ops.silu(self.gate_proj(x)) * self.up_proj(x))


class SparseExpertFFN(Layer):
    """y = sum over a token's top_k experts e of w_e * SwiGLU_e(x)
           + SwiGLU_shared(x): dropless (no capacity; every assignment is
    computed, whatever the imbalance). Three routers exist:

    * the linear sigmoid one (`ops.moe_route`; `laguna-xs2-l5-e64`):
      w = routed_scale * s / sum_chosen s, s = sigmoid(x W_r), the top_k
      largest chosen;
    * `router_score="softmax"`: the linear softmax one (`ops.moe_route`
      with `score="softmax"`; `qwen3-next-80b-l4-e64`): the same with
      s = softmax(x W_r) over all `num_experts`;
    * `router_mlp=(width, draws)`: the MLP one (`ops.moe_route_mlp`;
      `zaya1-8b-l5-e8`): a down-projection to `width` to which the
      layer before's is added (depth averaging), two hidden layers,
      softmax, the one largest chosen and w its probability. forward
      then takes and returns the router's state: `(x, state) ->
      (y, counts, state, weights, experts)`.

    `shared_gate=True`: the shared expert's output is multiplied by
    sigmoid(x w_g), w_g [hidden, 1] (`shared_expert_gate`), a token.

    `router_normalize=False` (the linear routers): w = routed_scale * s,
    the chosen scores as they are (`deepseek-v2-lite-e8`).
    `aux="sequence_balance"`: forward returns a third value, the
    layer's sequence-wise balance term (`ops.moe_sequence_balance`: over
    a row of x [rows, T, hidden], sum_e f_e P_e, the rows' mean), for a
    criterion to add to the loss. It runs over all `num_experts` router
    outputs, so it is whole on a share.

    `held = (first, count)`: the experts this layer's weights are, of
    `num_experts`. The router keeps its `num_experts` outputs and routes
    over all of them; the layer computes its own experts' part of the
    routed sum, and the shared expert in full. What the experts held
    elsewhere would add is left out: an expert-parallel deployment sums
    the parts over the chips that share the layer, and nothing here
    stands in for them or for that exchange.

    Weights: `gate_up_proj` [count, hidden, 2 * width] (gate in the first
    `width` columns) and `down_proj` [count, width, hidden]. forward
    returns (y, counts): counts [count] int32, the assignments each held
    expert got. On a TPU the expert products are the grouped-matmul
    kernels and the way back to the tokens' order the kernel
    `moe_sum_rows`, which moves the rows of the held assignments alone;
    elsewhere `jax.lax.ragged_dot` and a gather over every slot
    (`ops.moe_experts`). The `moe` note of the step's compile record
    says which."""

    def __init__(self, hidden, width, num_experts=256, top_k=8, held=None,
                 shared_width=512, routed_scale=2.5, std=0.02,
                 router_mlp=None, router_score="sigmoid",
                 shared_gate=False, router_normalize=True, aux=None):
        super().__init__()
        if aux not in (None, "sequence_balance") or (
                router_mlp is not None and (aux or not router_normalize)):
            raise ValueError(
                f"SparseExpertFFN: aux {aux!r}; the balance term and "
                "unnormalised weights are the linear routers'")
        self.aux = aux
        first, count = held or (0, num_experts)
        if first < 0 or count < 1 or first + count > num_experts:
            raise ValueError(f"held {held} is no range of {num_experts} "
                             "experts")
        self.num_experts = num_experts
        self.first, self.count = first, count
        if router_mlp is None:
            self.router = _Router(hidden, num_experts, top_k, routed_scale,
                                  std, router_score, router_normalize,
                                  aux is not None)
        else:
            if top_k != 1:
                raise ValueError("the MLP router chooses one expert")
            self.router = _MLPRouter(hidden, num_experts, *router_mlp)
        self.top_k = top_k
        self.gate_up_proj = self.create_parameter(
            (count, hidden, 2 * width), attr=Normal(std=std))
        self.down_proj = self.create_parameter(
            (count, width, hidden), attr=Normal(std=std))
        self.shared_expert = SwiGLU(hidden, shared_width, std) \
            if shared_width else None
        self.shared_expert_gate = Linear(
            hidden, 1, weight_attr=Normal(std=std), bias_attr=False) \
            if shared_gate else None
        self._scores_note = ", softmax scores" \
            if router_mlp is None and router_score == "softmax" else ""
        if not router_normalize:
            self._scores_note += ", weights as scored"
        if aux:
            self._scores_note += ", sequence balance term"

    def forward(self, x, state=None):
        from ...kernels.pallas.grouped_matmul import (ROW_TILE, gmm_path,
                                                      tiles_note)
        from ...ops.moe_ops import way_back_path
        shape = x.shape
        flat = ops.reshape(x, (-1, shape[-1]))
        carries = self.router.carries_state
        if carries:
            weights, experts, state = self.router(
                flat, ops.reshape(state, (-1, state.shape[-1])))
            state = ops.reshape(state, tuple(shape[:-1]) + (-1,))
        elif self.aux:
            weights, experts, scores = self.router(flat)
        else:
            weights, experts = self.router(flat)
        perf.trace_note("moe", f"{gmm_path()}, experts {self.count} held "
                        f"of {self.num_experts}, top {self.top_k}, "
                        f"tiles of {ROW_TILE} rows"
                        f"{tiles_note(self.gate_up_proj.shape)}, way back: "
                        f"{way_back_path()}{self._scores_note}")
        y, counts = ops.moe_experts(flat, weights, experts,
                                    self.gate_up_proj, self.down_proj,
                                    self.first)
        y = ops.reshape(y, shape)
        if self.shared_expert is not None:
            shared = self.shared_expert(x)
            if self.shared_expert_gate is not None:
                shared = shared * ops.sigmoid(self.shared_expert_gate(x))
            y = y + shared
        if self.aux:
            rows = 1 if len(shape) < 3 else int(np.prod(shape[:-2]))
            return y, counts, ops.moe_sequence_balance(scores, experts, rows)
        return (y, counts, state, weights, experts) if carries \
            else (y, counts)


def observe_expert_load(counts, assignments: int, top_weight=None) -> dict:
    """From a step's counts (a numpy array [sparse layers, held experts]
    the caller read with the loss: the step's program made them) the
    gauges
    `moe.assignments_held` (the share of all tokens x top_k assignments
    that went to experts held here, a layer's mean),
    `moe.load_max_over_mean` (the busiest held expert's assignments over
    the mean, the layers' mean),
    `moe.way_back_rows_share` (the rows the way back to the tokens' order
    reads into its sums, over tokens x top_k: the held assignments where
    the kernel `moe_sum_rows` is the path, every slot where the gather
    `_sum_slots` is) and, with `top_weight` (a one-choice router's mean
    chosen probability a layer, [sparse layers]),
    `moe.top1_weight_mean` (their mean: 1 / num_experts says the router
    is flat). Returns them; sets the gauges where metrics are enabled."""
    from ...ops.moe_ops import way_back_reads_held_rows_only
    c = counts.astype(np.float64)
    held = float(c.sum(axis=1).mean() / assignments)
    mean = np.maximum(c.mean(axis=1), 1e-9)
    peak = float((c.max(axis=1) / mean).mean())
    back = held if way_back_reads_held_rows_only() else 1.0
    out = {"moe.assignments_held": held, "moe.load_max_over_mean": peak,
           "moe.way_back_rows_share": back}
    if top_weight is not None:
        out["moe.top1_weight_mean"] = float(np.mean(top_weight))
    if _metrics._ENABLED:
        reg = _metrics.registry()
        reg.gauge("paddle_tpu_moe_assignments_held",
                  "share of a step's tokens x top_k assignments routed to "
                  "experts this process holds (moe.assignments_held)"
                  ).set(held)
        reg.gauge("paddle_tpu_moe_load_max_over_mean",
                  "busiest held expert's assignments over the mean held "
                  "expert's, mean over sparse layers "
                  "(moe.load_max_over_mean)").set(peak)
        reg.gauge("paddle_tpu_moe_way_back_rows_share",
                  "rows the way back from the experts' order reads into "
                  "its sums over tokens x top_k (moe.way_back_rows_share)"
                  ).set(back)
        if top_weight is not None:
            reg.gauge("paddle_tpu_moe_top1_weight_mean",
                      "mean probability of the one expert a token chose, "
                      "mean over sparse layers (moe.top1_weight_mean)"
                      ).set(out["moe.top1_weight_mean"])
    return out

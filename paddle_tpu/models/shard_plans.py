"""Megatron-style tensor-parallel sharding plans for the model families.

Maps parameter names to `PartitionSpec`s over a ("dp", "mp") mesh — the
GSPMD expression of the reference's ColumnParallelLinear /
RowParallelLinear / VocabParallelEmbedding placement
(/root/reference/python/paddle/distributed/fleet/layers/mpu/mp_layers.py:47,
333, 540). Column-parallel weights shard the output dim, row-parallel
weights shard the input dim, embeddings shard the vocab dim; XLA inserts
the matching allreduce/allgather collectives during propagation.
"""
from __future__ import annotations

from jax.sharding import PartitionSpec as P


def gpt_tp_rules(name: str, shape) -> P:
    """Shard plan for models.gpt.GPTForCausalLM parameters."""
    if "word_embeddings" in name:
        return P("mp", None)           # vocab-sharded
    if "position_embeddings" in name:
        return P()
    if "qkv_proj.weight" in name or "fc1.weight" in name:
        return P(None, "mp")           # column parallel
    if "qkv_proj.bias" in name or "fc1.bias" in name:
        return P("mp")
    if "out_proj.weight" in name or "fc2.weight" in name:
        return P("mp", None)           # row parallel
    if "lm_head.weight" in name:
        return P(None, "mp")
    return P()                         # norms, remaining biases: replicated


def llama_tp_rules(name: str, shape) -> P:
    """Shard plan for models.llama.LlamaForCausalLM parameters."""
    if "embed_tokens" in name:
        return P("mp", None)
    if any(k in name for k in ("q_proj.weight", "k_proj.weight",
                               "v_proj.weight", "gate_proj.weight",
                               "up_proj.weight", "lm_head.weight")):
        return P(None, "mp")
    if "o_proj.weight" in name or "down_proj.weight" in name:
        return P("mp", None)
    return P()


def fsdp_rules(name: str, shape) -> P:
    """ZeRO-3-style fully-sharded plan: shard the largest dim on "dp"
    (GSPMD rendering of GroupShardedStage3 param partitioning,
    ref: .../meta_parallel/sharding/group_sharded_stage3.py:85)."""
    if not shape:
        return P()
    big = max(range(len(shape)), key=lambda i: shape[i])
    spec = [None] * len(shape)
    spec[big] = "dp"
    return P(*spec)


def expert_parallel_rules(axis: str = "ep"):
    """-> the `shard_param(name, shape)` of an expert-parallel layout
    over the mesh axis `axis` (`TrainStep(mesh=, shard_param=,
    expert_axis=axis)`): the stacked experts' leading dimension
    (`...gate_up_proj`, `...down_proj`, three-dimensional), the
    embedding's vocabulary rows and the head's vocabulary columns lie on
    the axis; attention, routers and norms are whole on every device,
    which runs them on its own rows of the batch (data parallel: the
    partitioner sums their gradients)."""
    def rule(name: str, shape) -> P:
        if len(shape) == 3 and name.endswith(("gate_up_proj", "down_proj")):
            return P(axis)
        if "embed_tokens" in name:
            return P(axis, None)
        if name.endswith("lm_head.weight"):
            return P(None, axis)
        return P()
    return rule

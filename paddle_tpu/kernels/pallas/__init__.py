"""Pallas TPU kernels — the escape hatch for ops XLA doesn't fuse well
(SURVEY §7.1: the role CINN's custom kernels played in the reference)."""
from .flash_attention import (  # noqa: F401
    flash_attention, flash_attention_qkv)
from .norms import layer_norm, rms_norm  # noqa: F401

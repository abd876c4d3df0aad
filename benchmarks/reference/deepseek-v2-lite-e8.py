"""The plain reference of `deepseek-v2-lite-e8`: the shared DeepSeek-V2
reference at this configuration's sizes and share (layers 0-6: the
leading dense layer and six sparse ones; experts 0-7 of 64; rows 0-12799
of the embedding and of the head). One row of 32768 tokens, two first
steps followed and only the first moment kept between them, so that
0.74 B float32 parameters, one moment and a layer's activations fit one
16 GB chip and the check ends inside a run's limit."""
from harness.deepseek_v2_reference import (Model, Trainer,  # noqa: F401
                                           change_norms, exact, fp8,
                                           head_sizes, latent_core, leaf,
                                           make, n_params, param_specs,
                                           softmax_scale)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

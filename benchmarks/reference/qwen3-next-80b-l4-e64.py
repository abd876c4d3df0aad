"""The plain reference of `qwen3-next-80b-l4-e64`: the shared Qwen3-Next
reference at this configuration's sizes and share (layers 0-3: three
Gated DeltaNet layers and the full one; experts 0-63 of 512; rows
0-18991 of the embedding and of the head). One row of 16384 tokens, two
first steps followed and only the first moment kept between them, so
that 1.03 B float32 parameters, one moment and a layer's activations fit
one 16 GB chip and the check ends inside a run's limit."""
from harness.qwen3next_reference import (Model, Trainer,  # noqa: F401
                                         change_norms, exact, fp8, leaf,
                                         linear_heads, make, param_specs,
                                         recurrence)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

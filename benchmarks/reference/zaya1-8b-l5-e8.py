"""The plain reference of `zaya1-8b-l5-e8`: the shared ZAYA1 reference at
this configuration's sizes and share (experts 0-7 of 16, rows 0-131135
of the tied embedding). One row of 32768 tokens, two first steps
followed and only the first moment kept between them, so that 0.8 B
float32 parameters, one moment and a layer's activations fit one 16 GB
chip and the check ends inside a run's limit."""
from harness.zaya_reference import (Model, Trainer,  # noqa: F401
                                    change_norms, exact, fp8, param_specs)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

"""The plain reference of `jamba2-3b-l14`: the shared Jamba reference at
this configuration's sizes. One row, two first steps followed and only
the first moment kept between them, so that 1.6 B float32 parameters,
one moment and a layer's activations fit one 16 GB chip and the check
ends inside a run's limit."""
from harness.jamba_reference import (Model, Trainer, exact, fp8,  # noqa: F401
                                     param_specs)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

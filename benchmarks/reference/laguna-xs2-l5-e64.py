"""The plain reference of `laguna-xs2-l5-e64`: the shared Laguna
reference at this configuration's sizes and share (experts 0-63 of 256,
rows 0-25087 of the vocabulary). One row of 8192 tokens at a time, two
first steps followed and only the first moment kept between them, so
that 1.15 B float32 parameters, one moment and a layer's activations fit
one 16 GB chip and the check ends inside a run's limit."""
from harness.laguna_reference import (Model, Trainer, exact, fp8,  # noqa: F401
                                      param_specs)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

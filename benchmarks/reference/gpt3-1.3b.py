"""The plain reference of `gpt3-1.3b`: the shared GPT reference at this
configuration's sizes. One row at a time, and two first steps followed
instead of three, so that 1.3B float32 parameters, one moment and the
activations fit one 16 GB chip and the check stays shorter than a run's
window."""
from harness.gpt_reference import (Model, Trainer, exact, fp8,  # noqa: F401
                                   param_specs)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

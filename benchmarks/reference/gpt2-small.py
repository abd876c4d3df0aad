"""The plain reference of `gpt2-small`: the shared GPT reference at this
configuration's sizes; everything fits on the chip."""
from harness.gpt_reference import (Model, Trainer, exact, fp8,  # noqa: F401
                                   param_specs)

ROW_BLOCK = 4
CHECK_STEPS = 3

"""The plain reference of `ouro-2.6b-l8`: the shared Ouro reference at
this configuration's sizes (layers 0-7 of 48, run four times over; the
embedding, the final norm, the exit gate and the untied head over the
whole vocabulary). One row of 4096 tokens at a time, two first steps
followed and only the first moment kept between them, so that 612 M
float32 parameters, one moment, the summed gradients and 32 layer
applications' inputs fit one 16 GB chip and the check ends inside a
run's limit."""
from harness.ouro_reference import (Model, Trainer,  # noqa: F401
                                    change_norms, exact, fp8, leaf, make,
                                    n_params, param_specs)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

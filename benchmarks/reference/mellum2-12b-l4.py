"""The plain reference of `mellum2-12b-l4`: the shared Mellum2 reference
at this configuration's sizes: one whole period, all 64 experts, all
98,304 rows. ONE model, not a share. On the four-chip host its leaves
are made in a layout over the four chips (`mellum2_reference.layout`:
placement alone, no `shard_map`, no collective in the source) and the
compiler partitions the plain functions from there; one row of 8192
tokens at a time, two first steps followed and only the first moment
kept between them, so that 2.124 B float32 parameters, one moment and a
layer's activations fit the host and the check ends inside a run's
limit."""
from harness.mellum2_reference import (Model, Trainer, change_norms,  # noqa: F401
                                       exact, fp8, layout, make, n_params,
                                       param_specs, routing, sparse_ffn)

ROW_BLOCK = 1       # rows of the batch computed at once
CHECK_STEPS = 2     # first steps the training reference follows

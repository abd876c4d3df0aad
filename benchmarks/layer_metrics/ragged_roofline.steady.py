"""The ragged paged-attention kernel's share of its roofline in the
traced window (`kernel_costs/ragged.py`)."""


def read(run):
    return run.spec.module("kernel_costs", "ragged").roofline_share(run)

"""Median time per output token after the first delivery, by the
harness's clock; above the knee it is recorded, not judged."""
from harness.runlib import percentile


def read(run):
    return percentile(run.window["tpot_ms"], 50)

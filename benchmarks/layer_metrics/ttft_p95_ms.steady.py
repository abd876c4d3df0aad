"""First-token time minus the time the request was due, 95th percentile
over every request whose first token landed in the window. Recorded
beside the median, which the driver reports end to end (`ttft_p50_ms`):
over 200 requests the 95th percentile spread by 10% from run to run on
the chip, wider than any bound may be (PERF.md, section 6)."""
from harness.runlib import percentile


def read(run):
    return percentile(run.window["ttft_ms"], 95)

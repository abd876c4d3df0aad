"""The chip's published peaks, keyed by the exact `device_kind`. A device
that is not in the table has no peaks and gets no default: `peaks()` is
None and a share of a peak is then not read at all."""
import json
import os


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)
    row = table.get(device_kind)
    return row if isinstance(row, dict) else None   # "source" is a note


def least_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of operations over
    peak rate and bytes over peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

"""The exchange's share of its roofline: the bytes a chip sends in a
step's exchanges (`harness/mellum2_flops.py:exchange_bytes_per_step`,
from the shapes; the run's notes carry the program's own count of a
forward beside it) over the seconds a step during which a transfer of
the exchange may be under way (`trace_chips.in_flight_s`, the chips'
mean) and over the chip's interconnect peak (`harness/ici_peaks.json`,
every port of a chip summed; a 2 x 2 host cannot use every port, so the
share reads low)."""
import json
import os
import statistics

from harness import trace_chips


def ici_peak(device_kind: str):
    with open(os.path.join(os.path.dirname(trace_chips.__file__),
                           "ici_peaks.json")) as f:
        row = json.load(f).get(device_kind)
    return row["ici_bytes_per_s"] if isinstance(row, dict) else None


def read(run):
    sent = (run.window or {}).get("exchange_bytes_per_step")
    peak = ici_peak(run.device["kind"])
    times = [trace_chips.in_flight_s(chip) for chip in trace_chips.of(run)]
    times = [t for t in times if t]
    if not sent or peak is None or not times:
        return None
    return 100.0 * sent / statistics.mean(times) / peak

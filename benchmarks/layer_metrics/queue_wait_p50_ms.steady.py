"""From when a request was due to the start of the `step()` that admitted
it (the step whose results first hold it): the front door's and the
engine's waiting list together. Median over the requests whose first
token landed in the window."""
from harness.runlib import percentile


def read(run):
    return percentile([1e3 * (r.admit - r.due)
                       for r in run.window["first_in"]], 50)

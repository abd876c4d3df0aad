"""How late the load generator ran: the time at which the harness took a
request off its schedule minus the time it was due, 95th percentile over
the requests due in the window. The driver is one thread, so an arrival
that falls due inside an `engine.step()` is seen when that step returns."""
from harness.runlib import percentile


def read(run):
    w = run.window
    lag = [1e3 * (r.seen - r.due) for r in w["reqs"]
           if w["t0"] < r.due <= w["t1"]]
    return percentile(lag, 95)

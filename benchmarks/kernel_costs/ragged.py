"""Operations and bytes the ragged paged-attention kernel's algorithm
needs for one layer of one launch: `new` fresh tokens of each row attend
causally among themselves and fully over the row's `cached` context.
What the algorithm needs is the context's keys and values, not the
pool's: a kernel that sweeps every pool tile reads more and its share of
this roofline is low — that is the finding, not an error of the count.
"""

from harness.trace_reduce import is_kernel


def classify(op_name: str):
    """The serving programs' only custom calls are the ragged kernel's
    (the decode program has none)."""
    return "ragged" if is_kernel(op_name) else None


def cost(rows, heads: int, dim: int):
    """rows: [(new tokens, cached tokens)] -> (operations, bytes)."""
    flops = nbytes = 0.0
    for new, cached in rows:
        pairs = new * cached + new * (new + 1) / 2
        flops += 2 * 2.0 * heads * dim * pairs           # scores, values
        nbytes += 3 * new * heads * dim * 2              # q, k, v in bf16
        nbytes += new * heads * dim * 4                  # o in float32
        nbytes += 2 * cached * heads * dim * 2           # cached k, v
    return flops, nbytes


def roofline_share(run):
    """The kernel's least possible time, for the fresh tokens and the
    cached context of every row of every wave launched while the trace
    ran, over its measured time in the traced window (%), or None."""
    from harness import peaks
    peak = peaks.peaks(run.device["kind"])
    w, cfg = run.window, run.cfg
    lo, hi = w["trace_span"]
    if peak is None or lo is None or hi is None:
        return None
    waves = [s[4] for s in w["steps"] if s[4] and lo <= s[0] and s[1] <= hi]
    times = [t for n, t in run.trace_summary.op_self_times() if classify(n)]
    if not waves or not times:
        return None
    # every wave launches the kernel once per layer; charge the trace's
    # calls with the waves' mean cost, so that a wave the host clock
    # places just outside the traced window does not skew the share
    per_layer = [peaks.least_seconds(
        *cost(rows, cfg["num_heads"], cfg["head_dim"]), peak)
        for rows in waves]
    return 100.0 * len(times) * sum(per_layer) / len(per_layer) / sum(times)

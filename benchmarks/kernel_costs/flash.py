"""Operations and bytes the flash-attention kernels' algorithm needs, for
one call on q, k, v of [rows, seq, heads, head_dim] in bfloat16, causal.

forward: the score and the value product, 2 * 2*rows*heads*seq*seq*dim,
halved by causality; reads q, k, v, writes o and the row log-sums.
backward: dV, dP, dQ, dK: four such products (the scores it recomputes
are not counted); reads q, k, v, o, dO and the log-sums, writes dq, dk, dv.
"""
from harness.trace_reduce import is_kernel, short_name


def classify(op_name: str):
    """Which flash kernel a device operation of the training step is, by
    the name the trace gives it, or None. The step's only kernels are the
    flash ones. XLA names the backward kernel after the transposed jvp it
    came from (`transpose_jvp___.18`) or, inside a recomputed block,
    `checkpoint.N`; the forward one `jvp__.N` or `rematted_computation.N`.
    An unknown name counts as forward, the smaller cost: a share can then
    read too low, never too high."""
    if not is_kernel(op_name):
        return None
    stem = short_name(op_name)
    return "bwd" if "transpose" in stem or stem.startswith("checkpoint") \
        else "fwd"


def cost(kind: str, rows: int, seq: int, heads: int, dim: int):
    """(operations, bytes) of one call."""
    product = 2.0 * rows * heads * seq * seq * dim / 2      # causal
    tensor = rows * seq * heads * dim * 2                    # bf16
    lse = rows * heads * seq * 4
    if kind == "fwd":
        return 2 * product, 4 * tensor + lse
    if kind == "bwd":
        return 4 * product, 8 * tensor + 2 * lse
    raise KeyError(kind)

"""Operations and bytes the flash kernels need for one call on a key in
two parts (multi-head latent attention): q and k [rows, seq, heads, dn],
q' [rows, seq, heads, dr], k' [rows, seq, 1, dr] (ONE head that every
query head reads), v [rows, seq, heads, dv], bfloat16, causal, every key
in sight: seq * (seq + 1) / 2 (row, key) pairs a head.

forward: the score over dn + dr lanes and the value product over dv, 2
operations a pair and lane; reads q, q', k, ONE k', v, writes o and the
row log-sums. backward: dV and dP over dv, dQ and dK over dn + dr, and
dK's part for k' (the scores it recomputes are not counted):
2 * (3 * (dn + dr) + 2 * dv) less the recomputed score's 2 * (dn + dr);
reads q, q', k, k', v, o, dO and the log-sums, writes dq, dq', dk, dk'
(once, summed over the heads), dv."""
import re

_LAYER = re.compile(r"(?:^|/)layers/(\d+)/")


def classify(component: str):
    """(kind, layer index) of a two-part flash kernel by the scope path
    the program gave it (`…/layers/3/attn/flash_mla_bwd_transpose`), or
    None."""
    leaf = component.rsplit("/", 1)[-1]
    kind = {"flash_mla_fwd": "fwd", "flash_mla_bwd_transpose": "bwd"}.get(leaf)
    where = _LAYER.search(component)
    if kind is None or where is None:
        return None
    return kind, int(where.group(1))


def cost(kind: str, rows: int, seq: int, heads: int, dn: int, dr: int,
         dv: int):
    """(operations, bytes) of one call."""
    pairs = rows * heads * seq * (seq + 1) / 2.0
    q = rows * seq * heads * (dn + dr) * 2                  # bf16
    k = rows * seq * (heads * dn + dr) * 2                  # ONE k'
    v = rows * seq * heads * dv * 2
    lse = rows * heads * seq * 4
    if kind == "fwd":
        return 2.0 * pairs * (dn + dr + dv), q + k + 2 * v + lse
    if kind == "bwd":
        # dQ and dK over dn + dr each, dV and dP over dv each
        return (2.0 * pairs * (2 * (dn + dr) + 2 * dv),
                2 * q + 2 * k + 4 * v + 2 * lse)
    raise KeyError(kind)

"""Operations and bytes the flash-attention kernels' algorithm needs for
one call on q [rows, seq, heads, dim] and k, v [rows, seq, kv_heads,
dim] in bfloat16, causal, with or without a window: `kernel_costs/
flash.py`'s counts with grouped heads and a window in them.

A row i sees min(i + 1, window) keys, so the (row, key) pairs of a
sequence are window * (seq - window) + window * (window + 1) / 2 under
a window shorter than it and seq * (seq + 1) / 2 otherwise. forward:
the score and the value product, 2 operations a pair, head and dim
each; reads q, k, v, writes o and the row log-sums. backward: dV, dP,
dQ, dK: four such products (the scores it recomputes are not counted);
reads q, k, v, o, dO and the log-sums, writes dq, dk, dv."""
import re

_LAYER = re.compile(r"(?:^|/)layers/(\d+)/")


def classify(component: str):
    """(kind, layer index) of a flash kernel by the scope path the
    program gave it (`…/layers/3/attn/flash_bwd_transpose`), or None."""
    leaf = component.rsplit("/", 1)[-1]
    kind = {"flash_fwd": "fwd", "flash_bwd_transpose": "bwd"}.get(leaf)
    where = _LAYER.search(component)
    if kind is None or where is None:
        return None
    return kind, int(where.group(1))


def pairs(seq: int, window=None) -> float:
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (seq - window) + window * (window + 1) / 2.0


def cost(kind: str, rows: int, seq: int, heads: int, kv_heads: int,
         dim: int, window=None):
    """(operations, bytes) of one call."""
    product = 2.0 * rows * heads * pairs(seq, window) * dim
    q = rows * seq * heads * dim * 2                     # bf16
    kv = rows * seq * kv_heads * dim * 2
    lse = rows * heads * seq * 4
    if kind == "fwd":
        return 2 * product, 2 * q + 2 * kv + lse
    if kind == "bwd":
        return 4 * product, 4 * q + 4 * kv + 2 * lse
    raise KeyError(kind)


def layer_shape(cfg: dict, layer: int):
    """(heads, kv_heads, dim, window) of a configuration's layer."""
    sliding = cfg["layer_types"][layer] == "sliding_attention"
    return (cfg["num_attention_heads_per_layer"][layer],
            cfg["num_key_value_heads"], cfg["head_dim"],
            cfg["sliding_window"] if sliding else None)

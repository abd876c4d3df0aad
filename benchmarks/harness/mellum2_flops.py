"""Operations a Mellum2's forward and backward passes require per token
(recomputed ones not counted), and the bytes its experts' exchange moves.

Operations: 6 per parameter of a matmul the token really meets, and
causal attention's score and value products by each layer's window. A
token meets every attention projection, the router, the whole head, and
of a layer's 64 experts the num_experts_per_tok it chose: every expert
is held on the cell's host, so nothing is at an expectation. The
embedding is a lookup; rotary, norms and the router's softmax and top-k
are no matmuls: not counted.

Bytes: `exchange_bytes_per_step`, what ONE chip sends over the
interconnect in a step's exchanges, from the shapes alone
(`paddle_tpu.ops.moe_ops.exchange_bytes` is the program's own count of a
forward, which the run's notes carry beside it)."""
from harness.laguna_flops import pairs_per_token


def parts_per_token(cfg: dict, seq: int) -> dict:
    """Operations a token by part: routed experts, full attention's and
    window attention's products, the head, everything else."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    expert = 3 * h * cfg["moe_intermediate_size"]
    out = {"routed_experts": 0.0, "full_attention": 0.0,
           "window_attention": 0.0, "head": 6.0 * h * cfg["vocab_size"],
           "other": 0.0}
    for kind in cfg["layer_types"]:
        out["other"] += 6.0 * (2 * h * q + 2 * h * kv
                               + h * cfg["num_experts"])
        sliding = kind == "sliding_attention"
        # two products of 2 operations a pair forward, twice that back
        out["window_attention" if sliding else "full_attention"] += \
            12.0 * q * pairs_per_token(
                seq, cfg["sliding_window"] if sliding else None)
        out["routed_experts"] += 6.0 * cfg["num_experts_per_tok"] * expert
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return sum(parts_per_token(cfg, seq).values())


def exchange_bytes_per_step(cfg: dict, mix: dict, chips: int) -> float:
    """Bytes one chip sends in a step's exchanges. A layer's forward
    sends the chip's rows (bfloat16, with 8 weights and 8 choices of 4
    bytes a token) to the other chips and their rows of its partial sums
    (bfloat16) back; run again by `jax.checkpoint` it sends both again;
    its backward sends the cotangent's rows out the way the sums came
    and the rows' partial gradients (bfloat16) and the weights' (float32)
    back the way the rows came."""
    tokens = mix["batch"] * mix["seq"] // chips
    k, row = cfg["num_experts_per_tok"], 2 * cfg["hidden_size"]
    others = chips - 1
    out = others * tokens * (row + 8 * k)       # rows, weights, choices
    back = others * tokens * row                # partial sums
    forward = out + back
    again = forward if cfg["training"]["recompute_interval"] else 0
    backward = back + others * tokens * (row + 4 * k)
    return float(len(cfg["layer_types"]) * (forward + again + backward))

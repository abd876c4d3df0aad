"""Operations a Laguna's forward and backward passes require per token
on this chip's share (recomputed ones not counted): 6 per parameter of a
matmul the token really meets, and causal attention's score and value
products by each layer's heads and window.

A token meets every attention projection, the dense feed-forward of a
dense layer, the router, the shared expert, the slice of the head, and
of the held experts those it chose: num_experts_per_tok * held /
published of them at the expectation of uniform routing (2 of the 8
here). The embedding is a lookup; rotary, norms and the router's
sigmoid and top-k are no matmuls: not counted."""


def published_experts(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held_per_token(cfg: dict) -> float:
    """Expected assignments of a token to experts held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / published_experts(cfg)


def pairs_per_token(seq: int, window=None) -> float:
    """Mean keys a row sees."""
    if window is None or window >= seq:
        return (seq + 1) / 2.0
    return (window * (seq - window) + window * (window + 1) / 2.0) / seq


def parts_per_token(cfg: dict, seq: int) -> dict:
    """Operations a token by part: routed experts, full attention's and
    window attention's products, everything else."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * d
    expert = 3 * h * cfg["moe_intermediate_size"]
    out = {"routed_experts": 0.0, "full_attention": 0.0,
           "window_attention": 0.0,
           "other": 6.0 * h * cfg["vocab_size"]}        # the head's slice
    for i in range(cfg["num_hidden_layers"]):
        q = cfg["num_attention_heads_per_layer"][i] * d
        out["other"] += 6.0 * (2 * h * q + 2 * h * kv)
        sliding = cfg["layer_types"][i] == "sliding_attention"
        # two products of 2 operations a pair forward, twice that back
        out["window_attention" if sliding else "full_attention"] += \
            12.0 * q * pairs_per_token(
                seq, cfg["sliding_window"] if sliding else None)
        if cfg["mlp_layer_types"][i] == "dense":
            out["other"] += 6.0 * 3 * h * cfg["intermediate_size"]
        else:
            out["other"] += 6.0 * (
                h * published_experts(cfg)
                + 3 * h * cfg["shared_expert_intermediate_size"])
            out["routed_experts"] += 6.0 * held_per_token(cfg) * expert
    return out


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return sum(parts_per_token(cfg, seq).values())

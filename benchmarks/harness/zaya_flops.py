"""Operations a ZAYA1's forward and backward passes require per token on
this chip's share (recomputed ones not counted): 6 per parameter of a
matmul the token really meets, and causal attention's score and value
products in the compressed latent.

A token meets the latent projections (W_q, W_k, W_v1, W_v2, W_o), the
grouped convolution's two taps, the router's down-projection and MLP,
the slice of the tied head, and of the held experts the one it chose
where that one is held: num_experts_per_tok * held / published of an
expert at the expectation of uniform routing (a half here). The
embedding is a lookup; the depthwise convolution, the mean, the norms,
rotary, the router's softmax and the residual's vectors are no matmuls:
not counted."""


def published_experts(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held_per_token(cfg: dict) -> float:
    """Expected assignments of a token to experts held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / published_experts(cfg)


def parts_per_token(cfg: dict, seq: int) -> dict:
    """Operations a token by part: routed experts, attention's products
    in the latent, the head, everything else."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    R = cfg["router_hidden_size"]
    n = cfg["num_hidden_layers"]
    latent = (h * (H + 2 * Hk) * d + H * d * h       # qkv_proj, o_proj
              + (H + Hk) * 2 * d * d)                # the grouped taps
    router = h * R + 2 * R * R + R * published_experts(cfg)
    expert = 3 * h * cfg["moe_intermediate_size"]
    return {
        "routed_experts": 6.0 * n * held_per_token(cfg) * expert,
        # two products of 2 operations a (row, key) pair forward, twice
        # that back: 12 a pair, (seq + 1) / 2 keys a row, H heads of d
        "attention": 12.0 * n * H * d * (seq + 1) / 2.0,
        "head": 6.0 * h * cfg["vocab_size"],
        "other": 6.0 * n * (latent + router),
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return sum(parts_per_token(cfg, seq).values())

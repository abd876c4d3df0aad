"""Operations a Qwen3-Next's forward and backward passes require per
token on this chip's share (recomputed ones not counted): 6 per
parameter of a matmul the token really meets, causal attention's score
and value products, and the gated delta rule's chunked products.

A token meets, in a Gated DeltaNet layer, `in_proj_qkvz`, `in_proj_ba`
and `out_proj`, and the rule's products (`kernel_costs/gated_delta.py`'s
count of the chunked algorithm: a chunk's products over its tokens); in
a full layer the four projections and the causal product; in every layer
the router, the shared expert with its gate, and of the held experts
those it chose that are held: num_experts_per_tok * held / published at
the expectation of uniform routing (1.25 here); the slice of the head.
The embedding is a lookup; the convolution, the norms, the gates, rotary
and the router's softmax are no matmuls: not counted."""
from kernel_costs import gated_delta
from paddle_tpu.kernels.pallas.gated_delta import CHUNK     # the program's


def published_experts(cfg: dict) -> int:
    return cfg.get("published", {}).get("num_experts", cfg["num_experts"])


def held_per_token(cfg: dict) -> float:
    """Expected assignments of a token to experts held here."""
    return cfg["num_experts_per_tok"] * cfg["num_experts"] \
        / published_experts(cfg)


def layers(cfg: dict):
    """(linear layers, full layers)."""
    n = cfg["num_hidden_layers"]
    full = sum((i + 1) % cfg["full_attention_interval"] == 0
               for i in range(n))
    return n - full, full


def parts_per_token(cfg: dict, seq: int) -> dict:
    """Operations a token by part: the linear mixers' projections, their
    delta rule, the full layers' projections, their causal product, the
    expert layers, the head."""
    h = cfg["hidden_size"]
    Hk, Hv, dl = (cfg["linear_num_key_heads"],
                  cfg["linear_num_value_heads"], cfg["linear_key_head_dim"])
    H, Hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    linear, full = layers(cfg)
    gdn = h * (2 * (Hk + Hv) * dl + 2 * Hv) + Hv * dl * h
    attn = h * (2 * H + 2 * Hkv) * d + H * d * h
    expert = 3 * h * cfg["moe_intermediate_size"]
    shared = 3 * h * cfg["shared_expert_intermediate_size"] + h
    router = h * published_experts(cfg)
    # a chunk's products forward, over its tokens; backward twice that
    rule = 3.0 * Hv * gated_delta.chunk_operations(CHUNK, dl, dl) / CHUNK
    return {
        "gdn_projections": 6.0 * linear * gdn,
        "delta_rule": linear * rule,
        "attention_projections": 6.0 * full * attn,
        # two products of 2 operations a (row, key) pair forward, twice
        # that back: 12 a pair, (seq + 1) / 2 keys a row, H heads of d
        "attention": 12.0 * full * H * d * (seq + 1) / 2.0,
        "experts": 6.0 * (linear + full) * (
            router + shared + held_per_token(cfg) * expert),
        "head": 6.0 * h * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return sum(parts_per_token(cfg, seq).values())

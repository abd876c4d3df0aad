"""Operations a DeepSeek-V2's forward and backward passes require per
token on this chip's share (recomputed ones not counted): 6 per
parameter of a matmul the token really meets, and causal attention's
score and value products at their own widths.

A token meets, in every layer, latent attention's four projections
(q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj) and the causal product:
a score over qk_nope_head_dim + qk_rope_head_dim, a value product over
v_head_dim, (seq + 1) / 2 keys a row; in a dense layer the feed-forward
of intermediate_size; in a sparse one the router over the published
count, the shared experts, and of the held experts those it chose that
are held: num_experts_per_tok * held / published at the expectation of
uniform routing (0.75 of the 6 here); the slice of the head. The
embedding is a lookup; norms, rotary, softmax, top-k and the balance
term are no matmuls: not counted."""


def published_experts(cfg: dict) -> int:
    return cfg.get("published", {}).get("n_routed_experts",
                                        cfg["n_routed_experts"])


def held_per_token(cfg: dict) -> float:
    """Expected assignments of a token to experts held here."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] \
        / published_experts(cfg)


def parts_per_token(cfg: dict, seq: int) -> dict:
    """Operations a token by part."""
    h, H = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    n = cfg["num_hidden_layers"]
    dense = min(cfg["first_k_dense_replace"], n)
    sparse = n - dense
    projections = (h * H * (dn + dr) + h * (rank + dr)
                   + rank * H * (dn + dv) + H * dv * h)
    wide = cfg["moe_intermediate_size"]
    return {
        "attention_projections": 6.0 * n * projections,
        # 2 operations a (row, key) pair and lane forward, twice that
        # back: the score over dn + dr, the value product over dv
        "attention": 6.0 * n * H * (dn + dr + dv) * (seq + 1) / 2.0,
        "dense_ffn": 6.0 * dense * 3 * h * cfg["intermediate_size"],
        "router_and_shared": 6.0 * sparse * (
            h * published_experts(cfg)
            + 3 * h * cfg["n_shared_experts"] * wide),
        "routed_experts": 6.0 * sparse * held_per_token(cfg) * 3 * h * wide,
        "head": 6.0 * h * cfg["vocab_size"],
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return sum(parts_per_token(cfg, seq).values())

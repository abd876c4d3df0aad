"""Operations an Ouro's forward and backward passes require per token on
this chip's share (recomputed ones not counted): 6 per parameter of a
matmul, and causal attention's score and value products, for every
layer APPLICATION (the held layers times `total_ut_steps`: the loop runs
them all for every token), and the head and the exit gate once a pass.
The embedding is a lookup; the norms, the rotary, softmax, the exit
distribution and the entropy are no matmuls: not counted."""


def parts_per_token(cfg: dict, seq: int) -> dict:
    """Operations a token by part: the layer applications' projections
    and feed-forwards, their causal products, the T heads, the T gates."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    H, Hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    T = cfg["total_ut_steps"]
    applications = cfg["num_hidden_layers"] * T
    matrices = h * (2 * H + 2 * Hk) * d + 3 * h * cfg["intermediate_size"]
    return {
        "layer_matrices": 6.0 * applications * matrices,
        # two products of 2 operations a (row, key) pair forward, twice
        # that back: 12 a pair, (seq + 1) / 2 keys a row, H heads of d
        "attention": 12.0 * applications * H * d * (seq + 1) / 2.0,
        "heads": 6.0 * T * h * cfg["vocab_size"],
        "exit_gates": 6.0 * T * h,
    }


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return sum(parts_per_token(cfg, seq).values())

"""Operations a GPT's forward and backward passes require per token
(recomputed ones not counted): 6 per parameter that takes part in a
matmul, plus causal attention's score and value products. Copied in
spirit from bench.py's `6 * N * tokens/s / peak`, with attention added
and the position table (a lookup, no matmul) left out."""


def matmul_params(cfg: dict) -> int:
    h, i, n = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_layers"]
    per_layer = 3 * h * h + h * h + 2 * h * i
    return n * per_layer + cfg["vocab_size"] * h     # the tied head


def train_flops_per_token(cfg: dict, seq: int) -> float:
    # causal attention: 2 matmuls of 2*seq*h/2 forward per layer, twice
    # that backward -> 6 * seq * h per layer and token
    attn = 6 * cfg["num_layers"] * seq * cfg["hidden_size"]
    return 6.0 * matmul_params(cfg) + attn

"""Operations a Jamba's forward and backward passes require per token
(recomputed ones not counted): 6 per parameter that takes part in a
matmul, causal attention's score and value products in the attention
layers, and the recurrence's counted operations in the Mamba layers
(`kernel_costs/ssm_scan.py`: they run on the vector unit, and are under
a hundredth of the whole). The embedding is a lookup and the causal
convolution 4 multiply-adds a channel: neither is counted."""

def layer_kinds(cfg: dict):
    """(Mamba layers, attention layers) of the configuration."""
    attn = sum(i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
               for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - attn, attn


def matmul_params(cfg: dict) -> int:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    e = cfg["mamba_expand"] * h
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * d, cfg["num_key_value_heads"] * d
    mamba = h * 2 * e + e * (r + 2 * n) + r * e + e * h
    attention = 2 * h * q + 2 * h * kv
    n_mamba, n_attn = layer_kinds(cfg)
    return (n_mamba * mamba + n_attn * attention
            + cfg["num_hidden_layers"] * 3 * h * inter
            + cfg["vocab_size"] * h)            # the tied head


def train_flops_per_token(cfg: dict, seq: int, scan_cost) -> float:
    """`scan_cost`: `kernel_costs/ssm_scan.py:cost`."""
    h = cfg["hidden_size"]
    d = cfg.get("head_dim") or h // cfg["num_attention_heads"]
    n_mamba, n_attn = layer_kinds(cfg)
    # causal attention: 2 matmuls of 2*seq*q/2 forward per layer, twice
    # that backward -> 6 * seq * q per layer and token
    attn = 6.0 * n_attn * seq * cfg["num_attention_heads"] * d
    e, n = cfg["mamba_expand"] * h, cfg["mamba_d_state"]
    per_token = sum(scan_cost(kind, 1, 1, e, n)[0]
                    for kind in ("fwd", "bwd"))
    return 6.0 * matmul_params(cfg) + attn + n_mamba * per_token

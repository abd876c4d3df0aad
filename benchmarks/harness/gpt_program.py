"""The glue to the program's GPT: its model object at a configuration's
sizes, holding the seed's weights. Both drivers build the system under
test through this, so that the training and the serving cells of one
configuration are the same model."""
from __future__ import annotations

from harness import weights


def build_model(cfg: dict, seed: int, ref, dtype, **model_kw):
    """`GPTForCausalLM` at `cfg`'s sizes with the seed's weights in
    `dtype`. The program initialises its own parameters first; each is
    then handed the harness's array of the same name and shape."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.models.gpt import GPTConfig

    pt.seed(0)
    model = GPTForCausalLM(GPTConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_layers=cfg["num_layers"], num_heads=cfg["num_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position_embeddings=cfg["max_position_embeddings"],
        hidden_dropout_prob=0.0, attention_dropout_prob=0.0,
        initializer_range=cfg["initializer_range"],
        layer_norm_eps=cfg["layer_norm_eps"], tie_word_embeddings=True,
        **model_kw))
    dtype = jnp.dtype(dtype)
    if dtype == jnp.bfloat16:
        model = model.bfloat16()
    specs = ref.param_specs(cfg)
    arrays = dict(zip((n for n, _s, _i in specs),
                      weights.make(seed, specs, dtype)))
    for name, p in model.named_parameters():
        if tuple(p.shape) != tuple(arrays[name].shape):
            raise RuntimeError(f"{name}: the program has {p.shape}, the "
                               f"reference {arrays[name].shape}")
        p._data = arrays.pop(name)
    if arrays:
        raise RuntimeError(f"the program lacks {sorted(arrays)}")
    return model

"""The glue to the program's DeepSeek-V2: its model object at a
configuration's sizes and share, holding the seed's weights."""
from __future__ import annotations


def build_model(cfg: dict, seed: int, ref, **model_kw):
    """`DeepseekV2ForCausalLM` at `cfg`'s sizes with the seed's float32
    weights. The program builds its parameters as placeholders
    (`paddle_tpu.LazyGuard`: its own draws would be thrown away); each is
    then handed the harness's array of the same name and shape
    (`deepseek_v2_reference.make`: a function of the seed)."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                               DeepseekV2ForCausalLM)

    with pt.LazyGuard():
        model = DeepseekV2ForCausalLM(
            DeepseekV2Config.from_dict(cfg, **model_kw))
    specs = ref.param_specs(cfg)
    arrays = dict(zip((n for n, _s, _i in specs),
                      ref.make(seed, specs, jnp.float32)))
    for name, p in model.named_parameters():
        if tuple(p.shape) != tuple(arrays[name].shape):
            raise RuntimeError(f"{name}: the program has {p.shape}, the "
                               f"reference {arrays[name].shape}")
        p._data = arrays.pop(name)
    if arrays:
        raise RuntimeError(f"the program lacks {sorted(arrays)}")
    return model

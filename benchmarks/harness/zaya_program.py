"""The glue to the program's ZAYA1: its model object at a
configuration's sizes and share, holding the seed's weights."""
from __future__ import annotations

from harness import zaya_reference


def build_model(cfg: dict, seed: int, ref, **model_kw):
    """`ZayaForCausalLM` at `cfg`'s sizes with the seed's float32
    weights. The program initialises its own parameters first; each is
    then handed the harness's array of the same name and shape
    (`zaya_reference.make`: a function of the seed, drawn here anew)."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from paddle_tpu.models.zaya import ZayaConfig, ZayaForCausalLM

    pt.seed(0)
    model = ZayaForCausalLM(ZayaConfig.from_dict(cfg, **model_kw))
    specs = ref.param_specs(cfg)
    arrays = dict(zip((n for n, _s, _i in specs),
                      zaya_reference.make(seed, specs, jnp.float32)))
    for name, p in model.named_parameters():
        if tuple(p.shape) != tuple(arrays[name].shape):
            raise RuntimeError(f"{name}: the program has {p.shape}, the "
                               f"reference {arrays[name].shape}")
        p._data = arrays.pop(name)
    if arrays:
        raise RuntimeError(f"the program lacks {sorted(arrays)}")
    return model

"""The glue to the program's Mellum2: the mesh of a cell's chips, and the
model object at a configuration's sizes holding the seed's weights, every
array of it made by shard: nothing is ever whole on one device (8.5 GB of
float32 weights whole beside their shards would not fit one)."""
from __future__ import annotations


def mesh_of(cfg: dict):
    """The configuration's `training.mesh` over the first devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh
    m = cfg["training"]["mesh"]
    n = int(np.prod(m["shape"]))
    return Mesh(np.array(jax.devices()[:n]).reshape(m["shape"]),
                tuple(m["axes"]))


def build_model(cfg: dict, seed: int, ref, mesh, **model_kw):
    """`Mellum2ForCausalLM` at `cfg`'s sizes with the seed's float32
    weights, laid over `mesh` by `shard_plans.expert_parallel_rules`.
    The program builds its parameters as placeholders, each in the
    shards the rule gives its shape (`paddle_tpu.LazyGuard(place=)`);
    each is then handed the harness's array of the same name and shape,
    which `ref.make` draws from the seed in those same shards."""
    import jax.numpy as jnp
    import paddle_tpu as pt
    from jax.sharding import NamedSharding
    from paddle_tpu.models.mellum2 import Mellum2Config, Mellum2ForCausalLM
    from paddle_tpu.models.shard_plans import expert_parallel_rules

    rule = expert_parallel_rules(cfg["training"]["mesh"]["expert_axis"])
    specs = ref.param_specs(cfg)
    shardings = [NamedSharding(mesh, rule(n, tuple(s))) for n, s, _i in specs]
    # a placeholder's shards by its shape; a shape that two layouts share
    # (tiny test sizes) is left to the default device: it is replaced
    by_shape = {}
    for (_name, shape, _i), sh in zip(specs, shardings):
        if by_shape.setdefault(tuple(shape), sh) != sh:
            by_shape[tuple(shape)] = None
    with pt.LazyGuard(place=lambda shape: by_shape[tuple(shape)]):
        model = Mellum2ForCausalLM(Mellum2Config.from_dict(cfg, **model_kw))
    arrays = dict(zip((n for n, _s, _i in specs),
                      ref.make(seed, specs, jnp.float32, shardings)))
    for name, p in model.named_parameters():
        if tuple(p.shape) != tuple(arrays[name].shape):
            raise RuntimeError(f"{name}: the program has {p.shape}, the "
                               f"reference {arrays[name].shape}")
        p._data = arrays.pop(name)
    if arrays:
        raise RuntimeError(f"the program lacks {sorted(arrays)}")
    return model

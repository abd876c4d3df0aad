"""Weights from the seed: one jitted call on the device, in the type
they are used in. The program is handed these arrays and the reference
makes its own from the same seed with the same function — neither takes
anything the other has made."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def key_of(seed: int):
    """A key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def leaf(key, index: int, shape, init, dtype):
    """Leaf `index` of a parameter list: ("normal", std) or
    ("const", value). Drawn in float32 and cast, so that the float32
    and the bfloat16 model of one seed are the same model rounded."""
    kind, arg = init
    if kind == "const":
        return jnp.full(shape, arg, dtype)
    k = jax.random.fold_in(key, index)
    return (jax.random.normal(k, shape, jnp.float32) * arg).astype(dtype)


def make(seed: int, specs, dtype):
    """[(name, shape, init)] -> list of arrays, all in one program."""
    key = key_of(seed)
    shapes = [tuple(s) for _n, s, _i in specs]
    inits = [tuple(i) for _n, _s, i in specs]

    @jax.jit
    def build(key):
        return [leaf(key, i, shapes[i], inits[i], dtype)
                for i in range(len(specs))]

    return build(key)


def change_norms(params, specs, seed: int):
    """Norm of every leaf's change since the seed's weights, which are
    drawn again leaf by leaf (a second copy of them all would not fit
    beside a 1.3B model's state)."""
    key = key_of(seed)

    @jax.jit
    def one(p, p0):
        return jnp.sqrt(jnp.sum(jnp.square(p - p0)))

    return [float(one(p, leaf(key, i, tuple(s), tuple(init), jnp.float32)))
            for i, (p, (_n, s, init)) in enumerate(zip(params, specs))]

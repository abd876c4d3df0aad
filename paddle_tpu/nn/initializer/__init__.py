"""Weight initializers (ref: python/paddle/nn/initializer/).

Each initializer is a callable (shape, dtype) -> jnp array, drawing keys
from the global generator for reproducibility under paddle_tpu.seed()."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from ...core.generator import next_key


class Initializer:
    def __call__(self, shape, dtype=jnp.float32):
        raise NotImplementedError


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype=jnp.float32):
        return jnp.full(shape, self.value, dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype=jnp.float32):
        # drawn as [rows, last dimension] and viewed as `shape`: the same
        # numbers (a draw is a function of the key and the element's
        # flat index), and the stacked experts' three-dimensional leaf
        # compiles for a TPU in a third of the time (3 s against 10 at
        # [64, 2048, 1024])
        flat = (math.prod(shape[:-1]), shape[-1]) if len(shape) > 2 \
            else shape
        return (jax.random.normal(next_key(), flat, dtype) * self.std
                + self.mean).reshape(shape)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, shape, dtype=jnp.float32):
        return (jax.random.truncated_normal(
            next_key(), self.a, self.b, shape, dtype) * self.std + self.mean)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype=jnp.float32):
        return jax.random.uniform(next_key(), shape, dtype,
                                  self.low, self.high)


def _fans(shape):
    if len(shape) == 1:
        return shape[0], shape[0]
    if len(shape) == 2:
        return shape[0], shape[1]
    # conv kernels [out, in, *k] (paddle layout)
    receptive = int(np.prod(shape[2:]))
    return shape[1] * receptive, shape[0] * receptive


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype=jnp.float32):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return jax.random.normal(next_key(), shape, dtype) * std


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype=jnp.float32):
        fi, fo = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        return jax.random.uniform(next_key(), shape, dtype, -limit, limit)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope
        self.nonlinearity = nonlinearity

    def __call__(self, shape, dtype=jnp.float32):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2))
        std = gain / math.sqrt(fi)
        return jax.random.normal(next_key(), shape, dtype) * std


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in = fan_in
        self.negative_slope = negative_slope

    def __call__(self, shape, dtype=jnp.float32):
        fi, _ = _fans(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope ** 2))
        limit = gain * math.sqrt(3.0 / fi)
        return jax.random.uniform(next_key(), shape, dtype, -limit, limit)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype=jnp.float32):
        from ...core.tensor import Tensor
        v = self.value
        if isinstance(v, Tensor):
            v = v._data
        arr = jnp.asarray(np.asarray(v), dtype).reshape(shape)
        return arr


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype=jnp.float32):
        return jax.nn.initializers.orthogonal(self.gain)(
            next_key(), shape, dtype)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype=jnp.float32):
        out = np.zeros(shape, np.float32)
        oc, ic = shape[0], shape[1]
        k = [s // 2 for s in shape[2:]]
        for i in range(min(oc, ic * self.groups)):
            idx = (i, i % ic) + tuple(k)
            out[idx] = 1.0
        return jnp.asarray(out, dtype)


def get_initializer(spec):
    if spec is None:
        return None
    if isinstance(spec, Initializer):
        return spec
    if callable(spec):
        return spec
    raise TypeError(f"cannot interpret initializer {spec!r}")


def calculate_gain(nonlinearity, param=None):
    if nonlinearity == "tanh":
        return 5.0 / 3.0
    if nonlinearity == "relu":
        return math.sqrt(2.0)
    if nonlinearity == "leaky_relu":
        a = 0.01 if param is None else param
        return math.sqrt(2.0 / (1 + a ** 2))
    if nonlinearity == "selu":
        return 3.0 / 4.0
    return 1.0


def set_global_initializer(weight_init, bias_init=None):
    # informational; layers read their own attrs
    global _global_weight_init, _global_bias_init
    _global_weight_init = weight_init
    _global_bias_init = bias_init


_global_weight_init = None
_global_bias_init = None

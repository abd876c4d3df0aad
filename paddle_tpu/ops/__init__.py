"""Public op namespace + Tensor method patching.

The reference patches ~700 methods onto Tensor from python/paddle/tensor/
(math_op_patch; python/paddle/tensor/__init__.py). Same approach here: every
registered op whose first parameter is a tensor becomes a Tensor method, and
python operators route through the registry so they are AMP-aware and
tape-recorded."""
from __future__ import annotations

import jax.numpy as jnp

from ..core.tensor import Tensor
from .registry import register_op, OPS, get_op

from .creation import (  # noqa: F401
    zeros, ones, full, empty, eye, arange, linspace, logspace, zeros_like,
    ones_like, full_like, empty_like, assign, tril, triu, diag, diagflat,
    meshgrid, tril_indices, triu_indices, clone, complex, as_complex, as_real,
)
from .math import *  # noqa: F401,F403
from .math import abs as _abs_op, pow as _pow_op, round as _round_op
from .reduction import *  # noqa: F401,F403
from .reduction import sum as _sum_op, max as _max_op, min as _min_op, \
    all as _all_op, any as _any_op
from .manipulation import *  # noqa: F401,F403
from .manipulation import split, slice, chunk, unbind, atleast_1d, \
    atleast_2d, atleast_3d, broadcast_tensors, _pad as pad
from .linalg import *  # noqa: F401,F403
from .linalg import einsum, t
from .logic import *  # noqa: F401,F403
from .logic import is_tensor
from .search import *  # noqa: F401,F403
from .search import unique
from .random import (  # noqa: F401
    rand, uniform, randn, normal, gaussian, standard_normal, randint,
    randint_like, randperm, multinomial, bernoulli, poisson, rand_like,
    randn_like, exponential_,
)
from .longtail import *  # noqa: F401,F403
from .nn_ops import *  # noqa: F401,F403
from .ssm_ops import selective_scan, causal_conv1d  # noqa: F401
from .moe_ops import (moe_route, moe_route_mlp, moe_sequence_balance,  # noqa: F401
                      moe_experts)
from .cca_ops import cca_mix  # noqa: F401
from .linear_attn_ops import gated_delta_rule, gdn_operands  # noqa: F401
from .rope_ops import rope_rotate_half  # noqa: F401
from .vision_ops import (  # noqa: F401
    depthwise_conv2d, conv3d_transpose, deformable_conv, fold,
    max_pool2d_with_index, unpool, roi_pool, psroi_pool, prior_box,
    yolo_box, matrix_nms, multiclass_nms, max_pool3d_with_index, unpool3d,
    generate_proposals, distribute_fpn_proposals,
)
from .sequence_ops import (  # noqa: F401
    ctc_loss, viterbi_decode, gather_tree, top_p_sampling, edit_distance,
    class_center_sample,
)
from .math import logcumsumexp, clip_by_norm, renorm, add_n, \
    elementwise_pow  # noqa: F401
from .linalg import p_norm, lu_unpack, spectral_norm  # noqa: F401
from .manipulation import unstack, fill_diagonal  # noqa: F401
from .random import (  # noqa: F401
    binomial, dirichlet, standard_gamma, truncated_normal,
)


# ---------------------------------------------------------------------------
# indexing ops
# ---------------------------------------------------------------------------
@register_op("getitem")
def _getitem(x, index):
    return x[index]


@register_op("setitem")
def _setitem(x, index, value):
    return x.at[index].set(value)


def _normalize_index(idx):
    """Unwrap any Tensor leaves stay as-is (dispatch handles them)."""
    return idx


def _tensor_getitem(self, idx):
    if isinstance(idx, tuple):
        idx = tuple(i for i in idx)
    return _getitem(self, idx)


def _tensor_setitem(self, idx, value):
    out = _setitem(self, idx, value)
    # transplant the new version into self (functional under the hood,
    # mutation semantics at the API — ref: tensor inplace version counter)
    self._data = out._data
    self._grad_node = out._grad_node
    self._out_idx = out._out_idx
    if not out.stop_gradient:
        self.stop_gradient = False


Tensor.__getitem__ = _tensor_getitem
Tensor.__setitem__ = _tensor_setitem


# ---------------------------------------------------------------------------
# operator dunders
# ---------------------------------------------------------------------------
def _binop(op):
    def f(self, other):
        return op(self, other)

    return f


def _rbinop(op):
    def f(self, other):
        return op(Tensor(other) if not isinstance(other, Tensor) else other,
                  self)

    return f


from .math import add, subtract, multiply, divide, floor_divide, mod
from .linalg import matmul
from .logic import (equal, not_equal, greater_than, greater_equal, less_than,
                    less_equal, logical_and, logical_or, logical_xor,
                    logical_not, bitwise_and, bitwise_or, bitwise_xor,
                    bitwise_not)

Tensor.__add__ = _binop(add)
Tensor.__radd__ = _rbinop(add)
Tensor.__sub__ = _binop(subtract)
Tensor.__rsub__ = _rbinop(subtract)
Tensor.__mul__ = _binop(multiply)
Tensor.__rmul__ = _rbinop(multiply)
Tensor.__truediv__ = _binop(divide)
Tensor.__rtruediv__ = _rbinop(divide)
Tensor.__floordiv__ = _binop(floor_divide)
Tensor.__rfloordiv__ = _rbinop(floor_divide)
Tensor.__mod__ = _binop(mod)
Tensor.__rmod__ = _rbinop(mod)
Tensor.__pow__ = _binop(_pow_op)
Tensor.__rpow__ = _rbinop(_pow_op)
Tensor.__matmul__ = _binop(matmul)
Tensor.__rmatmul__ = _rbinop(matmul)
Tensor.__neg__ = lambda self: neg(self)  # noqa: F405
Tensor.__abs__ = lambda self: _abs_op(self)
Tensor.__eq__ = _binop(equal)
Tensor.__ne__ = _binop(not_equal)
Tensor.__gt__ = _binop(greater_than)
Tensor.__ge__ = _binop(greater_equal)
Tensor.__lt__ = _binop(less_than)
Tensor.__le__ = _binop(less_equal)
Tensor.__and__ = _binop(bitwise_and)
Tensor.__or__ = _binop(bitwise_or)
Tensor.__xor__ = _binop(bitwise_xor)
Tensor.__invert__ = lambda self: bitwise_not(self)
Tensor.__hash__ = lambda self: id(self)


# ---------------------------------------------------------------------------
# method patching
# ---------------------------------------------------------------------------
_METHOD_NAMES = [
    # math
    "abs", "add", "subtract", "multiply", "divide", "floor_divide", "mod",
    "pow", "maximum", "minimum", "fmax", "fmin", "exp", "expm1", "log",
    "log2", "log10", "log1p", "sqrt", "rsqrt", "square", "reciprocal",
    "sign", "sin", "cos", "tan", "asin", "acos", "atan", "sinh", "cosh",
    "tanh", "asinh", "acosh", "atanh", "ceil", "floor", "round", "trunc",
    "frac", "erf", "erfinv", "lgamma", "digamma", "sigmoid", "neg", "clip",
    "isnan", "isinf", "isfinite", "nan_to_num", "lerp", "scale", "atan2",
    "heaviside", "hypot",
    # reductions
    "sum", "mean", "max", "min", "amax", "amin", "prod", "logsumexp", "var",
    "std", "median", "nanmedian", "nansum", "nanmean", "quantile", "all",
    "any", "count_nonzero", "cumsum", "cumprod", "cummax", "cummin",
    # manipulation
    "reshape", "transpose", "flatten", "squeeze", "unsqueeze", "tile",
    "expand", "expand_as", "broadcast_to", "roll", "flip", "gather",
    "gather_nd", "scatter", "scatter_nd_add", "index_select", "index_sample",
    "index_add", "index_fill", "masked_select", "masked_fill", "split",
    "chunk", "unbind", "cast", "repeat_interleave", "moveaxis", "swapaxes",
    "take_along_axis", "put_along_axis", "unfold", "view", "as_strided",
    "flatten", "tril", "triu", "diagonal", "masked_scatter",
    # linalg
    "matmul", "mm", "bmm", "dot", "inner", "outer", "mv", "t", "cross",
    "norm", "dist", "cholesky", "inverse", "pinv", "trace", "kron",
    "matrix_power",
    # logic
    "equal", "not_equal", "greater_than", "greater_equal", "less_than",
    "less_equal", "equal_all", "allclose", "isclose", "logical_and",
    "logical_or", "logical_xor", "logical_not", "bitwise_and", "bitwise_or",
    "bitwise_xor", "bitwise_not", "is_empty",
    # search
    "argmax", "argmin", "argsort", "sort", "topk", "kthvalue", "mode",
    "nonzero", "unique", "unique_consecutive", "searchsorted", "bucketize",
    # creation-ish
    "zeros_like", "ones_like", "full_like",
]

_ns = globals()
for _name in _METHOD_NAMES:
    _fn = _ns.get(_name)
    if _fn is None:
        continue
    if not hasattr(Tensor, _name) or _name in ("t",):
        setattr(Tensor, _name, _fn)

Tensor.remainder = _ns["mod"]


def _astype(self, dtype):
    return cast(self, dtype)  # noqa: F405


Tensor.astype = _astype
Tensor.type = _astype


# ---- inplace variants (ref: paddle's *_ API; functional underneath) ----
def _make_inplace(op):
    def f(self, *args, **kwargs):
        out = op(self, *args, **kwargs)
        self._data = out._data
        self._grad_node = out._grad_node
        self._out_idx = out._out_idx
        if not out.stop_gradient:
            self.stop_gradient = False
        return self

    return f


for _name in ["add", "subtract", "multiply", "divide", "clip", "scale",
              "floor", "ceil", "exp", "sqrt", "rsqrt", "reciprocal",
              "tanh", "sigmoid", "cast"]:
    _fn = _ns.get(_name)
    if _fn is not None:
        setattr(Tensor, _name + "_", _make_inplace(_fn))


def _zero_(self):
    self._data = jnp.zeros_like(self._data)
    return self


def _fill_(self, value):
    self._data = jnp.full_like(self._data, value)
    return self


def _uniform_(self, min=-1.0, max=1.0, seed=0):
    from ..core.generator import next_key
    import jax
    self._data = jax.random.uniform(next_key(), self._data.shape,
                                    self._data.dtype, min, max)
    return self


def _normal_(self, mean=0.0, std=1.0):
    from ..core.generator import next_key
    import jax
    self._data = (jax.random.normal(next_key(), self._data.shape,
                                    self._data.dtype) * std + mean)
    return self


Tensor.zero_ = _zero_
Tensor.fill_ = _fill_
Tensor.uniform_ = _uniform_
Tensor.normal_ = _normal_
Tensor.exponential_ = exponential_

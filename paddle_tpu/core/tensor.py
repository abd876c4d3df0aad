"""Eager Tensor.

TPU-native analog of paddle::Tensor + AutogradMeta
(/root/reference/paddle/phi/api/include/tensor.h:82,
/root/reference/paddle/fluid/eager/autograd_meta.h:61). The device buffer is
a jax.Array (PJRT-owned memory — no framework allocator needed, matching the
survey's M0 design); autograd meta is (grad_node, out_idx, grad, hooks).

Most math/manipulation methods are patched on from paddle_tpu.ops (the
reference patches methods the same way: python/paddle/tensor/__init__.py).
"""
from __future__ import annotations

import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from .device import get_place

_name_counter = itertools.count()


class Tensor:
    __slots__ = (
        "_data", "stop_gradient", "persistable", "name",
        "_grad", "_grad_node", "_out_idx", "_hooks", "_hook_counter",
        "_retain_grad", "_dist_attr", "__weakref__",
    )

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 name: Optional[str] = None):
        if isinstance(data, Tensor):
            data = data._data
        if dtype is not None:
            data = jnp.asarray(data, dtypes.to_jnp(dtype))
        elif isinstance(data, (bool, int, float, list, tuple, np.ndarray)):
            arr = np.asarray(data)
            # default float is float32, default int is int64 (ref convention)
            if arr.dtype == np.float64:
                arr = arr.astype(np.float32)
            data = jnp.asarray(arr)
        else:
            data = jnp.asarray(data)
        if place is not None and not _is_tracer(data):
            data = jax.device_put(data, place.jax_device())
        self._data = data
        self.stop_gradient = stop_gradient
        self.persistable = False
        self.name = name or f"generated_tensor_{next(_name_counter)}"
        self._grad = None
        self._grad_node = None
        self._out_idx = 0
        self._hooks = {}
        self._hook_counter = itertools.count()
        self._retain_grad = False
        self._dist_attr = None

    # -- fast constructor used by dispatch --
    @staticmethod
    def _wrap(arr, stop_gradient=True, name=None) -> "Tensor":
        t = Tensor.__new__(Tensor)
        t._data = arr
        t.stop_gradient = stop_gradient
        t.persistable = False
        t.name = name or f"generated_tensor_{next(_name_counter)}"
        t._grad = None
        t._grad_node = None
        t._out_idx = 0
        t._hooks = {}
        t._hook_counter = itertools.count()
        t._retain_grad = False
        t._dist_attr = None
        return t

    # ---- metadata ----
    @property
    def shape(self):
        return list(self._data.shape)

    @property
    def ndim(self):
        return self._data.ndim

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    @property
    def dtype(self) -> dtypes.DType:
        return dtypes.from_np(self._data.dtype)

    @property
    def place(self):
        try:
            dev = self._data.devices()
            dev = next(iter(dev))
            from .device import Place
            kind = "cpu" if dev.platform == "cpu" else "tpu"
            return Place(kind, dev.id)
        except Exception:
            return get_place()

    @property
    def is_leaf(self):
        return self._grad_node is None

    @property
    def grad(self):
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    def clear_grad(self):
        self._grad = None

    clear_gradient = clear_grad

    def retain_grads(self):
        self._retain_grad = True
        return self

    # ---- interop ----
    def numpy(self):
        return np.asarray(self._data)

    def __array__(self, dtype=None):
        a = np.asarray(self._data)
        return a.astype(dtype) if dtype is not None else a

    def __jax_array__(self):
        return self._data

    def item(self, *args):
        return self._data.item(*args)

    def tolist(self):
        return np.asarray(self._data).tolist()

    def __float__(self):
        return float(self._data)

    def __int__(self):
        return int(self._data)

    def __bool__(self):
        return bool(self._data)

    def __index__(self):
        return int(self._data)

    def __len__(self):
        if self._data.ndim == 0:
            raise TypeError("len() of a 0-d tensor")
        return self._data.shape[0]

    def __hash__(self):
        return id(self)

    # ---- autograd ----
    def backward(self, grad_tensor=None, retain_graph=False):
        from ..autograd.tape import run_backward
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def register_hook(self, hook):
        hid = next(self._hook_counter)
        self._hooks[hid] = hook

        class _Removable:
            def __init__(self, d, k):
                self._d, self._k = d, k

            def remove(self):
                self._d.pop(self._k, None)

        return _Removable(self._hooks, hid)

    def detach(self) -> "Tensor":
        t = Tensor._wrap(self._data, stop_gradient=True, name=self.name)
        return t

    def detach_(self):
        self._grad_node = None
        self.stop_gradient = True
        return self

    # ---- in-place data management (optimizer update path) ----
    def _set_data(self, arr):
        """Replace the underlying buffer (used by optimizers / load).
        Device arrays rebind directly: jnp.asarray's dtype
        canonicalization walk cost ~80us per call on the fused
        optimizer's per-param update path (ISSUE 13 profile), and a
        jax.Array is already exactly what `_data` holds."""
        if isinstance(arr, Tensor):
            arr = arr._data
        if isinstance(arr, jax.Array):
            self._data = arr
        else:
            self._data = jnp.asarray(arr)
        return self

    def set_value(self, value):
        if isinstance(value, Tensor):
            value = value._data
        self._data = jnp.asarray(value, self._data.dtype).reshape(self._data.shape)
        return self

    def copy_(self, other, blocking=True):
        return self.set_value(other)

    def get_tensor(self):  # LoDTensor-compat shim
        return self

    # ---- convenience ----
    def clone(self) -> "Tensor":
        from ..ops import assign
        return assign(self)

    def to_sparse_coo(self, sparse_dim=None):
        """Dense -> SparseCooTensor (ref: to_sparse_coo in
        phi/api/yaml/sparse_ops.yaml; Tensor method in
        python/paddle/tensor/manipulation.py). sparse_dim < ndim yields
        a hybrid COO: indices over the leading sparse dims, values keep
        the trailing dims dense (BCOO n_dense)."""
        from ..sparse import SparseCooTensor, _dense_to_coo
        nd = self._data.ndim
        if sparse_dim is None or int(sparse_dim) == nd:
            return _dense_to_coo(self._data)
        sd = int(sparse_dim)
        if not 1 <= sd <= nd:
            raise ValueError(
                f"to_sparse_coo: sparse_dim must be in [1, {nd}], "
                f"got {sparse_dim}")
        from jax.experimental import sparse as jsparse
        return SparseCooTensor(
            jsparse.BCOO.fromdense(self._data, n_dense=nd - sd))

    def to_sparse_csr(self):
        """Dense -> SparseCsrTensor (ref: to_sparse_csr,
        sparse_ops.yaml)."""
        from ..sparse import _dense_to_csr
        return _dense_to_csr(self._data)

    def to(self, *args, **kwargs):
        """to(dtype) / to(device) / to(device, dtype)."""
        dst_dtype = None
        dst_place = None
        from .device import Place, parse_device
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, (dtypes.DType,)) or (
                    isinstance(a, str) and a in dtypes._BY_NAME):
                dst_dtype = dtypes.to_dtype(a)
            elif isinstance(a, Place):
                dst_place = a
            elif isinstance(a, str):
                dst_place = parse_device(a)
        arr = self._data
        if dst_dtype is not None:
            from ..ops import cast
            return cast(self, dst_dtype) if dst_place is None else Tensor(
                np.asarray(arr), dtype=dst_dtype, place=dst_place,
                stop_gradient=self.stop_gradient)
        if dst_place is not None:
            arr = jax.device_put(arr, dst_place.jax_device())
            t = Tensor._wrap(arr, stop_gradient=self.stop_gradient, name=self.name)
            return t
        return self

    def cpu(self):
        from .device import CPUPlace
        return self.to(CPUPlace())

    def cuda(self, device_id=0):
        return self

    def pin_memory(self):
        return self

    def __deepcopy__(self, memo):
        # the wrapper must be fresh (independent autograd meta) AND the
        # buffer must be a distinct device allocation: deep-copied params
        # (e.g. TransformerEncoder replicating its layer) are donated as
        # separate arguments by TrainStep, and XLA rejects donating one
        # buffer twice
        t = type(self).__new__(type(self))
        t._data = (self._data if _is_tracer(self._data)
                   else jnp.array(self._data, copy=True))
        t.stop_gradient = self.stop_gradient
        t.persistable = self.persistable
        t.name = self.name
        t._grad = None
        t._grad_node = None
        t._out_idx = 0
        t._hooks = {}
        t._hook_counter = itertools.count()
        t._retain_grad = False
        t._dist_attr = self._dist_attr
        memo[id(self)] = t
        return t

    def __reduce__(self):
        # pickle via a NUMPY roundtrip, not the jax.Array's own pickle:
        # the payload is then backend-neutral — a Tensor built in a
        # JAX_PLATFORMS=cpu DataLoader worker materialises on whatever
        # device the unpickling parent runs (jax re-imports lazily at
        # load time). Autograd meta is deliberately dropped: a pickled
        # tensor crosses a process boundary, where grad graph nodes
        # have no meaning.
        return (_rebuild_tensor, (np.asarray(self._data),
                                  self.stop_gradient, self.name))

    def __repr__(self):
        grad_info = "" if self.stop_gradient else ", stop_gradient=False"
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}"
                f"{grad_info},\n       {np.asarray(self._data)!r})")

    def __iter__(self):
        if self._data.ndim == 0:
            raise TypeError("iteration over a 0-d tensor")
        for i in range(self._data.shape[0]):
            yield self[i]

    # __getitem__/__setitem__ and math dunders patched in ops/__init__.py


def _rebuild_tensor(arr, stop_gradient, name):
    """Unpickle target of Tensor.__reduce__ (numpy -> device array)."""
    return Tensor._wrap(jnp.asarray(arr), stop_gradient=stop_gradient,
                        name=name)


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


# Register Tensor as a jax pytree so jit/vmap over Tensor-carrying
# structures works (functional interop for the to_static path).
def _tensor_flatten(t: Tensor):
    return (t._data,), (t.stop_gradient,)


def _tensor_unflatten(aux, children):
    t = Tensor._wrap(children[0], stop_gradient=aux[0])
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)


class DeferredTensor(Tensor):
    """A Tensor that stands for an array nobody has read yet.

    `compute()` runs at the first read of `_data`, which is how every op
    and every interop method reaches the array, so any reader gets what
    an ordinary Tensor would have held. A consumer that knows a cheaper
    way to its own result asks for `producer` (whatever the maker left
    there to say what the array is the product of) while `computed` is
    still False, and never pays for the array (GPT's head and loss:
    models/gpt.py). Shape and dtype are known without the array. Made
    only inside traced programs, where no tape records."""
    __slots__ = ("_compute", "_array", "_aval", "producer")

    def __init__(self, compute, shape, dtype, producer=None):
        self._compute = compute
        self._array = None
        self._aval = jax.ShapeDtypeStruct(tuple(shape), dtype)
        self.producer = producer
        self.stop_gradient = True   # as any op's output under no_grad
        self.persistable = False
        self.name = f"generated_tensor_{next(_name_counter)}"
        self._grad = None
        self._grad_node = None
        self._out_idx = 0
        self._hooks = {}
        self._hook_counter = itertools.count()
        self._retain_grad = False
        self._dist_attr = None

    @property
    def computed(self) -> bool:
        return self._array is not None

    @property
    def _data(self):
        if self._array is None:
            self._array = self._compute()
            self._compute = None
        return self._array

    @_data.setter
    def _data(self, arr):
        self._array = arr
        self._compute = None

    @property
    def shape(self):
        return list(self._aval.shape)

    @property
    def ndim(self):
        return len(self._aval.shape)

    dim = ndim

    @property
    def size(self):
        return int(np.prod(self._aval.shape)) if self._aval.shape else 1

    @property
    def dtype(self) -> dtypes.DType:
        return dtypes.from_np(self._aval.dtype)

    def __deepcopy__(self, memo):
        return self.detach().__deepcopy__(memo)


jax.tree_util.register_pytree_node(
    DeferredTensor, _tensor_flatten, _tensor_unflatten)


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """paddle.to_tensor analog (ref: python/paddle/tensor/creation.py)."""
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)

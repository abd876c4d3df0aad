"""nn.Layer base class.

Analog of the reference's Layer (/root/reference/python/paddle/nn/layer/
layers.py:331): parameter/sublayer registration via __setattr__, state_dict
with buffers, train/eval mode, forward pre/post hooks, to()/astype.
"""
from __future__ import annotations

import contextlib
import functools
import itertools
import threading
from collections import OrderedDict
from typing import Callable, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core import dtype as dtypes
from ..core.tensor import Tensor
from ..observability import perf as _pf


class Parameter(Tensor):
    """Trainable tensor (stop_gradient=False by default)."""

    def __init__(self, data, dtype=None, name=None, trainable=True):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, v):
        self.stop_gradient = not v

    def __repr__(self):
        return "Parameter " + super().__repr__()


jax.tree_util.register_pytree_node(
    Parameter,
    lambda p: ((p._data,), (p.stop_gradient,)),
    lambda aux, ch: Tensor._wrap(ch[0], stop_gradient=aux[0]),
)

_hook_id = itertools.count()

class _Tracing(threading.local):
    """`depth` > 0 while this thread traces a program
    (`jit._functional_params`, which TrainStep, to_static, recompute,
    generate and the engine's program builders all enter, counts it up
    and down). Layer.__call__ then runs forward under
    `jax.named_scope(<the name its parent holds it by>)`, so every
    operation's `op_name` carries a path like `gpt/layers/3/attn` that
    the profiler's trace hands back (benchmarks/harness/
    trace_scopes.py), and the seconds the trace spends in it go to the
    program's `compile_record(family)["trace_by_scope"]`. Per thread: an
    engine prewarm tracing on one thread leaves eager calls on the
    others as they are. Never on the eager path: one attribute read
    there."""
    depth = 0


_TRACING = _Tracing()


class LazyGuard:
    """Inside it a Layer's parameters are placeholders of their shape and
    type (zeros), not draws: for a model whose weights are loaded next
    (`set_state_dict`, a harness's arrays). A draw that is thrown away
    costs a compile a shape on a cold TPU (30 s at 45 leaves of 1 B
    parameters). ref: python/paddle/lazy_init.py (LazyGuard).

    `place(shape) -> a jax sharding or None`: where a placeholder of
    that shape is made. A model that only fits laid over a mesh is built
    so, shard by shard: its leaves whole would all sit on the default
    device."""
    on = False
    place = None

    def __init__(self, place=None):
        self._place = place

    def __enter__(self):
        self._placed, LazyGuard.place = LazyGuard.place, self._place
        self._before, LazyGuard.on = LazyGuard.on, True
        return self

    def __exit__(self, *exc):
        LazyGuard.on, LazyGuard.place = self._before, self._placed


_NO_SCOPE = contextlib.nullcontext()


@contextlib.contextmanager
def _scope(name: str):
    with jax.named_scope(name), _pf.trace_timed(name):
        yield


def traced_scope(name: str):
    """`jax.named_scope(name)` while a program is being traced (and the
    trace's seconds under that name, `perf.trace_timed`), else a shared
    no-op: for what is not a Layer (a tied head, a method)."""
    return _scope(name) if _TRACING.depth else _NO_SCOPE


def _is_holder(layer: "Layer") -> bool:
    # a LayerList, a LayerDict: no forward of its own, so never called
    return type(layer).forward is Layer.forward


def _hold(parent: "Layer", name: str, child: "Layer") -> None:
    """Leave on `child` the name `parent` holds it by; a layer held
    twice keeps its first. A holder is never called, so the layers it
    holds carry its name before theirs (`layers/3`), whether they join
    it before or after it is attached itself."""
    if "_scope_name" in child.__dict__:
        return
    if _is_holder(parent) and "_scope_name" in parent.__dict__:
        name = f"{parent.__dict__['_scope_name']}/{name}"
    _set_scope_name(child, None, name)


def _set_scope_name(layer: "Layer", old, new: str) -> None:
    layer.__dict__["_scope_name"] = new
    if _is_holder(layer):
        for key, sub in layer._sub_layers.items():
            was = f"{old}/{key}" if old else key
            if isinstance(sub, Layer) and \
                    sub.__dict__.get("_scope_name") == was:
                _set_scope_name(sub, was, f"{new}/{key}")


class HookRemoveHelper:
    def __init__(self, hooks, hid):
        self._hooks, self._hid = hooks, hid

    def remove(self):
        self._hooks.pop(self._hid, None)


def _built(init):
    """A constructor inside the set-up phase `build.model`
    (`perf.setup_record`): the outermost constructor's seconds are the
    model's, the sublayers' count as entries."""
    @functools.wraps(init)
    def __init__(self, *args, **kwargs):
        with _pf.setup_phase("build.model"):
            init(self, *args, **kwargs)
    return __init__


class Layer:
    def __init_subclass__(cls, **kwargs):
        super().__init_subclass__(**kwargs)
        if "__init__" in cls.__dict__:
            cls.__init__ = _built(cls.__dict__["__init__"])

    @_built
    def __init__(self, name_scope=None, dtype="float32"):
        self.training = True
        self._dtype = dtype
        self._parameters = OrderedDict()
        self._sub_layers = OrderedDict()
        self._buffers = OrderedDict()
        self._non_persistable_buffer_names = set()
        self._forward_pre_hooks = OrderedDict()
        self._forward_post_hooks = OrderedDict()
        self._name_scope = name_scope or self.__class__.__name__.lower()

    # ------------- registration -------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError(
                    "call super().__init__() before assigning parameters")
            for d in (layers, buffers):
                if d is not None:
                    d.pop(name, None)
            params[name] = value
            self.__dict__.pop(name, None)
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError(
                    "call super().__init__() before assigning sublayers")
            for d in (params, buffers):
                if d is not None:
                    d.pop(name, None)
            layers[name] = value
            self.__dict__.pop(name, None)
            _hold(self, name, value)
        else:
            if params is not None and name in params:
                if value is None:
                    params.pop(name)
                    object.__setattr__(self, name, value)
                    return
            if layers is not None and name in layers and value is None:
                layers.pop(name)
                object.__setattr__(self, name, value)
                return
            if buffers is not None and name in buffers:
                if value is None or isinstance(value, Tensor):
                    buffers[name] = value
                    return
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        # only called when normal lookup fails
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(
            f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_sub_layers", "_buffers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def add_parameter(self, name, parameter):
        if parameter is not None and not isinstance(parameter, Parameter):
            parameter = Parameter(parameter)
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name, sublayer):
        self._sub_layers[str(name)] = sublayer
        if isinstance(sublayer, Layer):
            _hold(self, str(name), sublayer)
        return sublayer

    def register_buffer(self, name, tensor, persistable=True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    def create_parameter(self, shape, dtype=None, attr=None,
                         is_bias=False, default_initializer=None):
        from .initializer import Constant, XavierUniform, get_initializer
        dtype = dtype or self._dtype
        init = None
        name = None
        if attr is not None and attr is not False:
            from .param_attr import ParamAttr
            if isinstance(attr, ParamAttr):
                init = attr.initializer
                name = attr.name
            elif callable(attr):
                init = attr
        if init is None:
            init = default_initializer
        if init is None:
            init = Constant(0.0) if is_bias else XavierUniform()
        with _pf.setup_phase("build.params") as phase:
            shape = tuple(int(s) for s in shape)
            if LazyGuard.on:
                data = jnp.zeros(
                    shape, dtypes.to_jnp(dtype),
                    device=LazyGuard.place and LazyGuard.place(shape))
            else:
                data = init(shape, dtypes.to_jnp(dtype))
            phase.count(params=1, bytes=int(getattr(data, "nbytes", 0)))
        p = Parameter(data, name=name)
        return p

    def create_tensor(self, dtype=None, name=None):
        return Tensor(jnp.zeros((), dtypes.to_jnp(dtype or self._dtype)),
                      name=name)

    # ------------- iteration -------------
    def named_parameters(self, prefix="", include_sublayers=True):
        seen = set()
        for name, p in self._parameters.items():
            if p is not None and id(p) not in seen:
                seen.add(id(p))
                yield (f"{prefix}.{name}" if prefix else name), p
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, p in layer.named_parameters(sub_prefix):
                    yield n, p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(
            include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        for name, b in self._buffers.items():
            if b is not None:
                yield (f"{prefix}.{name}" if prefix else name), b
        if include_sublayers:
            for lname, layer in self._sub_layers.items():
                if layer is None:
                    continue
                sub_prefix = f"{prefix}.{lname}" if prefix else lname
                for n, b in layer.named_buffers(sub_prefix):
                    yield n, b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(
            include_sublayers=include_sublayers)]

    def children(self):
        for _, l in self.named_children():
            yield l

    def named_children(self):
        for name, l in self._sub_layers.items():
            if l is not None:
                yield name, l

    def sublayers(self, include_self=False):
        out = [self] if include_self else []
        for l in self.children():
            out.extend(l.sublayers(include_self=True))
        return out

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, l in self.named_children():
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield from l.named_sublayers(sub_prefix, include_self=True)

    def apply(self, fn):
        for l in self.children():
            l.apply(fn)
        fn(self)
        return self

    # ------------- state dict -------------
    def state_dict(self, destination=None, include_sublayers=True,
                   structured_name_prefix="", use_hook=True):
        dest = destination if destination is not None else OrderedDict()
        for n, p in self.named_parameters(structured_name_prefix.rstrip(".")):
            dest[n] = p
        for n, b in self.named_buffers(structured_name_prefix.rstrip(".")):
            short = n.split(".")[-1]
            # find owning layer to check persistability
            dest[n] = b
        # drop non-persistable buffers
        for lname, layer in self.named_sublayers("", include_self=True):
            for bname in layer._non_persistable_buffer_names:
                full = f"{lname}.{bname}" if lname else bname
                dest.pop(full, None)
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        own = self.state_dict()
        missing, unexpected = [], []
        for k, v in state_dict.items():
            if k in own:
                tgt = own[k]
                arr = v._data if isinstance(v, Tensor) else jnp.asarray(
                    np.asarray(v))
                tgt._set_data(arr.astype(tgt._data.dtype).reshape(
                    tgt._data.shape))
            else:
                unexpected.append(k)
        for k in own:
            if k not in state_dict:
                missing.append(k)
        return missing, unexpected

    load_dict = set_state_dict
    set_dict = set_state_dict

    # ------------- mode -------------
    def train(self):
        self.training = True
        for l in self.children():
            l.train()
        return self

    def eval(self):
        self.training = False
        for l in self.children():
            l.eval()
        return self

    # ------------- hooks -------------
    def register_forward_pre_hook(self, hook):
        hid = next(_hook_id)
        self._forward_pre_hooks[hid] = hook
        return HookRemoveHelper(self._forward_pre_hooks, hid)

    def register_forward_post_hook(self, hook):
        hid = next(_hook_id)
        self._forward_post_hooks[hid] = hook
        return HookRemoveHelper(self._forward_post_hooks, hid)

    # ------------- call -------------
    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            res = hook(self, inputs)
            if res is not None:
                inputs = res if isinstance(res, tuple) else (res,)
        if _TRACING.depth:
            with _scope(self.scope_name()):
                out = self.forward(*inputs, **kwargs)
        else:
            out = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            res = hook(self, inputs, out)
            if res is not None:
                out = res
        return out

    def forward(self, *inputs, **kwargs):
        raise NotImplementedError

    # ------------- dtype / device movement -------------
    def _transform(self, fn):
        for _, p in self.named_parameters():
            p._set_data(fn(p._data))
        for _, b in self.named_buffers():
            if isinstance(b, Tensor):
                b._set_data(fn(b._data))
        return self

    def to(self, device=None, dtype=None, blocking=None):
        if dtype is not None:
            jdt = dtypes.to_jnp(dtype)

            def cast_float(a):
                if jnp.issubdtype(a.dtype, jnp.floating):
                    return a.astype(jdt)
                return a

            self._transform(cast_float)
            self._dtype = dtypes.to_dtype(dtype).name
        if device is not None:
            from ..core.device import Place, parse_device
            place = (device if isinstance(device, Place)
                     else parse_device(str(device)))
            self._transform(lambda a: jax.device_put(a, place.jax_device()))
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    def full_name(self):
        return self._name_scope

    def scope_name(self):
        """The `jax.named_scope` this layer's forward is traced under:
        the name its parent holds it by, or its class's for a root."""
        return self.__dict__.get("_scope_name") or self._name_scope

    def extra_repr(self):
        return ""

    def __repr__(self):
        lines = []
        for name, child in self.named_children():
            child_repr = repr(child).replace("\n", "\n  ")
            lines.append(f"  ({name}): {child_repr}")
        extra = self.extra_repr()
        main = f"{type(self).__name__}({extra}" + ("" if not lines else "")
        if lines:
            return main + "\n" + "\n".join(lines) + "\n)"
        return main + ")"

"""Custom C++ op loading (paddle.utils.cpp_extension parity, C6).

The reference JIT-compiles user C++/CUDA against its PD_BUILD_OP ABI
(/root/reference/python/paddle/utils/cpp_extension/extension_utils.py,
paddle/phi/api/ext/op_meta_info.h). The TPU-native split is:

  * DEVICE custom kernels are Pallas — that IS the plugin ABI for the
    accelerator (kernels/pallas/*), no C++ device path exists on TPU.
  * HOST custom ops (pre/post-processing, tokenizers, CPU math the
    framework lacks) compile here with g++ into a shared object and run
    inside the XLA program via `jax.pure_callback` — the host-callback
    analog of the reference's CPU custom kernels.

C ABI (v1, documented contract):

    extern "C" void <op_name>(
        const void* const* inputs,     // n_inputs data pointers
        const long long*  sizes,       // n_inputs element counts
        int               n_inputs,
        void*             output,      // preallocated
        long long         out_elems);

dtype is carried python-side (all inputs and the output share the first
input's dtype). Gradients: host callbacks are opaque to autograd — wrap
the returned op in `paddle_tpu.autograd.PyLayer` to attach a custom
backward, exactly like the reference's custom-grad story.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .runtime_env import CHECKOUT

__all__ = ["load", "CppExtension", "CUDAExtension", "get_build_directory"]


def get_build_directory():
    """`PT_EXTENSION_DIR`, else `<checkout>/.pt_extensions` (gitignored):
    a fixed place next to the sources, so that what is built belongs to
    this checkout and nothing is left in the system temp directory."""
    d = os.environ.get("PT_EXTENSION_DIR") or os.path.join(
        CHECKOUT, ".pt_extensions")
    os.makedirs(d, exist_ok=True)
    return d


def _compile(name, sources, extra_cflags, build_directory, verbose,
             ldflags=()):
    build_dir = build_directory or get_build_directory()
    tag = hashlib.sha256()
    for s in sources:
        with open(s, "rb") as f:
            tag.update(f.read())
    tag.update(" ".join(list(extra_cflags or []) + list(ldflags)).encode())
    lib_path = os.path.join(build_dir, f"{name}_{tag.hexdigest()[:12]}.so")
    if not os.path.exists(lib_path):
        # built beside its final name and renamed into place: another
        # process compiling the same sources never loads a partial file
        tmp_path = f"{lib_path}.{os.getpid()}.tmp"
        # -l libraries must FOLLOW the objects that reference them
        cmd = (["g++", "-O3", "-shared", "-fPIC", "-std=c++17"]
               + list(extra_cflags or []) + list(sources)
               + list(ldflags) + ["-o", tmp_path])
        if verbose:
            print("cpp_extension:", " ".join(cmd))
        try:
            subprocess.run(cmd, check=True, capture_output=not verbose)
        except FileNotFoundError as e:
            raise RuntimeError(
                f"g++ not found: {name} is compiled from "
                f"{', '.join(sources)} on first use and there is no "
                "other implementation") from e
        os.replace(tmp_path, lib_path)
    return lib_path


class CustomOpModule:
    """Holds the dlopened library; attribute access returns wrapped ops."""

    def __init__(self, name, lib_path, op_names):
        self._name = name
        self._lib = ctypes.CDLL(lib_path)
        self._ops = {}
        for op in op_names:
            self._ops[op] = self._make_op(op)

    def _make_op(self, op_name):
        cfn = getattr(self._lib, op_name)
        cfn.restype = None
        cfn.argtypes = [ctypes.POINTER(ctypes.c_void_p),
                        ctypes.POINTER(ctypes.c_longlong),
                        ctypes.c_int, ctypes.c_void_p, ctypes.c_longlong]

        def _host_call(shape, dtype):
            def call(*arrays):
                arrays = [np.ascontiguousarray(a) for a in arrays]
                out = np.empty(shape, dtype)
                ptrs = (ctypes.c_void_p * len(arrays))(
                    *[a.ctypes.data_as(ctypes.c_void_p).value
                      for a in arrays])
                sizes = (ctypes.c_longlong * len(arrays))(
                    *[a.size for a in arrays])
                cfn(ptrs, sizes, len(arrays),
                    out.ctypes.data_as(ctypes.c_void_p), out.size)
                return out
            return call

        def op(*tensors, out_shape=None, out_dtype=None):
            datas = [t._data if isinstance(t, Tensor) else jnp.asarray(t)
                     for t in tensors]
            shape = (tuple(out_shape) if out_shape is not None
                     else tuple(datas[0].shape))
            dtype = np.dtype(out_dtype) if out_dtype is not None \
                else np.dtype(str(datas[0].dtype))
            aval = jax.ShapeDtypeStruct(shape, dtype)
            res = jax.pure_callback(_host_call(shape, dtype), aval, *datas,
                                    vmap_method="sequential")
            # host callbacks are opaque to autograd; custom backward goes
            # through PyLayer (see module docstring)
            return Tensor._wrap(res, stop_gradient=True)

        op.__name__ = op_name
        return op

    def __getattr__(self, item):
        ops = object.__getattribute__(self, "_ops")
        if item in ops:
            return ops[item]
        raise AttributeError(
            f"custom-op module {self._name!r} has no op {item!r}; "
            f"loaded ops: {sorted(ops)}")


def _discover_ops(sources):
    """Exported op names: every `extern "C"` function following the v1
    signature, declared with PT_EXPORT_OP(<name>) or parsed from an
    extern "C" void <name>( pattern."""
    import re
    names = []
    pat = re.compile(
        r'(?:PT_EXPORT_OP\s*\(\s*(\w+)\s*\))|'
        r'(?:extern\s+"C"\s+void\s+(\w+)\s*\()')
    for s in sources:
        with open(s) as f:
            for m in pat.finditer(f.read()):
                names.append(m.group(1) or m.group(2))
    return list(dict.fromkeys(names))


def load(name, sources, extra_cflags=None, extra_cuda_cflags=None,
         extra_ldflags=None, extra_include_paths=None,
         build_directory=None, verbose=False):
    """Compile C++ sources and expose their ops (ref API:
    python/paddle/utils/cpp_extension/cpp_extension.py load)."""
    if extra_cuda_cflags:
        raise RuntimeError(
            "CUDA custom ops are not supported on TPU; write device "
            "kernels in Pallas (paddle_tpu/kernels/pallas) instead")
    cflags = list(extra_cflags or [])
    for inc in extra_include_paths or []:
        cflags.append(f"-I{inc}")
    lib_path = _compile(name, sources, cflags, build_directory, verbose,
                        ldflags=list(extra_ldflags or []))
    op_names = _discover_ops(sources)
    if not op_names:
        raise RuntimeError(
            f"no extern \"C\" v1-ABI ops found in {sources}; see "
            "paddle_tpu.utils.cpp_extension docstring for the contract")
    return CustomOpModule(name, lib_path, op_names)


class CppExtension:
    """setup()-style spec shim; `load` is the supported JIT path."""

    def __init__(self, sources, *args, **kwargs):
        self.sources = sources
        self.kwargs = kwargs


def CUDAExtension(*args, **kwargs):
    raise RuntimeError(
        "CUDAExtension is not supported on TPU; device kernels are "
        "Pallas (see paddle_tpu/kernels/pallas) and host ops use "
        "CppExtension/load")

"""paddle_tpu: a TPU-native deep learning framework with the capability
surface of PaddlePaddle (reference surveyed in /root/repo/SURVEY.md).

Eager tensors execute op-by-op on TPU through JAX/XLA; `loss.backward()`
drives a tape autograd engine; `paddle_tpu.jit` traces whole steps to a
single XLA executable; `paddle_tpu.distributed` provides mesh-based
DP/TP/SP/PP/EP + ZeRO sharding lowered to GSPMD + ICI collectives.
"""
from __future__ import annotations

import time as _time

_T_IMPORT = _time.perf_counter()     # the set-up phase `import` (below)

__version__ = "0.1.0"

# ---- core ----
from .core.dtype import (  # noqa: F401
    DType, bool_, uint8, int8, int16, int32, int64, float16, bfloat16,
    float32, float64, complex64, complex128, float8_e4m3, float8_e5m2,
)
from .core.dtype import bool_ as bool  # noqa: F401
from .core.tensor import Tensor, to_tensor  # noqa: F401
from .core.device import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPlace, Place, set_device, get_device,
    is_compiled_with_cuda, is_compiled_with_tpu, device_count,
)
from .core.generator import seed, Generator, default_generator  # noqa: F401
from .core.flags import set_flags, get_flags  # noqa: F401
from .core.dtype import iinfo, finfo  # noqa: F401
from . import hub  # noqa: F401

# ---- ops (also patches Tensor methods) ----
from .ops import *  # noqa: F401,F403
from .ops import cast, split, slice, unique  # noqa: F401

# ---- autograd ----
from .autograd import no_grad, enable_grad, set_grad_enabled, grad  # noqa: F401
from .autograd import is_grad_enabled  # noqa: F401

# ---- subpackages ----
from . import autograd  # noqa: F401
from . import nn  # noqa: F401
from .nn.layer import LazyGuard  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import device  # noqa: F401
from . import metric  # noqa: F401
from . import vision  # noqa: F401
from . import distribution  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler  # noqa: F401
from . import inference  # noqa: F401
from . import onnx  # noqa: F401  (documented exclusion: raises w/ guidance)
from . import utils  # noqa: F401
from . import callbacks  # noqa: F401
from .framework_io import save, load  # noqa: F401
from .tensor_array import (  # noqa: F401
    create_array, array_write, array_read, array_length,
)
from .hapi.model_api import Model, summary  # noqa: F401
from .observability import perf as _perf

# top to bottom of this file: `perf.setup_record()["import"]`. It holds
# `jax`'s import when the caller has not imported it first.
_perf.setup_since("import", _T_IMPORT)


def __getattr__(name):
    # heavy/cyclic subpackages resolved lazily
    if name == "distributed":
        import importlib
        mod = importlib.import_module(".distributed", __name__)
        globals()["distributed"] = mod
        return mod
    if name == "sparse":
        import importlib
        mod = importlib.import_module(".sparse", __name__)
        globals()["sparse"] = mod
        return mod
    if name in ("fft", "signal", "quantization", "geometric", "audio", "text",
                "resilience", "observability", "embedding"):
        import importlib
        mod = importlib.import_module("." + name, __name__)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def disable_static():  # API-compat: eager is the default
    return None


def enable_static():
    from .static import _enable_static_mode
    _enable_static_mode()


def in_dynamic_mode():
    from .static import _in_static_mode
    return not _in_static_mode()

"""paddle_tpu.device (ref: python/paddle/device/__init__.py — set_device:265,
Stream:617/Event:445). On TPU, streams/events are owned by XLA; the classes
keep API parity and expose synchronization via jax block_until_ready."""
from __future__ import annotations

import jax

from . import memory  # noqa: F401
from ..core.device import (  # noqa: F401
    set_device, get_device, get_place, Place, CPUPlace, TPUPlace, CUDAPlace,
    device_count, is_compiled_with_cuda, is_compiled_with_tpu,
)


def get_all_device_type():
    return sorted({d.platform for d in jax.devices()})


def get_available_device():
    return [f"{d.platform}:{d.id}" for d in jax.devices()]


def synchronize(device=None):
    # XLA queues are flushed by blocking on a trivial transfer
    import jax.numpy as jnp
    jnp.zeros(()).block_until_ready()


class Stream:
    """API-parity stream object; XLA owns real stream assignment
    (the reference's StreamAnalyzer role is inside the compiler here)."""

    def __init__(self, device=None, priority=2):
        self.device = device

    def synchronize(self):
        synchronize()

    def wait_event(self, event):
        pass

    def wait_stream(self, stream):
        pass

    def record_event(self, event=None):
        return event or Event()


class Event:
    def __init__(self, device=None, enable_timing=False, blocking=False):
        self.device = device

    def record(self, stream=None):
        pass

    def query(self):
        return True

    def synchronize(self):
        synchronize()


def current_stream(device=None):
    return Stream(device)


def set_stream(stream):
    return stream


class stream_guard:
    def __init__(self, stream):
        self.stream = stream

    def __enter__(self):
        return self.stream

    def __exit__(self, *exc):
        return False


class cuda:  # namespace shim: paddle.device.cuda.*
    """ref: python/paddle/device/cuda/__init__.py — on TPU the stats
    come from PJRT via paddle_tpu.device.memory."""

    @staticmethod
    def synchronize(device=None):
        synchronize()

    max_memory_allocated = staticmethod(memory.max_memory_allocated)
    memory_allocated = staticmethod(memory.memory_allocated)
    memory_reserved = staticmethod(memory.memory_reserved)
    max_memory_reserved = staticmethod(memory.max_memory_reserved)
    reset_max_memory_allocated = staticmethod(
        memory.reset_max_memory_allocated)
    reset_peak_memory_stats = staticmethod(memory.reset_peak_memory_stats)
    empty_cache = staticmethod(memory.empty_cache)
    memory_stats = staticmethod(memory.memory_stats)

    @staticmethod
    def device_count():
        return device_count()


cuda.Stream = Stream
cuda.Event = Event


# ======================= vendor plugins (C5) =======================
# The reference's CustomDevice path loads vendor runtimes via a C plugin
# ABI (/root/reference/paddle/phi/backends/custom/custom_device.cc,
# device/__init__.py get_all_custom_device_type). The TPU-native analog
# IS PJRT: a vendor ships a PJRT plugin .so and registers it here; every
# op then lowers through StableHLO to that backend with no per-vendor
# kernel work in this framework — the plugin boundary sits below the
# compiler instead of at the kernel registry.

_registered_plugins = {}


def register_pjrt_plugin(platform_name, library_path, options=None,
                         priority=400, make_default=False):
    """Register a vendor PJRT plugin (CustomDevice analog).

    platform_name: backend name as it will appear in device lists;
    library_path: path to the vendor's PJRT plugin shared object.
    """
    from jax._src import xla_bridge
    if xla_bridge.backends_are_initialized():
        import warnings
        warnings.warn(
            "register_pjrt_plugin called after jax backends initialized: "
            "the plugin registers but this process's device list is "
            "already fixed. Register before the first jax computation "
            "(or set PJRT_NAMES_AND_LIBRARY_PATHS before launch).",
            RuntimeWarning, stacklevel=2)
    try:
        xla_bridge.register_plugin(platform_name,
                                   library_path=str(library_path),
                                   options=options, priority=priority)
    except Exception as e:
        raise RuntimeError(
            f"PJRT plugin {platform_name!r} failed to load from "
            f"{library_path}: {e}") from e
    _registered_plugins[platform_name] = str(library_path)
    if make_default:
        jax.config.update("jax_platforms", platform_name)
    return platform_name


def get_all_custom_device_type():
    """Registered vendor (non-builtin) backend names
    (ref: device/__init__.py:get_all_custom_device_type)."""
    return sorted(_registered_plugins)


def get_available_custom_device():
    out = []
    for name in _registered_plugins:
        try:
            out.extend(f"{name}:{d.id}" for d in jax.devices(name))
        except RuntimeError:
            pass  # registered but not initializable on this host
    return out


def is_compiled_with_custom_device(device_type):
    """Parity API: with PJRT the framework needs no per-vendor compile —
    support is a runtime plugin question, so this reports registration."""
    return device_type in _registered_plugins

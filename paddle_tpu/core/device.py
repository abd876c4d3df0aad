"""Device / place abstraction.

TPU-native analog of the reference's Place zoo
(/root/reference/paddle/fluid/pybind/place.cc — CPUPlace/CUDAPlace/XPUPlace/
CustomPlace) and paddle.device.set_device
(/root/reference/python/paddle/device/__init__.py:265).

Here a Place names a jax device. The default place follows jax's default
backend; `set_device("tpu:0")` pins eager op outputs to that device and
raises when the process has no such device.
"""
from __future__ import annotations

import jax


class Place:
    __slots__ = ("device_type", "device_id")

    def __init__(self, device_type: str, device_id: int = 0):
        self.device_type = device_type
        self.device_id = device_id

    def __repr__(self):
        return f"Place({self.device_type}:{self.device_id})"

    def __eq__(self, other):
        return (
            isinstance(other, Place)
            and self.device_type == other.device_type
            and self.device_id == other.device_id
        )

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def jax_device(self):
        return jax.devices(self.device_type)[self.device_id]

    def is_cpu_place(self):
        return self.device_type == "cpu"

    def is_tpu_place(self):
        return self.device_type == "tpu"


class CPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("cpu", device_id)


class TPUPlace(Place):
    def __init__(self, device_id: int = 0):
        super().__init__("tpu", device_id)


# CUDAPlace alias kept for API familiarity: maps to the accelerator place.
CUDAPlace = TPUPlace

_current_place: Place | None = None


def _default_device_type() -> str:
    return jax.default_backend()


def get_device() -> str:
    p = get_place()
    return f"{p.device_type}:{p.device_id}"


def get_place() -> Place:
    global _current_place
    if _current_place is None:
        _current_place = Place(_default_device_type(), 0)
    return _current_place


_PLATFORMS = ("cpu", "tpu", "gpu")


def parse_device(device: str) -> Place:
    """"<platform>[:index]" -> Place, for a platform jax knows ("cpu",
    "tpu", "gpu"). A platform or index this process does not have is
    an error, never another device."""
    kind, _, idx = device.lower().partition(":")
    idx = int(idx) if idx else 0
    if kind not in _PLATFORMS:
        raise ValueError(
            f"unknown device {device!r}: expected one of {_PLATFORMS}")
    try:
        n = len(jax.devices(kind))
    except RuntimeError as e:
        raise RuntimeError(
            f"device {device!r}: this process has no {kind!r} backend "
            f"(default backend: {jax.default_backend()})") from e
    if not 0 <= idx < n:
        raise ValueError(f"device {device!r}: {kind!r} has {n} device(s)")
    return Place(kind, idx)


def set_device(device) -> Place:
    """Accepts a Place or a string parse_device() accepts."""
    global _current_place
    _current_place = (device if isinstance(device, Place)
                      else parse_device(device))
    return _current_place


def is_compiled_with_cuda() -> bool:  # API-compat shim
    return False


def is_compiled_with_tpu() -> bool:
    return _default_device_type() == "tpu"


def device_count() -> int:
    return len(jax.devices())

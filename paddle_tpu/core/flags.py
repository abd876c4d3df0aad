"""Runtime flag registry.

Analog of the reference's gflags-workalike
(/root/reference/paddle/utils/flags_native.h:112 PD_DEFINE_VARIABLE,
/root/reference/paddle/phi/core/flags.cc) plus the Python surface
paddle.set_flags/get_flags
(/root/reference/python/paddle/base/framework.py:64,89).

Flags are typed, registered as data, and initialisable from FLAGS_* env vars.
"""
from __future__ import annotations

import os
from typing import Any, Callable, Dict


class _Flag:
    __slots__ = ("name", "value", "default", "type", "help", "on_change")

    def __init__(self, name, default, typ, help_str, on_change=None):
        self.name = name
        self.default = default
        self.value = default
        self.type = typ
        self.help = help_str
        self.on_change = on_change


_REGISTRY: Dict[str, _Flag] = {}


def define_flag(name: str, default: Any, help_str: str = "",
                on_change: Callable[[Any], None] | None = None):
    typ = type(default)
    flag = _Flag(name, default, typ, help_str, on_change)
    _REGISTRY[name] = flag
    env = os.environ.get(name)
    if env is not None:
        set_flags({name: env})
    return flag


def _coerce(flag: _Flag, value):
    if flag.type is bool and isinstance(value, str):
        return value.lower() in ("1", "true", "yes", "on")
    return flag.type(value)


def set_flags(flags: Dict[str, Any]):
    for name, value in flags.items():
        if name not in _REGISTRY:
            raise ValueError(f"unknown flag {name!r}")
        flag = _REGISTRY[name]
        flag.value = _coerce(flag, value)
        if flag.on_change is not None:
            flag.on_change(flag.value)


def get_flags(flags):
    if isinstance(flags, str):
        flags = [flags]
    out = {}
    for name in flags:
        if name not in _REGISTRY:
            raise ValueError(f"unknown flag {name!r}")
        out[name] = _REGISTRY[name].value
    return out


def flag_value(name: str):
    return _REGISTRY[name].value


# --- core flags (mirroring the reference's most-used ones) ---
define_flag("FLAGS_check_nan_inf", False,
            "post-op NaN/Inf sanitizer (ref: phi/core/flags.cc:74)")
define_flag("FLAGS_benchmark", False, "benchmark mode: sync after each op")
define_flag("FLAGS_fast_bn_stats", False,
            "one-pass batch-norm statistics (running-mean pivot): one "
            "HBM read instead of 2-3 per BN during training. Bit-exact "
            "for normalized "
            "activations; loses f32 precision only if a channel's "
            "|mean| exceeds ~1e3 x its std while the running mean is "
            "still far from the data (cold start). Default off = "
            "exact two-pass stats (reference cuDNN parity).",
            on_change=lambda v: _bump_trace_epoch())

# epoch folded into every trace-cache key (registry exec cache,
# to_static program cache, graph-break region signatures): bumping it
# makes executables that baked a stale flag value unreachable
trace_epoch = [0]


def _bump_trace_epoch():
    """Flag-dependent op bodies bake the flag value at trace time;
    flipping such a flag must invalidate every cached trace — the
    registry's per-op executables AND whole-program caches (to_static
    / TrainStep / staged regions) whose traces inlined the op body."""
    trace_epoch[0] += 1
    import sys
    reg = sys.modules.get("paddle_tpu.ops.registry")
    if reg is not None:
        for opdef in reg.OPS.values():
            opdef.exec_cache.clear()
define_flag("FLAGS_eager_op_jit", True,
            "cache per-op jitted executables for eager dispatch")
define_flag("FLAGS_seed", 0, "global RNG seed")
define_flag("FLAGS_allocator_strategy", "pjrt",
            "memory strategy (informational; PJRT owns device memory)")
define_flag("FLAGS_log_level", 0, "framework vlog level")
define_flag("FLAGS_watchdog_timeout_s", 0.0,
            "hang watchdog: dump thread stacks when a blocking region "
            "(train step / checkpoint) exceeds this many seconds; 0 off")
define_flag("FLAGS_watchdog_abort", False,
            "hang watchdog: os._exit(124) after the dump so the "
            "elastic layer restarts the worker")

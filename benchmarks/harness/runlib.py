"""What every run shares: the device check, the watch for compiles and
tuning sweeps inside the window, the profiler slice, the comparison's
report and the result line."""
from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

clock = time.perf_counter


class NoChip(SystemExit):
    pass


def device_info():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(chips: int) -> dict:
    """A measurement needs the chips the cell asks for; without them the
    run ends with no result line."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"benchmark: the cell needs {chips} TPU chip(s), JAX "
                     f"reports {info}: no measurement without them")
    return info


def memory_peak_bytes(programs=()) -> int:
    """Peak on the fullest chip. The runtime's `peak_bytes_in_use` counts
    live buffers and not the temporaries of a running program (on the
    chip the GPT-2 small step reads the same 2.26 GB at 16 rows and at 64,
    PERF.md section 6), so the window's programs add their own: the bytes
    in use now, with the window's state still alive, plus the largest
    `memory_analysis().temp_size_in_bytes` among `programs` (the compiled
    programs the window ran)."""
    import jax
    temp = 0
    for prog in programs:
        try:
            temp = max(temp, int(prog.memory_analysis().temp_size_in_bytes))
        except (AttributeError, TypeError):     # not an AOT-compiled one
            continue
    best = 0
    for d in jax.local_devices():
        st = d.memory_stats() or {}
        best = max(best, st.get("peak_bytes_in_use", 0),
                   st.get("bytes_in_use", 0) + temp)
    return int(best)


class WindowWatch:
    """Counts what must not happen inside a measured window: a compile
    (any program JAX builds or loads) and a kernel tuning sweep."""

    _COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                       "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring
        self.compiles = 0
        self._armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _seconds, **_kw):
        if self._armed and event in self._COMPILE_EVENTS:
            self.compiles += 1

    def arm(self):
        from paddle_tpu.kernels.pallas import autotune
        self.setup_sweeps = autotune.drain_sweeps()
        self.compiles, self._armed = 0, True

    def disarm(self) -> dict:
        from paddle_tpu.kernels.pallas import autotune
        self._armed = False
        return {"compiles_in_window": self.compiles,
                "sweeps_in_window": len(autotune.drain_sweeps())}


class TraceSlice:
    """Profiles `for_s` seconds of the window, starting `after_s` in:
    traces are large and tracing slows the host, so a traced run
    profiles a slice. `poll(now)` is called at step boundaries."""

    def __init__(self, enabled: bool, out_dir: str, after_s: float,
                 for_s: float):
        self.dir = out_dir
        self.after_s, self.for_s = after_s, for_s
        self.state = "off" if not enabled else "waiting"
        self.t_start = self.t_stop = None

    def poll(self, since_window_start: float):
        import jax
        if self.state == "waiting" and since_window_start >= self.after_s:
            shutil.rmtree(self.dir, ignore_errors=True)
            os.makedirs(self.dir, exist_ok=True)
            jax.profiler.start_trace(self.dir)
            self.state, self.t_start = "on", clock()
        elif self.state == "on" and \
                clock() - self.t_start >= self.for_s:
            self.finish()

    def finish(self):
        import jax
        if self.state == "on":
            self.t_stop = clock()
            jax.profiler.stop_trace()
            self.state = "done"

    def xplane(self):
        if self.state != "done":
            return None
        found = glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb"))
        return max(found, key=os.path.getmtime) if found else None


def annotate(name: str):
    """A host span on the profiler's clock, so that an idle gap of the
    device can be named by what the host was doing."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def percentile(values, q: float):
    """Linear-interpolated percentile of all the values (no trimming)."""
    xs = sorted(values)
    if not xs:
        return None
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def judge(compared: dict) -> bool:
    """compared: {name: {"value": x, "limit": y}}; correct when every
    value is finite and within its limit."""
    ok = True
    for item in compared.values():
        v = item["value"]
        ok = ok and v is not None and v == v and v <= item["limit"]
    return ok


def emit(*, correct, attempted, failed, metrics, device, breakdown=None,
         compared=None, notes=None):
    """The numbers compared beside their limits as the last lines of
    standard error, then the result as the last line of standard output."""
    compared = compared or {}
    if notes:
        print(json.dumps({"notes": notes}), flush=True)
    sys.stdout.flush()
    for name, item in compared.items():
        print(f"compared {name} value={item['value']!r} "
              f"limit={item['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    print(json.dumps(line), flush=True)

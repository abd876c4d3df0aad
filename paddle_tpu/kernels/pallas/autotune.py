"""Pallas block-size autotune cache.

Match for the reference's per-shape algorithm-selection cache
(ref: paddle/phi/kernels/autotune/switch_autotune.cc + cache.h): the
first call at a new (kernel, shape-class, device-generation) measures a
small candidate set of {block_q, block_k} pairs on the live chip and
caches the winner — in-process AND on disk, so another TPU generation
does not inherit v5e hand-tuning and later processes skip the search
entirely.

Design notes:
  - The hand-tuned defaults are ALWAYS in the candidate set, so a tuned
    config can only tie or beat them (up to measurement noise).
  - Candidates are timed round-robin over two rounds with a min-reduce,
    so a slow moment on the host costs one sample, not a candidate.
  - The kernels call tune() while an enclosing jit is tracing them.
    JAX's tracing state belongs to a thread, so the measurement runs
    on a thread of its own: there its arrays are real and its kernels
    compile and execute, instead of being staged into the caller's
    trace (where every candidate fails on the first value it reads).
  - The cache key is the full shape class (kind, sq, sk, H, Hk, D,
    causal, segmented) + device kind; values survive in
    $PADDLE_TPU_CACHE_DIR (default ~/.cache/paddle_tpu).
  - PADDLE_TPU_PALLAS_AUTOTUNE=0 disables the search (defaults used);
    a cache HIT costs one dict lookup.
  - BANDWIDTH-WINDOW VALIDATION: `tune(..., bw_window=(lo, hi))` probes
    effective copy bandwidth before and after the candidate rounds;
    unless both probes land inside the window, the sweep result is
    DISCARDED (defaults returned, nothing persisted) so a later process
    retries. No caller passes a window today: there is no measured
    window for the chip this runs on.
    Every sweep — validated or not — is recorded in the in-process
    sweep log with its candidate timings, failures and wall time.
"""
from __future__ import annotations

import concurrent.futures
import json
import os
import threading
import time

_MEM: dict = {}
_LOCK = threading.Lock()
_LOADED_FILES: set = set()
_TUNING = threading.local()     # reentrancy guard of a sweep's thread
_SWEEPS: list = []              # sweep records since the last drain


def enabled() -> bool:
    return os.environ.get("PADDLE_TPU_PALLAS_AUTOTUNE", "1") != "0"


def _device_kind() -> str:
    import jax
    try:
        return getattr(jax.devices()[0], "device_kind",
                       jax.default_backend()).replace(" ", "_")
    except Exception:
        return "unknown"


def _cache_path(kind: str) -> str:
    d = os.path.expanduser(os.environ.get("PADDLE_TPU_CACHE_DIR",
                                          "~/.cache/paddle_tpu"))
    os.makedirs(d, exist_ok=True)
    return os.path.join(d, f"pallas_tune_{kind}.json")


def _load_disk(dev: str) -> None:
    path = _cache_path(dev)
    if path in _LOADED_FILES:
        return
    _LOADED_FILES.add(path)
    try:
        with open(path) as f:
            for k, v in json.load(f).items():
                _MEM.setdefault(k, tuple(v))
    except (OSError, json.JSONDecodeError):
        pass


def _save_disk(dev: str) -> None:
    path = _cache_path(dev)
    try:
        import fcntl
        # cross-PROCESS exclusive section around the read-merge-write:
        # without it two concurrently-tuning jobs interleave and the
        # last writer silently drops the other's fresh entries
        with open(path + ".lock", "w") as lockf:
            fcntl.flock(lockf, fcntl.LOCK_EX)
            on_disk = {}
            try:
                with open(path) as f:
                    on_disk = json.load(f)
            except (OSError, json.JSONDecodeError):
                pass
            on_disk.update({k: list(v) for k, v in _MEM.items()})
            tmp = path + f".tmp{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(on_disk, f, indent=0, sort_keys=True)
            os.replace(tmp, path)
    except OSError:
        pass


def lookup(key_parts) -> tuple | None:
    dev = _device_kind()
    key = "|".join(str(p) for p in key_parts) + "|" + dev
    with _LOCK:
        _load_disk(dev)
        hit = _MEM.get(key)
    return tuple(hit) if hit else None


def dedup_candidates(cands, normalize, keep_original=False):
    """Divisibility-normalized candidate dedup (grown by the ragged
    autotuner in PR 7, now shared with the flash kernels): candidates
    that collapse to one effective block config after the use site's
    fit/pick clamps are measured once. `normalize(*c)` maps a raw
    candidate to its effective config; returns the deduped list of
    effective configs (or, with keep_original=True, the first raw
    candidate per effective class — for use sites whose runner wants
    the raw values)."""
    seen, keep = set(), []
    for c in cands:
        e = normalize(*c)
        if e not in seen:
            seen.add(e)
            keep.append(tuple(c) if keep_original else tuple(e))
    return keep


def measure_effective_bw(nbytes=1 << 26, iters=4):
    """Effective device copy bandwidth (bytes/s) RIGHT NOW: one jitted
    elementwise pass over `nbytes` (read + write = 2x), blocked on.
    The probe the bandwidth-window validation compares against its
    window; returns None when measurement fails (missing backend,
    transient error) — callers treat that as 'cannot validate'."""
    import jax
    import jax.numpy as jnp
    try:
        x = jnp.zeros((nbytes // 4,), jnp.float32)
        f = jax.jit(lambda a: a + 1.0)
        f(x).block_until_ready()        # compile + settle
        t0 = time.perf_counter()
        out = None
        for _ in range(iters):
            out = f(x)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / iters
        if dt <= 0:
            return None
        return (2.0 * nbytes) / dt
    except Exception:
        return None


def _off_trace(fn):
    """fn() on a thread of its own; its result, or its exception."""
    with concurrent.futures.ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="pallas-autotune") as pool:
        return pool.submit(fn).result()


def drain_sweeps() -> list:
    """Return and clear the sweep records accumulated since the last
    drain (`benchmarks/harness/runlib.py` counts them into a run's
    set-up; chip_smoke.py prints their count and total seconds)."""
    out = list(_SWEEPS)
    _SWEEPS.clear()
    return out


def tune(key_parts, candidates, run_candidate, rounds=2, bw_window=None):
    """Measure `candidates` with run_candidate(c) -> seconds; memoize
    and persist the fastest. Returns the winning candidate. Reentrant
    calls (the measurement itself dispatches the kernel) fall through
    to the first candidate.

    bw_window=(lo, hi) bytes/s: validate the measurement window — the
    effective copy bandwidth is probed before and after the candidate
    rounds, and unless BOTH probes land inside the window the sweep is
    discarded (defaults returned, nothing persisted) so a degraded
    window cannot freeze a noise winner into the cache. The sweep
    record (candidate timings, probes, verdict) is logged either way
    for `drain_sweeps()`."""
    if getattr(_TUNING, "active", False):
        return candidates[0]
    hit = lookup(key_parts)
    if hit is not None:
        return hit
    t_start = time.perf_counter()
    dev = _device_kind()
    key = "|".join(str(p) for p in key_parts) + "|" + dev
    probes = []
    best = {c: float("inf") for c in candidates}
    errors = {}

    def in_window():
        bw = measure_effective_bw()
        probes.append(bw)
        return bw is not None and bw_window[0] <= bw <= bw_window[1]

    def measure():
        """The sweep; whether its window validated (True without one)."""
        # the kernel under measurement calls the tuned entry point:
        # on this (the sweep's own) thread that must not search again
        _TUNING.active = True
        # a transient dip should not kill the sweep: three tries
        if bw_window is not None and not any(
                in_window() for _ in range(3)):
            return False
        for _ in range(rounds):
            for c in candidates:
                try:
                    t = run_candidate(c)
                except Exception as e:       # a candidate may not fit
                    t = float("inf")
                    errors[c] = f"{type(e).__name__}: {e}"[:300]
                if t < best[c]:
                    best[c] = t
        return bw_window is None or in_window()

    window_ok = _off_trace(measure)
    winner = min(candidates, key=lambda c: best[c])
    measured = best[winner] != float("inf")
    # every measurement failed (chip busy / transient error) or the
    # window never validated: fall back WITHOUT persisting, so the next
    # process retries instead of freezing a glitch into "tuned" state
    persisted = window_ok and measured
    _SWEEPS.append({
        "key": list(key_parts), "device": dev,
        "candidates": {str(tuple(c)): (None if best[c] == float("inf")
                                       else round(best[c], 6))
                       for c in candidates},
        "winner": list(winner) if persisted else list(candidates[0]),
        "bw_probes_bytes_per_s": [None if p is None else round(p, 1)
                                  for p in probes],
        "bw_window": list(bw_window) if bw_window is not None else None,
        "window_validated": window_ok if bw_window is not None else None,
        "persisted": persisted,
        "rounds": rounds,
        "errors": {str(tuple(c)): e for c, e in errors.items()},
        "seconds": round(time.perf_counter() - t_start, 3),
    })
    if not persisted:
        return tuple(candidates[0])
    with _LOCK:
        _MEM[key] = tuple(winner)
        _save_disk(dev)
    return tuple(winner)


def clear() -> None:
    with _LOCK:
        _MEM.clear()
        _LOADED_FILES.clear()


def _time_call(fn, iters=20) -> float:
    """fn() -> one jax array; returns mean seconds per call. Syncs by
    fetching a single element (a full transfer would be timed with the
    kernel). iters is high because compile time dominates tuning cost
    anyway and the differences being ranked are a few percent."""
    import numpy as np

    def _sync(out):
        np.asarray(out[(0,) * out.ndim])

    _sync(fn())     # compile + settle
    t0 = time.perf_counter()
    out = None
    for _ in range(iters):
        out = fn()
    _sync(out)
    return (time.perf_counter() - t0) / iters

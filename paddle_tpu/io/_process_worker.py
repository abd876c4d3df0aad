"""Multiprocess DataLoader worker (ref: python/paddle/io/reader.py:216 —
the reference's default workers are PROCESSES because Python transforms
hold the GIL; thread workers serialize behind transform-heavy
pipelines).

Design: spawned processes (never fork — the parent owns a live TPU
client; fork would duplicate its state) + SharedMemory array transport.
Workers are compute-only and must never open the parent's chip: the
DataLoader spawns them with JAX_PLATFORMS=cpu in their environment
(`utils.runtime_env.cpu_only_child_env`), so it holds before
`spawn_main` imports or unpickles anything. The dataset/collate/init
objects cross the spawn boundary as one pickle BYTES blob, made once
per epoch and shared by every worker and respawn. The default collate
produces NUMPY batches —
Tensors are materialised by the parent. Large arrays travel via
multiprocessing.shared_memory (one copy into the segment, one copy out
in the parent — no pickle of the payload bytes); small leaves ride the
queue pickle.

Self-healing contract (resilience layer): a worker that dies without
reporting (OOM kill, segfault, chaos `io.worker.batch` fault) is
detected by the parent's queue-wait loop and respawned with
`resume_from` pointing at the first batch the parent still needs; on
every SOFT exit path — orderly stop, early consumer exit, error —
SharedMemory payloads that never reached the parent are unlinked
(worker-side for unplaced ones, parent-side `discard()` after join for
in-flight ones), so /dev/shm does not leak. A HARD kill landing
strictly between segment creation in `_pack` and the payload reaching
the parent's queue loses that batch's payload with the dead worker
(its segments are deliberately unregistered from the resource tracker
so ownership can pass to the consumer) — but not their names: every
segment is named from the epoch's stem, and the parent ends the epoch
with `unlink_stem`, which removes whatever of that stem is left where
/dev/shm can be listed."""
from __future__ import annotations

import os
import traceback

import numpy as np

# arrays below this ride the regular queue pickle (a SharedMemory
# segment costs two syscalls + mmap; not worth it for scalars)
_SHM_THRESHOLD = 1 << 16

# set inside a spawned worker process (io.get_worker_info reads it)
_WORKER_INFO = None


def np_collate(batch):
    """Default collate producing numpy leaves (worker-side twin of
    io.default_collate_fn — the parent wraps leaves into Tensors)."""
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        return np.stack(batch)
    if hasattr(sample, "numpy") and hasattr(sample, "_data"):
        # framework Tensor samples (duck-typed: this module must stay
        # importable without paddle_tpu/jax) -> stacked numpy; the
        # parent re-wraps into one batched Tensor like the thread tier
        return np.stack([np.asarray(s.numpy()) for s in batch])
    if isinstance(sample, (int, float, np.number)):
        return np.asarray(batch)
    if isinstance(sample, (list, tuple)):
        return [np_collate(list(s)) for s in zip(*batch)]
    if isinstance(sample, dict):
        return {k: np_collate([d[k] for d in batch]) for k in sample}
    return batch


def _pack(obj, segments, stem):
    """Replace large ndarray leaves with shared-memory markers. Segment
    k of the payload is named `<stem>n<k>`: the stem starts with the
    loader's own prefix (`DataLoader._shm_prefix`), so what a loader
    left in /dev/shm can be told from everybody else's."""
    if isinstance(obj, np.ndarray) and obj.nbytes >= _SHM_THRESHOLD:
        from multiprocessing import shared_memory
        seg = shared_memory.SharedMemory(
            name=f"{stem}n{len(segments)}", create=True, size=obj.nbytes)
        # ownership passes to the CONSUMER: unregister from this
        # process's resource tracker, or the tracker would unlink the
        # segment when this (short-lived) worker exits — before the
        # parent has copied it out (the classic shared_memory pitfall)
        try:
            from multiprocessing import resource_tracker
            resource_tracker.unregister(seg._name, "shared_memory")
        except Exception:
            pass
        np.ndarray(obj.shape, obj.dtype, buffer=seg.buf)[...] = obj
        segments.append(seg)
        return ("__shm__", seg.name, str(obj.dtype), obj.shape)
    if isinstance(obj, list):
        return ["__list__"] + [_pack(x, segments, stem) for x in obj]
    if isinstance(obj, tuple):
        return ("__tuple__",) + tuple(
            _pack(x, segments, stem) for x in obj)
    if isinstance(obj, dict):
        return {k: _pack(v, segments, stem) for k, v in obj.items()}
    return obj


def _strip_ndarrays(obj):
    """Replace ndarray leaves with None — what's left is what a batch
    payload would pickle onto the queue (ndarrays either ride a
    SharedMemory segment or pickle trivially). Used by the parent's
    collate-output picklability probe."""
    if isinstance(obj, np.ndarray):
        return None
    if isinstance(obj, list):
        return [_strip_ndarrays(x) for x in obj]
    if isinstance(obj, tuple):
        return tuple(_strip_ndarrays(x) for x in obj)
    if isinstance(obj, dict):
        return {k: _strip_ndarrays(v) for k, v in obj.items()}
    return obj


def shm_payload_bytes(obj) -> int:
    """Total SharedMemory bytes a packed payload references (from the
    markers alone — no segment is attached). The parent's shm-traffic
    metrics read this at receipt time."""
    if isinstance(obj, tuple) and obj[:1] == ("__shm__",):
        _, _, dtype, shape = obj
        n = np.dtype(dtype).itemsize
        for d in shape:
            n *= d
        return n
    if isinstance(obj, list):
        return sum(shm_payload_bytes(x) for x in obj)
    if isinstance(obj, tuple):
        return sum(shm_payload_bytes(x) for x in obj)
    if isinstance(obj, dict):
        return sum(shm_payload_bytes(v) for v in obj.values())
    return 0


def discard(obj):
    """Unlink every SharedMemory segment a packed payload references
    WITHOUT copying it out — the parent's cleanup path for batches
    nobody will consume."""
    from multiprocessing import shared_memory
    if isinstance(obj, tuple) and obj[:1] == ("__shm__",):
        try:
            seg = shared_memory.SharedMemory(name=obj[1])
        except FileNotFoundError:
            return
        seg.close()
        try:
            seg.unlink()
        except FileNotFoundError:
            pass
    elif isinstance(obj, list):
        for x in obj:
            discard(x)
    elif isinstance(obj, tuple):
        for x in obj:
            discard(x)
    elif isinstance(obj, dict):
        for v in obj.values():
            discard(v)


def unlink_stem(stem):
    """Unlink every segment whose name starts with `stem` — the
    parent's last step of an epoch, after its workers are gone and its
    queues drained: what is left then is what a hard-killed worker
    created and never delivered. Where /dev/shm is not a directory
    (not Linux) nothing can be listed and nothing is done."""
    try:
        names = os.listdir("/dev/shm")
    except OSError:
        return
    for name in names:
        if name.startswith(stem):
            discard(("__shm__", name))


def unpack(obj):
    """Parent-side inverse of _pack: attach, copy out, release."""
    from multiprocessing import shared_memory
    if isinstance(obj, tuple) and obj[:1] == ("__shm__",):
        _, name, dtype, shape = obj
        seg = shared_memory.SharedMemory(name=name)
        try:
            arr = np.array(
                np.ndarray(shape, np.dtype(dtype), buffer=seg.buf))
        finally:
            seg.close()
            try:
                seg.unlink()
            except FileNotFoundError:
                pass
        return arr
    if isinstance(obj, list) and obj[:1] == ["__list__"]:
        return [unpack(x) for x in obj[1:]]
    if isinstance(obj, tuple) and obj[:1] == ("__tuple__",):
        return tuple(unpack(x) for x in obj[1:])
    if isinstance(obj, dict):
        return {k: unpack(v) for k, v in obj.items()}
    return obj


def worker_main(wid, num_workers, payload_bytes, idx_batches, out_queue,
                stop_event, shm_stem, resume_from=0, fault_specs=None,
                attempt=0, obs_enabled=False):
    """Entry point of a spawned worker process. Round-robin ownership:
    worker w produces batches w, w+W, w+2W, ... in order into its own
    bounded queue (deterministic reassembly, per-worker backpressure —
    same protocol as the in-process thread tier).

    payload_bytes: pickle of (dataset, collate_fn_or_None,
    worker_init_fn_or_None).
    shm_stem: this epoch's prefix of the loader's segment names; a
    batch's segments are `<shm_stem>w<wid>a<attempt>b<bi>n<k>`, so a
    respawn never meets a name its dead predecessor left.
    resume_from: first batch index the parent still needs; a worker
    respawned to replace a dead one skips its stripe's earlier batches.
    fault_specs: a faults.snapshot() from the parent, re-armed in this
    process so `io.*` fault points work across the spawn boundary.
    attempt: this worker slot's incarnation number (0 = original spawn)
    — exposed in the fault context so a chaos kill can target only the
    first life (match={"bi": 2, "attempt": 0}) and let the respawn
    survive.
    obs_enabled: the parent's (metrics_on, tracing_on) observability
    flags at spawn time (a bare bool means metrics only) — when set,
    this worker records its own produce-latency/batch metrics and
    per-batch trace events and ships {"metrics": snapshot, "trace":
    events} back with its "done" farewell; the parent merges both
    (worker observability survives the spawn boundary the same way
    fault specs cross it). A worker killed before its farewell loses
    its (partial) series — its replacement recounts the recomputed
    batches."""
    import pickle
    import queue as _q
    import time as _time
    try:
        dataset, collate_fn, worker_init_fn = pickle.loads(payload_bytes)
        from ..resilience import faults
        faults.install(fault_specs)
        metrics_on, tracing_on = (
            obs_enabled if isinstance(obs_enabled, tuple)
            else (obs_enabled, False))
        wm = wt = None
        if metrics_on or tracing_on:
            from ..observability import metrics as _om
            from ..observability import tracing as _otr
            if tracing_on:
                _otr.enable()
                wt = _otr
            if metrics_on:
                _om.enable()
                r = _om.registry()
                wm = {
                    "produce": r.histogram(
                        "paddle_tpu_dataloader_worker_batch_seconds",
                        "worker-side dataset load + collate + shm pack "
                        "time per batch"),
                    "batches": r.counter(
                        "paddle_tpu_dataloader_worker_batches_total",
                        "batches produced by spawned DataLoader "
                        "workers"),
                }
        global _WORKER_INFO
        import types
        _WORKER_INFO = types.SimpleNamespace(
            id=wid, num_workers=num_workers, dataset=dataset)
        if worker_init_fn is not None:
            worker_init_fn(wid)
        collate = collate_fn if collate_fn is not None else np_collate
        for bi in range(wid, len(idx_batches), num_workers):
            if bi < resume_from:
                continue        # the parent already consumed this one
            if stop_event.is_set():
                return
            faults.fault_point("io.worker.batch", wid=wid, bi=bi,
                               attempt=attempt)
            t_produce = _time.perf_counter() if (wm or wt) else 0.0
            samples = [dataset[i] for i in idx_batches[bi]]
            batch = collate(samples)
            segments = []
            try:
                payload = _pack(batch, segments,
                                f"{shm_stem}w{wid}a{attempt}b{bi}")
            except BaseException:
                # mid-pack failure (e.g. ENOSPC on /dev/shm): the
                # segments created so far are unregistered from the
                # tracker, so WE must unlink them or they outlive us
                for seg in segments:
                    seg.close()
                    try:
                        seg.unlink()
                    except FileNotFoundError:
                        pass
                raise
            if wm:
                wm["produce"].observe(_time.perf_counter() - t_produce)
                wm["batches"].inc()
            if wt:
                # trace event per produced batch, recorded IN this
                # process (its pid); ships with the farewell
                t_done = _time.perf_counter()
                wt.add_event("io.worker.batch", t_produce * 1e6,
                             (t_done - t_produce) * 1e6,
                             args={"wid": wid, "bi": bi,
                                   "attempt": attempt})
            placed = False
            while not stop_event.is_set():
                try:
                    out_queue.put(("batch", bi, payload), timeout=0.2)
                    placed = True
                    break
                except _q.Full:
                    continue
            for seg in segments:
                seg.close()
            if not placed:      # consumer went away: free the payload
                discard(payload)
                return
        # farewell carries this worker's observability as a fleet
        # bundle (None when observability is off) — the SAME wire
        # format and merge path the standing fleet obs agent uses
        # (observability.fleet), just one-shot. Stop-aware like the
        # batch puts — an unbounded put would block against a full
        # queue after early consumer exit and stall the parent's
        # join-then-drain teardown — but always attempt at least ONCE:
        # the parent sets stop the instant it consumes the last batch,
        # and that common race must not drop the farewell (the
        # parent's post-join drain merges it)
        snap = None
        if wm is not None or wt is not None:
            from ..observability import fleet as _ofleet
            _ofleet.set_identity(process=f"io-worker-{wid}",
                                 role="io-worker")
            snap = _ofleet.worker_farewell(metrics=wm is not None,
                                           trace=wt is not None)
        while True:
            try:
                out_queue.put(("done", wid, snap), timeout=0.2)
                break
            except _q.Full:
                if stop_event.is_set():
                    break
    except BaseException:
        try:
            out_queue.put(("error", wid, traceback.format_exc()),
                          timeout=1.0)
        except Exception:
            pass

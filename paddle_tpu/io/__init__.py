"""Data loading (ref: python/paddle/io/reader.py:216 DataLoader,
io/dataloader/batch_sampler.py).

Single-thread mode uses a background prefetch thread (host->TPU
transfer overlaps compute). num_workers > 0 feeds batches through the
NATIVE C++ blocking queue (io/native/queue.cc — the analog of the
reader BlockingQueue under the reference's DataLoader workers) with
ordered reassembly, and large-sample collation runs through its
parallel memcpy."""
from __future__ import annotations

import os
import queue
import secrets
import threading
import time
from typing import Iterable, List, Optional

import numpy as np

from ..core.tensor import Tensor
from ..core.generator import default_generator
from ..observability import metrics as _om
from ..observability import tracing as _ot

# process-global DataLoader metrics (handles cached: the disabled path
# through any of them is one module-flag check inside inc/observe)
_IO_METRICS = None


def _io_metrics():
    global _IO_METRICS
    if _IO_METRICS is None:
        r = _om.registry()
        _IO_METRICS = {
            "wait": r.histogram(
                "paddle_tpu_dataloader_batch_wait_seconds",
                "consumer-side wait for the next batch (all tiers)"),
            "restarts": r.counter(
                "paddle_tpu_dataloader_worker_restarts_total",
                "spawned workers respawned after dying without "
                "reporting (OOM kill, segfault)"),
            "shm_bytes": r.counter(
                "paddle_tpu_dataloader_shm_bytes_total",
                "bytes transported worker->parent via SharedMemory "
                "segments"),
            "shm_inflight": r.gauge(
                "paddle_tpu_dataloader_shm_bytes_in_flight",
                "SharedMemory payload bytes received but not yet "
                "copied out of /dev/shm"),
        }
    return _IO_METRICS


def _merge_farewell(payload) -> None:
    """Fold a spawned worker's farewell observability payload into the
    parent: metric snapshot merges additively, worker-side trace
    events append to the parent ring verbatim (their pid distinguishes
    them in exports; perf_counter is CLOCK_MONOTONIC on Linux, so the
    timestamps interleave correctly). The payload is a fleet bundle
    (observability.fleet) — the worker farewell and the standing fleet
    obs agent share one wire format and one merge path."""
    from ..observability import fleet as _ofleet
    _ofleet.merge_bundle_local(payload)


class Dataset:
    def __getitem__(self, idx):
        raise NotImplementedError

    def __len__(self):
        raise NotImplementedError


class IterableDataset(Dataset):
    def __iter__(self):
        raise NotImplementedError


class TensorDataset(Dataset):
    def __init__(self, tensors):
        self.tensors = tensors

    def __getitem__(self, idx):
        return tuple(t[idx] for t in self.tensors)

    def __len__(self):
        return self.tensors[0].shape[0]


class ComposeDataset(Dataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __len__(self):
        return min(len(d) for d in self.datasets)

    def __getitem__(self, idx):
        out = []
        for d in self.datasets:
            item = d[idx]
            out.extend(item if isinstance(item, (tuple, list)) else [item])
        return tuple(out)


class ChainDataset(IterableDataset):
    def __init__(self, datasets):
        self.datasets = datasets

    def __iter__(self):
        for d in self.datasets:
            yield from d


class Subset(Dataset):
    def __init__(self, dataset, indices):
        self.dataset = dataset
        self.indices = indices

    def __getitem__(self, idx):
        return self.dataset[self.indices[idx]]

    def __len__(self):
        return len(self.indices)


def random_split(dataset, lengths, generator=None):
    total = len(dataset)
    if all(isinstance(l, float) for l in lengths):
        lengths = [int(l * total) for l in lengths]
        lengths[-1] = total - sum(lengths[:-1])
    perm = np.random.RandomState(
        default_generator().seed() or None).permutation(total)
    out, off = [], 0
    for l in lengths:
        out.append(Subset(dataset, perm[off:off + l].tolist()))
        off += l
    return out


class Sampler:
    def __init__(self, data_source=None):
        self.data_source = data_source

    def __iter__(self):
        raise NotImplementedError


class SequenceSampler(Sampler):
    def __iter__(self):
        return iter(range(len(self.data_source)))

    def __len__(self):
        return len(self.data_source)


class RandomSampler(Sampler):
    def __init__(self, data_source, replacement=False, num_samples=None,
                 generator=None):
        super().__init__(data_source)
        self.replacement = replacement
        self.num_samples = num_samples or len(data_source)

    def __iter__(self):
        n = len(self.data_source)
        rng = np.random.RandomState()
        if self.replacement:
            return iter(rng.randint(0, n, self.num_samples).tolist())
        return iter(rng.permutation(n)[:self.num_samples].tolist())

    def __len__(self):
        return self.num_samples


class WeightedRandomSampler(Sampler):
    def __init__(self, weights, num_samples, replacement=True):
        self.weights = np.asarray(weights, np.float64)
        self.num_samples = num_samples
        self.replacement = replacement

    def __iter__(self):
        p = self.weights / self.weights.sum()
        idx = np.random.choice(len(self.weights), self.num_samples,
                               replace=self.replacement, p=p)
        return iter(idx.tolist())

    def __len__(self):
        return self.num_samples


class BatchSampler(Sampler):
    def __init__(self, dataset=None, sampler=None, shuffle=False,
                 batch_size=1, drop_last=False):
        self.batch_size = batch_size
        self.drop_last = drop_last
        if sampler is not None:
            self.sampler = sampler
        elif shuffle:
            self.sampler = RandomSampler(dataset)
        else:
            self.sampler = SequenceSampler(dataset)

    def __iter__(self):
        batch = []
        for idx in self.sampler:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        n = len(self.sampler)
        if self.drop_last:
            return n // self.batch_size
        return (n + self.batch_size - 1) // self.batch_size


class DistributedBatchSampler(BatchSampler):
    """(ref: io/dataloader/batch_sampler.py DistributedBatchSampler) —
    shards sample indices across data-parallel ranks."""

    def __init__(self, dataset, batch_size, num_replicas=None, rank=None,
                 shuffle=False, drop_last=False):
        self.dataset = dataset
        self.batch_size = batch_size
        self.shuffle = shuffle
        self.drop_last = drop_last
        if num_replicas is None or rank is None:
            from ..distributed import get_world_size, get_rank
            num_replicas = num_replicas or get_world_size()
            rank = rank if rank is not None else get_rank()
        self.nranks = num_replicas
        self.local_rank = rank
        self.epoch = 0
        self.num_samples = int(np.ceil(len(dataset) / num_replicas))
        self.total_size = self.num_samples * num_replicas

    def __iter__(self):
        n = len(self.dataset)
        if self.shuffle:
            rng = np.random.RandomState(self.epoch)
            indices = rng.permutation(n).tolist()
        else:
            indices = list(range(n))
        indices += indices[: self.total_size - len(indices)]
        indices = indices[self.local_rank:self.total_size:self.nranks]
        batch = []
        for idx in indices:
            batch.append(idx)
            if len(batch) == self.batch_size:
                yield batch
                batch = []
        if batch and not self.drop_last:
            yield batch

    def __len__(self):
        if self.drop_last:
            return self.num_samples // self.batch_size
        return (self.num_samples + self.batch_size - 1) // self.batch_size

    def set_epoch(self, epoch):
        self.epoch = epoch


_WORKER_ERROR = object()


def default_collate_fn(batch):
    sample = batch[0]
    if isinstance(sample, np.ndarray):
        from .native import collate_stack
        return Tensor(collate_stack(batch))  # falls back to np.stack
    if isinstance(sample, Tensor):
        import jax.numpy as jnp
        return Tensor(jnp.stack([s._data for s in batch]))
    if isinstance(sample, (int, float, np.number)):
        return Tensor(np.asarray(batch))
    if isinstance(sample, (list, tuple)):
        transposed = list(zip(*batch))
        return [default_collate_fn(list(s)) for s in transposed]
    if isinstance(sample, dict):
        return {k: default_collate_fn([d[k] for d in batch])
                for k in sample}
    return batch


class DataLoader:
    def __init__(self, dataset, feed_list=None, places=None,
                 return_list=True, batch_sampler=None, batch_size=1,
                 shuffle=False, drop_last=False, collate_fn=None,
                 num_workers=0, use_buffer_reader=True, prefetch_factor=2,
                 use_shared_memory=True, timeout=0, worker_init_fn=None,
                 persistent_workers=False, max_worker_restarts=2):
        self.dataset = dataset
        self.collate_fn = collate_fn or default_collate_fn
        self.num_workers = max(0, int(num_workers))
        self.prefetch_factor = max(prefetch_factor, 1)
        self.use_buffer_reader = use_buffer_reader
        self.use_shared_memory = use_shared_memory
        self.worker_init_fn = worker_init_fn
        self.timeout = timeout
        # self-healing: how many times EACH spawned worker may be
        # respawned after dying without reporting (OOM kill, segfault)
        self.max_worker_restarts = max(0, int(max_worker_restarts))
        # every SharedMemory segment this loader's workers create is
        # named with this prefix (and the epoch's number after it)
        self._shm_prefix = f"ptdl{os.getpid():x}x{secrets.token_hex(4)}"
        self._shm_epochs = 0
        self._iterable_mode = isinstance(dataset, IterableDataset)
        if self._iterable_mode:
            self.batch_sampler = None
            self.batch_size = batch_size
            self.drop_last = drop_last
        elif batch_sampler is not None:
            self.batch_sampler = batch_sampler
        else:
            self.batch_sampler = BatchSampler(
                dataset, shuffle=shuffle, batch_size=batch_size,
                drop_last=drop_last)

    def __len__(self):
        if self._iterable_mode:
            raise TypeError("IterableDataset has no len()")
        return len(self.batch_sampler)

    def _batches(self):
        if self._iterable_mode:
            batch = []
            for item in self.dataset:
                batch.append(item)
                if len(batch) == self.batch_size:
                    yield self.collate_fn(batch)
                    batch = []
            if batch and not self.drop_last:
                yield self.collate_fn(batch)
        else:
            for idx_batch in self.batch_sampler:
                samples = [self.dataset[i] for i in idx_batch]
                yield self.collate_fn(samples)

    def __iter__(self):
        if not self.use_buffer_reader:
            yield from self._batches()
            return
        if self.num_workers > 0 and not self._iterable_mode:
            # PROCESS workers by default (ref: reader.py:216 — python
            # transforms hold the GIL, so thread workers serialize);
            # unpicklable datasets/collates fall back to the in-process
            # thread tier with a warning
            if self.use_shared_memory and self._spawn_picklable():
                yield from self._iter_process_workers()
            else:
                yield from self._iter_workers()
            return
        yield from self._iter_buffered()

    def _spawn_picklable(self) -> bool:
        import pickle
        import warnings
        cached = self.__dict__.get("_spawn_picklable_result")
        if cached is not None:      # probe once, not per epoch: pickling
            return cached           # a large in-memory dataset is not free

        def fallback(detail):
            warnings.warn(
                f"DataLoader(num_workers={self.num_workers}): {detail} "
                "— falling back to in-process thread workers (GIL-bound "
                "for python transforms). Define the dataset and "
                "collate_fn at module level to enable process workers.",
                UserWarning, stacklevel=4)
            self._spawn_picklable_result = False
            return False

        custom = (None if self.collate_fn is default_collate_fn
                  else self.collate_fn)
        try:
            pickle.dumps((self.dataset, custom, self.worker_init_fn))
        except Exception as e:
            return fallback(
                "dataset/collate_fn is not picklable for spawned worker "
                f"processes ({type(e).__name__}: {e})")
        if custom is not None:
            # the collate OUTPUT must survive the queue pickle too.
            # Framework Tensors are fine since they gained a pickle
            # protocol (numpy roundtrip, Tensor.__reduce__): a worker-
            # side Tensor re-materialises through the parent's jax
            # runtime at unpickle time, so Tensor-returning collate_fns
            # keep the process tier.
            from . import _process_worker as PW
            sample_out = None
            try:
                # only draw the probe index from a sampler chain we
                # KNOW re-iterates (our own classes over their own
                # index sources) — anything user-supplied may be a
                # one-shot iterable whose first batch must not be
                # silently consumed by a probe
                # (WeightedRandomSampler is excluded: its __iter__
                # draws from the GLOBAL numpy RNG, so probing it would
                # silently shift seeded runs relative to num_workers=0)
                bs = self.batch_sampler
                reiterable = isinstance(
                    bs, DistributedBatchSampler) or (
                    isinstance(bs, BatchSampler) and isinstance(
                        getattr(bs, "sampler", None),
                        (SequenceSampler, RandomSampler)))
                first = next(iter(bs), None) if reiterable else None
                if first:
                    sample_out = custom([self.dataset[first[0]]])
            except Exception:
                pass    # dataset errors surface in the worker, with
                        # a real traceback — not the probe's business
            if sample_out is not None:
                try:
                    pickle.dumps(PW._strip_ndarrays(sample_out))
                except Exception as e:
                    return fallback(
                        "collate_fn output is not picklable for the "
                        "worker->parent queue "
                        f"({type(e).__name__}: {e})")
        self._spawn_picklable_result = True
        return True

    def _iter_process_workers(self):
        """num_workers > 0 process tier: spawned workers (never fork —
        the parent owns a live TPU client) load + collate into numpy,
        batches travel via SharedMemory segments, and the parent
        reassembles round-robin and materialises Tensors. One bounded
        queue per worker: deterministic order, per-worker backpressure,
        W * prefetch_factor batches of memory cap (same protocol as the
        thread tier).

        Self-healing: a worker that dies without reporting an error
        (OOM kill, segfault) is respawned — bounded exponential-backoff
        retries per worker — resuming at the first batch of its stripe
        the parent still needs; stale re-produced batches are discarded
        (their segments unlinked). On exit the parent joins workers
        FIRST and only then drains, so in-flight SharedMemory payloads
        are always unlinked — no /dev/shm leak on early consumer exit —
        and last unlinks by name what a killed worker never delivered."""
        import multiprocessing as mp
        import time as _time
        import warnings
        from . import _process_worker as PW
        from ..resilience import faults
        from ..utils.runtime_env import cpu_only_child_env

        idx_batches = list(self.batch_sampler)
        if not idx_batches:
            return
        ctx = mp.get_context("spawn")
        W = min(self.num_workers, len(idx_batches))
        queues = [ctx.Queue(maxsize=self.prefetch_factor)
                  for _ in range(W)]
        stop = ctx.Event()
        custom = (None if self.collate_fn is default_collate_fn
                  else self.collate_fn)
        # re-pickled EVERY epoch (only the picklability verdict is
        # cached): a dataset mutated between epochs (curriculum state,
        # swapped transform) must reach the workers, exactly as it does
        # in the num_workers=0 and thread tiers. One dumps() per epoch,
        # shared by all workers and respawns.
        import pickle
        payload_bytes = pickle.dumps(
            (self.dataset, custom, self.worker_init_fn))
        # io.* faults cross the spawn boundary via snapshot/install
        specs = faults.snapshot()

        # workers are compute-only and must never open the parent's
        # chip: they are spawned with JAX_PLATFORMS=cpu in their
        # environment, which holds from the interpreter's first
        # instruction (before spawn_main unpickles anything).
        # workers inherit the parent's observability flags at spawn
        # time and ship their metric snapshots + trace events back
        # with the "done" farewell
        obs_on = (_om._ENABLED, _ot._ENABLED)
        self._shm_epochs += 1
        shm_stem = f"{self._shm_prefix}e{self._shm_epochs}"

        def spawn(w, resume_from=0, attempt=0):
            p = ctx.Process(
                target=PW.worker_main,
                args=(w, W, payload_bytes, idx_batches, queues[w], stop,
                      shm_stem, resume_from, specs, attempt, obs_on),
                daemon=True)
            with cpu_only_child_env():
                p.start()
            return p

        procs = [spawn(w) for w in range(W)]
        restarts = [0] * W

        import queue as _q

        def wrap(obj):
            if isinstance(obj, np.ndarray):
                return Tensor(obj)
            if isinstance(obj, list):
                return [wrap(x) for x in obj]
            if isinstance(obj, tuple):
                return tuple(wrap(x) for x in obj)
            if isinstance(obj, dict):
                return {k: wrap(v) for k, v in obj.items()}
            return obj

        deadline = (None if not self.timeout
                    else self.timeout)
        try:
            for bi in range(len(idx_batches)):
                w = bi % W
                q = queues[w]
                waited = 0.0
                obs = _om._ENABLED
                t_wait = time.perf_counter() if obs else 0.0
                while True:
                    try:
                        kind, tag, payload = q.get(timeout=0.5)
                    except _q.Empty:
                        waited += 0.5
                        if not procs[w].is_alive():
                            if restarts[w] >= self.max_worker_restarts:
                                raise RuntimeError(
                                    f"DataLoader worker {w} died "
                                    "without reporting an error (OOM-"
                                    f"killed?) and exhausted its "
                                    f"{self.max_worker_restarts} "
                                    "restarts") from None
                            restarts[w] += 1
                            _io_metrics()["restarts"].inc()
                            backoff = min(
                                0.05 * (1 << (restarts[w] - 1)), 2.0)
                            warnings.warn(
                                f"DataLoader worker {w} died without "
                                f"reporting an error — respawning at "
                                f"batch {bi} (restart {restarts[w]}/"
                                f"{self.max_worker_restarts})",
                                UserWarning)
                            _time.sleep(backoff)
                            # a hard kill can land mid-pipe-write,
                            # leaving the queue's SHARED write-lock
                            # held by the corpse — any successor
                            # putting into the same queue would block
                            # forever. Drain what did arrive, then
                            # hand the replacement a fresh queue.
                            while True:
                                try:
                                    kind, _, payload = q.get_nowait()
                                except Exception:
                                    break
                                if kind == "batch":
                                    PW.discard(payload)
                            queues[w] = ctx.Queue(
                                maxsize=self.prefetch_factor)
                            q = queues[w]
                            procs[w] = spawn(w, resume_from=bi,
                                             attempt=restarts[w])
                            # re-arm the batch deadline: the respawned
                            # worker re-loads the batch from scratch,
                            # and that recompute must not be billed
                            # against the previous incarnation's clock
                            waited = 0.0
                        if deadline and waited >= deadline:
                            raise TimeoutError(
                                f"DataLoader worker {w} produced "
                                f"no batch within timeout={deadline}s")
                        continue
                    if kind == "error":
                        raise RuntimeError(
                            f"DataLoader worker {tag} failed:\n{payload}")
                    if kind == "done":
                        # finished worker's farewell (its successor may
                        # still owe batches): merge its metrics + trace
                        _merge_farewell(payload)
                        continue
                    assert kind == "batch", (kind, tag, bi)
                    if tag < bi:    # stale duplicate after a restart
                        PW.discard(payload)
                        continue
                    assert tag == bi, (tag, bi)
                    break
                shm_bytes = 0
                if obs:
                    iom = _io_metrics()
                    iom["wait"].observe(time.perf_counter() - t_wait)
                    shm_bytes = PW.shm_payload_bytes(payload)
                    if shm_bytes:
                        iom["shm_bytes"].inc(shm_bytes)
                        iom["shm_inflight"].inc(shm_bytes)
                batch = PW.unpack(payload)
                if shm_bytes:
                    _io_metrics()["shm_inflight"].dec(shm_bytes)
                yield batch if custom is not None else wrap(batch)
        finally:
            stop.set()
            # join FIRST: workers observe stop within ~0.2s, self-unlink
            # unplaced payloads, and flush their queue feeders on exit —
            # after the join no new batch can arrive behind the drain
            # (the single get_nowait sweep here used to race exactly
            # that, leaking /dev/shm segments on early consumer exit)
            for p in procs:
                p.join(timeout=5.0)
            for p in procs:
                if p.is_alive():
                    p.terminate()
                    p.join(timeout=1.0)
            for q in queues:
                while True:
                    try:
                        kind, _, payload = q.get_nowait()
                    except Exception:
                        break
                    if kind == "batch":
                        PW.discard(payload)
                    elif kind == "done":
                        # the common race: the worker's farewell (with
                        # its metrics + trace) lands after the parent
                        # consumed the last batch — merge it here
                        _merge_farewell(payload)
            # what a hard-killed worker created and never delivered
            PW.unlink_stem(shm_stem)

    def _iter_buffered(self):
        q: queue.Queue = queue.Queue(maxsize=self.prefetch_factor)
        sentinel = object()
        err = []

        def worker():
            try:
                for b in self._batches():
                    q.put(b)
            except BaseException as e:  # propagate to consumer
                err.append(e)
            finally:
                q.put(sentinel)

        t = threading.Thread(target=worker, daemon=True)
        t.start()
        while True:
            if _om._ENABLED:
                t0 = time.perf_counter()
                item = q.get()
                _io_metrics()["wait"].observe(time.perf_counter() - t0)
            else:
                item = q.get()
            if item is sentinel:
                if err:
                    raise err[0]
                break
            yield item

    def _iter_workers(self):
        """num_workers > 0: worker threads load+collate batches into
        NATIVE C++ blocking queues (ref: the reader BlockingQueue
        under paddle's DataLoader workers, operators/reader/
        blocking_queue.h). One bounded queue PER worker with
        round-robin consumption: batch i comes from queue i % W, so
        ordering is deterministic, memory stays capped at
        W * prefetch_factor batches, and a slow worker backpressures
        only itself (a shared queue would need an unbounded reorder
        buffer). Falls back to the single-thread buffered reader when
        the native library can't build."""
        from .native import NativeQueue, available
        if not available():
            yield from self._iter_buffered()
            return
        idx_batches = list(self.batch_sampler)
        W = self.num_workers
        queues = [NativeQueue(max(self.prefetch_factor, 1))
                  for _ in range(W)]
        stop = threading.Event()
        errs = []

        def worker(wid):
            nq = queues[wid]
            try:
                for bi in range(wid, len(idx_batches), W):
                    if stop.is_set():
                        return
                    samples = [self.dataset[i] for i in idx_batches[bi]]
                    while not stop.is_set():
                        if nq.push(self.collate_fn(samples),
                                   timeout_ms=200):
                            break
            except StopIteration:
                return  # consumer closed the queue: orderly shutdown
            except BaseException as e:
                if not stop.is_set():
                    errs.append(e)
                try:
                    nq.push(_WORKER_ERROR, timeout_ms=0)
                except Exception:
                    pass

        threads = [threading.Thread(target=worker, args=(w,),
                                    daemon=True)
                   for w in range(W)]
        for t in threads:
            t.start()
        try:
            for bi in range(len(idx_batches)):
                obs = _om._ENABLED
                t0 = time.perf_counter() if obs else 0.0
                while True:
                    if errs:
                        raise errs[0]
                    try:
                        batch = queues[bi % W].pop(timeout_ms=500)
                        break
                    except TimeoutError:
                        continue
                if obs:
                    _io_metrics()["wait"].observe(
                        time.perf_counter() - t0)
                if batch is _WORKER_ERROR:
                    raise errs[0] if errs else RuntimeError(
                        "dataloader worker failed")
                yield batch
        finally:
            stop.set()
            for nq in queues:
                nq.close()


def get_worker_info():
    """ref: io/dataloader/worker.py get_worker_info. Returns the worker
    context (id, num_workers, dataset) inside a spawned DataLoader
    worker process; None in the main process (and in the in-process
    thread/native tiers, matching the reference outside a worker)."""
    from . import _process_worker
    return _process_worker._WORKER_INFO
